"""The sparse attention kernels' share of their floor: the time the traced
steps' selected latent rows need — the decode lane's as bytes (the rows at
1,152 B, queries in and outputs out, `arith_hy_v4.sparse_decode_bytes`) over
819 GB/s, the prefill windows' as operations (`sparse_attn_flops`) over the
peak — over the summed device time of `sparse_mla_decode` and
`sparse_mla_window`. The counts are the model's work (a row's selection),
not what a kernel sweeps, so the share cannot pass 100 whatever implements
it."""
from benchmark import arith_hy_v4 as arith
from benchmark.readers import _hy4


def read(ctx):
    lanes, secs = _hy4.per_lane(ctx), _hy4.label_seconds(ctx, _hy4.SPARSE)
    if not lanes or not secs:
        return None
    m, peaks = ctx["config"], ctx["peaks"]
    steps, windows = _hy4.traced(ctx)
    need = 0.0
    if "decode" in lanes:
        need += steps * arith.sparse_decode_bytes(
            m, ctx["slots"], lanes["decode"][1]) / peaks["hbm_bytes_per_s"]
    if "window" in lanes:
        need += windows * arith.sparse_attn_flops(
            m, lanes["window"][1]) / peaks["bf16_flops_per_s"]
    return 100.0 * need / secs
