"""The indexer kernel's share of its floor: for the traced decode steps the
larger of the index keys' bytes (256 B a key a row: each row reads its own
pages) over 819 GB/s and the operations over the peak; for the traced prefill
windows the operations alone (the window's rows share each page they read);
over the device time of `dsa_index_scores`."""
from benchmark import arith_hy_v4 as arith
from benchmark.readers import _hy4


def read(ctx):
    lanes, secs = _hy4.per_lane(ctx), _hy4.label_seconds(ctx, _hy4.INDEX)
    if not lanes or not secs:
        return None
    m, peaks = ctx["config"], ctx["peaks"]
    steps, windows = _hy4.traced(ctx)
    need = 0.0
    if "decode" in lanes:
        scored = lanes["decode"][0]
        need += steps * max(
            scored * arith.index_key_bytes(m) / peaks["hbm_bytes_per_s"],
            arith.index_flops(m, scored) / peaks["bf16_flops_per_s"])
    if "window" in lanes:
        need += windows * arith.index_flops(m, lanes["window"][0]) \
            / peaks["bf16_flops_per_s"]
    return 100.0 * need / secs
