"""The whole decode step's share of its read bound: the bytes the traced
decode steps must read — every weight multiplied (every held expert's blocks,
as the grouped matmul fetches them; the head in f32), the index keys the
`full` layers score and the latent rows every layer attends, a step's means
from the window's counters (`arith_hy_v4.decode_step_bytes`) — over 819 GB/s,
over the device's busy time in the trace. The prefill windows' work is left
out, which only lowers the share."""
from benchmark import arith_hy_v4 as arith
from benchmark.readers import _hy4


def read(ctx):
    lanes = _hy4.per_lane(ctx)
    if not lanes or "decode" not in lanes or not ctx["trace"]["busy_s"]:
        return None
    steps, _ = _hy4.traced(ctx)
    scored, attended = lanes["decode"]
    need = steps * arith.decode_step_bytes(ctx["config"], ctx["slots"],
                                           scored, attended)
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] \
        / ctx["trace"]["busy_s"]
