"""The grouped matmul's share of its roofline over the traced steps, by the
accepted `kernel.grouped_mm_roofline_share`'s rule over `arith_afmoe`: the
least time for the three kernels' products — the larger of their FLOPs over
the bf16 peak and their bytes over the HBM bandwidth, from the buffer rows
they really multiplied, padding included (`moe.rows_multiplied`, the traced
steps' mean, times the steps the trace holds) — over the kernels' summed
device time."""
from benchmark import arith_afmoe as arith
from benchmark.readers import _afmoe, _moe


def read(ctx):
    seconds = _moe.kernel_seconds(ctx)
    rows = _moe.mean(ctx, "moe.rows_multiplied", traced_only=True)
    steps = _afmoe.traced_steps(ctx)
    if seconds is None or rows is None or not steps:
        return None
    m = ctx["config"]
    launches = arith.PRODUCTS_PER_BLOCK * arith.blocks(m)[1] * steps
    floor = arith.grouped_matmul_floor_s(m, rows * steps, launches,
                                         ctx["peaks"])
    return 100.0 * floor / seconds
