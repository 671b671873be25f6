"""The grouped matmul's share of its roofline over the traced steps: the
least time the chip could take for the three kernels' products — the larger
of their FLOPs over the bf16 peak and their bytes over the HBM bandwidth,
from the buffer rows they really multiplied, padding included
(`moe.rows_multiplied`, the traced steps' mean, times the steps the trace
holds) — over the kernels' summed device time. Rows that are recomputed are
multiplied twice and take time twice: both sides count them."""
from benchmark import arith_glm4_moe_lite as arith
from benchmark.readers import _moe


def read(ctx):
    seconds = _moe.kernel_seconds(ctx)
    rows = _moe.mean(ctx, "moe.rows_multiplied", traced_only=True)
    steps = len(max(ctx["trace"]["programs"].values(), key=sum, default=[]))
    if seconds is None or rows is None or not steps:
        return None
    m = ctx["config"]
    _, expert, mtp = arith.blocks(m)
    launches = arith.PRODUCTS_PER_BLOCK * (expert + mtp) * steps
    floor = arith.grouped_matmul_floor_s(m, rows * steps, launches,
                                         ctx["peaks"])
    return 100.0 * floor / seconds
