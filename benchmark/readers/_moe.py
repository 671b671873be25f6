"""What the expert layer's readers share: the step reports the driver read
back (`raw["reports"]`, one dict of floats a step) and the grouped matmul's
device time in the trace."""

KERNELS = ("grouped_matmul", "grouped_matmul_dlhs", "grouped_matmul_drhs")


def reports(ctx, traced_only: bool = False):
    """The steps' reports; with `traced_only`, of the steps that finished
    while the profiler ran (all of them where none did)."""
    recs = ctx.get("reports") or []
    if traced_only and recs:
        lo, hi = ctx.get("traced") or (None, None)
        lo = float("-inf") if lo is None else lo
        hi = float("inf") if hi is None else hi
        recs = [r for r in recs if lo <= r["t"] <= hi] or recs
    return recs


def mean(ctx, key: str, traced_only: bool = False):
    recs = [r[key] for r in reports(ctx, traced_only) if key in r]
    return sum(recs) / len(recs) if recs else None


def kernel_seconds(ctx):
    """Summed device time of the three grouped-matmul kernels (a label is the
    kernel's name and its result's shape), or None where the trace has none:
    the CPU's trace, or a program without the kernels."""
    total = sum(s for label, s in ctx["trace"]["device_op_s"].items()
                if label.split()[0] in KERNELS)
    return total or None
