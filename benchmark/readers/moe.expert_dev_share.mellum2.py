"""The expert layer's kernels' device time — the grouped matmul and the row
movements into and out of its buffer — over the device's busy time."""
from benchmark.readers import _mellum

KERNELS = ("grouped_matmul", "moe_rows_in", "moe_rows_out")


def read(ctx):
    secs = _mellum.label_seconds(ctx, KERNELS)
    return 100.0 * secs / ctx["trace"]["busy_s"] if secs else None
