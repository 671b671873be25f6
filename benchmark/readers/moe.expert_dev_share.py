"""The three grouped-matmul kernels' device time over the device's busy
time in the trace."""
from benchmark.readers import _moe


def read(ctx):
    seconds = _moe.kernel_seconds(ctx)
    if seconds is None:
        return None
    return 100.0 * seconds / ctx["trace"]["busy_s"]
