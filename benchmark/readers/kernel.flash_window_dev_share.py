"""The window kernels' device time (the labels that hold `flash` and
`window`) over the device's busy time in the trace."""
from benchmark.readers import _afmoe


def read(ctx):
    seconds = _afmoe.window_kernel_seconds(ctx)
    if seconds is None:
        return None
    return 100.0 * seconds / ctx["trace"]["busy_s"]
