"""The mixers' recurrence kernels (`ssd_decode`, `ssd_scan`) over the
device's busy time in the trace."""
from benchmark.readers import _falconh1


def read(ctx):
    busy = ctx["trace"]["busy_s"]
    secs = _falconh1.label_seconds(ctx, _falconh1.DECODE + _falconh1.SCAN)
    if not secs or not busy:
        return None
    return 100.0 * secs / busy
