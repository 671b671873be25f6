"""The learned sparse attention's device time — the index scores, the
selection and the sparse attention kernels — over the device's busy time in
the trace. The selection's own operations carry no kernel label: the decode
lane's `top-k` (a label whose first word holds `top`/`sort`) is counted, the
prefill window's threshold search is not."""
from benchmark.readers import _hy4


def read(ctx):
    busy = ctx["trace"]["busy_s"]
    secs = _hy4.label_seconds(ctx, _hy4.INDEX + _hy4.SPARSE)
    if not secs or not busy:
        return None
    secs += sum(s for label, s in ctx["trace"]["device_op_s"].items()
                if label.split()[0].lower().startswith(("top", "sort")))
    return 100.0 * secs / busy
