"""Model FLOPs of one step (matmuls and attention, forward + backward, nothing
recomputed, from shapes) over the device time of the step in the profiler's
trace, as a share of the chips' published bf16 peak. Device time and not the
host's timer: the traced run is slowed by the profiler itself, its steps are
not."""
from benchmark import arith
from benchmark.readers import _programs


def read(ctx):
    ms = _programs.step_ms(ctx)
    if ms is None:
        return None
    flops = arith.train_flops_per_token(ctx["config"], ctx["seq"]) \
        * ctx["batch"] * ctx["seq"]
    return 100.0 * flops / (ms * 1e-3) / (
        ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
