"""What the trainer's readers share: the device time of one step."""
from benchmark import arith


def step_ms(ctx):
    """Median device duration, in the profiler's trace, of the program that
    takes most of the device's time: the training step."""
    programs = ctx["trace"]["programs"]
    if not programs:
        return None
    return arith.median(max(programs.values(), key=sum)) * 1e3
