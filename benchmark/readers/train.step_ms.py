"""Device time of one training step (median over the trace)."""
from benchmark.readers import _programs


def read(ctx):
    return _programs.step_ms(ctx)
