"""Device time of one step of the `afmoe` trainer (median over the trace)."""
from benchmark.readers import _programs


def read(ctx):
    return _programs.step_ms(ctx)
