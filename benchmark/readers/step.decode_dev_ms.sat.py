"""Device time of the pure decode chunk (`jit_serve_decode_chunk`), median
over the trace."""
from benchmark.readers import _phases


def read(ctx):
    return _phases.program_ms(ctx, "serve_decode_chunk")
