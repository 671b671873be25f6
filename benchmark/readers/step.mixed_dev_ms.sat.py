"""Device time of the mixed step, decode chunk + one prefill window
(`jit_serve_unified_step`), median over the trace."""
from benchmark.readers import _phases


def read(ctx):
    return _phases.program_ms(ctx, "serve_unified_step")
