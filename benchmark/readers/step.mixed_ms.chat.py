"""Mixed step (decode chunk + one prefill window), dispatch -> commit, median
over the window."""
from benchmark.readers import _spans


def read(ctx):
    return _spans.chunk_ms(ctx, mixed=True)
