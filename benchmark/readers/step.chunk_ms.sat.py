"""Pure-decode chunk, dispatch -> commit, median over the window."""
from benchmark.readers import _spans


def read(ctx):
    return _spans.chunk_ms(ctx, mixed=False)
