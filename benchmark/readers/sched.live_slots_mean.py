"""Mean number of live slots over the window's decode dispatches
(`decode.dispatch` span argument `live`)."""
from benchmark.readers import _spans


def read(ctx):
    live = [e["args"]["live"] for e in _spans.in_window(ctx, "decode.dispatch")]
    return sum(live) / len(live) if live else None
