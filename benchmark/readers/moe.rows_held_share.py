"""(token, expert) assignments this chip's held experts computed over all
the router made, over the steps read back: held / published experts where
routing is even."""
from benchmark.readers import _moe


def read(ctx):
    held, routed = (_moe.mean(ctx, k) for k in ("moe.rows_held",
                                                "moe.rows_routed"))
    if not routed:
        return None
    return 100.0 * held / routed
