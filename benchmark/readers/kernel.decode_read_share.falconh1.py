"""The whole decode step's share of its read bound: the bytes the traced
decode steps must move — every weight multiplied, the head, the live tokens'
K and V (the traced steps' mean of cached tokens) and each live sequence's
state of every layer read and written (`arith_falcon_h1.decode_step_bytes`,
the live sequences from the counted slot-steps) — over 819 GB/s, over the
device's busy time in the trace. The prefill windows' work is left out, which
only lowers the share."""
from benchmark import arith_falcon_h1 as arith
from benchmark.readers import _falconh1


def read(ctx):
    rows = _falconh1.rows_per_step(ctx)
    g = ctx.get("gauges_traced")
    if rows is None or not g or not ctx["trace"]["busy_s"]:
        return None
    need = _falconh1.decode_steps(ctx) * arith.decode_step_bytes(
        ctx["config"], rows, g["kv_tokens_live"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] \
        / ctx["trace"]["busy_s"]
