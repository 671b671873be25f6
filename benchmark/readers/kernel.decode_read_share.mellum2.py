"""The whole decode step's share of its read bound: the time the chip's memory
would need for the bytes the traced decode steps must read, over the time the
device was busy in the trace.

Bytes (`arith_mellum.decode_step_bytes`): every scheduling step in the trace
runs `steps_per_sync` decode steps; each reads the attention, router and norm
weights of every layer, the head, the weights of the experts that got at least
one row — as the program counted them in its decode lane, never all of them by
assumption — and the K and V its layers attend: the live tokens on the full
layers, min(len, W) a sequence on the window layers (the engine's own gauges,
their means over the steps the trace holds). The prefill windows' work is left
out, which only lowers the share.
"""
from benchmark import arith_mellum as arith
from benchmark.readers import _mellum


def read(ctx):
    c, n = _mellum.counters(ctx), _mellum.traced_steps(ctx)
    g = ctx.get("gauges_traced")
    if not c or not n or not g:
        return None
    hit = _mellum.hit_per_layer_step(c, "_decode")
    if hit is None:
        return None
    need = n * ctx["steps_per_sync"] * arith.decode_step_bytes(
        ctx["config"], hit, g["kv_tokens_live"], g["kv_tokens_window"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] \
        / ctx["trace"]["busy_s"]
