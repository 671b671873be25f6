"""Held experts that got at least one row, of the held experts, a routed
layer each time it ran: `moe_experts_hit / (held x moe_layer_steps)` (the
model counts hits among the experts this chip holds)."""
from benchmark.readers import _mellum


def read(ctx):
    c = _mellum.counters(ctx)
    if not c:
        return None
    held = len(ctx["config"]["deployment"]["held"])
    return 100.0 * c["moe_experts_hit"] / (held * c["moe_layer_steps"])
