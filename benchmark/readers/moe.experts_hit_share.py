"""Experts that got at least one row, of all the experts, a routed layer each
time it ran: `moe_experts_hit / (num_experts x moe_layer_steps)`."""
from benchmark.readers import _mellum


def read(ctx):
    c = _mellum.counters(ctx)
    if not c:
        return None
    return 100.0 * c["moe_experts_hit"] \
        / (ctx["config"]["num_experts"] * c["moe_layer_steps"])
