"""The busiest expert's rows over the experts' mean, a routed layer each time
it ran: `moe_load_max / (moe_rows_routed / num_experts)`."""
from benchmark.readers import _mellum


def read(ctx):
    c = _mellum.counters(ctx)
    if not c or not c["moe_rows_routed"]:
        return None
    return c["moe_load_max"] * ctx["config"]["num_experts"] \
        / c["moe_rows_routed"]
