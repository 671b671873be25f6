"""The busiest held expert's rows over the held experts' mean, a routed
layer each time it ran: `moe_load_max / (moe_rows_routed / held)` (the model
counts the rows routed to the experts this chip holds)."""
from benchmark.readers import _mellum


def read(ctx):
    c = _mellum.counters(ctx)
    if not c or not c["moe_rows_routed"]:
        return None
    held = len(ctx["config"]["deployment"]["held"])
    return c["moe_load_max"] * held / c["moe_rows_routed"]
