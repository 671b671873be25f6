"""Due time -> first `prefill.dispatch` of the request, median over the
measured requests (engine Tracer spans)."""
from benchmark import arith


def read(ctx):
    first = {}
    for e in ctx["spans"]:
        if e.get("name") == "prefill.dispatch":
            for rid in e["args"]["req_ids"]:
                first.setdefault(rid, e["ts"] / 1e6)
    waits = [(first[r.req_id] - r.arrival_time) * 1e3
             for r, _ in ctx["measured"] if r.req_id in first]
    return arith.median(waits) if waits else None
