"""KV bytes held — the full layers' pages and the window layers' rings in use,
the window's means — over what the same reservations would hold with every
layer full (`arith_mellum.window_kv_share`)."""
from benchmark import arith_mellum as arith


def read(ctx):
    g = ctx.get("gauges")
    if not g or not g.get("kv_pages_full") or not g.get("kv_pages_window"):
        return None
    return arith.window_kv_share(ctx["config"], g["kv_pages_full"],
                                 g["kv_pages_window"])
