"""What the readers share: the engine Tracer's spans of one run, cut to the
measured window. Span times are microseconds on `time.perf_counter`."""
from benchmark import arith


def in_window(ctx, name: str) -> list:
    lo, hi = ctx["t0"] * 1e6, ctx["t1"] * 1e6
    return [e for e in ctx["spans"]
            if e.get("name") == name and e.get("ph") == "X"
            and lo <= e["ts"] < hi]


def chunk_ms(ctx, mixed: bool):
    """Median dispatch -> commit time of the window's pure-decode (or mixed)
    steps. Chunks commit in the order they were dispatched, so the k-th
    `decode.dispatch` pairs with the k-th `decode.sync_wait` whether or not
    the engine pipelines them."""
    disp = [e for e in ctx["spans"] if e.get("name") == "decode.dispatch"]
    sync = [e for e in ctx["spans"] if e.get("name") == "decode.sync_wait"]
    lo, hi = ctx["t0"] * 1e6, ctx["t1"] * 1e6
    ms = [(s["ts"] + s["dur"] - d["ts"]) / 1e3 for d, s in zip(disp, sync)
          if lo <= d["ts"] < hi
          and bool(d.get("args", {}).get("prefill_window")) == mixed]
    return arith.median(ms) if ms else None
