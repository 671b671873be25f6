"""What the host-path readers share: the engine's spans inside
`decode.dispatch` (`decode.stage`: the program's arguments made and put on
the device; `decode.enqueue`: the jitted call alone) and inside
`decode.sync_wait` (`decode.device_wait`: the host waiting on the device;
`decode.readback`: the copies of the finished outputs), PR 37. Each carries
`chunk`, the program it belongs to (the engine's `device_steps` when it was
dispatched: decode chunks and mixed steps count on one sequence), so the
readers pair spans by program and not by order. Microseconds on
`time.perf_counter`, as every engine span. A program without these spans
gives these readers nothing to read."""
from benchmark import arith
from benchmark.readers import _spans


def by_chunk(ctx, name: str, window: bool = True) -> dict:
    """{chunk: [span, ...]} of the `name` spans (those that start in the
    measured window, or all of them)."""
    spans = _spans.in_window(ctx, name) if window else [
        e for e in ctx["spans"] if e.get("name") == name
        and e.get("ph") == "X"]
    out = {}
    for e in spans:
        chunk = e.get("args", {}).get("chunk")
        if chunk is not None:
            out.setdefault(chunk, []).append(e)
    return out


def per_program_ms(ctx, name: str):
    """Median over the window's programs of each one's summed `name`
    spans, in milliseconds."""
    ms = [sum(e["dur"] for e in spans) / 1e3
          for spans in by_chunk(ctx, name).values()]
    return arith.median(ms) if ms else None


def gap_ms(ctx):
    """For each program k enqueued in the window: from the end of k's
    `decode.device_wait` to the end of program k+1's `decode.enqueue`,
    floored at 0; the median, in milliseconds."""
    def end(e):
        return e["ts"] + e["dur"]

    waits = by_chunk(ctx, "decode.device_wait", window=False)
    enqueued = by_chunk(ctx, "decode.enqueue", window=False)
    ms = [max(0.0, end(enqueued[k + 1][0]) - end(waits[k][-1])) / 1e3
          for k in by_chunk(ctx, "decode.enqueue")
          if k in waits and k + 1 in enqueued]
    return arith.median(ms) if ms else None
