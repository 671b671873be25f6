"""What the phase readers share: the program's own account of one scheduling
iteration. The engine wraps each iteration in a `sched.step` span and its
host work in `sched.admit`, `sched.build` and `sched.commit` spans beside
`decode.dispatch` and `decode.sync_wait` (engine Tracer, microseconds on
`time.perf_counter`), and names its jitted programs by role
(`jit_serve_decode_chunk`, `jit_serve_unified_step`). A program that has
neither gives these readers nothing to read.
"""
import bisect

from benchmark import arith
from benchmark.readers import _spans


def step_phase_ms(ctx, names) -> list:
    """For each `sched.step` span that starts in the window: (its duration,
    the summed durations of the spans named in `names` that lie inside it on
    the same thread), both in milliseconds."""
    inner = sorted((e for e in ctx["spans"] if e.get("ph") == "X"
                    and e.get("name") in names), key=lambda e: e["ts"])
    starts = [e["ts"] for e in inner]
    out = []
    for st in _spans.in_window(ctx, "sched.step"):
        end = st["ts"] + st["dur"]
        lo = bisect.bisect_left(starts, st["ts"])
        hi = bisect.bisect_right(starts, end)
        out.append((st["dur"] / 1e3, sum(
            e["dur"] for e in inner[lo:hi]
            if e["ts"] + e["dur"] <= end
            and e.get("tid") == st.get("tid")) / 1e3))
    return out


def phase_ms(ctx, names):
    """Median over the window's iterations of the time spent in `names`."""
    per_step = step_phase_ms(ctx, names)
    return arith.median([ms for _, ms in per_step]) if per_step else None


def program_ms(ctx, role: str):
    """Median device duration, in the profiler's trace, of the programs whose
    name holds `role`."""
    secs = [d for name, ds in ctx["trace"]["programs"].items()
            if role in name for d in ds]
    return arith.median(secs) * 1e3 if secs else None
