"""The profiler's trace of a few seconds inside the window, and its reduction
to numbers: device busy time, the operations that took it, and the idle gaps
named by what the host was doing in them.

The benchmark's own host spans (`span("step")`, ...) are
`jax.profiler.TraceAnnotation`s, so they land in the same trace, on the same
clock, as the device's operations. `reduce_events` is plain arithmetic over
(name, start, duration) tuples and is tested on hand-built ones; `read_xplane`
is the thin layer that gets such tuples out of an `.xplane.pb` with nothing
but JAX.
"""
from __future__ import annotations

import bisect
import glob
import re
import os
import shutil
import tempfile

import jax

# trace this long, starting this far into the window: a trace of the whole
# window would be large and tracing slows the host
TRACE_START_S = 2.0
TRACE_SECONDS = 4.0
TOP = 10


def span(name: str):
    """A host span of the benchmark's own, visible in the profiler's trace."""
    return jax.profiler.TraceAnnotation("bench." + name)


class Profile:
    """Starts the profiler TRACE_START_S into the window and stops it
    TRACE_SECONDS later; `tick(seconds into the window)` is called from the
    driving loop. With `on` false it does nothing."""

    def __init__(self, on: bool):
        self.on = bool(on)
        self.state = "idle" if on else "off"
        self.dir = None
        self.events = None      # reduce_events' input, after close()
        self.t_start = self.t_stop = None   # seconds into the window

    def tick(self, t: float) -> None:
        if self.state == "idle" and t >= TRACE_START_S:
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.state, self.t_start = "tracing", t
        elif self.state == "tracing" \
                and t >= self.t_start + TRACE_SECONDS:
            jax.profiler.stop_trace()
            self.state, self.t_stop = "done", t

    def close(self) -> None:
        """Stop if still tracing, read the trace, remove its files."""
        if self.state == "tracing":
            jax.profiler.stop_trace()
            self.state = "done"
        if self.state == "done" and self.events is None:
            try:
                self.events = read_xplane(self.dir)
            finally:
                shutil.rmtree(self.dir, ignore_errors=True)


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def read_xplane(trace_dir: str) -> dict:
    """{"device": {plane: {line: [(name, start_ns, dur_ns), ...]}},
        "host": [(name, start_ns, dur_ns), ...]} of the one trace under
    `trace_dir`. Host events are the benchmark's own spans only."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise SystemExit(f"benchmark: expected one .xplane.pb under "
                         f"{trace_dir}, found {len(paths)}")
    out = {"device": {}, "host": []}
    for plane in ProfileData.from_file(paths[0]).planes:
        if is_device_plane(plane.name):
            out["device"][plane.name] = {
                line.name: [(e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events]
                for line in plane.lines}
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [
                    (e.name[len("bench."):], float(e.start_ns),
                     float(e.duration_ns))
                    for e in line.events if e.name.startswith("bench.")]
    return out


_OP = re.compile(r"^%?([\w\-]+?)(?:\.\d+)? = \(?(\w+\[[\d,]*\])")


def op_label(name: str) -> str:
    """Short label of a device operation: the profiler names an XLA op by its
    whole HLO line. `fusion bf16[2048,5504]` — the op without its number and
    its (first) result's shape, so that the same op of every layer adds up."""
    m = _OP.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def union_ns(intervals) -> list:
    """Sorted, merged [start, end] intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def op_events(lines: dict) -> list:
    """The events of one device plane that say "an operation ran": the
    "XLA Ops" line, or failing that the whole programs of "XLA Modules"."""
    for name in ("XLA Ops", "XLA Modules"):
        if lines.get(name):
            return lines[name]
    return []


def reduce_events(events: dict) -> dict:
    """Busy and idle time of the traced window, averaged over the device
    planes; the TOP operations by summed time, and under `device_op_s` the
    summed time of every label, uncut, for the readers (a kernel's share of
    its roofline needs its label's whole sum whatever its rank); the TOP idle
    gaps by what the host was doing while the device waited; the device
    durations of every program ("XLA Modules") by name; how many of each host
    span the window holds.

    The window is the span of the benchmark's own host spans inside the trace
    (the profiler's start and stop themselves are left out); a gap is named by
    the host span that holds its middle, `host` if none does. The drivers'
    spans follow one another and do not nest."""
    outer = sorted(events["host"], key=lambda e: e[1])
    planes = {k: op_events(v) for k, v in events["device"].items()}
    planes = {k: v for k, v in planes.items() if v}
    if not planes:
        raise SystemExit("benchmark: the trace holds no device operation")
    if outer:
        w0, w1 = outer[0][1], max(s + d for _, s, d in outer)
    else:
        w0 = min(s for v in planes.values() for _, s, _ in v)
        w1 = max(s + d for v in planes.values() for _, s, d in v)
    busy, ops, gaps = [], {}, {}
    for evs in planes.values():
        clipped = [(max(s, w0), min(s + d, w1)) for _, s, d in evs
                   if s + d > w0 and s < w1]
        merged = union_ns(clipped)
        busy.append(sum(b - a for a, b in merged))
        for name, s, d in evs:
            label = op_label(name)
            # a loop's or branch's time is its body's, which is listed
            if s + d > w0 and s < w1 \
                    and label.split()[0] not in ("while", "conditional"):
                ops[label] = ops.get(label, 0.0) + d / len(planes)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                who = _covering(outer, a, b)
                gaps[who] = gaps.get(who, 0.0) + (b - a) / len(planes)
    programs = {}
    for lines in events["device"].values():
        for name, s, d in lines.get("XLA Modules", []):
            if w0 <= s < w1:
                programs.setdefault(name, []).append(d / 1e9)
    counts = {}
    for name, s, d in outer:
        counts[name] = counts.get(name, 0) + 1

    def top(d):
        return [[k, v / 1e9] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": sum(busy) / len(busy) / 1e9,
            "window_s": (w1 - w0) / 1e9, "chips": len(planes),
            "device_ops": top(ops), "idle_gaps": top(gaps),
            "device_op_s": {k: v / 1e9 for k, v in ops.items()},
            "programs": programs, "host_spans": counts,
            "planes": {k: {ln: len(ev) for ln, ev in v.items()}
                       for k, v in events["device"].items()}}


def _covering(spans, a: float, b: float) -> str:
    """Name of the span (sorted by start, not nested) that holds the middle
    of [a, b]; `host` where none does."""
    mid = (a + b) / 2
    i = bisect.bisect_right(spans, mid, key=lambda e: e[1]) - 1
    if i >= 0 and mid < spans[i][1] + spans[i][2]:
        return spans[i][0]
    return "host"
