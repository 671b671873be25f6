"""Host-side span tracing with Perfetto/chrome://tracing export.

The one trace emitter the stack shares (ISSUE 8). Three writers used to
coexist — `profiler.Profiler.export`, `parallel/pipeline_viz.
save_chrome_trace`, and ad-hoc bench timing — each with its own JSON
assembly; they now all emit through `write_chrome_trace` here, and live
host spans are recorded by ONE `Tracer`:

- **monotonic-clock spans** (`time.perf_counter_ns`) in a bounded ring
  buffer (`collections.deque(maxlen=...)`): a long-serving engine can
  trace forever without growing memory — old spans fall off the back;
- **nested spans, per-thread tracks**: spans are chrome "X" complete
  events keyed by thread id, so Perfetto renders nesting per track from
  timestamp containment; `set_thread_name` labels the track;
- **structured instant events** (`instant`) for point-in-time facts
  (retire, eviction, chaos fault, watchdog retirement);
- **one clock with the device trace**: every `span()` also enters a
  `jax.profiler.TraceAnnotation` of the same name with the span's
  arguments (`step_span` a `StepTraceAnnotation`), so whenever a
  profiler session is live the span is an event on the host plane of
  the same `.xplane.pb` as the device's operations, its arguments the
  event's stats. The two records count on different clocks — this
  JSON `time.perf_counter_ns`, the profiler nanoseconds from its
  session's `profile_start_time` (CLOCK_REALTIME) — and the Tracer
  reads both of its own clocks back to back (`clock_anchor`, taken at
  construction and at `clear()`, written into the exported JSON's
  metadata), so `session_ns` lays any JSON event on the session's
  clock to within microseconds. Outside a session an annotation is one
  check of an atomic. `complete()` records after the fact and so
  cannot enter one;
- **trace-safety guard** (lint rule TPU602): a span/instant emitted
  while jax is TRACING a program would bake a host callback — and a
  per-execution host round-trip — into the compiled artifact. Like
  `resilience.checkpoint`'s TPU601 trace guard, the recorder raises
  `TraceUnderJitError` at trace time instead; the static analyzer's
  TPU602 rule catches emitters smuggled in via explicit callbacks.

Activation: `FLAGS_trace` / `PADDLE_TPU_TRACE=<path>` arms the global
tracer and `export_global()` (atexit-registered on first use) writes
the chrome-trace JSON to `<path>`. When the flag is empty the module
functions are a single `is None` check — the disabled fast path
allocates nothing and is unmeasurable next to a device dispatch
(asserted by tests/test_observability.py and the `bench_continuous
--trace` overhead summary).
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

__all__ = ["Tracer", "TraceUnderJitError", "write_chrome_trace",
           "merge_chrome_traces", "get_tracer", "enable", "disable",
           "span", "instant", "export_global", "session_ns",
           "profile_start_time"]


class TraceUnderJitError(RuntimeError):
    """A trace span/instant was emitted while jax was tracing a program
    (lint rule TPU602): the emitter would compile into the jitted
    artifact as a host callback and stall the device every execution.
    Trace on the HOST between dispatches, never inside traced code."""


def _under_jit() -> bool:
    """True when jax is mid-trace. Cheap (one C call) and import-lazy:
    a pure-host process that never imports jax never pays for it."""
    import sys

    if "jax" not in sys.modules:
        return False
    from jax._src import core

    return not core.trace_state_clean()


def write_chrome_trace(events, path: str, *, metadata: Optional[dict] = None,
                       display_time_unit: Optional[str] = None) -> str:
    """THE chrome://tracing / Perfetto JSON writer (JSON Object Format:
    {"traceEvents": [...]}). `profiler.Profiler.export` and
    `parallel.pipeline_viz.save_chrome_trace` both emit through here —
    one schema implementation, their output paths/filenames unchanged."""
    doc = {"traceEvents": list(events)}
    if display_time_unit:
        doc["displayTimeUnit"] = display_time_unit
    if metadata:
        doc["metadata"] = metadata
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


def merge_chrome_traces(paths, out: Optional[str] = None, *,
                        labels=None) -> dict:
    """Merge per-worker chrome traces into ONE Perfetto JSON document
    (the ROADMAP cross-host trace-merge follow-up, ISSUE 17).

    Every input file becomes one PROCESS in the merged timeline: its
    events are re-stamped ``pid=i`` (in-process fleet workers all share
    the real pid — without the re-stamp their tracks would interleave
    into one unreadable process) and a ``process_name`` metadata row
    names the track (``labels[i]`` or the file's basename). Wall-clock
    ``ts`` values are left untouched: all workers of one serving group
    share a clock, so cross-worker causality (kill -> requeue ->
    re-prefill) reads directly off the merged view. Returns the merged
    document; also writes it when `out` is given."""
    paths = list(paths)
    merged: list = []
    meta: dict = {"merged_from": []}
    for i, p in enumerate(paths):
        with open(p) as f:
            doc = json.load(f)
        if isinstance(doc, list):       # bare event-array form
            doc = {"traceEvents": doc}
        events = doc.get("traceEvents") or []
        label = labels[i] if labels and i < len(labels) else None
        if label is None:
            label = os.path.splitext(os.path.basename(p))[0]
        merged.append({"name": "process_name", "ph": "M", "pid": i,
                       "tid": 0, "args": {"name": label}})
        for ev in events:
            ev = dict(ev)
            ev["pid"] = i
            merged.append(ev)
        meta["merged_from"].append({"pid": i, "label": label,
                                    "path": str(p)})
        for k, v in (doc.get("metadata") or {}).items():
            meta.setdefault(k, v)
    doc = {"traceEvents": merged, "metadata": meta}
    if out:
        write_chrome_trace(merged, out, metadata=meta)
    return doc


def _clock_anchor() -> dict:
    """One reading of the Tracer's clock (`perf_counter_ns`, the midpoint
    of two reads) and the wall clock (`time_ns`) taken between them."""
    a = time.perf_counter_ns()
    wall = time.time_ns()
    b = time.perf_counter_ns()
    return {"perf_counter_ns": (a + b) // 2, "time_ns": wall}


def session_ns(ts_us: float, clock_anchor: dict,
               profile_start_time: int) -> float:
    """An exported event's `ts` (microseconds on `perf_counter`) as
    nanoseconds from the start of a profiler session, the clock of every
    event in that session's `.xplane.pb`. `clock_anchor` is the exported
    JSON's `metadata["clock_anchor"]`; `profile_start_time` the session's
    (`profile_start_time(path)`). Exact up to the anchor's read and the
    wall clock's steps between anchor and event."""
    return (ts_us * 1e3 - clock_anchor["perf_counter_ns"]
            + clock_anchor["time_ns"] - profile_start_time)


def profile_start_time(xplane_path: str) -> int:
    """The session's start (CLOCK_REALTIME ns): the `Task Environment`
    plane's `profile_start_time` stat, from which its events count."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name == "Task Environment":
            return int(dict(plane.stats)["profile_start_time"])
    raise ValueError(f"{xplane_path}: no Task Environment plane")


class _SpanHandle:
    """Context manager for one live span (created only when tracing is
    ON — the disabled path never reaches here). `ann` is the profiler
    annotation entered and left with the span."""

    __slots__ = ("tracer", "name", "args", "t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: dict, ann):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.t0 = 0
        self._ann = ann

    def set(self, **args) -> None:
        """Arguments known only inside the span (a count, what was
        retired): added to the recorded event and, in a live profiler
        session, to the annotation's stats."""
        self.args.update(args)
        self._ann.set_metadata(**args)

    def __enter__(self):
        if _under_jit():
            raise TraceUnderJitError(
                f"span {self.name!r} opened while jax is tracing a "
                "program: the emitter would compile into the jitted "
                "artifact (lint rule TPU602); trace on the host "
                "between dispatches instead")
        self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self.tracer._record_complete(self.name, self.t0, t1, self.args)
        return False


class Tracer:
    """Thread-safe bounded span recorder with chrome-trace export.

    ::

        tr = Tracer(capacity=65536)
        with tr.span("decode.dispatch", chunk=n):
            ...
        tr.instant("req.retire", req_id=7)
        tr.export("trace.json")
    """

    def __init__(self, capacity: int = 65536, pid: Optional[int] = None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.pid = os.getpid() if pid is None else int(pid)
        self._lock = threading.Lock()
        self._events = deque(maxlen=self.capacity)
        self._thread_names = {}  # tid -> name (metadata, never evicted)
        self.dropped = 0         # spans the ring buffer evicted
        self.n_recorded = 0
        # both clocks read back to back: `session_ns` maps the events
        # onto a profiler session's clock through it
        self.clock_anchor = _clock_anchor()

    # -- recording -----------------------------------------------------
    def span(self, name: str, **args) -> _SpanHandle:
        """Context manager recording a complete ("X") span on this
        thread's track, and a `jax.profiler.TraceAnnotation` of the
        same name and arguments in the profiler's trace when a session
        is live. `handle.set(**args)` adds arguments from inside."""
        return _SpanHandle(self, name, args, TraceAnnotation(name, **args))

    def step_span(self, name: str, step: int) -> _SpanHandle:
        """Span for one training/serving step, entered as a
        `jax.profiler.StepTraceAnnotation` (the annotation XProf's step
        views key on)."""
        return _SpanHandle(self, name, {"step": int(step)},
                           StepTraceAnnotation(name, step_num=step))

    def instant(self, name: str, **args) -> None:
        """Structured point-in-time event ("i" phase, thread scope)."""
        if _under_jit():
            raise TraceUnderJitError(
                f"instant {name!r} emitted while jax is tracing a "
                "program (lint rule TPU602)")
        ev = {"name": name, "ph": "i", "s": "t",
              "ts": time.perf_counter_ns() / 1e3,
              "pid": self.pid, "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        self._push(ev)

    def complete(self, name: str, t0_ns: int, t1_ns: int, **args) -> None:
        """Record an interval whose ends were stamped elsewhere
        (`hapi.Model.fit` times its data fetch and checkpoint save
        anyway). Not in the profiler's trace: an annotation cannot be
        entered after the fact."""
        if _under_jit():
            raise TraceUnderJitError(
                f"complete {name!r} recorded while jax is tracing a "
                "program (lint rule TPU602)")
        self._record_complete(name, t0_ns, t1_ns, args)

    def set_thread_name(self, name: str, tid: Optional[int] = None) -> None:
        with self._lock:
            self._thread_names[tid if tid is not None
                               else threading.get_ident()] = str(name)

    # -- internals -----------------------------------------------------
    def _record_complete(self, name, t0_ns, t1_ns, args):
        ev = {"name": name, "ph": "X", "ts": t0_ns / 1e3,
              "dur": max(t1_ns - t0_ns, 0) / 1e3,
              "pid": self.pid, "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        self._push(ev)

    def _push(self, ev):
        with self._lock:
            if len(self._events) == self.capacity:
                self.dropped += 1
            self._events.append(ev)
            self.n_recorded += 1

    # -- export --------------------------------------------------------
    def events(self) -> list:
        """Snapshot of buffered events (metadata rows first)."""
        with self._lock:
            meta = [{"name": "thread_name", "ph": "M", "pid": self.pid,
                     "tid": tid, "args": {"name": nm}}
                    for tid, nm in sorted(self._thread_names.items())]
            return meta + list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0
            self.clock_anchor = _clock_anchor()

    def export(self, path: str, metadata: Optional[dict] = None) -> str:
        md = {"n_recorded": self.n_recorded, "dropped": self.dropped,
              "clock_anchor": dict(self.clock_anchor)}
        if metadata:
            md.update(metadata)
        return write_chrome_trace(self.events(), path, metadata=md,
                                  display_time_unit="ms")


# -- global tracer, armed by FLAGS_trace / PADDLE_TPU_TRACE=<path> -----
_global: Optional[Tracer] = None
_global_path: Optional[str] = None
_resolved = False
_atexit_armed = False


def _resolve_from_flags():
    try:
        from ..framework.flags import flag

        path = str(flag("trace")).strip()
    except Exception:
        path = os.environ.get("PADDLE_TPU_TRACE", "").strip()
    if path:
        enable(path)


def enable(path: Optional[str] = None, capacity: int = 65536) -> Tracer:
    """Arm the global tracer (programmatic equivalent of
    PADDLE_TPU_TRACE=<path>); `path` is where `export_global` lands."""
    global _global, _global_path, _resolved, _atexit_armed
    _resolved = True
    _global = Tracer(capacity=capacity)
    _global_path = path
    if path and not _atexit_armed:
        import atexit

        atexit.register(export_global)
        _atexit_armed = True
    return _global


def disable() -> None:
    global _global, _global_path, _resolved
    _global, _global_path, _resolved = None, None, True


def get_tracer() -> Optional[Tracer]:
    """The armed global tracer, or None (THE disabled fast path: every
    instrumentation site holds this result and does one `is None`
    check per event). The flag is re-read on every unarmed call — a
    registry dict lookup — so `set_flags({'trace': ...})` AFTER some
    earlier instrumented call still arms tracing; only an explicit
    `enable()`/`disable()` latches the decision (`_resolved`)."""
    if _global is None and not _resolved:
        _resolve_from_flags()
    return _global


class _NullSpan:
    """Singleton no-op context manager — `span()` with tracing off
    returns this one shared object, allocating nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def span(name: str, **args):
    """Module-level span against the global tracer; a shared no-op when
    tracing is off."""
    tr = get_tracer()
    return _NULL_SPAN if tr is None else tr.span(name, **args)


def instant(name: str, **args) -> None:
    tr = get_tracer()
    if tr is not None:
        tr.instant(name, **args)


def export_global(path: Optional[str] = None) -> Optional[str]:
    """Write the global tracer's buffer to `path` (default: the
    FLAGS_trace path). No-op when tracing is off."""
    tr = get_tracer()
    if tr is None:
        return None
    p = path or _global_path
    return tr.export(p) if p else None
