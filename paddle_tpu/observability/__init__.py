"""paddle_tpu.observability: unified tracing + metrics (ISSUE 8).

Two halves, one activation story:

- `trace` — thread-safe monotonic-clock span recorder (bounded ring
  buffer, nested spans on per-thread tracks, instant events) with
  Perfetto/chrome://tracing export; every span is also a
  `jax.profiler.TraceAnnotation`, so a live profiler session holds it
  on the same clock as the device's operations.
  Armed by `FLAGS_trace` / ``PADDLE_TPU_TRACE=<path>`` (the export
  path); `trace.enable(path)` programmatically.
- `metrics` — registry of counters / gauges / bucketed histograms
  (TTFT, time-per-output-token, queue wait, prefill/decode chunk time,
  sync wait) plus a bounded structured-event log that folds the
  resilience telemetry (RetryStats give-ups, chaos firings, watchdog
  retirements, preemptions) into one place. `snapshot()` for dicts,
  `emit_jsonl()` for logging, `prometheus_text()` for scraping. Armed
  by `FLAGS_metrics` / ``PADDLE_TPU_METRICS=1``; `metrics.enable()`
  programmatically.

Both are OFF by default with a compiled-out-style fast path: every
instrumentation site resolves `get_tracer()` / `get_metrics()` once
and does a single ``is None`` check per event — disabled overhead is
unmeasurable (< 2% tokens/s on `bench_continuous`, asserted by its
``--trace`` summary line). Emitting a span while jax is TRACING raises
`TraceUnderJitError` (lint rule TPU602) — tracing must never compile
into a program.

Instrumented out of the box: the serving engine's full request
lifecycle (enqueue → admit → prefill dispatch/commit → handoff →
per-chunk decode → retire, eviction + watchdog retirement + stall
spans), `hapi.Model.fit` step phases (data fetch, step dispatch,
checkpoint save), and the resilience seams. See README.md here for
the span catalogue and the Perfetto workflow.
"""
from __future__ import annotations

from . import metrics, trace  # noqa: F401
from .metrics import (Counter, Gauge, Histogram,  # noqa: F401
                      MetricsRegistry, get_metrics)
from .trace import (Tracer, TraceUnderJitError,  # noqa: F401
                    get_tracer, merge_chrome_traces, write_chrome_trace)

__all__ = ["trace", "metrics", "Tracer", "TraceUnderJitError",
           "MetricsRegistry", "Counter", "Gauge", "Histogram",
           "get_tracer", "get_metrics", "record_event",
           "write_chrome_trace", "merge_chrome_traces"]


def record_event(name: str, **fields) -> None:
    """Fire-and-forget structured event into BOTH armed sinks (metrics
    event log + trace instant). The one-liner the resilience modules
    call from their hot paths — a no-op (two None checks) when
    observability is off, and never raises: telemetry must not take
    down the step it observes (except under jax tracing, where the
    TPU602 guard in `trace.instant` must propagate)."""
    m = metrics.get_metrics()
    if m is not None:
        try:
            m.event(name, **fields)
        except Exception:  # pragma: no cover - defensive
            pass
    t = trace.get_tracer()
    if t is not None:
        t.instant(name, **fields)
