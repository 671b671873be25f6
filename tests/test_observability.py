"""paddle_tpu.observability: unified tracing + metrics (ISSUE 8).

Covers the recorder (span nesting, thread safety, ring bound, chrome
JSON schema, under-jit guard), the metrics registry (bucketed
percentiles vs numpy quantiles, Prometheus exposition, JSONL), the
disabled fast path (singleton no-op span, zero net allocations), and
the serving engine's request-lifecycle instrumentation end-to-end
(TTFT histogram populated, watchdog retirement + chaos firings as
structured events, spans covering every request's lifecycle).
"""
import dataclasses
import json
import threading
import time
import unittest

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability import trace as obs_trace
from paddle_tpu.observability.metrics import Histogram, MetricsRegistry
from paddle_tpu.observability.trace import (Tracer, TraceUnderJitError,
                                            write_chrome_trace)


class TestTracer(unittest.TestCase):
    def test_nested_spans_contained_on_one_track(self):
        tr = Tracer()
        with tr.span("outer", kind="test"):
            with tr.span("inner"):
                pass
            tr.instant("mark", k=1)
        evs = [e for e in tr.events() if e["ph"] != "M"]
        self.assertEqual([e["name"] for e in evs],
                         ["inner", "mark", "outer"])  # close order
        outer = next(e for e in evs if e["name"] == "outer")
        inner = next(e for e in evs if e["name"] == "inner")
        mark = next(e for e in evs if e["name"] == "mark")
        self.assertEqual(outer["tid"], inner["tid"])
        # timestamp containment is what Perfetto renders nesting from
        self.assertLessEqual(outer["ts"], inner["ts"])
        self.assertGreaterEqual(outer["ts"] + outer["dur"],
                                inner["ts"] + inner["dur"])
        self.assertLessEqual(outer["ts"], mark["ts"])
        self.assertEqual(outer["args"], {"kind": "test"})

    def test_thread_safety_and_per_thread_tracks(self):
        tr = Tracer(capacity=100000)
        n_threads, n_spans = 8, 200
        errors = []
        # barrier: all workers alive at once, so OS thread ids are
        # distinct (idents recycle once a thread exits)
        gate = threading.Barrier(n_threads)

        def work(i):
            try:
                gate.wait(timeout=10)
                tr.set_thread_name(f"worker-{i}")
                for k in range(n_spans):
                    with tr.span("w", i=i, k=k):
                        pass
            except Exception as e:  # pragma: no cover
                errors.append(e)

        ts = [threading.Thread(target=work, args=(i,))
              for i in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        self.assertFalse(errors)
        evs = tr.events()
        spans = [e for e in evs if e["ph"] == "X"]
        self.assertEqual(len(spans), n_threads * n_spans)
        self.assertEqual(len({e["tid"] for e in spans}), n_threads)
        names = [e for e in evs if e["ph"] == "M"
                 and e["name"] == "thread_name"]
        self.assertEqual(len(names), n_threads)

    def test_ring_buffer_bounds_memory(self):
        tr = Tracer(capacity=8)
        for i in range(20):
            tr.instant("e", i=i)
        evs = [e for e in tr.events() if e["ph"] != "M"]
        self.assertEqual(len(evs), 8)
        self.assertEqual(tr.dropped, 12)
        self.assertEqual(tr.n_recorded, 20)
        # oldest fell off the back, newest survives
        self.assertEqual(evs[-1]["args"]["i"], 19)
        self.assertEqual(evs[0]["args"]["i"], 12)

    def test_chrome_trace_json_schema(self, tmp_path=None):
        import tempfile

        tr = Tracer()
        tr.set_thread_name("main")
        with tr.span("a", x=1):
            tr.instant("i")
        with tempfile.TemporaryDirectory() as d:
            path = tr.export(d + "/t.json", metadata={"run": "test"})
            with open(path) as f:
                doc = json.load(f)
        self.assertIn("traceEvents", doc)
        self.assertEqual(doc["displayTimeUnit"], "ms")
        self.assertEqual(doc["metadata"]["run"], "test")
        phases = set()
        for e in doc["traceEvents"]:
            self.assertIn("name", e)
            self.assertIn("ph", e)
            self.assertIn("pid", e)
            phases.add(e["ph"])
            if e["ph"] != "M":
                self.assertIn("ts", e)
                self.assertIn("tid", e)
            if e["ph"] == "X":
                self.assertGreaterEqual(e["dur"], 0)
        self.assertEqual(phases, {"M", "X", "i"})

    def test_shared_writer_serves_pipeline_viz_and_profiler(self):
        """The satellite dedup: both legacy writers emit through
        observability.trace.write_chrome_trace with their original
        schemas intact."""
        import tempfile

        from paddle_tpu.parallel.pipeline_viz import (pipeline_timeline,
                                                      save_chrome_trace)
        from paddle_tpu.profiler import Profiler, RecordEvent

        tl = pipeline_timeline("1F1B", n_stages=2, n_micro=4)
        with tempfile.TemporaryDirectory() as d:
            save_chrome_trace(tl, d + "/pipe.json")
            with open(d + "/pipe.json") as f:
                doc = json.load(f)
            self.assertIn("traceEvents", doc)
            self.assertIn("stats", doc["metadata"])
            self.assertTrue(any(e["ph"] == "X"
                                for e in doc["traceEvents"]))

            p = Profiler(timer_only=True)
            p.start()
            with RecordEvent("unit_span"):
                pass
            p.stop()
            p.export(d + "/prof.json")
            with open(d + "/prof.json") as f:
                doc = json.load(f)
            self.assertEqual(doc["displayTimeUnit"], "ms")
            self.assertTrue(any(e["name"] == "unit_span"
                                for e in doc["traceEvents"]))

    def test_span_under_jit_raises(self):
        import jax
        import jax.numpy as jnp

        tr = Tracer()

        def f(x):
            with tr.span("bad"):
                return x * 2

        with pytest.raises(TraceUnderJitError, match="TPU602"):
            jax.jit(f)(jnp.ones((2,)))

        def g(x):
            tr.instant("bad")
            return x

        with pytest.raises(TraceUnderJitError):
            jax.jit(g)(jnp.ones((2,)))

        def k(x):
            tr.complete("bad", 0, 1)
            return x

        with pytest.raises(TraceUnderJitError):
            jax.jit(k)(jnp.ones((2,)))
        # the tracer is still usable on the host afterwards
        with tr.span("fine"):
            pass
        self.assertTrue(any(e["name"] == "fine" for e in tr.events()))

    def test_write_chrome_trace_plain(self):
        import tempfile

        with tempfile.TemporaryDirectory() as d:
            path = write_chrome_trace(
                [{"name": "x", "ph": "X", "ts": 0, "dur": 1,
                  "pid": 0, "tid": 0}], d + "/sub/dir/t.json")
            with open(path) as f:
                doc = json.load(f)
            self.assertEqual(len(doc["traceEvents"]), 1)
            self.assertNotIn("displayTimeUnit", doc)


class TestHistogram(unittest.TestCase):
    def _assert_percentile_within_bucket(self, h, samples, q):
        est = h.percentile(q)
        true = float(np.percentile(samples, q))
        # bucket-interpolated percentile is exact to within the bucket
        # holding the true quantile (allow one bucket of slack for
        # rank-convention differences at the edge)
        bounds = (0.0,) + h.bounds
        idx = next((i for i in range(1, len(bounds))
                    if true <= bounds[i]), len(bounds) - 1)
        lo = bounds[max(idx - 1, 0)]
        hi = bounds[min(idx + 1, len(bounds) - 1)]
        self.assertLessEqual(lo, est,
                             f"p{q}: est {est} below bucket lo {lo} "
                             f"(true {true})")
        self.assertLessEqual(est, hi,
                             f"p{q}: est {est} above bucket hi {hi} "
                             f"(true {true})")

    def test_percentiles_vs_numpy_quantiles(self):
        rng = np.random.default_rng(7)
        samples = np.exp(rng.uniform(np.log(2e-4), np.log(5.0), 5000))
        h = Histogram("lat")
        for s in samples:
            h.observe(float(s))
        self.assertEqual(h.count, len(samples))
        self.assertAlmostEqual(h.sum, float(samples.sum()), places=6)
        self.assertEqual(h.min, float(samples.min()))
        self.assertEqual(h.max, float(samples.max()))
        for q in (10, 50, 90, 99):
            self._assert_percentile_within_bucket(h, samples, q)

    def test_percentile_edge_cases(self):
        h = Histogram("x", bounds=(1.0, 2.0, 4.0))
        self.assertIsNone(h.percentile(50))
        h.observe(0.5)
        self.assertLessEqual(h.percentile(50), 1.0)
        h2 = Histogram("y", bounds=(1.0,))
        h2.observe(100.0)  # all mass overflowed: exact min clamps up
        self.assertEqual(h2.percentile(99), 100.0)
        self.assertEqual(h2.percentile(100), 100.0)  # terminal = max
        with self.assertRaises(ValueError):
            Histogram("bad", bounds=(2.0, 1.0))

    def test_percentile_overflow_bucket_mid_rank_not_max(self):
        # mass past the top bound must NOT drag mid percentiles to the
        # recorded max: samples over the top edge plus one huge
        # outlier — p50 reports the overflow bucket's lower bound
        # (the exact min when ALL mass overflowed, the top edge
        # otherwise); only the terminal rank reports the exact max
        h = Histogram("z", bounds=(0.5, 1.0))
        for _ in range(100):
            h.observe(2.0)
        h.observe(600.0)
        self.assertEqual(h.percentile(50), 2.0)   # exact min, not 600
        self.assertEqual(h.percentile(100), 600.0)
        h.observe(0.4)  # mixed: some mass below the top edge
        self.assertEqual(h.percentile(50), 1.0)   # top edge, not 600

    def test_threaded_observe_counts_exact(self):
        h = Histogram("t")
        n_threads, n_obs = 8, 500

        def work():
            for i in range(n_obs):
                h.observe(1e-3 * (i + 1))

        ts = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        self.assertEqual(h.count, n_threads * n_obs)
        self.assertEqual(sum(h.counts), n_threads * n_obs)


class TestMetricsRegistry(unittest.TestCase):
    def test_snapshot_and_events(self):
        m = MetricsRegistry()
        m.counter("reqs").inc()
        m.counter("reqs").inc(2)
        m.gauge("depth").set(7)
        m.histogram("lat").observe(0.01)
        m.event("watchdog.retire", slot=3)
        snap = m.snapshot()
        self.assertEqual(snap["counters"]["reqs"], 3)
        self.assertEqual(snap["gauges"]["depth"], 7)
        self.assertEqual(snap["histograms"]["lat"]["count"], 1)
        self.assertIn("p99", snap["histograms"]["lat"])
        self.assertEqual(snap["n_events"], 1)
        evs = m.events("watchdog.retire")
        self.assertEqual(evs[0]["slot"], 3)
        self.assertIn("t", evs[0])
        json.dumps(snap)  # snapshot must be JSON-serializable

    def test_event_log_bounded(self):
        m = MetricsRegistry(max_events=4)
        for i in range(10):
            m.event("e", i=i)
        evs = m.events()
        self.assertEqual(len(evs), 4)
        self.assertEqual(evs[-1]["i"], 9)

    def test_jsonl_emission(self):
        import io

        m = MetricsRegistry()
        m.counter("c").inc()
        buf = io.StringIO()
        m.emit_jsonl(buf, extra={"policy": "x"})
        m.emit_jsonl(buf)
        lines = buf.getvalue().strip().split("\n")
        self.assertEqual(len(lines), 2)
        doc = json.loads(lines[0])
        self.assertEqual(doc["policy"], "x")
        self.assertEqual(doc["counters"]["c"], 1)

    def test_prometheus_text_exposition(self):
        m = MetricsRegistry()
        m.counter("requests", doc="total requests").inc(5)
        m.gauge("pool_pages").set(42)
        h = m.histogram("ttft_s", bounds=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(10.0)
        text = m.prometheus_text()
        self.assertIn("# TYPE paddle_tpu_requests_total counter", text)
        self.assertIn("paddle_tpu_requests_total 5", text)
        self.assertIn("# TYPE paddle_tpu_pool_pages gauge", text)
        self.assertIn("paddle_tpu_pool_pages 42", text)
        self.assertIn('paddle_tpu_ttft_s_bucket{le="0.1"} 1', text)
        self.assertIn('paddle_tpu_ttft_s_bucket{le="1"} 2', text)
        self.assertIn('paddle_tpu_ttft_s_bucket{le="+Inf"} 3', text)
        self.assertIn("paddle_tpu_ttft_s_count 3", text)
        self.assertTrue(text.endswith("\n"))


class TestDisabledFastPath(unittest.TestCase):
    def test_globals_off_by_default(self):
        self.assertIsNone(obs_trace.get_tracer())
        self.assertIsNone(obs_metrics.get_metrics())

    def test_noop_span_is_singleton(self):
        # the disabled path returns ONE shared context manager object —
        # no per-call allocation
        a = obs_trace.span("x", k=1)
        b = obs_trace.span("y")
        self.assertIs(a, b)
        with a:
            pass
        obs_trace.instant("x")        # no-op, no error
        obs.record_event("x", k=2)    # no-op, no error
        self.assertIsNone(obs_trace.export_global())

    def test_zero_net_allocations_when_off(self):
        import gc
        import sys

        tr = obs_trace.get_tracer()     # None: what a site holds when off
        null = obs_trace._NULL_SPAN

        def loop(n):
            for i in range(n):
                with obs_trace.span("hot"):
                    pass
                obs_trace.instant("hot")
                # a module-level site with arguments (hapi's `fit.step`)
                with obs_trace.span("hot.args", step=i):
                    pass
                # the engine's sites: one `is None` test picks the shared
                # no-op, arguments are never evaluated, `set` never called
                with (null if tr is None else tr.span(
                        "sched.step", iter=i)) as sp:
                    with (null if tr is None else tr.span(
                            "sched.commit", iter=i)) as inner:
                        if tr is not None:
                            inner.set(produced=i, emitted={i: 1})
                    if tr is not None:
                        sp.set(kind="decode")

        loop(100)  # warm any lazy caches
        gc.collect()
        before = sys.getallocatedblocks()
        loop(10000)
        gc.collect()
        after = sys.getallocatedblocks()
        # interpreter noise only; a per-event allocation would be >= 20k
        self.assertLess(abs(after - before), 500)

    def test_flag_armed_after_first_use(self):
        # arming FLAGS_trace/FLAGS_metrics AFTER an earlier unarmed
        # get_*() must still take effect (the lookup is re-resolved on
        # every unarmed call; only explicit enable/disable latches —
        # clear the latch another test's disable() may have set)
        obs_trace._resolved = obs_metrics._resolved = False
        self.assertIsNone(obs_trace.get_tracer())
        self.assertIsNone(obs_metrics.get_metrics())
        import tempfile

        with tempfile.TemporaryDirectory() as d:
            paddle.set_flags({"trace": d + "/t.json", "metrics": True})
            try:
                self.assertIsNotNone(obs_trace.get_tracer())
                self.assertIsNotNone(obs_metrics.get_metrics())
            finally:
                paddle.set_flags({"trace": "", "metrics": False})
                obs_trace.disable()
                obs_metrics.disable()

    def test_enable_disable_roundtrip(self):
        try:
            tr = obs_trace.enable()
            self.assertIs(obs_trace.get_tracer(), tr)
            m = obs_metrics.enable()
            self.assertIs(obs_metrics.get_metrics(), m)
            obs.record_event("both", k=1)
            self.assertEqual(len(m.events("both")), 1)
            self.assertTrue(any(e["name"] == "both"
                                for e in tr.events()))
        finally:
            obs_trace.disable()
            obs_metrics.disable()
        self.assertIsNone(obs_trace.get_tracer())
        self.assertIsNone(obs_metrics.get_metrics())


def _tiny_engine(tracer=None, metrics=None, **kw):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ContinuousBatchingEngine

    cfg = dataclasses.replace(LlamaConfig.tiny(), num_key_value_heads=2)
    paddle.seed(21)
    params = dict(LlamaForCausalLM(cfg).raw_state())
    kw.setdefault("slots", 2)
    kw.setdefault("prompt_bucket", 8)
    kw.setdefault("max_prompt_len", 16)
    kw.setdefault("max_new_tokens", 4)
    kw.setdefault("block_size", 8)
    kw.setdefault("steps_per_sync", 2)
    eng = ContinuousBatchingEngine(cfg, params, tracer=tracer,
                                   metrics=metrics, **kw)
    return cfg, eng


class TestEngineLifecycleObservability(unittest.TestCase):
    def test_request_lifecycle_spans_and_histograms(self):
        tr = Tracer()
        mt = MetricsRegistry()
        cfg, eng = _tiny_engine(tracer=tr, metrics=mt)
        rng = np.random.default_rng(3)
        reqs = [eng.add_request(rng.integers(1, cfg.vocab_size,
                                             (n,)).tolist())
                for n in (5, 7, 3)]
        eng.run(max_iters=100)
        self.assertEqual(len(eng.finished), 3)

        evs = tr.events()
        names = {e["name"] for e in evs}
        for expected in ("req.enqueue", "req.admit", "prefill.dispatch",
                         "decode.dispatch", "decode.sync_wait",
                         "req.retire"):
            self.assertIn(expected, names, f"missing span {expected}")
        # every request's lifecycle instants are present
        for stage in ("req.enqueue", "req.admit", "req.retire"):
            ids = {e["args"]["req_id"] for e in evs
                   if e["name"] == stage}
            self.assertEqual(ids, {r.req_id for r in reqs},
                             f"{stage} must cover every request")

        snap = mt.snapshot()
        self.assertEqual(snap["histograms"]["ttft_s"]["count"], 3)
        self.assertEqual(snap["histograms"]["queue_wait_s"]["count"], 3)
        self.assertGreaterEqual(
            snap["histograms"]["decode_chunk_s"]["count"], 1)
        self.assertGreaterEqual(
            snap["histograms"]["sync_wait_s"]["count"], 1)
        # max_new=4 > 1 so every request decodes past its first token
        self.assertEqual(snap["histograms"]["tpot_s"]["count"], 3)
        self.assertEqual(snap["counters"]["requests_enqueued"], 3)
        self.assertEqual(snap["counters"]["requests_finished"], 3)
        self.assertGreater(snap["counters"]["output_tokens"], 0)

    def test_slotless_prefill_retire_still_instrumented(self):
        # a disaggregated request fully served by its prefill
        # (max_new=1) retires at the handoff WITHOUT a decode slot —
        # its req.retire instant and requests_finished count must not
        # be skipped, or span-coverage checks report a missing request
        tr = Tracer()
        mt = MetricsRegistry()
        cfg, eng = _tiny_engine(tracer=tr, metrics=mt,
                                disaggregated=True)
        rng = np.random.default_rng(3)
        req = eng.add_request(
            rng.integers(1, cfg.vocab_size, (5,)).tolist(), max_new=1)
        eng.run(max_iters=50)
        self.assertEqual(len(eng.finished), 1)
        retires = [e for e in tr.events() if e["name"] == "req.retire"]
        self.assertEqual([e["args"]["req_id"] for e in retires],
                         [req.req_id])
        self.assertIsNone(retires[0]["args"]["slot"])
        self.assertEqual(
            mt.snapshot()["counters"]["requests_finished"], 1)

    def test_engine_metrics_method_one_dict(self):
        cfg, eng = _tiny_engine()
        rng = np.random.default_rng(3)
        eng.add_request(rng.integers(1, cfg.vocab_size, (5,)).tolist())
        eng.run(max_iters=50)
        m = eng.metrics()
        for key in ("prefix_hit_rate", "sync_wait_s", "blocked_syncs",
                    "prefill_handoffs", "hung_retired", "compile_stats",
                    "kv_pool_bytes", "pool_occupancy", "n_cacheable_pages",
                    "requests_finished", "device_steps"):
            self.assertIn(key, m)
        self.assertEqual(m["requests_finished"], 1)
        self.assertGreater(m["kv_pool_bytes"], 0)
        self.assertIsInstance(m["compile_stats"], dict)
        self.assertGreaterEqual(m["pool_occupancy"], 0.0)
        json.dumps(m)  # one JSON-able dict, no attribute poking

    def test_watchdog_retirement_and_chaos_hang_emit_events(self):
        from paddle_tpu.resilience import chaos

        mt = obs_metrics.enable()  # module seams report to the globals
        tr = obs_trace.enable()
        try:
            cfg, eng = _tiny_engine()  # defaults pick up armed globals
            rng = np.random.default_rng(3)
            for _ in range(3):
                eng.add_request(
                    rng.integers(1, cfg.vocab_size, (5,)).tolist())
            eng.warm(buckets=[8])
            chaos.install("hang:decode:20")
            eng.run(watchdog_timeout=2.0)
            self.assertEqual(eng.hung_retired, 1)
            # the whole failure chain lands in ONE event log: the chaos
            # fault that fired, the watchdog deadline it blew, and the
            # victim the engine retired
            self.assertEqual(len(mt.events("chaos.hang")), 1)
            self.assertEqual(len(mt.events("watchdog.timeout")), 1)
            self.assertEqual(
                len(mt.events("watchdog.retire_hung_slot")), 1)
            wd = mt.events("watchdog.timeout")[0]
            self.assertEqual(wd["watchdog"], "engine.step")
            names = {e["name"] for e in tr.events()}
            self.assertIn("watchdog.retire_hung_slot", names)
        finally:
            chaos.uninstall()
            obs_metrics.disable()
            obs_trace.disable()

    def test_chaos_io_error_fires_as_event(self):
        from paddle_tpu.resilience import chaos
        from paddle_tpu.resilience.chaos import ChaosError

        mt = obs_metrics.enable()
        try:
            chaos.install("io_error:1.0:shard_read")
            with self.assertRaises(ChaosError):
                chaos.maybe_io_error("shard_read")
            evs = mt.events("chaos.io_error")
            self.assertEqual(len(evs), 1)
            self.assertEqual(evs[0]["seam"], "shard_read")
        finally:
            chaos.uninstall()
            obs_metrics.disable()

    def test_retry_backoff_folds_into_event_log(self):
        from paddle_tpu.resilience import RetryPolicy

        mt = obs_metrics.enable()
        try:
            calls = []
            policy = RetryPolicy(max_attempts=3, base_delay=0.001,
                                 sleep=lambda d: calls.append(d),
                                 retry_on=(IOError,))

            def flaky():
                if len(calls) < 2:
                    raise IOError("transient")
                return 42

            self.assertEqual(policy.call(flaky), 42)
            self.assertEqual(len(mt.events("retry.backoff")), 2)

            def always():
                raise IOError("permanent")

            with self.assertRaises(IOError):
                policy.call(always)
            self.assertEqual(len(mt.events("retry.giveup")), 1)
        finally:
            obs_metrics.disable()


class TestEngineObservabilityOverhead(unittest.TestCase):
    def test_false_forces_off_despite_armed_globals(self):
        # an untraced bench baseline must stay untraced even when the
        # operator armed PADDLE_TPU_TRACE / FLAGS_metrics: False
        # overrides the global fallback (None defers to it)
        tr = obs_trace.enable()
        mt = obs_metrics.enable()
        try:
            cfg, eng = _tiny_engine(tracer=False, metrics=False)
            self.assertIsNone(eng._tracer)
            self.assertIsNone(eng._metrics)
            cfg, eng2 = _tiny_engine()  # None still defers to globals
            self.assertIs(eng2._tracer, tr)
            self.assertIs(eng2._metrics, mt)
        finally:
            obs_trace.disable()
            obs_metrics.disable()

    def test_disabled_engine_paths_do_not_record(self):
        """With flags off the engine holds None sinks — serving records
        nothing anywhere (the bench-grade <2% overhead bar is asserted
        by bench_continuous --trace on silicon; here we pin the
        mechanism: no sink, no work)."""
        cfg, eng = _tiny_engine()
        self.assertIsNone(eng._tracer)
        self.assertIsNone(eng._metrics)
        rng = np.random.default_rng(3)
        eng.add_request(rng.integers(1, cfg.vocab_size, (5,)).tolist())
        eng.run(max_iters=50)
        self.assertEqual(len(eng.finished), 1)


# ---- ISSUE 25: the engine and the trainer trace themselves ---------------

STEP_CHILDREN = {"sched.admit", "sched.build", "sched.commit",
                 "decode.dispatch", "decode.sync_wait", "prefill.dispatch",
                 "spec.verify"}
ENGINE_MODES = {
    "unified": dict(),
    "split": dict(unified_step=False),
    "pipelined": dict(double_buffer=True),
    "speculative": dict(speculative="ngram", spec_k=2),
    "disaggregated": dict(disaggregated=True),
    "disaggregated_split": dict(disaggregated=True, unified_step=False),
}


def _serve_some(eng, cfg, lengths=(5, 7, 3, 12), seed=3):
    rng = np.random.default_rng(seed)
    reqs = [eng.add_request(rng.integers(1, cfg.vocab_size, (n,)).tolist())
            for n in lengths]
    eng.run(max_iters=200)
    assert all(r.done and not r.failed for r in reqs)
    return reqs


def _inside(e, st) -> bool:
    return st["ts"] <= e["ts"] \
        and e["ts"] + e["dur"] <= st["ts"] + st["dur"]


def _coverage(st, spans) -> float:
    """Share of `st` its children cover (union of their intervals)."""
    covered, cur = 0.0, st["ts"]
    for e in sorted((e for e in spans if e is not st and _inside(e, st)
                     and e["name"] in STEP_CHILDREN),
                    key=lambda e: e["ts"]):
        end = e["ts"] + e["dur"]
        if end > cur:
            covered += end - max(e["ts"], cur)
            cur = end
    return covered / st["dur"]


@pytest.mark.parametrize("mode", sorted(ENGINE_MODES))
def test_step_phase_spans_account_for_every_iteration(mode):
    """Every scheduling iteration is one `sched.step`; its host work lies
    in its children, which cover it; every token a request holds was
    stamped by one `sched.commit`; every request was admitted once."""
    tr = Tracer()
    cfg, eng = _tiny_engine(tracer=tr, steps_per_sync=8, slots=4,
                            max_new_tokens=16, **ENGINE_MODES[mode])
    _serve_some(eng, cfg, seed=5)         # compiles; spans not counted
    tr.clear()
    reqs = _serve_some(eng, cfg)
    spans = [e for e in tr.events() if e["ph"] == "X"]
    steps = [e for e in spans if e["name"] == "sched.step"]
    assert [e["args"]["iter"] for e in steps] == list(range(
        steps[0]["args"]["iter"], eng.sched_iters + 1))
    assert {e["args"]["kind"] for e in steps} <= {"decode", "mixed", "spec"}
    assert ("spec" in {e["args"]["kind"] for e in steps}) \
        == (mode == "speculative")
    assert "mixed" in {e["args"]["kind"] for e in steps}
    # children: inside one step, carrying its `iter`; covering it
    for e in spans:
        if e["name"] in STEP_CHILDREN:
            (st,) = [st for st in steps if _inside(e, st)]
            if e["name"].startswith("sched."):
                assert e["args"]["iter"] == st["args"]["iter"], e
    # the median step is covered; a step the host's scheduler preempted
    # between two spans may fall short, but one in ten at most
    cover = sorted(_coverage(st, spans) for st in steps)
    assert cover[len(cover) // 2] >= 0.95, cover
    assert sum(c < 0.8 for c in cover) <= max(1, len(cover) // 10), cover
    # the per-chunk emission stamp: every token surfaced is in one commit
    commits = [e["args"] for e in spans if e["name"] == "sched.commit"]
    emitted = {}
    for c in commits:
        assert c["produced"] == sum(c["emitted"].values()), c
        for rid, n in c["emitted"].items():
            emitted[rid] = emitted.get(rid, 0) + n
    assert emitted == {r.req_id: len(r.tokens) for r in reqs}
    assert sorted(rid for c in commits for rid in c["retired"]) \
        == sorted(r.req_id for r in reqs)
    # admissions name the requests they touched, once each
    admitted = [rid for e in spans if e["name"] == "sched.admit"
                for rid in e["args"]["req_ids"]]
    if "disaggregated" not in mode:     # there install names them again
        assert sorted(admitted) == sorted(r.req_id for r in reqs)


def _profiler_host_events(trace_dir):
    """{name: [(line, start_ns, end_ns, stats)]} of the host planes."""
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (line.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)))
    return out


@pytest.mark.parametrize("mode", ["unified", "split"])
def test_spans_are_events_of_a_live_profiler_session(mode, tmp_path):
    """One clock: with a profiler session live, every span is an event on
    the host plane of the same trace as the device's operations, its
    arguments the event's stats, nested as the Tracer nests them."""
    import jax

    tr = Tracer()
    cfg, eng = _tiny_engine(tracer=tr, **ENGINE_MODES[mode])
    _serve_some(eng, cfg, seed=5)
    tr.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _serve_some(eng, cfg)
        t = time.perf_counter_ns()
        tr.complete("after.the.fact", t - 1000, t)
    finally:
        jax.profiler.stop_trace()
    got = _profiler_host_events(str(tmp_path))
    spans = [e for e in tr.events() if e["ph"] == "X"]
    want_stats = {"sched.step": {"iter", "kind"},
                  "sched.admit": {"iter", "waiting", "req_ids"},
                  "sched.commit": {"iter", "produced", "retired",
                                   "emitted"},
                  "decode.dispatch": {"chunk", "live"},
                  "decode.sync_wait": {"stalled"},
                  "prefill.dispatch": {"bucket", "batch", "cached_prefix",
                                       "req_ids"}}
    for name, keys in want_stats.items():
        mine = [e for e in spans if e["name"] == name]
        assert mine and len(got.get(name, [])) == len(mine), name
        for (_, _, _, stats), e in zip(
                sorted(got[name], key=lambda x: x[1]),
                sorted(mine, key=lambda e: e["ts"])):
            assert keys <= set(stats), (name, stats)
            for k in keys:      # stats come back as numbers or strings
                v = e["args"][k]
                assert str(stats[k]) in (str(v), str(int(v)) if isinstance(
                    v, bool) else ""), (name, k, stats[k], v)
    # `complete()` is after the fact: in the JSON, not in the profiler
    assert any(e["name"] == "after.the.fact" for e in spans)
    assert "after.the.fact" not in got
    steps = got["sched.step"]
    for name in ("sched.admit", "sched.commit", "decode.dispatch",
                 "decode.sync_wait"):
        for line, a, b, _ in got[name]:
            assert any(ln == line and s <= a and b <= t
                       for ln, s, t, _ in steps), name
    # the profiler counts from its session's start, the Tracer from the
    # process's monotonic clock: the same span, two time bases
    first = min(spans, key=lambda e: e["ts"])
    assert min(a for v in got.values() for _, a, _, _ in v) \
        < first["ts"] * 1e3


def test_disabled_engine_enters_no_span(monkeypatch):
    """With no tracer every site takes the shared no-op: no span handle
    and no profiler annotation is ever made."""
    def boom(*a, **k):
        raise AssertionError("a span was made with tracing off")

    monkeypatch.setattr(obs_trace._SpanHandle, "__init__", boom)
    monkeypatch.setattr(obs_trace, "TraceAnnotation", boom)
    for kw in (dict(), dict(unified_step=False)):
        cfg, eng = _tiny_engine(**kw)
        assert eng._tracer is None
        _serve_some(eng, cfg)


@pytest.mark.parametrize("mode", ["unified", "split"])
def test_dispatch_span_starts_at_the_dispatch_stamp(mode):
    """`decode.dispatch` starts where its stamp was taken before it was
    a context manager: `t_disp0`, which `decode_chunk_s` and
    `step.chunk_ms.sat` count from, is the first thing inside it."""
    tr = Tracer()
    cfg, eng = _tiny_engine(tracer=tr, **ENGINE_MODES[mode])
    stamps = []
    commit = eng._commit_chunk

    def spy(rec, token=None):
        stamps.append(rec["t_disp0"] * 1e6)
        return commit(rec, token)

    eng._commit_chunk = spy
    _serve_some(eng, cfg)
    disp = sorted((e for e in tr.events() if e["name"] == "decode.dispatch"),
                  key=lambda e: e["ts"])
    assert stamps and len(stamps) == len(disp)
    for t, e in zip(sorted(stamps), disp):
        assert e["ts"] <= t <= e["ts"] + e["dur"], (t, e)
        # the stamp is at the start, before the lock and the enqueue
        assert t - e["ts"] <= 0.5 * e["dur"], (t, e)


# ---- ISSUE 37: the host path between two programs ------------------------

HOST_PATH = ("decode.stage", "decode.enqueue", "decode.device_wait",
             "decode.readback")


def _host_path_children(parent, spans):
    """The host-path spans inside `parent` on its thread, by start."""
    return sorted((e for e in spans if e["name"] in HOST_PATH
                   and e["tid"] == parent["tid"] and _inside(e, parent)),
                  key=lambda e: e["ts"])


@pytest.mark.parametrize("mode", sorted(ENGINE_MODES))
def test_dispatch_and_wait_split_into_the_host_path_by_chunk(mode):
    """Every `decode.dispatch` holds one `decode.stage`, then one
    `decode.enqueue`; every `decode.sync_wait` one `decode.device_wait`,
    then its `decode.readback`s; all of them carry the program's
    `chunk`, and every chunk dispatched is waited on once (but a
    pipelined engine's last, left in flight when the work ran out)."""
    from collections import Counter

    tr = Tracer()
    cfg, eng = _tiny_engine(tracer=tr, **ENGINE_MODES[mode])
    _serve_some(eng, cfg)
    spans = [e for e in tr.events() if e["ph"] == "X"]
    disp = [e for e in spans if e["name"] == "decode.dispatch"]
    waits = [e for e in spans if e["name"] == "decode.sync_wait"]
    assert disp and waits
    for d in disp:
        kids = _host_path_children(d, spans)
        assert [e["name"] for e in kids] \
            == ["decode.stage", "decode.enqueue"], kids
        assert {e["args"]["chunk"] for e in kids} == {d["args"]["chunk"]}
    for w in waits:
        names = [e["name"] for e in _host_path_children(w, spans)]
        assert names[0] == "decode.device_wait" and len(names) >= 2 \
            and set(names[1:]) == {"decode.readback"}, names
        assert {e["args"]["chunk"] for e in _host_path_children(
            w, spans)} == {w["args"]["chunk"]}
    waited = Counter(w["args"]["chunk"] for w in waits)
    assert max(waited.values()) == 1
    dispatched = {d["args"]["chunk"] for d in disp}
    assert dispatched - set(waited) <= (
        {max(dispatched)} if mode == "pipelined" else set())
    # no host-path span outside a dispatch or a wait
    parents = disp + waits
    assert all(any(_inside(e, p) for p in parents)
               for e in spans if e["name"] in HOST_PATH)


def _mellum_engine(tracer):
    from paddle_tpu.models import MellumConfig, mellum
    from paddle_tpu.serving import ContinuousBatchingEngine

    cfg = MellumConfig.tiny()
    p = mellum.init_serving_params(cfg, seed=7, dtype="float32")
    return cfg, ContinuousBatchingEngine(
        cfg, p, slots=2, prompt_bucket=16, block_size=8, max_prompt_len=64,
        max_new_tokens=8, token_budget=16, steps_per_sync=4,
        dtype="float32", logprobs=True, tracer=tracer)


@pytest.mark.parametrize("engine", ["unified", "unified_logprobs", "split",
                                    "routed_logprobs"])
def test_transfers_are_the_programs_arguments_and_outputs(engine):
    """Each served program crosses the host boundary once each way (PR 38):
    `decode.stage` moves one array (`h2d` 1), the packed buffer of the
    program's host inputs, and the program's `decode.readback` one array
    (`d2h` 1), the packed vector of its host-visible outputs —
    log-probabilities, routed layers' counts and a mixed step's first token
    among them. The bytes are the layouts' sizes, which are the sizes of the
    program's packed argument and first output."""
    import jax

    tr = Tracer()
    if engine == "routed_logprobs":
        cfg, eng = _mellum_engine(tr)
        assert eng._routed and eng.logprobs
    else:
        cfg, eng = _tiny_engine(
            tracer=tr, logprobs=engine.endswith("logprobs"),
            **ENGINE_MODES[engine.split("_")[0]])
    _serve_some(eng, cfg)
    want = {}
    for kind, fn, args in [("decode", eng._decode,
                            eng._decode_example_args()),
                           ("mixed", eng._unified,
                            eng._unified_example_args()
                            if eng.unified else None)]:
        if fn is None:
            continue
        io_in, io_out = eng._io[kind]
        packed_out = jax.eval_shape(fn, *args)[0]
        assert args[3].shape == (io_in.size,) and args[3].dtype == np.int32
        assert packed_out.shape == (io_out.size,) \
            and packed_out.dtype == np.int32
        want[kind] = ((1, 4 * io_in.size), (1, 4 * io_out.size))
    spans = [e for e in tr.events() if e["ph"] == "X"]
    kind = {e["args"]["chunk"]: "mixed" if e["args"].get("prefill_window")
            else "decode" for e in spans if e["name"] == "decode.dispatch"}
    assert set(kind.values()) == set(want)
    back = {}
    for e in spans:
        if e["name"] == "decode.readback":
            n, b = back.get(e["args"]["chunk"], (0, 0))
            back[e["args"]["chunk"]] = (n + e["args"]["d2h"],
                                        b + e["args"]["d2h_bytes"])
    for e in spans:
        if e["name"] == "decode.stage":
            k = kind[e["args"]["chunk"]]
            h2d, d2h = want[k]
            assert (e["args"]["h2d"], e["args"]["h2d_bytes"]) == h2d, k
            assert back[e["args"]["chunk"]] == d2h, k
    if eng.unified:
        # the mixed step hands back more than the decode chunk: its
        # window's routed counts, its first token, and the first token's
        # log-probability
        from paddle_tpu.models.llama import MOE_COUNTS

        _, dec = eng._io["decode"]
        _, mix = eng._io["mixed"]
        assert mix.size - dec.size == 1 + eng.logprobs \
            + eng._routed * len(MOE_COUNTS)


@pytest.mark.parametrize("mode", ["unified", "pipelined"])
def test_a_warmed_step_splits_no_key_and_makes_no_scalar_on_the_host(
        mode, monkeypatch):
    """Once the programs are compiled, serving runs no device program on
    the host's side but the served ones: no `jax.random.split` (the
    programs split the key they are handed) and no scalar made into an
    array (temperature and top-p are device scalars made once)."""
    import jax
    import jax.numpy as jnp

    cfg, eng = _tiny_engine(do_sample=True, temperature=0.7, top_p=0.9,
                            **ENGINE_MODES[mode])
    _serve_some(eng, cfg, seed=5)               # compiles every program
    calls = {"split": 0, "scalar": 0}
    split, asarray = jax.random.split, jnp.asarray

    def counted_split(*a, **k):
        calls["split"] += 1
        return split(*a, **k)

    def counted_asarray(x, *a, **k):
        calls["scalar"] += np.ndim(x) == 0
        return asarray(x, *a, **k)

    monkeypatch.setattr(jax.random, "split", counted_split)
    monkeypatch.setattr(jnp, "asarray", counted_asarray)
    before = eng.compile_stats()
    steps0 = eng.device_steps
    _serve_some(eng, cfg)
    assert eng.device_steps > steps0 and eng.prefill_chunks
    assert calls == {"split": 0, "scalar": 0}
    assert eng.compile_stats() == before


@pytest.mark.parametrize("mode", ["unified", "split", "pipelined"])
def test_the_tracer_changes_no_token_and_no_program(mode):
    """One code path: with the Tracer on the engine serves the same tokens
    from the same compiled programs as with it off."""
    got = []
    for tracer in (False, Tracer()):
        cfg, eng = _tiny_engine(tracer=tracer, **ENGINE_MODES[mode])
        reqs = _serve_some(eng, cfg)
        got.append(([r.tokens for r in reqs], eng.compile_stats()))
    assert got[0] == got[1]


def test_clock_anchor_lays_a_span_on_the_profiler_session(tmp_path):
    """The exported JSON's `clock_anchor` and the session's
    `profile_start_time` put a Tracer span on the device trace's clock,
    within 1 ms of its own annotation in the `.xplane.pb`."""
    import glob
    import os

    import jax

    tr = Tracer()
    anchor0 = tr.clock_anchor
    time.sleep(0.002)
    tr.clear()                      # a fresh anchor with a fresh buffer
    assert tr.clock_anchor["time_ns"] > anchor0["time_ns"]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path / "tb"), profiler_options=opts)
    try:
        for i in range(5):
            with tr.span("clock.probe", i=i):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    with open(tr.export(str(tmp_path / "t.json"))) as f:
        doc = json.load(f)
    anchor = doc["metadata"]["clock_anchor"]
    assert anchor == tr.clock_anchor
    (path,) = glob.glob(os.path.join(str(tmp_path / "tb"), "plugins",
                                     "profile", "*", "*.xplane.pb"))
    start = obs_trace.profile_start_time(path)
    got = sorted(_profiler_host_events(str(tmp_path / "tb"))["clock.probe"],
                 key=lambda x: x[1])
    mine = sorted((e for e in doc["traceEvents"]
                   if e["name"] == "clock.probe"), key=lambda e: e["ts"])
    assert len(got) == len(mine) == 5
    # a span's stamps sit just inside its annotation's; the median keeps a
    # probe the host preempted between the two from failing the clock
    errs = sorted(max(abs(obs_trace.session_ns(e["ts"], anchor, start) - a),
                      abs(obs_trace.session_ns(e["ts"] + e["dur"], anchor,
                                               start) - b))
                  for (_, a, b, _), e in zip(got, mine))
    assert errs[2] < 1e6, errs


if __name__ == "__main__":
    unittest.main()
