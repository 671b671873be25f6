"""Static memory auditor (ISSUE 10): jaxpr liveness peak-HBM estimates,
donation-miss detection (TPU701), budget/bloat rules (TPU702/703), the
engine fleet audit, the Model.fit hook, rule-config plumbing, and the
CLI `--memory --format json` schema CI gates on."""
import dataclasses
import json
import os
import subprocess
import sys
import unittest

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.analysis import (Severity, analyze, audit_graph,
                                 audit_memory, memory, trace_for_memory)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import ContinuousBatchingEngine

KB = 1024


def _pool_chunk(n_pages=128, steps=4):
    """Toy decode-chunk shape: a pool threaded through a scan with an
    in-place page update per step. Pool bytes = n_pages*2*16*16*4."""
    pool0 = jnp.zeros((n_pages, 2, 16, 16), jnp.float32)

    def chunk(pool, tok):
        def body(carry, _):
            pool, tok = carry
            pool = pool.at[tok % n_pages].set(pool[tok % n_pages] + 1.0)
            return (pool, tok + 1), tok

        (pool, tok), ys = jax.lax.scan(body, (pool, tok), None,
                                       length=steps)
        return pool, ys

    return chunk, pool0, jnp.asarray(0)


class TestLivenessPass(unittest.TestCase):
    def test_peak_simple_chain(self):
        """x -> y -> z: at the second eqn x (pinned input), y (operand)
        and z (result) are all live — peak is exactly 3 buffers."""
        nb = 256 * 4  # f32[256]

        def f(x):
            y = x * 2.0
            return y + 1.0

        rep = audit_memory(f, jnp.zeros((256,), jnp.float32))
        self.assertEqual(rep.peak_bytes, 3 * nb)
        self.assertEqual(rep.n_eqns, 2)

    def test_dead_value_freed(self):
        """A value consumed early stops counting: y dies at eqn 1, so
        the later adds never see it."""
        def f(x):
            y = x * 2.0          # dies immediately below
            z = y + 1.0
            for _ in range(4):
                z = z + 1.0
            return z

        rep = audit_memory(f, jnp.zeros((256,), jnp.float32))
        # input + two chain buffers live at any add
        self.assertEqual(rep.peak_bytes, 3 * 256 * 4)

    def test_donated_pool_counted_once(self):
        chunk, pool0, tok = _pool_chunk()
        rep = audit_memory(jax.jit(chunk, donate_argnums=(0,)), pool0,
                           tok)
        self.assertLess(rep.peak_bytes, int(1.2 * pool0.nbytes))
        self.assertEqual(rep.donation["donated_bytes"], pool0.nbytes)
        self.assertEqual(rep.donation["misses"], [])

    def test_undonated_pool_doubles_and_reports_miss(self):
        chunk, pool0, tok = _pool_chunk()
        rep = audit_memory(jax.jit(chunk), pool0, tok)
        self.assertGreaterEqual(rep.peak_bytes, 2 * pool0.nbytes)
        misses = [m for m in rep.donation["misses"]
                  if m["bytes"] == pool0.nbytes]
        self.assertEqual(len(misses), 1)
        self.assertEqual(misses[0]["input_index"], 0)

    def test_reshape_is_a_view(self):
        """A reshaped big buffer must not double-count (XLA bitcast)."""
        def f(x):
            y = x.reshape(64, 32)
            return jnp.sum(y, axis=1), x

        nb = 64 * 32 * 4
        rep = audit_memory(f, jnp.zeros((2048,), jnp.float32))
        self.assertLess(rep.peak_bytes, 2 * nb)

    def test_shard_map_per_chip_accounting(self):
        """Inside shard_map, sharded operands count their LOCAL shard
        bytes; replicated operands count whole; rep.mp records the mesh
        size."""
        from jax.sharding import Mesh, PartitionSpec as P

        from jax import shard_map

        mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))

        def body(x, w):
            return x * 2.0 + jnp.sum(w)

        sm = shard_map(body, mesh=mesh, in_specs=(P("mp"), P()),
                       out_specs=P("mp"), check_vma=False)
        x = jnp.zeros((64, 128), jnp.float32)   # 32 KB -> 16 KB/chip
        w = jnp.zeros((128,), jnp.float32)      # replicated, 512 B
        rep = audit_memory(sm, x, w)
        self.assertEqual(rep.mp, 2)
        x_buf = next(b for b in rep.buffers if b.label == "in[0]")
        self.assertEqual(x_buf.bytes, x.nbytes // 2)
        w_buf = next(b for b in rep.buffers if b.label == "in[1]")
        self.assertEqual(w_buf.bytes, w.nbytes)

    def test_report_to_json_stable(self):
        chunk, pool0, tok = _pool_chunk()
        fn = jax.jit(chunk, donate_argnums=(0,))
        a = audit_memory(fn, pool0, tok).to_json()
        b = audit_memory(fn, pool0, tok).to_json()
        self.assertEqual(a, b)
        d = json.loads(a)
        for key in ("target", "peak_hbm_bytes", "peak_at", "per_chip",
                    "mp", "n_eqns", "n_buffers", "donation",
                    "peak_buffers", "timeline"):
            self.assertIn(key, d)
        self.assertTrue(all({"t", "where", "live_bytes"} <= set(pt)
                            for pt in d["timeline"]))


class TestMemoryRules(unittest.TestCase):
    def test_tpu701_fires_on_undonated_toy_decode(self):
        chunk, pool0, tok = _pool_chunk()  # 128-page pool = 128 KiB
        g = trace_for_memory(jax.jit(chunk), pool0, tok)
        report = analyze(None, graph=g, rules=["TPU701"])
        hits = report.by_rule().get("TPU701", [])
        self.assertEqual(len(hits), 1)
        self.assertEqual(hits[0].severity, Severity.ERROR)

    def test_tpu701_silent_when_donated(self):
        chunk, pool0, tok = _pool_chunk()
        g = trace_for_memory(jax.jit(chunk, donate_argnums=(0,)), pool0,
                             tok)
        report = analyze(None, graph=g, rules=["TPU701"])
        self.assertEqual(len(report), 0)

    def test_tpu701_needs_donation_info(self):
        """The generic lint trace (no jit-option knowledge) must not
        guess: same program through plain analyze() stays silent."""
        chunk, pool0, tok = _pool_chunk()
        report = analyze(chunk, pool0, tok, rules=["TPU701"])
        self.assertEqual(len(report), 0)

    def test_tpu701_min_bytes_filters_scheduling_vectors(self):
        def f(lens):
            stepped = lens + 1   # lens dead strictly before the output
            return stepped * 2   # same shape/dtype as lens, 32 bytes

        g = trace_for_memory(jax.jit(f), jnp.zeros((8,), jnp.int32))
        self.assertEqual(
            len(analyze(None, graph=g, rules=["TPU701"])), 0)
        tightened = analyze(None, graph=g, rules=["TPU701"],
                            rule_config={"TPU701.min_bytes": 1})
        self.assertEqual(len(tightened), 1)

    def test_tpu701_input_read_at_or_after_output_not_flagged(self):
        """An input still read when (or after) a same-aval output
        materializes is NOT a donation miss — XLA may have to copy
        either way, and an advisory ERROR must not guess."""
        def f(x):
            y = jnp.tanh(x)          # early same-aval output...
            return y, x * x.sum()    # ...but x is read by the LAST eqn

        g = trace_for_memory(jax.jit(f),
                             jnp.zeros((32768,), jnp.float32))
        self.assertEqual(
            len(analyze(None, graph=g, rules=["TPU701"])), 0)

    def test_tpu702_off_by_default_fires_with_budget(self):
        chunk, pool0, tok = _pool_chunk()
        g = trace_for_memory(jax.jit(chunk, donate_argnums=(0,)), pool0,
                             tok)
        self.assertEqual(len(analyze(None, graph=g, rules=["TPU702"])),
                         0)
        report = analyze(None, graph=g, rules=["TPU702"],
                         rule_config={"TPU702.hbm_budget_bytes": 1024})
        self.assertEqual(len(report), 1)
        self.assertEqual(report.diagnostics[0].severity,
                         Severity.WARNING)
        under = analyze(None, graph=g, rules=["TPU702"],
                        rule_config={"TPU702.hbm_budget_bytes": 1 << 30})
        self.assertEqual(len(under), 0)

    def test_tpu703_live_range_bloat(self):
        def f(x):
            big = x * 2.0            # held across the whole chain
            z = x[:8] * 1.0
            for _ in range(30):
                z = z + 1.0
            return z + big[:8]

        x = jnp.zeros((4096,), jnp.float32)
        report = analyze(f, x, rules=["TPU703"],
                         rule_config={"TPU703.min_bytes": 4096,
                                      "TPU703.max_live_eqns": 20})
        self.assertGreaterEqual(len(report), 1)
        self.assertIn("stays live", report.diagnostics[0].message)
        # defaults (1 MiB / 150 eqns) stay silent on this toy
        self.assertEqual(len(analyze(f, x, rules=["TPU703"])), 0)


class TestRuleConfigPlumbing(unittest.TestCase):
    def test_prefixed_keys_route_to_one_rule(self):
        from paddle_tpu.analysis.rules import rule_config_for

        cfg = {"max_collective_bytes": 1, "TPU702.hbm_budget_bytes": 2,
               "TPU701.min_bytes": 3}
        self.assertEqual(rule_config_for("TPU702", cfg),
                         {"max_collective_bytes": 1,
                          "hbm_budget_bytes": 2})
        self.assertEqual(rule_config_for("TPU701", cfg),
                         {"max_collective_bytes": 1, "min_bytes": 3})

    def test_unknown_prefix_raises(self):
        with self.assertRaisesRegex(ValueError, "TPU999"):
            analyze(lambda x: x, jnp.zeros((4,)),
                    rule_config={"TPU999.knob": 1})

    def test_cli_value_parsing(self):
        from paddle_tpu.analysis.__main__ import _parse_rule_config

        cfg = _parse_rule_config(
            ["TPU702.hbm_budget_bytes=1048576", "ratio=0.5",
             "flag=true", "name=abc"])
        self.assertEqual(cfg["TPU702.hbm_budget_bytes"], 1048576)
        self.assertEqual(cfg["ratio"], 0.5)
        self.assertIs(cfg["flag"], True)
        self.assertEqual(cfg["name"], "abc")
        with self.assertRaises(SystemExit):
            _parse_rule_config(["nonsense"])

    def test_report_to_json_schema(self):
        report = analyze(lambda x: x @ x, jnp.zeros((100, 100)),
                         rules=["TPU101"])
        d = json.loads(report.to_json())
        self.assertEqual(sorted(d), ["counts", "diagnostics", "target"])
        self.assertEqual(d["counts"]["warning"], len(d["diagnostics"]))
        for diag in d["diagnostics"]:
            self.assertEqual(
                sorted(diag),
                ["hint", "message", "rule", "severity", "where"])


def _tiny_engine(mp=1, **kw):
    cfg = dataclasses.replace(LlamaConfig.tiny(), num_key_value_heads=2)
    paddle.seed(7)
    model = LlamaForCausalLM(cfg)
    eng = ContinuousBatchingEngine(
        cfg, dict(model.raw_state()), slots=4, prompt_bucket=16,
        max_prompt_len=32, max_new_tokens=8, block_size=16,
        steps_per_sync=4, prefill_batch=2, serving_mp=mp, **kw)
    return eng


def _per_chip_ref(eng):
    """Hand reference for the decode program's residency: per-chip
    param bytes + per-chip pool bytes (donation folded in — pools count
    ONCE), activations excluded (the ≤10% slack they must fit in)."""
    return memory.pytree_local_bytes(eng.p) \
        + memory.pytree_local_bytes((eng.kcs, eng.vcs))


class TestEngineAudit(unittest.TestCase):
    def test_decode_peak_within_10pct_mp1(self):
        eng = _tiny_engine()
        rep = audit_memory(eng._decode, *eng._decode_example_args(),
                           name="decode")
        ref = _per_chip_ref(eng)
        self.assertLessEqual(abs(rep.peak_bytes - ref) / ref, 0.10,
                             f"est {rep.peak_bytes} vs ref {ref}")

    def test_decode_peak_within_10pct_per_chip_mp2(self):
        eng = _tiny_engine(mp=2)
        rep = audit_memory(eng._decode, *eng._decode_example_args(),
                           name="decode")
        ref = _per_chip_ref(eng)  # local shards: pools + params / chip
        self.assertEqual(rep.mp, 2)
        self.assertLessEqual(abs(rep.peak_bytes - ref) / ref, 0.10,
                             f"est {rep.peak_bytes} vs ref {ref}")
        # per-chip peak at mp=2 must undercut the mp=1 program's
        self.assertLess(rep.peak_bytes,
                        audit_memory(_tiny_engine()._decode,
                                     *eng._decode_example_args(),
                                     name="decode@1").peak_bytes)

    def test_warmed_programs_donation_clean_mp1_and_mp2(self):
        """The acceptance gate: every pool-threading program the engine
        warms is donation-clean — TPU701 silent across the whole cache
        at mp=1 AND mp=2."""
        # mp=1 audits the SPLIT fleet (decode + every prefill
        # variant), mp=2 the UNIFIED fleet (decode + the one mixed
        # prefill+decode program, ISSUE 14) — both must thread the
        # donated pools cleanly
        for mp, unified in ((1, False), (2, True)):
            eng = _tiny_engine(mp=mp, unified_step=unified)
            eng.warm([16, 32])
            fleet = eng.audit_memory()
            if unified:
                self.assertEqual(fleet["programs_audited"], 2)
                self.assertIn("unified", fleet["programs"])
            else:
                self.assertGreaterEqual(fleet["programs_audited"], 5)
            self.assertTrue(fleet["donation_clean"], fleet)
            for name, prog in fleet["programs"].items():
                self.assertEqual(prog["donation_misses"], 0, name)
                self.assertEqual(
                    [d for d in prog["diagnostics"]
                     if d["rule"] == "TPU701"], [], name)
                self.assertEqual(prog["donation_coverage"], 1.0)
            self.assertEqual(fleet["mp"], mp)
            self.assertIs(eng.metrics()["memory_audit"], fleet)

    def test_undonated_decode_program_fires_tpu701(self):
        """The same decode-chunk body jitted WITHOUT donate_argnums is
        the deliberate miss: TPU701 must fire on the pool pair. Pools
        sized past the rule's 64 KiB noise floor (the default engine's
        tiny 13-page pools are deliberately below it)."""
        eng = _tiny_engine(max_pages=260)
        undonated = jax.jit(
            eng._shard_program(eng._build_decode_chunk(), 6, 4))
        g = trace_for_memory(undonated, *eng._decode_example_args(),
                             name="undonated-decode")
        report = analyze(None, graph=g, rules=["TPU701"])
        hits = report.by_rule().get("TPU701", [])
        self.assertGreaterEqual(len(hits), 1)
        # and the residency penalty is visible in the pass itself
        rep = audit_graph(g)
        donated_rep = audit_memory(eng._decode,
                                   *eng._decode_example_args(),
                                   name="decode")
        pool_bytes = memory.pytree_local_bytes((eng.kcs, eng.vcs))
        self.assertGreaterEqual(rep.peak_bytes,
                                donated_rep.peak_bytes
                                + pool_bytes // 2)

    def test_budget_derivation_and_tpu702(self):
        """kv_pool_bytes-sized engines derive a TPU702 budget (pool
        budget + params + headroom): clean by construction, and an
        explicit tiny budget fires."""
        eng = _tiny_engine(kv_pool_bytes=1 << 20)
        clean = eng.audit_memory(programs=("decode",))
        self.assertGreater(clean["hbm_budget_bytes"],
                           clean["fleet_peak_hbm_bytes"])
        self.assertEqual(clean["n_diagnostics"], 0)
        tight = eng.audit_memory(hbm_budget_bytes=64 * KB,
                                 programs=("decode",))
        rules = [d["rule"]
                 for d in tight["programs"]["decode"]["diagnostics"]]
        self.assertIn("TPU702", rules)

    def test_warm_audit_hook_and_flag_composition(self):
        eng = _tiny_engine()
        eng.warm([16], audit_memory=True)
        self.assertIsNotNone(eng.metrics()["memory_audit"])
        # PADDLE_TPU_LINT composes: the lint switch implies the audit
        from paddle_tpu.analysis.memory import resolve_audit_memory

        prev = paddle.get_flags(["tpu_lint", "audit_memory"])
        try:
            paddle.set_flags({"tpu_lint": True, "audit_memory": False})
            self.assertTrue(resolve_audit_memory(None))
            paddle.set_flags({"tpu_lint": False})
            self.assertFalse(resolve_audit_memory(None))
            paddle.set_flags({"audit_memory": True})
            self.assertTrue(resolve_audit_memory(None))
            self.assertFalse(resolve_audit_memory(False))
        finally:
            paddle.set_flags({k.replace("FLAGS_", ""): v
                              for k, v in prev.items()})

    def test_audit_emits_observability_event(self):
        from paddle_tpu.observability import MetricsRegistry

        mt = MetricsRegistry()
        eng = _tiny_engine(metrics=mt)
        # a programs=-narrowed run is PARTIAL: it must not touch the
        # fleet sinks (a decode-only clean bill would mask a prefill
        # regression from monitoring)
        partial = eng.audit_memory(programs=("decode",))
        self.assertTrue(partial["partial"])
        self.assertEqual(mt.events("memory.audit"), [])
        self.assertIsNone(eng.metrics()["memory_audit"])
        # unknown filter names must raise, not report vacuously clean
        with self.assertRaisesRegex(ValueError, "decoed"):
            eng.audit_memory(programs=("decoed",))
        full = eng.audit_memory()
        self.assertFalse(full["partial"])
        events = mt.events("memory.audit")
        self.assertEqual(len(events), 1)
        self.assertGreater(events[0]["fleet_peak_hbm_bytes"], 0)
        snap = mt.snapshot()
        self.assertIn("predicted_peak_hbm_bytes", snap["gauges"])
        self.assertIs(eng.metrics()["memory_audit"], full)

    def test_tpu702_budget_must_be_integer(self):
        with self.assertRaisesRegex(ValueError, "hbm_budget_bytes"):
            analyze(lambda x: x + 1, jnp.zeros((4,)), rules=["TPU702"],
                    rule_config={"TPU702.hbm_budget_bytes": "32GiB"})


class TestFitAudit(unittest.TestCase):
    def test_fit_audit_memory_hook(self):
        from paddle_tpu import nn, optimizer as opt

        paddle.seed(5)
        net = nn.Linear(4, 1)
        model = paddle.Model(net)
        model.prepare(optimizer=opt.Adam(learning_rate=0.01,
                                         parameters=net.parameters()),
                      loss=lambda out, y: ((out - y) ** 2).mean())
        rng = np.random.default_rng(0)
        batches = [(rng.normal(size=(4, 4)).astype(np.float32),
                    rng.normal(size=(4, 1)).astype(np.float32))]
        model.fit(batches, epochs=1, verbose=0, audit_memory=True)
        self.assertIsNotNone(model.memory_audit)
        self.assertGreater(model.memory_audit["peak_hbm_bytes"], 0)
        self.assertEqual(model.memory_audit["target"], "fit.forward")

    def test_fit_audit_off_by_default(self):
        from paddle_tpu import nn, optimizer as opt

        paddle.seed(5)
        net = nn.Linear(4, 1)
        model = paddle.Model(net)
        model.prepare(optimizer=opt.Adam(learning_rate=0.01,
                                         parameters=net.parameters()),
                      loss=lambda out, y: ((out - y) ** 2).mean())
        batches = [(np.zeros((4, 4), np.float32),
                    np.zeros((4, 1), np.float32))]
        model.fit(batches, epochs=1, verbose=0)
        self.assertIsNone(model.memory_audit)


class TestCLIMemoryJSON(unittest.TestCase):
    def test_cli_memory_json_schema(self):
        """The CI gate (ISSUE 10 satellite): `python -m
        paddle_tpu.analysis --memory --format json` over the tiny llama
        decode program emits one valid JSON object with the documented
        schema and exits 0."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.analysis", "--memory",
             "--format", "json"],
            capture_output=True, text=True, env=env,
            cwd=os.path.dirname(os.path.dirname(__file__)), timeout=300)
        self.assertEqual(proc.returncode, 0, proc.stderr[-2000:])
        d = json.loads(proc.stdout)
        self.assertEqual(sorted(d),
                         ["counts", "diagnostics", "memory", "target"])
        self.assertEqual(d["counts"]["error"], 0)
        m = d["memory"]
        for key in ("peak_hbm_bytes", "peak_at", "per_chip", "mp",
                    "n_eqns", "n_buffers", "donation", "peak_buffers",
                    "timeline", "input_bytes", "output_bytes"):
            self.assertIn(key, m)
        self.assertGreater(m["peak_hbm_bytes"], 0)
        self.assertEqual(m["mp"], 1)
        self.assertIsInstance(m["donation"]["misses"], list)
        for b in m["peak_buffers"]:
            self.assertLessEqual(
                {"label", "shape", "dtype", "bytes", "kind"},
                set(b))
        # the decode program's donated pools must be visible
        self.assertGreater(m["donation"]["donated_bytes"], 0)


if __name__ == "__main__":
    unittest.main()
