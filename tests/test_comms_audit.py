"""Static communication auditor (ISSUE 11): jaxpr bytes-on-wire pass +
per-chip collective cost model, loop amplification, implicit-reshard
detection, TPU801/802/803 rules, the engine fleet audit, the Model.fit
dp-gradient hook, the TPU401 amplified-bytes dedupe, and the CLI
`--comms --format json` gate CI scripts against."""
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
from paddle_tpu.analysis import Severity, analyze, comms
from paddle_tpu.analysis.memory import trace_auto, trace_for_memory
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import ContinuousBatchingEngine


def _smap(fn, n, in_specs=None, out_specs=None):
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()[:n]), ("mp",))
    return shard_map(fn, mesh=mesh,
                     in_specs=P("mp") if in_specs is None else in_specs,
                     out_specs=P("mp") if out_specs is None
                     else out_specs, check_vma=False)


class TestCostModel(unittest.TestCase):
    """Hand-computed per-chip wire bytes: ring all-reduce moves
    2(n-1)/n of the payload, all-gather / reduce-scatter (n-1)/n of the
    full / local payload."""

    def _events(self, fn, x, n):
        rep = comms.audit_comms(_smap(fn, n), x)
        return {e.kind: e for e in rep.events}, rep

    def test_psum_all_gather_reduce_scatter_mp2(self):
        def f(x):
            g = jax.lax.all_gather(x, "mp", axis=0, tiled=True)
            s = jax.lax.psum(x, "mp")
            r = jax.lax.psum_scatter(x, "mp", scatter_dimension=0,
                                     tiled=True)
            return g[:x.shape[0]] + s + jnp.sum(r)

        x = jnp.zeros((8, 128), jnp.float32)   # local [4,128] = 2 KiB
        local = 4 * 128 * 4
        ev, rep = self._events(f, x, 2)
        self.assertEqual(ev["psum"].wire_bytes, local)           # 2*1/2
        self.assertEqual(ev["all_gather"].wire_bytes, local)     # 1/2*2x
        self.assertEqual(ev["reduce_scatter"].wire_bytes, local // 2)
        self.assertTrue(all(e.n_devices == 2 for e in rep.events))
        self.assertEqual(rep.mp, 2)
        self.assertEqual(rep.total_wire_bytes, local + local + local // 2)

    def test_psum_all_gather_reduce_scatter_mp4(self):
        def f(x):
            g = jax.lax.all_gather(x, "mp", axis=0, tiled=True)
            s = jax.lax.psum(x, "mp")
            r = jax.lax.psum_scatter(x, "mp", scatter_dimension=0,
                                     tiled=True)
            return g[:x.shape[0]] + s + jnp.sum(r)

        x = jnp.zeros((16, 128), jnp.float32)  # local [4,128] = 2 KiB
        local = 4 * 128 * 4
        ev, rep = self._events(f, x, 4)
        self.assertEqual(ev["psum"].wire_bytes,
                         int(2 * 3 / 4 * local))
        self.assertEqual(ev["all_gather"].wire_bytes,
                         int(3 / 4 * 4 * local))
        self.assertEqual(ev["reduce_scatter"].wire_bytes,
                         int(3 / 4 * local))
        self.assertEqual(rep.mp, 4)

    def test_single_chip_program_has_zero_events(self):
        rep = comms.audit_comms(lambda x: x * 2.0 + jnp.sum(x),
                                jnp.zeros((64,), jnp.float32))
        self.assertEqual(rep.events, [])
        self.assertEqual(rep.total_wire_bytes, 0)
        self.assertEqual(rep.mp, 1)

    def test_float_payload_excludes_int(self):
        def f(q, idx):
            g = jax.lax.all_gather(q, "mp", axis=0, tiled=True)
            i = jax.lax.all_gather(idx, "mp", axis=0, tiled=True)
            return g, i

        from jax.sharding import PartitionSpec as P

        rep = comms.audit_comms(
            _smap(f, 2, in_specs=(P("mp"), P("mp")),
                  out_specs=(P(None), P(None))),
            jnp.zeros((8, 64), jnp.bfloat16),
            jnp.zeros((8, 64), jnp.int32))
        by_dtype = {e.dtype: e for e in rep.events}
        self.assertGreater(by_dtype["bfloat16"].float_payload_bytes, 0)
        self.assertEqual(by_dtype["int32"].float_payload_bytes, 0)
        # wire bytes count regardless of dtype (the ICI carries both)
        self.assertGreater(by_dtype["int32"].wire_bytes, 0)


class TestAmplification(unittest.TestCase):
    def test_scan_amplifies_per_layer_collectives(self):
        """One collective per layer x scan length: n_layers sites, each
        with count = steps — the '1 all-gather per layer x 32 layers'
        accounting, first-class."""
        n_layers, steps = 3, 5

        def loop(x):
            def step(c, _):
                for _layer in range(n_layers):
                    c = c + jax.lax.psum(c * 1.0, "mp")
                return c, None

            c, _ = jax.lax.scan(step, x, None, length=steps)
            return c

        rep = comms.audit_comms(_smap(loop, 2),
                                jnp.zeros((8, 128), jnp.float32))
        self.assertEqual(rep.n_collective_sites, n_layers)
        self.assertEqual(rep.n_collectives, n_layers * steps)
        self.assertTrue(all(e.count == steps and e.in_loop
                            for e in rep.events))
        per_occurrence = rep.events[0].wire_bytes
        self.assertEqual(rep.total_wire_bytes,
                         n_layers * steps * per_occurrence)

    def test_nested_scan_multiplies_trips(self):
        def inner(x):
            def istep(c, _):
                return c + jax.lax.psum(c * 1.0, "mp"), None
            c, _ = jax.lax.scan(istep, x, None, length=4)
            return c

        def outer(x):
            def ostep(c, _):
                return inner(c), None
            c, _ = jax.lax.scan(ostep, x, None, length=3)
            return c

        rep = comms.audit_comms(_smap(outer, 2),
                                jnp.zeros((8, 16), jnp.float32))
        self.assertEqual(rep.events[0].count, 12)

    def test_while_body_marked_in_loop(self):
        def loop(x):
            def cond(c):
                return jnp.sum(c[0]) < 100.0

            def body(c):
                x_, = c
                return (x_ + jax.lax.psum(x_ * 1.0, "mp"),)

            return jax.lax.while_loop(cond, body, (x,))[0]

        rep = comms.audit_comms(_smap(loop, 2),
                                jnp.zeros((8, 16), jnp.float32))
        self.assertEqual(len(rep.collectives), 1)
        self.assertTrue(rep.collectives[0].in_loop)
        self.assertEqual(rep.collectives[0].count, 1)  # trip unknown


class TestShardMapAttribution(unittest.TestCase):
    def test_per_chip_local_bytes_and_axis_split(self):
        """Inside shard_map the operand avals are the LOCAL shard's —
        per-chip math by construction — and totals split per axis."""
        from jax.sharding import Mesh, PartitionSpec as P

        from jax import shard_map

        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("dp", "mp"))

        def f(x):
            a = jax.lax.psum(x, "mp")       # local [4, 64] f32 = 1 KiB
            b = jax.lax.psum(x, "dp")
            return a + b

        sm = shard_map(f, mesh=mesh, in_specs=P("dp", ("mp",)),
                       out_specs=P("dp", ("mp",)), check_vma=False)
        rep = comms.audit_comms(sm, jnp.zeros((8, 128), jnp.float32))
        local = 4 * 64 * 4
        per_axis = rep.per_axis()
        self.assertEqual(per_axis["mp"], local)   # 2*(1/2)*local
        self.assertEqual(per_axis["dp"], local)
        for e in rep.events:
            self.assertEqual(e.shape, (4, 64))    # local shard aval
            self.assertEqual(e.n_devices, 2)


class TestImplicitReshard(unittest.TestCase):
    def _mesh(self):
        from jax.sharding import Mesh

        return Mesh(np.array(jax.devices()[:2]), ("mp",))

    def test_pjit_boundary_disagreement_detected(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self._mesh()
        producer = jax.jit(lambda x: x + 1.0,
                           out_shardings=NamedSharding(mesh, P("mp")))
        consumer = jax.jit(lambda x: x * 2.0,
                           in_shardings=NamedSharding(mesh,
                                                      P(None, "mp")),
                           out_shardings=NamedSharding(mesh,
                                                       P(None, "mp")))

        def outer(x):
            return consumer(producer(x))

        rep = comms.audit_comms(jax.jit(outer),
                                jnp.zeros((8, 128), jnp.float32))
        self.assertEqual(len(rep.reshards), 1)
        r = rep.reshards[0]
        self.assertTrue(r.implicit)
        # global 4 KiB, dst sharded 2 ways -> local 2 KiB, (n-1)/n = 1/2
        self.assertEqual(r.wire_bytes, 1024)
        self.assertIn("->", r.detail)

    def test_agreeing_boundary_clean(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self._mesh()
        sh = NamedSharding(mesh, P("mp"))
        producer = jax.jit(lambda x: x + 1.0, out_shardings=sh)
        consumer = jax.jit(lambda x: x * 2.0, in_shardings=sh,
                           out_shardings=sh)

        rep = comms.audit_comms(
            jax.jit(lambda x: consumer(producer(x))),
            jnp.zeros((8, 128), jnp.float32))
        self.assertEqual(rep.reshards, [])

    def test_replicated_source_costs_nothing(self):
        """replicated -> sharded is a local slice, not communication."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self._mesh()
        producer = jax.jit(lambda x: x + 1.0,
                           out_shardings=NamedSharding(mesh, P()))
        consumer = jax.jit(lambda x: x * 2.0,
                           in_shardings=NamedSharding(mesh, P("mp")),
                           out_shardings=NamedSharding(mesh, P("mp")))

        rep = comms.audit_comms(
            jax.jit(lambda x: consumer(producer(x))),
            jnp.zeros((8, 128), jnp.float32))
        self.assertEqual(rep.reshards, [])

    def test_shard_map_boundary_disagreement_detected(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self._mesh()
        producer = jax.jit(lambda x: x + 1.0,
                           out_shardings=NamedSharding(mesh, P("mp")))
        body = _smap(lambda x: x * 2.0, 2, in_specs=P(None, "mp"),
                     out_specs=P(None, "mp"))

        rep = comms.audit_comms(jax.jit(lambda x: body(producer(x))),
                                jnp.zeros((8, 128), jnp.float32))
        self.assertEqual(len(rep.reshards), 1)


class TestRules(unittest.TestCase):
    """TPU801/802/803 fire-and-silent pairs."""

    def _loop_graph(self, shape=(8, 4096), steps=8):
        def loop(x):
            def step(c, _):
                return c + jax.lax.psum(c * 1.0, "mp"), None
            c, _ = jax.lax.scan(step, x, None, length=steps)
            return c

        return trace_auto(_smap(loop, 2),
                          jnp.zeros(shape, jnp.float32))

    def test_tpu801_fires_on_amplified_loop_collective(self):
        g = self._loop_graph()
        # local [4,4096] f32 = 64 KiB -> wire 64 KiB/iter x 8 = 512 KiB
        r = analyze(None, graph=g, rules=["TPU801"],
                    rule_config={"TPU801.max_step_wire_bytes": 1 << 18})
        hits = r.by_rule().get("TPU801", [])
        self.assertEqual(len(hits), 1)
        self.assertEqual(hits[0].severity, Severity.WARNING)
        self.assertIn("8 loop iterations", hits[0].message)

    def test_tpu801_silent_under_budget_and_at_top_level(self):
        g = self._loop_graph()
        self.assertEqual(len(analyze(None, graph=g, rules=["TPU801"])),
                         0)  # default 32 MiB budget
        # a top-level (unamplified) collective never fires TPU801
        g_top = trace_auto(_smap(lambda x: jax.lax.psum(x * 1.0, "mp"),
                                 2),
                           jnp.zeros((8, 1 << 22), jnp.float32))
        self.assertEqual(
            len(analyze(None, graph=g_top, rules=["TPU801"],
                        rule_config={"TPU801.max_step_wire_bytes": 1})),
            0)

    def test_tpu802_fires_and_silent_pair(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))
        producer = jax.jit(lambda x: x + 1.0,
                           out_shardings=NamedSharding(mesh, P("mp")))
        consumer = jax.jit(lambda x: x * 2.0,
                           in_shardings=NamedSharding(mesh,
                                                      P(None, "mp")),
                           out_shardings=NamedSharding(mesh,
                                                       P(None, "mp")))
        # 512 KiB global -> 128 KiB wire, over the 64 KiB floor
        g = trace_auto(jax.jit(lambda x: consumer(producer(x))),
                       jnp.zeros((512, 256), jnp.float32))
        r = analyze(None, graph=g, rules=["TPU802"])
        hits = r.by_rule().get("TPU802", [])
        self.assertEqual(len(hits), 1)
        self.assertIn("never wrote", hits[0].message)
        # agreeing shardings: silent
        same = jax.jit(lambda x: x * 2.0,
                       in_shardings=NamedSharding(mesh, P("mp")),
                       out_shardings=NamedSharding(mesh, P("mp")))
        g2 = trace_auto(jax.jit(lambda x: same(producer(x))),
                        jnp.zeros((512, 256), jnp.float32))
        self.assertEqual(len(analyze(None, graph=g2,
                                     rules=["TPU802"])), 0)

    def test_tpu802_min_bytes_floors_small_reshards(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))
        producer = jax.jit(lambda x: x + 1.0,
                           out_shardings=NamedSharding(mesh, P("mp")))
        consumer = jax.jit(lambda x: x * 2.0,
                           in_shardings=NamedSharding(mesh,
                                                      P(None, "mp")),
                           out_shardings=NamedSharding(mesh,
                                                       P(None, "mp")))
        g = trace_auto(jax.jit(lambda x: consumer(producer(x))),
                       jnp.zeros((8, 128), jnp.float32))  # 1 KiB wire
        self.assertEqual(len(analyze(None, graph=g,
                                     rules=["TPU802"])), 0)
        tightened = analyze(None, graph=g, rules=["TPU802"],
                            rule_config={"TPU802.min_bytes": 1})
        self.assertEqual(len(tightened), 1)

    def test_tpu803_fires_on_float_silent_on_int8(self):
        def f(x):
            return jax.lax.all_gather(x, "mp", axis=0, tiled=True)

        from jax.sharding import PartitionSpec as P

        big_f = jnp.zeros((8, 1 << 17), jnp.bfloat16)  # 2 MiB payload
        g = trace_auto(_smap(f, 2, out_specs=P(None)), big_f)
        r = analyze(None, graph=g, rules=["TPU803"])
        hits = r.by_rule().get("TPU803", [])
        self.assertEqual(len(hits), 1)
        self.assertIn("int8", hits[0].hint)
        # the already-quantized payload is the rule's GOAL state
        big_i = jnp.zeros((8, 1 << 18), jnp.int8)      # 2 MiB of int8
        g2 = trace_auto(_smap(f, 2, out_specs=P(None)), big_i)
        self.assertEqual(len(analyze(None, graph=g2,
                                     rules=["TPU803"])), 0)
        # under the threshold: silent; amplification counts toward it
        small = jnp.zeros((8, 1 << 12), jnp.bfloat16)  # 64 KiB
        g3 = trace_auto(_smap(f, 2, out_specs=P(None)), small)
        self.assertEqual(len(analyze(None, graph=g3,
                                     rules=["TPU803"])), 0)

    def test_tpu803_amplified_payload_crosses_threshold(self):
        """A per-iteration payload under min_bytes fires once the scan
        amplification pushes the total over — the in-scan collective
        accounting TPU401 used to under-report."""
        def loop(x):
            def step(c, _):
                return c + jax.lax.psum(c * 1.0, "mp"), None
            c, _ = jax.lax.scan(step, x, None, length=64)
            return c

        # local 32 KiB/iter x 64 = 2 MiB amplified
        g = trace_auto(_smap(loop, 2),
                       jnp.zeros((8, 2048), jnp.float32))
        r = analyze(None, graph=g, rules=["TPU803"])
        self.assertEqual(len(r.by_rule().get("TPU803", [])), 1)
        self.assertIn("x 64 iterations", r.diagnostics[0].message)

    def test_tpu401_counts_amplified_bytes(self):
        """The dedupe satellite: TPU401's max_collective_bytes now
        compares the AMPLIFIED payload via the shared comms inventory,
        so an in-scan collective under the threshold per occurrence
        still fires when the loop pushes it over."""
        def loop(x):
            def step(c, _):
                return c + jax.lax.psum(c * 1.0, "mp"), None
            c, _ = jax.lax.scan(step, x, None, length=64)
            return c

        g = trace_auto(_smap(loop, 2),
                       jnp.zeros((8, 2048), jnp.float32))  # 32 KiB/it
        r = analyze(None, graph=g, rules=["TPU401"],
                    rule_config={"max_collective_bytes": 1 << 20})
        loud = [d for d in r.by_rule().get("TPU401", [])
                if "float payload" in d.message]
        self.assertEqual(len(loud), 1)
        self.assertIn("loop body", loud[0].message)
        self.assertEqual(loud[0].severity, Severity.WARNING)

    def test_rule_config_cli_routing(self):
        from paddle_tpu.analysis.__main__ import _parse_rule_config
        from paddle_tpu.analysis.rules import rule_config_for

        cfg = _parse_rule_config(
            ["TPU801.max_step_wire_bytes=1048576",
             "TPU803.min_bytes=256"])
        self.assertEqual(
            rule_config_for("TPU801", cfg),
            {"max_step_wire_bytes": 1048576})
        self.assertEqual(rule_config_for("TPU803", cfg),
                         {"min_bytes": 256})


class TestReportSchema(unittest.TestCase):
    def test_to_json_stable(self):
        def f(x):
            return jax.lax.psum(x * 1.0, "mp")

        fn = _smap(f, 2)
        x = jnp.zeros((8, 128), jnp.float32)
        a = comms.audit_comms(fn, x).to_json()
        b = comms.audit_comms(fn, x).to_json()
        self.assertEqual(a, b)
        d = json.loads(a)
        for key in ("target", "per_chip", "mp", "n_collective_sites",
                    "n_collectives", "n_implicit_reshards",
                    "bytes_on_wire", "float_payload_bytes",
                    "implicit_reshard_bytes", "per_axis", "per_kind",
                    "top_talkers"):
            self.assertIn(key, d)
        for ev in d["top_talkers"]:
            self.assertLessEqual(
                {"kind", "path", "axes", "wire_bytes", "count",
                 "total_wire_bytes", "in_loop", "implicit"}, set(ev))

    def test_audit_graph_memoized(self):
        g = trace_auto(_smap(lambda x: jax.lax.psum(x * 1.0, "mp"), 2),
                       jnp.zeros((8, 128), jnp.float32))
        self.assertIs(comms.audit_graph(g), comms.audit_graph(g))


def _tiny_engine(mp=1, **kw):
    cfg = LlamaConfig.tiny()
    paddle.seed(7)
    model = LlamaForCausalLM(cfg)
    return ContinuousBatchingEngine(
        cfg, dict(model.raw_state()), slots=4, prompt_bucket=16,
        max_prompt_len=32, max_new_tokens=8, block_size=16,
        steps_per_sync=4, prefill_batch=2, serving_mp=mp, **kw), cfg


class TestEngineAudit(unittest.TestCase):
    def test_mp2_decode_wire_matches_hand_reference(self):
        """ACCEPTANCE: the mp=2 decode chunk's predicted bytes-on-wire
        matches the hand-computed one-all-gather-per-layer reference
        within 10%. The gathered payload is BF16 (itemsize 2 — ISSUE
        14 satellite: `ServingTP.gather_heads` now casts an f32
        attention output to bf16 BEFORE the wire; PR 11's auditor had
        surfaced the downcast landing at the o-proj, after it):
        per token per chip = layers x nh x dh x 2 x (mp-1)/mp."""
        eng, cfg = _tiny_engine(mp=2)
        fleet = eng.audit_comms(programs=("decode",))
        ref = cfg.num_hidden_layers * cfg.num_attention_heads \
            * cfg.head_dim * 2 * (2 - 1) / 2
        got = fleet["predicted_bytes_on_wire_per_token"]
        self.assertLessEqual(abs(got - ref) / ref, 0.10,
                             f"est {got} vs ref {ref}")
        dec = fleet["programs"]["decode"]
        # one o-proj all-gather per layer, NOTHING else
        self.assertEqual(dec["n_collective_sites"],
                         cfg.num_hidden_layers)
        self.assertEqual(set(dec["per_kind"]), {"all_gather"})
        self.assertEqual(set(dec["per_axis"]), {"mp"})
        self.assertEqual(dec["n_collectives"],
                         cfg.num_hidden_layers * eng.steps)
        self.assertEqual(dec["n_implicit_reshards"], 0)

    def test_mp1_engine_audits_clean_zero_collectives(self):
        """ACCEPTANCE: the bf16/mp=1 engine audits clean — zero
        collectives, zero wire bytes, no diagnostics."""
        eng, _ = _tiny_engine()
        eng.warm([16])
        fleet = eng.audit_comms()
        self.assertTrue(fleet["comms_clean"])
        self.assertEqual(fleet["total_bytes_on_wire"], 0)
        self.assertEqual(fleet["predicted_bytes_on_wire_per_token"], 0)
        for name, prog in fleet["programs"].items():
            self.assertEqual(prog["n_collectives"], 0, name)
            self.assertEqual(prog["diagnostics"], [], name)
        self.assertIs(eng.metrics()["comms_audit"], fleet)

    def test_mp2_warm_hook_fleet_report_and_tpu803(self):
        """warm(audit_comms=True) audits every cached program; the
        prefill variants carry their own per-layer gathers; TPU803
        fires on the unquantized decode gather once its threshold
        covers the payload (ACCEPTANCE)."""
        eng, cfg = _tiny_engine(mp=2, unified_step=False)  # split fleet
        eng.warm([16], prefix_widths=[1], audit_comms=True)
        fleet = eng.metrics()["comms_audit"]
        self.assertIsNotNone(fleet)
        self.assertGreaterEqual(fleet["programs_audited"], 3)
        self.assertEqual(fleet["mp"], 2)
        for name, prog in fleet["programs"].items():
            self.assertEqual(set(prog["per_kind"]) - {"all_gather"},
                             set(), name)
            self.assertGreater(prog["bytes_on_wire"], 0, name)
        # tiny payloads stay under the default 1 MiB: clean...
        self.assertTrue(fleet["comms_clean"])
        # ...and a tightened threshold makes TPU803 name the gather
        tight = eng.audit_comms(
            programs=("decode",),
            rule_config={"TPU803.min_bytes": 256})
        rules = [d["rule"] for d
                 in tight["programs"]["decode"]["diagnostics"]]
        self.assertIn("TPU803", rules)

    def test_audit_emits_observability_sinks(self):
        from paddle_tpu.observability import MetricsRegistry

        mt = MetricsRegistry()
        eng, _ = _tiny_engine(mp=2, metrics=mt)
        partial = eng.audit_comms(programs=("decode",))
        self.assertTrue(partial["partial"])
        self.assertEqual(mt.events("comms.audit"), [])
        self.assertIsNone(eng.metrics()["comms_audit"])
        with self.assertRaisesRegex(ValueError, "nonesuch"):
            eng.audit_comms(programs=("nonesuch",))
        full = eng.audit_comms()
        self.assertFalse(full["partial"])
        events = mt.events("comms.audit")
        self.assertEqual(len(events), 1)
        self.assertGreater(events[0]["total_bytes_on_wire"], 0)
        snap = mt.snapshot()
        self.assertIn("predicted_bytes_on_wire_per_token",
                      snap["gauges"])

    def test_flag_composition(self):
        from paddle_tpu.analysis.comms import resolve_audit_comms

        prev = paddle.get_flags(["tpu_lint", "audit_comms"])
        try:
            paddle.set_flags({"tpu_lint": True, "audit_comms": False})
            self.assertTrue(resolve_audit_comms(None))
            paddle.set_flags({"tpu_lint": False})
            self.assertFalse(resolve_audit_comms(None))
            paddle.set_flags({"audit_comms": True})
            self.assertTrue(resolve_audit_comms(None))
            self.assertFalse(resolve_audit_comms(False))
        finally:
            paddle.set_flags({k.replace("FLAGS_", ""): v
                              for k, v in prev.items()})


class TestFitAudit(unittest.TestCase):
    def _model(self, width=512):
        from paddle_tpu import nn, optimizer as opt

        paddle.seed(5)
        net = nn.Linear(width, width)
        model = paddle.Model(net)
        model.prepare(optimizer=opt.Adam(learning_rate=0.01,
                                         parameters=net.parameters()),
                      loss=lambda out, y: ((out - y) ** 2).mean())
        rng = np.random.default_rng(0)
        batches = [(rng.normal(size=(4, width)).astype(np.float32),
                    rng.normal(size=(4, width)).astype(np.float32))]
        return model, batches

    def test_fit_dp_gradient_psum_fires_tpu803(self):
        """ACCEPTANCE: fit(audit_comms=True) under a dp mesh surfaces
        the dp gradient psum — ~1 MiB of f32 grads for a 512x512
        Linear — and TPU803 names it at default thresholds."""
        from paddle_tpu.parallel import mesh as mesh_mod

        prev = mesh_mod.get_global_mesh()
        try:
            mesh_mod.set_global_mesh(mesh_mod.build_mesh(
                {"dp": 2}, devices=jax.devices()[:2]))
            model, batches = self._model()
            model.fit(batches, epochs=1, verbose=0, audit_comms=True)
        finally:
            mesh_mod.set_global_mesh(prev)
        audit = model.comms_audit
        self.assertIsNotNone(audit)
        self.assertIn("fit.step[dp=2]", audit["target"])
        self.assertEqual(audit["mp"], 2)
        self.assertGreaterEqual(audit["n_collective_sites"], 1)
        self.assertEqual(set(audit["per_axis"]), {"dp"})
        # grads = 512*512*4 + 512*4 f32 bytes, psum'd once per step
        ref = (512 * 512 + 512) * 4
        got = audit["float_payload_bytes"]
        self.assertLessEqual(abs(got - ref) / ref, 0.10,
                             f"{got} vs {ref}")
        self.assertIn("TPU803",
                      [d["rule"] for d in audit["diagnostics"]])

    def test_fit_without_dp_mesh_audits_zero_collectives(self):
        from paddle_tpu.parallel import mesh as mesh_mod

        prev = mesh_mod.get_global_mesh()
        try:
            mesh_mod.set_global_mesh(None)
            model, batches = self._model(width=8)
            model.fit(batches, epochs=1, verbose=0, audit_comms=True)
        finally:
            mesh_mod.set_global_mesh(prev)
        self.assertIsNotNone(model.comms_audit)
        self.assertEqual(model.comms_audit["n_collectives"], 0)
        self.assertEqual(model.comms_audit["bytes_on_wire"], 0)

    def test_fit_dp_incompatible_batch_warns_on_fallback(self):
        """A dp mesh whose batch leading dim does not divide dp falls
        back to the single-chip step — but WARNS, because the clean
        zero-collective report would otherwise hide the very psum the
        audit exists to count."""
        from paddle_tpu.parallel import mesh as mesh_mod

        prev = mesh_mod.get_global_mesh()
        try:
            mesh_mod.set_global_mesh(mesh_mod.build_mesh(
                {"dp": 2}, devices=jax.devices()[:2]))
            model, _ = self._model(width=8)
            rng = np.random.default_rng(0)
            odd = [(rng.normal(size=(3, 8)).astype(np.float32),
                    rng.normal(size=(3, 8)).astype(np.float32))]
            with pytest.warns(UserWarning,
                              match="dp gradient psum is NOT counted"):
                model.fit(odd, epochs=1, verbose=0, audit_comms=True)
        finally:
            mesh_mod.set_global_mesh(prev)
        self.assertEqual(model.comms_audit["n_collectives"], 0)

    def test_default_pipeline_reports_each_site_once(self):
        """TPU401 defers the size check to TPU803 in the default
        pipeline: a quantizable collective is reported ONCE, not by
        both rules with the same hint (TPU401's legacy channel re-arms
        via an explicit max_collective_bytes=)."""
        def f(x):
            return jax.lax.all_gather(x, "mp", axis=0, tiled=True)

        from jax.sharding import PartitionSpec as P

        big = jnp.zeros((8, 1 << 18), jnp.bfloat16)  # 4 MiB payload
        fn = _smap(f, 2, out_specs=P(None))
        r = analyze(fn, big)  # every registered rule
        sized = [d for d in r if "float payload" in d.message]
        self.assertEqual(len(sized), 1)
        self.assertEqual(sized[0].rule, "TPU803")
        armed = analyze(fn, big, rules=["TPU401"],
                        rule_config={"max_collective_bytes": 1 << 20})
        self.assertEqual(len(armed), 1)  # the explicit legacy channel

    def test_fit_audit_off_by_default(self):
        model, batches = self._model(width=8)
        model.fit(batches, epochs=1, verbose=0)
        self.assertIsNone(model.comms_audit)


class TestCLICommsJSON(unittest.TestCase):
    def test_cli_comms_json_schema_and_gate(self):
        """The CI gate (ISSUE 11 satellite): `python -m
        paddle_tpu.analysis --comms --format json` over the mp=2
        sharded decode demo emits one valid JSON object with the
        documented schema and exits 0; the same invocation with a
        tightened TPU803 threshold and --fail-on warning exits 1 — the
        scriptable gate, mirroring the `--memory` test."""
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=2")
        cwd = os.path.dirname(os.path.dirname(__file__))
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.analysis", "--comms",
             "--format", "json"],
            capture_output=True, text=True, env=env, cwd=cwd,
            timeout=300)
        self.assertEqual(proc.returncode, 0, proc.stderr[-2000:])
        d = json.loads(proc.stdout)
        self.assertEqual(sorted(d),
                         ["comms", "counts", "diagnostics", "target"])
        c = d["comms"]
        for key in ("bytes_on_wire", "per_axis", "per_kind", "mp",
                    "n_collective_sites", "n_collectives",
                    "top_talkers", "per_chip"):
            self.assertIn(key, c)
        self.assertEqual(c["mp"], 2)
        self.assertGreater(c["bytes_on_wire"], 0)
        self.assertEqual(set(c["per_kind"]), {"all_gather"})
        # the scriptable gate: ERROR-severity findings exit non-zero
        gated = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.analysis", "--comms",
             "--format", "json",
             "--rule-config", "TPU803.min_bytes=256",
             "--fail-on", "warning"],
            capture_output=True, text=True, env=env, cwd=cwd,
            timeout=300)
        self.assertEqual(gated.returncode, 1, gated.stderr[-2000:])
        gd = json.loads(gated.stdout)
        self.assertIn("TPU803",
                      [x["rule"] for x in gd["diagnostics"]])


if __name__ == "__main__":
    unittest.main()
