"""Quantized collectives (FLAGS_quantized_collectives, ISSUE 15):
int8 all-gather / psum with an f32 scale sidecar on the two audited
hot seams — the serving o-proj activation gather at mp>1 and the dp
gradient psum in Model.fit.

Contracts under test:
- quantization numerics: roundtrip error <= scale/2 per element, exact
  zeros, NON-FINITE payloads stay visibly non-finite (never silent
  corruption), unquantizable payloads fall back with a warning;
- psum: matches the exact psum within quantization tolerance at world
  sizes 2 AND 4 (f32 dequant-accumulate — error does not scale with
  n), zero gradients exact, tree variant preserves shapes/dtypes;
- serving: mp=2 engine with the flag ON matches the bf16-gather
  baseline at the int8-KV token-match bar through prefix/recycling
  churn; the flag joins every program key and zero-recompile-after-
  warm holds; flag OFF stays byte-identical (guarded by the existing
  mp identity suite);
- analysis: the comms pass recognizes the packed int8 buffer (the f32
  sidecar rides bitcast-int8 inside the payload — ONE collective per
  hop since the ISSUE 18 packing) and prices payload + sidecar; the
  quantized decode gather is ~0.5-0.65x the bf16 wire (exact 0.5x
  plus the sidecar, which is proportionally wider at tiny head dims);
  TPU803 fires on the bf16 gather at a tightened threshold and is
  SILENT on the quantized one at the DEFAULT threshold;
- training: dp-trained tiny-llama loss curve with the quantized sync
  matches the eager unquantized run within the PR 5 quantization
  tolerance, and fit(audit_comms=) prices the quantized step;
- CLI: `python -m paddle_tpu.analysis --comms` emits the
  quantized-vs-unquantized wire-bytes ratio in its stable JSON schema
  (tier-1 subprocess gate).
"""
import dataclasses
import json
import os
import subprocess
import sys
import unittest
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.parallel import collectives as qc
from jax import shard_map
from paddle_tpu.serving import ContinuousBatchingEngine


def _smap(fn, n, in_specs=P("dp"), out_specs=P("dp")):
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("dp",))
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))


class TestQuantizeBlocks(unittest.TestCase):
    def test_roundtrip_error_le_half_scale(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(4, 3, 256)).astype(np.float32))
        q, s = qc.quantize_blocks(x)
        self.assertEqual(q.dtype, jnp.int8)
        self.assertEqual(s.shape, (4, 3, 2))
        y = qc.dequantize_blocks(q, s, out_dim=256)
        err = np.abs(np.asarray(y - x))
        bound = np.repeat(np.asarray(s), 128, axis=-1) / 2 + 1e-9
        self.assertTrue((err <= bound).all())

    def test_zero_block_exact_zero(self):
        x = jnp.zeros((2, 64), jnp.float32)
        q, s = qc.quantize_blocks(x)
        np.testing.assert_array_equal(np.asarray(s), 0.0)
        np.testing.assert_array_equal(
            np.asarray(qc.dequantize_blocks(q, s)), 0.0)

    def test_partial_block_pads_and_trims(self):
        x = jnp.asarray(np.arange(300, dtype=np.float32)[None])
        q, s = qc.quantize_blocks(x)           # 3 blocks of 128, padded
        self.assertEqual(q.shape, (1, 384))
        self.assertEqual(s.shape, (1, 3))
        y = qc.dequantize_blocks(q, s, out_dim=300)
        self.assertEqual(y.shape, (1, 300))
        self.assertLess(float(jnp.max(jnp.abs(y - x))),
                        float(jnp.max(s)) / 2 + 1e-6)

    def test_block_clamps_to_narrow_dim(self):
        x = jnp.ones((2, 16), jnp.bfloat16)
        q, s = qc.quantize_blocks(x)
        self.assertEqual(q.shape, (2, 16))     # no pad to 128
        self.assertEqual(s.shape, (2, 1))

    def test_nonfinite_block_dequantizes_nonfinite(self):
        """Never silent corruption: NaN/inf in a block poisons the
        STORED scale, so the dequant is visibly non-finite instead of
        finite garbage."""
        for bad in (np.nan, np.inf):
            x = np.ones((1, 128), np.float32)
            x[0, 7] = bad
            q, s = qc.quantize_blocks(jnp.asarray(x))
            self.assertFalse(np.isfinite(np.asarray(s)).all())
            y = np.asarray(qc.dequantize_blocks(q, s))
            self.assertFalse(np.isfinite(y).all())


class TestQuantizedPsum(unittest.TestCase):
    def _exact_and_quant(self, n, size=1000, seed=3):
        rng = np.random.default_rng(seed)
        x = rng.normal(size=(n, size)).astype(np.float32)
        exact = x.sum(axis=0)
        out = _smap(lambda v: qc.quantized_psum(v[0], "dp"), n,
                    in_specs=P("dp"), out_specs=P(None))(
            jnp.asarray(x)[:, None])
        return exact, np.asarray(out)

    def test_matches_exact_psum_ws2_and_ws4(self):
        """Order-independence across world sizes: the f32
        dequant-accumulate keeps the error at quantization noise for
        BOTH n=2 and n=4 (two roundings per element, independent of
        n)."""
        for n in (2, 4):
            exact, got = self._exact_and_quant(n)
            denom = np.maximum(np.abs(exact), 1.0)
            rel = np.max(np.abs(got - exact) / denom)
            self.assertLess(rel, 0.05, f"ws={n}: rel err {rel}")

    def test_error_does_not_scale_with_world_size(self):
        e2, g2 = self._exact_and_quant(2, seed=7)
        e4, g4 = self._exact_and_quant(4, seed=7)
        err2 = np.max(np.abs(g2 - e2) / np.maximum(np.abs(e2), 1.0))
        err4 = np.max(np.abs(g4 - e4) / np.maximum(np.abs(e4), 1.0))
        # both at quantization noise; ws=4 not catastrophically worse
        self.assertLess(err4, max(4 * err2, 0.05))

    def test_zero_gradient_exact(self):
        out = _smap(lambda v: qc.quantized_psum(v[0], "dp"), 2,
                    in_specs=P("dp"), out_specs=P(None))(
            jnp.zeros((2, 1, 300), jnp.float32))
        np.testing.assert_array_equal(np.asarray(out), 0.0)

    def test_nonfinite_payload_propagates(self):
        x = np.ones((2, 1, 256), np.float32)
        x[0, 0, 3] = np.nan
        out = _smap(lambda v: qc.quantized_psum(v[0], "dp"), 2,
                    in_specs=P("dp"), out_specs=P(None))(jnp.asarray(x))
        self.assertFalse(np.isfinite(np.asarray(out)).all())

    def test_int_payload_falls_back_with_warning(self):
        with pytest.warns(UserWarning, match="falling back"):
            out = _smap(lambda v: qc.quantized_psum(v[0], "dp"), 2,
                        in_specs=P("dp"), out_specs=P(None))(
                jnp.ones((2, 1, 8), jnp.int32))
        np.testing.assert_array_equal(np.asarray(out), 2)

    def test_psum_tree_shapes_dtypes_and_values(self):
        rng = np.random.default_rng(5)
        tree = {
            "w": rng.normal(size=(2, 17, 33)).astype(np.float32),
            "b": rng.normal(size=(2, 5)).astype(np.float32),
            "z": np.zeros((2, 9), np.float32),
        }

        def f(t):
            local = {k: v[0] for k, v in t.items()}
            return qc.quantized_psum_tree(local, "dp")

        out = _smap(f, 2, in_specs=({k: P("dp") for k in tree},),
                    out_specs={k: P(None) for k in tree})(
            {k: jnp.asarray(v) for k, v in tree.items()})
        for k in ("w", "b"):
            exact = tree[k].sum(axis=0)
            got = np.asarray(out[k])
            self.assertEqual(got.shape, exact.shape)
            rel = np.max(np.abs(got - exact)
                         / np.maximum(np.abs(exact), 1.0))
            self.assertLess(rel, 0.05, k)
        np.testing.assert_array_equal(np.asarray(out["z"]), 0.0)

    def test_reduce_scatter_matches_psum_scatter(self):
        rng = np.random.default_rng(9)
        x = rng.normal(size=(2, 4, 256)).astype(np.float32)

        def f(v):
            return qc.quantized_reduce_scatter(v[0], "dp")

        got = np.asarray(_smap(f, 2, in_specs=P("dp"),
                               out_specs=P("dp"))(jnp.asarray(x)))
        exact = x.sum(axis=0).reshape(2, 2, 256).reshape(4, 256)
        rel = np.max(np.abs(got.reshape(4, 256) - exact)
                     / np.maximum(np.abs(exact), 1.0))
        self.assertLess(rel, 0.05)


class TestQuantizedAllGather(unittest.TestCase):
    def test_matches_plain_gather_within_tolerance(self):
        rng = np.random.default_rng(11)
        x = rng.normal(size=(4, 4, 64)).astype(np.float32)

        def f(v):
            return qc.quantized_all_gather(v, "dp", axis=1, tiled=True)

        got = np.asarray(_smap(f, 2, in_specs=P(None, "dp"),
                               out_specs=P(None))(jnp.asarray(x)))
        self.assertEqual(got.shape, x.shape)
        scale = np.abs(x).reshape(4, 4, 1, 64).max(-1) / 127.0
        bound = np.repeat(scale, 64, axis=-1).reshape(x.shape) / 2 + 1e-9
        self.assertTrue((np.abs(got - x) <= bound).all())

    def test_last_axis_gather_falls_back(self):
        x = jnp.ones((2, 2, 8), jnp.float32)

        def f(v):
            return qc.quantized_all_gather(v, "dp", axis=v.ndim - 1,
                                           tiled=True)

        with pytest.warns(UserWarning, match="falling back"):
            out = _smap(f, 2, in_specs=P(None, None, "dp"),
                        out_specs=P(None))(x)
        np.testing.assert_array_equal(np.asarray(out), 1.0)


class TestFlagResolution(unittest.TestCase):
    def test_default_off_and_explicit_win(self):
        prev = paddle.get_flags("quantized_collectives")
        try:
            self.assertFalse(qc.resolve_quantized_collectives(None))
            self.assertTrue(qc.resolve_quantized_collectives(True))
            paddle.set_flags({"quantized_collectives": True})
            self.assertTrue(qc.resolve_quantized_collectives(None))
            self.assertFalse(qc.resolve_quantized_collectives(False))
        finally:
            paddle.set_flags({k.replace("FLAGS_", ""): v
                              for k, v in prev.items()})


# --------------------------------------------------------------------------
# serving integration
# --------------------------------------------------------------------------

def _tiny_setup(seed=21):
    cfg = dataclasses.replace(LlamaConfig.tiny(), num_key_value_heads=2)
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    params = {k: (v.astype(jnp.bfloat16) if v.dtype == jnp.float32
                  else v)
              for k, v in dict(model.raw_state()).items()}
    return cfg, params


def _engine(cfg, params, mp=1, **over):
    kw = dict(slots=2, prompt_bucket=8, max_prompt_len=16,
              max_new_tokens=6, block_size=8, steps_per_sync=3,
              serving_mp=mp)
    kw.update(over)
    return ContinuousBatchingEngine(cfg, dict(params), **kw)


def _churn_prompts(cfg, rng):
    shared = rng.integers(1, cfg.vocab_size, (8,)).tolist()
    return ([shared + rng.integers(1, cfg.vocab_size, (n,)).tolist()
             for n in (3, 5, 2)]
            + [rng.integers(1, cfg.vocab_size, (n,)).tolist()
               for n in (7, 9, 4)])


def _serve(eng, prompts):
    for i, pr in enumerate(prompts):
        eng.add_request(pr, max_new=2 + i % 4)
    eng.run(max_iters=300)
    assert len(eng.finished) == len(prompts)
    return {r.req_id: list(r.tokens) for r in eng.finished}


def _match_rate(a, b):
    total = agree = 0
    for rid in a:
        xa, xb = np.asarray(a[rid]), np.asarray(b.get(rid, []))
        n = min(len(xa), len(xb))
        total += max(len(xa), len(xb))
        agree += int((xa[:n] == xb[:n]).sum())
    return agree / max(total, 1)


class TestServingQuantizedGather(unittest.TestCase):
    def test_mp2_token_match_vs_bf16_gather_through_churn(self):
        """ACCEPTANCE: mp=2 with the int8 gather serves the churn trace
        (prefix hits + page recycling) at >= the int8-KV token-match
        bar vs the bf16-gather baseline — quantization noise, not
        corruption."""
        cfg, params = _tiny_setup()
        rng = np.random.default_rng(7)
        prompts = _churn_prompts(cfg, rng)
        base = _engine(cfg, params, mp=2)
        t_base = _serve(base, prompts)
        eng = _engine(cfg, params, mp=2, quantized_collectives=True)
        t_q = _serve(eng, prompts)
        self.assertTrue(eng.quantized_collectives)
        self.assertGreaterEqual(_match_rate(t_base, t_q), 0.8)
        n_ident = sum(t_base[r] == t_q.get(r) for r in t_base)
        self.assertGreaterEqual(n_ident, len(t_base) - 2)
        self.assertGreater(eng.prefix_hit_tokens, 0)

    def test_flag_joins_program_keys_and_zero_recompiles(self):
        """The flag rides every prefill program key (mp stays the LAST
        component) and warm() covers the quantized programs — serving
        traffic adds zero compiles."""
        cfg, params = _tiny_setup()
        rng = np.random.default_rng(19)
        eng = _engine(cfg, params, mp=2, prefill_batch=1,
                      prefix_cache=True, unified_step=False,
                      quantized_collectives=True)
        eng.warm(buckets=[8, 16])
        before = eng.compile_stats()
        self.assertNotIn(-1, before.values())
        for k in before:
            if k == "decode":
                continue
            parts = k.split(":")
            self.assertEqual(parts[-1], "2", k)      # mp last
            self.assertEqual(parts[-2], "1", k)      # qcoll flag on
        off = _engine(cfg, params, mp=2, prefill_batch=1,
                      unified_step=False)
        off.warm(buckets=[8])
        self.assertTrue(all(k == "decode" or k.split(":")[-2] == "0"
                            for k in off.compile_stats()))
        prompts = _churn_prompts(cfg, rng)[:4]
        for i, pr in enumerate(prompts):
            eng.add_request(pr, max_new=2 + i % 3)
        eng.run(max_iters=300)
        self.assertEqual(len(eng.finished), len(prompts))
        self.assertEqual(eng.compile_stats(), before)

    def test_engine_metrics_record_flag(self):
        cfg, params = _tiny_setup()
        eng = _engine(cfg, params, mp=1, quantized_collectives=True)
        self.assertTrue(eng.metrics()["quantized_collectives"])
        self.assertFalse(
            _engine(cfg, params)
            .metrics()["quantized_collectives"])

    def test_cp_merge_quantized_parity(self):
        """The serving psum seam: under cp > 1 ServingTP.
        merge_attn_partials ships the weighted accumulator through the
        int8 two-hop psum when the flag is on and merges the m / l
        statistics exactly — parity with the exact merge at
        quantization tolerance."""
        from paddle_tpu.models.llama import ServingTP

        cfg, _ = _tiny_setup()
        tp_q = ServingTP(cfg, 1, quantized=True, cp=2)
        tp_x = ServingTP(cfg, 1, quantized=False, cp=2)
        rng = np.random.default_rng(23)
        # per-shard partials [cp, rows, heads(, dh)]: shard 1 holds the
        # larger running max in some rows, so both rescales are live
        m = rng.normal(size=(2, 4, 4)).astype(np.float32)
        l = rng.uniform(0.5, 4.0, size=(2, 4, 4)).astype(np.float32)
        acc = rng.normal(size=(2, 4, 4, 64)).astype(np.float32)
        mesh = Mesh(np.asarray(jax.devices()[:2]), (tp_x.cp_axis,))

        def smap(tp):
            spec = P(tp.cp_axis)
            return jax.jit(shard_map(
                lambda a, b, c: tp.merge_attn_partials(a[0], b[0], c[0]),
                mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=P(None), check_vma=False))

        exact = [np.asarray(t) for t in smap(tp_x)(m, l, acc)]
        got = [np.asarray(t) for t in smap(tp_q)(m, l, acc)]
        np.testing.assert_array_equal(got[0], exact[0])
        np.testing.assert_array_equal(got[1], exact[1])
        self.assertFalse(np.array_equal(got[2], exact[2]))
        rel = np.max(np.abs(got[2] - exact[2])
                     / np.maximum(np.abs(exact[2]), 1.0))
        self.assertLess(rel, 0.05)
        # and against the merge written out: softmax over both shards
        w = np.exp(m - m.max(0))
        np.testing.assert_allclose(exact[2], (acc * w[..., None]).sum(0),
                                   rtol=1e-5, atol=1e-5)

    def test_cp2_token_match_vs_exact_merge_through_churn(self):
        """cp=2 with the int8 merge serves the churn trace at the
        int8-KV token-match bar against the exact-merge cp=2 engine —
        the flag's one effect on a head-unsharded mesh."""
        cfg, params = _tiny_setup()
        prompts = _churn_prompts(cfg, np.random.default_rng(7))
        t_base = _serve(_engine(cfg, params, serving_cp=2), prompts)
        eng = _engine(cfg, params, serving_cp=2,
                      quantized_collectives=True)
        t_q = _serve(eng, prompts)
        self.assertTrue(eng.quantized_collectives)
        self.assertGreaterEqual(_match_rate(t_base, t_q), 0.8)
        # the merge's int8 psum is in the program that was served
        dec = eng.audit_comms(programs=("decode",))["programs"]["decode"]
        self.assertGreaterEqual(dec["n_quantized_sites"], 1)


class TestCommsAuditQuantized(unittest.TestCase):
    def _decode_graphs(self, quantized):
        cfg, params = _tiny_setup()
        eng = _engine(cfg, params, mp=2,
                      quantized_collectives=quantized)
        return eng, eng._traced_inventory(programs=("decode",))

    def test_wire_ratio_and_pattern_recognized(self):
        """The quantized decode gather is priced payload + sidecar:
        ~0.5x the bf16 wire at serving head dims (0.625x at the tiny
        dh=16: int8 1 B/elt + f32/16-elt sidecar vs bf16 2 B/elt), and
        the pass marks the packed int8 buffer — ONE collective per hop
        since the sidecar packing, so every quantized event is int8
        and the hop count matches the unquantized program's."""
        from paddle_tpu.analysis import comms as comms_mod

        e_b, g_b = self._decode_graphs(False)
        e_q, g_q = self._decode_graphs(True)
        rep_b = e_b.audit_comms(programs=("decode",), graphs=g_b)
        rep_q = e_q.audit_comms(programs=("decode",), graphs=g_q)
        wb = rep_b["predicted_bytes_on_wire_per_token"]
        wq = rep_q["predicted_bytes_on_wire_per_token"]
        self.assertGreater(wb, 0)
        ratio = wq / wb
        self.assertLess(ratio, 0.7, f"ratio {ratio}")
        self.assertGreater(ratio, 0.4, f"ratio {ratio}")
        dec_q = rep_q["programs"]["decode"]
        self.assertGreaterEqual(dec_q["n_quantized_sites"], 1)
        self.assertEqual(dec_q["quantized_wire_bytes"],
                         dec_q["bytes_on_wire"])
        # packed form: EVERY quantized event is the single int8
        # buffer (no float sidecar twin rides the wire anymore), and
        # the quantized program issues no more collectives than the
        # bf16 one — the launch-bound-decode risk is closed
        crep = comms_mod.audit_graph(g_q[0][1])
        self.assertTrue(crep.quantized_events)
        kinds = {e.dtype.startswith("int8") for e in
                 crep.quantized_events}
        self.assertEqual(kinds, {True})
        brep = comms_mod.audit_graph(g_b[0][1])
        self.assertLessEqual(crep.n_collective_sites,
                             brep.n_collective_sites)
        self.assertLessEqual(crep.n_collectives, brep.n_collectives)
        dec_b = rep_b["programs"]["decode"]
        self.assertEqual(dec_b["n_quantized_sites"], 0)

    def test_tpu803_fire_then_silent_pair(self):
        """Regression pair (ISSUE 15 satellite): flag OFF fires TPU803
        on the decode o-proj gather at a tightened threshold; flag ON
        is CLEAN at the DEFAULT threshold — int8 payloads never fire
        by design and the sidecar sits far under the floor."""
        from paddle_tpu.analysis.pipeline import analyze

        _, g_b = self._decode_graphs(False)
        _, g_q = self._decode_graphs(True)
        fired = analyze(None, graph=g_b[0][1], rules=["TPU803"],
                        rule_config={"TPU803.min_bytes": 256})
        self.assertIn("TPU803", [d.rule for d in fired])
        clean = analyze(None, graph=g_q[0][1], rules=["TPU803"])
        self.assertEqual([d.rule for d in clean], [])
        # ... and even tightened, the quantized program stays quiet on
        # float payloads (only the sidecar is float, under 256 bytes
        # per occurrence amplified above the floor would still be the
        # sidecar — assert the default threshold explicitly)
        self.assertEqual(len(clean), 0)


class TestFitQuantizedDP(unittest.TestCase):
    def _dp_mesh(self):
        from paddle_tpu.parallel import mesh as mesh_mod

        return mesh_mod, mesh_mod.build_mesh(
            {"dp": 2}, devices=jax.devices()[:2])

    def _tiny_llama_model(self, seed=5):
        cfg = LlamaConfig.tiny()
        paddle.seed(seed)
        net = LlamaForCausalLM(cfg)
        model = paddle.Model(net)
        from paddle_tpu import optimizer as opt

        model.prepare(
            optimizer=opt.Adam(learning_rate=0.01,
                               parameters=net.parameters()),
            loss=lambda out, y: ((out - y) ** 2).mean())
        rng = np.random.default_rng(0)
        batches = [
            (rng.integers(1, cfg.vocab_size, (4, 8)).astype(np.int32),
             rng.normal(size=(4, 8, cfg.vocab_size)).astype(np.float32))
            for _ in range(4)]
        return model, batches

    def test_dp_loss_curve_matches_unquantized(self):
        """ACCEPTANCE: the dp-trained tiny-llama loss curve with the
        quantized gradient sync matches the eager unquantized run
        within the PR 5 quantization tolerance (the sync is a
        dp-mean; two int8 roundings per grad element)."""
        mesh_mod, mesh = self._dp_mesh()
        prev = mesh_mod.get_global_mesh()

        class Rec(paddle.hapi.callbacks.Callback):
            def __init__(self):
                self.losses = []

            def on_train_batch_end(self, step, logs=None):
                self.losses.append(logs["loss"][0])

        try:
            mesh_mod.set_global_mesh(mesh)
            m1, b1 = self._tiny_llama_model()
            r1 = Rec()
            m1.fit(b1, epochs=1, verbose=0, callbacks=[r1])
            self.assertEqual(m1.quantized_dp_steps, 0)
            m2, b2 = self._tiny_llama_model()
            r2 = Rec()
            m2.fit(b2, epochs=1, verbose=0, callbacks=[r2],
                   quantized_collectives=True)
        finally:
            mesh_mod.set_global_mesh(prev)
        self.assertEqual(m2.quantized_dp_steps, len(b2))
        self.assertEqual(len(r1.losses), len(r2.losses))
        for a, b in zip(r1.losses, r2.losses):
            self.assertLess(abs(a - b) / max(abs(a), 1e-6), 0.05,
                            f"{r1.losses} vs {r2.losses}")

    def test_fit_audit_prices_quantized_step(self):
        """fit(audit_comms=True, quantized_collectives=True) audits
        the SAME program training runs: the int8+sidecar pair replaces
        the f32 grads psum, TPU803 stays silent at default, and the
        wire bytes drop well below the unquantized psum's."""
        mesh_mod, mesh = self._dp_mesh()
        prev = mesh_mod.get_global_mesh()
        try:
            mesh_mod.set_global_mesh(mesh)
            from paddle_tpu import nn, optimizer as opt

            def build():
                paddle.seed(5)
                net = nn.Linear(512, 512)
                model = paddle.Model(net)
                model.prepare(
                    optimizer=opt.Adam(learning_rate=0.01,
                                       parameters=net.parameters()),
                    loss=lambda out, y: ((out - y) ** 2).mean())
                rng = np.random.default_rng(0)
                b = [(rng.normal(size=(4, 512)).astype(np.float32),
                      rng.normal(size=(4, 512)).astype(np.float32))]
                return model, b

            m_off, b_off = build()
            m_off.fit(b_off, epochs=1, verbose=0, audit_comms=True)
            m_on, b_on = build()
            m_on.fit(b_on, epochs=1, verbose=0, audit_comms=True,
                     quantized_collectives=True)
        finally:
            mesh_mod.set_global_mesh(prev)
        off, on = m_off.comms_audit, m_on.comms_audit
        self.assertIn("fit.step[dp=2]", off["target"])
        self.assertIn("+int8coll", on["target"])
        self.assertIn("TPU803", [d["rule"] for d in off["diagnostics"]])
        self.assertNotIn("TPU803",
                         [d["rule"] for d in on["diagnostics"]])
        self.assertGreaterEqual(on["n_quantized_sites"], 2)
        self.assertLess(on["bytes_on_wire"],
                        0.5 * off["bytes_on_wire"])
        self.assertEqual(m_on.quantized_dp_steps, 1)

    def test_no_dp_mesh_warns_and_falls_back(self):
        from paddle_tpu.parallel import mesh as mesh_mod

        prev = mesh_mod.get_global_mesh()
        try:
            mesh_mod.set_global_mesh(None)
            from paddle_tpu import nn, optimizer as opt

            paddle.seed(5)
            net = nn.Linear(8, 8)
            model = paddle.Model(net)
            model.prepare(
                optimizer=opt.Adam(learning_rate=0.01,
                                   parameters=net.parameters()),
                loss=lambda out, y: ((out - y) ** 2).mean())
            rng = np.random.default_rng(0)
            b = [(rng.normal(size=(2, 8)).astype(np.float32),
                  rng.normal(size=(2, 8)).astype(np.float32))]
            with pytest.warns(UserWarning,
                              match="no gradient sync to quantize"):
                model.fit(b, epochs=1, verbose=0,
                          quantized_collectives=True)
        finally:
            mesh_mod.set_global_mesh(prev)
        self.assertEqual(model.quantized_dp_steps, 0)


class TestCLIQuantizedDemo(unittest.TestCase):
    def test_cli_comms_reports_wire_ratio(self):
        """Tier-1 CI gate (ISSUE 15 satellite): the --comms demo emits
        the quantized-vs-unquantized wire-bytes ratio through the
        stable JSON schema — ~0.5x plus the sidecar (0.625x at the
        tiny demo's dh=16)."""
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=2")
        cwd = os.path.dirname(os.path.dirname(__file__))
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.analysis", "--comms",
             "--format", "json"],
            capture_output=True, text=True, env=env, cwd=cwd,
            timeout=300)
        self.assertEqual(proc.returncode, 0, proc.stderr[-2000:])
        c = json.loads(proc.stdout)["comms"]
        q = c["quantized_decode"]
        self.assertGreater(q["bytes_on_wire"], 0)
        self.assertEqual(q["quantized_wire_bytes"], q["bytes_on_wire"])
        self.assertGreaterEqual(q["n_quantized_sites"], 1)
        ratio = q["wire_bytes_ratio_vs_unquantized"]
        self.assertLess(ratio, 0.7, ratio)
        self.assertGreater(ratio, 0.4, ratio)


if __name__ == "__main__":
    unittest.main()
