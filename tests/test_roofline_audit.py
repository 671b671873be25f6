"""Static roofline auditor (ISSUE 13): jaxpr FLOPs/bytes pass against
the device-spec table, fusion-aware HBM accounting, loop amplification,
shard_map per-chip math, the KernelConstraint roofline models (paged
attention counts pool pages), predicted step latency + MFU, the
TPU901/902/903 rules, the shared kernel-launch walker, the engine fleet
audit, the Model.fit hook, and the CLI `--roofline --format json` gate
CI scripts against."""
import dataclasses
import json
import math
import os
import subprocess
import sys
import unittest

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.analysis import Severity, analyze, roofline
from paddle_tpu.analysis.device_specs import DEVICE_SPECS, get_spec
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import ContinuousBatchingEngine

V5E = DEVICE_SPECS["tpu-v5e"]


def _smap(fn, n, in_specs=None, out_specs=None):
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()[:n]), ("mp",))
    return shard_map(fn, mesh=mesh,
                     in_specs=P("mp") if in_specs is None else in_specs,
                     out_specs=P("mp") if out_specs is None
                     else out_specs, check_vma=False)


class TestDeviceSpecs(unittest.TestCase):
    def test_table_rows_and_bench_literals(self):
        """The hoisted constants keep their exact legacy values: v5e
        819e9 HBM GB/s (bench_roofline/bench_serving) and 197e12 bf16
        peak (bench_mfu); v6e 918e12 (bench.py's device-kind switch)."""
        self.assertEqual(V5E.hbm_gbs, 819e9)
        self.assertEqual(V5E.peak_for("bfloat16"), 197e12)
        self.assertEqual(DEVICE_SPECS["tpu-v6e"].peak_for("bfloat16"),
                         918e12)
        self.assertIn("cpu-container", DEVICE_SPECS)
        for row in DEVICE_SPECS.values():
            self.assertGreater(row.hbm_gbs, 0)
            self.assertGreater(row.ici_gbs, 0)
            self.assertGreater(row.ridge_point("bfloat16"), 0)

    def test_get_spec_resolution(self):
        self.assertIs(get_spec("tpu-v5p"), DEVICE_SPECS["tpu-v5p"])
        self.assertIs(get_spec(V5E), V5E)
        # CPU host with no TPU attached: the v5e baseline (prediction
        # targets the serving chip, not the tracing host)
        self.assertIs(get_spec(None), V5E)
        with self.assertRaisesRegex(KeyError, "tpu-v5e"):
            get_spec("nonesuch")

    def test_spec_for_device_kind_matches_bench_switch(self):
        from paddle_tpu.analysis.device_specs import spec_for_device_kind

        self.assertEqual(spec_for_device_kind("TPU v6e").name, "tpu-v6e")
        self.assertEqual(spec_for_device_kind("TPU v5 lite").name,
                         "tpu-v5e")
        self.assertEqual(spec_for_device_kind("TPU v4").name, "tpu-v4")

    def test_unknown_device_kind_raises(self):
        """An attached device the table does not know is an error, not
        the v5e row (PR 22)."""
        from unittest import mock

        import jax

        from paddle_tpu.analysis.device_specs import spec_for_device_kind

        with self.assertRaisesRegex(KeyError, "TPU v9"):
            spec_for_device_kind("TPU v9 mega")

        class _Dev:
            device_kind = "TPU v9 mega"

        with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
                mock.patch.object(jax, "devices", lambda *a: [_Dev()]):
            with self.assertRaisesRegex(KeyError, "TPU v9"):
                get_spec(None)


class TestFlopsBytesReferences(unittest.TestCase):
    """Hand-computed FLOPs/bytes references (ISSUE 13 satellite)."""

    def test_matmul_hand_reference(self):
        def f(x, w):
            return x @ w

        x = jnp.zeros((128, 256), jnp.float32)
        w = jnp.zeros((256, 512), jnp.float32)
        rep = roofline.audit_roofline(f, x, w, device="tpu-v5e")
        self.assertEqual(rep.total_flops, 2 * 128 * 256 * 512)
        self.assertEqual(rep.total_hbm_bytes,
                         (128 * 256 + 256 * 512 + 128 * 512) * 4)
        self.assertEqual(rep.kernel_launches, 1)
        # aligned dims: zero padding waste
        self.assertEqual(rep.padding_waste_flops, 0)
        self.assertEqual(rep.bound, "compute")  # intensity 36 > f32 ridge

    def test_dequant_chain_counts_one_weight_read(self):
        """The int8 weight-only serving contract: w_int8 -> convert ->
        dot reads the weight ONCE at int8 width — elementwise/convert
        links fuse, so the naive operand+result sum (int8 + 2x bf16
        copies) never appears. This is what lets the decode prediction
        track the weight-read bound."""
        def g(x, wq, sc):
            out = jnp.einsum("mk,nk->mn", x, wq.astype(jnp.bfloat16))
            return out * sc

        x = jnp.zeros((8, 256), jnp.bfloat16)
        wq = jnp.zeros((512, 256), jnp.int8)
        sc = jnp.zeros((512,), jnp.float32)
        rep = roofline.audit_roofline(g, x, wq, sc)
        dots = [e for e in rep.events if e.prim == "dot_general"]
        self.assertEqual(len(dots), 1)
        # x bf16 + w int8 + out bf16 — no dequantized copy
        self.assertEqual(dots[0].hbm_bytes,
                         8 * 256 * 2 + 512 * 256 * 1 + 8 * 512 * 2)
        # the fused convert/mul carry zero traffic
        self.assertEqual(sum(e.hbm_bytes for e in rep.events
                             if e.prim in ("convert_element_type",
                                           "mul")), 0)

    def test_gqa_paged_attention_counts_pool_pages(self):
        """The KernelConstraint roofline model: the paged GQA decode
        kernel streams exactly the B x n_blocks pages its table names
        (not the whole pool), and FLOPs = 4·B·Hq·D·ctx."""
        from paddle_tpu.kernels.decode_attention import (
            paged_decode_attention)

        B, HQ, HKV, D, BS, W = 2, 4, 2, 128, 16, 2
        n_pages = 64  # pool much larger than the referenced pages
        kc = jnp.zeros((n_pages, HKV, BS, D), jnp.bfloat16)
        vc = jnp.zeros((n_pages, HKV, BS, D), jnp.bfloat16)
        tbl = jnp.zeros((B, W), jnp.int32)
        lens = jnp.zeros((B,), jnp.int32)
        q = jnp.zeros((B, HQ, D), jnp.bfloat16)
        rep = roofline.audit_roofline(
            lambda q_: paged_decode_attention(q_, kc, vc, tbl, lens), q)
        ker = [e for e in rep.events if e.prim == "pallas_call"]
        self.assertEqual(len(ker), 1)
        ctx = W * BS
        self.assertEqual(ker[0].flops, 4 * (B * HQ * D) * ctx)
        kv_bytes = 2 * B * ctx * HKV * D * 2     # referenced pages only
        q_bytes = 2 * B * HQ * D * 2             # q in + out
        self.assertEqual(ker[0].hbm_bytes, kv_bytes + q_bytes)
        # sanity: the whole pool would have been ~16x bigger
        self.assertLess(ker[0].hbm_bytes,
                        2 * n_pages * HKV * BS * D * 2)

    def test_int8_paged_attention_prices_at_pool_dtype(self):
        """The int8 kernels append f32 scale rows as the LAST pallas
        operands — the event's compute dtype must come from the
        largest operand (the int8 pool), not the scales, or the
        quantized path prices at the f32 MXU rate."""
        from paddle_tpu.kernels.decode_attention import (
            paged_decode_attention)

        B, HQ, HKV, D, BS, W, P = 2, 4, 2, 128, 16, 2, 8
        kc = jnp.zeros((P, HKV, BS, D), jnp.int8)
        vc = jnp.zeros((P, HKV, BS, D), jnp.int8)
        ksc = jnp.zeros((P, HKV), jnp.float32)
        vsc = jnp.zeros((P, HKV), jnp.float32)
        tbl = jnp.zeros((B, W), jnp.int32)
        lens = jnp.zeros((B,), jnp.int32)
        rep = roofline.audit_roofline(
            lambda q: paged_decode_attention(q, kc, vc, tbl, lens,
                                             k_scale=ksc, v_scale=vsc),
            jnp.zeros((B, HQ, D), jnp.bfloat16))
        ker = [e for e in rep.events if e.prim == "pallas_call"]
        self.assertEqual(len(ker), 1)
        self.assertEqual(ker[0].dtype, "int8")
        # scale sidecars counted: int8 pages + 2 x f32 rows per page
        ctx = W * BS
        self.assertEqual(ker[0].hbm_bytes,
                         2 * B * ctx * HKV * D * 1    # int8 pages
                         + 2 * B * W * HKV * 4        # scale rows
                         + 2 * B * HQ * D * 2)        # q in + out

    def test_prefix_prefill_counts_pool_pages_not_pool(self):
        """The prefix-prefill roofline model reads the kernel's real
        operand order (q, pools, [scales], suffix k/v): prefix bytes =
        q_rows · w · page · dh per cache — the table-named pages —
        never the whole pool, and int8 pools price at int8 width."""
        from paddle_tpu.kernels.prefix_prefill import (
            prefix_prefill_attention)

        B, SB, NH, NKV, DH, BS, W, P = 2, 64, 4, 2, 128, 16, 4, 256
        q = jnp.zeros((B, SB, NH, DH), jnp.bfloat16)
        ksuf = jnp.zeros((B, SB, NKV, DH), jnp.bfloat16)
        kc = jnp.zeros((P, NKV, BS, DH), jnp.bfloat16)
        tbl = jnp.zeros((B, W), jnp.int32)
        plens = jnp.full((B,), W * BS, jnp.int32)
        rep = roofline.audit_roofline(
            lambda q_: prefix_prefill_attention(q_, ksuf, ksuf, kc, kc,
                                                tbl, plens), q)
        ker = [e for e in rep.events if e.prim == "pallas_call"]
        self.assertEqual(len(ker), 1)
        # collapsed q rows = B*NKV*nq; blocks fit to the full bucket
        # here (block_q = SB), so nq = 1
        q_rows = B * NKV
        prefix_bytes = 2 * q_rows * W * BS * DH * 2
        suffix_bytes = 2 * B * SB * NKV * DH * 2
        q_bytes = 2 * B * SB * NH * DH * 2
        self.assertEqual(ker[0].hbm_bytes,
                         prefix_bytes + suffix_bytes + q_bytes)
        # the whole 256-page pool would be ~16x the referenced pages
        self.assertLess(ker[0].hbm_bytes, 2 * P * NKV * BS * DH * 2)
        self.assertEqual(ker[0].dtype, "bfloat16")

    def test_scan_layers_amplification(self):
        """n_layers dot sites x scan steps: each site carries
        count=steps, totals multiply out (the PR 11 amplification
        contract, compute-side)."""
        n_layers, steps = 3, 5
        ws = [jnp.zeros((64, 64), jnp.float32) for _ in range(n_layers)]

        def loop(x):
            def step(c, _):
                for w in ws:
                    c = c @ w
                return c, None

            c, _ = jax.lax.scan(step, x, None, length=steps)
            return c

        rep = roofline.audit_roofline(loop, jnp.zeros((8, 64),
                                                      jnp.float32))
        dots = [e for e in rep.events if e.prim == "dot_general"]
        self.assertEqual(len(dots), n_layers)
        self.assertTrue(all(e.count == steps and e.in_loop
                            for e in dots))
        per = 2 * 8 * 64 * 64
        self.assertEqual(sum(e.total_flops for e in dots),
                         n_layers * steps * per)
        self.assertEqual(rep.kernel_launches, n_layers * steps)

    def test_mp2_per_chip_flops_bytes_halve(self):
        """ACCEPTANCE: mp=2 per-chip FLOPs/bytes on sharded eqns are
        exactly half of mp=1 — the shard_map body's local avals carry
        the division."""
        from jax.sharding import PartitionSpec as P

        def f(x, w):
            return x @ w

        x = jnp.zeros((8, 256), jnp.float32)
        w = jnp.zeros((256, 64), jnp.float32)
        rep1 = roofline.audit_roofline(f, x, w)
        d1 = [e for e in rep1.events if e.prim == "dot_general"][0]
        sm = _smap(f, 2, in_specs=(P(), P(None, "mp")),
                   out_specs=P(None, "mp"))
        rep2 = roofline.audit_roofline(sm, x, w)
        d2 = [e for e in rep2.events if e.prim == "dot_general"][0]
        self.assertEqual(rep2.mp, 2)
        self.assertEqual(d2.flops * 2, d1.flops)
        # x replicated (whole), w/out sharded (half each)
        x_b, w_b, o_b = 8 * 256 * 4, 256 * 64 * 4, 8 * 64 * 4
        self.assertEqual(d1.hbm_bytes, x_b + w_b + o_b)
        self.assertEqual(d2.hbm_bytes, x_b + w_b // 2 + o_b // 2)


class TestPredictedStep(unittest.TestCase):
    def test_roofline_terms_and_overhead(self):
        def f(x, w):
            return x @ w

        x = jnp.zeros((1024, 1024), jnp.bfloat16)
        rep = roofline.audit_roofline(f, x, x, device="tpu-v5e")
        self.assertAlmostEqual(
            rep.compute_s, rep.total_flops / V5E.peak_for("bfloat16"))
        self.assertAlmostEqual(rep.bandwidth_s,
                               rep.total_hbm_bytes / V5E.hbm_gbs)
        self.assertAlmostEqual(rep.launch_overhead_s,
                               rep.kernel_launches
                               * V5E.launch_overhead_s)
        self.assertAlmostEqual(
            rep.predicted_step_s,
            max(rep.compute_s, rep.bandwidth_s, rep.wire_s)
            + rep.launch_overhead_s)
        self.assertGreater(rep.predicted_mfu, 0)
        self.assertLessEqual(rep.predicted_mfu, 1.0)

    def test_device_rows_reprice_memoized_pass(self):
        def f(x, w):
            return x @ w

        from paddle_tpu.analysis.memory import trace_auto

        g = trace_auto(f, jnp.zeros((256, 256), jnp.bfloat16),
                       jnp.zeros((256, 256), jnp.bfloat16))
        a = roofline.audit_graph(g, "tpu-v5e")
        b = roofline.audit_graph(g, "tpu-v5p")
        self.assertIs(a, roofline.audit_graph(g, "tpu-v5e"))  # memoized
        self.assertEqual(a.total_flops, b.total_flops)  # one walk
        self.assertGreater(a.compute_s, b.compute_s)    # repriced

    def test_to_json_stable_schema(self):
        def f(x):
            return jnp.sum(x @ x)

        x = jnp.zeros((128, 128), jnp.float32)
        a = roofline.audit_roofline(f, x).to_json()
        b = roofline.audit_roofline(f, x).to_json()
        self.assertEqual(a, b)
        d = json.loads(a)
        for key in ("target", "device", "per_chip", "mp", "flops",
                    "flops_by_dtype", "hbm_bytes", "wire_bytes",
                    "kernel_launches", "compute_ms", "bandwidth_ms",
                    "wire_ms", "launch_overhead_ms",
                    "predicted_step_ms", "predicted_mfu", "bound",
                    "padding_waste_fraction", "bottlenecks"):
            self.assertIn(key, d)


class TestAcceptanceTinyLlamaInt8Decode(unittest.TestCase):
    def test_decode_predicted_bandwidth_bound_near_weight_read(self):
        """ACCEPTANCE: the tiny-llama int8 decode step is predicted
        BANDWIDTH-bound, with predicted ms within 15% of the analytic
        weight-read bound (the `bench_serving.quant_weight_gb` read
        side — int8 projections + bf16 norms — plus the f32 dequant
        scales the formula rounds away). The comparison excludes the
        fixed launch-overhead term because the measured side is a
        paired SLOPE (bench_roofline/bench_serving): fixed per-step
        dispatch cancels in the slope, so the static prediction must
        exclude it too. hidden=128 puts the step in the weight-
        dominated regime the 1B/7B serving bounds live in."""
        from paddle_tpu.models import init_quant_serving_params
        from paddle_tpu.models.llama import _make_decode_step

        cfg = LlamaConfig.tiny(hidden_size=128, intermediate_size=256)
        p = init_quant_serving_params(cfg, "weight_only_int8", seed=0)
        b, max_seq = 1, 16
        nkv, dh = cfg.num_key_value_heads, cfg.head_dim
        step = _make_decode_step(cfg, b, max_seq)
        kcs = [jnp.zeros((b, nkv, max_seq, dh), jnp.bfloat16)
               for _ in range(cfg.num_hidden_layers)]
        spec = dataclasses.replace(get_spec("tpu-v5e"),
                                   launch_overhead_s=0.0)
        rep = roofline.audit_roofline(
            step, p, kcs, list(kcs), jnp.ones((b, 1), jnp.int32),
            jnp.asarray(4, jnp.int32), device=spec)
        self.assertEqual(rep.bound, "bandwidth")
        h, im, v = (cfg.hidden_size, cfg.intermediate_size,
                    cfg.vocab_size)
        L = cfg.num_hidden_layers
        proj = L * (2 * h * h + 2 * h * nkv * dh + 3 * h * im) + h * v
        norms = (2 * L + 1) * h
        scales = L * (3 * h + 2 * nkv * dh + 2 * im) + v
        bound_ms = (proj + norms * 2 + scales * 4) / spec.hbm_gbs * 1e3
        ratio = rep.predicted_step_ms / bound_ms
        self.assertLessEqual(abs(ratio - 1.0), 0.15,
                             f"predicted {rep.predicted_step_ms} ms vs "
                             f"weight-read bound {bound_ms} ms "
                             f"(ratio {ratio:.3f})")


class TestRules(unittest.TestCase):
    """TPU901/902/903 fire-and-silent pairs."""

    def test_tpu901_fires_on_low_intensity_scan(self):
        """ACCEPTANCE (fire half): a thin matmul re-reading a 16 MiB
        operand every scan iteration — intensity ~4 vs the f32 ridge
        ~30, amplified HBM time ~1.3 ms — is named at DEFAULT
        thresholds."""
        w = jnp.zeros((2048, 8), jnp.float32)

        def loop(x):
            def step(c, _):
                return c + (x @ w), None

            c, _ = jax.lax.scan(step, jnp.zeros((2048, 8), jnp.float32),
                                None, length=64)
            return c

        r = analyze(loop, jnp.zeros((2048, 2048), jnp.float32),
                    rules=["TPU901"])
        hits = r.by_rule().get("TPU901", [])
        self.assertEqual(len(hits), 1)
        self.assertEqual(hits[0].severity, Severity.WARNING)
        self.assertIn("x 64 iterations", hits[0].message)
        self.assertIn("ridge", hits[0].message)

    def test_tpu901_silent_on_flash_attention(self):
        """ACCEPTANCE (silent half): flash attention in a hot loop sits
        ABOVE the ridge (the kernel exists so the S^2 score matrix
        never round-trips HBM) — no TPU901."""
        from paddle_tpu.kernels.flash_attention import flash_attention

        k = jnp.zeros((1, 1024, 2, 64), jnp.bfloat16)

        def loop(q):
            def step(c, _):
                return flash_attention(c, k, k, causal=False), None

            c, _ = jax.lax.scan(step, q, None, length=8)
            return c

        q = jnp.zeros((1, 1024, 2, 64), jnp.bfloat16)
        from paddle_tpu.analysis.memory import trace_auto

        g = trace_auto(loop, q)
        # the kernel IS in the trace and modeled compute-side
        rep = roofline.audit_graph(g)
        ker = [e for e in rep.events if e.prim == "pallas_call"]
        self.assertTrue(ker)
        self.assertGreater(ker[0].intensity,
                           rep.spec.ridge_point("bfloat16"))
        self.assertEqual(len(analyze(None, graph=g,
                                     rules=["TPU901"])), 0)

    def test_tpu901_min_ms_floors_small_streams(self):
        def loop(x):
            def step(c, _):
                return c + (x @ jnp.zeros((64, 8), jnp.float32)), None

            c, _ = jax.lax.scan(step, jnp.zeros((64, 8), jnp.float32),
                                None, length=4)
            return c

        from paddle_tpu.analysis.memory import trace_auto

        g = trace_auto(loop, jnp.zeros((64, 64), jnp.float32))
        self.assertEqual(len(analyze(None, graph=g,
                                     rules=["TPU901"])), 0)
        tightened = analyze(None, graph=g, rules=["TPU901"],
                            rule_config={"TPU901.min_amplified_ms":
                                         1e-9})
        self.assertGreaterEqual(len(tightened), 1)

    def test_tpu902_fires_and_silent_pair(self):
        def f(x, w):
            return x @ w

        # K=100 pads to 128, N=1000 to 1024: ~24% of padded FLOPs
        # wasted, 62 MFLOP — over both default floors
        r = analyze(f, jnp.zeros((1000, 100), jnp.float32),
                    jnp.zeros((100, 1000), jnp.float32),
                    rules=["TPU902"])
        hits = r.by_rule().get("TPU902", [])
        self.assertEqual(len(hits), 1)
        self.assertIn("tile padding", hits[0].message)
        # aligned: silent
        r2 = analyze(f, jnp.zeros((1024, 1024), jnp.float32),
                     jnp.zeros((1024, 1024), jnp.float32),
                     rules=["TPU902"])
        self.assertEqual(len(r2.by_rule().get("TPU902", [])), 0)

    def test_tpu903_fires_and_silent_pair(self):
        """800 amplified tiny-dot launches = ~0.4 ms of predicted
        dispatch dominating a near-zero roofline -> fires; one big
        matmul launch stays silent."""
        ws = [jnp.zeros((64, 64), jnp.float32) for _ in range(4)]

        def loop(x):
            def step(c, _):
                for w in ws:
                    c = c @ w
                return c, None

            c, _ = jax.lax.scan(step, x, None, length=200)
            return c

        r = analyze(loop, jnp.zeros((8, 64), jnp.float32),
                    rules=["TPU903"])
        hits = r.by_rule().get("TPU903", [])
        self.assertEqual(len(hits), 1)
        self.assertIn("800 kernel launches", hits[0].message)
        self.assertIn("fuse", hits[0].hint)
        big = analyze(lambda x, w: x @ w,
                      jnp.zeros((1024, 1024), jnp.bfloat16),
                      jnp.zeros((1024, 1024), jnp.bfloat16),
                      rules=["TPU903"])
        self.assertEqual(len(big.by_rule().get("TPU903", [])), 0)

    def test_rule_device_config_routes(self):
        """TPU901.device prices against the requested row: the same
        graph is bandwidth-bound on v5e terms either way, but the
        knob must not crash and must change the ridge in the
        message."""
        w = jnp.zeros((2048, 8), jnp.float32)

        def loop(x):
            def step(c, _):
                return c + (x @ w), None

            c, _ = jax.lax.scan(step, jnp.zeros((2048, 8), jnp.float32),
                                None, length=64)
            return c

        # v5p's 3.4x bandwidth drops the amplified stream under the
        # default 0.5 ms floor — lower it so the row swap itself is
        # what's under test
        r = analyze(loop, jnp.zeros((2048, 2048), jnp.float32),
                    rules=["TPU901"],
                    rule_config={"TPU901.device": "tpu-v5p",
                                 "TPU901.min_amplified_ms": 0.1})
        hits = r.by_rule().get("TPU901", [])
        self.assertEqual(len(hits), 1)
        self.assertIn("tpu-v5p", hits[0].message)


class TestKernelWalkerHoist(unittest.TestCase):
    """The _count_step_kernels satellite: ONE walker, three consumers."""

    def test_count_matches_bench_delegate(self):
        def step(x, w):
            return jnp.tanh(x @ w) @ w

        x = jnp.zeros((64, 64), jnp.float32)
        self.assertEqual(roofline.count_step_kernels(step, x, x), 2)
        import bench

        self.assertEqual(bench._count_step_kernels(step, x, x), 2)

    def test_tpu105_shares_the_prim_inventory(self):
        from paddle_tpu.analysis.rules import FusionMissRule

        self.assertIs(FusionMissRule().KERNEL_PRIMS,
                      roofline.KERNEL_LAUNCH_PRIMS)

    def test_scan_bodies_count_once_unamplified(self):
        def loop(x, w):
            def step(c, _):
                return c @ w, None

            c, _ = jax.lax.scan(step, x, None, length=16)
            return c

        x = jnp.zeros((8, 64), jnp.float32)
        w = jnp.zeros((64, 64), jnp.float32)
        # bench semantics: launches per jaxpr, NOT amplified
        self.assertEqual(roofline.count_step_kernels(loop, x, w), 1)
        # the roofline launch term IS amplified
        rep = roofline.audit_roofline(loop, x, w)
        self.assertEqual(rep.kernel_launches, 16)


def _tiny_engine(**kw):
    cfg = LlamaConfig.tiny()
    paddle.seed(7)
    model = LlamaForCausalLM(cfg)
    return ContinuousBatchingEngine(
        cfg, dict(model.raw_state()), slots=4, prompt_bucket=16,
        max_prompt_len=32, max_new_tokens=8, block_size=16,
        steps_per_sync=4, prefill_batch=2, **kw), cfg


class TestEngineAudit(unittest.TestCase):
    def test_decode_chunk_predicted_bandwidth_bound(self):
        eng, cfg = _tiny_engine()
        rep = eng.audit_roofline(programs=("decode",))
        self.assertTrue(rep["partial"])
        dec = rep["programs"]["decode"]
        self.assertEqual(dec["bound"], "bandwidth")
        self.assertGreater(dec["predicted_step_ms"], 0)
        self.assertGreater(dec["flops"], 0)
        self.assertGreater(dec["kernel_launches"], 0)
        self.assertEqual(rep["device"], "tpu-v5e")
        # per-token division: steps_per_sync x slots
        self.assertAlmostEqual(
            rep["predicted_ms_per_token"],
            rep["predicted_step_ms"] / (eng.steps * eng.slots))

    def test_partial_vs_fleet_sinks_and_gauges(self):
        from paddle_tpu.observability import MetricsRegistry

        mt = MetricsRegistry()
        eng, _ = _tiny_engine(metrics=mt)
        partial = eng.audit_roofline(programs=("decode",))
        self.assertTrue(partial["partial"])
        self.assertEqual(mt.events("roofline.audit"), [])
        self.assertIsNone(eng.metrics()["roofline_audit"])
        with self.assertRaisesRegex(ValueError, "nonesuch"):
            eng.audit_roofline(programs=("nonesuch",))
        full = eng.audit_roofline()
        self.assertFalse(full["partial"])
        self.assertIs(eng.metrics()["roofline_audit"], full)
        events = mt.events("roofline.audit")
        self.assertEqual(len(events), 1)
        self.assertEqual(events[0]["device"], "tpu-v5e")
        snap = mt.snapshot()
        self.assertIn("predicted_step_ms", snap["gauges"])
        self.assertIn("predicted_mfu", snap["gauges"])

    def test_warm_hook_and_device_override(self):
        eng, _ = _tiny_engine()
        eng.warm([16], audit_roofline=True)
        fleet = eng.metrics()["roofline_audit"]
        self.assertIsNotNone(fleet)
        self.assertGreaterEqual(fleet["programs_audited"], 2)
        for name, prog in fleet["programs"].items():
            self.assertIn(prog["bound"],
                          ("compute", "bandwidth", "wire"), name)
        # an explicit row reprices the same traced fleet
        v5p = eng.audit_roofline(device="tpu-v5p",
                                 programs=("decode",))
        self.assertEqual(v5p["device"], "tpu-v5p")
        self.assertLess(
            v5p["programs"]["decode"]["bandwidth_ms"],
            fleet["programs"]["decode"]["bandwidth_ms"])

    def test_custom_spec_prices_rules_and_report_together(self):
        """A caller-built DeviceSpec (no table row) must drive BOTH the
        report numbers and the TPU90x diagnostics — contradictory
        'below the tpu-v5e ridge' findings on a custom-row report
        would be wrong."""
        sim = dataclasses.replace(DEVICE_SPECS["tpu-v5e"],
                                  name="my-sim",
                                  launch_overhead_s=1.0)  # absurd: 1 s
        eng, _ = _tiny_engine()
        rep = eng.audit_roofline(device=sim, programs=("decode",))
        self.assertEqual(rep["device"], "my-sim")
        dec = rep["programs"]["decode"]
        # the rules priced on the SAME spec: the 1 s/launch overhead
        # dominates every step, so TPU903 must fire
        self.assertIn("TPU903",
                      [d["rule"] for d in dec["diagnostics"]])
        self.assertGreater(dec["launch_overhead_ms"], 1000)

    def test_flag_composition(self):
        from paddle_tpu.analysis.roofline import resolve_audit_roofline

        prev = paddle.get_flags(["tpu_lint", "audit_roofline"])
        try:
            paddle.set_flags({"tpu_lint": True, "audit_roofline": False})
            self.assertTrue(resolve_audit_roofline(None))
            paddle.set_flags({"tpu_lint": False})
            self.assertFalse(resolve_audit_roofline(None))
            paddle.set_flags({"audit_roofline": True})
            self.assertTrue(resolve_audit_roofline(None))
            self.assertFalse(resolve_audit_roofline(False))
        finally:
            paddle.set_flags({k.replace("FLAGS_", ""): v
                              for k, v in prev.items()})


class TestCostModelShim(unittest.TestCase):
    def test_static_estimate_beside_measured_table(self):
        from paddle_tpu.cost_model import CostModel

        cm = CostModel()
        est = cm.static_estimate(
            lambda x, w: x @ w,
            jnp.zeros((128, 256), jnp.bfloat16),
            jnp.zeros((256, 512), jnp.bfloat16), name="mm")
        for key in ("time", "bound", "mfu", "flops", "hbm_bytes",
                    "kernel_launches", "device"):
            self.assertIn(key, est)
        self.assertEqual(est["flops"], 2 * 128 * 256 * 512)
        table = cm.static_cost_data()
        self.assertEqual(table["static:mm"], est["time"])


class TestFitAudit(unittest.TestCase):
    def _model(self, width=64):
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

    def test_fit_audit_roofline_traces_training_step(self):
        model, batches = self._model()
        model.fit(batches, epochs=1, verbose=0, audit_roofline=True)
        a = model.roofline_audit
        self.assertIsNotNone(a)
        self.assertEqual(a["target"], "fit.step")
        self.assertIn(a["bound"], ("compute", "bandwidth", "wire"))
        self.assertGreater(a["flops"], 0)
        # fwd + bwd: the fwd matmul and the dW grad matmul (dx is
        # dead — the grad is w.r.t. params only)
        self.assertGreaterEqual(a["kernel_launches"], 2)
        self.assertIn("diagnostics", a)

    def test_fit_audit_dp_mesh_audits_sharded_step(self):
        """Under a dp mesh the roofline hook audits the SAME sharded
        step the comms hook builds — per-chip FLOPs halve and the dp
        gradient psum shows up as wire bytes (not the un-sharded
        global-batch step)."""
        from paddle_tpu.parallel import mesh as mesh_mod

        prev = mesh_mod.get_global_mesh()
        try:
            mesh_mod.set_global_mesh(mesh_mod.build_mesh(
                {"dp": 2}, devices=jax.devices()[:2]))
            model, batches = self._model()
            model.fit(batches, epochs=1, verbose=0,
                      audit_roofline=True)
        finally:
            mesh_mod.set_global_mesh(prev)
        a = model.roofline_audit
        self.assertEqual(a["target"], "fit.step[dp=2]")
        self.assertEqual(a["mp"], 2)
        self.assertGreater(a["wire_bytes"], 0)  # the dp grad psum

    def test_fit_both_audits_share_one_trace(self):
        """fit with comms AND roofline on (the PADDLE_TPU_LINT=1
        shape) traces the training step ONCE — the shared Graph serves
        both memoized passes (the fit-side twin of the engine's shared
        _traced_inventory)."""
        from unittest import mock

        from paddle_tpu.analysis import memory as _mem

        model, batches = self._model()
        with mock.patch.object(_mem, "trace_auto",
                               wraps=_mem.trace_auto) as spy:
            model.fit(batches, epochs=1, verbose=0, audit_comms=True,
                      audit_roofline=True)
        self.assertEqual(spy.call_count, 1)
        self.assertIsNotNone(model.comms_audit)
        self.assertIsNotNone(model.roofline_audit)
        self.assertEqual(model.comms_audit["target"],
                         model.roofline_audit["target"])

    def test_fit_audit_off_by_default(self):
        model, batches = self._model(width=8)
        model.fit(batches, epochs=1, verbose=0)
        self.assertIsNone(model.roofline_audit)


class TestCLIRooflineJSON(unittest.TestCase):
    def test_cli_roofline_json_schema_and_gate(self):
        """The CI gate (ISSUE 13 satellite): `python -m
        paddle_tpu.analysis --roofline --format json` over the
        tiny-llama paged decode demo emits one valid JSON object with
        the documented schema and exits 0; `--fail-on warning` exits 1
        with TPU902 naming the b=1 decode padding — the scriptable
        gate, mirroring the `--memory`/`--comms` tests."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        cwd = os.path.dirname(os.path.dirname(__file__))
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.analysis", "--roofline",
             "--format", "json"],
            capture_output=True, text=True, env=env, cwd=cwd,
            timeout=300)
        self.assertEqual(proc.returncode, 0, proc.stderr[-2000:])
        d = json.loads(proc.stdout)
        self.assertEqual(sorted(d),
                         ["counts", "diagnostics", "roofline", "target"])
        r = d["roofline"]
        for key in ("device", "bound", "predicted_step_ms",
                    "predicted_mfu", "flops", "hbm_bytes",
                    "kernel_launches", "launch_overhead_ms",
                    "bottlenecks", "per_chip"):
            self.assertIn(key, r)
        self.assertEqual(r["device"], "tpu-v5e")
        self.assertEqual(r["bound"], "bandwidth")
        self.assertGreater(r["predicted_step_ms"], 0)
        # the scriptable gate: warning-severity findings exit non-zero
        gated = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.analysis", "--roofline",
             "--format", "json", "--device", "tpu-v5e",
             "--fail-on", "warning"],
            capture_output=True, text=True, env=env, cwd=cwd,
            timeout=300)
        self.assertEqual(gated.returncode, 1, gated.stderr[-2000:])
        gd = json.loads(gated.stdout)
        self.assertIn("TPU902",
                      [x["rule"] for x in gd["diagnostics"]])


if __name__ == "__main__":
    unittest.main()
