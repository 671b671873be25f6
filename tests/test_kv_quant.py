"""int8 paged KV cache (FLAGS_kv_cache_dtype, ISSUE 5): parity of the
dequantize-in-kernel paged decode and prefix-prefill paths against the
bf16/f32 references within symmetric-absmax quantization tolerance —
across GQA ratios, ragged prefix/suffix lengths and pad rows — plus the
engine-level guards: greedy-token match rate vs the bf16 engine over
shared-prefix traffic, zero recompiles after warm() on the int8 path,
and the capacity math (an int8 pool holds ~2x the pages of a bf16 pool
at the same byte budget)."""
import dataclasses
import math
import unittest

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.kernels import prefix_prefill as pp
from paddle_tpu.kernels.decode_attention import paged_decode_attention
from paddle_tpu.models import PagedKVManager, quantize_kv_pages

# absmax int8 keeps each element within scale/2 = absmax/254 of its f32
# value; through one masked softmax that lands comfortably inside this
# bar on O(1)-scale inputs (measured ~1.5e-2 max abs err on gaussian
# K/V) — the tolerance documented in serving/README.md
QUANT_TOL = 5e-2


def _quant_pool(pool):
    """(int8 pool, per-(page, head) scale) via the exported helper —
    reshaped through the page-stack layout quantize_kv_pages reduces
    over."""
    q, s = quantize_kv_pages(jnp.asarray(pool))
    return q, s


def _dequant(q, s):
    return q.astype(jnp.float32) * s[..., None, None]


def _paged_oracle(q, kc, vc, tables, lens):
    """f32 gathered masked-softmax decode oracle (any GQA ratio)."""
    B, HQ, D = q.shape
    HK, BS = kc.shape[1], kc.shape[2]
    NBLK = tables.shape[1]
    g = HQ // HK
    kl = jnp.transpose(kc[tables], (0, 2, 1, 3, 4)).reshape(
        B, HK, NBLK * BS, D).astype(jnp.float32)
    vl = jnp.transpose(vc[tables], (0, 2, 1, 3, 4)).reshape(
        B, HK, NBLK * BS, D).astype(jnp.float32)
    qg = q.astype(jnp.float32).reshape(B, HK, g, D)
    s = jnp.einsum("bkgd,bksd->bkgs", qg, kl) / math.sqrt(D)
    valid = jnp.arange(NBLK * BS)[None, None, None, :] <= \
        lens[:, None, None, None]
    p = jax.nn.softmax(jnp.where(valid, s, -1e30), axis=-1)
    return jnp.einsum("bkgs,bksd->bkgd", p, vl).reshape(B, HQ, D)


class TestQuantizeRoundtrip(unittest.TestCase):
    def test_roundtrip_within_half_step(self):
        rng = np.random.default_rng(0)
        kv = jnp.asarray(rng.normal(size=(2, 3, 2, 8, 16)), jnp.float32)
        q, s = quantize_kv_pages(kv)
        self.assertEqual(q.dtype, jnp.int8)
        self.assertEqual(s.shape, (2, 3, 2))
        back = q.astype(jnp.float32) * s[..., None, None]
        step = np.asarray(s)[..., None, None]
        err = np.abs(np.asarray(back) - np.asarray(kv))
        self.assertTrue((err <= step / 2 + 1e-7).all())

    def test_zero_page_stays_exact_zero(self):
        kv = jnp.zeros((1, 1, 2, 8, 16))
        q, s = quantize_kv_pages(kv)
        np.testing.assert_array_equal(np.asarray(q), 0)
        np.testing.assert_array_equal(np.asarray(s), 0.0)

    def test_bf16_inputs_absmax_in_f32(self):
        # the scale comes out f32 even from bf16 pages
        kv = jnp.asarray(np.random.default_rng(1).normal(
            size=(1, 2, 2, 8, 16)), jnp.bfloat16)
        _, s = quantize_kv_pages(kv)
        self.assertEqual(s.dtype, jnp.float32)


class TestPagedDecodeInt8Parity(unittest.TestCase):
    def _case(self, B, HQ, HK, D, BS=8, NBLK=4, seed=0):
        rng = np.random.default_rng(seed)
        max_pages = B * NBLK + 1
        kc = jnp.asarray(rng.normal(size=(max_pages, HK, BS, D)),
                         jnp.float32)
        vc = jnp.asarray(rng.normal(size=(max_pages, HK, BS, D)),
                         jnp.float32)
        q = jnp.asarray(rng.normal(size=(B, HQ, D)), jnp.float32)
        tables = jnp.asarray(
            rng.permutation(max_pages - 1)[:B * NBLK].reshape(B, NBLK)
            + 1, jnp.int32)
        lens = jnp.asarray(rng.integers(1, NBLK * BS, B), jnp.int32)
        kq, ks = _quant_pool(kc)
        vq, vs = _quant_pool(vc)
        out = paged_decode_attention(q, kq, vq, tables, lens,
                                     k_scale=ks, v_scale=vs)
        # exact (kernel-roundoff) vs the oracle over DEQUANTIZED pools:
        # the in-kernel dequant must be the same math
        ref_dq = _paged_oracle(q, _dequant(kq, ks), _dequant(vq, vs),
                               tables, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_dq),
                                   rtol=1e-5, atol=1e-5)
        # quantization tolerance vs the ORIGINAL f32 pools
        ref = _paged_oracle(q, kc, vc, tables, lens)
        err = float(jnp.max(jnp.abs(out - ref)))
        self.assertLess(err, QUANT_TOL,
                        f"quant err {err} at HQ={HQ} HK={HK} D={D}")

    def test_gqa_group_2(self):
        self._case(3, 4, 2, 16)

    def test_gqa_group_4(self):
        self._case(2, 8, 2, 16, seed=1)

    def test_full_mqa(self):
        self._case(2, 4, 1, 16, seed=2)

    def test_equal_heads_group_1(self):
        # D=16 routes group=1 through the grouped kernel
        self._case(2, 4, 4, 16, seed=3)

    def test_equal_heads_lane_aligned_kernel(self):
        # D=128, Hq == Hkv: the non-GQA `_paged_decode_q8_kernel` grid
        self._case(2, 4, 4, 128, seed=4)

    def test_scales_required_for_int8(self):
        kq = jnp.zeros((3, 2, 8, 16), jnp.int8)
        with self.assertRaisesRegex(ValueError, "k_scale"):
            paged_decode_attention(
                jnp.zeros((1, 4, 16)), kq, kq,
                jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32))

    def test_scales_rejected_for_bf16(self):
        kc = jnp.zeros((3, 2, 8, 16), jnp.bfloat16)
        with self.assertRaisesRegex(ValueError, "only apply"):
            paged_decode_attention(
                jnp.zeros((1, 4, 16), jnp.bfloat16), kc, kc,
                jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
                k_scale=jnp.zeros((3, 2)), v_scale=jnp.zeros((3, 2)))


class TestPrefixPrefillInt8Parity(unittest.TestCase):
    def _case(self, b, sb, nh, nkv, dh, bs, w, plens_blocks, slens,
              seed=0, **kw):
        rng = np.random.default_rng(seed)
        npages = b * w + 2
        q = jnp.asarray(rng.normal(size=(b, sb, nh, dh)), jnp.float32)
        ks = jnp.asarray(rng.normal(size=(b, sb, nkv, dh)), jnp.float32)
        vs = jnp.asarray(rng.normal(size=(b, sb, nkv, dh)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(npages, nkv, bs, dh)),
                         jnp.float32)
        vc = jnp.asarray(rng.normal(size=(npages, nkv, bs, dh)),
                         jnp.float32)
        tables = jnp.asarray(
            rng.permutation(npages - 1)[:b * w].reshape(b, w) + 1,
            jnp.int32)
        plens = jnp.asarray([pb * bs for pb in plens_blocks], jnp.int32)
        slens_a = jnp.asarray(slens, jnp.int32)
        kq, ksc = _quant_pool(kc)
        vq, vsc = _quant_pool(vc)
        out = pp.prefix_prefill_attention(
            q, ks, vs, kq, vq, tables, plens, slens_a,
            k_scale=ksc, v_scale=vsc, **kw)
        # pad query rows stay exact zeros on the int8 path too
        for row in range(b):
            np.testing.assert_array_equal(
                np.asarray(out, np.float32)[row, slens[row]:], 0.0,
                err_msg=f"int8 pad rows of row {row} must be zeros")
        # exact vs the int8-aware reference (the fallback/oracle math)
        ref = pp.prefix_prefill_reference(
            q, ks, vs, kq, vq, tables, plens, k_scale=ksc, v_scale=vsc)
        for row in range(b):
            np.testing.assert_allclose(
                np.asarray(out, np.float32)[row, :slens[row]],
                np.asarray(ref, np.float32)[row, :slens[row]],
                rtol=2e-5, atol=2e-5,
                err_msg=f"row {row} vs int8 reference")
        # quantization tolerance vs the ORIGINAL pools
        ref0 = pp.prefix_prefill_reference(q, ks, vs, kc, vc, tables,
                                           plens)
        for row in range(b):
            err = float(np.max(np.abs(
                np.asarray(out, np.float32)[row, :slens[row]]
                - np.asarray(ref0, np.float32)[row, :slens[row]])))
            self.assertLess(err, QUANT_TOL, f"row {row} quant err {err}")

    def test_ragged_gqa_with_pad_rows_and_empty_prefix(self):
        self._case(3, 16, 4, 2, 16, 8, 3, (3, 1, 0), (16, 9, 5))

    def test_equal_heads_group_one(self):
        self._case(2, 16, 4, 4, 16, 8, 2, (2, 0), (16, 3), seed=1)

    def test_mqa_full_group(self):
        self._case(2, 8, 4, 1, 16, 8, 2, (1, 2), (8, 1), seed=2)

    def test_multi_tile_streaming_explicit_blocks(self):
        self._case(2, 32, 4, 2, 16, 8, 2, (2, 1), (32, 17), seed=3,
                   block_q=8, block_s=16)

    def test_reference_requires_scales_for_int8(self):
        kq = jnp.zeros((3, 2, 8, 16), jnp.int8)
        with self.assertRaisesRegex(ValueError, "k_scale"):
            pp.prefix_prefill_reference(
                jnp.zeros((1, 8, 2, 16)), jnp.zeros((1, 8, 2, 16)),
                jnp.zeros((1, 8, 2, 16)), kq, kq,
                jnp.zeros((1, 1), jnp.int32), jnp.zeros((1,), jnp.int32))

    def test_fit_blocks_int8_cap_doubles(self):
        # at a huge suffix the cap binds; int8 rows are half the bytes,
        # so the fitted suffix block may only grow, never shrink
        bq2, bs2 = pp.fit_blocks(1 << 14, 64, 4, 128, kv_itemsize=2)
        bq1, bs1 = pp.fit_blocks(1 << 14, 64, 4, 128, kv_itemsize=1)
        self.assertEqual(bq1, bq2)  # q tiles are bf16 either way
        self.assertGreaterEqual(bs1, bs2)
        self.assertEqual(bs1 % 64, 0)


def _tiny_setup(nkv=2, seed=21):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = dataclasses.replace(LlamaConfig.tiny(), num_key_value_heads=nkv)
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    return cfg, model, dict(model.raw_state())


class TestEngineInt8(unittest.TestCase):
    def _serve(self, cfg, params, prompts, kv, **over):
        from paddle_tpu.serving import ContinuousBatchingEngine

        kw = dict(slots=2, prompt_bucket=8, max_prompt_len=24,
                  max_new_tokens=6, block_size=8, steps_per_sync=3,
                  prefill_batch=2, prefix_cache=True, kv_cache_dtype=kv)
        kw.update(over)
        eng = ContinuousBatchingEngine(cfg, params, **kw)
        for pr in prompts:
            eng.add_request(pr)
        eng.run(max_iters=300)
        return eng, {r.req_id: r.tokens for r in eng.finished}

    @pytest.mark.slow  # tier-1 budget: int8 engine traffic stays
    # covered by the parity suites above + the bench traces carry the
    # >=99% match bar; run explicitly with -m slow
    def test_token_match_rate_vs_bf16_over_shared_prefix(self):
        """The engine-level accuracy guard: int8 greedy tokens over
        shared-prefix traffic agree with the bf16 engine on the vast
        majority of positions. (Exact identity is NOT the contract —
        absmax quantization legitimately flips near-tie argmaxes, and
        one flip cascades through the rest of that request's greedy
        sequence; the serving bar on the real bench traces is >= 99%
        token match, asserted on silicon via bench_continuous.)"""
        cfg, _, params = _tiny_setup()
        rng = np.random.default_rng(3)
        shared = rng.integers(1, cfg.vocab_size, (16,)).tolist()
        prompts = [shared + rng.integers(1, cfg.vocab_size, (n,)).tolist()
                   for n in (3, 7, 2, 5, 6, 4)]
        e8, t8 = self._serve(cfg, params, prompts, "int8")
        eb, tb = self._serve(cfg, params, prompts, "bf16")
        self.assertEqual(len(t8), len(prompts))
        self.assertEqual(len(tb), len(prompts))
        total = agree = perfect = 0
        for rid in tb:
            a, b = np.asarray(tb[rid]), np.asarray(t8[rid])
            n = min(len(a), len(b))
            total += n
            agree += int((a[:n] == b[:n]).sum())
            perfect += int(len(a) == len(b) and (a == b).all())
        self.assertGreaterEqual(agree / total, 0.8,
                                f"match rate {agree / total:.3f}")
        self.assertGreaterEqual(perfect, len(prompts) - 2,
                                "more than 2 requests diverged")
        # both engines exercised the cached-prefix path equally
        self.assertGreater(e8.prefix_hit_tokens, 0)
        self.assertEqual(e8.prefix_hit_tokens, eb.prefix_hit_tokens)
        # full drain: every page back except scratch
        self.assertEqual(e8.mgr.n_available, e8.mgr.max_pages - 1)

    @pytest.mark.slow  # tier-1 budget: the mixed-traffic and mp=2
    # zero-recompile guards (test_serving_engine / test_serving_mp)
    # keep the warm()-covers-every-key contract in tier-1
    def test_zero_recompiles_after_warm_int8(self):
        """The int8 path keeps the steady-state compile guarantee:
        after warm() covering the traffic's buckets, serving mixed
        cold/cached traffic grows no jit cache entry."""
        cfg, _, params = _tiny_setup()
        from paddle_tpu.serving import ContinuousBatchingEngine

        eng = ContinuousBatchingEngine(
            cfg, params, slots=2, prompt_bucket=8, max_prompt_len=24,
            max_new_tokens=6, block_size=8, steps_per_sync=3,
            prefill_batch=2, prefix_cache=True, kv_cache_dtype="int8",
            unified_step=False)  # split program keys under test
        eng.warm([8, 16, 24])
        before = eng.compile_stats()
        self.assertTrue(all(":int8" in k or k == "decode"
                            for k in before))
        rng = np.random.default_rng(5)
        shared = rng.integers(1, cfg.vocab_size, (16,)).tolist()
        for n in (3, 8, 2, 7, 5):
            eng.add_request(shared + rng.integers(
                1, cfg.vocab_size, (n,)).tolist())
        eng.run(max_iters=300)
        self.assertEqual(len(eng.finished), 5)
        self.assertEqual(eng.compile_stats(), before)

    @pytest.mark.slow  # tier-1 budget: the match-rate guard above
    # already serves this traffic end-to-end on the int8 path; this
    # adds the kernel-on-vs-off identity (2 more full engine runs)
    def test_int8_engine_tokens_kernel_on_vs_off(self):
        """On the int8 path too, the prefix-prefill KERNEL changes cost,
        never tokens: kernel on (Pallas interpret) == masked-softmax
        fallback (which dequantizes at the gather)."""
        cfg, _, params = _tiny_setup()
        rng = np.random.default_rng(7)
        shared = rng.integers(1, cfg.vocab_size, (16,)).tolist()
        prompts = [shared + rng.integers(1, cfg.vocab_size, (n,)).tolist()
                   for n in (3, 6, 2, 5)]

        def serve(kernel_on):
            prev = paddle.get_flags("prefix_prefill_kernel")[
                "FLAGS_prefix_prefill_kernel"]
            paddle.set_flags({"prefix_prefill_kernel": kernel_on})
            try:
                return self._serve(cfg, params, prompts, "int8")[1]
            finally:
                paddle.set_flags({"prefix_prefill_kernel": prev})

        self.assertEqual(serve(True), serve(False))


class TestCapacityMath(unittest.TestCase):
    def test_int8_pool_holds_2x_pages_per_byte_budget(self):
        kw = dict(n_layers=2, num_kv_heads=2, head_dim=16)
        bf16 = PagedKVManager.page_bytes(8, kv_cache_dtype="bf16", **kw)
        q8 = PagedKVManager.page_bytes(8, kv_cache_dtype="int8", **kw)
        # int8 page = half the bf16 bytes + the f32 scale rows
        self.assertLess(q8, 0.55 * bf16)
        budget = 64 * bf16
        n_bf16 = PagedKVManager.pages_for_bytes(
            budget, 8, kv_cache_dtype="bf16", **kw)
        n_q8 = PagedKVManager.pages_for_bytes(
            budget, 8, kv_cache_dtype="int8", **kw)
        self.assertEqual(n_bf16, 64)
        self.assertGreaterEqual(n_q8, int(1.8 * n_bf16))

    def test_engine_kv_pool_bytes_and_n_cacheable(self):
        cfg, _, params = _tiny_setup()
        from paddle_tpu.serving import ContinuousBatchingEngine

        kw = dict(slots=2, prompt_bucket=8, max_prompt_len=16,
                  max_new_tokens=6, block_size=8, prefix_cache=True)
        eb = ContinuousBatchingEngine(cfg, params, kv_cache_dtype="bf16",
                                      **kw)
        budget = eb.mgr.kv_pool_bytes()
        # same byte budget, int8 pools: ~2x the cacheable pages
        e8 = ContinuousBatchingEngine(cfg, params, kv_cache_dtype="int8",
                                      kv_pool_bytes=budget, **kw)
        self.assertGreaterEqual(e8.n_cacheable_pages,
                                int(1.8 * eb.n_cacheable_pages))
        self.assertLessEqual(e8.mgr.kv_pool_bytes(), budget)
        # capacity math in PAGES is dtype-independent
        self.assertEqual(e8._capacity_pages_for(16, 6),
                         eb._capacity_pages_for(16, 6))
        with self.assertRaisesRegex(ValueError, "not both"):
            ContinuousBatchingEngine(cfg, params, kv_cache_dtype="int8",
                                     kv_pool_bytes=budget, max_pages=8,
                                     **kw)

    def test_geometry_required_for_pool_bytes(self):
        mgr = PagedKVManager(4, 8)
        with self.assertRaisesRegex(RuntimeError, "set_pool_geometry"):
            mgr.kv_pool_bytes()


class TestKVQuantLint(unittest.TestCase):
    """TPU103 + the q8 KernelConstraint registrations (TPU102 covers
    the int8 kernels)."""

    def test_q8_constraints_registered(self):
        from paddle_tpu import kernels
        from paddle_tpu.kernels import decode_attention as da

        c = kernels.KERNEL_CONSTRAINTS["decode_attention_q8"]
        self.assertIn("_paged_gqa_q8_kernel", c.kernel_fns)
        self.assertIn("_paged_decode_q8_kernel", c.kernel_fns)
        self.assertEqual(c.blocks["block_s"], da.BLOCK_S)
        cp = kernels.KERNEL_CONSTRAINTS["prefix_prefill_q8"]
        self.assertIn("_prefix_prefill_q8_kernel", cp.kernel_fns)
        self.assertEqual(cp.blocks["block_q"], pp.BLOCK_Q)

    def test_q8_checker_wants_scales(self):
        from paddle_tpu import kernels

        c = kernels.KERNEL_CONSTRAINTS["decode_attention_q8"]
        bad = c.check([(2, 4), (2,), (2, 4, 128), (9, 4, 8, 128),
                       (9, 4, 8, 128)],
                      ["int32", "int32", "bfloat16", "int8", "int8"])
        self.assertTrue(any("scale" in str(v) for v in bad))
        ok = c.check([(2, 4), (2,), (2, 4, 128), (9, 4, 8, 128),
                      (9, 4, 8, 128), (9, 4), (9, 4)],
                     ["int32", "int32", "bfloat16", "int8", "int8",
                      "float32", "float32"])
        self.assertFalse(any("scale" in str(v) for v in ok))

    def test_tpu103_flags_f32_pools_and_scaleless_int8(self):
        import paddle_tpu.analysis as analysis

        def att(q, kc, vc, tbl, lens):
            return paged_decode_attention(q, kc, vc, tbl, lens)

        tbl = jax.ShapeDtypeStruct((2, 4), jnp.int32)
        lens = jax.ShapeDtypeStruct((2,), jnp.int32)
        f32p = jax.ShapeDtypeStruct((9, 4, 8, 128), jnp.float32)
        r = analysis.analyze(
            att, jax.ShapeDtypeStruct((2, 4, 128), jnp.float32),
            f32p, f32p, tbl, lens, rules=["TPU103"])
        found = [d for d in r if d.rule == "TPU103"]
        self.assertTrue(found and "float32" in found[0].message)
        # bf16 pools: clean
        bf = jax.ShapeDtypeStruct((9, 4, 8, 128), jnp.bfloat16)
        r2 = analysis.analyze(
            att, jax.ShapeDtypeStruct((2, 4, 128), jnp.bfloat16),
            bf, bf, tbl, lens, rules=["TPU103"])
        self.assertFalse([d for d in r2 if d.rule == "TPU103"])
        # int8 + scales through the real call path: clean
        i8 = jax.ShapeDtypeStruct((9, 4, 8, 128), jnp.int8)
        sc = jax.ShapeDtypeStruct((9, 4), jnp.float32)

        def att8(q, kc, vc, tbl, lens, ks, vs):
            return paged_decode_attention(q, kc, vc, tbl, lens,
                                          k_scale=ks, v_scale=vs)

        r3 = analysis.analyze(
            att8, jax.ShapeDtypeStruct((2, 4, 128), jnp.bfloat16),
            i8, i8, tbl, lens, sc, sc, rules=["TPU103"])
        self.assertFalse([d for d in r3 if d.rule == "TPU103"])

    def test_tpu103_shape_logic_int8_without_scales(self):
        # the ValueError guard in the wrappers means no public call
        # path can trace this graph; probe the rule's shape logic
        from paddle_tpu.analysis.rules import _kv_pool_findings

        bad = _kv_pool_findings(
            [(2, 4, 128), (36, 8, 128), (36, 8, 128)],
            ["bfloat16", "int8", "int8"])
        self.assertTrue(any("scale" in m for _, m in bad))
        clean = _kv_pool_findings(
            [(2, 4, 128), (36, 8, 128), (36, 8, 128), (36, 1), (36, 1)],
            ["bfloat16", "int8", "int8", "float32", "float32"])
        self.assertFalse(clean)


if __name__ == "__main__":
    unittest.main()
