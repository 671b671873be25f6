"""Decode megakernel (ISSUE 6 + ISSUE 20): interpret-mode parity of
the fused per-layer serving decode step against the multi-kernel
oracle it replaces, the in-kernel paged-KV commit epilogue's exactness
(bf16 byte-identical, int8 identical to the q8 helpers' monotone-scale
read-modify-write), engine token identity megakernel-on-vs-off through
recycling churn, the zero-recompile-after-warm guard under the new
flag, and the unsupported-shape fallback.

ISSUE 20 deepens the ladder: the 'full' rung (attention + MLP half in
one call per layer) matches the oracle, the 'scan' rung (every layer
in ONE layer-walked call over stacked weights and a stacked pool) is
BITWISE the per-layer full chain, both serve token-identical engines
with the scanned int8 pool committing byte-identically per layer, the
scan decode step traces to <= 3 kernel launches regardless of depth,
and the in-kernel o-proj quantize epilogue emits exactly the
quantize_blocks wire so quantized_psum_prequant is bit-identical to
the f32-partial quantized_psum."""
import dataclasses
import unittest

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.kernels.decode_attention import paged_decode_attention
from paddle_tpu.kernels.decode_megakernel import (
    CONSTRAINT, PAGES_PER_STEP, decode_layer_megakernel,
    decode_layer_megakernel_full, decode_layers_megakernel,
    megakernel_supported)
from paddle_tpu.kernels.rms_norm import rms_norm
from paddle_tpu.kernels.rope import apply_rotary_emb
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.llama import (_mm, make_paged_kv_helpers,
                                     make_paged_kv_q8_helpers,
                                     quantize_kv_pages)
from paddle_tpu.serving import ContinuousBatchingEngine

BASE, EPS = 10000.0, 1e-6


def _ref_layer(h, lens, tables, w_in, wq, wk, wv, wo, kct, vct):
    """The multi-kernel oracle: exactly the `_make_decode_step` attention
    block (rms -> _mm projections -> rope -> paged commit -> paged
    attention -> o-proj + residual), bf16 or int8 pools."""
    b = h.shape[0]
    quant = isinstance(kct, tuple)
    kc = kct[0] if quant else kct
    nkv, bs, dh = kc.shape[1], kc.shape[2], kc.shape[3]
    nh = (wq[0].shape[0] if isinstance(wq, tuple) else wq.shape[1]) // dh
    x = rms_norm(h, w_in, EPS)
    q = _mm(x, wq).reshape(b, 1, nh, dh)
    k = _mm(x, wk).reshape(b, 1, nkv, dh)
    v = _mm(x, wv).reshape(b, 1, nkv, dh)
    q, k = apply_rotary_emb(q, k, position_ids=lens[:, None], base=BASE)
    if quant:
        _, kv_write = make_paged_kv_q8_helpers(b, 0, nkv, dh, bs, tables)
        kct, vct = kv_write(kct, vct, k, v, lens)
        ctx = paged_decode_attention(q[:, 0], kct[0], vct[0], tables,
                                     lens, k_scale=kct[1],
                                     v_scale=vct[1])
    else:
        _, kv_write = make_paged_kv_helpers(b, 0, nkv, dh, bs, tables)
        kct, vct = kv_write(kct, vct, k, v, lens)
        ctx = paged_decode_attention(q[:, 0], kct, vct, tables, lens)
    h = h + _mm(ctx.reshape(b, 1, nh * dh), wo)
    return h, kct, vct


def _quantize_w(w):
    """nn.quant weight_only_int8-shaped pair: int8 [N, K] + scale [N]."""
    wf = np.asarray(w, np.float32)
    sc = np.abs(wf).max(axis=0) / 127.0
    sc = np.where(sc > 0, sc, 1.0)
    q = np.clip(np.round(wf / sc[None, :]), -127, 127).astype(np.int8).T
    return (jnp.asarray(q), jnp.asarray(sc, jnp.float32))


def _case(dtype, nh, nkv, dh, H, b=4, bs=8, W=4, seed=0, quant_w=False,
          lens=None):
    rng = np.random.default_rng(seed)
    max_pages = b * W + 1
    h = jnp.asarray(rng.normal(size=(b, 1, H)) * 0.5, dtype)
    w_in = jnp.asarray(rng.normal(size=(H,)) * 0.1 + 1.0, dtype)
    ws = [rng.normal(size=s) * 0.05
          for s in ((H, nh * dh), (H, nkv * dh), (H, nkv * dh),
                    (nh * dh, H))]
    if quant_w:
        wq, wk, wv, wo = (_quantize_w(w) for w in ws)
    else:
        wq, wk, wv, wo = (jnp.asarray(w, dtype) for w in ws)
    kc = jnp.asarray(rng.normal(size=(max_pages, nkv, bs, dh)), dtype)
    vc = jnp.asarray(rng.normal(size=(max_pages, nkv, bs, dh)), dtype)
    tables = jnp.asarray(
        rng.permutation(max_pages - 1)[:b * W].reshape(b, W) + 1,
        jnp.int32)
    if lens is None:
        # ragged slot occupancy: partial page, last slot of the last
        # page, a retired row (0), mid-cache
        lens = [3, bs * W - 1, 0, 17][:b]
    lens = jnp.asarray(lens, jnp.int32)
    return h, lens, tables, w_in, wq, wk, wv, wo, kc, vc


class TestLayerParityBf16(unittest.TestCase):
    """Interpret-mode parity vs the multi-kernel oracle on bf16/f32
    pools: layer output to tolerance, the page commit EXACT, untouched
    pages byte-identical."""

    def _check(self, dtype, nh, nkv, dh, H, tol, **kw):
        h, lens, tables, w_in, wq, wk, wv, wo, kc, vc = _case(
            dtype, nh, nkv, dh, H, **kw)
        hm, kcm, vcm = jax.jit(lambda a: decode_layer_megakernel(
            a, lens, tables, w_in, wq, wk, wv, wo, kc, vc,
            rope_base=BASE, eps=EPS))(h)
        hr, kcr, vcr = jax.jit(lambda a: _ref_layer(
            a, lens, tables, w_in, wq, wk, wv, wo, kc, vc))(h)
        err = float(jnp.max(jnp.abs(hm.astype(jnp.float32)
                                    - hr.astype(jnp.float32))))
        self.assertLess(err, tol)
        # the commit (and every untouched page) is EXACT vs kv_write
        np.testing.assert_array_equal(np.asarray(kcm), np.asarray(kcr))
        np.testing.assert_array_equal(np.asarray(vcm), np.asarray(vcr))

    def test_gqa_group_2_f32(self):
        self._check(jnp.float32, 4, 2, 16, 32, 1e-5)

    def test_equal_heads_group_1(self):
        self._check(jnp.float32, 4, 4, 16, 32, 1e-5)

    def test_full_mqa(self):
        self._check(jnp.float32, 4, 1, 16, 32, 1e-5)

    def test_bf16(self):
        self._check(jnp.bfloat16, 4, 2, 16, 32, 3e-2)

    def test_quant_weights(self):
        self._check(jnp.bfloat16, 4, 2, 16, 32, 3e-2, quant_w=True)

    def test_multi_page_inner_step_divisible_width(self):
        # W=8 takes the pages_per_step=4 inner step (2 inner steps);
        # W=3 fits a single 3-page step; W=5 degrades to 1 page/step
        self._check(jnp.float32, 4, 2, 16, 32, 1e-5, W=8,
                    lens=[3, 8 * 8 - 1, 0, 40])
        self._check(jnp.float32, 4, 2, 16, 32, 1e-5, W=3,
                    lens=[3, 8 * 3 - 1, 0, 20])
        self._check(jnp.float32, 4, 2, 16, 32, 1e-5, W=5,
                    lens=[3, 8 * 5 - 1, 0, 33])

    def test_untouched_pages_preserved_in_place(self):
        """Only the commit page of each (row, kv head) may change; every
        other pool byte must survive the aliased in-place update."""
        h, lens, tables, w_in, wq, wk, wv, wo, kc, vc = _case(
            jnp.float32, 4, 2, 16, 32)
        _, kcm, _ = jax.jit(lambda a: decode_layer_megakernel(
            a, lens, tables, w_in, wq, wk, wv, wo, kc, vc,
            rope_base=BASE, eps=EPS))(h)
        commit_pages = {int(tables[b, int(lens[b]) // 8])
                        for b in range(4)}
        before, after = np.asarray(kc), np.asarray(kcm)
        for p in range(kc.shape[0]):
            if p not in commit_pages:
                np.testing.assert_array_equal(after[p], before[p])


class TestLayerParityInt8(unittest.TestCase):
    """int8 pools: hidden state within quant tolerance; the in-kernel
    commit IDENTICAL (int values and f32 scales) to the q8 helpers'
    monotone-scale read-modify-write."""

    def _check(self, nh, nkv, dh, H, quant_w=False, lens=None, seed=0):
        h, lens, tables, w_in, wq, wk, wv, wo, kc, vc = _case(
            jnp.bfloat16, nh, nkv, dh, H, quant_w=quant_w, lens=lens,
            seed=seed)
        kq, ks = quantize_kv_pages(kc)
        vq, vs = quantize_kv_pages(vc)
        hm, kctm, vctm = jax.jit(lambda a: decode_layer_megakernel(
            a, lens, tables, w_in, wq, wk, wv, wo, kq, vq,
            rope_base=BASE, eps=EPS, k_scale=ks, v_scale=vs))(h)
        hr, kctr, vctr = jax.jit(lambda a: _ref_layer(
            a, lens, tables, w_in, wq, wk, wv, wo, (kq, ks),
            (vq, vs)))(h)
        err = float(jnp.max(jnp.abs(hm.astype(jnp.float32)
                                    - hr.astype(jnp.float32))))
        self.assertLess(err, 1e-1)
        for (pm, sm), (pr, sr) in ((kctm, kctr), (vctm, vctr)):
            np.testing.assert_array_equal(np.asarray(pm), np.asarray(pr))
            np.testing.assert_allclose(np.asarray(sm), np.asarray(sr),
                                       atol=1e-7)

    def test_gqa(self):
        self._check(4, 2, 16, 32)

    def test_equal_heads_quant_weights(self):
        self._check(4, 4, 16, 32, quant_w=True)

    def test_recycled_page_slot0_resets_scale(self):
        """A commit at slot 0 must reset the page's absmax chain — the
        recycled-page guarantee — identically to the q8 helper."""
        # lens multiples of the page size land every commit at slot 0
        self._check(4, 2, 16, 32, lens=[8, 16, 0, 24], seed=3)


class TestSupportGate(unittest.TestCase):
    def test_packed_int4_weights_rejected(self):
        h, lens, tables, w_in, wq, wk, wv, wo, kc, vc = _case(
            jnp.bfloat16, 4, 2, 16, 32, quant_w=True)
        # halve the stored K columns: the packed-int4 layout
        wq_p = (wq[0][:, ::2], wq[1])
        reason = megakernel_supported(
            jax.ShapeDtypeStruct((4, 1, 32), jnp.bfloat16), w_in, wq_p,
            wk, wv, wo, kc, vc, tables)
        self.assertIsNotNone(reason)
        with self.assertRaises(ValueError):
            decode_layer_megakernel(h, lens, tables, w_in, wq_p, wk, wv,
                                    wo, kc, vc)

    def test_mixed_weights_rejected(self):
        h, lens, tables, w_in, wq, wk, wv, wo, kc, vc = _case(
            jnp.float32, 4, 2, 16, 32)
        wq_q = _quantize_w(np.asarray(wq))
        reason = megakernel_supported(
            jax.ShapeDtypeStruct((4, 1, 32), jnp.float32), w_in, wq_q,
            wk, wv, wo, kc, vc, tables)
        self.assertIn("mixed", reason)

    def test_supported_serving_shape(self):
        h, lens, tables, w_in, wq, wk, wv, wo, kc, vc = _case(
            jnp.bfloat16, 4, 2, 16, 32)
        self.assertIsNone(megakernel_supported(
            jax.ShapeDtypeStruct((4, 1, 32), jnp.bfloat16), w_in, wq,
            wk, wv, wo, kc, vc, tables))

    def test_int4_generate_falls_back_and_still_serves(self):
        """jit_generate with packed-int4 weights + the flag on must fall
        back to the multi-kernel path (with a warning) and emit the
        same tokens as with the flag off."""
        import warnings

        paddle.seed(5)
        cfg = LlamaConfig.tiny(dtype="bfloat16")
        model = LlamaForCausalLM(cfg)
        x = paddle.to_tensor(np.random.default_rng(6).integers(
            1, cfg.vocab_size, (2, 9)))
        kw = dict(max_new_tokens=4, cache_layout="paged",
                  kv_block_size=8, quant="weight_only_int4")
        off = model.jit_generate(x, **kw).numpy()
        paddle.set_flags({"decode_megakernel": True})
        try:
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                on = model.jit_generate(x, **kw).numpy()
        finally:
            paddle.set_flags({"decode_megakernel": False})
        np.testing.assert_array_equal(off, on)
        self.assertTrue(any("megakernel" in str(w.message)
                            for w in caught))


def _engine_run(megakernel, kv_dtype):
    """Build + warm + churn one tiny engine; returns (tokens, engine,
    warm-time compile stats) so rung tests can inspect pools/plan."""
    cfg = dataclasses.replace(LlamaConfig.tiny(),
                              num_key_value_heads=2)
    paddle.seed(21)
    model = LlamaForCausalLM(cfg)
    params = dict(model.raw_state())
    rng = np.random.default_rng(7)
    shared = rng.integers(1, cfg.vocab_size, (8,)).tolist()
    prompts = ([shared + rng.integers(1, cfg.vocab_size,
                                      (n,)).tolist()
                for n in (3, 5)]
               + [rng.integers(1, cfg.vocab_size, (n,)).tolist()
                  for n in (2, 9, 14, 4, 11)])
    eng = ContinuousBatchingEngine(
        cfg, params, slots=2, prompt_bucket=8, max_prompt_len=16,
        max_new_tokens=6, block_size=8, steps_per_sync=3,
        prefill_batch=1, prefix_cache=True, kv_cache_dtype=kv_dtype,
        decode_megakernel=megakernel)
    eng.warm(buckets=[8, 16])
    before = eng.compile_stats()
    for i, pr in enumerate(prompts):
        eng.add_request(pr, max_new=2 + i % 4)
    eng.run(max_iters=300)
    assert len(eng.finished) == len(prompts)
    return ({r.req_id: list(r.tokens) for r in eng.finished}, eng,
            before)


class TestGenerateAndEngine(unittest.TestCase):
    def _engine_tokens(self, megakernel, kv_dtype):
        toks, eng, before = _engine_run(megakernel, kv_dtype)
        from paddle_tpu.models.llama import resolve_decode_megakernel
        self.assertEqual(eng.use_megakernel,
                         resolve_decode_megakernel(megakernel))
        self.assertNotIn(-1, before.values())
        # zero-recompile-after-warm guard, extended to the new flag
        self.assertEqual(eng.compile_stats(), before)
        return toks

    def test_engine_token_identity_bf16_through_churn(self):
        """Megakernel-on tokens == megakernel-off tokens through prefix
        hits, per-request max_new variety, and page recycling churn —
        and neither path compiles anything after warm()."""
        self.assertEqual(self._engine_tokens(False, "bf16"),
                         self._engine_tokens(True, "bf16"))

    @pytest.mark.slow  # tier-1 budget: bf16 identity above exercises
    # the same engine wiring; the int8 epilogue parity stays in tier-1
    # via TestLayerParityInt8
    def test_engine_token_identity_int8_through_churn(self):
        self.assertEqual(self._engine_tokens(False, "int8"),
                         self._engine_tokens(True, "int8"))

    def test_engine_token_identity_scan_bf16(self):
        """ISSUE 20 acceptance (tier-1): the deepest rung — 'scan',
        one layer-walked call over the stacked pool — serves
        token-identical to the multi-kernel oracle through the same
        churn, with zero compiles after warm and the served rung
        reported in metrics."""
        self.assertEqual(self._engine_tokens("off", "bf16"),
                         self._engine_tokens("scan", "bf16"))

    @pytest.mark.slow  # tier-1 budget: scan above covers the ladder's
    # deep end, and scan == per-layer-full bitwise is tier-1 at the
    # kernel level (TestFullAndScanKernels); this leg only re-serves
    # the middle rung through the same engine wiring
    def test_engine_token_identity_full_bf16(self):
        self.assertEqual(self._engine_tokens("off", "bf16"),
                         self._engine_tokens("full", "bf16"))

    def test_jit_generate_paged_identity_and_flag_in_key(self):
        paddle.seed(7)
        cfg = LlamaConfig.tiny(dtype="bfloat16")
        model = LlamaForCausalLM(cfg)
        x = paddle.to_tensor(np.random.default_rng(5).integers(
            1, cfg.vocab_size, (2, 9)))
        kw = dict(max_new_tokens=6, cache_layout="paged", kv_block_size=8)
        off = model.jit_generate(x, **kw).numpy()
        n_progs = len(model._jit_gen_cache)
        paddle.set_flags({"decode_megakernel": True})
        try:
            on = model.jit_generate(x, **kw).numpy()
        finally:
            paddle.set_flags({"decode_megakernel": False})
        np.testing.assert_array_equal(off, on)
        # the flag joins the jit cache signature: a second program, and
        # flipping back serves the original compiled entry
        self.assertEqual(len(model._jit_gen_cache), n_progs + 1)
        again = model.jit_generate(x, **kw).numpy()
        np.testing.assert_array_equal(off, again)
        self.assertEqual(len(model._jit_gen_cache), n_progs + 1)


class TestConstraintAndBenchHelpers(unittest.TestCase):
    def test_constraint_registered(self):
        from paddle_tpu.kernels.constraints import (
            KERNEL_CONSTRAINTS, constraint_for_kernel_fn)

        self.assertIn("decode_megakernel", KERNEL_CONSTRAINTS)
        c = constraint_for_kernel_fn("_decode_megakernel_kernel",
                                     "decode_megakernel.py")
        self.assertIs(c, CONSTRAINT)
        self.assertEqual(c.blocks["pages_per_step"], PAGES_PER_STEP)

    def test_checker_flags_narrow_head_dim_and_scaleless_int8(self):
        warn = CONSTRAINT.check([(4, 8), (4,), (40, 8, 100)],
                                ["int32", "int32", "bfloat16"])
        self.assertTrue(any("head_dim" in m for _, m in warn))
        warn = CONSTRAINT.check(
            [(4, 8), (4,), (40, 8, 128), (40, 8, 128)],
            ["int32", "int32", "int8", "int8"])
        self.assertTrue(any("scale" in m for _, m in warn))

    def test_rope_and_swiglu_constraints_registered(self):
        """Satellite small fix: the last kernels modules join the
        TPU102 registry — swiglu with its real kernel fns, rope as the
        documented (pure-jnp) layout contract."""
        from paddle_tpu.kernels import swiglu
        from paddle_tpu.kernels.constraints import (
            KERNEL_CONSTRAINTS, constraint_for_kernel_fn)

        self.assertIn("rope", KERNEL_CONSTRAINTS)
        self.assertIn("swiglu", KERNEL_CONSTRAINTS)
        c = constraint_for_kernel_fn("_swiglu_fwd_kernel", "swiglu.py")
        self.assertEqual(c.name, "swiglu")
        self.assertEqual(c.blocks["block"], swiglu._BLOCK)
        # misaligned K fires the swiglu checker
        warn = c.check([(256, 100), (100, 512), (100, 512)],
                       ["bfloat16"] * 3)
        self.assertTrue(any("K=100" in m for _, m in warn))

    def test_kernels_per_step_counts_fusion_win(self):
        """bench.py's kernels_per_step attribution: the fused step must
        trace to strictly fewer pallas/dot launches than the
        multi-kernel step at the same shape."""
        from bench import _count_step_kernels
        from paddle_tpu.models.llama import (
            _make_decode_step, _make_decode_step_megakernel,
            make_paged_kv_helpers)

        cfg = dataclasses.replace(LlamaConfig.tiny(),
                                  num_key_value_heads=2)
        paddle.seed(3)
        model = LlamaForCausalLM(cfg)
        params = dict(model.raw_state())
        b, bs, W = 2, 8, 2
        max_pages = b * W + 1
        nkv, dh = cfg.num_key_value_heads, cfg.head_dim
        tables = jnp.asarray(np.arange(b * W).reshape(b, W) + 1,
                             jnp.int32)
        pools = lambda: [jnp.zeros((max_pages, nkv, bs, dh),
                                   jnp.float32)
                         for _ in range(cfg.num_hidden_layers)]
        _, kv_write = make_paged_kv_helpers(b, 0, nkv, dh, bs, tables)
        base = _make_decode_step(
            cfg, b, kv_write=kv_write,
            kv_attend=lambda q1, kc, vc, lens: paged_decode_attention(
                q1, kc, vc, tables, lens))
        mega = _make_decode_step_megakernel(cfg, b, tables)
        tok = jnp.ones((b, 1), jnp.int32)
        lens = jnp.full((b,), 3, jnp.int32)
        n_base = _count_step_kernels(base, params, pools(), pools(),
                                     tok, lens)
        n_mega = _count_step_kernels(mega, params, pools(), pools(),
                                     tok, lens)
        self.assertLess(n_mega, n_base)

    def test_megakernel_bench_row_is_gated(self):
        """`decode_step_1b_megakernel` rides the rolling-best gate;
        the multi-kernel comparison row is informational only."""
        import bench

        self.assertNotIn("decode_step_1b_megakernel",
                         bench.INFORMATIONAL_OPS)
        self.assertIn("decode_step_1b_paged_ref",
                      bench.INFORMATIONAL_OPS)


class TestFullAndScanKernels(unittest.TestCase):
    """ISSUE 20 tentpole, kernel level: the FULL rung matches the attn
    oracle + jnp MLP half; the scan rung is BITWISE the per-layer full
    chain (same math in the same order — only the launch count and the
    stacked-operand layout change)."""

    def _full_case(self, dtype, quant_w=False, seed=0):
        h, lens, tables, w_in, wq, wk, wv, wo, kc, vc = _case(
            dtype, 4, 2, 16, 32, quant_w=quant_w, seed=seed)
        rng = np.random.default_rng(seed + 100)
        H, F = 32, 64
        w_post = jnp.asarray(rng.normal(size=(H,)) * 0.1 + 1.0, dtype)
        ms = [rng.normal(size=s) * 0.05
              for s in ((H, F), (H, F), (F, H))]
        if quant_w:
            wg, wu, wd = (_quantize_w(w) for w in ms)
        else:
            wg, wu, wd = (jnp.asarray(w, dtype) for w in ms)
        return (h, lens, tables, w_in, w_post, wq, wk, wv, wo,
                wg, wu, wd, kc, vc)

    @staticmethod
    def _ref_full(h, lens, tables, w_in, w_post, wq, wk, wv, wo,
                  wg, wu, wd, kc, vc):
        ha, kcr, vcr = _ref_layer(h, lens, tables, w_in, wq, wk, wv,
                                  wo, kc, vc)
        x2 = rms_norm(ha, w_post, EPS)
        hm = ha + _mm(jax.nn.silu(_mm(x2, wg)) * _mm(x2, wu), wd)
        return hm, kcr, vcr

    def _check_full(self, dtype, tol, quant_w=False):
        ops = self._full_case(dtype, quant_w=quant_w)
        hm, kcm, vcm = jax.jit(lambda a: decode_layer_megakernel_full(
            a, *ops[1:], rope_base=BASE, eps=EPS))(ops[0])
        hr, kcr, vcr = jax.jit(lambda a: self._ref_full(
            a, *ops[1:]))(ops[0])
        err = float(jnp.max(jnp.abs(hm.astype(jnp.float32)
                                    - hr.astype(jnp.float32))))
        self.assertLess(err, tol)
        np.testing.assert_array_equal(np.asarray(kcm), np.asarray(kcr))
        np.testing.assert_array_equal(np.asarray(vcm), np.asarray(vcr))

    def test_full_layer_parity_f32(self):
        self._check_full(jnp.float32, 1e-5)

    def test_full_layer_parity_bf16(self):
        self._check_full(jnp.bfloat16, 5e-2)

    def test_full_layer_parity_quant_weights(self):
        self._check_full(jnp.bfloat16, 5e-2, quant_w=True)

    def test_scan_bitwise_equals_per_layer_full_chain(self):
        L = 2
        cases = [self._full_case(jnp.bfloat16, seed=i)
                 for i in range(L)]
        h, lens, tables = cases[0][0], cases[0][1], cases[0][2]
        # per-layer full chain, residual carried between calls
        hc, kcs, vcs = h, [], []
        for i in range(L):
            hc, kc2, vc2 = jax.jit(
                lambda a, c=cases[i]: decode_layer_megakernel_full(
                    a, lens, tables, *c[3:12], c[12], c[13],
                    rope_base=BASE, eps=EPS))(hc)
            kcs.append(kc2)
            vcs.append(vc2)
        # one layer-walked call over stacked weights + stacked pool
        stacked = [jnp.stack([cases[i][j] for i in range(L)])
                   for j in range(3, 12)]
        kc_st = jnp.concatenate([c[12] for c in cases], axis=0)
        vc_st = jnp.concatenate([c[13] for c in cases], axis=0)
        hs, kcn, vcn = jax.jit(
            lambda a: decode_layers_megakernel(
                a, lens, tables, *stacked, kc_st, vc_st, n_layers=L,
                rope_base=BASE, eps=EPS))(h)
        np.testing.assert_array_equal(np.asarray(hs), np.asarray(hc))
        stride = cases[0][12].shape[0]
        for i in range(L):
            sl = slice(i * stride, (i + 1) * stride)
            np.testing.assert_array_equal(np.asarray(kcn[sl]),
                                          np.asarray(kcs[i]))
            np.testing.assert_array_equal(np.asarray(vcn[sl]),
                                          np.asarray(vcs[i]))

    def test_scan_bitwise_equals_full_chain_int8_pools(self):
        """int8 pools through the scan: per-layer commit slices (int
        values AND f32 scales) bitwise the per-layer full chain's —
        the monotone absmax chain is preserved per layer step."""
        L = 2
        cases = [self._full_case(jnp.bfloat16, seed=i)
                 for i in range(L)]
        h, lens, tables = cases[0][0], cases[0][1], cases[0][2]
        qs = [(quantize_kv_pages(c[12]), quantize_kv_pages(c[13]))
              for c in cases]
        hc, kcs, vcs = h, [], []
        for i in range(L):
            (kq, ks), (vq, vsc) = qs[i]
            hc, kct, vct = jax.jit(
                lambda a, c=cases[i], kq=kq, ks=ks, vq=vq, vsc=vsc:
                decode_layer_megakernel_full(
                    a, lens, tables, *c[3:12], kq, vq,
                    rope_base=BASE, eps=EPS, k_scale=ks,
                    v_scale=vsc))(hc)
            kcs.append(kct)
            vcs.append(vct)
        stacked = [jnp.stack([cases[i][j] for i in range(L)])
                   for j in range(3, 12)]
        kq_st = jnp.concatenate([k[0] for k, _ in qs], axis=0)
        ks_st = jnp.concatenate([k[1] for k, _ in qs], axis=0)
        vq_st = jnp.concatenate([v[0] for _, v in qs], axis=0)
        vs_st = jnp.concatenate([v[1] for _, v in qs], axis=0)
        hs, kcn, vcn = jax.jit(
            lambda a: decode_layers_megakernel(
                a, lens, tables, *stacked, kq_st, vq_st, n_layers=L,
                rope_base=BASE, eps=EPS, k_scale=ks_st,
                v_scale=vs_st))(h)
        np.testing.assert_array_equal(np.asarray(hs), np.asarray(hc))
        stride = cases[0][12].shape[0]
        for i in range(L):
            sl = slice(i * stride, (i + 1) * stride)
            for got, want in ((kcn, kcs[i]), (vcn, vcs[i])):
                np.testing.assert_array_equal(
                    np.asarray(got[0][sl]), np.asarray(want[0]))
                np.testing.assert_array_equal(
                    np.asarray(got[1][sl]), np.asarray(want[1]))


class TestScanServing(unittest.TestCase):
    @pytest.mark.slow  # tier-1 budget: three full engine builds; the
    # int8 per-layer-step byte contract stays tier-1 at the kernel
    # level via test_scan_bitwise_equals_full_chain_int8_pools
    def test_scan_int8_pool_commits_byte_identical_per_layer(self):
        """ISSUE 20 acceptance: int8 pool commits byte-identical per
        layer STEP — after identical churn the scanned engine's single
        stacked pool holds, per layer slice, exactly the bytes (int
        values AND f32 scales) the per-layer 'full' engine's pools
        hold; both emit the multi-kernel oracle's tokens. (The oracle's
        pools are NOT the byte reference: its unfused MLP rounds the
        next layer's input differently, which is the attn-rung
        TestLayerParityInt8 contract, not the scan one.)"""
        off_toks, _, _ = _engine_run("off", "int8")
        full_toks, full_eng, _ = _engine_run("full", "int8")
        scan_toks, scan_eng, _ = _engine_run("scan", "int8")
        self.assertEqual(scan_eng.megakernel_rung, "scan")
        self.assertEqual(scan_eng.metrics()["megakernel_rung"], "scan")
        self.assertEqual(full_eng.megakernel_rung, "full")
        self.assertEqual(off_toks, scan_toks)
        self.assertEqual(full_toks, scan_toks)
        self.assertEqual(len(scan_eng.kcs), 1)
        (kq, ks), (vq, vs) = scan_eng.kcs[0], scan_eng.vcs[0]
        n_layers = len(full_eng.kcs)
        stride = kq.shape[0] // n_layers
        for i in range(n_layers):
            (okq, oks), (ovq, ovs) = full_eng.kcs[i], full_eng.vcs[i]
            sl = slice(i * stride, (i + 1) * stride)
            np.testing.assert_array_equal(np.asarray(kq[sl]),
                                          np.asarray(okq))
            np.testing.assert_array_equal(np.asarray(vq[sl]),
                                          np.asarray(ovq))
            np.testing.assert_array_equal(np.asarray(ks[sl]),
                                          np.asarray(oks))
            np.testing.assert_array_equal(np.asarray(vs[sl]),
                                          np.asarray(ovs))

    def test_scan_kernels_per_step_flat_in_depth(self):
        """ISSUE 20 acceptance: the scanned decode step of a 4-layer
        tiny llama traces to <= 3 kernel launches (the megakernel, the
        final rms_norm, the lm head) — launch count flat in depth,
        strictly below the multi-kernel step's."""
        from paddle_tpu.analysis.roofline import count_step_kernels
        from paddle_tpu.models.llama import (
            _make_decode_step_megakernel, stack_decode_layer_params)

        cfg = dataclasses.replace(LlamaConfig.tiny(),
                                  num_hidden_layers=4,
                                  num_key_value_heads=2)
        paddle.seed(3)
        model = LlamaForCausalLM(cfg)
        params = stack_decode_layer_params(dict(model.raw_state()),
                                           cfg.num_hidden_layers)
        b, bs, W = 2, 8, 2
        max_pages = b * W + 1
        nkv, dh = cfg.num_key_value_heads, cfg.head_dim
        tables = jnp.asarray(np.arange(b * W).reshape(b, W) + 1,
                             jnp.int32)
        pool = lambda: [jnp.zeros(
            (max_pages * cfg.num_hidden_layers, nkv, bs, dh),
            jnp.float32)]
        step = _make_decode_step_megakernel(cfg, b, tables,
                                            mode="scan")
        tok = jnp.ones((b, 1), jnp.int32)
        lens = jnp.full((b,), 3, jnp.int32)
        n = count_step_kernels(step, params, pool(), pool(), tok, lens)
        self.assertLessEqual(n, 3)


class TestQuantizeOutEpilogue(unittest.TestCase):
    """ISSUE 20 satellite: the in-kernel o-proj quantize epilogue emits
    exactly the quantize_blocks wire layout of the f32 partial, and
    quantized_psum_prequant over that wire is bit-identical to
    quantized_psum of the f32 partial — the TP seam never round-trips
    an f32 partial through HBM."""

    def test_bitwise_matches_quantize_blocks_of_f32_partial(self):
        from paddle_tpu.parallel.collectives import quantize_blocks

        # lane-aligned H=128 (nh=4, dh=32): the serving gate's shape
        h, lens, tables, w_in, wq, wk, wv, wo, kc, vc = _case(
            jnp.bfloat16, 4, 2, 32, 128)
        part, kc1, vc1 = jax.jit(lambda a: decode_layer_megakernel(
            a, lens, tables, w_in, wq, wk, wv, wo, kc, vc,
            rope_base=BASE, eps=EPS, residual=False))(h)
        (q8, sc), kc2, vc2 = jax.jit(lambda a: decode_layer_megakernel(
            a, lens, tables, w_in, wq, wk, wv, wo, kc, vc,
            rope_base=BASE, eps=EPS, residual=False,
            quantize_out=True))(h)
        self.assertEqual(q8.dtype, jnp.int8)
        self.assertEqual(part.dtype, jnp.float32)
        qr, sr = quantize_blocks(part.reshape(4, 128))
        np.testing.assert_array_equal(np.asarray(q8), np.asarray(qr))
        np.testing.assert_array_equal(np.asarray(sc), np.asarray(sr))
        # the quantize epilogue leaves the pool commit untouched
        np.testing.assert_array_equal(np.asarray(kc1), np.asarray(kc2))
        np.testing.assert_array_equal(np.asarray(vc1), np.asarray(vc2))

    def test_quantize_out_requires_residual_off_and_aligned_h(self):
        h, lens, tables, w_in, wq, wk, wv, wo, kc, vc = _case(
            jnp.bfloat16, 4, 2, 32, 128)
        with self.assertRaisesRegex(ValueError, "residual"):
            decode_layer_megakernel(
                h, lens, tables, w_in, wq, wk, wv, wo, kc, vc,
                quantize_out=True)
        ops = _case(jnp.bfloat16, 4, 2, 16, 32)
        with self.assertRaisesRegex(ValueError, "lane-aligned"):
            decode_layer_megakernel(*ops[:10], residual=False,
                                    quantize_out=True)

    def test_prequant_psum_bit_identical_to_f32_partial_psum(self):
        from jax.sharding import Mesh, PartitionSpec as P

        from paddle_tpu.parallel import collectives as qc
        from jax import shard_map

        rng = np.random.default_rng(11)
        for n in (2, 4):
            x = jnp.asarray(
                rng.normal(size=(n, 4, 256)).astype(np.float32))
            mesh = Mesh(np.asarray(jax.devices()[:n]), ("mp",))

            def smap(fn):
                return jax.jit(shard_map(
                    fn, mesh=mesh, in_specs=P("mp"),
                    out_specs=P("mp"), check_vma=False))

            ref = smap(lambda v: qc.quantized_psum(v[0], "mp")[None])(x)
            pre = smap(lambda v: qc.quantized_psum_prequant(
                *qc.quantize_blocks(v[0]), "mp", shape=v[0].shape,
                dtype=v[0].dtype)[None])(x)
            np.testing.assert_array_equal(np.asarray(ref),
                                          np.asarray(pre))

    def test_prequant_psum_rejects_misaligned_payload(self):
        from jax.sharding import Mesh, PartitionSpec as P

        from paddle_tpu.parallel import collectives as qc
        from jax import shard_map

        # 3 * 128 = 384 flat elements do not split into 2 * 128 blocks
        x = jnp.ones((2, 3, 128), jnp.float32)
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("mp",))
        with self.assertRaisesRegex(ValueError, "split"):
            jax.jit(shard_map(
                lambda v: qc.quantized_psum_prequant(
                    *qc.quantize_blocks(v[0]), "mp",
                    shape=v[0].shape, dtype=v[0].dtype)[None],
                mesh=mesh, in_specs=P("mp"), out_specs=P("mp"),
                check_vma=False))(x)


if __name__ == "__main__":
    unittest.main()
