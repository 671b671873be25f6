"""Ragged paged prefix-prefill Pallas kernel (kernels/prefix_prefill.py):
interpret-mode parity against the masked-softmax reference that
`_make_prefill_with_prefix` keeps as its fallback, across ragged
prefix/suffix lengths, GQA ratios, pad query rows and the
single-page/empty-prefix edges — plus engine-level token identity with
the kernel on vs off through page-recycling churn."""
import dataclasses
import math
import unittest

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.kernels import prefix_prefill as pp


# the oracle IS the exported fallback math: the serving fallback
# (models.llama), this parity suite, bench.py's prefix_prefill_ref row
# and chip_smoke all share the one prefix_prefill_reference
def _reference(q, k_suf, v_suf, kc, vc, tables, plens, scale):
    return pp.prefix_prefill_reference(q, k_suf, v_suf, kc, vc, tables,
                                       plens, scale=scale)


class TestKernelParity(unittest.TestCase):
    def _case(self, b, sb, nh, nkv, dh, bs, w, plens_blocks, slens,
              seed=0, dtype=jnp.float32, **kw):
        rng = np.random.default_rng(seed)
        npages = b * w + 2
        q = jnp.asarray(rng.normal(size=(b, sb, nh, dh)), dtype)
        ks = jnp.asarray(rng.normal(size=(b, sb, nkv, dh)), dtype)
        vs = jnp.asarray(rng.normal(size=(b, sb, nkv, dh)), dtype)
        kc = jnp.asarray(rng.normal(size=(npages, nkv, bs, dh)), dtype)
        vc = jnp.asarray(rng.normal(size=(npages, nkv, bs, dh)), dtype)
        # scattered (non-contiguous) page placement, page 0 = pad filler
        tables = jnp.asarray(
            rng.permutation(npages - 1)[:b * w].reshape(b, w) + 1,
            jnp.int32)
        plens = jnp.asarray([pb * bs for pb in plens_blocks], jnp.int32)
        out = pp.prefix_prefill_attention(
            q, ks, vs, kc, vc, tables, plens,
            jnp.asarray(slens, jnp.int32), **kw)
        self.assertTrue(
            np.isfinite(np.asarray(out, np.float32)).all(),
            "pad rows must stay finite — a NaN there poisons later "
            "layers' K/V pages")
        for row in range(b):
            np.testing.assert_array_equal(
                np.asarray(out, np.float32)[row, slens[row]:], 0.0,
                err_msg=f"pad query rows of row {row} must be exact "
                        "zeros (the documented contract)")
        ref = _reference(q, ks, vs, kc, vc, tables, plens,
                         1.0 / math.sqrt(dh))
        tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 \
            else dict(rtol=2e-5, atol=2e-5)
        for row in range(b):
            np.testing.assert_allclose(
                np.asarray(out, np.float32)[row, :slens[row]],
                np.asarray(ref, np.float32)[row, :slens[row]],
                err_msg=f"row {row} (real suffix {slens[row]})", **tol)

    def test_ragged_gqa_with_pad_rows_and_empty_prefix(self):
        # per-row prefix depths 3/1/0 blocks, pad query rows on two rows
        self._case(3, 16, 4, 2, 16, 8, 3, (3, 1, 0), (16, 9, 5))

    def test_equal_heads_group_one(self):
        self._case(2, 16, 4, 4, 16, 8, 2, (2, 0), (16, 3))

    def test_mqa_full_group(self):
        self._case(2, 16, 4, 1, 16, 8, 2, (1, 2), (8, 16))

    def test_single_page_prefix_and_one_token_suffix(self):
        self._case(2, 8, 4, 2, 16, 8, 1, (1, 0), (8, 1))

    def test_multi_tile_streaming_with_explicit_blocks(self):
        # several q tiles and page-multiple suffix tiles: exercises the
        # causal block skipping and the online-softmax carry across j
        self._case(2, 32, 4, 2, 16, 8, 2, (2, 1), (32, 17),
                   block_q=8, block_s=16)

    def test_bf16_inputs_f32_accumulation(self):
        self._case(2, 16, 8, 2, 32, 8, 2, (2, 1), (16, 11),
                   dtype=jnp.bfloat16)

    def test_fit_blocks_page_granular_under_cap(self):
        bq, bsx = pp.fit_blocks(256, 64, 4, 128)
        self.assertEqual(256 % bq, 0)
        self.assertEqual(bsx % 64, 0)
        self.assertEqual(256 % bsx, 0)
        # a tiny suffix degenerates to one block of each
        self.assertEqual(pp.fit_blocks(64, 64, 1, 128), (64, 64))

    def test_unsupported_shapes_raise(self):
        q = jnp.zeros((1, 12, 2, 16))
        kv = jnp.zeros((1, 12, 2, 16))
        kc = jnp.zeros((3, 2, 8, 16))
        tbl = jnp.zeros((1, 1), jnp.int32)
        lens = jnp.zeros((1,), jnp.int32)
        with self.assertRaisesRegex(ValueError, "whole number"):
            # suffix bucket 12 is not a multiple of the 8-token page
            pp.prefix_prefill_attention(q, kv, kv, kc, kc, tbl, lens)
        with self.assertRaisesRegex(ValueError, "at least one page"):
            pp.prefix_prefill_attention(
                jnp.zeros((1, 8, 2, 16)), jnp.zeros((1, 8, 2, 16)),
                jnp.zeros((1, 8, 2, 16)), kc, kc,
                jnp.zeros((1, 0), jnp.int32), lens)


class TestEngineKernelIdentity(unittest.TestCase):
    def test_tokens_identical_kernel_on_vs_off_through_churn(self):
        """End-to-end guarantee: the kernel changes COST, never tokens.
        Shared-prefix traffic through a pool small enough to force
        retire/recycle churn must emit identical greedy tokens with
        FLAGS_prefix_prefill_kernel on (Pallas interpret) and off
        (masked-softmax fallback)."""
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.serving import ContinuousBatchingEngine

        cfg = dataclasses.replace(LlamaConfig.tiny(),
                                  num_key_value_heads=2)
        paddle.seed(21)
        model = LlamaForCausalLM(cfg)
        params = dict(model.raw_state())
        rng = np.random.default_rng(3)
        shared = rng.integers(1, cfg.vocab_size, (16,)).tolist()
        prompts = [shared + rng.integers(1, cfg.vocab_size,
                                         (n,)).tolist()
                   for n in (3, 7, 2, 5, 6, 4)]

        def serve(kernel_on):
            prev = paddle.get_flags("prefix_prefill_kernel")[
                "FLAGS_prefix_prefill_kernel"]
            paddle.set_flags({"prefix_prefill_kernel": kernel_on})
            try:
                eng = ContinuousBatchingEngine(
                    cfg, params, slots=2, prompt_bucket=8,
                    max_prompt_len=24, max_new_tokens=6, block_size=8,
                    steps_per_sync=3, prefill_batch=2,
                    prefix_cache=True)
                for pr in prompts:
                    eng.add_request(pr)
                eng.run(max_iters=300)
                return eng, {r.req_id: r.tokens for r in eng.finished}
            finally:
                paddle.set_flags({"prefix_prefill_kernel": prev})

        on_eng, on = serve(True)
        off_eng, off = serve(False)
        self.assertEqual(on, off)
        self.assertEqual(len(on), len(prompts))
        # both runs actually exercised the cached-prefix path, and the
        # churn the test exists for actually happened
        self.assertGreater(on_eng.prefix_hit_tokens, 0)
        self.assertEqual(on_eng.prefix_hit_tokens,
                         off_eng.prefix_hit_tokens)
        self.assertEqual(on_eng.mgr.n_available,
                         on_eng.mgr.max_pages - 1)


if __name__ == "__main__":
    unittest.main()
