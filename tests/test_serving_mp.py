"""Tensor-parallel paged serving (FLAGS_serving_mp) on an 8-device CPU
mesh: kv-head-sharded pools must be TOKEN-IDENTICAL to the single-chip
engine (the o-proj activation all-gather is the only collective and
every per-element computation is replicated), per-chip pool bytes must
drop to 1/mp at equal aggregate page capacity, the zero-recompile-after-
warm guard must hold with `mp` in every program key, and the
prefill/decode disaggregation handoff must neither change tokens nor
leak pages. Heavy engine-pair runs are marked @slow to hold the tier-1
budget; the bf16 mp=2 identity + recompile guard stay in tier-1."""
import dataclasses
import unittest
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.llama import (PagedKVManager, ServingTP,
                                     build_paged_generate,
                                     make_serving_tp)
from paddle_tpu.serving import ContinuousBatchingEngine


def _tiny_setup(nkv=2, seed=21):
    cfg = dataclasses.replace(LlamaConfig.tiny(), num_key_value_heads=nkv)
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    # bf16 params = the production serving regime: the o-proj gather
    # payload is bf16 on BOTH the mp=1 and mp>1 paths (ISSUE 14
    # satellite casts an f32 stream to bf16 before the wire — identity
    # across mp degrees is asserted at the dtype serving actually runs)
    import jax.numpy as jnp

    params = {k: (v.astype(jnp.bfloat16) if v.dtype == jnp.float32
                  else v)
              for k, v in dict(model.raw_state()).items()}
    return cfg, model, params


def _engine(cfg, params, mp=1, disaggregated=False, kv="bf16",
            **over):
    kw = dict(slots=2, prompt_bucket=8, max_prompt_len=16,
              max_new_tokens=6, block_size=8, steps_per_sync=3,
              serving_mp=mp, disaggregated=disaggregated,
              kv_cache_dtype=kv)
    kw.update(over)
    return ContinuousBatchingEngine(cfg, dict(params), **kw)


def _churn_prompts(cfg, rng):
    """Shared-prefix + cold prompts sized so a 2-slot engine recycles
    pages and the prefix cache takes hits AND evictions."""
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


class TestServingTPGeometry(unittest.TestCase):
    """Pure host math — no device programs compile here."""

    def test_shard_layout(self):
        cfg, _, _ = _tiny_setup(nkv=2)      # nh=4, nkv=2
        tp = ServingTP(cfg, 2)
        self.assertEqual((tp.nh_local, tp.nkv_local), (2, 1))
        self.assertTrue(tp.kv_sharded)

    def test_mp1_is_no_tp(self):
        cfg, _, _ = _tiny_setup()
        self.assertIsNone(make_serving_tp(cfg, 1))

    def test_q_heads_must_divide(self):
        cfg, _, _ = _tiny_setup()
        with self.assertRaisesRegex(ValueError, "q.*heads|heads.*shard"):
            ServingTP(cfg, 3)

    def test_mqa_fallback_warns_and_replicates(self):
        """nkv=1 cannot shard by kv head: k/v stay replicated, q heads
        still shard, and the build warns (satellite: the GQA group
        derives from LOCAL head counts, so the fallback grid is
        nh_local // nkv, never the full-model nh // nkv)."""
        cfg, _, _ = _tiny_setup(nkv=1)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            tp = ServingTP(cfg, 2)
        self.assertTrue(any("replicated-KV" in str(x.message)
                            for x in w))
        self.assertFalse(tp.kv_sharded)
        self.assertEqual(tp.nkv_local, 1)   # full kv heads, not 1//2
        self.assertEqual(tp.nh_local, 2)

    def test_mqa_without_whole_groups_rejected(self):
        # nh=4, nkv=2, mp=4: kv can't shard and 1 local q head is not a
        # whole number of the 2 kv groups — no valid grid either way
        cfg, _, _ = _tiny_setup(nkv=2)
        with self.assertRaisesRegex(ValueError, "kv groups"):
            ServingTP(cfg, 4)

    def test_page_bytes_per_shard_geometry(self):
        """Satellite: page_bytes/pages_for_bytes/kv_pool_bytes size the
        PER-CHIP pool under kv-head sharding — each chip holds nkv/mp
        heads of every page, so a page costs 1/mp per chip and a
        per-chip byte budget buys ~mp x the aggregate pages."""
        kw = dict(n_layers=2, num_kv_heads=2, head_dim=16)
        full = PagedKVManager.page_bytes(8, **kw)
        half = PagedKVManager.page_bytes(8, mp=2, **kw)
        self.assertEqual(half * 2, full)
        budget = 64 * full
        self.assertEqual(
            PagedKVManager.pages_for_bytes(budget, 8, mp=2, **kw),
            2 * PagedKVManager.pages_for_bytes(budget, 8, **kw))
        with self.assertRaises(ValueError):
            PagedKVManager.page_bytes(8, n_layers=2, num_kv_heads=1,
                                      head_dim=16, mp=2)
        mgr = PagedKVManager(8, 8)
        mgr.set_pool_geometry(kv_cache_dtype="bf16", mp=2, **kw)
        self.assertEqual(mgr.kv_pool_bytes(), 8 * half)
        self.assertEqual(mgr.kv_pool_bytes(aggregate=True), 8 * full)
        with self.assertRaises(ValueError):
            mgr.set_pool_geometry(n_layers=2, num_kv_heads=1,
                                  head_dim=16, mp=2)

    def test_engine_budget_sizes_per_chip_pool(self):
        """`kv_pool_bytes=` is a PER-CHIP budget: at mp=2 the same
        bytes hold ~2x the aggregate pages (and the engine records the
        shard count so kv_pool_bytes() reports per-chip cost)."""
        cfg, _, params = _tiny_setup()
        budget = 96 * PagedKVManager.page_bytes(
            8, n_layers=cfg.num_hidden_layers,
            num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim)
        e1 = _engine(cfg, params, mp=1, kv_pool_bytes=budget)
        e2 = _engine(cfg, params, mp=2, kv_pool_bytes=budget)
        self.assertEqual(e2.mgr.max_pages, 2 * e1.mgr.max_pages)
        self.assertEqual(e2.kv_shards, 2)
        # per-chip bytes within one page of the budget on both
        for e in (e1, e2):
            self.assertLessEqual(e.mgr.kv_pool_bytes(), budget)
        self.assertEqual(e2.mgr.kv_pool_bytes(aggregate=True),
                         2 * e2.mgr.kv_pool_bytes())

    def test_mqa_engine_records_replicated_pools(self):
        cfg, _, params = _tiny_setup(nkv=1)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            eng = _engine(cfg, params, mp=2)
        self.assertEqual(eng.kv_shards, 1)  # pools replicated
        self.assertEqual(eng.mp, 2)         # q compute still shards


class TestShardedTokenIdentity(unittest.TestCase):
    def test_mp2_disaggregated_identity_bf16_churn(self):
        """Tier-1 core guarantee: an mp=2 kv-head-sharded DISAGGREGATED
        engine serves byte-identical tokens to the single-chip unified
        engine through prefix-cache churn (hits + recycling), with
        per-chip pool bytes at exactly half and every request crossing
        the prefill->decode handoff."""
        cfg, _, params = _tiny_setup()
        rng = np.random.default_rng(7)
        prompts = _churn_prompts(cfg, rng)
        ref = _engine(cfg, params, mp=1)
        t_ref = _serve(ref, prompts)
        eng = _engine(cfg, params, mp=2, disaggregated=True)
        t_mp = _serve(eng, prompts)
        self.assertEqual(t_ref, t_mp)
        self.assertGreater(eng.prefix_hit_tokens, 0)
        self.assertEqual(eng.prefill_handoffs, len(prompts))
        # same page capacity, half the per-chip bytes
        self.assertEqual(eng.mgr.max_pages, ref.mgr.max_pages)
        self.assertEqual(2 * eng.mgr.kv_pool_bytes(),
                         ref.mgr.kv_pool_bytes())
        # drain: every page back (scratch aside), nothing leaked at the
        # handoff
        self.assertEqual(eng.mgr.n_available, eng.mgr.max_pages - 1)

    @pytest.mark.slow  # tier-1 keeps the disaggregated mp=2 pair above
    def test_mp2_unified_identity_bf16(self):
        """The sharded engine alone (no disaggregation) — isolates the
        shard_map programs from the scheduler split."""
        cfg, _, params = _tiny_setup()
        rng = np.random.default_rng(7)
        prompts = _churn_prompts(cfg, rng)
        t1 = _serve(_engine(cfg, params, mp=1), prompts)
        t2 = _serve(_engine(cfg, params, mp=2), prompts)
        self.assertEqual(t1, t2)

    @pytest.mark.slow
    def test_mp2_identity_int8_pools(self):
        """Sharded INT8 pools: the f32 scale sidecars shard with their
        pages and quantize-on-scatter/dequantize-in-kernel runs per
        shard — still token-identical to single-chip int8."""
        cfg, _, params = _tiny_setup()
        rng = np.random.default_rng(11)
        prompts = _churn_prompts(cfg, rng)
        t1 = _serve(_engine(cfg, params, mp=1, kv="int8"), prompts)
        t2 = _serve(_engine(cfg, params, mp=2, kv="int8"), prompts)
        self.assertEqual(t1, t2)

    @pytest.mark.slow
    def test_mp4_identity(self):
        cfg, _, params = _tiny_setup(nkv=4)
        rng = np.random.default_rng(13)
        prompts = _churn_prompts(cfg, rng)
        t1 = _serve(_engine(cfg, params, mp=1), prompts)
        t4 = _serve(_engine(cfg, params, mp=4), prompts)
        self.assertEqual(t1, t4)

    @pytest.mark.slow
    def test_mqa_fallback_identity(self):
        """nkv=1 replicated-KV fallback still serves identical tokens
        (each shard streams the FULL pools against its local q group)."""
        cfg, _, params = _tiny_setup(nkv=1)
        rng = np.random.default_rng(17)
        prompts = _churn_prompts(cfg, rng)
        t1 = _serve(_engine(cfg, params, mp=1), prompts)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            t2 = _serve(_engine(cfg, params, mp=2), prompts)
        self.assertEqual(t1, t2)

    @pytest.mark.slow
    def test_paged_generate_mp2_identity(self):
        """Model-level API: build_paged_generate(serving_mp=2) is
        byte-identical to the single-chip program."""
        import jax
        import jax.numpy as jnp

        cfg, _, params = _tiny_setup()
        b, sb, max_new, bs = 2, 8, 4, 8
        n_pages = -(-(sb + max_new) // bs)
        tables = jnp.arange(b * n_pages, dtype=jnp.int32).reshape(
            b, n_pages)
        args = (params, jnp.ones((b, sb), jnp.int32),
                jnp.full((b,), sb, jnp.int32), tables,
                jax.random.PRNGKey(0), jnp.float32(1.0),
                jnp.float32(1.0))
        out1 = np.asarray(
            build_paged_generate(cfg, b, sb, max_new, bs,
                                 serving_mp=1)(*args))
        out2 = np.asarray(
            build_paged_generate(cfg, b, sb, max_new, bs,
                                 serving_mp=2)(*args))
        np.testing.assert_array_equal(out1, out2)


class TestCompileGuardMP(unittest.TestCase):
    def test_zero_recompiles_after_warm_mp2(self):
        """warm() covers the sharded programs: mixed traffic (cold at
        two buckets, prefix hits, retire/recycle churn) adds ZERO
        compiles, and `mp` rides every prefill program key."""
        cfg, _, params = _tiny_setup()
        rng = np.random.default_rng(19)
        eng = _engine(cfg, params, mp=2, prefill_batch=1,
                      prefix_cache=True,
                      unified_step=False)  # split program keys under test
        eng.warm(buckets=[8, 16])
        before = eng.compile_stats()
        self.assertNotIn(-1, before.values(),
                         "jit cache-size counter unavailable")
        self.assertTrue(all(k.split(":")[-1] == "2"
                            for k in before if k != "decode"),
                        f"mp missing from program keys: {before}")
        shared = rng.integers(1, cfg.vocab_size, (8,)).tolist()
        prompts = ([shared + rng.integers(1, cfg.vocab_size,
                                          (n,)).tolist() for n in (3, 5)]
                   + [rng.integers(1, cfg.vocab_size, (n,)).tolist()
                      for n in (2, 9, 14)])
        for i, pr in enumerate(prompts):
            eng.add_request(pr, max_new=2 + i % 4)
        eng.run(max_iters=300)
        self.assertEqual(len(eng.finished), len(prompts))
        self.assertGreater(eng.prefix_hit_tokens, 0)
        self.assertEqual(eng.compile_stats(), before)


class TestDisaggregation(unittest.TestCase):
    def test_prefill_runs_ahead_of_decode_slots(self):
        """The decoupling itself: with every decode slot occupied, the
        prefill worker still admits into the handoff (up to `slots`
        ahead) — under the unified scheduler admission would block."""
        cfg, _, params = _tiny_setup()
        rng = np.random.default_rng(23)
        # pool sized for all 4 requests at once: this test watches the
        # SLOT decoupling, not page pressure (2 pages per request at
        # bucket 8 + max_new 6, + the scratch page)
        eng = _engine(cfg, params, mp=1, disaggregated=True,
                      max_pages=16)
        for _ in range(4):
            eng.add_request(
                rng.integers(1, cfg.vocab_size, (5,)).tolist(),
                max_new=6)
        eng.warm(buckets=[8])
        eng._admit()            # prefill worker: fills slots' worth...
        self.assertEqual(len(eng._handoff), 2)
        self.assertEqual(eng.n_active, 0)   # ...without taking a slot
        eng._install_handoffs()             # decode worker maps them
        self.assertEqual(eng.n_active, 2)
        self.assertEqual(len(eng._handoff), 0)
        eng._admit()            # headroom again: next pair prefills
        self.assertEqual(len(eng._handoff), 2)
        eng.run(max_iters=300)
        self.assertEqual(len(eng.finished), 4)
        self.assertEqual(eng.prefill_handoffs, 4)
        self.assertEqual(eng.mgr.n_available, eng.mgr.max_pages - 1)

    @pytest.mark.slow  # tier-1 budget: disagg identity also guarded by
    # TestShardedTokenIdentity's mp=2+disagg churn pair
    def test_disaggregated_identity_unified(self):
        """Handoff changes WHEN a request reaches a slot, never its
        tokens: disaggregated == unified on the same traffic, and a
        first-token-EOS request retires at the handoff without ever
        taking a decode slot."""
        cfg, model, params = _tiny_setup()
        rng = np.random.default_rng(29)
        prompts = [rng.integers(1, cfg.vocab_size, (n,)).tolist()
                   for n in (3, 7, 9, 5)]
        t_uni = _serve(_engine(cfg, params, mp=1), prompts)
        eng = _engine(cfg, params, mp=1, disaggregated=True)
        t_dis = _serve(eng, prompts)
        self.assertEqual(t_uni, t_dis)
        # max_new=1 rows (i % 4 == 3 in _serve gives max_new 5..2) —
        # force one explicitly: it must finish without a slot
        eng2 = _engine(cfg, params, mp=1, disaggregated=True)
        r = eng2.add_request(prompts[0], max_new=1)
        eng2.run(max_iters=50)
        self.assertEqual(len(r.tokens), 1)
        self.assertIsNone(r.slot)           # never bound to a slot
        self.assertEqual(eng2.prefill_handoffs, 1)


class TestWatchdogSharded(unittest.TestCase):
    @pytest.mark.slow  # two warmed engines + a 2 s watchdog deadline
    def test_hung_retire_never_frees_sharded_prefix_page(self):
        """chaos hang:decode + watchdog retire of the slot OWNING a
        shard-mapped prefix page: the surviving slot still maps the
        page on EVERY shard (refcounts are host state, replicated by
        construction), so its tokens come out exactly as on an
        unsharded, uncached engine."""
        from paddle_tpu.resilience import chaos

        cfg, _, params = _tiny_setup()
        rng = np.random.default_rng(3)
        shared = rng.integers(1, cfg.vocab_size, (8,)).tolist()
        pa = shared + rng.integers(1, cfg.vocab_size, (5,)).tolist()
        pb = shared + rng.integers(1, cfg.vocab_size, (4,)).tolist()

        ref = _engine(cfg, params, mp=1, prefix_cache=False,
                      max_new_tokens=4, steps_per_sync=2)
        ref_b = ref.add_request(pb)
        ref.run(max_iters=100)

        eng = _engine(cfg, params, mp=2, max_new_tokens=4,
                      steps_per_sync=2,
                      unified_step=False)  # split watchdog semantics
        ra = eng.add_request(pa)
        eng.warm(buckets=[8, 16])  # compiles land before the deadline
        eng.step()                 # A prefills, inserts the shared block
        rb = eng.add_request(pb)   # hits the block next step
        chaos.install("hang:decode:20")
        try:
            eng.run(watchdog_timeout=2.0)
        finally:
            chaos.uninstall()
        self.assertTrue(ra.failed)
        self.assertFalse(rb.failed)
        self.assertEqual(rb.cached_tokens, 8)
        self.assertEqual(eng.hung_retired, 1)
        self.assertEqual(rb.tokens, ref_b.tokens)
        self.assertEqual(eng.mgr.n_available, eng.mgr.max_pages - 1)
        self.assertGreaterEqual(eng.mgr.n_cached, 1)


if __name__ == "__main__":
    unittest.main()
