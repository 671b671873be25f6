"""Unified ragged serving step (ISSUE 14): ONE chunked-prefill+decode
program (over `ragged_paged_attention`) vs the split program zoo —
token identity per ROW CLASS (pure decode / cold prefill /
cached-prefix / chunked prefill resumed across steps) through
recycling churn on bf16 AND int8 pools at mp=1 and mp=2, the
zero-recompile-after-warm guard on the unified program key, strictly
fewer warmed programs than the split engine, disaggregated handoff
and double buffering on the unified path, the unified watchdog
timeline, and the audit wiring (the unified program joins
`_program_inventory()` and audits clean)."""
import dataclasses
import unittest
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import ContinuousBatchingEngine
from paddle_tpu.serving import engine as engine_mod


def _tiny_setup(nkv=2, seed=21, dtype=None):
    cfg = dataclasses.replace(LlamaConfig.tiny(), num_key_value_heads=nkv)
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    params = dict(model.raw_state())
    if dtype is not None:
        params = {k: (v.astype(dtype) if v.dtype == jnp.float32 else v)
                  for k, v in params.items()}
    return cfg, model, params


def _engine(cfg, params, unified, **over):
    kw = dict(slots=2, prompt_bucket=8, max_prompt_len=32,
              max_new_tokens=6, block_size=8, steps_per_sync=3,
              prefix_cache=True, unified_step=unified)
    kw.update(over)
    return ContinuousBatchingEngine(cfg, dict(params), **kw)


def _serve(eng, prompts, max_new=None):
    for i, pr in enumerate(prompts):
        eng.add_request(pr, max_new=max_new if max_new is not None
                        else 2 + i % 4)
    eng.run(max_iters=500)
    assert len(eng.finished) == len(prompts)
    assert eng.mgr.n_available == eng.mgr.max_pages - 1  # drain
    return {r.req_id: list(r.tokens) for r in eng.finished}


def _row_class_prompts(cfg, rng):
    """One trace exercising every row class through a 2-slot engine:
    cached-prefix rows (shared 8-token head), cold short rows
    (single-window prefill), and CHUNKED rows (prompts wider than the
    8-token budget resume across steps) — sized so pages recycle."""
    shared = rng.integers(1, cfg.vocab_size, (8,)).tolist()
    return ([shared + rng.integers(1, cfg.vocab_size, (n,)).tolist()
             for n in (3, 7, 2)]                       # cached-prefix
            + [rng.integers(1, cfg.vocab_size, (n,)).tolist()
               for n in (5, 2)]                        # cold, 1 window
            + [rng.integers(1, cfg.vocab_size, (n,)).tolist()
               for n in (30, 22, 17)])                 # chunked


def _solo_decode_logits(cfg, params, unified, prompt, max_new):
    """Serve ONE request alone and return (its tokens, the logits
    behind tokens 1.. as the path's own programs computed them): every
    `_sample_next` call reports its logits, and after the last prefill
    call (batch 1; the decode lane is `slots` wide) the decode calls
    are the request's tokens in order, at row 0."""
    seen = []
    sample = engine_mod._sample_next

    def spy(logits, *a):
        jax.debug.callback(lambda x: seen.append(np.asarray(x, np.float32)),
                           logits, ordered=True)
        return sample(logits, *a)

    with mock.patch.object(engine_mod, "_sample_next", spy):
        eng = _engine(cfg, params, unified)
        assert eng.slots > 1
        eng.add_request(prompt, max_new=max_new)
        eng.run(max_iters=100)
    last_prefill = max(i for i, x in enumerate(seen) if x.shape[0] == 1)
    return (list(eng.finished[0].tokens),
            [x[0] for x in seen[last_prefill + 1:]])


class TestTokenIdentity(unittest.TestCase):
    """ACCEPTANCE: unified-vs-split token identity per row class.
    Decode rows are literally the same program (pure-decode steps
    dispatch the split decode chunk); prefill row classes go through
    the ragged window and must still emit identical greedy tokens."""

    def _identity(self, dtype, **over):
        cfg, _, params = _tiny_setup(dtype=dtype)
        rng = np.random.default_rng(3)
        prompts = _row_class_prompts(cfg, rng)
        t_split = _serve(_engine(cfg, params, False, **over), prompts)
        eng = _engine(cfg, params, True, **over)
        t_uni = _serve(eng, prompts)
        self.assertEqual(t_split, t_uni)
        # every row class actually ran: prefix hits, chunked windows
        self.assertGreater(eng.prefix_hit_tokens, 0)
        self.assertGreater(eng.prefill_chunks, len(prompts))
        self.assertGreater(eng.chunk_tokens, 0)
        return eng

    def test_identity_bf16_all_row_classes(self):
        """bf16 keeps 8 bits, so the two prefill paths (flash over the
        whole bucket, ragged windows over the pool) hand decode K/V
        that differ in the last bit and a near-tie between two logits
        may fall either way. Where a request's tokens part, the two
        paths' logits for that token must agree within 2 bf16 steps at
        the logits' size (|logit| < 4: a step is 2**-6), and the two
        tokens must be both paths' top two. Tokens before that point,
        and every other request, are identical; the f32 twin below
        holds all of them to identity."""
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        prompts = _row_class_prompts(cfg, np.random.default_rng(3))
        t_split = _serve(_engine(cfg, params, False), prompts)
        eng = _engine(cfg, params, True)
        t_uni = _serve(eng, prompts)
        self.assertGreater(eng.prefix_hit_tokens, 0)
        self.assertGreater(eng.prefill_chunks, len(prompts))
        parted = [r for r in t_split if t_split[r] != t_uni[r]]
        self.assertLessEqual(len(parted), 1, f"{t_split} vs {t_uni}")
        for r in parted:
            a, b = t_split[r], t_uni[r]
            self.assertEqual(len(a), len(b))
            j = next(i for i in range(len(a)) if a[i] != b[i])
            self.assertGreater(j, 0, "the first token is prefill's")
            rows = []
            for unified, toks in ((False, a), (True, b)):
                solo, logits = _solo_decode_logits(
                    cfg, params, unified, prompts[r], len(toks))
                self.assertEqual(solo, toks, "served alone, the "
                                 "request takes another course")
                rows.append(logits[j - 1])
            self.assertLess(np.max(np.abs(rows[0])), 4.0)
            self.assertLessEqual(np.max(np.abs(rows[0] - rows[1])),
                                 2 * 2.0 ** -6)
            for row in rows:
                self.assertEqual(set(np.argsort(row)[-2:]),
                                 {a[j], b[j]})

    def test_identity_f32_all_row_classes(self):
        self._identity(None)

    def test_int8_pools_strong_match_all_row_classes(self):
        """int8 pools: unified-vs-split is a STRONG-MATCH contract,
        not bitwise identity (the PR 5 precedent — int8 near-ties
        cascade). Two inherent divergence sources, both quantization
        noise rather than scheduling bugs: (a) a page holding window
        pad positions bakes DIFFERENT garbage into its absmax scale
        than the split flash-prefill's causally-computed pads, and
        (b) a chunked row reads its earlier chunks back through the
        QUANTIZED pool where the split one-shot prefill attends raw
        K/V. Scheduling, capacity and drain behavior must still be
        exact, and greedy agreement high."""
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        rng = np.random.default_rng(5)
        prompts = _row_class_prompts(cfg, rng)
        kw = dict(kv_cache_dtype="int8")
        t_split = _serve(_engine(cfg, params, False, **kw), prompts)
        t_uni = _serve(_engine(cfg, params, True, **kw), prompts)
        same = sum(t_split[r] == t_uni[r] for r in t_split)
        self.assertGreaterEqual(same, len(prompts) - 2,
                                f"{t_split} vs {t_uni}")
        total = agree = 0
        for r in t_split:
            a, b = t_split[r], t_uni[r]
            n = min(len(a), len(b))
            total += max(len(a), len(b))
            agree += sum(x == y for x, y in zip(a[:n], b[:n]))
        self.assertGreaterEqual(agree / total, 0.8,
                                f"match rate {agree}/{total}")

    def test_identity_mp2(self):
        """Unified mp=2 (kv-head-sharded pools, ONE bf16 o-proj
        all-gather per layer covering both lanes) is token-identical
        to unified mp=1 through every row class."""
        if len(jax.devices()) < 2:
            self.skipTest("needs 2 devices")
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        rng = np.random.default_rng(7)
        prompts = _row_class_prompts(cfg, rng)
        t1 = _serve(_engine(cfg, params, True, serving_mp=1), prompts)
        t2 = _serve(_engine(cfg, params, True, serving_mp=2), prompts)
        self.assertEqual(t1, t2)

    @pytest.mark.slow  # tier-1 keeps the bf16 mp=2 guard above
    def test_identity_mp2_int8(self):
        if len(jax.devices()) < 2:
            self.skipTest("needs 2 devices")
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        rng = np.random.default_rng(9)
        prompts = _row_class_prompts(cfg, rng)
        kw = dict(kv_cache_dtype="int8")
        t1 = _serve(_engine(cfg, params, True, serving_mp=1, **kw),
                    prompts)
        t2 = _serve(_engine(cfg, params, True, serving_mp=2, **kw),
                    prompts)
        self.assertEqual(t1, t2)

    def test_db_and_disaggregated_identity(self):
        """Double buffering (pure-decode chunks still pipeline between
        mixed steps) and the disaggregated handoff both preserve tokens
        on the unified path."""
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        rng = np.random.default_rng(11)
        prompts = _row_class_prompts(cfg, rng)
        t_ref = _serve(_engine(cfg, params, True), prompts)
        t_db = _serve(_engine(cfg, params, True, double_buffer=True),
                      prompts)
        eng = _engine(cfg, params, True, disaggregated=True)
        t_dis = _serve(eng, prompts)
        self.assertEqual(t_ref, t_db)
        self.assertEqual(t_ref, t_dis)
        self.assertEqual(eng.prefill_handoffs, len(prompts))

    def test_full_prefix_hit_never_trimmed(self):
        """The unified planner reserves EXACT pages (no bucket
        rounding), so a block-aligned prefix is mapped in full — the
        split planner's trim (bucket-widening guard) is dead weight
        here. A repeat prompt hits every full block."""
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        rng = np.random.default_rng(12)
        prompt = rng.integers(1, cfg.vocab_size, (25,)).tolist()
        eng = _engine(cfg, params, True, slots=1, prompt_bucket=16,
                      max_new_tokens=8, steps_per_sync=4)
        r1 = eng.add_request(prompt)
        r2 = eng.add_request(prompt)
        eng.run(max_iters=200)
        self.assertTrue(r1.done and r2.done)
        self.assertEqual(r1.tokens, r2.tokens)
        # all 3 full blocks hit — the split path trims this to 16
        self.assertEqual(r2.cached_tokens, 24)


class TestCompileGuard(unittest.TestCase):
    def test_zero_recompiles_after_warm_and_fewer_programs(self):
        """ACCEPTANCE: after a one-program warm(), a full mixed trace
        (cold, cached, chunked, per-request max_new variety, recycle
        churn) adds ZERO compiles to the unified key — and the unified
        engine warms STRICTLY fewer programs than the split engine
        over the same traffic."""
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        rng = np.random.default_rng(13)
        prompts = _row_class_prompts(cfg, rng)

        split = _engine(cfg, params, False)
        split.warm(buckets=[8, 16, 24, 32])
        uni = _engine(cfg, params, True)
        uni.warm()
        before = uni.compile_stats()
        self.assertEqual(set(before), {"decode", "unified"})
        self.assertNotIn(-1, before.values(),
                         "jit cache-size counter unavailable")
        self.assertLess(len(before), len(split.compile_stats()))
        _serve(uni, prompts)
        self.assertGreater(uni.prefix_hit_tokens, 0)
        self.assertGreater(uni.chunk_tokens, 0)
        self.assertEqual(uni.compile_stats(), before)

    def test_token_budget_validation(self):
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        with self.assertRaisesRegex(ValueError, "token_budget"):
            _engine(cfg, params, True, token_budget=12)  # not page mult
        with self.assertRaisesRegex(ValueError, "token_budget"):
            _engine(cfg, params, True, token_budget=4)   # < block


class TestWatchdogUnified(unittest.TestCase):
    def test_hung_decode_retires_victim_keeps_shared_prefix(self):
        """The unified watchdog timeline: a hang on a DECODE dispatch
        (after A's prefill inserted the shared block) retires A; B
        still maps the shared page on admission and emits exactly the
        uncached engine's tokens."""
        from paddle_tpu.resilience import chaos

        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        rng = np.random.default_rng(3)
        shared = rng.integers(1, cfg.vocab_size, (8,)).tolist()
        pa = shared + rng.integers(1, cfg.vocab_size, (5,)).tolist()
        pb = shared + rng.integers(1, cfg.vocab_size, (4,)).tolist()

        ref = _engine(cfg, params, True, prefix_cache=False,
                      max_new_tokens=4, steps_per_sync=2)
        ref_b = ref.add_request(pb)
        ref.run(max_iters=100)

        eng = _engine(cfg, params, True, max_new_tokens=4,
                      steps_per_sync=2)
        ra = eng.add_request(pa)
        eng.warm()
        # drive A through prefill so the shared block is inserted and
        # A is DECODING before the chaos seam arms
        while eng._prefilling is not None or eng.n_active == 0:
            eng.step()
        self.assertGreater(eng.prefix_inserts, 0)
        rb = eng.add_request(pb)
        # drive B through ITS prefill too: the hang must land on a
        # PURE-DECODE dispatch — a mixed-step timeout blames the
        # prefilling request first (see the requeue test below), and
        # this test guards the decode-victim path's refcount invariant
        while eng._prefilling is not None or rb.prefill_time is None:
            eng.step()
        self.assertEqual(eng.n_active, 2)
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

    def test_hung_prefill_window_requeues_once(self):
        """A timeout while a request is mid-chunked-prefill blames THE
        PREFILLING REQUEST (its window rode the hung dispatch; blaming
        decode first would serially fail innocent slots against a
        deterministically hanging window): under requeue_hung it gets
        its one retry (prefill restarts at the prompt, pages released
        through the refcounted pool) and completes with the
        undisturbed engine's tokens."""
        from paddle_tpu.resilience import chaos

        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        rng = np.random.default_rng(6)
        prompt = rng.integers(1, cfg.vocab_size, (20,)).tolist()

        ref = _engine(cfg, params, True, max_new_tokens=4,
                      steps_per_sync=2)
        ref_r = ref.add_request(prompt)
        ref.run(max_iters=100)

        eng = _engine(cfg, params, True, max_new_tokens=4,
                      steps_per_sync=2)
        eng.warm()
        req = eng.add_request(prompt)
        chaos.install("hang:decode:20")  # first window dispatch hangs
        try:
            eng.run(watchdog_timeout=2.0, requeue_hung=True)
        finally:
            chaos.uninstall()
        self.assertFalse(req.failed)
        self.assertTrue(req.requeued)
        self.assertEqual(eng.hung_requeued, 1)
        self.assertIsNone(eng._prefilling)
        self.assertEqual(req.tokens, ref_r.tokens)
        self.assertEqual(eng.mgr.n_available, eng.mgr.max_pages - 1)

    def test_hung_prefill_window_fails_without_requeue(self):
        from paddle_tpu.resilience import chaos

        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        rng = np.random.default_rng(8)
        eng = _engine(cfg, params, True, max_new_tokens=4,
                      steps_per_sync=2)
        eng.warm()
        req = eng.add_request(
            rng.integers(1, cfg.vocab_size, (20,)).tolist())
        chaos.install("hang:decode:20")
        try:
            eng.run(watchdog_timeout=2.0)
        finally:
            chaos.uninstall()
        self.assertTrue(req.failed)
        self.assertEqual(eng.hung_retired, 1)
        # the finished contract holds even for a never-prefilled
        # failure: TTFT consumers iterating `finished` see no None
        self.assertIsNotNone(req.prefill_time)
        self.assertEqual(eng.mgr.n_available, eng.mgr.max_pages - 1)


class TestAuditWiring(unittest.TestCase):
    def test_unified_program_joins_inventory_and_audits(self):
        """ISSUE 14 satellite: the unified program rides
        `_program_inventory()`, so one shared trace prices it through
        all three static auditors — donation-clean, the expected bf16
        all-gather wire profile at mp=2, and a roofline row."""
        if len(jax.devices()) < 2:
            self.skipTest("needs 2 devices")
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        eng = _engine(cfg, params, True, serving_mp=2)
        names = [n for n, _, _ in eng._program_inventory()]
        self.assertEqual(names, ["decode", "unified"])
        graphs = eng._traced_inventory()
        mem = eng.audit_memory(graphs=graphs)
        self.assertTrue(mem["donation_clean"], mem)
        self.assertIn("unified", mem["programs"])
        com = eng.audit_comms(graphs=graphs)
        uni = com["programs"]["unified"]
        self.assertEqual(set(uni["per_kind"]), {"all_gather"})
        self.assertEqual(uni["top_talkers"][0]["dtype"], "bfloat16")
        roof = eng.audit_roofline(graphs=graphs)
        self.assertIn("unified", roof["programs"])
        self.assertGreater(
            roof["programs"]["unified"]["predicted_step_ms"], 0)

    def test_tpu105_quieter_per_program_fewer_distinct_launches(self):
        """ISSUE 14 satellite: the unified step is QUIETER for TPU105
        (fusion-miss, scan-body launch counting) — strictly fewer
        distinct programs dispatch per serving cycle, and NO program
        carries more TPU105 diagnostics than the split fleet's worst
        (the unified program's only scan is the decode lane the split
        decode chunk already has: the chunk lane adds zero loop-body
        launch sites)."""
        from paddle_tpu.analysis.pipeline import analyze

        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        split = _engine(cfg, params, False)
        split.warm(buckets=[8, 16])
        uni = _engine(cfg, params, True)

        def tpu105_per_program(eng):
            return {name: len(analyze(None, graph=g, rules=["TPU105"]))
                    for name, g in eng._traced_inventory()}

        d_split = tpu105_per_program(split)
        d_uni = tpu105_per_program(uni)
        self.assertLess(len(d_uni), len(d_split))
        self.assertLessEqual(max(d_uni.values()), max(d_split.values()))
        # the chunk lane adds no fusion-miss sites over the decode body
        self.assertEqual(d_uni["unified"], d_uni["decode"])


if __name__ == "__main__":
    unittest.main()
