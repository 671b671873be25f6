"""Speculative decoding (ISSUE 19): n-gram / draft-model drafting with
one-ragged-window verification. Drafter units (hit / miss / history
growth / commit-clamp bookkeeping), verify-window parity against
sequential decode via an oracle drafter (acceptance must be total when
the drafts ARE the sequential continuation), the ACCEPTANCE bar —
spec-on greedy bf16 TOKEN-IDENTICAL to spec-off through prefix-cache
churn and slot recycling at mp=1 (tier-1) and mp=2 / int8 strong-match
(@slow) — the zero-recompile-after-warm guard with spec_k in every
program key, watchdog hang mid-verify retiring/requeueing without
corrupting survivors, tuner knob-space canonicalisation, and the
`python -m paddle_tpu.serving.speculative` CI smoke gate."""
import dataclasses
import json
import os
import subprocess
import sys
import unittest

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import ContinuousBatchingEngine
from paddle_tpu.serving.speculative import (Drafter, DraftModelDrafter,
                                            NGramDrafter, resolve_spec_k,
                                            resolve_speculative)


def _tiny_setup(nkv=2, seed=21, dtype=None):
    cfg = dataclasses.replace(LlamaConfig.tiny(), num_key_value_heads=nkv)
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    params = dict(model.raw_state())
    if dtype is not None:
        params = {k: (v.astype(dtype) if v.dtype == jnp.float32 else v)
                  for k, v in params.items()}
    return cfg, model, params


def _engine(cfg, params, **over):
    kw = dict(slots=2, prompt_bucket=8, max_prompt_len=64,
              max_new_tokens=8, block_size=8, steps_per_sync=3,
              prefix_cache=True)
    kw.update(over)
    return ContinuousBatchingEngine(cfg, dict(params), **kw)


def _serve(eng, prompts, max_new=None):
    for i, pr in enumerate(prompts):
        eng.add_request(pr, max_new=max_new if max_new is not None
                        else 3 + i % 4)
    eng.run(max_iters=1000)
    assert len(eng.finished) == len(prompts)
    assert eng.mgr.n_available == eng.mgr.max_pages - 1  # drain
    return {r.req_id: list(r.tokens) for r in eng.finished}


def _churn_prompts(cfg, rng):
    """Prefix-cache churn + slot recycling through a 2-slot engine:
    repetitive rows the n-gram drafter accepts on (shared 8-token head
    followed by a repeated phrase), plus cold unique rows that must
    degrade to k=0 drafting."""
    shared = rng.integers(1, cfg.vocab_size, (8,)).tolist()
    phrase = rng.integers(1, cfg.vocab_size, (6,)).tolist()
    return ([shared + phrase * 3 + rng.integers(
                1, cfg.vocab_size, (n,)).tolist() for n in (3, 5, 2)]
            + [rng.integers(1, cfg.vocab_size, (n,)).tolist()
               for n in (5, 2, 17)])


class TestResolvers(unittest.TestCase):
    def test_resolve_speculative(self):
        self.assertEqual(resolve_speculative(None), "off")  # flag default
        self.assertEqual(resolve_speculative("NGRAM "), "ngram")
        self.assertEqual(resolve_speculative(""), "off")
        with self.assertRaisesRegex(ValueError, "speculative"):
            resolve_speculative("treeverify")

    def test_resolve_spec_k(self):
        self.assertEqual(resolve_spec_k(None), 4)  # flag default
        self.assertEqual(resolve_spec_k(8), 8)
        with self.assertRaisesRegex(ValueError, "spec_k"):
            resolve_spec_k(0)

    def test_flag_fallback(self):
        prev = paddle.get_flags(["speculative", "spec_k"])
        paddle.set_flags({"speculative": "ngram", "spec_k": 8})
        try:
            self.assertEqual(resolve_speculative(None), "ngram")
            self.assertEqual(resolve_spec_k(None), 8)
        finally:
            paddle.set_flags({k.replace("FLAGS_", ""): v
                              for k, v in prev.items()})

    def test_build_validation(self):
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        with self.assertRaisesRegex(ValueError, "greedy-only"):
            _engine(cfg, params, speculative="ngram", do_sample=True)
        with self.assertRaisesRegex(ValueError, "serving_cp"):
            _engine(cfg, params, speculative="ngram", serving_cp=2)
        with self.assertRaisesRegex(ValueError, "DraftModelDrafter"):
            _engine(cfg, params, speculative="draft")


class TestNGramDrafter(unittest.TestCase):
    def test_hit_returns_continuation_of_most_recent_match(self):
        d = NGramDrafter()
        # tail (7, 8) last occurs at positions 2..3, followed by 9, 10
        hist = [1, 2, 7, 8, 9, 10, 7, 8]
        self.assertEqual(d.draft(0, 0, hist, 2), [9, 10])
        # k wider than the remaining continuation: returns what exists
        self.assertEqual(d.draft(0, 0, hist, 10), [9, 10, 7, 8])

    def test_prefers_widest_ngram(self):
        d = NGramDrafter(max_ngram=3)
        # tail (5, 6, 7): the 3-gram match at 0..2 (followed by 100)
        # must win over the 1-gram match of (7,) at position 2
        hist = [5, 6, 7, 100, 42, 5, 6, 7]
        self.assertEqual(d.draft(0, 0, hist, 1), [100])

    def test_miss_returns_empty(self):
        d = NGramDrafter()
        self.assertEqual(d.draft(0, 0, [1, 2, 3, 4, 5], 4), [])
        self.assertEqual(d.draft(0, 0, [1], 4), [])   # too short
        self.assertEqual(d.draft(0, 0, [1, 2, 1, 3], 0), [])  # k=0

    def test_history_growth_reuses_generated_tokens(self):
        """Generated tokens join the lookup corpus: a phrase that first
        appears in generation drafts on its second occurrence."""
        d = NGramDrafter()
        hist = [1, 2, 3]
        self.assertEqual(d.draft(0, 0, hist, 2), [])
        hist = hist + [9, 8, 7, 5, 9, 8]          # generation repeats
        self.assertEqual(d.draft(0, 0, hist, 2), [7, 5])

    def test_validation(self):
        with self.assertRaisesRegex(ValueError, "min_ngram"):
            NGramDrafter(max_ngram=1, min_ngram=2)


class _OracleDrafter(Drafter):
    """Drafts the EXACT tokens a spec-off engine produced — the
    verify-window parity probe: if window row j's logits match the j'th
    sequential decode step's, every offered draft is accepted."""

    def __init__(self, answers, prompt_lens):
        self.answers = answers          # {req_id: full off-run tokens}
        self.prompt_lens = prompt_lens  # {req_id: prompt length}

    def draft(self, slot_id, req_id, history, k, table_row=None,
              budget=None):
        emitted = len(history) - self.prompt_lens[req_id]
        return list(self.answers[req_id][emitted:emitted + k])


class TestVerifyWindowParity(unittest.TestCase):
    def test_oracle_drafts_fully_accepted(self):
        """ONE ragged verify window over [pending, d1..dk] must score
        exactly what k+1 sequential decode steps would: feeding the
        true sequential continuation as drafts, the target accepts
        every offered token and the output stays identical."""
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        rng = np.random.default_rng(3)
        prompts = _churn_prompts(cfg, rng)
        off = _engine(cfg, params)
        t_off = _serve(off, prompts, max_new=8)
        oracle = _OracleDrafter(
            t_off, {r.req_id: len(r.prompt) for r in off.finished})
        eng = _engine(cfg, params, speculative="ngram", spec_k=3,
                      drafter=oracle)
        t_on = _serve(eng, prompts, max_new=8)
        self.assertEqual(t_off, t_on)
        self.assertGreater(eng.spec_drafted, 0)
        # total acceptance is the parity statement
        self.assertEqual(eng.spec_accepted, eng.spec_drafted)
        em = eng.metrics()
        self.assertEqual(em["acceptance_rate"], 1.0)
        self.assertEqual(em["spec_steps"], eng.spec_steps)


class TestTokenIdentity(unittest.TestCase):
    """ACCEPTANCE: spec-on greedy bf16 is TOKEN-IDENTICAL to spec-off
    through prefix-cache churn and slot recycling."""

    def _identity(self, **over):
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        rng = np.random.default_rng(5)
        prompts = _churn_prompts(cfg, rng)
        t_off = _serve(_engine(cfg, params, **over), prompts, max_new=8)
        eng = _engine(cfg, params, speculative="ngram", spec_k=4, **over)
        t_on = _serve(eng, prompts, max_new=8)
        self.assertEqual(t_off, t_on)
        # speculation actually happened: drafts offered AND accepted
        self.assertGreater(eng.spec_drafted, 0)
        self.assertGreater(eng.spec_accepted, 0)
        self.assertGreater(eng.prefix_hit_tokens, 0)  # churn was real
        return eng

    def test_identity_split_mp1(self):
        eng = self._identity()
        # accepted tokens mean FEWER verify dispatches than spec-off
        # decode steps would need at steps_per_sync tokens a chunk
        self.assertGreater(eng.metrics()["acceptance_rate"], 0.0)

    def test_identity_unified_path(self):
        """Unified engine: prefill phases keep the mixed ragged window,
        pure-decode phases dispatch the verify window."""
        self._identity(unified_step=True)

    def test_identity_double_buffer(self):
        """Speculative steps are synchronous — the pipelined scheduler
        drains its in-flight chunk and still emits identical tokens."""
        self._identity(double_buffer=True)

    @pytest.mark.slow
    def test_identity_ngram_k8(self):
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        rng = np.random.default_rng(7)
        prompts = _churn_prompts(cfg, rng)
        t_off = _serve(_engine(cfg, params), prompts)
        t_on = _serve(_engine(cfg, params, speculative="ngram",
                              spec_k=8), prompts)
        self.assertEqual(t_off, t_on)

    @pytest.mark.slow
    def test_identity_mp2(self):
        if len(jax.devices()) < 2:
            self.skipTest("needs 2 devices")
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        rng = np.random.default_rng(9)
        prompts = _churn_prompts(cfg, rng)
        t_off = _serve(_engine(cfg, params, serving_mp=2), prompts)
        t_on = _serve(_engine(cfg, params, serving_mp=2,
                              speculative="ngram", spec_k=4), prompts)
        self.assertEqual(t_off, t_on)

    @pytest.mark.slow  # tier-1 keeps the bf16 guards above
    def test_int8_pools_strong_match(self):
        """int8 pools: spec-on vs spec-off is a STRONG-MATCH contract,
        not bitwise identity (the PR 5/14 precedent): a rejected
        window position re-written later rides the page's monotone
        absmax chain, so near-ties can flip. Scheduling/drain behavior
        must stay exact and greedy agreement high."""
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        rng = np.random.default_rng(11)
        prompts = _churn_prompts(cfg, rng)
        kw = dict(kv_cache_dtype="int8")
        t_off = _serve(_engine(cfg, params, **kw), prompts, max_new=8)
        eng = _engine(cfg, params, speculative="ngram", spec_k=4, **kw)
        t_on = _serve(eng, prompts, max_new=8)
        self.assertGreater(eng.spec_accepted, 0)
        total = agree = 0
        for r in t_off:
            a, b = t_off[r], t_on[r]
            n = min(len(a), len(b))
            total += max(len(a), len(b))
            agree += sum(x == y for x, y in zip(a[:n], b[:n]))
        self.assertGreaterEqual(agree / total, 0.8,
                                f"match rate {agree}/{total}")


class TestDraftModelDrafter(unittest.TestCase):
    def test_draft_model_identity_and_acceptance(self):
        """speculative='draft' with the TARGET's own weights as the
        draft model: proposals are the target's own greedy continuation
        modulo kernel numerics, so acceptance is high and output stays
        token-identical to spec-off (acceptance-independent)."""
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        rng = np.random.default_rng(13)
        prompts = _churn_prompts(cfg, rng)
        t_off = _serve(_engine(cfg, params), prompts, max_new=6)
        drafter = DraftModelDrafter(cfg, dict(params))
        eng = _engine(cfg, params, speculative="draft", spec_k=3,
                      drafter=drafter)
        t_on = _serve(eng, prompts, max_new=6)
        self.assertEqual(t_off, t_on)
        self.assertGreater(eng.spec_drafted, 0)
        self.assertGreater(eng.spec_accepted, 0)
        # the drafter's program joined the compile-stats inventory
        self.assertIn("draft", eng.compile_stats())

    def test_note_commit_clamps_and_release_resets(self):
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        drafter = DraftModelDrafter(cfg, dict(params))
        eng = _engine(cfg, params, speculative="draft", spec_k=2,
                      drafter=drafter)
        req = eng.add_request([3, 1, 4, 1, 5], max_new=4)
        eng.run(max_iters=200)
        self.assertTrue(req.done)
        # retire released the slot's draft binding
        self.assertEqual(drafter._bound, [None] * eng.slots)
        self.assertEqual(list(drafter._len), [0] * eng.slots)


class TestCompileGuard(unittest.TestCase):
    def test_zero_recompiles_after_warm_with_spec_key(self):
        """ACCEPTANCE: warm() covers the verify program (and the
        drafter's); a full churn trace adds ZERO compiles, and spec_k
        rides every prefill cache key."""
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        rng = np.random.default_rng(15)
        prompts = _churn_prompts(cfg, rng)
        eng = _engine(cfg, params, speculative="ngram", spec_k=4)
        eng.warm(buckets=[8, 16, 24, 32])
        before = eng.compile_stats()
        self.assertIn("verify", before)
        self.assertNotIn(-1, before.values())
        _serve(eng, prompts)
        self.assertGreater(eng.spec_steps, 0)
        self.assertEqual(eng.compile_stats(), before)

    def test_spec_k_in_prefill_keys(self):
        """On the split path the prefill program zoo is keyed per
        shape — spec_k joins every key (right after the kv dtype; the
        cp/qcoll/mp tail keeps its cross-suite positions), so an off
        engine and a k=4 engine can never share a stale program."""
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        eng = _engine(cfg, params, speculative="ngram", spec_k=4,
                      unified_step=False)
        eng.warm(buckets=[8])
        prefill_keys = [k for k in eng.compile_stats()
                        if k.startswith("prefill:")]
        self.assertTrue(prefill_keys)
        for k in prefill_keys:
            self.assertEqual(k.split(":")[-4], "4", k)

    def test_off_engine_builds_no_verify_program(self):
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        eng = _engine(cfg, params)
        self.assertIsNone(eng._verify)
        self.assertEqual(eng.spec_k, 0)
        self.assertNotIn("verify", eng.compile_stats())
        em = eng.metrics()
        self.assertEqual(em["speculative"], "off")
        self.assertEqual(em["acceptance_rate"], 0.0)

    def test_verify_program_in_inventory_and_audits(self):
        """The verify window joins `_program_inventory()` and the three
        static auditors run clean over it."""
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        eng = _engine(cfg, params, speculative="ngram", spec_k=2)
        names = [n for n, _, _ in eng._program_inventory()]
        self.assertIn("verify", names)
        graphs = eng._traced_inventory(programs=("verify",))
        mem = eng.audit_memory(programs=("verify",), graphs=graphs)
        self.assertGreater(mem["fleet_peak_hbm_bytes"], 0)
        roof = eng.audit_roofline(programs=("verify",), graphs=graphs)
        self.assertIn("verify", roof["programs"])
        comms = eng.audit_comms(programs=("verify",), graphs=graphs)
        self.assertIsNotNone(comms)


class TestAdaptiveSpec(unittest.TestCase):
    """ISSUE 20 satellite: acceptance-adaptive draft depth. The policy
    is pure host state — the verify window stays spec_k+1 rows, only
    the per-step `want` cap moves, so no program key changes and no
    new compiles ever."""

    def test_policy_shrinks_to_floor_on_dead_drafting(self):
        from paddle_tpu.serving.speculative import AdaptiveSpecPolicy

        pol = AdaptiveSpecPolicy(4)
        self.assertEqual(pol.spec_k_effective, 4)
        for _ in range(10):
            pol.observe(4, 0)
        self.assertEqual(pol.spec_k_effective, 1)  # floor, never 0
        self.assertLess(pol.acceptance_ewma, 0.4)

    def test_policy_grows_back_after_patience(self):
        from paddle_tpu.serving.speculative import AdaptiveSpecPolicy

        pol = AdaptiveSpecPolicy(4, patience=3)
        for _ in range(10):
            pol.observe(4, 0)          # walk to the floor
        for _ in range(30):
            pol.observe(4, 4)          # sustained full acceptance
        self.assertEqual(pol.spec_k_effective, 4)  # capped at spec_k
        pol.observe(4, 4)
        self.assertEqual(pol.spec_k_effective, 4)  # never above the cap

    def test_policy_ignores_empty_windows_and_validates(self):
        from paddle_tpu.serving.speculative import AdaptiveSpecPolicy

        pol = AdaptiveSpecPolicy(4)
        pol.observe(0, 0)              # no drafts offered: no signal
        self.assertIsNone(pol.acceptance_ewma)
        self.assertEqual(pol.spec_k_effective, 4)
        with self.assertRaisesRegex(ValueError, "spec_k"):
            AdaptiveSpecPolicy(0)

    def test_resolver_and_flag(self):
        from paddle_tpu.serving.speculative import resolve_spec_adaptive

        self.assertFalse(resolve_spec_adaptive(None))  # flag default
        self.assertTrue(resolve_spec_adaptive(True))
        self.assertTrue(resolve_spec_adaptive("on"))
        self.assertFalse(resolve_spec_adaptive("0"))
        prev = paddle.get_flags(["spec_adaptive"])
        paddle.set_flags({"spec_adaptive": True})
        try:
            self.assertTrue(resolve_spec_adaptive(None))
        finally:
            paddle.set_flags({k.replace("FLAGS_", ""): v
                              for k, v in prev.items()})

    def test_engine_identity_metrics_and_zero_compiles(self):
        """Adaptive-on serves token-identical to spec-off (acceptance
        logic is unchanged — only draft depth adapts), reports the live
        depth in metrics(), and adds zero compiles after warm."""
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        rng = np.random.default_rng(5)
        prompts = _churn_prompts(cfg, rng)
        t_off = _serve(_engine(cfg, params), prompts, max_new=8)
        eng = _engine(cfg, params, speculative="ngram", spec_k=4,
                      spec_adaptive=True)
        eng.warm(buckets=[8, 16, 24, 32])
        before = eng.compile_stats()
        t_on = _serve(eng, prompts, max_new=8)
        self.assertEqual(t_off, t_on)
        self.assertEqual(eng.compile_stats(), before)
        self.assertGreater(eng.spec_drafted, 0)
        em = eng.metrics()
        self.assertTrue(em["spec_adaptive"])
        self.assertTrue(1 <= em["spec_k_effective"] <= eng.spec_k)
        # the policy actually saw the served windows
        self.assertIsNotNone(eng._spec_policy.acceptance_ewma)

    def test_off_engine_reports_static_depth(self):
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        eng = _engine(cfg, params, speculative="ngram", spec_k=3)
        self.assertFalse(eng.spec_adaptive)
        self.assertIsNone(eng._spec_policy)
        self.assertEqual(eng.metrics()["spec_k_effective"], 3)


class TestWatchdogSpec(unittest.TestCase):
    def tearDown(self):
        from paddle_tpu.resilience import chaos
        chaos.uninstall()

    def test_hang_mid_verify_retires_victim_keeps_survivors(self):
        """chaos hang:decode lands on the speculative verify dispatch
        (the same pre-lock seam as the decode chunk): the watchdog
        retires ONE victim, survivors finish, the pool drains whole."""
        from paddle_tpu.resilience import chaos

        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        rng = np.random.default_rng(3)
        eng = _engine(cfg, params, max_new_tokens=4,
                      speculative="ngram", spec_k=4)
        reqs = [eng.add_request(rng.integers(
            1, cfg.vocab_size, (5,)).tolist(), max_new=4)
            for _ in range(3)]
        eng.warm(buckets=[8])
        chaos.install("hang:decode:20")
        eng.run(watchdog_timeout=2.0)
        self.assertEqual(len(eng.finished), 3)
        failed = [r for r in eng.finished if r.failed]
        self.assertEqual(len(failed), 1)
        self.assertEqual(eng.hung_retired, 1)
        for r in eng.finished:
            if not r.failed:
                self.assertEqual(len(r.tokens), 4)
        self.assertEqual(eng.mgr.n_available, eng.mgr.max_pages - 1)

    def test_hang_mid_verify_requeue_token_identical(self):
        """requeue_hung: the victim restarts from its prompt and the
        FINAL output of every request matches an undisturbed spec-off
        engine — a hang mid-verify never corrupts committed state."""
        from paddle_tpu.resilience import chaos

        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, cfg.vocab_size, (5,)).tolist()
                   for _ in range(3)]
        ref = _engine(cfg, params, max_new_tokens=4)
        oracle = {tuple(p): ref.add_request(p, max_new=4)
                  for p in prompts}
        ref.run(max_iters=200)

        eng = _engine(cfg, params, max_new_tokens=4,
                      speculative="ngram", spec_k=4)
        reqs = [eng.add_request(p, max_new=4) for p in prompts]
        eng.warm(buckets=[8])
        chaos.install("hang:decode:20")
        eng.run(watchdog_timeout=2.0, requeue_hung=True)
        self.assertFalse(any(r.failed for r in eng.finished))
        self.assertEqual(eng.hung_requeued, 1)
        for p, r in zip(prompts, reqs):
            self.assertEqual(r.tokens, oracle[tuple(p)].tokens)
        self.assertEqual(eng.mgr.n_available, eng.mgr.max_pages - 1)


class TestTunerKnobs(unittest.TestCase):
    def test_knob_space_and_canonicalisation(self):
        from paddle_tpu.analysis import tuner
        self.assertIn("speculative", tuner.KNOBS)
        self.assertIn("spec_k", tuner.KNOBS)
        kw = dict(slots=2, prompt_bucket=8, block_size=8)
        space = tuner.default_space(LlamaConfig.tiny(), kw)
        self.assertIn("ngram", space["speculative"])
        self.assertNotIn("draft", space["speculative"])
        # off collapses spec_k; cp>1 collapses speculation entirely
        geo = tuner._engine_geometry(dict(kw))
        base = tuner.baseline_config(LlamaConfig.tiny(), kw)
        c = tuner.canonical_config(
            dict(base, speculative="off", spec_k=8), geo)
        self.assertEqual(c["spec_k"], 0)
        c = tuner.canonical_config(
            dict(base, serving_cp=2, speculative="ngram", spec_k=4),
            geo)
        self.assertEqual(c["speculative"], "off")
        self.assertEqual(c["spec_k"], 0)

    def test_tuned_config_spec_kwargs_build(self):
        """A tuned artifact carrying spec_k=0 + speculative='off' must
        build (the engine skips the >=1 validation when off)."""
        cfg, _, params = _tiny_setup(dtype=jnp.bfloat16)
        eng = _engine(cfg, params, speculative="off", spec_k=0)
        self.assertEqual(eng.spec_k, 0)
        self.assertIsNone(eng._verify)


class TestSmokeSubprocess(unittest.TestCase):
    def test_module_smoke_gate(self):
        """`python -m paddle_tpu.serving.speculative` (the CI smoke
        gate): rc 0 and a JSON row with total token match + a nonzero
        acceptance rate, on CPU."""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.serving.speculative",
             "--requests", "4", "--max-new", "16"],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(
                __file__))))
        self.assertEqual(proc.returncode, 0, proc.stderr[-2000:])
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        self.assertEqual(row["bench"], "speculative_smoke")
        self.assertEqual(row["token_match"], 1.0)
        self.assertGreater(row["acceptance_rate"], 0.0)
        self.assertTrue(row["ok"])


if __name__ == "__main__":
    unittest.main()
