"""Auditor-driven static autotuner + persistent compile cache
(ISSUE 16): deterministic ranking over the engine config space, the
two-stage HBM feasibility gate, the TunedConfig artifact round-trip /
staleness contract, engine `config=` application, and the
zero-recompile / zero-cache-miss warm gates."""
import dataclasses
import functools
import json
import os
import subprocess
import sys
import tempfile
import unittest
import warnings
from unittest import mock

import pytest

import paddle_tpu as paddle
import paddle_tpu.analysis as analysis
from paddle_tpu.analysis import tuner
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import ContinuousBatchingEngine

# the demo geometry (analysis/__main__.py --tune uses the same shape):
# block_size 8 leaves a LARGER candidate class (16) above the baseline,
# split decode keeps the baseline's traced peak under a budget sitting
# just below that class's static bound — so one run exercises both
# prune stages AND keeps the all-defaults baseline rankable
_KW = dict(slots=2, prompt_bucket=16, max_prompt_len=32,
           max_new_tokens=8, block_size=8, steps_per_sync=4,
           unified_step=False)


def _tiny_setup(seed=21):
    cfg = LlamaConfig.tiny()
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    return cfg, dict(model.raw_state())


@functools.lru_cache(maxsize=None)
def _demo_runs():
    """ONE pair of identical autotune runs shared by every ranking
    test (each run builds + traces ~10 engines; don't repeat that per
    test)."""
    cfg, params = _tiny_setup()
    space = tuner.default_space(cfg, _KW)
    # conftest forces 8 host devices, which would add serving_mp=2
    # and serving_cp=2/4/8 to the space and multiply the engine-build
    # work; mesh behavior has its own suites (test_serving_mp,
    # test_serving_cp) — pin both sweeps to 1 here
    space["serving_mp"] = [1]
    space["serving_cp"] = [1]
    # same rationale for the ISSUE 19 sweep: speculative=ngram triples
    # the candidate count (off + k=4/8) and builds a verify program
    # per candidate; speculation has its own suite (test_speculative)
    space["speculative"] = ["off"]
    space["spec_k"] = [0]
    geo = tuner._engine_geometry(dict(_KW))
    budget = max(tuner.static_candidate_bound(cfg, params, c, _KW)
                 for c in tuner.enumerate_candidates(space, geo)) - 1
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        r1 = analysis.autotune(cfg, params, engine_kwargs=dict(_KW),
                               hbm_budget_bytes=budget,
                               space=space)
        r2 = analysis.autotune(cfg, params, engine_kwargs=dict(_KW),
                               hbm_budget_bytes=budget,
                               space=space)
    return cfg, params, r1, r2


class TestAutotuneRanking(unittest.TestCase):
    def test_deterministic_across_runs(self):
        """Two autotune runs over the same inputs must emit
        byte-identical reports — ranking order included (the
        tie-break must not depend on dict order or trace timing)."""
        _, _, r1, r2 = _demo_runs()
        self.assertEqual(r1.to_dict(top_k=0), r2.to_dict(top_k=0))
        self.assertEqual(r1.to_json(), r2.to_json())

    def test_feasibility_gate_prunes_both_stages(self):
        """Over-budget candidates are pruned, never ranked: the
        largest block-size class on static params+pool bounds BEFORE
        any engine is built, the unified candidates on traced liveness
        peaks — and the all-defaults baseline survives."""
        _, _, rep, _ = _demo_runs()
        d = rep.to_dict(top_k=0)
        self.assertGreater(d["n_pruned"], 0)
        self.assertGreater(d["n_feasible"], 0)
        static_pruned = [p for p in d["pruned"]
                        if "before tracing" in p["pruned_reason"]]
        traced_pruned = [p for p in d["pruned"]
                        if "traced per-chip peak" in p["pruned_reason"]]
        self.assertTrue(static_pruned, "no stage-A (pre-trace) prunes")
        self.assertTrue(traced_pruned, "no stage-B (traced) prunes")
        # every statically pruned candidate provably exceeds the budget
        for p in static_pruned:
            self.assertGreater(p["static_bound_bytes"],
                               d["hbm_budget_bytes"])
        # pruned configs never appear in the ranking
        ranked = {tuner._config_key(r["config"]) for r in d["ranking"]}
        for p in d["pruned"]:
            self.assertNotIn(tuner._config_key(p["config"]), ranked)
        # the baseline is feasible and the winner at least matches it
        self.assertTrue(d["baseline"]["feasible"])
        self.assertLessEqual(d["best"]["predicted_step_ms"],
                             d["baseline"]["predicted_step_ms"])
        self.assertGreaterEqual(d["predicted_speedup_vs_default"], 1.0)

    def test_winner_for_the_served_step(self):
        """What the search decides on the demo space: the all-defaults
        bf16 baseline wins at a predicted speedup of exactly 1.0, and
        every other candidate that fits holds int8 pools and is
        predicted marginally slower — for the step that is served the
        roofline prices the dequant above the halved pool read at this
        size (a prediction no chip reading has calibrated: ROADMAP
        D6)."""
        _, _, rep, _ = _demo_runs()
        best = rep.best
        self.assertIs(best, rep.ranking[0])
        self.assertEqual(best.config, rep.baseline.config)
        self.assertEqual(best.config["kv_cache_dtype"], "bf16")
        self.assertEqual(rep.tuned_config().knobs, best.config)
        self.assertEqual(
            rep.to_dict(top_k=0)["predicted_speedup_vs_default"], 1.0)
        others = rep.ranking[1:]
        self.assertTrue(others, "nothing ranked beside the baseline")
        for r in others:
            self.assertEqual(r.config["kv_cache_dtype"], "int8")
            self.assertLess(best.predicted_step_ms, r.predicted_step_ms)
            self.assertLess(r.predicted_step_ms,
                            best.predicted_step_ms * 1.01)

    def test_int8_kv_monotonic_vs_bf16(self):
        """For every candidate pair differing ONLY in kv_cache_dtype,
        int8 must bound no more HBM than bf16 (smaller pool, same
        activations) — the auditors' objective must price the
        quantized pool as a strict memory win. The TIME claim is
        softer: the pool read halves but the dequant adds FLOPs, so
        predicted step may move either way by the dequant term —
        assert the int8 twin is never more than marginally slower at
        mp=1 (where the pool is unsharded, so the bandwidth win is
        biggest), and that the search REWARDS int8 somewhere: a twin
        that fits the budget where its bf16 counterpart is pruned
        (otherwise the knob could never win a search)."""
        _, _, rep, _ = _demo_runs()
        results = list(rep.ranking) + list(rep.pruned)
        by_key = {tuner._config_key(r.config): r for r in results}
        pairs = 0
        int8_fits_alone = False
        for r in results:
            if r.config["kv_cache_dtype"] != "int8":
                continue
            twin_cfg = dict(r.config, kv_cache_dtype="bf16")
            twin = by_key.get(tuner._config_key(twin_cfg))
            if twin is None:
                continue
            pairs += 1
            self.assertLessEqual(r.static_bound_bytes,
                                 twin.static_bound_bytes)
            if r.feasible and not twin.feasible:
                int8_fits_alone = True
            if not (r.feasible and twin.feasible):
                continue
            self.assertLessEqual(r.peak_hbm_bytes, twin.peak_hbm_bytes)
            if r.config["serving_mp"] == 1:
                self.assertLessEqual(
                    r.predicted_step_ms,
                    twin.predicted_step_ms * 1.02,
                    f"int8 twin of {twin.config} predicted more than "
                    "marginally slower than its bf16 counterpart")
        self.assertGreater(pairs, 0, "no int8/bf16 twins in the space")
        self.assertTrue(int8_fits_alone,
                        "no twin where int8 fits and bf16 is pruned — "
                        "the search never rewards the knob")

    def test_token_budget_collapses_on_the_split_path(self):
        """Without the unified step no window program is built, so two
        token budgets name one program and the enumeration scores it
        once; with it they stay apart."""
        cfg = LlamaConfig.tiny()
        geo = tuner._engine_geometry(dict(_KW))
        base = tuner.baseline_config(cfg, _KW)
        for budget in (16, 32):
            c = tuner.canonical_config(
                dict(base, unified_step=False, token_budget=budget), geo)
            self.assertEqual(c["token_budget"], geo["prompt_bucket"])
            c = tuner.canonical_config(
                dict(base, unified_step=True, token_budget=budget), geo)
            self.assertEqual(c["token_budget"], budget)
        space = dict(tuner.default_space(cfg, _KW), serving_mp=[1],
                     serving_cp=[1], speculative=["off"], spec_k=[0],
                     block_size=[8], kv_cache_dtype=["bf16"],
                     quantized_collectives=[False])
        self.assertEqual(len(space["token_budget"]), 2)
        keys = [tuner._config_key(c)
                for c in tuner.enumerate_candidates(space, geo)]
        self.assertEqual(len(keys), len(set(keys)))
        self.assertEqual(len(keys), 3)   # split once, unified at 16 / 32

    def test_budget_candidates_keeps_baseline(self):
        """A budget_candidates prefix cap must still score the
        all-defaults baseline (the speedup denominator rides along
        even when it is outside the prefix)."""
        cfg, params, _, _ = _demo_runs()
        rep = analysis.autotune(cfg, params, engine_kwargs=dict(_KW),
                                budget_candidates=2)
        d = rep.to_dict()
        self.assertLessEqual(d["n_candidates"], 3)  # 2 + baseline
        self.assertIsNotNone(d["baseline"])


class TestServingCPKnob(unittest.TestCase):
    """ISSUE 18: serving_cp joins the config space — divisibility-
    filtered against a pinned pool, per-chip stage-A bound, and
    unbuildable cp*mp meshes pruned by name (never an engine crash)."""

    def test_space_filters_and_static_bound_shrinks(self):
        cfg, params = _tiny_setup()
        space = tuner.default_space(cfg, _KW)
        self.assertIn("serving_cp", space)
        self.assertIn(2, space["serving_cp"])  # conftest: 8 devices
        # a pinned max_pages filters degrees that don't divide it
        s2 = tuner.default_space(cfg, dict(_KW, max_pages=6))
        self.assertEqual(s2["serving_cp"], [1, 2])
        # stage-A bound carries fleet/cp LOCAL pages: the pool term
        # must strictly shrink as cp grows (params are replicated)
        base = tuner.baseline_config(cfg, _KW)
        bounds = [tuner.static_candidate_bound(
            cfg, params, dict(base, serving_cp=c), _KW)
            for c in (1, 2, 4)]
        self.assertGreater(bounds[0], bounds[1])
        self.assertGreater(bounds[1], bounds[2])
        # a per-chip kv_pool_bytes budget is cp-invariant by contract
        # (pages_for_bytes buys budget*cp fleet pages)
        kwb = dict(_KW, kv_pool_bytes=1 << 20)
        self.assertEqual(
            tuner.static_candidate_bound(
                cfg, params, dict(base, serving_cp=1), kwb),
            tuner.static_candidate_bound(
                cfg, params, dict(base, serving_cp=4), kwb))

    def test_qcoll_survives_collapse_under_cp(self):
        """quantized_collectives only collapses when BOTH mesh axes
        are 1 — the cp merge ships quantized acc partials at mp=1."""
        geo = tuner._engine_geometry(dict(_KW))
        base = tuner.baseline_config(cfg=LlamaConfig.tiny(),
                                     engine_kwargs=_KW)
        c = tuner.canonical_config(
            dict(base, serving_cp=2, quantized_collectives=True), geo)
        self.assertTrue(c["quantized_collectives"])
        c = tuner.canonical_config(
            dict(base, serving_cp=1, serving_mp=1,
                 quantized_collectives=True), geo)
        self.assertFalse(c["quantized_collectives"])

    def test_unbuildable_mesh_pruned_by_name(self):
        """cp*mp products past the host's device count are pruned
        with a named reason, distinct from both HBM prune stages."""
        cfg, params = _tiny_setup()
        base = tuner.baseline_config(cfg, _KW)
        space = {k: [v] for k, v in base.items()}
        space["serving_cp"] = [8]
        space["serving_mp"] = [2]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            # budget of 1 B statically prunes every buildable
            # candidate, so the test never builds an engine
            rep = analysis.autotune(cfg, params,
                                    engine_kwargs=dict(_KW),
                                    hbm_budget_bytes=1, space=space)
        reasons = [p.pruned_reason for p in rep.pruned]
        self.assertTrue(any(
            "serving_cp*serving_mp = 16" in r and "host has" in r
            for r in reasons), reasons)
        self.assertFalse(rep.ranking)


class TestTunedConfigArtifact(unittest.TestCase):
    def test_round_trip_and_staleness(self):
        """save/load preserves the artifact exactly; the staleness
        contract invalidates on schema version, model shape, device
        row, and searched-space hash — each independently."""
        cfg, _, rep, _ = _demo_runs()
        tc = rep.tuned_config()
        with tempfile.TemporaryDirectory() as d:
            path = tc.save(d)  # a directory gets the canonical name
            self.assertEqual(os.path.basename(path),
                             tuner.TUNE_FILENAME)
            back = analysis.TunedConfig.load(d)
        self.assertEqual(back.to_dict(), tc.to_dict())
        self.assertIsNone(back.stale_reason(
            cfg=cfg, device=rep.device, space=rep.space))
        # model-shape mismatch
        grown = dataclasses.replace(cfg, hidden_size=128)
        self.assertIn("model signature", back.stale_reason(cfg=grown))
        # device-row mismatch
        other = "tpu-v4" if rep.device != "tpu-v4" else "tpu-v5p"
        self.assertIn("device row", back.stale_reason(device=other))
        # flag-space mismatch
        space2 = dict(rep.space, kv_cache_dtype=["bf16"])
        self.assertIn("hash", back.stale_reason(space=space2))
        # schema mismatch always checked, even with no arguments
        d2 = dict(back.to_dict(), schema_version=0)
        self.assertIn("schema_version",
                      analysis.TunedConfig.from_dict(d2).stale_reason())

    def test_apply_explicit_caller_wins(self):
        tc = analysis.TunedConfig(
            knobs={"kv_cache_dtype": "int8", "block_size": 16},
            device="tpu-v5e", model="m", space_hash="x")
        merged = tc.apply({"kv_cache_dtype": "bf16", "block_size": None})
        self.assertEqual(merged["kv_cache_dtype"], "bf16")  # pinned
        self.assertEqual(merged["block_size"], 16)          # filled


class TestEngineTunedConfig(unittest.TestCase):
    def _geometry(self):
        return {k: v for k, v in _KW.items() if k not in tuner.KNOBS}

    @staticmethod
    def _int8_artifact(rep):
        """The demo's winner with one knob off the engine's default, so
        that applying the artifact shows."""
        tc = rep.tuned_config()
        return dataclasses.replace(
            tc, knobs=dict(tc.knobs, kv_cache_dtype="int8"))

    def test_engine_applies_artifact_and_stays_compiled(self):
        """An engine built from the persisted artifact resolves every
        tuned knob, reports it through metrics(), and — the steady-
        state guard — serves traffic after warm() without one new
        compile."""
        cfg, params, rep, _ = _demo_runs()
        tc = self._int8_artifact(rep)
        with tempfile.TemporaryDirectory() as d:
            path = tc.save(d)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                eng = ContinuousBatchingEngine(
                    cfg, dict(params), config=path, **self._geometry())
        for knob, val in tc.knobs.items():
            if knob == "kv_cache_dtype":
                self.assertEqual(eng.kv_dtype, val)
            elif knob == "unified_step":
                self.assertEqual(eng.unified, val)
            elif knob == "token_budget":
                self.assertEqual(eng.token_budget, val)
            elif knob == "block_size":
                self.assertEqual(eng.block_size, val)
        m = eng.metrics()
        self.assertEqual(m["tuned_config"], tc.to_dict())
        self.assertIsNone(m["warm_compile_stats"])  # not warmed yet
        # warm every prompt bucket the requests below can land in
        # (warm()'s default is the max bucket only)
        eng.warm(buckets=(16, 32))
        before = eng.compile_stats()
        self.assertNotIn(-1, before.values())
        for n in (3, 9, 14):
            eng.add_request(list(range(1, n + 1)), max_new=3)
        eng.run(max_iters=120)
        self.assertEqual(len(eng.finished), 3)
        self.assertEqual(eng.compile_stats(), before)
        self.assertIsNotNone(eng.metrics()["warm_compile_stats"])

    def test_engine_explicit_kwarg_beats_artifact(self):
        cfg, params, rep, _ = _demo_runs()
        tc = self._int8_artifact(rep)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            eng = ContinuousBatchingEngine(
                cfg, dict(params), config=tc, kv_cache_dtype="bf16",
                **self._geometry())
        self.assertEqual(eng.kv_dtype, "bf16")

    def test_engine_rejects_stale_explicit_artifact(self):
        """config= (explicit) with a stale artifact must raise; the
        FLAGS_tuned_config path only warns and falls back to defaults
        (a fleet-wide env var must not brick other models' engines)."""
        cfg, params, rep, _ = _demo_runs()
        stale = analysis.TunedConfig.from_dict(
            dict(rep.tuned_config().to_dict(), model="llama:other"))
        with self.assertRaisesRegex(ValueError, "stale TunedConfig"):
            ContinuousBatchingEngine(cfg, dict(params), config=stale,
                                     **self._geometry())
        with tempfile.TemporaryDirectory() as d:
            stale.save(d)
            paddle.set_flags({"tuned_config": d})
            try:
                with warnings.catch_warnings(record=True) as caught:
                    warnings.simplefilter("always")
                    eng = ContinuousBatchingEngine(
                        cfg, dict(params), **_KW)
            finally:
                paddle.set_flags({"tuned_config": ""})
        self.assertTrue(any("stale" in str(w.message) for w in caught))
        self.assertIsNone(eng.tuned_config)
        self.assertEqual(eng.kv_dtype, "bf16")  # registry default

    def test_config_false_forces_off(self):
        cfg, params, _, _ = _demo_runs()
        with tempfile.TemporaryDirectory() as d:
            _demo_runs()[2].tuned_config().save(d)
            paddle.set_flags({"tuned_config": d})
            try:
                eng = ContinuousBatchingEngine(
                    cfg, dict(params), config=False, **_KW)
            finally:
                paddle.set_flags({"tuned_config": ""})
        self.assertIsNone(eng.tuned_config)


class TestPersistentCompileCache(unittest.TestCase):
    def test_second_warm_has_zero_cache_misses(self):
        """The fleet-restart gate: a second engine warmed off the same
        populated cache directory must report cache_misses == 0 in
        warm_compile_stats — every program served from disk, no
        compile storm."""
        import jax

        from paddle_tpu.serving import compile_cache as cc

        cfg, params = _tiny_setup()
        tmp = tempfile.mkdtemp()
        self.addCleanup(
            lambda: jax.config.update("jax_compilation_cache_dir",
                                      None))
        # a cache placed from outside wins over any directory named in
        # code; this test names its own, so it runs without one
        env = mock.patch.dict(os.environ)
        env.start()
        self.addCleanup(env.stop)
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        self.assertEqual(cc.enable_compile_cache(tmp), tmp)
        self.assertEqual(cc.cache_dir(), tmp)
        kw = dict(_KW, kv_cache_dtype="int8")
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            e1 = ContinuousBatchingEngine(cfg, dict(params), **kw)
            e1.warm()
            cold = e1.warm_compile_stats
            e2 = ContinuousBatchingEngine(cfg, dict(params), **kw)
            e2.warm()
            hot = e2.warm_compile_stats
        if not cold["counters_available"]:
            self.skipTest("jax monitoring counters unavailable")
        self.assertEqual(cold["persistent_cache_dir"], tmp)
        self.assertGreater(cold["cache_misses"], 0)   # cold compiles
        self.assertGreater(hot["compile_requests"], 0)
        self.assertEqual(hot["cache_misses"], 0, hot)
        self.assertEqual(hot["cache_hits"], hot["compile_requests"])


class TestCompileCachePlacement(unittest.TestCase):
    """Where the persistent cache lives is ONE decision
    (compile_cache.enable_compile_cache, PR 22): placed from outside by
    JAX_COMPILATION_CACHE_DIR, else a fixed path in the checkout."""

    def _decide(self, env_dir, *args):
        """Run the decision with the module's memory cleared and every
        jax-side effect recorded instead of applied (the suite's own
        cache must stay where conftest put it)."""
        import jax

        from paddle_tpu.serving import compile_cache as cc

        updates = {}
        env = dict(os.environ)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        if env_dir:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        with mock.patch.dict(os.environ, env, clear=True), \
                mock.patch.object(cc, "_CACHE_DIR", None), \
                mock.patch.object(jax.config, "update",
                                  lambda k, v: updates.__setitem__(k, v)), \
                mock.patch("jax._src.compilation_cache.reset_cache",
                           lambda: None), \
                mock.patch("os.makedirs", lambda *a, **k: None):
            return cc.enable_compile_cache(*args), updates

    def test_env_placement_wins_and_no_code_sets_a_directory(self):
        for args in ((), ("/somewhere/else",)):
            got, updates = self._decide("/placed/from/outside", *args)
            self.assertEqual(got, "/placed/from/outside")
            self.assertNotIn("jax_compilation_cache_dir", updates)
            # the floors are still zeroed: every engine program persists
            self.assertEqual(
                updates["jax_persistent_cache_min_compile_time_secs"], 0.0)

    def test_unset_means_the_fixed_in_checkout_path(self):
        from paddle_tpu.serving import compile_cache as cc

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        self.assertEqual(cc.DEFAULT_CACHE_DIR,
                         os.path.join(repo, ".jax_cache"))
        for _ in range(2):      # the same on every run
            got, updates = self._decide(None)
            self.assertEqual(got, cc.DEFAULT_CACHE_DIR)
            self.assertEqual(updates["jax_compilation_cache_dir"],
                             cc.DEFAULT_CACHE_DIR)

    def test_repo_flag_places_it_only_when_env_is_unset(self):
        paddle.set_flags({"compile_cache": "/from/the/flag"})
        try:
            self.assertEqual(self._decide(None)[0], "/from/the/flag")
            self.assertEqual(self._decide("/placed")[0], "/placed")
        finally:
            paddle.set_flags({"compile_cache": ""})


class TestCLITune(unittest.TestCase):
    def _run(self, *extra):
        # pin the demo to ONE host device: conftest's 8-device
        # XLA_FLAGS would double the searched space (serving_mp=2
        # joins) and with it the subprocess runtime, without adding
        # coverage here
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=1")
        return subprocess.run(
            [sys.executable, "-m", "paddle_tpu.analysis", "--tune",
             *extra],
            capture_output=True, text=True, env=env,
            cwd=os.path.dirname(os.path.dirname(__file__)), timeout=520)

    def test_cli_tune_json_schema(self):
        """Tier-1 CI gate (ISSUE 16 satellite): `--tune --format json`
        exits 0 and emits the documented TuningReport schema with a
        feasible baseline, provable prunes at both stages, and a
        winner no slower than the defaults."""
        proc = self._run("--format", "json")
        self.assertEqual(proc.returncode, 0, proc.stderr[-2000:])
        d = json.loads(proc.stdout)
        self.assertEqual(sorted(d),
                         ["counts", "diagnostics", "target", "tuning"])
        t = d["tuning"]
        for key in ("device", "model", "space", "space_hash",
                    "hbm_budget_bytes", "n_candidates", "n_feasible",
                    "n_pruned", "ranking", "pruned", "baseline",
                    "best", "predicted_speedup_vs_default",
                    "engine_geometry"):
            self.assertIn(key, t)
        self.assertGreater(t["n_pruned"], 0)
        self.assertTrue(any("before tracing" in p["pruned_reason"]
                            for p in t["pruned"]))
        self.assertTrue(t["baseline"]["feasible"])
        self.assertLessEqual(t["best"]["predicted_step_ms"],
                             t["baseline"]["predicted_step_ms"])
        self.assertGreaterEqual(t["predicted_speedup_vs_default"], 1.0)
        self.assertEqual(d["counts"]["error"], 0)

    @pytest.mark.slow  # tier-1 keeps the rc-0 schema gate above; the
    # rc-1 leg re-runs the whole tune in a second subprocess
    def test_cli_tune_fail_on_warning_exits_1(self):
        """The tiny decode program lints with TPU10x/TPU201 warnings,
        so --fail-on warning must gate rc 1 on the WINNER's program."""
        proc = self._run("--budget-candidates", "2", "--fail-on",
                         "warning")
        self.assertEqual(proc.returncode, 1, proc.stderr[-2000:])


if __name__ == "__main__":
    unittest.main()
