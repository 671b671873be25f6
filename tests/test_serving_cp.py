"""Context-parallel paged serving (FLAGS_serving_cp, ISSUE 18) on an
8-device CPU mesh: PAGE-sharded pools must be TOKEN-IDENTICAL to the
single-chip engine on bf16 pools (each chip streams only its LOCAL
pages and emits online-softmax partials; the cross-chip merge runs the
kernels' own rescale recurrence, so the math is associative up to the
float rounding the bf16 output cast absorbs), per-chip pool bytes must
drop to 1/cp of the fleet at equal fleet page capacity, the
zero-recompile-after-warm guard must hold with `cp` in every program
key, non-divisible fleet page counts must raise the NAMED
PageShardingError, and the comms auditor must price the partial merge
(stats + weighted acc — never the KV) at < 5% of the per-step KV bytes
page-sharding avoids moving — the acceptance bar's pre-silicon proof.
Heavy engine pairs (2-D cp x mp mesh, int8 x cp, disaggregated) are
@slow; the bf16 cp=2 churn identity, the budget wall, the merge audit,
and the recompile guard stay in tier-1."""
import dataclasses
import unittest

import numpy as np
import pytest

import jax
import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.llama import (PagedKVManager, PageShardingError,
                                     ServingTP, make_serving_tp,
                                     resolve_serving_cp)
from paddle_tpu.parallel.mesh import serving_mesh
from paddle_tpu.serving import ContinuousBatchingEngine


def _tiny_setup(nkv=2, seed=21, **cfg_over):
    cfg = dataclasses.replace(LlamaConfig.tiny(),
                              num_key_value_heads=nkv, **cfg_over)
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    import jax.numpy as jnp

    params = {k: (v.astype(jnp.bfloat16) if v.dtype == jnp.float32
                  else v)
              for k, v in dict(model.raw_state()).items()}
    return cfg, model, params


def _engine(cfg, params, cp=1, mp=1, kv="bf16", **over):
    kw = dict(slots=2, prompt_bucket=8, max_prompt_len=16,
              max_new_tokens=6, block_size=8, steps_per_sync=3,
              serving_cp=cp, serving_mp=mp, kv_cache_dtype=kv)
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


class TestServingCPGeometry(unittest.TestCase):
    """Pure host math — no device programs compile here."""

    def test_cp1_mp1_is_no_tp(self):
        cfg, _, _ = _tiny_setup()
        self.assertIsNone(make_serving_tp(cfg, 1, serving_cp=1))

    def test_cp_only_tp_has_no_head_seam(self):
        cfg, _, _ = _tiny_setup()
        tp = make_serving_tp(cfg, 1, serving_cp=2)
        self.assertIsNotNone(tp)
        self.assertEqual((tp.mp, tp.cp), (1, 2))
        # full head counts: cp never splits heads
        self.assertEqual((tp.nh_local, tp.nkv_local), (4, 2))

    def test_resolve_rejects_sub_one(self):
        with self.assertRaises(ValueError):
            resolve_serving_cp(0)
        self.assertEqual(resolve_serving_cp(None), 1)  # flag default
        self.assertEqual(resolve_serving_cp(4), 4)

    def test_serving_mesh_2d(self):
        m = serving_mesh(2, cp=2)
        self.assertEqual(dict(m.shape), {"cp": 2, "mp": 2})
        m1 = serving_mesh(1, cp=4)     # size-1 mp axis is KEPT
        self.assertEqual(dict(m1.shape), {"cp": 4, "mp": 1})
        self.assertIsNone(serving_mesh(1, cp=1))
        with self.assertRaisesRegex(ValueError, "devices"):
            serving_mesh(4, cp=4)      # 16 > the 8-device CPU mesh

    def test_pages_for_bytes_buys_fleet_pages(self):
        """A PER-CHIP byte budget buys cp x the FLEET page count: each
        chip holds 1/cp of the pages at full per-page cost (cp splits
        pages, not page bytes — the dual of mp's geometry)."""
        kw = dict(n_layers=2, num_kv_heads=2, head_dim=16)
        pb = PagedKVManager.page_bytes(8, **kw)
        budget = 64 * pb
        base = PagedKVManager.pages_for_bytes(budget, 8, **kw)
        self.assertEqual(
            PagedKVManager.pages_for_bytes(budget, 8, cp=2, **kw),
            2 * base)
        mgr = PagedKVManager(64, 8)
        mgr.set_pool_geometry(kv_cache_dtype="bf16", cp=2, **kw)
        self.assertEqual(mgr.kv_pool_bytes(), 32 * pb)    # per chip
        self.assertEqual(mgr.kv_pool_bytes(aggregate=True), 64 * pb)

    def test_non_divisible_pages_raise_named_error(self):
        kw = dict(n_layers=2, num_kv_heads=2, head_dim=16)
        mgr = PagedKVManager(7, 8)
        with self.assertRaisesRegex(PageShardingError, "divisible"):
            mgr.set_pool_geometry(kv_cache_dtype="bf16", cp=2, **kw)
        self.assertTrue(issubclass(PageShardingError, ValueError))

    def test_engine_rounds_default_pool_to_cp_multiple(self):
        cfg, _, params = _tiny_setup()
        eng = _engine(cfg, params, cp=4)
        self.assertEqual(eng.mgr.max_pages % 4, 0)
        self.assertEqual(eng.cp, 4)
        self.assertEqual(eng.metrics()["serving_cp"], 4)


class TestCPBudgetWall(unittest.TestCase):
    def test_halved_budget_walls_cp1_and_serves_cp2(self):
        """ACCEPTANCE (bench_longcontext serving-cp leg in miniature):
        at a per-chip byte budget holding HALF of one request's pages,
        the cp=1 build fails its capacity floor — the per-chip pool
        provably cannot hold the context — while cp=2 serves the same
        depth from identical per-chip bytes, because page-sharding
        makes the FLEET pool the ceiling."""
        cfg, _, params = _tiny_setup()
        cap = -(-(16 + 6) // 8)                    # one request's pages
        pb = PagedKVManager.page_bytes(
            8, n_layers=cfg.num_hidden_layers,
            num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim)
        budget = ((cap + 3) // 2) * pb
        with self.assertRaisesRegex(ValueError, "holds only"):
            _engine(cfg, params, cp=1, kv_pool_bytes=budget)
        eng = _engine(cfg, params, cp=2, kv_pool_bytes=budget)
        rng = np.random.default_rng(3)
        prompt = rng.integers(1, cfg.vocab_size, (15,)).tolist()
        r = eng.add_request(prompt, max_new=4)
        eng.run(max_iters=100)
        self.assertEqual(len(r.tokens), 4)
        self.assertLessEqual(eng.mgr.kv_pool_bytes(), budget)


class TestCPTokenIdentity(unittest.TestCase):
    def test_cp2_identity_bf16_churn(self):
        """ACCEPTANCE: the cp=2 page-sharded engine serves tokens
        identical to the single-chip engine on bf16 pools through
        prefix-cache churn (hits + recycling) — the merge recurrence
        is the kernels' own, and bf16's rounding grid absorbs the
        f32 association difference on every sampled logit."""
        cfg, _, params = _tiny_setup()
        rng = np.random.default_rng(7)
        prompts = _churn_prompts(cfg, rng)
        t1 = _serve(_engine(cfg, params, cp=1), prompts)
        eng = _engine(cfg, params, cp=2)
        t2 = _serve(eng, prompts)
        self.assertEqual(t1, t2)
        self.assertGreater(eng.prefix_hit_tokens, 0)
        # fleet pages match up to the cp-divisibility rounding, and
        # per-chip bytes are exactly half the (rounded) fleet's
        ref = _engine(cfg, params, cp=1)
        self.assertEqual(eng.mgr.max_pages,
                         -(-ref.mgr.max_pages // 2) * 2)
        self.assertEqual(2 * eng.mgr.kv_pool_bytes(),
                         eng.mgr.kv_pool_bytes(aggregate=True))
        # drain: nothing leaked through the cp scatter's drop mode
        self.assertEqual(eng.mgr.n_available, eng.mgr.max_pages - 1)

    @pytest.mark.slow
    def test_cp2_mp2_2d_mesh_identity(self):
        """The composed 2-D serving mesh: pages shard over cp AND kv
        heads shard over mp, with both seams (partial merge, o-proj
        gather) live in one program."""
        cfg, _, params = _tiny_setup()
        rng = np.random.default_rng(7)
        prompts = _churn_prompts(cfg, rng)
        t1 = _serve(_engine(cfg, params, cp=1), prompts)
        t22 = _serve(_engine(cfg, params, cp=2, mp=2), prompts)
        self.assertEqual(t1, t22)

    @pytest.mark.slow
    def test_cp2_int8_pool_identity(self):
        """int8-KV x cp composition: the f32 scale sidecars shard by
        PAGE with their pools, quantize-on-scatter targets only local
        rows (mode='drop' translation), and dequant-in-partial matches
        the single-chip int8 engine token for token."""
        cfg, _, params = _tiny_setup()
        rng = np.random.default_rng(11)
        prompts = _churn_prompts(cfg, rng)
        t1 = _serve(_engine(cfg, params, cp=1, kv="int8"), prompts)
        t2 = _serve(_engine(cfg, params, cp=2, kv="int8"), prompts)
        self.assertEqual(t1, t2)

    @pytest.mark.slow
    def test_cp2_disaggregated_identity(self):
        """The prefill->decode handoff under page sharding: prefix
        pages committed by the prefill worker are owned by the same cp
        shards when the decode worker maps them."""
        cfg, _, params = _tiny_setup()
        rng = np.random.default_rng(7)
        prompts = _churn_prompts(cfg, rng)
        t1 = _serve(_engine(cfg, params, cp=1), prompts)
        eng = _engine(cfg, params, cp=2, disaggregated=True)
        t2 = _serve(eng, prompts)
        self.assertEqual(t1, t2)
        self.assertEqual(eng.prefill_handoffs, len(prompts))


class TestCompileGuardCP(unittest.TestCase):
    def test_zero_recompiles_after_warm_cp2(self):
        """warm() covers the page-sharded programs: mixed traffic adds
        ZERO compiles, and `cp` rides every prefill program key (third
        from last — kv_dtype:cp:qcoll:mp keeps mp the LAST component,
        the ISSUE 15 key contract)."""
        cfg, _, params = _tiny_setup()
        rng = np.random.default_rng(19)
        eng = _engine(cfg, params, cp=2, prefill_batch=1,
                      prefix_cache=True, unified_step=False)
        eng.warm(buckets=[8, 16])
        before = eng.compile_stats()
        self.assertNotIn(-1, before.values(),
                         "jit cache-size counter unavailable")
        for k in before:
            if k == "decode":
                continue
            parts = k.split(":")
            self.assertEqual(parts[-3], "2", k)   # cp
            self.assertEqual(parts[-1], "1", k)   # mp stays last
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


class TestCPMergeWire(unittest.TestCase):
    """Satellite: the comms auditor is the pre-silicon proof the merge
    is cheap — per-token online-softmax state (m, l, weighted acc)
    crosses the wire, never KV pages."""

    def _long_engine(self, cp, mp=1):
        cfg, _, params = _tiny_setup(max_position_embeddings=256)
        return cfg, _engine(cfg, params, cp=cp, mp=mp,
                            prompt_bucket=16, max_prompt_len=200,
                            max_new_tokens=8, steps_per_sync=4,
                            tracer=False)

    def test_merge_wire_under_5pct_of_kv_moved(self):
        """ACCEPTANCE: audited cp-axis wire bytes per decode step are
        < 5% of the per-step KV bytes page-sharding avoids moving (the
        (cp-1)/cp remote share of every page the chunk's block tables
        can touch) — and the deeper the context, the better the ratio,
        since the merge is per-TOKEN state, independent of depth."""
        cfg, eng = self._long_engine(cp=2)
        rep = eng.audit_comms(programs=("decode",))
        dec = rep["programs"]["decode"]
        merge = sum(b for a, b in dec["per_axis"].items()
                    if "cp" in a.split(","))
        self.assertGreater(merge, 0)
        merge_per_step = merge / eng.steps
        pb = PagedKVManager.page_bytes(
            eng.mgr.block_size, n_layers=cfg.num_hidden_layers,
            num_kv_heads=cfg.num_key_value_heads,
            head_dim=cfg.head_dim)
        kv_per_step = eng.slots * eng.table_width * pb * (1 / 2)
        self.assertLess(merge_per_step, 0.05 * kv_per_step,
                        f"merge {merge_per_step} vs KV {kv_per_step}")

    def test_per_axis_rows_split_cp_from_mp(self):
        """The 2-D mesh audit separates the axes: the partial merge
        prices on 'cp', the o-proj head gather on 'mp' — neither
        hides in a combined row."""
        _, eng = self._long_engine(cp=2, mp=2)
        rep = eng.audit_comms(programs=("decode",))
        axes = rep["programs"]["decode"]["per_axis"]
        self.assertIn("cp", axes)
        self.assertIn("mp", axes)
        self.assertGreater(axes["cp"], 0)
        self.assertGreater(axes["mp"], 0)
        # fleet report carries both degrees
        full = eng.audit_comms()
        self.assertEqual((full["cp"], full["mp"]), (2, 2))


if __name__ == "__main__":
    unittest.main()
