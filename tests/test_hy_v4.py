"""`models/hy_v4.py` served through `ContinuousBatchingEngine` at CPU-test
sizes, against the plain reference (`benchmark/reference_hy_v4.py`): the
engine's prefill windows and decode steps through latent and index-key pages,
the selection a `shared` layer reuses, selection below `index_topk` as dense
attention, the hyper-connections' doubly stochastic map, a held share of the
experts, the third pool kind's accounting and what the engine refuses.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_hy_v4 as reference  # noqa: E402
from paddle_tpu.kernels import sparse_attention as sa  # noqa: E402
from paddle_tpu.models import HyV4Config  # noqa: E402
from paddle_tpu.models import hy_v4  # noqa: E402
from paddle_tpu.models.llama import PagedKVManager  # noqa: E402
from paddle_tpu.serving import ContinuousBatchingEngine  # noqa: E402

# contexts of 40+ tokens, so that the selection of 8 binds
PROMPTS = (44, 21)
NEW = 12


def _file_dict(cfg: HyV4Config) -> dict:
    """`cfg` as a benchmark configuration file states it (the reference's
    input): the held experts counted, the router's width published."""
    d = {k: list(v) if isinstance(v, tuple) else v
         for k, v in dataclasses.asdict(cfg).items()}
    d.update(n_routed_experts=len(cfg.held),
             published={"n_routed_experts": cfg.n_routed_experts},
             deployment={"held": list(cfg.held)})
    return d


def _engine(cfg, p, dtype, **kw):
    return ContinuousBatchingEngine(
        cfg, p, slots=2, max_prompt_len=64, max_new_tokens=16, block_size=8,
        prompt_bucket=16, token_budget=16, logprobs=True, dtype=dtype, **kw)


def _served(cfg, dtype):
    p = hy_v4.init_serving_params(cfg, 3, dtype)
    eng = _engine(cfg, p, jnp.dtype(dtype))
    rng = np.random.default_rng(0)
    for n in PROMPTS:
        eng.add_request(list(rng.integers(1, 128, size=n)), max_new=NEW)
    eng.run()
    return eng, p


def _against_reference(cfg, eng, p):
    """(gap, logprob error) at every generated position, in the reference
    logits' deviations."""
    m = _file_dict(cfg)
    gaps, errs = [], []
    for r in eng.finished:
        ids = list(r.prompt) + list(r.tokens[:-1])
        pad = -(-len(ids) // reference.QUERY_BLOCK) * reference.QUERY_BLOCK
        got = reference.token_scores(m, jax.device_get(p),
                                     ids + [0] * (pad - len(ids)),
                                     len(r.prompt) - 1, r.tokens)
        gaps.append(got["gap"])
        errs.append(np.abs(np.asarray(r.logprobs) - got["logprob"])
                    / got["std"])
    return np.concatenate(gaps), np.concatenate(errs)


# the stated tolerances, in the reference logits' deviations: the f32
# engine keeps its latent rows in bf16 and its attention products take bf16
# operands; the bf16 engine rounds everything
TOL = {"float32": {"gap": 0.1, "err_mean": 0.01, "err_max": 0.06},
       "bfloat16": {"gap": 0.5, "err_mean": 0.08, "err_max": 1.0}}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_engine_matches_the_reference_at_every_position(dtype):
    cfg = HyV4Config.tiny(dtype=dtype)
    eng, p = _served(cfg, dtype)
    assert sorted(len(r.tokens) for r in eng.finished) == [NEW, NEW]
    gaps, errs = _against_reference(cfg, eng, p)
    tol = TOL[dtype]
    assert gaps.max() < tol["gap"], gaps
    assert errs.mean() < tol["err_mean"] and errs.max() < tol["err_max"], errs
    em = eng.metrics()
    # every program counted its sparse attention: two full layers a decode
    # step, contexts past the selection's width
    assert em["dsa_full_layer_steps_decode"] == em["moe_layer_steps_decode"]
    assert em["dsa_keys_attended_decode"] < em["dsa_keys_scored_decode"] \
        * 3 / 2
    assert em["prefix_cache_off"] and "latent" in em["prefix_cache_off"]


def _selected(plan, bs=8):
    """A decode plan's selection: each row's selected pool rows, in
    position order ([B, n] where every row selects n)."""
    page, count, first = plan.meta[:, 0], plan.meta[:, 1], plan.meta[:, 2]
    return np.stack([np.concatenate([
        page[r, j] * bs + plan.offsets[r, 0, first[r, j]:][:count[r, j]]
        for j in range(plan.pages[r])]) for r in range(len(plan.pages))])


def _recording(monkeypatch):
    """Every selection the sparse attention kernels are handed, a call at a
    time: decode (its plan) and window (scores, thr, tie)."""
    got = {"decode": [], "window": []}
    real_d, real_w = sa.sparse_mla_decode, sa.sparse_mla_window

    def dec(q, pool, plan, sink, **kw):
        got["decode"].append(jax.tree.map(np.asarray, plan))
        return real_d(q, pool, plan, sink, **kw)

    def win(q, pool, table, pos, scores, thr, tie, sink, **kw):
        got["window"].append(tuple(np.asarray(x) for x in (scores, thr,
                                                           tie)))
        return real_w(q, pool, table, pos, scores, thr, tie, sink, **kw)

    monkeypatch.setattr(sa, "sparse_mla_decode", dec)
    monkeypatch.setattr(sa, "sparse_mla_window", win)
    return got


def test_a_shared_layer_uses_the_full_layers_selection(monkeypatch):
    """Layer 0 and 1 select for themselves, layer 2 (`shared`) attends
    exactly layer 1's selection, in the window and in decode."""
    from paddle_tpu.models.llama import _make_chunk_prefill, _make_decode_step

    cfg = HyV4Config.tiny()
    p = hy_v4.init_serving_params(cfg, 4, "float32")
    model = cfg.served_model()
    assert [l.attn.full for l in model.layers] == [True, True, False]
    got = _recording(monkeypatch)
    bs, pages = 8, 8
    made = [l.attn.pools(pages + 1, bs, jnp.float32) for l in model.layers]
    kcs, vcs = [k for k, _ in made], [v for _, v in made]
    table = jnp.arange(pages, dtype=jnp.int32)[None]
    ids = jnp.asarray(np.random.default_rng(1).integers(1, 128, (1, 48)),
                      jnp.int32)
    prefill = _make_chunk_prefill(cfg, 48, block_size=bs)
    prefill(p, kcs, vcs, ids, table, jnp.zeros((1,), jnp.int32),
            jnp.full((1,), 45, jnp.int32),
            chunk_pages=jnp.arange(6, dtype=jnp.int32)[None])
    w0, w1, w2 = got["window"]
    for a, b in zip(w1, w2):
        np.testing.assert_array_equal(a, b)
    assert not np.array_equal(w0[0], w1[0])
    step = _make_decode_step(cfg, 1, tables=table, block_size=bs)
    step(p, kcs, vcs, jnp.asarray([[7]], jnp.int32),
         jnp.asarray([45], jnp.int32))
    d0, d1, d2 = got["decode"]
    for a, b in zip(d1, d2):
        np.testing.assert_array_equal(a, b)
    d0, d1 = _selected(d0), _selected(d1)
    assert not np.array_equal(d0, d1)
    # a selection of 8 of the 46 positions, none past the token's own
    assert d1.shape == (1, 8) and (d1 <= 45).all()


def _plan_rows(plan, bs):
    """The pool rows a decode plan names, in position order ([B, K]; past a
    row's n_sel, rows of its last page)."""
    k = plan.offsets.shape[-1]
    first = plan.meta[:, 2]
    live = jnp.arange(first.shape[1])[None] < plan.pages[:, None]
    j = jnp.sum(live[:, None] & (first[:, None] <= jnp.arange(k)[:, None]),
                axis=2) - 1
    page = jnp.take_along_axis(plan.meta[:, 0], jnp.maximum(j, 0), axis=1)
    return page * bs + plan.offsets[:, 0]


def test_the_decode_lane_attends_the_rows_its_plans_name(monkeypatch):
    """Served, every call of the decode kernel, which copies whole each page
    that holds a selected row, gives the attention over the pool rows its
    plan names as `sparse_decode_reference` gathers them; the plans'
    selected rows are the keys the decode lane counts as attended."""
    cfg = HyV4Config.tiny()
    real = sa.sparse_mla_decode
    seen = []

    def dec(q, pool, plan, sink, **kw):
        out = real(q, pool, plan, sink, **kw)
        want = sa.sparse_decode_reference(
            q, pool, _plan_rows(plan, kw["block_size"]), plan.n_sel, sink,
            scale=kw["scale"], value_width=kw["value_width"])
        jax.debug.callback(
            lambda a, b, c: seen.append((np.abs(a - b).max(), c.sum())),
            out[..., :kw["value_width"]].astype(jnp.float32), want,
            plan.meta[:, 1])
        return out

    monkeypatch.setattr(sa, "sparse_mla_decode", dec)
    eng, _ = _served(cfg, "float32")
    monkeypatch.undo()
    err, attended = np.asarray(seen).T
    assert len(seen) and err.max() < 1e-2
    assert attended.sum() == eng.metrics()["dsa_keys_attended_decode"]


def test_below_index_topk_the_selection_is_every_earlier_position(
        monkeypatch):
    """Contexts shorter than `index_topk`: served and reference selections
    are the causal mask, so the attention is dense attention."""
    from paddle_tpu.models.llama import _make_chunk_prefill

    cfg = HyV4Config.tiny(index_topk=64)
    p = hy_v4.init_serving_params(cfg, 5, "float32")
    model = cfg.served_model()
    got = _recording(monkeypatch)
    bs, pages = 8, 6
    made = [l.attn.pools(pages + 1, bs, jnp.float32) for l in model.layers]
    kcs, vcs = [k for k, _ in made], [v for _, v in made]
    ids = list(np.random.default_rng(2).integers(1, 128, 40))
    prefill = _make_chunk_prefill(cfg, 48, block_size=bs)
    prefill(p, kcs, vcs, jnp.asarray([ids + [0] * 8], jnp.int32),
            jnp.arange(pages, dtype=jnp.int32)[None],
            jnp.zeros((1,), jnp.int32), jnp.full((1,), 40, jnp.int32),
            chunk_pages=jnp.arange(pages, dtype=jnp.int32)[None])
    causal = np.tril(np.ones((40, 40), bool))
    col = np.arange(pages * bs)[None, :]
    for sc, thr, tie in got["window"]:
        mask = (col <= np.arange(48)[:, None]) & (
            (sc > thr[:, None]) | ((sc == thr[:, None]) & (col <= tie[:, None])))
        np.testing.assert_array_equal(mask[:40, :40], causal)
        assert not mask[:40, 40:].any()
    keep = []
    reference.hidden_states(_file_dict(cfg), p, ids + [0] * 24, keep=keep)
    assert len(keep) == 2
    for mask in keep:
        np.testing.assert_array_equal(mask[:40, :40], causal)


@pytest.mark.parametrize("hidden", [64, 6144])
def test_h_res_is_doubly_stochastic_within_hc_eps(hidden):
    cfg = HyV4Config.tiny(hidden_size=hidden)
    shapes = hy_v4.serving_param_shapes(cfg)
    pre = "model.layers.1.attn_hc."
    key = jax.random.key(9)
    w = {n: hy_v4._init_value(n, shapes[n], jax.random.fold_in(key, i),
                              jnp.float32)
         for i, n in enumerate((pre + "phi", pre + "alpha", pre + "bias"))}
    x = jax.random.normal(jax.random.fold_in(key, 7), (6, cfg.hc_mult,
                                                       hidden))
    h_pre, h_post, h_res = hy_v4.hc_maps(cfg, x, w[pre + "phi"],
                                         w[pre + "alpha"], w[pre + "bias"])
    assert h_res.shape == (6, 4, 4) and (h_res > 0).all()
    np.testing.assert_allclose(h_res.sum(-1), 1.0, atol=cfg.hc_eps)
    np.testing.assert_allclose(h_res.sum(-2), 1.0, atol=cfg.hc_eps)
    assert (h_pre > 0).all() and (h_pre < 1).all()
    assert (h_post > 0).all() and (h_post < cfg.hc_magnitude).all()
    # the reference's own maps agree
    r_pre, r_post, r_res = reference.hc_maps(
        _file_dict(cfg), x, w[pre + "phi"], w[pre + "alpha"],
        w[pre + "bias"])
    np.testing.assert_allclose(r_res, h_res, atol=1e-5)
    np.testing.assert_allclose(r_pre, h_pre, atol=1e-5)


def test_two_held_halves_sum_to_the_uncut_layer():
    """The share: the routed layer holding experts 0-3 and the one holding
    4-7, the shared expert and the router counted once, sum to the layer
    holding all 8 — and to the reference's uncut layer."""
    whole = HyV4Config.tiny(held=tuple(range(8)))
    p = hy_v4.init_serving_params(whole, 6, "float32")
    pre = "model.layers.1."
    x = jax.random.normal(jax.random.key(3), (1, 24, whole.hidden_size))

    def layer(held):
        cfg = HyV4Config.tiny(held=held)
        q = {k: (v[held[0]:held[-1] + 1] if ".experts." in k else v)
             for k, v in p.items()}
        return hy_v4._routed(cfg)(x, q, pre)[0]

    m = pre + "mlp.shared_experts."
    shared = hy_v4.clamped_swiglu(
        x.reshape(24, -1), p[m + "gate_proj.weight"],
        p[m + "up_proj.weight"], p[m + "down_proj.weight"],
        whole.swiglu_limit).reshape(x.shape)
    halves = layer((0, 1, 2, 3)) + layer((4, 5, 6, 7)) - shared
    uncut = layer(tuple(range(8)))
    np.testing.assert_allclose(halves, uncut, rtol=1e-4, atol=1e-4)
    # the reference's layer, every expert held
    md = _file_dict(whole)
    w = {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}
    rows = x.reshape(24, -1)
    weight = reference.route(md, rows, w["mlp.gate.weight"],
                             w["mlp.gate.e_score_correction_bias"], 8)
    want = reference.swiglu(md, rows, w["mlp.shared_experts.gate_proj.weight"],
                            w["mlp.shared_experts.up_proj.weight"],
                            w["mlp.shared_experts.down_proj.weight"])
    want = want + reference._expert_block(
        md, rows, w["mlp.experts.gate_proj"], w["mlp.experts.up_proj"],
        w["mlp.experts.down_proj"], weight, ())
    np.testing.assert_allclose(uncut.reshape(24, -1), want, rtol=1e-3,
                               atol=1e-3)
    # and the clamp binds in this layer's experts
    g = rows @ p[pre + "mlp.experts.gate_proj"][0]
    assert float(jnp.max(g)) > whole.swiglu_limit


def test_the_third_pool_kind_is_counted():
    cfg = HyV4Config.tiny()
    model = cfg.served_model()
    per_token = sum(sum(l.attn.row_bytes(jnp.float32)) for l in model.layers)
    # a packed latent row of 128 words on each of 3 layers, an index key of
    # 8 f32 values on the 2 full layers
    assert per_token == 3 * 512 + 2 * 32
    assert sum(sum(l.attn.row_bytes(jnp.bfloat16)) for l in model.layers) \
        == 3 * 512 + 2 * 16
    assert PagedKVManager.page_bytes(8, n_layers=0, num_kv_heads=2,
                                     head_dim=16, latent_bytes=1536,
                                     index_bytes=64) == 8 * per_token
    assert PagedKVManager.pages_for_bytes(
        8 * per_token * 40, 8, n_layers=0, num_kv_heads=2, head_dim=16,
        latent_bytes=1536, index_bytes=64) == 40
    p = hy_v4.init_serving_params(cfg, 0, "float32")
    eng = _engine(cfg, p, jnp.float32)
    em = eng.metrics()
    assert em["kv_latent_token_bytes"] == 1536
    assert em["kv_index_token_bytes"] == 64
    assert em["kv_pool_bytes"] == eng.mgr.max_pages * 8 * per_token
    assert [k.shape for k in eng.kcs] == [(eng.mgr.max_pages * 8, 1, 128)] * 3
    assert [v is None for v in eng.vcs] == [False, False, True]
    sized = _engine(cfg, p, jnp.float32, kv_pool_bytes=8 * per_token * 40,
                    max_pages=None)
    assert sized.mgr.max_pages == 40


@pytest.mark.parametrize("option", [
    {"speculative": "ngram"}, {"kv_cache_dtype": "int8"},
    {"unified_step": False}, {"disaggregated": True}])
def test_the_engine_refuses_what_it_does_not_build(option):
    cfg = HyV4Config.tiny()
    p = hy_v4.init_serving_params(cfg, 0, "float32")
    with pytest.raises(ValueError, match="latent-attention"):
        _engine(cfg, p, jnp.float32, **option)
