"""The `mellum` block in the serving engine, held to the plain reference
(`benchmark/reference_mellum.py`, at tiny sizes here): window and full
attention layers over the two kinds of KV pool, per-layer-kind rotary tables,
routed experts inside the served programs — float32 on the CPU.

The tiny model is one period of four layers (three window layers, one full),
a window of 16 tokens = two pages of 8, a prefill window of 16, so a ring is
five pages = 40 tokens: a context of 100 wraps it twice.
"""
import dataclasses
import os
import sys
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_mellum as reference  # noqa: E402
from paddle_tpu.kernels.grouped_matmul import row_tile  # noqa: E402
from paddle_tpu.kernels.rope import (YarnScaling, rope_freqs,  # noqa: E402
                                     rope_inv_freq)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.models import MellumConfig  # noqa: E402
from paddle_tpu.models import mellum  # noqa: E402
from paddle_tpu.serving import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.serving import engine as engine_mod  # noqa: E402

# f32 on both sides, and the same sums in another order (the kernels' online
# softmax, the experts' grouped rows): a logit moves by a few 1e-6 of the
# logits' spread; a wrong mask, table or gate moves it by tenths
LOGIT_TOL = 2e-4


def as_dict(cfg: MellumConfig) -> dict:
    """The configuration under the source's keys, as the reference reads it."""
    return dataclasses.asdict(cfg)


@pytest.fixture(scope="module")
def tiny():
    cfg = MellumConfig.tiny()
    return cfg, mellum.init_serving_params(cfg, seed=7, dtype="float32")


def _engine(cfg, p, **over):
    kw = dict(slots=2, prompt_bucket=16, block_size=8, max_prompt_len=64,
              max_new_tokens=96, token_budget=16, steps_per_sync=4,
              dtype=jnp.float32)
    kw.update(over)
    return ContinuousBatchingEngine(cfg, dict(p), **kw)


def _served_logits(cfg, p, prompt, max_new):
    """Serve one request alone; (its tokens, the logits behind each of them
    as the engine's own programs computed them, the engine)."""
    seen = []
    sample = engine_mod._sample_next

    def spy(logits, *a):
        jax.debug.callback(lambda x: seen.append(np.asarray(x, np.float32)),
                           logits, ordered=True)
        return sample(logits, *a)

    with mock.patch.object(engine_mod, "_sample_next", spy):
        eng = _engine(cfg, p)
        eng.add_request(prompt, max_new=max_new)
        eng.run(max_iters=1000)
    # the last call of the prefill lane (batch 1) gave the first token; the
    # decode lane's calls after it (batch `slots`) the rest, at row 0
    last_prefill = max(i for i, x in enumerate(seen) if x.shape[0] == 1)
    req = eng.finished[0]
    logits = [seen[last_prefill][0]] + [x[0] for x in seen[last_prefill + 1:]]
    return list(req.tokens), np.stack(logits[:len(req.tokens)]), eng


# (prompt, new tokens): inside the window; the window passed inside prefill,
# in the third of three windows; passed in decode and the ring wrapped twice
CONTEXTS = {"inside": (6, 8), "passed_in_prefill": (44, 6),
            "ring_wrapped_twice": (10, 92)}


@pytest.mark.parametrize("case", sorted(CONTEXTS))
def test_served_logits_equal_the_reference_at_every_position(tiny, case):
    cfg, p = tiny
    n_prompt, n_new = CONTEXTS[case]
    prompt = np.random.default_rng(n_prompt).integers(
        1, cfg.vocab_size, n_prompt).tolist()
    tokens, got, eng = _served_logits(cfg, p, prompt, n_new)
    assert len(tokens) == n_new
    ids = prompt + tokens[:-1]
    want = np.asarray(reference.logits_at(
        as_dict(cfg), p, ids, np.arange(n_prompt - 1, len(ids))))
    err = np.abs(got - want).max(-1) / want.std(-1)
    assert err.max() < LOGIT_TOL, (case, err.max(), int(err.argmax()))
    assert (reference.tie_gaps(want, tokens) < LOGIT_TOL).all()
    m = eng.metrics()
    ring_tokens = eng.mgr.ring_pages * eng.block_size
    assert ring_tokens == 40
    if case == "ring_wrapped_twice":
        assert len(ids) > 2 * ring_tokens
    # the cache ends at prompt + max_new tokens (the device's own budget)
    assert m["window_tokens_dropped"] == max(
        n_prompt + n_new - cfg.sliding_window, 0)
    # everything given back, both kinds
    assert eng.mgr.n_rings_free == eng.mgr.n_rings == eng.slots + 1
    assert eng.mgr.n_available == eng.mgr.max_pages - 1
    assert m["kv_pages_window"] == 0 and m["kv_pages_full"] == 0


def test_a_fault_in_the_reference_shows_in_the_logits(tiny):
    """The comparison can see each mechanism: the same served logits against
    a reference that reads the source wrongly."""
    cfg, p = tiny
    prompt = np.random.default_rng(1).integers(1, cfg.vocab_size, 44).tolist()
    tokens, got, _ = _served_logits(cfg, p, prompt, 12)
    ids = prompt + tokens[:-1]
    at = np.arange(len(prompt) - 1, len(ids))
    for fault in reference.FAULTS:
        want = np.asarray(reference.logits_at(as_dict(cfg), p, ids, at,
                                              faults=(fault,)))
        err = np.abs(got - want).max(-1) / want.std(-1)
        assert err.max() > 50 * LOGIT_TOL, (fault, err.max())


def test_window_pools_do_not_grow_with_the_context(tiny):
    cfg, p = tiny
    eng = _engine(cfg, p, slots=3)
    rng = np.random.default_rng(5)
    for n, new in ((40, 90), (8, 96), (30, 20), (12, 70), (64, 50)):
        eng.add_request(rng.integers(1, cfg.vocab_size, n).tolist(),
                        max_new=new)
    window_pages, full_pages = [], []
    while eng.has_work:
        eng.step()
        m = eng.metrics()
        window_pages.append(m["kv_pages_window"])
        full_pages.append(m["kv_pages_full"])
        assert m["kv_pages_window"] <= (eng.slots + 1) * eng.mgr.ring_pages
    assert max(window_pages) == 3 * eng.mgr.ring_pages  # one ring a sequence
    assert max(full_pages) > max(window_pages) // 2
    # a window layer's pool is the rings' size, a full layer's the contexts'
    shapes = {i: kc.shape[0] for i, kc in enumerate(eng.kcs)}
    assert shapes[0] == shapes[1] == shapes[2] == eng.mgr.window_pool_pages
    assert shapes[3] == eng.mgr.max_pages
    assert len(eng.finished) == 5 and not any(r.failed for r in eng.finished)
    # both kinds counted, K and V, at the pools' bf16 width
    assert eng.mgr.kv_pool_bytes() == sum(2 * kc.size * 2 for kc in eng.kcs)


def test_moe_counters_add_up(tiny):
    cfg, p = tiny
    eng = _engine(cfg, p, slots=2)
    rng = np.random.default_rng(9)
    for n in (20, 5, 33):
        eng.add_request(rng.integers(1, cfg.vocab_size, n).tolist(),
                        max_new=9)
    eng.run(max_iters=500)
    m = eng.metrics()
    layers, k, n_exp = 4, cfg.num_experts_per_tok, cfg.num_experts
    dec_steps = m["device_steps"] * eng.steps
    assert m["moe_layer_steps_decode"] == layers * dec_steps
    assert m["moe_rows_routed_decode"] == layers * dec_steps * eng.slots * k
    assert m["moe_layer_steps"] - m["moe_layer_steps_decode"] \
        == layers * m["prefill_chunks"]
    assert m["moe_rows_routed"] - m["moe_rows_routed_decode"] \
        == layers * m["prefill_chunks"] * eng.token_budget * k
    assert 0 < m["moe_experts_hit"] <= n_exp * m["moe_layer_steps"]
    # the largest group holds at least the mean
    assert m["moe_load_max"] * n_exp >= m["moe_rows_routed"]
    # no expert can get more rows than a lane has tokens, and neither lane
    # has more than its row tile: every expert one tile a layer-step
    assert m["moe_rows_multiplied_decode"] \
        == layers * dec_steps * n_exp * row_tile(eng.slots, k, n_exp)
    assert m["moe_rows_multiplied"] - m["moe_rows_multiplied_decode"] \
        == layers * m["prefill_chunks"] * n_exp \
        * row_tile(eng.token_budget, k, n_exp)
    assert m["prefix_cache_off"] and "rings" in m["prefix_cache_off"]


# ---- rotary tables ---------------------------------------------------------

PUBLISHED_YARN = YarnScaling(16.0, 8192, 32.0, 1.0, 1.2772588722239782)


def test_yarn_table_against_its_closed_form():
    dh, theta = 128, 500000.0
    inv, factor = rope_inv_freq(dh, theta, PUBLISHED_YARN)
    i = np.arange(dh // 2)
    pos_freq = theta ** (2.0 * i / dh)

    def dim_of(turns):
        return dh * np.log(8192 / (turns * 2 * np.pi)) / (2 * np.log(theta))

    low, high = np.floor(dim_of(32)), np.ceil(dim_of(1))
    assert (dim_of(32), dim_of(1)) == pytest.approx((18.08, 34.98), abs=0.01)
    assert (low, high) == (18, 35)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = ramp / (16 * pos_freq) + (1 - ramp) / pos_freq
    np.testing.assert_allclose(np.asarray(inv), want, rtol=1e-6)
    # fast pairs keep their frequency, slow ones are slowed 16 x
    np.testing.assert_allclose(np.asarray(inv[:19]), 1 / pos_freq[:19],
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(inv[35:]), 1 / (16 * pos_freq[35:]),
                               rtol=1e-6)
    assert factor == 1.2772588722239782
    # the published attention_factor is YaRN's own 0.1 ln(factor) + 1
    assert abs(factor - (0.1 * np.log(16) + 1)) < 1e-12
    cos, sin = rope_freqs(8, dh, theta, scaling=PUBLISHED_YARN)
    ang = np.arange(8)[:, None] * want
    np.testing.assert_allclose(np.asarray(cos), factor * np.cos(ang),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(sin), factor * np.sin(ang),
                               rtol=1e-5, atol=1e-6)


def test_yarn_at_factor_one_is_the_plain_table():
    plain = rope_freqs(64, 128, 500000.0,
                       position_ids=jnp.arange(1000, 1064))
    yarn = rope_freqs(64, 128, 500000.0, position_ids=jnp.arange(1000, 1064),
                      scaling=YarnScaling(1.0, 8192))
    for a, b in zip(plain, yarn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_the_models_tables_are_the_references(kind):
    cfg = MellumConfig()
    base, scaling = cfg.rope_of(kind)
    pos = jnp.asarray([0, 1, 1023, 5000, 70000])
    got = rope_freqs(5, cfg.head_dim, base, position_ids=pos, scaling=scaling)
    want = reference.rotary_table(as_dict(cfg), kind, pos)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=2e-3)   # f32 angles near 70000 rad
    assert (scaling is None) == (kind == "sliding_attention")


# ---- routing ----------------------------------------------------------------

def test_routing_is_the_references_top_k_renormalised():
    cfg = MellumConfig.tiny(num_experts=16, num_experts_per_tok=4)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((64, cfg.hidden_size)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((cfg.hidden_size, 16)), jnp.float32)
    idx, gates = mellum.route(x, w, 4, True)
    ridx, rgates = reference.route(as_dict(cfg), x, w)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(rgates),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-6)
    raw = mellum.route(x, w, 4, False)[1]
    assert np.asarray(raw).sum(-1).max() <= 1.0 + 1e-6
    assert np.asarray(raw).sum(-1).min() < 0.99


def test_a_routing_tie_goes_to_the_lower_index_on_both_sides():
    cfg = MellumConfig.tiny(num_experts=8, num_experts_per_tok=2)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((32, cfg.hidden_size)), jnp.float32)
    # experts 1, 4 and 6 score alike on every token
    w = rng.standard_normal((cfg.hidden_size, 8))
    w[:, 4] = w[:, 6] = w[:, 1]
    w = jnp.asarray(w, jnp.float32)
    idx, gates = mellum.route(x, w, 2, True)
    ridx, rgates = reference.route(as_dict(cfg), x, w)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    tied = np.asarray(jnp.dot(x, w))
    rows = (tied[:, 1] == tied.max(-1))
    assert rows.any()
    assert (np.asarray(idx)[rows] == [1, 4]).all()
    np.testing.assert_allclose(np.asarray(gates)[rows], 0.5, rtol=1e-6)


def test_the_served_expert_layer_is_the_references(tiny):
    cfg, p = tiny
    mlp = cfg.served_model().layers[0].mlp
    x = jnp.asarray(np.random.default_rng(8).standard_normal(
        (2, 24, cfg.hidden_size)), jnp.float32)
    pre = "model.layers.0."
    y, counts = jax.jit(lambda x: mlp(x, p, pre))(x)
    w = {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}
    want = reference.experts(as_dict(cfg), x.reshape(48, -1), w)
    np.testing.assert_allclose(np.asarray(y).reshape(48, -1),
                               np.asarray(want), rtol=2e-4, atol=2e-5)
    counts = np.asarray(counts)
    assert counts[0] == 1 and counts[1] == 48 * cfg.num_experts_per_tok
    assert 1 <= counts[2] <= cfg.num_experts
    assert counts[3] * cfg.num_experts >= counts[1]


# ---- Llama through the same builders -----------------------------------------

# what the engine at the commit before the contract (cfc15e1) generated for
# the same seeds, sizes and prompts, written down from a run of that tree
LLAMA_TOKENS_BEFORE = [[87, 87, 31, 31, 31, 4, 69, 46],
                       [93, 93, 93, 87, 12, 57, 120, 14],
                       [115, 67, 94, 114, 61, 115, 87, 95]]


def test_llama_through_the_contract_is_token_identical_to_before():
    """The builders read `served_model(cfg)`; a Llama config has no such
    method and gets the dense block under its own names: the engine's greedy
    tokens are what they were."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import served_model

    cfg = LlamaConfig.tiny()
    paddle.seed(11)
    model = LlamaForCausalLM(cfg)
    spec = served_model(cfg)
    assert spec.head_dim == cfg.head_dim and not spec.window_layers \
        and not spec.routed
    assert [l.prefix for l in spec.layers] == [
        f"llama.layers.{i}." for i in range(cfg.num_hidden_layers)]
    eng = ContinuousBatchingEngine(
        cfg, dict(model.raw_state()), slots=2, prompt_bucket=8,
        max_prompt_len=32, max_new_tokens=8, block_size=8, token_budget=8)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in (5, 19, 30)]
    reqs = [eng.add_request(q, max_new=8) for q in prompts]
    eng.run(max_iters=500)
    assert [list(r.tokens) for r in reqs] == LLAMA_TOKENS_BEFORE
    m = eng.metrics()
    assert m["prefix_cache_off"] is None and m["moe_layer_steps"] == 0
    assert m["kv_pages_window"] == 0 and eng.mgr.n_rings == 0


# ---- log-probabilities ride out with the tokens --------------------------------

def test_each_tokens_logprob_is_the_references(tiny):
    """`logprobs=True`: the same tokens as without it, and beside each its
    log-probability — the first from the prefill lane, the rest from the
    decode chunk, several requests live — equal to the reference's."""
    cfg, p = tiny
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in (44, 10, 6)]
    new = (20, 50, 9)

    def serve(**kw):
        eng = _engine(cfg, p, **kw)
        reqs = [eng.add_request(q, max_new=n) for q, n in zip(prompts, new)]
        eng.run(max_iters=1000)
        return reqs

    plain, scored = serve(), serve(logprobs=True)
    assert [r.tokens for r in plain] == [r.tokens for r in scored]
    assert all(r.logprobs == [] for r in plain)
    for r in scored:
        ids = r.prompt + r.tokens[:-1]
        got = reference.token_scores(as_dict(cfg), p, ids, len(r.prompt) - 1,
                                     r.tokens)
        assert len(r.logprobs) == len(r.tokens)
        err = np.abs(np.asarray(r.logprobs) - got["logprob"]) / got["std"]
        assert err.max() < LOGIT_TOL and (got["gap"] < LOGIT_TOL).all()


def test_llama_hands_out_logprobs_with_the_same_tokens():
    import paddle_tpu as paddle

    cfg = LlamaConfig.tiny()
    paddle.seed(11)
    model = LlamaForCausalLM(cfg)
    eng = ContinuousBatchingEngine(
        cfg, dict(model.raw_state()), slots=2, prompt_bucket=8,
        max_prompt_len=32, max_new_tokens=8, block_size=8, token_budget=8,
        logprobs=True)
    rng = np.random.default_rng(0)
    reqs = [eng.add_request(rng.integers(1, cfg.vocab_size, n).tolist(),
                            max_new=8) for n in (5, 19, 30)]
    eng.run(max_iters=500)
    assert [list(r.tokens) for r in reqs] == LLAMA_TOKENS_BEFORE
    for r in reqs:
        assert len(r.logprobs) == 8
        assert all(-np.log(cfg.vocab_size) - 5 < lp < 0 for lp in r.logprobs)


@pytest.mark.parametrize("option", ["unified_step", "speculative",
                                    "disaggregated"])
def test_logprobs_refuse_what_does_not_return_them(option):
    import paddle_tpu as paddle

    cfg = LlamaConfig.tiny()
    paddle.seed(11)
    p = dict(LlamaForCausalLM(cfg).raw_state())
    with pytest.raises(ValueError) as e:
        ContinuousBatchingEngine(cfg, p, slots=2, logprobs=True,
                                 **REFUSED[option])
    assert option in str(e.value) and "logprobs=True" in str(e.value)


# ---- what is not built refuses by name ---------------------------------------

REFUSED = {
    "unified_step": dict(unified_step=False),
    "speculative": dict(speculative="ngram"),
    "serving_mp": dict(serving_mp=2),
    "serving_cp": dict(serving_cp=2),
    "disaggregated": dict(disaggregated=True),
    "kv_cache_dtype": dict(kv_cache_dtype="int8"),
}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_an_option_that_is_not_built_refuses_by_name(tiny, option):
    cfg, p = tiny
    with pytest.raises(ValueError) as e:
        _engine(cfg, p, **REFUSED[option])
    assert option in str(e.value)
    assert "sliding-window and routed-expert layers" in str(e.value)


def test_a_routed_model_without_window_layers_is_refused_the_same(tiny):
    cfg = MellumConfig.tiny(layer_types=("full_attention",) * 4)
    p = mellum.init_serving_params(cfg, 1, "float32")
    with pytest.raises(ValueError, match="speculative.*routed-expert layers"):
        _engine(cfg, p, speculative="ngram")
    eng = _engine(cfg, p)                   # no window: the prefix cache stays
    assert eng.prefix_cache and eng.mgr.n_rings == 0


def test_the_config_reads_the_source_and_cuts_its_lists():
    import json

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mellum2-12b-a2.5b.json")) as f:
        m = json.load(f)
    cfg = MellumConfig.from_dict(m)
    assert cfg.num_hidden_layers == len(cfg.layer_types) == 12
    spec = cfg.served_model()
    assert spec.window_layers == (0, 1, 2, 4, 5, 6, 8, 9, 10)
    assert spec.routed and spec.head_dim == 128
    assert cfg.hidden_size != cfg.head_dim * cfg.num_attention_heads
    shapes = mellum.serving_param_shapes(cfg)
    n = sum(int(np.prod(s)) for s in shapes.values())
    per_layer = 2304 * 4096 * 2 + 2 * 2304 * 512 + 2304 * 64 \
        + 64 * 3 * 2304 * 896 + 2 * 2304
    assert n == 12 * per_layer + 2 * 98304 * 2304 + 2304
    with pytest.raises(ValueError, match="layer_types names 28 layers"):
        MellumConfig.tiny(num_hidden_layers=29)
