"""The `glm4_moe_lite` family on the training path, piece by piece against the
plain reference the benchmark keeps (`benchmark/reference_glm4_moe_lite.py`:
float32, no kernels, experts as a masked loop over `held`): latent attention,
the bias-balanced router and its bias rule, the dropless expert layer over
the experts held here — and the share test that ties one chip's share to the
whole layer —, the multi-token-prediction module, both loss parts, the whole
model's loss and gradients, and `make_train_step` with the real AdamW.
Tiny sizes, seeded random weights, float32, CPU.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from benchmark import reference_glm4_moe_lite as reference  # noqa: E402
from paddle_tpu.core.tensor import Tensor, unwrap  # noqa: E402
from paddle_tpu.kernels.rope import rope_freqs  # noqa: E402
from paddle_tpu.models import glm4_moe_lite as glm  # noqa: E402
from paddle_tpu.parallel import make_train_step, read_report  # noqa: E402
from paddle_tpu.parallel.moe import (BiasBalancedSigmoidGate,  # noqa: E402
                                     DroplessMoELayer)

S = 32


def tiny_m(**over) -> dict:
    """The benchmark's configuration file under its tiny preset: the dict the
    reference takes (published keys, `published`, `assumed`, `deployment`)."""
    with open(os.path.join(ROOT, "benchmark/configs/glm-4.7-flash.json")) as f:
        m = json.load(f)
    with open(os.path.join(
            ROOT, "tests/benchmark/tiny/configs/glm-4.7-flash.json")) as f:
        tiny = json.load(f)
    for k, v in tiny.items():
        if isinstance(v, dict):
            m[k].update(v)
        else:
            m[k] = v
    m.update(over)
    return m


def config_of(m: dict, **over):
    return glm.Glm4MoeLiteConfig.from_dict(
        m, n_routed_experts=reference.router_width(m),
        held=m["deployment"]["held"],
        router_bias_update_rate=m["assumed"]["router_bias_update_rate"],
        mtp_loss_weight=m["assumed"]["mtp_loss_weight"], **over)


def rows(seed: int, batch: int = 2, vocab: int = 128):
    return np.random.default_rng(seed).integers(
        0, vocab, (batch, S + 2), dtype=np.int32)


def hidden(seed: int, batch: int = 2, width: int = 64):
    return np.random.default_rng(seed).standard_normal(
        (batch, S, width)).astype(np.float32)


def close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)
    assert err < tol, err


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_latent_attention_alone(seed):
    m = tiny_m()
    paddle.seed(seed)
    layer = glm.Glm4MoeLiteAttention(config_of(m))
    # norm scales off one, so that a norm left out or misplaced shows
    state = {k: v + 0.1 * jax.random.normal(jax.random.PRNGKey(i), v.shape)
             for i, (k, v) in enumerate(layer.raw_state().items())}
    layer.load_raw_state(state)
    x = hidden(seed)
    cos, sin = rope_freqs(S, m["qk_rope_head_dim"], base=m["rope_theta"])
    got = unwrap(layer(Tensor(jnp.asarray(x)), cos, sin))
    for b in range(x.shape[0]):
        close(got[b], reference.latent_attention(m, state, x[b]))


def test_latent_attention_is_causal_and_rotates_only_the_rope_part():
    m = tiny_m()
    paddle.seed(3)
    layer = glm.Glm4MoeLiteAttention(config_of(m))
    x = hidden(3, batch=1)
    cos, sin = rope_freqs(S, m["qk_rope_head_dim"], base=m["rope_theta"])
    base = np.asarray(unwrap(layer(Tensor(jnp.asarray(x)), cos, sin)))
    later = x.copy()
    later[0, 20:] += 1.0
    moved = np.asarray(unwrap(layer(Tensor(jnp.asarray(later)), cos, sin)))
    np.testing.assert_array_equal(base[0, :20], moved[0, :20])
    assert np.abs(base[0, 20:] - moved[0, 20:]).max() > 1e-3
    dn = m["qk_nope_head_dim"]
    q = jnp.asarray(hidden(4, width=4 * (dn + 4)).reshape(2, S, 4, dn + 4))
    kv = jnp.asarray(hidden(5, width=4 * (dn + 16)).reshape(2, S, 4, dn + 16))
    kr = jnp.asarray(hidden(6, width=4).reshape(2, S, 1, 4))
    qf, kf, v = glm._rope_join(q, kv, kr, cos, sin, dn)
    np.testing.assert_array_equal(qf[..., :dn], q[..., :dn])
    np.testing.assert_array_equal(kf[..., :dn], kv[..., :dn])
    np.testing.assert_array_equal(v, kv[..., dn:])
    close(qf[0, :, 1, dn:], reference.rope(q[0, :, 1, dn:], m["rope_theta"]))
    for h in range(4):      # one rotated key part, shared by every head
        close(kf[1, :, h, dn:], reference.rope(kr[1, :, 0], m["rope_theta"]))


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------

def _gate(m, seed):
    paddle.seed(seed)
    gate = BiasBalancedSigmoidGate(
        m["hidden_size"], reference.router_width(m),
        m["num_experts_per_tok"], m["norm_topk_prob"],
        m["routed_scaling_factor"])
    bias = np.random.default_rng(seed).standard_normal(
        gate.num_experts).astype(np.float32) * 0.3
    gate.load_raw_state({"e_score_correction_bias": jnp.asarray(bias)})
    return gate


@pytest.mark.parametrize("seed", [0, 1])
def test_router_chooses_by_score_plus_bias_and_gates_by_score(seed):
    m = tiny_m()
    gate = _gate(m, seed)
    state = gate.raw_state()
    x = hidden(seed).reshape(-1, m["hidden_size"])
    idx, gates = (np.asarray(unwrap(t)) for t in gate(Tensor(jnp.asarray(x))))
    want_idx, want_g, _, own = reference.route(
        m, x, state["weight"], state["e_score_correction_bias"])
    np.testing.assert_array_equal(own, want_idx)
    np.testing.assert_array_equal(np.sort(idx, -1),
                                  np.sort(np.asarray(want_idx), -1))
    close(np.sort(gates, -1), np.sort(np.asarray(want_g), -1))
    # by hand: the bias moves the choice and not the gates
    s = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ np.asarray(
        state["weight"], np.float64))))
    chosen = np.argsort(-(s + np.asarray(
        state["e_score_correction_bias"])), -1)[:, :m["num_experts_per_tok"]]
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(chosen, -1))
    by_hand = np.take_along_axis(s, chosen, -1)
    by_hand = by_hand / by_hand.sum(-1, keepdims=True) \
        * m["routed_scaling_factor"]
    close(np.sort(gates, -1), np.sort(by_hand, -1))
    close(gates.sum(-1), np.full(len(x), m["routed_scaling_factor"]))
    no_bias, _, _, _ = reference.route(m, x, state["weight"],
                                       np.zeros(gate.num_experts, np.float32))
    assert (np.sort(np.asarray(no_bias), -1) != np.sort(idx, -1)).any()
    # a routing handed in takes the place of the reference's own, entry by
    # entry where it is not negative: gates and loads follow it, and the
    # reference's own choice is still told
    forced = np.where(np.arange(len(x))[:, None] % 2 == 0,
                      np.asarray(no_bias), -1)
    idx_f, g_f, load_f, own_f = reference.route(
        m, x, state["weight"], state["e_score_correction_bias"],
        jnp.asarray(forced))
    np.testing.assert_array_equal(own_f, want_idx)
    np.testing.assert_array_equal(idx_f[::2], np.asarray(no_bias)[::2])
    np.testing.assert_array_equal(idx_f[1::2], np.asarray(want_idx)[1::2])
    close(g_f, np.take_along_axis(s, np.asarray(idx_f), -1)
          / np.take_along_axis(s, np.asarray(idx_f), -1).sum(-1, keepdims=True)
          * m["routed_scaling_factor"])
    np.testing.assert_array_equal(
        load_f, np.bincount(np.asarray(idx_f).ravel(),
                            minlength=gate.num_experts))


def test_router_bias_rule():
    load = jnp.asarray([5., 0., 9., 4., 4., 2., 8., 0.])
    bias = jnp.linspace(-0.1, 0.1, 8)
    got = BiasBalancedSigmoidGate.updated_bias(bias, load, 0.001)
    close(got, reference.bias_update(bias, load, 0.001), 1e-7)
    want = np.asarray(bias) + 0.001 * np.sign(4.0 - np.asarray(load))
    close(got, want, 1e-7)


def test_router_bias_takes_no_gradient():
    m = tiny_m()
    gate = _gate(m, 2)
    state = gate.raw_state()
    x = jnp.asarray(hidden(2).reshape(-1, m["hidden_size"]))

    def total(st):
        idx, gates = gate.func_call(st, Tensor(x))
        return unwrap(gates)[:, 0].sum()

    grads = jax.grad(total)(dict(state))
    assert float(jnp.abs(grads["e_score_correction_bias"]).max()) == 0.0
    assert float(jnp.abs(grads["weight"]).max()) > 0.0


# ---------------------------------------------------------------------------
# expert layer
# ---------------------------------------------------------------------------

def _moe(m, seed, held=None):
    paddle.seed(seed)
    cfg = config_of(m)
    cfg.held = None if held is None else tuple(held)
    layer = glm.Glm4MoeLiteMoE(cfg)
    bias = np.random.default_rng(seed).standard_normal(
        cfg.n_routed_experts).astype(np.float32) * 0.3
    layer.load_raw_state({"gate.e_score_correction_bias": jnp.asarray(bias)})
    return layer


@pytest.mark.parametrize("seed", [0, 1])
def test_expert_layer_holding_every_expert(seed):
    m = tiny_m()
    layer = _moe(m, seed)
    x = hidden(seed)
    y, counters = layer(Tensor(jnp.asarray(x)))
    flat = x.reshape(-1, m["hidden_size"])
    want, load, choice = reference.expert_layer(
        m, layer.raw_state(), flat, range(reference.router_width(m)))
    close(unwrap(y).reshape(flat.shape), want)
    np.testing.assert_array_equal(counters["moe.load"], load)
    np.testing.assert_array_equal(np.sort(counters["moe.choice"], -1),
                                  np.sort(choice, -1))
    routed = flat.shape[0] * m["num_experts_per_tok"]
    assert int(counters["moe.rows_routed"]) == routed
    assert int(counters["moe.rows_held"]) == routed
    assert int(counters["moe.rows_dropped"]) == 0
    assert int(counters["moe.load_max"]) == int(np.asarray(load).max())


SHARES = [(0, 1), (2, 3), (4, 5), (6, 7)]


@pytest.mark.parametrize("shares", [SHARES, [(7, 0, 3), (1,), (2, 6, 5, 4)]],
                         ids=["four_pairs", "uneven_unordered"])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """The routed parts that every chip's share gives, plus the shared expert
    counted once, are the uncut layer — in the program and in the reference,
    each held to the reference's uncut layer."""
    m = tiny_m()
    whole = _moe(m, 5)
    state = whole.raw_state()
    x = hidden(5)
    flat = x.reshape(-1, m["hidden_size"])
    uncut, _, _ = reference.expert_layer(m, state, flat,
                                         range(reference.router_width(m)))
    total = reference.shared_expert(state, flat)
    total_ref = total
    held_rows = 0
    for held in shares:
        part = _moe(m, 5, held)
        sel = jnp.asarray(held)
        part.load_raw_state({
            **{k: v for k, v in state.items() if "experts." not in k
               or "shared" in k},
            **{f"experts.{n}": state[f"experts.{n}"][sel]
               for n in ("gate_proj", "up_proj", "down_proj")}})
        routed, counters = DroplessMoELayer.forward(part,
                                                    Tensor(jnp.asarray(x)))
        total = total + unwrap(routed).reshape(flat.shape)
        ref_part, _, _ = reference.routed_experts(m, part.raw_state(), flat,
                                                  held)
        close(unwrap(routed).reshape(flat.shape), ref_part)
        total_ref = total_ref + ref_part
        held_rows += int(counters["moe.rows_held"])
        assert int(counters["moe.rows_dropped"]) == 0
    close(total, uncut)
    close(total_ref, uncut)
    assert held_rows == flat.shape[0] * m["num_experts_per_tok"]


def test_held_experts_must_be_distinct_and_in_range():
    with pytest.raises(ValueError, match="distinct"):
        DroplessMoELayer(16, 8, 4, 2, held=(1, 1))
    with pytest.raises(ValueError, match="distinct"):
        DroplessMoELayer(16, 8, 4, 2, held=(4,))


@pytest.mark.parametrize("held", [(2, 5), tuple(range(8))],
                         ids=["a_share", "every_expert"])
def test_expert_layer_gradients(held):
    """Against the reference's, for the input and every weight. The router's
    weight trains where the layer holds every expert; under a share the
    gates are constants of the backward pass, on both sides."""
    m = tiny_m()
    assert reference.router_width(m) == 8
    layer = _moe(m, 6, held)
    state = {k: v for k, v in layer.raw_state().items()}
    x = jnp.asarray(hidden(6).reshape(-1, m["hidden_size"]))
    w = jnp.asarray(hidden(7).reshape(-1, m["hidden_size"]))

    def program(st, xx):
        y, _ = layer.func_call(st, Tensor(xx))
        return jnp.sum(unwrap(y) * w)

    def plain(st, xx):
        y, _, _ = reference.expert_layer(m, st, xx, held)
        return jnp.sum(y * w)

    keep = [k for k in state if not k.endswith("bias")]
    got = jax.grad(program, argnums=(0, 1))(state, x)
    want = jax.grad(plain, argnums=(0, 1))(state, x)
    close(got[1], want[1])
    for k in keep:
        close(got[0][k], want[0][k])
    assert bool(jnp.any(got[0]["gate.weight"] != 0)) == (len(held) == 8)


# ---------------------------------------------------------------------------
# the whole model: MTP module, both losses, gradients, the trainer
# ---------------------------------------------------------------------------

def _model(m, seed, **over):
    paddle.seed(seed)
    cfg = config_of(m, **over)
    return glm.Glm4MoeLiteForCausalLM(cfg), \
        glm.Glm4MoeLitePretrainingCriterion(cfg)


def test_mtp_module_input_and_both_loss_parts():
    m = tiny_m()
    model, crit = _model(m, 8)
    state = model.raw_state()
    r = rows(8)
    out = model(Tensor(jnp.asarray(r[:, :S + 1])))
    loss, report = crit(out, Tensor(jnp.asarray(r[:, 1:S + 1])),
                        Tensor(jnp.asarray(r[:, 2:S + 2])))
    want = [reference.forward(m, state, r[b], m["deployment"]["held"],
                              tuple(range(S))) for b in range(len(r))]
    for part in ("loss.main", "loss.mtp"):
        close(report[part], np.mean([w[part] for w in want]), 1e-6)
    close(unwrap(loss), np.mean([w["loss"] for w in want]), 1e-6)
    close(unwrap(loss), float(report["loss.main"])
          + m["assumed"]["mtp_loss_weight"] * float(report["loss.mtp"]), 1e-6)
    for b, w in enumerate(want):
        close(unwrap(out.logits)[b], w["logits.main"])
        close(unwrap(out.mtp_logits[0])[b], w["logits.mtp"])
    close(report["moe.load"], sum(w["moe.load"] for w in want), 1e-7)
    # the module's input: [norm(h) ; norm(emb of the next token)] W_eh
    mod = model.mtp[0]
    h = hidden(9)
    emb = hidden(10)
    joined = unwrap(mod.eh_proj(paddle.concat(
        [mod.hnorm(Tensor(jnp.asarray(h))),
         mod.enorm(Tensor(jnp.asarray(emb)))], axis=-1)))
    w = {k[len("mtp.0."):]: v for k, v in state.items()
         if k.startswith("mtp.0.")}
    close(joined[0], reference.mtp_input(m, w, h[0], emb[0]))


def test_evaluation_runs_no_module_and_returns_logits():
    m = tiny_m()
    model, _ = _model(m, 8)
    model.eval()
    r = rows(8)
    logits = model(Tensor(jnp.asarray(r[:, :S])))
    want = reference.forward(m, model.raw_state(), r[0],
                             m["deployment"]["held"], tuple(range(S)))
    close(unwrap(logits)[0], want["logits.main"])


@pytest.mark.parametrize("recompute", [False, True],
                         ids=["saved", "recomputed"])
def test_whole_model_loss_and_gradients(recompute):
    m = tiny_m()
    model, crit = _model(m, 11)
    strategy = {"recompute": {"enable": True}} if recompute else None
    step, params, _ = make_train_step(model, crit, None, strategy=strategy)
    r = rows(11, batch=1)
    (loss, report), grads = step.loss_and_grads(
        params, r[:, :S + 1], r[:, 1:S + 1], r[:, 2:S + 2])
    names = [k for k in params if not k.endswith("e_score_correction_bias")]
    want, want_grads = reference.forward_and_grads(
        m, params, r[0], m["deployment"]["held"], names)
    close(loss, want["loss"], 1e-6)
    assert int(report["moe.rows_dropped"]) == 0
    for k in names:
        close(grads[k], want_grads[k], 5e-5)
    for k in set(params) - set(names):
        assert float(jnp.abs(grads[k]).max()) == 0.0


def test_three_steps_of_the_trainer_with_adamw():
    from paddle_tpu.observability import metrics as obs_metrics
    from paddle_tpu.optimizer import AdamW

    m = tiny_m()
    model, crit = _model(m, 12)
    optimizer = AdamW(learning_rate=1e-3, weight_decay=0.01,
                      apply_decay_param_fun=lambda n: "norm" not in n,
                      parameters=model.parameters())
    step, params, opt = make_train_step(model, crit, None,
                                        optimizer=optimizer)
    biases = [name for name, _ in model.routers()]
    assert len(biases) == 3             # two expert layers and the module's
    # state, not parameters: the optimizer keeps nothing for them
    assert not any(b in str(jax.tree_util.tree_flatten_with_path(opt)[0])
                   for b in biases)
    before = {b: np.asarray(params[b]) for b in biases}
    r = rows(12)
    batch = (r[:, :S + 1], r[:, 1:S + 1], r[:, 2:S + 2])
    registry = obs_metrics.MetricsRegistry()
    losses = []
    for _ in range(3):
        loss, params, opt, report = step(params, opt, *batch)
        read = read_report(report, registry)
        assert "moe.load" not in read and read["moe.rows_dropped"] == 0
        assert read["moe.rows_routed"] == 3 * 2 * S * 2
        assert 0 < read["moe.rows_held"] <= read["moe.rows_routed"]
        losses.append(float(loss))
        close(loss, read["loss.main"] + 0.3 * read["loss.mtp"], 1e-6)
    assert np.isfinite(losses).all() and losses[2] < losses[1] < losses[0]
    for b in biases:
        moved = np.asarray(params[b]) - before[b]
        assert np.abs(moved).max() > 0
        rate = m["assumed"]["router_bias_update_rate"]
        np.testing.assert_allclose(moved / rate, np.round(moved / rate),
                                   atol=1e-3)    # whole steps of the rate
        assert params[b].dtype == jnp.float32
    assert registry.gauge("moe.rows_held").value == read["moe.rows_held"]
    assert registry.gauge("loss.mtp").value == read["loss.mtp"]
    # one program, named by its role
    assert "jit_train_step" in step.jitted.lower(
        params, opt, jnp.float32(1e-3), *batch).as_text()[:200]


def test_bias_rule_follows_the_reference_through_the_step():
    """One step of the trainer moves each router's bias as the reference's
    rule does from the reference's loads."""
    m = tiny_m()
    model, crit = _model(m, 13)
    step, params, opt = make_train_step(model, crit, None)
    state = {k: np.asarray(v) for k, v in params.items()}
    r = rows(13)
    want = [reference.forward(m, state, r[b], m["deployment"]["held"])
            for b in range(len(r))]
    loads = sum(w["moe.load"] for w in want)
    _, params, _, report = step(params, opt, r[:, :S + 1], r[:, 1:S + 1],
                                r[:, 2:S + 2])
    close(report["moe.load"], loads, 1e-7)
    for i, (name, _) in enumerate(model.routers()):
        close(params[name], reference.bias_update(
            state[name], loads[i], m["assumed"]["router_bias_update_rate"]),
            1e-7)
