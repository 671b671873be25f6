"""The `afmoe` family (Trinity-Mini) on the training path, piece by piece
against the plain reference the benchmark keeps (`benchmark/reference_afmoe.py`:
float32, no kernels, attention as a masked softmax, experts as a masked loop
over `held`): the gated q/k-normed attention block on a window layer and on a
full layer, the expert layer with its shared expert — and the share test that
ties one chip's share to the whole layer —, the whole model's logits, loss and
gradients, and `make_train_step` with the real AdamW.
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
from benchmark import reference_afmoe as reference  # noqa: E402
from paddle_tpu.core.tensor import Tensor, unwrap  # noqa: E402
from paddle_tpu.kernels.rope import rope_freqs  # noqa: E402
from paddle_tpu.models import afmoe  # noqa: E402
from paddle_tpu.parallel import make_train_step, read_report  # noqa: E402
from paddle_tpu.parallel.moe import DroplessMoELayer  # noqa: E402

S = 32


def tiny_m(**over) -> dict:
    """The benchmark's configuration file under its tiny preset: the dict the
    reference takes (published keys, `published`, `assumed`, `deployment`)."""
    with open(os.path.join(ROOT, "benchmark/configs/trinity-mini.json")) as f:
        m = json.load(f)
    with open(os.path.join(
            ROOT, "tests/benchmark/tiny/configs/trinity-mini.json")) as f:
        tiny = json.load(f)
    for k, v in tiny.items():
        if isinstance(v, dict):
            m[k].update(v)
        else:
            m[k] = v
    m.update(over)
    return m


def rows(seed: int, batch: int = 2, vocab: int = 128):
    return np.random.default_rng(seed).integers(
        0, vocab, (batch, S + 1), dtype=np.int32)


def hidden(seed: int, batch: int = 2, width: int = 64):
    return np.random.default_rng(seed).standard_normal(
        (batch, S, width)).astype(np.float32)


def close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)
    assert err < tol, err


def shaken(layer, seed=0):
    """The layer's state with every leaf moved off its initial value, so that
    a norm scale left out or misplaced shows; loaded back into the layer."""
    state = {k: v + 0.1 * jax.random.normal(jax.random.PRNGKey(seed + i),
                                            v.shape, v.dtype)
             if jnp.issubdtype(v.dtype, jnp.floating) else v
             for i, (k, v) in enumerate(layer.raw_state().items())}
    layer.load_raw_state(state)
    return state


def test_config_from_the_cut_file():
    m = tiny_m()
    cfg = afmoe.AfmoeConfig.from_dict(m)
    assert cfg.num_experts == 8 and cfg.held == (0, 1)     # the router whole
    assert cfg.layer_types == ("sliding_attention",) * 3 + (
        "full_attention", "sliding_attention")
    assert cfg.load_balance_coeff == 0.001 and cfg.route_scale == 2.826
    whole = afmoe.AfmoeConfig()
    assert (whole.num_hidden_layers, whole.num_experts, whole.held) \
        == (32, 128, None)
    with pytest.raises(ValueError, match="layer_types"):
        afmoe.AfmoeConfig(num_hidden_layers=33)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layer", [1, 3], ids=["window", "full"])
def test_attention_alone(layer):
    m = tiny_m()
    paddle.seed(layer)
    block = afmoe.AfmoeAttention(afmoe.AfmoeConfig.from_dict(m), layer)
    state = shaken(block)
    x = hidden(layer)
    cos, sin = rope_freqs(S, m["head_dim"], base=m["rope_theta"])
    got = unwrap(block(Tensor(jnp.asarray(x)), cos, sin))
    for b in range(x.shape[0]):
        close(got[b], reference.attention(m, state, x[b],
                                          m["layer_types"][layer]))


def test_a_window_layer_forgets_and_a_full_layer_does_not_rotate():
    m = tiny_m()
    cfg = afmoe.AfmoeConfig.from_dict(m)
    cos, sin = rope_freqs(S, m["head_dim"], base=m["rope_theta"])
    x = hidden(3, batch=1)
    early = x.copy()
    early[0, :4] += 1.0             # rows 0-3: behind row 12's window of 8
    for layer, kind in ((1, "window"), (3, "full")):
        paddle.seed(7)
        block = afmoe.AfmoeAttention(cfg, layer)
        base, moved = (np.asarray(unwrap(block(Tensor(jnp.asarray(t)), cos,
                                               sin))) for t in (x, early))
        assert np.abs(base[0, :4] - moved[0, :4]).max() > 1e-3
        if kind == "window":
            np.testing.assert_allclose(base[0, 11:], moved[0, 11:],
                                       atol=1e-6)
        else:
            assert np.abs(base[0, 11:] - moved[0, 11:]).max() > 1e-3
            # no rotary: the table's values do not reach a full layer
            other = np.asarray(unwrap(block(Tensor(jnp.asarray(x)),
                                            cos * 0.5, sin * 0.5)))
            np.testing.assert_array_equal(base, other)


# ---------------------------------------------------------------------------
# the expert layer and its shares
# ---------------------------------------------------------------------------

def _moe(m, seed, held=None):
    cfg = afmoe.AfmoeConfig.from_dict(m, held=held)
    paddle.seed(seed)
    layer = afmoe.AfmoeMoE(cfg)
    bias = np.random.default_rng(seed).standard_normal(
        cfg.num_experts).astype(np.float32) * 0.3
    layer.load_raw_state({"gate.e_score_correction_bias": jnp.asarray(bias)})
    return layer


def test_expert_layer_holding_every_expert():
    m = tiny_m()
    layer = _moe(m, 1)
    x = hidden(1)
    y, counters = layer(Tensor(jnp.asarray(x)))
    flat = x.reshape(-1, m["hidden_size"])
    want, load, choice = reference.expert_layer(
        m, layer.raw_state(), flat, range(reference.router_width(m)))
    close(unwrap(y).reshape(flat.shape), want)
    np.testing.assert_array_equal(counters["moe.load"], load)
    np.testing.assert_array_equal(np.sort(counters["moe.choice"], -1),
                                  np.sort(choice, -1))
    routed = flat.shape[0] * m["num_experts_per_tok"]
    assert int(counters["moe.rows_held"]) == routed
    assert int(counters["moe.rows_dropped"]) == 0


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips each hold 2 of 16 experts (the cell: 16 of 128), every
    token takes 4: the routed parts that every share gives, plus the shared
    expert counted once, are the uncut layer — in the program and in the
    reference, each held to the reference's uncut layer."""
    m = tiny_m(num_experts_per_tok=4)
    m["published"]["num_experts"] = 16
    whole = _moe(m, 5)
    state = whole.raw_state()
    x = hidden(5)
    flat = x.reshape(-1, m["hidden_size"])
    uncut, _, _ = reference.expert_layer(m, state, flat, range(16))
    total = total_ref = reference.shared_expert(state, flat)
    held_rows = 0
    for share in range(8):
        held = (2 * share, 2 * share + 1)
        part = _moe(m, 5, held)
        part.load_raw_state({
            **{k: v for k, v in state.items() if "experts." not in k
               or "shared" in k},
            **{f"experts.{n}": state[f"experts.{n}"][jnp.asarray(held)]
               for n in ("gate_proj", "up_proj", "down_proj")}})
        routed, counters = DroplessMoELayer.forward(part,
                                                    Tensor(jnp.asarray(x)))
        ref_part, _, _ = reference.routed_experts(m, part.raw_state(), flat,
                                                  held)
        close(unwrap(routed).reshape(flat.shape), ref_part)
        total = total + unwrap(routed).reshape(flat.shape)
        total_ref = total_ref + ref_part
        held_rows += int(counters["moe.rows_held"])
    close(total, uncut)
    close(total_ref, uncut)
    assert held_rows == flat.shape[0] * 4


# ---------------------------------------------------------------------------
# the whole model
# ---------------------------------------------------------------------------

def _model(m, seed, **over):
    cfg = afmoe.AfmoeConfig.from_dict(m, **over)
    paddle.seed(seed)
    model = afmoe.AfmoeForCausalLM(cfg)
    shaken(model, seed)
    return model, afmoe.AfmoePretrainingCriterion(cfg)


def test_whole_model_logits_loss_and_gradients():
    m = tiny_m()
    model, crit = _model(m, 11)
    step, params, _ = make_train_step(model, crit, None)
    r = rows(11, batch=1)
    (loss, report), grads = step.loss_and_grads(params, r[:, :S], r[:, 1:])
    names = [k for k in params if not k.endswith("e_score_correction_bias")]
    pos = np.arange(0, S, 3)
    want, want_grads = reference.forward_and_grads(
        m, params, r[0], m["deployment"]["held"], names, pos)
    close(loss, want["loss"], 1e-6)
    out = model.func_call(params, Tensor(jnp.asarray(r[:, :S])))
    close(unwrap(out.logits)[0, pos], want["logits"])
    np.testing.assert_array_equal(
        np.sort(out.counters["moe.choice"], -1),
        np.sort(want["moe.choice"], -1))
    assert int(report["moe.rows_dropped"]) == 0
    for k in names:
        if k.endswith("mlp.gate.weight"):
            # under a share the gates are constants, on both sides
            assert float(jnp.abs(grads[k]).max()) == 0.0
            assert float(jnp.abs(want_grads[k]).max()) == 0.0
            continue
        close(grads[k], want_grads[k], 5e-5)
    model.eval()
    close(unwrap(model(Tensor(jnp.asarray(r[:, :S]))))[0, pos],
          want["logits"])


def test_the_report_counts_the_window_kernels_pairs():
    """Four window layers of a window of 8 over 32 rows, one block each way:
    swept is the square, forward and backward; in the mask W*S - W*(W-1)/2."""
    m = tiny_m()
    model, crit = _model(m, 2)
    r = rows(2)
    _, report = crit(model(Tensor(jnp.asarray(r[:, :S]))),
                     Tensor(jnp.asarray(r[:, 1:])))
    layers, batch = 4, 2
    assert float(report["attn.window_pairs_swept"]) \
        == layers * batch * 2 * S * S
    assert float(report["attn.window_pairs_in_mask"]) \
        == layers * batch * 2 * (8 * S - 8 * 7 // 2)
    # a window no row outgrows is a full layer: nothing to report
    wide, crit = _model(tiny_m(sliding_window=64), 2)
    _, report = crit(wide(Tensor(jnp.asarray(r[:, :S]))),
                     Tensor(jnp.asarray(r[:, 1:])))
    assert float(report["attn.window_pairs_in_mask"]) == 0.0


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
    assert len(biases) == 4             # layers 1-4; layer 0 is dense
    # state, not parameters: the optimizer keeps nothing for them
    assert not any(b in str(jax.tree_util.tree_flatten_with_path(opt)[0])
                   for b in biases)
    before = {b: np.asarray(params[b]) for b in biases}
    r = rows(12)
    registry = obs_metrics.MetricsRegistry()
    losses = []
    for _ in range(3):
        loss, params, opt, report = step(params, opt, r[:, :S], r[:, 1:])
        read = read_report(report, registry)
        assert set(read) == {
            "moe.rows_held", "moe.rows_routed", "moe.rows_multiplied",
            "moe.rows_dropped", "moe.rows_moved", "moe.load_max",
            "moe.load_mean", "attn.window_pairs_swept",
            "attn.window_pairs_in_mask"}
        assert read["moe.rows_routed"] == 4 * 2 * S * 2
        assert 0 < read["moe.rows_held"] <= read["moe.rows_routed"]
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[2] < losses[1] < losses[0]
    for b in biases:
        moved = (np.asarray(params[b]) - before[b]) / m["load_balance_coeff"]
        assert np.abs(moved).max() > 0
        np.testing.assert_allclose(moved, np.round(moved), atol=1e-3)
        assert params[b].dtype == jnp.float32
    assert registry.gauge("moe.rows_held").value == read["moe.rows_held"]
    # one program, named by its role
    assert "jit_train_step" in step.jitted.lower(
        params, opt, jnp.float32(1e-3), r[:, :S], r[:, 1:]).as_text()[:200]


def test_bias_rule_follows_the_reference_through_the_step():
    m = tiny_m()
    model, crit = _model(m, 13)
    step, params, opt = make_train_step(model, crit, None)
    state = {k: np.asarray(v) for k, v in params.items()}
    r = rows(13)
    loads = sum(reference.forward(m, state, r[b],
                                  m["deployment"]["held"])["moe.load"]
                for b in range(len(r)))
    _, params, _, report = step(params, opt, r[:, :S], r[:, 1:])
    close(report["moe.load"], loads, 1e-7)
    for i, (name, _) in enumerate(model.routers()):
        close(params[name], reference.bias_update(
            state[name], loads[i], m["load_balance_coeff"]), 1e-7)


def test_no_family_branch_in_the_trainer():
    """The model trains through `make_train_step` as any reporting model
    with state does: the trainer knows no `afmoe`."""
    path = os.path.join(ROOT, "paddle_tpu", "parallel")
    for name in os.listdir(path):
        if name.endswith(".py"):
            with open(os.path.join(path, name)) as f:
                assert "afmoe" not in f.read().lower(), name
