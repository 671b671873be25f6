"""The plain reference of the `falcon_h1` block: what the served programs are
held to. `jax.numpy` alone, float32, true f32 matmuls
(`jax.default_matmul_precision("highest")` and `Precision.HIGHEST`), no cache,
no kernels, no paging, no chunks: one forward pass over a whole context, the
mixer's recurrence run token by token. Nothing here imports `paddle_tpu`.

The equations (x a token's normed layer input, position t; the configuration's
keys under their published names):

    x = rms_norm(h, input_layernorm)
    attention: u = x * attention_in_multiplier;  q = u W_q;
      k = (u W_k) * key_multiplier;  v = u W_v, heads of head_dim, kv heads
      shared by num_attention_heads / num_key_value_heads q heads; rotary on
      q and k over the whole head (pairs as halves, rope_theta); causal
      softmax(q k / sqrt(head_dim)) v over every earlier position;
      a = (attn W_o) * attention_out_multiplier
    mixer: u = x * ssm_in_multiplier;
      [z | x_s | B | C | dt] = (u W_in) * mup (ssm_multipliers by part);
      [x_s | B | C] = silu(sum_k w_k [x_s|B|C]_{t - 3 + k} + bias), the
      inputs before the first position zero;
      Δ = softplus(dt + dt_bias);  A = -exp(A_log);  head h reads group
      h // (mamba_n_heads / mamba_n_groups);
      S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t B_tᵀ, S_{-1} = 0;
      y_t = S_t C_t + D x_t;
      y = group_rms_norm(y * silu(z)) * w (mamba_n_groups groups,
      rms_norm_eps);  m = (y W_out) * ssm_out_multiplier
    h <- h + a + m
    h <- h + (silu((x' W_gate) * mlp_multipliers[0]) * (x' W_up)) W_down
             * mlp_multipliers[1],  x' = rms_norm(h, pre_ff_layernorm)
    the embedding times embedding_multiplier; rms_norm(final_layernorm),
    then the untied head, the logits times lm_head_multiplier.

Departures from the published description, each also under `assumed` of
`benchmark/configs/falcon-h1-34b.json`: none in the equations; the weights'
layout is the program's (matrices [in, out], the conv's weight [taps,
channels]) and the second norm is read under the program's name
(`post_attention_layernorm`).

Weights come in as the served ones under the names of
`paddle_tpu/models/falcon_h1.py`, on the device or as host arrays; each
layer's are cast up inside its own pieces.

`faults` plants a wrong reading of the model or of its state (the driver's
`correct` must fail each), and `lower_precision` rounds every matmul operand to
a lower precision first (the control that must fail too). The faults of the
state stand for what a serving program can get wrong: a sequence that starts
from the state its row held before (`state_not_zeroed`: the state this layer
reaches at the end of the same context), a prompt whose state is dropped at
each of the engine's prefill windows (`state_not_carried`: zero at every
multiple of the deployment's `token_budget` inside the prompt), a state kept
in bfloat16 (`state_bf16`).
"""
from __future__ import annotations

import contextlib
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
_ROUND_TO = [None]

# wrong readings that `correct` has to catch
FAULTS = ("state_not_zeroed", "state_not_carried", "conv_shifted",
          "d_dropped", "mup_dropped", "bc_groups_swapped", "state_bf16")

# queries whose scores against the whole context are held at once
QUERY_BLOCK = 64
# positions whose logits over the vocabulary are held at once
LOGIT_BLOCK = 256
# columns of the head cast to f32 at once
VOCAB_BLOCK = 32768


@contextlib.contextmanager
def lower_precision(dtype):
    """Inside, every matmul's operands are rounded to `dtype` first (the
    products still accumulate in f32), as a run in that precision would
    round them."""
    _ROUND_TO[0] = dtype
    try:
        yield
    finally:
        _ROUND_TO[0] = None


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _r(x):
    x = _f32(x)
    if _ROUND_TO[0] is not None:
        x = _f32(x.astype(_ROUND_TO[0]))
    return x


def _mm(x, w):
    return jnp.dot(_r(x), _r(w), precision=_HI)


def rms(x, w, eps):
    x = _f32(x)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(w)


def depth(m: dict) -> int:
    return int(m["num_hidden_layers"])


def _rotate(x, positions, theta):
    """Rotary over the last axis of x [S, ..., D], pairs (i, i + D / 2)."""
    d = x.shape[-1]
    inv = 1.0 / (float(theta) ** (jnp.arange(d // 2, dtype=jnp.float32)
                                  * 2.0 / d))
    ang = _f32(positions)[:, None] * inv
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(m: dict, x, w):
    """x [S, hidden] after the first norm -> the block's output [S, hidden]
    (its multiplier applied), a block of query rows against every key at a
    time."""
    s = x.shape[0]
    h, kvh, d = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    theta = m["rope_theta"]
    pos = jnp.arange(s)
    u = x * m["attention_in_multiplier"]
    q = _rotate(_mm(u, w["self_attn.q_proj.weight"]).reshape(s, h, d), pos,
                theta)
    k = _rotate(_mm(u, w["self_attn.k_proj.weight"]).reshape(s, kvh, d)
                * m["key_multiplier"], pos, theta)
    v = _mm(u, w["self_attn.v_proj.weight"]).reshape(s, kvh, d)
    k, v = (_r(jnp.repeat(a, h // kvh, axis=1)) for a in (k, v))
    qb = min(QUERY_BLOCK, s)

    def attend(block):
        qq, t = block                                   # [qb, h, d], [qb]
        sc = jnp.einsum("qhd,khd->hqk", _r(qq), k, precision=_HI) \
            / math.sqrt(d)
        sc = jnp.where(pos[None, None, :] <= t[None, :, None], sc, -jnp.inf)
        probs = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", _r(probs), v, precision=_HI)

    out = jax.lax.map(attend, (q.reshape(s // qb, qb, h, d),
                               pos.reshape(s // qb, qb)))
    return _mm(out.reshape(s, h * d), w["self_attn.o_proj.weight"]) \
        * m["attention_out_multiplier"]


def mup_vector(m: dict):
    d, gn = m["mamba_d_ssm"], m["mamba_n_groups"] * m["mamba_d_state"]
    widths = (d, d, gn, gn, m["mamba_n_heads"])
    return jnp.concatenate([jnp.full((n, ), v, jnp.float32)
                            for n, v in zip(widths, m["ssm_multipliers"])])


def _window_starts(m: dict, s: int):
    """[S] bool: the positions where the engine starts a prefill window
    after the first (multiples of the deployment's token_budget)."""
    tb = int(m["deployment"]["token_budget"])
    t = jnp.arange(s)
    return (t > 0) & (t % tb == 0)


def _conv_inputs(m: dict, xbc, prompt_len, faults):
    """[S, taps, C]: the inputs each position's conv reads, oldest first
    (before position 0: zero; with `conv_shifted` one position too early;
    with `state_not_carried` nothing from before the prompt's window)."""
    taps = m["mamba_d_conv"]
    s = xbc.shape[0]
    lag = 1 if "conv_shifted" in faults else 0
    ext = jnp.concatenate([jnp.zeros((taps - 1 + lag, xbc.shape[1])), xbc])
    idx = jnp.arange(s)[:, None] + jnp.arange(taps)[None, :]   # into ext
    got = ext[idx]
    if "state_not_carried" in faults:
        tb = int(m["deployment"]["token_budget"])
        t = jnp.arange(s)
        src = t[:, None] - (taps - 1 + lag) + jnp.arange(taps)[None, :]
        # the window a position's state began in: the prompt's windows, and
        # the last of them for every position after the prompt
        last = jnp.minimum(t, prompt_len - 1)
        start = last - last % tb
        got = jnp.where((src >= start[:, None])[..., None], got, 0.0)
    return got


def mixer(m: dict, x, w, prompt_len, upto, faults=()):
    """x [S, hidden] after the first norm -> (the mixer's output [S, hidden],
    its multiplier applied; S [heads, d_head, d_state] after position
    `upto` - 1); the recurrence over the positions one at a time."""
    s = x.shape[0]
    d, hn, p_ = m["mamba_d_ssm"], m["mamba_n_heads"], m["mamba_d_head"]
    g, n = m["mamba_n_groups"], m["mamba_d_state"]
    gn, c_dim = g * n, d + 2 * g * n
    eps = m["rms_norm_eps"]
    u = x * m["ssm_in_multiplier"]
    zxbcdt = _mm(u, w["mamba.in_proj.weight"])
    if "mup_dropped" not in faults:
        zxbcdt = zxbcdt * mup_vector(m)
    z, xbc, dt = zxbcdt[:, :d], zxbcdt[:, d:d + c_dim], zxbcdt[:, d + c_dim:]
    taps_in = _conv_inputs(m, xbc, prompt_len, faults)
    xbc = jax.nn.silu(jnp.einsum("skc,kc->sc", taps_in,
                                 _f32(w["mamba.conv1d.weight"]),
                                 precision=_HI)
                      + _f32(w["mamba.conv1d.bias"]))
    xs = xbc[:, :d].reshape(s, hn, p_)
    b = xbc[:, d:d + gn].reshape(s, g, n)
    c = xbc[:, d + gn:].reshape(s, g, n)
    if "bc_groups_swapped" in faults:
        b, c = b[:, ::-1], c[:, ::-1]
    group = jnp.arange(hn) // (hn // g)
    b, c = b[:, group], c[:, group]                     # [S, heads, N]
    delta = jax.nn.softplus(dt + _f32(w["mamba.dt_bias"]))
    a = -jnp.exp(_f32(w["mamba.A_log"]))
    restart = _window_starts(m, s) & (jnp.arange(s) < prompt_len) \
        if "state_not_carried" in faults else jnp.zeros((s,), bool)
    # positions from `upto` on leave the state as it was, so the scan ends
    # with the state after position upto - 1 (outputs past it are then no
    # longer the model's)
    live = jnp.arange(s) < upto

    def step(st, inp):
        dl, xt, bt, ct, again, on = inp
        new = jnp.where(again, 0.0, st)
        new = jnp.exp(dl * a)[:, None, None] * new \
            + (dl[:, None] * xt)[:, :, None] * bt[:, None, :]
        if "state_bf16" in faults:
            # a round trip through a convert may be dropped by the
            # compiler as a no-op; reduce_precision never is
            new = jax.lax.reduce_precision(new, exponent_bits=8,
                                           mantissa_bits=7)
        st = jnp.where(on, new, st)
        return st, jnp.einsum("hpn,hn->hp", new, ct, precision=_HI)

    s0 = jnp.zeros((hn, p_, n), jnp.float32)
    if "state_not_zeroed" in faults:
        # the row still holds what this layer left at the end of a context
        s0, _ = jax.lax.scan(step, s0, (delta, xs, b, c,
                                        jnp.zeros((s,), bool), live))
    last, y = jax.lax.scan(step, s0, (delta, xs, b, c, restart, live))
    if "d_dropped" not in faults:
        y = y + _f32(w["mamba.D"])[:, None] * xs
    y = y.reshape(s, d) * jax.nn.silu(z)
    y = y.reshape(s, g, d // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    y = y.reshape(s, d) * _f32(w["mamba.norm.weight"])
    return _mm(y, w["mamba.out_proj.weight"]) * m["ssm_out_multiplier"], last


def mlp(m: dict, x, w):
    gate_mult, down_mult = m["mlp_multipliers"]
    gate = _mm(x, w["feed_forward.gate_proj.weight"]) * gate_mult
    up = _mm(x, w["feed_forward.up_proj.weight"])
    return _mm(jax.nn.silu(gate) * up, w["feed_forward.down_proj.weight"]) \
        * down_mult


_PIECES = {}


def _piece(name, m, fn, *static):
    key = (name, json.dumps(m, sort_keys=True, default=str), static,
           str(_ROUND_TO[0]))
    if key not in _PIECES:
        _PIECES[key] = jax.jit(fn, donate_argnums=0)
    return _PIECES[key]


def _layer_weights(p: dict, i: int) -> dict:
    pre = f"model.layers.{i}."
    return {k[len(pre):]: jnp.asarray(v) for k, v in p.items()
            if k.startswith(pre)}


def hidden_states(m: dict, p: dict, ids, prompt_len: int, faults=(),
                  upto=None):
    """(final-norm input of every position of `ids` [S], each layer's S
    [heads, d_head, d_state] after position `upto` - 1, the last by
    default); `prompt_len` says where the engine's prefill ended (what
    `state_not_carried` reads)."""
    faults = tuple(faults)
    eps = m["rms_norm_eps"]
    emb = jnp.asarray(p["model.embed_tokens.weight"])[jnp.asarray(ids)]
    h = _f32(emb) * m["embedding_multiplier"]

    def layer(h, w, prompt_len, upto):
        with jax.default_matmul_precision("highest"):
            x = rms(h, w["input_layernorm.weight"], eps)
            out, state = mixer(m, x, w, prompt_len, upto, faults)
            h = h + attention(m, x, w) + out
            return h + mlp(m, rms(h, w["post_attention_layernorm.weight"],
                                  eps), w), state

    run = _piece("layer", m, layer, faults)
    states = []
    for i in range(depth(m)):
        h, state = run(h, _layer_weights(p, i), jnp.int32(prompt_len),
                       jnp.int32(len(ids) if upto is None else upto))
        states.append(state)
    return h, states


def head_logits(m: dict, p: dict, h):
    """f32 logits [len(h), vocab] of final-norm inputs `h`, the head's
    columns cast up a block at a time."""
    def part(h, g, w):
        with jax.default_matmul_precision("highest"):
            return _mm(rms(h, g, m["rms_norm_eps"]), w) \
                * m["lm_head_multiplier"]

    run = jax.jit(part)
    g = jnp.asarray(p["model.final_layernorm.weight"])
    w = p["lm_head.weight"]
    return jnp.concatenate(
        [run(h, g, jnp.asarray(w[:, a:a + VOCAB_BLOCK]))
         for a in range(0, w.shape[1], VOCAB_BLOCK)], axis=-1)


@jax.jit
def _scores(logits, tokens):
    chosen = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    std = jnp.std(logits, axis=-1)
    return {"gap": (jnp.max(logits, axis=-1) - chosen) / std,
            "logprob": chosen - jax.nn.logsumexp(logits, axis=-1),
            "std": std}


def token_scores(m: dict, p: dict, ids, first: int, tokens, faults=()):
    """One forward pass over `ids` (the caller's padding included; its
    prompt ends after position `first`), then for each of `tokens` — chosen
    after positions `first`, `first` + 1, ... — under this reference's
    logits there: "gap", how far the top logit leads the token's, in units
    of the logits' standard deviation (0 where the token is the argmax);
    "logprob", the token's log-probability; "std", that deviation. float64
    arrays, one entry a token."""
    h, _ = hidden_states(m, p, ids, first + 1, faults)
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    parts = []
    for a in range(0, n, LOGIT_BLOCK):
        at = np.minimum(first + a + np.arange(min(LOGIT_BLOCK, n)),
                        first + n - 1)
        toks = tokens[np.minimum(a + np.arange(len(at)), n - 1)]
        parts.append(_scores(head_logits(m, p, h[jnp.asarray(at)]),
                             jnp.asarray(toks)))
    return {k: np.concatenate([np.asarray(x[k], np.float64)
                               for x in parts])[:n] for k in parts[0]}
