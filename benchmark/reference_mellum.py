"""The plain reference of the `mellum` block: what the served programs are held
to. `jax.numpy` alone, float32, true f32 matmuls (`Precision.HIGHEST`), no
cache, no kernels, no paging: one forward pass over a whole context with every
mask written out. Nothing here imports `paddle_tpu`.

The equations (x a token's hidden state, position t; the configuration's keys
under their published names):

    a = rms_norm(x, g1, eps);  q = a W_q (heads x head_dim), k = a W_k,
    v = a W_v (kv heads x head_dim), no bias
    rotary over the whole head, pairs as halves (i, i + head_dim / 2):
      sliding_attention layers: inv_freq_i = theta^(-2i / head_dim), scale 1
      full_attention layers (YaRN): pos_freq_i = theta^(2i / head_dim);
        low, high = the pair indices that turn beta_fast and beta_slow times
        over original_max_position_embeddings, floored / ceiled;
        ramp_i = clip((i - low) / (high - low), 0, 1);
        inv_freq_i = ramp_i / (factor pos_freq_i) + (1 - ramp_i) / pos_freq_i;
        cos and sin times attention_factor
    scores q k^T / sqrt(head_dim); key s is visible to query t iff s <= t and,
    on sliding_attention layers, t - s < sliding_window; query heads grouped
    over the kv heads; x = x + attn W_o
    b = rms_norm(x, g2, eps);  p = softmax(b W_r) over the experts, in f32;
    the num_experts_per_tok largest (the lower index wins a tie), their
    weights divided by their sum (norm_topk_prob);
    x = x + sum_e w_e (silu(b W_gate,e) * (b W_up,e)) W_down,e
    final rms_norm, untied head.

Departures from what a reader of the source might expect, each also under
`assumed` / `notes` of `benchmark/configs/mellum2-12b-a2.5b.json`:
  - no q / k norm: config.json has no key for one, and `model_type: mellum`
    is no class this repository can read;
  - no MTP head: the catalog's description mentions one, config.json has no
    key for it; the config wins;
  - `intermediate_size` is used by no layer (`mlp_layer_types` is all
    `sparse`; a `dense` entry is refused);
  - rotary pairs are halves (rotate-half); YaRN's ramp ends are floored and
    ceiled to whole pairs (`truncate` absent: the default, true).

Weights come in as the served ones (bfloat16 on the chip) under the names of
`paddle_tpu/models/mellum.py` and are cast up a layer — and within it a block
of experts — at a time, so the f32 copies never sit beside the engine's
weights whole.

`faults` plants a wrong reading of the source (the driver's `correct` must
fail each), and `lower_precision` rounds every matmul operand to a lower
precision first (the control that must fail too).
"""
from __future__ import annotations

import contextlib
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
_ROUND_TO = [None]

# wrong readings of the source that `correct` has to catch
FAULTS = ("window_layers_full", "yarn_factor_dropped",
          "attention_factor_dropped", "window_layers_full_table", "top_k_less_one",
          "gates_not_renormalised")

# experts whose f32 copies are held at once
EXPERT_BLOCK = 8
# queries whose scores against the whole context are held at once, and
# positions whose logits over the whole vocabulary are
QUERY_BLOCK = 512


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


def _mm(x, w):
    x, w = _f32(x), _f32(w)
    if _ROUND_TO[0] is not None:
        x, w = (_f32(t.astype(_ROUND_TO[0])) for t in (x, w))
    return jnp.dot(x, w, precision=_HI)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(w)


def depth(m: dict) -> int:
    return int(m["num_hidden_layers"])


def layer_kinds(m: dict):
    """(attention kind, mlp kind) of each layer that is run: the lists' first
    `num_hidden_layers` entries."""
    n = depth(m)
    return list(zip(m["layer_types"][:n], m["mlp_layer_types"][:n]))


def rotary_table(m: dict, kind: str, positions, faults=()):
    """(cos, sin) [S, head_dim / 2] of a layer kind at `positions`."""
    dh = m["head_dim"]
    if kind == "sliding_attention" and "window_layers_full_table" in faults:
        kind = "full_attention"
    g = m["rope_parameters"][kind]
    i = jnp.arange(dh // 2, dtype=jnp.float32)
    pos_freq = float(g["rope_theta"]) ** (2.0 * i / dh)
    inv, scale = 1.0 / pos_freq, 1.0
    if g["rope_type"] == "yarn":
        def turns_dim(turns):
            return dh * math.log(g["original_max_position_embeddings"]
                                 / (turns * 2 * math.pi)) \
                / (2 * math.log(g["rope_theta"]))

        low = max(math.floor(turns_dim(g["beta_fast"])), 0)
        high = min(math.ceil(turns_dim(g["beta_slow"])), dh - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
        factor = 1.0 if "yarn_factor_dropped" in faults else g["factor"]
        inv = ramp / (factor * pos_freq) + (1.0 - ramp) / pos_freq
        scale = 1.0 if "attention_factor_dropped" in faults \
            else g["attention_factor"]
    elif g["rope_type"] != "default":
        raise ValueError(f"rope_type {g['rope_type']!r}")
    ang = _f32(positions)[:, None] * inv
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def _rotate(x, cos, sin):
    """x [S, H, D], pairs (i, i + D / 2)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    cos, sin = cos[:, None], sin[:, None]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(m: dict, x, w, kind: str, faults=()):
    """x [S, hidden] after the first norm -> the block's output [S, hidden]
    before the residual add."""
    s = x.shape[0]
    nh, nkv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    cos, sin = rotary_table(m, kind, jnp.arange(s), faults)
    q = _rotate(_mm(x, w["self_attn.q_proj.weight"]).reshape(s, nh, dh),
                cos, sin)
    k = _rotate(_mm(x, w["self_attn.k_proj.weight"]).reshape(s, nkv, dh),
                cos, sin)
    v = _mm(x, w["self_attn.v_proj.weight"]).reshape(s, nkv, dh)
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    if _ROUND_TO[0] is not None:
        q, k, v = (_f32(t.astype(_ROUND_TO[0])) for t in (q, k, v))
    # a block of queries against every key at a time: a long context's
    # [heads, S, S] scores would not fit beside a deployment
    qb = min(s, QUERY_BLOCK)
    if s % qb:
        raise ValueError(f"a context of {s} tokens: pad it to a multiple of "
                         f"{QUERY_BLOCK}")

    def attend(block):
        q, t = block                        # [qb, heads, dh], positions [qb]
        scores = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) \
            / math.sqrt(dh)
        t, u = t[:, None], jnp.arange(s)[None, :]
        seen = u <= t
        if kind == "sliding_attention" and "window_layers_full" not in faults:
            seen &= t - u < m["sliding_window"]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                               axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=_HI)

    out = jax.lax.map(attend, (q.reshape(s // qb, qb, nh, dh),
                               jnp.arange(s).reshape(s // qb, qb)))
    return _mm(out.reshape(s, nh * dh), w["self_attn.o_proj.weight"])


def route(m: dict, x, w_router, faults=()):
    """x [S, hidden] -> (experts [S, k], weights [S, k]): softmax over every
    expert in f32, the k largest — a stable sort, so the lower index wins a
    tie —, renormalised."""
    k = m["num_experts_per_tok"] - ("top_k_less_one" in faults)
    probs = jax.nn.softmax(jnp.dot(_f32(x), _f32(w_router), precision=_HI),
                           axis=-1)
    idx = jnp.argsort(-probs, axis=-1, stable=True)[:, :k]
    gates = jnp.take_along_axis(probs, idx, axis=-1)
    if m["norm_topk_prob"] and "gates_not_renormalised" not in faults:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return idx, gates


@functools.partial(jax.jit, static_argnames=("round_to",))
def _expert_block(out, x, gate, up, down, weight, round_to=None):
    """out + sum over the block's experts of weight[:, e] * expert_e(x):
    every token through every expert of the block (weight 0 where the router
    did not choose it). The block's weights are cast up here, inside."""
    if round_to is not None:
        x, gate, up, down = (t.astype(round_to) for t in (x, gate, up, down))
    x, gate, up, down = (_f32(t) for t in (x, gate, up, down))
    act = jax.nn.silu(jnp.einsum("sd,edf->esf", x, gate, precision=_HI)) \
        * jnp.einsum("sd,edf->esf", x, up, precision=_HI)
    y = jnp.einsum("esf,efd->esd", act, down, precision=_HI)
    return out + jnp.einsum("esd,se->sd", y, weight, precision=_HI)


# One compiled program a piece of the forward pass, configuration, layer kind,
# planted faults and rounding: op by op the chip would compile some hundred
# small programs for every new context length (PR 33: 200 s of a run's set-up)
_PIECES = {}


def _piece(name, m, fn, *static):
    key = (name, json.dumps(m, sort_keys=True, default=str), static,
           str(_ROUND_TO[0]))
    if key not in _PIECES:
        _PIECES[key] = jax.jit(fn)
    return _PIECES[key]


def experts(m: dict, x, w, faults=()):
    """x [S, hidden] after the second norm -> the routed layer's output."""
    n = m["num_experts"]

    def weights(x, w_router):
        idx, gates = route(m, x, w_router, faults)
        return jnp.zeros((x.shape[0], n), jnp.float32).at[
            jnp.arange(x.shape[0])[:, None], idx].add(gates)

    weight = _piece("route", m, weights, tuple(faults))(
        x, w["mlp.gate.weight"])
    out = jnp.zeros_like(x)
    for a in range(0, n, EXPERT_BLOCK):
        blk = slice(a, a + EXPERT_BLOCK)
        out = _expert_block(
            out, x, *(w["mlp.experts." + nm][blk]
                      for nm in ("gate_proj", "up_proj", "down_proj")),
            weight[:, blk], round_to=_ROUND_TO[0])
    return out


def hidden_states(m: dict, p: dict, ids, faults=()):
    """Final-norm input of every position of `ids` [S]."""
    h = _f32(p["model.embed_tokens.weight"][jnp.asarray(ids)])
    eps = m["rms_norm_eps"]
    faults = tuple(faults)
    for i, (kind, mlp) in enumerate(layer_kinds(m)):
        pre = f"model.layers.{i}."
        w = {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}
        attn_w = {k: v for k, v in w.items() if not k.startswith("mlp.")}
        def block(h, w, kind=kind):
            h = h + attention(m, rms(h, w["input_layernorm.weight"], eps),
                              w, kind, faults)
            return h, rms(h, w["post_attention_layernorm.weight"], eps)

        h, b = _piece("attention", m, block, kind, faults)(h, attn_w)
        if mlp != "sparse":
            raise ValueError(f"mlp_layer_types: {mlp!r} is not built")
        h = h + experts(m, b, w, faults)
    return h


def head_logits(m: dict, p: dict, h):
    """f32 logits [len(h), vocab] of final-norm inputs `h`."""
    head = p["lm_head.weight"] if not m["tie_word_embeddings"] \
        else p["model.embed_tokens.weight"].T
    # the head in vocabulary chunks: the whole matrix in f32 would be a
    # large transient beside a deployment's pools
    step = -(-head.shape[1] // 8)
    chunk = _piece("head", m, lambda h, g, w: _mm(
        rms(h, g, m["rms_norm_eps"]), w))
    return jnp.concatenate([chunk(h, p["model.norm.weight"],
                                  head[:, a:a + step])
                            for a in range(0, head.shape[1], step)], axis=1)


def logits_at(m: dict, p: dict, ids, positions, faults=()):
    """f32 logits [len(positions), vocab] of one forward pass over the whole
    of `ids`, at `positions` (each predicts the token after it). Causal:
    ids after the last position asked for change nothing, so a caller may
    pad `ids` to a common length and pay each compilation once."""
    return head_logits(m, p, hidden_states(m, p, ids, faults)[
        jnp.asarray(positions)])


@jax.jit
def _scores(logits, tokens):
    chosen = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    std = jnp.std(logits, axis=-1)
    return {"gap": (jnp.max(logits, axis=-1) - chosen) / std,
            "logprob": chosen - jax.nn.logsumexp(logits, axis=-1),
            "std": std}


def token_scores(m: dict, p: dict, ids, first: int, tokens, faults=()):
    """One forward pass over `ids` (the caller's padding included), then for
    each of `tokens` — chosen after positions `first`, `first` + 1, ... —
    under this reference's logits there: "gap", how far the top logit leads
    the token's, in units of the logits' standard deviation (0 where the
    token is the argmax); "logprob", the token's log-probability; "std",
    that deviation. float64 arrays, one entry a token. The logits of
    QUERY_BLOCK positions are held at a time."""
    h = hidden_states(m, p, ids, faults)
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    parts = []
    for a in range(0, n, QUERY_BLOCK):
        # the last block is filled up to the blocks' one length
        at = np.minimum(first + a + np.arange(min(QUERY_BLOCK, n)),
                        first + n - 1)
        toks = tokens[np.minimum(a + np.arange(len(at)), n - 1)]
        parts.append(_scores(head_logits(m, p, h[jnp.asarray(at)]),
                             jnp.asarray(toks)))
    return {k: np.concatenate([np.asarray(x[k], np.float64)
                               for x in parts])[:n] for k in parts[0]}


def tie_gaps(ref_logits, tokens):
    """How far each position's top reference logit leads the logit of the
    token that was chosen there, in units of that position's logits'
    standard deviation (0 where the choice is the argmax)."""
    ref = np.asarray(ref_logits, np.float64)
    chosen = ref[np.arange(len(tokens)), np.asarray(tokens)]
    return (ref.max(-1) - chosen) / ref.std(-1)
