"""The plain reference of the `hy_v4` block: what the served programs are held
to. `jax.numpy` alone, float32, true f32 matmuls (`Precision.HIGHEST`), no
cache, no kernels, no paging: one forward pass over a whole context, every
mask written out, attention EXPANDED (k_nope and v made from c_kv per head,
not absorbed into the query). Nothing here imports `paddle_tpu`.

The equations (x a token's normed layer input, position t; the configuration's
keys under their published names; X the n = hc_mult residual streams):

    hyper-connections around each sub-block F (mHC, arXiv:2512.24880):
      x~ = X / rms(X) over its n * hidden values (no learned scale);
      z = x~ phi;  H_pre = sigmoid(a0 z_pre + b_pre),
      H_post = hc_magnitude sigmoid(a1 z_post + b_post),
      H_res = Sinkhorn(exp(a2 z_res + b_res)), 20 rounds of rows then
      columns, hc_eps in each denominator;
      X <- H_res X + H_post^T F(H_pre X).
    The embedding enters as n copies; the streams are summed before the
    final rms_norm; the head is f32, untied.
    attention: F(u) = attn(rms_norm(u, g1)):
      c_q = rms_norm(x W_qa, g_q);  q = c_q W_qb, a head [nope | rope];
      [c_kv ; k_r] = x W_kva;  c_kv = rms_norm(c_kv, g_kv);
      rotary on q_rope and k_r (pairs as halves, rope_theta);
      [k_nope | v] = c_kv W_kvb a head;
      s(t, u) = (q_nope . k_nope + q_rope . k_r) / sqrt(qk_head_dim) for u in
      S_t; p = exp(s) / (sum exp(s) + exp(sink_h));
      o = (sigmoid(x W_g) * sum p v) W_o.
    the indexer of a `full` layer (DeepSeek-V3.2): q^I = c_q W_Iq, heads of
      index_head_dim; k^I = LayerNorm(x W_Ik) (weight, bias, rms_norm_eps);
      rotary on their first qk_rope_head_dim dims; w = x W_Iw;
      I(t, u) = sum_j w_j relu(q^I_j . k^I_u), u <= t;
      S_t = the top min(index_topk, t + 1) of I(t, .), a stable sort (the
      lower position wins a tie). A `shared` layer uses the S_t of the
      nearest `full` layer above it.
    MLP: F(u) = mlp(rms_norm(u, g2)): `dense` a SwiGLU; `sparse`
      s = sigmoid(x W_r) over every routed expert, the top
      num_experts_per_tok of s + bias (stable), gates s / sum s x
      routed_scaling_factor, the HELD experts' SwiGLUs weighted by their
      gates (an expert this chip does not hold adds nothing), plus the
      shared expert. Every SwiGLU: silu(min(g, L)) * clip(u, -L, L),
      L = swiglu_limit.

Departures from the published description, each also under `assumed` of
`benchmark/configs/hy4-preview.json`: the mHC reading of `enable_ihc` and its
details above; the gate reads the normed layer input; the indexer's rotary
covers its first qk_rope_head_dim dims and its LayerNorm takes rms_norm_eps;
DeepSeek's positive index scale factors and Hadamard rotation are left out
(the order of I is unchanged); the MTP module is not run.

Weights come in as the served ones under the names of
`paddle_tpu/models/hy_v4.py`, on the device or as host arrays; each layer's
are put on the device and cast up inside its own pieces, so the f32 copies
never sit beside a deployment's weights whole.

`faults` plants a wrong reading of the source (the driver's `correct` must
fail each), and `lower_precision` rounds every matmul operand to a lower
precision first (the control that must fail too).
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

# wrong readings of the source that `correct` has to catch
FAULTS = ("sink_dropped", "gate_dropped", "shared_layers_recompute",
          "topk_off_by_one", "hres_unnormalised", "clamp_removed")

SINKHORN_ITERS = 20
# experts whose f32 copies are held at once
EXPERT_BLOCK = 4
# queries whose scores against the whole context are held at once
QUERY_BLOCK = 64
# positions whose logits over the vocabulary are held at once
LOGIT_BLOCK = 512


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


def _ein(spec, a, b):
    return jnp.einsum(spec, _r(a), _r(b), precision=_HI)


def rms(x, w, eps):
    x = _f32(x)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 if w is None else _f32(w))


def depth(m: dict) -> int:
    return int(m["num_hidden_layers"])


def layer_kinds(m: dict):
    """(indexer kind, mlp kind) of each layer that is run: the lists' first
    `num_hidden_layers` entries."""
    n = depth(m)
    return list(zip(m["indexer_types"][:n], m["mlp_layer_types"][:n]))


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


def sinkhorn(m, iters: int, eps: float):
    for _ in range(iters):
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)
    return m


def hc_maps(m: dict, x, phi, alpha, bias, faults=()):
    """(H_pre [S, n], H_post [S, n], H_res [S, n, n]) of streams x [S, n, C]."""
    n = m["hc_mult"]
    flat = x.reshape(x.shape[0], -1)
    z = _mm(rms(flat, None, m["rms_norm_eps"]), phi)
    a, b = _f32(alpha), _f32(bias)
    pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    post = m["hc_magnitude"] * jax.nn.sigmoid(a[1] * z[:, n:2 * n]
                                              + b[n:2 * n])
    res = jnp.exp(a[2] * z[:, 2 * n:] + b[2 * n:]).reshape(-1, n, n)
    if "hres_unnormalised" not in faults:
        res = sinkhorn(res, SINKHORN_ITERS, m["hc_eps"])
    return pre, post, res


def _hc(m, x, w, sub, faults):
    """(u = H_pre X, a function joining F(u) into X)."""
    pre, post, res = hc_maps(m, x, w[sub + "phi"], w[sub + "alpha"],
                             w[sub + "bias"], faults)
    u = jnp.einsum("si,sic->sc", pre, x, precision=_HI)

    def join(y):
        return jnp.einsum("sij,sjc->sic", res, x, precision=_HI) \
            + post[..., None] * _f32(y)[:, None, :]

    return u, join


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(w) + _f32(b)


def _blocks(s: int) -> int:
    qb = min(s, QUERY_BLOCK)
    if s % qb:
        raise ValueError(f"a context of {s} tokens: pad it to a multiple of "
                         f"{QUERY_BLOCK}")
    return qb


def selection(m: dict, x, c_q, w, faults=()):
    """S_t of a `full` layer as a mask [S, S], a block of query rows at a
    time: I over every earlier position, then the top min(index_topk, t + 1)
    of each row, a stable sort (the lower position wins a tie)."""
    s = x.shape[0]
    n, d, r = m["index_n_heads"], m["index_head_dim"], m["qk_rope_head_dim"]
    theta = m["rope_parameters"]["rope_theta"]
    pos = jnp.arange(s)
    qi = _mm(c_q, w["self_attn.indexer.wq_b.weight"]).reshape(s, n, d)
    ki = _layer_norm(_mm(x, w["self_attn.indexer.wk.weight"]),
                     w["self_attn.indexer.k_norm.weight"],
                     w["self_attn.indexer.k_norm.bias"], m["rms_norm_eps"])
    qi = jnp.concatenate([_rotate(qi[..., :r], pos, theta), qi[..., r:]], -1)
    ki = jnp.concatenate([_rotate(ki[:, None, :r], pos, theta)[:, 0],
                          ki[:, r:]], -1)
    wt = _mm(x, w["self_attn.indexer.weights_proj.weight"])
    k = min(m["index_topk"] - ("topk_off_by_one" in faults), s)
    qb = _blocks(s)

    def block(args):
        q, wb, t = args                      # [qb, n, d], [qb, n], [qb]
        dots = jnp.maximum(_ein("tjd,ud->tju", q, ki), 0.0)
        score = jnp.einsum("tju,tj->tu", dots, wb, precision=_HI)
        seen = pos[None, :] <= t[:, None]
        order = jnp.argsort(-jnp.where(seen, score, -jnp.inf), axis=-1,
                            stable=True)[:, :k]
        mask = jnp.zeros((qb, s), bool).at[
            jnp.arange(qb)[:, None], order].set(True)
        return mask & seen

    return jax.lax.map(block, (qi.reshape(s // qb, qb, n, d),
                               wt.reshape(s // qb, qb, n),
                               pos.reshape(s // qb, qb))).reshape(s, s)


def _c_q(m, x, w):
    return rms(_mm(x, w["self_attn.q_a_proj.weight"]),
               w["self_attn.q_a_layernorm.weight"], m["rms_norm_eps"])


def attention(m: dict, x, w, sel, faults=()):
    """x [S, hidden] after the first norm, sel [S, S] -> the block's output
    [S, hidden], expanded: k_nope and v made from c_kv a head, a block of
    query rows against every key at a time."""
    s = x.shape[0]
    h, nope, rope, dv = (m["num_attention_heads"], m["qk_nope_head_dim"],
                         m["qk_rope_head_dim"], m["v_head_dim"])
    kvr, eps = m["kv_lora_rank"], m["rms_norm_eps"]
    theta = m["rope_parameters"]["rope_theta"]
    pos = jnp.arange(s)
    c_q = _c_q(m, x, w)
    kv = _mm(x, w["self_attn.kv_a_proj_with_mqa.weight"])
    c_kv = rms(kv[:, :kvr], w["self_attn.kv_a_layernorm.weight"], eps)
    k_r = _r(_rotate(kv[:, None, kvr:], pos, theta)[:, 0])      # [S, rope]
    w_kvb = _f32(w["self_attn.kv_b_proj.weight"]).reshape(kvr, h, nope + dv)
    k_nope = _r(_ein("sk,khd->shd", c_kv, w_kvb[..., :nope]))
    v = _r(_ein("sk,khd->shd", c_kv, w_kvb[..., nope:]))
    sink = _f32(w["self_attn.sinks"])
    qb = _blocks(s)

    def attend(block):
        cq, seen, t = block                  # [qb, q_rank], [qb, S], [qb]
        q = _mm(cq, w["self_attn.q_b_proj.weight"]).reshape(qb, h,
                                                            nope + rope)
        q_nope = _r(q[..., :nope])
        q_rope = _r(_rotate(q[..., nope:], t, theta))
        sc = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope, precision=_HI)
              + jnp.einsum("qhd,kd->hqk", q_rope, k_r, precision=_HI)) \
            / math.sqrt(nope + rope)
        sc = jnp.where(seen[None], sc, -jnp.inf)
        mx = jnp.max(sc, -1, keepdims=True)
        if "sink_dropped" in faults:
            e = jnp.exp(sc - mx)
            probs = e / jnp.sum(e, -1, keepdims=True)
        else:
            mx = jnp.maximum(mx, sink[:, None, None])
            e = jnp.exp(sc - mx)
            probs = e / (jnp.sum(e, -1, keepdims=True)
                         + jnp.exp(sink[:, None, None] - mx))
        return jnp.einsum("hqk,khd->qhd", _r(probs), v, precision=_HI)

    out = jax.lax.map(attend, (c_q.reshape(s // qb, qb, -1),
                               sel.reshape(s // qb, qb, s),
                               pos.reshape(s // qb, qb)))
    out = out.reshape(s, h, dv)
    if "gate_dropped" not in faults:
        out = jax.nn.sigmoid(_mm(x, w["self_attn.g_proj.weight"])).reshape(
            s, h, dv) * out
    return _mm(out.reshape(s, h * dv), w["self_attn.o_proj.weight"])


def swiglu(m, x, gate, up, down, faults=()):
    g, u = _mm(x, gate), _mm(x, up)
    if "clamp_removed" not in faults:
        lim = m["swiglu_limit"]
        g, u = jnp.minimum(g, lim), jnp.clip(u, -lim, lim)
    return _mm(jax.nn.silu(g) * u, down)


def route(m: dict, x, w_router, bias, n_routed: int):
    """x [S, hidden] -> dense gate weights [S, n_routed] (0 where not
    chosen)."""
    k = m["num_experts_per_tok"]
    s = jax.nn.sigmoid(jnp.dot(_f32(x), _f32(w_router), precision=_HI))
    idx = jnp.argsort(-(s + _f32(bias)), axis=-1, stable=True)[:, :k]
    gates = jnp.take_along_axis(s, idx, axis=-1)
    gates = gates / jnp.sum(gates, -1, keepdims=True) \
        * m["routed_scaling_factor"]
    return jnp.zeros((x.shape[0], n_routed), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], idx].add(gates)


def _expert_block(m, x, gate, up, down, weight, faults):
    """sum over a block of held experts of weight[:, e] * expert_e(x)."""
    out = 0.0
    for e in range(gate.shape[0]):
        out = out + weight[:, e:e + 1] * swiglu(m, x, gate[e], up[e],
                                                down[e], faults)
    return out


# One compiled program a piece of the forward pass, configuration, layer kind,
# planted faults and rounding: op by op the chip would compile hundreds of
# small programs for every new context length
_PIECES = {}


def _piece(name, m, fn, *static, donate=()):
    key = (name, json.dumps(m, sort_keys=True, default=str), static,
           str(_ROUND_TO[0]))
    if key not in _PIECES:
        _PIECES[key] = jax.jit(fn, donate_argnums=donate)
    return _PIECES[key]


def _layer_weights(p: dict, i: int) -> dict:
    pre = f"model.layers.{i}."
    return {k[len(pre):]: jnp.asarray(v) for k, v in p.items()
            if k.startswith(pre)}


def hidden_states(m: dict, p: dict, ids, faults=(), keep=None):
    """Final-norm input of every position of `ids` [S]. `keep`, a list,
    receives each `full` layer's selection mask ([S, S], numpy bool)."""
    faults = tuple(faults)
    held = tuple(m["deployment"]["held"])
    n_routed = m["published"]["n_routed_experts"] \
        if "n_routed_experts" in m.get("published", {}) \
        else m["n_routed_experts"]
    eps = m["rms_norm_eps"]
    emb = jnp.asarray(p["model.embed_tokens.weight"])[jnp.asarray(ids)]
    x = jnp.broadcast_to(_f32(emb)[:, None, :],
                         (len(ids), m["hc_mult"], emb.shape[-1]))
    sel, idx_w = None, None
    for i, (dsa, mlp) in enumerate(layer_kinds(m)):
        w = _layer_weights(p, i)
        if dsa == "full":
            idx_w = {k: v for k, v in w.items() if ".indexer." in k}

        def select(x, w, idx_w):
            u, _ = _hc(m, x, w, "attn_hc.", faults)
            a = rms(u, w["input_layernorm.weight"], eps)
            return selection(m, a, _c_q(m, a, w), {**w, **idx_w}, faults)

        if dsa == "full" or "shared_layers_recompute" in faults:
            sel = _piece("select", m, select, faults)(x, w, idx_w)
            if keep is not None and dsa == "full":
                keep.append(np.asarray(sel))

        def attn_part(x, w, sel):
            u, join = _hc(m, x, w, "attn_hc.", faults)
            y = attention(m, rms(u, w["input_layernorm.weight"], eps), w,
                          sel, faults)
            return join(y)

        x = _piece("attention", m, attn_part, faults, donate=0)(x, w, sel)

        def mlp_in(x, w):
            u, _ = _hc(m, x, w, "mlp_hc.", faults)
            return rms(u, w["post_attention_layernorm.weight"], eps)

        b = _piece("mlp_in", m, mlp_in, faults)(x, w)
        if mlp == "dense":
            y = _piece("dense", m, lambda b, w: swiglu(
                m, b, w["mlp.gate_proj.weight"], w["mlp.up_proj.weight"],
                w["mlp.down_proj.weight"], faults), faults)(b, w)
        else:
            weight = _piece("route", m, lambda b, w: route(
                m, b, w["mlp.gate.weight"],
                w["mlp.gate.e_score_correction_bias"], n_routed))(b, w)
            y = _piece("shared", m, lambda b, w: swiglu(
                m, b, w["mlp.shared_experts.gate_proj.weight"],
                w["mlp.shared_experts.up_proj.weight"],
                w["mlp.shared_experts.down_proj.weight"], faults),
                faults)(b, w)
            block = _piece("experts", m, lambda b, g, u, d, wt: _expert_block(
                m, b, g, u, d, wt, faults), faults)
            for a in range(0, len(held), EXPERT_BLOCK):
                blk = slice(a, a + EXPERT_BLOCK)
                y = y + block(b, w["mlp.experts.gate_proj"][blk],
                              w["mlp.experts.up_proj"][blk],
                              w["mlp.experts.down_proj"][blk],
                              weight[:, list(held[blk])])

        def mlp_out(x, w, y):
            _, join = _hc(m, x, w, "mlp_hc.", faults)
            return join(y)

        x = _piece("mlp_out", m, mlp_out, faults, donate=0)(x, w, y)
        del w
    return jnp.sum(x, axis=1)


def head_logits(m: dict, p: dict, h):
    """f32 logits [len(h), vocab] of final-norm inputs `h`."""
    chunk = _piece("head", m, lambda h, g, w: _mm(
        rms(h, g, m["rms_norm_eps"]), w))
    return chunk(h, jnp.asarray(p["model.norm.weight"]),
                 jnp.asarray(p["lm_head.weight"]))


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
    that deviation. float64 arrays, one entry a token."""
    h = hidden_states(m, p, ids, faults)
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
