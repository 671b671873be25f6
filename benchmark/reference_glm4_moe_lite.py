"""The plain reference of the `glm4_moe_lite` block (GLM-4.7-Flash; the block
is DeepSeek-V3's, arXiv:2412.19437 sections 2.1-2.2): latent attention, a
bias-balanced sigmoid router over all the published experts, the part of the
expert layer that the experts HELD here give, a shared expert, and one
multi-token-prediction module; both losses and their gradients.

Plain `jax.numpy`, float32, true f32 matmuls (`Precision.HIGHEST`), no kernel,
experts as a loop over `held` with masks. It imports nothing of paddle_tpu.
Weights come under the names `raw_state()` gives them (listed in `NAMES`); `m`
holds the sizes under their published keys, the router's width under
`published.n_routed_experts`, and what the source does not settle under
`assumed`. Attention runs one head at a time and every layer is
rematerialised in the backward pass, so that 4,096 tokens fit beside the
model on one chip; neither changes a value.

Departures from the published description, all listed in the configuration's
file: what the experts that are not held would add is left out (the chip's
share, model-configs guide section 4), and where that leaves a share of the
experts the gates are constants of the backward pass (their gradient needs
every chosen expert's output); rotary pairs are the two halves of the rope
part; the MTP module's input is [norm(h) ; norm(emb)] in that order.
"""
from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST

NAMES = """
model.embed_tokens.weight [V, H]          lm_head.weight [H, V]
model.norm.weight [H]
<block> = model.layers.<i> | mtp.<k>.block:
  <block>.input_layernorm.weight, <block>.post_attention_layernorm.weight [H]
  <block>.self_attn.q_a_proj.weight [H, q_lora_rank]
  <block>.self_attn.q_a_layernorm.weight [q_lora_rank]
  <block>.self_attn.q_b_proj.weight [q_lora_rank, heads * (nope + rope)]
  <block>.self_attn.kv_a_proj_with_mqa.weight [H, kv_lora_rank + rope]
  <block>.self_attn.kv_a_layernorm.weight [kv_lora_rank]
  <block>.self_attn.kv_b_proj.weight [kv_lora_rank, heads * (nope + v)]
  <block>.self_attn.o_proj.weight [heads * v, H]
  dense (i < first_k_dense_replace): <block>.mlp.{gate,up,down}_proj.weight
  expert: <block>.mlp.gate.weight [H, E], <block>.mlp.gate.e_score_correction_bias [E]
          <block>.mlp.experts.{gate_proj,up_proj} [held, H, F], .down_proj [held, F, H]
          <block>.mlp.shared_experts.{gate,up,down}_proj.weight
mtp.<k>.hnorm.weight, mtp.<k>.enorm.weight [H], mtp.<k>.eh_proj.weight [2H, H],
mtp.<k>.norm.weight [H]
"""


_ROUND_TO = [None]


@contextlib.contextmanager
def lower_precision(dtype):
    """The control of the comparison that decides `correct`: inside, every
    matmul's operands are rounded to `dtype` first (the products still
    accumulate in f32), as a run in that precision would round them. What it
    gives has to fail the limits a run in the stated precision passes."""
    _ROUND_TO[0] = dtype
    try:
        yield
    finally:
        _ROUND_TO[0] = None


def _f32(w):
    return jnp.asarray(w).astype(jnp.float32)


def _mm(x, w):
    x, w = _f32(x), _f32(w)
    if _ROUND_TO[0] is not None:
        x, w = (_f32(t.astype(_ROUND_TO[0])) for t in (x, w))
    return jnp.dot(x, w, precision=_HI)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def swiglu(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def _sub(p: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def router_width(m: dict) -> int:
    return m.get("published", {}).get("n_routed_experts",
                                      m["n_routed_experts"])


def rope(x, theta: float):
    """Rotary embedding over the whole last axis of x [S, ..., d]: pairs are
    (x[i], x[i + d/2]), position = row."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def latent_attention(m: dict, w: dict, x):
    """Multi-head latent attention of x [S, H] (already normed): low-rank q
    and kv projections, a rotary part of `qk_rope_head_dim` that the keys of
    all heads share, causal softmax over sqrt(nope + rope)."""
    s = x.shape[0]
    nh, eps = m["num_attention_heads"], m["rms_norm_eps"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    c_q = rms(_mm(x, w["q_a_proj.weight"]), w["q_a_layernorm.weight"], eps)
    q = _mm(c_q, w["q_b_proj.weight"]).reshape(s, nh, dn + dr)
    kva = _mm(x, w["kv_a_proj_with_mqa.weight"])
    c_kv = rms(kva[:, :m["kv_lora_rank"]], w["kv_a_layernorm.weight"], eps)
    k_r = rope(kva[:, m["kv_lora_rank"]:], m["rope_theta"])        # [S, dr]
    kv = _mm(c_kv, w["kv_b_proj.weight"]).reshape(s, nh, dn + dv)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], m["rope_theta"])],
                        -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, None], (s, nh, dr))], -1)
    v = kv[..., dn:]
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv
        sc = _mm(qh, kh.T) / math.sqrt(dn + dr)
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return _mm(pr, vh)

    out = jax.lax.map(head, tuple(jnp.swapaxes(t, 0, 1) for t in (q, k, v)))
    return _mm(jnp.swapaxes(out, 0, 1).reshape(s, nh * dv),
               w["o_proj.weight"])


def route(m: dict, x, w_r, bias, choice=None):
    """(experts [T, k] chosen by sigmoid score + bias, gates [T, k] from the
    score alone, normalised and scaled, load [E] = tokens routed to each,
    the router's own choice). `choice` [T, k], where given and not negative,
    takes the place of the router's own choice in all but the last result:
    the comparison that decides `correct` hands the reference the program's
    routing, so that a near-tie rounded the other way is counted as a flip
    and not as an error of everything computed after it."""
    s = jax.nn.sigmoid(_mm(x, w_r))
    _, own = jax.lax.top_k(s + _f32(bias), m["num_experts_per_tok"])
    idx = own if choice is None else jnp.where(choice >= 0, choice, own)
    g = jnp.take_along_axis(s, idx, -1)
    if m["norm_topk_prob"]:
        g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20)
    g = g * m["routed_scaling_factor"]
    load = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=jnp.float32),
                   axis=(0, 1))
    return idx, g, load, own


def bias_update(bias, load, rate: float):
    """The auxiliary-loss-free balancing rule: after a step, an expert that
    saw less than the mean load is made likelier by `rate`, one that saw more
    less likely."""
    return _f32(bias) + rate * jnp.sign(jnp.mean(load) - load)


def routed_experts(m: dict, w: dict, x, held, choice=None):
    """What the experts in `held` (global indices; `w`'s stacked weights are
    in that order) add for the tokens routed to them, the loads and the
    router's own choice: a loop over the held experts, each computing every
    token and masked to its own (a `scan`, so that the compiler sees one
    expert's program and not eight)."""
    idx, g, load, own = route(m, x, w["gate.weight"],
                              w["gate.e_score_correction_bias"], choice)
    if len(held) < router_width(m):
        # a share of the experts gives a share of the gates' gradient: none
        # of it reaches the router (the module docstring's departures)
        g = jax.lax.stop_gradient(g)

    def add_expert(y, expert):
        e, gate, up, down = expert
        gate_e = jnp.sum(jnp.where(idx == e, g, 0.0), -1, keepdims=True)
        return y + gate_e * swiglu(x, gate, up, down), None

    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (jnp.asarray(held, jnp.int32), w["experts.gate_proj"],
         w["experts.up_proj"], w["experts.down_proj"]))
    return y, load, own


def shared_expert(w: dict, x):
    return swiglu(x, w["shared_experts.gate_proj.weight"],
                  w["shared_experts.up_proj.weight"],
                  w["shared_experts.down_proj.weight"])


def expert_layer(m: dict, w: dict, x, held, choice=None):
    y, load, own = routed_experts(m, w, x, held, choice)
    return shared_expert(w, x) + y, load, own


def block(m: dict, w: dict, h, choice=None, *, held, dense: bool):
    """One pre-norm decoder block, [S, H] in and out, its router's load and
    own choice (None for the dense block)."""
    eps = m["rms_norm_eps"]
    h = h + latent_attention(m, _sub(w, "self_attn."),
                             rms(h, w["input_layernorm.weight"], eps))
    x = rms(h, w["post_attention_layernorm.weight"], eps)
    if dense:
        return h + swiglu(x, w["mlp.gate_proj.weight"],
                          w["mlp.up_proj.weight"],
                          w["mlp.down_proj.weight"]), None, None
    y, load, own = expert_layer(m, _sub(w, "mlp."), x, held, choice)
    return h + y, load, own


def _block(m, p, prefix, h, held, dense, choice=None):
    fn = jax.checkpoint(functools.partial(block, m, held=tuple(held),
                                          dense=dense))
    return fn(_sub(p, prefix), h, choice)


def mtp_input(m: dict, w: dict, h, emb):
    """[norm(h) ; norm(emb of the next token)] W_eh."""
    eps = m["rms_norm_eps"]
    return _mm(jnp.concatenate([rms(h, w["hnorm.weight"], eps),
                                rms(emb, w["enorm.weight"], eps)], -1),
               w["eh_proj.weight"])


def cross_entropy(logits, labels):
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(
        logits, labels[:, None], -1)[:, 0])


def _head_ce(h, head, labels, rows=512):
    """Mean cross-entropy of `labels` under h @ head, at most `rows` rows at
    a time: the [S, V] logits in f32 are never whole."""
    s = h.shape[0]
    rows = max(r for r in range(1, min(rows, s) + 1) if s % r == 0)
    block = jax.checkpoint(lambda hl: cross_entropy(_mm(hl[0], head), hl[1]))
    return jnp.mean(jax.lax.map(block, (h.reshape(s // rows, rows, -1),
                                        labels.reshape(s // rows, rows))))


def forward(m: dict, p: dict, row, held, positions=None, choice=None):
    """The whole model on ONE row of S + D token ids (D =
    `num_nextn_predict_layers`): inputs row[:S], the main head's labels
    row[1:S+1] (module k embeds row[1+k:S+1+k]), module k's labels
    row[2+k:S+2+k]. Returns the two losses, the total, each expert block's
    load [blocks, E] and router's own choice `moe.choice` [blocks, S, k]
    (main stack, then the modules), and at `positions` the f32 logits of the
    main head and of the last module's. `choice` [blocks, S, k]: the routing
    to compute under in place of the routers' own (`route`)."""
    depth = m["num_nextn_predict_layers"]
    row = jnp.asarray(row, jnp.int32)
    s = row.shape[0] - 1 - depth
    eps = m["rms_norm_eps"]
    emb = p["model.embed_tokens.weight"]
    h = _f32(emb[row[:s]])
    loads, chosen = [], []

    def run_block(prefix, h, dense):
        h, load, own = _block(
            m, p, prefix, h, held, dense,
            None if choice is None or dense else choice[len(loads)])
        if not dense:
            loads.append(load)
            chosen.append(own)
        return h

    for i in range(m["num_hidden_layers"]):
        h = run_block(f"model.layers.{i}.", h, i < m["first_k_dense_replace"])
    head = p["lm_head.weight"]
    hn = rms(h, p["model.norm.weight"], eps)
    out = {"loss.main": _head_ce(hn, head, row[1:s + 1])}
    if positions is not None:
        out["logits.main"] = _mm(hn[jnp.asarray(positions)], head)
    mtp_losses = []
    for k in range(depth):
        w = _sub(p, f"mtp.{k}.")
        h = mtp_input(m, w, h, _f32(emb[row[1 + k:s + 1 + k]]))
        h = run_block(f"mtp.{k}.block.", h, False)
        hn = rms(h, w["norm.weight"], eps)
        mtp_losses.append(_head_ce(hn, head, row[2 + k:s + 2 + k]))
        if positions is not None and k == depth - 1:
            out["logits.mtp"] = _mm(hn[jnp.asarray(positions)], head)
    out["loss.mtp"] = sum(mtp_losses) / depth if depth else \
        jnp.zeros((), jnp.float32)
    out["loss"] = out["loss.main"] \
        + m["assumed"]["mtp_loss_weight"] * out["loss.mtp"]
    out["moe.load"] = jnp.stack(loads)
    out["moe.choice"] = jnp.stack(chosen)
    return out


def forward_and_grads(m: dict, p: dict, row, held, names, positions=None,
                      choice=None):
    """`forward`, and d loss / d p[name] for each of `names`, in f32."""
    held = tuple(int(e) for e in held)

    @jax.jit
    def run(wrt, rest, row, positions, choice):
        # `positions` and `choice` are arguments: one program whatever the
        # seed drew, and whether or not a routing is handed in
        def loss(wrt):
            out = forward(m, {**rest, **wrt}, row, held, positions, choice)
            return out["loss"], out

        (_, out), grads = jax.value_and_grad(loss, has_aux=True)(wrt)
        return out, grads

    wrt = {k: _f32(p[k]) for k in names}
    rest = {k: v for k, v in p.items() if k not in wrt}
    blocks = m["num_hidden_layers"] - m["first_k_dense_replace"] \
        + m["num_nextn_predict_layers"]
    own = jnp.full((blocks, len(row) - 1 - m["num_nextn_predict_layers"],
                    m["num_experts_per_tok"]), -1, jnp.int32)
    return run(wrt, rest, jnp.asarray(row, jnp.int32),
               None if positions is None
               else jnp.asarray(positions, jnp.int32),
               own if choice is None else jnp.asarray(choice, jnp.int32))
