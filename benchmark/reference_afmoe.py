"""The plain reference of the `afmoe` block (Arcee Trinity-Mini): a muP-scaled
embedding, four RMSNorms a layer, grouped attention with q/k norms and a
sigmoid gate on its output, window layers (rotary, keys 0 <= t - s < window)
beside full layers (causal, NOT rotated), a bias-balanced sigmoid router over
all the published experts, the part of the expert layer that the experts HELD
here give, a shared expert; the next-token loss and its gradients.

Plain `jax.numpy`, float32, true f32 matmuls (`Precision.HIGHEST`), no kernel,
attention as an explicit masked softmax, experts as a loop over `held` with
masks. It imports nothing of paddle_tpu. Weights come under the names
`raw_state()` gives them (listed in `NAMES`); `m` holds the sizes under their
published keys, the router's width under `published.num_experts`, and what the
source does not settle under `assumed`. Attention runs one head and
`QUERY_ROWS` queries at a time and every layer is rematerialised in the
backward pass, so that 8,192 tokens fit beside the model on one chip; neither
changes a value.

Departures from the published description, all listed in the configuration's
file: what the experts that are not held would add is left out (the chip's
share, model-configs guide section 4), and where that leaves a share of the
experts the gates are constants of the backward pass (their gradient needs
every chosen expert's output); rotary pairs are the two halves of the head.
What `config.json` has no key for (the four norms, the q/k norms, the gate,
no rotary on full layers, sqrt(hidden) on the embedding) is the public
reference implementation of `model_type: afmoe`.
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
<block> = model.layers.<i>:
  <block>.{input,post_attention,pre_mlp,post_mlp}_layernorm.weight [H]
  <block>.self_attn.{q,gate}_proj.weight [H, heads * head_dim]
  <block>.self_attn.{k,v}_proj.weight [H, kv_heads * head_dim]
  <block>.self_attn.{q,k}_norm.weight [head_dim]
  <block>.self_attn.o_proj.weight [heads * head_dim, H]
  dense (i < num_dense_layers): <block>.mlp.{gate,up,down}_proj.weight
  expert: <block>.mlp.gate.weight [H, E], <block>.mlp.gate.e_score_correction_bias [E]
          <block>.mlp.experts.{gate_proj,up_proj} [held, H, F], .down_proj [held, F, H]
          <block>.mlp.shared_experts.{gate,up,down}_proj.weight
"""

# queries of one head whose scores are whole at a time: [1024, S] in f32
QUERY_ROWS = 1024

# what can be planted in the reference, one at a time (`planted`): each is a
# piece of the block's mathematics left out or done the other layer kind's
# way, and each has to fail the comparison that decides `correct`
FAULTS = ("window_layers_full", "full_layers_rotated", "attn_gate_dropped",
          "qk_norm_dropped", "mup_scale_dropped", "sandwich_norms_dropped",
          "route_scale_dropped", "shared_expert_dropped", "top_k_less_one")

_ROUND_TO = [None]
_FAULT = [None]


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


@contextlib.contextmanager
def planted(fault: str):
    """Inside, the reference computes with `fault` (one of `FAULTS`)."""
    if fault not in FAULTS:
        raise ValueError(f"no fault {fault!r} ({FAULTS})")
    _FAULT[0] = fault
    try:
        yield
    finally:
        _FAULT[0] = None


def _is(fault: str) -> bool:
    return _FAULT[0] == fault


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
    return m.get("published", {}).get("num_experts", m["num_experts"])


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


def attention(m: dict, w: dict, x, kind: str):
    """Gated, q/k-normed grouped attention of x [S, H] (already normed).
    `kind` is the layer's entry of `layer_types`: `sliding_attention` rotates
    q and k and sees the keys s with 0 <= t - s < `sliding_window`;
    `full_attention` sees every key up to its own and rotates nothing.
    Scale 1/sqrt(head_dim); the heads' output times sigmoid(x Wg), then Wo."""
    s = x.shape[0]
    nh, nkv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    eps = m["rms_norm_eps"]
    window = kind == "sliding_attention"
    q = _mm(x, w["q_proj.weight"]).reshape(s, nh, dh)
    k = _mm(x, w["k_proj.weight"]).reshape(s, nkv, dh)
    v = _mm(x, w["v_proj.weight"]).reshape(s, nkv, dh)
    if not _is("qk_norm_dropped"):
        q = rms(q, w["q_norm.weight"], eps)
        k = rms(k, w["k_norm.weight"], eps)
    if window or _is("full_layers_rotated"):
        q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    reach = m["sliding_window"] if window and \
        not _is("window_layers_full") else s
    rows = max(r for r in range(1, min(QUERY_ROWS, s) + 1) if s % r == 0)
    key_pos = jnp.arange(s)

    @jax.checkpoint
    def head_rows(qkv):
        qh, kh, vh, first = qkv                  # [rows, dh], [S, dh] x 2
        t = first + jnp.arange(rows)[:, None]
        seen = (t >= key_pos) & (t - key_pos < reach)
        sc = _mm(qh, kh.T) / math.sqrt(dh)
        return _mm(jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1), vh)

    def head(qkv):
        qh, kh, vh = qkv
        return jax.lax.map(
            lambda part: head_rows((part[0], kh, vh, part[1])),
            (qh.reshape(s // rows, rows, dh),
             jnp.arange(0, s, rows))).reshape(s, dh)

    kv_of = jnp.arange(nh) // (nh // nkv)        # a q head's kv head
    out = jax.lax.map(head, (jnp.swapaxes(q, 0, 1),
                             jnp.swapaxes(k, 0, 1)[kv_of],
                             jnp.swapaxes(v, 0, 1)[kv_of]))
    out = jnp.swapaxes(out, 0, 1).reshape(s, nh * dh)
    if not _is("attn_gate_dropped"):
        out = out * jax.nn.sigmoid(_mm(x, w["gate_proj.weight"]))
    return _mm(out, w["o_proj.weight"])


def route(m: dict, x, w_r, bias, choice=None):
    """(experts [T, k] chosen by sigmoid score + bias, gates [T, k] from the
    score alone, normalised (`route_norm`) and scaled (`route_scale`), load
    [E] = tokens routed to each, the router's own choice). `choice` [T, k],
    where given and not negative, takes the place of the router's own choice
    in all but the last result: the comparison that decides `correct` hands
    the reference the program's routing, so that a near-tie rounded the other
    way is counted as a flip and not as an error of everything computed
    after it."""
    s = jax.nn.sigmoid(_mm(x, w_r))
    k = m["num_experts_per_tok"]
    _, own = jax.lax.top_k(s + _f32(bias), k)
    idx = own if choice is None else jnp.where(choice >= 0, choice, own)
    g = jnp.take_along_axis(s, idx, -1)
    if _is("top_k_less_one"):
        g = g.at[:, -1].set(0.0)     # the last choice's expert adds nothing
    if m["route_norm"]:
        g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20)
    if not _is("route_scale_dropped"):
        g = g * m["route_scale"]
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
    expert's program and not sixteen)."""
    idx, g, load, own = route(m, x, w["gate.weight"],
                              w["gate.e_score_correction_bias"], choice)
    if len(held) < router_width(m):
        # a share of the experts gives a share of the gates' gradient: none
        # of it reaches the router (the module docstring's departures)
        g = jax.lax.stop_gradient(g)

    @jax.checkpoint
    def one(x, gate_e, gate, up, down):
        return gate_e * swiglu(x, gate, up, down)

    def add_expert(y, expert):
        e, gate, up, down = expert
        gate_e = jnp.sum(jnp.where(idx == e, g, 0.0), -1, keepdims=True)
        return y + one(x, gate_e, gate, up, down), None

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
    if _is("shared_expert_dropped"):
        return y, load, own
    return shared_expert(w, x) + y, load, own


def block(m: dict, w: dict, h, choice=None, *, held, kind: str,
          dense: bool):
    """One decoder block, [S, H] in and out, its router's load and own choice
    (None for a dense block): a norm before AND after each sub-block."""
    eps = m["rms_norm_eps"]
    after = (lambda y, name: y) if _is("sandwich_norms_dropped") else \
        (lambda y, name: rms(y, w[name], eps))
    h = h + after(attention(m, _sub(w, "self_attn."),
                            rms(h, w["input_layernorm.weight"], eps), kind),
                  "post_attention_layernorm.weight")
    x = rms(h, w["pre_mlp_layernorm.weight"], eps)
    if dense:
        y, load, own = swiglu(x, w["mlp.gate_proj.weight"],
                              w["mlp.up_proj.weight"],
                              w["mlp.down_proj.weight"]), None, None
    else:
        y, load, own = expert_layer(m, _sub(w, "mlp."), x, held, choice)
    return h + after(y, "post_mlp_layernorm.weight"), load, own


def _block(m, p, i, h, held, choice=None):
    fn = jax.checkpoint(functools.partial(
        block, m, held=tuple(held), kind=m["layer_types"][i],
        dense=i < m["num_dense_layers"]))
    return fn(_sub(p, f"model.layers.{i}."), h, choice)


def cross_entropy(logits, labels):
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(
        logits, labels[:, None], -1)[:, 0])


def _head_ce(h, head, labels, rows=512):
    """Mean cross-entropy of `labels` under h @ head, at most `rows` rows at
    a time: the [S, V] logits in f32 are never whole."""
    s = h.shape[0]
    rows = max(r for r in range(1, min(rows, s) + 1) if s % r == 0)
    part = jax.checkpoint(lambda hl: cross_entropy(_mm(hl[0], head), hl[1]))
    return jnp.mean(jax.lax.map(part, (h.reshape(s // rows, rows, -1),
                                       labels.reshape(s // rows, rows))))


def forward(m: dict, p: dict, row, held, positions=None, choice=None):
    """The whole model on ONE row of S + 1 token ids: inputs row[:S], labels
    row[1:]. Returns the loss, each expert block's load [blocks, E] and
    router's own choice `moe.choice` [blocks, S, k], and at `positions` the
    f32 logits. `choice` [blocks, S, k]: the routing to compute under in
    place of the routers' own (`route`)."""
    row = jnp.asarray(row, jnp.int32)
    s = row.shape[0] - 1
    h = _f32(p["model.embed_tokens.weight"][row[:s]])
    if m["mup_enabled"] and not _is("mup_scale_dropped"):
        h = h * math.sqrt(m["hidden_size"])
    loads, chosen = [], []
    for i in range(m["num_hidden_layers"]):
        dense = i < m["num_dense_layers"]
        h, load, own = _block(
            m, p, i, h, held,
            None if choice is None or dense else choice[len(loads)])
        if not dense:
            loads.append(load)
            chosen.append(own)
    hn = rms(h, p["model.norm.weight"], m["rms_norm_eps"])
    out = {"loss": _head_ce(hn, p["lm_head.weight"], row[1:])}
    if positions is not None:
        out["logits"] = _mm(hn[jnp.asarray(positions)], p["lm_head.weight"])
    if loads:
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
    blocks = m["num_hidden_layers"] - min(m["num_dense_layers"],
                                          m["num_hidden_layers"])
    own = jnp.full((blocks, len(row) - 1, m["num_experts_per_tok"]), -1,
                   jnp.int32)
    return run(wrt, rest, jnp.asarray(row, jnp.int32),
               None if positions is None
               else jnp.asarray(positions, jnp.int32),
               own if choice is None else jnp.asarray(choice, jnp.int32))
