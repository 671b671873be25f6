"""The plain reference the system is held to, and the sizing rule of the
serving deployment. Copied from chip_smoke.py (PR 22, ran on the chip) so that
later PRs may change chip_smoke.py but not the yardstick. Independent of
paddle_tpu's model code: embedding, one jitted f32 layer, final rms, head —
true f32 matmuls (`Precision.HIGHEST`), weights upcast one layer at a time.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# first generated token vs the f32 reference: a disagreement is accepted only
# where the reference itself is a near-tie — its top logit leads the engine's
# choice by less than this fraction of the logits' standard deviation. bf16
# rounding through the stack moves a logit by a few hundredths of that
# deviation; a wrong rotary table or mask moves the argmax by several of them.
BF16_TIE_TOL = 0.1

# device memory kept clear of weights and pools when the serving depth is
# chosen: the step programs' temporaries (the unified step copies the KV
# pools: 2.79 GiB at depth 23 with a 36,864-token pool, next to 0.26 GiB the
# runtime reserves — the chip's compiler, PR 24), the f32 reference's
# per-layer weight copies (~0.9 GB at 7B widths), activations and logits
SERVE_HEADROOM_BYTES = int(3.5 * 2**30)

_HI = jax.lax.Precision.HIGHEST


def _mm(x, w):
    return jnp.dot(x, w.astype(jnp.float32), precision=_HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("nh", "nkv", "dh", "eps",
                                             "theta"))
def _reference_layer(h, w, *, nh, nkv, dh, eps, theta):
    """One decoder layer of the reference, [S, H] f32 in and out: rms,
    rotary (rotate-half), causal GQA softmax attention, SwiGLU."""
    s = h.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

    def rope(x):
        x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    x = _rms(h, w["input_layernorm.weight"], eps)
    q = rope(_mm(x, w["self_attn.q_proj.weight"]).reshape(s, nh, dh))
    k = rope(_mm(x, w["self_attn.k_proj.weight"]).reshape(s, nkv, dh))
    v = _mm(x, w["self_attn.v_proj.weight"]).reshape(s, nkv, dh)
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", pr, v, precision=_HI)
    h = h + _mm(a.reshape(s, nh * dh), w["self_attn.o_proj.weight"])
    x = _rms(h, w["post_attention_layernorm.weight"], eps)
    gate = _mm(x, w["mlp.gate_proj.weight"])
    return h + _mm(jax.nn.silu(gate) * _mm(x, w["mlp.up_proj.weight"]),
                   w["mlp.down_proj.weight"])


def _geom(m: dict) -> dict:
    return dict(nh=m["num_attention_heads"], nkv=m["num_key_value_heads"],
                dh=m["head_dim"], eps=m["rms_norm_eps"],
                theta=m["rope_theta"])


def _hidden(m: dict, p: dict, ids):
    """Final-norm input of every position of the (right-padded) ids."""
    h = p["llama.embed_tokens.weight"][jnp.asarray(ids)].astype(jnp.float32)
    for i in range(m["num_hidden_layers"]):
        pre = f"llama.layers.{i}."
        h = _reference_layer(h, {k[len(pre):]: v for k, v in p.items()
                                 if k.startswith(pre)}, **_geom(m))
    return h


def _head(p: dict):
    return p["lm_head.weight"] if "lm_head.weight" in p \
        else p["llama.embed_tokens.weight"].T


def reference_last_logits(m: dict, p: dict, ids, n_real: int):
    """f32 logits at position n_real - 1 of the forward pass of the weights
    `p` (names as `raw_state()` gives them) over the right-padded `ids`.
    `m` holds the sizes under their published keys."""
    h = _hidden(m, p, ids)
    h = _rms(h[n_real - 1][None], p["llama.norm.weight"], m["rms_norm_eps"])
    head = _head(p)
    # the head in vocab chunks: the whole [H, V] matrix in f32 would be a
    # large transient next to a serving deployment's pools
    step = -(-head.shape[1] // 8)
    return jnp.concatenate([_mm(h, head[:, a:a + step])[0]
                            for a in range(0, head.shape[1], step)])


@functools.partial(jax.jit, static_argnames=("eps",))
def _ce_rows(h, norm_w, head, labels, *, eps):
    lg = _mm(_rms(h, norm_w, eps), head)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    return jnp.sum(lse - jnp.take_along_axis(lg, labels[:, None], -1)[:, 0])


def reference_cross_entropy(m: dict, p: dict, ids, labels) -> float:
    """Mean f32 cross-entropy of `labels` [B, S] under the reference's
    forward pass over `ids` [B, S], one sequence and 512 rows at a time."""
    total, rows = 0.0, 512
    for seq, lab in zip(ids, labels):
        h = _hidden(m, p, seq)
        for a in range(0, h.shape[0], rows):
            total += float(_ce_rows(
                h[a:a + rows], p["llama.norm.weight"], _head(p),
                jnp.asarray(lab[a:a + rows], jnp.int32),
                eps=m["rms_norm_eps"]))
    return total / (len(ids) * len(ids[0]))


def serve_depth(m: dict, bytes_limit: int, pool_tokens: int) -> int:
    """Deepest stack of `m`'s layers whose bf16 weights and KV pool pages
    (`pool_tokens` cached tokens per layer), next to the embedding and head,
    leave SERVE_HEADROOM_BYTES of `bytes_limit` free — depth is cut only as
    far as the device's memory forces."""
    h, im = m["hidden_size"], m["intermediate_size"]
    qkvo = h * m["head_dim"] * 2 * (m["num_attention_heads"]
                                    + m["num_key_value_heads"])
    pool = 2 * pool_tokens * m["num_key_value_heads"] * m["head_dim"]
    per_layer = 2 * (qkvo + 3 * h * im + 2 * h + pool)
    fixed = 2 * m["vocab_size"] * h * (1 if m["tie_word_embeddings"] else 2)
    return int((bytes_limit - SERVE_HEADROOM_BYTES - fixed) // per_layer)


def tie_gap(ref_logits, token: int) -> float:
    """How far the reference's top logit leads `token`'s, in units of the
    logits' standard deviation (0 when `token` is the argmax)."""
    return float(ref_logits.max() - ref_logits[token]) / float(
        ref_logits.std())
