"""Attention functionals.

Reference: python/paddle/nn/functional/flash_attention.py:198 (flash_attention
op family, ops.yaml:1765-1777) and scaled_dot_product_attention. On TPU the
fused path is a Pallas flash-attention kernel
(paddle_tpu/kernels/flash_attention.py); a jnp reference path covers CPU
tests and odd shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.tensor import Tensor, dispatch, unwrap
from ...framework.flags import flag

__all__ = ["scaled_dot_product_attention", "flash_attention", "flash_attn_unpadded", "sdp_kernel"]


def _sdpa_ref(q, k, v, mask, dropout_p, causal, scale=None, window=None):
    """Reference attention in fp32 accumulation. q/k/v: [B, S, H, D] (paddle
    flash_attn layout). `window` (causal only): row t sees keys s with
    0 <= t - s < window."""
    qt = jnp.swapaxes(q, 1, 2)  # [B,H,S,D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    hq, hk = qt.shape[1], kt.shape[1]
    if hk != hq:  # GQA/MQA: repeat kv heads
        rep = hq // hk
        kt = jnp.repeat(kt, rep, axis=1)
        vt = jnp.repeat(vt, rep, axis=1)
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt).astype(jnp.float32) * s
    if causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
        if window is not None:
            cm = cm & jnp.triu(jnp.ones((ql, kl), bool),
                               k=kl - ql - (window - 1))
        logits = jnp.where(cm, logits, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -1e30)
        else:
            logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    return jnp.swapaxes(out, 1, 2)  # back to [B,S,H,D]


def flash_attention(query, key, value, dropout=0.0, causal=False, return_softmax=False,
                    fixed_seed_offset=None, rng_name="", training=True, name=None,
                    window=None):
    """paddle.nn.functional.flash_attention.flash_attention.

    Layout [batch, seqlen, num_heads, head_dim] (ref ops.yaml:1765 flash_attn).
    Uses the Pallas kernel pack for the no-dropout path (the flag and the
    dropout rate decide, before the call: nothing the kernel raises is
    caught). `window` (with `causal=True`, equal sequence lengths): row t
    sees the keys s with 0 <= t - s < window — a sliding-window layer; the
    kernels skip the score blocks behind it.
    """
    if window is not None and not causal:
        raise ValueError("a window is causal attention: pass causal=True")
    if flag("FLAGS_enable_pallas_kernels") and dropout == 0.0:
        from ...kernels.flash_attention import flash_attention_fwd

        out = dispatch(
            "flash_attn",
            lambda q, k, v: flash_attention_fwd(q, k, v, causal=causal,
                                                window=window),
            (query, key, value),
        )
        return out, None
    out = dispatch(
        "flash_attn_ref",
        lambda q, k, v: _sdpa_ref(q, k, v, None, dropout, causal,
                                  window=window),
        (query, key, value),
    )
    return out, None


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, name=None):
    """paddle.nn.functional.scaled_dot_product_attention (layout [B,S,H,D])."""
    if attn_mask is None:
        out, _ = flash_attention(query, key, value, dropout=dropout_p if training else 0.0, causal=is_causal)
        return out
    return dispatch(
        "sdpa",
        lambda q, k, v, m: _sdpa_ref(q, k, v, m, dropout_p, is_causal),
        (query, key, value, attn_mask),
    )


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k, max_seqlen_q,
                        max_seqlen_k, scale, dropout=0.0, causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True, name=None):
    """Varlen flash attention (ref: flash_attn_unpadded, ops.yaml:1779).
    Implemented by segment-masked attention over the packed sequence."""

    def impl(q, k, v, cq, ck):
        # q: [total_q, H, D]; build segment ids from cu_seqlens
        total_q = q.shape[0]
        seg_q = jnp.cumsum(jnp.zeros(total_q, jnp.int32).at[cq[1:-1]].add(1))
        total_k = k.shape[0]
        seg_k = jnp.cumsum(jnp.zeros(total_k, jnp.int32).at[ck[1:-1]].add(1))
        logits = jnp.einsum("qhd,khd->hqk", q, k).astype(jnp.float32) * scale
        mask = seg_q[:, None] == seg_k[None, :]
        if causal:
            pos_q = jnp.arange(total_q) - jnp.take(cq, seg_q)
            pos_k = jnp.arange(total_k) - jnp.take(ck, seg_k)
            mask = mask & (pos_q[:, None] >= pos_k[None, :])
        logits = jnp.where(mask[None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = dispatch("flash_attn_unpadded", impl, (query, key, value, cu_seqlens_q, cu_seqlens_k))
    return out, None


def sdp_kernel(*args, **kwargs):
    import contextlib

    @contextlib.contextmanager
    def _noop():
        yield

    return _noop()
