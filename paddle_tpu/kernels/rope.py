"""Rotary position embedding (RoPE), fused.

TPU-native counterpart of fused_rotary_position_embedding
(paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu; python surface
python/paddle/incubate/nn/functional/fused_rotary_position_embedding.py).
Pure jnp: the rotate+multiply is bandwidth-bound elementwise work that XLA
fuses into neighbouring ops on TPU — a dedicated Pallas kernel buys nothing
here (the reference needed CUDA fusion because its eager mode launches one
kernel per op; XLA does not).

Uses the paddle/neox "rotate_half" convention: pairs are (x[..., :d/2],
x[..., d/2:]) when use_neox_rotary_style else interleaved even/odd lanes.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax.numpy as jnp

from .constraints import KernelConstraint, LANE, register_constraint

# the rotate-half contract every rope consumer shares: head_dim splits
# into two PAIRED halves of HALF_PAIR * (dh // 2) lanes each — an odd
# head_dim cannot be rotated
HALF_PAIR = 2

# Registered so the kernels/ TPU102 inventory covers every module: rope
# itself is pure jnp (XLA fuses the rotate+multiply; no pallas_call
# exists to lint), so `kernel_fns` is empty and the entry documents the
# layout contract.
CONSTRAINT = register_constraint(KernelConstraint(
    name="rope",
    kernel_fns=(),
    blocks={"half_pair": HALF_PAIR, "lane": LANE},
    note="rotary tables are [S, head_dim/2] (neox rotate-half pairs); "
         "head_dim must be even",
    source="rope.py",
))


class YarnScaling(NamedTuple):
    """YaRN (arXiv:2309.00071) as a published `rope_parameters` group of
    `rope_type: yarn` states it. Dimension pairs that turn more than
    `beta_fast` times over the original context keep their frequency, those
    that turn fewer than `beta_slow` times are slowed by `factor`, a linear
    ramp between whole pairs; cos and sin carry `attention_factor`."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


def rope_inv_freq(head_dim: int, base: float = 10000.0,
                  scaling: Optional[YarnScaling] = None):
    """(inverse frequencies [D/2] f32, the factor on cos and sin)."""
    pos_freq = base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                        / head_dim)
    if scaling is None:
        return 1.0 / pos_freq, 1.0

    def turns_dim(turns):
        # the (fractional) pair index that turns `turns` times over the
        # original context
        return head_dim * math.log(
            scaling.original_max_position_embeddings
            / (turns * 2 * math.pi)) / (2 * math.log(base))

    # the ramp's ends, floored and ceiled to whole pairs
    low = max(math.floor(turns_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(turns_dim(scaling.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    inv = ramp / (scaling.factor * pos_freq) + (1.0 - ramp) / pos_freq
    return inv, float(scaling.attention_factor)


def rope_freqs(seq_len: int, head_dim: int, base: float = 10000.0,
               position_ids=None, dtype=jnp.float32,
               scaling: Optional[YarnScaling] = None):
    """cos/sin tables [S, D/2] (fp32 for accuracy, cast at apply)."""
    inv, factor = rope_inv_freq(head_dim, base, scaling)
    pos = (jnp.arange(seq_len, dtype=jnp.float32)
           if position_ids is None else position_ids.astype(jnp.float32))
    # broadcast multiply, NOT einsum: the outer product would lower to
    # a dot_general and ride the decode step's kernels_per_step count
    freqs = pos[..., None] * inv
    cos, sin = jnp.cos(freqs), jnp.sin(freqs)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    return cos.astype(dtype), sin.astype(dtype)


def _rotate_neox(x, cos, sin):
    # x: [..., S, H, D]; cos/sin: [S, D/2] or [..., S, D/2]
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    cos = jnp.expand_dims(cos, -2)  # broadcast over heads
    sin = jnp.expand_dims(sin, -2)
    while cos.ndim < x.ndim:
        cos = cos[None]
        sin = sin[None]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.concatenate([o1, o2], axis=-1)


def _rotate_interleaved(x, cos, sin):
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    cos = jnp.expand_dims(cos, -2)
    sin = jnp.expand_dims(sin, -2)
    while cos.ndim < x.ndim:
        cos = cos[None]
        sin = sin[None]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape)


def apply_rotary_emb(q, k=None, v=None, sin=None, cos=None,
                     position_ids=None, use_neox_rotary_style: bool = True,
                     base: float = 10000.0,
                     scaling: Optional[YarnScaling] = None):
    """Apply RoPE to q (and k) in paddle layout [B, S, H, D].

    Mirrors fused_rotary_position_embedding(q, k, v, sin, cos, position_ids,
    use_neox_rotary_style): v passes through untouched (kept for signature
    parity). Returns the same number of tensors it was given. `scaling`
    (with no table given) picks YaRN frequencies over `base`.
    """
    seq = q.shape[1]
    dh = q.shape[-1]
    if cos is None or sin is None:
        cos, sin = rope_freqs(seq, dh, base=base, position_ids=position_ids,
                              scaling=scaling)
    else:
        # paddle passes [1, S, 1, D] tables with values duplicated over the
        # two halves; reduce to [S, D/2]. Reduce by EXPLICIT dims — a blind
        # squeeze collapses the seq dim at S == 1 (single-token decode) and
        # mis-broadcasts the rotation across frequencies.
        cos = jnp.asarray(cos)
        sin = jnp.asarray(sin)
        if cos.ndim == 4:            # [1, S, 1, D]
            cos = cos[0, :, 0, :]
            sin = sin[0, :, 0, :]
        elif cos.ndim == 1:          # a bare frequency row: one position
            cos = cos[None, :]
            sin = sin[None, :]
        if cos.shape[-1] == dh:
            cos = cos[..., : dh // 2]
            sin = sin[..., : dh // 2]
        if position_ids is not None:
            # gather table rows per position (KV-cache decode pattern);
            # result [..., seq, dh/2] broadcasts against q's batch
            pid = jnp.asarray(position_ids)
            cos = jnp.take(cos, pid, axis=0)
            sin = jnp.take(sin, pid, axis=0)
        elif cos.shape[0] != seq:
            cos = cos[:seq]
            sin = sin[:seq]
    rot = _rotate_neox if use_neox_rotary_style else _rotate_interleaved
    cos = cos.astype(q.dtype)
    sin = sin.astype(q.dtype)
    outs: Tuple = (rot(q, cos, sin),)
    if k is not None:
        outs += (rot(k, cos, sin),)
    if v is not None:
        outs += (v,)
    return outs if len(outs) > 1 else outs[0]
