"""Ring attention: exact attention over sequence-sharded q/k/v.

Reference gap: the reference snapshot has NO ring-attention kernel — its
long-context story is the `sep` axis with all-to-all (Ulysses-class)
patterns (SURVEY.md §5.7). This module is the leapfrog: context parallelism
where each `sep` rank holds a sequence chunk of q/k/v and k/v chunks rotate
around the ring with `lax.ppermute`, combining per-chunk attention with
online-softmax statistics (the blockwise-attention recurrence of the
flash/ring-attention papers). Peak memory per chip is O(S/n * S/n) for one
score block — never the full S x S matrix — and the rotation overlaps with
compute on ICI.

Differentiable: the ring loop is a `lax.scan` of jax.checkpoint'ed steps;
autodiff replays the ring in reverse with the same collectives.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from . import mesh as mesh_mod

from jax import shard_map
from .pipeline_spmd import _to_varying

__all__ = ["ring_attention"]

_NEG_INF = -1e30


def _block_attn(q, k, v, scale, q_off, k_off, causal):
    """One blockwise contribution. q: [B,Sq,Hq,D]; k/v: [B,Sk,Hk,D] with
    Hk dividing Hq (GQA via grouped einsum — no materialized repeat).
    Returns (num [B,Sq,Hq,D] f32, m [B,Sq,Hq,1] f32, l [B,Sq,Hq,1] f32) —
    unnormalized output + row stats."""
    b, sq_, hq, d = q.shape
    hk = k.shape[2]
    rep = hq // hk
    qg = q.reshape(b, sq_, hk, rep, d)
    s = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k).astype(jnp.float32) * scale
    if causal:
        sk_ = k.shape[1]
        qpos = q_off + jnp.arange(sq_)
        kpos = k_off + jnp.arange(sk_)
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)                 # [B,Hk,rep,Sq,1]
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    num = jnp.einsum("bhrqk,bkhd->bqhrd", p.astype(v.dtype), v).astype(
        jnp.float32).reshape(b, sq_, hq, d)
    # stats to [B,Sq,Hq,1]
    m = jnp.moveaxis(m[..., 0], 3, 1).reshape(b, sq_, hq)[..., None]
    l = jnp.moveaxis(l[..., 0], 3, 1).reshape(b, sq_, hq)[..., None]
    return num, m, l


def ring_attention(q, k, v, *, mesh: Optional[Mesh] = None,
                   axis: str = "sep", causal: bool = True,
                   scale: Optional[float] = None):
    """Exact attention with q/k/v sequence-sharded over `axis`.

    q/k/v: [B, S, H, D] global arrays (S divisible by the axis size);
    returns [B, S, H, D] with the same sequence sharding.
    """
    b, s, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    mesh = mesh or mesh_mod.get_global_mesh()
    if mesh is None or axis not in mesh.axis_names \
            or int(mesh.shape[axis]) == 1:
        num, m, l = _block_attn(q, k, v, scale, 0, 0, causal)
        return (num / l).astype(q.dtype)

    n = int(mesh.shape[axis])
    if s % n:
        raise ValueError(f"seq {s} not divisible by {axis} size {n}")
    chunk = s // n

    @functools.partial(shard_map, mesh=mesh, axis_names={axis},
                       in_specs=(P(None, axis), P(None, axis),
                                 P(None, axis)),
                       out_specs=P(None, axis))
    def run(ql, kl, vl):
        idx = jax.lax.axis_index(axis)
        q_off = idx * chunk
        perm = [(i, (i + 1) % n) for i in range(n)]

        @jax.checkpoint
        def step_compute(ql, kv, r):
            kc, vc = kv
            src = (idx - r) % n          # rank that produced this kv chunk
            return _block_attn(ql, kc, vc, scale, q_off, src * chunk,
                               causal)

        def combine(acc, block):
            num, m, l = acc
            bnum, bm, bl = block
            m_new = jnp.maximum(m, bm)
            c_old = jnp.exp(m - m_new)
            c_new = jnp.exp(bm - m_new)
            return (num * c_old + bnum * c_new, m_new,
                    l * c_old + bl * c_new)

        def tick(carry, r):
            num, m, l, kv = carry
            num, m, l = combine((num, m, l), step_compute(ql, kv, r))
            kv = jax.tree.map(lambda t: jax.lax.ppermute(t, axis, perm), kv)
            return (num, m, l, kv), None

        num0 = _to_varying(jnp.zeros(ql.shape, jnp.float32), axis)
        m0 = _to_varying(jnp.full((b, chunk, h, 1), _NEG_INF, jnp.float32),
                         axis)
        l0 = _to_varying(jnp.zeros((b, chunk, h, 1), jnp.float32), axis)
        # n-1 rotating ticks, then the final block without the (wasted)
        # last rotation
        (num, m, l, kv), _ = jax.lax.scan(
            tick, (num0, m0, l0, (kl, vl)), jnp.arange(n - 1))
        num, m, l = combine((num, m, l),
                            step_compute(ql, kv, jnp.asarray(n - 1)))
        # rows with no valid key (can't happen with causal self-attention
        # of equal lengths, but guard the division)
        return (num / jnp.maximum(l, 1e-30)).astype(ql.dtype)

    return run(q, k, v)
