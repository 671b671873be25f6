"""Quantized collectives: int8 all-gather / psum / reduce-scatter with
an f32 scale sidecar (EQuARX-style, ISSUE 15).

The wire is the scarce resource at the two hot seams PR 11's comms
auditor priced (TPU803 names both): the per-layer decode o-proj
activation all-gather at serving_mp > 1, and the dp gradient psum in
`Model.fit`. This module ships those payloads as absmax-scaled int8
with a tiny f32 scale sidecar — the exact proven scheme of the PR 5
int8 KV pools (per-block absmax/127, zero block -> scale 0 ->
exact-zero dequant), block-quantized along the LAST dim so the sidecar
stays ~3% of the payload at block 128:

- **quantized_all_gather**: quantize locally, all-gather ONE int8
  buffer (the f32 scales ride bitcast-int8, concatenated onto the
  payload's last axis), dequantize locally. One rounding per element;
  wire bytes ~0.5x a bf16 payload, ~0.25x an f32 one.
- **quantized_psum**: reduce-scatter on int8 shards (an `all_to_all` of
  per-destination quantized chunks, sidecar packed in), local
  dequant-ACCUMULATE in f32 (so accumulation error does NOT scale with
  world size — each contribution is rounded once, the sum is exact
  f32), then a quantized all-gather of the reduced shard. Two roundings
  per element total, independent of n.
- **quantized_reduce_scatter**: the first hop alone (the
  `lax.psum_scatter(tiled=True)` shape contract).
- **quantized_psum_tree**: the dp gradient sync — flattens a grad
  pytree into ONE f32 vector, runs one quantized psum (one collective
  pair instead of one per leaf), and unflattens at the leaves' dtypes.

Numerics guards (never silent corruption):

- an all-zero block keeps scale 0 and dequantizes to EXACT zeros (zero
  gradients survive bit-exactly);
- a block containing NaN/inf stores a NON-FINITE scale, so the whole
  block dequantizes non-finite — a poisoned payload stays VISIBLY
  poisoned instead of silently clipping to finite garbage;
- payloads that cannot be quantized at all (non-float dtypes, empty or
  0-d arrays, a gather along the block axis) fall back to the plain
  collective with a build-time warning.

Cost model note: each quantized hop issues ONE collective — the f32
sidecar is bitcast to int8 and PACKED into the payload buffer
(`_pack_scales` / `_unpack_scales`), so the launch count matches the
plain op exactly and a launch-bound tiny-payload path (the per-layer
decode gather the ROADMAP silicon note flagged) cannot lose on
dispatch. The bitcast is a free relayout on both ends; the wire sees
the identical byte count the two-collective form shipped.

Flag: FLAGS_quantized_collectives / PADDLE_TPU_QUANTIZED_COLLECTIVES,
default OFF, resolved at program-BUILD time like every serving flag
(`resolve_quantized_collectives`): it joins the serving jit program
keys and `warm()` covers it; flag OFF is byte-identical to a build
without it. `analysis/comms.py` recognizes the packed int8 buffers
(the only int8 tensors the stack ever puts on a collective) and
prices them as quantized wire; TPU803 never fires on an int8 payload
by design.
"""
from __future__ import annotations

import warnings
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = [
    "QCOLL_BLOCK", "QCOLL_FALLBACK_MSG", "resolve_quantized_collectives",
    "quantize_blocks", "dequantize_blocks", "quantized_all_gather",
    "quantized_psum", "quantized_psum_tree", "quantized_reduce_scatter",
]

# lane-width blocks along the last dim: one f32 scale per 128 int8
# payload bytes keeps the sidecar ~3% of the payload (payloads narrower
# than a block use one scale per row — the block clamps to the dim)
QCOLL_BLOCK = 128

QCOLL_FALLBACK_MSG = (
    "payload cannot be block-quantized; falling back to the "
    "unquantized collective (full-width wire bytes, exact numerics)")


def resolve_quantized_collectives(quantized: Optional[bool] = None) -> bool:
    """Resolve the quantized-collectives switch from the argument or
    FLAGS_quantized_collectives / PADDLE_TPU_QUANTIZED_COLLECTIVES.
    Read at program-BUILD time (like FLAGS_kv_cache_dtype /
    FLAGS_serving_mp): flip it before constructing or warming an
    engine, or before calling Model.fit. False (default) keeps every
    wire byte-identical to a build without the flag."""
    if quantized is None:
        from ..framework.flags import flag as _flag

        quantized = _flag("quantized_collectives")
    return bool(quantized)


def _quantizable(x) -> bool:
    return (getattr(x, "ndim", 0) >= 1 and x.size > 0
            and jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating))


def quantize_blocks(x, block: int = QCOLL_BLOCK):
    """Symmetric absmax int8 quantization in blocks along the LAST dim
    (the PR 5 KV-pool scheme, per wire block instead of per page).

    x [..., d] float -> (q int8 [..., nb*be], scale f32 [..., nb]) with
    be = min(block, d), nb = ceil(d / be); the last partial block pads
    with zeros (trimmed again by `dequantize_blocks(..., out_dim=)`).
    The absmax is taken in f32 BEFORE any half-precision round-trip;
    scale = absmax / 127. An all-zero block keeps scale 0 (dequantizes
    to exact zeros); a block with NaN/inf stores a NON-FINITE scale so
    the dequant is visibly poisoned, never silently finite."""
    d = int(x.shape[-1])
    be = min(int(block), d)
    nb = -(-d // be)
    xf = x.astype(jnp.float32)
    pad = nb * be - d
    if pad:
        xf = jnp.pad(xf, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    xb = xf.reshape(x.shape[:-1] + (nb, be))
    scale = jnp.max(jnp.abs(xb), axis=-1) / 127.0
    # a NaN absmax fails the > 0 test, so safe stays 1.0 and q holds
    # garbage ints — harmless, because the STORED scale is the
    # non-finite absmax and the dequant poisons the whole block
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.round(xb / safe[..., None]).astype(jnp.int8)
    return q.reshape(x.shape[:-1] + (nb * be,)), scale


def dequantize_blocks(q, scale, out_dim: Optional[int] = None,
                      dtype=None):
    """Inverse of `quantize_blocks`: q [..., nb*be] int8 with scale
    [..., nb] -> float [..., out_dim or nb*be]. The block width is
    derived from the operand shapes, so gathered payloads (block
    structure preserved along any non-last axis) dequantize with the
    same call."""
    nb = int(scale.shape[-1])
    be = int(q.shape[-1]) // nb
    xb = q.astype(jnp.float32).reshape(scale.shape + (be,))
    x = (xb * scale[..., None]).reshape(q.shape)
    if out_dim is not None and out_dim != x.shape[-1]:
        x = x[..., :out_dim]
    return x.astype(dtype) if dtype is not None else x


def _pack_scales(q, s):
    """ONE wire buffer per hop: bitcast the f32 sidecar to int8 (4
    bytes per scale, a free relayout) and concatenate it onto the
    payload's last axis — q [..., nb*be] + s [..., nb] -> packed
    [..., nb*be + 4*nb] int8. The collective then ships a single
    tensor, so the quantized hop's launch count matches the plain
    op's (the ROADMAP launch-bound-decode note)."""
    sb = jax.lax.bitcast_convert_type(s, jnp.int8)   # [..., nb, 4]
    return jnp.concatenate(
        [q, sb.reshape(s.shape[:-1] + (4 * s.shape[-1],))], axis=-1)


def _unpack_scales(packed, nb: int):
    """Inverse of `_pack_scales` after the collective: split the
    trailing 4*nb sidecar bytes off the last axis and bitcast them
    back to the f32 [..., nb] scale."""
    split = packed.shape[-1] - 4 * nb
    q, sb = packed[..., :split], packed[..., split:]
    s = jax.lax.bitcast_convert_type(
        sb.reshape(packed.shape[:-1] + (nb, 4)), jnp.float32)
    return q, s


def quantized_all_gather(x, axis_name: str, *, axis: int = 0,
                         tiled: bool = True, block: int = QCOLL_BLOCK):
    """`lax.all_gather` shipping an int8 payload with the f32 scale
    sidecar packed in: quantize locally (blocks along the last dim),
    gather ONE int8 buffer along `axis`, split + dequantize locally at
    x.dtype. One rounding per element, one collective per hop.
    Gathering along the block axis itself (the last dim) would
    interleave shards' blocks, so that case — like non-float or empty
    payloads — falls back to the plain collective with a warning."""
    nd = getattr(x, "ndim", 0)
    if not _quantizable(x) or axis % max(nd, 1) == nd - 1:
        warnings.warn(f"quantized_all_gather: {QCOLL_FALLBACK_MSG}",
                      stacklevel=2)
        return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)
    q, s = quantize_blocks(x, block)
    pg = jax.lax.all_gather(_pack_scales(q, s), axis_name, axis=axis,
                            tiled=tiled)
    qg, sg = _unpack_scales(pg, int(s.shape[-1]))
    return dequantize_blocks(qg, sg, out_dim=int(x.shape[-1]),
                             dtype=x.dtype)


def quantized_psum(x, axis_name: str, *, block: int = QCOLL_BLOCK):
    """`lax.psum` as a two-hop quantized exchange (EQuARX):

    1. each chip flattens its addend to f32, splits it into n
       per-destination chunks, quantizes each chunk and `all_to_all`s
       ONE int8 buffer per chunk (sidecar packed in) — the
       reduce-scatter hop;
    2. every chip dequantizes the n received chunks and ACCUMULATES in
       f32 — one rounding per contribution, exact summation, so the
       error does not grow with world size;
    3. the reduced shard re-quantizes and all-gathers its packed
       buffer, dequantizing back to x's shape and dtype.

    Two roundings per element total, two collectives total (exactly
    the plain-psum ring's hop count). Zero addends stay exactly zero;
    non-finite addends poison their block visibly (see module doc).
    Non-float payloads fall back to the plain psum with a warning."""
    if not _quantizable(x):
        warnings.warn(f"quantized_psum: {QCOLL_FALLBACK_MSG}",
                      stacklevel=2)
        return jax.lax.psum(x, axis_name)
    n = jax.lax.psum(1, axis_name)  # static: the axis size
    if n == 1:
        return x
    shape, dtype = x.shape, x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    chunk = -(-flat.size // (n * block)) * block
    padded = jnp.pad(flat, (0, n * chunk - flat.size))
    parts = padded.reshape(n, chunk)
    q, s = quantize_blocks(parts, block)
    px = jax.lax.all_to_all(_pack_scales(q, s), axis_name,
                            split_axis=0, concat_axis=0)
    qx, sx = _unpack_scales(px, int(s.shape[-1]))
    red = jnp.sum(dequantize_blocks(qx, sx), axis=0)        # f32 [chunk]
    q2, s2 = quantize_blocks(red, block)
    pg = jax.lax.all_gather(_pack_scales(q2, s2), axis_name, axis=0,
                            tiled=False)
    qg, sg = _unpack_scales(pg, int(s2.shape[-1]))
    out = dequantize_blocks(qg, sg).reshape(-1)[:flat.size]
    return out.reshape(shape).astype(dtype)


def quantized_reduce_scatter(x, axis_name: str, *,
                             block: int = QCOLL_BLOCK):
    """`lax.psum_scatter(..., scatter_dimension=0, tiled=True)` with an
    int8 wire: x [N, ...] (N divisible by the axis size) -> this chip's
    summed shard [N/n, ...] — the first hop of `quantized_psum` alone,
    for callers that keep working on the reduced shard (ZeRO-style
    grad sharding). Accumulation is local f32 over once-rounded int8
    contributions."""
    if not _quantizable(x):
        warnings.warn(f"quantized_reduce_scatter: {QCOLL_FALLBACK_MSG}",
                      stacklevel=2)
        return jax.lax.psum_scatter(x, axis_name, scatter_dimension=0,
                                    tiled=True)
    n = jax.lax.psum(1, axis_name)
    if n == 1:
        return x.astype(x.dtype)
    if x.shape[0] % n:
        raise ValueError(
            f"quantized_reduce_scatter: leading dim {x.shape[0]} does "
            f"not divide the '{axis_name}' axis size {n}")
    parts = x.astype(jnp.float32).reshape((n, x.shape[0] // n)
                                          + x.shape[1:])
    q, s = quantize_blocks(parts, block)
    px = jax.lax.all_to_all(_pack_scales(q, s), axis_name,
                            split_axis=0, concat_axis=0)
    qx, sx = _unpack_scales(px, int(s.shape[-1]))
    red = jnp.sum(dequantize_blocks(qx, sx,
                                    out_dim=int(x.shape[-1])), axis=0)
    return red.astype(x.dtype)


def quantized_psum_tree(tree, axis_name: str, *,
                        block: int = QCOLL_BLOCK):
    """The dp gradient sync: psum a pytree of float leaves (a grads
    dict) through ONE quantized exchange — leaves flatten-concatenate
    into a single f32 vector (so the wire sees one payload + one
    sidecar per hop, not one pair per leaf), and the summed vector
    splits back at each leaf's shape and dtype. Non-float leaves (none
    in a grads tree — guards misuse) ride a plain psum."""
    leaves, treedef = jax.tree.flatten(tree)
    qleaves = [l for l in leaves if _quantizable(l)]
    if not qleaves:
        return jax.lax.psum(tree, axis_name)
    flat = jnp.concatenate(
        [l.astype(jnp.float32).reshape(-1) for l in qleaves])
    red = quantized_psum(flat, axis_name, block=block)
    out, off = [], 0
    for l in leaves:
        if _quantizable(l):
            sz = int(l.size)
            out.append(red[off:off + sz].reshape(l.shape)
                       .astype(l.dtype))
            off += sz
        else:
            out.append(jax.lax.psum(l, axis_name))
    return jax.tree.unflatten(treedef, out)
