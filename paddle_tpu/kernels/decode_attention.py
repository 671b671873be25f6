"""Single-token decode attention as Pallas TPU kernels.

TPU-native counterpart of the reference's serving decode kernels
(paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu for the
contiguous cache, block_attn.h for the paged cache). Decode is
bandwidth-bound: the whole KV cache streams through once per token, so the
win is fusing mask + online softmax + weighted sum into one pass instead of
XLA's materialized [B, H, S] logits round-trip.

Layouts match the incubate serving API:
  contiguous: cache [B, H, max_seq, D], q [B, H, D], lens [B]
  paged:      cache [max_pages, H, block_size, D], block_tables [B, n_blk]
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .constraints import (KernelConstraint, LANE, VMEM_BUDGET_BYTES,
                          fit_vmem_block, is_scale_operand,
                          missing_scale_finding, tensor_operands,
                          register_constraint)

_NEG_INF = -1e30

# default kv-block length each grid step streams through VMEM
BLOCK_S = 512
# below this block length the grid degenerates (near-prime max_seq) and
# the kernel warns to pad the cache
MIN_BLOCK_S = 32


def _fitted_block(block_s: int, max_seq: int, h: int, d: int,
                  itemsize: int = 2) -> int:
    """Largest divisor of max_seq under both the requested block and the
    VMEM double-buffering cap — the block the contiguous kernel runs.
    Thin shape adapter over the shared `constraints.fit_vmem_block`
    (`itemsize` lets int8 caches fit 2x the rows of bf16)."""
    return fit_vmem_block(block_s, max_seq, h * d * itemsize)


def _check_decode_shapes(shapes, dtypes):
    """Checker for the contiguous/GQA decode pallas calls. Operands lead
    with the scalar-prefetch args; the q/cache trio sits at the tail:
    q [B, H, D] (or [B*Hkv, group, D]), caches [..., block, D]. Only the
    lane check is shape-decidable here: a small second-minor cache dim
    is a legitimate page length in the paged layout, so block-length
    degradation is surfaced by the kernel's own runtime warning
    instead."""
    out = []
    arr = [s for s in shapes if len(s) >= 3]
    if not arr:
        return out
    d = arr[0][-1]
    if d % LANE:
        out.append(("warning",
                    f"head_dim {d} is not a multiple of the {LANE}-lane "
                    "tile; decode streams the whole cache padded to "
                    f"{-(-d // LANE) * LANE} lanes"))
    return out


def _decode_attention_roofline(shapes, dtypes):
    """Roofline model for one decode-attention launch (contiguous and
    paged, bf16 and int8 pools): FLOPs = qk^T + p·v = 4·B·Hq·D·ctx;
    HBM bytes = q in + out + the K/V actually STREAMED — for the paged
    grids that is the `B x n_blocks` POOL PAGES the block table names
    (plus their f32 scale rows when quantized), never the whole pool.
    Pure shape math (the KernelConstraint contract); None when the
    operand layout doesn't resolve."""
    from .constraints import dtype_itemsize

    arrs = tensor_operands(shapes, dtypes)
    if len(arrs) < 3 or not arrs[0][0][0]:
        return None
    (q_s, q_d), (pool_s, pool_d) = arrs[0], arrs[1]
    d_head = q_s[-1]
    q_elems = math.prod(q_s)               # == B*Hq*D in every layout
    tables = next((s for s, dt in zip(shapes, dtypes)
                   if len(s) == 2 and dt.startswith("int")), None)
    if tables is not None:                 # paged: stream table pages
        b, n_blocks = tables
        # rank-4 pool [P, Hkv, page, D]; rank-3 (GQA grid) collapses
        # (page, kv head) -> [P*Hkv, page, D]
        page = pool_s[2] if len(pool_s) >= 4 else pool_s[1]
        hkv = pool_s[1] if len(pool_s) >= 4 \
            else max(q_s[0] // max(b, 1), 1)
        ctx = n_blocks * page
        kv_bytes = 2 * b * ctx * hkv * d_head * dtype_itemsize(pool_d)
        # int8 pools travel with per-(page, kv head) f32 scale rows
        n_scales = sum(1 for s, dt in zip(shapes, dtypes)
                       if is_scale_operand(s, dt))
        if n_scales:
            kv_bytes += n_scales * b * n_blocks * hkv * 4
    else:                                  # contiguous: whole cache
        if len(pool_s) >= 4:               # [B, H, S, D]
            ctx = pool_s[-2]
        else:                              # GQA collapse [B*Hkv*nb, bs, D]
            ctx = (pool_s[0] // max(q_s[0], 1)) * pool_s[1]
        kv_bytes = 2 * math.prod(pool_s) * dtype_itemsize(pool_d)
    q_bytes = q_elems * dtype_itemsize(q_d)
    return {"flops": 4 * q_elems * ctx,
            "hbm_bytes": 2 * q_bytes + kv_bytes}


CONSTRAINT = register_constraint(KernelConstraint(
    name="decode_attention",
    kernel_fns=("_decode_kernel", "_paged_decode_kernel",
                "_gqa_contig_kernel", "_paged_gqa_kernel"),
    blocks={"block_s": BLOCK_S, "min_block_s": MIN_BLOCK_S},
    note="bandwidth-bound single-token decode; cache length should admit "
         f"a divisor >= {MIN_BLOCK_S} under the VMEM double-buffer cap",
    checker=_check_decode_shapes,
    source="decode_attention.py",
    roofline=_decode_attention_roofline,
))


def _check_q8_decode_shapes(shapes, dtypes):
    """Checker for the int8 paged decode calls: the quantized pools MUST
    travel with two f32 scale operands (per (page, kv head) absmax), and
    the lane check from the bf16 checker still applies."""
    out = list(_check_decode_shapes(shapes, dtypes))
    finding = missing_scale_finding(shapes, dtypes)
    if finding is not None:
        out.append(finding)
    return out


CONSTRAINT_Q8 = register_constraint(KernelConstraint(
    name="decode_attention_q8",
    kernel_fns=("_paged_decode_q8_kernel", "_paged_gqa_q8_kernel"),
    blocks={"block_s": BLOCK_S, "min_block_s": MIN_BLOCK_S},
    note="int8 paged decode streams quantized page tiles + their "
         "per-(page, kv head) f32 absmax scale rows; the dequantized "
         "bf16 pool never materializes",
    checker=_check_q8_decode_shapes,
    source="decode_attention.py",
    roofline=_decode_attention_roofline,
))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, block_s: int, scale: float):
    """Grid (B, S // block_s). Blocks: q [H, D], k/v [H, block_s, D].
    Online softmax over seq blocks; rows masked at positions > len."""
    b = pl.program_id(0)
    si = pl.program_id(1)
    ns = pl.num_programs(1)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # this step's token sits at position len; positions > len are invalid
    valid_until = len_ref[b]

    @pl.when(si * block_s <= valid_until)
    def _compute():
        q = q_ref[0]                                   # [H, D]
        k = k_ref[0]                                   # [H, block_s, D]
        # decode is bandwidth-bound (intensity ~1): VPU mul+reduce, not
        # MXU (Mosaic also cannot lower a batched matvec dot_general)
        s = jnp.sum(q[:, None, :].astype(jnp.float32)
                    * k.astype(jnp.float32), axis=-1) * scale  # [H, block_s]
        pos = si * block_s + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos <= valid_until, s, _NEG_INF)
        m_prev = m_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev[:, :1] - m_new[:, :1])
        p = jnp.exp(s - m_new[:, :1])
        l_new = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jnp.sum(p[:, :, None] * v_ref[0].astype(jnp.float32),
                     axis=1)                           # [H, D]
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(si == ns - 1)
    def _final():
        o_ref[0] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lens: jax.Array, *, block_s: int = BLOCK_S,
                     scale: float | None = None) -> jax.Array:
    """One decode step over a contiguous cache.

    q: [B, H, D] (the current token's queries, k/v already written to the
    cache at position lens[b]); k_cache/v_cache: [B, H, max_seq, D];
    lens: [B] int32, number of PREVIOUS tokens (the current token is at
    position lens[b]). Returns [B, H, D].
    """
    b, h, d = q.shape
    max_seq = k_cache.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if d % LANE:
        # Mosaic cannot shape-cast the [H, 1, D] broadcast at narrow
        # head dims; the GQA grid's dot-general form lowers at any D
        # (including group=1 — verified on silicon at D=32)
        return gqa_decode_attention(q, k_cache, v_cache, lens,
                                    block_s=block_s, scale=scale)
    # take the largest divisor of max_seq under both the requested block
    # and the VMEM double-buffering cap so the grid covers the cache
    # exactly (2 operands x 2 buffers x itemsize 2 = 8 bytes per element)
    block_s = _fitted_block(block_s, max_seq, h, d)
    if block_s < min(MIN_BLOCK_S, max_seq):
        # near-prime max_seq: the largest divisor under the VMEM cap is
        # pathologically small — a 3-row-block grid would be an
        # order-of-magnitude silent slowdown. Surface it.
        import warnings

        warnings.warn(
            f"decode_attention: max_seq {max_seq} forces block_s "
            f"{block_s} (largest divisor under the VMEM cap); pad "
            f"the cache to a rounder length", stacklevel=2)
    grid = (b, max_seq // block_s)
    kernel = functools.partial(_decode_kernel, block_s=block_s, scale=scale)
    return pl.pallas_call(
        kernel,
        name=CONSTRAINT.name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, h, d), lambda b, j, lens: (b, 0, 0)),
                pl.BlockSpec((1, h, block_s, d),
                             lambda b, j, lens: (b, 0, j, 0)),
                pl.BlockSpec((1, h, block_s, d),
                             lambda b, j, lens: (b, 0, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, h, d), lambda b, j, lens: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, 128), jnp.float32),
                pltpu.VMEM((h, 128), jnp.float32),
                pltpu.VMEM((h, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=not _on_tpu(),
    )(lens.astype(jnp.int32), q, k_cache, v_cache)


def _paged_decode_kernel(tables_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, block_size: int,
                         scale: float):
    """Grid (B, n_blocks_per_seq). k/v blocks are whole PAGES selected via
    the block-table scalar prefetch; otherwise identical online softmax."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid_until = len_ref[b]

    @pl.when(j * block_size <= valid_until)
    def _compute():
        q = q_ref[0]                                   # [H, D]
        k = k_ref[0]                                   # [H, block_size, D]
        s = jnp.sum(q[:, None, :].astype(jnp.float32)
                    * k.astype(jnp.float32), axis=-1) * scale
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos <= valid_until, s, _NEG_INF)
        m_prev = m_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev[:, :1] - m_new[:, :1])
        p = jnp.exp(s - m_new[:, :1])
        l_new = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jnp.sum(p[:, :, None] * v_ref[0].astype(jnp.float32),
                     axis=1)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == nb - 1)
    def _final():
        o_ref[0] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)


def _paged_decode_q8_kernel(tables_ref, len_ref, q_ref, k_ref, v_ref,
                            ksc_ref, vsc_ref, o_ref, m_scr, l_scr,
                            acc_scr, *, block_size: int, scale: float):
    """int8 equal-heads paged decode: `_paged_decode_kernel`'s grid with
    int8 page tiles [H, block, D] and a per-head f32 scale row [1, H]
    riding each step. Scales vary across the head axis inside the tile,
    so scores rescale per head row after the reduce and the weighted
    sum rescales by the v scale row."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid_until = len_ref[b]

    @pl.when(j * block_size <= valid_until)
    def _compute():
        q = q_ref[0].astype(jnp.float32)               # [H, D]
        k = k_ref[0].astype(jnp.float32)               # [H, block, D]
        s = jnp.sum(q[:, None, :] * k, axis=-1) * scale
        s = s * ksc_ref[0][:, None]                    # per-head dequant
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos <= valid_until, s, _NEG_INF)
        m_prev = m_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev[:, :1] - m_new[:, :1])
        p = jnp.exp(s - m_new[:, :1])
        l_new = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jnp.sum(p[:, :, None] * v_ref[0].astype(jnp.float32),
                     axis=1)                           # [H, D]
        pv = pv * vsc_ref[0][:, None]
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == nb - 1)
    def _final():
        o_ref[0] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)


def _gqa_grid_body(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, block_size: int, scale: float,
                   ksc_ref=None, vsc_ref=None):
    """Shared grouped-query decode body for grid (B, Hkv, n_blocks):
    each step streams ONE kv block of ONE kv head and scores the whole
    query group against it — the block never leaves VMEM at query-head
    width (reference GQA decode: block_attn.h with gqa_group_size). The
    paged and contiguous kernels differ only in how their k/v index maps
    pick the block.

    With `ksc_ref`/`vsc_ref` (the int8 paged path) the k/v blocks are
    symmetric-absmax int8 and each step also carries that (page, kv
    head)'s f32 scale as a (1, 1) tile: scores rescale by the k scale
    AFTER the dot (the scale is uniform over the tile, so the dequant
    never materializes a widened block) and the weighted sum rescales by
    the v scale — the f32 accumulation the bf16 path already does."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    nb = pl.num_programs(2)
    quant = ksc_ref is not None

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid_until = len_ref[b]

    @pl.when(j * block_size <= valid_until)
    def _compute():
        q = q_ref[0]                                   # [group, D]
        k = k_ref[0]                                   # [block_size, D]
        if quant:
            # int8 tiles score through the f32 path; one scalar multiply
            # folds the absmax scale into the softmax scale
            q = q.astype(jnp.float32)
            k = k.astype(jnp.float32)
        # grouped decode has real matmuls (group >= 2 rows), so the MXU
        # does the scoring — unlike the equal-heads kernels' batched
        # matvec, these 2-D dots lower cleanly at any D
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [group, bs]
        if quant:
            s = s * ksc_ref[0, 0]
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos <= valid_until, s, _NEG_INF)
        m_prev = m_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev[:, :1] - m_new[:, :1])
        p = jnp.exp(s - m_new[:, :1])
        l_new = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [group, D]
        if quant:
            pv = pv * vsc_ref[0, 0]
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == nb - 1)
    def _final():
        o_ref[0] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)


def _paged_gqa_kernel(tables_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                      m_scr, l_scr, acc_scr, *, block_size: int,
                      scale: float):
    # tables_ref is consumed by the BlockSpec index maps, not the body
    _gqa_grid_body(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, block_size=block_size, scale=scale)


def _paged_gqa_q8_kernel(tables_ref, len_ref, q_ref, k_ref, v_ref,
                         ksc_ref, vsc_ref, o_ref, m_scr, l_scr, acc_scr,
                         *, block_size: int, scale: float):
    """int8 paged GQA decode: the `_gqa_grid_body` grid streaming int8
    (kv head, page) tiles plus their (1, 1) f32 absmax scales — the
    dequantized bf16 pool never materializes, HBM reads stay at int8
    width."""
    _gqa_grid_body(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, block_size=block_size, scale=scale,
                   ksc_ref=ksc_ref, vsc_ref=vsc_ref)


def gqa_decode_attention(q: jax.Array, k_cache: jax.Array,
                         v_cache: jax.Array, lens: jax.Array, *,
                         block_s: int = BLOCK_S,
                         scale: float | None = None) -> jax.Array:
    """Grouped-query decode over a CONTIGUOUS cache — the GQA grid of
    the paged kernel without a table: one kv block of one kv head per
    step, whole query group scored in VMEM via MXU dots.

    q: [B, Hq, D]; k_cache/v_cache: [B, Hkv, max_seq, D] with
    Hq % Hkv == 0; lens: [B] previous-token counts. Returns [B, Hq, D].
    """
    b, hq, d = q.shape
    hkv, max_seq = k_cache.shape[1], k_cache.shape[2]
    group = hq // hkv
    if hq % hkv:
        raise ValueError(f"Hq {hq} not a multiple of Hkv {hkv}")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # largest divisor of max_seq <= block_s keeps the collapsed view a
    # whole number of blocks (any divisor lowers: the block equals the
    # collapsed trailing dims; row_bytes=0 = no VMEM cap — one kv head's
    # block is small at every supported shape)
    bs = fit_vmem_block(block_s, max_seq, 0)
    if bs < min(MIN_BLOCK_S, max_seq):
        import warnings

        warnings.warn(
            f"gqa_decode_attention: max_seq {max_seq} forces block "
            f"{bs}; pad the cache to a rounder length", stacklevel=2)
    nb = max_seq // bs
    # free row-major collapses: q/out [b*hkv, group, d]; caches
    # [b*hkv*nb, bs, d] with block row (b*hkv + h)*nb + j
    qg = q.reshape(b * hkv, group, d)
    kc = k_cache.reshape(b * hkv * nb, bs, d)
    vc = v_cache.reshape(b * hkv * nb, bs, d)
    kernel = functools.partial(_gqa_contig_kernel, block_size=bs,
                               scale=scale)
    out = pl.pallas_call(
        kernel,
        name=CONSTRAINT.name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, nb),
            in_specs=[
                pl.BlockSpec((1, group, d),
                             lambda b, h, j, lens, hkv=hkv:
                             (b * hkv + h, 0, 0)),
                pl.BlockSpec((1, bs, d),
                             lambda b, h, j, lens, hkv=hkv, nb=nb:
                             ((b * hkv + h) * nb + j, 0, 0)),
                pl.BlockSpec((1, bs, d),
                             lambda b, h, j, lens, hkv=hkv, nb=nb:
                             ((b * hkv + h) * nb + j, 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, group, d),
                lambda b, h, j, lens, hkv=hkv: (b * hkv + h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * hkv, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=not _on_tpu(),
    )(lens.astype(jnp.int32), qg, kc, vc)
    return out.reshape(b, hq, d)


def _gqa_contig_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                       acc_scr, *, block_size: int, scale: float):
    _gqa_grid_body(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, block_size=block_size, scale=scale)


def _paged_decode_gqa(q, key_cache, value_cache, block_tables, lens, scale,
                      k_scale=None, v_scale=None):
    """Refs stay rank-3 (Mosaic cannot shape-cast 4-D blocks): q/out
    collapse (hkv, group) into one axis indexed at h*group; the pools
    collapse (page, hkv) so page selection becomes tbl[b, j]*hkv + h —
    both are metadata-only row-major collapses, no data movement. With
    `k_scale`/`v_scale` [max_pages, hkv] (int8 pools) the collapse also
    flattens the scales to [max_pages*hkv, 1, 1] so each grid step's
    (1, 1, 1) scale tile rides the same tbl[b, j]*hkv + h row (and the
    same index map) as its page — the trailing (1, 1) equals the array's
    own trailing dims, which is what the Mosaic lowering accepts."""
    b, hq, d = q.shape
    hkv = key_cache.shape[1]
    group = hq // hkv
    block_size = key_cache.shape[2]
    n_blocks = block_tables.shape[1]
    max_pages = key_cache.shape[0]
    quant = k_scale is not None
    # blocks must exactly span trailing array dims unless 8/128-divisible,
    # so q/out collapse to [b*hkv, group, d] (block = one full row) and
    # the pools to [pages*hkv, block_size, d] (block = one page x one kv
    # head at flat row tbl[b, j]*hkv + h)
    qg = q.reshape(b * hkv, group, d)
    kc = key_cache.reshape(max_pages * hkv, block_size, d)
    vc = value_cache.reshape(max_pages * hkv, block_size, d)

    def pool_map(b_, h, j, tbl, lens_, hkv=hkv):
        return (tbl[b_, j] * hkv + h, 0, 0)

    def q_map(b_, h, j, tbl, lens_, hkv=hkv):
        return (b_ * hkv + h, 0, 0)

    in_specs = [
        pl.BlockSpec((1, group, d), q_map),
        pl.BlockSpec((1, block_size, d), pool_map),
        pl.BlockSpec((1, block_size, d), pool_map),
    ]
    operands = [qg, kc, vc]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, 1), pool_map),
                     pl.BlockSpec((1, 1, 1), pool_map)]
        operands += [k_scale.astype(jnp.float32).reshape(-1, 1, 1),
                     v_scale.astype(jnp.float32).reshape(-1, 1, 1)]
        kernel = functools.partial(_paged_gqa_q8_kernel,
                                   block_size=block_size, scale=scale)
    else:
        kernel = functools.partial(_paged_gqa_kernel,
                                   block_size=block_size, scale=scale)
    out = pl.pallas_call(
        kernel,
        name=(CONSTRAINT_Q8 if quant else CONSTRAINT).name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hkv, n_blocks),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, group, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * hkv, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=not _on_tpu(),
    )(block_tables.astype(jnp.int32), lens.astype(jnp.int32), *operands)
    return out.reshape(b, hq, d)


def paged_decode_attention(q: jax.Array, key_cache: jax.Array,
                           value_cache: jax.Array, block_tables: jax.Array,
                           lens: jax.Array,
                           scale: float | None = None, *,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None) -> jax.Array:
    """One decode step over a paged cache (reference: block_attn.h).

    q: [B, Hq, D]; key_cache/value_cache: [max_pages, Hkv, block_size, D]
    with Hq a multiple of Hkv (grouped queries take the GQA grid, equal
    heads the all-heads-per-page grid); block_tables: [B, n_blocks] page
    ids covering positions [0, n_blocks*block_size); lens: [B]
    previous-token counts (current token already written at position
    lens[b]). Returns [B, Hq, D].

    int8 pools (``FLAGS_kv_cache_dtype=int8``): pass the per-(page, kv
    head) f32 absmax scale arrays as ``k_scale``/``v_scale``
    [max_pages, Hkv] — each grid step then streams the int8 tile plus
    its scale and rescales inside the f32 accumulation; the dequantized
    bf16 pool never materializes.

    Head counts (and therefore the GQA group) derive from the OPERAND
    shapes, never a model config: under tensor-parallel serving
    (FLAGS_serving_mp) this call sees the shard-LOCAL q heads and pool
    kv heads inside shard_map, so both the kv-head-sharded grid and
    the replicated-KV MQA fallback (full Hkv, local Hq) lower to the
    correct group without any head-offset plumbing.
    """
    b, h, d = q.shape
    hkv = key_cache.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    quant = key_cache.dtype == jnp.int8
    if quant and (k_scale is None or v_scale is None):
        raise ValueError(
            "int8 KV pools need their per-(page, kv head) k_scale / "
            "v_scale arrays — a quantized pool without scales decodes "
            "garbage (TPU103 lints this)")
    if not quant and (k_scale is not None or v_scale is not None):
        raise ValueError("k_scale/v_scale only apply to int8 KV pools")
    if h != hkv or d % LANE:
        # grouped queries — or narrow head dims, where the equal-heads
        # kernel's [H, 1, D] broadcast fails to lower (see
        # decode_attention); the GQA grid covers group=1 too
        if h % hkv:
            raise ValueError(f"Hq {h} not a multiple of Hkv {hkv}")
        return _paged_decode_gqa(q, key_cache, value_cache, block_tables,
                                 lens, scale, k_scale, v_scale)
    block_size = key_cache.shape[2]
    n_blocks = block_tables.shape[1]
    in_specs = [
        pl.BlockSpec((1, h, d), lambda b, j, tbl, lens: (b, 0, 0)),
        pl.BlockSpec((1, h, block_size, d),
                     lambda b, j, tbl, lens: (tbl[b, j], 0, 0, 0)),
        pl.BlockSpec((1, h, block_size, d),
                     lambda b, j, tbl, lens: (tbl[b, j], 0, 0, 0)),
    ]
    operands = [q, key_cache, value_cache]
    if quant:
        in_specs += [pl.BlockSpec((1, h),
                                  lambda b, j, tbl, lens: (tbl[b, j], 0)),
                     pl.BlockSpec((1, h),
                                  lambda b, j, tbl, lens: (tbl[b, j], 0))]
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]
        kernel = functools.partial(_paged_decode_q8_kernel,
                                   block_size=block_size, scale=scale)
    else:
        kernel = functools.partial(_paged_decode_kernel,
                                   block_size=block_size, scale=scale)
    # page selection: the k/v BlockSpec index maps read the prefetched
    # block table — each grid step streams exactly one page of one sequence
    return pl.pallas_call(
        kernel,
        name=(CONSTRAINT_Q8 if quant else CONSTRAINT).name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_blocks),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, h, d), lambda b, j, tbl, lens: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, 128), jnp.float32),
                pltpu.VMEM((h, 128), jnp.float32),
                pltpu.VMEM((h, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=not _on_tpu(),
    )(block_tables.astype(jnp.int32), lens.astype(jnp.int32), *operands)
