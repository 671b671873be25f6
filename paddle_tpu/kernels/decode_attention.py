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

Work schedules:
  contiguous, equal heads   grid (B, S // block): all heads of one block
  contiguous, grouped       grid (B, Hkv, S // block): one kv head's block
  paged, equal heads        grid (B, n_blk): all heads of one table column,
                            dead columns skipped by `pl.when` (a grid step
                            each all the same)
  paged, grouped (serving)  work follows `lens`, not the table's width; bf16
                            and int8 pools alike. Head dims of whole lane
                            tiles: grid (B,), a loop over the slot's LIVE
                            pages, `_pages_per_step` pages of every kv head
                            a step, copied from the pools in HBM into two
                            VMEM buffers (`_paged_gqa_body`). Narrower heads
                            (and equal heads narrower than a lane tile): grid
                            over the flattened list of live (slot, column)
                            pairs, a page of every kv head a step through
                            BlockSpecs (`_paged_gqa_list_kernel`)
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
                          register_constraint, vmem_row_cap)

_NEG_INF = -1e30

# default kv-block length each grid step streams through VMEM
BLOCK_S = 512
# below this block length the grid degenerates (near-prime max_seq) and
# the kernel warns to pad the cache
MIN_BLOCK_S = 32
# (kv head, token) rows one step of the paged GQA kernel fetches and
# scores at once. On one v5e at 32 slots x 8 kv heads x 64-token pages
# (PR 26's chip runs): 512 rows (a page a step) left the loop's fixed
# cost showing (0.129 ms a call at ~6 live pages a slot, 0.52 ms at 28),
# 2048 reads 0.094 / 0.33 ms, 4096 the same but 0.058 ms against 0.038
# where every slot holds one page
STEP_ROWS = 2048


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
    HBM bytes = q in + out + K/V — for the paged kernels the
    `B x n_blocks` POOL PAGES the block table names (plus their f32
    scale rows when quantized), never the whole pool. That is an UPPER
    BOUND for the paged kernels: shapes cannot see `lens`, and the
    grouped kernel copies a slot's live pages only (the equal-heads
    grid skips the dead ones' compute), so a launch over short slots
    moves fewer bytes than this says. Pure shape math (the
    KernelConstraint contract); None when the operand layout doesn't
    resolve."""
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
        _, hkv, page, _ = pool_s           # both paged kernels: rank 4
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
                "_gqa_contig_kernel", "_paged_gqa_kernel",
                "_paged_gqa_body", "_paged_gqa_list_kernel"),
    blocks={"block_s": BLOCK_S, "min_block_s": MIN_BLOCK_S,
            "step_rows": STEP_ROWS},
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
    kernel_fns=("_paged_decode_q8_kernel", "_paged_gqa_q8_kernel",
                "_paged_gqa_body", "_paged_gqa_list_kernel"),
    blocks={"block_s": BLOCK_S, "min_block_s": MIN_BLOCK_S,
            "step_rows": STEP_ROWS},
    note="int8 paged decode streams quantized pages + their "
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
                   acc_scr, *, block_size: int, scale: float):
    """Grouped-query decode body of the CONTIGUOUS grid (B, Hkv,
    n_blocks): each step streams ONE kv block of ONE kv head and scores
    the whole query group against it — the block never leaves VMEM at
    query-head width (reference GQA decode: block_attn.h with
    gqa_group_size)."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid_until = len_ref[b]

    @pl.when(j * block_size <= valid_until)
    def _compute():
        # grouped decode has real matmuls (group >= 2 rows), so the MXU
        # does the scoring — unlike the equal-heads kernels' batched
        # matvec, these 2-D dots lower cleanly at any D
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [group, bs]
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
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == nb - 1)
    def _final():
        o_ref[0] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)


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


def _pages_per_step(n_blocks: int, hkv: int, page: int, d: int,
                    itemsize: int) -> int:
    """Pages one loop step of the paged GQA kernel fetches and scores:
    enough for `STEP_ROWS` (kv head, token) rows, no more than the table
    is wide, and K and V double-buffered under the VMEM budget."""
    want = max(1, STEP_ROWS // (hkv * page))
    return min(want, n_blocks,
               vmem_row_cap(hkv * page * d * itemsize, n_buffers=4))


def _step_columns(hq: int, hkv: int, page: int, pps: int):
    """A paged GQA step scores `pps` pages of every kv head as one
    [Hq, pps*Hkv*page] product; column c is (page i, kv head h, token t)
    of the step. Returns each column's offset from the step's first
    position — past every length where the column is another kv head's
    than the query head's (row's) own — and the page i it lies in."""
    shape = (hq, pps * hkv * page)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col_page = col // (hkv * page)
    offset = jnp.where((col // page) % hkv == row // (hq // hkv),
                       col_page * page + col % page, 2 ** 30)
    return offset, col_page


def _head_scales(page_rows, hq: int, hkv: int, col_page):
    """int8 pools: `page_rows` holds, for each page of a step, its
    [1, lanes] row of per-kv-head f32 scales (lanes >= Hkv, padded).
    Returns what rescales the step's [Hq, rows] scores: each query
    head's own kv head's scale of the page a column belongs to ([Hq, 1]
    at one page a step)."""
    lanes = page_rows[0].shape[-1]
    own_head = (jax.lax.broadcasted_iota(jnp.int32, (hq, lanes), 1)
                == jax.lax.broadcasted_iota(jnp.int32, (hq, lanes), 0)
                // (hq // hkv))
    out = None
    for i, sc in enumerate(page_rows):
        sc = jnp.sum(jnp.where(own_head, sc, 0.0), axis=1, keepdims=True)
        out = sc if out is None else jnp.where(col_page >= i, sc, out)
    return out


def _gqa_softmax_step(q, k, v, live, carry, scale, k_sc=None, v_sc=None):
    """One step of the paged GQA kernels' online softmax: q [Hq, D]
    against the step's rows k, v [rows, D]; `live` [Hq, rows] marks a
    query head's own kv head's columns at positions <= lens. bf16
    products (f32 for int8 pools, whose scales `k_sc`/`v_sc` rescale the
    scores after the dot and the probabilities before the weighted
    sum), f32 scores, f32 running max / sum / accumulator."""
    m_prev, l_prev, acc = carry
    if k_sc is not None:
        q, k = q.astype(jnp.float32), k.astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # [Hq, rows]
    if k_sc is not None:
        s = s * k_sc
    s = jnp.where(live, s, _NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
    if v_sc is not None:
        p = p * v_sc
    pv = jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # [Hq, D]
    return m_new, l_new, acc * corr + pv


def _first_live_page(length, page: int, window):
    """The table column of the oldest position a query at `length` sees:
    0, or under a window of `window` positions (the query's own included)
    the page of position `length - window + 1`."""
    if window is None:
        return 0
    return jnp.maximum(length - (window - 1), 0) // page


def _paged_gqa_body(tables_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf,
                    v_buf, sem, cur_ref, *, scale: float, window=None,
                    ksc_hbm=None, vsc_hbm=None, ksc_buf=None, vsc_buf=None):
    """Paged grouped-query decode, grid (B,): one grid step is one slot,
    and inside it a loop visits the slot's LIVE pages only —
    `lens[b] // page + 1` of them, `pps` (= k_buf.shape[1]) a step. Under
    a `window` the loop starts at the page of position `lens[b] - window
    + 1` (`_first_live_page`): pages wholly behind the window are never
    fetched, and the mask cuts inside the first one.

    The pools stay in HBM; a step's pages, every kv head of each (one
    contiguous [Hkv, page, D] slab), are fetched by async copies into
    one of two VMEM buffers while the other is scored, and the last
    step of a slot already fetches the first pages of the next slot, so
    only the call's very first fetch is exposed. Which buffer is
    current survives from one grid step to the next in `cur_ref`.

    A step scores all query heads against all its rows at once
    (`_gqa_softmax_step`) and masks the columns that belong to another
    kv head or lie past `lens[b]`. Where a slot's last step runs over
    its last live page, the pages beyond are not fetched (a dead table
    column costs no copy) and their columns are masked. Online softmax
    in f32 over the steps, one division at the end.

    int8 pools: `ksc_hbm`/`vsc_hbm` are the per-(page, kv head) f32
    absmax scales, [P, Hkv] padded to whole lane tiles (a row narrower
    than a tile cannot be sliced out by a copy); a page's row travels
    with the page (`ksc_buf`/`vsc_buf` [2, pps, 1, lanes]) — the
    dequantized pool never materializes."""
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    w = tables_ref.shape[1]
    hq = q_ref.shape[1]
    _, pps, hkv, page, d = k_buf.shape
    rows = pps * hkv * page
    quant = ksc_hbm is not None

    def last_page(slot):                   # the slot's last live column
        return jnp.minimum(len_ref[slot] // page, w - 1)

    def first_page(slot):
        return _first_live_page(len_ref[slot], page, window)

    def copies(slot, step, buf, act):
        """Start or wait for the copies of a step's live pages."""
        last = last_page(slot)
        for i in range(pps):
            col = first_page(slot) + step * pps + i

            @pl.when(col <= last)
            def _live(i=i, col=col):
                pid = tables_ref[slot, col]
                act(pltpu.make_async_copy(k_hbm.at[pid], k_buf.at[buf, i],
                                          sem.at[0, buf]))
                act(pltpu.make_async_copy(v_hbm.at[pid], v_buf.at[buf, i],
                                          sem.at[1, buf]))
                if quant:
                    row = pl.ds(pid, 1)
                    act(pltpu.make_async_copy(
                        ksc_hbm.at[row], ksc_buf.at[buf, i], sem.at[0, buf]))
                    act(pltpu.make_async_copy(
                        vsc_hbm.at[row], vsc_buf.at[buf, i], sem.at[1, buf]))

    def fetch(slot, step, buf):
        copies(slot, step, buf, lambda cp: cp.start())

    @pl.when(b == 0)
    def _first_fetch():
        cur_ref[0] = 0
        # a page a step does not fetch keeps what the buffer held: its
        # columns are masked, but 0 x v must not meet what an unwritten
        # buffer may hold
        v_buf[...] = jnp.zeros_like(v_buf)
        if quant:
            vsc_buf[...] = jnp.zeros_like(vsc_buf)
        fetch(0, 0, 0)

    offset, col_page = _step_columns(hq, hkv, page, pps)
    valid_until = len_ref[b]
    first = first_page(b)
    n_steps = (last_page(b) - first) // pps + 1

    def step_fn(step, carry):
        *state, cur = carry
        more = step + 1 < n_steps
        nxt_slot = jnp.where(more, b, b + 1)

        @pl.when(nxt_slot < n_slots)
        def _fetch_next():
            fetch(nxt_slot, jnp.where(more, step + 1, 0), 1 - cur)

        copies(b, step, cur, lambda cp: cp.wait())
        k_sc = v_sc = None
        if quant:
            k_sc, v_sc = (
                _head_scales([buf[cur, i] for i in range(pps)], hq, hkv,
                             col_page) for buf in (ksc_buf, vsc_buf))
        base = (first + step * pps) * page   # the step's first position
        live = offset <= valid_until - base
        if window is not None:
            live &= offset > valid_until - window - base
        state = _gqa_softmax_step(
            q_ref[0], k_buf[cur].reshape(rows, d),
            v_buf[cur].reshape(rows, d), live, state, scale, k_sc, v_sc)
        return *state, 1 - cur

    _, l_fin, acc, cur = jax.lax.fori_loop(
        0, n_steps, step_fn,
        (jnp.full((hq, 1), _NEG_INF, jnp.float32),
         jnp.zeros((hq, 1), jnp.float32),
         jnp.zeros((hq, d), jnp.float32), cur_ref[0]))
    cur_ref[0] = cur
    o_ref[0] = (acc / l_fin).astype(o_ref.dtype)


def _paged_gqa_kernel(tables_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                      k_buf, v_buf, sem, cur_ref, *, scale: float,
                      window=None):
    _paged_gqa_body(tables_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf,
                    v_buf, sem, cur_ref, scale=scale, window=window)


def _paged_gqa_q8_kernel(tables_ref, len_ref, q_ref, k_hbm, v_hbm, ksc_hbm,
                         vsc_hbm, o_ref, k_buf, v_buf, sem, cur_ref,
                         ksc_buf, vsc_buf, *, scale: float, window=None):
    _paged_gqa_body(tables_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf,
                    v_buf, sem, cur_ref, scale=scale, window=window,
                    ksc_hbm=ksc_hbm, vsc_hbm=vsc_hbm, ksc_buf=ksc_buf,
                    vsc_buf=vsc_buf)


# rows of an int8 pool's scales one block of the listed kernel holds
_SCALE_ROWS = 8


def _paged_gqa_list_kernel(tables_ref, len_ref, slot_ref, col_ref, n_ref,
                           q_ref, k_ref, v_ref, *rest, scale: float,
                           window=None):
    """Paged grouped-query decode where the head dim is not a whole
    number of lane tiles (a copy cannot slice such a page out of the
    pool, so `_paged_gqa_body` does not lower): grid (N,) over the
    flattened list of LIVE (slot, table column) pairs that XLA builds
    from `lens` (`_live_page_list`). BlockSpec index maps read the
    list — one page of every kv head a step — and a slot's items are
    consecutive, so its softmax state sits in scratch from its first
    item to its last. N is the static bound B x W: steps past the
    list's end name the last item again (no copy) and do nothing.

    int8 pools: `rest` leads with the two scale blocks, `_SCALE_ROWS`
    rows of the lane-padded [P, Hkv] scales around the page's own."""
    *scales, o_ref, m_scr, l_scr, acc_scr = rest
    i = pl.program_id(0)
    slot, col = slot_ref[i], col_ref[i]
    listed = i < n_ref[0]
    hq = q_ref.shape[1]
    _, hkv, page, d = k_ref.shape
    valid_until = len_ref[slot]

    @pl.when(listed & (col == _first_live_page(valid_until, page, window)))
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(listed)
    def _step():
        offset, col_page = _step_columns(hq, hkv, page, 1)
        live = offset <= valid_until - col * page
        if window is not None:
            live &= offset > valid_until - window - col * page
        m_scr[...], l_scr[...], acc_scr[...] = _gqa_softmax_step(
            q_ref[0], k_ref[0].reshape(hkv * page, d),
            v_ref[0].reshape(hkv * page, d), live,
            (m_scr[...], l_scr[...], acc_scr[...]), scale,
            *(_head_scales(
                [sc[pl.ds(tables_ref[slot, col] % _SCALE_ROWS, 1), :]],
                hq, hkv, col_page) for sc in scales))

    @pl.when(listed & (col == jnp.minimum(valid_until // page,
                                          tables_ref.shape[1] - 1)))
    def _final():
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def _live_page_list(lens, page: int, w: int, n: int, window=None):
    """The live (slot, table column) pairs in order, padded to `n` with
    the last pair, and their count: what `_paged_gqa_list_kernel`'s
    grid walks. Under a `window` a slot's columns start at the window's
    first page."""
    first = _first_live_page(lens, page, window)             # [B] (or 0)
    n_live = jnp.minimum(lens // page, w - 1) + 1 - first    # [B]
    ends = jnp.cumsum(n_live)
    item = jnp.minimum(jnp.arange(n, dtype=jnp.int32), ends[-1] - 1)
    slot = jnp.searchsorted(ends, item, side="right").astype(jnp.int32)
    return (slot, item - (ends - n_live)[slot]
            + jnp.broadcast_to(first, lens.shape)[slot], ends[-1:])


@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "window"))
def _paged_decode_gqa(q, key_cache, value_cache, block_tables, lens,
                      k_scale=None, v_scale=None, *, scale: float,
                      interpret: bool, window=None):
    """Launch the paged GQA decode. Head dims of whole lane tiles take
    `_paged_gqa_body`: q and the output ride BlockSpecs (one slot's
    [Hq, D] a grid step), the rank-4 pools — and an int8 pool's scales —
    are handed over whole in HBM, and the table and the lengths are
    scalar-prefetched so the kernel can name the pages it copies.
    Narrower heads take `_paged_gqa_list_kernel`, whose pages ride
    BlockSpecs too. Both see an int8 pool's [P, Hkv] scales padded to
    whole lane tiles.

    Jitted so that a program which calls it once a layer traces and
    lowers the kernel once: 21 separate traces made a serving program's
    lowering 1.3 s longer, and the benchmark's set-up 10 s (PR 26)."""
    b, hq, d = q.shape
    n_pages, hkv, page, _ = key_cache.shape
    w = block_tables.shape[1]
    quant = k_scale is not None
    tables = block_tables.astype(jnp.int32)
    lens = lens.astype(jnp.int32)
    operands = [q, key_cache, value_cache]
    lanes = hkv + -hkv % LANE
    if quant:
        pad = (0, -n_pages % _SCALE_ROWS), (0, lanes - hkv)
        operands += [jnp.pad(k_scale.astype(jnp.float32), pad),
                     jnp.pad(v_scale.astype(jnp.float32), pad)]
    name = (CONSTRAINT_Q8 if quant else CONSTRAINT).name
    if window is not None:
        # its own label in the device trace: the windowed layers' reads
        # are a different quantity from the full layers'
        name += "_window"
    out_shape = jax.ShapeDtypeStruct((b, hq, d), q.dtype)
    # both carry state from one grid step to the next: in order
    params = pltpu.CompilerParams(dimension_semantics=("arbitrary",))

    if d % LANE:
        slot, col, n_items = _live_page_list(lens, page, w, b * w, window)

        def slot_map(i, tbl, lens_, slot_, col_, n_):
            return (slot_[i], 0, 0)

        def page_map(i, tbl, lens_, slot_, col_, n_):
            return (tbl[slot_[i], col_[i]], 0, 0, 0)

        def scale_map(i, tbl, lens_, slot_, col_, n_):
            return (tbl[slot_[i], col_[i]] // _SCALE_ROWS, 0)

        return pl.pallas_call(
            functools.partial(_paged_gqa_list_kernel, scale=scale,
                              window=window),
            name=name,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(b * w,),
                in_specs=[pl.BlockSpec((1, hq, d), slot_map)]
                + [pl.BlockSpec((1, hkv, page, d), page_map)] * 2
                + [pl.BlockSpec((_SCALE_ROWS, lanes), scale_map)]
                * (2 * quant),
                out_specs=pl.BlockSpec((1, hq, d), slot_map),
                scratch_shapes=[pltpu.VMEM((hq, 1), jnp.float32),
                                pltpu.VMEM((hq, 1), jnp.float32),
                                pltpu.VMEM((hq, d), jnp.float32)],
            ),
            out_shape=out_shape, compiler_params=params,
            interpret=interpret,
        )(tables, lens, slot, col, n_items, *operands)

    # a window's pages are few: no wider a step than they are
    w_live = w if window is None else min(w, -(-(window - 1) // page) + 1)
    pps = _pages_per_step(w_live, hkv, page, d, key_cache.dtype.itemsize)

    def slot_map(b_, tbl, lens_):
        return (b_, 0, 0)

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(
            _paged_gqa_q8_kernel if quant else _paged_gqa_kernel,
            scale=scale, window=window),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, hq, d), slot_map)]
            + [in_hbm] * (len(operands) - 1),
            out_specs=pl.BlockSpec((1, hq, d), slot_map),
            scratch_shapes=[
                pltpu.VMEM((2, pps, hkv, page, d), key_cache.dtype),
                pltpu.VMEM((2, pps, hkv, page, d), value_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),   # (k | v, buffer)
                pltpu.SMEM((1,), jnp.int32),
            ] + [pltpu.VMEM((2, pps, 1, lanes), jnp.float32)] * (2 * quant),
        ),
        out_shape=out_shape, compiler_params=params, interpret=interpret,
    )(tables, lens, *operands)


def paged_decode_attention(q: jax.Array, key_cache: jax.Array,
                           value_cache: jax.Array, block_tables: jax.Array,
                           lens: jax.Array,
                           scale: float | None = None, *,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           window: int | None = None) -> jax.Array:
    """One decode step over a paged cache (reference: block_attn.h).

    q: [B, Hq, D]; key_cache/value_cache: [max_pages, Hkv, block_size, D]
    with Hq a multiple of Hkv (grouped queries take the live-page loop
    of `_paged_gqa_body`, whose cost follows `lens`; equal heads the
    all-heads-per-page grid); block_tables: [B, n_blocks] page
    ids covering positions [0, n_blocks*block_size); lens: [B]
    previous-token counts (current token already written at position
    lens[b]). Returns [B, Hq, D].

    `window` (a layer of sliding-window attention): the query at position
    lens[b] sees positions (lens[b] - window, lens[b]] only; the live-page
    loop starts at the window's first page, so a table column behind the
    window is never read — it may name a page that has since been given to
    a later position (a per-sequence ring: column j -> ring page j % R).

    int8 pools (``FLAGS_kv_cache_dtype=int8``): pass the per-(page, kv
    head) f32 absmax scale arrays as ``k_scale``/``v_scale``
    [max_pages, Hkv] — each step then streams the int8 pages plus
    their scales and rescales inside the f32 accumulation; the
    dequantized bf16 pool never materializes.

    Head counts (and therefore the GQA group) derive from the OPERAND
    shapes, never a model config: under tensor-parallel serving
    (FLAGS_serving_mp) this call sees the shard-LOCAL q heads and pool
    kv heads inside shard_map, so both the kv-head-sharded call and
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
    if window is not None and window < 1:
        raise ValueError(f"window {window} must be at least 1")
    if h != hkv or d % LANE or window is not None:
        # grouped queries — or narrow head dims, where the equal-heads
        # kernel's [H, 1, D] broadcast fails to lower (see
        # decode_attention); the grouped kernel's 2-D dots cover
        # group=1 too, and it alone knows a window
        if h % hkv:
            raise ValueError(f"Hq {h} not a multiple of Hkv {hkv}")
        return _paged_decode_gqa(q, key_cache, value_cache, block_tables,
                                 lens, k_scale, v_scale, scale=scale,
                                 interpret=not _on_tpu(), window=window)
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
