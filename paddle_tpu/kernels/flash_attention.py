"""Flash attention as a Pallas TPU kernel.

TPU-native counterpart of the reference's flash_attn op family
(paddle/phi/ops/yaml/ops.yaml:1765-1777, kernel
paddle/phi/kernels/gpu/flash_attn_kernel.cu): online-softmax tiled attention
that never materialises the [S, S] score matrix. The forward runs on the MXU
with fp32 accumulators in VMEM scratch; the backward recomputes each block of
scores ONCE from q/k and the forward's row statistic and takes dq, dk and dv
from it in one kernel (flash-attention-2 recompute strategy, one pass).

Public layout matches paddle: [batch, seqlen, num_heads, head_dim]; GQA/MQA
(fewer kv heads) is supported by routing each query head to its kv head in
the BlockSpec index maps (no materialised repeat in the forward).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._vma import like_primal, operand_vma
from .constraints import KernelConstraint, LANE, register_constraint

_NEG_INF = -1e30

# default seq tiling of the forward kernel: its grid walks the kv axis in
# BLOCK_K steps with BLOCK_Q query rows resident in VMEM (clamped to the
# actual seq len; seq lens must then divide the clamped block)
BLOCK_Q = 512
BLOCK_K = 512
# on a TPU, equal or grouped heads on long 512-divisible sequences at a
# lane-aligned head (`_wide_blocks_ok`) tile the forward at 1024 rows (at
# [1, 8192, 32/4, 128] the causal forward takes 4.93 ms so, 7.96 at 512: my
# chip runs, PR 35). The backward tiles at 1024 rows at a head of one lane
# tile, fewer at wider heads (`_bwd_block_cap`)
FAST_PATH_BLOCK = 1024
FAST_PATH_SEQ_MULTIPLE = 512
# ... and at most 512 under a window: of a band of 2,048 keys 1024-row blocks
# sweep 1.5 x the mask, 512-row blocks 1.25 x (the backward at that shape:
# 5.54 against 5.25 ms; the forward is faster at 1024 all the same, 2.89
# against 3.97 ms: my chip runs, PR 35)
WINDOW_BWD_BLOCK = 512
# the widest head whose forward takes the 1024-row blocks (compiled for the
# chip up to here: the backward works in blocks of 256 rows at 384 and 512)
WIDE_BLOCK_MAX_HEAD = 512
# scoped VMEM on a v5e: what Mosaic gives a kernel unasked, and the most the
# backward asks for of a core's 128 MiB. Beside dq's whole-sequence f32
# accumulator its blocks and intermediates take 8.3 MiB at 1024 rows x 128
# (the compiler's own count; less at the wider heads' smaller blocks)
VMEM_DEFAULT_BYTES = 16 << 20
VMEM_MAX_BYTES = 96 << 20
BWD_VMEM_BESIDE_DQ_BYTES = 12 << 20


def _wide_blocks_ok(sq, sk, hq, hk, dh) -> bool:
    """Shapes whose forward tiles at FAST_PATH_BLOCK rows on a TPU: equal or
    grouped heads — in the core's layout, batch * heads — on long
    block-divisible sequences at a lane-aligned head."""
    return (hq % hk == 0 and dh % LANE == 0 and dh <= WIDE_BLOCK_MAX_HEAD
            and sq % FAST_PATH_SEQ_MULTIPLE == 0
            and sk % FAST_PATH_SEQ_MULTIPLE == 0 and sq == sk)


def _block_rows(seq: int, cap: int) -> int:
    """Rows of a block of at most `cap` (a power of two) rows: the largest
    power of two up to it that divides the sequence, so that a block stays a
    lane multiple. A sequence no longer than the cap, or one that no lane
    multiple of such rows divides, is one block."""
    rows = math.gcd(seq, cap)
    return rows if seq > cap and rows % LANE == 0 else seq


def _bwd_block_cap(d: int, window: Optional[int] = None) -> int:
    """The most rows of a backward block: FAST_PATH_BLOCK at a head of one
    lane tile, fewer as the head widens (the kernel holds q, k, v, do, dk and
    dv blocks and four `[block_k, block_q]` f32 intermediates at once), by
    powers of two: 128 -> 1024, 256 -> 512, 384 and 512 -> 256; under a
    window no more than WINDOW_BWD_BLOCK."""
    cap = min(FAST_PATH_BLOCK,
              1 << (FAST_PATH_BLOCK * LANE // d).bit_length() - 1)
    return cap if window is None else min(cap, WINDOW_BWD_BLOCK)


def _fwd_blocks(q_shape, k_shape, on_tpu: bool):
    """(block_q, block_k) of the forward kernel on q [BH, Sq, D] and k
    [BKVH, Sk, D]."""
    (bh, sq, d), (bkv, sk) = q_shape, k_shape[:2]
    if on_tpu and _wide_blocks_ok(sq, sk, bh, bkv, d):
        return (_block_rows(sq, FAST_PATH_BLOCK),
                _block_rows(sk, FAST_PATH_BLOCK))
    return min(BLOCK_Q, sq), min(BLOCK_K, sk)


def _bwd_vmem_bytes(sq: int, d: int) -> int:
    """Scoped VMEM the one-pass backward needs: dq's whole-sequence f32
    accumulator and what its blocks take beside it."""
    return sq * d * 4 + BWD_VMEM_BESIDE_DQ_BYTES


def _bwd_refusal(sq: int, d: int) -> Optional[str]:
    """Why the one-pass backward does not take `sq` query rows at head size
    `d`, or None where it does: up to sq * d = 22M, which is 172,032 rows at
    a head of 128, 86,016 at 256, 43,008 at 512 (compiled for the chip there,
    `tests/test_chip_compile.py`)."""
    if _bwd_vmem_bytes(sq, d) <= VMEM_MAX_BYTES:
        return None
    return (f"the flash-attention backward keeps dq for all {sq} query rows "
            f"at head size {d} in VMEM ({sq * d * 4 >> 20} MiB in f32) and "
            f"takes {VMEM_MAX_BYTES - BWD_VMEM_BESIDE_DQ_BYTES >> 20} MiB; "
            "split the sequence over chips (ring or Ulysses attention) or "
            "differentiate it in shorter pieces")


def _check_attention_shapes(shapes, dtypes):
    """Checker for the fwd/bwd pallas calls: q [BH, Sq, D], k/v
    [BKVH, Sk, D] (bwd appends do/lse/delta operands — same leading trio).
    The blocks are the forward's on the chip (`_fwd_blocks`); the backward's
    (`_block_rows` under `_bwd_block_cap`) divide whatever those divide."""
    out = []
    if len(shapes) < 3:
        return out
    q, k = shapes[0], shapes[1]
    if len(q) == 3 and len(k) == 3:
        bh, sq, d = q
        bkv, sk = k[0], k[1]
        block_q, block_k = _fwd_blocks(q, k, True)
        if d % LANE:
            out.append(("warning",
                        f"head_dim {d} is not a multiple of the {LANE}-"
                        "lane tile; VMEM pads every row to "
                        f"{-(-d // LANE) * LANE} lanes"))
        if sq % block_q:
            out.append(("error",
                        f"q seq len {sq} does not divide the "
                        f"{block_q} query block; the kernel "
                        "raises at call time"))
        if sk % block_k:
            out.append(("error",
                        f"kv seq len {sk} does not divide the "
                        f"{block_k} kv block; the kernel "
                        "raises at call time"))
        if bkv and bh % bkv:
            out.append(("error",
                        f"q heads*batch {bh} not a multiple of kv "
                        f"heads*batch {bkv}; GQA grouping requires "
                        "Hq % Hkv == 0"))
        if _bwd_refusal(sq, d):
            out.append(("warning", _bwd_refusal(sq, d)
                        + " (differentiating this call raises)"))
    return out


def _flash_attention_roofline(shapes, dtypes):
    """Roofline model for one flash-attention launch: FLOPs =
    qk^T + p·v = 4·BH·Sq·Sk·D (full-mask upper bound — causality is a
    kernel param invisible to shape math), HBM bytes = q/k/v in + out.
    The whole point of the kernel is that the [Sq, Sk] score matrix
    never round-trips HBM, so intensity ~ O(S) and the static pass
    classifies it compute-bound — TPU901 stays silent here. Covers the
    backward kernels too (same O(S^2 D) shape class). Pure shape math;
    None when the layout doesn't resolve."""
    from .constraints import dtype_itemsize

    arrs = [(s, d) for s, d in zip(shapes, dtypes) if len(s) >= 3]
    if len(arrs) < 3:
        return None
    (q_s, q_d), (k_s, _), _ = arrs[0], arrs[1], arrs[2]
    bh, sq, d = q_s[0], q_s[-2], q_s[-1]
    sk = k_s[-2]
    io_bytes = sum(math.prod(s) * dtype_itemsize(dt)
                   for s, dt in arrs[:3])
    out_bytes = math.prod(q_s) * dtype_itemsize(q_d)
    return {"flops": 4 * bh * sq * sk * d,
            "hbm_bytes": io_bytes + out_bytes}


CONSTRAINT = register_constraint(KernelConstraint(
    name="flash_attention",
    kernel_fns=("_fwd_kernel", "_bwd_kernel"),
    blocks={"block_q": BLOCK_Q, "block_k": BLOCK_K,
            "wide_block": FAST_PATH_BLOCK},
    note="online-softmax tiled attention; seq lens must divide the "
         "(clamped) q/kv blocks — `wide_block` rows for equal heads on long "
         "512-divisible sequences on a TPU, and in the backward, there fewer "
         "as the head widens — and head_dim should be 128-lane aligned",
    checker=_check_attention_shapes,
    source="flash_attention.py",
    roofline=_flash_attention_roofline,
))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# a window: row t sees keys s with 0 <= t - s < window (Sq == Sk). The kernels
# walk, of each q block (forward) or k block (backward), the span of blocks
# the band crosses, and visit nothing behind it.
# ---------------------------------------------------------------------------

def _check_window(causal: bool, sq: int, sk: int) -> None:
    if not causal or sq != sk:
        raise ValueError("a window is causal self-attention: causal=True and "
                         f"equal sequence lengths (got causal={causal}, "
                         f"{sq} and {sk})")


def _kv_span(qi, block_q: int, block_k: int, window: int, mx=jnp.maximum):
    """(first, last) k block that q block `qi` sees inside the window; `mx`
    is `max` for Python ints, `jnp.maximum` inside a kernel or an index map."""
    return (mx(qi * block_q - (window - 1), 0) // block_k,
            (qi * block_q + block_q - 1) // block_k)


def _q_span(ki, block: int, nq: int, window: int, mn=jnp.minimum):
    """(first, last) q block that sees k block `ki` inside the window, at
    equal blocks: from the diagonal down to the window's far edge."""
    return ki, mn((ki * block + block - 1 + window - 1) // block, nq - 1)


def _span_blocks(span) -> int:
    return span[1] - span[0] + 1


def window_pairs(seq: int, heads: int, kv_heads: int, head_dim: int,
                 window: int, on_tpu: bool):
    """(query, key) pairs of ONE head of ONE sequence: (those of the score
    blocks `flash_attention_window_fwd` sweeps, those `_window_bwd` sweeps,
    those inside the mask: `window` keys a row, fewer for the first rows).
    Constants of the shapes, from the same block arithmetic the kernels'
    grids are built with; a sequence off the forward's blocks takes the jnp
    form, which masks the whole square."""
    w = min(window, seq)
    mask = w * seq - w * (w - 1) // 2
    bq, bk = _fwd_blocks((heads, seq, head_dim), (kv_heads, seq), on_tpu)
    if seq % bq or seq % bk:
        return seq * seq, seq * seq, mask
    b = _block_rows(seq, _bwd_block_cap(head_dim, window))
    fwd = sum(_span_blocks(_kv_span(i, bq, bk, window, max))
              for i in range(seq // bq)) * bq * bk
    bwd = sum(_span_blocks(_q_span(j, b, seq // b, window, min))
              for j in range(seq // b)) * b * b
    return fwd, bwd, mask


# ---------------------------------------------------------------------------
# forward kernel: grid (batch*q_heads, num_q_blocks, num_k_blocks)
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, causal: bool, scale: float,
                block_q: int, block_k: int, q_offset: int, lse_rows: bool,
                window: Optional[int] = None):
    """q_offset = sk - sq aligns the causal diagonal to the END of the kv
    sequence (paddle/flash-attn convention: the last q row sees all keys).
    `lse_rows`: the row statistic leaves as ONE lane-dense row `[1, block_q]`
    (a block of whole lane tiles can be transposed) instead of replicated
    across 128 lanes. With a `window` the last grid axis walks the q block's
    own span of k blocks (`_kv_span`), not the whole kv axis."""
    qi = pl.program_id(1)
    step = ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    if window is not None:
        # the grid holds the widest span: a q block near the start has fewer
        first, last = _kv_span(qi, block_q, block_k, window)
        ki = first + step
        run = ki <= last
    else:
        # causal: skip k blocks strictly above the diagonal band
        run = ((qi * block_q + block_q - 1 + q_offset >= ki * block_k)
               if causal else True)

    @pl.when(run)
    def _compute():
        q = q_ref[0]                      # [block_q, d]
        k = k_ref[0]                      # [block_k, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * block_q + q_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            seen = qpos >= kpos
            if window is not None:
                seen = jnp.logical_and(seen, qpos - kpos < window)
            s = jnp.where(seen, s, _NEG_INF)
        m_prev = m_scr[...]               # [block_q, 128] (row stat replicated)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev[:, :1] - m_new[:, :1])
        p = jnp.exp(s - m_new[:, :1])
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(step == nk - 1)
    def _final():
        o_ref[0] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)
        # the scratch holds the row statistic replicated across 128 lanes
        lse = m_scr[...] + jnp.log(l_scr[...])
        lse_ref[0] = lse.T[:1] if lse_rows else lse


def _fwd_pallas(q, k, v, causal: bool, scale: float,
                block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                interpret: Optional[bool] = None,
                window: Optional[int] = None):
    """q: [BH, Sq, D]; k/v: [BKVH, Sk, D]. Returns (out [BH, Sq, D],
    lse [BH, Sq] fp32, the row statistic the backward starts from). The
    kernel writes it one f32 a row where its q block is whole lane tiles,
    and replicated across 128 lanes (lane 0 kept here) where it is not.
    With a `window` (causal, Sq == Sk) the grid's last axis is as long as the
    widest span of k blocks a q block sees, and the blocks behind the window
    are never visited: `flash_attention_window_fwd`."""
    bh, sq, d = q.shape
    bkv, sk, _ = k.shape
    rep = bh // bkv                      # q heads per kv head (GQA)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(f"seq lens ({sq},{sk}) not divisible by blocks "
                         f"({block_q},{block_k})")
    nk = sk // block_k
    if window is not None:
        _check_window(causal, sq, sk)
        nk = max(_span_blocks(_kv_span(i, block_q, block_k, window, max))
                 for i in range(sq // block_q))
    grid = (bh, sq // block_q, nk)
    vma = operand_vma(q, k, v)
    q_offset = sk - sq
    lse_rows = block_q % LANE == 0
    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, q_offset=q_offset, lse_rows=lse_rows, window=window)

    def kv_map(b, i, j):
        # a kv block the causal band skips is not fetched: the index stays
        # on the last one this q block needs
        if window is not None:
            first, last = _kv_span(i, block_q, block_k, window)
            j = jnp.minimum(first + j, last)
        elif causal:
            j = jnp.minimum(j, jnp.maximum(
                (i * block_q + block_q - 1 + q_offset) // block_k, 0))
        return (b // rep, j, 0)

    if lse_rows:
        lse_spec = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))
        lse_shape = (bh, 1, sq)
    else:
        lse_spec = pl.BlockSpec((1, block_q, LANE), lambda b, i, j: (b, i, 0))
        lse_shape = (bh, sq, LANE)
    out, lse = pl.pallas_call(
        kernel,
        name=CONSTRAINT.name + ("_fwd" if window is None else "_window_fwd"),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            lse_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype, vma=vma),
            jax.ShapeDtypeStruct(lse_shape, jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=not _on_tpu() if interpret is None else interpret,
    )(q, k, v)
    return out, lse[:, 0, :] if lse_rows else lse[:, :, 0]


# ---------------------------------------------------------------------------
# jnp reference core (oracle + odd-shape fallback), layout [BH, S, D]
# ---------------------------------------------------------------------------

def _seen(sq: int, sk: int, window: Optional[int]):
    """[Sq, Sk] bool: the causal mask aligned to the end of the keys, and
    inside it the window's band."""
    mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
    if window is not None:
        mask = jnp.logical_and(mask, jnp.triu(jnp.ones((sq, sk), bool),
                                              k=sk - sq - (window - 1)))
    return mask


def _fwd_ref(q, k, v, causal: bool, scale: float,
             window: Optional[int] = None):
    bh, sq, d = q.shape
    bkv, sk, _ = k.shape
    if bkv != bh:
        rep = bh // bkv
        k = jnp.repeat(k, rep, axis=0)
        v = jnp.repeat(v, rep, axis=0)
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    if causal:
        s = jnp.where(_seen(sq, sk, window), s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bqk,bkd->bqd", (p / l).astype(q.dtype), v)
    return out


def _pallas_ok(q, k, v, on_tpu: bool, window: Optional[int] = None):
    """Whether the in-repo kernels take these operands — decided here,
    before the call, so whatever the kernel then raises propagates."""
    block_q, block_k = _fwd_blocks(q.shape, k.shape, on_tpu)
    if q.shape[1] % block_q or k.shape[1] % block_k \
            or q.shape[0] % k.shape[0]:
        return False
    if window is not None and q.shape[1] != k.shape[1]:
        return False
    # jax's Pallas interpreter cannot run under a vma-checked shard_map
    return on_tpu or not operand_vma(q, k, v)


# ---------------------------------------------------------------------------
# backward kernel: dq, dk and dv from one pass over the score blocks
# ---------------------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *,
                causal: bool, scale: float, block_q: int, block_k: int,
                q_offset: int, window: Optional[int] = None):
    """Grid (batch*q_heads, k blocks, q blocks), q innermost. Each block of
    scores is computed once, TRANSPOSED (`[block_k, block_q]`: k rows, q
    columns), so that the per-row terms `lse` and `delta` come in as one
    lane-dense row `[1, block_q]` and broadcast down the sublanes, and dv and
    dk are plain products. dk and dv accumulate over the inner axis in
    `[block_k, d]` scratch; dq accumulates over the OUTER axis in a
    whole-sequence `[sq, d]` f32 scratch and leaves block by block during the
    last k block's steps (`_bwd_pallas`'s dq index map). That scratch is what
    bounds the sequence: `_bwd_vmem_bytes` against `VMEM_MAX_BYTES`.

    With a `window` (equal blocks, Sq == Sk) the inner axis walks the k
    block's own span of q blocks (`_q_span`), from the diagonal down; q block
    i has its last k block on its diagonal, so dq's block i leaves at the
    first step of k block i."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nk = pl.num_programs(1)
    nq = pl.num_programs(2)
    step = qi
    if window is not None:
        first, last = _q_span(ki, block_q, dq_scr.shape[0] // block_q, window)
        qi = first + step
    rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)

    @pl.when(jnp.logical_and(ki == 0, step == 0))
    def _init_dq():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(step == 0)
    def _init_dkv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    if window is not None:
        # the grid holds the widest span: the last k blocks have fewer
        run = qi <= last
    else:
        # causal: skip q blocks strictly above the diagonal band
        run = ((qi * block_q + block_q - 1 + q_offset >= ki * block_k)
               if causal else True)

    @pl.when(run)
    def _compute():
        q = q_ref[0]                      # [block_q, d]
        k = k_ref[0]                      # [block_k, d]
        do = do_ref[0]
        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            qpos = qi * block_q + q_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            seen = qpos >= kpos
            if window is not None:
                seen = jnp.logical_and(seen, qpos - kpos < window)
            st = jnp.where(seen, st, _NEG_INF)
        pt = jnp.exp(st - lse_ref[0])                 # [block_k, block_q]
        dv_scr[...] += jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(
            v_ref[0], do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta_ref[0]) * scale).astype(q.dtype)
        dk_scr[...] += jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq_scr[rows, :] += jax.lax.dot_general(
            dst, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(step == nq - 1)
    def _final_dkv():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when(step == 0 if window is not None else ki == nk - 1)
    def _final_dq():
        dq_ref[0] = dq_scr[rows, :].astype(dq_ref.dtype)


def _bwd_pallas(q, k, v, out, lse, do, causal: bool, scale: float,
                interpret: bool, block_q: Optional[int] = None,
                block_k: Optional[int] = None,
                window: Optional[int] = None):
    """Flash backward from `lse` [BH, Sq] f32. Returns (dq [BH,Sq,D], dk/dv
    [BH,Sk,D] per q-head — caller reduces over GQA groups). The scoped VMEM
    limit follows dq's accumulator by arithmetic (`_bwd_vmem_bytes`): Mosaic's
    default up to 4 MiB of it (2048 x 128 and 4096 x 256, the trained cells),
    raised above that, and a sequence past `VMEM_MAX_BYTES` is the caller's
    to refuse (`_bwd_refusal`). With a `window` (causal, Sq == Sk, equal
    blocks) the inner axis is as long as the widest span of q blocks that see
    a k block, and the q blocks past the window's far edge are never
    visited: `flash_attention_window_bwd`."""
    bh, sq, d = q.shape
    bkv, sk, _ = k.shape
    rep = bh // bkv
    block_q = block_q or _block_rows(sq, _bwd_block_cap(d, window))
    block_k = block_k or _block_rows(sk, _bwd_block_cap(d, window))
    nq, nk = sq // block_q, sk // block_k
    q_offset = sk - sq
    steps = nq
    if window is not None:
        _check_window(causal, sq, sk)
        if block_q != block_k:
            raise ValueError(f"the window backward takes equal blocks, not "
                             f"({block_q},{block_k})")
        steps = max(_span_blocks(_q_span(j, block_q, nq, window, min))
                    for j in range(nk))
    vma = operand_vma(q, k, v, do)
    vmem = _bwd_vmem_bytes(sq, d)
    # one f32 a row, never broadcast: [BH, 1, Sq] puts a block's rows in lanes
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]
    lse = lse[:, None, :]

    def q_map(b, j, i):
        # a q block the causal band skips is not fetched: the index stays on
        # the first one this k block needs
        if window is not None:
            first, last = _q_span(j, block_q, nq, window)
            i = jnp.minimum(first + i, last)
        elif causal:
            i = jnp.maximum(i, jnp.clip((j * block_k - q_offset) // block_q,
                                        0, nq - 1))
        return (b, i, 0)

    def row_map(b, j, i):
        return (b, 0, q_map(b, j, i)[1])

    q_spec = pl.BlockSpec((1, block_q, d), q_map)
    row_spec = pl.BlockSpec((1, 1, block_q), row_map)
    kv_spec = pl.BlockSpec((1, block_k, d),
                           lambda b, j, i: (b // rep, j, 0))
    # dq is whole only after the last k block: until then the output window
    # rests on block 0, which nothing writes back before the index moves
    # (with a window, block j is whole at k block j's first step)
    dq_spec = pl.BlockSpec(
        (1, block_q, d), (lambda b, j, i: (b, j, 0)) if window is not None
        else lambda b, j, i: (b, jnp.where(j == nk - 1, i, 0), 0))
    dkv_spec = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k,
                          q_offset=q_offset, window=window),
        name=CONSTRAINT.name + ("_bwd" if window is None else "_window_bwd"),
        grid=(bh, nk, steps),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[dq_spec, dkv_spec, dkv_spec],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, d), q.dtype, vma=vma),
                   jax.ShapeDtypeStruct((bh, sk, d), k.dtype, vma=vma),
                   jax.ShapeDtypeStruct((bh, sk, d), v.dtype, vma=vma)],
        scratch_shapes=[pltpu.VMEM((sq, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem if vmem > VMEM_DEFAULT_BYTES else None),
        interpret=interpret,
    )(q, k, v, do, lse, delta)


# ---------------------------------------------------------------------------
# custom_vjp over the [BH, S, D] core, under ONE jit
# ---------------------------------------------------------------------------

def _fwd_core(q, k, v, causal, scale, on_tpu, window=None):
    """Returns (out, lse): lse is [BH, Sq] f32 from the kernel, or None behind
    the jnp form (whose backward recomputes the statistics)."""
    if _pallas_ok(q, k, v, on_tpu, window):
        return _fwd_pallas(q, k, v, causal, scale,
                           *_fwd_blocks(q.shape, k.shape, on_tpu),
                           interpret=not on_tpu, window=window)
    return _fwd_ref(q, k, v, causal, scale, window), None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_vjp(q, k, v, causal: bool, scale: float, on_tpu: bool,
               window: Optional[int] = None):
    return _fwd_core(q, k, v, causal, scale, on_tpu, window)[0]


def _flash_vjp_fwd(q, k, v, causal, scale, on_tpu, window):
    out, lse = _fwd_core(q, k, v, causal, scale, on_tpu, window)
    if lse is not None and _bwd_refusal(q.shape[1], q.shape[2]):
        raise ValueError(_bwd_refusal(q.shape[1], q.shape[2]))
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, scale, on_tpu, window, res, do):
    """FA2 backward: dv = P^T dO ; dS = P * (dO V^T - rowsum(dO*O)) * scale;
    dq = dS K ; dk = dS^T Q (reference math:
    paddle/phi/kernels/gpu/flash_attn_grad_kernel.cu via the flashattn
    library). The one-pass kernel when a forward kernel saved LSE; jnp
    recompute otherwise."""
    q, k, v, out, lse = res
    bh, sq, d = q.shape
    if lse is not None:
        dq, dk, dv = _bwd_pallas(q, k, v, out, lse, do, causal, scale,
                                 interpret=not on_tpu, window=window)
        rep = bh // k.shape[0]
        if rep > 1:
            dk = dk.reshape(k.shape[0], rep, *dk.shape[1:]).sum(1)
            dv = dv.reshape(v.shape[0], rep, *dv.shape[1:]).sum(1)
        return like_primal(dq, q), like_primal(dk, k), like_primal(dv, v)
    bkv, sk, _ = k.shape
    rep = bh // bkv
    kr = jnp.repeat(k, rep, axis=0) if rep > 1 else k
    vr = jnp.repeat(v, rep, axis=0) if rep > 1 else v
    s = jnp.einsum("bqd,bkd->bqk", q, kr).astype(jnp.float32) * scale
    if causal:
        s = jnp.where(_seen(sq, sk, window), s, _NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])                       # [BH, Sq, Sk] fp32
    do32 = do.astype(jnp.float32)
    dv = jnp.einsum("bqk,bqd->bkd", p, do32)
    dp = jnp.einsum("bqd,bkd->bqk", do32, vr.astype(jnp.float32))
    delta = jnp.sum(do32 * out.astype(jnp.float32), axis=-1)  # [BH, Sq]
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bqk,bkd->bqd", ds, kr.astype(jnp.float32))
    dk = jnp.einsum("bqk,bqd->bkd", ds, q.astype(jnp.float32))
    if rep > 1:
        dk = dk.reshape(bkv, rep, sk, d).sum(1)
        dv = dv.reshape(bkv, rep, sk, d).sum(1)
    return (like_primal(dq.astype(q.dtype), q),
            like_primal(dk.astype(k.dtype), k),
            like_primal(dv.astype(v.dtype), v))


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)

# N layers of a model share one trace and one private function of the lowered
# module, so a step's text holds each kernel's Mosaic payload once; what the
# trace depends on beside the operands (`jax.default_backend()`) is an
# argument, so that it is part of the jit's key
_flash_jit = jax.jit(_flash_vjp, static_argnums=(3, 4, 5, 6))


def _flash_core(q, k, v, causal: bool, scale: float,
                window: Optional[int] = None):
    """q: [BH, Sq, D]; k/v: [BKVH, Sk, D]; differentiable."""
    return _flash_jit(q, k, v, causal, scale, _on_tpu(), window)


# ---------------------------------------------------------------------------
# public API, paddle layout [B, S, H, D]
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    window: Optional[int] = None):
    """Differentiable flash attention; layout [B, S, H, D] (paddle
    flash_attn layout, ops.yaml:1765). kv heads may divide q heads (GQA).

    What runs where, chosen from shapes before the call (nothing a kernel
    raises is caught): everything goes through the jitted `[B*H, S, D]` core
    — the in-repo `flash_attention_fwd` where `_pallas_ok` holds (on a TPU at
    1024-row blocks for equal or grouped heads on long 512-divisible
    sequences, `_wide_blocks_ok`, at 512 otherwise), which hands one f32 a
    row to the one-pass `flash_attention_bwd`; the jnp form, which
    differentiates itself, otherwise. Grouped heads reach their kv head
    through the index maps, nothing is repeated; the backward returns dk and
    dv a q head and sums them over the group. One route for every shape:
    at `[1, 8192, 32/4, 128]` causal this pair takes 4.93 + 9.12 ms where
    splash, the kernel bundled with jax, takes 4.68 + 13.22 (my chip runs,
    PR 35).

    `window` (with `causal=True`, equal sequence lengths): row t sees the
    keys s with 0 <= t - s < window. The same two kernels under the labels
    `flash_attention_window_fwd|_bwd`, their grids as long as the widest
    span of blocks the band crosses, so the blocks behind the window are
    never visited (at the shape above under a window of 2,048: 2.89 + 5.25
    ms against the causal 4.93 + 9.12). A window no shorter than the
    sequence is no window.

    The backward keeps dq for the whole sequence in VMEM, so differentiating
    raises a ValueError past `sq * head_dim` = 22M (`_bwd_refusal`: 172,032
    rows at a head of 128, 86,016 at 256): such a sequence is split over
    chips.
    """
    b, sq, hq, dh = q.shape
    hk = k.shape[2]
    sk = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    if window is not None:
        _check_window(causal, sq, sk)
        if window < 1:
            raise ValueError(f"a window holds at least the row's own key, "
                             f"not {window}")
        if window >= sk:            # a window that masks nothing is none
            window = None
    qc = jnp.swapaxes(q, 1, 2).reshape(b * hq, sq, dh)
    kc = jnp.swapaxes(k, 1, 2).reshape(b * hk, sk, dh)
    vc = jnp.swapaxes(v, 1, 2).reshape(b * hk, sk, dh)
    out = _flash_core(qc, kc, vc, causal, scale, window)
    return jnp.swapaxes(out.reshape(b, hq, sq, dh), 1, 2)


flash_attention_fwd = flash_attention
