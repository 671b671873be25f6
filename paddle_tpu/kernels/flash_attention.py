"""Flash attention as a Pallas TPU kernel.

TPU-native counterpart of the reference's flash_attn op family
(paddle/phi/ops/yaml/ops.yaml:1765-1777, kernel
paddle/phi/kernels/gpu/flash_attn_kernel.cu): online-softmax tiled attention
that never materialises the [S, S] score matrix. The forward runs on the MXU
with fp32 accumulators in VMEM scratch; the backward recomputes scores and
softmax statistics from q/k/v (flash-attention-2 recompute strategy).

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

# default seq tiling of the in-repo kernels: both grids walk the kv axis
# in BLOCK_K steps with BLOCK_Q query rows resident in VMEM (clamped to
# the actual seq len; seq lens must then divide the clamped block)
BLOCK_Q = 512
BLOCK_K = 512
# the bundled jax MHA / splash fast paths tile at 1024 and require
# 512-divisible seqs and a 128-lane-aligned head dim
FAST_PATH_BLOCK = 1024
FAST_PATH_SEQ_MULTIPLE = 512
# the widest head the bundled MHA kernel is given: its backward blocks are
# 1024 * 128 / head size rows, rounded down to a power of two (256 here)
BUNDLED_MAX_HEAD = 512


def _check_attention_shapes(shapes, dtypes):
    """Checker for the fwd/bwd pallas calls: q [BH, Sq, D], k/v
    [BKVH, Sk, D] (bwd appends o/do/lse operands — same leading trio)."""
    out = []
    if len(shapes) < 3:
        return out
    q, k = shapes[0], shapes[1]
    if len(q) == 3 and len(k) == 3:
        bh, sq, d = q
        bkv, sk = k[0], k[1]
        if d % LANE:
            out.append(("warning",
                        f"head_dim {d} is not a multiple of the {LANE}-"
                        "lane tile; VMEM pads every row to "
                        f"{-(-d // LANE) * LANE} lanes"))
        if sq % min(BLOCK_Q, sq):
            out.append(("error",
                        f"q seq len {sq} does not divide the "
                        f"{min(BLOCK_Q, sq)} query block; the kernel "
                        "raises at call time"))
        if sk % min(BLOCK_K, sk):
            out.append(("error",
                        f"kv seq len {sk} does not divide the "
                        f"{min(BLOCK_K, sk)} kv block; the kernel "
                        "raises at call time"))
        if bkv and bh % bkv:
            out.append(("error",
                        f"q heads*batch {bh} not a multiple of kv "
                        f"heads*batch {bkv}; GQA grouping requires "
                        "Hq % Hkv == 0"))
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
    kernel_fns=("_fwd_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel"),
    blocks={"block_q": BLOCK_Q, "block_k": BLOCK_K},
    note="online-softmax tiled attention; seq lens must divide the "
         "(clamped) q/kv blocks and head_dim should be 128-lane aligned",
    checker=_check_attention_shapes,
    source="flash_attention.py",
    roofline=_flash_attention_roofline,
))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# forward kernel: grid (batch*q_heads, num_q_blocks, num_k_blocks)
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, causal: bool, scale: float,
                block_q: int, block_k: int, q_offset: int):
    """q_offset = sk - sq aligns the causal diagonal to the END of the kv
    sequence (paddle/flash-attn convention: the last q row sees all keys)."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

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
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
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

    @pl.when(ki == nk - 1)
    def _final():
        o_ref[0] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)
        # row statistic replicated across the 128 lanes (min tile layout)
        lse_ref[0] = m_scr[...] + jnp.log(l_scr[...])


def _fwd_pallas(q, k, v, causal: bool, scale: float,
                block_q: int = BLOCK_Q, block_k: int = BLOCK_K):
    """q: [BH, Sq, D]; k/v: [BKVH, Sk, D]. Returns (out [BH, Sq, D],
    lse [BH, Sq, 128] fp32 — the row statistic replicated across lanes,
    the TPU-tileable layout the backward kernels consume directly)."""
    bh, sq, d = q.shape
    bkv, sk, _ = k.shape
    rep = bh // bkv                      # q heads per kv head (GQA)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(f"seq lens ({sq},{sk}) not divisible by blocks "
                         f"({block_q},{block_k})")
    grid = (bh, sq // block_q, sk // block_k)
    vma = operand_vma(q, k, v)
    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, q_offset=sk - sq)
    out, lse = pl.pallas_call(
        kernel,
        name=CONSTRAINT.name + "_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j, rep=rep: (b // rep, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j, rep=rep: (b // rep, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, sq, 128), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=not _on_tpu(),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# jnp reference core (oracle + odd-shape fallback), layout [BH, S, D]
# ---------------------------------------------------------------------------

def _fwd_ref(q, k, v, causal: bool, scale: float):
    bh, sq, d = q.shape
    bkv, sk, _ = k.shape
    if bkv != bh:
        rep = bh // bkv
        k = jnp.repeat(k, rep, axis=0)
        v = jnp.repeat(v, rep, axis=0)
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bqk,bkd->bqd", (p / l).astype(q.dtype), v)
    return out


def _pallas_ok(q, k, v):
    """Whether the in-repo kernels take these operands — decided here,
    before the call, so whatever the kernel then raises propagates."""
    # must match the kernels' default block choice (min(BLOCK, seq))
    if (q.shape[1] % min(BLOCK_Q, q.shape[1])
            or k.shape[1] % min(BLOCK_K, k.shape[1])
            or q.shape[0] % k.shape[0]):
        return False
    # jax's Pallas interpreter cannot run under a vma-checked shard_map
    return _on_tpu() or not operand_vma(q, k, v)


def _fwd_core(q, k, v, causal, scale):
    """Returns (out, lse) — lse is [BH,Sq,128] from the pallas path or None
    (the jnp form recomputes stats in the backward)."""
    if _pallas_ok(q, k, v):
        return _fwd_pallas(q, k, v, causal, scale)
    return _fwd_ref(q, k, v, causal, scale), None


# ---------------------------------------------------------------------------
# backward kernels (FA2): dq over k blocks; dk/dv over q blocks
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref,
                   dq_scr, *, causal: bool, scale: float, block_q: int,
                   block_k: int, q_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    run = ((qi * block_q + block_q - 1 + q_offset >= ki * block_k)
           if causal else True)

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        o = o_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]                       # [block_q, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * block_q + q_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        delta = jnp.sum(do * o, axis=-1, keepdims=True)
        ds = p * (dp - delta) * scale
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _final():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, causal: bool,
                    scale: float, block_q: int, block_k: int,
                    q_offset: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = ((qi * block_q + block_q - 1 + q_offset >= ki * block_k)
           if causal else True)

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        o = o_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * block_q + q_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - lse)                          # [block_q, block_k]
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do.astype(do_ref.dtype),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        delta = jnp.sum(do * o, axis=-1, keepdims=True)
        ds = p * (dp - delta) * scale
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _final():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_pallas(q, k, v, out, lse, do, causal: bool, scale: float,
                block_q: int = BLOCK_Q, block_k: int = BLOCK_K):
    """Flash backward. Returns (dq [BH,Sq,D], dk/dv [BH,Sk,D] per q-head —
    caller reduces over GQA groups)."""
    bh, sq, d = q.shape
    bkv, sk, _ = k.shape
    rep = bh // bkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    kern_kw = dict(causal=causal, scale=scale, block_q=block_q,
                   block_k=block_k, q_offset=sk - sq)
    vma = operand_vma(q, k, v, do)
    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, block_k, d),
                           lambda b, i, j, rep=rep: (b // rep, j, 0))
    lse_spec = pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kern_kw),
        name=CONSTRAINT.name + "_bwd_dq",
        grid=(bh, sq // block_q, sk // block_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, lse_spec],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype, vma=vma),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=not _on_tpu(),
    )(q, k, v, out, do, lse)
    # dkv grid: (bh, k blocks, q blocks) — q innermost for accumulation
    q_spec2 = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
    kv_spec2 = pl.BlockSpec((1, block_k, d),
                            lambda b, j, i, rep=rep: (b // rep, j, 0))
    lse_spec2 = pl.BlockSpec((1, block_q, 128), lambda b, j, i: (b, i, 0))
    dkv_out = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **kern_kw),
        name=CONSTRAINT.name + "_bwd_dkv",
        grid=(bh, sk // block_k, sq // block_q),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, q_spec2, lse_spec2],
        out_specs=[dkv_out, dkv_out],
        out_shape=[jax.ShapeDtypeStruct((bh, sk, d), k.dtype, vma=vma),
                   jax.ShapeDtypeStruct((bh, sk, d), v.dtype, vma=vma)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=not _on_tpu(),
    )(q, k, v, out, do, lse)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp over [BH, S, D] core
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_core(q, k, v, causal: bool, scale: float):
    return _fwd_core(q, k, v, causal, scale)[0]


def _flash_core_fwd(q, k, v, causal, scale):
    out, lse = _fwd_core(q, k, v, causal, scale)
    return out, (q, k, v, out, lse)


def _flash_core_bwd(causal, scale, res, do):
    """FA2 backward: dv = P^T dO ; dS = P * (dO V^T - rowsum(dO*O)) * scale;
    dq = dS K ; dk = dS^T Q (reference math:
    paddle/phi/kernels/gpu/flash_attn_grad_kernel.cu via the flashattn
    library). Pallas kernels when the forward saved LSE; jnp recompute
    fallback otherwise."""
    q, k, v, out, lse = res
    bh, sq, d = q.shape
    if lse is not None:
        dq, dk, dv = _bwd_pallas(q, k, v, out, lse, do, causal, scale)
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
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, _NEG_INF)
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


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# ---------------------------------------------------------------------------
# public API, paddle layout [B, S, H, D]
# ---------------------------------------------------------------------------

def _bundled_ok(sq, sk, hq, hk, dh) -> bool:
    """Shapes the bundled jax pallas MHA kernel handles well (equal heads,
    long block-divisible sequences)."""
    return (_on_tpu() and hq == hk and dh % LANE == 0
            and dh <= BUNDLED_MAX_HEAD
            and sq % FAST_PATH_SEQ_MULTIPLE == 0
            and sk % FAST_PATH_SEQ_MULTIPLE == 0 and sq == sk)


def _splash_ok(sq, sk, hq, hk, dh) -> bool:
    """GQA shapes for the splash kernel (grouped heads natively — the fast
    path for Llama-2-70B/Llama-3-class configs where hk < hq)."""
    return (_on_tpu() and hq != hk and hq % hk == 0 and dh % LANE == 0
            and sq % FAST_PATH_SEQ_MULTIPLE == 0
            and sk % FAST_PATH_SEQ_MULTIPLE == 0 and sq == sk)


@functools.lru_cache(maxsize=16)
def _splash_kernel(sq, sk, hq, causal: bool):
    """Build (and cache) a splash GQA kernel.

    Block sizes tuned on v5e at b8/s2048/hq16/hkv4/d128: fwd 20.1 TF/s,
    fwd+bwd 34.3 TF/s (vs 19.8/30.7 for the in-repo kernel and 16.5/26.7
    for kv-repeat through the bundled MHA kernel). Callers must construct
    under jax.ensure_compile_time_eval(): built inside a jit trace, the
    kernel's mask-info arrays become trace-local constants and poison the
    cache for later traces (UnexpectedTracerError)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as _sk, splash_attention_mask as _sm)

    mk = (_sm.CausalMask((sq, sk)) if causal else _sm.FullMask((sq, sk)))
    mask = _sm.MultiHeadMask([mk for _ in range(hq)])
    bq = min(FAST_PATH_BLOCK, sq)
    bkv = min(FAST_PATH_BLOCK, sk)
    bc = min(FAST_PATH_SEQ_MULTIPLE, sk)
    blocks = _sk.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bc,
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bc,
        block_q_dq=bq, block_kv_dq=bkv)
    return _sk.make_splash_mha(mask, head_shards=1, q_seq_shards=1,
                               block_sizes=blocks)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None):
    """Differentiable flash attention; layout [B, S, H, D] (paddle
    flash_attn layout, ops.yaml:1765). kv heads may divide q heads (GQA).

    Fast path: the pallas flash kernel bundled with the installed jax
    (jax.experimental.pallas.ops.tpu.flash_attention) — the TPU analog of
    the reference vendoring Dao's flash-attn library
    (third_party/flashattn) — and splash for GQA, each behind its shape
    predicate (`_bundled_ok`, `_splash_ok`). Every other shape takes the
    in-repo kernel pack where `_pallas_ok` holds and the jnp form
    otherwise. The choice is made from shapes before the call: nothing a
    kernel raises is caught.
    """
    b, sq, hq, dh = q.shape
    hk = k.shape[2]
    sk = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    if _splash_ok(sq, sk, hq, hk, dh):
        with jax.ensure_compile_time_eval():
            kernel = _splash_kernel(sq, sk, hq, bool(causal))
        # splash takes pre-scaled q, per-example [h, s, d] layout
        qs = jnp.swapaxes(q, 1, 2) * jnp.asarray(scale, q.dtype)
        out = jax.vmap(kernel)(qs, jnp.swapaxes(k, 1, 2),
                               jnp.swapaxes(v, 1, 2))
        return jnp.swapaxes(out, 1, 2)
    if _bundled_ok(sq, sk, hq, hk, dh):
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            BlockSizes, flash_attention as _jax_fa)

        bs = min(FAST_PATH_BLOCK, sq)
        # the backward kernels hold q, k, v, do and dk, dv blocks at once:
        # at head size 256 (latent attention) 1024-row blocks overrun the
        # 16 MiB of scoped VMEM by 0.9 MiB (the chip's compiler, PR 28), so
        # their rows shrink as the head widens, by powers of two so that a
        # block stays a lane multiple and a divisor of the sequence (head
        # size 384 gives 256, not 341); head size 128 keeps 1024
        bw = min(bs, 1 << (FAST_PATH_BLOCK * LANE // dh).bit_length() - 1)
        blocks = BlockSizes(
            block_q=bs, block_k_major=bs, block_k=bs, block_b=1,
            block_q_major_dkv=bw, block_k_major_dkv=bw,
            block_k_dkv=bw, block_q_dkv=bw,
            block_k_major_dq=bw, block_k_dq=bw, block_q_dq=bw)
        out = _jax_fa(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                      jnp.swapaxes(v, 1, 2), causal=causal,
                      sm_scale=scale, block_sizes=blocks)
        return jnp.swapaxes(out, 1, 2)
    qc = jnp.swapaxes(q, 1, 2).reshape(b * hq, sq, dh)
    kc = jnp.swapaxes(k, 1, 2).reshape(b * hk, sk, dh)
    vc = jnp.swapaxes(v, 1, 2).reshape(b * hk, sk, dh)
    out = _flash_core(qc, kc, vc, causal, scale)
    return jnp.swapaxes(out.reshape(b, hq, sq, dh), 1, 2)


flash_attention_fwd = flash_attention
