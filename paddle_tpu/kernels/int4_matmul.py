"""In-register int4 dequant-matmul for weight-bound decode.

TPU-native counterpart of the reference's weight-only int4 GEMV
(paddle/phi/kernels/fusion/cutlass/fpA_intB_gemm — the CUTLASS
mixed-dtype path behind nn.quant.weight_only_linear(weight_dtype='int4')).

XLA materializes the sign-extended nibble halves of a packed int4 weight
before the dot, so the HBM read stays int8-sized and int4 decode measured
SLOWER than int8 (older record, removed in PR 22). This kernel keeps the packed bytes all the
way into VMEM and unpacks in-register per tile: HBM traffic is the true
0.5 byte/weight, which is the whole point of int4 on a weight-bound
decode. Per-channel scales applied on the output tile.

Layout matches nn.quant.weight_quantize(algo="weight_only_int4"):
w_packed [N, K//2] int8, low nibble = even k, high nibble = odd k,
scale [N] float32. x [M, K] with small M (decode): M is padded to the
sublane minimum outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .constraints import KernelConstraint, LANE, register_constraint

# output-channel tile each grid step dequantises and multiplies
BLOCK_N = 512
# fp32 sublane minimum: x rows are padded up to this before the kernel
SUBLANE_MIN = 8
# beyond this M the whole-x-in-VMEM decode shape stops fitting (measured
# OOM at M=512, K=5504) and calls route to the XLA shift fallback
MAX_DECODE_M = 64


def _check_int4_shapes(shapes, dtypes):
    """Checker for the decode pallas call: xe/xo [M, K/2], w [N, K/2],
    scale [1, N]."""
    out = []
    if len(shapes) < 3:
        return out
    xe, w = shapes[0], shapes[2]
    if len(xe) == 2 and len(w) == 2:
        m, khalf = xe
        n = w[0]
        # NOTE: no M-cap check here — int4_matmul routes M > MAX_DECODE_M
        # to the XLA fallback before any pallas_call exists, so a traced
        # graph can never show an oversized M
        if n % min(BLOCK_N, n):
            out.append(("warning",
                        f"output channels N={n} do not divide the "
                        f"{min(BLOCK_N, n)} channel block"))
        if (2 * khalf) % LANE:
            out.append(("warning",
                        f"K={2 * khalf} is not a multiple of the "
                        f"{LANE}-lane tile; the packed nibble rows pad "
                        "in VMEM"))
    return out


CONSTRAINT = register_constraint(KernelConstraint(
    name="int4_matmul",
    kernel_fns=("_kernel",),
    blocks={"block_n": BLOCK_N, "sublane_min": SUBLANE_MIN,
            "max_decode_m": MAX_DECODE_M},
    note="in-register int4 dequant GEMV; decode-shaped M only, N walks "
         f"in {BLOCK_N}-channel tiles",
    checker=_check_int4_shapes,
    source="int4_matmul.py",
))


def _kernel(xe_ref, xo_ref, w_ref, s_ref, o_ref, *, dot_dtype):
    # Mosaic has no i8 vector shifts: nibble math in i32
    # (xor-subtract sign extension: (v & 15) ^ 8 - 8)
    w32 = w_ref[...].astype(jnp.int32)  # [bn, K/2]
    lo = (jnp.bitwise_and(w32, 15) ^ 8) - 8                 # even k
    hi = (jnp.bitwise_and(jnp.right_shift(w32, 4), 15) ^ 8) - 8  # odd k
    # int4 values are exact in bf16, so the dequant dot runs at the
    # MXU's bf16 rate (8x fp32) with fp32 accumulation — round-4 small-M
    # tuning; fp32 dot inputs were the round-3 kernel's hidden cost
    acc = jax.lax.dot_general(
        xe_ref[...].astype(dot_dtype), lo.astype(dot_dtype),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    acc += jax.lax.dot_general(
        xo_ref[...].astype(dot_dtype), hi.astype(dot_dtype),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    o_ref[...] = (acc * s_ref[...]).astype(o_ref.dtype)


def int4_matmul(x, w_packed, scale, *, block_n: int = BLOCK_N,
                dot_dtype=None):
    """x [M, K] @ dequant(w_packed [N, K//2]).T * scale [N] → [M, K?N].

    Decode-shaped: the whole x lives in VMEM per tile (small M, padded
    only to the 8-row sublane minimum — never to the full MXU tile); the
    grid walks N. `dot_dtype` sets the dequant-dot input precision
    (default: x's own dtype — bf16 decode runs the dot at the MXU bf16
    rate; int4 values are exact in bf16). Falls back to the XLA shift
    form off-TPU or on misaligned shapes."""
    m, k = x.shape
    n = w_packed.shape[0]
    bn = min(block_n, n)
    aligned = (n % bn == 0) and (k % 2 == 0) and (w_packed.shape[1] * 2 == k)
    # the kernel is decode-shaped: all of x + a dequant tile must fit
    # scoped VMEM (~16 MB). Large-M calls (prefill through the same _mm)
    # are compute-bound, where the XLA shift form is the right tool —
    # measured VMEM OOM at M=512, K=5504 without this route.
    if not aligned or m > MAX_DECODE_M:
        return _xla_fallback(x, w_packed, scale)
    on_tpu = jax.default_backend() == "tpu"
    if dot_dtype is None:
        # XLA:CPU (the interpret path) cannot execute bf16 x bf16 -> f32
        # dots; the bf16 fast path is TPU-only
        dot_dtype = x.dtype if on_tpu and x.dtype in (
            jnp.bfloat16, jnp.float32) else jnp.float32
    elif not on_tpu and jnp.dtype(dot_dtype) == jnp.bfloat16:
        # same CPU limitation applies to an explicitly requested bf16
        dot_dtype = jnp.float32
    pad_m = max(SUBLANE_MIN - m, 0)
    xp = jnp.pad(x, ((0, pad_m), (0, 0))) if pad_m else x
    # even/odd split outside the kernel (Mosaic has no strided gather);
    # x is decode-tiny so this costs nothing
    xe, xo = xp[:, 0::2], xp[:, 1::2]
    scale2d = scale.reshape(1, n)  # 2-D: 1-D operands hit XLA/Mosaic
    # tiling mismatches
    out = pl.pallas_call(
        functools.partial(_kernel, dot_dtype=dot_dtype),
        name=CONSTRAINT.name,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((xp.shape[0], k // 2), lambda j: (0, 0)),
            pl.BlockSpec((xp.shape[0], k // 2), lambda j: (0, 0)),
            pl.BlockSpec((bn, k // 2), lambda j: (j, 0)),
            pl.BlockSpec((1, bn), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((xp.shape[0], bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], n), x.dtype),
        interpret=not on_tpu,
    )(xe, xo, w_packed, scale2d)
    return out[:m] if pad_m else out


def _xla_fallback(x, w_packed, scale):
    lo = jnp.right_shift(jnp.left_shift(w_packed, 4), 4)
    hi = jnp.right_shift(w_packed, 4)
    out = jnp.einsum("mk,nk->mn", x[:, 0::2], lo.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    out += jnp.einsum("mk,nk->mn", x[:, 1::2], hi.astype(x.dtype),
                      preferred_element_type=jnp.float32)
    return (out * scale).astype(x.dtype)
