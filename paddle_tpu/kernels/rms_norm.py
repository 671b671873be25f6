"""Fused RMSNorm.

TPU-native counterpart of the reference's fused_rms_norm op
(paddle/phi/kernels/gpu/rms_norm_kernel.cu; python surface
python/paddle/incubate/nn/functional/fused_rms_norm.py). The row statistic +
scale is one Pallas kernel on TPU; a jnp path (which XLA fuses into one
loop anyway) covers CPU and serves as the numerics oracle. fp32 statistics
regardless of input dtype, matching the reference kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._vma import like_primal, operand_vma


def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    o_ref[...] = (x * inv * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


_BLOCK_ROWS = 256


def _row_block(n: int):
    """Rows per grid step, or None when the kernel does not take this
    row count: the Mosaic lowering wants the block's row dim to equal
    the array's or be a multiple of 8 that divides it."""
    if n <= _BLOCK_ROWS:
        return n
    for b in range(_BLOCK_ROWS, 7, -8):
        if n % b == 0:
            return b
    return None


def _rms_pallas(x2d, w, eps: float, block_rows: int, vma, interpret):
    n, d = x2d.shape
    return pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        name="rms_norm",
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x2d.dtype, vma=vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2d, w)


def _rms_ref(x, w, eps: float):
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv * w.astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x, w, eps: float = 1e-6):
    """y = x / rms(x) * w over the last axis. Two cases take the jnp
    form (XLA fuses it into one loop), both decided before the call: a
    row count `_row_block` rejects, and interpret mode inside a
    vma-checked shard_map, which jax's Pallas interpreter cannot
    evaluate. Whatever the kernel raises otherwise propagates."""
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    block_rows = _row_block(x2d.shape[0])
    interpret = jax.default_backend() != "tpu"
    # under shard_map the output varies over every manual axis an
    # operand varies over
    vma = operand_vma(x, w)
    if block_rows is None or (interpret and vma):
        return _rms_ref(x, w, eps)
    return _rms_pallas(x2d, w, eps, block_rows, vma,
                       interpret).reshape(shape)


def _rms_fwd(x, w, eps):
    return rms_norm(x, w, eps), (x, w)


def _rms_bwd(eps, res, dy):
    x, w = res
    x32 = x.astype(jnp.float32)
    dy32 = dy.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    xhat = x32 * inv
    dw = jnp.sum(dy32 * xhat, axis=tuple(range(x.ndim - 1)))
    g = dy32 * w32
    dx = inv * (g - xhat * jnp.mean(g * xhat, axis=-1, keepdims=True))
    return (like_primal(dx.astype(x.dtype), x),
            like_primal(dw.astype(w.dtype), w))


rms_norm.defvjp(_rms_fwd, _rms_bwd)
