"""Dtype system for paddle_tpu.

TPU-native counterpart of the reference's `phi::DataType` / `paddle.dtype`
(reference: paddle/phi/common/data_type.h, python/paddle/framework/dtype.py).
We standardise on `numpy.dtype` objects (which JAX consumes directly) plus
JAX's bfloat16 extension type, and keep paddle's public names.
"""
from __future__ import annotations

import contextlib

import numpy as np
import jax.numpy as jnp
import ml_dtypes

# Canonical dtype objects. np.dtype instances are hashable, comparable and
# accepted everywhere by jax.numpy.
bfloat16 = np.dtype(ml_dtypes.bfloat16)
float16 = np.dtype("float16")
float32 = np.dtype("float32")
float64 = np.dtype("float64")
float8_e4m3fn = np.dtype(ml_dtypes.float8_e4m3fn)
float8_e5m2 = np.dtype(ml_dtypes.float8_e5m2)
int8 = np.dtype("int8")
int16 = np.dtype("int16")
int32 = np.dtype("int32")
int64 = np.dtype("int64")
uint8 = np.dtype("uint8")
uint16 = np.dtype("uint16")
uint32 = np.dtype("uint32")
uint64 = np.dtype("uint64")
bool_ = np.dtype("bool")
complex64 = np.dtype("complex64")
complex128 = np.dtype("complex128")

_ALIASES = {
    "bool": bool_,
    "bfloat16": bfloat16,
    "bf16": bfloat16,
    "float16": float16,
    "fp16": float16,
    "half": float16,
    "float32": float32,
    "fp32": float32,
    "float": float32,
    "float64": float64,
    "fp64": float64,
    "double": float64,
    "float8_e4m3fn": float8_e4m3fn,
    "float8_e5m2": float8_e5m2,
    "int8": int8,
    "int16": int16,
    "int32": int32,
    "int64": int64,
    "int": int32,
    "long": int64,
    "uint8": uint8,
    "uint16": uint16,
    "uint32": uint32,
    "uint64": uint64,
    "complex64": complex64,
    "complex128": complex128,
}

_DEFAULT_DTYPE = [float32]


def convert_dtype(dtype) -> np.dtype:
    """Normalise any dtype spec (str / np.dtype / python type / jnp dtype)
    to a canonical np.dtype. Mirrors paddle.base.data_feeder.convert_dtype."""
    if dtype is None:
        return None
    if isinstance(dtype, str):
        key = dtype.replace("paddle.", "")
        if key in _ALIASES:
            return _ALIASES[key]
        return np.dtype(key)
    if dtype is bool:
        return bool_
    if dtype is int:
        return int64
    if dtype is float:
        return float32
    return np.dtype(dtype)


def set_default_dtype(dtype):
    """paddle.set_default_dtype (python/paddle/framework/framework.py)."""
    d = convert_dtype(dtype)
    if d not in (float16, float32, float64, bfloat16):
        raise TypeError(
            f"set_default_dtype only supports float16/bfloat16/float32/float64, got {d}"
        )
    _DEFAULT_DTYPE[0] = d


def get_default_dtype() -> np.dtype:
    return _DEFAULT_DTYPE[0]


@contextlib.contextmanager
def default_dtype(dtype):
    """The default floating dtype for the duration of the block — layers
    built inside create their parameters in it directly."""
    prev = get_default_dtype()
    set_default_dtype(dtype)
    try:
        yield
    finally:
        _DEFAULT_DTYPE[0] = prev


def is_floating_point(dtype) -> bool:
    return jnp.issubdtype(convert_dtype(dtype), jnp.floating)


def is_integer(dtype) -> bool:
    d = convert_dtype(dtype)
    return jnp.issubdtype(d, jnp.integer) or d == bool_


def is_complex(dtype) -> bool:
    return jnp.issubdtype(convert_dtype(dtype), jnp.complexfloating)


def is_inexact(dtype) -> bool:
    """Differentiable dtypes (float or complex, incl. bf16/fp8)."""
    return jnp.issubdtype(convert_dtype(dtype), jnp.inexact)


#: dtype promotion follows jax/numpy rules (jnp.promote_types), which matches
#: the reference's phi promotion table for the common cases.
promote_types = jnp.promote_types

iinfo = jnp.iinfo
finfo = jnp.finfo
