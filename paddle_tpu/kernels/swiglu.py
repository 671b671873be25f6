"""Fused SwiGLU: silu(x @ Wg) * (x @ Wu) in one Pallas kernel.

TPU-native counterpart of the reference's swiglu fused op
(paddle/phi/kernels/fusion/gpu/swiglu_kernel.cu; python surface
python/paddle/incubate/nn/functional/swiglu.py) — SURVEY §7.1 names it in
the Pallas kernel pack.

Why fuse on TPU: the two gate/up projections share the SAME x tiles; one
kernel streams x once, keeps both accumulators in VMEM, and writes ONE
[M, F] product to HBM instead of two matmul outputs plus an elementwise
pass — 2/3 of the intermediate HBM writes for the MLP's first stage.
Backward is a custom vjp: recompute gate/up per tile (the remat the bench
runs anyway), then three XLA matmuls for dx/dWg/dWu.

A jnp path covers CPU and is the numerics oracle. An older record (removed
in PR 22, predates PRs 1-20; not measured on this machine) had XLA's own
dual-matmul schedule beating this kernel at an MLP shape it does tile,
so the fused path is opt-in (`fused=True`) per the let-XLA-fuse rule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .constraints import (KernelConstraint, LANE, SUBLANE,
                          register_constraint)


_BLOCK = 512  # default tile edge; alignment and the pallas paths share it


def _check_swiglu_shapes(shapes, dtypes):
    """Checker for the fused swiglu pallas calls. Operands are x2d
    [M, K] then wg/wu [K, F] (+ dout [M, F] in backward); the wrapper
    already routes non-_BLOCK-divisible shapes to the XLA path, so what
    remains shape-decidable here is hardware-tile alignment of the dims
    the kernel actually tiles."""
    out = []
    arr = [s for s in shapes if len(s) == 2]
    if len(arr) < 3:
        return out
    (m, k), (_, f) = arr[0], arr[1]
    sub = SUBLANE.get(dtypes[0], 8) if dtypes else 8
    if m % sub:
        out.append(("warning",
                    f"M={m} is not a multiple of the {sub}-row sublane "
                    "tile; every x tile pads its rows"))
    for name, v in (("K", k), ("F", f)):
        if v % LANE:
            out.append(("warning",
                        f"{name}={v} is not a multiple of the {LANE}-"
                        "lane tile; the MXU pads the contraction"))
    return out


CONSTRAINT = register_constraint(KernelConstraint(
    name="swiglu",
    kernel_fns=("_swiglu_fwd_kernel", "_swiglu_bwd_kernel"),
    blocks={"block": _BLOCK},
    note="fused gate/up matmul + silu-mul; opt-in (fused=True) — XLA's "
         "dual-matmul schedule wins at the bench MLP shape, see "
         "swiglu_matmul",
    checker=_check_swiglu_shapes,
    source="swiglu.py",
))


def _aligned(m: int, f: int, k: int) -> bool:
    return m % _BLOCK == 0 and f % _BLOCK == 0 and k % _BLOCK == 0


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _swiglu_ref(x, wg, wu):
    return _silu(x @ wg) * (x @ wu)


# ---------------------------------------------------------------------------
# forward kernel: grid (M/bm, F/bf, K/bk), k innermost accumulation
# ---------------------------------------------------------------------------
def _swiglu_fwd_kernel(x_ref, wg_ref, wu_ref, o_ref, acc_g, acc_u, *,
                       n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_g[...] = jnp.zeros_like(acc_g)
        acc_u[...] = jnp.zeros_like(acc_u)

    x = x_ref[...]
    acc_g[...] += jax.lax.dot_general(
        x, wg_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_u[...] += jax.lax.dot_general(
        x, wu_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = (_silu(acc_g[...]) * acc_u[...]).astype(o_ref.dtype)


def _fwd_pallas(x2d, wg, wu, *, bm: int = _BLOCK, bf: int = _BLOCK,
                bk: int = _BLOCK):
    m, k = x2d.shape
    f = wg.shape[1]
    bm, bf, bk = min(bm, m), min(bf, f), min(bk, k)
    if m % bm or f % bf or k % bk:
        return _swiglu_ref(x2d, wg, wu)  # odd shapes: XLA path
    n_k = k // bk
    grid = (m // bm, f // bf, n_k)
    return pl.pallas_call(
        functools.partial(_swiglu_fwd_kernel, n_k=n_k),
        name=CONSTRAINT.name + "_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bf), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, bf), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bf), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, f), x2d.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bf), jnp.float32),
                        pltpu.VMEM((bm, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(x2d, wg, wu)


# ---------------------------------------------------------------------------
# backward kernel: recompute gate/up per tile, emit dh_g and dh_u
# ---------------------------------------------------------------------------
def _swiglu_bwd_kernel(x_ref, wg_ref, wu_ref, g_ref, dg_ref, du_ref,
                       acc_g, acc_u, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_g[...] = jnp.zeros_like(acc_g)
        acc_u[...] = jnp.zeros_like(acc_u)

    x = x_ref[...]
    acc_g[...] += jax.lax.dot_general(
        x, wg_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_u[...] += jax.lax.dot_general(
        x, wu_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        g = acc_g[...]
        u = acc_u[...]
        dout = g_ref[...].astype(jnp.float32)
        sig = jax.nn.sigmoid(g)
        silu = g * sig
        dsilu = sig * (1.0 + g * (1.0 - sig))  # d silu(g)/dg
        dg_ref[...] = (dout * u * dsilu).astype(dg_ref.dtype)
        du_ref[...] = (dout * silu).astype(du_ref.dtype)


def _bwd_pallas(x2d, wg, wu, dout, *, bm: int = _BLOCK, bf: int = _BLOCK,
                bk: int = _BLOCK):
    m, k = x2d.shape
    f = wg.shape[1]
    bm, bf, bk = min(bm, m), min(bf, f), min(bk, k)
    if m % bm or f % bf or k % bk:
        raise ValueError(
            f"_bwd_pallas needs block-aligned shapes, got {x2d.shape} x "
            f"{wg.shape} (the custom vjp routes misaligned shapes to the "
            "XLA ref path before reaching here)")
    n_k = k // bk
    grid = (m // bm, f // bf, n_k)
    return pl.pallas_call(
        functools.partial(_swiglu_bwd_kernel, n_k=n_k),
        name=CONSTRAINT.name + "_bwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bf), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, bf), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, bf), lambda i, j, kk: (i, j)),
        ],
        out_specs=[pl.BlockSpec((bm, bf), lambda i, j, kk: (i, j)),
                   pl.BlockSpec((bm, bf), lambda i, j, kk: (i, j))],
        out_shape=[jax.ShapeDtypeStruct((m, f), x2d.dtype),
                   jax.ShapeDtypeStruct((m, f), x2d.dtype)],
        scratch_shapes=[pltpu.VMEM((bm, bf), jnp.float32),
                        pltpu.VMEM((bm, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(x2d, wg, wu, dout)


# ---------------------------------------------------------------------------
# custom-vjp wrapper
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=())
def _swiglu_fused(x2d, wg, wu):
    return _fwd_pallas(x2d, wg, wu)


def _swiglu_fused_fwd(x2d, wg, wu):
    return _fwd_pallas(x2d, wg, wu), (x2d, wg, wu)


def _swiglu_fused_bwd(res, dout):
    x2d, wg, wu = res
    dh_g, dh_u = _bwd_pallas(x2d, wg, wu, dout)
    dx = dh_g @ wg.T + dh_u @ wu.T
    dwg = x2d.T @ dh_g
    dwu = x2d.T @ dh_u
    return dx.astype(x2d.dtype), dwg.astype(wg.dtype), dwu.astype(wu.dtype)


_swiglu_fused.defvjp(_swiglu_fused_fwd, _swiglu_fused_bwd)


def swiglu_matmul(x, wg, wu, fused=None):
    """silu(x @ wg) * (x @ wu); x [..., K], wg/wu [K, F] → [..., F].

    fused=None picks the XLA composition: on the bench MLP shape
    (M=16k, K=2048, F=5632, bf16, v5e) the measured MLP time is XLA
    5.88 ms vs 6.97-7.8 ms for this kernel across block configs — XLA's
    own dual-matmul schedule wins, so the Pallas path is opt-in
    (fused=True), kept as the §7.1 inventory item and for shapes/hardware
    where it may win. fused=True on a shape the kernel does not tile
    (M, K, F not all multiples of 512) raises: a caller who asked for
    the kernel is never handed the XLA form in its place."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2d = x.reshape(-1, k)
    m, f = x2d.shape[0], wg.shape[1]
    if fused:
        if not _aligned(m, f, k):
            raise ValueError(
                f"swiglu_matmul(fused=True): M={m}, K={k}, F={f} must all "
                f"be multiples of {_BLOCK}; pass fused=None for the XLA "
                f"form")
        out = _swiglu_fused(x2d, wg, wu)
    else:
        out = _swiglu_ref(x2d, wg, wu)
    return out.reshape(*lead, f)
