"""The decode step's K/V commit as an in-place update of the paged pools.

One token's K and V a slot and layer, written at `pool[page, :, slot, :]` of
pools `[P, Hkv, block, D]`. XLA's form of it (`.at[page, :, slot, :].set`) is
a scatter for which the compiler picks a pool layout of its own (slot major
to kv heads: a token's update window contiguous), while the Pallas decode
kernel reads the pools in the default layout — so every pool was copied whole
once a decode step between the two (26% of the device's busy time in the
served expert cell, PR 33's trace). Here the pools stay in HBM in the default
layout, handed over with `memory_space=pl.ANY` and aliased to the outputs, and
only the row tiles that hold the new rows move.

Mosaic copies no slice thinner than a tile along the second-minor dimension
(16 rows of bf16, 8 of f32), so a row is written by fetching the aligned row
tile `[Hkv, tile, D]` of its page, replacing the one row in VMEM, and writing
the tile back. K and V of a layer go in ONE call; all slots' reads are started
before any is waited for, then all writes, so a call costs a few DMA
latencies and not one a slot.

Rows that name the same page (frozen and free slots all point at the scratch
page) read and write the same tiles concurrently: which row lands is
undefined, as it is for a scatter's duplicate indices, and nothing reads the
scratch page as live. Live slots never share a page.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .constraints import (KernelConstraint, LANE, SUBLANE, dtype_itemsize,
                          fit_vmem_block, register_constraint)
from .decode_attention import _on_tpu

# VMEM the staged tiles of K and V may take together: the slots of a call
# are walked in grid steps of as many slots as fit (both cells: all 32,
# 0.5-1 MiB a pool). More than one step is the guard against a call that
# would not fit, not a shape a cell runs: PERF.md 7d says what is known of it
STAGE_BYTES = 8 << 20


def _pool_ok(shape, dtype) -> bool:
    """A pool the kernel takes, by shape and dtype name: bf16 or f32, pages
    of whole row tiles, heads of whole lane tiles."""
    return dtype in ("bfloat16", "float32") and len(shape) == 4 \
        and shape[2] % SUBLANE[dtype] == 0 and shape[3] % LANE == 0


def commit_ok(kc, vc) -> bool:
    """Whether `kv_commit` takes this pair of pools — what a caller asks
    before it chooses between the kernel and XLA's scatter."""
    return kc.shape == vc.shape and kc.dtype == vc.dtype \
        and _pool_ok(kc.shape, str(kc.dtype))


def _check_commit_shapes(shapes, dtypes):
    return [("error", f"kv_commit takes no {d} pool of shape {s}")
            for s, d in zip(shapes, dtypes)
            if len(s) == 4 and not _pool_ok(s, d)]


def _commit_roofline(shapes, dtypes):
    """One launch reads and writes a row tile of every kv head a slot and
    pool, and reads the new rows; no arithmetic."""
    pools = [(s, d) for s, d in zip(shapes, dtypes) if len(s) == 4]
    rows = [(s, d) for s, d in zip(shapes, dtypes) if len(s) == 3]
    if not pools or not rows:
        return None
    (_, hkv, _, d), dt = pools[0]
    b = rows[0][0][0]
    tile_bytes = hkv * SUBLANE.get(dt, 8) * d * dtype_itemsize(dt)
    new_bytes = b * hkv * d * dtype_itemsize(rows[0][1])
    return {"flops": 0, "hbm_bytes": 2 * (2 * b * tile_bytes + new_bytes)}


CONSTRAINT = register_constraint(KernelConstraint(
    name="kv_commit",
    kernel_fns=("_commit_kernel",),
    blocks={"stage_bytes": STAGE_BYTES},
    note="pools [P, Hkv, block, D] in HBM, aliased to the outputs; block a "
         "multiple of the dtype's sublane tile, D of the lane tile",
    checker=_check_commit_shapes,
    source="kv_commit.py",
    roofline=_commit_roofline,
))


def _commit_kernel(page_ref, slot_ref, k_new_ref, v_new_ref, kc_in, vc_in,
                   kc_hbm, vc_hbm, k_buf, v_buf, sem):
    """One grid step commits `n` (= k_buf.shape[0]) slots' rows to both
    pools. `kc_in` / `vc_in` are the outputs `kc_hbm` / `vc_hbm` themselves
    (aliased)."""
    del kc_in, vc_in
    n, hkv, tile, d = k_buf.shape
    first = pl.program_id(0) * n

    def moves(i):
        """(tile in HBM, its stage, semaphore) of slot i in each pool."""
        sl = slot_ref[first + i]
        rows = pl.ds(pl.multiple_of(sl // tile * tile, tile), tile)
        pg = page_ref[first + i]
        return ((kc_hbm.at[pg, :, rows, :], k_buf.at[i], sem.at[0]),
                (vc_hbm.at[pg, :, rows, :], v_buf.at[i], sem.at[1]))

    def every(act, read):
        def body(i, carry):
            for hbm, stage, s in moves(i):
                src, dst = (hbm, stage) if read else (stage, hbm)
                act(pltpu.make_async_copy(src, dst, s))
            return carry

        jax.lax.fori_loop(0, n, body, 0)

    every(lambda cp: cp.start(), read=True)
    every(lambda cp: cp.wait(), read=True)
    row = jax.lax.broadcasted_iota(jnp.int32, (hkv, tile, d), 1)

    def put(i, carry):
        hit = row == slot_ref[first + i] % tile
        for new_ref, buf in ((k_new_ref, k_buf), (v_new_ref, v_buf)):
            buf[i] = jnp.where(hit, new_ref[i][:, None, :], buf[i])
        return carry

    jax.lax.fori_loop(0, n, put, 0)
    every(lambda cp: cp.start(), read=False)
    every(lambda cp: cp.wait(), read=False)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _commit(kc, vc, k_new, v_new, page, slot, *, interpret: bool):
    """Jitted so that a program which commits once a layer traces and lowers
    the kernel once (PR 26, PR 29: a kernel outside any jit is lowered a
    layer at a time, and the benchmark's set-up pays for it)."""
    b, hkv, d = k_new.shape
    tile = SUBLANE[str(kc.dtype)]
    tile_bytes = hkv * tile * d * kc.dtype.itemsize
    n = fit_vmem_block(b, b, tile_bytes, n_buffers=2, budget=STAGE_BYTES)
    new = pl.BlockSpec((n, hkv, d), lambda g, page_, slot_: (g, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _commit_kernel,
        name=CONSTRAINT.name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b // n,),
            in_specs=[new, new, in_hbm, in_hbm],
            out_specs=[in_hbm, in_hbm],
            scratch_shapes=[pltpu.VMEM((n, hkv, tile, d), kc.dtype),
                            pltpu.VMEM((n, hkv, tile, d), vc.dtype),
                            pltpu.SemaphoreType.DMA((2,))],   # k | v
        ),
        out_shape=[jax.ShapeDtypeStruct(kc.shape, kc.dtype),
                   jax.ShapeDtypeStruct(vc.shape, vc.dtype)],
        # operands count the two prefetched scalars: the pools are 4 and 5
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page.astype(jnp.int32), slot.astype(jnp.int32),
      k_new.astype(kc.dtype), v_new.astype(vc.dtype), kc, vc)


def kv_commit(kc: jax.Array, vc: jax.Array, k_new: jax.Array,
              v_new: jax.Array, page: jax.Array, slot: jax.Array):
    """`(kc.at[page, :, slot, :].set(k_new), vc.at[...].set(v_new))`, in
    place where the caller donates the pools.

    kc / vc: `[P, Hkv, block, D]`, one dtype and shape; k_new / v_new:
    `[b, Hkv, D]`; page / slot: `[b]` int, each inside the pool (a copy out
    of bounds is a fault on the chip, not a dropped update). Rows that name
    one page may overwrite each other's update (the module's docstring).
    Raises for pools `commit_ok` turns away — the caller chooses the jnp
    form there, never this function under the kernel's name."""
    if not commit_ok(kc, vc):
        raise ValueError(
            f"kv_commit takes bf16 or f32 pools [P, Hkv, block, D] of one "
            f"shape with block a multiple of the row tile and D of {LANE}; "
            f"got {kc.shape} {kc.dtype} and {vc.shape} {vc.dtype}")
    if k_new.shape != (page.shape[0], kc.shape[1], kc.shape[3]) \
            or v_new.shape != k_new.shape or slot.shape != page.shape:
        raise ValueError(
            f"kv_commit: rows {k_new.shape} / {v_new.shape} for pages "
            f"{page.shape}, slots {slot.shape} of pools {kc.shape}")
    return _commit(kc, vc, k_new, v_new, page, slot,
                   interpret=not _on_tpu())
