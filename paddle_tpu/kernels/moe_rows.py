"""Row movement of a dropless expert layer (parallel/moe.py
`dropless_experts`): tokens -> buffer rows, buffer rows -> tokens weighted by
the gates, and the backward pass of each, in proportion to the rows that
exist and not to the buffer, which is sized for the worst case.

Reference analog: global_scatter / global_gather
(python/paddle/distributed/utils/moe_utils.py:20,153) seen from one chip.

How. The (token, choice) assignments are enumerated token-major and sorted
by expert with a STABLE sort, so inside one expert's group the buffer rows lie
in increasing token order: the rows that a tile of `TOKEN_TILE` tokens owns in
a group are contiguous. Mosaic copies no slice of a bf16 array thinner than
its tiling (a one-row copy is refused), so rows move in aligned chunks of
`CHUNK` rows, and the MXU places them: for a token tile, the chunks that hold
its rows are staged `SLOTS` at a time, `W[row, token]` is built from an iota
compare against `dest` (the gate of the assignment where the row is the
token's, else 0), and

- buffer -> tokens (`gather_rows`): the staged chunks are FETCHED by async
  copies from the buffer, left in HBM, and `out[tokens] = W^T @ staged`;
- tokens -> buffer (`scatter_rows`): `staged = W @ x[tokens]`, and each
  staged chunk is WRITTEN by an async copy once it is whole. A chunk that two
  token tiles share stays in VMEM (one open chunk a group) until the later
  tile has added its rows. The chunks between a group's last row and the end
  of its last row tile are written as zeros from the last token tile's list,
  so a live row tile is whole; row tiles no group uses are never written.

The gates stay f32: `W` is split into three bf16 terms whose sum is the f32
gate exactly, each product with a bf16 row is exact in the MXU's f32
accumulator, so a result is the f32 sum over a token's choices of f32 gate x
bf16 row, as the jnp form computes it. A staged row that no token of the
tile owns (a neighbour's, padding, what a skipped slot held before) is
replaced by zeros before it meets the MXU: 0 x NaN is NaN.

Kernels by name in the compiled program and the device trace: `moe_rows_in`
(x -> xs), `moe_rows_out` (ys -> y, gates), `moe_rows_out_bwd` (dy -> dys,
gates), `moe_rows_in_bwd` (dxs -> dx) and, where the gates train,
`moe_rows_dgates` (dy . ys rows). The chunk lists (`tile_chunks`) are a few
thousand integers computed by XLA from the routing, once a layer.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .constraints import KernelConstraint, LANE, register_constraint
from .grouped_matmul import (SUBLANE_TILE, GroupLayout, _VMEM_LIMIT,
                             _interpret, _pallas_ok)

# rows of one copy: a bf16 tile's sublanes, the thinnest slice Mosaic moves
# (and the thinnest row tile of a layout, so a row tile is whole chunks)
CHUNK = SUBLANE_TILE
# chunks staged for one product: a contraction of SLOTS * CHUNK = 256 rows
SLOTS = 16
# tokens of one grid step; the rows of a group that a tile owns are contiguous
TOKEN_TILE = 128
_CARRY, _KEEP, _GROUP_SHIFT = 1, 2, 2


class TileChunks(NamedTuple):
    """The buffer chunks (row // CHUNK) that hold each token tile's rows, in
    slot order. All int32."""
    chunk: jax.Array    # [token tiles, S] chunk of each slot, -1 past n_write
    flags: jax.Array    # [token tiles, S] _CARRY | _KEEP | group << 2
    n_read: jax.Array   # [token tiles] slots gather_rows fetches
    n_write: jax.Array  # [token tiles] slots scatter_rows forms (zeros too)


def rows_ok(tokens: int, d: int, f: int, rows: int, tile: int) -> bool:
    """Shapes the kernels take: whole token tiles, and rows of width `d` in a
    buffer of `rows` in row tiles of `tile` (whole chunks) that the grouped
    matmul's kernels take too, against weights [d, f] — what these kernels
    leave unwritten would meet its jnp form's 0 x row products."""
    return tokens % TOKEN_TILE == 0 and _pallas_ok(rows, d, f, tile)


def _max_slots(k: int, groups: int, tile: int) -> int:
    """Slots a token tile's list can need: its rows in chunks (a group's
    stretch may start and end inside a chunk), and on the last tile the
    zero chunks that complete every group's last row tile of `tile` rows."""
    n = TOKEN_TILE * min(k, groups) // CHUNK + 2 * groups \
        + groups * (tile // CHUNK)
    return -(-n // SLOTS) * SLOTS


def _pick(table, index, n: int):
    """table[..., index] for a small last axis of `n`, as compares and a sum:
    XLA's gather costs the chip ~40 ns an element."""
    hot = index[..., None] == jnp.arange(n, dtype=index.dtype)
    return jnp.sum(jnp.where(hot, table, 0), axis=-1)


def tile_chunks(ends, layout: GroupLayout, k: int, tile: int) -> TileChunks:
    """`ends` [token tiles, G]: the assignments of each group up to the end
    of each token tile (a running count in token order); `tile`: the
    layout's row tile. Plain jnp over [token tiles, S, G] integers."""
    nt, g = ends.shape
    s_max = _max_slots(k, g, tile)
    hi = layout.starts + ends                               # [nt, G]
    lo = jnp.concatenate([layout.starts[None], hi[:-1]])
    end = layout.starts + layout.sizes
    first = lo // CHUNK
    n = jnp.where(hi > lo, (hi - 1) // CHUNK - first + 1, 0)
    stop = jnp.cumsum(n, axis=1)
    n_read = stop[:, -1]
    s = jnp.arange(s_max, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.sum(s[None, :, None] >= stop[:, None, :], axis=-1), g - 1)
    of = lambda a: _pick(a[:, None, :], group, g)           # noqa: E731
    pos = s - of(stop - n)
    live = s < n_read[:, None]
    carry = live & (pos == 0) & (of(lo) % CHUNK != 0)
    keep = live & (pos == of(n) - 1) & (of(hi) % CHUNK != 0) \
        & (of(hi) < _pick(end, group, g))
    chunk = of(first) + pos
    # the zero chunks from each group's end to the end of its last row tile
    tail_first = -(-end // CHUNK)
    tail_n = (layout.starts + jnp.maximum(1, -(-layout.sizes // tile))
              * tile) // CHUNK - tail_first
    tail_stop = jnp.cumsum(tail_n)
    p = s - n_read[-1]
    tail_group = jnp.minimum(
        jnp.sum(p[:, None] >= tail_stop[None, :], axis=-1), g - 1)
    tail = (p >= 0) & (p < tail_stop[-1])
    tail_chunk = _pick(tail_first - (tail_stop - tail_n), tail_group, g) + p
    last = jnp.arange(nt)[:, None] == nt - 1
    chunk = jnp.where(last & tail, tail_chunk, chunk)
    live = live | (last & tail)
    flags = carry.astype(jnp.int32) * _CARRY + keep.astype(jnp.int32) * _KEEP \
        + group.astype(jnp.int32) * (1 << _GROUP_SHIFT)
    return TileChunks(
        jnp.where(live, chunk, -1).astype(jnp.int32),
        jnp.where(live, flags, 0).astype(jnp.int32),
        n_read.astype(jnp.int32),
        (n_read + jnp.where(last[:, 0], tail_stop[-1], 0)).astype(jnp.int32))


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _placement(chunk_ref, dest_ref, gate_ref, row_ref, i, kb):
    """For the SLOTS chunks staged in block `kb` of token tile `i`:
    `hit[j]` [rows, tokens], whether the staged row is the token's j-th
    choice, and (with gates) `w` [rows, tokens] f32, that choice's gate.
    `row_ref` [rows, 1] takes the staged rows' numbers (a slot past the
    tile's count holds chunk -1: rows no `dest` names)."""
    sub = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, 1), 0)

    def number(s, carry):
        row_ref[_slot_rows(s), :] = chunk_ref[i, kb * SLOTS + s] * CHUNK + sub
        return carry

    jax.lax.fori_loop(0, SLOTS, number, 0)
    row = row_ref[...]
    hits = [dest_ref[j:j + 1, :] == row for j in range(dest_ref.shape[0])]
    if gate_ref is None:
        return hits, None
    w = sum(jnp.where(h, gate_ref[j:j + 1, :], 0.0)
            for j, h in enumerate(hits))
    return hits, w


def _any(hits):
    return functools.reduce(jnp.logical_or, hits)


def _terms(hits, w, dtype):
    """`W` as operands of the MXU: three terms that sum to the f32 gates
    exactly where `dtype` is bf16, else the 0 / 1 placement itself."""
    if w is None:
        return [_any(hits).astype(dtype)]
    if jnp.dtype(dtype).itemsize >= 4:
        return [w.astype(dtype)]
    out = []
    for _ in range(3):
        part = w.astype(dtype)
        out.append(part)
        w = w - part.astype(jnp.float32)
    return out


def _chunk_rows(chunk):
    return pl.ds(pl.multiple_of(chunk * CHUNK, CHUNK), CHUNK)


def _slot_rows(s):
    return pl.ds(pl.multiple_of(s * CHUNK, CHUNK), CHUNK)


def _live_slots(n_ref, i, kb, body):
    """body(slot, index in the tile's list) for block `kb`'s live slots."""
    def step(s, carry):
        body(s, kb * SLOTS + s)
        return carry

    jax.lax.fori_loop(0, jnp.minimum(SLOTS, n_ref[i] - kb * SLOTS), step, 0)


def _fetch(chunk_ref, n_ref, src_hbm, stage, sem, i, kb, act):
    """Start, or wait for, the copies of block `kb`'s live slots."""
    _live_slots(n_ref, i, kb, lambda s, idx: act(pltpu.make_async_copy(
        src_hbm.at[_chunk_rows(chunk_ref[i, idx])], stage.at[_slot_rows(s)],
        sem)))


def _fetch_placed(chunk_ref, n_ref, dest_ref, gate_ref, row_ref, pairs, sem,
                  i, kb):
    """Block `kb`'s chunks of every (source, stage) pair fetched, the
    placement (`_placement`) built while the copies fly."""
    for src, stage in pairs:
        _fetch(chunk_ref, n_ref, src, stage, sem, i, kb,
               lambda cp: cp.start())
    placed = _placement(chunk_ref, dest_ref, gate_ref, row_ref, i, kb)
    for src, stage in pairs:
        _fetch(chunk_ref, n_ref, src, stage, sem, i, kb, lambda cp: cp.wait())
    return placed


def _staged(hits, stage):
    """The staged rows some token of the tile owns, zeros for the rest."""
    owned = jnp.max(_any(hits).astype(jnp.float32), axis=1,
                    keepdims=True) > 0
    return jnp.where(owned, stage[...], jnp.zeros_like(stage))


_NN = (((1,), (0,)), ((), ()))          # lhs @ rhs
_TN = (((0,), (0,)), ((), ()))          # lhs^T @ rhs
_NT = (((1,), (1,)), ((), ()))          # lhs @ rhs^T


def _dot(lhs, rhs, dims):
    """f32 result; f32 operands (the CPU tests') are multiplied as f32."""
    wide = jnp.dtype(lhs.dtype).itemsize >= 4
    return jax.lax.dot_general(
        lhs, rhs, dims, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if wide else None)


def _gather_kernel(chunk_ref, n_ref, dest_ref, *refs, weighted: bool,
                   sources: int):
    gate_ref = refs[0] if weighted else None
    refs = refs[1 if weighted else 0:]
    srcs, (out_ref, stage, acc, row_ref, sem) = refs[:sources], refs[sources:]
    pairs = [(src, stage.at[a]) for a, src in enumerate(srcs)]
    i = pl.program_id(0)
    acc[...] = jnp.zeros_like(acc)

    def block(kb, carry):
        hits, w = _fetch_placed(chunk_ref, n_ref, dest_ref, gate_ref, row_ref,
                                pairs, sem, i, kb)
        terms = _terms(hits, w, stage.dtype)
        for _, staged in pairs:
            rows = _staged(hits, staged)
            for term in terms:
                acc[...] += _dot(term, rows, _TN)
        return carry

    jax.lax.fori_loop(0, pl.cdiv(n_ref[i], SLOTS), block, 0)
    out_ref[...] = acc[...].astype(out_ref.dtype)


def _dgates_kernel(chunk_ref, n_ref, dest_ref, dy_ref, src_hbm, out_ref,
                   stage, row_ref, sem):
    """out[j, token] = dy[token] . src[dest[token, j]], f32."""
    i = pl.program_id(0)
    out_ref[...] = jnp.zeros_like(out_ref)

    def block(kb, carry):
        hits, _ = _fetch_placed(chunk_ref, n_ref, dest_ref, None, row_ref,
                                [(src_hbm, stage)], sem, i, kb)
        dots = _dot(_staged(hits, stage), dy_ref[...], _NT)  # [rows, tokens]
        for j, h in enumerate(hits):
            out_ref[j:j + 1, :] += jnp.sum(jnp.where(h, dots, 0.0), axis=0,
                                           keepdims=True)
        return carry

    jax.lax.fori_loop(0, pl.cdiv(n_ref[i], SLOTS), block, 0)


def _scatter_kernel(chunk_ref, n_ref, flag_ref, dest_ref, *refs,
                    weighted: bool):
    gate_ref = refs[0] if weighted else None
    x_ref, out_hbm, stage, open_ref, row_ref, sem = refs[1 if weighted else 0:]
    i = pl.program_id(0)

    def write(s, idx, act):
        @pl.when(flag_ref[i, idx] & _KEEP == 0)
        def _():
            act(pltpu.make_async_copy(
                stage.at[_slot_rows(s)],
                out_hbm.at[_chunk_rows(chunk_ref[i, idx])], sem))

    def place(s, idx):
        rows, flag = _slot_rows(s), flag_ref[i, idx]
        group = flag >> _GROUP_SHIFT

        @pl.when(flag & _CARRY != 0)
        def _():            # the rows an earlier token tile left: disjoint
            stage[rows, :] = stage[rows, :] + open_ref[group]

        @pl.when(flag & _KEEP != 0)
        def _():
            open_ref[group] = stage[rows, :]

        write(s, idx, lambda cp: cp.start())

    def block(kb, carry):
        hits, w = _placement(chunk_ref, dest_ref, gate_ref, row_ref, i, kb)
        stage[...] = sum(
            _dot(term, x_ref[...], _NN)
            for term in _terms(hits, w, x_ref.dtype)).astype(stage.dtype)
        _live_slots(n_ref, i, kb, place)
        _live_slots(n_ref, i, kb,
                    lambda s, idx: write(s, idx, lambda cp: cp.wait()))
        return carry

    jax.lax.fori_loop(0, pl.cdiv(n_ref[i], SLOTS), block, 0)


# ---------------------------------------------------------------------------
# calls
# ---------------------------------------------------------------------------

def _token_specs(dest_t, gates_t):
    """Block specs of `dest_t` [k, T] and, if given, `gates_t` [k, T]."""
    k = dest_t.shape[0]
    spec = pl.BlockSpec((k, TOKEN_TILE), lambda i, *_: (0, i))
    return [spec] * (1 if gates_t is None else 2)


_ROW_NUMBERS = pltpu.VMEM((SLOTS * CHUNK, 1), jnp.int32)


def _params():
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=_VMEM_LIMIT)


def gather_rows(srcs, dest_t, gates_t, chunks: TileChunks, *, name: str):
    """out[t] = sum over `srcs` (each [rows, d]) and over the choices j whose
    `dest_t[j, t]` lies inside them of gates_t[j, t] * src[dest_t[j, t]]
    (`gates_t` None: the plain sum), in their dtype, f32 accumulation.
    -> [T, d]."""
    d, dtype = srcs[0].shape[1], srcs[0].dtype
    tokens = dest_t.shape[1]
    weighted = gates_t is not None
    ops = (dest_t,) + ((gates_t,) if weighted else ()) + tuple(srcs)
    return pl.pallas_call(
        functools.partial(_gather_kernel, weighted=weighted,
                          sources=len(srcs)),
        name=name,
        out_shape=jax.ShapeDtypeStruct((tokens, d), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=_token_specs(dest_t, gates_t)
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(srcs),
            out_specs=pl.BlockSpec((TOKEN_TILE, d), lambda i, *_: (i, 0)),
            grid=(tokens // TOKEN_TILE,),
            scratch_shapes=[pltpu.VMEM((len(srcs), SLOTS * CHUNK, d), dtype),
                            pltpu.VMEM((TOKEN_TILE, d), jnp.float32),
                            _ROW_NUMBERS, pltpu.SemaphoreType.DMA(())]),
        compiler_params=_params(),
        interpret=_interpret(),
    )(chunks.chunk, chunks.n_read, *ops)


def gather_dots(src, dy, dest_t, chunks: TileChunks):
    """out[j, t] = dy[t] . src[dest_t[j, t]] in f32, 0 where `dest_t` lies
    outside `src`. -> [k, T]."""
    d = src.shape[1]
    k, tokens = dest_t.shape
    return pl.pallas_call(
        _dgates_kernel,
        name="moe_rows_dgates",
        out_shape=jax.ShapeDtypeStruct((k, tokens), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=_token_specs(dest_t, None)
            + [pl.BlockSpec((TOKEN_TILE, d), lambda i, *_: (i, 0)),
               pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((k, TOKEN_TILE), lambda i, *_: (0, i)),
            grid=(tokens // TOKEN_TILE,),
            scratch_shapes=[pltpu.VMEM((SLOTS * CHUNK, d), src.dtype),
                            _ROW_NUMBERS, pltpu.SemaphoreType.DMA(())]),
        compiler_params=_params(),
        interpret=_interpret(),
    )(chunks.chunk, chunks.n_read, dest_t, dy, src)


def scatter_rows(x, dest_t, gates_t, chunks: TileChunks, rows: int,
                 groups: int, *, name: str):
    """out[dest_t[j, t]] = gates_t[j, t] * x[t] for the choices whose
    `dest_t` lies below `rows` (`gates_t` None: x[t] itself), zeros in the
    rest of every row tile a group uses; other row tiles are not written.
    -> [rows, d] in x's dtype."""
    tokens, d = x.shape
    weighted = gates_t is not None
    ops = (dest_t,) + ((gates_t,) if weighted else ()) + (x,)
    return pl.pallas_call(
        functools.partial(_scatter_kernel, weighted=weighted),
        name=name,
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=_token_specs(dest_t, gates_t)
            + [pl.BlockSpec((TOKEN_TILE, d), lambda i, *_: (i, 0))],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            grid=(tokens // TOKEN_TILE,),
            scratch_shapes=[pltpu.VMEM((SLOTS * CHUNK, d), x.dtype),
                            pltpu.VMEM((groups, CHUNK, d), x.dtype),
                            _ROW_NUMBERS, pltpu.SemaphoreType.DMA(())]),
        compiler_params=_params(),
        interpret=_interpret(),
    )(chunks.chunk, chunks.n_write, chunks.flags, *ops)


def _check_rows_shapes(shapes, dtypes):
    out = []
    for s in shapes:
        if len(s) == 2 and s[1] >= LANE and s[1] % LANE:
            out.append(("error", f"dim {s[1]} is not a multiple of the "
                                 f"{LANE}-lane tile; the layer takes the "
                                 "jnp form for it"))
    return out


CONSTRAINT = register_constraint(KernelConstraint(
    name="moe_rows",
    kernel_fns=("_gather_kernel", "_scatter_kernel", "_dgates_kernel"),
    blocks={"chunk": CHUNK, "slots": SLOTS, "token_tile": TOKEN_TILE},
    note="rows move in 16-row chunks placed by a one-hot product; tokens in "
         "whole 128-token tiles, the row width whole lane tiles, else the "
         "layer's jnp form",
    checker=_check_rows_shapes,
    source="moe_rows.py",
))
