"""Grouped matmul: rows of `lhs` multiply the weight of the group they belong
to, `[rows, K] x [G, K, N] -> [rows, N]` — the per-expert products of a
dropless expert layer (parallel/moe.py `DroplessMoE`).

Reference analog: the per-expert GEMM loop behind global_scatter /
global_gather (python/paddle/incubate/distributed/models/moe/moe_layer.py:263)
and the cutlass grouped GEMM of the fused MoE ops. TPU-native form: the rows
are laid out sorted by group with every group starting on a row tile
(`group_layout`), so one row tile belongs to one group, the kernels need no
row masks on the forward path, and the grid walks only the tiles in use: work
is in proportion to the rows present (each group rounded up to the tile), not
to the buffer, which is sized for the worst case.

Three Pallas kernels, named in the compiled program and the device trace
`grouped_matmul` (forward), `grouped_matmul_dlhs` (d lhs = d out x rhs^T) and
`grouped_matmul_drhs` (d rhs[g] = lhs[g]^T x d out[g]), tied by a custom_vjp;
a jnp form covers shapes off the lane tile (the CPU tests' tiny models). The
form is chosen from shapes before the call; nothing a kernel raises is caught.

Rows between a group's size and the end of its last tile are padding: the
forward kernels multiply them like any row (their output rows mean nothing),
the drhs kernel leaves them out. Rows past the tiles in use are never read
and never written.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .constraints import (KernelConstraint, LANE, dtype_itemsize,
                          register_constraint)

# rows of one tile: every group starts on a multiple of it, and a group of n
# rows costs ceil(n / tile) tiles. The tile is a function of the call
# (`row_tile`): ROW_TILE where groups are large (12% padding at the ~512 rows
# an expert one chip's share of an 8-chip group sees, 1.5% at a deployment's
# ~4,096), down to SUBLANE_TILE, a bf16 tile's sublanes and the thinnest the
# kernels take, where a group holds a few rows (a decode step's 256 rows on
# 64 experts: at 128-row tiles 33 of every 34 buffer rows were padding)
ROW_TILE = 128
SUBLANE_TILE = 16
# widest block of the non-contracted weight dim: [K, COL_BLOCK] of bf16 at
# K 2048 is 4 MiB, double-buffered
COL_BLOCK = 1024
_VMEM_LIMIT = 64 << 20


class GroupLayout(NamedTuple):
    """Where each group's rows lie in a buffer of `tile_group.size * tile`
    rows. All int32."""
    sizes: jax.Array        # [G] rows of each group
    starts: jax.Array       # [G] first row of each group, a tile multiple
    tile_group: jax.Array   # [tiles] group of each row tile
    tile_rows: jax.Array    # [tiles] rows of the tile that are the group's
    n_tiles: jax.Array      # [] row tiles in use


def row_tile(tokens: int, choices: int, groups: int) -> int:
    """The row tile of a buffer for `tokens` tokens that each take `choices`
    of `groups` groups: twice the rows a group holds on average in the worst
    case (every token on `min(choices, groups)` of them), rounded up to a
    power of two, no thinner than SUBLANE_TILE and no wider than ROW_TILE.
    Twice, not once: on the chip a tile costs its grid steps first (~3 us a
    tile over a SwiGLU layer's three products), the passes over the whole
    buffer second and its padding rows least (PERF.md section 6, PR 36), and
    a tile of twice the mean still holds nearly every group whole."""
    mean = -(-tokens * min(choices, groups) // groups)
    return min(ROW_TILE, max(SUBLANE_TILE, 1 << (2 * mean - 1).bit_length()))


def buffer_rows(max_rows: int, groups: int, tile: int = ROW_TILE) -> int:
    """Rows of a buffer that holds `max_rows` rows in `groups` groups however
    they fall: every group may end in a partly filled tile, and an empty group
    keeps one tile (the drhs kernel zeroes its weight gradient there)."""
    return (-(-max_rows // tile) + groups) * tile


def group_layout(sizes, rows: int, tile: int = ROW_TILE) -> GroupLayout:
    """The layout of groups of `sizes` rows in a buffer of `rows` rows."""
    if rows % tile:
        raise ValueError(f"{rows} buffer rows do not divide into row tiles "
                         f"of {tile}")
    sizes = sizes.astype(jnp.int32)
    n = rows // tile
    tiles = jnp.maximum(1, -(-sizes // tile))
    ends = jnp.cumsum(tiles)
    first = ends - tiles
    t = jnp.arange(n, dtype=jnp.int32)
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, t, side="right").astype(jnp.int32),
        sizes.shape[0] - 1)
    tile_rows = jnp.clip(
        sizes[tile_group] - (t - first[tile_group]) * tile, 0, tile)
    return GroupLayout(sizes, first * tile, tile_group,
                       jnp.where(t < ends[-1], tile_rows, 0).astype(jnp.int32),
                       ends[-1].astype(jnp.int32))


def _tile(lhs, layout: GroupLayout) -> int:
    return lhs.shape[0] // layout.tile_group.shape[0]


def _col_block(n: int) -> int:
    """Largest lane-tile multiple <= COL_BLOCK that divides n."""
    return max(b for b in range(LANE, min(n, COL_BLOCK) + 1, LANE)
               if n % b == 0)


def _pallas_ok(m: int, k: int, n: int, tile: int) -> bool:
    """Shapes the kernels take: both weight dims whole lane tiles, rows in
    sublane-aligned tiles."""
    return k % LANE == 0 and n % LANE == 0 and tile % SUBLANE_TILE == 0 \
        and m % tile == 0


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _mm_kernel(tg_ref, lhs_ref, rhs_ref, out_ref, *, rhs_t: bool):
    del tg_ref
    dims = (((1,), (1 if rhs_t else 0,)), ((), ()))
    out_ref[...] = jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...], dims,
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _rows_matmul(lhs, rhs, layout: GroupLayout, *, rhs_t: bool, name: str):
    """out[r] = lhs[r] @ rhs[group of r] (`rhs_t`: @ rhs[group]^T). Grid
    (column blocks, row tiles in use), rows inner: a group's weight block is
    fetched once a column block, the rows stream past it."""
    m, c = lhs.shape
    g, k, n = rhs.shape
    width = k if rhs_t else n           # the output's columns
    tm, tb = _tile(lhs, layout), _col_block(width)
    if rhs_t:
        rhs_spec = pl.BlockSpec((None, tb, n), lambda j, t, tg: (tg[t], j, 0))
    else:
        rhs_spec = pl.BlockSpec((None, k, tb), lambda j, t, tg: (tg[t], 0, j))
    return pl.pallas_call(
        functools.partial(_mm_kernel, rhs_t=rhs_t),
        name=name,
        out_shape=jax.ShapeDtypeStruct((m, width), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, c), lambda j, t, tg: (t, 0)),
                      rhs_spec],
            out_specs=pl.BlockSpec((tm, tb), lambda j, t, tg: (t, j)),
            grid=(width // tb, layout.n_tiles)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * c + g * k * n + m * width)
            * lhs.dtype.itemsize),
        interpret=_interpret(),
    )(layout.tile_group, lhs, rhs)


def _drhs_kernel(tg_ref, rows_ref, lhs_ref, dout_ref, out_ref, acc_ref, *,
                 tile: int):
    t, last = pl.program_id(2), pl.num_programs(2) - 1
    group = tg_ref[t]
    rows = rows_ref[t]
    dims = (((0,), (0,)), ((), ()))

    @pl.when((t == 0) | (tg_ref[jnp.maximum(t - 1, 0)] != group))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(rows == tile)
    def _():
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], dout_ref[...], dims,
            preferred_element_type=jnp.float32)

    @pl.when((rows > 0) & (rows < tile))
    def _():
        # the group's last, partly filled tile: its other rows are padding
        def keep(ref):
            live = jax.lax.broadcasted_iota(jnp.int32, ref.shape, 0) < rows
            return jnp.where(live, ref[...], jnp.zeros_like(ref))

        acc_ref[...] += jax.lax.dot_general(
            keep(lhs_ref), keep(dout_ref), dims,
            preferred_element_type=jnp.float32)

    @pl.when((t == last) | (tg_ref[jnp.minimum(t + 1, last)] != group))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _drhs_pallas(lhs, dout, layout: GroupLayout, groups: int, dtype):
    """out[g] = lhs[rows of g]^T @ dout[rows of g]. Grid (K blocks, N blocks,
    row tiles in use), rows innermost: a [K block, N block] accumulator in
    VMEM is zeroed on a group's first tile and written on its last."""
    m, k = lhs.shape
    n = dout.shape[1]
    tm, tk, tn = _tile(lhs, layout), _col_block(k), _col_block(n)
    return pl.pallas_call(
        functools.partial(_drhs_kernel, tile=tm),
        name="grouped_matmul_drhs",
        out_shape=jax.ShapeDtypeStruct((groups, k, n), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda a, b, t, tg, tr: (t, a)),
                pl.BlockSpec((tm, tn), lambda a, b, t, tg, tr: (t, b))],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda a, b, t, tg, tr: (tg[t], a, b)),
            grid=(k // tk, n // tn, layout.n_tiles),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k + m * n) * lhs.dtype.itemsize
            + groups * k * n * jnp.dtype(dtype).itemsize),
        interpret=_interpret(),
    )(layout.tile_group, layout.tile_rows, lhs, dout)


# ---------------------------------------------------------------------------
# jnp form
# ---------------------------------------------------------------------------

def _row_onehot(m: int, layout: GroupLayout, dtype):
    """[rows, G] one-hot of each row's group; zero for padding rows."""
    tile = m // layout.tile_group.shape[0]
    r = jnp.arange(m, dtype=jnp.int32)
    group = layout.tile_group[r // tile]
    live = (r - layout.starts[group] < layout.sizes[group]) \
        & (r // tile < layout.n_tiles)
    return jax.nn.one_hot(jnp.where(live, group, -1), layout.sizes.shape[0],
                          dtype=dtype)


def _forward(lhs, rhs, layout):
    if _pallas_ok(lhs.shape[0], rhs.shape[1], rhs.shape[2],
                  _tile(lhs, layout)):
        return _rows_matmul(lhs, rhs, layout, rhs_t=False,
                            name="grouped_matmul")
    hot = _row_onehot(lhs.shape[0], layout, lhs.dtype)
    return jnp.einsum("mg,mk,gkn->mn", hot, lhs, rhs,
                      preferred_element_type=jnp.float32).astype(lhs.dtype)


def _dlhs(dout, rhs, layout):
    if _pallas_ok(dout.shape[0], rhs.shape[1], rhs.shape[2],
                  _tile(dout, layout)):
        return _rows_matmul(dout, rhs, layout, rhs_t=True,
                            name="grouped_matmul_dlhs")
    hot = _row_onehot(dout.shape[0], layout, dout.dtype)
    return jnp.einsum("mg,mn,gkn->mk", hot, dout, rhs,
                      preferred_element_type=jnp.float32).astype(dout.dtype)


def _drhs(lhs, dout, layout, groups, dtype):
    if _pallas_ok(lhs.shape[0], lhs.shape[1], dout.shape[1],
                  _tile(lhs, layout)):
        return _drhs_pallas(lhs, dout, layout, groups, dtype)
    hot = _row_onehot(lhs.shape[0], layout, lhs.dtype)
    return jnp.einsum("mg,mk,mn->gkn", hot, lhs, dout,
                      preferred_element_type=jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------

@jax.custom_vjp
def grouped_matmul(lhs, rhs, layout: GroupLayout):
    """`lhs` [rows, K] laid out as `layout` says (group_layout) times `rhs`
    [G, K, N] -> [rows, N] in lhs's dtype, f32 accumulation. Output rows that
    are no group's are undefined."""
    return _forward(lhs, rhs, layout)


def _gmm_fwd(lhs, rhs, layout):
    return _forward(lhs, rhs, layout), (lhs, rhs, layout)


def _gmm_bwd(res, dout):
    lhs, rhs, layout = res
    return (_dlhs(dout, rhs, layout),
            _drhs(lhs, dout, layout, rhs.shape[0], rhs.dtype), None)


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)


# ---------------------------------------------------------------------------
# registry: shape check and roofline
# ---------------------------------------------------------------------------

def _operands(shapes, dtypes):
    """The (shape, dtype) pairs of one call's rank-2 operands and of its
    rank-3 ones, scalar-prefetch vectors skipped: forward and dlhs take one
    of each (rows, weights), drhs two of rank 2 (lhs, dout)."""
    two = [(s, d) for s, d in zip(shapes, dtypes) if len(s) == 2]
    three = [(s, d) for s, d in zip(shapes, dtypes) if len(s) == 3]
    return two, three


def _check_grouped_shapes(shapes, dtypes):
    out = []
    two, three = _operands(shapes, dtypes)
    dims = [d for s, _ in two for d in s[1:]] \
        + [d for s, _ in three for d in s[1:]]
    for d in sorted(set(dims)):
        if d % LANE:
            out.append(("error",
                        f"dim {d} is not a multiple of the {LANE}-lane "
                        "tile; the wrapper takes the jnp form for it"))
    for s, _ in two:
        if s[0] % SUBLANE_TILE:
            out.append(("error", f"{s[0]} rows do not divide into "
                                 "sublane-aligned row tiles"))
    return out


def grouped_matmul_cost(rows: int, k: int, n: int, groups: int,
                        itemsize: int = 2) -> dict:
    """FLOPs and least HBM bytes of ONE of the three products over `rows`
    rows that are multiplied (padding included): every product is
    2 * rows * K * N; each reads two of {lhs, rhs, out}-sized operands and
    writes the third."""
    return {"flops": 2 * rows * k * n,
            "hbm_bytes": itemsize * (rows * k + groups * k * n + rows * n)}


def _grouped_roofline(shapes, dtypes):
    """One launch at the buffer's whole size: the static pass knows no group
    sizes, so this is the upper bound; the benchmark's reader counts the rows
    in use (benchmark/arith_glm4_moe_lite.py)."""
    two, three = _operands(shapes, dtypes)
    if two and three:                      # forward, dlhs: rows x weights
        (m, _), (g, k, n) = two[0][0], three[0][0]
        size = dtype_itemsize(two[0][1])
    elif len(two) >= 2:                    # drhs: lhs [M, K], dout [M, N]
        (m, k), (_, n) = two[0][0], two[1][0]
        g, size = 1, dtype_itemsize(two[0][1])
    else:
        return None
    return grouped_matmul_cost(m, k, n, g, size)


CONSTRAINT = register_constraint(KernelConstraint(
    name="grouped_matmul",
    kernel_fns=("_mm_kernel", "_drhs_kernel"),
    blocks={"row_tile": ROW_TILE, "col_block": COL_BLOCK},
    note="rows sorted by group, every group on a row-tile boundary "
         "(group_layout) — the layout's own tile, 16 to row_tile rows, which "
         "row_tile() gives from the call's tokens, choices and groups; K and "
         "N whole lane tiles, else the jnp form",
    checker=_check_grouped_shapes,
    source="grouped_matmul.py",
    roofline=_grouped_roofline,
))
