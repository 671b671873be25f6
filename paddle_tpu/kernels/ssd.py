"""The state-space recurrence of a Mamba-2 mixer (SSD, arXiv:2405.21060) over
per-sequence float32 state held in place.

For head h of group g, with Δ_t > 0, A_h < 0, x_t [P], B_t, C_t [N]:

    S_t = exp(Δ_t A_h) S_{t-1} + Δ_t x_t B_{t,g}ᵀ      S [P, N]
    y_t = S_t C_{t,g}

(the skip term D_h x_t is the caller's). The state lives in one array a
layer, `[R, H, N, P]` float32: row r is one sequence's state, stored
transposed (state dimensions on sublanes, head dimensions on the 128 lanes),
and row 0 is the sink that idle batch rows read and write. Both kernels take
the state by row through scalar-prefetched row ids and alias it to their
output, so a caller that donates it updates it in place: no program copies it
whole.

- `ssd_decode`: one token of every batch row, a grid step a row and a block
  of heads of one group. Memory-bound: each live row's state is read and
  written once. All arithmetic is float32 on the vector unit.
- `ssd_scan`: the chunked form over segments of a window (a segment: one
  sequence's tokens, from its first row; rows past its length carry Δ = 0, so
  they neither decay nor feed the state). Chunks of `chunk` tokens are counted
  from the segment's first row, wherever its first position lies in the
  sequence. A segment starts from its row's carried state, or from zero where
  `fresh` says it begins the sequence; its state after its last token goes
  back to the row. Within a chunk (Q tokens, c the within-chunk cumulative
  sum of Δ A, S the state entering the chunk):

      y     = (C Bᵀ ⊙ L) (Δ x) + exp(c) ⊙ (C Sᵀ)      L[t, s] = exp(c_t - c_s), s <= t
      S_out = exp(c_Q) S + (exp(c_Q - c) ⊙ Δ x)ᵀ B

  The matmuls run at `Precision.HIGHEST`: the state is float32 and so is what
  feeds it.

`ssd_decode_reference` and `ssd_scan_reference` are the same contracts in
`jax.numpy`, token by token (the oracle of the tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .constraints import KernelConstraint, register_constraint
from .decode_attention import _on_tpu

_HI = jax.lax.Precision.HIGHEST
# heads a decode grid step updates: one sublane tile of the [H, P] inputs
HEAD_BLOCK = 8


def _decode_roofline(shapes, dtypes):
    """One launch reads and writes the state of every batch row (idle rows
    the sink's) and reads its inputs."""
    if len(shapes) < 6 or len(shapes[-1]) != 4:
        return None
    b, h, p = shapes[2]
    n = shapes[-1][2]
    state = b * h * n * p * 4
    return {"flops": 5 * b * h * n * p,
            "hbm_bytes": 2 * state + 2 * b * h * p * 4 + 2 * b * 2 * n * 4}


def _scan_roofline(shapes, dtypes):
    """One launch: four matmuls a chunk and head, each segment's row of
    state read and written once a head, the inputs read and `y` written
    (B and C once a head: every head of a group reads its group's)."""
    if len(shapes) < 8 or len(shapes[4]) != 4 or len(shapes[7]) != 4:
        return None
    s, h, t, p = shapes[4]
    n = shapes[7][2]
    n_k = shapes[2][0] // max(1, s * h)
    if not n_k:
        return None
    q = t // n_k
    flops = s * h * n_k * 2 * q * (q * n + q * p + 2 * n * p)
    return {"flops": flops,
            "hbm_bytes": 4 * s * h * (2 * n * p + 2 * t * p + 2 * t * n + t)}


CONSTRAINT_DECODE = register_constraint(KernelConstraint(
    name="ssd_decode",
    kernel_fns=("_decode_kernel",),
    blocks={"head_block": HEAD_BLOCK},
    note="state [R, H, N, P] f32 in place by row; heads of a grid step in "
         "one group; inputs [b, H, P] and [b, G, N, 1]",
    source="ssd.py",
    roofline=_decode_roofline,
))

CONSTRAINT_SCAN = register_constraint(KernelConstraint(
    name="ssd_scan",
    kernel_fns=("_scan_kernel",),
    blocks={},
    note="state [R, H, N, P] f32 in place by row; a segment's window a "
         "whole number of chunks",
    source="ssd.py",
    roofline=_scan_roofline,
))


# ---- one token: the decode step ---------------------------------------------

def _decode_kernel(rows_ref, da_ref, xdt_ref, b_ref, c_ref, s_in, s_out,
                   y_ref):
    """Grid (batch row, head block). `s_in` / `s_out`: the row's state of
    the block's heads [HB, N, P] (aliased); `b_ref` / `c_ref`: the group's
    B and C as columns [N, 1]; `xdt_ref` [HB, P]; `da_ref`: exp(Δ A) of
    every row and head, in SMEM."""
    del rows_ref
    r, hb = pl.program_id(0), pl.program_id(1)
    heads = xdt_ref.shape[0]
    b_col, c_col = b_ref[...], c_ref[...]
    for j in range(heads):
        s = s_in[j] * da_ref[r, hb * heads + j] \
            + b_col * xdt_ref[j:j + 1, :]
        s_out[j] = s
        y_ref[j:j + 1, :] = jnp.sum(c_col * s, axis=0, keepdims=True)


def _head_block(heads_per_group: int) -> int:
    return max(d for d in range(1, min(HEAD_BLOCK, heads_per_group) + 1)
               if heads_per_group % d == 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode(state, rows, xdt, da, b, c, *, interpret: bool):
    bsz, h, p = xdt.shape
    n = state.shape[2]
    g = b.shape[1]
    hb = _head_block(h // g)
    per_group = h // g // hb

    def by_group(r, j, rows_):
        return (r, j // per_group, 0, 0)

    col = pl.BlockSpec((None, None, n, 1), by_group)
    heads = pl.BlockSpec((None, hb, p), lambda r, j, rows_: (r, j, 0))
    st = pl.BlockSpec((None, hb, n, p), lambda r, j, rows_: (rows_[r], j, 0,
                                                            0))
    state, y = pl.pallas_call(
        _decode_kernel,
        name=CONSTRAINT_DECODE.name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, h // hb),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), heads, col,
                      col, st],
            out_specs=[st, heads]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((bsz, h, p), jnp.float32)],
        # operands count the prefetched rows: the state is operand 5
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(rows.astype(jnp.int32), da.astype(jnp.float32),
      xdt.astype(jnp.float32), b.astype(jnp.float32)[..., None],
      c.astype(jnp.float32)[..., None], state)
    return y, state


def ssd_decode(state, rows, xdt, da, b, c):
    """One recurrence step of each batch row, in place where the caller
    donates `state`.

    state [R, H, N, P] f32; rows [b] int32, each row's state (idle rows name
    the sink row 0; live rows are distinct); xdt [b, H, P]: Δ x; da [b, H]:
    exp(Δ A); b, c [b, G, N]: B and C of each group (head h reads group
    h // (H / G)). Returns (y [b, H, P] f32 = S_t C, the state)."""
    bsz, h, p = xdt.shape
    if state.dtype != jnp.float32 or state.ndim != 4 \
            or state.shape[1] != h or state.shape[3] != p \
            or b.shape != (bsz, b.shape[1], state.shape[2]) \
            or c.shape != b.shape or h % b.shape[1] \
            or da.shape != (bsz, h) or rows.shape != (bsz,):
        raise ValueError(
            f"ssd_decode: state {state.shape} {state.dtype}, rows "
            f"{rows.shape}, xdt {xdt.shape}, da {da.shape}, b {b.shape}, "
            f"c {c.shape}")
    return _decode(state, rows, xdt, da, b, c, interpret=not _on_tpu())


def ssd_decode_reference(state, rows, xdt, da, b, c):
    """`ssd_decode` in jax.numpy (a gather, the step, a scatter)."""
    h, g = xdt.shape[1], b.shape[1]
    group = jnp.arange(h) // (h // g)
    s = state[rows] * da[:, :, None, None] \
        + b[:, group, :, None] * xdt[:, :, None, :]
    y = jnp.einsum("bhnp,bhn->bhp", s, c[:, group], precision=_HI)
    return y, state.at[rows].set(s)


# ---- a window: the chunked scan ---------------------------------------------

def _scan_kernel(rows_ref, fresh_ref, decay_ref, cum_ref, x_ref, b_ref,
                 c_ref, s_in, y_ref, s_out, s_scr):
    """Grid (segment, head, chunk), the chunks in order. `s_scr` carries the
    head's state [N, P] from chunk to chunk; `s_in` / `s_out` are the
    segment's row (aliased), read before the first chunk and written after
    the last. `cum_ref` [1, Q]: the within-chunk cumulative Δ A; `x_ref`
    [Q, P]: Δ x; `b_ref` / `c_ref` [Q, N]; `decay_ref` (SMEM): exp of each
    chunk's total Δ A."""
    del rows_ref
    seg, h, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_h, n_k = pl.num_programs(1), pl.num_programs(2)

    @pl.when(k == 0)
    def _():
        s_scr[...] = jnp.where(fresh_ref[seg] != 0, 0.0, s_in[...])

    q = x_ref.shape[0]
    # cum_s along lanes, and its transpose: cum_t along lanes
    on_cols = jnp.broadcast_to(cum_ref[...], (q, q))
    on_rows = on_cols.T
    causal = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1) \
        <= jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    decay = jnp.exp(jnp.where(causal, on_rows - on_cols, -jnp.inf))
    xdt, bb, cc = x_ref[...], b_ref[...], c_ref[...]
    s = s_scr[...]
    cb = jax.lax.dot_general(cc, bb, (((1,), (1,)), ((), ())), precision=_HI,
                             preferred_element_type=jnp.float32)
    y = jnp.dot(cb * decay, xdt, precision=_HI,
                preferred_element_type=jnp.float32)
    y = y + jnp.exp(on_rows[:, :1]) * jnp.dot(
        cc, s, precision=_HI, preferred_element_type=jnp.float32)
    y_ref[...] = y
    # exp(c_Q - c_t), one a row
    to_end = jnp.exp(on_rows[q - 1:q, :] - on_rows)[:, :1]
    s = s * decay_ref[(seg * n_h + h) * n_k + k] + jax.lax.dot_general(
        bb, xdt * to_end, (((0,), (0,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)
    s_scr[...] = s

    @pl.when(k == n_k - 1)
    def _():
        s_out[...] = s


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _scan(state, rows, fresh, xdt, a, b, c, *, chunk: int, interpret: bool):
    n_seg, h, t, p = xdt.shape
    n, g = state.shape[2], b.shape[1]
    n_k = t // chunk
    per_group = h // g
    # the within-chunk cumulative sums, and each chunk's decay whole
    cum = jnp.cumsum(a.astype(jnp.float32).reshape(n_seg, h, n_k, chunk),
                     axis=-1)
    decay = jnp.exp(cum[..., -1]).reshape(-1)
    cum = cum.reshape(n_seg, h, 1, t)
    st = pl.BlockSpec((None, None, n, p),
                      lambda s, j, k, rows_, fresh_: (rows_[s], j, 0, 0))
    by_head = pl.BlockSpec((None, None, chunk, p),
                           lambda s, j, k, rows_, fresh_: (s, j, k, 0))
    by_group = pl.BlockSpec(
        (None, None, chunk, n),
        lambda s, j, k, rows_, fresh_: (s, j // per_group, k, 0))
    y, state = pl.pallas_call(
        _scan_kernel,
        name=CONSTRAINT_SCAN.name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_seg, h, n_k),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec((None, None, 1, chunk),
                                   lambda s, j, k, rows_, fresh_: (s, j, 0,
                                                                   k)),
                      by_head, by_group, by_group, st],
            out_specs=[by_head, st],
            scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((n_seg, h, t, p), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the two prefetched scalars: the state is operand 7
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(rows.astype(jnp.int32), fresh.astype(jnp.int32), decay, cum,
      xdt.astype(jnp.float32), b.astype(jnp.float32), c.astype(jnp.float32),
      state)
    return y, state


def ssd_scan(state, rows, fresh, xdt, a, b, c, *, chunk: int):
    """The recurrence over each segment's window, in place where the caller
    donates `state`.

    state [R, H, N, P] f32; rows [S] int32, each segment's state row
    (distinct); fresh [S]: nonzero where the segment begins its sequence
    (it starts from zero state, not the row's); xdt [S, H, T, P]: Δ x; a
    [S, H, T]: Δ A; b, c [S, G, T, N]. Rows past a segment's length must
    carry Δ = 0 (a = 0, xdt = 0): the state is then that after its last
    token. T a whole number of `chunk`s. Returns (y [S, H, T, P] f32 =
    S_t C_t, the state)."""
    n_seg, h, t, p = xdt.shape
    g = b.shape[1]
    if state.dtype != jnp.float32 or state.ndim != 4 \
            or state.shape[1] != h or state.shape[3] != p or t % chunk \
            or a.shape != (n_seg, h, t) or h % g \
            or b.shape != (n_seg, g, t, state.shape[2]) or c.shape != b.shape \
            or rows.shape != (n_seg,) or fresh.shape != (n_seg,):
        raise ValueError(
            f"ssd_scan: state {state.shape} {state.dtype}, rows {rows.shape}, "
            f"fresh {fresh.shape}, xdt {xdt.shape}, a {a.shape}, b "
            f"{b.shape}, c {c.shape}, chunk {chunk}")
    return _scan(state, rows, fresh, xdt, a, b, c, chunk=chunk,
                 interpret=not _on_tpu())


def ssd_scan_reference(state, rows, fresh, xdt, a, b, c):
    """`ssd_scan` in jax.numpy, token by token (no chunks)."""
    h, g = xdt.shape[1], b.shape[1]
    group = jnp.arange(h) // (h // g)
    s0 = jnp.where((fresh != 0)[:, None, None, None], 0.0, state[rows])

    def step(s, t):
        s = s * jnp.exp(a[:, :, t])[..., None, None] \
            + b[:, group, t, :, None] * xdt[:, :, t, None, :]
        return s, jnp.einsum("shnp,shn->shp", s, c[:, group, t],
                             precision=_HI)

    s, y = jax.lax.scan(step, s0, jnp.arange(xdt.shape[2]))
    return jnp.moveaxis(y, 0, 2), state.at[rows].set(s)
