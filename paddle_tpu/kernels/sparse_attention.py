"""Learned sparse attention over latent pages, as Pallas TPU kernels.

The attention of DeepSeek-V3.2's sparse layers served from a paged cache: an
indexer scores every cached key of a sequence, the top `index_topk` positions
are chosen, and latent attention (MLA, absorbed projections) reads only the
latent rows of those positions. Four pieces, each under its own trace label:

  dsa_index_scores   I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s]) over every
                     cached index key of the row's pages, the heads reduced
                     inside the kernel (the f32 [rows, heads, context] tensor
                     is never written); -inf past each row's position
  (selection)        exact top-k of I, the lower position winning a tie: in
                     decode `jax.lax.top_k` (the positions, which
                     `decode_plan` sorts by page); in a prefill window
                     each row's k-th largest score and its last kept tie
                     (`window_selection`, no sort), which the window kernel
                     reads as its mask. Each lane keeps the faster on a
                     v5e at 2,048 of 27,648 keys:
                     top_k 0.55 ms for 24 rows (the bisection and a
                     compaction 3.3), the bisection 17 ms for 2,048 rows
                     (top_k 62, and 648 MiB of temporaries)
  sparse_mla_decode  one query row a sequence over the pages that hold its
                     selected rows (`decode_plan`, made once a `full` layer
                     and reused by the `shared` ones), each page one DMA of
                     the whole page, `DECODE_DEPTH` in flight; the page's
                     selected rows are copied out in VMEM and scored, in
                     position order, a chunk at a time as each chunk fills:
                     online softmax with a learned sink per head in the
                     denominator
  sparse_mla_window  a prefill window's query rows, blockwise: a masked sweep
                     over the window's context pages, the mask being each
                     row's selection (scores above its k-th largest, ties to
                     the lower index) — no [window, k, row] gather exists

Latent rows are stored packed: a row of `width` bf16 values (c_kv, then the
rotary key) padded to 2 x W values, W a whole number of lane tiles, held as
W int32 words — value i in the low half of word i, value W + i in the high
half. Mosaic copies no slice thinner than a tile along the second-minor
dimension, and a [N, 1, W] array of 32-bit words tiles by (1, 128): one row
is one tile, so a single row can be fetched and written by DMA. Unpacking is
two shifts and two bitcasts; the halves are exact bf16 values.

The index-key pools are [pages, 1, block, index_dim] bf16 and are read a page
a grid step through BlockSpecs. The latent pool of a layer is [pages * block,
1, W] int32: page p holds rows p * block ... p * block + block - 1.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .constraints import LANE, KernelConstraint, register_constraint
from .decode_attention import _on_tpu

_NEG = -1e30
# selected rows one step of the decode kernel scores, and the pages it keeps
# in flight. Every page that holds a selected row is copied whole: on a v5e
# at 24 sequences, 64 heads, 384-word rows and 256-row pages a page copied
# whole costs 0.63 us up to 16 selected rows (384 KiB at ~620 GB/s, the
# rows' copies and scoring hidden under it), a page fetched a row at a time
# 0.24 us + ~37 ns a row, so a row-by-row path would save at most ~0.4 us,
# and only on a page with 10 or fewer selected rows. A call at the cell's
# selections (`tools/sparse_decode_sweep.py`) takes 1,177 / 1,035 / 963 /
# 945 us at 2 / 3 / 4 / 6 pages in flight
DECODE_CHUNK, DECODE_DEPTH = 256, 4
# query rows one grid step of the window kernels takes
WINDOW_ROWS = 8
# query rows one grid step of the index kernel takes in a prefill window
INDEX_ROWS = 128


for _name, _fn, _blocks, _note in (
        ("dsa_index_scores", "_index_kernel", {"index_rows": INDEX_ROWS},
         "q [groups, rows x heads, d], a page of index keys [1, 1, block, d] "
         "a grid step through the table; scores [groups, rows, W x block]"),
        ("sparse_mla_decode", "_decode_kernel",
         {"decode_chunk": DECODE_CHUNK, "decode_depth": DECODE_DEPTH},
         "a sequence's pages that hold a selected row, DECODE_DEPTH in "
         "flight, each one DMA of [block, 1, W] int32; the selected rows "
         "[K + 1, 1, W] in VMEM, the plan in SMEM a sequence"),
        ("sparse_mla_window", "_window_kernel", {"window_rows": WINDOW_ROWS},
         "a masked sweep of a window's query blocks over its context pages "
         "[block, 1, W]; the mask each row's selection"),
        ("latent_commit", "_row_commit_kernel", {},
         "one packed row a slot into a [N, 1, W] int32 pool in HBM, aliased "
         "to the output")):
    register_constraint(KernelConstraint(
        name=_name, kernel_fns=(_fn,), blocks=_blocks, note=_note,
        source="sparse_attention.py"))


def latent_words(width: int) -> int:
    """int32 words of a packed latent row of `width` bf16 values."""
    return -(-width // (2 * LANE)) * LANE


def pack_rows(x):
    """bf16 rows [..., width] -> packed int32 words [..., W]."""
    w = latent_words(x.shape[-1])
    x = jnp.pad(x.astype(jnp.bfloat16),
                [(0, 0)] * (x.ndim - 1) + [(0, 2 * w - x.shape[-1])])
    bits = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.int32)
    return bits[..., :w] | (bits[..., w:] << 16)


def _halves(words):
    """Packed words -> (low values, high values) as f32, exact."""
    lo = jax.lax.bitcast_convert_type(words << 16, jnp.float32)
    hi = jax.lax.bitcast_convert_type(words & jnp.int32(-65536), jnp.float32)
    return lo, hi


def unpack_rows(words, width: int):
    """Packed int32 words [..., W] -> bf16 rows [..., width]."""
    lo, hi = _halves(words)
    return jnp.concatenate([lo, hi], -1)[..., :width].astype(jnp.bfloat16)


def _interpret() -> bool:
    return not _on_tpu()


# ---- indexer scores ------------------------------------------------------

def _index_kernel(tables_ref, last_ref, q_ref, w_ref, pos_ref, k_ref, o_ref,
                  *, heads: int, bs: int):
    """Grid (groups, table columns). q [tq * heads, d], w [tq * heads, 1],
    pos [tq, 1], one page of index keys [bs, d] -> scores [tq, bs]."""
    del tables_ref
    g, j = pl.program_id(0), pl.program_id(1)
    tq = o_ref.shape[1]

    @pl.when(j <= last_ref[g])
    def _():
        s = jax.lax.dot_general(q_ref[0], k_ref[0, 0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.maximum(s, 0.0) * w_ref[0]
        s = jnp.sum(s.reshape(tq, heads, bs), axis=1)
        col = j * bs + jax.lax.broadcasted_iota(jnp.int32, (tq, bs), 1)
        o_ref[0] = jnp.where(col <= pos_ref[0], s, -jnp.inf)

    @pl.when(j > last_ref[g])
    def _():
        o_ref[0] = jnp.full((tq, bs), -jnp.inf, jnp.float32)


def dsa_index_scores(q, w, pool, tables, pos):
    """I of query rows over the cached index keys of their pages.

    q [G, tq, H, d] (bf16), w [G, tq, H] (f32), pool [P, 1, bs, d],
    tables [G, W] int32 (a group of tq rows shares one table row), pos
    [G, tq] int32: the last position each row sees. Returns [G, tq, W * bs]
    f32, -inf past each row's position. Pages past the group's last
    position are neither fetched nor scored."""
    g, tq, h, d = q.shape
    bs, width = pool.shape[2], tables.shape[1]
    last = jnp.minimum(jnp.max(pos, axis=1) // bs, width - 1).astype(
        jnp.int32)
    kernel = functools.partial(_index_kernel, heads=h, bs=bs)
    return pl.pallas_call(
        kernel,
        name="dsa_index_scores",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(g, width),
            in_specs=[
                pl.BlockSpec((1, tq * h, d), lambda i, j, t, l: (i, 0, 0)),
                pl.BlockSpec((1, tq * h, 1), lambda i, j, t, l: (i, 0, 0)),
                pl.BlockSpec((1, tq, 1), lambda i, j, t, l: (i, 0, 0)),
                pl.BlockSpec((1, 1, bs, d), lambda i, j, t, l: (
                    t[i, jnp.minimum(j, l[i])], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, tq, bs), lambda i, j, t, l: (i, 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((g, tq, width * bs), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
    )(tables.astype(jnp.int32), last, q.reshape(g, tq * h, d),
      w.astype(jnp.float32).reshape(g, tq * h, 1),
      pos.astype(jnp.int32).reshape(g, tq, 1), pool)


def index_scores_reference(q, w, pool, tables, pos):
    """`dsa_index_scores` in jnp: the pages gathered, every head kept."""
    g, tq, h, d = q.shape
    bs, width = pool.shape[2], tables.shape[1]
    keys = pool[tables][:, :, 0].reshape(g, width * bs, d)
    s = jnp.einsum("gthd,gsd->gths", q.astype(jnp.float32),
                   keys.astype(jnp.float32))
    s = jnp.einsum("gths,gth->gts", jnp.maximum(s, 0.0),
                   w.astype(jnp.float32))
    col = jnp.arange(width * bs)[None, None, :]
    return jnp.where(col <= pos[..., None], s, -jnp.inf)


# ---- selection -----------------------------------------------------------

def select_topk(scores, k: int):
    """(values, positions) of the top `k` of each row of `scores` [R, S],
    exact; the lower position wins a tie. Rows with fewer than `k` finite
    scores end in -inf values."""
    vals, idx = jax.lax.top_k(scores, min(k, scores.shape[-1]))
    return vals, idx.astype(jnp.int32)


def value_lanes(value_width: int) -> int:
    """Lanes of the attention's output: the value part (c_kv) of a row,
    rounded up to whole lane tiles."""
    return -(-value_width // LANE) * LANE


def _accumulate(p, alpha, lo, hi, acc_lo, acc_hi):
    """acc <- alpha * acc + p @ rows, over the lanes the output keeps
    (`acc_hi`: () where they all lie in the low half)."""
    p = p.astype(jnp.bfloat16)
    acc_lo[...] = alpha * acc_lo[...] + jnp.dot(
        p, lo, preferred_element_type=jnp.float32)
    for acc in acc_hi:
        acc[...] = alpha * acc[...] + jnp.dot(
            p, hi[:, :acc.shape[-1]], preferred_element_type=jnp.float32)


def _finish(acc_lo, acc_hi, den, lanes: int):
    out = jnp.concatenate([acc_lo[...], *(a[...] for a in acc_hi)],
                          axis=-1) if acc_hi else acc_lo[...]
    return (out[:, :lanes] / den).astype(jnp.bfloat16)


def _acc_scratch(rows: int, words: int, lanes: int):
    """VMEM accumulators of the low half and, where the output reaches past
    it, of the high half's first lanes."""
    out = [pltpu.VMEM((rows, words), jnp.float32)]
    if lanes > words:
        out.append(pltpu.VMEM((rows, lanes - words), jnp.float32))
    return out


# ---- sparse latent attention, decode -------------------------------------

# meta rows of a `DecodePlan`
_PAGE, _COUNT, _FIRST = range(3)


class DecodePlan(NamedTuple):
    """The decode lane's selection arranged by page (`decode_plan`): made
    once a `full` layer, reused by the layers that share its selection.
    `cols` is the table's width."""
    # [B] int32: selected rows, and pages that hold at least one of them
    n_sel: jax.Array
    pages: jax.Array
    # [B, 3, cols] int32: of the j-th such page in position order, its pool
    # page, its selected rows and the first of them in `offsets`
    meta: jax.Array
    # [B, 1, K] int32: the selected positions' offsets in their pages,
    # ascending by position
    offsets: jax.Array


def decode_plan(idx, n_sel, tables, block_size: int):
    """The selection `idx` [B, K] (positions in score order, as
    `select_topk` gives them; past `n_sel` [B] unread) by the pages of
    `tables` [B, cols]: one sort of the positions, no scatter."""
    b, k = idx.shape
    cols, bs = tables.shape[1], block_size
    live = jnp.arange(k)[None] < n_sel[:, None]
    pos = jnp.sort(jnp.where(live, idx, cols * bs), axis=1)
    # selected rows before each page's first position
    before = jnp.sum(pos[:, :, None] < jnp.arange(cols + 1) * bs, axis=1,
                     dtype=jnp.int32)
    count = before[:, 1:] - before[:, :-1]
    # the columns that hold a selected row first, ascending
    order = jnp.argsort(count == 0, axis=1, stable=True)
    meta = jnp.stack([jnp.take_along_axis(a.astype(jnp.int32), order, axis=1)
                      for a in (tables, count, before[:, :-1])], axis=1)
    return DecodePlan(
        n_sel=n_sel.astype(jnp.int32),
        pages=jnp.sum(count > 0, axis=1, dtype=jnp.int32), meta=meta,
        offsets=(pos % bs).astype(jnp.int32)[:, None])


def _decode_kernel(nsel_ref, pages_ref, meta_ref, offs_ref, q_ref, sink_ref,
                   pool_hbm, o_ref, page_buf, rows_buf, sem, m_ref, l_ref,
                   acc_lo, *acc_hi, bs: int, chunk: int, scale: float,
                   words: int):
    """Grid (sequences,). The sequence's pages that hold a selected row, in
    position order, `depth` (`page_buf`'s first dimension) in flight, each
    one DMA of the whole page into `page_buf`; its selected rows are then
    copied into `rows_buf` by vector loads. So `rows_buf` fills with the
    selection in position order (its last row takes what the unrolled
    copies carry past a page's count), and each chunk of it is scored as
    soon as it is whole, while the next pages' copies run. Rows at or past
    n_sel score -inf and weigh as zeros: whatever they hold (an earlier
    sequence's rows, or VMEM never written) reaches no sum, so each
    sequence stands alone."""
    b = pl.program_id(0)
    n, pages, k = nsel_ref[b], pages_ref[b], rows_buf.shape[0] - 1
    depth = page_buf.shape[0]

    m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    for acc in (acc_lo, *acc_hi):
        acc[...] = jnp.zeros(acc.shape, jnp.float32)

    def copy(j):
        slot = j % depth
        return pltpu.make_async_copy(
            pool_hbm.at[pl.ds(meta_ref[0, _PAGE, j] * bs, bs)],
            page_buf.at[slot], sem.at[slot])

    def gather(j):
        count, first = meta_ref[0, _COUNT, j], meta_ref[0, _FIRST, j]

        def eight(t, carry):
            for u in range(8):
                i = t * 8 + u
                at = jnp.minimum(first + i, k - 1)
                rows_buf[jnp.where(i < count, first + i, k)] = \
                    page_buf[j % depth, offs_ref[0, 0, at]]
            return carry

        jax.lax.fori_loop(0, (count + 7) // 8, eight, 0)

    def score(c, carry):
        start = pl.multiple_of(c * chunk, chunk)
        rows = rows_buf[pl.ds(start, chunk), 0, :]
        row = start + jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
        lo, hi = _halves(jnp.where(row < n, rows, 0))
        lo, hi = lo.astype(jnp.bfloat16), hi.astype(jnp.bfloat16)
        q = q_ref[0]
        dims = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(q[:, :words], lo, dims,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(q[:, words:], hi, dims,
                                   preferred_element_type=jnp.float32)) \
            * scale
        col = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < n, s, -jnp.inf)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        _accumulate(p, alpha, lo, hi, acc_lo, acc_hi)
        m_ref[...] = m_new
        return carry

    for j in range(depth - 1):
        @pl.when(j < pages)
        def _():
            copy(j).start()

    def page(j, carry):
        @pl.when(j + depth - 1 < pages)
        def _():
            copy(j + depth - 1).start()

        copy(j).wait()
        gather(j)
        # the chunks this page completes
        first = meta_ref[0, _FIRST, j]
        jax.lax.fori_loop(first // chunk,
                          (first + meta_ref[0, _COUNT, j]) // chunk, score, 0)
        return carry

    jax.lax.fori_loop(0, pages, page, 0)
    jax.lax.fori_loop(n // chunk, (n + chunk - 1) // chunk, score, 0)
    den = l_ref[...] + jnp.exp(sink_ref[...] - m_ref[...])
    o_ref[0] = _finish(acc_lo, acc_hi, den, o_ref.shape[-1])


def sparse_mla_decode(q, pool, plan: DecodePlan, sink, *, scale: float,
                      value_width: int, block_size: int):
    """Latent attention of one query row a sequence over its selected rows.

    q [B, H, width] (the absorbed query: q_nope W_uk, then q_rope), pool
    [N, 1, W] int32 packed latent rows in pages of `block_size`, `plan` the
    selection by page (`decode_plan` over the pool's page tables), sink [H]
    f32. Returns [B, H, value_lanes(value_width)] bf16: the
    softmax-weighted sum of the rows' first `value_width` values (c_kv), a
    sink in the denominator."""
    b, h, _ = q.shape
    words = pool.shape[-1]
    cols, k = plan.meta.shape[-1], plan.offsets.shape[-1]
    chunk = min(DECODE_CHUNK, k)
    if k % chunk:
        raise ValueError(f"sparse_mla_decode: {k} rows in chunks of {chunk}")
    lanes = value_lanes(value_width)
    qp = jnp.pad(q.astype(jnp.bfloat16), ((0, 0), (0, 0),
                                          (0, 2 * words - q.shape[-1])))
    kernel = functools.partial(_decode_kernel, bs=block_size, chunk=chunk,
                               scale=scale, words=words)
    seq = lambda i, n, pg: (i, 0, 0)  # noqa: E731
    return pl.pallas_call(
        kernel,
        name="sparse_mla_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, 3, cols), seq, memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 1, k), seq, memory_space=pltpu.SMEM),
                pl.BlockSpec((1, h, 2 * words), seq),
                pl.BlockSpec((h, 1), lambda i, n, pg: (0, 0)),
                pl.BlockSpec(memory_space=pltpu.HBM),
            ],
            out_specs=pl.BlockSpec((1, h, lanes), seq),
            scratch_shapes=[pltpu.VMEM((DECODE_DEPTH, block_size, 1, words),
                                       jnp.int32),
                            pltpu.VMEM((k + 1, 1, words), jnp.int32),
                            pltpu.SemaphoreType.DMA((DECODE_DEPTH,)),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            *_acc_scratch(h, words, lanes)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, lanes), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(),
    )(plan.n_sel, plan.pages, plan.meta, plan.offsets, qp,
      sink.astype(jnp.float32).reshape(h, 1), pool)


def sparse_decode_reference(q, pool, rows, n_sel, sink, *, scale: float,
                            value_width: int):
    """`sparse_mla_decode` in jnp (f32, the value part unpadded): the
    selected rows gathered whole."""
    kv = unpack_rows(pool[rows][:, :, 0], q.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("bhd,bkd->bhk", q.astype(jnp.bfloat16).astype(
        jnp.float32), kv) * scale
    live = jnp.arange(rows.shape[1])[None, None, :] < n_sel[:, None, None]
    probs = _sink_softmax(jnp.where(live, s, -jnp.inf), sink)
    return jnp.einsum("bhk,bkd->bhd", probs, kv[..., :value_width])


def _sink_softmax(s, sink):
    """softmax over the last axis of s [..., H, K] with exp(sink[h]) added to
    each head's denominator."""
    m = jnp.maximum(jnp.max(s, -1, keepdims=True), sink[:, None])
    e = jnp.exp(s - m)
    return e / (jnp.sum(e, -1, keepdims=True) + jnp.exp(sink[:, None] - m))


# ---- sparse latent attention, a prefill window ---------------------------

def _window_kernel(tables_ref, last_ref, q_ref, sc_ref, thr_ref, tie_ref,
                   pos_ref, sink_ref, kv_ref, o_ref, m_ref, l_ref,
                   acc_lo, *acc_hi, heads: int, bs: int, scale: float,
                   words: int):
    """Grid (query blocks, table columns). q [tq * heads, 2W] bf16, the
    block's index scores over the page [tq, bs], each row's k-th largest
    score `thr` and the last position of the ties it keeps `tie` [tq, 1],
    its position [tq, 1]; one page of packed latent rows [bs, 1, W]."""
    del tables_ref
    g, j = pl.program_id(0), pl.program_id(1)
    tq = sc_ref.shape[1]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        for acc in (acc_lo, *acc_hi):
            acc[...] = jnp.zeros(acc.shape, jnp.float32)

    @pl.when(j <= last_ref[g])
    def _():
        lo, hi = _halves(kv_ref[:, 0, :])
        lo, hi = lo.astype(jnp.bfloat16), hi.astype(jnp.bfloat16)
        q = q_ref[0]
        dims = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(q[:, :words], lo, dims,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(q[:, words:], hi, dims,
                                   preferred_element_type=jnp.float32)) \
            * scale
        score, thr = sc_ref[0], thr_ref[0]
        col = j * bs + jax.lax.broadcasted_iota(jnp.int32, (tq, bs), 1)
        sel = (col <= pos_ref[0]) & ((score > thr) | (
            (score == thr) & (col <= tie_ref[0])))
        sel = jnp.broadcast_to(sel[:, None, :], (tq, heads, bs)).reshape(
            tq * heads, bs)
        s = jnp.where(sel, s, -jnp.inf)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        _accumulate(p, alpha, lo, hi, acc_lo, acc_hi)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        sink = jnp.broadcast_to(sink_ref[...][None], (tq, heads, 1)).reshape(
            tq * heads, 1)
        den = l_ref[...] + jnp.exp(sink - m_ref[...])
        o_ref[0] = _finish(acc_lo, acc_hi, den, o_ref.shape[-1])


def window_selection(scores, k: int):
    """A prefill window's selection as the window kernel reads it: each
    row's k-th largest score and the last position among the ties at that
    score it keeps ([R] each), from the rows' scores [R, S] — the set
    `select_topk` would return, found without sorting: the k-th largest
    by a bisection over the scores' order-preserving 32-bit keys (32
    counting passes), the kept ties by a running count."""
    r, n = scores.shape
    if n <= k:
        return (jnp.full((r,), -jnp.inf, jnp.float32),
                jnp.full((r,), n - 1, jnp.int32))
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32),
                                        jnp.int32)
    # order-preserving: flip every bit but the sign of a negative float,
    # then move the sign so the keys compare as unsigned
    key = jax.lax.bitcast_convert_type(
        jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits),
        jnp.uint32) ^ jnp.uint32(0x80000000)

    def bit(i, ans):
        cand = ans | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(key >= cand[:, None], axis=1) >= k
        return jnp.where(enough, cand, ans)

    ans = jax.lax.fori_loop(0, 32, bit, jnp.zeros((r,), jnp.uint32))
    need = k - jnp.sum(key > ans[:, None], axis=1)
    kept = jnp.cumsum(key == ans[:, None], axis=1, dtype=jnp.int32)
    tie = jnp.where(kept[:, -1] >= need,
                    jnp.argmax(kept >= need[:, None], axis=1), n - 1)
    back = jax.lax.bitcast_convert_type(ans ^ jnp.uint32(0x80000000),
                                        jnp.int32)
    thr = jax.lax.bitcast_convert_type(
        jnp.where(back < 0, back ^ jnp.int32(0x7FFFFFFF), back), jnp.float32)
    return thr, tie.astype(jnp.int32)


def sparse_mla_window(q, pool, table, pos, scores, thr, tie, sink, *,
                      scale: float, value_width: int, block_size: int):
    """Latent attention of a prefill window's query rows over their
    selections.

    q [T, H, width] absorbed queries, pool [N, 1, W] packed latent rows,
    table [W_pages] int32 (the sequence's pages), pos [T] int32, scores
    [T, W_pages * bs] (the indexer's), thr / tie [T] (`window_selection`),
    sink [H]. Returns [T, H, value_lanes(value_width)] bf16."""
    t, h, _ = q.shape
    words = pool.shape[-1]
    bs, cols = block_size, table.shape[0]
    tq = min(WINDOW_ROWS, t)
    if t % tq:
        raise ValueError(f"sparse_mla_window: {t} rows in blocks of {tq}")
    g = t // tq
    lanes = value_lanes(value_width)
    qp = jnp.pad(q.astype(jnp.bfloat16), ((0, 0), (0, 0),
                                          (0, 2 * words - q.shape[-1])))
    pos_g = pos.astype(jnp.int32).reshape(g, tq)
    last = jnp.minimum(jnp.max(pos_g, axis=1) // bs, cols - 1).astype(
        jnp.int32)
    tables = jnp.broadcast_to(table.astype(jnp.int32)[None], (g, cols))
    kernel = functools.partial(_window_kernel, heads=h, bs=bs, scale=scale,
                               words=words)
    row = pl.BlockSpec((1, tq, 1), lambda i, j, tb, l: (i, 0, 0))
    out = pl.pallas_call(
        kernel,
        name="sparse_mla_window",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(g, cols),
            in_specs=[
                pl.BlockSpec((1, tq * h, 2 * words),
                             lambda i, j, tb, l: (i, 0, 0)),
                pl.BlockSpec((1, tq, bs), lambda i, j, tb, l: (
                    i, 0, jnp.minimum(j, l[i]))),
                row, row, row,
                pl.BlockSpec((h, 1), lambda i, j, tb, l: (0, 0)),
                pl.BlockSpec((bs, 1, words), lambda i, j, tb, l: (
                    tb[i, jnp.minimum(j, l[i])], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, tq * h, lanes),
                                   lambda i, j, tb, l: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((tq * h, 1), jnp.float32),
                            pltpu.VMEM((tq * h, 1), jnp.float32),
                            *_acc_scratch(tq * h, words, lanes)],
        ),
        out_shape=jax.ShapeDtypeStruct((g, tq * h, lanes), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
    )(tables, last, qp.reshape(g, tq * h, 2 * words),
      scores.astype(jnp.float32).reshape(g, tq, cols * bs),
      thr.astype(jnp.float32).reshape(g, tq, 1),
      tie.astype(jnp.int32).reshape(g, tq, 1), pos_g.reshape(g, tq, 1),
      sink.astype(jnp.float32).reshape(h, 1), pool)
    return out.reshape(t, h, lanes)


# ---- commits -------------------------------------------------------------

def _row_commit_kernel(flat_ref, rows_ref, pool_in, pool_hbm, sem):
    del pool_in
    n = rows_ref.shape[0]

    def start(i, carry):
        pltpu.make_async_copy(rows_ref.at[i], pool_hbm.at[flat_ref[i]],
                              sem.at[0]).start()
        return carry

    def wait(i, carry):
        pltpu.make_async_copy(rows_ref.at[0], pool_hbm.at[0],
                              sem.at[0]).wait()
        return carry

    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n, wait, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _row_commit(pool, rows, flat, *, interpret: bool):
    b, words = rows.shape[0], pool.shape[-1]
    return pl.pallas_call(
        _row_commit_kernel,
        name="latent_commit",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec((b, 1, words), lambda i, f: (0, 0, 0)),
                      pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.HBM),
            scratch_shapes=[pltpu.SemaphoreType.DMA((1,))],
        ),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(flat.astype(jnp.int32), rows.reshape(b, 1, words), pool)


def latent_commit(pool, rows, flat):
    """pool [N, 1, W] int32 with row `flat[i]` set to `rows[i]` ([B, W]
    packed words), in place where the caller donates the pool: a row is a
    whole tile of this layout, so each is one DMA. Rows that name one pool
    row (idle slots on the scratch page) overwrite each other."""
    return _row_commit(pool, rows, flat, interpret=_interpret())


def window_commit(pool, rows, pages, block_size: int):
    """A window's packed rows [n_pages * bs, W] written over whole pages
    `pages` [n_pages] of a latent pool [N, 1, W]."""
    for i in range(pages.shape[0]):
        pool = jax.lax.dynamic_update_slice(
            pool, rows[i * block_size:(i + 1) * block_size, None, :],
            (pages[i] * block_size, 0, 0))
    return pool
