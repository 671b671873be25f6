"""`kernels/sparse_attention.py` in interpret mode against its jnp forms: the
index scores with the heads reduced inside, the exact selection (`top_k` and
the window's threshold search choose the same set, ties to the lower
position), the decode kernel over the selection by page (each page copied
whole, dense or sparse; short selections, partial pages; rows it copies
but does not select, and a sequence before it, reach nothing), the window's
masked sweep against the same attention over the gathered selection, the
packed rows and the in-place row commit."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.kernels import sparse_attention as sa

WIDTH, VALUE, HEADS, ROWS = 20, 16, 4, 64


@pytest.fixture(scope="module")
def latent():
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.normal(size=(ROWS, WIDTH)), jnp.bfloat16)
    return rows, sa.pack_rows(rows)[:, None, :]


def test_packed_rows_round_trip(latent):
    rows, packed = latent
    assert packed.shape == (ROWS, 1, 128) and packed.dtype == jnp.int32
    assert sa.latent_words(576) == 384 and sa.latent_words(20) == 128
    assert (sa.unpack_rows(packed[:, 0], WIDTH) == rows).all()


def test_index_scores_reduce_the_heads_inside():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(3, 2, 2, 8)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(3, 2, 2)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(10, 1, 8, 8)), jnp.bfloat16)
    tables = jnp.asarray(rng.integers(0, 10, size=(3, 4)), jnp.int32)
    pos = jnp.asarray([[3, 4], [17, 18], [30, 31]], jnp.int32)
    got = np.asarray(sa.dsa_index_scores(q, w, pool, tables, pos))
    want = np.asarray(sa.index_scores_reference(q, w, pool, tables, pos))
    assert got.shape == (3, 2, 32)
    np.testing.assert_array_equal(np.isfinite(got), np.isfinite(want))
    live = np.isfinite(want)
    np.testing.assert_allclose(got[live], want[live], rtol=1e-5, atol=1e-5)
    # past a row's position: -inf, the pages after the group's last unread
    assert np.isneginf(got[0, 0, 4:]).all() and np.isfinite(got[0, 0, :4]).all()


@pytest.mark.parametrize("n,k", [(40, 8), (6, 8), (64, 64), (300, 32)])
def test_the_threshold_search_selects_what_top_k_selects(n, k):
    rng = np.random.default_rng(n)
    sc = jnp.asarray(rng.integers(-3, 4, size=(50, n)).astype(np.float32))
    sc = jnp.where(jnp.asarray(rng.random((50, n)) < 0.2), -jnp.inf, sc)
    thr, tie = sa.window_selection(sc, k)
    _, idx = sa.select_topk(sc, k)
    want = np.zeros((50, n), bool)
    np.put_along_axis(want, np.asarray(idx), True, axis=1)
    s, col = np.asarray(sc), np.arange(n)[None]
    thr, tie = np.asarray(thr)[:, None], np.asarray(tie)[:, None]
    got = (s > thr) | ((s == thr) & (col <= tie))
    np.testing.assert_array_equal(got, want)


def _selection(rng, tables, bs, counts, k, order, ctx=None):
    """Positions [B, k] in score order with `counts[b][c]` selected rows on
    table column c of row b, none at or past `ctx[b]` (n_sel the sum; past
    it positions no plan may read), and the pool row ids
    `sparse_decode_reference` takes."""
    b, cols = tables.shape
    ctx = ctx or [cols * bs] * b
    idx = np.zeros((b, k), np.int64)
    n_sel = np.zeros((b,), np.int32)
    for r in range(b):
        pos = np.concatenate([
            c * bs + rng.choice(min(bs, ctx[r] - c * bs), n, replace=False)
            for c, n in enumerate(counts[r]) if n]).astype(np.int64)
        pos = rng.permutation(pos) if order == "shuffled" \
            else np.sort(pos)[::-1]
        n_sel[r] = len(pos)
        idx[r, :len(pos)] = pos
        idx[r, len(pos):] = rng.integers(0, ctx[r], k - len(pos))
    idx = jnp.asarray(idx, jnp.int32)
    rows = tables[jnp.arange(b)[:, None], idx // bs] * bs + idx % bs
    return idx, jnp.asarray(n_sel), rows


# (page rows, selected rows a table column for each of two sequences,
# selection width k, order, context lengths): every page that holds a
# selected row is copied whole, however few it holds
PLANS = {
    "dense pages": (256, [[40, 16, 256, 20], [16, 33, 0, 90]], 512,
                    "shuffled", None),
    "one or two rows a page": (
        64, [[1, 2, 0, 2, 1, 1], [2, 1, 1, 1, 0, 2]], 16, "shuffled", None),
    "sparse and dense large pages": (
        256, [[7, 30, 5], [1, 0, 60]], 128, "shuffled", None),
    "a page selected whole beside sparse ones": (
        256, [[256, 1, 0, 25, 2, 15], [0, 0, 40, 7, 1, 0]], 512, "shuffled",
        None),
    "n_sel below k": (8, [[3, 2, 0, 0], [5, 0, 0, 0]], 24, "shuffled", None),
    "partial last page": (64, [[64, 30, 20], [10, 36, 0]], 192, "shuffled",
                          [150, 100]),
    "positions in score order, highest first": (
        64, [[6, 1, 64, 2], [3, 3, 3, 3]], 96, "descending", None),
}


def _paged(case, rng, fill=None):
    """(q, pool, plan, sink, the pool row ids, n_sel) of a PLANS case;
    `fill(rows, chosen)` may overwrite the pool's rows before they are
    packed, `chosen` [B, pool rows] the rows each sequence selects."""
    bs, counts, k, order, ctx = PLANS[case]
    b, cols = len(counts), len(counts[0])
    npages = b * cols + 2
    rows = rng.normal(size=(npages * bs, WIDTH))
    tables = jnp.asarray(rng.permutation(npages)[:b * cols].reshape(b, cols),
                         jnp.int32)
    idx, n_sel, flat = _selection(rng, tables, bs, counts, k, order, ctx)
    if fill is not None:
        chosen = np.zeros((b, len(rows)), bool)
        for r in range(b):
            chosen[r, np.asarray(flat[r, :int(n_sel[r])])] = True
        fill(rows, chosen)
    pool = sa.pack_rows(jnp.asarray(rows, jnp.bfloat16))[:, None, :]
    plan = sa.decode_plan(idx, n_sel, tables, bs)
    q = jnp.asarray(rng.normal(size=(b, HEADS, WIDTH)), jnp.float32)
    sink = jnp.asarray(rng.normal(size=(HEADS,)), jnp.float32)
    return q, pool, plan, sink, flat, n_sel


@pytest.mark.parametrize("case", list(PLANS))
def test_the_paged_decode_is_attention_over_the_selection(case):
    bs, counts = PLANS[case][:2]
    q, pool, plan, sink, flat, n_sel = _paged(case,
                                              np.random.default_rng(len(case)))
    count = np.asarray(counts)
    assert list(np.asarray(plan.pages)) == list((count > 0).sum(1))
    got = sa.sparse_mla_decode(q, pool, plan, sink, scale=0.3,
                               value_width=VALUE, block_size=bs)
    assert got.shape == (len(counts), HEADS, 128)
    assert got.dtype == jnp.bfloat16
    want = sa.sparse_decode_reference(q, pool, flat, n_sel, sink, scale=0.3,
                                      value_width=VALUE)
    np.testing.assert_allclose(np.asarray(got[..., :VALUE], np.float32),
                               np.asarray(want), atol=1e-2)


def test_unselected_rows_of_a_copied_page_reach_nothing():
    """A page is copied whole, but only its selected rows are attended: the
    pool's other rows set to NaN change no output."""
    case = "a page selected whole beside sparse ones"
    bs = PLANS[case][0]
    def unselected_nan(rows, chosen):
        rows[~chosen.any(0)] = np.nan

    clean = _paged(case, np.random.default_rng(5))
    dirty = _paged(case, np.random.default_rng(5), fill=unselected_nan)
    for q, pool, plan, sink, _, _ in (clean, dirty):
        got = sa.sparse_mla_decode(q, pool, plan, sink, scale=0.3,
                                   value_width=VALUE, block_size=bs)
        assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_array_equal(
        np.asarray(sa.sparse_mla_decode(*clean[:4], scale=0.3,
                                        value_width=VALUE, block_size=bs)),
        np.asarray(got))


def test_each_sequence_of_the_decode_stands_alone():
    """A sequence's output is the same after a longer sequence as alone,
    even where the rows the first selects hold infinities: what it leaves
    past the next sequence's selection weighs nothing."""
    case = "partial last page"
    bs = PLANS[case][0]

    def first_inf(rows, chosen):
        rows[chosen[0]] = np.inf

    q, pool, plan, sink, _, _ = _paged(case, np.random.default_rng(9),
                                       fill=first_inf)
    both = sa.sparse_mla_decode(q, pool, plan, sink, scale=0.3,
                                value_width=VALUE, block_size=bs)
    alone = sa.sparse_mla_decode(
        q[1:], pool, jax.tree.map(lambda a: a[1:], plan), sink, scale=0.3,
        value_width=VALUE, block_size=bs)
    assert int(plan.n_sel[0]) > int(plan.n_sel[1])
    assert np.isfinite(np.asarray(alone, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(both[1:]), np.asarray(alone))


def test_decode_attends_the_selected_rows_with_a_sink(latent):
    _, packed = latent
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(2, HEADS, WIDTH)), jnp.float32)
    tables = jnp.asarray([[3, 1, 6, 0], [7, 2, 5, 4]], jnp.int32)
    idx = jnp.asarray(rng.permutation(32)[:16].reshape(2, 8), jnp.int32)
    n_sel = jnp.asarray([5, 8], jnp.int32)
    sink = jnp.asarray(rng.normal(size=(HEADS,)), jnp.float32)
    plan = sa.decode_plan(idx, n_sel, tables, 8)
    got = sa.sparse_mla_decode(q, packed, plan, sink, scale=0.3,
                               value_width=VALUE, block_size=8)
    assert got.shape == (2, HEADS, 128) and got.dtype == jnp.bfloat16
    rows = tables[jnp.arange(2)[:, None], idx // 8] * 8 + idx % 8
    want = sa.sparse_decode_reference(q, packed, rows, n_sel, sink,
                                      scale=0.3, value_width=VALUE)
    np.testing.assert_allclose(np.asarray(got[..., :VALUE], np.float32),
                               np.asarray(want), atol=1e-2)
    # positions past n_sel are read by nobody: another there changes nothing
    moved = sa.decode_plan(idx.at[0, 5:].set(0), n_sel, tables, 8)
    again = sa.sparse_mla_decode(q, packed, moved, sink, scale=0.3,
                                 value_width=VALUE, block_size=8)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


def test_the_window_sweep_is_attention_over_each_rows_selection(latent):
    _, packed = latent
    rng = np.random.default_rng(3)
    t, bs, cols = 32, 8, 8
    table = jnp.asarray(rng.permutation(ROWS // bs)[:cols], jnp.int32)
    pos = jnp.arange(t, dtype=jnp.int32) + 20
    sc = jnp.asarray(rng.normal(size=(t, cols * bs)), jnp.float32)
    sc = sc.at[:, 5].set(sc[:, 9])                    # ties at a threshold
    sc = jnp.where(jnp.arange(cols * bs)[None] <= pos[:, None], sc, -jnp.inf)
    thr, tie = sa.window_selection(sc, 8)
    q = jnp.asarray(rng.normal(size=(t, HEADS, WIDTH)), jnp.float32)
    sink = jnp.asarray(rng.normal(size=(HEADS,)), jnp.float32)
    got = sa.sparse_mla_window(q, packed, table, pos, sc, thr, tie, sink,
                               scale=0.3, value_width=VALUE, block_size=bs)
    _, idx = sa.select_topk(sc, 8)
    flat = table[idx // bs] * bs + idx % bs
    want = sa.sparse_decode_reference(q, packed, flat,
                                      jnp.full((t,), 8, jnp.int32), sink,
                                      scale=0.3, value_width=VALUE)
    np.testing.assert_allclose(np.asarray(got[..., :VALUE], np.float32),
                               np.asarray(want), atol=1e-2)


def test_the_row_commit_writes_its_rows_alone(latent):
    _, packed = latent
    rng = np.random.default_rng(4)
    new = sa.pack_rows(jnp.asarray(rng.normal(size=(3, WIDTH)), jnp.bfloat16))
    at = jnp.asarray([5, 9, 60], jnp.int32)
    out = sa.latent_commit(packed, new, at)
    np.testing.assert_array_equal(np.asarray(out[at, 0]), np.asarray(new))
    keep = np.setdiff1d(np.arange(ROWS), np.asarray(at))
    np.testing.assert_array_equal(np.asarray(out[keep]),
                                  np.asarray(packed[keep]))
    pages = jnp.asarray([2, 5], jnp.int32)
    rows = jnp.zeros((16, 128), jnp.int32) + 7
    out = sa.window_commit(packed, rows, pages, 8)
    assert (np.asarray(out[16:24]) == 7).all() and \
        (np.asarray(out[40:48]) == 7).all()
    np.testing.assert_array_equal(np.asarray(out[:16]),
                                  np.asarray(packed[:16]))
