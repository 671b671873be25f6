"""`kernels/sparse_attention.py` in interpret mode against its jnp forms: the
index scores with the heads reduced inside, the exact selection (`top_k` and
the window's threshold search choose the same set, ties to the lower
position), the decode kernel over rows fetched one at a time, the window's
masked sweep against the same attention over the gathered selection, the
packed rows and the in-place row commit."""
import numpy as np
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


def test_decode_attends_the_selected_rows_with_a_sink(latent):
    _, packed = latent
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(2, HEADS, WIDTH)), jnp.float32)
    rows = jnp.asarray(rng.integers(0, ROWS, size=(2, 8)), jnp.int32)
    n_sel = jnp.asarray([5, 8], jnp.int32)
    sink = jnp.asarray(rng.normal(size=(HEADS,)), jnp.float32)
    got = sa.sparse_mla_decode(q, packed, rows, n_sel, sink, scale=0.3,
                               value_width=VALUE)
    assert got.shape == (2, HEADS, 128) and got.dtype == jnp.bfloat16
    want = sa.sparse_decode_reference(q, packed, rows, n_sel, sink,
                                      scale=0.3, value_width=VALUE)
    np.testing.assert_allclose(np.asarray(got[..., :VALUE], np.float32),
                               np.asarray(want), atol=1e-2)
    # rows past n_sel are read by nobody: another id there changes nothing
    moved = rows.at[0, 5:].set(0)
    again = sa.sparse_mla_decode(q, packed, moved, n_sel, sink, scale=0.3,
                                 value_width=VALUE)
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
