"""Sliding-window attention in the two served kernels, against a jnp oracle
that knows no pages: the whole K/V history of every sequence, the mask written
out. The pools are per-sequence RINGS — position t lives at ring page
(t // page) % R, older positions overwritten — read through a logical table
(column j -> ring page j % R), so a kernel that fetched a column behind the
window would read a later position's keys and miss the oracle.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.decode_attention import paged_decode_attention
from paddle_tpu.kernels.ragged_attention import (
    ragged_paged_attention, ragged_paged_attention_reference)

PAGE = 8


def _history(rng, b, n, hkv, d):
    k = rng.standard_normal((b, n, hkv, d)).astype(np.float32)
    v = rng.standard_normal((b, n, hkv, d)).astype(np.float32)
    return k, v


def _rings(k, v, lens, ring_pages, width):
    """Pools [B * R + 1, Hkv, PAGE, D] holding, for each sequence, positions
    [0, lens[b]) written in order into its ring, and the logical tables
    [B, width]. Page 0 is a sink of NaN no table names inside a window."""
    b, _, hkv, d = k.shape
    kc = np.full((b * ring_pages + 1, hkv, PAGE, d), np.nan, np.float32)
    vc = np.full_like(kc, np.nan)
    tables = np.zeros((b, width), np.int32)
    for i in range(b):
        ring = 1 + i * ring_pages + np.arange(ring_pages)
        tables[i] = ring[np.arange(width) % ring_pages]
        for t in range(int(lens[i])):
            pg = ring[(t // PAGE) % ring_pages]
            if t % PAGE == 0:      # a page given to a new position starts clean
                kc[pg], vc[pg] = 0.0, 0.0
            kc[pg, :, t % PAGE] = k[i, t]
            vc[pg, :, t % PAGE] = v[i, t]
    return jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(tables)


def _oracle(q, k, v, qpos, window):
    """q [B, T, Hq, D] at absolute positions qpos [B, T] over the history
    k, v [B, N, Hkv, D]: causal, and under `window` only (t - window, t]."""
    group = q.shape[2] // k.shape[2]
    kk, vv = (np.repeat(x, group, axis=2) for x in (k, v))
    s = np.einsum("bthd,bnhd->bhtn", q, kk) / np.sqrt(q.shape[-1])
    kpos = np.arange(k.shape[1])[None, None, None, :]
    qp = qpos[:, None, :, None]
    seen = kpos <= qp
    if window is not None:
        seen &= qp - kpos < window
    s = np.where(seen, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhtn,bnhd->bthd", p, vv)


# (window, lens of the batch BEFORE the query's own token): below the window,
# at it, one past it, far past it with the ring wrapped several times, ragged
DECODE = {
    "below": (16, [3, 9, 14]),
    "at": (16, [15, 16, 17]),
    "far": (16, [100, 57, 31]),
    "ragged": (24, [0, 5, 23, 24, 99, 200]),
    "window_of_one_page": (8, [7, 8, 64]),
    "window_one": (1, [0, 9, 40]),
}


@pytest.mark.parametrize("d", [128, 32], ids=["lane_heads", "narrow_heads"])
@pytest.mark.parametrize("case", sorted(DECODE))
def test_windowed_paged_decode_matches_the_history(case, d):
    window, lens = DECODE[case]
    lens = np.asarray(lens, np.int32)
    b, hq, hkv = len(lens), 4, 2
    rng = np.random.default_rng(len(case) + d)
    n = int(lens.max()) + 1
    k, v = _history(rng, b, n, hkv, d)
    q = rng.standard_normal((b, 1, hq, d)).astype(np.float32)
    ring_pages = -(-window // PAGE) + 1
    width = -(-n // PAGE)
    # the query's own token is already written at position lens[b]
    kc, vc, tables = _rings(k, v, lens + 1, ring_pages, width)
    got = paged_decode_attention(jnp.asarray(q[:, 0]), kc, vc, tables,
                                 jnp.asarray(lens), window=window)
    want = _oracle(q, k, v, lens[:, None], window)[:, 0]
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_a_window_wider_than_the_context_is_full_attention():
    lens = np.asarray([5, 30, 12], np.int32)
    rng = np.random.default_rng(3)
    k, v = _history(rng, 3, 31, 2, 128)
    q = jnp.asarray(rng.standard_normal((3, 4, 128)).astype(np.float32))
    kc, vc, tables = _rings(k, v, lens + 1, 4, 4)
    full = paged_decode_attention(q, kc, vc, tables, jnp.asarray(lens))
    wide = paged_decode_attention(q, kc, vc, tables, jnp.asarray(lens),
                                  window=4096)
    np.testing.assert_allclose(np.asarray(wide), np.asarray(full),
                               rtol=1e-6, atol=1e-6)


def test_the_windowed_calls_carry_their_own_label():
    """The device trace tells a window layer's kernels from a full layer's."""
    def names(fn, *a):
        found = []

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    found.append(eqn.params["name"])
                for val in eqn.params.values():
                    for sub in (val if isinstance(val, (list, tuple))
                                else [val]):
                        inner = getattr(sub, "jaxpr", sub)
                        if hasattr(inner, "eqns"):
                            walk(inner)
        walk(jax.make_jaxpr(fn)(*a).jaxpr)
        return found

    S = jax.ShapeDtypeStruct
    pool = S((9, 2, PAGE, 128), jnp.float32)
    tbl, lens = S((2, 4), jnp.int32), S((2,), jnp.int32)
    q1, qw = S((2, 4, 128), jnp.float32), S((2, 16, 4, 128), jnp.float32)
    kw = S((2, 16, 2, 128), jnp.float32)
    assert names(lambda *a: paged_decode_attention(*a, window=8),
                 q1, pool, pool, tbl, lens) == ["decode_attention_window"]
    assert names(paged_decode_attention, q1, pool, pool, tbl, lens) \
        == ["decode_attention"]
    assert names(lambda *a: ragged_paged_attention(*a, window=8),
                 qw, kw, kw, pool, pool, tbl, lens, lens) \
        == ["ragged_attention_window"]
    assert names(ragged_paged_attention, qw, kw, kw, pool, pool, tbl, lens,
                 lens) == ["ragged_attention"]


# (window, tokens of the new window, (cached, new) of each row): a cold
# prompt, a window passed inside the prefill, a wrapped ring, pad rows
RAGGED = {
    "cold": (16, 16, [(0, 16), (0, 9)]),
    "passes_inside": (16, 16, [(8, 16), (16, 16), (24, 5)]),
    "wrapped": (16, 16, [(96, 16), (200, 11), (48, 1)]),
    "window_narrower_than_the_chunk": (8, 32, [(0, 32), (40, 32), (16, 20)]),
    "decode_rows": (24, 8, [(5, 1), (77, 1), (0, 1)]),
}


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "jnp_form"])
@pytest.mark.parametrize("case", sorted(RAGGED))
def test_windowed_ragged_attention_matches_the_history(case, kernel):
    window, tn, rows = RAGGED[case]
    cached = np.asarray([c for c, _ in rows], np.int32)
    new = np.asarray([n for _, n in rows], np.int32)
    b, hq, hkv, d = len(rows), 4, 2, 128
    rng = np.random.default_rng(len(case))
    n = int((cached + tn).max())
    k, v = _history(rng, b, n, hkv, d)
    q = rng.standard_normal((b, tn, hq, d)).astype(np.float32)
    ring_pages = -(-(window + tn) // PAGE) + 1
    width = -(-n // PAGE)
    kc, vc, tables = _rings(k, v, cached, ring_pages, width)
    # NaN in the pools' dead pages would reach the jnp form through its
    # gather (0 x NaN); the engine's pools are zeros there
    kc, vc = jnp.nan_to_num(kc), jnp.nan_to_num(vc)
    rows_idx = cached[:, None] + np.arange(tn)[None]
    k_new = np.take_along_axis(k, rows_idx[:, :, None, None], axis=1)
    v_new = np.take_along_axis(v, rows_idx[:, :, None, None], axis=1)
    fn = ragged_paged_attention if kernel \
        else ragged_paged_attention_reference
    got = np.asarray(fn(jnp.asarray(q), jnp.asarray(k_new),
                        jnp.asarray(v_new), kc, vc, tables,
                        jnp.asarray(cached), jnp.asarray(new),
                        window=window))
    want = _oracle(q, k, v, rows_idx, window)
    live = np.arange(tn)[None] < new[:, None]
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)
    assert not got[~live].any()          # pad rows are exact zeros


def test_the_jnp_form_reads_a_ring_only_inside_the_window():
    """The oracle form gathers the whole logical table: behind the window the
    columns name pages of later positions, and the mask must drop them."""
    window, tn = 16, 8
    cached, new = np.asarray([120], np.int32), np.asarray([8], np.int32)
    rng = np.random.default_rng(0)
    k, v = _history(rng, 1, 128, 2, 128)
    q = rng.standard_normal((1, tn, 4, 128)).astype(np.float32)
    kc, vc, tables = _rings(k, v, cached, 4, 16)
    idx = 120 + np.arange(tn)
    got = ragged_paged_attention_reference(
        jnp.asarray(q), jnp.asarray(k[:, idx]), jnp.asarray(v[:, idx]),
        jnp.nan_to_num(kc), jnp.nan_to_num(vc), tables, jnp.asarray(cached),
        jnp.asarray(new), window=window)
    want = _oracle(q, k, v, idx[None], window)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
