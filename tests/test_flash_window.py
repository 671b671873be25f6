"""A window in the trainer's flash kernels (ISSUE 35): `flash_attention(...,
causal=True, window=W)` against the masked-softmax form in f32 — forward and
all three gradients, equal and grouped heads, windows smaller than, equal to
and larger than a block and than the sequence —, the blocks the kernels
visit, their labels, and `window=None` left as it was.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

F32 = jnp.float32
SCALE = 0.088


def _fa():
    return importlib.import_module("paddle_tpu.kernels.flash_attention")


def _operands(b, s, hq, hk, d=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, s, hq, d), F32),
            jax.random.normal(ks[1], (b, s, hk, d), F32),
            jax.random.normal(ks[2], (b, s, hk, d), F32),
            jax.random.normal(ks[3], (b, s, hq, d), F32))


def _masked_softmax(q, k, v, window):
    """Row t sees the keys s with 0 <= t - s < window; plain f32."""
    s = q.shape[1]
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                    precision=jax.lax.Precision.HIGHEST) * SCALE
    t, u = jnp.arange(s)[:, None], jnp.arange(s)[None]
    seen = (t >= u) & ((t - u < window) if window is not None else True)
    pr = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", pr, v,
                      precision=jax.lax.Precision.HIGHEST)


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _both(fn, q, k, v, do):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + vjp(do)


# the CPU's forward blocks are 512 rows, and the backward's under a window
@pytest.mark.parametrize("group", [1, 8], ids=["equal_heads", "8_q_a_kv"])
@pytest.mark.parametrize("seq, window", [
    (1024, 100), (1024, 512), (1024, 513), (1024, 700), (2048, 1024),
    (2048, 1500), (1024, 1024), (1024, 5000), (384, 100)],
    ids=lambda x: str(x))
def test_window_matches_masked_softmax(seq, window, group):
    fa = _fa()
    q, k, v, do = _operands(1, seq, group, 1)
    got = _both(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, scale=SCALE, window=window), q, k, v, do)
    want = _both(lambda q, k, v: _masked_softmax(q, k, v, window),
                 q, k, v, do)
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        assert g.shape == w.shape
        assert _rel(g, w) < 2e-5, name


@pytest.mark.parametrize("window", [1, 64, 128, 129, 300, 512, 640])
def test_window_backward_over_many_blocks(window):
    """Eight blocks of 128 a side: spans of one to six q blocks a k block,
    the shorter spans of the last k blocks, dq's block leaving at its
    diagonal step."""
    fa = _fa()
    s, bh, bkv = 1024, 4, 2
    q, k, v, do = (jnp.swapaxes(x, 1, 2).reshape(-1, s, 128)
                   for x in _operands(1, s, bh, bkv, seed=3))
    k, v = k[:bkv], v[:bkv]
    out, lse = fa._fwd_pallas(q, k, v, True, SCALE, 128, 128,
                              interpret=True, window=window)
    want_out, vjp = jax.vjp(
        lambda q, k, v: fa._fwd_ref(q, k, v, True, SCALE, window), q, k, v)
    assert _rel(out, want_out) < 2e-5
    dq, dk, dv = fa._bwd_pallas(q, k, v, out, lse, do, True, SCALE, True,
                                block_q=128, block_k=128, window=window)
    dk, dv = (x.reshape(bkv, bh // bkv, s, 128).sum(1) for x in (dk, dv))
    for name, g, w in zip(("dq", "dk", "dv"), (dq, dk, dv), vjp(do)):
        if window == 1 and name != "dv":      # one key a row: p == 1, ds == 0
            assert float(jnp.abs(g).max()) < 1e-4
            continue
        assert _rel(g, w) < 2e-5, (name, window)


def _kernels(fn, *args):
    """[(name, grid)] of the Pallas calls `fn` traces to."""
    found = []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                found.append((e.params["name"],
                              tuple(e.params["grid_mapping"].grid)))
                continue
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def test_window_none_is_todays_call():
    """No window, the window left out and a window that masks nothing give
    the same values bit for bit, from the same lowered program."""
    fa = _fa()
    q, k, v, do = _operands(2, 512, 4, 2)

    def text(**kw):
        return jax.jit(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, **kw)).lower(q, k, v).as_text()

    assert text() == text(window=None) == text(window=512)
    assert text() != text(window=100)
    a = _both(lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
              q, k, v, do)
    b = _both(lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                                 window=None), q, k, v, do)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_window_kernels_carry_their_own_labels():
    fa = _fa()
    q, k, v, do = _operands(1, 1024, 2, 1)

    def names(**kw):
        return [n for n, _ in _kernels(jax.grad(
            lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                               **kw).sum(), (0, 1, 2)),
            q, k, v)]

    assert names(window=256) == ["flash_attention_window_fwd",
                                 "flash_attention_window_bwd"]
    assert names() == names(window=1024) == ["flash_attention_fwd",
                                             "flash_attention_bwd"]


def test_the_grid_visits_the_band_alone():
    """The forward's last grid axis and the backward's are as long as the
    widest span of blocks the band crosses, not the sequence."""
    fa = _fa()
    s, w = 2048, 256
    q, k, v, do = (x[0].swapaxes(0, 1) for x in _operands(1, s, 2, 2))

    def grids(fn, *args):
        return [g for _, g in _kernels(fn, *args)]

    assert grids(lambda q, k, v: fa._fwd_pallas(
        q, k, v, True, SCALE, 128, 128, True, window=w), q, k, v) \
        == [(2, 16, 3)]
    assert grids(lambda q, k, v: fa._fwd_pallas(
        q, k, v, True, SCALE, 128, 128, True), q, k, v) == [(2, 16, 16)]
    lse = jnp.zeros((2, s), F32)
    assert grids(lambda q, k, v, do: fa._bwd_pallas(
        q, k, v, q, lse, do, True, SCALE, True, block_q=128, block_k=128,
        window=w), q, k, v, do) == [(2, 16, 3)]


def test_window_pairs_count_blocks_and_mask():
    fa = _fa()
    s, w = 8192, 2048
    fwd, bwd, mask = fa.window_pairs(s, 32, 4, 128, w, True)
    assert mask == w * s - w * (w - 1) // 2 == 14_681_088
    # on the chip, 1024-row blocks forward: 3 k blocks a q block, fewer for
    # the first two
    assert fwd == (1 + 2 + 6 * 3) * 1024 * 1024
    # 512-row blocks backward: 5 q blocks a k block, fewer for the last four
    assert bwd == (12 * 5 + 4 + 3 + 2 + 1) * 512 * 512
    assert 1.45 < fwd / mask < 1.55 and 1.2 < bwd / mask < 1.3
    # in interpret mode the forward's blocks are 512 rows too
    assert fa.window_pairs(s, 32, 4, 128, w, False) == (bwd, bwd, mask)
    causal = s * (s + 1) // 2
    assert 2.2 < causal / mask < 2.4      # what a sweep that only masks does
    # a window as long as the sequence is the causal triangle
    assert fa.window_pairs(1024, 2, 2, 128, 4096, False)[2] \
        == 1024 * 1025 // 2


@pytest.mark.parametrize("kw, says", [
    (dict(causal=False, window=8), "causal=True"),
    (dict(causal=True, window=0), "at least the row's own key")])
def test_what_a_window_refuses(kw, says):
    fa = _fa()
    q, k, v, _ = _operands(1, 128, 1, 1)
    with pytest.raises(ValueError, match=says):
        fa.flash_attention(q, k, v, **kw)


def test_functional_takes_a_window():
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import unwrap
    from paddle_tpu.nn import functional as F

    q, k, v, _ = _operands(1, 256, 2, 1)
    out, _ = F.flash_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                               paddle.to_tensor(v), causal=True, window=64)
    want = _fa().flash_attention(q, k, v, causal=True, window=64)
    assert np.array_equal(np.asarray(unwrap(out)), np.asarray(want))
    # the default scale is 1/sqrt(128), a hair off this file's
    assert _rel(unwrap(out), _masked_softmax(q, k, v, 64)) < 1e-2
    assert _rel(unwrap(out), _masked_softmax(q, k, v, None)) > 0.1
    with pytest.raises(ValueError, match="causal=True"):
        F.flash_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                          paddle.to_tensor(v), window=64)
