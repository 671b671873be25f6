"""The dropless expert layer's four row movements as kernels
(`kernels/moe_rows.py`, interpret mode here; `tests/test_chip_compile.py`
compiles them for the chip) against the jnp forms that stay in
`parallel/moe.py` as their oracle: each movement alone, the layer's values and
gradients with a share of the experts held and with all of them (the gates
train: `moe_rows_dgates`), under even routing, one expert taking every row
(the buffer full), an empty group, and rows that are no tile multiple, at
the 128-row tile of a trainer's shapes and at the thinner tiles the layer
takes where a group holds few rows (`grouped_matmul.row_tile`); the decode
lane's 32 tokens on 64 experts against a dense oracle and against the same
call on 128-row tiles; NaN planted where nothing may read; the train step's
text holding each payload once.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import grouped_matmul as gm
from paddle_tpu.kernels import moe_rows
from paddle_tpu.parallel import moe

F32, BF = jnp.float32, jnp.bfloat16
D, F = 128, 128

# routing -> (experts the router sees, experts held, how idx is drawn,
# tokens, choices a token); the last three are thinner than a trainer's
ROUTINGS = {
    "even": (8, (1, 5, 6), "uniform", 256, 2),
    "one_expert_takes_every_row": (8, (0, 3, 4), "first_held", 256, 2),
    "an_empty_group": (8, (2, 7, 4), "never_7", 256, 2),
    "rows_no_tile_multiple": (4, (0, 1, 2, 3), "uniform", 256, 2),  # all held
    "all_held_one_crowded": (4, (0, 1, 2, 3), "first_held", 256, 2),
    "tile_16_one_crowded": (32, tuple(range(32)), "first_held", 128, 2),
    "tile_32_an_empty_group": (24, (2, 7, 4, 9, 1, 0, 12, 15, 3, 5, 6, 8,
                                    10, 11, 13, 14), "never_7", 128, 2),
    "tile_64": (16, tuple(range(8)), "uniform", 128, 2),
}
TILES = {"tile_16_one_crowded": 16, "tile_32_an_empty_group": 32,
         "tile_64": 64}


def _tokens(case):
    return ROUTINGS[case][3:]


def _tile(case):
    experts, held, _, t, k = ROUTINGS[case]
    tile = gm.row_tile(t, k, len(held))
    assert tile == TILES.get(case, gm.ROW_TILE)
    return tile


def _routing(case, seed=0):
    experts, held, how, t, k = ROUTINGS[case]
    rng = np.random.default_rng(seed)
    score = rng.random((t, experts))
    if how == "first_held":
        score[:, held[0]] += 2.0
    if how == "never_7":
        score[:, 7] -= 2.0
    idx = np.argsort(-score, axis=1)[:, :k].astype(np.int32)
    gates = rng.uniform(0.1, 1.0, (t, k)).astype(np.float32)
    return experts, held, jnp.asarray(idx), jnp.asarray(gates)


def _plan(case, idx, held, experts):
    """The routing's integers as `dropless_experts` gets them, and the row
    maps of the jnp forms."""
    (t, k), g, tile = _tokens(case), len(held), _tile(case)
    rows = gm.buffer_rows(t * min(k, g), g, tile)
    layout, dest, chunks, _ = moe._route(idx, held, experts, rows, tile, True)
    row_assign = jnp.full((rows,), t * k, jnp.int32).at[
        dest.reshape(-1)].set(jnp.arange(t * k, dtype=jnp.int32), mode="drop")
    row_token = jnp.where(row_assign < t * k, row_assign // k, t)
    live = np.zeros(rows, bool)                 # rows of the tiles in use
    live[:int(layout.n_tiles) * tile] = True
    return rows, layout, dest, chunks, row_token, row_assign, live


def _rand(seed, shape, dtype):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       dtype)


@pytest.mark.parametrize("dtype", [F32, BF], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(ROUTINGS))
def test_each_movement_against_its_jnp_form(case, dtype):
    experts, held, idx, gates = _routing(case)
    rows, layout, dest, chunks, row_token, row_assign, live = _plan(
        case, idx, held, experts)
    g, (T, _) = len(held), _tokens(case)
    row_gate = jnp.take(gates.reshape(-1), row_assign, mode="fill",
                        fill_value=0)
    x, dy = _rand(1, (T, D), dtype), _rand(2, (T, D), dtype)
    # a buffer as the grouped matmul leaves it: rows of the tiles in use
    # hold values, padding rows among them too
    ys, dxs = _rand(3, (rows, D), dtype), _rand(4, (rows, D), dtype)
    tol = dict(rtol=0, atol=0) if dtype == F32 else dict(rtol=0, atol=2e-2)

    def same(got, want, where=None, **kw):
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        if where is not None:
            got, want = got[where], want[where]
        np.testing.assert_allclose(got, want, **(kw or tol))

    # tokens -> buffer: every row of a tile in use is written, padding as 0
    xs = moe_rows.scatter_rows(x, dest.T, None, chunks, rows, g,
                               name="moe_rows_in")
    same(xs, moe._rows_in(x, row_token, dest), live)
    dys = moe_rows.scatter_rows(dy, dest.T, gates.T, chunks, rows, g,
                                name="moe_rows_out_bwd")
    want_dys, want_dg = moe._rows_out_bwd((ys, row_token, dest, row_gate),
                                          dy)[:2]
    same(dys, want_dys, live)
    # buffer -> tokens
    y = moe_rows.gather_rows((ys,), dest.T, gates.T, chunks,
                             name="moe_rows_out")
    same(y, moe._rows_out(ys, gates, row_token, dest, row_gate),
         rtol=1e-6 if dtype == F32 else 0, atol=1e-6 if dtype == F32 else 2e-2)
    dx = moe_rows.gather_rows((dxs, ys), dest.T, None, chunks,
                              name="moe_rows_in_bwd")
    same(dx, moe._rows_in_bwd(dest, dxs.astype(F32) + ys.astype(F32))[0],
         rtol=1e-6 if dtype == F32 else 0, atol=1e-6 if dtype == F32 else 4e-2)
    dg = moe_rows.gather_dots(ys, dy, dest.T, chunks)
    same(dg.T, want_dg, rtol=1e-5, atol=1e-4 if dtype == F32 else 1e-2)


@pytest.mark.parametrize("case", sorted(ROUTINGS))
def test_chunk_lists_cover_each_tile_in_use_once(case):
    """What the lists walk: every chunk of a row tile in use is written once
    (`n_write` counts a chunk two token tiles share twice: it is kept, then
    carried), no chunk of another tile is touched, and the chunks read are
    the ones that hold a token tile's rows."""
    experts, held, idx, _ = _routing(case)
    rows, layout, dest, chunks, *_ = _plan(case, idx, held, experts)
    per_tile = _tile(case) // moe_rows.CHUNK
    chunk, flags = np.asarray(chunks.chunk), np.asarray(chunks.flags)
    n_read, n_write = np.asarray(chunks.n_read), np.asarray(chunks.n_write)
    written = []
    for i in range(chunk.shape[0]):
        assert (chunk[i, n_write[i]:] == -1).all()
        assert (chunk[i, :n_write[i]] >= 0).all()
        keep = flags[i, :n_write[i]] & 2 != 0
        written += list(chunk[i, :n_write[i]][~keep])
        mine = np.asarray(dest)[i * moe_rows.TOKEN_TILE:
                                (i + 1) * moe_rows.TOKEN_TILE].reshape(-1)
        assert set(chunk[i, :n_read[i]]) \
            == set(mine[mine < rows] // moe_rows.CHUNK)
    assert sorted(written) == list(range(int(layout.n_tiles) * per_tile))
    assert chunk.shape[1] == moe_rows._max_slots(
        _tokens(case)[1], len(held), _tile(case))


def _layer(case, dtype, seed=0):
    """(value_and_grad of the layer's loss, its arguments). `dropless_experts`
    looks the grouped matmul up when it is traced, so a test may wrap it."""
    experts, held, idx, gates = _routing(case, seed)
    g, (T, _) = len(held), _tokens(case)
    x, dy = _rand(5, (T, D), dtype), _rand(6, (T, D), dtype)
    ws = [_rand(7 + i, s, dtype) * 0.1
          for i, s in enumerate([(g, D, F), (g, D, F), (g, F, D)])]

    def loss(x, gates, wg, wu, wd):
        y, counted = moe.dropless_experts(x, idx, gates, wg, wu, wd, held,
                                          experts)
        return jnp.sum(y.astype(F32) * dy.astype(F32)), (y, counted)

    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True), \
        (x, gates, *ws)


@pytest.mark.parametrize("dtype", [F32, BF], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(ROUTINGS))
def test_layer_values_and_gradients_against_the_jnp_forms(case, dtype,
                                                          monkeypatch):
    fn, args = _layer(case, dtype)
    (_, (y, counted)), grads = fn(*args)
    monkeypatch.setattr(moe_rows, "rows_ok", lambda *a: False)
    (_, (y0, counted0)), grads0 = fn(*args)
    held_all = len(ROUTINGS[case][1]) == ROUTINGS[case][0]
    T, K = _tokens(case)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == F32 \
        else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y0, np.float32), **tol)
    for name, got, want in zip(("dx", "dgates", "dwg", "dwu", "dwd"), grads,
                               grads0):
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        assert np.isfinite(got).all(), name
        np.testing.assert_allclose(got, want, err_msg=name, **tol)
    # the gates train only where every expert is held
    assert bool(np.any(np.asarray(grads[1]))) == held_all
    for k in ("moe.rows_held", "moe.rows_multiplied", "moe.rows_dropped",
              "moe.load_max", "moe.load_mean"):
        assert float(counted[k]) == float(counted0[k]), k
    assert int(counted["moe.rows_dropped"]) == 0
    # the kernels move the tiles in use and the chunks that hold a token
    # tile's rows; the jnp forms walk the buffer and every assignment
    rows = gm.buffer_rows(T * min(K, len(ROUTINGS[case][1])),
                          len(ROUTINGS[case][1]), _tile(case))
    assert int(counted0["moe.rows_moved"]) == rows + T * K
    held_rows, tiles = int(counted["moe.rows_held"]), \
        int(counted["moe.rows_multiplied"])
    assert tiles + held_rows <= int(counted["moe.rows_moved"]) \
        <= tiles + held_rows + 2 * moe_rows.CHUNK * len(ROUTINGS[case][1]) \
        * (T // moe_rows.TOKEN_TILE)


@pytest.mark.parametrize("case", ["even", "rows_no_tile_multiple",
                                  "tile_32_an_empty_group"])
def test_nan_in_dead_tiles_and_padding_rows_reaches_nothing(case,
                                                            monkeypatch):
    """Every buffer between the movements gets NaN where no assignment lives
    — the row tiles no group uses and the padding rows of the tiles in use —
    on the way forward and on the way back: y, dx and the three weight
    gradients stay finite and equal what they are without it (the test above
    holds those to the jnp forms)."""
    experts, held, idx, _ = _routing(case)
    rows, layout, *_ = _plan(case, idx, held, experts)
    r = jnp.arange(rows)
    tile = r // _tile(case)
    group = layout.tile_group[tile]
    dead = ((r - layout.starts[group] >= layout.sizes[group])
            | (tile >= layout.n_tiles))[:, None]

    @jax.custom_vjp
    def poison(buf):
        return jnp.where(dead, jnp.nan, buf)

    poison.defvjp(lambda buf: (poison(buf), None),
                  lambda _, d: (jnp.where(dead, jnp.nan, d),))

    real = gm.grouped_matmul
    fn, args = _layer(case, BF)
    (_, (want_y, _)), want = fn(*args)
    monkeypatch.setattr(
        gm, "grouped_matmul",
        lambda lhs, rhs, lay: poison(real(poison(lhs), rhs, lay)))
    (_, (y, _)), grads = fn(*args)
    assert bool(jnp.isnan(poison(jnp.zeros((rows, 1)))).any())
    for name, got, ref in zip(("y", "dx", "dwg", "dwu", "dwd"),
                              (y, grads[0]) + grads[2:],
                              (want_y, want[0]) + want[2:]):
        got, ref = (np.asarray(a, np.float32) for a in (got, ref))
        assert np.isfinite(got).all(), name
        np.testing.assert_array_equal(got, ref, err_msg=name)


def test_shapes_off_the_tiles_take_the_jnp_forms():
    assert moe_rows.rows_ok(8192, 2048, 1536, 33792, 128)
    assert moe_rows.rows_ok(128, 2304, 896, 3072, 32)
    assert not moe_rows.rows_ok(64, 2048, 1536, 1024, 128)   # no token tile
    assert not moe_rows.rows_ok(32, 2304, 896, 1280, 16)     # the decode lane
    assert not moe_rows.rows_ok(256, 64, 1536, 1024, 128)    # no lane tile
    assert not moe_rows.rows_ok(256, 128, 96, 1024, 128)     # the matmul's jnp
    assert not moe_rows.rows_ok(256, 128, 128, 1024, 8)      # no whole chunk
    # rows counted as the jnp forms walk them
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, 2, (64, 1)), jnp.int32)
    w = jnp.ones((2, 64, 32), F32)
    _, counted = moe.dropless_experts(
        jnp.ones((64, 64), F32), idx, jnp.ones((64, 1), F32), w, w,
        jnp.ones((2, 32, 64), F32), (0, 1), 2)
    assert int(counted["moe.rows_moved"]) \
        == gm.buffer_rows(64, 2, gm.row_tile(64, 1, 2)) + 64


# ---------------------------------------------------------------------------
# the row tile follows from the call
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tokens,held,choices,tile,rows", [
    (8192, 8, 4, 128, 33792),       # glm47flash-train-4k
    (8192, 16, 8, 128, 67584),      # trinitymini-train-8k
    (32, 64, 8, 16, 1280),          # mellum2-reason-long, the decode lane
    (512, 64, 8, 128, 12288),       # ... and a prefill window
    (128, 64, 8, 32, 3072),         # a decode lane of 128 slots
    (64, 2, 1, 64, 192),            # a tiny CPU model
], ids=["glm", "trinity", "mellum_decode", "mellum_prefill", "128_slots",
        "tiny"])
def test_row_tile_at_the_shapes_the_cells_call_it_with(tokens, held, choices,
                                                       tile, rows):
    assert gm.row_tile(tokens, choices, held) == tile
    assert gm.buffer_rows(tokens * min(choices, held), held, tile) == rows


def _dense_oracle(x, idx, gates, wg, wu, wd):
    """y[t] = sum_j gates[t, j] * SwiGLU_{idx[t, j]}(x[t]): every expert over
    every token in f32, the chosen ones picked."""
    x, wg, wu, wd = (a.astype(F32) for a in (x, wg, wu, wd))
    hi = jax.lax.Precision.HIGHEST
    act = jax.nn.silu(jnp.einsum("td,edf->tef", x, wg, precision=hi)) \
        * jnp.einsum("td,edf->tef", x, wu, precision=hi)
    ys = jnp.einsum("tef,efd->ted", act, wd, precision=hi)
    picked = jnp.take_along_axis(ys, idx[:, :, None], axis=1)
    return jnp.einsum("tk,tkd->td", gates, picked, precision=hi)


@pytest.mark.parametrize("dtype", [F32, BF], ids=["f32", "bf16"])
@pytest.mark.parametrize("routing", ["even", "one_over_16_rows_and_empty"])
def test_decode_lane_on_sublane_tiles(routing, dtype, monkeypatch):
    """32 tokens, each on 8 of 64 experts, all held, lane-tile widths (the
    grouped matmul's kernels run; 32 tokens are no token tile, so the rows
    move by the jnp forms): the layer takes 16-row tiles, multiplies a
    fraction of what 128-row tiles would, agrees with a dense oracle, and
    gives every token what the same call on 128-row tiles gives it.
    `one_over_...`: an expert every token chooses (two tiles of one group)
    among experts no token chooses."""
    t, e, k = 32, 64, 8
    rng = np.random.default_rng(3)
    score = rng.random((t, e))
    if routing != "even":
        score[:, 5] += 2.0
        score[:, 20:50] -= 2.0
    idx = jnp.asarray(np.argsort(-score, axis=1)[:, :k], jnp.int32)
    gates = jnp.asarray(rng.dirichlet(np.ones(k), t), F32)
    x = _rand(11, (t, D), dtype)
    ws = [_rand(12 + i, s, dtype) * 0.1
          for i, s in enumerate([(e, D, F), (e, D, F), (e, F, D)])]

    def layer():
        return moe.dropless_experts(x, idx, gates, *ws, tuple(range(e)), e)

    y, counted = layer()
    sizes = np.bincount(np.asarray(idx).reshape(-1), minlength=e)
    assert (sizes.max() > 16 and (sizes == 0).sum() >= 30) \
        == (routing != "even")
    assert int(counted["moe.rows_multiplied"]) \
        == int(np.maximum(1, -(-sizes // 16)).sum()) * 16
    assert int(counted["moe.rows_held"]) == t * k
    assert int(counted["moe.rows_dropped"]) == 0
    want = _dense_oracle(x, idx, gates, *ws)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(want), rtol=0,
        atol=1e-5 if dtype == F32 else 3e-2)

    monkeypatch.setattr(gm, "row_tile", lambda *a: gm.ROW_TILE)
    y128, counted128 = layer()
    assert int(counted128["moe.rows_multiplied"]) \
        == int(np.maximum(1, -(-sizes // 128)).sum()) * 128 \
        >= 4 * int(counted["moe.rows_multiplied"])
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(y128, np.float32))


# ---------------------------------------------------------------------------
# the train step's text
# ---------------------------------------------------------------------------

def _train_step_text(layers: int) -> str:
    import paddle_tpu as paddle
    from paddle_tpu.models import glm4_moe_lite as glm
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import make_train_step

    cfg = glm.Glm4MoeLiteConfig(
        vocab_size=256, hidden_size=128, intermediate_size=256,
        moe_intermediate_size=128, num_hidden_layers=layers,
        num_attention_heads=2, q_lora_rank=32, kv_lora_rank=32,
        qk_nope_head_dim=96, qk_rope_head_dim=32, v_head_dim=128,
        n_routed_experts=8, num_experts_per_tok=2, held=(1, 4),
        dtype="bfloat16")
    paddle.seed(0)
    model = glm.Glm4MoeLiteForCausalLM(cfg)
    crit = glm.Glm4MoeLitePretrainingCriterion(cfg)
    step, params, opt = make_train_step(
        model, crit, None, donate=False,
        optimizer=AdamW(learning_rate=1e-3, parameters=model.parameters()))
    x = jnp.zeros((1, 129), jnp.int32)
    y = jnp.zeros((1, 128), jnp.int32)
    return step.jitted.trace(params, opt, jnp.float32(1e-3), x, y, y).lower(
        lowering_platforms=("tpu",)).as_text()


def test_train_step_holds_each_row_kernel_once(monkeypatch):
    """`jit_train_step` of a model with three expert blocks (two layers and
    the MTP module) carries as many `moe_rows_*` Mosaic payloads as one with
    two: each of the four once (PR 29 lowered every layer's kernels again
    and lost 2.4 s of set-up to it)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows = re.compile(r'kernel_name = "(moe_rows[^"]*)"')
    two, three = (_train_step_text(n) for n in (2, 3))
    assert sorted(rows.findall(two)) == [
        "moe_rows_in", "moe_rows_in_bwd", "moe_rows_out", "moe_rows_out_bwd"]
    assert rows.findall(three) == rows.findall(two)
    # the calls themselves grow with the blocks
    calls = [text.count("call @_rows_in_vjp") for text in (two, three)]
    assert calls[0] > 0 and calls[1] * 2 == calls[0] * 3
