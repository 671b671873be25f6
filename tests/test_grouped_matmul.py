"""The grouped matmul `[rows, K] x [G, K, N]` by group sizes and its two
backward products, each against a loop of per-group `numpy` matmuls: the jnp
form (shapes off the lane tile) and the Pallas kernels (interpret mode here;
`tests/test_chip_compile.py` compiles them for the chip), with empty groups,
one group holding every row, and garbage in the padding rows.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import grouped_matmul as gm
from paddle_tpu.kernels.constraints import KERNEL_CONSTRAINTS

# (group sizes, K, N, row tile); K and N whole lane tiles -> the kernels
CASES = {
    "jnp_form": ([5, 0, 40, 3], 64, 32, 16),
    "kernels": ([5, 0, 40, 3], 128, 256, 16),
    "one_group_holds_every_row": ([0, 0, 70, 0], 128, 128, 32),
    "first_and_last_empty": ([0, 33, 31, 64, 0], 256, 128, 32),
    "every_group_empty": ([0, 0, 0], 128, 128, 16),
    "whole_tiles": ([32, 64, 32], 128, 384, 32),
    "jnp_form_one_group": ([0, 19], 24, 40, 8),
}


def _setup(sizes, k, n, tile, spare_rows=37):
    g = len(sizes)
    m = gm.buffer_rows(sum(sizes) + spare_rows, g, tile)
    layout = gm.group_layout(jnp.asarray(sizes), m, tile)
    starts = np.asarray(layout.starts)
    rng = np.random.default_rng(sum(sizes) + k)
    live = np.zeros(m, bool)
    for size, start in zip(sizes, starts):
        live[start:start + size] = True
    lhs = np.where(live[:, None], rng.standard_normal((m, k)), 7.0) \
        .astype(np.float32)         # padding rows hold garbage, not zeros
    rhs = rng.standard_normal((g, k, n)).astype(np.float32)
    dout = np.where(live[:, None], rng.standard_normal((m, n)), 0.0) \
        .astype(np.float32)
    return layout, starts, live, lhs, rhs, dout


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_and_both_backward_products(case):
    sizes, k, n, tile = CASES[case]
    layout, starts, live, lhs, rhs, dout = _setup(sizes, k, n, tile)
    assert gm._pallas_ok(lhs.shape[0], k, n, tile) == (
        not case.startswith("jnp_form"))

    def f(a, b):
        out = gm.grouped_matmul(a, b, layout)
        # rows that are no group's are undefined: keep them out of the sum
        return jnp.sum(jnp.where(live[:, None], out, 0.0) * dout), out

    (_, out), (dl, dr) = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(
        jnp.asarray(lhs), jnp.asarray(rhs))
    want = np.zeros((lhs.shape[0], n), np.float32)
    want_dl = np.zeros_like(lhs)
    want_dr = np.zeros_like(rhs)
    for g, (size, start) in enumerate(zip(sizes, starts)):
        rows = slice(start, start + size)
        want[rows] = lhs[rows] @ rhs[g]
        want_dl[rows] = dout[rows] @ rhs[g].T
        want_dr[g] = lhs[rows].T @ dout[rows]
    tol = dict(rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(out)[live], want[live], **tol)
    np.testing.assert_allclose(np.asarray(dl)[live], want_dl[live], **tol)
    # an empty group's weight gradient is zero, not what the buffer held
    np.testing.assert_allclose(np.asarray(dr), want_dr, **tol)


@pytest.mark.parametrize("sizes,tile", [([5, 0, 40, 3], 16), ([0, 0, 0], 8),
                                        ([128, 1, 127], 128)])
def test_layout_walks_only_the_tiles_in_use(sizes, tile):
    rows = gm.buffer_rows(4 * sum(sizes) + 1, len(sizes), tile)
    layout = gm.group_layout(jnp.asarray(sizes), rows, tile)
    tiles = [max(1, -(-s // tile)) for s in sizes]
    assert int(layout.n_tiles) == sum(tiles) <= rows // tile
    assert list(np.asarray(layout.starts)) == [
        tile * sum(tiles[:g]) for g in range(len(sizes))]
    used = int(layout.n_tiles)
    assert list(np.asarray(layout.tile_group)[:used]) == [
        g for g, t in enumerate(tiles) for _ in range(t)]
    assert int(np.asarray(layout.tile_rows).sum()) == sum(sizes)
    assert not np.asarray(layout.tile_rows)[used:].any()


def test_buffer_rows_hold_the_worst_case():
    # every group may end in a partly filled tile; an empty one keeps a tile
    assert gm.buffer_rows(32768, 8) == 32768 + 8 * gm.ROW_TILE
    assert gm.buffer_rows(1, 3, 16) == 4 * 16
    with pytest.raises(ValueError, match="row tiles"):
        gm.group_layout(jnp.asarray([3]), 100, 16)


def test_registered_with_a_shape_check_and_a_roofline():
    c = KERNEL_CONSTRAINTS["grouped_matmul"]
    assert c.blocks == {"row_tile": gm.ROW_TILE, "col_block": gm.COL_BLOCK}
    bf = "bfloat16"
    fwd = ([(264,), (33792, 2048), (8, 2048, 1536)], ["int32", bf, bf])
    assert c.check(*fwd) == []
    assert c.roofline(*fwd) == {
        "flops": 2 * 33792 * 2048 * 1536,
        "hbm_bytes": 2 * (33792 * 2048 + 8 * 2048 * 1536 + 33792 * 1536)}
    drhs = ([(264,), (264,), (33792, 2048), (33792, 1536)],
            ["int32", "int32", bf, bf])
    assert c.roofline(*drhs)["flops"] == 2 * 33792 * 2048 * 1536
    bad = c.check([(4,), (100, 96), (2, 96, 128)], ["int32", bf, bf])
    assert {sev for sev, _ in bad} == {"error"}
    assert any("96" in msg for _, msg in bad)
    assert any("100 rows" in msg for _, msg in bad)
    assert gm.grouped_matmul_cost(512, 2048, 1536, 8)["flops"] \
        == 2 * 512 * 2048 * 1536
