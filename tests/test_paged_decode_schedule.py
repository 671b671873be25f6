"""The paged GQA decode kernel's work schedule (ISSUE 26): a loop over a
slot's LIVE pages, several kv heads and pages a step, fetched by async
copies from pools that stay in HBM.

One parametrised parity test against the plain jnp masked softmax in f32.
Every batch is ragged — the lengths at which the schedule changes shape sit
side by side — its table is a permutation of the pool, and every DEAD table
column names a page of NaN (for int8 pools: NaN scales), so a kernel that
reads past `lens` fails loudly instead of averaging garbage in.
"""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import decode_attention as da
from paddle_tpu.models import quantize_kv_pages

# (Hq, Hkv, D, page, table width): the serving cell's heads; the same with
# pages so small that a step spans the whole table; a `serving_mp` shard's
# local heads; the replicated-KV MQA fallback; narrow heads with group 1;
# and the head groupings the decode step's own test runs (group 2, equal
# heads, one kv head) at the serving head size and at a narrow one
GEOMETRIES = {
    "gqa4": (32, 8, 128, 64, 10),
    "gqa4-small-pages": (32, 8, 128, 16, 12),
    "mp-local-8q-2kv": (8, 2, 128, 64, 20),
    "mqa-fallback": (8, 1, 128, 64, 6),
    "group1-d64": (4, 4, 64, 32, 9),
    "gqa2": (8, 4, 128, 32, 8),
    "gqa2-d64-small-pages": (4, 2, 64, 8, 14),
    "group1-d128": (4, 4, 128, 64, 6),
    "mqa-d64": (4, 1, 64, 16, 10),
}


def _oracle(q, k_pool, v_pool, tables, lens):
    """f32 masked softmax over the table's pages; positions past `lens`
    are zeroed before they are scored, so a NaN there cannot reach it."""
    b, hq, d = q.shape
    hkv, page = k_pool.shape[1], k_pool.shape[2]

    def rows(pool):                        # [B, Hkv, W*page, D]
        g = np.moveaxis(pool[tables], 2, 1).reshape(b, hkv, -1, d)
        live = np.arange(g.shape[2])[None, None, :, None] <= \
            lens[:, None, None, None]
        return np.where(live, g, 0.0), live[..., 0]

    (k, live), (v, _) = rows(k_pool), rows(v_pool)
    qg = q.astype(np.float32).reshape(b, hkv, hq // hkv, d)
    s = np.einsum("bhgd,bhtd->bhgt", qg, k) / math.sqrt(d)
    s = np.where(live[:, :, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhgt,bhtd->bhgd", p, v).reshape(b, hq, d)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_live_page_schedule_matches_the_oracle(geometry, kv_dtype):
    hq, hkv, d, page, w = GEOMETRIES[geometry]
    rng = np.random.default_rng(len(geometry) * 7 + hq)
    # one batch, every shape of slot: 1 token; a page short of one token;
    # the first token of a new page; mid-page; the full table; two more
    # ragged rows; and a retired row
    lens = np.array([0, page - 1, page, 2 * page + page // 2,
                     w * page - 1, 3 * page - 1, 5 * page, page + 3],
                    np.int32)
    b = len(lens)
    retired = b - 1
    nan_page, scratch_page, first_live = 0, 1, 2
    n_pool = first_live + b * w
    k_pool = rng.normal(size=(n_pool, hkv, page, d)).astype(np.float32)
    v_pool = rng.normal(size=(n_pool, hkv, page, d)).astype(np.float32)
    # a permuted, non-contiguous table; dead columns name the NaN page,
    # the retired row (length frozen) names the scratch page throughout
    tables = (first_live + rng.permutation(b * w)).reshape(b, w)
    tables = np.where(np.arange(w)[None, :] <= lens[:, None] // page,
                      tables, nan_page).astype(np.int32)
    tables[retired] = scratch_page
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.bfloat16)

    if kv_dtype == "int8":
        # the engine's own quantiser: absmax int8 per (page, kv head)
        k8, k_scale = (np.array(x) for x in quantize_kv_pages(k_pool))
        v8, v_scale = (np.array(x) for x in quantize_kv_pages(v_pool))
        k_ref = k8.astype(np.float32) * k_scale[..., None, None]
        v_ref = v8.astype(np.float32) * v_scale[..., None, None]
        k_scale[nan_page] = v_scale[nan_page] = np.nan
        k_ref[nan_page] = v_ref[nan_page] = np.nan
        out = da.paged_decode_attention(
            q, jnp.asarray(k8), jnp.asarray(v8), jnp.asarray(tables),
            jnp.asarray(lens), k_scale=jnp.asarray(k_scale),
            v_scale=jnp.asarray(v_scale))
    else:
        k_bf, v_bf = (jnp.asarray(x, jnp.bfloat16) for x in (k_pool, v_pool))
        k_ref, v_ref = (np.array(x.astype(jnp.float32)) for x in (k_bf, v_bf))
        k_ref[nan_page] = v_ref[nan_page] = np.nan
        nan = jnp.full((hkv, page, d), jnp.nan, jnp.bfloat16)
        out = da.paged_decode_attention(
            q, k_bf.at[nan_page].set(nan), v_bf.at[nan_page].set(nan),
            jnp.asarray(tables), jnp.asarray(lens))

    ref = _oracle(np.asarray(q.astype(jnp.float32)), k_ref, v_ref, tables,
                  lens)
    out = np.asarray(out.astype(jnp.float32))
    assert np.isfinite(out).all(), "the kernel read a dead table column"
    # the output is rounded to bf16 once (2**-9 relative); the sums are f32
    np.testing.assert_allclose(out, ref, rtol=8e-3, atol=2e-3)


def test_pages_per_step_come_from_shapes():
    """A step covers STEP_ROWS (kv head, token) rows: several small pages,
    one large one, never more than the table is wide."""
    rows = da.STEP_ROWS
    assert da._pages_per_step(28, 8, 64, 128, 2) == rows // (8 * 64)
    assert da._pages_per_step(28, 2, 16, 128, 2) == min(28, rows // 32)
    assert da._pages_per_step(3, 2, 16, 128, 2) == 3
    assert da._pages_per_step(28, 8, 512, 128, 2) == 1
