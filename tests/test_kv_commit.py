"""The decode step's in-place K/V commit (`kernels/kv_commit.py`, interpret
mode here) against XLA's scatter `.at[page, :, slot, :].set`, bit for bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.kv_commit import commit_ok, kv_commit
from paddle_tpu.models.llama import make_paged_kv_helpers

BF, F32 = jnp.bfloat16, jnp.float32
# (kv heads, head dim, page length, pool dtype): both serving cells' pools,
# an f32 pool (8-row tiles), and pages of one row tile
GEOMETRIES = [(8, 128, 64, BF), (4, 128, 64, BF), (4, 128, 64, F32),
              (8, 128, 16, BF)]
IDS = ["mistral", "mellum", "f32", "one-tile-page"]
PAGES = 7


def _pools(hkv, d, block, dtype, seed=0):
    k1, k2 = jax.random.split(jax.random.key(seed))
    shape = (PAGES, hkv, block, d)
    return (jax.random.normal(k1, shape, F32).astype(dtype),
            jax.random.normal(k2, shape, F32).astype(dtype))


def _rows(b, hkv, d, dtype, seed=1):
    k1, k2 = jax.random.split(jax.random.key(seed))
    return (jax.random.normal(k1, (b, hkv, d), F32).astype(dtype),
            jax.random.normal(k2, (b, hkv, d), F32).astype(dtype))


def _same(got, want):
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("hkv,d,block,dtype", GEOMETRIES, ids=IDS)
def test_rows_land_where_the_scatter_puts_them(hkv, d, block, dtype):
    """Slots at the first and last row of a tile, across a tile edge and at
    the page's last row; every other page and row as it was."""
    kc, vc = _pools(hkv, d, block, dtype)
    slot = jnp.asarray([0, 15, 16 % block, block - 1], jnp.int32)
    page = jnp.asarray([1, 3, 4, 6], jnp.int32)
    k, v = _rows(4, hkv, d, dtype)
    assert commit_ok(kc, vc)
    got_k, got_v = kv_commit(kc, vc, k, v, page, slot)
    _same(got_k, kc.at[page, :, slot, :].set(k))
    _same(got_v, vc.at[page, :, slot, :].set(v))
    untouched = np.asarray([0, 2, 5])
    _same(got_k[untouched], kc[untouched])
    _same(got_v[untouched], vc[untouched])
    assert got_k.dtype == kc.dtype and got_k.shape == kc.shape


@pytest.mark.parametrize("hkv,d,block,dtype", GEOMETRIES, ids=IDS)
def test_frozen_rows_share_the_scratch_page(hkv, d, block, dtype):
    """Frozen and free slots all name the scratch page (page 0 here), some the
    same row of it: the live slots' rows land, the live pages hold nothing
    else new, and the scratch page holds only rows that were sent to it."""
    kc, vc = _pools(hkv, d, block, dtype, seed=2)
    page = jnp.asarray([0, 2, 0, 0, 5, 0], jnp.int32)
    slot = jnp.asarray([3, block - 1, 3, 4, 0, 3], jnp.int32)
    k, v = _rows(6, hkv, d, dtype, seed=3)
    got_k, got_v = kv_commit(kc, vc, k, v, page, slot)
    live = np.asarray([1, 4])
    for got, pool, new in ((got_k, kc, k), (got_v, vc, v)):
        _same(got[1:], pool.at[page[live], :, slot[live], :].set(
            new[live])[1:])
        got0, was0 = np.asarray(got[0], np.float32), np.asarray(
            pool[0], np.float32)
        sent = np.asarray(new, np.float32)
        for row in range(block):
            ok = [was0[:, row]] + [sent[i] for i in (0, 2, 3, 5)
                                   if int(slot[i]) == row]
            assert any(np.array_equal(got0[:, row], r) for r in ok), row


def test_under_jit_the_pools_are_donated_and_updated_in_a_scan():
    """As the served programs call it: inside a jitted scan over decode steps,
    the pools carried and donated."""
    hkv, d, block = 4, 128, 16
    kc, vc = _pools(hkv, d, block, BF, seed=4)
    want_k, want_v = kc, vc
    page = jnp.asarray([1, 2, 3], jnp.int32)
    rows = [_rows(3, hkv, d, BF, seed=10 + t) for t in range(block)]

    def chunk(kc, vc, ks, vs):
        def body(carry, new):
            kc, vc, t = carry
            kc, vc = kv_commit(kc, vc, *new, page, jnp.full((3,), t))
            return (kc, vc, t + 1), ()

        (kc, vc, _), _ = jax.lax.scan(body, (kc, vc, jnp.int32(0)), (ks, vs))
        return kc, vc

    for t, (k, v) in enumerate(rows):
        want_k = want_k.at[page, :, t, :].set(k)
        want_v = want_v.at[page, :, t, :].set(v)
    got_k, got_v = jax.jit(chunk, donate_argnums=(0, 1))(
        kc, vc, jnp.stack([r[0] for r in rows]),
        jnp.stack([r[1] for r in rows]))
    _same(got_k, want_k)
    _same(got_v, want_v)


@pytest.mark.parametrize("shape,dtype,why", [
    ((PAGES, 4, 8, 128), BF, "a page of half a bf16 row tile"),
    ((PAGES, 4, 16, 64), BF, "a head of half a lane tile"),
    ((PAGES, 4, 32, 128), jnp.int8, "an int8 pool"),
    ((PAGES, 4, 12, 128), F32, "a page of one and a half f32 row tiles"),
], ids=["block8-bf16", "d64", "int8", "block12-f32"])
def test_the_shape_gate(shape, dtype, why):
    """What the kernel does not take: `kv_write` commits through XLA's
    scatter, and a caller that forces the kernel gets an error — never the
    jnp form under the kernel's name."""
    pool = jnp.zeros(shape, dtype)
    assert not commit_ok(pool, pool), why
    _, hkv, block, d = shape
    b = 2
    k = jnp.ones((b, hkv, d), dtype)
    with pytest.raises(ValueError, match="kv_commit takes"):
        kv_commit(pool, pool, k, k, jnp.zeros((b,), jnp.int32),
                  jnp.zeros((b,), jnp.int32))
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    _, kv_write = make_paged_kv_helpers(b, 0, hkv, d, block, tables)
    lens = jnp.asarray([1, block + 2], jnp.int32)
    jaxpr = jax.make_jaxpr(kv_write)(pool, pool, k[:, None], k[:, None], lens)
    assert "pallas_call" not in str(jaxpr)
    got, _ = kv_write(pool, pool, k[:, None], k[:, None], lens)
    _same(got[1, :, 1], k[0])
    _same(got[4, :, 2], k[1])


def test_mismatched_pools_and_rows_are_refused():
    kc = jnp.zeros((PAGES, 4, 16, 128), BF)
    k = jnp.ones((2, 4, 128), BF)
    at = jnp.zeros((2,), jnp.int32)
    assert not commit_ok(kc, kc.astype(F32))
    assert not commit_ok(kc, kc[:, :2])
    with pytest.raises(ValueError, match="kv_commit takes"):
        kv_commit(kc, kc.astype(F32), k, k, at, at)
    with pytest.raises(ValueError, match="rows"):
        kv_commit(kc, kc, k[:, :2], k[:, :2], at, at)
    with pytest.raises(ValueError, match="rows"):
        kv_commit(kc, kc, k, k, at, at[:1])


@pytest.mark.parametrize("hkv,d,block,dtype", GEOMETRIES, ids=IDS)
def test_kv_write_takes_the_kernel(hkv, d, block, dtype):
    """`make_paged_kv_helpers.kv_write` over pools the kernel takes: one
    `kv_commit` call for K and V together, the scatter's result."""
    b = 3
    kc, vc = _pools(hkv, d, block, dtype, seed=5)
    tables = jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.int32)
    _, kv_write = make_paged_kv_helpers(b, 0, hkv, d, block, tables)
    lens = jnp.asarray([0, block - 1, block + 1], jnp.int32)
    k, v = _rows(b, hkv, d, dtype, seed=6)
    text = str(jax.make_jaxpr(kv_write)(kc, vc, k[:, None], v[:, None], lens))
    assert text.count("pallas_call") == 1 and "kv_commit" in text
    assert "scatter" not in text
    got_k, got_v = kv_write(kc, vc, k[:, None], v[:, None], lens)
    page, slot = jnp.asarray([1, 3, 6]), jnp.asarray([0, block - 1, 1])
    _same(got_k, kc.at[page, :, slot, :].set(k))
    _same(got_v, vc.at[page, :, slot, :].set(v))


def test_many_slots_go_in_grid_steps_of_what_vmem_holds():
    """More slots than `STAGE_BYTES` stages at once: the call walks them in
    equal grid steps, and every row still lands."""
    from paddle_tpu.kernels import kv_commit as mod

    hkv, d, block, b = 8, 128, 16, 12
    kc = jnp.zeros((b + 1, hkv, block, d), F32)
    k, v = _rows(b, hkv, d, F32, seed=7)
    page = jnp.arange(1, b + 1, dtype=jnp.int32)
    slot = jnp.arange(b, dtype=jnp.int32) % block
    one_tile = hkv * 8 * d * 4
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(mod, "STAGE_BYTES", 2 * 5 * one_tile)    # 5 slots -> 4
        mod._commit.clear_cache()
        got_k, got_v = kv_commit(kc, kc, k, v, page, slot)
        text = str(jax.make_jaxpr(kv_commit)(kc, kc, k, v, page, slot))
    mod._commit.clear_cache()
    assert "grid=(3,)" in text
    _same(got_k, kc.at[page, :, slot, :].set(k))
    _same(got_v, kc.at[page, :, slot, :].set(v))


# ---- through the serving engine ------------------------------------------
# heads of 128 and pages of 16 rows, f32 on the CPU: the shape gate takes the
# kernel, which the tiny models of the engine's own suites (heads of 16) never
# reach

def _llama():
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=96, hidden_size=256, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=2,
                      num_key_value_heads=1, max_position_embeddings=128,
                      dtype="float32")
    paddle.seed(11)
    return cfg, dict(LlamaForCausalLM(cfg).raw_state()), {}


def _mellum():
    from paddle_tpu.models import MellumConfig, mellum

    cfg = MellumConfig.tiny(head_dim=128, num_attention_heads=2,
                            num_key_value_heads=1, sliding_window=32)
    return (cfg, mellum.init_serving_params(cfg, seed=7, dtype="float32"),
            dict(token_budget=16, logprobs=True))


def _serve(cfg, p, kw, watch=None):
    from paddle_tpu.serving import ContinuousBatchingEngine

    eng = ContinuousBatchingEngine(
        cfg, dict(p), slots=2, prompt_bucket=16, block_size=16,
        max_prompt_len=32, max_new_tokens=40, steps_per_sync=4,
        dtype=jnp.float32, **kw)
    if watch is not None:
        watch(eng)
    rng = np.random.default_rng(5)
    # three requests on two slots: a slot is frozen on the scratch page while
    # the other decodes, and recycled
    for n, new in ((5, 40), (19, 9), (30, 22)):
        eng.add_request(rng.integers(1, cfg.vocab_size, (n,)).tolist(),
                        max_new=new)
    eng.run(max_iters=1000)
    assert len(eng.finished) == 3
    programs = {name: str(jax.make_jaxpr(fn)(*args))
                for name, fn, args in eng._program_inventory()}
    return eng, programs


@pytest.mark.parametrize("family", [_llama, _mellum],
                         ids=["llama", "mellum"])
def test_the_engine_serves_the_same_tokens_through_the_kernel(
        family, monkeypatch):
    """Both served programs commit through `kv_commit` (full pools and ring
    pools alike), and serve what XLA's scatter serves: tokens, their
    log-probabilities and every page but the scratch page, bit for bit."""
    from paddle_tpu.kernels import kv_commit as mod

    cfg, p, kw = family()
    eng, programs = _serve(cfg, p, kw)
    assert {"decode", "unified"} <= set(programs)
    for name in ("decode", "unified"):
        assert "kv_commit" in programs[name], name
    monkeypatch.setattr(mod, "commit_ok", lambda kc, vc: False)
    ref, ref_programs = _serve(cfg, p, kw)
    assert not any("kv_commit" in t for t in ref_programs.values())
    by_id = {r.req_id: r for r in ref.finished}
    for req in eng.finished:
        assert req.tokens == by_id[req.req_id].tokens
        if kw.get("logprobs"):
            assert req.logprobs == by_id[req.req_id].logprobs
    assert len(set(kc.shape for kc in eng.kcs)) == (
        2 if family is _mellum else 1)
    for got, want in zip(eng.kcs + eng.vcs, ref.kcs + ref.vcs):
        keep = np.arange(got.shape[0]) != eng.scratch_page
        _same(got[keep], want[keep])


@pytest.mark.parametrize("family", [_llama, _mellum],
                         ids=["llama", "mellum"])
def test_every_table_a_program_gets_names_pages_of_its_pools(family):
    """The kernel's copies reach whatever page a table names — a page outside
    the pool is a fault on the chip, where the scatter dropped the update. So
    every table the engine hands a served program (the tables among the
    fields `_put` packs: the slots' tables, a chunk's, the warm-up's),
    through admission, retirement and a recycled slot, names pages of the
    pool kind it is for: full layers' pools and the window layers' ring
    pools (`*_ring`) have sizes of their own."""
    seen = []

    def watch(eng):
        real = eng._put
        window = eng._window_layers
        full_pages = min(kc.shape[0] for i, kc in enumerate(eng.kcs)
                         if i not in window)
        ring_pages = min((kc.shape[0] for i, kc in enumerate(eng.kcs)
                          if i in window), default=None)

        def checked(layout, values):
            for name in layout.names:
                if name.split("_ring")[0] not in (
                        "tables", "chunk_table", "chunk_pages"):
                    continue
                pages = ring_pages if name.endswith("_ring") \
                    else full_pages
                table = np.asarray(values[name])
                assert table.min() >= 0 and table.max() < pages, (
                    name, table.min(), table.max(), pages)
                seen.append(pages)
            return real(layout, values)

        eng._put = checked

    cfg, p, kw = family()
    eng, _ = _serve(cfg, p, kw, watch=watch)
    # tables of every pool kind went by, many times over the run's steps
    assert set(seen) == {kc.shape[0] for kc in eng.kcs}
    assert len(seen) > 20
