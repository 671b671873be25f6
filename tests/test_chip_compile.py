"""The main path's Pallas kernels compiled by the TPU compiler, no chip.

Rehearsal 3 of the on-chip-measurement guide kept as tests: each kernel is
lowered and compiled for a DESCRIBED v5e at the widths chip_smoke.py serves
and trains (llama3-8B heads: 32 q / 8 kv, dh 128; the 1B trainer: 16 q / 4 kv,
seq 2048), and must come out as a Mosaic custom call — interpret mode hid
three kernels the lowering refused for fifteen PRs.

This is the ONE file that touches the TPU library, and only from inside the
`topo` fixture: the driver's xdist workers each import every test file, only
one process may load libtpu, and a module that loads it while imported gives
the workers different collections (the whole suite then counts 0). Nothing
here runs at import time; shardings and shapes are built in fixtures/tests.
"""
import importlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_chip(monkeypatch):
    """Steer the kernels' am-I-on-a-TPU question to yes (the process itself
    still sees the CPU, and would lower the interpreter), and keep the
    persistent cache out of the way: an entry compiled for a described chip
    cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= 1, "no Mosaic kernel in program"
    return text


BF, I32, I8, F32 = jnp.bfloat16, jnp.int32, jnp.int8, jnp.float32
# serving geometry: 8 slots, 32 q / 8 kv heads, dh 128, 64-token pages
B, HQ, HK, D, PAGE, W, TN = 8, 32, 8, 128, 64, 17, 64
MAX_PAGES = B * W + 1
POOL = ((MAX_PAGES, HK, PAGE, D), BF)
POOL8 = ((MAX_PAGES, HK, PAGE, D), I8)
SCALE = ((MAX_PAGES, HK), F32)
TABLES, LENS = ((B, W), I32), ((B,), I32)
QWIN, KWIN = ((B, TN, HQ, D), BF), ((B, TN, HK, D), BF)
# trainer geometry: batch 4, seq 2048, 16 q / 4 kv heads
TQ, TKV = ((4, 2048, 16, 128), BF), ((4, 2048, 4, 128), BF)


def _mod(name):
    # `import paddle_tpu.kernels.flash_attention` resolves to the re-exported
    # function of the same name; the module itself comes from importlib
    return importlib.import_module(f"paddle_tpu.kernels.{name}")


def test_flash_fwd(for_chip, one_chip):
    fa = _mod("flash_attention")
    _compile(lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
             one_chip, TQ, TKV, TKV)


def test_flash_bwd(for_chip, one_chip):
    fa = _mod("flash_attention")

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True).astype(F32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, TQ, TKV, TKV)


def test_flash_in_repo_kernels(for_chip, one_chip):
    """The in-repo fwd/bwd kernels behind `_flash_core` at grouped heads."""
    fa = _mod("flash_attention")
    q, kv = ((64, 2048, 128), BF), ((16, 2048, 128), BF)

    def loss(q, k, v):
        return fa._flash_core(q, k, v, True, 0.088).astype(F32).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, q, kv, kv)
    assert text.count("tpu_custom_call") >= 2      # fwd, one-pass bwd
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text


def test_flash_window_kernels_at_grouped_heads(for_chip, one_chip):
    """The `afmoe` trainer's window layers (`trinitymini-train-8k`): one row
    of 8,192 tokens, 32 q heads on 4 kv heads of 128, a window of 2,048 —
    forward at 1,024-row blocks, the one-pass backward at 512 with dq's 4 MiB
    accumulator, both walking the band alone."""
    fa = _mod("flash_attention")
    q, kv = ((1, 8192, 32, 128), BF), ((1, 8192, 4, 128), BF)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True,
                                  window=2048).astype(F32).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, q, kv, kv)
    assert "flash_attention_window_fwd" in text
    assert "flash_attention_window_bwd" in text


@pytest.mark.parametrize("rows,hidden", [(4 * 2048, 2048), (8, 4096),
                                         (8 * 64, 4096)])
def test_rms_norm(for_chip, one_chip, rows, hidden):
    rn = _mod("rms_norm")
    _compile(lambda x, w: rn.rms_norm(x, w, 1e-6), one_chip,
             ((rows, hidden), BF), ((hidden,), BF))


# (slots, q heads, kv heads, head dim, page, table width, pool pages)
PAGED_GQA = {
    "smoke-8x17": (B, HQ, HK, D, PAGE, W, MAX_PAGES),
    # the geometry the ledger measures (`mistral7b-reason-sat`): 32 slots,
    # a 28-page table, 576 pool pages — four pages of all kv heads a step
    "cell-32x28": (32, HQ, HK, D, PAGE, 28, 576),
    # head dims that are not whole lane tiles take the listed kernel: a copy
    # cannot slice their pages out of the pool (refused on the chip, PR 26)
    "d64-group1": (8, 4, 4, 64, 32, 9, 74),
    "d16-tiny-llama": (2, 4, 2, 16, 8, 16, 34),
}


@pytest.mark.parametrize("kv", [BF, I8], ids=["bf16", "int8"])
@pytest.mark.parametrize("geometry", sorted(PAGED_GQA))
def test_paged_gqa_decode(for_chip, one_chip, geometry, kv):
    da = _mod("decode_attention")
    slots, hq, hk, d, page, width, pages = PAGED_GQA[geometry]
    shapes = [((slots, hq, d), BF)] + [((pages, hk, page, d), kv)] * 2 \
        + [((slots, width), I32), ((slots,), I32)]
    if kv == I8:
        shapes += [((pages, hk), F32)] * 2
        _compile(lambda q, k, v, t, n, ks, vs: da.paged_decode_attention(
            q, k, v, t, n, k_scale=ks, v_scale=vs), one_chip, *shapes)
    else:
        _compile(da.paged_decode_attention, one_chip, *shapes)


# (slots, kv heads, head dim, page, pool pages, pool dtype)
KV_COMMIT = {
    # `mistral7b-reason-sat`: 32 slots, 8 kv heads, the 576-page pool
    "cell-mistral": (32, HK, D, PAGE, 576, BF),
    # `mellum2-reason-long`: 4 kv heads, a full layer's pool and a ring pool
    "cell-mellum-full": (32, 4, D, PAGE, 1793, BF),
    "cell-mellum-ring": (32, 4, D, PAGE, 826, BF),
    # 8-row tiles, and more slots than one grid step stages
    "f32": (8, 4, D, 16, 34, F32),
    "slots-512": (512, HK, D, PAGE, 576, BF),
}


@pytest.mark.parametrize("geometry", sorted(KV_COMMIT))
def test_kv_commit(for_chip, one_chip, geometry):
    """The decode step's in-place K/V commit: one Mosaic call named
    `kv_commit` whose outputs are its pool operands; with the pools donated,
    as the served programs donate them, no copy beside it."""
    kc = _mod("kv_commit")
    slots, hk, d, page, pages, dtype = KV_COMMIT[geometry]
    pool, new = ((pages, hk, page, d), dtype), ((slots, hk, d), dtype)
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in (
        pool, pool, new, new, ((slots,), I32), ((slots,), I32))]
    text = jax.jit(kc.kv_commit, donate_argnums=(0, 1)).lower(
        *args).compile().as_text()
    call = re.search(r"%kv_commit(\.\d+)? = [^\n]*custom-call[^\n]*", text)
    assert call, "no custom call named kv_commit"
    assert "output_to_operand_aliasing={{0}: (4, {}), {1}: (5, {})}" \
        in call.group(0)
    assert not re.search(r" copy\(", text)


def test_role_names_reach_the_compiled_program(for_chip, one_chip):
    """What a profiler's trace will list (ISSUE 25): the Mosaic call is the
    instruction `%decode_attention...` — the kernel's registry name, whatever
    its shapes — of the module `jit_<the jitted function's role>`."""
    import re

    da = _mod("decode_attention")

    def serve_decode_chunk(*a):
        return da.paged_decode_attention(*a)

    text = _compile(serve_decode_chunk, one_chip,
                    ((B, HQ, D), BF), POOL, POOL, TABLES, LENS)
    assert text.startswith("HloModule jit_serve_decode_chunk")
    assert re.search(r"%decode_attention(\.\d+)? = [^\n]*custom-call", text)


def test_ragged_step(for_chip, one_chip):
    ra = _mod("ragged_attention")
    _compile(ra.ragged_paged_attention, one_chip,
             QWIN, KWIN, KWIN, POOL, POOL, TABLES, LENS, LENS)


def test_prefix_prefill(for_chip, one_chip):
    pp = _mod("prefix_prefill")
    _compile(pp.prefix_prefill_attention, one_chip,
             QWIN, KWIN, KWIN, POOL, POOL, TABLES, LENS, LENS)


# int8 KV: the ragged and prefix-prefill grids carry the f32 scale sidecar as
# a (1, 1, 1) block of a [pages*nkv, 1, 1] array — the layout the Mosaic
# lowering accepts (PR 22; the (1, 1) block of [pages*nkv, 1] it replaced was
# refused); the paged GQA decode copies a page's lane-padded scale row

def test_int8_paged_gqa_decode(for_chip, one_chip):
    da = _mod("decode_attention")
    _compile(lambda q, k, v, t, n, ks, vs: da.paged_decode_attention(
        q, k, v, t, n, k_scale=ks, v_scale=vs), one_chip,
        ((B, HQ, D), BF), POOL8, POOL8, TABLES, LENS, SCALE, SCALE)


def test_int8_ragged_step(for_chip, one_chip):
    ra = _mod("ragged_attention")
    _compile(lambda q, kn, vn, k, v, t, c, n, ks, vs:
             ra.ragged_paged_attention(q, kn, vn, k, v, t, c, n,
                                       k_scale=ks, v_scale=vs), one_chip,
             QWIN, KWIN, KWIN, POOL8, POOL8, TABLES, LENS, LENS, SCALE, SCALE)


def test_int8_prefix_prefill(for_chip, one_chip):
    pp = _mod("prefix_prefill")
    _compile(lambda q, kn, vn, k, v, t, c, n, ks, vs:
             pp.prefix_prefill_attention(q, kn, vn, k, v, t, c, n,
                                         k_scale=ks, v_scale=vs), one_chip,
             QWIN, KWIN, KWIN, POOL8, POOL8, TABLES, LENS, LENS, SCALE, SCALE)


# the expert trainer (glm-4.7-flash cell): 2 x 4096 tokens, 20 heads of 256;
# 8 held experts of width 1536 under hidden 2048
MLA_QKV = ((2, 4096, 20, 256), BF)


@pytest.mark.parametrize("qkv", [
    # the dense trainer's cell (`dscoder1p3b-train-2k`): 16 equal heads of
    # 128, backward blocks of 1024 rows, dq's accumulator 1 MiB
    ((4, 2048, 16, 128), BF),
    # the expert trainer's: blocks of 512 rows, the accumulator 4 MiB
    MLA_QKV,
    # no power of two: the backward blocks are 256 rows, not 1024 * 128 //
    # 384 = 341, which is no lane multiple and divides no sequence
    ((1, 2048, 4, 384), BF),
    ((1, 2048, 4, 512), BF)], ids=["16x128", "20x256", "4x384", "4x512"])
def test_flash_fwd_bwd_at_wide_heads(for_chip, one_chip, qkv):
    """Equal heads on long sequences: the in-repo forward at 1024-row blocks
    (its row statistic transposed into one lane-dense row) and the in-repo
    one-pass backward (dq, dk, dv from one kernel, with a whole-sequence f32
    dq in VMEM), both inside the default scoped VMEM limit at both train
    cells' shapes — latent attention hands over 20 heads of 192 + 64 = 256, a
    head size no other configuration has — and at the widest heads
    `_wide_blocks_ok` admits."""
    fa = _mod("flash_attention")
    assert fa._wide_blocks_ok(qkv[0][1], qkv[0][1], qkv[0][2], qkv[0][2],
                              qkv[0][3])
    assert fa._bwd_vmem_bytes(qkv[0][1], qkv[0][3]) <= fa.VMEM_DEFAULT_BYTES

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True).astype(F32).sum()

    text = _compile(lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
                    one_chip, qkv, qkv, qkv)
    assert text.count("tpu_custom_call") == 1
    assert "flash_attention_fwd" in text
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                    qkv, qkv, qkv)
    assert text.count("tpu_custom_call") == 2
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
    assert "flash_mha_bwd" not in text


@pytest.mark.parametrize("seq,d", [
    # dq's accumulator 8 MiB: the first size past Mosaic's default limit
    # (16.3 MiB asked of 16 at 1024-row blocks), so the limit is raised
    (16384, 128),
    # the longest sequences the backward admits: 84 MiB of dq under the
    # 96 MiB it may ask for
    (172032, 128), (86016, 256), (43008, 512)])
def test_flash_bwd_at_the_longest_sequences(for_chip, one_chip, seq, d):
    """The one-pass backward's VMEM grows with the sequence (dq's
    whole-sequence accumulator): its limit is raised by arithmetic, up to
    the bound `_bwd_refusal` holds callers to, and one block of rows past
    that bound differentiation raises before anything is compiled."""
    fa = _mod("flash_attention")
    assert fa._bwd_vmem_bytes(seq, d) > fa.VMEM_DEFAULT_BYTES
    assert fa._bwd_refusal(seq, d) is None

    def loss(q, k, v):
        return fa._flash_core(q, k, v, True, 0.088).astype(F32).sum()

    qkv = ((1, seq, d), BF)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                    qkv, qkv, qkv)
    assert "flash_attention_bwd" in text
    if fa._bwd_vmem_bytes(seq, d) == fa.VMEM_MAX_BYTES:
        past = jax.ShapeDtypeStruct((1, seq + 1024, d), BF)
        with pytest.raises(ValueError, match="keeps dq for all"):
            jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)),
                           past, past, past)


@pytest.mark.parametrize("groups,rows,tile,k,n", [
    (8, 4096, 128, 2048, 1536), (8, 4096, 128, 1536, 2048),
    (8, 32768, 128, 2048, 1536), (8, 32768, 128, 1536, 2048),
    (64, 256, 16, 2304, 896), (64, 256, 16, 896, 2304)])
def test_grouped_matmul(for_chip, one_chip, groups, rows, tile, k, n):
    """Forward and both backward products: over 8 groups on 128-row tiles,
    from the rows one chip's share sees in a step to the buffer's worst case;
    and on the sublane tile at the served expert layer's widths (a decode
    step's 256 rows on 64 experts: [1280, 2304] x [64, 2304, 896] and
    [1280, 896] x [64, 896, 2304])."""
    gm = _mod("grouped_matmul")
    m = gm.buffer_rows(rows, groups, tile)
    assert tile == 128 or m == 1280

    def loss(lhs, rhs, sizes):
        out = gm.grouped_matmul(lhs, rhs, gm.group_layout(sizes, m, tile))
        return jnp.square(out.astype(F32)).sum()    # keeps the forward

    text = _compile(jax.grad(loss, argnums=(0, 1)), one_chip,
                    ((m, k), BF), ((groups, k, n), BF), ((groups,), I32))
    for name in ("grouped_matmul", "grouped_matmul_dlhs",
                 "grouped_matmul_drhs"):
        assert f"{name}" in text, name
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("held,experts", [(8, 64), (8, 8)],
                         ids=["a_share_held", "all_held"])
def test_moe_rows(for_chip, one_chip, held, experts):
    """The expert layer's row movements at the expert cell's sizes (8192 x
    2048 tokens, 4 choices, 33,792 buffer rows, bf16) beside the grouped
    matmul they feed: forward and backward, with a share of the experts held
    (the cell) and with all of them (the gates train: `moe_rows_dgates`)."""
    from paddle_tpu.parallel import moe

    t, d, f, k = 8192, 2048, 1536, 4

    def loss(x, gates, wg, wu, wd, idx):
        y, _ = moe.dropless_experts(x, idx, gates, wg, wu, wd,
                                    tuple(range(held)), experts)
        return jnp.square(y.astype(F32)).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), one_chip,
                    ((t, d), BF), ((t, k), F32), ((held, d, f), BF),
                    ((held, d, f), BF), ((held, f, d), BF), ((t, k), I32))
    names = ["moe_rows_in", "moe_rows_out", "moe_rows_out_bwd",
             "moe_rows_in_bwd"] + ["moe_rows_dgates"] * (held == experts)
    for name in names:
        assert f'"{name}"' in text or f"{name}" in text, name
    assert ("moe_rows_dgates" in text) == (held == experts)
    assert text.count("tpu_custom_call") >= 9 + len(names)
    # nothing outside the kernels walks every (token, choice) pair's row
    assert not re.search(r"(bf16|f32)\[32768,2048\]", text)


# ---- the served `mellum` block at its published widths (PR 33) --------------
# 32 slots, 32 q / 4 kv heads of 128, 64-token pages, contexts up to 7,168
# tokens (a 112-column table), a window of 1,024 kept in rings of 25 pages

MQ, MKV, MW, MTN, MWIN = 32, 4, 112, 512, 1024
M_RING_POOL = ((33 * 25 + 1, MKV, PAGE, D), BF)
M_FULL_POOL = ((1793, MKV, PAGE, D), BF)
M_TABLES, M_LENS = ((32, MW), I32), ((32,), I32)


@pytest.mark.parametrize("window", [MWIN, None], ids=["window", "full"])
def test_mellum_paged_decode(for_chip, one_chip, window):
    da = _mod("decode_attention")
    pool = M_RING_POOL if window else M_FULL_POOL
    text = _compile(
        lambda *a: da.paged_decode_attention(*a, window=window), one_chip,
        ((32, MQ, D), BF), pool, pool, M_TABLES, M_LENS)
    assert ("decode_attention_window" in text) == bool(window)


@pytest.mark.parametrize("window", [MWIN, None], ids=["window", "full"])
def test_mellum_ragged_window(for_chip, one_chip, window):
    ra = _mod("ragged_attention")
    pool = M_RING_POOL if window else M_FULL_POOL
    one = ((1,), I32)
    text = _compile(
        lambda *a: ra.ragged_paged_attention(*a, window=window), one_chip,
        ((1, MTN, MQ, D), BF), ((1, MTN, MKV, D), BF), ((1, MTN, MKV, D), BF),
        pool, pool, ((1, MW), I32), one, one)
    assert ("ragged_attention_window" in text) == bool(window)


@pytest.mark.parametrize("tokens", [32, 128, MTN],
                         ids=["decode", "decode_128_slots", "prefill_window"])
def test_mellum_expert_layer(for_chip, one_chip, tokens):
    """The routed layer as the served programs call it: 64 experts of 2304 x
    896 all held, 8 a token, forward alone — 256 rows on 64 groups in a
    decode step, on 16-row tiles in a buffer of 1,280 rows (at 128-row tiles
    it held 8,448; the row movements' jnp forms: 32 tokens are no token
    tile), 4,096 in a prefill window on 128-row tiles in 12,288 (their
    kernels); and a decode lane of 128 slots, which no cell runs: a token
    tile on 32-row tiles, the row kernels on a thin layout."""
    from paddle_tpu.parallel import moe

    d, f, e, k = 2304, 896, 64, 8

    def layer(x, gates, wg, wu, wd, idx):
        return moe.dropless_experts(x, idx, gates, wg, wu, wd,
                                    tuple(range(e)), e)[0]

    text = _compile(layer, one_chip, ((tokens, d), BF), ((tokens, k), F32),
                    ((e, d, f), BF), ((e, d, f), BF), ((e, f, d), BF),
                    ((tokens, k), I32))
    assert "grouped_matmul" in text
    assert ("moe_rows_in" in text and "moe_rows_out" in text) \
        == (tokens % 128 == 0)
    assert "_bwd" not in text and "dgates" not in text
    rows = {32: 1280, 128: 3072, MTN: 12288}[tokens]
    assert f"bf16[{rows},{d}]" in text and f"bf16[{rows},{f}]" in text
    assert not re.search(r"\[8448,", text)


@pytest.fixture
def mellum_engine(for_chip):
    """An engine over one period of the published block (three window layers
    and a full one, every width as published), its parameters shapes alone."""
    from paddle_tpu.models import MellumConfig
    from paddle_tpu.models.mellum import serving_param_shapes
    from paddle_tpu.serving import ContinuousBatchingEngine

    cfg = MellumConfig(num_hidden_layers=4)
    p = {k: jax.ShapeDtypeStruct(v, BF)
         for k, v in serving_param_shapes(cfg).items()}
    return ContinuousBatchingEngine(
        cfg, p, slots=32, max_prompt_len=4096, max_new_tokens=3072,
        token_budget=512, max_pages=225, logprobs=True)


def _served_program(eng, program, one_chip):
    """The compiled text of one program of the engine's inventory."""
    fn, args = {name: (fn, args)
                for name, fn, args in eng._program_inventory()}[program]
    args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip), args)
    return fn.lower(*args).compile().as_text()


def _pools_in_another_layout(text, eng):
    """Every mention of an array of a K/V pool's shape in a layout other
    than the default one, which the decode kernel and the commit read: a
    pool copied whole between two layouts (PR 33's trace: 26% of the served
    expert cell's device time) shows as one, whichever side the copy is on."""
    found = []
    for shape in {kc.shape for kc in eng.kcs}:
        dims = ",".join(str(n) for n in shape)
        found += [m.group(0) for m in re.finditer(
            rf"\w+\[{dims}\]\{{(?!3,2,1,0[:}}])[^}}]*\}}", text)]
    return found


@pytest.mark.parametrize("program", ["decode", "unified"])
def test_mellum_served_programs(mellum_engine, one_chip, program):
    eng = mellum_engine
    assert eng.mgr.ring_pages == 25 and eng.table_width == MW
    text = _served_program(eng, program, one_chip)
    for name in ("decode_attention_window", "decode_attention",
                 "grouped_matmul", "kv_commit"):
        assert re.search(rf"%{name}(\.\d+)? = [^\n]*custom-call", text), name
    assert not _pools_in_another_layout(text, eng)
    # the decode lane's expert buffer on 16-row tiles, not 128-row ones
    assert "bf16[1280,2304]" in text and not re.search(r"\[8448,", text)
    if program == "unified":
        for name in ("ragged_attention_window", "ragged_attention",
                     "moe_rows_in", "moe_rows_out"):
            assert re.search(rf"%{name}(\.\d+)? = [^\n]*custom-call",
                             text), name
    assert text.startswith(
        "HloModule jit_serve_" + {"decode": "decode_chunk",
                                  "unified": "unified_step"}[program])


@pytest.fixture
def mistral_engine(for_chip):
    """The dense served block at the widths and sizes `mistral7b-reason-sat`
    serves (hidden 4096, MLP 14336, 32 q / 8 kv heads of 128; 32 slots, a
    pool of 36,864 tokens = 576 pages of 64 and the scratch page's room), cut
    to two layers, its parameters shapes alone. The numbers are written here
    and not read from `benchmark/`: the program's tests import none of the
    benchmark, so that a later change to its files cannot break them."""
    from paddle_tpu.models import LlamaConfig
    from paddle_tpu.serving import ContinuousBatchingEngine

    h, f, v, layers, pool_tokens = 4096, 14336, 32768, 2, 36864
    cfg = LlamaConfig(
        vocab_size=v, hidden_size=h, intermediate_size=f,
        num_hidden_layers=layers, num_attention_heads=HQ,
        num_key_value_heads=HK, max_position_embeddings=32768,
        rms_norm_eps=1e-5, rope_theta=1e6, tie_word_embeddings=False,
        dtype="bfloat16")
    shapes = {"llama.embed_tokens.weight": (v, h), "llama.norm.weight": (h,),
              "lm_head.weight": (h, v)}
    for i in range(layers):
        pre = f"llama.layers.{i}."
        shapes.update({
            pre + "self_attn.q_proj.weight": (h, HQ * D),
            pre + "self_attn.k_proj.weight": (h, HK * D),
            pre + "self_attn.v_proj.weight": (h, HK * D),
            pre + "self_attn.o_proj.weight": (HQ * D, h),
            pre + "mlp.gate_proj.weight": (h, f),
            pre + "mlp.up_proj.weight": (h, f),
            pre + "mlp.down_proj.weight": (f, h),
            pre + "input_layernorm.weight": (h,),
            pre + "post_attention_layernorm.weight": (h,)})
    p = {k: jax.ShapeDtypeStruct(s, BF) for k, s in shapes.items()}
    # K and V, bf16, every layer: the bytes of the cell's pool of tokens
    return ContinuousBatchingEngine(
        cfg, p, slots=32, max_prompt_len=1024, max_new_tokens=768,
        kv_pool_bytes=pool_tokens * 2 * layers * HK * D * 2)


@pytest.mark.parametrize("program", ["decode", "unified"])
def test_llama_served_programs(mistral_engine, one_chip, program):
    """`mellum`'s twin for the dense block: the decode step commits K/V where
    the pools lie, so neither served program copies a pool whole."""
    eng = mistral_engine
    assert eng.kcs[0].shape == (576, HK, PAGE, D)
    text = _served_program(eng, program, one_chip)
    names = ["decode_attention", "kv_commit", "rms_norm"] \
        + ["ragged_attention"] * (program == "unified")
    for name in names:
        assert re.search(rf"%{name}(\.\d+)? = [^\n]*custom-call", text), name
    assert not _pools_in_another_layout(text, eng)
    assert text.startswith(
        "HloModule jit_serve_" + {"decode": "decode_chunk",
                                  "unified": "unified_step"}[program])


# the learned sparse attention of `hy4-longctx-reason` at its widths: 24 slots,
# 64 heads over a 576-value latent row (384 packed words), 32 index heads of
# 128, 2,048 selected, 256-token pages, a table of 108 pages
HB, HH, HW, HP, HPAGES, HK_SEL, HTN = 24, 64, 108, 256, 300, 2048, 2048
HLATENT = ((HPAGES * HP, 1, 384), I32)
HINDEX = ((HPAGES, 1, HP, 128), BF)


def _labels(text, *names):
    for name in names:
        assert re.search(rf"%{name}(\.\d+)? = [^\n]*custom-call", text), name


def test_hy4_sparse_attention_kernels(for_chip, one_chip):
    sa = _mod("sparse_attention")

    def decode(q, w, index, latent, tables, pos, sink):
        scores = sa.dsa_index_scores(q[:, None], w[:, None], index, tables,
                                     pos[:, None])
        _, idx = sa.select_topk(scores[:, 0], HK_SEL)
        plan = sa.decode_plan(idx, pos + 1, tables, HP)
        qa = jnp.zeros((HB, HH, 576), BF)
        return sa.sparse_mla_decode(qa, latent, plan, sink, scale=1 / 16,
                                    value_width=512, block_size=HP)

    text = _compile(decode, one_chip, ((HB, 32, 128), BF), ((HB, 32), F32),
                    HINDEX, HLATENT, ((HB, HW), I32), ((HB,), I32),
                    ((HH,), F32))
    _labels(text, "dsa_index_scores", "sparse_mla_decode")
    # the plan that arranges the selection by page is no scatter
    assert " scatter(" not in text

    def window(q, w, index, latent, table, pos, sink, qa):
        g = HTN // sa.INDEX_ROWS
        scores = sa.dsa_index_scores(
            q.reshape(g, -1, 32, 128), w.reshape(g, -1, 32), index,
            jnp.broadcast_to(table[None], (g, HW)),
            pos.reshape(g, -1)).reshape(HTN, -1)
        thr, tie = sa.window_selection(scores, HK_SEL)
        return sa.sparse_mla_window(qa, latent, table, pos, scores, thr, tie,
                                    sink, scale=1 / 16, value_width=512,
                                    block_size=HP)

    text = _compile(window, one_chip, ((HTN, 32, 128), BF), ((HTN, 32), F32),
                    HINDEX, HLATENT, ((HW,), I32), ((HTN,), I32),
                    ((HH,), F32), ((HTN, HH, 576), BF))
    _labels(text, "dsa_index_scores", "sparse_mla_window")
    text = _compile(lambda pool, rows, flat: sa.latent_commit(pool, rows,
                                                              flat),
                    one_chip, HLATENT, ((HB, 384), I32), ((HB,), I32))
    _labels(text, "latent_commit")


@pytest.fixture
def hy4_engine(for_chip):
    """An engine over the published block's first three layers (dense and
    full, sparse and full, sparse and shared), every width as published, 16
    held experts, its parameters shapes alone."""
    from paddle_tpu.models.hy_v4 import (HyV4Config, param_dtype,
                                         serving_param_shapes)
    from paddle_tpu.serving import ContinuousBatchingEngine

    cfg = HyV4Config(num_hidden_layers=3, vocab_size=15104,
                     held=tuple(range(16)))
    p = {k: jax.ShapeDtypeStruct(v, param_dtype(k, BF))
         for k, v in serving_param_shapes(cfg).items()}
    return ContinuousBatchingEngine(
        cfg, p, slots=HB, max_prompt_len=24576, max_new_tokens=3072,
        token_budget=HTN, block_size=HP, prompt_bucket=HP, max_pages=HPAGES,
        logprobs=True)


@pytest.mark.parametrize("program", ["decode", "unified"])
def test_hy4_served_programs(hy4_engine, one_chip, program):
    """Both served programs of `hy4-longctx-reason`: the sparse attention
    kernels, the in-place latent commit and the expert layer in the
    program, no pool copied whole into another layout."""
    eng = hy4_engine
    assert eng.table_width == HW
    text = _served_program(eng, program, one_chip)
    _labels(text, "dsa_index_scores", "sparse_mla_decode", "latent_commit",
            "grouped_matmul")
    if program == "unified":
        _labels(text, "sparse_mla_window", "moe_rows_in", "moe_rows_out")
    for pool in [x for x in eng.kcs + eng.vcs if x is not None]:
        dims = ",".join(str(n) for n in pool.shape)
        default = ",".join(str(i) for i in reversed(range(len(pool.shape))))
        assert not re.findall(rf"\w+\[{dims}\]\{{(?!{default}[:}}])[^}}]*\}}",
                              text), pool.shape
    assert text.startswith(
        "HloModule jit_serve_" + {"decode": "decode_chunk",
                                  "unified": "unified_step"}[program])


# the Mamba-2 mixer of `falconh1-chat-sat` at its widths: 32 heads of 128 over
# 2 groups of d_state 256, float32 state rows (64 slots, the one prefilling
# and the sink), windows of 512 tokens in chunks of 128
FS, FROWS, FH, FN, FP, FG, FTN = 64, 66, 32, 256, 128, 2, 512
FSTATE = ((FROWS, FH, FN, FP), F32)


def _state_copied(text, shape):
    """Mentions of a copy whose result has the state's shape: a state
    copied whole, in whatever layout."""
    dims = ",".join(str(n) for n in shape)
    return re.findall(rf"= f32\[{dims}\]\{{[^}}]*\}} copy\(", text)


def _compile_donating_state(fn, one_chip, *shapes):
    """`_compile`, the state (the first argument) donated as the engine's
    programs donate it: the kernels then update it where it lies."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn, donate_argnums=0).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= 1, "no Mosaic kernel in program"
    return text


def test_ssd_kernels(for_chip, one_chip):
    ssd = _mod("ssd")
    text = _compile_donating_state(
        ssd.ssd_decode, one_chip, FSTATE, ((FS,), I32), ((FS, FH, FP), F32),
        ((FS, FH), F32), ((FS, FG, FN), F32), ((FS, FG, FN), F32))
    _labels(text, "ssd_decode")
    assert not _state_copied(text, FSTATE[0])
    text = _compile_donating_state(
        lambda *a: ssd.ssd_scan(*a, chunk=128), one_chip, FSTATE,
        ((1,), I32), ((1,), I32), ((1, FH, FTN, FP), F32),
        ((1, FH, FTN), F32), ((1, FG, FTN, FN), F32), ((1, FG, FTN, FN), F32))
    _labels(text, "ssd_scan")
    assert not _state_copied(text, FSTATE[0])


@pytest.fixture
def falcon_engine(for_chip):
    """An engine over one layer of the published block (attention beside
    the Mamba-2 mixer, every width and multiplier as published), 16 slots
    and a pool of 128 pages (the programs take any), its parameters shapes
    alone."""
    from paddle_tpu.models.falcon_h1 import (FalconH1Config, param_dtype,
                                             serving_param_shapes)
    from paddle_tpu.serving import ContinuousBatchingEngine

    cfg = FalconH1Config(num_hidden_layers=1)
    p = {k: jax.ShapeDtypeStruct(v, param_dtype(k, BF))
         for k, v in serving_param_shapes(cfg).items()}
    return ContinuousBatchingEngine(
        cfg, p, slots=16, max_prompt_len=1536, max_new_tokens=1024,
        token_budget=FTN, block_size=PAGE, prompt_bucket=PAGE, max_pages=129,
        logprobs=True)


@pytest.mark.parametrize("program", ["decode", "unified"])
def test_falcon_served_programs(falcon_engine, one_chip, program):
    """Both served programs of `falconh1-chat-sat`: the recurrence's kernels
    beside the attention kernels and the in-place commit, and no pool or
    state row copied whole."""
    eng = falcon_engine
    text = _served_program(eng, program, one_chip)
    _labels(text, "ssd_decode", "decode_attention", "kv_commit")
    if program == "unified":
        _labels(text, "ssd_scan", "ragged_attention")
    assert not _pools_in_another_layout(text, eng)
    for state in jax.tree.leaves(eng.states):
        assert not _state_copied(text, state.shape), state.shape
    assert text.startswith(
        "HloModule jit_serve_" + {"decode": "decode_chunk",
                                  "unified": "unified_step"}[program])


def test_swiglu_fused_refuses_the_1b_mlp_shape():
    """K 2048, F 5504 is not 512-tileable: a caller who asked for the kernel
    gets an error, never the XLA form under the kernel's name."""
    sw = _mod("swiglu")
    x, w = jnp.zeros((512, 2048), BF), jnp.zeros((2048, 5504), BF)
    with pytest.raises(ValueError, match="fused=True"):
        sw.swiglu_matmul(x, w, w, fused=True)
    assert sw.swiglu_matmul(x[:8], w, w).shape == (8, 5504)
