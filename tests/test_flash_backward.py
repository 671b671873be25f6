"""The one-pass flash-attention backward (ISSUE 30): dq, dk and dv of
`flash_attention_bwd` against the f32 jnp form, in interpret mode; the block
rule; and the guard on what refused PR 29 — a model's N layers share ONE
lowered flash kernel of each kind, so tracing and lowering do not grow with
the depth.
"""
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BF, F32 = jnp.bfloat16, jnp.float32
SCALE = 0.088


def _fa():
    return importlib.import_module("paddle_tpu.kernels.flash_attention")


def _operands(bh, bkv, sq, sk, d, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (bh, sq, d), F32).astype(dtype)
    k = jax.random.normal(ks[1], (bkv, sk, d), F32).astype(dtype)
    v = jax.random.normal(ks[2], (bkv, sk, d), F32).astype(dtype)
    do = jax.random.normal(ks[3], (bh, sq, d), F32).astype(dtype)
    return q, k, v, do


def _f32_form(q, k, v, do, causal):
    """out, lse, (dq, dk, dv) of plain f32 attention on the operands' values;
    dk/dv per kv head (summed over a group's q heads)."""
    q, k, v, do = (x.astype(F32) for x in (q, k, v, do))
    rep = q.shape[0] // k.shape[0]
    sq, sk = q.shape[1], k.shape[1]

    def fwd(q, k, v):
        kr, vr = jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0)
        s = jnp.einsum("bqd,bkd->bqk", q, kr) * SCALE
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq),
                          s, -1e30)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        return jnp.einsum("bqk,bkd->bqd", jnp.exp(s - lse[..., None]),
                          vr), lse

    (out, lse), vjp = jax.vjp(fwd, q, k, v)
    return out, lse, vjp((do, jnp.zeros_like(lse)))


def _rel(got, want):
    got, want = got.astype(F32), want.astype(F32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


# bf16: p and ds are rounded to bf16 before their products, as in the parent
TOL = {F32: 2e-5, BF: 2e-2}


def _kernel_grads(fa, q, k, v, do, causal, out=None, **blocks):
    ref_out, lse, want = _f32_form(q, k, v, do, causal)
    out = ref_out.astype(q.dtype) if out is None else out
    got = fa._bwd_pallas(q, k, v, out, lse, do, causal, SCALE, True,
                         **blocks)
    return got, want


@pytest.mark.parametrize("dtype", [BF, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("blocks", [1, 4], ids=["one_block", "four_blocks"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("d", [128, 256])
def test_one_pass_backward_matches_f32(d, causal, blocks, dtype):
    """Four blocks a side: the diagonal skip, dk/dv's accumulation over the
    inner axis and dq's over the outer one are all exercised."""
    fa = _fa()
    rows = 128
    q, k, v, do = _operands(2, 2, rows * blocks, rows * blocks, d, dtype)
    got, want = _kernel_grads(fa, q, k, v, do, causal,
                              block_q=rows, block_k=rows)
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        assert g.dtype == dtype and g.shape == w.shape
        assert _rel(g, w) < TOL[dtype], name


@pytest.mark.parametrize("bq,bk", [(256, 128), (128, 256)])
def test_blocks_need_not_be_square(bq, bk):
    fa = _fa()
    q, k, v, do = _operands(1, 1, 512, 512, 128, F32)
    got, want = _kernel_grads(fa, q, k, v, do, True, block_q=bq, block_k=bk)
    assert max(_rel(g, w) for g, w in zip(got, want)) < TOL[F32]


def test_planted_fault_delta_left_out_fails():
    """With `delta = rowsum(do * o)` left out (o = 0 makes it 0) dq and dk
    must land far outside the tolerance; dv does not depend on it."""
    fa = _fa()
    q, k, v, do = _operands(2, 2, 512, 512, 128, F32)
    got, want = _kernel_grads(fa, q, k, v, do, True, out=jnp.zeros_like(q),
                              block_q=128, block_k=128)
    dq, dk, dv = (_rel(g, w) for g, w in zip(got, want))
    assert dq > 0.05 and dk > 0.05
    assert dv < TOL[F32]


@pytest.mark.parametrize("case", ["grouped", "short_q"])
def test_core_grad_matches_f32(case):
    """Through the jitted core, as a model calls it: grouped heads (dk/dv
    summed over a group) and fewer q rows than keys (the causal diagonal
    ends at the last key)."""
    fa = _fa()
    bh, bkv, sq, sk = {"grouped": (4, 2, 256, 256),
                       "short_q": (2, 2, 128, 512)}[case]
    q, k, v, do = _operands(bh, bkv, sq, sk, 128, F32)
    out, vjp = jax.vjp(lambda q, k, v: fa._flash_core(q, k, v, True, SCALE),
                       q, k, v)
    ref_out, _, want = _f32_form(q, k, v, do, True)
    assert _rel(out, ref_out) < TOL[F32]
    for g, w in zip(vjp(do), want):
        assert _rel(g, w) < TOL[F32]


@pytest.mark.parametrize("block_q", [128, 64], ids=["row", "replicated"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_forward_hands_over_one_f32_a_row(block_q, causal):
    """The forward's row statistic in both layouts the kernel writes: one
    lane-dense row where the q block is whole lane tiles (transposed in the
    kernel), replicated across 128 lanes where it is not; `[BH, Sq]` either
    way, equal to the f32 form's logsumexp."""
    fa = _fa()
    q, k, v, do = _operands(2, 2, 256, 256, 128, F32)
    out, lse = fa._fwd_pallas(q, k, v, causal, SCALE, block_q, 128,
                              interpret=True)
    ref_out, ref_lse, _ = _f32_form(q, k, v, do, causal)
    assert lse.shape == (2, 256) and lse.dtype == F32
    assert _rel(lse, ref_lse) < 1e-6
    assert _rel(out, ref_out) < TOL[F32]


@pytest.mark.parametrize("seq,d,rows", [
    (2048, 128, 1024), (4096, 256, 512), (2048, 384, 256), (2048, 512, 256),
    (1536, 128, 512),          # the largest power of two that divides it
    (512, 128, 512), (1024, 64, 1024),      # no longer than a block: whole
    (300, 512, 300)])          # no lane multiple divides it: whole
def test_block_rows_follow_the_head_size(seq, d, rows):
    fa = _fa()
    assert fa._block_rows(seq, fa._bwd_block_cap(d)) == rows


@pytest.mark.parametrize("seq,blocks", [
    (2048, (1024, 1024)), (4096, (1024, 1024)), (512, (512, 512)),
    (1536, (512, 512))])       # 512-divisible, and 1024 does not divide it
def test_forward_blocks_divide_the_sequence(seq, blocks):
    """Equal and grouped heads on long sequences tile the forward at up to
    1024 rows on a TPU and at 512 in interpret mode."""
    fa = _fa()
    q, kv = (8, seq, 128), (2, seq, 128)
    assert fa._fwd_blocks(q, q, True) == blocks
    assert fa._fwd_blocks(q, q, False) == (512, 512)
    assert fa._fwd_blocks(q, kv, True) == blocks
    assert fa._fwd_blocks(q, kv, False) == (512, 512)
    assert not fa.CONSTRAINT.check([(8, seq, 128)] * 3, ["bfloat16"] * 3)


def _bwd_call_params(seq, d):
    fa = _fa()
    x = jax.ShapeDtypeStruct((1, seq, d), BF)
    row = jax.ShapeDtypeStruct((1, seq), F32)
    jaxpr = jax.make_jaxpr(lambda q, k, v, o, lse, do: fa._bwd_pallas(
        q, k, v, o, lse, do, True, SCALE, True))(x, x, x, x, row, x)
    call, = (e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call")
    return call.params["compiler_params"]["mosaic_tpu"]


@pytest.mark.parametrize("seq,d,limit", [
    # dq's accumulator up to 4 MiB (the trained cells'): Mosaic's default
    (2048, 128, None), (4096, 256, None), (8192, 128, None),
    # past it: the accumulator and 12 MiB beside it
    (16384, 128, 20 << 20), (8192, 256, 20 << 20), (32768, 128, 28 << 20),
    (172032, 128, 96 << 20), (43008, 512, 96 << 20)])
def test_backward_vmem_limit_follows_the_sequence(seq, d, limit):
    """dq's whole-sequence f32 accumulator is the one part of the backward's
    VMEM that grows with the sequence; the limit it asks for is arithmetic on
    shapes (compiled for the chip in `tests/test_chip_compile.py`)."""
    fa = _fa()
    assert _bwd_call_params(seq, d).vmem_limit_bytes == limit
    assert fa._bwd_refusal(seq, d) is None


@pytest.mark.parametrize("seq,d", [(173056, 128), (262144, 128),
                                   (131072, 256), (65536, 512)])
def test_backward_refuses_a_sequence_past_its_vmem(seq, d):
    """Past 84 MiB of dq, differentiating raises at trace time, with the
    sizes and the way out in the message; the forward alone still runs."""
    fa = _fa()
    assert "split the sequence" in fa._bwd_refusal(seq, d)
    x = jax.ShapeDtypeStruct((1, seq, d), BF)

    def loss(q, k, v):
        return fa._flash_core(q, k, v, True, SCALE).astype(F32).sum()

    assert jax.eval_shape(loss, x, x, x).shape == ()
    with pytest.raises(ValueError, match=f"all {seq} query rows"):
        jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    warned = [m for sev, m in fa.CONSTRAINT.check([(1, seq, d)] * 3,
                                                  ["bfloat16"] * 3)
              if sev == "warning"]
    assert warned and "differentiating this call raises" in warned[0]


def test_row_terms_are_never_broadcast():
    """What the backward hands its kernel beside q, k, v, do: one f32 a row,
    `[BH, 1, S]` — and no f32 array wider than that exists around it."""
    fa = _fa()
    q, k, v, do = _operands(2, 2, 512, 512, 128, BF)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: fa._flash_core(q, k, v, True, SCALE)
        .astype(F32).sum(), argnums=(0, 1, 2)))(q, k, v)

    def walk(j):
        for eqn in j.eqns:
            yield eqn
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else [p]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns") \
                            and eqn.primitive.name != "pallas_call":
                        yield from walk(inner)

    eqns = list(walk(jaxpr.jaxpr))
    bwd = [e for e in eqns if e.primitive.name == "pallas_call"
           and e.params["name"] == "flash_attention_bwd"]
    assert len(bwd) == 1
    assert [tuple(x.aval.shape) for x in bwd[0].invars[4:]] \
        == [(2, 1, 512), (2, 1, 512)]
    # the widest f32 value outside the kernels: the product do * o that
    # delta sums (XLA fuses it into the sum); nothing is [.., S, S]-like
    widest = max(int(np.prod(x.aval.shape)) for e in eqns for x in e.outvars
                 if hasattr(x.aval, "dtype") and x.aval.dtype == F32)
    assert widest <= 2 * 512 * 128


@pytest.fixture
def lowering_for_tpu(monkeypatch):
    """The kernels ask `jax.default_backend()` whether to interpret; lowering
    for the TPU platform needs no TPU library (nothing is compiled)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _train_step_text(layers: int) -> str:
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaPretrainingCriterion)
    from paddle_tpu.parallel import make_train_step

    # 2 equal heads of 128 over 512 tokens: a `_wide_blocks_ok` shape
    cfg = LlamaConfig(vocab_size=256, hidden_size=256, intermediate_size=256,
                      num_hidden_layers=layers, num_attention_heads=2,
                      max_position_embeddings=512, dtype="bfloat16")
    crit = LlamaPretrainingCriterion(cfg)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    step, params, state = make_train_step(
        model, lambda lg, lb: crit(lg, lb), mesh=None,
        optimizer=opt.AdamW(learning_rate=1e-3,
                            parameters=model.parameters()), donate=False)
    x = jnp.zeros((1, 512), jnp.int32)
    return step.jitted.trace(params, state, jnp.float32(1e-3), x, x).lower(
        lowering_platforms=("tpu",)).as_text()


def test_train_step_holds_each_flash_kernel_once(lowering_for_tpu):
    """`jit_train_step` of a 4-layer model carries as many flash Mosaic
    payloads as a 1-layer model's: one forward, one backward. (PR 29 lowered
    every layer's kernels again and lost 2.4 s of set-up to it.)"""
    flash = re.compile(r'kernel_name = "[^"]*flash[^"]*"')
    one, four = (_train_step_text(n) for n in (1, 4))
    assert len(flash.findall(one)) == 2, flash.findall(one)
    assert flash.findall(four) == flash.findall(one)
    assert 'kernel_name = "flash_attention_bwd"' in four
    # the calls themselves do grow with the depth
    assert four.count("call @_flash_vjp") == 4 * one.count("call @_flash_vjp")
