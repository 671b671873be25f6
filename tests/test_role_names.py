"""Every Pallas kernel and every jitted program carries the name of its role
(ISSUE 25): a profiler's trace lists `decode_attention ...` and
`jit_serve_decode_chunk`, whatever the shapes and whatever Python closure
holds the code. One list of kernel names — the constraint registry's — and
the lint's lookup from a traced `pallas_call` to its registry entry still
resolves through it.
"""
import importlib

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.analysis.rules import _pallas_kernel_name
from paddle_tpu.kernels.constraints import (KERNEL_CONSTRAINTS,
                                            constraint_for_kernel_fn)

BF, I32, I8, F32 = jnp.bfloat16, jnp.int32, jnp.int8, jnp.float32
# the serving geometry of tests/test_chip_compile.py: tracing runs nothing
B, HQ, HK, D, PAGE, W, TN = 8, 32, 8, 128, 64, 17, 64
MAX_PAGES = B * W + 1
POOL = ((MAX_PAGES, HK, PAGE, D), BF)
POOL8 = ((MAX_PAGES, HK, PAGE, D), I8)
SCALE = ((MAX_PAGES, HK), F32)
TABLES, LENS = ((B, W), I32), ((B,), I32)
Q1 = ((B, HQ, D), BF)
QWIN, KWIN = ((B, TN, HQ, D), BF), ((B, TN, HK, D), BF)


def _mod(name):
    return importlib.import_module(f"paddle_tpu.kernels.{name}")


def _flash_grad(q, k, v):
    fa = _mod("flash_attention")
    return jax.grad(lambda q, k, v: fa._flash_core(
        q, k, v, True, 0.088).astype(F32).sum(), argnums=(0, 1, 2))(q, k, v)


def _flash_window_grad(q, k, v):
    fa = _mod("flash_attention")
    return jax.grad(lambda q, k, v: fa._flash_core(
        q, k, v, True, 0.088, 128).astype(F32).sum(),
        argnums=(0, 1, 2))(q, k, v)


def _swiglu_grad(x, wg, wu):
    sw = _mod("swiglu")
    return jax.grad(lambda x, wg, wu: sw.swiglu_matmul(
        x, wg, wu, fused=True).astype(F32).sum(), argnums=(0, 1, 2))(
            x, wg, wu)


def _grouped_grad(lhs, rhs, sizes):
    gm = _mod("grouped_matmul")
    return jax.grad(lambda lhs, rhs: gm.grouped_matmul(
        lhs, rhs, gm.group_layout(sizes, lhs.shape[0])).astype(F32).sum(),
        argnums=(0, 1))(lhs, rhs)


def _moe_rows_grad(x, gates, wg, wu, wd, idx):
    moe = importlib.import_module("paddle_tpu.parallel.moe")
    return jax.grad(lambda x, gates, wg, wu, wd: moe.dropless_experts(
        x, idx, gates, wg, wu, wd, (0, 1, 2, 3), 4)[0].astype(F32).sum(),
        argnums=(0, 1, 2, 3, 4))(x, gates, wg, wu, wd)


def _with_scales(fn):
    return lambda *a: fn(*a[:-2], k_scale=a[-2], v_scale=a[-1])


# (traced function, operand shapes, {pallas name: registry entry})
CASES = {
    "flash_attention": (
        _flash_grad, [((64, 512, 128), BF)] + [((16, 512, 128), BF)] * 2,
        {"flash_attention_fwd": "flash_attention",
         "flash_attention_bwd": "flash_attention"}),
    "flash_attention_window": (
        _flash_window_grad,
        [((64, 512, 128), BF)] + [((16, 512, 128), BF)] * 2,
        {"flash_attention_window_fwd": "flash_attention",
         "flash_attention_window_bwd": "flash_attention"}),
    "swiglu": (
        _swiglu_grad, [((512, 512), BF), ((512, 1024), BF),
                       ((512, 1024), BF)],
        {"swiglu_fwd": "swiglu", "swiglu_bwd": "swiglu"}),
    "grouped_matmul": (
        _grouped_grad, [((1024, 256), BF), ((4, 256, 128), BF), ((4,), I32)],
        {"grouped_matmul": "grouped_matmul",
         "grouped_matmul_dlhs": "grouped_matmul",
         "grouped_matmul_drhs": "grouped_matmul"}),
    # the expert layer with every expert held: the gates train too
    "moe_rows": (
        _moe_rows_grad,
        [((256, 128), BF), ((256, 2), F32), ((4, 128, 256), BF),
         ((4, 128, 256), BF), ((4, 256, 128), BF), ((256, 2), I32)],
        {"moe_rows_in": "moe_rows", "moe_rows_out": "moe_rows",
         "moe_rows_out_bwd": "moe_rows", "moe_rows_in_bwd": "moe_rows",
         "moe_rows_dgates": "moe_rows",
         "grouped_matmul": "grouped_matmul",
         "grouped_matmul_dlhs": "grouped_matmul",
         "grouped_matmul_drhs": "grouped_matmul"}),
    "rms_norm": (
        lambda x, w: _mod("rms_norm").rms_norm(x, w, 1e-6),
        [((512, 4096), BF), ((4096,), BF)], {"rms_norm": None}),
    "decode_attention.dense": (
        lambda *a: _mod("decode_attention").decode_attention(*a),
        [((B, HQ, D), BF)] + [((B, HQ, 1024, D), BF)] * 2 + [LENS],
        {"decode_attention": "decode_attention"}),
    "decode_attention.gqa": (
        lambda *a: _mod("decode_attention").gqa_decode_attention(*a),
        [Q1] + [((B, HK, 1024, D), BF)] * 2 + [LENS],
        {"decode_attention": "decode_attention"}),
    "decode_attention.paged_mha": (
        lambda *a: _mod("decode_attention").paged_decode_attention(*a),
        [((B, HK, D), BF), POOL, POOL, TABLES, LENS],
        {"decode_attention": "decode_attention"}),
    "decode_attention.paged_gqa": (
        lambda *a: _mod("decode_attention").paged_decode_attention(*a),
        [Q1, POOL, POOL, TABLES, LENS],
        {"decode_attention": "decode_attention"}),
    # head dims narrower than a lane tile: the listed kernel's own call site
    "decode_attention.paged_gqa_d64": (
        lambda *a: _mod("decode_attention").paged_decode_attention(*a),
        [((B, HQ, 64), BF)] + [((MAX_PAGES, HK, PAGE, 64), BF)] * 2
        + [TABLES, LENS],
        {"decode_attention": "decode_attention"}),
    "decode_attention.paged_gqa_d64_q8": (
        _with_scales(lambda *a, **kw: _mod(
            "decode_attention").paged_decode_attention(*a, **kw)),
        [((B, HQ, 64), BF)] + [((MAX_PAGES, HK, PAGE, 64), I8)] * 2
        + [TABLES, LENS, SCALE, SCALE],
        {"decode_attention_q8": "decode_attention_q8"}),
    "decode_attention.paged_mha_q8": (
        _with_scales(lambda *a, **kw: _mod(
            "decode_attention").paged_decode_attention(*a, **kw)),
        [((B, HK, D), BF), POOL8, POOL8, TABLES, LENS, SCALE, SCALE],
        {"decode_attention_q8": "decode_attention_q8"}),
    "decode_attention.paged_gqa_q8": (
        _with_scales(lambda *a, **kw: _mod(
            "decode_attention").paged_decode_attention(*a, **kw)),
        [Q1, POOL8, POOL8, TABLES, LENS, SCALE, SCALE],
        {"decode_attention_q8": "decode_attention_q8"}),
    # a sliding-window layer's calls carry their own label (PR 33)
    "decode_attention.paged_gqa_window": (
        lambda *a: _mod("decode_attention").paged_decode_attention(
            *a, window=1024),
        [Q1, POOL, POOL, TABLES, LENS],
        {"decode_attention_window": "decode_attention"}),
    "decode_attention.paged_gqa_d64_window": (
        lambda *a: _mod("decode_attention").paged_decode_attention(
            *a, window=96),
        [((B, HQ, 64), BF)] + [((MAX_PAGES, HK, PAGE, 64), BF)] * 2
        + [TABLES, LENS],
        {"decode_attention_window": "decode_attention"}),
    "ragged_attention_window": (
        lambda *a: _mod("ragged_attention").ragged_paged_attention(
            *a, window=1024),
        [QWIN, KWIN, KWIN, POOL, POOL, TABLES, LENS, LENS],
        {"ragged_attention_window": "ragged_attention"}),
    "ragged_attention": (
        lambda *a: _mod("ragged_attention").ragged_paged_attention(*a),
        [QWIN, KWIN, KWIN, POOL, POOL, TABLES, LENS, LENS],
        {"ragged_attention": "ragged_attention"}),
    "ragged_attention_q8": (
        _with_scales(lambda *a, **kw: _mod(
            "ragged_attention").ragged_paged_attention(*a, **kw)),
        [QWIN, KWIN, KWIN, POOL8, POOL8, TABLES, LENS, LENS, SCALE, SCALE],
        {"ragged_attention_q8": "ragged_attention_q8"}),
    "prefix_prefill": (
        lambda *a: _mod("prefix_prefill").prefix_prefill_attention(*a),
        [QWIN, KWIN, KWIN, POOL, POOL, TABLES, LENS, LENS],
        {"prefix_prefill": "prefix_prefill"}),
    "prefix_prefill_q8": (
        _with_scales(lambda *a, **kw: _mod(
            "prefix_prefill").prefix_prefill_attention(*a, **kw)),
        [QWIN, KWIN, KWIN, POOL8, POOL8, TABLES, LENS, LENS, SCALE, SCALE],
        {"prefix_prefill_q8": "prefix_prefill_q8"}),
    "int4_matmul": (
        lambda *a: _mod("int4_matmul").int4_matmul(*a),
        [((8, 4096), BF), ((4096, 2048), I8), ((4096,), F32)],
        {"int4_matmul": "int4_matmul"}),
    "kv_commit": (
        lambda *a: _mod("kv_commit").kv_commit(*a),
        [POOL, POOL, ((B, HK, D), BF), ((B, HK, D), BF), LENS, LENS],
        {"kv_commit": "kv_commit"}),
}


def _pallas_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _pallas_eqns(inner)


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_pallas_call_is_named_from_the_registry(case):
    fn, shapes, want = CASES[case]
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    eqns = list(_pallas_eqns(jax.make_jaxpr(fn)(*args).jaxpr))
    assert eqns, "no pallas_call traced: the wrapper took its jnp form"
    seen = {}
    for eqn in eqns:
        name = eqn.params["name"]
        assert name, f"a pallas_call of {case} passes no name="
        # what the lint and the audits do with the same equation
        found = constraint_for_kernel_fn(*_pallas_kernel_name(eqn))
        seen[name] = found.name if found is not None else None
    assert seen == want
    for name, entry in want.items():
        if entry is not None:
            assert name.startswith(KERNEL_CONSTRAINTS[entry].name)


def test_no_pallas_call_in_kernels_lacks_a_name():
    """The cases above trace every call site there is today; this reads the
    sources, so a `pl.pallas_call` added without `name=` is caught too."""
    import ast
    import os

    import paddle_tpu.kernels as pkg

    root = os.path.dirname(pkg.__file__)
    calls = []
    for f in sorted(os.listdir(root)):
        if f.endswith(".py"):
            with open(os.path.join(root, f)) as fh:
                tree = ast.parse(fh.read())
            calls += [(f, node.lineno, {k.arg for k in node.keywords})
                      for node in ast.walk(tree)
                      if isinstance(node, ast.Call)
                      and getattr(node.func, "attr", "") == "pallas_call"]
    assert len(calls) == 19     # the call sites CASES covers
    assert [c[:2] for c in calls if "name" not in c[2]] == []


# ---- jitted programs -----------------------------------------------------

def _tiny_engine(**kw):
    import dataclasses

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ContinuousBatchingEngine

    cfg = dataclasses.replace(LlamaConfig.tiny(), num_key_value_heads=2)
    paddle.seed(21)
    params = {k: (v.astype(BF) if v.dtype == F32 else v)
              for k, v in dict(LlamaForCausalLM(cfg).raw_state()).items()}
    base = dict(slots=2, prompt_bucket=8, max_prompt_len=16,
                max_new_tokens=4, block_size=8, steps_per_sync=2)
    return ContinuousBatchingEngine(cfg, params, **dict(base, **kw))


def _module_name(fn, args) -> str:
    return fn.lower(*args).as_text().split("\n", 1)[0]


ENGINES = {
    "unified": (dict(unified_step=True),
                {"decode": "serve_decode_chunk",
                 "unified": "serve_unified_step"}),
    "split": (dict(unified_step=False), {"decode": "serve_decode_chunk"}),
    "speculative": (dict(speculative="ngram", spec_k=2),
                    {"decode": "serve_decode_chunk",
                     "unified": "serve_unified_step",
                     "verify": "serve_verify_chunk"}),
    # the name has to survive `_shard_program`'s shard_map
    "mp2": (dict(serving_mp=2),
            {"decode": "serve_decode_chunk",
             "unified": "serve_unified_step"}),
}


@pytest.mark.parametrize("mode", sorted(ENGINES))
def test_engine_programs_lower_under_their_role(mode):
    kw, want = ENGINES[mode]
    eng = _tiny_engine(**kw)
    w = eng._prefix_width_ladder()[0]
    eng._get_prefill(8, 2)
    eng._get_prefix_prefill(8, 1, w)
    want = dict(want)
    for name, fn, args in eng._program_inventory():
        head = _module_name(fn, args)
        if name.startswith("prefill:cold"):
            assert "@jit_serve_prefill_s8_b2 " in head, head
        elif name.startswith("prefill:prefix"):
            assert f"@jit_serve_prefix_prefill_s8_b1_w{w} " in head, head
        else:
            assert f"@jit_{want.pop(name)} " in head, head
    assert not want, f"programs never built: {want}"


def test_trainer_programs_lower_under_their_role():
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaPretrainingCriterion)
    from paddle_tpu.parallel import make_train_step
    from paddle_tpu.parallel.trainer import make_eval_step

    cfg = LlamaConfig.tiny()
    crit = LlamaPretrainingCriterion(cfg)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    step, params, state = make_train_step(
        model, lambda lg, lb: crit(lg, lb), mesh=None,
        optimizer=opt.AdamW(learning_rate=1e-3,
                            parameters=model.parameters()), donate=False)
    x = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 16)))
    assert "@jit_train_step " in _module_name(
        step.jitted, (params, state, jnp.float32(1e-3), x, x))
    assert "@jit_eval_step " in _module_name(
        make_eval_step(model), (params, x))
