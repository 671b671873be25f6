"""MoE training benchmark: the EP stack's first measured datum.

Round-5 VERDICT #8: every perf figure in four rounds is dense. This
bench trains a 1B-class MoE transformer (8 experts, top-2, sort-based
dispatch — parallel/moe.py MoELayer) against a dense model at MATCHED
ACTIVE parameters on one chip, and isolates the dispatch+combine
overhead by slope-timing the routing alone at the same token count.

Model: the Llama backbone (h=1024, L=12, GQA 16/4) with each layer's
MLP swapped for MoELayer(E=8, d_hidden=2048, top-2, gelu). Active MLP
params/token = 2*2*h*2048 = 8.4M/layer; the dense comparator uses a
swiglu MLP with intermediate 2816 => 3*h*2816 = 8.65M/layer (+3%).
Total params: MoE ~0.9B (experts dominate), dense ~0.2B.

Reference anchor: incubate/distributed/models/moe/moe_layer.py:263.

Usage: python bench_moe.py [moe|dense|dispatch ...] (default: all)
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

SEQ, BATCH, ITERS = 2048, 4, 8
H, L, E, DH_E, TOPK = 1024, 12, 8, 2048, 2


def backbone_cfg(im):
    from paddle_tpu.models import LlamaConfig

    # per-layer remat for BOTH variants: the MoE model's 0.9B params at
    # fp32 moments leave no room for bs-8 no-remat activations (measured
    # HBM OOM by 0.9 GB); the comparison stays apples-to-apples
    return LlamaConfig(vocab_size=32000, hidden_size=H,
                       intermediate_size=im, num_hidden_layers=L,
                       num_attention_heads=16, num_key_value_heads=4,
                       max_position_embeddings=SEQ, recompute=True,
                       dtype="bfloat16")


def build_model(kind):
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.parallel.moe import MoELayer

    paddle.seed(0)
    cfg = backbone_cfg(2816)
    model = LlamaForCausalLM(cfg)
    if kind == "moe":
        for layer in model.llama.layers:
            layer.mlp = MoELayer(d_model=H, num_experts=E, d_hidden=DH_E,
                                 topk=TOPK)
    return cfg, model


def run_train(kind):
    from paddle_tpu.models import LlamaPretrainingCriterion
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import make_train_step

    cfg, model = build_model(kind)
    crit = LlamaPretrainingCriterion(cfg)
    optimizer = AdamW(learning_rate=1e-4, weight_decay=0.01,
                      parameters=model.parameters())
    step, params, opt = make_train_step(
        model, lambda lg, lb: crit(lg, lb), None, optimizer=optimizer)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, cfg.vocab_size, (BATCH, SEQ)))
    y = jnp.asarray(rng.integers(0, cfg.vocab_size, (BATCH, SEQ)))
    loss, params, opt = step(params, opt, x, y)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        loss, params, opt = step(params, opt, x, y)
    float(loss)
    dt = (time.perf_counter() - t0) / ITERS
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    # active params: total minus the (E - topk)/E inactive expert share
    expert_params = L * E * 2 * H * DH_E if kind == "moe" else 0
    active = n_params - expert_params * (E - TOPK) // E
    print(json.dumps({
        "config": kind, "tok_s": round(BATCH * SEQ / dt, 1),
        "ms_step": round(dt * 1e3, 1),
        "params_m": round(n_params / 1e6, 1),
        "active_params_m": round(active / 1e6, 1),
        "loss": round(float(loss), 3)}), flush=True)


def run_dispatch():
    """Routing cost alone: gate -> sort dispatch -> combine (fwd+bwd),
    identity experts, at the bench token count — the overhead share the
    profiler's device-op table attributes to routing."""
    from paddle_tpu.core.tensor import unwrap
    from paddle_tpu.parallel.moe import (moe_combine_sorted,
                                         moe_dispatch_sorted)

    T = BATCH * SEQ
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(T, H)), jnp.bfloat16)
    wg = jnp.asarray(rng.normal(size=(H, E)) * 0.02, jnp.float32)

    def route(hh):
        probs = jax.nn.softmax(hh.astype(jnp.float32) @ wg, -1)
        ein, dst, wts, aux = (unwrap(t) for t in moe_dispatch_sorted(
            hh, probs, E, TOPK))
        y = unwrap(moe_combine_sorted(ein, dst, wts, T, TOPK))
        return jnp.sum(y.astype(jnp.float32)) + unwrap(aux)

    grad = jax.grad(route)

    @jax.jit
    def loop(n, hh):
        def body(i, acc):
            g = grad(hh + (acc * 1e-9).astype(hh.dtype))
            return jnp.sum(g.astype(jnp.float32))
        return jax.lax.fori_loop(0, n, body, jnp.zeros((), jnp.float32))

    from bench_util import paired_slope_ms

    lo, hi = 2, 42
    float(loop(lo, h)); float(loop(hi, h))  # warm (trip count traced)
    ms = paired_slope_ms(lambda n: float(loop(n, h)), lo, hi, pairs=5)
    print(json.dumps({
        "config": "dispatch_combine_fwd_bwd",
        "ms_per_layer_call": round(ms, 3),
        "ms_per_step_all_layers": round(ms * L, 2),
        "tokens": T}), flush=True)


if __name__ == "__main__":
    from paddle_tpu.serving.compile_cache import enable_compile_cache

    enable_compile_cache()   # the one decision where the cache lives
    which = sys.argv[1:] or ["moe", "dense", "dispatch"]
    for w in which:
        if w == "dispatch":
            run_dispatch()
        else:
            run_train(w)
