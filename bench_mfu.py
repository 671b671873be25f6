"""MFU recovery sweep: remat-granularity x activation-memory levers.

Usage: python bench_mfu.py [name ...]   (default: the full matrix)

Round-4 VERDICT item 4: the honest step (fp32 Adam moments, decay
exclusion) costs 13% MFU vs round 2; the untried levers are (a) the
fused-swiglu custom-vjp as an activation-memory lever (its per-tile
recompute never saves the two [B,S,F] gate/up intermediates, possibly
buying whole no-remat layers), and (b) sub-layer remat policies
(attn-only / mlp-only per layer — reference recompute granularity is
op-level, fleet/recompute/recompute.py:109).

Each config runs the SAME honest train step as bench.py (real AdamW,
fp32 moments, norm/bias decay exclusion) on the 1B GQA bench shape.
OOMs are recorded, not fatal. One JSON line per config.
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

MATRIX = {
    # name: config overrides
    "skip8_layer": dict(recompute=True, recompute_skip=8),       # baseline
    "skip10_layer": dict(recompute=True, recompute_skip=10),
    "skip12_layer": dict(recompute=True, recompute_skip=12),
    "mlp_all": dict(recompute=True, recompute_skip=0,
                    remat_scope="mlp"),
    "mlp_skip8": dict(recompute=True, recompute_skip=8,
                      remat_scope="mlp"),
    "attn_all": dict(recompute=True, recompute_skip=0,
                     remat_scope="attn"),
    # no fused_swiglu rows: the 1B MLP (F=5504) is not 512-tileable, so
    # swiglu_matmul(fused=True) refuses it (before PR 22 those rows ran
    # the XLA form under the kernel's name)
    # save_only_these_names("attn_out"): backward skips re-running the
    # flash forward (the FLOPs-densest recompute share) at 64 MB/layer
    # of saved attention outputs
    "saveattn_all": dict(recompute=True, recompute_skip=0,
                         remat_policy="save_attn"),
    "saveattn_skip4": dict(recompute=True, recompute_skip=4,
                           remat_policy="save_attn"),
    "saveattn_skip8": dict(recompute=True, recompute_skip=8,
                           remat_policy="save_attn"),
    # batch axis: smaller batches shrink the activation pool, buying
    # remat-free layers at the cost of MXU tile efficiency
    "bs4_noremat": dict(batch=4, recompute=False),
    "bs4_skip12": dict(batch=4, recompute=True, recompute_skip=12),
    "bs6_noremat": dict(batch=6, recompute=False),
}

_OOM_MARKS = ("RESOURCE_EXHAUSTED", "Allocation type: HLO temp",
              "out of memory", "exceeds the limit", "exceeds available")


def run_config(name: str, overrides: dict, batch=8, seq=2048, iters=8):
    overrides = dict(overrides)
    batch = overrides.pop("batch", batch)
    import paddle_tpu as paddle
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaPretrainingCriterion)
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import make_train_step

    cfg = LlamaConfig.llama_1b(dtype="bfloat16", num_key_value_heads=4,
                               max_position_embeddings=seq, **overrides)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    crit = LlamaPretrainingCriterion(cfg)

    def _decay(nm):
        return "norm" not in nm and not nm.endswith(".b_0")

    optimizer = AdamW(learning_rate=1e-4, weight_decay=0.01,
                      apply_decay_param_fun=_decay,
                      parameters=model.parameters())
    step, params, opt = make_train_step(
        model, lambda lg, lb: crit(lg, lb), None, optimizer=optimizer)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)))
    y = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)))
    try:
        loss, params, opt = step(params, opt, x, y)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            loss, params, opt = step(params, opt, x, y)
        float(loss)
        dt = (time.perf_counter() - t0) / iters
    except Exception as e:
        msg = str(e)
        if any(m in msg for m in _OOM_MARKS):
            # compile-time HBM OOM: the config does not fit 16 GB — a
            # data point for the frontier, not an infrastructure failure
            print(json.dumps({"config": name, "oom": True}), flush=True)
        else:
            print(json.dumps({"config": name, "error":
                              f"{type(e).__name__}: {msg[:160]}"}),
                  flush=True)
        return
    tok_s = batch * seq / dt
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    # peak from THE spec table (analysis/device_specs.py; ISSUE 13
    # hoist — value unchanged: v5e bf16 197e12)
    from paddle_tpu.analysis.device_specs import DEVICE_SPECS

    mfu = tok_s * 6 * n_params / DEVICE_SPECS["tpu-v5e"].peak_for(
        "bfloat16")
    print(json.dumps({"config": name, "tok_s": round(tok_s, 1),
                      "mfu": round(mfu, 4),
                      "loss": round(float(loss), 3)}), flush=True)


if __name__ == "__main__":
    from paddle_tpu.serving.compile_cache import enable_compile_cache

    enable_compile_cache()   # the one decision where the cache lives
    names = sys.argv[1:] or list(MATRIX)
    for nm in names:
        run_config(nm, MATRIX[nm])
