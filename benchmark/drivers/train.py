"""Training cells: the Llama trainer's step (`parallel.make_train_step`, real
AdamW with f32 moments) on fresh seeded batches, dispatched back to back.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmark import reference
from benchmark.drivers.serve import llama_config
from benchmark.tracing import Profile, span
from benchmark.traffic import train_batch

# the step's bf16 loss on the probe sequence against the f32 reference's
# cross-entropy of the same weights and tokens. On the chip the two differ by
# 7e-7 to 8e-6 of the loss (three seeds, PR 24): the mean over 2,048 tokens
# averages bf16's rounding away. The tolerance is ten times the worst seen. At
# a random initialisation the loss is ln(vocab) plus half the logits'
# variance, so the check catches a step that computes another model (a
# missing layer, a wrong head or norm) and cannot see a wrong mask or rotary
# table, which leave that variance alone — the serving cells hold those to
# the reference logit by logit, through the same models/llama.py.
LOSS_REL_TOL = 1e-4


def build_train_step(cfg, mesh=None, seed: int = 0):
    """(step, params, opt_state, model) for `cfg`: real AdamW through the
    FusedOptimizer path, weight decay excluded from norm scales / biases.
    Copied from bench.py:build_train_step, which ROADMAP.md D7 deletes."""
    import paddle_tpu as paddle
    from paddle_tpu.models import (LlamaForCausalLM,
                                   LlamaPretrainingCriterion, shard_llama)
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import make_train_step

    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    if mesh is not None:
        model = shard_llama(model, mesh)
    crit = LlamaPretrainingCriterion(cfg)

    def _decay(name: str) -> bool:
        # auto names: "linear_3.w_0" / "llamarmsnorm_7.w_0" / "...b_0"
        return "norm" not in name and not name.endswith(".b_0")

    optimizer = AdamW(learning_rate=1e-4, weight_decay=0.01,
                      apply_decay_param_fun=_decay,
                      parameters=model.parameters())
    step, params, opt = make_train_step(
        model, lambda lg, lb: crit(lg, lb), mesh, optimizer=optimizer)
    return step, params, opt, model


def run(cell, seed: int, seconds: float, trace: bool, say, dtype="bfloat16"):
    from paddle_tpu.parallel.mesh import build_mesh, set_global_mesh
    from paddle_tpu.serving.compile_cache import enable_compile_cache

    m = cell["config"]
    dep = m["deployment"]
    say(f"train: compile cache in {enable_compile_cache()}")
    mesh = None
    if dep.get("mesh"):
        n = int(np.prod(list(dep["mesh"].values())))
        mesh = build_mesh(dep["mesh"], devices=jax.devices()[:n])
        set_global_mesh(mesh)
    try:
        # paddle.seed takes 32 signed bits; the driver's seeds are larger
        return _run(m, mesh, seed % 2**31, seconds, trace, say, dtype)
    finally:
        if mesh is not None:
            set_global_mesh(None)


def _run(m, mesh, seed, seconds, trace, say, dtype):
    dep = m["deployment"]
    batch, seq, vocab = dep["batch"], dep["seq"], m["vocab_size"]
    step, params, opt, _ = build_train_step(llama_config(m, dtype), mesh,
                                            seed)
    bad = []
    # probe batch: ONE seeded sequence, tiled over the batch, so the step's
    # loss (a mean over the batch) is that sequence's. Under the initial
    # weights it is held to the f32 reference (computed first: the step
    # donates the parameters). A second step on the same batch must read a
    # lower loss: the update descends. (On fresh uniform ids nothing but
    # noise can be learnt and the loss of new batches drifts UP, 10.439 ->
    # 10.450 over 167 steps on the chip, PR 24: a probe's loss after the
    # window says nothing about the trainer.)
    px, py = (np.repeat(a[:1], batch, 0)
              for a in train_batch(vocab, batch, seq, seed, 0))
    ref = reference.reference_cross_entropy(m, params, px[:1], py[:1])
    loss, params, opt = step(params, opt, px, py)   # compiles
    probe0 = float(loss)
    loss, params, opt = step(params, opt, px, py)
    probe1 = float(loss)
    rel = abs(probe0 - ref) / abs(ref)
    say(f"check: first step's loss {probe0:.5f}, f32 reference's "
        f"cross-entropy of the same sequence {ref:.5f}, relative difference "
        f"{rel:.2e} (tolerance {LOSS_REL_TOL}); second step on the same "
        f"batch {probe1:.5f}")
    if not rel < LOSS_REL_TOL:
        bad.append("first step's loss differs from the f32 reference")
    if not probe1 < probe0:
        bad.append("a second step on the same batch did not lower its loss")
    profile = Profile(trace)
    losses, ahead, n, slowest = [], [], 0, 0.0
    t0 = last = time.perf_counter()
    while True:
        with span("next_batch"):
            x, y = train_batch(vocab, batch, seq, seed, n + 1)
        with span("step"):
            loss, params, opt = step(params, opt, x, y)
        ahead.append(loss)
        n += 1
        # stay a few steps ahead of the device, never a whole window: the
        # host must know the time to stop. The loss of three steps back is
        # read as a job reads it for its log — and so that no small buffer
        # outlives its step in a memory that is 95% full
        if len(ahead) == 3:
            with span("sync"):
                losses.append(float(ahead.pop(0)))
        now = time.perf_counter()
        slowest, last = max(slowest, now - last), now
        profile.tick(now - t0)
        if now - t0 >= seconds:
            break
    losses += [float(v) for v in ahead]      # the barrier at the window's end
    t1 = time.perf_counter()
    profile.close()
    say(f"train: {n} steps of {batch} x {seq} tokens in {t1 - t0:.3f} s, the "
        f"slowest turn of the loop {slowest * 1e3:.0f} ms; loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}")
    if not np.isfinite(losses).all():
        bad.append("a loss is not finite")
    for line in bad:
        say(f"check: FAILED: {line}")
    tok_s = n * batch * seq / (t1 - t0)
    raw = {"steps": n, "t0": t0, "t1": t1, "batch": batch, "seq": seq,
           "chips": 1 if mesh is None else mesh.devices.size}
    return {"correct": not bad, "attempted": n, "failed": 0,
            "end_to_end": {"train_tok_s": tok_s}, "raw": raw,
            "profile": profile}
