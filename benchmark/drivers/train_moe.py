"""Expert-model training cells: `parallel.make_train_step` with the real
AdamW over a `glm4_moe_lite` model that holds one chip's share of its experts,
on fresh seeded rows, dispatched back to back. Modelled on `drivers/train.py`;
what is new is what the model reports of itself each step (the two loss parts
and the routers' counters, read some steps back with the loss) and a
comparison with the plain reference that sees masks, rotary tables and
routing: logits and gradients, not only the loss.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmark import reference_glm4_moe_lite as reference
from benchmark.tracing import Profile, span
from benchmark.traffic_train_moe import split, train_rows

# logits are compared at this many seeded positions of the probe sequence
POSITIONS = 16

# steps dispatched ahead of the one whose loss and counters the host reads,
# as drivers/train.py: a stall of the host's loop longer than three steps
# (~0.9 s) costs the steps a job's loop would lose to it
AHEAD = 3

# The limits of `correct`, by the precision the run states. Every reading is
# of the probe sequence at the cell's sizes (4,096 tokens) on the chip, PR 28
# (PERF.md section 6 has the table): "run" the largest over its seeds of the
# timed path in that precision, "control" the reference itself computed one
# precision lower (`reference.lower_precision`: float8_e4m3fn below bfloat16,
# bfloat16 below float32), which has to fail. The reference is computed UNDER
# THE ROUTING OF WHAT IT IS COMPARED WITH (`reference.route`'s `choice`): a
# near-tie of two experts' scores that rounding decides the other way is
# counted once, as a flip, and what follows it is held to the reference like
# everything else.
#   loss:    |step's loss part - reference's| / reference's, each part
#   logits:  of each head, the LARGEST |logit - reference's| over the 16
#            positions and the vocabulary, in units of the reference logits'
#            standard deviation
#   grads:   relative L2 error of the trainer's own gradient, each leaf of
#            `grad_names` whole; a router's weight under a share of the
#            experts has no gradient on either side (parallel/moe.py) and
#            reads the norm of the program's, which has to be 0
#   flips:   share of a block's (token, expert) assignments that the
#            reference's own router, given the same inputs, does not make
# Readings (bfloat16: the chip at the cell's sizes, my chip runs, PR 28;
# float32: the CPU at the tiny size, seeds 5-7), largest of the run | the
# control:
#   bfloat16 (12 seeds) loss 1.1e-4 | 6.2e-4 (main; the MTP part reads
#     1.1e-4 | 2.7e-4: the control does NOT fail it, a mean over 4,096
#     tokens averages rounding away, and only planted faults hold that limit
#     from above: a mask that is not causal 5.9e-4, a held set shifted by
#     one 5.6e-4); logits 0.084 | 4.9; grads 0.142 (experts; kv_a 0.101,
#     q_b 0.099, eh_proj 0.076) | 0.92-1.03 (float8 flushes the backward
#     pass to zero); flips 0.016 | 0.62
#   float32 loss 9.0e-8 | 5.3e-5; logits 1.8e-6 | 0.027; grads 1.7e-6 |
#     0.011; flips 0 | 0 (0.016 on one seed)
# What the gradients' 0.13-0.15 is NOT: routing flips. Each side under its
# own routing reads the same (seed 3000000501: experts 0.126 own | 0.136
# under the program's routing, kv_a 0.090 | 0.086, logits 0.084 | 0.083, 3 of
# the 16 positions flipped somewhere), and the reference with its matmul
# operands rounded to bfloat16 reads 0.015 (the CPU, the same seed). It is
# XLA:TPU's excess precision (on by default: no bfloat16 round trips inside
# a fusion, so the forward pass runs on finer values than the residuals the
# backward pass is handed): with --xla_allow_excess_precision=false the chip
# reads 0.023-0.025, as the same program does on the CPU (PERF.md 6).
LIMITS = {
    "bfloat16": {"loss": 4e-4, "logits": 0.25, "grads": 0.3, "flips": 0.04},
    "float32": {"loss": 1e-5, "logits": 2e-3, "grads": 2e-3, "flips": 2e-3},
}


def grad_names(m: dict):
    """The gradients held to the reference: the first expert block's router,
    its held experts' three stacked matrices, a kv and a q up-projection,
    and eh_proj."""
    blk = f"model.layers.{m['first_k_dense_replace']}."
    names = [blk + "mlp.gate.weight", blk + "mlp.experts.gate_proj",
             blk + "mlp.experts.up_proj", blk + "mlp.experts.down_proj",
             blk + "self_attn.kv_a_proj_with_mqa.weight",
             blk + "self_attn.q_b_proj.weight"]
    if m["num_nextn_predict_layers"]:
        names.append("mtp.0.eh_proj.weight")
    return names


def model_config(m: dict, dtype: str):
    """The program's configuration from the file's keys: the published ones
    as they are, the router as wide as published, the experts held from the
    deployment, the rates from `assumed`. Sizes only."""
    from paddle_tpu.models import Glm4MoeLiteConfig

    return Glm4MoeLiteConfig.from_dict(
        m, n_routed_experts=reference.router_width(m),
        held=m["deployment"]["held"],
        router_bias_update_rate=m["assumed"]["router_bias_update_rate"],
        mtp_loss_weight=m["assumed"]["mtp_loss_weight"], dtype=dtype)


def build_model(cfg, seed: int = 0):
    """(model, make_step): the seeded model, and `make_step() -> (step,
    params, opt_state)` through `make_train_step` with the real AdamW
    (FusedOptimizer path, weight decay excluded from norm scales; the
    routers' biases are buffers and never reach the optimizer). Two stages,
    because the reference is computed between them."""
    import paddle_tpu as paddle
    from paddle_tpu.models import (Glm4MoeLiteForCausalLM,
                                   Glm4MoeLitePretrainingCriterion)
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import make_train_step

    paddle.seed(seed)
    model = Glm4MoeLiteForCausalLM(cfg)
    optimizer = AdamW(learning_rate=1e-4, weight_decay=0.01,
                      apply_decay_param_fun=lambda name: "norm" not in name,
                      parameters=model.parameters())
    return model, lambda: make_train_step(
        model, Glm4MoeLitePretrainingCriterion(cfg), None,
        optimizer=optimizer)


def _rel(a, b) -> float:
    """|a - b| / |b|; |a| where the reference has no gradient at all."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) or 1.0))


def logit_errors(want, got):
    """Of each probed position, the largest |logit - reference's| over the
    vocabulary, in units of the reference logits' standard deviation."""
    w, g = np.asarray(want, np.float32), np.asarray(got, np.float32)
    return np.abs(g - w).max(-1) / w.std()


def flips(got_choice, own_choice):
    """Of each expert block, the share of the (token, expert) assignments in
    `got_choice` [blocks, T, k] that `own_choice` does not make."""
    g, w = np.asarray(got_choice), np.asarray(own_choice)
    return 1.0 - (g[..., :, None] == w[..., None, :]).any(-1).mean((1, 2))


def compare(m, want, want_grads, got, got_grads, limits) -> list:
    """[(what, reading, limit)] of the probe against the reference: `want` is
    `reference.forward`'s result on the probe row under the routing
    `got["moe.choice"]`, `got` holds the program's `loss.main`, `loss.mtp`,
    `logits.main`, `logits.mtp`, `moe.choice` and `moe.rows_dropped` on
    copies of that row."""
    out = []
    for part in ("loss.main", "loss.mtp"):
        if part == "loss.mtp" and not m["num_nextn_predict_layers"]:
            continue
        w = float(want[part])
        out.append((part, abs(float(got[part]) - w) / abs(w),
                    limits["loss"]))
    for head in ("logits.main", "logits.mtp"):
        if head in want:
            out.append((head, float(logit_errors(want[head],
                                                 got[head]).max()),
                        limits["logits"]))
    for name, w in want_grads.items():
        out.append(("grad " + name, _rel(got_grads[name], w),
                    limits["grads"]))
    for i, share in enumerate(flips(got["moe.choice"], want["moe.choice"])):
        out.append((f"flips expert block {i}", float(share),
                    limits["flips"]))
    out.append(("moe.rows_dropped", float(got["moe.rows_dropped"]), 0.5))
    return out


def reference_probe(m, state, row, pos, choice=None):
    """The reference's losses, routing, logits at `pos` and gradients of
    `grad_names(m)` on one probe row under the routing `choice` (its own
    where None), as numpy."""
    want, grads = reference.forward_and_grads(
        m, state, row, m["deployment"]["held"], grad_names(m), pos, choice)
    return ({k: np.asarray(v) for k, v in want.items()},
            {k: np.asarray(v) for k, v in grads.items()})


def heads(model, p, tokens, at):
    """Both heads' logits of the model's own forward pass over `tokens` at
    the positions `at` of its first row (an argument: the program is the
    same whatever the seed), and every router's choice."""
    from paddle_tpu.core.tensor import Tensor, unwrap

    out = model.func_call(p, Tensor(tokens))
    picked = {"logits.main": unwrap(out.logits)[0, at].astype("float32"),
              "moe.choice": out.counters["moe.choice"]}
    if out.mtp_logits:
        picked["logits.mtp"] = unwrap(
            out.mtp_logits[-1])[0, at].astype("float32")
    return picked


def probe(m, model, make_step, seed: int, limits: dict, say):
    """The comparison that decides `correct`, before the window, of what the
    timed path computes at the timed sizes: ONE seeded row, tiled over the
    batch, so the step's loss (a mean over the batch) is that row's. The
    model's own forward pass and the reference under its routing come
    first, while the device holds the parameters alone.
    Returns (step, params, opt_state, what failed)."""
    dep = m["deployment"]
    batch, seq = dep["batch"], dep["seq"]
    ahead = m["num_nextn_predict_layers"]
    row = train_rows(m["vocab_size"], 1, seq, ahead, seed, 0)
    pos = np.sort(np.random.default_rng([seed, 11]).choice(
        seq, min(POSITIONS, seq), replace=False))
    x, *labels = split(np.repeat(row, batch, 0), seq, ahead)

    got = {k: np.asarray(v) for k, v in jax.jit(
        lambda p, tokens, at: heads(model, p, tokens, at))(
            model.raw_state(), x[:1], pos).items()}
    want, want_grads = reference_probe(m, model.raw_state(), row[0], pos,
                                       got["moe.choice"])

    step, params, opt = make_step()
    # the backward pass apart from the step keeps every gradient beside the
    # activations: no room for it next to the optimizer's moments, which are
    # zeros until the first step — dropped here and made again below
    del opt
    _, grads = step.loss_and_grads(params, x, *labels)
    got_grads = {k: np.asarray(grads[k], np.float32) for k in want_grads}
    del grads
    opt = step.fused_optimizer.init_state(params)
    loss0, params, opt, report0 = step(params, opt, x, *labels)   # compiles
    got.update({k: np.asarray(v) for k, v in report0.items()})
    loss1, params, opt, _ = step(params, opt, x, *labels)
    bad = []
    for what, reading, limit in compare(m, want, want_grads, got, got_grads,
                                        limits):
        say(f"check: {what}: {reading:.3e} (limit {limit:g})")
        if not reading < limit:
            bad.append(f"{what} is {reading:.3e}, over {limit:g}")
    say(f"check: first step's loss {float(loss0):.5f} (main "
        f"{float(got['loss.main']):.5f}, mtp {float(got['loss.mtp']):.5f}; "
        f"reference {float(want['loss.main']):.5f}, "
        f"{float(want['loss.mtp']):.5f}); second step on the same batch "
        f"{float(loss1):.5f}")
    if not float(loss1) < float(loss0):
        bad.append("a second step on the same batch did not lower its loss")
    return step, params, opt, bad


# the nearest precision below each: the control's
BELOW = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}


def control(m, model, seed: int, dtype: str):
    """[(what, reading, limit)] of the REFERENCE computed one precision below
    `dtype` against the reference itself under that run's routing, on the
    probe row, under `dtype`'s limits: the reading that has to fail where a
    run in `dtype` passes."""
    dep = m["deployment"]
    row = train_rows(m["vocab_size"], 1, dep["seq"],
                     m["num_nextn_predict_layers"], seed, 0)[0]
    pos = np.arange(0, dep["seq"], max(1, dep["seq"] // POSITIONS))
    state = model.raw_state()
    with reference.lower_precision(BELOW[dtype]):
        got, got_grads = reference_probe(m, state, row, pos)
    want, want_grads = reference_probe(m, state, row, pos, got["moe.choice"])
    got["moe.rows_dropped"] = 0.0
    return compare(m, want, want_grads, got, got_grads, LIMITS[dtype])


def run(cell, seed: int, seconds: float, trace: bool, say,
        dtype="bfloat16"):
    from paddle_tpu.parallel import read_report
    from paddle_tpu.serving.compile_cache import enable_compile_cache

    m = cell["config"]
    dep = m["deployment"]
    if dep.get("mesh"):
        raise SystemExit("benchmark: the expert trainer runs on one chip; "
                         "its exchange over a mesh is not built")
    say(f"train_moe: compile cache in {enable_compile_cache()}")
    seed %= 2**31           # paddle.seed takes 32 signed bits
    batch, seq, vocab = dep["batch"], dep["seq"], m["vocab_size"]
    ahead = m["num_nextn_predict_layers"]
    model, make_step = build_model(model_config(m, dtype), seed)
    step, params, opt, bad = probe(m, model, make_step, seed, LIMITS[dtype],
                                   say)

    profile = Profile(trace)
    read, ahead_q, n, slowest = [], [], 0, 0.0

    def read_back(item):
        """The loss and the counters of one step, as a job's log reads them
        (and the metrics registry, when it is armed). The read waits for
        the step, so `t` is when it finished on the device: the readers
        pick the steps the profiler saw by it."""
        loss, rep = item
        read.append(dict(read_report(rep), loss=float(loss),
                         t=time.perf_counter() - t0))

    t0 = last = time.perf_counter()
    while True:
        with span("next_batch"):
            x, *labels = split(train_rows(vocab, batch, seq, ahead, seed,
                                          n + 1), seq, ahead)
        with span("step"):
            loss, params, opt, rep = step(params, opt, x, *labels)
        ahead_q.append((loss, rep))
        n += 1
        # stay AHEAD steps ahead of the device, never a whole window; what
        # that many steps back reported is read as a job reads it for its log
        if len(ahead_q) == AHEAD:
            with span("sync"):
                read_back(ahead_q.pop(0))
        now = time.perf_counter()
        slowest, last = max(slowest, now - last), now
        profile.tick(now - t0)
        if now - t0 >= seconds:
            break
    for item in ahead_q:                 # the barrier at the window's end
        read_back(item)
    t1 = time.perf_counter()
    profile.close()
    losses = [r["loss"] for r in read]
    say(f"train_moe: {n} steps of {batch} x {seq} tokens in {t1 - t0:.3f} s, "
        f"the slowest turn of the loop {slowest * 1e3:.0f} ms; loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}; rows held a step "
        f"{np.mean([r['moe.rows_held'] for r in read]):.0f} of "
        f"{read[0]['moe.rows_routed']:.0f} routed (first step "
        f"{read[0]['moe.rows_held']:.0f}, last {read[-1]['moe.rows_held']:.0f})")
    if not np.isfinite([[r["loss"], r["loss.main"], r["loss.mtp"]]
                        for r in read]).all():
        bad.append("a loss is not finite")
    if any(r["moe.rows_dropped"] for r in read):
        bad.append("an expert layer dropped rows")
    for line in bad:
        say(f"check: FAILED: {line}")
    raw = {"steps": n, "t0": t0, "t1": t1, "batch": batch, "seq": seq,
           "chips": 1, "reports": read,
           "traced": (profile.t_start, profile.t_stop)}
    return {"correct": not bad, "attempted": n, "failed": 0,
            "end_to_end": {"train_tok_s": n * batch * seq / (t1 - t0)},
            "raw": raw, "profile": profile}
