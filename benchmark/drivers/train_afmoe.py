"""The `afmoe` trainer's cells (Trinity-Mini): `parallel.make_train_step` with
the real AdamW over an `AfmoeForCausalLM` that holds one chip's share of its
experts, on fresh seeded rows of ONE long sequence, dispatched back to back.
The loop is `drivers/train_moe.py`'s; what differs is the model (window and
full attention layers, no MTP head, so a row has one label column) and the
comparison that decides `correct`, which holds the program to
`reference_afmoe` — and can hold the reference with a fault planted, or
computed one precision lower, to the same limits.
"""
from __future__ import annotations

import math
import time

import jax
import numpy as np

from benchmark import reference_afmoe as reference
from benchmark.drivers.train_moe import (AHEAD, BELOW, POSITIONS, _rel,
                                         flips, logit_errors)
from benchmark.tracing import Profile, span
from benchmark.traffic_train_moe import split, train_rows

# The limits of `correct`, by the precision the run states. Every reading is
# of the probe row at the cell's sizes (8,192 tokens) — "run" the largest over
# its seeds of the timed path in that precision, "control" the reference
# itself computed one precision lower (float8_e4m3fn below bfloat16, bfloat16
# below float32), which has to fail by at least one limit. As in
# drivers/train_moe.py the reference is computed UNDER THE ROUTING OF WHAT IT
# IS COMPARED WITH, so a near-tie of two experts' scores that rounding
# decides the other way is counted once, as a flip.
#   loss:    |step's loss - reference's| / reference's
#   logits:  the LARGEST |logit - reference's| over the 16 positions and the
#            vocabulary slice, in units of the reference logits' standard
#            deviation
#   grads:   relative L2 error of the trainer's own gradient, each leaf of
#            `grad_names` whole; a router's weight under a share of the
#            experts has no gradient on either side and reads the norm of the
#            program's, which has to be 0
#   flips:   share of a block's (token, expert) assignments that the
#            reference's own router, given the same inputs, does not make
# Readings (bfloat16: the chip at the cell's sizes, my chip runs, PR 35,
# PERF.md section 6; float32: the CPU at the tiny size, seeds 5-9), largest
# of the run | the control:
#   bfloat16 (19 seeds) loss 2.6e-5 | 6.1e-5 (the control does NOT fail it: a
#     mean over 8,192 tokens averages rounding away, as in the GLM cell; the
#     limit stands 7 x over the run, under what a planted fault reads —
#     window layers run full: 3.1e-4); logits 0.065 | 3.2; grads 0.113 (the
#     held experts; q_norm 0.064, the projections 0.05-0.056) | 0.91-1.00
#     (float8 flushes the backward pass to zero); flips 0.012 | 0.39-0.44
#   float32 loss 9.2e-8 | 1.4e-5; logits 2.6e-6 | 0.040; grads 1.7e-6 |
#     0.018; flips 0 | 0 (0.031 on one seed)
# The experts' 0.09-0.11 in bfloat16 reads as the GLM cell's 0.13-0.15 did,
# which PR 28 traced to XLA:TPU's excess precision and not to routing
# (PERF.md section 6; not measured again here).
LIMITS = {
    "bfloat16": {"loss": 2e-4, "logits": 0.25, "grads": 0.3, "flips": 0.04},
    "float32": {"loss": 1e-5, "logits": 2e-3, "grads": 2e-3, "flips": 2e-3},
}


def grad_names(m: dict):
    """The gradients held to the reference: the first expert block's router
    and its held experts' three stacked matrices, that block's q, k and gate
    projections and its q norm (a window layer where the pattern starts with
    one), and the first full layer's q and k projections."""
    blk = f"model.layers.{m['num_dense_layers']}."
    full = "model.layers.%d." % m["layer_types"][
        :m["num_hidden_layers"]].index("full_attention")
    return [blk + "mlp.gate.weight", blk + "mlp.experts.gate_proj",
            blk + "mlp.experts.up_proj", blk + "mlp.experts.down_proj",
            blk + "self_attn.q_proj.weight", blk + "self_attn.k_proj.weight",
            blk + "self_attn.gate_proj.weight",
            blk + "self_attn.q_norm.weight",
            full + "self_attn.q_proj.weight",
            full + "self_attn.k_proj.weight"]


def model_config(m: dict, dtype: str):
    """The program's configuration from the file's keys: the published ones
    as they are, the router as wide as published and the experts held from
    the deployment (`AfmoeConfig.from_dict`). The bias rule's rate is the
    published `load_balance_coeff` unless the deployment states another."""
    try:
        from paddle_tpu.models import AfmoeConfig
    except ImportError:
        raise SystemExit("benchmark: no configuration: this program has no "
                         "`afmoe` family (paddle_tpu.models.AfmoeConfig)")

    rate = m["deployment"].get("router_bias_update_rate",
                               m["load_balance_coeff"])
    return AfmoeConfig.from_dict(m, load_balance_coeff=rate, dtype=dtype)


def build_model(cfg, seed: int = 0):
    """(model, make_step): the seeded model, and `make_step() -> (step,
    params, opt_state)` through `make_train_step` with the real AdamW
    (FusedOptimizer path, weight decay excluded from norm scales; the
    routers' biases are buffers and never reach the optimizer). Two stages,
    because the reference is computed between them."""
    import paddle_tpu as paddle
    from paddle_tpu.models import AfmoeForCausalLM, AfmoePretrainingCriterion
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import make_train_step

    paddle.seed(seed)
    model = AfmoeForCausalLM(cfg)
    optimizer = AdamW(learning_rate=1e-4, weight_decay=0.01,
                      apply_decay_param_fun=lambda name: "norm" not in name,
                      parameters=model.parameters())
    return model, lambda: make_train_step(
        model, AfmoePretrainingCriterion(cfg), None, optimizer=optimizer)


def compare(want, want_grads, got, got_grads, limits) -> list:
    """[(what, reading, limit)] of the probe against the reference: `want` is
    `reference.forward`'s result on the probe row under the routing
    `got["moe.choice"]`, `got` holds the program's `loss`, `logits`,
    `moe.choice` and `moe.rows_dropped` on that row."""
    w = float(want["loss"])
    out = [("loss", abs(float(got["loss"]) - w) / abs(w), limits["loss"]),
           ("logits", float(logit_errors(want["logits"],
                                         got["logits"]).max()),
            limits["logits"])]
    for name, wg in want_grads.items():
        out.append(("grad " + name, _rel(got_grads[name], wg),
                    limits["grads"]))
    for i, share in enumerate(flips(got["moe.choice"], want["moe.choice"])):
        out.append((f"flips expert block {i}", float(share),
                    limits["flips"]))
    out.append(("moe.rows_dropped", float(got["moe.rows_dropped"]), 0.5))
    return out


def reference_probe(m, state, row, pos, choice=None):
    """The reference's loss, routing, logits at `pos` and gradients of
    `grad_names(m)` on one probe row under the routing `choice` (its own
    where None), as numpy."""
    want, grads = reference.forward_and_grads(
        m, state, row, m["deployment"]["held"], grad_names(m), pos, choice)
    return ({k: np.asarray(v) for k, v in want.items()},
            {k: np.asarray(v) for k, v in grads.items()})


def heads(model, p, tokens, at):
    """The logits of the model's own forward pass over `tokens` at the
    positions `at` of its first row (an argument: the program is the same
    whatever the seed), and every router's choice."""
    from paddle_tpu.core.tensor import Tensor, unwrap

    out = model.func_call(p, Tensor(tokens))
    return {"logits": unwrap(out.logits)[0, at].astype("float32"),
            "moe.choice": out.counters["moe.choice"]}


def probe_row(m, seed: int):
    """(the probe row [1, seq + 1], the positions whose logits are held)."""
    seq = m["deployment"]["seq"]
    row = train_rows(m["vocab_size"], 1, seq, 0, seed, 0)
    pos = np.sort(np.random.default_rng([seed, 11]).choice(
        seq, min(POSITIONS, seq), replace=False))
    return row, pos


def probe(m, model, make_step, seed: int, limits: dict, say):
    """The comparison that decides `correct`, before the window, of what the
    timed path computes at the timed sizes: ONE seeded row, tiled over the
    batch, so the step's loss (a mean over the batch) is that row's. The
    model's own forward pass and the reference under its routing come
    first, while the device holds the parameters alone.
    Returns (step, params, opt_state, what failed)."""
    dep = m["deployment"]
    batch, seq = dep["batch"], dep["seq"]
    row, pos = probe_row(m, seed)
    x, labels = split(np.repeat(row, batch, 0), seq, 0)

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
    _, grads = step.loss_and_grads(params, x, labels)
    got_grads = {k: np.asarray(grads[k], np.float32) for k in want_grads}
    del grads
    opt = step.fused_optimizer.init_state(params)
    loss0, params, opt, report0 = step(params, opt, x, labels)   # compiles
    got.update({k: np.asarray(v) for k, v in report0.items()}, loss=loss0)
    loss1, params, opt, _ = step(params, opt, x, labels)
    bad = []
    for what, reading, limit in compare(want, want_grads, got, got_grads,
                                        limits):
        say(f"check: {what}: {reading:.3e} (limit {limit:g})")
        if not reading < limit:
            bad.append(f"{what} is {reading:.3e}, over {limit:g}")
    say(f"check: first step's loss {float(loss0):.5f} (reference "
        f"{float(want['loss']):.5f}); second step on the same batch "
        f"{float(loss1):.5f}")
    if not float(loss1) < float(loss0):
        bad.append("a second step on the same batch did not lower its loss")
    return step, params, opt, bad


def control(m, model, seed: int, dtype: str, fault: str = None):
    """[(what, reading, limit)] of the REFERENCE computed one precision below
    `dtype` — or, with `fault`, in full precision with that fault of
    `reference.FAULTS` planted — against the reference itself under that
    run's routing, on the probe row, under `dtype`'s limits: a reading that
    has to fail where a run in `dtype` passes."""
    row, pos = probe_row(m, seed)
    state = model.raw_state()
    with (reference.planted(fault) if fault
          else reference.lower_precision(BELOW[dtype])):
        got, got_grads = reference_probe(m, state, row[0], pos)
    want, want_grads = reference_probe(m, state, row[0], pos,
                                       got["moe.choice"])
    got["moe.rows_dropped"] = 0.0
    return compare(want, want_grads, got, got_grads, LIMITS[dtype])


def run(cell, seed: int, seconds: float, trace: bool, say,
        dtype="bfloat16"):
    from paddle_tpu.parallel import read_report
    from paddle_tpu.serving.compile_cache import enable_compile_cache

    m = cell["config"]
    dep = m["deployment"]
    if dep.get("mesh"):
        raise SystemExit("benchmark: the expert trainer runs on one chip; "
                         "its exchange over a mesh is not built")
    say(f"train_afmoe: compile cache in {enable_compile_cache()}")
    seed %= 2**31           # paddle.seed takes 32 signed bits
    batch, seq, vocab = dep["batch"], dep["seq"], m["vocab_size"]
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
            x, labels = split(train_rows(vocab, batch, seq, 0, seed, n + 1),
                              seq, 0)
        with span("step"):
            loss, params, opt, rep = step(params, opt, x, labels)
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
    held = [r["moe.rows_held"] for r in read]
    say(f"train_afmoe: {n} steps of {batch} x {seq} tokens in {t1 - t0:.3f} "
        f"s, the slowest turn of the loop {slowest * 1e3:.0f} ms; loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}; rows held a step "
        f"{np.mean(held):.0f} of {read[0]['moe.rows_routed']:.0f} routed "
        f"(least {min(held):.0f}, most {max(held):.0f}); busiest expert over "
        f"the mean {np.mean([r['moe.load_max'] / max(r['moe.load_mean'], 1) for r in read]):.2f}")
    # the window's own steps: every loss finite and no worse than a model
    # that knows nothing of the slice (ln V) by more than a tenth
    if not (np.isfinite(losses).all()
            and max(losses) < 1.1 * math.log(vocab) + 0.5):
        bad.append(f"a step's loss is not finite or is far over ln V = "
                   f"{math.log(vocab):.3f} (largest {max(losses):.4f})")
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
