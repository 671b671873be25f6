"""Serving cells of a model with window / full attention layers and routed
experts: `ContinuousBatchingEngine(cfg, params, <sizes>, logprobs=True)` over
a `MellumConfig`, every tuning option at the program's default, driven by the
cell's traffic through `drivers/serve.py`'s own loop (`drive`, `end_to_end`).

What is new beside that driver is the comparison that decides `correct`,
after `drivers/train_moe.py`'s pattern. It is made AFTER the window, on
requests the window itself finished — served at the timed load, every slot
live, the pools under pressure — and on every token of theirs: ONE forward
pass of the plain reference (`benchmark/reference_mellum.py`) over a
request's prompt and the engine's own tokens, held against the tokens and the
log-probabilities the engine handed out with them, under a `LIMITS` table with
a lower-precision control and planted faults that must fail it
(`benchmark/serve_moe_faults.py` reads them, at the cell's sizes on the chip;
the tests at the tiny size; PERF.md has the readings).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmark import reference_mellum as reference
from benchmark import tracing
from benchmark.drivers.serve import drive, end_to_end
from benchmark.traffic import Traffic
# at import, so that a program without the model fails here, at once
from paddle_tpu.models import MellumConfig
from paddle_tpu.models.mellum import init_serving_params

# The limits of `correct`, by the precision the run states. The reference
# runs ONE forward pass over each sampled request's prompt and the engine's
# own tokens, so a near-tie that rounding decides the other way is counted
# once, where it happens, and not again in what follows it. Over every
# generated position of the sampled requests:
#   tie:     how far the reference's top logit may lead the logit of the
#            token the engine chose there, in units of that position's
#            logits' standard deviation (0 where the engine chose the
#            reference's argmax); the largest over the positions
#   flips:   the share of the positions where the engine's token is not the
#            reference's argmax at all
#   logprob: the mean distance between the log-probability the engine gave
#            its token and the reference's of the same token, in the same
#            unit. Greedy continuations of seeded weights run into loops
#            whose top logit leads by whole deviations: there no fault flips
#            a token, and only the logits' own values show it
# "run": the largest reading over the seeds of the timed path in that
# precision; "control": the same requests held to the reference with every
# matmul operand rounded to the nearest precision below (float8_e4m3fn below
# bfloat16, bfloat16 below float32): it has to fail. Readings are in PERF.md
# section 6 (the chip, the cell's sizes) and
# tests/benchmark/test_benchmark_serve_moe.py (the CPU, the tiny size).
LIMITS = {"bfloat16": {"tie": 0.45, "flips": 0.15, "logprob": 0.03},
          "float32": {"tie": 2e-3, "flips": 0.02, "logprob": 1e-3}}

# the nearest precision below each: the control's
BELOW = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}

# requests of the window held to the reference: the one with the longest
# prompt (the window passed inside prefill) and seeded others
SAMPLE_REQUESTS = 4
# the trace opens at the window's first retirement — the request admitted in
# its place prefills inside the traced span, so the span holds mixed steps —
# or this far into the window (half of a shorter window) if nothing retires
TRACE_WAIT_CAP_S = 20.0


def model_config(m: dict, dtype: str):
    return MellumConfig.from_dict(m, dtype=dtype)


def build(m: dict, seed: int, tracer=None, engine_kw=None):
    """Weights from the seed and a warmed engine of the deployment's sizes.
    `engine_kw` is for the CPU rehearsals' tiny page geometry only."""
    from paddle_tpu.serving import ContinuousBatchingEngine

    dep = m["deployment"]
    kw = dict(engine_kw or {})
    dtype = jax.numpy.dtype(kw.get("dtype", "bfloat16"))
    kw["dtype"] = dtype
    cfg = model_config(m, dtype.name)
    p = init_serving_params(cfg, seed, dtype.name)
    jax.block_until_ready(p)
    if tracer is not None:
        kw["tracer"] = tracer
    block = kw.get("block_size", 64)
    eng = ContinuousBatchingEngine(
        cfg, p, slots=dep["slots"], max_prompt_len=dep["max_prompt_len"],
        max_new_tokens=dep["max_new_tokens"],
        token_budget=dep["token_budget"],
        # the full layers' pool and its scratch page; the window layers'
        # rings are sized by the engine from the window
        max_pages=dep["kv_pool_tokens"] // block + 1,
        # each token's log-probability rides out with it: `correct` reads it
        logprobs=True, **kw)
    ring = eng.metrics()["kv_ring_tokens"]
    if ring != dep["ring_tokens"]:
        raise SystemExit(f"benchmark: the engine's ring holds {ring} tokens, "
                         f"the configuration says {dep['ring_tokens']}")
    eng.warm()
    return eng, p, dtype.name


class Profile(tracing.Profile):
    """`tracing.Profile`, opened at the window's first retirement."""

    def __init__(self, on: bool, eng, seconds: float):
        super().__init__(on)
        self.eng, self.retired0 = eng, None
        self.wait_cap_s = min(TRACE_WAIT_CAP_S, seconds / 2)

    def tick(self, t: float) -> None:
        if self.state == "idle":
            if self.retired0 is None:
                self.retired0 = len(self.eng.finished)
            if len(self.eng.finished) == self.retired0 \
                    and t < self.wait_cap_s:
                return
        super().tick(t)


def sample_requests(measured, seed: int, k: int = SAMPLE_REQUESTS) -> list:
    """(prompt, tokens, logprobs) of `k` of the requests the window finished
    whole: the one with the longest prompt and seeded others."""
    whole = [r for r, n in measured
             if r.done and not r.failed and len(r.tokens) == n]
    if not whole:
        return []
    order = np.random.default_rng([int(seed), 33]).permutation(len(whole))
    longest = max(range(len(whole)), key=lambda i: len(whole[i].prompt))
    picks = [longest] + [int(i) for i in order if i != longest][:k - 1]
    return [(list(whole[i].prompt), list(whole[i].tokens),
             list(whole[i].logprobs)) for i in picks]


def padded(n: int) -> int:
    """A context of `n` tokens is padded to this many (causal: the padding
    changes nothing before it): the reference compiles once a length."""
    block = reference.QUERY_BLOCK if n > reference.QUERY_BLOCK else 64
    return -(-n // block) * block


def position_readings(m: dict, p: dict, samples, pad_to: int, faults=()):
    """(gaps, errs) at every generated position of `samples` under the
    reference (with `faults` planted, or inside `reference.lower_precision`),
    one forward pass over each sample's prompt and tokens: how far the
    reference's top logit leads the engine's token's, and how far the
    engine's log-probability of that token lies from the reference's, both
    in the logits' standard deviations."""
    gaps, errs = [], []
    for prompt, tokens, logprobs in samples:
        ids = prompt + tokens[:-1]
        got = reference.token_scores(
            m, p, ids + [0] * (pad_to - len(ids)), len(prompt) - 1, tokens,
            faults)
        gaps.append(got["gap"])
        errs.append(np.abs(np.asarray(logprobs, np.float64)
                           - got["logprob"]) / got["std"])
    return np.concatenate(gaps), np.concatenate(errs)


def readings(gaps, errs) -> dict:
    """{"tie", "flips", "logprob", "positions"} of `position_readings`."""
    if not (np.isfinite(gaps).all() and np.isfinite(errs).all()):
        return {"tie": float("inf"), "flips": 1.0, "logprob": float("inf"),
                "positions": len(gaps)}
    return {"tie": float(gaps.max()), "flips": float((gaps > 0).mean()),
            "logprob": float(errs.mean()), "positions": len(gaps)}


def over(reading: dict, limits: dict) -> list:
    """The names of the limits `reading` does not keep."""
    return [k for k, limit in limits.items() if not reading[k] < limit]


def check(m: dict, p: dict, samples, pad_to: int, limits: dict, say) -> list:
    """Hold the sampled requests to the reference; what failed."""
    if not samples:
        return ["the window finished no request to hold to the reference"]
    got = readings(*position_readings(m, p, samples, pad_to))
    say(f"check: {len(samples)} requests the window finished (prompts "
        f"{[len(q) for q, _, _ in samples]}, tokens "
        f"{[len(t) for _, t, _ in samples]}) against the f32 reference, "
        f"{got['positions']} positions: " + ", ".join(
            f"{k} {got[k]:.3e} (limit {limits[k]:g})" for k in limits))
    return [f"the engine's tokens against the f32 reference: {k} is "
            f"{got[k]:.3e}, over {limits[k]:g}" for k in over(got, limits)]


# engine counters the readers take as the window's deltas
COUNTERS = ("moe_layer_steps", "moe_rows_routed", "moe_experts_hit",
            "moe_load_max", "moe_layer_steps_decode",
            "moe_rows_routed_decode", "moe_experts_hit_decode",
            "window_tokens_dropped")
# engine gauges sampled once a scheduling step
GAUGES = ("kv_pages_full", "kv_pages_window", "kv_tokens_live",
          "kv_tokens_window")


def serve(cell, seed: int, seconds: float, trace: bool, say, engine_kw=None):
    """The cell's window, and what its checks of the run itself found:
    (the result's raw material, the profile, the failures so far, the
    weights, the precision, the reference's padded context length)."""
    from paddle_tpu.observability.trace import Tracer

    m, mix = cell["config"], cell["mix"]
    traffic = Traffic(mix, m["vocab_size"], seed)
    dep = m["deployment"]
    if traffic.max_prompt_tokens > dep["max_prompt_len"] \
            or traffic.max_output_tokens > dep["max_new_tokens"]:
        raise SystemExit("benchmark: the mix's longest request does not fit "
                         "the deployment's max_prompt_len / max_new_tokens")
    tracer = Tracer(capacity=1 << 20) if trace else None
    eng, p, dtype = build(m, seed, tracer, engine_kw)
    em = eng.metrics()
    wc = em["warm_compile_stats"]
    say(f"serve_moe: step={'unified' if em['unified_step'] else 'split'} "
        f"token_budget={em['token_budget']} steps_per_sync={eng.steps} "
        f"block_size={eng.block_size} kv={em['kv_cache_dtype']} full pages="
        f"{em['n_cacheable_pages']} ring={em['kv_ring_tokens']} tokens x "
        f"{dep['slots'] + 1}; prefix cache: "
        f"{em['prefix_cache_off'] or 'on'}; warm(): "
        f"{wc['compile_requests']} programs, {wc['cache_hits']} from the "
        f"cache in {wc['persistent_cache_dir']}")
    # the pools' occupancy once a scheduling step, through the engine's own
    # account of itself (drive() picks `eng.step` up from the instance)
    samples, step = [], eng.step

    def sampled_step():
        n = step()
        now = eng.metrics()
        samples.append([time.perf_counter()] + [now[k] for k in GAUGES])
        return n

    eng.step = sampled_step
    before, c0 = eng.compile_stats(), {k: em[k] for k in COUNTERS}
    profile = Profile(trace, eng, seconds)
    raw = drive(eng, traffic, seconds, profile, say)
    profile.close()
    after, em = eng.compile_stats(), eng.metrics()
    bad = []
    if after != before:
        bad.append(f"programs compiled inside the window: {before} -> "
                   f"{after}")
    wrong = [r.req_id for r, n in raw["measured"]
             if r.failed or not r.done or len(r.tokens) != n]
    if wrong:
        bad.append(f"requests {wrong[:8]} did not finish with their token "
                   f"count")
    gauges = np.asarray(samples, float)
    at, gauges = gauges[:, 0] - raw["t0"], gauges[:, 1:]
    in_window = at >= 0
    say(f"serve_moe: pools over {int(in_window.sum())} steps: full pages "
        f"{gauges[in_window, 0].min():.0f}-{gauges[in_window, 0].max():.0f}, "
        f"window pages {gauges[in_window, 1].min():.0f}-"
        f"{gauges[in_window, 1].max():.0f} (of "
        f"{(dep['slots'] + 1) * dep['ring_tokens'] // eng.block_size}), live "
        f"tokens {gauges[in_window, 2].min():.0f}-"
        f"{gauges[in_window, 2].max():.0f}")

    def mean(rows):
        return {k: float(v) for k, v in zip(GAUGES, gauges[rows].mean(0))} \
            if rows.any() else None

    # the device-trace readers take the gauges of the steps the trace holds
    traced = in_window & (at >= profile.t_start) & (
        at < (profile.t_stop or float("inf"))) \
        if profile.t_start is not None else np.zeros(len(at), bool)
    raw.update(spans=tracer.events() if tracer is not None else [],
               steps_per_sync=eng.steps, slots=dep["slots"],
               moe={k: em[k] - c0[k] for k in COUNTERS},
               gauges=mean(in_window), gauges_traced=mean(traced),
               wrong=len(wrong))
    pad_to = padded(traffic.max_prompt_tokens + traffic.max_output_tokens)
    return raw, profile, bad, p, dtype, pad_to


def run(cell, seed: int, seconds: float, trace: bool, say, engine_kw=None):
    raw, profile, bad, p, dtype, pad_to = serve(cell, seed, seconds, trace,
                                                say, engine_kw)
    bad += check(cell["config"], p, sample_requests(raw["measured"], seed),
                 pad_to, LIMITS[dtype], say)
    for line in bad:
        say(f"check: FAILED: {line}")
    return {"correct": not bad, "attempted": len(raw["measured"]),
            "failed": raw["wrong"], "end_to_end": end_to_end(raw),
            "raw": raw, "profile": profile}
