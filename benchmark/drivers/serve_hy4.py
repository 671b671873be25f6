"""Serving cells of a model of learned sparse latent attention, hyper-connection
streams and a held share of sigmoid-routed experts: `ContinuousBatchingEngine
(cfg, params, <sizes>, logprobs=True)` over a `HyV4Config`, every tuning
option at the program's default but the page size, driven by the cell's
traffic through `drivers/serve.py`'s own loop (`drive`, `end_to_end`). The
mix's lengths come in the order it names (`benchmark/traffic_golden.py`).

`correct` follows `drivers/serve_moe.py`: it is decided AFTER the window, on
the longest requests the window finished (`SAMPLE_REQUESTS`, every token): one
forward pass of the plain reference (`benchmark/reference_hy_v4.py`) over a
request's prompt and the engine's own tokens, held against the tokens and
log-probabilities the engine handed out, under `LIMITS`, with a lower-precision
control and planted faults that must fail it (`benchmark/serve_hy4_faults.py`
at the cell's sizes; the tests at the tiny size; PERF.md has the readings).
At the cell's sizes the comparison is blind to a dropped sink (one logit
beside 2,048 keys), to a selection one key wider or narrower and to a
`shared` layer that selects for itself instead of reusing the `full` layer's
choice: bfloat16 moves the log-probabilities more than any of them (PERF.md
section 6). The tiny tests see all three.

Before the reference runs, the engine's pools are let go and the weights moved
to the host: the reference puts one layer's weights at a time on the device.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference_hy_v4 as reference
from benchmark import tracing, traffic_golden
from benchmark.drivers import serve as serve_loop
from benchmark.drivers import serve_moe
from benchmark.drivers.serve import drive, end_to_end
from benchmark.drivers.serve_moe import over, readings
# at import, so that a program without the model fails here, at once
from paddle_tpu.models import HyV4Config
from paddle_tpu.models.hy_v4 import init_serving_params

# The limits of `correct`, by the precision the run states, over every
# generated position of the sampled requests (drivers/serve_moe.py says what
# each reads): "tie" the largest lead of the reference's top logit over the
# engine's token's, "flips" the share of positions where the engine's token
# is not the reference's argmax, "logprob" the mean distance of the engine's
# log-probability from the reference's, both in the logits' deviations. Each
# lies between the largest reading of sound seeds and the lower-precision
# control's (float8_e4m3fn below bfloat16, bfloat16 below float32): PERF.md
# section 6 (the chip, the cell's sizes) and
# tests/test_serve_hy4_correct.py (the CPU, the tiny size). The
# float32 engine stores its latent rows in bfloat16 (the kernels' packing),
# so its limits are a bfloat16 cache's.
LIMITS = {"bfloat16": {"tie": 4.0, "flips": 0.4, "logprob": 0.3},
          "float32": {"tie": 1.0, "flips": 0.05, "logprob": 0.015}}

# the nearest precision below each: the control's
BELOW = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}

# requests of the window held to the reference: the longest it finished
SAMPLE_REQUESTS = 2
# the window opens when every slot is live or at this cap: 24 prompts of
# ~12k tokens are ~150 prefill windows of 2,048 (~60 s), and the first
# requests retire before the last is admitted, so the slots may never all
# be live at once; `serve_loop.FILL_CAP_S` would open the window half full
FILL_CAP_S = 90.0


class Profile(tracing.Profile):
    """`tracing.Profile`, opened at the first program after TRACE_START_S
    that finds every slot live (or at `serve_moe.TRACE_WAIT_CAP_S`): no
    prompt can be prefilling then, so the traced seconds hold decode programs
    up to the next retirement and the windows of the prompt it admits. Under
    the fixed order of the lengths the trace falls on the same programs under
    every seed; opened at a retirement (`serve_moe.Profile`) they were the
    back-to-back windows of two prompts, and no decode program."""

    def __init__(self, on: bool, eng, seconds: float):
        super().__init__(on)
        self.eng = eng
        self.wait_cap_s = min(serve_moe.TRACE_WAIT_CAP_S, seconds / 2)

    def tick(self, t: float) -> None:
        if self.state == "idle" and self.eng.n_active < self.eng.slots \
                and t < self.wait_cap_s:
            return
        super().tick(t)


def model_config(m: dict, dtype: str) -> HyV4Config:
    """The configuration file's keys as the engine builds them: the router
    keeps its published width, the chip holds `deployment.held`."""
    return HyV4Config.from_dict(
        {**m, "n_routed_experts": m["published"]["n_routed_experts"]},
        held=tuple(m["deployment"]["held"]), dtype=dtype)


def build(m: dict, seed: int, tracer=None, engine_kw=None):
    """Weights from the seed and a warmed engine of the deployment's sizes.
    `engine_kw` is for the CPU rehearsals' tiny page geometry only."""
    from paddle_tpu.serving import ContinuousBatchingEngine

    dep = m["deployment"]
    kw = dict(engine_kw or {})
    dtype = jnp.dtype(kw.get("dtype", "bfloat16"))
    kw["dtype"] = dtype
    block = kw.pop("block_size", dep["block_size"])
    cfg = model_config(m, dtype.name)
    p = init_serving_params(cfg, seed, dtype.name)
    jax.block_until_ready(p)
    if tracer is not None:
        kw["tracer"] = tracer
    eng = ContinuousBatchingEngine(
        cfg, p, slots=dep["slots"], max_prompt_len=dep["max_prompt_len"],
        max_new_tokens=dep["max_new_tokens"],
        token_budget=dep["token_budget"], block_size=block,
        prompt_bucket=kw.pop("prompt_bucket", block),
        # the pool and its scratch page
        max_pages=dep["kv_pool_tokens"] // block + 1,
        # each token's log-probability rides out with it: `correct` reads it
        logprobs=True, **kw)
    eng.warm()
    return eng, p, dtype.name


def sample_requests(measured, k: int = SAMPLE_REQUESTS) -> list:
    """(prompt, tokens, logprobs) of the `k` longest requests (prompt and
    output) the window finished whole."""
    whole = [r for r, n in measured
             if r.done and not r.failed and len(r.tokens) == n]
    whole.sort(key=lambda r: (-(len(r.prompt) + len(r.tokens)), r.req_id))
    return [(list(r.prompt), list(r.tokens), list(r.logprobs))
            for r in whole[:k]]


def padded(n: int) -> int:
    """A context of `n` tokens padded to whole query blocks of the reference
    (causal: the padding changes nothing before it)."""
    block = reference.QUERY_BLOCK
    return -(-n // block) * block


def position_readings(m: dict, p: dict, samples, pad_to: int, faults=()):
    """(gaps, errs) at every generated position of `samples` under the
    reference (with `faults` planted, or inside `reference.lower_precision`):
    drivers/serve_moe.py's readings."""
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


def check(m: dict, p: dict, samples, pad_to: int, limits: dict,
          say) -> tuple:
    """Hold the sampled requests to the reference: (what failed, the
    readings)."""
    if not samples:
        return ["the window finished no request to hold to the reference"], {}
    got = readings(*position_readings(m, p, samples, pad_to))
    say(f"check: {len(samples)} requests the window finished (prompts "
        f"{[len(q) for q, _, _ in samples]}, tokens "
        f"{[len(t) for _, t, _ in samples]}) against the f32 reference, "
        f"{got['positions']} positions: " + ", ".join(
            f"{k} {got[k]:.3e} (limit {limits[k]:g})" for k in limits))
    return [f"the engine's tokens against the f32 reference: {k} is "
            f"{got[k]:.3e}, over {limits[k]:g}"
            for k in over(got, limits)], got


# engine counters the readers take as the window's deltas
COUNTERS = ("dsa_full_layer_steps", "dsa_keys_scored", "dsa_keys_attended",
            "dsa_full_layer_steps_decode", "dsa_keys_scored_decode",
            "dsa_keys_attended_decode", "moe_layer_steps", "moe_rows_routed",
            "moe_experts_hit", "moe_load_max", "moe_layer_steps_decode",
            "moe_rows_routed_decode", "moe_experts_hit_decode")
# engine gauges sampled once a scheduling step, for the log line
GAUGES = ("kv_pages_full", "kv_tokens_live")


def serve(cell, seed: int, seconds: float, trace: bool, say, engine_kw=None):
    """The cell's window, and what its checks of the run itself found:
    (the result's raw material, the profile, the failures so far, the
    weights, the precision, the reference's padded context length). The
    engine's pools are let go before this returns."""
    from paddle_tpu.observability.trace import Tracer

    m, mix = cell["config"], cell["mix"]
    traffic = traffic_golden.make(mix, m["vocab_size"], seed)
    dep = m["deployment"]
    if traffic.max_prompt_tokens > dep["max_prompt_len"] \
            or traffic.max_output_tokens > dep["max_new_tokens"]:
        raise SystemExit("benchmark: the mix's longest request does not fit "
                         "the deployment's max_prompt_len / max_new_tokens")
    tracer = Tracer(capacity=1 << 20) if trace else None
    eng, p, dtype = build(m, seed, tracer, engine_kw)
    em = eng.metrics()
    wc = em["warm_compile_stats"]
    say(f"serve_hy4: step={'unified' if em['unified_step'] else 'split'} "
        f"token_budget={em['token_budget']} steps_per_sync={eng.steps} "
        f"block_size={eng.block_size} pages={em['n_cacheable_pages']} "
        f"bytes a token: latent {em['kv_latent_token_bytes']} + index "
        f"{em['kv_index_token_bytes']}; prefix cache: "
        f"{em['prefix_cache_off'] or 'on'}; warm(): "
        f"{wc['compile_requests']} programs, {wc['cache_hits']} from the "
        f"cache in {wc['persistent_cache_dir']}")
    samples, step = [], eng.step

    def sampled_step():
        n = step()
        now = eng.metrics()
        samples.append([time.perf_counter()] + [now[k] for k in GAUGES])
        return n

    eng.step = sampled_step
    before, c0 = eng.compile_stats(), {k: em[k] for k in COUNTERS}
    profile = Profile(trace, eng, seconds)
    cap, serve_loop.FILL_CAP_S = serve_loop.FILL_CAP_S, FILL_CAP_S
    try:
        raw = drive(eng, traffic, seconds, profile, say)
    finally:
        serve_loop.FILL_CAP_S = cap
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
    say(f"serve_hy4: pool over {int(in_window.sum())} steps: pages "
        f"{gauges[in_window, 0].min():.0f}-{gauges[in_window, 0].max():.0f} "
        f"of {em['n_cacheable_pages']}, live tokens "
        f"{gauges[in_window, 1].min():.0f}-{gauges[in_window, 1].max():.0f}")
    raw.update(spans=tracer.events() if tracer is not None else [],
               steps_per_sync=eng.steps, slots=dep["slots"],
               moe={k: em[k] - c0[k] for k in COUNTERS}, wrong=len(wrong))
    # the pools go: the reference needs the room
    profile.eng = eng.kcs = eng.vcs = eng.p = None
    del eng
    gc.collect()
    pad_to = padded(traffic.max_prompt_tokens + traffic.max_output_tokens)
    return raw, profile, bad, p, dtype, pad_to


def run(cell, seed: int, seconds: float, trace: bool, say, engine_kw=None):
    raw, profile, bad, p, dtype, pad_to = serve(cell, seed, seconds, trace,
                                                say, engine_kw)
    samples = sample_requests(raw["measured"])
    host = jax.device_get(p)
    for a in jax.tree.leaves(p):
        a.delete()
    failed, got = check(cell["config"], host, samples, pad_to,
                        LIMITS[dtype], say)
    bad += failed
    for line in bad:
        say(f"check: FAILED: {line}")
    raw["check"] = got
    return {"correct": not bad, "attempted": len(raw["measured"]),
            "failed": raw["wrong"], "end_to_end": end_to_end(raw),
            "raw": raw, "profile": profile}
