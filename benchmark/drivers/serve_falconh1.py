"""Serving cells of a model whose every layer runs attention and a Mamba-2
mixer side by side: `ContinuousBatchingEngine(cfg, params, <sizes>,
logprobs=True)` over a `FalconH1Config`, every tuning option at the program's
default but the page size, driven by the cell's traffic through
`drivers/serve.py`'s own loop (`drive`, `end_to_end`). The mix's lengths come
in the order it names (`benchmark/traffic_golden.py`).

`correct` follows `drivers/serve_hy4.py`: it is decided AFTER the window, by
the plain reference (`benchmark/reference_falcon_h1.py`, the mixer's
recurrence token by token), under `LIMITS`, with a lower-precision control and
planted faults (`benchmark/serve_falconh1_faults.py` at the cell's sizes; the
tests at the tiny size; PERF.md has the readings). Two comparisons:

- the tokens: requests the window finished (`sample_requests`, every token),
  one reference pass over a request's prompt and the engine's own tokens,
  held against the log-probabilities and argmax flips of the tokens the
  engine handed out. Besides the longest requests, the sample takes those
  whose tokens come soonest after a point where the mixer's state starts
  anew: the shortest prompt, and the prompt that ends soonest after one of
  its windows past the first began.
- the state: when the window closes, the rows of two requests still decoding
  on rows an earlier request held (`held_rows`: the shortest context, and the
  prompt that ends soonest after a window's start) are copied out, and each
  head's S is held to the reference's after the same tokens, in every
  layer.

Before the reference runs, the engine's pools and state rows are let go; the
weights stay on the device and the reference casts one layer's up at a time.
"""
from __future__ import annotations

import collections
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference_falcon_h1 as reference
from benchmark import tracing, traffic_golden
from benchmark.drivers.serve import drive, end_to_end
from benchmark.drivers.serve_moe import over, readings
# at import, so that a program without the model fails here, at once
from paddle_tpu.models import FalconH1Config
from paddle_tpu.models.falcon_h1 import init_serving_params

# The limits of `correct`, by the precision the run states, over every
# generated position of the sampled requests (drivers/serve_moe.py says what
# each reads): "tie" the largest lead of the reference's top logit over the
# engine's token's, "flips" the share of positions where the engine's token
# is not the reference's argmax, "logprob" the mean distance of the engine's
# log-probability from the reference's, both in the logits' deviations;
# "state" the largest distance of a head's S from the reference's, over the
# reference's (Frobenius norms), in any layer of the held rows. Each
# lies between the largest reading of sound seeds and the lower-precision
# control's (float8_e4m3fn below bfloat16, bfloat16 below float32): PERF.md
# section 6 (the chip, the cell's sizes) and tests/test_falcon_h1.py (the
# CPU, the tiny size).
LIMITS = {"bfloat16": {"tie": 0.08, "flips": 0.06, "logprob": 0.015,
                       "state": 0.03},
          "float32": {"tie": 1e-3, "flips": 0.01, "logprob": 5e-6,
                      "state": 1e-4}}

# the nearest precision below each: the control's
BELOW = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}

# requests of the window held to the reference: this many of the longest it
# finished, then the two whose tokens come soonest after a fresh state
LONGEST = 2
SAMPLE_REQUESTS = LONGEST + 2

# engine counters sampled once a scheduling step: the readers take their
# change over the steps the trace holds
COUNTERS = ("ssm_decode_slot_steps", "ssm_scan_tokens", "ssm_scan_segments",
            "ssm_scan_carried")
# engine gauges sampled once a scheduling step: cached tokens and slots live
GAUGES = ("kv_tokens_live", "requests_active")


def model_config(m: dict, dtype: str) -> FalconH1Config:
    return FalconH1Config.from_dict(m, dtype=dtype)


def engine_args(m: dict) -> dict:
    """The engine's sizes as the deployment states them."""
    dep = m["deployment"]
    block = dep["block_size"]
    return dict(slots=dep["slots"], max_prompt_len=dep["max_prompt_len"],
                max_new_tokens=dep["max_new_tokens"],
                token_budget=dep["token_budget"], block_size=block,
                prompt_bucket=block,
                # the pool and its scratch page
                max_pages=dep["kv_pool_tokens"] // block + 1,
                # each token's log-probability rides out with it: `correct`
                # reads it
                logprobs=True)


def build(m: dict, seed: int, tracer=None, engine_kw=None):
    """Weights from the seed and a warmed engine of the deployment's sizes.
    `engine_kw` is for the CPU rehearsals (their precision) only."""
    from paddle_tpu.serving import ContinuousBatchingEngine

    kw = dict(engine_kw or {})
    dtype = jnp.dtype(kw.get("dtype", "bfloat16"))
    kw["dtype"] = dtype
    cfg = model_config(m, dtype.name)
    p = init_serving_params(cfg, seed, dtype.name)
    jax.block_until_ready(p)
    if tracer is not None:
        kw["tracer"] = tracer
    eng = ContinuousBatchingEngine(cfg, p, **engine_args(m), **kw)
    eng.warm()
    return eng, p, dtype.name


def last_window(prompt_len: int, token_budget: int) -> int:
    """The tokens of a prompt's last prefill window (windows of
    `token_budget` from its first token)."""
    return prompt_len - token_budget * ((prompt_len - 1) // token_budget)


def sample_requests(measured, token_budget: int) -> list:
    """(prompt, tokens, logprobs) of the requests the window finished whole
    that `correct` holds to the reference: the `LONGEST` longest (prompt and
    output), the one of the shortest prompt, and the one of a prompt past
    its first window whose last window is shortest (ties: the longer
    output)."""
    whole = [r for r, n in measured
             if r.done and not r.failed and len(r.tokens) == n]
    picks = sorted(whole, key=lambda r: (-(len(r.prompt) + len(r.tokens)),
                                         r.req_id))[:LONGEST]
    for key in (lambda r: len(r.prompt),
                lambda r: last_window(len(r.prompt), token_budget)
                if len(r.prompt) > token_budget else float("inf")):
        rest = [r for r in whole if all(r is not q for q in picks)]
        if rest:
            pick = min(rest, key=lambda r: (key(r), -len(r.tokens),
                                            r.req_id))
            if key(pick) != float("inf"):
                picks.append(pick)
    return [(list(r.prompt), list(r.tokens), list(r.logprobs))
            for r in picks]


def held_rows(eng, reused, token_budget: int) -> list:
    """(the tokens the state has read, the prompt's length, S of each layer
    [heads, d_state, d_head] f32) of two requests decoding when the window
    closes, each on a row an earlier request held (`reused`): the shortest
    context, and of the prompts past their first window the one that ends
    soonest after its last window began (ties: the lower request id)."""
    live = []
    for slot in eng._slots:
        r = slot.req
        if r is not None and r.tokens and r.state_row in reused:
            # the last token is sampled, not yet read
            live.append((r, list(r.prompt) + list(r.tokens[:-1])))
    picks = []
    for key in (lambda r, ids: len(ids),
                lambda r, ids: len(ids) - len(r.prompt) + last_window(
                    len(r.prompt), token_budget)
                if len(r.prompt) > token_budget else float("inf")):
        rest = [(r, ids) for r, ids in live
                if all(r is not q for q, _ in picks)]
        if rest:
            pick = min(rest, key=lambda x: (key(*x), x[0].req_id))
            if key(*pick) != float("inf"):
                picks.append(pick)
    return [(ids, len(r.prompt),
             [np.asarray(st[0][r.state_row]) for st in eng.states
              if st is not None])
            for r, ids in picks]


def padded(n: int) -> int:
    """A context of `n` tokens padded to whole query blocks of the reference
    (causal: the padding changes nothing before it)."""
    block = reference.QUERY_BLOCK
    return -(-n // block) * block


def state_readings(m: dict, p: dict, rows, pad_to: int, faults=()):
    """[layers, heads]: the distance of each head's S from the reference's
    (after the same tokens, with `faults` planted or inside
    `reference.lower_precision`) over the reference's, Frobenius norms, the
    largest over the held `rows`."""
    worst = 0.0
    for ids, prompt_len, held in rows:
        _, want = reference.hidden_states(
            m, p, ids + [0] * (pad_to - len(ids)), prompt_len, faults,
            upto=len(ids))
        got = np.stack(held).astype(np.float64)     # [layers, heads, N, P]
        ref = np.swapaxes(np.stack([np.asarray(w, np.float64)
                                    for w in want]), 2, 3)
        err = np.linalg.norm((got - ref).reshape(*ref.shape[:2], -1), axis=2) \
            / np.linalg.norm(ref.reshape(*ref.shape[:2], -1), axis=2)
        worst = np.maximum(worst, err)
    return worst


def reading(m: dict, p: dict, samples, rows, pad_to: int, faults=()) -> dict:
    """What `LIMITS` holds: `readings` of the sampled requests' tokens, with
    "state" (the largest of `state_readings`) and "state_by_layer" (each
    layer's largest)."""
    by_layer = state_readings(m, p, rows, pad_to, faults).max(1)
    return dict(readings(*position_readings(m, p, samples, pad_to, faults)),
                state=float(by_layer.max()),
                state_by_layer=[float(v) for v in by_layer])


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


def check(m: dict, p: dict, samples, rows, pad_to: int, limits: dict,
          say) -> tuple:
    """Hold the sampled requests and the held rows to the reference: (what
    failed, the readings)."""
    if not samples:
        return ["the window finished no request to hold to the reference"], {}
    if not rows:
        return ["no request decoding at the window's close held a row an "
                "earlier request held"], {}
    got = reading(m, p, samples, rows, pad_to)
    say(f"check: {len(samples)} requests the window finished (prompts "
        f"{[len(q) for q, _, _ in samples]}, tokens "
        f"{[len(t) for _, t, _ in samples]}) and the state rows of "
        f"{len(rows)} decoding (contexts {[len(i) for i, _, _ in rows]}, "
        f"prompts {[n for _, n, _ in rows]}) against the f32 reference, "
        f"{got['positions']} positions: " + ", ".join(
            f"{k} {got[k]:.3e} (limit {limits[k]:g})" for k in limits)
        + f"; state by layer {[float(f'{v:.3e}') for v in got['state_by_layer']]}")
    return [f"the engine against the f32 reference: {k} is {got[k]:.3e}, "
            f"over {limits[k]:g}" for k in over(got, limits)], got


def serve(cell, seed: int, seconds: float, trace: bool, say, engine_kw=None):
    """The cell's window, and what its checks of the run itself found:
    (the result's raw material, the profile, the failures so far, the
    weights, the precision, the reference's padded context length). The
    engine's pools and state rows are let go before this returns."""
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
    say(f"serve_falconh1: step={'unified' if em['unified_step'] else 'split'}"
        f" token_budget={em['token_budget']} steps_per_sync={eng.steps} "
        f"block_size={eng.block_size} pages={em['n_cacheable_pages']} state "
        f"rows {em['state_rows']} of {em['state_row_bytes']} B; pools and "
        f"rows {em['kv_pool_bytes'] / 2**30:.2f} GiB; prefix cache: "
        f"{em['prefix_cache_off'] or 'on'}; warm(): "
        f"{wc['compile_requests']} programs, {wc['cache_hits']} from the "
        f"cache in {wc['persistent_cache_dir']}")
    samples, step = [], eng.step

    def sampled_step():
        n = step()
        now = eng.metrics()
        samples.append([time.perf_counter()]
                       + [now[k] for k in GAUGES + COUNTERS])
        return n

    eng.step = sampled_step
    # how often each state row was handed out: `held_rows` takes rows an
    # earlier request held, where a row not zeroed would show
    handed, alloc = collections.Counter(), eng.mgr.alloc_state

    def counted_alloc():
        row = alloc()
        handed[row] += 1
        return row

    eng.mgr.alloc_state = counted_alloc
    before, c0 = eng.compile_stats(), {k: em[k] for k in COUNTERS}
    profile = tracing.Profile(trace)
    raw = drive(eng, traffic, seconds, profile, say)
    profile.close()
    after, em = eng.compile_stats(), eng.metrics()
    held = held_rows(eng, {row for row, n in handed.items() if n > 1},
                     dep["token_budget"])
    bad = []
    if after != before:
        bad.append(f"programs compiled inside the window: {before} -> "
                   f"{after}")
    wrong = [r.req_id for r, n in raw["measured"]
             if r.failed or not r.done or len(r.tokens) != n]
    if wrong:
        bad.append(f"requests {wrong[:8]} did not finish with their token "
                   f"count")
    rows = np.asarray(samples, float)
    at, gauges = rows[:, 0] - raw["t0"], rows[:, 1:1 + len(GAUGES)]
    counts = rows[:, 1 + len(GAUGES):]
    in_window = at >= 0

    def mean(rows):
        return {k: float(v) for k, v in zip(GAUGES, gauges[rows].mean(0))} \
            if rows.any() else None

    def counted(rows):
        """The counters' change over the steps `rows` marks (a run of
        them): from the step before the first to the last."""
        at_rows = np.flatnonzero(rows)
        if not len(at_rows):
            return None
        first, last = at_rows[0], at_rows[-1]
        start = counts[first - 1] if first else np.asarray(
            [c0[k] for k in COUNTERS], float)
        return {k: int(v) for k, v in zip(COUNTERS, counts[last] - start)}

    # the device-trace readers take the gauges and the counts of the steps
    # the trace holds
    traced = in_window & (at >= profile.t_start) & (
        at < (profile.t_stop or float("inf"))) \
        if profile.t_start is not None else np.zeros(len(at), bool)
    raw.update(spans=tracer.events() if tracer is not None else [],
               steps_per_sync=eng.steps, slots=dep["slots"],
               ssm=counted(in_window), ssm_traced=counted(traced),
               gauges=mean(in_window), gauges_traced=mean(traced),
               wrong=len(wrong), held_rows=held)
    say(f"serve_falconh1: over {int(in_window.sum())} steps: live tokens "
        f"{gauges[in_window, 0].min():.0f}-{gauges[in_window, 0].max():.0f}, "
        f"live slots {gauges[in_window, 1].min():.0f}-"
        f"{gauges[in_window, 1].max():.0f}; the layers counted "
        f"{raw['ssm']['ssm_scan_segments']} windows, "
        f"{raw['ssm']['ssm_scan_carried']} from a carried state")
    # the pools and state rows go: the reference needs the room
    eng.kcs = eng.vcs = eng.states = eng.p = None
    del eng
    gc.collect()
    pad_to = padded(traffic.max_prompt_tokens + traffic.max_output_tokens)
    return raw, profile, bad, p, dtype, pad_to


def run(cell, seed: int, seconds: float, trace: bool, say, engine_kw=None):
    raw, profile, bad, p, dtype, pad_to = serve(cell, seed, seconds, trace,
                                                say, engine_kw)
    samples = sample_requests(raw["measured"],
                              cell["config"]["deployment"]["token_budget"])
    failed, got = check(cell["config"], p, samples, raw.pop("held_rows"),
                        pad_to, LIMITS[dtype], say)
    bad += failed
    for line in bad:
        say(f"check: FAILED: {line}")
    raw["check"] = got
    return {"correct": not bad, "attempted": len(raw["measured"]),
            "failed": raw["wrong"], "end_to_end": end_to_end(raw),
            "raw": raw, "profile": profile}
