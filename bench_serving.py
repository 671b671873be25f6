"""Serving benchmark: quantized Llama decode on one chip.

Usage: python bench_serving.py CONFIG [CONFIG...] [--trace out.json]
  CONFIG: any key of CONFIGS ({7b,13b,1b}_{int8,int4}, llama3_8b_int8)
  plus `_paged` / `_paged_ragged` variants; each large config runs in
  its own process invocation (a 7B int8 + int4 pair would not co-reside
  in 16 GB HBM).
  --trace out.json (ISSUE 8): record every timed generate call as an
  observability span (per-config tracks) and export the chrome-trace/
  Perfetto JSON; each result row then embeds a `metrics` snapshot
  (generate-call latency histogram percentiles).

Loadgen mode (ISSUE 17): drive the fleet front-end with a timed
arrival process instead of steady-state slopes::

    python bench_serving.py --arrivals poisson:2,8,32 --workers 2
    python bench_serving.py --arrivals replay:trace.json

Each offered rate prints one JSON row: useful tok/s (tokens of
FINISHED requests over the serve wall time), shed rate, and router
TTFT/TPOT p99 per priority class — sweep rates to find the saturation
knee, the point where useful tok/s flattens while shed rate climbs.
``replay:FILE`` reads ``{"arrivals": [t..], "prompts": [[tok..]..]}``
(optional ``priorities``, ``max_new``) and replays the recorded
arrival clock.

Measures ms/decode-step by paired slope (bench_util.paired_slope_ms):
the program runs at max_new=2 and max_new=130, the step cost is the
MEDIAN over 8 adjacent-pair slopes (t_130 - t_2)/128 — prefill and
dispatch cancel in the slope, drift in the fixed cost cancels within a pair.
Weights are random, generated and quantized
ON DEVICE (models.llama.init_quant_serving_params), so no full-precision
model ever exists and nothing bulk-crosses the host link: this is the only
way a 7B (13.5 GB bf16) model fits next to its caches on a 16 GB chip.

Reference anchor: BASELINE config 3 (Llama-2-7B) + the weight-only
serving path of python/paddle/nn/quant/quantized_linear.py:180 under the
fused_multi_transformer generation loop.
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.analysis.device_specs import DEVICE_SPECS
from paddle_tpu.models import (LlamaConfig, PagedKVManager,
                               build_paged_generate, build_quant_generate,
                               init_quant_serving_params)

# ONE spec table (analysis/device_specs.py) owns the hardware numbers
# (ISSUE 13 hoist; value unchanged: v5e ~819 GB/s HBM)
HBM_GBS = DEVICE_SPECS["tpu-v5e"].hbm_gbs

CONFIGS = {
    "7b_int8": ("llama2_7b", "weight_only_int8"),
    "7b_int4": ("llama2_7b", "weight_only_int4"),
    "13b_int4": ("llama2_13b", "weight_only_int4"),  # capacity proof
    "13b_int8": ("llama2_13b", "weight_only_int8"),  # ~13.1 GB: tight
    "llama3_8b_int8": ("llama3_8b", "weight_only_int8"),  # GQA at scale
    "1b_int8": ("llama_1b", "weight_only_int8"),
    "1b_int4": ("llama_1b", "weight_only_int4"),
}

# paged-KV variants of the same serving stack (round-5 VERDICT #3:
# quote paged overhead vs the contiguous step). `_ragged` serves rows of
# different true lengths through the same compiled program.
PAGED_CONFIGS = {f"{k}_paged": v for k, v in CONFIGS.items()}
PAGED_CONFIGS.update({f"{k}_paged_ragged": v for k, v in CONFIGS.items()})


# decode-step slope over max_new (bench_util.paired_slope_ms: adjacent
# lo/hi pairs, median). Round-5 fix: the round-3/4 min-of-5 at a 64-step
# spread had a ~±0.5 ms/step noise floor — it once measured a paged
# config BELOW its weight-read bound, and it is the whole of the
# round-3→4 "1.11 → 1.33 ms drift" flagged in VERDICT.
MN_LO, MN_HI = 2, 130

# armed by --trace (observability, ISSUE 8): spans per timed generate
# call + a per-config latency histogram embedded in each result row
_TRACER = None
_METRICS = None


def _paired_slope_ms(run, pairs: int = 8):
    from bench_util import paired_slope_ms

    return paired_slope_ms(run, MN_LO, MN_HI, pairs)


def _timed_run(run, name: str):
    """Wrap the blocking generate call with a span + histogram sample
    when --trace armed the sinks; byte-identical callable otherwise."""
    if _TRACER is None and _METRICS is None:
        return run

    def wrapped(mn):
        t0 = time.perf_counter()
        out = run(mn)
        t1 = time.perf_counter()
        if _TRACER is not None:
            _TRACER.complete(f"generate:{name}", int(t0 * 1e9),
                             int(t1 * 1e9), max_new=int(mn))
        if _METRICS is not None:
            _METRICS.histogram(f"generate_call_s:{name}").observe(t1 - t0)
        return out

    return wrapped


def _row_metrics(name: str):
    """Percentile snapshot for one config's result row (None when
    --trace is off)."""
    if _METRICS is None:
        return None
    from bench_util import hist_percentiles_ms

    ms = hist_percentiles_ms(_METRICS.histogram(f"generate_call_s:{name}"))
    return None if ms is None else {"generate_call_ms": ms}


def quant_weight_gb(cfg, quant):
    """(capacity_gb, read_gb): total resident weights vs the bytes a
    decode step actually STREAMS. The embedding table is capacity but
    not read traffic — decode gathers B rows of it, the matmuls never
    touch it (roofline finding: with embed counted, the measured
    no-attention step beat the 'bound', i.e. the bound was wrong)."""
    h, im, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    L = cfg.num_hidden_layers
    nkv = cfg.num_key_value_heads
    proj = L * (2 * h * h + 2 * h * nkv * cfg.head_dim + 3 * h * im) \
        + h * v
    norms = (2 * L + 1) * h
    per = 1.0 if quant.endswith("int8") else 0.5
    read = (proj * per + norms * 2) / 2**30
    return read + v * h * 2 / 2**30, read


def run_config(name: str, b: int = 4, sb: int = 128):
    model_name, quant = CONFIGS[name]
    cfg = getattr(LlamaConfig, model_name)(dtype="bfloat16")
    t0 = time.perf_counter()
    p = init_quant_serving_params(cfg, quant, seed=0)
    # sync via device_get (block_until_ready would do as well: both are
    # true barriers on the v5e, chip_smoke.py PR 22)
    np.asarray(jax.tree.leaves(p)[-1])
    t_init = time.perf_counter() - t0
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (b, sb)))
    s0 = jnp.asarray(sb - 7, jnp.int32)  # exercise the bucket watermark
    key = jax.random.PRNGKey(0)
    one = jnp.asarray(1.0, jnp.float32)

    fns = {}
    for max_new in (MN_LO, MN_HI):
        fns[max_new] = jax.jit(build_quant_generate(cfg, b, sb, max_new))
        np.asarray(fns[max_new](p, ids, s0, key, one, one))  # compile
    ms_step = _paired_slope_ms(_timed_run(
        lambda mn: np.asarray(fns[mn](p, ids, s0, key, one, one)), name))
    tok_s = b / (ms_step / 1e3)
    gb, read_gb = quant_weight_gb(cfg, quant)
    bound_ms = read_gb * 2**30 / HBM_GBS * 1e3
    result = {
        "config": name, "ms_per_decode_step": round(ms_step, 3),
        "decode_tok_s": round(tok_s, 1),
        "weight_gb": round(gb, 2), "read_gb": round(read_gb, 2),
        "weight_read_bound_ms": round(bound_ms, 3),
        "bound_fraction": round(bound_ms / ms_step, 3),
        "init_s": round(t_init, 1), "batch": b,
    }
    m = _row_metrics(name)
    if m is not None:
        result["metrics"] = m
    print(json.dumps(result), flush=True)
    return result


def run_paged_config(name: str, b: int = 4, sb: int = 128,
                     block_size: int = 64):
    base = name.replace("_paged_ragged", "").replace("_paged", "")
    model_name, quant = CONFIGS[base]
    ragged = name.endswith("_ragged")
    cfg = getattr(LlamaConfig, model_name)(dtype="bfloat16")
    t0 = time.perf_counter()
    p = init_quant_serving_params(cfg, quant, seed=0)
    np.asarray(jax.tree.leaves(p)[-1])
    t_init = time.perf_counter() - t0
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (b, sb)))
    if ragged:  # rows of very different true lengths, one program
        s0_vec = jnp.asarray(
            np.linspace(sb // 4, sb, b).round().astype(np.int32))
    else:
        s0_vec = jnp.full((b,), sb - 7, jnp.int32)
    key = jax.random.PRNGKey(0)
    one = jnp.asarray(1.0, jnp.float32)

    fns, tbls = {}, {}
    for max_new in (MN_LO, MN_HI):
        total = sb + max_new
        mgr = PagedKVManager(b * -(-total // block_size), block_size)
        tbls[max_new], _ = mgr.tables_for_batch([total] * b)
        fns[max_new] = jax.jit(
            build_paged_generate(cfg, b, sb, max_new, block_size))
        np.asarray(fns[max_new](p, ids, s0_vec, tbls[max_new], key,
                                one, one))
    ms_step = _paired_slope_ms(_timed_run(
        lambda mn: np.asarray(fns[mn](p, ids, s0_vec, tbls[mn], key,
                                      one, one)), name))
    gb, read_gb = quant_weight_gb(cfg, quant)
    bound_ms = read_gb * 2**30 / HBM_GBS * 1e3
    result = {
        "config": name, "ms_per_decode_step": round(ms_step, 3),
        "decode_tok_s": round(b / (ms_step / 1e3), 1),
        "weight_gb": round(gb, 2), "read_gb": round(read_gb, 2),
        "weight_read_bound_ms": round(bound_ms, 3),
        "bound_fraction": round(bound_ms / ms_step, 3),
        "init_s": round(t_init, 1), "batch": b,
        "kv_block_size": block_size,
    }
    m = _row_metrics(name)
    if m is not None:
        result["metrics"] = m
    print(json.dumps(result), flush=True)
    return result


# ---------------------------------------------------------------------
# loadgen mode (ISSUE 17): trace-driven arrivals against the SLO router
# ---------------------------------------------------------------------

def _loadgen_trace(spec: str, n: int, max_new: int, seed: int, vocab: int):
    """One arrival trace: (arrival_offsets_s, prompts, priorities,
    max_new, offered_rate). `poisson:R` draws exponential interarrivals
    at R req/s; `replay:FILE` replays a recorded clock."""
    rng = np.random.default_rng(seed)
    kind, _, arg = spec.partition(":")
    if kind == "poisson":
        rate = float(arg)
        gaps = rng.exponential(1.0 / rate, n)
        arrivals = np.cumsum(gaps).tolist()
        prompts = [rng.integers(1, vocab, (int(rng.integers(3, 9)),))
                   .tolist() for _ in range(n)]
        prios = [("high", "normal", "low")[i % 3] for i in range(n)]
        return arrivals, prompts, prios, [max_new] * n, rate
    if kind == "replay":
        with open(arg) as f:
            doc = json.load(f)
        arrivals = [float(t) for t in doc["arrivals"]]
        prompts = [[int(t) for t in p] for p in doc["prompts"]]
        n = len(arrivals)
        prios = list(doc.get("priorities") or ["normal"] * n)
        mn = doc.get("max_new") or max_new
        mns = [int(mn)] * n if isinstance(mn, (int, float)) \
            else [int(v) for v in mn]
        span = arrivals[-1] - arrivals[0] if n > 1 else 1.0
        return arrivals, prompts, prios, mns, n / max(span, 1e-9)
    raise SystemExit(f"--arrivals must be poisson:RATE or replay:FILE, "
                     f"got {spec!r}")


def run_loadgen(argv):
    import argparse
    import dataclasses

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.serving import (ContinuousBatchingEngine, Fleet,
                                    Rejected, Router)

    ap = argparse.ArgumentParser(
        prog="python bench_serving.py --arrivals ...")
    ap.add_argument("--arrivals", required=True,
                    help="poisson:RATE[,RATE...] | replay:FILE")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--requests", type=int, default=24,
                    help="requests per offered rate (poisson mode)")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ttft-slo", type=float, default=None,
                    help="per-request TTFT budget handed to admission")
    ap.add_argument("--model", default="tiny")
    args = ap.parse_args(argv)

    cfg = getattr(LlamaConfig, args.model)()
    if args.model == "tiny":
        cfg = dataclasses.replace(cfg, num_key_value_heads=2)
    paddle.seed(args.seed)
    params = dict(LlamaForCausalLM(cfg).raw_state())

    def factory(*, metrics, tracer):
        return ContinuousBatchingEngine(
            cfg, params, slots=2, prompt_bucket=8, max_prompt_len=32,
            max_new_tokens=max(args.max_new, 4), block_size=8,
            steps_per_sync=2, metrics=metrics, tracer=tracer)

    kind, _, arg = args.arrivals.partition(":")
    specs = ([f"poisson:{r}" for r in arg.split(",")]
             if kind == "poisson" else [args.arrivals])
    rows = []
    for spec in specs:
        arrivals, prompts, prios, mns, rate = _loadgen_trace(
            spec, args.requests, args.max_new, args.seed,
            cfg.vocab_size)
        fleet = Fleet(factory, heartbeat_s=0.25)
        router = Router(fleet, max_queue=8)
        for _ in range(args.workers):
            fleet.add_worker()
        t0 = time.perf_counter()
        base = arrivals[0]
        results = []
        for t, p, pr, mn in zip(arrivals, prompts, prios, mns):
            delay = (t - base) - (time.perf_counter() - t0)
            if delay > 0:
                time.sleep(delay)
            results.append(router.submit(
                p, mn, priority=pr, ttft_deadline_s=args.ttft_slo))
            router.poll()
        router.join(timeout=600)
        wall = time.perf_counter() - t0
        fleet.stop()
        m = router.metrics()
        live = [r for r in results if not isinstance(r, Rejected)]
        useful_tokens = sum(len(r.tokens) for r in live
                            if r.state == "finished")
        row = {
            "bench": "serving_loadgen", "arrivals": spec,
            "workers": args.workers, "offered_req_s": round(rate, 3),
            "submitted": len(results), "finished": len(
                [r for r in live if r.state == "finished"]),
            "shed": len(results) - len(live),
            "shed_rate": round((len(results) - len(live))
                               / max(len(results), 1), 3),
            "shed_by_reason": {k: v for k, v
                               in m["shed_by_reason"].items() if v},
            "useful_tok_s": round(useful_tokens / wall, 2),
            "wall_s": round(wall, 2),
            "deadline_miss": m["deadline_miss"],
        }
        for p in ("high", "normal", "low"):
            for which in ("ttft", "tpot"):
                h = router.mt.histogram(f"router_{which}_s_{p}")
                if h.count:
                    row[f"{which}_p99_s_{p}"] = round(
                        h.percentile(99), 4)
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


if __name__ == "__main__":
    from paddle_tpu.serving.compile_cache import enable_compile_cache

    enable_compile_cache()   # the one decision where the cache lives
    args = sys.argv[1:]
    if "--arrivals" in args:
        run_loadgen(args)
        sys.exit(0)
    from bench_util import pop_trace_arg

    trace_path = pop_trace_arg(
        args, "usage: bench_serving.py CONFIG [CONFIG...] "
              "[--trace out.json]")
    if trace_path:
        from paddle_tpu.observability import MetricsRegistry, Tracer

        _TRACER = Tracer(capacity=1 << 18)
        _METRICS = MetricsRegistry()
    names = args or ["1b_int8"]
    for nm in names:
        if nm in PAGED_CONFIGS:
            run_paged_config(nm)
        else:
            run_config(nm)
    if _TRACER is not None:
        _TRACER.export(trace_path,
                       metadata={"bench": "bench_serving",
                                 "configs": names})
