"""Long-context benchmark: seq 8192 train step + attention kernel on one
chip (SURVEY.md §5.7 — the axis this rebuild is chartered to leapfrog),
plus the long-context SERVING row (ISSUE 14): chunked prefill through
the unified ragged step vs the split engine's one-shot prefill —
decode TPOT p99 while a 2k-token prompt streams in.

Usage: python bench_longcontext.py [bs ...]   (default bs 1 2)
       python bench_longcontext.py serving [prompt_len]
       python bench_longcontext.py serving-cp [prompt_len]

Prints one JSON line per config:
- full train step (fwd+bwd+AdamW, per-layer remat) tok/s + MFU at
  seq 8192 on the 1B-class GQA config;
- the attention kernel's own TF/s at the 8k shape (fwd and fwd+bwd,
  grouped heads through the in-repo flash kernels), so the attention share
  of the step is explicit.

The multi-chip ring-attention path (parallel/ring_attention.py) cannot
be wall-clocked on one chip — its numerics at the 8k shape are asserted
on the virtual CPU mesh in tests/test_ring_attention.py; the single-chip
8k attention below is the flash kernel the ring degenerates to at
sep=1.
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def sync(x):
    return np.asarray(jax.tree.leaves(x)[0]).ravel()[0]


def attn_kernel_8k(bs: int):
    """Loop-slope timing with IN-DEVICE scalar reduction: a single timed
    call would also time the fixed per-call cost and the transfer of a
    ~33 MB gradient array back to the host (to re-check in the benchmark
    PR whether that still matters on a directly attached chip). The
    fori_loop body perturbs q by the carry so XLA cannot hoist it."""
    from paddle_tpu.kernels.flash_attention import flash_attention

    S, HQ, HK, D = 8192, 16, 4, 128
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(bs, S, HQ, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(bs, S, HK, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(bs, S, HK, D)), jnp.bfloat16)

    def loss(a):
        return jnp.sum(flash_attention(a, k, v,
                                       causal=True).astype(jnp.float32))

    def loss3(qq, kk, vv):
        return jnp.sum(flash_attention(qq, kk, vv,
                                       causal=True).astype(jnp.float32))

    # differentiate wrt q AND k AND v: a dq-only grad lets XLA drop the
    # dk/dv kernels while the 3.5x FLOPs convention counts all three —
    # the TF/s would overcount (round-5 fix; the first draft measured a
    # physically impossible 98% of peak)
    grad3 = jax.grad(loss3, argnums=(0, 1, 2))

    def grad_all(a):
        dq, dk, dv = grad3(a, k, v)
        return (jnp.sum(dq.astype(jnp.float32))
                + jnp.sum(dk.astype(jnp.float32))
                + jnp.sum(dv.astype(jnp.float32)))

    def timed(fn):
        @jax.jit
        def run(n, xx):
            def body(i, acc):
                return fn(xx + (acc * 1e-9).astype(xx.dtype))
            return jax.lax.fori_loop(0, n, body,
                                     jnp.zeros((), jnp.float32))
        lo, hi = 2, 62   # ~120+ ms of signal even at bs1
        float(run(lo, q)); float(run(hi, q))
        slopes = []
        for _ in range(6):
            t0 = time.perf_counter(); float(run(lo, q))
            tl = time.perf_counter() - t0
            t0 = time.perf_counter(); float(run(hi, q))
            th = time.perf_counter() - t0
            slopes.append(max(th - tl, 0.0) / (hi - lo))
        slopes.sort()
        return (slopes[2] + slopes[3]) / 2

    out = {}
    for name, fn, mult in (
            ("fwd", loss, 1.0),
            ("fwd+bwd", grad_all, 3.5)):
        t = timed(fn)
        # causal flash FLOPs: 0.5 * 4 * B * S^2 * Hq * D per fwd
        flops = 0.5 * 4 * bs * S * S * HQ * D * mult
        out[name] = {"ms": round(t * 1e3, 2),
                     "tf_s": round(flops / t / 1e12, 1)}
    return out


def train_step_8k(bs: int, recompute: bool = True):
    import paddle_tpu as paddle
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaPretrainingCriterion)
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import make_train_step

    seq = 8192
    cfg = LlamaConfig.llama_1b(dtype="bfloat16", recompute=recompute,
                               num_key_value_heads=4,
                               max_position_embeddings=seq)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    crit = LlamaPretrainingCriterion(cfg)

    def _decay(name):
        return "norm" not in name and not name.endswith(".b_0")

    optimizer = AdamW(learning_rate=1e-4, weight_decay=0.01,
                      apply_decay_param_fun=_decay,
                      parameters=model.parameters())
    step, params, opt = make_train_step(
        model, lambda lg, lb: crit(lg, lb), None, optimizer=optimizer)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, cfg.vocab_size, (bs, seq)))
    y = jnp.asarray(rng.integers(0, cfg.vocab_size, (bs, seq)))
    loss, params, opt = step(params, opt, x, y)
    float(loss)
    iters = 5
    t0 = time.perf_counter()
    for _ in range(iters):
        loss, params, opt = step(params, opt, x, y)
    float(loss)
    dt = (time.perf_counter() - t0) / iters
    tok_s = bs * seq / dt
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    # 6NF weight FLOPs + causal attention FLOPs (12*L*S^2*Hq*D per seq
    # fwd+bwd-with-remat ~ 4*3.5/2... keep the same 6N convention as
    # bench.py and report attention share separately)
    mfu = tok_s * 6 * n_params / 197e12
    return {"ms_step": round(dt * 1e3, 1), "tok_s": round(tok_s, 1),
            "mfu_6N": round(mfu, 3), "loss": round(float(loss), 3)}


def serving_chunked_prefill(prompt_len: int = 2048):
    """Long-context SERVING row (ISSUE 14): a `prompt_len`-token cold
    prompt lands while 7 slots stream steady decode — the head-of-line
    regime chunked prefill exists for. Served twice over the same 1B
    int8-weight engine shapes: the SPLIT program zoo (the whole prompt
    prefills in one bucketed call, every decode slot stalls behind it)
    vs the UNIFIED ragged step (the prompt streams through
    token-budget windows dispatched WITH the decode chunks). Reports
    decode TPOT percentiles (the p99 is the blocking number), the long
    prompt's TTFT, warmed program counts, and the unified window
    count."""
    from bench_util import hist_percentiles_ms
    from paddle_tpu.models import (LlamaConfig,
                                   init_quant_serving_params)
    from paddle_tpu.observability import MetricsRegistry
    from paddle_tpu.serving import ContinuousBatchingEngine

    cfg = LlamaConfig.llama_1b(dtype="bfloat16")
    p = init_quant_serving_params(cfg, "weight_only_int8", seed=0)
    np.asarray(jax.tree.leaves(p)[-1])
    bucket, block = 128, 64
    mpl = prompt_len + bucket
    # the long prompt buckets at ceil(prompt_len/bucket) — warm THAT,
    # or the split row compiles its prefill inside the timed run and
    # the TPOT comparison measures compile time, not scheduling
    long_bucket = -(-prompt_len // bucket) * bucket
    row = {"config": f"serving_chunked_prefill_{prompt_len}"}
    for name, unified in (("split", False), ("unified", True)):
        rng = np.random.default_rng(0)
        mt = MetricsRegistry()
        eng = ContinuousBatchingEngine(
            cfg, p, slots=8, prompt_bucket=bucket, max_prompt_len=mpl,
            max_new_tokens=64, block_size=block, steps_per_sync=8,
            prefill_batch=1, prefix_cache=False, unified_step=unified,
            token_budget=bucket, metrics=mt, tracer=False)
        eng.warm([bucket, long_bucket])
        for _ in range(7):
            eng.add_request(rng.integers(1, 32000, (48,)).tolist(),
                            max_new=64)
        for _ in range(2):   # decode reaches steady state first
            eng.step()
        long_req = eng.add_request(
            rng.integers(1, 32000, (prompt_len,)).tolist(), max_new=8)
        t0 = time.perf_counter()
        eng.run(max_iters=100000)
        row[name] = {
            "decode_tpot_ms": hist_percentiles_ms(
                mt.histogram("tpot_s")),
            "long_ttft_s": round(long_req.prefill_time
                                 - long_req.arrival_time, 3),
            "wall_s": round(time.perf_counter() - t0, 2),
            "n_programs": len(eng.compile_stats()),
            "prefill_chunks": eng.metrics()["prefill_chunks"],
        }
        del eng
    sp = (row["split"]["decode_tpot_ms"] or {}).get("p99")
    up = (row["unified"]["decode_tpot_ms"] or {}).get("p99")
    if sp and up:
        row["tpot_p99_gain"] = round(sp / up, 3)
        row["tpot_p99_improved"] = bool(up < sp)
    return row


def serving_cp_sweep(prompt_len: int = 4096):
    """Context-parallel serving leg (ISSUE 18): the same long-prompt
    trace over cp=1/2/4 PAGE-sharded engines (FLAGS_serving_cp) at a
    per-chip `kv_pool_bytes` budget HALVED against what one request
    needs — sized so the cp=1 build provably cannot hold the context
    (its capacity check raises, and the row records that error as the
    wall) while cp>=2 serves it from the same per-chip bytes. Served
    rows carry tok_s, the cp-merge wire bytes per decoded token
    (m/l/acc partials crossing chips — never the KV), and the three
    static-auditor `predicted_*` twins, so the silicon run lands an
    estimate/actual ratio per cp. cp degrees beyond the local device
    count emit a skipped-row note instead of failing the sweep."""
    from paddle_tpu.models import (LlamaConfig,
                                   init_quant_serving_params)
    from paddle_tpu.serving import ContinuousBatchingEngine

    cfg = LlamaConfig.llama_1b(dtype="bfloat16")
    p = init_quant_serving_params(cfg, "weight_only_int8", seed=0)
    np.asarray(jax.tree.leaves(p)[-1])
    bucket, block, max_new = 128, 64, 32
    mpl = prompt_len + bucket
    long_bucket = -(-prompt_len // bucket) * bucket
    # one full request's pages (the engine's own capacity formula:
    # a full-length prompt plus its new tokens, ceil per block) — the
    # per-chip budget buys HALF that, so cp=1 (fleet pages == per-chip
    # pages) fails its `cap + 2` admission floor by construction and
    # cp=2 (fleet = 2x per-chip) clears it from identical bytes
    cap = -(-(mpl + max_new) // block)
    from paddle_tpu.models.llama import PagedKVManager
    page_bytes = PagedKVManager.page_bytes(
        block, n_layers=cfg.num_hidden_layers,
        num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim)
    budget = ((cap + 3) // 2) * page_bytes
    row = {"config": f"serving_cp_{prompt_len}",
           "kv_pool_bytes_per_chip": budget,
           "one_request_pages": cap}
    n_dev = len(jax.devices())
    for cp in (1, 2, 4):
        key = f"cp{cp}"
        if cp > n_dev:
            row[key] = {"skipped":
                        f"needs {cp} devices, found {n_dev}"}
            continue
        rng = np.random.default_rng(0)
        try:
            eng = ContinuousBatchingEngine(
                cfg, dict(p), slots=4, prompt_bucket=bucket,
                max_prompt_len=mpl, max_new_tokens=max_new,
                block_size=block, steps_per_sync=8, prefill_batch=1,
                prefix_cache=False, serving_cp=cp,
                kv_pool_bytes=budget, tracer=False)
        except ValueError as e:
            # the acceptance wall: this per-chip pool cannot hold the
            # context at this cp degree
            row[key] = {"oom_build": str(e)[:200]}
            continue
        eng.warm([bucket, long_bucket])
        eng.add_request(rng.integers(1, 32000, (prompt_len,)).tolist(),
                        max_new=max_new)
        for _ in range(2):
            eng.add_request(rng.integers(1, 32000, (48,)).tolist(),
                            max_new=max_new)
        t0 = time.perf_counter()
        eng.run(max_iters=100000)
        wall = time.perf_counter() - t0
        toks = sum(len(r.tokens) for r in eng.finished)
        graphs = eng._traced_inventory()
        mem = eng.audit_memory(graphs=graphs)
        com = eng.audit_comms(graphs=graphs)
        roof = eng.audit_roofline(graphs=graphs)
        dec = com["programs"].get("decode", {})
        # the cp merge is every wire byte on a cp-containing axis of
        # the decode chunk; a chunk decodes steps_per_sync tokens for
        # each slot
        merge = sum(b for a, b in dec.get("per_axis", {}).items()
                    if "cp" in a.split(","))
        row[key] = {
            "tok_s": round(toks / wall, 2),
            "wall_s": round(wall, 2),
            "merge_wire_bytes_per_token":
                round(merge / max(eng.steps * eng.slots, 1), 1),
            "predicted_bytes_on_wire_per_token":
                com["predicted_bytes_on_wire_per_token"],
            "predicted_peak_hbm_bytes": mem["fleet_peak_hbm_bytes"],
            "predicted_step_ms": roof["predicted_step_ms"],
            "predicted_mfu": roof["predicted_mfu"],
            "fleet_pages": eng.mgr.max_pages,
            "kv_pool_bytes_per_chip": eng.mgr.kv_pool_bytes(),
        }
        del eng
    return row


if __name__ == "__main__":
    from paddle_tpu.serving.compile_cache import enable_compile_cache

    enable_compile_cache()   # the one decision where the cache lives
    # args: batch sizes, optionally suffixed "nr" for no-remat (the
    # bs4@2048 matrix lesson: fewer tokens in flight can drop remat);
    # "trainonly" skips the attention kernel sweep; "serving [len]"
    # runs ONLY the chunked-prefill serving row (ISSUE 14)
    args = sys.argv[1:] or ["1", "2"]
    if args and args[0] == "serving":
        plen = int(args[1]) if len(args) > 1 else 2048
        print(json.dumps(serving_chunked_prefill(plen)), flush=True)
        sys.exit(0)
    if args and args[0] == "serving-cp":
        plen = int(args[1]) if len(args) > 1 else 4096
        print(json.dumps(serving_cp_sweep(plen)), flush=True)
        sys.exit(0)
    train_only = "trainonly" in args
    for a in args:
        if a == "trainonly":
            continue
        nr = a.endswith("nr")
        bs = int(a[:-2] if nr else a)
        row = {"config": f"1b_gqa_seq8192_bs{bs}" + ("_noremat" if nr
                                                     else "")}
        if not train_only:
            row["attention"] = attn_kernel_8k(bs)
        try:
            row["train"] = train_step_8k(bs, recompute=not nr)
        except Exception as e:
            msg = str(e)
            oom = any(m in msg for m in (
                "RESOURCE_EXHAUSTED", "Allocation type: HLO temp",
                "out of memory", "exceeds the limit"))
            row["train"] = {"oom": True} if oom else {
                "error": f"{type(e).__name__}: {msg[:160]}"}
        print(json.dumps(row), flush=True)
    # the long-context SERVING story (ISSUE 14): chunked prefill keeps
    # decode TPOT flat while a long prompt streams in
    try:
        print(json.dumps(serving_chunked_prefill()), flush=True)
    except Exception as e:  # train rows stay useful without serving
        print(json.dumps({"config": "serving_chunked_prefill",
                          "error": f"{type(e).__name__}: "
                                   f"{str(e)[:160]}"}), flush=True)
    # the context-parallel ceiling lift (ISSUE 18): page-sharded pools
    # serve a depth the cp=1 per-chip pool provably cannot hold
    try:
        print(json.dumps(serving_cp_sweep()), flush=True)
    except Exception as e:
        print(json.dumps({"config": "serving_cp",
                          "error": f"{type(e).__name__}: "
                                   f"{str(e)[:160]}"}), flush=True)
