"""Serving cells: `ContinuousBatchingEngine(cfg, params, <sizes>)` with every
tuning option at the program's default, driven by the traffic of the cell.

Copied from what PR 22 proved on the chip (chip_smoke.py's serve phase) and
from bench_continuous.py's driving loop: add what is due with
`arrival_time` = its due time, then one scheduling step.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import arith, reference
from benchmark.tracing import Profile, span
from benchmark.traffic import Traffic

# after an open-loop window closes the arrivals go on until every measured
# request has finished; one still unfinished after this long counts as failed
DRAIN_CAP_S = 60.0
# a closed-loop window opens at the latest this long after the clients start
FILL_CAP_S = 30.0
# tokens each of the two sample requests generates for the reference check:
# the first comes from the prefill lane, the 16th through the KV cache
SAMPLE_TOKENS = 16


def weight_shapes(m: dict) -> dict:
    """Name -> shape of every serving weight, as `raw_state()` names them."""
    h, f, v = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    out = {"llama.embed_tokens.weight": (v, h)}
    for i in range(m["num_hidden_layers"]):
        pre = f"llama.layers.{i}."
        out.update({
            pre + "self_attn.q_proj.weight": (h, q),
            pre + "self_attn.k_proj.weight": (h, kv),
            pre + "self_attn.v_proj.weight": (h, kv),
            pre + "self_attn.o_proj.weight": (q, h),
            pre + "mlp.gate_proj.weight": (h, f),
            pre + "mlp.up_proj.weight": (h, f),
            pre + "mlp.down_proj.weight": (f, h),
            pre + "input_layernorm.weight": (h,),
            pre + "post_attention_layernorm.weight": (h,)})
    out["llama.norm.weight"] = (h,)
    if not m["tie_word_embeddings"]:
        out["lm_head.weight"] = (h, v)
    return out


def make_weights(m: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Every weight made on the device from the seed, in the type it is served
    in: Xavier-normal matrices (the model's own initializer's scale), norm
    scales 1. One jitted program makes a layer and is called once per layer
    (all layers in one program is 159 MB of generated code: the machine keeps
    192 MiB of compile cache); a second makes the embedding, norm and head."""
    def fill(shapes, key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            if len(shape) == 1:
                out[name] = jnp.ones(shape, dtype)
                continue
            std = math.sqrt(2.0 / (shape[0] + shape[1]))
            out[name] = (std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)).astype(dtype)
        return out

    shapes = weight_shapes(m)
    pre0 = "llama.layers.0."
    layer = {k[len(pre0):]: v for k, v in shapes.items()
             if k.startswith(pre0)}
    rest = {k: v for k, v in shapes.items()
            if not k.startswith("llama.layers.")}
    make_layer = jax.jit(lambda key: fill(layer, key))
    # the seed may need more than 32 bits: fold its two halves in
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF, impl="rbg"),
                             seed >> 32)
    out = jax.jit(lambda key: fill(rest, key))(jax.random.fold_in(key, 0))
    for i in range(m["num_hidden_layers"]):
        made = make_layer(jax.random.fold_in(key, i + 1))
        out.update({f"llama.layers.{i}.{k}": v for k, v in made.items()})
    return out


def llama_config(m: dict, dtype: str = "bfloat16"):
    from paddle_tpu.models import LlamaConfig

    if m["hidden_size"] != m["head_dim"] * m["num_attention_heads"]:
        raise SystemExit("benchmark: LlamaConfig derives head_dim as hidden "
                         "/ heads; this configuration's differs")
    return LlamaConfig(**{k: m[k] for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "max_position_embeddings", "rms_norm_eps", "rope_theta",
        "tie_word_embeddings")}, dtype=dtype)


def build(m: dict, seed: int, tracer=None, engine_kw=None):
    """Weights from the seed and a warmed engine of the deployment's sizes.
    `engine_kw` is for the CPU rehearsals' tiny page geometry only: the
    command line never passes it."""
    from paddle_tpu.serving import ContinuousBatchingEngine

    dep = m["deployment"]
    kw = dict(engine_kw or {})
    dtype = jnp.dtype(kw.get("dtype", jnp.bfloat16))
    p = make_weights(m, seed, dtype)
    jax.block_until_ready(p)
    if tracer is not None:
        kw["tracer"] = tracer
    eng = ContinuousBatchingEngine(
        llama_config(m, dtype.name), p, slots=dep["slots"],
        max_prompt_len=dep["max_prompt_len"],
        max_new_tokens=dep["max_new_tokens"],
        kv_pool_bytes=dep["kv_pool_tokens"] * arith.kv_bytes_per_token(
            m, dtype.itemsize), **kw)
    eng.warm()
    return eng, p


def check_samples(eng, m: dict, p: dict, traffic: Traffic, say) -> list:
    """Serve two seeded sample requests and hold the first token (prefill
    lane) and the 16th (through the KV cache) to the f32 reference. Returns
    the failures as strings."""
    pad_to = -(-(traffic.max_prompt_tokens + SAMPLE_TOKENS) // 64) * 64
    prompts = [traffic.request(i)[0] for i in (0, 1)]
    reqs = [eng.add_request(q, max_new=SAMPLE_TOKENS) for q in prompts]
    eng.run()
    bad = []
    for q, r in zip(prompts, reqs):
        if r.failed or len(r.tokens) != SAMPLE_TOKENS:
            bad.append(f"sample request {r.req_id}: {len(r.tokens)} of "
                       f"{SAMPLE_TOKENS} tokens, failed={r.failed}")
            continue
        for n in (1, SAMPLE_TOKENS):
            ctx = q + r.tokens[:n - 1]
            ref = np.asarray(reference.reference_last_logits(
                m, p, ctx + [0] * (pad_to - len(ctx)), len(ctx)))
            gap = reference.tie_gap(ref, r.tokens[n - 1]) \
                if np.isfinite(ref).all() else float("inf")
            say(f"check: sample {r.req_id} (prompt {len(q)}, "
                f"{r.cached_tokens} cached) token {n}: engine "
                f"{r.tokens[n - 1]}, reference argmax {int(ref.argmax())}, "
                f"engine's choice trails by {gap:.3f} std "
                f"(tolerance {reference.BF16_TIE_TOL})")
            if not gap < reference.BF16_TIE_TOL:
                bad.append(f"sample request {r.req_id} token {n} trails the "
                           f"f32 reference's argmax by {gap:.3f} std")
    return bad


def _counters(eng) -> dict:
    em = eng.metrics()
    return {k: em[k] for k in ("device_steps", "prefill_chunks",
                               "prefix_hit_tokens", "prompt_tokens")}


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


def drive(eng, traffic: Traffic, seconds: float, profile: Profile, say,
          first: int = 0) -> dict:
    """Offer the cell's traffic, starting at request `first`, and measure a
    window of `seconds`. Returns the raw material of every metric: the
    measured requests, window times, token counts, counters."""
    # run() makes the same choice between its two step functions
    step = eng._pipeline_step if eng.double_buffer else eng.step
    reqs, out = [], {}

    def issue(i: int, arrival: float):
        prompt, n_out = traffic.request(i)
        r = eng.add_request(prompt, max_new=n_out, arrival_time=arrival)
        reqs.append((r, n_out))
        open_.append(r)

    def tokens_so_far() -> int:
        return sum(len(r.tokens) for r, _ in reqs)

    open_, kv_samples = [], []

    def sample_live_kv():
        # cached tokens of the requests that hold a slot, once a step
        open_[:] = [r for r in open_ if not r.done]
        kv_samples.append(sum(len(r.prompt) + len(r.tokens)
                              for r in open_ if r.slot is not None))

    nxt = first
    if traffic.kind == "closed":
        clients = int(traffic.mix["arrivals"]["clients"])
        for _ in range(clients):
            issue(nxt, time.perf_counter())
            nxt += 1
        seen, t0, t_fill = len(eng.finished), None, time.perf_counter()
        while True:
            with span("step"):
                step()
            now = time.perf_counter()
            n_done = len(eng.finished) - seen
            seen += n_done
            with span("add_request"):
                for _ in range(n_done):
                    issue(nxt, now)
                    nxt += 1
            if t0 is None:
                # the window opens when every slot is live for the first
                # time (retirements can outpace the one-request prefill lane
                # for a while: FILL_CAP_S bounds the wait)
                if eng.n_active == eng.slots or now - t_fill >= FILL_CAP_S:
                    t0, tok0, c0 = now, tokens_so_far(), _counters(eng)
                continue
            profile.tick(now - t0)
            sample_live_kv()
            if now - t0 >= seconds:
                break
        t1 = time.perf_counter()
        out.update(t0=t0, t1=t1, tokens=tokens_so_far() - tok0,
                   live_kv_tokens=sum(kv_samples) / len(kv_samples),
                   counters=_delta(c0, _counters(eng)),
                   measured=[(r, n) for r, n in reqs
                             if r.done and r.finish_time > t0],
                   unfinished=0)
        return out

    # open loop: requests are due on the schedule whatever the engine does
    lead = traffic.lead_in_s
    start = time.perf_counter()
    late, c0, c1 = [], None, None
    measured, pending = [], []
    while True:
        now = time.perf_counter() - start
        with span("add_request"):
            while traffic.due(nxt - first) <= now:
                due = traffic.due(nxt - first)
                issue(nxt, start + due)
                late.append(now - due)
                if lead <= due < lead + seconds:
                    measured.append(reqs[-1])
                    pending.append(reqs[-1][0])
                nxt += 1
        if c0 is None and now >= lead:
            c0, tok0 = _counters(eng), tokens_so_far()
            out["backlog"] = []
        if c0 is not None and c1 is None:
            profile.tick(now - lead)
            sample_live_kv()
            # backlog (waiting + prefilling + live) at the window's middle
            # and end: a queue that grows through the window is past the knee
            if len(out["backlog"]) == 0 and now >= lead + seconds / 2 \
                    or len(out["backlog"]) == 1 and now >= lead + seconds:
                out["backlog"].append(len(open_))
            if now >= lead + seconds:
                c1, tok1 = _counters(eng), tokens_so_far()
        if c1 is not None:
            pending = [r for r in pending if not r.done]
            if not pending or now >= lead + seconds + DRAIN_CAP_S:
                break
        if not eng.has_work:
            with span("wait_for_arrival"):
                time.sleep(max(0.0, min(
                    0.002, traffic.due(nxt - first) - now)))
            continue
        with span("step"):
            step()
    out.update(t0=start + lead, t1=start + lead + seconds,
               tokens=tok1 - tok0, counters=_delta(c0, c1),
               live_kv_tokens=sum(kv_samples) / len(kv_samples),
               measured=measured, unfinished=len(pending),
               lateness_s=late)
    say(f"load: generator ran late by p50 "
        f"{arith.median(late) * 1e3:.2f} ms, max {max(late) * 1e3:.2f} ms "
        f"over {len(late)} arrivals; backlog at the window's middle and end "
        f"{out['backlog']}; drained {time.perf_counter() - out['t1']:.1f} s "
        f"after the window")
    return out


def end_to_end(raw: dict) -> dict:
    """Every end-to-end serving metric this run can give; BENCHMARK.json
    says which of them the cell reports."""
    ok = [(r, n) for r, n in raw["measured"]
          if r.done and not r.failed and len(r.tokens) == n]
    out = {"output_tok_s": raw["tokens"] / (raw["t1"] - raw["t0"])}
    ttft = [(r.prefill_time - r.arrival_time) * 1e3 for r, _ in ok]
    tpot = [(r.finish_time - r.prefill_time) / (n - 1) * 1e3
            for r, n in ok if n > 1]
    if ttft:
        out["ttft_p90_ms"] = arith.percentile(ttft, 90)
    if tpot:
        out["tpot_p90_ms"] = arith.percentile(tpot, 90)
    return out


def run(cell, seed: int, seconds: float, trace: bool, say, engine_kw=None):
    from paddle_tpu.observability.trace import Tracer

    m, mix = cell["config"], cell["mix"]
    traffic = Traffic(mix, m["vocab_size"], seed)
    dep = m["deployment"]
    if traffic.max_prompt_tokens > dep["max_prompt_len"] \
            or traffic.max_output_tokens > dep["max_new_tokens"]:
        raise SystemExit("benchmark: the mix's longest request does not fit "
                         "the deployment's max_prompt_len / max_new_tokens")
    tracer = Tracer(capacity=1 << 20) if trace else None
    eng, p = build(m, seed, tracer, engine_kw)
    bad = check_samples(eng, m, p, traffic, say)
    em = eng.metrics()
    wc = em["warm_compile_stats"]
    say(f"serve: step={'unified' if em['unified_step'] else 'split'} "
        f"token_budget={em['token_budget']} steps_per_sync={eng.steps} "
        f"double_buffer={eng.double_buffer} block_size={eng.block_size} "
        f"kv={em['kv_cache_dtype']} "
        f"speculative={em['speculative']} pages={em['n_cacheable_pages']}; "
        f"warm(): {wc['compile_requests']} programs, {wc['cache_hits']} "
        f"from the cache in {wc['persistent_cache_dir']}")
    if tracer is not None:
        tracer.clear()
    before = eng.compile_stats()
    profile = Profile(trace)
    raw = drive(eng, traffic, seconds, profile, say, first=2)
    profile.close()
    after = eng.compile_stats()
    if after != before:
        bad.append(f"programs compiled inside the window: {before} -> "
                   f"{after}")
    wrong = [r.req_id for r, n in raw["measured"]
             if r.failed or not r.done or len(r.tokens) != n]
    if wrong:
        bad.append(f"requests {wrong[:8]} did not finish with their token "
                   f"count")
    for line in bad:
        say(f"check: FAILED: {line}")
    raw.update(spans=tracer.events() if tracer is not None else [],
               steps_per_sync=eng.steps)
    return {"correct": not bad, "attempted": len(raw["measured"]),
            "failed": len(wrong), "end_to_end": end_to_end(raw),
            "raw": raw, "profile": profile}
