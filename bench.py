"""Benchmark: Llama pretrain step throughput on the available device.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Metric: tokens/sec for a full train step (fwd + bwd + AdamW) of a ~1B-param
Llama (bf16 weights, fp32 optimizer states), sized for one chip.
vs_baseline is measured MFU vs the 45% MFU north-star from BASELINE.json (no
published reference numbers exist). Needs a chip: without one it fails.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np


# per-op metadata rows (NOT timings, never gated): populated by
# _op_bench alongside the ms table and written into OPBENCH.json's
# `info` key — e.g. kv_bytes_per_token for the paged decode /
# prefix-prefill rows, so the int8-vs-bf16 bandwidth ratio is recorded
# next to the latencies it explains
OP_INFO = {}


def _count_step_kernels(step_fn, *args):
    """Kernel-launch count of ONE decode step: pallas_call + dot_general
    equations in its jaxpr, sub-jaxprs included (the number TPU105
    budgets). Recorded in OPBENCH `info`. THE walker lives in
    `analysis/roofline.py` (ISSUE 13) — one inventory shared by this
    counter, TPU105's fusion budget, and the roofline launch-overhead
    term."""
    from paddle_tpu.analysis.roofline import count_step_kernels

    return count_step_kernels(step_fn, *args)


def _op_bench(only=None):
    """Per-op latency table (reference: tools/ci_op_benchmark.sh +
    check_op_benchmark_result.py — the regression gate over op kernels).

    Timing is TWO-POINT SLOPE: each op is measured as
    (t(iters_hi) - t(iters_lo)) / (iters_hi - iters_lo), each a
    fori_loop inside ONE jitted call. The slope cancels whatever fixed
    cost a call carries (dispatch, the sync), so the table measures the
    kernels themselves and not the per-call overhead. The spread and pair
    counts below were chosen on an earlier device setup with a large and
    drifting fixed cost per call; they are conservative on a directly
    attached chip — to re-check in the benchmark PR.
    (a) ONE compile per op — the iteration count is a TRACED argument
        (fori_loop with dynamic trip count), so no op builds two
        programs;
    (b) the spread adapts per op so the kernel signal is ~300 ms, well
        above call-to-call jitter;
    (c) the value is the MEDIAN of paired slopes (each pair = adjacent
        lo/hi calls, so drift cancels) — median is unbiased where
        min-of-mins is not, and one drifty window cannot set the number;
    (d) the gate re-measures a flagged op once before failing
        (see _op_regressions).

    `only`: optional iterable of op names — re-measure just those
    (used by the gate's re-measure-before-fail pass)."""
    import numpy as np

    rng = np.random.default_rng(0)
    ops = {}

    IT_LO = 20

    def timed(name, make_body, x0, n_pairs=10):
        if only is not None and name not in only:
            return

        @jax.jit
        def run(n):
            out = jax.lax.fori_loop(0, n, lambda i, x: make_body(x), x0,
                                    unroll=1)
            return jnp.sum(out.astype(jnp.float32))

        n_lo = jnp.asarray(IT_LO, jnp.int32)
        float(run(n_lo))  # compile once (trip count is traced)
        # rough est from one extra pair sizes the spread for ~300 ms of
        # kernel signal (to re-check in the benchmark PR)
        n_r = jnp.asarray(IT_LO + 100, jnp.int32)
        t0 = time.perf_counter(); float(run(n_lo)); tl = time.perf_counter() - t0
        t0 = time.perf_counter(); float(run(n_r)); tr = time.perf_counter() - t0
        est = max((tr - tl) / 100, 1e-5)  # sec/iter, floor avoids blowup
        spread = int(min(3000, max(100, 0.3 / est)))
        n_hi = jnp.asarray(IT_LO + spread, jnp.int32)
        slopes = []
        for _ in range(n_pairs):
            t0 = time.perf_counter(); float(run(n_lo))
            t_lo = time.perf_counter() - t0
            t0 = time.perf_counter(); float(run(n_hi))
            t_hi = time.perf_counter() - t0
            slopes.append(max(t_hi - t_lo, 0.0) / spread)
        slopes.sort()
        mid = len(slopes) // 2
        med = slopes[mid] if len(slopes) % 2 else \
            (slopes[mid - 1] + slopes[mid]) / 2
        ops[name] = round(med * 1e3, 4)

    # matmul 4096^3 bf16 (MXU headline)
    def want(*names):
        # skip an op's INPUT setup too when it isn't being re-measured —
        # device_put of multi-hundred-MB operands is the expensive part
        # of a re-measure pass
        return only is None or any(nm in only for nm in names)

    if want("matmul_4096_bf16"):
        a = jnp.asarray(rng.normal(size=(4096, 4096)), jnp.bfloat16)
        timed("matmul_4096_bf16", lambda x: (x @ a), a)

    # flash attention fwd and fwd+bwd on the bench GQA shape
    B, S, HQ, HK, D = 8, 2048, 16, 4, 128
    if want("flash_attn_fwd_gqa", "flash_attn_fwdbwd_gqa"):
        from paddle_tpu.kernels.flash_attention import flash_attention

        q = jnp.asarray(rng.normal(size=(B, S, HQ, D)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(B, S, HK, D)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(B, S, HK, D)), jnp.bfloat16)
        timed("flash_attn_fwd_gqa",
              lambda x: flash_attention(x, k, v, causal=True), q)

        def fa_grad(x):
            return jax.grad(lambda qq: jnp.sum(
                flash_attention(qq, k, v,
                                causal=True).astype(jnp.float32)))(x)

        timed("flash_attn_fwdbwd_gqa", fa_grad, q)

    if want("rms_norm"):
        from paddle_tpu.kernels.rms_norm import rms_norm

        h = jnp.asarray(rng.normal(size=(8, 2048, 2048)), jnp.bfloat16)
        w = jnp.ones((2048,), jnp.bfloat16)
        timed("rms_norm", lambda x: rms_norm(x, w, 1e-6), h)

    if want("decode_attention"):
        # single-token decode attention over a full cache
        from paddle_tpu.kernels.decode_attention import decode_attention

        kc = jnp.asarray(rng.normal(size=(B, HQ, S, D)), jnp.bfloat16)
        vc = jnp.asarray(rng.normal(size=(B, HQ, S, D)), jnp.bfloat16)
        lens = jnp.full((B,), S - 1, jnp.int32)
        qd = jnp.asarray(rng.normal(size=(B, HQ, D)), jnp.bfloat16)
        timed("decode_attention",
              lambda x: decode_attention(x, kc, vc, lens), qd)

    if want("paged_decode", "paged_decode_int8"):
        # paged GQA decode over a 16-page (1024-token) striped cache,
        # bf16 vs int8 pools at the identical shape (ISSUE 5): decode is
        # bandwidth-bound on KV bytes, so the int8 row's win should
        # track its kv_bytes_per_token ratio (recorded in OPBENCH's
        # `info`; the acceptance bar is <= 0.55x the bf16 bytes — half
        # the pool + the f32 scale rows)
        from paddle_tpu.kernels.decode_attention import (
            paged_decode_attention)
        from paddle_tpu.models import quantize_kv_pages

        GB, GHQ, GHK, GD, GBS, GW = 8, 16, 4, 128, 64, 16
        g_pages = GB * GW + 1
        gkc = jnp.asarray(rng.normal(size=(g_pages, GHK, GBS, GD)),
                          jnp.bfloat16)
        gvc = jnp.asarray(rng.normal(size=(g_pages, GHK, GBS, GD)),
                          jnp.bfloat16)
        gq = jnp.asarray(rng.normal(size=(GB, GHQ, GD)), jnp.bfloat16)
        gtbl = jnp.asarray(
            rng.permutation(g_pages - 1)[:GB * GW].reshape(GB, GW) + 1,
            jnp.int32)
        glens = jnp.full((GB,), GW * GBS - 1, jnp.int32)
        timed("paged_decode",
              lambda x: paged_decode_attention(x, gkc, gvc, gtbl, glens),
              gq)
        OP_INFO["paged_decode"] = {
            "kv_bytes_per_token": 2 * GHK * GD * 2}
        gkq, gks = quantize_kv_pages(gkc)
        gvq, gvs = quantize_kv_pages(gvc)
        timed("paged_decode_int8",
              lambda x: paged_decode_attention(
                  x, gkq, gvq, gtbl, glens, k_scale=gks, v_scale=gvs),
              gq)
        OP_INFO["paged_decode_int8"] = {
            "kv_bytes_per_token": round(
                2 * GHK * GD * 1 + 2 * GHK * 4 / GBS, 2)}
        del gkc, gvc, gkq, gvq

    if want("prefix_prefill", "prefix_prefill_ref", "prefix_prefill_int8"):
        # deep-prefix suffix prefill (ISSUE 4): a 1024-token cached
        # prefix (16 pages) streamed from the paged pools + a
        # 128-token bucketed suffix at the bench GQA ratio. The gated
        # `prefix_prefill` row times the ragged paged Pallas kernel;
        # `prefix_prefill_ref` times the masked-softmax gather fallback
        # at the identical shape (informational — it exists so OPBENCH
        # trends show the gather-bound vs bandwidth-bound gap, not to
        # gate the fallback)
        from paddle_tpu.kernels.prefix_prefill import (
            prefix_prefill_attention, prefix_prefill_reference)

        PB, PSB, PNH, PNKV, PDH, PBS, PW = 4, 128, 16, 4, 128, 64, 16
        n_pages = PB * PW + 1
        pq = jnp.asarray(rng.normal(size=(PB, PSB, PNH, PDH)),
                         jnp.bfloat16)
        pks = jnp.asarray(rng.normal(size=(PB, PSB, PNKV, PDH)),
                          jnp.bfloat16)
        pvs = jnp.asarray(rng.normal(size=(PB, PSB, PNKV, PDH)),
                          jnp.bfloat16)
        pkc = jnp.asarray(rng.normal(size=(n_pages, PNKV, PBS, PDH)),
                          jnp.bfloat16)
        pvc = jnp.asarray(rng.normal(size=(n_pages, PNKV, PBS, PDH)),
                          jnp.bfloat16)
        ptbl = jnp.asarray(
            rng.permutation(n_pages - 1)[:PB * PW].reshape(PB, PW) + 1,
            jnp.int32)
        pplens = jnp.full((PB,), PW * PBS, jnp.int32)
        pslens = jnp.full((PB,), PSB, jnp.int32)
        timed("prefix_prefill",
              lambda x: prefix_prefill_attention(
                  x, pks, pvs, pkc, pvc, ptbl, pplens, pslens), pq)
        OP_INFO["prefix_prefill"] = {
            "kv_bytes_per_token": 2 * PNKV * PDH * 2}

        def _pp_ref(x):
            # the _make_prefill_with_prefix fallback math (the shared
            # prefix_prefill_reference): gather every prefix page to
            # query width, one masked softmax
            return prefix_prefill_reference(
                x, pks, pvs, pkc, pvc, ptbl, pplens).astype(x.dtype)

        timed("prefix_prefill_ref", _pp_ref, pq)

        # int8 pools at the identical shape (ISSUE 5): the prefix phase
        # streams half the bytes per cached token + the f32 scale tiles
        from paddle_tpu.models import quantize_kv_pages

        pkq, pksc = quantize_kv_pages(pkc)
        pvq, pvsc = quantize_kv_pages(pvc)
        timed("prefix_prefill_int8",
              lambda x: prefix_prefill_attention(
                  x, pks, pvs, pkq, pvq, ptbl, pplens, pslens,
                  k_scale=pksc, v_scale=pvsc), pq)
        OP_INFO["prefix_prefill_int8"] = {
            "kv_bytes_per_token": round(
                2 * PNKV * PDH * 1 + 2 * PNKV * 4 / PBS, 2)}
        del pkc, pvc, pkq, pvq

    if want("all_reduce_4mb"):
        # all_reduce across the visible devices — INFORMATIONAL only (see
        # INFORMATIONAL_OPS): on 1 chip psum is a self-copy, and the slope
        # timer correctly reports ~0.01 ms. A rolling-best that small gates
        # nothing and would false-fail any future multi-device config, so
        # the row is recorded but never flagged.
        from jax.sharding import Mesh, PartitionSpec as P

        mesh1 = Mesh(np.array(jax.devices()), ("i",))
        # out_specs P("i") keeps the global carry shape stable on n>1
        # devices (P() would shrink it to one shard's worth and break the
        # fori_loop)
        psum = jax.shard_map(lambda x: jax.lax.psum(x, "i"), mesh=mesh1,
                             in_specs=P("i"), out_specs=P("i"))
        g = jnp.asarray(rng.normal(size=(1024, 1024)), jnp.float32)
        timed("all_reduce_4mb", psum, g, n_pairs=4)

    if want("decode_step_1b_int8"):
        # the flagship serving metric under the regression gate (round-5
        # VERDICT #6): one full 1B int8 decode step (32-layer loop via
        # _make_decode_step, contiguous cache) — a composite row, so a
        # regression anywhere in the serving path (quant matmul, decode
        # attention, rms/rope fusion) trips it
        from paddle_tpu.models import (LlamaConfig,
                                       init_quant_serving_params)
        from bench_roofline import build_decode_loop

        from bench_util import paired_slope_ms

        dcfg = LlamaConfig.llama_1b(dtype="bfloat16")
        dp = init_quant_serving_params(dcfg, "weight_only_int8", seed=0)
        np.asarray(jax.tree.leaves(dp)[-1])
        # cache sized so the hi leg (pos 128 + 194 steps) never clamps
        # past capacity — a saturated cache would skew the gate number
        dkcs = [jnp.zeros((4, dcfg.num_key_value_heads, 512,
                           dcfg.head_dim), jnp.bfloat16)
                for _ in range(dcfg.num_hidden_layers)]
        dvcs = list(dkcs)
        dfn = build_decode_loop(dcfg, 4, 512)
        dtok = jnp.ones((4,), jnp.int32)
        dpos = jnp.asarray(128, jnp.int32)

        def drun(n):
            return float(dfn(dp, dkcs, dvcs, dtok, dpos,
                             jnp.asarray(n, jnp.int32)))

        drun(2); drun(194)  # warm (trip count traced: one compile)
        ops["decode_step_1b_int8"] = round(
            paired_slope_ms(drun, 2, 194, pairs=8), 4)
        del dp, dkcs, dvcs

    if want("decode_step_1b_paged_ref"):
        # informational: one full 1B int8-weight decode step over PAGED
        # bf16 pools, with its kernels_per_step (pallas_call +
        # dot_general launches per decode step) in OPBENCH's `info`
        from paddle_tpu.models import (LlamaConfig,
                                       init_quant_serving_params)
        from paddle_tpu.models.llama import (_make_decode_step,
                                             make_paged_kv_helpers)
        from paddle_tpu.kernels.decode_attention import (
            paged_decode_attention)
        from bench_util import paired_slope_ms

        gcfg = LlamaConfig.llama_1b(dtype="bfloat16")
        gp = init_quant_serving_params(gcfg, "weight_only_int8", seed=0)
        np.asarray(jax.tree.leaves(gp)[-1])
        gl = gcfg.num_hidden_layers
        MB, MBS, MW = 4, 64, 8              # 4 rows x 8 pages (512 ctx)
        mnkv, mdh = gcfg.num_key_value_heads, gcfg.head_dim
        m_pages = MB * MW + 1
        mtables = jnp.asarray(
            np.arange(MB * MW).reshape(MB, MW) + 1, jnp.int32)

        def paged_pools():
            return [jnp.zeros((m_pages, mnkv, MBS, mdh), jnp.bfloat16)
                    for _ in range(gl)]

        _, kv_write = make_paged_kv_helpers(MB, 0, mnkv, mdh, MBS,
                                            mtables)

        def kv_attend(q1, kc, vc, lens):
            return paged_decode_attention(q1, kc, vc, mtables, lens)

        step = _make_decode_step(gcfg, MB, kv_write=kv_write,
                                 kv_attend=kv_attend)

        def mloop(p, kcs, vcs, tok0, lens0, n):
            def body(i, carry):
                tok, lens, kcs_, vcs_ = carry
                logits, kcs_, vcs_ = step(p, kcs_, vcs_,
                                          tok[:, None], lens)
                return (jnp.argmax(logits, -1).astype(tok.dtype),
                        lens + 1, kcs_, vcs_)

            tok, lens, _, _ = jax.lax.fori_loop(
                0, n, body, (tok0, lens0, kcs, vcs))
            return jnp.sum(tok) + jnp.sum(lens)

        mloop = jax.jit(mloop)
        mtok = jnp.ones((MB,), jnp.int32)
        mlens = jnp.full((MB,), 128, jnp.int32)
        kcs, vcs = paged_pools(), paged_pools()

        def mrun(n):
            return float(mloop(gp, kcs, vcs, mtok, mlens,
                               jnp.asarray(n, jnp.int32)))

        mrun(2); mrun(194)  # warm (trip count traced: one compile)
        ops["decode_step_1b_paged_ref"] = round(
            paired_slope_ms(mrun, 2, 194, pairs=8), 4)
        OP_INFO["decode_step_1b_paged_ref"] = {
            "kernels_per_step": _count_step_kernels(
                step, gp, paged_pools(), paged_pools(), mtok[:, None],
                mlens),
            "pages_per_seq": MW,
        }
        del gp, kcs, vcs

    def _serving_chunk_harness(serving_mp=1, quantized_collectives=False,
                               compile_run=True):
        """The 1B engine decode-chunk timing rig shared by the
        serving_decode_chunk and decode_step_1b_mp rows: an 8-slot
        steps_per_sync=16 engine whose chunks are timed by chaining N
        donated invocations and syncing once (the slope cancels the
        fixed per-call cost). budget == lens freezes every row at a
        representative mid-generation context (full per-step compute
        incl. paged attention over 96 cached tokens, writes aimed at
        the scratch page, constant cost per chunk — slope-stable).
        Returns (engine, make_run); `make_run(tracer=None,
        metrics=None)` builds the N-chunk loop over the ONE compiled
        program — with sinks armed it emits per chunk exactly what the
        engine's scheduler emits per sync (a dispatch span + the chunk
        histogram/gauges), so the traced-vs-untraced slope pair is the
        honest observability overhead on the decode hot path
        (ISSUE 8; recorded in OPBENCH `info`)."""
        from paddle_tpu.models import (LlamaConfig,
                                       init_quant_serving_params)
        from paddle_tpu.serving import ContinuousBatchingEngine

        scfg = LlamaConfig.llama_1b(dtype="bfloat16")
        sp = init_quant_serving_params(scfg, "weight_only_int8", seed=0)
        np.asarray(jax.tree.leaves(sp)[-1])
        eng = ContinuousBatchingEngine(
            scfg, sp, slots=8, prompt_bucket=128, max_prompt_len=128,
            max_new_tokens=64, block_size=64, steps_per_sync=16,
            prefill_batch=1, prefix_cache=False, serving_mp=serving_mp,
            quantized_collectives=quantized_collectives)
        # every row live at length 96 on the scratch page; the tokens and
        # lengths chain through the programs' device carries
        sflat, _ = eng._put(eng._io["decode"][0], dict(
            eng._scratch_inputs(), budgets=96, live=True, override=False))
        slens = jnp.full((eng.slots,), 96, jnp.int32)
        sone = jnp.asarray(1.0, jnp.float32)
        skey = jax.random.PRNGKey(0)

        def make_run(tracer=None, metrics=None):
            def run(n):
                toks, lens = jnp.zeros((eng.slots,), jnp.int32), slens
                for i in range(int(n)):
                    if tracer is not None:
                        t0 = time.perf_counter_ns()
                    _, toks, lens, _, eng.kcs, eng.vcs = eng._decode(
                        eng.p, eng.kcs, eng.vcs, sflat, toks, lens, skey,
                        sone, sone)
                    if tracer is not None:
                        tracer.complete("decode.dispatch", t0,
                                        time.perf_counter_ns(), chunk=i,
                                        live=eng.slots)
                    if metrics is not None:
                        metrics.histogram("decode_chunk_s").observe(1e-3)
                        metrics.gauge("live_slots").set(eng.slots)
                        metrics.gauge("kv_pages_available").set(0)
                return float(jnp.sum(lens))

            return run

        if compile_run:
            make_run()(1)  # compile once
        return eng, make_run

    def _tuned_info(serving_mp=1, budget_candidates=6):
        """Auditor-driven autotuner stub (ISSUE 16) for the chunk rig:
        rank a capped slice of the engine config space for the SAME 1B
        geometry `_serving_chunk_harness` times, and record the winning
        knobs + their predicted step/MFU/wire numbers in OPBENCH
        `info`. Static only (trace + auditor passes per candidate, no
        compiles) — the next TPU run lands estimate/actual ratios for
        the config the tuner actually recommends, not just the
        defaults."""
        from paddle_tpu.analysis import autotune
        from paddle_tpu.models import (LlamaConfig,
                                       init_quant_serving_params)

        scfg = LlamaConfig.llama_1b(dtype="bfloat16")
        sp = init_quant_serving_params(scfg, "weight_only_int8", seed=0)
        rep = autotune(
            scfg, sp, budget_candidates=budget_candidates,
            engine_kwargs=dict(
                slots=8, prompt_bucket=128, max_prompt_len=128,
                max_new_tokens=64, block_size=64, steps_per_sync=16,
                prefill_batch=1, prefix_cache=False,
                serving_mp=serving_mp))
        best = rep.best
        out = dict(best.config)
        out.update({
            "predicted_step_ms": round(best.predicted_step_ms, 4),
            "predicted_ms_per_token": round(
                best.predicted_ms_per_token, 6),
            "predicted_mfu": best.predicted_mfu,
            "predicted_wire_bytes_per_token": int(
                best.predicted_wire_bytes_per_token),
            "predicted_peak_hbm_bytes": int(best.peak_hbm_bytes),
            "predicted_speedup_vs_default":
                rep.to_dict(top_k=1)["predicted_speedup_vs_default"],
        })
        return out

    if want("serving_decode_chunk"):
        # the engine's decode hot loop under the gate (ISSUE 3): one
        # steps_per_sync=16 chunk for 8 slots over the PAGED pools —
        # the program ContinuousBatchingEngine re-dispatches for every
        # scheduling sync, so a regression in the paged decode kernel,
        # the scan, or the per-chunk dispatch glue shows up in the
        # bench trajectory.
        from bench_util import paired_slope_ms

        eng, smake = _serving_chunk_harness()
        untraced = paired_slope_ms(smake(), 1, 13, pairs=6)
        ops["serving_decode_chunk"] = round(untraced, 4)
        # observability overhead (ISSUE 8): the SAME compiled chunk
        # with the engine's per-sync span/metric emissions armed —
        # recorded as info (trend), not a gated timing: the delta is
        # host-side and should be unmeasurable next to the chunk
        from paddle_tpu.observability import MetricsRegistry, Tracer

        traced = paired_slope_ms(
            smake(Tracer(capacity=1 << 16), MetricsRegistry()),
            1, 13, pairs=6)
        OP_INFO["observability"] = {
            "untraced_chunk_ms": round(untraced, 4),
            "traced_chunk_ms": round(traced, 4),
            "overhead_pct": round(
                100.0 * (traced - untraced) / max(untraced, 1e-9), 2),
        }
        # static auditors (ISSUES 10 + 13): predicted per-chip peak AND
        # predicted roofline latency/MFU of the timed chunk program,
        # recorded NEXT TO the measured slope so the next TPU run lands
        # estimate/actual ratios (one shared trace serves both)
        sgraphs = eng._traced_inventory(programs=("decode",))
        sroof = eng.audit_roofline(programs=("decode",),
                                   graphs=sgraphs)["programs"]["decode"]
        OP_INFO["serving_decode_chunk"] = {
            "predicted_peak_hbm_bytes": eng.audit_memory(
                programs=("decode",),
                graphs=sgraphs)["fleet_peak_hbm_bytes"],
            "predicted_step_ms": round(sroof["predicted_step_ms"], 4),
            "predicted_mfu": sroof["predicted_mfu"],
            "predicted_bound": sroof["bound"],
            # auditor-driven autotuner (ISSUE 16): the config the
            # static tuner recommends for THIS rig and its predicted
            # numbers — calibration stub, the next TPU run lands the
            # measured chunk slope next to the winner's prediction
            "tuned": _tuned_info(),
        }
        del eng, smake

    if want("decode_step_1b_mp") and len(jax.devices()) >= 2:
        # tensor-parallel serving decode (ISSUE 7): the SAME chunk rig,
        # kv-head-sharded across an mp=2 mesh (FLAGS_serving_mp) — the
        # per-layer o-proj activation all-gather is the one cross-chip
        # collective, and bytes_all_gathered_per_token in OPBENCH's
        # `info` records its per-chip wire cost per decoded token (the
        # number the EQuARX-style quantized all-gather follow-up will
        # halve; TPU401's collective-size lint watches the same seam).
        # Skipped (row absent, nothing gates) on single-device runs.
        from bench_util import paired_slope_ms

        teng, tmake = _serving_chunk_harness(serving_mp=2)
        trun = tmake()
        ops["decode_step_1b_mp"] = round(
            paired_slope_ms(trun, 1, 13, pairs=6), 4)
        # per decoded token per chip: every layer all-gathers the
        # [b, 1, nh_local*dh] o-proj activations — each chip RECEIVES
        # (mp-1)/mp of the full head axis. Itemsize 2: ISSUE 14's
        # satellite casts the payload to BF16 BEFORE the gather
        # (ServingTP.gather_heads) — PR 11's auditor had exposed an
        # f32 activation stream shipping f32 here with the downcast
        # landing after the wire; the pre-cast halves the mp seam's
        # bytes, and EQuARX-style int8 remains the follow-up
        mp_, tcfg = teng.mp, teng.cfg
        # ONE decode trace serves all three static auditors
        tgraphs = teng._traced_inventory(programs=("decode",))
        troof = teng.audit_roofline(programs=("decode",),
                                    graphs=tgraphs)["programs"]["decode"]
        # quantized-collectives twin (ISSUE 15): the SAME chunk
        # program with FLAGS_quantized_collectives ON — the o-proj
        # gather ships int8 + an f32 scale sidecar. Audit-only (no
        # timing until the default flips): the predicted wire bytes
        # land next to the bf16 row's so the ~2x ratio is recorded,
        # and the hand formula prices payload (1 byte/elt) + sidecar
        # (4 bytes per block of min(128, dh) elements).
        from paddle_tpu.parallel.collectives import QCOLL_BLOCK

        # the SAME rig, quantized (audit-only: no compile) — one set of
        # engine literals lives in _serving_chunk_harness
        qeng, _ = _serving_chunk_harness(serving_mp=2,
                                         quantized_collectives=True,
                                         compile_run=False)
        qwire = qeng.audit_comms(
            programs=("decode",),
            graphs=qeng._traced_inventory(programs=("decode",))
        )["predicted_bytes_on_wire_per_token"]
        dh_ = tcfg.head_dim
        nblk = -(-dh_ // min(QCOLL_BLOCK, dh_))
        OP_INFO["decode_step_1b_mp"] = {
            "mp": mp_,
            "bytes_all_gathered_per_token": int(
                tcfg.num_hidden_layers * tcfg.num_attention_heads
                * tcfg.head_dim * 2 * (mp_ - 1) // mp_),
            # static comms auditor (ISSUE 11): jaxpr-derived wire bytes
            # per decoded token per chip — next to the hand formula
            # above so the next TPU run lands an estimate/actual ratio
            "predicted_bytes_on_wire_per_token": int(
                teng.audit_comms(programs=("decode",), graphs=tgraphs)
                ["predicted_bytes_on_wire_per_token"]),
            # int8 quantized-collectives twin (ISSUE 15): payload
            # 1 byte/elt + f32 sidecar per min(128, dh)-elt block —
            # ~0.5x the bf16 hand formula above; the measured row
            # rides the next TPU run once the flag default flips
            "bytes_all_gathered_per_token_int8coll": int(
                tcfg.num_hidden_layers * tcfg.num_attention_heads
                * (tcfg.head_dim * 1 + nblk * 4) * (mp_ - 1) // mp_),
            "predicted_bytes_on_wire_per_token_int8coll": int(qwire),
            # per-chip under kv-head sharding — pairs with the mp=1
            # row's estimate to confirm the 1/mp pool scaling on device
            "predicted_peak_hbm_bytes": teng.audit_memory(
                programs=("decode",),
                graphs=tgraphs)["fleet_peak_hbm_bytes"],
            # static roofline (ISSUE 13): predicted chunk latency next
            # to the measured slope — estimate/actual on the next run
            "predicted_step_ms": round(troof["predicted_step_ms"], 4),
            "predicted_mfu": troof["predicted_mfu"],
            "predicted_bound": troof["bound"],
            # autotuner stub (ISSUE 16) at mp=2: the recommended
            # sharded-serving config and its predictions — includes
            # whether int8 collectives / kv int8 win on this rig
            "tuned": _tuned_info(serving_mp=2),
        }
        # the recorded ~2x: bf16 wire / int8coll wire per decoded token
        OP_INFO["decode_step_1b_mp"]["int8coll_wire_ratio"] = round(
            OP_INFO["decode_step_1b_mp"]
            ["predicted_bytes_on_wire_per_token"] / max(qwire, 1), 3)
        del teng, trun, qeng

    if want("fit_dp_psum") and len(jax.devices()) >= 2:
        # dp gradient-sync wire bytes, unquantized vs int8coll (ISSUE
        # 15): Model.fit(audit_comms=True) under a dp=2 mesh audits
        # the EXPLICIT dp step — `lax.psum` over the grads (what GSPMD
        # inserts), or the quantized two-hop exchange with
        # quantized_collectives=True, which also RUNS one real
        # quantized-dp training batch. The bytes delta is the
        # quantized-collectives win on the training seam; audit-only
        # info, the gated OPBENCH row update rides the next TPU run.
        import paddle_tpu as _pd
        from paddle_tpu import nn as _nn, optimizer as _opt
        from paddle_tpu.parallel import mesh as _mesh

        prev_mesh = _mesh.get_global_mesh()
        try:
            _mesh.set_global_mesh(_mesh.build_mesh(
                {"dp": 2}, devices=jax.devices()[:2]))
            fit_rows = {}
            for tag, qc in (("bytes_on_wire", False),
                            ("bytes_on_wire_int8coll", True)):
                _pd.seed(5)
                fnet = _nn.Linear(512, 512)
                fm = _pd.Model(fnet)
                fm.prepare(
                    optimizer=_opt.Adam(learning_rate=0.01,
                                        parameters=fnet.parameters()),
                    loss=lambda out, y: ((out - y) ** 2).mean())
                frng = np.random.default_rng(0)
                fb = [(frng.normal(size=(4, 512)).astype(np.float32),
                       frng.normal(size=(4, 512)).astype(np.float32))]
                fm.fit(fb, epochs=1, verbose=0, audit_comms=True,
                       quantized_collectives=qc)
                fit_rows[tag] = int(fm.comms_audit["bytes_on_wire"])
            fit_rows["int8coll_wire_ratio"] = round(
                fit_rows["bytes_on_wire"]
                / max(fit_rows["bytes_on_wire_int8coll"], 1), 3)
            fit_rows["quantized_dp_steps"] = fm.quantized_dp_steps
            OP_INFO["fit_dp_psum"] = fit_rows
        finally:
            _mesh.set_global_mesh(prev_mesh)

    if want("ragged_step"):
        # unified ragged serving step (ISSUE 14): ONE program running a
        # full mixed cycle — 8 slots x 16 decode tokens PLUS a
        # 128-token prefill window streamed through
        # ragged_paged_attention — at the 1B serving shape. The slope
        # prices what a mixed scheduling sync costs once chunked
        # prefill rides the decode dispatch; predicted_step_ms /
        # predicted_mfu / kernels_per_step land beside it so the next
        # TPU run gets estimate/actual ratios (and the
        # FLAGS_unified_step silicon default has its number).
        from bench_util import paired_slope_ms
        from paddle_tpu.analysis import roofline as _roof
        from paddle_tpu.models import (LlamaConfig,
                                       init_quant_serving_params)
        from paddle_tpu.serving import ContinuousBatchingEngine

        ucfg = LlamaConfig.llama_1b(dtype="bfloat16")
        up = init_quant_serving_params(ucfg, "weight_only_int8", seed=0)
        np.asarray(jax.tree.leaves(up)[-1])
        ueng = ContinuousBatchingEngine(
            ucfg, up, slots=8, prompt_bucket=128, max_prompt_len=128,
            max_new_tokens=64, block_size=64, steps_per_sync=16,
            prefill_batch=1, prefix_cache=False, unified_step=True,
            token_budget=128)
        # every row live at length 96 and a full window of a cached
        # prefix, all on the scratch page
        tn = ueng.token_budget
        uflat, _ = ueng._put(ueng._io["mixed"][0], dict(
            ueng._scratch_inputs(), lens=96, budgets=96, live=True,
            chunk_ids=1, chunk_len=tn))
        uone = jnp.asarray(1.0, jnp.float32)
        ukey = jax.random.PRNGKey(0)

        def urun(n):
            # chained donated invocations, synced once — the slope
            # cancels the fixed per-call cost like the decode-chunk rig; a full
            # 64-token cached window keeps per-call cost constant
            for _ in range(int(n)):
                packed, _, ueng.kcs, ueng.vcs = ueng._unified(
                    ueng.p, ueng.kcs, ueng.vcs, uflat, ukey, uone, uone)
            return float(jnp.sum(packed))

        urun(1)  # compile once
        ops["ragged_step"] = round(paired_slope_ms(urun, 1, 13,
                                                   pairs=6), 4)
        ugraphs = ueng._traced_inventory(programs=("unified",))
        uroof = ueng.audit_roofline(
            programs=("unified",), graphs=ugraphs)["programs"]["unified"]
        OP_INFO["ragged_step"] = {
            "token_budget": tn,
            "decode_tokens_per_step": ueng.slots * ueng.steps,
            "kernels_per_step": _roof.count_kernel_launches(
                ugraphs[0][1].jaxpr),
            "predicted_step_ms": round(uroof["predicted_step_ms"], 4),
            "predicted_mfu": uroof["predicted_mfu"],
            "predicted_bound": uroof["bound"],
            "predicted_peak_hbm_bytes": ueng.audit_memory(
                programs=("unified",),
                graphs=ugraphs)["fleet_peak_hbm_bytes"],
        }
        del ueng, urun

    if want("verify_chunk"):
        # speculative verify window (ISSUE 19): ONE ragged pass scoring
        # 8 slots x (k=4 drafts + the pending token) at the 1B serving
        # shape — the program a speculative step dispatches instead of
        # k+1 sequential decode steps. The slope prices one window; the
        # auditor twins (predicted_step_ms / wire bytes / peak HBM)
        # land beside it like the ragged_step row so the next TPU run
        # gets estimate/actual ratios.
        from bench_util import paired_slope_ms
        from paddle_tpu.analysis import roofline as _roof
        from paddle_tpu.models import (LlamaConfig,
                                       init_quant_serving_params)
        from paddle_tpu.serving import ContinuousBatchingEngine

        vcfg = LlamaConfig.llama_1b(dtype="bfloat16")
        vp = init_quant_serving_params(vcfg, "weight_only_int8", seed=0)
        np.asarray(jax.tree.leaves(vp)[-1])
        veng = ContinuousBatchingEngine(
            vcfg, vp, slots=8, prompt_bucket=128, max_prompt_len=128,
            max_new_tokens=64, block_size=64, steps_per_sync=16,
            prefill_batch=1, prefix_cache=False,
            speculative="ngram", spec_k=4)
        w = veng.spec_k + 1
        vtables = jnp.full((veng.slots, veng.table_width),
                           veng.scratch_page, jnp.int32)
        vids = jnp.ones((veng.slots, w), jnp.int32)
        vcached = jnp.full((veng.slots,), 96, jnp.int32)
        vnew = jnp.full((veng.slots,), w, jnp.int32)

        def vrun(n):
            # chained donated invocations, synced once — the slope
            # cancels the fixed per-call cost like the decode-chunk rig
            acc = None
            for _ in range(int(n)):
                preds, veng.kcs, veng.vcs = veng._verify(
                    veng.p, veng.kcs, veng.vcs, vids, vtables, vcached,
                    vnew)
                acc = preds
            return float(jnp.sum(acc))

        vrun(1)  # compile once
        ops["verify_chunk"] = round(paired_slope_ms(vrun, 1, 13,
                                                    pairs=6), 4)
        vgraphs = veng._traced_inventory(programs=("verify",))
        vroof = veng.audit_roofline(
            programs=("verify",), graphs=vgraphs)["programs"]["verify"]
        OP_INFO["verify_chunk"] = {
            "spec_k": veng.spec_k,
            "window_rows": veng.slots * w,
            "kernels_per_step": _roof.count_kernel_launches(
                vgraphs[0][1].jaxpr),
            "predicted_step_ms": round(vroof["predicted_step_ms"], 4),
            "predicted_mfu": vroof["predicted_mfu"],
            "predicted_bound": vroof["bound"],
            "predicted_bytes_on_wire_per_token": int(
                veng.audit_comms(programs=("verify",), graphs=vgraphs)
                ["predicted_bytes_on_wire_per_token"]),
            "predicted_peak_hbm_bytes": veng.audit_memory(
                programs=("verify",),
                graphs=vgraphs)["fleet_peak_hbm_bytes"],
        }
        del veng, vrun

    # eager dispatch overhead: one tiny op, eager, host-timed — tracks the
    # per-op cost of the eager tape + device round-trip over rounds
    # (reference: test/cpp/eager/performance_tests/benchmark_eager_cuda.cc).
    # INFORMATIONAL: the number is dominated by the host's dispatch and
    # sync round trip, which is environment state as much as code —
    # useful trend, dishonest gate.
    if only is None or "eager_dispatch_add" in only:
        import paddle_tpu as _paddle

        t_small = _paddle.to_tensor(np.ones((8, 8), np.float32))
        (t_small + t_small)  # warm the dispatch path
        reps = 20
        t0 = time.perf_counter()
        for _ in range(reps):
            out = t_small + t_small
        float(out.numpy().sum())
        ops["eager_dispatch_add"] = round(
            (time.perf_counter() - t0) / reps * 1e3, 4)
    return ops


# recorded in OPBENCH.json for trend-watching but excluded from the
# regression gate: on a single chip their values measure the
# environment (host round trip, self-copy psum), not the kernels
# — and prefix_prefill_ref is the masked-softmax fallback timed only as
# the comparison line for the gated prefix_prefill kernel row.
INFORMATIONAL_OPS = {"all_reduce_4mb", "eager_dispatch_add",
                     "prefix_prefill_ref", "decode_step_1b_paged_ref"}


# regressions consciously accepted, with a dated reason — an entry here is
# the ONLY way to silence the gate (reference: the PR-note workflow of
# tools/check_op_benchmark_result.py). The corresponding note must also
# land in PERF.md.
ACKNOWLEDGED_REGRESSIONS = {
    # 2026-07-31: the op timer changed from fixed-30-iteration calls to
    # two-point slope (see _op_bench docstring) because the old numbers
    # measured per-call round-trip amortization, not kernels; every op's
    # scale shifted, so the first slope-based run rebaselines the table.
    "__rebaseline_2026_07_31__": "timer change, see _op_bench docstring",
    # 2026-07-31 (round 4): timer hardened again — one compile per op
    # (traced trip count), adaptive ~300 ms spread, median of 10 paired
    # slopes — after the round-3 rc=3 proved a single min-of-6 slope has
    # ±30% error on sub-0.2 ms ops (the flagged "+50% rms_norm"
    # re-measured at 0.188 ms ≈ the 0.164 ms bandwidth bound; the kernel
    # never changed). Scales shift again → rebaseline.
    "__rebaseline_r4_2026_07_31__": "timer hardening, see _op_bench",
}


def _op_regressions(ops, path="OPBENCH.json", threshold=0.10):
    """>10% (+0.1 ms) slower than the BEST ever recorded for the op ⇒ a
    regression. The rolling-best baseline cannot be inflated by a noisy
    run (a slow sample never becomes the bar), so real regressions keep
    flagging every round until fixed or acknowledged. Unacknowledged
    regressions surface in the driver-parsed JSON line AND fail the run
    (the round-2 warn-only gate was ignorable by design; this one is not).
    """
    prev = best = None
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
            prev = data.get("ops")
            best = data.get("best") or prev
        except Exception:
            prev = best = None
    rebaseline = any(k.startswith("__rebaseline") and best is not None
                     and k not in (best or {})
                     for k in ACKNOWLEDGED_REGRESSIONS)

    def _flagged(table):
        out = []
        for name, ms in table.items():
            old = (best or {}).get(name)
            if old and ms > old * (1 + threshold) and ms - old > 0.1 \
                    and name not in ACKNOWLEDGED_REGRESSIONS \
                    and name not in INFORMATIONAL_OPS:
                out.append(name)
        return out

    warned = []
    if best and not rebaseline:
        suspects = _flagged(ops)
        if suspects:
            # re-measure-before-fail: a flagged sub-ms op is more often
            # timing variance than regression (round-3 lesson). One fresh
            # measurement of just the suspects; keep the better number.
            import sys
            print(f"op gate: re-measuring suspects {suspects}",
                  file=sys.stderr)
            try:
                second = _op_bench(only=set(suspects))
            except Exception:
                second = {}
            for name in suspects:
                if name in second:
                    ops[name] = round(min(ops[name], second[name]), 4)
        for name in _flagged(ops):
            old = best[name]
            ms = ops[name]
            warned.append(f"{name}: best {old:.3f} -> {ms:.3f} ms "
                          f"(+{(ms / old - 1) * 100:.0f}%)")
    marker = {k: v for k, v in ACKNOWLEDGED_REGRESSIONS.items()}
    if rebaseline or not best:
        new_best = dict(ops)
    else:
        new_best = {n: min(ms, best.get(n, ms)) for n, ms in ops.items()}
    sentinel = {k: 0.0 for k in marker if k.startswith("__")}
    with open(path, "w") as f:
        json.dump({"ops": dict(ops, **sentinel),
                   "best": dict(new_best, **sentinel),
                   "prev": prev, "acknowledged": marker,
                   "info": dict(OP_INFO)}, f, indent=1)
    if warned:
        import sys
        print("OP REGRESSION (>10% and >0.1 ms vs best recorded, "
              "unacknowledged):\n  " + "\n  ".join(warned), file=sys.stderr)
    return warned


def train_config():
    """(cfg, batch, seq) of the train bench — chip_smoke.py's train phase
    runs the same step. GQA config (4 kv heads, llama-2-70B/llama-3 class
    ratio) so the gate measures the grouped-attention fast path — the
    config class that matters for real deployments. The step runs the
    HONEST production config — real AdamW with fp32 moments and norm/bias
    decay exclusion. An older record (removed in PR 22, predates PRs 1-20)
    had bs 8 forcing 8/16 layers to remat under the fp32 moments and bs 4
    needing none on one 16 GB chip; not measured on this machine."""
    from paddle_tpu.models import LlamaConfig

    cfg = LlamaConfig.llama_1b(dtype="bfloat16", recompute=False,
                               num_key_value_heads=4,
                               max_position_embeddings=2048)
    return cfg, 4, 2048


def build_train_step(cfg, mesh=None, seed: int = 0):
    """(step, params, opt_state, model) for `cfg`: real AdamW through the
    FusedOptimizer path, weight decay excluded from norm scales / biases
    (reference: python/paddle/optimizer/adamw.py apply_decay_param_fun) —
    the step users would actually run, not a shortcut."""
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


def main():
    from paddle_tpu.parallel.mesh import build_mesh, set_global_mesh
    from paddle_tpu.serving.compile_cache import enable_compile_cache

    if jax.default_backend() != "tpu":
        # a number from a CPU run is never written under the name of a
        # device metric: without a chip the bench fails
        raise SystemExit("bench.py: no TPU backend "
                         f"({jax.default_backend()}); nothing to measure")
    enable_compile_cache()   # the one decision where the cache lives
    n_dev = jax.device_count()
    cfg, batch, seq = train_config()
    iters = 10

    mesh = None
    if n_dev > 1:
        mesh = build_mesh({"dp": 1, "sharding": n_dev, "mp": 1, "sep": 1})
        set_global_mesh(mesh)

    step, params, opt, model = build_train_step(cfg, mesh)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)))
    y = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)))

    # warmup / compile; sync via device_get. On this machine
    # block_until_ready IS a true barrier too: chip_smoke.py's train phase
    # (PR 22, one v5e) waited 0.334 s per step in it and a device_get of
    # the loss right after took under 1 ms — either sync is honest here
    loss, params, opt = step(params, opt, x, y)
    float(loss)

    t0 = time.perf_counter()
    for _ in range(iters):
        loss, params, opt = step(params, opt, x, y)
    float(loss)
    dt = time.perf_counter() - t0

    tokens = batch * seq * iters
    tok_per_s = tokens / dt
    if os.environ.get("BENCH_DEBUG"):
        import sys
        print(f"debug: dt={dt:.4f} iters={iters} batch={batch} seq={seq} "
              f"n_dev={n_dev} loss={float(loss):.4f}", file=sys.stderr)

    # parameter count & model FLOPs (6 * N * tokens for fwd+bwd; +33% remat)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    flops_per_token = 6 * n_params
    achieved = tok_per_s * flops_per_token
    # per-chip peak: v5e 197 TFLOPs bf16, v6e 918; detect via device
    # kind — ONE spec table (analysis/device_specs.py) serves this, the
    # static roofline pass, and the other benches (ISSUE 13 hoist;
    # values unchanged)
    from paddle_tpu.analysis.device_specs import spec_for_device_kind

    kind = jax.devices()[0].device_kind.lower()
    peak = spec_for_device_kind(kind).peak_for("bfloat16")
    mfu = achieved / (peak * n_dev)

    # silicon numerics gate: the Pallas kernels are asserted against
    # on-device fp32 oracles every bench run (chip_smoke.py's kernel
    # phase; reference: op_test.py check_output_with_place on CUDAPlace).
    # A numerics failure rides the same driver-parsed field as a perf
    # regression; a check that cannot run raises.
    from chip_smoke import run_kernel_checks

    regressions = [f"chip_smoke: {f}" for f in run_kernel_checks()]
    # per-op regression gate: unacknowledged >10% regressions go into
    # the driver-parsed JSON line AND fail the process (round-2's
    # warn-only gate could be ignored; this one cannot)
    # free the train state first: the op table's serving row puts a
    # second model (1B int8) on the chip
    del params, opt
    regressions += _op_regressions(_op_bench())

    result = {
        "metric": "llama_train_tokens_per_sec",
        "value": round(tok_per_s, 2),
        "unit": f"tokens/s (1B-class llama, bf16, {n_dev} chip; "
                f"loss={float(loss):.3f}; mfu={mfu:.3f})",
        "vs_baseline": round(mfu / 0.45, 3),
    }
    if regressions:
        result["regressions"] = regressions
    print(json.dumps(result))
    if regressions:
        raise SystemExit(3)


if __name__ == "__main__":
    main()
