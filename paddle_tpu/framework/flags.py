"""Global runtime flag registry.

TPU-native equivalent of the reference's exported-flag registry
(paddle/common/flags.h:336 `ExportedFlagInfoMap`, paddle/common/flags.cc which
defines ~176 FLAGS_*). Flags are plain Python values, overridable from the
environment (``FLAGS_check_nan_inf=1 python ...``) and via
``paddle_tpu.set_flags``.
"""
from __future__ import annotations

import os
from typing import Any, Dict

_REGISTRY: Dict[str, Any] = {}


def _coerce(default, raw: str):
    if isinstance(default, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(raw)
    if isinstance(default, float):
        return float(raw)
    return raw


def define_flag(name: str, default, doc: str = "", env_aliases=()):
    """Register a flag; `env_aliases` are extra environment variable
    names honoured besides FLAGS_<name> (first set one wins) — used for
    user-facing switches like PADDLE_TPU_LINT."""
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    env = os.environ.get(name)
    for alias in env_aliases:
        if env is not None:
            break
        env = os.environ.get(alias)
    _REGISTRY[name] = _coerce(default, env) if env is not None else default
    return _REGISTRY[name]


def set_flags(flags: Dict[str, Any]):
    """paddle.set_flags equivalent (python/paddle/base/framework.py)."""
    for k, v in flags.items():
        if not k.startswith("FLAGS_"):
            k = "FLAGS_" + k
        if k not in _REGISTRY:
            raise KeyError(f"unknown flag {k}; known: {sorted(_REGISTRY)}")
        _REGISTRY[k] = v


def get_flags(flags=None) -> Dict[str, Any]:
    if flags is None:
        return dict(_REGISTRY)
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for k in flags:
        if not k.startswith("FLAGS_"):
            k = "FLAGS_" + k
        out[k] = _REGISTRY[k]
    return out


def flag(name: str):
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    return _REGISTRY[name]


# --- core flags (subset of paddle/common/flags.cc, TPU-relevant) ---
define_flag("check_nan_inf", False, "check every op output for NaN/Inf (reference: flags.cc:72)")
define_flag("check_nan_inf_level", 0, "0: raise on NaN/Inf, >0: log only")
define_flag("benchmark", False, "synchronous op execution for timing")
define_flag("use_deterministic_ops", False, "prefer deterministic lowering")
define_flag("eager_delete_tensor_gb", 0.0, "no-op on TPU (XLA owns buffers)")
define_flag("allocator_strategy", "xla", "allocation is owned by the XLA runtime")
define_flag("tpu_matmul_precision", "default", "jax default_matmul_precision for fp32 matmuls")
define_flag("enable_pallas_kernels", True, "use Pallas kernels for fused ops when on TPU")
define_flag("log_level", 0, "VLOG-style verbosity")

# --- analysis / lint (paddle_tpu.analysis) ---
define_flag("tpu_lint", False,
            "run the jaxpr lint pipeline on every to_static trace "
            "(also: PADDLE_TPU_LINT=1)", env_aliases=("PADDLE_TPU_LINT",))
define_flag("tpu_lint_fail_on", "error",
            "severity that aborts the trace when tpu_lint is on: "
            "error|warning|info|never "
            "(also: PADDLE_TPU_LINT_FAIL_ON)",
            env_aliases=("PADDLE_TPU_LINT_FAIL_ON",))
define_flag("audit_memory", False,
            "run the static memory auditor (analysis/memory.py: jaxpr "
            "liveness peak-HBM estimate + donation analysis) at the "
            "audit hooks — ContinuousBatchingEngine.warm() over every "
            "cached program and Model.fit over the forward pass. "
            "PADDLE_TPU_LINT=1 implies it (the hooks compose with the "
            "lint switch) (also: PADDLE_TPU_AUDIT_MEMORY)",
            env_aliases=("PADDLE_TPU_AUDIT_MEMORY",))
define_flag("audit_comms", False,
            "run the static communication auditor (analysis/comms.py: "
            "jaxpr bytes-on-wire pass + per-chip collective cost "
            "model) at the audit hooks — "
            "ContinuousBatchingEngine.warm() over every cached program "
            "and Model.fit over the training step. PADDLE_TPU_LINT=1 "
            "implies it (the hooks compose with the lint switch) "
            "(also: PADDLE_TPU_AUDIT_COMMS)",
            env_aliases=("PADDLE_TPU_AUDIT_COMMS",))
define_flag("audit_roofline", False,
            "run the static roofline auditor (analysis/roofline.py: "
            "jaxpr FLOPs/bytes pass against the device-spec table -> "
            "predicted step latency, bound class, MFU) at the audit "
            "hooks — ContinuousBatchingEngine.warm() over every cached "
            "program and Model.fit over the training step. "
            "PADDLE_TPU_LINT=1 implies it (the hooks compose with the "
            "lint switch) (also: PADDLE_TPU_AUDIT_ROOFLINE)",
            env_aliases=("PADDLE_TPU_AUDIT_ROOFLINE",))

# --- serving kernels ---
define_flag("prefix_prefill_kernel", True,
            "serve cached-prefix suffix prefills through the ragged "
            "paged Pallas kernel (kernels/prefix_prefill.py); off = "
            "masked-softmax gather fallback. Read when the prefill "
            "program is BUILT, so flip it before constructing (or "
            "warming) an engine "
            "(also: PADDLE_TPU_PREFIX_PREFILL_KERNEL)",
            env_aliases=("PADDLE_TPU_PREFIX_PREFILL_KERNEL",))

define_flag("kv_cache_dtype", "bf16",
            "element type of the PAGED serving KV pools: 'bf16' "
            "(default) or 'int8' (symmetric per-(page, kv-head) absmax "
            "quantization — halves the HBM bytes every decode / "
            "prefix-prefill step streams AND doubles the pages a byte "
            "budget holds before LRU eviction). Read when a paged "
            "program / engine is BUILT, so flip it before constructing "
            "(or warming) an engine "
            "(also: PADDLE_TPU_KV_CACHE_DTYPE)",
            env_aliases=("PADDLE_TPU_KV_CACHE_DTYPE",))

define_flag("unified_step", "auto",
            "serve mixed prefill+decode traffic through the UNIFIED "
            "ragged step (ISSUE 14): the engine's program zoo (cold + "
            "prefix prefill keyed over suffix bucket x batch x "
            "prefix-width rung) collapses to ONE chunked-prefill+decode "
            "program over the ragged_paged_attention kernel, admission "
            "becomes token-budget packing, and long prompts prefill in "
            "chunks so decode latency is immune to prefill bursts. "
            "'auto' (default) = on, on every backend: the chip serves "
            "the step tier-1 exercises (PR 22; before it 'auto' meant "
            "off on a TPU, so the chip's default was the path the "
            "tests ran least). '1'/'0' force. The split-program path "
            "stays the oracle. Read when the engine is BUILT "
            "(also: PADDLE_TPU_UNIFIED_STEP)",
            env_aliases=("PADDLE_TPU_UNIFIED_STEP",))

define_flag("serving_mp", 1,
            "tensor-parallel degree of the PAGED serving stack: the "
            "engine's K/V pools (and their int8 scale sidecars) shard "
            "by kv head across an `mp` mesh of this many devices, the "
            "decode / prefill / prefix-prefill programs run under "
            "shard_map with each shard streaming only its local kv "
            "heads, and the sole per-layer cross-chip traffic is the "
            "all-gather of the per-shard o-proj activations. 1 "
            "(default) = today's single-chip path, byte-identical. "
            "Read when a paged program / engine is BUILT (it joins "
            "every program key), so flip it before constructing (or "
            "warming) an engine (also: PADDLE_TPU_SERVING_MP)",
            env_aliases=("PADDLE_TPU_SERVING_MP",))

define_flag("serving_cp", 1,
            "context-parallel degree of the PAGED serving stack: the "
            "engine's K/V pools shard by PAGE across a `cp` mesh axis "
            "of this many devices (composable with serving_mp as a 2-D "
            "cp x mp serving mesh), each shard streams only its LOCAL "
            "pages of a request through the attention programs and "
            "emits online-softmax partials (m, l, acc), and a small "
            "cross-chip merge of those stats — never the KV pages — "
            "applies the kernel's own rescale recurrence one level up "
            "(ServingTP.merge_attn_partials). Lifts the per-request "
            "context ceiling to cp x one chip's pool. 1 (default) = "
            "today's page-replicated path, byte-identical. Read when a "
            "paged program / engine is BUILT (it joins every program "
            "key), so flip it before constructing (or warming) an "
            "engine (also: PADDLE_TPU_SERVING_CP)",
            env_aliases=("PADDLE_TPU_SERVING_CP",))

define_flag("quantized_collectives", False,
            "ship the hot cross-chip payloads as absmax-scaled int8 "
            "with an f32 scale sidecar (parallel/collectives.py, "
            "EQuARX-style — the int8 KV pools' proven scheme): the "
            "per-layer o-proj activation all-gather at serving_mp > 1, "
            "and the dp gradient psum in Model.fit (reduce-scatter on int8 "
            "shards + f32 dequant-accumulate + all-gather). ~0.5x the "
            "bf16 wire bytes, ~0.25x f32. Off (default) = every wire "
            "byte-identical to today. Read at program-BUILD time like "
            "every serving flag (it joins the jit program keys; "
            "warm() covers it), so flip it before constructing (or "
            "warming) an engine or calling fit "
            "(also: PADDLE_TPU_QUANTIZED_COLLECTIVES)",
            env_aliases=("PADDLE_TPU_QUANTIZED_COLLECTIVES",))

define_flag("speculative", "off",
            "speculative decoding policy of the serving engine "
            "(serving/speculative.py): 'ngram' drafts k tokens per "
            "slot host-side by prompt-lookup (match the last n "
            "generated tokens against the request's own prompt + "
            "history and propose the continuation — no draft model), "
            "'draft' runs a small draft llama on its own tiny paged "
            "pools; either way the target model verifies all k "
            "drafts + the pending token as ONE ragged window "
            "(new_len=k+1) through the same paged attention kernel, "
            "greedy acceptance keeps the longest matching prefix "
            "plus one corrected token, and rejection is pure length "
            "bookkeeping. 'off' (default) = today's one-token-per-"
            "step path, byte-identical. Read when a paged program / "
            "engine is BUILT (spec_k joins every program key; "
            "warm() covers it), so flip it before constructing (or "
            "warming) an engine (also: PADDLE_TPU_SPECULATIVE)",
            env_aliases=("PADDLE_TPU_SPECULATIVE",))
define_flag("spec_k", 4,
            "tokens drafted per slot per speculative step (the "
            "verify window is spec_k+1 rows). Read at engine BUILD "
            "time alongside `speculative` (also: PADDLE_TPU_SPEC_K)",
            env_aliases=("PADDLE_TPU_SPEC_K",))
define_flag("spec_adaptive", False,
            "acceptance-adaptive speculative draft depth: a pure HOST "
            "policy (serving/speculative.py AdaptiveSpecPolicy) that "
            "shrinks the active draft window when the measured "
            "acceptance_rate says drafts are being wasted and grows "
            "it back when acceptance recovers. The verify program is "
            "ragged over new_lens, so every effective k <= spec_k "
            "rides the ONE already-warmed window program — no new "
            "compiles ever (spec_k_effective in engine.metrics() "
            "reports the live depth). Off (default) = fixed spec_k. "
            "Read at engine BUILD time "
            "(also: PADDLE_TPU_SPEC_ADAPTIVE)",
            env_aliases=("PADDLE_TPU_SPEC_ADAPTIVE",))

define_flag("compile_cache", "",
            "persistent XLA compile-cache directory for the serving "
            "engine (serving/compile_cache.py): jax's compilation "
            "cache lives there from engine build on, so a fleet "
            "restart / elastic scale-out serves warm()'s program zoo "
            "from disk instead of recompiling (warm_compile_stats in "
            "engine.metrics() reports cold vs warm counts). Empty "
            "(default) = the fixed <checkout>/.jax_cache. Never "
            "overrides JAX_COMPILATION_CACHE_DIR "
            "(also: PADDLE_TPU_COMPILE_CACHE)",
            env_aliases=("PADDLE_TPU_COMPILE_CACHE",))
define_flag("tuned_config", "",
            "path of a persisted TunedConfig artifact "
            "(analysis/tuner.py, .paddle_tpu_tune.json; a directory "
            "means <dir>/.paddle_tpu_tune.json): non-empty makes "
            "ContinuousBatchingEngine default its build-time knobs "
            "(kv_cache_dtype, unified_step, serving_mp, "
            "quantized_collectives, token_budget, "
            "block_size) from the autotuner's winner; explicit "
            "engine kwargs still win per knob. A stale artifact "
            "(schema/model mismatch) is ignored with a warning. "
            "Empty (default) = off "
            "(also: PADDLE_TPU_TUNED_CONFIG)",
            env_aliases=("PADDLE_TPU_TUNED_CONFIG",))
define_flag("fleet_heartbeat_s", 0.25,
            "decode-fleet worker heartbeat interval in seconds "
            "(serving/fleet.py): each worker renews a TTL lease in the "
            "fleet store every interval; a lease older than 4x the "
            "interval marks the worker dead and triggers fencing + "
            "in-flight request recovery "
            "(also: PADDLE_TPU_FLEET_HEARTBEAT_S)",
            env_aliases=("PADDLE_TPU_FLEET_HEARTBEAT_S",))
define_flag("router_max_queue", 64,
            "SLO router queue-depth bound (serving/router.py): the "
            "admission cap for LOW-priority requests; normal gets 2x, "
            "high 4x. Beyond its class cap a request is shed with a "
            "structured Rejected(reason='overloaded', retry_after_s) "
            "instead of growing an unbounded backlog "
            "(also: PADDLE_TPU_ROUTER_MAX_QUEUE)",
            env_aliases=("PADDLE_TPU_ROUTER_MAX_QUEUE",))

# --- observability (paddle_tpu.observability) ---
define_flag("trace", "",
            "host span tracing: a non-empty value arms the global "
            "observability tracer and is the chrome-trace/Perfetto "
            "JSON export path (written at exit, or via "
            "observability.trace.export_global()). Empty (default) = "
            "off with a no-allocation fast path "
            "(also: PADDLE_TPU_TRACE)",
            env_aliases=("PADDLE_TPU_TRACE",))
define_flag("metrics", False,
            "arm the global observability metrics registry (TTFT / "
            "TPOT / queue-wait / chunk-time histograms, resilience "
            "event log; snapshot()/emit_jsonl()/prometheus_text()). "
            "Off (default) = a single is-None check per site "
            "(also: PADDLE_TPU_METRICS)",
            env_aliases=("PADDLE_TPU_METRICS",))

# --- resilience (paddle_tpu.resilience) ---
define_flag("tpu_chaos", "",
            "fault-injection spec, e.g. 'io_error:0.1,preempt_at:200,"
            "hang:decode' (also: PADDLE_TPU_CHAOS; see resilience/chaos.py)",
            env_aliases=("PADDLE_TPU_CHAOS",))
define_flag("tpu_chaos_seed", 0,
            "seed of the deterministic chaos schedule "
            "(also: PADDLE_TPU_CHAOS_SEED)",
            env_aliases=("PADDLE_TPU_CHAOS_SEED",))
define_flag("io_retry_attempts", 3,
            "attempts for transient-IOError retry at the io seams "
            "(shard reads, DataLoader fetch); 1 disables retrying "
            "(also: PADDLE_TPU_IO_RETRIES)",
            env_aliases=("PADDLE_TPU_IO_RETRIES",))
define_flag("io_retry_base_delay_s", 0.05,
            "first backoff delay of the io RetryPolicy (doubles per "
            "retry, jittered)")
define_flag("step_timeout_s", 0.0,
            "default wall-clock watchdog deadline per serving-engine "
            "step; 0 disables (also: PADDLE_TPU_STEP_TIMEOUT_S)",
            env_aliases=("PADDLE_TPU_STEP_TIMEOUT_S",))
define_flag("barrier_timeout_s", 60.0,
            "default deadline of a gang coordination barrier "
            "(resilience/coordination.py): how long a host waits for "
            "its peers at a checkpoint stage/commit or generation "
            "agreement before raising a structured BarrierTimeout "
            "naming the missing ranks (also: "
            "PADDLE_TPU_BARRIER_TIMEOUT_S)",
            env_aliases=("PADDLE_TPU_BARRIER_TIMEOUT_S",))
