"""Continuous-batching serving engine over the paged KV cache.

The scheduler layer the paged cache exists for (reference contract:
python/paddle/incubate/nn/functional/block_multihead_attention.py:25 —
block tables + per-sequence lengths serve a ragged, CHANGING batch):
new prompts enter while other sequences decode, finished rows retire
mid-stream, and their pages recycle into the live pool. Static batching
waits for a full batch and holds every slot until the slowest row ends;
this engine keeps the decode program's slots full instead.

TPU-first design: the decode program is compiled ONCE for a fixed slot
count and scans `steps_per_sync` tokens per invocation (multi-step
scheduling), so host<->device round-trips amortise over the chunk.
Admission, retirement and page accounting are host-side between chunks;
the device only ever sees fixed shapes:

- per-layer K/V pools [max_pages, Hkv, block_size, D] (donated through
  every program, so pages are updated in place);
- a block table [slots, table_width] mapping each slot's logical blocks
  to pool pages (retired/empty slots point at a reserved scratch page);
- per-slot lengths/tokens/budgets/done flags.

Two serving optimisations ride on that substrate (see README.md in this
directory for the full design):

**Block-aligned prefix caching.** Every full `block_size`-token prompt
block is hashed (chained, so a hit implies the whole prefix matches)
into `PagedKVManager`'s refcounted page cache. An admitted request maps
its cached prefix pages straight into its block table and prefills only
the uncached suffix — suffix-bucketed, so prefill programs stay keyed
by (bucket, batch, prefix-width rung) over a small warm-able ladder and
compile counts don't grow with hit patterns. The suffix attends over
the cached prefix through the ragged paged prefix-prefill Pallas
kernel by default (FLAGS_prefix_prefill_kernel; jnp fallback retained).
Retire paths release references; a page recycles only at refcount 0
(LRU-evicted under pool pressure), so a hung-slot retire can never pull
a shared prefix out from under a surviving slot.

**Double-buffered scheduling.** In pipelined mode the engine dispatches
decode chunk N+1 — its token/length inputs chained on chunk N's
device-side outputs — BEFORE blocking on chunk N's host-visible
results, hiding the per-sync host RTT behind device compute. Host-side
changes (admission, retirement) override the chained values per slot at
the next dispatch; a per-row budget length freezes rows on-device at
prompt+max_new so a speculatively-dispatched chunk can never write past
a request's reserved pages. Because the KV pools are donated through
every program, device programs serialize in dispatch order — a stale
chunk's writes for a retired row always land before any new owner of
those pages scatters or reads them.

**int8 KV cache** (FLAGS_kv_cache_dtype=int8, default bf16): the paged
pools become (int8, per-(page, kv-head) f32 absmax scale) pairs —
quantized on the K/V page scatter, dequantized inside the Pallas
kernels, halving the HBM bytes every decode / prefix-prefill step
streams and doubling the pages (and therefore cacheable prefix blocks,
`n_cacheable_pages`) a byte budget holds. Page-count capacity math is
unchanged; `kv_pool_bytes=` sizes the pool by bytes instead.

Weights go through the `_decode_params` layout (`_mm`), so dense AND
weight-only int8/int4 serving compose with the engine unchanged (and
with the int8 KV cache: weight quant and KV quant are independent).

**Tensor-parallel serving** (FLAGS_serving_mp, default 1): the paged
pools and their int8 scale sidecars shard by KV HEAD across an `mp`
mesh; block tables, budgets, lengths and every other scheduling input
replicate, so page ids mean the same thing on every chip and all host
bookkeeping above is untouched. Each device program runs under
shard_map — prefill, prefix prefill and the decode chunk all stream
only their shard's kv heads — and the sole cross-chip traffic is the
per-layer all-gather of the o-proj activations (the per-shard
attention outputs; the o-proj itself and the whole MLP/lm-head tail
compute replicated, which keeps every per-element computation
identical to the single-chip program: mp=2/4 is TOKEN-IDENTICAL, not
just close). Per-chip KV bytes drop to 1/mp at the same aggregate
page capacity — the lever that lets batch x context outgrow one
chip's HBM. MQA models (nkv=1) fall back to replicated pools with
sharded query heads, warned at build time.

**Prefill/decode disaggregation** (`disaggregated=True`): admission
(the prefill worker) and decode chunking (the decode worker) decouple
— prefill runs up to `slots` requests ahead into a handoff queue
without waiting for a free decode slot, and the decode worker maps
handed-off requests into slots as they free. Because the pools are
shared (and sharded), the "KV transfer" between workers is nothing
but block-table bookkeeping, and the refcounted prefix cache is a
cross-worker resource: a prefill-worker insert serves later decode-
worker admissions, and retire paths on either side only ever release
references.
"""
from __future__ import annotations

import threading
import time
from collections import namedtuple
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.llama import (MOE_COUNTS, PagedKVManager, _make_chunk_prefill,
                            _make_decode_step, _make_head_logits,
                            _make_prefill, _make_prefill_with_prefix,
                            _make_verify_window, _sample_next,
                            hash_prefix_blocks, make_paged_kv_helpers,
                            make_paged_kv_q8_helpers, make_serving_tp,
                            resolve_kv_cache_dtype, resolve_serving_cp,
                            resolve_serving_mp, resolve_unified_step,
                            served_model, serving_param_specs,
                            shard_serving_params)
from ..observability import metrics as obs_metrics
from ..observability import trace as obs_trace
from ..observability.trace import _NULL_SPAN
from ..resilience import chaos


def _moved(direction: str, arrays) -> dict:
    """A transfer's span arguments: how many arrays (`h2d` / `d2h`) and
    how many bytes (`h2d_bytes` / `d2h_bytes`) it moved."""
    leaves = jax.tree.leaves(arrays)
    return {direction: len(leaves),
            direction + "_bytes": sum(int(a.nbytes) for a in leaves)}


def _token_logprob(logits, tok):
    """log softmax(logits)[tok], [B, V] and [B] -> f32 [B]."""
    logits = logits.astype(jnp.float32)
    return jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0] \
        - jax.nn.logsumexp(logits, axis=-1)


class _Flat:
    """Named fields at fixed offsets in one flat int32 vector: how a served
    program's host inputs go in (one transfer) and its host-visible outputs
    come back (one copy). A field is int32, bool (as 0 / 1) or float32 (its
    bits). `pack` and `unpack` take numpy arrays on the host and traced
    arrays inside a program alike; `pack` broadcasts a scalar over its
    field, and ignores values no field names."""

    def __init__(self, fields):
        self.fields, self.size = [], 0
        for name, shape, dtype in fields:
            n = int(np.prod(shape))
            self.fields.append((name, shape, np.dtype(dtype), self.size, n))
            self.size += n
        self.names = [f[0] for f in self.fields]

    def pack(self, values: dict):
        xp = jnp if any(isinstance(values[name], jax.Array)
                        for name in self.names) else np
        parts = []
        for name, shape, dtype, _, _ in self.fields:
            x = xp.broadcast_to(xp.asarray(values[name], dtype), shape)
            if dtype == np.float32:
                x = jax.lax.bitcast_convert_type(x, jnp.int32) \
                    if xp is jnp else x.view(np.int32)
            parts.append(x.astype(np.int32).reshape(-1))
        return xp.concatenate(parts)

    def unpack(self, flat) -> dict:
        out = {}
        for name, shape, dtype, off, n in self.fields:
            x = flat[off:off + n].reshape(shape)
            if dtype == np.float32:
                x = x.view(np.float32) if isinstance(flat, np.ndarray) \
                    else jax.lax.bitcast_convert_type(x, jnp.float32)
            elif dtype == bool:
                x = x != 0
            out[name] = x
        return out


@dataclass
class ServeRequest:
    """One generation request tracked through the engine."""
    req_id: int
    prompt: list
    max_new: int
    arrival_time: float = 0.0
    # filled by the engine
    tokens: list = field(default_factory=list)
    # log-probability of each of `tokens` under the distribution it was
    # chosen from (engine option `logprobs`; empty without it)
    logprobs: list = field(default_factory=list)
    prefill_time: Optional[float] = None   # when the first token was ready
    finish_time: Optional[float] = None
    failed: bool = False                   # retired by the watchdog
    error: Optional[str] = None
    requeued: bool = False                 # already got its one retry
                                           # (run(requeue_hung=True))
    # host-side scheduling state (None until admitted)
    slot: Optional[int] = None
    pages: Optional[list] = None
    ring: Optional[list] = None            # window layers' ring, if any
    bucket: Optional[int] = None           # suffix bucket it prefilled at
    n_prefix: int = 0                      # cached prefix blocks mapped in
    cached_tokens: int = 0                 # prompt tokens served from cache
    # chained block hashes, computed ONCE at add_request — _plan runs
    # for every waiting request on every scheduling step
    block_hashes: Optional[list] = None
    # SLO metadata (ISSUE 17): set by the caller, carried through
    # handoff untouched, surfaced in req.enqueue/req.retire instants —
    # the engine itself never sheds on them (that is the router's job)
    priority: str = "normal"
    deadline_s: Optional[float] = None

    @property
    def done(self) -> bool:
        return self.finish_time is not None


class _AbandonedStep(RuntimeError):
    """Raised inside a watchdog-abandoned step thread at its next
    checkpoint: the main loop moved on, this thread must not commit."""


class _Slot:
    __slots__ = ("req", "length", "emitted", "done")

    def __init__(self):
        self.req = None        # ServeRequest or None (free)
        self.length = 0        # tokens cached (prompt + emitted - 1 pending)
        self.emitted = 0       # new tokens produced so far
        self.done = False      # EOS seen inside a chunk


# admission plan for one waiting request: suffix bucket, cached prefix
# blocks, cached pages currently refcount-0 (they leave the available
# pool on acquire), private pages to reserve, true suffix length
_Plan = namedtuple("_Plan", "sb_suf n_cached n_lru need suffix_len")


class ContinuousBatchingEngine:
    """vLLM-class continuous batching over `PagedKVManager`.

    Usage::

        eng = ContinuousBatchingEngine(cfg, dec_params, slots=8,
                                       max_new_tokens=64,
                                       eos_token_id=2)
        eng.add_request([1, 5, 9, ...])
        eng.run()                      # until all queues drain
        for req in eng.finished: print(req.tokens)

    What the model is, the config says (`models.llama.served_model`): a
    model with sliding-window layers keeps those layers' K/V in a second
    kind of pool — a ring a live sequence, `window + token_budget` tokens
    and a page, reserved whole at admission beside the request's pages and
    never growing (`PagedKVManager.set_window_rings`) — and serves without
    the prefix cache (`metrics()["prefix_cache_off"]`); a model with routed
    layers counts what they did inside the programs (`metrics()["moe_*"]`).
    Both are served by the unified step and the decode chunk, one chip,
    bf16 pools; every other option refuses such a model by name.

    Scheduling policy: FIFO admission; a request is admitted when a slot
    is free AND the pool can hold its full per-request capacity
    (cached prefix blocks map in for free; ceil((bucketed_suffix +
    req.max_new) / block_size) private pages are reserved, so no
    preemption is ever needed). Prefill runs batched per (suffix bucket,
    cached-vs-cold) run of waiting requests; decode runs
    `steps_per_sync` tokens for ALL slots per invocation, then the host
    retires EOS/finished rows and admits from the wait queue. With
    `double_buffer` the next chunk is dispatched before the previous
    chunk's results are read back (see module docstring).
    """

    # a commit wait longer than this counts as a blocked sync (the host
    # sat idle waiting on the device) — the stat double buffering exists
    # to shrink
    stall_threshold_s = 1e-3
    # class-level default: the watchdog's no-live-slot path reads this
    # on engines that never reached the unified-path init
    _prefilling = None

    def __init__(self, cfg, dec_params, *, slots: int = 8,
                 prompt_bucket: int = 64, max_prompt_len: int = 512,
                 max_new_tokens: int = 64,
                 block_size: Optional[int] = None,
                 max_pages: Optional[int] = None, steps_per_sync: int = 8,
                 prefill_batch: int = 4,
                 eos_token_id: Optional[int] = None, do_sample: bool = False,
                 top_k: int = 0, temperature: float = 1.0,
                 top_p: float = 1.0, seed: int = 0, dtype=jnp.bfloat16,
                 prefix_cache: bool = True, double_buffer: bool = False,
                 kv_cache_dtype: Optional[str] = None,
                 kv_pool_bytes: Optional[int] = None,
                 serving_mp: Optional[int] = None,
                 serving_cp: Optional[int] = None,
                 quantized_collectives: Optional[bool] = None,
                 disaggregated: bool = False,
                 unified_step=None, token_budget: Optional[int] = None,
                 speculative: Optional[str] = None,
                 spec_k: Optional[int] = None, drafter=None,
                 spec_adaptive: Optional[bool] = None,
                 logprobs: bool = False,
                 config=None, tracer=None, metrics=None):
        """`kv_cache_dtype` ('bf16' | 'int8'; default from
        FLAGS_kv_cache_dtype / PADDLE_TPU_KV_CACHE_DTYPE) picks the
        paged-pool element type: int8 pools halve the HBM bytes every
        decode / prefix-prefill step streams and carry per-(page, kv
        head) f32 absmax scales (quantized on the page scatter,
        dequantized in-kernel). `kv_pool_bytes` sizes the pool by a
        DEVICE BYTE budget instead of `max_pages` — at the same budget
        an int8 pool holds ~2x the pages, i.e. ~2x `n_cacheable_pages`
        before LRU eviction; under kv-head sharding the budget is
        PER-CHIP, so mp shards hold ~mp x the aggregate pages.

        `serving_mp` (default from FLAGS_serving_mp /
        PADDLE_TPU_SERVING_MP, resolved HERE at build time like the
        kv-dtype flag — it joins every program key and `warm()`
        covers it) shards the engine across an `mp` mesh: the
        paged K/V pools and their int8 scale sidecars shard by kv head,
        block tables / budgets / slot state replicate, and every device
        program runs under shard_map with ONE cross-chip collective per
        layer (the o-proj activation all-gather). mp=1 is byte-
        identical to a build without the flag. Models whose kv heads
        don't divide mp (MQA) fall back to replicated-KV
        head-sharded-Q with a build-time warning.

        `quantized_collectives` (ISSUE 15; default from
        FLAGS_quantized_collectives /
        PADDLE_TPU_QUANTIZED_COLLECTIVES, resolved HERE at build time
        like every serving flag — it joins every program key and
        `warm()` covers it) ships the per-layer o-proj activation
        all-gather at mp > 1 as absmax-scaled int8 blocks + an f32
        scale sidecar (`parallel/collectives.py`, the int8 KV pools'
        proven scheme):
        ~0.5x the bf16 wire bytes per token at quantization-noise
        accuracy (the token-match gate is the int8-KV bar, not
        identity). OFF (default) keeps every wire byte-identical; at
        mp=1 the flag is key-only (no collectives exist).

        `serving_cp` (ISSUE 18; default from FLAGS_serving_cp /
        PADDLE_TPU_SERVING_CP, resolved HERE at build time like every
        serving flag — it joins every program key and `warm()` covers
        it) shards the paged pools along the PAGE axis across a `cp`
        mesh axis, composable with `serving_mp` as a 2-D `cp x mp`
        serving mesh: global page id g lives on cp shard
        g // (max_pages / cp), block tables stay replicated (global
        ids), each shard streams only its LOCAL pages as
        online-softmax partials, and the per-layer cross-chip merge
        ships only (m, l, weighted acc) stats — never the KV — via
        `ServingTP.merge_attn_partials`. A `kv_pool_bytes` budget
        stays PER-CHIP, so cp shards hold cp x the fleet pages: the
        per-request context ceiling grows cp x. cp=1 is byte-
        identical to a build without the flag.

        `unified_step` (ISSUE 14; default from FLAGS_unified_step /
        PADDLE_TPU_UNIFIED_STEP, 'auto' = ON, resolved HERE at
        build time like every other serving flag) serves prefill
        through the UNIFIED ragged step: ONE
        chunked-prefill+decode-chunk program over
        `ragged_paged_attention` replaces the whole (suffix bucket x
        batch x prefix-width rung) prefill program zoo. Admission
        becomes token-budget packing — the FIFO head request is
        admitted when its EXACT page reservation
        (ceil((prompt - cached + max_new)/block) private pages, no
        bucket rounding, cached prefix blocks free and never trimmed)
        fits the pool, and its prompt then streams through
        `token_budget`-token windows interleaved with every live
        slot's decode chunk — a 100k-token prompt can no longer
        head-of-line-block decode. Pure-decode steps keep dispatching
        the plain decode-chunk program (bitwise the split engine's
        steady state, multi-step sync amortization included). The
        split path stays available as the oracle
        (`unified_step=False`).

        `token_budget` is the prefill window width in tokens (a
        multiple of `block_size`; default = the prompt bucket): each
        mixed step advances the prefilling prompt by up to this many
        tokens next to `slots x steps_per_sync` decode tokens.

        `disaggregated` splits scheduling into a PREFILL worker and a
        DECODE worker with paged-KV handoff: admission prefills up to
        `slots` requests ahead without waiting for a free decode slot
        (their pages — sharded under mp — are already resident), and
        the decode worker maps handed-off requests into slots via the
        replicated block table; the refcounted prefix cache is shared
        by both workers. Token output is identical to the unified
        scheduler; what changes is that prefill admission no longer
        queues behind decode slot occupancy.

        `logprobs` hands out, beside each generated token, its
        log-probability under the softmax of the logits it was chosen
        from (`ServeRequest.logprobs`, one a token): the decode chunk
        and the unified step return them with the tokens the commit
        waits for anyway. Off (default) no program changes. Built for
        the unified step and the decode chunk; refused by name beside
        `unified_step=False`, speculation and `disaggregated`.

        `tracer` / `metrics` (observability, ISSUE 8): an
        `observability.Tracer` records the full request lifecycle
        (enqueue -> admit -> prefill dispatch/commit -> handoff ->
        per-chunk decode -> retire, plus eviction / watchdog /
        double-buffer-stall events) as Perfetto-exportable spans; a
        `MetricsRegistry` accumulates the TTFT / TPOT / queue-wait /
        chunk-time / sync-wait histograms and the structured event
        log. Default (None): the flag-armed globals (FLAGS_trace /
        FLAGS_metrics), i.e. off unless the operator opted in — every
        instrumented site is then one `is None` check. Pass False to
        force OFF even when the global flags are armed (an untraced
        baseline must stay untraced).

        `config` (ISSUE 16): a `TunedConfig` artifact from the static
        autotuner (`analysis/tuner.py`) — or a dict / a path to a
        persisted `.paddle_tpu_tune.json` — that DEFAULTS every
        build-time knob the tuner swept (kv_cache_dtype,
        unified_step, serving_mp, quantized_collectives,
        token_budget, block_size). Explicit kwargs win per knob; the
        flag-registry defaults only apply to
        knobs neither the caller nor the artifact set. None follows
        FLAGS_tuned_config / PADDLE_TPU_TUNED_CONFIG (a stale
        flag-loaded artifact warns and is ignored; a stale EXPLICIT
        one raises — the operator named it, so silence would serve
        the wrong config); False forces OFF even when the flag is
        set. The persistent compile cache (serving/compile_cache.py:
        JAX_COMPILATION_CACHE_DIR, else FLAGS_compile_cache, else a
        fixed path in the checkout) is enabled at build time, and
        `warm()` reports cold-vs-warm compile counts on
        `metrics()['warm_compile_stats']`."""
        # tuned-config artifact (analysis/tuner.py): fill unset
        # build-time knobs from the autotuner's winner BEFORE any flag
        # resolution below — the resolve_* helpers only see a value
        # when the caller or the artifact pinned one
        self.tuned_config = self._resolve_tuned_config(config, cfg)
        if self.tuned_config is not None:
            merged = self.tuned_config.apply(dict(
                kv_cache_dtype=kv_cache_dtype,
                unified_step=unified_step, serving_mp=serving_mp,
                serving_cp=serving_cp,
                quantized_collectives=quantized_collectives,
                token_budget=token_budget, block_size=block_size,
                speculative=speculative, spec_k=spec_k))
            kv_cache_dtype = merged["kv_cache_dtype"]
            unified_step = merged["unified_step"]
            serving_mp = merged["serving_mp"]
            serving_cp = merged.get("serving_cp", serving_cp)
            quantized_collectives = merged["quantized_collectives"]
            token_budget = merged["token_budget"]
            block_size = merged["block_size"]
            speculative = merged.get("speculative", speculative)
            spec_k = merged.get("spec_k", spec_k)
            spec_adaptive = merged.get("spec_adaptive", spec_adaptive)
        if block_size is None:
            block_size = 64
        block_size = int(block_size)
        # persistent compile cache: on at build time so this engine's
        # warm() compiles persist to (and load from) disk; WHERE it
        # lives is compile_cache.enable_compile_cache's one decision
        from . import compile_cache as _compile_cache

        _compile_cache.enable_compile_cache()
        self.warm_compile_stats = None  # set by warm()
        if prompt_bucket % block_size:
            raise ValueError(
                f"prompt_bucket {prompt_bucket} must be a whole number of "
                f"KV pages (multiple of block_size {block_size}) so "
                f"prefill scatters whole pages")
        self.cfg = cfg
        self.p = dec_params
        self.slots = slots
        self.prompt_bucket = prompt_bucket
        self.max_prompt_len = -(-max_prompt_len // prompt_bucket) \
            * prompt_bucket
        self.max_new = max_new_tokens
        self.block_size = block_size
        self.steps = steps_per_sync
        self.prefill_batch = max(1, prefill_batch)
        self.eos = eos_token_id
        self.do_sample = do_sample
        self.top_k = int(top_k)
        self.temperature = temperature
        self.top_p = top_p
        self.prefix_cache = bool(prefix_cache)
        self.double_buffer = bool(double_buffer)
        # pool dtype is baked into every program at build time (like
        # FLAGS_prefix_prefill_kernel); it also joins the program-cache
        # keys so the compile-point helpers can never mix dtypes
        self.kv_dtype = resolve_kv_cache_dtype(kv_cache_dtype)
        # unified ragged step (FLAGS_unified_step, ISSUE 14), resolved
        # at build time like the flags above: ONE chunked-prefill +
        # decode program instead of the split prefill program zoo
        self.unified = resolve_unified_step(unified_step)
        if token_budget is None:
            token_budget = prompt_bucket
        if token_budget % block_size or token_budget < block_size:
            raise ValueError(
                f"token_budget {token_budget} must be a whole number "
                f"of KV pages (multiple of block_size {block_size}) so "
                "chunk boundaries stay page-aligned and the window "
                "scatter writes whole pages")
        self.token_budget = int(token_budget)
        # tensor-parallel degree (FLAGS_serving_mp), resolved at build
        # time like the flags above; mp=1 builds exactly the single-chip
        # programs (no mesh, no shard_map — byte-identical)
        self.mp = resolve_serving_mp(serving_mp)
        # context-parallel degree (FLAGS_serving_cp, ISSUE 18),
        # resolved at build time like mp; cp=1 builds exactly the
        # page-replicated programs (byte-identical)
        self.cp = resolve_serving_cp(serving_cp)
        # quantized collectives (ISSUE 15), resolved at build time like
        # the flags above — resolved even at mp=1 so the flag rides the
        # program keys uniformly (it is a no-op there: no collectives)
        from ..parallel.collectives import resolve_quantized_collectives

        self.quantized_collectives = resolve_quantized_collectives(
            quantized_collectives)
        # speculative decoding (ISSUE 19), resolved at build time like
        # every serving flag: the policy + draft depth bake into the
        # verify program, spec_k joins every program key, and warm()
        # covers it — "off" builds byte-identical to a build without
        # the flag (no verify program, no drafter, today's step loop)
        from .speculative import (AdaptiveSpecPolicy, NGramDrafter,
                                  resolve_spec_adaptive, resolve_spec_k,
                                  resolve_speculative)

        self.speculative = resolve_speculative(speculative)
        self.spec_k = resolve_spec_k(spec_k or None) \
            if self.speculative != "off" else 0
        # acceptance-adaptive draft depth (pure host policy: the verify
        # window stays spec_k+1 rows, only the per-step `want` cap
        # moves — no program key change, no new compiles)
        self.spec_adaptive = resolve_spec_adaptive(spec_adaptive) \
            if self.spec_k else False
        self._spec_policy = AdaptiveSpecPolicy(self.spec_k) \
            if self.spec_adaptive else None
        self._drafter = None
        if self.speculative != "off":
            if do_sample:
                raise ValueError(
                    "speculative decoding is greedy-only: acceptance "
                    "compares drafts against the target's argmax "
                    "(draft-aware sampling is a ROADMAP follow-up) — "
                    "build with do_sample=False or speculative='off'")
            if self.cp > 1:
                raise ValueError(
                    "speculative decoding does not compose with "
                    "serving_cp yet (page-sharded partial-attention "
                    "merge of a multi-row verify window is a ROADMAP "
                    "follow-up)")
            if self.speculative == "draft":
                if drafter is None:
                    raise ValueError(
                        "speculative='draft' needs a DraftModelDrafter "
                        "(its config + params) via drafter=")
                self._drafter = drafter
            else:
                self._drafter = drafter if drafter is not None \
                    else NGramDrafter()
        # the model as the program builders read it (models/llama.py's
        # contract): which layers keep a window, which are routed
        self._model = served_model(cfg)
        self._window_layers = frozenset(self._model.window_layers)
        # the window those layers keep (one width a model), 0 without any
        self._window = max((self._model.layers[i].window
                            for i in self._window_layers), default=0)
        self._routed = self._model.routed
        # why the prefix cache is off, where the model turns it off
        self.prefix_cache_off = None
        if self._window_layers or self._routed:
            what = " and ".join(
                w for w, on in (("sliding-window", self._window_layers),
                                ("routed-expert", self._routed)) if on)
            for name, on in (
                    ("unified_step=False (the split prefill programs)",
                     not self.unified),
                    (f"speculative={self.speculative!r}",
                     self.speculative != "off"),
                    (f"serving_mp={self.mp}", self.mp > 1),
                    (f"serving_cp={self.cp}", self.cp > 1),
                    ("disaggregated=True", bool(disaggregated)),
                    ("kv_cache_dtype='int8'", self.kv_dtype == "int8")):
                if on:
                    raise ValueError(
                        f"{name} is not built for a model with {what} "
                        "layers: they are served by the unified step and "
                        "the decode chunk on one chip, bf16 pools "
                        "(ROADMAP M2 / M3)")
            if self._window_layers and self.prefix_cache:
                self.prefix_cache = False
                self.prefix_cache_off = (
                    "the model has sliding-window layers: their K/V live "
                    "in per-sequence rings, which hold no page a later "
                    "request could map")
        self._tp = make_serving_tp(
            cfg, self.mp,
            quantized_collectives=self.quantized_collectives,
            serving_cp=self.cp)
        self.mp_mesh = None
        if self._tp is not None:
            from ..parallel.mesh import serving_mesh

            self.mp_mesh = serving_mesh(self.mp, cp=self.cp)
        # kv-head shard count of the POOLS: mp when they shard, 1 when
        # replicated (single-chip or the MQA fallback) — the geometry
        # byte accounting and budget sizing run on
        self.kv_shards = self.mp if (self._tp is not None
                                     and self._tp.kv_sharded) else 1
        # prefill/decode disaggregation: prefilled-but-unslotted
        # requests wait here with their pages already committed
        self.disaggregated = bool(disaggregated)
        self.logprobs = bool(logprobs)
        if self.logprobs:
            for name, on in (
                    ("unified_step=False (the split prefill programs)",
                     not self.unified),
                    (f"speculative={self.speculative!r}",
                     self.speculative != "off"),
                    ("disaggregated=True", self.disaggregated)):
                if on:
                    raise ValueError(
                        f"{name} is not built with logprobs=True: the "
                        "unified step and the decode chunk return them")
        self._handoff: list[ServeRequest] = []
        self.prefill_handoffs = 0   # requests that crossed the handoff
        # pool capacity: every slot simultaneously full-length at the
        # ENGINE budget, +1 scratch page. Per-request reservations are
        # never larger — _plan TRIMS a cached prefix until the hit
        # path's total pages (cached blocks + bucketed-suffix capacity)
        # fit the cold-path worst case, because a block-aligned but not
        # bucket-aligned prefix widens the suffix bucket and could
        # otherwise out-reserve the pool the cold path was sized for
        # (admission would livelock) — so admission cannot deadlock and
        # the cold-path width bounds every block table
        cap = self._capacity_pages(self.max_prompt_len)
        self.table_width = cap
        # widest cached prefix any request can map (>= 1 suffix token
        # always prefills, so the last block is never part of a prefix)
        self._prefix_width = max(1, (self.max_prompt_len - 1) // block_size)
        nkv, dh = cfg.num_key_value_heads, self._model.head_dim
        # layers of the first pool kind (whole contexts on pages) and of
        # the second (a ring a live sequence: `slots` + the one prefilling)
        n_full = len(self._model.layers) - len(self._window_layers)
        n_rings = slots + 1 if self._window_layers else 0
        if kv_pool_bytes is not None:
            if max_pages is not None:
                raise ValueError(
                    "pass max_pages OR kv_pool_bytes, not both")
            # the budget is both kinds': the rings' share comes off first
            ring_bytes = 0
            if n_rings:
                ring_bytes = (n_rings * self._capacity_ring_pages() + 1) \
                    * PagedKVManager.page_bytes(
                        block_size, n_layers=len(self._window_layers),
                        num_kv_heads=nkv, head_dim=dh)
            # PER-CHIP budget: under kv-head sharding each chip holds
            # only nkv/mp heads of every page, so the same per-chip
            # bytes buy ~mp x the aggregate cacheable pages; under
            # page sharding (cp, ISSUE 18) each chip holds 1/cp of the
            # fleet's pages, so the same bytes buy cp x the FLEET page
            # count — the context-ceiling lift
            max_pages = PagedKVManager.pages_for_bytes(
                kv_pool_bytes - ring_bytes, block_size,
                n_layers=n_full, num_kv_heads=nkv,
                head_dim=dh, kv_cache_dtype=self.kv_dtype,
                mp=self.kv_shards, cp=self.cp)
            if max_pages < cap + 2:
                raise ValueError(
                    f"kv_pool_bytes {kv_pool_bytes} holds only "
                    f"{max_pages} pages at kv_cache_dtype="
                    f"{self.kv_dtype} (cp={self.cp}); need at least "
                    f"{cap + 2} "
                    "(one full request + scratch + one cacheable page)")
        if max_pages is None:
            # round the default up to a whole number of cp shards —
            # set_pool_geometry rejects a fleet count with ownerless
            # remainder pages (PageShardingError)
            max_pages = -(-(slots * cap + 1) // self.cp) * self.cp
        # the operator's explicit PER-CHIP pool byte budget (None when
        # sized by max_pages) — audit_memory() derives its default
        # TPU702 HBM budget from it
        self._kv_pool_budget = kv_pool_bytes
        self._memory_audit = None   # fleet report from the last audit
        self._comms_audit = None    # wire-side twin (ISSUE 11)
        self._roofline_audit = None  # compute-time leg (ISSUE 13)
        self.mgr = PagedKVManager(max_pages, block_size)
        self.mgr.set_pool_geometry(n_layers=n_full,
                                   num_kv_heads=nkv, head_dim=dh,
                                   kv_cache_dtype=self.kv_dtype,
                                   mp=self.kv_shards, cp=self.cp)
        if n_rings:
            self.mgr.set_window_rings(n_rings, self._capacity_ring_pages(),
                                      len(self._window_layers))
        self.scratch_page = self.mgr.alloc_pages(1)[0]  # retired rows' sink
        if self.kv_dtype == "int8":
            # (int8 pool, per-(page, kv head) f32 absmax scale) pairs —
            # every program threads the pair, so donation keeps scales
            # in place exactly like the pools
            def _pool():
                return (jnp.zeros((max_pages, nkv, block_size, dh),
                                  jnp.int8),
                        jnp.zeros((max_pages, nkv), jnp.float32))
        else:
            def _pool(n=max_pages):
                return jnp.zeros((n, nkv, block_size, dh), dtype)
        if self._tp is not None:
            # pools are BORN on the serving mesh (kv-head sharded, or
            # replicated under the MQA fallback): max_pages was sized
            # from a PER-CHIP byte budget, so materializing a full pool
            # on one chip and resharding would transiently hold mp x
            # that budget — the exact overflow kv-head sharding exists
            # to avoid. jit with out_shardings allocates each shard on
            # its own device; one compile covers all layers (k and v
            # entries share shape/dtype/spec).
            from jax.sharding import NamedSharding

            sp = self._pool_entry_spec()
            # sp is a (pool, scale) spec PAIR on int8, a single spec on
            # bf16 (PartitionSpec subclasses tuple, so key on the dtype)
            out = tuple(NamedSharding(self.mp_mesh, s) for s in sp) \
                if self.kv_dtype == "int8" \
                else NamedSharding(self.mp_mesh, sp)
            _pool.__name__ = "serve_kv_pool_init"
            _pool = jax.jit(_pool, out_shardings=out)
        if self._window_layers:
            # a window layer's pools are as large as the rings, whatever
            # the contexts grow to
            sizes = [self.mgr.window_pool_pages if i in self._window_layers
                     else max_pages for i in range(len(self._model.layers))]
            self.kcs = [_pool(n) for n in sizes]
            self.vcs = [_pool(n) for n in sizes]
        else:
            self.kcs = [_pool() for _ in range(cfg.num_hidden_layers)]
            self.vcs = [_pool() for _ in range(cfg.num_hidden_layers)]
        if self._tp is not None:
            # params per `serving_param_specs` (q/k/v columns sharded,
            # the rest — o-proj included — replicated). Logical shapes
            # are unchanged: the shard_map bodies see the local slices
            self.p = shard_serving_params(self.p, self.mp_mesh, self._tp)
            self._param_specs = serving_param_specs(self.p, self._tp)
        self._slots = [_Slot() for _ in range(slots)]
        self._tables = np.full((slots, cap), self.scratch_page, np.int32)
        # the window layers' tables: column j names ring page j % R of the
        # slot's ring (page 0 of their pools is the sink), set once a bind
        self._ring_tables = np.zeros((slots, cap), np.int32) \
            if self._window_layers else None
        # what the routed layers counted, summed over layers and steps
        # (MOE_COUNTS), for the decode lane and for the prefill windows
        self.moe_counts = {"decode": np.zeros(len(MOE_COUNTS), np.int64),
                           "chunk": np.zeros(len(MOE_COUNTS), np.int64)}
        self.window_tokens_dropped = 0   # cached tokens that left a window
        self._tokens = np.zeros((slots,), np.int32)
        self._budgets = np.zeros((slots,), np.int32)  # prompt + max_new
        # the key lives on the device: the decode chunk and the mixed
        # step split it themselves and hand the next one back
        self._key = self._replicated(jax.random.PRNGKey(seed))
        self._sampling_at = None    # (temperature, top_p) of _sampling_dev
        self.waiting: list[ServeRequest] = []
        self.finished: list[ServeRequest] = []
        self._next_id = 0
        self._prefill_cache = {}
        self._io = self._io_layouts()
        self._decode = self._program(
            self._build_decode_chunk(), "serve_decode_chunk", 6, 4)
        # the ONE mixed prefill+decode program (ISSUE 14) — built only
        # on the unified path; its shape key is (token_budget, slots,
        # steps, kv-dtype, mp) and warm() compiles it once
        self._unified = self._program(
            self._build_unified_step(), "serve_unified_step", 4, 2) \
            if self.unified else None
        # speculative verify: one ragged window of spec_k+1 rows per
        # slot scores every draft + the pending token in a single pass
        # (models/llama._make_verify_window); built only when the
        # policy is on, so "off" stays byte-identical
        self._verify = self._program(
            self._build_verify_chunk(), "serve_verify_chunk", 4, 1) \
            if self.spec_k else None
        # the request currently streaming prefill windows through the
        # unified step: {"req": ServeRequest, "done": tokens committed}
        self._prefilling = None
        self.prefill_chunks = 0  # unified prefill windows dispatched
        self.chunk_tokens = 0    # prompt tokens prefilled via windows
        self.device_steps = 0    # decode-chunk dispatches (for metrics)
        self.sched_iters = 0     # scheduling iterations (spans' `iter`)
        self._step_kind = "decode"  # this iteration's `sched.step` kind
        self.prefill_calls = 0   # batched-admission device calls
        self.spec_steps = 0      # speculative verify dispatches
        self.spec_drafted = 0    # draft tokens offered for verification
        self.spec_accepted = 0   # draft tokens the target agreed with
        self.hung_retired = 0    # slots retired by the watchdog
        self.hung_requeued = 0   # hung slots requeued (requeue_hung=)
        self._requeue_hung = False  # armed per run()
        self._admission_paused = False  # pause_admission() / drain()
        self.prefix_hit_tokens = 0   # prompt tokens served from cache
        self.prompt_tokens = 0       # prompt tokens admitted in total
        self.prefix_inserts = 0      # blocks registered into the cache
        self.sync_wait_s = 0.0   # host time blocked on decode readbacks
        self.blocked_syncs = 0   # readbacks that waited > stall threshold
        self._watchdog = None    # armed by run(watchdog_timeout=...)
        self._step_epoch = 0     # bumped on timeout; zombie steps abort
        # double-buffer pipeline state: the uncommitted in-flight chunk,
        # the device-side token/length carries chunk N+1 chains from,
        # and the per-slot mask saying "host state changed since the
        # last dispatch — override the chained value"
        self._inflight = None
        self._chain_tok = None
        self._chain_lens = None
        self._override = np.ones((slots,), bool)
        # what an unchained chunk passes for the carries: every row
        # overrides them
        self._no_chain = self._replicated(jnp.zeros((slots,), jnp.int32))
        # observability (ISSUE 8): None defers to the flag-armed
        # globals, False forces OFF regardless of flags (how an
        # untraced bench baseline stays untraced next to an armed
        # PADDLE_TPU_TRACE), an instance wins outright. The hot paths
        # hold the attribute and branch once per event, so the
        # disabled overhead is unmeasurable (bench_continuous --trace
        # asserts < 2% tokens/s)
        self._tracer = obs_trace.get_tracer() if tracer is None \
            else (tracer or None)
        self._metrics = obs_metrics.get_metrics() if metrics is None \
            else (metrics or None)
        self._evictions_seen = 0  # mgr.prefix_evictions already reported
        # makes ownership-check + device dispatch + host-state commit
        # atomic against the timeout path's epoch-bump + victim-retire
        # (a step completing exactly at the deadline must either fully
        # commit before the bump or fully abort after it — never
        # interleave; a zombie thread must never dispatch against
        # donated pools the live loop still owns)
        # attach last: a draft-model drafter sizes its own tiny pools
        # off mgr/block_size/spec_k, which must all exist by now
        if self._drafter is not None:
            self._drafter.attach(self)
        self._commit_lock = threading.Lock()

    # ---- host-side accounting -------------------------------------------

    def _capacity_pages(self, sb: int) -> int:
        """Pages a request at bucket `sb` needs at the ENGINE-wide token
        budget (pool sizing; per-request admission uses the request's
        own max_new via _capacity_pages_for)."""
        return self._capacity_pages_for(sb, self.max_new)

    def _capacity_pages_for(self, sb: int, max_new: int) -> int:
        # same ceil-division as PagedKVManager.pages_needed (which is not
        # constructed yet when __init__ sizes the pool from this)
        return -(-(sb + max_new) // self.block_size)

    def _capacity_ring_pages(self) -> int:
        """Pages of one sequence's ring in the window layers' pools: the
        window, one prefill window of `token_budget` written behind it
        before the oldest page is due again, and a page because neither
        starts on a page's edge. 0 for a model without window layers."""
        if not self._window_layers:
            return 0
        return -(-(self._window + self.token_budget)
                 // self.block_size) + 1

    def _ring_table(self, ring, width: int):
        """The logical table of a ring: column j -> ring page j % R."""
        return np.asarray(ring, np.int32)[np.arange(width) % len(ring)]

    # ---- the served programs' host inputs and outputs (PR 38) -----------

    def _io_layouts(self) -> dict:
        """{"decode" | "mixed": (inputs, outputs)}: the `_Flat` layouts of
        the decode chunk's and the mixed step's host inputs and host-visible
        outputs. Their offsets follow from the engine's shapes — slots,
        table width, steps, token budget, whether window or routed layers
        exist, `logprobs` — and from nothing else. A `*_ring` field is a
        table of the window layers' ring pools, beside the full layers'
        table of the same name."""
        b, W, steps = self.slots, self.table_width, self.steps
        n_win = self.token_budget // self.block_size
        i32, f32 = np.int32, np.float32
        rings = bool(self._window_layers)

        def table(name, shape):
            return [(name, shape, i32)] \
                + ([(name + "_ring", shape, i32)] if rings else [])

        slots = [("toks", (b,), i32), ("lens", (b,), i32),
                 ("budgets", (b,), i32), ("live", (b,), bool)] \
            + table("tables", (b, W))
        outs = [("out", (b, steps), i32), ("lens", (b,), i32),
                ("done", (b,), bool)]

        def counted(lanes):
            # the routed layers' MOE_COUNTS: one row a lane
            return [("moe", (lanes, len(MOE_COUNTS)), i32)] \
                if self._routed else []

        lps = [("logprobs", (b, steps), f32)] if self.logprobs else []
        return {
            # `override`: rows whose host state wins over the chained
            # device carries of a double-buffered chunk
            "decode": (_Flat(slots + [("override", (b,), bool)]),
                       _Flat(outs + counted(1) + lps)),
            "mixed": (_Flat(slots + [("chunk_ids", (1, self.token_budget),
                                      i32)]
                            + table("chunk_table", (1, W))
                            + [("chunk_cached", (1,), i32),
                               ("chunk_len", (1,), i32)]
                            + table("chunk_pages", (1, n_win))),
                      _Flat(outs + [("first", (1,), i32)] + counted(2)
                            + lps + ([("first_logprob", (1,), f32)]
                                     if self.logprobs else []))),
        }

    def _table_arg(self, a: dict, name: str):
        """The program argument of table `name` in unpacked inputs `a`: the
        full layers' table, or the pair with the window layers' ring
        table."""
        return (a[name], a[name + "_ring"]) if self._window_layers \
            else a[name]

    def _put(self, layout: _Flat, values: dict):
        """A served program's host inputs packed into `layout` and put on
        the device in one transfer: (device buffer, host buffer)."""
        flat = layout.pack(values)
        return jax.device_put(flat), flat

    def _sampling(self) -> tuple:
        """Temperature and top-p as device scalars: made once, and again
        only when either attribute changes."""
        at = (self.temperature, self.top_p)
        if self._sampling_at != at:
            self._sampling_at = at
            self._sampling_dev = tuple(
                self._replicated(jnp.asarray(x, jnp.float32)) for x in at)
        return self._sampling_dev

    def _replicated(self, x):
        """`x` placed as the served programs hand their replicated outputs
        back (the key, the chained carries): over the serving mesh when
        there is one. An argument placed otherwise would be a second entry
        in a program's jit cache."""
        if self._tp is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(x, NamedSharding(self.mp_mesh,
                                               PartitionSpec()))

    def _slot_inputs(self, live) -> dict:
        """Every slot's host state as the served programs read it."""
        return {"toks": self._tokens,
                "lens": np.asarray([s.length for s in self._slots],
                                   np.int32),
                "budgets": self._budgets, "live": live,
                "tables": self._tables, "tables_ring": self._ring_tables}

    def _scratch_inputs(self) -> dict:
        """Inputs that aim every row of both served programs at the
        scratch page (the ring pools' sink is their page 0): warm-up and
        the audits' example arguments. A window of chunk_len 0 is all
        pad."""
        return {"toks": 0, "lens": 0, "budgets": 0, "live": False,
                "override": True, "chunk_ids": 0, "chunk_cached": 0,
                "chunk_len": 0,
                **{name + ring: 0 if ring else self.scratch_page
                   for name in ("tables", "chunk_table", "chunk_pages")
                   for ring in ("", "_ring")}}

    def _release(self, req) -> None:
        """Give back everything `req` holds in both pool kinds."""
        self.mgr.free(req.pages)
        req.pages = None
        self.mgr.free_ring(req.ring)
        req.ring = None

    # ---- tensor-parallel plumbing (FLAGS_serving_mp) --------------------

    @property
    def _nkv_eff(self) -> int:
        """kv-head count of the pools a program BODY sees: the local
        shard's under kv-head sharding, the full model's otherwise
        (single chip, or the replicated-KV MQA fallback)."""
        return self._tp.nkv_local if self._tp is not None \
            else self.cfg.num_key_value_heads

    def _pool_entry_spec(self):
        """PartitionSpec(s) of one per-layer K or V pool entry on the
        serving mesh: [max_pages, nkv, block, dh] sharded on the
        kv-head axis over `mp` (scale sidecars [max_pages, nkv]
        likewise) and/or on the PAGE axis over `cp` (ISSUE 18 — each
        chip holds a contiguous 1/cp of the fleet's pages, matching
        `cp_local_view`'s owner arithmetic); fully replicated under
        the MQA fallback at cp=1."""
        from jax.sharding import PartitionSpec as P

        mp_shard = self._tp is not None and self._tp.kv_sharded \
            and self._tp.mp > 1
        cp_shard = self._tp is not None and self._tp.cp > 1
        # NOTE: trailing-None-free form — jit normalizes output specs
        # (P(None, 'mp', None, None) comes back as P(None, 'mp')) and
        # treats the two spellings as DIFFERENT shardings; matching the
        # normalized form keeps warm()'s compile serving the steady
        # state instead of donating into a one-entry-stale cache
        if cp_shard:
            pool = sc = P(self._tp.cp_axis, self._tp.axis) if mp_shard \
                else P(self._tp.cp_axis)
        else:
            pool = sc = P(None, self._tp.axis) if mp_shard else P()
        return (pool, sc) if self.kv_dtype == "int8" else pool

    def _shard_program(self, fn, n_repl: int, n_out_repl: int):
        """Wrap an engine device program (signature: p, kcs, vcs,
        *replicated) in shard_map over the serving mesh. Params follow
        `serving_param_specs`, pools follow `_pool_entry_spec`, every
        other input — and the leading `n_out_repl` outputs (tokens,
        lengths, done flags) — replicates; the trailing outputs are the
        threaded (donated) pools. Identity at mp=1: the single-chip
        engine never touches shard_map."""
        if self._tp is None:
            return fn
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        pools = [self._pool_entry_spec()] * len(self.kcs)
        in_specs = (self._param_specs, pools, pools) + (P(),) * n_repl
        out_specs = (P(),) * n_out_repl + (pools, pools)
        return shard_map(fn, mesh=self.mp_mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    def _program(self, fn, name: str, n_repl: int, n_out_repl: int):
        """An engine device program, sharded over the serving mesh and
        jitted under the name of its ROLE: the compiler's module and
        the profiler's "XLA Modules" row read `jit_<name>`, so the pure
        decode chunk and the mixed step are two programs in a trace
        (every builder's closure is called `run`). The pools (arguments
        1 and 2) are donated."""
        fn = self._shard_program(fn, n_repl, n_out_repl)
        fn.__name__ = name
        return jax.jit(fn, donate_argnums=(1, 2))

    @property
    def n_active(self) -> int:
        return sum(1 for s in self._slots if s.req is not None)

    @property
    def n_cacheable_pages(self) -> int:
        """Pages that can hold K/V content (everything but the scratch
        page) — the ceiling on resident prefix-cache blocks. Capacity
        math is UNCHANGED in pages across pool dtypes
        (`_capacity_pages_for` counts pages, not bytes); what int8
        changes is how many pages a byte budget buys: at the same
        `kv_pool_bytes`, an int8 pool holds ~2x of these before LRU
        eviction."""
        return self.mgr.max_pages - 1

    @property
    def has_work(self) -> bool:
        return bool(self.waiting) or bool(self._handoff) \
            or self._prefilling is not None or self.n_active > 0

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of admitted prompt tokens served from the prefix
        cache instead of being prefilled."""
        if not self.prompt_tokens:
            return 0.0
        return self.prefix_hit_tokens / self.prompt_tokens

    def compile_stats(self) -> dict:
        """jit cache sizes for every engine program — the steady-state
        guard: after warm(), serving traffic must not grow any entry."""
        stats = {"decode": self._jit_cache_size(self._decode)}
        if self._unified is not None:
            stats["unified"] = self._jit_cache_size(self._unified)
        if self._verify is not None:
            stats["verify"] = self._jit_cache_size(self._verify)
            if self._drafter is not None:
                stats.update(self._drafter.compile_stats())
        for key, fn in self._prefill_cache.items():
            stats["prefill:" + ":".join(str(k) for k in key)] = \
                self._jit_cache_size(fn)
        return stats

    def metrics(self) -> dict:
        """Every engine counter in ONE dict (ISSUE 8 satellite) —
        callers stop poking `eng.sync_wait_s`-style attributes:
        scheduling counters, prefix-cache effectiveness, sync-wait
        telemetry, compile stats, and pool occupancy (byte budget via
        `PagedKVManager.kv_pool_bytes()`). Pure host bookkeeping —
        safe to call mid-serve from another thread."""
        mgr = self.mgr
        in_use = mgr.max_pages - mgr.n_available
        # cached tokens of each sequence that holds pages: the live slots'
        # and the one prefilling
        lens = [s.length for s in self._slots if s.req is not None] \
            + ([self._prefilling["done"]] if self._prefilling else [])
        return {
            "requests_finished": len(self.finished),
            "requests_waiting": len(self.waiting),
            "requests_active": self.n_active,
            "prefill_calls": self.prefill_calls,
            "device_steps": self.device_steps,
            "prefill_handoffs": self.prefill_handoffs,
            # unified ragged step (ISSUE 14)
            "unified_step": self.unified,
            "token_budget": self.token_budget,
            "prefill_chunks": self.prefill_chunks,
            "chunk_tokens": self.chunk_tokens,
            "hung_retired": self.hung_retired,
            "hung_requeued": self.hung_requeued,
            # speculative decoding (ISSUE 19): draft/accept counters —
            # acceptance_rate is the fraction of OFFERED draft tokens
            # the target's greedy argmax agreed with (the +1 corrected
            # token per window is regular decode output, not counted)
            "speculative": self.speculative,
            "spec_k": self.spec_k,
            "spec_adaptive": self.spec_adaptive,
            "spec_k_effective": (
                self._spec_policy.spec_k_effective
                if self._spec_policy is not None else self.spec_k),
            "spec_steps": self.spec_steps,
            "spec_drafted": self.spec_drafted,
            "spec_accepted": self.spec_accepted,
            "acceptance_rate": (self.spec_accepted / self.spec_drafted
                                if self.spec_drafted else 0.0),
            # prefix cache
            "prefix_hit_rate": self.prefix_hit_rate,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prompt_tokens": self.prompt_tokens,
            "prefix_inserts": self.prefix_inserts,
            "prefix_evictions": mgr.prefix_evictions,
            # None, or why this model serves without the prefix cache
            "prefix_cache_off": self.prefix_cache_off,
            # routed layers (MOE_COUNTS summed over layers and steps; a
            # dense model reads zeros): every time a routed layer ran, the
            # (token, choice) rows it routed, the experts that got at
            # least one, the largest group, the buffer rows its matmuls
            # walked (padding included) — and the decode lane's part
            **{f"moe_{name}": int(self.moe_counts["decode"][i]
                                  + self.moe_counts["chunk"][i])
               for i, name in enumerate(MOE_COUNTS)},
            **{f"moe_{name}_decode": int(self.moe_counts["decode"][i])
               for i, name in enumerate(MOE_COUNTS)},
            # both pool kinds: pages held on the full layers' pools (the
            # scratch page left out), pages of the window layers' rings in
            # use (a ring holds kv_ring_tokens, 0 without window layers),
            # tokens cached by the live and the prefilling sequences, those
            # of them a window layer still attends (min(len, W) a
            # sequence), and those that have left a window, ever
            "kv_pages_full": in_use - 1,
            "kv_pages_window": (mgr.n_rings - mgr.n_rings_free)
            * mgr.ring_pages,
            "kv_ring_tokens": mgr.ring_pages * self.block_size,
            "kv_tokens_live": sum(lens),
            "kv_tokens_window": sum(min(n, self._window) for n in lens),
            "window_tokens_dropped": self.window_tokens_dropped,
            # sync-wait telemetry (what double buffering hides)
            "sync_wait_s": self.sync_wait_s,
            "blocked_syncs": self.blocked_syncs,
            # constant: benchmark/drivers/serve.py prints it
            "megakernel_rung": "off",
            # quantized collectives (ISSUE 15): int8 wire on the mp
            # o-proj gather when True
            "quantized_collectives": self.quantized_collectives,
            # serving parallelism degrees: kv-head (mp) and page-axis
            # context (cp, ISSUE 18) shard counts
            "serving_mp": self.mp,
            "serving_cp": self.cp,
            # pool occupancy: pages not reclaimable right now / bytes
            "kv_cache_dtype": self.kv_dtype,
            "kv_pool_bytes": mgr.kv_pool_bytes(),
            "n_cacheable_pages": self.n_cacheable_pages,
            "n_available": mgr.n_available,
            "n_cached": mgr.n_cached,
            "pages_in_use": in_use,
            "pool_occupancy": in_use / max(mgr.max_pages, 1),
            "compile_stats": self.compile_stats(),
            # static memory audit (ISSUE 10): the fleet report from the
            # last audit_memory() / warm(audit_memory=True) run — None
            # until one ran
            "memory_audit": self._memory_audit,
            # static communication audit (ISSUE 11): bytes-on-wire
            # fleet report from the last audit_comms() /
            # warm(audit_comms=True) run — None until one ran
            "comms_audit": self._comms_audit,
            # static roofline audit (ISSUE 13): predicted step time /
            # MFU fleet report from the last audit_roofline() /
            # warm(audit_roofline=True) run — None until one ran
            "roofline_audit": self._roofline_audit,
            # autotuner artifact + persistent compile cache (ISSUE 16):
            # the knobs this engine was built from (None = registry
            # defaults) and cold-vs-warm compile traffic from the last
            # warm() — cache_misses == 0 is the "no compile storm" gate
            "tuned_config": (self.tuned_config.to_dict()
                             if self.tuned_config is not None else None),
            "warm_compile_stats": self.warm_compile_stats,
        }

    @staticmethod
    def _jit_cache_size(fn) -> int:
        try:
            return int(fn._cache_size())
        except Exception:
            return -1

    @staticmethod
    def _resolve_tuned_config(config, cfg):
        """`config=` -> a validated TunedConfig or None. Accepts a
        TunedConfig, a dict (its to_dict form), or a path; None falls
        back to FLAGS_tuned_config / PADDLE_TPU_TUNED_CONFIG, False
        forces off. Staleness (schema version / model-shape mismatch)
        raises for an explicit artifact and warns+ignores for a
        flag-loaded one — a fleet-wide env var must not brick engines
        built for a different model."""
        import warnings

        if config is False:
            return None
        from ..analysis.tuner import TunedConfig

        explicit = config is not None
        if config is None:
            from ..framework.flags import flag

            path = str(flag("tuned_config") or "")
            if not path:
                return None
            try:
                tuned = TunedConfig.load(path)
            except Exception as e:
                warnings.warn(
                    f"FLAGS_tuned_config {path!r} unreadable ({e}); "
                    "building with registry defaults", stacklevel=3)
                return None
        elif isinstance(config, TunedConfig):
            tuned = config
        elif isinstance(config, dict):
            tuned = TunedConfig.from_dict(config)
        else:
            tuned = TunedConfig.load(str(config))
        stale = tuned.stale_reason(cfg=cfg)
        if stale is not None:
            if explicit:
                raise ValueError(
                    f"stale TunedConfig (config=): {stale}; re-run "
                    "the autotuner (python -m paddle_tpu.analysis "
                    "--tune) for this model/device")
            warnings.warn(
                f"FLAGS_tuned_config artifact is stale ({stale}); "
                "building with registry defaults", stacklevel=3)
            return None
        return tuned

    def add_request(self, prompt, max_new: Optional[int] = None,
                    arrival_time: Optional[float] = None,
                    priority: Optional[str] = None,
                    deadline_s: Optional[float] = None) -> ServeRequest:
        """Validate + enqueue. Every reject happens HERE, before the
        request owns a slot or pages — failing deep inside `_admit` /
        prefill bucketing would wedge scheduling state.

        `priority` / `deadline_s` (ISSUE 17 satellite) are pure
        metadata: they ride the request through prefill handoff and
        show up in the `req.enqueue` / `req.retire` trace instants so a
        single-engine deployment gets deadline observability without
        the fleet router. The engine never reorders or sheds on them —
        SLO policy lives in `serving/router.py`."""
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not 1 <= len(prompt) <= self.max_prompt_len:
            raise ValueError(f"prompt length {len(prompt)} outside "
                             f"[1, {self.max_prompt_len}]")
        if max_new is not None and int(max_new) != max_new:
            raise TypeError(f"max_new must be an int, got {max_new!r}")
        req = ServeRequest(self._next_id, prompt,
                           int(max_new) if max_new is not None
                           else self.max_new,
                           arrival_time if arrival_time is not None
                           else time.perf_counter())
        if req.max_new <= 0:
            raise ValueError(
                f"max_new must be >= 1, got {req.max_new} (a request "
                "that may emit no token cannot retire its slot)")
        if req.max_new > self.max_new:
            raise ValueError(f"max_new {req.max_new} > engine budget "
                             f"{self.max_new}")
        sb = -(-len(prompt) // self.prompt_bucket) * self.prompt_bucket
        # fail fast on the request's OWN budget (not the engine-wide
        # max_new): a short-max_new request needs fewer pages, so it is
        # servable in pools a worst-case reservation would reject
        need = self._capacity_pages_for(sb, req.max_new)
        if need > self.mgr.max_pages - 1:
            # this request could never be admitted even with the whole
            # pool free (minus the scratch page) and a cold cache
            raise ValueError(
                f"request needs {need} pages "
                f"(bucketed prompt {sb} + max_new {req.max_new}) but the "
                f"pool holds only {self.mgr.max_pages - 1}")
        if self.prefix_cache:
            req.block_hashes = hash_prefix_blocks(prompt, self.block_size)
        if priority is not None:
            req.priority = str(priority)
        if deadline_s is not None:
            req.deadline_s = float(deadline_s)
        self._next_id += 1
        self.waiting.append(req)
        tr, mt = self._tracer, self._metrics
        if tr is not None:
            tr.instant("req.enqueue", req_id=req.req_id,
                       prompt_len=len(prompt), max_new=req.max_new,
                       priority=req.priority, deadline_s=req.deadline_s)
        if mt is not None:
            mt.counter("requests_enqueued").inc()
        return req

    # ---- fleet hooks (ISSUE 17): drain / progress export ----------------

    def pause_admission(self, paused: bool = True) -> None:
        """Stop (or resume) pulling from `waiting`. In-flight slots,
        the streaming unified prefill, and parked handoffs keep
        running to completion — only NEW admissions stop. The building
        block of an elastic drain: a worker being scaled in finishes
        what it owns while its queued requests move elsewhere."""
        self._admission_paused = bool(paused)

    def take_waiting(self) -> list:
        """Remove and return every not-yet-admitted request. They own
        no slot and no pages (admission is where reservations happen),
        so they re-enqueue on any engine as if freshly added."""
        taken, self.waiting = self.waiting, []
        return taken

    def export_progress(self) -> list:
        """Per-request progress snapshot for every request the engine
        still owns — what a fleet checkpoints/streams so a worker
        death preserves completed tokens. Pure host bookkeeping (safe
        from another thread); tokens lists are copied."""
        out = []

        def row(req, state):
            out.append({
                "req_id": req.req_id, "state": state,
                "prompt": list(req.prompt), "tokens": list(req.tokens),
                "max_new": req.max_new,
                "remaining": max(req.max_new - len(req.tokens), 0),
                "priority": req.priority, "deadline_s": req.deadline_s,
            })

        for req in self.waiting:
            row(req, "waiting")
        if self._prefilling is not None:
            row(self._prefilling["req"], "prefilling")
        for req in self._handoff:
            row(req, "handoff")
        for slot in self._slots:
            if slot.req is not None:
                row(slot.req, "active")
        return out

    def drain(self, max_iters: int = 100000) -> list:
        """Graceful drain: pause admission, run the in-flight work
        (live slots + streaming prefill + parked handoffs) to
        completion, and return the untouched `waiting` requests for
        re-admission elsewhere. Admission stays paused afterwards —
        `pause_admission(False)` to serve again."""
        self.pause_admission(True)
        while (self.n_active > 0 or self._prefilling is not None
               or self._handoff) and max_iters:
            self.step()
            max_iters -= 1
        if self.n_active > 0 or self._prefilling is not None \
                or self._handoff:
            raise RuntimeError("engine did not drain within max_iters")
        return self.take_waiting()

    # ---- device programs ------------------------------------------------

    def _build_prefill(self, sb: int, bsz: int):
        """Prefill `bsz` requests in ONE program (batched admission —
        b=1 prefills underuse the MXU and cost one host round-trip
        each): scatter each row's pages, sample each row's first token
        at its own true length. One compile per (bucket, batch) pair;
        _admit pads partial batches with rows aimed at the scratch
        page."""
        cfg = self.cfg
        bs = self.block_size
        n_pre = sb // bs
        base = _make_prefill(cfg, bsz, sb, tp=self._tp)
        head_logits = _make_head_logits(cfg)
        do_sample, top_k = self.do_sample, self.top_k
        scatter = self._page_scatter(bsz, n_pre)

        def run(p, kcs, vcs, ids, s0_vec, pages, key, temperature, top_p):
            h, kvs = base(p, ids)
            for i, (k, v) in enumerate(kvs):
                kcs[i], vcs[i] = scatter(kcs[i], vcs[i], k, v, pages)
            h_last = h[jnp.arange(bsz), s0_vec - 1][:, None, :]
            logits = head_logits(h_last, p)[:, -1]
            first = _sample_next(logits.astype(jnp.float32), key,
                                 do_sample, temperature, top_k, top_p)
            return first, kcs, vcs

        return run

    def _page_scatter(self, bsz: int, n_pre: int):
        """The prefill K/V page scatter shared by the cold and
        cached-prefix prefill programs — THE quantize-on-scatter seam:
        the int8 path computes each page's absmax in f32 and stores the
        int8 page + its scale row in the same update. Under serving_mp
        the helpers are built at the LOCAL kv-head count — the scatter
        runs inside the shard_map body on the local pool shard."""
        cfg = self.cfg
        nkv, dh = self._nkv_eff, self._model.head_dim
        bs = self.block_size
        tp = self._tp
        cp_drop = tp is not None and tp.cp > 1
        to_pages, _ = make_paged_kv_helpers(bsz, n_pre, nkv, dh, bs, None)

        def _local(pages, pps):
            # cp page-axis translation (ISSUE 18): this shard owns
            # global ids [idx*pps, (idx+1)*pps); non-owned writes
            # translate OUT OF RANGE (pps) and mode='drop' discards
            # them — never a redirect onto a real local page
            idx = jax.lax.axis_index(tp.cp_axis)
            return jnp.where((pages // pps) == idx, pages % pps, pps)

        if self.kv_dtype != "int8":
            if cp_drop:
                def scatter(kc, vc, k, v, pages):
                    loc = _local(pages, kc.shape[0])
                    return (kc.at[loc].set(to_pages(k).astype(kc.dtype),
                                           mode="drop"),
                            vc.at[loc].set(to_pages(v).astype(vc.dtype),
                                           mode="drop"))
                return scatter

            def scatter(kc, vc, k, v, pages):
                return (kc.at[pages].set(to_pages(k).astype(kc.dtype)),
                        vc.at[pages].set(to_pages(v).astype(vc.dtype)))
            return scatter
        to_pages_q8, _ = make_paged_kv_q8_helpers(bsz, n_pre, nkv, dh,
                                                  bs, None)

        if cp_drop:
            def scatter_q8(kct, vct, k, v, pages):
                (kc, ksc), (vc, vsc) = kct, vct
                loc = _local(pages, kc.shape[0])
                qk, sk = to_pages_q8(k)
                qv, sv = to_pages_q8(v)
                return ((kc.at[loc].set(qk, mode="drop"),
                         ksc.at[loc].set(sk, mode="drop")),
                        (vc.at[loc].set(qv, mode="drop"),
                         vsc.at[loc].set(sv, mode="drop")))
            return scatter_q8

        def scatter_q8(kct, vct, k, v, pages):
            (kc, ksc), (vc, vsc) = kct, vct
            qk, sk = to_pages_q8(k)
            qv, sv = to_pages_q8(v)
            return ((kc.at[pages].set(qk), ksc.at[pages].set(sk)),
                    (vc.at[pages].set(qv), vsc.at[pages].set(sv)))

        return scatter_q8

    def _build_prefix_prefill(self, sb: int, bsz: int, w_pre: int):
        """Like _build_prefill, but for rows whose prompt head hit the
        prefix cache: only the `sb`-bucketed suffix is computed, reading
        the cached prefix K/V through per-row prefix tables (the Pallas
        prefix-prefill kernel by default; see _make_prefill_with_prefix
        and FLAGS_prefix_prefill_kernel). One compile per (suffix
        bucket, batch, prefix width) key — prefix LENGTH stays traced,
        so every hit depth under the width shares the program, and the
        width itself is bucketed to the small `_prefix_width_ladder`
        (page-multiple padded) instead of always paying for the deepest
        possible prefix: neither the fallback's gather nor the kernel's
        streaming axis touches table columns the batch cannot fill."""
        cfg = self.cfg
        bs = self.block_size
        n_pre = sb // bs
        base = _make_prefill_with_prefix(cfg, bsz, sb, w_pre, bs,
                                         tp=self._tp)
        head_logits = _make_head_logits(cfg)
        do_sample, top_k = self.do_sample, self.top_k
        scatter = self._page_scatter(bsz, n_pre)

        def run(p, kcs, vcs, ids, s0_vec, pages, ptables, plens, key,
                temperature, top_p):
            h, kvs = base(p, kcs, vcs, ids, ptables, plens, s0_vec)
            for i, (k, v) in enumerate(kvs):
                kcs[i], vcs[i] = scatter(kcs[i], vcs[i], k, v, pages)
            h_last = h[jnp.arange(bsz), s0_vec - 1][:, None, :]
            logits = head_logits(h_last, p)[:, -1]
            first = _sample_next(logits.astype(jnp.float32), key,
                                 do_sample, temperature, top_k, top_p)
            return first, kcs, vcs

        return run

    def _decode_step_maker(self):
        """make_step(tables) -> the decode step of `_build_decode_chunk`,
        its one caller (the unified step runs that chunk as its decode
        lane)."""
        from ..kernels.decode_attention import paged_decode_attention

        cfg, b, bs = self.cfg, self.slots, self.block_size
        quant = self.kv_dtype == "int8"
        nkv_eff = self._nkv_eff
        tp = self._tp
        cp_parts = tp is not None and tp.cp > 1

        def window_step(tables, rings):
            """The step of a model with window layers (one chip, bf16
            pools: the constructor refused everything else): the two kinds
            of layer write and read through their own tables."""
            window = self._window
            _, kv_write = make_paged_kv_helpers(
                b, 0, nkv_eff, self._model.head_dim, bs, tables)
            _, ring_write = make_paged_kv_helpers(
                b, 0, nkv_eff, self._model.head_dim, bs, rings)

            def kv_attend(q1, kc, vc, lens_):
                return paged_decode_attention(q1, kc, vc, tables, lens_)

            def ring_attend(q1, kc, vc, lens_):
                return paged_decode_attention(q1, kc, vc, rings, lens_,
                                              window=window)

            return _make_decode_step(cfg, b, kv_write=kv_write,
                                     kv_attend=kv_attend, tp=tp,
                                     kv_window=(ring_write, ring_attend))

        def make_step(tables):
            """Per-layer decode body for one chunk. Under serving_mp
            this runs inside the shard_map body — the kv helpers and
            the attention see the LOCAL kv heads. Under serving_cp
            (ISSUE 18) the pools arrive PAGE-sharded: the kv commit
            translates global page ids to local rows (non-
            owned writes drop out of range), the attend streams only
            the owned pages as online-softmax partials, and
            `merge_attn_partials` folds the per-shard stats — never
            the KV — into the global context."""
            if self._window_layers:
                return window_step(*tables)    # `_table_arg`'s pair
            if cp_parts:
                from ..kernels.partial_attention import (
                    cp_local_view, decode_paged_partials,
                    finalize_partials)

                def _cp_attend(q1, kc, vc, lens_, ksc=None, vsc=None):
                    loc, owned = cp_local_view(tables, kc.shape[0],
                                               tp.cp_axis)
                    part = decode_paged_partials(
                        q1, kc, vc, loc, lens_, owned, k_scale=ksc,
                        v_scale=vsc)
                    m, l, acc = tp.merge_attn_partials(*part)
                    return finalize_partials(m, l, acc).astype(q1.dtype)

            if quant:
                if cp_parts:
                    # the q8 commit gathers the page's running absmax,
                    # rescales, and writes back — feeding it the
                    # TRANSLATED table (non-owned ids pushed out of
                    # range) makes its reads clamp to a don't-care row
                    # and its writes drop, so only the owning shard
                    # mutates a page (jax scatters drop out-of-bounds
                    # by default; the gathered garbage never lands)
                    def _q8_local_tables(kct):
                        pps = kct[0].shape[0]
                        idx = jax.lax.axis_index(tp.cp_axis)
                        return jnp.where((tables // pps) == idx,
                                         tables % pps, pps)

                    def kv_write(kct, vct, k, v, lens_):
                        _, w = make_paged_kv_q8_helpers(
                            b, 0, nkv_eff, self._model.head_dim, bs,
                            _q8_local_tables(kct))
                        return w(kct, vct, k, v, lens_)

                    def kv_attend(q1, kct, vct, lens_):
                        (kc, ksc), (vc, vsc) = kct, vct
                        return _cp_attend(q1, kc, vc, lens_, ksc, vsc)
                else:
                    _, kv_write = make_paged_kv_q8_helpers(
                        b, 0, nkv_eff, self._model.head_dim, bs, tables)

                    def kv_attend(q1, kct, vct, lens_):
                        (kc, ksc), (vc, vsc) = kct, vct
                        return paged_decode_attention(q1, kc, vc,
                                                      tables, lens_,
                                                      k_scale=ksc,
                                                      v_scale=vsc)
            else:
                if cp_parts:
                    def kv_write(kc, vc, k, v, lens_):
                        page = tables[jnp.arange(b), lens_ // bs]
                        slot = lens_ % bs
                        pps = kc.shape[0]
                        idx = jax.lax.axis_index(tp.cp_axis)
                        loc = jnp.where((page // pps) == idx,
                                        page % pps, pps)
                        return (kc.at[loc, :, slot, :].set(
                                    k[:, 0].astype(kc.dtype),
                                    mode="drop"),
                                vc.at[loc, :, slot, :].set(
                                    v[:, 0].astype(vc.dtype),
                                    mode="drop"))

                    kv_attend = _cp_attend
                else:
                    _, kv_write = make_paged_kv_helpers(
                        b, 0, nkv_eff, self._model.head_dim, bs, tables)

                    def kv_attend(q1, kc, vc, lens_):
                        return paged_decode_attention(q1, kc, vc,
                                                      tables, lens_)

            return _make_decode_step(cfg, b, kv_write=kv_write,
                                     kv_attend=kv_attend, tp=tp)

        return make_step

    def _decode_chunk_body(self):
        """`steps` decode tokens for every slot. Retired / free rows point
        their table at the scratch page and freeze their length, so they
        compute (fixed shape) but touch nothing live. `budgets` [slots]
        freezes each row on-device at prompt+max_new — the guarantee that
        a speculatively-dispatched chunk (double buffering) can never
        write past a request's reserved pages. Returns (outputs, last
        tokens, pools): `outputs` holds the tokens [slots, steps], the
        lengths and the done flags; a model with routed layers adds their
        summed MOE_COUNTS vector (`moe`), and `logprobs` the tokens'
        log-probabilities [slots, steps]."""
        b, steps = self.slots, self.steps
        do_sample, top_k, eos = self.do_sample, self.top_k, self.eos
        make_step = self._decode_step_maker()
        routed, logprobs = self._routed, self.logprobs

        def run(p, kcs, vcs, toks, lens, budgets, tables, live, key,
                temperature, top_p):
            decode_step = make_step(tables)

            def step(carry, _):
                tok, lens_, kcs_, vcs_, done, key_ = carry
                logits, kcs_, vcs_, *count = decode_step(
                    p, kcs_, vcs_, tok[:, None], lens_)
                key_, ks = jax.random.split(key_)
                nxt = _sample_next(logits.astype(jnp.float32), ks,
                                   do_sample, temperature, top_k, top_p)
                if logprobs:
                    count.append(_token_logprob(logits, nxt))
                frozen = done | ~live | (lens_ >= budgets)
                if eos is not None:
                    nxt = jnp.where(frozen, eos, nxt)
                    done = done | (nxt == eos)
                else:
                    nxt = jnp.where(frozen, 0, nxt)
                lens_ = jnp.where(frozen, lens_, lens_ + 1)
                return (nxt, lens_, kcs_, vcs_, done, key_), (nxt, *count)

            # every live row enters a chunk un-done (retire clears slots
            # at chunk end); `done` only freezes rows WITHIN the chunk
            done0 = jnp.zeros((b,), bool)
            (tok, lens, kcs, vcs, done, _), (out, *counts) = jax.lax.scan(
                step, (toks, lens, kcs, vcs, done0, key), None,
                length=steps)
            res = {"out": jnp.swapaxes(out, 0, 1), "lens": lens,
                   "done": done}
            # a routed model's steps counted what their layers did: the
            # sums ride out with the tokens the commit waits for anyway
            if routed:
                res["moe"] = jnp.sum(counts[0], axis=0)
            if logprobs:
                res["logprobs"] = jnp.swapaxes(counts[-1], 0, 1)
            return res, tok, kcs, vcs

        return run

    def _build_decode_chunk(self):
        """The decode chunk as the engine serves it, one transfer each
        way: it splits the key it is handed (the host's split, moved in)
        and returns the next one, reads every host input from ONE packed
        buffer (`_io_layouts()["decode"]`) and returns every host-visible
        output in ONE packed vector. A double-buffered chunk's tokens and
        lengths come from the previous chunk's device carries — also
        returned — except in rows the host overrides. Returns (packed,
        last tokens, lengths, next key, pools)."""
        body = self._decode_chunk_body()
        io_in, io_out = self._io["decode"]

        def run(p, kcs, vcs, flat, chain_tok, chain_lens, key, temperature,
                top_p):
            key, k = jax.random.split(key)
            a = io_in.unpack(flat)
            toks = jnp.where(a["override"], a["toks"], chain_tok)
            lens = jnp.where(a["override"], a["lens"], chain_lens)
            res, tok, kcs, vcs = body(
                p, kcs, vcs, toks, lens, a["budgets"],
                self._table_arg(a, "tables"), a["live"], k, temperature,
                top_p)
            if "moe" in res:
                res["moe"] = res["moe"][None]
            return io_out.pack(res), tok, res["lens"], key, kcs, vcs

        return run

    def _build_unified_step(self):
        """ONE program for mixed prefill + decode (ISSUE 14 tentpole):
        the decode-chunk scan (every live slot advances `steps` tokens
        — bitwise the split program's math) composed with ONE ragged
        prefill WINDOW of `token_budget` tokens for the request
        currently prefilling, through `ragged_paged_attention` (decode
        rows, prefill rows and prefill chunks coexist over the same
        pools). The lanes touch disjoint pages by construction (the
        chunk's window pages belong to a request no decode slot maps),
        so their order inside the program is free and the pools thread
        straight through — donated, exactly like the split programs.

        Replaces the entire (suffix bucket x batch x prefix-width rung)
        prefill program zoo: cold prompts are windows with cached_len
        0, cache-hit prompts start at their prefix depth, long prompts
        stream across steps (chunked prefill — decode latency becomes
        immune to a 100k-token prompt). The program's shape key is just
        (token_budget, slots, steps, kv-dtype, mp).

        Served like the decode chunk, one transfer each way: the key in
        and the next key out, the host inputs in one packed buffer and
        the host-visible outputs — the decode lane's, the first token,
        its log-probability — in one packed vector
        (`_io_layouts()["mixed"]`). Returns (packed, next key, pools)."""
        cfg, bs = self.cfg, self.block_size
        tn = self.token_budget
        n_win = tn // bs
        do_sample, top_k = self.do_sample, self.top_k
        decode_chunk = self._decode_chunk_body()
        chunk_body = _make_chunk_prefill(cfg, tn, tp=self._tp)
        head_logits = _make_head_logits(cfg)
        scatter = self._page_scatter(1, n_win)
        window_layers = self._window_layers
        io_in, io_out = self._io["mixed"]

        def run(p, kcs, vcs, flat, key, temperature, top_p):
            key, k = jax.random.split(key)
            _, kd, ks = jax.random.split(k, 3)
            a = io_in.unpack(flat)
            chunk_len = a["chunk_len"]
            chunk_pages = self._table_arg(a, "chunk_pages")
            # ---- decode lane: the split decode chunk, verbatim ----
            res, _, kcs, vcs = decode_chunk(
                p, kcs, vcs, a["toks"], a["lens"], a["budgets"],
                self._table_arg(a, "tables"), a["live"], kd, temperature,
                top_p)
            # ---- chunk lane: one ragged prefill window ----
            h, kvs, *chunk_count = chunk_body(
                p, kcs, vcs, a["chunk_ids"],
                self._table_arg(a, "chunk_table"), a["chunk_cached"],
                chunk_len)
            for i, (k, v) in enumerate(kvs):
                # a window layer's rows go to its ring's pages (the pair's
                # second), a full layer's to the request's own
                pages_i = chunk_pages if not window_layers \
                    else chunk_pages[i in window_layers]
                kcs[i], vcs[i] = scatter(kcs[i], vcs[i], k, v, pages_i)
            # first-token logits at the chunk's true last position —
            # meaningful only when this window completes the prompt
            # (the host ignores it otherwise)
            h_last = jax.lax.dynamic_index_in_dim(
                h, jnp.maximum(chunk_len[0] - 1, 0), axis=1,
                keepdims=True)
            logits = head_logits(h_last, p)[:, -1]
            res["first"] = _sample_next(logits.astype(jnp.float32), ks,
                                        do_sample, temperature, top_k, top_p)
            if "moe" in res:
                # routed layers' MOE_COUNTS, the decode lane's and the
                # window's
                res["moe"] = jnp.stack([res["moe"], *chunk_count])
            if "first_logprob" in io_out.names:
                res["first_logprob"] = _token_logprob(logits, res["first"])
            return io_out.pack(res), key, kcs, vcs

        return run

    def _verify_scatter(self, w: int):
        """Token-granular K/V commit for the speculative verify window:
        column j of every slot writes at position cached_len+j into the
        slot's own pages; pad columns (j >= new_len) and dead slots
        redirect to the scratch page. Columns commit SEQUENTIALLY —
        adjacent window positions share a page, and the int8 read-
        modify-write absmax chain needs each column to see the previous
        one's page state (w <= spec_k+1, so the unrolled loop is
        tiny)."""
        b, bs, W = self.slots, self.block_size, self.table_width
        nkv, dh = self._nkv_eff, self._model.head_dim
        quant = self.kv_dtype == "int8"
        scratch = self.scratch_page

        def scatter(kc, vc, k, v, tables, cached_lens, new_lens):
            for j in range(w):
                pos = cached_lens + j
                col = jnp.minimum(pos // bs, W - 1)
                page = jnp.where(j < new_lens,
                                 tables[jnp.arange(b), col], scratch)
                if quant:
                    # the q8 committer owns the absmax rescale; feeding
                    # it a width-1 table of the REDIRECTED page makes
                    # its internal tables[arange, pos//bs] lookup clamp
                    # onto exactly that page
                    _, w8 = make_paged_kv_q8_helpers(
                        b, 0, nkv, dh, bs, page[:, None])
                    kc, vc = w8(kc, vc, k[:, j:j + 1], v[:, j:j + 1],
                                pos)
                else:
                    slot = pos % bs
                    kc = kc.at[page, :, slot, :].set(
                        k[:, j].astype(kc.dtype))
                    vc = vc.at[page, :, slot, :].set(
                        v[:, j].astype(vc.dtype))
            return kc, vc

        return scatter

    def _build_verify_chunk(self):
        """The speculative verify program (ISSUE 19 tentpole): ONE
        ragged window of spec_k+1 rows per slot — row j holds
        [pending, d1..dk][j] at position cached_len+j — through the
        chunk-prefill body batched over slots
        (models/llama._make_verify_window). Row j's logits score the
        token AFTER window token j, so the host's acceptance walk
        (longest matching draft prefix + one corrected token) reads
        straight off the returned argmax. Every window row's K/V
        scatters into the slot's own pages; rejected rows' K/V past
        the committed length is masked garbage the ragged kernels
        never attend to, overwritten at the same positions by a later
        commit (bf16 bitwise; int8's monotone per-page absmax makes a
        re-write quantization noise — the PR 5 contract)."""
        b, w = self.slots, self.spec_k + 1
        body = _make_verify_window(self.cfg, b, w, tp=self._tp)
        head_logits = _make_head_logits(self.cfg)
        scatter = self._verify_scatter(w)

        def run(p, kcs, vcs, ids, tables, cached_lens, new_lens):
            h, kvs = body(p, kcs, vcs, ids, tables, cached_lens,
                          new_lens)
            for i, (k, v) in enumerate(kvs):
                kcs[i], vcs[i] = scatter(kcs[i], vcs[i], k, v, tables,
                                         cached_lens, new_lens)
            logits = head_logits(h, p)  # [b, w, vocab]
            preds = jnp.argmax(logits.astype(jnp.float32),
                               axis=-1).astype(jnp.int32)
            return preds, kcs, vcs

        return run

    # ---- scheduling loop ------------------------------------------------

    def _get_prefill(self, sb: int, bsz: int):
        """The single compile point for (bucket, batch) prefill programs
        (warm and _admit must never diverge in jit options). The pool
        dtype rides every key: an engine only ever builds programs at
        its own kv_cache_dtype, and the key makes that self-evident in
        compile_stats()."""
        key = ("cold", sb, bsz, self.kv_dtype, self.spec_k, self.cp,
               int(self.quantized_collectives), self.mp)
        if key not in self._prefill_cache:
            self._prefill_cache[key] = self._program(
                self._build_prefill(sb, bsz),
                f"serve_prefill_s{sb}_b{bsz}", 6, 1)
        return self._prefill_cache[key]

    def _get_prefix_prefill(self, sb: int, bsz: int, w_pre: int):
        key = ("prefix", sb, bsz, w_pre, self.kv_dtype, self.spec_k,
               self.cp, int(self.quantized_collectives), self.mp)
        if key not in self._prefill_cache:
            self._prefill_cache[key] = self._program(
                self._build_prefix_prefill(sb, bsz, w_pre),
                f"serve_prefix_prefill_s{sb}_b{bsz}_w{w_pre}", 8, 1)
        return self._prefill_cache[key]

    def _prefix_width_ladder(self) -> list:
        """The prefix-table widths (in pages) prefix-prefill programs
        compile at: powers of two of the pages-per-prompt-bucket
        quantum, capped at `_prefix_width`. A batch's width is padded
        UP to the next rung (`_prefix_width_for`), so program keys stay
        a small warm-able set while shallow-prefix batches stop paying
        the deepest-possible-prefix table width."""
        ppb = max(1, self.prompt_bucket // self.block_size)
        widths, w = [], ppb
        while w < self._prefix_width:
            widths.append(w)
            w *= 2
        widths.append(self._prefix_width)
        return widths

    def _prefix_width_for(self, n_blocks: int) -> int:
        for w in self._prefix_width_ladder():
            if w >= n_blocks:
                return w
        return self._prefix_width

    def _max_prefill_bsz(self) -> int:
        """_admit can never batch beyond the slot count — warming larger
        pow2 variants would be dead full-model compiles."""
        bsz = 1
        while bsz < min(self.prefill_batch, self.slots):
            bsz *= 2
        return bsz

    def warm(self, buckets=None, prefix_widths=None, audit_memory=None,
             audit_comms=None, audit_roofline=None):
        """Compile (and cache) every program the engine can need for the
        given prompt buckets — each power-of-two prefill batch (cold AND
        cached-prefix variants) plus the decode chunk — by running them
        against the scratch page. Call before serving latency-sensitive
        traffic; mid-stream compiles would otherwise land on the first
        matching admit. NOTE: buckets must cover the SUFFIX buckets
        cache-hit requests will prefill at, not just full prompt
        buckets (a hit's suffix is shorter than its prompt).
        `prefix_widths` narrows the cached-prefix variants to specific
        `_prefix_width_ladder` rungs (benches that know their hit depth
        skip the full ladder); default warms every rung.

        `audit_memory` (ISSUE 10): after warming, run the static memory
        auditor (`analysis/memory.py`) over EVERY program in the cache
        and keep the fleet report (per-program per-chip peak-HBM
        estimate, donation coverage, TPU701/702/703 diagnostics) on
        `metrics()['memory_audit']`, also emitted through the
        observability event log. Default (None) follows
        FLAGS_audit_memory / PADDLE_TPU_AUDIT_MEMORY — and composes
        with PADDLE_TPU_LINT=1, which implies it.

        `audit_comms` (ISSUE 11): likewise runs the static
        COMMUNICATION auditor (`analysis/comms.py`) over the cache —
        per-program bytes-on-wire with the per-chip collective cost
        model, TPU801/802/803 diagnostics, and the
        `predicted_bytes_on_wire_per_token` gauge — onto
        `metrics()['comms_audit']`. Default (None) follows
        FLAGS_audit_comms / PADDLE_TPU_AUDIT_COMMS, also implied by
        PADDLE_TPU_LINT=1.

        `audit_roofline` (ISSUE 13): likewise runs the static ROOFLINE
        auditor (`analysis/roofline.py`) over the cache — per-program
        FLOPs/HBM-bytes against the device-spec table, predicted step
        time + MFU + bound class, TPU901/902/903 diagnostics, and the
        `predicted_step_ms` / `predicted_mfu` gauges — onto
        `metrics()['roofline_audit']`. Default (None) follows
        FLAGS_audit_roofline / PADDLE_TPU_AUDIT_ROOFLINE, also implied
        by PADDLE_TPU_LINT=1.

        Compile-cache accounting (ISSUE 16): the persistent-cache
        counter delta over this warm() lands on
        `self.warm_compile_stats` / `metrics()['warm_compile_stats']`
        — a COLD warm reports misses (fresh compiles written to the
        cache), a WARM one off a populated cache dir must report
        `cache_misses == 0`."""
        from . import compile_cache as _compile_cache

        cc_snap = _compile_cache.snapshot()
        buckets = [self.max_prompt_len] if buckets is None else buckets
        if self.unified:
            # ONE program covers every prompt shape (cold, cached,
            # chunked): warm it once against the scratch page.
            # `buckets` / `prefix_widths` are accepted for driver
            # compatibility but meaningless — there is no program
            # ladder to enumerate, which is the point.
            buckets = []
        if prefix_widths is None:
            prefix_widths = self._prefix_width_ladder()
        elif not self.unified:
            bad = [w for w in prefix_widths
                   if w not in self._prefix_width_ladder()]
            if bad:
                raise ValueError(
                    f"prefix widths {bad} are not on the ladder "
                    f"{self._prefix_width_ladder()}; _admit only ever "
                    "uses ladder rungs, so warming others is dead")
        cap = self._max_prefill_bsz()
        for sb in buckets:
            if sb % self.prompt_bucket:
                raise ValueError(f"bucket {sb} is not a multiple of "
                                 f"prompt_bucket {self.prompt_bucket}")
            n_pre = sb // self.block_size
            bsz = 1
            while True:
                self._key, k = jax.random.split(self._key)
                _, self.kcs, self.vcs = self._get_prefill(sb, bsz)(
                    self.p, self.kcs, self.vcs,
                    jnp.zeros((bsz, sb), jnp.int32),
                    jnp.ones((bsz,), jnp.int32),
                    jnp.full((bsz, n_pre), self.scratch_page, jnp.int32),
                    k, *self._sampling())
                if self.prefix_cache:
                    # prefix length 0 masks the whole (scratch) prefix:
                    # the warm run computes garbage, touches only the
                    # scratch page, and caches the compiled program
                    for w in prefix_widths:
                        self._key, k = jax.random.split(self._key)
                        _, self.kcs, self.vcs = self._get_prefix_prefill(
                            sb, bsz, w)(
                            self.p, self.kcs, self.vcs,
                            jnp.zeros((bsz, sb), jnp.int32),
                            jnp.ones((bsz,), jnp.int32),
                            jnp.full((bsz, n_pre), self.scratch_page,
                                     jnp.int32),
                            jnp.full((bsz, w), self.scratch_page,
                                     jnp.int32),
                            jnp.zeros((bsz,), jnp.int32),
                            k, *self._sampling())
                if bsz >= cap:
                    break
                bsz *= 2
        # scratch-only inputs: warming against the live tables would
        # scatter the warm token's K/V into an admitted request's pages
        scratch = self._scratch_inputs()
        if self.unified:
            # the unified mixed program: an all-scratch window of
            # chunk_len 0 (every window row is pad — the ragged kernel
            # emits zeros, the scatter hits only the scratch page)
            flat, _ = self._put(self._io["mixed"][0], scratch)
            _, self._key, self.kcs, self.vcs = self._unified(
                self.p, self.kcs, self.vcs, flat, self._key,
                *self._sampling())
        flat, _ = self._put(self._io["decode"][0], scratch)
        *_, self._key, self.kcs, self.vcs = self._decode(
            self.p, self.kcs, self.vcs, flat, self._no_chain,
            self._no_chain, self._key, *self._sampling())
        if self._verify is not None:
            # the speculative verify window: every slot all-scratch
            # with new_len=1 (the pending-token row only — pad columns
            # and the scatter both land on the scratch page)
            vout = self._verify(
                self.p, self.kcs, self.vcs,
                jnp.zeros((self.slots, self.spec_k + 1), jnp.int32),
                jnp.full((self.slots, self.table_width), self.scratch_page,
                         jnp.int32),
                jnp.zeros((self.slots,), jnp.int32),
                jnp.ones((self.slots,), jnp.int32))
            _, self.kcs, self.vcs = vout
        if self._drafter is not None:
            self._drafter.warm()
        np.asarray(jax.tree.leaves(self.kcs)[0])  # sync
        self.warm_compile_stats = _compile_cache.stats_since(cc_snap)
        from ..analysis.comms import resolve_audit_comms
        from ..analysis.memory import resolve_audit_memory
        from ..analysis.roofline import resolve_audit_roofline

        do_mem = resolve_audit_memory(audit_memory)
        do_comms = resolve_audit_comms(audit_comms)
        do_roof = resolve_audit_roofline(audit_roofline)
        # one jaxpr trace per program serves EVERY auditor (their
        # passes memoize on the Graph) — under PADDLE_TPU_LINT=1,
        # which implies all three, the warm path must not trace the
        # whole fleet three times
        shared = self._traced_inventory() \
            if do_mem + do_comms + do_roof >= 2 else None
        if do_mem:
            self.audit_memory(graphs=shared)
        if do_comms:
            self.audit_comms(graphs=shared)
        if do_roof:
            self.audit_roofline(graphs=shared)

    # ---- static memory audit (ISSUE 10) ---------------------------------

    def _decode_example_args(self):
        flat, _ = self._put(self._io["decode"][0], self._scratch_inputs())
        return (self.p, self.kcs, self.vcs, flat, self._no_chain,
                self._no_chain, jax.random.PRNGKey(0), *self._sampling())

    def _prefill_example_args(self, key):
        """Warm()-shaped example args for a `_prefill_cache` entry —
        tracing only, nothing executes, so zeros aimed nowhere are
        fine."""
        kind, sb, bsz = key[0], key[1], key[2]
        n_pre = sb // self.block_size
        head = (self.p, self.kcs, self.vcs,
                jnp.zeros((bsz, sb), jnp.int32),
                jnp.ones((bsz,), jnp.int32),
                jnp.zeros((bsz, n_pre), jnp.int32))
        tail = (jax.random.PRNGKey(0), *self._sampling())
        if kind == "prefix":
            w = key[3]
            return head + (jnp.zeros((bsz, w), jnp.int32),
                           jnp.zeros((bsz,), jnp.int32)) + tail
        return head + tail

    def _unified_example_args(self):
        flat, _ = self._put(self._io["mixed"][0], self._scratch_inputs())
        return (self.p, self.kcs, self.vcs, flat, jax.random.PRNGKey(0),
                *self._sampling())

    def _verify_example_args(self):
        b, W = self.slots, self.table_width
        return (self.p, self.kcs, self.vcs,
                jnp.zeros((b, self.spec_k + 1), jnp.int32),
                jnp.zeros((b, W), jnp.int32),
                jnp.zeros((b,), jnp.int32), jnp.ones((b,), jnp.int32))

    def _program_inventory(self):
        """(name, jitted_fn, example_args) for every program this
        engine can dispatch: the decode chunk, the unified mixed
        program (ISSUE 14, when enabled), the speculative verify
        window (ISSUE 19, when enabled), plus every compiled prefill
        variant — the enumeration the fleet audit (and any future
        whole-cache tooling) walks."""
        progs = [("decode", self._decode, self._decode_example_args())]
        if self._unified is not None:
            progs.append(("unified", self._unified,
                          self._unified_example_args()))
        if self._verify is not None:
            progs.append(("verify", self._verify,
                          self._verify_example_args()))
        for key, fn in sorted(self._prefill_cache.items(),
                              key=lambda kv: str(kv[0])):
            name = "prefill:" + ":".join(str(k) for k in key)
            progs.append((name, fn, self._prefill_example_args(key)))
        return progs

    def _traced_inventory(self, programs=None):
        """(name, Graph) pairs for every (optionally filtered) cached
        program — ONE donation-aware jaxpr trace per program. Both
        static auditors run over these graphs (their passes memoize on
        the Graph), so a caller wanting memory AND comms reports —
        warm() under PADDLE_TPU_LINT=1, the bench drivers — traces
        each program once, not once per audit. Unknown filter names
        raise: a typo'd filter must not yield a vacuously clean report
        a CI gate would wave through."""
        from ..analysis import memory as _mem

        inventory = self._program_inventory()
        if programs is not None:
            want = set(programs)
            inventory = [it for it in inventory if it[0] in want]
            missing = want - {it[0] for it in inventory}
            if missing:
                raise ValueError(
                    f"programs {sorted(missing)} not in the inventory "
                    f"{[it[0] for it in self._program_inventory()]}")
        return [(name, _mem.trace_for_memory(fn, *args, name=name))
                for name, fn, args in inventory]

    def audit_memory(self, hbm_budget_bytes=None, programs=None,
                     graphs=None) -> dict:
        """Static memory audit (ISSUE 10): run the jaxpr liveness pass
        (`analysis/memory.py`) over every program in the cache and
        return ONE fleet report — per-program per-chip peak-HBM
        estimates, donation coverage, and the TPU701/702/703
        diagnostics. Programs share the pools and params and execute
        serially, so the fleet-resident bound is the MAX per-program
        peak, not the sum.

        `hbm_budget_bytes` arms TPU702; default (None) derives a
        budget from the engine's explicit `kv_pool_bytes=` sizing when
        one was given — pool budget + per-chip param bytes + 25%
        activation headroom — and otherwise from the device-spec row
        (`analysis.device_specs.auto_hbm_budget`: HBM capacity minus
        the default headroom fraction), the same gate the autotuner
        prunes against. Pass 0 to disarm TPU702 entirely.
        `programs` filters by inventory name ("decode",
        "prefill:cold:..."); unknown names raise, and a filtered run
        returns a `partial` report WITHOUT touching the fleet sinks.
        Full audits land on `metrics()['memory_audit']` and are
        emitted through the observability event log. Host-side tracing
        only: nothing executes on device. `graphs` (pre-traced
        (name, Graph) pairs from `_traced_inventory`) shares one
        trace with `audit_comms` — pass the same `programs` filter
        you traced with."""
        from ..analysis import memory as _mem
        from ..analysis.pipeline import analyze as _analyze

        if hbm_budget_bytes is None and self._kv_pool_budget is not None:
            # pool budget + per-chip params + activation/workspace
            # headroom: 25% relative, floored at 1 MiB — prefill
            # activations scale with batch x bucket x hidden, not with
            # the pool budget, so a pure percentage under-provisions
            # small pools
            base = self._kv_pool_budget \
                + _mem.pytree_local_bytes(self.p)
            hbm_budget_bytes = base + max(base // 4, 1 << 20)
        elif hbm_budget_bytes is None:
            # no explicit pool sizing to derive from: fall back to the
            # device row's capacity minus headroom (ISSUE 16 satellite)
            # — ONE budget helper shared with the autotuner's
            # feasibility gate and TPU702's auto-arm default
            from ..analysis.device_specs import auto_hbm_budget
            hbm_budget_bytes = auto_hbm_budget()
        # always pass the resolved budget through: 0 explicitly
        # DISARMS TPU702 (the rule auto-arms from the device row when
        # the key is absent, so omission would re-enable it)
        rule_config = {"TPU702.hbm_budget_bytes": int(hbm_budget_bytes)}
        if graphs is None:
            graphs = self._traced_inventory(programs)
        min_miss = _mem.DonationMissRule.MIN_BYTES
        out, diags = {}, 0
        for name, g in graphs:
            rep = _mem.audit_graph(g)
            lint = _analyze(None, graph=g,
                            rules=["TPU701", "TPU702", "TPU703"],
                            rule_config=rule_config)
            misses = [m for m in rep.donation["misses"]
                      if m["bytes"] >= min_miss]
            donated = rep.donation["donated_bytes"]
            missed = sum(m["bytes"] for m in misses)
            diags += len(lint)
            out[name] = {
                "peak_hbm_bytes": rep.peak_bytes,
                "n_eqns": rep.n_eqns,
                "mp": rep.mp,
                "donated_bytes": donated,
                "missed_bytes": missed,
                "donation_misses": len(misses),
                "donation_coverage": donated / (donated + missed)
                if donated + missed else 1.0,
                "diagnostics": lint.to_dict()["diagnostics"],
            }
        fleet_peak = max((p["peak_hbm_bytes"] for p in out.values()),
                         default=0)
        report = {
            "programs": out,
            "programs_audited": len(out),
            "fleet_peak_hbm_bytes": fleet_peak,
            "per_chip": True,
            "mp": self.mp,
            "cp": self.cp,
            "kv_pool_bytes": self.mgr.kv_pool_bytes(),
            "hbm_budget_bytes": hbm_budget_bytes,
            "donation_clean": all(p["donation_misses"] == 0
                                  for p in out.values()),
            "n_diagnostics": diags,
            "partial": programs is not None,
        }
        if report["partial"]:
            # a programs=-narrowed run (the bench drivers' decode-only
            # audits) must not overwrite the FLEET report monitoring
            # reads off metrics()['memory_audit'] — a prefill donation
            # regression would hide behind a decode-only clean bill
            return report
        self._memory_audit = report
        # instance sinks, like every other engine site (they default to
        # the flag-armed globals when the engine was built with None)
        tr, mt = self._tracer, self._metrics
        if tr is not None:
            tr.instant("memory.audit", fleet_peak_hbm_bytes=fleet_peak,
                       programs=len(out), mp=self.mp,
                       donation_clean=report["donation_clean"])
        if mt is not None:
            mt.event("memory.audit", fleet_peak_hbm_bytes=fleet_peak,
                     programs=len(out), mp=self.mp,
                     donation_clean=report["donation_clean"],
                     n_diagnostics=diags)
            mt.gauge("predicted_peak_hbm_bytes",
                     "static auditor per-chip peak over cached "
                     "programs").set(fleet_peak)
        return report

    def audit_comms(self, programs=None, rule_config=None,
                    graphs=None) -> dict:
        """Static communication audit (ISSUE 11): run the jaxpr
        bytes-on-wire pass (`analysis/comms.py`) over every program in
        the cache and return ONE fleet report — per-program per-chip
        wire bytes (ring cost model, loop amplification folded in),
        per-axis/per-kind splits, and the TPU801/802/803 diagnostics.
        The headline gauge is `predicted_bytes_on_wire_per_token`: the
        decode chunk's amplified wire bytes divided by the tokens one
        chunk produces (steps_per_sync x slots) — the number that
        pairs with the measured `bytes_all_gathered_per_token` bench
        counter, and the one an EQuARX-style quantized collective
        must beat. At mp=1 every program audits to zero collectives.

        `programs` filters by inventory name like `audit_memory`;
        filtered runs return a `partial` report without touching the
        fleet sinks. `rule_config` passes TPU80x knobs through
        (`{"TPU803.min_bytes": ...}`). `graphs` (pre-traced
        (name, Graph) pairs from `_traced_inventory`) shares one
        trace with `audit_memory`. Host-side tracing only."""
        from ..analysis import comms as _comms
        from ..analysis.pipeline import analyze as _analyze

        if graphs is None:
            graphs = self._traced_inventory(programs)
        out, diags = {}, 0
        for name, g in graphs:
            rep = _comms.audit_graph(g)
            lint = _analyze(None, graph=g,
                            rules=["TPU801", "TPU802", "TPU803"],
                            rule_config=rule_config)
            diags += len(lint)
            out[name] = {
                "bytes_on_wire": rep.total_wire_bytes,
                # recognized int8+sidecar pairs (ISSUE 15): bytes
                # attributed to the quantized-collective rewrite
                "quantized_wire_bytes": rep.quantized_wire_bytes,
                "n_quantized_sites": rep.n_quantized_sites,
                "n_collective_sites": rep.n_collective_sites,
                "n_collectives": rep.n_collectives,
                "n_implicit_reshards": len(rep.reshards),
                "mp": rep.mp,
                "per_axis": rep.per_axis(),
                "per_kind": rep.per_kind(),
                "top_talkers": [e.to_dict()
                                for e in rep.top_talkers(4)],
                "diagnostics": lint.to_dict()["diagnostics"],
            }
        # per decoded token per chip: one decode chunk produces
        # steps_per_sync tokens for each of the `slots` rows
        per_token = None
        if "decode" in out:
            per_token = out["decode"]["bytes_on_wire"] \
                / max(self.steps * self.slots, 1)
        report = {
            "programs": out,
            "programs_audited": len(out),
            "per_chip": True,
            "mp": self.mp,
            "cp": self.cp,
            "total_bytes_on_wire": sum(p["bytes_on_wire"]
                                       for p in out.values()),
            "predicted_bytes_on_wire_per_token": per_token,
            "comms_clean": diags == 0,
            "n_diagnostics": diags,
            "partial": programs is not None,
        }
        if report["partial"]:
            # same contract as audit_memory: a narrowed run must not
            # overwrite the FLEET report monitoring reads
            return report
        self._comms_audit = report
        tr, mt = self._tracer, self._metrics
        if tr is not None:
            tr.instant("comms.audit",
                       total_bytes_on_wire=report["total_bytes_on_wire"],
                       programs=len(out), mp=self.mp,
                       comms_clean=report["comms_clean"])
        if mt is not None:
            mt.event("comms.audit",
                     total_bytes_on_wire=report["total_bytes_on_wire"],
                     programs=len(out), mp=self.mp,
                     comms_clean=report["comms_clean"],
                     n_diagnostics=diags)
            if per_token is not None:
                mt.gauge("predicted_bytes_on_wire_per_token",
                         "static auditor per-chip wire bytes per "
                         "decoded token (decode chunk)").set(per_token)
        return report

    def audit_roofline(self, device=None, programs=None,
                       rule_config=None, graphs=None) -> dict:
        """Static roofline audit (ISSUE 13): run the jaxpr FLOPs/bytes
        pass (`analysis/roofline.py`) over every program in the cache
        and return ONE fleet report — per-program predicted step time
        (max of compute / HBM / wire time + launch overhead), bound
        class, predicted MFU, and the TPU901/902/903 diagnostics, all
        against one `analysis/device_specs.py` row. The headline
        gauges are `predicted_step_ms` and `predicted_mfu` for the
        decode chunk — the numbers the gated OPBENCH serving rows
        record next to their measured latencies, so the next TPU run
        lands an estimate/actual ratio (same contract as the
        memory/comms gauges). `predicted_ms_per_token` divides the
        chunk by the tokens it produces (steps_per_sync x slots).

        `device` picks the spec row (name or DeviceSpec; None =
        detect live TPU, else the v5e baseline). `programs` filters by
        inventory name like the other audits; filtered runs return a
        `partial` report without touching the fleet sinks. `graphs`
        (pre-traced pairs from `_traced_inventory`) shares one trace
        with the other auditors. Host-side tracing only."""
        from ..analysis import roofline as _roof
        from ..analysis.pipeline import analyze as _analyze
        from ..analysis.rules import RULES, rule_config_for

        spec = _roof.get_spec(device)
        rc = dict(rule_config or {})

        def _rules():
            # instantiated directly so the rules price against the
            # EXACT spec object the report uses — a caller-built
            # DeviceSpec has no row name a string knob could route,
            # and diagnostics priced on a different device than the
            # predicted_step_ms beside them would be contradictory.
            # An explicit TPUxxx.device knob still wins.
            out = []
            for rid in ("TPU901", "TPU902", "TPU903"):
                knobs = rule_config_for(rid, rc)
                knobs.setdefault("device", spec)
                out.append(RULES[rid](**knobs))
            return out

        if graphs is None:
            graphs = self._traced_inventory(programs)
        out, diags = {}, 0
        for name, g in graphs:
            rep = _roof.audit_graph(g, spec)
            lint = _analyze(None, graph=g, rules=_rules())
            diags += len(lint)
            d = rep.to_dict(max_events=4)
            out[name] = {
                "predicted_step_ms": d["predicted_step_ms"],
                "predicted_mfu": d["predicted_mfu"],
                "bound": d["bound"],
                "flops": d["flops"],
                "hbm_bytes": d["hbm_bytes"],
                "wire_bytes": d["wire_bytes"],
                "kernel_launches": d["kernel_launches"],
                "compute_ms": d["compute_ms"],
                "bandwidth_ms": d["bandwidth_ms"],
                "wire_ms": d["wire_ms"],
                "launch_overhead_ms": d["launch_overhead_ms"],
                "padding_waste_fraction": d["padding_waste_fraction"],
                "mp": rep.mp,
                "bottlenecks": d["bottlenecks"],
                "diagnostics": lint.to_dict()["diagnostics"],
            }
        # the decode chunk produces steps_per_sync tokens per slot
        step_ms = mfu = per_token_ms = None
        if "decode" in out:
            step_ms = out["decode"]["predicted_step_ms"]
            mfu = out["decode"]["predicted_mfu"]
            per_token_ms = step_ms / max(self.steps * self.slots, 1)
        report = {
            "programs": out,
            "programs_audited": len(out),
            "device": spec.name,
            "per_chip": True,
            "mp": self.mp,
            "cp": self.cp,
            "predicted_step_ms": step_ms,
            "predicted_mfu": mfu,
            "predicted_ms_per_token": per_token_ms,
            "roofline_clean": diags == 0,
            "n_diagnostics": diags,
            "partial": programs is not None,
        }
        if report["partial"]:
            # same contract as the other audits: a narrowed run must
            # not overwrite the FLEET report monitoring reads
            return report
        self._roofline_audit = report
        tr, mt = self._tracer, self._metrics
        if tr is not None:
            tr.instant("roofline.audit", device=spec.name,
                       predicted_step_ms=step_ms, predicted_mfu=mfu,
                       programs=len(out), mp=self.mp,
                       roofline_clean=report["roofline_clean"])
        if mt is not None:
            mt.event("roofline.audit", device=spec.name,
                     predicted_step_ms=step_ms, predicted_mfu=mfu,
                     programs=len(out), mp=self.mp,
                     roofline_clean=report["roofline_clean"],
                     n_diagnostics=diags)
            if step_ms is not None:
                mt.gauge("predicted_step_ms",
                         "static roofline auditor predicted decode "
                         "chunk latency").set(step_ms)
                mt.gauge("predicted_mfu",
                         "static roofline auditor predicted decode "
                         "chunk MFU").set(mfu)
        return report

    def _check_owner(self, token: Optional[int]):
        """A watchdog-abandoned step thread must stop mutating shared
        state the moment the main loop reclaims it (see run())."""
        if token is not None and token != self._step_epoch:
            raise _AbandonedStep(
                "step abandoned by the watchdog; discarding its work")

    def _plan(self, req: ServeRequest) -> _Plan:
        """Admission plan: cached-prefix split + page reservation for
        one waiting request (pure lookup — takes no references)."""
        bs = self.block_size
        L = len(req.prompt)
        cold_sb = -(-L // self.prompt_bucket) * self.prompt_bucket
        cold_total = self._capacity_pages_for(cold_sb, req.max_new)
        n_cached = 0
        if self.prefix_cache:
            # cap so at least one suffix token always prefills (the
            # first-token logits must be computed even on a full hit)
            max_blocks = (L - 1) // bs
            if max_blocks > 0:
                n_cached, _ = self.mgr.prefix_lookup(
                    req.prompt, max_blocks, hashes=req.block_hashes)
        while True:
            suffix_len = L - n_cached * bs
            sb_suf = -(-suffix_len // self.prompt_bucket) \
                * self.prompt_bucket
            need = self._capacity_pages_for(sb_suf, req.max_new)
            # a prefix that is block- but not bucket-aligned widens the
            # suffix bucket; trim it until the hit path's total resident
            # pages never exceed the cold path's (the bound the pool and
            # table_width are sized to — otherwise admission could
            # out-reserve the pool and livelock)
            if n_cached == 0 or n_cached + need <= cold_total:
                break
            n_cached -= 1
        n_lru = 0
        if n_cached:
            n_lru = self.mgr.prefix_lookup(req.prompt, n_cached,
                                           hashes=req.block_hashes)[1]
        return _Plan(sb_suf, n_cached, n_lru, need, suffix_len)

    def _admit(self, token: Optional[int] = None):
        """FIFO admission, batched: the head run of waiting requests
        sharing a (suffix bucket, cached-vs-cold) key — bounded by free
        slots, available pages, and prefill_batch — prefills in ONE
        device call; partial batches pad with rows aimed at the scratch
        page. Cache-hit rows map their cached prefix pages into their
        block tables and prefill only the suffix; cold rows take the
        flash-attention prefill path unchanged. After commit, every
        freshly computed full prompt block is inserted into the prefix
        cache for future requests. Returns the ids of the requests
        admitted (the caller's `sched.admit` span names them)."""
        admitted = []
        if self._admission_paused:
            return admitted
        bs = self.block_size
        while self.waiting:
            self._check_owner(token)
            if self.disaggregated:
                # prefill WORKER: bounded by handoff headroom (at most
                # `slots` prefilled requests parked at the handoff) and
                # by pages — never by decode slot occupancy; that
                # decoupling is the disaggregation
                room = self.slots - len(self._handoff)
                if room <= 0:
                    return admitted
                limit = min(room, self.prefill_batch)
            else:
                free_slots = [i for i, s in enumerate(self._slots)
                              if s.req is None]
                if not free_slots:
                    return admitted
                limit = min(len(free_slots), self.prefill_batch)
            head = self._plan(self.waiting[0])
            key = (head.sb_suf, head.n_cached > 0)
            batch, plans = [], []
            # available = free + evictable; acquiring a refcount-0
            # cached page also consumes availability (n_lru)
            avail = self.mgr.n_available
            for req in self.waiting:
                if len(batch) >= limit:
                    break
                plan = head if not batch else self._plan(req)
                if (plan.sb_suf, plan.n_cached > 0) != key:
                    break
                if plan.need + plan.n_lru > avail:
                    break  # FIFO: a short request must not starve the head
                avail -= plan.need + plan.n_lru
                batch.append(req)
                plans.append(plan)
            if not batch:
                return admitted  # head is blocked on pages
            sb_suf, has_prefix = key
            n_pre = sb_suf // bs
            bsz = 1
            while bsz < len(batch):
                bsz *= 2
            ids = np.zeros((bsz, sb_suf), np.int32)
            s0s = np.ones((bsz,), np.int32)
            pages = np.full((bsz, n_pre), self.scratch_page, np.int32)
            # prefix-table width: the ladder rung covering the DEEPEST
            # hit in this batch, not the deepest prefix the engine
            # could ever cache — shallow-hit batches stop streaming
            # (kernel) / gathering (fallback) pad table columns
            w_call = self._prefix_width_for(
                max(plan.n_cached for plan in plans)) if has_prefix else 1
            ptbl = np.full((bsz, w_call), self.scratch_page, np.int32)
            plens = np.zeros((bsz,), np.int32)
            tr, mt = self._tracer, self._metrics
            # dispatch + readback as ONE span: the prefill program's
            # host-visible cost for this admission batch
            with (_NULL_SPAN if tr is None else tr.span(
                    "prefill.dispatch", bucket=sb_suf, batch=len(batch),
                    cached_prefix=has_prefix,
                    req_ids=[r.req_id for r in batch])):
                t_disp0 = time.perf_counter()
                with self._commit_lock:
                    self._check_owner(token)
                    # pin every row's cached prefix BEFORE any alloc —
                    # alloc_pages evicts refcount-0 cached pages, and a
                    # pinned page can never be the victim
                    acquired = [self.mgr.acquire_prefix(
                                    req.prompt, plan.n_cached,
                                    hashes=req.block_hashes)
                                if plan.n_cached else []
                                for req, plan in zip(batch, plans)]
                    for row, (req, plan) in enumerate(zip(batch, plans)):
                        cached = acquired[row]
                        priv = self.mgr.alloc_pages(plan.need)
                        req.bucket = sb_suf
                        if not self.disaggregated:
                            req.slot = free_slots[row]
                        req.pages = cached + priv
                        req.n_prefix = len(cached)
                        req.cached_tokens = len(cached) * bs
                        suffix = req.prompt[req.cached_tokens:]
                        ids[row, :len(suffix)] = suffix
                        s0s[row] = len(suffix)
                        pages[row] = priv[:n_pre]
                        if cached:
                            ptbl[row, :len(cached)] = cached
                            plens[row] = req.cached_tokens
                    self._key, k = jax.random.split(self._key)
                    self.prefill_calls += 1
                    self._step_kind = "mixed"
                    if has_prefix:
                        fn = self._get_prefix_prefill(sb_suf, bsz, w_call)
                        out = fn(self.p, self.kcs, self.vcs, jnp.asarray(ids),
                                 jnp.asarray(s0s), jnp.asarray(pages),
                                 jnp.asarray(ptbl), jnp.asarray(plens), k,
                                 *self._sampling())
                    else:
                        fn = self._get_prefill(sb_suf, bsz)
                        out = fn(self.p, self.kcs, self.vcs, jnp.asarray(ids),
                                 jnp.asarray(s0s), jnp.asarray(pages), k,
                                 *self._sampling())
                    firsts_dev, self.kcs, self.vcs = out
                # blocking readback OUTSIDE the lock: a hung device wait
                # must never hold the lock the timeout path needs
                firsts = np.asarray(firsts_dev)
            if mt is not None:
                mt.histogram(
                    "prefill_chunk_s",
                    "prefill dispatch + first-token readback").observe(
                        time.perf_counter() - t_disp0)
            # abandoned mid-prefill: commit NOTHING. The batch is still
            # in `waiting` (popped only below), so the live loop
            # re-admits it with fresh pages; this thread's page
            # allocation (and prefix references) leak until drain —
            # leaking beats racing the live thread for the free list.
            # The lock makes check+commit atomic against the timeout
            # path's epoch-bump+retire.
            with (_NULL_SPAN if tr is None else tr.span(
                    "sched.commit", iter=self.sched_iters,
                    produced=len(batch))) as sp:
                with self._commit_lock:
                    self._check_owner(token)
                    del self.waiting[:len(batch)]
                    now = time.perf_counter()
                    for row, (req, plan) in enumerate(zip(batch, plans)):
                        first = int(firsts[row])
                        req.tokens.append(first)
                        req.prefill_time = now
                        self.prompt_tokens += len(req.prompt)
                        self.prefix_hit_tokens += req.cached_tokens
                        if tr is not None:
                            tr.instant("req.admit", req_id=req.req_id,
                                       cached_tokens=req.cached_tokens,
                                       suffix_bucket=sb_suf)
                        if mt is not None:
                            # TTFT = arrival -> first token committed;
                            # queue wait = arrival -> prefill dispatch
                            mt.histogram(
                                "ttft_s", "arrival to first token").observe(
                                    now - req.arrival_time)
                            mt.histogram(
                                "queue_wait_s",
                                "arrival to prefill dispatch").observe(
                                    max(t_disp0 - req.arrival_time, 0.0))
                            mt.counter("requests_admitted").inc()
                            mt.counter("prompt_tokens").inc(len(req.prompt))
                            mt.counter("prefix_hit_tokens").inc(
                                req.cached_tokens)
                        if self.prefix_cache:
                            # register every freshly computed FULL prompt
                            # block (its K/V is prefix-deterministic; decode
                            # writes start at position len(prompt), never
                            # inside it) — first writer wins on hash races
                            full = len(req.prompt) // bs
                            if full > req.n_prefix:
                                self.prefix_inserts += self.mgr.insert_prefix(
                                    req.prompt,
                                    req.pages[req.n_prefix:full],
                                    start_block=req.n_prefix,
                                    hashes=req.block_hashes)
                        if self.disaggregated:
                            # prefill -> decode HANDOFF: the "KV transfer"
                            # is nothing — the pages (sharded under mp) are
                            # already resident; the decode worker maps them
                            # through the replicated block table at install
                            self.prefill_handoffs += 1
                            if tr is not None:
                                tr.instant("req.handoff", req_id=req.req_id)
                            if mt is not None:
                                mt.counter("prefill_handoffs").inc()
                            if (self.eos is not None and first == self.eos) \
                                    or req.max_new == 1:
                                self._finish_prefilled(req)
                            else:
                                self._handoff.append(req)
                        else:
                            self._bind_slot(req.slot, req)
                if tr is not None:
                    sp.set(retired=[r.req_id for r in batch if r.done],
                           emitted={r.req_id: 1 for r in batch})
            admitted += [r.req_id for r in batch]
            # LRU prefix evictions since last report (alloc_pages evicts
            # under pool pressure; surfacing the delta here keeps the
            # manager observability-free)
            ev_delta = self.mgr.prefix_evictions - self._evictions_seen
            if ev_delta:
                self._evictions_seen = self.mgr.prefix_evictions
                if tr is not None:
                    tr.instant("prefix.evict", n=ev_delta)
                if mt is not None:
                    mt.counter("prefix_evictions").inc(ev_delta)
        return admitted

    def _bind_slot(self, slot_id: int, req: ServeRequest):
        """Install a prefilled request into a decode slot: map its
        already-resident pages into the replicated block table and seed
        the chunk inputs from its first sampled token. Shared by
        unified admission and the disaggregated decode worker
        (`_install_handoffs`) — the install is pure host bookkeeping
        either way."""
        first = req.tokens[0]
        slot = self._slots[slot_id]
        req.slot = slot_id
        slot.req = req
        slot.length = len(req.prompt)
        slot.emitted = 1
        slot.done = self.eos is not None and first == self.eos
        padded = req.pages + [req.pages[-1]] * \
            (self.table_width - len(req.pages))
        self._tables[slot_id] = padded
        if req.ring:
            self._ring_tables[slot_id] = self._ring_table(
                req.ring, self.table_width)
        self._tokens[slot_id] = first
        self._budgets[slot_id] = len(req.prompt) + req.max_new
        self._override[slot_id] = True
        if slot.done or req.max_new == 1:
            self._retire(slot_id)

    def _finish_prefilled(self, req: ServeRequest):
        """A request fully served by its prefill (EOS first token, or
        max_new == 1) retires at the handoff without ever taking a
        decode slot; its pages release through the refcounted free."""
        req.finish_time = time.perf_counter()
        self.finished.append(req)
        tr, mt = self._tracer, self._metrics
        if tr is not None:
            # same lifecycle terminator as _retire — span-coverage
            # checks must see every request retire, slotless or not
            tr.instant("req.retire", req_id=req.req_id, slot=None,
                       tokens=len(req.tokens), failed=False)
        if mt is not None:
            mt.counter("requests_finished").inc()
        self._release(req)

    def _install_handoffs(self, token: Optional[int] = None):
        """Decode-worker half of the disaggregated split: map handed-
        off requests (FIFO) into free decode slots. No device work —
        prefill committed the pages, so installing is writing the
        replicated block table row and chunk seeds."""
        if not self._handoff:
            return
        tr = self._tracer
        with (_NULL_SPAN if tr is None else tr.span(
                "sched.admit", iter=self.sched_iters,
                waiting=len(self._handoff))) as sp:
            installed = []
            with self._commit_lock:
                self._check_owner(token)
                for slot_id, slot in enumerate(self._slots):
                    if not self._handoff:
                        break
                    if slot.req is None:
                        req = self._handoff.pop(0)
                        self._bind_slot(slot_id, req)
                        installed.append(req.req_id)
            if tr is not None:
                sp.set(req_ids=installed)

    # ---- unified ragged step scheduling (ISSUE 14) ----------------------

    def _plan_unified(self, req: ServeRequest) -> _Plan:
        """Token-budget admission plan: the EXACT page reservation for
        one waiting request — ceil((prompt - cached + max_new)/block)
        private pages next to its cached prefix blocks. No bucket
        rounding, and therefore no prefix TRIM: cached + private =
        ceil((prompt + max_new)/block) exactly, the cold-path bound the
        pool and table_width are sized to."""
        bs = self.block_size
        L = len(req.prompt)
        n_cached = n_lru = 0
        if self.prefix_cache:
            # at least one window token always prefills (the
            # first-token logits must be computed even on a full hit);
            # no trim ever shrinks n_cached, so ONE lookup serves both
            # the depth and the refcount-0 count (the split planner
            # must re-look-up after trimming)
            max_blocks = (L - 1) // bs
            if max_blocks > 0:
                n_cached, n_lru = self.mgr.prefix_lookup(
                    req.prompt, max_blocks, hashes=req.block_hashes)
        suffix = L - n_cached * bs
        need = -(-(suffix + req.max_new) // bs)
        return _Plan(None, n_cached, n_lru, need, suffix)

    def _admit_unified(self, token: Optional[int] = None):
        """Unified-path admission: start prefilling the FIFO head when
        the chunk lane is free, its pages fit, and a decode slot will
        exist at completion (disaggregated: handoff headroom instead —
        prefill admission never queues behind decode occupancy). The
        request's WHOLE reservation (cached prefix pinned + private
        pages) commits here; its prompt then streams through
        `token_budget` windows across steps. Returns the ids of the
        requests admitted (one or none)."""
        if self._admission_paused:
            return []
        if self._prefilling is not None or not self.waiting:
            return []
        req = self.waiting[0]
        plan = self._plan_unified(req)
        if self.disaggregated:
            if len(self._handoff) >= self.slots:
                return []
        else:
            # one free slot now guarantees one at completion: only
            # completion binds slots in unified mode, retires only add
            if not any(s.req is None for s in self._slots):
                return []
        if plan.need + plan.n_lru > self.mgr.n_available:
            return []
        if self._window_layers and not self.mgr.n_rings_free:
            return []
        tr, mt = self._tracer, self._metrics
        with self._commit_lock:
            self._check_owner(token)
            cached = self.mgr.acquire_prefix(
                req.prompt, plan.n_cached,
                hashes=req.block_hashes) if plan.n_cached else []
            priv = self.mgr.alloc_pages(plan.need)
            self.waiting.pop(0)
            req.pages = cached + priv
            # both kinds are reserved whole here: the ring never grows
            req.ring = self.mgr.alloc_ring() if self._window_layers \
                else None
            req.n_prefix = len(cached)
            req.cached_tokens = len(cached) * self.block_size
            req.bucket = self.token_budget
            # "dispatched" flips once a window of this request has
            # actually ridden a device dispatch — the watchdog blames
            # the prefilling request only then (a timeout draining a
            # pure-decode chunk dispatched BEFORE this admission is
            # the decode program's fault, not this request's)
            self._prefilling = {"req": req, "done": req.cached_tokens,
                                "t0": time.perf_counter(),
                                "dispatched": False}
        ev_delta = self.mgr.prefix_evictions - self._evictions_seen
        if ev_delta:
            self._evictions_seen = self.mgr.prefix_evictions
            if tr is not None:
                tr.instant("prefix.evict", n=ev_delta)
            if mt is not None:
                mt.counter("prefix_evictions").inc(ev_delta)
        return [req.req_id]

    def _dispatch_commit_unified(self, token: Optional[int] = None) -> int:
        """One MIXED step: dispatch the unified program — every live
        slot's decode chunk + the next prefill window of the active
        request — and commit both lanes. The decode lane commits
        through `_commit_chunk` unchanged; the chunk lane advances the
        prefill cursor and, on the final window, turns the sampled
        first token into a slot bind (or disaggregated handoff)."""
        st = self._prefilling
        req = st["req"]
        tr, mt = self._tracer, self._metrics
        with (_NULL_SPAN if tr is None else tr.span(
                "sched.build", iter=self.sched_iters, req_id=req.req_id)):
            L = len(req.prompt)
            done = st["done"]
            tn, bs = self.token_budget, self.block_size
            n_win = tn // bs
            this_chunk = min(L - done, tn)
            wp0 = done // bs
            win_pages = req.pages[wp0:wp0 + n_win]
            win_pages += [self.scratch_page] * (n_win - len(win_pages))
            ids = np.zeros((1, tn), np.int32)
            ids[0, :this_chunk] = req.prompt[done:done + this_chunk]
            tbl = np.full((1, self.table_width), self.scratch_page,
                          np.int32)
            tbl[0, :len(req.pages)] = req.pages
            window = {"chunk_ids": ids, "chunk_table": tbl,
                      "chunk_cached": done, "chunk_len": this_chunk,
                      "chunk_pages": win_pages}
            if req.ring:
                # the window's rows in the ring: pages past the chunk's
                # end take pad rows, which go to the sink as the full
                # layers' go to the scratch page
                ring_tbl = self._ring_table(req.ring, self.table_width)
                window["chunk_table_ring"] = ring_tbl
                window["chunk_pages_ring"] = np.where(
                    np.arange(n_win) < -(-this_chunk // bs),
                    ring_tbl[wp0:wp0 + n_win], 0)
        if self._watchdog is not None:
            self._watchdog.phase = "decode"
        chaos.maybe_hang("decode")
        self._step_kind = "mixed"
        # the window is this request's prefill work for the step —
        # span-coverage checks see the same prefill.dispatch lifecycle
        # event the split engine's batched admit emits
        with (_NULL_SPAN if tr is None else tr.span(
                "prefill.dispatch", bucket=self.token_budget, batch=1,
                cached_prefix=req.n_prefix > 0, chunk_tokens=this_chunk,
                req_ids=[req.req_id])):
            with (_NULL_SPAN if tr is None
                  else tr.span("decode.dispatch")) as sp:
                t_disp0 = time.perf_counter()
                with self._commit_lock:
                    self._check_owner(token)
                    st["dispatched"] = True
                    chunk = self.device_steps + 1
                    with (_NULL_SPAN if tr is None else tr.span(
                            "decode.stage", chunk=chunk)) as stage:
                        live = np.asarray(
                            [s.req is not None for s in self._slots])
                        io_in, io_out = self._io["mixed"]
                        flat, host = self._put(
                            io_in, {**self._slot_inputs(live), **window})
                        if tr is not None:
                            stage.set(**_moved("h2d", host))
                    with (_NULL_SPAN if tr is None else tr.span(
                            "decode.enqueue", chunk=chunk)):
                        res = self._unified(self.p, self.kcs, self.vcs,
                                            flat, self._key,
                                            *self._sampling())
                    packed, self._key, self.kcs, self.vcs = res
                    self.device_steps += 1
                    self.prefill_chunks += 1
                    # a mixed step is authoritative host state — never
                    # chain a pipelined decode chunk across it
                    self._chain_tok = None
                    self._chain_lens = None
                    self._override[:] = True
                    if tr is not None:
                        sp.set(chunk=self.device_steps,
                               live=int(live.sum()), prefill_window=True,
                               req_id=req.req_id)
                    if mt is not None:
                        mt.gauge("live_slots", "slots decoding").set(
                            int(live.sum()))
                        mt.gauge("kv_pages_available",
                                 "free + evictable pool pages").set(
                                     self.mgr.n_available)
                    rec = {"packed": packed, "layout": io_out,
                           "reqs": [s.req for s in self._slots],
                           "t_disp0": t_disp0, "chunk": chunk}
            # the first token and its log-probability come back with the
            # decode lane's outputs, in the same copy
            produced = self._commit_chunk(rec, token)
            first = int(rec["host"]["first"][0])
            first_lp = [float(x) for x in
                        rec["host"].get("first_logprob", ())]
        if mt is not None:
            mt.histogram(
                "prefill_chunk_s",
                "prefill dispatch + first-token readback").observe(
                    time.perf_counter() - t_disp0)
        with (_NULL_SPAN if tr is None else tr.span(
                "sched.commit", iter=self.sched_iters)) as sp:
            with self._commit_lock:
                self._check_owner(token)
                st["done"] = done + this_chunk
                self.chunk_tokens += this_chunk
                self._count_dropped(done, st["done"])
                final = st["done"] >= L
                if final:
                    self._prefilling = None
                    req.logprobs.extend(first_lp)
                    self._finish_unified_prefill(req, first, st["t0"])
            if tr is not None:
                sp.set(produced=int(final),
                       retired=[req.req_id] if req.done else [],
                       emitted={req.req_id: 1} if final else {})
        return produced

    def _finish_unified_prefill(self, req: ServeRequest, first: int,
                                t_disp0: float):
        """The final window of a prompt committed: account the
        admission, register freshly computed full prompt blocks into
        the prefix cache, and install the request — a decode slot bind,
        or the disaggregated handoff (EOS-first / max_new==1 retires at
        the handoff without ever taking a slot, as in the split path).
        Called under `_commit_lock`."""
        bs = self.block_size
        req.tokens.append(first)
        now = time.perf_counter()
        req.prefill_time = now
        self.prompt_tokens += len(req.prompt)
        self.prefix_hit_tokens += req.cached_tokens
        tr, mt = self._tracer, self._metrics
        if tr is not None:
            tr.instant("req.admit", req_id=req.req_id,
                       cached_tokens=req.cached_tokens,
                       suffix_bucket=self.token_budget)
        if mt is not None:
            mt.histogram("ttft_s", "arrival to first token").observe(
                now - req.arrival_time)
            mt.histogram("queue_wait_s",
                         "arrival to prefill dispatch").observe(
                             max(t_disp0 - req.arrival_time, 0.0))
            mt.counter("requests_admitted").inc()
            mt.counter("prompt_tokens").inc(len(req.prompt))
            mt.counter("prefix_hit_tokens").inc(req.cached_tokens)
        if self.prefix_cache:
            full = len(req.prompt) // bs
            if full > req.n_prefix:
                self.prefix_inserts += self.mgr.insert_prefix(
                    req.prompt, req.pages[req.n_prefix:full],
                    start_block=req.n_prefix, hashes=req.block_hashes)
        if self.disaggregated:
            self.prefill_handoffs += 1
            if tr is not None:
                tr.instant("req.handoff", req_id=req.req_id)
            if mt is not None:
                mt.counter("prefill_handoffs").inc()
            if (self.eos is not None and first == self.eos) \
                    or req.max_new == 1:
                self._finish_prefilled(req)
            else:
                self._handoff.append(req)
            return
        free = [i for i, s in enumerate(self._slots) if s.req is None]
        if not free:
            raise RuntimeError(
                "no free decode slot at unified prefill completion — "
                "_admit_unified guarantees one (slots only free up "
                "between admission and completion)")
        self._bind_slot(free[0], req)

    def _step_unified(self, token: Optional[int], pipeline: bool) -> int:
        """One unified-path scheduling iteration. Pure-decode phases
        dispatch the plain decode-chunk program — synchronous or
        double-buffered exactly like the split engine (bitwise the same
        program). When a request is prefilling, the step becomes a
        MIXED dispatch of the unified program; any pipelined chunk in
        flight commits first (its device-side chain cannot span a
        program that rewrites host state)."""
        wd, tr = self._watchdog, self._tracer
        if wd is not None:
            wd.phase = "admit"
        with (_NULL_SPAN if tr is None else tr.span(
                "sched.admit", iter=self.sched_iters,
                waiting=len(self.waiting))) as sp:
            admitted = self._admit_unified(token)
            if tr is not None:
                sp.set(req_ids=admitted)
        if self.disaggregated:
            self._install_handoffs(token)
        if self._prefilling is not None:
            with self._commit_lock:
                self._check_owner(token)
                prev, self._inflight = self._inflight, None
            n = 0
            if prev is not None:
                if wd is not None:
                    wd.phase = "commit"
                n = self._commit_chunk(prev, token)
                if wd is not None:
                    wd.phase = "admit"
            return n + self._dispatch_commit_unified(token)
        if self.spec_k:
            # pure-decode phase: speculative verify replaces the plain
            # chunk (prefill phases above keep the unified mixed
            # program — drafting against a half-prefilled prompt has
            # nothing to verify against)
            return self._drain_inflight(token) + self._step_spec(token)
        rec = self._dispatch_chunk(token, chain=pipeline)
        if pipeline:
            with self._commit_lock:
                self._check_owner(token)
                prev, self._inflight = self._inflight, rec
            if prev is not None:
                if wd is not None:
                    wd.phase = "commit"
                return self._commit_chunk(prev, token)
            return 0
        if rec is None:
            return 0
        return self._commit_chunk(rec, token)

    def _count_dropped(self, before: int, after: int) -> None:
        """A sequence's cache grew from `before` to `after` tokens: count
        those that left its window layers' window on the way."""
        if self._window:
            self.window_tokens_dropped += max(after - self._window, 0) \
                - max(before - self._window, 0)

    def _retire(self, slot_id: int, failed: bool = False,
                error: Optional[str] = None):
        slot = self._slots[slot_id]
        req = slot.req
        req.finish_time = time.perf_counter()
        req.failed = failed
        req.error = error
        self.finished.append(req)
        tr, mt = self._tracer, self._metrics
        if tr is not None:
            tr.instant("req.retire", req_id=req.req_id, slot=slot_id,
                       tokens=len(req.tokens), failed=failed,
                       priority=req.priority, deadline_s=req.deadline_s,
                       deadline_miss=(
                           req.deadline_s is not None
                           and req.finish_time - req.arrival_time
                           > req.deadline_s))
        if mt is not None:
            mt.counter("requests_failed" if failed
                       else "requests_finished").inc()
            if not failed and req.prefill_time is not None \
                    and len(req.tokens) > 1:
                # time per OUTPUT token, first (prefill) token excluded
                mt.histogram(
                    "tpot_s", "decode seconds per output token").observe(
                        (req.finish_time - req.prefill_time)
                        / (len(req.tokens) - 1))
        # refcount-aware: private pages recycle now; shared prefix pages
        # only once NO live slot maps them (then LRU, evict on pressure)
        self._release(req)
        slot.req, slot.length, slot.emitted, slot.done = None, 0, 0, False
        # the row MUST stop pointing at freed pages before they recycle
        self._tables[slot_id] = self.scratch_page
        if self._ring_tables is not None:
            self._ring_tables[slot_id] = 0
        self._tokens[slot_id] = 0
        self._budgets[slot_id] = 0
        self._override[slot_id] = True
        if self._drafter is not None:
            self._drafter.release(slot_id)

    def _dispatch_chunk(self, token: Optional[int] = None,
                        chain: bool = False):
        """Enqueue one decode chunk WITHOUT waiting for its results.
        Returns the pending-chunk record (None if no slot is live).
        With `chain`, token/length inputs ride the previous chunk's
        device outputs except where the host changed a slot since the
        last dispatch (admission/retire set `_override`); without it,
        inputs come from host state and the chain is invalidated."""
        live = np.asarray([s.req is not None for s in self._slots])
        if not live.any():
            return None
        if self._watchdog is not None:
            self._watchdog.phase = "decode"
        # chaos hang seam sits BEFORE the device call and BEFORE the
        # lock: a watchdog-abandoned step must unwind (ChaosHang) or
        # abort at the owner check without ever dispatching against the
        # donated KV pools from a dead thread
        chaos.maybe_hang("decode")
        tr, mt = self._tracer, self._metrics
        with (_NULL_SPAN if tr is None
              else tr.span("decode.dispatch")) as sp:
            t_disp0 = time.perf_counter()
            with self._commit_lock:
                self._check_owner(token)
                chunk = self.device_steps + 1
                with (_NULL_SPAN if tr is None else tr.span(
                        "decode.stage", chunk=chunk)) as stage:
                    chained = chain and self._chain_tok is not None
                    io_in, io_out = self._io["decode"]
                    flat, host = self._put(io_in, {
                        **self._slot_inputs(live),
                        "override": self._override if chained else True})
                    if tr is not None:
                        stage.set(**_moved("h2d", host))
                carries = (self._chain_tok, self._chain_lens) if chained \
                    else (self._no_chain, self._no_chain)
                with (_NULL_SPAN if tr is None else tr.span(
                        "decode.enqueue", chunk=chunk)):
                    res = self._decode(self.p, self.kcs, self.vcs, flat,
                                       *carries, self._key,
                                       *self._sampling())
                packed, tok, new_lens, self._key, self.kcs, self.vcs = res
                self.device_steps += 1
                if chain:
                    self._chain_tok = tok
                    self._chain_lens = new_lens
                    self._override[:] = False
                else:
                    # host state is authoritative after a synchronous
                    # step; a later pipelined dispatch must not chain a
                    # stale chunk
                    self._chain_tok = None
                    self._chain_lens = None
                    self._override[:] = True
                if tr is not None:
                    sp.set(chunk=self.device_steps, live=int(live.sum()))
                if mt is not None:
                    mt.gauge("live_slots", "slots decoding").set(
                        int(live.sum()))
                    mt.gauge("kv_pages_available",
                             "free + evictable pool pages").set(
                                 self.mgr.n_available)
                # dispatch wall time rides the record: _commit_chunk
                # turns (dispatch start -> readback done) into
                # decode_chunk_s
                rec = {"packed": packed, "layout": io_out,
                       "reqs": [s.req for s in self._slots],
                       "t_disp0": t_disp0, "chunk": chunk}
        return rec

    def _read_back(self, chunk: int, out):
        """The host's side of a program's end, under the caller's
        `decode.sync_wait`: wait until the program's one host-visible
        output is ready (`decode.device_wait`: the host waiting on the
        device), then copy it (`decode.readback`: one copy of a finished
        array, the transfer alone). Returns the host copy."""
        tr = self._tracer
        with (_NULL_SPAN if tr is None else tr.span(
                "decode.device_wait", chunk=chunk)):
            out.block_until_ready()
        with (_NULL_SPAN if tr is None else tr.span(
                "decode.readback", chunk=chunk)) as sp:
            host = np.asarray(out)
            if tr is not None:
                sp.set(**_moved("d2h", host))
        return host

    def _commit_chunk(self, rec, token: Optional[int] = None) -> int:
        """Block on a dispatched chunk's host-visible outputs and commit
        them: extend token lists, advance lengths, retire EOS/finished
        rows. Rows whose slot changed hands since the chunk was
        dispatched (double buffering: retired then re-admitted) are
        skipped — their device work was speculative waste, their writes
        are confined to pages that are overwritten before any new owner
        reads them. The outputs, split by `rec["layout"]`, stay on
        `rec["host"]` (a mixed step's first token is among them).
        Returns live tokens produced."""
        tr, mt = self._tracer, self._metrics
        # a `stalled` span is the double-buffer stall the pipeline
        # exists to hide (Perfetto query: name='decode.sync_wait' AND
        # args.stalled)
        with (_NULL_SPAN if tr is None else tr.span(
                "decode.sync_wait", chunk=rec["chunk"])) as sp:
            t0 = time.perf_counter()
            got = rec["host"] = rec["layout"].unpack(
                self._read_back(rec["chunk"], rec["packed"]))
            out, new_lens, done = got["out"], got["lens"], got["done"]
            lps = got.get("logprobs")
            t1 = time.perf_counter()
            wait = t1 - t0
            stalled = wait > self.stall_threshold_s
            if tr is not None:
                sp.set(stalled=stalled)
        with (_NULL_SPAN if tr is None else tr.span(
                "sched.commit", iter=self.sched_iters)) as sp:
            if mt is not None:
                mt.histogram(
                    "sync_wait_s",
                    "host blocked on decode readback").observe(wait)
                mt.histogram("decode_chunk_s",
                             "decode-chunk dispatch to readback").observe(
                                 t1 - rec.get("t_disp0", t0))
                if stalled:
                    mt.counter("blocked_syncs").inc()
            if tr is not None:
                retired, emitted = [], {}
            with self._commit_lock:
                self._check_owner(token)  # abandoned mid-wait: discard
                self.sync_wait_s += wait
                if stalled:
                    self.blocked_syncs += 1
                # the routed layers' counts came with the tokens: the
                # decode lane's row, and a mixed step's window's
                for lane, row in zip(("decode", "chunk"), got.get("moe", ())):
                    self.moe_counts[lane] += row
                produced = 0
                for slot_id, slot in enumerate(self._slots):
                    req = rec["reqs"][slot_id]
                    if req is None or slot.req is not req or req.done:
                        continue
                    take = min(self.steps, req.max_new - slot.emitted)
                    toks = out[slot_id, :take].tolist()
                    if self.eos is not None and self.eos in toks:
                        toks = toks[:toks.index(self.eos) + 1]
                    req.tokens.extend(toks)
                    if lps is not None:
                        req.logprobs.extend(
                            lps[slot_id, :len(toks)].tolist())
                    produced += len(toks)
                    slot.emitted += len(toks)
                    self._count_dropped(slot.length,
                                        int(new_lens[slot_id]))
                    slot.length = int(new_lens[slot_id])
                    slot.done = bool(done[slot_id])
                    self._tokens[slot_id] = toks[-1] if toks else 0
                    if slot.done or slot.emitted >= req.max_new:
                        self._retire(slot_id)
                    if tr is not None:
                        emitted[req.req_id] = len(toks)
                        if req.done:
                            retired.append(req.req_id)
                if mt is not None:
                    mt.counter("output_tokens").inc(produced)
            if tr is not None:
                sp.set(produced=produced, retired=retired,
                       emitted=emitted)
        return produced

    def _step_spec(self, token: Optional[int] = None) -> int:
        """One speculative iteration (ISSUE 19): draft up to spec_k
        tokens per live slot (host-side n-gram lookup, or the draft
        model on its own tiny pools), verify every draft plus the
        slot's pending token as ONE ragged window of spec_k+1 rows
        through the target, then commit the longest matching draft
        prefix + the target's one corrected token. Greedy-only by
        construction, so accepted output is EXACTLY what sequential
        decode would have produced. A slot whose drafter comes up
        empty rides the window at new_len=1 — one verified token, the
        plain decode step's math. Synchronous: the committed length is
        a host-side acceptance decision, so no device-side chain can
        span a speculative step (the double-buffer chain is
        invalidated at dispatch)."""
        live = np.asarray([s.req is not None for s in self._slots])
        if not live.any():
            return 0
        wd = self._watchdog
        if wd is not None:
            wd.phase = "decode"
        # chaos hang seam BEFORE the device call and BEFORE the lock,
        # exactly like _dispatch_chunk: an abandoned speculative step
        # must unwind without ever dispatching against donated pools
        chaos.maybe_hang("decode")
        tr, mt = self._tracer, self._metrics
        b, k = self.slots, self.spec_k
        # adaptive depth caps how much we ASK the drafter for — the
        # verify window stays k+1 rows, unproposed depth just verifies
        # as a narrower ragged window (same program, no recompile)
        k_eff = self._spec_policy.spec_k_effective \
            if self._spec_policy is not None else k
        self._step_kind = "spec"
        with (_NULL_SPAN if tr is None else tr.span("spec.verify")) as sp:
            t_disp0 = time.perf_counter()
            with self._commit_lock:
                self._check_owner(token)
                with (_NULL_SPAN if tr is None else tr.span(
                        "sched.build", iter=self.sched_iters)):
                    ids = np.zeros((b, k + 1), np.int32)
                    new_lens = np.ones((b,), np.int32)
                    lens = np.asarray([s.length for s in self._slots],
                                      np.int32)
                    drafts = [None] * b
                    reqs = [s.req for s in self._slots]
                    for slot_id, slot in enumerate(self._slots):
                        req = slot.req
                        if req is None:
                            continue  # dead rows ride the scratch page
                        ids[slot_id, 0] = self._tokens[slot_id]
                        # never draft past the row budget: window
                        # position L+j writes K/V there, and the
                        # corrected token needs its own headroom too
                        want = min(
                            k_eff,
                            int(self._budgets[slot_id]) - slot.length - 1,
                            req.max_new - slot.emitted - 1)
                        d = []
                        if want > 0 and self._drafter is not None:
                            d = list(self._drafter.draft(
                                slot_id, req.req_id,
                                req.prompt + req.tokens, want,
                                table_row=self._tables[slot_id],
                                budget=int(self._budgets[slot_id])))[:want]
                        drafts[slot_id] = d
                        ids[slot_id, 1:1 + len(d)] = d
                        new_lens[slot_id] = 1 + len(d)
                res = self._verify(
                    self.p, self.kcs, self.vcs, jnp.asarray(ids),
                    jnp.asarray(self._tables), jnp.asarray(lens),
                    jnp.asarray(new_lens))
                preds_dev, self.kcs, self.vcs = res
                self.device_steps += 1
                self.spec_steps += 1
                # acceptance rewrites host tokens/lengths per slot — a
                # later pipelined dispatch must not chain stale device
                # state
                self._chain_tok = None
                self._chain_lens = None
                self._override[:] = True
                if tr is not None:
                    sp.set(chunk=self.device_steps, live=int(live.sum()),
                           drafted=int(sum(len(d) for d in drafts if d)))
                if mt is not None:
                    mt.gauge("live_slots", "slots decoding").set(
                        int(live.sum()))
        # the blocking readback stays OUTSIDE the lock — sync-wait
        # telemetry identical to _commit_chunk's
        chunk = self.device_steps
        with (_NULL_SPAN if tr is None else tr.span(
                "decode.sync_wait", chunk=chunk)) as sp:
            t0 = time.perf_counter()
            preds = self._read_back(chunk, preds_dev)
            t1 = time.perf_counter()
            wait = t1 - t0
            stalled = wait > self.stall_threshold_s
            if tr is not None:
                sp.set(stalled=stalled)
        if wd is not None:
            wd.phase = "commit"
        with (_NULL_SPAN if tr is None else tr.span(
                "sched.commit", iter=self.sched_iters)) as sp:
            if mt is not None:
                mt.histogram(
                    "sync_wait_s",
                    "host blocked on decode readback").observe(wait)
                mt.histogram("decode_chunk_s",
                             "decode-chunk dispatch to readback").observe(
                                 t1 - t_disp0)
                if stalled:
                    mt.counter("blocked_syncs").inc()
            if tr is not None:
                retired, emitted = [], {}
            with self._commit_lock:
                self._check_owner(token)  # abandoned mid-wait: discard
                self.sync_wait_s += wait
                if stalled:
                    self.blocked_syncs += 1
                produced = 0
                for slot_id, slot in enumerate(self._slots):
                    req = reqs[slot_id]
                    if req is None or slot.req is not req or req.done:
                        continue
                    d = drafts[slot_id]
                    row = preds[slot_id]
                    n_acc = 0
                    while n_acc < len(d) and d[n_acc] == int(row[n_acc]):
                        n_acc += 1
                    # accepted drafts + the target's corrected token;
                    # clip to the request's remaining output budget,
                    # then to EOS
                    toks = d[:n_acc] + [int(row[n_acc])]
                    toks = toks[:max(req.max_new - slot.emitted, 0)]
                    if self.eos is not None and self.eos in toks:
                        toks = toks[:toks.index(self.eos) + 1]
                    self.spec_drafted += len(d)
                    self.spec_accepted += min(n_acc, len(toks))
                    if d and self._spec_policy is not None:
                        self._spec_policy.observe(len(d), n_acc)
                    if d and mt is not None:
                        mt.histogram(
                            "spec_acceptance",
                            "accepted draft fraction per window").observe(
                                n_acc / len(d))
                    req.tokens.extend(toks)
                    produced += len(toks)
                    slot.emitted += len(toks)
                    # the window WROTE positions L..L+new_len-1;
                    # everything before the new pending token
                    # (toks[-1]) is committed cache, the rest is
                    # garbage a later commit overwrites
                    slot.length += len(toks)
                    self._tokens[slot_id] = toks[-1] if toks else 0
                    if self._drafter is not None:
                        self._drafter.note_commit(slot_id, slot.length)
                    if (self.eos is not None and toks
                            and toks[-1] == self.eos) \
                            or slot.emitted >= req.max_new:
                        self._retire(slot_id)
                    if tr is not None:
                        emitted[req.req_id] = len(toks)
                        if req.done:
                            retired.append(req.req_id)
                if mt is not None:
                    mt.counter("output_tokens").inc(produced)
            if tr is not None:
                sp.set(produced=produced, retired=retired,
                       emitted=emitted)
        return produced

    def _drain_inflight(self, token: Optional[int] = None) -> int:
        """Commit (and clear) any pipelined chunk in flight — the
        speculative step rewrites host lengths, so a chained chunk
        from before it must land first."""
        with self._commit_lock:
            self._check_owner(token)
            prev, self._inflight = self._inflight, None
        if prev is None:
            return 0
        if self._watchdog is not None:
            self._watchdog.phase = "commit"
        return self._commit_chunk(prev, token)

    def step(self) -> int:
        """One synchronous scheduling iteration: admit -> decode chunk
        -> wait -> retire. Returns the number of live tokens produced."""
        wd, tr = self._watchdog, self._tracer
        # ownership token: if the watchdog abandons this step, run()
        # bumps _step_epoch and every later commit point in THIS thread
        # raises _AbandonedStep instead of racing the live loop
        token = self._step_epoch if wd is not None else None
        self.sched_iters += 1
        self._step_kind = "decode"  # a prefill or a verify renames it
        with (_NULL_SPAN if tr is None else tr.span(
                "sched.step", iter=self.sched_iters)) as step_sp:
            if self.unified:
                n = self._step_unified(token, pipeline=False)
            else:
                if wd is not None:
                    wd.phase = "admit"
                with (_NULL_SPAN if tr is None else tr.span(
                        "sched.admit", iter=self.sched_iters,
                        waiting=len(self.waiting))) as sp:
                    admitted = self._admit(token)
                    if tr is not None:
                        sp.set(req_ids=admitted)
                if self.disaggregated:
                    self._install_handoffs(token)
                if self.spec_k:
                    n = self._step_spec(token)
                else:
                    rec = self._dispatch_chunk(token, chain=False)
                    n = 0 if rec is None \
                        else self._commit_chunk(rec, token)
            if tr is not None:
                step_sp.set(kind=self._step_kind)
        return n

    def _pipeline_step(self) -> int:
        """One double-buffered iteration: admit, dispatch chunk N+1,
        THEN block on chunk N — the host-side commit work (token
        readback, retirement, next admission's planning) overlaps chunk
        N+1's device time instead of serializing with it. Admissions and
        retirements take effect one chunk later than in synchronous
        mode; budgets and the slot-ownership snapshot keep the
        speculative chunk harmless (see module docstring)."""
        wd, tr = self._watchdog, self._tracer
        token = self._step_epoch if wd is not None else None
        self.sched_iters += 1
        self._step_kind = "decode"
        with (_NULL_SPAN if tr is None else tr.span(
                "sched.step", iter=self.sched_iters)) as step_sp:
            if self.unified:
                n = self._step_unified(token, pipeline=True)
            else:
                if wd is not None:
                    wd.phase = "admit"
                with (_NULL_SPAN if tr is None else tr.span(
                        "sched.admit", iter=self.sched_iters,
                        waiting=len(self.waiting))) as sp:
                    admitted = self._admit(token)
                    if tr is not None:
                        sp.set(req_ids=admitted)
                if self.disaggregated:
                    self._install_handoffs(token)
                if self.spec_k:
                    # speculative steps are synchronous (acceptance is a
                    # host decision) — drain any chained chunk, then
                    # verify
                    n = self._drain_inflight(token) \
                        + self._step_spec(token)
                else:
                    rec = self._dispatch_chunk(token, chain=True)
                    with self._commit_lock:
                        self._check_owner(token)
                        prev, self._inflight = self._inflight, rec
                    n = 0
                    if prev is not None:
                        if wd is not None:
                            wd.phase = "commit"
                        n = self._commit_chunk(prev, token)
            if tr is not None:
                step_sp.set(kind=self._step_kind)
        return n

    def run(self, max_iters: int = 100000,
            watchdog_timeout: Optional[float] = None,
            double_buffer: Optional[bool] = None,
            requeue_hung: bool = False):
        """Drain the queues. `watchdog_timeout` (seconds; default from
        FLAGS_step_timeout_s / PADDLE_TPU_STEP_TIMEOUT_S, 0 = off)
        bounds every scheduling step with a wall-clock deadline: a hung
        step retires ONE victim slot (marked `failed`, its pages freed —
        but a cached prefix page another live slot maps stays pinned by
        its refcount) and the engine keeps serving the remaining
        requests instead of wedging. A timeout with no live slot to
        blame re-raises — the engine itself is stuck, not a request. In
        double-buffered mode a timeout also DROPS the uncommitted
        in-flight chunk: its tokens were never committed, so the
        surviving rows simply regenerate them from the last committed
        host state (the overwritten KV slots were never read). Call
        `warm()` before arming a tight deadline: a first-admit compile
        inside a watchdogged step would eat the whole budget (and an
        abandoned step mid-compile keeps running on its worker
        thread).

        `requeue_hung` (ISSUE 12 satellite — the shed/requeue building
        block of an SLO-aware front-end): instead of retiring the
        victim as `failed`, give it ONE retry — the request re-enters
        `waiting` (head of queue: it is the oldest row) with its slot
        freed and pages RELEASED through the refcount-aware pool, never
        recycled in place; generation restarts from the prompt on
        re-admission. The second timeout of the same request retires
        it failed as before. Counted by `metrics()['hung_requeued']`."""
        if watchdog_timeout is None:
            from ..framework.flags import flag

            watchdog_timeout = float(flag("step_timeout_s"))
        self._requeue_hung = bool(requeue_hung)
        db = self.double_buffer if double_buffer is None else double_buffer
        step_fn = self._pipeline_step if db else self.step
        wd = None
        if watchdog_timeout and watchdog_timeout > 0:
            from ..resilience.watchdog import StepTimeout, Watchdog

            wd = Watchdog(watchdog_timeout, name="engine.step")
        self._watchdog = wd
        try:
            while self.has_work and max_iters:
                if wd is None:
                    step_fn()
                else:
                    try:
                        wd.call(step_fn)
                    except StepTimeout as e:
                        # reclaim ownership FIRST: the abandoned thread
                        # aborts at its next _check_owner instead of
                        # committing stale results (or dispatching
                        # against donated pools) under the live loop;
                        # the lock serializes this against a commit in
                        # flight RIGHT at the deadline (either it fully
                        # lands before the bump, or fully aborts after).
                        with self._commit_lock:
                            self._step_epoch += 1
                            self._inflight = None
                            self._chain_tok = None
                            self._chain_lens = None
                            self._override[:] = True
                            retired = self._retire_hung_slot(e)
                        if not retired:
                            raise
                max_iters -= 1
        finally:
            self._watchdog = None
            # a drained pipeline may exit with one uncommitted
            # speculative chunk (every row in it already retired);
            # drop it so a later run() never commits a stale record
            with self._commit_lock:
                self._inflight = None
                self._chain_tok = None
                self._chain_lens = None
                self._override[:] = True
        if self.has_work:
            raise RuntimeError("engine did not drain within max_iters")
        return self.finished

    def _retire_hung_slot(self, exc) -> bool:
        """Degrade gracefully after a StepTimeout: fail the victim slot
        (lowest-id live slot — deterministic, and FIFO admission makes
        it the longest-running row), recycle its pages, keep the rest.
        With `requeue_hung` armed, a first-time victim is REQUEUED
        instead (see `run`). Returns False when no slot is live
        (nothing to blame). Always called under `_commit_lock` AFTER
        the epoch bump, so the abandoned step thread can never commit
        tokens into (or dispatch against the pages of) the request we
        reset here."""
        # unified path: a timeout while the prefilling request's
        # window HAS ridden a dispatch blames THAT request first — the
        # decode scan is the long-proven program, and blaming decode
        # would serially fail up to `slots` innocent rows against a
        # deterministically hanging window (the same window
        # re-dispatches every step — `done` never advanced).
        # requeue_hung still gives it its one retry; its committed
        # chunks release through the refcounted pool. A freshly
        # admitted request whose window never dispatched (the timeout
        # hit the drain of a pure-decode chunk from BEFORE admission)
        # is innocent — the split decode-victim policy applies.
        if self._prefilling is not None                 and self._prefilling.get("dispatched"):
            self._fail_prefilling(exc)
            return True
        live = [i for i, s in enumerate(self._slots) if s.req is not None]
        if not live:
            if self._prefilling is not None:
                # nothing else to blame: the undispatched-window edge
                # collapses back onto the prefilling request
                self._fail_prefilling(exc)
                return True
            return False
        victim = live[0]
        if self._requeue_hung and not self._slots[victim].req.requeued:
            self._requeue_slot(victim)
            return True
        self.hung_retired += 1
        self._emit_hung_retire(victim, exc)
        self._retire(victim, failed=True, error=str(exc))
        return True

    def _emit_hung_retire(self, slot, exc):
        """The watchdog.retire_hung_slot tracer/metrics emission shared
        by the slot-victim and prefilling-victim paths (slot is None
        for the latter)."""
        tr, mt = self._tracer, self._metrics
        if tr is not None:
            tr.instant("watchdog.retire_hung_slot", slot=slot,
                       phase=getattr(exc, "phase", None),
                       elapsed_s=getattr(exc, "elapsed_s", None))
        if mt is not None:
            mt.counter("hung_slots_retired").inc()
            mt.event("watchdog.retire_hung_slot", slot=slot,
                     phase=getattr(exc, "phase", None),
                     timeout_s=getattr(exc, "timeout_s", None))

    def _emit_hung_requeue(self, slot, req):
        """The watchdog.requeue_hung_slot emission shared by both
        requeue paths."""
        tr, mt = self._tracer, self._metrics
        if tr is not None:
            tr.instant("watchdog.requeue_hung_slot", slot=slot,
                       req_id=req.req_id)
        if mt is not None:
            mt.counter("hung_slots_requeued").inc()
            mt.event("watchdog.requeue_hung_slot", slot=slot,
                     req_id=req.req_id)

    def _fail_prefilling(self, exc):
        """Watchdog victim = the request mid-chunked-prefill (unified
        path, no decode slot to blame): release its reservation through
        the refcount-aware pool (shared prefix pages another slot maps
        stay pinned) and fail it — or, under `requeue_hung`, give it
        its one retry from the head of `waiting` (prefill restarts at
        the prompt; the committed windows' pages were released, never
        recycled in place). Called under `_commit_lock` after the epoch
        bump, like `_retire_hung_slot`."""
        st, self._prefilling = self._prefilling, None
        req = st["req"]
        self._release(req)
        req.n_prefix = 0
        req.cached_tokens = 0
        req.bucket = None
        if self._requeue_hung and not req.requeued:
            req.requeued = True
            self.hung_requeued += 1
            self.waiting.insert(0, req)
            self._emit_hung_requeue(None, req)
            return
        self.hung_retired += 1
        req.finish_time = time.perf_counter()
        # every request in `finished` carries a prefill_time (the split
        # path prefills before any failure can land) — a mid-prefill
        # failure pins it to finish_time so TTFT consumers iterating
        # `finished` never hit a None hole
        if req.prefill_time is None:
            req.prefill_time = req.finish_time
        req.failed = True
        req.error = str(exc)
        self.finished.append(req)
        self._emit_hung_retire(None, exc)
        tr, mt = self._tracer, self._metrics
        if tr is not None:
            tr.instant("req.retire", req_id=req.req_id, slot=None,
                       tokens=len(req.tokens), failed=True)
        if mt is not None:
            mt.counter("requests_failed").inc()

    def _requeue_slot(self, slot_id: int):
        """Put a hung slot's request back at the head of `waiting` for
        exactly one retry: release its pages through the refcount-aware
        pool (a shared prefix page a live peer maps stays pinned — the
        pages are never recycled in place), reset the request to its
        pre-admission state (tokens regenerate from the prompt; the
        memoized block hashes survive), and free the slot row."""
        slot = self._slots[slot_id]
        req = slot.req
        req.requeued = True
        self.hung_requeued += 1
        self._release(req)
        req.slot = None
        req.bucket = None
        req.tokens, req.logprobs = [], []
        req.prefill_time = None
        req.n_prefix = 0
        req.cached_tokens = 0
        slot.req, slot.length, slot.emitted, slot.done = None, 0, 0, False
        # the row must stop pointing at released pages before they are
        # handed to another request
        self._tables[slot_id] = self.scratch_page
        if self._ring_tables is not None:
            self._ring_tables[slot_id] = 0
        self._tokens[slot_id] = 0
        self._budgets[slot_id] = 0
        self._override[slot_id] = True
        if self._drafter is not None:
            self._drafter.release(slot_id)
        self.waiting.insert(0, req)
        self._emit_hung_requeue(slot_id, req)
