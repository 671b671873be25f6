"""Auditor-driven static autotuner (ISSUE 16): the three pre-silicon
auditors turned into an objective function.

PRs 10/11/13 built the predictors — peak-HBM liveness
(`analysis/memory.py`), bytes-on-wire (`analysis/comms.py`), and the
roofline step-time/MFU pass (`analysis/roofline.py`). This module
points them at the config space the serving engine already exposes and
lets them DECIDE instead of lint:

- **search space**: the engine's build-time knobs — KV page size
  (`block_size`, candidates from the kernels' own `fit_vmem_block`
  rule via `models.llama.serving_block_size_candidates`),
  `kv_cache_dtype` (bf16 | int8 pools),
  `unified_step` (split program zoo vs ONE ragged step),
  `token_budget` (unified prefill window), `serving_mp` (kv-head
  sharding degree; only degrees the host's device count and the
  model's kv heads admit), `serving_cp` (page-axis context-parallel
  degree, ISSUE 18; only degrees that divide a pinned `max_pages` —
  the default pool rounds itself — with cp*mp meshes the host cannot
  build pruned by name), and `quantized_collectives` (int8 wire;
  collapsed at mp=1 AND cp=1 — the cp merge ships quantized acc
  partials, so the knob is live whenever either axis is).
- **feasibility gate** (memory.py + `device_specs.auto_hbm_budget`):
  a candidate is pruned BEFORE any trace when its static
  params + pool byte bound already exceeds the device row's budget,
  and after tracing when the liveness pass's per-chip peak does. Both
  comparisons use the same budget derivation TPU702 auto-arms with.
- **objective** (roofline.py + comms.py): surviving candidates are
  ranked by the decode chunk's predicted per-chip step time
  (compute/bandwidth/wire max + launch overhead), with wire bytes per
  decoded token, traced peak HBM, then the canonical config string as
  deterministic tie-breaks. The all-defaults config is always
  enumerated, so the winner's predicted step time can never exceed
  it.

Everything runs off traced jaxprs on the host — no silicon, no RNG,
no wall clock: same inputs always produce the identical ranking.
On-device top-k verification is the gated follow-up (ROADMAP).

The winner exports as a `TunedConfig` artifact
(`.paddle_tpu_tune.json`, schema-versioned, invalidated when the
device row / model shape / flag-space hash changes) that
`ContinuousBatchingEngine(config=...)` — or the
PADDLE_TPU_TUNED_CONFIG flag — applies at build time, pairing with
the persistent compile cache (`serving/compile_cache.py`) so a fleet
restart warms from disk instead of recompiling the tuned programs.

CLI::

    python -m paddle_tpu.analysis --tune [--device tpu-v5e]
        [--budget-candidates N] [--format json]
"""
from __future__ import annotations

import dataclasses
import hashlib
import itertools
import json
import os
import warnings
from typing import Dict, List, Optional, Sequence

from .device_specs import (DEFAULT_HBM_HEADROOM, auto_hbm_budget,
                           get_spec)

__all__ = [
    "KNOBS", "SCHEMA_VERSION", "TUNE_FILENAME", "CandidateResult",
    "TunedConfig", "TuningReport", "autotune", "baseline_config",
    "canonical_config", "default_space", "enumerate_candidates",
    "model_signature", "space_hash", "static_candidate_bound",
]

# the engine build-time knobs the tuner sweeps — every one is a
# ContinuousBatchingEngine kwarg of the same name, which is what makes
# TunedConfig.apply() a plain dict merge
KNOBS = ("block_size", "kv_cache_dtype", "quantized_collectives",
         "serving_cp", "serving_mp", "spec_k", "speculative",
         "token_budget", "unified_step")

SCHEMA_VERSION = 2
# the artifact the engine loads; lives next to the persistent compile
# cache so the tuned knobs and the programs they compiled travel
# together
TUNE_FILENAME = ".paddle_tpu_tune.json"


def model_signature(cfg) -> str:
    """Stable shape identity of a model config — what a TunedConfig is
    valid FOR. Any field that changes a traced program's shapes (and
    therefore every auditor estimate) participates; dtype-of-weights
    does not (the engine's `_decode_params` layout owns that)."""
    return ("llama:h{hidden}:l{layers}:q{q}:kv{kv}:d{dh}"
            ":i{inter}:v{vocab}").format(
        hidden=cfg.hidden_size, layers=cfg.num_hidden_layers,
        q=cfg.num_attention_heads, kv=cfg.num_key_value_heads,
        dh=cfg.head_dim, inter=cfg.intermediate_size,
        vocab=cfg.vocab_size)


def space_hash(space: Dict[str, Sequence]) -> str:
    """Hash of the searched flag space: a TunedConfig tuned over one
    space is stale against another (a new knob or widened axis can
    change the winner, so the artifact must not outlive it)."""
    blob = json.dumps({k: list(v) for k, v in sorted(space.items())},
                      sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _engine_geometry(engine_kwargs: dict) -> dict:
    """The non-swept engine sizing the tuner holds fixed, with the
    engine's own defaults filled in (engine.py __init__ signature)."""
    kw = dict(engine_kwargs or {})
    out = {
        "slots": int(kw.get("slots", 8)),
        "prompt_bucket": int(kw.get("prompt_bucket", 64)),
        "max_prompt_len": int(kw.get("max_prompt_len", 512)),
        "max_new_tokens": int(kw.get("max_new_tokens", 64)),
        "steps_per_sync": int(kw.get("steps_per_sync", 8)),
        "block_size": int(kw.get("block_size") or 64),
        "max_pages": kw.get("max_pages"),
        "kv_pool_bytes": kw.get("kv_pool_bytes"),
    }
    out["max_prompt_len"] = -(-out["max_prompt_len"]
                              // out["prompt_bucket"]) \
        * out["prompt_bucket"]
    return out


def baseline_config(cfg, engine_kwargs: Optional[dict] = None) -> dict:
    """The ALL-DEFAULTS candidate: every knob resolved exactly the way
    a plain `ContinuousBatchingEngine(cfg, params, **engine_kwargs)`
    build would resolve it (explicit kwargs win, then the FLAGS_*
    registry). Always enumerated, so `TuningReport.best` can never
    predict worse than what the operator would get by doing nothing."""
    from ..models.llama import (resolve_kv_cache_dtype,
                                resolve_serving_cp, resolve_serving_mp,
                                resolve_unified_step)
    from ..parallel.collectives import resolve_quantized_collectives
    from ..serving.speculative import resolve_spec_k, resolve_speculative

    kw = dict(engine_kwargs or {})
    geo = _engine_geometry(kw)
    speculative = resolve_speculative(kw.get("speculative"))
    config = {
        "block_size": geo["block_size"],
        "kv_cache_dtype": resolve_kv_cache_dtype(
            kw.get("kv_cache_dtype")),
        "quantized_collectives": resolve_quantized_collectives(
            kw.get("quantized_collectives")),
        "serving_cp": resolve_serving_cp(kw.get("serving_cp")),
        "serving_mp": resolve_serving_mp(kw.get("serving_mp")),
        "spec_k": (resolve_spec_k(kw.get("spec_k"))
                   if speculative != "off" else 0),
        "speculative": speculative,
        "token_budget": int(kw.get("token_budget")
                            or geo["prompt_bucket"]),
        "unified_step": resolve_unified_step(kw.get("unified_step")),
    }
    return canonical_config(config, geo)


def canonical_config(config: dict, geo: dict) -> dict:
    """Collapse knob combinations that build byte-identical programs,
    so the enumeration never scores the same program twice under two
    names: `quantized_collectives` is meaningless at mp=1 AND cp=1
    (no collectives exist; with cp>1 the partial merge ships
    quantized acc partials even head-unsharded) and `token_budget` is
    meaningless on the split path (no unified window program is
    built), and `spec_k` is meaningless with speculation off (no
    verify program is built — the window width collapses to 0)."""
    out = dict(config)
    if out["serving_mp"] == 1 and out.get("serving_cp", 1) == 1:
        out["quantized_collectives"] = False
    if not out["unified_step"]:
        out["token_budget"] = geo["prompt_bucket"]
    if out.get("serving_cp", 1) > 1:
        # speculative verify windows don't compose with page-sharded
        # pools yet (ROADMAP follow-up) — the engine refuses the build,
        # so the candidate collapses to its non-speculative twin
        out["speculative"] = "off"
    if out.get("speculative", "off") == "off":
        out["spec_k"] = 0
    return out


def default_space(cfg, engine_kwargs: Optional[dict] = None) -> dict:
    """The default search space for one model + engine geometry:
    knob -> candidate values, deterministic. serving_mp enumerates only
    degrees the HOST can build a mesh for (the tuner builds candidate
    engines to trace them) AND the model's kv heads divide — the MQA
    fallback replicates pools, which defeats the knob's purpose.
    block_size candidates come from the kernels' shared VMEM fit rule;
    token_budget doubles once (wider unified prefill windows trade
    step peak for fewer chunks — the auditors price both sides)."""
    import jax

    from ..models.llama import serving_block_size_candidates

    geo = _engine_geometry(engine_kwargs)
    blocks = sorted(set(
        serving_block_size_candidates(
            cfg, prompt_bucket=geo["prompt_bucket"])
        + [geo["block_size"]]))
    n_dev = len(jax.devices())
    nkv = cfg.num_key_value_heads
    mps = [m for m in (1, 2, 4, 8)
           if m <= n_dev and (m == 1 or nkv % m == 0)]
    # serving_cp shards the PAGE axis, so divisibility is against the
    # pool page count, not the model: a pinned max_pages filters the
    # degrees here; the default pool rounds itself up to a cp
    # multiple, so every host-buildable degree is admissible. cp*mp
    # meshes the host cannot build are pruned by autotune per
    # candidate (a per-knob list cannot express the product bound).
    cps = [c for c in (1, 2, 4, 8)
           if c <= n_dev and (geo["max_pages"] is None
                              or int(geo["max_pages"]) % c == 0)]
    tb = geo["prompt_bucket"]
    # speculative sweeps only the model-free ngram policy (the draft
    # policy needs a drafter instance the tuner cannot conjure) at two
    # draft depths; "off" collapses spec_k, so the product stays tight
    return {
        "block_size": blocks,
        "kv_cache_dtype": ["bf16", "int8"],
        "quantized_collectives": [False, True],
        "serving_cp": cps,
        "serving_mp": mps,
        "spec_k": [4, 8],
        "speculative": ["off", "ngram"],
        "token_budget": sorted({tb, 2 * tb}),
        "unified_step": [False, True],
    }


def enumerate_candidates(space: dict, geo: dict) -> List[dict]:
    """Deterministic candidate list: the cartesian product of the
    space in knob-name order, canonicalized and deduplicated (first
    occurrence wins, so enumeration order is reproducible)."""
    names = sorted(space)
    seen, out = set(), []
    for values in itertools.product(*(space[k] for k in names)):
        config = canonical_config(dict(zip(names, values)), geo)
        key = _config_key(config)
        if key in seen:
            continue
        seen.add(key)
        out.append(config)
    return out


def _config_key(config: dict) -> str:
    return json.dumps(config, sort_keys=True, default=str)


def static_candidate_bound(cfg, params, config: dict,
                           engine_kwargs: Optional[dict] = None) -> int:
    """CHEAP per-chip byte lower bound for one candidate — params +
    the KV pool the engine would allocate — computed from
    `PagedKVManager.page_bytes` static math alone: no engine is built
    and nothing is traced, which is what lets the feasibility gate
    prune OOM configs before any trace-heavy scoring. A lower bound:
    the traced liveness peak adds activations/workspace on top, so
    stage-2 re-checks survivors against the same budget."""
    from ..analysis.memory import pytree_local_bytes
    from ..models.llama import PagedKVManager

    geo = _engine_geometry(engine_kwargs)
    bs = int(config["block_size"])
    mp = int(config["serving_mp"])
    cp = int(config.get("serving_cp", 1))
    nkv = cfg.num_key_value_heads
    # engine __init__'s own sizing: every slot simultaneously
    # full-length, +1 scratch page (kv_pool_bytes sizing would make
    # the pool the budget itself). serving_cp shards the page axis,
    # so the PER-CHIP bound carries fleet_pages/cp local pages — the
    # whole point of the knob is that this term shrinks with cp.
    if geo["kv_pool_bytes"] is not None:
        # kv_pool_bytes is the engine's per-chip budget contract
        # already (pages_for_bytes buys budget*cp fleet pages)
        pool_bytes = int(geo["kv_pool_bytes"])
    else:
        cap = -(-(geo["max_prompt_len"] + geo["max_new_tokens"]) // bs)
        fleet = geo["max_pages"] or -(-(geo["slots"] * cap + 1)
                                      // cp) * cp
        kv_shards = mp if (mp > 1 and nkv % mp == 0) else 1
        pool_bytes = (fleet // cp) * PagedKVManager.page_bytes(
            bs, n_layers=cfg.num_hidden_layers, num_kv_heads=nkv,
            head_dim=cfg.head_dim,
            kv_cache_dtype=config["kv_cache_dtype"], mp=kv_shards)
    # params as passed (host/replicated view): a conservative per-chip
    # bound — serving_mp shards only the q/k/v projection columns
    return pytree_local_bytes(params) + pool_bytes


@dataclasses.dataclass
class CandidateResult:
    """One scored (or pruned) point of the search space."""

    config: dict
    feasible: bool
    static_bound_bytes: int
    pruned_reason: Optional[str] = None
    peak_hbm_bytes: Optional[int] = None
    predicted_step_ms: Optional[float] = None
    predicted_ms_per_token: Optional[float] = None
    predicted_mfu: Optional[float] = None
    predicted_wire_bytes_per_token: Optional[float] = None
    bound: Optional[str] = None
    n_programs: int = 0

    def sort_key(self):
        """Ascending-is-better, fully deterministic: predicted decode
        step time, then wire bytes per token, traced peak, and the
        canonical config string (ties between byte-identical programs
        resolve to the same winner on every run)."""
        return (self.predicted_step_ms or 0.0,
                self.predicted_wire_bytes_per_token or 0.0,
                self.peak_hbm_bytes or 0,
                _config_key(self.config))

    def to_dict(self) -> dict:
        out = {"config": dict(self.config), "feasible": self.feasible,
               "static_bound_bytes": self.static_bound_bytes}
        if self.feasible:
            out.update({
                "peak_hbm_bytes": self.peak_hbm_bytes,
                "predicted_step_ms": self.predicted_step_ms,
                "predicted_ms_per_token": self.predicted_ms_per_token,
                "predicted_mfu": self.predicted_mfu,
                "predicted_wire_bytes_per_token":
                    self.predicted_wire_bytes_per_token,
                "bound": self.bound,
                "n_programs": self.n_programs,
            })
        else:
            out["pruned_reason"] = self.pruned_reason
        return out


@dataclasses.dataclass
class TunedConfig:
    """The persisted winner: engine knobs + the identity they were
    tuned against (device row, model shape, searched space) + the
    auditor predictions for the winning config (satellite: the
    estimate/actual calibration stubs a TPU run scores the RANKING
    against, not just individual predictors)."""

    knobs: dict
    device: str
    model: str
    space_hash: str
    predicted: dict = dataclasses.field(default_factory=dict)
    schema_version: int = SCHEMA_VERSION

    def to_dict(self) -> dict:
        return {
            "schema_version": self.schema_version,
            "device": self.device,
            "model": self.model,
            "space_hash": self.space_hash,
            "knobs": dict(self.knobs),
            "predicted": dict(self.predicted),
        }

    @classmethod
    def from_dict(cls, d: dict) -> "TunedConfig":
        return cls(knobs=dict(d["knobs"]), device=d["device"],
                   model=d["model"], space_hash=d["space_hash"],
                   predicted=dict(d.get("predicted", {})),
                   schema_version=int(d.get("schema_version", -1)))

    def save(self, path: str) -> str:
        """Write the artifact (a directory gets `.paddle_tpu_tune.json`
        inside it — next to a persistent compile-cache dir is the
        intended home). Atomic rename so a crashed writer can never
        leave a half-artifact a later engine build would load."""
        if os.path.isdir(path):
            path = os.path.join(path, TUNE_FILENAME)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_dict(), f, sort_keys=True, indent=2)
        os.replace(tmp, path)
        return path

    @classmethod
    def load(cls, path: str) -> "TunedConfig":
        if os.path.isdir(path):
            path = os.path.join(path, TUNE_FILENAME)
        with open(path) as f:
            return cls.from_dict(json.load(f))

    def stale_reason(self, cfg=None, device=None,
                     space: Optional[dict] = None) -> Optional[str]:
        """None when the artifact is valid for this (model, device,
        space); else WHY it is stale. Every check is opt-in via its
        argument except the schema version — an engine only knows its
        model config, a CLI rerun also knows the device row and the
        space it would search."""
        if self.schema_version != SCHEMA_VERSION:
            return (f"schema_version {self.schema_version} != "
                    f"{SCHEMA_VERSION}")
        unknown = sorted(set(self.knobs) - set(KNOBS))
        if unknown:
            return f"knobs {unknown} are not engine knobs {KNOBS}"
        if cfg is not None and model_signature(cfg) != self.model:
            return (f"model signature {model_signature(cfg)!r} != "
                    f"tuned {self.model!r}")
        if device is not None and get_spec(device).name != self.device:
            return (f"device row {get_spec(device).name!r} != "
                    f"tuned {self.device!r}")
        if space is not None and space_hash(space) != self.space_hash:
            return (f"flag-space hash {space_hash(space)} != "
                    f"tuned {self.space_hash}")
        return None

    def apply(self, engine_kwargs: dict) -> dict:
        """Merge the tuned knobs into an engine kwargs dict — explicit
        caller values WIN (a knob the operator pinned stays pinned;
        None counts as unset, matching the engine's flag-resolution
        contract)."""
        out = dict(engine_kwargs)
        for k, v in self.knobs.items():
            if out.get(k) is None:
                out[k] = v
        return out


@dataclasses.dataclass
class TuningReport:
    """Ranked outcome of one `autotune` run (stable to_dict/to_json —
    the CLI's --format json schema CI diffs)."""

    device: str
    model: str
    space: dict
    hbm_budget_bytes: int
    ranking: List[CandidateResult]
    pruned: List[CandidateResult]
    baseline: CandidateResult
    n_candidates: int
    engine_geometry: dict

    @property
    def best(self) -> CandidateResult:
        if not self.ranking:
            raise RuntimeError(
                "no feasible candidate: every config in the space "
                f"exceeded the {self.hbm_budget_bytes} B budget")
        return self.ranking[0]

    @property
    def n_pruned(self) -> int:
        return len(self.pruned)

    @property
    def space_hash(self) -> str:
        return space_hash(self.space)

    def tuned_config(self) -> TunedConfig:
        best = self.best
        return TunedConfig(
            knobs=dict(best.config), device=self.device,
            model=self.model, space_hash=self.space_hash,
            predicted={
                "step_ms": best.predicted_step_ms,
                "ms_per_token": best.predicted_ms_per_token,
                "mfu": best.predicted_mfu,
                "wire_bytes_per_token":
                    best.predicted_wire_bytes_per_token,
                "peak_hbm_bytes": best.peak_hbm_bytes,
            })

    def to_dict(self, top_k: int = 8) -> dict:
        base = self.baseline
        best = self.ranking[0] if self.ranking else None
        speedup = None
        if best is not None and base.feasible \
                and best.predicted_step_ms:
            speedup = round(base.predicted_step_ms
                            / best.predicted_step_ms, 4)
        return {
            "device": self.device,
            "model": self.model,
            "space": {k: list(v) for k, v in sorted(self.space.items())},
            "space_hash": self.space_hash,
            "hbm_budget_bytes": self.hbm_budget_bytes,
            "engine_geometry": dict(self.engine_geometry),
            "n_candidates": self.n_candidates,
            "n_feasible": len(self.ranking),
            "n_pruned": self.n_pruned,
            "ranking": [c.to_dict() for c in self.ranking[:top_k]],
            "pruned": [c.to_dict() for c in self.pruned],
            "baseline": base.to_dict(),
            "best": best.to_dict() if best is not None else None,
            "predicted_speedup_vs_default": speedup,
        }

    def to_json(self, top_k: int = 8) -> str:
        return json.dumps(self.to_dict(top_k), sort_keys=True, indent=2)

    def format(self, top_k: int = 8) -> str:
        lines = [
            f"autotune: {self.model} on {self.device} "
            f"(budget {self.hbm_budget_bytes / (1 << 20):.1f} MiB)",
            f"  candidates: {self.n_candidates}  feasible: "
            f"{len(self.ranking)}  pruned over-HBM: {self.n_pruned}",
        ]
        for i, c in enumerate(self.ranking[:top_k]):
            mark = " <- best" if i == 0 else ""
            lines.append(
                f"  #{i + 1} {c.predicted_step_ms:.4f} ms/step  "
                f"mfu={c.predicted_mfu:.4f}  "
                f"wire/tok={c.predicted_wire_bytes_per_token:.0f}B  "
                f"peak={c.peak_hbm_bytes / (1 << 20):.2f} MiB  "
                f"{_config_key(c.config)}{mark}")
        for c in self.pruned:
            lines.append(f"  pruned {_config_key(c.config)}: "
                         f"{c.pruned_reason}")
        if self.ranking:
            base = self.baseline
            if base.feasible and base.predicted_step_ms:
                lines.append(
                    f"  baseline (all defaults): "
                    f"{base.predicted_step_ms:.4f} ms/step -> best is "
                    f"{base.predicted_step_ms / self.best.predicted_step_ms:.2f}x")
        return "\n".join(lines)


def _score_candidate(cfg, params, config, engine_kwargs, spec, budget,
                     static_bound) -> CandidateResult:
    """Build the candidate engine, trace its steady-state programs
    ONCE (decode + the unified step when enabled; tracing only —
    nothing compiles or runs), gate the traced per-chip liveness peak
    against the budget, then price the decode chunk with the memoized
    roofline + comms passes."""
    from . import comms as _comms
    from . import memory as _mem
    from . import roofline as _roof
    from ..serving import ContinuousBatchingEngine

    geo = _engine_geometry(engine_kwargs)
    kw = dict(engine_kwargs or {})
    kw.update(config)
    with warnings.catch_warnings():
        # candidate builds legitimately warn (MQA mp fallback) — the
        # tuner scores the program that would actually run, so the
        # warnings are noise here; the build the operator ships still
        # warns
        warnings.simplefilter("ignore")
        eng = ContinuousBatchingEngine(cfg, dict(params), **kw)
    progs = ["decode"] + (["unified"] if eng._unified is not None
                          else [])
    graphs = dict(eng._traced_inventory(programs=progs))
    peak = max(_mem.audit_graph(g).peak_bytes for g in graphs.values())
    if peak > budget:
        return CandidateResult(
            config=config, feasible=False,
            static_bound_bytes=static_bound, peak_hbm_bytes=peak,
            pruned_reason=(
                f"traced per-chip peak {peak} B exceeds the "
                f"{budget} B budget"))
    roof = _roof.audit_graph(graphs["decode"], spec)
    wire = _comms.audit_graph(graphs["decode"]).total_wire_bytes
    tokens = max(geo["steps_per_sync"] * geo["slots"], 1)
    return CandidateResult(
        config=config, feasible=True,
        static_bound_bytes=static_bound, peak_hbm_bytes=peak,
        predicted_step_ms=roof.predicted_step_ms,
        predicted_ms_per_token=roof.predicted_step_ms / tokens,
        predicted_mfu=roof.predicted_mfu,
        predicted_wire_bytes_per_token=wire / tokens,
        bound=roof.bound, n_programs=len(graphs))


def autotune(cfg, params, *, engine_kwargs: Optional[dict] = None,
             device=None, hbm_budget_bytes: Optional[int] = None,
             headroom: float = DEFAULT_HBM_HEADROOM,
             space: Optional[dict] = None,
             budget_candidates: Optional[int] = None) -> TuningReport:
    """Enumerate, gate, score, rank. Deterministic end to end: the
    space enumerates in sorted knob order, every score comes from the
    memoized static passes, and ties break on the canonical config
    string — same inputs, identical ranking, no RNG.

    `engine_kwargs` is the FIXED engine geometry (slots, buckets,
    steps_per_sync, ...); knob values inside it pin that knob's
    baseline but the space still sweeps it unless `space` says
    otherwise. `hbm_budget_bytes` overrides the feasibility budget
    (default: `auto_hbm_budget(device, headroom=headroom)` — the
    TPU702 derivation). `budget_candidates` caps how many candidates
    are evaluated (enumeration-order prefix; the all-defaults
    baseline is always kept so the winner comparison stands)."""
    spec = get_spec(device)
    geo = _engine_geometry(engine_kwargs)
    if space is None:
        space = default_space(cfg, engine_kwargs)
    base_cfg = baseline_config(cfg, engine_kwargs)
    # the baseline must be scoreable even when the caller's space (or
    # flags) exclude one of its values; appended, so the caller's
    # deterministic value order is preserved
    space = {k: (list(v) if base_cfg[k] in v
                 else list(v) + [base_cfg[k]])
             for k, v in space.items()}
    budget = int(hbm_budget_bytes) if hbm_budget_bytes is not None \
        else auto_hbm_budget(spec, headroom=headroom)
    candidates = enumerate_candidates(space, geo)
    if budget_candidates is not None and budget_candidates > 0:
        kept = candidates[:int(budget_candidates)]
        if base_cfg not in kept:
            kept.append(base_cfg)
        candidates = kept
    import jax

    n_dev = len(jax.devices())
    ranking, pruned = [], []
    baseline_result = None
    for config in candidates:
        bound = static_candidate_bound(cfg, params, config,
                                       engine_kwargs)
        chips = int(config.get("serving_cp", 1)) \
            * int(config["serving_mp"])
        if chips > n_dev:
            # the knob lists are each host-buildable alone, but the
            # 2-D serving mesh needs cp*mp chips — an unbuildable
            # product is a hardware miss, not an HBM miss, so it gets
            # its own named prune instead of an engine-build crash
            res = CandidateResult(
                config=config, feasible=False,
                static_bound_bytes=bound,
                pruned_reason=(
                    f"serving mesh needs serving_cp*serving_mp = "
                    f"{chips} chips; host has {n_dev}"))
        elif bound > budget:
            res = CandidateResult(
                config=config, feasible=False,
                static_bound_bytes=bound,
                pruned_reason=(
                    f"static params+pool bound {bound} B exceeds the "
                    f"{budget} B budget (pruned before tracing)"))
        else:
            res = _score_candidate(cfg, params, config, engine_kwargs,
                                   spec, budget, bound)
        (ranking if res.feasible else pruned).append(res)
        if config == base_cfg:
            baseline_result = res
    ranking.sort(key=CandidateResult.sort_key)
    assert baseline_result is not None  # always enumerated above
    return TuningReport(
        device=spec.name, model=model_signature(cfg), space=space,
        hbm_budget_bytes=budget, ranking=ranking, pruned=pruned,
        baseline=baseline_result, n_candidates=len(candidates),
        engine_geometry=geo)
