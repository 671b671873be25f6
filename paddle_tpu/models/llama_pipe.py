"""Pipeline-parallel Llama training step.

Reference: fleet PipelineLayer + PipelineParallel.train_batch
(fleet/meta_parallel/parallel_layers/pp_layers.py:257 SegmentLayers —
partitioning decoder layers into stages — and pipeline_parallel.py 1F1B).

TPU-native: decoder layers are grouped into `pp` stages; per-stage parameter
pytrees are stacked with the stage dim sharded over the `pp` mesh axis and
the microbatch loop runs as scan+ppermute inside ONE jitted program
(parallel/pipeline_spmd.py). Embedding, final norm and the LM head run
outside the pipeline region (replicated over pp, still TP/FSDP-sharded over
the other axes) — the reference shares the embedding across first/last
stages similarly.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..core.tensor import Tensor, unwrap
from ..core import tape as _tape
from ..kernels.rope import rope_freqs
from ..parallel import mesh as mesh_mod
from ..parallel.pipeline_spmd import (pipeline_1f1b, pipeline_eager_1f1b,
                                      pipeline_forward, pipeline_vpp_forward,
                                      pipeline_zb1f1b, stack_stage_params)
from ..parallel.trainer import adamw_update, batch_sharding, \
    init_adamw_state
from .llama import LlamaConfig, LlamaForCausalLM, LlamaPretrainingCriterion

__all__ = ["make_llama_pp_train_step", "split_llama_state",
           "chunk_llama_state", "merge_llama_chunked_state"]

_LAYER_PREFIX = "llama.layers."


def _parse_layer_state(state):
    """Split a flat raw_state into (outer, per_layer list of sub-dicts)."""
    per_layer = []
    outer = {}
    for k, v in state.items():
        if k.startswith(_LAYER_PREFIX):
            rest = k[len(_LAYER_PREFIX):]
            idx, sub = rest.split(".", 1)
            idx = int(idx)
            while len(per_layer) <= idx:
                per_layer.append({})
            per_layer[idx][sub] = v
        else:
            outer[k] = v
    return outer, per_layer


def split_llama_state(state: Dict[str, jax.Array], n_layers: int,
                      n_stages: int, mesh: Optional[Mesh] = None):
    """Split a flat raw_state into (outer_params, stacked_stage_params).

    Layer params are grouped into n_stages contiguous blocks (reference:
    SegmentLayers uniform partition), stacked [n_stages, layers_per_stage,
    ...] with the stage dim sharded over `pp`."""
    if n_layers % n_stages:
        raise ValueError(f"{n_layers} layers not divisible into "
                         f"{n_stages} stages")
    outer, per_layer = _parse_layer_state(state)
    lps = n_layers // n_stages
    per_stage = []
    for s in range(n_stages):
        block = per_layer[s * lps:(s + 1) * lps]
        per_stage.append(jax.tree.map(lambda *xs: jnp.stack(xs), *block))
    stacked = stack_stage_params(per_stage, mesh, axis="pp")
    return outer, stacked


def chunk_llama_state(state: Dict[str, jax.Array], n_layers: int,
                      n_stages: int, vpp_degree: int,
                      mesh: Optional[Mesh] = None):
    """Split a flat raw_state into (outer, chunked_stage_params) for the
    interleaved (VPP) schedule: n_stages*vpp_degree chunks of contiguous
    layers, laid out [S, V, layers_per_chunk, ...] with [r, v] = chunk
    v*S + r (Megatron interleaved assignment; reference:
    PipelineParallelWithInterleave's _build_layer_impl chunking)."""
    n_chunks = n_stages * vpp_degree
    if n_layers % n_chunks:
        raise ValueError(f"{n_layers} layers not divisible into "
                         f"{n_chunks} chunks (pp={n_stages} x V={vpp_degree})")
    outer, per_layer = _parse_layer_state(state)
    lpc = n_layers // n_chunks
    chunks = []
    for c in range(n_chunks):
        block = per_layer[c * lpc:(c + 1) * lpc]
        chunks.append(jax.tree.map(lambda *xs: jnp.stack(xs), *block))
    per_rank = []
    for r in range(n_stages):
        rank_chunks = [chunks[v * n_stages + r] for v in range(vpp_degree)]
        per_rank.append(jax.tree.map(lambda *xs: jnp.stack(xs), *rank_chunks))
    return outer, stack_stage_params(per_rank, mesh, axis="pp")


def merge_llama_chunked_state(outer: Dict, chunked, n_layers: int) -> Dict:
    """Inverse of chunk_llama_state."""
    state = dict(outer)
    leaves = jax.tree.leaves(chunked)
    n_stages, vpp = leaves[0].shape[0], leaves[0].shape[1]
    lpc = n_layers // (n_stages * vpp)
    flat = jax.tree.flatten_with_path(chunked)[0]
    for path, arr in flat:
        sub = ".".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        for r in range(n_stages):
            for v in range(vpp):
                c = v * n_stages + r
                for l in range(lpc):
                    state[f"{_LAYER_PREFIX}{c * lpc + l}.{sub}"] = arr[r, v, l]
    return state


def merge_llama_state(outer: Dict, stacked, n_layers: int) -> Dict:
    """Inverse of split_llama_state (for state_dict/checkpoint export)."""
    state = dict(outer)
    n_stages = jax.tree.leaves(stacked)[0].shape[0]
    lps = n_layers // n_stages
    flat = jax.tree.flatten_with_path(stacked)[0]
    for path, arr in flat:
        sub = ".".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        for s in range(n_stages):
            for l in range(lps):
                state[f"{_LAYER_PREFIX}{s * lps + l}.{sub}"] = arr[s, l]
    return state


def make_llama_pp_train_step(model: LlamaForCausalLM,
                             mesh: Optional[Mesh] = None,
                             n_micro: Optional[int] = None,
                             lr: float = 1e-4, weight_decay: float = 0.01,
                             grad_clip_norm: Optional[float] = 1.0,
                             schedule: Optional[str] = None, strategy=None,
                             vpp_degree: Optional[int] = None,
                             coop_head: Optional[bool] = None):
    """Build (step_fn, params, opt_state) where params =
    {"outer": ..., "stages": ...} and step_fn runs embed -> pp pipeline of
    decoder stages -> norm -> head -> CE loss -> AdamW, fully jitted.

    schedule (reference: pipeline_scheduler passes — FThenB/1F1B/VPP/ZBH1,
    distributed/passes/pipeline_scheduler_pass/):
      - "1F1B" (default): one-pass fwd+bwd schedule, loss inside the last
        stage, activations bounded at ~2*n_stages microbatch inputs
        (pipeline_spmd.pipeline_1f1b).
      - "FThenB": forward pipeline + autodiff (GPipe memory profile).
      - "VPP": interleaved virtual stages (`vpp_degree` chunks per rank,
        pipeline_spmd.pipeline_vpp_forward + autodiff) — the tick body
        dynamic-indexes ONE chunk, so interleaving pays control flow, not
        V× compute; pipeline bubble shrinks by 1/vpp_degree. Requires
        n_micro %% pp == 0 and layers %% (pp*vpp_degree) == 0.
      - "ZBH1": zero-bubble-style 1F1B — activation-grad-only ticks, all
        weight grads batched after the scan (pipeline_spmd.pipeline_zb1f1b
        documents the TPU-native cost model).
      - "Eager1F1B": 1F1B with a full tick of slack on every boundary
        exchange so XLA overlaps the collective-permute with compute, at
        the cost of more in-flight activations — the reference
        eager-1F1B's memory-for-overlap trade
        (pipeline_scheduler_pass/pipeline_eager_1f1b.py:31) in
        one-program form (pipeline_spmd.pipeline_eager_1f1b).

    coop_head (default: on for 1F1B/ZBH1 when vocab %% pp == 0): the final
    norm+LM-head+CE run COOPERATIVELY — every rank holds vocab/pp of the
    head weight and computes its shard's piece of the loss each tick
    (ParallelCrossEntropy math over the pp axis, reference:
    fleet/layers/mpu/mp_layers.py:742), so per-tick head FLOPs are 1/pp of
    a full head instead of the pp× a replicated per-rank head costs.

    `strategy`: a pipeline-scheduler pass output / Strategy whose
    `pipeline` section supplies schedule_mode and accumulate_steps
    (reference: distributed/passes/pipeline_scheduler_pass) — explicit
    `schedule`/`n_micro` arguments win over the strategy.
    """
    if strategy is not None:
        from ..parallel.trainer import _resolve_strategy

        pipe_cfg = _resolve_strategy(strategy)["pipeline"]
        if pipe_cfg.get("enable", True):
            if pipe_cfg.get("schedule_mode") and schedule is None:
                schedule = pipe_cfg["schedule_mode"]
            # accumulate_steps <= 1 is the pass's own default, not a
            # request for a degenerate one-microbatch pipeline
            if n_micro is None and int(
                    pipe_cfg.get("accumulate_steps") or 0) > 1:
                n_micro = int(pipe_cfg["accumulate_steps"])
            if pipe_cfg.get("vpp_degree") and vpp_degree is None:
                vpp_degree = int(pipe_cfg["vpp_degree"])
    if schedule is None:
        schedule = "1F1B"
    if schedule not in ("1F1B", "Eager1F1B", "FThenB", "VPP", "ZBH1"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    if vpp_degree is None:
        vpp_degree = 2
    mesh = mesh or mesh_mod.get_global_mesh()
    cfg = model.config
    n_stages = int(mesh.shape["pp"]) if (mesh is not None
                                         and "pp" in mesh.axis_names) else 1
    if coop_head:
        if schedule not in ("1F1B", "Eager1F1B", "ZBH1") or n_stages == 1:
            raise ValueError(
                "coop_head=True requires a 1F1B-family schedule with a "
                f"pp axis > 1 (got schedule={schedule!r}, pp={n_stages}); "
                "FThenB/VPP compute the head once per step outside the "
                "pipeline, so there is nothing to cooperate on")
        if cfg.vocab_size % n_stages != 0:
            raise ValueError(
                f"coop_head needs vocab_size ({cfg.vocab_size}) divisible "
                f"by the pp axis ({n_stages}) to shard the head")
    if schedule == "VPP" and n_stages > 1:
        outer, stacked = chunk_llama_state(
            dict(model.raw_state()), cfg.num_hidden_layers, n_stages,
            vpp_degree, mesh)
        lps = cfg.num_hidden_layers // (n_stages * vpp_degree)
    else:
        outer, stacked = split_llama_state(
            dict(model.raw_state()), cfg.num_hidden_layers, n_stages, mesh)
        lps = cfg.num_hidden_layers // n_stages
    params = {"outer": outer, "stages": stacked}
    opt_state = init_adamw_state(params)
    template = model.llama.layers[0]
    crit = LlamaPretrainingCriterion(cfg)
    if coop_head is None:
        coop_head = (schedule in ("1F1B", "Eager1F1B", "ZBH1")
                     and n_stages > 1
                     and cfg.vocab_size % n_stages == 0)

    def stage_fn(stage_params, h):
        s = h.shape[1]
        cos, sin = rope_freqs(s, cfg.head_dim, base=cfg.rope_theta)
        for i in range(lps):
            lp = jax.tree.map(lambda t, i=i: t[i], stage_params)
            with _tape.no_grad():
                # mesh=None: no explicit activation constraints inside the
                # manual-pp region (they would reference Auto-typed axes);
                # the weights' shardings still steer GSPMD on auto axes
                h = unwrap(template.func_call(lp, Tensor(h), cos, sin,
                                              mesh=None))
        return h

    def head_fn(hp, hidden, y_mb):
        """Final norm + LM head + shifted-CE for one microbatch — the last
        pipeline stage's tail (reference: shared embedding / LMHead stage
        in fleet pp_layers)."""
        from ..kernels.rms_norm import rms_norm as _k_rms

        with _tape.no_grad():
            hidden = _k_rms(hidden, hp["llama.norm.weight"],
                            cfg.rms_norm_eps)
            if cfg.tie_word_embeddings:
                logits = hidden @ hp["llama.embed_tokens.weight"].T
            else:
                logits = hidden @ hp["lm_head.weight"]
            loss = crit(Tensor(logits), Tensor(y_mb))
        return unwrap(loss).astype(jnp.float32)

    vocab_shard = cfg.vocab_size // n_stages if n_stages else cfg.vocab_size
    head_key = ("llama.embed_tokens.weight" if cfg.tie_word_embeddings
                else "lm_head.weight")

    def coop_head_fn(hp, hidden, y_mb):
        """Cooperative vocab-parallel head: this rank holds vocab/pp of
        the head weight; the shifted softmax-CE combines across the pp
        axis with pmax/psum — the ParallelCrossEntropy math
        (fleet/layers/mpu/mp_layers.py:742) laid over the pipeline axis,
        so per-tick head FLOPs are 1/pp of a full head."""
        from ..kernels.rms_norm import rms_norm as _k_rms

        h = _k_rms(hidden, hp["llama.norm.weight"], cfg.rms_norm_eps)
        w = hp[head_key]
        logits = h @ w.T if cfg.tie_word_embeddings else h @ w
        # labels arrive pre-shifted (LlamaPretrainingCriterion contract:
        # plain CE over every position)
        lg = logits.astype(jnp.float32)  # [mb, s, Vs]
        lb = y_mb
        sid = jax.lax.axis_index("pp")
        off = sid * vocab_shard
        # global max via all_gather (pmax has no autodiff rule; the max is
        # stop-gradient anyway — standard logsumexp stabilization)
        m = jax.lax.stop_gradient(jnp.max(
            jax.lax.all_gather(jnp.max(lg, axis=-1), "pp"), axis=0))
        se = jax.lax.psum(
            jnp.sum(jnp.exp(lg - m[..., None]), axis=-1), "pp")
        log_z = m + jnp.log(se)
        local = (lb >= off) & (lb < off + vocab_shard)
        idx = jnp.clip(lb - off, 0, vocab_shard - 1)
        corr = jnp.take_along_axis(lg, idx[..., None], axis=-1)[..., 0]
        corr = jax.lax.psum(jnp.where(local, corr, 0.0), "pp")
        return jnp.mean(log_z - corr).astype(jnp.float32)

    def embed(p, x):
        with _tape.no_grad():
            return unwrap(model.llama.embed_tokens.func_call(
                {"weight": p["outer"]["llama.embed_tokens.weight"]},
                Tensor(x)))

    def compute_loss(p, x, y):
        hidden = embed(p, x)
        if schedule == "VPP" and n_stages > 1:
            hidden = pipeline_vpp_forward(stage_fn, p["stages"], hidden,
                                          mesh=mesh, axis="pp",
                                          n_micro=n_micro)
        else:
            hidden = pipeline_forward(stage_fn, p["stages"], hidden,
                                      mesh=mesh, axis="pp", n_micro=n_micro)
        return head_fn(p["outer"], hidden, y)

    def loss_and_grads(p, x, y):
        if mesh is not None:
            x = jax.lax.with_sharding_constraint(
                x, batch_sharding(mesh, x.shape, (("dp", "sharding"),)))
        if schedule in ("FThenB", "VPP") or n_stages == 1:
            return jax.value_and_grad(compute_loss)(p, x, y)
        emb_w = p["outer"]["llama.embed_tokens.weight"]
        # the manual scatter-add below implements plain-gather embedding
        # semantics; a padding_idx would need its rows masked here
        assert getattr(model.llama.embed_tokens, "_padding_idx", None) \
            is None, "1F1B embed-grad closure assumes padding_idx=None"
        hidden = embed(p, x)
        # hand the pipeline only the params head_fn reads — every other
        # outer leaf would be carried (and psummed) as an f32 zero
        # accumulator through the whole scan
        head_keys = {"llama.norm.weight", head_key}
        head_p = {k: p["outer"][k] for k in head_keys}
        pipe = {"ZBH1": pipeline_zb1f1b,
                "Eager1F1B": pipeline_eager_1f1b}.get(schedule,
                                                      pipeline_1f1b)
        if coop_head:
            from jax.sharding import PartitionSpec as _P

            head_specs = {
                "llama.norm.weight": _P(),
                head_key: (_P("pp", None) if cfg.tie_word_embeddings
                           else _P(None, "pp")),
            }
            loss, d_st, d_head, d_hid = pipe(
                stage_fn, coop_head_fn, p["stages"], head_p, hidden, y,
                mesh=mesh, axis="pp", n_micro=n_micro,
                head_specs=head_specs)
        else:
            loss, d_st, d_head, d_hid = pipe(
                stage_fn, head_fn, p["stages"], head_p, hidden, y,
                mesh=mesh, axis="pp", n_micro=n_micro)
        # close the embedding lookup's gradient manually: d_emb[v] =
        # sum of d_hidden rows where input token == v (+ the tied-head
        # cotangent already present in d_head when tied)
        d_emb = jnp.zeros(emb_w.shape, jnp.float32).at[
            x.reshape(-1)].add(d_hid.reshape(-1, emb_w.shape[1]))
        d_outer = {k: jnp.zeros_like(v) for k, v in p["outer"].items()}
        d_outer.update(d_head)
        d_outer["llama.embed_tokens.weight"] = (
            d_outer["llama.embed_tokens.weight"]
            + d_emb.astype(emb_w.dtype))
        return loss, {"outer": d_outer, "stages": d_st}

    def step(p, s, x, y):
        loss, grads = loss_and_grads(p, x, y)
        new_p, new_s = adamw_update(
            p, grads, s, jnp.asarray(lr, jnp.float32),
            weight_decay=weight_decay, grad_clip_norm=grad_clip_norm)
        return loss, new_p, new_s

    jitted = jax.jit(step, donate_argnums=(0, 1))
    return jitted, params, opt_state
