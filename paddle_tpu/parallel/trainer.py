"""Functional hybrid-parallel train step.

Reference analog: the fleet dygraph train loop
(fleet/meta_parallel/pipeline_parallel.py train_batch + HybridParallelOptimizer
step) and the semi-auto static Engine (auto_parallel/static/engine.py). On
TPU both collapse into ONE jitted pure function over the mesh:

    (params, opt_state, batch) -> (loss, params', opt_state')

Params carry NamedShardings (TP over `mp`, ZeRO over `sharding`); the batch
is constrained over (dp, sharding); XLA SPMD emits all collectives
(grad psum ≙ EagerReducer allreduce; Shard(0) states ≙ sharding stage 1/2;
Shard params ≙ stage 3 gather/release with async prefetch). Buffer donation
makes the update in-place in HBM.

The optimizer update is a pure fused AdamW over the whole pytree — the role
of the reference's multi_tensor / fused adam kernels
(paddle/phi/kernels/fusion/gpu/fused_adam_kernel.cu).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor, unwrap
from ..core import tape as _tape
from ..nn.layer.layers import Layer
from . import mesh as mesh_mod


def batch_sharding(mesh: Mesh, shape, batch_spec=None) -> NamedSharding:
    """NamedSharding for a data batch: dim i takes batch_spec[i]'s axes,
    keeping only axis groups whose PRODUCT divides the dim size."""
    dims = batch_spec or (("dp", "sharding"), "sep")
    spec = []
    for i in range(len(shape)):
        d = dims[i] if i < len(dims) else None
        names = (d,) if isinstance(d, str) else (d or ())
        kept = mesh_mod.divisible_prefix(mesh, shape[i], names)
        spec.append(kept if kept else None)
    return NamedSharding(mesh, P(*spec))


class AdamWState(NamedTuple):
    m: Any
    v: Any
    step: Any


def init_adamw_state(params: Dict[str, jax.Array]) -> AdamWState:
    """Moments inherit each param's NamedSharding via zeros_like — this IS
    sharding stage 1/2 when params are FSDP-sharded (states follow params)."""
    zeros = jax.tree.map(jnp.zeros_like, params)
    return AdamWState(m=zeros, v=jax.tree.map(jnp.zeros_like, params),
                      step=jnp.zeros((), jnp.int32))


def adamw_update(params, grads, state: AdamWState, lr, *, beta1=0.9,
                 beta2=0.999, eps=1e-8, weight_decay=0.01,
                 grad_clip_norm: Optional[float] = 1.0):
    """Pure AdamW with global-norm clipping (ClipGradByGlobalNorm analog).

    Weight decay applies to params with ndim > 1 only: 1-D leaves are norm
    scales / biases, which standard AdamW configs exclude (reference:
    apply_decay_param_fun in python/paddle/optimizer/adamw.py — pass a real
    AdamW(apply_decay_param_fun=...) through make_train_step(optimizer=)
    for name-based control). Decaying RMSNorm scales was the round-2
    default-path footgun; off by default now."""
    step = state.step + 1
    if grad_clip_norm is not None:
        gnorm = jnp.sqrt(sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, grad_clip_norm / (gnorm + 1e-6))
        grads = jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)
    c1 = 1.0 - beta1 ** step.astype(jnp.float32)
    c2 = 1.0 - beta2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g32 = g.astype(jnp.float32)
        m_ = beta1 * m + (1 - beta1) * g32
        v_ = beta2 * v + (1 - beta2) * jnp.square(g32)
        mhat = m_ / c1
        vhat = v_ / c2
        p32 = p.astype(jnp.float32)
        wd = weight_decay if p.ndim > 1 else 0.0
        p_ = p32 - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p32)
        return p_.astype(p.dtype), m_.astype(m.dtype), v_.astype(v.dtype)

    out = jax.tree.map(upd, params, grads, state.m, state.v)
    new_p = jax.tree.map(lambda t: t[0], out,
                         is_leaf=lambda t: isinstance(t, tuple))
    new_m = jax.tree.map(lambda t: t[1], out,
                         is_leaf=lambda t: isinstance(t, tuple))
    new_v = jax.tree.map(lambda t: t[2], out,
                         is_leaf=lambda t: isinstance(t, tuple))
    return new_p, AdamWState(m=new_m, v=new_v, step=step)


def _resolve_strategy(strategy) -> Dict[str, dict]:
    """Normalize a Strategy object / pass-produced config dict / None into
    plain section dicts (reference: auto_parallel/strategy.py sections)."""
    sections = ("amp", "recompute", "sharding", "gradient_merge", "pipeline")
    out = {s: {} for s in sections}
    if strategy is None:
        return out
    for s in sections:
        val = strategy.get(s) if isinstance(strategy, dict) \
            else getattr(strategy, s, None)
        if isinstance(val, dict):
            out[s] = dict(val)
    return out


_REMAT_POLICIES = {
    None: None,
    "full": None,
    "nothing_saveable": None,
    "save_attn": "dots_saveable",
    "dots_saveable": "dots_saveable",
    "dots_with_no_batch_dims_saveable": "dots_with_no_batch_dims_saveable",
}


def _remat_policy(name):
    mapped = _REMAT_POLICIES.get(name, name)
    if mapped is None:
        return None
    return getattr(jax.checkpoint_policies, mapped)


def _shard_dim0(arr, mesh, axis):
    """Extend `arr`'s sharding spec with Shard(0) over `axis` when dim 0 is
    free and divisible; otherwise return it unchanged. The single predicate
    behind both ZeRO stage-3 params and stage-1/2 accumulator layouts."""
    if getattr(arr, "ndim", 0) == 0:
        return arr
    spec = [None] * arr.ndim
    s = getattr(arr, "sharding", None)
    if isinstance(s, NamedSharding):
        got = list(s.spec) + [None] * (arr.ndim - len(s.spec))
        spec = got[:arr.ndim]
    n = int(mesh.shape[axis])
    if spec[0] is None and arr.shape[0] % n == 0 and arr.shape[0] >= n:
        spec[0] = axis
        return jax.device_put(arr, NamedSharding(mesh, P(*spec)))
    return arr


def _zero_stage3_params(params, mesh, axis):
    """ZeRO stage 3: Shard(0) every param whose dim 0 is divisible and not
    already claimed by another mesh axis (composes with TP layouts)."""
    return {k: _shard_dim0(v, mesh, axis) for k, v in params.items()}


def _zero_shard_states(opt_state, params, mesh, axis):
    """ZeRO stage 1/2: lay optimizer accumulators out Shard(0) over the
    sharding axis (on top of whatever spec they inherited from the param)."""

    def shard_one(name, st):
        p = params[name]

        def f(arr):
            if getattr(arr, "shape", None) != p.shape:
                return arr
            return _shard_dim0(arr, mesh, axis)

        return jax.tree.map(f, st)

    if isinstance(opt_state, AdamWState):
        return AdamWState(
            m={k: shard_one(k, v) for k, v in opt_state.m.items()},
            v={k: shard_one(k, v) for k, v in opt_state.v.items()},
            step=opt_state.step)
    acc = {k: shard_one(k, v) for k, v in opt_state["acc"].items()}
    return {"step": opt_state["step"], "acc": acc}


def read_report(report: dict, registry=None) -> Dict[str, float]:
    """The host's reading of what a step reported of itself (the fourth
    result of a `make_train_step` step whose loss_fn returns a report): every
    scalar as a float, per-expert arrays left out. With a metrics registry
    armed (`observability.metrics.get_metrics()`, or the one passed) each is
    set as a gauge of its own name (`loss.main`, `moe.rows_held`, ...). The
    values are results of the step's program: reading them waits for that
    step and for nothing else."""
    from ..observability import metrics as obs_metrics

    # one transfer for all of them
    out = {k: float(v) for k, v in jax.device_get(
        {k: v for k, v in report.items()
         if getattr(v, "ndim", 0) == 0}).items()}
    registry = obs_metrics.get_metrics() if registry is None else registry
    if registry is not None:
        for k, v in out.items():
            registry.gauge(k).set(v)
    return out


def make_train_step(model: Layer, loss_fn: Callable, mesh: Optional[Mesh] = None,
                    lr: float = 1e-4, weight_decay: float = 0.01,
                    grad_clip_norm: Optional[float] = 1.0,
                    batch_spec: Optional[Tuple] = None,
                    donate: bool = True, optimizer=None, strategy=None):
    """Build (step_fn, params, opt_state) for `model`.

    `loss_fn(logits_or_output, *batch_rest) -> scalar Tensor`; batch is
    (input, *rest). The returned step_fn is jitted with buffer donation;
    call it as `loss, params, opt_state = step_fn(params, opt_state, *batch)`.

    A model may report on itself: where `loss_fn` returns `(loss, report)`,
    `report` a dict of device values (the parts of the loss, routing
    counters), the step returns it as a fourth result — `loss, params,
    opt_state, report = step_fn(...)` — for the host to read with the loss,
    at no barrier of its own. And a model may keep state that is no
    parameter (a buffer: no gradient, no optimizer state): where it defines
    `state_updates(state, report) -> {leaf name: new value}`, that rule runs
    inside the step, after the optimizer and outside it.
    `step_fn.loss_and_grads(params, *batch)` is the step's own loss (with the
    report, if any) and gradients, jitted apart, for checks against a
    reference.

    `optimizer`: any paddle_tpu Optimizer with a pure update rule — its
    update math, per-group weight decay, decay-exclusion fns, grad clip and
    LR schedule run inside the jitted step (reference: the static Engine
    building the optimizer into the program, auto_parallel/static/engine.py:69).
    Without it, a fused AdamW(lr, weight_decay) is used.

    `strategy`: Strategy / pass-produced config consumed at trace time
    (reference: distributed/passes/*):
      - amp.enable[, dtype]: cast fp32 params+inputs to bf16 for fwd/bwd,
        keep fp32 master params in the update (O2 semantics).
      - recompute.enable[, remat_policy]: jax.checkpoint over the loss.
      - gradient_merge.enable + k_steps[, avg]: lax.scan microbatch
        accumulation inside the step (passes/auto_parallel_gradient_merge.py).
      - sharding.enable + stage/axis: ZeRO 1/2 (states Shard(0)) or
        3 (+params Shard(0)) over the sharding mesh axis.
    """
    from .fused_optimizer import FusedOptimizer

    mesh = mesh or mesh_mod.get_global_mesh()
    strat = _resolve_strategy(strategy)
    params = dict(model.raw_state())

    shard_cfg = strat["sharding"]
    shard_axis = shard_cfg.get("axis", "sharding")
    sharding_on = bool(shard_cfg.get("enable")) and mesh is not None \
        and shard_axis in getattr(mesh, "axis_names", ())
    if sharding_on and int(shard_cfg.get("stage", 2)) >= 3:
        params = _zero_stage3_params(params, mesh, shard_axis)

    fused = FusedOptimizer(optimizer, model) if optimizer is not None else None
    opt_state = fused.init_state(params) if fused is not None \
        else init_adamw_state(params)
    if sharding_on:
        opt_state = _zero_shard_states(opt_state, params, mesh, shard_axis)

    amp_cfg = strat["amp"]
    # bf16 is the TPU-native half type; a float16 request (fp16 pass) maps
    # onto it (same contract as FP16Pass defaulting to bfloat16)
    amp_dtype = jnp.bfloat16 if amp_cfg.get("enable") else None

    def batch_constraint(x):
        if mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, batch_sharding(mesh, x.shape, batch_spec))

    def compute_loss(p, *batch):
        if fused is not None:
            # frozen params / buffers contribute no cotangents
            p = {k: (v if k in fused.trainable else jax.lax.stop_gradient(v))
                 for k, v in p.items()}
        if amp_dtype is not None:
            p = {k: (v.astype(amp_dtype) if v.dtype == jnp.float32 else v)
                 for k, v in p.items()}
            batch = tuple(
                b.astype(amp_dtype) if b.dtype == jnp.float32 else b
                for b in batch)
        inputs = batch_constraint(batch[0])
        rest = [batch_constraint(b) for b in batch[1:]]
        with _tape.no_grad():
            out = model.func_call(p, Tensor(inputs))
            loss = loss_fn(out, *(Tensor(r) for r in rest))
        report = {}
        if isinstance(loss, tuple):
            loss, report = loss
        return unwrap(loss).astype(jnp.float32), jax.tree.map(unwrap, report)

    if strat["recompute"].get("enable"):
        model_cfg = getattr(model, "config", None)
        if model_cfg is not None and hasattr(model_cfg, "recompute"):
            # per-layer remat via the model's own segmentation — the real
            # peak-memory reducer (reference: passes/auto_parallel_recompute
            # checkpointing segments, fleet/recompute/recompute.py:109).
            # The flip is scoped to this step's trace so the shared model
            # object keeps its own config everywhere else.
            knobs = {"recompute": True}
            for knob in ("recompute_skip", "remat_policy"):
                if strat["recompute"].get(knob) is not None:
                    knobs[knob] = strat["recompute"][knob]
            inner_loss = compute_loss

            def compute_loss(p, *batch, _inner=inner_loss, _knobs=knobs):
                saved = {k: getattr(model_cfg, k) for k in _knobs}
                try:
                    for k, v in _knobs.items():
                        setattr(model_cfg, k, v)
                    return _inner(p, *batch)
                finally:
                    for k, v in saved.items():
                        setattr(model_cfg, k, v)
        else:
            # generic fallback: whole-fn checkpoint (saves only the policy's
            # residuals between fwd and bwd; no per-segment peak reduction)
            compute_loss = jax.checkpoint(
                compute_loss,
                policy=_remat_policy(strat["recompute"].get("remat_policy")))

    gm_cfg = strat["gradient_merge"]
    k_steps = int(gm_cfg.get("k_steps", 1)) if gm_cfg.get("enable") else 1
    gm_avg = bool(gm_cfg.get("avg", True))

    def loss_and_grads(p, *batch):
        """((loss, report), grads); the report of a merged step is its last
        microbatch's."""
        if k_steps <= 1:
            return jax.value_and_grad(compute_loss, has_aux=True)(p, *batch)
        micro = tuple(
            b.reshape((k_steps, b.shape[0] // k_steps) + b.shape[1:])
            for b in batch)

        def acc_add(a, g):
            # integer params get float0 cotangents; nothing to accumulate
            if g.dtype == jax.dtypes.float0:
                return a
            return a + g.astype(jnp.float32)

        def body(carry, mb):
            acc_loss, acc_g = carry
            (loss, report), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(p, *mb)
            return (acc_loss + loss,
                    jax.tree.map(acc_add, acc_g, grads)), report

        zeros = jax.tree.map(
            lambda x: jnp.zeros(x.shape, jnp.float32), p)
        (loss_sum, g_sum), reports = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zeros), micro)
        scale = 1.0 / k_steps if gm_avg else 1.0
        grads = jax.tree.map(
            lambda g, x: (g * scale).astype(
                x.dtype if jnp.issubdtype(x.dtype, jnp.floating)
                else jnp.float32), g_sum, p)
        return (loss_sum / k_steps,
                jax.tree.map(lambda r: r[-1], reports)), grads

    def train_step(p, s, lr_, *batch):
        (loss, report), grads = loss_and_grads(p, *batch)
        if fused is not None:
            new_p, new_s = fused.update(p, grads, s, lr_)
        else:
            new_p, new_s = adamw_update(
                p, grads, s, lr_, weight_decay=weight_decay,
                grad_clip_norm=grad_clip_norm)
        if hasattr(model, "state_updates"):
            new_p = {**new_p, **model.state_updates(p, report)}
        # a model that reports nothing keeps the three results it had
        return (loss, new_p, new_s) + ((report,) if report else ())

    # named by role: the profiler's module row reads `jit_train_step`
    jitted = jax.jit(train_step, donate_argnums=(0, 1) if donate else ())

    def step_fn(p, s, *batch):
        cur_lr = fused.host_lr() if fused is not None else lr
        loss, new_p, new_s, *report = jitted(
            p, s, jnp.asarray(cur_lr, jnp.float32), *batch)
        # keep the Layer view fresh: donation invalidated the old arrays
        # (pointer swap only, no transfer)
        model.load_raw_state(new_p)
        if fused is not None:
            fused.latest_state = new_s  # lazily exported by state_dict()
            fused.host_tick()
        return (loss, new_p, new_s, *report)

    step_fn.jitted = jitted  # for lowering/compile introspection
    step_fn.loss_and_grads = jax.jit(loss_and_grads)
    if fused is not None:
        step_fn.fused_optimizer = fused
    return step_fn, params, opt_state


def make_eval_step(model: Layer, mesh: Optional[Mesh] = None,
                   batch_spec: Optional[Tuple] = None):
    mesh = mesh or mesh_mod.get_global_mesh()

    def eval_step(p, inputs):
        if mesh is not None:
            inputs = jax.lax.with_sharding_constraint(
                inputs, batch_sharding(mesh, inputs.shape, batch_spec))
        with _tape.no_grad():
            return unwrap(model.func_call(p, Tensor(inputs), training=False))

    return jax.jit(eval_step)
