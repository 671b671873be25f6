"""SPMD pipeline parallelism: microbatch loop over a `pp` mesh axis.

Reference: fleet/meta_parallel/pipeline_parallel.py (1F1B train_batch :697,
forward_backward_pipeline :459) and the static pipeline_scheduler passes
(FThenB/1F1B/VPP/ZB). There, stages are separate processes exchanging
activations via NCCL p2p (pp_utils/p2p_communication.py batch_isend_irecv).

TPU-native: ONE program under `jax.shard_map` over the `pp` axis. The stage
dimension of the stacked layer parameters is sharded over `pp`, so each
device holds its stage's weights. The schedule is a `lax.scan` over
T = n_micro + n_stages - 1 ticks; each tick every stage processes one
microbatch slot and the boundary activation moves to the next stage with
`lax.ppermute` — the classic collective-permute pipeline from the public
scaling playbook. Autodiff through scan+ppermute gives the backward
schedule for free (fwd-then-bwd, GPipe-equivalent bubble profile);
`pipeline_1f1b` below implements the memory-bounded 1F1B schedule
manually (one fwd + one bwd per tick, loss inside the last stage).

Because everything is one XLA program, this composes with dp/mp/sharding
axes of the same mesh: the non-pp axes partition the per-stage math.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import mesh as mesh_mod

__all__ = ["pipeline_forward", "pipeline_1f1b", "pipeline_eager_1f1b",
           "pipeline_vpp_forward", "pipeline_zb1f1b", "stack_stage_params",
           "unstack_stage_params"]


def _to_varying(x, axis):
    """Mark x as varying over the manual axis (scan-carry requirement)."""
    return jax.lax.pcast(x, axis, to="varying")


def stack_stage_params(per_stage_params: list, mesh: Optional[Mesh] = None,
                       axis: str = "pp"):
    """Stack a list of per-stage pytrees along a new leading stage dim and
    shard that dim over `axis` (each pp rank stores only its stage's
    weights — the pp analog of ZeRO partitioning)."""
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)
    mesh = mesh or mesh_mod.get_global_mesh()
    if mesh is not None and axis in mesh.axis_names:
        def put(x):
            spec = [axis] + [None] * (x.ndim - 1)
            return jax.device_put(x, NamedSharding(mesh, P(*spec)))

        stacked = jax.tree.map(put, stacked)
    return stacked


def unstack_stage_params(stacked, n_stages: int):
    return [jax.tree.map(lambda x, i=i: x[i], stacked)
            for i in range(n_stages)]


def pipeline_forward(stage_fn: Callable, stacked_params, x, *,
                     mesh: Optional[Mesh] = None, axis: str = "pp",
                     n_micro: Optional[int] = None):
    """Run x through n_stages pipeline stages with microbatching.

    stage_fn(stage_params, h) -> h  (the per-stage computation; it may use
    other mesh axes internally — their sharding propagates through
    shard_map via the residual spec being Replicated on `axis` only).

    x: [batch, ...] global input activations (already embedded);
    returns [batch, ...] output of the last stage, replicated over `axis`.
    """
    mesh = mesh or mesh_mod.get_global_mesh()
    if mesh is None or axis not in mesh.axis_names \
            or int(mesh.shape[axis]) == 1:
        # degenerate: run stages sequentially in one program
        n_stages = jax.tree.leaves(stacked_params)[0].shape[0]
        h = x
        for i in range(n_stages):
            p_i = jax.tree.map(lambda t, i=i: t[i], stacked_params)
            h = stage_fn(p_i, h)
        return h

    n_stages = int(mesh.shape[axis])
    stacked_n = int(jax.tree.leaves(stacked_params)[0].shape[0])
    if stacked_n != n_stages:
        raise ValueError(
            f"stacked stage dim {stacked_n} != pp axis size {n_stages}; "
            f"group layers into exactly one block per pp rank")
    batch = x.shape[0]
    n_micro = n_micro or n_stages
    if batch % n_micro != 0:
        raise ValueError(f"batch {batch} not divisible by n_micro {n_micro}")
    mb = batch // n_micro

    # manual only over `axis`: the other mesh axes stay "auto" so TP/FSDP
    # shardings of the per-stage weights keep working inside the body
    # (on jax 0.4.x the compat shim must force the replication check OFF
    # in partial-auto mode; newer jax keeps check_vma on)
    @partial(shard_map, mesh=mesh, axis_names={axis},
             in_specs=(P(axis), P()), out_specs=P())
    def run(params_local, xg):
        # params_local: stage dim reduced to 1 on this rank
        p_stage = jax.tree.map(lambda t: t[0], params_local)
        stage_id = jax.lax.axis_index(axis)
        micro = xg.reshape((n_micro, mb) + xg.shape[1:])

        t_total = n_micro + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            boundary, outputs = carry
            # microbatch index this stage works on at tick t
            mb_idx = t - stage_id
            active = (mb_idx >= 0) & (mb_idx < n_micro)
            # stage 0 reads its microbatch; others read the boundary
            # activation received from the previous stage
            x_in = jnp.where(
                stage_id == 0,
                micro[jnp.clip(mb_idx, 0, n_micro - 1)],
                boundary)
            y = stage_fn(p_stage, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage records its finished microbatch
            outputs = jnp.where(
                (stage_id == n_stages - 1) & active,
                outputs.at[jnp.clip(mb_idx, 0, n_micro - 1)].set(y),
                outputs)
            # activation moves stage s -> s+1 for the next tick
            boundary = jax.lax.ppermute(y, axis, perm)
            return (boundary, outputs), None

        boundary0 = _to_varying(
            jnp.zeros((mb,) + xg.shape[1:], xg.dtype), axis)
        outputs0 = _to_varying(
            jnp.zeros((n_micro, mb) + xg.shape[1:], xg.dtype), axis)
        (boundary, outputs), _ = jax.lax.scan(
            tick, (boundary0, outputs0), jnp.arange(t_total))
        out = outputs.reshape((batch,) + xg.shape[1:])
        # every rank returns the same value: broadcast the last stage's
        # outputs (psum over one-hot mask keeps it differentiable)
        mask = (stage_id == n_stages - 1).astype(out.dtype)
        return jax.lax.psum(out * mask, axis)

    return run(stacked_params, x)


def pipeline_vpp_forward(chunk_fn: Callable, chunked_params, x, *,
                         mesh: Optional[Mesh] = None, axis: str = "pp",
                         n_micro: Optional[int] = None):
    """Interleaved (VPP) pipeline forward — one SPMD program.

    Reference: fleet/meta_parallel/pipeline_parallel.py:1009
    PipelineParallelWithInterleave and
    passes/pipeline_scheduler_pass/pipeline_vpp.py. There, each rank holds
    V non-contiguous model chunks and a hand-written schedule interleaves
    them; here the same interleaving is ONE scan whose tick body picks the
    rank's active chunk by a dynamic index derived from (tick, rank) — a
    gather over the rank's V chunk parameter slices, NOT V× compute (the
    round-2 punt claimed otherwise; it was wrong).

    Layout: ``chunked_params`` leaves are [S, V, ...] — element [r, v] is
    model chunk ``v*S + r`` (Megatron interleaved assignment), dim 0
    sharded over `axis`. Microbatch m flows through chunks 0..S*V-1 in
    order; every chunk boundary moves rank r → r+1 (mod S), produced at
    one tick and consumed exactly at the next, so no boundary buffering is
    needed. With the local clock u = t - r:

        g = u // (S*V);  w = u % (S*V);  v = w // S;  m = g*S + (w % S)

    T = n_micro*V + S - 1 ticks of ONE chunk's work — the interleaved
    bubble is (S-1) chunk-ticks vs (S-1) full-stage-ticks for V=1, the
    1/V bubble shrink VPP exists for. Requires n_micro % S == 0 (the same
    constraint the reference's interleaved schedule imposes).
    """
    mesh = mesh or mesh_mod.get_global_mesh()
    leaves = jax.tree.leaves(chunked_params)
    S_dim, V = int(leaves[0].shape[0]), int(leaves[0].shape[1])
    if mesh is None or axis not in mesh.axis_names \
            or int(mesh.shape[axis]) == 1:
        h = x
        for c in range(S_dim * V):
            p_c = jax.tree.map(lambda t, c=c: t[c % S_dim, c // S_dim],
                               chunked_params)
            h = chunk_fn(p_c, h)
        return h

    n_stages = int(mesh.shape[axis])
    if S_dim != n_stages:
        raise ValueError(f"chunk rank-dim {S_dim} != pp axis {n_stages}")
    batch = x.shape[0]
    n_micro = n_micro or n_stages
    if batch % n_micro != 0:
        raise ValueError(f"batch {batch} not divisible by n_micro {n_micro}")
    if n_micro % n_stages != 0:
        raise ValueError(
            f"VPP needs n_micro ({n_micro}) divisible by pp ({n_stages}) — "
            "the reference interleaved schedule has the same constraint")
    mb = batch // n_micro
    SV = n_stages * V

    @partial(shard_map, mesh=mesh, axis_names={axis},
             in_specs=(P(axis), P()), out_specs=P())
    def run(params_local, xg):
        chunks = jax.tree.map(lambda t: t[0], params_local)  # [V, ...]
        r = jax.lax.axis_index(axis)
        micro = xg.reshape((n_micro, mb) + xg.shape[1:])
        t_total = n_micro * V + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            boundary, outputs = carry
            u = t - r
            active = (u >= 0) & (u < n_micro * V)
            uc = jnp.clip(u, 0, n_micro * V - 1)
            g = uc // SV
            w = uc % SV
            v = w // n_stages
            m = g * n_stages + (w % n_stages)
            p_v = jax.tree.map(
                lambda t_: jax.lax.dynamic_index_in_dim(
                    t_, v, axis=0, keepdims=False), chunks)
            first_chunk = (r == 0) & (v == 0)
            x_in = jnp.where(first_chunk, micro[m], boundary)
            y = chunk_fn(p_v, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            last_chunk = (r == n_stages - 1) & (v == V - 1)
            outputs = jnp.where(
                last_chunk & active, outputs.at[m].set(y), outputs)
            boundary = jax.lax.ppermute(y, axis, perm)
            return (boundary, outputs), None

        boundary0 = _to_varying(
            jnp.zeros((mb,) + xg.shape[1:], xg.dtype), axis)
        outputs0 = _to_varying(
            jnp.zeros((n_micro, mb) + xg.shape[1:], xg.dtype), axis)
        (boundary, outputs), _ = jax.lax.scan(
            tick, (boundary0, outputs0), jnp.arange(t_total))
        out = outputs.reshape((batch,) + xg.shape[1:])
        mask = ((r == n_stages - 1)).astype(out.dtype)
        return jax.lax.psum(out * mask, axis)

    return run(chunked_params, x)


def pipeline_zb1f1b(stage_fn: Callable, head_fn: Callable, stacked_params,
                    head_params, x, labels, *, mesh: Optional[Mesh] = None,
                    axis: str = "pp", n_micro: Optional[int] = None,
                    head_specs=None):
    """Zero-bubble-style 1F1B: weight gradients leave the tick loop.

    Reference: distributed/passes/pipeline_scheduler_pass/
    pipeline_zero_bubble.py (ZBH1) — split each backward into B
    (activation grad, on the critical path) and W (weight grad, not), and
    schedule W into bubble slots.

    TPU-native translation: the SPMD pipeline is ONE program whose ticks
    synchronize at every ppermute, so per-rank-asynchronous W slotting (the
    GPU form) cannot shorten a tick — any tick in which SOME rank does W
    costs F+B+W for everyone. What the one-program model CAN do is take W
    out of the scan entirely: ticks run F + B only (dx via a vjp w.r.t.
    the input alone), each microbatch's (input, output-cotangent) pair is
    saved, and ALL weight gradients are computed after the scan as one
    vmapped-and-summed vjp — n_micro microbatches of weight-grad matmuls
    batched into single large MXU-friendly contractions instead of
    n_micro small ones serialized through the scan.

    Cost model vs 1F1B (T = n_micro + 2S - 1 ticks): the scan saves T
    weight-grad units; the post-pass spends n_micro recompute-forward +
    n_micro weight-grad units (batched). Net tick-FLOP saving ≈
    (2S - 1 - n_micro) weight-grad units — a win for n_micro < 2S-1, a
    wash above, with the batched W pass's better MXU utilization on top
    either way. Memory: 2·n_micro microbatch activations (x and dy
    buffers) vs 1F1B's 2S inputs — the classic zero-bubble
    compute-for-memory trade (ZB-H2 territory).
    Same contract and return values as pipeline_1f1b.
    """
    return _pipeline_1f1b_impl(stage_fn, head_fn, stacked_params,
                               head_params, x, labels, mesh=mesh, axis=axis,
                               n_micro=n_micro, defer_weight_grads=True,
                               head_specs=head_specs)


def pipeline_1f1b(stage_fn: Callable, head_fn: Callable, stacked_params,
                  head_params, x, labels, *, mesh: Optional[Mesh] = None,
                  axis: str = "pp", n_micro: Optional[int] = None,
                  head_specs=None):
    """One-pass fwd+bwd pipeline with the (eager-)1F1B memory profile.

    Reference: fleet/meta_parallel/pipeline_parallel.py:459
    forward_backward_pipeline (1F1B) and the pipeline_scheduler passes.
    There the schedule is a list of p2p send/recv + fwd/bwd calls per rank;
    here it is ONE scan under shard_map where every tick runs one stage
    forward AND one stage backward:

        fwd of microbatch i at stage s happens at tick  s + i
        bwd of microbatch i at stage s happens at tick  2S - 1 - s + i

    so the backward of microbatch 0 starts at tick S (while forwards of
    later microbatches are still streaming in) and a stage holds at most
    2S-1 in-flight microbatch INPUTS — the backward recomputes the stage
    from its saved input (recompute is how the reference runs 1F1B at scale
    too), so peak activation memory is O(n_stages * microbatch) instead of
    the O(n_micro * stage_residuals) that autodiff-of-scan (GPipe) keeps.

    stage_fn(stage_params, h) -> h
    head_fn(head_params, h, labels_mb) -> scalar mean loss of the microbatch
       (the last stage's norm/head/criterion — running the loss inside the
       pipeline is what makes an early backward possible)

    Returns (loss, d_stacked, d_head_params, d_x): mean loss over
    microbatches and gradients w.r.t. the stacked stage params, the head
    params, and the pipeline input activations.

    The head runs COOPERATIVELY when `head_specs` is passed (a pytree of
    PartitionSpecs for head_params, sharding e.g. the vocab dim over
    `axis`; see make_llama_pp_train_step): every tick, the last rank's
    recomputed stage output is broadcast and all ranks evaluate the head
    on their own vocab shard, psum-combining the CE pieces — per-tick head
    FLOPs are 1/n_stages of a full head instead of the n_stages× a
    replicated per-rank head pays. head_fn must then combine its partial
    results with collectives over `axis` itself (coop_head_fn in
    models/llama_pipe.py is the model of this contract).
    """
    return _pipeline_1f1b_impl(stage_fn, head_fn, stacked_params,
                               head_params, x, labels, mesh=mesh, axis=axis,
                               n_micro=n_micro, defer_weight_grads=False,
                               head_specs=head_specs)


def pipeline_eager_1f1b(stage_fn: Callable, head_fn: Callable,
                        stacked_params, head_params, x, labels, *,
                        mesh: Optional[Mesh] = None, axis: str = "pp",
                        n_micro: Optional[int] = None, head_specs=None):
    """Eager-1F1B: trade activation memory for guaranteed comm overlap.

    Reference: distributed/passes/pipeline_scheduler_pass/
    pipeline_eager_1f1b.py:31 — relative to 1F1B, stage s issues
    2*(S-s)-1 warmup forwards instead of S-s, holding more microbatches
    in flight so activation sends overlap with compute
    (enable_send_recv_overlap) instead of stalling the steady state.

    TPU-native translation: the one-program lockstep scan already has the
    eager in-flight *profile* (a stage cannot stall on a recv — every
    ppermute is a program-ordered collective), so "eager" here takes the
    same trade one step further in the direction the reference's schedule
    exists for: every boundary exchange gets a FULL TICK of slack.
    Forward of microbatch i runs at stage s at tick 2s+i (vs s+i) and its
    backward at tick 4S-4-2s+i (vs 2S-1-s+i); an activation produced at
    tick t is consumed at t+2, so XLA's latency-hiding scheduler can run
    the collective-permute entirely under tick t+1's compute — on a real
    ICI mesh no tick ever waits on the wire. Cost, exactly the
    reference's: more in-flight activations (a stage buffers up to
    4(S-s)-3 microbatch inputs vs 2(S-s)-1 — asserted relative to 1F1B
    in tests/test_pipeline.py) and 2S-3 extra (masked) schedule ticks.
    Same contract and return values as pipeline_1f1b.
    """
    return _pipeline_1f1b_impl(stage_fn, head_fn, stacked_params,
                               head_params, x, labels, mesh=mesh, axis=axis,
                               n_micro=n_micro, defer_weight_grads=False,
                               head_specs=head_specs, eager=True)


def _pipeline_1f1b_impl(stage_fn, head_fn, stacked_params, head_params, x,
                        labels, *, mesh, axis, n_micro, defer_weight_grads,
                        head_specs=None, eager=False):
    if eager and defer_weight_grads:
        raise ValueError("eager comm-slack scheduling composes with plain "
                         "1F1B only (ZBH1 already restructures the ticks)")
    mesh = mesh or mesh_mod.get_global_mesh()
    n_stages = int(mesh.shape[axis]) if (
        mesh is not None and axis in mesh.axis_names) else 1
    if n_stages == 1:
        n_all = jax.tree.leaves(stacked_params)[0].shape[0]

        def full_loss(stacked, hp, xx):
            h = xx
            for i in range(n_all):
                p_i = jax.tree.map(lambda t, i=i: t[i], stacked)
                h = stage_fn(p_i, h)
            return head_fn(hp, h, labels)

        loss, (d_st, d_hp, d_x) = jax.value_and_grad(
            full_loss, argnums=(0, 1, 2))(stacked_params, head_params, x)
        return loss, d_st, d_hp, d_x

    stacked_n = int(jax.tree.leaves(stacked_params)[0].shape[0])
    if stacked_n != n_stages:
        raise ValueError(
            f"stacked stage dim {stacked_n} != pp axis size {n_stages}")
    batch = x.shape[0]
    n_micro = n_micro or n_stages
    if batch % n_micro != 0:
        raise ValueError(f"batch {batch} not divisible by n_micro {n_micro}")
    mb = batch // n_micro
    # ZBH1 keeps every microbatch input for the post-scan W pass; plain
    # 1F1B only needs the 2S-1 in-flight inputs (slots reused modulo);
    # eager's slack scheduling stretches a slot's lifetime to 4(S-s)-3
    if defer_weight_grads:
        buf_n = n_micro
    elif eager:
        buf_n = min(n_micro, 4 * n_stages - 3)
    else:
        buf_n = 2 * n_stages
    inv_m = 1.0 / n_micro
    coop = head_specs is not None
    hp_specs = head_specs if coop else jax.tree.map(
        lambda _: P(), head_params)

    @partial(shard_map, mesh=mesh, axis_names={axis},
             in_specs=(P(axis), hp_specs, P(), P()),
             out_specs=(P(), P(axis), hp_specs, P()))
    def run(params_local, head_p, xg, lbg):
        p_stage = jax.tree.map(lambda t: t[0], params_local)
        # make REPLICATED head params VARYING before differentiating: the
        # cotangent of an unvaried input gets an automatic psum over the
        # manual axis, which would leak every rank's (masked-garbage)
        # head gradients into the last stage's accumulation. Leaves whose
        # spec mentions the axis arrive sharded (already varying).
        head_p = jax.tree.map(
            lambda a, s: a if axis in jax.tree.leaves(tuple(s))
            else _to_varying(a, axis), head_p, hp_specs)
        sid = jax.lax.axis_index(axis)
        is_first = sid == 0
        is_last = sid == n_stages - 1
        micro_x = xg.reshape((n_micro, mb) + xg.shape[1:])
        micro_lb = lbg.reshape((n_micro, mb) + lbg.shape[1:])
        # per-stage tick offsets of the schedule (eager doubles the
        # stride so every boundary has one tick of comm slack)
        f_off = 2 * sid if eager else sid
        b_off = (4 * n_stages - 4 - 2 * sid) if eager \
            else (2 * n_stages - 1 - sid)
        h_off = (2 * n_stages - 2) if eager else n_stages
        t_total = n_micro + (4 * n_stages - 4 if eager
                             else 2 * n_stages - 1)
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        bwd_perm = [(i, (i - 1) % n_stages) for i in range(n_stages)]

        def masked_add(acc, g, active):
            return jax.tree.map(
                lambda a, gg: a + jnp.where(active, gg, 0).astype(a.dtype),
                acc, g)

        def run_head(head_p, y2, t):
            """One head evaluation + vjp. Cooperative mode: the head's
            microbatch is the LAST rank's backward microbatch, its hidden
            is broadcast from the last rank, and every rank computes its
            own vocab shard's piece (head_fn psum-combines internally)."""
            if coop:
                i_h = t - h_off  # the last rank's i_b
                act_h = (i_h >= 0) & (i_h < n_micro)
                ih_c = jnp.clip(i_h, 0, n_micro - 1)
                h_in = jax.lax.psum(
                    jnp.where(is_last, y2, jnp.zeros_like(y2)), axis)
                lb_mb = micro_lb[ih_c]
            else:
                i_b = t - b_off
                act_h = (i_b >= 0) & (i_b < n_micro)
                ih_c = jnp.clip(i_b, 0, n_micro - 1)
                h_in = y2
                lb_mb = micro_lb[ih_c]
            loss_i, vjp_head = jax.vjp(
                lambda hp, yy: head_fn(hp, yy, lb_mb), head_p, h_in)
            dhp_i, dy_head = vjp_head(
                _to_varying(jnp.asarray(inv_m, loss_i.dtype), axis))
            if coop:
                # each rank's dy is its shard's partial: sum them
                dy_head = jax.lax.psum(dy_head, axis)
            return loss_i, dhp_i, dy_head, act_h

        def tick(carry, t):
            if eager:
                (fwd_bnd, fwd_rdy, bwd_bnd, bwd_rdy, in_buf, dy_buf, dp,
                 dhp, dx_buf, loss) = carry
            else:
                fwd_bnd, bwd_bnd, in_buf, dy_buf, dp, dhp, dx_buf, \
                    loss = carry
                fwd_rdy, bwd_rdy = fwd_bnd, bwd_bnd

            # ---- forward slot: stage `sid` forwards microbatch i_f ----
            i_f = t - f_off
            act_f = (i_f >= 0) & (i_f < n_micro)
            if_c = jnp.clip(i_f, 0, n_micro - 1)
            x_in = jnp.where(is_first, micro_x[if_c], fwd_rdy)
            y = stage_fn(p_stage, x_in)
            y = jnp.where(act_f, y, jnp.zeros_like(y))
            slot_f = if_c % buf_n
            in_buf = in_buf.at[slot_f].set(
                jnp.where(act_f, x_in, in_buf[slot_f]))

            # ---- backward slot: stage `sid` backwards microbatch i_b ----
            i_b = t - b_off
            act_b = (i_b >= 0) & (i_b < n_micro)
            ib_c = jnp.clip(i_b, 0, n_micro - 1)
            x_sv = in_buf[ib_c % buf_n]
            if defer_weight_grads:
                # ZBH1: activation-grad only — the weight part of this
                # vjp happens once, batched, after the scan
                y2, vjp_x = jax.vjp(
                    lambda xx: stage_fn(p_stage, xx), x_sv)
            else:
                y2, vjp_stage = jax.vjp(stage_fn, p_stage, x_sv)
            loss_i, dhp_i, dy_head, act_h = run_head(head_p, y2, t)
            dy_in = jnp.where(is_last, dy_head.astype(bwd_rdy.dtype),
                              bwd_rdy)
            if defer_weight_grads:
                (dx,) = vjp_x(dy_in)
                dy_buf = dy_buf.at[ib_c].set(
                    jnp.where(act_b, dy_in.astype(dy_buf.dtype),
                              dy_buf[ib_c]))
            else:
                dp_i, dx = vjp_stage(dy_in)
                dp = masked_add(dp, dp_i, act_b)
            dhp = masked_add(dhp, dhp_i,
                             act_h if coop else (act_b & is_last))
            loss = loss + jnp.where(
                (act_h if coop else act_b) & is_last,
                loss_i.astype(loss.dtype) * inv_m, 0.0)
            dx_buf = dx_buf.at[ib_c].set(
                jnp.where(act_b & is_first, dx.astype(dx_buf.dtype),
                          dx_buf[ib_c]))

            # ---- boundary exchange for the next tick ----
            fwd_new = jax.lax.ppermute(y, axis, fwd_perm)
            bwd_new = jax.lax.ppermute(
                jnp.where(act_b, dx, jnp.zeros_like(dx)), axis, bwd_perm)
            if eager:
                # received boundaries rest one tick before consumption —
                # the slack XLA overlaps the collective-permute into
                return (fwd_new, fwd_bnd, bwd_new, bwd_bnd, in_buf,
                        dy_buf, dp, dhp, dx_buf, loss), None
            return (fwd_new, bwd_new, in_buf, dy_buf, dp, dhp, dx_buf,
                    loss), None

        act_shape = (mb,) + xg.shape[1:]
        vary = lambda z: _to_varying(z, axis)
        dy_slots = buf_n if defer_weight_grads else 1  # 1: placeholder
        carry0 = (
            vary(jnp.zeros(act_shape, xg.dtype)),               # fwd_bnd
            *((vary(jnp.zeros(act_shape, xg.dtype)),)           # fwd_rdy
              if eager else ()),
            vary(jnp.zeros(act_shape, xg.dtype)),               # bwd_bnd
            *((vary(jnp.zeros(act_shape, xg.dtype)),)           # bwd_rdy
              if eager else ()),
            vary(jnp.zeros((buf_n,) + act_shape, xg.dtype)),    # in_buf
            vary(jnp.zeros((dy_slots,) + act_shape, xg.dtype)),  # dy_buf
            # ZBH1 computes dp post-scan: don't carry a param-sized zero
            vary(jnp.zeros((), jnp.float32)) if defer_weight_grads else
            jax.tree.map(
                lambda a: vary(jnp.zeros(a.shape, jnp.float32)), p_stage),
            jax.tree.map(
                lambda a: vary(jnp.zeros(a.shape, jnp.float32)), head_p),
            vary(jnp.zeros((n_micro,) + act_shape, jnp.float32)),  # dx
            vary(jnp.zeros((), jnp.float32)),                   # loss
        )
        carry, _ = jax.lax.scan(tick, carry0, jnp.arange(t_total))
        in_buf, dy_buf, dp, dhp, dx_buf, loss = carry[-6:]
        if defer_weight_grads:
            # ZBH1 W pass: all microbatches' weight grads in ONE batched
            # vjp (recompute-forward per microbatch, like the in-tick
            # backward would have done — just batched and off the
            # critical path)
            def wgrad(x_i, dy_i):
                _, vjp_p = jax.vjp(lambda pp: stage_fn(pp, x_i), p_stage)
                return vjp_p(dy_i)[0]

            dps = jax.vmap(wgrad)(in_buf, dy_buf)
            dp = jax.tree.map(
                lambda g: g.astype(jnp.float32).sum(axis=0), dps)
        d_stacked = jax.tree.map(lambda a: a[None], dp)
        if coop:
            # sharded head leaves already hold exactly their shard's grad;
            # replicated leaves (e.g. the final norm) hold partials
            d_head = jax.tree.map(
                lambda a, s: a if axis in jax.tree.leaves(tuple(s))
                else jax.lax.psum(a, axis), dhp, hp_specs)
        else:
            d_head = jax.tree.map(lambda a: jax.lax.psum(a, axis), dhp)
        d_x = jax.lax.psum(dx_buf, axis).reshape((batch,) + xg.shape[1:])
        return jax.lax.psum(loss, axis), d_stacked, d_head, d_x

    return run(stacked_params, head_params, x, labels)
