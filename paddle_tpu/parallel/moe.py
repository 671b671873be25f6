"""Expert parallelism: MoE layer with all-to-all dispatch over the `ep` axis.

Reference: python/paddle/incubate/distributed/models/moe/moe_layer.py:263
(MoELayer with global_scatter/global_gather all-to-all ops), gates in
moe/gate/{gshard,switch,naive}_gate.py, helpers
python/paddle/distributed/utils/moe_utils.py:20,153.

TPU-native: experts are stacked into one weight tensor with the expert dim
sharded over `ep` (aliasing `mp` or `dp` when no dedicated axis exists);
tokens are routed with a capacity-bounded one-hot dispatch einsum
(GShard-style — compiler-friendly static shapes, no dynamic gather), and
XLA lowers the dispatch/combine einsums against expert-sharded weights to
the same all-to-all pattern as global_scatter/global_gather.

Beside that capacity-bounded `MoELayer` (GShard: softmax gates, a capacity
that drops tokens, every expert present) lives the dropless layer of today's
expert models, `DroplessMoELayer`: it is told which experts it HOLDS (one
chip's share of an expert-parallel group; all of them by default), routes
over all of them with `BiasBalancedSigmoidGate` (sigmoid scores, choice by
score + a bias that a balancing rule moves after each step and no gradient
reaches, gates from the scores alone), sorts the (token, expert) assignments
that fall on held experts by expert and hands them to the grouped matmul
(kernels/grouped_matmul.py) with the group sizes. No capacity exists and no
token is dropped: a held expert computes every row routed to it, and what the
experts held elsewhere would add is left out (on one chip the layer runs
without its exchange). Rows move between tokens and buffer in proportion to
the rows in use (kernels/moe_rows.py). It returns its result with the step's
counters (`moe.rows_held`, `moe.rows_routed`, `moe.rows_multiplied`,
`moe.rows_moved`, `moe.load_max`, `moe.load_mean`, `moe.rows_dropped`,
`moe.load`), computed on the device, and the router's choice (`moe.choice`).

A layer that holds a share of the experts does not train its router's weight:
the gates' gradient needs every chosen expert's output, which the exchange
brings and one chip alone has not (`dropless_experts`). The bias rule runs
either way.
"""
from __future__ import annotations

import functools
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, dispatch, unwrap
from ..nn.layer.layers import Layer
from . import mesh as mesh_mod
from .api import shard_constraint
from .placement import Replicate, Shard

__all__ = ["NaiveGate", "SwitchGate", "GShardGate", "MoELayer",
           "moe_dispatch", "moe_dispatch_sorted", "moe_combine_sorted",
           "BiasBalancedSigmoidGate", "HeldExperts", "DroplessMoELayer",
           "dropless_experts", "MOE_COUNTERS"]


class NaiveGate(Layer):
    """reference: moe/gate/naive_gate.py — linear router, top-k softmax."""

    def __init__(self, d_model, num_experts, topk=2):
        super().__init__()
        self.num_experts = num_experts
        self.topk = topk
        self.gate_weight = self.create_parameter([d_model, num_experts])

    def forward(self, x):
        from ..nn import functional as F

        return F.softmax(x @ self.gate_weight, axis=-1)


class SwitchGate(NaiveGate):
    """reference: moe/gate/switch_gate.py — top-1 routing."""

    def __init__(self, d_model, num_experts, topk=1, **kw):
        super().__init__(d_model, num_experts, topk=1)


class GShardGate(NaiveGate):
    """reference: moe/gate/gshard_gate.py — top-2 + capacity + aux loss."""

    def __init__(self, d_model, num_experts, topk=2, capacity_factor=1.25, **kw):
        super().__init__(d_model, num_experts, topk=topk)
        self.capacity_factor = capacity_factor


def moe_dispatch(x, gate_probs, num_experts: int, topk: int,
                 capacity_factor: float = 1.25):
    """Capacity-bounded top-k dispatch (GShard). Returns (dispatch_mask
    [tokens, experts, capacity], combine_weights same shape, aux_loss).

    Static-shape re-expression of global_scatter (moe_utils.py:20): instead
    of variable-length token lists per expert, a fixed `capacity` slot
    matrix — the XLA-friendly form."""
    tokens = x.shape[0]
    capacity = max(1, int(capacity_factor * tokens * topk / num_experts))

    def impl(probs):
        topv, topi = jax.lax.top_k(probs, topk)  # [tokens, topk]
        mask = jax.nn.one_hot(topi, num_experts, dtype=probs.dtype)  # [t,k,e]
        # positions within each expert queue
        flat = mask.reshape(tokens * topk, num_experts)
        pos = jnp.cumsum(flat, axis=0) - 1.0  # [t*k, e]
        pos = pos.reshape(tokens, topk, num_experts)
        keep = pos < capacity
        mask = mask * keep
        # aux load-balance loss (gshard eq.)
        density = mask.sum(axis=(0, 1)) / tokens
        density_proxy = probs.mean(axis=0)
        aux = (density * density_proxy).sum() * num_experts
        slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                              dtype=probs.dtype)  # [t,k,e,c]
        disp = (mask[..., None] * slot).sum(1)  # [t,e,c]
        weights = (mask * topv[..., None]).sum(1)  # [t,e]
        combine = disp * weights[..., None]
        return disp, combine, aux

    return dispatch("moe_dispatch", impl, (gate_probs,), n_outs=3)


def moe_dispatch_sorted(x, gate_probs, num_experts: int, topk: int,
                        capacity_factor: float = 1.25):
    """Sort-based capacity dispatch — the scalable form of global_scatter
    (reference: moe_utils.py:20, and §7.1's 'MoE dispatch' kernel slot).

    The dense `moe_dispatch` materializes a [T, K, E, C] slot one-hot:
    with C ≈ T·K/E that is O(T²K²) memory — fine for tests, fatal at real
    token counts. Here assignments are sorted by expert id (stable, so
    arrival order — and therefore capacity drops — matches the dense
    form), each kept assignment scatters its token row straight into its
    [E, C, D] expert slot, and dropped rows land in one overflow slot.
    Memory is O(T·K·D + E·C·D); one scatter + one gather, both XLA-native
    on TPU.

    Returns (expert_inputs [E, C, D], slot_dst [T*K] int32 — flat slot per
    (token, k) assignment with E*C meaning dropped, weights [T*K], aux).
    Combine with :func:`moe_combine_sorted`.
    """
    tokens = x.shape[0]
    capacity = max(1, int(capacity_factor * tokens * topk / num_experts))

    def impl(hh, probs):
        d = hh.shape[1]
        topv, topi = jax.lax.top_k(probs, topk)  # [T, K]
        eid = topi.reshape(-1)  # slot s = t*K + k
        order = jnp.argsort(eid, stable=True)
        e_sorted = eid[order]
        counts = jnp.bincount(eid, length=num_experts)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(tokens * topk) - starts[e_sorted]
        keep = pos < capacity
        dst = jnp.where(keep, e_sorted * capacity + pos,
                        num_experts * capacity)  # overflow slot
        src_tok = order // topk
        buf = jnp.zeros((num_experts * capacity + 1, d), hh.dtype)
        buf = buf.at[dst].set(hh[src_tok])
        expert_in = buf[:-1].reshape(num_experts, capacity, d)
        # per-assignment combine metadata, back in slot order
        slot_dst = jnp.full((tokens * topk,), num_experts * capacity,
                            jnp.int32).at[order].set(dst.astype(jnp.int32))
        slot_keep = jnp.zeros((tokens * topk,), bool).at[order].set(keep)
        weights = jnp.where(slot_keep, topv.reshape(-1), 0.0)
        # gshard aux loss on the kept assignment density
        density = jnp.minimum(counts, capacity).astype(probs.dtype) / tokens
        aux = (density * probs.mean(axis=0)).sum() * num_experts
        return expert_in, slot_dst, weights, aux

    return dispatch("moe_dispatch_sorted", impl, (x, gate_probs), n_outs=4)


def moe_combine_sorted(expert_out, slot_dst, weights, tokens: int, topk: int):
    """Inverse of moe_dispatch_sorted — the global_gather analog
    (reference: moe_utils.py:153): gather each assignment's expert output
    row and weighted-sum the top-k per token."""

    def impl(out_ecd, dstv, wv):
        e, c, d = out_ecd.shape
        flat = jnp.concatenate(
            [out_ecd.reshape(e * c, d), jnp.zeros((1, d), out_ecd.dtype)])
        rows = flat[dstv] * wv[:, None].astype(out_ecd.dtype)
        return rows.reshape(tokens, topk, d).sum(axis=1)

    return dispatch("moe_combine_sorted", impl,
                    (expert_out, slot_dst, weights))


class MoELayer(Layer):
    """reference: moe_layer.py:263 MoELayer(d_model, experts, gate, ...).

    forward: gate -> dispatch all-to-all -> expert MLPs -> combine."""

    def __init__(self, d_model: int, experts: Optional[List[Layer]] = None,
                 gate=None, moe_group=None, mp_group=None,
                 num_experts: Optional[int] = None, d_hidden: Optional[int] = None,
                 topk: int = 2, capacity_factor: float = 1.25, **kw):
        super().__init__()
        if experts is not None:
            num_experts = len(experts)
            from ..nn.layer.container import LayerList

            self.experts = LayerList(experts)
            self._stacked = False
            self._ep_axis = None
        else:
            assert num_experts and d_hidden
            # stacked expert weights [E, d, h] / [E, h, d]: expert dim
            # sharded over the ep axis
            self.w1 = self.create_parameter([num_experts, d_model, d_hidden])
            self.w2 = self.create_parameter([num_experts, d_hidden, d_model])
            self._stacked = True
            mesh = mesh_mod.get_global_mesh()
            ep_axis = next((a for a in ("ep", "mp", "sharding")
                            if mesh is not None and a in mesh.axis_names
                            and num_experts % int(mesh.shape[a]) == 0), None)
            self._ep_axis = ep_axis
            if ep_axis is not None:
                sh = jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec(ep_axis))
                self.w1._array = jax.device_put(self.w1._array, sh)
                self.w2._array = jax.device_put(self.w2._array, sh)
        self.num_experts = num_experts
        self.topk = topk
        self.capacity_factor = capacity_factor
        self.gate = gate or NaiveGate(d_model, num_experts, topk=topk)
        self.aux_loss = None

    def forward(self, x):
        orig_shape = x.shape
        h = x.reshape([-1, orig_shape[-1]])
        probs = self.gate(h)

        if self._stacked:
            # scalable path: sort-based dispatch (no [T,E,C] one-hot)
            expert_in, slot_dst, weights, aux = moe_dispatch_sorted(
                h, probs, self.num_experts, self.topk, self.capacity_factor)
            self.aux_loss = aux
            mesh = mesh_mod.get_global_mesh()
            if mesh is not None and self._ep_axis is not None:
                # constrain the expert dim over ep: GSPMD lowers the
                # scatter->sharded-einsum boundary to the all-to-all
                expert_in = shard_constraint(
                    expert_in,
                    [Shard(0) if a == self._ep_axis else Replicate()
                     for a in mesh.axis_names], mesh)

            def expert_impl(ein, w1, w2):
                act = jax.nn.gelu(jnp.einsum("ecd,edh->ech", ein, w1))
                return jnp.einsum("ech,ehd->ecd", act, w2)

            out_ecd = dispatch("moe_experts", expert_impl,
                               (expert_in, self.w1, self.w2))
            y = moe_combine_sorted(out_ecd, slot_dst, weights,
                                   h.shape[0], self.topk)
        else:
            disp, combine, aux = moe_dispatch(
                h, probs, self.num_experts, self.topk, self.capacity_factor)
            self.aux_loss = aux
            ein = dispatch("moe_dispatch_einsum",
                           lambda d, hh: jnp.einsum("tec,td->ecd", d, hh),
                           (disp, h))
            outs = []
            for e, expert in enumerate(self.experts):
                outs.append(expert(ein[e]))
            from .. import ops

            stacked = ops.stack(outs, axis=0)
            y = dispatch("moe_combine",
                         lambda c, o: jnp.einsum("tec,ecd->td", c, o),
                         (combine, stacked))
        return y.reshape(orig_shape)


# ---------------------------------------------------------------------------
# dropless layer over the experts held here
# ---------------------------------------------------------------------------

class BiasBalancedSigmoidGate(Layer):
    """The auxiliary-loss-free router of DeepSeek-V3 (arXiv:2412.19437
    section 2.1.2; `topk_method: noaux_tc` with one group): scores are
    sigmoids in f32, the `topk` experts are the top of score + bias, the
    gates are the scores alone at those experts, normalised to sum to one
    (`norm_topk_prob`) and scaled. The bias is a buffer: state, not a
    parameter — no gradient, no optimizer state; `updated_bias` is its
    rule."""

    def __init__(self, d_model: int, num_experts: int, topk: int,
                 norm_topk_prob: bool = True,
                 routed_scaling_factor: float = 1.0):
        super().__init__()
        self.num_experts, self.topk = num_experts, topk
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.weight = self.create_parameter([d_model, num_experts])
        self.register_buffer("e_score_correction_bias", Tensor(
            jnp.zeros((num_experts,), jnp.float32)))

    def forward(self, x):
        """x [T, d] -> (experts [T, topk] int32, gates [T, topk] f32)."""

        def impl(h, w, bias):
            score = jax.nn.sigmoid(jnp.dot(
                h.astype(jnp.float32), w.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            _, idx = jax.lax.top_k(
                score + jax.lax.stop_gradient(bias), self.topk)
            gates = jnp.take_along_axis(score, idx, axis=-1)
            if self.norm_topk_prob:
                gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
            return idx, gates * self.routed_scaling_factor

        return dispatch("moe_route", impl,
                        (x, self.weight, self.e_score_correction_bias),
                        n_outs=2)

    @staticmethod
    def updated_bias(bias, load, rate: float):
        """bias + rate * sign(mean load - load): an expert that saw fewer
        tokens than the mean becomes likelier, a busier one less likely."""
        load = load.astype(jnp.float32)
        return bias + rate * jnp.sign(jnp.mean(load) - load)


@jax.custom_vjp
def _rows_in(x, row_token, dest):
    """xs[r] = x[row_token[r]] (a zero row where row_token is out of range).
    `dest` [T, k] is the inverse map: the buffer row of each assignment, out
    of range where it has none. Both directions are gathers."""
    return jnp.take(x, row_token, axis=0, mode="fill", fill_value=0)


def _rows_in_fwd(x, row_token, dest):
    return _rows_in(x, row_token, dest), dest


def _rows_in_bwd(dest, dxs):
    picked = jnp.take(dxs, dest.reshape(-1), axis=0, mode="fill",
                      fill_value=0)
    return (picked.reshape(dest.shape + dxs.shape[1:]).sum(1)
            .astype(dxs.dtype), None, None)


_rows_in.defvjp(_rows_in_fwd, _rows_in_bwd)


@jax.custom_vjp
def _rows_out(ys, gates, row_token, dest, row_gate):
    """y[t] = sum_j gates[t, j] * ys[dest[t, j]], f32 accumulation.
    `row_gate` [rows] is the gate of the assignment in each buffer row (0 in
    padding): the backward pass spreads dy over the rows by a gather too."""
    picked = jnp.take(ys, dest.reshape(-1), axis=0, mode="fill",
                      fill_value=0).reshape(dest.shape + ys.shape[1:])
    return jnp.einsum("tk,tkd->td", gates, picked,
                      preferred_element_type=jnp.float32).astype(ys.dtype)


def _rows_out_fwd(ys, gates, row_token, dest, row_gate):
    return (_rows_out(ys, gates, row_token, dest, row_gate),
            (ys, row_token, dest, row_gate))


def _rows_out_bwd(res, dy):
    ys, row_token, dest, row_gate = res
    dys = jnp.take(dy, row_token, axis=0, mode="fill", fill_value=0) \
        .astype(jnp.float32) * row_gate[:, None]
    picked = jnp.take(ys, dest.reshape(-1), axis=0, mode="fill",
                      fill_value=0).reshape(dest.shape + ys.shape[1:])
    dgates = jnp.einsum("td,tkd->tk", dy, picked,
                        preferred_element_type=jnp.float32)
    return dys.astype(ys.dtype), dgates, None, None, None


_rows_out.defvjp(_rows_out_fwd, _rows_out_bwd)


# ---------------------------------------------------------------------------
# the same four movements as kernels that walk the rows that exist
# (kernels/moe_rows.py); the jnp forms above stay as the tests' oracle and for
# shapes off the kernels' tiles (the CPU tests' tiny models)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rows_in_vjp(x, dest_t, chunks, rows, groups, on_tpu):
    from ..kernels import moe_rows

    # the buffer twice, for its two readers: their cotangents then reach
    # the backward pass apart, and no pass over the buffer adds them
    xs = moe_rows.scatter_rows(x, dest_t, None, chunks, rows, groups,
                               name="moe_rows_in")
    return xs, xs


def _rows_in_vjp_fwd(x, dest_t, chunks, rows, groups, on_tpu):
    return (_rows_in_vjp(x, dest_t, chunks, rows, groups, on_tpu),
            (dest_t, chunks))


def _rows_in_vjp_bwd(rows, groups, on_tpu, res, dxs):
    from ..kernels import moe_rows

    dest_t, chunks = res
    return (moe_rows.gather_rows(dxs, dest_t, None, chunks,
                                 name="moe_rows_in_bwd"), None, None)


_rows_in_vjp.defvjp(_rows_in_vjp_fwd, _rows_in_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _rows_out_vjp(ys, gates_t, dest_t, chunks, groups, train_gates, on_tpu):
    from ..kernels import moe_rows

    return moe_rows.gather_rows((ys,), dest_t, gates_t, chunks,
                                name="moe_rows_out")


def _rows_out_vjp_fwd(ys, gates_t, dest_t, chunks, groups, train_gates,
                      on_tpu):
    return (_rows_out_vjp(ys, gates_t, dest_t, chunks, groups, train_gates,
                          on_tpu), (ys, gates_t, dest_t, chunks))


def _rows_out_vjp_bwd(groups, train_gates, on_tpu, res, dy):
    from ..kernels import moe_rows

    ys, gates_t, dest_t, chunks = res
    dys = moe_rows.scatter_rows(dy, dest_t, gates_t, chunks, ys.shape[0],
                                groups, name="moe_rows_out_bwd")
    # under a share of the experts the gates are constants (dropless_experts)
    dgates_t = moe_rows.gather_dots(ys, dy, dest_t, chunks) if train_gates \
        else jnp.zeros_like(gates_t)
    return dys, dgates_t, None, None


_rows_out_vjp.defvjp(_rows_out_vjp_fwd, _rows_out_vjp_bwd)

# the blocks of a model share one trace and one private function of the
# lowered module, so a step's text holds each kernel's payload once (PERF.md
# section 6, PR 29); what the trace depends on beside its operands
# (`jax.default_backend()`) is an argument, so it is part of the jit's key
_rows_in_jit = jax.jit(_rows_in_vjp, static_argnums=(3, 4, 5))
_rows_out_jit = jax.jit(_rows_out_vjp, static_argnums=(4, 5, 6))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _route(idx, held, num_experts: int, rows: int, tile: int, chunked: bool):
    """Where each assignment of `idx` [T, k] goes in a buffer of `rows` rows
    in row tiles of `tile`: (the groups' layout, dest [T, k] — `rows` where
    the expert is held elsewhere —, the token tiles' chunk lists if
    `chunked`, the assignments on held experts). One jit: a model's blocks
    share its trace.

    A counting sort, stable: an assignment's row is its group's start plus
    the assignments of its group before it, so inside a group the rows are
    in token order (kernels/moe_rows.py rests on that)."""
    from ..kernels import moe_rows
    from ..kernels.grouped_matmul import group_layout

    t, k = idx.shape
    g = len(held)
    local = jnp.full((num_experts,), g, jnp.int32).at[
        jnp.asarray(held, jnp.int32)].set(jnp.arange(g, dtype=jnp.int32))
    lid = local[idx.reshape(-1)]                    # [T*k]; g = held elsewhere
    mine = lid[:, None] == jnp.arange(g, dtype=jnp.int32)   # [T*k, G]
    upto = jnp.cumsum(mine.astype(jnp.int32), axis=0)
    layout = group_layout(upto[-1], rows, tile)
    dest = jnp.where(lid < g, jnp.sum(jnp.where(
        mine, layout.starts + upto - 1, 0), axis=1), rows)
    step = moe_rows.TOKEN_TILE * k
    chunks = moe_rows.tile_chunks(upto[step - 1::step], layout, k, tile) \
        if chunked else None
    return layout, dest.reshape(t, k), chunks, jnp.sum(upto[-1])


# what a dropless layer counts in a step, on the device (observability/README);
# the last is no counter but the router's choice itself [T, k], for a check
# that holds the layer to a reference token by token
MOE_COUNTERS = ("moe.rows_held", "moe.rows_routed", "moe.rows_multiplied",
                "moe.rows_dropped", "moe.rows_moved", "moe.load_max",
                "moe.load_mean", "moe.load", "moe.choice")


def dropless_experts(x, idx, gates, w_gate, w_up, w_down, held,
                     num_experts: int, limit: Optional[float] = None):
    """The held experts' part of the layer: x [T, d], the router's choice
    idx [T, k] over all `num_experts` and its gates [T, k]; stacked SwiGLU
    weights [G, d, f], [G, d, f], [G, f, d] of the experts `held` (global
    indices, G of them). Returns (y [T, d], counters).

    The assignments that fall on held experts are sorted by expert into a
    buffer in which every expert's rows start on a row tile, sized for the
    worst case (every token on `min(k, G)` held experts); the rest are not
    computed, here or anywhere on this chip. The tile follows from T, k and
    G alone (`grouped_matmul.row_tile`): a trainer's thousands of rows an
    expert take 128-row tiles, a decode step's handful the 16-row sublane
    tile. Everything that touches the buffer walks the rows in use: the
    grouped matmul its row tiles, the row movements (kernels/moe_rows.py)
    the 16-row chunks that hold a token tile's rows. Row tiles no expert
    uses are neither written nor read.

    `limit` clamps the SwiGLU (`swiglu_limit`): silu(min(g, limit)) *
    clip(u, -limit, limit); None leaves it unclamped.

    Where `held` is a share of the experts, the gates are constants of the
    backward pass: a gate's gradient needs the outputs of every expert its
    token chose, and only the held ones are here. The part this chip could
    form says "an absent expert adds nothing, a held one adds noise", and a
    router trained from it moves its tokens off the held experts within a
    hundred steps (PERF.md section 6, PR 28), which no deployment's router
    does: there the exchange brings every chosen expert's output back to the
    token. So the router's weight trains where the layer holds every expert,
    and waits for the exchange where it holds a share; the bias rule, which
    needs counts alone, runs in both."""
    from ..kernels import moe_rows
    from ..kernels.grouped_matmul import (buffer_rows, grouped_matmul,
                                          row_tile)

    t, k = idx.shape
    g = len(held)
    if g < num_experts:
        gates = jax.lax.stop_gradient(gates)
    tile = row_tile(t, k, g)
    rows = buffer_rows(t * min(k, g), g, tile)
    chunked = moe_rows.rows_ok(t, x.shape[1], w_gate.shape[2], rows, tile)
    layout, dest, chunks, on_held = _route(
        idx, tuple(int(e) for e in held), num_experts, rows, tile, chunked)

    if chunked:
        on_tpu = jax.default_backend() == "tpu"
        dest_t = dest.T
        gates_t = gates.astype(jnp.float32).T

        def rows_in(x):
            return _rows_in_jit(x, dest_t, chunks, rows, g, on_tpu)

        def rows_out(ys):
            return _rows_out_jit(ys, gates_t, dest_t, chunks, g,
                                 g == num_experts, on_tpu)

        moved = (jnp.sum(chunks.n_read) + layout.n_tiles
                 * (tile // moe_rows.CHUNK)) * moe_rows.CHUNK
    else:
        row_assign = jnp.full((rows,), t * k, jnp.int32).at[
            dest.reshape(-1)].set(jnp.arange(t * k, dtype=jnp.int32),
                                  mode="drop")
        row_token = jnp.where(row_assign < t * k, row_assign // k, t)
        row_gate = jnp.take(gates.reshape(-1), row_assign, mode="fill",
                            fill_value=0)

        def rows_in(x):
            return (_rows_in(x, row_token, dest),) * 2

        def rows_out(ys):
            return _rows_out(ys, gates, row_token, dest, row_gate)

        moved = jnp.asarray(rows + t * k, jnp.int32)

    xs_gate, xs_up = rows_in(x)
    g_out = grouped_matmul(xs_gate, w_gate, layout)
    u_out = grouped_matmul(xs_up, w_up, layout)
    if limit is not None:
        g_out = jnp.minimum(g_out, limit)
        u_out = jnp.clip(u_out, -limit, limit)
    act = jax.nn.silu(g_out) * u_out
    ys = grouped_matmul(act, w_down, layout)
    y = rows_out(ys)

    load = jnp.bincount(idx.reshape(-1), length=num_experts)
    live = jnp.sum(layout.tile_rows)
    counters = {
        "moe.rows_held": live,
        "moe.rows_routed": jnp.asarray(t * k, jnp.int32),
        "moe.rows_multiplied": layout.n_tiles * tile,
        "moe.rows_dropped": on_held - live,
        "moe.rows_moved": moved.astype(jnp.int32),
        "moe.load_max": jnp.max(layout.sizes),
        "moe.load_mean": jnp.mean(layout.sizes.astype(jnp.float32)),
        "moe.load": load.astype(jnp.float32),
        "moe.choice": idx,
    }
    return y, counters


class HeldExperts(Layer):
    """Stacked SwiGLU weights of the experts this chip holds."""

    def __init__(self, d_model: int, d_hidden: int, held):
        super().__init__()
        from ..nn.initializer import Normal

        self.held = tuple(int(e) for e in held)
        g = len(self.held)
        # each expert a Xavier-normal [d, f] matrix (the stacked shape's own
        # fans would count the expert dim)
        init = Normal(std=(2.0 / (d_model + d_hidden)) ** 0.5)
        self.gate_proj = self.create_parameter(
            [g, d_model, d_hidden], default_initializer=init)
        self.up_proj = self.create_parameter(
            [g, d_model, d_hidden], default_initializer=init)
        self.down_proj = self.create_parameter(
            [g, d_hidden, d_model], default_initializer=init)


class DroplessMoELayer(Layer):
    """gate -> sort the assignments on held experts -> grouped matmuls ->
    weighted sum. `held`: the global indices of the experts held here, all
    `num_experts` by default. forward(x [..., d]) -> (y, counters)."""

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 topk: int, held=None, norm_topk_prob: bool = True,
                 routed_scaling_factor: float = 1.0):
        super().__init__()
        held = range(num_experts) if held is None else held
        self.num_experts = num_experts
        self.gate = BiasBalancedSigmoidGate(
            d_model, num_experts, topk, norm_topk_prob,
            routed_scaling_factor)
        self.experts = HeldExperts(d_model, d_hidden, held)
        if not all(0 <= e < num_experts for e in self.experts.held) \
                or len(set(self.experts.held)) != len(self.experts.held):
            raise ValueError(f"held experts {self.experts.held} are not "
                             f"distinct indices below {num_experts}")

    def forward(self, x):
        shape = x.shape
        h = x.reshape([-1, shape[-1]])
        with jax.named_scope("moe.route"):
            idx, gates = self.gate(h)
        ex = self.experts

        def impl(hh, ii, gg, wg, wu, wd):
            y, c = dropless_experts(hh, ii, gg, wg, wu, wd, ex.held,
                                    self.num_experts)
            return (y,) + tuple(c[k] for k in MOE_COUNTERS)

        with jax.named_scope("moe.experts"):
            y, *counted = dispatch(
                "moe_dropless_experts", impl,
                (h, idx, gates, ex.gate_proj, ex.up_proj, ex.down_proj))
        return y.reshape(shape), {k: unwrap(v) for k, v in
                                  zip(MOE_COUNTERS, counted)}
