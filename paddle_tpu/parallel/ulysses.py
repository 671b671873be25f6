"""Ulysses sequence-parallel attention reshard (explicit all-to-all).

Reference analog: the `sep` axis groups of fleet/base/topology.py:224-244 and
the reference's SegmentParallel attention (DeepSpeed-Ulysses style,
arXiv:2309.14509): activations enter attention sharded over sequence, and
attention needs full sequence per head — so the seq shards are exchanged for
head shards with one all-to-all over the sep group, and swapped back after.

GSPMD cannot lower the seq<->head re-constraint efficiently (it logs
"[SPMD] Involuntary full rematerialization" and replicates), so the swap is
done explicitly with jax.shard_map + lax.all_to_all riding ICI.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import BATCH_AXES, divisible_prefix as _divisible_prefix

from jax import shard_map


def _axes_size(mesh: Mesh, names) -> int:
    return math.prod(int(mesh.shape[n]) for n in names)


def sep_degree(mesh: Optional[Mesh], seq_axis: str = "sep") -> int:
    if mesh is None or seq_axis not in mesh.axis_names:
        return 1
    return int(mesh.shape[seq_axis])


def ulysses_available(mesh: Optional[Mesh], num_heads: int, seq_len: int,
                      seq_axis: str = "sep",
                      head_axes: Tuple[str, ...] = ("mp",)) -> bool:
    """True when the explicit a2a path applies: sep>1 and both the head and
    seq dims split evenly over their axes."""
    if sep_degree(mesh, seq_axis) <= 1:
        return False
    g = _axes_size(mesh, [a for a in head_axes if a in mesh.axis_names])
    sep = int(mesh.shape[seq_axis])
    return num_heads % (g * sep) == 0 and seq_len % sep == 0


def minimal_kv_repeat(mesh: Mesh, num_heads: int, num_kv_heads: int,
                      seq_axis: str = "sep",
                      head_axes: Tuple[str, ...] = ("mp",)) -> int:
    """Smallest per-kv-head repeat factor r so nkv*r splits evenly over
    mp*sep AND still block-aligns with q's contiguous head shards
    (num_heads % (nkv*r) == 0). Falls back to the full nh/nkv repeat when
    no smaller factor aligns."""
    g = _axes_size(mesh, [a for a in head_axes if a in mesh.axis_names])
    g *= int(mesh.shape[seq_axis])
    full = num_heads // num_kv_heads
    r = g // math.gcd(num_kv_heads, g)
    if r <= full and num_heads % (num_kv_heads * r) == 0:
        return r
    return full


def _specs(mesh, shape, seq_axis, head_axes):
    """(seq-sharded spec, head-sharded spec) for a [b, s, h, d] tensor."""
    bspec = _divisible_prefix(mesh, shape[0], BATCH_AXES)
    heads = tuple(a for a in head_axes if a in mesh.axis_names)
    seq_spec = P(bspec or None, seq_axis, heads or None, None)
    head_spec = P(bspec or None, None, (heads + (seq_axis,)) or None, None)
    return seq_spec, head_spec


def seq_to_head(x: jax.Array, mesh: Mesh, seq_axis: str = "sep",
                head_axes: Tuple[str, ...] = ("mp",)) -> jax.Array:
    """[b, s/sep, H/mp, d] -> [b, s, H/(mp*sep), d]: one tiled all-to-all
    over the sep group (split heads, concat sequence)."""
    seq_spec, head_spec = _specs(mesh, x.shape, seq_axis, head_axes)

    def swap(a):
        return jax.lax.all_to_all(a, seq_axis, split_axis=2, concat_axis=1,
                                  tiled=True)

    return shard_map(swap, mesh=mesh, in_specs=seq_spec,
                         out_specs=head_spec, check_vma=False)(x)


def head_to_seq(x: jax.Array, mesh: Mesh, seq_axis: str = "sep",
                head_axes: Tuple[str, ...] = ("mp",)) -> jax.Array:
    """[b, s, H/(mp*sep), d] -> [b, s/sep, H/mp, d]: the reverse swap."""
    seq_spec, head_spec = _specs(mesh, x.shape, seq_axis, head_axes)

    def swap(a):
        return jax.lax.all_to_all(a, seq_axis, split_axis=1, concat_axis=2,
                                  tiled=True)

    return shard_map(swap, mesh=mesh, in_specs=head_spec,
                         out_specs=seq_spec, check_vma=False)(x)
