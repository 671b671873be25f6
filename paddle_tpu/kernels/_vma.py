"""Varying-manual-axes plumbing for kernels called under `jax.shard_map`.

With `check_vma=True` every value inside a shard_map body carries the set
of manual mesh axes it varies over. A `pallas_call` has to declare that
set on its `out_shape`, and a `custom_vjp` bwd rule has to return
cotangents whose set equals the primal's. jax's Pallas interpreter cannot
evaluate a kernel under that check at all, so interpret mode inside a
vma-checked shard_map is one of the cases a kernel wrapper routes to its
jnp form — decided before the call, from `operand_vma`.
"""
from __future__ import annotations

import jax


def operand_vma(*xs) -> frozenset:
    """Manual axes any operand varies over (empty outside shard_map and
    under check_vma=False) — the set a kernel's outputs vary over."""
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def like_primal(ct, primal):
    """Give a cotangent exactly the primal's varying manual axes: axes
    the primal does not vary over are summed out (the transpose of the
    implicit broadcast), axes only the primal varies over are marked
    varying."""
    have, want = jax.typeof(ct).vma, jax.typeof(primal).vma
    extra = tuple(a for a in have if a not in want)
    if extra:
        ct = jax.lax.psum(ct, extra)
    missing = tuple(a for a in want if a not in have)
    if missing:
        ct = jax.lax.pcast(ct, missing, to="varying")
    return ct
