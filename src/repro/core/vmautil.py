"""Helpers for shard_map's varying-manual-axes (vma) type tracking.

Fresh zero-initialized scan carries (recurrent states, accumulators) are
vma-invariant while the values computed from real inputs vary over mesh axes;
lax.scan requires carry types to match exactly. `vary_like` upcasts the
zeros to the union of the reference values' vma (a pure type cast — pcast to
'varying' moves no data)."""

from __future__ import annotations

import jax
from jax import lax


def vary_to(x, axes):
    need = tuple(a for a in axes if a not in jax.typeof(x).vma)
    return lax.pcast(x, need, to="varying") if need else x


def tree_vma_union(tree) -> frozenset:
    out: frozenset = frozenset()
    for leaf in jax.tree.leaves(tree):
        out |= jax.typeof(leaf).vma
    return out


def vary_like(tree, ref_tree):
    """Upcast every leaf of `tree` to the vma union of `ref_tree`."""
    axes = tuple(sorted(tree_vma_union(ref_tree)))
    return jax.tree.map(lambda v: vary_to(v, axes), tree)


def pallas_operands(*xs):
    """Cast a `pallas_call`'s operands to one common vma and return them
    with it: inside shard_map every `out_shape` must carry the vma of the
    values it is computed from (`ShapeDtypeStruct(..., vma=vma)`)."""
    xs = vary_like(xs, xs)
    return (*xs, tree_vma_union(xs))
