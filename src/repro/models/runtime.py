"""Glue between model definitions and the mesh: storage conversion,
spec building, and shard_map-wrapped step construction.

Used by train/, launch/dryrun, tests and examples so they all build steps
the same way.
"""

from __future__ import annotations

import jax
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.core.api import shard_params, unshard_params
from repro.core.dist import DistConfig, make_mesh
from repro.core.meta import abstract_storage, storage_specs

# The one canonical full<->storage transform lives in core/api.py
# (stacked-aware); these names are kept for existing call sites.
tree_to_storage = shard_params
tree_from_storage = unshard_params


def stacked_keys(model) -> dict:
    """Which top-level param groups carry a leading layer-stack dim.

    Part of the model contract: every model declares `stacked_keys`
    explicitly (no `n_steps` guessing — models without the attribute get a
    pointed error instead of an AttributeError deep in a tree map)."""
    sk = getattr(model, "stacked_keys", None)
    if sk is None:
        raise TypeError(
            f"{type(model).__name__} does not declare `stacked_keys`; the "
            "model contract (models/common.py) requires a property mapping "
            "each layer-stacked param group to its stack length, e.g. "
            "{'blocks': n_steps}")
    return dict(sk)


def model_storage_specs(model, dcfg: DistConfig):
    metas = model.metas(dcfg)
    sk = stacked_keys(model)
    return {
        k: storage_specs(metas[k], dcfg, stacked=(k in sk))
        for k in metas
    }


def model_abstract_storage(model, dcfg: DistConfig):
    metas = model.metas(dcfg)
    sk = stacked_keys(model)
    return {
        k: abstract_storage(metas[k], dcfg, n_layers=sk.get(k))
        for k in metas
    }


def init_storage(model, key, dcfg: DistConfig):
    full = model.init_full(key, dcfg)
    metas = model.metas(dcfg)
    return {k: tree_to_storage(full[k], metas[k], dcfg) for k in full}


def batch_specs(model, shape, dcfg: DistConfig):
    """Batch sharding: rows over the data axes; under context parallelism
    the SEQUENCE dim (dim 1 of every 2D+ input) additionally shards over
    the ctx axis — each rank receives its contiguous slice of the
    host-side zigzag-permuted sequence (core/context.zigzag_batch)."""
    axes = dp_axes(dcfg)
    cp_seq = dcfg.cp_axis if dcfg.cp_size > 1 else None
    specs = {}
    for k, sds in model.input_specs(shape, dcfg).items():
        if cp_seq is not None and len(sds.shape) >= 2:
            specs[k] = P(axes, cp_seq, *([None] * (len(sds.shape) - 2)))
        else:
            specs[k] = P(axes, *([None] * (len(sds.shape) - 1)))
    return specs


def dp_axes(dcfg: DistConfig) -> tuple[str, ...]:
    """Batch-ROW sharding axes: everything that is not TP, not the pipe
    axis (every pipe rank sees the same microbatch stream) and not the ctx
    axis (cp ranks replicate rows and shard the sequence dim instead)."""
    return tuple(a for a in dcfg.mesh_axes
                 if a != dcfg.tp_axis and a != dcfg.pp_axis
                 and a != dcfg.cp_axis)


def make_loss_step(model, dcfg: DistConfig, with_grads: bool = True):
    """Returns step(storage, batch) -> (loss, grads?) for shard_map."""
    def step(storage, batch):
        if with_grads:
            loss, grads = jax.value_and_grad(
                lambda s: model.loss_local(s, batch, dcfg)[0])(storage)
        else:
            loss = model.loss_local(storage, batch, dcfg)[0]
            grads = None
        # undo the 1/tp gradient-convention scaling for the LOGGED value
        loss = lax.pmean(loss, dcfg.mesh_axes) * dcfg.tp_size
        return (loss, grads) if with_grads else loss
    return step


def wrap_step(model, dcfg: DistConfig, shape, step_fn, out_specs,
              mesh=None):
    mesh = mesh or make_mesh(dcfg)
    in_specs = (model_storage_specs(model, dcfg),
                batch_specs(model, shape, dcfg))
    return jax.jit(shard_map(step_fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs)), mesh
