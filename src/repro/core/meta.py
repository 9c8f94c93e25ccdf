"""Parameter metadata and the ZeRO-3 storage layout.

The paper stores each parameter as a DTensor ``Shard(0)`` (optionally 2-D
sharded with TP). We use the flat-shard equivalent, which is divisibility-proof
and TPU-layout friendly:

  * Every parameter is flattened (per TP rank), padded to a multiple of
    ``fsdp_size * LANE`` and sharded 1-D over the FSDP mesh axes.
  * TP-sharded parameters carry an explicit leading ``tp`` index axis in
    storage: shape ``(tp, padded_flat)`` with spec ``P(tp_axis, fsdp_axes)``.
    Row ``t`` is the flattened TP-local block of rank ``t``.
  * Layer-stacked parameters (for ``lax.scan`` over blocks) get a leading
    ``L`` axis on top of that.

`ParamMeta` records the logical <-> storage mapping; `to_storage` /
`from_storage` are exact inverses (property-tested).  Inside ``shard_map`` a
device holds the ``(1, chunk)`` / ``(chunk,)`` local shard; the gather path in
`core/collectives.py` reconstructs the TP-local compute tensor.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.dist import DistConfig

LANE = 128  # pad flat shards so per-device chunks are lane-aligned


@dataclasses.dataclass(frozen=True)
class ParamMeta:
    name: str
    global_shape: tuple[int, ...]     # logical full shape (after head padding)
    tp_dim: int | None = None         # which logical dim is TP-sharded
    dtype: Any = jnp.float32          # storage (master) dtype

    # ------------------------------------------------------------- derived --
    def local_shape(self, cfg: DistConfig) -> tuple[int, ...]:
        """TP-local compute shape (what the model sees after FSDP gather)."""
        if self.tp_dim is None:
            return self.global_shape
        tp = cfg.tp_size
        s = list(self.global_shape)
        if s[self.tp_dim] % tp != 0:
            raise ValueError(
                f"{self.name}: dim {self.tp_dim} ({s[self.tp_dim]}) "
                f"not divisible by tp={tp}; pad the config."
            )
        s[self.tp_dim] //= tp
        return tuple(s)

    def numel_local(self, cfg: DistConfig) -> int:
        return math.prod(self.local_shape(cfg))

    def padded_len(self, cfg: DistConfig) -> int:
        quantum = cfg.fsdp_size * LANE
        return ((self.numel_local(cfg) + quantum - 1) // quantum) * quantum

    def chunk_len(self, cfg: DistConfig) -> int:
        return self.padded_len(cfg) // cfg.fsdp_size

    def storage_shape(self, cfg: DistConfig) -> tuple[int, ...]:
        if self.tp_dim is None:
            return (self.padded_len(cfg),)
        return (cfg.tp_size, self.padded_len(cfg))

    def storage_spec(self, cfg: DistConfig) -> P:
        fsdp = cfg.fsdp_axes if len(cfg.fsdp_axes) > 1 else cfg.fsdp_axes[0]
        if self.tp_dim is None:
            return P(fsdp)
        return P(cfg.tp_axis, fsdp)

    def stacked_storage_shape(self, cfg: DistConfig, n: int) -> tuple[int, ...]:
        return (n, *self.storage_shape(cfg))

    def stacked_storage_spec(self, cfg: DistConfig) -> P:
        return P(None, *self.storage_spec(cfg))

    def pipe_stacked_storage_spec(self, cfg: DistConfig) -> P:
        """Spec for an (S, storage...) stage stack: the leading stage dim is
        sharded over the pipe axis (each pipe rank holds ITS stage's ZeRO-3
        shard), inner dims keep the plain storage layout."""
        if cfg.pp_axis is None:
            raise ValueError("pipe_stacked_storage_spec needs cfg.pp_axis")
        return P(cfg.pp_axis, *self.storage_spec(cfg))

    def shard_shape(self, cfg: DistConfig) -> tuple[int, ...]:
        """Per-device shape inside shard_map."""
        if self.tp_dim is None:
            return (self.chunk_len(cfg),)
        return (1, self.chunk_len(cfg))


# --------------------------------------------------------------------------
# Layout transforms (host-side; exact inverses).
# --------------------------------------------------------------------------
def to_storage(full: jax.Array | np.ndarray, meta: ParamMeta,
               cfg: DistConfig) -> jax.Array:
    """Logical full param -> storage layout (flat/padded/TP-stacked)."""
    full = jnp.asarray(full, dtype=meta.dtype)
    if full.shape != meta.global_shape:
        raise ValueError(
            f"{meta.name}: expected {meta.global_shape}, got {full.shape}"
        )
    pad = meta.padded_len(cfg)
    if meta.tp_dim is None:
        flat = full.reshape(-1)
        return jnp.pad(flat, (0, pad - flat.size))
    tp = cfg.tp_size
    # split the tp_dim into (tp, local) and move tp to the front
    moved = jnp.moveaxis(full, meta.tp_dim, 0)
    blk = moved.reshape(tp, moved.shape[0] // tp, *moved.shape[1:])
    blk = jnp.moveaxis(blk, 1, meta.tp_dim + 1)  # restore dim order per block
    flat = blk.reshape(tp, -1)
    return jnp.pad(flat, ((0, 0), (0, pad - flat.shape[1])))


def from_storage(storage: jax.Array | np.ndarray, meta: ParamMeta,
                 cfg: DistConfig) -> jax.Array:
    """Inverse of `to_storage` (used by checkpointing export and tests)."""
    storage = jnp.asarray(storage)
    local = meta.local_shape(cfg)
    if meta.tp_dim is None:
        return storage[: meta.numel_local(cfg)].reshape(local)
    tp = cfg.tp_size
    blk = storage[:, : meta.numel_local(cfg)].reshape(tp, *local)
    blk = jnp.moveaxis(blk, meta.tp_dim + 1, 1)   # (tp, loc_tp, ...)
    merged = blk.reshape(tp * blk.shape[1], *blk.shape[2:])
    return jnp.moveaxis(merged, 0, meta.tp_dim)


def unflatten_local(flat: jax.Array, meta: ParamMeta,
                    cfg: DistConfig) -> jax.Array:
    """Gathered padded flat (padded_len,) -> TP-local compute tensor."""
    return flat[: meta.numel_local(cfg)].reshape(meta.local_shape(cfg))


def flatten_local(x: jax.Array, meta: ParamMeta, cfg: DistConfig) -> jax.Array:
    """TP-local compute tensor -> padded flat (padded_len,)."""
    flat = x.reshape(-1)
    return jnp.pad(flat, (0, meta.padded_len(cfg) - flat.size))


# --------------------------------------------------------------------------
# Pytree helpers: params and metas travel as parallel pytrees keyed by path.
# --------------------------------------------------------------------------
def named_leaves(tree) -> list[tuple[str, Any]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(path, simple=True, separator="/"), leaf)
            for path, leaf in flat]


def tree_paths(tree) -> list[str]:
    return [k for k, _ in named_leaves(tree)]


def abstract_storage(metas, cfg: DistConfig, n_layers: int | None = None):
    """ShapeDtypeStructs of the storage layout (dry-run / meta-init)."""
    def one(m: ParamMeta):
        shape = (m.stacked_storage_shape(cfg, n_layers)
                 if n_layers is not None else m.storage_shape(cfg))
        return jax.ShapeDtypeStruct(shape, m.dtype)
    return jax.tree.map(one, metas,
                        is_leaf=lambda x: isinstance(x, ParamMeta))


def storage_specs(metas, cfg: DistConfig, stacked: bool = False):
    def one(m: ParamMeta):
        return m.stacked_storage_spec(cfg) if stacked else m.storage_spec(cfg)
    return jax.tree.map(one, metas,
                        is_leaf=lambda x: isinstance(x, ParamMeta))


def pipe_shardable(metas, cfg: DistConfig) -> bool:
    """True iff every ParamMeta leaf's per-device FSDP chunk splits evenly
    over the pipe axis — the condition for storing a single-owner (pre/post)
    param group as (S, chunk/S) pipe-sharded slices instead of zero-filling
    non-owner stage slots (models/staging.py).  All-or-nothing per group so
    the staged layout stays uniform.  chunk_len is a multiple of LANE=128,
    so any power-of-two pipe degree qualifies in practice."""
    if cfg.pp_axis is None or cfg.pp_size <= 1:
        return False
    ms = jax.tree.leaves(metas, is_leaf=lambda x: isinstance(x, ParamMeta))
    return bool(ms) and all(
        m.chunk_len(cfg) % cfg.pp_size == 0 for m in ms)


def param_bytes(metas, cfg: DistConfig, n_layers: int = 1) -> int:
    total = 0
    for _, m in named_leaves(metas):
        total += n_layers * m.padded_len(cfg) * (
            cfg.tp_size if m.tp_dim is not None else 1
        ) * jnp.dtype(m.dtype).itemsize
    return total
