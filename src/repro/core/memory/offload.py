"""Host offload: the device<->host channel the memory planner can spend.

Two offloadable stores (chosen by `core/memory/planner.plan_memory`):

  * optimizer state (AdamW m/v) — cold between steps, 2x the master param
    bytes; round-trips host once per step;
  * segment-boundary residuals — the per-layer saved block inputs; streamed
    out during forward and prefetched back double-buffered during backward,
    so only the spill over a layer's compute time is exposed (the cost
    model in planner._offload_cost_s).

Where the backend exposes host DRAM as the ``pinned_host`` memory kind
(TPU, and the CPU backend of current JAX) these helpers place arrays there
for real, and a transfer that fails raises. Only a backend without that
memory kind gets identity, so the plan's offload is then modeled and not
executed.
"""

from __future__ import annotations

import functools

import jax
from jax.sharding import SingleDeviceSharding

HOST_MEMORY_KIND = "pinned_host"
DEVICE_MEMORY_KIND = "device"


@functools.lru_cache(maxsize=1)
def host_offload_supported() -> bool:
    """True when the backend exposes a pinned_host memory space."""
    return any(m.kind == HOST_MEMORY_KIND
               for m in jax.devices()[0].addressable_memories())


def _transfer(tree, kind: str):
    if not host_offload_supported():
        return tree
    sh = SingleDeviceSharding(jax.devices()[0], memory_kind=kind)
    return jax.tree.map(lambda x: jax.device_put(x, sh), tree)


def to_host(tree):
    """Move a pytree to pinned host memory (identity when unsupported)."""
    return _transfer(tree, HOST_MEMORY_KIND)


def to_device(tree):
    """Move a pytree back to device HBM (identity when unsupported)."""
    return _transfer(tree, DEVICE_MEMORY_KIND)
