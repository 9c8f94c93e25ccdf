"""Pallas TPU RMSNorm: one pass over rows, fp32 accumulation in VMEM.

Tiling: rows x D blocks of (ROW_BLOCK, D). D (model dim) stays whole per
block — for every assigned arch D <= 7168, so a (8, 7168) fp32 block is
~229 KiB, far under the ~128 MiB VMEM budget, and keeps the reduction
lane-local. Row count is padded to a multiple of ROW_BLOCK by `ops`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.vmautil import pallas_operands

ROW_BLOCK = 8


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float, unit_offset: bool):
    x = x_ref[...].astype(jnp.float32)            # (ROW_BLOCK, D)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    w = w_ref[...].astype(jnp.float32)
    scale = w + 1.0 if unit_offset else w
    o_ref[...] = (x * inv * scale[None, :]).astype(o_ref.dtype)


def rmsnorm_fwd(x2d: jax.Array, w: jax.Array, eps: float,
                unit_offset: bool, interpret: bool = False) -> jax.Array:
    rows, d = x2d.shape
    assert rows % ROW_BLOCK == 0, "ops.py pads rows"
    kernel = functools.partial(_rmsnorm_kernel, eps=eps,
                               unit_offset=unit_offset)
    x2d, w, vma = pallas_operands(x2d, w)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, d), x2d.dtype, vma=vma),
        grid=(rows // ROW_BLOCK,),
        in_specs=[
            pl.BlockSpec((ROW_BLOCK, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((ROW_BLOCK, d), lambda i: (i, 0)),
        interpret=interpret,
        name="rmsnorm",
    )(x2d, w)
