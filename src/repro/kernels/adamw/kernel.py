"""Fused AdamW Pallas kernel: param, grad, m, v in one HBM pass.

The optimizer touches every parameter byte x4 reads + x3 writes; unfused XLA
on CPU/older compilers can issue these as several kernels. Fusing gives a
pure memory-bound single pass — the optimizer step's memory roofline term.

Tiling: everything is flat ZeRO-shard data; tile 1-D in (8*128)-element
blocks (fp32 vreg-aligned). ops.py pads to the block multiple.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.vmautil import pallas_operands

BLOCK = 8 * 128


def _adamw_kernel(p_ref, g_ref, m_ref, v_ref, s_ref,
                  po_ref, mo_ref, vo_ref, *, b1, b2, eps, wd):
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    lr, bc1, bc2 = s_ref[0], s_ref[1], s_ref[2]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat = m / bc1
    vhat = v / bc2
    p = p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)
    po_ref[...] = p.astype(po_ref.dtype)
    mo_ref[...] = m.astype(mo_ref.dtype)
    vo_ref[...] = v.astype(vo_ref.dtype)


def adamw_flat(p, g, m, v, scalars, *, b1, b2, eps, wd,
               interpret: bool = False):
    """All inputs flat (N,) with N % BLOCK == 0; `scalars` is the f32 (3,)
    array (lr, 1 - b1**t, 1 - b2**t). The bias corrections arrive
    precomputed because Mosaic has no lowering for a power with a traced
    exponent."""
    n = p.shape[0]
    kern = functools.partial(_adamw_kernel, b1=b1, b2=b2, eps=eps, wd=wd)
    p, g, m, v, scalars, vma = pallas_operands(p, g, m, v, scalars)
    out_shape = [jax.ShapeDtypeStruct((n,), p.dtype, vma=vma)] * 3
    blk = pl.BlockSpec((BLOCK,), lambda i: (i,))
    return pl.pallas_call(
        kern,
        out_shape=out_shape,
        grid=(n // BLOCK,),
        in_specs=[blk, blk, blk, blk, pl.BlockSpec((3,), lambda i: (0,))],
        out_specs=[blk, blk, blk],
        interpret=interpret,
        name="adamw",
    )(p, g, m, v, scalars)
