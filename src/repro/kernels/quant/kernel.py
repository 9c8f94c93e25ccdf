"""Pallas TPU quantize/dequantize over the (n_chunks, QCHUNK) chunk view.

Tiling: row blocks of (ROW_BLOCK, QCHUNK) = (8, 128) — one fp32 block is
4 KiB, the scale reduction is lane-local, and QCHUNK equals the flat-shard
storage LANE so bucket buffers chunk without reshuffling.  Scales are a
(n_chunks, 1) f32 output blocked (ROW_BLOCK, 1).  The stochastic-rounding
dither seed arrives as a (1, 1) u32 operand (it is traced — derived from
the buffer's own bits by ops.py); per-element dither indices come from
2-D broadcasted iotas offset by the grid position.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.vmautil import pallas_operands
from repro.kernels.quant import ref

ROW_BLOCK = 8
QCHUNK = ref.QCHUNK


def _quant_kernel(x_ref, seed_ref, q_ref, s_ref, *, codec: str,
                  stochastic: bool):
    x = x_ref[...].astype(jnp.float32)           # (ROW_BLOCK, QCHUNK)
    scale = ref.chunk_scales(x, codec)
    qmax = ref.QMAX[codec]
    y = jnp.clip(x / scale, -qmax, qmax)
    if stochastic:
        row0 = (pl.program_id(0) * ROW_BLOCK).astype(jnp.uint32)
        r = jax.lax.broadcasted_iota(jnp.uint32, x.shape, 0) + row0
        c = jax.lax.broadcasted_iota(jnp.uint32, x.shape, 1)
        h = ref.hash_u32(r * jnp.uint32(QCHUNK) + c, seed_ref[0, 0])
        q = ref.sr_fp8(y, h) if codec == "fp8" else ref.sr_int8(y, h)
    elif codec == "fp8":
        q = y.astype(jnp.float8_e4m3fn)
    else:
        q = jnp.clip(jnp.round(y), -127.0, 127.0).astype(jnp.int8)
    q_ref[...] = q
    s_ref[...] = scale


def quant_fwd(x2d: jax.Array, seed: jax.Array, codec: str,
              stochastic: bool, interpret: bool = False):
    """(m, QCHUNK) f32 -> ((m, QCHUNK) wire dtype, (m, 1) f32 scales).
    m must be a multiple of ROW_BLOCK (ops.py pads)."""
    m, d = x2d.shape
    assert d == QCHUNK and m % ROW_BLOCK == 0, "ops.py pads"
    kernel = functools.partial(_quant_kernel, codec=codec,
                               stochastic=stochastic)
    x2d, seed, vma = pallas_operands(x2d, seed.reshape(1, 1))
    return pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((m, d), ref.WIRE_DTYPE[codec], vma=vma),
            jax.ShapeDtypeStruct((m, 1), jnp.float32, vma=vma),
        ],
        grid=(m // ROW_BLOCK,),
        in_specs=[
            pl.BlockSpec((ROW_BLOCK, d), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((ROW_BLOCK, d), lambda i: (i, 0)),
            pl.BlockSpec((ROW_BLOCK, 1), lambda i: (i, 0)),
        ],
        interpret=interpret,
        name="quant",
    )(x2d, seed)


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = (q_ref[...].astype(jnp.float32)
                  * s_ref[...]).astype(o_ref.dtype)


def dequant_fwd(q: jax.Array, scales: jax.Array,
                interpret: bool = False) -> jax.Array:
    """Inverse pass: wire values + per-chunk scales -> f32 chunk view."""
    m, d = q.shape
    assert d == QCHUNK and m % ROW_BLOCK == 0, "ops.py pads"
    q, scales, vma = pallas_operands(q, scales)
    return pl.pallas_call(
        _dequant_kernel,
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32, vma=vma),
        grid=(m // ROW_BLOCK,),
        in_specs=[
            pl.BlockSpec((ROW_BLOCK, d), lambda i: (i, 0)),
            pl.BlockSpec((ROW_BLOCK, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ROW_BLOCK, d), lambda i: (i, 0)),
        interpret=interpret,
        name="dequant",
    )(q, scales)
