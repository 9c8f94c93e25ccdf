"""Mamba-2 SSD chunk kernel (Pallas TPU).

One grid step processes one (batch*head, chunk) cell: the intra-chunk
quadratic term (Lc x Lc decay-masked CB^T), the inter-chunk contribution of
the carried state, and the state update — state lives in VMEM scratch and
carries across the sequential chunk dimension (same schedule as the flash
kernel's kv dim). Mirrors kernels/ssd/ref.ssd_chunked for ngroups folded to
per-head B/C (the ops wrapper pre-broadcasts groups).

Layout per (BH) slice: x (nC, Lc, P), dt (nC, Lc), B/C (nC, Lc, N).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


CHUNK = 128


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, s_ref, *,
                n_chunks):
    jc = pl.program_id(1)

    @pl.when(jc == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    A = a_ref[0]                                    # scalar decay rate (neg)
    dt = dt_ref[0, 0].astype(jnp.float32)           # (Lc,)
    x = x_ref[0, 0].astype(jnp.float32)             # (Lc, P)
    Bm = b_ref[0, 0].astype(jnp.float32)            # (Lc, N)
    Cm = c_ref[0, 0].astype(jnp.float32)            # (Lc, N)
    Lc = dt.shape[0]

    dA = dt * A                                     # (Lc,)
    cum = jnp.cumsum(dA)                            # inclusive
    dec = cum[:, None] - cum[None, :]               # (Lc, Lc)
    tri = jax.lax.broadcasted_iota(jnp.int32, (Lc, Lc), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (Lc, Lc), 1)
    L = jnp.where(tri, jnp.exp(dec), 0.0)
    xw = x * dt[:, None]                            # dt-weighted input
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # (Lc, Lc)
    y = jax.lax.dot_general(CB * L, xw, (((1,), (0,)), ((), ())))
    # inter-chunk
    S_in = s_ref[...]                               # (P, N)
    y += jnp.exp(cum)[:, None] * jax.lax.dot_general(
        Cm, S_in, (((1,), (1,)), ((), ())))
    y_ref[0, 0, ...] = y.astype(y_ref.dtype)
    # state update
    w = jnp.exp(cum[-1] - cum)                      # (Lc,)
    s_ref[...] = jnp.exp(cum[-1]) * S_in + jax.lax.dot_general(
        xw * w[:, None], Bm, (((0,), (0,)), ((), ())))


def ssd_fwd(A, x, dt, Bm, Cm, interpret: bool = False):
    """A (BH,); x (BH, nC, Lc, P); dt (BH, nC, Lc); Bm/Cm (BH, nC, Lc, N).
    Returns y (BH, nC, Lc, P)."""
    BH, nC, Lc, P = x.shape
    N = Bm.shape[-1]
    kern = functools.partial(_ssd_kernel, n_chunks=nC)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((BH, nC, Lc, P), x.dtype),
        grid=(BH, nC),
        in_specs=[
            pl.BlockSpec((1,), lambda b, c: (b,)),
            pl.BlockSpec((1, 1, Lc, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Lc), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, Lc, N), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Lc, N), lambda b, c: (b, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Lc, P), lambda b, c: (b, c, 0, 0)),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(A, x, dt, Bm, Cm)
