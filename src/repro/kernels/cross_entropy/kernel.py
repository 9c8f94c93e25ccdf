"""Streaming cross-entropy Pallas kernel.

At 256k vocab the logits row (1 MiB fp32 per token) dominates the LM head's
memory traffic; materializing softmax doubles it. This kernel streams vocab
blocks through VMEM keeping only running (max, sumexp, target-logit)
accumulators — one pass for the loss, one fused pass for dlogits.

Grid (row_blocks, vocab_blocks); vocab dim sequential so the scratch
accumulators carry; loss written on the last vocab step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


ROW_BLOCK = 8
V_BLOCK = 2048
NEG = -1e30


def _xent_kernel(x_ref, t_ref, loss_ref, lse_ref, m_ref, s_ref, tl_ref, *,
                 vocab, n_v):
    jv = pl.program_id(1)

    @pl.when(jv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        s_ref[...] = jnp.zeros_like(s_ref)
        tl_ref[...] = jnp.zeros_like(tl_ref)

    x = x_ref[...].astype(jnp.float32)            # (R, Vb)
    col = jv * V_BLOCK + jax.lax.broadcasted_iota(
        jnp.int32, (ROW_BLOCK, V_BLOCK), 1)
    x = jnp.where(col < vocab, x, NEG)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, x.max(-1))
    corr = jnp.exp(m_prev - m_new)
    s_ref[...] = s_ref[...] * corr + jnp.exp(x - m_new[:, None]).sum(-1)
    m_ref[...] = m_new
    t = t_ref[...]                                 # (R,)
    hit = (col == t[:, None])
    tl_ref[...] = tl_ref[...] + jnp.where(hit, x, 0.0).sum(-1)

    @pl.when(jv == n_v - 1)
    def _finish():
        lse = jnp.log(jnp.maximum(s_ref[...], 1e-30)) + m_ref[...]
        lse_ref[...] = lse
        loss_ref[...] = lse - tl_ref[...]


def xent_fwd(logits, targets, vocab: int | None = None,
             interpret: bool = False):
    """logits (R, V) with R % ROW_BLOCK == 0; V padded to V_BLOCK outside.
    `vocab` = real (unpadded) vocab width; padding columns are masked."""
    R, V = logits.shape
    n_v = V // V_BLOCK
    kern = functools.partial(_xent_kernel, vocab=vocab or V, n_v=n_v)
    return pl.pallas_call(
        kern,
        out_shape=[jax.ShapeDtypeStruct((R,), jnp.float32),
                   jax.ShapeDtypeStruct((R,), jnp.float32)],
        grid=(R // ROW_BLOCK, n_v),
        in_specs=[
            pl.BlockSpec((ROW_BLOCK, V_BLOCK), lambda i, j: (i, j)),
            pl.BlockSpec((ROW_BLOCK,), lambda i, j: (i,)),
        ],
        out_specs=[pl.BlockSpec((ROW_BLOCK,), lambda i, j: (i,)),
                   pl.BlockSpec((ROW_BLOCK,), lambda i, j: (i,))],
        scratch_shapes=[pltpu.VMEM((ROW_BLOCK,), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(logits, targets)


def _dx_kernel(x_ref, t_ref, lse_ref, g_ref, dx_ref):
    jv = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    p = jnp.exp(x - lse_ref[...][:, None])
    col = jv * V_BLOCK + jax.lax.broadcasted_iota(
        jnp.int32, (ROW_BLOCK, V_BLOCK), 1)
    onehot = (col == t_ref[...][:, None]).astype(jnp.float32)
    dx_ref[...] = ((p - onehot) * g_ref[...][:, None]).astype(dx_ref.dtype)


def xent_bwd(logits, targets, lse, g, interpret: bool = False):
    R, V = logits.shape
    return pl.pallas_call(
        _dx_kernel,
        out_shape=jax.ShapeDtypeStruct((R, V), logits.dtype),
        grid=(R // ROW_BLOCK, V // V_BLOCK),
        in_specs=[
            pl.BlockSpec((ROW_BLOCK, V_BLOCK), lambda i, j: (i, j)),
            pl.BlockSpec((ROW_BLOCK,), lambda i, j: (i,)),
            pl.BlockSpec((ROW_BLOCK,), lambda i, j: (i,)),
            pl.BlockSpec((ROW_BLOCK,), lambda i, j: (i,)),
        ],
        out_specs=pl.BlockSpec((ROW_BLOCK, V_BLOCK), lambda i, j: (i, j)),
        interpret=interpret,
    )(logits, targets, lse, g)
