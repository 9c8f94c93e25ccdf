"""Flash attention (forward) Pallas TPU kernel.

Online-softmax over KV blocks with VMEM accumulators. Grid is
(batch*heads, q_blocks, kv_blocks); the kv dimension is sequential
("arbitrary") so the fp32 accumulator/max/sum scratch persists across kv
steps — the canonical TPU flash schedule. Blocks are MXU-aligned
(Q_BLOCK x head_dim and KV_BLOCK x head_dim with 128 defaults).

Variants (static): causal masking, sliding window (gemma2 local layers),
logit softcap. GQA is handled by the ops wrapper (q heads grouped to their
kv head before the kernel sees a plain (BH, S, hd) problem).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


Q_BLOCK = 128
KV_BLOCK = 128
NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  causal, window, softcap, scale, kv_len, n_kv):
    jq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (Qb, hd)
    k = k_ref[0].astype(jnp.float32)                  # (Kb, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (Qb, Kb)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = jq * Q_BLOCK + jax.lax.broadcasted_iota(jnp.int32,
                                                    (Q_BLOCK, KV_BLOCK), 0)
    k_pos = jk * KV_BLOCK + jax.lax.broadcasted_iota(jnp.int32,
                                                     (Q_BLOCK, KV_BLOCK), 1)
    mask = k_pos < kv_len
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())))
    m_ref[...] = m_new

    @pl.when(jk == n_kv - 1)
    def _finish():
        o_ref[0, ...] = (acc_ref[...]
                         / jnp.maximum(l_ref[...], 1e-30)[:, None]
                         ).astype(o_ref.dtype)


def flash_fwd(q, k, v, *, causal=True, window=None, softcap=None,
              q_scale=None, interpret: bool = False):
    """q: (BH, Sq, hd); k/v: (BH, Skv, hd). Sq % Q_BLOCK == 0,
    Skv padded to KV_BLOCK by the caller; kv_len masks the padding."""
    BH, Sq, hd = q.shape
    Skv = k.shape[1]
    assert Sq % Q_BLOCK == 0 and Skv % KV_BLOCK == 0
    n_q = Sq // Q_BLOCK
    n_kv = Skv // KV_BLOCK
    scale = q_scale if q_scale is not None else 1.0 / math.sqrt(hd)
    kern = functools.partial(
        _flash_kernel, causal=causal, window=window, softcap=softcap,
        scale=scale, kv_len=Skv, n_kv=n_kv)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        grid=(BH, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, Q_BLOCK, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, KV_BLOCK, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, KV_BLOCK, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q_BLOCK, hd), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((Q_BLOCK, hd), jnp.float32),   # acc
            pltpu.VMEM((Q_BLOCK,), jnp.float32),      # running max
            pltpu.VMEM((Q_BLOCK,), jnp.float32),      # running sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
