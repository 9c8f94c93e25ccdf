"""Fused AdamW Pallas kernel: param, grad, m, v in one HBM pass, in place.

The optimizer reads every parameter's p, g, m, v and writes p, m, v once.
On a TPU that pass is bound by the fixed cost of each grid step (about a
third of a microsecond) while a step moves a few KiB, and by HBM bandwidth
once it moves half a MiB or more; so the kernel is planned per leaf by
`block_plan`:

- The view: the leaf as it is stored, seen in 3-D (leading, rows,
  columns) without a relayout. A leaf whose second-minor dim is 1 (or a
  1-D leaf) is linear in memory and is seen as rows of 128 lanes; any other
  leaf keeps its last two dims, which the TPU tiles, and stacks the rest.
  Only a leaf neither view fits (its element count or last dim not a
  multiple of 128) is flattened and padded, and the padding is sliced off
  again before the result is returned.
- The block: about `BLOCK_BYTES` of f32 per operand, or the whole leaf
  where that is smaller; a block that does not divide the view leaves a
  partial last block, whose out-of-range part Pallas never writes back.
  The body updates the whole block at once; the VMEM its f32 temporaries
  take is counted in the plan.
- p, m and v are aliased to their results, so the step, which donates its
  state, updates them in place.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.vmautil import pallas_operands

LANES = 128
SUBLANES = 8
# f32 bytes per operand per grid step: the smallest block that ran as fast
# as any in a sweep of 0.5-4 MiB on a TPU v5e (`sweep.py`, PERF.md)
BLOCK_BYTES = 512 << 10
# block-sized f32 temporaries of the body that Mosaic keeps in VMEM: up to
# 4.5 for a bf16 leaf in its scoped allocation for a TPU v5e
BODY_TEMPS = 5
# VMEM the kernel may use beyond its blocks and temporaries
VMEM_HEADROOM = 1 << 20


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """How the kernel walks one leaf: the 3-D `view` of it (after `pad`
    elements appended to the flattened leaf, 0 for every storage leaf),
    the 2-D `block` of one leading index that a grid step moves per
    operand, the `grid`, and the VMEM the double-buffered blocks and the
    body's temporaries take (`vmem_bytes`) under the limit the kernel
    sets."""
    view: tuple[int, int, int]
    pad: int
    block: tuple[int, int]
    grid: tuple[int, int, int]
    vmem_bytes: int
    vmem_limit: int

    @property
    def steps(self) -> int:
        return math.prod(self.grid)


def _view(shape) -> tuple[tuple[int, int, int], int]:
    # a TPU tiles a second-minor dim of 1 as (1, 128) and a 1-D array in
    # runs of 1024, so such a leaf is linear in memory, as (n/128, 128)
    # rows tiled (8, 128) are; other leaves are tiled on their last two dims
    n = math.prod(shape)
    linear = len(shape) < 2 or shape[-2] == 1
    if linear and n % LANES == 0:
        return (1, n // LANES, LANES), 0
    if not linear and shape[-1] % LANES == 0:
        return (n // shape[-2] // shape[-1], *shape[-2:]), 0
    rows = -(-n // LANES)
    return (1, rows, LANES), rows * LANES - n


def block_plan(shape, dtype, block_bytes: int = BLOCK_BYTES) -> BlockPlan:
    """The plan for a leaf of `shape` whose p, m and v are `dtype` (the
    gradient is counted as f32, the widest it arrives in), in blocks of
    about `block_bytes` of f32 per operand."""
    (lead, rows, cols), pad = _view(tuple(shape))
    elems = block_bytes // 4
    # rows of a block as VMEM holds them: padded to the sublane tile
    sub = min(rows, SUBLANES)
    if sub * cols <= elems:
        bc = cols
        br = rows if rows <= SUBLANES else min(
            rows, max(SUBLANES, elems // cols // SUBLANES * SUBLANES))
    else:
        br = sub
        bc = min(cols, max(LANES, elems // SUBLANES // LANES * LANES))
    item = jnp.dtype(dtype).itemsize
    held = -(-br // SUBLANES) * SUBLANES * bc
    vmem = held * (2 * (6 * item + 4) + BODY_TEMPS * 4)
    return BlockPlan(view=(lead, rows, cols), pad=pad, block=(br, bc),
                     grid=(lead, -(-rows // br), -(-cols // bc)),
                     vmem_bytes=vmem, vmem_limit=vmem + VMEM_HEADROOM)


def _adamw_kernel(s_ref, p_ref, g_ref, m_ref, v_ref, po_ref, mo_ref, vo_ref,
                  *, b1, b2, eps, wd):
    lr, bc1, bc2, scale = s_ref[0], s_ref[1], s_ref[2], s_ref[3]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32) * scale
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat = m / bc1
    vhat = v / bc2
    p = p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)
    po_ref[...] = p.astype(po_ref.dtype)
    mo_ref[...] = m.astype(mo_ref.dtype)
    vo_ref[...] = v.astype(vo_ref.dtype)


def adamw_blocks(p, g, m, v, scalars, plan: BlockPlan, *, b1, b2, eps, wd,
                 interpret: bool = False):
    """p, g, m, v in the shape `plan.view`; `scalars` is the f32 (4,)
    array (lr, 1 - b1**t, 1 - b2**t, clip scale). The bias corrections
    arrive precomputed because Mosaic has no lowering for a power with a
    traced exponent; the gradient is scaled in f32 inside the kernel.
    Returns p, m, v updated, in their own dtypes and buffers."""
    kern = functools.partial(_adamw_kernel, b1=b1, b2=b2, eps=eps, wd=wd)
    scalars, p, g, m, v, vma = pallas_operands(scalars, p, g, m, v)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma)
                 for x in (p, m, v)]
    blk = pl.BlockSpec((None, *plan.block), lambda k, i, j: (k, i, j))
    return pl.pallas_call(
        kern,
        out_shape=out_shape,
        grid=plan.grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), blk, blk, blk, blk],
        out_specs=[blk, blk, blk],
        input_output_aliases={1: 0, 3: 1, 4: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=plan.vmem_limit),
        interpret=interpret,
        name="adamw",
    )(scalars, p, g, m, v)
