"""AdamW op dispatcher: Pallas fused kernel on TPU, jnp oracle elsewhere."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.adamw import kernel as K
from repro.kernels.adamw import ref


def adamw_update(p, g, m, v, *, lr, b1, b2, eps, wd, t, scale=1.0):
    """One AdamW step of a leaf, the gradient first multiplied by the clip
    `scale` in f32. Returns (p, m, v)."""
    if jax.default_backend() == "tpu":
        return adamw_update_pallas(p, g, m, v, lr=lr, b1=b1, b2=b2, eps=eps,
                                   wd=wd, t=t, scale=scale)
    return ref.adamw_update(p, g.astype(jnp.float32) * scale, m, v, lr=lr,
                            b1=b1, b2=b2, eps=eps, wd=wd, t=t)


def adamw_update_pallas(p, g, m, v, *, lr, b1, b2, eps, wd, t, scale=1.0,
                        interpret: bool = False):
    """The fused kernel over the leaf as `K.block_plan` views it: storage
    leaves are reshaped to a 3-D view of the same bytes (no relayout) and
    updated in place; any other leaf is flattened and padded, and the
    padding is dropped from the results."""
    plan = K.block_plan(p.shape, p.dtype)

    def view(x):
        if plan.pad:
            x = jnp.pad(x.reshape(-1), (0, plan.pad))
        return x.reshape(plan.view)

    def unview(x):
        if plan.pad:
            x = x.reshape(-1)[:p.size]
        return x.reshape(p.shape)

    t = jnp.asarray(t, jnp.float32)
    scalars = jnp.stack([jnp.asarray(lr, jnp.float32), 1 - b1 ** t,
                         1 - b2 ** t, jnp.asarray(scale, jnp.float32)])
    out = K.adamw_blocks(view(p), view(g), view(m), view(v), scalars, plan,
                         b1=b1, b2=b2, eps=eps, wd=wd, interpret=interpret)
    return tuple(unview(x) for x in out)
