"""AdamW op dispatcher: Pallas fused kernel on TPU, jnp oracle elsewhere."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.adamw import kernel as K
from repro.kernels.adamw import ref


def adamw_update(p, g, m, v, *, lr, b1, b2, eps, wd, t):
    if jax.default_backend() == "tpu":
        return adamw_update_pallas(p, g, m, v, lr=lr, b1=b1, b2=b2, eps=eps,
                                   wd=wd, t=t)
    return ref.adamw_update(p, g, m, v, lr=lr, b1=b1, b2=b2, eps=eps, wd=wd,
                            t=t)


def adamw_update_pallas(p, g, m, v, *, lr, b1, b2, eps, wd, t,
                        interpret: bool = False):
    shape = p.shape
    n = p.size
    pad = (-n) % K.BLOCK

    def flat(x):
        f = x.reshape(-1).astype(jnp.float32) if x.dtype != p.dtype \
            else x.reshape(-1)
        return jnp.pad(f, (0, pad)) if pad else f

    t = jnp.asarray(t, jnp.float32)
    scalars = jnp.stack([jnp.asarray(lr, jnp.float32),
                         1 - b1 ** t, 1 - b2 ** t])
    po, mo, vo = K.adamw_flat(flat(p), flat(g).astype(p.dtype),
                              flat(m), flat(v), scalars,
                              b1=b1, b2=b2, eps=eps, wd=wd,
                              interpret=interpret)
    unflat = lambda x: x[:n].reshape(shape)
    return unflat(po), unflat(mo), unflat(vo)
