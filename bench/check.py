"""How `correct` is decided for a training cell.

The program's first three steps (made in set-up through the window's own
step and feed) are read as: each step's loss; the first gradient as the
optimizer got it, m / (1 - b1) from the state after step 1; and the change
of the parameters after three steps. The plain reference follows the same
three steps from the same weights and batches. Each of the three is reduced
to one number, the widest gap, and held to the cell's limit:

  loss_gap    max over steps of |loss - ref| / |ref|
  grad_gap    max over leaves of |norm - ref norm| / max(ref norm, median)
  update_gap  the same for the norm of each leaf's change

A leaf is one weight of one layer (a stacked leaf counts once per layer).
Gaps of norms are measured against the leaf's reference norm or the median
leaf's, whichever is larger, since some gradients are all but zero. Leaves
whose reference gradient is under a thousandth of the median leaf's move by
round-off alone under Adam, and are left out of update_gap.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

STEPS = 3
TINY = 1e-3


def leaf_norms(tree) -> dict:
    """{path: per-layer norms (L,) for layer-stacked leaves, else a scalar},
    as device arrays. Zero padding of a storage layout adds nothing."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path, simple=True, separator="/")
        x = x.astype(jnp.float32)
        axes = tuple(range(1, x.ndim)) if name.startswith("blocks/") \
            else tuple(range(x.ndim))
        out[name] = jnp.sqrt(jnp.sum(x * x, axis=axes))
    return out


def flat_leaves(norms: dict) -> dict[str, float]:
    """Per-layer entries as 'name#layer'."""
    out = {}
    for name, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim:
            out.update({f"{name}#{i}": float(x) for i, x in enumerate(v)})
        else:
            out[name] = float(v)
    return out


def _norm_gap(prog: dict, ref: dict, keep) -> float:
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def readings(prog: dict, ref: dict) -> dict[str, float]:
    """The three compared numbers, from two records of the form
    {"loss": [3 floats], "grad": {leaf: norm}, "update": {leaf: norm}}."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))
    g = ref["grad"]
    med = float(np.median(list(g.values())))
    moving = [k for k in g if g[k] >= TINY * med]
    vals = [loss, _norm_gap(prog["grad"], g, list(g)),
            _norm_gap(prog["update"], ref["update"], moving)]
    # a NaN anywhere fails every limit
    vals = [v if math.isfinite(v) else math.inf for v in vals]
    return dict(zip(("loss_gap", "grad_gap", "update_gap"), vals))


def verdict(values: dict[str, float], limits: dict[str, float]) -> bool:
    return all(values[k] <= limits[k] for k in limits)


# ------------------------------------------------------------ reference --
def _spec(shape, n):
    """Shard a leaf's largest axis that the devices divide (the layer axis
    of a stacked leaf last), else keep it whole."""
    dims = sorted(range(len(shape)), key=lambda i: (i == 0 and len(shape) > 2,
                                                    -shape[i]))
    for i in dims:
        if n > 1 and shape[i] % n == 0:
            return P(*[("x" if j == i else None) for j in range(len(shape))])
    return P()


def fp8_matmul(spec, a, b):
    """An einsum whose operands, forward and backward, are rounded to
    float8_e4m3fn under a per-tensor scale: the control's arithmetic."""
    def q(x):
        s = lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s

    @jax.custom_vjp
    def mm(x, y):
        return jnp.einsum(spec, q(x), q(y), precision=lax.Precision.HIGHEST)

    def fwd(x, y):
        qx, qy = q(x), q(y)
        return jnp.einsum(spec, qx, qy,
                          precision=lax.Precision.HIGHEST), (qx, qy)

    def bwd(res, ct):
        _, f = jax.vjp(lambda x, y: jnp.einsum(
            spec, x, y, precision=lax.Precision.HIGHEST), *res)
        return f(q(ct))

    mm.defvjp(fwd, bwd)
    return mm(a, b)


def reference_record(ref, arch: dict, opt: dict, key_data, batches,
                     devices, matmul=None, steps: int = STEPS) -> dict:
    """The reference's three steps from the seed's weights on `batches`,
    sharded over `devices` so that the state of a model no chip holds
    fits. Returns the record `readings` compares."""
    mesh = Mesh(np.array(devices), ("x",))
    n = len(devices)
    shapes = ref.param_shapes(arch)
    is_shape = lambda x: isinstance(x, tuple)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, _spec(s, n)), shapes,
                       is_leaf=is_shape)
    bsh = NamedSharding(mesh, P("x") if batches[0]["tokens"].shape[0] % n
                        == 0 else P())
    make = lambda kd: ref.init_params(arch, jax.random.wrap_key_data(kd))
    with jax.default_matmul_precision("highest"):
        params = jax.jit(make, out_shardings=psh)(key_data)
        zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                        out_shardings=psh)
        m, v = zeros(params), zeros(params)

        def one(params, m, v, t, batch):
            loss, grads = jax.value_and_grad(
                lambda p: ref.loss_fn(arch, p, batch, matmul))(params)
            params, m, v, g = ref.adamw(opt, params, grads, m, v, t)
            return params, m, v, loss, leaf_norms(g)

        step = jax.jit(one, donate_argnums=(0, 1, 2),
                       out_shardings=(psh, psh, psh, None, None))
        loss, grad = [], None
        for t in range(steps):
            batch = jax.device_put(batches[t], bsh)
            params, m, v, lt, gn = step(params, m, v, jnp.int32(t), batch)
            loss.append(float(lt))
            if t == 0:
                grad = flat_leaves(gn)
        del m, v
        delta = jax.jit(lambda p, kd: leaf_norms(jax.tree.map(
            jnp.subtract, p, make(kd))))(params, key_data)
        return {"loss": loss, "grad": grad, "update": flat_leaves(delta)}
