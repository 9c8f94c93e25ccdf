"""ZeRO-sharded AdamW.

Because parameters live as flat local shards (core/meta.py), the optimizer is
trivially ZeRO-3: moments are allocated per-shard and the update is purely
elementwise on local data — no optimizer-state collectives, ever. Global-norm
clipping needs one scalar psum per vma class (TP-sharded leaves are summed
over the model axis; TP-replicated leaves are counted once).

The elementwise update dispatches to the fused Pallas kernel on TPU
(kernels/adamw) and to the jnp reference elsewhere.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.dist import DistConfig
from repro.core.meta import ParamMeta


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0


def init_opt_state(storage_tree, cfg: DistConfig | None = None):
    """Fresh moments (+ the error-feedback accumulator when the config's
    comm_precision carries one — `DistConfig.needs_ef`).  The EF residual is
    strictly smaller than one quantization step, so it lives in float32
    regardless of the param dtype; it is storage-shaped like m/v (ZeRO-3:
    per-shard, no optimizer-state collectives)."""
    zeros = lambda p: jnp.zeros_like(p)
    state = {
        "m": jax.tree.map(zeros, storage_tree),
        "v": jax.tree.map(zeros, storage_tree),
        "step": jnp.zeros((), jnp.int32),
    }
    if cfg is not None and cfg.needs_ef:
        state["ef"] = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), storage_tree)
    return state


def _leaf_metas(metas_tree):
    return jax.tree_util.tree_flatten(
        metas_tree, is_leaf=lambda x: isinstance(x, ParamMeta))[0]


def global_grad_norm(grads_tree, metas_tree, cfg: DistConfig,
                     pp_replicated: tuple[str, ...] = ()):
    """sqrt(sum of squares over every distinct gradient element).

    `pp_replicated` names top-level groups replicated across pipeline
    stages (StageSpec.replicated_keys): after the pipe-axis grad psum every
    stage holds the SAME values, so their squares are scaled by 1/pp_size
    to count each element once under the pipe-axis psum below."""
    tp_sq = jnp.zeros((), jnp.float32)
    rep_sq = jnp.zeros((), jnp.float32)
    for k in sorted(grads_tree):   # match jax dict-key flatten order
        w = 1.0 / cfg.pp_size if k in pp_replicated else 1.0
        for g, m in zip(jax.tree.leaves(grads_tree[k]),
                        _leaf_metas(metas_tree[k])):
            s = jnp.sum(g.astype(jnp.float32) ** 2) * w
            if m.tp_dim is not None:
                tp_sq = tp_sq + s
            else:
                rep_sq = rep_sq + s
    # shards are distinct across fsdp axes -> always psum there;
    # tp-sharded leaves are also distinct across the model axis.
    total = lax.psum(rep_sq, cfg.fsdp_axes) \
        + lax.psum(tp_sq, (*cfg.fsdp_axes, cfg.tp_axis))
    if cfg.pp_axis is not None:
        # each pipe rank holds a distinct stage: the global norm (and hence
        # the clip scale, which must agree across stages) spans all of them
        total = lax.psum(total, cfg.pp_axis)
    return jnp.sqrt(total)


def _update_leaf(p, g, m, v, lr, ocfg: AdamWConfig, t, scale):
    from repro.kernels.adamw import ops as adamw_ops
    return adamw_ops.adamw_update(p, g, m, v, lr=lr, b1=ocfg.b1, b2=ocfg.b2,
                                  eps=ocfg.eps, wd=ocfg.weight_decay, t=t,
                                  scale=scale)


def _error_feedback(grads, ef):
    """Quantize-compensate hop (QSGD/EF14 style): the shard-local reduced
    gradient is pushed through the SAME fp8 wire codec the quantized
    reduce-scatter uses, with the rounding residual carried to the next
    step.  `g2 = g + ef; gq = dq(q(g2)); ef' = g2 - gq` — deterministic RTN
    here (EF compensates the bias; the in-collective hop is the stochastic
    one).  Applied uniformly whenever the state carries "ef"
    (comm_precision in {"fp8_ef", "auto"}): the step function and the state
    tree must not depend on the per-block traced plan."""
    from repro.kernels.quant import ops as quant_ops

    def one(g, e):
        g2 = g.astype(jnp.float32) + e
        gq = quant_ops.roundtrip(g2, "fp8", stochastic=False)
        return gq, g2 - gq

    out = jax.tree.map(one, grads, ef)
    gq = jax.tree.map(lambda o: o[0], out,
                      is_leaf=lambda x: isinstance(x, tuple))
    new_ef = jax.tree.map(lambda o: o[1], out,
                          is_leaf=lambda x: isinstance(x, tuple))
    return gq, new_ef


@jax.named_scope("optimizer")              # core/obs.SCOPES
def apply_adamw(storage, grads, opt_state, metas_tree, cfg: DistConfig,
                ocfg: AdamWConfig, lr, pp_replicated: tuple[str, ...] = ()):
    """One AdamW step on the sharded storage. Returns (params, opt_state,
    grad_norm)."""
    t = opt_state["step"] + 1
    new_ef = None
    if "ef" in opt_state:
        grads, new_ef = _error_feedback(grads, opt_state["ef"])
    gnorm = global_grad_norm(grads, metas_tree, cfg, pp_replicated)
    scale = jnp.minimum(1.0, ocfg.grad_clip / jnp.maximum(gnorm, 1e-12)) \
        if ocfg.grad_clip else 1.0

    def upd(p, g, m, v):
        # the clip scale is applied to the f32 gradient inside the update
        return _update_leaf(p, g, m, v, lr, ocfg, t, scale)

    out = jax.tree.map(upd, storage, grads, opt_state["m"], opt_state["v"])
    new_p = jax.tree.map(lambda o: o[0], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    new_m = jax.tree.map(lambda o: o[1], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    new_v = jax.tree.map(lambda o: o[2], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    new_state = {"m": new_m, "v": new_v, "step": t}
    if new_ef is not None:
        new_state["ef"] = new_ef
    return new_p, new_state, gnorm
