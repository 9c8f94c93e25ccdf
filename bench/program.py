"""The system under test, built from a configuration file the way a user
builds it (`launch/train.py`): `Trainer` -> `parallelize` -> the SimpleFSDP
train step. This is the one module of the benchmark that imports the
program; everything it hands back is the program's own objects.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp


def build(cfg: dict, traffic: dict, ckpt_dir: str):
    """A `Trainer` for the configuration `cfg` and the batch shape of
    `traffic`. The schedule's horizon and warm-up come from the file, so
    every run compiles the same step."""
    from repro.core.dist import DistConfig
    from repro.models.common import ShapeConfig
    from repro.models.registry import build_model, get_arch
    from repro.optim.adamw import AdamWConfig
    from repro.train.trainer import Trainer, TrainerConfig

    prog = cfg["program"]
    arch, _ = get_arch(prog["arch"])
    arch = dataclasses.replace(arch, **prog.get("replace", {}))
    dist = dict(cfg["dist"])
    for k in ("param_dtype", "reduce_dtype"):
        dist[k] = jnp.dtype(dist[k])
    dist["mesh_axes"] = tuple(dist["mesh_axes"])
    dist["mesh_shape"] = tuple(dist["mesh_shape"])
    dcfg = DistConfig(**dist)
    shape = ShapeConfig("train", traffic["seq_len"], traffic["global_batch"],
                        "train")
    opt = cfg["optimizer"]
    ocfg = AdamWConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                       eps=opt["eps"], weight_decay=opt["weight_decay"],
                       grad_clip=opt["grad_clip"])
    os.makedirs(ckpt_dir, exist_ok=True)
    tcfg = TrainerConfig(ckpt_dir=ckpt_dir, total_steps=opt["total_steps"],
                         warmup=opt["warmup"])
    return Trainer(build_model(arch), dcfg, shape, ocfg, tcfg)


def state_fn(trainer, make_params):
    """(key data -> train state) on the step's shardings: the plain weights
    that `make_params(key_data)` makes, laid out by the program's own
    plain-to-storage transform, with a fresh optimizer state."""
    from repro.core.api import shard_params
    from repro.optim.adamw import init_opt_state
    from repro.train.train_step import train_state_shardings

    par = trainer.par
    metas = trainer.model.metas(par.dcfg)

    def make(kd):
        storage = shard_params(make_params(kd), metas, par.dcfg)
        return storage, init_opt_state(storage, par.dcfg)

    return jax.jit(make, out_shardings=train_state_shardings(par))


def storage_fn(trainer, make_params):
    """(key data -> parameters in the storage layout), no optimizer state."""
    from repro.core.api import shard_params

    par = trainer.par
    metas = trainer.model.metas(par.dcfg)
    return lambda kd: shard_params(make_params(kd), metas, par.dcfg)


def batch_specs(trainer):
    return trainer.model.input_specs(trainer.shape, trainer.dcfg)


def kernel_name(op_name: str) -> str:
    """The Pallas kernel a `tpu_custom_call` belongs to, from its op_name
    metadata: autodiff wraps it, so transpose(jvp(rmsnorm)) is rmsnorm's."""
    import re

    parts = op_name.split("/")
    name = parts[-2] if len(parts) >= 2 else "?"
    return re.sub(r"^(\w+\()+|\)+$", "", name)


def feed(trainer, batch: dict, step: int, seed: int) -> dict:
    """A host batch fitted to the step's inputs, as `Trainer` feeds it."""
    from repro.data.pipeline import adapt_batch

    return adapt_batch(batch, batch_specs(trainer), step=step, seed=seed)
