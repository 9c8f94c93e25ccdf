"""Training step assembly: SimpleFSDP forward/backward + gradient
accumulation (microbatches) + clipping + AdamW + LR schedule, all inside one
shard_map'd jit — the "full computation-communication graph" the paper traces.

Two families, one front door (`wrap_any_train_step` / `wrap_loss_step`,
driven by `core/api.parallelize` off the resolved `ParallelPlan`):

  * pp = 1 — the whole-model step (`make_train_step`): microbatch scan +
    AdamW on the plain storage layout.
  * pp > 1 — the STAGED step (`make_staged_train_step`): storage is
    stage-stacked (models/staging.py), each pipe rank trains its stage
    slice through `core/pipeline`'s GPipe/1F1B schedules using the model's
    stage-partition contract (stage_pre / stage_blocks / stage_loss); the
    batch splits into `plan.microbatches` microbatches, stage-replicated
    groups (StageSpec.replicated_keys) get their grads psum'ed over the
    pipe axis, and AdamW runs on each rank's own stage shards — all still
    one shard_map'd jit (FSDP gathers AND pipeline sends in one graph).

`make_pipeline_train_step` (bring-your-own `stage_fn`/`stage_metas`)
remains for explicitly staged synthetic modules (benchmarks,
dist_harness `pipeline`).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.dist import DistConfig, make_mesh
from repro.models import runtime as RT
from repro.optim.adamw import AdamWConfig, apply_adamw, init_opt_state
from repro.optim.schedule import warmup_cosine


def step_wire_metrics(model, plan) -> dict:
    """Per-step collective wire-byte accounting by comm precision, straight
    from the plan's own bucket groups and precision assignments — the
    numbers `Trainer` mirrors into `train/wire_bytes/<prec>` counters each
    step.  Host math only (no tracing): {"total_bytes", "by_precision"}."""
    from repro.core.autowrap import _cfg_precision
    from repro.core.irgraph import build_nodes

    dcfg = plan.dcfg
    metas = model.metas(dcfg)
    by_prec: dict[str, float] = {}
    total = 0.0
    for key, bplan in plan.bucket_plans.items():
        if key not in metas:
            continue
        nodes = {n.name: n for n in build_nodes(metas[key], dcfg, None)}
        precs = bplan.precisions or \
            [_cfg_precision(dcfg)] * len(bplan.groups)
        mult = max(1, plan.stacked_keys.get(key, 1))
        for grp, prec in zip(bplan.groups, precs):
            wire = sum(nodes[n].ag_wire(prec) + nodes[n].rs_wire(prec)
                       for n in grp if n in nodes) * mult
            by_prec[prec] = by_prec.get(prec, 0.0) + wire
            total += wire
    return {"total_bytes": total, "by_precision": by_prec}


def _opt_specs(pspecs, dcfg: DistConfig):
    """Optimizer-state specs: moments mirror the params; the error-feedback
    accumulator (quantized-RS configs, `DistConfig.needs_ef`) is
    storage-shaped too."""
    specs = {"m": pspecs, "v": pspecs, "step": P()}
    if dcfg.needs_ef:
        specs["ef"] = pspecs
    return specs


def _opt_local(opt_state, local):
    """Strip the leading stage dim off every storage-shaped entry."""
    return {k: (v if k == "step" else local(v)) for k, v in opt_state.items()}


def make_train_step(model, dcfg: DistConfig, ocfg: AdamWConfig,
                    schedule: Callable | None = None):
    """Returns step_local(storage, opt_state, batch) -> (storage, opt_state,
    metrics); run it inside shard_map via `wrap_train_step`."""
    metas = model.metas(dcfg)
    sched = schedule or (lambda t: ocfg.lr)

    def loss_of(storage, mb):
        return model.loss_local(storage, mb, dcfg)[0]

    def step_local(storage, opt_state, batch):
        k = dcfg.microbatches
        if k > 1:
            split = jax.tree.map(
                lambda x: x.reshape(k, x.shape[0] // k, *x.shape[1:]), batch)
            mb0 = jax.tree.map(lambda x: x[0], split)
            # peel microbatch 0 so the accumulator carry has real vma types
            loss, grads = jax.value_and_grad(loss_of)(storage, mb0)

            def body(carry, mb):
                acc_l, acc_g = carry
                l, g = jax.value_and_grad(loss_of)(storage, mb)
                return (acc_l + l, jax.tree.map(jnp.add, acc_g, g)), None

            rest = jax.tree.map(lambda x: x[1:], split)
            (loss, grads), _ = lax.scan(body, (loss, grads), rest)
            inv = 1.0 / k
            loss = loss * inv
            grads = jax.tree.map(lambda g: g * inv, grads)
        else:
            loss, grads = jax.value_and_grad(loss_of)(storage, batch)

        lr = sched(opt_state["step"])
        new_p, new_opt, gnorm = apply_adamw(storage, grads, opt_state,
                                            metas, dcfg, ocfg, lr)
        metrics = {
            "loss": lax.pmean(loss, dcfg.mesh_axes) * dcfg.tp_size,
            "grad_norm": gnorm,
            "lr": jnp.asarray(lr, jnp.float32),
        }
        return new_p, new_opt, metrics

    return step_local


def train_state_shardings(par):
    """NamedShardings of (storage, opt_state) as the train step of `par`
    (a `core.api.Parallelized`) lays them out. State placed so traces the
    same program as the step's own outputs do; host-initialised state
    would trace, and compile, a second one."""
    pspecs = par.storage_specs
    return jax.tree.map(lambda s: NamedSharding(par.mesh, s),
                        (pspecs, _opt_specs(pspecs, par.dcfg)),
                        is_leaf=lambda x: isinstance(x, P))


def wrap_train_step(model, dcfg: DistConfig, shape, ocfg: AdamWConfig,
                    schedule=None, mesh=None, donate: bool = True):
    """jit(shard_map(train_step)) with the full in/out sharding specs."""
    mesh = mesh or make_mesh(dcfg)
    step_local = make_train_step(model, dcfg, ocfg, schedule)
    pspecs = RT.model_storage_specs(model, dcfg)
    opt_specs = _opt_specs(pspecs, dcfg)
    in_specs = (pspecs, opt_specs, RT.batch_specs(model, shape, dcfg))
    out_specs = (pspecs, opt_specs,
                 {"loss": P(), "grad_norm": P(), "lr": P()})
    fn = shard_map(step_local, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs)
    return jax.jit(fn, donate_argnums=(0, 1) if donate else ()), mesh


# ---------------------------------------------------------------------------
# Staged full-model training (paper SS4 x the stage-partition contract):
# the model's own embedding/blocks/head partitioned across the pipe axis.
# ---------------------------------------------------------------------------
def _staged_pieces(model, plan, dcfg: DistConfig):
    """The pre_fn/stage_step/chunk_step/loss_fn quadruple + state template
    builder for the model contract (see core/pipeline module docstring).

    `pre_fn` is the hoisted stage-0 entry stream: `model.stage_pre`
    (including encdec's ENTIRE encoder) is traced exactly once per step —
    a single `lax.map` over the M microbatches — instead of once per
    pipeline slot; the engines thread the per-microbatch entry states (and
    their cotangents) through the scan carry."""
    from jax import lax as _lax

    spec = plan.stage
    M = plan.microbatches
    bplan = plan.bucket_plan(spec.pipelined)

    def pre_fn(params, mbs):
        return _lax.map(lambda mb: model.stage_pre(params, mb, dcfg), mbs)

    def stage_step(params, state, mb, pre):
        # every rank ran the (SPMD-uniform) entry stream via pre_fn; only
        # rank 0 keeps it — others pass the piped state through
        rank0 = _lax.axis_index(dcfg.pp_axis) == 0
        state = jax.tree.map(lambda a, b: jnp.where(rank0, a, b),
                             pre, state)
        return model.stage_blocks(params, state, dcfg, plan=bplan)

    def chunk_step(params, chunk, state, mb, pre):
        # interleaved: the pipelined stack is laid out (V, Lp/V, ...) per
        # rank; virtual stage j = chunk*S + rank runs chunk's layer slice.
        # The entry state injects at virtual stage 0 = (rank 0, chunk 0).
        inject = (_lax.axis_index(dcfg.pp_axis) == 0) & (chunk == 0)
        state = jax.tree.map(lambda a, b: jnp.where(inject, a, b),
                             pre, state)
        sliced = dict(params)
        sliced[spec.pipelined] = jax.tree.map(
            lambda a: _lax.dynamic_index_in_dim(a, chunk, axis=0,
                                                keepdims=False),
            params[spec.pipelined])
        return model.stage_blocks(sliced, state, dcfg, plan=bplan)

    def loss_fn(params, y, mb):
        # per-microbatch contribution; 1/M makes the total the local mean
        return model.stage_loss(params, y, mb, dcfg) / M

    def state_template(params, mb0):
        # zeros_like of a traced stage_pre: only shapes/dtypes survive (the
        # computation is dead-code-eliminated), no eval_shape needed
        return jax.tree.map(jnp.zeros_like,
                            model.stage_pre(params, mb0, dcfg))

    return pre_fn, stage_step, chunk_step, loss_fn, state_template


def _split_microbatches(batch, m: int):
    def one(x):
        if x.shape[0] % m:
            raise ValueError(
                f"local batch {x.shape[0]} does not split into {m} "
                "pipeline microbatches; adjust global_batch or "
                "pp_microbatches")
        return x.reshape(m, x.shape[0] // m, *x.shape[1:])
    return jax.tree.map(one, batch)


def _materialize_fn(model, plan, dcfg: DistConfig):
    """(stage-LOCAL storage) -> storage with pipe-SHARDED pre/post groups
    re-assembled into full FSDP chunks (ONE pipe-axis all-gather per group
    per step; models/staging.py).  Differentiated with jax.vjp around the
    whole pipeline engine, so the transpose is the matching psum-scatter —
    non-consuming ranks contribute exact-zero cotangents by schedule
    masking, keeping pp parity exact."""
    from repro.core import collectives as coll
    from repro.models import staging

    sharded = staging.pipe_sharded_groups(model, dcfg, plan.stage)

    def materialize(local):
        out = dict(local)
        for k in sharded:
            out[k] = jax.tree.map(
                lambda a: coll.pipe_param_gather(a, dcfg.pp_axis,
                                                 dcfg.pp_size),
                local[k])
        return out

    return materialize


def _staged_loss_grads_fn(model, plan, dcfg: DistConfig):
    """The shared staged core: (stage-LOCAL storage, batch) ->
    (total loss, stage grads with replicated groups psum'ed over pipe).

    Routes the plan-resolved schedule (dcfg here is plan.exec_dcfg, which
    carries the scored pp_schedule/pp_virtual write-back)."""
    from repro.core.pipeline import pipeline_loss_grads

    spec = plan.stage
    pre_fn, stage_step, chunk_step, loss_fn, state_template = \
        _staged_pieces(model, plan, dcfg)
    materialize = _materialize_fn(model, plan, dcfg)

    def loss_grads(local, batch):
        mbs = _split_microbatches(batch, plan.microbatches)
        full, mat_vjp = jax.vjp(materialize, local)
        state0 = state_template(full, jax.tree.map(lambda a: a[0], mbs))
        loss, grads, _ = pipeline_loss_grads(
            stage_step, loss_fn, full, mbs, state0, dcfg,
            pre_fn=pre_fn,
            chunk_step=chunk_step if spec.virtual > 1 else None)
        (grads,) = mat_vjp(grads)
        for k in spec.replicated_keys:
            grads[k] = jax.tree.map(lambda g: lax.psum(g, dcfg.pp_axis),
                                    grads[k])
        return loss, grads

    return loss_grads


def make_staged_loss_step(model, plan, dcfg: DistConfig,
                          with_grads: bool = True):
    """step(staged_storage, batch) -> (loss, staged_grads?) under pp."""
    from repro.core.pipeline import gpipe_loss

    spec = plan.stage
    loss_grads = _staged_loss_grads_fn(model, plan, dcfg)
    pre_fn, stage_step, _, loss_fn, state_template = \
        _staged_pieces(model, plan, dcfg)
    materialize = _materialize_fn(model, plan, dcfg)

    def step(staged, batch):
        local = jax.tree.map(lambda a: a[0], staged)   # this rank's stage
        if with_grads or spec.virtual > 1:
            # interleaved lays the stack out in virtual chunks, which the
            # plain forward-only gpipe stream cannot traverse — reuse the
            # full engine and drop the grads for eval
            loss, grads = loss_grads(local, batch)
        else:
            mbs = _split_microbatches(batch, plan.microbatches)
            full = materialize(local)
            state0 = state_template(full,
                                    jax.tree.map(lambda a: a[0], mbs))
            loss = gpipe_loss(stage_step, loss_fn, full, mbs, state0,
                              dcfg.pp_size, dcfg.pp_axis, pre_fn=pre_fn)
        loss = lax.pmean(loss, dcfg.mesh_axes) * dcfg.tp_size
        if not with_grads:
            return loss
        return loss, jax.tree.map(lambda g: g[None], grads)

    return step


def make_staged_train_step(model, plan, dcfg: DistConfig, ocfg: AdamWConfig,
                           schedule: Callable | None = None):
    """Staged analogue of `make_train_step`: pipeline schedule + AdamW on
    each rank's stage shards, stage-replicated grads psum'ed over pipe."""
    spec = plan.stage
    metas = model.metas(dcfg)
    sched = schedule or (lambda t: ocfg.lr)
    loss_grads = _staged_loss_grads_fn(model, plan, dcfg)

    def _local(tree):
        return jax.tree.map(lambda a: a[0], tree)

    def _restack(tree):
        return jax.tree.map(lambda a: a[None], tree)

    def step_local(staged, opt_state, batch):
        local = _local(staged)
        opt_local = _opt_local(opt_state, _local)
        loss, grads = loss_grads(local, batch)
        lr = sched(opt_local["step"])
        new_p, new_opt, gnorm = apply_adamw(
            local, grads, opt_local, metas, dcfg, ocfg, lr,
            pp_replicated=spec.replicated_keys)
        metrics = {
            "loss": lax.pmean(loss, dcfg.mesh_axes) * dcfg.tp_size,
            "grad_norm": gnorm,
            "lr": jnp.asarray(lr, jnp.float32),
        }
        return _restack(new_p), _opt_local(new_opt, _restack), metrics

    return step_local


def _staged_specs(model, dcfg: DistConfig, spec=None):
    from repro.models import staging

    return staging.stage_storage_specs(model, dcfg, spec)


def wrap_loss_step(model, plan, dcfg: DistConfig, shape,
                   with_grads: bool = True, mesh=None):
    """jit(shard_map(step)): (storage, batch) -> loss | (loss, grads) —
    staged under plan.pipelined, the whole-model step otherwise."""
    mesh = mesh or make_mesh(dcfg)
    if not plan.pipelined:
        step = RT.make_loss_step(model, dcfg, with_grads=with_grads)
        pspecs = RT.model_storage_specs(model, dcfg)
        out_specs = (P(), pspecs) if with_grads else P()
        fn, _ = RT.wrap_step(model, dcfg, shape, step, out_specs, mesh=mesh)
        return fn
    pspecs = _staged_specs(model, dcfg, plan.stage)
    step = make_staged_loss_step(model, plan, dcfg, with_grads=with_grads)
    in_specs = (pspecs, RT.batch_specs(model, shape, dcfg))
    out_specs = (P(), pspecs) if with_grads else P()
    fn = shard_map(step, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def wrap_any_train_step(model, plan, dcfg: DistConfig, shape,
                        ocfg: AdamWConfig, schedule=None, mesh=None,
                        donate: bool = True):
    """jit(shard_map(train_step)), staged or whole-model per the plan."""
    mesh = mesh or make_mesh(dcfg)
    if not plan.pipelined:
        fn, _ = wrap_train_step(model, dcfg, shape, ocfg, schedule,
                                mesh=mesh, donate=donate)
        return fn
    step_local = make_staged_train_step(model, plan, dcfg, ocfg, schedule)
    pspecs = _staged_specs(model, dcfg, plan.stage)
    opt_specs = _opt_specs(pspecs, dcfg)
    in_specs = (pspecs, opt_specs, RT.batch_specs(model, shape, dcfg))
    out_specs = (pspecs, opt_specs,
                 {"loss": P(), "grad_norm": P(), "lr": P()})
    fn = shard_map(step_local, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return jax.jit(fn, donate_argnums=(0, 1) if donate else ())


# ---------------------------------------------------------------------------
# Pipeline-parallel training with a bring-your-own staged module (synthetic
# stage stacks under pp x dp x tp; benchmarks and the raw parity harness).
# ---------------------------------------------------------------------------
def make_pipeline_train_step(stage_fn, stage_metas, dcfg: DistConfig,
                             ocfg: AdamWConfig, loss_fn,
                             schedule: str | None = None, plan=None,
                             lr_schedule: Callable | None = None):
    """Pipelined analogue of `make_train_step` for an explicitly staged
    module: `stage_fn(full_params, x) -> y` is ONE stage's compute (TP-local;
    psum over `dcfg.tp_axis` yourself where needed), `loss_fn(y) -> scalar`
    is one microbatch's contribution to the total loss.

    Storage/opt-state leaves carry a leading stage dim sharded over
    `dcfg.pp_axis` (spec `ParamMeta.pipe_stacked_storage_spec`); inside the
    step each rank trains its own stage with SimpleFSDP bucket gathers per
    use (ZeRO-3 over `fsdp_axes`), activations streaming between stages per
    `dcfg.pp_schedule` — all inside one shard_map'd jit, the paper's
    full-graph property.
    """
    from repro.core.pipeline import fsdp_stage_fn, pipeline_grads

    sched = lr_schedule or (lambda t: ocfg.lr)
    stage = fsdp_stage_fn(stage_fn, stage_metas, dcfg, plan)
    dp_axes = RT.dp_axes(dcfg)

    def _local(tree):
        return jax.tree.map(lambda a: a[0], tree)

    def _restack(tree):
        return jax.tree.map(lambda a: a[None], tree)

    def step_local(storage, opt_state, xs):
        local = _local(storage)               # this rank's stage shards
        opt_local = _opt_local(opt_state, _local)
        loss, grads, _ = pipeline_grads(stage, local, xs, loss_fn, dcfg,
                                        schedule)
        lr = sched(opt_local["step"])
        new_p, new_opt, gnorm = apply_adamw(local, grads, opt_local,
                                            stage_metas, dcfg, ocfg, lr)
        metrics = {
            "loss": lax.pmean(loss, dp_axes) if dp_axes else loss,
            "grad_norm": gnorm,
            "lr": jnp.asarray(lr, jnp.float32),
        }
        return _restack(new_p), _opt_local(new_opt, _restack), metrics

    return step_local


def pipeline_storage_specs(stage_metas, dcfg: DistConfig):
    from repro.core.meta import ParamMeta

    return jax.tree.map(lambda m: m.pipe_stacked_storage_spec(dcfg),
                        stage_metas,
                        is_leaf=lambda x: isinstance(x, ParamMeta))


def wrap_pipeline_train_step(stage_fn, stage_metas, dcfg: DistConfig,
                             ocfg: AdamWConfig, loss_fn, xs_ndim: int,
                             schedule: str | None = None, plan=None,
                             lr_schedule=None, mesh=None,
                             donate: bool = True):
    """jit(shard_map(pipeline_train_step)). `xs_ndim` is the rank of the
    (M, batch, ...) microbatch activation stack fed to stage 0 (dim 0 is the
    microbatch schedule dim — replicated; dim 1 is sharded over the data
    axes)."""
    mesh = mesh or make_mesh(dcfg)
    step_local = make_pipeline_train_step(stage_fn, stage_metas, dcfg, ocfg,
                                          loss_fn, schedule, plan,
                                          lr_schedule)
    pspecs = pipeline_storage_specs(stage_metas, dcfg)
    opt_specs = _opt_specs(pspecs, dcfg)
    xs_spec = P(None, RT.dp_axes(dcfg), *([None] * (xs_ndim - 2)))
    in_specs = (pspecs, opt_specs, xs_spec)
    out_specs = (pspecs, opt_specs,
                 {"loss": P(), "grad_norm": P(), "lr": P()})
    fn = shard_map(step_local, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return jax.jit(fn, donate_argnums=(0, 1) if donate else ()), mesh


def init_pipeline_state(stage_params_fn, stage_metas, dcfg: DistConfig,
                        key=None):
    """Build the (S, storage...) stage-stacked params + fresh opt state.

    `stage_params_fn(key, stage_idx) -> full param tree` initializes one
    stage; stage s's tree is converted to ZeRO-3 storage and stacked along
    the leading pipe dim.
    """
    from repro.core.meta import ParamMeta, to_storage

    key = key if key is not None else jax.random.PRNGKey(0)
    fulls = [stage_params_fn(jax.random.fold_in(key, s), s)
             for s in range(dcfg.pp_size)]
    storage = jax.tree.map(
        lambda m, *ps: jnp.stack([to_storage(p, m, dcfg) for p in ps]),
        stage_metas, *fulls, is_leaf=lambda x: isinstance(x, ParamMeta))
    return storage, init_opt_state(storage, dcfg)


def make_eval_step(model, dcfg: DistConfig, shape, mesh=None):
    mesh = mesh or make_mesh(dcfg)
    step = RT.make_loss_step(model, dcfg, with_grads=False)
    pspecs = RT.model_storage_specs(model, dcfg)
    fn = shard_map(step, mesh=mesh,
                   in_specs=(pspecs, RT.batch_specs(model, shape, dcfg)),
                   out_specs=P())
    return jax.jit(fn), mesh


def default_schedule(ocfg: AdamWConfig, total_steps: int, warmup: int = 100):
    return functools.partial(warmup_cosine, peak_lr=ocfg.lr, warmup=warmup,
                             total=total_steps)


def init_train_state(model, dcfg: DistConfig, key=None, plan=None):
    """Fresh storage + optimizer state (stage-stacked when `plan` pipelines
    — the optimizer moments live in the same layout as the params)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    storage = RT.init_storage(model, key, dcfg)
    if plan is not None and plan.pipelined:
        from repro.models import staging
        storage = staging.stage_tree(
            storage, plan.stage, dcfg,
            staging.pipe_sharded_groups(model, dcfg, plan.stage))
    return storage, init_opt_state(storage, dcfg)
