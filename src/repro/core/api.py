"""The single user-facing entry point — the analogue of the paper's

    model = simple_fsdp(model)
    model = torch.compile(model, fullgraph=True)

Two objects carry the whole story:

  * **`ParallelPlan`** — a frozen, fully RESOLVED description of how one
    model runs on one mesh: the stacked param groups, the bucket plan per
    group (the paper's wrapping decision, manual or auto), the remat
    policy, and — when ``dcfg.pp_axis`` is set — the pipeline stage
    partition (models/common.StageSpec) plus the microbatch count.  Built
    once by `plan_parallel(model, dcfg, shape)` and validated there
    (stage partitions cover every top-level param group exactly once,
    layer slices divide evenly); every downstream consumer — `Trainer`,
    the dry-run, benches, tests — reads the same plan instead of
    re-deriving flags.
  * **`parallelize(model, dcfg, shape)`** — returns a `Parallelized`
    bundle: the plan, the mesh, the (stage-aware) storage specs, storage
    init, and the shard_map-wrapped loss/train steps.  Under
    ``dcfg.pp_axis`` the steps route through `core/pipeline`'s GPipe/1F1B
    schedules with per-stage SimpleFSDP storage; otherwise they are the
    familiar whole-model SimpleFSDP steps.  pp x dp x tp is a config flip,
    not a different trainer.

Any model implementing the model contract (``metas`` / ``init_full`` /
``loss_local`` / ``input_specs`` / ``stacked_keys`` + the stage-partition
methods, see models/common.py) goes through this path — all registered
architectures do.

The original bring-your-own-module wrapper `simple_fsdp(apply_fn, params,
cfg)` is kept as a thin DEPRECATED shim for raw apply functions that have no
model contract (examples/quickstart.py shows both). `shard_params` /
`unshard_params` are the one canonical full<->storage layout transform
(models/runtime.tree_to_storage delegates here).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp

from repro.core import collectives as coll
from repro.core.bucketing import BucketPlan, plan_for
from repro.core.dist import DistConfig, make_mesh
from repro.core.meta import ParamMeta, from_storage, to_storage
from repro.core.remat import AUTO_PREFIX, parse_remat

# ---------------------------------------------------------------------------
# The canonical full <-> storage layout transforms (stacked-aware).
# ---------------------------------------------------------------------------


def _is_meta(x):
    return isinstance(x, ParamMeta)


def shard_params(params_full, metas, cfg: DistConfig):
    """Full shaped params -> flat/padded/TP-indexed ZeRO-3 storage layout.

    Leaves with one extra leading dim relative to their meta are treated as
    layer-stacked (the `lax.scan` stacks). Host-side layout transform;
    placement onto the mesh happens via jax.device_put with
    `meta.storage_spec` — see train/trainer.py.  The ONE implementation:
    models/runtime.tree_to_storage is an alias.
    """
    def one(p, m):
        if p.ndim == len(m.global_shape) + 1:
            return jnp.stack(
                [to_storage(p[i], m, cfg) for i in range(p.shape[0])])
        return to_storage(p, m, cfg)
    return jax.tree.map(one, params_full, metas,
                        is_leaf=lambda x: _is_meta(x) or hasattr(x, "shape"))


def unshard_params(storage, metas, cfg: DistConfig):
    """Inverse of `shard_params` (stacked-aware)."""
    def one(p, m):
        if p.ndim == len(m.storage_shape(cfg)) + 1:
            return jnp.stack(
                [from_storage(p[i], m, cfg) for i in range(p.shape[0])])
        return from_storage(p, m, cfg)
    return jax.tree.map(one, storage, metas, is_leaf=_is_meta)


# ---------------------------------------------------------------------------
# ParallelPlan: one resolved, frozen description of the parallelism.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ParallelPlan:
    """Resolved mesh/bucketing/remat/pipeline decisions for (model, dcfg).

    `bucket_plans[k]` is the BucketPlan of stacked group `k` (the paper's
    wrapping decision — what gathers together); `stage` is the pipeline
    partition (None when ``dcfg.pp_axis`` is unset) and `microbatches` the
    pipeline M (0 without pipelining). Frozen: later passes consume one
    schedule instead of scattered flags.
    """

    dcfg: DistConfig
    stacked_keys: Mapping[str, int]
    bucket_plans: Mapping[str, BucketPlan]
    remat: str
    stage: Any = None                   # models/common.StageSpec | None
    microbatches: int = 0
    memory: Any = None                  # core/memory.MemoryPlan | None
    # Resolved pipeline schedule: dcfg.pp_schedule="auto" is scored here
    # (bubble_fraction argmin, peak in-flight state as the tie-break) and
    # the winner recorded; pp_virtual is the resolved V for 'interleaved'
    # (1 for every other schedule).  "" when not pipelined.
    pp_schedule: str = ""
    pp_virtual: int = 1

    @property
    def pipelined(self) -> bool:
        return self.stage is not None

    def bucket_plan(self, key: str) -> BucketPlan | None:
        return self.bucket_plans.get(key)

    @property
    def exec_dcfg(self) -> DistConfig:
        """The DistConfig the steps actually trace with: `dcfg` with the
        memory plan's decisions written back — the resolved per-segment
        policy vector replacing ``remat="auto:<GB>"`` and, when the planner
        retightened buckets against the budget, the chosen BucketPlan as
        the explicit bucket_mode.  This is what keeps the pp=1 path (which
        re-resolves plans inside `apply_stack`) executing exactly the plan
        this object reports."""
        d = self.dcfg
        kw = {}
        if self.pipelined and self.pp_schedule != d.pp_schedule:
            kw["pp_schedule"] = self.pp_schedule
        if self.pipelined and self.pp_virtual != d.pp_virtual:
            kw["pp_virtual"] = self.pp_virtual
        if self.memory is not None:
            if self.memory.policy_spec != d.remat:
                kw["remat"] = self.memory.policy_spec
            if self.memory.bucket_plan is not None:
                kw["bucket_mode"] = self.memory.bucket_plan
        return d.with_(**kw) if kw else d

    def describe(self) -> str:
        d = self.dcfg
        mesh = "x".join(f"{a}={s}" for a, s in
                        zip(d.mesh_axes, d.mesh_shape))
        sched = self.pp_schedule + (
            f"xV{self.pp_virtual}" if self.pp_virtual > 1 else "")
        pp = (f" pp={self.stage.n_stages}({sched},M="
              f"{self.microbatches})" if self.pipelined else "")
        cp = f" cp={d.cp_size}(ring)" if d.cp_size > 1 else ""
        buckets = ",".join(f"{k}:{p.n_buckets}"
                           for k, p in self.bucket_plans.items())
        mem = f" mem[{self.memory.describe()}]" if self.memory is not None \
            else ""
        quant = ""
        if d.comm_precision != "bf16":
            per_bucket = {q for p in self.bucket_plans.values()
                          for q in (p.precisions or ())}
            quant = f" comm={d.comm_precision}"
            if per_bucket:
                quant += "(" + ",".join(sorted(per_bucket)) + ")"
        return (f"mesh[{mesh}] fsdp={d.fsdp_axes} tp={d.tp_size}"
                f"{cp}{pp} remat={self.remat} buckets[{buckets}]{quant}{mem}")


def _auto_virtual(dcfg: DistConfig, stage) -> int:
    """The V the planner proposes for 'interleaved': dcfg.pp_virtual when
    the user pinned one, else the smallest divisor >= 2 of layers_per_stage
    (smallest V already captures most of the ~1/V bubble shrink while
    holding the least extra in-flight state).  0 when no valid V exists."""
    if dcfg.pp_virtual >= 2:
        return dcfg.pp_virtual
    lps = stage.layers_per_stage
    for v in range(2, lps + 1):
        if lps % v == 0:
            return v
    return 0


def _resolve_pp_schedule(dcfg: DistConfig, stage, microbatches: int):
    """Resolve dcfg.pp_schedule to a concrete (schedule, V, stage).

    'auto' scores every schedule valid for this stage partition by modeled
    bubble fraction (core/pipeline.bubble_fraction — computed from the real
    slot tables for interleaved/zb) with peak in-flight saved state as the
    tie-break, and picks the argmin.  An explicit schedule is honored but
    validated (interleaved needs a chunkable, even partition and V >= 2).
    Returns the stage with `virtual` stamped in so the staged storage
    layout, the memory simulator and the engines all see the same V.
    """
    from repro.core.pipeline import (PIPE_SCHEDULES, bubble_fraction,
                                     schedule_peak_state)

    def interleave_ok(v: int) -> str | None:
        if not stage.chunkable:
            return ("this model's stage program is not chunkable "
                    "(StageSpec.chunkable=False — e.g. zamba2's superblock "
                    "cadence)")
        if stage.stage_layers is not None:
            return "uneven stage partitions cannot be virtual-chunked"
        if v < 2:
            return (f"layers_per_stage={stage.layers_per_stage} has no "
                    "divisor >= 2 to chunk into virtual stages")
        if stage.layers_per_stage % v:
            return (f"pp_virtual={v} does not divide layers_per_stage="
                    f"{stage.layers_per_stage}")
        return None

    req = dcfg.pp_schedule
    if req == "auto":
        v = _auto_virtual(dcfg, stage)
        # candidate order is the tie-break of last resort: prefer the
        # bounded-memory baseline when scores come out equal
        cands = [("1f1b", 1), ("zb", 1), ("gpipe", 1)]
        if interleave_ok(v) is None:
            cands.append(("interleaved", v))

        def score(c):
            s, cv = c
            bf = bubble_fraction(microbatches, stage.n_stages, s, cv)
            peak = max(schedule_peak_state(
                microbatches, stage.n_stages, s, cv))
            return (round(bf, 6), peak)

        sched, virtual = min(cands, key=score)
    elif req == "interleaved":
        virtual = _auto_virtual(dcfg, stage)
        why = interleave_ok(virtual)
        if why is not None:
            raise ValueError(f"pp_schedule='interleaved': {why}")
        sched = req
    elif req in PIPE_SCHEDULES:
        sched, virtual = req, 1
    else:
        raise ValueError(
            f"unknown pp_schedule {req!r}; valid: "
            f"{PIPE_SCHEDULES + ('auto',)}")
    if virtual != stage.virtual:
        stage = dataclasses.replace(stage, virtual=virtual)
    return sched, virtual, stage


def plan_parallel(model, dcfg: DistConfig, shape=None) -> ParallelPlan:
    """Build + validate the frozen `ParallelPlan` for one (model, dcfg).

    `shape` (models/common.ShapeConfig) feeds the auto bucket planners'
    workload model (per-device batch); without it the planners fall back to
    their distribution prior.  Raises with a pointed message when the
    requested pipeline degree cannot partition this model.
    """
    from repro.models.runtime import stacked_keys as model_stacked_keys

    # malformed remat strings ('auto:' without a budget, unknown policies,
    # bad vectors) fail HERE, once, not at first trace
    remat_kind, _ = parse_remat(dcfg.remat)

    # ---- context parallelism (core/context.py): validate the cp axis,
    # the model contract and the zigzag divisibility ONCE, at plan time
    if dcfg.cp_axis is not None:
        if dcfg.cp_axis not in dcfg.mesh_axes:
            raise ValueError(
                f"cp_axis={dcfg.cp_axis!r} is not a mesh axis "
                f"({dcfg.mesh_axes})")
        if dcfg.cp_axis in (dcfg.tp_axis, dcfg.pp_axis):
            raise ValueError(
                f"cp_axis={dcfg.cp_axis!r} collides with the TP/PP axis; "
                "context parallelism needs its own mesh axis")
        if dcfg.cp_size > 1:
            from repro.core.context import supports_cp
            if not supports_cp(model):
                raise ValueError(
                    f"{type(model).__name__} does not support context "
                    "parallelism (cp_supported is not set); the ctx axis "
                    "requires the model to route attention/RoPE/loss "
                    "through the zigzag sequence shard (models/dense.py "
                    "is the reference)")
            if dcfg.cp_axis not in dcfg.fsdp_axes:
                raise ValueError(
                    f"cp_axis={dcfg.cp_axis!r} must be one of fsdp_axes="
                    f"{dcfg.fsdp_axes}: parameters shard over data x ctx "
                    "so every cross-ctx gradient flow is an explicit "
                    "collective with an exact transpose (bucket "
                    "reduce-scatter / reverse-ring ppermute — see "
                    "core/context.py); a ctx-replicated layout would "
                    "depend on vma replication-transpose")
            if shape is not None:
                cp = dcfg.cp_size
                if shape.seq_len % (2 * cp):
                    raise ValueError(
                        f"seq_len={shape.seq_len} does not split into "
                        f"2*cp={2 * cp} zigzag chunks; pad the sequence "
                        "or lower the cp degree")
                if (shape.seq_len // cp) % dcfg.tp_size:
                    raise ValueError(
                        f"per-ctx-rank sequence {shape.seq_len // cp} is "
                        f"not divisible by tp={dcfg.tp_size} (the SP "
                        "layout shards the cp-local sequence over the "
                        "model axis)")

    metas = model.metas(dcfg)
    sk = model_stacked_keys(model)     # pointed error for non-contract models
    for k, n in sk.items():
        if k not in metas:
            raise ValueError(
                f"{type(model).__name__}.stacked_keys names {k!r} which is "
                f"not a param group ({sorted(metas)})")

    stats = None
    if shape is not None and hasattr(model, "block_stats") \
            and "blocks" in metas:
        # per-device workload: rows shard over batch_dp, the sequence over
        # the ctx axis — planners see the cp-shrunk compute and re-tighten
        b_local = max(1, shape.global_batch // max(1, dcfg.batch_dp))
        stats = model.block_stats(
            dcfg, (b_local, shape.seq_len // max(1, dcfg.cp_size)))

    bucket_plans = {}
    for k in sk:
        segments = model.block_segments(dcfg) \
            if k == "blocks" and hasattr(model, "block_segments") else None
        bucket_plans[k] = plan_for(metas[k], dcfg,
                                   stats if k == "blocks" else None,
                                   segments=segments)

    stage, microbatches, pp_schedule, pp_virtual = None, 0, "", 1
    if dcfg.pp_axis is not None:
        if not hasattr(model, "stage_spec"):
            raise ValueError(
                f"{type(model).__name__} does not implement the "
                "stage-partition contract (stage_spec/stage_pre/"
                "stage_blocks/stage_loss) — cannot pipeline it")
        if dcfg.microbatches > 1:
            raise ValueError(
                "dcfg.microbatches (gradient accumulation) is not "
                "implemented for the staged pipeline step; use "
                "dcfg.pp_microbatches — pipeline microbatches ARE the "
                "accumulation under pp")
        stage = model.stage_spec(dcfg.pp_size)
        microbatches = dcfg.pp_microbatches or dcfg.pp_size
        pp_schedule, pp_virtual, stage = _resolve_pp_schedule(
            dcfg, stage, microbatches)
        stage.validate(metas.keys(), sk)

    # ---- memory plan: simulate (and, for remat="auto:<GB>", CHOOSE) the
    # per-segment policy vector + offload under the HBM budget.  Needs the
    # workload shape to size activations; fixed-policy plans without a
    # shape simply carry no memory record (nothing to choose).
    memory = None
    if remat_kind == AUTO_PREFIX and not hasattr(model, "block_stats"):
        raise ValueError(
            f"remat={dcfg.remat!r}: the budgeted auto form needs the "
            f"model's cost contract, but {type(model).__name__} does not "
            "implement block_stats; set an explicit policy (or vector) "
            "instead")
    if (shape is not None or remat_kind == AUTO_PREFIX) \
            and hasattr(model, "block_stats"):
        from repro.core.memory import plan_memory
        # the memory model walks the RESOLVED schedule (in-flight state and
        # the zb W-queue depend on it), not the user's 'auto'
        mem_dcfg = dcfg if stage is None else dcfg.with_(
            pp_schedule=pp_schedule, pp_virtual=pp_virtual)
        memory = plan_memory(model, mem_dcfg, shape,
                             bucket_plans=bucket_plans,
                             stage=stage, microbatches=microbatches)
        if memory.bucket_plan is not None:
            bucket_plans = dict(bucket_plans)
            bucket_plans[memory.main_key] = memory.bucket_plan

    return ParallelPlan(dcfg=dcfg, stacked_keys=sk,
                        bucket_plans=bucket_plans, remat=dcfg.remat,
                        stage=stage, microbatches=microbatches,
                        memory=memory, pp_schedule=pp_schedule,
                        pp_virtual=pp_virtual)


# ---------------------------------------------------------------------------
# parallelize(): the one entry point.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Parallelized:
    """What `parallelize` returns: the plan plus everything a training or
    eval loop needs — storage specs/init and jit(shard_map(...)) steps, all
    stage-aware.  Step builders import train/ lazily (core stays importable
    without the training stack)."""

    model: Any
    plan: ParallelPlan
    mesh: Any
    shape: Any = None

    # ------------------------------------------------------------ layout --
    @property
    def dcfg(self) -> DistConfig:
        return self.plan.dcfg

    @property
    def storage_specs(self):
        if self.plan.pipelined:
            from repro.models import staging
            return staging.stage_storage_specs(self.model, self.dcfg,
                                               self.plan.stage)
        from repro.models import runtime as RT
        return RT.model_storage_specs(self.model, self.dcfg)

    @property
    def pipe_sharded(self) -> frozenset:
        """The single-owner param groups stored pipe-SHARDED (see
        models/staging.pipe_sharded_groups) — empty at pp=1."""
        if not self.plan.pipelined:
            return frozenset()
        from repro.models import staging
        return staging.pipe_sharded_groups(self.model, self.dcfg,
                                           self.plan.stage)

    @property
    def abstract_storage(self):
        if self.plan.pipelined:
            from repro.models import staging
            return staging.stage_abstract_storage(self.model, self.dcfg,
                                                  self.plan.stage)
        from repro.models import runtime as RT
        return RT.model_abstract_storage(self.model, self.dcfg)

    def _resolve_shape(self, shape, what: str):
        shape = shape or self.shape
        if shape is None:
            raise ValueError(
                f"{what} needs a ShapeConfig for the batch specs; pass "
                "shape= to parallelize() or to this call")
        return shape

    def batch_specs(self, shape=None):
        from repro.models import runtime as RT
        shape = self._resolve_shape(shape, "batch_specs")
        return RT.batch_specs(self.model, shape, self.dcfg)

    def init_storage(self, key=None):
        """Init full params host-side and lay them out (staged under pp)."""
        key = key if key is not None else jax.random.PRNGKey(0)
        from repro.models import runtime as RT
        storage = RT.init_storage(self.model, key, self.dcfg)
        return self.stage_storage(storage)

    # ------------------------------------------- staged layout round-trip --
    def stage_storage(self, storage):
        """Plain storage -> the layout this plan trains on (no-op at pp=1).

        Checkpoints always store the PLAIN layout (topology-independent);
        Trainer stages on restore and unstages on save."""
        if not self.plan.pipelined:
            return storage
        from repro.models import staging
        return staging.stage_tree(storage, self.plan.stage, self.dcfg,
                                  self.pipe_sharded)

    def unstage_storage(self, storage):
        if not self.plan.pipelined:
            return storage
        from repro.models import staging
        return staging.unstage_tree(storage, self.plan.stage, self.dcfg,
                                    self.pipe_sharded)

    # ------------------------------------------------------------- steps --
    # Steps trace with plan.exec_dcfg — dcfg with the memory plan's resolved
    # per-segment remat vector (and any bucket retightening) written back,
    # so the executed schedule IS the plan's (core/memory).
    def loss_step(self, with_grads: bool = True, shape=None):
        """jit(shard_map(step)): (storage, batch) -> loss | (loss, grads)."""
        from repro.train import train_step as TS
        return TS.wrap_loss_step(self.model, self.plan, self.plan.exec_dcfg,
                                 self._resolve_shape(shape, "loss_step"),
                                 with_grads=with_grads, mesh=self.mesh)

    def train_step(self, ocfg, lr_schedule=None, donate: bool = True,
                   shape=None):
        """jit(shard_map(step)): (storage, opt_state, batch) ->
        (storage, opt_state, metrics)."""
        from repro.train import train_step as TS
        return TS.wrap_any_train_step(
            self.model, self.plan, self.plan.exec_dcfg,
            self._resolve_shape(shape, "train_step"), ocfg, lr_schedule,
            mesh=self.mesh, donate=donate)


def parallelize(model, dcfg: DistConfig, shape=None,
                plan: ParallelPlan | None = None) -> Parallelized:
    """The paper's one-line wrap, resolved for (model, dcfg[, shape]).

    Returns a `Parallelized` bundle (plan + specs + steps).  Pass a
    pre-built `plan` to skip re-resolution (it must describe the same
    dcfg)."""
    plan = plan if plan is not None else plan_parallel(model, dcfg, shape)
    if plan.dcfg is not dcfg and plan.dcfg != dcfg:
        raise ValueError("plan was resolved for a different DistConfig")
    return Parallelized(model=model, plan=plan, mesh=make_mesh(dcfg),
                        shape=shape)


# ---------------------------------------------------------------------------
# DEPRECATED bring-your-own-module shim (pre-ParallelPlan API).
# ---------------------------------------------------------------------------
def build_metas(params_full, cfg: DistConfig, tp_dims: dict[str, int] | None
                = None, dtype=None):
    """One ParamMeta per leaf; `tp_dims` maps param path -> TP-sharded dim."""
    tp_dims = tp_dims or {}

    def one(path, leaf):
        return ParamMeta(
            name=path,
            global_shape=tuple(leaf.shape),
            tp_dim=tp_dims.get(path),
            dtype=dtype or leaf.dtype,
        )

    flat, treedef = jax.tree_util.tree_flatten_with_path(params_full)
    metas = [one(jax.tree_util.keystr(p, simple=True, separator="/"), l)
             for p, l in flat]
    return jax.tree_util.tree_unflatten(treedef, metas)


def simple_fsdp(apply_fn: Callable, params_full, cfg: DistConfig,
                tp_dims: dict[str, int] | None = None,
                plan: BucketPlan | None = None):
    """DEPRECATED: wrap a raw `apply_fn(params, *args)` with FSDP semantics.

    Kept as a thin shim for modules with no model contract (the paper's
    Fig. 1(3) bring-your-own-module loop); registered architectures should
    go through `parallelize()` instead.  Returns (sharded_params, metas,
    wrapped_apply) where `wrapped_apply` expects the sharded storage layout
    and must run inside shard_map over cfg's mesh.
    """
    metas = build_metas(params_full, cfg, tp_dims)
    sharded = shard_params(params_full, metas, cfg)
    resolved_plan = plan if plan is not None else plan_for(metas, cfg)

    def wrapped_apply(shards, *args, **kwargs):
        full = coll.replicate_tree(shards, metas, cfg, resolved_plan)
        return apply_fn(full, *args, **kwargs)

    return sharded, metas, wrapped_apply
