"""Measured-execution step profiler: the measurement half of the
profile -> calibrate -> replan loop.

`profile_step(model, plan, shape)` times the EXECUTED schedule of a frozen
`ParallelPlan` at span granularity and freezes the result as a
JSON-serializable `MeasuredProfile`:

  * per-segment compute — each block segment (models/common.BlockSegments)
    is compiled as its own sub-step on a 1-device mesh (the same masked
    params + threaded-state scaffolding `launch/dryrun.harvest_block_stats`
    uses to COST segments, here executed with concrete buffers and
    block-until-ready fences).  Measured-over-modeled ratios become the
    per-segment scales `calibrated_block_stats` applies.
  * per-bucket AG/RS — the flat-buffer collective path
    (`core/collectives.gather_flat` / `reduce_scatter_flat`) is timed at
    the plan's own bucket sizes on the plan's mesh; an effective per-axis
    bandwidth is fit for the calibration context.
  * quant codec — the existing `launch/dryrun.harvest_quant_timing`, once
    per wire codec the plan (or the 'auto' lattice) can use.
  * wall step — `steps` full optimizer steps through the plan's own
    `train_step`; per-rank rows when more than one JAX process is attached
    (tests/dist_harness.py runs one process, so it contributes one row).

Every measurement here is host wall clock on THIS backend (the container
runs CPU), while the analytic model targets the TPU roofline — the point
of the profile is exactly to close that gap: a global closure factor is
folded into the segment scales so the plan's own `modeled_step_time`,
re-evaluated with the calibrated stats, lands on the measured wall step.
"""

from __future__ import annotations

import dataclasses
import json
import statistics
import time

import jax
import jax.numpy as jnp

from repro.core import hw
from repro.core.dist import make_auto_mesh, precision_codecs
from repro.core.irgraph import build_nodes


@dataclasses.dataclass(frozen=True)
class MeasuredProfile:
    """Frozen result of one `profile_step` run.  JSON-serializable; every
    consumer (`calibrated_block_stats`, `calibration`, the trace overlay)
    reads it read-only, so two emissions from the same profile are
    byte-identical."""

    # provenance: arch/plan describe, steps, backend, closure factor,
    # segment-name order (segment index -> name, for the trace overlay)
    meta: dict = dataclasses.field(default_factory=dict)
    # measured wall clock of ONE optimizer step (median over steps)
    wall_step_s: float = 0.0
    # raw span table: {"name", "cat", "dur_s", ...} rows in record order
    spans: tuple = ()
    # segment name -> multiplicative scale on that segment's analytic
    # (flops, bytes) — scaling both scales the roofline time linearly
    seg_scales: dict = dataclasses.field(default_factory=dict)
    # param name -> segment name (how the scales distribute over params)
    param_segment: dict = dataclasses.field(default_factory=dict)
    # mesh axis -> {"bytes_per_s", "alpha_s"} measured collective bandwidth
    comm_bandwidth: dict = dataclasses.field(default_factory=dict)
    # wire codec -> measured roundtrip rate (bytes of input / s)
    quant_rates: dict = dataclasses.field(default_factory=dict)
    # process rank -> measured wall step (straggler rows)
    rank_step_s: dict = dataclasses.field(default_factory=dict)

    def is_empty(self) -> bool:
        return not (self.seg_scales or self.comm_bandwidth
                    or self.quant_rates)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "MeasuredProfile":
        d = json.loads(s)
        d["spans"] = tuple(d.get("spans", ()))
        return cls(**d)

    @classmethod
    def empty(cls) -> "MeasuredProfile":
        return cls(meta={"source": "empty"})

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            f.write(self.to_json())
        return path


def _dcfg1(dcfg):
    """The degenerate 1-device mesh config the harvest scaffolding uses."""
    return dcfg.with_(mesh_axes=("data", "model"), mesh_shape=(1, 1),
                      fsdp_axes=("data",), tp_axis="model", pp_axis=None,
                      microbatches=1)


def _time_fn(fn, args, iters: int) -> float:
    """Median wall time of `fn(*args)` with full-readiness fences; one
    warmup call absorbs compile."""
    jax.block_until_ready(fn(*args))
    walls = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        walls.append(time.perf_counter() - t0)
    return statistics.median(walls)


# ---------------------------------------------------------------------------
# per-segment compute sub-steps
# ---------------------------------------------------------------------------
def _profile_segments(model, dcfg, bshape, iters, spans):
    """Compile + execute each block segment on a 1-device mesh; return
    (seg_scales, param_segment, seg_names).  Scales are measured-over-
    modeled at the SAME mesh/shape, so they transfer multiplicatively to
    the target mesh's analytic stats (the assumption
    `harvest_block_stats` already rests on)."""
    from jax.sharding import PartitionSpec as P

    from repro.core.bucketing import assign_segments
    from repro.core.meta import ParamMeta, named_leaves

    if not (hasattr(model, "block_stats") and hasattr(model, "block_metas")
            and hasattr(model, "block_fn")):
        return {}, {}, []
    saved = getattr(model, "measured_stats", None)
    if hasattr(model, "measured_stats"):
        model.measured_stats = None
    try:
        dcfg1 = _dcfg1(dcfg)
        an_ref = model.block_stats(dcfg1, bshape)
    finally:
        if hasattr(model, "measured_stats"):
            model.measured_stats = saved

    mesh1 = make_auto_mesh((1, 1), ("data", "model"),
                           devices=jax.devices()[:1])
    metas = model.block_metas(dcfg1)
    B, S = bshape
    consts = model.consts(S, dcfg1)
    x = jnp.zeros((B, S, model.cfg.d_model), dcfg1.param_dtype)
    params = jax.tree.map(
        lambda m: jnp.zeros(m.local_shape(dcfg1), dcfg1.param_dtype),
        metas, is_leaf=lambda v: isinstance(v, ParamMeta))
    names = [k for k, _ in named_leaves(metas)]
    nodes = {n.name: n for n in build_nodes(metas, dcfg1, an_ref)}

    segments = model.block_segments(dcfg1) \
        if hasattr(model, "block_segments") else None
    if segments is not None and len(segments.fns) > 1:
        seg_names = list(segments.names)
        seg_of = assign_segments(names, segments.param_globs, seg_names)
        seg_fns = list(segments.fns)
    else:
        seg_names = ["block"]
        seg_of = [0] * len(names)
        seg_fns = [lambda p, c, st: model.block_fn(p, c, st, dcfg1)]

    leaves, treedef = jax.tree_util.tree_flatten(
        params, is_leaf=lambda v: v is None)
    param_segment = {n: seg_names[sg] for n, sg in zip(names, seg_of)}
    seg_scales = {}
    state = x
    for s, seg_name in enumerate(seg_names):
        masked = jax.tree_util.tree_unflatten(
            treedef, [lf if seg_of[i] == s else None
                      for i, lf in enumerate(leaves)])

        def seg_fn(p, st, s=s):
            return seg_fns[s](p, consts, st)

        wrapped = jax.shard_map(seg_fn, mesh=mesh1, in_specs=(P(), P()),
                                out_specs=P(), check_vma=False)
        jfn = jax.jit(wrapped)
        dt = _time_fn(jfn, (masked, state), iters)
        state = jfn(masked, state)
        modeled = sum(nodes[n].t_comp()
                      for n, sg in zip(names, seg_of) if sg == s)
        spans.append({"name": f"compute[{seg_name}]", "cat": "compute",
                      "dur_s": dt, "modeled_s": modeled,
                      "segment": seg_name})
        if modeled > 0.0 and dt > 0.0:
            seg_scales[seg_name] = dt / modeled
    return seg_scales, param_segment, seg_names


# ---------------------------------------------------------------------------
# per-bucket collectives through the flat-buffer path
# ---------------------------------------------------------------------------
def _profile_collectives(model, plan, iters, spans,
                         cap_elems: int = 1 << 20):
    """Time one flat-buffer all-gather + reduce-scatter per bucket of the
    plan's main group and fit an effective bandwidth per FSDP axis.
    Skipped (empty dict back) when the FSDP domain is trivial or the
    attached devices cannot host the plan's mesh."""
    import math

    from jax.sharding import PartitionSpec as P

    from repro.core import collectives as C

    dcfg = plan.dcfg
    if dcfg.fsdp_size <= 1 \
            or math.prod(dcfg.mesh_shape) > jax.device_count():
        return {}
    key = "blocks" if "blocks" in plan.bucket_plans \
        else next(iter(plan.bucket_plans))
    metas = model.block_metas(dcfg) if key == "blocks" \
        and hasattr(model, "block_metas") else None
    if metas is None:
        return {}
    nodes = {n.name: n for n in build_nodes(metas, dcfg, None)}
    mesh = make_auto_mesh(dcfg.mesh_shape, dcfg.mesh_axes)
    fsdp = dcfg.fsdp_size
    itemsize = jnp.dtype(dcfg.param_dtype).itemsize
    axes = dcfg.fsdp_axes
    frac = sum((dcfg.axis_size(a) - 1) / dcfg.axis_size(a)
               for a in axes if dcfg.axis_size(a) > 1)

    def ag_fn(buf):
        return C.gather_flat(buf, dcfg)

    def rs_fn(ct):
        return C.reduce_scatter_flat(ct, dcfg)

    ag_w = jax.jit(jax.shard_map(ag_fn, mesh=mesh, in_specs=(P(axes),),
                                 out_specs=P(), check_vma=False))
    rs_w = jax.jit(jax.shard_map(rs_fn, mesh=mesh, in_specs=(P(),),
                                 out_specs=P(axes), check_vma=False))

    rows = []
    groups = plan.bucket_plans[key].groups
    for i, grp in enumerate(groups):
        n_tot = sum(nodes[p].n_elems for p in grp if p in nodes)
        if n_tot <= 0:
            continue
        shard = min(max(1, n_tot // fsdp), cap_elems)
        buf = jnp.zeros((fsdp * shard,), dcfg.param_dtype)
        ct = jnp.zeros((fsdp, shard), dcfg.param_dtype)
        t_ag = _time_fn(ag_w, (buf,), iters)
        t_rs = _time_fn(rs_w, (ct,), iters)
        nbytes = fsdp * shard * itemsize
        modeled = hw.collective_time_s(nbytes, dcfg.axis_sizes, axes)
        spans.append({"name": f"AG[bucket {i}]", "cat": "all_gather",
                      "dur_s": t_ag, "modeled_s": modeled,
                      "bytes": nbytes, "bucket": i})
        spans.append({"name": f"RS[bucket {i}]", "cat": "reduce_scatter",
                      "dur_s": t_rs, "modeled_s": modeled,
                      "bytes": nbytes, "bucket": i})
        rows.append((nbytes, t_ag, t_rs))
    if not rows or frac <= 0.0:
        return {}
    # effective bandwidth from the largest timed bucket (alpha ~ 0 there),
    # split evenly over the active FSDP axes: t = frac * n / bw
    nbytes, t_ag, t_rs = max(rows)
    t = (t_ag + t_rs) / 2.0
    bw = frac * nbytes / max(1e-12, t)
    n_active = sum(1 for a in axes if dcfg.axis_size(a) > 1)
    # residual fixed cost from the smallest bucket, floored at zero
    nb0, ta0, tr0 = min(rows)
    alpha = max(0.0, (ta0 + tr0) / 2.0 - frac * nb0 / bw) / max(1, n_active)
    return {a: {"bytes_per_s": bw, "alpha_s": alpha}
            for a in axes if dcfg.axis_size(a) > 1}


# ---------------------------------------------------------------------------
# quant codec rates (the existing dryrun harvest, per codec in play)
# ---------------------------------------------------------------------------
def _plan_codecs(plan) -> list[str]:
    """Wire codecs the plan executes — or, under comm_precision='auto',
    every codec the planner lattice can assign (so a replan can price
    int8 against fp8 with measured rates on both)."""
    dcfg = plan.dcfg
    if dcfg.comm_precision == "bf16":
        return []
    if dcfg.comm_precision == "auto":
        return ["fp8", "int8"]
    codecs = set()
    for bp in plan.bucket_plans.values():
        for prec in (bp.precisions or [dcfg.comm_precision]):
            codecs.update(c for c in precision_codecs(prec) if c)
    return sorted(codecs)


def _profile_quant(model, plan, spans) -> dict:
    from repro.launch.dryrun import harvest_quant_timing

    codecs = _plan_codecs(plan)
    if not codecs:
        return {}
    key = "blocks" if "blocks" in plan.bucket_plans \
        else next(iter(plan.bucket_plans))
    metas = model.block_metas(plan.dcfg) if hasattr(model, "block_metas") \
        else None
    if metas is None:
        return {}
    nodes = {n.name: n for n in build_nodes(metas, plan.dcfg, None)}
    elems = [sum(nodes[p].n_elems for p in grp if p in nodes)
             for grp in plan.bucket_plans[key].groups]
    rates = {}
    for codec in codecs:
        q = harvest_quant_timing(elems, codec=codec)
        if q is None:
            continue
        rates[codec] = q["rate_bytes_per_s"]
        for s in q["samples"]:
            spans.append({"name": f"quant[{codec} n={s['n_elems']}]",
                          "cat": "quant", "dur_s": s["t_us"] * 1e-6,
                          "bytes": s["bytes"], "codec": codec})
    return rates


# ---------------------------------------------------------------------------
# wall step through the plan's own train step
# ---------------------------------------------------------------------------
def _profile_wall(model, plan, shape, steps, spans):
    from repro.core.api import parallelize
    from repro.data.pipeline import DataConfig, SyntheticC4, adapt_batch
    from repro.optim.adamw import AdamWConfig
    from repro.train.train_step import init_train_state

    dcfg = plan.dcfg
    par = parallelize(model, dcfg, shape, plan=plan)
    step_fn = par.train_step(AdamWConfig(lr=1e-3))
    storage, opt = init_train_state(model, dcfg, jax.random.PRNGKey(0),
                                    plan=plan)
    data = SyntheticC4(DataConfig(vocab=model.cfg.vocab,
                                  seq_len=shape.seq_len,
                                  global_batch=shape.global_batch))
    batch = adapt_batch(data.batch(0), model.input_specs(shape, dcfg),
                        step=0)
    if dcfg.cp_size > 1:
        from repro.core.context import zigzag_batch
        batch = zigzag_batch(batch, dcfg)
    storage, opt, m = step_fn(storage, opt, batch)       # compile + warmup
    jax.block_until_ready((storage, m))
    walls = []
    for k in range(max(1, steps)):
        t0 = time.perf_counter()
        storage, opt, m = step_fn(storage, opt, batch)
        jax.block_until_ready((storage, m))
        dt = time.perf_counter() - t0
        walls.append(dt)
        spans.append({"name": f"step[{k}]", "cat": "wall", "dur_s": dt})
    return statistics.median(walls)


# ---------------------------------------------------------------------------
# closure: fold the residual model error into the segment scales
# ---------------------------------------------------------------------------
def _close_scales(model, plan, shape, profile: MeasuredProfile,
                  rounds: int = 6, tol: float = 0.02) -> MeasuredProfile:
    """Multiply every segment scale by a common factor until the plan's
    own `modeled_step_time`, evaluated with the calibrated stats under the
    calibration context, lands on the measured wall step.  Fixed-point
    iteration — `modeled_step_time` is monotone in a uniform compute
    scale, so g <- g * wall / modeled converges in a few rounds."""
    from repro.core.obs.calibrate import calibrated_step_time

    if not profile.seg_scales or profile.wall_step_s <= 0.0:
        return profile
    g, wall = 1.0, profile.wall_step_s
    base = dict(profile.seg_scales)
    for _ in range(rounds):
        trial = dataclasses.replace(
            profile, seg_scales={k: v * g for k, v in base.items()})
        m = calibrated_step_time(model, plan, shape, trial)
        if m is None or m <= 0.0:
            return profile
        if abs(m - wall) / wall <= tol:
            break
        g = min(1e12, max(1e-12, g * wall / m))
    meta = dict(profile.meta)
    meta["closure_factor"] = g
    return dataclasses.replace(
        profile, meta=meta,
        seg_scales={k: v * g for k, v in base.items()})


def profile_step(model, plan, shape, steps: int = 2,
                 wall_step_s: float | None = None) -> MeasuredProfile:
    """Profile the executed schedule of a frozen plan; returns the frozen
    `MeasuredProfile` (see module docstring for what is timed).  Pass
    `wall_step_s` (e.g. the Trainer's own drift-measured step time) to
    skip re-executing the full train step."""
    dcfg = plan.dcfg
    spans: list[dict] = []
    mb = max(1, plan.microbatches)
    b_local = max(1, shape.global_batch // max(1, dcfg.batch_dp) // mb)
    bshape = (b_local, shape.seq_len // max(1, dcfg.cp_size))

    seg_scales, param_segment, seg_names = _profile_segments(
        model, dcfg, bshape, steps, spans)
    comm_bw = _profile_collectives(model, plan, steps, spans)
    quant_rates = _profile_quant(model, plan, spans)
    if wall_step_s is None:
        wall_step_s = _profile_wall(model, plan, shape, steps, spans)
    else:
        spans.append({"name": "step[given]", "cat": "wall",
                      "dur_s": wall_step_s})

    rank_step_s = {str(jax.process_index()): wall_step_s}
    if jax.process_count() > 1:       # per-rank rows under a real multi-
        from jax.experimental import multihost_utils  # process launch
        walls = multihost_utils.process_allgather(jnp.asarray(wall_step_s))
        rank_step_s = {str(r): float(w) for r, w in enumerate(walls)}

    profile = MeasuredProfile(
        meta={"plan": plan.describe(),
              "arch": type(model).__name__,
              "steps": steps,
              "backend": jax.default_backend(),
              "seg_names": seg_names},
        wall_step_s=wall_step_s,
        spans=tuple(spans),
        seg_scales=seg_scales,
        param_segment=param_segment,
        comm_bandwidth=comm_bw,
        quant_rates=quant_rates,
        rank_step_s=rank_step_s,
    )
    return _close_scales(model, plan, shape, profile)
