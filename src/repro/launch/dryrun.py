import os

if __name__ == "__main__":
    # entry-point only: must land before jax initializes.  Library imports
    # (tests harvesting BlockStats/MemoryStats in-process) must NOT mutate
    # the environment — os.environ leaks into every later subprocess.  A
    # CPU planner tool: it runs on 512 virtual CPU devices even where a
    # chip is attached.
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", "")
    )

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent — the full
SimpleFSDP computation+communication graph lowers, SPMD-partitions over the
production mesh (16x16 single-pod / 2x16x16 multi-pod) and compiles — and
extracts the roofline raw material:

  * compiled.memory_analysis()  -> per-device bytes (fits-in-HBM check)
  * compiled.cost_analysis()    -> per-device HLO FLOPs / bytes accessed
  * compiled.as_text()          -> collective ops parsed into per-axis-class
                                   payload bytes (ICI vs DCN)

It is also where measured planner inputs come from: `harvest_block_stats`
compiles ONE block and turns its XLA cost/memory analysis into a measured
`BlockStats` that replaces the analytic roofline defaults for the auto
planners (`plan_for` with bucket_mode='auto'/'auto_dp'); the chosen plan and
its modeled exposure are recorded on each auto-mode result row under
"autowrap". Analytic stats remain the fallback when the local backend cannot
cost the block (CPU-only containers with no cost model).

Results land in benchmarks/results/dryrun_<mesh>.json; EXPERIMENTS.md
sections SSDry-run and SSRoofline are generated from them.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek_coder_33b \
      --shape train_4k [--multi-pod]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""

import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import hw
from repro.core.dist import DistConfig, make_auto_mesh
from repro.core.irgraph import BlockStats
from repro.core.meta import ParamMeta
from repro.launch.mesh import make_production_mesh, production_dcfg
from repro.models import runtime as RT
from repro.models.common import SHAPE_SUITE, ShapeConfig, get_shape
from repro.models.registry import ARCH_IDS, get_arch
from repro.optim.adamw import AdamWConfig
from repro.train import serve as SV
from repro.train.train_step import make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results")


def pick_microbatches(model, dcfg: DistConfig, shape,
                      calibrate: bool = False) -> int:
    """Gradient-accumulation count for one training cell: the memory
    simulator's stage peaks decide (core/memory.auto_microbatches — this
    replaced the hand-kept per-(arch, shape) MICROBATCH table; --microbatch
    remains as an explicit override).  With `calibrate` the activation
    model is first calibrated against a 1-device XLA compile
    (harvest_memory_stats); otherwise the pick uses the conservative
    default act_scale."""
    if shape.kind != "train" or not hasattr(model, "block_stats"):
        return 1
    from repro.core.memory import auto_microbatches
    act_scale = None
    if calibrate:
        bshape1 = (max(1, shape.global_batch // dcfg.batch_dp),
                   shape.seq_len // max(1, dcfg.cp_size))
        ms = harvest_memory_stats(model, dcfg, bshape1)
        act_scale = ms.act_scale if ms is not None else None
    return auto_microbatches(model, dcfg, shape, act_scale=act_scale)


def _sds_with_sharding(tree_abs, tree_specs, mesh):
    def one(a, s):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(mesh, s))
    return jax.tree.map(one, tree_abs, tree_specs,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _batch_specs(model, shape, dcfg, B):
    """`models/runtime.batch_specs` (the ONE cp/rows sharding contract),
    with the leading dim downgraded to replicated when the global batch
    does not divide over the row axes (long_500k has global_batch=1)."""
    base = RT.batch_specs(model, shape, dcfg)
    dp_total = dcfg.batch_dp
    specs = {}
    for k, sds in model.input_specs(shape, dcfg).items():
        lead = sds.shape[0]
        spec = base[k]
        if lead % dp_total or lead < dp_total:
            spec = P(None, *spec[1:])
        specs[k] = spec
    return specs


# ---------------------------------------------------------------------------
# collective parsing
# ---------------------------------------------------------------------------
_SHAPE_RE = re.compile(r"(bf16|f32|f16|s32|u32|s8|u8|pred|f64)\[([\d,]*)\]")
_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "f64": 8}
_COLL_RE = re.compile(
    r"= \S+ (all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([\d,]+)\}")


def _line_out_bytes(line: str) -> int:
    m = _SHAPE_RE.search(line.split("=", 1)[1] if "=" in line else line)
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def parse_collectives(hlo_text: str, dcfg: DistConfig) -> dict:
    """Classify each collective by replica-group size -> axis class (ICI/DCN)
    and accumulate effective per-device payload bytes."""
    per_class = {"ici_bytes": 0.0, "dcn_bytes": 0.0}
    ops = []
    pod = dcfg.axis_sizes.get("pod", 1)
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        g = _GROUPS_RE.search(line)
        gsize = len(g.group(1).split(",")) if g else 1
        if gsize <= 1:
            continue
        out_b = _line_out_bytes(line)
        k = gsize
        frac = (k - 1) / k
        if kind == "all-gather":
            payload = out_b * frac
        elif kind == "reduce-scatter":
            payload = out_b * (k - 1)          # input = out*k; moves (k-1)/k
        elif kind == "all-reduce":
            payload = 2.0 * out_b * frac
        elif kind == "all-to-all":
            payload = out_b * frac
        else:                                   # collective-permute
            payload = out_b
        # axis class: a group spanning across pods touches DCN
        is_dcn = pod > 1 and gsize in (pod, pod * dcfg.axis_size("data"))
        per_class["dcn_bytes" if is_dcn else "ici_bytes"] += payload
        ops.append({"kind": kind, "group": k, "bytes": out_b})
    per_class["n_collectives"] = len(ops)
    kinds = {}
    for o in ops:
        kinds[o["kind"]] = kinds.get(o["kind"], 0) + 1
    per_class["by_kind"] = kinds
    return per_class


# ---------------------------------------------------------------------------
# compiled-cost harvesting: measured BlockStats for the auto planners
# ---------------------------------------------------------------------------
def _harvest_setup(model, dcfg: DistConfig, batch_shape):
    """Shared 1-device harvest scaffolding: (dcfg1, mesh1, metas, consts,
    x_abs, params_abs, analytic target/reference stats)."""
    saved = getattr(model, "measured_stats", None)
    if hasattr(model, "measured_stats"):
        model.measured_stats = None
    try:
        an_tgt = model.block_stats(dcfg, batch_shape)
        dcfg1 = dcfg.with_(mesh_axes=("data", "model"),
                           mesh_shape=(1, 1), fsdp_axes=("data",),
                           tp_axis="model", pp_axis=None,
                           microbatches=1)
        an_ref = model.block_stats(dcfg1, batch_shape)
    finally:
        if hasattr(model, "measured_stats"):
            model.measured_stats = saved

    mesh1 = make_auto_mesh((1, 1), ("data", "model"),
                           devices=jax.devices()[:1])
    metas = model.block_metas(dcfg1)
    B, S = batch_shape
    consts = model.consts(S, dcfg1)
    x_abs = jax.ShapeDtypeStruct((B, S, model.cfg.d_model),
                                 dcfg1.param_dtype)
    params_abs = jax.tree.map(
        lambda m: jax.ShapeDtypeStruct(m.local_shape(dcfg1),
                                       dcfg1.param_dtype),
        metas, is_leaf=lambda v: isinstance(v, ParamMeta))
    return dcfg1, mesh1, metas, consts, x_abs, params_abs, an_tgt, an_ref


def _compile_costs(fn, mesh1, in_abs):
    """jit(shard_map(fn)) on the 1-device mesh ->
    (flops, bytes, temp, out_aval) — out_aval feeds the next segment's
    abstract state (collectives only have bound axes inside the wrap)."""
    wrapped = shard_map(fn, mesh=mesh1,
                        in_specs=tuple(P() for _ in in_abs),
                        out_specs=P(), check_vma=False)
    compiled = jax.jit(wrapped).lower(*in_abs).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            float(compiled.memory_analysis().temp_size_in_bytes),
            jax.eval_shape(wrapped, *in_abs))


def harvest_block_stats(model, dcfg: DistConfig,
                        batch_shape) -> BlockStats | None:
    """Measured per-block costs from XLA, as a `BlockStats` the planners use
    in place of the analytic roofline model.

    The block is compiled on the local backend over a degenerate 1x1 mesh
    (so the model's TP collectives lower as no-ops).  Models that declare a
    segment chain (models/common.BlockSegments) are compiled PER SEGMENT —
    each segment's XLA FLOPs / bytes-accessed / activation footprint scales
    that segment's analytic shares, so both the exposure DP and the memory
    simulator see measured per-segment numbers instead of whole-block
    totals smeared proportionally (ROADMAP bucketing-v2 follow-up).
    Unsegmented blocks keep the whole-block attribution.  Harvest at the
    same per-device microbatch shape the cell runs.

    Returns None whenever compilation or costing is unavailable (e.g. a
    backend whose cost model reports no FLOPs) — callers fall back to the
    analytic stats.
    """
    try:
        (dcfg1, mesh1, metas, consts, x_abs, params_abs,
         an_tgt, an_ref) = _harvest_setup(model, dcfg, batch_shape)
        segments = model.block_segments(dcfg1) \
            if hasattr(model, "block_segments") else None

        if segments is not None and len(segments.fns) > 1:
            return _harvest_segmented(model, dcfg1, mesh1, metas, consts,
                                      x_abs, params_abs, an_tgt, an_ref,
                                      segments)

        def blk(params, x):
            return model.block_fn(params, consts, x, dcfg1)

        flops, bts, act, _ = _compile_costs(blk, mesh1, (params_abs, x_abs))
        if flops <= 0.0:
            return None
        f_ref = sum(an_ref.param_flops.values())
        b_ref = sum(an_ref.param_bytes.values())
        f_scale = flops / f_ref if f_ref > 0 else 1.0
        b_scale = bts / b_ref if b_ref > 0 and bts > 0 else 1.0
        a_scale = act / an_ref.act_bytes if an_ref.act_bytes > 0 and act > 0 \
            else 1.0
        return BlockStats(
            param_flops={k: v * f_scale
                         for k, v in an_tgt.param_flops.items()},
            param_bytes={k: v * b_scale
                         for k, v in an_tgt.param_bytes.items()},
            act_bytes=an_tgt.act_bytes * a_scale,
            source="measured",
        )
    except Exception as e:
        # Analytic fallback is legitimate on backends without a cost model,
        # but the reason must be visible or a harvest regression silently
        # reverts every auto plan to analytic stats.
        print(f"[harvest] measured BlockStats unavailable "
              f"({type(e).__name__}: {e}); falling back to analytic",
              flush=True)
        return None


def _harvest_segmented(model, dcfg1, mesh1, metas, consts, x_abs,
                       params_abs, an_tgt, an_ref, segments) -> BlockStats:
    """Per-segment compilation: one XLA executable per segment of the
    chain, abstract inter-segment states threaded with `jax.eval_shape`."""
    from repro.core.bucketing import assign_segments
    from repro.core.meta import named_leaves

    names = [k for k, _ in named_leaves(metas)]
    seg_of = assign_segments(names, segments.param_globs, segments.names)
    leaves, treedef = jax.tree_util.tree_flatten(
        params_abs, is_leaf=lambda v: v is None)

    pf = dict(an_tgt.param_flops)
    pb = dict(an_tgt.param_bytes)
    seg_act: dict[str, float] = {}
    state = x_abs
    total_flops = 0.0
    act_ratio = an_tgt.act_bytes / an_ref.act_bytes \
        if an_ref.act_bytes > 0 else 1.0
    for s, seg_name in enumerate(segments.names):
        masked = jax.tree_util.tree_unflatten(
            treedef, [lf if seg_of[i] == s else None
                      for i, lf in enumerate(leaves)])

        def seg_fn(params, st, s=s):
            return segments.fns[s](params, consts, st)

        flops, bts, act, state = _compile_costs(seg_fn, mesh1,
                                                (masked, state))
        total_flops += flops
        in_seg = [n for n, sg in zip(names, seg_of) if sg == s]
        f_ref = sum(an_ref.param_flops[n] for n in in_seg)
        b_ref = sum(an_ref.param_bytes[n] for n in in_seg)
        f_scale = flops / f_ref if f_ref > 0 and flops > 0 else 1.0
        b_scale = bts / b_ref if b_ref > 0 and bts > 0 else 1.0
        for n in in_seg:
            pf[n] = an_tgt.param_flops[n] * f_scale
            pb[n] = an_tgt.param_bytes[n] * b_scale
        # segment activation footprint, rescaled to the target mesh (the
        # analytic target/reference ratio carries the tp/batch scaling)
        seg_act[seg_name] = act * act_ratio
    if total_flops <= 0.0:
        raise RuntimeError("cost model reported no FLOPs for any segment")
    return BlockStats(
        param_flops=pf, param_bytes=pb,
        act_bytes=an_tgt.act_bytes,        # block input: analytic shape math
        source="measured", seg_act_bytes=seg_act,
    )


# ---------------------------------------------------------------------------
# memory-model calibration: measured residual footprint for the simulator
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MemoryStats:
    """Calibration of core/memory's activation model against XLA.

    `measured_bytes` is ``memory_analysis().temp_size`` of a 1-device
    forward+backward block compile under `policy`; `modeled_bytes` the
    simulator's residency for the same policy; `act_scale` their clamped
    ratio — multiply every activation-derived term by it
    (`simulate_peak(act_scale=...)`)."""

    measured_bytes: float
    modeled_bytes: float
    act_scale: float
    policy: str = "fsdp_only"
    source: str = "measured"


def harvest_memory_stats(model, dcfg: DistConfig, batch_shape,
                         policy: str = "fsdp_only") -> MemoryStats | None:
    """Compile ONE block's loss+grad on a 1-device mesh and calibrate the
    live-range simulator's activation model against
    ``compiled.memory_analysis()``.  Returns None when the backend cannot
    compile/cost the block (callers keep act_scale=1.0)."""
    try:
        from repro.core.memory import build_block_profile
        from repro.core.remat import maybe_remat

        (dcfg1, mesh1, metas, consts, x_abs, params_abs,
         _, an_ref) = _harvest_setup(model, dcfg, batch_shape)
        segments = model.block_segments(dcfg1) \
            if hasattr(model, "block_segments") else None

        blk = maybe_remat(
            lambda params, x: model.block_fn(params, consts, x, dcfg1)[0],
            policy)

        def grad_step(params, x):
            def loss(xx):
                y = blk(params, xx)
                return jnp.sum(y.astype(jnp.float32) ** 2)
            return jax.grad(loss)(x)

        _, _, measured, _ = _compile_costs(grad_step, mesh1,
                                           (params_abs, x_abs))
        prof = build_block_profile(metas, dcfg1, an_ref, segments)
        n_seg = len(prof.segments)
        modeled = prof.residency((policy,) * n_seg)
        if measured <= 0 or modeled <= 0:
            return None
        scale = min(4.0, max(0.25, measured / modeled))
        return MemoryStats(measured_bytes=measured, modeled_bytes=modeled,
                           act_scale=scale, policy=policy)
    except Exception as e:
        print(f"[harvest] memory calibration unavailable "
              f"({type(e).__name__}: {e}); act_scale=1.0", flush=True)
        return None


def harvest_quant_timing(bucket_elems, codec: str = "fp8", iters: int = 4,
                         cap_elems: int = 1 << 21) -> dict | None:
    """Time the quant round-trip kernel at the plan's actual bucket sizes
    (jit-compiled on THIS backend) and derive a measured codec throughput,
    replacing the analytic 2x-HBM-pass prior in `quant_overhead_s`.
    `bucket_elems`: per-bucket element counts (each capped at `cap_elems`
    so a 1-bucket 8B-param plan doesn't allocate the full buffer).
    Returns {"rate_bytes_per_s", "codec", "samples"} or None when the
    backend can't run the kernel."""
    try:
        import functools

        import numpy as np

        from repro.kernels.quant import ops as QOPS

        sizes = sorted({min(int(n), cap_elems)
                        for n in bucket_elems if n and n > 0})
        if not sizes:
            return None
        # smallest / median / largest: enough to see the fixed-cost knee
        # without timing every bucket of a 30-bucket plan
        picks = sorted({sizes[0], sizes[len(sizes) // 2], sizes[-1]})
        fn = jax.jit(functools.partial(QOPS.roundtrip, codec=codec))
        samples = []
        for n in picks:
            n = max(QOPS.QCHUNK, (n // QOPS.QCHUNK) * QOPS.QCHUNK)
            x = jnp.asarray(
                np.random.default_rng(0).standard_normal(n), jnp.bfloat16)
            fn(x).block_until_ready()             # compile + warmup
            t0 = time.perf_counter()
            for _ in range(iters):
                y = fn(x)
            y.block_until_ready()
            dt = (time.perf_counter() - t0) / iters
            samples.append({"n_elems": n, "bytes": n * 2,
                            "t_us": dt * 1e6})
        big = samples[-1]
        rate = big["bytes"] / max(1e-12, big["t_us"] * 1e-6)
        return {"rate_bytes_per_s": rate, "codec": codec,
                "samples": samples}
    except Exception as e:
        print(f"[harvest] quant timing unavailable "
              f"({type(e).__name__}: {e}); analytic estimate stands",
              flush=True)
        return None


def _autowrap_record(model, dcfg: DistConfig, batch_shape, stats,
                     measure_quant: bool = False) -> dict:
    """The partition the cell will EXECUTE + its modeled exposure (logged
    into the dryrun row so perf numbers are attributable to a concrete
    plan). exposed_comm_time rewrites the plan to the executed segmented
    partition (split + segment-major + pooled hiding), matching fig4.

    `measure_quant`: on quantized-comm cells, time the codec kernel at
    this plan's bucket sizes first and price `quant_overhead_s` by the
    measured rate (the record then carries the measured AND the analytic
    estimate side by side)."""
    from repro.core import irgraph
    from repro.core.autowrap import exposed_comm_time
    from repro.core.bucketing import (_active_segments, plan_for,
                                      split_plan_at_segments)

    metas = model.block_metas(dcfg)
    segments = model.block_segments(dcfg) \
        if hasattr(model, "block_segments") else None
    segments, _ = _active_segments(metas, dcfg, segments)

    qtiming = None
    prev_rate = None
    if measure_quant and dcfg.comm_precision != "bf16":
        nodes = {n.name: n for n in
                 irgraph.build_nodes(metas, dcfg, stats)}
        pre_plan = plan_for(metas, dcfg, stats, segments=segments)
        qtiming = harvest_quant_timing(
            [sum(nodes[p].n_elems for p in grp if p in nodes)
             for grp in pre_plan.groups])
        if qtiming is not None:
            prev_rate = irgraph.set_measured_quant_rate(
                qtiming["rate_bytes_per_s"])
    try:
        plan = plan_for(metas, dcfg, stats, segments=segments)
        r = exposed_comm_time(plan, metas, dcfg, stats, segments=segments)
    finally:
        if qtiming is not None:
            irgraph.set_measured_quant_rate(prev_rate)
    if segments is not None:
        plan = split_plan_at_segments(plan, metas, segments)   # as executed
    rec = {
        "bucket_mode": str(dcfg.bucket_mode),
        "stats_source": getattr(stats, "source", None) or "default",
        "n_buckets": r["n_buckets"],
        "exposed_us": r["exposed_s"] * 1e6,
        "total_comm_us": r["total_comm_s"] * 1e6,
        "compute_us": r["compute_s"] * 1e6,
        "comm_precision": dcfg.comm_precision,
        "precisions": list(r["precisions"]),
        "comm_wire_bytes": r["comm_wire_bytes"],
        "plan": [list(g) for g in plan.groups],
    }
    if qtiming is not None:
        meas_us = r["quant_overhead_s"] * 1e6
        # overhead is linear in 1/rate, so the analytic counterpart is
        # the measured figure rescaled to the 2x-HBM-pass prior
        est_us = meas_us * (qtiming["rate_bytes_per_s"]
                            / (hw.HBM_BANDWIDTH / 2.0))
        rec["quant_overhead_meas_us"] = meas_us
        rec["quant_overhead_est_us"] = est_us
        rec["quant_rate_bytes_per_s"] = qtiming["rate_bytes_per_s"]
        rec["quant_timing_samples"] = qtiming["samples"]
    return rec


# ---------------------------------------------------------------------------
# per-cell lowering
# ---------------------------------------------------------------------------
def build_lowered(arch_id: str, shape_name: str, dcfg: DistConfig, mesh,
                  bucket_mode="block", reorder=True, measured_stats=None,
                  microbatches: int = 1):
    cfg, model = get_arch(arch_id)
    if measured_stats is not None and hasattr(model, "measured_stats"):
        model.measured_stats = measured_stats
    shape = get_shape(shape_name)
    b_local = max(1, shape.global_batch // dcfg.batch_dp)
    mb = min(microbatches, b_local)  # can't split below one sample/device
    dcfg = dcfg.with_(microbatches=mb, bucket_mode=bucket_mode,
                      reorder=reorder)

    if shape.kind == "train":
        step = make_train_step(model, dcfg, AdamWConfig())
        pspecs = RT.model_storage_specs(model, dcfg)
        opt_specs = {"m": pspecs, "v": pspecs, "step": P()}
        if dcfg.needs_ef:
            opt_specs["ef"] = pspecs
        bspecs = _batch_specs(model, shape, dcfg, shape.global_batch)
        fn = shard_map(step, mesh=mesh,
                       in_specs=(pspecs, opt_specs, bspecs),
                       out_specs=(pspecs, opt_specs,
                                  {"loss": P(), "grad_norm": P(),
                                   "lr": P()}),
                       check_vma=False)
        params_abs = RT.model_abstract_storage(model, dcfg)
        opt_abs = {"m": params_abs, "v": params_abs,
                   "step": jax.ShapeDtypeStruct((), jnp.int32)}
        if dcfg.needs_ef:
            opt_abs["ef"] = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                params_abs)
        batch_abs = model.input_specs(shape, dcfg)
        args = (
            _sds_with_sharding(params_abs, pspecs, mesh),
            _sds_with_sharding(opt_abs, opt_specs, mesh),
            _sds_with_sharding(batch_abs, bspecs, mesh),
        )
        lowered = jax.jit(fn, donate_argnums=(0, 1)).lower(*args)
    elif shape.kind == "prefill":
        if cfg.family in ("dense", "moe", "vlm"):
            dcfg = dcfg.with_(kv_cache_int8=True)   # cache decode consumes
        dp = tuple(a for a in dcfg.mesh_axes if a != dcfg.tp_axis)
        bspecs = _batch_specs(model, shape, dcfg, shape.global_batch)
        _, cache_specs = SV.cache_abstract(model, shape, dcfg)

        def step(params, batch):
            return model.prefill_local(params, batch, dcfg)

        fn = shard_map(step, mesh=mesh,
                       in_specs=(SV.serve_param_specs(model, dcfg), bspecs),
                       out_specs=(P(bspecs["tokens"][0], dcfg.tp_axis),
                                  cache_specs),
                       check_vma=False)
        args = (
            _sds_with_sharding(SV.serve_abstract_params(model, dcfg),
                               SV.serve_param_specs(model, dcfg), mesh),
            _sds_with_sharding(model.input_specs(shape, dcfg), bspecs,
                               mesh),
        )
        lowered = jax.jit(fn).lower(*args)
    else:  # decode
        if cfg.family in ("dense", "moe", "vlm"):
            # int8 KV-cache quantization: halves the dominant decode buffer
            dcfg = dcfg.with_(kv_cache_int8=True)
        dp = tuple(a for a in dcfg.mesh_axes if a != dcfg.tp_axis)
        B = shape.global_batch
        lead = dp if B % dcfg.dp_total == 0 and B >= dcfg.dp_total else None
        cache_abs, cache_specs = SV.cache_abstract(model, shape, dcfg)
        # re-spec the cache batch dim when batch is replicated
        if lead is None:
            cache_specs = jax.tree.map(
                lambda s: P(*[None if ax else ax for ax in [None]])
                if False else _strip_dp(s, dcfg), cache_specs,
                is_leaf=lambda x: isinstance(x, P))

        def step(params, cache, tok, pos):
            logits, cache = model.decode_local(params, cache, tok, pos,
                                               dcfg)
            return logits, cache

        fn = shard_map(step, mesh=mesh,
                       in_specs=(SV.serve_param_specs(model, dcfg),
                                 cache_specs, P(lead), P(lead)),
                       out_specs=(P(lead, dcfg.tp_axis), cache_specs),
                       check_vma=False)
        args = (
            _sds_with_sharding(SV.serve_abstract_params(model, dcfg),
                               SV.serve_param_specs(model, dcfg), mesh),
            _sds_with_sharding(cache_abs, cache_specs, mesh),
            jax.ShapeDtypeStruct((B,), jnp.int32,
                                 sharding=NamedSharding(mesh, P(lead))),
            jax.ShapeDtypeStruct((B,), jnp.int32,
                                 sharding=NamedSharding(mesh, P(lead))),
        )
        # donate the cache: decode updates it in place (halves HBM)
        lowered = jax.jit(fn, donate_argnums=(1,)).lower(*args)
    return lowered, model, shape, dcfg


def _strip_dp(spec: P, dcfg: DistConfig):
    """Replace dp-axis entries with None (batch replicated)."""
    dp = set(a for a in dcfg.mesh_axes if a != dcfg.tp_axis)

    def clean(e):
        if e is None:
            return None
        if isinstance(e, tuple):
            kept = tuple(a for a in e if a not in dp)
            return kept if kept else None
        return None if e in dp else e

    return P(*[clean(e) for e in spec])


# ---------------------------------------------------------------------------
# roofline terms
# ---------------------------------------------------------------------------
def roofline_terms(cost: dict, colls: dict, model, shape: ShapeConfig,
                   dcfg: DistConfig) -> dict:
    flops = float(cost.get("flops", 0.0))
    bts = float(cost.get("bytes accessed", 0.0))
    t_comp = flops / hw.PEAK_FLOPS_BF16
    t_mem = bts / hw.HBM_BANDWIDTH
    # per-axis bandwidths from hw.axis_bandwidth — the same single source
    # the bucket planners and the ring scheduler cost against
    t_ici = colls["ici_bytes"] / hw.axis_bandwidth("data").bytes_per_s
    t_dcn = colls["dcn_bytes"] / hw.axis_bandwidth("pod").bytes_per_s
    t_coll = t_ici + t_dcn
    cfg = model.cfg
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        model_flops = 6.0 * cfg.n_params_active() * tokens / dcfg.n_devices
    elif shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        model_flops = 2.0 * cfg.n_params_active() * tokens / dcfg.n_devices
    else:
        tokens = shape.global_batch
        model_flops = 2.0 * cfg.n_params_active() * tokens / dcfg.n_devices
    dominant = max((("compute", t_comp), ("memory", t_mem),
                    ("collective", t_coll)), key=lambda kv: kv[1])[0]
    return {
        "t_compute_s": t_comp, "t_memory_s": t_mem, "t_collective_s": t_coll,
        "t_ici_s": t_ici, "t_dcn_s": t_dcn,
        "dominant": dominant,
        "hlo_flops_per_dev": flops, "hlo_bytes_per_dev": bts,
        "model_flops_per_dev": model_flops,
        "useful_flop_frac": model_flops / flops if flops else 0.0,
        "roofline_frac": (min(t_comp, max(t_comp, t_mem, t_coll))
                          / max(t_comp, t_mem, t_coll, 1e-30)),
    }


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             bucket_mode="block", reorder=True, zero3=False,
             mesh_shape=None, microbatch=None, harvest=None,
             remat=None, context_degree: int = 1,
             comm_precision=None) -> dict:
    """Lower+compile one (arch, shape, mesh) cell.

    `harvest`: None = harvest measured BlockStats iff an auto planner will
    consume them; True/False force it. Harvested stats are plumbed into the
    cell's model so `plan_for` plans over measured costs; on failure the
    analytic model is the fallback and the row records which one fed the
    plan.

    `remat`: override dcfg.remat for the cell — a fixed policy, a
    per-segment vector, or ``"auto:<GB>"`` (resolved by core/memory's
    budgeted planner BEFORE lowering; an infeasible budget raises the
    planner's pointed error and the row records it).

    `context_degree` > 1 carves the 'ctx' axis out of the data axis (ring
    attention, core/context.py): training cells of cp-capable models lower
    with the sequence sharded; the row records the per-device sequence
    shard and the modeled ring exposure.

    Gradient-accumulation microbatches come from the memory simulator
    (`pick_microbatches`) unless `microbatch` overrides them."""
    cfg, model = get_arch(arch_id)
    if shape_name in cfg.skip_shapes:
        return {"arch": arch_id, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "SKIP",
                "reason": "quadratic attention at 500k (DESIGN.md)"}
    if context_degree > 1:
        from repro.core.context import supports_cp
        shape0 = get_shape(shape_name)
        if shape0.kind != "train":
            return {"arch": arch_id, "shape": shape_name, "status": "SKIP",
                    "cp": context_degree,
                    "reason": "context parallelism is a training-path "
                              "feature (serving shards the KV cache "
                              "instead)"}
        if not supports_cp(model):
            return {"arch": arch_id, "shape": shape_name, "status": "SKIP",
                    "cp": context_degree,
                    "reason": f"{type(model).__name__} does not implement "
                              "the cp contract (cp_supported)"}
        if shape0.seq_len % (2 * context_degree):
            return {"arch": arch_id, "shape": shape_name, "status": "SKIP",
                    "cp": context_degree,
                    "reason": f"seq {shape0.seq_len} not divisible into "
                              f"{2 * context_degree} zigzag chunks"}
    if mesh_shape is not None:      # hillclimb: alternative factorization
        import math as _m
        assert _m.prod(mesh_shape) == (512 if multi_pod else 256)
        assert context_degree == 1, "--mesh-shape and --cp are exclusive"
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
        mesh = make_auto_mesh(mesh_shape, axes)
        dcfg = production_dcfg(multi_pod=multi_pod, zero3_global=zero3) \
            .with_(mesh_shape=mesh_shape)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod,
                                    context_degree=context_degree)
        dcfg = production_dcfg(multi_pod=multi_pod, zero3_global=zero3,
                               context_degree=context_degree)
    if remat is not None:
        dcfg = dcfg.with_(remat=remat)
    if comm_precision is not None:
        dcfg = dcfg.with_(comm_precision=comm_precision)

    # ---- measured-cost harvest + plan/memory records ----
    if harvest is None:
        harvest = bucket_mode in ("auto", "auto_dp")

    # microbatches: the simulator's stage-peak rule (calibrated against a
    # 1-device compile when harvesting), overridable per cell
    shape0 = get_shape(shape_name)
    mb = microbatch if microbatch is not None \
        else pick_microbatches(model, dcfg, shape0, calibrate=harvest)
    mb = min(mb, max(1, shape0.global_batch // dcfg.batch_dp))
    measured = None
    autowrap_rec = None
    memory_rec = None
    ring_rec = None
    mem_plan = None
    step_time_rec = None
    # bucket/memory plans (and thus harvest records) only exist on the
    # training stack — serving paths run prefill/decode without apply_stack
    if shape0.kind == "train":
        _, model0 = get_arch(arch_id)
        if hasattr(model0, "block_stats"):
            mb0 = mb
            b_local = max(1, shape0.global_batch // dcfg.batch_dp // mb0)
            bshape = (b_local, shape0.seq_len // max(1, dcfg.cp_size))
            dcfg_plan = dcfg.with_(microbatches=mb0, bucket_mode=bucket_mode,
                                   reorder=reorder)
            if harvest:
                measured = harvest_block_stats(model0, dcfg_plan, bshape)
                if measured is not None:
                    model0.measured_stats = measured
            if bucket_mode in ("auto", "auto_dp"):
                stats = model0.block_stats(dcfg_plan, bshape)
                autowrap_rec = _autowrap_record(model0, dcfg_plan, bshape,
                                                stats,
                                                measure_quant=harvest)
            # live-range memory model for the cell (core/memory): resolves
            # remat="auto:<GB>" to its policy vector before lowering and
            # feeds the modeled-vs-measured fits-in-HBM check below
            from repro.core.memory import plan_memory
            mstats = harvest_memory_stats(model0, dcfg_plan, bshape) \
                if harvest else None
            mem_plan = plan_memory(
                model0, dcfg_plan, batch_shape=bshape,
                stats=measured,
                act_scale=mstats.act_scale if mstats else 1.0)
            memory_rec = {
                "policy_spec": mem_plan.policy_spec,
                "offload_opt_state": mem_plan.offload_opt_state,
                "offload_residuals": mem_plan.offload_residuals,
                "bucket_override_n_buckets":
                    mem_plan.bucket_plan.n_buckets
                    if mem_plan.bucket_plan is not None else None,
                "modeled_peak_bytes": mem_plan.peak,
                "budget_bytes": mem_plan.budget_bytes,
                "cost_s": mem_plan.cost_s,
                "act_scale": mstats.act_scale if mstats else 1.0,
                "breakdown": [b.describe() for b in mem_plan.breakdown],
            }
            if dcfg.remat != mem_plan.policy_spec:
                dcfg = dcfg.with_(remat=mem_plan.policy_spec)
            # modeled step-time promise of the cell, analytic next to
            # calibrated: the analytic row prices the pure roofline
            # priors; the calibrated row re-plans with the harvested
            # measured BlockStats installed (equal when no harvest ran)
            try:
                from repro.core.api import plan_parallel
                from repro.core.obs import modeled_step_time
                saved_ms = getattr(model0, "measured_stats", None)
                try:
                    model0.measured_stats = None
                    p_a = plan_parallel(model0, dcfg_plan, shape0)
                    t_an = modeled_step_time(model0, p_a, shape0)
                    t_cal = t_an
                    if measured is not None:
                        model0.measured_stats = measured
                        p_c = plan_parallel(model0, dcfg_plan, shape0)
                        t_cal = modeled_step_time(model0, p_c, shape0)
                finally:
                    model0.measured_stats = saved_ms
                if t_an is not None:
                    step_time_rec = {
                        "step_time_us": t_an * 1e6,
                        "step_time_calibrated_us": t_cal * 1e6,
                    }
            except Exception as e:  # keep the cell alive on model gaps
                print(f"[step] modeled step time unavailable: {e}",
                      flush=True)
            if dcfg.cp_size > 1:
                # modeled ring-attention schedule of the cell (per layer):
                # hop sizes/compute and the exposed exchange time
                from repro.core.context import ring_cost
                ring_rec = ring_cost(cfg, dcfg_plan, bshape,
                                     window=cfg.sliding_window)

    # when the memory planner retightened buckets against the budget, the
    # cell must execute that partition (build_lowered re-applies the mode)
    bucket_mode_exec = mem_plan.bucket_plan \
        if mem_plan is not None and mem_plan.bucket_plan is not None \
        else bucket_mode
    t0 = time.time()
    lowered, model, shape, dcfg = build_lowered(arch_id, shape_name, dcfg,
                                                mesh, bucket_mode_exec,
                                                reorder,
                                                measured_stats=measured,
                                                microbatches=mb)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):     # older jax: one dict per device
        cost = cost[0] if cost else {}
    colls = parse_collectives(compiled.as_text(), dcfg)
    terms = roofline_terms(cost, colls, model, shape, dcfg)
    per_dev = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
               + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    rec = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "OK",
        "fits_hbm": bool(per_dev <= hw.HBM_BYTES),
        "mem": {
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "per_device_bytes": per_dev,
        },
        "collectives": colls,
        "roofline": terms,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "bucket_mode": bucket_mode, "reorder": reorder,
        "microbatches": mb,
        "comm_precision": dcfg.comm_precision,
    }
    if dcfg.cp_size > 1:
        rec["cp"] = dcfg.cp_size
        rec["seq_local"] = shape.seq_len // dcfg.cp_size
        if ring_rec is not None:
            rec["ring"] = {
                "hop_bytes": ring_rec["hop_bytes"],
                "hop_comm_us": ring_rec["hop_comm_s"] * 1e6,
                "hop_comp_us": ring_rec["hop_comp_s"] * 1e6,
                "live_hops": ring_rec["live_hops"],
                "exposed_us": ring_rec["exposed_s"] * 1e6,
            }
            print(f"[ctx] {arch_id} x {shape_name}: cp={dcfg.cp_size} "
                  f"seq/dev={rec['seq_local']} ring exposed "
                  f"{rec['ring']['exposed_us']:.1f}us "
                  f"(live hops {ring_rec['live_hops']}/{dcfg.cp_size})",
                  flush=True)
    if step_time_rec is not None:
        rec.update(step_time_rec)
    if autowrap_rec is not None:
        rec["autowrap"] = autowrap_rec
    if memory_rec is not None:
        # modeled (live-range simulator) vs measured (XLA memory_analysis),
        # side by side — the fits-in-HBM check now consumes BOTH
        gib = 1 / 1024**3
        modeled = memory_rec["modeled_peak_bytes"]
        memory_rec["measured_peak_bytes"] = per_dev
        memory_rec["modeled_over_measured"] = modeled / max(1.0, per_dev)
        rec["memory"] = memory_rec
        rec["fits_hbm_modeled"] = bool(modeled <= hw.HBM_BYTES)
        # the ONE audited modeled-vs-measured peak path (core/obs):
        # same gauges + format as trainer.memory_report
        from repro.core.obs import default_registry
        print("[mem] " + default_registry().record_peak(
            f"{arch_id} x {shape_name}", modeled, per_dev,
            budget_bytes=hw.HBM_BYTES,
            note=f"remat={memory_rec['policy_spec']}"), flush=True)
        if modeled > hw.HBM_BYTES:
            worst = max(mem_plan.breakdown, key=lambda b: b.peak_bytes)
            msg = (f"{arch_id} x {shape_name}: modeled peak "
                   f"{modeled*gib:.2f} GiB exceeds the "
                   f"{hw.HBM_BYTES*gib:.0f} GiB HBM budget on stage "
                   f"{worst.stage} ({worst.describe()}); tighten remat "
                   f"(remat='auto:{hw.HBM_BYTES*gib:.0f}'), raise "
                   f"microbatching, or add parallelism")
            rec["memory_error"] = msg
            print(f"[mem] OVER BUDGET: {msg}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--zero3", action="store_true")
    ap.add_argument("--bucket-mode", default="block")
    ap.add_argument("--remat", default=None,
                    help="override dcfg.remat: a policy, a per-segment "
                         "vector ('attn=full,mlp=fsdp_only'), or the "
                         "budgeted 'auto:<GB>' form")
    ap.add_argument("--no-reorder", action="store_true")
    ap.add_argument("--mesh-shape", default=None,
                    help="alternative factorization, e.g. 64,4")
    ap.add_argument("--cp", type=int, default=1,
                    help="context-parallel degree: carves a 'ctx' axis out "
                         "of the data axis (ring attention; train cells of "
                         "cp-capable archs only)")
    ap.add_argument("--comm-precision", default=None,
                    help="override dcfg.comm_precision: bf16 | fp8_ag | "
                         "fp8 | fp8_ef | int8_ag | int8 | int8_ef | auto "
                         "(per-bucket planner choice)")
    ap.add_argument("--microbatch", type=int, default=None,
                    help="override the simulator-picked gradient-"
                         "accumulation count")
    ap.add_argument("--harvest-stats", dest="harvest", action="store_true",
                    default=None,
                    help="force measured BlockStats harvesting (default: "
                         "only for auto bucket modes)")
    ap.add_argument("--no-harvest-stats", dest="harvest",
                    action="store_false")
    ap.add_argument("--tag", default=None, help="suffix for the result row")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCH_IDS:
            if a == "llama3_8b":
                continue
            for s in SHAPE_SUITE:
                cells.append((a, s.name))
    else:
        cells.append((args.arch, args.shape))

    results = []
    for a, s in cells:
        try:
            ms = tuple(int(x) for x in args.mesh_shape.split(",")) \
                if args.mesh_shape else None
            rec = run_cell(a, s, args.multi_pod,
                           bucket_mode=args.bucket_mode,
                           reorder=not args.no_reorder,
                           zero3=args.zero3, mesh_shape=ms,
                           microbatch=args.microbatch,
                           harvest=args.harvest, remat=args.remat,
                           context_degree=args.cp,
                           comm_precision=args.comm_precision)
            if args.tag:
                rec["tag"] = args.tag
        except Exception as e:
            rec = {"arch": a, "shape": s,
                   "mesh": "2x16x16" if args.multi_pod else "16x16",
                   "status": "FAIL", "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
        results.append(rec)
        status = rec["status"]
        extra = ""
        if status == "OK":
            r = rec["roofline"]
            extra = (f" mem={rec['mem']['per_device_bytes']/2**30:.2f}GiB"
                     f" fits={rec['fits_hbm']}"
                     f" dom={r['dominant']}"
                     f" comp={r['t_compute_s']:.3f}s"
                     f" coll={r['t_collective_s']:.3f}s")
        elif status == "FAIL":
            extra = " " + rec["error"][:160]
        print(f"[{status}] {a} x {s}{extra}", flush=True)

    os.makedirs(RESULTS_DIR, exist_ok=True)
    tag = "multipod" if args.multi_pod else "singlepod"
    out = args.out or os.path.join(RESULTS_DIR, f"dryrun_{tag}.json")
    existing = []
    if os.path.exists(out) and not args.all:
        existing = json.load(open(out))
        keep = {(r["arch"], r["shape"], r.get("tag")) for r in results}
        existing = [r for r in existing
                    if (r["arch"], r["shape"], r.get("tag")) not in keep]
    json.dump(existing + results, open(out, "w"), indent=1)
    print("wrote", out)


if __name__ == "__main__":
    main()
