"""Training launcher CLI.

Two run modes, one code path:

  * on an accelerator (JAX's default platform), the mesh spans the real
    devices: without --mesh it is (jax.device_count(), 1) over
    (data, model);
  * on the CPU (JAX_PLATFORMS=cpu), --devices N (or the size of --mesh)
    virtual CPU devices stand in for chips.

Selects any assigned architecture; mesh axes are DERIVED from the flags (2
entries -> (data, model), 3 -> (pod, data, model); --pp>1 prepends an
outermost 'pipe' axis), and the single Trainer routes through
`core/api.parallelize` — pp x dp x tp is a config flip. Each run starts
from a fresh checkpoint directory unless --ckpt-dir names one to resume.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3_1_7b --layers 7 \
      --steps 3 --seq 4096 --batch 1
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.train \
      --arch qwen3_1_7b --smoke --steps 50 --mesh 4,2
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.train \
      --arch qwen3_1_7b --smoke --steps 50 --mesh 2,2 --pp 2 \
      --pp-schedule zb --pp-microbatches 4
"""

import argparse
import contextlib
import dataclasses
import math
import tempfile

from repro.launch import jaxenv


def mesh_from_flags(mesh: str, pp: int, cp: int = 1) \
        -> tuple[tuple[int, ...], tuple[str, ...]]:
    """Mesh (shape, axes) from the --mesh/--pp/--cp flags.

    `mesh` names the non-pipe part: "D,M" -> (data, model), "P,D,M" ->
    (pod, data, model). --pp>1 prepends the 'pipe' axis OUTERMOST
    (core/pipeline layout convention: tiny point-to-point sends tolerate
    the slowest interconnect; fat FSDP gathers stay inner). --cp>1 inserts
    the 'ctx' axis BETWEEN data and model (ring ppermute traffic is
    lighter than FSDP gathers, heavier than pipe sends; TP psums stay
    innermost — core/context.py)."""
    shape = tuple(int(x) for x in mesh.split(","))
    if len(shape) == 2:
        axes: tuple[str, ...] = ("data", "model")
    elif len(shape) == 3:
        axes = ("pod", "data", "model")
    else:
        raise SystemExit(f"--mesh must have 2 or 3 entries, got {mesh!r}")
    if cp > 1:
        shape = (*shape[:-1], cp, shape[-1])
        axes = (*axes[:-1], "ctx", axes[-1])
    if pp > 1:
        return (pp, *shape), ("pipe", *axes)
    return shape, axes


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_1_7b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the stack to this many layers (0 = the "
                         "config's): one chip's stage of a deeper model")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--devices", type=int, default=0,
                    help="virtual CPU device count under JAX_PLATFORMS=cpu "
                         "(0 = sized to --mesh); not allowed elsewhere")
    ap.add_argument("--mesh", default=None,
                    help="non-pipe mesh: 'data,model' or 'pod,data,model' "
                         "(default: every device on 'data')")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline stages; >1 adds an outermost 'pipe' axis")
    ap.add_argument("--pp-schedule", default="auto",
                    choices=("auto", "gpipe", "1f1b", "interleaved", "zb"),
                    help="'auto' scores every valid schedule by modeled "
                         "bubble fraction + in-flight memory and picks the "
                         "argmin (core/api); the resolved pick is printed "
                         "in the plan line")
    ap.add_argument("--pp-virtual", type=int, default=0,
                    help="virtual stage chunks per rank for 'interleaved' "
                         "(0 = smallest divisor >= 2 of layers_per_stage)")
    ap.add_argument("--pp-microbatches", type=int, default=0,
                    help="pipeline microbatches M (0 = use the stage count)")
    ap.add_argument("--cp", type=int, default=1,
                    help="context-parallel degree; >1 inserts a 'ctx' axis "
                         "between data and model (zigzag seq sharding + "
                         "ring attention, cp-capable archs only)")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation microbatches (pp=1 only; "
                         "under --pp use --pp-microbatches)")
    ap.add_argument("--bucket-mode", default="block")
    ap.add_argument("--comm-precision", default="bf16",
                    choices=("bf16", "fp8_ag", "fp8", "fp8_ef",
                             "int8_ag", "int8", "int8_ef", "auto"),
                    help="collective wire precision (kernels/quant): bf16 "
                         "is bit-exact; fp8_ag quantizes param all-gathers "
                         "only; fp8 adds stochastically-rounded grad "
                         "reduce-scatters; fp8_ef adds the error-feedback "
                         "accumulator; int8_* are the same modes on the "
                         "int8 codec; 'auto' lets the bucket planner pick "
                         "per bucket from the full lattice")
    ap.add_argument("--no-reorder", action="store_true")
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory; a run resumes from the "
                         "newest checkpoint in it (default: a fresh "
                         "temporary directory)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--metrics-jsonl", default=None,
                    help="append a metrics-registry snapshot (step time, "
                         "tokens/s, wire bytes, drift gauges) here at "
                         "every log interval (core/obs)")
    ap.add_argument("--xprof-dir", default=None,
                    help="run the training loop under jax.profiler.trace "
                         "into this directory: the measured device trace, "
                         "with the step's named scopes on its ops and the "
                         "loop's host spans (core/obs.spans)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome/Perfetto trace of the executed "
                         "plan's MODELED timeline here after the run "
                         "(core/obs.plan_trace); no device measurement")
    ap.add_argument("--profile-out", default=None,
                    help="after the run, time the executed schedule's "
                         "segments and collectives one by one on the host's "
                         "wall clock (core/obs.profile_step; not a device "
                         "trace), write the frozen "
                         "MeasuredProfile JSON here plus a modeled-vs-"
                         "host-timed overlay trace next to it "
                         "(<profile-out>.trace.json)")
    ap.add_argument("--replan-threshold", type=float, default=None,
                    help="arm profile-guided replanning: mean |rel| "
                         "step-time drift above this for --replan-patience "
                         "consecutive steps triggers profile_step + replan "
                         "(core/obs)")
    ap.add_argument("--replan-patience", type=int, default=3)
    ap.add_argument("--replan-apply", action="store_true",
                    help="restart the loop onto the replanned ParallelPlan "
                         "(default: log the delta only)")
    args = ap.parse_args()

    if args.devices and not jaxenv.on_cpu():
        raise SystemExit("--devices needs JAX_PLATFORMS=cpu")
    if args.mesh:
        mesh_shape, mesh_axes = mesh_from_flags(args.mesh, args.pp, args.cp)
        jaxenv.set_cpu_device_count(args.devices or math.prod(mesh_shape))
    else:
        jaxenv.set_cpu_device_count(args.devices)

    import logging

    import jax
    import jax.numpy as jnp

    from repro.core.dist import DistConfig
    from repro.models.common import ShapeConfig
    from repro.models.registry import build_model, get_arch, get_arch_for_pp
    from repro.optim.adamw import AdamWConfig
    from repro.train.trainer import Trainer, TrainerConfig

    logging.basicConfig(level=logging.INFO)
    print(f"compile cache: {jaxenv.init_compile_cache()}")
    if not args.mesh:
        data = max(1, jax.device_count() // (args.pp * args.cp))
        mesh_shape, mesh_axes = mesh_from_flags(f"{data},1", args.pp, args.cp)
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="repro_train_")
    print(f"devices: {jax.device_count()} x {jax.devices()[0].device_kind}; "
          f"checkpoints: {ckpt_dir}")
    dcfg = DistConfig(
        mesh_axes=mesh_axes, mesh_shape=mesh_shape,
        pp_axis="pipe" if args.pp > 1 else None,
        pp_schedule=args.pp_schedule,
        pp_virtual=args.pp_virtual,
        pp_microbatches=args.pp_microbatches,
        cp_axis="ctx" if args.cp > 1 else None,
        # the ctx axis joins the FSDP domain: params shard over data x ctx
        # so cross-ctx grads ride explicit collectives (core/context.py)
        fsdp_axes=("data", "ctx") if args.cp > 1 else ("data",),
        param_dtype=jnp.bfloat16, reduce_dtype=jnp.float32,
        bucket_mode=args.bucket_mode, reorder=not args.no_reorder,
        comm_precision=args.comm_precision,
        microbatches=args.microbatches,
        grad_compression=args.grad_compression)
    if args.pp > 1:
        # smoke stacks too shallow to partition get the registry override
        cfg, model = get_arch_for_pp(args.arch, n_stages=args.pp,
                                     smoke=args.smoke)
    else:
        cfg, model = get_arch(args.arch, smoke=args.smoke)
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
        model = build_model(cfg)
    shape = ShapeConfig("train", args.seq, args.batch, "train")
    tcfg = TrainerConfig(total_steps=args.steps, ckpt_every=args.steps,
                         log_every=5, warmup=10, ckpt_dir=ckpt_dir,
                         metrics_jsonl=args.metrics_jsonl,
                         replan_threshold=args.replan_threshold,
                         replan_patience=args.replan_patience,
                         replan_apply=args.replan_apply)
    trainer = Trainer(model, dcfg, shape, AdamWConfig(lr=args.lr), tcfg)
    print(f"plan: {trainer.plan.describe()}")
    with (jax.profiler.trace(args.xprof_dir) if args.xprof_dir
          else contextlib.nullcontext()):
        _, _, hist = trainer.run()
    if args.xprof_dir:
        print(f"xprof: {args.xprof_dir}")
    print(f"done: loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}")
    if trainer.drift.records:
        print(trainer.drift.report())
    if trainer.replans:
        last = trainer.replans[-1]
        print(f"replan: changed={last['changed']} "
              f"applied={last['applied']} gain={last['modeled_gain_s']}")
    profile = trainer.profile
    if args.profile_out:
        from repro.core.obs import profile_step
        if profile is None:
            # reuse the measured wall from the run so the profiler only
            # has to time segments/collectives, not re-drive full steps
            rows = trainer.drift.records.get("step_time", [])
            wall = rows[-1]["measured"] if rows else None
            profile = profile_step(model, trainer.plan, shape,
                                   wall_step_s=wall)
        profile.save(args.profile_out)
        print(f"profile: {args.profile_out} "
              f"(wall {profile.wall_step_s:.4f}s, "
              f"{len(profile.spans)} spans)")
    if args.trace_out or (args.profile_out and profile is not None):
        from repro.core.obs import plan_trace
        out = args.trace_out or f"{args.profile_out}.trace.json"
        tb = plan_trace(model, trainer.plan, shape, arch_cfg=cfg,
                        profile=profile)
        tb.save(out)
        print(f"trace: {out} ({len(tb.events)} events; "
              f"{'overlay' if profile is not None else 'modeled only'}; "
              f"open in Perfetto)")


if __name__ == "__main__":
    main()
