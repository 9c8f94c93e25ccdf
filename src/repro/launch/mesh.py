"""Production mesh definitions (TPU v5e).

Single pod: 16x16 = 256 chips, axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model); the 'pod' axis
crosses DCN. With pipeline parallelism the 'pipe' axis is carved out of the
data axis and placed OUTERMOST (per-slot pipeline traffic is one small
point-to-point activation send, so it tolerates the slowest interconnect,
while FSDP gathers and TP psums stay on the inner ICI axes — see
core/pipeline.py for the layout convention). With context parallelism the
'ctx' axis is also carved out of the data axis and sits BETWEEN data and
model: its per-hop ring ppermute traffic (one KV block per layer per hop,
core/context.py) is lighter than the fat FSDP all-gathers riding 'data' but
heavier than pipeline sends, while the highest-frequency TP psums keep the
innermost axis.  The ctx axis joins `fsdp_axes` (parameters shard over
data x ctx) so every cross-ctx gradient is an explicit collective. Defined
as FUNCTIONS so importing this module never touches jax device state (the
dry-run pins a fake 512-device platform first).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.dist import DistConfig, make_auto_mesh


def _production_layout(multi_pod: bool, pipeline_stages: int,
                       context_degree: int = 1):
    inner = pipeline_stages * context_degree
    if inner > 1 and 16 % inner:
        raise ValueError(
            f"pipeline_stages={pipeline_stages} x context_degree="
            f"{context_degree} must divide the 16-chip data axis")
    data = 16 // inner
    shape: tuple[int, ...] = (data,)
    axes: tuple[str, ...] = ("data",)
    if context_degree > 1:
        shape = shape + (context_degree,)
        axes = axes + ("ctx",)
    shape, axes = shape + (16,), axes + ("model",)
    if multi_pod:
        shape, axes = (2,) + shape, ("pod",) + axes
    if pipeline_stages > 1:
        shape, axes = (pipeline_stages,) + shape, ("pipe",) + axes
    return shape, axes


def make_production_mesh(*, multi_pod: bool = False,
                         pipeline_stages: int = 1,
                         context_degree: int = 1):
    shape, axes = _production_layout(multi_pod, pipeline_stages,
                                     context_degree)
    return make_auto_mesh(shape, axes)


def production_dcfg(*, multi_pod: bool = False, zero3_global: bool = False,
                    pipeline_stages: int = 1, pp_schedule: str = "auto",
                    context_degree: int = 1, **overrides) -> DistConfig:
    """bf16 training config on the production mesh. Default multi-pod
    sharding is HSDP (shard in-pod, replicate across pods — bounded DCN
    traffic); zero3_global shards over pod x data instead.
    pipeline_stages > 1 adds an outermost 'pipe' axis ('auto' by default:
    plan_parallel scores gpipe/1f1b/interleaved/zb by modeled bubble
    fraction and picks the argmin, see core/pipeline.py + core/api.py);
    context_degree > 1 adds the 'ctx' axis between data and model (ring
    attention, core/context.py) and folds it into the FSDP domain."""
    shape, axes = _production_layout(multi_pod, pipeline_stages,
                                     context_degree)
    fsdp = ("pod", "data") if (multi_pod and zero3_global) else ("data",)
    if context_degree > 1:
        fsdp = fsdp + ("ctx",)
    base = dict(
        mesh_axes=axes, mesh_shape=shape, fsdp_axes=fsdp,
        param_dtype=jnp.bfloat16, reduce_dtype=jnp.float32,
        storage_dtype=jnp.float32,
    )
    if pipeline_stages > 1:
        base.update(pp_axis="pipe", pp_schedule=pp_schedule)
    if context_degree > 1:
        base.update(cp_axis="ctx")
    base.update(overrides)
    return DistConfig(**base)


def production_dcfg_for(arch_cfg, *, shape=None, model=None,
                        **kw) -> DistConfig:
    """Production DistConfig honouring the arch's recommended pipeline
    degree (`ArchConfig.pp_stages`): validates that stages split the layer
    stack evenly before carving the pipe axis out of the data axis.

    When the workload `shape` (models/common.ShapeConfig) and the `model`
    instance are given, the gradient-accumulation microbatch count is
    picked automatically from the memory simulator's stage peaks
    (core/memory.auto_microbatches — the modeled-peak-fits-HBM rule that
    replaced the dry-run's hand-kept MICROBATCH table)."""
    stages = arch_cfg.pp_stages
    if stages > 1 and arch_cfg.n_layers % stages:
        raise ValueError(
            f"{arch_cfg.name}: pp_stages={stages} does not divide "
            f"n_layers={arch_cfg.n_layers}")
    dcfg = production_dcfg(pipeline_stages=stages, **kw)
    if shape is not None and model is not None and shape.kind == "train":
        from repro.core.memory import auto_microbatches
        stage = model.stage_spec(stages) if stages > 1 else None
        # the pick is a DIVISOR of the per-device rows (the step reshapes
        # rows into equal microbatches) and, under pp, the pipeline M
        # itself — simulated with that M in flight (GPipe holds all M)
        mb = auto_microbatches(model, dcfg, shape, stage=stage)
        if stages > 1:
            dcfg = dcfg.with_(pp_microbatches=mb)
        elif mb > 1:
            dcfg = dcfg.with_(microbatches=mb)
    return dcfg
