"""Distributed configuration: mesh axes, FSDP domain, dtypes, schedule flags.

One frozen `DistConfig` object flows through the whole system (models, core,
train/serve steps). It is the JAX-side analogue of the paper's
``torch._inductor.config.simplefsdp.*`` knobs plus the DTensor device-mesh.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp


Dtype = Any  # jnp dtype-like

# Wire-precision vocabulary for the bucket collectives (see
# DistConfig.comm_precision) and the per-bucket lattice the auto_dp planner
# searches over.  'fp8'/'int8' (stateless SR reduce-scatter, no error
# feedback) are valid config values but not in the auto lattice: at equal
# wire bytes the *_ef variants strictly dominate them on convergence.
# int8 and fp8 occupy the same wire format (1 byte/elem + per-chunk f32
# scales), so the planner can only separate them through a measured codec
# rate (`irgraph.set_measured_quant_rate(rate, codec)`, harvested by
# `launch/dryrun.harvest_quant_timing` / the step profiler); the int8
# entries sit AFTER fp8 in the lattice, and every planner improves on
# strict `<` only — with no measured rates installed the resolved plans
# are unchanged.
COMM_PRECISIONS = ("bf16", "fp8_ag", "fp8", "fp8_ef",
                   "int8_ag", "int8", "int8_ef", "auto")
AUTO_PRECISIONS = ("bf16", "fp8_ag", "fp8_ef", "int8_ag", "int8_ef")


def precision_codecs(precision: str) -> tuple[str | None, str | None]:
    """(all-gather codec, reduce-scatter codec) of one RESOLVED precision —
    None means uncompressed.  'auto' must be resolved to a per-bucket
    precision by the planner before reaching here."""
    return {
        "bf16": (None, None),
        "fp8_ag": ("fp8", None),
        "fp8": ("fp8", "fp8"),
        "fp8_ef": ("fp8", "fp8"),
        "int8_ag": ("int8", None),
        "int8": ("int8", "int8"),
        "int8_ef": ("int8", "int8"),
    }[precision]


@dataclasses.dataclass(frozen=True)
class DistConfig:
    # Mesh ------------------------------------------------------------------
    # No default: a mesh is sized to the devices a run has (the launchers
    # derive it from jax.device_count()).
    mesh_shape: tuple[int, ...]
    mesh_axes: tuple[str, ...] = ("data", "model")
    # ZeRO-3 sharding domain for parameters/grads/optimizer states.
    # ('data',)        -> HSDP when a 'pod' axis exists (shard in-pod,
    #                     replicate across pods, grad all-reduce over 'pod')
    # ('pod', 'data')  -> global ZeRO-3 over every data-parallel chip
    fsdp_axes: tuple[str, ...] = ("data",)
    tp_axis: str | None = "model"

    # Pipeline parallelism (paper SS4 "Pipeline Parallel") --------------------
    # When set, the named mesh axis holds one pipeline stage per rank: stage
    # parameters are ordinary SimpleFSDP storage sharded over `fsdp_axes`
    # WITHIN each pipe rank, and activations stream between stages with
    # ppermute inside the same shard_map (core/pipeline.py).  Convention:
    # 'pipe' is the OUTERMOST mesh axis — per-slot activation traffic is tiny
    # point-to-point, so it tolerates the slowest interconnect, while the fat
    # FSDP gathers stay on the inner (ICI) axes.
    pp_axis: str | None = None
    # 'gpipe' | '1f1b' | 'interleaved' | 'zb' | 'auto'.  'interleaved' gives
    # each pipe rank V non-contiguous virtual stage slices (bubble / V);
    # 'zb' splits the backward into input-grad (Bx) and weight-grad (W)
    # halves so the W work fills the cooldown bubble; 'auto' lets
    # plan_parallel score every valid schedule (bubble_fraction + the memory
    # simulator) and pick the argmin (core/pipeline.py, core/api.py).
    pp_schedule: str = "gpipe"
    # Virtual stages per pipe rank for the interleaved schedule (0 = let the
    # planner pick the smallest divisor >= 2 of layers_per_stage).  Ignored
    # by the other schedules.
    pp_virtual: int = 0
    # Expected microbatch count M per pipelined step; 0 accepts any M.
    # When set, pipeline_grads rejects an xs stack whose leading dim
    # disagrees (M is otherwise inferred from xs).  GPipe keeps M live
    # activations per stage; 1F1B bounds that to S (see core/pipeline.py).
    pp_microbatches: int = 0

    # Context parallelism (core/context.py) -----------------------------------
    # When set, the named mesh axis shards the SEQUENCE dimension: every
    # batch row is split into load-balanced zigzag chunks (rank r owns
    # chunks r and 2*cp-1-r of 2*cp, so each rank carries equal causal
    # attention work) and attention runs as a ring — KV blocks circulate
    # over the ctx axis via ppermute, the next hop's exchange overlapped
    # behind the current chunk's attention compute.  Convention: 'ctx' sits
    # BETWEEN the data and model axes — its per-hop ppermute traffic (one
    # KV block) is lighter than the fat FSDP all-gathers on 'data' but
    # heavier/more frequent than pipeline sends, while TP psums stay
    # innermost.  The ctx axis must be part of `fsdp_axes`: parameters are
    # then ZeRO-3 sharded over data x ctx and every cross-rank gradient
    # flow (bucket reduce-scatter, ring reverse permute) is an explicit
    # collective with an exact transpose — no reliance on vma
    # replication-transpose (exact on every jax, like core/pipeline).
    cp_axis: str | None = None

    # Mixed precision (paper SS4) --------------------------------------------
    param_dtype: Dtype = jnp.bfloat16    # forward/backward compute dtype
    reduce_dtype: Dtype = jnp.float32    # gradient reduce-scatter dtype
    storage_dtype: Dtype = jnp.float32   # sharded master weights

    # Beyond-paper: cast to param_dtype BEFORE the all-gather (halves AG
    # bytes). The paper gathers in param_dtype too via DTensor forward_dtype;
    # turning this off gathers in storage_dtype (the naive ZeRO-3 baseline).
    gather_in_param_dtype: bool = True

    # SimpleFSDP schedule knobs (paper SS3.2, Tables 5/6) ----------------------
    # 'none' | 'block' | 'auto' (greedy Alg. 1) | 'auto_dp' (exposure-
    # minimizing DP, core/autowrap.py) | an explicit BucketPlan.
    bucket_mode: str = "block"
    reorder: bool = True                 # prefetch next bucket (reordering)
    # Pipeline the prefetch at BUCKET granularity when the model declares
    # block segments (models/common.BlockSegments): segment b's compute
    # overlaps bucket b+1's all-gather within the layer, and the last bucket
    # prefetches layer i+1's first bucket across the boundary. Off = one
    # whole-layer gather point per layer (the pre-v2 schedule).
    segment_prefetch: bool = True
    # Table 6 ablation: issue the prefetch AG before (True) or after (False)
    # the current block's compute, in forward and backward respectively.
    ag_before_wait_fwd: bool = True
    ag_before_wait_bwd: bool = False
    # Delay each reduce-scatter by one layer so it overlaps the next layer's
    # backward compute (paper: "Wr12 placed before RS34").
    rs_delay: bool = True

    # Memory policy -----------------------------------------------------------
    # Activation-checkpoint spec (core/remat.py, ONE vocabulary):
    #   'none' | 'fsdp_only' | 'full' | 'save_dots'   — uniform policy
    #   'auto:<GB>'    — budgeted auto-SAC: core/memory picks the cheapest
    #                    per-segment vector (+ offload) whose modeled peak
    #                    fits the per-device HBM budget (resolved once by
    #                    core/api.plan_parallel)
    #   'attn=full,mlp=fsdp_only' — an explicit per-segment vector
    remat: str = "fsdp_only"
    # Auto-wrap memory cap (paper Alg. 1 M_max), bytes of prefetched params.
    autowrap_mem_limit: float = 1.0 * 1024**3

    # Gradient compression: reduce-scatter in bf16 with fp32 master accumulate.
    grad_compression: bool = False

    # Quantized collectives (kernels/quant): per-128-chunk-scaled fp8 e4m3
    # wire format for the bucket collectives.  Modes:
    #   'bf16'    — off (bit-exact today's path; the name is the wire story:
    #               payloads already travel in param/reduce dtype)
    #   'fp8_ag'  — quantize param all-gathers only (deterministic RTN;
    #               grads stay full precision)
    #   'fp8'     — AG + stochastically-rounded grad reduce-scatter
    #               (unbiased, stateless — Markov et al.'s SR condition)
    #   'fp8_ef'  — 'fp8' plus a persistent per-shard error-feedback
    #               accumulator in the optimizer state compensating the
    #               reduced shard's wire format (optim/adamw.py)
    #   'int8_ag' / 'int8' / 'int8_ef' — the same three modes on the int8
    #               wire codec (identical wire bytes; chosen over fp8 only
    #               when a measured codec rate makes it cheaper)
    #   'auto'    — the auto_dp planner picks per-BUCKET from
    #               AUTO_PRECISIONS (bf16 + the fp8/int8 *_ag and *_ef
    #               variants) jointly with the partition
    comm_precision: str = "bf16"

    # Quantized KV cache: serving caches/pages store wire-codec values +
    # per-128-chunk f32 scales (kernels/quant — the SAME audited codec the
    # quantized collectives use).  'int8' | 'fp8' | None.  The legacy
    # ``kv_cache_int8`` bool is kept as an alias for codec='int8'.
    kv_cache_codec: str | None = None
    kv_cache_int8: bool = False

    # Microbatching (gradient accumulation) for activation memory.
    microbatches: int = 1

    def __post_init__(self):
        if self.comm_precision not in COMM_PRECISIONS:
            raise ValueError(
                f"comm_precision={self.comm_precision!r} not in "
                f"{COMM_PRECISIONS}")
        if self.kv_cache_codec not in (None, "int8", "fp8"):
            raise ValueError(
                f"kv_cache_codec={self.kv_cache_codec!r} not in "
                f"(None, 'int8', 'fp8')")

    @property
    def kv_codec(self) -> str | None:
        """Resolved KV-cache wire codec (kernels/quant vocabulary)."""
        return self.kv_cache_codec or ("int8" if self.kv_cache_int8
                                       else None)

    # ------------------------------------------------------------------ utils
    @property
    def needs_ef(self) -> bool:
        """Whether the optimizer state carries the error-feedback
        accumulator: the *_ef modes always, 'auto' too (the planner may
        assign an _ef precision to any bucket, and the state tree's
        structure must not depend on the plan)."""
        return self.comm_precision in ("fp8_ef", "int8_ef", "auto")

    def axis_size(self, name: str) -> int:
        return self.mesh_shape[self.mesh_axes.index(name)]

    @property
    def axis_sizes(self) -> dict[str, int]:
        return dict(zip(self.mesh_axes, self.mesh_shape))

    @property
    def fsdp_size(self) -> int:
        return math.prod(self.axis_size(a) for a in self.fsdp_axes)

    @property
    def tp_size(self) -> int:
        return self.axis_size(self.tp_axis) if self.tp_axis else 1

    @property
    def pp_size(self) -> int:
        """Number of pipeline stages (1 when no pipe axis is configured)."""
        return self.axis_size(self.pp_axis) if self.pp_axis else 1

    @property
    def cp_size(self) -> int:
        """Context-parallel degree (1 when no ctx axis is configured)."""
        return self.axis_size(self.cp_axis) if self.cp_axis else 1

    @property
    def dp_total(self) -> int:
        """Total data-parallel ways = every axis that is not TP or PP.

        Pipe ranks hold DIFFERENT stage parameters and see the same
        microbatch stream, so the pipe axis is neither a data- nor a
        tensor-parallel domain.  The ctx axis COUNTS here: cp ranks hold
        disjoint token shards of the same rows, so the per-device-mean
        gradient convention (reduce-scatter divides by dp_total) treats
        sequence shards exactly like batch shards.
        """
        return math.prod(
            s for a, s in self.axis_sizes.items()
            if a != self.tp_axis and a != self.pp_axis
        )

    @property
    def batch_dp(self) -> int:
        """Batch-ROW sharding ways: dp_total without the ctx axis (cp ranks
        replicate rows and shard the sequence dim instead)."""
        return self.dp_total // self.cp_size

    @property
    def grad_sync_axes(self) -> tuple[str, ...]:
        """Axes over which params are replicated (grads need all-reduce).

        Under HSDP the 'pod' axis replicates parameters, so gradients are
        psum'ed over it after the in-pod reduce-scatter.  The pipe axis is
        excluded: each pipe rank owns a distinct stage, nothing to sync.
        """
        return tuple(
            a for a in self.mesh_axes
            if a not in self.fsdp_axes and a != self.tp_axis
            and a != self.pp_axis
        )

    @property
    def n_devices(self) -> int:
        return math.prod(self.mesh_shape)

    def with_(self, **kw) -> "DistConfig":
        return dataclasses.replace(self, **kw)


def make_auto_mesh(shape, axes, devices=None) -> jax.sharding.Mesh:
    """`jax.make_mesh` with Auto axis types (its default is Explicit); the
    steps place data with shard_map specs, not with sharding-in-types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_mesh(cfg: DistConfig, devices=None) -> jax.sharding.Mesh:
    return make_auto_mesh(cfg.mesh_shape, cfg.mesh_axes, devices=devices)


def single_device_config(**kw) -> DistConfig:
    """A 1x1 mesh config — used by smoke tests and eager debugging."""
    defaults = dict(mesh_axes=("data", "model"), mesh_shape=(1, 1))
    defaults.update(kw)
    return DistConfig(**defaults)
