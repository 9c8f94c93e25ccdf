"""SimpleFSDP-JAX core: the paper's contribution as a composable library.

Layers (see DESIGN.md):
  dist        DistConfig — mesh axes, FSDP domain, dtypes, schedule flags
  meta        ParamMeta — ZeRO-3 flat-shard storage layout
  collectives replicate/gather_group — the differentiable parametrization
  remat       selective-AC policies (re-gather in backward)
  bucketing   BucketPlan — manual wrapping; plan_for memoizes auto plans
  autowrap    greedy Algorithm 1 + exposure-minimizing DP — auto wrapping
  stack       apply_stack — bucketed + reordered (prefetch) layer stacks,
              pipelined at bucket granularity for segmented blocks
  pipeline    gpipe / 1F1B schedules over a 'pipe' mesh axis (paper SS4)
  context     zigzag sequence sharding + ring attention over a 'ctx' axis
              (context parallelism; reverse-ring exact gradients)
  api         parallelize() + ParallelPlan — the single entry point
              (simple_fsdp kept as the deprecated bring-your-own-module
              shim)
"""

from repro.core.api import (ParallelPlan, Parallelized, build_metas,
                            parallelize, plan_parallel, shard_params,
                            simple_fsdp, unshard_params)
from repro.core.autowrap import (auto_dp_plan, auto_plan, exposed_comm_time,
                                 partition_exposure)
from repro.core.bucketing import (BucketPlan, manual_plan, per_param_plan,
                                  whole_block_plan)
from repro.core.collectives import gather_group, replicate, replicate_tree
from repro.core.context import (ring_attention, ring_cost, zigzag_batch,
                                zigzag_positions)
from repro.core.dist import DistConfig, make_mesh, single_device_config
from repro.core.irgraph import BlockStats
from repro.core.meta import (ParamMeta, abstract_storage, from_storage,
                             storage_specs, to_storage)
from repro.core.pipeline import (fsdp_stage_fn, gpipe, gpipe_grads,
                                 one_f_one_b, pipe_shift, pipeline_grads,
                                 pipeline_loss_grads)
from repro.core.remat import checkpoint_policy, maybe_remat
from repro.core.stack import apply_stack

__all__ = [
    "BlockStats", "BucketPlan", "DistConfig", "ParallelPlan",
    "Parallelized", "ParamMeta", "abstract_storage", "apply_stack",
    "auto_dp_plan", "auto_plan", "build_metas", "checkpoint_policy",
    "exposed_comm_time", "from_storage", "fsdp_stage_fn", "gather_group",
    "gpipe", "gpipe_grads", "make_mesh", "manual_plan", "maybe_remat",
    "one_f_one_b", "parallelize", "partition_exposure", "per_param_plan",
    "pipe_shift", "pipeline_grads", "pipeline_loss_grads", "plan_parallel",
    "replicate", "replicate_tree", "ring_attention", "ring_cost",
    "shard_params", "simple_fsdp", "single_device_config",
    "storage_specs", "to_storage", "unshard_params", "whole_block_plan",
    "zigzag_batch", "zigzag_positions",
]
