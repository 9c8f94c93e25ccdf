"""System-level behaviour: the paper's semantics visible in lowered HLO.

These check the *structural* claims — bucketing reduces collective count,
buckets merge payloads, schedules lower coherently — on a small single-device
lowering (collective counts are read from the pre-optimization stablehlo,
which preserves program structure).
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import DistConfig, make_mesh
from repro.models import runtime as RT
from repro.models.common import ShapeConfig
from repro.models.registry import get_arch

DCFG = DistConfig(mesh_axes=("data", "model"), mesh_shape=(1, 1),
                  param_dtype=jnp.float32, reduce_dtype=jnp.float32)


def _lower(bucket_mode, reorder):
    cfg, model = get_arch("qwen3_1_7b", smoke=True)
    dcfg = DCFG.with_(bucket_mode=bucket_mode, reorder=reorder)
    shape = ShapeConfig("t", 32, 2, "train")
    storage = RT.init_storage(model, jax.random.PRNGKey(0), dcfg)
    batch = {
        "tokens": jnp.zeros((2, 32), jnp.int32),
        "targets": jnp.zeros((2, 32), jnp.int32),
        "valid": jnp.ones((2, 32)),
    }
    step = RT.make_loss_step(model, dcfg)
    specs = RT.model_storage_specs(model, dcfg)
    fn, mesh = RT.wrap_step(model, dcfg, shape, step, (P(), specs))
    return fn.lower(storage, batch).as_text()


def _count(txt, op):
    return len(re.findall(rf"stablehlo\.{op}\b", txt))


def test_bucketing_reduces_collective_count():
    """Per-block bucketing merges per-parameter all-gathers (paper SS3.2.1).
    Needs fsdp>1 so the FSDP gathers actually lower — delegated to the
    multi-device harness."""
    from tests.test_distributed import _run
    _run("hlo_structure")


def test_reorder_path_lowers_with_buckets():
    txt = _lower("block", True)
    assert _count(txt, "all_gather") > 0
    assert _count(txt, "reduce_scatter") > 0


def test_auto_wrap_plan_lowers():
    txt = _lower("auto", True)
    assert _count(txt, "all_gather") > 0


def test_quickstart_example_runs():
    import subprocess
    import sys
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    out = subprocess.run([sys.executable, "examples/quickstart.py"],
                         capture_output=True, text=True, timeout=540,
                         env=env, cwd=root)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "loss" in out.stdout
