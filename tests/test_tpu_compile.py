"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: each case lowers and compiles at real widths for a chip that
is described but not attached, which surfaces what Mosaic refuses (casts,
ops with no lowering, vma typing inside shard_map) without a chip. Every
compiled program must hold a `tpu_custom_call`, i.e. the kernel itself and
not a fallback.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and this file's tests
stay together on the worker that loads it.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.adamw.kernel import BLOCK_BYTES, block_plan
from repro.kernels.adamw.ops import adamw_update_pallas
from repro.kernels.quant.ops import roundtrip_pallas
from repro.kernels.rmsnorm.ops import rmsnorm_pallas

ADAMW = dict(lr=3e-4, b1=0.9, b2=0.95, eps=1e-8, wd=0.1)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("shape", [(4096, 2048), (4096, 16, 128)],
                         ids=["hidden", "qk_norm"])
def test_rmsnorm_compiles(one_chip, shape):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct(shape[-1:], jnp.bfloat16, sharding=one_chip)
    _compiled_text(lambda x, w: rmsnorm_pallas(x, w, 1e-6, False), x, w)


def _leaf_ops(text: str, n: int) -> list[str]:
    """The ops of the compiled entry computation whose result (or first
    result, for a tuple) has n elements."""
    entry = text[text.index("\nENTRY"):]
    ops = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) ([\w-]+)\(", line)
        dims = m and re.match(r"\(?\w+\[([\d,]*)\]", m.group(1))
        if dims and dims.group(1) and math.prod(
                int(d) for d in dims.group(1).split(",")) == n:
            ops.append(m.group(2))
    return ops


@pytest.mark.parametrize("shape,dtype", [
    pytest.param((2048, 6144), jnp.bfloat16, id="bf16"),
    pytest.param((2048, 6144), jnp.float32, id="f32"),
    # the cells' largest leaves: the tied embedding and a stacked matrix,
    # with and without their tp axis of 1
    pytest.param((311164928,), jnp.float32, id="embed-f32"),
    pytest.param((1, 311164928), jnp.float32, id="embed-tp-f32"),
    pytest.param((7, 12582912), jnp.float32, id="stacked-f32"),
    pytest.param((7, 1, 12582912), jnp.float32, id="stacked-tp-f32"),
    # the same leaves behind a tp axis of 2, as a TP-sharded leaf is stored
    # before shard_map hands each device its (1, chunk) row
    pytest.param((2, 155582464), jnp.float32, id="embed-tp2-f32"),
    pytest.param((7, 2, 6291456), jnp.bfloat16, id="stacked-tp2-bf16"),
])
def test_adamw_compiles(one_chip, shape, dtype):
    """The kernel compiles at the leaf's shape with grid steps that move at
    least half a block of the rows the view has (a view of fewer than 8
    rows fills only that share of a block), within the VMEM limit its plan
    sets, updates p, m and v in place, and the leaf reaches and leaves it
    through bitcasts alone."""
    plan = block_plan(shape, dtype)
    rows = min(plan.view[1], 8)
    assert plan.steps <= 2 * 4 * math.prod(shape) * 8 // (
        rows * BLOCK_BYTES) + 1
    assert plan.vmem_bytes <= plan.vmem_limit
    p = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    g = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    s = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    # the step donates the train state, as here
    text = jax.jit(lambda p, g, m, v, t, s: adamw_update_pallas(
        p, g, m, v, t=t, scale=s, **ADAMW), donate_argnums=(0, 2, 3)).lower(
        p, g, p, p, t, s).compile().as_text()
    call = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(call) == 1
    assert "output_to_operand_aliasing={{0}: (1, {}), {1}: (3, {}), " \
        "{2}: (4, {})}" in call[0]
    ops = set(_leaf_ops(text, math.prod(shape)))
    assert "custom-call" in ops
    assert ops <= {"parameter", "bitcast", "custom-call",
                   "get-tuple-element", "tuple"}, ops


@pytest.mark.parametrize("codec", ["fp8", "int8"])
@pytest.mark.parametrize("stochastic", [False, True], ids=["rtn", "sr"])
def test_quant_roundtrip_compiles(one_chip, codec, stochastic):
    x = jax.ShapeDtypeStruct((2048, 6144), jnp.float32, sharding=one_chip)
    _compiled_text(lambda x: roundtrip_pallas(x, codec, stochastic), x)


def test_kernels_compile_inside_shard_map(topo):
    """The models call the kernels inside shard_map with check_vma on:
    every out_shape must carry its operands' vma."""
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1),
                ("data", "model"))

    def step(x, w, p, g, m, v, t):
        y = rmsnorm_pallas(x, w, 1e-6, False)
        return (y, *adamw_update_pallas(p, g, m, v, t=t, **ADAMW))

    row, rep = P("data"), P()
    fn = jax.shard_map(step, mesh=mesh,
                       in_specs=(row, rep, row, row, row, row, rep),
                       out_specs=(row, row, row, row))
    x = jax.ShapeDtypeStruct((4096, 2048), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((2048,), jnp.bfloat16)
    p = jax.ShapeDtypeStruct((2048, 6144), jnp.float32)
    t = jax.ShapeDtypeStruct((), jnp.int32)
    text = _compiled_text(fn, x, w, p, p, p, p, t)
    assert text.count("tpu_custom_call") >= 2
