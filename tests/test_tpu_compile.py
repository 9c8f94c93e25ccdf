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

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P
from jax.sharding import SingleDeviceSharding

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


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_adamw_compiles(one_chip, dtype):
    p = jax.ShapeDtypeStruct((2048, 6144), dtype, sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    _compiled_text(lambda p, g, m, v, t: adamw_update_pallas(
        p, g, m, v, t=t, **ADAMW), p, p, p, p, t)


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
