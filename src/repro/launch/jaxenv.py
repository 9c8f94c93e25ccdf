"""Process set-up shared by the entry points (chip_smoke.py, launch/train,
launch/serve): the virtual CPU device count and the persistent compile
cache. Library code and tests never call these; both must run before JAX
initialises its backends.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
COMPILE_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def on_cpu() -> bool:
    """Whether JAX is pinned to the CPU (``JAX_PLATFORMS=cpu``)."""
    return os.environ.get("JAX_PLATFORMS", "") == "cpu"


def set_cpu_device_count(n: int) -> None:
    """Give the CPU backend `n` virtual devices (XLA's host-device-count
    flag) when JAX is pinned to the CPU; on any other platform the devices
    are the real ones and nothing is set."""
    if n > 1 and on_cpu():
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n} "
            + os.environ.get("XLA_FLAGS", ""))


def init_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is set here; otherwise the cache goes to ``<checkout>/.jax_cache``
    (git-ignored), a fixed path so that every later run in this checkout
    finds what an earlier one compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
