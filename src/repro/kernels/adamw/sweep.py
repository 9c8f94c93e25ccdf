"""Time the fused AdamW kernel on a TPU over a sweep of block sizes.

  PYTHONPATH=src python -m repro.kernels.adamw.sweep [--kib 512 1024 ...]

For each leaf (the tied embedding's chunk and two stacked matrices of
qwen3-1.7b, f32) and each block size it checks the kernel against
`ref.adamw_update` once, then times `--reps` calls in one program, each
updating in place the state the last one left, as the train step does,
and prints the time a call and its share of the HBM roofline (4 reads and
3 writes of 4 bytes a parameter). `BLOCK_BYTES` is the smallest block
within a few per cent of the fastest. Off a TPU it exits non-zero.
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.adamw import kernel as K
from repro.kernels.adamw import ref

# TPU v5e HBM bandwidth, bytes/s
HBM_BW = 819e9
HP = dict(b1=0.9, b2=0.95, eps=1e-8, wd=0.1)
LEAVES = {"embed": (1, 311164928), "stacked": (7, 1, 12582912),
          "wide": (7, 2097152)}


def _state(shape, key):
    ks = jax.random.split(key, 4)
    p, g, m = (jax.random.normal(k, shape, jnp.float32) for k in ks[:3])
    v = jnp.abs(jax.random.normal(ks[3], shape, jnp.float32)) * 0.01
    return p, g, m * 0.1, v


def sweep_leaf(name, shape, kibs, reps) -> None:
    t = 3
    scalars = jnp.asarray([1e-3, 1 - HP["b1"] ** t, 1 - HP["b2"] ** t, 0.5],
                          jnp.float32)
    want = jax.jit(lambda p, g, m, v: ref.adamw_update(
        p, g * 0.5, m, v, lr=1e-3, t=t, **HP))(
        *_state(shape, jax.random.PRNGKey(0)))
    for kib in kibs:
        plan = K.block_plan(shape, jnp.float32, kib << 10)

        def update(p, g, m, v):
            return K.adamw_blocks(p, g, m, v, scalars, plan, **HP)

        def loop(p, g, m, v):
            # reps updates in one program, so that no host dispatch sits
            # between the kernel calls timed
            return lax.fori_loop(0, reps, lambda _, s: update(
                s[0], g, s[1], s[2]), (p, m, v))

        p, g, m, v = (x.reshape(plan.view)
                      for x in _state(shape, jax.random.PRNGKey(0)))
        got = jax.jit(update, donate_argnums=(0, 2, 3))(p, g, m, v)
        gaps = [float(jnp.max(jnp.abs(a.reshape(shape) - b))
                      / jnp.max(jnp.abs(b))) for a, b in zip(got, want)]
        fn = jax.jit(loop, donate_argnums=(0, 2, 3))
        p, m, v = jax.block_until_ready(fn(*got[:1], g, *got[1:]))
        t0 = time.perf_counter()
        p, m, v = jax.block_until_ready(fn(p, g, m, v))
        sec = (time.perf_counter() - t0) / reps
        print(f"{name} {shape} block {kib} KiB {plan.block} "
              f"{plan.steps} steps: {sec * 1e3:.3f} ms, "
              f"{100 * 28 * p.size / HBM_BW / sec:.1f}% of roofline; "
              f"max|kernel-ref|/max|ref| p {gaps[0]:.2e} m {gaps[1]:.2e} "
              f"v {gaps[2]:.2e}", flush=True)
        del p, g, m, v, got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--kib", type=int, nargs="+",
                    default=[512, 1024, 2048, 4096],
                    help="f32 KiB per operand per grid step to time")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--leaves", nargs="+", default=list(LEAVES),
                    choices=list(LEAVES))
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    print(jax.devices()[0].device_kind, flush=True)
    for name in args.leaves:
        sweep_leaf(name, LEAVES[name], args.kib, args.reps)
    return 0


if __name__ == "__main__":
    sys.exit(main())
