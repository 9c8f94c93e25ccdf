"""The four-chip cell at smoke size on 4 fake CPU devices, each case in a
process of its own (the device count is fixed when JAX first starts): the
program's dp=4 step against the plain reference sharded over the same four
devices, read as `correct` reads it (bench/check.py); and the same step
with each chip keeping its own slice of the gradient in place of the
reduce-scatter, which has to read not correct. The sound case also counts
the compiled step's collectives (bench/collectives.py) beside the plan's
own wire bytes.

The limits are the cell's, or 1.5 times what the same step reads on one
device where that is larger: the l7x4 cell at smoke size is that step (the
same widths, batch 4 of 64 tokens, on a 1x1 mesh). At smoke widths the
norm scales' updates read an `update_gap` of 1e-3 to 5e-3, on one device
as on four, above the limit set at published widths on the chip."""

import json
import os
import subprocess
import sys

import bench_smoke
import check

CELL = "train.qwen3_1_7b-l28.fsdp4.s4096.uniform"
SEED = 2**33 + 11


def keep_own_slice(ct, cfg):
    """`reduce_scatter_flat` with no exchange: this chip's slice of its own
    gradient, as if the other chips' batches were never seen."""
    from jax import lax

    from repro.core import collectives

    if cfg.fsdp_size == 1:
        return ct[0]
    return ct.reshape(cfg.fsdp_size, -1)[
        lax.axis_index(collectives._fsdp_axes(cfg))]


def child(case: str) -> dict:
    import jax

    import collectives
    import train_bench

    assert jax.device_count() == 4, jax.devices()
    loaded = bench_smoke.cell(CELL)
    out = {"one_device": _checks(bench_smoke.get("l7x4"))["checks"]}
    if case == "no_exchange":
        from repro.core import collectives as program_collectives

        program_collectives.reduce_scatter_flat = keep_own_slice
    else:
        r = train_bench.Run(loaded, jax.devices()[:4])
        r.compile()
        ex = collectives.exchanged(r.hlo)
        out["compiled"] = {k: ex[k] for k in collectives.KINDS}
        out["planned_bytes"] = r.trainer._wire["total_bytes"]
    result = _checks(loaded)
    out.update(checks=result["checks"], count=result["device"]["count"])
    return out


def _checks(loaded) -> dict:
    import train_bench

    code, result = train_bench.run(None, SEED, 0.3, False, loaded=loaded,
                                   require_tpu=False)
    assert code == 0
    return result


def _run_child(case: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), case], env=env,
        cwd=bench_smoke.ROOT, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def verdict(found) -> bool:
    limits = {k: max(c["limit"], 1.5 * found["one_device"][k]["value"])
              for k, c in found["checks"].items()}
    return check.verdict({k: c["value"] for k, c in found["checks"].items()},
                         limits)


def test_dp4_step_matches_the_reference_on_four_devices():
    found = _run_child("sound")
    assert found["count"] == 4
    assert verdict(found), found
    compiled = found["compiled"]
    # the forward and the backward re-gather both cross the four devices,
    # and every gradient bucket is reduced across them
    assert compiled["all-gather"][0] > 0
    assert compiled["reduce-scatter"][0] + compiled["all-reduce"][0] > 0
    print(json.dumps(found))


def test_step_without_the_gradient_exchange_is_not_correct():
    found = _run_child("no_exchange")
    assert found["count"] == 4
    assert not verdict(found), found
    grad = found["checks"]["grad_gap"]
    assert grad["value"] > grad["limit"], found["checks"]


if __name__ == "__main__":
    print(json.dumps(child(sys.argv[1])), flush=True)
