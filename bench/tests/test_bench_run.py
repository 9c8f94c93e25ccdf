"""The harness off the chip: its window on the smoke config, its refusal to
run without a TPU, and whole runs at smoke size, sound and with the faults
a training cell can have planted in the timed path: `correct` has to come
out true for the first and false for each fault."""

import math

import jax
import pytest

import bench_smoke
import faults
import train_bench

L7 = bench_smoke.CELLS["l7"]


@pytest.fixture(autouse=True)
def cache_env(monkeypatch, tmp_path):
    # run() points JAX's cache at the checkout; keep that out of the
    # environment later tests' child processes inherit
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    code = train_bench.main(["--workload", L7, "--seed", "1",
                             "--seconds", "1"])
    assert code != 0
    assert '"correct"' not in capsys.readouterr().out


def test_window_runs_steps_of_the_smoke_config():
    loaded = bench_smoke.get("l7")
    r = train_bench.Run(loaded, jax.devices()[:1])
    r.compile()
    prog = r.first_steps(7)
    assert len(prog["loss"]) == 3 and all(map(math.isfinite, prog["loss"]))
    before = jax.device_get(r.state[0]["final_norm"])
    steps, secs, losses = r.window(0.0, first_step=3)
    assert steps == 1 and secs > 0
    steps, secs, more = r.window(0.0, first_step=4)
    assert steps == 1
    assert all(map(math.isfinite, losses + more))
    assert (jax.device_get(r.state[0]["final_norm"]) != before).any()


def _run(key, seed=2**33 + 5):
    code, result = train_bench.run(None, seed, 0.3, False,
                                   loaded=bench_smoke.get(key),
                                   require_tpu=False)
    assert code == 0
    return result


def test_sound_run_is_correct():
    result = _run("l7x4")
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s", "peak_hbm_gib",
                                      "setup_s"}


@pytest.mark.parametrize("key,fault", [("l7", "frozen"), ("l7", "half_batch"),
                                       ("l7x4", "half_batch")])
def test_planted_fault_is_not_correct(monkeypatch, key, fault):
    monkeypatch.setattr(train_bench.Run, "step", train_bench.Run.step)
    monkeypatch.setattr(train_bench.Run, "feed", train_bench.Run.feed)
    faults.plant(fault, train_bench)
    result = _run(key)
    assert not result["correct"], result["checks"]
