"""chip_smoke.py off the chip: its train phase on the smoke config, its
refusal to run without a TPU, and the launchers' CPU mode."""

import importlib.util
import math
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_train_phase_smoke_config(smoke, tmp_path):
    cfg, model = smoke.model_config(smoke=True)
    r = smoke.train_phase(model, smoke.dist_config(1), seq=32, batch=2,
                          steps=2, ckpt_dir=str(tmp_path / "ckpt"))
    assert len(r["loss"]) == len(r["step_s"]) == 2
    assert all(math.isfinite(x) for x in r["loss"] + r["grad_norm"])
    assert r["compile_s"] > 0
    assert not (tmp_path / "ckpt").exists()      # the phase cleans up


def test_param_count_published_widths(smoke):
    cfg, model = smoke.model_config()
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.vocab) == (2048, 16, 8, 128, 6144, 151936)
    assert cfg.n_layers == smoke.LAYERS
    n = smoke.param_count(model, smoke.dist_config(1))
    assert 660e6 < n < 670e6, n


def test_kernel_counts_reads_pallas_names(smoke):
    hlo = ('  %rmsnorm.3 = bf16[8,128] custom-call(%x), custom_call_target='
           '"tpu_custom_call", metadata={op_name="jit(step)/jvp(f)/rmsnorm/'
           'pallas_call" stack_frame_id=4}\n'
           '  %a = f32[8] custom-call(%p), custom_call_target='
           '"tpu_custom_call", metadata={op_name="jit(step)/adamw/'
           'pallas_call"}\n  %b = f32[8] add(%a, %a)\n')
    assert dict(smoke.kernel_counts(hlo)) == {"rmsnorm": 1, "adamw": 1}


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def _launch(module, *args, env_extra=None, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "-m", module, *args], env=env,
                          cwd=ROOT, capture_output=True, text=True,
                          timeout=timeout)


def test_train_launcher_cpu_defaults(tmp_path):
    """No --devices/--mesh: one CPU device, a (1, 1) mesh, a fresh
    checkpoint directory, and the compile cache where the environment
    puts it."""
    cache = tmp_path / "cache"
    out = _launch("repro.launch.train", "--arch", "qwen3_1_7b", "--smoke",
                  "--steps", "3", "--seq", "32", "--batch", "2",
                  env_extra={"JAX_COMPILATION_CACHE_DIR": str(cache),
                             "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS":
                                 "0"})
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert f"compile cache: {cache}" in out.stdout
    assert "devices: 1 x cpu" in out.stdout
    assert "mesh[data=1xmodel=1]" in out.stdout
    assert "done: loss" in out.stdout
    assert any(cache.iterdir())


def test_train_launcher_rejects_devices_off_cpu():
    out = _launch("repro.launch.train", "--smoke", "--devices", "8",
                  env_extra={"JAX_PLATFORMS": "tpu"}, timeout=60)
    assert out.returncode != 0
    assert "--devices needs JAX_PLATFORMS=cpu" in out.stderr
