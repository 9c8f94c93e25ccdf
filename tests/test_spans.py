"""Named parts of the step (core/obs/spans.py): the device scopes in the
compiled step's op_name metadata, on one device and on a dp=4 mesh of fake
CPU devices, and the host spans in the registry and on a profiler trace's
host plane."""

import gc
import glob
import json
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from repro.core.dist import DistConfig
from repro.core.obs import SCOPES, MetricsRegistry, gc_span, span, span_times
from repro.models.common import ShapeConfig
from repro.models.registry import get_arch
from repro.optim.adamw import AdamWConfig, init_opt_state

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OP = re.compile(r'^\s*(?:ROOT\s+)?%?(\S+) = .*? ([\w\-]+)\(.*?'
                r'op_name="([^"]*)"')
WRAPPER = re.compile(r"^(\w+\()+|\)+$")


def bench_dist(mesh_shape) -> DistConfig:
    """The benchmark cells' distribution settings on a (data, model) mesh."""
    return DistConfig(mesh_shape=mesh_shape, param_dtype=jnp.bfloat16,
                      reduce_dtype=jnp.float32, remat="fsdp_only")


def compiled_step(dcfg, seq, batch):
    """The smoke qwen3's compiled train step, as text."""
    from repro.core.api import parallelize
    from repro.train.train_step import default_schedule

    _, model = get_arch("qwen3_1_7b", smoke=True)
    shape = ShapeConfig("train", seq, batch, "train")
    par = parallelize(model, dcfg, shape)
    ocfg = AdamWConfig()
    step = par.train_step(ocfg, default_schedule(ocfg, 10, 0))
    storage = par.abstract_storage
    opt = jax.eval_shape(lambda s: init_opt_state(s, dcfg), storage)
    return step.lower(storage, opt,
                      model.input_specs(shape, dcfg)).compile().as_text()


def ops(hlo: str):
    """(instruction, opcode, op_name) for every instruction with metadata."""
    return [m.groups() for m in map(OP.match, hlo.splitlines()) if m]


def kinds(op_names, scope: str) -> set[str]:
    """Which passes an op_name reaches `scope` in: forward, the recompute
    inside a transposed region, or the transposed (backward) op itself."""
    out = set()
    for name in op_names:
        parts = name.split("/")
        for i, p in enumerate(parts):
            if WRAPPER.sub("", p) != scope:
                continue
            if p.startswith("transpose("):
                out.add("backward")
            elif any(q.startswith("transpose(") for q in parts[:i]):
                out.add("recompute")
            else:
                out.add("forward")
    return out


@pytest.fixture(scope="module")
def one_device_hlo():
    return compiled_step(bench_dist((1, 1)), seq=64, batch=2)


def test_every_scope_is_in_the_compiled_step(one_device_hlo):
    names = {WRAPPER.sub("", p) for _, _, n in ops(one_device_hlo)
             for p in n.split("/")}
    assert set(SCOPES) <= names, set(SCOPES) - names


@pytest.mark.parametrize("scope", ["attn", "mlp"])
def test_block_scopes_reach_forward_recompute_and_backward(one_device_hlo,
                                                           scope):
    names = [n for _, _, n in ops(one_device_hlo)]
    assert kinds(names, scope) == {"forward", "recompute", "backward"}


def test_kernel_names_stay_next_to_the_pallas_call(one_device_hlo):
    # a scope sits outside each kernel: op_name's last component but one
    # is still the kernel's name (program.kernel_name in bench/)
    calls = [n for _, op, n in ops(one_device_hlo)
             if n.endswith("/pallas_call")]
    for n in calls:
        assert WRAPPER.sub("", n.split("/")[-2]) in ("adamw", "rmsnorm"), n


def test_span_nests_and_records_into_the_registry():
    reg = MetricsRegistry()
    with span("outer", reg):
        with span("inner", reg):
            time.sleep(0.01)
        with span("inner", reg):
            pass
    snap = reg.snapshot()
    assert snap["span/inner"]["count"] == 2
    assert snap["span/outer"]["count"] == 1
    times = span_times(reg)
    assert set(times) == {"outer", "inner"}
    assert times["outer"] >= times["inner"] >= 0.01


def test_span_records_when_its_body_raises():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        with span("failing", reg):
            raise ValueError
    assert reg.snapshot()["span/failing"]["count"] == 1


def test_gc_span_records_a_forced_collection_only_while_active():
    reg = MetricsRegistry()
    with gc_span(reg):
        gc.collect()
    n = reg.snapshot()["span/gc"]["count"]
    assert n >= 1
    gc.collect()
    assert reg.snapshot()["span/gc"]["count"] == n


def host_events(trace_dir: str) -> set[str]:
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    return names


def test_trainer_spans_in_registry_and_on_the_host_plane(tmp_path):
    from repro.train.trainer import Trainer, TrainerConfig

    _, model = get_arch("qwen3_1_7b", smoke=True)
    trainer = Trainer(model, bench_dist((1, 1)),
                      ShapeConfig("train", 32, 2, "train"), AdamWConfig(),
                      TrainerConfig(ckpt_dir=str(tmp_path / "ckpt"),
                                    total_steps=2, warmup=0))
    init = span_times(trainer.registry)
    assert set(init) == {"trainer_init", "checkpointer", "data",
                         "parallelize", "train_step", "cost_model"}
    assert init["trainer_init"] >= init["parallelize"] > 0
    trace_dir = str(tmp_path / "trace")
    with jax.profiler.trace(trace_dir):
        trainer.run()
        with gc_span(trainer.registry):
            gc.collect()
    loop = {"restore", "batch", "step", "sync", "record", "checkpoint"}
    assert loop <= set(span_times(trainer.registry))
    assert trainer.registry.snapshot()["span/step"]["count"] == 2
    assert loop | {"gc"} <= host_events(trace_dir)


def test_train_launcher_writes_the_device_trace(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    trace_dir = str(tmp_path / "xprof")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen3_1_7b",
         "--smoke", "--steps", "2", "--seq", "32", "--batch", "2",
         "--ckpt-dir", str(tmp_path / "ckpt"), "--xprof-dir", trace_dir],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert f"xprof: {trace_dir}" in out.stdout
    assert {"batch", "step", "sync"} <= host_events(trace_dir)


# ---- dp=4 on fake CPU devices: the child runs in its own process, since
# the device count is fixed when JAX first starts ----
def fsdp4_collectives() -> dict:
    """{opcode: [op_name, ...]} of the collectives across all four devices
    in a dp=4 step."""
    out: dict[str, list[str]] = {}
    hlo = compiled_step(bench_dist((4, 1)), seq=32, batch=4)
    for line in hlo.splitlines():
        m = OP.match(line)
        groups = re.search(r"replica_groups=(\{\{[\d,]+\}|\[[\d,]+\]<=\[\d+\])",
                           line)
        if not (m and groups and re.match(r"all-gather|reduce-scatter",
                                          m.group(2))):
            continue
        g = groups.group(1)
        size = len(g.strip("{}").split(",")) if g.startswith("{") else \
            int(g[1:].split("]")[0].split(",")[-1])
        if size == 4:
            out.setdefault(m.group(2), []).append(m.group(3))
    return out


def test_fsdp_collectives_carry_their_scopes_on_dp4():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src") + os.pathsep + ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-m", "tests.test_spans"],
                         env=env, cwd=ROOT, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    found = json.loads(out.stdout.strip().splitlines()[-1])
    gathers = [n for op, ns in found.items() if op.startswith("all-gather")
               for n in ns]
    scatters = [n for op, ns in found.items()
                if op.startswith("reduce-scatter") for n in ns]
    assert gathers and scatters, found
    scope = lambda n: [WRAPPER.sub("", p) for p in n.split("/")]
    assert all("fsdp_gather" in scope(n) for n in gathers), gathers
    assert all("fsdp_reduce_scatter" in scope(n) for n in scatters), scatters


if __name__ == "__main__":
    assert jax.device_count() == 4, jax.devices()
    print(json.dumps(fsdp4_collectives()))
