"""Training benchmark: one cell of BENCHMARK.json in one process that holds
all of the cell's chips.

  python bench/train_bench.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

Set-up builds the cell's `Trainer` (the program's own entry point), makes the
weights on the device from the seed, compiles the step, and drives it through
its first three steps: the steps that `correct` compares with the plain
reference. The window then drives the same compiled step on the same state
for `--seconds`, a host batch per step, one `block_until_ready` per step and
the step's metrics copied to the host, as `Trainer.run` does. After the
window the peak memory is read, the program's state is freed and the
reference runs.

With --trace 0 the result reports the cell's end-to-end metrics; with
--trace 1 the window runs under the profiler and the result reports the
per-layer metrics, each read by bench/metrics/<name>.py. The last line of
standard output is one JSON object; the numbers that decided `correct` end
both it and standard error. Off a TPU, or with fewer chips than the cell
asks for, it prints no result and exits non-zero.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import spec  # noqa: E402

GiB = 2**30
OUT = os.path.join(BENCH, "out")
CACHE = os.path.join(BENCH, ".cache", "jax")


def log(line: str) -> None:
    print(line, flush=True)


class CompileCounter:
    """Counts XLA compilations through JAX's monitoring events."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name.endswith("backend_compile_duration"):
            self.n += 1


class Run:
    """A cell's program, compiled once: then per seed, its first steps, the
    window, and the reference's record of the same first steps."""

    def __init__(self, loaded: dict, devices):
        import program

        self.loaded, self.devices = loaded, devices
        cfg = loaded["config"]
        self.arch = cfg
        self.ref = spec.reference(cfg["reference"])
        self.trainer = program.build(cfg, loaded["traffic"],
                                     os.path.join(OUT, "ckpt"))
        self.phases: dict[str, float] = {}
        self.gaps: list[float] = []
        self.step_s: list[float] = []       # the window's sync-to-sync times

    # the feed and the step, as the window drives them
    def feed(self, step: int):
        import jax
        import program

        b = program.feed(self.trainer, self.gen.batch(step), step, self.seed)
        return jax.device_put(b, self.batch_shardings)

    def step(self, state, batch):
        return self.step_c(*state, batch)

    def _make_params(self, kd):
        import jax

        return self.ref.init_params(self.arch, jax.random.wrap_key_data(kd))

    def compile(self):
        """The state's initialisation and the train step, compiled (from
        the persistent cache after a cell's first run)."""
        import jax
        import jax.numpy as jnp
        import program
        from check import leaf_norms

        t = time.perf_counter()
        make_state = program.state_fn(self.trainer, self._make_params)
        kd_abs = jax.ShapeDtypeStruct((2,), jnp.uint32)
        self.init_c = make_state.lower(kd_abs).compile()
        self.phases["init_compile_s"] = time.perf_counter() - t
        t = time.perf_counter()
        abstract = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            jax.eval_shape(make_state, kd_abs), self.init_c.output_shardings)
        self.step_c = self.trainer.step_fn.lower(
            *abstract, program.batch_specs(self.trainer)).compile()
        self.phases["step_compile_s"] = time.perf_counter() - t
        self.batch_shardings = self.step_c.input_shardings[0][2]
        self.hlo = self.step_c.as_text()
        self.hbm_temp = self.step_c.memory_analysis().temp_size_in_bytes
        b1 = self.loaded["config"]["optimizer"]["b1"]
        self.grad_of_m = jax.jit(lambda m: leaf_norms(
            jax.tree.map(lambda x: x / (1 - b1), m)))
        self.change = jax.jit(lambda p, kd: leaf_norms(jax.tree.map(
            jnp.subtract, p,
            program.storage_fn(self.trainer, self._make_params)(kd))))

    def first_steps(self, seed: int):
        """The state from the seed, driven through the first three steps by
        the window's own feed and step; returns the program's record of
        them for the check."""
        import jax
        import numpy as np
        import traffic
        from check import STEPS, flat_leaves

        self.seed = seed
        self.gen = traffic.PackedDocuments(
            self.loaded["traffic"], self.loaded["config"]["vocab_size"], seed)
        self.key_data = np.asarray(self.ref.seed_words(seed))
        t = time.perf_counter()
        state = jax.block_until_ready(self.init_c(self.key_data))
        self.phases["init_s"] = time.perf_counter() - t
        t = time.perf_counter()
        losses, grad = [], None
        for i in range(STEPS):
            *state, metrics = self.step(state, self.feed(i))
            losses.append(float(metrics["loss"]))
            if i == 0:
                grad = flat_leaves(self.grad_of_m(state[1]["m"]))
        # the parameters after the checked steps wait on the host, so that
        # the check allocates nothing beside the live state on the device
        self.params3 = jax.device_get(state[0])
        self.state = state
        self.phases["warmup_s"] = time.perf_counter() - t
        return {"loss": losses, "grad": grad}

    def update(self) -> dict:
        """Per-leaf norms of the parameters' change over the checked steps,
        once `free` has emptied the device."""
        import jax
        from check import flat_leaves

        p3 = jax.device_put(self.params3, self.init_c.output_shardings[0])
        self.params3 = None
        return flat_leaves(self.change(p3, self.key_data))

    def window(self, seconds: float, first_step: int):
        """Steps on a loop until `seconds` have passed; returns (steps,
        seconds, losses). Host spans mark each step's feed, dispatch and
        wait for the profiler."""
        import jax
        import numpy as np
        from jax.profiler import TraceAnnotation

        state, losses, step = self.state, [], first_step
        last_sync = None
        t0 = prev = time.perf_counter()
        while True:
            with TraceAnnotation("batch"):
                batch = self.feed(step)
            with TraceAnnotation("dispatch"):
                t = time.perf_counter()
                if last_sync is not None:
                    self.gaps.append(t - last_sync)
                *state, metrics = self.step(state, batch)
            with TraceAnnotation("sync"):
                metrics = jax.tree.map(np.asarray,
                                       jax.block_until_ready(metrics))
                last_sync = time.perf_counter()
            self.step_s.append(last_sync - prev)
            prev = last_sync
            losses.append(float(metrics["loss"]))
            step += 1
            if last_sync - t0 >= seconds:
                break
        self.state = state
        return step - first_step, last_sync - t0, losses

    def free(self):
        """Delete the program's state, so that the reference has the chip."""
        import jax

        for x in jax.tree.leaves(self.state):
            x.delete()
        self.state = None

    def reference(self, matmul=None):
        import check

        cfg = self.loaded["config"]
        # the reference draws its batches from the seed itself, not from
        # what the program's feed made of them
        batches = [self.gen.batch(i) for i in range(check.STEPS)]
        return check.reference_record(self.ref, self.arch, cfg["optimizer"],
                                      self.key_data, batches, self.devices,
                                      matmul=matmul)


def kernel_calls(hlo: str) -> dict[str, tuple[str, int]]:
    """{HLO instruction name: (Pallas kernel name, operand + result bytes)}
    for every `tpu_custom_call` of the compiled step. The compiled text
    names a call's operands without their types, so each operand's bytes
    come from the instruction that defines it."""
    import re

    import program
    from flops import array_bytes
    from tracelib import HLO_HEAD

    types, calls = {}, []
    for line in hlo.splitlines():
        head = HLO_HEAD.match(line)
        if not head:
            continue
        types[head.group(1)] = head.group(2)
        if 'custom_call_target="tpu_custom_call"' in line:
            calls.append((head.group(1), line))
    out = {}
    for name, line in calls:
        args = line.split("custom-call(", 1)[1].split("custom_call_target=")[0]
        operands = re.findall(r"%([\w.\-]+)", args)
        op = re.search(r'op_name="([^"]*)"', line)
        kernel = program.kernel_name(op.group(1)) if op else \
            re.sub(r"^(jvp_|transpose_)+|_+$", "", name.rsplit(".", 1)[0])
        out[name] = (kernel, array_bytes(types[name]) + sum(
            array_bytes(types.get(o, "")) for o in operands))
    return out


def per_layer(run: Run, loaded, trace_dir, steps, devices, kind) -> tuple:
    """Every per-layer metric this cell reports, from the trace of the
    window; and the trace's breakdown and device busy time."""
    import types

    import tracelib
    from flops import train_flops_per_token

    tr = tracelib.load(trace_dir)
    lo, hi = tracelib.window(tr)
    ids = [d.id for d in devices]
    missing = [i for i in ids if i not in tr.ops]
    if missing:
        raise SystemExit(f"the trace has no op line for devices {missing}")
    traffic = loaded["traffic"]
    ctx = types.SimpleNamespace(
        trace=tr, lo=lo, hi=hi, devices=ids, steps=steps,
        tokens_per_step=traffic["seq_len"] * traffic["global_batch"],
        seq_len=traffic["seq_len"], arch=run.arch,
        flops_per_token=train_flops_per_token(run.arch, traffic["seq_len"]),
        chips=len(devices), peaks=spec.peaks(kind), host_gaps_s=run.gaps,
        compile_s=run.phases["init_compile_s"] + run.phases["step_compile_s"],
        hbm_temp_bytes=run.hbm_temp, kernels=kernel_calls(run.hlo))
    log(f"pallas calls: {ctx.kernels}")
    metrics = {}
    for m in loaded["per_layer"]:
        v = spec.reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    busy = [tracelib.length(tracelib.busy(tr, i, lo, hi)) for i in ids]
    totals = tracelib.self_times(tr.ops[ids[0]], lo, hi)
    labels = {o.name: o for o in tr.ops[ids[0]]}
    gaps = sorted(tracelib.idle_gaps(tr, ids[0], lo, hi),
                  key=lambda g: -g[1])[:10]
    breakdown = {
        "device_ops": [[tracelib.label(labels[k]), v / 1e9] for k, v in
                       sorted(totals.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[n, s / 1e9] for n, s in gaps]}
    return metrics, breakdown, sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9


def run(workload: str, seed: int, seconds: float, trace: bool,
        loaded: dict | None = None, require_tpu: bool = True):
    """One run; returns (exit code, result dict or None)."""
    loaded = loaded or spec.cell(workload)
    chips = loaded["cell"]["chips"]
    # the compile cache lives in the checkout, at a path that never moves
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    import jax

    from repro.launch import jaxenv

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        print(f"train_bench: JAX found no TPU (platform "
              f"{devs[0].platform!r}); this benchmark runs on a TPU only",
              file=sys.stderr)
        return 2, None
    if len(devs) < chips:
        print(f"train_bench: the cell needs {chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return 2, None
    devices = devs[:chips]
    kind = devices[0].device_kind
    log(f"device: {devices[0].platform} {kind} x {len(devices)}; jax "
        f"{jax.__version__}; compile cache {jaxenv.init_compile_cache()}")
    counter = CompileCounter()

    r = Run(loaded, devices)
    r.phases["imports_plan_s"] = time.perf_counter() - T_START
    r.compile()
    prog = r.first_steps(seed)
    log("setup phases: " + " ".join(f"{k} {v:.3f}"
                                    for k, v in r.phases.items()))
    log("first steps: loss " + " ".join(f"{x:.6f}" for x in prog["loss"]))
    trace_dir = os.path.join(OUT, "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    compiles = counter.n
    setup_s = time.perf_counter() - T_START
    from check import STEPS
    steps, window_s, losses = r.window(seconds, first_step=STEPS)
    compiles = counter.n - compiles
    if trace:
        jax.profiler.stop_trace()
    slow = max(r.step_s)
    log(f"window: {steps} steps in {window_s:.4f} s; compiles in the "
        f"window: {compiles}; loss {losses[0]:.6f} -> {losses[-1]:.6f}; "
        f"sync to sync median {sorted(r.step_s)[len(r.step_s) // 2]:.4f} s, "
        f"slowest {slow:.4f} s at step {r.step_s.index(slow)}")
    # a TPU keeps the compiled programs' temporaries in memory it reserves
    # apart from the buffers it counts in use; the chip holds both. (The
    # CPU backend of the tests keeps no memory statistics.)
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(m.get("peak_bytes_in_use", 0) + m.get("peak_bytes_reserved", 0)
               for m in stats)
    log(f"memory stats: {stats[0]}")
    failed = sum(not math.isfinite(x) for x in losses)

    traffic = loaded["traffic"]
    tokens = steps * traffic["seq_len"] * traffic["global_batch"]
    if trace:
        metrics, breakdown, busy_s, traced_s = per_layer(
            r, loaded, trace_dir, steps, devices, kind)
    else:
        e2e = {"tokens_per_s": tokens / window_s,
               "peak_hbm_gib": peak / GiB, "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in loaded["end_to_end"]}
    r.free()
    prog["update"] = r.update()

    import check

    t_ref = time.perf_counter()
    ref = r.reference()
    t_ref = time.perf_counter() - t_ref
    values = check.readings(prog, ref)
    limits = loaded["limits"]
    correct = check.verdict(values, limits) and failed == 0
    log("reference: loss " + " ".join(f"{x:.6f}" for x in ref["loss"])
        + f" in {t_ref:.3f} s")
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": steps, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=busy_s, window_s=traced_s)
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": values[k], "limit": limits[k]}
                        for k in limits}
    for k in limits:
        print(f"check {k}: {values[k]!r} limit {limits[k]!r}",
              file=sys.stderr, flush=True)
    return 0, result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    code, result = run(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    if result is not None:
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
