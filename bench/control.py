"""The readings that a cell's limits are set from, on the chip at the cell's
own size; the benchmark's own runs never run this.

  python bench/control.py --workload <cell> --seeds <n> [<n> ...] \
      [--control 3] [--faults half_batch]

For every seed: the program's first three steps as a benchmark run makes
them, and the reference's, compared as `correct` compares them (the lower
readings: the largest over the seeds). For the first `--control` seeds, the
control: the reference put in the program's place and computed with every
matrix product's operands in float8_e4m3fn (the precision below the bf16
that the configuration states). For the first three seeds, each fault of
bench/faults.py named, planted in the program. The upper reading of a
number is the smallest that the control or a fault gives. A state left
unchanged reads 1 on grad_gap and update_gap and needs no run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import train_bench  # noqa: E402  (sets up the paths and the cache)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", nargs="*", default=[])
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = train_bench.CACHE
    import jax

    import check
    import faults
    import spec

    devs = jax.devices()
    loaded = spec.cell(args.workload)
    chips = loaded["cell"]["chips"]
    if devs[0].platform != "tpu" or len(devs) < chips:
        print("control: needs a TPU with the cell's chips", file=sys.stderr)
        return 2
    devices = devs[:chips]
    from repro.launch import jaxenv

    jaxenv.init_compile_cache()
    run = train_bench.Run(loaded, devices)
    run.compile()
    refs, rows = {}, []

    def record(kind, seed, values, secs):
        row = {"kind": kind, "seed": seed, "secs": round(secs, 3), **values}
        rows.append(row)
        print(json.dumps(row), flush=True)

    for i, seed in enumerate(args.seeds):
        t = time.perf_counter()
        prog = run.first_steps(seed)
        run.free()
        prog["update"] = run.update()
        t_prog = time.perf_counter() - t
        t = time.perf_counter()
        refs[seed] = run.reference()
        record("program", seed, check.readings(prog, refs[seed]),
               time.perf_counter() - t + t_prog)
        if i < args.control:
            t = time.perf_counter()
            ctl = run.reference(matmul=check.fp8_matmul)
            record("control", seed, check.readings(ctl, refs[seed]),
                   time.perf_counter() - t)
    for fault in args.faults:
        faults.plant(fault, train_bench)
        frun = train_bench.Run(loaded, devices)
        frun.compile()
        for seed in args.seeds[:3]:
            t = time.perf_counter()
            prog = frun.first_steps(seed)
            frun.free()
            prog["update"] = frun.update()
            record(fault, seed, check.readings(prog, refs[seed]),
                   time.perf_counter() - t)
    names = ("loss_gap", "grad_gap", "update_gap")
    summary = {"lower": {k: max(r[k] for r in rows if r["kind"] == "program")
                         for k in names}}
    for kind in sorted({r["kind"] for r in rows} - {"program"}):
        summary[kind] = {k: min(r[k] for r in rows if r["kind"] == kind)
                         for k in names}
    print("summary " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
