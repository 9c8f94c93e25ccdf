"""The benchmark's data, found by name: BENCHMARK.json at the checkout's
root, and under bench/ a file per configuration, traffic mix, cell limit
and per-layer metric reader, and the table of chip peaks."""

from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _json(root, "BENCHMARK.json")


def cell(name: str, root: str = ROOT) -> dict:
    """The cell `name` with what it names loaded: {"cell", "config",
    "traffic", "limits", "per_layer", "end_to_end"}. The per-layer and
    end-to-end metrics are those that this cell reports."""
    spec = benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    here = lambda m: name in m.get("workloads", [name])
    return {
        "cell": w,
        "config": _json(root, conf["file"]),
        "traffic": _json(root, "bench", "traffic", w["traffic"] + ".json"),
        "limits": _json(root, "bench", "limits", name + ".json"),
        "end_to_end": [m for m in spec["end_to_end"] if here(m)],
        "per_layer": [m for m in spec["per_layer"] if here(m)],
    }


def peaks(device_kind: str, root: str = ROOT) -> dict:
    table = _json(root, "bench", "peaks.json")
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         "bench/peaks.json")
    return table[device_kind]


def _module(path: str, name: str):
    s = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def reader(metric: str, root: str = ROOT):
    """bench/metrics/<metric>.py's `read(ctx) -> float | None`."""
    return _module(os.path.join(root, "bench", "metrics", metric + ".py"),
                   "bench_metric_" + metric.replace(".", "_")).read


def reference(name: str, root: str = ROOT):
    """bench/reference/<name>.py, the configuration's plain reference."""
    return _module(os.path.join(root, "bench", "reference", name + ".py"),
                   "bench_reference_" + name)
