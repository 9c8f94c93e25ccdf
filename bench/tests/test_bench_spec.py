"""BENCHMARK.json against the files the harness finds by name, and against
the rules a later PR must keep to when it adds a cell or a metric."""

import json
import os
import re

import pytest

import bench_smoke  # noqa: F401  (puts bench/ on the path)
import spec

BENCH = bench_smoke.BENCH
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark()


def test_every_cell_names_files_that_exist(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        assert w["config"] in configs, w
        loaded = spec.cell(w["name"])
        assert loaded["config"]["reference"]
        assert set(loaded["limits"]) == {"loss_gap", "grad_gap",
                                         "update_gap"}
        assert w["chips"] == loaded["config"]["dist"]["mesh_shape"][0] \
            * loaded["config"]["dist"]["mesh_shape"][1]
        assert os.path.exists(os.path.join(
            BENCH, "reference", loaded["config"]["reference"] + ".py"))
    used = {w["config"] for w in bench["workloads"]}
    assert used == set(configs)


def test_every_metric_has_its_reader(bench):
    for m in bench["per_layer"]:
        assert callable(spec.reader(m["name"])), m["name"]


def test_names_units_and_keys_keep_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names), names
    assert len({x["name"] for x in bench["workloads"]}) \
        == len(bench["workloads"])
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert e2e == {"tokens_per_s", "peak_hbm_gib", "setup_s"}
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for c in bench["configs"]:
        with open(os.path.join(bench_smoke.ROOT, c["file"])) as f:
            assert set(c["reduced"]) <= set(json.load(f)["reduced"]) | {
                "num_hidden_layers"}


def test_a_config_file_holds_the_published_widths():
    for name in ("qwen3_1_7b-l7",):
        with open(os.path.join(BENCH, "configs", name + ".json")) as f:
            c = json.load(f)
        assert (c["hidden_size"], c["intermediate_size"],
                c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"], c["vocab_size"]) \
            == (2048, 6144, 16, 8, 128, 151936)
