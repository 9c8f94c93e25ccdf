"""Device time per named scope and the set-up spans: the op_name parse, the
reduction on hand-made events, and the eight readers with and without
anything to read."""

import types

import jax
import pytest

import bench_smoke
import scopes
import spec
from tracelib import Op, Trace

KNOWN = ("embed", "attn", "mlp", "head_loss", "optimizer", "fsdp_gather",
         "fsdp_reduce_scatter")
DEVICE = {"embed_ms": ("embed",), "attn_ms": ("attn",), "mlp_ms": ("mlp",),
          "head_loss_ms": ("head_loss",), "optimizer_ms": ("optimizer",),
          "fsdp_ms": ("fsdp_gather", "fsdp_reduce_scatter")}
HOST = {"trainer_init_s": "trainer_init", "parallelize_s": "parallelize"}

HLO = "\n".join([
    '  %while.1 = (s32[]) while(%t), body=%b, metadata={op_name='
    '"jit(step)/jvp()/while"}',
    '  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f.2, '
    'metadata={op_name="jit(step)/jvp()/while/body/closed_call/attn/dot"}',
    '  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f.3, '
    'metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/'
    'transpose(jvp(mlp))/mul"}',
    '  %convert.4 = bf16[8]{0} convert(%p), metadata={op_name="jit(step)/'
    'jvp(embed)/fsdp_gather/convert_element_type"}',
    '  ROOT %adamw.5 = f32[8]{0} custom-call(%p), custom_call_target='
    '"tpu_custom_call", metadata={op_name="jit(step)/optimizer/adamw/'
    'pallas_call"}',
    '  %copy.6 = f32[8]{0} copy(%p), metadata={op_name="jit(step)/copy"}',
    '  %add.7 = f32[8]{0} add(%p, %p)'])


def test_scope_of_takes_the_innermost_known_scope():
    assert scopes.scope_of("jit(s)/transpose(jvp(attn))/dot", KNOWN) == "attn"
    assert scopes.scope_of("jit(s)/jvp(head_loss)/fsdp_gather/convert",
                           KNOWN) == "fsdp_gather"
    assert scopes.scope_of("jit(s)/transpose(jvp(jvp()))/checkpoint/embed/"
                           "jit(_take)/scatter-add", KNOWN) == "embed"
    assert scopes.scope_of("jit(s)/while/body/add", KNOWN) is None


def test_op_scopes_read_each_instructions_own_metadata():
    assert scopes.op_scopes(HLO, KNOWN) == {
        "fusion.2": "attn", "fusion.3": "mlp", "convert.4": "fsdp_gather",
        "adamw.5": "optimizer"}


def events():
    # window [0, 100): a loop 0-50 whose body ops (attn 10-20, mlp 30-45)
    # run inside it; the embed gather 50-60; an unscoped copy 60-70; the
    # optimizer 80-100, and a step's tail past the window
    return [Op(0, 50, "while.1"), Op(10, 20, "fusion.2"),
            Op(30, 45, "fusion.3"), Op(50, 60, "convert.4"),
            Op(60, 70, "copy.6"), Op(80, 110, "adamw.5")]


def test_scope_times_split_the_busy_time():
    import tracelib

    t = scopes.scope_times(events(), scopes.op_scopes(HLO, KNOWN), 0, 100)
    # the loop keeps only its own 25 ns; the copy and the loop's own time
    # are unscoped; the gather inside `embed` is the gather's
    assert t == {scopes.UNSCOPED: 35, "attn": 10, "mlp": 15,
                 "fsdp_gather": 10, "optimizer": 20}
    trace = Trace({0: events()}, [])
    assert sum(t.values()) == tracelib.length(tracelib.busy(trace, 0, 0, 100))


def ctx_of(gaps, steps=2):
    return types.SimpleNamespace(
        trace=Trace({0: events()}, [(0, 100, "batch")]), lo=0, hi=100,
        devices=[0], steps=steps, host_gaps_s=gaps)


def registry_with(spans: dict):
    from repro.core.obs import MetricsRegistry

    reg = MetricsRegistry()
    reg.gauge("train/loss").set(1.0)
    for name, secs in spans.items():
        reg.histogram(f"span/{name}").observe(secs)
    return reg


@pytest.mark.parametrize("metric", sorted(DEVICE) + sorted(HOST))
def test_reader_is_none_without_the_harness_run(metric):
    assert spec.reader(metric)(ctx_of([0.1])) is None


@pytest.mark.parametrize("metric", sorted(DEVICE) + sorted(HOST))
def test_reader_is_none_for_a_program_that_names_no_parts(metric):
    plain = "\n".join(line.replace("attn/", "").replace("(mlp)", "()")
                      .replace("embed", "e").replace("fsdp_gather/", "")
                      .replace("optimizer/", "") for line in HLO.split("\n"))
    run = types.SimpleNamespace(
        gaps=[0.1], hlo=plain,
        trainer=types.SimpleNamespace(registry=registry_with({})))
    assert spec.reader(metric)(ctx_of(run.gaps)) is None


def test_readers_read_the_harness_run():
    run = types.SimpleNamespace(
        gaps=[0.1], hlo=HLO, trainer=types.SimpleNamespace(
            registry=registry_with({"trainer_init": 3.5,
                                    "parallelize": 1.25})))
    ctx = ctx_of(run.gaps, steps=2)
    # ns over 2 steps, in ms
    want = {"embed_ms": 0.0, "attn_ms": 5e-6, "mlp_ms": 7.5e-6,
            "head_loss_ms": 0.0, "optimizer_ms": 1e-5, "fsdp_ms": 5e-6}
    for metric, v in want.items():
        assert spec.reader(metric)(ctx) == pytest.approx(v), metric
    assert spec.reader("trainer_init_s")(ctx) == 3.5
    assert spec.reader("parallelize_s")(ctx) == 1.25


def test_the_harness_reports_every_part_of_the_smoke_cell(monkeypatch):
    """The compiled smoke step names every scope, and `train_bench.per_layer`
    hands the readers what they find: a trace of one op per scope (10 ns
    each) and an unscoped one stands in for the chip's."""
    import tracelib
    import train_bench
    from repro.core.obs import SCOPES

    loaded = bench_smoke.get("l7")
    run = train_bench.Run(loaded, jax.devices()[:1])
    run.compile()
    run.gaps.append(0.01)
    first = {}
    for name, scope in scopes.op_scopes(run.hlo, SCOPES).items():
        first.setdefault(scope, name)
    assert set(first) == set(SCOPES)
    names = [first[k] for k in SCOPES] + ["copy.unscoped"]
    dev = run.devices[0].id
    trace = Trace({dev: [Op(10 * i, 10 * i + 10, n)
                         for i, n in enumerate(names)]},
                  [(0, 10 * len(names), "batch")])
    monkeypatch.setattr(tracelib, "load", lambda _: trace)
    monkeypatch.setattr(spec, "peaks", lambda _: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    metrics = train_bench.per_layer(run, loaded, "unused", 2, run.devices,
                                    "cpu")[0]
    got = {k: v["value"] for k, v in metrics.items()}
    for metric, parts in DEVICE.items():
        assert got[metric] == pytest.approx(5e-6 * len(parts)), metric
    for metric in HOST:
        assert got[metric] > 0, metric
