"""The readers of the cross-chip collectives (bench/collectives.py) on
hand-made traces and compiled text, and on a slice of a trace recorded on
the chip: exposure of an async pair with and without compute under it, ops
inside a collective's event counted as its own, the bytes of collectives of
known shapes in a loop, a TPU async chain counted once, and silence on one
chip. The compiled text reaches the readers as the harness hands it over:
on the `Run` in the caller's frame whose `gaps` are `ctx.host_gaps_s`."""

import json
import os
import types

import pytest

import bench_smoke
import collectives
import spec
import tracelib
from tracelib import Op, Trace

CELL = "train.qwen3_1_7b-l28.fsdp4.s4096.uniform"
READERS = ("collective_ms", "collective_exposed_pct", "collective_gib")

# one all-gather of 256 bf16 a trip in a loop of 7 trips, a reduce-scatter
# of f32[4096] to f32[1024] before it, and an all-reduce on one device
HLO = """HloModule jit_step, num_partitions=4

%add (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %sum = f32[] add(%x, %y)
}

%body (p: (s32[], bf16[256])) -> (s32[], bf16[256]) {
  %p = (s32[], bf16[256]{0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %w = bf16[256]{0} get-tuple-element(%p), index=1
  %all-gather-start.1 = (bf16[256]{0}, bf16[1024]{0}) all-gather-start(%w), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}
  %fusion.1 = bf16[256]{0} fusion(%w), kind=kLoop, calls=%add
  %all-gather-done.1 = bf16[1024]{0} all-gather-done(%all-gather-start.1)
  ROOT %t = (s32[], bf16[256]{0}) tuple(%i, %fusion.1)
}

%cond (q: (s32[], bf16[256])) -> pred[] {
  %q = (s32[], bf16[256]{0}) parameter(0)
  %n = s32[] get-tuple-element(%q), index=0
  %constant.9 = s32[] constant(7)
  ROOT %lt.1 = pred[] compare(%n, %constant.9), direction=LT
}

ENTRY %main (a: f32[4096], b: bf16[256]) -> f32[1024] {
  %a = f32[4096]{0} parameter(0)
  %b = bf16[256]{0} parameter(1)
  %reduce-scatter.2 = f32[1024]{0} reduce-scatter(%a), channel_id=2, replica_groups=[1,4]<=[4], dimensions={0}, to_apply=%add
  %all-reduce.3 = f32[1024]{0} all-reduce(%reduce-scatter.2), channel_id=3, replica_groups={{0}}, to_apply=%add
  %constant.0 = s32[] constant(0)
  %copy.0 = s32[] copy(%constant.0)
  %tuple.1 = (s32[], bf16[256]{0}) tuple(%copy.0, %b)
  %while.1 = (s32[], bf16[256]{0}) while(%tuple.1), condition=%cond, body=%body
  ROOT %r = f32[1024]{0} copy(%all-reduce.3)
}
"""

# the TPU's async collective fusions: a chain from start to done, each
# fusion holding a copy of one all-gather of f32[512] to f32[2048]
CHAIN = """HloModule jit_step

%fused_computation.1 (param_0: f32[512]) -> (f32[512], f32[2048]) {
  %param_0 = f32[512]{0} parameter(0)
  %all-gather.1 = f32[2048]{0} all-gather(%param_0), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}, frontend_attributes={chain_id="0"}
  ROOT %custom-call.1 = (f32[512]{0}, f32[2048]{0}) custom-call(%all-gather.1), custom_call_target="AsyncCollectiveFusionStart"
}

%async_collective_fusion.2 (param_1: f32[512]) -> (f32[512], f32[2048]) {
  %param_1 = f32[512]{0} parameter(0)
  %all-gather.2 = f32[2048]{0} all-gather(%param_1), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}, frontend_attributes={chain_id="0"}
  ROOT %custom-call.2 = (f32[512]{0}, f32[2048]{0}) custom-call(%all-gather.2), custom_call_target="AsyncCollectiveFusionStep"
}

%fused_computation.3 (param_2: f32[512]) -> f32[2048] {
  %param_2 = f32[512]{0} parameter(0)
  %all-gather.3 = f32[2048]{0} all-gather(%param_2), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}, frontend_attributes={chain_id="0"}
  ROOT %custom-call.3 = f32[2048]{0} custom-call(%all-gather.3), custom_call_target="AsyncCollectiveFusionDone"
}

ENTRY %main (a: f32[512]) -> f32[2048] {
  %a = f32[512]{0} parameter(0)
  %async-collective-start = (f32[512]{0}, f32[2048]{0}) fusion(%a), kind=kCustom, calls=%fused_computation.1
  %get-tuple-element.1 = f32[512]{0} get-tuple-element(%async-collective-start), index=0
  %fusion.2 = (f32[512]{0}, f32[2048]{0}) fusion(%get-tuple-element.1), kind=kCustom, calls=%async_collective_fusion.2
  %get-tuple-element.2 = f32[512]{0} get-tuple-element(%fusion.2), index=0
  ROOT %async-collective-done = f32[2048]{0} fusion(%get-tuple-element.2), kind=kCustom, calls=%fused_computation.3
}
"""


def harness(hlo):
    """A stand-in for the harness's `Run`: what the readers look up."""
    return types.SimpleNamespace(gaps=[0.1], hlo=hlo)


def ctx_of(ops, run, chips=4, steps=1):
    return types.SimpleNamespace(
        trace=Trace({0: ops}, [(0, 100, "sync")]), lo=0, hi=100,
        devices=[0], steps=steps, chips=chips, host_gaps_s=run.gaps)


def pair(start, done, under=()):
    """An async all-gather from `start` to `done` (ns), each end 1 ns long,
    with compute ops `under` it."""
    return [Op(start, start + 1, "all-gather-start.1"),
            Op(done - 1, done, "all-gather-done.1")] + \
        [Op(s, e, f"fusion.{i}") for i, (s, e) in enumerate(under)]


def test_a_pair_wholly_under_compute_is_not_exposed():
    run = harness(HLO)
    ctx = ctx_of(pair(10, 50, under=[(0, 10.5), (10.5, 49.5), (49.5, 60)]),
                 run)
    # its start and done, 1 ns each; not the 40 ns in flight between them
    assert spec.reader("collective_ms")(ctx) == pytest.approx(2e-6)
    assert spec.reader("collective_exposed_pct")(ctx) == pytest.approx(0.0)


def test_a_bare_pair_is_wholly_exposed():
    run = harness(HLO)
    ctx = ctx_of(pair(10, 50, under=[(0, 10), (50, 60)]), run)
    assert spec.reader("collective_ms")(ctx) == pytest.approx(2e-6)
    assert spec.reader("collective_exposed_pct")(ctx) == pytest.approx(
        100.0)


def test_a_loop_does_not_hide_the_collectives_inside_it():
    # a while event spans its body's ops; it is not compute
    run = harness(HLO)
    ops = pair(10, 50, under=[(20, 30)]) + [
        Op(0, 90, "while.1", text="%while.1 = (s32[]) while(%tuple.1)"),
        Op(60, 80, "reduce-scatter.2")]
    ctx = ctx_of(ops, run)
    assert spec.reader("collective_ms")(ctx) == pytest.approx(22e-6)
    assert spec.reader("collective_exposed_pct")(ctx) == pytest.approx(100.0)


def test_an_op_inside_a_collective_is_its_own():
    # the profiler may nest the steps of a synchronous collective inside
    # its event: they do not hide it; an op beside it does
    run = harness(HLO)
    ops = [Op(10, 50, "reduce-scatter.2"), Op(20, 30, "fusion.7"),
           Op(45, 60, "fusion.8")]
    ctx = ctx_of(ops, run)
    assert spec.reader("collective_ms")(ctx) == pytest.approx(40e-6)
    assert spec.reader("collective_exposed_pct")(ctx) == pytest.approx(
        100.0 * 35 / 40)
    sp = collectives.split(ops, collectives.roles(HLO), 0, 100)
    assert sp.inside == {("reduce-scatter.2", "fusion.7"): 10}
    assert collectives.beside(sp, "reduce-scatter.2") == [("fusion.8", 5)]


def test_bytes_of_known_shapes_with_trip_counts():
    ex = collectives.exchanged(HLO)
    # the bf16[1024] gathered 7 times; f32[1024] scattered from f32[4096]
    # once; the all-reduce over one device is no exchange
    assert ex["all-gather"] == [7, 7 * 1024 * 2]
    assert ex["reduce-scatter"] == [1, 4096 * 4]
    assert ex["all-reduce"] == [0, 0]
    assert ex["unknown_trips"] == []
    run = harness(HLO)
    ctx = ctx_of([], run)
    assert spec.reader("collective_gib")(ctx) == pytest.approx(
        (7 * 2048 + 16384) / 2**30)
    # the compiler's own trip count wins over the loop's condition; empty
    # replica groups are every device of the module (num_partitions)
    known = HLO.replace(
        "condition=%cond, body=%body",
        'condition=%cond, body=%body, backend_config={"known_trip_count":'
        '{"n":"5"}}').replace("replica_groups={{0}}", "replica_groups={}")
    ex = collectives.exchanged(known)
    assert ex["all-gather"] == [5, 5 * 1024 * 2]
    assert ex["all-reduce"] == [1, 1024 * 4]


def test_an_async_chain_counts_once_and_times_start_to_done():
    assert collectives.exchanged(CHAIN)["all-gather"] == [1, 2048 * 4]
    r = collectives.roles(CHAIN)
    assert r.pairs == {"async-collective-done": "async-collective-start"}
    # the chain's step fusion is compute that the transfer may run under:
    # the metrics count the chain's start and done, the log its flight
    ops = [Op(0, 2, "async-collective-start"), Op(2, 30, "fusion.2"),
           Op(40, 42, "async-collective-done")]
    run = harness(CHAIN)
    ctx = ctx_of(ops, run)
    assert spec.reader("collective_ms")(ctx) == pytest.approx(4e-6)
    assert spec.reader("collective_exposed_pct")(ctx) == pytest.approx(
        100.0)
    sp = collectives.split(ops, r, 0, 100)
    assert sp.flight == {"async-collective-done": [(0, 42)]}
    assert sp.ends == [(0, 2), (40, 42)]


def test_a_recorded_chip_trace():
    """A slice of the four-chip cell's trace on the chip (device 0): the
    synchronous all-reduces and all-gather run with nothing beside or
    inside them, and the chains' start-to-done spans, mostly under other
    ops, count in neither metric."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "l28_trace_slice.json")
    with open(path) as f:
        rec = json.load(f)
    names, opcodes = rec["names"], rec["opcodes"]
    ops = [Op(s, e, names[i], text=f"%{names[i]} = x {opcodes[i]}(")
           for s, e, i in rec["events"]]
    ro = rec["roles"]
    r = collectives.Roles(frozenset(ro["sync"]), frozenset(ro["starts"]),
                          frozenset(ro["dones"]), ro["pairs"])
    sp = collectives.split(ops, r, min(o.start for o in ops),
                           max(o.end for o in ops))
    own = sp.own()
    mine = set(ro["sync"]) | set(ro["starts"]) | set(ro["dones"])
    assert tracelib.length(own) == sum(o.end - o.start for o in ops
                                       if o.name in mine) == 4111893
    assert tracelib.minus(own, sp.others()) == own
    assert collectives.beside(sp, "all-reduce.28") == [] and not sp.inside
    assert sp.flight == {"async-collective-done": [(375010426, 383946922)],
                         "async-collective-done.2": [(704565, 4820688)]}
    flight = tracelib.merge(iv for v in sp.flight.values() for iv in v)
    assert tracelib.length(tracelib.minus(flight, sp.others())) == 3523611


@pytest.mark.parametrize("name", READERS)
def test_every_reader_is_silent_on_one_chip(name):
    run = harness(HLO)
    ctx = ctx_of(pair(10, 50), run, chips=1)
    assert spec.reader(name)(ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_every_reader_is_silent_without_the_compiled_text(name):
    run = harness(None)
    ctx = ctx_of(pair(10, 50), run)
    assert spec.reader(name)(ctx) is None


def test_the_four_chip_cell_loads_with_its_chips():
    loaded = spec.cell(CELL)
    assert loaded["cell"]["chips"] == 4
    assert loaded["config"]["dist"]["mesh_shape"] == [4, 1]
    assert loaded["config"]["reduced"] == []
    assert loaded["config"]["num_hidden_layers"] == 28
    assert loaded["traffic"]["global_batch"] == 4
    assert {m["name"] for m in loaded["per_layer"]} >= set(READERS)
    # the l7 cells report none of the new readers
    for other in bench_smoke.CELLS.values():
        names = {m["name"] for m in spec.cell(other)["per_layer"]}
        assert not names & set(READERS)


def test_the_whole_model_holds_the_published_widths():
    c = spec.cell(CELL)["config"]
    assert (c["hidden_size"], c["intermediate_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["vocab_size"], c["num_hidden_layers"]) \
        == (2048, 6144, 16, 8, 128, 151936, 28)
    assert "n_layers" not in c["program"]["replace"]
    assert c["dist"]["remat"] == "fsdp_only"


def test_at_most_half_the_cells_ask_for_four_chips():
    cells = spec.benchmark()["workloads"]
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 2)
    assert all(w["chips"] in (1, 4) for w in cells)
