"""The trace reduction on hand-made events: busy unions, idle share,
collective exposure and kernel time found by the Pallas call's name."""

import types

import pytest

import bench_smoke  # noqa: F401
import spec
import tracelib
from tracelib import Op, Trace


def trace():
    # device 0, window [0, 100): compute 0-30 and 20-40 overlap (busy 40),
    # an all-gather 35-60 half hidden behind compute (exposed 40-60), a
    # reduce-scatter 70-80 alone (exposed), the adamw call 85-95
    ops = [Op(0, 30, "fusion.1"), Op(20, 40, "fusion.2"),
           Op(35, 60, "all-gather-start.3", "collective"),
           Op(70, 80, "reduce-scatter.4"), Op(85, 95, "custom-call.7")]
    spans = [(0, 10, "batch"), (10, 62, "dispatch"), (62, 100, "sync")]
    return Trace({0: ops}, spans)


def test_busy_union_and_idle_share():
    t = trace()
    lo, hi = tracelib.window(t)
    assert (lo, hi) == (0, 100)
    busy = tracelib.busy(t, 0, lo, hi)
    assert busy == [(0, 60), (70, 80), (85, 95)]
    assert tracelib.length(busy) == 80
    ctx = types.SimpleNamespace(trace=t, lo=lo, hi=hi, devices=[0])
    assert spec.reader("device_idle_pct")(ctx) == pytest.approx(20.0)


def test_idle_gaps_carry_the_host_span():
    t = trace()
    gaps = tracelib.idle_gaps(t, 0, 0, 100)
    assert gaps == [("sync", 10), ("sync", 5), ("sync", 5)]


def test_collective_exposure_with_and_without_overlap():
    t = trace()
    exp = tracelib.collective_exposed(t, 0, 0, 100)
    assert exp == [(40, 60), (70, 80)]
    # union of collective intervals: 35-60 and 70-80
    coll = tracelib.merge((o.start, o.end) for o in t.ops[0]
                          if tracelib.is_collective(o))
    assert tracelib.length(coll) == 35


def test_kernel_time_by_pallas_name():
    t = trace()
    ctx = types.SimpleNamespace(
        trace=t, lo=0, hi=100, devices=[0],
        kernels={"custom-call.7": ("adamw", 819)},
        peaks={"hbm_bytes_per_s": 819e9})
    # 819 bytes at 819e9 B/s is 1 ns, over 10 ns of kernel time
    assert spec.reader("adamw_roofline")(ctx) == pytest.approx(10.0)
    assert spec.reader("rmsnorm_roofline")(ctx) is None


def test_minus_and_merge():
    assert tracelib.merge([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tracelib.minus([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tracelib.minus([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def test_window_falls_back_to_the_device_extent():
    t = Trace({0: [Op(500, 530, "fusion.1"), Op(540, 600, "fusion.2")]},
              [(0, 10, "batch"), (10, 100, "sync")])
    assert tracelib.window(t) == (500, 600)


def test_self_time_leaves_out_nested_ops():
    # a loop 0-50 whose body ops take 10-20 and 30-45; a fusion after it
    ops = [Op(0, 50, "while.1"), Op(10, 20, "fusion.2"),
           Op(30, 45, "fusion.3"), Op(60, 70, "fusion.4")]
    assert tracelib.self_times(ops, 0, 100) == {
        "while.1": 25, "fusion.2": 10, "fusion.3": 15, "fusion.4": 10}
    assert tracelib.self_times(ops, 0, 15)["fusion.2"] == 5


def test_op_names_and_labels_from_hlo_text():
    text = ("%fusion.578 = (f32[4096]{0:T(1024)S(1)}, f32[4096,151936]"
            "{1,0:T(8,128)}) fusion(bf16[4096,2048]{1,0:T(8,128)(2,1)} "
            "%x.1), kind=kOutput, calls=%fused_computation.1040")
    assert tracelib.HLO_NAME.match(text).group(1) == "fusion.578"
    assert tracelib.label(Op(0, 1, "fusion.578", "", text)) == \
        "fusion.578 fusion (f32[4096], f32[4096,151936])"


def test_kernel_bytes_count_operands_named_in_the_compiled_text():
    import train_bench

    hlo = "\n".join([
        "  %copy.1 = f32[1024]{0:T(1024)} copy(f32[1024]{0} %p.0)",
        "  %copy.2 = f32[1024]{0:T(1024)} copy(f32[1024]{0} %p.1)",
        "  %adamw.24 = (f32[1024]{0:T(1024)}, f32[1024]{0:T(1024)}) "
        "custom-call(%copy.1, %copy.2), custom_call_target="
        "\"tpu_custom_call\", metadata={op_name=\"jit(step)/adamw/"
        "pallas_call\"}",
        "  %jvp_rmsnorm_.57 = bf16[8,128]{1,0} custom-call(%copy.1), "
        "custom_call_target=\"tpu_custom_call\""])
    assert train_bench.kernel_calls(hlo) == {
        "adamw.24": ("adamw", 4 * 4096), "jvp_rmsnorm_.57": ("rmsnorm",
                                                             2048 + 4096)}
