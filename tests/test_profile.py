"""Profile-guided replanning: step profiler, calibrated BlockStats,
modeled-vs-measured trace overlay, and the replan loop (core/obs/profile +
core/obs/calibrate).

The load-bearing claims:

  * closure — the calibrated plan's `modeled_step_time`, evaluated under
    `calibration`, lands on the measured wall step, so the calibrated
    |residual| is strictly below the analytic prior's (~1.0 here, since
    the analytic model prices the TPU roofline and the container runs
    CPU);
  * monotonicity — `calibrated_block_stats` never invents data: unseen
    params keep their analytic values, an empty profile is the identity,
    and `replan` with unchanged rates reproduces the plan verbatim;
  * overlay isolation — the measured track rides PID_MEASURED only; the
    modeled lanes (and the PR-9 invariant `nonoverlapped_comm_s ==
    exposed_s`) are byte-identical with or without a profile attached.

Everything runs on the single default CPU device (mesh 1x1 for executed
paths; planner-only tests use larger meshes, which are pure math).
"""

import dataclasses
import json
import math

import jax.numpy as jnp
import pytest

from repro.core import hw, irgraph
from repro.core.api import plan_parallel
from repro.core.dist import (AUTO_PRECISIONS, COMM_PRECISIONS, DistConfig,
                             precision_codecs)
from repro.core.obs import (PID_MEASURED, PID_MODELED, MeasuredProfile,
                            calibrated_block_stats, calibrated_step_time,
                            calibration, modeled_step_time,
                            nonoverlapped_comm_s, plan_trace, profile_step,
                            replan)
from repro.models.common import ShapeConfig
from repro.models.registry import get_arch

pytestmark = pytest.mark.profile

DCFG1 = DistConfig(mesh_axes=("data", "model"), mesh_shape=(1, 1),
                   param_dtype=jnp.float32, reduce_dtype=jnp.float32,
                   bucket_mode="auto")
SHAPE = ShapeConfig("t", 64, 8, "train")


@pytest.fixture(scope="module")
def profiled():
    """One measured profile of the executed 1-device plan, shared."""
    cfg, model = get_arch("qwen3_1_7b", smoke=True)
    plan = plan_parallel(model, DCFG1, SHAPE)
    prof = profile_step(model, plan, SHAPE, steps=2)
    return cfg, model, plan, prof


# ---------------------------------------------------------------------------
# calibrated_block_stats: identity + monotonicity
# ---------------------------------------------------------------------------
def test_calibrated_stats_identity_on_empty_profile():
    _, model = get_arch("qwen3_1_7b", smoke=True)
    stats = model.block_stats(DCFG1, (8, 64))
    assert calibrated_block_stats(stats, None) is stats
    assert calibrated_block_stats(stats, MeasuredProfile.empty()) is stats
    assert calibrated_block_stats(None, MeasuredProfile.empty()) is None


def test_calibrated_stats_monotone_unseen_params():
    _, model = get_arch("qwen3_1_7b", smoke=True)
    stats = model.block_stats(DCFG1, (8, 64))
    names = sorted(stats.param_flops)
    seen, unseen = names[0], names[-1]
    prof = MeasuredProfile(seg_scales={"seg": 2.0},
                           param_segment={seen: "seg"})
    cal = calibrated_block_stats(stats, prof)
    assert cal.source == "calibrated"
    assert cal.param_flops[seen] == pytest.approx(
        2.0 * stats.param_flops[seen])
    assert cal.param_bytes[seen] == pytest.approx(
        2.0 * stats.param_bytes[seen])
    # a param the profiler never saw keeps its analytic value
    assert cal.param_flops[unseen] == stats.param_flops[unseen]
    assert cal.param_bytes[unseen] == stats.param_bytes[unseen]
    assert cal.act_bytes == stats.act_bytes
    assert cal.seg_act_bytes == stats.seg_act_bytes
    # the calibrated contract re-keys the plan memo
    assert cal.cache_key() != stats.cache_key()


def test_replan_unchanged_rates_is_identity():
    _, model = get_arch("qwen3_1_7b", smoke=True)
    plan = plan_parallel(model, DCFG1, SHAPE)
    new_plan, delta = replan(model, plan, SHAPE, MeasuredProfile.empty())
    assert delta["changed"] is False
    assert new_plan.describe() == plan.describe()
    assert delta["fields"] == {}
    assert new_plan.dcfg == plan.dcfg


# ---------------------------------------------------------------------------
# calibration context: install + restore of measured hw rates
# ---------------------------------------------------------------------------
def test_calibration_context_installs_and_restores():
    prof = MeasuredProfile(
        comm_bandwidth={"data": {"bytes_per_s": 1e9, "alpha_s": 2e-6}},
        quant_rates={"int8": 1e11, "fp8": 2e11})
    analytic_bw = hw.axis_bandwidth("data")
    with calibration(prof):
        bw = hw.axis_bandwidth("data")
        assert bw.bytes_per_s == 1e9 and bw.alpha_s == 2e-6
        assert irgraph.quant_codec_rate("int8") == 1e11
        assert irgraph.quant_codec_rate("fp8") == 2e11
    assert hw.axis_bandwidth("data") == analytic_bw
    assert irgraph.quant_codec_rate("int8") == hw.HBM_BANDWIDTH / 2.0
    assert irgraph.quant_codec_rate("fp8") == hw.HBM_BANDWIDTH / 2.0


# ---------------------------------------------------------------------------
# the measured profile itself
# ---------------------------------------------------------------------------
def test_profile_json_roundtrip(profiled):
    _, _, _, prof = profiled
    p2 = MeasuredProfile.from_json(prof.to_json())
    assert p2.to_json() == prof.to_json()
    assert p2.wall_step_s == prof.wall_step_s
    assert p2.seg_scales == prof.seg_scales


def test_profile_wall_spans_match_wall_step(profiled):
    _, _, _, prof = profiled
    walls = [s["dur_s"] for s in prof.spans if s["cat"] == "wall"]
    assert len(walls) == prof.meta["steps"]
    assert all(w > 0 for w in walls)
    # the frozen wall step is the median of the timed step spans, so the
    # span table sums to within CPU-noise tolerance of steps x wall
    assert sum(walls) == pytest.approx(
        len(walls) * prof.wall_step_s, rel=0.5)
    assert sorted(walls)[len(walls) // 2] >= prof.wall_step_s * 0.999 \
        or len(walls) % 2 == 0
    assert prof.rank_step_s == {"0": prof.wall_step_s}


def test_closed_loop_residual_shrinks(profiled):
    """The acceptance loop on one arch (the bench covers three): the
    calibrated, replanned plan's step-time promise must land strictly
    closer to the measured wall than the analytic prior."""
    _, model, plan, prof = profiled
    wall = prof.wall_step_s
    before = modeled_step_time(model, plan, SHAPE)
    new_plan, delta = replan(model, plan, SHAPE, prof)
    after = calibrated_step_time(model, new_plan, SHAPE, prof)
    resid_before = abs(before - wall) / wall
    resid_after = abs(after - wall) / wall
    assert math.isfinite(resid_before) and math.isfinite(resid_after)
    assert resid_after < resid_before
    # closure tolerance: the fixed point stops within 2% + slack
    assert resid_after <= 0.05
    assert delta["wall_step_s"] == wall


# ---------------------------------------------------------------------------
# modeled-vs-measured trace overlay
# ---------------------------------------------------------------------------
def test_overlay_golden_byte_identical(profiled):
    cfg, model, plan, prof = profiled
    j1 = plan_trace(model, plan, SHAPE, arch_cfg=cfg,
                    profile=prof).to_json()
    j2 = plan_trace(model, plan, SHAPE, arch_cfg=cfg,
                    profile=prof).to_json()
    assert j1 == j2


def test_overlay_preserves_modeled_lanes(profiled):
    """Attaching the measured track must not move a single modeled event,
    so the PR-9 invariant (nonoverlapped comm == exposed_s) survives by
    construction."""
    cfg, model, plan, prof = profiled
    bare = plan_trace(model, plan, SHAPE, arch_cfg=cfg).to_doc()
    over = plan_trace(model, plan, SHAPE, arch_cfg=cfg,
                      profile=prof).to_doc()

    def modeled(doc):
        return [e for e in doc["traceEvents"]
                if e.get("pid") == PID_MODELED]

    assert modeled(bare) == modeled(over)
    assert nonoverlapped_comm_s(bare) == nonoverlapped_comm_s(over)
    meas = [e for e in over["traceEvents"] if e.get("pid") == PID_MEASURED]
    assert meas, "no measured track emitted"
    spans = [e for e in meas if e.get("ph") == "X"]
    assert spans
    for e in spans:
        assert {"modeled_s", "measured_s", "rel_residual"} \
            <= set(e["args"]), e["name"]


# ---------------------------------------------------------------------------
# int8 on the precision lattice (quant follow-up (b))
# ---------------------------------------------------------------------------
def test_int8_lattice_vocabulary():
    for p in ("int8_ag", "int8", "int8_ef"):
        assert p in COMM_PRECISIONS
        DistConfig(mesh_axes=("data", "model"), mesh_shape=(1, 1),
                   comm_precision=p)       # accepted by validation
    assert {"int8_ag", "int8_ef"} <= set(AUTO_PRECISIONS)
    # fp8 stays ahead of int8 in the lattice: strict-< improvement keeps
    # analytic ties on fp8, so plans only move on measured rates
    assert AUTO_PRECISIONS.index("fp8_ag") < AUTO_PRECISIONS.index(
        "int8_ag")
    assert precision_codecs("int8_ag") == ("int8", None)
    assert precision_codecs("int8") == ("int8", "int8")
    assert precision_codecs("int8_ef") == ("int8", "int8")
    d = DistConfig(mesh_axes=("data", "model"), mesh_shape=(1, 1),
                   comm_precision="int8_ef")
    assert d.needs_ef
    assert not DistConfig(mesh_axes=("data", "model"), mesh_shape=(1, 1),
                          comm_precision="int8_ag").needs_ef


def _auto_nodes():
    """Planner-only setup at a comm-bound mesh (pure math, no devices):
    fsdp=256 makes wire time dominate, so 'auto' quantizes — which codec
    it picks is then decided by the quant overhead pricing."""
    _, model = get_arch("qwen3_1_7b", smoke=True)
    dcfg = DistConfig(mesh_axes=("data", "model"), mesh_shape=(256, 1),
                      comm_precision="auto")
    stats = model.block_stats(dcfg, (8, 64))
    nodes = irgraph.build_nodes(model.block_metas(dcfg), dcfg, stats)
    return nodes, dcfg


def test_auto_planner_keeps_fp8_without_measured_rates():
    from repro.core.autowrap import dp_buckets_precision

    nodes, dcfg = _auto_nodes()
    _, precs = dp_buckets_precision(nodes, dcfg)
    assert any(p != "bf16" for p in precs), "comm-bound mesh must quantize"
    # int8 prices identically to fp8 analytically (same wire bytes, same
    # default codec rate); strict-< improvement keeps the fp8 pick
    assert not any(p.startswith("int8") for p in precs)


def test_auto_planner_picks_int8_on_measured_rates():
    from repro.core.autowrap import dp_buckets_precision

    nodes, dcfg = _auto_nodes()
    prof = MeasuredProfile(quant_rates={"int8": 1e14, "fp8": 1e7})
    with calibration(prof):
        _, precs = dp_buckets_precision(nodes, dcfg)
    assert any(p.startswith("int8") for p in precs), precs
    assert not any(p.startswith("fp8") for p in precs), precs
    # restored: the analytic tie goes back to fp8
    _, precs2 = dp_buckets_precision(nodes, dcfg)
    assert not any(p.startswith("int8") for p in precs2)


# ---------------------------------------------------------------------------
# trainer hook: drift streak -> profile -> replan -> restart
# ---------------------------------------------------------------------------
def test_trainer_replan_hook_applies(tmp_path):
    from repro.optim.adamw import AdamWConfig
    from repro.train.trainer import Trainer, TrainerConfig

    _, model = get_arch("qwen3_1_7b", smoke=True)
    tcfg = TrainerConfig(total_steps=4, ckpt_every=100, log_every=10,
                         warmup=1, ckpt_dir=str(tmp_path),
                         replan_threshold=0.5, replan_patience=2,
                         replan_apply=True, replan_profile_steps=1)
    tr = Trainer(model, DCFG1, SHAPE, AdamWConfig(lr=1e-3), tcfg)
    assert tr._modeled_step_s is not None
    tr.run()
    # the analytic promise is TPU-roofline us vs CPU-wall seconds, so the
    # |rel| streak trips on the first `replan_patience` steps
    assert len(tr.replans) >= 1
    delta = tr.replans[0]
    assert delta["step"] == tcfg.replan_patience
    assert tr.profile is not None and tr.profile.wall_step_s > 0
    assert tr.registry.counter("replan/count").value >= 1
    if delta["changed"]:
        assert delta["applied"]
        # the loop runs on the plan of the last replan it applied (CPU
        # walls may re-arm the streak after the first one)
        last = [d for d in tr.replans if d["applied"]][-1]
        assert tr.plan.describe() == last["after"]
        # the promise was re-anchored to the calibrated model: the loop's
        # remaining steps must not arm another replan
        rows = tr.drift.records["step_time"]
        assert abs(rows[-1]["rel"]) <= 0.5 or len(tr.replans) > 1
    # training survived the restart and ran to completion
    assert tr.registry.counter("train/steps").value == tcfg.total_steps
