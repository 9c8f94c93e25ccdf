"""Memory subsystem (core/memory): live-range simulator invariants, the
budgeted auto-SAC planner, remat-spec validation, runtime parity of
per-segment policy vectors, calibration against XLA, and the
BENCH_memory.json schema smoke.

Multi-device parity of per-segment remat vs whole-block remat at pp2 x dp2
lives in tests/dist_harness.py case `remat_vector`.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import memory as MEM
from repro.core.api import parallelize, plan_parallel
from repro.core.dist import DistConfig
from repro.core.remat import (POLICIES, parse_remat, parse_policy_vector,
                              resolve_segment_policies, whole_block_policy)
from repro.models.common import ShapeConfig
from repro.models.registry import ARCH_IDS, get_arch

pytestmark = pytest.mark.memory

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROD = DistConfig(mesh_axes=("data", "model"), mesh_shape=(16, 16))
BSHAPE = (1, 4096)


def _small_cfg(**kw) -> DistConfig:
    return DistConfig(mesh_axes=("data", "model"), mesh_shape=(1, 1),
                      param_dtype=jnp.float32, storage_dtype=jnp.float32,
                      reduce_dtype=jnp.float32, **kw)


# ---------------------------------------------------------------------------
# remat spec grammar: one place, pointed errors, validated at plan time
# ---------------------------------------------------------------------------
def test_parse_remat_forms():
    assert parse_remat("fsdp_only") == ("fsdp_only", None)
    kind, budget = parse_remat("auto:12.5")
    assert kind == "auto" and budget == 12.5 * 1024**3
    assert parse_remat("attn=full,mlp=fsdp_only")[0] == "vector"
    assert parse_policy_vector("full,none") == ((None, "full"),
                                                (None, "none"))


@pytest.mark.parametrize("bad,msg", [
    ("auto", "needs an HBM budget"),
    ("auto:", "needs an HBM budget"),
    ("auto:abc", "not a number"),
    ("auto:0", "finite GiB value > 0"),
    ("auto:-3", "finite GiB value > 0"),
    ("auto:nan", "finite GiB value > 0"),
    ("auto:inf", "finite GiB value > 0"),
    ("bogus", "unknown remat policy"),
    ("attn=bogus,mlp=full", "unknown policy"),
    ("attn=full,fsdp_only", "mix of named"),
    ("full,,none", "empty entry"),
])
def test_parse_remat_pointed_errors(bad, msg):
    with pytest.raises(ValueError, match=msg):
        parse_remat(bad)


def test_malformed_remat_fails_at_plan_time_not_first_trace():
    """Satellite: plan_parallel rejects malformed strings once, pointedly."""
    _, model = get_arch("qwen3_1_7b", smoke=True)
    shape = ShapeConfig("t", 32, 8, "train")
    for bad in ("auto:", "auto:x", "zzz"):
        with pytest.raises(ValueError):
            plan_parallel(model, _small_cfg(remat=bad), shape)
    # auto without a shape cannot size activations -> pointed, not cryptic
    with pytest.raises(ValueError, match="shape"):
        plan_parallel(model, _small_cfg(remat="auto:8"))


def test_resolve_segment_policies():
    assert resolve_segment_policies("full", ("attn", "mlp")) \
        == ("full", "full")
    assert resolve_segment_policies("attn=none,mlp=full",
                                    ("attn", "mlp")) == ("none", "full")
    assert resolve_segment_policies("none,full", ("attn", "mlp")) \
        == ("none", "full")
    with pytest.raises(ValueError, match="cover the block segments"):
        resolve_segment_policies("attn=none", ("attn", "mlp"))
    with pytest.raises(ValueError, match="3 entries for 2"):
        resolve_segment_policies("none,full,full", ("attn", "mlp"))
    with pytest.raises(ValueError, match="unresolved"):
        resolve_segment_policies("auto:8", ("attn", "mlp"))
    assert whole_block_policy("attn=none,mlp=full") == "full"
    assert whole_block_policy("save_dots") == "save_dots"
    # aggressiveness = residuals DROPPED: save_dots drops more than
    # fsdp_only, so the collapse must pick save_dots of the two
    assert whole_block_policy("attn=save_dots,mlp=fsdp_only") == "save_dots"
    assert whole_block_policy("attn=none,mlp=fsdp_only") == "fsdp_only"


# ---------------------------------------------------------------------------
# simulator invariants — every registered arch
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_simulator_policy_monotonicity(arch):
    """peak(full) <= peak(save_dots) <= peak(fsdp_only) <= peak(none), on
    both stack paths (saved residuals on vanilla, backward recompute
    residency on the prefetch schedule)."""
    _, model = get_arch(arch)
    for reorder in (True, False):
        d = PROD.with_(reorder=reorder)
        peaks = {}
        for pol in ("full", "save_dots", "fsdp_only", "none"):
            bk = MEM.simulate_peak(model, d.with_(remat=pol), BSHAPE)
            assert len(bk) == 1 and bk[0].peak_bytes > 0
            peaks[pol] = bk[0].peak_bytes
        assert peaks["full"] <= peaks["save_dots"] \
            <= peaks["fsdp_only"] <= peaks["none"], (arch, reorder, peaks)


def test_simulator_pipeline_inflight_bounds():
    """GPipe holds M stacks, 1F1B min(M, S - s) — and the simulated 1F1B
    peak is never above GPipe's on any stage."""
    assert MEM.in_flight_microbatches(PROD.with_(pp_schedule="gpipe"),
                                      0, 4, 8) == 8
    assert MEM.in_flight_microbatches(PROD.with_(pp_schedule="1f1b"),
                                      0, 4, 8) == 4
    assert MEM.in_flight_microbatches(PROD.with_(pp_schedule="1f1b"),
                                      3, 4, 8) == 1

    from repro.models.registry import get_arch_for_pp
    _, model = get_arch_for_pp("deepseek_coder_33b", n_stages=2,
                               smoke=False)
    stage = model.stage_spec(2)
    d = PROD.with_(mesh_axes=("pipe", "data", "model"),
                   mesh_shape=(2, 8, 16), pp_axis="pipe")
    g = MEM.simulate_peak(model, d.with_(pp_schedule="gpipe"), BSHAPE,
                          stage=stage, microbatches=8)
    f = MEM.simulate_peak(model, d.with_(pp_schedule="1f1b"), BSHAPE,
                          stage=stage, microbatches=8)
    assert len(g) == 2 and len(f) == 2
    for gs, fs in zip(g, f):
        assert fs.peak_bytes <= gs.peak_bytes


def test_segment_prefetch_off_models_the_executed_collapse():
    """With cfg.segment_prefetch off the prefetch runtime collapses any
    vector to its most aggressive entry on one whole-layer segment — the
    simulator and planner must model THAT schedule, not the declared one."""
    _, model = get_arch("qwen3_1_7b")
    off = PROD.with_(segment_prefetch=False)
    # fixed vector: modeled as the collapsed policy ('full' beats 'none')
    bk = MEM.simulate_peak(model, off.with_(remat="attn=full,mlp=none"),
                           BSHAPE)
    ref = MEM.simulate_peak(model, off.with_(remat="full"), BSHAPE)
    assert bk[0].peak_bytes == ref[0].peak_bytes
    # auto: the search space collapses to uniform single-segment vectors
    mp = MEM.plan_memory(model, off.with_(remat="auto:8"),
                         batch_shape=BSHAPE)
    assert mp.segment_names == ("block",) and len(mp.policies) == 1
    # the vanilla path executes vectors regardless of segment_prefetch
    mpv = MEM.plan_memory(
        model, off.with_(reorder=False, remat="attn=full,mlp=none"),
        batch_shape=BSHAPE)
    assert mpv.policies == ("full", "none")


def test_simulator_offload_reduces_device_peak():
    _, model = get_arch("deepseek_coder_33b")
    base = MEM.simulate_peak(model, PROD, BSHAPE)[0]
    off = MEM.simulate_peak(model, PROD, BSHAPE, offload_opt=True)[0]
    assert off.peak_bytes < base.peak_bytes
    assert off.host_bytes > 0


# ---------------------------------------------------------------------------
# the budgeted planner
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_auto_budget_satisfied_every_arch(arch):
    """remat='auto:<GB>' produces plans whose modeled peak respects the
    budget on every registered arch (acceptance criterion)."""
    _, model = get_arch(arch)
    budget_gb = 8.0
    mp = MEM.plan_memory(model, PROD.with_(remat=f"auto:{budget_gb}"),
                         batch_shape=BSHAPE)
    assert mp.budget_bytes == budget_gb * 1024**3
    assert mp.peak <= mp.budget_bytes, mp.describe()
    assert all(p in POLICIES for p in mp.policies)
    # the resolved spec round-trips through the grammar
    resolve_segment_policies(
        mp.policy_spec,
        mp.segment_names if mp.segment_names != ("block",) else ())


def test_auto_infeasible_budget_raises_pointed():
    _, model = get_arch("deepseek_coder_33b")
    with pytest.raises(ValueError, match="no plan fits .* budget"):
        MEM.plan_memory(model, PROD.with_(remat="auto:0.01"),
                        batch_shape=BSHAPE)


def test_auto_nonuniform_beats_every_uniform_policy():
    """Acceptance: for at least one arch/budget the chosen per-segment
    vector is NON-uniform and strictly beats every uniform global policy on
    modeled recompute+exposure cost (infeasible uniforms count as +inf)."""
    found = None
    for arch in ("deepseek_coder_33b", "qwen3_moe_30b_a3b", "llama3_8b"):
        _, model = get_arch(arch)
        d = PROD.with_(reorder=False)   # vanilla: residuals swing on policy
        uni = {}
        for pol in POLICIES:
            mp = MEM.plan_memory(model, d.with_(remat=pol),
                                 batch_shape=BSHAPE)
            uni[pol] = (mp.peak, mp.cost_s)
        peaks = sorted(p for p, _ in uni.values())
        # budgets straddling the uniform peaks force mixing
        for i in range(len(peaks) - 1):
            budget = (peaks[i] + peaks[i + 1]) / 2 / 1024**3
            try:
                mp = MEM.plan_memory(
                    model, d.with_(remat=f"auto:{budget:.6f}"),
                    batch_shape=BSHAPE)
            except ValueError:
                continue
            if len(set(mp.policies)) > 1 and not mp.offload_opt_state \
                    and not mp.offload_residuals:
                for pol, (peak, cost) in uni.items():
                    if peak <= mp.budget_bytes:
                        assert mp.cost_s < cost, \
                            f"{arch}: {mp.policies} not beating {pol}"
                found = (arch, mp.policies, budget)
                break
        if found:
            break
    assert found, "no arch produced a winning non-uniform policy vector"


def test_auto_prefers_cheapest_when_budget_is_loose():
    _, model = get_arch("qwen3_1_7b")
    mp = MEM.plan_memory(model, PROD.with_(remat="auto:16"),
                         batch_shape=BSHAPE)
    assert set(mp.policies) == {"none"}        # zero recompute fits easily
    assert not mp.offload_opt_state and not mp.offload_residuals


# ---------------------------------------------------------------------------
# plan_parallel integration: the plan the runtime executes IS the plan
# ---------------------------------------------------------------------------
def test_plan_parallel_resolves_auto_into_exec_dcfg():
    _, model = get_arch("qwen3_1_7b", smoke=True)
    shape = ShapeConfig("t", 32, 8, "train")
    plan = plan_parallel(model, _small_cfg(remat="auto:8"), shape)
    assert plan.memory is not None
    assert plan.remat == "auto:8"                      # user intent kept
    kind, _ = parse_remat(plan.exec_dcfg.remat)        # resolved for trace
    assert kind != "auto"
    assert plan.memory.peak <= 8 * 1024**3
    assert "mem[" in plan.describe()


def test_fixed_plan_records_memory_but_keeps_dcfg():
    _, model = get_arch("qwen3_1_7b", smoke=True)
    shape = ShapeConfig("t", 32, 8, "train")
    dcfg = _small_cfg()
    plan = plan_parallel(model, dcfg, shape)
    assert plan.memory is not None
    assert plan.memory.policy_spec == dcfg.remat
    assert plan.exec_dcfg == dcfg


def test_per_segment_vector_exact_parity_single_device():
    """Per-segment remat vs whole-block remat: same losses and grads to
    fp32 tolerance on both stack paths (the pp2 x dp2 twin lives in
    dist_harness `remat_vector`)."""
    cfg, model = get_arch("qwen3_1_7b", smoke=True)
    shape = ShapeConfig("t", 32, 4, "train")
    from repro.data.pipeline import DataConfig, SyntheticC4, adapt_batch
    ds = SyntheticC4(DataConfig(vocab=cfg.vocab, seq_len=32,
                                global_batch=4))
    base = _small_cfg()
    batch = adapt_batch(ds.batch(0), model.input_specs(shape, base), 0)

    def run(**kw):
        par = parallelize(model, _small_cfg(**kw), shape)
        storage = par.init_storage(jax.random.PRNGKey(0))
        return par.loss_step()(storage, batch)

    ref_l, ref_g = run(reorder=False, remat="fsdp_only")
    for kw in (dict(reorder=False, remat="attn=full,mlp=fsdp_only"),
               dict(reorder=False, remat="attn=none,mlp=save_dots"),
               dict(reorder=True, remat="attn=full,mlp=save_dots")):
        loss, grads = run(**kw)
        np.testing.assert_allclose(float(loss), float(ref_l), rtol=1e-6,
                                   err_msg=str(kw))
        for (pa, a), (_, b) in zip(
                jax.tree_util.tree_flatten_with_path(grads)[0],
                jax.tree_util.tree_flatten_with_path(ref_g)[0]):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6,
                err_msg=f"{kw} {jax.tree_util.keystr(pa)}")


# ---------------------------------------------------------------------------
# calibration vs XLA on a 1-device block (launch/dryrun.harvest_memory_stats)
# ---------------------------------------------------------------------------
def test_memory_calibration_within_tolerance():
    from repro.launch.dryrun import harvest_memory_stats

    _, model = get_arch("qwen3_1_7b", smoke=True)
    ms = harvest_memory_stats(model, _small_cfg(), (2, 64))
    assert ms is not None, "1-device block memory harvest failed"
    ratio = ms.measured_bytes / ms.modeled_bytes
    # loose envelope: the analytic residency must be the right ORDER of
    # magnitude; act_scale carries the residual into the simulator clamped
    assert 0.1 <= ratio <= 10.0, ratio
    assert 0.25 <= ms.act_scale <= 4.0


def test_per_segment_harvest_feeds_simulator():
    from repro.launch.dryrun import harvest_block_stats

    _, model = get_arch("qwen3_1_7b", smoke=True)
    d = _small_cfg()
    bs = harvest_block_stats(model, d, (2, 64))
    assert bs is not None and bs.source == "measured"
    assert bs.seg_act_bytes and set(bs.seg_act_bytes) == {"attn", "mlp"}
    assert all(v > 0 for v in bs.seg_act_bytes.values())
    prof = MEM.build_block_profile(model.block_metas(d), d, bs,
                                   model.block_segments(d))
    names = {s.name: s for s in prof.segments}
    # the simulator consumes the MEASURED per-segment activation numbers
    for k, v in bs.seg_act_bytes.items():
        assert names[k].act_bytes == v


# ---------------------------------------------------------------------------
# BENCH_memory.json schema smoke (tier-1 artifact, like overlap/pipeline)
# ---------------------------------------------------------------------------
def test_bench_memory_json_schema(tmp_path):
    import json

    sys.path.insert(0, ROOT)
    try:
        from benchmarks import paper_tables as T
    finally:
        sys.path.pop(0)
    path = str(tmp_path / "BENCH_memory.json")
    doc = T.memory_table(json_path=path, archs=("llama3_8b",))
    on_disk = json.load(open(path))
    assert on_disk == doc
    assert doc["schema"] == "bench_memory_v1"
    for arch, rec in doc["archs"].items():
        modes = rec["modes"]
        assert set(modes) == {"none", "save_dots", "fsdp_only", "full",
                              "auto"}
        # the paper's Table 3 ordering: no-AC > SAC > full-AC on memory...
        assert modes["none"]["peak_bytes"] >= modes["fsdp_only"]["peak_bytes"] \
            >= modes["full"]["peak_bytes"]
        # ...reversed on modeled step time (recompute costs time)
        assert modes["full"]["modeled_step_s"] \
            >= modes["none"]["modeled_step_s"]
        assert modes["auto"]["peak_bytes"] <= doc["budget_gb"] * 1024**3
        for row in modes.values():
            assert row["peak_bytes"] > 0 and row["modeled_step_s"] > 0


def test_checked_in_bench_memory_json_is_current_schema():
    import json

    path = os.path.join(ROOT, "benchmarks", "results", "BENCH_memory.json")
    assert os.path.exists(path), "run `python -m benchmarks.run mem --json`"
    doc = json.load(open(path))
    assert doc["schema"] == "bench_memory_v1"
    assert len(doc["archs"]) >= 3


def test_host_offload_round_trip():
    """The backend exposes pinned_host (jax 0.9's CPU does too): the
    transfer really moves the arrays, and back, with their values."""
    tree = {"m": jnp.arange(8.0), "v": jnp.ones((2, 4))}
    assert MEM.host_offload_supported()
    host = MEM.to_host(tree)
    assert all(x.sharding.memory_kind == "pinned_host"
               for x in jax.tree.leaves(host))
    back = MEM.to_device(host)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        assert a.sharding.memory_kind == "device"
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
