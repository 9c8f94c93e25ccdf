"""Planner + segmented-scheduler tests (bucketing v2).

Covers the exposure-minimizing DP planner (`bucket_mode="auto_dp"`), the
guarded greedy planner, plan memoization, the segmented bucket-granular
prefetch stack's exact parity against the vanilla stack (1 device, fp32),
and the
BENCH_overlap.json emission schema.

Property tests use `hypothesis` when available and fall back to a fixed
parametrized sample on bare environments.
"""

try:
    import hypothesis
    import hypothesis.strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.autowrap import (auto_dp_plan, auto_layer_group, auto_plan,
                                 dp_buckets, exposed_comm_time,
                                 greedy_partition, partition_exposure,
                                 per_param_partition)
from repro.core.bucketing import (BucketPlan, clear_plan_cache, plan_for,
                                  per_param_plan)
from repro.core.dist import DistConfig
from repro.core.irgraph import BlockStats, CommNode
from repro.core.meta import ParamMeta
from repro.models.common import BlockSegments

pytestmark = pytest.mark.autowrap

CFG2D = DistConfig(mesh_axes=("data", "model"), mesh_shape=(4, 2))
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rand_nodes(n, seed):
    rng = np.random.RandomState(seed)
    return [
        CommNode(f"p{i}",
                 ag_bytes=int(rng.randint(1, 1 << 22)),
                 rs_bytes=int(rng.randint(1, 1 << 22)),
                 comp_flops=float(10.0 ** rng.uniform(3, 13)),
                 comp_bytes=float(rng.randint(1, 1 << 22)),
                 mem_bytes=float(rng.randint(1, 1 << 22)))
        for i in range(n)
    ]


# ---------------------------------------------------------------------------
# exposure(auto_dp) <= exposure(greedy) <= exposure(none), + DP feasibility
# ---------------------------------------------------------------------------
def _check_planner_chain(n, seed, mem_limit):
    rng = np.random.RandomState((seed + 1) % (2 ** 31))
    nodes = _rand_nodes(n, seed)
    # random forced cuts (segment boundaries) half the time
    cuts = frozenset(int(i) for i in rng.choice(max(n - 1, 1),
                                                size=rng.randint(0, n),
                                                replace=False) + 1) \
        if n > 1 and rng.rand() < 0.5 else frozenset()
    dpb = dp_buckets(nodes, CFG2D, mem_limit, cuts)
    grd = greedy_partition(nodes, CFG2D, mem_limit, cuts)
    solo = per_param_partition(nodes)
    for b in dpb:           # buckets never span a forced cut
        lo = nodes.index(b[0])
        assert not any(lo < c < lo + len(b) for c in cuts)
    e_dp = partition_exposure(dpb, CFG2D)
    e_gr = partition_exposure(grd, CFG2D)
    e_pp = partition_exposure(solo, CFG2D)
    assert e_dp <= e_gr + 1e-15 * max(1.0, e_gr)
    assert e_gr <= e_pp + 1e-15 * max(1.0, e_pp)
    # DP output is an order-preserving complete partition under the cap
    flat = [nd.name for b in dpb for nd in b]
    assert flat == [nd.name for nd in nodes]
    for b in dpb:
        if len(b) > 1:
            assert sum(nd.mem_bytes for nd in b) <= mem_limit


CHAIN_SAMPLE = [
    (1, 0, 1e6), (2, 1, 1e4), (5, 2, 1e22), (8, 3, 1 << 21),
    (11, 4, 1 << 23), (14, 5, 1e5), (9, 6, 1 << 22), (12, 7, 3 << 20),
]

if HAVE_HYPOTHESIS:
    @hypothesis.given(
        n=st.integers(1, 14),
        seed=st.integers(0, 2**31 - 1),
        mem_limit=st.floats(1e4, 1e22),
    )
    @hypothesis.settings(max_examples=30, deadline=None)
    def test_planner_exposure_chain(n, seed, mem_limit):
        _check_planner_chain(n, seed, mem_limit)
else:
    @pytest.mark.parametrize("n,seed,mem_limit", CHAIN_SAMPLE)
    def test_planner_exposure_chain(n, seed, mem_limit):
        _check_planner_chain(n, seed, mem_limit)


def test_dp_exact_on_small_instances():
    """DP == brute-force minimum over all contiguous partitions (n <= 8)."""
    import itertools
    for seed in range(12):
        nodes = _rand_nodes(seed % 8 + 1, 100 + seed)
        n = len(nodes)
        m_max = [1 << 20, 1 << 23, 1e22][seed % 3]
        best = np.inf
        for mask in range(1 << max(0, n - 1)):
            cuts = [0] + [i + 1 for i in range(n - 1)
                          if (mask >> i) & 1] + [n]
            bks = [nodes[a:b] for a, b in zip(cuts, cuts[1:])]
            if any(len(b) > 1 and sum(x.mem_bytes for x in b) > m_max
                   for b in bks):
                continue
            best = min(best, partition_exposure(bks, CFG2D))
        e_dp = partition_exposure(dp_buckets(nodes, CFG2D, m_max), CFG2D)
        assert abs(e_dp - best) <= 1e-12 + 1e-9 * best


@pytest.mark.parametrize("arch", [
    "llama3_8b", "deepseek_coder_33b", "phi3_medium_14b", "gemma2_27b",
    "qwen3_1_7b", "qwen2_moe_a2_7b", "qwen3_moe_30b_a3b", "xlstm_1_3b",
    "seamless_m4t_large_v2", "zamba2_1_2b", "internvl2_26b",
])
def test_auto_dp_beats_greedy_on_shipped_configs(arch):
    """Acceptance: modeled exposure(auto_dp) <= exposure(greedy) on every
    shipped model config (production mesh, analytic stats)."""
    from repro.launch.mesh import production_dcfg
    from repro.models.registry import get_arch

    cfg, model = get_arch(arch)
    dcfg = production_dcfg()
    # enc-dec has no single homogeneous block; plan its decoder stack
    metas_fn = getattr(model, "block_metas", None) \
        or getattr(model, "dec_block_metas")
    metas = metas_fn(dcfg)
    stats = model.block_stats(dcfg, (1, 4096)) \
        if hasattr(model, "block_stats") else None
    segments = model.block_segments(dcfg) \
        if hasattr(model, "block_segments") else None
    e_dp = exposed_comm_time(
        auto_dp_plan(metas, dcfg, stats, segments=segments),
        metas, dcfg, stats, segments=segments)["exposed_s"]
    e_gr = exposed_comm_time(
        auto_plan(metas, dcfg, stats, segments=segments),
        metas, dcfg, stats, segments=segments)["exposed_s"]
    e_pp = exposed_comm_time(per_param_plan(metas), metas, dcfg, stats,
                             segments=segments)["exposed_s"]
    assert e_dp <= e_gr + 1e-15
    assert e_gr <= e_pp + 1e-15


# ---------------------------------------------------------------------------
# auto_layer_group memory accounting (satellite regression)
# ---------------------------------------------------------------------------
def test_auto_layer_group_mem_single_counted():
    """Regression: auto_layer_group applied an ad-hoc 2x multiplier to the
    candidate bucket's bytes, inconsistent with greedy_buckets' single-count
    cap (same bug class as the greedy double count fixed in PR 1). With a
    cap of exactly 4 layers' bytes and compute that hides everything, the
    answer must be 4 (the doubled accounting stopped at 2)."""
    node = CommNode("p", ag_bytes=1 << 10, rs_bytes=1 << 10,
                    comp_flops=1e13, comp_bytes=1.0, mem_bytes=1 << 20)
    k = auto_layer_group([node], CFG2D, n_layers=8,
                         mem_limit=4 * (1 << 20))
    assert k == 4


# ---------------------------------------------------------------------------
# plan_for memoization
# ---------------------------------------------------------------------------
def _metas():
    return {
        "attn": {"wq": ParamMeta("attn.wq", (8, 8), 1),
                 "wo": ParamMeta("attn.wo", (8, 8), 0)},
        "mlp": {"wu": ParamMeta("mlp.wu", (8, 16), 1)},
        "ln": ParamMeta("ln", (8,)),
    }


def test_plan_for_memoized():
    clear_plan_cache()
    metas = _metas()
    cfg = CFG2D.with_(bucket_mode="auto_dp")
    stats = BlockStats({"attn/wq": 1e9}, {"attn/wq": 1e3})
    p1 = plan_for(metas, cfg, stats)
    p2 = plan_for(_metas(), cfg,
                  BlockStats({"attn/wq": 1e9}, {"attn/wq": 1e3}))
    assert p1 is p2                      # cache hit on equal-valued inputs
    from repro.core import bucketing as B
    assert len(B._PLAN_CACHE) == 1
    plan_for(metas, cfg, BlockStats({"attn/wq": 2e9}, {"attn/wq": 1e3}))
    assert len(B._PLAN_CACHE) == 2       # different stats: new cache entry
    p4 = plan_for(metas, cfg.with_(bucket_mode="none"), stats)
    assert p4.n_buckets == 4             # cfg participates in the key
    assert len(B._PLAN_CACHE) == 3
    clear_plan_cache()


def test_plan_for_auto_dp_resolves():
    plan = plan_for(_metas(), CFG2D.with_(bucket_mode="auto_dp"))
    covered = sorted(n for grp in plan.groups for n in grp)
    assert covered == ["attn/wo", "attn/wq", "ln", "mlp/wu"]


# ---------------------------------------------------------------------------
# Segmented bucket-granular prefetch: exact parity vs the vanilla stack
# (1 device, fp32).
# ---------------------------------------------------------------------------
SD_CFG = DistConfig(mesh_axes=("data", "model"), mesh_shape=(1, 1),
                    param_dtype=jnp.float32, reduce_dtype=jnp.float32)


def _toy_setup():
    from repro.models import runtime as RT

    metas = {"a": {"w1": ParamMeta("a.w1", (8, 16)),
                   "b": ParamMeta("a.b", (16,)),
                   "w2": ParamMeta("a.w2", (16, 8))},
             "m": {"u": ParamMeta("m.u", (8, 12)),
                   "d": ParamMeta("m.d", (12, 8))}}
    L = 5
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    full = {"a": {"w1": jax.random.normal(ks[0], (L, 8, 16)) * 0.3,
                  "b": jax.random.normal(ks[1], (L, 16)) * 0.1,
                  "w2": jax.random.normal(ks[2], (L, 16, 8)) * 0.3},
            "m": {"u": jax.random.normal(ks[3], (L, 8, 12)) * 0.3,
                  "d": jax.random.normal(ks[4], (L, 12, 8)) * 0.3}}
    stacked = {k: RT.tree_to_storage(full[k], metas[k], SD_CFG)
               for k in full}
    x = jax.random.normal(jax.random.PRNGKey(9), (4, 8))

    def block_fn(p, consts, xc):
        h = jnp.tanh(xc @ p["a"]["w1"] + p["a"]["b"]) @ p["a"]["w2"]
        x1 = xc + h
        h2 = jax.nn.silu(x1 @ p["m"]["u"]) @ p["m"]["d"]
        return x1 + h2, {"z": (h2 ** 2).mean()}

    def seg_a(p, consts, xc):
        h = jnp.tanh(xc @ p["a"]["w1"] + p["a"]["b"]) @ p["a"]["w2"]
        return xc + h

    def seg_m(p, consts, x1):
        h2 = jax.nn.silu(x1 @ p["m"]["u"]) @ p["m"]["d"]
        return x1 + h2, {"z": (h2 ** 2).mean()}

    segs = BlockSegments(("a", "m"), (("a/*",), ("m/*",)), (seg_a, seg_m))
    return metas, stacked, x, block_fn, segs


@pytest.mark.parametrize("plan", [
    # multi-bucket, segment-aligned
    BucketPlan((("a/w1", "a/b"), ("a/w2",), ("m/u", "m/d"))),
    # a bucket SPANNING the segment boundary is split by the stack
    BucketPlan((("a/w1", "a/b", "a/w2", "m/u"), ("m/d",))),
])
@pytest.mark.parametrize("flags", [
    dict(),
    dict(rs_delay=False),
    dict(ag_before_wait_fwd=False, ag_before_wait_bwd=True),
])
def test_segmented_prefetch_matches_vanilla_toy(plan, flags):
    from repro.core.stack import apply_stack

    metas, stacked, x, block_fn, segs = _toy_setup()

    def loss(stacked_, reorder, use_segs, **kw):
        c = SD_CFG.with_(reorder=reorder, **kw)
        y, aux = apply_stack(block_fn, metas, c, stacked_, {}, x,
                             plan=plan, segments=segs if use_segs else None)
        return (y ** 2).mean() + aux["z"]

    l0, g0 = jax.value_and_grad(lambda s: loss(s, False, False))(stacked)
    l1, g1 = jax.value_and_grad(
        lambda s: loss(s, True, True, **flags))(stacked)
    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    for (ka, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(g0),
            jax.tree_util.tree_leaves_with_path(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6, err_msg=str(ka))


def test_segmented_prefetch_single_layer():
    """L=1: within-layer bucket pipelining without cross-layer prefetch."""
    from repro.core.stack import apply_stack
    from repro.models import runtime as RT

    metas, stacked, x, block_fn, segs = _toy_setup()
    stacked1 = jax.tree.map(lambda v: v[:1], stacked)
    plan = BucketPlan((("a/w1", "a/b", "a/w2"), ("m/u", "m/d")))

    def loss(s, reorder):
        c = SD_CFG.with_(reorder=reorder)
        y, aux = apply_stack(block_fn, metas, c, s, {}, x, plan=plan,
                             segments=segs)
        return (y ** 2).mean() + aux["z"]

    l0, g0 = jax.value_and_grad(lambda s: loss(s, False))(stacked1)
    l1, g1 = jax.value_and_grad(lambda s: loss(s, True))(stacked1)
    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_segment_globs_must_cover_params():
    from repro.core.stack import apply_stack

    metas, stacked, x, block_fn, segs = _toy_setup()
    bad = BlockSegments(("a", "m"), (("a/*",), ("m/u",)), segs.fns)
    with pytest.raises(ValueError, match="unassigned"):
        apply_stack(block_fn, metas, SD_CFG.with_(reorder=True), stacked,
                    {}, x, segments=bad)


def test_model_segmented_prefetch_matches_vanilla():
    """Acceptance: the segmented bucket-granular stack passes exact fp32
    parity (outputs + grads) against the vanilla stack for a multi-bucket
    auto_dp plan, on the real dense model (1 device)."""
    from jax.sharding import PartitionSpec as P

    from repro.models import runtime as RT
    from repro.models.common import ShapeConfig
    from repro.models.registry import get_arch

    cfg, model = get_arch("qwen3_1_7b", smoke=True)
    shape = ShapeConfig("t", 32, 2, "train")
    storage = RT.init_storage(model, jax.random.PRNGKey(0), SD_CFG)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                     cfg.vocab),
        "targets": jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0,
                                      cfg.vocab),
        "valid": jnp.ones((2, 32)),
    }
    # a plan with one bucket per segment -> true multi-bucket pipelining
    plan = BucketPlan((("ln1", "attn/wq", "attn/wk", "attn/wv", "attn/wo",
                        "attn/q_norm", "attn/k_norm"),
                       ("ln2", "mlp/wg", "mlp/wu", "mlp/wd")))
    outs = {}
    for name, kw in [("vanilla", dict(reorder=False, bucket_mode="none")),
                     ("segmented", dict(reorder=True, bucket_mode=plan)),
                     ("auto_dp", dict(reorder=True, bucket_mode="auto_dp"))]:
        dcfg = SD_CFG.with_(**kw)
        step = RT.make_loss_step(model, dcfg)
        specs = RT.model_storage_specs(model, dcfg)
        fn, _ = RT.wrap_step(model, dcfg, shape, step, (P(), specs))
        loss, grads = fn(storage, batch)
        outs[name] = (float(loss), grads)
    l0, g0 = outs["vanilla"]
    for name in ("segmented", "auto_dp"):
        l1, g1 = outs[name]
        np.testing.assert_allclose(l0, l1, rtol=1e-6, err_msg=name)
        for (ka, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(g0),
                jax.tree_util.tree_leaves_with_path(g1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=1e-6,
                                       err_msg=f"{name}/{ka}")


def test_gemma2_pair_segments_parity():
    """The 4-segment local/global pair (checkpointed segment fns, aux
    threaded through tuple inter-segment states) ships enabled by default —
    exact fp32 parity vs vanilla under block and auto_dp plans."""
    from jax.sharding import PartitionSpec as P

    from repro.models import runtime as RT
    from repro.models.common import ShapeConfig
    from repro.models.registry import get_arch

    cfg, model = get_arch("gemma2_27b", smoke=True)
    assert model.layers_per_step == 2   # the pair path, 4 segments
    shape = ShapeConfig("t", 32, 2, "train")
    storage = RT.init_storage(model, jax.random.PRNGKey(0), SD_CFG)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                     cfg.vocab),
        "targets": jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0,
                                      cfg.vocab),
        "valid": jnp.ones((2, 32)),
    }
    outs = {}
    for name, kw in [("vanilla", dict(reorder=False, bucket_mode="none")),
                     ("block", dict(reorder=True, bucket_mode="block")),
                     ("auto_dp", dict(reorder=True, bucket_mode="auto_dp"))]:
        dcfg = SD_CFG.with_(**kw)
        step = RT.make_loss_step(model, dcfg)
        fn, _ = RT.wrap_step(model, dcfg, shape, step,
                             (P(), RT.model_storage_specs(model, dcfg)))
        outs[name] = fn(storage, batch)
    l0, g0 = outs["vanilla"]
    for name in ("block", "auto_dp"):
        l1, g1 = outs[name]
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6,
                                   err_msg=name)
        for (ka, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(g0),
                jax.tree_util.tree_leaves_with_path(g1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=1e-6,
                                       err_msg=f"{name}/{ka}")


# ---------------------------------------------------------------------------
# BENCH_overlap.json emission (tier-1 smoke; plan regressions fail here)
# ---------------------------------------------------------------------------
def test_bench_overlap_json_schema(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + ROOT
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "fig4", "--json"],
        capture_output=True, text=True, timeout=540, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    path = os.path.join(ROOT, "benchmarks", "results", "BENCH_overlap.json")
    doc = json.load(open(path))
    assert doc["schema"] == "bench_overlap_v2"
    assert len(doc["archs"]) >= 2
    for arch, rec in doc["archs"].items():
        assert rec["stats_source"] in ("analytic", "measured")
        modes = rec["modes"]
        assert set(modes) == {"none", "block", "greedy", "auto_dp"}
        for m in modes.values():
            for k in ("exposed_s", "total_comm_s", "compute_s", "n_buckets",
                      "modeled_step_s"):
                assert k in m and m[k] >= 0
        # the acceptance invariant, re-checked on the emitted artifact
        assert modes["auto_dp"]["exposed_s"] \
            <= modes["greedy"]["exposed_s"] + 1e-12
        assert modes["greedy"]["exposed_s"] \
            <= modes["none"]["exposed_s"] + 1e-12
        # v2: the per-bucket comm_precision ablation (PR 7) — wire-byte
        # and exposed-comm claims re-checked on the emitted artifact
        cp = rec["comm_precision"]
        assert {"bf16", "fp8", "fp8_ef", "auto"} <= set(cp)
        bf16 = cp["bf16"]
        assert bf16["quant_overhead_s"] == 0.0
        for q in ("fp8", "fp8_ef"):
            assert cp[q]["comm_wire_bytes"] \
                <= 0.55 * bf16["comm_wire_bytes"], (arch, q)
            if bf16["exposed_comm_s"] > 0:
                assert cp[q]["exposed_comm_s"] < bf16["exposed_comm_s"], \
                    (arch, q)
        assert cp["auto"]["exposed_s"] <= bf16["exposed_s"] + 1e-12, arch
