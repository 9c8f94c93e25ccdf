"""Device-free tests of the context-parallelism subsystem (core/context.py).

Ring-attention NUMERICS run through the host emulators — the same per-hop
block math (`_accum_hop` / `_hop_grads`) the mesh ring executes, driven
over sliced shards instead of ppermute — asserted exactly against
`models/layers.attention_ref` (forward, autodiff grads, and the
HAND-WRITTEN reverse-ring backward) across causal x sliding-window x
softcap x GQA x odd seq/cp remainders.  The mesh plumbing itself (ppermute
ring, travelling dK/dV accumulators, cp2 == cp1 training parity at
pp2 x dp2 x cp2) is covered by tests/dist_harness.py case `context`.

Also here: zigzag layout invariants (permutation, equal causal work),
plan_parallel's cp validation errors, seq-sharded batch specs, the memory
simulator's ring-KV term + activations/cp scaling, the simulator-driven
`auto_microbatches` pick, and the BENCH_context.json schema smoke.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import context as CX
from repro.core.dist import DistConfig
from repro.models.layers import attention_ref

pytestmark = pytest.mark.context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _qkv(key, B=2, S=24, H=4, Kh=2, hd=8, scale=0.5):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd)) * scale
    k = jax.random.normal(ks[1], (B, S, Kh, hd)) * scale
    v = jax.random.normal(ks[2], (B, S, Kh, hd)) * scale
    return q, k, v


# ---------------------------------------------------------------------------
# Zigzag layout
# ---------------------------------------------------------------------------
def test_zigzag_index_is_a_permutation():
    for S, cp in ((32, 2), (24, 3), (64, 8)):
        idx = CX.zigzag_index(S, cp)
        assert sorted(idx.tolist()) == list(range(S))
        # rank r's contiguous slice == zigzag_positions(r)
        c = S // (2 * cp)
        for r in range(cp):
            got = idx[r * 2 * c:(r + 1) * 2 * c]
            want = np.asarray(CX.zigzag_positions(r, cp, S))
            np.testing.assert_array_equal(got, want)


def test_zigzag_index_rejects_indivisible():
    with pytest.raises(ValueError, match="2\\*cp"):
        CX.zigzag_index(30, 4)


def test_zigzag_balances_causal_work():
    """Every rank's summed causal key-span (the attention work its queries
    own) is identical — the point of the zigzag interleave."""
    S, cp = 64, 4
    work = [int(sum(p + 1 for p in np.asarray(
        CX.zigzag_positions(r, cp, S)))) for r in range(cp)]
    assert len(set(work)) == 1, work


def test_zigzag_positions_mark_padding_on_remainders():
    # S=30, cp=4 -> chunks of 4, padded global length 32: the two pad
    # positions live in the LAST chunk, which the zigzag gives to rank 0
    pos0 = np.asarray(CX.zigzag_positions(0, 4, 30))
    assert pos0.shape == (8,)
    assert (pos0 >= 30).sum() == 2
    for r in range(1, 4):
        assert (np.asarray(CX.zigzag_positions(r, 4, 30)) < 30).all()


def test_zigzag_batch_roundtrip():
    dcfg = DistConfig(mesh_axes=("data", "ctx", "model"),
                      mesh_shape=(1, 2, 1), fsdp_axes=("data", "ctx"),
                      cp_axis="ctx")
    batch = {"tokens": np.arange(32).reshape(2, 16),
             "pos1d": np.arange(2)}
    out = CX.zigzag_batch(batch, dcfg)
    assert out["pos1d"] is batch["pos1d"]          # 1D untouched
    inv = np.argsort(CX.zigzag_index(16, 2))
    np.testing.assert_array_equal(out["tokens"][:, inv], batch["tokens"])


# ---------------------------------------------------------------------------
# Ring attention numerics: host emulators vs attention_ref
# ---------------------------------------------------------------------------
CASES = [
    # (cp, S, window, softcap)   -- incl. odd seq/cp remainders
    (1, 24, None, None),
    (2, 24, None, None),
    (3, 24, 5, None),
    (4, 24, None, 8.0),
    (2, 32, 8, 30.0),            # gemma2-shaped: window + softcap
    (4, 30, None, None),         # S % 2cp != 0 -> padded shards
    (3, 26, 7, 8.0),             # remainder x window x softcap
]


@pytest.mark.parametrize("cp,S,window,softcap", CASES)
def test_ring_forward_matches_attention_ref(cp, S, window, softcap):
    q, k, v = _qkv(jax.random.PRNGKey(0), S=S)
    ref = attention_ref(q, k, v, causal=True, window=window,
                        softcap=softcap)
    got = CX.ring_attention_host(q, k, v, cp, causal=True, window=window,
                                 softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("cp,S,window,softcap", CASES)
def test_reverse_ring_backward_matches_autodiff(cp, S, window, softcap):
    """The HAND-WRITTEN per-hop backward (the exact math the mesh VJP's
    travelling accumulators run) == jax.grad of the dense reference."""
    q, k, v = _qkv(jax.random.PRNGKey(1), S=S)
    do = jax.random.normal(jax.random.PRNGKey(2), q.shape) * 0.3

    def loss(q, k, v):
        out = attention_ref(q, k, v, causal=True, window=window,
                            softcap=softcap)
        return jnp.sum(out.astype(jnp.float32) * do)

    dq_r, dk_r, dv_r = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    dq, dk, dv = CX.ring_attention_host_grads(
        q, k, v, do, cp, causal=True, window=window, softcap=softcap)
    for name, a, b in (("dq", dq, dq_r), ("dk", dk, dk_r),
                       ("dv", dv, dv_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-5, atol=5e-6, err_msg=name)


def test_ring_host_autodiff_grads_match_reference():
    """The emulator is also plain-differentiable (autodiff through the
    online softmax) — a second, independent check of the forward graph."""
    q, k, v = _qkv(jax.random.PRNGKey(3), S=16)
    do = jax.random.normal(jax.random.PRNGKey(4), q.shape) * 0.3

    def loss(fn):
        def inner(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32) * do)
        return jax.grad(inner, argnums=(0, 1, 2))(q, k, v)

    ref = loss(lambda q, k, v: attention_ref(q, k, v, causal=True))
    got = loss(lambda q, k, v: CX.ring_attention_host(q, k, v, 2,
                                                      causal=True))
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-5, atol=5e-6)


def test_ring_respects_q_scale():
    q, k, v = _qkv(jax.random.PRNGKey(5), S=16)
    ref = attention_ref(q, k, v, causal=True, q_scale=0.25)
    got = CX.ring_attention_host(q, k, v, 2, causal=True, q_scale=0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# plan_parallel validation + batch specs
# ---------------------------------------------------------------------------
def _cp_cfg(**kw):
    base = dict(mesh_axes=("data", "ctx", "model"), mesh_shape=(2, 2, 1),
                fsdp_axes=("data", "ctx"), cp_axis="ctx",
                param_dtype=jnp.float32, storage_dtype=jnp.float32)
    base.update(kw)
    return DistConfig(**base)


def test_dist_config_cp_properties():
    d = _cp_cfg()
    assert d.cp_size == 2 and d.dp_total == 4 and d.batch_dp == 2
    assert DistConfig(mesh_shape=(1, 1)).cp_size == 1


def test_plan_parallel_cp_validation_errors():
    from repro.core.api import plan_parallel
    from repro.models.common import ShapeConfig
    from repro.models.registry import get_arch

    _, model = get_arch("qwen3_1_7b", smoke=True)
    shape = ShapeConfig("t", 32, 8, "train")
    # happy path resolves (and the plan mentions the ring)
    plan = plan_parallel(model, _cp_cfg(), shape)
    assert "cp=2(ring)" in plan.describe()
    # ctx must be in fsdp_axes (explicit-transpose rationale)
    with pytest.raises(ValueError, match="fsdp_axes"):
        plan_parallel(model, _cp_cfg(fsdp_axes=("data",)), shape)
    # zigzag divisibility
    with pytest.raises(ValueError, match="zigzag"):
        plan_parallel(model, _cp_cfg(), ShapeConfig("t", 30, 8, "train"))
    # unknown axis name
    with pytest.raises(ValueError, match="not a mesh axis"):
        plan_parallel(model, _cp_cfg(cp_axis="seq"), shape)
    # models without the cp contract are rejected pointedly
    _, xl = get_arch("xlstm_1_3b", smoke=True)
    with pytest.raises(ValueError, match="cp_supported"):
        plan_parallel(xl, _cp_cfg(), shape)
    # per-rank sequence must still split over TP (24/2 = 12, tp=8)
    with pytest.raises(ValueError, match="divisible by tp"):
        plan_parallel(model, _cp_cfg(mesh_shape=(1, 2, 8)),
                      ShapeConfig("t", 24, 8, "train"))


def test_batch_specs_shard_sequence_over_ctx():
    from jax.sharding import PartitionSpec as P
    from repro.models import runtime as RT
    from repro.models.common import ShapeConfig
    from repro.models.registry import get_arch

    _, model = get_arch("qwen3_1_7b", smoke=True)
    shape = ShapeConfig("t", 32, 8, "train")
    specs = RT.batch_specs(model, shape, _cp_cfg())
    assert specs["tokens"] == P(("data",), "ctx")
    assert RT.dp_axes(_cp_cfg()) == ("data",)
    # without a ctx axis nothing changes
    flat = DistConfig(mesh_axes=("data", "model"), mesh_shape=(2, 2))
    assert RT.batch_specs(model, shape, flat)["tokens"] == P(("data",),
                                                            None)


def test_vlm_opts_out_of_cp():
    from repro.models.registry import get_arch

    _, vlm = get_arch("internvl2_26b", smoke=True)
    assert not CX.supports_cp(vlm)
    _, dense = get_arch("qwen3_1_7b", smoke=True)
    _, moe = get_arch("qwen2_moe_a2_7b", smoke=True)
    assert CX.supports_cp(dense) and CX.supports_cp(moe)


# ---------------------------------------------------------------------------
# Memory simulator: activations / cp + the ring KV term
# ---------------------------------------------------------------------------
def test_simulator_ring_kv_term_and_act_scaling():
    from repro.core.memory import simulate_peak
    from repro.launch.mesh import production_dcfg
    from repro.models.registry import get_arch

    _, model = get_arch("llama3_8b")
    S = 32_768
    peaks = {}
    for cp in (1, 2, 4):
        dcfg = production_dcfg(context_degree=cp)
        bk = simulate_peak(model, dcfg, (1, S // cp))[0]
        peaks[cp] = bk
        if cp == 1:
            assert bk.parts.get("ring_kv", 0.0) == 0.0
        else:
            assert bk.parts["ring_kv"] > 0.0
    # activations (saved residuals) scale ~1/cp; ring KV buffers shrink too
    r1 = peaks[1].parts["saved_residuals"]
    r2 = peaks[2].parts["saved_residuals"]
    r4 = peaks[4].parts["saved_residuals"]
    assert r1 > r2 > r4
    np.testing.assert_allclose(r2 / r1, 0.5, rtol=0.05)
    assert peaks[2].parts["ring_kv"] > peaks[4].parts["ring_kv"]
    # total modeled peak strictly decreases (params constant: fsdp spans
    # data x ctx, so the shard domain never changes)
    assert peaks[1].peak_bytes > peaks[2].peak_bytes \
        > peaks[4].peak_bytes


def test_ring_cost_model():
    from repro.launch.mesh import production_dcfg
    from repro.models.registry import get_arch

    cfg, _ = get_arch("gemma2_27b")
    dcfg = production_dcfg(context_degree=8)
    full = CX.ring_cost(cfg, dcfg, (1, 4096), window=None)
    win = CX.ring_cost(cfg, dcfg, (1, 4096), window=cfg.sliding_window)
    assert full["live_hops"] == 8
    assert win["live_hops"] < 8                 # window skips far hops
    assert full["hop_bytes"] > 0 and full["hop_comm_s"] > 0
    assert win["total_comm_s"] == full["total_comm_s"]  # ring always moves
    assert CX.ring_live_hops(1, 4096, 128) == 1


# ---------------------------------------------------------------------------
# auto_microbatches: the simulator's stage peaks pick the split
# ---------------------------------------------------------------------------
def test_auto_microbatches_fits_budget_and_monotone():
    from repro.core.memory import auto_microbatches, simulate_peak
    from repro.launch.mesh import production_dcfg, production_dcfg_for
    from repro.models.common import get_shape
    from repro.models.registry import get_arch

    shape = get_shape("train_4k")
    cfg, model = get_arch("gemma2_27b")
    dcfg = production_dcfg()
    mb = auto_microbatches(model, dcfg, shape)
    assert mb >= 1
    # the pick actually fits: modeled peak at mb within budget
    b = max(1, shape.global_batch // dcfg.batch_dp // mb)
    from repro.core import hw
    pk = simulate_peak(model, dcfg.with_(microbatches=mb),
                       (b, shape.seq_len), act_scale=4.0)
    assert max(x.peak_bytes for x in pk) <= hw.HBM_BYTES \
        or mb >= shape.global_batch // dcfg.batch_dp
    # a tighter budget can only deepen the split
    tighter = auto_microbatches(model, dcfg, shape,
                                budget=hw.HBM_BYTES / 4)
    assert tighter >= mb
    # models without a cost contract run unsplit
    class NoStats:
        pass
    assert auto_microbatches(NoStats(), dcfg, shape) == 1
    # production_dcfg_for wires the pick through (auto-accumulation)
    d2 = production_dcfg_for(cfg, shape=shape, model=model)
    assert d2.microbatches >= 1


def test_dryrun_pick_microbatches_replaces_table():
    """The dryrun module no longer carries the hand-kept MICROBATCH table;
    picks come from the simulator."""
    from repro.launch import dryrun

    assert not hasattr(dryrun, "MICROBATCH")
    from repro.models.common import get_shape
    from repro.models.registry import get_arch
    from repro.launch.mesh import production_dcfg

    _, model = get_arch("qwen3_1_7b")
    assert dryrun.pick_microbatches(model, production_dcfg(),
                                    get_shape("train_4k")) >= 1
    # serving cells never split
    assert dryrun.pick_microbatches(model, production_dcfg(),
                                    get_shape("prefill_32k")) == 1


# ---------------------------------------------------------------------------
# BENCH_context.json (tier-1 schema smoke + the checked-in artifact)
# ---------------------------------------------------------------------------
def _check_context_doc(doc):
    assert doc["schema"] == "bench_context_v1"
    assert len(doc["archs"]) >= 2
    for arch, rec in doc["archs"].items():
        degrees = [int(c) for c in rec["modes"]]
        assert 1 in degrees and max(degrees) >= 4
        acts, peaks = [], []
        for c in sorted(degrees):
            row = rec["modes"][str(c)]
            assert row["seq_local"] * c == doc["seq_len"]
            assert row["peak_bytes"] > 0
            assert 1 <= row["live_hops"] <= c
            if c == 1:
                assert row["ring_exposed_s"] == 0.0
            acts.append(row["act_bytes"])
            peaks.append(row["peak_bytes"])
        # the acceptance invariant: modeled peak activation memory
        # strictly decreases with the cp degree
        assert all(a > b for a, b in zip(acts, acts[1:])), (arch, acts)
        assert all(a > b for a, b in zip(peaks, peaks[1:])), (arch, peaks)


def test_bench_context_json_schema(tmp_path):
    sys.path.insert(0, ROOT)
    try:
        from benchmarks import paper_tables as T
    finally:
        sys.path.pop(0)
    path = str(tmp_path / "BENCH_context.json")
    doc = T.context_table(json_path=path)
    assert json.load(open(path)) == doc
    _check_context_doc(doc)


def test_bench_context_artifact_checked_in():
    path = os.path.join(ROOT, "benchmarks", "results",
                        "BENCH_context.json")
    assert os.path.exists(path), \
        "benchmarks/results/BENCH_context.json missing — run " \
        "`python -m benchmarks.run ctx --json`"
    _check_context_doc(json.load(open(path)))
