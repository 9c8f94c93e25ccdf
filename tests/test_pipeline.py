"""Single-device pipeline schedule tests (unit tier).

The multi-device pp x dp x tp parity lives in tests/dist_harness.py case
`pipeline`; here the pipe axis is a size-1 mesh axis, so the schedule
algebra (slot tables, occupancy, the 1F1B ring-buffer bound) is validated
analytically and gpipe/1F1B collapse to plain microbatched training whose
losses and gradients must match `jax.grad` exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map

from repro.core import DistConfig, make_mesh
from repro.core.dist import single_device_config
from repro.core.pipeline import (gpipe, gpipe_grads, gpipe_schedule,
                                 one_f_one_b, one_f_one_b_schedule,
                                 pipeline_grads, schedule_slots)
from jax.sharding import PartitionSpec as P


def _pipe1_cfg() -> DistConfig:
    return DistConfig(mesh_axes=("pipe",), mesh_shape=(1,), fsdp_axes=(),
                      tp_axis=None, pp_axis="pipe")


def _run_on_pipe1(fn, *args, out_specs):
    cfg = _pipe1_cfg()
    mesh = make_mesh(cfg)
    wrapped = shard_map(fn, mesh=mesh,
                        in_specs=tuple(P() for _ in args),
                        out_specs=out_specs, check_vma=False)
    return jax.jit(wrapped)(*args)


# ---------------------------------------------------------------------------
# GPipe schedule algebra
# ---------------------------------------------------------------------------
def test_gpipe_identity_single_stage():
    """Identity stage_fn with S=1: the output equals the input microbatch
    stack — the schedule is a pure pass-through."""
    xs = jax.random.normal(jax.random.PRNGKey(0), (5, 3, 4))
    outs = _run_on_pipe1(lambda xs: gpipe(lambda x: x, xs, 1, "pipe"),
                         xs, out_specs=P())
    np.testing.assert_array_equal(np.asarray(outs), np.asarray(xs))


@pytest.mark.parametrize("M,S", [(1, 1), (4, 1), (1, 4), (4, 4), (6, 3),
                                 (3, 6)])
def test_gpipe_slot_occupancy_analytic(M, S):
    """The (M, S) slot table spans exactly M + S - 1 slots; stage s is busy
    precisely on slots [s, s + M) working on microbatch t - s."""
    sched = gpipe_schedule(M, S)
    assert sched.shape == (M + S - 1, S)
    assert sched.shape[0] == schedule_slots(M, S, "gpipe")
    for s in range(S):
        col = sched[:, s]
        active = np.nonzero(col >= 0)[0]
        assert len(active) == M                       # every mb exactly once
        np.testing.assert_array_equal(active, np.arange(s, s + M))
        np.testing.assert_array_equal(col[active], active - s)


# ---------------------------------------------------------------------------
# 1F1B schedule algebra: occupancy + the S-bounded memory model
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("M,S", [(1, 1), (4, 1), (4, 4), (8, 4), (6, 3)])
def test_1f1b_schedule_occupancy_and_memory_bound(M, S):
    fwd, bwd = one_f_one_b_schedule(M, S)
    T = schedule_slots(M, S, "1f1b")
    assert fwd.shape == bwd.shape == (T, S)
    for s in range(S):
        # each microbatch's forward and backward run exactly once per stage,
        # never in the same slot (opposite parities)
        assert sorted(fwd[fwd[:, s] >= 0, s]) == list(range(M))
        assert sorted(bwd[bwd[:, s] >= 0, s]) == list(range(M))
        assert not np.any((fwd[:, s] >= 0) & (bwd[:, s] >= 0))
        # in-flight microbatches (forward done, backward pending) stay
        # bounded by min(M, S - s) <= S — the 1F1B memory model, vs
        # GPipe's M live activations
        in_flight = 0
        peak = 0
        for t in range(T):
            if fwd[t, s] >= 0:
                in_flight += 1
            peak = max(peak, in_flight)
            if bwd[t, s] >= 0:
                in_flight -= 1
        assert in_flight == 0
        assert peak <= min(M, S - s)
        # causality: backward of m strictly after its forward
        f_slot = {int(m): t for t in range(T) if (m := fwd[t, s]) >= 0}
        b_slot = {int(m): t for t in range(T) if (m := bwd[t, s]) >= 0}
        assert all(b_slot[m] > f_slot[m] for m in range(M))


# ---------------------------------------------------------------------------
# Differentiability: S=1 pipelines == plain microbatched jax.grad
# ---------------------------------------------------------------------------
def _dense_ref(w, xs):
    ys = jnp.tanh(xs @ w)
    return jnp.mean(ys ** 2)


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_single_stage_grads_match_dense(schedule):
    M, B, D = 3, 2, 4
    w = jax.random.normal(jax.random.PRNGKey(1), (D, D)) * 0.5
    xs = jax.random.normal(jax.random.PRNGKey(2), (M, B, D))
    ref_loss = _dense_ref(w, xs)
    ref_dw, ref_dxs = jax.grad(_dense_ref, argnums=(0, 1))(w, xs)

    def stage_fn(p, x):
        return jnp.tanh(x @ p)

    def loss_fn(y):
        return jnp.mean(y ** 2) / M

    fn = gpipe_grads if schedule == "gpipe" else one_f_one_b
    loss, dw, dxs = _run_on_pipe1(
        lambda w, xs: fn(stage_fn, w, xs, loss_fn, 1, "pipe"),
        w, xs, out_specs=(P(), P(), P()))
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(ref_dw),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(dxs), np.asarray(ref_dxs),
                               rtol=1e-5, atol=1e-7)


def test_pipeline_grads_dispatch_validates():
    cfg = single_device_config()          # no pp_axis configured
    with pytest.raises(ValueError):
        pipeline_grads(lambda p, x: x, {}, jnp.zeros((2, 2)),
                       lambda y: 0.0, cfg)
    with pytest.raises(ValueError):
        schedule_slots(4, 2, "wavefront")       # not a known schedule
    # a declared microbatch count must match the xs stack
    cfg_m = DistConfig(mesh_axes=("pipe",), mesh_shape=(1,), fsdp_axes=(),
                       tp_axis=None, pp_axis="pipe", pp_microbatches=8)
    with pytest.raises(ValueError, match="microbatches"):
        pipeline_grads(lambda p, x: x, {}, jnp.zeros((2, 2)),
                       lambda y: 0.0, cfg_m)


# ---------------------------------------------------------------------------
# PR-6 table schedules: interleaved 1F1B (virtual stages) + zero-bubble
# W-split.  Multi-device parity lives in dist_harness case `pipeline_v2`;
# here the tables themselves are validated analytically.
# ---------------------------------------------------------------------------
from repro.core.pipeline import (bubble_fraction, build_pipe_schedule,
                                 schedule_peak_state, zb_queue_depth,
                                 zero_bubble)


@pytest.mark.parametrize("M,S,V", [(2, 2, 2), (4, 2, 2), (8, 2, 4),
                                   (4, 4, 2), (8, 4, 2)])
def test_interleaved_table_validity(M, S, V):
    """Every (virtual chunk, microbatch) forward and backward appears
    exactly once, on its owning rank j % S, at most one work unit per rank
    per slot, and the ring-buffer registers stay within the declared
    depths."""
    sched = build_pipe_schedule(M, S, "interleaved", V)
    VS = V * S
    seen_f, seen_b = set(), set()
    for t in range(sched.slots):
        for s in range(S):
            assert not (sched.f_mb[t, s] >= 0 and sched.b_mb[t, s] >= 0)
            if sched.f_mb[t, s] >= 0:
                seen_f.add((int(sched.f_chunk[t, s]) * S + s,
                            int(sched.f_mb[t, s])))
            if sched.b_mb[t, s] >= 0:
                seen_b.add((int(sched.b_chunk[t, s]) * S + s,
                            int(sched.b_mb[t, s])))
    want = {(j, m) for j in range(VS) for m in range(M)}
    assert seen_f == want and seen_b == want
    assert sched.f_in.max() < sched.depth_in
    assert sched.b_ct.max() < sched.depth_ct
    # the table's own utilization accounting is consistent
    assert sched.slots == schedule_slots(M, S, "interleaved", V)
    assert sched.work_units == 2 * V * M


@pytest.mark.parametrize("M,S", [(2, 2), (4, 2), (8, 2), (4, 4), (8, 4)])
def test_new_schedules_shrink_the_bubble(M, S):
    """The PR-6 claim, analytically: at every benched (M, S) the modeled
    idle fraction of interleaved (V=2) and zb is STRICTLY below 1F1B's
    (S-1)/(M+S-1), and zb fills the cooldown best."""
    base = bubble_fraction(M, S, "1f1b")
    assert base == pytest.approx((S - 1) / (M + S - 1))
    assert bubble_fraction(M, S, "gpipe") == pytest.approx(base)
    bi = bubble_fraction(M, S, "interleaved", 2)
    bz = bubble_fraction(M, S, "zb")
    assert bi < base and bz < base
    assert bz < bi                        # W-fill beats chunking at V=2
    # more virtual chunks shrink the ramps further (where the greedy
    # builder lands on the ideal Megatron pattern; deep V x deep S tables
    # can fall short of it, but never below 1F1B)
    if S == 2 and M % S == 0:
        assert bubble_fraction(M, S, "interleaved", 4) < bi
    assert bubble_fraction(M, S, "interleaved", 4) < base


@pytest.mark.parametrize("M,S", [(2, 2), (4, 2), (8, 4), (4, 4)])
def test_zb_wqueue_fifo_drain(M, S):
    """The weight-grad halves drain from the W queue in microbatch (FIFO)
    order, each strictly after its Bx, never sharing a slot with F or Bx,
    and the declared queue depth bounds the register indices."""
    sched = build_pipe_schedule(M, S, "zb")
    assert zb_queue_depth(M, S) == sched.depth_w
    for s in range(S):
        b_slot = {int(m): t for t in range(sched.slots)
                  if (m := sched.b_mb[t, s]) >= 0}
        w_slots = [t for t in range(sched.slots)
                   if sched.w_idx[t, s] >= 0]
        assert len(w_slots) == M
        for t in w_slots:                 # one work unit per slot
            assert sched.f_mb[t, s] < 0 and sched.b_mb[t, s] < 0
        drains = []
        for m in range(M):                # match push register to drain
            reg = int(sched.b_push[b_slot[m], s])
            assert 0 <= reg < sched.depth_w
            t = next(t for t in w_slots
                     if t > b_slot[m] and int(sched.w_idx[t, s]) == reg
                     and t not in drains)
            drains.append(t)
        assert drains == sorted(drains)   # FIFO in microbatch order


def test_schedule_peak_state_models():
    """The in-flight memory model the simulator consumes: gpipe holds all
    M, 1f1b/zb are bounded by min(M, S-s), interleaved's V chunk slices
    hold MORE chunk-granular state than plain 1F1B on the interior ranks
    (its known memory cost)."""
    assert schedule_peak_state(8, 4, "gpipe") == [8] * 4
    assert schedule_peak_state(8, 4, "1f1b") == [4, 3, 2, 1]
    assert schedule_peak_state(8, 4, "zb") == [4, 3, 2, 1]
    inter = schedule_peak_state(8, 4, "interleaved", 2)
    assert len(inter) == 4 and all(p >= 1 for p in inter)
    # interior ranks: more resident chunk states than 1F1B's stage bound
    assert inter[1] > 3 and inter[2] > 2
    with pytest.raises(ValueError):
        schedule_peak_state(8, 4, "wavefront")


def test_single_stage_zb_grads_match_dense():
    """S=1 zero-bubble == plain microbatched jax.grad: the W-split and
    queue drain must be a pure reordering of the same accumulation."""
    M, B, D = 3, 2, 4
    w = jax.random.normal(jax.random.PRNGKey(1), (D, D)) * 0.5
    xs = jax.random.normal(jax.random.PRNGKey(2), (M, B, D))
    ref_loss = _dense_ref(w, xs)
    ref_dw, ref_dxs = jax.grad(_dense_ref, argnums=(0, 1))(w, xs)

    loss, dw, dxs = _run_on_pipe1(
        lambda w, xs: zero_bubble(lambda p, x: jnp.tanh(x @ p), w, xs,
                                  lambda y: jnp.mean(y ** 2) / M, 1,
                                  "pipe"),
        w, xs, out_specs=(P(), P(), P()))
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(ref_dw),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(dxs), np.asarray(ref_dxs),
                               rtol=1e-5, atol=1e-7)


def test_production_dcfg_honours_arch_pp_stages():
    """The per-arch recommended pipeline degree (configs satellite) flows
    into the production mesh with its validity checks."""
    from repro.launch.mesh import production_dcfg_for
    from repro.models.registry import get_arch

    for arch, stages in [("llama3_8b", 4), ("deepseek_coder_33b", 2)]:
        cfg, _ = get_arch(arch)
        assert cfg.pp_stages == stages
        assert cfg.n_layers % stages == 0
        d = production_dcfg_for(cfg)
        assert d.pp_axis == "pipe" and d.pp_size == stages
        assert d.mesh_axes[0] == "pipe"              # pipe outermost
        assert d.mesh_shape == (stages, 16 // stages, 16)
    # a degree that doesn't split the layer stack is rejected
    import dataclasses
    bad = dataclasses.replace(get_arch("llama3_8b")[0], pp_stages=5)
    with pytest.raises(ValueError, match="pp_stages"):
        production_dcfg_for(bad)