"""Quantized collectives: the fp8/int8 wire codec (kernels/quant), the
error-feedback hop in the optimizer, and the precision-aware planner.

Round-trip property tests run the pure-jnp reference AND the Pallas kernel
in interpret mode (bit-identical by construction — both share ref.py's
chunk/scale/SR helpers and the multiply-by-reciprocal scale).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dist import (AUTO_PRECISIONS, COMM_PRECISIONS, DistConfig,
                             precision_codecs)
from repro.kernels.quant import ops as quant_ops
from repro.kernels.quant import ref as quant_ref

pytestmark = pytest.mark.quant

CODECS = ("fp8", "int8")
# odd chunk remainders (n % QCHUNK != 0), LANE-aligned buffers, and
# TP-squeezed storage shapes (leading (1, chunk) shard dim)
SHAPES = ((7,), (127,), (129,), (1024,), (1, 384), (3, 5, 7))


def _x(shape, seed=0, scale=3.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape,
                                     jnp.float32)


# ---------------------------------------------------------------------------
# codec round-trip properties (reference implementation)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("codec", CODECS)
@pytest.mark.parametrize("shape", SHAPES)
def test_roundtrip_error_bound(codec, shape):
    """Deterministic RTN error is bounded per chunk: int8 by half a step
    (absmax/254 plus scale-rounding slack), fp8(e4m3) by half the step at
    the top binade (16/448 = absmax/28, again plus slack for the fp32
    reciprocal scale)."""
    x = _x(shape, seed=hash((codec, shape)) % 1000)
    rt = quant_ref.roundtrip(x, codec, stochastic=False)
    assert rt.shape == x.shape and rt.dtype == x.dtype
    x2, n = quant_ref.chunk(x)
    r2, _ = quant_ref.chunk(rt)
    absmax = jnp.max(jnp.abs(x2), axis=1)
    err = jnp.max(jnp.abs(x2 - r2), axis=1)
    bound = absmax * ((1.02 / 254.0) if codec == "int8" else (1.1 / 28.0)) + 1e-7
    assert bool(jnp.all(err <= bound)), (codec, shape, err, bound)


@pytest.mark.parametrize("codec", CODECS)
def test_quantize_value_bounds(codec):
    """Wire values stay inside the codec's representable range and zero
    chunks survive exactly (the all-zero scale guard)."""
    x = _x((1024,), seed=5)
    q, scales = quant_ref.quantize(x, codec, stochastic=False)
    assert q.dtype == quant_ref.WIRE_DTYPE[codec]
    qf = jnp.abs(q.astype(jnp.float32))
    assert float(jnp.max(qf)) <= quant_ref.QMAX[codec]
    assert scales.dtype == jnp.float32 and bool(jnp.all(scales > 0))

    z = jnp.zeros((256,), jnp.float32)
    assert bool(jnp.all(quant_ref.roundtrip(z, codec, True) == 0))
    assert bool(jnp.all(quant_ref.roundtrip(z, codec, False) == 0))


@pytest.mark.parametrize("codec", CODECS)
def test_stochastic_rounding_unbiased(codec):
    """The SR encode's signed error is tiny relative to the signal (the
    hash dither centers it); per-element error still respects one step."""
    x = _x((1 << 14,), seed=9)
    rt = quant_ref.roundtrip(x, codec, stochastic=True)
    err = np.asarray(rt - x, np.float64)
    assert abs(err.mean()) <= 0.01 * float(jnp.mean(jnp.abs(x)))
    x2, _ = quant_ref.chunk(x)
    absmax = jnp.max(jnp.abs(x2), axis=1, keepdims=True)
    step = absmax / (127.0 if codec == "int8" else 14.0)
    r2, _ = quant_ref.chunk(rt)
    assert bool(jnp.all(jnp.abs(x2 - r2) <= step + 1e-7))


def test_roundtrip_preserves_dtype():
    for dt in (jnp.float32, jnp.bfloat16):
        x = _x((640,), seed=2).astype(dt)
        rt = quant_ref.roundtrip(x, "fp8", stochastic=False)
        assert rt.dtype == dt
    # codec None is the identity (bf16 wire)
    x = _x((64,))
    assert bool(jnp.all(quant_ref.roundtrip(x, None, False) == x))


# ---------------------------------------------------------------------------
# Pallas kernel parity (interpret mode on CPU)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("codec", CODECS)
@pytest.mark.parametrize("stochastic", (False, True))
@pytest.mark.parametrize("shape", ((129,), (1024,), (1, 384)))
def test_pallas_matches_ref(codec, stochastic, shape):
    x = _x(shape, seed=hash((codec, stochastic)) % 1000)
    want = quant_ref.roundtrip(x, codec, stochastic=stochastic)
    got = quant_ops.roundtrip_pallas(x, codec, stochastic=stochastic,
                                     interpret=True)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# error feedback: the compensated quantizer recovers the true gradient
# ---------------------------------------------------------------------------
def test_error_feedback_converges():
    """With a constant gradient, the EF-compensated quantized stream's
    running mean converges to the true gradient (residual stays bounded by
    one quantization step, so the average error decays as 1/T)."""
    from repro.optim.adamw import _error_feedback

    g = {"w": _x((512,), seed=11)}
    ef = {"w": jnp.zeros((512,), jnp.float32)}
    total = jnp.zeros((512,), jnp.float32)
    T = 50
    for _ in range(T):
        gq, ef = _error_feedback(g, ef)
        total = total + gq["w"]
    avg = total / T
    x2, _ = quant_ref.chunk(g["w"])
    step = float(jnp.max(jnp.abs(x2))) / 14.0
    assert float(jnp.max(jnp.abs(avg - g["w"]))) <= 2.0 * step / T + 1e-6
    # the residual itself never exceeds one step
    assert float(jnp.max(jnp.abs(ef["w"]))) <= step + 1e-6


def test_quantized_adamw_tracks_bf16():
    """~50 toy AdamW steps on a least-squares problem: the fp8_ef run's
    loss trajectory tracks the unquantized run within a loose tolerance
    (EF-theory: compensated quantization preserves convergence)."""
    from jax import shard_map
    from repro.core.dist import make_mesh
    from repro.core.meta import ParamMeta, from_storage, to_storage
    from repro.optim.adamw import AdamWConfig, apply_adamw, init_opt_state

    D = 64
    w_true = _x((D,), seed=3, scale=1.0)
    X = _x((256, D), seed=4, scale=1.0)
    y = X @ w_true

    def run(comm_precision):
        cfg = DistConfig(
            mesh_axes=("data", "model"), mesh_shape=(1, 1),
            fsdp_axes=("data",), param_dtype=jnp.float32,
            reduce_dtype=jnp.float32, storage_dtype=jnp.float32,
            comm_precision=comm_precision)
        mesh = make_mesh(cfg)
        metas = {"w": ParamMeta("w", (D,), tp_dim=None)}
        st = {"w": to_storage(jnp.zeros((D,), jnp.float32),
                              metas["w"], cfg)}
        opt = init_opt_state(st, cfg)
        ocfg = AdamWConfig(lr=3e-2, weight_decay=0.0)

        def step(st, opt):
            def loss_of(s):
                w = from_storage(s["w"], metas["w"], cfg)
                return jnp.mean((X @ w - y) ** 2)

            loss, grads = jax.value_and_grad(loss_of)(st)
            new_p, new_opt, _ = apply_adamw(st, grads, opt, metas, cfg,
                                            ocfg, ocfg.lr)
            return new_p, new_opt, loss

        P = jax.sharding.PartitionSpec
        fn = jax.jit(shard_map(step, mesh=mesh, in_specs=P(),
                               out_specs=P(), check_vma=False))
        losses = []
        for _ in range(50):
            st, opt, l = fn(st, opt)
            losses.append(float(l))
        return losses, opt

    base, opt_b = run("bf16")
    quant, opt_q = run("fp8_ef")
    assert "ef" not in opt_b and "ef" in opt_q
    assert base[-1] < 0.1 * base[0]          # the problem actually trains
    assert quant[-1] < 0.1 * quant[0]
    # trajectory tracks within loose EF tolerance
    for b, q in zip(base, quant):
        assert abs(q - b) <= 0.2 * abs(b) + 1e-3, (b, q)


# ---------------------------------------------------------------------------
# wire pricing + precision plumbing
# ---------------------------------------------------------------------------
def test_wire_bytes_ratio():
    from repro.core.irgraph import wire_bytes

    n = 1 << 20
    bf16 = wire_bytes(n, 2)
    fp8 = wire_bytes(n, 2, "fp8")
    assert bf16 == 2 * n
    assert fp8 == n + 4 * (n // 128)
    assert fp8 / bf16 == pytest.approx(0.515625)
    # remainder chunks still pay a full scale
    assert wire_bytes(129, 2, "fp8") == 129 + 8


def test_precision_vocabulary():
    assert set(AUTO_PRECISIONS) <= set(COMM_PRECISIONS)
    assert precision_codecs("bf16") == (None, None)
    assert precision_codecs("fp8_ag") == ("fp8", None)
    assert precision_codecs("fp8") == ("fp8", "fp8")
    assert precision_codecs("fp8_ef") == ("fp8", "fp8")
    with pytest.raises(KeyError):
        precision_codecs("auto")      # must be resolved by the planner
    with pytest.raises(ValueError):
        DistConfig(mesh_axes=("data", "model"), mesh_shape=(1, 1),
                   fsdp_axes=("data",), comm_precision="int4")
    cfg = DistConfig(mesh_axes=("data", "model"), mesh_shape=(1, 1),
                     fsdp_axes=("data",), comm_precision="auto")
    assert cfg.needs_ef
    assert not cfg.with_(comm_precision="fp8").needs_ef


def test_auto_planner_never_worse_than_bf16():
    """The joint partition x precision DP's objective is <= the all-bf16
    DP's on the same workload, and every chosen precision is in the auto
    lattice."""
    from repro.core.autowrap import auto_dp_plan, exposed_comm_time
    from repro.core.meta import ParamMeta

    metas = {f"w{i}": ParamMeta(f"w{i}", (256, 256), tp_dim=None)
             for i in range(6)}
    base = DistConfig(mesh_axes=("data", "model"), mesh_shape=(64, 1),
                      fsdp_axes=("data",), bucket_mode="auto_dp")
    r_bf = exposed_comm_time(auto_dp_plan(metas, base), metas, base)
    auto = base.with_(comm_precision="auto")
    plan = auto_dp_plan(metas, auto)
    r_auto = exposed_comm_time(plan, metas, auto)
    assert r_auto["exposed_s"] <= r_bf["exposed_s"] + 1e-12
    assert plan.precisions is not None
    assert set(plan.precisions) <= set(AUTO_PRECISIONS)
    # per-group resolution survives the runtime lookup path
    precs = plan.group_precisions(metas, auto)
    assert precs == list(plan.precisions)


def test_bucket_plan_precisions_split_at_segments():
    from repro.core.bucketing import BucketPlan, split_plan_at_segments
    from repro.core.meta import ParamMeta
    from repro.models.common import BlockSegments

    metas = {"a": ParamMeta("a", (128,), tp_dim=None),
             "b": ParamMeta("b", (128,), tp_dim=None)}
    plan = BucketPlan((("a", "b"),), precisions=("fp8_ef",))
    segs = BlockSegments(names=("s0", "s1"),
                         fns=(lambda *a: None, lambda *a: None),
                         param_globs=(("a",), ("b",)))
    out = split_plan_at_segments(plan, metas, segs)
    assert out.groups == (("a",), ("b",))
    assert out.precisions == ("fp8_ef", "fp8_ef")
