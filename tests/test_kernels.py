"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

TOL = dict(rtol=2e-2, atol=2e-2)      # bf16 sweeps
TOL32 = dict(rtol=2e-4, atol=2e-5)    # fp32 sweeps


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rows,d", [(8, 128), (16, 256), (9, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("unit_offset", [False, True])
def test_rmsnorm_sweep(rows, d, dtype, unit_offset):
    from repro.kernels.rmsnorm import ops, ref
    x = (jax.random.normal(jax.random.PRNGKey(0), (rows, d)) * 2).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (d,))
    got = ops.rmsnorm_pallas(x, w, 1e-5, unit_offset, True)
    want = ref.rmsnorm(x, w, 1e-5, unit_offset)
    tol = TOL32 if dtype == jnp.float32 else TOL
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def test_rmsnorm_grad_matches_ref():
    from repro.kernels.rmsnorm import ops, ref
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 128))
    w = jax.random.normal(jax.random.PRNGKey(1), (128,))
    g1 = jax.grad(lambda x, w: ops.rmsnorm_pallas(
        x, w, 1e-5, False, True).sum(), argnums=(0, 1))(x, w)
    g2 = jax.grad(lambda x, w: ref.rmsnorm(x, w).sum(), argnums=(0, 1))(x, w)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL32)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("S,hd", [(256, 64), (384, 128)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_sweep(S, hd, causal, dtype):
    from repro.kernels.flash_attention import kernel as K, ref
    q = jax.random.normal(jax.random.PRNGKey(0), (2, S, hd)).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (2, S, hd)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (2, S, hd)).astype(dtype)
    got = K.flash_fwd(q, k, v, causal=causal, interpret=True)
    want = ref.attention(q, k, v, causal=causal)
    tol = TOL32 if dtype == jnp.float32 else TOL
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("window,softcap", [(64, None), (None, 30.0),
                                            (128, 50.0)])
def test_flash_variants(window, softcap):
    from repro.kernels.flash_attention import kernel as K, ref
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 256, 64))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 64))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 256, 64))
    got = K.flash_fwd(q, k, v, causal=True, window=window, softcap=softcap,
                      interpret=True)
    want = ref.attention(q, k, v, causal=True, window=window,
                         softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL32)


def test_flash_gqa_wrapper():
    from repro.kernels.flash_attention import ops
    from repro.models.layers import attention_ref
    B, S, H, Kh, hd = 2, 256, 8, 2, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Kh, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Kh, hd))
    got = ops.flash_attention(q, k, v, True, None, None, None, True)
    want = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL32)


# ---------------------------------------------------------------------------
# Cross entropy
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("R,V", [(16, 1000), (24, 5003), (8, 2048)])
def test_xent_sweep(R, V):
    from repro.kernels.cross_entropy import ops, ref
    logits = jax.random.normal(jax.random.PRNGKey(0), (R, V)) * 2
    targets = jax.random.randint(jax.random.PRNGKey(1), (R,), 0, V)
    got = ops.fused_xent(logits, targets, True)
    want, _ = ref.xent(logits, targets)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_xent_grad():
    from repro.kernels.cross_entropy import ops, ref
    R, V = 16, 3000
    logits = jax.random.normal(jax.random.PRNGKey(0), (R, V)) * 2
    targets = jax.random.randint(jax.random.PRNGKey(1), (R,), 0, V)
    g = jax.grad(lambda l: ops.fused_xent(l, targets, True).sum())(logits)
    gw = jax.grad(lambda l: ref.xent(l, targets)[0].sum())(logits)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gw),
                               rtol=2e-3, atol=2e-4)


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------
ADAMW_KW = dict(lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, wd=0.1)
# storage leaves are (chunk,), (tp, chunk), (L, chunk) or (L, tp, chunk)
# with chunk a multiple of 128 (core/meta.py); the rest are any other leaf
ADAMW_LEAVES = [
    pytest.param(1024, 1.0, id="1024"),
    pytest.param(5000, 1.0, id="5000"),
    pytest.param((1, 2048), 0.37, id="tp-chunk"),
    pytest.param((2, 1024), 0.37, id="tp2-chunk"),
    pytest.param((3, 1280), 0.37, id="L-chunk"),
    pytest.param((3, 1, 768), 0.37, id="L-tp-chunk"),
    pytest.param((2, 3, 256), 0.37, id="L-tp3-chunk"),
    pytest.param((3,), 0.37, id="under-one-vreg"),
    pytest.param((3, 100), 0.37, id="not-lane-dense"),
    # more than one block, the last one partial: a linear leaf splits its
    # rows, a stacked one its columns
    pytest.param((1, 600_064), 0.37, id="tp-chunk-blocks"),
    pytest.param((7, 100_352), 0.37, id="L-chunk-blocks"),
]


def _adamw_state(shape, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    p = jax.random.normal(ks[0], shape).astype(dtype)
    g = jax.random.normal(ks[1], shape).astype(dtype)
    m = (jax.random.normal(ks[2], shape) * 0.1).astype(dtype)
    v = (jnp.abs(jax.random.normal(ks[3], shape)) * 0.01).astype(dtype)
    return p, g, m, v


def _close(got, want, dtype):
    tol = dict(rtol=1e-6, atol=1e-7) if dtype == jnp.float32 else TOL
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)


@pytest.mark.parametrize("shape,scale", ADAMW_LEAVES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_adamw_sweep(shape, scale, dtype):
    """The kernel against the oracle given the clip-scaled gradient, over
    every storage rank, leaves under one block, past one block and not
    lane-dense, f32 and bf16 params."""
    from repro.kernels.adamw import ops, ref
    p, g, m, v = _adamw_state(shape, dtype)
    kw = dict(ADAMW_KW, t=jnp.asarray(3))
    got = ops.adamw_update_pallas(p, g, m, v, scale=scale, interpret=True,
                                  **kw)
    want = ref.adamw_update(p, g.astype(jnp.float32) * scale, m, v, **kw)
    _close(got, want, dtype)



@pytest.mark.parametrize("t", [1, 2, 10, 1000])
def test_adamw_bias_correction_traced_step(t):
    """The bias corrections 1 - b**t reach the kernel as operands computed
    from a traced step count (the optimizer state's), not as a power inside
    the kernel."""
    from repro.kernels.adamw import ops, ref
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    n = 3000
    p, g, m = (jax.random.normal(k, (n,)) for k in ks[:3])
    v = jnp.abs(jax.random.normal(ks[3], (n,))) * 0.01
    kw = dict(lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, wd=0.1)
    got = jax.jit(lambda t: ops.adamw_update_pallas(
        p, g, m, v, t=t, interpret=True, **kw))(jnp.asarray(t, jnp.int32))
    want = ref.adamw_update(p, g, m, v, t=jnp.asarray(t), **kw)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL32)


def test_adamw_block_plan():
    """The plan views each storage leaf as its own bytes (no padding), moves
    at most BLOCK_BYTES of f32 per operand a grid step in blocks the TPU
    can tile, covers the view exactly, and fits the VMEM limit it sets.
    The view keeps the last two dims of a leaf the TPU tiles there."""
    from repro.kernels.adamw import kernel as K

    views = {
        (311164928,): (1, 2430976, 128),       # the tied embedding's chunk
        (1, 311164928): (1, 2430976, 128),     # ... behind a tp axis of 1
        (2, 155582464): (1, 2, 155582464),     # ... behind a tp axis of 2
        (7, 1, 12582912): (1, 688128, 128),    # a stacked TP matrix
        (7, 2, 6291456): (7, 2, 6291456),      # ... with tp 2
        (7, 2097152): (1, 7, 2097152),         # a stacked matrix
        (4, 2, 1024): (4, 2, 1024),            # stacked, tp 2
        (7, 128): (1, 7, 128),                 # stacked qk-norm scales
        (2048,): (1, 16, 128),
        (5000,): (1, 40, 128),                 # not a storage leaf: padded
    }
    elems = K.BLOCK_BYTES // 4
    for shape, view in views.items():
        for dtype in (jnp.float32, jnp.bfloat16):
            plan = K.block_plan(shape, dtype)
            assert plan.view == view, shape
            assert plan.pad == (40 * 128 - 5000 if shape == (5000,) else 0)
            (k, r, c), (br, bc) = plan.view, plan.block
            assert br % K.SUBLANES == 0 or br == r, shape
            assert bc % K.LANES == 0 or bc == c, shape
            assert -(-br // K.SUBLANES) * K.SUBLANES * bc <= elems, shape
            assert plan.grid == (k, -(-r // br), -(-c // bc)), shape
            assert plan.vmem_bytes <= plan.vmem_limit <= 64 << 20, shape
    # the cells' large leaves: each grid step moves at least 7/8 of a block
    # (a 7-row block is 8 rows in VMEM), where 1024-element blocks took
    # 303,872 steps (embedding) and 86,016 (stacked)
    for shape, steps in [((1, 311164928), 2374), ((7, 1, 12582912), 672),
                         ((7, 2097152), 128)]:
        plan = K.block_plan(shape, jnp.float32)
        assert plan.steps == steps, shape
        assert 8 * 4 * math.prod(plan.block) >= 7 * K.BLOCK_BYTES, shape
    # a leaf under one block is one grid step over the whole leaf
    small = K.block_plan((3, 1, 768), jnp.float32)
    assert small.steps == 1 and small.view == (1, *small.block) == (
        1, 18, 128)


def test_adamw_tail_not_written():
    """Where the plan pads a leaf or leaves a partial last block, each
    result holds exactly the leaf's elements, every one updated as the
    oracle updates it: no padding lands in the leaf, none is dropped."""
    from repro.kernels.adamw import kernel as K
    from repro.kernels.adamw import ops, ref

    kw = dict(ADAMW_KW, t=jnp.asarray(2))
    for shape in [(5000,), (3, 100), (1, 600_064), (7, 100_352)]:
        plan = K.block_plan(shape, jnp.float32)
        partial = any(v % b for v, b in zip(plan.view[1:], plan.block))
        assert plan.pad or partial, shape
        p, g, m, v = _adamw_state(shape, jnp.float32, seed=5)
        got = jax.jit(lambda p, g, m, v: ops.adamw_update_pallas(
            p, g, m, v, interpret=True, **kw))(p, g, m, v)
        want = ref.adamw_update(p, g, m, v, **kw)
        _close(got, want, jnp.float32)
        for new, ora, old in zip(got, want, (p, m, v)):
            # every element the oracle moves moved: none was skipped
            moved = np.asarray(ora) != np.asarray(old)
            assert moved.mean() > 0.99, shape
            assert np.all(np.asarray(new)[moved] != np.asarray(old)[moved])
        # the padded view's tail (zeros in, zeros out) never reaches a result
        if plan.pad:
            assert got[0].size == p.size


def test_apply_adamw_unchanged(monkeypatch):
    """`apply_adamw` through the kernel, with the clip scale now applied
    inside it, gives the params and moments that scaling the gradient
    first and then running the oracle gives, on a tree of every storage
    rank with a gradient large enough to clip."""
    from jax import shard_map
    from repro.core.dist import DistConfig, make_mesh
    from repro.core.meta import ParamMeta
    from repro.kernels.adamw import ops, ref
    from repro.optim import adamw as A

    cfg = DistConfig(mesh_axes=("data", "model"), mesh_shape=(1, 1),
                     fsdp_axes=("data",), param_dtype=jnp.float32,
                     reduce_dtype=jnp.float32)
    shapes = {"a": (1024,), "b": (1, 768), "c": (3, 512), "d": (2, 1, 384)}
    metas = {k: ParamMeta(k, s, tp_dim=1 if k in "bd" else None)
             for k, s in shapes.items()}
    storage = {k: _adamw_state(s, jnp.float32, seed=i)[0]
               for i, (k, s) in enumerate(shapes.items())}
    grads = {k: 5 * _adamw_state(s, jnp.float32, seed=i + 9)[1]
             for i, (k, s) in enumerate(shapes.items())}
    opt = A.init_opt_state(storage)
    opt["m"] = jax.tree.map(lambda x: 0.1 * x, grads)
    opt["v"] = jax.tree.map(lambda x: 0.01 * x * x, grads)
    opt["step"] = jnp.asarray(4, jnp.int32)
    ocfg = A.AdamWConfig(lr=1e-3)
    P = jax.sharding.PartitionSpec
    mesh = make_mesh(cfg)

    def run(st, g, o):
        return A.apply_adamw(st, g, o, metas, cfg, ocfg, ocfg.lr)

    # the TPU branch, with the kernel interpreted
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "adamw_update_pallas", functools.partial(
        ops.adamw_update_pallas, interpret=True))
    new_p, new_opt, gnorm = jax.jit(shard_map(
        run, mesh=mesh, in_specs=P(), out_specs=P(),
        check_vma=False))(storage, grads, opt)
    monkeypatch.undo()
    scale = ocfg.grad_clip / float(gnorm)
    assert scale < 0.5
    t = jnp.asarray(5, jnp.int32)
    for k in shapes:
        want = ref.adamw_update(storage[k], grads[k] * scale, opt["m"][k],
                                opt["v"][k], lr=ocfg.lr, b1=ocfg.b1,
                                b2=ocfg.b2, eps=ocfg.eps,
                                wd=ocfg.weight_decay, t=t)
        got = (new_p[k], new_opt["m"][k], new_opt["v"][k])
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# SSD (Mamba-2)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("T,H,P,G,N,chunk", [
    (96, 4, 16, 2, 8, 32), (128, 2, 32, 1, 16, 64), (64, 4, 16, 4, 8, 64),
])
def test_ssd_sweep(T, H, P, G, N, chunk):
    from repro.kernels.ssd import ops, ref
    B = 2
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, T, G, N)) * 0.4
    Cm = jax.random.normal(ks[4], (B, T, G, N)) * 0.4
    D = jnp.ones((H,))
    got = ops.ssd(x, dt, A, Bm, Cm, D, chunk, True)
    want, _ = ref.ssd_chunked(x, dt, A, Bm, Cm, D=D, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_ssd_chunk_invariance():
    """Chunk size is an implementation detail — results must not change."""
    from repro.kernels.ssd import ref
    B, T, H, P, G, N = 1, 128, 2, 8, 1, 4
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, T, G, N)) * 0.4
    Cm = jax.random.normal(ks[4], (B, T, G, N)) * 0.4
    y1, _ = ref.ssd_chunked(x, dt, A, Bm, Cm, chunk=16)
    y2, _ = ref.ssd_chunked(x, dt, A, Bm, Cm, chunk=128)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-5)


def test_ssd_step_matches_chunked():
    """Recurrent decode step == chunked over a length-1 sequence chain."""
    from repro.kernels.ssd import ref
    B, T, H, P, G, N = 1, 8, 2, 4, 1, 4
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, T, G, N)) * 0.4
    Cm = jax.random.normal(ks[4], (B, T, G, N)) * 0.4
    y_chunk, _ = ref.ssd_chunked(x, dt, A, Bm, Cm, chunk=T)
    S = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(T):
        S, y = ref.ssd_step(S, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t])
        ys.append(y)
    y_step = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_step), np.asarray(y_chunk),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# mLSTM cell (xlstm)
# ---------------------------------------------------------------------------
def test_mlstm_chunk_invariance_and_step():
    from repro.models.xlstm import mlstm_chunked, mlstm_step
    B, T, H, dk, dv = 1, 32, 2, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(ks[0], (B, T, H, dk))
    k = jax.random.normal(ks[1], (B, T, H, dk))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    i_pre = jax.random.normal(ks[3], (B, T, H))
    f_pre = jax.random.normal(ks[4], (B, T, H)) + 2.0
    y1, s1 = mlstm_chunked(q, k, v, i_pre, f_pre, chunk=8)
    y2, s2 = mlstm_chunked(q, k, v, i_pre, f_pre, chunk=32)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-5)
    # recurrent form
    state = None
    ys = []
    from repro.models.xlstm import mlstm_step
    import jax.numpy as jnp2
    state = (jnp2.zeros((B, H, dk, dv)), jnp2.zeros((B, H, dk)),
             jnp2.full((B, H), -1e30))
    for t in range(T):
        state, y = mlstm_step(state, q[:, t], k[:, t], v[:, t],
                              i_pre[:, t], f_pre[:, t])
        ys.append(y)
    y_step = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_step), np.asarray(y1),
                               rtol=2e-4, atol=2e-5)
