"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""

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
@pytest.mark.parametrize("n", [1024, 5000])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_adamw_sweep(n, dtype):
    from repro.kernels.adamw import ops, ref
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    p = jax.random.normal(ks[0], (n,)).astype(dtype)
    g = jax.random.normal(ks[1], (n,)).astype(dtype)
    m = jax.random.normal(ks[2], (n,)).astype(dtype) * 0.1
    v = jnp.abs(jax.random.normal(ks[3], (n,))).astype(dtype) * 0.01
    kw = dict(lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, wd=0.1, t=jnp.asarray(3))
    got = ops.adamw_update_pallas(p, g, m, v, interpret=True, **kw)
    want = ref.adamw_update(p, g, m, v, **kw)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL32)


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
