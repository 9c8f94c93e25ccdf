"""The plain reference tied to the program: at smoke size on the CPU, the
program's train step and bench/reference/qwen3.py agree on the loss, the
gradients and the parameters after one AdamW step, from the same weights
and batch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_smoke
import check
import program
import spec
import traffic



@pytest.fixture(scope="module")
def pair(tmp_path_factory):
    loaded = bench_smoke.get("l7x4")
    cfg, opt = loaded["config"], loaded["config"]["optimizer"]
    ref = spec.reference(cfg["reference"])
    trainer = program.build(cfg, loaded["traffic"],
                            str(tmp_path_factory.mktemp("ckpt")))
    kd = np.asarray(ref.seed_words(12345))
    make = lambda k: ref.init_params(cfg, jax.random.wrap_key_data(k))
    p0, o0 = program.state_fn(trainer, make)(kd)
    batch = traffic.PackedDocuments(loaded["traffic"], cfg["vocab_size"],
                                    12345).batch(0)
    p1, o1, metrics = trainer.step_fn(p0, o0, program.feed(trainer, batch,
                                                           0, 12345))
    with jax.default_matmul_precision("highest"):
        params = jax.jit(make)(kd)
        loss, grads = jax.value_and_grad(
            lambda p: ref.loss_fn(cfg, p, batch))(params)
        zeros = jax.tree.map(jnp.zeros_like, params)
        new, m, _, g = ref.adamw(opt, params, grads, zeros, zeros, 0)
    metas = trainer.model.metas(trainer.dcfg)
    from repro.core.api import unshard_params
    plain = lambda s: jax.device_get(unshard_params(s, metas, trainer.dcfg))
    return {"metrics": metrics, "loss": float(loss), "opt": opt,
            "grad_prog": plain(jax.tree.map(lambda x: x / (1 - opt["b1"]),
                                            o1["m"])),
            "grad_ref": jax.device_get(g), "new_prog": plain(p1),
            "new_ref": jax.device_get(new)}


def test_loss(pair):
    # bf16 compute against f32: a few parts in 1e5 at these widths; 1e-3
    # relative leaves room and still fails a wrong mask or normaliser,
    # which moves the loss by a whole part in a hundred or more
    assert pair["metrics"]["loss"] == pytest.approx(pair["loss"], rel=1e-3)


def test_gradients(pair):
    # the clipped gradient the optimizer got, leaf by leaf: bf16 products
    # leave each leaf's norm within a per cent of the f32 one, while a
    # dropped term or a wrong scale moves it by tens of per cent
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(pair["grad_prog"])[0],
            jax.tree.leaves(pair["grad_ref"])):
        na, nb = np.linalg.norm(a), np.linalg.norm(b)
        assert na == pytest.approx(nb, rel=2e-2), path


def test_parameters_after_one_adamw_step(pair):
    # Adam's first step moves each weight by lr * g / |g| (plus decay):
    # where bf16 rounding flips the sign of a near-zero gradient the two
    # sides differ by 2 lr, never more; f32 rounding of the weight adds
    # a few ulps of its value
    lr = pair["opt"]["lr"]
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(pair["new_prog"])[0],
            jax.tree.leaves(pair["new_ref"])):
        gap = np.abs(np.asarray(a) - np.asarray(b))
        assert (gap <= 2 * lr * (1 + 1e-3)
                + 4e-7 * np.abs(np.asarray(b))).all(), path
        # and most weights take the same step on both sides
        assert (gap <= 0.1 * lr + 4e-7 * np.abs(np.asarray(b))).mean() \
            > 0.9, path
