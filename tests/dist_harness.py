"""Multi-device semantic checks, run in a subprocess with 8 fake CPU devices.

Invoked as ``python -m tests.dist_harness <case> [<case> ...]`` by
tests/test_distributed.py (jax pins the device count at first init, so the
main pytest process — which must see ONE device for the smoke tests — cannot
host these).

Each case builds a tiny TP+FSDP model three ways and asserts gradients and
outputs match a single-device dense reference EXACTLY (fp32 end to end):

  * gather_group (the parametrization custom_vjp) on a raw param tree
  * apply_stack vanilla (scan + remat policy, autodiff backward)
  * apply_stack prefetch (the hand-scheduled custom_vjp) under every
    combination of the Table-6 schedule flags and every bucket mode

across mesh layouts: 2D (data,model), 3D HSDP (pod,data,model; shard in-pod)
and 3D global ZeRO-3 (shard over pod+data).

The `pipeline` case covers paper SS4's pipeline-parallel composition: GPipe
and 1F1B schedules under (pipe, data, model) meshes with FSDP bucket gathers
active INSIDE each pipelined stage, asserted exactly against the sequential
dense reference (losses, parameter grads, and d/d(xs)) across bucket modes.

The `trainer_pipeline` case covers the unified `parallelize()` path — the
full-LM stage partition (embedding on stage 0, layer slices, head+loss on
the last stage, replicated tied embeddings): pp=2 vs the pp=1 baseline must
agree exactly on losses, assembled gradients, and one AdamW step.  The
`trainer_smoke_a/b` cases run every registered arch 2 Trainer steps (plus a
staged checkpoint) on a pp2 x dp2 x tp2 mesh.

The `pipeline_v2` case covers the PR-6 schedules: interleaved 1F1B (V=2
virtual stage chunks per rank) and zb (W-split zero-bubble) at pp2 x dp4
must reproduce pp=1 exactly (losses, grads, AdamW steps), plus zamba2's
uneven zero-padded stage partition over two chained train steps and the
stage_pre-hoist trace-count regression.

The `quant` case covers quantized collectives (kernels/quant +
DistConfig.comm_precision): comm_precision="bf16" must be BIT-exact vs the
default path over two chained AdamW steps, while fp8_ag / fp8 / fp8_ef /
auto must track the bf16 reference within documented EF-theory tolerance
(loss rtol 5e-2, per-coordinate weight drift <= 4*lr*steps) with the
error-feedback accumulator present exactly when DistConfig.needs_ef.

The `context` case covers context parallelism (core/context.py): zigzag
sequence sharding + ring attention over the ctx axis — cp2 x dp2 must
reproduce the cp1 x dp4 baseline exactly (losses, assembled grads, one
AdamW step) for dense + gemma2, and the 4-axis pp2 x dp2 x cp2 composition
must reproduce pp1 x dp4.
"""

from __future__ import annotations

import os
import sys

if __name__ == "__main__":  # device count must be set before jax init
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", "")
    )

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.core import (BucketPlan, DistConfig, ParamMeta, apply_stack,
                        from_storage, make_mesh, replicate_tree, to_storage)
from repro.core.bucketing import per_param_plan, whole_block_plan

D, H, B, L = 8, 16, 16, 4  # model dim, hidden, global batch, layers


# --------------------------------------------------------------------------
# Tiny TP-aware block (see module docstring for why each param is shaped so).
# --------------------------------------------------------------------------
def block_metas(cfg: DistConfig):
    return {
        "w1": ParamMeta("w1", (D, H), tp_dim=1),
        "b": ParamMeta("b", (H,), tp_dim=0),
        "g": ParamMeta("g", (1,), tp_dim=None),      # consumed TP-varying
        "w2": ParamMeta("w2", (H, D), tp_dim=0),
        "scale": ParamMeta("scale", (D,), tp_dim=None),  # consumed replicated
    }


def init_block(key):
    ks = jax.random.split(key, 5)
    return {
        "w1": jax.random.normal(ks[0], (D, H)) * 0.3,
        "b": jax.random.normal(ks[1], (H,)) * 0.1,
        "g": jnp.ones((1,)) * 0.7,
        "w2": jax.random.normal(ks[2], (H, D)) * 0.3,
        "scale": 1.0 + jax.random.normal(ks[3], (D,)) * 0.1,
    }


def block_local(p, consts, x, cfg: DistConfig):
    """TP-local compute: w1 col-parallel, w2 row-parallel + psum."""
    h = jnp.tanh(x @ p["w1"])          # (b, H/tp)
    h = h * p["g"][0] + p["b"]
    o = h @ p["w2"]                    # partial sums over H
    if cfg.tp_size > 1:
        o = lax.psum(o, cfg.tp_axis)
    y = x + o * p["scale"] + consts["shift"]
    return y, {"l2": jnp.sum(h.astype(jnp.float32) ** 2)}


def block_dense(p, consts, x):
    h = jnp.tanh(x @ p["w1"])
    h = h * p["g"][0] + p["b"]
    o = h @ p["w2"]
    y = x + o * p["scale"] + consts["shift"]
    return y, jnp.sum(h.astype(jnp.float32) ** 2)


def dense_loss(stacked_full, consts, x, dp_total=1):
    """Reference objective. The aux (l2) term is a *sum* over all elements;
    under the per-device-mean gradient convention (global objective = mean
    over DP ranks of local losses) the dense equivalent scales it by
    1/dp_total — see run_stack_case."""
    def body(c, p):
        y, l2 = block_dense(p, consts, c)
        return y, l2
    y, l2s = lax.scan(body, x, stacked_full)
    return jnp.mean(y**2) + 1e-3 * jnp.sum(l2s) / dp_total, y


# --------------------------------------------------------------------------
def fp32_cfg(mesh_axes, mesh_shape, fsdp_axes, **kw) -> DistConfig:
    return DistConfig(
        mesh_axes=mesh_axes, mesh_shape=mesh_shape, fsdp_axes=fsdp_axes,
        param_dtype=jnp.float32, reduce_dtype=jnp.float32,
        storage_dtype=jnp.float32, **kw,
    )


def stacked_storage(stacked_full, metas, cfg):
    """(L, ...)-stacked full params -> (L, storage...) layout."""
    return {
        k: jnp.stack([to_storage(stacked_full[k][i], metas[k], cfg)
                      for i in range(L)])
        for k in metas
    }


def run_stack_case(cfg: DistConfig, plan, tag: str):
    mesh = make_mesh(cfg)
    metas = block_metas(cfg)
    key = jax.random.PRNGKey(0)
    stacked_full = {
        k: jnp.stack([init_block(jax.random.fold_in(key, i))[k]
                      for i in range(L)])
        for k in block_metas(cfg)
    }
    x = jax.random.normal(jax.random.PRNGKey(7), (B, D))
    consts = {"shift": jnp.full((D,), 0.01)}

    dp = cfg.dp_total

    # dense reference ------------------------------------------------------
    ref_loss = dense_loss(stacked_full, consts, x, dp)[0]
    ref_grads, ref_dx = jax.grad(
        lambda s, xx: dense_loss(s, consts, xx, dp)[0], argnums=(0, 1))(
            stacked_full, x)

    # sharded --------------------------------------------------------------
    storage = stacked_storage(stacked_full, metas, cfg)
    blk = functools.partial(block_local, cfg=cfg)

    def local_loss(storage, consts, x):
        y, aux = apply_stack(blk, metas, cfg, storage, consts, x, plan=plan)
        l2 = aux["l2"]
        if cfg.tp_size > 1:
            l2 = lax.psum(l2, cfg.tp_axis)
        # per-device loss: local-mean main term + the full TP-summed aux for
        # the locally owned rows. Global objective = pmean over DP ranks.
        return jnp.mean(y**2) + 1e-3 * l2

    def step(storage, consts, x):
        (loss, _), grads = jax.value_and_grad(
            lambda s: (local_loss(s, consts, x), 0.0), has_aux=True)(storage)
        dx = jax.grad(lambda xx: local_loss(storage, consts, xx))(x)
        loss = lax.pmean(loss, tuple(a for a in cfg.mesh_axes
                                     if a != cfg.tp_axis))
        return loss, grads, dx

    dp_axes = tuple(a for a in cfg.mesh_axes if a != cfg.tp_axis)
    in_specs = (
        {k: metas[k].stacked_storage_spec(cfg) for k in metas},
        {"shift": P()},
        P(dp_axes),
    )
    out_specs = (
        P(),
        {k: metas[k].stacked_storage_spec(cfg) for k in metas},
        P(dp_axes),
    )
    fn = jax.jit(shard_map(step, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs))
    loss, grads, dx = fn(storage, consts, x)

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-5,
                               err_msg=f"{tag}: loss mismatch")
    # d(local_loss)/d(local x) is dp x the dense d(global mean)/dx
    np.testing.assert_allclose(np.asarray(dx) / dp, np.asarray(ref_dx),
                               rtol=2e-4, atol=2e-5,
                               err_msg=f"{tag}: dx mismatch")
    for k in metas:
        got = jnp.stack([from_storage(grads[k][i], metas[k], cfg)
                         for i in range(L)])
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref_grads[k]), rtol=2e-4, atol=2e-5,
            err_msg=f"{tag}: grad mismatch for {k}")
    print(f"PASS {tag}")


MESHES = {
    "2d": (("data", "model"), (4, 2), ("data",)),
    "hsdp": (("pod", "data", "model"), (2, 2, 2), ("data",)),
    "zero3": (("pod", "data", "model"), (2, 2, 2), ("pod", "data")),
}


def case_roundtrip():
    cfg = fp32_cfg(*MESHES["2d"])
    metas = block_metas(cfg)
    p = init_block(jax.random.PRNGKey(3))
    for k, m in metas.items():
        rt = from_storage(to_storage(p[k], m, cfg), m, cfg)
        np.testing.assert_allclose(np.asarray(rt), np.asarray(p[k]),
                                   err_msg=f"roundtrip {k}")
    print("PASS roundtrip")


def case_gather_values():
    """gather_group reconstructs exact full params on every device."""
    for mesh_name, spec in MESHES.items():
        cfg = fp32_cfg(*spec)
        mesh = make_mesh(cfg)
        metas = block_metas(cfg)
        p = init_block(jax.random.PRNGKey(3))
        storage = {k: to_storage(p[k], metas[k], cfg) for k in metas}

        def f(storage):
            full = replicate_tree(storage, metas, cfg,
                                  whole_block_plan(metas))
            # re-assemble the TP-sharded params for comparison outside
            return full

        out_specs = {}
        for k, m in metas.items():
            if m.tp_dim is None:
                out_specs[k] = P()
            else:
                axes = [None] * len(m.global_shape)
                axes[m.tp_dim] = cfg.tp_axis
                out_specs[k] = P(*axes)
        # gathered outputs are value-replicated but vma can't prove it —
        # this diagnostic case opts out of the replication check
        fn = jax.jit(shard_map(
            f, mesh=mesh,
            in_specs=({k: metas[k].storage_spec(cfg) for k in metas},),
            out_specs=out_specs, check_vma=False))
        full = fn(storage)
        for k in metas:
            np.testing.assert_allclose(
                np.asarray(full[k]), np.asarray(p[k]),
                err_msg=f"gather {mesh_name}/{k}")
    print("PASS gather_values")


def case_vanilla():
    for mesh_name, spec in MESHES.items():
        for bucket, plan_fn in [("none", per_param_plan),
                                ("block", whole_block_plan)]:
            cfg = fp32_cfg(*spec, reorder=False, remat="fsdp_only")
            run_stack_case(cfg, plan_fn(block_metas(cfg)),
                           f"vanilla/{mesh_name}/bucket={bucket}")


def case_prefetch():
    for mesh_name, spec in MESHES.items():
        for agf in (True, False):
            for agb in (True, False):
                for rsd in (True, False):
                    cfg = fp32_cfg(*spec, reorder=True,
                                   ag_before_wait_fwd=agf,
                                   ag_before_wait_bwd=agb, rs_delay=rsd)
                    run_stack_case(
                        cfg, whole_block_plan(block_metas(cfg)),
                        f"prefetch/{mesh_name}/agf={agf}/agb={agb}/rsd={rsd}")


def case_prefetch_buckets():
    """Prefetch path under per-param and custom two-bucket plans."""
    cfg = fp32_cfg(*MESHES["2d"], reorder=True)
    metas = block_metas(cfg)
    run_stack_case(cfg, per_param_plan(metas), "prefetch/bucket=none")
    custom = BucketPlan((("w1", "b"), ("g", "w2", "scale")))
    run_stack_case(cfg, custom, "prefetch/bucket=custom2")


def case_remat_modes():
    for remat in ("none", "fsdp_only", "full"):
        cfg = fp32_cfg(*MESHES["2d"], reorder=False, remat=remat)
        run_stack_case(cfg, whole_block_plan(block_metas(cfg)),
                       f"vanilla/remat={remat}")


CASES = {
    "roundtrip": case_roundtrip,
    "gather_values": case_gather_values,
    "vanilla": case_vanilla,
    "prefetch": case_prefetch,
    "prefetch_buckets": case_prefetch_buckets,
    "remat_modes": case_remat_modes,
}





# --------------------------------------------------------------------------
# Every architecture: (2 data x 4 model) mesh == single-device reference.
# Exercises TP/SP/EP/head-padding/replicated-kv paths end to end.
# --------------------------------------------------------------------------
def case_models():
    from repro.models.common import ShapeConfig
    from repro.models.registry import ARCH_IDS, get_arch
    from repro.models import runtime as RT

    for arch in ARCH_IDS:
        if arch == "llama3_8b":
            continue   # same code path as deepseek/qwen3
        cfg, model = get_arch(arch, smoke=True)
        dcfg1 = fp32_cfg(("data", "model"), (1, 1), ("data",))
        dcfg8 = fp32_cfg(("data", "model"), (2, 4), ("data",))

        B = 4
        if arch == "seamless_m4t_large_v2":
            S_total = 64
        elif arch == "internvl2_26b":
            S_total = 40           # 8 img + 32 text
        else:
            S_total = 32
        shape = ShapeConfig("t", S_total, B, "train")

        full = model.init_full(jax.random.PRNGKey(0), dcfg8)
        key = jax.random.PRNGKey(1)
        batch = {}
        for k, sd in model.input_specs(shape, dcfg8).items():
            key = jax.random.fold_in(key, 7)
            if jnp.issubdtype(sd.dtype, jnp.integer):
                batch[k] = jax.random.randint(key, sd.shape, 0, cfg.vocab)
            elif k == "valid":
                batch[k] = jnp.ones(sd.shape, sd.dtype)
            else:
                batch[k] = jax.random.normal(key, sd.shape, sd.dtype) * 0.3

        results = {}
        for name, dcfg in [("1dev", dcfg1), ("8dev", dcfg8)]:
            metas = model.metas(dcfg)
            storage = {k: RT.tree_to_storage(full[k], metas[k], dcfg)
                       for k in full}
            step = RT.make_loss_step(model, dcfg)
            specs = RT.model_storage_specs(model, dcfg)
            fn, _ = RT.wrap_step(model, dcfg, shape, step, (P(), specs))
            loss, grads = fn(storage, batch)
            gfull = {k: RT.tree_from_storage(grads[k], metas[k], dcfg)
                     for k in grads}
            results[name] = (float(loss), gfull)

        l1, g1 = results["1dev"]
        l8, g8 = results["8dev"]
        np.testing.assert_allclose(l8, l1, rtol=5e-5,
                                   err_msg=f"{arch}: loss mesh mismatch")
        flat1 = dict(jax.tree_util.tree_flatten_with_path(g1)[0] and
                     [(jax.tree_util.keystr(p), v) for p, v in
                      jax.tree_util.tree_flatten_with_path(g1)[0]])
        flat8 = dict([(jax.tree_util.keystr(p), v) for p, v in
                      jax.tree_util.tree_flatten_with_path(g8)[0]])
        for k in flat1:
            np.testing.assert_allclose(
                np.asarray(flat8[k]), np.asarray(flat1[k]),
                rtol=3e-3, atol=3e-5,
                err_msg=f"{arch}: grad mismatch at {k}")
        print(f"PASS models/{arch} (loss {l1:.4f})")


CASES["models"] = case_models


def case_hlo_structure():
    """Paper SS3.2.1 visible in the lowering: per-block bucketing MERGES
    per-parameter all-gathers/reduce-scatters (counted in stablehlo, which
    preserves program structure; scan bodies count once)."""
    import re
    from repro.models import runtime as RT
    from repro.models.common import ShapeConfig
    from repro.models.registry import get_arch

    def lower_text(bucket_mode, reorder):
        cfg, model = get_arch("qwen3_1_7b", smoke=True)
        dcfg = fp32_cfg(("data", "model"), (4, 2), ("data",),
                        bucket_mode=bucket_mode, reorder=reorder)
        storage = RT.init_storage(model, jax.random.PRNGKey(0), dcfg)
        batch = {"tokens": jnp.zeros((8, 32), jnp.int32),
                 "targets": jnp.zeros((8, 32), jnp.int32),
                 "valid": jnp.ones((8, 32))}
        step = RT.make_loss_step(model, dcfg)
        specs = RT.model_storage_specs(model, dcfg)
        fn, _ = RT.wrap_step(model, dcfg, ShapeConfig("t", 32, 8, "train"),
                             step, (P(), specs))
        return fn.lower(storage, batch).as_text()

    def count(txt, op):
        return len(re.findall(rf"stablehlo\.{op}\b", txt))

    none = lower_text("none", False)
    block = lower_text("block", False)
    n_ag, b_ag = count(none, "all_gather"), count(block, "all_gather")
    n_rs, b_rs = count(none, "reduce_scatter"), count(block, "reduce_scatter")
    assert b_ag < n_ag, (n_ag, b_ag)
    assert b_rs <= n_rs, (n_rs, b_rs)
    auto = lower_text("auto", True)
    assert count(auto, "all_gather") > 0
    print(f"PASS hlo_structure (AG {n_ag}->{b_ag}, RS {n_rs}->{b_rs})")


CASES["hlo_structure"] = case_hlo_structure



# --------------------------------------------------------------------------
# Pipeline parallelism: GPipe / 1F1B x SimpleFSDP x TP under a
# (pipe, data, model) mesh — paper SS4's composability, exact fp32 parity.
# --------------------------------------------------------------------------
PD, PH = 8, 16    # pipeline-stage model dim / hidden dim


def tp_stage_metas():
    """Every param TP-sharded: all cross-rank gradient flow goes through
    explicit collectives with exact transposes (all_gather <-> psum_scatter,
    ppermute <-> reverse ppermute), so pp x dp x tp parity is exact on any
    jax version (no reliance on vma replication-transpose psums)."""
    return {
        "w1": ParamMeta("w1", (PD, PH), tp_dim=1),
        "b": ParamMeta("b", (PH,), tp_dim=0),
        "w2": ParamMeta("w2", (PH, PD), tp_dim=0),
    }


def init_tp_stage(key):
    ks = jax.random.split(key, 3)
    return {
        "w1": jax.random.normal(ks[0], (PD, PH)) * 0.3,
        "b": jax.random.normal(ks[1], (PH,)) * 0.1,
        "w2": jax.random.normal(ks[2], (PH, PD)) * 0.3,
    }


def tp_stage_dense(p, x):
    h = jnp.tanh(x @ p["w1"]) + p["b"]
    return x + h @ p["w2"]


def tp_stage_local(p, x, cfg: DistConfig):
    """SP-style TP stage: x arrives batch-sharded over (data, model); the
    microbatch is all-gathered over TP for the sharded-H matmuls and the
    partial output is reduce-scattered back to the batch shard."""
    if cfg.tp_size > 1:
        xg = lax.all_gather(x, cfg.tp_axis, axis=0, tiled=True)
    else:
        xg = x
    h = jnp.tanh(xg @ p["w1"]) + p["b"]
    o = h @ p["w2"]
    if cfg.tp_size > 1:
        o = lax.psum_scatter(o, cfg.tp_axis, scatter_dimension=0, tiled=True)
    return x + o


def rep_stage_metas():
    """Mixed TP-sharded + replicated params (two vma bucket classes); run
    on tp=1 meshes where replicated-param grads are exact everywhere."""
    return {
        "w1": ParamMeta("w1", (PD, PH), tp_dim=1),
        "b": ParamMeta("b", (PH,), tp_dim=0),
        "g": ParamMeta("g", (1,), tp_dim=None),
        "w2": ParamMeta("w2", (PH, PD), tp_dim=0),
        "scale": ParamMeta("scale", (PD,), tp_dim=None),
    }


def init_rep_stage(key):
    ks = jax.random.split(key, 5)
    return {
        "w1": jax.random.normal(ks[0], (PD, PH)) * 0.3,
        "b": jax.random.normal(ks[1], (PH,)) * 0.1,
        "g": jnp.ones((1,)) * 0.7,
        "w2": jax.random.normal(ks[2], (PH, PD)) * 0.3,
        "scale": 1.0 + jax.random.normal(ks[3], (PD,)) * 0.1,
    }


def rep_stage_dense(p, x):
    h = jnp.tanh(x @ p["w1"]) * p["g"][0] + p["b"]
    return x + (h @ p["w2"]) * p["scale"]


def run_pipeline_case(cfg: DistConfig, plan, schedule: str, metas, init_fn,
                      dense_fn, local_fn, tag: str):
    """One pp x dp x tp configuration vs the single-device dense reference.

    Batch sharding spans (data, model) [SP]; grads come back under the
    repo's per-device-mean convention: param grads are tp x dense, dxs is
    (dp*tp) x dense (cf. run_stack_case's dp scaling).
    """
    from repro.core.pipeline import fsdp_stage_fn, pipeline_grads

    mesh = make_mesh(cfg)
    S, M, B = cfg.pp_size, 4, 8
    tp, dp = cfg.tp_size, cfg.dp_total
    stage_params = [init_fn(jax.random.PRNGKey(100 + s)) for s in range(S)]
    xs = jax.random.normal(jax.random.PRNGKey(9), (M, B, PD))

    # dense reference ------------------------------------------------------
    def dense_loss(ps, xs):
        y = xs
        for p in ps:
            y = dense_fn(p, y)
        return jnp.mean(y ** 2)

    ref_loss = dense_loss(stage_params, xs)
    ref_grads, ref_dxs = jax.grad(dense_loss, argnums=(0, 1))(
        stage_params, xs)

    # pipelined + FSDP + TP ------------------------------------------------
    # stage s's params live on pipe rank s, each ZeRO-3 sharded over 'data'
    # (and TP-indexed): storage (S, storage...) per leaf.
    storage = {
        k: jnp.stack([to_storage(stage_params[s][k], metas[k], cfg)
                      for s in range(S)])
        for k in metas
    }
    specs = {k: metas[k].pipe_stacked_storage_spec(cfg) for k in metas}
    batch_axes = ("data", "model") if tp > 1 else ("data",)
    xs_spec = P(None, batch_axes)
    nonpipe = tuple(a for a in cfg.mesh_axes if a != cfg.pp_axis)

    def loss_fn(y):
        return jnp.mean(y ** 2) / M

    stage = fsdp_stage_fn(lambda p, x: local_fn(p, x, cfg), metas, cfg, plan)

    def step(storage, xs):
        local = jax.tree.map(lambda a: a[0], storage)  # this rank's stage
        loss, grads, dxs = pipeline_grads(stage, local, xs, loss_fn, cfg,
                                          schedule)
        loss = lax.pmean(loss, nonpipe)
        grads = jax.tree.map(lambda g: g[None], grads)
        return loss, grads, dxs

    fn = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(specs, xs_spec),
        out_specs=(P(), specs, xs_spec), check_vma=False))
    loss, grads, dxs = fn(storage, xs)

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5,
                               err_msg=f"{tag}: loss mismatch")
    np.testing.assert_allclose(
        np.asarray(dxs) / (dp * tp), np.asarray(ref_dxs),
        rtol=2e-4, atol=2e-6, err_msg=f"{tag}: dxs mismatch")
    for k in metas:
        got = jnp.stack([from_storage(grads[k][s], metas[k], cfg)
                         for s in range(S)]) / tp
        want = jnp.stack([ref_grads[s][k] for s in range(S)])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=1e-6,
                                   err_msg=f"{tag}: grad mismatch {k}")
    print(f"PASS {tag}")


PIPE_MESHES = {
    # pipe OUTERMOST (core/pipeline.py layout convention)
    "pp2_dp2_tp2": (("pipe", "data", "model"), (2, 2, 2)),
    "pp4_dp2": (("pipe", "data", "model"), (4, 2, 1)),
}


def case_pipeline():
    """GPipe and 1F1B over a (pipe, data, model) mesh with FSDP bucket
    gathers active inside each pipelined stage: losses and gradients match
    the single-device dense reference exactly in fp32 across bucket modes."""
    for mesh_name, (axes, shape) in PIPE_MESHES.items():
        tp = shape[axes.index("model")]
        if tp > 1:
            metas_fn, init_fn, dense_fn, local_fn = (
                tp_stage_metas, init_tp_stage, tp_stage_dense,
                tp_stage_local)
        else:
            metas_fn, init_fn, dense_fn, local_fn = (
                rep_stage_metas, init_rep_stage, rep_stage_dense,
                lambda p, x, cfg: rep_stage_dense(p, x))
        metas = metas_fn()
        plans = {"block": whole_block_plan(metas),
                 "none": per_param_plan(metas)}
        if tp == 1:
            plans["custom2"] = BucketPlan((("w1", "b", "g"),
                                           ("w2", "scale")))
        for schedule in ("gpipe", "1f1b"):
            for plan_name, plan in plans.items():
                cfg = fp32_cfg(axes, shape, ("data",), pp_axis="pipe",
                               pp_schedule=schedule)
                run_pipeline_case(
                    cfg, plan, schedule, metas, init_fn, dense_fn, local_fn,
                    f"pipeline/{mesh_name}/{schedule}/bucket={plan_name}")
    print("PASS pipeline (GPipe+1F1B x FSDP x TP, exact grads)")


CASES["pipeline"] = case_pipeline


# --------------------------------------------------------------------------
# The unified Trainer path (core/api.parallelize): full-LM stage partition.
# --------------------------------------------------------------------------
def _fp32_pp(schedule: str, microbatches: int = 2) -> DistConfig:
    return fp32_cfg(("pipe", "data", "model"), (2, 4, 1), ("data",),
                    pp_axis="pipe", pp_schedule=schedule,
                    pp_microbatches=microbatches)


def _synth_batch(model, shape, dcfg, vocab, valid_ones=True):
    from repro.data.pipeline import DataConfig, SyntheticC4, adapt_batch

    ds = SyntheticC4(DataConfig(vocab=vocab, seq_len=shape.seq_len,
                                global_batch=shape.global_batch))
    batch = adapt_batch(ds.batch(0), model.input_specs(shape, dcfg), 0)
    if valid_ones and "valid" in batch:
        # equal per-microbatch token counts: the microbatched mean-of-means
        # then equals the whole-batch mean exactly
        batch["valid"] = np.ones_like(batch["valid"])
    return batch


def case_trainer_pipeline():
    """Exact parity of the unified `parallelize()` path: the SAME model,
    params and batch through (a) the whole-model pp=1 loss/grad step and
    (b) the staged GPipe/1F1B pipeline at pp=2 — losses and every assembled
    full gradient must agree (tp=1, so this case is exact on every jax
    version; the stage partition covers untied heads, tied/replicated
    embeddings, and the MoE aux channel)."""
    from repro.core.api import parallelize
    from repro.models import runtime as RT
    from repro.models.common import ShapeConfig
    from repro.models.registry import get_arch_for_pp

    for arch in ("deepseek_coder_33b", "qwen3_1_7b", "qwen2_moe_a2_7b"):
        cfg, model = get_arch_for_pp(arch, n_stages=2)
        shape = ShapeConfig("t", 32, 8, "train")
        d1 = fp32_cfg(("data", "model"), (4, 1), ("data",))
        batch = _synth_batch(model, shape, d1, cfg.vocab)
        full = model.init_full(jax.random.PRNGKey(0), d1)

        metas1 = model.metas(d1)
        st1 = {k: RT.tree_to_storage(full[k], metas1[k], d1) for k in full}
        par1 = parallelize(model, d1, shape)
        l1, g1 = par1.loss_step()(st1, batch)
        g1full = {k: RT.tree_from_storage(g1[k], metas1[k], d1) for k in g1}
        flat1 = {jax.tree_util.keystr(p): v for p, v in
                 jax.tree_util.tree_flatten_with_path(g1full)[0]}

        for schedule in ("gpipe", "1f1b"):
            dp = _fp32_pp(schedule)
            parp = parallelize(model, dp, shape)
            metasp = model.metas(dp)
            stp = parp.stage_storage(
                {k: RT.tree_to_storage(full[k], metasp[k], dp)
                 for k in full})
            lp, gp = parp.loss_step()(stp, batch)
            gplain = parp.unstage_storage(
                jax.tree.map(np.asarray, gp))
            gpfull = {k: RT.tree_from_storage(gplain[k], metasp[k], dp)
                      for k in gplain}
            flatp = {jax.tree_util.keystr(p): v for p, v in
                     jax.tree_util.tree_flatten_with_path(gpfull)[0]}
            tag = f"trainer_pipeline/{arch}/{schedule}"
            np.testing.assert_allclose(float(lp), float(l1), rtol=2e-5,
                                       err_msg=f"{tag}: loss mismatch")
            assert set(flatp) == set(flat1), f"{tag}: grad tree mismatch"
            for k, want in flat1.items():
                np.testing.assert_allclose(
                    np.asarray(flatp[k]), np.asarray(want),
                    rtol=3e-4, atol=3e-6,
                    err_msg=f"{tag}: grad mismatch at {k}")
            print(f"PASS {tag} (loss {float(lp):.4f})")

    # one TRAIN step through the replicated-embedding arch: the pipe-axis
    # grad psum + the deduplicated grad-norm must reproduce the baseline
    # metrics and the updated weights
    from repro.optim.adamw import AdamWConfig, init_opt_state

    cfg, model = get_arch_for_pp("qwen3_1_7b", n_stages=2)
    shape = ShapeConfig("t", 32, 8, "train")
    d1 = fp32_cfg(("data", "model"), (4, 1), ("data",))
    batch = _synth_batch(model, shape, d1, cfg.vocab)
    full = model.init_full(jax.random.PRNGKey(0), d1)
    metas1 = model.metas(d1)
    st1 = {k: RT.tree_to_storage(full[k], metas1[k], d1) for k in full}
    par1 = parallelize(model, d1, shape)
    fn1 = par1.train_step(AdamWConfig(lr=1e-3), donate=False)
    new1, _, m1 = fn1(st1, init_opt_state(st1), batch)

    dp = _fp32_pp("1f1b")
    parp = parallelize(model, dp, shape)
    metasp = model.metas(dp)
    stp = parp.stage_storage(
        {k: RT.tree_to_storage(full[k], metasp[k], dp) for k in full})
    fnp = parp.train_step(AdamWConfig(lr=1e-3), donate=False)
    newp, _, mp = fnp(stp, init_opt_state(stp), batch)
    np.testing.assert_allclose(float(mp["loss"]), float(m1["loss"]),
                               rtol=2e-5, err_msg="train step loss")
    np.testing.assert_allclose(float(mp["grad_norm"]),
                               float(m1["grad_norm"]), rtol=2e-4,
                               err_msg="train step grad_norm")
    new_plain = parp.unstage_storage(jax.tree.map(np.asarray, newp))
    for k in new1:
        a = {jax.tree_util.keystr(p): v for p, v in
             jax.tree_util.tree_flatten_with_path(new_plain[k])[0]}
        b = {jax.tree_util.keystr(p): v for p, v in
             jax.tree_util.tree_flatten_with_path(
                 jax.tree.map(np.asarray, new1[k]))[0]}
        for kk in b:
            np.testing.assert_allclose(
                a[kk], b[kk], rtol=2e-4, atol=1e-6,
                err_msg=f"updated params mismatch {k}{kk}")
    print("PASS trainer_pipeline/train_step (loss+gnorm+updated weights)")


CASES["trainer_pipeline"] = case_trainer_pipeline


# --------------------------------------------------------------------------
# PR-6 schedules: interleaved 1F1B (virtual stages) + zero-bubble W-split.
# --------------------------------------------------------------------------
def case_pipeline_v2():
    """Exact parity of the NEW table-driven schedules through
    `parallelize()`: at pp2 x dp4, `interleaved` (V=2 virtual stage chunks
    per rank) and `zb` (W-split zero-bubble) must reproduce the pp=1 losses
    and every assembled full gradient for a dense and an MoE arch, and the
    zb AdamW step must reproduce the pp=1 updated weights (tp=1, explicit
    collectives only, so exact on every jax version).  Also covers zamba2's
    UNEVEN superblock partition (stage_layers=(3,5), slots zero-padded to
    6): two chained train steps at pp=2 must track pp=1 — step 2 only
    agrees if the padded slots stayed exact identities through step 1's
    optimizer update — and the padded rows are asserted still exactly 0."""
    import dataclasses as _dc

    from repro.core.api import parallelize
    from repro.models import runtime as RT
    from repro.models.common import ShapeConfig
    from repro.models.registry import build_model, get_arch
    from repro.optim.adamw import AdamWConfig, init_opt_state

    def _flat(tree):
        return {jax.tree_util.keystr(p): np.asarray(v) for p, v in
                jax.tree_util.tree_flatten_with_path(tree)[0]}

    shape = ShapeConfig("t", 32, 8, "train")
    d1 = fp32_cfg(("data", "model"), (4, 1), ("data",))

    for arch in ("qwen3_1_7b", "qwen2_moe_a2_7b"):
        cfg, _ = get_arch(arch, smoke=True)
        cfg = _dc.replace(cfg, n_layers=4)     # Lps=2 -> V=2 chunks
        model = build_model(cfg)
        batch = _synth_batch(model, shape, d1, cfg.vocab)
        full = model.init_full(jax.random.PRNGKey(0), d1)
        metas1 = model.metas(d1)
        st1 = {k: RT.tree_to_storage(full[k], metas1[k], d1) for k in full}
        l1, g1 = parallelize(model, d1, shape).loss_step()(st1, batch)
        flat1 = _flat({k: RT.tree_from_storage(g1[k], metas1[k], d1)
                       for k in g1})

        for schedule, virtual in (("zb", 0), ("interleaved", 2)):
            dp = _fp32_pp(schedule).with_(pp_virtual=virtual)
            parp = parallelize(model, dp, shape)
            assert parp.plan.pp_schedule == schedule, parp.plan.pp_schedule
            if schedule == "interleaved":
                assert parp.plan.pp_virtual == 2, parp.plan.pp_virtual
            metasp = model.metas(dp)
            stp = parp.stage_storage(
                {k: RT.tree_to_storage(full[k], metasp[k], dp)
                 for k in full})
            lp, gp = parp.loss_step()(stp, batch)
            gplain = parp.unstage_storage(jax.tree.map(np.asarray, gp))
            flatp = _flat({k: RT.tree_from_storage(gplain[k], metasp[k], dp)
                           for k in gplain})
            tag = f"pipeline_v2/{arch}/{schedule}"
            np.testing.assert_allclose(float(lp), float(l1), rtol=2e-5,
                                       err_msg=f"{tag}: loss mismatch")
            assert set(flatp) == set(flat1), f"{tag}: grad tree mismatch"
            for k, want in flat1.items():
                np.testing.assert_allclose(
                    flatp[k], want, rtol=3e-4, atol=3e-6,
                    err_msg=f"{tag}: grad mismatch at {k}")
            print(f"PASS {tag} (loss {float(lp):.4f})")

        if arch == "qwen3_1_7b":       # one zb AdamW step vs pp=1
            fn1 = parallelize(model, d1, shape).train_step(
                AdamWConfig(lr=1e-3), donate=False)
            new1, _, m1 = fn1(st1, init_opt_state(st1), batch)
            dp = _fp32_pp("zb")
            parp = parallelize(model, dp, shape)
            metasp = model.metas(dp)
            stp = parp.stage_storage(
                {k: RT.tree_to_storage(full[k], metasp[k], dp)
                 for k in full})
            fnp = parp.train_step(AdamWConfig(lr=1e-3), donate=False)
            newp, _, mp = fnp(stp, init_opt_state(stp), batch)
            np.testing.assert_allclose(float(mp["loss"]), float(m1["loss"]),
                                       rtol=2e-5, err_msg="zb step loss")
            np.testing.assert_allclose(
                float(mp["grad_norm"]), float(m1["grad_norm"]), rtol=2e-4,
                err_msg="zb step grad_norm")
            a = _flat(parp.unstage_storage(jax.tree.map(np.asarray, newp)))
            b = _flat(jax.tree.map(np.asarray, new1))
            for k in b:
                np.testing.assert_allclose(
                    a[k], b[k], rtol=2e-4, atol=1e-5,
                    err_msg=f"zb updated params mismatch at {k}")
            print("PASS pipeline_v2/qwen3_1_7b/zb_train_step")

    # zamba2's uneven stages: (3, 5) real layers zero-padded to 6-row slots
    cfg, model = get_arch("zamba2_1_2b", smoke=True)
    spec = model.stage_spec(2)
    assert spec.stage_layers == (3, 5), spec.stage_layers
    assert spec.layers_per_stage == 6, spec.layers_per_stage
    batch = _synth_batch(model, shape, d1, cfg.vocab)
    full = model.init_full(jax.random.PRNGKey(0), d1)
    metas1 = model.metas(d1)
    st1 = {k: RT.tree_to_storage(full[k], metas1[k], d1) for k in full}
    fn1 = parallelize(model, d1, shape).train_step(
        AdamWConfig(lr=1e-3), donate=False)
    opt1 = init_opt_state(st1)
    new1, opt1, m1a = fn1(st1, opt1, batch)
    new1, _, m1b = fn1(new1, opt1, batch)

    dp = _fp32_pp("1f1b")
    parp = parallelize(model, dp, shape)
    metasp = model.metas(dp)
    stp = parp.stage_storage(
        {k: RT.tree_to_storage(full[k], metasp[k], dp) for k in full})
    fnp = parp.train_step(AdamWConfig(lr=1e-3), donate=False)
    optp = init_opt_state(stp)
    newp, optp, mpa = fnp(stp, optp, batch)
    newp, _, mpb = fnp(newp, optp, batch)
    np.testing.assert_allclose(float(mpa["loss"]), float(m1a["loss"]),
                               rtol=2e-5, err_msg="zamba2 step-1 loss")
    np.testing.assert_allclose(float(mpb["loss"]), float(m1b["loss"]),
                               rtol=2e-4, err_msg="zamba2 step-2 loss")
    # padded rows (slot 0 holds 3 real layers of 6) must still be EXACT
    # zeros after two optimizer steps — the identity-slot invariant
    for leaf in jax.tree.leaves(jax.tree.map(np.asarray,
                                             newp[spec.pipelined])):
        pad = leaf[0, spec.stage_layers[0]:]
        assert not np.any(pad), "zamba2 padded slot drifted from zero"
    a = _flat(parp.unstage_storage(jax.tree.map(np.asarray, newp)))
    b = _flat(jax.tree.map(np.asarray, new1))
    for k in b:
        np.testing.assert_allclose(
            a[k], b[k], rtol=5e-4, atol=3e-5,
            err_msg=f"zamba2 2-step params mismatch at {k}")
    print("PASS pipeline_v2/zamba2_1_2b/uneven_stages (2 chained steps)")

    # regression: stage-0's `stage_pre` (the embedding) is HOISTED out of
    # the slot loop — per step build it traces once inside the lax.map
    # over microbatches (+1 for the hoisted-vjp replay), NOT once per
    # pipeline slot (2(M+S-1) slots would each retrace it before the fix)
    calls = []
    orig_pre = model.stage_pre

    def counting_pre(*a, **kw):
        calls.append(1)
        return orig_pre(*a, **kw)

    model.stage_pre = counting_pre
    try:
        par2 = parallelize(model, dp, shape)
        jax.eval_shape(par2.loss_step(), stp, batch)
    finally:
        model.stage_pre = orig_pre
    n_slots = 2 * (dp.pp_microbatches + dp.pp_size - 1)
    assert 1 <= len(calls) <= 2 < n_slots, \
        f"stage_pre traced {len(calls)}x per step (slots={n_slots})"
    print(f"PASS pipeline_v2/stage_pre_hoist (traced {len(calls)}x, "
          f"{n_slots} slots)")


CASES["pipeline_v2"] = case_pipeline_v2


def case_remat_vector():
    """Memory subsystem parity (core/memory): per-segment remat policy
    vectors — including a budget-resolved auto plan — produce EXACTLY the
    same losses and assembled full gradients as the whole-block policy at
    pp2 x dp2 (tp=1, exact on every jax version).  Covers both stack paths:
    the segmented-vanilla per-segment checkpoint chain and the prefetch
    schedule's residency wraps."""
    from repro.core.api import parallelize
    from repro.models import runtime as RT
    from repro.models.common import ShapeConfig
    from repro.models.registry import get_arch_for_pp

    cfg, model = get_arch_for_pp("qwen3_1_7b", n_stages=2)
    shape = ShapeConfig("t", 32, 8, "train")
    dp = fp32_cfg(("pipe", "data", "model"), (2, 4, 1), ("data",),
                  pp_axis="pipe", pp_schedule="1f1b", pp_microbatches=2)
    batch = _synth_batch(model, shape, dp, cfg.vocab)
    full = model.init_full(jax.random.PRNGKey(0), dp)
    metas = model.metas(dp)

    def run(dcfg):
        par = parallelize(model, dcfg, shape)
        st = par.stage_storage(
            {k: RT.tree_to_storage(full[k], metas[k], dcfg) for k in full})
        loss, grads = par.loss_step()(st, batch)
        plain = par.unstage_storage(jax.tree.map(np.asarray, grads))
        gfull = {k: RT.tree_from_storage(plain[k], metas[k], dcfg)
                 for k in plain}
        flat = {jax.tree_util.keystr(p): np.asarray(v) for p, v in
                jax.tree_util.tree_flatten_with_path(gfull)[0]}
        return float(loss), flat, par.plan

    ref_l, ref_g, _ = run(dp)                      # whole-block fsdp_only
    variants = [
        ("vector/vanilla", dp.with_(reorder=False,
                                    remat="attn=full,mlp=fsdp_only")),
        ("vector/prefetch", dp.with_(remat="attn=full,mlp=save_dots")),
        ("auto_budget", dp.with_(remat="auto:8")),
    ]
    for tag, dcfg in variants:
        loss, grads, plan = run(dcfg)
        if tag == "auto_budget":
            assert plan.memory is not None \
                and plan.memory.peak <= plan.memory.budget_bytes
        np.testing.assert_allclose(loss, ref_l, rtol=2e-5,
                                   err_msg=f"remat_vector/{tag}: loss")
        assert set(grads) == set(ref_g), f"remat_vector/{tag}: grad tree"
        for k, want in ref_g.items():
            np.testing.assert_allclose(
                grads[k], want, rtol=3e-4, atol=3e-6,
                err_msg=f"remat_vector/{tag}: grad mismatch at {k}")
        print(f"PASS remat_vector/{tag} (loss {loss:.4f})")


CASES["remat_vector"] = case_remat_vector


# --------------------------------------------------------------------------
# Context parallelism (core/context.py): zigzag seq sharding + ring
# attention on the ctx axis — cp2 training must reproduce the cp1 baseline
# exactly (explicit collectives only: bucket RS over data x ctx, reverse-
# ring ppermute — exact on every jax version, like `pipeline`).
# --------------------------------------------------------------------------
def case_context():
    """cp2 x dp2 == cp1 x dp4: losses, every assembled gradient, and one
    AdamW step, for a dense arch and gemma2 (sliding window + softcaps —
    the ring's masked-hop path); then the full 4-axis composition
    pp2 x dp2 x cp2 against the pp1 x dp4 baseline."""
    from repro.core import context as CX
    from repro.core.api import parallelize
    from repro.models import runtime as RT
    from repro.models.common import ShapeConfig
    from repro.models.registry import get_arch, get_arch_for_pp
    from repro.optim.adamw import AdamWConfig, init_opt_state

    def cp_cfg(**kw):
        return fp32_cfg(("data", "ctx", "model"), (2, 2, 1),
                        ("data", "ctx"), cp_axis="ctx", **kw)

    def flat_grads(par, dcfg, metas, grads):
        plain = par.unstage_storage(jax.tree.map(np.asarray, grads))
        full = {k: RT.tree_from_storage(plain[k], metas[k], dcfg)
                for k in plain}
        return {jax.tree_util.keystr(p): np.asarray(v) for p, v in
                jax.tree_util.tree_flatten_with_path(full)[0]}

    for arch in ("qwen3_1_7b", "gemma2_27b"):
        cfg, model = get_arch(arch, smoke=True)
        shape = ShapeConfig("t", 32, 8, "train")
        d_ref = fp32_cfg(("data", "model"), (4, 1), ("data",))
        d_cp = cp_cfg()
        batch = _synth_batch(model, shape, d_ref, cfg.vocab)
        full = model.init_full(jax.random.PRNGKey(0), d_ref)

        m_ref = model.metas(d_ref)
        st_ref = {k: RT.tree_to_storage(full[k], m_ref[k], d_ref)
                  for k in full}
        par_ref = parallelize(model, d_ref, shape)
        l_ref, g_ref = par_ref.loss_step()(st_ref, batch)
        f_ref = flat_grads(par_ref, d_ref, m_ref, g_ref)

        m_cp = model.metas(d_cp)
        st_cp = {k: RT.tree_to_storage(full[k], m_cp[k], d_cp)
                 for k in full}
        par_cp = parallelize(model, d_cp, shape)
        assert "cp=2(ring)" in par_cp.plan.describe()
        l_cp, g_cp = par_cp.loss_step()(
            st_cp, CX.zigzag_batch(batch, d_cp))
        f_cp = flat_grads(par_cp, d_cp, m_cp, g_cp)

        tag = f"context/{arch}/cp2_vs_cp1"
        np.testing.assert_allclose(float(l_cp), float(l_ref), rtol=2e-5,
                                   err_msg=f"{tag}: loss mismatch")
        assert set(f_cp) == set(f_ref), f"{tag}: grad tree mismatch"
        for k, want in f_ref.items():
            np.testing.assert_allclose(
                f_cp[k], want, rtol=3e-4, atol=3e-6,
                err_msg=f"{tag}: grad mismatch at {k}")
        print(f"PASS {tag} (loss {float(l_cp):.4f})")

    # one AdamW train step: cp2's metrics and updated weights reproduce the
    # baseline (grad-norm psums span the data x ctx FSDP domain)
    cfg, model = get_arch("qwen3_1_7b", smoke=True)
    shape = ShapeConfig("t", 32, 8, "train")
    d_ref = fp32_cfg(("data", "model"), (4, 1), ("data",))
    d_cp = cp_cfg()
    batch = _synth_batch(model, shape, d_ref, cfg.vocab)
    full = model.init_full(jax.random.PRNGKey(0), d_ref)

    def one_step(dcfg, b):
        metas = model.metas(dcfg)
        st = {k: RT.tree_to_storage(full[k], metas[k], dcfg) for k in full}
        par = parallelize(model, dcfg, shape)
        fn = par.train_step(AdamWConfig(lr=1e-3), donate=False)
        new, _, met = fn(st, init_opt_state(st), b)
        new_full = {k: RT.tree_from_storage(jax.tree.map(np.asarray,
                                                         new[k]),
                                            metas[k], dcfg) for k in new}
        flat = {jax.tree_util.keystr(p): np.asarray(v) for p, v in
                jax.tree_util.tree_flatten_with_path(new_full)[0]}
        return met, flat

    met1, w1 = one_step(d_ref, batch)
    met2, w2 = one_step(d_cp, CX.zigzag_batch(batch, d_cp))
    np.testing.assert_allclose(float(met2["loss"]), float(met1["loss"]),
                               rtol=2e-5, err_msg="context: step loss")
    np.testing.assert_allclose(float(met2["grad_norm"]),
                               float(met1["grad_norm"]), rtol=2e-4,
                               err_msg="context: step grad_norm")
    # atol 1e-5: AdamW's m/sqrt(v) amplifies fp reassociation noise on
    # near-zero-variance coordinates (same magnitude as trainer_pipeline)
    for k in w1:
        np.testing.assert_allclose(w2[k], w1[k], rtol=2e-4, atol=1e-5,
                                   err_msg=f"context: updated weights {k}")
    print("PASS context/train_step (loss+gnorm+updated weights)")

    # ---- the 4-axis composition: pp2 x dp2 x cp2 vs pp1 x dp4 ----
    cfg, model = get_arch_for_pp("qwen3_1_7b", n_stages=2)
    d1 = fp32_cfg(("data", "model"), (4, 1), ("data",))
    dpc = fp32_cfg(("pipe", "data", "ctx", "model"), (2, 2, 2, 1),
                   ("data", "ctx"), cp_axis="ctx", pp_axis="pipe",
                   pp_schedule="1f1b", pp_microbatches=2)
    batch = _synth_batch(model, shape, d1, cfg.vocab)
    full = model.init_full(jax.random.PRNGKey(0), d1)

    m1 = model.metas(d1)
    st1 = {k: RT.tree_to_storage(full[k], m1[k], d1) for k in full}
    par1 = parallelize(model, d1, shape)
    l1, g1 = par1.loss_step()(st1, batch)
    f1 = flat_grads(par1, d1, m1, g1)
    fn1 = par1.train_step(AdamWConfig(lr=1e-3), donate=False)
    new1, _, met1 = fn1(st1, init_opt_state(st1), batch)

    mp = model.metas(dpc)
    parp = parallelize(model, dpc, shape)
    assert parp.plan.pipelined and dpc.cp_size == 2
    stp = parp.stage_storage(
        {k: RT.tree_to_storage(full[k], mp[k], dpc) for k in full})
    bz = CX.zigzag_batch(batch, dpc)
    lp, gp = parp.loss_step()(stp, bz)
    fp_ = flat_grads(parp, dpc, mp, gp)
    tag = "context/pp2_dp2_cp2"
    np.testing.assert_allclose(float(lp), float(l1), rtol=2e-5,
                               err_msg=f"{tag}: loss mismatch")
    for k, want in f1.items():
        np.testing.assert_allclose(fp_[k], want, rtol=3e-4, atol=3e-6,
                                   err_msg=f"{tag}: grad mismatch at {k}")
    fnp = parp.train_step(AdamWConfig(lr=1e-3), donate=False)
    newp, _, metp = fnp(stp, init_opt_state(stp), bz)
    np.testing.assert_allclose(float(metp["loss"]), float(met1["loss"]),
                               rtol=2e-5, err_msg=f"{tag}: step loss")
    np.testing.assert_allclose(float(metp["grad_norm"]),
                               float(met1["grad_norm"]), rtol=2e-4,
                               err_msg=f"{tag}: step grad_norm")
    new_plain = parp.unstage_storage(jax.tree.map(np.asarray, newp))
    for k in new1:
        a = {jax.tree_util.keystr(p): v for p, v in
             jax.tree_util.tree_flatten_with_path(new_plain[k])[0]}
        b = {jax.tree_util.keystr(p): v for p, v in
             jax.tree_util.tree_flatten_with_path(
                 jax.tree.map(np.asarray, new1[k]))[0]}
        for kk in b:
            np.testing.assert_allclose(
                a[kk], b[kk], rtol=2e-4, atol=1e-5,
                err_msg=f"{tag}: updated params mismatch {k}{kk}")
    print(f"PASS {tag} (loss {float(lp):.4f}, AdamW step exact)")


CASES["context"] = case_context


# --------------------------------------------------------------------------
# Quantized collectives (kernels/quant + comm_precision): the wire codec is
# simulated by a local quantize->dequantize roundtrip before each collective,
# so dp4 runs every real code path (bucketed AG encode, RS encode, EF hop).
# --------------------------------------------------------------------------
def case_quant():
    """comm_precision end to end on a dp4 mesh (qwen3_1_7b smoke):
    (a) "bf16" is BIT-exact vs the default config over two chained AdamW
        steps (the identity codec must compile away);
    (b) fp8_ag / fp8 / fp8_ef / auto stay within documented EF-theory
        tolerance of the bf16 reference: losses rtol 5e-2, and per-
        coordinate updated-weight drift <= 4*lr*steps (AdamW's update is
        bounded by ~lr per step, so two quantized steps can disagree with
        the reference by at most ~2*lr per coordinate);
    (c) modes with an RS codec visibly perturb the weights (the codec is
        engaged, not silently skipped), and exactly the needs_ef modes
        carry a persistent error-feedback accumulator in opt_state."""
    from repro.core.api import parallelize
    from repro.models import runtime as RT
    from repro.models.common import ShapeConfig
    from repro.models.registry import get_arch
    from repro.optim.adamw import AdamWConfig, init_opt_state

    cfg, model = get_arch("qwen3_1_7b", smoke=True)
    shape = ShapeConfig("t", 32, 8, "train")
    d_ref = fp32_cfg(("data", "model"), (4, 1), ("data",))
    batch = _synth_batch(model, shape, d_ref, cfg.vocab)
    full = model.init_full(jax.random.PRNGKey(0), d_ref)

    def two_steps(dcfg):
        metas = model.metas(dcfg)
        st = {k: RT.tree_to_storage(full[k], metas[k], dcfg) for k in full}
        par = parallelize(model, dcfg, shape)
        fn = par.train_step(AdamWConfig(lr=1e-3), donate=False)
        opt = init_opt_state(st, dcfg)
        losses = []
        for _ in range(2):
            st, opt, met = fn(st, opt, batch)
            losses.append(float(met["loss"]))
        flat = {jax.tree_util.keystr(p): np.asarray(v) for p, v in
                jax.tree_util.tree_flatten_with_path(st)[0]}
        return losses, flat, opt

    l_ref, w_ref, opt_ref = two_steps(d_ref)
    assert "ef" not in opt_ref

    # ---- (a) explicit bf16 == default path, bit for bit ----
    l_bf, w_bf, opt_bf = two_steps(d_ref.with_(comm_precision="bf16"))
    assert l_bf == l_ref, f"quant/bf16: losses {l_bf} != {l_ref}"
    assert set(w_bf) == set(w_ref)
    for k in w_ref:
        assert np.array_equal(w_bf[k], w_ref[k]), \
            f"quant/bf16: storage leaf {k} not bit-exact"
    assert "ef" not in opt_bf
    print(f"PASS quant/bf16_bit_exact (losses {l_bf})")

    # ---- (b)+(c) quantized modes ----
    lr, steps = 1e-3, 2
    drift_bound = 4.0 * lr * steps
    for mode in ("fp8_ag", "fp8", "fp8_ef", "auto"):
        dq = d_ref.with_(comm_precision=mode)
        l_q, w_q, opt_q = two_steps(dq)
        tag = f"quant/{mode}"
        assert all(np.isfinite(l) for l in l_q), f"{tag}: {l_q}"
        np.testing.assert_allclose(l_q, l_ref, rtol=5e-2,
                                   err_msg=f"{tag}: loss drift")
        worst = max(float(np.max(np.abs(w_q[k] - w_ref[k])))
                    for k in w_ref)
        assert worst <= drift_bound, \
            f"{tag}: weight drift {worst:.2e} > {drift_bound:.2e}"
        if mode in ("fp8", "fp8_ef"):  # RS codec active -> SR perturbs
            assert any(not np.array_equal(w_q[k], w_ref[k])
                       for k in w_ref), f"{tag}: codec silently skipped"
        assert ("ef" in opt_q) == dq.needs_ef, f"{tag}: ef presence"
        if "ef" in opt_q:
            ef_mag = max(float(jnp.max(jnp.abs(l)))
                         for l in jax.tree.leaves(opt_q["ef"]))
            assert ef_mag > 0.0, f"{tag}: EF accumulator never updated"
        print(f"PASS {tag} (losses {l_q}, max drift {worst:.2e})")


CASES["quant"] = case_quant


TRAINER_SMOKE_ARCHS = {
    "trainer_smoke_a": ("deepseek_coder_33b", "phi3_medium_14b",
                        "gemma2_27b", "qwen3_1_7b", "llama3_8b"),
    "trainer_smoke_b": ("qwen2_moe_a2_7b", "qwen3_moe_30b_a3b",
                        "xlstm_1_3b", "seamless_m4t_large_v2",
                        "zamba2_1_2b", "internvl2_26b"),
}


def _case_trainer_smoke(archs):
    """Every registered arch trains 2 steps (incl. a staged checkpoint
    save) through the ONE Trainer on a pp2 x dp2 x tp2 mesh via
    parallelize() — the api_redesign acceptance gate. Smoke (finite,
    recorded losses), not parity: tp=2 grads are version-gated elsewhere."""
    import shutil
    import tempfile

    from repro.models.common import ShapeConfig
    from repro.models.registry import get_arch_for_pp
    from repro.optim.adamw import AdamWConfig
    from repro.train.trainer import Trainer, TrainerConfig

    for i, arch in enumerate(archs):
        cfg, model = get_arch_for_pp(arch, n_stages=2)
        seq = 64 if arch == "seamless_m4t_large_v2" else \
            40 if arch == "internvl2_26b" else 32
        shape = ShapeConfig("t", seq, 8, "train")
        dcfg = fp32_cfg(("pipe", "data", "model"), (2, 2, 2), ("data",),
                        pp_axis="pipe",
                        pp_schedule="1f1b" if i % 2 else "gpipe")
        ckpt_dir = tempfile.mkdtemp(prefix=f"pp_smoke_{arch}_")
        try:
            tcfg = TrainerConfig(total_steps=2, ckpt_every=2, log_every=1,
                                 warmup=1, ckpt_dir=ckpt_dir)
            tr = Trainer(model, dcfg, shape, AdamWConfig(lr=1e-3), tcfg)
            assert tr.plan.pipelined and tr.plan.stage.n_stages == 2
            _, _, hist = tr.run()
            assert hist and all(np.isfinite(h["loss"]) for h in hist), \
                f"{arch}: non-finite loss {hist}"
            assert tr.ckpt.latest_step() == 2, f"{arch}: no staged ckpt"
            print(f"PASS trainer_smoke/{arch} "
                  f"({dcfg.pp_schedule}, loss {hist[-1]['loss']:.4f})")
        finally:
            shutil.rmtree(ckpt_dir, ignore_errors=True)


CASES["trainer_smoke_a"] = \
    lambda: _case_trainer_smoke(TRAINER_SMOKE_ARCHS["trainer_smoke_a"])
CASES["trainer_smoke_b"] = \
    lambda: _case_trainer_smoke(TRAINER_SMOKE_ARCHS["trainer_smoke_b"])


# --------------------------------------------------------------------------
# Serving (core/serving): paged KV decode at tp2 x dp2 — pages sharded over
# the data axis, heads over model.  Two claims:
#   1. paged decode == dense-cache decode BITWISE on the same mesh (the
#      gather path reconstructs the identical logical (B, T, ...) view, so
#      the einsum/softmax work is token-for-token the same computation);
#   2. the tp2 x dp2 pipeline matches the tp1 x dp1 reference within the
#      harness's standard cross-mesh tolerance (psum reassociation makes
#      bitwise cross-mesh equality impossible even for dense prefill),
#      with identical greedy tokens at every step.
# Explicit-collective design (shard_map + check_vma=False).
# --------------------------------------------------------------------------
def case_serving():
    from repro.core.serving import pages as PG
    from repro.models import runtime as RT
    from repro.models.common import ShapeConfig
    from repro.models.registry import get_arch
    from repro.train import serve as SV

    for arch, codec in (("qwen3_1_7b", None), ("qwen3_1_7b", "int8"),
                        ("gemma2_27b", None)):
        cfg, model = get_arch(arch, smoke=True)
        B, prompt, gen, page = 4, 12, 4, 4
        T = prompt + gen
        max_pages = T // page
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, prompt), 3,
                                  cfg.vocab)
        padded = jnp.pad(toks, ((0, 0), (0, gen)), constant_values=3)

        results = {}
        for name, mesh_shape in (("1dev", (1, 1)), ("4dev", (2, 2))):
            dcfg = fp32_cfg(("data", "model"), mesh_shape, ("data",),
                            kv_cache_codec=codec)
            dp = dcfg.dp_total
            n_pages_local = (B // dp) * max_pages + 2
            storage = RT.init_storage(model, jax.random.PRNGKey(0), dcfg)
            params = SV.serve_params_from_storage(model, storage, dcfg)
            pf, mesh = SV.make_prefill_step(
                model, dcfg, ShapeConfig("p", T, B, "prefill"))
            dec, _ = SV.make_decode_step(
                model, dcfg, ShapeConfig("d", T, B, "decode"), mesh=mesh)
            pstep, _ = SV.make_paged_step(
                model, dcfg, ShapeConfig("d", T, B, "decode"), page=page,
                n_pages_local=n_pages_local, max_pages=max_pages,
                mesh=mesh)
            logits, cache = pf(params, {"tokens": padded})
            arena, table, pools = PG.dense_to_pages(
                cache, np.full((B,), prompt), page, n_pages_local,
                max_pages, dp_shards=dp)
            tbl = np.array(table)
            filled = -(-prompt // page)
            for b in range(B):
                ids = pools[b // (B // dp)].alloc(max_pages - filled)
                for j, pid in enumerate(ids):
                    tbl[b, filled + j] = pid
            table = jnp.asarray(tbl)
            tok_d = tok_p = jnp.argmax(logits, -1).astype(jnp.int32)
            step_logits, step_toks = [], []
            for i in range(gen):
                pos = jnp.full((B,), prompt + i, jnp.int32)
                ld, cache = dec(params, cache, tok_d, pos)
                lp, arena = pstep(params, arena, table, tok_p[:, None],
                                  pos[:, None])
                assert np.array_equal(np.asarray(ld), np.asarray(lp)), (
                    f"serving/{arch}/codec={codec}: paged != dense "
                    f"(bitwise) at step {i} on {name}")
                tok_d = jnp.argmax(ld, -1).astype(jnp.int32)
                tok_p = jnp.argmax(lp, -1).astype(jnp.int32)
                step_logits.append(np.asarray(lp))
                step_toks.append(np.asarray(tok_p))
            results[name] = (step_logits, step_toks)

        (l1, t1), (l4, t4) = results["1dev"], results["4dev"]
        for i in range(gen):
            np.testing.assert_allclose(
                l4[i], l1[i], rtol=2e-5, atol=1e-6,
                err_msg=f"serving/{arch}/codec={codec}: step {i} "
                        f"tp2xdp2 vs tp1xdp1 logits")
            assert np.array_equal(t4[i], t1[i]), (
                f"serving/{arch}/codec={codec}: step {i} greedy tokens "
                f"diverged across meshes")
        print(f"PASS serving/{arch}/codec={codec} "
              f"(paged==dense bitwise per mesh; tp2xdp2 ~ tp1xdp1)")


CASES["serving"] = case_serving


if __name__ == "__main__":
    names = sys.argv[1:] or list(CASES)
    for name in names:
        CASES[name]()
    print("ALL OK")
