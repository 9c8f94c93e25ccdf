"""Serving runtime: TP-sharded weight layout, KV-cache/recurrent-state
abstracts, and shard_map-wrapped prefill/decode steps.

Inference keeps weights TP-sharded and FSDP-ungathered-once (gathered at
load; the inference analogue of ``reshard_after_forward=False`` — see
DESIGN.md SSArch-applicability): every param is a stacked TP-local tensor
with spec P(None, ..., 'model' @ tp_dim, ...), replicated over data/pod.
Caches shard batch over the data axes and heads over the model axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.dist import DistConfig, make_mesh
from repro.core.meta import ParamMeta
from repro.kernels.quant import ops as QOPS
from repro.models import runtime as RT
from repro.models.common import ShapeConfig


def _dp_axes(dcfg: DistConfig):
    return tuple(a for a in dcfg.mesh_axes if a != dcfg.tp_axis)


# ---------------------------------------------------------------------------
# Serve parameter layout
# ---------------------------------------------------------------------------
def _serve_spec(m: ParamMeta, dcfg: DistConfig, stacked: bool) -> P:
    dims = [None] * len(m.global_shape)
    if m.tp_dim is not None:
        dims[m.tp_dim] = dcfg.tp_axis
    return P(None, *dims) if stacked else P(*dims)


def _serve_abs(m: ParamMeta, dcfg: DistConfig, stacked: bool, n: int):
    shape = m.global_shape if not stacked else (n, *m.global_shape)
    return jax.ShapeDtypeStruct(shape, dcfg.param_dtype)


def serve_param_specs(model, dcfg: DistConfig):
    metas = model.metas(dcfg)
    sk = RT.stacked_keys(model)
    is_meta = lambda x: isinstance(x, ParamMeta)
    return {
        k: jax.tree.map(lambda m: _serve_spec(m, dcfg, k in sk), metas[k],
                        is_leaf=is_meta)
        for k in metas
    }


def serve_abstract_params(model, dcfg: DistConfig):
    metas = model.metas(dcfg)
    sk = RT.stacked_keys(model)
    is_meta = lambda x: isinstance(x, ParamMeta)
    return {
        k: jax.tree.map(lambda m: _serve_abs(m, dcfg, k in sk, sk.get(k, 0)),
                        metas[k], is_leaf=is_meta)
        for k in metas
    }


def serve_params_from_storage(model, storage, dcfg: DistConfig):
    """Gather-once: training storage -> logical arrays in param_dtype."""
    metas = model.metas(dcfg)
    logical = {k: RT.tree_from_storage(storage[k], metas[k], dcfg)
               for k in storage}
    return jax.tree.map(lambda x: x.astype(dcfg.param_dtype), logical)


# ---------------------------------------------------------------------------
# Cache / recurrent-state abstracts per family
# ---------------------------------------------------------------------------
def _kl_total(cfg, tp):
    """Global head count of the cache: per-rank kl x tp (grouped-kv archs
    store each rank's contiguous slice explicitly — runtime state, not
    params)."""
    lay = cfg.gqa_layout(tp)
    if lay["mode"] == "sharded":
        return cfg.n_kv_heads
    return max(1, lay["kvp"] // tp) * tp


def cache_abstract(model, shape: ShapeConfig, dcfg: DistConfig):
    """(cache_abstract_pytree, cache_specs_pytree) for one decode step."""
    cfg = model.cfg
    tp = dcfg.tp_size
    dp = _dp_axes(dcfg)
    B, T = shape.global_batch, shape.seq_len
    fam = cfg.family

    def kv_pair(t_len, heads):
        spec = P(None, dp, None, dcfg.tp_axis, None)
        codec = dcfg.kv_codec
        if codec:
            q = jax.ShapeDtypeStruct((model.n_steps, B, t_len, heads,
                                      cfg.head_dim), QOPS.kv_wire_dtype(codec))
            sc = jax.ShapeDtypeStruct(
                (model.n_steps, B, t_len, heads,
                 QOPS.kv_chunks(cfg.head_dim)), jnp.float32)
            return ({"k": q, "ks": sc, "v": q, "vs": sc},
                    {"k": spec, "ks": spec, "v": spec, "vs": spec})
        sds = jax.ShapeDtypeStruct((model.n_steps, B, t_len, heads,
                                    cfg.head_dim), dcfg.param_dtype)
        return (sds, sds), (spec, spec)

    if fam in ("dense", "moe", "vlm"):
        heads = _kl_total(cfg, tp)
        a, s = kv_pair(T, heads)
        if cfg.local_global_alternate:   # gemma2 (local, global) pairs
            return (a, a), (s, s)
        return a, s

    if fam == "encdec":
        heads = _kl_total(cfg, tp)
        S_src = T // 2
        self_sds = jax.ShapeDtypeStruct(
            (model.n_dec, B, T, heads, cfg.head_dim), dcfg.param_dtype)
        cross_sds = jax.ShapeDtypeStruct(
            (model.n_dec, B, S_src, heads, cfg.head_dim), dcfg.param_dtype)
        spec = P(None, dp, None, dcfg.tp_axis, None)
        return ({"self": (self_sds, self_sds),
                 "cross": (cross_sds, cross_sds)},
                {"self": (spec, spec), "cross": (spec, spec)})

    if fam == "xlstm":
        H, dk = model.n_heads, model.dk
        dv = dk                       # dv == dk per head
        d = cfg.d_model
        hd = d // H
        K = cfg.ssm_conv
        L = model.n_steps
        di = model.d_inner

        def sds(shape_, spec_):
            return (jax.ShapeDtypeStruct((L, *shape_), jnp.float32), spec_)

        m_abs, m_spec = {}, {}
        for i in range(model.per - 1):
            a = {"C": sds((B, H, dk, dv), P(None, dp, None, None,
                                            dcfg.tp_axis)),
                 "n": sds((B, H, dk), P(None, dp, None, None)),
                 "m": sds((B, H), P(None, dp, None)),
                 "conv": sds((B, K - 1, di), P(None, dp, None, None))}
            m_abs[f"m{i}"] = {k: v[0] for k, v in a.items()}
            m_spec[f"m{i}"] = {k: v[1] for k, v in a.items()}
        s_a = {"h": sds((B, H, hd), P(None, dp, None, None)),
               "c": sds((B, H, hd), P(None, dp, None, None)),
               "n": sds((B, H, hd), P(None, dp, None, None)),
               "m": sds((B, H, hd), P(None, dp, None, None))}
        m_abs["s"] = {k: v[0] for k, v in s_a.items()}
        m_spec["s"] = {k: v[1] for k, v in s_a.items()}
        return m_abs, m_spec

    if fam == "zamba":
        L = cfg.n_layers
        nh, hd, ds = model.nh, model.hd, model.ds
        K = cfg.ssm_conv
        heads = _kl_total(cfg, tp)
        abs_ = {
            "S": jax.ShapeDtypeStruct((L, B, nh, hd, ds), jnp.float32),
            "conv_x": jax.ShapeDtypeStruct((L, B, K - 1, nh * hd),
                                           jnp.float32),
            "conv_bc": jax.ShapeDtypeStruct((L, B, K - 1, 2 * ds),
                                            jnp.float32),
            "sh_kv": tuple(
                (jax.ShapeDtypeStruct((B, T, heads, cfg.head_dim),
                                      dcfg.param_dtype),) * 2
                for _ in range(model.n_super)),
        }
        spec = {
            "S": P(None, dp, dcfg.tp_axis, None, None),
            "conv_x": P(None, dp, None, dcfg.tp_axis),
            "conv_bc": P(None, dp, None, None),
            "sh_kv": tuple(
                (P(dp, None, dcfg.tp_axis, None),) * 2
                for _ in range(model.n_super)),
        }
        return abs_, spec

    raise ValueError(fam)


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------
def make_decode_step(model, dcfg: DistConfig, shape: ShapeConfig, mesh=None):
    mesh = mesh or make_mesh(dcfg)
    dp = _dp_axes(dcfg)
    _, cache_specs = cache_abstract(model, shape, dcfg)

    def step(params, cache, tok, pos):
        logits, cache = model.decode_local(params, cache, tok, pos, dcfg)
        return logits, cache

    in_specs = (serve_param_specs(model, dcfg), cache_specs, P(dp), P(dp))
    out_specs = (P(dp, dcfg.tp_axis), cache_specs)
    return jax.jit(shard_map(step, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False),
                   donate_argnums=(1,)), mesh


def make_prefill_step(model, dcfg: DistConfig, shape: ShapeConfig,
                      mesh=None):
    mesh = mesh or make_mesh(dcfg)
    dp = _dp_axes(dcfg)

    def step(params, batch):
        return model.prefill_local(params, batch, dcfg)

    batch_specs = {}
    for k, sds in model.input_specs(shape, dcfg).items():
        batch_specs[k] = P(dp, *([None] * (len(sds.shape) - 1)))
    # cache out specs are family-shaped; infer from a decode-cache template
    _, cache_specs = cache_abstract(model, shape, dcfg)
    out_specs = (P(dp, dcfg.tp_axis), _prefill_cache_specs(model, dcfg,
                                                           cache_specs))
    in_specs = (serve_param_specs(model, dcfg), batch_specs)
    return jax.jit(shard_map(step, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)), mesh


def _prefill_cache_specs(model, dcfg, decode_specs):
    """Prefill emits the same pytree as decode consumes (specs identical)."""
    return decode_specs


def decode_inputs_abstract(model, shape: ShapeConfig, dcfg: DistConfig):
    B = shape.global_batch
    cache_abs, _ = cache_abstract(model, shape, dcfg)
    return {
        "params": serve_abstract_params(model, dcfg),
        "cache": cache_abs,
        "tok": jax.ShapeDtypeStruct((B,), jnp.int32),
        "pos": jax.ShapeDtypeStruct((B,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# Paged decode/chunked-prefill step (core.serving arena layout)
# ---------------------------------------------------------------------------
def paged_abstracts(model, shape: ShapeConfig, dcfg: DistConfig, *,
                    page: int, n_pages_local: int, max_pages: int):
    """(arena_abs, arena_specs, table_abs, table_spec) for a paged step."""
    from repro.core.serving import pages as PG
    cache_abs, cache_specs = cache_abstract(model, shape, dcfg)
    arena_abs, arena_specs = PG.arena_abstract(
        cache_abs, cache_specs, n_pages_local, page, dcfg.dp_total)
    dp = _dp_axes(dcfg)
    table_abs = jax.ShapeDtypeStruct(
        (shape.global_batch, max_pages), jnp.int32)
    return arena_abs, arena_specs, table_abs, P(dp)


def make_paged_step(model, dcfg: DistConfig, shape: ShapeConfig, *,
                    page: int, n_pages_local: int, max_pages: int,
                    chunk: int = 1, mesh=None):
    """Jitted paged step over (params, arena, table, toks, qpos).

    chunk=1 is one decode step; chunk>1 runs one chunked-prefill slab
    through the same kernel.  toks/qpos are (B, chunk); the table holds
    LOCAL page ids (each data shard allocates from its own pool)."""
    if not getattr(model, "paged_kv", False):
        raise ValueError(
            f"{model.cfg.family}: no paged decode path (see plan_serve)")
    mesh = mesh or make_mesh(dcfg)
    dp = _dp_axes(dcfg)
    _, arena_specs, _, table_spec = paged_abstracts(
        model, shape, dcfg, page=page, n_pages_local=n_pages_local,
        max_pages=max_pages)

    def step(params, arena, table, toks, qpos):
        return model.paged_step_local(params, arena, table, toks, qpos,
                                      dcfg, page=page)

    in_specs = (serve_param_specs(model, dcfg), arena_specs, table_spec,
                P(dp), P(dp))
    out_specs = (P(dp, dcfg.tp_axis), arena_specs)
    return jax.jit(shard_map(step, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False),
                   donate_argnums=(1,)), mesh
