"""Plain float32 Qwen3 decoder, its loss and AdamW: the yardstick that the
benchmark's `correct` holds the program's training step to.

Written from the published architecture (Qwen/Qwen3-1.7B `config.json`):
RMSNorm pre-norm blocks, grouped-query attention with RMSNorm on each query
and key head, rotary positions (rotate-half, base `rope_theta`), a SwiGLU
MLP, a final RMSNorm and a head tied to the embedding. Packed documents see
each other (plain causal attention, no document mask) and the loss is the
mean token cross-entropy over the positions the batch marks valid.

It imports nothing of the program under test and takes nothing the program
made: the weights come from `init_params` below, from the seed alone. Its
parameter tree has the program's plain (checkpoint) layout, so one set of
weights can be handed to both. Every matrix product runs at
`jax.default_matmul_precision("highest")` unless `matmul` says otherwise,
which is how the control (the same arithmetic in a lower precision) is made.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

INIT_STD = 0.02        # Qwen3 `initializer_range`
Q_CHUNK = 512          # attention rows per block: bounds the score matrix
LOSS_CHUNK = 1024      # rows per block of the (rows, vocab) logits


def seed_words(seed: int) -> np.ndarray:
    """Threefry key data from all the bits of a (possibly > 32-bit) seed;
    `jax.random.wrap_key_data` makes the key."""
    return np.random.SeedSequence([seed, 0]).generate_state(2)


def param_shapes(arch: dict) -> dict:
    """The parameter tree's shapes, in the program's plain layout: layer
    leaves stacked on a leading axis; wk/wv as (kv*hd, d), the others as
    (in, out)."""
    d, f = arch["hidden_size"], arch["intermediate_size"]
    h, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd, L = arch["head_dim"], arch["num_hidden_layers"]
    return {
        "embed": (arch["vocab_size"], d),
        "blocks": {
            "ln1": (L, d),
            "attn": {"wq": (L, d, h * hd), "wk": (L, kv * hd, d),
                     "wv": (L, kv * hd, d), "wo": (L, h * hd, d),
                     "q_norm": (L, hd), "k_norm": (L, hd)},
            "ln2": (L, d),
            "mlp": {"wg": (L, d, f), "wu": (L, d, f), "wd": (L, f, d)},
        },
        "final_norm": (d,),
    }


def _is_shape(x):
    return isinstance(x, tuple)


def leaf_names(arch: dict) -> list[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(param_shapes(arch),
                                                   is_leaf=_is_shape)
    return [jax.tree_util.keystr(p, simple=True, separator="/")
            for p, _ in flat]


def init_params(arch: dict, key, dtype=jnp.float32) -> dict:
    """Weights from the key: N(0, 0.02) matrices and unit norm scales.
    Leaf i draws from fold_in(key, i) in `leaf_names` order."""
    flat, tree = jax.tree_util.tree_flatten(param_shapes(arch),
                                            is_leaf=_is_shape)
    names = leaf_names(arch)
    out = []
    for i, (name, shape) in enumerate(zip(names, flat)):
        if name.endswith("norm") or name.endswith(("ln1", "ln2")):
            out.append(jnp.ones(shape, dtype))
        else:
            out.append((INIT_STD * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)).astype(dtype))
    return jax.tree_util.tree_unflatten(tree, out)


# --------------------------------------------------------------- model --
def _mm(spec, a, b, matmul):
    return matmul(spec, a, b) if matmul is not None else \
        jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)


def rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w


def rope(x, theta):
    """x: (B, S, H, hd), rotate-half convention, positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(q, k, v, matmul):
    """Causal grouped-query attention, one block of Q_CHUNK query rows at a
    time (each block recomputed in the backward pass)."""
    B, S, H, hd = q.shape
    kvh = k.shape[2]
    g = H // kvh
    qc = min(Q_CHUNK, S)
    assert S % qc == 0, (S, qc)
    qb = (q / math.sqrt(hd)).reshape(B, S // qc, qc, kvh, g, hd)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(args):
        qi, i = args                                 # (B, qc, kvh, g, hd)
        s = _mm("bqkgh,btkh->bkgqt", qi, k, matmul)
        qpos = i * qc + jnp.arange(qc)
        s = jnp.where((qpos[:, None] >= kpos[None, :])[None, None, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("bkgqt,btkh->bqkgh", p, v, matmul)

    out = lax.map(block, (jnp.moveaxis(qb, 1, 0), jnp.arange(S // qc)))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, hd)


def layer(arch, x, p, matmul=None):
    eps = arch["rms_norm_eps"]
    B, S, _ = x.shape
    H, kvh = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd = arch["head_dim"]
    a = p["attn"]
    h = rmsnorm(x, p["ln1"], eps)
    q = _mm("bsd,dh->bsh", h, a["wq"], matmul).reshape(B, S, H, hd)
    k = _mm("bsd,hd->bsh", h, a["wk"], matmul).reshape(B, S, kvh, hd)
    v = _mm("bsd,hd->bsh", h, a["wv"], matmul).reshape(B, S, kvh, hd)
    q = rope(rmsnorm(q, a["q_norm"], eps), arch["rope_theta"])
    k = rope(rmsnorm(k, a["k_norm"], eps), arch["rope_theta"])
    o = attention(q, k, v, matmul).reshape(B, S, H * hd)
    x = x + _mm("bsh,hd->bsd", o, a["wo"], matmul)
    m = p["mlp"]
    h = rmsnorm(x, p["ln2"], eps)
    gate = _mm("bsd,df->bsf", h, m["wg"], matmul)
    up = _mm("bsd,df->bsf", h, m["wu"], matmul)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, m["wd"], matmul)


def loss_fn(arch, params, batch, matmul=None):
    """Mean cross-entropy over valid positions; each layer and each block
    of logits is recomputed in the backward pass to bound memory."""
    tokens, targets = batch["tokens"], batch["targets"]
    valid = batch["valid"].astype(jnp.float32)
    x = jnp.take(params["embed"], tokens, axis=0)
    x, _ = lax.scan(
        lambda c, p: (jax.checkpoint(
            lambda cc, pp: layer(arch, cc, pp, matmul))(c, p), None),
        x, params["blocks"])
    x = rmsnorm(x, params["final_norm"], arch["rms_norm_eps"])
    d = x.shape[-1]
    rows = x.reshape(-1, d)
    tg, vd = targets.reshape(-1), valid.reshape(-1)
    n = rows.shape[0]
    rc = min(LOSS_CHUNK, n)
    assert n % rc == 0, (n, rc)

    @jax.checkpoint
    def block(args):
        r, t, w = args
        logits = _mm("rd,vd->rv", r, params["embed"], matmul)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - tl) * w)

    per = lax.map(block, (rows.reshape(-1, rc, d), tg.reshape(-1, rc),
                          vd.reshape(-1, rc)))
    return per.sum() / jnp.maximum(vd.sum(), 1.0)


# ----------------------------------------------------------- optimizer --
def learning_rate(opt: dict, t):
    """Cosine decay from `lr` to `lr * floor_frac` over `total_steps`, after
    a linear warm-up of `warmup` steps; `t` counts steps taken before."""
    t = jnp.asarray(t, jnp.float32)
    peak, warm = opt["lr"], opt["warmup"]
    prog = jnp.clip((t - warm) / max(opt["total_steps"] - warm, 1), 0, 1)
    cos = peak * (opt["floor_frac"] + (1 - opt["floor_frac"])
                  * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
    return jnp.where(t < warm, peak * t / max(warm, 1), cos)


def adamw(opt: dict, params, grads, m, v, t):
    """One AdamW step (global-norm clipping, bias correction, decoupled
    weight decay on every leaf). `t` counts steps taken before this one.
    Returns (params, m, v, the clipped gradient)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-12))
    lr = learning_rate(opt, t)
    b1, b2 = opt["b1"], opt["b2"]
    n = jnp.asarray(t + 1, jnp.float32)
    g = jax.tree.map(lambda x: x * scale, grads)
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
    params = jax.tree.map(
        lambda p, mm, vv: p - lr * ((mm / (1 - b1 ** n))
                                    / (jnp.sqrt(vv / (1 - b2 ** n))
                                       + opt["eps"])
                                    + opt["weight_decay"] * p),
        params, m, v)
    return params, m, v, g
