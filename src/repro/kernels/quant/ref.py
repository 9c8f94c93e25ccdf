"""Reference (pure-jnp) per-chunk quantization codec for wire compression.

Codec: the flat bucket buffer is chunked into QCHUNK=128-element groups;
each chunk carries one f32 absmax-derived scale plus one byte per element
(fp8 e4m3 or int8).  Wire bytes = n + 4*ceil(n/128), i.e. 0.516x of bf16
for LANE-aligned buckets — the figure the planner prices.

Encode is round-to-nearest for params (forward all-gather: deterministic,
bit-identical across ranks) and stochastic for grads (reduce-scatter:
unbiased, the condition Markov et al.'s EF convergence analysis needs).
Stochastic rounding is hand-rolled, so the Pallas kernel and this reference
share it bit for bit: fp8 adds a 20-bit uniform dither below e4m3's 3 retained mantissa bits in
the f32 bit pattern and truncates; int8 uses floor(y + u).  The dither is
an integer hash of (seed + flat index); the seed is the wraparound u32 sum
of the buffer's own bits — data-dependent yet trace-safe, so no PRNG key
threads through the gather custom_vjp.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QCHUNK = 128          # elements per scale group (= flat-shard storage LANE)
SCALE_BYTES = 4       # one f32 scale per chunk rides along on the wire
QMAX = {"fp8": 448.0, "int8": 127.0}
WIRE_DTYPE = {"fp8": jnp.float8_e4m3fn, "int8": jnp.int8}
CODECS = tuple(QMAX)


def hash_u32(idx: jax.Array, seed: jax.Array) -> jax.Array:
    """Cheap integer mix (Knuth multiplicative + xor-shift avalanche)."""
    h = seed + idx * jnp.uint32(2654435761)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x45D9F3B)
    return h ^ (h >> 16)


def buffer_seed(x2: jax.Array) -> jax.Array:
    """Wraparound u32 sum of the buffer's bits: a trace-safe, data-dependent
    dither seed (|1 so an all-zero buffer still dithers)."""
    bits = jax.lax.bitcast_convert_type(x2.astype(jnp.float32), jnp.uint32)
    return jnp.sum(bits, dtype=jnp.uint32) | jnp.uint32(1)


def sr_fp8(y: jax.Array, h: jax.Array) -> jax.Array:
    """Stochastic-round f32 (pre-clipped to +-448) to e4m3: add a 20-bit
    uniform dither below the 3 retained mantissa bits, truncate, cast.
    Carries into the exponent are correct SR at binade boundaries; the e4m3
    subnormal range re-rounds deterministically on cast (negligible mass)."""
    bits = jax.lax.bitcast_convert_type(y, jnp.uint32)
    sign = bits & jnp.uint32(0x80000000)
    mag = bits & jnp.uint32(0x7FFFFFFF)
    mag = (mag + (h >> 12)) & jnp.uint32(0xFFF00000)
    z = jax.lax.bitcast_convert_type(sign | mag, jnp.float32)
    return jnp.clip(z, -448.0, 448.0).astype(jnp.float8_e4m3fn)


def sr_int8(y: jax.Array, h: jax.Array) -> jax.Array:
    # (h >> 8) fits in 24 bits: the int32 hop is exact, and Mosaic has no
    # uint32 -> float32 cast
    u = (h >> 8).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 24))
    return jnp.clip(jnp.floor(y + u), -127.0, 127.0).astype(jnp.int8)


def chunk(x: jax.Array) -> tuple[jax.Array, int]:
    """Flatten + zero-pad to the (n_chunks, QCHUNK) f32 view the codec
    quantizes over. Returns (view, original element count)."""
    n = x.size
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-n) % QCHUNK
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, QCHUNK), n


def chunk_scales(x2: jax.Array, codec: str) -> jax.Array:
    """Per-chunk f32 scale: absmax / QMAX, with 1.0 guarding all-zero
    chunks (and the zero padding `chunk` appends).  Computed as a multiply
    by the reciprocal so the Pallas kernel and this reference produce
    bit-identical scales (a divide by a non-power-of-two constant is
    strength-reduced differently across backends)."""
    absmax = jnp.max(jnp.abs(x2), axis=1, keepdims=True)
    return jnp.where(absmax > 0, absmax * (1.0 / QMAX[codec]), 1.0)


def encode_chunks(x2: jax.Array, scale: jax.Array, codec: str,
                  stochastic: bool, seed: jax.Array | None = None):
    """Quantize a pre-chunked (m, QCHUNK) f32 view against `scale`."""
    qmax = QMAX[codec]
    y = jnp.clip(x2 / scale, -qmax, qmax)
    if stochastic:
        if seed is None:
            seed = buffer_seed(x2)
        idx = jnp.arange(x2.size, dtype=jnp.uint32).reshape(x2.shape)
        h = hash_u32(idx, seed)
        return sr_fp8(y, h) if codec == "fp8" else sr_int8(y, h)
    if codec == "fp8":
        return y.astype(jnp.float8_e4m3fn)
    return jnp.clip(jnp.round(y), -127.0, 127.0).astype(jnp.int8)


def quantize(x: jax.Array, codec: str = "fp8", stochastic: bool = False):
    """-> (q, scales): wire values (n_chunks, QCHUNK) in e4m3/int8
    (zero-padded past x.size) and f32 scales (n_chunks, 1)."""
    x2, _ = chunk(x)
    scale = chunk_scales(x2, codec)
    return encode_chunks(x2, scale, codec, stochastic), scale


def dequantize(q: jax.Array, scales: jax.Array, n: int, shape, dtype):
    """Inverse of `quantize`: wire values + scales back to the original
    shape/dtype."""
    x = q.astype(jnp.float32) * scales
    return x.reshape(-1)[:n].reshape(shape).astype(dtype)


def roundtrip(x: jax.Array, codec: str | None = "fp8",
              stochastic: bool = False) -> jax.Array:
    """quantize -> dequantize in one call — numerically identical to
    sending `x` over the wire in `codec` and decoding on the receiver
    (dequant commutes with gather/direct-reduce, so quantizing each
    contribution once before the existing collective reproduces the
    wire-quantized result exactly)."""
    if codec is None:
        return x
    q, s = quantize(x, codec, stochastic)
    return dequantize(q, s, x.size, x.shape, x.dtype)
