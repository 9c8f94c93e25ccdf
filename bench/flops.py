"""Operations and bytes, from shapes alone: the model FLOPs a training step
needs, and the bytes a kernel call moves."""

from __future__ import annotations

import re

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s16": 2,
               "u16": 2, "f64": 8, "s64": 8, "u64": 8}


def matmul_params(arch: dict) -> int:
    """Weights that multiply each token once in the forward pass: every
    projection of every layer, and the (tied) head. The embedding lookup
    is a gather, not a product."""
    d, f = arch["hidden_size"], arch["intermediate_size"]
    q = arch["num_attention_heads"] * arch["head_dim"]
    kv = arch["num_key_value_heads"] * arch["head_dim"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return arch["num_hidden_layers"] * per_layer + arch["vocab_size"] * d


def train_flops_per_token(arch: dict, seq_len: int) -> float:
    """Forward and backward FLOPs per trained token, recomputation not
    counted: 6 per matmul weight, plus causal attention's scores and
    weighted sum (2 products of 2 FLOPs per key, over the (S+1)/2 keys a
    query sees on average, times 3 for forward and backward)."""
    attn = (3 * 4 * arch["num_attention_heads"] * arch["head_dim"]
            * arch["num_hidden_layers"] * (seq_len + 1) / 2)
    return 6.0 * matmul_params(arch) + attn


_ARRAY = re.compile(r"\b(" + "|".join(sorted(DTYPE_BYTES, key=len,
                                              reverse=True))
                    + r")\[([0-9,]*)\]")


def array_bytes(text: str) -> int:
    """Bytes of every array type written in `text` (an HLO instruction's
    result and operand types), e.g. 'f32[1024,8]' counts 32768."""
    total = 0
    for dt, dims in _ARRAY.findall(text):
        n = 1
        for x in filter(None, dims.split(",")):
            n *= int(x)
        total += n * DTYPE_BYTES[dt]
    return total
