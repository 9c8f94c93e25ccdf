"""FLOP and byte counts against counts made by hand."""

import bench_smoke  # noqa: F401
from flops import array_bytes, matmul_params, train_flops_per_token


def test_flops_per_token_matches_a_hand_count():
    arch = {"hidden_size": 8, "intermediate_size": 16,
            "num_attention_heads": 2, "num_key_value_heads": 1,
            "head_dim": 4, "vocab_size": 10, "num_hidden_layers": 3}
    # per layer: wq 8x8, wk 8x4, wv 8x4, wo 8x8, three 8x16 MLP matrices
    per_layer = 64 + 32 + 32 + 64 + 3 * 128
    assert matmul_params(arch) == 3 * per_layer + 10 * 8
    seq = 5
    # QK^T and PV: 2 products of 2 FLOPs per key, 3 keys on average per
    # query at seq 5, 2 heads x 4 wide, 3 layers, forward + backward (x3)
    attn = 3 * 2 * 2 * 3 * 2 * 4 * 3
    assert train_flops_per_token(arch, seq) == 6 * (3 * per_layer + 80) \
        + attn


def test_qwen3_1_7b_counts():
    arch = {"hidden_size": 2048, "intermediate_size": 6144,
            "num_attention_heads": 16, "num_key_value_heads": 8,
            "head_dim": 128, "vocab_size": 151936, "num_hidden_layers": 7}
    assert abs(train_flops_per_token(arch, 4096) / 1e9 - 4.33) < 0.01
    arch["num_hidden_layers"] = 28
    assert abs(train_flops_per_token(arch, 4096) / 1e9 - 11.73) < 0.01


def test_array_bytes_reads_hlo_types():
    line = ("(f32[1024]{0}, bf16[8,128]{1,0:T(8,128)}) custom-call("
            "f32[1024]{0} %p, s32[] %i, f32[3]{0} %s)")
    assert array_bytes(line) == 4096 + 2048 + 4096 + 4 + 12
