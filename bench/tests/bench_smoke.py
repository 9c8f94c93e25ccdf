"""A benchmark cell cut to a size the CPU test suite holds: the cell's own
files, with the published widths replaced by the program's smoke widths and
the sequence cut to 64. Everything else (the limits, the optimizer, the
traffic's statistics, the mesh) is the cell's own."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import spec  # noqa: E402

ARCH = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 256, "num_hidden_layers": 2, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-5}
PROGRAM = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
           "d_ff": 128, "vocab": 256, "head_dim": 16, "rope_theta": 10000.0,
           "norm_eps": 1e-5, "pad_to": 4}
SEQ = 64


def cell(name: str) -> dict:
    loaded = spec.cell(name)
    loaded["config"].update(ARCH)
    loaded["config"]["program"]["replace"] = PROGRAM
    loaded["traffic"].update(seq_len=SEQ, mean_doc_len=16)
    return loaded


# the cells the tests drive, by a short key
CELLS = {"l7": "train.qwen3_1_7b-l7.s4096.uniform",
         "l7x4": "train.qwen3_1_7b-l7.s1024x4.uniform"}


def get(key: str) -> dict:
    """The test cell `key` at smoke size."""
    return cell(CELLS[key])
