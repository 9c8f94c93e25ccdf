"""The one traffic generator: batches of packed synthetic documents, made
from a traffic file's parameters and the run's seed.

A batch holds `global_batch` rows of `seq_len` tokens. Tokens follow a Zipf
law over the vocabulary (rank r has weight r ** -zipf_exponent, ids below
`first_token` reserved), documents end at EOS tokens placed at
seq_len // mean_doc_len random cuts per row, and each row opens with BOS.
Targets are the tokens shifted by one; a target is valid unless it is BOS.
This is the statistics of the program's SyntheticC4 stream, drawn here so
that no change to the program can change the work.
"""

from __future__ import annotations

import numpy as np


class PackedDocuments:
    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.t = traffic
        self.seed = seed
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        w = ranks ** -float(traffic["zipf_exponent"])
        self._cdf = np.cumsum(w / w.sum())
        self._vocab = vocab

    @property
    def tokens_per_batch(self) -> int:
        return self.t["global_batch"] * self.t["seq_len"]

    def batch(self, step: int) -> dict[str, np.ndarray]:
        """Batch `step` of this seed's stream: the same (seed, step) gives
        the same batch, and no two steps share a row."""
        t = self.t
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, step]))
        B, S = t["global_batch"], t["seq_len"]
        toks = np.searchsorted(self._cdf, rng.random((B, S + 1)),
                               side="right")
        toks = np.minimum(toks, self._vocab - 1).astype(np.int32)
        toks = np.maximum(toks, t["first_token"])
        n_docs = max(1, (S + 1) // t["mean_doc_len"])
        cuts = rng.integers(1, S, size=(B, n_docs))
        np.put_along_axis(toks, cuts, t["eos"], axis=1)
        toks[:, 0] = t["bos"]
        targets = np.ascontiguousarray(toks[:, 1:])
        return {"tokens": np.ascontiguousarray(toks[:, :-1]),
                "targets": targets,
                "valid": (targets != t["bos"]).astype(np.float32)}
