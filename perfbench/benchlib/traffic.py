"""Training traffic: zipf-distributed token rows, stateless in
(seed, step), so every run with one seed sees the same batches.

Copied from the program's ``training/data.py`` (``SyntheticLM``), so that
a change to the program cannot change what the benchmark feeds it."""
from __future__ import annotations

import hashlib
from typing import Dict

import numpy as np


def _rng_for(seed: int, step: int, shard: int) -> np.random.Generator:
    h = hashlib.blake2b(f"{seed}/{step}/{shard}".encode(), digest_size=8)
    return np.random.default_rng(int.from_bytes(h.digest(), "little"))


class ZipfTokens:
    """Next-token rows of a traffic mix: ``global_batch`` rows of
    ``seq_len`` tokens, ids drawn from zipf(``zipf_a``) and clipped to the
    vocabulary."""

    def __init__(self, mix: dict, vocab_size: int, seed: int):
        self.batch_rows = int(mix["global_batch"])
        self.seq_len = int(mix["seq_len"])
        self.zipf_a = float(mix["zipf_a"])
        self.vocab_size = vocab_size
        self.seed = seed

    @property
    def tokens_per_step(self) -> int:
        return self.batch_rows * self.seq_len

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        rng = _rng_for(self.seed, step, 0)
        toks = rng.zipf(self.zipf_a, size=(self.batch_rows, self.seq_len + 1))
        toks = np.minimum(toks, self.vocab_size - 1).astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
