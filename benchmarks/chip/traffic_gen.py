"""The one generator of training rows, read by every traffic mix.

A noisy Markov chain over the vocabulary, drawn from the seed: with
probability ``p_det`` the next token is a fixed permutation of the
current one, else uniform. It is a copy of ``repro.data.SyntheticLM``'s
generator, kept here so that no change to the program can change the
benchmark's inputs; every row differs.
"""
from __future__ import annotations

import numpy as np


class MarkovRows:
    def __init__(self, vocab: int, seed: int, p_det: float = 0.9):
        self.vocab = vocab
        self.p_det = p_det
        rng = np.random.default_rng(seed)
        self.perm = rng.permutation(vocab).astype(np.int32)
        self._rng = np.random.default_rng(seed + 1)

    def batch(self, rows: int, seq_len: int) -> np.ndarray:
        rng = self._rng
        out = np.empty((rows, seq_len), np.int32)
        cur = rng.integers(0, self.vocab, rows, dtype=np.int32)
        for t in range(seq_len):
            out[:, t] = cur
            det = rng.random(rows) < self.p_det
            rnd = rng.integers(0, self.vocab, rows, dtype=np.int32)
            cur = np.where(det, self.perm[cur], rnd)
        return out


def batches(traffic: dict, vocab: int, seed: int, n: int):
    """The first ``n`` steps' rows of a traffic mix."""
    gen = MarkovRows(vocab, seed, traffic.get("p_det", 0.9))
    rows = traffic["micro_batches"] * traffic["micro_batch"]
    return [gen.batch(rows, traffic["seq_len"]) for _ in range(n)]
