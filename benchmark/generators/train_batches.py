"""Training batches from a seeded host generator: a new batch of token
ids every step, Zipf-distributed over the vocabulary so that fresh
batches still have something to learn (the unigram distribution).

Every seed gives the same shapes and the same distribution; the seed
chooses which ids are the frequent ones and draws the tokens.
"""

from __future__ import annotations

import numpy as np


class Batches:
    def __init__(self, params: dict, seed: int, *, global_batch: int,
                 seq_len: int, vocab_size: int):
        self.shape = (global_batch, seq_len)
        self.rng = np.random.default_rng([seed, 0x7b1])
        weights = 1.0 / np.arange(1, vocab_size + 1) ** params[
            "zipf_exponent"]
        self.cdf = np.cumsum(weights / weights.sum())
        self.ids = self.rng.permutation(vocab_size).astype(np.int32)

    def __iter__(self):
        return self

    def __next__(self) -> np.ndarray:
        ranks = np.searchsorted(self.cdf, self.rng.random(self.shape))
        return self.ids[np.minimum(ranks, len(self.ids) - 1)]


def make(params: dict, seed: int, **shapes) -> Batches:
    return Batches(params, seed, **shapes)
