"""Seeded pretraining token stream: Zipf-distributed token ids, a fresh
batch every step.

Parameters (the traffic file): ``seq`` (positions per row), ``zipf_a`` (the
exponent of the rank-frequency law, token frequency ~ rank ** -a) and
``check_rows`` (distinct rows in the batch the reference is compared on).
Ranks are mapped to ids by a seeded permutation of the published
vocabulary, so the padded rows are never produced.  Labels are the next
token of the same stream.
"""

from __future__ import annotations

import numpy as np


class TokenStream:
    def __init__(self, params: dict, vocab: int, seed: int):
        self.seq = int(params["seq"])
        self.check_rows = int(params.get("check_rows", 2))
        rng = np.random.default_rng([seed, 0x70C5])
        self._ids = rng.permutation(vocab).astype(np.int32)
        w = np.arange(1, vocab + 1, dtype=np.float64) ** -float(params["zipf_a"])
        self._cdf = np.cumsum(w / w.sum())
        self._seed = seed

    def _rows(self, n: int, rng) -> tuple[np.ndarray, np.ndarray]:
        ranks = np.searchsorted(self._cdf, rng.random((n, self.seq + 1)))
        toks = self._ids[np.minimum(ranks, len(self._ids) - 1)]
        return toks[:, :-1].copy(), toks[:, 1:].copy()

    def batch(self, step: int, batch: int) -> tuple[np.ndarray, np.ndarray]:
        """(ids, labels), each (batch, seq) int32, of step ``step``."""
        return self._rows(batch, np.random.default_rng([self._seed, 1, step]))

    def check_batch(self, batch: int):
        """A batch of ``check_rows`` distinct rows tiled to ``batch`` rows:
        its mean loss is the mean over the distinct rows, so the reference
        runs only those.  Returns (ids, labels, distinct ids, distinct
        labels)."""
        n = self.check_rows
        assert batch % n == 0, (batch, n)
        ids, labels = self._rows(n, np.random.default_rng([self._seed, 2]))
        reps = batch // n
        return np.tile(ids, (reps, 1)), np.tile(labels, (reps, 1)), ids, labels


def make(params: dict, *, vocab: int, seed: int, **_) -> TokenStream:
    return TokenStream(params, vocab, seed)
