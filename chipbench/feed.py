"""The benchmark's token generator: one general source for every training
traffic file.

A traffic file gives the rows per step (``batch_per_chip`` times the chips),
the sequence length and the law the token ids follow. Ids follow a Zipf law
of the file's exponent over the configuration's vocabulary, with the ranks
permuted by the seed: natural text is Zipfian, and uniform ids would spread
a router's load more evenly than text does. Each row holds ``seq_len + 1``
draws; the first ``seq_len`` are the tokens and the last ``seq_len`` the
labels. Step ``k`` of seed ``s`` always gives the same rows, and no two
steps share a row.

``batch(step)`` is what the trainer calls. It first calls ``on_call`` (the
harness's clock), then makes the rows inside a profiler span named
``feed.batch`` so that idle gaps in a device trace can be laid to it.
"""

from __future__ import annotations

import jax
import numpy as np


class Feed:
    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.rows = traffic["batch_per_chip"] * traffic["chips"]
        self.seq = traffic["seq_len"]
        self.vocab = vocab
        law = traffic["token_law"]
        if law["kind"] != "zipf":
            raise ValueError(f"unknown token law {law['kind']!r}")
        weights = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** law["exponent"]
        self._cdf = np.cumsum(weights) / weights.sum()
        self.on_call = None
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        self.seed = seed
        self._ids = np.random.default_rng([seed, 0]).permutation(self.vocab).astype(np.int32)

    @property
    def tokens_per_step(self) -> int:
        return self.rows * self.seq

    def make(self, step: int) -> dict[str, np.ndarray]:
        rng = np.random.default_rng([self.seed, 1, step])
        ranks = np.searchsorted(self._cdf, rng.random((self.rows, self.seq + 1)))
        rows = self._ids[np.minimum(ranks, self.vocab - 1)]
        return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}

    def batch(self, step: int) -> dict[str, np.ndarray]:
        if self.on_call is not None:
            self.on_call(step)
        with jax.profiler.TraceAnnotation("feed.batch"):
            return self.make(step)
