"""Seeded host batches of token ids ``[rows, seq_len + 2]`` over the slice
of the vocabulary the configuration holds: a pool the stream cycles through,
every row different, the same sizes for every seed."""
from __future__ import annotations

import numpy as np


class Stream:
    def __init__(self, traffic: dict, vocab: int, seed: int):
        rows = traffic["per_chip_batch"] * traffic["chips"]
        rng = np.random.default_rng([seed, 0x70C5])
        # a skewed draw, so that the counts differ and the rule has work
        p = np.arange(1, vocab + 1, dtype=np.float64) ** -0.7
        tokens = rng.choice(vocab, (traffic["distinct_batches"], rows,
                                    traffic["seq_len"] + 2), p=p / p.sum())
        self._pool = [{"tokens": t.astype(np.int32)} for t in tokens]
        self.rows = rows

    def batch(self, i: int):
        return self._pool[i % len(self._pool)]

    def __iter__(self):
        i = 0
        while True:
            yield self.batch(i)
            i += 1


def make(traffic: dict, config: dict, seed: int) -> Stream:
    return Stream(traffic, config["model"]["vocab_held"], seed)
