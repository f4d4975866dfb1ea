"""Seeded host batches of token ids: the generator of the token models.

A traffic file gives ``seq_len`` (a row holds ``seq_len + 1`` ids: inputs and
next-token targets), the per-chip batch in sequences, ``zipf_exponent`` and
``distinct_batches``, the size of the pool the stream cycles through. Ids are
drawn over the vocabulary rows the configuration holds (``model.vocab_held``)
with p ~ rank^-exponent, so that tokens, and with them the experts' loads,
are uneven and a router's rule has work. Each row is one unbroken stream with
no boundary mask. Every row of every batch in the pool differs; the same
seed gives the same stream, another seed other ids in the same sizes.
"""
from __future__ import annotations

from typing import Dict, Iterator

import numpy as np


class Stream:
    """``batch(i)`` is the i-th batch of the stream, whoever asks; iterating
    yields batch(0), batch(1), ... for ever."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        rows = traffic["per_chip_batch"] * traffic["chips"]
        rng = np.random.default_rng([seed, 0x70CE])
        p = np.arange(1, vocab + 1, dtype=np.float64) ** -traffic["zipf_exponent"]
        tokens = rng.choice(vocab, (traffic["distinct_batches"], rows,
                                    traffic["seq_len"] + 1), p=p / p.sum())
        self._pool = [{"tokens": t.astype(np.int32)} for t in tokens]
        self.rows = rows

    def batch(self, i: int) -> Dict[str, np.ndarray]:
        return self._pool[i % len(self._pool)]

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        i = 0
        while True:
            yield self.batch(i)
            i += 1


def make(traffic: dict, config: dict, seed: int) -> Stream:
    return Stream(traffic, config["model"]["vocab_held"], seed)
