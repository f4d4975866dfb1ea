"""Seeded host batches for the block-diffusion objective: the generator of
the sdar_moe family.

A traffic file gives ``seq_len`` (a row holds ``seq_len`` ids: a position
predicts its own id, so there is no target beside them), the per-chip batch
in sequences, ``zipf_exponent`` and ``distinct_batches``, the size of the
pool the stream cycles through. A batch is three leaves: ``tokens`` int32
[rows, seq_len], ids over the vocabulary rows below the configuration's
``model.mask_token_held`` with p ~ rank^-exponent (the row that stands for a
masked id is never data); ``t`` float32 [rows, seq_len / block_length], a
diffusion block's noise level, uniform on [noise_eps, 1]; ``masked`` uint8 as
``tokens``, 1 with probability t of the id's block (the linear schedule of
arXiv:2503.09573). Every row of every batch in the pool differs; the same
seed gives the same stream, another seed other ids and another noising in
the same sizes.
"""
from __future__ import annotations

import numpy as np

from benchmark.generators import token_stream


class Stream(token_stream.Stream):
    """``token_stream.Stream`` (``batch(i)``, iteration) over a pool of
    three-leaf batches."""

    def __init__(self, traffic: dict, model: dict, seed: int):
        rows = traffic["per_chip_batch"] * traffic["chips"]
        pool, length = traffic["distinct_batches"], traffic["seq_len"]
        block, eps, vocab = model["block_length"], model["noise_eps"], model["mask_token_held"]
        rng = np.random.default_rng([seed, 0xB10C])
        p = np.arange(1, vocab + 1, dtype=np.float64) ** -traffic["zipf_exponent"]
        tokens = rng.choice(vocab, (pool, rows, length), p=p / p.sum()).astype(np.int32)
        t = (eps + (1.0 - eps) * rng.random((pool, rows, length // block))).astype(np.float32)
        masked = rng.random((pool, rows, length), np.float32) < np.repeat(t, block, axis=-1)
        self._pool = [{"tokens": tokens[i], "masked": masked[i].astype(np.uint8), "t": t[i]}
                      for i in range(pool)]
        self.rows = rows


def make(traffic: dict, config: dict, seed: int) -> Stream:
    return Stream(traffic, config["model"], seed)
