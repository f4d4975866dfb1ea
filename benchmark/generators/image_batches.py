"""Seeded host batches of images with labels: the one general generator.

A traffic file gives the parameters: ``dtype`` (``uint8`` crops as a decode
pool yields them, uniform over 0..255; ``float32`` standard-normal tensors
as ``data/synthetic.py`` makes them — copied from there, with the one change
that the batches differ), the image size, the per-chip batch and
``distinct_batches``, the size of the pool the stream cycles through.
Every row of every batch in the pool differs. The same seed gives the same
stream; another seed gives other pixels and labels in the same sizes, so
the work does not depend on the seed.
"""
from __future__ import annotations

from typing import Dict, Iterator

import numpy as np


class Stream:
    """``batch(i)`` is the i-th batch of the stream, whoever asks; iterating
    yields batch(0), batch(1), ... for ever."""

    def __init__(self, traffic: dict, num_classes: int, seed: int):
        rows = traffic["per_chip_batch"] * traffic["chips"]
        size, n = traffic["image_size"], traffic["distinct_batches"]
        rng = np.random.default_rng([seed, 0x1A6E])
        shape = (n, rows, size, size, 3)
        if traffic["dtype"] == "uint8":
            images = rng.integers(0, 256, shape, dtype=np.uint8)
        elif traffic["dtype"] == "float32":
            images = rng.standard_normal(shape, dtype=np.float32)
        else:
            raise ValueError(f"no generator for dtype {traffic['dtype']!r}")
        labels = rng.integers(0, num_classes, (n, rows)).astype(np.int32)
        self._pool = [{"images": images[i], "labels": labels[i]} for i in range(n)]
        self.rows = rows

    def batch(self, i: int) -> Dict[str, np.ndarray]:
        return self._pool[i % len(self._pool)]

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        i = 0
        while True:
            yield self.batch(i)
            i += 1


def make(traffic: dict, config: dict, seed: int) -> Stream:
    return Stream(traffic, config["model"]["num_classes"], seed)
