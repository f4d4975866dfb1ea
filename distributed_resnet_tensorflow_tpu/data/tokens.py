"""Seeded token streams packed to fixed sequences — the token models' input.

A batch is ``{"tokens": int32 [B, seq_len + 1]}``: the model reads all but
the last id of a row as inputs and all but the first as next-token targets
(models/transformer.NextTokenObjective). Documents of heavy-tailed length
are drawn from the seed (ids ~ rank^-0.7 over the vocabulary rows held,
an end-of-document id 0 between them) and PACKED end to end into rows with
no padding and no boundary mask: a row is one unbroken stream, attention
crosses document boundaries (document masks in the kernel are not built).
Batches go through the same ``CoalescedStager`` / ``device_prefetch`` as
any other (train/loop.py); the packing of one batch is the span
``input.tokens``.

The block-diffusion models' batch (models/transformer.
BlockDiffusionObjective) is a row of ``seq_len`` ids and its noising, drawn
on the host (``block_diffusion_noise``, span ``input.noise``): ``{"tokens":
int32 [B, seq_len], "masked": uint8 [B, seq_len], "t": float32
[B, seq_len / block]}``.
"""
from __future__ import annotations

from typing import Dict, Iterator

import numpy as np

from ..telemetry.tracer import span

#: the id that closes a document
END_OF_DOCUMENT = 0
#: p(id) ~ rank^-exponent over the ids held
ZIPF_EXPONENT = 0.7


def token_stream_iterator(batch_size: int, seq_len: int, vocab: int,
                          seed: int = 0, mean_document: int = 1024
                          ) -> Iterator[Dict[str, np.ndarray]]:
    """Yields packed batches for ever; the same seed gives the same stream."""
    rng = np.random.default_rng([seed, 0x70CE])
    p = np.arange(1, vocab, dtype=np.float64) ** -ZIPF_EXPONENT
    p /= p.sum()
    row = seq_len + 1
    carry = np.zeros((0,), np.int32)
    while True:
        with span("input.tokens"):
            need = batch_size * row
            parts, have = [carry], carry.shape[0]
            while have < need:
                # log-normal lengths: most documents short, a few very long
                n = max(1, int(rng.lognormal(np.log(mean_document), 1.0)))
                doc = rng.choice(vocab - 1, n, p=p).astype(np.int32) + 1
                parts += [doc, np.full((1,), END_OF_DOCUMENT, np.int32)]
                have += n + 1
            stream = np.concatenate(parts)
            carry = stream[need:]
            batch = {"tokens": stream[:need].reshape(batch_size, row)}
        yield batch


def block_diffusion_noise(tokens: np.ndarray, rng: np.random.Generator,
                          block: int, eps: float):
    """The linear schedule of arXiv:2503.09573 over diffusion blocks of
    ``block`` ids: a block's level t is uniform on [eps, 1], an id of the
    block is masked with probability t (and its term of the loss weighs
    1/t). Returns (masked uint8 as ``tokens``, t float32 (rows, blocks))."""
    rows, length = tokens.shape
    if length % block:
        raise ValueError(f"rows of {length} ids are no whole number of "
                         f"diffusion blocks of {block}")
    t = (eps + (1.0 - eps) * rng.random((rows, length // block))).astype(
        np.float32)
    masked = rng.random((rows, length), np.float32) < np.repeat(t, block, axis=1)
    return masked.astype(np.uint8), t


def block_diffusion_iterator(batch_size: int, seq_len: int, vocab: int,
                             block: int, eps: float, seed: int = 0
                             ) -> Iterator[Dict[str, np.ndarray]]:
    """``token_stream_iterator``'s rows cut to ``seq_len`` ids (no target
    beside them: a position predicts its own id) over ids below ``vocab``,
    each batch with its noising; the same seed gives the same stream."""
    rng = np.random.default_rng([seed, 0xB10C])
    for batch in token_stream_iterator(batch_size, seq_len - 1, vocab, seed):
        with span("input.noise"):
            masked, t = block_diffusion_noise(batch["tokens"], rng, block, eps)
        yield {"tokens": batch["tokens"], "masked": masked, "t": t}
