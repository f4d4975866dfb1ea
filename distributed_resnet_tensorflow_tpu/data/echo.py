"""Data echoing over a bounded decoded-sample host cache.

BENCH_r05 measured the regime this module exists for: the device trains
ImageNet RN50 at 2691 img/s while a single host core decodes ~220-350
JPEG img/s — the input-bound regime where "Massively Distributed SGD"
(arXiv:1811.05233) and the data-echoing literature show that REUSING
decoded samples buys real wall-clock: one JPEG decode feeds
``echo_factor`` training batches instead of one.

Mechanism (``echoing_iterator``): decoded samples stream into a bounded
pool of host uint8 crops (byte cap ``data.echo_cache_mb``; oldest-first
eviction when it overflows — the memory bound wins over echo
completeness, and every such eviction is counted). Whenever the pool
holds at least one batch worth of pending servings, a batch is emitted by
drawing DISTINCT samples via a seeded permutation — every emitted batch
is a fresh reshuffle of the cache, so echoed copies of a sample land in
different batches with different batchmates ("reshuffled per echo").
Each sample carries ``echo_factor`` total servings; exhausted samples
leave the pool. At stream end the pool drains through the same path, so
a finite stream under echo_factor=e yields each sample exactly e times
(modulo a trailing partial batch, logged — the no-silent-caps rule).

Echoed batches are raw host batches: they flow through the ordinary
threaded stacker → coalesced stager → device path, and the device-side
augmentation (ops/augment.py) draws fresh crops/flips per appearance —
which is what keeps echoed steps from being exact repeats. The
transfer-level analog (one H2D transfer feeding multiple steps) is
``data.echo_transfer`` in the train loop, not here.

Telemetry: emission busy time (the ``input.echo_emit`` span) lands in
``utils.metrics.input_stages`` under the "echo" stage; hits/misses/evictions in
``utils.metrics.echo_stats`` (``{"event": "input_echo"}`` rows via
InputEchoHook; registered in EVENT_SCHEMAS).
"""
from __future__ import annotations

import logging
from typing import Dict, Iterator, Optional

import numpy as np

log = logging.getLogger(__name__)


class _Entry:
    __slots__ = ("leaves", "uses", "served", "nbytes")

    def __init__(self, leaves: tuple, uses: int):
        self.leaves = leaves          # one row per batch key, copied
        self.uses = uses
        self.served = False
        self.nbytes = sum(getattr(v, "nbytes", 8) for v in leaves)


def echoing_iterator(src: Iterator[Dict[str, np.ndarray]],
                     echo_factor: int,
                     cache_mb: float = 256.0,
                     seed: int = 0,
                     stats=None) -> Iterator[Dict[str, np.ndarray]]:
    """Wrap a host batch iterator so each sample feeds ``echo_factor``
    batches (see module docstring). ``echo_factor <= 1`` returns ``src``
    unchanged. Deterministic: the same ``seed`` over the same source
    stream yields byte-identical echoed batches — the draw order is a
    seeded permutation, independent of wall-clock or thread timing.

    Closing the returned generator propagates close() to ``src`` (the
    worker-thread shutdown contract every input stage follows)."""
    if echo_factor <= 1:
        return src
    if stats is None:
        from ..utils.metrics import echo_stats
        stats = echo_stats
    cap = max(1, int(cache_mb * 1e6))
    stats.configure(echo_factor, cap)

    def gen():
        from ..telemetry.tracer import span
        rng = np.random.RandomState((seed * 1_000_003 + 12345) % (2 ** 32))
        # FIFO of _Entry: live entries are pool[head:] — eviction only
        # advances `head` (O(1)); the dead prefix is trimmed periodically
        # so a cap-bound stream never pays an O(pool) shift per eviction
        pool: list = []
        head = 0
        pool_bytes = 0
        pending_uses = 0          # sum of uses over the live pool
        keys: Optional[tuple] = None
        batch_size = 0
        # emission waits for the pool to reach this fill (derived from the
        # first sample's size: ~4 batches, capped by what the byte bound
        # can actually hold — a floor above the cap would never be reached
        # and the stream would block forever) so emitted batches MIX
        # samples across several source batches — greedy emission would
        # drain each source batch's uses before the next arrived and
        # "reshuffled" would degrade to within-batch permutation. The
        # end-of-stream drain ignores it.
        fill_entries: Optional[int] = None

        def emit():
            """One batch: distinct samples via a seeded permutation
            (duplicates only when the pool holds fewer distinct samples
            than a batch — a byte-capped pool or the drain tail)."""
            nonlocal pool, head, pool_bytes, pending_uses
            with span("input.echo_emit") as sp:
                n = len(pool) - head
                if n >= batch_size:
                    # distinct samples per batch (within-batch uniqueness)
                    take = rng.permutation(n)[:batch_size]
                else:
                    # pool smaller than a batch (byte-capped / tiny stream /
                    # drain tail): draw from the multiset of remaining
                    # servings so no entry is served past its uses — epoch
                    # accounting stays exact (each sample emitted exactly
                    # echo_factor times)
                    avail = np.repeat(np.arange(n),
                                      [e.uses for e in pool[head:]])
                    take = avail[rng.permutation(len(avail))[:batch_size]]
                hits = 0
                rows = []
                exhausted = False
                for i in take:
                    e = pool[head + i]
                    if e.served:
                        hits += 1
                    e.served = True
                    e.uses -= 1
                    pending_uses -= 1
                    exhausted = exhausted or e.uses <= 0
                    rows.append(e.leaves)
                out = {k: np.stack([r[ki] for r in rows])
                       for ki, k in enumerate(keys)}
                if exhausted:
                    kept = []
                    for e in pool[head:]:
                        if e.uses > 0:
                            kept.append(e)
                        else:
                            pool_bytes -= e.nbytes
                    pool = kept
                    head = 0
                nbytes = sum(v.nbytes for v in out.values())
            sp.charge("echo", items=batch_size, nbytes=nbytes)
            stats.add(emitted=batch_size, hits=hits, cache_bytes=pool_bytes)
            return out

        try:
            for batch in src:
                if keys is None:
                    keys = tuple(sorted(batch))
                    batch_size = int(np.shape(batch[keys[0]])[0])
                with span("input.echo"):
                    for i in range(batch_size):
                        entry = _Entry(
                            tuple(np.array(batch[k][i]) for k in keys),
                            echo_factor)
                        pool.append(entry)
                        pool_bytes += entry.nbytes
                        pending_uses += echo_factor
                        evic = lost = 0
                        while pool_bytes > cap and len(pool) - head > 1:
                            old = pool[head]
                            head += 1
                            pool_bytes -= old.nbytes
                            pending_uses -= old.uses
                            evic += 1
                            lost += old.uses
                        if evic:
                            stats.add(evictions=evic, lost_uses=lost,
                                      cache_bytes=pool_bytes)
                    if head and head >= max(256, batch_size):
                        del pool[:head]  # trim the dead prefix, amortized
                        head = 0
                    stats.add(decoded=batch_size, cache_bytes=pool_bytes)
                    if fill_entries is None and pool:
                        per_entry = max(1, pool[head].nbytes)
                        max_live = max(1, int(cap // per_entry))
                        if max_live * echo_factor < batch_size:
                            # the cap can never accumulate one batch worth
                            # of servings: emission would block forever —
                            # fail loudly instead of hanging the train loop
                            raise ValueError(
                                f"data.echo_cache_mb={cache_mb:g} holds "
                                f"only ~{max_live} decoded sample(s) "
                                f"(~{per_entry} B each); with echo_factor="
                                f"{echo_factor} that can never fill a "
                                f"batch of {batch_size} — raise "
                                "echo_cache_mb or lower the batch size")
                        fill_entries = min(4 * batch_size, max_live)
                while pending_uses >= batch_size and \
                        len(pool) - head >= fill_entries:
                    yield emit()
            # stream end: drain the pool through the same path (full
            # batches only — a partial batch cannot be dispatched)
            while pending_uses >= batch_size and len(pool) - head > 0:
                yield emit()
            if pending_uses:
                log.warning(
                    "echoing_iterator: dropping %d trailing echo "
                    "serving(s) at stream end (smaller than one batch of "
                    "%d)", pending_uses, batch_size)
        finally:
            close = getattr(src, "close", None)
            if close is not None:
                try:
                    close()
                except ValueError:  # generator running on another thread
                    pass

    return gen()
