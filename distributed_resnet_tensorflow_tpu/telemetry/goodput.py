"""Goodput accounting: classify train-loop wall time by where it went.

The break-down that dominates at scale (arXiv:1711.00705): a cluster's
billed wall-clock splits into useful compute vs input-wait vs checkpoint
stalls vs eval rounds vs restart overhead — and the reference could not
measure ANY of it (stdout logs + TensorBoard scalars only, SURVEY.md
§2.15). Here every second of the train loop lands in exactly one bucket:

  * ``input_wait``  — loop blocked on the next device batch
    (``span("input.wait")`` in train/loop.py),
  * ``checkpoint``  — loop blocked in save()/wait_until_finished
    (checkpoint/manager.py),
  * ``eval``        — in-loop evaluation rounds (Trainer.evaluate),
  * ``restart``     — NaN-rollback restores (resilience/sentinel.py),
  * ``stall``       — watchdog-attributed dead time (hang verdicts,
    resilience/watchdog.py),
  * ``reshard``     — elastic mesh-generation transitions: barrier +
    teardown + re-init + restore + rebuild (resilience/elastic.py),
  * ``compute``     — everything else: the remainder of the wall interval.
    Remainder-as-compute is the honest choice under async dispatch — the
    loop thread does not block per step, so its non-waiting wall time IS
    the window in which the device pipeline runs.

Categorized spans (telemetry/tracer.py) feed ``GoodputMeter.add``; the
chief's ``GoodputHook`` (train/hooks.py) emits one registered
``{"event": "goodput"}`` metrics row per summary cadence with per-category
seconds and percentages (summing to ~100% of the interval's wall by
construction). ``main.py monitor`` consumes the same numbers — ROADMAP open items 2 (input gap) and 5 (zero-stall
persistence) are measured against exactly these buckets.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Optional

#: the classification buckets, in display order. "compute" is always the
#: interval remainder; the others are measured from categorized spans.
CATEGORIES = ("compute", "input_wait", "checkpoint", "eval", "stall",
              "restart", "reshard")

#: the buckets spans may charge (everything but the remainder)
MEASURED_CATEGORIES = CATEGORIES[1:]


class GoodputMeter:
    """Thread-safe cumulative seconds per category + interval summaries.

    ``add`` is the span-exit hot path (one lock + one float add);
    ``interval()`` differences the cumulative totals against the previous
    call and classifies the wall time in between; ``rebase()`` restarts
    the window without emitting (call at train-segment start so compile /
    restore time before step 1 is not billed as compute).
    """

    def __init__(self, clock=time.monotonic):
        self._clock = clock
        self._lock = threading.Lock()
        self._totals: Dict[str, float] = {c: 0.0 for c in
                                          MEASURED_CATEGORIES}
        self._mark_t: Optional[float] = None
        self._mark_totals: Dict[str, float] = dict(self._totals)

    def add(self, category: str, seconds: float) -> None:
        with self._lock:
            # unknown categories accumulate too (forward compatibility);
            # interval() only reports the registered set
            self._totals[category] = \
                self._totals.get(category, 0.0) + seconds

    def snapshot(self) -> Dict[str, float]:
        """Cumulative measured seconds per category since process start."""
        with self._lock:
            return dict(self._totals)

    def rebase(self) -> None:
        """Restart the interval window at now."""
        with self._lock:
            self._mark_t = self._clock()
            self._mark_totals = dict(self._totals)

    def interval(self) -> Dict[str, object]:
        """Classify the wall time since the last interval()/rebase().

        Returns ``{"wall_secs", "seconds": {cat: s}, "pct": {cat: p}}``
        with ``compute`` = wall − Σ(measured), clamped at 0 (overlapping
        charges from a second thread can only shrink compute, never push
        the sum past 100%: percentages are normalized over max(wall, Σ)).
        The first call after construction measures from the first
        ``rebase()`` — without one it returns an empty interval."""
        now = self._clock()
        with self._lock:
            if self._mark_t is None:
                self._mark_t = now
                self._mark_totals = dict(self._totals)
                return {"wall_secs": 0.0,
                        "seconds": {c: 0.0 for c in CATEGORIES},
                        "pct": {c: 0.0 for c in CATEGORIES}}
            wall = max(0.0, now - self._mark_t)
            delta = {c: self._totals.get(c, 0.0)
                     - self._mark_totals.get(c, 0.0)
                     for c in MEASURED_CATEGORIES}
            self._mark_t = now
            self._mark_totals = dict(self._totals)
        measured = sum(delta.values())
        seconds = {"compute": max(0.0, wall - measured), **delta}
        denom = max(wall, measured, 1e-9)
        pct = {c: 100.0 * s / denom for c, s in seconds.items()}
        return {
            "wall_secs": round(wall, 4),
            "seconds": {c: round(seconds[c], 4) for c in CATEGORIES},
            "pct": {c: round(pct[c], 2) for c in CATEGORIES},
        }


#: the process-global meter categorized spans feed
goodput = GoodputMeter()
