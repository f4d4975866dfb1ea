"""Open-loop synthetic load generator for the inference server.

OPEN loop: arrivals are scheduled on a fixed clock (request i at its
precomputed arrival offset) regardless of completions — the load a real
user population offers, and the one that exposes queueing collapse. A
closed-loop driver (wait for each response before sending the next) would
self-throttle exactly when the server is slowest and report flattering
latency (coordinated omission). The generator never blocks on a Future
until the offered load is fully submitted; per-request latency is recorded
by the server at result time, so a late response is charged its full
queue + service time.

Load shapes (``shape=``): the arrival SCHEDULE is precomputed by
inverting the cumulative integral of a rate function, so every shape
stays coordinated-omission-free — the clock, not the server, decides
when request i goes out:

  * ``steady``  — constant ``qps`` (the historical behavior).
  * ``diurnal`` — one full sinusoid period over the run, ±50% around
    ``qps`` (day/night traffic compressed into the window).
  * ``burst``   — 70% of ``qps`` baseline with periodic 3× bursts (a
    tenth of the window each, five per run) — retry storms / batch jobs.
  * ``spike``   — ``qps`` baseline with a single 4× spike across the
    middle tenth of the window — the flash-crowd shape that trips
    admission (shed/degrade) in the fleet front door.

Every shape offers ≈ ``qps × duration`` total requests, so reports stay
comparable across shapes.
"""
from __future__ import annotations

import logging
import time
from concurrent.futures import wait as futures_wait
from typing import Optional

import numpy as np

log = logging.getLogger(__name__)

LOAD_SHAPES = ("steady", "diurnal", "burst", "spike")


def synthetic_requests(image_shape, dtype, pool: int = 32, seed: int = 0):
    """A small pool of random request images, cycled by the generator (the
    per-request content doesn't affect timing; generating fresh images at
    high QPS would bottleneck the GENERATOR, not measure the server)."""
    rng = np.random.RandomState(seed)
    dtype = np.dtype(dtype)
    if dtype == np.uint8:
        return [rng.randint(0, 256, image_shape, np.uint8)
                for _ in range(pool)]
    return [rng.randn(*image_shape).astype(dtype) for _ in range(pool)]


def _rate_fn(shape: str, qps: float, duration_secs: float):
    """Instantaneous request rate at time t ∈ [0, duration)."""
    if shape == "steady":
        return lambda t: qps
    if shape == "diurnal":
        w = 2.0 * np.pi / duration_secs
        return lambda t: qps * (1.0 + 0.5 * np.sin(w * t))
    if shape == "burst":
        period = duration_secs / 5.0

        def burst(t):
            return 3.0 * qps if (t % period) < period * 0.1 else 0.7 * qps
        return burst
    if shape == "spike":
        lo, hi = 0.45 * duration_secs, 0.55 * duration_secs
        return lambda t: 4.0 * qps if lo <= t < hi else qps
    raise ValueError(f"unknown load shape {shape!r}; "
                     f"one of {LOAD_SHAPES}")


def arrival_times(shape: str, qps: float, duration_secs: float) -> np.ndarray:
    """Precomputed arrival offsets (seconds from start) for the whole
    run: cumulative-rate inversion on a fine grid, so the i-th arrival is
    where the integral of the rate function crosses i. Deterministic and
    independent of server behavior — the open-loop guarantee."""
    rate = _rate_fn(shape, qps, duration_secs)
    grid = np.linspace(0.0, duration_secs, max(1000, int(duration_secs * 200)))
    rates = np.asarray([rate(t) for t in grid], dtype=np.float64)
    cum = np.concatenate([[0.0], np.cumsum(
        (rates[1:] + rates[:-1]) / 2.0 * np.diff(grid))])
    n = max(1, int(round(cum[-1])))
    return np.interp(np.arange(n) * (cum[-1] / n), cum, grid)


def run_open_loop(server, qps: float, duration_secs: float,
                  seed: int = 0, timeout_secs: Optional[float] = None,
                  variant: Optional[str] = None,
                  shape: str = "steady") -> dict:
    """Offer ≈ ``qps × duration_secs`` requests on the ``shape`` arrival
    schedule, then wait for every outstanding Future. Returns
    offered/completed/failed/late counts and the achieved submit rate;
    latency percentiles live in ``server.report()`` (recorded server-side
    per request).

    ``variant`` targets one serving precision variant (docs/precision.md;
    None = the replica's default): one open loop per variant."""
    offsets = arrival_times(shape, qps, duration_secs)
    n = len(offsets)
    pool = synthetic_requests(server.image_shape, server.image_dtype,
                              seed=seed)
    futures = []
    late = 0
    t0 = time.perf_counter()
    for i in range(n):
        target = t0 + offsets[i]
        now = time.perf_counter()
        if now < target:
            time.sleep(target - now)
        elif now - target > 0.5:
            late += 1  # generator itself fell behind the open-loop clock
        futures.append(server.submit(pool[i % len(pool)], variant=variant))
    submit_wall = time.perf_counter() - t0
    done, not_done = futures_wait(
        futures, timeout=timeout_secs if timeout_secs is not None
        else max(60.0, duration_secs))
    failed = sum(1 for f in done if f.exception() is not None)
    if not_done:
        log.error("open-loop load: %d request(s) unresolved at timeout",
                  len(not_done))
    return {
        "offered": n,
        "completed": len(done) - failed,
        "failed": failed,
        "unresolved": len(not_done),
        "late_submits": late,
        "shape": shape,
        "offered_qps": round(qps, 1),
        "achieved_submit_qps": round(n / max(submit_wall, 1e-9), 1),
        "wall_secs": round(submit_wall, 2),
    }
