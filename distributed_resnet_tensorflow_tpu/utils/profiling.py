"""Profiling / tracing — the subsystem the reference left vestigial.

The reference had commented-out ``tf.contrib.tfprof`` param/FLOP counting
(reference resnet_single.py:58-66, commented at resnet_cifar_main.py:260-268)
and measured throughput offline from log timestamps (SURVEY.md §5). Here:

  * ``count_params`` / ``flops_per_step``  — live counters from the compiled
    XLA executable (cost analysis), not estimates.
  * ``mfu``                                — model FLOPs utilization against
    a per-generation peak table.
  * ``trace``                              — context manager around
    ``jax.profiler`` emitting a TensorBoard-viewable trace.
"""
from __future__ import annotations

import contextlib
import logging
from typing import Any, Iterator, Optional

import jax

log = logging.getLogger(__name__)

# bf16 peak TFLOP/s per JAX DEVICE, keyed by a substring of the lowered
# ``device_kind`` (public spec-sheet numbers). mfu() multiplies by
# jax.device_count(), and on v2/v3 JAX exposes each of the chip's 2 cores
# as a device — so those entries are per-CORE (chip peak / 2); v4+ are one
# device per chip. The ONE peaks table of the repo: a device that is not
# here is an error where a peak is asked for (detect_peak_tflops).
TPU_PEAK_TFLOPS = {
    "v2": 45.0 / 2, "v3": 123.0 / 2,
    "v4": 275.0,
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip;
    # JAX reports the chip as device_kind "TPU v5 lite"
    "v5e": 197.0, "v5 lite": 197.0,
    "v5p": 459.0, "v6e": 918.0,
}


def count_params(params: Any) -> int:
    return sum(p.size for p in jax.tree_util.tree_leaves(params))


def lowered_flops(lowered) -> Optional[float]:
    """FLOPs of one lowered step once compiled, from XLA's own cost
    analysis."""
    try:
        cost = lowered.compile().cost_analysis()
        return float(cost.get("flops", 0.0)) or None
    except Exception as e:  # cost analysis not supported on this backend
        log.warning("cost analysis unavailable, no FLOP count: %s", e)
        return None


def flops_per_step(jitted_fn, *example_args) -> Optional[float]:
    """FLOPs of one compiled step of ``jitted_fn(*example_args)``."""
    return lowered_flops(jitted_fn.lower(*example_args))


def detect_peak_tflops() -> Optional[float]:
    """bf16 peak TFLOP/s of one attached device. None on the CPU backend —
    a host CPU has no row, so callers there report no utilization and say
    so. An ACCELERATOR whose ``device_kind`` is not in the table raises: a
    utilization against a guessed or borrowed peak is worse than none."""
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    kind = dev.device_kind.lower()
    for key, peak in TPU_PEAK_TFLOPS.items():
        if key in kind:
            return peak
    raise ValueError(
        f"no peak TFLOP/s for device_kind {dev.device_kind!r} (platform "
        f"{dev.platform!r}) in utils/profiling.TPU_PEAK_TFLOPS — add the "
        "row with its source before reporting a utilization on it")


def mfu(steps_per_sec: float, step_flops: float,
        num_devices: Optional[int] = None,
        peak_tflops: Optional[float] = None) -> Optional[float]:
    """Model FLOPs utilization in [0,1]: achieved / peak. None when the
    FLOP count is unknown or the backend is the CPU (no peak to hold it
    against — see detect_peak_tflops, which raises for an accelerator it
    does not know)."""
    peak = peak_tflops or detect_peak_tflops()
    if not peak or not step_flops:
        return None
    n = num_devices or jax.device_count()
    return (steps_per_sec * step_flops) / (peak * 1e12 * n)


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """jax.profiler trace → TensorBoard 'profile' plugin directory."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def trace_window(logdir: str, duration_secs: float = 5.0) -> str:
    """On-demand jax.profiler window: start, wait ``duration_secs``, stop.

    The flight recorder's anomaly hook (telemetry/tracer.py,
    ``telemetry.profile_on_anomaly``) calls this from the watchdog's
    daemon thread so a hang/straggler incident captures DEVICE-side
    activity alongside the host-side span dump — profiling runs out of
    band of the (possibly wedged) main thread. Safe to call anywhere; a
    profiler that is already active raises inside jax and the caller
    treats that as best-effort."""
    import os
    import time as _time
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    try:
        _time.sleep(max(0.1, duration_secs))
    finally:
        jax.profiler.stop_trace()
    log.info("jax.profiler window (%.1fs) captured to %s",
             duration_secs, logdir)
    return logdir
