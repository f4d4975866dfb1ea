"""Device-memory telemetry: turn OOMs from postmortems into trends.

Nothing in the framework measured memory at runtime: an HBM OOM surfaced
as an XLA allocation error after hours, host-RSS creep (a leaking decode
pool, an unbounded cache) as a SLURM OOM-kill, and neither left a trend
line to read back. This module is the sampler behind the registered
``{"event": "memory"}`` rows (utils.metrics.EVENT_SCHEMAS):

  * **device side** — live ``jax.Array`` bytes per addressable device
    (``jax.live_arrays()``: portable, works on the CPU test mesh), plus
    the allocator's ``memory_stats()`` (``bytes_in_use`` /
    ``peak_bytes_in_use`` / ``bytes_limit``) where the backend reports it
    (TPU); the allocator peak is authoritative where present, the
    live-array watermark is the portable fallback. The watermark is
    SAMPLED — a spike between samples is invisible; that limitation is
    exactly why the allocator stats ride along when available.
  * **host side** — ``VmRSS`` / ``VmHWM`` from ``/proc/self/status``.
  * **pipeline occupancy** — the decoded-sample echo cache
    (utils.metrics.echo_stats) and the coalesced staging rings
    (parallel/sharding.staging_occupancy), the two byte-bounded host
    pools a mis-sized config silently grows into.

Sampled at the train-loop summary cadence (train/hooks.MemoryHook, every
process — each host owns its devices) and the serve report cadence
(serve/server.py); ``main.py monitor`` rolls the per-host HBM watermark
up with a warn threshold (docs/observability.md).
"""
from __future__ import annotations

import logging
import threading
from typing import Any, Dict, Optional

log = logging.getLogger(__name__)


class MemoryWatermarks:
    """Process-global sampled high-water marks (per device + total):
    ``update`` folds one sample in and returns the running peaks."""

    def __init__(self):
        self._lock = threading.Lock()
        self._peak_by_device: Dict[str, int] = {}
        self._peak_total = 0

    def update(self, live_by_device: Dict[str, int]) -> Dict[str, Any]:
        total = sum(live_by_device.values())
        with self._lock:
            for dev, n in live_by_device.items():
                if n > self._peak_by_device.get(dev, 0):
                    self._peak_by_device[dev] = n
            self._peak_total = max(self._peak_total, total)
            return {"by_device": dict(self._peak_by_device),
                    "total": self._peak_total}

    def reset(self) -> None:
        with self._lock:
            self._peak_by_device.clear()
            self._peak_total = 0


#: the process-global watermark tracker every sampler feeds
watermarks = MemoryWatermarks()


def _live_bytes_by_device() -> Dict[str, int]:
    """Live jax.Array bytes per addressable device. O(live arrays) — a
    summary-cadence cost, not a hot-path one."""
    import jax
    out: Dict[str, int] = {str(d.id): 0 for d in jax.local_devices()}
    for arr in jax.live_arrays():
        try:
            for shard in arr.addressable_shards:
                key = str(shard.device.id)
                if key in out:
                    out[key] += int(shard.data.nbytes)
        except Exception:  # a deleted/donated array mid-scan
            continue
    return out


def _host_rss() -> Dict[str, int]:
    """VmRSS/VmHWM in bytes from /proc/self/status; empty off-Linux."""
    out: Dict[str, int] = {}
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    out["host_rss_bytes"] = int(line.split()[1]) * 1024
                elif line.startswith("VmHWM:"):
                    out["host_peak_rss_bytes"] = int(line.split()[1]) * 1024
    except OSError:
        pass
    return out


def sample_memory(process_index: Optional[int] = None) -> Dict[str, Any]:
    """One ``{"event": "memory"}`` payload (minus the event/step keys the
    exporting hook adds). Never raises — telemetry must not kill the
    run; a failed probe degrades to fewer fields."""
    import jax
    row: Dict[str, Any] = {}
    try:
        row["process"] = jax.process_index() if process_index is None \
            else int(process_index)
    except Exception:
        row["process"] = int(process_index or 0)
    try:
        live = _live_bytes_by_device()
        peaks = watermarks.update(live)
        devices: Dict[str, Dict[str, int]] = {
            dev: {"live_bytes": n,
                  "live_peak_bytes": peaks["by_device"].get(dev, n)}
            for dev, n in live.items()}
        for d in jax.local_devices():
            # None on a backend without allocator stats (the CPU); a
            # failing probe is logged by the handler below, not dropped
            stats = d.memory_stats()
            if stats:
                cell = devices.setdefault(str(d.id), {})
                for src, dst in (("bytes_in_use", "bytes_in_use"),
                                 ("peak_bytes_in_use", "peak_bytes_in_use"),
                                 ("bytes_limit", "bytes_limit")):
                    if src in stats:
                        cell[dst] = int(stats[src])
        row["devices"] = devices
        row["live_bytes_total"] = sum(live.values())
        row["live_peak_bytes_total"] = peaks["total"]
    except Exception:  # pragma: no cover - observability best effort
        log.exception("device-memory sample failed")
    row.update(_host_rss())
    try:
        from ..utils.metrics import echo_stats
        row["echo_cache_bytes"] = echo_stats.cache_bytes
        row["echo_cache_cap_bytes"] = echo_stats.cache_cap_bytes
    except Exception:  # pragma: no cover
        pass
    try:
        from ..parallel.sharding import staging_occupancy
        slots, inflight = staging_occupancy()
        row["staging_ring_slots"] = slots
        row["staging_ring_inflight"] = inflight
    except Exception:  # pragma: no cover
        pass
    return row
