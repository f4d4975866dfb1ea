"""Metrics / observability.

The reference's three channels (SURVEY.md §2.15, §5):
  1. stdout logging every N steps (``LoggingTensorHook``,
     reference resnet_cifar_main.py:280-285),
  2. TensorBoard scalar summaries every 100 steps (``SummarySaverHook``,
     reference resnet_cifar_main.py:274-278; scalars cross_entropy/cost/lr,
     reference resnet_model.py:82-93),
  3. per-process log files (reference run_dist_train_eval_daint.sh:161,188).

Here: one ``MetricsWriter`` that fans out to a machine-readable JSONL event
stream and (when available) TensorBoard via tensorboardX, plus a
``Throughput`` meter giving steps/sec and images/sec — the number the
reference only derived offline from log timestamps (SURVEY.md §6).
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Any, Dict, Optional

log = logging.getLogger(__name__)


class StageStats:
    """Thread-safe busy-time/item counters for the input-pipeline stages
    (decode / echo / stack / stage / transfer / dispatch_wait) and, under
    its own name, for every flight-recorder span (telemetry/tracer.py).

    A span's exit calls ``add(<span name>, seconds)``; a site that feeds a
    stage key charges it from the same span (``sp.charge(stage, items=...,
    nbytes=...)``) — no site reads the clock a second time. Totals are
    kept PER THREAD so ``rates()`` can estimate a
    stage's throughput as items / busiest-thread-seconds — the number that
    stays honest for multi-worker stages (a 4-thread decode pool that spent
    40 thread-seconds decoding 1000 images over a 10 s wall ran at ~100
    img/s, not 25). The end-to-end input rate is attributed from these
    counters instead of re-measuring each component in isolation, so the
    attribution reflects the overlapped pipeline as it actually ran.
    """

    def __init__(self):
        self._lock = threading.Lock()
        # (stage, worker_key) -> [count, items, seconds, bytes]
        self._cells: Dict[tuple, list] = {}

    def add(self, stage: str, seconds: float, items: int = 0,
            nbytes: int = 0, worker=None) -> None:
        """``worker`` overrides the default thread-identity cell key — the
        merge path for counters that were accumulated in ANOTHER process
        (imagenet decode worker processes ship snapshots back over their
        result queue; the parent merges them here under a per-worker key so
        ``max_thread_seconds`` still reflects the busiest worker, not the
        merging thread)."""
        key = (stage, threading.get_ident() if worker is None else worker)
        with self._lock:
            cell = self._cells.get(key)
            if cell is None:
                cell = self._cells[key] = [0, 0, 0.0, 0]
            cell[0] += 1
            cell[1] += items
            cell[2] += seconds
            cell[3] += nbytes

    def reset(self) -> None:
        with self._lock:
            self._cells.clear()

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """Per-stage aggregate: count, items, seconds (summed over threads),
        max_thread_seconds (the busiest worker), workers, bytes."""
        with self._lock:
            cells = {k: list(v) for k, v in self._cells.items()}
        out: Dict[str, Dict[str, float]] = {}
        for (stage, _tid), (count, items, secs, nbytes) in cells.items():
            agg = out.setdefault(stage, {
                "count": 0, "items": 0, "seconds": 0.0,
                "max_thread_seconds": 0.0, "workers": 0, "bytes": 0})
            agg["count"] += count
            agg["items"] += items
            agg["seconds"] += secs
            agg["max_thread_seconds"] = max(agg["max_thread_seconds"], secs)
            agg["workers"] += 1
            agg["bytes"] += nbytes
        return out

    def rates(self) -> Dict[str, float]:
        """stage -> items/sec estimate (items / busiest-thread busy time)."""
        out = {}
        for stage, agg in self.snapshot().items():
            if agg["items"] > 0 and agg["max_thread_seconds"] > 0:
                out[stage] = agg["items"] / agg["max_thread_seconds"]
        return out


# process-global input-pipeline telemetry: decode workers, the batch
# stacker, the echo cache, the staging/transfer thread and the dispatch
# loop all feed this one registry through their spans (so does every
# other span, under its own name); InputStagesHook exports it to
# metrics.jsonl. Decode
# worker PROCESSES (data.decode_processes > 0) accumulate in their own
# process and ship counter snapshots back over the result queue; the
# parent merges them here under per-worker keys (data/imagenet.py,
# docs/input_pipeline.md).
input_stages = StageStats()


class EchoStats:
    """Thread-safe counters for the data-echoing decoded-sample cache
    (data/echo.py): decoded (fresh samples inserted = cache misses),
    emitted (samples served into batches), hits (servings of a sample
    past its first — the decodes echoing saved), evictions (samples
    dropped by the byte bound with echo uses still pending) and the lost
    uses those evictions cost. ``InputEchoHook`` exports snapshots to
    metrics.jsonl as ``{"event": "input_echo"}`` rows."""

    def __init__(self):
        self._lock = threading.Lock()
        self._c = dict(decoded=0, emitted=0, hits=0, evictions=0,
                       lost_uses=0)
        self.echo_factor = 1
        self.cache_cap_bytes = 0
        self.cache_bytes = 0
        self.peak_cache_bytes = 0

    def configure(self, echo_factor: int, cache_cap_bytes: int) -> None:
        with self._lock:
            self.echo_factor = int(echo_factor)
            self.cache_cap_bytes = int(cache_cap_bytes)

    def add(self, decoded: int = 0, emitted: int = 0, hits: int = 0,
            evictions: int = 0, lost_uses: int = 0,
            cache_bytes: Optional[int] = None) -> None:
        with self._lock:
            self._c["decoded"] += decoded
            self._c["emitted"] += emitted
            self._c["hits"] += hits
            self._c["evictions"] += evictions
            self._c["lost_uses"] += lost_uses
            if cache_bytes is not None:
                self.cache_bytes = int(cache_bytes)
                self.peak_cache_bytes = max(self.peak_cache_bytes,
                                            self.cache_bytes)

    def reset(self) -> None:
        with self._lock:
            for k in self._c:
                self._c[k] = 0
            self.cache_bytes = 0
            self.peak_cache_bytes = 0

    def snapshot(self) -> Dict[str, Any]:
        """Counters + hit_rate (hits / emitted: the fraction of served
        samples that did NOT cost a fresh decode)."""
        with self._lock:
            out = dict(self._c)
            out["echo_factor"] = self.echo_factor
            out["cache_cap_bytes"] = self.cache_cap_bytes
            out["cache_bytes"] = self.cache_bytes
            out["peak_cache_bytes"] = self.peak_cache_bytes
        out["hit_rate"] = round(out["hits"] / out["emitted"], 4) \
            if out["emitted"] else 0.0
        return out


# process-global echo-cache telemetry (one echoing stream per train run)
echo_stats = EchoStats()


class CkptAsyncStats:
    """Thread-safe counters splitting checkpoint cost by WHO paid it
    (checkpoint/manager.py): the step-loop thread's share (device→host
    snapshot + backpressure waiting on an in-flight save) versus the
    writer thread's share (stage → fsync → manifest → commit) — the
    charge-split behind the goodput contract that only loop-blocking time
    lands in the ``checkpoint`` bucket while writer seconds ride the
    ``{"event": "ckpt_async"}`` row (train/hooks.CkptAsyncHook)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._c = dict(saves=0, committed=0, sync_saves=0, overtakes=0,
                       snapshot_seconds=0.0, backpressure_seconds=0.0,
                       writer_seconds=0.0, shard_bytes=0, shard_files=0,
                       shard_seconds=0.0, finalize_wait_seconds=0.0)
        self.last_committed_step = -1

    def add(self, saves: int = 0, committed: int = 0, sync_saves: int = 0,
            overtakes: int = 0, snapshot_seconds: float = 0.0,
            backpressure_seconds: float = 0.0,
            writer_seconds: float = 0.0,
            shard_bytes: int = 0, shard_files: int = 0,
            shard_seconds: float = 0.0,
            finalize_wait_seconds: float = 0.0,
            step: Optional[int] = None) -> None:
        with self._lock:
            self._c["saves"] += saves
            self._c["committed"] += committed
            self._c["sync_saves"] += sync_saves
            self._c["overtakes"] += overtakes
            self._c["snapshot_seconds"] += snapshot_seconds
            self._c["backpressure_seconds"] += backpressure_seconds
            self._c["writer_seconds"] += writer_seconds
            self._c["shard_bytes"] += shard_bytes
            self._c["shard_files"] += shard_files
            self._c["shard_seconds"] += shard_seconds
            self._c["finalize_wait_seconds"] += finalize_wait_seconds
            if step is not None:
                self.last_committed_step = max(self.last_committed_step,
                                               int(step))

    def reset(self) -> None:
        with self._lock:
            for k in self._c:
                self._c[k] = 0 if isinstance(self._c[k], int) else 0.0
            self.last_committed_step = -1

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            out = dict(self._c)
            out["last_committed_step"] = self.last_committed_step
        for k in ("snapshot_seconds", "backpressure_seconds",
                  "writer_seconds", "shard_seconds",
                  "finalize_wait_seconds"):
            out[k] = round(out[k], 4)
        return out


# process-global async-checkpoint accounting (one writer per train run)
ckpt_async_stats = CkptAsyncStats()


#: The metrics.jsonl event registry — the ONE source of truth for every
#: typed ``{"event": <name>, ...}`` record any part of the framework may
#: emit. Each entry: {"fields": {field: one-line description},
#: "emitted_by": module that writes it}. Scalar rows (step/time + metric
#: keys, no "event" key) are not events and are not registered here.
#:
#: Contract, enforced two ways:
#:   * statically — analysis/rules/registry_drift.py (event-registry)
#:     resolves every ``write_event("name", ...)`` literal and every
#:     ``{"event": "name"}`` mention in docs/ and scripts/ against this
#:     dict, so code and documentation cannot drift apart;
#:   * at runtime — ``MetricsWriter.write_event`` warns once per unknown
#:     name (never raises: observability must not kill a training run).
#:
#: Adding an event = add it HERE first, then emit/document it.
EVENT_SCHEMAS = {
    "input_stages": {
        "emitted_by": "train/hooks.py InputStagesHook",
        "fields": {
            "step": "step at export time",
            "stages": "per stage key and per span name {count, items, "
                      "seconds, max_thread_seconds, workers, bytes} — "
                      "cumulative since process start/reset (difference "
                      "consecutive rows for window rates)",
        },
    },
    "input_echo": {
        "emitted_by": "train/hooks.py InputEchoHook",
        "fields": {
            "step": "step at export time",
            "echo_factor": "configured data.echo_factor",
            "decoded": "fresh decoded samples inserted (cache misses) — "
                       "cumulative, like the input_stages counters",
            "emitted": "samples served into training batches",
            "hits": "servings past a sample's first (decodes saved)",
            "hit_rate": "hits / emitted",
            "evictions": "samples evicted by the echo_cache_mb bound with "
                         "echo uses still pending",
            "lost_uses": "echo servings those evictions cost",
            "cache_bytes": "decoded-sample cache size at export",
            "peak_cache_bytes": "high-water cache size (bound witness)",
            "cache_cap_bytes": "configured byte bound",
        },
    },
    "ckpt_async": {
        "emitted_by": "train/hooks.py CkptAsyncHook (summary cadence, "
                      "when saves advanced)",
        "fields": {
            "step": "step at export time",
            "saves": "save() calls that snapshotted/wrote (cumulative)",
            "committed": "commits that completed (manifest + rename)",
            "sync_saves": "saves that ran the whole write on the loop "
                          "thread (async off / multi-process)",
            "overtakes": "saves that found the previous one still in "
                         "flight (backpressure waits)",
            "snapshot_seconds": "loop-thread device→host snapshot time "
                                "(charged to goodput 'checkpoint')",
            "backpressure_seconds": "loop-thread waits on in-flight "
                                    "saves (charged to goodput "
                                    "'checkpoint')",
            "writer_seconds": "dedicated writer-thread stage/fsync/"
                              "commit time (overlaps compute; NOT in "
                              "the goodput checkpoint bucket)",
            "shard_bytes": "bytes THIS host's writer staged into its "
                           "per-host shard files (sharded layout only)",
            "shard_files": "per-host shard files this host staged",
            "shard_seconds": "writer time spent staging this host's "
                             "shard files",
            "finalize_wait_seconds": "writer time waiting on peer-host "
                                     "shard markers / the chief's "
                                     "commit (sharded multi-process "
                                     "finalize)",
            "last_committed_step": "newest step the writer committed",
        },
    },
    "ckpt_shard": {
        "emitted_by": "train/hooks.py CkptShardHook (summary cadence, "
                      "when this host's shard bytes advanced; every "
                      "process exports — the monitor rolls hosts up)",
        "fields": {
            "step": "step at export time",
            "process": "jax.process_index() of the exporting host",
            "shard_bytes": "cumulative bytes this host staged into its "
                           "per-host shard files",
            "shard_files": "cumulative per-host shard files staged",
            "shard_seconds": "cumulative writer time staging them",
            "finalize_wait_seconds": "cumulative writer time in the "
                                     "marker-file finalize wait",
            "last_committed_step": "newest step committed on this host's "
                                   "view",
        },
    },
    "zero1": {
        "emitted_by": "train/hooks.py Zero1Hook (once per resolved "
                      "partition plan)",
        "fields": {
            "step": "step at export time",
            "data_shards": "data-axis size the optimizer state shards "
                           "over",
            "sharded_leaves": "optimizer-state leaves sharded over data",
            "replicated_leaves": "leaves left replicated (see reasons)",
            "sharded_bytes": "global bytes of the sharded leaves",
            "replicated_bytes": "global bytes of the replicated leaves",
            "bytes_per_replica": "per-replica optimizer-state bytes "
                                 "under this plan",
            "bytes_per_replica_unsharded": "per-replica bytes the "
                                           "replicated update would "
                                           "cost (the ZeRO-1 saving's "
                                           "denominator)",
            "reasons": "per-reason fallback counts (below-min-size, "
                       "no-divisible-dim, bookkeeping, ...)",
        },
    },
    "memory": {
        "emitted_by": "train/hooks.py MemoryHook (every process, summary "
                      "cadence) + serve/server.py (every 50 dispatch "
                      "batches and at close)",
        "fields": {
            "step": "step at export time (serving: checkpoint step)",
            "process": "jax.process_index() of the exporting host",
            "devices": "per-local-device {live_bytes, live_peak_bytes} "
                       "from jax.live_arrays(), plus the allocator's "
                       "{bytes_in_use, peak_bytes_in_use, bytes_limit} "
                       "where the backend reports memory_stats() (TPU; "
                       "absent on CPU)",
            "live_bytes_total": "live jax.Array bytes across this "
                                "process's devices at sample time",
            "live_peak_bytes_total": "high-water of live_bytes_total "
                                     "over the run's samples (a SAMPLED "
                                     "watermark — peaks between samples "
                                     "are invisible; the allocator peak "
                                     "is authoritative where present)",
            "host_rss_bytes": "this process's resident set size",
            "host_peak_rss_bytes": "VmHWM — the process's RSS high-water",
            "echo_cache_bytes": "decoded-sample echo cache occupancy "
                                "(data/echo.py; 0 when echoing is off)",
            "echo_cache_cap_bytes": "configured echo cache byte bound",
            "staging_ring_slots": "CoalescedStager host-ring slots across "
                                  "live stagers (parallel/sharding.py)",
            "staging_ring_inflight": "ring slots with an in-flight H2D "
                                     "transfer at sample time",
        },
    },
    "perf_anomaly": {
        "emitted_by": "resilience/watchdog.py (perf-anomaly sentinel: "
                      "median+MAD step-time outlier over the rolling "
                      "window; telemetry.anomaly_* knobs)",
        "fields": {
            "step": "last completed step when the outlier fired",
            "detail": "human-readable verdict",
            "step_secs": "the outlying per-step time",
            "median_secs": "rolling-window median per-step time",
            "mad_secs": "rolling-window median absolute deviation",
            "threshold_secs": "median + max(anomaly_mad_k × MAD, "
                              "(anomaly_min_ratio − 1) × median) — what "
                              "the sample exceeded",
            "window": "samples in the rolling window at detection",
        },
    },
    "precision": {
        "emitted_by": "train/hooks.py PrecisionHook (once per resolved "
                      "policy — a property of the run, not of any "
                      "step)",
        "fields": {
            "step": "step at export time",
            "policy": "resolved train.precision (off | bf16)",
            "compute_dtype": "activation/matmul dtype under the policy "
                             "(null when off)",
            "master_dtype": "persisted parameter/optimizer dtype "
                            "(float32 — the checkpoint contract)",
            "param_leaves": "parameter leaves in the master tree",
            "master_param_bytes": "f32 master parameter bytes (what "
                                  "checkpoints persist regardless of "
                                  "policy)",
        },
    },
    "corrupt_record": {
        "emitted_by": "train/hooks.py CorruptRecordsHook",
        "fields": {
            "step": "step at export time",
            "count": "distinct corrupt (file, offset) sites skipped",
            "repeats": "re-reads of already-counted sites",
            "by_reason": "per-reason breakdown",
            "recent": "most recent offenders (file, reason)",
        },
    },
    "heartbeat": {
        "emitted_by": "resilience/watchdog.py (straggler_window cadence)",
        "fields": {
            "hosts": "per-process {step, progress, phase, host, age_secs}",
        },
    },
    "straggler": {
        "emitted_by": "resilience/watchdog.py (straggler_window cadence)",
        "fields": {
            "window_secs": "accounting window",
            "rates": "per-process steps/sec over the window",
            "median": "median step rate",
            "lag_steps": "per-process steps behind the leader",
            "flagged": "process ids slower than median by straggler_ratio",
        },
    },
    "peer_lost": {
        "emitted_by": "resilience/watchdog.py (detection verdict)",
        "fields": {"detail": "human-readable verdict",
                   "exit_code": "intended exit code (75)",
                   "grace_secs": "grace window before hard exit",
                   "via": "'collective_error' when classified from the "
                          "main thread's exception path"},
    },
    "peer_failed": {
        "emitted_by": "resilience/watchdog.py (detection verdict)",
        "fields": {"detail": "human-readable verdict",
                   "exit_code": "intended exit code (1)",
                   "grace_secs": "grace window before hard exit",
                   "via": "'collective_error' when classified from the "
                          "main thread's exception path"},
    },
    "hang": {
        "emitted_by": "resilience/watchdog.py (detection verdict)",
        "fields": {"detail": "human-readable verdict",
                   "exit_code": "intended exit code (75)",
                   "grace_secs": "grace window before hard exit"},
    },
    "watchdog_cleared": {
        "emitted_by": "resilience/watchdog.py",
        "fields": {"kind": "the verdict that cleared within grace"},
    },
    "watchdog_exit": {
        "emitted_by": "resilience/watchdog.py",
        "fields": {"kind": "verdict kind", "exit_code": "code passed to "
                   "os._exit", "detail": "human-readable verdict"},
    },
    "serve_request": {
        "emitted_by": "serve/server.py InferenceServer (report cadence + "
                      "shutdown)",
        "fields": {
            "step": "serving checkpoint step at export time",
            "requests": "requests completed since process start",
            "dropped": "requests that did not complete (contract: 0)",
            "buckets": "per-bucket {count, p50_ms, p99_ms, mean_ms} request "
                       "latency (submit -> result on host) — cumulative, "
                       "like the input_stages counters",
        },
    },
    "serve_batch": {
        "emitted_by": "serve/server.py InferenceServer (per dispatched "
                      "bucket batch)",
        "fields": {
            "step": "checkpoint step the batch was served from",
            "bucket": "padded batch size dispatched",
            "n": "real (un-padded) requests in the batch",
            "variant": "serving precision variant the batch ran on "
                       "(serve.variants; docs/precision.md)",
            "queue_ms": "oldest request's queue wait before dispatch",
            "run_ms": "dispatch -> logits-on-host wall time",
        },
    },
    "goodput": {
        "emitted_by": "train/hooks.py GoodputHook (summary cadence)",
        "fields": {
            "step": "step at export time",
            "wall_secs": "wall seconds classified in this interval",
            "seconds": "per-category seconds {compute, input_wait, "
                       "checkpoint, eval, stall, restart, reshard} — "
                       "compute is the interval remainder "
                       "(telemetry/goodput.py)",
            "pct": "per-category percentages; sum to ~100 of wall by "
                   "construction",
        },
    },
    "trace_dump": {
        "emitted_by": "telemetry/tracer.py FlightRecorder.dump_on_anomaly "
                      "(watchdog escalations, straggler flags, fatal "
                      "exits)",
        "fields": {
            "reason": "what triggered the dump (hang | peer_lost | "
                      "peer_failed | straggler | perf_anomaly | "
                      "exception | on_demand)",
            "detail": "human-readable trigger detail",
            "path": "trace.json written (Chrome-trace / Perfetto format)",
            "spans": "events in the ring at dump time",
            "span_schema_version": "telemetry.tracer.SPAN_SCHEMA_VERSION",
        },
    },
    "serve_swap": {
        "emitted_by": "serve/server.py / serve/swap.py (hot checkpoint "
                      "swap)",
        "fields": {
            "from_step": "previously serving step (-1 = fresh init)",
            "to_step": "checkpoint step now serving (absent when rejected)",
            "digest": "manifest digest of the swapped-in checkpoint "
                      "(resilience.manifest.manifest_digest)",
            "restore_ms": "off-path host restore + verify wall time",
            "apply_ms": "on-path atomic apply (device placement + pointer "
                        "swap) wall time",
            "rejected": "present (with the reason string) when a damaged/"
                        "torn checkpoint failed manifest verification and "
                        "was skipped without touching the serving params",
            "to_step_attempted": "the rejected checkpoint's step (rejected "
                                 "rows only; applied rows carry to_step)",
        },
    },
    "route": {
        "emitted_by": "serve/router.py Router (route.row_interval_secs "
                      "cadence + shutdown; the fleet front door's "
                      "headline row — docs/serving.md fleet section)",
        "fields": {
            "requests": "requests admitted since router start",
            "completed": "requests answered (first winning attempt)",
            "errors": "client-visible failures (every attempt exhausted "
                      "or deadline passed) — the smoke bounds these",
            "shed": "requests refused with the shed verdict (cumulative)",
            "degraded": "requests rerouted to the degrade variant under "
                        "queue pressure (cumulative)",
            "hedges": "extra attempts issued after hedge_ms without a "
                      "response (cumulative)",
            "retries": "extra attempts issued after a FAILED attempt "
                       "(cumulative; hedges and retries are both bounded "
                       "by route.max_attempts)",
            "qps": "completions/sec over the row's window",
            "p99_ms": "router-observed p99 request latency (submit → "
                      "first winning response) over the row's window",
            "replicas": "per-replica {state, step, outstanding, served, "
                        "failures, p99_ms, beat_age_secs} snapshot",
        },
    },
    "replica_health": {
        "emitted_by": "serve/router.py Router (health-state transitions "
                      "only, not per scan)",
        "fields": {
            "replica": "replica id (serve.replica_id of the process)",
            "from": "previous health state (warming | ready | degraded | "
                    "suspect | draining | dead)",
            "to": "new health state",
            "reason": "what moved it (probe_ok | failures | beat_stale | "
                      "slo_pressure | recovered | drain | readmit)",
            "beat_age_secs": "heartbeat age at the transition (absent "
                             "when the replica never published a beat)",
            "failures": "consecutive transport failures at the "
                        "transition",
        },
    },
    "canary": {
        "emitted_by": "serve/router.py CanaryController (one row per "
                      "lifecycle action: start, promote, rollback)",
        "fields": {
            "action": "start | promote | rollback",
            "step": "checkpoint step under canary",
            "from_step": "fleet step the canary would replace (rollback "
                         "re-pins it)",
            "canary": "replica ids serving the canary fraction",
            "rollback": "true on the rollback row — the auto-rollback "
                        "witness scripts/serve_fleet_smoke.sh asserts",
            "reason": "decision detail (p99_regression | "
                      "confidence_regression | no_confirm | promoted | "
                      "single_replica)",
            "p99_canary_ms": "canary-arm p99 over the watch window",
            "p99_base_ms": "control-arm p99 over the watch window",
            "conf_canary": "canary-arm mean top-1 softmax confidence "
                           "(the accuracy proxy)",
            "conf_base": "control-arm mean top-1 softmax confidence",
            "samples_canary": "canary-arm responses measured",
            "samples_base": "control-arm responses measured",
        },
    },
    "shed": {
        "emitted_by": "serve/router.py Router (rate-limited: at most one "
                      "row per second while shedding/degrading)",
        "fields": {
            "count": "requests shed since router start (cumulative)",
            "degraded": "requests rerouted to the degrade variant "
                        "(cumulative)",
            "est_queue_ms": "estimated queue delay that tripped the "
                            "verdict (outstanding × EWMA service time / "
                            "eligible replicas)",
            "threshold_ms": "route.shed_queue_ms the estimate exceeded",
        },
    },
    "replica_replace": {
        "emitted_by": "serve/fleet.py FleetSupervisor (watchdog replace "
                      "ladder: drain → kill → respawn → readmit)",
        "fields": {
            "replica": "replica id being replaced",
            "action": "kill | respawn | readmit | gave_up",
            "reason": "what condemned it (exited | wedged | dead)",
            "pid": "pid of the condemned process (kill rows)",
            "rc": "exit code observed (when the process had exited)",
            "new_pid": "pid of the respawned process (respawn/readmit "
                       "rows)",
            "wait_secs": "respawn → READY wall time (readmit rows)",
        },
    },
    "reshard": {
        "emitted_by": "resilience/elastic.py ElasticRuntime (one row per "
                      "completed mesh-generation transition; docs/"
                      "resilience.md elastic mesh)",
        "fields": {
            "generation": "mesh generation ENTERED by this transition",
            "reason": "what triggered it (peer_lost | hang | grow | "
                      "rejoin)",
            "old_hosts": "process count of the generation left behind",
            "new_hosts": "process count of the new generation",
            "restore_step": "committed checkpoint step the new generation "
                            "resumed from (-1 = fresh init, no committed "
                            "checkpoint existed)",
            "global_batch": "global batch size of the new generation "
                            "(resilience.elastic.batch_policy)",
            "barrier_ms": "join-barrier wall time (membership settle + "
                          "commit)",
            "total_ms": "whole transition wall time: barrier + teardown + "
                        "re-init + restore + rebuild",
        },
    },
    "mesh_generation": {
        "emitted_by": "resilience/elastic.py ElasticRuntime (chief, one "
                      "row when a generation starts stepping — including "
                      "generation 0 of an elastic run)",
        "fields": {
            "generation": "the mesh generation now live",
            "hosts": "live process count in this generation",
            "devices": "global device count in this generation",
            "step": "first step of this generation's step loop",
            "coordinator": "epoch-suffixed coordinator address the "
                           "generation initialized over "
                           "(parallel/distributed.py)",
        },
    },
    "plan": {
        "emitted_by": "telemetry/planner.py (main.py plan --root; "
                      "docs/planner.md)",
        "fields": {
            "preset": "preset the prediction is for",
            "layout": "layout name (dp | dp_fsdp | dp_tp | dp_pp | "
                      "dp_pp_ep)",
            "devices": "global device count the prediction assumes",
            "knobs": "knob dict {precision, zero1} the prediction "
                     "assumes",
            "predicted": "{step_secs, compute_secs, comm_secs, "
                         "comm_fraction, "
                         "hbm_bytes, wire_bytes} — the cost model's "
                         "output (telemetry/planner.py)",
            "bandwidth_source": "'catalog' (results/bandwidth/"
                                "<fabric>.json) or 'reference' (baked-in "
                                "table)",
            "recommended": "true on the row for the layout main.py plan "
                           "ranked first",
        },
    },
}

# unknown event names already warned about (warn once, not per row)
_UNKNOWN_EVENTS_WARNED: set = set()


class MetricsWriter:
    """JSONL + optional TensorBoard scalar writer. Process-0-only by default
    (matching chief-only summaries in the reference).

    The JSONL stream is SIZE-BOUNDED: past ``max_bytes`` the file rotates
    (atomic rename to ``metrics.jsonl.1``, older segments shifting up to
    ``max_segments`` before the oldest is dropped) — a week-long serve or
    monitor run cannot fill the disk with event rows. ``read_metrics``
    reads rotated segments oldest-first, so consumers see one continuous
    stream."""

    def __init__(self, logdir: str, enable_tensorboard: bool = True,
                 filename: str = "metrics.jsonl",
                 max_bytes: int = 256 * 1024 * 1024,
                 max_segments: int = 4):
        self.logdir = logdir
        os.makedirs(logdir, exist_ok=True)
        self._path = os.path.join(logdir, filename)
        self._max_bytes = max(0, max_bytes)  # 0 = rotation off
        self._max_segments = max(1, max_segments)
        self._jsonl = open(self._path, "a", buffering=1)
        self._size = self._jsonl.tell()  # append mode: position == size
        # the watchdog's detection thread writes events concurrently with
        # the hook thread's scalars; serialize so rows never interleave
        self._wlock = threading.Lock()
        self._tb = None
        if enable_tensorboard:
            try:
                from tensorboardX import SummaryWriter
                self._tb = SummaryWriter(logdir=logdir)
            except Exception:  # tensorboardX optional
                log.info("tensorboardX unavailable; JSONL metrics only")

    def _write_line(self, line: str) -> None:
        """Caller holds ``_wlock``. Size-triggered rotation happens BEFORE
        the write so a rotated segment never exceeds the bound by more
        than one row."""
        if self._max_bytes and self._size + len(line) > self._max_bytes \
                and self._size > 0:
            self._rotate_locked()
        self._jsonl.write(line)
        self._size += len(line)

    def _rotate_locked(self) -> None:
        """Shift ``.1 -> .2 -> ...`` (dropping the oldest past
        ``max_segments``), atomically rename the live file to ``.1``, and
        reopen. Rotation failures degrade to an unbounded stream — a full
        disk must not kill the run over telemetry."""
        try:
            self._jsonl.close()
            oldest = f"{self._path}.{self._max_segments}"
            if os.path.exists(oldest):
                os.remove(oldest)
            for i in range(self._max_segments - 1, 0, -1):
                seg = f"{self._path}.{i}"
                if os.path.exists(seg):
                    os.replace(seg, f"{self._path}.{i + 1}")
            os.replace(self._path, f"{self._path}.1")
        except OSError as e:
            log.warning("metrics rotation failed (%s); stream unbounded "
                        "until it succeeds", e)
        self._jsonl = open(self._path, "a", buffering=1)
        self._size = self._jsonl.tell()

    def write_images(self, step: int, tag: str, images) -> None:
        """Image summaries (parity with reference cifar_input.py:114's
        tf.summary.image of input batches). TensorBoard-only; no-op without
        tensorboardX. Accepts uint8, or float in any range — floats are
        min-max rescaled per image (training inputs are standardized,
        zero-mean, so clipping to [0,1] would render garbage)."""
        if self._tb is None:
            return
        import numpy as np
        arr = np.asarray(images)
        if arr.dtype != np.uint8:
            arr = arr.astype(np.float32)
            lo = arr.min(axis=(1, 2, 3), keepdims=True)
            hi = arr.max(axis=(1, 2, 3), keepdims=True)
            arr = ((arr - lo) / np.maximum(hi - lo, 1e-6) * 255).astype(np.uint8)
        for i, img in enumerate(arr[:4]):
            self._tb.add_image(f"{tag}/{i}", img, int(step),
                               dataformats="HWC")

    def write_scalars(self, step: int, scalars: Dict[str, Any]) -> None:
        rec = {"step": int(step), "time": time.time()}
        for k, v in scalars.items():
            rec[k] = float(v)
        with self._wlock:
            self._write_line(json.dumps(rec) + "\n")
        if self._tb is not None:
            for k, v in scalars.items():
                self._tb.add_scalar(k, float(v), int(step))

    def write_event(self, event: str, payload: Dict[str, Any]) -> None:
        """Typed (non-scalar) JSONL record: ``{"event": <name>, ...}``.
        Consumers of metrics.jsonl that expect scalar rows must filter on
        the "event" key (read_metrics returns both kinds). ``event`` must
        be declared in EVENT_SCHEMAS — unknown names still write (a
        training run must not die on telemetry) but warn once."""
        if event not in EVENT_SCHEMAS and event not in _UNKNOWN_EVENTS_WARNED:
            _UNKNOWN_EVENTS_WARNED.add(event)
            log.warning(
                "metrics event %r is not declared in "
                "utils.metrics.EVENT_SCHEMAS — register it (the "
                "event-registry lint rejects undeclared literals)", event)
        rec = {"event": event, "time": time.time()}
        rec.update(payload)
        with self._wlock:
            self._write_line(json.dumps(rec) + "\n")

    def flush(self) -> None:
        # under _wlock: rotation closes and swaps the handle mid-write —
        # an unlocked flush from the watchdog/tracer thread could hit the
        # closed file
        with self._wlock:
            self._jsonl.flush()
        if self._tb is not None:
            self._tb.flush()

    def close(self) -> None:
        self.flush()
        self._jsonl.close()
        if self._tb is not None:
            self._tb.close()


class LatencyStats:
    """Thread-safe per-key latency recorder with percentile summaries.

    The serving path (serve/server.py) records one sample per request keyed
    by its dispatch bucket; ``summary_ms`` is what the ``serve_request``
    metrics rows and the ``main.py serve`` report both read — one
    implementation so p50/p99 can't be computed two different ways. Samples are capped (default 200k ≈ hours of smoke-load
    serving) to bound memory on long-lived servers; past the cap each new
    sample overwrites a deterministic pseudo-random slot, so the buffer
    becomes a RECENCY-WEIGHTED window (~the last cap samples; older ones
    decay away). For serving that is the useful estimate — current p99,
    not a lifetime average diluted by the warm-up epoch — but it is NOT an
    unbiased whole-run sample; ``count`` still reports the true total.
    """

    def __init__(self, max_samples_per_key: int = 200_000):
        self._lock = threading.Lock()
        self._samples: Dict[str, list] = {}
        self._counts: Dict[str, int] = {}
        self._max = max(1, max_samples_per_key)

    def record(self, key: str, seconds: float) -> None:
        with self._lock:
            buf = self._samples.setdefault(key, [])
            n = self._counts.get(key, 0)
            self._counts[key] = n + 1
            if len(buf) < self._max:
                buf.append(seconds)
            else:
                # deterministic LCG slot (no random import on the hot path)
                buf[(n * 48271 + 11) % self._max] = seconds

    def summary_ms(self) -> Dict[str, Dict[str, float]]:
        """key -> {count, p50_ms, p99_ms, mean_ms} over recorded samples."""
        import numpy as np
        with self._lock:
            snap = {k: (list(v), self._counts.get(k, 0))
                    for k, v in self._samples.items()}
        out = {}
        for key, (vals, count) in snap.items():
            if not vals:
                continue
            arr = np.asarray(vals) * 1000.0
            out[key] = {"count": count,
                        "p50_ms": round(float(np.percentile(arr, 50)), 3),
                        "p99_ms": round(float(np.percentile(arr, 99)), 3),
                        "mean_ms": round(float(arr.mean()), 3)}
        return out


class Throughput:
    """Steps/sec + images/sec meter over a sliding window."""

    def __init__(self, batch_size: int):
        self.batch_size = batch_size
        self._t0: Optional[float] = None
        self._step0: Optional[int] = None

    def reset(self) -> None:
        """Restart the window — call when training resumes after a pause
        (eval round, checkpoint restore): a window spanning non-training
        wall time would deflate steps/sec and the derived MFU column."""
        self._t0 = self._step0 = None

    def update(self, step: int) -> Dict[str, float]:
        now = time.monotonic()
        if self._t0 is None:
            self._t0, self._step0 = now, step
            return {}
        dt = now - self._t0
        dsteps = step - self._step0
        if dt <= 0 or dsteps <= 0:
            return {}
        out = {"steps_per_sec": dsteps / dt,
               "images_per_sec": dsteps * self.batch_size / dt}
        self._t0, self._step0 = now, step
        return out


def read_metrics(logdir: str, filename: str = "metrics.jsonl",
                 tolerant: bool = False):
    """Load the JSONL event stream back (for tests/analysis/monitor),
    including rotated segments in order: ``metrics.jsonl.N`` (oldest,
    highest N) down to ``.1``, then the live file — one continuous stream
    across rotations. ``tolerant`` skips torn lines (a live writer can be
    mid-row) instead of raising."""
    path = os.path.join(logdir, filename)
    segments = []
    i = 1
    while os.path.exists(f"{path}.{i}"):
        segments.append(f"{path}.{i}")
        i += 1
    paths = list(reversed(segments))
    if os.path.exists(path) or not paths:
        paths.append(path)  # preserve FileNotFoundError when nothing exists
    out = []
    for p in paths:
        with open(p) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except ValueError:
                    if not tolerant:
                        raise
    return out


def metric_stream_dirs(root: str, filename: str = "metrics.jsonl"):
    """Every directory holding a metrics stream under ``root`` (the root
    itself included — ``**`` matches zero directories) — the ONE
    stream-layout discovery the monitor and the offline reducers share;
    fix the layout here and every consumer follows. Readers differ on
    purpose: the monitor tails a bounded window per frame, the reducers
    read whole streams."""
    import glob as _glob
    return sorted({os.path.dirname(p) for p in _glob.glob(
        os.path.join(root, "**", filename), recursive=True)})


def iter_metric_streams(root: str, filename: str = "metrics.jsonl"):
    """Yield the rows of every metrics stream under ``root``, tolerant
    of torn lines and vanished files — the offline reducers' read path
    (`main.py trace-merge`)."""
    for d in metric_stream_dirs(root, filename):
        try:
            yield read_metrics(d, filename=filename, tolerant=True)
        except OSError:
            continue
