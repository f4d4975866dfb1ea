"""The inference server: AOT cache + dynamic batcher + hot swap, composed.

``InferenceServer`` owns a Trainer (model + mesh + shardings — the same
construction path training and eval use), serves single-example requests
through the dynamic batcher, and follows the training run's checkpoint
directory via the hot-swap thread. ``main.py serve`` builds one, optionally
drives the open-loop load generator against it, and prints a JSON report
(p50/p99 latency and QPS per bucket).

Threading recap (docs/serving.md has the diagram):
  * submitter threads — numpy in, Future out (``submit``);
  * ONE dispatch thread — stages batches through the Trainer's put path
    (CoalescedStager on accelerators), finalizes, executes the
    AOT-compiled predict, resolves futures, applies pending swaps at batch
    boundaries;
  * swap thread — filesystem + host deserialization only.
The dispatch sanitizer (PR 5) passes over this arrangement by
construction; ``scripts/serve_smoke.sh`` runs with it armed.
"""
from __future__ import annotations

import logging
import os
import threading
import time
from concurrent.futures import Future
from typing import Optional, Tuple

import jax
import numpy as np

from ..telemetry.tracer import span
from ..train.loop import Trainer
from ..utils.config import ExperimentConfig, resolve_checkpoint_dir
from ..utils.metrics import LatencyStats, MetricsWriter
from .batcher import DynamicBatcher
from .compile_cache import ServeCompileCache, bucket_sizes
from .swap import CheckpointSwapper, PendingSwap

log = logging.getLogger(__name__)

#: dispatch batches between ``{"event": "memory"}`` samples
#: (telemetry.memory gates the rows entirely): coarse enough that the
#: live_arrays scan never shows in serve tail latency, fine enough that a
#: leak over a day-long run has hundreds of trend points
_MEMORY_EVERY_BATCHES = 50


def serve_stream_dir(cfg: ExperimentConfig) -> str:
    """Where this serving process keeps its metrics stream / READY marker
    / swap pin: ``<log_root>/serve`` standalone, ``<log_root>/serve-r<id>``
    as a fleet replica (matches serve/fleet.replica_dir so supervisor and
    replica agree without talking)."""
    sub = "serve" if cfg.serve.replica_id < 0 \
        else f"serve-r{cfg.serve.replica_id}"
    return os.path.join(cfg.log_root, sub)


def serve_image_spec(cfg: ExperimentConfig) -> Tuple[Tuple[int, ...], type]:
    """(per-example shape, dtype) of a serving request — must match what
    the eval input pipeline would deliver, because the predict step shares
    the eval step's prep contract (make_predict_step): imagenet with
    device-side standardize takes raw uint8 crops, everything else
    host-prepped float32."""
    from ..data import device_augment_enabled
    if cfg.model.name == "logistic":
        return (cfg.model.input_size,), np.float32
    s = cfg.data.image_size
    if cfg.data.dataset == "imagenet" and device_augment_enabled(cfg, "eval"):
        return (s, s, 3), np.uint8
    return (s, s, 3), np.float32


class InferenceServer:
    """Batched, hot-swappable inference over a training run's checkpoints.

    Single-process (a serving replica is one jax world; fleet-level
    replication is the launcher's job). ``start()`` restores the newest
    committed checkpoint (if any), AOT-warms every (bucket, variant),
    then starts the dispatch + swap threads; ``submit()`` returns a
    Future resolving to ``(logits_row, served_step)``.
    ``start(start_threads=False)`` leaves the threads off for
    deterministic single-thread driving (``service_once`` — tests, bench
    warm paths).

    Variants (``serve.variants``; docs/precision.md): each configured
    precision variant ("bf16") carries its own weight copy cast from the
    f32 masters and its own AOT bucket programs; requests pick one at
    ``submit(variant=...)`` and hot swaps rebuild every variant from the
    newly restored masters, so no variant can lag a checkpoint behind.
    """

    def __init__(self, cfg: ExperimentConfig,
                 writer: Optional[MetricsWriter] = None, mesh=None):
        if jax.process_count() > 1:
            raise NotImplementedError(
                "serve/ is single-process per replica; run one server per "
                "host and load-balance above them")
        self.cfg = cfg
        self.writer = writer
        self.trainer = Trainer(cfg, mesh=mesh)
        self.trainer.init_state()
        # serving precision variants (docs/precision.md): every variant
        # keeps its own weight copy cast from the f32 masters + its own
        # AOT programs; the FIRST is the default a variant-less request
        # gets. The f32 masters themselves live on the trainer state —
        # variants are rebuilt from them at every (startup/hot) swap.
        from ..parallel.precision import (make_variant_cast,
                                          resolve_serve_variants)
        self.variants = resolve_serve_variants(cfg)
        self._variant_casts = {v: make_variant_cast(v)
                               for v in self.variants}
        # the f32 MASTER state every variant casts from — kept even when
        # "f32" is not a served variant (swap validation compares
        # checkpoints against the masters, never a cast copy). Variant
        # weight copies are built LAZILY (start() after the restore
        # attempt, or first dispatch): casting fresh-init params that a
        # startup restore immediately replaces would waste a per-leaf
        # device cast and transient HBM per non-f32 variant.
        self._master_state = self.trainer.state
        self._states = None
        self.serving_step = -1  # -1 = fresh init, no checkpoint applied
        self.image_shape, self.image_dtype = serve_image_spec(cfg)
        max_batch = cfg.serve.max_batch or cfg.data.eval_batch_size
        self.buckets = bucket_sizes(max_batch,
                                    self.trainer.eval_pad_multiple())
        variant_predicts = {
            v: self.trainer.make_variant_predict_step(v)
            for v in self.variants if v != "f32"}
        if "f32" in self.variants and self.trainer.precision_active:
            # the f32 variant is the FULL-PRECISION oracle even when the
            # serving config carries a bf16 TRAINING policy: the
            # trainer's own predict step computes in the policy dtype,
            # so the f32 variant needs its own f32-compute program
            variant_predicts["f32"] = \
                self.trainer.make_variant_predict_step("f32")
        self.cache = ServeCompileCache(self.trainer,
                                       variant_predicts=variant_predicts)
        self.latency = LatencyStats()
        # fleet mode: swaps follow the router's per-replica pin file
        # (canary/rollback control) instead of chasing the newest commit
        gate = os.path.join(serve_stream_dir(cfg), "SWAP_CONTROL.json") \
            if cfg.serve.swap_gate else None
        self.swapper = CheckpointSwapper(
            resolve_checkpoint_dir(cfg),
            poll_secs=cfg.serve.poll_interval_secs,
            on_reject=self._on_swap_reject,
            seed=cfg.serve.load_seed,
            gate_path=gate)
        self.batcher = DynamicBatcher(
            self.buckets, self._run_bucket, self.image_shape,
            self.image_dtype,
            max_queue_delay_ms=cfg.serve.max_queue_delay_ms,
            boundary_hook=self._apply_pending_swap,
            variants=self.variants)
        self.completed = 0
        self.swaps = 0
        self._t_start = time.monotonic()
        self._closed = False
        self._batches_since_mem = 0  # serve-side memory-row cadence
        # fleet chaos knobs (DRT_FAULT_SERVE_*, scoped by replica id) —
        # inert unless armed; fired at the top of every dispatch batch
        from ..resilience.faultinject import ServeFaults
        self._faults = ServeFaults.from_env(cfg.serve.replica_id)
        # optional HeartbeatPublisher a fleet replica's run loop attaches
        # (main.py run_serve); the dispatch thread updates step/progress
        # so a wedged dispatch shows as frozen progress with live beats
        self.heartbeat = None

    # -- lifecycle ---------------------------------------------------------
    def start(self, start_threads: bool = True) -> "InferenceServer":
        # initial restore runs the swap machinery ONCE on the caller
        # thread (host restore + device_put — no multi-device execution,
        # so thread ownership is not claimed here): the newest committed
        # step that VERIFIES — older good checkpoints beat serving random
        # params when the single newest commit is torn. take_pending()
        # CLAIMS the parked swap — otherwise the dispatch thread's first
        # boundary hook would re-apply the same checkpoint a second time
        pending = self.swapper.take_pending() \
            if self.swapper.restore_newest_valid() is not None else None
        if pending is not None:
            self._apply_swap(pending)  # builds the variant states
            # `swaps` counts HOT swaps (a checkpoint published while
            # serving): the startup restore is not one, and counting it
            # would let the smoke's "a hot swap landed" assertion pass
            # with hot swap entirely broken
            self.swaps = 0
        else:
            log.warning(
                "serve: no usable committed checkpoint in %s — serving "
                "freshly initialized params until a training run "
                "publishes one", self.swapper.directory)
        if self._states is None:  # no restore landed: cast the init state
            self._states = self._build_variant_states(self._master_state)
        if self.cfg.serve.warm_buckets:
            warm = self.cache.warm(self.buckets, self.image_shape,
                                   self.image_dtype,
                                   variants=self.variants)
            log.info("serve: %d bucket(s) %s × %d variant(s) %s "
                     "AOT-compiled in %.1fs", len(self.buckets),
                     self.buckets, len(self.variants),
                     list(self.variants), warm)
        if start_threads:
            # a jitted state init already ran on this (caller) thread; the
            # dispatch thread owns all multi-device executions from here on
            # — tell an armed sanitizer this is a legitimate handoff
            from ..analysis import dispatch_sanitizer as _ds
            if _ds.is_installed():
                _ds.reset_owner()
            self.batcher.start()
            self.swapper.start()
        self._t_start = time.monotonic()
        return self

    def close(self) -> None:
        """Drain + stop: intake closes first, every accepted request is
        answered before the dispatch thread exits (zero dropped), then the
        swap thread stops. Idempotent."""
        if self._closed:
            return
        self._closed = True
        self.batcher.close()
        self.swapper.close()
        self._write_request_summary()

    # -- variant states ----------------------------------------------------
    def _build_variant_states(self, f32_state):
        """Cast the f32 master state into every configured variant's
        weight copy (parallel/precision.make_variant_cast). Runs on the
        thread that owns dispatch at the time: the caller thread during
        __init__/startup (before the dispatch thread exists), the
        dispatch thread at hot-swap boundaries."""
        out = {}
        for v in self.variants:
            with span("serve.variant_build", variant=v):
                out[v] = self._variant_casts[v](f32_state)
        return out

    # -- request path ------------------------------------------------------
    def submit(self, image, variant: Optional[str] = None) -> Future:
        """One example in, Future of ``(logits_row, served_step)`` out.
        ``variant`` picks the serving precision variant (None = the
        configured default; unknown names are rejected loudly)."""
        return self.batcher.submit(image, variant=variant)

    def service_once(self, block_secs: float = 0.0) -> int:
        """Single synchronous service turn on the calling thread (see
        DynamicBatcher.service_once) — deterministic tests/embedding."""
        return self.batcher.service_once(block_secs)

    def _run_bucket(self, images: np.ndarray, group) -> None:
        """Dispatch-thread only: stage → finalize → compiled predict →
        resolve futures. ``images`` is already padded to its bucket; the
        group is single-variant by the batcher's collection contract."""
        from ..parallel.sharding import finalize_staged
        self._faults.maybe_fire(self.batcher.batches + 1, self.serving_step)
        if self.heartbeat is not None:
            self.heartbeat.update(step=max(0, self.serving_step))
        t0 = time.perf_counter()
        bucket = images.shape[0]
        variant = group[0].variant
        if self._states is None:
            # dispatch before start() (thread-less embedding driving the
            # batcher directly): build here, on the thread that owns
            # dispatch by definition
            self._states = self._build_variant_states(self._master_state)
        with span("serve.batch", bucket=bucket, n=len(group),
                  variant=variant):
            compiled = self.cache.get(bucket, self.image_shape,
                                      self.image_dtype, variant=variant)
            # the Trainer's put path: CoalescedStager on accelerators (one
            # batched transfer issue), per-leaf device_put fallback on CPU;
            # finalize (a multi-device execution) stays on THIS thread
            dev = finalize_staged(self.trainer._put_batch({"images": images}))
            logits = np.asarray(compiled(self._states[variant], dev))
        t1 = time.perf_counter()
        step = self.serving_step
        # latency keys carry the variant past the default f32 — the
        # (batch, variant) breakdown the serve report shows; plain
        # f32 keys keep their historical names
        key = f"bucket_{bucket}" if variant == "f32" \
            else f"bucket_{bucket}_{variant}"
        for i, req in enumerate(group):
            req.future.set_result((logits[i], step))
            self.latency.record(key, t1 - req.t_submit)
        self.completed += len(group)
        if self.writer is not None:
            self.writer.write_event("serve_batch", {
                "step": step, "bucket": bucket, "n": len(group),
                "variant": variant,
                "queue_ms": round((t0 - group[0].t_submit) * 1000.0, 3),
                "run_ms": round((t1 - t0) * 1000.0, 3)})
            self._batches_since_mem += 1
            if self._batches_since_mem >= _MEMORY_EVERY_BATCHES:
                self._write_memory_row()

    def _write_memory_row(self) -> None:
        """One ``{"event": "memory"}`` sample (telemetry/memory.py) from
        the serving process — HBM/RSS trend lines for a server that runs
        for days, at the batch cadence so an idle server stays silent."""
        if self.writer is None or not self.cfg.telemetry.memory:
            return
        self._batches_since_mem = 0
        from ..telemetry.memory import sample_memory
        self.writer.write_event("memory", {"step": self.serving_step,
                                           **sample_memory()})

    # -- hot swap ----------------------------------------------------------
    def _apply_pending_swap(self) -> None:
        """Batch-boundary hook (dispatch thread): apply a restored
        checkpoint atomically between batches."""
        pending = self.swapper.take_pending()
        if pending is not None:
            self._apply_swap(pending)

    def _apply_swap(self, pending: PendingSwap) -> None:
        with span("serve.swap_apply", step=pending.step):
            self._apply_swap_inner(pending)

    def _apply_swap_inner(self, pending: PendingSwap) -> None:
        from ..parallel.sharding import put_to_sharding
        t0 = time.perf_counter()
        # validate against the F32 MASTER state: checkpoints always
        # persist f32 masters (docs/precision.md), so the shape/dtype
        # check must not compare against a cast variant's bf16 leaves
        live = self._master_state

        def check_leaf(host_leaf, live_leaf):
            # validate BEFORE any placement: a same-structure checkpoint
            # from a different model config (other num_classes/width)
            # would device_put fine and then blow up the AOT-compiled
            # executable on EVERY subsequent request — reject it here
            # instead, with the offending shapes
            hs, hd = np.shape(host_leaf), np.asarray(host_leaf).dtype
            if hs != live_leaf.shape or hd != live_leaf.dtype:
                raise ValueError(
                    f"checkpoint leaf {hs}/{hd} != serving model "
                    f"{live_leaf.shape}/{live_leaf.dtype}")
            return host_leaf

        try:
            # tree_map also raises on structure mismatch
            jax.tree_util.tree_map(check_leaf, pending.params, live.params)
            jax.tree_util.tree_map(check_leaf, pending.batch_stats,
                                   live.batch_stats)
            params_sh = jax.tree_util.tree_map(lambda x: x.sharding,
                                               live.params)
            bs_sh = jax.tree_util.tree_map(lambda x: x.sharding,
                                           live.batch_stats)
            new_params = put_to_sharding(pending.params, params_sh)
            new_bs = put_to_sharding(pending.batch_stats, bs_sh)
        except Exception as e:
            # a structure/shape mismatch (checkpoint from a different
            # model/config sharing the directory) must not take the
            # replica down — keep serving the old params, loudly
            self.swapper.rejected += 1
            log.exception("serve swap: checkpoint step %d does not fit the "
                          "serving model — keeping current params",
                          pending.step)
            self._on_swap_reject(pending.step,
                                 f"state mismatch: {type(e).__name__}: {e}")
            return
        new_step = put_to_sharding(
            np.asarray(pending.step, np.asarray(live.step).dtype),
            live.step.sharding)
        old = self.serving_step
        # one reference assignment = the atomic swap: the dispatch thread
        # is the only reader on the request path, and it is HERE, between
        # batches — in-flight requests completed on the old states, the
        # next batch reads `self._states`. EVERY variant rebuilds from
        # the new f32 masters (the cast is the swap's only extra cost),
        # so no variant can serve a stale checkpoint
        self._master_state = live.replace(step=new_step, params=new_params,
                                          batch_stats=new_bs)
        self._states = self._build_variant_states(self._master_state)
        self.serving_step = int(pending.step)
        self.swaps += 1
        apply_ms = (time.perf_counter() - t0) * 1000.0
        log.info("serve swap: now serving checkpoint step %d (was %s; "
                 "restore %.0fms off-path, apply %.0fms)", pending.step,
                 old if old >= 0 else "fresh init", pending.restore_ms,
                 apply_ms)
        if self.writer is not None:
            self.writer.write_event("serve_swap", {
                "from_step": old, "to_step": pending.step,
                "digest": pending.digest,
                "restore_ms": round(pending.restore_ms, 1),
                "apply_ms": round(apply_ms, 1)})

    def _on_swap_reject(self, step: int, reason: str) -> None:
        if self.writer is not None:
            self.writer.write_event("serve_swap", {
                "from_step": self.serving_step, "rejected": reason,
                "to_step_attempted": step})

    # -- reporting ---------------------------------------------------------
    @property
    def dropped(self) -> int:
        """Accepted requests not yet answered (contract after close: 0)."""
        done = self.completed + self.batcher.failed_requests
        return max(0, self.batcher.requests_in - done)

    def _write_request_summary(self) -> None:
        if self.writer is not None and self.batcher.requests_in:
            self.writer.write_event("serve_request", {
                "step": self.serving_step,
                "requests": self.completed, "dropped": self.dropped,
                "buckets": self.latency.summary_ms()})
            self._write_memory_row()  # the run's closing watermark

    def report(self) -> dict:
        """Snapshot report (pure read — the serve_request metrics row is
        written by close(), so report() stays callable after teardown)."""
        wall = max(time.monotonic() - self._t_start, 1e-9)
        return {
            "serving_step": self.serving_step,
            "variants": list(self.variants),
            "requests": self.batcher.requests_in,
            "completed": self.completed,
            "dropped": self.dropped,
            "errors": self.batcher.errors,
            "batches": self.batcher.batches,
            "qps": round(self.completed / wall, 1),
            "swaps": self.swaps,
            "rejected_swaps": self.swapper.rejected,
            "buckets": self.buckets,
            "latency_by_bucket_ms": self.latency.summary_ms(),
            "compile": {
                "warm_secs": round(self.cache.warm_secs, 2),
                "serve_time_compiles": self.cache.serve_time_compiles,
            },
        }
