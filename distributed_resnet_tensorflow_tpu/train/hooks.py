"""Training hooks — plain host-side callbacks ``hook(step, state, metrics)``.

Successor of the reference's session-hook stack (SURVEY.md §2.11-2.15):
``LoggingTensorHook`` → LoggingHook, ``SummarySaverHook`` → SummaryHook,
``MonitoredTrainingSession`` checkpointing → CheckpointHook,
``_LearningRateSetterHook`` → gone (the LR schedule is computed inside the
jitted step, no per-step host feed).

Hooks receive the step's metrics as they left the jitted call: jax.Arrays,
futures of a dispatch the device may not have reached yet. Off their
cadence hooks touch none of them. At a cadence step the hooks that turn
metrics into host numbers (NanGuardHook, LoggingHook, SummaryHook) keep
the step number and the arrays, and read them at their NEXT call, one
dispatch later: ``Trainer.train`` has enqueued the following dispatch by
then, so the wait for the kept step's values ends with work still queued
on the device (a read of the dispatch just sent ends with the device
drained, and the host's whole next turn is then the chips' idle time:
6.7 of every 119 ms a step in ``vit_l16_dp4``, PERF.md §6 PR 32). Every
line, row and check carries the kept step's number and values;
``Trainer.train`` flushes a kept reading before it returns. What decides
is the value's type and nothing else: a host number (Python float, NumPy
scalar) is read at once. CheckpointHook reads the CURRENT metrics, at
once, when its cadence fires: the save gate never runs late.
"""
from __future__ import annotations

import logging
import time
from typing import Any, Dict, Optional, Tuple

import jax

from ..telemetry.tracer import span
from ..utils.metrics import MetricsWriter, Throughput

log = logging.getLogger(__name__)


from ..utils import cadence_crossed  # noqa: F401  (re-export; shared impl)


#: the divergence indicators: an exploding gradient shows in grad_norm a
#: step before the loss goes non-finite
DIVERGENCE_KEYS = ("loss", "grad_norm")


def nonfinite_metric(metrics: Optional[Dict[str, Any]]) -> Optional[str]:
    """The first divergence-indicator key ("loss", "grad_norm") whose value
    is non-finite, else None. ONE definition shared by NanGuardHook (the
    detector) and CheckpointHook (the save gate) — the pair must agree or a
    cadence save could commit the very state the guard is about to flag.
    Calling this forces a device sync (float()); gate on cadence first."""
    import math
    if not metrics:
        return None
    for key in DIVERGENCE_KEYS:
        value = metrics.get(key)
        if value is not None and not math.isfinite(float(value)):
            return key
    return None


class _CadenceHook:
    """Shared cadence cursor for hooks gating on ``cadence_crossed``, and
    the one late read of device metrics (module docstring): a subclass
    that turns metrics into host numbers states which (``_reads``) and
    what it does with them (``_emit``) and inherits ``__call__``; the
    exporters of host-side counters override ``__call__`` and keep
    nothing."""

    _last = 0
    #: (step, metrics, the entries to read) of a cadence step whose device
    #: values are unread
    _kept: Optional[Tuple[int, Dict[str, Any], Dict[str, Any]]] = None

    def rollback_to(self, step: int) -> None:
        """Rewind the cadence after a checkpoint rollback
        (resilience/sentinel.py): a cursor still pointing at the trip step
        would treat every replayed step as already-handled — for the NaN
        guard that is a blind window in which a cadence save could commit
        NaN params; for logging/summaries the replayed span would vanish.
        A kept reading belongs to the abandoned timeline and goes."""
        self._last = min(self._last, step)
        self._kept = None

    def _reads(self, metrics: Dict[str, Any]) -> Dict[str, Any]:
        """The entries of ``metrics`` this hook turns into host numbers."""
        raise NotImplementedError

    def _emit(self, step: int, metrics: Dict[str, Any],
              values: Dict[str, Any]) -> None:
        """Print, write or check ``values`` (``_reads(metrics)`` as host
        numbers) under ``step``."""
        raise NotImplementedError

    def __call__(self, step: int, state, metrics: Dict[str, Any]) -> None:
        self.flush()
        if not cadence_crossed(step, self.every_steps, self._last):
            return
        self._last = step
        wanted = self._reads(metrics)
        futures = [v for v in wanted.values() if isinstance(v, jax.Array)]
        if not futures:  # host numbers: nothing to wait for
            self._emit(step, metrics, wanted)
            return
        for v in futures:  # the scalars travel as soon as they exist
            v.copy_to_host_async()
        self._kept = (step, metrics, wanted)

    def flush(self) -> None:
        """Read a kept reading now and emit it under its own step: the
        hook's next call does this first, and ``Trainer.train`` before it
        returns, so no line is lost and a non-finite loss of the last
        cadence step still raises out of ``train``."""
        if self._kept is None:
            return
        (step, metrics, wanted), self._kept = self._kept, None
        # its cell: count = late reads, seconds = the loop's wait for the
        # device
        with span("train.hook_read"):
            host = jax.device_get(list(wanted.values()))
        # zipped back by hand: a dict through device_get comes back with
        # its keys sorted, and the line keeps the order of its columns
        self._emit(step, metrics, dict(zip(wanted, host)))


class _SnapshotExportHook(_CadenceHook):
    """Shared skeleton for the plan/summary exporters (Zero1Hook,
    PrecisionHook, CkptShardHook, MemoryHook):
    at the cadence, pull a snapshot row and write it as ONE
    ``{"event": <event>}`` record per CHANGE — these rows describe a
    property of the run's compiled programs / writer state, not of any
    single step, so re-exporting an unchanged row per cadence would be
    noise, while gating on anything less than the whole row freezes
    mid-flight values forever (the CkptAsyncHook lesson, round 10).
    Subclasses set ``event`` and implement ``_snapshot() -> dict|None``
    (None = nothing to export yet)."""

    event: str = ""

    def __init__(self, writer: MetricsWriter, every_steps: int = 100):
        self.writer = writer
        self.every_steps = max(1, every_steps)
        self._last = 0
        self._exported: Dict[str, Any] = {}

    def _snapshot(self) -> Optional[Dict[str, Any]]:
        raise NotImplementedError

    def __call__(self, step: int, state, metrics: Dict[str, Any]) -> None:
        if not cadence_crossed(step, self.every_steps, self._last):
            return
        self._last = step
        snap = self._snapshot()
        if snap is None:
            return
        if snap != self._exported:
            self._exported = snap
            self.writer.write_event(self.event, {"step": int(step),
                                                 **snap})


class LoggingHook(_CadenceHook):
    """Print step/loss/precision/lr every N steps + throughput (reference
    LoggingTensorHook cadence: 20 cifar / 40 imagenet,
    resnet_cifar_main.py:280-285)."""

    def __init__(self, every_steps: int = 20, batch_size: int = 0,
                 print_fn=None, step_flops: Optional[float] = None):
        self.every_steps = max(1, every_steps)
        self.throughput = Throughput(batch_size)
        self.print_fn = print_fn or (lambda s: log.info("%s", s))
        self.step_flops = step_flops  # enables an MFU column when known
        if step_flops:
            from ..utils.profiling import detect_peak_tflops
            # raises for an accelerator the peaks table does not know
            if detect_peak_tflops() is None:
                self.step_flops = None
                self.print_fn("no mfu column: the cpu backend has no peak "
                              "in utils/profiling.TPU_PEAK_TFLOPS")
        self._last = 0

    def reset_window(self) -> None:
        """Called by Trainer.train at segment start so the throughput
        window never spans an eval round / checkpoint pause between
        segments (which would deflate stp/s and MFU for the first line
        of each segment)."""
        self.throughput.reset()

    def _reads(self, metrics):
        return {k: metrics[k] for k in (
            "loss", "cross_entropy", "precision", "learning_rate",
            # a routing model's load (models/transformer.NextTokenObjective)
            "moe_assignments_held", "moe_load_max_over_mean",
            "moe_windows") if k in metrics}

    def _emit(self, step, metrics, values):
        # stamped with the values on the host: the device has finished
        # ``step``, so img/s is its rate and not the host's enqueue rate
        tp = self.throughput.update(step)
        parts = [f"step {step}"]
        parts += [f"{k} {float(v):.4f}" for k, v in values.items()]
        if tp:
            parts.append(f"{tp['steps_per_sec']:.2f} stp/s")
            if self.throughput.batch_size:
                parts.append(f"{tp['images_per_sec']:.0f} img/s")
            if self.step_flops:
                from ..utils.profiling import mfu
                util = mfu(tp["steps_per_sec"], self.step_flops)
                if util is not None:
                    parts.append(f"mfu {util * 100:.1f}%")
        self.print_fn("  ".join(parts))


class SummaryHook(_CadenceHook):
    """Write scalars to the MetricsWriter every N steps (reference
    SummarySaverHook every 100, resnet_cifar_main.py:274-278)."""

    def __init__(self, writer: MetricsWriter, every_steps: int = 100):
        self.writer = writer
        self.every_steps = max(1, every_steps)
        self._last = 0

    def _reads(self, metrics):
        return {k: v for k, v in metrics.items()
                if hasattr(v, "__float__") or isinstance(v, (int, float))}

    def _emit(self, step, metrics, values):
        self.writer.write_scalars(step,
                                  {k: float(v) for k, v in values.items()})


class InputStagesHook(_CadenceHook):
    """Export the input-pipeline stage counters (utils.metrics.input_stages:
    decode / stack / stage / transfer / dispatch_wait) to metrics.jsonl as a
    typed ``{"event": "input_stages", ...}`` record every N steps — the
    attribution telemetry docs/input_pipeline.md describes.
    Counters are cumulative since process start (or the last reset), so
    consumers can difference consecutive records for window rates."""

    def __init__(self, writer: MetricsWriter, every_steps: int = 100):
        self.writer = writer
        self.every_steps = max(1, every_steps)
        self._last = 0

    def __call__(self, step: int, state, metrics: Dict[str, Any]) -> None:
        if not cadence_crossed(step, self.every_steps, self._last):
            return
        self._last = step
        from ..utils.metrics import input_stages
        snap = input_stages.snapshot()
        if snap:
            self.writer.write_event("input_stages",
                                    {"step": int(step), "stages": snap})


class InputEchoHook(_CadenceHook):
    """Export the data-echoing cache counters (utils.metrics.echo_stats:
    decoded/emitted/hits/evictions + cache bytes) to metrics.jsonl as
    typed ``{"event": "input_echo"}`` rows every N steps — the telemetry
    docs/input_pipeline.md reads for the echo hit rate. Counters are cumulative, like input_stages; rows are
    only written once the echo path has actually served something (a run
    with echo_factor=1 emits nothing)."""

    def __init__(self, writer: MetricsWriter, every_steps: int = 100):
        self.writer = writer
        self.every_steps = max(1, every_steps)
        self._last = 0

    def __call__(self, step: int, state, metrics: Dict[str, Any]) -> None:
        if not cadence_crossed(step, self.every_steps, self._last):
            return
        self._last = step
        from ..utils.metrics import echo_stats
        snap = echo_stats.snapshot()
        if snap["emitted"]:
            self.writer.write_event("input_echo",
                                    {"step": int(step), **snap})


class GoodputHook(_CadenceHook):
    """Export the goodput classification (telemetry/goodput.py) to
    metrics.jsonl as ``{"event": "goodput"}`` rows every N steps: per-
    category seconds + percentages of the interval's wall clock, summing
    to ~100% by construction (compute is the remainder). The break-down an
    operator needs to know whether the cluster is training or waiting —
    and the number ROADMAP items 2 and 5 are measured against."""

    def __init__(self, writer: MetricsWriter, every_steps: int = 100):
        self.writer = writer
        self.every_steps = max(1, every_steps)
        self._last = 0
        self._based = False

    def reset_window(self) -> None:
        """Trainer.train calls this at every segment start; only the FIRST
        rebases the meter (setup/restore wall before step 1 must not be
        billed as compute). Later segment boundaries must NOT rebase: the
        pause between segments is an eval round or a checkpoint — exactly
        the wall time goodput exists to classify, unlike the throughput
        window (LoggingHook) which rightly excludes it."""
        if not self._based:
            self._based = True
            from ..telemetry.goodput import goodput
            goodput.rebase()

    def __call__(self, step: int, state, metrics: Dict[str, Any]) -> None:
        if not cadence_crossed(step, self.every_steps, self._last):
            return
        self._last = step
        from ..telemetry.goodput import goodput
        itv = goodput.interval()
        if itv["wall_secs"] > 0:
            self.writer.write_event("goodput", {"step": int(step), **itv})


class CkptAsyncHook(_CadenceHook):
    """Export the async-checkpoint charge split (utils.metrics.
    ckpt_async_stats: loop-thread snapshot/backpressure seconds vs
    writer-thread stage/fsync/commit seconds) as ``{"event": "ckpt_async"}``
    rows every N steps WHEN a save advanced since the last export — the
    row that proves the writer's wall time overlapped compute instead of
    stalling the loop (only the snapshot + backpressure legs also appear
    in the goodput ``checkpoint`` bucket). docs/resilience.md has the
    commit-timeline diagram these numbers annotate."""

    def __init__(self, writer: MetricsWriter, every_steps: int = 100):
        self.writer = writer
        self.every_steps = max(1, every_steps)
        self._last = 0
        self._exported: Dict[str, Any] = {}

    def __call__(self, step: int, state, metrics: Dict[str, Any]) -> None:
        if not cadence_crossed(step, self.every_steps, self._last):
            return
        self._last = step
        from ..utils.metrics import ckpt_async_stats
        snap = ckpt_async_stats.snapshot()
        # gate on the WHOLE snapshot changing, not just the save counter:
        # a row exported while the writer was still mid-commit would
        # otherwise freeze writer_seconds/committed at ~0 forever —
        # exactly the final save of every run
        if snap["saves"] > 0 and snap != self._exported:
            self._exported = snap
            self.writer.write_event("ckpt_async",
                                    {"step": int(step), **snap})


class CkptShardHook(_SnapshotExportHook):
    """Export THIS host's sharded-checkpoint accounting as
    ``{"event": "ckpt_shard"}`` rows every N steps when its shard bytes
    advanced — the per-host view ``main.py monitor`` rolls up into
    cluster shard-byte totals. Unlike the chief-only observability
    hooks this runs on EVERY process (each host stages only its own
    shard; the chief's row alone would claim the cluster wrote 1/N of
    what it did). Writes nothing on the single-payload layout (no
    shard files ever staged)."""

    event = "ckpt_shard"

    def _snapshot(self):
        from ..utils.metrics import ckpt_async_stats
        snap = ckpt_async_stats.snapshot()
        if not snap["shard_files"]:
            return None
        return {"process": jax.process_index(),
                "shard_bytes": snap["shard_bytes"],
                "shard_files": snap["shard_files"],
                "shard_seconds": snap["shard_seconds"],
                "finalize_wait_seconds": snap["finalize_wait_seconds"],
                "last_committed_step": snap["last_committed_step"]}


class Zero1Hook(_SnapshotExportHook):
    """Export the ZeRO-1 partition plan (parallel/sharding.zero1_stats:
    sharded/replicated leaf+byte counts, per-replica optimizer bytes,
    fallback reasons) as ONE ``{"event": "zero1"}`` row per resolved
    plan: the plan is a property of the compiled step. Writes nothing
    when optimizer.zero1 resolved off."""

    event = "zero1"

    def _snapshot(self):
        from ..parallel.sharding import zero1_stats
        return zero1_stats.snapshot()


class PrecisionHook(_SnapshotExportHook):
    """Export the resolved mixed-precision policy (parallel/precision.
    precision_stats: policy/compute/master dtypes, master-tree
    accounting) as ONE ``{"event": "precision"}`` row per resolved
    policy — the per-run precision summary (docs/precision.md). Writes
    nothing when no policy resolved on."""

    event = "precision"

    def _snapshot(self):
        from ..parallel.precision import precision_stats
        return precision_stats.snapshot()


class MemoryHook(_SnapshotExportHook):
    """Export the device/host memory sample (telemetry/memory.py:
    per-device live-array bytes + allocator stats where present, host
    RSS, echo-cache and staging-ring occupancy) as ``{"event": "memory"}``
    rows every N steps — the trend line that turns an OOM from a
    postmortem into a graph. Runs on EVERY process (each host samples its
    own devices; non-chief processes export into their per-process
    ``train-p<idx>`` stream, which ``main.py monitor`` rolls up into the
    per-host HBM watermark). Samples change between cadences, so the
    skeleton's change-gate passes and the rows form a time series — for
    memory that is the point, not noise."""

    event = "memory"

    def _snapshot(self):
        from ..telemetry.memory import sample_memory
        return sample_memory()


class CorruptRecordsHook(_CadenceHook):
    """Export the corrupt-TFRecord tally (data/tfrecord.corrupt_records) to
    metrics.jsonl as ``{"event": "corrupt_record"}`` rows — one row per
    cadence WHEN the count advanced, carrying the cumulative count, the
    per-reason breakdown, and the most recent offenders. Dataset bit rot
    thereby shows up in run telemetry instead of only in a decode worker's
    log file."""

    def __init__(self, writer: MetricsWriter, every_steps: int = 100):
        self.writer = writer
        self.every_steps = max(1, every_steps)
        self._last = 0
        self._exported_count = 0

    def __call__(self, step: int, state, metrics: Dict[str, Any]) -> None:
        if not cadence_crossed(step, self.every_steps, self._last):
            return
        self._last = step
        from ..data.tfrecord import corrupt_records
        snap = corrupt_records.snapshot()
        if snap["count"] > self._exported_count:
            self._exported_count = snap["count"]
            self.writer.write_event("corrupt_record",
                                    {"step": int(step), **snap})


class HeartbeatHook:
    """Feed the heartbeat publisher at every step boundary
    (resilience/heartbeat.py): one locked field write, no I/O — the
    publisher's daemon thread does the actual beat. Runs on EVERY process
    (unlike the chief-only observability hooks): peer-loss detection needs
    every host beating. Also maintains the rolling per-step-time estimate
    the watchdog derives its hang deadline from, which is why this hook is
    unthrottled — a cadence would quantize the estimate."""

    def __init__(self, publisher):
        self.publisher = publisher

    def __call__(self, step: int, state, metrics: Dict[str, Any]) -> None:
        self.publisher.update(step=step, phase="train")


class CheckpointHook:
    """Save via CheckpointManager on its step/time policy.

    Refuses to checkpoint a visibly non-finite state: with time-based
    cadence the save timer can fire between a loss blow-up and the NaN
    guard's next check, and a committed NaN checkpoint (valid manifest!)
    would then be what every rollback restores — defeating the recovery in
    resilience/sentinel.py. The finite check runs only when the cadence
    actually fires, so the hot path pays no device sync.

    ``heartbeat`` (assigned by main.py when the watchdog is armed) flips
    the phase to the unmonitored "save" around the save: a large state on
    a slow shared FS can legitimately stall the main thread past the hang
    deadline, and the watchdog must not 75 a healthy run mid-checkpoint.
    The phase flip also marks an EWMA interlude, so the save time never
    inflates the rolling step-time estimate."""

    def __init__(self, manager, heartbeat=None):
        self.manager = manager
        self.heartbeat = heartbeat

    def __call__(self, step: int, state, metrics: Dict[str, Any]) -> None:
        # gate first so the finite check (a device sync via float()) is
        # paid only when the cadence actually fires
        should = getattr(self.manager, "should_save", None)
        if should is not None and not should(step):
            return
        bad = nonfinite_metric(metrics)
        if bad is not None:
            log.warning("skipping checkpoint at step %d: non-finite %s "
                        "(the NaN guard will handle recovery)", step, bad)
            return
        if self.heartbeat is not None:
            self.heartbeat.set_phase("save")
            try:
                self.manager.maybe_save(step, state)
            finally:
                self.heartbeat.set_phase("train")
        else:
            self.manager.maybe_save(step, state)


class NanGuardHook(_CadenceHook):
    """Abort (or callback) on non-finite loss — active divergence detection.

    The reference's only guard was a human watching the 20-step loss log
    (SURVEY.md §4.4); a NaN there kept burning cluster hours until someone
    looked. Checks at a cadence to avoid forcing a device sync every step.
    """

    class NanLossError(RuntimeError):
        pass

    def __init__(self, every_steps: int = 100, on_nan=None):
        self.every_steps = max(1, every_steps)
        self.on_nan = on_nan
        self._last = 0

    def _reads(self, metrics):
        return {k: v for k in DIVERGENCE_KEYS
                if (v := (metrics or {}).get(k)) is not None}

    def _emit(self, step, metrics, values):
        # loss AND grad_norm (nonfinite_metric): an exploding gradient
        # shows up in grad_norm a step before the loss goes non-finite
        # (the optimizer has already eaten the inf update by then) —
        # catching either is the trigger for the rollback policy in
        # resilience/sentinel.py
        bad = nonfinite_metric(values)
        if bad is not None:
            if self.on_nan is not None:
                self.on_nan(step, metrics)
                return
            raise self.NanLossError(
                f"non-finite {bad} {float(values[bad])} at step {step}")
