"""CLI entry point — the successor of ALL the reference mains.

One binary replaces resnet_cifar_main.py / resnet_imagenet_main.py /
resnet_cifar_main_horovod.py / resnet_single.py / resnet_cifar_eval.py /
resnet_imagenet_eval.py (SURVEY.md §1 L3): dataset and topology are config,
not separate entry points, and there is no ps/worker split to dispatch on.

Usage:
    python -m distributed_resnet_tensorflow_tpu.main --preset cifar10_resnet50 \
        --set train.batch_size=256 --set log_root=/tmp/run1
    python -m distributed_resnet_tensorflow_tpu.main --preset cifar10_resnet50 \
        --set mode=eval          # standalone polling evaluator

Multi-host: launch one copy per TPU host (launcher.py / SLURM shim); every
process runs this same SPMD program — replacing the reference's per-role
process trees (reference resnet_cifar_main.py:339-399).
"""
from __future__ import annotations

import contextlib
import logging
import os
import sys
import time
from typing import Optional

import jax

from .checkpoint import CheckpointManager
from .data import create_input_iterator
from .evaluator import Evaluator, make_eval_iterator
from .parallel import initialize_from_config, is_chief
from .resilience import Preempted, PreemptionListener, RESUMABLE_EXIT_CODE
from .resilience.elastic import (ElasticImpossible, ElasticRuntime,
                                 ReshardRequired)
from .resilience.heartbeat import (PHASE_DONE, PHASE_FAILED,
                                   PHASE_PREEMPTED, PHASE_RESHARD)
from .resilience.preemption import (collective_preempted,
                                    collective_should_stop)
from .resilience.faultinject import maybe_wrap_from_env
from .resilience.sentinel import train_with_nan_recovery
from .telemetry import configure_from_config as _configure_telemetry
from .telemetry.tracer import recorder as _flight_recorder
from .train.hooks import (CheckpointHook, CkptAsyncHook, CkptShardHook,
                          CorruptRecordsHook, GoodputHook, HeartbeatHook,
                          InputEchoHook, InputStagesHook, LoggingHook,
                          MemoryHook, NanGuardHook, PrecisionHook,
                          SummaryHook, Zero1Hook)
from .train.loop import Trainer
from .utils.compile_cache import configure_compile_cache
from .utils.config import (ExperimentConfig, parse_args,
                           resolve_checkpoint_dir, stacked_layout_stamp)
from .utils.metrics import MetricsWriter

log = logging.getLogger(__name__)


def _make_writer(cfg: ExperimentConfig, sub: str) -> MetricsWriter:
    """The run's metrics stream, size-bounded per the telemetry knobs
    (utils/metrics.MetricsWriter rotation): one construction site so every
    mode gets the same disk bound."""
    t = cfg.telemetry
    return MetricsWriter(
        os.path.join(cfg.log_root, sub),
        max_bytes=int(t.metrics_max_mb * 1024 * 1024),
        max_segments=t.metrics_max_segments)


def _per_process_batch(global_bs: int, nproc: int) -> int:
    """Global batch must divide evenly across input shards — a silent floor
    would train a different effective batch than configured."""
    if global_bs % nproc:
        raise ValueError(
            f"train.batch_size={global_bs} is not divisible by "
            f"{nproc} input shards; the global batch would silently shrink")
    return global_bs // nproc


def _make_train_source(cfg: ExperimentConfig, trainer: Trainer):
    """Training data source. Device-resident dataset (host ships indices,
    data/device_dataset.py) when enabled; otherwise the streamed per-process
    input shard (fixes the reference Horovod path's unsharded input,
    SURVEY.md §3.2)."""
    from .data import device_dataset_enabled
    if device_dataset_enabled(cfg, "train"):
        from .data import load_cifar
        from .data.device_dataset import epoch_index_iterator
        images, labels = load_cifar(
            cfg.data.dataset, cfg.data.data_dir, "train",
            use_native=cfg.data.use_native_loader)
        trainer.attach_device_dataset(images, labels)
        log.info("device-resident dataset: %d examples in HBM", len(labels))
        return epoch_index_iterator(len(labels), cfg.train.batch_size,
                                    cfg.train.seed)
    # input shards are keyed by the process's BATCH slice, not its index:
    # when a non-batch mesh axis (pipeline/tensor/...) spans processes,
    # replica processes must feed identical data (parallel/mesh.py
    # process_batch_slice)
    from .parallel.mesh import batch_slice_replicated, process_batch_slice
    shard_index, num_shards = process_batch_slice(trainer.mesh)
    it = create_input_iterator(
        cfg, mode="train", shard_index=shard_index,
        num_shards=num_shards,
        batch_size=_per_process_batch(cfg.train.batch_size, num_shards),
        deterministic=batch_slice_replicated(trainer.mesh))
    # inert unless the chaos harness armed it via env
    # (resilience/faultinject.py; tests/test_resilience.py)
    return maybe_wrap_from_env(it)


def _start_watchdog(cfg: ExperimentConfig, writer, listener,
                    trainer: Optional[Trainer] = None,
                    role: str = "train", elastic=None):
    """Build + start the heartbeat publisher and the health watchdog
    (resilience/heartbeat.py, resilience/watchdog.py) when enabled —
    ``resilience.watchdog.enabled=auto`` resolves to on iff the run has
    peers. Returns (publisher, watchdog), both None when disabled.

    The watchdog escalates through ``listener.request_stop`` (graceful,
    coordinated stop at a step boundary) before its hard ``os._exit(75)``;
    the publisher is attached to the trainer so eval batches tick liveness
    too. ``role`` scopes the default beat directory: a standalone
    evaluator job is its OWN jax world but shares ``log_root`` with the
    trainers — publishing into their dir as "process 0" would mask
    trainer-0's death from its peers and pollute their straggler
    accounting."""
    from .resilience.watchdog import Watchdog, watchdog_enabled
    wd_cfg = cfg.resilience.watchdog
    if not watchdog_enabled(wd_cfg, jax.process_count()):
        return None, None
    from .resilience.heartbeat import FileBeatTransport, HeartbeatPublisher
    subdir = "heartbeats" if role == "train" else f"heartbeats-{role}"
    if wd_cfg.heartbeat_dir:
        # an explicit override is still role-scoped: trainers keep the
        # exact dir, a non-train world gets a subdir under it — otherwise
        # a standalone evaluator sharing the config would impersonate
        # trainer process 0 in the trainers' beat directory
        hb_dir = wd_cfg.heartbeat_dir if role == "train" \
            else os.path.join(wd_cfg.heartbeat_dir, role)
    else:
        hb_dir = os.path.join(cfg.log_root, subdir)
    transport = FileBeatTransport(hb_dir, jax.process_index())
    publisher = HeartbeatPublisher(
        transport, jax.process_index(),
        interval_secs=wd_cfg.interval_secs,
        # beats are generation-stamped so the monitor (and a peer's
        # straggler accounting) can tell a live host of generation g from
        # a stale file of generation g-1 (resilience/elastic.py)
        generation=elastic.generation if elastic is not None else 0).start()
    if trainer is not None:
        trainer.heartbeat = publisher
    watchdog = Watchdog(
        transport, publisher, jax.process_index(), jax.process_count(),
        wd_cfg, writer=writer,
        request_stop=listener.request_stop if listener is not None else None,
        # perf-anomaly sentinel knobs (telemetry.anomaly_*): the online
        # step-time outlier detector rides the watchdog's detection thread
        anomaly_cfg=cfg.telemetry,
    ).start()
    if elastic is not None:
        # escalation fork: a peer-lost verdict defers its hard exit while
        # this process can reshard instead (resilience/watchdog.py)
        watchdog.set_elastic(elastic.watchdog_defer)
    log.info("health watchdog armed: %d processes, beats -> %s "
             "(peer_timeout %.0fs, grace %.0fs)", jax.process_count(),
             hb_dir, wd_cfg.peer_timeout_secs, wd_cfg.grace_secs)
    return publisher, watchdog


def _teardown_watchdog(publisher, watchdog, final_phase: str) -> None:
    """Orderly watchdog shutdown: disarm FIRST (the run is leaving through
    a legitimate path; the daemon must not hard-exit under it), then
    publish the final phase so peers distinguish done/preempted (clean
    departure) from failed (stop resumable, surface the real error)."""
    if watchdog is not None:
        watchdog.close()
    if publisher is not None:
        publisher.close(final_phase)


@contextlib.contextmanager
def _watchdog_session(cfg: ExperimentConfig, writer, listener,
                      trainer: Optional[Trainer] = None,
                      role: str = "train", elastic=None):
    """The teardown choreography every entry point needs, in ONE place:
    success publishes a final ``done`` beat, Preempted publishes
    ``preempted`` (clean coordinated departure — peers must not flag us as
    lost), and any other error first asks the watchdog whether a PEER
    caused it (exits with the verdict code; does not return) before
    publishing ``failed``. With a live elastic runtime the peer-lost exit
    becomes a :class:`ReshardRequired` unwind instead, leaving through the
    ``reshard`` final phase (a coordinated departure into the next mesh
    generation — resilience/elastic.py). Yields (publisher, watchdog),
    both None when the watchdog is disabled."""
    publisher, watchdog = _start_watchdog(cfg, writer, listener, trainer,
                                          role=role, elastic=elastic)
    try:
        yield publisher, watchdog
    except Preempted:
        _teardown_watchdog(publisher, watchdog, PHASE_PREEMPTED)
        raise
    except ReshardRequired:
        # the grow path raises from the step loop itself (post-loop fork
        # in _train_one_generation): a clean departure into the barrier
        _teardown_watchdog(publisher, watchdog, PHASE_RESHARD)
        raise
    except BaseException as e:
        if isinstance(e, Exception):
            # a collective error caused by a dead peer exits 75 here
            # (does not return) — or, elastic, unwinds into the reshard
            # barrier; our OWN errors fall through and propagate
            try:
                _exit_for_peer_failure(watchdog, e, elastic=elastic)
            except ReshardRequired as rr:
                _teardown_watchdog(publisher, watchdog, PHASE_RESHARD)
                raise rr from e
        _teardown_watchdog(publisher, watchdog, PHASE_FAILED)
        raise
    else:
        _teardown_watchdog(publisher, watchdog, PHASE_DONE)


def _arm_watchdog_hooks(hooks: list, publisher) -> None:
    """Wire the heartbeat publisher into the step-hook chain — shared by
    run_train and run_train_and_eval so the two can't drift."""
    if publisher is None:
        return
    # position 0: the beat must reflect step N even if a later hook
    # raises mid-chain
    hooks.insert(0, HeartbeatHook(publisher))
    for h in hooks:
        # cadence saves flip to the unmonitored "save" phase — a slow
        # shared-FS save must not read as a hang
        if isinstance(h, CheckpointHook):
            h.heartbeat = publisher


#: substrings that mark an exception as possibly caused by a dead/wedged
#: peer (gloo transport, XLA collectives, the jax coordination service) —
#: only these are worth the failure_verdict beat-poll; a plainly local
#: error (NaN give-up, corrupt data, a hook TypeError) must propagate
#: immediately, not stall every process ~peer_timeout_secs first.
#: Deliberately BROAD ("connection", "timeout", "unavailable" can match a
#: local NFS/object-store error too): a false positive costs one bounded
#: ~peer_timeout beat-poll on an already-fatal crash, a false negative
#: turns a requeue-able peer loss into a real-failure exit code
_COLLECTIVE_ERROR_MARKERS = (
    "collective", "gloo", "allreduce", "all-reduce", "all_gather",
    "allgather", "connection", "socket", "barrier", "coordination",
    "distributed", "deadline", "timed out", "timeout", "unavailable",
    "peer", "preempt")


def _collective_shaped(exc: BaseException) -> bool:
    text = f"{type(exc).__name__}: {exc}".lower()
    return any(m in text for m in _COLLECTIVE_ERROR_MARKERS)


def _exit_for_peer_failure(watchdog, exc: BaseException, elastic=None):
    """After a runtime error in a multi-process step: if the beats say a
    peer died or reported failure, exit with the watchdog's verdict code
    (75 = peer loss, requeue; 1 = peer's real failure) instead of letting
    the exception propagate into the atexit ``jax.distributed.shutdown``
    barrier — which would block on the very peers that are gone.

    With a live elastic runtime, a peer-LOST verdict raises
    :class:`ReshardRequired` instead — the shrink entry: the survivors
    meet in the join barrier and continue as a smaller mesh generation;
    exit 75 is now the FALLBACK for when that is impossible
    (docs/resilience.md). A peer-FAILED verdict (the peer reported its
    own real error) still exits 1 — resharding around a determinism bug
    would silently change the experiment.

    Collective-shaped errors poll the beats up to the watchdog's default
    wait (the error can surface milliseconds after the peer died, before
    its beats age past the timeout); other errors get one immediate check
    only — they are our own, and the stall would cost every process
    ~peer_timeout_secs per crash."""
    if watchdog is None:
        return
    verdict = watchdog.failure_verdict(
        wait_secs=None if _collective_shaped(exc) else 0.0)
    if verdict is not None:
        kind, code, detail = verdict
        if kind == "peer_lost" and elastic is not None \
                and elastic.can_reshard():
            log.warning("peer loss behind %r — entering the elastic "
                        "reshard barrier instead of exit 75 (%s)",
                        exc, detail)
            raise ReshardRequired("peer_lost", detail)
        log.error("step loop error attributed to a peer (%s): %r",
                  kind, exc)
        watchdog.exit_now(kind, code, detail)  # does not return


def _peek(data_iter):
    """(first_batch_or_None, iterator yielding the same stream)."""
    import itertools
    try:
        first = next(data_iter)
    except StopIteration:
        return None, data_iter
    return first, itertools.chain([first], data_iter)


def _write_input_grid(writer: MetricsWriter, batch, trainer: Trainer) -> None:
    """One grid of raw input images at step 1 (reference cifar_input.py:114
    logged every summarized batch; once is the useful part)."""
    import numpy as np
    if "idx" in batch and trainer._dev_data is not None:
        # gather the 8 rows ON DEVICE; np.asarray of the full HBM dataset
        # would pull ~600 MB to host for 8 images
        import jax.numpy as jnp
        idx8 = jnp.asarray(np.asarray(batch["idx"])[:8])
        images = np.asarray(trainer._dev_data[0][idx8])
    else:
        images = batch.get("images")
    if images is not None:
        writer.write_images(1, "inputs", np.asarray(images)[:8])


def _check_resume_config(cfg: ExperimentConfig) -> None:
    """Record this run's config next to the checkpoints and WARN loudly
    when resuming under a different training recipe.

    Shape-identical configs (e.g. the gbs=128 and gbs=512 CIFAR presets)
    restore into each other without any error, silently entering the new
    LR schedule mid-stream — the reference had the same hazard via
    MonitoredTrainingSession. A changed recipe can be deliberate
    (fine-tuning), so this warns rather than refuses; the snapshot then
    reflects the NEW recipe."""
    import json as _json
    ckpt_dir = resolve_checkpoint_dir(cfg)
    os.makedirs(ckpt_dir, exist_ok=True)
    path = os.path.join(ckpt_dir, "config.json")
    now = cfg.to_dict()
    if cfg.checkpoint.resume and os.path.exists(path):
        try:
            with open(path) as f:
                saved = _json.load(f)
        except Exception:
            saved = None
        if saved:
            # benign continuation knobs — changing them is the normal way
            # to extend/observe a run, not a recipe change
            benign = {("train", "train_steps"), ("train", "log_every_steps"),
                      ("train", "summary_every_steps"),
                      ("train", "eval_every_steps"),
                      ("train", "steps_per_loop"), ("train", "scan_unroll"),
                      ("train", "log_mfu")}

            def norm(v):
                return list(v) if isinstance(v, (tuple, list)) else v

            diffs = []
            for section in ("optimizer", "train", "model", "data"):
                for key, val in now.get(section, {}).items():
                    if (section, key) in benign:
                        continue
                    old = saved.get(section, {}).get(key, val)
                    if norm(old) != norm(val):
                        diffs.append(f"{section}.{key}: {old} -> {val}")
            if diffs:
                log.warning(
                    "resuming %s under a DIFFERENT config than it was "
                    "trained with: %s — if this is not a deliberate "
                    "fine-tune/schedule change, point log_root elsewhere",
                    ckpt_dir, "; ".join(diffs))
    if is_chief():
        with open(path, "w") as f:
            _json.dump(now, f, indent=1, sort_keys=True)


def _newest_committed_step(cfg: ExperimentConfig) -> Optional[int]:
    """The step a new mesh generation restores from: the newest COMMITTED
    checkpoint. The committing chief pins this into the barrier record
    (resilience/elastic.py) so survivors and rejoiners restore the EXACT
    same step with no post-teardown agreement collective."""
    from .resilience.manifest import committed_steps
    steps = committed_steps(resolve_checkpoint_dir(cfg))
    return steps[-1] if steps else None


def run_train(cfg: ExperimentConfig, max_steps: Optional[int] = None):
    """Train across MESH GENERATIONS. Returns (state, metrics).

    Resilience wiring (docs/resilience.md): a PreemptionListener stops the
    loop at a step boundary on SIGTERM/SIGINT or a config deadline, commits
    a final checkpoint, and raises Preempted (main() maps it to exit code
    75); the NaN sentinel rolls back to the last good checkpoint with LR
    back-off when the guard trips.

    With ``resilience.elastic.enabled=on`` (resilience/elastic.py) a lost
    peer no longer ends the job: the step loop unwinds here with
    :class:`ReshardRequired`, the survivors meet in a file-based join
    barrier, tear down the dead jax world, re-initialize over the new
    membership at an epoch-suffixed coordinator, and the next iteration of
    this loop rebuilds the Trainer (every sharding rule re-elaborates
    against the shrunken topology) and restores the committed step the
    barrier pinned. A respawned worker (launch.py --elastic sets
    ``DRT_ELASTIC_REJOIN``) enters the SAME loop through ``rejoin()`` and
    the fleet grows back. Exit 75 remains the fallback whenever the
    transition is impossible (chief lost, < min_hosts, barrier timeout,
    generation budget, non-elastic layout)."""
    res = cfg.resilience
    rejoin = bool(os.environ.get("DRT_ELASTIC_REJOIN"))
    if rejoin:
        # identity comes from the launcher slot (--set mesh.process_id):
        # there is no live jax world to ask yet
        runtime = ElasticRuntime(cfg)
    else:
        runtime = ElasticRuntime(cfg, worker_id=jax.process_index(),
                                 num_processes=jax.process_count())
    if not runtime.enabled:
        runtime = None
    if rejoin and runtime is None:
        raise RuntimeError(
            "DRT_ELASTIC_REJOIN is set but resilience.elastic is off or "
            "the run has no peers — nothing to rejoin")

    listener = None
    if res.handle_signals:
        listener = PreemptionListener(deadline_secs=res.deadline_secs)
        if not listener.install():
            listener = None  # not the main thread — run without handlers

    gen_cfg = cfg
    record = None
    reshard_info = None
    if rejoin:
        from .parallel.distributed import reinitialize
        try:
            # the restore_step_fn covers the whole-fleet-died case: every
            # worker rejoins and the rejoined chief commits the round — it
            # must pin the newest committed checkpoint like a survivor would
            record = runtime.rejoin(lambda: _newest_committed_step(cfg))
        except ElasticImpossible as e:
            # the supervisor respawns on 75 with a bounded budget —
            # re-posting the join later beats failing the slot for good
            log.error("elastic rejoin failed (%s); exiting resumable",
                      e.reason)
            raise Preempted(0, f"rejoin failed: {e.reason}")
        reinitialize(record["coordinator"], len(record["members"]),
                     runtime.rank(record))
        gen_cfg = runtime.derive_config(record)

    try:
        while True:
            try:
                return _train_one_generation(
                    gen_cfg, listener, max_steps, runtime=runtime,
                    record=record, reshard_info=reshard_info)
            except ReshardRequired as rr:
                from .parallel.distributed import (reinitialize,
                                                   teardown_for_reshard)
                from .telemetry.tracer import span
                old_hosts = len(runtime.members)
                t0 = time.monotonic()
                try:
                    with span("reshard.barrier", category="reshard"):
                        record = runtime.transition(
                            rr.reason,
                            lambda: _newest_committed_step(gen_cfg))
                except ElasticImpossible as e:
                    # the requeue contract is the FALLBACK: a mesh that
                    # cannot reshard leaves exactly the way the watchdog
                    # always did — hard resumable exit, no distributed
                    # shutdown barrier against peers that are gone
                    log.error("elastic reshard impossible (%s) — exiting "
                              "resumable for the requeue contract",
                              e.reason)
                    logging.shutdown()
                    os._exit(e.exit_code)
                barrier_ms = (time.monotonic() - t0) * 1000.0
                with span("reshard.teardown", category="reshard"):
                    teardown_for_reshard(runtime.ecfg.teardown_timeout_secs)
                with span("reshard.init", category="reshard"):
                    reinitialize(record["coordinator"],
                                 len(record["members"]),
                                 runtime.rank(record))
                gen_cfg = runtime.derive_config(record)
                if listener is not None:
                    # the old generation's stop request (watchdog peer-lost
                    # escalation / the chief's grow request) is consumed;
                    # a real SIGTERM survives the reset
                    listener.reset()
                reshard_info = {
                    "generation": record["generation"],
                    "reason": rr.reason,
                    "old_hosts": old_hosts,
                    "new_hosts": len(record["members"]),
                    "restore_step": record["restore_step"],
                    "global_batch": record["global_batch"],
                    "barrier_ms": round(barrier_ms, 1),
                    "_t0": t0,  # total_ms completes once the mesh is live
                }
                log.warning(
                    "elastic: generation %d -> %d (%s): %d -> %d hosts, "
                    "restore step %s, global batch %s",
                    record["generation"] - 1, record["generation"],
                    rr.reason, old_hosts, len(record["members"]),
                    record["restore_step"], record["global_batch"])
    finally:
        if listener is not None:
            listener.uninstall()


def _train_one_generation(cfg: ExperimentConfig, listener,
                          max_steps: Optional[int], runtime=None,
                          record=None, reshard_info=None):
    """Build → (maybe) restore → train with hooks for ONE mesh generation
    (the whole job, when elastic is off). Returns (state, metrics);
    raises ReshardRequired to unwind into run_train's generation loop."""
    from .telemetry.tracer import span
    res = cfg.resilience
    rebuild_span = span("reshard.rebuild", category="reshard") \
        if record is not None else contextlib.nullcontext()
    with rebuild_span:
        trainer = Trainer(cfg)
        trainer.init_state()
    if record is None:
        # generation transitions deliberately change world size/batch —
        # re-running the recipe-drift check would warn on every reshard
        _check_resume_config(cfg)

    manager = CheckpointManager(
        resolve_checkpoint_dir(cfg), max_to_keep=cfg.checkpoint.max_to_keep,
        save_every_steps=cfg.checkpoint.save_every_steps,
        save_every_secs=cfg.checkpoint.save_every_secs,
        async_save=cfg.checkpoint.async_save,
        layout_stamp=stacked_layout_stamp(cfg),
        verify_on_restore=res.verify_on_restore,
        io_retries=res.io_retries,
        sharded=cfg.checkpoint.sharded,
        finalize_timeout_secs=cfg.checkpoint.finalize_timeout_secs)

    start_step = 0
    if record is not None and int(record.get("restore_step", -1)) >= 0:
        # the barrier pinned the step: every member of the new generation
        # restores it EXACTLY — through the sharded M≠N assemble path
        # (checkpoint/shards.py) when the layout changed under it
        with span("reshard.restore", category="reshard"):
            trainer.state, restored = manager.restore(
                trainer.state, step=int(record["restore_step"]))
        if restored is None:
            raise RuntimeError(
                f"generation {runtime.generation}: committed restore step "
                f"{record['restore_step']} failed to restore — the "
                "generations would diverge")
        start_step = int(trainer.state.step)
        log.info("generation %d: restored committed step %d into the new "
                 "mesh layout", runtime.generation, start_step)
    elif record is not None:
        log.warning("generation %d: no committed checkpoint existed at the "
                    "transition — restarting from step 0 on the new mesh",
                    runtime.generation)
    elif cfg.checkpoint.resume:
        with span("restore"):
            trainer.state, restored = manager.restore(trainer.state)
        if restored is not None:
            start_step = int(trainer.state.step)
            log.info("resumed from checkpoint at step %d", start_step)

    data_iter = _make_train_source(cfg, trainer)

    # peek ONE batch to (a) log an input-image grid (parity with the
    # reference's tf.summary.image of input batches, cifar_input.py:114) and
    # (b) optionally pre-lower the step for MFU logging; then chain it back
    writer = None
    step_flops = None
    if is_chief():
        writer = _make_writer(cfg, "train")
        first, data_iter = _peek(data_iter)
        if first is not None:
            _write_input_grid(writer, first, trainer)
            if cfg.train.log_mfu:
                step_flops = trainer.step_flops(first)
    # flight recorder + goodput (telemetry/): dump dir, ring bound, the
    # chief's writer for trace_dump/goodput rows; every process records
    _configure_telemetry(cfg, writer, jax.process_index())

    guard_every = res.nan_check_every_steps or max(cfg.train.log_every_steps, 1)
    hooks = [NanGuardHook(every_steps=guard_every)]
    if is_chief():
        hooks.append(LoggingHook(cfg.train.log_every_steps,
                                 batch_size=cfg.train.batch_size,
                                 print_fn=print, step_flops=step_flops))
        hooks.append(SummaryHook(writer, cfg.train.summary_every_steps))
        # input-pipeline stage attribution rides the summary cadence
        hooks.append(InputStagesHook(writer, cfg.train.summary_every_steps))
        # data-echoing cache hit/miss/eviction telemetry (data/echo.py)
        if cfg.data.echo_factor > 1:
            hooks.append(InputEchoHook(writer, cfg.train.summary_every_steps))
        # corrupt-TFRecord tally (data.max_corrupt_records skips) likewise
        hooks.append(CorruptRecordsHook(writer, cfg.train.summary_every_steps))
        # goodput break-down (telemetry/goodput.py): compute vs input_wait
        # vs checkpoint vs eval vs stall vs restart, per interval. Gated
        # on the tracer: with spans off nothing charges the measured
        # buckets and every row would read compute=100% — wrong data is
        # worse than none
        if cfg.telemetry.enabled:
            hooks.append(GoodputHook(writer,
                                     cfg.telemetry.goodput_every_steps
                                     or cfg.train.summary_every_steps))
        # async-checkpoint charge split (loop-thread vs writer-thread
        # seconds) — rows only appear once a save actually ran
        hooks.append(CkptAsyncHook(writer, cfg.train.summary_every_steps))
        # ZeRO-1 partition plan (parallel/sharding.py rule table) — one
        # row per resolved plan; silent when optimizer.zero1 resolved off
        if trainer.zero1_active:
            hooks.append(Zero1Hook(writer, cfg.train.summary_every_steps))
        # per-run precision summary (parallel/precision.py) — one row
        # per resolved policy; silent when everything runs f32
        if trainer.precision_active:
            hooks.append(PrecisionHook(writer,
                                       cfg.train.summary_every_steps))
    # per-host accounting exported by EVERY process (the chief's stream
    # alone would claim 1/N of the cluster): sharded-checkpoint bytes
    # (ckpt_shard) and the device-memory trend (memory — each host
    # samples its OWN devices). Non-chief processes get a tiny dedicated
    # event stream (train-p<idx>) the monitor's rollup sums across hosts.
    shard_writer = None
    if cfg.checkpoint.sharded != "off" or cfg.telemetry.memory:
        shard_writer = writer
        if shard_writer is None:
            shard_writer = _make_writer(
                cfg, f"train-p{jax.process_index()}")
        if cfg.checkpoint.sharded != "off":
            hooks.append(CkptShardHook(shard_writer,
                                       cfg.train.summary_every_steps))
        if cfg.telemetry.memory:
            hooks.append(MemoryHook(shard_writer,
                                    cfg.train.summary_every_steps))
    if cfg.checkpoint.save_every_steps or cfg.checkpoint.save_every_secs:
        hooks.append(CheckpointHook(manager))

    num_steps = max_steps if max_steps is not None else cfg.train.train_steps
    try:
        # distributed health watchdog: every process beats; peer loss /
        # hangs escalate to a coordinated stop, then exit 75 — or, with
        # elastic on, a reshard into the next generation
        # (docs/resilience.md); the session publishes the final
        # done/preempted/failed/reshard beat on every exit path
        with _watchdog_session(cfg, writer, listener, trainer,
                               elastic=runtime) \
                as (publisher, watchdog):
            _arm_watchdog_hooks(hooks, publisher)
            if runtime is not None:
                if reshard_info is not None and writer is not None:
                    info = dict(reshard_info)
                    t0 = info.pop("_t0", None)
                    if t0 is not None:
                        info["total_ms"] = round(
                            (time.monotonic() - t0) * 1000.0, 1)
                    writer.write_event("reshard", info)
                # generation.json + heartbeat tombstones + the
                # mesh_generation row: the new mesh is about to step
                runtime.mark_live(record, start_step, writer)
            stop_fn = None
            if listener is not None:
                # multi-process: the stop decision must flip at the SAME
                # step boundary on every process or the SPMD step / save
                # barrier deadlocks (preemption.py collective_should_stop)
                stop_fn = collective_should_stop(listener) \
                    if jax.process_count() > 1 else listener.should_stop
                if runtime is not None and jax.process_index() == 0:
                    # chief's between-steps grow poll: a rejoiner posting
                    # into the next round stops the fleet at a step
                    # boundary through the NORMAL collective agreement;
                    # the post-loop fork below turns the stop into a grow
                    base_stop = stop_fn

                    def stop_fn():
                        if runtime.pending_join():
                            listener.request_stop("reshard")
                        return base_stop()
            # NOTE: the phase stays "init" (unmonitored) until the FIRST
            # step completes and HeartbeatHook flips it to "train" — the
            # first step includes XLA compilation, which can legitimately
            # exceed min_step_timeout_secs; arming hang detection before it
            # would hard-exit 75 mid-compile and requeue-loop the job
            if res.nan_max_strikes > 0:
                def iter_factory(attempt: int):
                    if attempt == 0:
                        return data_iter
                    # re-seed so the rollback does not replay the exact
                    # batch sequence that blew up (large odd stride keeps
                    # the offset seeds disjoint across attempts)
                    prev_seed = cfg.train.seed
                    cfg.train.seed = prev_seed + 1_000_003 * attempt
                    try:
                        return _make_train_source(cfg, trainer)
                    finally:
                        cfg.train.seed = prev_seed

                state, metrics = train_with_nan_recovery(
                    trainer, manager, iter_factory, num_steps=num_steps,
                    hooks=tuple(hooks), start_step=start_step,
                    max_strikes=res.nan_max_strikes,
                    lr_backoff=res.nan_lr_backoff, stop_fn=stop_fn)
            else:
                state, metrics = trainer.train(
                    data_iter, num_steps=num_steps, hooks=tuple(hooks),
                    start_step=start_step, stop_fn=stop_fn)
            # agreed across processes: the save below is collective, so no
            # process may enter it on a merely-local flag
            preempted = collective_preempted(listener) \
                if listener is not None else False
            if preempted and int(state.step) < num_steps:
                # a signal landing AFTER the last step finished is not a
                # preemption — the run is done; exiting 75 would requeue a
                # job with nothing left to do. Otherwise commit the
                # preemption checkpoint UNCONDITIONALLY (even when cadence
                # checkpointing is off): the whole point of a graceful stop
                # is that a relaunch resumes instead of restarting
                step = int(state.step)
                reason = listener.reason()
                if (runtime is not None and runtime.can_reshard()
                        and not reason.startswith("signal ")
                        and reason != "deadline"
                        and runtime.pending_join(force=True)):
                    # GROW fork: the stop was the chief's reshard request
                    # (reason "reshard" there, its collective mirror "peer
                    # preempted" elsewhere — both non-signal) and a join
                    # for the next round is pending. Every process reads
                    # the same files + config, so the fork agrees; commit
                    # a checkpoint for the next generation to restore and
                    # unwind into the barrier
                    if publisher is not None:
                        publisher.set_phase("save")
                    manager.save(step, state, force=True)
                    manager.wait_until_finished()
                    log.info("elastic: grow requested — checkpoint "
                             "committed at step %d; entering the join "
                             "barrier", step)
                    raise ReshardRequired("grow",
                                          f"pending join at step {step}")
                if publisher is not None:
                    publisher.set_phase("save")
                manager.save(step, state, force=True)
                manager.wait_until_finished()
                log.warning("preempted (%s): checkpoint committed at step "
                            "%d; exiting resumable", reason, step)
                raise Preempted(step, reason)
            # final checkpoint + drain async saves
            if cfg.checkpoint.save_every_steps or \
                    cfg.checkpoint.save_every_secs:
                if publisher is not None:
                    publisher.set_phase("save")
                manager.save(int(state.step), state, force=True)
    finally:
        # the listener is NOT uninstalled here — run_train owns it across
        # generations (a SIGTERM mid-reshard must still be caught)
        manager.close()
        if shard_writer is not None and shard_writer is not writer:
            shard_writer.close()  # the non-chief ckpt_shard stream
        if writer is not None:
            # tensorboardX buffers events (~2 min flush window): without
            # the close, the tail of a completed run's summaries is lost
            writer.close()
    return state, metrics


def run_eval(cfg: ExperimentConfig, max_evals: Optional[int] = None,
             timeout_secs: float = 0.0):
    writer = None
    if is_chief():
        writer = _make_writer(cfg, "eval")
    _configure_telemetry(cfg, writer, jax.process_index())
    try:
        with _watchdog_session(cfg, writer, None, role="eval") \
                as (publisher, watchdog):
            ev = Evaluator(cfg, writer=writer)
            if publisher is not None:
                # eval batches tick liveness; between rounds the evaluator
                # parks in the unmonitored "poll" phase (checkpoint
                # droughts are normal, not hangs)
                ev.trainer.heartbeat = publisher
                publisher.set_phase("poll")
            return ev.run(max_evals=max_evals, timeout_secs=timeout_secs)
    finally:
        if writer is not None:
            writer.close()  # flush buffered events (see run_train)


def run_serve(cfg: ExperimentConfig):
    """Inference server mode (serve/; docs/serving.md): restore the newest
    committed checkpoint, AOT-compile every batch bucket, serve dynamic
    request batches, hot-swap newer checkpoints with zero downtime.

    With ``serve.load_qps > 0`` the open-loop synthetic load generator
    drives the server for ``serve.load_duration_secs``, then a JSON report
    (p50/p99 per bucket, QPS, swaps, dropped-request count) prints and the
    process exits — scripts/serve_smoke.sh and capacity planning. With
    ``load_qps = 0`` the server runs until SIGINT/SIGTERM (requests come
    from in-process ``InferenceServer.submit`` embedders)."""
    import json as _json
    import time as _time

    from .serve.loadgen import run_open_loop, synthetic_requests
    from .serve.server import InferenceServer, serve_stream_dir

    serve_dir = serve_stream_dir(cfg)
    replica_id = cfg.serve.replica_id
    writer = _make_writer(cfg, os.path.basename(serve_dir)) \
        if is_chief() else None
    _configure_telemetry(cfg, writer, jax.process_index())
    server = InferenceServer(cfg, writer=writer)
    publisher = None
    listener = None
    if replica_id >= 0:
        # fleet replica: publish liveness beats under the replica id so
        # the router/supervisor can tell dead (no beats) from wedged
        # (beats flowing, requests failing) — docs/serving.md fleet
        from .resilience.heartbeat import (FileBeatTransport,
                                           HeartbeatPublisher)
        publisher = HeartbeatPublisher(
            FileBeatTransport(
                os.path.join(cfg.log_root, "heartbeats-serve"), replica_id),
            process_id=replica_id).start()
        publisher.set_phase("serve")
        server.heartbeat = publisher
    load = None
    try:
        server.start()
        if cfg.serve.listen_port > 0:
            from .serve.wire import ReplicaListener
            listener = ReplicaListener(server,
                                       cfg.serve.listen_port).start()
        # orchestration marker (scripts/serve_smoke.sh and the fleet
        # supervisor wait on it before publishing checkpoints / routing:
        # a commit landing before the initial restore would be picked up
        # at startup, not hot-swapped)
        os.makedirs(serve_dir, exist_ok=True)
        with open(os.path.join(serve_dir, "READY"), "w") as f:
            f.write(_json.dumps({
                "pid": os.getpid(),
                "port": listener.port if listener is not None else 0}))
        if cfg.serve.load_qps > 0:
            load = run_open_loop(server, cfg.serve.load_qps,
                                 cfg.serve.load_duration_secs,
                                 seed=cfg.serve.load_seed)
            if cfg.serve.wait_for_swap_secs > 0 and server.swaps == 0:
                # smoke determinism: a training publisher is racing us —
                # keep serving (idle) until its commit lands or we time out
                deadline = _time.monotonic() + cfg.serve.wait_for_swap_secs
                while server.swaps == 0 and _time.monotonic() < deadline:
                    _time.sleep(0.25)
            # post-load probe: a few requests AFTER any swap prove the
            # server still answers (the smoke's "zero downtime" witness)
            probes = [server.submit(im) for im in synthetic_requests(
                server.image_shape, server.image_dtype, pool=4,
                seed=cfg.serve.load_seed + 1)]
            for f in probes:
                f.result(timeout=120.0)
        else:
            # park until SIGTERM/SIGINT — HANDLED, not defaulted: the
            # default SIGTERM action would kill the process mid-request
            # (no drain, no close(), unresolved futures), and systemd/k8s
            # stop with SIGTERM. The finally below then drains: every
            # accepted request is answered before exit.
            import signal
            import threading
            stop = threading.Event()
            prev = {}
            if threading.current_thread() is threading.main_thread():
                for sig in (signal.SIGTERM, signal.SIGINT):
                    prev[sig] = signal.signal(
                        sig, lambda *_args: stop.set())
            log.info("serving (no load generator); SIGTERM/Ctrl-C stops "
                     "with a full drain")
            try:
                while not stop.wait(1.0):
                    pass
            except KeyboardInterrupt:
                pass
            finally:
                for sig, handler in prev.items():
                    signal.signal(sig, handler)
    finally:
        if listener is not None:
            listener.close()  # stop intake before the drain
        server.close()  # drains: every accepted request is answered
        if publisher is not None:
            publisher.close()
        if writer is not None:
            writer.close()
    report = server.report()
    if load is not None:
        report["load"] = load
    print(_json.dumps(report))
    return report


def run_route(cfg: ExperimentConfig):
    """Fleet front door mode (serve/router.py + serve/fleet.py;
    docs/serving.md fleet section): spawn ``route.replicas`` serving
    replica processes, route open-loop load across them with
    least-outstanding dispatch + hedged retries, watchdog-replace dead or
    wedged replicas, canary new checkpoints with auto-rollback, and shed
    or degrade under queue pressure.

    With ``route.load_qps > 0`` the open-loop generator
    (``route.load_shape`` arrival schedule) drives the fleet, an
    in-flight canary is drained to a verdict on trickle traffic, then a
    JSON report prints and the process exits — scripts/serve_fleet_smoke.sh.
    With ``load_qps = 0`` the router runs
    until SIGTERM/SIGINT (requests would come from in-process submit)."""
    import json as _json
    import time as _time

    from .resilience.manifest import committed_steps
    from .serve.fleet import FleetSupervisor, write_pin
    from .serve.loadgen import run_open_loop, synthetic_requests
    from .serve.router import Router
    from .serve.server import serve_image_spec
    from .serve.wire import TcpReplicaClient

    route_dir = os.path.join(cfg.log_root, "route")
    writer = _make_writer(cfg, "route")
    _configure_telemetry(cfg, writer, 0)
    ckpt_dir = resolve_checkpoint_dir(cfg)
    fleet = FleetSupervisor(cfg, writer=writer)
    router = None
    load = None
    try:
        fleet.start()
        clients = {rid: TcpReplicaClient("127.0.0.1", port)
                   for rid, port in fleet.ports.items()}
        shape, dtype = serve_image_spec(cfg)
        router = Router(
            cfg.route, clients, shape, dtype, writer=writer,
            beats_dir=fleet.beats_dir,
            committed_steps_fn=lambda: committed_steps(ckpt_dir),
            pin_fn=lambda rid, step: write_pin(cfg.log_root, rid, step),
            initial_step=fleet.pinned_step).start()
        fleet.attach_router(router)
        fleet.start_watch()
        os.makedirs(route_dir, exist_ok=True)
        with open(os.path.join(route_dir, "READY"), "w") as f:
            f.write(_json.dumps({"pid": os.getpid()}))
        if cfg.route.load_qps > 0:
            load = run_open_loop(router, cfg.route.load_qps,
                                 cfg.route.load_duration_secs,
                                 seed=cfg.route.load_seed,
                                 shape=cfg.route.load_shape)
            # a checkpoint committed near the end of the load may not
            # have started its canary yet — give the health loop a few
            # turns to notice it before deciding whether to drain one
            grace = _time.monotonic() + 3 * cfg.route.health_interval_secs
            while (_time.monotonic() < grace
                   and router.canary.active is None):
                steps = committed_steps(ckpt_dir)
                newest = max(steps) if steps else -1
                if (newest <= router.canary.fleet_step
                        or newest in router.canary.bad_steps):
                    break
                _time.sleep(0.2)
            # drain an in-flight canary to a verdict: without traffic the
            # arms never accumulate samples and every canary would decay
            # to no_confirm/starved — trickle probes keep both arms fed
            pool = synthetic_requests(router.image_shape,
                                      router.image_dtype, pool=4,
                                      seed=cfg.route.load_seed + 1)
            deadline = _time.monotonic() + cfg.route.canary_window_secs \
                + cfg.route.canary_confirm_secs + 15.0
            i = 0
            while (router.canary.active is not None
                   and _time.monotonic() < deadline):
                fut = router.submit(pool[i % len(pool)])
                i += 1
                try:
                    fut.result(timeout=10.0)
                except Exception:  # noqa: BLE001 — probe losses are fine
                    pass
                _time.sleep(0.05)
        else:
            import signal
            import threading
            stop = threading.Event()
            prev = {}
            if threading.current_thread() is threading.main_thread():
                for sig in (signal.SIGTERM, signal.SIGINT):
                    prev[sig] = signal.signal(
                        sig, lambda *_args: stop.set())
            log.info("routing (no load generator); SIGTERM/Ctrl-C stops "
                     "with a full drain")
            try:
                while not stop.wait(1.0):
                    pass
            except KeyboardInterrupt:
                pass
            finally:
                for sig, handler in prev.items():
                    signal.signal(sig, handler)
    finally:
        if router is not None:
            router.close()  # before fleet.stop(): no requests race kills
        fleet.stop()
        writer.close()
    report = {"router": router.report() if router is not None else {},
              "fleet": fleet.report()}
    if load is not None:
        report["load"] = load
    print(_json.dumps(report))
    return report


def run_train_and_eval(cfg: ExperimentConfig):
    """In-process alternation: train eval_every_steps, then eval (the
    reference instead dedicated a whole node to the evaluator,
    run_dist_train_eval_daint.sh:211-222 — that mode still exists via two
    processes with mode=train / mode=eval)."""
    trainer = Trainer(cfg)
    trainer.init_state()
    _check_resume_config(cfg)
    manager = CheckpointManager(
        resolve_checkpoint_dir(cfg), max_to_keep=cfg.checkpoint.max_to_keep,
        save_every_steps=cfg.checkpoint.save_every_steps,
        save_every_secs=cfg.checkpoint.save_every_secs,
        async_save=cfg.checkpoint.async_save,
        layout_stamp=stacked_layout_stamp(cfg),
        verify_on_restore=cfg.resilience.verify_on_restore,
        io_retries=cfg.resilience.io_retries,
        sharded=cfg.checkpoint.sharded,
        finalize_timeout_secs=cfg.checkpoint.finalize_timeout_secs)
    if cfg.checkpoint.resume:
        trainer.state, _ = manager.restore(trainer.state)

    writer = _make_writer(cfg, "train") if is_chief() else None
    _configure_telemetry(cfg, writer, jax.process_index())
    # detection-only NaN guard (raises; the rollback sentinel is a
    # run_train capability — docs/resilience.md): dying loudly still beats
    # training and checkpointing NaN state to train_steps
    guard_every = cfg.resilience.nan_check_every_steps \
        or max(cfg.train.log_every_steps, 1)
    hooks = [NanGuardHook(every_steps=guard_every), CheckpointHook(manager)]
    if is_chief():
        hooks.append(LoggingHook(cfg.train.log_every_steps,
                                 batch_size=cfg.train.batch_size,
                                 print_fn=print))
        if writer:
            hooks.append(SummaryHook(writer, cfg.train.summary_every_steps))
            hooks.append(InputStagesHook(writer,
                                         cfg.train.summary_every_steps))
            if cfg.data.echo_factor > 1:
                hooks.append(InputEchoHook(writer,
                                           cfg.train.summary_every_steps))
            # corrupt-TFRecord tally exports here too — bit rot must be
            # visible in telemetry in every training mode
            hooks.append(CorruptRecordsHook(writer,
                                            cfg.train.summary_every_steps))
            if cfg.telemetry.enabled:  # see run_train: no spans, no rows
                hooks.append(GoodputHook(
                    writer, cfg.telemetry.goodput_every_steps
                    or cfg.train.summary_every_steps))
            hooks.append(CkptAsyncHook(writer,
                                       cfg.train.summary_every_steps))
            if trainer.zero1_active:
                hooks.append(Zero1Hook(writer,
                                       cfg.train.summary_every_steps))
            if trainer.precision_active:
                hooks.append(PrecisionHook(
                    writer, cfg.train.summary_every_steps))
    # per-host sharded-ckpt + device-memory accounting: every process
    # exports, like run_train (the monitor's per-host rollup reads these)
    te_shard_writer = None
    if cfg.checkpoint.sharded != "off" or cfg.telemetry.memory:
        te_shard_writer = writer
        if te_shard_writer is None:
            te_shard_writer = _make_writer(
                cfg, f"train-p{jax.process_index()}")
        if cfg.checkpoint.sharded != "off":
            hooks.append(CkptShardHook(te_shard_writer,
                                       cfg.train.summary_every_steps))
        if cfg.telemetry.memory:
            hooks.append(MemoryHook(te_shard_writer,
                                    cfg.train.summary_every_steps))

    train_iter = _make_train_source(cfg, trainer)

    listener = None
    if cfg.resilience.handle_signals:
        listener = PreemptionListener(
            deadline_secs=cfg.resilience.deadline_secs)
        if not listener.install():
            listener = None
    stop_fn = None
    if listener is not None:
        stop_fn = collective_should_stop(listener) \
            if jax.process_count() > 1 else listener.should_stop

    every = cfg.train.eval_every_steps or cfg.checkpoint.save_every_steps or 1000
    best = 0.0
    step = int(trainer.state.step)
    result = {}
    try:
        with _watchdog_session(cfg, writer, listener, trainer) \
                as (publisher, watchdog):
            _arm_watchdog_hooks(hooks, publisher)
            while step < cfg.train.train_steps:
                target = min(step + every, cfg.train.train_steps)
                # phase flips to "train" at the first completed step via
                # HeartbeatHook (NOT here): round 1's first step carries
                # the XLA compile, which must stay in the unmonitored
                # "init" phase
                state, _ = trainer.train(train_iter, num_steps=target,
                                         hooks=tuple(hooks), start_step=step,
                                         stop_fn=stop_fn)
                step = int(state.step)
                preempted = collective_preempted(listener) \
                    if listener is not None else False
                if preempted and step < cfg.train.train_steps:
                    if publisher is not None:
                        publisher.set_phase("save")
                    manager.save(step, trainer.state, force=True)
                    manager.wait_until_finished()
                    log.warning("preempted (%s): checkpoint committed at "
                                "step %d; exiting resumable",
                                listener.reason(), step)
                    raise Preempted(step, listener.reason())
                # fresh iterator per round: the ImageNet eval stream is
                # one-pass
                result = trainer.evaluate(
                    make_eval_iterator(cfg, trainer.mesh),
                    cfg.eval.eval_batch_count)
                best = max(best, result["precision"])
                if writer:
                    writer.write_scalars(
                        step, {"eval/precision": result["precision"],
                               "eval/best_precision": best})
                if is_chief():
                    print(f"eval @ step {step}: precision "
                          f"{result['precision']:.4f} best {best:.4f}")
            if publisher is not None:
                publisher.set_phase("save")
            manager.save(step, trainer.state, force=True)
    finally:
        if listener is not None:
            listener.uninstall()
        manager.close()
        if te_shard_writer is not None and te_shard_writer is not writer:
            te_shard_writer.close()  # the non-chief ckpt_shard stream
        if writer:
            # flush buffered tensorboardX events even on a mid-run error
            writer.close()
    return trainer.state, {**result, "best_precision": best}


def main(argv=None):
    # force=True: absl/jax may have already claimed the root logger, which
    # would otherwise swallow our INFO lines (e.g. the resume notice)
    logging.basicConfig(
        level=logging.INFO, force=True,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s")
    if argv is None:
        argv = sys.argv[1:]
    configure_compile_cache()
    if argv and argv[0] == "check":
        # shardcheck gate (analysis/): lint + static elaboration on a
        # virtual CPU mesh — no cluster, no data (docs/static_analysis.md)
        from .analysis.cli import main_check
        sys.exit(main_check(argv[1:]))
    if argv and argv[0] == "monitor":
        # cluster rollup (telemetry/monitor.py, docs/observability.md):
        # tails every metrics stream + heartbeat file under a log_root —
        # pure filesystem reads, no jax world, safe beside a live run
        from .telemetry.monitor import main_monitor
        sys.exit(main_monitor(argv[1:]))
    if argv and argv[0] == "trace-merge":
        # cluster trace correlation (telemetry/merge.py): merge the
        # per-process trace[.procN].json dumps onto ONE timeline with
        # per-host lanes + heartbeat-estimated clock offsets — pure
        # filesystem reads, like monitor
        from .telemetry.merge import main_trace_merge
        sys.exit(main_trace_merge(argv[1:]))
    if argv and argv[0] == "plan":
        # what-if performance planner (telemetry/planner.py,
        # docs/planner.md): predict step time / HBM watermark / comm
        # fraction per layout from the committed collective schedules ×
        # the fabric's bandwidth catalog, rank them, RECOMMEND a layout
        # — no cluster needed
        from .telemetry.planner import main_plan
        sys.exit(main_plan(argv[1:]))
    serve_cmd = False
    if argv and argv[0] == "serve":
        # inference server (serve/, docs/serving.md): same flags as the
        # trainer — `main.py serve --preset X --set serve.load_qps=...`
        # is sugar for `--set mode=serve`
        serve_cmd = True
        argv = argv[1:]
    route_cmd = False
    if argv and argv[0] == "route":
        # serving-fleet front door (serve/router.py + serve/fleet.py,
        # docs/serving.md fleet section) — sugar for `--set mode=route`
        route_cmd = True
        argv = argv[1:]
    cfg = parse_args(argv)
    if serve_cmd:
        cfg.mode = "serve"
    if route_cmd:
        cfg.mode = "route"
    if cfg.analysis.dispatch_sanitizer:
        # opt-in cross-thread dispatch guard (analysis/dispatch_sanitizer):
        # a second dispatching thread raises at its call site instead of
        # deadlocking the next collective
        from .analysis.dispatch_sanitizer import install as _install_ds
        _install_ds()
        log.info("dispatch sanitizer armed (analysis.dispatch_sanitizer)")
    if os.environ.get("DRT_ELASTIC_REJOIN"):
        # elastic rejoin (resilience/elastic.py): the generation this
        # worker died in is gone and its coordinator port with it —
        # run_train joins the live fleet's barrier and initializes into
        # the NEXT generation instead of the config's dead world
        log.info("elastic rejoin: deferring distributed init to the "
                 "join barrier")
    else:
        initialize_from_config(cfg.mesh)
    if cfg.mode != "route":
        # the modes that compute own the accelerator; `route` only starts
        # replica processes, and a parent that has opened the backend
        # holds the chip its children need
        dev = jax.devices()[0]
        log.info("devices: %d x %s/%s (%d processes)", jax.device_count(),
                 dev.platform, dev.device_kind, jax.process_count())
    try:
        if cfg.mode == "train":
            run_train(cfg)
        elif cfg.mode == "eval":
            run_eval(cfg, timeout_secs=0.0 if cfg.eval.eval_once else 86400.0)
        elif cfg.mode == "train_and_eval":
            run_train_and_eval(cfg)
        elif cfg.mode == "serve":
            run_serve(cfg)
        elif cfg.mode == "route":
            run_route(cfg)
        else:
            raise ValueError(f"unknown mode {cfg.mode!r}")
    except Preempted as p:
        # the exit-code contract launchers key off (docs/resilience.md):
        # 75 = checkpoint committed, relaunch to resume
        log.info("%s", p)
        sys.exit(RESUMABLE_EXIT_CODE)
    except Exception as e:
        # non-zero exit: leave the flight-recorder dump next to the run —
        # the post-mortem's first stop (telemetry/tracer.py; never raises)
        _flight_recorder.dump_on_anomaly(
            "exception", f"{type(e).__name__}: {e}"[:300])
        if jax.process_count() > 1:
            # a real failure with peers still alive: the run published a
            # final phase="failed" beat (peers stop through their
            # watchdogs) — exit hard NOW. sys.exit would run atexit's
            # jax.distributed.shutdown, whose barrier waits on peers that
            # are already leaving: measured minutes of hang per crash
            log.exception("fatal error in a multi-process run; exiting 1 "
                          "without the distributed shutdown barrier")
            logging.shutdown()
            os._exit(1)
        raise


if __name__ == "__main__":
    main(sys.argv[1:])
