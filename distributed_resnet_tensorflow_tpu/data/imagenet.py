"""ImageNet input pipeline over TFRecord shards.

Parity with the reference's duplicated input_fn/record_parser
(reference resnet_imagenet_main.py:103-183, resnet_imagenet_eval.py:70-150):
  * shard naming train-{i:05d}-of-01024 / validation-{i:05d}-of-00128
    (reference :106-112),
  * Example parsing of image/encoded + image/class/label
    (reference record_parser:115-136; bbox features parsed but unused by the
    crop the reference actually applied — VGG preprocessing ignores them),
  * file-level shuffle each epoch + sample-level shuffle buffer
    (reference :98-99,163,174),
  * VGG preprocess train/eval (preprocessing.py), labels already 1-based
    with 0 = background ⇒ num_classes=1001 dense ids (the reference one-hotted
    to 1001, resnet_imagenet_main.py:151-155; we keep dense ids and one-hot
    in the loss).

Multi-process sharding: each process reads files[shard_index::num_shards] —
disjoint by construction (the reference's Horovod path read everything
everywhere, SURVEY.md §3.2).

Parallelism: a pool of decode threads feeding a bounded queue — host-side
successor of tf.data's num_parallel_calls=5 map (reference :166-168). Each
worker decodes via PIL (DCT-scaled draft) or, with ``use_native`` and a
libjpeg-enabled build, the fused C++ transform (native/dataloader.cc —
scaled decode + resize/crop/flip in one GIL-free call, measured 1.6× the
PIL rate per core); the C++ record prefetcher feeds the bytes.
"""
from __future__ import annotations

import glob
import os
import queue as queue_mod
import threading
from typing import Dict, Iterator, List, Optional

import numpy as np

from .tfrecord import parse_example, read_tfrecords

TRAIN_SHARDS = 1024   # reference resnet_imagenet_main.py:106
VAL_SHARDS = 128      # reference resnet_imagenet_main.py:111
SHUFFLE_BUFFER = 1500  # reference resnet_imagenet_main.py:174


def dataset_filenames(data_dir: str, mode: str) -> List[str]:
    """Accept both the exact reference naming and any train-*/validation-*
    TFRecord layout present in data_dir."""
    prefix = "train" if mode == "train" else "validation"
    files = sorted(glob.glob(os.path.join(data_dir, f"{prefix}-*")))
    if not files:
        raise FileNotFoundError(
            f"no {prefix}-* TFRecord shards under {data_dir!r}")
    return files


def _example_to_sample(features: Dict) -> Optional[tuple]:
    enc = features.get("image/encoded")
    label = features.get("image/class/label")
    if not enc or label is None or len(label) == 0:
        return None
    return bytes(enc[0]), int(label[0])


def imagenet_iterator(data_dir: str, batch_size: int, mode: str,
                      image_size: int = 224, seed: int = 0,
                      shard_index: int = 0, num_shards: int = 1,
                      num_decode_threads: int = 4,
                      prefetch_batches: int = 2,
                      shuffle_buffer: int = SHUFFLE_BUFFER,
                      use_native: bool = False,
                      device_standardize: bool = False,
                      device_flip: bool = False,
                      decode_processes: int = 0,
                      deterministic: bool = False,
                      max_corrupt_records: int = 0,
                      verify_crc: bool = False,
                      ) -> Iterator[Dict[str, np.ndarray]]:
    """``device_standardize``: batches stay uint8 (crop done, VGG
    mean-subtract deferred to ops/augment inside the jitted step or the
    staged-unpack program) — 4× smaller host→device transfers and no host
    float pass. Both modes use the fused DCT-scaled decode
    (preprocessing.decode_and_resize).

    ``device_flip``: the device augmentation owns the horizontal flip
    (ops/augment.imagenet_train_augment draws one per appearance — fresh
    per echo, data/echo.py), so the host decode draws its flip (the RNG
    stream contract keeps the draw order: side, top, left, flip) but does
    NOT apply it. Train mode only; without it device-augmented batches
    would be flipped twice.

    ``decode_processes`` > 0 replaces the decode THREAD pool with worker
    PROCESSES (fork): full GIL independence for the decode stage, at the
    price of pickling jpeg bytes in and decoded crops out. The thread pool
    already scales while decoders hold the GIL released (PIL and the
    native transform both release it); the process pool is the escape
    hatch for hosts where the python-side feeder contends
    (tools/input_scaling.py measures both, docs/input_scaling_r4.json).
    Workers start via forkserver/spawn (fork from a threaded parent can
    inherit held locks), so the calling program needs the standard
    ``if __name__ == "__main__"`` guard multiprocessing requires.

    ``deterministic``: two iterators built with identical arguments yield
    byte-identical batch streams regardless of worker scheduling. Needed
    when several processes feed the SAME replicated batch slice (a
    non-batch mesh axis spans processes — parallel/mesh.py
    process_batch_slice): without it, decode workers emit in completion
    order and draw augmentations from per-worker RNG streams, so replica
    processes silently assemble different batches. Mechanism: samples are
    sequence-tagged at the feeder, each item's augmentation RNG derives
    from (seed, sequence) instead of the worker's stream, and the
    consumer reorders by sequence; the native record PREFETCHER is
    bypassed (its file interleave is thread-timing-dependent) while the
    native JPEG decode stays usable.
    """
    files = dataset_filenames(data_dir, mode)
    if num_shards > 1:
        total_files = len(files)
        files = files[shard_index::num_shards]
        if not files:
            raise ValueError(f"process {shard_index}: no files to read "
                             f"({num_shards} shards over {total_files} files)")
    is_train = mode == "train"
    rng = np.random.RandomState(seed + shard_index)

    # native C++ multithreaded record reader. Train: file order is
    # thread-interleaved → extra shuffle for free. Eval (round 4): also
    # allowed — aggregate eval metrics are order-independent and the
    # prefetcher delivers every record exactly once, so only the
    # meaningless per-batch composition changes (VERDICT r3 #6: the
    # single-stream python reader capped a 50k validation pass)
    native = use_native and not deterministic
    if use_native and deterministic:
        # say it: the operator asked for the native record prefetcher
        # (the r3 fix for the single-stream reader cap) but determinism
        # must bypass its thread-timing-dependent file interleave — eval
        # wall-clock on this process is back on the python reader
        import logging
        logging.getLogger(__name__).warning(
            "use_native prefetcher disabled: deterministic mode (replica "
            "processes share a batch slice) requires a stable record "
            "order; the python reader streams files in order instead "
            "(native JPEG decode stays active)")
    if native:
        try:
            from .native_loader import NativePrefetcher, native_available
            native = native_available()
        except Exception:
            native = False

    def record_stream(ordered_files):
        if native:
            # record-reader threads track the decode width (round 9): a
            # 4-thread reader fed an 8-wide decode pool starved it on
            # fast storage
            pf = NativePrefetcher(
                list(ordered_files),
                num_threads=min(len(ordered_files),
                                max(4, decode_processes,
                                    num_decode_threads)))
            try:
                yield from pf
            finally:
                pf.close()
        else:
            # max_corrupt_records > 0: tolerate truncated tails / torn
            # shards with counted skips (data/tfrecord.py; {"event":
            # "corrupt_record"} rows via CorruptRecordsHook). Flipped
            # payload bytes are only caught when verify_crc is on (a
            # python CRC32C pass per record — data.verify_crc). The
            # native C++ prefetcher has its own CRC handling, stays strict.
            for path in ordered_files:
                yield from read_tfrecords(path, verify_crc=verify_crc,
                                          max_corrupt=max_corrupt_records)

    # stage 1: raw (jpeg_bytes, label) stream with file + buffer shuffle
    def raw_stream():
        epoch = 0
        while True:
            order = rng.permutation(len(files)) if is_train else range(len(files))
            buf: List[tuple] = []
            for rec in record_stream([files[fi] for fi in order]):
                sample = _example_to_sample(parse_example(rec))
                if sample is None:
                    continue
                if is_train and shuffle_buffer > 1:
                    buf.append(sample)
                    if len(buf) >= shuffle_buffer:
                        j = rng.randint(len(buf))
                        yield buf.pop(j)
                else:
                    yield sample
            while buf:
                j = rng.randint(len(buf))
                yield buf.pop(j)
            epoch += 1
            if not is_train:
                return

    # stage 2: parallel decode+preprocess workers (threads, or processes
    # when decode_processes > 0)
    use_procs = decode_processes > 0
    n_workers = decode_processes if use_procs else num_decode_threads
    emit_uint8 = device_standardize
    # the fused C++ decode (one GIL-free call per image) when built with
    # libjpeg; PIL otherwise — identical crop geometry either way
    native_decode = False
    if use_native:
        try:
            from .native_loader import native_jpeg_available
            native_decode = native_jpeg_available()
        except Exception:
            native_decode = False
        if deterministic and not native_decode:
            # replica peers that DO have the native build will decode the
            # same records through libjpeg's interpolation path — pixel
            # divergence deterministic mode cannot see. Loud, so a
            # heterogeneous fleet is discoverable from the degraded host.
            import logging
            logging.getLogger(__name__).warning(
                "native JPEG decode unavailable on this process but "
                "deterministic mode is on: if replica peers resolve the "
                "native path, their batches will differ pixel-wise from "
                "this host's PIL decode — install the native loader on "
                "all hosts (or set data.use_native_loader=false fleet-"
                "wide)")

    # worker processes ship their decode stage-counters back as
    # _StageDelta messages on the result queue (merged below): without the
    # merge, the input attribution under decode_processes > 0
    # undercounted decode busy time — the workers' own registries die with
    # the workers
    if use_procs:
        import multiprocessing as mp
        # NOT "fork": the parent is multi-threaded by the time an iterator
        # is built (JAX runtime threads, earlier iterators' feeders), and a
        # child forked while another thread holds a lock (malloc, logging)
        # can deadlock — observed nondeterministically in round 4.
        # forkserver forks from a clean single-threaded server process;
        # spawn is the fallback where it's unavailable. The worker body
        # (_decode_worker) is module-level and numpy/PIL-only, so both
        # start methods can import it.
        try:
            ctx = mp.get_context("forkserver")
        except ValueError:  # platform without forkserver
            ctx = mp.get_context("spawn")
        in_q = ctx.Queue(maxsize=4 * batch_size)
        out_q = ctx.Queue(maxsize=max(2, prefetch_batches) * batch_size)
        workers = [
            ctx.Process(target=_decode_worker,
                        args=(in_q, out_q,
                              seed * 7919 if deterministic
                              else seed * 7919 + i,
                              is_train, image_size, native_decode,
                              emit_uint8, deterministic, i, device_flip),
                        daemon=True)
            for i in range(n_workers)]
        for w in workers:
            w.start()
        # parent only, AFTER the workers start (children must keep normal
        # join semantics so their final puts flush at exit): without this,
        # an abandoned iterator leaves the parent's atexit joining a queue
        # feeder thread that can never drain once workers are gone
        in_q.cancel_join_thread()
        out_q.cancel_join_thread()
    else:
        in_q = queue_mod.Queue(maxsize=4 * batch_size)
        out_q = queue_mod.Queue(
            maxsize=max(2, prefetch_batches) * batch_size)
    stop = threading.Event()

    def _put_checked(item) -> bool:
        """Timed put so the feeder notices `stop` even when the queue is
        full (a blocking put would never wake once consumers are gone —
        at interpreter exit multiprocessing joins its queue threads and a
        stuck feeder turns teardown into a hang)."""
        while not stop.is_set():
            try:
                in_q.put(item, timeout=0.2)
                return True
            except queue_mod.Full:
                continue
        return False

    def feeder():
        try:
            for seq, sample in enumerate(raw_stream()):
                if not _put_checked((seq, sample) if deterministic
                                    else sample):
                    return
            for _ in range(n_workers):
                if not _put_checked(_END):
                    return
        except BaseException as e:
            out_q.put(_Failure(repr(e)))

    def decoder(widx: int):
        try:
            # deterministic: ONE shared seed base — the item's RNG derives
            # from its sequence number, not from which worker got it
            wseed = seed * 7919 if deterministic else seed * 7919 + widx
            _decode_loop(in_q, out_q, wseed, is_train,
                         image_size, native_decode, emit_uint8, stop,
                         deterministic, widx, device_flip)
        except BaseException as e:
            out_q.put(_Failure(repr(e)))

    worker_threads = [threading.Thread(target=feeder, daemon=True)]
    if not use_procs:
        worker_threads += [
            threading.Thread(target=decoder, args=(i,), daemon=True)
            for i in range(n_workers)]
    for t in worker_threads:
        t.start()

    def batches():
        images = np.empty((batch_size, image_size, image_size, 3),
                          np.uint8 if emit_uint8 else np.float32)
        labels = np.empty((batch_size,), np.int32)
        fill = 0
        ended = 0
        # deterministic reorder state: emit strictly by sequence number.
        # The out-of-order window is bounded by in-flight items
        # (in_q capacity + workers), so `pending` stays small.
        expected = [0]
        pending: Dict[int, tuple] = {}

        def in_order(item):
            """Payloads ready to consume, in sequence order
            (deterministic mode only)."""
            seq, payload = item
            pending[seq] = payload
            while expected[0] in pending:
                yield pending.pop(expected[0])
                expected[0] += 1

        def next_item():
            # a worker killed without enqueueing _Failure or _END (a
            # signal death for processes; interpreter teardown or a hard
            # native crash for threads) must become a loud error, not a
            # permanent out_q.get() block — timed get + liveness poll on
            # BOTH paths (hangcheck untimed-blocking-call,
            # docs/static_analysis.md)
            while True:
                try:
                    return out_q.get(timeout=5.0)
                except queue_mod.Empty:
                    if not use_procs:
                        # decode THREADS: all dead with nothing queued
                        # means items were lost, not still in flight
                        if not any(t.is_alive() for t in worker_threads):
                            try:
                                return out_q.get_nowait()
                            except queue_mod.Empty:
                                raise RuntimeError(
                                    "imagenet decode thread(s) died "
                                    "without reporting — stream lost"
                                ) from None
                        continue
                    dead = [w for w in workers if not w.is_alive()
                            and w.exitcode not in (0, None)]
                    if dead:
                        raise RuntimeError(
                            "imagenet decode worker(s) died without "
                            f"reporting: exitcodes "
                            f"{[w.exitcode for w in dead]}") from None

        from ..utils.metrics import input_stages
        try:
            while True:
                item = next_item()
                if isinstance(item, _StageDelta):
                    # decode-PROCESS counter snapshot: merge into the
                    # parent registry under a per-worker key so
                    # max_thread_seconds still means "busiest worker"
                    input_stages.add("decode", item.seconds,
                                     items=item.count, nbytes=item.nbytes,
                                     worker=("decode-proc", item.widx))
                    continue
                if isinstance(item, _Failure):
                    raise RuntimeError(
                        f"imagenet pipeline worker failed: {item.err}")
                if item is _END or isinstance(item, _EndMarker):
                    ended += 1
                    if ended == n_workers:
                        # every worker's items precede its own _END in
                        # queue order, so by the n-th _END all items have
                        # been consumed and `pending` has drained. Explicit
                        # raise (not assert): under `python -O` a violated
                        # invariant must still fail loudly, not silently
                        # drop the tail of a deterministic eval stream
                        if pending:
                            raise RuntimeError(
                                "imagenet deterministic reorder drain "
                                f"invariant violated: {len(pending)} "
                                "item(s) undelivered at stream end, first "
                                f"seqs {sorted(pending)[:4]} — refusing to "
                                "silently drop the stream tail")
                        if fill and not is_train:
                            # final partial eval batch: pad + mask
                            mask = np.zeros((batch_size,), np.float32)
                            mask[:fill] = 1.0
                            images[fill:] = 0.0
                            labels[fill:] = 0
                            yield {"images": images.copy(),
                                   "labels": labels.copy(), "mask": mask}
                        return
                    continue
                # non-deterministic stays a plain tuple wrap — no
                # per-image generator on the measured host hot path
                for payload in (in_order(item) if deterministic
                                else (item,)):
                    images[fill], labels[fill] = payload
                    fill += 1
                    if fill == batch_size:
                        yield {"images": images.copy(),
                               "labels": labels.copy()}
                        fill = 0
        finally:
            stop.set()
            if use_procs:
                # don't let atexit try to flush/join the queue threads:
                # with the workers gone the pipes never drain
                in_q.cancel_join_thread()
                out_q.cancel_join_thread()
                for w in workers:
                    w.terminate()

    return batches()


class _EndMarker:
    """Worker-exhausted sentinel that survives a multiprocessing queue."""


class _StageDelta:
    """A decode worker PROCESS's stage-counter increment, shipped to the
    parent over the result queue (pickle-friendly; see ``_decode_loop``).
    The parent merges it into ``utils.metrics.input_stages`` so the
    input attribution sees process-pool decode busy time too."""

    __slots__ = ("widx", "count", "seconds", "nbytes")

    def __init__(self, widx: int, count: int, seconds: float, nbytes: int):
        self.widx = widx
        self.count = count
        self.seconds = seconds
        self.nbytes = nbytes


class _Failure:
    def __init__(self, err: str):
        self.err = err


_END = _EndMarker()


def _decode_loop(in_q, out_q, wseed, is_train, image_size, native_decode,
                 emit_uint8, stop=None, deterministic=False, widx=0,
                 device_flip=False):
    from .preprocessing import (RGB_MEANS, eval_crop_from_bytes,
                                train_crop_from_bytes)
    import queue as queue_mod

    from ..telemetry.tracer import span
    from ..utils.metrics import input_stages
    wrng = np.random.RandomState(wseed)
    # decode counters flush in small groups: an input_stages.add per image
    # would contend the registry lock across the whole decode pool (and a
    # _StageDelta per image would double the result-queue traffic)
    pend_n = 0
    pend_s = pend_b = 0

    def flush_counters():
        """Thread mode: straight into the process registry. Process mode
        (stop is None): our registry dies with this worker — ship the
        delta to the parent over the result queue instead (merged into
        the parent's input_stages; see imagenet_iterator.batches)."""
        nonlocal pend_n, pend_s, pend_b
        if not pend_n:
            return
        if stop is None:
            out_q.put(_StageDelta(widx, pend_n, pend_s, pend_b))
        else:
            input_stages.add("decode", pend_s, items=pend_n, nbytes=pend_b)
        pend_n = 0
        pend_s = pend_b = 0

    def put_checked(item) -> bool:
        """Timed put in thread mode so `stop` is observed even on a FULL
        out_q (decoders outpacing an abandoned consumer park here, not in
        get). Process mode (stop=None) keeps the blocking put — workers
        are terminate()d."""
        if stop is None:
            out_q.put(item)
            return True
        while not stop.is_set():
            try:
                out_q.put(item, timeout=0.2)
                return True
            except queue_mod.Full:
                continue
        return False

    try:
        while stop is None or not stop.is_set():
            # timed get in thread mode so `stop` is observed between
            # items: an abandoned iterator (eval warmup, a polling
            # evaluator sized below the dataset) sets `stop` while workers
            # sit in get(); a blocking get would strand
            # num_decode_threads daemon threads per iterator, growing
            # unboundedly in a long-lived poll loop.
            try:
                item = in_q.get(timeout=None if stop is None else 0.2)
            except queue_mod.Empty:
                continue
            if item is _END or isinstance(item, _EndMarker):
                # counters BEFORE the _END marker: the parent stops
                # consuming at the n-th _END, so a delta after ours could
                # only be read by luck
                flush_counters()
                put_checked(_END)
                return
            if deterministic:
                # per-item RNG from the sample's sequence number: the same
                # record gets the same augmentation no matter which worker
                # decodes it (see imagenet_iterator's `deterministic`)
                seq, (data, label) = item
                rng = np.random.RandomState((wseed + 2654435761 * seq)
                                            % (2 ** 32))
            else:
                seq, (data, label) = None, item
                rng = wrng
            with span("input.decode") as sp:
                if is_train:
                    img = train_crop_from_bytes(data, rng, image_size,
                                                use_native=native_decode,
                                                apply_flip=not device_flip)
                else:
                    img = eval_crop_from_bytes(data, image_size,
                                               use_native=native_decode)
                if not emit_uint8:
                    img = img.astype(np.float32) / 255.0 - RGB_MEANS
            # decode busy time, from the span's own measurement (stage
            # counters, utils/metrics.py; none when telemetry is off);
            # worker PROCESSES flush deltas to the parent (flush_counters)
            if sp.seconds is not None:
                pend_n += 1
                pend_s += sp.seconds
                pend_b += img.nbytes
                if pend_n >= 16:
                    flush_counters()
            out = (img, label) if seq is None else (seq, (img, label))
            if not put_checked(out):
                return
    finally:
        # thread mode only: a worker PROCESS's terminal flush would land
        # AFTER its _END (already flushed there) and could race the
        # parent's teardown drain
        if stop is not None:
            flush_counters()


def _decode_worker(in_q, out_q, wseed, is_train, image_size, native_decode,
                   emit_uint8, deterministic=False, widx=0,
                   device_flip=False):
    """Process-pool worker body (fork target)."""
    try:
        _decode_loop(in_q, out_q, wseed, is_train, image_size,
                     native_decode, emit_uint8, deterministic=deterministic,
                     widx=widx, device_flip=device_flip)
    except BaseException as e:  # pragma: no cover - transported to parent
        out_q.put(_Failure(repr(e)))
