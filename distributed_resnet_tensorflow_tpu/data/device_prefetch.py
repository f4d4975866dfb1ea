"""Device prefetch + background input threads.

The reference's analog was tf.data's prefetch buffering and the 16-thread
queue runners (reference resnet_cifar_main.py:232, cifar_input.py:77-96).
Here:

  * ``device_prefetch``   — a DEDICATED transfer thread runs the host→device
    placement fn and feeds a bounded queue of already-device-resident
    batches, so decode, stacking, H2D transfer and dispatch each own a
    thread and run concurrently. (The pre-overlap version dispatched
    transfers inline on the consumer thread — staging was serial with
    dispatch, which is exactly the "serial staging" bottleneck BENCH_r05
    measured.)
  * ``threaded_iterator`` — run ANY iterator on a background thread with a
    bounded queue; the single implementation of the worker/stop/error
    machinery used by every threaded input stage.
  * ``threaded_stacker``  — draw K batches + np.stack on a background thread
    (the input side of the fused ``steps_per_loop`` dispatch).

Every stage is timed by ONE flight-recorder span (telemetry/tracer.py),
which charges busy time + item counts into ``utils.metrics.input_stages``
(stages: decode / stack / stage / transfer / dispatch_wait — see
docs/input_pipeline.md), so attribution of the end-to-end input rate comes
from the pipeline as it actually ran.

All returned generators stop their worker thread when closed — a replaced
or abandoned pipeline must not leave a thread parked on its queue holding
batches.
"""
from __future__ import annotations

import logging
import queue as queue_mod
import threading
from typing import Callable, Iterator

log = logging.getLogger(__name__)


def _batch_items(batch) -> int:
    """Number of examples a host batch carries (for stage-rate counters):
    the label leaf's element count covers both flat (B,) and stacked (K, B)
    batches; index batches ({"idx"}) count indices."""
    try:
        for key in ("labels", "idx"):
            leaf = batch.get(key) if hasattr(batch, "get") else None
            if leaf is not None:
                return int(getattr(leaf, "size", len(leaf)))
        leaf = next(iter(batch.values()))
        return int(leaf.shape[0])
    except Exception:
        return 0


def device_prefetch(host_iter: Iterator, put: Callable, depth: int = 2
                    ) -> Iterator:
    """Yield device-resident batches staged by a dedicated transfer thread.

    ``put`` is the host→device placement fn (e.g. Trainer._put_batch). It
    runs on its own thread: while the consumer dispatches step N, the
    transfer thread is already staging batches N+1.. into a bounded queue
    of ``depth`` device-resident batches, with one more transfer kept in
    flight behind the current ``put`` call. A slow ``put`` therefore never
    blocks the consumer while staged batches remain queued.

    A put returning a ``StagedBatch`` (the coalesced stager) is finalized
    on the CONSUMER thread: the staging thread then only moves data, and
    every multi-device XLA execution (unpack + step) is dispatched from
    one thread — launching them from two threads interleaves per-device
    enqueue order and can deadlock against a collective-bearing step.

    Closing the returned generator stops the transfer thread and propagates
    close() to ``host_iter`` (so upstream worker threads shut down too).
    """
    import jax

    from ..telemetry.tracer import span

    # a put that records its own stage spans (CoalescedStager splits
    # pack → input.stage/"stage" and issue → input.issue/"transfer") must
    # not be timed or have its items counted twice; we then only charge
    # the completion wait. Any other put IS the issue (per-leaf
    # device_put): its seconds join the completion wait in ONE "transfer"
    # cell per batch, with the items.
    put_records = getattr(put, "records_stages", False)

    def staged():
        # Batches are yielded the moment their transfer is ISSUED (jax
        # arrays are futures — the consumer's dispatch does not need them
        # materialized), so a put() blocked on batch N never withholds an
        # already-issued batch from the consumer. The issue point is
        # DOUBLE-BUFFERED (round 9): up to two issued transfers ride
        # behind the current put before the thread waits on the oldest,
        # so packing batch N+1 (host memcpy, the "stage" counter) overlaps
        # batch N's H2D DMA instead of serializing with it — the residue
        # behind BENCH_r05's e2e_vs_slowest_component = 0.544. The wait on
        # the oldest still makes the "transfer" counter reflect true H2D
        # throughput (issue alone is async and near-free), and the
        # staging ring bounds how far the host buffers can run ahead
        # (a slot is only rewritten once its transfer completed).
        from collections import deque
        pending = deque()  # (device_batch, items, issue_seconds)

        def charge(entry):
            dev, items, issue_s = entry
            # StagedBatch exposes block_until_ready (transfer only); plain
            # pytrees block leaf-wise (non-jax leaves pass through). A
            # failed transfer raises here and re-raises on the consumer
            # (threaded_iterator) — it must not train on a batch that
            # never arrived.
            with span("input.transfer") as wait:
                blocker = getattr(dev, "block_until_ready", None)
                if blocker is not None:
                    blocker()
                else:
                    jax.block_until_ready(dev)
            if put_records:
                wait.charge("transfer")
            else:
                wait.charge("transfer", items=items, extra_s=issue_s or 0.0)

        try:
            for batch in host_iter:
                if put_records:
                    out, items, issue_s = put(batch), 0, 0.0
                else:
                    with span("input.issue") as issue:
                        out = put(batch)
                    items, issue_s = _batch_items(batch), issue.seconds
                pending.append((out, items, issue_s))
                while len(pending) > 2:  # double-buffered issue window
                    charge(pending.popleft())
                yield out
            while pending:
                charge(pending.popleft())
        finally:
            # propagate close() (e.g. Trainer replacing its cached
            # prefetcher) down to the source so worker threads shut down
            close = getattr(host_iter, "close", None)
            if close is not None:
                close()

    inner = threaded_iterator(staged(), depth, name="drt-device-stage")

    def finalized():
        # runs on the CONSUMER thread: resolve StagedBatch handles into
        # their leaf pytrees (an async multi-device dispatch of the unpack
        # program — µs when the runtime takes it, longer when it pushes
        # back; input.finalize says which)
        try:
            for item in inner:
                fin = getattr(item, "finalize", None)
                if fin is not None:
                    with span("input.finalize"):
                        item = fin()
                yield item
        finally:
            inner.close()

    return finalized()


class _WorkerError:
    def __init__(self, exc: BaseException):
        self.exc = exc


_STOP = object()


def threaded_iterator(src: Iterator, depth: int = 2,
                      name: str = "drt-input-worker") -> Iterator:
    """Run ``src`` on a daemon thread feeding a bounded queue of ``depth``.

    Worker exceptions re-raise on the consuming thread; closing the returned
    generator (or GC'ing it) sets a stop event that EVERY queue put honors —
    including the terminal sentinel/error puts — so the thread can never
    park forever on a full queue.
    """
    q: queue_mod.Queue = queue_mod.Queue(maxsize=depth)
    stop = threading.Event()

    def put_checked(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.2)
                # re-check after the put: the consumer's shutdown drain may
                # have freed the slot we just filled — starting another
                # next(src) now would outlive the join and leak nested
                # workers, so report shutdown even though the put landed
                return not stop.is_set()
            except queue_mod.Full:
                continue
        return False

    def worker():
        try:
            for item in src:
                if not put_checked(item):
                    return
            put_checked(_STOP)
        except BaseException as e:  # surface on the consumer thread
            put_checked(_WorkerError(e))

    thread = threading.Thread(target=worker, daemon=True, name=name)
    thread.start()

    def get_checked():
        # timed get + liveness re-check (hangcheck untimed-blocking-call,
        # docs/static_analysis.md): a worker killed without posting its
        # _STOP/error sentinel (interpreter teardown, a hard native
        # crash) must become a loud RuntimeError on the consumer thread,
        # not a permanent park on an empty queue
        while True:
            try:
                return q.get(timeout=5.0)
            except queue_mod.Empty:
                if not thread.is_alive():
                    try:  # a sentinel may have landed after the timeout
                        return q.get_nowait()
                    except queue_mod.Empty:
                        raise RuntimeError(
                            f"input worker thread {name!r} died without "
                            "reporting — upstream iterator lost") from None

    try:
        while True:
            item = get_checked()
            if item is _STOP:
                return
            if isinstance(item, _WorkerError):
                raise item.exc
            yield item
    finally:
        stop.set()
        # The worker may still be executing next(src); a generator cannot be
        # closed from another thread while executing, so unblock any pending
        # put and join (briefly) before closing. A worker stuck in blocking
        # IO is a daemon thread — abandoned after the timeout, and close()
        # then tolerates the cross-thread race.
        try:
            q.get_nowait()
        except queue_mod.Empty:
            pass
        try:
            thread.join(timeout=1.0)
        except TypeError:
            # interpreter teardown: a GC'd generator can land here after
            # threading internals are already None'd out
            pass
        close = getattr(src, "close", None)
        if close is not None:
            try:
                close()
            except ValueError:  # generator still executing on the worker
                pass


def threaded_stacker(host_iter: Iterator, k: int, depth: int = 2) -> Iterator:
    """Draw K batches and np.stack them in a background thread.

    This is the input side of the fused ``steps_per_loop`` dispatch
    (Trainer.jitted_multi_step): the K-batch draw + stack is real host work
    (decode, memcpy) that would otherwise sit between scan dispatches; a
    bounded queue of ``depth`` pre-stacked loops keeps the dispatch thread
    hot. Iterator exhaustion ends the stream cleanly; a trailing partial
    group of < k batches cannot be dispatched as a fused loop and is
    dropped — logged once at stream end, never silently (the no-silent-caps
    rule). Closing the returned generator stops the worker thread.
    """
    import numpy as np

    from ..telemetry.tracer import span

    def groups():
        while True:
            batches = []
            try:
                for _ in range(k):
                    batches.append(next(host_iter))
            except StopIteration:
                if batches:
                    log.warning(
                        "threaded_stacker: dropping %d trailing batch(es) "
                        "at stream end (shorter than the k=%d fused-loop "
                        "group)", len(batches), k)
                return
            with span("input.stack") as sp:
                out = {key: np.stack([b[key] for b in batches])
                       for key in batches[0]}
            sp.charge("stack", items=_batch_items(out))
            yield out

    return threaded_iterator(groups(), depth, name="drt-batch-stacker")
