"""Flight-recorder span tracer: low-overhead per-thread spans in a ring.

The reference's only answer to "where did the wall-clock go" was offline log
scraping (SURVEY.md §2.15, §5); Horovod shipped a timeline tracer precisely
because distributed step-time mysteries cannot be debugged from scalars
(arXiv:1802.05799). This module is that capability for the framework:

  * ``span("input.wait")`` — a context manager recording one timed event
    per use into a BOUNDED in-memory ring (a crashed/wedged run holds the
    last ~N events per process, like an aircraft flight recorder). The hot
    path is two ``perf_counter`` reads, one locked deque append, one
    ``(count, seconds)`` cell and one ``TraceMe`` — cheap enough to leave
    on in production (the acceptance bar was <2% on the CIFAR headline;
    PERF.md, PR 24: nothing that shows on the chip).
  * ONE CLOCK: every span also enters a ``jax.profiler.TraceAnnotation``
    of its own name (a ``StepTraceAnnotation`` when it carries
    ``step_num``), so whenever a profiler session listens — the
    benchmark's ``--trace 1``, ``telemetry.profile_on_anomaly``, an
    operator's own ``start_trace`` — the span is an event on the host
    plane of the ``.xplane.pb``, on the profiler's clock, on the thread
    that ran it, beside the device ops. No switch: ``TraceMe`` records
    only while a session is active. A process in which ``jax`` is not
    loaded (a spawned worker that never imports it) skips the annotation.
  * ONE TIMER: the span is the only thing that reads the clock at a site.
    On exit it charges a ``(count, seconds)`` cell under its own name in
    ``utils.metrics.input_stages``; a site that feeds a legacy stage key
    (``decode``/``echo``/``stack``/``stage``/``transfer``/
    ``dispatch_wait``, with items and bytes) calls ``sp.charge(stage,
    ...)`` after the ``with`` — charged from the span's measured
    duration, never from a second ``perf_counter`` pair.
  * ``FlightRecorder.dump()`` — serialize the ring as a Chrome-trace /
    Perfetto ``trace.json`` (``{"traceEvents": [...]}``, complete "X"
    events with per-thread lanes and thread-name metadata), atomically.
  * ``dump_on_anomaly()`` — the watchdog's hook (resilience/watchdog.py):
    when a hang / peer-loss escalation or a straggler flag fires, the ring
    dumps automatically and a ``{"event": "trace_dump"}`` row lands in
    metrics.jsonl, so the post-mortem starts with "what was each thread
    doing", not with reproducing the hang. Optionally brackets an
    on-demand ``jax.profiler`` window (utils/profiling.trace_window) for
    device-side visibility too.

Spans may carry a goodput ``category`` (telemetry/goodput.py): the span's
duration is charged to that category on exit, so ONE instrumentation site
feeds both the flight recorder and the goodput accounting. Nested
categorized spans charge only the outermost one (per thread) — an
``eval.batch`` inside an ``eval.round`` must not double-count.

Span names are REGISTERED in :data:`SPAN_CATALOG` — the same drift
contract as ``utils.metrics.EVENT_SCHEMAS``: the registry-drift lint rule
(analysis/rules/registry_drift.py) resolves every ``span("<name>")``
literal against the catalog, and unknown names warn once at runtime
(observability must never kill a run). docs/observability.md is the
operator-facing catalog. The names the program puts on DEVICE operations
(``jax.named_scope``) are registered beside it, in :data:`SCOPE_CATALOG`,
under the same rule; they are trace-time metadata and nothing here runs
for them.
"""
from __future__ import annotations

import collections
import json
import logging
import os
import sys
import threading
import time
from typing import Any, Dict, NamedTuple, Optional

from ..utils.metrics import input_stages

log = logging.getLogger(__name__)

#: bump when the trace.json event shape changes (consumers key on it via
#: the ``trace_dump`` metrics row and the file's otherData block)
SPAN_SCHEMA_VERSION = 13  # 13: + input.noise (data/tokens.py: one batch's
#                              block-diffusion noising drawn on the host)
#                              12: + input.tokens (data/tokens.py: one packed
#                              batch of a token stream)
#                              11: + train.hook_read (a cadence hook's
#                               late read of device metrics) and
#                               train.lead_wait (the fused loop's bound
#                               on dispatches in flight), PR 32
#                          10: + train.hooks/train.build/
#                               train.init_state/input.finalize/
#                               input.issue; input.stage narrowed to the
#                               pack; every span is also a profiler
#                               TraceAnnotation and a (count, seconds)
#                               cell in input_stages (PR 25)
#                          9: + route.attempt/route.health (serving
#                              fleet front door, round 19)
#                          8: + plan.predict/plan.drift_check (what-if
#                              performance planner, round 17)
#                          7: + reshard.* family (elastic mesh
#                              shrink/grow transition, round 16)
#                          6: + comm.probe; comm.bucket / zero1.gather
#                              gain a bucket-index arg so the merged
#                              timeline / comm report can join spans to
#                              the plan (performance observability,
#                              round 14)
#                          5: + serve.variant_build; comm.bucket /
#                              zero1.gather gain a wire_bytes arg
#                              (low-precision hot paths, round 12)
#                          4: + checkpoint.shard/checkpoint.finalize/
#                              zero1.gather (ZeRO-1 sharded update +
#                              per-host sharded checkpoints, round 11)
#                          3: + checkpoint.snapshot/checkpoint.writer/
#                              comm.bucket (zero-stall step loop, round 10)

#: every span name the framework emits — register HERE first (the
#: registry-drift rule rejects unregistered ``span("...")`` literals, the
#: runtime warns once). Value = one-line description for the docs.
SPAN_CATALOG = {
    # input pipeline (data/device_prefetch.py, data/imagenet.py)
    "input.decode": "one image decoded + cropped (imagenet decode worker) "
                    "or one batch gathered + augmented (cifar iterator); "
                    "charges the 'decode' stage",
    "input.tokens": "one batch of a token stream drawn and packed to "
                    "fixed sequences (data/tokens.py)",
    "input.noise": "one batch's block-diffusion noising drawn on the host: "
                   "a level per diffusion block, a mask per id "
                   "(data/tokens.block_diffusion_noise)",
    "input.stack": "K host batches np.stack'ed (stacker thread; charges "
                   "the 'stack' stage)",
    "input.echo": "one source batch absorbed into the decoded-sample echo "
                  "cache (data/echo.py)",
    "input.echo_emit": "one batch drawn from the echo cache (charges the "
                       "'echo' stage)",
    "input.stage": "host batch packed into the staging ring "
                   "(CoalescedStager.put, staging thread; charges the "
                   "'stage' stage)",
    "input.issue": "the batch's host→device transfer issued: the "
                   "stager's device_put + global-array assembly, or the "
                   "whole per-leaf put (staging thread; charges "
                   "'transfer') — what XlaLinearize gaps are put down to",
    "input.transfer": "wait for an issued H2D transfer to complete "
                      "(staging thread; charges 'transfer')",
    "input.wait": "train (or eval) loop blocked waiting for the next "
                  "device batch, its input.finalize included (goodput: "
                  "input_wait in the train loop; charges 'dispatch_wait')",
    "input.finalize": "consumer-thread finalize of a StagedBatch: the "
                      "dispatch of the unpack(+augment) program (a child "
                      "of input.wait)",
    # train loop (train/loop.py)
    "train.build": "Trainer.__init__: mesh, model, optimizer, resolvers, "
                   "stagers",
    "train.init_state": "Trainer.init_state: the jitted state "
                        "initialisation + placement",
    "train.step": "one optimizer-step (or fused K-step) dispatch: host "
                  "time inside the jitted call; a StepTraceAnnotation "
                  "(step_num = first step of the dispatch)",
    "train.hooks": "the hook loop after one dispatch (all hooks, one "
                   "span)",
    "train.hook_read": "a cadence hook's late read of device metrics "
                       "(train/hooks.py): the values kept at its last "
                       "cadence step pulled to the host, one dispatch "
                       "later (inside train.hooks, or after the loop at "
                       "Trainer.train's flush); count = late reads, "
                       "seconds = the loop's wait for the device",
    "train.lead_wait": "the loop's wait, after sending a dispatch, for "
                       "the one FUSED_DISPATCH_LEAD back (fused loop) or "
                       "the step STEP_LEAD_SECONDS of device work back "
                       "(one-step loop) (train/loop.py): bounds the "
                       "dispatches in flight, the groups and outputs the "
                       "runtime holds for them, and what a stop_fn waits "
                       "behind",
    "eval.round": "one full evaluation round (goodput: eval)",
    "eval.batch": "one eval batch: stage wait + step dispatch",
    # checkpointing (checkpoint/manager.py)
    "checkpoint.save": "save() on the step-loop thread: backpressure + "
                       "host snapshot + handoff (async) or the full "
                       "write (sync) (goodput: checkpoint)",
    "checkpoint.snapshot": "device→host state copy on the step-loop "
                           "thread (async issue, one overlapped D2H "
                           "transfer; the loop-blocking leg of an async "
                           "save)",
    "checkpoint.wait": "step-loop thread blocked on an in-flight async "
                       "save (goodput: checkpoint)",
    "checkpoint.writer": "the dedicated writer thread's whole "
                         "stage→fsync→manifest→commit pass over a host "
                         "snapshot (overlaps compute; accounted in the "
                         "ckpt_async row, NOT goodput checkpoint)",
    "checkpoint.stage": "orbax serialization into the staging dir "
                        "(writer thread when async)",
    "checkpoint.shard": "this host's per-host shard files staged + "
                        "fsynced (sharded layout; writer thread)",
    "checkpoint.finalize": "sharded multi-process finalize: marker-file "
                           "wait for peer shards, then manifest + "
                           "commit rename (chief writer) or the wait "
                           "for the chief's commit (peers)",
    "checkpoint.fsync": "manifest write + fsync",
    "checkpoint.commit": "atomic rename + parent-dir fsync",
    "restore": "checkpoint restore into the live state (goodput: restart "
               "when on the NaN-rollback path)",
    # serving (serve/server.py, serve/swap.py)
    "serve.batch": "one bucket dispatch: stage + AOT predict + resolve",
    "serve.swap_restore": "off-path host restore of a newer checkpoint",
    "serve.swap_apply": "atomic param swap at a batch boundary",
    "serve.variant_build": "one serving precision variant's weight copy "
                           "cast from the f32 masters (startup and every "
                           "hot swap; docs/precision.md)",
    # serving fleet front door (serve/router.py, docs/serving.md)
    "route.attempt": "one request attempt forwarded to a replica "
                     "(router worker thread: send → response/failure; "
                     "replica/attempt args — hedges and retries are "
                     "extra route.attempt spans for the same request)",
    "route.health": "one health-scan pass over the fleet (router health "
                    "thread: heartbeat ages + telemetry tails + canary "
                    "controller turn)",
    # elastic mesh generation transition (resilience/elastic.py;
    # goodput: reshard for every leg — the whole transition is
    # non-compute wall time)
    "reshard.barrier": "file-based join barrier: post membership, wait "
                       "for the settle window + the chief candidate's "
                       "commit record (no collectives — peers may be "
                       "dead)",
    "reshard.teardown": "dead-mesh teardown: abandon the blocking "
                        "distributed-client shutdown in a daemon thread, "
                        "reset jax's process-global distributed state, "
                        "clear backends + caches",
    "reshard.init": "jax.distributed re-initialize over the survivors at "
                    "the new generation's epoch-suffixed coordinator",
    "reshard.restore": "last committed checkpoint restored into the new "
                       "topology (sharded M≠N assemble path when the "
                       "layout is sharded)",
    "reshard.rebuild": "Trainer/mesh/sharding re-elaboration + input "
                       "source rebuild for the new generation",
    # what-if performance planner (telemetry/planner.py)
    "plan.predict": "one layout candidate costed by the analytic "
                    "model (preset/layout args; main.py plan and the "
                    "plan-drift gate phase)",
}

class Scope(NamedTuple):
    """One component of a device operation's scope path (the ``tf_op`` stat
    of a TPU trace, ``loc("...")`` in lowered text)."""
    origin: str   # "scope": a jax.named_scope of ours; "module": a flax
    #               module's name that a reader keys on; "jax": a
    #               component JAX's own transforms add
    under: str    # the registered components it always lies below
    where: str    # the emit site
    holds: str    # the operations under it
    read_by: str  # the benchmark metric (or tool) that reads it


#: every component of a device operation's scope path that something reads
#: — register HERE first. The device side of SPAN_CATALOG, and the same
#: contract: the registry-drift rule resolves every
#: ``jax.named_scope("<literal>")`` (call and decorator) in the package and
#: every ``named_scope("<name>")`` docs/observability.md mentions against
#: it. A scope is trace-time metadata: nothing checks it at run time, and a
#: program fetched from the compile cache shows the scopes of the commit
#: that compiled it. A reader matches a component bare or wrapped by the
#: backward pass's transforms (``transpose(jvp(attention))``); no name here
#: may be a flax module's name in the decoder's paths but the two listed
#: as such. ``step_parts.py`` is benchmark/tools/step_parts.py, which
#: prints a kept trace by these rows.
SCOPE_CATALOG = {
    # the step program (train/loop.py, train/state.py)
    "forward": Scope(
        "scope", "", "train/loop.make_train_step: loss_fn",
        "model apply, loss and in-loss decay; transpose(jvp(forward)) is "
        "the backward pass",
        "step_parts.py (the forward, recomputed and backward columns)"),
    "optimizer": Scope(
        "scope", "", "train/state.TrainState.apply_gradients; "
        "train/loop.py (the ZeRO-1 update)",
        "the weight update; reads near nothing where XLA fuses it into the "
        "op that makes the gradient", "step_parts.py"),
    "input_prep": Scope(
        "scope", "", "train/loop.ClassifierObjective.prepare",
        "the in-step device augmentation", "step_parts.py"),
    "after_update": Scope(
        "scope", "", "train/loop.make_train_step: update",
        "the family's rule after the optimizer's update (afmoe: the "
        "router-bias rule)", "step_parts.py"),
    # the stager's unpack program (parallel/sharding.py)
    "unpack": Scope(
        "scope", "", "parallel/sharding._build_unpack",
        "the staged bytes sliced and bitcast to the batch's leaves",
        "step_parts.py"),
    "augment": Scope(
        "scope", "", "parallel/sharding._build_unpack",
        "flip, crop and standardise on the staged uint8 crops",
        "step_parts.py"),
    # the decoder family (models/transformer.py, models/moe.py)
    "blockdiff_input": Scope(
        "scope", "forward",
        "models/transformer.BlockDiffusionObjective.forward",
        "masked ids replaced, the noisy and the clean copy joined, "
        "position ids and 1/t weights", "step_parts.py"),
    "attention": Scope(
        "scope", "forward", "models/transformer._decoder_layer, MixerBlock",
        "input norm, projections, head norms or latents, rotary, the "
        "kernels, gate, output projection, post-norm and the residual add",
        "attention_ms, attention_rest_ms"),
    "latent": Scope(
        "scope", "attention", "models/transformer.LatentAttention",
        "both latents' projections and RMS norms, the query and key/value "
        "up-projections, the rotary key split off and broadcast to the "
        "heads, the rotary", "mla_latent_ms"),
    "rotary": Scope(
        "scope", "attention", "models/transformer.GroupedAttention",
        "rotate-half and sin/cos over queries and keys",
        "step_parts.py (inside attention_rest_ms)"),
    "core": Scope(
        "scope", "attention",
        "models/transformer.GroupedAttention, LatentAttention",
        "the flash kernels or their dense twin with their casts, reshapes "
        "and block tables", "attention_rest_ms (attention without it)"),
    "moe": Scope(
        "scope", "forward", "models/transformer.DecoderBlock",
        "route, experts and shared; the grouped products' kernels carry no "
        "path in a TPU trace and are read by name", "moe_ms"),
    "route": Scope(
        "scope", "moe", "models/moe.DroplessMoe.walked",
        "the float32 router product, scores, top-k and counts",
        "step_parts.py (inside moe_ms)"),
    "experts": Scope(
        "module", "moe", "models/moe.HeldExperts, named by DroplessMoe",
        "the held experts' walk: the six parts below; under it and under "
        "none of them, the loops' own (the compiler's relayouts of the "
        "kernels every window, which it names after the while)",
        "moe_experts_roofline, sdar_experts_roofline"),
    "shared": Scope(
        "module", "moe", "models/moe.SwiGLU or Relu2, named by DroplessMoe",
        "the shared expert's three products (SwiGLU) or two (relu²)",
        "step_parts.py (inside moe_ms)"),
    "plan": Scope(
        "scope", "moe/experts", "models/moe.held_experts_sum, _window",
        "a layer's five sorts and a window's int32 bookkeeping",
        "step_parts.py"),
    "operands": Scope(
        "scope", "moe/experts", "models/moe._operands, _walk_bwd",
        "tokens and kernels cast to the products' precision and "
        "zero-padded to product_widths, the cotangent padded, once a walk",
        "step_parts.py"),
    "gather": Scope(
        "scope", "moe/experts", "models/moe._walk_fwd, _walk_bwd",
        "a window's rows of tokens, weights and cotangents gathered",
        "step_parts.py"),
    "products": Scope(
        "scope", "moe/experts", "models/moe._walk_fwd, _walk_bwd",
        "silu x up, x weights, the live-row masks, casts and cuts back to "
        "the published width around the grouped products (the ragged-dot "
        "kernels are found by name)",
        "step_parts.py; the kernels by moe_products_ms, "
        "moe_products_roofline, moe_product_calls"),
    "to_tokens": Scope(
        "scope", "moe/experts",
        "models/moe._sum_to_tokens, _token_order, _walk_fwd, _walk_bwd",
        "a window's rows summed back to their tokens and written back, "
        "the tokens' own order, the one scatter of the weights' gradient",
        "step_parts.py"),
    "carry": Scope(
        "scope", "moe/experts", "models/moe._walk_bwd",
        "the kernels' gradient added into the walk's float32 "
        "accumulators, once a window", "moe_carry_ms"),
    "lm_head": Scope(
        "scope", "forward", "models/transformer.CausalDecoder, "
        "predict_further (under mtp)",
        "the chunked head and loss, the main stack's and each prediction "
        "block's", "lm_head_ms"),
    "mtp": Scope(
        "scope", "forward", "models/transformer.CausalDecoder.predict_further",
        "the multi-token-prediction blocks: the next ids' embedding, both "
        "norms, W_eh, the block (its attention and moe scopes under it) "
        "and its head's loss chunks", "mtp_ms"),
    # the Mamba-2 mixer (models/mamba.py) of the one-mixer blocks
    "mamba": Scope(
        "scope", "forward", "models/transformer.MixerBlock",
        "the block's norm, the input projection, the three parts below, "
        "the output projection and the residual add", "mamba_ms"),
    "conv": Scope(
        "scope", "mamba", "models/mamba.Mamba2",
        "the causal depthwise convolution over x, B and C, its SiLU and "
        "their hand-written gradient",
        "step_parts.py (inside mamba_ms)"),
    "scan": Scope(
        "scope", "mamba", "models/mamba.Mamba2",
        "softplus of the step sizes, the chunked scan (ops/ssd.py) with its "
        "reshapes, and the D term", "ssd_roofline"),
    "gated_norm": Scope(
        "scope", "mamba", "models/mamba.Mamba2",
        "y times SiLU(z) and the grouped RMS norm",
        "step_parts.py (inside mamba_ms)"),
    # JAX's own (jax/_src/ad_checkpoint.py), under nn.remat and
    # jax.checkpoint: not ours to emit, ours to read
    "checkpoint": Scope(
        "jax", "", "nn.remat(DecoderBlock), the chunked head's loss",
        "everything a rematerialised function's backward pass runs; alone "
        "it does not tell recomputation", "step_parts.py"),
    "rematted_computation": Scope(
        "jax", "checkpoint", "the same, below checkpoint",
        "the forward operations the backward pass computes again",
        "recompute_ms"),
}


# unknown span names already warned about (warn once, like write_event)
_UNKNOWN_SPANS_WARNED: set = set()


class _NoopSpan:
    """Shared do-nothing span for a disabled recorder: no ring entry, no
    annotation, no counter."""

    __slots__ = ()
    seconds = None  # nothing was measured

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def charge(self, stage, items=0, nbytes=0, extra_s=0.0):
        pass


_NOOP = _NoopSpan()

# (TraceAnnotation, StepTraceAnnotation) once jax is loaded in this process
_ANNOTATIONS = None


def _annotations():
    """jax.profiler's annotation classes, or None in a process that has not
    loaded jax (the tracer must not be what imports it there)."""
    global _ANNOTATIONS
    if _ANNOTATIONS is None and "jax" in sys.modules:
        from jax.profiler import StepTraceAnnotation, TraceAnnotation
        _ANNOTATIONS = (TraceAnnotation, StepTraceAnnotation)
    return _ANNOTATIONS


class _Span:
    __slots__ = ("_rec", "name", "category", "args", "step_num", "_t0",
                 "_counted", "_ann", "seconds")

    def __init__(self, rec: "FlightRecorder", name: str,
                 category: Optional[str], args: Optional[dict],
                 step_num: Optional[int] = None):
        self._rec = rec
        self.name = name
        self.category = category
        self.args = args
        self.step_num = step_num
        self.seconds = None  # the measured duration, once exited

    def __enter__(self):
        self._counted = False
        if self.category is not None:
            # outermost-categorized-span guard (see module docstring):
            # only spans that carried a category touch the depth counter
            local = self._rec._local
            depth = getattr(local, "cat_depth", 0)
            local.cat_depth = depth + 1
            self._counted = True
            if depth:
                self.category = None
        self._t0 = time.perf_counter()
        # the annotation starts inside the ring entry's interval and ends
        # inside it; TraceMe records only while a profiler session listens
        ann = _annotations()
        if ann is None:
            self._ann = None
        elif self.step_num is not None:
            self._ann = ann[1](self.name, step_num=self.step_num)
        elif self.args:
            self._ann = ann[0](self.name, **self.args)
        else:
            self._ann = ann[0](self.name)
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        t1 = time.perf_counter()
        rec = self._rec
        self.seconds = dur = t1 - self._t0
        tid = threading.get_ident()
        if tid not in rec._thread_names:
            rec._thread_names[tid] = threading.current_thread().name
        with rec._lock:
            rec._events.append((self.name, tid, self._t0, dur, self.args))
        if self._counted:
            rec._local.cat_depth -= 1
        if self.category is not None:
            from .goodput import goodput
            goodput.add(self.category, dur)
        input_stages.add(self.name, dur)
        return False

    def charge(self, stage: str, items: int = 0, nbytes: int = 0,
               extra_s: float = 0.0) -> None:
        """After the ``with``: charge the measured duration (plus
        ``extra_s``, another span's ``seconds``) to a legacy stage key of
        ``utils.metrics.input_stages`` with its items and bytes."""
        input_stages.add(stage, self.seconds + extra_s, items=items,
                         nbytes=nbytes)


class FlightRecorder:
    """The process-global bounded span ring + dump machinery.

    ``configure()`` is called once per entry point (main.py) with the run's
    dump directory and (chief-only) metrics writer; until then spans still
    record — only automatic dumps need the configuration.
    """

    def __init__(self, ring: int = 65536, enabled: bool = True):
        self._lock = threading.Lock()
        self._events: collections.deque = collections.deque(maxlen=ring)
        self._thread_names: Dict[int, str] = {}
        self._local = threading.local()
        self._enabled = enabled
        self._epoch_perf = time.perf_counter()
        self._epoch_wall = time.time()
        self._dump_dir: Optional[str] = None
        self._writer = None
        self._process_index = 0
        self._profile_on_anomaly = False
        self._profile_secs = 5.0
        self._profiled = False

    # -- configuration ------------------------------------------------------
    def configure(self, dump_dir: Optional[str] = None, writer=None,
                  ring: Optional[int] = None,
                  enabled: Optional[bool] = None,
                  process_index: Optional[int] = None,
                  profile_on_anomaly: Optional[bool] = None,
                  profile_secs: Optional[float] = None) -> None:
        if ring is not None and ring != self._events.maxlen:
            with self._lock:
                self._events = collections.deque(self._events, maxlen=ring)
        if enabled is not None:
            self._enabled = enabled
        if dump_dir is not None:
            self._dump_dir = dump_dir
        if writer is not None:
            self._writer = writer
        if process_index is not None:
            self._process_index = process_index
        if profile_on_anomaly is not None:
            self._profile_on_anomaly = profile_on_anomaly
        if profile_secs is not None:
            self._profile_secs = profile_secs

    @property
    def enabled(self) -> bool:
        return self._enabled

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def __len__(self) -> int:
        return len(self._events)

    # -- recording ----------------------------------------------------------
    def span(self, name: str, category: Optional[str] = None,
             step_num: Optional[int] = None, **args):
        """Context manager timing one event. ``category`` charges the
        duration to the goodput meter (outermost categorized span per
        thread only); ``**args`` ride into the trace event and the
        profiler annotation (keep them off hot paths — the dict
        allocation is the cost). ``step_num`` makes the profiler event a
        step (``StepTraceAnnotation``) and rides nowhere else."""
        if not self._enabled:
            return _NOOP
        if name not in SPAN_CATALOG and name not in _UNKNOWN_SPANS_WARNED:
            _UNKNOWN_SPANS_WARNED.add(name)
            log.warning(
                "span %r is not declared in telemetry.tracer.SPAN_CATALOG "
                "— register it (the registry-drift lint rejects "
                "undeclared literals)", name)
        return _Span(self, name, category, args or None, step_num)

    # -- dumping ------------------------------------------------------------
    def trace_events(self) -> list:
        """The ring as Chrome-trace event dicts (ts/dur in microseconds,
        relative to the recorder epoch)."""
        with self._lock:
            snap = list(self._events)
        names = dict(self._thread_names)
        pid = os.getpid()
        events = [
            {"name": f"thread: {tname}", "ph": "M", "pid": pid, "tid": tid,
             "ts": 0, "cat": "__metadata", "args": {"name": tname}}
            for tid, tname in sorted(names.items())]
        # Perfetto also honors the canonical thread_name metadata record
        events += [
            {"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
             "ts": 0, "args": {"name": tname}}
            for tid, tname in sorted(names.items())]
        for name, tid, t0, dur, args in snap:
            ev = {"name": name, "ph": "X", "pid": pid, "tid": tid,
                  "ts": round((t0 - self._epoch_perf) * 1e6, 3),
                  "dur": round(dur * 1e6, 3), "cat": "span"}
            if args:
                ev["args"] = {k: _jsonable(v) for k, v in args.items()}
            events.append(ev)
        return events

    def default_dump_path(self) -> Optional[str]:
        if self._dump_dir is None:
            return None
        name = "trace.json" if self._process_index == 0 \
            else f"trace.proc{self._process_index}.json"
        return os.path.join(self._dump_dir, name)

    def dump(self, path: Optional[str] = None,
             reason: str = "on_demand") -> Optional[str]:
        """Write the ring as ``trace.json`` (atomic tmp+rename). Returns
        the path written, or None when no path is known. Never raises —
        the callers are crash/teardown paths."""
        try:
            path = path or self.default_dump_path()
            if path is None:
                return None
            events = self.trace_events()
            doc = {
                "traceEvents": events,
                "displayTimeUnit": "ms",
                "otherData": {
                    "span_schema_version": SPAN_SCHEMA_VERSION,
                    "reason": reason,
                    "process_index": self._process_index,
                    "pid": os.getpid(),
                    "epoch_wall_time": self._epoch_wall,
                    "ring_capacity": self._events.maxlen,
                },
            }
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            tmp = f"{path}.tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, path)
            log.info("flight recorder: %d span(s) dumped to %s (%s)",
                     sum(1 for e in events if e.get("ph") == "X"), path,
                     reason)
            return path
        except Exception:  # a failed dump must not worsen the teardown
            log.exception("flight recorder dump failed")
            return None

    def dump_on_anomaly(self, kind: str, detail: str = "") -> Optional[str]:
        """The watchdog / fatal-exit hook: dump the ring, record a
        ``trace_dump`` metrics row (chief), optionally bracket a
        ``jax.profiler`` window (telemetry.profile_on_anomaly — once per
        process: a flapping straggler must not profile in a loop)."""
        path = self.dump(reason=kind)
        if self._writer is not None:
            try:
                self._writer.write_event("trace_dump", {
                    "reason": kind, "detail": detail,
                    "path": path or "",
                    "spans": len(self._events),
                    "span_schema_version": SPAN_SCHEMA_VERSION})
                self._writer.flush()
            except Exception:  # pragma: no cover - observability best effort
                log.exception("trace_dump metrics row failed")
        if self._profile_on_anomaly and not self._profiled \
                and self._dump_dir is not None:
            self._profiled = True
            try:
                from ..utils.profiling import trace_window
                trace_window(os.path.join(self._dump_dir, "profile"),
                             self._profile_secs)
            except Exception:  # pragma: no cover - profiler best effort
                log.exception("anomaly-triggered jax.profiler window failed")
        return path


def _jsonable(v: Any):
    return v if isinstance(v, (str, int, float, bool, type(None))) else str(v)


#: the process-global recorder every instrumentation site uses
recorder = FlightRecorder()

#: ``from ..telemetry import span`` — the one spelling the lint rule knows
span = recorder.span
