"""Jitted train/eval steps and the explicit training loop.

Replaces the reference's ``MonitoredTrainingSession`` + hook machinery and
``while not should_stop(): run(train_op)`` hot loop (reference
resnet_cifar_main.py:311-337) with an explicit, functional loop:

    state, metrics = train_step(state, batch)    # one fused XLA program

Everything the reference did with session hooks — LR feed (SURVEY §2.12),
logging cadence, summaries, checkpoints — becomes either (a) pure computation
inside the jitted step (LR schedule, metrics) or (b) plain Python callbacks on
the host (hooks.py), with NO per-step host→device feed_dict traffic.

Distribution: the step is jitted over a Mesh; the batch arrives sharded over
the ``data``(×``fsdp``) axes, so XLA's sharding propagation inserts the
gradient all-reduce on ICI — the entire replacement for SyncReplicasOptimizer
(reference resnet_model.py:102-135) and hvd.DistributedOptimizer (reference
resnet_model.py:114-116). Gradient accumulation (lax.scan over microbatches)
stands in for very large global batches on small meshes.
"""
from __future__ import annotations

import collections
import contextlib
import time
from functools import partial
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import (batch_shard_count, create_mesh, data_sharding,
                             present_batch_axes, shard_map_unchecked)
from ..telemetry.tracer import span
from ..parallel.sharding import (finalize_staged, make_global_batch,
                                 shard_batch)
from .optimizers import (create_optimizer, decoupled_decay,
                         loss_weight_decay)
from .schedules import create_schedule
from .state import (TrainState, create_train_state, init_input,
                    state_shardings)


#: fused dispatches queued behind the one the loop waits for (Trainer.train)
FUSED_DISPATCH_LEAD = 2
#: the one-step loop's lead: seconds of device work it lets stand behind the
#: step that runs, at least two steps (Trainer.train)
STEP_LEAD_SECONDS = 2.0


def per_example_cross_entropy(logits: jax.Array, labels: jax.Array,
                              label_smoothing: float = 0.0) -> jax.Array:
    """Per-example softmax CE (optax path). Labels are int class ids (the
    reference one-hotted in the input pipeline, resnet_cifar_main.py:171;
    we one-hot here once, keeping the input pipeline dense)."""
    num_classes = logits.shape[-1]
    onehot = jax.nn.one_hot(labels, num_classes, dtype=jnp.float32)
    if label_smoothing > 0:
        onehot = onehot * (1 - label_smoothing) + label_smoothing / num_classes
    return optax.softmax_cross_entropy(logits.astype(jnp.float32), onehot)


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       label_smoothing: float = 0.0) -> jax.Array:
    """Mean softmax CE over the batch."""
    return per_example_cross_entropy(logits, labels, label_smoothing).mean()


def resolve_fused_xent(fused_xent: str, label_smoothing: float = 0.0) -> str:
    """``train.fused_xent`` → "on" | "interpret" | "off" (see make_ce_fn).
    "auto" never resolves to the interpreter: that is a CPU test seam."""
    if fused_xent not in ("auto", "on", "interpret", "off"):
        raise ValueError(f"unknown fused_xent mode {fused_xent!r}")
    if label_smoothing > 0:
        return "off"  # the kernel computes plain NLL
    if fused_xent == "auto":
        return "on" if jax.default_backend() == "tpu" else "off"
    return fused_xent


def make_ce_fn(label_smoothing: float = 0.0, fused_xent: str = "off",
               mesh: Optional[Mesh] = None) -> Callable:
    """Resolve ``train.fused_xent`` into the batch CE function.

    Modes: "auto" (Pallas kernel iff running on TPU — the default),
    "on" (always compile the kernel), "interpret" (kernel in the Pallas
    interpreter; CPU tests), "off" (optax). The fused kernel replaces the
    reference's fused softmax_cross_entropy_with_logits TF op in-kind
    (reference resnet_model.py:78-80). Label smoothing > 0 falls back to
    optax (the kernel computes plain NLL).

    When the mesh splits the batch over >1 shards, the kernel runs under
    ``shard_map`` so each device computes its local (b/n, C) tile — a plain
    ``jit`` would have to replicate the custom call (all-gathering logits)."""
    mode = resolve_fused_xent(fused_xent, label_smoothing)
    if mode == "off":
        return lambda logits, labels: cross_entropy_loss(
            logits, labels, label_smoothing)
    interpret = mode == "interpret"
    from ..ops.pallas import softmax_xent

    def per_ex(logits, labels):
        return softmax_xent(logits.astype(jnp.float32), labels, interpret)

    if mesh is not None and batch_shard_count(mesh) > 1:
        batch_axes = present_batch_axes(mesh)
        batch_spec = P(batch_axes)
        sharded = shard_map_unchecked(
            per_ex, mesh,
            in_specs=(P(batch_axes, None), batch_spec),
            out_specs=batch_spec)
        return lambda logits, labels: sharded(logits, labels).mean()
    return lambda logits, labels: per_ex(logits, labels).mean()


class ClassifierObjective:
    """What an image classifier asks of the step: ``{"images", "labels"}``
    batches, device-side augmentation at the top of the step, logits into
    the batch cross-entropy, the top-1 share as ``precision``.

    An *objective* is how a model family supplies its loss over its own
    batch (``make_train_step``): ``batch_keys``; ``prepare(batch, step,
    midx)``; ``forward(apply_fn, variables, batch) -> (ce, metrics,
    batch_stats, sown_losses, aux)``; and ``after_update(params, aux) ->
    params`` or None, a rule that moves leaves after the optimizer's update
    from what the forward pass returned beside its loss. A model whose
    class has an ``objective()`` brings its own
    (models/transformer.CausalDecoder); every other gets this one."""
    batch_keys = ("images", "labels")
    after_update = None

    def __init__(self, ce_fn, augment_fn=None, augment_seed: int = 0,
                 precision=None):
        self.ce_fn, self.augment_fn = ce_fn, augment_fn
        self.augment_seed, self.precision = augment_seed, precision

    def prepare(self, batch, step, midx=None):
        if self.augment_fn is None:
            return batch
        with jax.named_scope("input_prep"):
            rng = jax.random.fold_in(jax.random.PRNGKey(self.augment_seed),
                                     step)
            if midx is not None:  # distinct draws per accumulation microbatch
                rng = jax.random.fold_in(rng, midx)
            return dict(batch, images=self.augment_fn(batch["images"], rng))

    def forward(self, apply_fn, variables, batch):
        images, labels = batch["images"], batch["labels"]
        if self.precision is not None:
            # the policy cast wraps model apply (parallel/precision.py):
            # activations enter in the compute dtype; params stay f32
            # masters (flax casts them per-op, and the cast's transpose
            # re-accumulates the gradient into the f32 cotangent)
            images = self.precision.cast_compute(images)
        logits, mutated = apply_fn(variables, images, train=True,
                                   mutable=["batch_stats", "losses"])
        top1 = jnp.mean(
            (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))
        # auxiliary losses sown by modules (e.g. the Switch MoE
        # load-balancing term, models/moe.py)
        sown = jax.tree_util.tree_leaves(mutated.get("losses", {}))
        return (self.ce_fn(logits, labels), {"precision": top1},
                mutated["batch_stats"], sown, None)


def make_train_step(schedule: Callable, weight_decay: float,
                    label_smoothing: float = 0.0,
                    decay_in_loss: bool = True,
                    grad_accum_steps: int = 1,
                    decay_all_params: bool = False,
                    ce_fn: Optional[Callable] = None,
                    augment_fn: Optional[Callable] = None,
                    augment_seed: int = 0,
                    aux_loss_weight: float = 0.01,
                    apply_gradients_fn: Optional[Callable] = None,
                    precision=None, objective=None):
    """Build the pure train_step(state, batch) -> (state, metrics).

    ``objective`` is the model family's loss over its own batch
    (``ClassifierObjective`` documents the contract; None builds that one
    from ``ce_fn``/``augment_fn``/``precision``). The step is one path: what
    differs between an image classifier and a token model is the objective.

    ``augment_fn(images, rng) -> images`` runs device-side augmentation at
    the top of the step (raw uint8 in, standardized f32 out — see
    ops/augment.py); RNG is fold_in(seed, step): deterministic and
    resume-stable.

    ``apply_gradients_fn(state, grads) -> state`` replaces the default
    ``state.apply_gradients(grads)`` — the ZeRO-1 sharded weight update
    (Trainer._make_zero1_apply: reduce-scattered grads → local optimizer
    shard update → all-gathered param updates) plugs in here.

    ``precision`` (a ``parallel.precision.PrecisionPolicy``, or None =
    the bit-identical legacy path): the policy cast that wraps model
    apply — float inputs enter the model in the policy's compute dtype
    (bf16), while the loss/CE/metric arithmetic around the apply stays
    f32 (make_ce_fn casts logits up before the softmax) and the
    gradients/optimizer update run on the f32 masters."""
    if objective is None:
        objective = ClassifierObjective(
            ce_fn if ce_fn is not None else make_ce_fn(label_smoothing),
            augment_fn, augment_seed, precision)
    if apply_gradients_fn is None:
        apply_gradients_fn = lambda state, grads: \
            state.apply_gradients(grads)  # noqa: E731

    # the named scopes are metadata only: they name the device ops of a
    # profiler trace (input_prep / forward / transpose(jvp(forward)) = the
    # backward pass / optimizer), so the step's device time splits by phase
    @jax.named_scope("forward")
    def loss_fn(params, batch_stats, batch, apply_fn):
        variables = {"params": params, "batch_stats": batch_stats}
        ce, extra, new_bs, sown, aux = objective.forward(apply_fn, variables,
                                                         batch)
        loss = ce
        if decay_in_loss:
            # L2 in the loss like the reference (resnet_model.py:78-86);
            # decay_all_params toggles kernels-only vs all-trainables
            loss = loss + loss_weight_decay(params, weight_decay,
                                            decay_all_params)
        if sown:
            loss = loss + aux_loss_weight * sum(jnp.sum(a) for a in sown)
        return loss, (ce, extra, new_bs, aux)

    def update(state, grads, new_bs, aux):
        """The optimizer's update, then the family's rule (if it has one)
        inside the same program."""
        new_state = apply_gradients_fn(state, grads).replace(
            batch_stats=new_bs)
        if objective.after_update is not None:
            with jax.named_scope("after_update"):
                new_state = new_state.replace(
                    params=objective.after_update(new_state.params, aux))
        return new_state

    def single_step(state: TrainState, batch) -> Tuple[TrainState, Dict[str, Any]]:
        batch = objective.prepare(batch, state.step)
        (loss, (ce, extra, new_bs, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params, state.batch_stats,
                                   batch, state.apply_fn)
        metrics = {
            "loss": loss, "cross_entropy": ce, **extra,
            "learning_rate": schedule(state.step),
            "grad_norm": optax.global_norm(grads),
        }
        return update(state, grads, new_bs, aux), metrics

    if grad_accum_steps <= 1:
        return single_step

    def accum_step(state: TrainState, batch) -> Tuple[TrainState, Dict[str, Any]]:
        """lax.scan over microbatches: grads averaged, BN stats from the last
        microbatch (the reference had no accumulation; this enables reference
        global-batch parity on few chips); the objective's metrics averaged
        and what its rule reads (``aux``) summed.

        Augmentation/standardization runs INSIDE the scan body, one
        microbatch at a time — prepping the whole global batch up front
        would materialize it in float32 (at gbs 32k × 224² that is ~20 GB,
        more than a chip's HBM; the uint8 input is 4×-8× smaller)."""
        n = grad_accum_steps
        batch = jax.tree_util.tree_map(
            lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch)
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        def body(carry, xs):
            grads_acc, bs = carry
            mb, midx = xs
            mb = objective.prepare(mb, state.step, midx)
            (loss, (ce, extra, new_bs, aux)), grads = grad_fn(
                state.params, bs, mb, state.apply_fn)
            grads_acc = jax.tree_util.tree_map(jnp.add, grads_acc, grads)
            # scalars and the rule's small sums: stacked, reduced below
            return (grads_acc, new_bs), (
                {"loss": loss, "cross_entropy": ce, **extra}, aux)

        zero_grads = jax.tree_util.tree_map(
            lambda p: jnp.zeros_like(p, jnp.float32), state.params)
        (grads, new_bs), (per_micro, aux) = jax.lax.scan(
            body, (zero_grads, state.batch_stats), (batch, jnp.arange(n)))
        grads = jax.tree_util.tree_map(lambda g: g / n, grads)
        aux = jax.tree_util.tree_map(lambda a: a.sum(axis=0), aux)
        metrics = {
            **{k: v.mean() for k, v in per_micro.items()},
            "learning_rate": schedule(state.step),
            "grad_norm": optax.global_norm(grads),
        }
        return update(state, grads, new_bs, aux), metrics

    return accum_step


def make_eval_step(prep_fn: Optional[Callable] = None):
    """eval_step(state, batch) -> {correct, count, loss_sum} (summable over
    batches — the reference's numpy precision accumulation,
    resnet_cifar_eval.py:111-122, done on-device instead).

    ``prep_fn(images) -> images`` runs device-side input prep (the
    deterministic VGG standardize when the imagenet iterator ships raw
    uint8 crops — data/__init__.device_augment_enabled decides, both
    sides consult it)."""

    def eval_step(state: TrainState, batch):
        variables = {"params": state.params, "batch_stats": state.batch_stats}
        images = batch["images"]
        if prep_fn is not None:
            images = prep_fn(images)
        logits = state.apply_fn(variables, images, train=False)
        labels = batch["labels"]
        # optional "mask" marks padding in the final partial batch
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones((labels.shape[0],), jnp.float32)
        hit = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
        onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
        per_ex_ce = optax.softmax_cross_entropy(
            logits.astype(jnp.float32), onehot)
        return {"correct": jnp.sum(hit * mask).astype(jnp.int32),
                "count": jnp.sum(mask).astype(jnp.int32),
                "loss_sum": jnp.sum(per_ex_ce * mask)}

    return eval_step


def make_predict_step(prep_fn: Optional[Callable] = None,
                      precision=None, apply_fn: Optional[Callable] = None):
    """predict_step(state, batch) -> float32 logits — the SERVING forward
    (serve/): eval's forward pass without the metric reduction, so the
    dynamic batcher can slice per-request rows out of one bucket dispatch.
    Padding rows (serve buckets) simply produce logits nobody reads; with
    ``train=False`` BN uses running stats, so each row's logits are
    independent of its batchmates — bucket-batched serving is numerically
    the unbatched eval forward.

    ``prep_fn`` is the SAME device-side input prep the eval step uses
    (make_eval_step) — the serve path must agree with eval about who
    standardizes or requests would be double-/un-normalized.

    ``precision`` applies the policy input cast AFTER prep (prep
    standardizes in f32, the model computes in the policy dtype); logits
    always leave f32. ``apply_fn`` overrides ``state.apply_fn`` — the
    serving reduced-precision VARIANT's apply
    (Trainer.make_variant_predict_step builds a same-architecture model
    with a different compute dtype), so one TrainState layout serves
    every variant."""

    def predict_step(state: TrainState, batch):
        variables = {"params": state.params, "batch_stats": state.batch_stats}
        images = batch["images"]
        if prep_fn is not None:
            images = prep_fn(images)
        if precision is not None:
            images = precision.cast_compute(images)
        fn = apply_fn if apply_fn is not None else state.apply_fn
        logits = fn(variables, images, train=False)
        return logits.astype(jnp.float32)

    return predict_step


class Trainer:
    """End-to-end orchestration: mesh + model + optimizer + jitted steps.

    The constructor is the successor of the reference main() bodies
    (reference resnet_cifar_main.py:339-399): build input, build model, build
    train op, pick devices — minus the ps/worker split, which no longer exists.
    """

    def __init__(self, cfg, mesh: Optional[Mesh] = None):
        # the trainer's own construction is a third of set-up that no
        # compile counter covers (PERF.md): one span, train.build
        with span("train.build"):
            self._build(cfg, mesh)

    def _build(self, cfg, mesh: Optional[Mesh]) -> None:
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else create_mesh(cfg.mesh)
        from ..models import create_model
        # mixed-precision policy (parallel/precision.py; docs/precision.md):
        # resolved FIRST because it overrides the model's compute dtype —
        # train.precision=off keeps the legacy model.compute_dtype
        # contract BIT-identical (no policy code on that path)
        from ..parallel.precision import precision_stats, resolve_precision
        self._precision = resolve_precision(cfg)
        # what every train-step program is compiled under: on one TPU
        # host with a mesh of data shards alone, the options that let the
        # compiler hide the gradient all-reduces (None everywhere else)
        from ..parallel.overlap import exchange_compiler_options
        self._step_compiler_options = exchange_compiler_options(self.mesh)
        # ZeRO-1 sharded weight update (arXiv:2004.13336; parallel/
        # sharding.py rule table): optimizer state shards over `data`,
        # gradients reduce-scatter into the shard layout, the update runs
        # on 1/N state per replica, param updates all-gather back.
        # optimizer.zero1=on raises here when the (mesh) is outside the
        # envelope; the replicated (off) path stays bit-identical to the
        # pre-ZeRO step — the exactness oracle the tests pin against.
        from ..parallel.sharding import resolve_zero1
        self._zero1 = resolve_zero1(cfg, self.mesh)
        # cross_replica_bn=True (default): global BN moments — one group.
        # False: reference-faithful per-replica BN — one moment group per
        # batch shard (see ops/batch_norm.py).
        bn_groups = 1 if cfg.model.cross_replica_bn else batch_shard_count(self.mesh)
        # reject dead-axis configs loudly (a >1 axis that shards nothing
        # would silently waste chips): seq/tensor/pipeline/expert only have
        # consumers in the transformer family
        if cfg.model.name != "vit":
            for axis in ("seq", "tensor", "pipeline", "expert"):
                if self.mesh.shape.get(axis, 1) > 1:
                    raise ValueError(
                        f"mesh axis {axis!r} > 1 requires model.name='vit' "
                        f"(got {cfg.model.name!r}); ResNets parallelize over "
                        "data/fsdp")
        else:
            n_exp_axis = self.mesh.shape.get("expert", 1)
            if n_exp_axis > 1:
                if cfg.model.vit_num_experts <= 0:
                    raise ValueError(
                        "mesh axis 'expert' > 1 requires a MoE model: set "
                        "model.vit_num_experts")
                if cfg.model.vit_num_experts % n_exp_axis:
                    raise ValueError(
                        f"vit_num_experts={cfg.model.vit_num_experts} not "
                        f"divisible by the expert axis ({n_exp_axis})")
                # indivisible tensor splits (expert FFNs etc.) warn at the
                # drop-back site itself: parallel/sharding.py
                # _warn_tensor_dropback covers every leaf, not just MoE
            # MoE×tensor composes since round 5: expert FFNs are
            # Megatron-split over `tensor` (parallel/sharding.py SwitchMlp
            # rule, stacked_encoder_spec moe leaves, expert_ffn psum), so
            # ep×tp and pp×ep×tp shard rather than replicate the expert
            # FLOPs. Indivisible hidden dims degrade to replicated weights
            # (the sharding rules check divisibility leaf-by-leaf).
            # pp composes with dp/fsdp (microbatch over local batch), tp
            # (Megatron psums inside each stage), ep (stacked-stage Switch
            # MoE, models/pipeline.py _moe_mlp) and, since round 5, seq
            # (ring attention inside the stage blocks) — no remaining
            # pairwise rejection on the pipeline axis.
        # model-resolution choices saved for the serving variant builder
        # (make_variant_predict_step): a variant must differ ONLY in
        # compute dtype, never in BN wiring or remat
        self._bn_groups = bn_groups
        self.model = create_model(cfg.model, cfg.data.dataset,
                                  remat=cfg.train.remat, bn_groups=bn_groups,
                                  mesh=self.mesh,
                                  compute_dtype=self._precision.compute_dtype
                                  if self._precision is not None else None)
        precision_stats.record_policy(self._precision)
        self.schedule = create_schedule(cfg.optimizer)
        decay_in_loss = not decoupled_decay(cfg.optimizer.name)
        if cfg.optimizer.decay_all_params and not decay_in_loss:
            # LARS/AdamW take decay inside the optimizer (non-BN mask); the
            # reference-faithful all-params L2 only exists on the loss path
            raise ValueError(
                "optimizer.decay_all_params is incompatible with "
                f"optimizer.name={cfg.optimizer.name!r} (decoupled decay "
                "is applied inside the optimizer)")
        self.tx = create_optimizer(cfg.optimizer, self.schedule)
        ct = cfg.data.coalesced_transfer
        if ct not in ("auto", "on", "off"):
            raise ValueError(f"unknown coalesced_transfer setting {ct!r}")
        if ct == "auto":
            # like data.device_augment: auto = on iff a real accelerator is
            # attached. Coalescing exists to amortize per-call transfer
            # overhead on a device link; on the CPU backend (tests, tiny
            # local runs) the extra pack/unpack per batch only costs
            ct = "off" if jax.default_backend() == "cpu" else "on"
        self._coalesced = ct == "on"
        from ..data import device_augment_enabled
        aug_fn = None
        # (leaf, kind, pad) when the imagenet train augmentation FUSES into
        # the CoalescedStager's unpack program (parallel/sharding.py): one
        # XLA program unpacks the staged uint8 bytes AND flips/jitters/
        # standardizes them, keyed per staged batch. Requires the stager,
        # and is OFF under data.echo_transfer > 1: transfer reuse re-runs
        # the STEP on one staged batch, so the augment must draw inside
        # the step (step-keyed RNG) to stay fresh per reuse.
        self._train_augment_spec = None
        # Only the iterator/step contract decides who augments. A streamed
        # iterator with device_augment off yields host-augmented float32, so
        # forcing the device path here would double-augment; when a device
        # dataset (raw uint8 in HBM) is actually attached,
        # attach_device_dataset forces the augment step on itself.
        if device_augment_enabled(cfg, "train"):
            from ..ops.augment import device_augment_fn
            if cfg.data.dataset == "imagenet":
                spec = ("images", "imagenet_train", cfg.data.augment_pad)
                if self._coalesced and cfg.data.echo_transfer <= 1:
                    self._train_augment_spec = spec
                else:
                    aug_fn = device_augment_fn(spec[1], spec[2])
            else:
                from ..ops.augment import cifar_train_augment
                aug_fn = cifar_train_augment
        if cfg.data.echo_transfer > 1 and aug_fn is None \
                and self._train_augment_spec is None:
            # without device-side augmentation a reused dispatch repeats
            # the SAME pixels: k>1 still reshuffles batch composition on
            # device, but k=1 reuses are bit-identical replays — probably
            # not what the operator meant by echoing
            import logging
            logging.getLogger(__name__).warning(
                "data.echo_transfer=%d with no device-side augmentation "
                "(device_augment resolved off): reused dispatches repeat "
                "identical samples (steps_per_loop=1: identical batches). "
                "Enable data.device_augment, or prefer data.echo_factor "
                "(host echo reshuffles every batch)",
                cfg.data.echo_transfer)
        self._aug_fn = aug_fn
        self._cfg_aug_fn = aug_fn  # the config-resolved choice, for detach
        self._train_step = self._build_train_step(aug_fn)
        eval_prep = None
        if cfg.data.dataset == "imagenet" and \
                device_augment_enabled(cfg, "eval"):
            from ..ops.augment import vgg_standardize
            eval_prep = vgg_standardize
        self._eval_prep = eval_prep
        self._eval_step = make_eval_step(eval_prep)
        # serving forward (serve/; elaborated per bucket by
        # analysis/elaborate.py): same prep contract as the eval step
        self._predict_step = make_predict_step(eval_prep,
                                               precision=self._precision)
        self._jitted_train = None
        self._jitted_multi = None
        self._jitted_eval = None
        self._jitted_predict = None
        self._dev_prefetch = None
        self._multi_prefetch = None
        self._dev_data = None
        self._jitted_idx = None
        self._jitted_idx_multi = None
        self.state: Optional[TrainState] = None
        # optional resilience/heartbeat.HeartbeatPublisher (set by
        # main.run_train when the watchdog is enabled): evaluate() ticks it
        # per eval batch so hang detection stays live outside the train
        # loop — eval makes no optimizer-step progress, and without ticks a
        # long eval round would read as a wedged process
        self.heartbeat = None
        if self._coalesced:
            # coalesced staging (parallel/sharding.CoalescedStager): one
            # contiguous ring-buffered host region per device, a single
            # device_put issue per batch, per-shard placement via
            # make_array_from_single_device_arrays — covers single- AND
            # multi-process (each process contributes its local regions)
            from ..parallel.sharding import CoalescedStager
            ring = max(cfg.data.staging_ring, cfg.data.transfer_depth + 2)
            self._put_batch = CoalescedStager(self.mesh, stacked=False,
                                              ring=ring)
            self._put_multi_batch = CoalescedStager(self.mesh, stacked=True,
                                                    ring=ring)
            if self._train_augment_spec is not None:
                # TRAIN-only stagers whose unpack program fuses the
                # device augmentation; eval/serve keep the neutral
                # stagers above (an augmenting put must never touch
                # their batches)
                self._put_train_batch = CoalescedStager(
                    self.mesh, stacked=False, ring=ring,
                    augment=self._train_augment_spec,
                    augment_seed=cfg.train.seed)
                self._put_train_multi_batch = CoalescedStager(
                    self.mesh, stacked=True, ring=ring,
                    augment=self._train_augment_spec,
                    augment_seed=cfg.train.seed)
            else:
                self._put_train_batch = self._put_batch
                self._put_train_multi_batch = self._put_multi_batch
        else:
            if jax.process_count() > 1:
                # per-leaf fallback. single-process: device_put the full
                # batch sharded; multi-process: every process contributes
                # its local shard of the global array
                from ..parallel.sharding import make_global_stacked_batch
                self._put_batch = lambda b: make_global_batch(b, self.mesh)
                self._put_multi_batch = \
                    lambda b: make_global_stacked_batch(b, self.mesh)
            else:
                from ..parallel.sharding import shard_stacked_batch
                self._put_batch = lambda b: shard_batch(b, self.mesh)
                self._put_multi_batch = \
                    lambda b: shard_stacked_batch(b, self.mesh)
            self._put_train_batch = self._put_batch
            self._put_train_multi_batch = self._put_multi_batch
        import logging
        logging.getLogger(__name__).info(
            "resolved on %s: %s", jax.default_backend(),
            " ".join(f"{k}={v}" for k, v in self.resolutions().items()))

    def resolutions(self) -> Dict[str, str]:
        """What every ``auto`` switch of this run resolved to — logged at
        construction so any run shows which paths it took."""
        from ..data import device_augment_enabled, device_dataset_enabled
        cfg = self.cfg

        def onoff(flag) -> str:
            return "on" if flag else "off"

        attention = widths = "n/a"
        from ..models.transformer import FAMILIES, causal_flash_or_dense
        if cfg.model.name in FAMILIES:
            attention = causal_flash_or_dense(self.model.attention_impl)
            from ..models.moe import product_widths
            d, m = cfg.model.hidden_size, cfg.model.moe_intermediate_size
            padded = product_widths(d, m)
            widths = "as published" if padded == (d, m) else \
                "{}x{} from {}x{}".format(*padded, d, m)
        if cfg.model.name == "vit":
            attention = self.model.attention_impl
            if attention == "auto":  # no seq axis (create_model resolves it)
                from ..models.transformer import flash_or_dense
                attention = flash_or_dense(
                    (cfg.data.image_size // cfg.model.vit_patch_size) ** 2)
            if attention == "ring":
                from ..ops.attention import resolve_ring_kernel
                attention = f"ring/{resolve_ring_kernel('auto')}"
        return {
            "fused_xent": resolve_fused_xent(cfg.train.fused_xent,
                                             cfg.optimizer.label_smoothing),
            "coalesced_transfer": onoff(self._coalesced),
            "device_augment": onoff(device_augment_enabled(cfg, "train")),
            "device_dataset": onoff(device_dataset_enabled(cfg, "train")),
            "attention": attention,
            "moe.product_widths": widths,
            "zero1": onoff(self.zero1_active),
            "step.compiler_options": ",".join(
                f"{k}={v}" for k, v in
                (self._step_compiler_options or {}).items()) or "none",
        }

    def _zero1_min_size(self) -> int:
        from ..parallel.sharding import ZERO1_MIN_SIZE
        return self.cfg.optimizer.zero1_min_size or ZERO1_MIN_SIZE

    def _state_shardings(self, shapes):
        """state_shardings with this Trainer's resolved ZeRO-1 choice —
        the ONE resolution point every jitted entry uses, so the live
        state, the jit in/out shardings and the grad constraint cannot
        disagree about the optimizer layout."""
        return state_shardings(shapes, self.mesh, zero1=self._zero1,
                               zero1_min_size=self._zero1_min_size())

    def _make_zero1_apply(self):
        """The ZeRO-1 weight update, ``(state, grads) -> state``:
        gradients pinned to the rule-table shard layout (the
        ``with_sharding_constraint`` turns the all-reduce XLA would emit
        into reduce-scatter — the arXiv:2004.13336 transformation), the
        optimizer transform then runs on each replica's 1/N shard
        (cross-shard reductions like the LARS/LAMB trust-ratio norms get
        their collectives from sharding propagation), and the param
        updates return to the base layout through the jit output
        sharding's gather."""
        mesh = self.mesh
        min_size = self._zero1_min_size()

        @jax.named_scope("optimizer")
        def apply_gradients_fn(state, grads):
            from jax.lax import with_sharding_constraint
            from ..parallel.sharding import zero1_grad_specs
            specs = zero1_grad_specs(state.params, mesh,
                                     min_size=min_size)
            shard_tree = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), specs,
                is_leaf=lambda x: isinstance(x, P))
            grads = with_sharding_constraint(grads, shard_tree)
            updates, new_opt = state.tx.update(grads, state.opt_state,
                                               state.params)
            updates = with_sharding_constraint(updates, shard_tree)
            import optax as _optax
            new_params = _optax.apply_updates(state.params, updates)
            return state.replace(step=state.step + 1, params=new_params,
                                 opt_state=new_opt)

        return apply_gradients_fn

    def _build_train_step(self, aug_fn):
        cfg = self.cfg
        # a model family that is no image classifier brings its own loss
        # over its own batch (ClassifierObjective documents the contract)
        make = getattr(self.model, "objective", None)
        objective = make() if make is not None else None
        self._batch_keys = objective.batch_keys if objective is not None \
            else ClassifierObjective.batch_keys
        return make_train_step(
            self.schedule, cfg.optimizer.weight_decay,
            cfg.optimizer.label_smoothing,
            decay_in_loss=not decoupled_decay(cfg.optimizer.name),
            grad_accum_steps=cfg.train.grad_accum_steps,
            decay_all_params=cfg.optimizer.decay_all_params,
            ce_fn=make_ce_fn(cfg.optimizer.label_smoothing,
                             cfg.train.fused_xent, self.mesh),
            augment_fn=aug_fn, augment_seed=cfg.train.seed,
            aux_loss_weight=cfg.model.moe_aux_weight,
            apply_gradients_fn=self._make_zero1_apply()
            if self._zero1 else None,
            precision=self._precision, objective=objective)

    @property
    def zero1_active(self) -> bool:
        """True when the optimizer state and weight update are sharded
        over the ``data`` axis (parallel/sharding.py ZeRO-1 rule table)."""
        return self._zero1

    @property
    def precision_active(self) -> bool:
        """True when a mixed-precision policy (train.precision) shapes
        the step: bf16 compute over f32 masters
        (parallel/precision.py)."""
        return self._precision is not None

    def make_variant_predict_step(self, variant: str):
        """The serving VARIANT forward (serve/compile_cache.py buckets
        are (batch, variant)): a predict step whose model computes in
        the variant's compute dtype
        (``parallel.precision.SERVE_VARIANT_DTYPES``), sharing every
        other model-resolution choice with this Trainer (BN axis/groups,
        remat, prep contract) so the variant differs only in precision.
        The caller supplies the matching (cast) TrainState — the step
        uses its own apply, not ``state.apply_fn``.

        Weight-only variants ("int8"): the cast state carries quantized
        ``{"int8_q", "int8_scale"}`` kernels, so the apply first
        dequantizes them (``parallel.precision.dequantize_params`` —
        fused into the consuming ops by XLA) and the model computes f32
        over int8-at-rest weights."""
        from ..models import create_model
        from ..parallel.precision import (SERVE_VARIANT_DTYPES,
                                          WEIGHT_ONLY_VARIANTS,
                                          dequantize_params)
        model = create_model(self.cfg.model, self.cfg.data.dataset,
                             remat=self.cfg.train.remat,
                             bn_groups=self._bn_groups, mesh=self.mesh,
                             compute_dtype=SERVE_VARIANT_DTYPES[variant])
        apply_fn = model.apply
        if variant in WEIGHT_ONLY_VARIANTS:
            def apply_fn(variables, *args, _apply=model.apply, **kw):
                variables = dict(variables)
                variables["params"] = dequantize_params(
                    variables["params"])
                return _apply(variables, *args, **kw)
        return make_predict_step(self._eval_prep, apply_fn=apply_fn)

    # -- state ------------------------------------------------------------
    def init_state(self, seed: Optional[int] = None) -> TrainState:
        with span("train.init_state"):
            rng = jax.random.PRNGKey(
                self.cfg.train.seed if seed is None else seed)
            c = self.cfg
            # one example per batch shard: shard_map-based ops (ring
            # attention) need the init dummy batch divisible by the batch
            # mesh axes
            nb = batch_shard_count(self.mesh)
            self.state = create_train_state(
                rng, self.model, self.tx, init_input(self.model, c, nb),
                mesh=self.mesh,
                zero1=self._zero1, zero1_min_size=self._zero1_min_size())
            if self._precision is not None:
                # the policy's checkpoint contract: f32 MASTERS only — a
                # cast param leaf here would bake the compute dtype into
                # every checkpoint this run writes (parallel/precision.py)
                from ..parallel.precision import (check_master_dtypes,
                                                  precision_stats)
                check_master_dtypes(self.state.params,
                                    self._precision.master_dtype)
                precision_stats.record_params(self.state.params)
        return self.state

    # -- jitted steps ------------------------------------------------------
    def jitted_train_step(self):
        if self._jitted_train is None:
            shapes = jax.eval_shape(lambda s: s, self.state)
            st_sh = self._state_shardings(shapes)
            b_sh = data_sharding(self.mesh)
            self._jitted_train = jax.jit(
                self._train_step,
                in_shardings=(st_sh, {k: b_sh for k in self._batch_keys}),
                out_shardings=(st_sh, None),
                donate_argnums=(0,),
                compiler_options=self._step_compiler_options)
        return self._jitted_train

    @property
    def train_put_augments(self) -> bool:
        """True when the train put path's unpack program carries the fused
        device augmentation (so train batches come out float32 and the
        step itself has no augment op) — tests size their probe batches
        by this."""
        return self._train_augment_spec is not None

    def jitted_multi_step(self, k: int = 0):
        """Fused optimizer steps per dispatch: lax.scan over stacked batches
        (the step count comes from the input's leading axis; ``k`` is
        documentation only). Returns (state, metrics-of-last-step).

        With ``data.echo_transfer`` > 1 the program starts by reshuffling
        the group's batch composition with a step-keyed on-device
        permutation over the flattened K×B samples: each REUSE of one
        staged group (train() dispatches it echo_transfer times) trains on
        differently-composed batches — the transfer-level echo's analog of
        the host echo cache's per-echo reshuffle, at zero extra
        host→device traffic."""
        del k
        if self._jitted_multi is None:
            step = self._train_step
            unroll = max(1, self.cfg.train.scan_unroll)
            reshuffle = self.cfg.data.echo_transfer > 1
            perm_seed = self.cfg.train.seed + 0x5EED

            def multi(state, batches):
                if reshuffle:
                    lead = batches[self._batch_keys[-1]].shape
                    kb = lead[0] * lead[1]
                    perm = jax.random.permutation(
                        jax.random.fold_in(jax.random.PRNGKey(perm_seed),
                                           state.step), kb)

                    def resh(x):
                        flat = x.reshape((kb,) + x.shape[2:])
                        return jnp.take(flat, perm,
                                        axis=0).reshape(x.shape)

                    batches = jax.tree_util.tree_map(resh, batches)

                def body(s, batch):
                    s, m = step(s, batch)
                    return s, m
                state, ms = jax.lax.scan(body, state, batches, unroll=unroll)
                last = jax.tree_util.tree_map(lambda x: x[-1], ms)
                return state, last

            shapes = jax.eval_shape(lambda s: s, self.state)
            st_sh = self._state_shardings(shapes)
            b_sh = NamedSharding(
                self.mesh, P(None, *data_sharding(self.mesh).spec))
            self._jitted_multi = jax.jit(
                multi,
                in_shardings=(st_sh, {k: b_sh for k in self._batch_keys}),
                out_shardings=(st_sh, None),
                donate_argnums=(0,),
                compiler_options=self._step_compiler_options)
        return self._jitted_multi

    def jitted_eval_step(self):
        if self._jitted_eval is None:
            self._jitted_eval = jax.jit(self._eval_step)
        return self._jitted_eval

    def jitted_predict_step(self):
        """JIT entry for the serving forward — tests and ad-hoc callers;
        the serving hot path AOT-compiles the same ``_predict_step`` per
        batch bucket instead (serve/compile_cache.py) so the first request
        never pays a compile."""
        if self._jitted_predict is None:
            self._jitted_predict = jax.jit(self._predict_step)
        return self._jitted_predict

    # -- device-resident dataset (data/device_dataset.py) ------------------
    def attach_device_dataset(self, images, labels) -> None:
        """Upload the full dataset to HBM (replicated); train() then expects
        an index iterator ({"idx": (bs,) int32}) and gathers batches on
        device. Single-process only.

        The dataset is raw uint8, so the step MUST augment+standardize on
        device — if the Trainer was built without an augment_fn (e.g. config
        resolved device_augment off on a CPU backend), rebuild the step with
        one rather than silently training on unnormalized pixels."""
        if jax.process_count() > 1:
            raise ValueError("device dataset requires a single process")
        if self._aug_fn is None:
            # the idx path bypasses the put stagers, so a FUSED train
            # augmentation (carried by the stager's unpack, step aug_fn
            # None) must move back into the step — and it must be the
            # config's own augmentation, not the cifar default, or an
            # imagenet Trainer would train on cifar-normalized pixels
            from ..ops.augment import device_augment_fn
            if self._train_augment_spec is not None:
                _, kind, pad = self._train_augment_spec
                self._aug_fn = device_augment_fn(kind, pad)
            else:
                self._aug_fn = device_augment_fn("cifar_train")
            self._train_step = self._build_train_step(self._aug_fn)
            self._jitted_train = None
            self._jitted_multi = None
        from ..parallel.mesh import replicated
        from ..parallel.sharding import put_to_sharding
        rep = replicated(self.mesh)
        import numpy as np
        self._dev_data = (put_to_sharding(np.asarray(images), rep),
                          put_to_sharding(np.asarray(labels), rep))
        self._jitted_idx = None
        self._jitted_idx_multi = None

    def detach_device_dataset(self) -> None:
        """Drop the HBM dataset and restore the config-resolved augment
        choice (attach may have forced device-side augmentation; a streamed
        iterator on a non-TPU backend standardizes on the host, and keeping
        the forced augment would double-augment)."""
        self._dev_data = None
        self._jitted_idx = None
        self._jitted_idx_multi = None
        if self._aug_fn is not self._cfg_aug_fn:
            self._aug_fn = self._cfg_aug_fn
            self._train_step = self._build_train_step(self._aug_fn)
            self._jitted_train = None
            self._jitted_multi = None

    def _gathered_step(self):
        step = self._train_step

        # the name is the compiled module's (jit_gathered_train_step): it
        # is how a trace or a compile-cache entry is told apart
        def gathered_train_step(state, batch, images, labels):
            idx = batch["idx"]
            return step(state, {"images": jnp.take(images, idx, axis=0),
                                "labels": jnp.take(labels, idx, axis=0)})
        return gathered_train_step

    def jitted_index_step(self):
        if self._dev_data is None:
            # a RuntimeError (not assert): the guard must survive python -O
            raise RuntimeError(
                "jitted_index_step requires an attached device dataset "
                "(attach_device_dataset)")
        if self._jitted_idx is None:
            from ..parallel.mesh import replicated
            shapes = jax.eval_shape(lambda s: s, self.state)
            st_sh = self._state_shardings(shapes)
            b_sh = data_sharding(self.mesh)
            rep = replicated(self.mesh)
            jit_fn = jax.jit(
                self._gathered_step(),
                in_shardings=(st_sh, {"idx": b_sh}, rep, rep),
                out_shardings=(st_sh, None),
                donate_argnums=(0,),
                compiler_options=self._step_compiler_options)
            self._jitted_idx_raw = jit_fn
            self._jitted_idx = \
                lambda s, b: jit_fn(s, b, *self._dev_data)
        return self._jitted_idx

    def device_batch(self, batch):
        """One host batch — as the training iterator yields it
        ({"images",..} or {"idx"}) — staged on the devices the way
        ``train()`` stages it for the single-step dispatch."""
        if self._dev_data is not None and "idx" in batch:
            return self._put_idx(batch)
        # the TRAIN put path: with the fused-augment stager the step's
        # traced program expects the unpack's augmented float32 images,
        # and the counted FLOPs then include the on-device augmentation
        return finalize_staged(self._put_train_batch(batch))

    def lowered_step(self, batch):
        """The single train step as ``train()`` dispatches it, lowered for
        one host ``batch`` through the same jit entry training uses — read
        by step_flops and by chip_smoke.py (which looks for the Pallas
        custom calls in the program)."""
        dev = self.device_batch(batch)
        if self._dev_data is not None and "idx" in batch:
            self.jitted_index_step()
            return self._jitted_idx_raw.lower(self.state, dev,
                                              *self._dev_data)
        return self.jitted_train_step().lower(self.state, dev)

    def step_flops(self, batch) -> Optional[float]:
        """XLA cost-analysis FLOPs of one compiled optimizer step. This
        compiles the lowered step: a persistent-cache hit for a plain XLA
        program, but a SECOND full compile when the step holds a Pallas
        kernel — Mosaic serializes the kernel with its trace-time
        call-stack locations, so the cache key differs from the dispatch
        path's (PERF.md, PR 21)."""
        from ..utils import profiling
        return profiling.lowered_flops(self.lowered_step(batch))

    def jitted_index_multi_step(self, k: int = 0):
        del k
        if self._dev_data is None:
            raise RuntimeError(
                "jitted_index_multi_step requires an attached device "
                "dataset (attach_device_dataset)")
        if self._jitted_idx_multi is None:
            from ..parallel.mesh import replicated
            gathered = self._gathered_step()
            unroll = max(1, self.cfg.train.scan_unroll)

            def multi(state, batches, images, labels):
                def body(s, batch):
                    return gathered(s, batch, images, labels)
                state, ms = jax.lax.scan(body, state, batches, unroll=unroll)
                last = jax.tree_util.tree_map(lambda x: x[-1], ms)
                return state, last

            shapes = jax.eval_shape(lambda s: s, self.state)
            st_sh = self._state_shardings(shapes)
            b_sh = NamedSharding(
                self.mesh, P(None, *data_sharding(self.mesh).spec))
            rep = replicated(self.mesh)
            jit_fn = jax.jit(
                multi,
                in_shardings=(st_sh, {"idx": b_sh}, rep, rep),
                out_shardings=(st_sh, None),
                donate_argnums=(0,),
                compiler_options=self._step_compiler_options)
            self._jitted_idx_multi = \
                lambda s, b: jit_fn(s, b, *self._dev_data)
        return self._jitted_idx_multi

    def _put_idx(self, batch):
        from ..parallel.sharding import put_to_sharding
        return put_to_sharding(batch, {"idx": data_sharding(self.mesh)})

    def _put_idx_multi(self, batch):
        from ..parallel.sharding import put_to_sharding
        sh = NamedSharding(self.mesh, P(None, *data_sharding(self.mesh).spec))
        return put_to_sharding(batch, {"idx": sh})

    # -- resilience --------------------------------------------------------
    def scale_lr(self, scale: float) -> None:
        """Rebuild the LR schedule multiplied by ``scale`` and invalidate
        the jitted steps — the NaN sentinel's back-off knob
        (resilience/sentinel.py). Costs one recompile on the recovery path;
        the hot path is untouched at scale 1. The live TrainState's
        optimizer is swapped too (tx is a static field, so replace() keeps
        the restored pytree leaves)."""
        base = create_schedule(self.cfg.optimizer)
        self.schedule = base if scale == 1.0 else \
            (lambda step: base(step) * scale)
        self.tx = create_optimizer(self.cfg.optimizer, self.schedule)
        self._train_step = self._build_train_step(self._aug_fn)
        self._jitted_train = None
        self._jitted_multi = None
        self._jitted_idx = None
        self._jitted_idx_multi = None
        if self.state is not None:
            self.state = self.state.replace(tx=self.tx)

    # -- loops -------------------------------------------------------------
    def train(self, data_iter: Iterator, num_steps: Optional[int] = None,
              hooks: Tuple = (), start_step: int = 0,
              stop_fn: Optional[Callable[[], bool]] = None):
        """The hot loop (reference resnet_cifar_main.py:336-337).

        With ``train.steps_per_loop > 1``, K steps run inside one XLA
        dispatch (lax.scan); hooks fire at loop boundaries with the last
        step's metrics.

        ``stop_fn`` is polled at step/loop boundaries (after hooks): when it
        returns True the loop returns immediately with the state as of the
        last finished step — the preemption listener's entry point
        (resilience/preemption.py). The poll is one Event check; it does not
        force a device sync.

        Hooks that read device metrics at a cadence read them one call
        late (train/hooks.py), so every normal return — end of
        ``num_steps``, ``stop_fn``, an exhausted stream — first flushes
        what they still hold: the last cadence line is printed and a
        non-finite loss of the last cadence dispatch raises out of here.
        Not while an exception propagates: that one is the news.
        """
        out = self._dispatch_loop(data_iter, num_steps, hooks, start_step,
                                  stop_fn)
        for h in hooks:
            flush = getattr(h, "flush", None)
            if flush is not None:
                flush()
        return out

    def _dispatch_loop(self, data_iter, num_steps, hooks, start_step,
                       stop_fn):
        """``train`` up to its return; every ``return`` in here is one of
        its normal ends."""
        if self.state is None:
            self.init_state()
        for h in hooks:
            reset = getattr(h, "reset_window", None)
            if reset is not None:  # throughput windows must not span the
                reset()            # pause between train segments
        if self.cfg.model.norm == "group" \
                and not getattr(self, "_gn_lr_warned", False):
            # measured (docs/perf_norm_r5.md): GroupNorm starting at bare
            # lr>=0.1 sits on a long optimization plateau with
            # seed-dependent escape; a short warmup removes it. Probe the
            # RESOLVED schedule at step 0 (raw config fields lie: piecewise
            # ignores learning_rate, constant ignores warmup_steps). Warn
            # once, at training time only (the evaluator builds a Trainer
            # too), and don't refuse — small models are fine without it.
            self._gn_lr_warned = True
            if float(self.schedule(0)) > 0.05:
                import logging
                logging.getLogger(__name__).warning(
                    "model.norm='group' and the schedule starts at "
                    "lr=%.3g (no effective warmup): GroupNorm measured a "
                    "seed-dependent optimization plateau at bare high lr "
                    "(docs/perf_norm_r5.md) — consider "
                    "optimizer.schedule='warmup_piecewise' with ~500 "
                    "warmup steps", float(self.schedule(0)))
        num_steps = num_steps or self.cfg.train.train_steps
        k = max(1, self.cfg.train.steps_per_loop)
        metrics = None
        # device-resident dataset: data_iter carries {"idx"} batches; the
        # step gathers images/labels from HBM (attach_device_dataset)
        use_idx = self._dev_data is not None
        put_one = self._put_idx if use_idx else self._put_train_batch
        put_multi = self._put_idx_multi if use_idx \
            else self._put_train_multi_batch
        depth = max(1, self.cfg.data.transfer_depth)
        # transfer-level data echoing (data.echo_transfer > 1): each staged
        # batch (group) is dispatched `reuse` times before the next draw —
        # one H2D transfer feeds reuse × k steps. The fused path reshuffles
        # batch composition per dispatch on device (jitted_multi_step) and
        # the step-keyed device augmentation re-draws per step, so reuses
        # are not replays. The index path never reuses (the device dataset
        # ships only indices — there is no transfer to amortize).
        reuse = 1 if use_idx else max(1, self.cfg.data.echo_transfer)
        if k == 1:
            from ..data.device_prefetch import device_prefetch
            step_fn = self.jitted_index_step() if use_idx \
                else self.jitted_train_step()
            # a dedicated transfer thread keeps `depth` device-resident
            # batches queued behind compute; the wrapped iterator is cached
            # per data_iter so segmented training (repeated train() calls
            # over one shared iterator, e.g. train_and_eval) doesn't drop
            # the prefetched batches between segments
            if self._dev_prefetch is None or self._dev_prefetch[0] is not data_iter:
                if self._dev_prefetch is not None:
                    self._dev_prefetch[1].close()  # stop old worker threads
                self._dev_prefetch = (
                    data_iter,
                    device_prefetch(iter(data_iter), put_one, depth=depth))
            dev_iter = self._dev_prefetch[1]
            # heartbeat phase flip around the blocking draw: a hang during
            # the fetch is OUR input pipeline, not a peer's collective —
            # the watchdog attributes by phase (resilience/heartbeat.py
            # data_fetch)
            fetch_cm = self.heartbeat.data_fetch \
                if self.heartbeat is not None else contextlib.nullcontext
            batch = None
            batch_uses = 0
            # metrics of the steps sent and not yet waited for. The
            # runtime stops the host only at 32 programs in flight (16
            # steps of an unpack and a step program): with steps of a
            # second that is 16 s of work queued behind a stop_fn (a
            # preemption waits that long for its state), and a profile of
            # 12 dispatches holds one execution of the step. So the lead
            # is STEP_LEAD_SECONDS of device work: the loop waits for the
            # step `lead` back, and `lead` is that many seconds over the
            # time between two waits that follow each other, a step's
            # time while the device is what the loop waits for. Where
            # that is more than the runtime allows (89 ms a step: 22
            # steps, the ResNet cell) the step waited for has long run,
            # the wait returns at once and the runtime's bound holds.
            sent = collections.deque()
            lead, waited_at = 2, None
            for step in range(start_step, num_steps):
                if batch_uses <= 0:
                    try:
                        # flight-recorder + goodput: time blocked on input
                        # (telemetry/; the span is the one timer of the
                        # site — ring, goodput, profiler annotation and the
                        # dispatch_wait stage all read it — and a shared
                        # no-op when telemetry is off)
                        with span("input.wait",
                                  category="input_wait") as wait, \
                                fetch_cm():
                            batch = next(dev_iter)
                        wait.charge("dispatch_wait", items=1)
                    except StopIteration:
                        # finite stream exhausted: end training cleanly,
                        # same contract as the fused k>1 path
                        return self.state, metrics
                    batch_uses = reuse
                batch_uses -= 1
                with span("train.step", step_num=step):
                    self.state, metrics = step_fn(self.state, batch)
                sent.append(metrics)
                if len(sent) > lead:
                    with span("train.lead_wait"):
                        jax.block_until_ready(sent.popleft())
                    now = time.perf_counter()
                    if waited_at is not None and now > waited_at:
                        lead = max(2, int(STEP_LEAD_SECONDS
                                          / (now - waited_at)))
                    waited_at = now
                else:
                    waited_at = None
                with span("train.hooks"):
                    for h in hooks:
                        h(step + 1, self.state, metrics)
                if stop_fn is not None and stop_fn():
                    return self.state, metrics
            return self.state, metrics

        multi_fn = self.jitted_index_multi_step(k) if use_idx \
            else self.jitted_multi_step(k)
        step = start_step
        # K-batch draw + stack runs on its own thread; the dedicated
        # transfer thread stages stacked groups behind the scan dispatch, so
        # the dispatch thread never waits on host-side input prep. Cached per
        # data_iter (like the K=1 path) so segmented training keeps its
        # queue; entry[2] carries a [stacked_group, offset] remainder left by
        # a previous segment's tail so no drawn batch is ever discarded.
        if self._multi_prefetch is None or self._multi_prefetch[0] is not data_iter:
            from ..data.device_prefetch import device_prefetch, threaded_stacker
            if self._multi_prefetch is not None:
                self._multi_prefetch[1].close()  # stop old worker threads
            self._multi_prefetch = [
                data_iter,
                device_prefetch(threaded_stacker(iter(data_iter), k),
                                put_multi, depth=depth),
                None]
        entry = self._multi_prefetch
        stacked_iter = entry[1]
        # metrics of the fused dispatches sent and not yet waited for. The
        # runtime allocates a dispatch's buffers when it is enqueued, and
        # its staged group (k batches) lives until it has run, so the
        # loop's lead is device memory (168 MiB a dispatch in ViT-L's
        # cells, PERF.md §6 PR 32). Nothing else bounds it: the hooks read
        # late (train/hooks.py) and the runtime stops the host only at 32
        # programs in flight. So the loop waits for the dispatch
        # FUSED_DISPATCH_LEAD back: one runs, that many are queued behind
        # it, and a host turn as long as a whole dispatch costs the device
        # nothing.
        sent = collections.deque()
        fetch_cm = self.heartbeat.data_fetch \
            if self.heartbeat is not None else contextlib.nullcontext

        def single_fn():
            return self.jitted_index_step() if use_idx \
                else self.jitted_train_step()

        def run_singles(stacked, offset, count):
            """Returns the number of steps actually run (a stop_fn stop may
            cut it short; the caller's remainder bookkeeping must not drop
            the unconsumed batches)."""
            nonlocal step, metrics
            step_fn = single_fn()
            for i in range(offset, offset + count):
                if stop_fn is not None and stop_fn():
                    return i - offset
                b = jax.tree_util.tree_map(lambda x, i=i: x[i], stacked)
                with span("train.step", step_num=step):
                    self.state, metrics = step_fn(self.state, b)
                step += 1
                with span("train.hooks"):
                    for h in hooks:
                        h(step, self.state, metrics)
            return count

        # 1) consume a previous tail's remainder, one step at a time
        if entry[2] is not None and step < num_steps:
            stacked, offset = entry[2]
            take = min(k - offset, num_steps - step)
            done = run_singles(stacked, offset, take)
            offset += done
            entry[2] = None if offset >= k else [stacked, offset]
            if done < take:  # stop_fn fired mid-remainder
                return self.state, metrics
        # 2) fused full groups. A finite stream that exhausts ends training
        # early — the reference's serial path had the same stop condition
        # (input exhaustion, SURVEY.md §3.5); train streams here repeat
        # forever, so this only triggers for deliberately truncated inputs.
        while step + k <= num_steps:
            if stop_fn is not None and stop_fn():
                return self.state, metrics
            try:
                with span("input.wait", category="input_wait") as wait, \
                        fetch_cm():
                    stacked = next(stacked_iter)
                wait.charge("dispatch_wait", items=1)
            except StopIteration:
                return self.state, metrics
            for _r in range(reuse):
                if step + k > num_steps:
                    break
                with span("train.step", step_num=step):
                    self.state, metrics = multi_fn(self.state, stacked)
                step += k
                sent.append(metrics)
                if len(sent) > FUSED_DISPATCH_LEAD:
                    with span("train.lead_wait"):
                        jax.block_until_ready(sent.popleft())
                with span("train.hooks"):
                    for h in hooks:
                        h(step, self.state, metrics)
                if _r + 1 < reuse and stop_fn is not None and stop_fn():
                    return self.state, metrics
        # 3) tail shorter than k: draw one more group, run the first
        # (num_steps - step) unfused, bank the remainder for the next
        # segment. Never touch data_iter directly — the stacker's worker
        # thread iterates it concurrently.
        if step < num_steps:
            try:
                with fetch_cm():
                    stacked = next(stacked_iter)
            except StopIteration:
                return self.state, metrics
            take = num_steps - step
            done = run_singles(stacked, 0, take)
            entry[2] = [stacked, done] if done < k else None
        return self.state, metrics

    def eval_pad_multiple(self) -> int:
        """The multiple eval batches must pad to: the batch-shard count,
        times the pipeline microbatch count when the encoder is pipelined
        (each shard's LOCAL batch must divide into microbatches — the
        PipelinedEncoder fails loudly otherwise). Found by the static
        elaborator: the default eval_batch_size=100 over a dp=2 × pp=2
        mesh left a local batch of 50 against 4 microbatches — a
        guaranteed step-1 eval crash (analysis/elaborate.py)."""
        n = batch_shard_count(self.mesh)
        pstages = self.mesh.shape.get("pipeline", 1)
        if self.cfg.model.name == "vit" and pstages > 1:
            from ..models.pipeline import resolve_microbatches
            n *= resolve_microbatches(
                self.cfg.model.vit_pipeline_microbatches, pstages)
        return n

    def evaluate(self, data_iter: Iterator, num_batches: int) -> Dict[str, float]:
        """Pipelined evaluation: padding + host→device staging run on the
        dedicated transfer thread (data/device_prefetch.device_prefetch)
        while the consumer dispatches eval steps — the serial
        pad → put → run chain was the measured 46.7 vs 499 img/s eval gap
        (BENCH_r05). The prefetcher may draw up to transfer_depth + 2
        batches beyond ``num_batches`` from ``data_iter``; eval streams are
        one-pass per round (or infinite), so nothing meaningful is lost."""
        from ..data.device_prefetch import device_prefetch
        from ..parallel.sharding import pad_batch_to_multiple
        step_fn = self.jitted_eval_step()
        n_shards = self.eval_pad_multiple()

        def padded():
            for batch in data_iter:
                yield pad_batch_to_multiple(batch, n_shards)

        dev_iter = device_prefetch(
            padded(), self._put_batch,
            depth=max(1, self.cfg.data.transfer_depth))
        # accumulate ON DEVICE (tiny async adds) and pull once at the end —
        # a per-batch int() would sync host<->device every eval step
        totals = None
        hb = self.heartbeat
        # goodput: in-loop eval rounds are their own wall-clock bucket
        # (telemetry/goodput.py); the per-batch spans nest inside this one
        # and charge nothing extra (outermost-categorized-span rule)
        try:
            with span("eval.round", category="eval"):
                for i in range(num_batches):
                    if hb is not None:
                        # batch 0 carries the eval step's XLA compile, which
                        # can legitimately exceed the hang deadline — keep it
                        # in an unmonitored phase, exactly like the train
                        # path's "init" (a mid-compile hard-exit 75 would
                        # requeue-loop the job); monitoring arms at batch 1
                        hb.tick(phase="eval_init" if i == 0 else "eval")
                    with span("eval.batch"):
                        try:
                            # no goodput category of its own: the round's
                            # `eval` is the outermost and takes the time
                            with span("input.wait") as wait:
                                batch = next(dev_iter)
                            wait.charge("dispatch_wait", items=1)
                        except StopIteration:
                            # one-pass streams (ImageNet eval) can exhaust
                            # before num_batches; single-process, return
                            # metrics over the batches actually consumed.
                            # Multi-process we must NOT break unilaterally —
                            # the other processes would block in the next
                            # collective — so fail loudly instead.
                            if jax.process_count() > 1:
                                raise RuntimeError(
                                    "eval stream exhausted mid-evaluation on "
                                    "this process; with multiple processes "
                                    "this would deadlock the collective step "
                                    "— size eval_batch_count to the smallest "
                                    "per-process shard") from None
                            break
                        out = step_fn(self.state, batch)
                        totals = out if totals is None else \
                            jax.tree_util.tree_map(jnp.add, totals, out)
        finally:
            # stop the staging thread (the caller keeps ownership of
            # data_iter itself — Evaluator reuses caller-supplied iterators)
            dev_iter.close()
        if totals is None:
            return {"precision": 0.0, "loss": 0.0, "count": 0}
        count = int(totals["count"])
        return {"precision": int(totals["correct"]) / max(count, 1),
                "loss": float(totals["loss_sum"]) / max(count, 1),
                "count": count}
