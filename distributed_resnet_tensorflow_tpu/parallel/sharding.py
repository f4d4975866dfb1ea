"""Sharding rules for params / optimizer state / batches.

Replaces the reference's ``tf.train.replica_device_setter`` variable placement
(reference resnet_cifar_main.py:392-396 — round-robin variables onto ps tasks)
with ``NamedSharding`` annotations: parameters are replicated by default (pure
DP, matching the reference capability) and optionally sharded ZeRO-style over
the ``fsdp`` axis for large models/optimizers, with XLA inserting
all-gather/reduce-scatter instead of grpc push/pull.
"""
from __future__ import annotations

import threading
import weakref
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def coerce_batch_dtypes(batch: Any) -> Any:
    """Narrow platform-default 64-bit leaves before the host→device hop.

    Labels/indices arrive int64 whenever they pass through a numpy op that
    defaults to the platform int (np.arange/np.concatenate on mixed inputs,
    a user-supplied list), and jax silently ships the 8-byte payload —
    doubling label transfer bytes for data the model reads as int32 anyway
    (x64 is off; jax would truncate AFTER the transfer). One shared
    coercion, applied by every put path (shard_batch / make_global_batch /
    the coalesced stager): integer leaves → int32, float64 → float32.
    """
    def fix(x):
        dt = getattr(x, "dtype", None)
        if dt is None:
            return x
        if dt == np.int64:
            return np.asarray(x, np.int32)
        if dt == np.float64:
            return np.asarray(x, np.float32)
        return x

    return jax.tree_util.tree_map(fix, batch)


def stacked_encoder_spec(leaf_name: str, ndim: int, tensor: int = 1) -> P:
    """PartitionSpec for one PipelinedEncoder stacked-param leaf: ``pipeline``
    on the leading depth axis, plus (when ``tensor`` > 1) the Megatron
    placement on the head/hidden axis — whole heads of qkv (L,D,3,H,hd) and
    proj (L,H,hd,D), columns of mlp_w1 (L,D,F)/mlp_b1 (L,F), rows of
    mlp_w2 (L,F,D) — and, for the MoE pipeline (pp×ep), ``expert`` on the
    expert-stacked axis of moe_w1/b1/w2/b2 (L,E,...) while the router
    stays replicated across ``expert`` (routing must be globally
    consistent). Single source of truth for BOTH the training-state
    sharding (param_sharding_rule) and the pipeline shard_map in_specs
    (models/pipeline.py) — they must agree or every step reshards."""
    if leaf_name.startswith("moe_"):
        if tensor > 1:
            # Megatron INSIDE each expert (MoE×tensor, round 5): columns
            # of moe_w1 (L,E,D,F)/moe_bias1 (L,E,F), rows of moe_w2
            # (L,E,F,D); moe_bias2 stays replicated across `tensor`
            # (added after the completing psum, models/moe.expert_ffn)
            spec = {
                "moe_w1": P("pipeline", "expert", None, "tensor"),
                "moe_bias1": P("pipeline", "expert", "tensor"),
                "moe_w2": P("pipeline", "expert", "tensor", None),
            }.get(leaf_name)
            if spec is not None:
                return spec
        return P(*(("pipeline", "expert") + (None,) * (ndim - 2)))
    if tensor > 1:
        spec = {
            "qkv_kernel": P("pipeline", None, None, "tensor", None),
            "proj_kernel": P("pipeline", "tensor", None, None),
            "mlp_w1": P("pipeline", None, "tensor"),
            "mlp_b1": P("pipeline", "tensor"),
            "mlp_w2": P("pipeline", "tensor", None),
        }.get(leaf_name)
        if spec is not None:
            return spec
    return P(*(("pipeline",) + (None,) * (ndim - 1)))


# (leaf, shape, tensor) triples already warned about below — once per
# distinct drop-back, not per retrace/model rebuild
_TENSOR_DROPBACK_WARNED: set = set()


def _warn_tensor_dropback(path: str, shape, tensor: int) -> None:
    """A requested tensor split the shape does not divide falls back to
    replication — numerics stay correct, but the leaf's FLOPs (often the
    dominant MLP matmuls) then run in full on every tensor peer. Silent
    replicated compute is the failure mode the Trainer's dead-axis config
    checks exist to prevent, so say it loudly, once per leaf shape."""
    key = (path.rsplit("['", 1)[-1], tuple(shape), tensor)
    if key in _TENSOR_DROPBACK_WARNED:
        return
    _TENSOR_DROPBACK_WARNED.add(key)
    import logging
    logging.getLogger(__name__).warning(
        "tensor axis (%d) does not divide the split dim of %s (shape %s) "
        "— this leaf will REPLICATE across tensor peers; pick model dims "
        "divisible by the tensor axis", tensor, path, tuple(shape))


def param_sharding_rule(path: str, shape: tuple, mesh: Mesh,
                        fsdp_min_size: int = 2 ** 16) -> P:
    """Parameter placement rule.

    Tensor parallelism (Megatron-style, transformer blocks only): when the
    ``tensor`` axis is >1, attention heads and the MLP hidden dim split
    column-/row-wise so each block needs exactly one all-reduce, inserted by
    XLA at the row-parallel contraction:

        qkv kernel (D, 3, H, hd) → P(None, None, "tensor", None)  (whole heads)
        out  kernel (H, hd, D)   → P("tensor", None, None)
        mlp  up    (D, 4D)       → P(None, "tensor")
        mlp  down  (4D, D)       → P("tensor", None)

    ZeRO-3-style fsdp: shard the largest dimension of big params over
    ``fsdp`` when it divides evenly; small params stay replicated (a sharded
    1-D BN scale buys nothing and costs collective latency)."""
    pipeline = mesh.shape.get("pipeline", 1)
    if pipeline > 1 and "['encoder']" in path and shape \
            and shape[0] % pipeline == 0:
        # PipelinedEncoder stacks per-layer params on a leading depth axis;
        # sharding it over `pipeline` (× `tensor` on the Megatron axes) puts
        # each stage's weights (and optimizer moments) on its own devices —
        # matching the shard_map in_specs so no per-step resharding is needed
        leaf = path.rsplit("['", 1)[-1].rstrip("]'")
        spec = stacked_encoder_spec(leaf, len(shape),
                                    mesh.shape.get("tensor", 1))
        # only honor a tensor split the shape actually divides (dropping
        # back to the tensor-free spec keeps `expert` on MoE leaves)
        for axis_name, dim in zip(spec, shape):
            if axis_name == "tensor" and dim % mesh.shape["tensor"]:
                _warn_tensor_dropback(path, shape, mesh.shape["tensor"])
                return stacked_encoder_spec(leaf, len(shape), 1)
        return spec
    expert = mesh.shape.get("expert", 1)
    tensor = mesh.shape.get("tensor", 1)
    if "SwitchMlp" in path and "router" not in path and shape:
        # Switch MoE expert-stacked weights: each expert group holds its
        # own experts (+ moments); the router stays replicated. With a
        # tensor axis, each expert's FFN additionally splits Megatron-
        # style (w1/bias1 columns, w2 rows; one psum — expert_ffn), so
        # ep×tp and tp-only MoE stop replicating the dominant FLOPs.
        e_ax = "expert" if (expert > 1 and shape[0] % expert == 0) else None
        leaf = path.rsplit("['", 1)[-1].rstrip("]'")
        t_pos = {"w1": 2, "bias1": 1, "w2": 1}.get(leaf)
        spec = [e_ax] + [None] * (len(shape) - 1)
        if tensor > 1 and t_pos is not None and len(shape) > t_pos:
            if shape[t_pos] % tensor == 0:
                spec[t_pos] = "tensor"
            else:
                _warn_tensor_dropback(path, shape, tensor)
        if any(spec):
            return P(*spec)
        # no expert/tensor split applies — fall through to the fsdp rule
    if tensor > 1 and ("EncoderBlock" in path or "MultiHeadAttention" in path):
        if "kernel" in path:
            split_dim = None
            if "qkv" in path and len(shape) == 4:
                split_dim, spec = 2, P(None, None, "tensor", None)
            elif "proj" in path and len(shape) == 3:
                split_dim, spec = 0, P("tensor", None, None)
            elif "Dense_0" in path and len(shape) == 2:
                split_dim, spec = 1, P(None, "tensor")
            elif "Dense_1" in path and len(shape) == 2:
                split_dim, spec = 0, P("tensor", None)
            if split_dim is not None:
                if shape[split_dim] % tensor == 0:
                    return spec
                _warn_tensor_dropback(path, shape, tensor)
        if "bias" in path and len(shape) == 1 and "Dense_0" in path \
                and shape[0] % tensor == 0:
            return P("tensor")
    fsdp = mesh.shape["fsdp"]
    if fsdp <= 1 or int(np.prod(shape)) < fsdp_min_size:
        return P()
    # choose the largest axis divisible by the fsdp size
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in order:
        if shape[i] % fsdp == 0:
            spec = [None] * len(shape)
            spec[i] = "fsdp"
            return P(*spec)
    return P()


def tree_param_shardings(params: Any, mesh: Mesh,
                         fsdp_min_size: int = 2 ** 16):
    """Map a param pytree to NamedShardings via `param_sharding_rule`."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        name = "/".join(str(p) for p in path)
        spec = param_sharding_rule(name, np.shape(leaf), mesh)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# ZeRO-1: optimizer-state / weight-update sharding over the `data` axis
# (arXiv:2004.13336 — "Automatic Cross-Replica Sharding of Weight Update
# in Data-Parallel Training"). The regex→PartitionSpec rule-table shape
# follows the `match_partition_rules` exemplar (SNIPPETS.md [2]).
# ---------------------------------------------------------------------------

#: leaves below this many ELEMENTS stay replicated under ZeRO-1 by default
#: (config knob: optimizer.zero1_min_size) — sharding a (64,) BN-scale
#: moment buys bytes nobody misses and costs a collective per step
ZERO1_MIN_SIZE = 2048


class _SizesMesh:
    """Duck-typed stand-in for a Mesh where only axis SIZES matter (the
    sharding rules read nothing else) — lets the lint rule and the
    big-mesh elaboration sweep resolve specs without materializing 256
    virtual devices."""

    def __init__(self, sizes: Dict[str, int]):
        # every axis present (param_sharding_rule indexes "fsdp" directly)
        self.shape = {"pipeline": 1, "data": 1, "fsdp": 1, "expert": 1,
                      "seq": 1, "tensor": 1, **sizes}


def match_partition_rules(rules, tree_shapes):
    """``(regex, maker)`` rule table → a PartitionSpec pytree (the
    SNIPPETS.md [2] ``match_partition_rules`` pattern): for every leaf the
    FIRST rule whose regex searches the flattened ``/``-joined path wins;
    ``maker`` is either a literal PartitionSpec or a callable
    ``(path, shape) -> PartitionSpec``. Raises if no rule matches — a
    rule table is exhaustive by contract (end it with ``(".*", ...)``)."""
    import re
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree_shapes)
    out = []
    for path, leaf in flat:
        name = "/".join(str(p) for p in path)
        for pattern, maker in rules:
            if re.search(pattern, name) is not None:
                spec = maker(name, np.shape(leaf)) if callable(maker) \
                    else maker
                out.append(spec)
                break
        else:
            raise ValueError(f"no partition rule matched leaf {name!r}")
    return jax.tree_util.tree_unflatten(treedef, out)


def _zero1_augment(base_spec: P, shape, data: int, min_size: int,
                   report: Optional["Zero1Report"], name: str) -> P:
    """Insert ``data`` into ``base_spec`` on the largest FREE dim it
    divides; fall back to the base (replicated-over-data) spec otherwise,
    counting why. Dims already sharded (fsdp/tensor/...) are left alone —
    composing axes on one dim would entangle the reduce-scatter layout
    with the fsdp gather order for marginal extra savings."""
    nbytes = int(np.prod(shape, dtype=np.int64)) * 4  # f32 moments
    if data <= 1:
        if report:
            report.count(name, nbytes, None, "no-data-axis")
        return base_spec
    if int(np.prod(shape, dtype=np.int64)) < min_size:
        if report:
            report.count(name, nbytes, None, "below-min-size")
        return base_spec
    base = tuple(base_spec) + (None,) * (len(shape) - len(base_spec))
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for d in order:
        if base[d] is None and shape[d] % data == 0:
            spec = list(base)
            spec[d] = "data"
            if report:
                report.count(name, nbytes, d, "sharded")
            return P(*spec)
    if report:
        report.count(name, nbytes, None, "no-divisible-dim")
    return base_spec


def zero1_rules(mesh, min_size: int = ZERO1_MIN_SIZE,
                report: Optional["Zero1Report"] = None):
    """The ZeRO-1 rule table for OPTIMIZER-STATE leaves: regex on the
    flattened path → PartitionSpec (first match wins). Scalar bookkeeping
    (step counts, schedule state) stays replicated; moment tensors
    (momentum ``trace``, Adam/LAMB ``mu``/``nu``) and any other
    param-shaped leaf shard their largest free dim over ``data`` on top
    of the base fsdp/tensor placement (``param_sharding_rule``), falling
    back to the base spec — counted in ``report`` — when nothing
    divides. ``mesh`` may be a real Mesh or a ``_SizesMesh``."""
    data = mesh.shape.get("data", 1)

    def shard(name, shape):
        base = param_sharding_rule(name, shape, mesh)
        return _zero1_augment(base, shape, data, min_size, report, name)

    def replicate(name, shape):
        if report:
            report.count(name, int(np.prod(shape, dtype=np.int64)) * 4,
                         None, "bookkeeping")
        return P()

    return (
        # optimizer bookkeeping scalars/schedules: never sharded. Matched
        # at NamedTuple-ATTR positions only (flattened as ".count") — a
        # PARAM named e.g. "scale" flattens as "['scale']" and must fall
        # through to the moment rules below
        (r"\.(count|mini_step|gradient_step|inner_state|"
         r"notfinite_count|scale)($|/)", replicate),
        # moment tensors: momentum trace, Adam/LAMB mu+nu — the ZeRO-1
        # payload proper
        (r"\.(trace|mu|nu)($|/)", shard),
        # anything else param-shaped (future optimizers) gets the same
        # treatment; scalars fall below min_size and replicate
        (r".*", shard),
    )


class Zero1Report:
    """Counted record of one ZeRO-1 spec resolution: how many leaves (and
    bytes) actually sharded over ``data`` vs fell back replicated, and
    why — the ``{"event": "zero1"}`` row (train/hooks.Zero1Hook), the
    bench ``zero1`` row, and the ``unsharded-opt-state`` lint rule all
    read this instead of re-deriving it."""

    def __init__(self, data: int = 1):
        self.data = max(1, int(data))
        self.sharded_leaves = 0
        self.replicated_leaves = 0
        self.sharded_bytes = 0
        self.replicated_bytes = 0
        self.reasons: Dict[str, int] = {}
        self.decisions: Dict[str, Optional[int]] = {}

    def count(self, name: str, nbytes: int, dim: Optional[int],
              reason: str) -> None:
        self.decisions[name] = dim
        self.reasons[reason] = self.reasons.get(reason, 0) + 1
        if dim is None:
            self.replicated_leaves += 1
            self.replicated_bytes += int(nbytes)
        else:
            self.sharded_leaves += 1
            self.sharded_bytes += int(nbytes)

    @property
    def bytes_per_replica(self) -> int:
        """Per-replica optimizer-state bytes under this resolution:
        sharded leaves cost 1/data, replicated leaves full."""
        return self.replicated_bytes + self.sharded_bytes // self.data

    def snapshot(self) -> Dict[str, Any]:
        total = self.sharded_bytes + self.replicated_bytes
        return {
            "data_shards": self.data,
            "sharded_leaves": self.sharded_leaves,
            "replicated_leaves": self.replicated_leaves,
            "sharded_bytes": self.sharded_bytes,
            "replicated_bytes": self.replicated_bytes,
            "bytes_per_replica": self.bytes_per_replica,
            "bytes_per_replica_unsharded": total,
            "reasons": dict(self.reasons),
        }


class Zero1Stats:
    """Process-global record of the most recent ZeRO-1 resolution — what
    the ``{"event": "zero1"}`` metrics row exports."""

    def __init__(self):
        self._lock = threading.Lock()
        self._snap: Optional[Dict[str, Any]] = None

    def record_report(self, report: Zero1Report) -> None:
        with self._lock:
            base = self._snap or {}
            self._snap = {**base, **report.snapshot()}

    def reset(self) -> None:
        with self._lock:
            self._snap = None

    def snapshot(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            return dict(self._snap) if self._snap is not None else None


#: process-global ZeRO-1 telemetry (one training process = one resolution)
zero1_stats = Zero1Stats()


def zero1_unsupported_reason(cfg, mesh) -> Optional[str]:
    """None when the ZeRO-1 sharded weight update applies to this
    (cfg, mesh); else a one-line reason. The sharded update is a layout
    transformation, not a step rewrite: it needs only a >1 ``data`` axis and no program-shaping axes (those bake their own
    shard_maps and optimizer layouts into the model)."""
    if mesh.shape.get("data", 1) <= 1:
        return ("a single data shard holds the whole optimizer state "
                "either way — nothing to shard")
    for axis in ("pipeline", "tensor", "expert", "seq"):
        if mesh.shape.get(axis, 1) > 1:
            return (f"mesh axis {axis!r} > 1 already lays the optimizer "
                    "state out with the model's own shard_maps; the "
                    "ZeRO-1 rule table covers data/fsdp meshes")
    return None


def resolve_zero1(cfg, mesh) -> bool:
    """``optimizer.zero1`` → active? ``auto`` = on iff the run has >1
    process (where per-replica optimizer memory binds) and the envelope
    supports it; ``on`` forces — raising the reason, except on a
    single-data-shard mesh (what checkpoint CONSUMERS like the standalone
    evaluator and 1-device serving replicas see when they build a Trainer
    from a training config: a train-step-only knob must resolve off
    loudly there, not crash them)."""
    import logging
    mode = cfg.optimizer.zero1
    if mode not in ("auto", "on", "off"):
        raise ValueError(f"unknown optimizer.zero1 setting {mode!r}")
    if mode == "off":
        return False
    reason = zero1_unsupported_reason(cfg, mesh)
    if mode == "on":
        if reason is not None:
            if mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1) <= 1:
                logging.getLogger(__name__).warning(
                    "optimizer.zero1=on resolved OFF: %s", reason)
                return False
            raise ValueError(
                f"optimizer.zero1=on is unsupported here: {reason}")
        return True
    return reason is None and jax.process_count() > 1


def zero1_grad_specs(params, mesh, min_size: int = ZERO1_MIN_SIZE,
                     report: Optional[Zero1Report] = None):
    """Per-leaf ZeRO-1 PartitionSpecs for a PARAM-shaped tree (grads and
    updates): the base ``param_sharding_rule`` placement with ``data``
    inserted on the largest free divisible dim. This is the layout the
    reduce-scattered gradients land in and the one the optimizer shard
    update runs in — it must agree leaf-by-leaf with the optimizer-state
    shardings (``zero1_state_shardings`` applies the same augment to the
    mirrored moment leaves), or every step would reshard."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    data = mesh.shape.get("data", 1)
    out = []
    for path, leaf in flat:
        name = "/".join(str(p) for p in path)
        base = param_sharding_rule(name, np.shape(leaf), mesh)
        out.append(_zero1_augment(base, np.shape(leaf), data, min_size,
                                  report, name))
    return jax.tree_util.tree_unflatten(treedef, out)


def zero1_state_shardings(opt_state_shapes, mesh: Mesh,
                          min_size: int = ZERO1_MIN_SIZE,
                          report: Optional[Zero1Report] = None):
    """NamedShardings for an OPTIMIZER-STATE tree under ZeRO-1: the rule
    table (``zero1_rules``) resolves every leaf. Requires a real Mesh
    (NamedShardings embed it); spec-only callers (lint, big-mesh sweeps)
    use ``zero1_rules`` with a ``_SizesMesh`` directly."""
    specs = match_partition_rules(zero1_rules(mesh, min_size, report),
                                  opt_state_shapes)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


def shard_batch(batch: Any, mesh: Mesh) -> Any:
    """Device-put a host batch with the leading dim split over the batch axes.

    For multi-host, use `make_global_batch` instead — each process contributes
    its local shard (the reference's Horovod path never sharded input at all;
    each rank shuffled the full dataset independently, SURVEY.md §3.2 — fixed
    here by construction).
    """
    from .mesh import data_sharding
    sharding = data_sharding(mesh)
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), coerce_batch_dtypes(batch))


def pad_batch_to_multiple(batch: dict, multiple: int) -> dict:
    """Pad the leading dim to a multiple of the batch-shard count, adding (or
    extending) a float "mask" entry so padded rows don't count in metrics.
    Needed because an eval batch (reference used 100, resnet_cifar_eval.py)
    need not divide the device count."""
    b = next(iter(batch.values())).shape[0]
    rem = b % multiple
    if rem == 0:
        return batch
    pad = multiple - rem
    out = {}
    for k, v in batch.items():
        if k == "mask":
            continue
        pad_width = ((0, pad),) + ((0, 0),) * (v.ndim - 1)
        out[k] = np.pad(np.asarray(v), pad_width)
    mask = batch.get("mask")
    if mask is None:
        mask = np.ones((b,), np.float32)
    out["mask"] = np.concatenate([np.asarray(mask),
                                  np.zeros((pad,), np.float32)])
    return out


def pad_batch_to_bucket(batch: dict, bucket: int) -> dict:
    """Pad the leading dim up to EXACTLY ``bucket`` rows — the serving
    batcher's padding (serve/batcher.py): a partial group of in-flight
    requests lands in its power-of-two bucket so every bucket size maps to
    ONE AOT-compiled program. Same mask semantics as
    ``pad_batch_to_multiple`` (padded rows carry mask 0); buckets are sized
    in multiples of ``Trainer.eval_pad_multiple`` so the padded batch also
    divides over the batch shards (× pipeline microbatches)."""
    b = next(iter(batch.values())).shape[0]
    if b > bucket:
        raise ValueError(f"batch of {b} rows does not fit bucket {bucket}")
    pad = bucket - b
    out = {}
    for k, v in batch.items():
        if k == "mask":
            continue
        pad_width = ((0, pad),) + ((0, 0),) * (np.asarray(v).ndim - 1)
        out[k] = np.pad(np.asarray(v), pad_width)
    mask = batch.get("mask")
    if mask is None:
        mask = np.ones((b,), np.float32)
    out["mask"] = np.concatenate([np.asarray(mask, np.float32),
                                  np.zeros((pad,), np.float32)])
    return out


def shard_stacked_batch(batch: Any, mesh: Mesh) -> Any:
    """Like shard_batch but for K-step stacked batches (K, B, ...): the K
    axis is unsharded (scan iterates it), B splits over the batch axes."""
    from .mesh import data_sharding
    sharding = NamedSharding(mesh, P(None, *data_sharding(mesh).spec))
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), coerce_batch_dtypes(batch))


def make_global_stacked_batch(local_batch: Any, mesh: Mesh) -> Any:
    """Multi-process variant of shard_stacked_batch: each process holds
    (K, B_local, ...); the global array is (K, B_local·num_input_shards,
    ...). The multiplier is the number of DISTINCT batch slices across
    processes (mesh.process_batch_slice) — equal to process_count for pure
    data-over-processes, smaller when a non-batch axis spans processes
    (those processes feed identical replicated slices)."""
    from .mesh import data_sharding, process_batch_slice
    sharding = NamedSharding(mesh, P(None, *data_sharding(mesh).spec))
    _, n_shards = process_batch_slice(mesh)

    def _make(x):
        global_shape = (x.shape[0], x.shape[1] * n_shards) + x.shape[2:]
        return jax.make_array_from_process_local_data(sharding, x, global_shape)

    return jax.tree_util.tree_map(_make, coerce_batch_dtypes(local_batch))


def _issue_device_put(arrays, devices):
    """The ONE host→device transfer issue point of the coalesced staging
    path: a single batched ``jax.device_put`` call moves every per-device
    staging region of a batch. Module-level so tests can wrap it with a
    counting shim and assert exactly one transfer per training batch."""
    return jax.device_put(arrays, devices)


def put_to_sharding(tree, shardings):
    """Generic host→device placement for the NON-coalesced paths (device
    dataset upload, index batches, per-leaf fallback). This module is the
    single home of ``jax.device_put``: every transfer either funnels
    through ``_issue_device_put`` (coalesced hot path) or this thin
    wrapper, so transfer accounting and the thread-safety story
    (docs/input_pipeline.md) have exactly one file to audit — enforced by
    ``analysis/rules/device_put.py`` (stray-device-put)."""
    return jax.device_put(tree, shardings)


def _device_batch_shards(mesh: Mesh):
    """[(device, batch_shard_id)] for this process's addressable devices,
    ordered by mesh position. shard_id = data_coord * fsdp_size + fsdp_coord
    — the same linearization data_sharding uses for the leading batch dim."""
    ax = {name: i for i, name in enumerate(mesh.axis_names)}
    fsdp_size = mesh.shape.get("fsdp", 1)
    out = []
    pi = jax.process_index()
    for idx in np.ndindex(mesh.devices.shape):
        dev = mesh.devices[idx]
        if dev.process_index != pi:
            continue
        d = idx[ax["data"]] if "data" in ax else 0
        f = idx[ax["fsdp"]] if "fsdp" in ax else 0
        out.append((dev, d * fsdp_size + f))
    return out


def _staging_fields(spec: Tuple, batch_axis: int, b_local: int, pb: int,
                    with_seed: bool):
    """Byte layout of one batch spec inside a per-device staging region:
    ``(fields, region_nbytes, seed_off)``. ``with_seed`` reserves a
    trailing 4-byte slot for the fused-augment RNG counter (see
    ``_build_unpack``) so the per-batch augmentation key rides the ONE
    coalesced transfer instead of costing a second host→device hop.
    Shared by the live ``_StagingLayout`` and the allocation-free
    ``abstract_staged_unpack`` gate path — the two must lay bytes out
    identically or the gate would trace a different program than
    production runs."""
    fields = []
    off = 0
    for key, shape, dtype in spec:
        if len(shape) <= batch_axis or shape[batch_axis] != b_local:
            raise ValueError(
                f"leaf {key!r} shape {shape} does not carry the batch "
                f"dim {b_local} on axis {batch_axis}")
        rest = shape[batch_axis + 1:]
        k_steps = shape[0] if batch_axis == 1 else 1
        nbytes = pb * int(np.prod(rest, dtype=np.int64)) \
            * k_steps * dtype.itemsize
        fields.append((key, shape, dtype, off, int(nbytes)))
        off += (int(nbytes) + 7) // 8 * 8  # 8-byte-align every leaf
    seed_off = None
    if with_seed:
        seed_off = off
        off += 8
    return tuple(fields), off, seed_off


class _StagingLayout:
    """Byte layout of one batch spec inside the coalesced staging buffer,
    plus its reusable host ring and compiled device-side unpack."""

    __slots__ = ("fields", "region_nbytes", "ring_buf", "inflight", "slot",
                 "unpack", "pb", "batch_axis", "seed_off")

    def __init__(self, mesh: Mesh, spec: Tuple, stacked: bool, ring: int,
                 shards, augment: Optional[Tuple] = None,
                 augment_seed: int = 0):
        self.batch_axis = 1 if stacked else 0
        n_shards = batch_shard_count_total(mesh)
        n_local = len({s for _, s in shards})
        b_local = spec[0][1][self.batch_axis]
        if b_local % n_local:
            raise ValueError(
                f"local batch {b_local} not divisible by this process's "
                f"{n_local} batch shards")
        self.pb = b_local // n_local
        self.fields, self.region_nbytes, self.seed_off = _staging_fields(
            spec, self.batch_axis, b_local, self.pb, augment is not None)
        self.ring_buf = np.empty((ring, len(shards), self.region_nbytes),
                                 np.uint8)
        self.inflight: list = [None] * ring
        self.slot = 0
        self.unpack = _build_unpack(mesh, self.fields, stacked, n_shards,
                                    self.pb, augment=augment,
                                    seed_off=self.seed_off,
                                    augment_seed=augment_seed)

    def pack(self, batch, shards, lo_shard: int, ctr: int = 0):
        """Copy each device's rows of every leaf into its staging region
        (one host memcpy pass); returns (slot, per-device uint8 views).
        ``ctr`` is the stager's put counter — written into every shard's
        seed slot when the layout carries a fused augment, so the unpack
        program derives a fresh per-batch RNG key from the staged bytes
        themselves."""
        slot = self.slot
        self.slot = (slot + 1) % len(self.inflight)
        prev = self.inflight[slot]
        if prev is not None:
            # the slot's previous transfer may still be reading the host
            # buffer (async H2D): wait before overwriting
            jax.block_until_ready(prev)
            self.inflight[slot] = None
        buf = self.ring_buf[slot]
        stacked = self.batch_axis == 1
        for di, (_dev, shard) in enumerate(shards):
            r0 = (shard - lo_shard) * self.pb
            r1 = r0 + self.pb
            for key, shape, dtype, off, nbytes in self.fields:
                src = batch[key][:, r0:r1] if stacked else batch[key][r0:r1]
                dst = buf[di, off:off + nbytes].view(dtype)
                np.copyto(dst.reshape(src.shape), src)
        if self.seed_off is not None:
            seed_bytes = np.frombuffer(
                np.uint32(ctr & 0xFFFFFFFF).tobytes(), np.uint8)
            buf[:, self.seed_off:self.seed_off + 4] = seed_bytes
        # (1, region) row views: the per-device shard shape of the global
        # (n_shards, region) flat array
        return slot, [buf[di:di + 1] for di in range(len(shards))]


def batch_shard_count_total(mesh: Mesh) -> int:
    return mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)


# unpack programs shared across equal meshes (weak keys: a cache entry dies
# with its mesh instead of pinning device arrays — see mesh.py note)
_UNPACK_CACHE: "weakref.WeakKeyDictionary[Mesh, Dict]" = \
    weakref.WeakKeyDictionary()
_UNPACK_LOCK = threading.Lock()


def _build_unpack(mesh: Mesh, fields: Tuple, stacked: bool, n_shards: int,
                  pb: int, augment: Optional[Tuple] = None,
                  seed_off: Optional[int] = None, augment_seed: int = 0):
    """Compile flat (n_shards, region_bytes) uint8 → the batch pytree.

    Each leaf is sliced out of its shard's region, bitcast to its dtype and
    reshaped back; the shard axis merges into the batch dim. The slicing is
    shard-local, so XLA lowers it to per-device copies — no collectives.

    ``augment`` = (leaf_name, kind, pad) — a hashable spec resolved by
    ``ops.augment.device_augment_fn`` — FUSES the device-side train
    augmentation into this same program: the named leaf (raw uint8 crops)
    comes out flipped/jittered/standardized float32, so augmentation costs
    no extra dispatch and runs exactly once per staged batch. Its RNG key
    derives from a per-put counter embedded in the staged bytes at
    ``seed_off`` (see ``_StagingLayout.pack``) — fresh draws per batch
    with still exactly ONE host→device transfer. Reading that counter
    broadcasts 4 bytes from shard 0 (the one non-shard-local access);
    the augment ops themselves are batch-elementwise and stay shard-local
    under GSPMD. Like the rest of the program, a fused-augment unpack is a
    multi-device execution: consumer-thread dispatch only (StagedBatch).
    """
    from .mesh import data_sharding
    key = (fields, stacked, augment, augment_seed)
    with _UNPACK_LOCK:
        per_mesh = _UNPACK_CACHE.get(mesh)
        if per_mesh is None:
            per_mesh = {}
            _UNPACK_CACHE[mesh] = per_mesh
        hit = per_mesh.get(key)
    if hit is not None:
        return hit
    flat_sh = NamedSharding(mesh, P(("data", "fsdp")))
    leaf_sh = data_sharding(mesh) if not stacked else \
        NamedSharding(mesh, P(None, *data_sharding(mesh).spec))

    def unpack(flat):
        import jax.numpy as jnp
        out = {}
        channels = None
        # the scopes name the device ops in a profiler trace
        with jax.named_scope("unpack"):
            for name, shape, dtype, off, nbytes in fields:
                jdt = dtype if dtype != np.bool_ else np.dtype(np.uint8)
                seg = jax.lax.slice(flat, (0, off), (n_shards, off + nbytes))
                rest = shape[2:] if stacked else shape[1:]
                if augment is not None and name == augment[0] \
                        and len(rest) == 3:
                    # the augmented image leaf leaves the staged bytes as
                    # the lane-dense (.., H, W*C) view its augmentation
                    # computes on (ops/augment.py): a (.., W, C) tensor
                    # keeps C=3 in the TPU's 128 lanes
                    channels = rest[2]
                    rest = (rest[0], rest[1] * rest[2])
                if stacked:
                    k_steps = shape[0]
                    tgt = (n_shards, k_steps, pb) + rest
                else:
                    tgt = (n_shards, pb) + rest
                isize = np.dtype(dtype).itemsize
                if isize > 1:
                    seg = seg.reshape(tgt + (isize,))
                else:
                    seg = seg.reshape(tgt)
                val = jax.lax.bitcast_convert_type(seg, jdt)
                if dtype == np.bool_:
                    val = val.astype(jnp.bool_)
                if stacked:
                    val = val.transpose((1, 0, 2) + tuple(
                        range(3, 3 + len(rest))))
                    val = val.reshape((shape[0], n_shards * pb) + rest)
                else:
                    val = val.reshape((n_shards * pb,) + rest)
                out[name] = val
        if augment is not None:
            with jax.named_scope("augment"):
                from ..ops.augment import device_augment_fn
                leaf_name, kind, pad = augment
                fn = device_augment_fn(kind, pad)
                seg = jax.lax.slice(flat, (0, seed_off), (1, seed_off + 4))
                ctr = jax.lax.bitcast_convert_type(seg.reshape((4,)),
                                                   jnp.uint32)
                akey = jax.random.fold_in(
                    jax.random.PRNGKey(augment_seed), ctr)
                img = out[leaf_name]
                if stacked:
                    # one key per scan step of the fused-loop group, applied
                    # with lax.map so the float32 intermediate is one
                    # microbatch at a time, not the whole (K, B, ...) group
                    keys = jax.random.split(akey, img.shape[0])
                    img = jax.lax.map(
                        lambda kv: fn(kv[0], kv[1], channels=channels),
                        (img, keys))
                else:
                    img = fn(img, akey, channels=channels)
                out[leaf_name] = img
        return out

    out_sh = {name: leaf_sh for name, *_ in fields}
    jitted = jax.jit(unpack, in_shardings=flat_sh, out_shardings=out_sh)
    with _UNPACK_LOCK:
        per_mesh[key] = jitted
    return jitted


def staged_unpack_program(mesh: Mesh, batch_shapes: Dict,
                          stacked: bool = False,
                          augment: Optional[Tuple] = None,
                          augment_seed: int = 0):
    """The coalesced unpack(+fused augment) program a stager would run for
    this batch spec, with the ShapeDtypeStruct of its flat input: ``(jitted
    unpack, flat)`` — zero allocation, nothing compiled. ``batch_shapes``
    maps leaf name → ShapeDtypeStruct exactly as the host iterator would
    deliver the batch. For the analysis gate (``abstract_staged_unpack``)
    and for tests that read the lowered program."""
    spec = tuple(sorted(
        (k, tuple(v.shape), np.dtype(v.dtype))
        for k, v in batch_shapes.items()))
    shards = _device_batch_shards(mesh)
    if not shards:
        raise ValueError("no addressable devices on this process")
    n_local = len({s for _, s in shards})
    batch_axis = 1 if stacked else 0
    b_local = spec[0][1][batch_axis]
    if b_local % n_local:
        raise ValueError(
            f"local batch {b_local} not divisible by this process's "
            f"{n_local} batch shards")
    pb = b_local // n_local
    fields, region, seed_off = _staging_fields(
        spec, batch_axis, b_local, pb, augment is not None)
    n_shards = batch_shard_count_total(mesh)
    unpack = _build_unpack(mesh, fields, stacked, n_shards, pb,
                           augment=augment, seed_off=seed_off,
                           augment_seed=augment_seed)
    return unpack, jax.ShapeDtypeStruct((n_shards, region), np.uint8)


def abstract_staged_unpack(mesh: Mesh, batch_shapes: Dict,
                           stacked: bool = False,
                           augment: Optional[Tuple] = None,
                           augment_seed: int = 0):
    """Trace the coalesced unpack(+fused augment) program ABSTRACTLY —
    zero allocation, zero compile — and return its output
    ShapeDtypeStructs. The static-elaboration gate (analysis/elaborate.py)
    calls this per preset so an unpack or fused-augment program that
    cannot trace is a pre-submit finding, not a step-1 crash on the
    cluster."""
    return jax.eval_shape(*staged_unpack_program(
        mesh, batch_shapes, stacked, augment, augment_seed))


class StagedBatch:
    """A batch whose bytes are on device (single coalesced transfer issued)
    but whose leaf arrays are not yet sliced out.

    The split exists for thread safety: the staging thread only MOVES DATA
    (``device_put`` has no cross-device rendezvous, so it is safe to issue
    concurrently with the main thread's jitted steps), while ``finalize()``
    — the tiny compiled unpack program, a multi-device XLA execution —
    must run on the CONSUMER thread. Launching multi-device executions
    from two threads interleaves their per-device enqueue order and can
    deadlock against a collective-bearing train/eval step (observed on the
    CPU backend); dispatching unpack and step from one thread keeps the
    order consistent by construction. Dispatch is async, so none of the
    overlap is lost.
    """

    __slots__ = ("flat", "_unpack")

    def __init__(self, flat, unpack):
        self.flat = flat
        self._unpack = unpack

    def block_until_ready(self):
        """Wait for the host→device transfer (used by the staging thread's
        transfer-time accounting; jax.block_until_ready duck-calls this)."""
        self.flat.block_until_ready()
        return self

    def finalize(self):
        """Slice/bitcast the device-resident bytes into the batch pytree.
        Consumer-thread only (see class docstring)."""
        return self._unpack(self.flat)


def finalize_staged(batch):
    """Resolve a StagedBatch to its pytree; pass anything else through."""
    return batch.finalize() if isinstance(batch, StagedBatch) else batch


# live stagers, for the device-memory telemetry's staging-ring occupancy
# (telemetry/memory.py): weak so a Trainer teardown releases its rings
_LIVE_STAGERS: "weakref.WeakSet" = weakref.WeakSet()


def staging_occupancy() -> Tuple[int, int]:
    """(ring slots, slots with an in-flight H2D transfer) summed across
    every live CoalescedStager's layouts — the staging-ring occupancy the
    ``{"event": "memory"}`` rows report. Lock-free reads of telemetry-
    grade accuracy: a slot flipping mid-scan is off by one for one
    sample."""
    slots = inflight = 0
    for stager in list(_LIVE_STAGERS):
        for layout in list(stager._layouts.values()):
            slots += len(layout.inflight)
            inflight += sum(1 for p in layout.inflight if p is not None)
    return slots, inflight


class CoalescedStager:
    """Coalesced host→device staging: ONE transfer issue per batch.

    Instead of a ``device_put`` per leaf (and per shard under the hood),
    each batch is packed into one contiguous, reused (ring-buffered) host
    staging region per addressable device, moved with a single batched
    ``device_put`` call, and assembled into a global flat array via
    ``make_array_from_single_device_arrays`` (no host-side gather — every
    device receives exactly its shard's bytes). ``put`` returns a
    ``StagedBatch``; the consumer finalizes it into leaf arrays via a tiny
    compiled on-device program (see StagedBatch for why that split is
    load-bearing). Fewer, larger transfers is what moves
    ``device_put_MBps``; the ring means zero per-batch host allocation on
    the hot path.

    ``stacked=True`` stages (K, B, ...) fused-loop batches (batch dim =
    axis 1). Works single- and multi-process (each process contributes its
    addressable devices' regions). Thread-safe: one lock serializes pack +
    issue, so the train and eval staging threads may share a stager.

    Stage spans and counters: the pack is ``input.stage`` → "stage", the
    transfer issue ``input.issue`` → "transfer" (``records_stages`` tells
    device_prefetch to only add its completion wait, not to time the put
    again or re-count items).

    ``augment`` = (leaf_name, kind, pad): fuse the device-side train
    augmentation for that leaf into the unpack program (see
    ``_build_unpack``) — the imagenet flip/jitter/standardize runs inside
    the one XLA program that already unpacks the staged uint8 buffer,
    drawing fresh RNG per put via a counter embedded in the staged bytes.
    Train-path stagers only: an augmenting stager must never serve eval
    or serving batches (Trainer keeps separate neutral stagers for
    those).
    """

    records_stages = True

    def __init__(self, mesh: Mesh, stacked: bool = False, ring: int = 3,
                 augment: Optional[Tuple] = None, augment_seed: int = 0):
        self.mesh = mesh
        self.stacked = stacked
        self.ring = max(2, ring)
        self.augment = augment
        self.augment_seed = augment_seed
        self._put_ctr = 0
        self._lock = threading.Lock()
        self._layouts: Dict[Tuple, _StagingLayout] = {}
        self._shards = _device_batch_shards(mesh)
        if not self._shards:
            raise ValueError("no addressable devices on this process")
        self._devices = [d for d, _ in self._shards]
        self._n_shards = batch_shard_count_total(mesh)
        self._lo_shard = min(s for _, s in self._shards)
        _LIVE_STAGERS.add(self)  # staging-ring occupancy telemetry

    def _spec_of(self, batch) -> Tuple:
        return tuple(sorted(
            (k, np.shape(v), np.dtype(np.asarray(v).dtype))
            for k, v in batch.items()))

    def __call__(self, batch):
        return self.put(batch)

    def put(self, batch):
        from ..telemetry.tracer import span
        batch = coerce_batch_dtypes(
            {k: np.asarray(v) for k, v in batch.items()})
        items = 0
        for key in ("labels", "idx"):
            if key in batch:
                items = int(batch[key].size)
                break
        with self._lock:
            with span("input.stage") as pack:
                spec = self._spec_of(batch)
                layout = self._layouts.get(spec)
                if layout is None:
                    layout = _StagingLayout(self.mesh, spec, self.stacked,
                                            self.ring, self._shards,
                                            augment=self.augment,
                                            augment_seed=self.augment_seed)
                    self._layouts[spec] = layout
                ctr = self._put_ctr
                self._put_ctr += 1
                slot, views = layout.pack(batch, self._shards,
                                          self._lo_shard, ctr)
            nbytes = len(views) * layout.region_nbytes
            pack.charge("stage", items=items, nbytes=nbytes)
            with span("input.issue") as issue:
                pieces = _issue_device_put(views, self._devices)
                layout.inflight[slot] = pieces
                flat = jax.make_array_from_single_device_arrays(
                    (self._n_shards, layout.region_nbytes),
                    NamedSharding(self.mesh, P(("data", "fsdp"))), pieces)
            issue.charge("transfer", items=items, nbytes=nbytes)
            return StagedBatch(flat, layout.unpack)

    def put_now(self, batch):
        """put + finalize in one call — for single-thread callers (tests,
        step_flops); the pipelined path finalizes on the consumer thread."""
        return self.put(batch).finalize()


def make_global_batch(local_batch: Any, mesh: Mesh) -> Any:
    """Assemble a global jax.Array from per-process local data (multi-host).
    Global batch = local × num distinct batch slices (see
    make_global_stacked_batch)."""
    from .mesh import data_sharding, process_batch_slice
    sharding = data_sharding(mesh)
    _, n_shards = process_batch_slice(mesh)

    def _make(x):
        global_shape = (x.shape[0] * n_shards,) + x.shape[1:]
        return jax.make_array_from_process_local_data(sharding, x, global_shape)

    return jax.tree_util.tree_map(_make, coerce_batch_dtypes(local_batch))
