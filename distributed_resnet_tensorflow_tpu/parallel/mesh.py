"""Device mesh construction — the single SPMD replacement for BOTH reference
communication backends.

The reference shipped two data-parallel backends (SURVEY.md §2.8-2.9):
  (a) grpc parameter-server + ``tf.train.SyncReplicasOptimizer``
      (reference resnet_cifar_main.py:350-399, resnet_model.py:102-135) —
      variables sharded round-robin onto ps tasks, gradient push/pull over
      grpc, token-queue chief machinery; documented not to scale
      (reference README.md:7-15).
  (b) Horovod MPI/NCCL ring allreduce (reference resnet_cifar_main_horovod.py).

Here both collapse into one path: a named ``jax.sharding.Mesh`` over which
``jax.jit`` lays out arrays and XLA inserts the collectives (all-reduce /
all-gather / reduce-scatter) on ICI/DCN. The parameter-server topology
disappears; Horovod's rank-0 broadcast becomes replicated init by construction.

Mesh axes (all present from day one so sequence/expert/pipeline workloads can
be added without re-architecting — see SURVEY.md §5 "long-context" note):
  data     — batch data parallelism (the reference's only axis)
  fsdp     — ZeRO-like parameter/optimizer-state sharding
  tensor   — tensor (op-level) parallelism
  pipeline — pipeline stage parallelism
  seq      — sequence/context parallelism (ring attention)
  expert   — expert parallelism
"""
from __future__ import annotations

import math
import threading
import weakref
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Canonical axis order: fastest-varying (innermost, highest-bandwidth ICI)
# axes last, so tensor/seq collectives ride the tightest links.
AXES = ("pipeline", "data", "fsdp", "expert", "seq", "tensor")


def resolve_axis_sizes(mesh_cfg, num_devices: Optional[int] = None) -> Tuple[int, ...]:
    """Resolve a MeshConfig into concrete per-axis sizes.

    Any axis set to -1 absorbs all remaining devices (at most one may be -1);
    the product must equal the device count.
    """
    if num_devices is None:
        num_devices = jax.device_count()
    sizes = {
        "pipeline": mesh_cfg.pipeline,
        "data": mesh_cfg.data,
        "fsdp": mesh_cfg.fsdp,
        "expert": mesh_cfg.expert,
        "seq": mesh_cfg.sequence,
        "tensor": mesh_cfg.tensor,
    }
    # 0 and 1 both mean "collapsed axis"
    sizes = {a: (1 if s == 0 else s) for a, s in sizes.items()}
    wild = [a for a, s in sizes.items() if s == -1]
    if len(wild) > 1:
        raise ValueError(f"at most one mesh axis may be -1, got {wild}")
    fixed = math.prod(s for s in sizes.values() if s != -1)
    if wild:
        if num_devices % fixed != 0:
            raise ValueError(
                f"{num_devices} devices not divisible by fixed axes product {fixed}")
        sizes[wild[0]] = num_devices // fixed
    total = math.prod(sizes.values())
    if total != num_devices:
        raise ValueError(
            f"mesh {sizes} covers {total} devices but {num_devices} are present")
    return tuple(sizes[a] for a in AXES)


def create_mesh(mesh_cfg=None, devices: Optional[Sequence[jax.Device]] = None,
                axis_sizes: Optional[Tuple[int, ...]] = None) -> Mesh:
    """Build the global mesh. ``jax.make_mesh`` / ``mesh_utils`` pick a
    device permutation that keeps inner axes on the fastest ICI links."""
    if devices is None:
        devices = jax.devices()
    if axis_sizes is None:
        if mesh_cfg is None:
            axis_sizes = tuple(
                1 if a != "data" else len(devices) for a in AXES)
        else:
            axis_sizes = resolve_axis_sizes(mesh_cfg, len(devices))
    try:
        from jax.experimental import mesh_utils
        dev_array = mesh_utils.create_device_mesh(
            axis_sizes, devices=np.asarray(devices))
    except Exception:
        # host-aware fallback order: group each host's devices
        # contiguously (stable by (process_index, id)) before the reshape,
        # so consecutive ``data`` coordinates land on one host whenever
        # the axis sizes allow
        ordered = sorted(devices, key=lambda d: (
            getattr(d, "process_index", 0), getattr(d, "id", 0)))
        dev_array = np.asarray(ordered).reshape(axis_sizes)
    return Mesh(dev_array, AXES)


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for a batch: leading dim split over every batch-like axis
    (data × fsdp), rest replicated."""
    return NamedSharding(mesh, P(("data", "fsdp")))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def present_batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Batch-splitting axes that are actually >1 (tolerates hand-built
    meshes missing axes). May be empty — callers wanting a PartitionSpec
    use ``present_batch_axes(mesh) or None``."""
    return tuple(a for a in ("data", "fsdp") if mesh.shape.get(a, 1) > 1)


def batch_shard_count(mesh: Mesh) -> int:
    return mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)


def shard_map_unchecked(fn, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` over every mesh axis with the varying-mesh-axes
    check off — our bodies wrap collectives and ``pallas_call``, which
    don't declare that info."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# weak-key memo: an lru_cache here would pin up to maxsize Mesh objects
# (and their device arrays) for the process lifetime — a real leak in long
# sessions that build many meshes (tests, notebooks). Weak keys drop an
# entry the moment its mesh is collected; equal live meshes still share one.
_batch_slice_cache: "weakref.WeakKeyDictionary[Mesh, Tuple[int, int]]" = \
    weakref.WeakKeyDictionary()
_batch_slice_lock = threading.Lock()


def process_batch_slice(mesh: Mesh) -> Tuple[int, int]:
    """(input_shard_index, num_input_shards) for THIS process.

    Multi-process input feeding must be keyed by which slice of the BATCH
    dimension (the data × fsdp coordinate range) this process's devices
    address — NOT by process_index. When a non-batch axis (pipeline,
    tensor, expert, seq) crosses the process boundary, several processes
    address the SAME batch slice and must feed identical data; sharding
    input by process_index there desynchronizes the replicas (caught by
    tests/test_launch.py::test_two_process_pipeline_vit_checkpoint_eval).
    Pure data-over-processes reduces to (process_index, process_count).

    Memoized per mesh (weak-key, see above): the result is a pure function
    of the mesh, but the computation scans every device coordinate
    (O(total devices) in Python) and the callers (make_global_batch /
    make_global_stacked_batch) sit in the per-step input hot path.
    """
    with _batch_slice_lock:
        hit = _batch_slice_cache.get(mesh)
    if hit is not None:
        return hit
    pi = jax.process_index()
    arr = mesh.devices
    ax = {name: i for i, name in enumerate(mesh.axis_names)}
    fsdp_size = mesh.shape.get("fsdp", 1)
    ids = set()
    for idx in np.ndindex(arr.shape):
        if arr[idx].process_index == pi:
            d = idx[ax["data"]] if "data" in ax else 0
            f = idx[ax["fsdp"]] if "fsdp" in ax else 0
            ids.add(d * fsdp_size + f)
    total = mesh.shape.get("data", 1) * fsdp_size
    lo, n = min(ids), len(ids)
    if sorted(ids) != list(range(lo, lo + n)) or total % n or lo % n:
        raise ValueError(
            f"process {pi}'s devices cover batch shards {sorted(ids)} — "
            "not an aligned contiguous range; choose mesh axis sizes so "
            "each process's batch slice is contiguous")
    result = (lo // n, total // n)
    with _batch_slice_lock:
        _batch_slice_cache[mesh] = result
    return result


def batch_slice_replicated(mesh: Mesh) -> bool:
    """True when several processes feed the SAME batch slice (a non-batch
    mesh axis spans the process boundary): fewer distinct slices than
    processes. Replicas must then assemble byte-identical batches — input
    builders pass this as the pipeline's ``deterministic`` flag
    (data/imagenet.py)."""
    _, num_shards = process_batch_slice(mesh)
    return jax.process_count() > num_shards


def local_batch_size(global_batch: int, mesh: Mesh) -> int:
    n = batch_shard_count(mesh)
    if global_batch % n != 0:
        raise ValueError(f"global batch {global_batch} not divisible by "
                         f"{n} batch shards")
    return global_batch // n
