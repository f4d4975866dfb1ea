"""ZeRO-1 sharded weight update (parallel/sharding.py rule table +
train/loop.py; arXiv:2004.13336).

The load-bearing claims, pinned on the virtual 8-device mesh:

  * the ZeRO-1 step is numerically allclose (f32 tolerance) to the
    replicated update on dp AND dp_fsdp — and the replicated (off) path
    is the untouched exactness oracle;
  * the optimizer state is ACTUALLY sharded: per-replica optimizer bytes
    shrink by exactly (N-1)/N for the shardable leaves, measured from
    the live state's shard shapes;
  * the regex→PartitionSpec rule table (match_partition_rules) resolves
    moment tensors sharded, bookkeeping scalars replicated, and a PARAM
    named like a bookkeeping attr ("scale") is NOT swallowed by the
    attr rule;
  * the resolver refuses unsupported combinations loudly and resolves
    off (with a warning) for single-shard checkpoint consumers.
"""
import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from distributed_resnet_tensorflow_tpu.parallel import create_mesh
from distributed_resnet_tensorflow_tpu.parallel.sharding import (
    ZERO1_MIN_SIZE, Zero1Report, _SizesMesh, match_partition_rules,
    resolve_zero1, zero1_grad_specs, zero1_rules, zero1_stats,
    zero1_unsupported_reason)
from distributed_resnet_tensorflow_tpu.train import Trainer
from distributed_resnet_tensorflow_tpu.utils.config import (MeshConfig,
                                                            get_preset)


def _tiny_cfg(**kw):
    cfg = get_preset("smoke")
    cfg.model.compute_dtype = "float32"
    cfg.model.resnet_size = 8
    cfg.model.num_classes = 4
    cfg.data.image_size = 8
    cfg.train.batch_size = 16
    cfg.optimizer.schedule = "constant"
    cfg.checkpoint.save_every_secs = 0.0
    for k, v in kw.items():
        cfg.override(k, v)
    return cfg


def _fixed_batches(n=4, bs=16, size=8, classes=4):
    rng = np.random.RandomState(7)
    imgs = rng.randn(n, bs, size, size, 3).astype(np.float32)
    labs = rng.randint(0, classes, (n, bs)).astype(np.int32)
    return [{"images": imgs[i], "labels": labs[i]} for i in range(n)]


def _train(mesh_cfg, batches, **kw):
    cfg = _tiny_cfg(**kw)
    tr = Trainer(cfg, mesh=create_mesh(mesh_cfg))
    tr.init_state()
    state, metrics = tr.train(iter(list(batches)), num_steps=len(batches))
    flat = np.concatenate([np.asarray(l).ravel() for l in
                           jax.tree_util.tree_leaves(state.params)])
    return tr, state, flat, metrics


def _opt_bytes_per_replica(state):
    total = 0
    for leaf in jax.tree_util.tree_leaves(state.opt_state):
        if not hasattr(leaf, "sharding"):
            continue
        shard_shape = leaf.sharding.shard_shape(leaf.shape)
        total += int(np.prod(shard_shape, dtype=np.int64)) * \
            leaf.dtype.itemsize
    return total


# ---------------------------------------------------------------------------
# numerics (the acceptance claim)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh_cfg", [
    MeshConfig(data=8), MeshConfig(data=4, fsdp=2)], ids=["dp", "dp_fsdp"])
@pytest.mark.parametrize("opt", ["momentum", "lamb"])
def test_zero1_matches_replicated_update(mesh_cfg, opt):
    """ZeRO-1 on vs off after a few steps: allclose at f32 tolerance
    (the reduction trees differ — reduce-scatter + sharded norms vs the
    replicated update). The off path is byte-for-byte the pre-ZeRO step
    (no code touches it when the knob is off), so this doubles as the
    exactness-oracle check."""
    batches = _fixed_batches()
    kw = {"optimizer.name": opt}
    if opt == "lamb":
        kw["optimizer.weight_decay"] = "1e-4"
    _, _, off, m0 = _train(mesh_cfg, batches, **kw)
    tr, st, on, m1 = _train(mesh_cfg, batches, **kw,
                            **{"optimizer.zero1": "on",
                               "optimizer.zero1_min_size": "16"})
    assert tr.zero1_active
    np.testing.assert_allclose(on, off, rtol=2e-4, atol=2e-5)
    assert abs(float(m0["loss"]) - float(m1["loss"])) < 1e-4
    # ...and the state is genuinely sharded, not just relabeled
    sharded = [l for l in jax.tree_util.tree_leaves(st.opt_state)
               if hasattr(l, "sharding")
               and not l.sharding.is_fully_replicated]
    assert sharded, "zero1=on left every optimizer leaf replicated"


@pytest.mark.slow  # re-tiered out of the 870s tier-1 (ISSUE 13)
def test_zero1_memory_shrinks_by_n_minus_1_over_n(devices):
    """Per-replica optimizer bytes, measured from live shard shapes: the
    shardable leaves cost exactly 1/N per replica; the total matches the
    partition report's projection."""
    batches = _fixed_batches(n=1)
    _, st_off, _, _ = _train(MeshConfig(data=8), batches,
                             **{"optimizer.name": "lamb",
                                "optimizer.weight_decay": "1e-4"})
    tr, st_on, _, _ = _train(MeshConfig(data=8), batches,
                             **{"optimizer.name": "lamb",
                                "optimizer.weight_decay": "1e-4",
                                "optimizer.zero1": "on",
                                "optimizer.zero1_min_size": "16"})
    off_bytes = _opt_bytes_per_replica(st_off)
    on_bytes = _opt_bytes_per_replica(st_on)
    plan = zero1_stats.snapshot()
    assert plan["bytes_per_replica"] == on_bytes
    assert plan["bytes_per_replica_unsharded"] == off_bytes
    # shardable leaves shrink by exactly (N-1)/N
    assert plan["sharded_bytes"] > 0
    assert on_bytes == plan["replicated_bytes"] + \
        plan["sharded_bytes"] // 8
    # and they dominate this model, so the total shrinks hard too
    assert on_bytes < off_bytes / 4


# ---------------------------------------------------------------------------
# rule table
# ---------------------------------------------------------------------------

def test_match_partition_rules_first_match_wins_and_exhaustive():
    shapes = {"a": jax.ShapeDtypeStruct((8, 4), np.float32),
              "b": jax.ShapeDtypeStruct((3,), np.float32)}
    specs = match_partition_rules(
        ((r"a", P("data", None)), (r".*", P())), shapes)
    assert specs["a"] == P("data", None) and specs["b"] == P()
    with pytest.raises(ValueError, match="no partition rule"):
        match_partition_rules(((r"a", P()),), shapes)


def test_zero1_rules_classification():
    """Moment tensors shard on their largest free divisible dim;
    bookkeeping NamedTuple attrs (.count) replicate; a PARAM keyed
    "scale" (a dict key, not an attr) is NOT swallowed by the
    bookkeeping rule; non-divisible and small leaves fall back counted."""
    import optax
    p = {"w": np.zeros((128, 64), np.float32),
         "scale": np.zeros((256,), np.float32),       # param named scale
         "odd": np.zeros((129, 3), np.float32),       # nothing divides by 8
         "tiny": np.zeros((4,), np.float32)}
    state = jax.eval_shape(lambda: optax.lamb(0.01).init(p))
    report = Zero1Report(8)
    specs = match_partition_rules(
        zero1_rules(_SizesMesh({"data": 8}), min_size=16, report=report),
        state)
    adam = specs[0]
    assert adam.count == P()
    assert adam.mu["w"] == P("data", None)
    assert adam.mu["scale"] == P("data")
    assert adam.mu["odd"] == P()
    assert adam.mu["tiny"] == P()
    snap = report.snapshot()
    assert snap["reasons"]["sharded"] == 4          # w + scale, mu and nu
    assert snap["reasons"]["no-divisible-dim"] == 2  # odd, mu and nu
    assert snap["reasons"]["below-min-size"] == 2    # tiny, mu and nu
    assert snap["reasons"]["bookkeeping"] == 1      # .count
    assert snap["bytes_per_replica"] < snap["bytes_per_replica_unsharded"]


def test_zero1_grad_specs_agree_with_state_layout(mesh8):
    """The grads-tree specs (reduce-scatter targets) and the
    optimizer-state moment specs must name the same data dim per leaf —
    disagreement would reshard every step."""
    import optax
    p = {"w": np.zeros((128, 64), np.float32),
         "v": np.zeros((64, 32), np.float32)}
    gspecs = zero1_grad_specs(p, mesh8, min_size=16)
    state = jax.eval_shape(lambda: optax.sgd(0.1, momentum=0.9).init(p))
    sspecs = match_partition_rules(
        zero1_rules(mesh8, min_size=16), state)
    trace = sspecs[0].trace  # optax.sgd(momentum=...) chains TraceState
    assert gspecs["w"] == trace["w"]
    assert gspecs["v"] == trace["v"]


# ---------------------------------------------------------------------------
# resolver / envelope
# ---------------------------------------------------------------------------

def test_zero1_resolver_gates(devices):
    mesh = create_mesh(MeshConfig(data=8))
    assert resolve_zero1(_tiny_cfg(), mesh) is False            # default off
    assert resolve_zero1(
        _tiny_cfg(**{"optimizer.zero1": "on"}), mesh) is True
    # auto stays off single-process (the multi-host memory bind is the
    # target)
    assert resolve_zero1(
        _tiny_cfg(**{"optimizer.zero1": "auto"}), mesh) is False
    with pytest.raises(ValueError, match="unknown optimizer.zero1"):
        resolve_zero1(_tiny_cfg(**{"optimizer.zero1": "maybe"}), mesh)
    # a single-data-shard mesh is what checkpoint consumers see — a
    # forced train-only knob must resolve off loudly, not crash them
    single = create_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    assert resolve_zero1(
        _tiny_cfg(**{"optimizer.zero1": "on"}), single) is False
    # program-shaping axes are outside the envelope
    pp = create_mesh(MeshConfig(data=4, pipeline=2))
    assert zero1_unsupported_reason(
        _tiny_cfg(**{"optimizer.zero1": "on"}), pp) is not None
    with pytest.raises(ValueError, match="pipeline"):
        resolve_zero1(_tiny_cfg(**{"optimizer.zero1": "on"}), pp)


def test_lamb_and_warmup_poly_available():
    """The large-batch recipe pieces: LAMB builds + trains, warmup_poly
    warms linearly then decays polynomially to 0, and the new presets
    resolve end to end."""
    from distributed_resnet_tensorflow_tpu.train.schedules import (
        create_schedule, linear_scaled_lr, warmup_poly)
    sched = warmup_poly(warmup_steps=10, peak=2.0, total_steps=110)
    assert float(sched(0)) == 0.0
    np.testing.assert_allclose(float(sched(5)), 1.0, rtol=1e-6)
    np.testing.assert_allclose(float(sched(10)), 2.0, rtol=1e-6)
    assert float(sched(60)) < 2.0
    np.testing.assert_allclose(float(sched(110)), 0.0, atol=1e-7)
    assert linear_scaled_lr(0.1, 4096) == pytest.approx(1.6)
    for preset in ("imagenet_resnet50_lars4k", "imagenet_resnet50_lamb4k"):
        cfg = get_preset(preset)
        assert cfg.optimizer.zero1 == "on"
        assert cfg.optimizer.warmup_steps > 0
        create_schedule(cfg.optimizer)  # resolves without error


def test_zero1_event_row(tmp_path, devices):
    from distributed_resnet_tensorflow_tpu.train.hooks import Zero1Hook
    from distributed_resnet_tensorflow_tpu.utils.metrics import (
        MetricsWriter, read_metrics)
    zero1_stats.reset()
    batches = _fixed_batches(n=2)
    cfg = _tiny_cfg(**{"optimizer.zero1": "on",
                       "optimizer.zero1_min_size": "16"})
    tr = Trainer(cfg, mesh=create_mesh(MeshConfig(data=8)))
    assert tr.zero1_active
    tr.init_state()
    w = MetricsWriter(str(tmp_path), enable_tensorboard=False)
    hook = Zero1Hook(w, every_steps=1)
    tr.train(iter(batches), num_steps=2, hooks=(hook,))
    w.close()
    rows = [r for r in read_metrics(str(tmp_path))
            if r.get("event") == "zero1"]
    assert len(rows) == 1  # one row per resolved plan, not per step
    row = rows[0]
    assert row["data_shards"] == 8
    assert row["sharded_leaves"] > 0
    assert row["bytes_per_replica"] < row["bytes_per_replica_unsharded"]
