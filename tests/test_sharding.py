"""Mesh/sharding tests on the fake 8-device mesh — the distributed layer
that replaces the reference's grpc PS and Horovod backends (SURVEY.md
§2.8-2.9). Verifies the sharded step equals the single-device step: sync
data parallelism by construction (what SyncReplicasOptimizer promised)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_resnet_tensorflow_tpu.parallel import (
    batch_shard_count, create_mesh, data_sharding, local_batch_size,
    param_sharding_rule, resolve_axis_sizes, shard_batch,
    tree_param_shardings)
from distributed_resnet_tensorflow_tpu.utils.config import MeshConfig, get_preset


def test_resolve_axis_sizes():
    sizes = resolve_axis_sizes(MeshConfig(data=-1), 8)
    assert sizes == (1, 8, 1, 1, 1, 1)
    sizes = resolve_axis_sizes(MeshConfig(data=4, fsdp=2), 8)
    assert sizes == (1, 4, 2, 1, 1, 1)
    with pytest.raises(ValueError):
        resolve_axis_sizes(MeshConfig(data=3), 8)
    with pytest.raises(ValueError):
        resolve_axis_sizes(MeshConfig(data=-1, fsdp=-1), 8)


def test_create_mesh_dp(mesh8):
    assert mesh8.shape["data"] == 8
    assert batch_shard_count(mesh8) == 8
    assert local_batch_size(64, mesh8) == 8
    with pytest.raises(ValueError):
        local_batch_size(10, mesh8)


def test_tensor_dropback_warns_once(caplog):
    """An indivisible tensor split silently replicating the leaf's FLOPs
    must be loud (once per leaf shape): the sharding rule logs the
    drop-back for plain encoder kernels AND MoE expert leaves."""
    import logging
    from distributed_resnet_tensorflow_tpu.parallel import sharding as sh
    mesh = create_mesh(MeshConfig(data=4, tensor=2))
    sh._TENSOR_DROPBACK_WARNED.clear()
    with caplog.at_level(logging.WARNING):
        spec = param_sharding_rule(
            "['EncoderBlock_0']['Dense_0']['kernel']", (32, 33), mesh)
        assert spec == P()  # dropped back to replication (33 % 2 != 0)
        spec = param_sharding_rule(
            "['EncoderBlock_0']['SwitchMlp_0']['w1']", (4, 32, 33), mesh)
        assert "tensor" not in tuple(spec)
        # repeat: warned once per distinct leaf shape
        param_sharding_rule(
            "['EncoderBlock_0']['Dense_0']['kernel']", (32, 33), mesh)
    msgs = [r for r in caplog.records if "REPLICATE" in r.getMessage()]
    assert len(msgs) == 2
    # divisible shapes stay silent and sharded
    assert param_sharding_rule(
        "['EncoderBlock_0']['Dense_0']['kernel']", (32, 64), mesh) \
        == P(None, "tensor")


def test_shard_batch_places_on_batch_axis(mesh8):
    batch = {"images": np.zeros((16, 8, 8, 3), np.float32),
             "labels": np.zeros((16,), np.int32)}
    out = shard_batch(batch, mesh8)
    assert out["images"].sharding.is_equivalent_to(
        data_sharding(mesh8), ndim=4)
    # each device holds 16/8=2 rows
    shard = out["images"].addressable_shards[0]
    assert shard.data.shape == (2, 8, 8, 3)


def test_param_sharding_rule(mesh_dp_fsdp):
    # small param → replicated
    assert param_sharding_rule("bn/scale", (64,), mesh_dp_fsdp) == P()
    # big matrix → sharded over fsdp on a divisible dim
    spec = param_sharding_rule("dense/kernel", (512, 1024), mesh_dp_fsdp)
    assert "fsdp" in spec
    # indivisible dims stay replicated
    assert param_sharding_rule("odd", (513, 1023), mesh_dp_fsdp) == P()


def _oracle_cfg(family: str, accum: int):
    """The smallest model of a family that still has every kind of leaf
    its sharding rules name, float32, one optimizer step of 16 examples.
    The ResNets are wide enough and the ViTs deep enough in the MLP that
    one kernel crosses the fsdp rule's 2**16-element floor."""
    cfg = get_preset("smoke")
    cfg.model.compute_dtype = "float32"
    cfg.model.num_classes = 4
    cfg.data.image_size = 8
    cfg.train.batch_size = 16
    cfg.train.grad_accum_steps = accum
    cfg.optimizer.schedule = "constant"
    cfg.optimizer.learning_rate = 0.05
    if family in ("resnet_bn", "resnet_gn"):
        cfg.model.resnet_size = 8
        cfg.model.width_multiplier = 4
        if family == "resnet_gn":
            cfg.model.norm = "group"
            cfg.model.gn_groups = 4
    elif family == "logistic":
        cfg.model.name = "logistic"
        cfg.model.input_size = 8 * 8 * 3
        cfg.model.hidden_units = 32
    else:
        cfg.model.name = "vit"
        cfg.model.vit_patch_size = 4
        cfg.model.vit_dim = 128
        cfg.model.vit_depth = 2
        cfg.model.vit_heads = 2
        cfg.optimizer.name = "adam"
        cfg.optimizer.learning_rate = 1e-3
        cfg.optimizer.weight_decay = 0.0
        if family == "vit_moe":
            cfg.model.vit_num_experts = 2
            # room for every token: the dispatch on an expert axis drops by
            # device-local group, one device by the whole batch, and only a
            # run without drops is the same function of the batch
            cfg.model.vit_expert_capacity_factor = 4.0
    return cfg


_ORACLE_LAYOUTS = {
    "dp": dict(data=8),
    "dp_fsdp": dict(data=4, fsdp=2),
    "dp_tp": dict(data=4, tensor=2),
    "dp_pp": dict(data=2, pipeline=2),
    "dp_pp_ep": dict(data=2, pipeline=2, expert=2),
    "dp_ep": dict(data=4, expert=2),
    "dp_sp": dict(data=4, sequence=2),
    "dp_tp_pp": dict(data=2, tensor=2, pipeline=2),
}


def _oracle_batch(cfg):
    rng = np.random.RandomState(11)
    shape = (16, cfg.model.input_size) if cfg.model.name == "logistic" \
        else (16, 8, 8, 3)
    return {"images": rng.randn(*shape).astype(np.float32),
            "labels": rng.randint(0, 4, (16,)).astype(np.int32)}


def _one_step(cfg, mesh, params=None):
    """One optimizer step through ``jitted_train_step`` on ``mesh``:
    (loss, params before, params after). ``params`` replaces the freshly
    drawn ones (the optimizer state of step 0 holds no trace of them)."""
    from distributed_resnet_tensorflow_tpu.train import Trainer
    tr = Trainer(cfg, mesh=mesh)
    tr.init_state(seed=0)
    named = set()
    for leaf in jax.tree_util.tree_leaves(tr.state.params):
        for entry in leaf.sharding.spec:
            named.update(entry if isinstance(entry, tuple) else (entry,))
    # every axis of the layout but `data` shards some parameter: the
    # comparison is of the layout the case names, not of replicas
    assert {a for a, n in mesh.shape.items() if n > 1} - {"data", "seq"} \
        <= named  # (a sequence axis shards activations alone)
    if params is not None:
        placed = jax.tree_util.tree_map(
            lambda new, old: jax.device_put(np.asarray(new), old.sharding),
            params, tr.state.params)
        tr.state = tr.state.replace(params=placed)
    before = jax.tree_util.tree_map(np.asarray, tr.state.params)
    state, m = tr.jitted_train_step()(
        tr.state, shard_batch(_oracle_batch(cfg), tr.mesh))
    return float(m["loss"]), before, \
        jax.tree_util.tree_map(np.asarray, state.params)


@functools.lru_cache(maxsize=None)
def _single_device_step(family, accum, moe_aux_weight):
    """The oracle's side, once for the layouts that share it."""
    cfg = _oracle_cfg(family, accum)
    cfg.model.moe_aux_weight = moe_aux_weight
    return _one_step(cfg, create_mesh(MeshConfig(data=1),
                                      devices=jax.devices()[:1]))


@pytest.mark.parametrize("family,layout,accum", [
    ("resnet_bn", "dp", 1), ("resnet_bn", "dp", 2),
    ("resnet_bn", "dp_fsdp", 1), ("resnet_bn", "dp_fsdp", 2),
    ("resnet_gn", "dp", 1), ("resnet_gn", "dp", 2),
    ("resnet_gn", "dp_fsdp", 1),
    ("logistic", "dp", 1), ("logistic", "dp", 2),
    ("vit", "dp", 1), ("vit", "dp", 2),
    ("vit", "dp_fsdp", 1), ("vit", "dp_fsdp", 2),
    ("vit", "dp_tp", 1), ("vit", "dp_tp", 2),
    ("vit", "dp_pp", 1), ("vit", "dp_pp", 2),
    # layouts the bucketed step's envelope refused, so that nothing ever
    # compared them with anything: a sequence axis (ring attention),
    # tensor beside pipeline, an expert axis without a pipeline
    ("vit", "dp_sp", 1), ("vit", "dp_sp", 2),
    ("vit", "dp_tp_pp", 1), ("vit", "dp_tp_pp", 2),
    ("vit_moe", "dp", 1),
    ("vit_moe", "dp_tp", 1), ("vit_moe", "dp_tp", 2),
    ("vit_moe", "dp_pp_ep", 1), ("vit_moe", "dp_pp_ep", 2),
    ("vit_moe", "dp_ep", 1), ("vit_moe", "dp_ep", 2),
], ids=lambda v: str(v))
def test_sharded_step_matches_single_device(devices, family, layout, accum):
    """The crux: one training step on a mesh == the same step on one device
    (sync data parallelism by construction; the reference could only
    approximate it through SyncReplicasOptimizer's token machinery,
    reference resnet_model.py:102-135). Every family, over every layout its
    sharding rules serve, with and without gradient accumulation: the
    loss to float rounding, the updated parameters up to the
    reassociation of the gradient sum (eight partial sums reduce in
    another order than one device adds)."""
    axes = _ORACLE_LAYOUTS[layout]
    count = int(np.prod(list(axes.values())))
    mesh = create_mesh(MeshConfig(**axes), devices=jax.devices()[:count])
    cfg = _oracle_cfg(family, accum)
    if "pipeline" in axes:
        cfg.model.vit_pipeline_microbatches = 2
        if family == "vit_moe":
            # a pipelined stage balances its experts over one microbatch
            # (models/pipeline.py), one device over the whole batch: a
            # different auxiliary loss by design, so it is left out here
            cfg.model.moe_aux_weight = 0.0
    loss1, before1, after1 = _single_device_step(
        family, accum, cfg.model.moe_aux_weight)
    if "pipeline" in axes:
        # the pipelined encoder stacks its blocks' parameters
        from distributed_resnet_tensorflow_tpu.models.pipeline import (
            pack_encoder_params)

        def packed(tree):
            out = {k: v for k, v in tree.items()
                   if not k.startswith("EncoderBlock_")}
            out["encoder"] = jax.tree_util.tree_map(
                np.asarray,
                pack_encoder_params(tree, cfg.model.vit_depth))
            return out

        before1, after1 = packed(before1), packed(after1)
    loss_n, _, after_n = _one_step(cfg, mesh, params=before1)
    assert np.isclose(loss1, loss_n, rtol=1e-5), (loss1, loss_n)
    flat1 = jax.tree_util.tree_leaves_with_path(after1)
    flat_n = jax.tree_util.tree_leaves_with_path(after_n)
    assert [p for p, _ in flat1] == [p for p, _ in flat_n]
    moved = 0.0
    for (path, a), (_, b), start in zip(
            flat1, flat_n, jax.tree_util.tree_leaves(before1)):
        np.testing.assert_allclose(b, a, rtol=1e-3, atol=5e-4,
                                   err_msg=jax.tree_util.keystr(path))
        moved = max(moved, float(np.abs(a - start).max()))
    assert moved > 1e-4  # the step updated something to compare


@pytest.mark.heavy
def test_fsdp_state_sharding(mesh_dp_fsdp):
    """Params/opt state shard over fsdp (ZeRO) — the capability replacing
    ps-side variable placement (reference resnet_cifar_main.py:392-396)."""
    from distributed_resnet_tensorflow_tpu.train import Trainer
    cfg = get_preset("smoke")
    cfg.model.compute_dtype = "float32"
    cfg.model.resnet_size = 8
    cfg.model.width_multiplier = 4   # big enough convs to cross the fsdp size threshold
    cfg.data.image_size = 32
    cfg.mesh = MeshConfig(data=4, fsdp=2)
    tr = Trainer(cfg, mesh=mesh_dp_fsdp)
    state = tr.init_state()
    shardings = [l.sharding for l in jax.tree_util.tree_leaves(state.params)]
    # at least one large leaf actually sharded over fsdp
    assert any("fsdp" in (s.spec[i] or "")
               for s in shardings if s.spec
               for i in range(len(s.spec)) if s.spec[i]), \
        "no parameter sharded over fsdp"
    # and the sharded train step still runs
    from distributed_resnet_tensorflow_tpu.data import synthetic_iterator
    it = synthetic_iterator(16, 32, 10)
    state, m = tr.train(it, num_steps=1)
    assert np.isfinite(float(m["loss"]))


def _stager_batch(rng):
    return {"images": rng.randint(0, 256, (16, 8, 8, 3)).astype(np.uint8),
            "labels": rng.randint(0, 10, (16,)).astype(np.int64),
            "mask": np.ones((16,), np.float32)}


def test_coalesced_stager_matches_shard_batch(mesh8, rng):
    """The coalesced single-transfer path must be value-, dtype- and
    sharding-identical to per-leaf shard_batch — including the int64→int32
    label narrowing both paths apply before the host→device hop."""
    from distributed_resnet_tensorflow_tpu.parallel.sharding import (
        CoalescedStager)
    st = CoalescedStager(mesh8, stacked=False, ring=3)
    batch = _stager_batch(rng)
    out, ref = st.put_now(batch), shard_batch(batch, mesh8)
    for k in batch:
        assert out[k].dtype == ref[k].dtype, k
        assert out[k].sharding == ref[k].sharding, k
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(ref[k]))
    assert out["labels"].dtype == np.int32  # int64 halved on the wire
    # ring reuse: many puts through the same layout stay correct
    for _ in range(6):
        b = _stager_batch(rng)
        o = st.put_now(b)
        np.testing.assert_array_equal(np.asarray(o["images"]), b["images"])
    # a second spec (no mask) builds its own layout on the fly
    b2 = {k: v for k, v in _stager_batch(rng).items() if k != "mask"}
    o2 = st.put_now(b2)
    np.testing.assert_array_equal(np.asarray(o2["images"]), b2["images"])


def test_coalesced_stager_stacked_and_fsdp(mesh8, mesh_dp_fsdp, rng):
    from distributed_resnet_tensorflow_tpu.parallel.sharding import (
        CoalescedStager, shard_stacked_batch)
    sb = {"images": rng.randint(0, 256, (3, 16, 8, 8, 3)).astype(np.uint8),
          "labels": rng.randint(0, 10, (3, 16)).astype(np.int64)}
    for mesh in (mesh8, mesh_dp_fsdp):
        st = CoalescedStager(mesh, stacked=True, ring=3)
        out, ref = st.put_now(sb), shard_stacked_batch(sb, mesh)
        for k in sb:
            assert out[k].dtype == ref[k].dtype
            assert out[k].sharding == ref[k].sharding, (k, mesh.shape)
            np.testing.assert_array_equal(np.asarray(out[k]),
                                          np.asarray(ref[k]))


def test_coalesced_stager_replicated_nonbatch_axis(rng):
    """tensor>1 mesh: several devices hold the SAME batch shard; each must
    receive its own copy of the shard's staging region."""
    from distributed_resnet_tensorflow_tpu.parallel.sharding import (
        CoalescedStager)
    mesh = create_mesh(MeshConfig(data=4, tensor=2))
    st = CoalescedStager(mesh)
    batch = _stager_batch(rng)
    out, ref = st.put_now(batch), shard_batch(batch, mesh)
    for k in batch:
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(ref[k]))
        assert out[k].sharding == ref[k].sharding


def test_put_paths_coerce_label_dtype(mesh8):
    """Labels must cross host→device as int32 on EVERY put path (the
    satellite audit): int64 labels (platform-default numpy) are narrowed by
    shard_batch / shard_stacked_batch / the stager alike."""
    from distributed_resnet_tensorflow_tpu.parallel.sharding import (
        shard_stacked_batch)
    flat = {"images": np.zeros((8, 4, 4, 3), np.uint8),
            "labels": np.arange(8)}                      # int64 by default
    assert flat["labels"].dtype == np.int64
    assert shard_batch(flat, mesh8)["labels"].dtype == np.int32
    stacked = {"images": np.zeros((2, 8, 4, 4, 3), np.uint8),
               "labels": np.zeros((2, 8), np.int64)}
    assert shard_stacked_batch(stacked, mesh8)["labels"].dtype == np.int32
    # float64 narrows too (an accidental float mask would double its bytes)
    m = shard_batch({"images": np.zeros((8, 2), np.float64),
                     "labels": np.zeros((8,), np.int32)}, mesh8)
    assert m["images"].dtype == np.float32
