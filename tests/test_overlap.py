"""Bucketed gradient-communication overlap (parallel/overlap.py).

The load-bearing claims, each pinned here on the virtual 8-device mesh:
bucketed and unbucketed (single-bucket) exchanges are BIT-IDENTICAL
(same per-leaf all-reduce over the same operands — the bucketing
transformation must be a pure scheduling change), the overlap path
agrees with the default XLA-propagation step to float rounding across
dp AND dp_fsdp, the envelope resolver refuses unsupported combinations
loudly, and the plan telemetry (comm_overlap event) exports what the
compiled step actually does.
"""
import numpy as np
import pytest

import jax

from distributed_resnet_tensorflow_tpu.parallel import create_mesh
from distributed_resnet_tensorflow_tpu.parallel import overlap as overlap_mod
from distributed_resnet_tensorflow_tpu.parallel.overlap import (
    EXCHANGE_COMPILER_OPTIONS, exchange_compiler_options, overlap_stats,
    overlap_unsupported_reason, plan_buckets, resolve_overlap)
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


def _flat_params(state):
    return np.concatenate([np.asarray(l).ravel() for l in
                           jax.tree_util.tree_leaves(state.params)])


def _train(mesh_cfg, batches, **kw):
    cfg = _tiny_cfg(**kw)
    tr = Trainer(cfg, mesh=create_mesh(mesh_cfg))
    tr.init_state()
    state, metrics = tr.train(iter(list(batches)), num_steps=len(batches))
    return _flat_params(state), metrics


# ---------------------------------------------------------------------------
# exactness (the acceptance claim)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh_cfg", [
    MeshConfig(data=8),                    # dp
    # dp_fsdp re-tiered out of the 870s tier-1 (ISSUE 20, ~10s: two full
    # trainings on the sharded layout); the dp leg keeps the bucketing
    # bit-identity claim in tier-1 and the dp_fsdp LAYOUT stays covered
    # by test_zero1_overlap_matches_plain_path[dp_fsdp]; the full
    # (unfiltered) suite runs both
    pytest.param(MeshConfig(data=4, fsdp=2), marks=pytest.mark.slow),
], ids=["dp", "dp_fsdp"])
def test_bucketed_is_bit_identical_to_unbucketed(mesh_cfg):
    """Many tiny buckets vs one bucket holding everything: the per-leaf
    psum operands are identical either way, so the trained params must be
    BITWISE equal — bucketing may only change collective scheduling,
    never numerics."""
    batches = _fixed_batches()
    many, m1 = _train(mesh_cfg, batches,
                      **{"comm.overlap": "on", "comm.bucket_mb": "0.05"})
    plan = overlap_stats.snapshot()
    assert plan is not None and plan["buckets"] > 1, plan
    one, m2 = _train(mesh_cfg, batches,
                     **{"comm.overlap": "on", "comm.bucket_mb": "4096"})
    assert overlap_stats.snapshot()["buckets"] == 1
    np.testing.assert_array_equal(many, one)
    assert float(m1["loss"]) == float(m2["loss"])


@pytest.mark.parametrize("mesh_cfg", [
    MeshConfig(data=8),
    # dp_fsdp re-tiered out of the 870s tier-1 (ISSUE 19, ~13s: two full
    # trainings on the sharded layout); the dp leg keeps the
    # overlap-vs-default allclose claim in tier-1 and
    # test_bucketed_is_bit_identical_to_unbucketed[dp_fsdp] keeps the
    # fsdp layout pinned — the full (unfiltered) suite runs the cross
    pytest.param(MeshConfig(data=4, fsdp=2), marks=pytest.mark.slow),
], ids=["dp", "dp_fsdp"])
def test_overlap_matches_default_path_to_float_rounding(mesh_cfg):
    """Against the default XLA-propagation exchange the reduction TREE
    differs (local-sum-then-psum vs XLA's schedule), so agreement is to
    float rounding, not bitwise — a few steps of a float32 model stay
    within a tight allclose."""
    batches = _fixed_batches()
    base, mb = _train(mesh_cfg, batches, **{"comm.overlap": "off"})
    over, mo = _train(mesh_cfg, batches, **{"comm.overlap": "on",
                                            "comm.bucket_mb": "0.1"})
    np.testing.assert_allclose(over, base, rtol=2e-4, atol=2e-5)
    assert abs(float(mo["loss"]) - float(mb["loss"])) < 1e-4


# re-tiered out of the 870s tier-1 (ISSUE 17, ~13s). Overlap×fused
# multi-step composition: each side stays pinned in tier-1 on its own
# (test_overlap_matches_default_path_to_float_rounding, the fused
# multi-step tests in test_train), the full (unfiltered) suite runs
# the cross.
@pytest.mark.slow
def test_overlap_composes_with_fused_multi_step(devices):
    """steps_per_loop > 1 wraps the shard_map'd step in lax.scan — the
    fused dispatch must produce the same params as the unfused loop."""
    batches = _fixed_batches(n=4)
    stacked_equal, _ = _train(MeshConfig(data=8), batches,
                              **{"comm.overlap": "on",
                                 "comm.bucket_mb": "0.05",
                                 "train.steps_per_loop": "2"})
    unfused, _ = _train(MeshConfig(data=8), batches,
                        **{"comm.overlap": "on", "comm.bucket_mb": "0.05"})
    np.testing.assert_allclose(stacked_equal, unfused, rtol=1e-5,
                               atol=1e-6)


# ---------------------------------------------------------------------------
# gradient accumulation inside the exchange body
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh_cfg", [
    MeshConfig(data=8),
    # dp_fsdp re-tiered out of the 870s tier-1 (~16s: two accumulated
    # trainings on the sharded layout); the dp leg keeps the bit-identity
    # claim in tier-1, the full (unfiltered) suite runs both
    pytest.param(MeshConfig(data=4, fsdp=2), marks=pytest.mark.slow),
], ids=["dp", "dp_fsdp"])
def test_accum_bucketed_is_bit_identical_and_wire_is_1x(mesh_cfg):
    """The acceptance claim for the accumulation scan: many-vs-one-bucket
    accumulated exchanges are BITWISE equal (bucketing stays a pure
    scheduling change with the scan inside the body), and the recorded
    per-step wire bytes equal the gradient bytes ONCE — 1/accum of what
    a per-microbatch exchange would move."""
    batches = _fixed_batches()
    kw = {"comm.overlap": "on", "train.grad_accum_steps": "2"}
    many, m1 = _train(mesh_cfg, batches, **{"comm.bucket_mb": "0.05", **kw})
    plan = overlap_stats.snapshot()
    assert plan["buckets"] > 1 and plan["accum_steps"] == 2
    assert plan["wire_bytes"] == plan["grad_bytes"]  # ONE exchange/step
    one, m2 = _train(mesh_cfg, batches, **{"comm.bucket_mb": "4096", **kw})
    assert overlap_stats.snapshot()["buckets"] == 1
    np.testing.assert_array_equal(many, one)
    assert float(m1["loss"]) == float(m2["loss"])


# re-tiered out of the 870s tier-1 (ISSUE 17, ~16s: a second accum
# exactness oracle). The accumulation contract stays pinned in tier-1
# by test_accum_bucketed_is_bit_identical_and_wire_is_1x[dp] (bit
# identity + wire accounting); the full (unfiltered) suite re-runs it
# against this composition-matched jit oracle too.
@pytest.mark.slow
def test_accum_matches_composition_matched_jit_oracle(devices):
    """The accumulated exchange vs the plain jit accumulation scan. The
    body slices microbatches PER SHARD (each shard's local batch splits
    into accum slices — no cross-shard reshard), while the jit scan
    slices the global batch contiguously; permuting the oracle's batch to
    the body's composition makes the two runs the same math: loss/ce
    agree to float equality, params to float rounding (the accumulation
    summation orders differ)."""
    shards, bs, accum = 8, 16, 2
    lb = bs // shards
    mbl = lb // accum
    perm = np.array([k * lb + m * mbl + j
                     for m in range(accum)
                     for k in range(shards)
                     for j in range(mbl)])
    batches = _fixed_batches()
    permuted = [{"images": b["images"][perm], "labels": b["labels"][perm]}
                for b in batches]
    over, mo = _train(MeshConfig(data=8), batches,
                      **{"comm.overlap": "on", "comm.bucket_mb": "0.05",
                         "train.grad_accum_steps": "2"})
    cfg = _tiny_cfg(**{"comm.overlap": "off", "train.grad_accum_steps": "2"})
    tr = Trainer(cfg, mesh=create_mesh(MeshConfig(data=8)))
    tr.init_state()
    state, mj = tr.train(iter(permuted), num_steps=len(permuted))
    base = _flat_params(state)
    assert abs(float(mo["loss"]) - float(mj["loss"])) < 1e-6
    assert abs(float(mo["cross_entropy"]) - float(mj["cross_entropy"])) \
        < 1e-6
    np.testing.assert_allclose(over, base, rtol=2e-3, atol=2e-5)


# ---------------------------------------------------------------------------
# transformer-family legs (the layout-aware exchange)
# ---------------------------------------------------------------------------

def _vit_cfg(experts=0, **kw):
    cfg = _tiny_cfg()
    cfg.model.name = "vit"
    cfg.model.vit_patch_size = 4
    cfg.model.vit_dim = 16
    cfg.model.vit_depth = 4
    cfg.model.vit_heads = 2
    cfg.model.vit_num_experts = experts
    cfg.optimizer.name = "adam"
    cfg.optimizer.learning_rate = 1e-3
    cfg.optimizer.weight_decay = 0.0
    for k, v in kw.items():
        cfg.override(k, v)
    return cfg


def _mesh_subset(mesh_cfg):
    import math
    n = math.prod(max(1, s) for s in (
        mesh_cfg.data, mesh_cfg.fsdp, mesh_cfg.tensor, mesh_cfg.pipeline,
        mesh_cfg.sequence, mesh_cfg.expert))
    return create_mesh(mesh_cfg, devices=jax.devices()[:n])


@pytest.mark.parametrize("mesh_cfg,experts,expect_axes", [
    # dp_tp re-tiered out of the 870s tier-1 (~16s: ViT leg pair on the
    # tensor-sharded layout); dp_pp and dp_pp_ep keep the multi-axis
    # overlap claim in tier-1, the full (unfiltered) suite runs all three
    pytest.param(MeshConfig(data=4, tensor=2), 0, {"data+fsdp"},
                 marks=pytest.mark.slow),
    (MeshConfig(data=2, pipeline=2), 0,
     {"data+fsdp", "data+fsdp+pipeline"}),
    # dp_pp_ep legs-match re-tiered out of tier-1 too (ISSUE 17, ~16s):
    # the dp_pp_ep layout keeps its tier-1 pin via
    # test_vit_overlap_bucketing_bit_identical_dp_pp_ep (the stronger
    # bit-identity claim); the full suite runs the allclose leg pair
    pytest.param(MeshConfig(data=2, pipeline=2, expert=2), 2,
                 {"data+fsdp", "data+fsdp+expert",
                  "data+fsdp+pipeline+expert"},
                 marks=pytest.mark.slow),
], ids=["dp_tp", "dp_pp", "dp_pp_ep"])
def test_vit_overlap_legs_match_default_path(mesh_cfg, experts,
                                             expect_axes):
    """The transformer legs of the universal envelope: the layout-aware
    exchange (partial-auto tensor / inline pipeline / per-expert-group
    buckets) must agree with the XLA-propagation step to float rounding,
    and the plan's per-bucket reduce-axis sets must be exactly the
    layout's expected partition of the leaves."""
    mesh = _mesh_subset(mesh_cfg)

    def run(overlap):
        cfg = _vit_cfg(experts=experts,
                       **{"comm.overlap": overlap,
                          "comm.bucket_mb": "0.01"})
        tr = Trainer(cfg, mesh=mesh)
        tr.init_state()
        state, metrics = tr.train(iter(_fixed_batches()), num_steps=4)
        return _flat_params(state), metrics

    base, mb = run("off")
    over, mo = run("on")
    plan = overlap_stats.snapshot()
    assert set(plan["bucket_reduce_axes"]) == expect_axes, plan
    np.testing.assert_allclose(over, base, rtol=5e-3, atol=5e-5)
    assert abs(float(mo["loss"]) - float(mb["loss"])) < 5e-4


@pytest.mark.slow  # re-tiered out of the 870s tier-1 (ISSUE 20, ~13s:
# two 4-step MoE-pipeline trainings); tier-1 keeps the same bit-identity
# claim via test_bucketed_is_bit_identical_to_unbucketed[dp] and the
# same dp_pp_ep-family layout through the overlap path via
# test_vit_overlap_legs_match_default_path[dp_pp]; the full (unfiltered)
# suite runs this grouped-bucket composition
def test_vit_overlap_bucketing_bit_identical_dp_pp_ep(devices):
    """Many-vs-one-bucket on the MoE pipeline layout: grouped buckets
    (one reduce-axis set each) are still a pure scheduling change."""
    mesh = _mesh_subset(MeshConfig(data=2, pipeline=2, expert=2))

    def run(bucket_mb):
        cfg = _vit_cfg(experts=2, **{"comm.overlap": "on",
                                     "comm.bucket_mb": bucket_mb})
        tr = Trainer(cfg, mesh=mesh)
        tr.init_state()
        state, _ = tr.train(iter(_fixed_batches(n=2)), num_steps=2)
        return _flat_params(state)

    many = run("0.01")
    assert overlap_stats.snapshot()["buckets"] > 3
    one = run("4096")
    # one bucket PER reduce-axis set is the floor — never fewer
    assert overlap_stats.snapshot()["buckets"] == 3
    np.testing.assert_array_equal(many, one)


# ---------------------------------------------------------------------------
# bucket planning
# ---------------------------------------------------------------------------

def test_plan_buckets_reverse_order_and_cap():
    # leaves of 3,3,3,3 bytes with a 6-byte cap: reverse-order pairs
    assert plan_buckets([3, 3, 3, 3], 6) == [[3, 2], [1, 0]]
    # an oversized leaf gets its own bucket, never split
    assert plan_buckets([100, 1, 1], 8) == [[2, 1], [0]]
    # everything fits: one bucket, still reverse order
    assert plan_buckets([1, 2, 3], 100) == [[2, 1, 0]]
    assert plan_buckets([], 8) == []


def test_plan_buckets_grouped():
    from distributed_resnet_tensorflow_tpu.parallel.overlap import (
        plan_buckets_grouped)
    A, B = ("data", "fsdp"), ("data", "fsdp", "expert")
    # one group degenerates to plan_buckets (same buckets, same order)
    assert plan_buckets_grouped([3, 3, 3, 3], [A] * 4, 6) == \
        [(A, [3, 2]), (A, [1, 0])]
    # mixed signatures never share a bucket, even under the byte cap;
    # issue order follows the reversed position of each bucket's first
    # leaf (backprop availability)
    assert plan_buckets_grouped([3, 3, 3, 3], [A, B, A, B], 100) == \
        [(B, [3, 1]), (A, [2, 0])]
    # per-group caps still apply
    assert plan_buckets_grouped([3, 3, 3, 3], [A, B, A, B], 3) == \
        [(B, [3]), (A, [2]), (B, [1]), (A, [0])]
    assert plan_buckets_grouped([], [], 8) == []


# ---------------------------------------------------------------------------
# envelope / resolver
# ---------------------------------------------------------------------------

def test_resolver_gates(devices):
    mesh = create_mesh(MeshConfig(data=8))
    # off → None regardless of support
    assert resolve_overlap(_tiny_cfg(**{"comm.overlap": "off"}), mesh) is None
    # auto on a single-process run stays off (the DCN path is the target;
    # test_auto_rule walks the whole rule, backend included)
    assert resolve_overlap(_tiny_cfg(), mesh) is None
    # on → forced
    plan = resolve_overlap(_tiny_cfg(**{"comm.overlap": "on"}), mesh)
    assert plan is not None and plan.bucket_bytes == 4 * 2 ** 20

    # gradient accumulation is IN-envelope now (the body owns the scan);
    # the resolver only checks the microbatch divisibility
    accum = _tiny_cfg(**{"comm.overlap": "on",
                         "train.grad_accum_steps": "2"})
    assert overlap_unsupported_reason(accum, mesh) is None
    assert resolve_overlap(accum, mesh) is not None

    # unsupported combinations raise WITH the reason under "on"
    for kw, needle in [
        ({"model.cross_replica_bn": "false"}, "cross_replica_bn"),
        ({"train.batch_size": "12"}, "does not divide"),
        # 16 divides 8 shards but not 8 shards × 3 microbatches
        ({"train.grad_accum_steps": "3"}, "microbatches"),
    ]:
        bad = _tiny_cfg(**{"comm.overlap": "on", **kw})
        assert overlap_unsupported_reason(bad, mesh) is not None
        with pytest.raises(ValueError, match=needle):
            resolve_overlap(bad, mesh)
        # ...and quietly resolve off under "auto"
        bad.comm.overlap = "auto"
        assert resolve_overlap(bad, mesh) is None

    # the transformer family is in-envelope on batch/tensor/pipeline
    # meshes now; the remaining refusals are the nesting-shard_map axes,
    # each with its precise reason
    vit = _tiny_cfg(**{"comm.overlap": "on"})
    vit.model.name = "vit"
    assert overlap_unsupported_reason(vit, mesh) is None
    seq_mesh = create_mesh(MeshConfig(data=4, sequence=2))
    assert "seq" in overlap_unsupported_reason(vit, seq_mesh)
    ep_mesh = create_mesh(MeshConfig(data=4, expert=2))
    assert "expert" in overlap_unsupported_reason(vit, ep_mesh)
    tp_pp_mesh = create_mesh(MeshConfig(data=2, tensor=2, pipeline=2))
    assert "tensor" in overlap_unsupported_reason(vit, tp_pp_mesh)

    # a single-shard mesh is what checkpoint consumers (evaluator, a
    # 1-device serving replica) see — a forced train-only knob must
    # resolve off there, loudly, not crash the consumer
    single = create_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    assert resolve_overlap(_tiny_cfg(**{"comm.overlap": "on"}),
                           single) is None


@pytest.fixture
def compiler_takes_the_options(monkeypatch):
    """The CPU compiler knows none of the TPU compiler's options: stand in
    for a libtpu that takes them (the refusal has its own test)."""
    monkeypatch.setattr(overlap_mod, "_compiler_refusal", lambda options: None)


@pytest.mark.parametrize("in_envelope", [True, False])
@pytest.mark.parametrize("processes", [1, 2])
@pytest.mark.parametrize("shards", [1, 8])
@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_auto_rule(monkeypatch, devices, compiler_takes_the_options,
                   backend, shards, processes, in_envelope):
    """What a mesh and a backend get. ``comm.overlap=auto``: the bucketed
    exchange iff there is an exchange (more than one batch shard), the
    envelope takes the combination and the run has peers — whatever the
    backend (on one TPU host the bucketed path lost, PERF.md §6 PR 30).
    The step programs' compiler options: one process, more than one data
    shard, a TPU backend — whatever the envelope says."""
    mesh = create_mesh(MeshConfig(data=shards),
                       devices=jax.devices()[:shards])
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "process_count", lambda: processes)
    cfg = _tiny_cfg(**({} if in_envelope
                       else {"model.cross_replica_bn": "false"}))
    plan = resolve_overlap(cfg, mesh)
    assert (plan is not None) == (shards > 1 and in_envelope
                                  and processes > 1)
    if plan is not None:
        # nothing else resolves on with it: the wire stays float32, flat
        assert plan.compress is None and plan.hierarchy is None
        assert plan.autotune == "off" and not plan.tuned
    options = exchange_compiler_options(mesh)
    if shards > 1 and processes == 1 and backend == "tpu":
        assert options == EXCHANGE_COMPILER_OPTIONS
        assert options is not EXCHANGE_COMPILER_OPTIONS  # a copy
    else:
        assert options is None
    # the seams are untouched by backend and process count
    cfg.comm.overlap = "off"
    assert resolve_overlap(cfg, mesh) is None


@pytest.mark.parametrize("axes,expect", [
    ({"data": 8}, True),
    ({"data": 4}, True),                  # the shape that ran on the chips
    ({"data": 1}, False),
    ({"data": 4, "fsdp": 2}, False),      # reduce-scatter and all-gather
    ({"data": 1, "fsdp": 8}, False),
    ({"data": 4, "tensor": 2}, False),    # activation all-reduces beside
    ({"data": 2, "pipeline": 2}, False),
    ({"data": 2, "expert": 2}, False),
    ({"data": 2, "sequence": 2}, False),
])
def test_options_only_on_a_mesh_of_data_shards(
        monkeypatch, devices, compiler_takes_the_options, axes, expect):
    """The options were measured on ``mesh.data=4`` and nothing else: a
    mesh with any other axis of size > 1 keeps the compiler's defaults."""
    count = int(np.prod(list(axes.values())))
    mesh = create_mesh(MeshConfig(**axes), devices=jax.devices()[:count])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = exchange_compiler_options(mesh)
    assert got == (EXCHANGE_COMPILER_OPTIONS if expect else None)


def test_a_compiler_that_refuses_the_options_gets_none(monkeypatch, devices,
                                                       caplog):
    """On another libtpu an option's name may be gone, and an unknown name
    fails every compile. The CPU compiler here is such a compiler: asked
    once, it refuses, and the Trainer builds its steps without options and
    says so, instead of failing its first step."""
    overlap_mod._compiler_refusal.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = create_mesh(MeshConfig(data=8))
    with caplog.at_level("WARNING", logger=overlap_mod.__name__):
        assert exchange_compiler_options(mesh) is None
    assert "No such compile option" in caplog.text
    tr = Trainer(_tiny_cfg(), mesh=mesh)
    assert tr._step_compiler_options is None
    assert tr.resolutions()["step.compiler_options"] == "none"
    assert overlap_mod._compiler_refusal.cache_info().misses == 1  # asked once
    overlap_mod._compiler_refusal.cache_clear()


@pytest.mark.parametrize("backend,shards,expect", [
    ("cpu", 8, False),   # the tier-1 mesh: the programs it always had
    ("tpu", 1, False),   # one chip: no exchange, no option
    ("tpu", 8, True),    # one TPU host, more than one data shard
])
def test_step_jit_sites_get_the_options(monkeypatch, devices,
                                        compiler_takes_the_options, backend,
                                        shards, expect):
    """All four train-step ``jax.jit`` sites (a streamed batch or a device
    dataset's indices, one step or a fused group) are handed the
    exchange's compiler options exactly when ``exchange_compiler_options``
    gives them, no other jit site of the Trainer is, and the resolved
    line says which options the step was built with."""
    cfg = _tiny_cfg()
    mesh = create_mesh(MeshConfig(data=shards),
                       devices=jax.devices()[:shards])
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    tr = Trainer(cfg, mesh=mesh)
    # init_state and attach compile for the backend that is there
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    tr.init_state()
    tr.attach_device_dataset(np.zeros((16, 8, 8, 3), np.uint8),
                             np.zeros((16,), np.int32))
    seen = []
    real_jit = jax.jit

    def recording_jit(fun, **kw):
        seen.append((fun, kw.get("compiler_options")))
        return real_jit(fun, **{k: v for k, v in kw.items()
                                if k != "compiler_options"})

    monkeypatch.setattr(jax, "jit", recording_jit)
    tr.jitted_train_step()
    tr.jitted_multi_step()
    tr.jitted_index_step()
    tr.jitted_index_multi_step()
    tr.jitted_eval_step()
    tr.jitted_predict_step()
    want = EXCHANGE_COMPILER_OPTIONS if expect else None
    steps, others = seen[:4], seen[4:]
    assert steps[0][0] is tr._train_step
    assert [f.__name__ for f, _ in steps[1:]] == \
        ["multi", "gathered_train_step", "multi"]
    assert all(got == want for _, got in steps)
    assert len(others) == 2 and all(got is None for _, got in others)
    line = tr.resolutions()["step.compiler_options"]
    assert tr.resolutions()["comm.overlap"] == "off"
    if expect:
        assert all(f"{k}={v}" in line
                   for k, v in EXCHANGE_COMPILER_OPTIONS.items())
    else:
        assert line == "none"


def test_chip_smoke_has_the_overlap_leg():
    import chip_smoke
    assert "overlap" in chip_smoke.LEGS and \
        chip_smoke.CHILD_LEGS["overlap"] is chip_smoke.leg_overlap


def test_per_replica_bn_envelope_exceptions(devices):
    """norm='group' has no batch coupling, so per-replica-BN gating must
    not block it; frozen BN likewise."""
    mesh = create_mesh(MeshConfig(data=8))
    for norm in ("group", "frozen"):
        cfg = _tiny_cfg(**{"comm.overlap": "on",
                           "model.cross_replica_bn": "false"})
        cfg.model.norm = norm
        assert overlap_unsupported_reason(cfg, mesh) is None


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_comm_overlap_event_row(tmp_path, devices):
    from distributed_resnet_tensorflow_tpu.train.hooks import CommOverlapHook
    from distributed_resnet_tensorflow_tpu.utils.metrics import (
        MetricsWriter, read_metrics)
    overlap_stats.reset()
    batches = _fixed_batches(n=2)
    cfg = _tiny_cfg(**{"comm.overlap": "on", "comm.bucket_mb": "0.05"})
    tr = Trainer(cfg, mesh=create_mesh(MeshConfig(data=8)))
    assert tr.comm_overlap_active
    tr.init_state()
    w = MetricsWriter(str(tmp_path), enable_tensorboard=False)
    hook = CommOverlapHook(w, every_steps=1)
    tr.train(iter(batches), num_steps=2, hooks=(hook,))
    w.close()
    rows = [r for r in read_metrics(str(tmp_path))
            if r.get("event") == "comm_overlap"]
    assert len(rows) == 1  # one row per traced plan, not per step
    row = rows[0]
    assert row["buckets"] > 1
    assert sum(row["bucket_bytes"]) == row["grad_bytes"]
    assert sum(row["bucket_leaves"]) == row["leaves"]


def test_overlap_off_writes_no_plan(devices):
    overlap_stats.reset()
    batches = _fixed_batches(n=1)
    _train(MeshConfig(data=8), batches, **{"comm.overlap": "off"})
    assert overlap_stats.snapshot() is None
