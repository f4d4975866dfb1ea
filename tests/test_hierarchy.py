"""Topology-aware hierarchical collectives + the startup comm autotune
(ISSUE 18; parallel/overlap.py module docstring, docs/observability.md).

The load-bearing claims, pinned here:

* the two-tier ``axis_index_groups`` factorization is sound: the staged
  RS -> inter-psum -> AG exchange is BITWISE equal to the flat psum on
  exactly-representable integer payloads (floats only reassociate, so
  the bitwise oracle uses payloads where association cannot matter),
  composed with compression, ZeRO-1 out_specs and fsdp-sharded leaves;
* the declared plan and the wire ledger come from ONE source
  (``_bucket_plan_ops``): staged op order is RS@data[k] ->
  psum@data[D/k] (the only inter-tier traffic, ~1/k of the payload) ->
  AG@data[k], and the flat plan moves the FULL payload inter-tier;
* end-to-end (Trainer) the hierarchical run stays allclose to flat
  (reduction reassociation only) and is bitwise REPRODUCIBLE, with the
  comm_overlap snapshot carrying hierarchy/inter-wire accounting;
* ``tune_comm_plan`` is deterministic given a fixed table, only admits
  hierarchical candidates backed by MEASURED plausible tier rows, and
  falls back flat LOUDLY on a seeded probe lie;
* the bandwidth catalog round-trips tier rows (schema v2) and still
  loads v1 documents.
"""
import json
import logging
import os

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from distributed_resnet_tensorflow_tpu.parallel import create_mesh
from distributed_resnet_tensorflow_tpu.parallel import overlap as ov
from distributed_resnet_tensorflow_tpu.parallel.mesh import (
    data_axis_host_factorization, shard_map_unchecked)
from distributed_resnet_tensorflow_tpu.parallel.overlap import (
    autotune_mode, hierarchy_factor, hierarchy_groups, overlap_stats,
    resolve_hierarchy)
from distributed_resnet_tensorflow_tpu.telemetry import planner
from distributed_resnet_tensorflow_tpu.train import Trainer
from distributed_resnet_tensorflow_tpu.utils.config import (MeshConfig,
                                                            get_preset)


# ---------------------------------------------------------------------------
# group construction + knob validation
# ---------------------------------------------------------------------------

def test_hierarchy_groups_partition():
    gi, ge = hierarchy_groups(4, 2)
    # intra: consecutive host blocks; inter: one peer per host by rank
    assert gi == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert ge == [[0, 4], [1, 5], [2, 6], [3, 7]]
    # both tilings partition the full axis (equal-size groups — the
    # replica-consistency precondition for grouped psum of replicated
    # operands)
    for groups, size in ((gi, 4), (ge, 2)):
        flat = sorted(i for g in groups for i in g)
        assert flat == list(range(8))
        assert all(len(g) == size for g in groups)


def test_hierarchy_factor_override_validation(mesh8):
    cfg = get_preset("smoke")
    for bad in (3, 8, 1, -4):
        cfg.comm.intra_axis_size = bad
        if bad in (1, 0):
            continue
        with pytest.raises(ValueError, match="intra_axis_size"):
            hierarchy_factor(cfg, mesh8)
    for good in (2, 4):
        cfg.comm.intra_axis_size = good
        assert hierarchy_factor(cfg, mesh8) == good


def test_virtual_mesh_resolves_flat_without_override(mesh8):
    """A single-process virtual mesh has no host boundary: auto stays
    flat quietly, on refuses loudly (naming the override), off is None,
    and unknown knob values are refused."""
    cfg = get_preset("smoke")
    assert data_axis_host_factorization(mesh8) is None
    cfg.comm.hierarchy = "off"
    assert resolve_hierarchy(cfg, mesh8) is None
    cfg.comm.hierarchy = "auto"
    assert resolve_hierarchy(cfg, mesh8) is None
    cfg.comm.hierarchy = "on"
    with pytest.raises(ValueError, match="intra_axis_size"):
        resolve_hierarchy(cfg, mesh8)
    cfg.comm.intra_axis_size = 4
    assert resolve_hierarchy(cfg, mesh8) == 4
    cfg.comm.hierarchy = "sometimes"
    with pytest.raises(ValueError, match="hierarchy"):
        resolve_hierarchy(cfg, mesh8)
    cfg.comm.hierarchy = "off"
    cfg.comm.autotune = "startup"
    assert autotune_mode(cfg) == "startup"
    cfg.comm.autotune = "always"
    with pytest.raises(ValueError, match="autotune"):
        autotune_mode(cfg)


# ---------------------------------------------------------------------------
# the staged exchange: bitwise vs flat (exchange level)
# ---------------------------------------------------------------------------

def _int_leaves(rng, shapes, lo=-8, hi=8):
    # exactly representable in f32 AND bf16 (including their 8-way sums):
    # association cannot change a bit, so bitwise equality is the oracle
    return [rng.randint(lo, hi, size=s).astype(np.float32) for s in shapes]


def _exchange(mesh, leaves, specs, hierarchy, data_size, out_specs=None,
              compress=None, reduce_axes=("data", "fsdp"), in_specs=None,
              run_out_specs=None):
    def body(*ls):
        return tuple(ov._exchange_bucket(
            list(ls), specs, out_specs=out_specs, compress=compress,
            reduce_axes=reduce_axes, hierarchy=hierarchy,
            data_size=data_size))
    n = len(leaves)
    f = shard_map_unchecked(
        body, mesh,
        in_specs=tuple(in_specs or (P(),) * n),
        out_specs=tuple(run_out_specs or in_specs or (P(),) * n))
    return [np.asarray(x) for x in jax.jit(f)(*leaves)]


@pytest.mark.parametrize("compress", [None, "bf16"], ids=["f32", "bf16"])
def test_staged_exchange_bitwise_vs_flat(mesh8, rng, compress):
    leaves = _int_leaves(rng, [(7, 3), (5,), (2, 2, 2)])
    specs = [P(), P(), P()]
    flat = _exchange(mesh8, leaves, specs, None, 8, compress=compress)
    hier = _exchange(mesh8, leaves, specs, 4, 8, compress=compress)
    for a, b in zip(flat, hier):
        np.testing.assert_array_equal(a, b)


def test_staged_exchange_bitwise_with_zero1_out_specs(mesh8, rng):
    """ZeRO-1 leaves keep their flat data reduce-scatter (they already
    move 1/N into the shard layout); the staged block restages only the
    replicated remainder — composition stays bitwise."""
    leaves = _int_leaves(rng, [(8, 3), (5,), (6,)])
    specs = [P(), P(), P()]
    out_specs = [P("data"), P(), P()]
    in_specs = (P(), P(), P())
    run_out = (P("data"), P(), P())
    kw = dict(out_specs=out_specs, in_specs=in_specs, run_out_specs=run_out)
    flat = _exchange(mesh8, leaves, specs, None, 8, **kw)
    hier = _exchange(mesh8, leaves, specs, 4, 8, **kw)
    for a, b in zip(flat, hier):
        np.testing.assert_array_equal(a, b)


def test_staged_exchange_bitwise_with_fsdp_leaves(mesh_dp_fsdp, rng):
    """dp(4)×fsdp(2): fsdp-sharded leaves reduce-scatter on fsdp first,
    then their remainders ride the trailing staged block over the
    factored data axis (k=2)."""
    # gradients enter the exchange FULL-size (replicated) and the
    # fsdp-sharded leaf leaves scattered into its training-state layout
    leaves = _int_leaves(rng, [(7, 3), (4, 6)])
    specs = [P(), P(None, "fsdp")]
    in_specs = (P(), P())
    run_out = (P(), P(None, "fsdp"))
    kw = dict(in_specs=in_specs, run_out_specs=run_out)
    flat = _exchange(mesh_dp_fsdp, leaves, specs, None, 4, **kw)
    hier = _exchange(mesh_dp_fsdp, leaves, specs, 2, 4, **kw)
    for a, b in zip(flat, hier):
        np.testing.assert_array_equal(a, b)


def test_declared_plan_and_inter_wire_quarter():
    """One source for the declared schedule AND the wire ledger: staged
    op order, the [k] group suffixes, and inter-tier bytes ~1/k of the
    flat plan's (the acceptance ratio; pad-tolerant 3x bound)."""
    specs = [P(), P(), P()]
    kw = dict(reduce_axes=("data", "fsdp"), leaf_elems=[21, 5, 8],
              wire_itemsize=4)
    hier = ov._bucket_plan_ops(specs, hierarchy=4, data_size=8, **kw)
    flat = ov._bucket_plan_ops(specs, **kw)
    assert [op["sig"] for op in flat] == ["psum@data+fsdp"]
    assert [op["sig"] for op in hier] == [
        "psum_scatter@data[4]", "psum@data[2]", "psum@fsdp",
        "all_gather@data[4]"]
    assert ov.declared_bucket_collectives(
        specs, reduce_axes=("data", "fsdp"), hierarchy=4,
        data_size=8) == [op["sig"] for op in hier]
    inter_h = sum(op["wire_bytes"] for op in hier if op["inter"])
    inter_f = sum(op["wire_bytes"] for op in flat if op["inter"])
    assert inter_f == 34 * 4  # flat: the FULL payload crosses the tier
    assert inter_h == 36  # 34 elems padded to 36, 1/4 shard, 4B each
    assert inter_h * 3 < inter_f
    # degenerate factorizations resolve flat (k must be a non-trivial
    # divisor and the bucket must reduce over data)
    for k, d, axes in ((8, 8, ("data",)), (3, 8, ("data",)),
                       (4, 8, ("fsdp",))):
        assert ov._resolve_hier(k, d, axes) is None


# ---------------------------------------------------------------------------
# end-to-end: Trainer hier-vs-flat + the snapshot accounting
# ---------------------------------------------------------------------------

def _tiny_cfg(**kw):
    cfg = get_preset("smoke")
    cfg.model.compute_dtype = "float32"
    cfg.model.resnet_size = 8
    cfg.model.num_classes = 4
    cfg.data.image_size = 8
    cfg.train.batch_size = 16
    cfg.optimizer.schedule = "constant"
    cfg.checkpoint.save_every_secs = 0.0
    cfg.comm.overlap = "on"
    cfg.comm.bucket_mb = 0.05
    # keep the per-process comm probe (and its bandwidth-catalog write)
    # out of the non-autotune legs — the autotune test re-enables it
    cfg.telemetry.comm_timing = False
    for k, v in kw.items():
        cfg.override(k, v)
    return cfg


def _fixed_batches(n=3, bs=16, size=8, classes=4):
    rng = np.random.RandomState(7)
    imgs = rng.randn(n, bs, size, size, 3).astype(np.float32)
    labs = rng.randint(0, classes, (n, bs)).astype(np.int32)
    return [{"images": imgs[i], "labels": labs[i]} for i in range(n)]


def _flat_params(state):
    return np.concatenate([np.asarray(l, np.float32).ravel() for l in
                           jax.tree_util.tree_leaves(state.params)])


def _train(mesh_cfg, batches, **kw):
    cfg = _tiny_cfg(**kw)
    tr = Trainer(cfg, mesh=create_mesh(mesh_cfg))
    tr.init_state()
    state, metrics = tr.train(iter(list(batches)), num_steps=len(batches))
    return tr, state, _flat_params(state), metrics


_HIER = {"comm.hierarchy": "on", "comm.intra_axis_size": "4"}


# re-tiered slow (ISSUE 18): ~18 s of multi-device compiles on the one
# CPU core, and the 870 s tier-1 budget has no headroom left. The
# bit-identity and staged-plan claims stay in tier-1 via the
# exchange-level tests above (sub-second each); this leg adds the
# whole-Trainer composition on top.
@pytest.mark.slow
def test_e2e_hierarchical_training_matches_flat(devices):
    """The e2e acceptance leg on the 2x4-factored virtual mesh: the
    staged run stays allclose to flat (float reassociation only — the
    staged sum is a different association of the SAME addends), is
    bitwise REPRODUCIBLE run-to-run, and the snapshot declares the
    staged plan with inter-tier wire ~1/4 of the flat run's."""
    batches = _fixed_batches()
    _, _, flat, m0 = _train(MeshConfig(data=8), batches)
    base = overlap_stats.snapshot()
    assert base["hierarchy"] == 0
    _, _, hier, m1 = _train(MeshConfig(data=8), batches, **_HIER)
    snap = overlap_stats.snapshot()
    assert snap["hierarchy"] == 4 and snap["tuned"] is False
    # same bucket plan, restaged collectives
    assert snap["bucket_bytes"] == base["bucket_bytes"]
    for ops in snap["declared_collectives"]:
        assert ops[0].startswith("psum_scatter@data[4]")
        assert ops[-1] == "all_gather@data[4]"
        assert any(op == "psum@data[2]" for op in ops)
    # the acceptance ratio: per-bucket inter-tier bytes drop to ~1/k
    # (pad-tolerant 3x bound; flat moves the full wire payload)
    assert sum(base["bucket_inter_wire_bytes"]) == base["wire_bytes"]
    assert sum(snap["bucket_inter_wire_bytes"]) * 3 < base["wire_bytes"]
    # op ledger aligns 1:1 with the declared schedule
    assert [len(b) for b in snap["bucket_op_wire_bytes"]] == \
        [len(b) for b in snap["declared_collectives"]]
    np.testing.assert_allclose(hier, flat, rtol=1e-4, atol=1e-5)
    assert abs(float(m0["loss"]) - float(m1["loss"])) < 1e-3
    _, _, hier2, _ = _train(MeshConfig(data=8), batches, **_HIER)
    np.testing.assert_array_equal(hier, hier2)


# re-tiered out of the 870s tier-1 (ISSUE 18, ~25s: four more full
# trainings). Each composition keeps a faster tier-1 sibling: the
# exchange-level bitwise grid above covers compress/zero1/fsdp staging,
# and test_e2e_hierarchical_training_matches_flat pins the plain-dp e2e
# leg; the full (unfiltered) suite runs the e2e compositions.
@pytest.mark.slow
@pytest.mark.parametrize("kw", [
    {"comm.compress": "bf16"},
    {"optimizer.zero1": "on", "optimizer.zero1_min_size": "16"},
    {"train.grad_accum_steps": "2"},
], ids=["compress", "zero1", "accum2"])
def test_e2e_hierarchical_compositions_match_flat(devices, kw):
    batches = _fixed_batches()
    _, _, flat, _ = _train(MeshConfig(data=8), batches, **kw)
    _, _, hier, _ = _train(MeshConfig(data=8), batches, **kw, **_HIER)
    snap = overlap_stats.snapshot()
    assert snap["hierarchy"] == 4
    tol = dict(rtol=2e-2, atol=5e-3) if "comm.compress" in kw \
        else dict(rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(hier, flat, **tol)


def test_hierarchy_without_overlap_warns(caplog, devices):
    """comm.hierarchy rides the bucketed exchange: with overlap resolved
    off the Trainer must warn loudly instead of silently training the
    flat unbucketed program."""
    cfg = _tiny_cfg(**_HIER)
    cfg.comm.overlap = "off"
    with caplog.at_level(
            logging.WARNING,
            logger="distributed_resnet_tensorflow_tpu.train.loop"):
        tr = Trainer(cfg, mesh=create_mesh(MeshConfig(data=8)))
    assert not tr.comm_overlap_active
    assert any("hierarchy" in r.message and "overlap" in r.message
               for r in caplog.records)


# ---------------------------------------------------------------------------
# the startup autotune: chooser determinism, fallback discipline, e2e
# ---------------------------------------------------------------------------

_SNAP = {"grad_bytes": 8 << 20,
         "bucket_bytes": [4 << 20, 4 << 20],
         "bucket_reduce_axes": ["data+fsdp", "data+fsdp"],
         "compress": "off"}


def _table(axes):
    return planner.BandwidthTable(source="probe", axes=axes,
                                  default_bps=4e8, default_latency=2e-4)


def test_tune_comm_plan_deterministic_and_ranks_hier():
    """Fast intra tier + slow-but-thin inter leg -> the staged plan wins;
    called twice on the same inputs the chooser returns the identical
    dict (the autotune-determinism contract)."""
    t = _table({"data+fsdp": (4e8, 2e-4),
                "data+fsdp:intra": (4e9, 1e-5),
                "data+fsdp:inter": (2e8, 2e-4)})
    a = planner.tune_comm_plan(_SNAP, t, intra_k=4, bucket_mb=4.0)
    b = planner.tune_comm_plan(_SNAP, t, intra_k=4, bucket_mb=4.0)
    assert a == b
    assert a["hierarchy"] == 4 and a["fallback"] is None
    assert a["axes"] == "data+fsdp"
    assert a["predicted_secs"] > 0
    # every (bucket_mb x form) candidate was costed
    assert any("/hier4" in k or k.endswith("hier4")
               for k in a["candidates"])
    # a slower intra tier than the flat fabric keeps the flat plan
    slow = _table({"data+fsdp": (4e8, 2e-4),
                   "data+fsdp:intra": (1e7, 5e-3),
                   "data+fsdp:inter": (1e7, 5e-3)})
    c = planner.tune_comm_plan(_SNAP, slow, intra_k=4, bucket_mb=4.0)
    assert c["hierarchy"] == 0 and c["fallback"] is None


def test_tune_comm_plan_requires_measured_tier_rows(caplog):
    with caplog.at_level(logging.WARNING):
        c = planner.tune_comm_plan(
            _SNAP, _table({"data+fsdp": (4e8, 2e-4)}),
            intra_k=4, bucket_mb=4.0)
    assert c["hierarchy"] == 0
    assert "no measured tier rows" in c["fallback"]
    assert any("DISABLED" in r.message for r in caplog.records)


def test_tune_comm_plan_probe_lie_falls_back_flat(caplog):
    """The seeded-probe-lie contract: an implausible tier row (1e15 B/s
    against a 4e8 B/s flat fabric) must NOT produce a hierarchical plan
    — the chooser screens tiers against TUNE_SANITY_FACTOR x flat and
    falls back flat with a loud warning."""
    lie = _table({"data+fsdp": (4e8, 2e-4),
                  "data+fsdp:intra": (1e15, 1e-12),
                  "data+fsdp:inter": (1e15, 1e-12)})
    with caplog.at_level(logging.WARNING):
        c = planner.tune_comm_plan(_SNAP, lie, intra_k=4, bucket_mb=4.0)
    assert c["hierarchy"] == 0
    assert "plausibility" in c["fallback"]
    assert any("DISABLED" in r.message for r in caplog.records)
    # compression candidates never introduce a lossy dtype the operator
    # didn't configure
    assert all("/bf16" not in k and "/fp16" not in k
               for k in c["candidates"])


# re-tiered slow (ISSUE 18): ~9 s — probe + retrace is two extra
# multi-device compiles. tune_comm_plan's choice/fallback/determinism
# contracts stay in tier-1 via the unit tests above; this leg adds the
# live probe -> retune -> mid-run rebuild wiring.
@pytest.mark.slow
def test_e2e_autotune_startup_records_tuned_plan(devices, tmp_path,
                                                 monkeypatch):
    """comm.autotune=startup on the live virtual-8 leg: the probe fires
    at the first step boundary, the chooser rewrites the plan, the step
    REBUILDS around it, and the re-traced snapshot (the comm_overlap
    row's source) records autotune=startup + tuned=True."""
    from distributed_resnet_tensorflow_tpu.telemetry import bandwidth
    monkeypatch.setenv(bandwidth.DIR_ENV, str(tmp_path))  # keep the
    # probe's catalog fold out of the committed results tree
    batches = _fixed_batches(n=4)
    tr, _, _, _ = _train(MeshConfig(data=8), batches,
                         **{"comm.autotune": "startup",
                            "telemetry.comm_timing": "true"})
    assert tr._autotune == "startup"
    snap = overlap_stats.snapshot()
    assert snap is not None
    assert snap["autotune"] == "startup" and snap["tuned"] is True


def test_autotune_without_comm_timing_degrades_loudly(caplog, devices):
    cfg = _tiny_cfg(**{"comm.autotune": "startup"})
    cfg.telemetry.comm_timing = False
    with caplog.at_level(
            logging.WARNING,
            logger="distributed_resnet_tensorflow_tpu.train.loop"):
        tr = Trainer(cfg, mesh=create_mesh(MeshConfig(data=8)))
    assert tr._autotune == "off"
    assert any("autotune" in r.message and "comm_timing" in r.message
               for r in caplog.records)


# ---------------------------------------------------------------------------
# bandwidth catalog v2: tier rows round-trip, v1 documents still load
# ---------------------------------------------------------------------------

def _probe_snapshot():
    return {
        "buckets": [{"axes": "data+fsdp", "wire_bytes": 1 << 20,
                     "probe_secs": 2e-3,
                     "wire_bytes_per_sec": (1 << 20) / 2e-3}],
        "tiers": [
            {"axes": "data+fsdp", "tier": "intra", "wire_bytes": 1 << 20,
             "probe_secs": 1e-3,
             "wire_bytes_per_sec": (1 << 20) / 1e-3},
            {"axes": "data+fsdp", "tier": "inter",
             "wire_bytes": (1 << 20) // 4, "probe_secs": 4e-3,
             "wire_bytes_per_sec": ((1 << 20) // 4) / 4e-3},
        ],
    }


def test_bandwidth_catalog_v2_tier_rows_roundtrip(tmp_path, monkeypatch):
    from distributed_resnet_tensorflow_tpu.telemetry import bandwidth
    monkeypatch.setenv(bandwidth.DIR_ENV, str(tmp_path))
    path = bandwidth.update_from_probe(_probe_snapshot())
    assert path and os.path.dirname(path) == str(tmp_path)
    doc = bandwidth.load_catalog(path)
    assert doc["schema_version"] == bandwidth.SCHEMA_VERSION == 2
    axes = doc["axes"]
    assert set(axes) == {"data+fsdp", "data+fsdp:intra",
                         "data+fsdp:inter"}
    assert axes["data+fsdp:intra"]["tier"] == "intra"
    assert axes["data+fsdp:inter"]["tier"] == "inter"
    # tier-aware lookup: exact tier row; a tiered query without a tier
    # row falls back to the flat base entry
    assert bandwidth.lookup(doc, "data+fsdp:intra") is \
        axes["data+fsdp:intra"]
    assert bandwidth.lookup(doc, "data+expert:intra") is not None
    del axes["data+fsdp:inter"]
    assert bandwidth.lookup(doc, "data+fsdp:inter") is axes["data+fsdp"]


def test_bandwidth_catalog_v1_document_still_loads(tmp_path, monkeypatch):
    from distributed_resnet_tensorflow_tpu.telemetry import bandwidth
    monkeypatch.setenv(bandwidth.DIR_ENV, str(tmp_path))
    v1 = {"schema_version": 1, "fabric": "cpu-8", "platform": "cpu",
          "device_kind": "cpu", "devices": 8,
          "axes": {"data+fsdp": {"bytes_per_sec": 5e8,
                                 "latency_secs": 2e-4, "samples": 3,
                                 "min_wire_bytes": 1024,
                                 "max_wire_bytes": 4096}}}
    p = tmp_path / "cpu-8.json"
    p.write_text(json.dumps(v1))
    doc = bandwidth.load_catalog(str(p))
    assert doc is not None
    assert bandwidth.lookup(doc, "data+fsdp")["bytes_per_sec"] == 5e8
    # a tiered query on a v1 document answers with the flat row
    assert bandwidth.lookup(doc, "data+fsdp:intra")["bytes_per_sec"] == 5e8
    # the first fold on this document stamps the schema forward and adds
    # the tier rows
    path = bandwidth.update_from_probe(_probe_snapshot(), path=str(p))
    doc2 = bandwidth.load_catalog(path)
    assert doc2["schema_version"] == 2
    assert "data+fsdp:intra" in doc2["axes"]
    assert doc2["axes"]["data+fsdp"]["samples"] == 4  # ratchet-merged
