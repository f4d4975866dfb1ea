"""What-if performance planner (telemetry/planner.py, docs/planner.md).

The load-bearing claims, pinned here:

* the analytic cost model is internally consistent over the COMMITTED
  collective schedules (step = compute + scheduled comm, rankings sort);
* ``analysis/plan_catalog.json`` is byte-identical across consecutive
  gate runs AND matches the committed file (the artifact must only
  ever diff on a real model/schedule change);
* a seeded bandwidth-table lie is caught by the gate's
  catalog-vs-micro-probe cross-check;
* a bandwidth catalog placed for a fabric loads, tier rows and version-1
  documents included, and its lookups fall back as documented.
"""
import json
import os

import numpy as np
import pytest

from distributed_resnet_tensorflow_tpu.analysis.collectives import (
    load_schedules)
from distributed_resnet_tensorflow_tpu.telemetry import planner
from distributed_resnet_tensorflow_tpu.utils.config import (MeshConfig,
                                                            get_preset)


# ---------------------------------------------------------------------------
# cost-model pieces
# ---------------------------------------------------------------------------

def test_layout_label_vocabulary():
    assert planner.layout_label(MeshConfig(data=8)) == "dp"
    assert planner.layout_label(MeshConfig(data=4, fsdp=2)) == "dp_fsdp"
    assert planner.layout_label(
        MeshConfig(data=2, pipeline=2, expert=2)) == "dp_pp_ep"


def test_ring_scale_shape():
    # 2(n-1)/n, clamped at the 2-device floor; large n → 2
    assert planner._ring_scale(2) == 1.0
    assert planner._ring_scale(1) == planner._ring_scale(2)
    assert 1.7 < planner._ring_scale(8) < planner._ring_scale(256) < 2.0


def test_flops_per_example_families():
    rn50 = get_preset("imagenet_resnet50")
    # anchored on the XLA-counted 4.1 GFLOP rn50@224 forward pass
    assert 3e9 < planner.flops_per_example(rn50) < 6e9
    cifar = get_preset("cifar10_resnet50")
    assert 0 < planner.flops_per_example(cifar) \
        < planner.flops_per_example(rn50)
    vit = get_preset("vit_moe")
    assert planner.flops_per_example(vit) > 0


def test_bandwidth_table_lookup_fallbacks():
    t = planner.BandwidthTable(
        source="test",
        axes={"data": (1e9, 1e-4), "data+fsdp": (2e9, 2e-4)},
        default_bps=5e8, default_latency=3e-4)
    assert t.lookup("data") == (1e9, 1e-4)
    # unseen signature sharing an axis falls back to the closest entry
    bps, _lat = t.lookup("data+expert")
    assert bps == 1e9
    # nothing shared -> defaults
    assert t.lookup("tensor") == (5e8, 3e-4)


# ---------------------------------------------------------------------------
# predictions over the committed schedules
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def signatures():
    sigs = load_schedules()
    assert sigs, "committed collective_schedules.json missing"
    return sigs


def test_plan_consistency_over_committed_schedules(signatures):
    """Internal consistency of every candidate the gate commits —
    the documented contract for reference-constant predictions is
    ranking + consistency, not stopwatch accuracy (docs/planner.md)."""
    for preset in ("cifar10_resnet50", "imagenet_resnet50", "vit_moe"):
        plan = planner.plan_for_preset(preset, signatures,
                                       include_hbm=False)
        cands = plan["candidates"]
        assert cands, preset
        for key, c in cands.items():
            assert np.isfinite(c["step_secs"]) and c["step_secs"] > 0
            assert c["step_secs"] == pytest.approx(
                c["compute_secs"] + c["comm_secs"], rel=1e-6)
            assert 0.0 <= c["comm_fraction"] <= 1.0
        # ranking is by predicted step time
        steps = [cands[k]["step_secs"] for k in plan["ranked"]]
        assert steps == sorted(steps)
        assert plan["recommended"] == plan["ranked"][0]


def test_vit_moe_plan_covers_transformer_layouts(signatures):
    plan = planner.plan_for_preset("vit_moe", signatures,
                                   include_hbm=False)
    layouts = {k.split("/", 1)[0] for k in plan["candidates"]}
    assert {"dp", "dp_tp", "dp_pp", "dp_pp_ep"} <= layouts
    # what a pipelined step writes out (stage hand-offs, the expert
    # all-to-all) is scheduled comm; the batch layout's exchange is XLA's
    c = plan["candidates"]
    assert c["dp_pp_ep/train"]["comm_secs"] > c["dp/train"]["comm_secs"] == 0


def test_recommend_layout_returns_mesh(signatures):
    rec = planner.recommend_layout("vit_moe", n_devices=8)
    assert rec is not None
    layout, mesh_cfg = rec
    assert hasattr(mesh_cfg, "data")
    assert planner.recommend_layout("no_such_preset") is None


# ---------------------------------------------------------------------------
# gate artifact: byte-identity + seeded-lie findings
# ---------------------------------------------------------------------------

def test_plan_catalog_byte_identical_across_runs(tmp_path, signatures):
    from distributed_resnet_tensorflow_tpu.analysis.plan_drift import (
        build_catalog, write_plan_catalog)
    fs1, doc1 = build_catalog(signatures)
    fs2, doc2 = build_catalog(signatures)
    assert fs1 == [] and fs2 == []
    p1 = write_plan_catalog(doc1, str(tmp_path / "a.json"))
    p2 = write_plan_catalog(doc2, str(tmp_path / "b.json"))
    b1, b2 = open(p1, "rb").read(), open(p2, "rb").read()
    assert b1 == b2
    doc = json.loads(b1)
    assert doc["schema_version"] == 1
    assert set(doc["plans"]) >= {"cifar10_resnet50",
                                 "imagenet_resnet50", "vit_moe"}


def test_committed_plan_catalog_is_fresh(tmp_path, signatures):
    """The committed artifact matches a fresh reference-constant build
    — like collective_schedules.json, a diff must mean a real change,
    and a stale commit must fail here, not confuse a reviewer."""
    from distributed_resnet_tensorflow_tpu.analysis.plan_drift import (
        build_catalog, plan_catalog_path, write_plan_catalog)
    _fs, doc = build_catalog(signatures)
    fresh = write_plan_catalog(doc, str(tmp_path / "fresh.json"))
    assert open(plan_catalog_path(), "rb").read() == \
        open(fresh, "rb").read()


def test_seeded_bandwidth_lie_is_a_gate_finding(tmp_path, monkeypatch):
    from distributed_resnet_tensorflow_tpu.analysis.plan_drift import (
        check_bandwidth_catalog)
    from distributed_resnet_tensorflow_tpu.telemetry import bandwidth
    monkeypatch.setenv(bandwidth.DIR_ENV, str(tmp_path))
    fabric = bandwidth.fabric_id()
    lie = {"schema_version": 1, "fabric": fabric, "platform": "cpu",
           "device_kind": "", "devices": 8,
           "axes": {"data": {"bytes_per_sec": 4.0e13,
                             "latency_secs": 1e-6, "samples": 1,
                             "min_wire_bytes": 1,
                             "max_wire_bytes": 1}}}
    path = bandwidth.catalog_path(fabric)
    with open(path, "w") as f:
        json.dump(lie, f)
    found = check_bandwidth_catalog(probe_bps=4.0e8)
    assert len(found) == 1
    assert "micro-probe" in found[0].message
    # a truthful catalog is silent
    lie["axes"]["data"]["bytes_per_sec"] = 5.0e8
    with open(path, "w") as f:
        json.dump(lie, f)
    assert check_bandwidth_catalog(probe_bps=4.0e8) == []


# ---------------------------------------------------------------------------
# bandwidth catalog: version-2 tier rows load, version-1 documents still do
# ---------------------------------------------------------------------------

def _row(bps, tier=None):
    row = {"bytes_per_sec": bps, "latency_secs": 2e-4, "samples": 3,
           "min_wire_bytes": 1024, "max_wire_bytes": 4096}
    return {**row, "tier": tier} if tier else row


def _place_catalog(tmp_path, monkeypatch, version, axes):
    from distributed_resnet_tensorflow_tpu.telemetry import bandwidth
    monkeypatch.setenv(bandwidth.DIR_ENV, str(tmp_path))
    path = bandwidth.catalog_path("cpu-8")
    assert os.path.dirname(path) == str(tmp_path)
    with open(path, "w") as f:
        json.dump({"schema_version": version, "fabric": "cpu-8",
                   "platform": "cpu", "device_kind": "cpu", "devices": 8,
                   "axes": axes}, f)
    return bandwidth, bandwidth.load_catalog(fabric="cpu-8")


def test_bandwidth_catalog_v2_tier_rows_roundtrip(tmp_path, monkeypatch):
    bandwidth, doc = _place_catalog(tmp_path, monkeypatch, 2, {
        "data+fsdp": _row(5e8),
        "data+fsdp:intra": _row(1e9, "intra"),
        "data+fsdp:inter": _row(6e7, "inter")})
    assert doc["schema_version"] == bandwidth.SCHEMA_VERSION == 2
    axes = doc["axes"]
    assert axes["data+fsdp:intra"]["tier"] == "intra"
    # tier-aware lookup: exact tier row; a tiered query without a tier
    # row falls back to the flat base entry
    assert bandwidth.lookup(doc, "data+fsdp:intra") is \
        axes["data+fsdp:intra"]
    assert bandwidth.lookup(doc, "data+expert:intra") is not None
    del axes["data+fsdp:inter"]
    assert bandwidth.lookup(doc, "data+fsdp:inter") is axes["data+fsdp"]
    # the planner's table reads the same rows
    table = planner.BandwidthTable.from_catalog(doc)
    assert table.source == "catalog"
    assert table.lookup("data+fsdp:intra") == (1e9, 2e-4)
    assert table.lookup("data+fsdp:inter") == (5e8, 2e-4)


def test_bandwidth_catalog_v1_document_still_loads(tmp_path, monkeypatch):
    bandwidth, doc = _place_catalog(tmp_path, monkeypatch, 1,
                                    {"data+fsdp": _row(5e8)})
    assert doc is not None
    assert bandwidth.lookup(doc, "data+fsdp")["bytes_per_sec"] == 5e8
    # a tiered query on a v1 document answers with the flat row
    assert bandwidth.lookup(doc, "data+fsdp:intra")["bytes_per_sec"] == 5e8
    # an unreadable document is no catalog, not an error
    with open(bandwidth.catalog_path("cpu-8"), "w") as f:
        f.write("{")
    assert bandwidth.load_catalog(fabric="cpu-8") is None


# ---------------------------------------------------------------------------
# main.py plan CLI
# ---------------------------------------------------------------------------

def test_main_plan_cli_ranks_three_presets(capsys):
    rc = planner.main_plan(["--preset", "cifar10_resnet50",
                            "--preset", "imagenet_resnet50",
                            "--preset", "vit_moe",
                            "--no-hbm", "--json"])
    assert rc == 0
    plans = json.loads(capsys.readouterr().out)
    assert [p["preset"] for p in plans] == [
        "cifar10_resnet50", "imagenet_resnet50", "vit_moe"]
    for p in plans:
        assert p["recommended"] in p["candidates"]
    moe = plans[-1]
    assert any(k.startswith("dp_pp_ep/") for k in moe["candidates"])


def test_main_plan_writes_registered_rows(tmp_path, capsys):
    rc = planner.main_plan(["--preset", "cifar10_resnet50", "--no-hbm",
                            "--root", str(tmp_path)])
    assert rc == 0
    rows = [json.loads(l) for l in
            open(os.path.join(str(tmp_path), "plan", "metrics.jsonl"))]
    plan_rows = [r for r in rows if r.get("event") == "plan"]
    assert plan_rows
    assert sum(r["recommended"] for r in plan_rows) == 1
    for r in plan_rows:
        assert {"preset", "layout", "devices", "knobs", "predicted",
                "bandwidth_source", "recommended"} <= set(r)
