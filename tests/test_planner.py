"""What-if performance planner (telemetry/planner.py, docs/planner.md).

The load-bearing claims, pinned here:

* the analytic cost model is internally consistent over the COMMITTED
  collective schedules (step = compute + exposed, accumulation scales
  compute, compression narrows wire bytes, rankings sort), and the
  probe-fed prediction of a LIVE virtual-8 bucketed leg lands inside
  the documented ``telemetry.plan_tolerance`` band of the measured
  step — the same band the drift sentinel enforces;
* ``analysis/plan_catalog.json`` is byte-identical across consecutive
  gate runs AND matches the committed file (the artifact must only
  ever diff on a real model/schedule change);
* a seeded bandwidth-table lie is caught twice over: statically by the
  gate's catalog-vs-micro-probe cross-check, and live by the
  DriftSentinel — which fires exactly ONCE per divergence episode,
  with a cooldown;
* the bandwidth catalog round-trips probe measurements (merge-best),
  and ``tools/bench_trajectory.py`` joins the BENCH rounds with
  correct per-key deltas.
"""
import json
import os

import numpy as np
import pytest

import jax

from distributed_resnet_tensorflow_tpu.telemetry import planner
from distributed_resnet_tensorflow_tpu.telemetry.comm_report import (
    load_schedules)
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
            assert c["comm_exposed_secs"] <= c["comm_secs"] + 1e-12
            assert c["step_secs"] == pytest.approx(
                c["compute_secs"] + c["comm_exposed_secs"], rel=1e-6)
            assert 0.0 <= c["comm_fraction"] <= 1.0
        # ranking is by predicted step time
        steps = [cands[k]["step_secs"] for k in plan["ranked"]]
        assert steps == sorted(steps)
        # the recommendation compares overlap variants with each other
        assert plan["recommended"].endswith("/overlap")


def test_accum_and_compress_variants_scale_the_model(signatures):
    plan = planner.plan_for_preset("cifar10_resnet50", signatures,
                                   include_hbm=False)
    c = plan["candidates"]
    # accumulation multiplies the compute term, not the exchange
    assert c["dp/overlap+accum4"]["compute_secs"] == pytest.approx(
        4 * c["dp/overlap"]["compute_secs"], rel=1e-6)
    assert c["dp/overlap+accum4"]["comm_secs"] == pytest.approx(
        c["dp/overlap"]["comm_secs"], rel=1e-6)
    # bf16 compression halves the exchange payload on the wire
    assert c["dp_fsdp/bf16+compress"]["wire_bytes"] == pytest.approx(
        c["dp/overlap"]["wire_bytes"] / 2, rel=0.1)
    # the zero1 variant exists for the preset that pins the knob
    lamb = planner.plan_for_preset("imagenet_resnet50_lamb4k",
                                   signatures, include_hbm=False)
    zero1 = [k for k in lamb["candidates"] if k.endswith("overlap+zero1")]
    assert zero1 and all(
        lamb["candidates"][k]["comm_secs"] > 0 for k in zero1)


def test_vit_moe_plan_covers_transformer_layouts(signatures):
    plan = planner.plan_for_preset("vit_moe", signatures,
                                   include_hbm=False)
    layouts = {k.split("/", 1)[0] for k in plan["candidates"]}
    assert {"dp", "dp_fsdp", "dp_tp", "dp_pp", "dp_pp_ep"} <= layouts


def test_recommend_layout_returns_mesh(signatures):
    rec = planner.recommend_layout("vit_moe", n_devices=8)
    assert rec is not None
    layout, mesh_cfg = rec
    assert hasattr(mesh_cfg, "data")
    assert planner.recommend_layout("no_such_preset") is None


# ---------------------------------------------------------------------------
# live virtual-8 leg: probe-fed prediction vs measured step
# ---------------------------------------------------------------------------

def _tiny_overlap_cfg():
    cfg = get_preset("smoke")
    cfg.model.compute_dtype = "float32"
    cfg.model.resnet_size = 8
    cfg.model.num_classes = 4
    cfg.data.image_size = 8
    cfg.train.batch_size = 16
    cfg.comm.overlap = "on"
    cfg.comm.bucket_mb = 0.05
    cfg.optimizer.schedule = "constant"
    cfg.checkpoint.save_every_secs = 0.0
    return cfg


@pytest.fixture(scope="module")
def tiny_overlap_trainer(devices):
    from distributed_resnet_tensorflow_tpu.parallel import create_mesh
    from distributed_resnet_tensorflow_tpu.train import Trainer
    cfg = _tiny_overlap_cfg()
    tr = Trainer(cfg, mesh=create_mesh(MeshConfig(data=8)))
    tr.init_state()
    return cfg, tr


def _batches(n, bs=16, size=8, classes=4):
    rng = np.random.RandomState(7)
    return [{"images": rng.randn(bs, size, size, 3).astype(np.float32),
             "labels": rng.randint(0, classes, (bs,)).astype(np.int32)}
            for _ in range(n)]


def test_probe_fed_prediction_within_documented_tolerance(
        tiny_overlap_trainer):
    """The bench.py discipline (docs/planner.md 'Tolerances'): measured
    compute + probe-fed bandwidths must predict the bucketed leg's step
    inside the plan_tolerance band the live sentinel enforces."""
    import time as _time
    from distributed_resnet_tensorflow_tpu.parallel.overlap import (
        overlap_stats, probe_comm_plan)
    cfg, tr = tiny_overlap_trainer
    state, _ = tr.train(iter(_batches(2)), num_steps=2)  # compile+warm
    n = 6
    t0 = _time.perf_counter()
    state, _ = tr.train(iter(_batches(n)), num_steps=n)
    jax.block_until_ready(state.params)
    measured_step = (_time.perf_counter() - t0) / n

    timing = probe_comm_plan(tr.mesh)
    assert timing is not None and timing["buckets"]
    bw = planner.BandwidthTable.from_probe(timing)
    assert bw is not None and bw.source == "probe"
    snap = overlap_stats.snapshot()
    comm = 0.0
    for wire, sig in zip(snap["bucket_wire_bytes"],
                         snap["bucket_reduce_axes"]):
        bps, lat = bw.lookup(sig)
        comm += lat + int(wire) / bps
    # CPU "compute" is the measured step itself net of the probed
    # exchange — the off-leg substitution bench.py records
    compute = max(measured_step - timing["comm_secs_total"], 1e-9)
    exposed = max(0.0, comm - planner.OVERLAP_EFFICIENCY * compute)
    predicted = compute + exposed
    tol = cfg.telemetry.plan_tolerance
    assert predicted / measured_step < tol
    assert measured_step / predicted < tol


def test_predict_live_builds_after_trace(tiny_overlap_trainer):
    cfg, tr = tiny_overlap_trainer
    pred = planner.predict_live(cfg, tr,
                                bandwidth=planner.BandwidthTable
                                .reference())
    assert pred is not None
    for k in ("step_secs", "compute_secs", "comm_secs",
              "comm_exposed_secs", "comm_fraction", "wire_bytes",
              "hbm_bytes"):
        assert k in pred, k
    assert pred["hbm_bytes"] >= pred["state_bytes"] > 0


def test_plan_drift_hook_fires_once_on_seeded_bandwidth_lie(
        tiny_overlap_trainer, tmp_path, monkeypatch):
    """Satellite contract: a lying bandwidth table (comm predicted as
    ~free, so the whole step is predicted orders of magnitude faster
    than a CPU can step) must arm the sentinel and produce exactly ONE
    plan_drift row per episode — plus the arming plan row."""
    from distributed_resnet_tensorflow_tpu.train.hooks import (
        PlanDriftHook)
    from distributed_resnet_tensorflow_tpu.utils.metrics import (
        MetricsWriter)
    cfg, tr = tiny_overlap_trainer
    monkeypatch.setattr(
        planner, "measured_bandwidth_table",
        lambda: planner.BandwidthTable(source="catalog",
                                       axes={}, default_bps=1e18,
                                       default_latency=0.0))
    cfg.telemetry.plan_drift_window = 2
    cfg.telemetry.plan_drift_cooldown_secs = 0.0
    w = MetricsWriter(str(tmp_path), enable_tensorboard=False)
    hook = PlanDriftHook(w, cfg, tr, every_steps=1)
    n = 8
    tr.train(iter(_batches(n)), num_steps=n, hooks=[hook])
    w.flush()
    w.close()
    rows = [json.loads(l) for l in
            open(os.path.join(str(tmp_path), "metrics.jsonl"))]
    plan_rows = [r for r in rows if r.get("event") == "plan"]
    drift_rows = [r for r in rows if r.get("event") == "plan_drift"]
    assert len(plan_rows) == 1
    assert plan_rows[0]["layout"] == "dp"
    assert plan_rows[0]["bandwidth_source"] == "catalog"
    # one episode, one firing — step_secs stays divergent the whole run
    step_firings = [r for r in drift_rows if r["metric"] == "step_secs"]
    assert len(step_firings) == 1
    assert step_firings[0]["ratio"] > cfg.telemetry.plan_tolerance
    assert step_firings[0]["windows"] >= cfg.telemetry.plan_drift_window


# ---------------------------------------------------------------------------
# DriftSentinel episode/cooldown semantics (fake clock)
# ---------------------------------------------------------------------------

def _sentinel(**kw):
    clock = {"t": 0.0}
    kw.setdefault("tolerance", 3.0)
    kw.setdefault("window", 3)
    kw.setdefault("cooldown_secs", 100.0)
    s = planner.DriftSentinel({"step_secs": 1.0, "comm_secs": 0.01},
                              clock=lambda: clock["t"], **kw)
    return s, clock


def test_sentinel_fires_exactly_once_per_episode():
    s, _clock = _sentinel()
    assert s.check("step_secs", 1.1) is None          # in tolerance
    for _ in range(2):
        assert s.check("step_secs", 10.0) is None     # streak building
    firing = s.check("step_secs", 10.0)               # window reached
    assert firing and firing["metric"] == "step_secs"
    assert firing["ratio"] == pytest.approx(10.0)
    for _ in range(20):                               # still divergent
        assert s.check("step_secs", 10.0) is None     # episode: silent
    assert s.check("step_secs", 1.0) is None          # episode ends
    for _ in range(2):
        assert s.check("step_secs", 10.0) is None


def test_sentinel_cooldown_defers_but_does_not_lose_the_fire():
    s, clock = _sentinel()
    for _ in range(2):
        s.check("step_secs", 10.0)
    assert s.check("step_secs", 10.0)                 # fires at t=0
    s.check("step_secs", 1.0)                         # episode ends
    # new episode inside the cooldown: suppressed, streak kept
    for _ in range(5):
        assert s.check("step_secs", 10.0) is None
    clock["t"] = 101.0                                # cooldown elapsed
    assert s.check("step_secs", 10.0) is not None


def test_sentinel_metrics_are_independent():
    s, _clock = _sentinel(window=2)
    s.check("comm_secs", 0.5)
    assert s.check("comm_secs", 0.5)["metric"] == "comm_secs"
    # step_secs' streak is untouched by comm's episode
    s.check("step_secs", 10.0)
    assert s.check("step_secs", 10.0) is None         # cooldown gates it
    assert s.check("hbm_bytes", 1e12) is None         # not predicted


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
# bandwidth catalog round-trip
# ---------------------------------------------------------------------------

def test_catalog_roundtrip_and_merge_best(tmp_path, monkeypatch):
    from distributed_resnet_tensorflow_tpu.telemetry import bandwidth
    monkeypatch.setenv(bandwidth.DIR_ENV, str(tmp_path))
    snap = {"buckets": [
        {"bucket": 0, "bytes": 100, "wire_bytes": 100, "leaves": 1,
         "axes": "data", "probe_secs": 2e-4,
         "wire_bytes_per_sec": 5e5}],
        "comm_secs_total": 2e-4, "reps": 3, "axes": ["data"],
        "compress": "off"}
    path = bandwidth.update_from_probe(snap)
    assert path and os.path.exists(path)
    doc = bandwidth.load_catalog(path)
    assert doc["axes"]["data"]["bytes_per_sec"] == 5e5
    assert doc["axes"]["data"]["samples"] == 1
    # a better later probe wins; a worse one does not regress the entry
    snap["buckets"][0]["wire_bytes_per_sec"] = 9e5
    snap["buckets"][0]["probe_secs"] = 1e-4
    bandwidth.update_from_probe(snap)
    snap["buckets"][0]["wire_bytes_per_sec"] = 1e5
    snap["buckets"][0]["probe_secs"] = 9e-4
    bandwidth.update_from_probe(snap)
    doc = bandwidth.load_catalog(path)
    assert doc["axes"]["data"]["bytes_per_sec"] == 9e5
    assert doc["axes"]["data"]["latency_secs"] == 1e-4
    assert doc["axes"]["data"]["samples"] == 3


def test_comm_report_synthesizes_from_catalog():
    from distributed_resnet_tensorflow_tpu.telemetry.comm_report import (
        synthesize_timing)
    overlap_row = {"bucket_wire_bytes": [1000, 2000],
                   "bucket_bytes": [1000, 2000],
                   "bucket_leaves": [3, 4],
                   "bucket_reduce_axes": ["data", "data+fsdp"],
                   "compress": "off"}
    catalog = {"schema_version": 1, "fabric": "cpu-8",
               "axes": {"data": {"bytes_per_sec": 1e6,
                                 "latency_secs": 1e-4}}}
    timing = synthesize_timing(overlap_row, catalog)
    assert timing["modeled_from_catalog"] == "cpu-8"
    assert len(timing["buckets"]) == 2
    assert all(b["modeled"] for b in timing["buckets"])
    assert timing["comm_secs_total"] == pytest.approx(
        2e-4 + 3000 / 1e6, rel=1e-6)


# ---------------------------------------------------------------------------
# main.py plan CLI + bench trajectory
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


def _repo_root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_trajectory_joins_rounds(tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_trajectory",
        os.path.join(_repo_root(), "tools", "bench_trajectory.py"))
    bt = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bt)
    for name, parsed in (
            ("BENCH_r01.json", {"a": {"x": 10.0}, "ok": True}),
            ("BENCH_r02.json", {}),                      # the r05 shape
            ("BENCH_r03.json", {"a": {"x": 15.0}, "b": 2})):
        with open(tmp_path / name, "w") as f:
            json.dump({"n": 1, "rc": 0, "cmd": "x", "parsed": parsed}, f)
    traj = bt.build_trajectory(bt.discover_rounds(str(tmp_path)))
    rows = traj["rounds"]
    assert [r["round"] for r in rows] == ["r01", "r02", "r03"]
    assert rows[1]["parsed_empty"] is True
    # the delta bridges the empty round to the last value seen
    assert rows[2]["deltas"]["a.x"] == {"abs": 5.0, "pct": 50.0}
    assert "ok" not in rows[0]["metrics"]  # bools are not magnitudes
    # the one record left in the tree joins too: BENCH_r05, the older
    # chip record (its payload was truncated, so nothing parsed)
    real = bt.build_trajectory(bt.discover_rounds(_repo_root()))
    assert [r["round"] for r in real["rounds"]] == ["r05"]
    assert real["rounds"][0]["parsed_empty"] is True


def test_monitor_bench_flag(capsys):
    from distributed_resnet_tensorflow_tpu.telemetry.monitor import (
        main_monitor)
    assert main_monitor(["--bench"]) == 0
    assert "bench trajectory" in capsys.readouterr().out
