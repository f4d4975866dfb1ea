"""Performance-observability plane (ISSUE 14): cluster trace merge with
heartbeat-estimated clock offsets, device-memory telemetry rows, the
watchdog's perf-anomaly sentinel, and the monitor's windowed steps/s +
per-host HBM watermark rollup. The live 2-process leg is
scripts/obs_smoke.sh; everything here is deterministic and fast."""
import json
import os
import time

import numpy as np
import pytest

from distributed_resnet_tensorflow_tpu.resilience.heartbeat import (
    BeatTransport)
from distributed_resnet_tensorflow_tpu.telemetry import merge
from distributed_resnet_tensorflow_tpu.telemetry.memory import (
    MemoryWatermarks, sample_memory, watermarks)
from distributed_resnet_tensorflow_tpu.telemetry.tracer import recorder
from distributed_resnet_tensorflow_tpu.utils.config import (
    TelemetryConfig, WatchdogConfig)
from distributed_resnet_tensorflow_tpu.utils.metrics import LatencyStats


class FakeWriter:
    def __init__(self):
        self.events = []

    def write_event(self, event, payload):
        self.events.append({"event": event, **payload})

    def flush(self):
        pass


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def _write_stream(d, rows):
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "metrics.jsonl"), "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


# ---------------------------------------------------------------------------
# clock-offset estimation + trace merge
# ---------------------------------------------------------------------------

#: proc1's wall clock reads 2.0s AHEAD of the chief's in every fixture
_SKEW = 2.0


def _write_heartbeats(root):
    """Chief-observed heartbeat rows for a 2-host world where proc1's
    clock is ``_SKEW`` ahead: observed age = true latency − skew."""
    lat0 = [0.10, 0.25, 0.40, 0.15]
    lat1 = [0.12, 0.30, 0.20, 0.45]
    rows = []
    for a0, a1 in zip(lat0, lat1):
        rows.append({"event": "heartbeat", "time": 1000.0, "hosts": {
            "0": {"step": 5, "age_secs": a0, "host": "h0"},
            "1": {"step": 5, "age_secs": a1 - _SKEW, "host": "h1"}}})
    _write_stream(os.path.join(root, "train"), rows)


def test_clock_offset_estimated_from_heartbeat_ages(tmp_path):
    _write_heartbeats(str(tmp_path))
    offs = merge.estimate_clock_offsets(str(tmp_path))
    assert set(offs) == {"0", "1"}
    # offset = (process clock − chief clock); the estimator is bounded by
    # the min true publish→observe latencies on both sides (≤ 0.12+0.10)
    assert offs["1"]["offset_secs"] == pytest.approx(_SKEW, abs=0.25)
    assert offs["0"]["offset_secs"] == pytest.approx(0.0, abs=0.15)
    assert offs["1"]["bound_secs"] >= 0.0
    assert offs["1"]["observations"] == 4
    assert offs["1"]["host"] == "h1"


def _trace_doc(process_index, epoch_wall, events):
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"process_index": process_index,
                          "pid": 100 + process_index,
                          "epoch_wall_time": epoch_wall,
                          "span_schema_version": 6}}


def test_merge_aligns_lanes_within_tolerance(tmp_path):
    """Two hosts start 0.5s apart but proc1's clock is 2.0s ahead: with
    the heartbeat-estimated offset applied, the merged timeline puts
    proc1's t=0 span ~0.5s after proc0's, not 2.5s."""
    t_dir = tmp_path / "telemetry"
    t_dir.mkdir()
    span0 = {"name": "train.step", "ph": "X", "pid": 1, "tid": 1,
             "ts": 100.0, "dur": 50.0}
    span1 = {"name": "train.step", "ph": "X", "pid": 2, "tid": 1,
             "ts": 100.0, "dur": 50.0}
    (t_dir / "trace.json").write_text(
        json.dumps(_trace_doc(0, 1000.0, [span0])))
    (t_dir / "trace.proc1.json").write_text(
        json.dumps(_trace_doc(1, 1000.5 + _SKEW, [span1])))
    _write_heartbeats(str(tmp_path))

    paths = merge.find_traces(str(tmp_path))
    assert len(paths) == 2
    offs = merge.estimate_clock_offsets(str(tmp_path))
    doc = merge.merge_traces(paths, offs)
    xs = {e["pid"]: e for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert set(xs) == {0, 1}
    # proc0 anchors the merged origin; proc1's span lands ~0.5s later
    shift_secs = (xs[1]["ts"] - xs[0]["ts"]) / 1e6
    assert shift_secs == pytest.approx(0.5, abs=0.3)
    # per-host lanes: process_name/process_sort_index metadata per source
    names = {e["pid"]: e["args"]["name"] for e in doc["traceEvents"]
             if e.get("name") == "process_name"}
    assert names[0].startswith("proc0") and names[1].startswith("proc1")
    assert "(h1)" in names[1]
    # the bounded-skew record rides in the merged file's metadata
    assert doc["otherData"]["clock_offsets"]["1"]["offset_secs"] == \
        pytest.approx(_SKEW, abs=0.25)
    assert [s["process_index"] for s in doc["otherData"]["sources"]] == \
        [0, 1]


def test_trace_merge_cli_writes_valid_perfetto_json(tmp_path, capsys):
    t_dir = tmp_path / "telemetry"
    t_dir.mkdir()
    (t_dir / "trace.json").write_text(json.dumps(_trace_doc(
        0, 1000.0, [{"name": "train.step", "ph": "X", "pid": 1,
                     "tid": 1, "ts": 10.0, "dur": 5.0}])))
    (t_dir / "trace.proc1.json").write_text(json.dumps(_trace_doc(
        1, 1001.0, [{"name": "train.hooks", "ph": "X", "pid": 1,
                     "tid": 1, "ts": 10.0, "dur": 5.0}])))
    rc = merge.main_trace_merge(["--root", str(tmp_path)])
    assert rc == 0
    out_path = tmp_path / "telemetry" / "trace.merged.json"
    doc = json.load(open(out_path))  # valid Perfetto/Chrome-trace JSON
    assert doc["otherData"]["merged"] is True
    assert {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"} \
        == {"train.step", "train.hooks"}
    # re-merge is idempotent: the merged output is not a merge input
    assert str(out_path) not in merge.find_traces(str(tmp_path))
    assert merge.main_trace_merge(["--root", str(tmp_path)]) == 0
    assert "no heartbeat rows" in capsys.readouterr().out


def test_trace_merge_cli_fails_loudly_on_empty_root(tmp_path):
    assert merge.main_trace_merge(["--root", str(tmp_path)]) == 1


# ---------------------------------------------------------------------------
# device-memory telemetry
# ---------------------------------------------------------------------------

def test_sample_memory_reports_devices_host_and_pools():
    watermarks.reset()
    row = sample_memory(process_index=0)
    assert row["process"] == 0
    assert row["devices"] and all(
        "live_bytes" in c for c in row["devices"].values())
    assert row["live_bytes_total"] >= 0
    assert row["live_peak_bytes_total"] >= row["live_bytes_total"]
    assert row["host_rss_bytes"] > 0  # /proc/self/status on linux
    assert "echo_cache_bytes" in row
    assert "staging_ring_slots" in row


def test_memory_watermark_is_monotone_under_shrinking_samples():
    wm = MemoryWatermarks()
    assert wm.update({"0": 100, "1": 50})["total"] == 150
    peaks = wm.update({"0": 30, "1": 20})
    assert peaks["total"] == 150 and peaks["by_device"]["0"] == 100
    wm.reset()
    assert wm.update({"0": 1})["total"] == 1


def test_memory_hook_exports_registered_rows():
    from distributed_resnet_tensorflow_tpu.train.hooks import MemoryHook
    w = FakeWriter()
    hook = MemoryHook(w, every_steps=1)
    hook(1, None, {})
    assert w.events and w.events[0]["event"] == "memory"
    assert "live_bytes_total" in w.events[0]
    assert w.events[0]["step"] == 1


# ---------------------------------------------------------------------------
# perf-anomaly sentinel
# ---------------------------------------------------------------------------

class _NullTransport(BeatTransport):
    def publish(self, beat):
        pass

    def peers(self):
        return {}


class _StubPublisher:
    """step_times()/snapshot() stand-in: the only surface the sentinel
    reads."""

    def __init__(self):
        self.samples = []
        self.seq = 0

    def push(self, dt):
        self.samples.append(dt)
        self.seq += 1

    def step_times(self):
        return {"seq": self.seq, "samples": list(self.samples)}

    def snapshot(self):
        return {"step": 42, "progress": 42, "phase": "train",
                "last_progress_t": 0.0, "ewma_step_secs": None,
                "step_stride": 1}


def _make_sentinel(tmp_path, **overrides):
    from distributed_resnet_tensorflow_tpu.resilience.watchdog import (
        Watchdog)
    acfg = TelemetryConfig()
    acfg.anomaly_window = 8
    acfg.anomaly_min_samples = 4
    acfg.anomaly_cooldown_secs = 30.0
    for k, v in overrides.items():
        setattr(acfg, k, v)
    pub = _StubPublisher()
    writer = FakeWriter()
    clock = FakeClock(t=1000.0)
    wd = Watchdog(_NullTransport(), pub, 0, 1, WatchdogConfig(),
                  writer=writer, clock=clock,
                  exit_fn=lambda code: None, anomaly_cfg=acfg)
    return wd, pub, writer, clock


def _rows(writer):
    return [e for e in writer.events if e["event"] == "perf_anomaly"]


def test_perf_anomaly_fires_on_slow_step_and_dumps_trace(tmp_path):
    wd, pub, writer, clock = _make_sentinel(tmp_path)
    dump_dir = str(tmp_path / "telemetry")
    stub = FakeWriter()
    recorder.configure(dump_dir=dump_dir, writer=stub, process_index=0)
    try:
        with recorder.span("train.step"):
            pass
        for _ in range(6):
            pub.push(0.1)
        wd._check_perf_anomaly(clock.t)
        assert _rows(writer) == []  # healthy window: silent
        pub.push(0.5)  # slow-but-alive: 5× the rolling median
        wd._check_perf_anomaly(clock.t)
        rows = _rows(writer)
        assert len(rows) == 1
        assert rows[0]["step"] == 42
        assert rows[0]["step_secs"] == pytest.approx(0.5)
        assert rows[0]["median_secs"] == pytest.approx(0.1)
        assert rows[0]["step_secs"] > rows[0]["threshold_secs"]
        # evidence while the slowness is LIVE: the flight-recorder dump
        assert os.path.exists(os.path.join(dump_dir, "trace.json"))
        dumps = [e for e in stub.events if e["event"] == "trace_dump"]
        assert dumps and dumps[0]["reason"] == "perf_anomaly"
    finally:
        recorder._writer = None


def test_perf_anomaly_episode_fires_once_then_rearms(tmp_path):
    wd, pub, writer, clock = _make_sentinel(tmp_path)
    for _ in range(6):
        pub.push(0.1)
    pub.push(0.5)
    wd._check_perf_anomaly(clock.t)
    assert len(_rows(writer)) == 1
    wd._check_perf_anomaly(clock.t)  # same seq: no re-judgment
    pub.push(0.55)  # still slow, same episode: no second firing
    wd._check_perf_anomaly(clock.t)
    assert len(_rows(writer)) == 1
    pub.push(0.1)  # healthy sample ends the episode
    wd._check_perf_anomaly(clock.t)
    pub.push(0.6)  # new outlier, but inside the cooldown window
    wd._check_perf_anomaly(clock.t)
    assert len(_rows(writer)) == 1
    pub.push(0.1)
    wd._check_perf_anomaly(clock.t)
    clock.t += 31.0  # cooldown over → a new episode may fire
    pub.push(0.6)
    wd._check_perf_anomaly(clock.t)
    assert len(_rows(writer)) == 2


def test_perf_anomaly_catches_transient_slow_step_between_ticks(tmp_path):
    """Several steps land per watchdog tick on a fast run: a slow step
    MASKED by fast ones before the next tick must still fire (the
    sentinel judges the worst fresh sample, not just the newest)."""
    wd, pub, writer, clock = _make_sentinel(tmp_path)
    for _ in range(6):
        pub.push(0.1)
    wd._check_perf_anomaly(clock.t)  # consume the healthy baseline
    assert _rows(writer) == []
    pub.push(0.5)  # one transient slow step...
    pub.push(0.1)  # ...followed by fast ones inside the same tick
    pub.push(0.1)
    wd._check_perf_anomaly(clock.t)
    rows = _rows(writer)
    assert len(rows) == 1
    assert rows[0]["step_secs"] == pytest.approx(0.5)


def test_perf_anomaly_ratio_floor_tolerates_steady_jitter(tmp_path):
    """MAD ≈ 0 on an ultra-steady run: the min_ratio floor keeps a
    micro-hiccup (1.2×) quiet while a real 2× step still fires."""
    wd, pub, writer, clock = _make_sentinel(tmp_path, anomaly_min_ratio=1.5)
    for _ in range(6):
        pub.push(0.1)
    pub.push(0.12)  # 1.2× — within the floor
    wd._check_perf_anomaly(clock.t)
    assert _rows(writer) == []
    pub.push(0.2)  # 2×
    wd._check_perf_anomaly(clock.t)
    assert len(_rows(writer)) == 1


def test_perf_anomaly_disabled_cfg_is_inert(tmp_path):
    wd, pub, writer, clock = _make_sentinel(tmp_path,
                                            anomaly_detection=False)
    for _ in range(6):
        pub.push(0.1)
    pub.push(5.0)
    wd._check_perf_anomaly(clock.t)
    assert _rows(writer) == []


def test_heartbeat_step_samples_respect_interlude_guard():
    """The sentinel's sample window shares the EWMA's honesty guards: no
    compile-laden first delta, no post-interlude (eval/save) delta."""
    from distributed_resnet_tensorflow_tpu.resilience.heartbeat import (
        HeartbeatPublisher)
    clock = FakeClock(t=0.0)
    pub = HeartbeatPublisher(_NullTransport(), 0, clock=clock)
    pub.update(step=1)  # first delta: discarded (compile)
    clock.t += 0.1
    pub.update(step=2)
    assert pub.step_times() == {"seq": 1, "samples": [pytest.approx(0.1)]}
    pub.tick(phase="eval")  # interlude: the next delta spans the pause
    clock.t += 30.0
    pub.update(step=3)
    clock.t += 0.1
    pub.update(step=4)
    st = pub.step_times()
    assert st["seq"] == 2
    assert st["samples"] == [pytest.approx(0.1), pytest.approx(0.1)]


# ---------------------------------------------------------------------------
# monitor: windowed steps/s + per-host HBM watermark
# ---------------------------------------------------------------------------

def test_monitor_windowed_rate_absorbs_hiccup_row(tmp_path):
    from distributed_resnet_tensorflow_tpu.telemetry.monitor import (
        summarize_stream)
    now = 1000.0
    # steady 1 st/s for 20s, then a burst row 1s later (+10 steps): the
    # newest-pair rate would read 10 st/s; the window reads ~1.4
    _write_stream(str(tmp_path / "train"), [
        {"step": 10, "time": now - 21, "loss": 2.0},
        {"step": 20, "time": now - 11, "loss": 1.9},
        {"step": 30, "time": now - 1, "loss": 1.8},
        {"step": 40, "time": now, "loss": 1.7},
    ])
    s = summarize_stream(str(tmp_path / "train"), now=now)
    assert s["steps_per_sec"] == pytest.approx(30 / 21, abs=0.01)


def test_monitor_windowed_rate_survives_step_reset(tmp_path):
    from distributed_resnet_tensorflow_tpu.telemetry.monitor import (
        summarize_stream)
    now = 1000.0
    _write_stream(str(tmp_path / "train"), [
        {"step": 500, "time": now - 40, "loss": 2.0},
        {"step": 600, "time": now - 30, "loss": 1.9},
        {"step": 5, "time": now - 10, "loss": 3.0},   # restarted run
        {"step": 15, "time": now, "loss": 2.8},
    ])
    s = summarize_stream(str(tmp_path / "train"), now=now)
    # only the monotone suffix after the reset counts
    assert s["steps_per_sec"] == pytest.approx(1.0, abs=0.01)


def _memory_row(process, peak, limit=None, live=1000):
    devices = {"0": {"live_bytes": live, "live_peak_bytes": peak}}
    if limit is not None:
        devices["0"].update({"bytes_in_use": live,
                             "peak_bytes_in_use": peak,
                             "bytes_limit": limit})
    return {"event": "memory", "time": 999.0, "step": 50,
            "process": process, "devices": devices,
            "live_bytes_total": live, "live_peak_bytes_total": peak,
            "host_rss_bytes": 10 * 1024 * 1024}


def test_monitor_rolls_up_per_host_hbm_watermark_and_warns(tmp_path):
    from distributed_resnet_tensorflow_tpu.telemetry.monitor import (
        aggregate, render)
    now = 1000.0
    _write_stream(str(tmp_path / "train"), [
        {"step": 50, "time": now - 1, "loss": 1.0},
        _memory_row(0, peak=950, limit=1000)])       # 95% of limit
    _write_stream(str(tmp_path / "train-p1"), [
        _memory_row(1, peak=400, limit=1000)])       # 40%
    agg = aggregate(str(tmp_path), now=now, hbm_warn_frac=0.9)
    mem = agg["memory_by_host"]
    assert set(mem) == {"0", "1"}
    assert mem["0"]["device_peak_bytes"] == 950
    assert mem["0"]["device_peak_frac"] == pytest.approx(0.95)
    assert agg["hbm_warn_hosts"] == ["0"]
    text = render(agg)
    assert "hbm watermark" in text and "!! hbm above 90%" in text
    # under a laxer threshold nothing flags
    agg2 = aggregate(str(tmp_path), now=now, hbm_warn_frac=0.99)
    assert "hbm_warn_hosts" not in agg2
    assert "!! hbm" not in render(agg2)


def test_monitor_memory_rollup_keeps_colocated_serve_distinct(tmp_path):
    """A serving replica shares jax.process_index()==0 with the train
    chief under a shared log_root — its watermark must get its own
    entry, not shadow (or be shadowed by) the trainer's."""
    from distributed_resnet_tensorflow_tpu.telemetry.monitor import (
        aggregate)
    _write_stream(str(tmp_path / "train"), [_memory_row(0, peak=900)])
    _write_stream(str(tmp_path / "serve"), [_memory_row(0, peak=100)])
    agg = aggregate(str(tmp_path), now=1000.0)
    mem = agg["memory_by_host"]
    assert set(mem) == {"0", "0/serve"}
    assert mem["0"]["device_peak_bytes"] == 900
    assert mem["0/serve"]["device_peak_bytes"] == 100


def test_monitor_hbm_line_without_allocator_limit(tmp_path):
    """CPU/portable runs have no bytes_limit: the watermark line renders
    from the live-array peak with no percentage and no warning."""
    from distributed_resnet_tensorflow_tpu.telemetry.monitor import (
        aggregate, render)
    _write_stream(str(tmp_path / "train"), [_memory_row(0, peak=700)])
    agg = aggregate(str(tmp_path), now=1000.0)
    assert agg["memory_by_host"]["0"]["device_peak_bytes"] == 700
    assert "hbm_warn_hosts" not in agg
    assert "hbm watermark" in render(agg)


# ---------------------------------------------------------------------------
# LatencyStats bounded reservoir
# ---------------------------------------------------------------------------

def test_latency_stats_reservoir_is_bounded_and_count_is_true():
    ls = LatencyStats(max_samples_per_key=64)
    for i in range(1000):
        ls.record("bucket_8", i / 1000.0)
    assert len(ls._samples["bucket_8"]) == 64  # memory bound holds
    summary = ls.summary_ms()["bucket_8"]
    assert summary["count"] == 1000  # the true total survives the cap
    # the reservoir is recency-weighted: early (small) samples decay, so
    # the median sits in the later half of the run
    assert summary["p50_ms"] > 250.0


def test_latency_stats_under_cap_keeps_every_sample():
    ls = LatencyStats(max_samples_per_key=64)
    for i in range(10):
        ls.record("k", 0.001 * (i + 1))
    s = ls.summary_ms()["k"]
    assert s["count"] == 10 and s["p50_ms"] == pytest.approx(5.5, abs=0.6)


# ---------------------------------------------------------------------------
# CLI dispatch (main.py trace-merge)
# ---------------------------------------------------------------------------

def test_main_dispatches_trace_merge(tmp_path):
    from distributed_resnet_tensorflow_tpu import main as main_mod
    t_dir = tmp_path / "telemetry"
    t_dir.mkdir()
    (t_dir / "trace.json").write_text(json.dumps(_trace_doc(
        0, 1000.0, [{"name": "train.step", "ph": "X", "pid": 1,
                     "tid": 1, "ts": 10.0, "dur": 5.0}])))
    with pytest.raises(SystemExit) as e:
        main_mod.main(["trace-merge", "--root", str(tmp_path)])
    assert e.value.code == 0
