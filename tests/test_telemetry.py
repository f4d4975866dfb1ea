"""telemetry/ suite: flight-recorder trace well-formedness (Chrome-trace
JSON, per-thread nesting, ring bound), fake-clock goodput classification,
metrics.jsonl rotation + read-back, decode-process counter ship-back, the
cluster monitor aggregate, and the watchdog's anomaly-triggered dump."""
import glob
import json
import os
import queue
import threading
import time

import numpy as np
import pytest

from distributed_resnet_tensorflow_tpu.telemetry.goodput import (
    CATEGORIES, GoodputMeter, goodput)
from distributed_resnet_tensorflow_tpu.telemetry.tracer import (
    SPAN_CATALOG, SPAN_SCHEMA_VERSION, FlightRecorder, recorder)
from distributed_resnet_tensorflow_tpu.utils.metrics import (
    EVENT_SCHEMAS, MetricsWriter, StageStats, read_metrics)


class FakeWriter:
    def __init__(self):
        self.events = []

    def write_event(self, event, payload):
        self.events.append({"event": event, **payload})

    def flush(self):
        pass


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_trace_dump_is_wellformed_chrome_trace(tmp_path):
    rec = FlightRecorder(ring=1024)
    with rec.span("train.step"):
        with rec.span("input.wait"):
            time.sleep(0.002)
        time.sleep(0.002)

    def worker():
        with rec.span("input.stage"):
            time.sleep(0.002)

    t = threading.Thread(target=worker, name="stage-thread")
    t.start()
    t.join()

    path = rec.dump(str(tmp_path / "trace.json"), reason="test")
    doc = json.load(open(path))  # loads = Perfetto/chrome://tracing accepts
    assert isinstance(doc["traceEvents"], list)
    assert doc["otherData"]["span_schema_version"] == SPAN_SCHEMA_VERSION
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"train.step", "input.wait",
                                       "input.stage"}
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] > 0 and "tid" in e and "pid" in e
    # thread-name metadata lanes for every emitting thread
    meta = [e for e in doc["traceEvents"]
            if e["ph"] == "M" and e["name"] == "thread_name"]
    assert {e["tid"] for e in meta} >= {e["tid"] for e in xs}
    # spans NEST per thread: input.wait lies within train.step's window
    # on the same tid; the other thread's span has a different tid
    by_name = {e["name"]: e for e in xs}
    outer, inner = by_name["train.step"], by_name["input.wait"]
    assert inner["tid"] == outer["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1.0
    assert by_name["input.stage"]["tid"] != outer["tid"]


def test_ring_bound_is_honored():
    rec = FlightRecorder(ring=64)
    for _ in range(500):
        with rec.span("train.step"):
            pass
    assert len(rec) == 64
    assert sum(1 for e in rec.trace_events() if e["ph"] == "X") == 64


def test_disabled_recorder_records_nothing():
    rec = FlightRecorder(ring=64, enabled=False)
    with rec.span("train.step"):
        pass
    assert len(rec) == 0


def test_unknown_span_warns_but_records(caplog):
    rec = FlightRecorder(ring=16)
    with rec.span("totally.unregistered.span"):  # shardcheck: ok(registry-drift)
        pass
    assert len(rec) == 1


def test_dump_without_configuration_is_a_noop():
    rec = FlightRecorder(ring=16)
    assert rec.dump(reason="x") is None  # no dump dir known — never raises


def test_span_catalog_covers_every_emitted_literal():
    """Every span name the package emits resolves in SPAN_CATALOG (the
    registry-drift rule enforces it repo-wide; this pins the catalog
    against accidental deletion) and trace_dump/goodput are registered
    events."""
    assert "goodput" in EVENT_SCHEMAS and "trace_dump" in EVENT_SCHEMAS
    for name in ("input.wait", "train.step", "eval.round",
                 "checkpoint.save", "serve.batch", "restore"):
        assert name in SPAN_CATALOG


# ---------------------------------------------------------------------------
# one clock: spans in a profiler trace; one timer: spans charge the counters
# ---------------------------------------------------------------------------

def _tiny_trainer(**overrides):
    from distributed_resnet_tensorflow_tpu.train import Trainer
    from distributed_resnet_tensorflow_tpu.utils.config import get_preset
    cfg = get_preset("smoke")
    cfg.model.compute_dtype = "float32"
    cfg.model.resnet_size = 8
    cfg.model.num_classes = 4
    cfg.data.image_size = 8
    cfg.train.batch_size = 16
    for key, value in overrides.items():
        cfg.override(key, value)
    tr = Trainer(cfg)
    tr.init_state()
    return tr


def _host_events(trace_dir, names):
    """(name, start_ns, duration_ns, stats, line) of the host plane's
    events under ``names``, from the one .xplane.pb under trace_dir."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(files) == 1, files
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    out.append((e.name, e.start_ns, e.duration_ns,
                                dict(e.stats), line.name))
    return out


def test_spans_enter_the_profiler_trace_inside_their_ring_interval(tmp_path):
    """Under a profiler session every span is an event on the host plane
    of the .xplane.pb, under its own name, on the profiler's clock, inside
    the ring entry's interval; train.step is the trace's step event. A
    span that ran with no session listening is not there."""
    import jax

    from distributed_resnet_tensorflow_tpu.data import (
        learnable_synthetic_iterator)
    tr = _tiny_trainer()
    it = learnable_synthetic_iterator(16, 8, 4)
    tr.train(it, num_steps=2)  # compile outside the session
    jax.block_until_ready(tr.state)
    with recorder.span("input.stack"):  # no session: ring only
        pass
    recorder.clear()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with recorder.span("input.wait"):
            time.sleep(0.003)
        tr.train(it, num_steps=5, start_step=2)
        jax.block_until_ready(tr.state)
    finally:
        jax.profiler.stop_trace()
    ring = [e for e in recorder.trace_events() if e["ph"] == "X"]
    names = {"input.wait", "train.step", "train.hooks", "input.stack"}
    host = _host_events(tmp_path, names)
    assert "input.stack" not in {h[0] for h in host}
    assert {"input.wait", "train.step", "train.hooks"} <= {h[0] for h in host}
    steps = sorted((h for h in host if h[0] == "train.step"),
                   key=lambda h: h[1])
    assert [h[3].get("step_num") for h in steps] == [2, 3, 4]
    assert all(h[3].get("_r") == 1 for h in steps)  # a step event
    # the two clocks differ by one offset: pair the k-th event of a name
    # in the trace with the k-th in the ring, take the median offset, and
    # hold every annotation inside its ring interval (200 us of slack
    # for the drift between the two clocks over the session)
    pairs = []
    for name in ("input.wait", "train.step", "train.hooks"):
        mine = sorted((e for e in ring if e["name"] == name),
                      key=lambda e: e["ts"])
        theirs = sorted((h for h in host if h[0] == name),
                        key=lambda h: h[1])
        assert len(mine) == len(theirs), name
        pairs += list(zip(mine, theirs))
    offset = float(np.median([e["ts"] - h[1] / 1e3 for e, h in pairs]))
    for e, h in pairs:
        start, end = h[1] / 1e3 + offset, (h[1] + h[2]) / 1e3 + offset
        assert e["ts"] - 200 <= start <= end <= e["ts"] + e["dur"] + 200, \
            (e, h)
        assert h[2] / 1e3 <= e["dur"] + 1  # inside: never longer
    # the loop thread's three spans share one line of the host plane
    assert len({h[4] for _, h in pairs}) == 1


def test_disabled_span_is_the_shared_noop_and_charges_nothing():
    from distributed_resnet_tensorflow_tpu.telemetry import tracer
    from distributed_resnet_tensorflow_tpu.utils.metrics import input_stages
    input_stages.reset()
    rec = FlightRecorder(ring=64, enabled=False)
    sp = rec.span("input.wait", category="input_wait")
    assert sp is tracer._NOOP and sp is rec.span("train.step", step_num=3)
    with sp as inside:
        pass
    inside.charge("dispatch_wait", items=1)
    assert inside.seconds is None and len(rec) == 0
    assert input_stages.snapshot() == {}


@pytest.mark.parametrize("name", sorted(SPAN_CATALOG))
def test_every_span_charges_a_cell_under_its_own_name(name):
    """One timer: the span's exit leaves (count, seconds) under its name in
    input_stages, so a reader of the snapshot needs no second counter."""
    from distributed_resnet_tensorflow_tpu.utils.metrics import input_stages
    input_stages.reset()
    rec = FlightRecorder(ring=16)
    for _ in range(3):
        with rec.span(name) as sp:
            pass
    cell = input_stages.snapshot()[name]
    assert cell["count"] == 3 and cell["items"] == 0 and cell["bytes"] == 0
    assert 0 < cell["seconds"] < 1 and sp.seconds <= cell["seconds"]
    assert len(rec) == 3


def test_span_charge_feeds_a_legacy_stage_from_its_own_duration():
    from distributed_resnet_tensorflow_tpu.utils.metrics import input_stages
    input_stages.reset()
    rec = FlightRecorder(ring=16)
    with rec.span("input.transfer") as sp:
        time.sleep(0.002)
    sp.charge("transfer", items=7, nbytes=11, extra_s=0.5)
    snap = input_stages.snapshot()
    assert snap["transfer"]["count"] == 1 and snap["transfer"]["items"] == 7
    assert snap["transfer"]["bytes"] == 11
    assert snap["transfer"]["seconds"] == pytest.approx(sp.seconds + 0.5)
    assert snap["input.transfer"]["seconds"] == pytest.approx(sp.seconds)


@pytest.mark.parametrize("coalesced", ["on", "off"])
def test_put_paths_keep_the_legacy_stage_cells(coalesced, mesh8):
    """N puts through device_prefetch: the coalesced stager charges N
    `stage` and N + N `transfer` cells (issue, then completion wait) with
    the items and bytes of its packs; the per-leaf path charges ONE
    `transfer` cell a batch (issue + wait) and no `stage` — what the
    benchmark's stage_ms divides by."""
    from distributed_resnet_tensorflow_tpu.data.device_prefetch import (
        device_prefetch)
    from distributed_resnet_tensorflow_tpu.parallel.sharding import (
        CoalescedStager, shard_batch)
    from distributed_resnet_tensorflow_tpu.utils.metrics import input_stages
    n, b = 5, 16
    rng = np.random.RandomState(0)
    batches = [{"images": rng.rand(b, 8, 8, 3).astype(np.float32),
                "labels": rng.randint(0, 4, (b,)).astype(np.int32)}
               for _ in range(n)]
    input_stages.reset()
    if coalesced == "on":
        stager = CoalescedStager(mesh8, ring=3)
        direct = [stager.put(dict(x)) for x in batches]
        snap = input_stages.snapshot()
        nbytes = n * direct[0].flat.size
        for stage, span_name in (("stage", "input.stage"),
                                 ("transfer", "input.issue")):
            assert snap[stage]["count"] == n == snap[span_name]["count"]
            assert snap[stage]["items"] == n * b
            assert snap[stage]["bytes"] == nbytes
            assert snap[stage]["seconds"] == pytest.approx(
                snap[span_name]["seconds"])
        put = stager
    else:
        put = lambda x: shard_batch(x, mesh8)  # noqa: E731
    input_stages.reset()
    got = list(device_prefetch(iter(batches), put, depth=2))
    assert len(got) == n
    snap = input_stages.snapshot()
    if coalesced == "on":
        assert snap["stage"]["count"] == n
        assert snap["transfer"]["count"] == 2 * n
        assert snap["transfer"]["items"] == n * b
        assert snap["input.finalize"]["count"] == n
    else:
        assert "stage" not in snap and "input.finalize" not in snap
        assert snap["transfer"]["count"] == n
        assert snap["transfer"]["items"] == n * b
        assert snap["transfer"]["seconds"] == pytest.approx(
            snap["input.issue"]["seconds"] + snap["input.transfer"]["seconds"])
    assert snap["input.issue"]["count"] == snap["input.transfer"]["count"] == n


# ---------------------------------------------------------------------------
# names the device trace keeps: scopes on the step's ops, names on kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("zero1", ["off", "on"])
def test_train_step_ops_carry_forward_backward_optimizer_scopes(zero1):
    """The lowered step (debug info on) names its ops by phase, so a
    profiler trace can split the step's device time: forward, its
    transpose (the backward pass) and the optimizer (plain and ZeRO-1)."""
    tr = _tiny_trainer(**{"optimizer.zero1": zero1})
    assert tr.zero1_active == (zero1 == "on")
    batch = {"images": np.zeros((16, 8, 8, 3), np.float32),
             "labels": np.zeros((16,), np.int32)}
    text = tr.jitted_train_step().lower(
        tr.state, tr._put_batch(batch)).as_text(debug_info=True)
    for scope in ("/jvp(forward)/", "/transpose(jvp(forward))/",
                  "/optimizer/"):
        assert scope in text, scope


@pytest.mark.parametrize("augment", [None, ("images", "imagenet_train", 0)])
def test_unpack_program_ops_carry_unpack_and_augment_scopes(augment, mesh8):
    from distributed_resnet_tensorflow_tpu.parallel.sharding import (
        CoalescedStager)
    rng = np.random.RandomState(0)
    batch = {"images": rng.randint(0, 256, (8, 8, 8, 3)).astype(np.uint8),
             "labels": rng.randint(0, 10, (8,)).astype(np.int32)}
    staged = CoalescedStager(mesh8, ring=3, augment=augment).put(batch)
    text = staged._unpack.lower(staged.flat).as_text(debug_info=True)
    assert "/unpack/" in text
    assert ("/augment/" in text) == (augment is not None)


@pytest.mark.parametrize("kernel", [
    "softmax_xent_fwd", "softmax_xent_bwd",
    "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"])
def test_pallas_kernels_lower_under_their_names(kernel):
    """Lowered for the TPU (no chip needed to lower), each Pallas call is
    a custom call whose kernel_name is the stable name a trace's events
    are found by."""
    import jax
    import jax.numpy as jnp
    if kernel.startswith("softmax"):
        from distributed_resnet_tensorflow_tpu.ops.pallas import softmax_xent
        labels = jnp.zeros((16,), jnp.int32)
        fn = jax.value_and_grad(lambda x: softmax_xent(x, labels).sum())
        args = (jnp.ones((16, 1001), jnp.float32),)
    else:
        from distributed_resnet_tensorflow_tpu.ops.pallas import (
            flash_attention)
        fn = jax.value_and_grad(
            lambda q, k, v: flash_attention(q, k, v).astype(
                jnp.float32).sum(), argnums=(0, 1, 2))
        args = (jnp.ones((1, 256, 2, 64), jnp.bfloat16),) * 3
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert f'kernel_name = "{kernel}"' in text


# ---------------------------------------------------------------------------
# goodput classification (fake clock)
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def test_goodput_interval_classifies_and_sums_to_100():
    clock = FakeClock()
    m = GoodputMeter(clock=clock)
    m.rebase()
    clock.t += 10.0
    m.add("input_wait", 2.0)
    m.add("checkpoint", 1.0)
    m.add("eval", 0.5)
    itv = m.interval()
    assert itv["wall_secs"] == 10.0
    assert itv["seconds"]["compute"] == pytest.approx(6.5)
    assert itv["seconds"]["input_wait"] == pytest.approx(2.0)
    assert set(itv["pct"]) == set(CATEGORIES)
    assert sum(itv["pct"].values()) == pytest.approx(100.0, abs=0.1)
    # the next interval starts fresh
    clock.t += 4.0
    m.add("stall", 4.0)
    itv2 = m.interval()
    assert itv2["seconds"]["compute"] == pytest.approx(0.0)
    assert itv2["seconds"]["stall"] == pytest.approx(4.0)
    assert itv2["seconds"]["input_wait"] == pytest.approx(0.0)


def test_goodput_overmeasured_interval_normalizes():
    """Charges exceeding the wall (a second thread charging the same
    window) clamp compute at 0 and normalize pct over the measured sum —
    never >100% total."""
    clock = FakeClock()
    m = GoodputMeter(clock=clock)
    m.rebase()
    clock.t += 5.0
    m.add("checkpoint", 8.0)
    itv = m.interval()
    assert itv["seconds"]["compute"] == 0.0
    assert sum(itv["pct"].values()) == pytest.approx(100.0, abs=0.1)


def test_goodput_first_interval_without_rebase_is_empty():
    m = GoodputMeter(clock=FakeClock())
    itv = m.interval()
    assert itv["wall_secs"] == 0.0


def test_nested_categorized_spans_charge_outermost_only():
    before = goodput.snapshot()
    with recorder.span("eval.round", category="eval"):
        with recorder.span("input.wait", category="input_wait"):
            time.sleep(0.002)
        time.sleep(0.002)
    after = goodput.snapshot()
    assert after.get("eval", 0) > before.get("eval", 0)
    # the inner categorized span charged NOTHING (outermost-span rule)
    assert after.get("input_wait", 0) == pytest.approx(
        before.get("input_wait", 0))


def test_goodput_hook_emits_registered_event(tmp_path):
    from distributed_resnet_tensorflow_tpu.train.hooks import GoodputHook
    w = MetricsWriter(str(tmp_path), enable_tensorboard=False)
    hook = GoodputHook(w, every_steps=10)
    hook.reset_window()
    goodput.add("input_wait", 0.001)
    time.sleep(0.005)
    hook(10, None, {})
    w.close()
    rows = [r for r in read_metrics(str(tmp_path))
            if r.get("event") == "goodput"]
    assert rows, "no goodput row emitted"
    row = rows[-1]
    assert row["step"] == 10
    assert set(row["pct"]) == set(CATEGORIES)
    assert sum(row["pct"].values()) == pytest.approx(100.0, abs=0.5)


# ---------------------------------------------------------------------------
# metrics.jsonl rotation
# ---------------------------------------------------------------------------

def test_metrics_rotation_bounds_size_and_reads_in_order(tmp_path):
    w = MetricsWriter(str(tmp_path), enable_tensorboard=False,
                      max_bytes=600, max_segments=3)
    for i in range(60):
        w.write_scalars(i, {"loss": float(i)})
    w.close()
    base = os.path.join(str(tmp_path), "metrics.jsonl")
    segs = sorted(glob.glob(base + ".*"))
    assert segs, "no rotation happened"
    assert len(segs) <= 3
    # every file honors the bound (±1 row slack by construction)
    for p in segs + [base]:
        assert os.path.getsize(p) <= 600 + 120
    rows = read_metrics(str(tmp_path))
    steps = [r["step"] for r in rows]
    # one continuous, ordered stream ending at the newest row; the oldest
    # rows beyond the segment budget are gone
    assert steps == sorted(steps)
    assert steps[-1] == 59
    assert len(set(steps)) == len(steps)


def test_read_metrics_tolerant_skips_torn_tail(tmp_path):
    w = MetricsWriter(str(tmp_path), enable_tensorboard=False)
    w.write_scalars(1, {"loss": 1.0})
    w.close()
    with open(os.path.join(str(tmp_path), "metrics.jsonl"), "a") as f:
        f.write('{"step": 2, "loss"')  # torn mid-write
    with pytest.raises(ValueError):
        read_metrics(str(tmp_path))
    rows = read_metrics(str(tmp_path), tolerant=True)
    assert [r["step"] for r in rows] == [1]


def test_rotation_off_by_default_threshold_not_hit(tmp_path):
    w = MetricsWriter(str(tmp_path), enable_tensorboard=False)
    for i in range(20):
        w.write_scalars(i, {"loss": 0.0})
    w.close()
    assert not glob.glob(os.path.join(str(tmp_path), "metrics.jsonl.*"))
    assert len(read_metrics(str(tmp_path))) == 20


# ---------------------------------------------------------------------------
# decode-process stage-counter ship-back (satellite)
# ---------------------------------------------------------------------------

def test_stage_stats_worker_merge_keeps_busiest_worker_honest():
    s = StageStats()
    s.add("decode", 2.0, items=10, worker=("decode-proc", 0))
    s.add("decode", 3.0, items=20, worker=("decode-proc", 1))
    s.add("decode", 1.0, items=5, worker=("decode-proc", 0))
    snap = s.snapshot()["decode"]
    assert snap["workers"] == 2
    assert snap["items"] == 35
    assert snap["seconds"] == pytest.approx(6.0)
    # busiest worker = proc0's 3.0 cumulative, not the 6.0 sum
    assert snap["max_thread_seconds"] == pytest.approx(3.0)


def _jpeg_bytes(size=48):
    import io

    from PIL import Image
    img = np.random.RandomState(0).randint(0, 256, (size, size, 3), np.uint8)
    buf = io.BytesIO()
    Image.fromarray(img).save(buf, "JPEG")
    return buf.getvalue()


def test_decode_loop_process_mode_ships_counter_deltas():
    """Process-mode _decode_loop (stop=None) must put _StageDelta rows on
    the result queue BEFORE its _END marker — the parent stops consuming
    at the n-th _END, so a later delta would be lost."""
    from distributed_resnet_tensorflow_tpu.data.imagenet import (
        _decode_loop, _END, _EndMarker, _StageDelta)
    jpeg = _jpeg_bytes()
    in_q, out_q = queue.Queue(), queue.Queue()
    for _ in range(3):
        in_q.put((jpeg, 1))
    in_q.put(_END)
    _decode_loop(in_q, out_q, wseed=0, is_train=False, image_size=32,
                 native_decode=False, emit_uint8=True, stop=None, widx=7)
    items = []
    while not out_q.empty():
        items.append(out_q.get_nowait())
    deltas = [i for i in items if isinstance(i, _StageDelta)]
    ends = [i for i, it in enumerate(items) if isinstance(it, _EndMarker)]
    assert deltas and sum(d.count for d in deltas) == 3
    assert all(d.widx == 7 for d in deltas)
    assert all(d.seconds > 0 for d in deltas)
    delta_idx = [i for i, it in enumerate(items)
                 if isinstance(it, _StageDelta)]
    assert max(delta_idx) < min(ends), "delta after _END would be dropped"


def test_decode_loop_with_telemetry_off_ships_no_counters():
    """telemetry.enabled=false: the decode span is the shared no-op, so
    the loop has no measured duration to count and ships no delta."""
    from distributed_resnet_tensorflow_tpu.data.imagenet import (
        _decode_loop, _END, _StageDelta)
    in_q, out_q = queue.Queue(), queue.Queue()
    in_q.put((_jpeg_bytes(), 1))
    in_q.put(_END)
    recorder.configure(enabled=False)
    try:
        _decode_loop(in_q, out_q, wseed=0, is_train=False, image_size=32,
                     native_decode=False, emit_uint8=True, stop=None)
    finally:
        recorder.configure(enabled=True)
    items = []
    while not out_q.empty():
        items.append(out_q.get_nowait())
    assert len(items) == 2  # the image and the end marker
    assert not any(isinstance(i, _StageDelta) for i in items)


def test_decode_process_counters_merge_into_parent_registry(tmp_path):
    """E2E: decode_processes > 0 leaves decode busy-time in the PARENT's
    input_stages — the attribution gap this satellite closes."""
    from test_imagenet_data import _write_fake_imagenet

    from distributed_resnet_tensorflow_tpu.data.imagenet import (
        imagenet_iterator)
    from distributed_resnet_tensorflow_tpu.utils.metrics import input_stages
    d, total = _write_fake_imagenet(tmp_path, mode="validation")
    input_stages.reset()
    it = imagenet_iterator(d, batch_size=5, mode="eval", image_size=32,
                           decode_processes=1)
    n = 0
    for b in it:
        mask = b.get("mask", np.ones(len(b["labels"])))
        n += int(mask.sum())
    assert n == total
    snap = input_stages.snapshot()
    assert "decode" in snap, "no decode counters merged from the worker"
    assert snap["decode"]["items"] == total
    assert snap["decode"]["seconds"] > 0


# ---------------------------------------------------------------------------
# cluster monitor
# ---------------------------------------------------------------------------

def _write_stream(d, rows):
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "metrics.jsonl"), "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


def test_monitor_aggregates_two_host_streams(tmp_path):
    from distributed_resnet_tensorflow_tpu.telemetry.monitor import aggregate
    now = 1000.0
    _write_stream(str(tmp_path / "host0" / "train"), [
        {"step": 100, "time": now - 20, "loss": 2.0},
        {"step": 200, "time": now - 10, "loss": 1.5},
        {"event": "goodput", "time": now - 10, "step": 200,
         "wall_secs": 10.0,
         "seconds": {c: 0.0 for c in CATEGORIES},
         "pct": {"compute": 80.0, "input_wait": 20.0, "checkpoint": 0.0,
                 "eval": 0.0, "stall": 0.0, "restart": 0.0}},
    ])
    _write_stream(str(tmp_path / "host1" / "train"), [
        {"step": 100, "time": now - 20, "loss": 2.1},
        {"step": 150, "time": now - 10, "loss": 1.9},
    ])
    hb = tmp_path / "heartbeats"
    hb.mkdir()
    for pid, step in ((0, 200), (1, 150)):
        (hb / f"proc{pid}.json").write_text(json.dumps({
            "process_id": pid, "pid": 10 + pid, "host": f"h{pid}",
            "seq": 9, "step": step, "progress": step, "phase": "train",
            "wall_time": now - 1}))
    agg = aggregate(str(tmp_path), now=now)
    assert set(agg["streams"]) == {os.path.join("host0", "train"),
                                   os.path.join("host1", "train")}
    s0 = agg["streams"][os.path.join("host0", "train")]
    assert s0["step"] == 200
    assert s0["steps_per_sec"] == pytest.approx(10.0)
    assert s0["goodput_pct"] == pytest.approx(80.0)
    s1 = agg["streams"][os.path.join("host1", "train")]
    assert s1["steps_per_sec"] == pytest.approx(5.0)
    # cluster headline: the fastest (chief) stream leads
    assert agg["steps_per_sec"] == pytest.approx(10.0)
    assert agg["goodput"]["compute"] == pytest.approx(80.0)
    assert set(agg["hosts"]) == {"0", "1"}
    assert agg["host_step_skew"] == 50
    assert "stale_hosts" not in agg


def test_monitor_once_json_cli(tmp_path, capsys):
    from distributed_resnet_tensorflow_tpu.telemetry.monitor import (
        main_monitor, render)
    _write_stream(str(tmp_path / "train"), [
        {"step": 10, "time": time.time() - 5, "loss": 1.0},
        {"step": 20, "time": time.time(), "loss": 0.9},
    ])
    rc = main_monitor(["--root", str(tmp_path), "--once", "--json"])
    assert rc == 0
    agg = json.loads(capsys.readouterr().out)
    assert "train" in agg["streams"]
    assert agg["streams"]["train"]["step"] == 20
    # the text renderer stays crash-free on the same aggregate
    assert "drt monitor" in render(agg)


def test_monitor_tolerates_torn_and_empty_streams(tmp_path):
    from distributed_resnet_tensorflow_tpu.telemetry.monitor import aggregate
    d = tmp_path / "train"
    d.mkdir(parents=True)
    (d / "metrics.jsonl").write_text('{"step": 1, "time": 1.0}\n{"torn')
    agg = aggregate(str(tmp_path))
    assert agg["streams"]["train"]["step"] == 1


def test_monitor_fleet_rollup_spans_rotation_mid_ladder(tmp_path):
    """A rotation landing in the middle of a replace ladder (kill/respawn
    in the rotated segment, readmit in the live file, plus a torn tail)
    must not lose the ladder: the fleet rollup surfaces the newest rung
    and the joined stream replays protocol-conformant (ISSUE 20)."""
    from distributed_resnet_tensorflow_tpu.analysis.protocol import (
        check_stream)
    from distributed_resnet_tensorflow_tpu.telemetry.monitor import aggregate
    now = 1000.0
    d = tmp_path / "route"
    d.mkdir(parents=True)
    rotated = [
        {"event": "route", "time": now - 30, "requests": 500,
         "completed": 480, "errors": 0, "shed": 0, "qps": 25.0,
         "p99_ms": 40.0},
        {"event": "replica_health", "time": now - 21, "replica": 0,
         "from": "ready", "to": "dead", "reason": "beat_stale"},
        {"event": "replica_replace", "time": now - 20, "replica": 0,
         "action": "kill", "reason": "wedged"},
        {"event": "replica_replace", "time": now - 15, "replica": 0,
         "action": "respawn"},
    ]
    live = [
        {"event": "replica_replace", "time": now - 5, "replica": 0,
         "action": "readmit"},
        {"event": "replica_health", "time": now - 4, "replica": 0,
         "from": "dead", "to": "warming", "reason": "readmit"},
        {"event": "route", "time": now - 1, "requests": 600,
         "completed": 575, "errors": 1, "shed": 0, "qps": 26.0,
         "p99_ms": 41.0},
    ]
    (d / "metrics.jsonl.1").write_text(
        "".join(json.dumps(r) + "\n" for r in rotated))
    (d / "metrics.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in live)
        + '{"event": "replica_re')                    # torn mid-write
    agg = aggregate(str(tmp_path), now=now)
    fleet = agg["fleet"]
    assert fleet["requests"] == 600                   # live file leads
    assert fleet["replica_replace"]["action"] == "readmit"
    assert fleet["replica_replace"]["replica"] == 0
    # the ladder that spans the rotation replays as ONE legal round
    assert check_stream(str(d / "metrics.jsonl")) == []


def test_monitor_elastic_rollup_spans_rotation_mid_round(tmp_path):
    """A reshard round split by rotation (the reshard row in the rotated
    segment, the new generation's mesh row in the live file): the
    elastic rollup sees generation + reason, and the step rate bridges
    the rotation boundary instead of resetting."""
    from distributed_resnet_tensorflow_tpu.analysis.protocol import (
        check_stream)
    from distributed_resnet_tensorflow_tpu.telemetry.monitor import aggregate
    now = 1000.0
    d = tmp_path / "train"
    d.mkdir(parents=True)
    rotated = [
        {"step": 80, "time": now - 20, "loss": 2.0},
        {"step": 90, "time": now - 15, "loss": 1.9},
        {"event": "reshard", "time": now - 12, "generation": 2,
         "reason": "peer_lost", "old_hosts": 2, "new_hosts": 1,
         "restore_step": 90},
    ]
    live = [
        {"event": "mesh_generation", "time": now - 8, "generation": 2,
         "hosts": 1, "devices": 8, "step": 90},
        {"step": 110, "time": now - 5, "loss": 1.8},
        {"step": 120, "time": now, "loss": 1.7},
    ]
    (d / "metrics.jsonl.1").write_text(
        "".join(json.dumps(r) + "\n" for r in rotated))
    (d / "metrics.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in live)
        + '{"step": 121, "ti')                        # torn mid-write
    agg = aggregate(str(tmp_path), now=now)
    assert agg["mesh_generation"] == 2
    assert agg["last_reshard"]["reason"] == "peer_lost"
    assert agg["last_reshard"]["new_hosts"] == 1
    s = agg["streams"]["train"]
    assert s["step"] == 120
    # (120 - 80) steps over 20 s across the rotation boundary
    assert s["steps_per_sec"] == pytest.approx(2.0)
    assert check_stream(str(d / "metrics.jsonl")) == []


# ---------------------------------------------------------------------------
# watchdog anomaly hook
# ---------------------------------------------------------------------------

def test_watchdog_escalation_dumps_flight_record(tmp_path):
    """A hang escalation must leave trace.json + a trace_dump metrics row
    + a goodput stall charge — the automatic flight-recorder contract
    (the live 2-process frozen-peer path is scripts/chaos_smoke.sh)."""
    from distributed_resnet_tensorflow_tpu.resilience.heartbeat import (
        HeartbeatPublisher, BeatTransport)
    from distributed_resnet_tensorflow_tpu.resilience.watchdog import Watchdog
    from distributed_resnet_tensorflow_tpu.utils.config import WatchdogConfig

    class NullTransport(BeatTransport):
        def publish(self, beat):
            pass

        def peers(self):
            return {}

    dump_dir = str(tmp_path / "telemetry")
    stub = FakeWriter()
    recorder.configure(dump_dir=dump_dir, writer=stub, process_index=0)
    try:
        with recorder.span("train.step"):
            pass
        clock = FakeClock()
        publisher = HeartbeatPublisher(NullTransport(), 0, clock=clock)
        publisher.update(step=3, phase="train")
        stall_before = goodput.snapshot().get("stall", 0.0)
        clock.t += 42.0
        wd = Watchdog(NullTransport(), publisher, 0, 2,
                      WatchdogConfig(), writer=FakeWriter(),
                      clock=clock, exit_fn=lambda code: None)
        wd._escalate("hang", 75, "no progress for 42s", now=clock.t)
        path = os.path.join(dump_dir, "trace.json")
        assert os.path.exists(path)
        doc = json.load(open(path))
        assert doc["otherData"]["reason"] == "hang"
        assert any(e.get("name") == "train.step"
                   for e in doc["traceEvents"])
        dumps = [e for e in stub.events if e["event"] == "trace_dump"]
        assert dumps and dumps[0]["reason"] == "hang"
        assert dumps[0]["span_schema_version"] == SPAN_SCHEMA_VERSION
        assert goodput.snapshot()["stall"] - stall_before == \
            pytest.approx(42.0)
    finally:
        recorder._writer = None  # don't leak the stub into other tests
