"""Input-pipeline stage telemetry tests (fast, `-m 'not slow'` CI smoke).

The overlapped input pipeline's attribution (the benchmark's stage_ms,
docs/input_pipeline.md) is computed FROM the stage counters in
utils.metrics.input_stages — if those counters silently rot, the benchmark
would keep printing an attribution built on nothing. This suite pins the
contract: counters populate during real training, are monotone, and export
through MetricsWriter/InputStagesHook to metrics.jsonl.
"""
import threading

import numpy as np

from distributed_resnet_tensorflow_tpu.utils.metrics import (
    MetricsWriter, StageStats, input_stages, read_metrics)


def test_stage_stats_accumulate_and_rates():
    s = StageStats()
    s.add("decode", 0.5, items=10, nbytes=100)
    s.add("decode", 0.5, items=10, nbytes=100)
    s.add("transfer", 0.25, items=20)
    snap = s.snapshot()
    assert snap["decode"]["count"] == 2
    assert snap["decode"]["items"] == 20
    assert np.isclose(snap["decode"]["seconds"], 1.0)
    assert snap["decode"]["bytes"] == 200
    assert np.isclose(s.rates()["decode"], 20.0)
    assert np.isclose(s.rates()["transfer"], 80.0)
    s.reset()
    assert s.snapshot() == {}


def test_stage_stats_per_thread_rate_estimate():
    """A 4-worker stage that spent 1 thread-second per worker on 100 items
    ran at ~100 items/s (items / busiest thread), not 25."""
    s = StageStats()
    barrier = threading.Barrier(4)

    def worker():
        s.add("decode", 1.0, items=25)
        barrier.wait(5)  # keep all 4 threads alive at once (no ident reuse)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = s.snapshot()
    assert snap["decode"]["workers"] == 4
    assert np.isclose(snap["decode"]["seconds"], 4.0)
    assert np.isclose(snap["decode"]["max_thread_seconds"], 1.0)
    assert np.isclose(s.rates()["decode"], 100.0)


def test_pipeline_counters_populated_and_monotone():
    """The CI tripwire for attribution telemetry: a real (tiny) training
    run must populate the staging counters, and they must be monotone in
    work done — so a counter-based attribution can't silently read an
    empty registry."""
    from distributed_resnet_tensorflow_tpu.data import (
        learnable_synthetic_iterator)
    from distributed_resnet_tensorflow_tpu.train import Trainer
    from distributed_resnet_tensorflow_tpu.utils.config import get_preset

    cfg = get_preset("smoke")
    cfg.model.compute_dtype = "float32"
    cfg.model.resnet_size = 8
    cfg.model.num_classes = 4
    cfg.data.image_size = 8
    cfg.train.batch_size = 16
    cfg.data.coalesced_transfer = "on"   # auto resolves off on CPU
    input_stages.reset()
    tr = Trainer(cfg)
    tr.init_state()
    it = learnable_synthetic_iterator(16, 8, 4)
    tr.train(it, num_steps=2)
    snap1 = input_stages.snapshot()
    for stage in ("stage", "transfer", "dispatch_wait"):
        assert stage in snap1, (stage, sorted(snap1))
        assert snap1[stage]["count"] > 0
        assert snap1[stage]["seconds"] >= 0.0
    assert snap1["stage"]["items"] >= 2 * 16
    assert snap1["stage"]["bytes"] > 0
    tr.train(it, num_steps=4, start_step=2)
    snap2 = input_stages.snapshot()
    for stage in ("stage", "transfer"):
        assert snap2[stage]["count"] >= snap1[stage]["count"]
        assert snap2[stage]["items"] >= snap1[stage]["items"]
        assert snap2[stage]["seconds"] >= snap1[stage]["seconds"]
    assert snap2["stage"]["items"] > snap1["stage"]["items"]


def test_input_stages_hook_writes_event(tmp_path):
    from distributed_resnet_tensorflow_tpu.train.hooks import InputStagesHook

    input_stages.reset()
    input_stages.add("decode", 0.1, items=5)
    w = MetricsWriter(str(tmp_path), enable_tensorboard=False)
    hook = InputStagesHook(w, every_steps=10)
    hook(5, None, {})     # below cadence: no record
    hook(10, None, {})    # fires
    w.write_scalars(11, {"loss": 1.0})
    w.close()
    recs = read_metrics(str(tmp_path))
    events = [r for r in recs if r.get("event") == "input_stages"]
    scalars = [r for r in recs if "event" not in r]
    assert len(events) == 1
    assert events[0]["step"] == 10
    assert events[0]["stages"]["decode"]["items"] == 5
    # scalar consumers can still filter rows by the "event" key
    assert scalars and scalars[0]["loss"] == 1.0
