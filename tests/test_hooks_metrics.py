"""Hooks + metrics writer tests (reference observability, SURVEY.md §2.15)."""
import os

import numpy as np
import pytest

from distributed_resnet_tensorflow_tpu.train.hooks import (
    CheckpointHook, LoggingHook, NanGuardHook, SummaryHook)
from distributed_resnet_tensorflow_tpu.utils.metrics import (
    MetricsWriter, Throughput, read_metrics)


def test_metrics_writer_jsonl_roundtrip(tmp_path):
    w = MetricsWriter(str(tmp_path), enable_tensorboard=False)
    w.write_scalars(10, {"loss": 1.5, "precision": 0.5})
    w.write_scalars(20, {"loss": 1.0, "precision": 0.7})
    w.close()
    recs = read_metrics(str(tmp_path))
    assert len(recs) == 2
    assert recs[0]["step"] == 10 and recs[0]["loss"] == 1.5
    assert recs[1]["precision"] == 0.7


def test_metrics_writer_tensorboard(tmp_path):
    w = MetricsWriter(str(tmp_path), enable_tensorboard=True)
    w.write_scalars(1, {"loss": 2.0})
    w.close()
    # tensorboardX event file written alongside the jsonl
    assert any(f.startswith("events") for f in os.listdir(tmp_path))


def test_logging_hook_cadence():
    lines = []
    h = LoggingHook(every_steps=10, batch_size=128, print_fn=lines.append)
    m = {"loss": np.float32(1.0), "precision": np.float32(0.5),
         "learning_rate": np.float32(0.1)}
    for step in range(1, 31):
        h(step, None, m)
    assert len(lines) == 3
    assert "step 10" in lines[0] and "loss 1.0000" in lines[0]
    # throughput appears once a window exists
    assert "stp/s" in lines[1]


def test_summary_hook_cadence(tmp_path):
    w = MetricsWriter(str(tmp_path), enable_tensorboard=False)
    h = SummaryHook(w, every_steps=5)
    for step in range(1, 11):
        h(step, None, {"loss": float(step)})
    w.close()
    recs = read_metrics(str(tmp_path))
    assert [r["step"] for r in recs] == [5, 10]


def test_throughput_meter():
    t = Throughput(batch_size=64)
    assert t.update(0) == {}
    import time
    time.sleep(0.01)
    out = t.update(10)
    assert out["steps_per_sec"] > 0
    assert np.isclose(out["images_per_sec"], out["steps_per_sec"] * 64)


def test_checkpoint_hook_delegates(tmp_path):
    calls = []

    class FakeMngr:
        def maybe_save(self, step, state):
            calls.append(step)

    h = CheckpointHook(FakeMngr())
    h(7, "state", {})
    assert calls == [7]


def test_nan_guard_hook():
    import pytest
    from distributed_resnet_tensorflow_tpu.train.hooks import NanGuardHook
    h = NanGuardHook(every_steps=10)
    h(10, None, {"loss": 1.0})           # fine
    h(5, None, {"loss": float("nan")})   # off-cadence: not checked
    with pytest.raises(NanGuardHook.NanLossError):
        h(20, None, {"loss": float("nan")})
    seen = []
    h2 = NanGuardHook(every_steps=1, on_nan=lambda s, m: seen.append(s))
    h2(3, None, {"loss": float("inf")})
    assert seen == [3]


def test_write_images(tmp_path):
    w = MetricsWriter(str(tmp_path), enable_tensorboard=True)
    w.write_images(1, "inputs", np.random.rand(2, 8, 8, 3).astype(np.float32))
    w.close()
    assert any(f.startswith("events") for f in os.listdir(tmp_path))


def test_cadence_crossing_with_fused_loops():
    """Hooks observing only loop-end steps (k=3) must still fire when the
    cadence (10) is crossed, even though 10 % 3 != 0."""
    from distributed_resnet_tensorflow_tpu.train.hooks import cadence_crossed
    fired = []
    last = 0
    for step in range(3, 100, 3):   # loop-end steps 3,6,9,12,...
        if cadence_crossed(step, 10, last):
            fired.append(step)
            last = step
    assert fired == [12, 21, 30, 42, 51, 60, 72, 81, 90]

    lines = []
    h = LoggingHook(every_steps=10, print_fn=lines.append)
    for step in range(3, 31, 3):
        h(step, None, {"loss": 1.0})
    assert len(lines) == 3  # crossed 10, 20, 30


def test_checkpoint_manager_crossing_cadence(tmp_path):
    from distributed_resnet_tensorflow_tpu.checkpoint import CheckpointManager
    m = CheckpointManager(str(tmp_path / "x"), save_every_steps=10,
                          save_every_secs=0.0, async_save=False)
    assert not m.should_save(3)
    assert m.should_save(12)          # crossed 10
    m._last_save_step = 12            # as save() would set
    assert not m.should_save(18)
    assert m.should_save(21)          # crossed 20
    m.close()


# ---------------------------------------------------------------------------
# device metrics (jax.Arrays) are read one hook call late (PR 32)
# ---------------------------------------------------------------------------

class _RowWriter:
    """A MetricsWriter's write_scalars, kept in memory."""

    def __init__(self):
        self.rows = []

    def write_scalars(self, step, scalars):
        self.rows.append((step, dict(scalars)))


def _late_reader(kind, every_steps):
    """One of the three hooks that turn metrics into host numbers, and a
    function giving what it has emitted so far as (step, repr of the loss):
    a printed line, a written row, or the guard's ``on_nan`` call."""
    seen = []

    def emitted():
        return [(step, repr(float(loss))) for step, loss in seen]
    if kind == "logging":
        def line(s):
            words = s.split()
            seen.append((int(words[1]), words[3]))
        return LoggingHook(every_steps, print_fn=line), emitted
    if kind == "summary":
        w = _RowWriter()
        return SummaryHook(w, every_steps), lambda: [
            (step, repr(float(row["loss"]))) for step, row in w.rows]
    return NanGuardHook(every_steps, on_nan=lambda s, m: seen.append(
        (s, m["loss"]))), emitted


def _nan():
    import jax.numpy as jnp
    return {"loss": jnp.asarray(float("nan"))}


THREE = pytest.mark.parametrize("kind", ["logging", "summary", "guard"])


@THREE
def test_device_metrics_are_emitted_at_the_next_call(kind):
    """A jax.Array is a future: nothing at the cadence call, the kept
    step's values under the kept step's number at the next call, whatever
    step that is."""
    h, emitted = _late_reader(kind, every_steps=10)
    h(10, None, _nan())
    assert emitted() == []
    h(13, None, {"loss": 7.0})  # off cadence; its own metrics are not read
    assert emitted() == [(10, "nan")]
    h(14, None, {"loss": 7.0})  # a reading is emitted once
    assert emitted() == [(10, "nan")]


@THREE
def test_flush_emits_a_kept_reading_once(kind):
    h, emitted = _late_reader(kind, every_steps=10)
    h.flush()  # nothing kept: nothing happens
    h(20, None, _nan())
    assert emitted() == []
    h.flush()
    assert emitted() == [(20, "nan")]
    h.flush()
    assert emitted() == [(20, "nan")]


@THREE
def test_rollback_drops_a_kept_reading(kind):
    """It belongs to the abandoned timeline: neither the next call nor a
    flush may emit it, and the replayed cadence step is read again."""
    h, emitted = _late_reader(kind, every_steps=10)
    h(20, None, _nan())
    h.rollback_to(15)
    h.flush()
    h(16, None, {"loss": 1.0})
    assert emitted() == []
    h(20, None, _nan())  # replayed
    h(21, None, {"loss": 1.0})
    assert emitted() == [(20, "nan")]


@THREE
def test_host_values_are_read_at_once(kind):
    """The rule is the value's type: a NumPy scalar is no future."""
    h, emitted = _late_reader(kind, every_steps=10)
    h(10, None, {"loss": np.float32("nan")})
    assert emitted() == [(10, "nan")]
    h.flush()
    assert emitted() == [(10, "nan")]


@pytest.mark.parametrize("kind", ["logging", "summary"])
@pytest.mark.parametrize("stride", [1, 3])
def test_late_lines_and_rows_equal_the_immediate_ones(kind, stride):
    """Over 30 steps (every step, or the loop-end steps of k=3): the same
    steps with the same values as host metrics give at once, nothing
    skipped, renumbered or averaged; the last one comes with the flush."""
    import jax.numpy as jnp
    loss = lambda step: 1.0 / step  # noqa: E731
    late, emitted_late = _late_reader(kind, every_steps=10)
    once, emitted_once = _late_reader(kind, every_steps=10)
    for step in range(stride, 31, stride):
        late(step, None, {"loss": jnp.asarray(loss(step), jnp.float32),
                          "precision": jnp.asarray(0.5)})
        once(step, None, {"loss": np.float32(loss(step)),
                          "precision": np.float32(0.5)})
    assert len(emitted_once()) == 3 and len(emitted_late()) == 2
    late.flush()
    assert emitted_late() == emitted_once()
    assert [s for s, _ in emitted_late()] == ([10, 20, 30] if stride == 1
                                              else [12, 21, 30])


def test_a_late_line_keeps_the_order_of_its_columns():
    import jax.numpy as jnp
    lines = []
    h = LoggingHook(every_steps=1, print_fn=lines.append)
    h(1, None, {"precision": jnp.asarray(0.5), "loss": jnp.asarray(2.0),
                "learning_rate": jnp.asarray(0.1),
                "cross_entropy": jnp.asarray(1.5), "grad_norm": jnp.asarray(3.)})
    h.flush()
    assert lines == ["step 1  loss 2.0000  cross_entropy 1.5000  "
                     "precision 0.5000  learning_rate 0.1000"]


def test_logging_hook_stamps_its_window_when_the_late_read_returns():
    """img/s stays the device's rate: the window is stamped with the kept
    step when its values are on the host, not when the step was kept."""
    import jax.numpy as jnp
    lines = []
    h = LoggingHook(every_steps=10, batch_size=4, print_fn=lines.append)
    stamps = []
    update = h.throughput.update
    h.throughput.update = lambda step: stamps.append(step) or update(step)
    h(10, None, {"loss": jnp.asarray(1.0)})
    assert stamps == []
    h(11, None, {"loss": jnp.asarray(1.0)})
    h(20, None, {"loss": jnp.asarray(1.0)})
    assert stamps == [10]
    h.flush()
    assert stamps == [10, 20]
    assert lines[1].startswith("step 20") and "img/s" in lines[1]


def test_nan_guard_raises_late_naming_the_kept_step():
    import jax.numpy as jnp
    h = NanGuardHook(every_steps=10)
    h(10, None, {"loss": jnp.asarray(1.0), "grad_norm": jnp.asarray(2.0)})
    h(20, None, {"loss": jnp.asarray(1.0),
                 "grad_norm": jnp.asarray(float("inf"))})  # kept, 10 was fine
    with pytest.raises(NanGuardHook.NanLossError,
                       match="grad_norm inf at step 20"):
        h(21, None, {"loss": jnp.asarray(1.0)})
    h(30, None, _nan())
    with pytest.raises(NanGuardHook.NanLossError, match="loss nan at step 30"):
        h.flush()
    got = []
    h2 = NanGuardHook(every_steps=1, on_nan=lambda s, m: got.append((s, m)))
    poisoned = _nan()
    h2(3, None, poisoned)
    h2(4, None, {"loss": jnp.asarray(1.0)})
    assert got == [(3, poisoned)] and got[0][1] is poisoned


@pytest.mark.parametrize("save_every", [5, 10])
def test_no_save_at_or_after_a_poisoned_cadence_step(save_every):
    """[NanGuardHook, CheckpointHook] as every hook list orders them: the
    save AT the poisoned step is refused by CheckpointHook's own gate on
    the current metrics, and at the next call the guard's late check
    raises before CheckpointHook runs."""
    import jax.numpy as jnp
    saved = []

    class FakeMngr:
        def should_save(self, step):
            return step % save_every == 0

        def maybe_save(self, step, state):
            saved.append(step)

    hooks = [NanGuardHook(every_steps=10), CheckpointHook(FakeMngr())]
    with pytest.raises(NanGuardHook.NanLossError, match="step 20"):
        for step in range(5, 41, 5):
            m = _nan() if step >= 20 else {"loss": jnp.asarray(1.0)}
            for h in hooks:
                h(step, "state", m)
    assert saved == [s for s in (5, 10, 15) if s % save_every == 0]
    assert step == 25
