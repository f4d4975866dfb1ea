"""The trace reduction on a hand-built trace with known answers."""
import pytest

from benchmark.harness import trace

MS = 1e6  # ns


def _trace():
    """Device 0: three executions of ``jit_step`` at 0, 100 and 200 ms.
    In each period: ``fusion.1`` 0-40, ``convolution.2`` 30-60 (overlaps the
    fusion by 10), an ``all-reduce.3`` 60-80 half hidden behind ``copy.4``
    70-90, then idle until the next period. A ``while.9`` holds the first
    period's operations. A small ``jit_unpack`` module runs at 95."""
    ops, modules = [], []
    for i in range(3):
        t = i * 100 * MS
        modules.append(("jit_step(123)", t, 90 * MS))
        modules.append(("jit_unpack(7)", t + 95 * MS, 2 * MS))
        ops += [("fusion.1", t, 40 * MS), ("convolution.2", t + 30 * MS, 30 * MS),
                ("all-reduce.3", t + 60 * MS, 20 * MS), ("copy.4", t + 70 * MS, 20 * MS),
                ("unpack_fusion", t + 95 * MS, 2 * MS)]
    ops.append(("while.9", 0.0, 90 * MS))
    host = [("XlaLinearize", 91 * MS, 3 * MS), ("main", 0.0, 300 * MS),
            ("pjrt-tpu-tasks/5864:XlaLinearize", 191 * MS, 3.5 * MS)]
    dev1_ops = [("fusion.1", i * 100 * MS, 50 * MS) for i in range(3)]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": modules},
                                             {"name": "XLA Ops", "events": ops},
                                             {"name": "Steps", "events": []}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": dev1_ops}]},
        {"name": "/host:CPU", "lines": [{"name": "pjrt-tpu-tasks/5864", "events": host}]},
    ]}


def test_union_counts_overlap_once():
    assert trace.union([(0, 40), (30, 60), (70, 80)]) == [(0, 60), (70, 80)]


def test_window_is_aligned_to_the_step_module():
    r = trace.reduce(_trace(), steps_per_dispatch=8)
    assert r["module"] == "jit_step"
    assert r["periods"] == 2 and r["steps"] == 16
    assert r["window_s"] == pytest.approx(0.2)


def test_busy_union_and_idle_share():
    r = trace.reduce(_trace())
    # each period: 0-90 busy (overlaps counted once) plus the 2 ms unpack
    assert r["busy_s_device0"] == pytest.approx(2 * 0.092)
    assert r["busy_s_per_device"][1] == pytest.approx(2 * 0.050)
    assert r["busy_s"] == pytest.approx((0.184 + 0.100) / 2)
    idle = 1 - r["busy_s"] / r["window_s"]
    assert idle == pytest.approx(0.29)


def test_collective_half_hidden_behind_compute():
    r = trace.reduce(_trace())
    # all-reduce 60-80, copy 70-90: 10 ms of each period are exposed; the
    # enclosing while does not hide them
    assert r["collective_exposed_s"] == pytest.approx(2 * 0.010)
    assert r["collective_events"] == 2


def test_op_families_by_self_time():
    fams = dict(trace.reduce(_trace())["device_ops"])
    assert fams["fusion"] == pytest.approx(0.080)
    assert fams["all-reduce"] == pytest.approx(0.040)
    assert fams["while"] < 0.001  # only what its children leave uncovered
    assert trace.family("%all-reduce-start.2") == "all-reduce-start"


def test_gaps_are_named_after_the_host_event_without_ids():
    gaps = trace.reduce(_trace())["idle_gaps"]
    assert [g[0] for g in gaps[:2]] == ["XlaLinearize", "XlaLinearize"]
    assert gaps[0][1] == pytest.approx(0.005)  # 90-95 ms
    assert trace.strip_ids("pjrt-tpu-tasks/5864:XlaLinearize") == "XlaLinearize"


def test_a_trace_without_a_device_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({"planes": [{"name": "/host:CPU", "lines": []}]})
