"""The trace reduction on a hand-built trace with known answers."""
import pytest

from benchmark.harness import trace

MS = 1e6  # ns


SMALL = ("slice", "pad", "iota", "select", "bitcast", "transpose")  # 3 us each


def _trace(named=False):
    """Device 0: three executions of ``jit_step`` at 0, 100 and 200 ms.
    In each period: ``fusion.1`` 0-40, ``convolution.2`` 30-60 (overlaps the
    fusion by 10), an ``all-reduce.3`` 60-80 half hidden behind ``copy.4``
    70-90, then idle until the next period. A ``while.9`` holds the first
    period's operations. A small ``jit_unpack`` module runs at 95.
    ``named``: inside every ``fusion.1`` also six small operations of 3 us and
    the kernel ``toy_kernel.7`` of 2 us, the twelfth family by cost; and
    the operations carry the scopes they were traced under."""
    ops, modules = [], []
    for i in range(3):
        t = i * 100 * MS
        modules.append(("jit_step(123)", t, 90 * MS))
        modules.append(("jit_unpack(7)", t + 95 * MS, 2 * MS))
        ops += [("fusion.1", t, 40 * MS), ("convolution.2", t + 30 * MS, 30 * MS),
                ("all-reduce.3", t + 60 * MS, 20 * MS), ("copy.4", t + 70 * MS, 20 * MS),
                ("unpack_fusion", t + 95 * MS, 2 * MS)]
        if named:
            ops += [(f"{fam}.{i}", t + 5 * MS + 10e3 * j, 3e3) for j, fam in enumerate(SMALL)]
            ops.append(("%toy_kernel.7 = f32[8]{0} custom-call()", t + 6 * MS, 2e3))
    ops.append(("while.9", 0.0, 90 * MS))
    tf_op = {"fusion.1": "jit(step)/jvp(forward)/ffn/dot_general:",
             "convolution.2": "jit(step)/transpose(jvp(forward))/ffn/conv_general_dilated:",
             "all-reduce.3": "jit(step)/transpose(jvp(forward))/psum:",
             "%toy_kernel.7 = f32[8]{0} custom-call()": "jit(step)/jvp(forward)/head/toy_kernel:",
             "unpack_fusion": "jit(unpack)/unpack/reshape:"} if named else {}
    host = [("XlaLinearize", 91 * MS, 3 * MS), ("main", 0.0, 300 * MS),
            ("pjrt-tpu-tasks/5864:XlaLinearize", 191 * MS, 3.5 * MS)]
    dev1_ops = [("fusion.1", i * 100 * MS, 50 * MS) for i in range(3)]
    return {"planes": [
        {"name": "/device:TPU:0", "tf_op": tf_op,
         "lines": [{"name": "XLA Modules", "events": modules},
                   {"name": "XLA Ops", "events": ops}, {"name": "Steps", "events": []}]},
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


def test_every_family_is_kept_with_its_seconds_and_its_events():
    r = trace.reduce(_trace(named=True))
    assert len(r["op_s"]) == 13 and len(r["device_ops"]) == 10
    assert "toy_kernel" not in dict(r["device_ops"])  # found by name, whatever its rank
    assert r["op_s"]["toy_kernel"] == pytest.approx(2 * 2e-6)
    assert r["op_events"]["toy_kernel"] == 2  # the third period starts where the window ends
    for fam in SMALL:
        assert r["op_s"][fam] == pytest.approx(2 * 3e-6) and r["op_events"][fam] == 2
    assert r["op_s"]["copy"] == pytest.approx(0.040) and r["op_events"]["copy"] == 2
    assert r["op_s"]["fusion"] == pytest.approx(0.080 - 2 * 20e-6)  # less what its children cover
    assert r["op_events"]["while"] == 1
    assert sum(r["op_s"].values()) == pytest.approx(sum(r["scope_s"].values()))
    assert dict(r["device_ops"]) == {n: r["op_s"][n] for n, _ in r["device_ops"]}


def test_self_seconds_by_scope_and_by_module():
    r = trace.reduce(_trace(named=True))
    assert r["scope_s"]["jit(step)/jvp(forward)/ffn"] == pytest.approx(0.080 - 40e-6)
    assert r["scope_s"]["jit(step)/transpose(jvp(forward))/ffn"] == pytest.approx(0.060)
    assert r["scope_s"]["jit(step)/jvp(forward)/head"] == pytest.approx(4e-6)
    assert r["scope_s"][""] == pytest.approx(0.040 + 36e-6)  # copy, the small ones, while
    assert trace.scope_seconds(r, "ffn") == pytest.approx(0.140 - 40e-6)
    assert trace.scope_seconds(r, "ffn", but_not=["transpose(jvp(forward))"]) \
        == pytest.approx(0.080 - 40e-6)
    assert trace.scope_seconds(r, "transpose(jvp(forward))") == pytest.approx(0.100)
    assert trace.scope_seconds(r, "forward") is None  # a component, not a substring
    assert r["module_s"]["jit_unpack"] == pytest.approx(0.004)
    assert r["module_s"]["jit_step"] == pytest.approx(0.220)  # the busy seconds but the unpack's
    assert trace.scope_of("jit(f)/a/b/dot_general:") == "jit(f)/a/b" and trace.scope_of("") == ""
    bare = trace.reduce(_trace())  # a trace without the stat: everything under no scope
    assert list(bare["scope_s"]) == [""] and trace.scope_seconds(bare, "ffn") is None


def test_the_result_lines_breakdown_comes_out_as_before():
    r = trace.reduce(_trace())
    assert [n for n, _ in r["device_ops"]] == ["fusion", "convolution", "all-reduce", "copy",
                                               "unpack_fusion", "while"]
    assert [s for _, s in r["device_ops"]] == pytest.approx([0.08, 0.06, 0.04, 0.04, 0.004, 0.0])
    assert [g[0] for g in r["idle_gaps"]] == ["XlaLinearize", "XlaLinearize",
                                              "unattributed", "unattributed"]
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx([0.005, 0.005, 0.003, 0.003])
    named = trace.reduce(_trace(named=True))
    assert named["idle_gaps"] == r["idle_gaps"]
    assert named["device_ops"][1:5] == r["device_ops"][1:5]
    for key in ("window_s", "busy_s", "busy_s_device0", "collective_exposed_s", "steps"):
        assert named[key] == r[key]


def test_gaps_are_named_after_the_host_event_without_ids():
    gaps = trace.reduce(_trace())["idle_gaps"]
    assert [g[0] for g in gaps[:2]] == ["XlaLinearize", "XlaLinearize"]
    assert gaps[0][1] == pytest.approx(0.005)  # 90-95 ms
    assert trace.strip_ids("pjrt-tpu-tasks/5864:XlaLinearize") == "XlaLinearize"


def test_a_trace_without_a_device_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({"planes": [{"name": "/host:CPU", "lines": []}]})
