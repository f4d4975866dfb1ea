"""The eight readers PR 37 added, on hand-built runs: each gives its value
from the scopes, kernels and counters it names, and nothing, without raising,
from a run without a trace or from a program that lacks the scope or the
span (the parent of that PR, or a step fetched from its compile cache)."""
import pytest

from benchmark.harness import spec

BWD = "jit(single_step)/transpose(jvp(forward))/CausalDecoder/jvp(forward)/CausalDecoder/checkpoint"
FWD = "jit(single_step)/jvp(forward)/CausalDecoder"
#: four steps in the window; seconds of self time by path and by family
TRACE = {
    "steps": 4,
    "scope_s": {
        f"{FWD}/layer1/attention/attn/q_proj": 0.040,
        f"{FWD}/layer1/attention/attn/rotary": 0.008,
        f"{FWD}/layer1/attention/attn/core/flash_fwd": 0.100,
        f"{BWD}/layer1/attention/attn/core/flash_bwd_dq": 0.200,
        f"{BWD}/rematted_computation/layer1/attention/attn/rotary": 0.012,
        f"{BWD}/rematted_computation/layer1/moe/moe.walked/experts/while/body/gather": 0.020,
        f"{BWD}/layer1/moe/moe.walked/experts/while/body/carry": 0.112,
        f"{BWD}/layer1/moe/moe.walked/experts/carry": 0.008,
        "jit(single_step)/transpose(jvp(forward))/CausalDecoder/lm_head/while/body/"
        "closed_call/checkpoint/rematted_computation": 0.016,
        "": 0.500,
    },
    "op_s": {"ragged-dot-none": 0.368, "ragged-dot-metadata": 0.0004, "fusion": 1.0},
    "op_events": {"ragged-dot-none": 960, "ragged-dot-metadata": 256, "fusion": 5000},
}
WANT = {
    "moe_products_ms": 92.0,                       # 0.368 s / 4 steps
    "moe_product_calls": 240.0,                    # the metadata op is no product
    "moe_carry_ms": 30.0,                          # in the loop and at its edges
    "attention_rest_ms": 1e3 * (0.040 + 0.008 + 0.012) / 4,
    "recompute_ms": 1e3 * (0.012 + 0.020 + 0.016) / 4,
    "step_unscoped_ms": 1e3 * (0.500 - 0.368) / 4,
}
PARENT = dict(TRACE, scope_s={path: s for path, s in TRACE["scope_s"].items()
                              if "core" not in path and "carry" not in path})


def run_of(trace, family="afmoe"):
    cell = spec.resolve({"afmoe": "trinity_mini_train_8k",
                         "sdar_moe": "sdar_30b_a3b_train_4k"}[family])
    return {"trace": trace, "config": cell.config, "traffic": cell.traffic,
            "peaks": {"bf16_flops_per_s": 197e12}, "stages_before": {}, "stages_after": {}}


def read(name, run):
    return spec.module("layer_metrics", name).read(run)


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_reads_its_scopes_and_kernels(name):
    assert read(name, run_of(TRACE)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT) + ["moe_products_roofline"])
def test_a_reader_finds_nothing_without_a_trace(name):
    assert read(name, run_of(None)) is None


@pytest.mark.parametrize("name,reads", [
    ("moe_carry_ms", False), ("attention_rest_ms", False),   # their scopes came with PR 37
    ("moe_products_ms", True), ("moe_product_calls", True),  # by the kernels' name
    ("recompute_ms", True), ("step_unscoped_ms", True),      # JAX's component, the empty path
])
def test_a_program_without_the_new_scopes(name, reads):
    got = read(name, run_of(PARENT))
    assert (got is not None) == reads
    if name in ("moe_products_ms", "moe_product_calls", "step_unscoped_ms"):
        assert got == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", ["moe_products_ms", "moe_product_calls", "moe_products_roofline"])
def test_a_program_without_the_kernels_reads_no_products(name):
    dense = dict(TRACE, op_s={"fusion": 1.0}, op_events={"fusion": 5000})
    assert read(name, run_of(dense)) is None
    assert read("step_unscoped_ms", run_of(dense)) == pytest.approx(125.0)


@pytest.mark.parametrize("family", ["afmoe", "sdar_moe"])
def test_the_products_roofline_counts_by_the_runs_family(family):
    """One reader for both families: the count is
    ``benchmark/flops/<family>.experts_flops_per_step``, the time the
    kernels' own, and the share stays a share."""
    run = run_of(TRACE, family)
    flops = spec.module("flops", family).experts_flops_per_step(run["config"], run["traffic"])
    got = read("moe_products_roofline", run)
    assert got == pytest.approx(100 * flops * 4 / (0.368 * 197e12))
    assert 5 < got < 100  # over 100 the harness stops the run (window.layer_metrics)


def cell(count, seconds):
    return {"count": count, "items": 0, "seconds": seconds,
            "max_thread_seconds": seconds, "workers": 1, "bytes": 0}


def test_loop_device_wait_reads_both_waits_over_the_dispatches():
    before = {"train.step": cell(3, 0.9), "train.lead_wait": cell(1, 0.8),
              "train.hook_read": cell(1, 0.1)}
    after = {"train.step": cell(13, 0.95), "train.lead_wait": cell(11, 7.8),
             "train.hook_read": cell(2, 1.1)}
    run = {"stages_before": before, "stages_after": after}
    assert read("loop_device_wait_ms", run) == pytest.approx(1e3 * (7.0 + 1.0) / 10)
    # a loop that never bounded its lead still reads its hooks' waits
    fused_less = {k: v for k, v in after.items() if k != "train.lead_wait"}
    assert read("loop_device_wait_ms", {"stages_before": {}, "stages_after": fused_less}) \
        == pytest.approx(1e3 * 1.1 / 13)


@pytest.mark.parametrize("before,after", [
    ({}, {}),                                                   # no counters at all
    ({}, {"train.step": cell(10, 0.5)}),                        # a program before the waits
    ({"train.step": cell(3, 0.9), "train.lead_wait": cell(3, 1.0)},
     {"train.step": cell(3, 0.9), "train.lead_wait": cell(3, 1.0)}),  # no dispatch in the window
])
def test_loop_device_wait_finds_nothing_to_read(before, after):
    assert read("loop_device_wait_ms", {"stages_before": before, "stages_after": after}) is None
