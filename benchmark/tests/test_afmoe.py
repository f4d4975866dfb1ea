"""The afmoe family (Trinity-Mini's share) at a size a test can hold: a tiny
cell laid over a copy of the benchmark as files and entries alone, correct
when sound, and a limit failed by each fault the cell can have; the family's
count of operations against a count by hand."""
import json
import os
import time

import jax
import pytest

from benchmark.flops import afmoe as flops
from benchmark.generators import token_stream
from benchmark.harness import check, spec, window
from benchmark.harness.program import Program
from benchmark.reference import afmoe as ref
from benchmark.reference import follow
from benchmark.tests import tiny

SEED = 2 ** 31 + 4321
MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "layer_types": ["sliding_attention", "sliding_attention", "full_attention"],
    "layers_published": 32, "num_dense_layers": 1, "dense_layers_published": 2,
    "sliding_window": 24, "rope_theta": 10000, "rms_norm_eps": 1e-05,
    "intermediate_size": 96, "moe_intermediate_size": 32, "experts_published": 16,
    "experts_held": [4, 8], "num_experts_per_tok": 4, "num_shared_experts": 1,
    "route_scale": 2.826, "route_norm": True, "score_func": "sigmoid",
    "load_balance_coeff": 0.001, "mup_enabled": True, "vocab_published": 400,
    "vocab_held": 50, "seq_len": 64, "compute_dtype": "float32"}
CONFIG = {
    "family": "afmoe", "source": "test fixture", "preset": "trinity_mini_share8",
    "overrides": {**{".".join(ref.HELD_ELSEWHERE.get(k, ["model", k])): v
                     for k, v in MODEL.items() if k not in ref.FIXED_IN_CODE["model"]},
                  "model.attention_impl": "flash_interpret"},
    "reduced": [], "start_step": 2000, "model": MODEL,
    "optimizer": {"name": "adamw", "learning_rate": 0.0003, "weight_decay": 0.1, "b1": 0.9,
                  "b2": 0.999, "eps": 1e-08, "schedule": "cosine", "warmup_steps": 2000,
                  "total_steps": 100000},
    "control_precision": "fp8"}
TRAFFIC = {"generator": "token_stream", "seq_len": 64, "per_chip_batch": 4, "chips": 1,
           "mesh": {"data": 1}, "zipf_exponent": 0.7, "distinct_batches": 4,
           "trace_dispatches": 2, "overrides": {}}
#: float32 against float32: rounding, the order of sums, and on a rare token
#: the eighth against the ninth expert
LIMITS = {"limits": {"loss_1": 1e-4, "loss_2": 1e-4, "loss_3": 1e-4, "grad_gap": 5e-3,
                     "grad_mid": 5e-4, "grad_dir": 2e-3, "change_gap": 5e-3,
                     "change_mid": 5e-4, "change_dir": 2e-3},
          "not_compared": {}}
CELL = "tiny_afmoe_cell"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark with the tiny cells, and the tiny afmoe cell
    added as a configuration, a traffic mix, a limits file and entries: no
    file that was there is touched."""
    root = tiny.make_root(str(tmp_path_factory.mktemp("afmoe")))
    here = os.path.join(root, "benchmark")
    before = {p: os.path.getmtime(p) for base, _, files in os.walk(here)
              for p in (os.path.join(base, f) for f in files)}
    for kind, name, body in (("configs", "tiny_afmoe", CONFIG),
                             ("traffic", "tiny_tokens_b4", TRAFFIC), ("limits", CELL, LIMITS)):
        with open(os.path.join(here, kind, name + ".json"), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny_afmoe", "source": "test fixture",
                             "file": "benchmark/configs/tiny_afmoe.json", "reduced": [],
                             "why": "fits a CPU test"})
    bench["workloads"].append({"name": CELL, "config": "tiny_afmoe",
                               "traffic": "tiny_tokens_b4", "chips": 1,
                               "why": "fits a CPU test"})
    for m in bench["per_layer"]:
        if "trinity_mini_train_8k" in m["workloads"]:
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    assert all(os.path.getmtime(p) == t for p, t in before.items())
    return root


def test_the_cell_resolves_with_the_thirteen_readers(root):
    cell = spec.resolve(CELL, root)
    assert sorted(cell.readers()) == sorted(
        ["compile_s", "stage_ms", "step_device_ms", "step_mfu", "device_idle_share",
         "input_wait_ms", "dispatch_ms", "trainer_init_s", "flash_roofline",
         "moe_experts_roofline", "attention_ms", "moe_ms", "lm_head_ms"])
    real = spec.resolve("trinity_mini_train_8k")
    assert sorted(real.readers()) == sorted(cell.readers())
    assert real.config["reduced"] == ["num_hidden_layers", "num_dense_layers", "layer_types",
                                      "num_experts", "vocab_size"]


def _run(root, monkeypatch=None, broken=None):
    cell = spec.resolve(CELL, root)
    if broken is not None:
        class Broken(Program):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                sound = self.trainer._train_step
                self.trainer._train_step = lambda s, b: broken(sound, s, b)
        monkeypatch.setattr(window, "Program", Broken)
    return window.run_cell(cell, SEED, 1.0, False, jax.devices()[:1], tiny.PEAKS, time.time())


def _rule_left_out(sound, state, batch):
    new, metrics = sound(state, batch)
    params = dict(new.params)
    for layer in ("layer1", "layer2"):
        moe = dict(params[layer]["moe"], router_bias=state.params[layer]["moe"]["router_bias"])
        params[layer] = dict(params[layer], moe=moe)
    return new.replace(params=params), metrics


FAULTS = {
    "half_batch": lambda sound, s, b: sound(
        s, jax.tree_util.tree_map(lambda x: x[:x.shape[0] // 2], b)),
    "rule_left_out": _rule_left_out,
}


def test_a_sound_run_through_the_trainer_is_correct(root):
    result = _run(root)
    assert result["correct"] is True, result["check"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"examples_per_s", "peak_hbm_gib", "setup_s"}
    assert sorted(result["check"]) == sorted(LIMITS["limits"])


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_under_the_timed_path_is_not_correct(root, monkeypatch, fault):
    result = _run(root, monkeypatch, FAULTS[fault])
    assert result["correct"] is False, result["check"]
    over = [n for n, c in result["check"].items() if c["value"] > c["limit"]]
    assert over
    if fault == "rule_left_out":  # the biases stand still, and the choices follow them
        assert "change_gap" in over


@pytest.fixture(scope="module")
def walked():
    """The reference's walk over the tiny cell's first batches."""
    stream = token_stream.make(TRAFFIC, CONFIG, SEED)
    batches = [stream.batch(i) for i in range(3)]
    return batches, follow.follow(CONFIG, SEED, batches, [1, 2, 3])


def _window_left_out_of_layer_1(monkeypatch):
    sound = ref._layer

    def layer(x, p, i, model, quant):
        return sound(x, p, i, dict(model, sliding_window=10 ** 6) if i == 1 else model, quant)
    monkeypatch.setattr(ref, "_layer", layer)
    return {}


@pytest.mark.parametrize("fault", ["control_fp8", "window_left_out_of_one_layer"])
def test_a_fault_of_the_walk_put_in_the_programs_place_fails_a_limit(walked, monkeypatch,
                                                                      fault):
    """The reference in the precision below, and the reference with one
    layer's window taken out of its mask, each put in the program's place."""
    batches, sound = walked
    follow._COMPILED.clear()  # the walk's programs are kept per configuration
    kwargs = {"precision": "fp8"} if fault == "control_fp8" else \
        _window_left_out_of_layer_1(monkeypatch)
    try:
        bad = follow.follow(CONFIG, SEED, batches, [1, 2, 3], **kwargs)
    finally:
        follow._COMPILED.clear()
    numbers, _ = check.compare(bad, sound)
    ok, rows = check.verdict(numbers, LIMITS)
    assert not ok, rows


def test_the_count_of_operations_is_the_count_by_hand():
    t, rows = 64, 4
    tokens = t * rows
    attn = 64 * (64 + 64 + 32 + 32) + 64 * 64      # q, gate, k, v; output
    per_token = 3 * attn + 3 * 64 * 96 + 2 * (64 * 16 + 3 * 64 * 32) + 64 * 50
    window_pairs = 24 * 25 // 2 + (64 - 24) * 24   # the first 24 rows, then 24 a row
    full_pairs = 64 * 65 // 2
    scores = 2 * (4 * 16) * rows * (2 * window_pairs + full_pairs)
    routed = 2 * (tokens * 4 * 4 / 16) * 3 * 64 * 32
    config = {"model": MODEL}
    assert flops.live_pairs(64, 24) == window_pairs and flops.live_pairs(64, None) == full_pairs
    assert flops.live_pairs(16, 24) == 16 * 17 // 2
    assert flops.forward_macs_per_step(config, TRAFFIC) == tokens * per_token + scores + routed
    assert flops.train_flops_per_step(config, TRAFFIC) == 6 * (tokens * per_token + scores
                                                               + routed)
    assert flops.flash_flops_per_step(config, TRAFFIC) == 6 * scores
    assert flops.experts_flops_per_step(config, TRAFFIC) == 3 * 2 * routed


def test_the_familys_readers_read_forward_and_backward_scopes_and_kernels_by_name(root):
    """A hand-built reduction: the scopes of the backward pass are wrapped
    by their transforms, the kernels carry the names their ``name=`` gave."""
    cell = spec.resolve(CELL, root)
    reduced = {"steps": 2, "op_s": {"flash_fwd": 1e-3, "flash_bwd_dq": 2e-3,
                                    "flash_bwd_dkv": 3e-3, "fusion": 9.0},
               "scope_s": {
                   "jit(step)/jvp(forward)/layer1/attention/attn/dot_general": 1e-3,
                   "jit(step)/transpose(jvp(forward))/layer1/transpose(jvp(attention))/attn": 3e-3,
                   "jit(step)/jvp(forward)/layer1/moe/experts": 2e-3,
                   "jit(step)/transpose(jvp(forward))/checkpoint/layer1/moe/shared/gate": 4e-3,
                   "jit(step)/jvp(forward)/jvp(lm_head)": 5e-3,
                   "jit(step)/optimizer/attention_free": 7.0}}
    run = {"trace": reduced, "config": cell.config, "traffic": cell.traffic,
           "peaks": tiny.PEAKS}
    got = {n: read(run) for n, read in cell.readers().items()
           if n in ("flash_roofline", "moe_experts_roofline", "attention_ms", "moe_ms",
                    "lm_head_ms")}
    assert got["attention_ms"] == pytest.approx(2.0) and got["moe_ms"] == pytest.approx(3.0)
    assert got["lm_head_ms"] == pytest.approx(2.5)
    assert got["flash_roofline"] == pytest.approx(
        100 * flops.flash_flops_per_step(cell.config, cell.traffic) * 2 / (6e-3 * 1e12))
    assert got["moe_experts_roofline"] == pytest.approx(
        100 * flops.experts_flops_per_step(cell.config, cell.traffic) * 2 / (2e-3 * 1e12))
    # the parent's program has neither the kernels' names nor the scopes
    nothing = dict(run, trace={"steps": 2, "op_s": {"fusion": 1.0},
                               "scope_s": {"jit(step)/jvp(forward)/EncoderBlock_0": 1.0}})
    assert all(read(nothing) is None for n, read in cell.readers().items() if n in got)
    assert all(read(dict(run, trace=None)) is None
               for n, read in cell.readers().items() if n in got)
