"""The nemotron_h family (Nemotron-3-Nano's share) at a size a test can hold:
a tiny cell laid over a copy of the benchmark as files and entries alone,
correct when sound, and a limit failed by each fault the cell can have; the
configuration against the catalog's row; the family's counts of operations
against counts by hand; its two readers."""
import json
import os
import time

import jax
import numpy as np
import pytest

from benchmark.flops import nemotron_h as flops
from benchmark.generators import token_stream
from benchmark.harness import check, spec, window
from benchmark.harness.program import Program
from benchmark.reference import follow
from benchmark.reference import nemotron_h as ref
from benchmark.tests import tiny

SEED = 2 ** 31 + 3939
MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "layer_types": ["mamba", "moe", "mamba", "full_attention", "moe"], "layers_published": 52,
    "num_dense_layers": 0, "rms_norm_eps": 1e-05, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 48, "experts_published": 16, "experts_held": [4, 8],
    "num_experts_per_tok": 3, "num_shared_experts": 1, "route_scale": 2.5,
    "norm_topk_prob": True, "score_func": "sigmoid", "router_dtype": "float32",
    "load_balance_coeff": 0.001, "mup_enabled": False, "mlp_hidden_act": "relu2",
    "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
    "conv_kernel": 4, "chunk_size": 16, "mamba_hidden_act": "silu", "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 0.0001, "tie_word_embeddings": False,
    "vocab_published": 400, "vocab_held": 50, "seq_len": 48, "compute_dtype": "float32"}
CONFIG = {
    "family": "nemotron_h", "source": "test fixture", "preset": "nemotron3_nano_share16",
    "overrides": {**{".".join(ref.HELD_ELSEWHERE.get(k, ["model", k])): v
                     for k, v in MODEL.items() if k not in ref.FIXED_IN_CODE["model"]},
                  "model.attention_impl": "flash_interpret"},
    "reduced": [], "start_step": 2000, "model": MODEL,
    "optimizer": {"name": "adamw", "learning_rate": 0.0003, "weight_decay": 0.1, "b1": 0.9,
                  "b2": 0.999, "eps": 1e-08, "schedule": "cosine", "warmup_steps": 2000,
                  "total_steps": 100000},
    "control_precision": "fp8"}
TRAFFIC = {"generator": "token_stream", "seq_len": 48, "per_chip_batch": 4, "chips": 1,
           "mesh": {"data": 1}, "zipf_exponent": 0.7, "distinct_batches": 4,
           "trace_dispatches": 2, "overrides": {}}
#: float32 against float32: rounding, the order of sums, and the chunked
#: scan against the recurrence a position at a time
LIMITS = {"limits": {"loss_1": 1e-4, "loss_2": 1e-4, "loss_3": 1e-4, "grad_gap": 5e-3,
                     "grad_mid": 5e-4, "grad_dir": 2e-3, "change_gap": 5e-3,
                     "change_mid": 5e-4, "change_dir": 2e-3},
          "not_compared": {}}
CELL = "tiny_nemotron_cell"
REAL = "nemotron3_nano_train_8k"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark with the tiny cells, and the tiny nemotron_h
    cell added as a configuration, a traffic mix, a limits file and entries:
    no file that was there is touched."""
    root = tiny.make_root(str(tmp_path_factory.mktemp("nemotron")))
    here = os.path.join(root, "benchmark")
    before = {p: os.path.getmtime(p) for base, _, files in os.walk(here)
              for p in (os.path.join(base, f) for f in files)}
    for kind, name, body in (("configs", "tiny_nemotron", CONFIG),
                             ("traffic", "tiny_tokens_b4", TRAFFIC), ("limits", CELL, LIMITS)):
        with open(os.path.join(here, kind, name + ".json"), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny_nemotron", "source": "test fixture",
                             "file": "benchmark/configs/tiny_nemotron.json", "reduced": [],
                             "why": "fits a CPU test"})
    bench["workloads"].append({"name": CELL, "config": "tiny_nemotron",
                               "traffic": "tiny_tokens_b4", "chips": 1,
                               "why": "fits a CPU test"})
    for m in bench["per_layer"]:
        if REAL in m["workloads"]:
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    assert all(os.path.getmtime(p) == t for p, t in before.items())
    return root


READERS = ["compile_s", "stage_ms", "step_device_ms", "step_mfu", "device_idle_share",
           "input_wait_ms", "dispatch_ms", "trainer_init_s", "loop_device_wait_ms",
           "attention_ms", "attention_rest_ms", "moe_ms", "lm_head_ms", "moe_products_ms",
           "moe_products_roofline", "moe_product_calls", "moe_carry_ms", "recompute_ms",
           "step_unscoped_ms", "mamba_ms", "ssd_roofline"]


def test_the_cell_resolves_with_its_readers_and_not_the_other_cells(root):
    cell = spec.resolve(CELL, root)
    assert sorted(cell.readers()) == sorted(READERS)
    real = spec.resolve(REAL)
    assert sorted(real.readers()) == sorted(READERS)
    assert real.traffic["per_chip_batch"] == 2 and real.traffic["seq_len"] == 8192
    for other in ("trinity_mini_train_8k", "sdar_30b_a3b_train_4k"):
        assert not {"mamba_ms", "ssd_roofline"} & set(spec.resolve(other).readers())
    # flash_roofline counts every layer of the list as attention: not here
    assert "flash_roofline" not in real.readers()


def _catalog_row():
    # a JSONL catalog of published configurations, one row per model
    path = os.environ.get("MODEL_CATALOG_JSONL", "")
    if not path or not os.path.isfile(path):
        return None
    with open(path) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16":
                return row
    return None


def test_the_configuration_is_the_rows_keys_at_published_widths():
    """Every key of the source's config.json is there under its own name,
    those cut listed in ``reduced`` and none of them a width; the stated
    count of parameters is the count of the leaves; one whole period of
    the pattern is kept."""
    config = spec.resolve(REAL).config
    assert config["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                                 "n_routed_experts", "vocab_size"]
    published = {"num_hidden_layers": 52, "n_routed_experts": 128, "vocab_size": 131072,
                 "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
                 "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
                 "ssm_state_size": 128, "n_groups": 8, "moe_intermediate_size": 1856,
                 "moe_shared_expert_intermediate_size": 3712, "num_experts_per_tok": 6,
                 "routed_scaling_factor": 2.5, "chunk_size": 128, "conv_kernel": 4}
    row = _catalog_row()
    if row is not None:
        assert row["source_url"] == config["source"]
        published = row["config"]
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == sorted(config["reduced"])
    model = config["model"]
    kinds = {"M": "mamba", "E": "moe", "*": "full_attention"}
    assert config["hybrid_override_pattern"] == published["hybrid_override_pattern"][:9]
    assert model["layer_types"] == [kinds[k] for k in config["hybrid_override_pattern"]]
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == \
        (len(model["layer_types"]), model["experts_held"][1] - model["experts_held"][0],
         model["vocab_held"]) == (9, 8, 16384)
    leaves = sum(int(np.prod(s)) for s in ref._shapes(model).values())
    assert leaves == config["deployment"]["parameters"] == 666963456
    assert 16 * leaves / 16.9e9 == pytest.approx(0.63, abs=0.01)
    assert set(config["assumed"]) >= {"residual_in_fp32", "time_step_limit", "attention",
                                      "weights", "router_bias_rule", "optimizer"}


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


FAULTS = {
    "half_batch": lambda sound, s, b: sound(
        s, jax.tree_util.tree_map(lambda x: x[:x.shape[0] // 2], b)),
    # every row's ids shifted one place: the targets are the inputs
    "targets_are_inputs": lambda sound, s, b: sound(
        s, {"tokens": jax.numpy.concatenate([b["tokens"][:, :1], b["tokens"][:, :-1]], 1)}),
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
    assert [n for n, c in result["check"].items() if c["value"] > c["limit"]]


@pytest.fixture(scope="module")
def walked():
    """The reference's walk over the tiny cell's first batches."""
    stream = token_stream.make(TRAFFIC, CONFIG, SEED)
    batches = [stream.batch(i) for i in range(3)]
    return batches, follow.follow(CONFIG, SEED, batches, [1, 2, 3])


@pytest.mark.parametrize("fault", ["control_fp8", "state_reset_at_chunks"])
def test_a_fault_of_the_walk_put_in_the_programs_place_fails_a_limit(walked, monkeypatch,
                                                                      fault):
    """The reference in the precision below, and with the scan's state
    dropped between chunks of ``chunk_size`` positions, each put in the
    program's place."""
    batches, sound = walked
    kwargs = {}
    if fault == "control_fp8":
        kwargs = {"precision": "fp8"}
    else:
        monkeypatch.setattr(ref, "STATE_RESET_EVERY", MODEL["chunk_size"])
    follow._COMPILED.clear()  # the walk's programs are kept per configuration
    try:
        bad = follow.follow(CONFIG, SEED, batches, [1, 2, 3], **kwargs)
    finally:
        follow._COMPILED.clear()
    numbers, _ = check.compare(bad, sound)
    ok, rows = check.verdict(numbers, LIMITS)
    assert not ok, rows


def test_the_fault_tool_reads_the_state_reset(root, capsys):
    from benchmark.tools import ssd_fault
    assert ssd_fault.main(["--workload", CELL, "--seeds", "1", "--first-seed", str(SEED),
                           "--cpu-root", root]) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1][len("SUMMARY "):])
    ok, _ = check.verdict(summary["state_reset_min"], LIMITS)
    assert not ok and ref.STATE_RESET_EVERY is None


def test_the_counts_of_operations_are_the_counts_by_hand():
    t, rows, d = 48, 4, 64
    tokens = t * rows
    mamba = d * (64 + 128 + 8) + 128 * 4 + 64 * d     # in_proj, conv, out_proj
    moe = d * 16 + 2 * d * 48                           # router, shared
    attn = d * (64 + 32 + 32) + 64 * d                  # q, k, v; output
    per_token = 2 * mamba + 2 * moe + attn + d * 50
    routed = 2 * (tokens * 3 * 4 / 16) * 2 * d * 32
    scores = 2 * 64 * rows * t * (t + 1) // 2
    q, chunks = 16, 3
    scan = rows * 2 * chunks * (2 * q * q * 16 + 8 * (q * q * 8 + 2 * q * 16 * 8))
    config = {"model": MODEL}
    assert flops.forward_macs_per_step(config, TRAFFIC) == pytest.approx(
        tokens * per_token + routed + scores + scan, rel=1e-12)
    assert flops.train_flops_per_step(config, TRAFFIC) == \
        6 * flops.forward_macs_per_step(config, TRAFFIC)
    assert flops.experts_flops_per_step(config, TRAFFIC) == 6 * routed
    assert flops.ssd_flops_per_step(config, TRAFFIC) == 6 * scan
    # the real cell: 358.9 M multiply-accumulates a token forward, 35.3
    # TFLOP a step; the mixers 45%, the scan's products 1.9%, the experts 27%
    real = spec.resolve(REAL)
    step = flops.train_flops_per_step(real.config, real.traffic)
    assert step / (6 * 16384) == pytest.approx(358.9e6, rel=1e-3)
    assert 35.2e12 < step < 35.4e12
    model = real.config["model"]
    mixers = 4 * (flops.mamba_token_macs(model) + flops.ssd_macs(model, 8192) / 8192)
    assert mixers * 6 * 16384 / step == pytest.approx(0.45, abs=0.005)
    assert flops.ssd_flops_per_step(real.config, real.traffic) / step == pytest.approx(
        0.019, abs=0.001)
    experts = 4 * (16384 * model["experts_published"] + 2 * 16384 * 3712) * 2688 * 6 \
        + flops.experts_flops_per_step(real.config, real.traffic)
    assert experts / step == pytest.approx(0.27, abs=0.005)


def test_the_familys_readers_read_scopes_and_kernels_by_name(root):
    """A hand-built reduction: the scopes of the backward pass are wrapped
    by their transforms, the compiler's grouped products carry a name and
    no scope."""
    cell = spec.resolve(CELL, root)
    reduced = {"steps": 2, "op_s": {"ragged-dot-none": 5e-4, "ragged-dot-metadata": 5e-4,
                                    "fusion": 9.0},
               "op_events": {"ragged-dot-none": 8},
               "scope_s": {
                   "jit(step)/jvp(forward)/layer0/mamba/mamba/scan/while/body": 2e-3,
                   "jit(step)/transpose(jvp(forward))/layer0/transpose(jvp(mamba))/mamba/"
                   "transpose(jvp(scan))": 4e-3,
                   "jit(step)/jvp(forward)/layer2/mamba/mamba/conv": 6e-3,
                   "jit(step)/jvp(forward)/layer1/moe/experts": 2e-3,
                   "jit(step)/jvp(forward)/layer3/attention/attn/dot_general": 1e-3,
                   "jit(step)/optimizer/scan_free": 7.0}}
    run = {"trace": reduced, "config": cell.config, "traffic": cell.traffic,
           "peaks": tiny.PEAKS}
    got = {n: read(run) for n, read in cell.readers().items()
           if n in ("mamba_ms", "ssd_roofline", "moe_products_roofline", "moe_ms")}
    assert got["mamba_ms"] == pytest.approx(6.0) and got["moe_ms"] == pytest.approx(1.0)
    assert got["ssd_roofline"] == pytest.approx(
        100 * flops.ssd_flops_per_step(cell.config, cell.traffic) * 2 / (6e-3 * 1e12))
    assert got["moe_products_roofline"] == pytest.approx(
        100 * flops.experts_flops_per_step(cell.config, cell.traffic) * 2 / (5e-4 * 1e12))
    nothing = dict(run, trace={"steps": 2, "op_s": {"fusion": 1.0}, "op_events": {},
                               "scope_s": {"jit(step)/jvp(forward)/layer1/attention": 1.0}})
    assert all(read(nothing) is None for n, read in cell.readers().items()
               if n in ("mamba_ms", "ssd_roofline", "moe_products_roofline"))
    assert all(read(dict(run, trace=None)) is None for n, read in cell.readers().items()
               if n in got)
