"""The glm4_moe_lite family (GLM-4.7-Flash's share) at a size a test can
hold: a tiny cell laid over a copy of the benchmark as files and entries
alone, correct when sound, and a limit failed by each fault the cell can
have; the configuration against the catalog's row; the family's counts of
operations against counts by hand; its three readers."""
import json
import os
import time

import jax
import numpy as np
import pytest

from benchmark.flops import glm4_moe_lite as flops
from benchmark.generators import token_stream
from benchmark.harness import check, spec, window
from benchmark.harness.program import Program
from benchmark.reference import follow
from benchmark.reference import glm4_moe_lite as ref
from benchmark.tests import tiny

SEED = 2 ** 31 + 4141
MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 24, "qk_rope_head_dim": 8,
    "v_head_dim": 32, "attention_bias": False, "rope_pairing": "rotate-half",
    "layer_types": ["full_attention"] * 3, "layers_published": 47, "num_dense_layers": 1,
    "rope_theta": 1000000, "rms_norm_eps": 1e-05, "intermediate_size": 96,
    "moe_intermediate_size": 32, "experts_published": 8, "experts_held": [0, 4],
    "num_experts_per_tok": 2, "num_shared_experts": 1, "route_scale": 1.8,
    "norm_topk_prob": True, "score_func": "sigmoid", "router_dtype": "float32",
    "load_balance_coeff": 0.001, "mup_enabled": False, "hidden_act": "silu",
    "tie_word_embeddings": False, "vocab_published": 400, "vocab_held": 50,
    "num_nextn_predict_layers": 1, "mtp_loss_weight": 0.3, "mtp_input_order": "[e ; h]",
    "seq_len": 48, "compute_dtype": "float32"}
CONFIG = {
    "family": "glm4_moe_lite", "source": "test fixture", "preset": "glm47_flash_share8",
    "overrides": {**{".".join(ref.HELD_ELSEWHERE.get(k, ["model", k])): v
                     for k, v in MODEL.items() if k not in ref.FIXED_IN_CODE["model"]},
                  "model.attention_impl": "flash_interpret"},
    "reduced": [], "start_step": 20, "model": MODEL,
    "optimizer": {"name": "adamw", "learning_rate": 0.0003, "weight_decay": 0.1, "b1": 0.9,
                  "b2": 0.999, "eps": 1e-08, "schedule": "cosine", "warmup_steps": 2000,
                  "total_steps": 100000},
    "control_precision": "fp8"}
TRAFFIC = {"generator": "token_stream", "seq_len": 48, "per_chip_batch": 4, "chips": 1,
           "mesh": {"data": 1}, "zipf_exponent": 0.7, "distinct_batches": 4,
           "trace_dispatches": 2, "overrides": {}}
#: float32 against float32: rounding and the order of sums (the walk's
#: sorted windows, the kernels' online softmax, the head's chunks)
LIMITS = {"limits": {"loss_1": 1e-4, "loss_2": 1e-4, "loss_3": 1e-4, "grad_gap": 5e-3,
                     "grad_mid": 5e-4, "grad_dir": 2e-3, "change_gap": 5e-3,
                     "change_mid": 5e-4, "change_dir": 2e-3},
          "not_compared": {}}
CELL = "tiny_glm_cell"
REAL = "glm47_flash_train_8k"
NEW = ["mla_latent_ms", "mtp_ms", "flash_kernels_roofline"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark with the tiny cells, and the tiny
    glm4_moe_lite cell added as a configuration, a traffic mix, a limits
    file and entries: no file that was there is touched."""
    root = tiny.make_root(str(tmp_path_factory.mktemp("glm")))
    here = os.path.join(root, "benchmark")
    before = {p: os.path.getmtime(p) for base, _, files in os.walk(here)
              for p in (os.path.join(base, f) for f in files)}
    for kind, name, body in (("configs", "tiny_glm", CONFIG),
                             ("traffic", "tiny_tokens_b4", TRAFFIC), ("limits", CELL, LIMITS)):
        with open(os.path.join(here, kind, name + ".json"), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny_glm", "source": "test fixture",
                             "file": "benchmark/configs/tiny_glm.json", "reduced": [],
                             "why": "fits a CPU test"})
    bench["workloads"].append({"name": CELL, "config": "tiny_glm",
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
           "step_unscoped_ms"] + NEW


def test_the_cell_resolves_with_its_readers_and_not_the_other_cells(root):
    cell = spec.resolve(CELL, root)
    assert sorted(cell.readers()) == sorted(READERS)
    real = spec.resolve(REAL)
    assert sorted(real.readers()) == sorted(READERS)
    assert real.traffic["per_chip_batch"] == 2 and real.traffic["seq_len"] == 8192
    for other in ("trinity_mini_train_8k", "sdar_30b_a3b_train_4k",
                  "nemotron3_nano_train_8k"):
        assert not set(NEW) & set(spec.resolve(other).readers())


def _catalog_row():
    # a JSONL catalog of published configurations, one row per model
    path = os.environ.get("MODEL_CATALOG_JSONL", "")
    if not path or not os.path.isfile(path):
        return None
    with open(path) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "GLM-4.7-Flash":
                return row
    return None


def test_the_configuration_is_the_rows_keys_at_published_widths():
    """Every key of the source's config.json is there under its own name,
    those cut listed in ``reduced`` and none of them a width; the stated
    count of parameters is the count of the leaves; the depth is the dense
    layer, four expert layers and the prediction block."""
    config = spec.resolve(REAL).config
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    published = {"num_hidden_layers": 47, "n_routed_experts": 64, "vocab_size": 154880,
                 "hidden_size": 2048, "num_attention_heads": 20, "q_lora_rank": 768,
                 "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
                 "v_head_dim": 256, "intermediate_size": 10240, "moe_intermediate_size": 1536,
                 "num_experts_per_tok": 4, "routed_scaling_factor": 1.8,
                 "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
                 "rope_theta": 1000000}
    row = _catalog_row()
    if row is not None:
        assert row["source_url"] == config["source"]
        published = row["config"]
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == sorted(config["reduced"])
    model = config["model"]
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == \
        (len(model["layer_types"]), model["experts_held"][1] - model["experts_held"][0],
         model["vocab_held"]) == (5, 8, 19360)
    assert model["num_dense_layers"] == config["first_k_dense_replace"] == 1
    assert model["v_head_dim"] == model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    leaves = sum(int(np.prod(s)) for s in ref._shapes(model).values())
    assert leaves == config["deployment"]["parameters"] == 706518848
    assert 16 * leaves / 16.9e9 == pytest.approx(0.67, abs=0.01)
    assert set(config["assumed"]) >= {"rope_pairing", "multi_token_prediction",
                                      "router_bias_rule", "sequence_wise_balance_loss",
                                      "weights", "start_step", "optimizer"}


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


@pytest.mark.parametrize("fault", ["control_fp8", "no_prediction_term"])
def test_a_fault_of_the_walk_put_in_the_programs_place_fails_a_limit(walked, fault):
    """The reference in the precision below, and the reference without its
    prediction block's loss (the block still in the leaves), each put in
    the program's place."""
    batches, sound = walked
    config, kwargs = CONFIG, {}
    if fault == "control_fp8":
        kwargs = {"precision": "fp8"}
    else:
        config = dict(CONFIG, model=dict(MODEL, mtp_loss_weight=0.0))
    follow._COMPILED.clear()  # the walk's programs are kept per configuration
    try:
        bad = follow.follow(config, SEED, batches, [1, 2, 3], **kwargs)
    finally:
        follow._COMPILED.clear()
    numbers, _ = check.compare(bad, sound)
    ok, rows = check.verdict(numbers, LIMITS)
    assert not ok, rows


def test_the_counts_of_operations_are_the_counts_by_hand():
    t, rows, d = 48, 4, 64
    tokens = t * rows
    mla = d * 32 + 32 * 4 * 32 + d * (16 + 8) + 16 * 4 * (24 + 32) + 4 * 32 * d
    moe = d * 8 + 3 * d * 32                            # router, shared
    per_token = 4 * mla + 3 * d * 96 + 3 * moe + 2 * d * d + 2 * d * 50
    routed = 3 * (tokens * 2 * 4 / 8) * 3 * d * 32
    scores = 4 * (4 * (32 + 32)) * rows * t * (t + 1) // 2
    config = {"model": MODEL}
    assert flops.forward_macs_per_step(config, TRAFFIC) == pytest.approx(
        tokens * per_token + routed + scores, rel=1e-12)
    assert flops.train_flops_per_step(config, TRAFFIC) == \
        6 * flops.forward_macs_per_step(config, TRAFFIC)
    assert flops.experts_flops_per_step(config, TRAFFIC) == 6 * routed
    assert flops.flash_flops_per_step(config, TRAFFIC) == 6 * scores
    # the real cell: 59.4 TFLOP a step; latent attention 63% (its core 42%,
    # its projections 22%), the heads 13%, the dense layer 10%, the shared
    # experts 8%, the routed experts 4%; the prediction block with its head
    # and W_eh about 21%
    real = spec.resolve(REAL)
    model, step = real.config["model"], flops.train_flops_per_step(real.config, real.traffic)
    assert step == pytest.approx(59.4e12, rel=1e-3)
    tok = 6 * 16384
    core = flops.flash_flops_per_step(real.config, real.traffic)
    projections = 6 * flops.mla_token_macs(model) * tok
    assert core / step == pytest.approx(0.42, abs=0.005)
    assert projections / step == pytest.approx(0.22, abs=0.005)
    assert 2 * 2048 * 19360 * tok / step == pytest.approx(0.13, abs=0.005)
    assert 3 * 2048 * 10240 * tok / step == pytest.approx(0.10, abs=0.005)
    assert 5 * 3 * 2048 * 1536 * tok / step == pytest.approx(0.08, abs=0.005)
    assert flops.experts_flops_per_step(real.config, real.traffic) / step == pytest.approx(
        0.04, abs=0.005)
    mtp = (flops.mla_token_macs(model) + 2048 * 64 + 3 * 2048 * 1536 + 2 * 2048 * 2048
           + 2048 * 19360) * tok + core / 6 + flops.experts_flops_per_step(
               real.config, real.traffic) / 5
    assert mtp / step == pytest.approx(0.21, abs=0.01)


def test_the_familys_readers_read_scopes_and_kernels_by_name(root):
    """A hand-built reduction: the scopes of the backward pass are wrapped
    by their transforms; the flash kernels are found by name."""
    cell = spec.resolve(CELL, root)
    reduced = {"steps": 2, "op_s": {"flash_fwd": 1e-3, "flash_bwd_dq": 1e-3,
                                    "flash_bwd_dkv": 2e-3, "fusion": 9.0},
               "op_events": {},
               "scope_s": {
                   "jit(step)/jvp(forward)/layer1/attention/attn/latent/dot_general": 2e-3,
                   "jit(step)/transpose(jvp(forward))/layer1/transpose(jvp(attention))/attn/"
                   "transpose(jvp(latent))": 4e-3,
                   "jit(step)/jvp(forward)/mtp/mtp0/attention/attn/latent": 6e-3,
                   "jit(step)/jvp(forward)/mtp/mtp0/moe/experts": 2e-3,
                   "jit(step)/transpose(jvp(forward))/transpose(jvp(mtp))/lm_head": 4e-3,
                   "jit(step)/jvp(forward)/layer3/attention/attn/core": 1e-3,
                   "jit(step)/optimizer/latent_free": 7.0}}
    run = {"trace": reduced, "config": cell.config, "traffic": cell.traffic,
           "peaks": tiny.PEAKS}
    got = {n: read(run) for n, read in cell.readers().items() if n in NEW}
    assert got["mla_latent_ms"] == pytest.approx(6.0)
    assert got["mtp_ms"] == pytest.approx(6.0)
    assert got["flash_kernels_roofline"] == pytest.approx(
        100 * flops.flash_flops_per_step(cell.config, cell.traffic) * 2 / (4e-3 * 1e12))
    nothing = dict(run, trace={"steps": 2, "op_s": {"fusion": 1.0}, "op_events": {},
                               "scope_s": {"jit(step)/jvp(forward)/layer1/attention": 1.0}})
    assert all(read(nothing) is None for n, read in cell.readers().items() if n in NEW)
    assert all(read(dict(run, trace=None)) is None for n, read in cell.readers().items()
               if n in NEW)
    # a family without the count: the roofline reads nothing
    nemotron = spec.resolve("nemotron3_nano_train_8k")
    from benchmark.layer_metrics import flash_kernels_roofline
    assert flash_kernels_roofline.read(dict(run, config=nemotron.config)) is None
