"""The sdar_moe family (SDAR-30B-A3B's share) at a size a test can hold: a
tiny cell laid over a copy of the benchmark as files and entries alone,
correct when sound, and a limit failed by each fault the cell can have; the
family's count of operations against a count by hand; its two readers."""
import json
import os
import time

import jax
import numpy as np
import pytest

from benchmark.flops import sdar_moe as flops
from benchmark.generators import blockdiff_stream
from benchmark.harness import check, spec, window
from benchmark.harness.program import Program
from benchmark.reference import follow
from benchmark.reference import sdar_moe as ref
from benchmark.tests import tiny

SEED = 2 ** 31 + 4321
MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "layer_types": ["full_attention"] * 3, "layers_published": 48, "num_dense_layers": 0,
    "rope_theta": 1000000, "rope_all_layers": True, "rms_norm_eps": 1e-06,
    "moe_intermediate_size": 32, "experts_published": 16, "experts_held": [4, 8],
    "num_experts_per_tok": 4, "num_shared_experts": 0, "norm_topk_prob": True,
    "score_func": "softmax", "mup_enabled": False, "vocab_published": 400, "vocab_held": 50,
    "mask_token_held": 49, "block_length": 4, "noise_eps": 0.001, "seq_len": 64,
    "compute_dtype": "float32"}
CONFIG = {
    "family": "sdar_moe", "source": "test fixture", "preset": "sdar_30b_a3b_share8",
    "overrides": {**{".".join(ref.HELD_ELSEWHERE.get(k, ["model", k])): v
                     for k, v in MODEL.items() if k not in ref.FIXED_IN_CODE["model"]},
                  "model.attention_impl": "flash_interpret"},
    "reduced": [], "start_step": 2000, "model": MODEL,
    "optimizer": {"name": "adamw", "learning_rate": 0.0003, "weight_decay": 0.1, "b1": 0.9,
                  "b2": 0.999, "eps": 1e-08, "schedule": "cosine", "warmup_steps": 2000,
                  "total_steps": 100000},
    "control_precision": "fp8"}
TRAFFIC = {"generator": "blockdiff_stream", "seq_len": 64, "per_chip_batch": 4, "chips": 1,
           "mesh": {"data": 1}, "zipf_exponent": 0.7, "distinct_batches": 4,
           "trace_dispatches": 2, "overrides": {}}
#: float32 against float32: rounding, the order of sums, and on a rare token
#: the fourth against the fifth expert
LIMITS = {"limits": {"loss_1": 1e-4, "loss_2": 1e-4, "loss_3": 1e-4, "grad_gap": 5e-3,
                     "grad_mid": 5e-4, "grad_dir": 2e-3, "change_gap": 5e-3,
                     "change_mid": 5e-4, "change_dir": 2e-3},
          "not_compared": {}}
CELL = "tiny_sdar_cell"
REAL = "sdar_30b_a3b_train_4k"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark with the tiny cells, and the tiny sdar_moe
    cell added as a configuration, a traffic mix, a limits file and entries:
    no file that was there is touched."""
    root = tiny.make_root(str(tmp_path_factory.mktemp("sdar")))
    here = os.path.join(root, "benchmark")
    before = {p: os.path.getmtime(p) for base, _, files in os.walk(here)
              for p in (os.path.join(base, f) for f in files)}
    for kind, name, body in (("configs", "tiny_sdar", CONFIG),
                             ("traffic", "tiny_blockdiff_b4", TRAFFIC), ("limits", CELL, LIMITS)):
        with open(os.path.join(here, kind, name + ".json"), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny_sdar", "source": "test fixture",
                             "file": "benchmark/configs/tiny_sdar.json", "reduced": [],
                             "why": "fits a CPU test"})
    bench["workloads"].append({"name": CELL, "config": "tiny_sdar",
                               "traffic": "tiny_blockdiff_b4", "chips": 1,
                               "why": "fits a CPU test"})
    for m in bench["per_layer"]:
        if REAL in m["workloads"]:
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    assert all(os.path.getmtime(p) == t for p, t in before.items())
    return root


READERS = ["compile_s", "stage_ms", "step_device_ms", "step_mfu", "device_idle_share",
           "input_wait_ms", "dispatch_ms", "trainer_init_s", "attention_ms", "moe_ms",
           "lm_head_ms", "blockdiff_flash_roofline", "sdar_experts_roofline"]


def test_the_cell_resolves_with_its_thirteen_readers_and_not_the_token_cells(root):
    cell = spec.resolve(CELL, root)
    assert sorted(cell.readers()) == sorted(READERS)
    real = spec.resolve(REAL)
    assert sorted(real.readers()) == sorted(READERS)
    assert real.config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert real.traffic["per_chip_batch"] == 2 and real.traffic["seq_len"] == 4096
    token_cell = spec.resolve("trinity_mini_train_8k")
    assert not {"blockdiff_flash_roofline", "sdar_experts_roofline"} & set(token_cell.readers())


def test_the_configuration_is_the_catalog_rows_at_published_widths():
    """Every key of the source's config.json is there under its own name;
    the three cut keys are listed in ``reduced`` and none of them is a
    width; the stated count of parameters is the count of the leaves."""
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
        "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    config = spec.resolve(REAL).config
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == sorted(config["reduced"]) == ["num_experts", "num_hidden_layers",
                                                    "vocab_size"]
    model = config["model"]
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == \
        (len(model["layer_types"]), model["experts_held"][1] - model["experts_held"][0],
         model["vocab_held"]) == (6, 16, 18992)
    leaves = sum(int(np.prod(s)) for s in ref._shapes(model).values())
    assert leaves == config["deployment"]["parameters"] == 645623296
    assert set(config["assumed"]) >= {"block_length", "noise", "mask_token_held",
                                      "norms_and_rotary", "auxiliary_loss", "weights"}


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


def _weights_left_out(sound, state, batch):
    """The 1/t weights left out of the loss: every masked id counts once."""
    return sound(state, dict(batch, t=jax.numpy.ones_like(batch["t"])))


FAULTS = {
    "half_batch": lambda sound, s, b: sound(
        s, jax.tree_util.tree_map(lambda x: x[:x.shape[0] // 2], b)),
    "weights_left_out": _weights_left_out,
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
    stream = blockdiff_stream.make(TRAFFIC, CONFIG, SEED)
    batches = [stream.batch(i) for i in range(3)]
    return batches, follow.follow(CONFIG, SEED, batches, [1, 2, 3])


def _clean_sees_its_noisy_twin(monkeypatch):
    """One rule of the four broken: a clean query sees the noisy keys of its
    own block too."""
    sound = ref.seen

    def seen(q_rows, k_rows, length, block):
        qb, kb = ((q_rows % length) // block)[:, None], ((k_rows % length) // block)[None, :]
        twin = (q_rows >= length)[:, None] & (k_rows < length)[None, :] & (qb == kb)
        return sound(q_rows, k_rows, length, block) | twin
    monkeypatch.setattr(ref, "seen", seen)
    return {}


def _positions_by_row(monkeypatch):
    """The clean copy at positions L..2L-1, as a rotary that counts rows."""
    sound = ref._rotary
    monkeypatch.setattr(ref, "_rotary", lambda x, theta, positions: sound(
        x, theta, np.arange(len(positions))))
    return {}


@pytest.mark.parametrize("fault", ["control_fp8", "clean_sees_its_noisy_twin",
                                   "positions_by_row"])
def test_a_fault_of_the_walk_put_in_the_programs_place_fails_a_limit(walked, monkeypatch,
                                                                      fault):
    """The reference in the precision below, with one rule of the mask
    broken, and with the twins at different positions, each put in the
    program's place."""
    batches, sound = walked
    follow._COMPILED.clear()  # the walk's programs are kept per configuration
    kwargs = {"control_fp8": lambda mp: {"precision": "fp8"},
              "clean_sees_its_noisy_twin": _clean_sees_its_noisy_twin,
              "positions_by_row": _positions_by_row}[fault](monkeypatch)
    try:
        bad = follow.follow(CONFIG, SEED, batches, [1, 2, 3], **kwargs)
    finally:
        follow._COMPILED.clear()
    numbers, _ = check.compare(bad, sound)
    ok, rows = check.verdict(numbers, LIMITS)
    assert not ok, rows


def test_the_generator_gives_the_three_leaves_from_the_seed():
    a = blockdiff_stream.make(TRAFFIC, CONFIG, SEED)
    b = blockdiff_stream.make(TRAFFIC, CONFIG, SEED)
    other = blockdiff_stream.make(TRAFFIC, CONFIG, SEED + 1)
    first = a.batch(0)
    assert {k: (v.shape, v.dtype.name) for k, v in first.items()} == {
        "tokens": ((4, 64), "int32"), "masked": ((4, 64), "uint8"), "t": ((4, 16), "float32")}
    assert all(np.array_equal(first[k], b.batch(0)[k]) for k in first)
    assert not np.array_equal(first["tokens"], other.batch(0)["tokens"])
    assert not np.array_equal(first["tokens"], a.batch(1)["tokens"])
    assert a.batch(4) is a.batch(0) and a.rows == 4
    pool = [a.batch(i) for i in range(4)]
    assert max(p["tokens"].max() for p in pool) < MODEL["mask_token_held"]
    assert min(p["t"].min() for p in pool) >= 0.001 and max(p["t"].max() for p in pool) <= 1
    real = spec.resolve(REAL)
    big = blockdiff_stream.make(dict(real.traffic, distinct_batches=1), real.config, SEED)
    batch = big.batch(0)
    assert batch["tokens"].shape == (2, 4096) and batch["t"].shape == (2, 1024)
    assert abs(batch["masked"].mean() - 0.5) < 0.03 and batch["tokens"].max() <= 18990


def test_the_count_of_operations_is_the_count_by_hand():
    length, rows, block = 64, 4, 4
    positions = 2 * length * rows
    attn = 64 * (64 + 32 + 32) + 64 * 64           # q, k, v; output
    per_position = 3 * (attn + 64 * 16)            # and the router
    pairs = sum(block * (i // block + 1) for i in range(length)) \
        + sum(block * (i // block) for i in range(length)) + length * block
    assert pairs == flops.kept_pairs(length, block) == length * length + length * block
    scores = 2 * (4 * 16) * rows * 3 * pairs
    routed = 3 * (positions * 4 * 4 / 16) * 3 * 64 * 32
    head = rows * length * 1.001 / 2 * 64 * 50
    config = {"model": MODEL}
    assert flops.forward_macs_per_step(config, TRAFFIC) == pytest.approx(
        positions * per_position + scores + routed + head, rel=1e-12)
    assert flops.train_flops_per_step(config, TRAFFIC) == \
        6 * flops.forward_macs_per_step(config, TRAFFIC)
    assert flops.flash_flops_per_step(config, TRAFFIC) == 6 * scores
    assert flops.experts_flops_per_step(config, TRAFFIC) == 3 * 2 * routed
    # the real cell: about 25 TFLOP a step, the scores 40% of it
    real = spec.resolve(REAL)
    step = flops.train_flops_per_step(real.config, real.traffic)
    assert 24.9e12 < step < 25.0e12
    assert flops.flash_flops_per_step(real.config, real.traffic) / step == pytest.approx(
        0.397, abs=0.002)


def test_the_familys_readers_read_scopes_and_kernels_by_name(root):
    """A hand-built reduction: the scopes of the backward pass are wrapped
    by their transforms, the kernels carry the names their ``name=`` gave,
    and the compiler's grouped products carry a name and no scope."""
    cell = spec.resolve(CELL, root)
    reduced = {"steps": 2, "op_s": {"flash_fwd": 1e-3, "flash_bwd_dq": 2e-3,
                                    "flash_bwd_dkv": 3e-3, "fusion": 9.0,
                                    "ragged-dot-none": 5e-4, "ragged-dot-metadata": 5e-4},
               "scope_s": {
                   "jit(step)/jvp(forward)/layer1/attention/attn/dot_general": 1e-3,
                   "jit(step)/transpose(jvp(forward))/layer1/transpose(jvp(attention))/attn": 3e-3,
                   "jit(step)/jvp(forward)/layer1/moe/experts": 2e-3,
                   "jit(step)/transpose(jvp(forward))/checkpoint/layer1/moe/route": 4e-3,
                   "jit(step)/jvp(forward)/jvp(lm_head)": 5e-3,
                   "jit(step)/jvp(forward)/blockdiff_input/concatenate": 6.0,
                   "jit(step)/optimizer/attention_free": 7.0}}
    run = {"trace": reduced, "config": cell.config, "traffic": cell.traffic,
           "peaks": tiny.PEAKS}
    mine = ("blockdiff_flash_roofline", "sdar_experts_roofline", "attention_ms", "moe_ms",
            "lm_head_ms")
    got = {n: read(run) for n, read in cell.readers().items() if n in mine}
    assert got["attention_ms"] == pytest.approx(2.0) and got["moe_ms"] == pytest.approx(3.0)
    assert got["lm_head_ms"] == pytest.approx(2.5)
    assert got["blockdiff_flash_roofline"] == pytest.approx(
        100 * flops.flash_flops_per_step(cell.config, cell.traffic) * 2 / (6e-3 * 1e12))
    assert got["sdar_experts_roofline"] == pytest.approx(
        100 * flops.experts_flops_per_step(cell.config, cell.traffic) * 2 / (3e-3 * 1e12))
    # a program that has neither the kernels' names nor the scopes
    nothing = dict(run, trace={"steps": 2, "op_s": {"fusion": 1.0},
                               "scope_s": {"jit(step)/jvp(forward)/EncoderBlock_0": 1.0}})
    assert all(read(nothing) is None for n, read in cell.readers().items() if n in got)
    assert all(read(dict(run, trace=None)) is None
               for n, read in cell.readers().items() if n in got)
