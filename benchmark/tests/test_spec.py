"""BENCHMARK.json against its contract, the harness driven by data, the
device gate, and the refusal of a share over 100."""
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark.harness import device, spec, window
from benchmark.tests import tiny, walks
from benchmark.tools import walk_size

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_benchmark_json_keeps_to_the_contract():
    bench = spec.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"] and bench["command"][:2] == ["python3", "benchmark/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [c["name"] for c in bench["configs"]] + [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("benchmark/") and os.path.isfile(os.path.join(spec.ROOT, c["file"]))
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and _line(w["why"])
        assert w["chips"] in (1, 4)
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert set(e2e) == {"examples_per_s", "peak_hbm_gib", "setup_s"}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"])
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        layers.add(m["layer"])
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert any("mfu" in m["name"] for m in bench["per_layer"])
    for m in bench["per_layer"]:  # what exists only across chips is read only there
        if m["name"] == "collective_exposed_ms":
            assert m["workloads"] == [w["name"] for w in four]
    assert len(json.dumps(bench)) < 64 * 1024


@pytest.mark.parametrize("name", spec.cell_names())
def test_every_cell_resolves_with_a_reader_per_metric(name):
    cell = spec.resolve(name)
    assert set(cell.readers()) == {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"examples_per_s", "peak_hbm_gib", "setup_s"}
    assert set(cell.limits["limits"]) | set(cell.limits.get("not_compared", {}))
    assert cell.traffic["chips"] == cell.chips


def test_a_metric_without_a_reader_or_a_cell_without_files_is_an_error(tmp_path):
    root = tiny.make_root(str(tmp_path))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["per_layer"].append({"name": "no_such_ms", "unit": "ms", "better": "lower",
                               "source": "device_trace", "layer": "step",
                               "moves": "examples_per_s"})
    bench["workloads"].append({"name": "orphan", "config": "tiny_vit", "traffic": "nowhere",
                               "chips": 1, "why": "x"})
    with open(path, "w") as f:
        json.dump(bench, f)
    with pytest.raises(spec.SpecError, match="layer_metrics/no_such_ms.py"):
        spec.resolve("tiny_vit1", root)
    with pytest.raises(spec.SpecError, match="missing file"):
        spec.resolve("orphan", root)


def _stamps(root):
    before = {}
    for base, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            p = os.path.join(base, f)
            before[p] = os.path.getmtime(p), os.path.getsize(p)
    return before


def test_a_cell_is_added_by_files_and_entries_alone(tmp_path):
    """Copy the benchmark, add a configuration, a traffic mix, a per-layer
    metric and a cell, edit no file that was there but BENCHMARK.json's
    lists, and the copy's own command lists and resolves the new cell."""
    root = tiny.make_root(str(tmp_path))
    before = _stamps(root)
    here = os.path.join(root, "benchmark")
    with open(os.path.join(here, "layer_metrics", "steps_traced.py"), "w") as f:
        f.write("def read(run):\n    return run['trace'] and run['trace']['steps']\n")
    with open(os.path.join(here, "traffic", "tiny_f32_b2.json"), "w") as f:
        json.dump(dict(tiny.TRAFFIC["tiny_f32_b4"], per_chip_batch=2), f)
    with open(os.path.join(here, "configs", "tiny_vit_deep.json"), "w") as f:
        body = json.loads(json.dumps(tiny.CONFIGS["tiny_vit"]))
        body["overrides"]["model.vit_depth"] = body["model"]["vit_depth"] = 3
        json.dump(body, f)
    with open(os.path.join(here, "limits", "new_cell.json"), "w") as f:
        json.dump(tiny.LIMITS, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny_vit_deep", "source": "test",
                             "file": "benchmark/configs/tiny_vit_deep.json",
                             "reduced": [], "why": "a third block"})
    bench["workloads"].append({"name": "new_cell", "config": "tiny_vit_deep",
                               "traffic": "tiny_f32_b2", "chips": 1, "why": "added as data"})
    bench["per_layer"].append({"name": "steps_traced", "unit": "steps", "better": "higher",
                               "source": "device_trace", "layer": "step",
                               "moves": "examples_per_s", "workloads": ["new_cell"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    for p, stamp in before.items():
        assert (os.path.getmtime(p), os.path.getsize(p)) == stamp
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    listed = subprocess.run([sys.executable, os.path.join(here, "run.py"), "--list"],
                            cwd=root, env=env,
                            capture_output=True, text=True, timeout=120)
    assert listed.returncode == 0 and "new_cell" in listed.stdout.split()
    code = ("import sys; sys.path.insert(0, '.'); from benchmark.harness import spec; "
            "c = spec.resolve('new_cell'); assert spec.ROOT == sys.argv[1], spec.ROOT; "
            "print(sorted(c.readers()), c.config['model']['vit_depth'], "
            "c.traffic['per_chip_batch'])")
    got = subprocess.run([sys.executable, "-c", code, root], cwd=root, env=env,
                         capture_output=True, text=True, timeout=120)
    assert got.returncode == 0, got.stderr
    assert "steps_traced" in got.stdout and got.stdout.strip().endswith("3 2")


def _add_the_toy_family(root):
    """``benchmark/tests/toy`` laid over the copy, file by new file, and its
    entries appended to BENCHMARK.json's lists."""
    walk_size.add_the_toy_files(os.path.join(root, "benchmark"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy_tokens", "source": "test",
                             "file": "benchmark/configs/toy_tokens.json",
                             "reduced": [], "why": "a family that is no image classifier"})
    bench["workloads"].append({"name": "toy_cell", "config": "toy_tokens",
                               "traffic": "toy_tokens_b16", "chips": 1, "why": "as files"})
    for name, unit in (("toy_ffn_roofline", "%"), ("toy_ffn_scope_ms", "ms")):
        bench["per_layer"].append({"name": name, "unit": unit, "better": "higher",
                                   "source": "device_trace", "layer": "kernels",
                                   "moves": "examples_per_s", "workloads": ["toy_cell"]})
    with open(path, "w") as f:
        json.dump(bench, f)


@pytest.fixture(scope="module")
def toy_walk(tmp_path_factory):
    """The toy family added to a copy as files and entries alone, and what
    ``benchmark/tests/toy_walk.py`` reads there (one process, the copy's
    own modules)."""
    root = tiny.make_root(str(tmp_path_factory.mktemp("toy")))
    before = _stamps(root)
    _add_the_toy_family(root)
    for p, stamp in before.items():  # no file that was there changed
        assert (os.path.getmtime(p), os.path.getsize(p)) == stamp, p
    out = walks.run_toy_walk(root)
    assert out["root"] == root
    return out


def test_a_family_that_is_no_image_classifier_comes_as_files_alone(toy_walk):
    """Token ids, a loss of two terms, a vector that a rule moves, a decay
    mask and blocks of whole sequences of its own: the cell resolves with
    its two readers, the walk in blocks gives the numbers of the walk in
    one, and put in the program's place it is correct."""
    assert sorted(toy_walk["read"]) == ["toy_ffn_roofline", "toy_ffn_scope_ms"]
    assert toy_walk["blocks_a_step"] >= 2
    assert toy_walk["ruled_moved"] > 0
    assert toy_walk["sound"]["correct"], toy_walk["sound"]
    assert max(toy_walk["sound"]["numbers"].values()) < 1e-5


@pytest.mark.parametrize("fault", ["half_batch", "second_term_left_out", "rule_left_out",
                                   "control_fp8"])
def test_a_fault_of_the_toy_family_fails_a_limit(toy_walk, fault):
    assert not toy_walk[fault]["correct"] and toy_walk[fault]["over"], toy_walk[fault]


@pytest.mark.parametrize("variant", ["sound", "half_batch", "quarter_batch", "control"])
def test_every_number_of_the_toy_walk_is_the_pinned_one(toy_walk, variant):
    """As ``test_walk.py`` holds the two image families: what the walk gave
    at PR 27, before its buffers were donated (to 1e-6)."""
    assert walks.gap(walks.numbers(toy_walk["raw"][variant]),
                     walks.pinned()["toy"][variant]) <= 1e-6


def test_the_toy_walk_with_its_moments_on_the_host_changes_no_number(toy_walk):
    """The rule that ``after_update`` moves and the leaf with no gradient go
    through the grouped update too."""
    on_host = toy_walk["raw_on_host"]
    assert on_host["walk"] == dict(on_host["walk"], moments="host", groups=3)
    assert toy_walk["raw"]["sound"]["walk"]["moments"] == "device"
    assert walks.gap(walks.numbers(on_host), walks.numbers(toy_walk["raw"]["sound"])) <= 1e-7


def test_the_toy_familys_readers_find_a_kernel_by_name_and_a_scope(toy_walk):
    """A reader in a new file finds its kernel among every family's seconds
    and counts, whatever its rank, counts its operations from the shapes
    ``run`` carries, and reads its scope's self seconds; with nothing to
    read it returns nothing."""
    macs = 3 * 16 * 32 * 16 * 12  # gate, up and down over 16 sequences of 12 tokens
    assert toy_walk["read"] == {
        "toy_ffn_roofline": pytest.approx(100 * 2 * macs * 4 / (2e-6 * 1e12)),
        "toy_ffn_scope_ms": pytest.approx(2.0)}
    assert toy_walk["read_nothing"] == {"toy_ffn_roofline": None, "toy_ffn_scope_ms": None}
    assert toy_walk["flops_per_step"] == 6 * 16 * 12 * (3 * 16 * 32 + 2 * 16 * 32)


def test_what_the_program_does_not_hold_comes_from_the_family_or_the_file():
    from types import SimpleNamespace as NS

    from benchmark.harness import program
    cfg = NS(model=NS(d_model=16), optimizer=NS(name="adamw"), data=NS(image_size=32))
    config = {"model": {"d_model": 16, "vocab_published": 256, "experts_published": 8},
              "optimizer": {"name": "adamw", "b1": 0.9}}
    family = NS(FIXED_IN_CODE={"model": ["vocab_published"], "optimizer": ["b1"]},
                HELD_ELSEWHERE={})
    with pytest.raises(AttributeError, match="experts_published"):
        program._stated(cfg, config, family)
    program._stated(cfg, dict(config, fixed_in_code={"model": ["experts_published"]}), family)
    # a family that states neither gets the table the two image families have
    program._stated(cfg, {"model": {"d_model": 16, "image_size": 32, "mlp_ratio": 4},
                          "optimizer": {"eps": 1e-8}}, NS())
    with pytest.raises(spec.SpecError, match="d_model=32"):
        program._stated(cfg, {"model": {"d_model": 32}, "optimizer": {}}, NS())


def test_the_first_moment_is_found_in_a_partitioned_optimizers_state():
    """A program that gives a rule-moved leaf an optimizer of its own keeps
    a dict of states, one per label: the reader walks it, passes the label
    that has no moment, and does not take an array's ``trace`` method for one."""
    import jax.numpy as jnp
    import optax

    from benchmark.harness.program import Program
    params = {"w": jnp.ones((2, 3)), "count_bias": jnp.zeros(3)}
    tx = optax.multi_transform({"a_ruled": optax.set_to_zero(), "adam": optax.adamw(1e-3)},
                               {"w": "adam", "count_bias": "a_ruled"})
    state = tx.init(params)
    assert isinstance(state.inner_states, dict)
    mu = Program.first_moment((optax.EmptyState(), state))
    assert mu["w"].shape == (2, 3) and not hasattr(mu["count_bias"], "shape")  # masked out
    # the chain of one optimizer, as the accepted cells have it, reads as before
    chain = optax.chain(optax.clip_by_global_norm(1.0), optax.sgd(0.1, momentum=0.9)).init(params)
    assert set(Program.first_moment(chain)) == {"w", "count_bias"}
    with pytest.raises(spec.SpecError, match="no first moment"):
        Program.first_moment((optax.EmptyState(), {"count": jnp.zeros(())}))


def test_the_device_gate_exits_non_zero_on_the_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run([sys.executable, os.path.join(spec.HERE, "run.py"), "--workload",
                          "rn50_staged", "--seed", "1", "--seconds", "1", "--trace", "0"],
                         cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert got.returncode != 0
    assert got.stdout.strip() == ""
    assert "no accelerator" in got.stderr


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(device.DeviceError):
        device.peaks_for("TPU v9 imaginary")
    with pytest.raises(device.DeviceError):
        device.peaks_for("_source")
    assert device.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_a_share_over_100_is_refused():
    cell = spec.resolve("rn50_staged")
    run = {"trace": {"steps": 10, "window_s": 1.0, "busy_s": 0.5, "busy_s_device0": 0.5,
                     "collective_exposed_s": 0.0, "collective_events": 0},
           "peaks": {"bf16_flops_per_s": 197e12}, "chips": 1, "compile_s": 1.0,
           "stages_before": {}, "stages_after": {},
           "flops_per_step": 197e12 * 0.2}  # 10 steps a second: 200% of peak
    with pytest.raises(RuntimeError, match="over 100"):
        window.layer_metrics(cell, run)
    run["flops_per_step"] = 197e12 * 0.05
    got = window.layer_metrics(cell, run)
    assert got["step_mfu"] == pytest.approx(50.0)
    assert got["device_idle_share"] == pytest.approx(50.0)
    assert "stage_ms" not in got and "collective_exposed_ms" not in got  # nothing to read
