#!/usr/bin/env python3
"""The walks whose every number ``pinned_walks.json`` holds: ``follow`` over
the two tiny image families, sound, with each fault, and in the control's
precision (the toy family's come from ``toy_walk.py``, which needs a copy).

    python3 benchmark/tests/walks.py > benchmark/tests/pinned_walks.json

writes the file anew, toy family included. Do that only on a tree whose walk
is known to be right: the file is what a later change to the walk is held to.
"""
import json
import os
import subprocess
import sys
import tempfile

if __name__ == "__main__":  # as benchmark/tests/conftest.py sets them for pytest
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

SEED = 2 ** 31 + 4321
CELLS = {"tiny_rn": ("tiny_resnet", "tiny_uint8_b8"), "tiny_vit": ("tiny_vit", "tiny_f32_b4")}
VARIANTS = {"sound": {}, "half_batch": {"fault": "half_batch"},
            "quarter_batch": {"fault": "quarter_batch"}, "control": {}}
PINNED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "pinned_walks.json")


def config(name: str) -> dict:
    from benchmark.tests import tiny
    return tiny.CONFIGS[CELLS[name][0]]


def walk(name: str, variant: str) -> dict:
    """What ``follow`` returns for one tiny family in one variant."""
    from benchmark.generators import image_batches
    from benchmark.harness import check
    from benchmark.reference import follow
    from benchmark.tests import tiny
    config, mix = tiny.CONFIGS[CELLS[name][0]], tiny.TRAFFIC[CELLS[name][1]]
    bounds = check.boundaries(8 if config["family"] == "vit" else 1)
    stream = image_batches.make(mix, config, SEED)
    batches = [stream.batch(i) for i in range(bounds[-1])]
    kw = dict(VARIANTS[variant])
    if variant == "control":
        kw["precision"] = config["control_precision"]
    return follow.follow(config, SEED, batches, bounds,
                         augment_seed=SEED % (2 ** 31 - 1), **kw)


def numbers(got: dict) -> dict:
    """The numbers of a walk, flat, by a path of names (what it says of
    itself beside them, ``walk``, is no number of the comparison)."""
    flat = {}

    def put(prefix, node):
        if isinstance(node, dict):
            for k, v in node.items():
                put(f"{prefix}/{k}" if prefix else str(k), v)
        else:
            flat[prefix] = node
    put("", {k: v for k, v in got.items() if k != "walk"})
    return flat


def gap(got: dict, want: dict) -> float:
    """The widest gap between two walks' numbers, each against the wanted
    number's own size, a probe's against its leaf's norm if that is larger."""
    if set(got) != set(want):
        raise KeyError(sorted(set(got) ^ set(want))[:6])
    worst = 0.0
    for path, w in want.items():
        scale = max(abs(w), abs(want.get(path.replace("/probe/", "/norm/"), 0.0)))
        worst = max(worst, abs(got[path] - w) / max(scale, 1e-30))
    return worst


def pinned() -> dict:
    with open(PINNED) as f:
        return json.load(f)


def run_toy_walk(root: str) -> dict:
    """What ``toy_walk.py`` prints, run in a copy that holds the toy family."""
    got = subprocess.run([sys.executable, os.path.join(root, "benchmark", "tests",
                                                       "toy_walk.py")],
                         cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=900)
    if got.returncode != 0:
        raise RuntimeError(got.stderr[-3000:])
    return json.loads(got.stdout.strip().splitlines()[-1])


def main() -> int:
    out = {name: {v: numbers(walk(name, v)) for v in VARIANTS} for name in CELLS}
    from benchmark.tests import test_spec, tiny
    with tempfile.TemporaryDirectory() as tmp:
        root = tiny.make_root(tmp)
        test_spec._add_the_toy_family(root)
        out["toy"] = {v: numbers(got) for v, got in run_toy_walk(root)["raw"].items()}
    print("{" + ",\n".join(  # one line a walk
        json.dumps(name) + ": {" + ",\n ".join(f"{json.dumps(v)}: {json.dumps(flat)}"
                                               for v, flat in by.items()) + "}"
        for name, by in out.items()) + "}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
