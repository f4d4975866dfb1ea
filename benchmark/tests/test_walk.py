"""The reference walk itself: its numbers are the ones pinned before its
buffers were donated and its moments given a place to wait, the host path
gives the device path's numbers, the initial weights are not kept, and the
rule that places the moments reads the device's limit and the count."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmark.harness import spec
from benchmark.reference import follow
from benchmark.tests import walks

PINNED = walks.pinned()
V5E_LIMIT = 16_909_336_064  # bytes_limit of one TPU v5 lite chip (chiprun_out/pr29)


@pytest.fixture(scope="module")
def walked():
    """Each tiny walk made once, whichever test asks first."""
    done = {}

    def get(name, variant):
        if (name, variant) not in done:
            done[name, variant] = walks.walk(name, variant)
        return done[name, variant]
    return get


@pytest.mark.parametrize("variant", list(walks.VARIANTS))
@pytest.mark.parametrize("name", list(walks.CELLS))
def test_every_number_of_the_walk_is_the_pinned_one(walked, name, variant):
    """Losses, the first gradient, the moment and the change, leaf by leaf,
    sound, with each fault and in the control's precision: what the walk
    gave at PR 27 (``pinned_walks.json``, to 1e-6: a CPU's compiled
    arithmetic is not the same to the last bit on every machine)."""
    got = walked(name, variant)
    assert got["walk"]["moments"] == "device" and got["walk"]["groups"] == 1
    assert walks.gap(walks.numbers(got), PINNED[name][variant]) <= 1e-6


@pytest.mark.parametrize("name", list(walks.CELLS))
def test_the_moments_waiting_on_the_host_change_no_number(walked, monkeypatch, name):
    """A limit handed in here, which the walk's bytes pass the half of: the
    moments wait on the host and the update runs over groups of leaves."""
    sound = walked(name, "sound")
    config = walks.config(name)
    weights = sum(s.size * 4 for s in jax.eval_shape(
        lambda k: follow.family_module(config["family"]).init_params(k, config["model"]),
        follow.init_key(0)).values())
    monkeypatch.setattr(follow, "device_bytes_limit", lambda devices: 2 * int(weights))
    on_host = walks.walk(name, "sound")
    assert on_host["walk"]["moments"] == "host" and on_host["walk"]["groups"] >= 4
    assert walks.gap(walks.numbers(on_host), walks.numbers(sound)) <= 1e-7


def test_the_initial_weights_are_made_twice_and_kept_never(monkeypatch):
    """The initialiser's program runs once at the start and once when the
    change is read, and by then no buffer of its first result is left: the
    update was given them."""
    monkeypatch.setattr(follow, "_COMPILED", {})  # pieces no other test has called
    fns = follow._functions(walks.config("tiny_vit"), None)
    made = []

    def init(*args):
        assert all(leaf.is_deleted() for first in made for leaf in first.values())
        made.append(fns_init(*args))
        return dict(made[-1])
    fns_init = fns["init"]
    monkeypatch.setitem(fns, "init", init)
    walks.walk("tiny_vit", "sound")
    assert len(made) == 2 and not any(leaf.is_deleted() for leaf in made[1].values())
    assert fns_init._cache_size() == 1  # one program, run twice: not traced again for the end


def _shapes(params_m, moments=2):
    leaf = jax.ShapeDtypeStruct((int(params_m * 1e6) // 8,), np.float32)
    params = {f"leaf{i}": leaf for i in range(8)}
    return params, {f"m{j}": dict(params) for j in range(moments)}


def test_where_the_moments_wait_follows_from_the_limit_and_the_count():
    assert follow.layout(*_shapes(304), V5E_LIMIT) == (False, [tuple(_shapes(304)[0])])
    assert follow.layout(*_shapes(25.6, moments=1), V5E_LIMIT)[0] is False
    assert follow.layout(*_shapes(707), None)[0] is False  # no limit stated: the CPU
    for params_m in (506, 707):
        on_host, groups = follow.layout(*_shapes(params_m), V5E_LIMIT)
        assert on_host and sorted(sum(groups, ())) == sorted(_shapes(params_m)[0])
        assert all(len(g) * (params_m * 1e6 / 8) * 4 <= V5E_LIMIT / 16 for g in groups)
    # a leaf larger than a sixteenth of the limit is a group of its own
    assert follow.layout({"big": jax.ShapeDtypeStruct((10 ** 9,), np.float32),
                          "small": jax.ShapeDtypeStruct((8,), np.float32)},
                         {}, 10 ** 9) == (True, [("big",), ("small",)])


def test_walk_size_rehearses_on_the_cpu_and_prints_its_four_numbers():
    tool = os.path.join(spec.HERE, "tools", "walk_size.py")
    got = subprocess.run([sys.executable, tool, "--cpu"], cwd=spec.ROOT,
                         capture_output=True, text=True, timeout=600)
    assert got.returncode == 0, got.stderr[-3000:]
    out = json.loads(got.stdout.strip().splitlines()[-1])
    assert {"parameters", "peak_bytes_in_use", "bytes_limit", "seconds_a_step"} <= set(out)
    assert out["parameters"] == 3120 and out["walked"] and out["seconds_a_step"] > 0
    assert out["blocks_a_step"] >= 2 and out["rule"] and len(out["loss"]) == 3
    assert out["walk"]["moments"] == "device"  # the CPU states no limit
