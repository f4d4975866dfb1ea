"""``correct`` at a size a test can hold: a sound run passes, the control
fails, and each fault the cells can have, planted under the timed path,
fails. The harness's look for a chip is skipped; the rest of a run is
driven as it stands."""
import time

import jax
import pytest

from benchmark.generators import image_batches
from benchmark.harness import check, spec, window
from benchmark.harness.program import Program
from benchmark.reference import follow
from benchmark.tests import tiny

SEED = 2 ** 31 + 4321


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def _run(root, name, monkeypatch=None, broken=None):
    cell = spec.resolve(name, root)
    if broken is not None:
        class Broken(Program):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                sound = self.trainer._train_step
                self.trainer._train_step = lambda s, b: broken(sound, s, b)
        monkeypatch.setattr(window, "Program", Broken)
    return window.run_cell(cell, SEED, 1.0, False, jax.devices()[:cell.chips],
                           tiny.PEAKS, time.time())


def _first_rows(batch, share):
    return jax.tree_util.tree_map(lambda x: x[:x.shape[0] // share], batch)


FAULTS = {
    "state_unchanged": lambda sound, s, b: (s, sound(s, b)[1]),
    "half_batch": lambda sound, s, b: sound(s, _first_rows(b, 2)),
    "no_exchange": lambda sound, s, b: sound(s, _first_rows(b, 4)),
}


@pytest.mark.parametrize("name", ["tiny_vit1", "tiny_vit4", "tiny_rn"])
def test_a_sound_run_is_correct(root, name):
    result = _run(root, name)
    assert result["correct"] is True, result["check"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "check"
    assert set(result["metrics"]) == {"examples_per_s", "peak_hbm_gib", "setup_s"}


@pytest.mark.parametrize("name,fault", [("tiny_vit1", "state_unchanged"),
                                        ("tiny_vit1", "half_batch"),
                                        ("tiny_vit4", "no_exchange"),
                                        ("tiny_rn", "half_batch")])
def test_a_fault_under_the_timed_path_is_not_correct(root, monkeypatch, name, fault):
    result = _run(root, name, monkeypatch, FAULTS[fault])
    assert result["correct"] is False, result["check"]
    over = [n for n, c in result["check"].items() if c["value"] > c["limit"]]
    assert over
    if fault == "state_unchanged":  # the change reads 1: nothing moved
        assert result["check"]["change_gap"]["value"] == pytest.approx(1.0, abs=1e-3)


@pytest.mark.parametrize("name,traffic", [("tiny_vit", "tiny_f32_b4"),
                                          ("tiny_resnet", "tiny_uint8_b8")])
def test_the_control_in_the_precision_below_is_not_correct(root, name, traffic):
    """The reference in the next precision down, put in the program's place."""
    config, mix = tiny.CONFIGS[name], tiny.TRAFFIC[traffic]
    bounds = check.boundaries(8 if config["family"] == "vit" else 1)
    stream = image_batches.make(mix, config, SEED)
    batches = [stream.batch(i) for i in range(bounds[-1])]
    ref = follow.follow(config, SEED, batches, bounds, augment_seed=SEED % (2 ** 31 - 1))
    control = follow.follow(config, SEED, batches, bounds,
                            augment_seed=SEED % (2 ** 31 - 1),
                            precision=config["control_precision"])
    limits = tiny.LIMITS_BY_CELL["tiny_rn" if name == "tiny_resnet" else "tiny_vit1"]
    ok, rows = check.verdict(check.compare(control, ref)[0], limits)
    assert not ok, rows
    same, rows = check.verdict(check.compare(ref, ref)[0], limits)
    assert same and all(value == 0 for _, value, _ in rows)
