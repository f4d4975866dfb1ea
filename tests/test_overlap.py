"""The compiler options of a one-host gradient exchange
(parallel/overlap.py).

A gradient is exchanged one way, by the step XLA propagates; what is
decided here is what that step is compiled under. Pinned on the virtual
8-device mesh: which mesh and backend get the options, that all four
train-step jit sites get them and no other, that a compiler which refuses
them gets none, and that the resolved line says which the step was built
with.
"""
import numpy as np
import pytest

import jax

from distributed_resnet_tensorflow_tpu.parallel import create_mesh
from distributed_resnet_tensorflow_tpu.parallel import overlap as overlap_mod
from distributed_resnet_tensorflow_tpu.parallel.overlap import (
    EXCHANGE_COMPILER_OPTIONS, exchange_compiler_options)
from distributed_resnet_tensorflow_tpu.train import Trainer
from distributed_resnet_tensorflow_tpu.utils.config import (MeshConfig,
                                                            get_preset)


def _tiny_cfg(**kw):
    cfg = get_preset("smoke")
    cfg.model.compute_dtype = "float32"
    cfg.model.resnet_size = 8
    cfg.model.num_classes = 4
    cfg.data.image_size = 8
    cfg.train.batch_size = 16
    cfg.optimizer.schedule = "constant"
    cfg.checkpoint.save_every_secs = 0.0
    for k, v in kw.items():
        cfg.override(k, v)
    return cfg


@pytest.fixture
def compiler_takes_the_options(monkeypatch):
    """The CPU compiler knows none of the TPU compiler's options: stand in
    for a libtpu that takes them (the refusal has its own test)."""
    monkeypatch.setattr(overlap_mod, "_compiler_refusal", lambda options: None)


@pytest.mark.parametrize("in_envelope", [True, False])
@pytest.mark.parametrize("processes", [1, 2])
@pytest.mark.parametrize("shards", [1, 8])
@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_auto_rule(monkeypatch, devices, compiler_takes_the_options,
                   backend, shards, processes, in_envelope):
    """What a mesh and a backend get: the step programs' compiler
    options on one process, more than one data shard, a TPU backend —
    whatever else the configuration says. Everything else, more than one
    process included, gets the compiler's defaults."""
    mesh = create_mesh(MeshConfig(data=shards),
                       devices=jax.devices()[:shards])
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "process_count", lambda: processes)
    cfg = _tiny_cfg(**({} if in_envelope
                       else {"model.cross_replica_bn": "false"}))
    options = exchange_compiler_options(mesh)
    if shards > 1 and processes == 1 and backend == "tpu":
        assert options == EXCHANGE_COMPILER_OPTIONS
        assert options is not EXCHANGE_COMPILER_OPTIONS  # a copy
    else:
        assert options is None
    if processes == 1:
        # the Trainer asks the same rule, whatever the model's BN says
        # (per-replica BN was outside the bucketed step's envelope)
        assert Trainer(cfg, mesh=mesh)._step_compiler_options == options


@pytest.mark.parametrize("axes,expect", [
    ({"data": 8}, True),
    ({"data": 4}, True),                  # the shape that ran on the chips
    ({"data": 1}, False),
    ({"data": 4, "fsdp": 2}, False),      # reduce-scatter and all-gather
    ({"data": 1, "fsdp": 8}, False),
    ({"data": 4, "tensor": 2}, False),    # activation all-reduces beside
    ({"data": 2, "pipeline": 2}, False),
    ({"data": 2, "expert": 2}, False),
    ({"data": 2, "sequence": 2}, False),
])
def test_options_only_on_a_mesh_of_data_shards(
        monkeypatch, devices, compiler_takes_the_options, axes, expect):
    """The options were measured on ``mesh.data=4`` and nothing else: a
    mesh with any other axis of size > 1 keeps the compiler's defaults."""
    count = int(np.prod(list(axes.values())))
    mesh = create_mesh(MeshConfig(**axes), devices=jax.devices()[:count])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = exchange_compiler_options(mesh)
    assert got == (EXCHANGE_COMPILER_OPTIONS if expect else None)


def test_a_compiler_that_refuses_the_options_gets_none(monkeypatch, devices,
                                                       caplog):
    """On another libtpu an option's name may be gone, and an unknown name
    fails every compile. The CPU compiler here is such a compiler: asked
    once, it refuses, and the Trainer builds its steps without options and
    says so, instead of failing its first step."""
    overlap_mod._compiler_refusal.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = create_mesh(MeshConfig(data=8))
    with caplog.at_level("WARNING", logger=overlap_mod.__name__):
        assert exchange_compiler_options(mesh) is None
    assert "No such compile option" in caplog.text
    tr = Trainer(_tiny_cfg(), mesh=mesh)
    assert tr._step_compiler_options is None
    assert tr.resolutions()["step.compiler_options"] == "none"
    assert overlap_mod._compiler_refusal.cache_info().misses == 1  # asked once
    overlap_mod._compiler_refusal.cache_clear()


@pytest.mark.parametrize("backend,shards,expect", [
    ("cpu", 8, False),   # the tier-1 mesh: the programs it always had
    ("tpu", 1, False),   # one chip: no exchange, no option
    ("tpu", 8, True),    # one TPU host, more than one data shard
])
def test_step_jit_sites_get_the_options(monkeypatch, devices,
                                        compiler_takes_the_options, backend,
                                        shards, expect):
    """All four train-step ``jax.jit`` sites (a streamed batch or a device
    dataset's indices, one step or a fused group) are handed the
    exchange's compiler options exactly when ``exchange_compiler_options``
    gives them, no other jit site of the Trainer is, and the resolved
    line says which options the step was built with."""
    cfg = _tiny_cfg()
    mesh = create_mesh(MeshConfig(data=shards),
                       devices=jax.devices()[:shards])
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    tr = Trainer(cfg, mesh=mesh)
    # init_state and attach compile for the backend that is there
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    tr.init_state()
    tr.attach_device_dataset(np.zeros((16, 8, 8, 3), np.uint8),
                             np.zeros((16,), np.int32))
    seen = []
    real_jit = jax.jit

    def recording_jit(fun, **kw):
        seen.append((fun, kw.get("compiler_options")))
        return real_jit(fun, **{k: v for k, v in kw.items()
                                if k != "compiler_options"})

    monkeypatch.setattr(jax, "jit", recording_jit)
    tr.jitted_train_step()
    tr.jitted_multi_step()
    tr.jitted_index_step()
    tr.jitted_index_multi_step()
    tr.jitted_eval_step()
    tr.jitted_predict_step()
    want = EXCHANGE_COMPILER_OPTIONS if expect else None
    steps, others = seen[:4], seen[4:]
    assert steps[0][0] is tr._train_step
    assert [f.__name__ for f, _ in steps[1:]] == \
        ["multi", "gathered_train_step", "multi"]
    assert all(got == want for _, got in steps)
    assert len(others) == 2 and all(got is None for _, got in others)
    line = tr.resolutions()["step.compiler_options"]
    if expect:
        assert all(f"{k}={v}" in line
                   for k, v in EXCHANGE_COMPILER_OPTIONS.items())
    else:
        assert line == "none"


def test_chip_smoke_has_the_overlap_leg():
    import chip_smoke
    assert "overlap" in chip_smoke.LEGS and \
        chip_smoke.CHILD_LEGS["overlap"] is chip_smoke.leg_overlap


def test_resolutions_name_one_exchange(devices):
    """The resolved line a run logs: no ``comm.overlap`` entry (there is
    one exchange and nothing resolves it), and still what ZeRO-1 and the
    step's compiler options resolved to."""
    got = Trainer(_tiny_cfg(), mesh=create_mesh(MeshConfig(data=8))) \
        .resolutions()
    assert "comm.overlap" not in got
    assert got["zero1"] == "off"
    assert got["step.compiler_options"] == "none"
