"""Config system tests (replaces nothing in the reference — it had no tests;
models the flag surface of SURVEY.md §2.16)."""
import json

import pytest

from distributed_resnet_tensorflow_tpu.utils.config import (
    ExperimentConfig, get_preset, parse_args, PRESETS)


def test_presets_exist():
    for name in ("cifar10_resnet50", "cifar100_wrn28_10", "imagenet_resnet50",
                 "imagenet_resnet50_lars32k", "smoke"):
        assert name in PRESETS
        cfg = get_preset(name)
        assert isinstance(cfg, ExperimentConfig)


def test_cifar_preset_matches_reference_recipe():
    """Reference CIFAR recipe: gbs 128, momentum, wd 2e-4, LR drops at
    40k/60k/80k (reference resnet_cifar_main.py:97-99,298-307)."""
    cfg = get_preset("cifar10_resnet50")
    assert cfg.train.batch_size == 128
    assert cfg.optimizer.name == "momentum"
    assert cfg.optimizer.weight_decay == 2e-4
    assert cfg.optimizer.boundaries == (40000, 60000, 80000)
    assert cfg.optimizer.values == (0.1, 0.01, 0.001, 0.0001)


def test_imagenet_preset_matches_reference_recipe():
    """Reference ImageNet recipe (resnet_imagenet_main.py:236-247)."""
    cfg = get_preset("imagenet_resnet50")
    assert cfg.train.batch_size == 1024
    assert cfg.optimizer.warmup_steps == 6240
    assert cfg.optimizer.boundaries == (37440, 74880, 99840)
    assert cfg.optimizer.weight_decay == 1e-4
    assert cfg.model.num_classes == 1001


def test_override_coercion():
    cfg = ExperimentConfig()
    cfg.override("train.batch_size", "256")
    assert cfg.train.batch_size == 256
    cfg.override("model.cross_replica_bn", "false")
    assert cfg.model.cross_replica_bn is False
    cfg.override("optimizer.boundaries", "100,200")
    assert cfg.optimizer.boundaries == (100, 200)
    cfg.override("optimizer.learning_rate", "0.5")
    assert cfg.optimizer.learning_rate == 0.5
    with pytest.raises(KeyError):
        cfg.override("train.nonexistent", "1")


def test_json_roundtrip():
    cfg = get_preset("imagenet_resnet50")
    d = json.loads(cfg.to_json())
    cfg2 = ExperimentConfig.from_dict(d)
    assert cfg2.to_dict() == cfg.to_dict()
    assert cfg2.optimizer.boundaries == cfg.optimizer.boundaries


def test_vit_large_224_preset():
    """The transformer-family >=0.55-MFU contract (measured 0.57,
    docs/perf_vit_classic_r5.md): ViT-L/16 shape, dense attention (196
    tokens is far below the 2k flash crossover), per-chip batch pinned at
    the measured optimum."""
    cfg = get_preset("vit_large_224")
    assert cfg.model.name == "vit"
    assert (cfg.model.vit_dim, cfg.model.vit_depth,
            cfg.model.vit_heads) == (1024, 24, 16)
    assert cfg.data.image_size // cfg.model.vit_patch_size == 14  # 196 tokens
    assert cfg.model.attention_impl == "dense"
    assert cfg.train.batch_size == 32
    assert not cfg.train.remat


def test_parse_args():
    cfg = parse_args(["--preset", "smoke", "--set", "train.train_steps=5"])
    assert cfg.train.train_steps == 5
    assert cfg.data.dataset == "synthetic"


def test_bs512_throughput_preset():
    """The measured single-chip throughput optimum (docs/perf_cifar_r5.md)
    as a preset: linear-scaled LR (x4) with the epoch budget of the
    gbs=128 recipe (4x fewer steps, proportional boundaries)."""
    cfg = get_preset("cifar10_resnet50_bs512")
    base = get_preset("cifar10_resnet50")
    assert cfg.train.batch_size == 4 * base.train.batch_size
    assert cfg.train.train_steps * 4 == base.train.train_steps
    assert cfg.optimizer.values[0] == 4 * base.optimizer.values[0]
    assert len(cfg.optimizer.boundaries) == len(base.optimizer.boundaries)
    assert all(4 * b == bb for b, bb in
               zip(cfg.optimizer.boundaries, base.optimizer.boundaries))
    # epoch budget preserved: steps x batch equal
    assert cfg.train.train_steps * cfg.train.batch_size == \
        base.train.train_steps * base.train.batch_size


@pytest.mark.parametrize("key", [
    "comm.overlap", "comm.bucket_mb", "comm.compress", "comm.hierarchy",
    "comm.intra_axis_size", "comm.autotune",
    "telemetry.comm_timing", "telemetry.comm_timing_reps",
    "telemetry.plan_drift", "telemetry.plan_tolerance",
    "telemetry.plan_drift_window", "telemetry.plan_drift_cooldown_secs",
])
def test_a_removed_key_is_refused(key):
    """The bucketed gradient exchange went with its six ``comm.*`` knobs
    and the probe and drift sentinel that read its plan: an old command
    line or a saved ``--config_json`` that still sets one fails with the
    loader's own unknown-key error, as any misspelt key does."""
    section, name = key.split(".")
    with pytest.raises(KeyError, match="unknown config key"):
        parse_args(["--preset", "smoke", "--set", f"{key}=1"])
    d = get_preset("smoke").to_dict()
    assert name not in d.get(section, {})
    d.setdefault(section, {})[name] = 1
    with pytest.raises(KeyError, match="unknown config key"):
        ExperimentConfig.from_dict(d)
