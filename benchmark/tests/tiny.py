"""Tiny cells for the CPU: the real harness, presets and references at
sizes a test can hold, in float32 so that program and reference agree to
rounding. ``make_root`` copies the benchmark to a directory and adds the
tiny cells as files and entries alone, as a later PR would add a cell."""
from __future__ import annotations

import json
import os
import shutil

from benchmark.harness import spec

PEAKS = {"bf16_flops_per_s": 1e12}

CONFIGS = {
    "tiny_resnet": {
        "family": "resnet_v2", "source": "test fixture", "preset": "imagenet_resnet50",
        "overrides": {"data.dataset": "imagenet", "data.device_augment": "on",
                      "data.coalesced_transfer": "on",
                      "model.num_classes": 11, "model.compute_dtype": "float32",
                      "train.log_every_steps": 2, "optimizer.warmup_start": 0.001,
                      "data.image_size": 64},
        "reduced": [], "start_step": 0,
        "model": {"resnet_size": 50, "num_classes": 11, "image_size": 64,
                  "compute_dtype": "float32", "bn_epsilon": 1e-05},
        "optimizer": {"name": "momentum", "momentum": 0.9, "weight_decay": 0.0001,
                      "schedule": "warmup_piecewise", "warmup_steps": 6240,
                      "warmup_start": 0.001, "boundaries": [37440, 74880, 99840],
                      "values": [0.4, 0.04, 0.004, 0.0004]},
        "control_precision": "bf16"},
    "tiny_vit": {
        "family": "vit", "source": "test fixture", "preset": "vit_large_224",
        "overrides": {"data.coalesced_transfer": "off", "data.image_size": 32,
                      "model.vit_dim": 64, "model.vit_depth": 2, "model.vit_heads": 4,
                      "model.vit_patch_size": 8, "model.num_classes": 10,
                      "model.compute_dtype": "float32"},
        "reduced": [], "start_step": 10000,
        "model": {"vit_patch_size": 8, "vit_dim": 64, "vit_depth": 2, "vit_heads": 4,
                  "mlp_ratio": 4, "num_classes": 10, "image_size": 32,
                  "compute_dtype": "float32", "layer_norm_epsilon": 1e-06},
        "optimizer": {"name": "adamw", "learning_rate": 0.0003, "weight_decay": 0.05,
                      "b1": 0.9, "b2": 0.999, "eps": 1e-08, "schedule": "cosine",
                      "warmup_steps": 10000, "total_steps": 300000},
        "control_precision": "bf16"},
}


def _traffic(dtype: str, rows: int, chips: int, pool: int, size: int = 32) -> dict:
    return {"generator": "image_batches", "dtype": dtype, "image_size": size,
            "per_chip_batch": rows, "chips": chips, "mesh": {"data": chips},
            "distinct_batches": pool, "trace_dispatches": 2, "overrides": {}}


TRAFFIC = {"tiny_uint8_b8": _traffic("uint8", 8, 1, 4, 64),
           "tiny_f32_b4": _traffic("float32", 4, 1, 8),
           "tiny_f32_b4x4": _traffic("float32", 4, 4, 8)}
CELLS = [("tiny_rn", "tiny_resnet", "tiny_uint8_b8", 1),
         ("tiny_vit1", "tiny_vit", "tiny_f32_b4", 1),
         ("tiny_vit4", "tiny_vit", "tiny_f32_b4x4", 4)]
#: float32 against float32: rounding and the order of sums only
LIMITS = {"limits": {"loss_8": 1e-4, "grad_gap": 2e-4, "change_gap": 2e-4,
                     "grad_mid": 1e-4, "change_mid": 1e-4, "grad_dir": 1e-4,
                     "change_dir": 1e-4},
          "not_compared": {}}
#: batch normalisation over 8 rows of 2x2 pixels amplifies rounding, by
#: how much depends on the seed: float32 against float32 reads grad_dir
#: 1e-6 on one seed and 6e-3 on another. Set as the chip cells' limits are,
#: limit = lower * (upper/lower)^0.6, from benchmark/tools/readings.py
#: --cpu-root on 10 seeds from 2**31 + 4321 (control and half batch on 4):
#: lower, the largest sound reading; upper, the bf16 control's smallest
#: where that is three times the lower or more (all but loss_3), else half
#: a batch's
#:           lower    control  half batch
#: loss_1    1.1e-7   1.2e-4   0.017
#: loss_2    3.1e-5   6.8e-4   0.028
#: loss_3    1.7e-4   1.3e-4   7.7e-3
#: grad_gap  4.3e-3   0.049    0.60
#: grad_mid  2.0e-4   4.1e-3   0.31
#: grad_dir  6.0e-3   0.105    0.49
#: change_gap 0.0127  0.048    0.52
#: change_mid 4.7e-4  3.9e-3   0.29
#: change_dir 0.0192  0.133    0.41
LIMITS_RN = {"limits": {"loss_1": 1e-5, "loss_2": 2e-4, "loss_3": 1.5e-3, "grad_gap": 0.018,
                        "grad_mid": 1.2e-3, "grad_dir": 0.033, "change_gap": 0.028,
                        "change_mid": 1.7e-3, "change_dir": 0.06},
             "not_compared": {}}
LIMITS_BY_CELL = {"tiny_rn": LIMITS_RN, "tiny_vit1": LIMITS, "tiny_vit4": LIMITS}


def make_root(dst: str) -> str:
    shutil.copytree(spec.HERE, os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = spec.load_benchmark()
    here = os.path.join(dst, "benchmark")
    for name, body in CONFIGS.items():
        with open(os.path.join(here, "configs", name + ".json"), "w") as f:
            json.dump(body, f)
        bench["configs"].append({"name": name, "source": "test fixture",
                                 "file": f"benchmark/configs/{name}.json",
                                 "reduced": [], "why": "fits a CPU test"})
    for name, body in TRAFFIC.items():
        with open(os.path.join(here, "traffic", name + ".json"), "w") as f:
            json.dump(body, f)
    for cell, config, traffic, chips in CELLS:
        bench["workloads"].append({"name": cell, "config": config, "traffic": traffic,
                                   "chips": chips, "why": "fits a CPU test"})
        with open(os.path.join(here, "limits", cell + ".json"), "w") as f:
            json.dump(LIMITS_BY_CELL[cell], f)
        for m in bench["per_layer"]:
            if m["name"] != "collective_exposed_ms" or chips > 1:
                m["workloads"].append(cell)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return dst
