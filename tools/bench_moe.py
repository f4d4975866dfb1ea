"""Measured MoE step time on real TPU — dense MLP vs Switch top-1 vs top-2.

Single chip (expert weights resident, no expert axis to shard over), ViT
encoder at a fixed token budget; reports ms/step of the full train step so
the one-hot dispatch/combine cost (O(N·E·C) einsums riding the MXU) is a
measured number, not a guess. Writes docs/moe_r3.json.

    python tools/bench_moe.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from distributed_resnet_tensorflow_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache)

configure_compile_cache()


def build(num_experts: int, top_k: int, bs=32, image=64, patch=4,
          dispatch="auto"):
    from distributed_resnet_tensorflow_tpu.parallel.sharding import (
        shard_stacked_batch)
    from distributed_resnet_tensorflow_tpu.train import Trainer
    from distributed_resnet_tensorflow_tpu.utils.config import get_preset
    cfg = get_preset("smoke")
    cfg.model.name = "vit"
    cfg.model.num_classes = 16
    cfg.model.vit_dim = 256
    cfg.model.vit_depth = 6
    cfg.model.vit_heads = 4
    cfg.model.vit_num_experts = num_experts
    cfg.model.vit_moe_top_k = top_k
    cfg.model.vit_moe_dispatch = dispatch
    cfg.data.image_size = image
    cfg.model.vit_patch_size = patch
    cfg.train.batch_size = bs
    k = 8
    cfg.train.steps_per_loop = k
    cfg.mesh.data = len(jax.devices())
    tr = Trainer(cfg)
    tr.init_state()
    fn = tr.jitted_multi_step(k)
    rng = np.random.RandomState(0)
    batch = shard_stacked_batch({
        "images": rng.randn(k, bs, image, image, 3).astype(np.float32),
        "labels": rng.randint(0, 16, (k, bs)).astype(np.int32),
    }, tr.mesh)
    return tr, fn, batch, k


def ms_per_step(tr, fn, batch, k, loops=5, reps=3):
    state = tr.state
    for _ in range(2):
        state, _ = fn(state, batch)
    jax.block_until_ready(state.params)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(loops):
            state, _ = fn(state, batch)
        jax.block_until_ready(state.params)
        best = min(best, (time.perf_counter() - t0) / (loops * k))
    return best * 1e3


def main():
    out = {"device": jax.devices()[0].device_kind,
           "tokens_per_batch": 32 * (64 // 4) ** 2, "configs": {}}
    for name, (e, tk, disp) in (("dense_mlp", (0, 1, "auto")),
                                ("moe_e8_top1_einsum", (8, 1, "einsum")),
                                ("moe_e8_top1_gather", (8, 1, "gather")),
                                ("moe_e8_top2_gather", (8, 2, "gather"))):
        tr, fn, batch, k = build(e, tk, dispatch=disp)
        ms = ms_per_step(tr, fn, batch, k)
        out["configs"][name] = round(ms, 3)
        print(f"{name:>12}: {ms:7.2f} ms/step", flush=True)
    d = out["configs"]
    out["gather_vs_einsum"] = round(
        d["moe_e8_top1_einsum"] / d["moe_e8_top1_gather"], 2)
    out["moe_top1_vs_dense"] = round(
        d["moe_e8_top1_gather"] / d["dense_mlp"], 2)
    out_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "moe_r3.json")
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
