"""Evidence run for the imagenet_resnet50_lars32k preset on one chip.

Runs the large-batch config truncated — REAL global batch 32,768 at 224²
via gradient accumulation (256 microbatches of 128 inside one jitted scan),
LARS with the preset's lr=29 + warmup + cosine — long enough to show the
warmup/trust-ratio machinery producing a stable loss descent where plain
momentum at lr 29 would explode. Data is a learnable synthetic pool
(class-coded mean color, the make_synth_imagenet content model) shipped as
uint8 with the VGG standardize on device, so the full global batch fits:
uint8 32k × 224² ≈ 4.6 GB HBM vs 19.7 GB if prepped to f32 up front (which
is why train/loop.py preps per microbatch).

    python tools/run_lars_evidence.py [--steps 60] [--out results/lars32k_evidence.json]
"""
from __future__ import annotations

import argparse
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


def make_pool(n_images: int, num_classes: int, size: int,
              seed: int) -> tuple:
    """Learnable uint8 pool: class-coded mean color + noise (the
    tools/make_synth_imagenet signal, generated directly as arrays)."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from make_synth_imagenet import class_color
    rng = np.random.RandomState(seed)
    labels = rng.randint(1, num_classes + 1, size=(n_images,)).astype(np.int32)
    images = np.empty((n_images, size, size, 3), np.uint8)
    for i, lab in enumerate(labels):
        base = 118.0 + 26.0 * class_color(int(lab) - 1, num_classes)
        img = base + rng.normal(0, 30.0, (size, size, 3))
        images[i] = np.clip(img, 0, 255).astype(np.uint8)
    return images, labels


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--pool", type=int, default=1024)
    ap.add_argument("--warmup", type=int, default=15)
    ap.add_argument("--out", default="results/lars32k_evidence.json")
    args = ap.parse_args()

    from distributed_resnet_tensorflow_tpu.train import Trainer
    from distributed_resnet_tensorflow_tpu.utils.config import get_preset

    cfg = get_preset("imagenet_resnet50_lars32k")
    gbs = cfg.train.batch_size                      # 32768
    accum = gbs // 128
    cfg.train.grad_accum_steps = accum
    cfg.data.device_augment = "on"                  # uint8 in, VGG std on device
    cfg.train.train_steps = args.steps
    # traverse warmup AND the full-lr cosine regime inside the truncated run
    cfg.optimizer.warmup_steps = args.warmup
    cfg.optimizer.total_steps = args.steps
    cfg.mesh.data = len(jax.devices())

    print(f"gbs={gbs} accum={accum} lr_peak={cfg.optimizer.learning_rate} "
          f"warmup={args.warmup} steps={args.steps}", flush=True)

    if gbs % args.pool:
        raise SystemExit(f"--pool {args.pool} must divide the global batch "
                         f"{gbs} (the tiled batch would silently shrink)")
    pool_imgs, pool_labels = make_pool(args.pool, 16, cfg.data.image_size,
                                       seed=0)
    reps = gbs // args.pool

    trainer = Trainer(cfg)
    trainer.init_state()
    step_fn = trainer.jitted_train_step()

    # ship only the pool (~150 MB) and tile to the 4.6 GB global batch ON
    # device — a slow host-to-device link would take minutes to push the
    # full batch.
    # The step does not donate its batch argument, so one device batch
    # serves every step.
    import jax.numpy as jnp
    pool_dev = trainer._put_batch({"images": pool_imgs,
                                   "labels": pool_labels})
    tile = jax.jit(lambda b: {
        "images": jnp.tile(b["images"], (reps, 1, 1, 1)),
        "labels": jnp.tile(b["labels"], (reps,))})
    dev_batch = tile(pool_dev)
    jax.block_until_ready(dev_batch["labels"])

    rows = []
    state = trainer.state
    t0 = time.time()
    for step in range(args.steps):
        state, m = step_fn(state, dev_batch)
        row = {"step": step + 1,
               "loss": float(m["loss"]),
               "cross_entropy": float(m["cross_entropy"]),
               "precision": float(m["precision"]),
               "learning_rate": float(m["learning_rate"]),
               "grad_norm": float(m["grad_norm"])}
        rows.append(row)
        print(f"step {row['step']:>3}  loss {row['loss']:.4f}  ce "
              f"{row['cross_entropy']:.4f}  prec {row['precision']:.4f}  "
              f"lr {row['learning_rate']:.3f}  |g| {row['grad_norm']:.2f}",
              flush=True)
    wall = time.time() - t0

    ces = [r["cross_entropy"] for r in rows]
    out = {
        "config": "imagenet_resnet50_lars32k (truncated)",
        "global_batch": gbs, "grad_accum_steps": accum,
        "peak_lr": cfg.optimizer.learning_rate,
        "warmup_steps": args.warmup, "steps": args.steps,
        "wall_secs": round(wall, 1),
        "images_per_sec": round(gbs * args.steps / wall, 1),
        "ce_first": round(ces[0], 4), "ce_last": round(ces[-1], 4),
        "ce_min": round(min(ces), 4),
        "finite": all(np.isfinite(r["loss"]) for r in rows),
        "rows": rows,
    }
    if os.path.dirname(args.out):
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"\nce {ces[0]:.3f} -> {ces[-1]:.3f} over {args.steps} steps of "
          f"gbs {gbs}; wrote {args.out}")


if __name__ == "__main__":
    main()
