"""Round-4 ImageNet RN50 step-time experiments (VERDICT r3 items 1+2).

Measures optimizer-step time / img/s / MFU for a grid of variants on the
real chip, attacking the two levers the round-3 trace localized
(docs/perf_imagenet_r3_ops.json): the scan-carry copy tax (~2.5 ms/step of
tiny async copies double-buffering the TrainState through the
steps_per_loop while loop) and conv efficiency (~75% of the MXU floor).

Variants are selected by name on the CLI so a partial grid can run inside
any time budget:

    python tools/profile_mfu_r4.py baseline unroll bs32 bs64 ...

Writes/merges docs/perf_imagenet_r4.json.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from distributed_resnet_tensorflow_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache)

configure_compile_cache()
OUT = os.path.join(REPO, "docs", "perf_imagenet_r4.json")


def measure(bs: int, k: int = 8, unroll: int = 1, reps: int = 5,
            loops: int = 5, **cfg_overrides):
    """One grid point: fused k-step dispatch, best-of-reps wall clock."""
    from distributed_resnet_tensorflow_tpu.parallel.sharding import (
        shard_batch, shard_stacked_batch)
    from distributed_resnet_tensorflow_tpu.train import Trainer
    from distributed_resnet_tensorflow_tpu.utils import profiling
    from distributed_resnet_tensorflow_tpu.utils.config import get_preset

    cfg = get_preset("imagenet_resnet50")
    cfg.data.dataset = "imagenet"
    cfg.train.batch_size = bs
    cfg.train.steps_per_loop = k
    cfg.train.scan_unroll = unroll
    cfg.mesh.data = len(jax.devices())
    for dotted, v in cfg_overrides.items():
        cfg.override(dotted, v)
    trainer = Trainer(cfg)
    trainer.init_state()
    multi_fn = trainer.jitted_multi_step(k)
    rng = np.random.RandomState(0)
    batch = shard_stacked_batch({
        "images": rng.randn(k, bs, 224, 224, 3).astype(np.float32),
        "labels": rng.randint(0, 1001, (k, bs)).astype(np.int32),
    }, trainer.mesh)
    state = trainer.state
    t_c = time.perf_counter()
    for _ in range(2):
        state, _m = multi_fn(state, batch)
    jax.block_until_ready(state.params)
    compile_s = time.perf_counter() - t_c
    # the jitted step donates the state arg, so never rewind to an already-
    # consumed state — carry it forward through every rep like training does
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(loops):
            state, _m = multi_fn(state, batch)
        jax.block_until_ready(state.params)
        best = min(best, time.perf_counter() - t0)
    steps_per_sec = loops * k / best

    single = trainer.jitted_train_step()
    one = shard_batch({"images": np.asarray(batch["images"])[0],
                       "labels": np.asarray(batch["labels"])[0]},
                      trainer.mesh)
    step_flops = profiling.flops_per_step(single, state, one)
    util = profiling.mfu(steps_per_sec, step_flops) if step_flops else None
    return {
        "bs": bs, "k": k, "unroll": unroll,
        "ms_per_step": round(1000.0 / steps_per_sec, 2),
        "images_per_sec": round(steps_per_sec * bs, 1),
        "mfu": round(util, 4) if util else None,
        "step_flops": step_flops,
        "compile_plus_warmup_s": round(compile_s, 1),
        **({"overrides": cfg_overrides} if cfg_overrides else {}),
    }


# NOTE on historical labels: rows in docs/perf_imagenet_r4.json were
# measured as the code evolved during round 4 (docs/perf_imagenet_r4.md
# records which code state each row reflects). On CURRENT code the defaults
# already include the kept levers (s2d stem, SAME maxpool), so "baseline"
# measures the shipping configuration; "no_s2d" reproduces the non-s2d
# floor. The "maxpool"/"s2d"* labels in the JSON are historical snapshots.
VARIANTS = {
    "baseline": lambda: measure(128, 8, 1),
    "no_s2d": lambda: measure(128, 8, 1,
                              **{"model.stem_space_to_depth": False}),
    # scan-unroll family — REFUTED (measured a wash; kept for reproduction)
    "unroll": lambda: measure(128, 8, 8),
    "unroll2": lambda: measure(128, 8, 2),
    "unroll4": lambda: measure(128, 8, 4),
    "k4_unroll": lambda: measure(128, 4, 4, loops=10),
    "k2_unroll": lambda: measure(128, 2, 2, loops=20),
    # dispatch-overhead control: k=1 (no scan at all, donation in place)
    "k1": lambda: measure(128, 1, 1, loops=40),
    # the per-chip batch regime rows (unroll stays 1 — measured a wash)
    "bs16": lambda: measure(16, 8, 1, loops=30),
    "bs32": lambda: measure(32, 8, 1, loops=20),
    "bs64": lambda: measure(64, 8, 1, loops=10),
    "bs256": lambda: measure(256, 8, 1, loops=3),
}


def main():
    names = sys.argv[1:] or ["baseline", "unroll"]
    results = {}
    if os.path.exists(OUT):
        with open(OUT) as f:
            results = json.load(f)
    for name in names:
        if name not in VARIANTS:
            print(f"unknown variant {name!r}; have {sorted(VARIANTS)}")
            continue
        t0 = time.time()
        try:
            r = VARIANTS[name]()
        except Exception as e:
            r = {"error": f"{type(e).__name__}: {e}"[:300]}
        r["wall_s"] = round(time.time() - t0, 1)
        results[name] = r
        print(json.dumps({name: r}))
        with open(OUT, "w") as f:
            json.dump(results, f, indent=2)


if __name__ == "__main__":
    main()
