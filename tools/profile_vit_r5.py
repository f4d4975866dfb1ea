"""Round-5 long-context ViT MFU — measuring the transformer half of the
BASELINE.md renegotiation instead of asserting it.

The renegotiated target says the >=0.55 MFU bar applies to MXU-filling
models; WRN-28-10 is measured (0.63, docs/perf_cifar_r5.md) but the
flash-attention ViT family was not. This measures the shipped
``vit_long_context`` preset (256² images, patch 4 → 4096 tokens, dim 512,
depth 8) on one chip:

  * attention_impl=dense — every FLOP visible to XLA's cost analysis, so
    the MFU number is fully accounted;
  * attention_impl=flash — the Pallas kernels are custom calls whose FLOPs
    XLA does NOT count, so the row reports wall-clock images/s plus an
    MFU bound built from the dense program's counted FLOPs (the flash
    program does the same mathematical work minus the materialized
    softmax; using the dense count OVERSTATES flash FLOPs slightly, so
    the reported flash MFU is a mild UPPER bound and the dense-count MFU
    with flash wall-clock a fair comparison).

Writes docs/perf_vit_r5.json.
"""
from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from distributed_resnet_tensorflow_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache)

configure_compile_cache()

OUT = os.path.join(REPO, "docs", "perf_vit_r5.json")


def measure(attn: str, bs: int, k: int = 4, loops: int = 5, reps: int = 5,
            remat=None, **overrides):
    from distributed_resnet_tensorflow_tpu.parallel.sharding import (
        shard_batch, shard_stacked_batch)
    from distributed_resnet_tensorflow_tpu.train import Trainer
    from distributed_resnet_tensorflow_tpu.utils import profiling
    from distributed_resnet_tensorflow_tpu.utils.config import get_preset

    cfg = get_preset("vit_long_context")
    cfg.model.attention_impl = attn
    cfg.train.batch_size = bs
    cfg.train.steps_per_loop = k
    if remat is not None:
        cfg.train.remat = remat
    for dotted, v in overrides.items():
        cfg.override(dotted.replace("__", "."), v)
    cfg.mesh.data = len(jax.devices())
    trainer = Trainer(cfg)
    trainer.init_state()
    multi_fn = trainer.jitted_multi_step(k)
    rng = np.random.RandomState(0)
    batch = shard_stacked_batch({
        "images": rng.randn(k, bs, 256, 256, 3).astype(np.float32),
        "labels": rng.randint(0, 10, (k, bs)).astype(np.int32),
    }, trainer.mesh)
    state = trainer.state

    def fence(st):
        # host pull: on the earlier machine block_until_ready could return
        # before compute finished (r4/r5 measurement note; a dense-4096
        # row "measured" 1.8k steps/s = 14 PFLOPs without this)
        return float(jax.numpy.sum(
            jax.tree_util.tree_leaves(st.params)[0].astype(jax.numpy.float32)))

    for _ in range(2):
        state, _m = multi_fn(state, batch)
    fence(state)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(loops):
            state, _m = multi_fn(state, batch)
        fence(state)
        best = min(best, time.perf_counter() - t0)
    sps = loops * k / best
    one = shard_batch({"images": np.asarray(batch["images"])[0],
                       "labels": np.asarray(batch["labels"])[0]},
                      trainer.mesh)
    step_flops = profiling.flops_per_step(
        trainer.jitted_train_step(), state, one)
    util = profiling.mfu(sps, step_flops) if step_flops else None
    return {"attention_impl": attn, "batch_size": bs,
            "tokens_per_image": (256 // 4) ** 2,
            "steps_per_sec": round(sps, 3),
            "images_per_sec": round(sps * bs, 2),
            "counted_step_flops": step_flops,
            "mfu_from_counted_flops": round(util, 4) if util else None}


def main():
    out = {"device": jax.devices()[0].device_kind,
           "workload": "vit_long_context preset: 256^2/patch4 = 4096 "
                       "tokens, dim 512, depth 8, remat, bf16"}
    rows = []
    for attn, bs, remat in (("dense", 4, None), ("flash", 8, None),
                            ("flash", 8, False)):
        try:
            r = measure(attn, bs, remat=remat)
            r["remat"] = remat if remat is not None else True
        except Exception as e:
            r = {"attention_impl": attn, "batch_size": bs, "remat": remat,
                 "error": f"{type(e).__name__}: {e}"[:200]}
        print(json.dumps(r), flush=True)
        rows.append(r)
    # flash MFU bound: same math as dense minus the materialized softmax,
    # so the dense program's per-image FLOP count is a (slight) over-count
    # for the flash program → flash MFU from it is a fair upper-ish bound
    dense = next((r for r in rows if r.get("attention_impl") == "dense"
                  and "error" not in r), None)
    if dense:
        per_img = dense["counted_step_flops"] / dense["batch_size"]
        for r in rows:
            if r.get("attention_impl") == "flash" and "error" not in r:
                flops = per_img * r["batch_size"]
                from distributed_resnet_tensorflow_tpu.utils import profiling
                r["mfu_using_dense_flop_count"] = round(
                    profiling.mfu(r["steps_per_sec"], flops), 4)
    out["rows"] = rows
    with open(OUT, "w") as f:
        json.dump(out, f, indent=1)
    print("wrote", OUT)


if __name__ == "__main__":
    main()
