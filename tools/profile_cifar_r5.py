"""Round-5 CIFAR flagship anatomy (VERDICT r4 weak #2 / next #5).

The CIFAR-10 ResNet-50 headline (the reference's flagship workload,
reference README.md:22-33) has run every round at ~0.17 MFU with no per-op
account of where the non-MXU time goes at 32² — this script gives it the
same treatment ImageNet got in rounds 3-4:

  * bs sweep 128/512/2048 (is the flagship recipe's gbs=128 dispatch- or
    compute-bound?),
  * k (steps_per_loop) sweep at bs=128 (dispatch amortization over a
    high-latency host-to-device link),
  * norm sweep (what share of the 32² step is BN stat work),
  * per-op xplane trace at bs=128 (category breakdown, MXU share).

Writes docs/perf_cifar_r5.json. Reuses bench.py's harness conventions
(same augment-in-step path as the headline row) and profile_trace.op_table.

    python tools/profile_cifar_r5.py [sweep] [kscan] [norm] [trace]
"""
from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from distributed_resnet_tensorflow_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache)

configure_compile_cache()

OUT = os.path.join(REPO, "docs", "perf_cifar_r5.json")


def build_step(bs: int, k: int, norm: str = "batch"):
    from distributed_resnet_tensorflow_tpu.parallel.sharding import (
        shard_batch, shard_stacked_batch)
    from distributed_resnet_tensorflow_tpu.train import Trainer
    from distributed_resnet_tensorflow_tpu.utils.config import get_preset

    cfg = get_preset("cifar10_resnet50")
    # same step as bench_cifar: dataset cifar10 → device-side augmentation
    # runs inside the jitted step (ops/augment.py)
    cfg.data.data_dir = "/tmp/drt_bench_cifar"
    cfg.train.batch_size = bs
    cfg.train.steps_per_loop = k
    cfg.model.norm = norm
    if os.environ.get("DRT_WIDTH"):
        # channel-width lever: same 32² topology, width× channels — the
        # MXU-lane-filling hypothesis test (16/32/64 channels use at most
        # half the 128-wide systolic array; width 10 → 160/320/640 fills it)
        cfg.model.resnet_size = 28
        cfg.model.width_multiplier = int(os.environ["DRT_WIDTH"])
    cfg.mesh.data = len(jax.devices())
    trainer = Trainer(cfg)
    trainer.init_state()
    multi_fn = trainer.jitted_multi_step(k)
    rng = np.random.RandomState(0)
    batch = shard_stacked_batch({
        "images": rng.randn(k, bs, 32, 32, 3).astype(np.float32),
        "labels": rng.randint(0, 10, (k, bs)).astype(np.int32),
    }, trainer.mesh)
    one = shard_batch({"images": np.asarray(batch["images"])[0],
                       "labels": np.asarray(batch["labels"])[0]},
                      trainer.mesh)
    return trainer, multi_fn, batch, one


def measure(bs: int, k: int = 20, loops: int = 10, reps: int = 5,
            norm: str = "batch"):
    from distributed_resnet_tensorflow_tpu.utils import profiling
    trainer, multi_fn, batch, one = build_step(bs, k, norm)
    state = trainer.state
    t_c = time.perf_counter()
    for _ in range(2):
        state, _ = multi_fn(state, batch)
    jax.block_until_ready(state.params)
    compile_s = time.perf_counter() - t_c
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(loops):
            state, _ = multi_fn(state, batch)
        jax.block_until_ready(state.params)
        best = min(best, time.perf_counter() - t0)
    sps = loops * k / best
    step_flops = profiling.flops_per_step(
        trainer.jitted_train_step(), state, one)
    mfu = profiling.mfu(sps, step_flops) if step_flops else None
    row = {"batch_size": bs, "k": k, "norm": norm,
           "steps_per_sec": round(sps, 2),
           "images_per_sec": round(sps * bs, 1),
           "ms_per_step": round(1000.0 / sps, 3),
           "mfu": round(mfu, 4) if mfu else None,
           "step_flops": step_flops,
           "compile_s": round(compile_s, 1)}
    print(json.dumps(row), flush=True)
    return row


def trace(bs: int, k: int, top: int = 20):
    from profile_trace import op_table
    logdir = f"/tmp/drt_cifar_trace_bs{bs}"
    trainer, multi_fn, batch, _one = build_step(bs, k)
    state = trainer.state
    for _ in range(2):
        state, _ = multi_fn(state, batch)
    jax.block_until_ready(state.params)
    with jax.profiler.trace(logdir):
        for _ in range(2):
            state, _ = multi_fn(state, batch)
        jax.block_until_ready(state.params)
    fams, _insts = op_table(logdir, top)
    steps = 2 * k
    cats = {}
    for row in fams:
        cats[row["category"]] = cats.get(row["category"], 0.0) \
            + row["self_us"]
    total = sum(cats.values())
    return {
        "per_step_us_by_category": {
            c: round(us / steps, 1) for c, us in
            sorted(cats.items(), key=lambda kv: -kv[1])},
        "category_share": {
            c: round(us / total, 3) for c, us in
            sorted(cats.items(), key=lambda kv: -kv[1])},
        "top_op_families_per_step_us": [
            {"op": r["op"], "category": r["category"],
             "us": round(r["self_us"] / steps, 1), "n": r["n"] // steps}
            for r in fams[:top]],
    }


def main(argv):
    want = set(argv) or {"sweep", "kscan", "norm", "trace"}
    out = {}
    if os.path.exists(OUT):
        out = json.load(open(OUT))
    out["device"] = jax.devices()[0].device_kind
    if "sweep" in want:
        out["bs_sweep"] = [measure(bs) for bs in (128, 512, 2048)]
    if "kscan" in want:
        out["k_scan_bs128"] = [
            measure(128, k=k, loops=max(1, 200 // k)) for k in (1, 5, 20, 60)]
    if "norm" in want:
        out["norm_bs128"] = [measure(128, norm=n)
                             for n in ("frozen", "group")]
    if "trace" in want:
        out["trace_bs128_k20"] = trace(128, 20)
    with open(OUT, "w") as f:
        json.dump(out, f, indent=1)
    print("wrote", OUT)


if __name__ == "__main__":
    main(sys.argv[1:])
