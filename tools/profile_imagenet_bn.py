"""Ablation profile of the ImageNet ResNet-50 train step on one TPU chip.

Quantifies where the step time goes — specifically the BatchNorm batch-stat
reduction tax identified in round 2 (MFU plateau at ~35%) — by timing the
SAME fused k-step train dispatch under controlled variants:

  * baseline      — exact BN moments (ops/batch_norm.py, stat_subsample=1)
  * subsample s   — moments from the ::s spatial lattice (s ∈ {2, 4})
  * frozen-stats  — normalize with running stats (NO moment reduction at
                    all; not a training mode — the upper bound on what
                    killing the stat tax could ever buy)
  * fwd-only      — loss forward without grad/update (fwd/bwd split)

Writes docs/perf_imagenet_r3.json and prints a markdown table; the committed
docs/perf_imagenet_r3.md is generated from this output. Run on real TPU:

    python tools/profile_imagenet_bn.py [--bs 128] [--k 8]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

# persistent compile cache: each variant is one compile of a large RN50 scan
# graph; re-runs (and re-invocations per variant) hit the cache
from distributed_resnet_tensorflow_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache)

configure_compile_cache()


def build_step(bs: int, k: int, stat_subsample: int = 1):
    from distributed_resnet_tensorflow_tpu.parallel.sharding import (
        shard_batch, shard_stacked_batch)
    from distributed_resnet_tensorflow_tpu.train import Trainer
    from distributed_resnet_tensorflow_tpu.utils.config import get_preset

    cfg = get_preset("imagenet_resnet50")
    cfg.train.batch_size = bs
    cfg.train.steps_per_loop = k
    cfg.model.bn_stat_subsample = stat_subsample
    cfg.mesh.data = len(jax.devices())
    trainer = Trainer(cfg)
    trainer.init_state()
    multi_fn = trainer.jitted_multi_step(k)
    rng = np.random.RandomState(0)
    batch = shard_stacked_batch({
        "images": rng.randn(k, bs, 224, 224, 3).astype(np.float32),
        "labels": rng.randint(0, 1001, (k, bs)).astype(np.int32),
    }, trainer.mesh)
    one = shard_batch({"images": np.asarray(batch["images"])[0],
                       "labels": np.asarray(batch["labels"])[0]}, trainer.mesh)
    return trainer, multi_fn, batch, one


def time_multi(multi_fn, state, batch, k: int, loops: int = 5, reps: int = 3):
    for _ in range(2):
        state, _ = multi_fn(state, batch)
    jax.block_until_ready(state.params)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(loops):
            # state threads through (its input buffer is donated each call)
            state, _ = multi_fn(state, batch)
        jax.block_until_ready(state.params)
        best = min(best, time.perf_counter() - t0)
    return best / (loops * k)  # sec per optimizer step


def frozen_stats_patch():
    """Context manager: GroupedBatchNorm normalizes with running stats even
    in train mode — removes every batch-moment reduction from the graph."""
    import contextlib
    from distributed_resnet_tensorflow_tpu.ops import batch_norm as bn_mod

    @contextlib.contextmanager
    def patch():
        orig = bn_mod.GroupedBatchNorm.__call__

        def frozen(self, x, train):
            return orig(self, x, False)
        bn_mod.GroupedBatchNorm.__call__ = frozen
        try:
            yield
        finally:
            bn_mod.GroupedBatchNorm.__call__ = orig
    return patch()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bs", type=int, default=128)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--out", default="docs/perf_imagenet_r3.json")
    ap.add_argument("--variant", default="all",
                    help="all | baseline | subsample2 | subsample4 | "
                         "frozen_stats | fwd_only")
    args = ap.parse_args()
    from distributed_resnet_tensorflow_tpu.utils import profiling

    bs, k = args.bs, args.k
    out = {"batch_size": bs, "steps_per_loop": k,
           "device": jax.devices()[0].device_kind,
           "peak_tflops": profiling.detect_peak_tflops(), "variants": {}}
    if os.path.exists(args.out):  # merge: one variant per invocation works
        with open(args.out) as f:
            prev = json.load(f)
        if prev.get("batch_size") == bs:
            out["variants"].update(prev.get("variants", {}))

    def want(name):
        return args.variant in ("all", name)

    def record(name, sec_per_step, step_flops):
        img_s = bs / sec_per_step
        mfu = profiling.mfu(1.0 / sec_per_step, step_flops) \
            if step_flops else None
        out["variants"][name] = {
            "ms_per_step": round(sec_per_step * 1e3, 3),
            "images_per_sec": round(img_s, 1),
            "step_flops": step_flops,
            "mfu": round(mfu, 4) if mfu else None,
        }
        print(f"{name:>14}: {sec_per_step*1e3:7.2f} ms/step  "
              f"{img_s:7.0f} img/s  MFU={mfu if mfu else float('nan'):.3f}")

    # MFU convention: model FLOPs = the exact-moment graph's FLOPs, so
    # variants are compared on useful work, not on their own (smaller)
    # op counts
    flops_exact = out["variants"].get("baseline", {}).get("step_flops")
    for s in (1, 2, 4):
        name = "baseline" if s == 1 else f"subsample{s}"
        if not want(name):
            continue
        trainer, multi_fn, batch, one = build_step(bs, k, stat_subsample=s)
        sec = time_multi(multi_fn, trainer.state, batch, k)
        if s == 1:
            flops_exact = profiling.flops_per_step(
                trainer.jitted_train_step(), trainer.state, one)
        record(name, sec, flops_exact)

    # frozen running-stats upper bound
    if want("frozen_stats"):
        with frozen_stats_patch():
            trainer, multi_fn, batch, one = build_step(bs, k, stat_subsample=1)
            sec = time_multi(multi_fn, trainer.state, batch, k)
            record("frozen_stats", sec, flops_exact)

    # forward-only (loss value, no grad) — fwd/bwd split
    if not want("fwd_only"):
        return finish(out, args)
    trainer, _multi, batch, one = build_step(bs, k, stat_subsample=1)
    state = trainer.state

    def fwd_loss(state, b):
        variables = {"params": state.params, "batch_stats": state.batch_stats}
        logits, _ = state.apply_fn(variables, b["images"], train=True,
                                   mutable=["batch_stats"])
        oh = jax.nn.one_hot(b["labels"], logits.shape[-1], dtype=jnp.float32)
        import optax
        return optax.softmax_cross_entropy(
            logits.astype(jnp.float32), oh).mean()

    fwd = jax.jit(fwd_loss)

    def fwd_multi(state, batches):
        def body(c, b):
            return c + fwd_loss(state, b), ()
        return jax.lax.scan(body, 0.0, batches)[0]
    fwd_multi_j = jax.jit(fwd_multi)
    fwd_multi_j(state, batch).block_until_ready()
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(5):
            r = fwd_multi_j(state, batch)
        r.block_until_ready()
        best = min(best, (time.perf_counter() - t0) / (5 * k))
    record("fwd_only", best, None)
    del fwd
    finish(out, args)


def finish(out, args):
    v = out["variants"]
    if "baseline" in v and "frozen_stats" in v:
        base = v["baseline"]["ms_per_step"]
        froz = v["frozen_stats"]["ms_per_step"]
        out["bn_stat_tax_fraction"] = round((base - froz) / base, 4)
        print(f"\nBN stat tax: {out['bn_stat_tax_fraction']:.1%} of the step")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
