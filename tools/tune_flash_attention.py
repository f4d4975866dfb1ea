"""Autotune the flash-attention Pallas tile sizes on real TPU.

Measures fwd+bwd (grad) wall time over (block_q, block_k) ∈ {128,256,512}²
for T ∈ {1024, 2048, 4096, 8192} × head dim ∈ {64, 128} (bf16, causal), plus
the XLA dense and blockwise baselines at each point — the evidence for
ops/pallas/flash_attention._BLOCK_TABLES and for the dense→flash ``auto``
crossover in models/transformer.py.

    python tools/tune_flash_attention.py [--out docs/flash_tune_r3.json]
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_resnet_tensorflow_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache)

configure_compile_cache()

BLOCKS = (128, 256, 512)
SEQS = (1024, 2048, 4096, 8192)
HEAD_DIMS = (64, 128)


def grad_time(attn_fn, q, k, v, iters=8, reps=3):
    """One shared harness with the bench (bench.attention_grad_ms) so the
    tuner's numbers and the bench's stay methodologically identical."""
    from bench import attention_grad_ms
    return attention_grad_ms(attn_fn, q, k, v, iters, reps)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="docs/flash_tune_r3.json")
    ap.add_argument("--seqs", default=",".join(map(str, SEQS)))
    ap.add_argument("--dims", default=",".join(map(str, HEAD_DIMS)))
    ap.add_argument("--heads_budget", type=int, default=8 * 64 * 4096,
                    help="keep B*H*T*D work roughly constant across points")
    args = ap.parse_args()
    from distributed_resnet_tensorflow_tpu.ops.attention import (
        attention, blockwise_attention)
    from distributed_resnet_tensorflow_tpu.ops.pallas import flash_attention

    results = []
    out = {"device": jax.devices()[0].device_kind, "results": results}
    if os.path.exists(args.out):
        with open(args.out) as f:
            prev = json.load(f)
        done = {(r["t"], r["d"]): r for r in prev.get("results", [])}
        # carry EVERY previously-measured point — a --dims/--seqs subset run
        # must extend the evidence file, not clobber it
        results.extend(prev.get("results", []))
    else:
        done = {}
    for t in map(int, args.seqs.split(",")):
        for d in map(int, args.dims.split(",")):
            if (t, d) in done:
                continue
            h = max(1, args.heads_budget // (t * d))
            rng = np.random.RandomState(0)
            q, k, v = (jnp.asarray(
                rng.randn(1, t, h, d).astype(np.float32) * 0.3)
                .astype(jnp.bfloat16) for _ in range(3))
            point = {"t": t, "d": d, "h": h, "blocks": {}}
            for bq, bk in itertools.product(BLOCKS, BLOCKS):
                ms = grad_time(lambda q, k, v, bq=bq, bk=bk: flash_attention(
                    q, k, v, True, False, bq, bk), q, k, v)
                point["blocks"][f"{bq}x{bk}"] = round(ms, 3)
                print(f"T={t} d={d} h={h} block {bq}x{bk}: {ms:.3f} ms",
                      flush=True)
            best = min(point["blocks"], key=point["blocks"].get)
            point["best"] = best
            point["dense_ms"] = round(grad_time(
                lambda q, k, v: attention(q, k, v, causal=True), q, k, v), 3)
            try:
                point["blockwise_ms"] = round(grad_time(
                    lambda q, k, v: blockwise_attention(q, k, v, causal=True),
                    q, k, v), 3)
            except Exception as e:
                point["blockwise_ms"] = f"error: {e}"[:80]
            point["speedup_vs_dense"] = round(
                point["dense_ms"] / point["blocks"][best], 2)
            print(f"T={t} d={d}: best {best} "
                  f"({point['blocks'][best]} ms) vs dense {point['dense_ms']}"
                  f" ms -> {point['speedup_vs_dense']}x", flush=True)
            results.append(point)
            if os.path.dirname(args.out):
                os.makedirs(os.path.dirname(args.out), exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(out, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
