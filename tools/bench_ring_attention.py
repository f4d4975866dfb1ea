"""Sequence-parallel attention measurements (VERDICT r3 #4).

Two surfaces, one artifact (docs/ring_attention_r4.json):

  * ``--tpu`` (default): the ring's INNER BLOCK on the real chip — the
    blockwise online-softmax recurrence (exactly what each ring step
    executes between ppermutes) timed fwd+bwd against the Pallas flash
    kernel and dense XLA attention, causal bf16, 8k-32k tokens. The r4
    change under test: QK/PV matmuls in bf16 with fp32 accumulation
    (preferred_element_type) instead of the r3 fp32-upcast inner.
  * ``--mesh``: ring_attention_sharded over the virtual 8-device CPU
    seq mesh vs the identical computation single-device — proves the
    sequence-parallel path and measures its collective overhead
    structure (CPU wall-clock; no multi-chip TPU exists here).

    python tools/bench_ring_attention.py --tpu
    python tools/bench_ring_attention.py --mesh   # separate process (CPU)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
OUT = os.path.join(REPO, "docs", "ring_attention_r4.json")


def _merge(update: dict) -> None:
    data = {}
    if os.path.exists(OUT):
        with open(OUT) as f:
            data = json.load(f)
    data.update(update)
    with open(OUT, "w") as f:
        json.dump(data, f, indent=2)
    print(f"wrote {OUT}")


def bench_tpu():
    from distributed_resnet_tensorflow_tpu.utils.compile_cache import (
        configure_compile_cache)
    configure_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import attention_grad_ms
    from distributed_resnet_tensorflow_tpu.ops.attention import (
        attention, blockwise_attention)
    from distributed_resnet_tensorflow_tpu.ops.pallas import flash_attention

    rng = np.random.RandomState(0)
    out = {"device": jax.devices()[0].device_kind, "rows": {}}
    for t, h in ((8192, 4), (16384, 2), (32768, 1)):
        q, k, v = (jnp.asarray(rng.randn(1, t, h, 64).astype(np.float32))
                   .astype(jnp.bfloat16) for _ in range(3))
        row = {}
        row["blockwise_grad_ms"] = round(attention_grad_ms(
            lambda q, k, v: blockwise_attention(q, k, v, causal=True),
            q, k, v, iters=6), 2)
        row["flash_grad_ms"] = round(attention_grad_ms(
            lambda q, k, v: flash_attention(q, k, v, True, False),
            q, k, v, iters=6), 2)
        if t <= 16384:  # dense O(T²) memory collapses beyond
            row["dense_grad_ms"] = round(attention_grad_ms(
                lambda q, k, v: attention(q, k, v, causal=True),
                q, k, v, iters=6), 2)
        row["blockwise_vs_flash"] = round(
            row["blockwise_grad_ms"] / row["flash_grad_ms"], 2)
        out["rows"][f"T{t}"] = row
        print(f"T{t}: {row}", flush=True)
    _merge({"tpu_inner": out})


def bench_mesh():
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from distributed_resnet_tensorflow_tpu.ops.attention import (
        blockwise_attention, ring_attention_sharded)
    from distributed_resnet_tensorflow_tpu.parallel import create_mesh
    from distributed_resnet_tensorflow_tpu.utils.config import MeshConfig

    mesh = create_mesh(MeshConfig(sequence=8))
    rng = np.random.RandomState(0)
    t = 8192
    q, k, v = (jnp.asarray(rng.randn(1, t, 4, 64).astype(np.float32))
               for _ in range(3))

    def ring_loss(q, k, v):
        return (ring_attention_sharded(q, k, v, mesh, causal=True)
                .astype(jnp.float32) ** 2).sum()

    def single_loss(q, k, v):
        return (blockwise_attention(q, k, v, block_size=t // 8, causal=True)
                .astype(jnp.float32) ** 2).sum()

    sh = NamedSharding(mesh, P(None, "seq", None, None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    ring_g = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))
    single_g = jax.jit(jax.grad(single_loss, argnums=(0, 1, 2)))

    # correctness first: sharded ring == single-device recurrence
    gr = ring_g(qs, ks, vs)
    gs_ = single_g(q, k, v)
    max_diff = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(gr, gs_))

    def best_ms(fn, args, reps=3):
        jax.block_until_ready(fn(*args))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, (time.perf_counter() - t0) * 1e3)
        return round(best, 1)

    out = {
        "tokens": t, "seq_devices": 8,
        "grad_max_abs_diff_vs_single": max_diff,
        "ring_grad_ms": best_ms(ring_g, (qs, ks, vs)),
        "single_grad_ms": best_ms(single_g, (q, k, v)),
        "note": "virtual CPU mesh: structure/correctness; per-device "
                "compute is 1/8 but one host core executes all 8",
    }
    print(out, flush=True)
    _merge({"virtual_mesh_ring": out})


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", action="store_true")
    ap.add_argument("--tpu", action="store_true")
    args = ap.parse_args()
    if args.mesh:
        bench_mesh()
    else:
        bench_tpu()
