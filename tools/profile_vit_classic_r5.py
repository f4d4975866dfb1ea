"""Round-5 classic-ViT MFU — can the transformer family hit the >=0.5 bar?

docs/perf_vit_r5.md measured the long-context preset (4096 tokens, dim 512)
at <=0.37 MFU bound and attributed the plateau to the dim-512 op mix, with
the width lever (dim 1024) reaching <=0.43. The open question it left: does
a CLASSIC short-sequence ViT — 224² at patch 16 → 196 tokens, where
attention is a rounding error and the step is almost entirely dense
(B·T, D)×(D, 4D) matmuls — fill the MXU the way the WRN-28-10 width lever
did for convs (0.63, docs/perf_cifar_r5.md)?

Dense attention only: every FLOP is visible to XLA's cost analysis, so
these MFU numbers are fully counted (no Pallas custom-call bound games).

Grid: ViT-B/16-shaped (dim 768, depth 12, heads 12) and ViT-L/16-shaped
(dim 1024, depth 24, heads 16), batch 32/64/128, remat off (196 tokens
needs no activation rematerialization).

Writes docs/perf_vit_classic_r5.json.
"""
from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402

from distributed_resnet_tensorflow_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache)

configure_compile_cache()

OUT = os.path.join(REPO, "docs", "perf_vit_classic_r5.json")


def measure(dim: int, depth: int, heads: int, bs: int, k: int = 8,
            loops: int = 5):
    """One grid point through bench._mfu_row — the shared single-chip MFU
    harness (host-pull fence, best-of-reps, XLA-counted FLOPs), so timing
    and accounting fixes land once (same reuse as tools/profile_norm_r5)."""
    import bench
    from distributed_resnet_tensorflow_tpu.utils.config import get_preset

    cfg = get_preset("vit_large_224")
    cfg.model.vit_dim = dim
    cfg.model.vit_depth = depth
    cfg.model.vit_heads = heads
    row = bench._mfu_row(cfg, bs, 224, 1000, k, loops, host_fence=True)
    row.update(dim=dim, depth=depth, heads=heads,
               tokens_per_image=(224 // 16) ** 2,
               counted_step_flops=row.pop("step_flops"))
    return row


def main():
    out = {"device": jax.devices()[0].device_kind,
           "workload": "classic ViT 224^2 / patch 16 = 196 tokens, dense "
                       "attention (all FLOPs XLA-counted), bf16, no remat"}
    rows = []
    for dim, depth, heads, label in ((768, 12, 12, "ViT-B/16"),
                                     (1024, 24, 16, "ViT-L/16")):
        for bs in (32, 64, 128):
            try:
                r = measure(dim, depth, heads, bs)
                r["shape"] = label
            except Exception as e:
                r = {"shape": label, "dim": dim, "batch_size": bs,
                     "error": f"{type(e).__name__}: {e}"[:200]}
            print(json.dumps(r), flush=True)
            rows.append(r)
    out["rows"] = rows
    with open(OUT, "w") as f:
        json.dump(out, f, indent=1)
    print("wrote", OUT)


if __name__ == "__main__":
    main()


