"""FLOPs a Vision Transformer needs for one example, from the
configuration's shapes alone: patch embedding, per block the q/k/v and
output projections, the two attention products and the two MLP products,
and the head; multiply-accumulates counted once in the forward pass. A
training step needs three passes' worth at two FLOPs a multiply-accumulate.
LayerNorm, softmax, GELU and the loss are left out (under one percent), and
nothing recomputed is counted."""
from __future__ import annotations


def forward_macs(model: dict) -> int:
    d, p = model["vit_dim"], model["vit_patch_size"]
    t = (model["image_size"] // p) ** 2
    hidden = model["mlp_ratio"] * d
    block = t * d * 3 * d + 2 * t * t * d + t * d * d + 2 * t * d * hidden
    return t * p * p * 3 * d + model["vit_depth"] * block + d * model["num_classes"]


def train_flops_per_example(model: dict) -> int:
    return 3 * 2 * forward_macs(model)
