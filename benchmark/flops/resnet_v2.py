"""FLOPs a ResNet v2 (ImageNet bottleneck form) needs for one example, from
the configuration's shapes alone: every convolution and the dense layer,
multiply-accumulates counted once in the forward pass. A training step
needs three passes' worth (forward, and in the backward pass the gradient
to the input and to the weights), at two FLOPs a multiply-accumulate.
Normalisation, ReLU, pooling and the loss are left out (under one percent),
so a utilization built on this reads a little low, never high."""
from __future__ import annotations

BLOCKS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


def forward_macs(model: dict) -> int:
    side = model["image_size"] // 2          # 7x7/2 stem
    macs = side * side * 7 * 7 * 3 * 64
    side //= 2                               # 3x3/2 max pool
    cin = 64
    for s, n in enumerate(BLOCKS[model["resnet_size"]]):
        f = 64 * 2 ** s
        for b in range(n):
            stride = 2 if (b == 0 and s > 0) else 1
            out = side // stride
            if b == 0:
                macs += out * out * cin * 4 * f          # projection shortcut
            macs += side * side * cin * f                # 1x1
            macs += out * out * 9 * f * f                # 3x3, strided
            macs += out * out * f * 4 * f                # 1x1
            cin, side = 4 * f, out
    return macs + cin * model["num_classes"]


def train_flops_per_example(model: dict) -> int:
    return 3 * 2 * forward_macs(model)
