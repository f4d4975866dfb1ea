"""Pre-activation ResNet (He et al., arXiv:1603.05027), ImageNet bottleneck
form, as a plain float32 ``jax.numpy`` function.

Follows the paper and the TF official ``resnet_model.py`` the original
project wrapped: 7x7/2 stem, 3x3/2 max pool (SAME), four stages of
bottleneck blocks (3, 4, 6, 3 for the 50-layer network) with widths
64/128/256/512 (x4 out), batch normalisation and ReLU BEFORE each
convolution, the projection shortcut taken after the first pre-activation,
stride on the 3x3 convolution, "fixed padding" for strided convolutions, a
final BN-ReLU, global average pool and one dense layer. Batch statistics
are those of the whole batch (training mode); nothing here keeps running
averages because they do not enter a training step.

No kernels, no mixed precision, no sharding; nothing imported from the
program. ``quant`` is the control's hook (identity for the reference): it
is applied to both operands of every convolution and matrix product and to
every tensor the program under test keeps in its compute type, which is the
output of every convolution, of every normalisation and of every residual
sum. The program rounds at each of those places, so the control has to.
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

BLOCKS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
#: VGG preprocessing's channel means on the 0..1 scale (123.68, 116.78,
#: 103.94 over 255), as the TF slim ``vgg_preprocessing.py`` subtracts them
RGB_MEANS = np.asarray([123.68, 116.78, 103.94], np.float32) / 255.0
#: whole batch at once: batch normalisation couples the rows
ROW_BLOCK = None


def _shapes(model: dict) -> Dict[str, tuple]:
    """Every trainable leaf with its shape, in network order."""
    out = {"stem.kernel": (7, 7, 3, 64)}
    cin = 64
    for s, n in enumerate(BLOCKS[model["resnet_size"]]):
        f = 64 * 2 ** s
        for b in range(n):
            p = f"stage{s}.block{b}"
            out[f"{p}.bn0.scale"] = out[f"{p}.bn0.bias"] = (cin,)
            if b == 0:
                out[f"{p}.shortcut.kernel"] = (1, 1, cin, 4 * f)
            out[f"{p}.conv1.kernel"] = (1, 1, cin, f)
            out[f"{p}.bn1.scale"] = out[f"{p}.bn1.bias"] = (f,)
            out[f"{p}.conv2.kernel"] = (3, 3, f, f)
            out[f"{p}.bn2.scale"] = out[f"{p}.bn2.bias"] = (f,)
            out[f"{p}.conv3.kernel"] = (1, 1, f, 4 * f)
            cin = 4 * f
    out["final_bn.scale"] = out["final_bn.bias"] = (cin,)
    out["dense.kernel"] = (cin, model["num_classes"])
    out["dense.bias"] = (model["num_classes"],)
    return out


#: the last convolution of every residual branch starts this much smaller
#: than He's rule gives (the zero-gamma / Fixup practice, here a quarter and
#: not zero so that every leaf has a gradient). At full scale a 50-layer
#: network of random weights under batch statistics amplifies a relative
#: 1e-7 to 3e-3 in the gradients (measured: float32 program against float32
#: reference), and bfloat16 rounding to an error of a third of the gradient:
#: no comparison could tell one precision from the next. Weights do not
#: change what a step costs.
RESIDUAL_SCALE = 0.25


def init_params(key, model: dict) -> Dict[str, jnp.ndarray]:
    """He initialisation (fan-out, as the TF official model) for kernels,
    with the residual branches' last kernels scaled by RESIDUAL_SCALE. The
    normalisation scales and the biases get a small seeded spread around 1
    and 0 so that no two channels start alike and every leaf has a gradient
    of its own size."""
    params = {}
    for i, (name, shape) in enumerate(_shapes(model).items()):
        k = jax.random.fold_in(key, i)
        if name.endswith("kernel") and len(shape) == 4:
            std = np.sqrt(2.0 / (shape[0] * shape[1] * shape[3]))
            if name.endswith("conv3.kernel"):
                std *= RESIDUAL_SCALE
            params[name] = std * jax.random.normal(k, shape, jnp.float32)
        elif name == "dense.kernel":
            params[name] = jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[0])
        elif name.endswith("scale"):
            params[name] = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        else:
            params[name] = 0.1 * jax.random.normal(k, shape, jnp.float32)
    return params


def program_paths(model: dict) -> Dict[str, str]:
    """Where the program under test keeps each leaf (its flax module path).
    Names only: no value crosses from the program to the reference."""
    out = {"stem.kernel": "StemConv_0/kernel"}
    for s, n in enumerate(BLOCKS[model["resnet_size"]]):
        for b in range(n):
            p, q = f"stage{s}.block{b}", f"BlockLayer_{s}/BottleneckBlock_{b}"
            convs = (["shortcut"] if b == 0 else []) + ["conv1", "conv2", "conv3"]
            for i, c in enumerate(convs):
                out[f"{p}.{c}.kernel"] = f"{q}/ConvFixedPadding_{i}/Conv_0/kernel"
            for i in range(3):
                for leaf in ("scale", "bias"):
                    out[f"{p}.bn{i}.{leaf}"] = \
                        f"{q}/BatchNormRelu_{i}/GroupedBatchNorm_0/{leaf}"
    for leaf in ("scale", "bias"):
        out[f"final_bn.{leaf}"] = f"BatchNormRelu_0/GroupedBatchNorm_0/{leaf}"
    out.update({"dense.kernel": "Dense_0/kernel", "dense.bias": "Dense_0/bias"})
    return out


def prepare(images: np.ndarray, flip: np.ndarray) -> jnp.ndarray:
    """uint8 crops -> what the network sees: random horizontal flip (the
    draws are given), then x/255 minus the channel means. float32 batches
    pass through unchanged."""
    if images.dtype != np.uint8:
        return jnp.asarray(images, jnp.float32)
    x = jnp.asarray(images)
    x = jnp.where(jnp.asarray(flip)[:, None, None, None], x[:, :, ::-1, :], x)
    return x.astype(jnp.float32) / 255.0 - jnp.asarray(RGB_MEANS)


def _conv(x, w, stride: int, quant: Callable):
    k = w.shape[0]
    if stride > 1:  # "fixed padding": independent of the input size
        total = k - 1
        pad = ((total // 2, total - total // 2),) * 2
    else:
        pad = "SAME"
    return quant(jax.lax.conv_general_dilated(
        quant(x), quant(w), (stride, stride), pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC")))


def _bn_relu(x, scale, bias, eps: float, quant: Callable):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
    y = (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias
    return quant(jnp.maximum(y, 0.0))


def _block(x, p: Dict[str, jnp.ndarray], stride: int, eps: float, quant):
    shortcut = x
    x = _bn_relu(x, p["bn0.scale"], p["bn0.bias"], eps, quant)
    if "shortcut.kernel" in p:
        shortcut = _conv(x, p["shortcut.kernel"], stride, quant)
    x = _conv(x, p["conv1.kernel"], 1, quant)
    x = _bn_relu(x, p["bn1.scale"], p["bn1.bias"], eps, quant)
    x = _conv(x, p["conv2.kernel"], stride, quant)
    x = _bn_relu(x, p["bn2.scale"], p["bn2.bias"], eps, quant)
    return quant(_conv(x, p["conv3.kernel"], 1, quant) + shortcut)


def logits(params, x, model: dict, quant: Callable = lambda a: a):
    eps = model["bn_epsilon"]
    x = quant(jax.lax.conv_general_dilated(
        quant(x), quant(params["stem.kernel"]), (2, 2), ((3, 3), (3, 3)),
        dimension_numbers=("NHWC", "HWIO", "NHWC")))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    # one block at a time is recomputed in the backward pass, so that a
    # float32 batch of 256 at 224x224 fits on one chip; the blocks of a stage
    # after its first are alike, so their leaves are stacked and scanned
    # (the compiler then sees each kind of block once)
    block = jax.checkpoint(_block, static_argnums=(2, 3, 4))
    for s, n in enumerate(BLOCKS[model["resnet_size"]]):
        def leaves(b):
            pre = f"stage{s}.block{b}."
            return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        x = block(x, leaves(0), 2 if s > 0 else 1, eps, quant)
        rest = [leaves(b) for b in range(1, n)]
        stacked = {k: jnp.stack([r[k] for r in rest]) for k in rest[0]}
        x, _ = jax.lax.scan(lambda c, p: (block(c, p, 1, eps, quant), None), x, stacked)
    x = _bn_relu(x, params["final_bn.scale"], params["final_bn.bias"], eps, quant)
    x = jnp.mean(x, axis=(1, 2))
    return quant(x) @ quant(params["dense.kernel"]) + params["dense.bias"]
