"""Vision Transformer (Dosovitskiy et al., arXiv:2010.11929) as a plain
float32 ``jax.numpy`` function.

Patch embedding as a strided convolution with bias, learned position
embeddings, pre-LayerNorm encoder blocks (multi-head self-attention without
q/k/v or output bias, then a GELU MLP with biases), a final LayerNorm and a
linear head. Departures from the paper, because the program under test
makes them and the configuration file states them: no class token — the
head reads the mean over the patch tokens; GELU in its tanh form;
LayerNorm epsilon 1e-6.

No kernels, no mixed precision, no sharding; nothing imported from the
program. ``quant`` is the control's hook, applied to both operands of every
matrix product (identity for the reference).
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

#: rows are independent (no batch statistics), so the comparison may take
#: the batch in blocks of this many rows per device and add the gradients
ROW_BLOCK = 16
BLOCK_LEAVES = ("ln1.scale", "ln1.bias", "qkv.kernel", "proj.kernel",
                "ln2.scale", "ln2.bias", "mlp1.kernel", "mlp1.bias",
                "mlp2.kernel", "mlp2.bias")


def _shapes(model: dict) -> Dict[str, tuple]:
    d, h, p = model["vit_dim"], model["vit_heads"], model["vit_patch_size"]
    t = (model["image_size"] // p) ** 2
    hd, m = d // h, model["mlp_ratio"] * d
    out = {"patch_embed.kernel": (p, p, 3, d), "patch_embed.bias": (d,),
           "pos_embed": (1, t, d)}
    per_block = {"ln1.scale": (d,), "ln1.bias": (d,),
                 "qkv.kernel": (d, 3, h, hd), "proj.kernel": (h, hd, d),
                 "ln2.scale": (d,), "ln2.bias": (d,),
                 "mlp1.kernel": (d, m), "mlp1.bias": (m,),
                 "mlp2.kernel": (m, d), "mlp2.bias": (d,)}
    for i in range(model["vit_depth"]):
        for leaf, shape in per_block.items():
            out[f"block{i}.{leaf}"] = shape
    out.update({"final_ln.scale": (d,), "final_ln.bias": (d,),
                "head.kernel": (d, model["num_classes"]),
                "head.bias": (model["num_classes"],)})
    return out


def init_params(key, model: dict) -> Dict[str, jnp.ndarray]:
    """Fan-in scaled normal kernels, the two kernels that close a residual
    branch (attention output and second MLP layer) smaller by
    1/sqrt(2 * depth) as GPT-2 starts them, so that the start of training is
    well-conditioned and rounding is not amplified; position embeddings at
    0.02; a small seeded spread on scales and biases (around 1 and 0) so
    that every leaf has a gradient of its own size from the first step."""
    params = {}
    closing = 1.0 / np.sqrt(2.0 * model["vit_depth"])
    for i, (name, shape) in enumerate(_shapes(model).items()):
        k = jax.random.fold_in(key, i)
        last = name.rsplit(".", 1)[-1]
        if name == "pos_embed":
            params[name] = 0.02 * jax.random.normal(k, shape, jnp.float32)
        elif last == "kernel":
            fan_in = int(np.prod(shape[:-1]))       # patch embed, proj, dense layers
            if name.endswith("qkv.kernel"):
                fan_in = shape[0]                   # (d, 3, heads, head_dim)
            std = 1.0 / np.sqrt(fan_in)
            if name.endswith(("proj.kernel", "mlp2.kernel")):
                std *= closing
            params[name] = std * jax.random.normal(k, shape, jnp.float32)
        elif last == "scale":
            params[name] = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        else:
            params[name] = 0.02 * jax.random.normal(k, shape, jnp.float32)
    return params


def program_paths(model: dict) -> Dict[str, str]:
    """Where the program under test keeps each leaf (its flax module path).
    Names only: no value crosses from the program to the reference."""
    out = {"patch_embed.kernel": "patch_embed/kernel",
           "patch_embed.bias": "patch_embed/bias", "pos_embed": "pos_embed",
           "final_ln.scale": "LayerNorm_0/scale", "final_ln.bias": "LayerNorm_0/bias",
           "head.kernel": "head/kernel", "head.bias": "head/bias"}
    theirs = {"ln1": "LayerNorm_0", "ln2": "LayerNorm_1",
              "qkv": "MultiHeadAttention_0/qkv", "proj": "MultiHeadAttention_0/proj",
              "mlp1": "Dense_0", "mlp2": "Dense_1"}
    for i in range(model["vit_depth"]):
        for leaf in BLOCK_LEAVES:
            mod, kind = leaf.split(".")
            out[f"block{i}.{leaf}"] = f"EncoderBlock_{i}/{theirs[mod]}/{kind}"
    return out


def prepare(images: np.ndarray, flip: np.ndarray) -> jnp.ndarray:
    """The synthetic float32 batches reach the network as they are."""
    del flip
    return jnp.asarray(images, jnp.float32)


def _layer_norm(x, scale, bias, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True) - jnp.square(mean)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p: Dict[str, jnp.ndarray], eps: float, quant: Callable):
    hd = p["qkv.kernel"].shape[-1]
    h = _layer_norm(x, p["ln1.scale"], p["ln1.bias"], eps)
    qkv = jnp.einsum("btd,dchk->btchk", quant(h), quant(p["qkv.kernel"]))
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = jnp.einsum("bqhk,bthk->bhqt", quant(q), quant(k)) / np.sqrt(hd)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqt,bthk->bqhk", quant(a), quant(v))
    x = x + jnp.einsum("bqhk,hkd->bqd", quant(o), quant(p["proj.kernel"]))
    h = _layer_norm(x, p["ln2.scale"], p["ln2.bias"], eps)
    h = _gelu_tanh(quant(h) @ quant(p["mlp1.kernel"]) + p["mlp1.bias"])
    return x + quant(h) @ quant(p["mlp2.kernel"]) + p["mlp2.bias"]


def logits(params, x, model: dict, quant: Callable = lambda a: a):
    eps, p = model["layer_norm_epsilon"], model["vit_patch_size"]
    x = jax.lax.conv_general_dilated(
        quant(x), quant(params["patch_embed.kernel"]), (p, p), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + params["patch_embed.bias"]
    x = x.reshape(x.shape[0], -1, x.shape[-1]) + params["pos_embed"]
    # the blocks are alike: stack their leaves and scan, one block at a
    # time recomputed in the backward pass (compiles once, fits easily)
    stacked = {leaf: jnp.stack([params[f"block{i}.{leaf}"]
                                for i in range(model["vit_depth"])])
               for leaf in BLOCK_LEAVES}
    body = jax.checkpoint(lambda c, bp: (_block(c, bp, eps, quant), None))
    x, _ = jax.lax.scan(body, x, stacked)
    x = _layer_norm(x, params["final_ln.scale"], params["final_ln.bias"], eps)
    x = jnp.mean(x, axis=1)
    return quant(x) @ quant(params["head.kernel"]) + params["head.bias"]
