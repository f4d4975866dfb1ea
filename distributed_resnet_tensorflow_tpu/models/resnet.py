"""Pre-activation ResNet v2 — TPU-native functional re-design.

Capability parity with the reference graph builders
(reference resnet_model_official.py):
  * CIFAR variant: 6n+2 layers, 3 stages of 16/32/64 filters, stem 3x3 conv,
    final BN+ReLU + global average pool + dense head
    (reference resnet_model_official.py:217-278), generalized with a
    ``width_multiplier`` for Wide-ResNet-28-10.
  * ImageNet variant: 7x7/2 stem + 3x3/2 maxpool, 4 stages 64/128/256/512,
    sizes 18/34/50/101/152/200 via a block-count table
    (reference resnet_model_official.py:281-359).
  * Fixed padding for strided convs (reference resnet_model_official.py:53-91).
  * BatchNorm momentum 0.997, eps 1e-5 (reference resnet_model_official.py:37-38).

TPU-first design decisions (NOT in the reference):
  * NHWC only — the layout XLA:TPU prefers; the reference's NCHW/NHWC switch
    (resnet_model_official.py:244-248) existed for cuDNN and is dropped.
  * bfloat16 compute / float32 params & batch stats (MXU-native mixed precision).
  * Cross-replica batch norm: under ``jit`` over a sharded batch the moments are
    global by construction (XLA inserts the all-reduce).
    This fixes the per-replica-BN accuracy gap the reference documented
    (reference README.md:38,54).
  * Optional ``remat`` (jax.checkpoint) on residual stages to trade FLOPs for
    HBM when scaling batch size.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

ModuleDef = Any

# Block-count table for ImageNet sizes (reference resnet_model_official.py:352-359).
IMAGENET_MODEL_PARAMS = {
    18: ("building", (2, 2, 2, 2)),
    34: ("building", (3, 4, 6, 3)),
    50: ("bottleneck", (3, 4, 6, 3)),
    101: ("bottleneck", (3, 4, 23, 3)),
    152: ("bottleneck", (3, 8, 36, 3)),
    200: ("bottleneck", (3, 24, 36, 3)),
}


# Round-4 negative result (docs/perf_imagenet_r4.md): re-expressing the
# stem 3x3/2 max pool as an elementwise max over the 9 shifted strided
# views — to replace the backward's serial select_and_scatter (1.3 ms/step,
# docs/perf_imagenet_r3_ops.json) with fusable masks — measured 12 ms/step
# WORSE (57.5 vs 45.0 ms): the nine [N,114,114,64] mask+pad passes the
# autodiff produces cost ~10x the op they remove. reduce_window stays.


class ConvFixedPadding(nn.Module):
    """Conv with SAME padding for stride 1, explicit fixed padding otherwise
    (reference resnet_model_official.py:80-91).

    The fixed padding is folded into the conv op's own low/high padding
    rather than materialized as a separate ``jnp.pad`` — numerically
    identical (conv with explicit padding == pad + VALID by definition of
    ``lax.conv_general_dilated``) but it removes a standalone ``pad`` HLO
    per strided conv that XLA was executing unfused (measured 0.6 ms/step
    on ImageNet RN50 bs128, docs/perf_imagenet_r3_ops.json)."""

    filters: int
    kernel_size: int
    strides: int = 1
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        if self.strides > 1:
            pad_total = self.kernel_size - 1
            pad = (pad_total // 2, pad_total - pad_total // 2)
            padding = (pad, pad)
        else:
            padding = "SAME"
        return nn.Conv(
            self.filters,
            (self.kernel_size, self.kernel_size),
            strides=(self.strides, self.strides),
            padding=padding,
            use_bias=False,
            kernel_init=nn.initializers.variance_scaling(2.0, "fan_out", "truncated_normal"),
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )(x)


class StemConv(nn.Module):
    """The ImageNet 7x7/2 stem conv, optionally evaluated via
    space-to-depth.

    The plain formulation gives the MXU a contraction of 7·7·3 with only
    3 input channels — a shape XLA tiles poorly. With
    ``space_to_depth=True`` the SAME arithmetic is re-expressed: the input
    is rearranged [N,224,224,3] → [N,115,115,12] (2×2 pixel blocks into
    channels) and the kernel [7,7,3,F] → [4,4,12,F] (zero-padded to 8 taps,
    split even/odd), turning the stem into a 4×4/1 conv whose taps align
    with the block grid. Weights are stored in the canonical [7,7,3,F]
    layout either way, so checkpoints are mode-portable. Equivalence is
    exact in math (same multiply-adds, reassociated) and pinned by
    tests/test_models.py::test_stem_space_to_depth_parity.
    """

    filters: int = 64
    space_to_depth: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        f = self.filters
        w = self.param(
            "kernel",
            nn.initializers.variance_scaling(2.0, "fan_out",
                                             "truncated_normal"),
            (7, 7, 3, f), self.param_dtype)
        dn = ("NHWC", "HWIO", "NHWC")
        if not self.space_to_depth:
            return jax.lax.conv_general_dilated(
                x, w.astype(self.dtype), (2, 2), ((3, 3), (3, 3)),
                dimension_numbers=dn)
        n, h, wd, c = x.shape
        if h % 2 or wd % 2 or c != 3:
            raise ValueError(
                f"space-to-depth stem needs even HxW RGB input, got {x.shape}")
        # kernel: zero tap at the BEGINNING of each spatial dim (k 7→8), so
        # with input padding (4, 2) every tap p = 2·out + a lands at
        # s2d cell (out + a//2, a%2) — a VALID 4×4 conv over the s2d grid
        w8 = jnp.pad(w, ((1, 0), (1, 0), (0, 0), (0, 0)))
        w4 = w8.reshape(4, 2, 4, 2, 3, f).transpose(0, 2, 1, 3, 4, 5) \
               .reshape(4, 4, 12, f)
        xp = jnp.pad(x, ((0, 0), (4, 2), (4, 2), (0, 0)))
        hs, ws = (h + 6) // 2, (wd + 6) // 2
        xs = xp.reshape(n, hs, 2, ws, 2, c).transpose(0, 1, 3, 2, 4, 5) \
               .reshape(n, hs, ws, 4 * c)
        return jax.lax.conv_general_dilated(
            xs, w4.astype(self.dtype), (1, 1), "VALID",
            dimension_numbers=dn)


class BatchNormRelu(nn.Module):
    """Normalization + ReLU, dispatched on ``norm``:

      * "batch"  — BN (momentum 0.997, eps 1e-5 — reference
        resnet_model_official.py:37-48). Stats in float32. ``groups=1`` →
        cross-replica BN (global moments); ``groups=G`` → per-replica/
        reference BN numerics (ops/batch_norm.py).
      * "frozen" — BN applied from the RUNNING statistics even in training
        (the trainable frozen-BN fine-tune contract): scale/bias still
        learn, the batch-moment passes and their cross-replica semantics
        disappear, stats never update. From-scratch this is a learned
        per-channel affine (stats stay at init 0/1); from a checkpoint it
        is classic frozen-BN fine-tuning.
      * "group"  — GroupNorm over ``norm_groups`` channel groups
        (ops/batch_norm.ChannelGroupNorm): batch-independent, stateless,
        no train/eval split — the BN-free training contract.
    """

    momentum: float = 0.997
    epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16
    groups: int = 1
    relu: bool = True
    stat_subsample: int = 1
    norm: str = "batch"
    norm_groups: int = 32

    @nn.compact
    def __call__(self, x: jax.Array, train: bool) -> jax.Array:
        if self.norm == "group":
            from ..ops.batch_norm import ChannelGroupNorm
            x = ChannelGroupNorm(groups=self.norm_groups,
                                 epsilon=self.epsilon,
                                 dtype=self.dtype)(x, train)
        elif self.norm in ("batch", "frozen"):
            from ..ops.batch_norm import GroupedBatchNorm
            x = GroupedBatchNorm(
                momentum=self.momentum,
                epsilon=self.epsilon,
                dtype=self.dtype,
                groups=self.groups,
                stat_subsample=self.stat_subsample,
            )(x, train and self.norm != "frozen")
        else:
            raise ValueError(
                f"model.norm must be batch|frozen|group, got {self.norm!r}")
        if self.relu:
            x = nn.relu(x)
        return x


class BuildingBlock(nn.Module):
    """v2 building block: BN-ReLU preact → 3x3 conv (stride) → BN-ReLU → 3x3
    conv, identity/projection shortcut taken after the preact
    (reference resnet_model_official.py:94-130)."""

    filters: int
    strides: int
    use_projection: bool
    dtype: Any = jnp.bfloat16
    bn_groups: int = 1
    bn_momentum: float = 0.997
    bn_epsilon: float = 1e-5
    bn_stat_subsample: int = 1
    norm: str = "batch"
    norm_groups: int = 32

    @nn.compact
    def __call__(self, x: jax.Array, train: bool) -> jax.Array:
        bn = partial(BatchNormRelu, momentum=self.bn_momentum,
                     epsilon=self.bn_epsilon, dtype=self.dtype,
                     groups=self.bn_groups,
                     stat_subsample=self.bn_stat_subsample,
                     norm=self.norm, norm_groups=self.norm_groups)
        conv = partial(ConvFixedPadding, dtype=self.dtype)
        shortcut = x
        x = bn()(x, train)
        if self.use_projection:
            shortcut = conv(self.filters, 1, self.strides)(x)
        x = conv(self.filters, 3, self.strides)(x)
        x = bn()(x, train)
        x = conv(self.filters, 3, 1)(x)
        return x + shortcut


class BottleneckBlock(nn.Module):
    """v2 bottleneck: preact → 1x1 f → 3x3 f (stride) → 1x1 4f
    (reference resnet_model_official.py:133-175)."""

    filters: int
    strides: int
    use_projection: bool
    dtype: Any = jnp.bfloat16
    bn_groups: int = 1
    bn_momentum: float = 0.997
    bn_epsilon: float = 1e-5
    bn_stat_subsample: int = 1
    norm: str = "batch"
    norm_groups: int = 32

    @nn.compact
    def __call__(self, x: jax.Array, train: bool) -> jax.Array:
        bn = partial(BatchNormRelu, momentum=self.bn_momentum,
                     epsilon=self.bn_epsilon, dtype=self.dtype,
                     groups=self.bn_groups,
                     stat_subsample=self.bn_stat_subsample,
                     norm=self.norm, norm_groups=self.norm_groups)
        conv = partial(ConvFixedPadding, dtype=self.dtype)
        shortcut = x
        x = bn()(x, train)
        if self.use_projection:
            shortcut = conv(4 * self.filters, 1, self.strides)(x)
        x = conv(self.filters, 1, 1)(x)
        x = bn()(x, train)
        x = conv(self.filters, 3, self.strides)(x)
        x = bn()(x, train)
        x = conv(4 * self.filters, 1, 1)(x)
        return x + shortcut


class BlockLayer(nn.Module):
    """One stage: first block projects + strides, the rest are identity
    (reference resnet_model_official.py:178-214)."""

    block_cls: Callable[..., nn.Module]
    filters: int
    num_blocks: int
    strides: int
    dtype: Any = jnp.bfloat16
    bn_groups: int = 1
    remat: bool = False
    bn_momentum: float = 0.997
    bn_epsilon: float = 1e-5
    bn_stat_subsample: int = 1
    norm: str = "batch"
    norm_groups: int = 32

    @nn.compact
    def __call__(self, x: jax.Array, train: bool) -> jax.Array:
        block_cls = self.block_cls
        if self.remat:
            block_cls = nn.remat(block_cls, static_argnums=(2,))
        for i in range(self.num_blocks):
            x = block_cls(
                filters=self.filters,
                strides=self.strides if i == 0 else 1,
                use_projection=(i == 0),
                dtype=self.dtype,
                bn_groups=self.bn_groups,
                bn_momentum=self.bn_momentum,
                bn_epsilon=self.bn_epsilon,
                bn_stat_subsample=self.bn_stat_subsample,
                norm=self.norm, norm_groups=self.norm_groups,
            )(x, train)
        return x


class CifarResNetV2(nn.Module):
    """CIFAR ResNet v2 generator: 6n+2 layers
    (reference resnet_model_official.py:217-278), widened by
    ``width_multiplier`` (Wide-ResNet-28-10 = size 28, width 10)."""

    resnet_size: int = 50
    num_classes: int = 10
    width_multiplier: int = 1
    dtype: Any = jnp.bfloat16
    bn_groups: int = 1
    remat: bool = False
    bn_momentum: float = 0.997
    bn_epsilon: float = 1e-5
    bn_stat_subsample: int = 1
    norm: str = "batch"
    norm_groups: int = 32

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = True) -> jax.Array:
        # classic preact convention 6n+2 (reference resnet_model_official.py:231);
        # Wide-ResNet papers count the same topology as 6n+4 (WRN-28-10 → n=4)
        if (self.resnet_size - 2) % 6 == 0:
            num_blocks = (self.resnet_size - 2) // 6
        elif (self.resnet_size - 4) % 6 == 0:
            num_blocks = (self.resnet_size - 4) // 6
        else:
            raise ValueError(
                f"cifar resnet_size must be 6n+2 or 6n+4, got {self.resnet_size}")
        k = self.width_multiplier
        x = x.astype(self.dtype)
        x = ConvFixedPadding(16, 3, 1, dtype=self.dtype)(x)
        for i, (filters, strides) in enumerate(((16 * k, 1), (32 * k, 2), (64 * k, 2))):
            x = BlockLayer(
                block_cls=BuildingBlock, filters=filters, num_blocks=num_blocks,
                strides=strides, dtype=self.dtype,
                bn_groups=self.bn_groups, remat=self.remat,
                bn_momentum=self.bn_momentum, bn_epsilon=self.bn_epsilon,
                bn_stat_subsample=self.bn_stat_subsample,
                norm=self.norm, norm_groups=self.norm_groups,
            )(x, train)
        x = BatchNormRelu(momentum=self.bn_momentum, epsilon=self.bn_epsilon,
                          dtype=self.dtype,
                          groups=self.bn_groups,
                          stat_subsample=self.bn_stat_subsample,
                          norm=self.norm,
                          norm_groups=self.norm_groups)(x, train)
        x = jnp.mean(x, axis=(1, 2))  # global avg pool (8x8 at 32px input)
        x = x.astype(jnp.float32)
        return nn.Dense(self.num_classes,
                        kernel_init=nn.initializers.variance_scaling(1.0, "fan_in", "truncated_normal"),
                        dtype=jnp.float32)(x)


class ImageNetResNetV2(nn.Module):
    """ImageNet ResNet v2 generator
    (reference resnet_model_official.py:281-359)."""

    resnet_size: int = 50
    num_classes: int = 1001
    dtype: Any = jnp.bfloat16
    bn_groups: int = 1
    remat: bool = False
    bn_momentum: float = 0.997
    bn_epsilon: float = 1e-5
    bn_stat_subsample: int = 1
    norm: str = "batch"
    norm_groups: int = 32
    stem_space_to_depth: bool = False

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = True) -> jax.Array:
        if self.resnet_size not in IMAGENET_MODEL_PARAMS:
            raise ValueError(
                f"imagenet resnet_size must be one of {sorted(IMAGENET_MODEL_PARAMS)}, "
                f"got {self.resnet_size}")
        block_kind, block_counts = IMAGENET_MODEL_PARAMS[self.resnet_size]
        block_cls = BottleneckBlock if block_kind == "bottleneck" else BuildingBlock

        x = x.astype(self.dtype)
        x = StemConv(64, space_to_depth=self.stem_space_to_depth,
                     dtype=self.dtype)(x)
        # reference semantics: tf.layers.max_pooling2d(..., padding='SAME')
        # (resnet_model_official.py:314-316) — SAME maxpool pads with -inf
        # (padding never wins the max) and at 112/2 pads (0,1), NOT the
        # zero-pad (1,1) this model used through round 3; SAME is both the
        # faithful geometry and one fused op cheaper (no standalone pad HLO)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, num_blocks in enumerate(block_counts):
            x = BlockLayer(
                block_cls=block_cls, filters=64 * (2 ** i), num_blocks=num_blocks,
                strides=1 if i == 0 else 2, dtype=self.dtype,
                bn_groups=self.bn_groups,
                remat=self.remat, bn_momentum=self.bn_momentum,
                bn_epsilon=self.bn_epsilon,
                bn_stat_subsample=self.bn_stat_subsample,
                norm=self.norm, norm_groups=self.norm_groups,
            )(x, train)
        x = BatchNormRelu(momentum=self.bn_momentum, epsilon=self.bn_epsilon,
                          dtype=self.dtype,
                          groups=self.bn_groups,
                          stat_subsample=self.bn_stat_subsample,
                          norm=self.norm,
                          norm_groups=self.norm_groups)(x, train)
        x = jnp.mean(x, axis=(1, 2))  # global avg pool (7x7 at 224px input)
        x = x.astype(jnp.float32)
        return nn.Dense(self.num_classes,
                        kernel_init=nn.initializers.variance_scaling(1.0, "fan_in", "truncated_normal"),
                        dtype=jnp.float32)(x)


def create_model(model_cfg, dataset: str,
                 remat: bool = False, bn_groups: int = 1,
                 mesh=None, compute_dtype=None) -> nn.Module:
    """Model factory; replaces the dataset dispatch in reference
    resnet_model.py:69-76 (which hard-coded resnet_size=50 for both).

    ``compute_dtype`` overrides ``model_cfg.compute_dtype`` — the
    mixed-precision policy's hook (parallel/precision.py: the Trainer
    passes the policy dtype; the serving variant builder passes the
    variant dtype). None keeps the legacy per-family contract, including
    the logistic toy's pinned-f32 compute."""
    dtype = jnp.dtype(compute_dtype) if compute_dtype is not None \
        else jnp.dtype(model_cfg.compute_dtype)
    if model_cfg.name == "logistic":
        from .logistic import LogisticNet
        # the toy MLP historically ignored compute_dtype (f32 always);
        # only an explicit policy/variant override changes its compute —
        # the legacy path must stay bit-identical
        return LogisticNet(num_classes=model_cfg.num_classes,
                           hidden_units=model_cfg.hidden_units,
                           dtype=dtype if compute_dtype is not None
                           else jnp.float32)
    from .transformer import FAMILIES, CausalDecoder
    if model_cfg.name in FAMILIES:
        return CausalDecoder(cfg=model_cfg, dtype=dtype,
                             attention_impl=model_cfg.attention_impl,
                             remat=remat, mesh=mesh)
    if model_cfg.name == "vit":
        from .transformer import VisionTransformer
        attn = model_cfg.attention_impl
        seq = mesh.shape.get("seq", 1) if mesh is not None else 1
        if attn == "auto" and seq > 1:
            # a seq axis routes through ring attention (sequence parallel);
            # the remaining flash-vs-dense choice is made at trace time
            # where the true token count is known. transformer._apply_attention
            # applies the SAME rules for direct VisionTransformer users — this
            # early resolution only makes model.attention_impl introspectable
            attn = "ring"
        if attn == "ring" and seq <= 1:
            raise ValueError(
                "attention_impl='ring' requires mesh.sequence > 1")
        return VisionTransformer(
            num_classes=model_cfg.num_classes,
            patch_size=model_cfg.vit_patch_size,
            dim=model_cfg.vit_dim, depth=model_cfg.vit_depth,
            num_heads=model_cfg.vit_heads, dtype=dtype,
            attention_impl=attn, remat=remat, mesh=mesh,
            pipeline_microbatches=model_cfg.vit_pipeline_microbatches,
            pipeline_interleave=model_cfg.vit_pipeline_interleave,
            num_experts=model_cfg.vit_num_experts,
            expert_capacity_factor=model_cfg.vit_expert_capacity_factor,
            moe_top_k=model_cfg.vit_moe_top_k,
            moe_dispatch=model_cfg.vit_moe_dispatch)
    if dataset in ("cifar10", "cifar100", "synthetic"):
        return CifarResNetV2(
            resnet_size=model_cfg.resnet_size,
            num_classes=model_cfg.num_classes,
            width_multiplier=model_cfg.width_multiplier,
            dtype=dtype, bn_groups=bn_groups, remat=remat,
            bn_momentum=model_cfg.bn_momentum, bn_epsilon=model_cfg.bn_epsilon,
            bn_stat_subsample=model_cfg.bn_stat_subsample,
            norm=model_cfg.norm, norm_groups=model_cfg.gn_groups)
    if dataset == "imagenet":
        return ImageNetResNetV2(
            resnet_size=model_cfg.resnet_size,
            num_classes=model_cfg.num_classes,
            dtype=dtype, bn_groups=bn_groups, remat=remat,
            bn_momentum=model_cfg.bn_momentum, bn_epsilon=model_cfg.bn_epsilon,
            bn_stat_subsample=model_cfg.bn_stat_subsample,
            norm=model_cfg.norm, norm_groups=model_cfg.gn_groups,
            stem_space_to_depth=model_cfg.stem_space_to_depth)
    raise ValueError(f"unknown dataset {dataset!r}")


def count_params(params) -> int:
    return sum(p.size for p in jax.tree_util.tree_leaves(params))
