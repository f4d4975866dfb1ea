"""Batch normalization with explicit replica-group semantics.

The reference trained per-replica BN (each worker normalized with its own
shard's moments — an implicit consequence of graph-per-worker data
parallelism) and attributed its distributed accuracy gap to it (reference
README.md:38,54). Under ``jit`` over a sharded batch the natural semantics
flip: moments are global (XLA all-reduces the mean), i.e. cross-replica BN.

To support BOTH numerics — cross-replica (better accuracy) and per-replica
(reference-faithful comparison) — this module computes moments over
configurable batch *groups*:

  * ``groups=1``  → one global moment set: cross-replica BN. When the batch
    is sharded over the mesh the mean is a cross-device ``all-reduce`` XLA
    lays on ICI.
  * ``groups=G``  → the batch is viewed as G equal groups, each normalized
    with its own moments. With G = number of batch shards and a
    shard-aligned leading dim, each group is exactly one device's shard, so
    XLA needs NO collective and the numerics equal the reference's
    per-replica BN — deterministically, on any mesh size.

Running statistics are always aggregated globally (mean of group means with
the between-group variance correction), matching what a synced-checkpoint
evaluator expects.

Performance: moments and affine coefficients are computed in float32, but the
per-element application is a single fused multiply-add in the COMPUTE dtype —
``y = x * a + b`` with ``a = scale·rsqrt(var+eps)`` and ``b = bias − mean·a``
— so the bandwidth-bound elementwise pass runs at bf16 VPU rate and XLA can
fuse it into the surrounding conv. Momentum 0.997 / eps 1e-5 defaults mirror
reference resnet_model_official.py:37-38.

The BN training tax — ~38% of the ImageNet ResNet-50 step is per-channel
reduction passes over the activations — was attacked four ways in round 3
(docs/perf_imagenet_r3.md has the measured table): a custom_vjp with
hand-scheduled minimal passes (parity — XLA's autodiff already multi-output-
fuses the paired reduces), a variadic ``lax.reduce`` (slower: bad TPU
lowering), streaming Pallas reduction kernels (much slower: per-call
overhead ≫ bandwidth saved at these sizes), and moment subsampling. Only
the last is kept: ``stat_subsample=s`` estimates the batch moments from the
CONTIGUOUS center band of H/s rows (a strided ::s lattice gathers and
measured slower than the full reduce; a band is a zero-copy prefix read and
its gradient a fused pad). It is ~neutral at bs=128 on one v5e — the stat
pass it trims is only ~15% of the step — but scales with batch and spatial
size; default 1 (exact reference numerics). Normalization, gradients and
running averages all use the band moments, so autodiff yields the exact
gradient of the band-stat forward.
"""
from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax


def _band(x: jax.Array, sub: int) -> jax.Array:
    """Center band of H/sub rows (axis 1) — the contiguous stat sample."""
    if sub <= 1 or x.ndim != 4:
        return x
    h = x.shape[1]
    bh = max(1, h // sub)
    lo = (h - bh) // 2
    return lax.slice_in_dim(x, lo, lo + bh, axis=1)


class GroupedBatchNorm(nn.Module):
    momentum: float = 0.997
    epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16
    groups: int = 1
    use_scale: bool = True
    use_bias: bool = True
    # >1: estimate batch moments from the center band of H/s rows (see
    # module docstring); 1 = exact moments (default, reference numerics)
    stat_subsample: int = 1

    @nn.compact
    def __call__(self, x: jax.Array, train: bool) -> jax.Array:
        features = x.shape[-1]
        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros((features,), jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones((features,), jnp.float32))
        scale = self.param("scale", nn.initializers.ones, (features,),
                           jnp.float32) if self.use_scale else None
        bias = self.param("bias", nn.initializers.zeros, (features,),
                          jnp.float32) if self.use_bias else None

        one = jnp.ones((features,), jnp.float32)
        zero = jnp.zeros((features,), jnp.float32)
        scale_f = scale if scale is not None else one
        bias_f = bias if bias is not None else zero

        def affine(mean, var):
            """f32 (…,C) moments → bf16 fused y = x·a + b."""
            a = scale_f * jax.lax.rsqrt(var + self.epsilon)
            b = bias_f - mean * a
            return a, b

        if not train:
            a, b = affine(ra_mean.value, ra_var.value)
            return (x * a.astype(x.dtype) + b.astype(x.dtype)).astype(self.dtype)

        g = self.groups
        reduce_axes = tuple(range(x.ndim - 1))  # all but channels
        s = self.stat_subsample
        # moments come from xs (the stat sample); normalization applies to x
        xs = _band(x, s)
        if g > 1:
            bsz = x.shape[0]
            if bsz % g != 0:
                raise ValueError(f"batch {bsz} not divisible by bn groups {g}")
            xg = x.reshape((g, bsz // g) + x.shape[1:])
            xsg = xs.reshape((g, bsz // g) + xs.shape[1:])
            xf = xsg.astype(jnp.float32)
            gaxes = tuple(range(1, xsg.ndim - 1))
            gmean = jnp.mean(xf, axis=gaxes)                       # (g, C)
            gsq = jnp.mean(jnp.square(xf), axis=gaxes)
            gvar = gsq - jnp.square(gmean)
            a, b = affine(gmean, gvar)                             # (g, C)
            bshape = (g,) + (1,) * (xg.ndim - 2) + (features,)
            y = xg * a.reshape(bshape).astype(x.dtype) + \
                b.reshape(bshape).astype(x.dtype)
            y = y.reshape(x.shape)
            # global stats for the running averages: law of total variance
            mean = jnp.mean(gmean, axis=0)
            var = jnp.mean(gvar + jnp.square(gmean), axis=0) - jnp.square(mean)
        else:
            xf = xs.astype(jnp.float32)
            mean = jnp.mean(xf, axis=reduce_axes)
            msq = jnp.mean(jnp.square(xf), axis=reduce_axes)
            var = msq - jnp.square(mean)
            a, b = affine(mean, var)
            y = x * a.astype(x.dtype) + b.astype(x.dtype)

        m = self.momentum
        if not self.is_initializing():
            ra_mean.value = m * ra_mean.value + (1 - m) * mean
            ra_var.value = m * ra_var.value + (1 - m) * var
        return y.astype(self.dtype)


def effective_gn_groups(channels: int, groups: int) -> int:
    """Largest valid group count ≤ ``groups`` for ``channels``: min(G, C)
    when it divides C, else gcd(G, C). Keeps the published G=32 on every
    ImageNet stage (64..2048 channels) and degrades deterministically on
    narrow CIFAR stages (16 → 16 groups)."""
    if groups < 1:
        raise ValueError(f"gn_groups must be >= 1, got {groups}")
    g = min(groups, channels)
    if channels % g:
        g = math.gcd(groups, channels) or 1
    return g


class ChannelGroupNorm(nn.Module):
    """GroupNorm (Wu & He 2018) over channel groups — the BN-free training
    contract (``model.norm='group'``).

    Batch-independent by construction: moments are per (sample, group) over
    (H, W, C/G), so there is NO cross-replica collective, no running
    statistics to checkpoint, and no train/eval numerics split — the
    properties BatchNorm costs this framework (the per-channel stat passes
    are ~38% of the faithful-BN ImageNet step, docs/perf_imagenet_r3.md,
    and the distributed moment semantics are the accuracy bug the reference
    documented, reference README.md:38,54).

    Same fused-application shape as GroupedBatchNorm: f32 moments and
    affine coefficients, one bf16 multiply-add per element (a/b broadcast
    as (N, 1, 1, C)) that XLA fuses into the surrounding conv."""

    groups: int = 32
    epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = True) -> jax.Array:
        del train  # stateless — identical in train and eval
        c = x.shape[-1]
        g = effective_gn_groups(c, self.groups)
        scale = self.param("scale", nn.initializers.ones, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        n = x.shape[0]
        xg = x.reshape((n,) + x.shape[1:-1] + (g, c // g)).astype(jnp.float32)
        axes = tuple(range(1, xg.ndim - 2)) + (xg.ndim - 1,)
        mean = jnp.mean(xg, axis=axes)                        # (N, G)
        var = jnp.mean(jnp.square(xg), axis=axes) - jnp.square(mean)
        rstd = lax.rsqrt(var + self.epsilon)                  # (N, G)
        # per-sample per-channel fused coefficients: broadcast (N,G) over
        # the C/G channels of each group, fold in the learned affine
        a = (scale.reshape(g, c // g)[None] * rstd[..., None]).reshape(n, c)
        b = (bias.reshape(g, c // g)[None]
             - mean[..., None] * scale.reshape(g, c // g)[None]
             * rstd[..., None]).reshape(n, c)
        bshape = (n,) + (1,) * (x.ndim - 2) + (c,)
        y = x * a.reshape(bshape).astype(x.dtype) \
            + b.reshape(bshape).astype(x.dtype)
        return y.astype(self.dtype)
