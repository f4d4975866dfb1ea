"""Optimizer factory.

Parity with the reference's optimizer selection — plain SGD or momentum-0.9
(reference resnet_model.py:96-99) — plus Adam (used by the toy model,
reference logist_model.py:60) and LARS for the large-batch bs=32k config
(BASELINE.json config 5; not in the reference, which collapsed at scale —
reference README.md:51-52).

Weight decay is applied in the LOSS like the reference (resnet_model.py:78-86),
not decoupled — except for LARS, which takes decay inside the optimizer per
the LARS paper formulation, and AdamW, which is the decoupled-decay
formulation by definition (the transformer-family presets use it: loss-side
L2 under Adam's per-parameter scaling is neither the reference's semantics
nor AdamW's). The decayed set differs by default: kernels-only (ndim>1,
excluding BN γ/β and biases), with ``optimizer.decay_all_params``
restoring the reference's all-trainables L2 for parity replays — see
``loss_weight_decay``.

There is no SyncReplicasOptimizer / DistributedOptimizer wrapper class: under
``jit`` over a sharded batch, the gradient all-reduce is induced by sharding
propagation (XLA emits it on ICI), so the base optimizer IS the distributed
optimizer.
"""
from __future__ import annotations

from typing import Callable

import optax


def create_optimizer(opt_cfg, schedule: Callable) -> optax.GradientTransformation:
    name = opt_cfg.name
    chain = []
    if opt_cfg.grad_clip_norm and opt_cfg.grad_clip_norm > 0:
        chain.append(optax.clip_by_global_norm(opt_cfg.grad_clip_norm))

    if name == "sgd":
        chain.append(optax.sgd(schedule))
    elif name == "momentum":
        chain.append(optax.sgd(schedule, momentum=opt_cfg.momentum))
    elif name == "adam":
        chain.append(optax.adam(schedule))
    elif name == "adamw":
        # decoupled decay (mask matches LARS: kernels only, no norm/bias);
        # the train loop skips the loss-side L2 for this optimizer
        chain.append(optax.adamw(
            schedule, weight_decay=opt_cfg.weight_decay,
            mask=_non_bn_mask))
    elif name == "lars":
        # optax.lars handles per-layer trust ratios; weight decay is part of
        # the LARS update (masked away from BN/bias by weight_decay_mask).
        chain.append(optax.lars(
            schedule,
            weight_decay=opt_cfg.weight_decay,
            weight_decay_mask=_non_bn_mask,
            trust_ratio_mask=_non_bn_mask,
            trust_coefficient=opt_cfg.lars_trust_coefficient,
            eps=opt_cfg.lars_eps,
            momentum=opt_cfg.momentum))
    elif name == "lamb":
        # LAMB (arXiv:1904.00962): Adam moments + LARS-style per-layer
        # trust ratio, decoupled decay — the large-batch recipe for the
        # bs>=4k presets (arXiv:1811.05233's warmup pairs with it). The
        # same non-BN/bias mask as LARS/AdamW: normalization scales and
        # biases get neither decay nor trust-ratio scaling. Doubles the
        # moment state (m AND v) — which is why the lamb presets turn on
        # optimizer.zero1 (the moments shard across the data axis).
        chain.append(optax.lamb(
            schedule,
            weight_decay=opt_cfg.weight_decay,
            mask=_non_bn_mask))
    else:
        raise ValueError(f"unknown optimizer {name!r}")
    return optax.chain(*chain) if len(chain) > 1 else chain[0]


def decoupled_decay(name: str) -> bool:
    """True for optimizers that take weight decay INSIDE the update (LARS,
    LAMB, AdamW) — the train loop must then skip the loss-side L2, and
    ``decay_all_params`` (a loss-path switch) is rejected. The single
    predicate behind both decisions (train/loop.py)."""
    return name in ("lars", "lamb", "adamw")


def _non_bn_mask(params):
    """True for params that should get weight decay / trust-ratio scaling:
    exclude BatchNorm scale/bias, all 1-D params (biases, norm scales, a
    router's rule-moved bias), position embeddings (`pos_embed`, (1, T, D) —
    ndim>1 but not a matmul kernel; ViT recipes conventionally exempt it
    from decay) and a token model's `embedding` table (a lookup, not a
    product: decaying it shrinks rare ids towards each other)."""
    import jax

    def keep(path, leaf):
        names = [str(p) for p in path]
        if any("BatchNorm" in n for n in names):
            return False
        # expert-stacked MoE biases are 2-D; exclude biases (and the ViT
        # pos_embed) by name too
        if names and any(kind in names[-1] for kind in
                         ("bias", "pos_embed", "embedding")):
            return False
        return leaf.ndim > 1

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(
        treedef, [keep(path, leaf) for path, leaf in flat])


def loss_weight_decay(params, rate: float, all_params: bool = False):
    """L2 decay term added to the loss: 0.5*rate*Σ‖w‖².

    Default (``all_params=False``) decays only conv/dense kernels (ndim>1),
    excluding BN γ/β and biases — the modern choice, and this repo's default.
    NOTE this deliberately DIFFERS from the reference, which summed
    ``tf.nn.l2_loss(v)`` over ALL trainable variables including BN scale/bias
    (reference resnet_model.py:85-86). ``all_params=True``
    (config ``optimizer.decay_all_params``) restores the reference-faithful
    behavior for parity replays."""
    import jax
    import jax.numpy as jnp

    if rate == 0.0:
        return 0.0

    def kernel_like(path, leaf):
        # 2-D+ non-bias leaves; "bias" checked by name because
        # expert-stacked MoE biases are 2-D (models/moe.py). pos_embed is
        # exempt like in _non_bn_mask so the loss-side and decoupled decay
        # paths define the SAME default decayed set (kernels only)
        name = str(path[-1])
        return leaf.ndim > 1 and "bias" not in name \
            and "pos_embed" not in name

    leaves = [leaf for path, leaf in
              jax.tree_util.tree_flatten_with_path(params)[0]
              if all_params or kernel_like(path, leaf)]
    return 0.5 * rate * sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                            for l in leaves)
