"""Device-side input augmentation — runs inside the jitted train step.

The reference augmented on the host CPU via TF ops (pad-36 → random 32-crop →
flip → per-image standardize, reference resnet_cifar_main.py:185-199,
cifar_input.py:66-75). At TPU step rates a single host core cannot feed that
pipeline (53k img/s for the CIFAR flagship), so the TPU-native design moves
augmentation into the XLA program: the host only gathers raw uint8 records
(4× smaller transfers, no float work), and the crop/flip/standardize run on
device where they cost noise next to the conv stack. RNG is
``jax.random.fold_in(seed_key, step)`` — deterministic, resume-stable, and
identical across data-parallel replicas' disjoint shards.

Semantics match the host-side numpy pipeline (data/cifar.py) op-for-op; the
random draws differ (jax vs numpy RNG), which changes nothing statistically.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def standardize(images: jax.Array) -> jax.Array:
    """Per-image standardization with TF's adjusted-std semantics:
    (x - mean) / max(std, 1/sqrt(N)) — same formula as the host path
    (data/cifar.py standardize; reference resnet_cifar_main.py:199)."""
    x = images.astype(jnp.float32)
    n = x.shape[1] * x.shape[2] * x.shape[3]
    mean = jnp.mean(x, axis=(1, 2, 3), keepdims=True)
    std = jnp.std(x, axis=(1, 2, 3), keepdims=True)
    adj = jnp.maximum(std, 1.0 / jnp.sqrt(jnp.float32(n)))
    return (x - mean) / adj


def random_crop_flip(images: jax.Array, rng: jax.Array,
                     pad: int = 4) -> jax.Array:
    """Pad H/W by ``pad``, take a per-image random crop back to the original
    size, random horizontal flip — the reference's train augmentation
    (resnet_cifar_main.py:188-198).

    Implementation is TPU-shaped: a per-image-offset crop is a gather, and
    TPU gathers with dynamic offsets serialize badly inside the scanned train
    step (measured 2.2 ms/step for CIFAR bs=128 — more than the whole
    ResNet-50 fwd+bwd). Instead the crop+flip is expressed as two one-hot
    selection matmuls that ride the MXU:

        out[b,i,j,c] = Σ_y Σ_x  R[b,i,y] · padded[b,y,x,c] · C[b,j,x]

    with R/C one-hot in the crop offset (C reversed for flipped images).
    Every output element is exactly one input element (single nonzero per
    row), so bf16 operands are exact for uint8 pixel values; ~0.1 ms/step.
    """
    b, h, w, c = images.shape
    padded = jnp.pad(images.astype(jnp.bfloat16),
                     ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    hp, wp = h + 2 * pad, w + 2 * pad
    ky, kx, kf = jax.random.split(rng, 3)
    ys = jax.random.randint(ky, (b,), 0, 2 * pad + 1)
    xs = jax.random.randint(kx, (b,), 0, 2 * pad + 1)
    flip = jax.random.bernoulli(kf, 0.5, (b,))

    # R[b,i,y] = 1 iff y == ys[b] + i  (row selector)
    iy = jax.lax.broadcasted_iota(jnp.int32, (1, h, hp), 2)
    ii = jax.lax.broadcasted_iota(jnp.int32, (1, h, hp), 1)
    rows = (iy - ii == ys[:, None, None]).astype(jnp.bfloat16)
    # C[b,j,x] = 1 iff x == xs[b] + j, with j reversed for flipped images
    jj = jnp.where(flip[:, None], (w - 1) - jnp.arange(w)[None, :],
                   jnp.arange(w)[None, :])
    ix = jax.lax.broadcasted_iota(jnp.int32, (1, w, wp), 2)
    cols = (ix == (xs[:, None] + jj)[:, :, None]).astype(jnp.bfloat16)

    tmp = jnp.einsum("biy,byxc->bixc", rows, padded,
                     preferred_element_type=jnp.float32)
    return jnp.einsum("bjx,bixc->bijc", cols, tmp,
                      preferred_element_type=jnp.float32)


@partial(jax.jit, static_argnames=("pad",))
def cifar_train_augment(images: jax.Array, rng: jax.Array,
                        pad: int = 4) -> jax.Array:
    """Full train-time pipeline for raw uint8 NHWC batches:
    crop/flip in integer space (like the host path) then standardize."""
    return standardize(random_crop_flip(images, rng, pad))


def vgg_standardize(images: jax.Array, rng: jax.Array = None) -> jax.Array:
    """ImageNet/VGG standardization on device: uint8 → x/255 − RGB means
    (reference vgg_preprocessing.py:37-39,196-227 — constant means, NOT
    per-image moments). The random crop/resize stay on the host (they
    depend on per-image source geometry); moving just this float conversion
    on-device quarters the host→HBM transfer (uint8 vs f32) and removes the
    host's per-pixel float pass — the two costs that dominate a streamed
    224² pipeline after the decode itself. Eval/serve prep; the TRAIN path
    is ``imagenet_train_augment`` (flip + standardize)."""
    del rng  # deterministic; matches the augment_fn(images, rng) contract
    from ..data.preprocessing import RGB_MEANS
    x = images.astype(jnp.float32) / 255.0
    return x - jnp.asarray(RGB_MEANS)


def _as_nhwc(images: jax.Array, channels) -> jax.Array:
    """Undo the lane-dense ``[B, H, W·C]`` view (``channels`` given) for an
    augmentation that computes on NHWC."""
    if channels is None:
        return images
    b, h, wc = images.shape
    return images.reshape(b, h, wc // channels, channels)


def random_flip(rows: jax.Array, rng: jax.Array, channels: int) -> jax.Array:
    """Per-image random horizontal flip of uint8 crops, computed on their
    lane-dense view: ``rows`` is ``[B, H, W·C]`` (the bytes of NHWC, the
    width and channel dimensions merged), the result the same view in
    float32, pixel scale.

    On that view a flip is the constant lane permutation
    ``j·C + c → (W−1−j)·C + c``, applied as a one-hot selection matmul on
    the MXU like ``random_crop_flip``'s: one non-zero per column, bf16
    operands (exact for uint8 values), float32 accumulation — every output
    element IS one input element. The per-image draw then selects between
    the permuted and the plain rows.

    Why not ``images[:, :, ::-1, :]``: on a TPU a ``[B, H, W, 3]`` tensor
    keeps the 3 in the lanes, padded to 128 — 125 of 128 lanes idle, 42×
    the bytes — and the reverse runs along its sublanes. In the staged
    unpack of ``[256,224,224,3]`` crops that form cost 27.0 ms for the
    ``rev`` and 14.8 ms for the standardize behind it, of a 148 ms step
    (TPU v5e, PR 25's trace); see ``imagenet_train_augment`` for the
    numbers after."""
    if rows.dtype != jnp.uint8:
        raise TypeError(
            f"random_flip permutes uint8 pixels exactly in bf16; got "
            f"{rows.dtype}")
    b, _, wc = rows.shape
    flip = jax.random.bernoulli(rng, 0.5, (b,))
    lane = jnp.arange(wc)
    # lane j·C + c of a flipped row reads lane (W−1−j)·C + c
    src = (wc - channels) - (lane // channels) * channels + lane % channels
    perm = (lane[:, None] == src[None, :]).astype(jnp.bfloat16)
    flipped = jax.lax.dot_general(
        rows.astype(jnp.bfloat16), perm, (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return jnp.where(flip[:, None, None], flipped, rows.astype(jnp.float32))


def imagenet_train_augment(images: jax.Array, rng: jax.Array,
                           pad: int = 0, channels=None) -> jax.Array:
    """ImageNet TRAIN augmentation for raw uint8 NHWC crops, on device:
    random horizontal flip (+ optional ``pad``-pixel random-crop jitter)
    then the VGG standardize. The host decode keeps the reference's random
    resize/crop (tied to per-image source geometry) and SKIPS its flip
    when this path is active (data/imagenet.py ``device_flip``), so at
    pad=0 the train distribution is exactly the reference's
    resize → crop → flip → standardize with the flip and the float pass
    moved on device. ``pad`` > 0 (data.augment_pad) adds a CIFAR-style
    pad/crop jitter via the MXU-shaped one-hot matmuls of
    ``random_crop_flip`` — spatial diversity for echoed appearances of
    one decoded crop (data/echo.py). Draws are per appearance: the same
    staged sample augments differently every time it feeds a step, which
    is what keeps data echoing from replaying identical batches.

    ``images`` is NHWC or, with ``channels`` given, the same bytes already
    viewed as ``[B, H, W·C]`` — how the stager's fused unpack slices them
    out of the staged region. At pad=0 everything before the result is
    computed on that lane-dense view: the flip as a lane permutation
    (``random_flip``), then ``/ 255`` and minus ``RGB_MEANS`` tiled to
    ``[W·C]``, the same two float32 operations per element as on NHWC, so
    the same bits; one reshape gives the NHWC result. No ``[…, W, 3]``
    tensor exists before it (tests/test_echo.py holds the lowered program
    to that). In ``rn50_staged`` this took the unpack program from 61.7 ms
    a step to 2.4 ms and the step period from 148.2 to 89.1 ms, 1,723 →
    2,868 examples/s (TPU v5e; PERF.md §5 and §6, PR 26): what is left is
    the slice out of the staged bytes 0.63 ms, the uint8 reshape and its
    move of the batch into the lanes 0.42, the product + select +
    standardize fusion 0.37, and two dense float32 copies into the layout
    the device keeps the NHWC result in (``[H][C][W][B]``, batch in the
    lanes), 0.47 each."""
    from ..data.preprocessing import RGB_MEANS
    if pad > 0:
        # float32, pixel scale
        x = random_crop_flip(_as_nhwc(images, channels), rng, pad)
        return x / 255.0 - jnp.asarray(RGB_MEANS)
    if channels is None:
        b, h, w, c = images.shape
    else:
        (b, h, wc), c = images.shape, channels
        w = wc // c
    if c != len(RGB_MEANS):
        raise ValueError(
            f"imagenet standardize has {len(RGB_MEANS)} channel means; "
            f"got {c} channels")
    x = random_flip(images.reshape(b, h, w * c), rng, c)
    x = x / 255.0 - jnp.asarray(np.tile(RGB_MEANS, w))
    return x.reshape(b, h, w, c)


def device_augment_fn(kind: str, pad: int = 0):
    """Resolve a HASHABLE device-augment spec — ``(leaf, kind, pad)`` is
    what the CoalescedStager's fused unpack (parallel/sharding.py) and the
    static elaborator cache/trace on — into the
    ``fn(images, rng, channels=None)`` callable. ``images`` is NHWC, or
    with ``channels`` given its lane-dense ``[B, H, W·C]`` view (the fused
    unpack passes that; see ``imagenet_train_augment``). One resolution
    point so the fused-unpack path, the step-side path and the analysis
    gate can never disagree about what a spec means."""
    if kind == "imagenet_train":
        return lambda images, rng, channels=None: imagenet_train_augment(
            images, rng, pad, channels)
    if kind == "imagenet_eval":
        fn = vgg_standardize
    elif kind == "cifar_train":
        def fn(images, rng):
            return cifar_train_augment(images, rng, pad or 4)
    else:
        raise ValueError(f"unknown device augment kind {kind!r}")
    return lambda images, rng, channels=None: fn(
        _as_nhwc(images, channels), rng)
