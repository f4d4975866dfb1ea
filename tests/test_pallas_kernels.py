"""Pallas kernel tests (interpret mode on CPU; the same kernels compile for
TPU where chip_smoke.py exercises them)."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_resnet_tensorflow_tpu.ops.pallas import (
    flash_attention, softmax_xent)
from distributed_resnet_tensorflow_tpu.ops.attention import attention


def test_softmax_xent_matches_optax():
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(37, 10).astype(np.float32))  # odd B, C
    labels = jnp.asarray(rng.randint(0, 10, 37))
    got = softmax_xent(logits, labels, True)
    want = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_softmax_xent_imagenet_classes():
    """1001 classes (non-128-multiple) — wrapper pads lanes."""
    rng = np.random.RandomState(1)
    logits = jnp.asarray(rng.randn(8, 1001).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 1001, 8))
    got = softmax_xent(logits, labels, True)
    want = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_softmax_xent_grad_matches_optax():
    rng = np.random.RandomState(2)
    logits = jnp.asarray(rng.randn(16, 12).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 12, 16))

    g1 = jax.grad(lambda l: softmax_xent(l, labels, True).mean())(logits)
    g2 = jax.grad(lambda l: optax.softmax_cross_entropy_with_integer_labels(
        l, labels).mean())(logits)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-5, atol=1e-6)


def test_flash_attention_matches_dense():
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(2, 64, 2, 16).astype(np.float32))
               for _ in range(3))
    got = flash_attention(q, k, v, False, True)
    want = attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_causal_matches_dense():
    rng = np.random.RandomState(4)
    q, k, v = (jnp.asarray(rng.randn(1, 64, 2, 8).astype(np.float32))
               for _ in range(3))
    got = flash_attention(q, k, v, True, True)
    want = attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_unaligned_seq():
    """T=100 (not a block multiple) exercises the padded/masked path."""
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(1, 100, 1, 8).astype(np.float32))
               for _ in range(3))
    got = flash_attention(q, k, v, False, True)
    want = attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_grad_matches_dense():
    rng = np.random.RandomState(6)
    q, k, v = (jnp.asarray(rng.randn(1, 32, 2, 8).astype(np.float32))
               for _ in range(3))

    g1 = jax.grad(lambda q: flash_attention(q, k, v, False, True).sum())(q)
    g2 = jax.grad(lambda q: attention(q, k, v).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-4)


def test_flash_attention_fused_bwd_all_grads_match_dense():
    """The fused Pallas backward (dq + dk/dv kernels) against dense-attention
    autodiff, for all three inputs at once."""
    rng = np.random.RandomState(9)
    q, k, v = (jnp.asarray(rng.randn(2, 64, 2, 16).astype(np.float32))
               for _ in range(3))
    w = jnp.asarray(rng.randn(2, 64, 2, 16).astype(np.float32))  # cotangent mix

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, False, True) * w).sum()

    def loss_dense(q, k, v):
        return (attention(q, k, v) * w).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_attention_fused_bwd_causal_padded():
    """Causal + unaligned T (valid_len mask + padded q rows) through the
    fused backward."""
    rng = np.random.RandomState(10)
    q, k, v = (jnp.asarray(rng.randn(1, 100, 2, 8).astype(np.float32))
               for _ in range(3))

    g_flash = jax.grad(
        lambda q, k, v: flash_attention(q, k, v, True, True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(
        lambda q, k, v: attention(q, k, v, causal=True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_attention_bwd_multiblock():
    """T=300 spans multiple q AND k blocks: accumulation across the
    sequential grid dimension in both backward kernels."""
    rng = np.random.RandomState(11)
    q, k, v = (jnp.asarray(rng.randn(1, 300, 1, 8).astype(np.float32))
               for _ in range(3))
    g_flash = jax.grad(
        lambda q, k, v: flash_attention(q, k, v, False, True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(
        lambda q, k, v: attention(q, k, v).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_flash_attention_padded_masked_path():
    """t=300 > block 256 and not a multiple: exercises the valid_len mask."""
    rng = np.random.RandomState(7)
    q, k, v = (jnp.asarray(rng.randn(1, 300, 1, 8).astype(np.float32))
               for _ in range(3))
    got = flash_attention(q, k, v, False, True)
    want = attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_padded_causal():
    rng = np.random.RandomState(8)
    q, k, v = (jnp.asarray(rng.randn(1, 300, 1, 8).astype(np.float32))
               for _ in range(3))
    got = flash_attention(q, k, v, True, True)
    want = attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
