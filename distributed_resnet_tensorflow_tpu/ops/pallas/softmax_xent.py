"""Fused softmax cross-entropy — Pallas TPU kernel with custom VJP.

The reference computed loss as softmax_cross_entropy_with_logits (a cuDNN/TF
fused op, reference resnet_model.py:78-80). The XLA default materializes
softmax probabilities in HBM between loss and grad; this kernel fuses
logsumexp + NLL in one VMEM pass per batch tile, and the backward kernel
fuses (softmax(logits) - onehot) * g without re-reading probabilities.

Shapes: logits (B, C) float32/bfloat16, labels (B,) int32 → per-example loss
(B,) float32. C is padded to a 128 multiple inside the wrapper (TPU lane
width); padded columns get -inf logits so they carry zero probability.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM
_NEG_INF = -1e30
_TILE_B = 128


def _fwd_kernel(logits_ref, labels_ref, loss_ref):
    logits = logits_ref[:].astype(jnp.float32)          # (TB, C)
    labels = labels_ref[:]                              # (TB, 1) int32
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1, keepdims=True)) + m
    tb, c = logits.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (tb, c), 1)
    picked = jnp.sum(jnp.where(cols == labels, logits, 0.0), axis=-1,
                     keepdims=True)
    loss_ref[:] = (lse - picked)                        # (TB, 1)


def _bwd_kernel(logits_ref, labels_ref, g_ref, grad_ref):
    logits = logits_ref[:].astype(jnp.float32)
    labels = labels_ref[:]
    g = g_ref[:]                                        # (TB, 1)
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    tb, c = logits.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (tb, c), 1)
    onehot = (cols == labels).astype(jnp.float32)
    grad_ref[:] = ((p - onehot) * g).astype(grad_ref.dtype)


def _pad(logits: jax.Array, labels: jax.Array) -> Tuple[jax.Array, jax.Array, int, int]:
    b, c = logits.shape
    cpad = (-c) % 128
    bpad = (-b) % _TILE_B
    if cpad:
        logits = jnp.pad(logits, ((0, 0), (0, cpad)),
                         constant_values=_NEG_INF)
    if bpad:
        logits = jnp.pad(logits, ((0, bpad), (0, 0)),
                         constant_values=_NEG_INF)
        # padded rows pick class 0; their loss rows are dropped by the caller
        labels = jnp.pad(labels, (0, bpad))
    return logits, labels, b, c


def _run_fwd(logits, labels, interpret=False):
    logits, labels, b, c = _pad(logits, labels)
    bp, cp = logits.shape
    grid = (bp // _TILE_B,)
    loss = pl.pallas_call(
        _fwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_TILE_B, cp), lambda i: (i, 0), memory_space=_VMEM),
            pl.BlockSpec((_TILE_B, 1), lambda i: (i, 0), memory_space=_VMEM),
        ],
        out_specs=pl.BlockSpec((_TILE_B, 1), lambda i: (i, 0),
                               memory_space=_VMEM),
        out_shape=jax.ShapeDtypeStruct((bp, 1), jnp.float32),
        interpret=interpret,
        name="softmax_xent_fwd",  # what a trace's events are found by
    )(logits, labels.astype(jnp.int32).reshape(-1, 1))
    return loss[:b, 0]


def _run_bwd(logits, labels, g, interpret=False):
    dtype = logits.dtype
    logits, labels, b, c = _pad(logits, labels)
    bp, cp = logits.shape
    g = jnp.pad(g.reshape(-1, 1), ((0, bp - b), (0, 0)))
    grid = (bp // _TILE_B,)
    grad = pl.pallas_call(
        _bwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_TILE_B, cp), lambda i: (i, 0), memory_space=_VMEM),
            pl.BlockSpec((_TILE_B, 1), lambda i: (i, 0), memory_space=_VMEM),
            pl.BlockSpec((_TILE_B, 1), lambda i: (i, 0), memory_space=_VMEM),
        ],
        out_specs=pl.BlockSpec((_TILE_B, cp), lambda i: (i, 0),
                               memory_space=_VMEM),
        out_shape=jax.ShapeDtypeStruct((bp, cp), dtype),
        interpret=interpret,
        name="softmax_xent_bwd",
    )(logits, labels.astype(jnp.int32).reshape(-1, 1), g)
    return grad[:b, :c]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def softmax_xent(logits: jax.Array, labels: jax.Array,
                 interpret: bool = False) -> jax.Array:
    """Per-example softmax cross-entropy, fused on TPU. ``interpret=True``
    runs the kernel in the Pallas interpreter (CPU tests)."""
    return _run_fwd(logits, labels, interpret)


def _vjp_fwd(logits, labels, interpret):
    return _run_fwd(logits, labels, interpret), (logits, labels)


def _vjp_bwd(interpret, res, g):
    logits, labels = res
    return _run_bwd(logits, labels, g, interpret), None


softmax_xent.defvjp(_vjp_fwd, _vjp_bwd)


def softmax_xent_mean(logits: jax.Array, labels: jax.Array,
                      interpret: bool = False) -> jax.Array:
    return softmax_xent(logits, labels, interpret).mean()
