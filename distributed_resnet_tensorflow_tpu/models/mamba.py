"""The Mamba-2 mixer (arXiv:2405.21060) of the hybrid decoders
(models/transformer.py, the ``mamba`` layer kind).

u (n, T, d) -> (n, T, d):

* ``[z | xBC | Δ] = u W_in``, widths I = H·P, I + 2·G·N and H, no bias;
* ``xBC <- SiLU(causal depthwise conv_K(xBC) + b_conv)``, zero-padded at the
  sequence's start, so a position reads itself and the K − 1 before it;
* x (H, P), B (G, N), C (G, N) out of xBC; Δ = softplus(Δ + dt_bias) per
  head, unclamped; A = −exp(A_log) per head;
* y = the chunked scan of ``ops/ssd.py`` (S_t = exp(Δ_t A) S_{t−1} +
  Δ_t x_t ⊗ B_t, y_t = S_t C_t, S_0 = 0) + D ⊙ x;
* ``y <- w ⊙ GroupRMS(y ⊙ SiLU(z))`` over groups of I/G channels;
* ``out = y W_out``.

The projections run in ``dtype`` with float32 results; Δ, A, D, the
convolution's sums, the gate and the norm are float32. Scopes: ``conv``
(the convolution and its SiLU), ``scan`` (the scan with its reshapes and the
D term), ``gated_norm``; the block wraps the whole in ``mamba``.
"""
from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.ssd import chunked_scan


def causal_conv(x, weight, bias):
    """Σ_k weight[k] · x[t − (K − 1) + k] + bias per channel, x (n, T, C)
    zero before the sequence's start, weight (K, C); float32."""
    k = weight.shape[0]
    t = x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    out = bias.astype(jnp.float32)
    for i in range(k):
        out = out + padded[:, i:i + t] * weight[i]
    return out


def dt_init(key, shape, floor: float, low: float, high: float):
    """dt_bias from Δ drawn log-uniform on [low, high] and floored: the
    softplus inverse, Δ + log(−expm1(−Δ))."""
    u = jax.random.uniform(key, shape, jnp.float32)
    dt = jnp.maximum(jnp.exp(u * (math.log(high) - math.log(low))
                             + math.log(low)), floor)
    return dt + jnp.log(-jnp.expm1(-dt))


class Mamba2(nn.Module):
    heads: int
    head_dim: int
    groups: int
    state: int
    conv_kernel: int
    chunk: int
    eps: float
    #: Δ's start: (floor, low, high), as ``dt_init`` takes them
    time_step: tuple = (1e-4, 1e-3, 0.1)
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u: jax.Array) -> jax.Array:
        n, t, d = u.shape
        h, p, g, s = self.heads, self.head_dim, self.groups, self.state
        inner, xbc = h * p, h * p + 2 * g * s
        from .moe import Kernel
        w_in = Kernel(inner + xbc + h, name="in_proj")(d)
        zxd = jnp.dot(u.astype(self.dtype), w_in.astype(self.dtype),
                      preferred_element_type=jnp.float32)
        z, xs, dt = jnp.split(zxd, [inner, inner + xbc], axis=-1)
        conv_w = self.param("conv_weight", nn.initializers.lecun_normal(
            in_axis=0, out_axis=1), (self.conv_kernel, xbc))
        conv_b = self.param("conv_bias", nn.initializers.zeros, (xbc,))
        with jax.named_scope("conv"):
            xs = nn.silu(causal_conv(xs, conv_w, conv_b))
        dt_bias = self.param("dt_bias", lambda k, sh: dt_init(k, sh, *self.time_step), (h,))
        a_log = self.param("A_log", lambda k, sh: jnp.log(jax.random.uniform(
            k, sh, jnp.float32, 1.0, 16.0)), (h,))
        skip = self.param("D", nn.initializers.ones, (h,))
        with jax.named_scope("scan"):
            x, b, c = jnp.split(xs, [inner, inner + g * s], axis=-1)
            x = x.reshape(n, t, h, p)
            y = chunked_scan(x, jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log),
                     b.reshape(n, t, g, s), c.reshape(n, t, g, s), self.chunk,
                     self.dtype)
            y = (y + x * skip[:, None]).reshape(n, t, inner)
        scale = self.param("norm_scale", nn.initializers.ones, (inner,))
        with jax.named_scope("gated_norm"):
            y = (y * nn.silu(z)).reshape(n, t, g, inner // g)
            y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                                  + self.eps)
            y = y.reshape(n, t, inner) * scale
        w_out = Kernel(d, name="out_proj")(inner)
        return jnp.dot(y.astype(self.dtype), w_out.astype(self.dtype),
                       preferred_element_type=jnp.float32)
