"""The optimizers and schedules the configurations state, written out.

Plain float32 ``jax.numpy``; nothing is imported from the program or from
optax. Each function follows the published rule:

* momentum SGD (Sutskever et al. 2013, the non-Nesterov form TensorFlow's
  ``MomentumOptimizer`` applies): ``t <- g + m t``; ``p <- p - lr t``.
* AdamW (Loshchilov & Hutter, arXiv:1711.05101, Algorithm 2):
  bias-corrected moments, decoupled decay on the kernels only.

``first_moment`` is what the comparison reads back as "the gradient as the
optimizer got it": ``t`` for momentum, ``mu`` for AdamW.
"""
from __future__ import annotations

import math
from typing import Callable, Dict

import jax.numpy as jnp


def schedule(opt: dict) -> Callable[[int], float]:
    """``count -> lr`` for the schedule the configuration states (count is a
    Python int: the reference unrolls its few steps)."""
    kind = opt["schedule"]
    if kind == "constant":
        return lambda count: opt["learning_rate"]
    if kind == "warmup_piecewise":
        warm, start, peak = opt["warmup_steps"], opt["warmup_start"], opt["values"][0]

        def piecewise(count):
            if count < warm:
                return start + (count / max(warm, 1)) * (peak - start)
            idx = sum(count >= b for b in opt["boundaries"])
            return opt["values"][idx]
        return piecewise
    if kind == "cosine":
        warm, peak, total = max(opt["warmup_steps"], 1), opt["learning_rate"], opt["total_steps"]

        def cosine(count):
            if count < warm:
                return peak * count / warm
            frac = min((count - warm) / max(total - warm, 1), 1.0)
            return peak * 0.5 * (1.0 + math.cos(math.pi * frac))
        return cosine
    raise ValueError(f"no reference for schedule {kind!r}")


def decayed(name: str, leaf) -> bool:
    """The default, for a family that states no rule of its own. Kernels
    only: not biases, not normalisation scales, not pos_embed."""
    last = name.rsplit(".", 1)[-1]
    return leaf.ndim > 1 and "bias" not in last and "pos_embed" not in last


def l2_term(params: Dict[str, jnp.ndarray], rate: float, decayed=decayed):
    """0.5 * rate * sum ||kernel||^2, the loss-side decay of momentum SGD."""
    return 0.5 * rate * sum(jnp.sum(jnp.square(p)) for n, p in params.items()
                            if decayed(n, p))


def init(opt: dict, params):
    zeros = {n: jnp.zeros_like(p) for n, p in params.items()}
    if opt["name"] == "momentum":
        return {"t": zeros}
    if opt["name"] == "adamw":
        return {"mu": zeros, "nu": {n: jnp.zeros_like(p) for n, p in params.items()}}
    raise ValueError(f"no reference for optimizer {opt['name']!r}")


def first_moment(opt: dict, state):
    return state["t"] if opt["name"] == "momentum" else state["mu"]


def update(opt: dict, params, grads, state, lr: float, n_updates: int, decayed=decayed):
    """One update; ``n_updates`` counts this one (1 for the first).
    ``decayed(name, leaf)`` is the family's rule where it states one."""
    if opt["name"] == "momentum":
        t = {n: grads[n] + opt["momentum"] * state["t"][n] for n in params}
        return {n: params[n] - lr * t[n] for n in params}, {"t": t}
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    mu = {n: b1 * state["mu"][n] + (1 - b1) * grads[n] for n in params}
    nu = {n: b2 * state["nu"][n] + (1 - b2) * jnp.square(grads[n]) for n in params}
    c1, c2 = 1 - b1 ** n_updates, 1 - b2 ** n_updates
    new = {}
    for n, p in params.items():
        step = (mu[n] / c1) / (jnp.sqrt(nu[n] / c2) + eps)
        if decayed(n, p):
            step = step + opt["weight_decay"] * p
        new[n] = p - lr * step
    return new, {"mu": mu, "nu": nu}
