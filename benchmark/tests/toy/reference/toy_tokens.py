"""A toy family that is no one's model, to prove that a family which is not
an image classifier comes as files alone (``benchmark/tests/test_spec.py``
copies this tree into a copy of the benchmark and changes no file there).

Token ids ``[B, T + 2]``; an embedding; one gated feed-forward with a
residual; a head over the slice of the vocabulary held here, trained on the
next token; a second head trained on the token two ahead, at the weight the
configuration states; and ``count_bias``, one vector added to the first
head's logits that has no gradient: after every update a rule moves it
towards the tokens the batch held fewer of than the mean. The family states
its whole contract (``benchmark/reference/follow.py``): its batch, its loss,
its blocks of whole sequences, its decay and its rule.
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

#: whole sequences to a block, per device
EXAMPLE_BLOCK = 2
#: leaves that only the rule moves
RULED = ("count_bias",)
#: what the program would fix in its code: the published vocabulary beside
#: the slice held here, the second head's weight and the rule's rate
FIXED_IN_CODE = {"model": ["vocab_published", "second_head_weight", "rule_rate"],
                 "optimizer": ["b1", "b2", "eps"]}
HELD_ELSEWHERE: dict = {}


def _shapes(model: dict) -> Dict[str, tuple]:
    v, d, h = model["vocab_held"], model["d_model"], model["d_ff"]
    return {"embed": (v, d), "ffn.gate": (d, h), "ffn.up": (d, h), "ffn.down": (h, d),
            "norm.scale": (d,), "head.kernel": (d, v), "head2.kernel": (d, v),
            "count_bias": (v,)}


def init_params(key, model: dict) -> Dict[str, jnp.ndarray]:
    params = {}
    for i, (name, shape) in enumerate(_shapes(model).items()):
        k = jax.random.fold_in(key, i)
        if name == "norm.scale":
            params[name] = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif name == "count_bias":
            params[name] = jnp.zeros(shape, jnp.float32)
        else:
            params[name] = jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[0])
    return params


def program_paths(model: dict) -> Dict[str, str]:
    return {name: name.replace(".", "/") for name in _shapes(model)}


def examples(batch: Dict[str, np.ndarray], step: int, augment_seed: int):
    """One example is one whole sequence; nothing is drawn per step."""
    del step, augment_seed
    return {"tokens": jnp.asarray(batch["tokens"], jnp.int32)}


def decayed(name: str, leaf) -> bool:
    """The feed-forward and the heads; not the embedding, the norm, the bias."""
    del leaf
    return name.startswith("ffn.") or name.endswith(".kernel")


def _xent(logits, targets):
    logz = jax.nn.logsumexp(logits, axis=-1)
    return logz - jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]


def loss_sum(params, block, weights, model: dict, quant: Callable = lambda a: a):
    """Sum over the block's sequences of weight x (mean over positions of the
    next token's cross-entropy + second_head_weight x the same two ahead),
    and beside it how often each held token stood among the inputs."""
    tokens = block["tokens"]
    inputs, next1, next2 = tokens[:, :-2], tokens[:, 1:-1], tokens[:, 2:]
    x = params["embed"][inputs]
    gate = jax.nn.silu(quant(x) @ quant(params["ffn.gate"]))
    x = x + quant(gate * (quant(x) @ quant(params["ffn.up"]))) @ quant(params["ffn.down"])
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + 1e-6) \
        * params["norm.scale"]
    logits1 = quant(x) @ quant(params["head.kernel"]) \
        + jax.lax.stop_gradient(params["count_bias"])
    logits2 = quant(x) @ quant(params["head2.kernel"])
    per_sequence = jnp.mean(_xent(logits1, next1), axis=1) \
        + model["second_head_weight"] * jnp.mean(_xent(logits2, next2), axis=1)
    counts = jnp.sum(weights[:, None, None]
                     * jax.nn.one_hot(inputs, model["vocab_held"]), axis=(0, 1))
    return jnp.sum(weights * per_sequence), {"counts": counts}


def after_update(params, aux, model: dict):
    """The rule: the bias of a token the batch held fewer of than the mean
    goes up by ``rule_rate``, of one it held more of goes down."""
    counts = aux["counts"]
    moved = params["count_bias"] + model["rule_rate"] * jnp.sign(jnp.mean(counts) - counts)
    return dict(params, count_bias=moved)
