"""SDAR-30B-A3B-Chat (JetLM, ``model_type: sdar_moe``) as a plain float32
``jax.numpy`` function: one chip's share of a deployment in which eight
chips share each layer, trained with the block-diffusion objective of
arXiv:2503.09573 (which arXiv:2510.06303 adopts).

The layer is the Qwen3-MoE block. RMS norm, attention of 32 query heads over
4 key/value heads of 128 with per-head RMS norms on queries and keys and
rotate-half rotary positions over all 128 dimensions on every layer, joined
to the residual stream as it is; RMS norm, then an expert layer: a float32
softmax over all 128 published experts, the 8 largest, their weights
renormalised over the eight, no bias, no shared expert, no auxiliary term,
and of the routed experts the contiguous range ``experts_held`` alone: what
the absent experts would add is left out, and that partial result goes on.
After the last layer an RMS norm and an untied head over the ``vocab_held``
rows of the vocabulary held here.

One example is a sequence x of L ids with its noising: m (L) of 0/1 and t
(L/B), one level a diffusion block of B ids. The decoder runs once over 2L
positions, the noisy copy (x with MASK where m) and then the clean copy,
each token at the position id of its place in the sequence (a noisy token
and its clean twin share it). With b(i) = i // B a query sees a key iff

* clean -> clean: b(j) <= b(i);
* noisy -> clean: b(j) <  b(i);
* noisy -> noisy: b(j) == b(i);
* clean -> noisy: never,

so attention runs both ways inside a block. The head reads the noisy copy
alone, each position predicting the id at its own place (no shift), and the
example's loss is (1/L) sum_i m_i / t_b(i) * nll_i.

Departures from the published description, each because the row of the
catalog does not give it (the configuration's ``assumed`` says so too): the
block length and the noise (linear schedule, t uniform a block, weight 1/t)
are the family's convention; the MASK id is the last row held, as the
published one lies outside the slice; the per-head norms and the rotary
convention are the Qwen3-MoE modelling code's.

No kernels, no mixed precision, no sharding, no sorting of tokens; nothing
imported from the program, and the copies are laid [noisy; clean] whatever
order the program walks them in. ``quant`` is the control's hook, applied to
both operands of every matrix product (identity for the reference); the
router's product is left out of it, as the configuration states it in
float32.
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

#: whole sequences to a block, per device
EXAMPLE_BLOCK = 1
#: queries to a block of the attention (scores are [heads, block, 2L keys])
QUERY_BLOCK = 256
#: what the program fixes in its code, or does not hold at all: the
#: published counts beside the counts held here, and the family's constants
FIXED_IN_CODE = {"model": ["vocab_published", "layers_published", "hidden_act",
                           "score_func", "norm_topk_prob", "tie_word_embeddings",
                           "router_dtype", "noise_schedule", "rope_all_layers"],
                 "optimizer": ["b1", "b2", "eps"]}
HELD_ELSEWHERE = {"seq_len": ["data", "seq_len"],
                  "experts_published": ["model", "num_experts"]}


def _shapes(model: dict) -> Dict[str, tuple]:
    d, hd = model["hidden_size"], model["head_dim"]
    q, kv = model["num_attention_heads"] * hd, model["num_key_value_heads"] * hd
    lo, hi = model["experts_held"]
    m, v = model["moe_intermediate_size"], model["vocab_held"]
    out = {"embed": (v, d)}
    for i in range(len(model["layer_types"])):
        p = f"layer{i}."
        out.update({p + "input_norm.scale": (d,), p + "attn.q_proj": (d, q),
                    p + "attn.k_proj": (d, kv), p + "attn.v_proj": (d, kv),
                    p + "attn.o_proj": (q, d), p + "attn.q_norm.scale": (hd,),
                    p + "attn.k_norm.scale": (hd,), p + "pre_mlp_norm.scale": (d,),
                    p + "moe.router": (d, model["experts_published"]),
                    p + "moe.experts.gate": (hi - lo, d, m),
                    p + "moe.experts.up": (hi - lo, d, m),
                    p + "moe.experts.down": (hi - lo, m, d)})
    out.update({"final_norm.scale": (d,), "lm_head": (d, v)})
    return out


#: the embedding's start, and how far a router's columns stand off their
#: period's pattern (init_params)
EMBEDDING_STD = 1.0
ROUTER_JITTER = 0.01


def init_params(key, model: dict) -> Dict[str, jnp.ndarray]:
    """Fan-in scaled normal matrices, the two that close a residual branch
    (attention's output, an expert's ``down``) smaller by
    1/sqrt(2 x layers); the norms' scales at 1. Two leaves start otherwise,
    each for what the start at the usual value did on the chip (the
    configuration's ``assumed`` has the numbers):

    * the embedding at 1, not 0.02 (this family has no embedding multiplier;
      Trinity's enters the stream at 0.9 through its own): at 0.02 the
      attention branch outweighs it from the first layer, the branch's
      component common to every token decides the routers' choices, and all
      tokens of a batch choose the same experts;
    * a router's columns in periods of experts_published / experts per token
      (16): column e is column e mod 16 of a fan-in normal matrix plus a
      hundredth of another, so a token's eight choices fall one into each
      range of 16, the range held here among them. Under this objective a
      quarter of a step's positions carry one and the same MASK embedding
      and choose alike, and with independent columns which experts they
      favour, and how many of those are held here, is the seed's: the held
      range's load, and the step's time with it, then swings with the seed.
      One choice a chip's range is what a balanced deployment gives, and
      the held load is then the expectation whatever the seed."""
    params = {}
    closing = 1.0 / np.sqrt(2.0 * len(model["layer_types"]))
    period = model["experts_published"] // model["num_experts_per_tok"]
    for i, (name, shape) in enumerate(_shapes(model).items()):
        k = jax.random.fold_in(key, i)
        if name.endswith(".scale"):
            params[name] = jnp.ones(shape, jnp.float32)
        elif name == "embed":
            params[name] = EMBEDDING_STD * jax.random.normal(k, shape, jnp.float32)
        elif name.endswith("moe.router"):
            std = 1.0 / np.sqrt(shape[0])
            pattern = jax.random.normal(jax.random.fold_in(k, 1), (shape[0], period))
            off = jax.random.normal(jax.random.fold_in(k, 2), shape)
            params[name] = std * (jnp.tile(pattern, (1, shape[1] // period))
                                  + ROUTER_JITTER * off).astype(jnp.float32)
        else:
            std = 1.0 / np.sqrt(shape[-2])
            if name.endswith(("o_proj", "down")):
                std *= closing
            params[name] = std * jax.random.normal(k, shape, jnp.float32)
    return params


def program_paths(model: dict) -> Dict[str, str]:
    """Where the program under test keeps each leaf (its flax module path).
    Names only: no value crosses from the program to the reference."""
    out = {}
    for name in _shapes(model):
        path = name.replace(".", "/")
        if name == "embed":
            path = "embed/embedding"
        elif name.endswith(("_proj", "lm_head", "moe.router")):
            path += "/kernel"
        out[name] = path
    return out


def examples(batch: Dict[str, np.ndarray], step: int, augment_seed: int):
    """One example is one whole sequence with its noising, which the host
    batch carries: nothing is drawn per step."""
    del step, augment_seed
    return {"tokens": jnp.asarray(batch["tokens"], jnp.int32),
            "masked": jnp.asarray(batch["masked"], jnp.float32),
            "t": jnp.asarray(batch["t"], jnp.float32)}


def decayed(name: str, leaf) -> bool:
    """The matrices; not the norms, not the embedding."""
    return leaf.ndim > 1 and name != "embed"


def _rms(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rotary(x, theta: float, positions: np.ndarray):
    """Rotate-half rotary positions over all of the last axis; x [T, H, hd],
    token r at position id ``positions[r]``."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    angles = positions.astype(np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(angles)] * 2, -1), jnp.float32)[:, None]
    sin = jnp.asarray(np.concatenate([np.sin(angles)] * 2, -1), jnp.float32)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def seen(q_rows, k_rows, length: int, block: int):
    """[queries, keys] bool over rows of the doubled sequence [noisy; clean]:
    the four rules of the module docstring."""
    q_clean, k_clean = (q_rows >= length)[:, None], (k_rows >= length)[None, :]
    qb, kb = ((q_rows % length) // block)[:, None], ((k_rows % length) // block)[None, :]
    clean_clean = q_clean & k_clean & (kb <= qb)
    noisy_clean = ~q_clean & k_clean & (kb < qb)
    noisy_noisy = ~q_clean & ~k_clean & (kb == qb)
    return clean_clean | noisy_clean | noisy_noisy


def _attention_core(q, k, v, length: int, block: int, quant: Callable):
    """softmax(q k^T / sqrt(hd)) v over the pairs ``seen`` keeps, for one
    doubled sequence, in blocks of queries. q [2L, H, hd]; k, v [2L, KV, hd].
    Each group of H/KV query heads reads one key/value head."""
    t, h, hd = q.shape
    kv = k.shape[1]
    blk = min(QUERY_BLOCK, t)
    while t % blk:
        blk -= 1
    qb = q.reshape(t // blk, blk, kv, h // kv, hd)
    k_rows = jnp.arange(t)

    @jax.checkpoint
    def one(args):
        qi, start = args
        s = jnp.einsum("qkgd,tkd->kgqt", quant(qi), quant(k)) / np.sqrt(hd)
        keep = seen(start + jnp.arange(blk), k_rows, length, block)
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", quant(p), quant(v))
    out = jax.lax.map(one, (qb, jnp.arange(0, t, blk)))
    return out.reshape(t, h * hd)


def _attention(x, p: Dict[str, jnp.ndarray], model: dict, quant: Callable):
    t = x.shape[0]
    length = t // 2
    hd, eps = model["head_dim"], model["rms_norm_eps"]
    q = (quant(x) @ quant(p["attn.q_proj"])).reshape(t, -1, hd)
    k = (quant(x) @ quant(p["attn.k_proj"])).reshape(t, -1, hd)
    v = (quant(x) @ quant(p["attn.v_proj"])).reshape(t, -1, hd)
    q, k = _rms(q, p["attn.q_norm.scale"], eps), _rms(k, p["attn.k_norm.scale"], eps)
    positions = np.tile(np.arange(length), 2)  # a twin shares its twin's id
    q = _rotary(q, model["rope_theta"], positions)
    k = _rotary(k, model["rope_theta"], positions)
    o = _attention_core(q, k, v, length, model["block_length"], quant)
    return quant(o) @ quant(p["attn.o_proj"])


def route(x, router, model: dict):
    """(chosen experts [T, k], their weights [T, k]) over all the published
    experts, in float32 whatever the control's precision."""
    p = jax.nn.softmax(jnp.dot(x, router, precision="highest"), axis=-1)
    picked, sel = jax.lax.top_k(p, model["num_experts_per_tok"])
    return sel, picked / jnp.sum(picked, axis=-1, keepdims=True)


def _expert(x, gate, up, down, quant: Callable):
    h = jax.nn.silu(quant(x) @ quant(gate)) * (quant(x) @ quant(up))
    return quant(h) @ quant(down)


def _moe(x, p: Dict[str, jnp.ndarray], model: dict, quant: Callable):
    """The held experts' part of the routed sum."""
    sel, w = route(x, p["moe.router"], model)
    n_exp = model["experts_published"]
    # [T, experts]: a token's weight for each expert, 0 where not chosen
    dense_w = jnp.sum(jax.nn.one_hot(sel, n_exp, dtype=jnp.float32) * w[..., None], axis=1)
    lo, hi = model["experts_held"]

    def one(acc, held):
        gate, up, down, w_e = held
        return acc + w_e[:, None] * _expert(x, gate, up, down, quant), None
    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (p["moe.experts.gate"], p["moe.experts.up"],
                           p["moe.experts.down"], dense_w[:, lo:hi].T))
    return out


def _layer(x, p: Dict[str, jnp.ndarray], model: dict, quant: Callable):
    eps = model["rms_norm_eps"]
    x = x + _attention(_rms(x, p["input_norm.scale"], eps), p, model, quant)
    return x + _moe(_rms(x, p["pre_mlp_norm.scale"], eps), p, model, quant)


def sequence_loss(params, tokens, masked, t, model: dict, quant: Callable = lambda a: a):
    """One sequence [L] with its noising (masked [L] of 0/1, t [L/B]): the
    mean over its L positions of masked / t x the cross-entropy of the id at
    that position, read off the noisy copy."""
    length = tokens.shape[0]
    noisy = jnp.where(masked > 0, model["mask_token_held"], tokens)
    x = params["embed"][jnp.concatenate([noisy, tokens])]
    for i in range(len(model["layer_types"])):
        prefix = f"layer{i}."
        p = {n[len(prefix):]: v for n, v in params.items() if n.startswith(prefix)}
        x = jax.checkpoint(lambda x, p: _layer(x, p, model, quant))(x, p)
    # the clean copy never meets the head
    x = _rms(x[:length], params["final_norm.scale"], model["rms_norm_eps"])
    logits = quant(x) @ quant(params["lm_head"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    weight = masked / jnp.repeat(t, model["block_length"])
    return jnp.mean(weight * (logz - picked))


def loss_sum(params, block, weights, model: dict, quant: Callable = lambda a: a):
    """Sum over the block's sequences of weight x the sequence's loss."""
    def one(total, row):
        tokens, masked, t, w = row
        return total + w * sequence_loss(params, tokens, masked, t, model, quant), None
    total, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32),
                            (block["tokens"], block["masked"], block["t"], weights))
    return total, None
