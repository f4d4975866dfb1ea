"""Trinity-Mini (arcee-ai, ``model_type: afmoe``) as a plain float32
``jax.numpy`` function: one chip's share of a deployment in which eight
chips share each layer.

A causal decoder. Every layer: RMS norm, attention of 32 query heads over 4
key/value heads of 128 with per-head RMS norms on queries and keys, rotary
positions on the ``sliding_attention`` layers only (none at all on the
``full_attention`` ones), a window of 2048 keys on the sliding layers, a
sigmoid gate on the attention's output before its projection, an RMS norm
on the branch's output before it joins the residual stream; then the same
sandwich around a SwiGLU feed-forward (the leading dense layers) or an
expert layer (the rest): sigmoid scores over all 128 experts, the top 8 by
score plus a bias that takes part in the choice only, weights renormalised
over the eight and scaled, a shared expert, and of the routed experts the
contiguous range ``experts_held`` alone: what the absent experts would add
is left out, and that partial result goes on. An untied head over the
``vocab_held`` rows of the vocabulary held here; the loss is the mean over
positions of the next token's cross-entropy. After the optimizer's update a
rule moves each router's bias against the step's load (arXiv:2408.15664).

Where the catalog row gives a key and not its meaning the configuration's
``assumed`` says what was taken: the embedding multiplier sqrt(hidden_size)
(``mup_enabled``), the output gate, the bias rule.

No kernels, no mixed precision, no sharding, no sorting of tokens; nothing
imported from the program. ``quant`` is the control's hook, applied to both
operands of every matrix product (identity for the reference); the router's
product is left out of it, as the configuration states it in float32.
"""
from __future__ import annotations

from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

#: whole sequences to a block, per device
EXAMPLE_BLOCK = 1
#: queries to a block of the attention (scores are [heads, block, keys])
QUERY_BLOCK = 256
#: what the program fixes in its code, or does not hold at all: the
#: published counts beside the counts held here, and the family's constants
FIXED_IN_CODE = {"model": ["vocab_published", "layers_published",
                           "dense_layers_published", "hidden_act", "score_func",
                           "route_norm", "tie_word_embeddings", "router_dtype",
                           "attention_output_gate", "embedding_multiplier"],
                 "optimizer": ["b1", "b2", "eps"]}
HELD_ELSEWHERE = {"seq_len": ["data", "seq_len"],
                  "experts_published": ["model", "num_experts"]}
#: leaves that only the rule moves: the router's bias of every expert layer
#: (a layer that has none is not among the leaves, and the name is idle)
RULED = tuple(f"layer{i}.moe.router_bias" for i in range(128))


def _is_expert_layer(model: dict, i: int) -> bool:
    return i >= model["num_dense_layers"]


def ruled_leaves(model: dict) -> List[str]:
    return [RULED[i] for i in range(len(model["layer_types"]))
            if _is_expert_layer(model, i)]


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
                    p + "attn.gate_proj": (d, q), p + "attn.o_proj": (q, d),
                    p + "attn.q_norm.scale": (hd,), p + "attn.k_norm.scale": (hd,),
                    p + "post_attn_norm.scale": (d,), p + "pre_mlp_norm.scale": (d,),
                    p + "post_mlp_norm.scale": (d,)})
        if _is_expert_layer(model, i):
            s = m * model["num_shared_experts"]
            out.update({p + "moe.router": (d, model["experts_published"]),
                        p + "moe.router_bias": (model["experts_published"],),
                        p + "moe.experts.gate": (hi - lo, d, m),
                        p + "moe.experts.up": (hi - lo, d, m),
                        p + "moe.experts.down": (hi - lo, m, d),
                        p + "moe.shared.gate": (d, s), p + "moe.shared.up": (d, s),
                        p + "moe.shared.down": (s, d)})
        else:
            f = model["intermediate_size"]
            out.update({p + "mlp.gate": (d, f), p + "mlp.up": (d, f), p + "mlp.down": (f, d)})
    out.update({"final_norm.scale": (d,), "lm_head": (d, v)})
    return out


def init_params(key, model: dict) -> Dict[str, jnp.ndarray]:
    """Fan-in scaled normal matrices, those that close a residual branch
    (attention output, every ``down``) smaller by 1/sqrt(2 x layers); the
    embedding at 0.02 (times sqrt(hidden_size) it enters at about 0.9); a
    seeded spread around 1 on the norms' scales, so that every leaf has a
    gradient of its own size from the first step, and around 0.1 on the
    two norms that close a branch (LayerScale's start for a network of up
    to 18 layers, arXiv:2103.17239; the published modelling code starts
    them at 1, the configuration's ``assumed`` says so): a branch's output
    is re-normalised
    before it joins the stream, attention's carries a component common to
    every token, and at 1 that component decides the routers' choices (a
    few experts favoured by all tokens, which ones by the seed: the held
    experts' load, and the step's time with it, then swing with the seed);
    at 0.1 the stream starts as the embedding, the choices as the tokens'
    own, near balance, as a trained router's are. The routers' biases at 0."""
    params = {}
    closing = 1.0 / np.sqrt(2.0 * len(model["layer_types"]))
    for i, (name, shape) in enumerate(_shapes(model).items()):
        k = jax.random.fold_in(key, i)
        if name.endswith("router_bias"):
            params[name] = jnp.zeros(shape, jnp.float32)
        elif name.endswith(".scale"):
            params[name] = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            if name.endswith(("post_attn_norm.scale", "post_mlp_norm.scale")):
                params[name] = 0.1 * params[name]
        elif name == "embed":
            params[name] = 0.02 * jax.random.normal(k, shape, jnp.float32)
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
        elif name.endswith(("_proj", "lm_head", "moe.router")) or \
                ".mlp." in name or ".shared." in name:
            path += "/kernel"
        out[name] = path
    return out


def examples(batch: Dict[str, np.ndarray], step: int, augment_seed: int):
    """One example is one whole sequence; nothing is drawn per step."""
    del step, augment_seed
    return {"tokens": jnp.asarray(batch["tokens"], jnp.int32)}


def decayed(name: str, leaf) -> bool:
    """The matrices; not the norms, the embedding, the routers' biases."""
    return leaf.ndim > 1 and name != "embed"


def _rms(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rotary(x, theta: float):
    """Rotate-half rotary positions over all of the last axis; x [T, H, hd]."""
    t, hd = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    angles = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(angles)] * 2, -1), jnp.float32)[:, None]
    sin = jnp.asarray(np.concatenate([np.sin(angles)] * 2, -1), jnp.float32)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _swiglu(x, gate, up, down, quant: Callable):
    h = jax.nn.silu(quant(x) @ quant(gate)) * (quant(x) @ quant(up))
    return quant(h) @ quant(down)


def _attention_core(q, k, v, window, quant: Callable):
    """softmax(q k^T / sqrt(hd)) v for one sequence, in blocks of queries.
    q [T, H, hd]; k, v [T, KV, hd]; query i sees keys j <= i, and with a
    window also j > i - window. Each group of H/KV query heads reads one
    key/value head."""
    t, h, hd = q.shape
    kv = k.shape[1]
    blk = min(QUERY_BLOCK, t)
    while t % blk:
        blk -= 1
    qb = q.reshape(t // blk, blk, kv, h // kv, hd)
    k_pos = jnp.arange(t)

    @jax.checkpoint
    def one(args):
        qi, start = args
        s = jnp.einsum("qkgd,tkd->kgqt", quant(qi), quant(k)) / np.sqrt(hd)
        q_pos = start + jnp.arange(blk)
        seen = k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            seen = seen & (k_pos[None, :] > q_pos[:, None] - window)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", quant(p), quant(v))
    out = jax.lax.map(one, (qb, jnp.arange(0, t, blk)))
    return out.reshape(t, h * hd)


def _attention(x, p: Dict[str, jnp.ndarray], kind: str, model: dict, quant: Callable):
    t = x.shape[0]
    hd, eps = model["head_dim"], model["rms_norm_eps"]
    q = (quant(x) @ quant(p["attn.q_proj"])).reshape(t, -1, hd)
    k = (quant(x) @ quant(p["attn.k_proj"])).reshape(t, -1, hd)
    v = (quant(x) @ quant(p["attn.v_proj"])).reshape(t, -1, hd)
    q, k = _rms(q, p["attn.q_norm.scale"], eps), _rms(k, p["attn.k_norm.scale"], eps)
    window = None
    if kind == "sliding_attention":
        q, k = _rotary(q, model["rope_theta"]), _rotary(k, model["rope_theta"])
        window = model["sliding_window"]
    o = _attention_core(q, k, v, window, quant)
    o = o * jax.nn.sigmoid(quant(x) @ quant(p["attn.gate_proj"]))
    return quant(o) @ quant(p["attn.o_proj"])


def route(x, router, bias, model: dict):
    """(chosen experts [T, k], their weights [T, k]) over all the published
    experts, in float32 whatever the control's precision."""
    s = jax.nn.sigmoid(jnp.dot(x, router, precision="highest"))
    _, sel = jax.lax.top_k(s + jax.lax.stop_gradient(bias), model["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, sel, axis=-1)
    w = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) * model["route_scale"]
    return sel, w


def _moe(x, p: Dict[str, jnp.ndarray], model: dict, quant: Callable):
    """Shared expert plus the held experts' part of the routed sum, and how
    many of the sequence's assignments went to each published expert."""
    sel, w = route(x, p["moe.router"], p["moe.router_bias"], model)
    n_exp = model["experts_published"]
    # [T, experts]: a token's weight for each expert, 0 where not chosen
    dense_w = jnp.sum(jax.nn.one_hot(sel, n_exp, dtype=jnp.float32) * w[..., None], axis=1)
    counts = jnp.sum(jax.nn.one_hot(sel, n_exp, dtype=jnp.float32), axis=(0, 1))
    lo, hi = model["experts_held"]
    out = _swiglu(x, p["moe.shared.gate"], p["moe.shared.up"], p["moe.shared.down"], quant)

    def one(acc, held):
        gate, up, down, w_e = held
        return acc + w_e[:, None] * _swiglu(x, gate, up, down, quant), None
    out, _ = jax.lax.scan(one, out, (p["moe.experts.gate"], p["moe.experts.up"],
                                     p["moe.experts.down"], dense_w[:, lo:hi].T))
    return out, counts


def _layer(x, p: Dict[str, jnp.ndarray], i: int, model: dict, quant: Callable):
    eps = model["rms_norm_eps"]
    a = _attention(_rms(x, p["input_norm.scale"], eps), p, model["layer_types"][i],
                   model, quant)
    x = x + _rms(a, p["post_attn_norm.scale"], eps)
    m = _rms(x, p["pre_mlp_norm.scale"], eps)
    if _is_expert_layer(model, i):
        f, counts = _moe(m, p, model, quant)
    else:
        f, counts = _swiglu(m, p["mlp.gate"], p["mlp.up"], p["mlp.down"], quant), None
    return x + _rms(f, p["post_mlp_norm.scale"], eps), counts


def sequence_loss(params, tokens, model: dict, quant: Callable = lambda a: a):
    """One sequence [T + 1]: the mean over its T positions of the next
    token's cross-entropy, and per expert layer the counts of assignments."""
    inputs, targets = tokens[:-1], tokens[1:]
    x = params["embed"][inputs]
    if model["mup_enabled"]:
        x = x * np.sqrt(model["hidden_size"])
    counts = {}
    for i in range(len(model["layer_types"])):
        prefix = f"layer{i}."
        p = {n[len(prefix):]: v for n, v in params.items() if n.startswith(prefix)}
        x, c = jax.checkpoint(lambda x, p, i=i: _layer(x, p, i, model, quant))(x, p)
        if c is not None:
            counts[prefix + "moe.router_bias"] = c
    x = _rms(x, params["final_norm.scale"], model["rms_norm_eps"])
    logits = quant(x) @ quant(params["lm_head"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked), counts


def loss_sum(params, block, weights, model: dict, quant: Callable = lambda a: a):
    """Sum over the block's sequences of weight x the sequence's loss, and
    beside it the weighted counts of assignments by the ruled leaf's name."""
    def one(carry, row):
        tokens, w = row
        loss, counts = sequence_loss(params, tokens, model, quant)
        total, acc = carry
        return (total + w * loss,
                {n: acc[n] + w * c for n, c in counts.items()}), None
    zero = {n: jnp.zeros((model["experts_published"],), jnp.float32) for n in ruled_leaves(model)}
    (total, counts), _ = jax.lax.scan(one, (jnp.zeros((), jnp.float32), zero),
                                      (block["tokens"], weights))
    return total, counts


def after_update(params, aux, model: dict):
    """The rule: the bias of an expert that got fewer assignments than the
    mean goes up by ``load_balance_coeff``, of one that got more goes down."""
    moved = {n: params[n] + model["load_balance_coeff"] * jnp.sign(jnp.mean(c) - c)
             for n, c in aux.items()}
    return dict(params, **moved)
