"""GLM-4.7-Flash (``model_type: glm4_moe_lite``) as a plain float32
``jax.numpy`` function: one chip's share of a deployment in which eight
chips share each layer.

A causal decoder of pre-norm layers, ``x <- x + MLA(RMSNorm(x))`` then
``x <- x + FFN(RMSNorm(x))``, no post-norms, no embedding multiplier, no
biases:

* MLA, latent attention in its plain (training) form: ``c_q =
  RMSNorm(u W_qa)`` (rank ``q_lora_rank``), ``q = c_q W_qb`` split per head
  into ``q_n`` (``qk_nope_head_dim``) and ``q_r`` (``qk_rope_head_dim``);
  ``[c_kv | k_r] = u W_kva``, ``c_kv`` RMS-normed (rank ``kv_lora_rank``),
  ``k_r`` ONE rotary key a token that every head shares; ``[k_n | v] =
  c_kv W_kvb`` per head; rotary positions (rotate-half, theta
  ``rope_theta``) on ``q_r`` and ``k_r`` alone; per head ``q = [q_n ;
  q_r]``, ``k = [k_n ; k_r]``; ``softmax(q k^T / sqrt(d_n + d_r) +
  causal) v``; ``o W_o``;
* the leading ``num_dense_layers`` layers: a SwiGLU feed-forward of
  ``intermediate_size``; the rest: sigmoid scores over all the published
  experts in float32, the top ``num_experts_per_tok`` by score plus a bias
  that takes part in the choice only, weights the chosen scores over their
  sum times ``route_scale``; SwiGLU experts of the contiguous range
  ``experts_held`` alone (what the absent experts would add is left out,
  and that partial result goes on) and a shared SwiGLU expert added
  unweighted.

After the layers an RMS norm and an untied head over the ``vocab_held`` rows
held here. Then ``num_nextn_predict_layers`` multi-token-prediction blocks
(DeepSeek-V3, arXiv:2412.19437 section 2.2): block k reads at position i
``W_eh [RMSNorm_e(Emb(t_{i+k+1})) ; RMSNorm_h(h_i)]``, h the main stack's
last hidden state before its final norm for k = 0 (the block before's
output after), runs one layer of the expert kind causally over the same
positions, an RMS norm and the SHARED head, and predicts ``t_{i+k+2}``. A
sequence's loss is the mean over its T positions of the next token's
cross-entropy plus ``mtp_loss_weight`` times the blocks' mean of
(1/T) Σ over the positions whose id exists of theirs. After the optimizer's
update a rule moves each router's bias against the step's load
(arXiv:2408.15664), the prediction blocks' among them.

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

# the batch, the decay, the rule and the parts that are Trinity's already:
# RMS norm, rotate-half rotary, a SwiGLU, the sigmoid-bias route and the
# expert layer of SwiGLU experts with a shared one
from benchmark.reference.afmoe import (  # noqa: F401 (what follow.py and the tests read)
    _moe, _rms, _rotary, _swiglu, after_update, decayed, examples, route)

#: whole sequences to a block, per device
EXAMPLE_BLOCK = 1
#: queries to a block of the attention (scores are [heads, block, keys])
QUERY_BLOCK = 256
#: what the program fixes in its code, or does not hold at all: the
#: published counts beside the counts held here, and the family's constants
FIXED_IN_CODE = {"model": ["vocab_published", "layers_published", "hidden_act",
                           "score_func", "norm_topk_prob", "router_dtype",
                           "tie_word_embeddings", "rope_pairing", "mtp_input_order",
                           "attention_bias"],
                 "optimizer": ["b1", "b2", "eps"]}
HELD_ELSEWHERE = {"seq_len": ["data", "seq_len"],
                  "experts_published": ["model", "num_experts"]}
#: leaves that only the rule moves: the router's bias of every expert layer
#: and prediction block (a layer that has none is not among the leaves, and
#: the name is idle)
RULED = tuple(f"layer{i}.moe.router_bias" for i in range(128)) + tuple(
    f"mtp{k}.moe.router_bias" for k in range(8))
#: the embedding's start, and how far a router's columns stand off their
#: period's pattern (init_params)
EMBEDDING_STD = 1.0
ROUTER_JITTER = 0.1


def _expert_layers(model: dict) -> List[str]:
    """The prefixes of the layers that route: the main stack's after the
    dense ones, then the prediction blocks'."""
    return ([f"layer{i}." for i in range(model["num_dense_layers"],
                                         len(model["layer_types"]))]
            + [f"mtp{k}." for k in range(model["num_nextn_predict_layers"])])


def ruled_leaves(model: dict) -> List[str]:
    return [p + "moe.router_bias" for p in _expert_layers(model)]


def _layer_shapes(model: dict, p: str, dense: bool) -> Dict[str, tuple]:
    d, h = model["hidden_size"], model["num_attention_heads"]
    nope, rope, dv = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    rq, rkv = model["q_lora_rank"], model["kv_lora_rank"]
    out = {p + "input_norm.scale": (d,), p + "attn.q_a_proj": (d, rq),
           p + "attn.q_a_norm.scale": (rq,), p + "attn.q_b_proj": (rq, h * (nope + rope)),
           p + "attn.kv_a_proj": (d, rkv + rope), p + "attn.kv_a_norm.scale": (rkv,),
           p + "attn.kv_b_proj": (rkv, h * (nope + dv)), p + "attn.o_proj": (h * dv, d),
           p + "pre_mlp_norm.scale": (d,)}
    if dense:
        f = model["intermediate_size"]
        out.update({p + "mlp.gate": (d, f), p + "mlp.up": (d, f), p + "mlp.down": (f, d)})
    else:
        lo, hi = model["experts_held"]
        m = model["moe_intermediate_size"]
        s = m * model["num_shared_experts"]
        out.update({p + "moe.router": (d, model["experts_published"]),
                    p + "moe.router_bias": (model["experts_published"],),
                    p + "moe.experts.gate": (hi - lo, d, m),
                    p + "moe.experts.up": (hi - lo, d, m),
                    p + "moe.experts.down": (hi - lo, m, d),
                    p + "moe.shared.gate": (d, s), p + "moe.shared.up": (d, s),
                    p + "moe.shared.down": (s, d)})
    return out


def _shapes(model: dict) -> Dict[str, tuple]:
    d = model["hidden_size"]
    out = {"embed": (model["vocab_held"], d)}
    for i in range(len(model["layer_types"])):
        out.update(_layer_shapes(model, f"layer{i}.", i < model["num_dense_layers"]))
    out.update({"final_norm.scale": (d,), "lm_head": (d, model["vocab_held"])})
    for k in range(model["num_nextn_predict_layers"]):
        p = f"mtp{k}."
        out.update({p + "enorm.scale": (d,), p + "hnorm.scale": (d,),
                    p + "eh_proj": (2 * d, d)})
        out.update(_layer_shapes(model, p, False))
        out[p + "norm.scale"] = (d,)
    return out


def init_params(key, model: dict) -> Dict[str, jnp.ndarray]:
    """Fan-in scaled normal matrices, those that close a residual branch
    (attention's ``o_proj``, every ``down``) smaller by 1/sqrt(2 x layers),
    the prediction blocks counted among the layers; every norm's scale at
    1; the routers' biases 0. Two leaves start as in nemotron_h's reference
    and for its reasons (the configuration's ``assumed``):

    * the embedding at 1 (no multiplier): the stream starts as the tokens'
      own and not as the first branch's output;
    * a router's columns in periods of the experts held here (8): column e
      is column e mod 8 of a fan-in normal matrix plus a tenth of another,
      so the 64 experts are eight copies of one chip's range, a token's
      four choices fall on four of the eight chips, at most one on each for
      most tokens, and the held range's load is a half of the tokens
      whatever the seed, where with independent columns which experts the
      frequent ids favour, and whether they are held here, is the seed's."""
    params = {}
    blocks = len(model["layer_types"]) + model["num_nextn_predict_layers"]
    closing = 1.0 / np.sqrt(2.0 * blocks)
    lo, hi = model["experts_held"]
    period = hi - lo
    for i, (name, shape) in enumerate(_shapes(model).items()):
        k = jax.random.fold_in(key, i)
        if name.endswith(".scale"):
            params[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith("router_bias"):
            params[name] = jnp.zeros(shape, jnp.float32)
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
        elif name.endswith(("_proj", "lm_head", "moe.router")) or \
                ".mlp." in name or ".shared." in name:
            path += "/kernel"
        out[name] = path
    return out


def _attention_core(q, k, v, quant: Callable):
    """softmax(q k^T / sqrt(q's head size) + causal) v for one sequence, in
    blocks of queries; q, k [T, H, d_qk], v [T, H, d_v]."""
    t, h, dqk = q.shape
    blk = min(QUERY_BLOCK, t)
    while t % blk:
        blk -= 1
    qb = q.reshape(t // blk, blk, h, dqk)
    k_pos = jnp.arange(t)

    @jax.checkpoint
    def one(args):
        qi, start = args
        s = jnp.einsum("qhd,thd->hqt", quant(qi), quant(k)) / np.sqrt(dqk)
        seen = k_pos[None, :] <= (start + jnp.arange(blk))[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thd->qhd", quant(p), quant(v))
    out = jax.lax.map(one, (qb, jnp.arange(0, t, blk)))
    return out.reshape(t, -1)


def latent_attention(x, p: Dict[str, jnp.ndarray], model: dict, quant: Callable):
    """MLA over one sequence x [T, d] (the module docstring)."""
    t, eps, theta = x.shape[0], model["rms_norm_eps"], model["rope_theta"]
    h, nope, rkv = model["num_attention_heads"], model["qk_nope_head_dim"], model["kv_lora_rank"]
    c_q = _rms(quant(x) @ quant(p["attn.q_a_proj"]), p["attn.q_a_norm.scale"], eps)
    q = (quant(c_q) @ quant(p["attn.q_b_proj"])).reshape(t, h, -1)
    c_kv = quant(x) @ quant(p["attn.kv_a_proj"])
    k_r = _rotary(c_kv[:, None, rkv:], theta)
    kv = (quant(_rms(c_kv[:, :rkv], p["attn.kv_a_norm.scale"], eps))
          @ quant(p["attn.kv_b_proj"])).reshape(t, h, -1)
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, (t, h, k_r.shape[-1]))],
                        axis=-1)
    o = _attention_core(q, k, kv[..., nope:], quant)
    return quant(o) @ quant(p["attn.o_proj"])


def _layer(x, p: Dict[str, jnp.ndarray], dense: bool, model: dict, quant: Callable):
    eps = model["rms_norm_eps"]
    x = x + latent_attention(_rms(x, p["input_norm.scale"], eps), p, model, quant)
    m = _rms(x, p["pre_mlp_norm.scale"], eps)
    if dense:
        return x + _swiglu(m, p["mlp.gate"], p["mlp.up"], p["mlp.down"], quant), None
    f, counts = _moe(m, p, model, quant)
    return x + f, counts


def _prefixed(params, prefix: str):
    return {n[len(prefix):]: v for n, v in params.items() if n.startswith(prefix)}


def _head_nll(x, head, targets, quant: Callable):
    """Per position: logsumexp(x W) − (x W)[target]; the logits are made
    again in the backward pass."""
    @jax.checkpoint
    def nll(x, head):
        logits = quant(x) @ quant(head)
        picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked
    return nll(x, head)


def sequence_loss(params, tokens, model: dict, quant: Callable = lambda a: a):
    """One sequence [T + 1]: the mean over its T positions of the next
    token's cross-entropy plus ``mtp_loss_weight`` times the prediction
    blocks' mean loss, and per expert layer the counts of assignments."""
    eps = model["rms_norm_eps"]
    inputs, targets = tokens[:-1], tokens[1:]
    t = inputs.shape[0]
    x = params["embed"][inputs]
    counts = {}
    for i in range(len(model["layer_types"])):
        prefix = f"layer{i}."
        dense = i < model["num_dense_layers"]
        x, c = jax.checkpoint(lambda x, p, dense=dense: _layer(x, p, dense, model, quant))(
            x, _prefixed(params, prefix))
        if c is not None:
            counts[prefix + "moe.router_bias"] = c
    head = params["lm_head"]
    loss = jnp.mean(_head_nll(_rms(x, params["final_norm.scale"], eps), head, targets, quant))
    depth = model["num_nextn_predict_layers"]
    h, further = x, 0.0
    for k in range(depth):
        prefix = f"mtp{k}."
        p = _prefixed(params, prefix)
        # the id k + 1 on read at each position and the id k + 2 on
        # predicted; past the row's end an id 0 stands in and weighs 0
        ids = jnp.pad(tokens[k + 1:], (0, k + 1))
        kept = (jnp.arange(t) + k + 2 <= t).astype(jnp.float32)

        def block(h, p, ids=ids):
            u = jnp.concatenate([_rms(params["embed"][ids[:t]], p["enorm.scale"], eps),
                                 _rms(h, p["hnorm.scale"], eps)], axis=-1)
            y, c = _layer(quant(u) @ quant(p["eh_proj"]), p, False, model, quant)
            return y, c
        h, c = jax.checkpoint(block)(h, p)
        counts[prefix + "moe.router_bias"] = c
        nll = _head_nll(_rms(h, p["norm.scale"], eps), head, ids[1:t + 1], quant)
        further = further + jnp.sum(kept * nll) / t
    if depth:
        loss = loss + model["mtp_loss_weight"] * further / depth
    return loss, counts


def loss_sum(params, block, weights, model: dict, quant: Callable = lambda a: a):
    """Sum over the block's sequences of weight x the sequence's loss, and
    beside it the weighted counts of assignments by the ruled leaf's name."""
    def one(carry, row):
        tokens, w = row
        loss, counts = sequence_loss(params, tokens, model, quant)
        total, acc = carry
        return (total + w * loss,
                {n: acc[n] + w * c for n, c in counts.items()}), None
    zero = {n: jnp.zeros((model["experts_published"],), jnp.float32)
            for n in ruled_leaves(model)}
    (total, counts), _ = jax.lax.scan(one, (jnp.zeros((), jnp.float32), zero),
                                      (block["tokens"], weights))
    return total, counts
