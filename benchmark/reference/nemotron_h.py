"""NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type: nemotron_h``) as a plain
float32 ``jax.numpy`` function: one chip's share of a deployment in which
sixteen chips share each layer.

A hybrid decoder of blocks that hold ONE mixer each, ``x <- x +
mixer(RMSNorm(x))``, the block's kind choosing it (the published
``hybrid_override_pattern``: M, E, *):

* ``mamba`` (M), the Mamba-2 mixer: ``[z | xBC | dt] = u W_in``; xBC through
  a causal depthwise convolution of width K with bias, zero before the
  sequence's start, and SiLU; x (H, P), B (G, N), C (G, N) out of it, head
  h reading group h·G/H; Δ = softplus(dt + dt_bias), unclamped; A =
  −exp(A_log); then, position by position with S_0 = 0,

      S_t = exp(Δ_t A) S_{t−1} + Δ_t x_t ⊗ B_t,    y_t = S_t C_t + D x_t;

  ``y <- w ⊙ GroupRMS(y ⊙ SiLU(z))`` over G groups of channels; ``y W_out``;
* ``moe`` (E): sigmoid scores over all 128 experts in float32, the top 6 by
  score plus a bias that takes part in the choice only, weights renormalised
  over the six and scaled by 2.5; relu² experts ``down(relu(x up)²)`` of the
  contiguous range ``experts_held`` alone (what the absent experts would add
  is left out, and that partial result goes on) and a relu² shared expert
  added unweighted;
* ``full_attention`` (*): 32 query heads over 2 key/value heads of 128,
  causal, no bias, no per-head norms, no gate and no positional term (the
  published modelling code applies none in its attention layers).

After the blocks an RMS norm and an untied head over the ``vocab_held`` rows
of the vocabulary held here; the loss is the mean over positions of the next
token's cross-entropy; after the optimizer's update a rule moves each
router's bias against the step's load (arXiv:2408.15664).

The recurrence is walked a position at a time (a ``lax.scan`` over
positions, checkpointed every ``SCAN_SEGMENT`` of them so that its backward
pass keeps a state a segment), NOT in the chunked form the program uses:
the reference is independent of the algorithm under test.
``STATE_RESET_EVERY`` is a fault's hook and no part of the model: set to a
number of positions it zeroes the state at every multiple of it, the scan's
state dropped between chunks.

No kernels, no mixed precision, no sharding, no sorting of tokens; nothing
imported from the program. ``quant`` is the control's hook, applied to both
operands of every matrix product (identity for the reference): the
projections, the recurrence's outer product and its read-out, attention,
experts and head; the router's product is left out of it, as the
configuration states it in float32.
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
#: positions of the recurrence a checkpointed segment holds
SCAN_SEGMENT = 128
#: a fault's hook (module docstring): None in the model
STATE_RESET_EVERY = None
#: what the program fixes in its code, or does not hold at all: the
#: published counts beside the counts held here, and the family's constants
FIXED_IN_CODE = {"model": ["vocab_published", "layers_published", "mlp_hidden_act",
                           "mamba_hidden_act", "score_func", "norm_topk_prob",
                           "router_dtype", "tie_word_embeddings", "time_step_limit",
                           "positional_term"],
                 "optimizer": ["b1", "b2", "eps"]}
HELD_ELSEWHERE = {"seq_len": ["data", "seq_len"],
                  "experts_published": ["model", "num_experts"]}
#: leaves that only the rule moves: the router's bias of every expert layer
#: (a layer that has none is not among the leaves, and the name is idle)
RULED = tuple(f"layer{i}.moe.router_bias" for i in range(128))


def ruled_leaves(model: dict) -> List[str]:
    return [RULED[i] for i, kind in enumerate(model["layer_types"]) if kind == "moe"]


def _shapes(model: dict) -> Dict[str, tuple]:
    d, hd = model["hidden_size"], model["head_dim"]
    q, kv = model["num_attention_heads"] * hd, model["num_key_value_heads"] * hd
    lo, hi = model["experts_held"]
    m, s = model["moe_intermediate_size"], model["moe_shared_expert_intermediate_size"]
    h, g, n = model["mamba_num_heads"], model["n_groups"], model["ssm_state_size"]
    inner = h * model["mamba_head_dim"]
    xbc = inner + 2 * g * n
    out = {"embed": (model["vocab_held"], d)}
    for i, kind in enumerate(model["layer_types"]):
        p = f"layer{i}."
        out[p + "input_norm.scale"] = (d,)
        if kind == "mamba":
            out.update({p + "mamba.in_proj": (d, inner + xbc + h),
                        p + "mamba.conv_weight": (model["conv_kernel"], xbc),
                        p + "mamba.conv_bias": (xbc,), p + "mamba.dt_bias": (h,),
                        p + "mamba.A_log": (h,), p + "mamba.D": (h,),
                        p + "mamba.norm_scale": (inner,), p + "mamba.out_proj": (inner, d)})
        elif kind == "moe":
            out.update({p + "moe.router": (d, model["experts_published"]),
                        p + "moe.router_bias": (model["experts_published"],),
                        p + "moe.experts.up": (hi - lo, d, m),
                        p + "moe.experts.down": (hi - lo, m, d),
                        p + "moe.shared.up": (d, s), p + "moe.shared.down": (s, d)})
        elif kind == "full_attention":
            out.update({p + "attn.q_proj": (d, q), p + "attn.k_proj": (d, kv),
                        p + "attn.v_proj": (d, kv), p + "attn.o_proj": (q, d)})
        else:
            raise ValueError(f"no block of kind {kind!r}")
    out.update({"final_norm.scale": (d,), "lm_head": (d, model["vocab_held"])})
    return out


#: the embedding's start, and how far a router's columns stand off their
#: period's pattern (init_params)
EMBEDDING_STD = 1.0
ROUTER_JITTER = 0.1


def init_params(key, model: dict) -> Dict[str, jnp.ndarray]:
    """Fan-in scaled normal matrices, those that close a block's branch
    (Mamba's and attention's output projections, the experts' ``down``)
    smaller by 1/sqrt(2 x blocks); the norms' scales at 1; the Mamba
    mixer's own leaves as the published initialisation draws them (Δ
    log-uniform on [time_step_min, time_step_max] floored at
    time_step_floor, dt_bias its softplus inverse; A_log = log U[1, 16];
    D = 1; the convolution fan-in normal over its width, its bias 0); the
    routers' biases 0. Two leaves start otherwise, as in sdar_moe's
    reference and for its reasons (the configuration's ``assumed``):

    * the embedding at 1 (no multiplier): the stream starts as the tokens'
      own and not as the first branch's output;
    * a router's columns in periods of the experts held here (8): column e
      is column e mod 8 of a fan-in normal matrix plus a tenth of another,
      so the 128 experts are sixteen copies of one chip's range and a
      token's six choices fall on six chips, at most one on each for nine
      tokens in ten: the held range's load is 6/16 of the tokens whatever
      the seed, where with independent columns which experts the frequent
      ids favour, and whether they are held here, is the seed's. A tenth
      and not a hundredth: the copies' scores then stand further apart than
      the router-bias rule moves them in a run (0.001 a step), which at a
      hundredth swung the held load by a tenth from step to step and the
      step's time with it (PERF.md section 6)."""
    params = {}
    closing = 1.0 / np.sqrt(2.0 * len(model["layer_types"]))
    lo, hi = model["experts_held"]
    period = hi - lo
    for i, (name, shape) in enumerate(_shapes(model).items()):
        k = jax.random.fold_in(key, i)
        leaf = name.rsplit(".", 1)[-1]
        if name.endswith(("scale", ".D")):
            params[name] = jnp.ones(shape, jnp.float32)
        elif leaf in ("router_bias", "conv_bias"):
            params[name] = jnp.zeros(shape, jnp.float32)
        elif leaf == "dt_bias":
            u = jax.random.uniform(k, shape, jnp.float32)
            low, high = np.log(model["time_step_min"]), np.log(model["time_step_max"])
            dt = jnp.maximum(jnp.exp(low + u * (high - low)), model["time_step_floor"])
            params[name] = dt + jnp.log(-jnp.expm1(-dt))
        elif leaf == "A_log":
            params[name] = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
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
            if name.endswith(("out_proj", "o_proj", "down")):
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
        elif name.endswith(("_proj", "lm_head", "moe.router")) or ".shared." in name:
            path += "/kernel"
        out[name] = path
    return out


def examples(batch: Dict[str, np.ndarray], step: int, augment_seed: int):
    """One example is one whole sequence; nothing is drawn per step."""
    del step, augment_seed
    return {"tokens": jnp.asarray(batch["tokens"], jnp.int32)}


def decayed(name: str, leaf) -> bool:
    """The matrices (the convolution's among them); not the norms, the
    embedding, the biases, dt_bias, A_log or D."""
    return leaf.ndim > 1 and name != "embed"


def _rms(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def recurrence(x, dt, a, b, c, quant: Callable = lambda v: v):
    """y [T, H, P] of S_t = exp(dt_t a) S_{t-1} + dt_t x_t ⊗ B_t, y_t = S_t
    C_t, S_0 = 0, one position at a time; x [T, H, P], dt [T, H], a [H],
    b and c [T, G, N]."""
    t, h, p = x.shape
    g, n = b.shape[1:]
    heads = np.arange(h) * g // h

    def one(s, row):
        xt, dtt, bt, ct, pos = row
        if STATE_RESET_EVERY:
            s = jnp.where(pos % STATE_RESET_EVERY == 0, 0.0, s)
        s = jnp.exp(dtt * a)[:, None, None] * s \
            + quant(dtt[:, None] * xt)[:, :, None] * quant(bt[heads])[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", quant(s), quant(ct[heads]))

    @jax.checkpoint
    def segment(s, rows):
        return jax.lax.scan(one, s, rows)
    seg = min(SCAN_SEGMENT, t)
    pad = -t % seg
    rows = [jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1)) for v in (x, dt, b, c)]
    rows = [v.reshape((-1, seg) + v.shape[1:]) for v in rows + [jnp.arange(t + pad)]]
    _, y = jax.lax.scan(segment, jnp.zeros((h, p, n), jnp.float32), tuple(rows))
    return y.reshape(-1, h, p)[:t]


def _mamba(u, p: Dict[str, jnp.ndarray], model: dict, quant: Callable):
    t = u.shape[0]
    h, hp = model["mamba_num_heads"], model["mamba_head_dim"]
    g, n = model["n_groups"], model["ssm_state_size"]
    inner = h * hp
    zxd = quant(u) @ quant(p["mamba.in_proj"])
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:2 * inner + 2 * g * n], zxd[:, 2 * inner + 2 * g * n:]
    k = model["conv_kernel"]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype), xbc])
    conv = p["mamba.conv_bias"] + sum(p["mamba.conv_weight"][i] * padded[i:i + t]
                                      for i in range(k))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(t, h, hp)
    b = xbc[:, inner:inner + g * n].reshape(t, g, n)
    c = xbc[:, inner + g * n:].reshape(t, g, n)
    y = recurrence(x, jax.nn.softplus(dt + p["mamba.dt_bias"]), -jnp.exp(p["mamba.A_log"]),
                   b, c, quant)
    y = (y + p["mamba.D"][:, None] * x).reshape(t, inner)
    y = (y * jax.nn.silu(z)).reshape(t, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + model["rms_norm_eps"])
    y = y.reshape(t, inner) * p["mamba.norm_scale"]
    return quant(y) @ quant(p["mamba.out_proj"])


def _attention_core(q, k, v, quant: Callable):
    """softmax(q k^T / sqrt(hd)) v for one causal sequence, in blocks of
    queries. q [T, H, hd]; k, v [T, KV, hd]; each group of H/KV query heads
    reads one key/value head."""
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
        seen = k_pos[None, :] <= (start + jnp.arange(blk))[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", quant(p), quant(v))
    out = jax.lax.map(one, (qb, jnp.arange(0, t, blk)))
    return out.reshape(t, h * hd)


def _attention(x, p: Dict[str, jnp.ndarray], model: dict, quant: Callable):
    t, hd = x.shape[0], model["head_dim"]
    q = (quant(x) @ quant(p["attn.q_proj"])).reshape(t, -1, hd)
    k = (quant(x) @ quant(p["attn.k_proj"])).reshape(t, -1, hd)
    v = (quant(x) @ quant(p["attn.v_proj"])).reshape(t, -1, hd)
    return quant(_attention_core(q, k, v, quant)) @ quant(p["attn.o_proj"])


def route(x, router, bias, model: dict):
    """(chosen experts [T, k], their weights [T, k]) over all the published
    experts, in float32 whatever the control's precision."""
    s = jax.nn.sigmoid(jnp.dot(x, router, precision="highest"))
    _, sel = jax.lax.top_k(s + jax.lax.stop_gradient(bias), model["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, sel, axis=-1)
    w = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) * model["route_scale"]
    return sel, w


def _relu2(x, up, down, quant: Callable):
    return quant(jnp.square(jax.nn.relu(quant(x) @ quant(up)))) @ quant(down)


def _moe(x, p: Dict[str, jnp.ndarray], model: dict, quant: Callable):
    """Shared expert plus the held experts' part of the routed sum, and how
    many of the sequence's assignments went to each published expert."""
    sel, w = route(x, p["moe.router"], p["moe.router_bias"], model)
    n_exp = model["experts_published"]
    # [T, experts]: a token's weight for each expert, 0 where not chosen
    dense_w = jnp.sum(jax.nn.one_hot(sel, n_exp, dtype=jnp.float32) * w[..., None], axis=1)
    counts = jnp.sum(jax.nn.one_hot(sel, n_exp, dtype=jnp.float32), axis=(0, 1))
    lo, hi = model["experts_held"]
    out = _relu2(x, p["moe.shared.up"], p["moe.shared.down"], quant)

    def one(acc, held):
        up, down, w_e = held
        return acc + w_e[:, None] * _relu2(x, up, down, quant), None
    out, _ = jax.lax.scan(one, out, (p["moe.experts.up"], p["moe.experts.down"],
                                     dense_w[:, lo:hi].T))
    return out, counts


def _block(x, p: Dict[str, jnp.ndarray], kind: str, model: dict, quant: Callable):
    u = _rms(x, p["input_norm.scale"], model["rms_norm_eps"])
    if kind == "mamba":
        return x + _mamba(u, p, model, quant), None
    if kind == "moe":
        f, counts = _moe(u, p, model, quant)
        return x + f, counts
    return x + _attention(u, p, model, quant), None


def sequence_logits(params, inputs, model: dict, quant: Callable = lambda a: a):
    """One sequence of ids [T]: the logits [T, vocab_held], and per expert
    layer the counts of assignments."""
    x = params["embed"][inputs]
    counts = {}
    for i, kind in enumerate(model["layer_types"]):
        prefix = f"layer{i}."
        p = {n[len(prefix):]: v for n, v in params.items() if n.startswith(prefix)}
        x, c = jax.checkpoint(lambda x, p, kind=kind: _block(x, p, kind, model, quant))(x, p)
        if c is not None:
            counts[prefix + "moe.router_bias"] = c
    x = _rms(x, params["final_norm.scale"], model["rms_norm_eps"])
    return quant(x) @ quant(params["lm_head"]), counts


def sequence_loss(params, tokens, model: dict, quant: Callable = lambda a: a):
    """One sequence [T + 1]: the mean over its T positions of the next
    token's cross-entropy, and per expert layer the counts of assignments."""
    inputs, targets = tokens[:-1], tokens[1:]
    logits, counts = sequence_logits(params, inputs, model, quant)
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
    zero = {n: jnp.zeros((model["experts_published"],), jnp.float32)
            for n in ruled_leaves(model)}
    (total, counts), _ = jax.lax.scan(one, (jnp.zeros((), jnp.float32), zero),
                                      (block["tokens"], weights))
    return total, counts


def after_update(params, aux, model: dict):
    """The rule: the bias of an expert that got fewer assignments than the
    mean goes up by ``load_balance_coeff``, of one that got more goes down."""
    moved = {n: params[n] + model["load_balance_coeff"] * jnp.sign(jnp.mean(c) - c)
             for n, c in aux.items()}
    return dict(params, **moved)
