"""Operations one training step of the glm4_moe_lite family (GLM-4.7-Flash's
share) needs, from the configuration and the traffic: multiply-accumulates
counted once in the forward pass, a training step three passes' worth at two
FLOPs a multiply-accumulate.

What is counted is what the mathematics needs, whatever implements it: the
latent attention of every layer and of every multi-token-prediction block
(``mla_token_macs``: both latents' down-projections, the query and
key/value up-projections, the output projection) and its core over the
(query, key) pairs a causal sequence keeps, q·k over ``qk_nope_head_dim +
qk_rope_head_dim`` and p·v over ``v_head_dim`` a head; the leading dense
SwiGLU layers; in every expert layer (the prediction blocks' among them)
the router over all the published experts, the shared expert, and the
routed experts at the expectation tokens x experts per token x held /
published; each prediction block's W_eh; the head once for the main stack
and once a prediction block. Nothing recomputed, no dead tile, no norm,
rotary, softmax or loss. The embedding is a lookup.

Beside the whole step's count, the counts of the parts that the readers
divide by their time: the routed experts' products
(``experts_flops_per_step``) and the attention core's
(``flash_flops_per_step``).
"""
from __future__ import annotations

# what a balanced router sends here and a causal sequence keeps is afmoe's
from benchmark.flops.afmoe import expected_assignments, expert_macs, live_pairs


def _sizes(config: dict, traffic: dict):
    return (config["model"], traffic["seq_len"],
            traffic["per_chip_batch"] * traffic["chips"])


def _depth(model: dict) -> int:
    return model["num_nextn_predict_layers"]


def attention_layers(model: dict) -> int:
    """Every main layer and every prediction block runs latent attention."""
    return len(model["layer_types"]) + _depth(model)


def expert_layers(model: dict) -> int:
    """The main layers after the dense ones, and every prediction block."""
    return len(model["layer_types"]) - model["num_dense_layers"] + _depth(model)


def mla_token_macs(model: dict) -> int:
    """Latent attention's projections for one token: W_qa, W_qb, W_kva,
    W_kvb and W_o."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    nope, rope, dv = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    rq, rkv = model["q_lora_rank"], model["kv_lora_rank"]
    return (d * rq + rq * h * (nope + rope) + d * (rkv + rope)
            + rkv * h * (nope + dv) + h * dv * d)


def attention_pair_macs(model: dict) -> int:
    """Multiply-accumulates one (query, key) pair costs in both products,
    all heads together: q·k over nope + rope, p·v over v_head_dim."""
    h = model["num_attention_heads"]
    return h * (model["qk_nope_head_dim"] + model["qk_rope_head_dim"] + model["v_head_dim"])


def forward_macs_per_step(config: dict, traffic: dict) -> float:
    model, t, sequences = _sizes(config, traffic)
    tokens = sequences * t
    d = model["hidden_size"]
    per_token = attention_layers(model) * mla_token_macs(model)
    per_token += model["num_dense_layers"] * 3 * d * model["intermediate_size"]
    per_token += expert_layers(model) * (d * model["experts_published"]
                                         + model["num_shared_experts"] * expert_macs(model))
    per_token += _depth(model) * 2 * d * d                     # W_eh
    per_token += (1 + _depth(model)) * d * model["vocab_held"]  # the head, each call
    routed = expert_layers(model) * expected_assignments(model, tokens) * expert_macs(model)
    scores = attention_pair_macs(model) * sequences * attention_layers(model) * live_pairs(t, None)
    return tokens * per_token + routed + scores


def train_flops_per_step(config: dict, traffic: dict) -> float:
    return 3 * 2 * forward_macs_per_step(config, traffic)


def flash_flops_per_step(config: dict, traffic: dict) -> float:
    """The attention core alone: q k^T and p v forward, four products
    backward (dV, dP, dQ, dK), over the causal pairs of every attention
    layer, the prediction blocks' among them."""
    model, t, sequences = _sizes(config, traffic)
    return (3 * 2 * attention_pair_macs(model) * sequences * attention_layers(model)
            * live_pairs(t, None))


def experts_flops_per_step(config: dict, traffic: dict) -> float:
    """The routed experts' three products at the expected assignments of
    every expert layer (the prediction blocks' among them), forward and
    backward."""
    model, t, sequences = _sizes(config, traffic)
    return (3 * 2 * expert_layers(model) * expected_assignments(model, sequences * t)
            * expert_macs(model))
