"""Operations one training step of the nemotron_h family (Nemotron-3-Nano's
share) needs, from the configuration and the traffic: multiply-accumulates
counted once in the forward pass, a training step three passes' worth at two
FLOPs a multiply-accumulate.

What is counted is what the mathematics needs, whatever implements it: the
Mamba-2 mixers' projections and convolution, and their scan as the four
products of a chunk of ``chunk_size`` positions over full Q x Q tiles
(``ssd_macs``); attention over the (query, key) pairs a causal sequence
keeps; the routed experts (two products each, relu²) at the expectation
tokens x experts per token x held / published; the shared expert; the router
over all the published experts. Nothing recomputed, no dead tile, no norm,
gate, softmax or loss, and not the recurrence between chunks or the scan's
elementwise work. The embedding is a lookup.

Beside the whole step's count, the counts of the parts that the family's
readers divide by their time: the routed experts' products
(``experts_flops_per_step``) and the scan's (``ssd_flops_per_step``).
"""
from __future__ import annotations

# what one pair and a balanced router cost is the afmoe family's
from benchmark.flops.afmoe import attention_pair_macs, expected_assignments, live_pairs


def _sizes(config: dict, traffic: dict):
    return (config["model"], traffic["seq_len"],
            traffic["per_chip_batch"] * traffic["chips"])


def _count(model: dict, kind: str) -> int:
    return sum(k == kind for k in model["layer_types"])


def expert_macs(model: dict) -> int:
    """One relu² expert for one token: up and down."""
    return 2 * model["hidden_size"] * model["moe_intermediate_size"]


def ssd_macs(model: dict, t: int) -> int:
    """The scan's four products over one sequence of t positions, one
    layer: per chunk of Q positions C·Bᵀ once a group (G Q² N), and per head
    that times Δ·x (Q² P), the chunk's state Bᵀ·(Δ·x) (Q N P) and C·S_prev
    (Q N P), over full Q x Q tiles."""
    q, n, p = model["chunk_size"], model["ssm_state_size"], model["mamba_head_dim"]
    chunks = -(-t // q)
    return chunks * (model["n_groups"] * q * q * n
                     + model["mamba_num_heads"] * (q * q * p + 2 * q * n * p))


def mamba_token_macs(model: dict) -> int:
    """A Mamba-2 mixer's projections and convolution for one token."""
    d, h = model["hidden_size"], model["mamba_num_heads"]
    inner = h * model["mamba_head_dim"]
    xbc = inner + 2 * model["n_groups"] * model["ssm_state_size"]
    return d * (inner + xbc + h) + xbc * model["conv_kernel"] + inner * d


def forward_macs_per_step(config: dict, traffic: dict) -> float:
    model, t, sequences = _sizes(config, traffic)
    tokens = sequences * t
    d = model["hidden_size"]
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    mamba, moe, attn = (_count(model, k) for k in ("mamba", "moe", "full_attention"))
    per_token = mamba * mamba_token_macs(model)
    per_token += moe * (d * model["experts_published"]
                        + 2 * d * model["moe_shared_expert_intermediate_size"])
    per_token += attn * (d * (q + 2 * kv) + q * d) + d * model["vocab_held"]
    routed = moe * expected_assignments(model, tokens) * expert_macs(model)
    scores = 2 * attention_pair_macs(model) * sequences * attn * live_pairs(t, None)
    scan = sequences * mamba * ssd_macs(model, t)
    return tokens * per_token + routed + scores + scan


def train_flops_per_step(config: dict, traffic: dict) -> float:
    return 3 * 2 * forward_macs_per_step(config, traffic)


def experts_flops_per_step(config: dict, traffic: dict) -> float:
    """The routed experts' two products at the expected assignments,
    forward and backward."""
    model, t, sequences = _sizes(config, traffic)
    return (3 * 2 * _count(model, "moe") * expected_assignments(model, sequences * t)
            * expert_macs(model))


def ssd_flops_per_step(config: dict, traffic: dict) -> float:
    """The scan's four chunk products of every Mamba layer, forward and
    backward."""
    model, t, sequences = _sizes(config, traffic)
    return 3 * 2 * sequences * _count(model, "mamba") * ssd_macs(model, t)
