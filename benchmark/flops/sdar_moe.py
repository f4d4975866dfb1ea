"""Operations one training step of the sdar_moe family (SDAR-30B-A3B's share)
needs, from the configuration and the traffic: multiply-accumulates counted
once in the forward pass, a training step three passes' worth at two FLOPs a
multiply-accumulate.

What is counted is what the mathematics needs, whatever implements it. The
block-diffusion objective runs every layer over 2L positions a sequence (a
noisy and a clean copy of its L ids); attention over the (query, key) pairs
its mask keeps, L^2 + L B a sequence a layer, whatever order the program
lays the copies in and whichever tiles its kernels walk; the head at the
masked positions alone, in expectation L (1 + eps) / 2 a sequence (the
others weigh nought in the loss); the routed experts at the expectation
positions x experts per token x held / published; the router over all the
published experts; nothing recomputed, no dead block, no norm, softmax or
loss (under one percent). The embedding is a lookup.

Beside the whole step's count, the counts of the two kernels' own operations
(``flash_flops_per_step``, ``experts_flops_per_step``), which the family's
two readers divide by the kernels' time.
"""
from __future__ import annotations

# what one pair, one expert and a balanced router cost is the afmoe family's
from benchmark.flops.afmoe import attention_pair_macs, expected_assignments, expert_macs


def kept_pairs(length: int, block: int) -> int:
    """(query, key) pairs the block-diffusion mask keeps over the 2 x length
    positions of one sequence: clean -> clean sum_b B x B (b + 1), noisy ->
    clean sum_b B x B b, noisy -> noisy length x B."""
    blocks = length // block
    clean_clean = block * block * blocks * (blocks + 1) // 2
    noisy_clean = block * block * blocks * (blocks - 1) // 2
    return clean_clean + noisy_clean + length * block


def _sizes(config: dict, traffic: dict):
    return (config["model"], traffic["seq_len"],
            traffic["per_chip_batch"] * traffic["chips"])


def expected_masked(model: dict, length: int) -> float:
    """Positions of a sequence that are masked, in expectation: a block's
    level is uniform on [eps, 1] and an id is masked with that probability."""
    return length * (1.0 + model["noise_eps"]) / 2.0


def _score_macs(model: dict, length: int, sequences: int) -> int:
    """Both products over the kept pairs (q k^T and p v), every layer."""
    return (2 * attention_pair_macs(model) * sequences * len(model["layer_types"])
            * kept_pairs(length, model["block_length"]))


def forward_macs_per_step(config: dict, traffic: dict) -> float:
    model, length, sequences = _sizes(config, traffic)
    positions = sequences * 2 * length
    d = model["hidden_size"]
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    layers = len(model["layer_types"])
    per_position = layers * (d * (q + 2 * kv) + q * d       # q, k, v; output
                             + d * model["experts_published"])   # router
    routed = layers * expected_assignments(model, positions) * expert_macs(model)
    head = sequences * expected_masked(model, length) * d * model["vocab_held"]
    return positions * per_position + routed + head + _score_macs(model, length, sequences)


def train_flops_per_step(config: dict, traffic: dict) -> float:
    return 3 * 2 * forward_macs_per_step(config, traffic)


def flash_flops_per_step(config: dict, traffic: dict) -> float:
    """The attention core alone: two products forward (q k^T, p v) and four
    backward (dV, dP, dQ, dK) over the kept pairs of every layer."""
    model, length, sequences = _sizes(config, traffic)
    return 3 * 2 * _score_macs(model, length, sequences)


def experts_flops_per_step(config: dict, traffic: dict) -> float:
    """The routed experts' three products at the expected assignments of the
    2L positions a sequence, forward and backward."""
    model, length, sequences = _sizes(config, traffic)
    return (3 * 2 * len(model["layer_types"])
            * expected_assignments(model, sequences * 2 * length) * expert_macs(model))
