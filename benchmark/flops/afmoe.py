"""Operations one training step of the afmoe family (Trinity-Mini's share)
needs, from the configuration and the traffic: multiply-accumulates counted
once in the forward pass, a training step three passes' worth at two FLOPs a
multiply-accumulate.

What is counted is what the mathematics needs, whatever implements it:
attention over the unmasked (query, key) pairs only, sum_i min(i + 1, W) a
sequence; the routed experts at the expectation tokens x experts per token x
held / published (what a balanced router sends to the experts held here);
the router over all the published experts; nothing recomputed, no dead
block, no norm, softmax, gate or loss (under one percent). The embedding is
a lookup.

Beside the whole step's count, the counts of the two kernels' own
operations (``flash_flops_per_step``, ``experts_flops_per_step``) and the
reading of a ``jax.named_scope`` in a reduced trace that the family's
per-layer readers share (``scope_seconds``).
"""
from __future__ import annotations

import re
from typing import Optional


def live_pairs(t: int, window: Optional[int]) -> int:
    """(query, key) pairs a causal sequence of t tokens keeps: query i sees
    keys j <= i, with a window also j > i - window."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def _pairs_by_layer(model: dict, t: int):
    return [live_pairs(t, model["sliding_window"] if kind == "sliding_attention" else None)
            for kind in model["layer_types"]]


def _sizes(config: dict, traffic: dict):
    t = traffic["seq_len"]
    sequences = traffic["per_chip_batch"] * traffic["chips"]
    return config["model"], t, sequences


def attention_pair_macs(model: dict) -> int:
    """Multiply-accumulates one product over one (query, key) pair costs,
    all query heads together."""
    return model["num_attention_heads"] * model["head_dim"]


def expert_macs(model: dict) -> int:
    """One SwiGLU expert for one token: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def expected_assignments(model: dict, tokens: int) -> float:
    """Assignments a balanced router sends to the experts held here."""
    lo, hi = model["experts_held"]
    return tokens * model["num_experts_per_tok"] * (hi - lo) / model["experts_published"]


def forward_macs_per_step(config: dict, traffic: dict) -> float:
    model, t, sequences = _sizes(config, traffic)
    tokens = sequences * t
    d = model["hidden_size"]
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    layers = len(model["layer_types"])
    dense = model["num_dense_layers"]
    per_token = layers * (d * (2 * q + 2 * kv) + q * d)       # q, gate, k, v; output
    per_token += dense * 3 * d * model["intermediate_size"]
    per_token += (layers - dense) * (d * model["experts_published"]   # router
                                     + model["num_shared_experts"] * expert_macs(model))
    per_token += d * model["vocab_held"]
    routed = (layers - dense) * expected_assignments(model, tokens) * expert_macs(model)
    scores = 2 * attention_pair_macs(model) * sequences * sum(_pairs_by_layer(model, t))
    return tokens * per_token + routed + scores


def train_flops_per_step(config: dict, traffic: dict) -> float:
    return 3 * 2 * forward_macs_per_step(config, traffic)


def flash_flops_per_step(config: dict, traffic: dict) -> float:
    """The attention core alone: two products forward (q k^T, p v) and four
    backward (dV, dP, dQ, dK) over the live pairs of every layer."""
    model, t, sequences = _sizes(config, traffic)
    return 6 * 2 * attention_pair_macs(model) * sequences * sum(_pairs_by_layer(model, t))


def experts_flops_per_step(config: dict, traffic: dict) -> float:
    """The routed experts' three products at the expected assignments,
    forward and backward."""
    model, t, sequences = _sizes(config, traffic)
    layers = len(model["layer_types"]) - model["num_dense_layers"]
    return 3 * 2 * layers * expected_assignments(model, sequences * t) * expert_macs(model)


def scope_ms_a_step(run: dict, *components: str) -> Optional[float]:
    """Device milliseconds a step under a scope, for a reader's ``run``;
    None where there is no trace, no step or no such scope."""
    t = run["trace"]
    seconds = t and t["steps"] and scope_seconds(t, *components)
    return 1e3 * seconds / t["steps"] if seconds else None


def scope_seconds(reduced: dict, *components: str) -> Optional[float]:
    """Self seconds of device 0's operations whose scope path holds every
    one of ``components``, each as a path component of its own or wrapped by
    the transforms of the backward pass (``transpose(jvp(moe))``), so that a
    scope's forward, recomputed and backward operations are all read; None
    where no operation does."""
    wanted = [re.compile(r"(?:\w+\()*" + re.escape(c) + r"\)*") for c in components]
    found = [s for path, s in reduced["scope_s"].items()
             if all(any(w.fullmatch(part) for part in path.split("/")) for w in wanted)]
    return sum(found) if found else None
