"""Device milliseconds a step under the ``mtp`` scope (``models/
transformer.CausalDecoder.predict_further``): the multi-token-prediction
blocks' embedding of the next ids, both norms, W_eh, the block (its
attention and expert layer) and its head's loss chunks; forward, recomputed
and backward together (self times of device 0's operations by
``jax.named_scope``, over the steps in the traced window). The block's
attention, experts and head are read by ``attention_ms``, ``moe_ms`` and
``lm_head_ms`` too. A program that has no such scope: nothing to read,
nothing returned. Layer: model. Moves ``examples_per_s``."""
from benchmark.flops import afmoe


def read(run: dict):
    return afmoe.scope_ms_a_step(run, "mtp")
