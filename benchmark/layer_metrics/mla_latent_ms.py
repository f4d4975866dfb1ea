"""Device milliseconds a step under ``attention/latent`` of a family whose
attention is latent (``models/transformer.LatentAttention``): both latents'
down-projections and RMS norms, the query and key/value up-projections, the
rotary key split off and broadcast to the heads, and the rotary; forward,
recomputed and backward together (self times of device 0's operations by
``jax.named_scope``, over the steps in the traced window). A program that
has no such scope: nothing to read, nothing returned.
Layer: model. Moves ``examples_per_s``."""
from benchmark.flops import afmoe


def read(run: dict):
    return afmoe.scope_ms_a_step(run, "attention", "latent")
