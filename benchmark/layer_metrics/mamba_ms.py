"""Device milliseconds a step under the ``mamba`` scope of a hybrid family's
step (the block's norm, the Mamba-2 mixer and the residual add), forward,
recomputed and backward together (self times of device 0's operations by
``jax.named_scope``, over the steps in the traced window). A program that
has no such scope: nothing to read, nothing returned.
Layer: model. Moves ``examples_per_s``."""
from benchmark.flops import afmoe


def read(run: dict):
    return afmoe.scope_ms_a_step(run, "mamba")
