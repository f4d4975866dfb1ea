"""Device milliseconds a step under the ``moe`` scope of the afmoe
family's step, forward, recomputed and backward together (self times of
device 0's operations by ``jax.named_scope``, over the steps in the traced
window). A program that has no such scope: nothing to read, nothing returned.
Layer: model. Moves ``examples_per_s``."""
from benchmark.flops import afmoe


def read(run: dict):
    return afmoe.scope_ms_a_step(run, "moe")
