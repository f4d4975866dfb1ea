"""Device milliseconds a step the expert layer's backward walk spends adding
a window's kernel gradients into its float32 accumulators: the self time of
device 0's operations under the scopes ``moe`` and ``carry``
(``models/moe._walk_bwd``), over the steps in the traced window. A program
that has no such scope (the parent of the PR that named the walk's parts, or
a step fetched from its compile cache): nothing to read, nothing returned.
Layer: model. Moves ``examples_per_s``."""
from benchmark.flops import afmoe


def read(run: dict):
    return afmoe.scope_ms_a_step(run, "moe", "carry")
