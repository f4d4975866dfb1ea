"""Device milliseconds a step under ``attention`` and outside the kernels:
the scope ``attention`` without what lies under ``attention`` and ``core``
(the flash kernels or their twin with their casts, reshapes and block
tables), forward, recomputed and backward together. What is left is the
input norm, the projections, the head norms, the rotary (scope ``rotary``),
the gate, the output projection, the post-norm and the residual add: the
part of attention that per-block recomputation runs twice. A program that
has no ``core`` scope (the parent of the PR that named it, or a step
fetched from its compile cache) leaves the metric out: ``attention`` alone
would read the kernels in. Layer: model. Moves ``examples_per_s``."""
from benchmark.flops import afmoe


def read(run: dict):
    whole = afmoe.scope_ms_a_step(run, "attention")
    core = afmoe.scope_ms_a_step(run, "attention", "core")
    return None if whole is None or core is None else whole - core
