"""Device milliseconds a step spent computing again what a forward pass had
computed: the self time of device 0's operations whose scope path holds
``rematted_computation``, the component JAX gives what a ``jax.checkpoint``
(``nn.remat`` on the decoder's blocks, the chunked head's loss) runs again
in the backward pass; ``checkpoint`` alone is every operation of such a
backward pass and does not tell them. Operations recomputed to save memory:
the price of ``peak_hbm_gib``. The grouped products made again are not in
it (their kernels carry no path, PERF.md section 7.6). A program that
recomputes nothing: nothing to read, nothing returned.
Layer: step. Moves ``examples_per_s``."""
from benchmark.flops import afmoe


def read(run: dict):
    return afmoe.scope_ms_a_step(run, "rematted_computation")
