"""Grouped products a step really ran: the events of the compiler's
``ragged-dot`` kernels on device 0 (``moe_products_ms``'s kernels, found by
name) over the steps in the traced window. Work done as a count: products a
window (3 forward, 9 in the gradient's window: gate, up and down made again
and two transposed products each) x windows a chunk x chunk-walks a step, so
it rises with a seed's load a window at a time, where ``moe_products_ms``
rises with the rows. A program that runs no such kernel: nothing to read,
nothing returned. Layer: kernels. Moves ``examples_per_s``."""
from benchmark.layer_metrics.moe_products_ms import kernels


def read(run: dict):
    t = run["trace"]
    found = t and t["steps"] and kernels(t)
    return found[1] / t["steps"] if found else None
