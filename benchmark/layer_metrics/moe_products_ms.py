"""Device milliseconds a step inside the routed experts' grouped products
themselves: the self time of the compiler's ``ragged-dot`` kernels on device
0, found by the name the compiler gives them, over the steps in the traced
window; forward, recomputed and backward together. The kernels reach the
trace with no scope path (PERF.md section 7.6), so ``moe_ms`` and the device
time under ``moe/experts`` leave them out: this is the other half of the
expert layer. ``ragged-dot-metadata``, the kernels' bookkeeping operation, is
no product and is left under the empty path (a tenth of a millisecond a
step). A program that runs no such kernel: nothing to read, nothing returned.
Layer: model. Moves ``examples_per_s``."""
from typing import Optional, Tuple

#: the grouped products' kernels, by the start of the name the compiler
#: gives them (``ragged-dot-none``), and its operation that is no product
PRODUCTS, NOT_A_PRODUCT = "ragged-dot", "ragged-dot-metadata"


def kernels(reduced: dict) -> Optional[Tuple[float, int]]:
    """(self seconds, events) of the grouped products' kernels in a reduced
    trace's window; None where the program ran none."""
    names = [n for n in reduced["op_s"]
             if n.startswith(PRODUCTS) and n != NOT_A_PRODUCT]
    seconds = sum(reduced["op_s"][n] for n in names)
    if not seconds:
        return None
    return seconds, sum(reduced["op_events"].get(n, 0) for n in names)


def read(run: dict):
    t = run["trace"]
    found = t and t["steps"] and kernels(t)
    return 1e3 * found[0] / t["steps"] if found else None
