"""Share of the chip's bf16 peak the routed experts' grouped products reach,
over the products' own time: their three products at the expected
assignments, forward and backward
(``benchmark/flops/<family>.experts_flops_per_step``, the family read from
the run's configuration, so one reader serves every family that has the
count), times the steps in the traced window, over the self time of the
compiler's ``ragged-dot`` kernels found by name (``moe_products_ms``'s
time). Products made again under a block's recomputation are in the time
and not in the count, as in ``flash_roofline``; rows of a window past the
live count are in neither. Beside ``moe_experts_roofline`` and
``sdar_experts_roofline``, which divide the same count by the passes around
the products: a change that speeds the kernels moves this one, a change
that moves work between the passes and the kernels moves them apart. A
program that runs no such kernel, or a family without the count: nothing to
read, nothing returned. Layer: kernels. Moves ``examples_per_s``."""
from benchmark.harness import spec
from benchmark.layer_metrics.moe_products_ms import kernels


def read(run: dict):
    t = run["trace"]
    found = t and t["steps"] and kernels(t)
    count = getattr(spec.module("flops", run["config"]["family"]),
                    "experts_flops_per_step", None)
    if not found or count is None:
        return None
    flops = count(run["config"], run["traffic"]) * t["steps"]
    return 100.0 * flops / (found[0] * run["peaks"]["bf16_flops_per_s"])
