"""Share of the chip's bf16 peak the routed experts' grouped products reach
in the sdar_moe family: their three products at the expected assignments of
the 2L positions a sequence (positions x experts per token x held /
published), forward and backward (``benchmark/flops/sdar_moe.py``; the
chunk's recomputation is not counted), times the steps in the traced window,
over the device time of the scope ``moe/experts`` AND of the grouped
products themselves. Sorting, gathering and combining the assignments are
under the scope, as they are what the grouped products cost a step; the
products are not: the compiler's ``ragged-dot`` kernels carry no scope path
in the trace (PERF.md section 7.6), so their self time is found by the
kernels' name and added. Should a tracing repair give them the scope they
were written under, the sum would count them twice: the reader then drops
the addition. A program that has no such scope: nothing to read, nothing
returned. Layer: kernels. Moves ``examples_per_s``."""
from benchmark.flops import sdar_moe
from benchmark.flops.afmoe import scope_seconds

#: the grouped products' kernels, by the name the compiler gives them
PRODUCTS = "ragged-dot"


def read(run: dict):
    t = run["trace"]
    seconds = t and t["steps"] and scope_seconds(t, "moe", "experts")
    if not seconds:
        return None
    seconds += sum(s for name, s in t["op_s"].items() if name.startswith(PRODUCTS))
    flops = sdar_moe.experts_flops_per_step(run["config"], run["traffic"]) * t["steps"]
    return 100.0 * flops / (seconds * run["peaks"]["bf16_flops_per_s"])
