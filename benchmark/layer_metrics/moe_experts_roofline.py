"""Share of the chip's bf16 peak the routed experts' grouped products reach:
their three products at the expected assignments (tokens x experts per token
x held / published), forward and backward (``benchmark/flops/afmoe.py``; the
chunk's recomputation is not counted), times the steps in the traced window,
over the device time under the scope ``moe/experts``: sorting, gathering and
combining the assignments are inside it, as they are what the grouped
products cost a step. A program that has no such scope: nothing to read,
nothing returned. Layer: kernels. Moves ``examples_per_s``."""
from benchmark.flops import afmoe


def read(run: dict):
    t = run["trace"]
    seconds = t and t["steps"] and afmoe.scope_seconds(t, "moe", "experts")
    if not seconds:
        return None
    flops = afmoe.experts_flops_per_step(run["config"], run["traffic"]) * t["steps"]
    return 100.0 * flops / (seconds * run["peaks"]["bf16_flops_per_s"])
