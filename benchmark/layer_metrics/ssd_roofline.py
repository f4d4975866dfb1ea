"""Share of the chip's bf16 peak the chunked scan of the Mamba-2 mixers
reaches: its four products a chunk over full tiles, forward and backward
(``benchmark/flops/<family>.ssd_flops_per_step``, the family read from the
run's configuration), times the steps in the traced window, over the device
time under the scopes ``mamba`` and ``scan`` (the scan with its reshapes,
the step sizes' softplus and the D term), whatever implements the scan. The
recurrence between chunks and the elementwise work are in the time and not
in the count, as are the chunks made again under recomputation. A program
that has no such scope, or a family without the count: nothing to read,
nothing returned. Layer: kernels. Moves ``examples_per_s``."""
from benchmark.flops import afmoe
from benchmark.harness import spec


def read(run: dict):
    t = run["trace"]
    seconds = t and t["steps"] and afmoe.scope_seconds(t, "mamba", "scan")
    count = getattr(spec.module("flops", run["config"]["family"]),
                    "ssd_flops_per_step", None)
    if not seconds or count is None:
        return None
    flops = count(run["config"], run["traffic"]) * t["steps"]
    return 100.0 * flops / (seconds * run["peaks"]["bf16_flops_per_s"])
