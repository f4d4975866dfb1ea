"""Share of the chip's bf16 peak the flash-attention kernels reach: the
operations the family's attention core needs
(``benchmark/flops/<family>.flash_flops_per_step``, the family read from the
run's configuration, so one reader serves every family that has the count:
two products forward and four backward over the kept pairs; dead tiles and
the forward pass recomputed under per-block recomputation are NOT counted)
times the steps in the traced window, over the summed time of the kernels'
events, found by the names their ``name=`` gave the instructions
(``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``). A program that runs
no such kernel, or a family without the count: nothing to read, nothing
returned. Layer: kernels. Moves ``examples_per_s``."""
from benchmark.harness import spec

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(run: dict):
    t = run["trace"]
    if not t or not t["steps"]:
        return None
    seconds = sum(s for name, s in t["op_s"].items() if name.startswith(KERNELS))
    count = getattr(spec.module("flops", run["config"]["family"]),
                    "flash_flops_per_step", None)
    if not seconds or count is None:
        return None
    flops = count(run["config"], run["traffic"]) * t["steps"]
    return 100.0 * flops / (seconds * run["peaks"]["bf16_flops_per_s"])
