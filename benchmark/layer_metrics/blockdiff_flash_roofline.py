"""Share of the chip's bf16 peak the flash-attention kernels reach under the
block-diffusion mask: the operations the kept pairs need
(``benchmark/flops/sdar_moe.py``: two products forward and four backward
over L^2 + L B pairs a sequence a layer; dead tiles, the dead pairs of a live
tile and the forward pass recomputed under per-block recomputation are NOT
counted) times the steps in the traced window, over the summed time of the
kernels' events, found by the names their ``name=`` gave the instructions
(``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``). A program that runs no
such kernel: nothing to read, nothing returned.
Layer: kernels. Moves ``examples_per_s``."""
from benchmark.flops import sdar_moe

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(run: dict):
    t = run["trace"]
    if not t or not t["steps"]:
        return None
    seconds = sum(s for name, s in t["op_s"].items() if name.startswith(KERNELS))
    if not seconds:
        return None
    flops = sdar_moe.flash_flops_per_step(run["config"], run["traffic"]) * t["steps"]
    return 100.0 * flops / (seconds * run["peaks"]["bf16_flops_per_s"])
