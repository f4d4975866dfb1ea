"""The whole step's share of the chips' peak: FLOPs one step needs (from the
configuration's shapes, ``benchmark/flops/<family>.py``; nothing from XLA's
cost analysis, nothing recomputed) times the steps in the traced window,
over the window's wall time times the peak bf16 FLOP/s of the chips used.
Host gaps are inside the window, so the share cannot pass 100%.
Layer: step. Moves ``examples_per_s``."""


def read(run: dict):
    t = run["trace"]
    if not t or not t["steps"]:
        return None
    peak = run["peaks"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * run["flops_per_step"] * t["steps"] / (t["window_s"] * peak)
