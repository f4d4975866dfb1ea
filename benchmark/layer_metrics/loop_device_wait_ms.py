"""Loop-thread milliseconds per dispatch spent waiting for the device on
purpose: the program's ``train.lead_wait`` span (the loop's bound on the
dispatches in flight) and ``train.hook_read`` span (a cadence hook's late
read of device scalars), seconds after the window minus before, over the
``train.step`` count in between. Where the device is what the loop waits
for this is about a dispatch's device time and the other host metrics are
small; near nothing, the loop is held elsewhere (``dispatch_ms``: the
runtime's bound on programs in flight; ``input_wait_ms``: input). The wait
moves between these spans from one PR to the next; their sum is what stays.
A program without the spans' counters leaves the metric out.
Layer: step. Moves ``examples_per_s``."""

SPANS = ("train.lead_wait", "train.hook_read")


def read(run: dict):
    before, after = run["stages_before"], run["stages_after"]
    mine = [s for s in SPANS if s in after]
    if not mine or "train.step" not in after:
        return None
    dispatches = after["train.step"]["count"] - before.get("train.step", {}).get("count", 0)
    if dispatches <= 0:
        return None
    waited = sum(after[s]["seconds"] - before.get(s, {}).get("seconds", 0.0) for s in mine)
    return 1e3 * waited / dispatches
