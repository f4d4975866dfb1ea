"""Loop-thread milliseconds per dispatch blocked on the next device batch:
the program's ``input.wait`` span (seconds after the window minus before)
over its ``train.step`` count in between. The consumer-side
``input.finalize`` (the dispatch of the unpack program) is inside it.
Layer: input. Moves ``examples_per_s``.

What it is not: device idle time. Hold it against the step period
(``1e3 * batch * K / examples_per_s``): near the period, the loop is
input-bound; a small share of it, the loop runs ahead of an asynchronous
device and waits elsewhere (in the dispatch call, ``dispatch_ms``, or in a
hook's pull). A program without these spans has no such counters and the
metric is left out."""


def read(run: dict):
    before, after = run["stages_before"], run["stages_after"]
    if "input.wait" not in after or "train.step" not in after:
        return None
    dispatches = after["train.step"]["count"] - before.get("train.step", {}).get("count", 0)
    if dispatches <= 0:
        return None
    waited = after["input.wait"]["seconds"] - before.get("input.wait", {}).get("seconds", 0.0)
    return 1e3 * waited / dispatches
