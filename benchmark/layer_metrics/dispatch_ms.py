"""Host milliseconds inside the jitted call per dispatch: the program's
``train.step`` span, seconds over count, after the window minus before it:
argument placement (``shard_args``), the enqueue, and whatever the runtime
makes the caller wait (back-pressure when the device is far behind). Not
the device's time for the step: that is ``step_device_ms``. Layer: step.
Moves ``examples_per_s``. A program without the span's counter (before the
spans charged ``input_stages``) leaves the metric out."""


def read(run: dict):
    before, after = run["stages_before"], run["stages_after"]
    if "train.step" not in after:
        return None
    was = before.get("train.step", {})
    dispatches = after["train.step"]["count"] - was.get("count", 0)
    if dispatches <= 0:
        return None
    return 1e3 * (after["train.step"]["seconds"] - was.get("seconds", 0.0)) / dispatches
