"""Device milliseconds per training step: the union of the intervals in
which any operation runs on device 0, over the steps in the traced window.
Layer: step. Moves ``examples_per_s``."""


def read(run: dict):
    t = run["trace"]
    if not t or not t["steps"]:
        return None
    return 1e3 * t["busy_s_device0"] / t["steps"]
