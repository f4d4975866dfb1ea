"""Share of the traced window in which no operation ran, mean over the
cell's devices: 1 - busy union / window. Layer: device. Moves
``examples_per_s``."""


def read(run: dict):
    t = run["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
