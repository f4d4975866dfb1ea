"""Milliseconds per step in which a collective operation runs on device 0
and nothing else does (device trace). Layer: sharding and collectives.
Moves ``examples_per_s``. A run on one chip, or a trace with no collective
in it, has nothing to read."""


def read(run: dict):
    t = run["trace"]
    if not t or not t["steps"] or run["chips"] < 2 or not t["collective_events"]:
        return None
    return 1e3 * t["collective_exposed_s"] / t["steps"]
