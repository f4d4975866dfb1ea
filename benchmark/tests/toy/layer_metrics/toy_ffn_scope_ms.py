"""Device milliseconds a step under the ``ffn`` scope, forward and backward
(self times of device 0's operations by ``named_scope``)."""
from benchmark.harness import trace


def read(run: dict):
    t = run["trace"]
    seconds = t and trace.scope_seconds(t, "ffn")
    if not seconds:
        return None
    return 1e3 * seconds / t["steps"]
