"""Device milliseconds a step that no scope owns: the self time of device
0's operations with an empty scope path (copies, slices and relayouts the
compiler adds from no source line) without the grouped products' kernels,
which sit under the empty path too and are ``moe_products_ms``'s. With
``attention_ms``, ``moe_ms``, ``moe_products_ms``, ``lm_head_ms`` and what
``benchmark/tools/step_parts.py`` prints for the optimizer and the blocks'
own norms and sums it adds up to ``step_device_ms``: work a change pushes
out of a layer's scope into compiler-added copies lands here. Should a
repair give the kernels a path, the subtraction goes (PERF.md section 7.6).
No trace: nothing returned. Layer: step. Moves ``examples_per_s``."""
from benchmark.layer_metrics.moe_products_ms import kernels


def read(run: dict):
    t = run["trace"]
    if not t or not t["steps"] or "" not in t["scope_s"]:
        return None
    products = kernels(t)
    return 1e3 * (t["scope_s"][""] - (products[0] if products else 0.0)) / t["steps"]
