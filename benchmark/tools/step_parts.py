#!/usr/bin/env python3
"""A kept profiler trace as a partition of the step: device milliseconds a
step by the program's registered scopes, the pass in columns.

    BENCH_KEEP_TRACE=<dir> python3 benchmark/run.py --workload <cell> ... --trace 1
    python3 benchmark/tools/step_parts.py <dir>/<cell>.xplane.pb [--steps-per-dispatch K]
    python3 benchmark/tools/step_parts.py --catalog   # docs/observability.md's table

Reads the file through ``benchmark/harness/trace.load`` and ``reduce`` (no
walk of its own, no TensorFlow) and prints a markdown table:

* one row for each run of components of
  ``telemetry/tracer.SCOPE_CATALOG`` a scope path holds (``attention/core``,
  ``moe/experts/carry``, ``optimizer``), wrappers of the backward pass
  stripped; a path that holds none is named, in brackets, after the flax
  modules it sits in (``[layer*/mlp]``, ``[final_norm]``,
  ``[EncoderBlock_*/MlpBlock_*]``), and the empty path is ``[no path]``. A row holds the operations that are under its last
  component and under none of the rows below it: ``attention`` is the
  projections, norms and gate beside ``attention/rotary`` and
  ``attention/core``;
* the pass in columns: ``recomputed`` where the path holds JAX's
  ``rematted_computation``, else ``backward`` where a component is wrapped
  in ``transpose(``, else ``forward`` under the ``forward`` scope, else
  ``outside`` the loss (the optimizer, the unpack program, the empty path);
* under the table the kernels by name (the flash kernels, the grouped
  products, the cross-entropy kernels): where their time sits, not a row
  more; the grouped products lie inside ``[no path]``;
* a last row ``sum``, and beside it ``step_device_ms`` (the busy union):
  self times partition the busy time, so the two agree but for rounding.

A fusion carries the scope of one of its instructions, and a program
fetched from the compile cache the scopes of the commit that compiled it
(PERF.md section 7, the two traps).
"""
from __future__ import annotations

import argparse
import os
import re
import sys
from collections import defaultdict
from typing import Dict, List, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PASSES = ("forward", "recomputed", "backward", "outside")
#: components that say which pass an operation belongs to, not which part
PASS_COMPONENTS = ("forward", "checkpoint", "rematted_computation")
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "ragged-dot",
           "softmax_xent_fwd", "softmax_xent_bwd")
#: components of a path that are control flow, not a module
STRUCTURE = ("closed_call", "while", "body", "cond", "pjit")
_WRAPPED = re.compile(r"(?:\w+\()*([^()]*)\)*")


def bare(component: str) -> str:
    """``transpose(jvp(attention))`` -> ``attention``."""
    m = _WRAPPED.fullmatch(component)
    return m.group(1) if m else component


def which_pass(path: str) -> str:
    components = path.split("/")
    if "rematted_computation" in components:
        return "recomputed"
    if any(c.startswith("transpose(") for c in components):
        return "backward"
    return "forward" if "forward" in map(bare, components) else "outside"


def row_of(path: str, catalog) -> str:
    if not path:
        return "[no path]"
    names = [bare(c) for c in path.split("/")]
    mine = [n for i, n in enumerate(names)
            if n in catalog and n not in PASS_COMPONENTS and names[i - 1:i] != [n]]
    if mine:
        return "/".join(mine)
    # no registered scope: the two flax modules under the model's own, every
    # layer's alike in one row (``layer3`` and ``EncoderBlock_3`` lose the 3)
    modules = [re.sub(r"\d+$", "*", c) for c in path.split("/")
               if "(" not in c and c not in STRUCTURE and c not in PASS_COMPONENTS]
    inner = [m for m in modules if m != modules[0]]  # the backward pass names the model twice
    return "[" + "/".join(inner[:2] or modules[:1] or ["no module"]) + "]"


def partition(reduced: dict, catalog) -> Dict[str, Dict[str, float]]:
    """row -> pass -> device ms a step."""
    rows: Dict[str, Dict[str, float]] = defaultdict(lambda: dict.fromkeys(PASSES, 0.0))
    for path, seconds in reduced["scope_s"].items():
        rows[row_of(path, catalog)][which_pass(path)] += \
            1e3 * seconds / reduced["steps"]
    return rows


def table(reduced: dict, catalog) -> Tuple[List[str], float]:
    rows = partition(reduced, catalog)
    lines = ["| scope | " + " | ".join(PASSES) + " | all |", "| --- |" + " ---: |" * 5]
    for name in sorted(rows, key=lambda r: (r.startswith("["), r)):
        cells = [rows[name][p] for p in PASSES]
        lines.append(f"| `{name}` | " + " | ".join(f"{c:.2f}" if c else "" for c in cells)
                     + f" | {sum(cells):.2f} |")
    totals = [sum(r[p] for r in rows.values()) for p in PASSES]
    lines.append("| **sum** | " + " | ".join(f"{c:.2f}" for c in totals)
                 + f" | **{sum(totals):.2f}** |")
    return lines, sum(totals)


def catalog_table(catalog) -> List[str]:
    """The catalog as docs/observability.md prints it, row for row."""
    kind = {"scope": 'named_scope("{}")', "module": "{} (a flax module's name)",
            "jax": "{} (JAX's own)"}
    lines = ["| component | under | where | what it holds | read by |",
             "| --- | --- | --- | --- | --- |"]
    for name, s in catalog.items():
        under = f"`{s.under}`" if s.under else ""
        lines.append(f"| `{kind[s.origin].format(name)}` | {under} | `{s.where}` | "
                     f"{s.holds} | {s.read_by} |")
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", nargs="?")
    ap.add_argument("--steps-per-dispatch", type=int, default=1)
    ap.add_argument("--catalog", action="store_true",
                    help="print SCOPE_CATALOG as a markdown table and exit")
    args = ap.parse_args(argv)
    from distributed_resnet_tensorflow_tpu.telemetry.tracer import SCOPE_CATALOG
    if args.catalog:
        print("\n".join(catalog_table(SCOPE_CATALOG)))
        return 0
    if not args.trace:
        ap.error("a trace file, or --catalog")
    from benchmark.harness import trace
    from benchmark.layer_metrics.moe_products_ms import kernels
    reduced = trace.reduce(trace.load(args.trace), args.steps_per_dispatch)
    steps = reduced["steps"]
    lines, total = table(reduced, SCOPE_CATALOG)
    device = 1e3 * reduced["busy_s_device0"] / steps
    print(f"{reduced['periods']} periods of {reduced['module']}, {steps} steps; device ms a "
          f"step, self times of device 0's operations in the aligned window")
    print("\n".join(lines))
    print(f"step_device_ms {device:.2f}; the sum is {100 * (total / device - 1):+.3f}% of it")
    products = kernels(reduced)
    if products:
        inside = 1e3 * products[0] / steps
        print(f"[no path] holds the grouped products' kernels, {inside:.2f} (moe_products_ms); "
              f"without them {1e3 * reduced['scope_s'].get('', 0.0) / steps - inside:.2f} "
              "(step_unscoped_ms)")
    print("kernels by name (inside the rows above):")
    for kernel in KERNELS:
        for name in sorted(n for n in reduced["op_s"] if n.startswith(kernel)):
            print(f"  {name}: {1e3 * reduced['op_s'][name] / steps:.2f} ms a step, "
                  f"{reduced['op_events'].get(name, 0) / steps:g} events a step")
    return 0


if __name__ == "__main__":
    sys.exit(main())
