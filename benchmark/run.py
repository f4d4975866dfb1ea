#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and prints,
as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``check``: every number compared beside its limit.
No accelerator, too few chips, an unknown ``device_kind``, a compilation
inside the window, a share over 100%: exit code 1 and no result line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def process_start() -> float:
    """Epoch seconds at which this process started (set-up counts from there)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            boot = next(int(line.split()[1]) for line in f if line.startswith("btime"))
        return boot + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration, IndexError):
        return time.time()


def main(argv=None) -> int:
    t_process = min(process_start(), time.time())
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--list", action="store_true", help="list the cells and exit")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not args.list and None in (args.workload, args.seed, args.seconds):
        ap.error("--workload, --seed and --seconds are required")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmark.harness import device, spec
    try:
        if args.list:
            print("\n".join(spec.cell_names()))
            return 0
        cell = spec.resolve(args.workload)
        devices, peaks = device.gate(cell.chips)
    except (spec.SpecError, device.DeviceError) as e:
        print(f"[benchmark] {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    try:
        from benchmark.harness import window  # imports the program under test
        result = window.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                                 devices, peaks, t_process)
    except Exception as e:  # the boundary: say what stopped the run, print no result
        import traceback
        traceback.print_exc()
        print(f"[benchmark] no result: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    rows = "  ".join(f"{n}={c['value']:.6g}(limit {c['limit']:g})"
                     for n, c in result["check"].items())
    print(f"[benchmark] correct={result['correct']} compared: {rows}",
          file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # the program's daemon input threads must not hold the exit
