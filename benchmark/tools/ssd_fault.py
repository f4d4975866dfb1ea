#!/usr/bin/env python3
"""Read what a fault of the chunked scan does to the comparison: the state
dropped between chunks, planted in the reference put in the program's place.

    python3 benchmark/tools/ssd_fault.py --workload nemotron3_nano_train_8k --seeds 2 \\
        [--first-seed N] [--out FILE]

For each seed the cell's first batches from its generator (no program runs),
the reference's walk over them, and the same walk with the recurrence's
state zeroed at every multiple of the configuration's ``chunk_size``
(``STATE_RESET_EVERY`` of the family's reference): the numbers
``benchmark/harness/check.py`` compares, as ``readings.py`` prints a fault's
(the upper readings of that fault). Needs the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2_200_000_011)
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu-root", default="", help="rehearsal: a tiny root, no gate")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmark.harness import check, device, spec
    from benchmark.harness.program import place_compile_cache, seed31
    from benchmark.reference import follow
    cell = spec.resolve(args.workload, args.cpu_root or spec.ROOT)
    if not args.cpu_root:
        device.gate(cell.chips)
    place_compile_cache()
    family = spec.module("reference", cell.config["family"])
    bounds = check.boundaries(1)  # a token cell dispatches one step at a time
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.time()
        stream = spec.module("generators", cell.traffic["generator"]).make(
            cell.traffic, cell.config, seed)
        batches = [stream.batch(j) for j in range(bounds[-1])]
        walk = lambda: follow.follow(cell.config, seed, batches, bounds,  # noqa: E731
                                     augment_seed=seed31(seed))
        sound = walk()
        family.STATE_RESET_EVERY = cell.config["model"]["chunk_size"]
        follow._COMPILED.clear()  # the walk's programs are kept per configuration
        try:
            bad = walk()
        finally:
            family.STATE_RESET_EVERY = None
            follow._COMPILED.clear()
        row = {"seed": seed, "state_reset": check.compare(bad, sound)[0],
               "seconds": round(time.time() - t0, 1)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    names = list(rows[0]["state_reset"])
    summary = {"state_reset_min": {n: min(r["state_reset"][n] for r in rows) for n in names}}
    print("SUMMARY " + json.dumps(summary), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"cell": cell.name, "rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
