#!/usr/bin/env python3
"""Read what the comparison compares, over many seeds, in one process.

    python3 benchmark/tools/readings.py --workload <cell> --seeds 12 --controls 3 \\
        [--first-seed N] [--out FILE]

For each seed: the program's first steps through ``Trainer.train`` at the
cell's own size (the lower reading is the largest of these), and the
reference. For the first ``--controls`` seeds also the control (the
reference in the precision below the configuration's, put in the program's
place) and each fault the cell can have, planted in the reference put in
the program's place (the upper reading is the smallest of those that a
limit has to fail). Needs the chips the cell asks for; no measured window.
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
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_011)
    ap.add_argument("--out", default="")
    ap.add_argument("--override", action="append", default=[], metavar="group.key=value",
                    help="a witness run: change the configuration for this call, "
                         "e.g. model.compute_dtype=float32")
    ap.add_argument("--cpu-root", default="", help="rehearsal: a tiny root, no gate")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from benchmark.harness import check, device, spec
    from benchmark.harness.program import Program, place_compile_cache
    from benchmark.harness.window import Recorder
    from benchmark.reference import follow
    if args.cpu_root:
        cell = spec.resolve(args.workload, args.cpu_root)
        devices = jax.devices()[:cell.chips]
    else:
        cell = spec.resolve(args.workload)
        devices, _ = device.gate(cell.chips)
    place_compile_cache()
    for item in args.override:
        key, value = item.split("=", 1)
        cell.config["overrides"][key] = value
        group, leaf = key.split(".", 1)
        if leaf in cell.config.get(group, {}):
            cell.config[group][leaf] = value
    faults = ["half_batch"] + (["quarter_batch"] if cell.chips == 4 else [])
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.time()
        program = Program(cell, seed, devices)
        bounds = check.boundaries(program.k)
        recorder = Recorder(program, bounds)
        program.trainer.train(program.data_iter, num_steps=bounds[-1],
                              hooks=tuple(program.hooks()) + (recorder,))
        jax.block_until_ready(program.trainer.state)
        mine = recorder.readings()
        augment_seed = program.cfg.train.seed
        batches = [program.stream.batch(j) for j in range(bounds[-1])]
        program.close()
        del program, recorder
        ref = follow.follow(cell.config, seed, batches, bounds, augment_seed=augment_seed)
        numbers, where = check.compare(mine, ref)
        by_leaf = check.gaps(mine["moment"]["norm"], ref["moment"]["norm"],
                             sorted(ref["moment"]["norm"]))
        look = [[n, round(by_leaf[n], 4), ref["moment"]["norm"][n], mine["moment"]["norm"][n]]
                for n in sorted(by_leaf, key=by_leaf.get, reverse=True)[:4]]
        import statistics
        row = {"seed": seed, "program": numbers, "where": where, "ref_loss": ref["loss"],
               "median_leaf_norm": statistics.median(ref["moment"]["norm"].values()),
               "worst_leaves_gap_ref_mine": look, "seconds": None}
        if i < args.controls:
            ctl = follow.follow(cell.config, seed, batches, bounds, augment_seed=augment_seed,
                                precision=cell.config["control_precision"])
            row["control"] = check.compare(ctl, ref)[0]
            for fault in faults:
                bad = follow.follow(cell.config, seed, batches, bounds,
                                    augment_seed=augment_seed, fault=fault)
                row[fault] = check.compare(bad, ref)[0]
        row["seconds"] = round(time.time() - t0, 1)
        rows.append(row)
        print(json.dumps(row), flush=True)
    names = list(rows[0]["program"])
    summary = {"lower": {n: max(r["program"][n] for r in rows) for n in names}}
    for kind in ["control"] + faults:
        have = [r[kind] for r in rows if kind in r]
        if have:
            summary[f"{kind}_min"] = {n: min(h[n] for h in have) for n in names}
    print("SUMMARY " + json.dumps(summary), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"cell": cell.name, "rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
