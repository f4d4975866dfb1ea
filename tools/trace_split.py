#!/usr/bin/env python3
"""Read a kept profiler trace's host side by hand: what the program's threads
were inside during the device's longest idle gaps.

    BENCH_KEEP_TRACE=<dir> python3 benchmark/run.py --workload <cell> ... --trace 1
    python tools/trace_split.py <dir>/<cell>.xplane.pb [--steps-per-dispatch K]

What it reads, all on the profiler's one clock:

* the host plane's lines (one per thread): the flight recorder's spans
  (``telemetry/tracer.SPAN_CATALOG``), how often and how long each ran;
* the ten longest idle gaps of device 0 inside the benchmark's window
  (first to last start of the most expensive module,
  ``benchmark/harness/trace.py``), each with the spans that overlap it,
  innermost first, per thread.

The device side (time by ``jax.named_scope``, the kernels by name) is
``python3 benchmark/tools/step_parts.py <file>``, over
``benchmark/harness/trace.reduce``; this file's own walk of it went with
PR 37. What is left here moves into ``benchmark/harness/trace.py`` with the
``benchmark`` issue that cuts ``stage_ms`` / ``input_wait_ms`` /
``dispatch_ms`` to the window and names a gap after the innermost span
(PERF.md section 7). Needs the ``xplane_pb2`` that TensorFlow ships
(``jax.profiler.ProfileData`` shows no thread ids and no event stats).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

def load(path: str):
    """[(plane, line, name, start_ns, dur_ns, stats)] with metadata stats."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = []
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}

        def value(st):
            kind = st.WhichOneof("value")
            if kind == "ref_value":
                return names.get(st.ref_value, "")
            return getattr(st, kind) if kind else None
        for line in plane.lines:
            for ev in line.events:
                md = plane.event_metadata[ev.metadata_id]
                stats = {names[s.metadata_id]: value(s) for s in md.stats}
                stats.update({names[s.metadata_id]: value(s) for s in ev.stats})
                # host threads share a name ("python3"): the id tells them apart
                label = line.display_name or line.name
                if plane.name.startswith("/host:"):
                    label = f"{label}/{line.id}"
                out.append((plane.name, label,
                            md.display_name or md.name, md.name,
                            line.timestamp_ns + ev.offset_ps / 1e3,
                            ev.duration_ps / 1e3, stats))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--steps-per-dispatch", type=int, default=1)
    ap.add_argument("--json", help="also write the reading here")
    args = ap.parse_args()
    from benchmark.harness import trace as harness
    from distributed_resnet_tensorflow_tpu.telemetry.tracer import SPAN_CATALOG

    events = load(args.trace)
    device = sorted({p for p, *_ in events if harness.DEVICE_PLANE.match(p)})
    if not device:
        print("no device plane in the trace", file=sys.stderr)
        return 1
    dev0 = device[0]
    plain = {"planes": [{"name": dev0, "lines": [
        {"name": ln, "events": [(full, s, d) for p, line, _, full, s, d, _ in events
                                if p == dev0 and line == ln]}
        for ln in ("XLA Modules", "XLA Ops")]}]}
    lo, hi, periods, module = harness.step_window(plain["planes"][0])
    steps = periods * args.steps_per_dispatch

    ops = [(full, s, d) for p, line, _, full, s, d, _ in events
           if p == dev0 and line == "XLA Ops"]
    print(f"window {1e-9 * (hi - lo):.3f} s, {periods} periods of {module}, {steps} steps")

    # -- the program's spans on the host plane --------------------------
    host = [(line, name, s, s + d, st) for p, line, name, _, s, d, st in events
            if p.startswith("/host:") and name in SPAN_CATALOG and d > 0]
    per_line = defaultdict(lambda: defaultdict(int))
    for line, name, *_ in host:
        per_line[line][name] += 1
    # Python threads carry no name into the trace: call them by their spans
    roles = (("train.step", "loop thread"), ("input.transfer", "staging thread"),
             ("input.stack", "stacker thread"))
    label = {line: next((role for span, role in roles if span in names), line)
             for line, names in per_line.items()}
    host = [(label[line], *rest) for line, *rest in host]
    per_line = {label[line]: names for line, names in per_line.items()}
    print("spans on the host plane, by thread line:")
    for line, names in per_line.items():
        print(f"  {line}: " + ", ".join(f"{n} x{c}" for n, c in sorted(names.items())))
    print("| span | events | mean ms | median ms | longest ms |")
    print("| --- | --- | --- | --- | --- |")
    durations = defaultdict(list)
    for _, name, a, b, _ in host:
        durations[name].append((b - a) / 1e6)
    for name, ds in sorted(durations.items()):
        ds.sort()
        print(f"| {name} | {len(ds)} | {sum(ds) / len(ds):.3f} | "
              f"{ds[len(ds) // 2]:.3f} | {ds[-1]:.3f} |")
    stepped = [st.get("step_num") for _, name, _, _, st in host if name == "train.step"]
    print(f"train.step events: {len(stepped)}, step_num {stepped[:3]} ... {stepped[-1:]}")

    # -- idle gaps and who was where ------------------------------------
    busy_iv = harness.union([(s, s + d) for _, s, d in ops])
    gaps, cursor = [], lo
    for a, b in busy_iv:
        if b <= lo or a >= hi:
            continue
        if a > cursor:
            gaps.append((cursor, min(a, hi)))
        cursor = max(cursor, b)
    gaps.sort(key=lambda g: g[0] - g[1])
    all_host = [(line, name, s, s + d) for p, line, name, _, s, d, _ in events
                if p.startswith("/host:") and d > 0]
    print("| idle gap ms | at ms | program spans, by thread (innermost first) | "
          "host event overlapping most |")
    print("| --- | --- | --- | --- |")
    reading = []
    for a, b in gaps[:10]:
        inside = defaultdict(list)
        for line, name, s, e, _ in host:
            if s < b and e > a:
                inside[line].append((e - s, name))
        spans = "; ".join(f"{line}: " + " < ".join(n for _, n in sorted(v))
                          for line, v in inside.items()) or "none"
        best, best_ov = "none", 0.0
        for line, name, s, e in all_host:
            ov = min(b, e) - max(a, s)
            if ov > best_ov and name not in SPAN_CATALOG and (e - s) <= 4 * (b - a):
                best, best_ov = f"{harness.strip_ids(name)} ({line})", ov
        print(f"| {(b - a) / 1e6:.3f} | {(a - lo) / 1e6:.1f} | {spans} | {best} |")
        reading.append({"gap_ms": (b - a) / 1e6, "at_ms": (a - lo) / 1e6,
                        "spans": spans, "host_event": best})
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"module": module, "steps": steps, "gaps": reading}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
