"""Cluster trace correlation: ``main.py trace-merge``.

Each process dumps its own flight-recorder ring as
``trace[.procN].json`` (telemetry/tracer.py) — useful alone, but a
distributed incident is a RELATIVE story: a straggling host's late
``train.step`` span is only visibly late against its peers' lanes on
ONE timeline. This module merges the per-process dumps into a single
Perfetto/Chrome-trace file with one process lane per host:

  * every source file's events keep their thread lanes but move to
    ``pid = process_index``, with ``process_name`` /
    ``process_sort_index`` metadata so Perfetto renders "proc0 (host)"
    groups in rank order;
  * timestamps are rebased onto one wall-clock timeline. Each recorder
    stamps ``epoch_wall_time`` at construction, so within one host the
    mapping is exact; ACROSS hosts the wall clocks skew (NTP is
    milliseconds on a good day, seconds on a bad one), so the merge
    estimates per-process clock offsets from the heartbeat
    publish/observe pairs the run already recorded: the chief's
    ``{"event": "heartbeat"}`` rows carry each peer's beat age at
    observation, and ``min(observed age)`` over many observations is a
    BOUNDED estimator of the peer's clock offset (true publish→observe
    latency is in ``[0, beat interval + poll cadence]``; the chief's own
    min age calibrates the zero point, cancelling the shared publish-lag
    bias). The estimate, its bound and the observation count land in the
    merged file's ``otherData.clock_offsets`` — a reader can always see
    how much to trust sub-second cross-host ordering.

Works on exactly the artifacts the chaos/obs smokes produce
(``scripts/obs_smoke.sh``); pure filesystem reads, no jax world.
"""
from __future__ import annotations

import argparse
import glob
import json
import logging
import os
from typing import Dict, List, Optional, Sequence

log = logging.getLogger(__name__)

#: the observer of the heartbeat rows — the chief's watchdog is the only
#: writer-bearing one (resilience/watchdog.py), and its own beats in the
#: same rows calibrate the estimator's zero point
_OBSERVER_PID = "0"


def find_traces(root: str) -> List[str]:
    """Every per-process flight-recorder dump under ``root`` (the merged
    output itself is excluded so re-merges are idempotent)."""
    paths = sorted(
        p for p in glob.glob(os.path.join(root, "**", "trace*.json"),
                             recursive=True)
        if not os.path.basename(p).startswith("trace.merged"))
    return paths


def _heartbeat_rows(root: str) -> List[dict]:
    from ..utils.metrics import iter_metric_streams
    return [r for stream in iter_metric_streams(root) for r in stream
            if r.get("event") == "heartbeat"]


def estimate_clock_offsets(root: str) -> Dict[str, dict]:
    """Per-process clock-offset estimates from the run's heartbeat rows:
    ``{pid: {offset_secs, bound_secs, observations, min_age_secs,
    host}}``. ``offset_secs`` is (process clock − chief clock): subtract
    it from a process's wall timestamps to land on the chief's timeline.
    Empty when the run recorded no heartbeat rows (single process, or
    the watchdog was off) — the merge then trusts raw wall clocks."""
    ages: Dict[str, List[float]] = {}
    hosts: Dict[str, str] = {}
    for row in _heartbeat_rows(root):
        for pid, h in (row.get("hosts") or {}).items():
            age = h.get("age_secs")
            if isinstance(age, (int, float)):
                ages.setdefault(str(pid), []).append(float(age))
            if h.get("host"):
                hosts[str(pid)] = h["host"]
    if not ages:
        return {}
    chief_min = min(ages.get(_OBSERVER_PID, [0.0]))
    out: Dict[str, dict] = {}
    for pid, samples in sorted(ages.items()):
        m = min(samples)
        # |error| <= the chief's and this process's min TRUE
        # publish->observe latencies, each in [0, beat interval + poll
        # cadence]. Neither true latency is observable, so the recorded
        # bound uses the observable proxies: the chief's min age (its
        # offset is 0 by definition, so that IS its min latency) plus
        # the spread of this process's low-end ages (the latency scale
        # on its side).
        lo = sorted(samples)
        spread = lo[len(lo) // 2] - m if len(lo) > 1 else chief_min
        out[pid] = {
            "offset_secs": round(chief_min - m, 4),
            "bound_secs": round(max(0.0, chief_min) + max(0.0, spread), 4),
            "observations": len(samples),
            "min_age_secs": round(m, 4),
        }
        if pid in hosts:
            out[pid]["host"] = hosts[pid]
    return out


def merge_traces(paths: Sequence[str],
                 offsets: Optional[Dict[str, dict]] = None) -> dict:
    """Merge per-process trace dumps into one Perfetto document. Raises
    ValueError when no source loads — the callers are CLIs that should
    fail loudly, unlike the in-run dump paths."""
    offsets = offsets or {}
    sources = []
    for path in paths:
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            log.warning("trace-merge: skipping unreadable %s (%s)", path, e)
            continue
        other = doc.get("otherData") or {}
        sources.append({
            "path": path,
            "doc": doc,
            "process_index": int(other.get("process_index", 0)),
            "pid": other.get("pid"),
            "epoch_wall_time": float(other.get("epoch_wall_time", 0.0)),
            "span_schema_version": other.get("span_schema_version"),
        })
    if not sources:
        raise ValueError("no readable trace files to merge")
    sources.sort(key=lambda s: s["process_index"])

    def corrected_epoch(src) -> float:
        off = offsets.get(str(src["process_index"]), {})
        return src["epoch_wall_time"] - float(off.get("offset_secs", 0.0))

    t0 = min(corrected_epoch(s) for s in sources)
    events: List[dict] = []
    for src in sources:
        p = src["process_index"]
        off = offsets.get(str(p), {})
        host = off.get("host")
        name = f"proc{p}" + (f" ({host})" if host else "")
        events.append({"name": "process_name", "ph": "M", "pid": p,
                       "ts": 0, "args": {"name": name}})
        events.append({"name": "process_sort_index", "ph": "M", "pid": p,
                       "ts": 0, "args": {"sort_index": p}})
        shift_us = (corrected_epoch(src) - t0) * 1e6
        for ev in src["doc"].get("traceEvents", []):
            ev = dict(ev)
            ev["pid"] = p
            if ev.get("ph") == "X":
                ev["ts"] = round(float(ev.get("ts", 0.0)) + shift_us, 3)
            events.append(ev)
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "merged": True,
            "span_schema_version": max(
                (s["span_schema_version"] or 0) for s in sources),
            "t0_wall_time": t0,
            "sources": [{
                "path": os.path.basename(s["path"]),
                "process_index": s["process_index"],
                "pid": s["pid"],
                "epoch_wall_time": s["epoch_wall_time"],
            } for s in sources],
            # the bounded-skew record: how much to trust cross-host
            # sub-second ordering in this file
            "clock_offsets": {
                pid: {k: v for k, v in off.items()}
                for pid, off in sorted(offsets.items())},
        },
    }


def main_trace_merge(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="main.py trace-merge",
        description="merge per-process flight-recorder trace dumps into "
                    "one Perfetto timeline with per-host lanes and "
                    "heartbeat-estimated clock offsets "
                    "(docs/observability.md)")
    ap.add_argument("traces", nargs="*",
                    help="explicit trace.json files (default: every "
                         "trace*.json under --root)")
    ap.add_argument("--root", default="/tmp/drt_tpu",
                    help="the run's log_root (trace dumps + metrics "
                         "streams for the clock-offset estimate)")
    ap.add_argument("--out", default="",
                    help="output path (default: "
                         "<root>/telemetry/trace.merged.json)")
    ns = ap.parse_args(argv)
    paths = list(ns.traces) or find_traces(ns.root)
    if not paths:
        print(f"trace-merge: no trace*.json found under {ns.root}")
        return 1
    offsets = estimate_clock_offsets(ns.root)
    try:
        doc = merge_traces(paths, offsets)
    except ValueError as e:
        print(f"trace-merge: {e}")
        return 1
    out = ns.out or os.path.join(ns.root, "telemetry", "trace.merged.json")
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    tmp = f"{out}.tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, out)
    spans = sum(1 for e in doc["traceEvents"] if e.get("ph") == "X")
    lanes = len(doc["otherData"]["sources"])
    print(f"trace-merge: {spans} span(s) across {lanes} process lane(s) "
          f"-> {out}")
    if offsets:
        for pid, off in sorted(offsets.items()):
            print(f"  clock offset proc{pid}: {off['offset_secs']:+.3f}s "
                  f"(±{off['bound_secs']:.3f}s over "
                  f"{off['observations']} beat observations)")
    else:
        print("  no heartbeat rows found: raw wall clocks trusted "
              "(offsets unknown)")
    return 0
