"""Cluster rollup: ``main.py monitor`` — what is the whole run doing NOW.

The per-process observability (metrics.jsonl event streams, heartbeat
files, flight-recorder dumps) answers post-mortem questions; an operator
mid-run needs the live aggregate: steps/s, goodput %, per-host skew, the
last committed checkpoint, serving QPS/p99. This module tails every
``metrics.jsonl`` stream under a root directory (the same shared-directory
layout the heartbeat transport and checkpoint manager already use — one
``log_root`` per host, or one shared one), merges the newest rows, and
renders either a live text dashboard or a machine-readable JSON blob:

    python -m distributed_resnet_tensorflow_tpu.main monitor --root /runs/r1
    python -m distributed_resnet_tensorflow_tpu.main monitor --root /runs/r1 \
        --once --json        # scripts / CI

Reads are tolerant by construction: a stream mid-rotation, a torn JSON
line, or a vanished heartbeat file degrade to "unknown", never to a crash —
the monitor must keep rendering exactly when the run is sickest.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import time
from typing import Dict, List, Optional

#: how much of each live stream one monitor frame reads. Every lookup the
#: rollup makes is "newest row of kind X" plus one rate pair — a bounded
#: tail covers them all, and a full-stream parse would make each refresh
#: frame of a week-long rotated run (GBs across segments) re-read
#: everything on the very filesystem the run depends on.
_TAIL_BYTES = 2 * 1024 * 1024


def _read_rows(stream_dir: str, tail_bytes: int = _TAIL_BYTES) -> List[dict]:
    """The newest rows of one metrics stream: the live file's last
    ``tail_bytes`` (partial first line dropped), prefixed by the newest
    rotated segment's tail when the live file is freshly rotated (so
    rates survive a rotation boundary). Torn lines skipped."""
    path = os.path.join(stream_dir, "metrics.jsonl")
    try:
        size = os.path.getsize(path)
    except OSError:
        return []
    paths = [(path, tail_bytes)]
    if size < tail_bytes // 8 and os.path.exists(path + ".1"):
        paths.insert(0, (path + ".1", tail_bytes // 4))
    rows: List[dict] = []
    for p, budget in paths:
        try:
            with open(p, "rb") as f:
                psize = os.fstat(f.fileno()).st_size
                if psize > budget:
                    f.seek(psize - budget)
                    f.readline()  # drop the partial first line
                data = f.read()
        except OSError:
            continue
        for line in data.decode("utf-8", errors="replace").splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                rows.append(json.loads(line))
            except ValueError:
                continue  # torn mid-write; the stream is live
    return rows


def _last(rows: List[dict], event: Optional[str]) -> Optional[dict]:
    """Newest row of a kind: ``event=None`` = newest scalar row."""
    for row in reversed(rows):
        if event is None and "event" not in row and "step" in row:
            return row
        if event is not None and row.get("event") == event:
            return row
    return None


#: scalar rows the steps/s window spans (at the log cadence this is
#: minutes of run — wide enough that one hiccup row amortizes away)
_RATE_WINDOW_ROWS = 12


def _steps_per_sec(rows: List[dict],
                   window: int = _RATE_WINDOW_ROWS) -> Optional[float]:
    """WINDOWED rate over the newest ``window`` scalar rows: endpoints
    only, so one hiccup row (an eval pause, a checkpoint, a torn write)
    moves the estimate by its share of the window instead of swinging
    the whole dashboard the way the old newest-pair rate did."""
    scalars = [r for r in rows if "event" not in r and "step" in r
               and "time" in r]
    if len(scalars) < 2:
        return None
    tail = scalars[-max(2, window):]
    # a restart resets the step counter mid-tail: rate only over the
    # monotone suffix
    suffix = [tail[-1]]
    for r in reversed(tail[:-1]):
        if r["step"] >= suffix[0]["step"] or r["time"] >= suffix[0]["time"]:
            break
        suffix.insert(0, r)
    a, b = suffix[0], suffix[-1]
    dt = b["time"] - a["time"]
    ds = b["step"] - a["step"]
    if dt <= 0 or ds <= 0:
        return None
    return ds / dt


def summarize_stream(stream_dir: str, now: Optional[float] = None) -> dict:
    """One stream's rollup (a stream = one directory holding
    metrics.jsonl, e.g. ``<log_root>/train``)."""
    now = time.time() if now is None else now
    rows = _read_rows(stream_dir)
    out: dict = {"rows": len(rows)}
    scalar = _last(rows, None)
    if scalar is not None:
        out["step"] = int(scalar["step"])
        out["age_secs"] = round(now - scalar["time"], 1)
        for key in ("loss", "precision", "eval/precision"):
            if key in scalar:
                out[key.replace("/", "_")] = round(float(scalar[key]), 4)
    rate = _steps_per_sec(rows)
    if rate is not None:
        out["steps_per_sec"] = round(rate, 3)
    gp = _last(rows, "goodput")
    if gp is not None and "pct" in gp:
        out["goodput_pct"] = gp["pct"].get("compute")
        out["goodput"] = gp["pct"]
    strag = _last(rows, "straggler")
    if strag is not None:
        out["lag_steps"] = strag.get("lag_steps")
        out["stragglers_flagged"] = strag.get("flagged")
    hb = _last(rows, "heartbeat")
    if hb is not None:
        out["heartbeat_hosts"] = {
            pid: {"step": h.get("step"), "phase": h.get("phase"),
                  "host": h.get("host")}
            for pid, h in (hb.get("hosts") or {}).items()}
    sr = _last(rows, "serve_request")
    if sr is not None:
        out["serve"] = {"requests": sr.get("requests"),
                        "dropped": sr.get("dropped"),
                        "buckets": sr.get("buckets")}
    sb = _last(rows, "serve_batch")
    if sb is not None:
        out.setdefault("serve", {})["last_batch"] = {
            "bucket": sb.get("bucket"), "n": sb.get("n"),
            "run_ms": sb.get("run_ms")}
    # fleet front door (serve/router.py): the route stream's periodic
    # rollup row plus the newest canary / shed / replace events — enough
    # to render the fleet line without re-deriving router state
    rt = _last(rows, "route")
    if rt is not None:
        out["route"] = {
            "requests": rt.get("requests"),
            "completed": rt.get("completed"),
            "errors": rt.get("errors"), "shed": rt.get("shed"),
            "degraded": rt.get("degraded"), "hedges": rt.get("hedges"),
            "retries": rt.get("retries"), "qps": rt.get("qps"),
            "p99_ms": rt.get("p99_ms"),
            "replicas": rt.get("replicas"),
            "age_secs": round(now - rt.get("time", now), 1)}
    cn = _last(rows, "canary")
    if cn is not None:
        out["canary"] = {
            "action": cn.get("action"), "step": cn.get("step"),
            "from_step": cn.get("from_step"), "canary": cn.get("canary"),
            "rollback": cn.get("rollback"), "reason": cn.get("reason")}
    sh = _last(rows, "shed")
    if sh is not None:
        out["shed"] = {"count": sh.get("count"),
                       "degraded": sh.get("degraded"),
                       "est_queue_ms": sh.get("est_queue_ms")}
    rr = _last(rows, "replica_replace")
    if rr is not None:
        out["replica_replace"] = {
            "replica": rr.get("replica"), "action": rr.get("action"),
            "reason": rr.get("reason")}
    dump = _last(rows, "trace_dump")
    if dump is not None:
        out["trace_dump"] = {"reason": dump.get("reason"),
                             "path": dump.get("path")}
    cs = _last(rows, "ckpt_shard")
    if cs is not None:
        out["ckpt_shard"] = {
            "process": cs.get("process"),
            "shard_bytes": cs.get("shard_bytes"),
            "shard_files": cs.get("shard_files"),
            "shard_seconds": cs.get("shard_seconds"),
            "last_committed_step": cs.get("last_committed_step")}
    z1 = _last(rows, "zero1")
    if z1 is not None:
        out["zero1"] = {
            "data_shards": z1.get("data_shards"),
            "bytes_per_replica": z1.get("bytes_per_replica"),
            "bytes_per_replica_unsharded":
                z1.get("bytes_per_replica_unsharded")}
    cr = _last(rows, "corrupt_record")
    if cr is not None:
        out["corrupt_records"] = cr.get("count")
    mg = _last(rows, "mesh_generation")
    if mg is not None:
        out["mesh_generation"] = {
            "generation": mg.get("generation"),
            "hosts": mg.get("hosts"),
            "devices": mg.get("devices"),
            "step": mg.get("step")}
    rs = _last(rows, "reshard")
    if rs is not None:
        out["reshard"] = {
            "generation": rs.get("generation"),
            "reason": rs.get("reason"),
            "old_hosts": rs.get("old_hosts"),
            "new_hosts": rs.get("new_hosts"),
            "restore_step": rs.get("restore_step"),
            "age_secs": round(now - rs.get("time", now), 1)}
    mem = _last(rows, "memory")
    if mem is not None:
        out["memory"] = _memory_summary(mem)
    return out


def _memory_summary(row: dict) -> dict:
    """One memory row folded to the rollup's per-host shape: the worst
    device's watermark (allocator ``peak_bytes_in_use`` where the backend
    reports it — authoritative — else the sampled live-array peak) plus
    its limit when known, host RSS, and the pipeline-pool occupancy."""
    peak = limit = None
    for cell in (row.get("devices") or {}).values():
        p = cell.get("peak_bytes_in_use", cell.get("live_peak_bytes"))
        if p is not None:
            peak = max(peak or 0, int(p))
        if cell.get("bytes_limit"):
            limit = max(limit or 0, int(cell["bytes_limit"]))
    out = {"process": row.get("process")}
    for key in ("live_bytes_total", "live_peak_bytes_total",
                "host_rss_bytes", "host_peak_rss_bytes",
                "echo_cache_bytes", "staging_ring_inflight"):
        if row.get(key) is not None:
            out[key] = row[key]
    if peak is not None:
        out["device_peak_bytes"] = peak
    if limit:
        out["device_bytes_limit"] = limit
        if peak is not None:
            out["device_peak_frac"] = round(peak / limit, 4)
    return out


def _beat_files(root: str) -> List[str]:
    return sorted(glob.glob(os.path.join(root, "**", "proc*.json"),
                            recursive=True))


def _read_beats(root: str, now: float) -> Dict[str, dict]:
    """Per-process latest beat across every heartbeat dir under root —
    the same files resilience/heartbeat.FileBeatTransport exchanges."""
    out: Dict[str, dict] = {}
    for path in _beat_files(root):
        if "heartbeats" not in os.path.dirname(path):
            continue
        try:
            with open(path) as f:
                beat = json.load(f)
        except (OSError, ValueError):
            continue
        pid = str(beat.get("process_id", "?"))
        prev = out.get(pid)
        if prev is None or beat.get("wall_time", 0) > prev.get("wall_time", 0):
            beat["age_secs"] = round(now - beat.get("wall_time", now), 1)
            out[pid] = beat
    return out


def _checkpoint_step(root: str) -> Optional[int]:
    """Newest committed step of any ``ckpt`` directory under root."""
    from ..resilience.manifest import committed_steps
    newest: Optional[int] = None
    for d in glob.glob(os.path.join(root, "**", "ckpt"), recursive=True) \
            + [os.path.join(root, "ckpt")]:
        try:
            steps = committed_steps(d)
        except OSError:
            continue
        if steps:
            newest = steps[-1] if newest is None else max(newest, steps[-1])
    return newest


#: per-host device-memory watermark share of the limit that flags in the
#: dashboard (where the backend reports bytes_limit); --hbm-warn-frac
_HBM_WARN_FRAC = 0.9


def aggregate(root: str, now: Optional[float] = None,
              hbm_warn_frac: float = _HBM_WARN_FRAC) -> dict:
    """The whole-run rollup: every metrics stream under ``root``, the
    heartbeat fleet, the newest committed checkpoint."""
    now = time.time() if now is None else now
    root = os.path.abspath(root)
    from ..utils.metrics import metric_stream_dirs
    streams: Dict[str, dict] = {}
    for d in metric_stream_dirs(root):
        rel = os.path.relpath(d, root)
        if rel in streams:
            continue
        streams[rel] = summarize_stream(d, now=now)
    beats = _read_beats(root, now)
    out: dict = {"root": root, "time": now, "streams": streams}
    if beats:
        out["hosts"] = beats
        steps = [b.get("step", 0) for b in beats.values()]
        if steps:
            out["host_step_skew"] = max(steps) - min(steps)
        stale = [pid for pid, b in beats.items()
                 if b.get("age_secs", 0) > 60
                 and b.get("phase") not in ("done", "preempted", "failed",
                                            "reshard")]
        if stale:
            out["stale_hosts"] = stale
        # elastic fleet shape: the beats carry the mesh generation each
        # process is currently stepping in (resilience/heartbeat.py);
        # the live count excludes departed phases
        gens = [b.get("generation") for b in beats.values()
                if b.get("generation") is not None]
        if gens:
            out["mesh_generation"] = max(gens)
            out["live_hosts"] = sum(
                1 for b in beats.values()
                if b.get("generation") == out["mesh_generation"]
                and b.get("phase") not in ("done", "preempted", "failed",
                                           "reshard"))
    ckpt = _checkpoint_step(root)
    if ckpt is not None:
        out["last_committed_step"] = ckpt
    # per-host sharded-checkpoint rollup: each process's ckpt_shard rows
    # (chief in its train stream, peers in train-p<idx>) sum to the
    # cluster's staged shard bytes — the number that shows host-balanced
    # sharded saves are actually host-balanced
    shard_hosts = {name: s["ckpt_shard"] for name, s in streams.items()
                   if "ckpt_shard" in s}
    if shard_hosts:
        by_host = {}
        for row in shard_hosts.values():
            pid = str(row.get("process", "?"))
            prev = by_host.get(pid)
            if prev is None or (row.get("shard_bytes") or 0) > \
                    (prev.get("shard_bytes") or 0):
                by_host[pid] = row
        out["ckpt_shard_bytes_by_host"] = {
            pid: row.get("shard_bytes") for pid, row in
            sorted(by_host.items())}
        out["ckpt_shard_bytes_total"] = sum(
            row.get("shard_bytes") or 0 for row in by_host.values())
    # per-host device-memory watermark: each process samples its OWN
    # devices (chief in its train stream, peers in train-p<idx>), so the
    # per-pid max over streams IS the cluster's HBM picture — the trend
    # an OOM used to be the first sign of. A colocated serving replica
    # is a DIFFERENT process with the same jax.process_index(); it gets
    # its own "<pid>/serve" entry rather than shadowing (or being
    # shadowed by) the trainer's watermark
    mem_by_host: Dict[str, dict] = {}
    for name, s in streams.items():
        m = s.get("memory")
        if m is None:
            continue
        pid = str(m.get("process", "?"))
        if os.path.basename(name).startswith("serve"):
            pid = f"{pid}/serve"
        prev = mem_by_host.get(pid)
        if prev is None or (m.get("device_peak_bytes") or 0) > \
                (prev.get("device_peak_bytes") or 0):
            mem_by_host[pid] = m
    if mem_by_host:
        out["memory_by_host"] = {
            pid: m for pid, m in sorted(mem_by_host.items())}
        warn = sorted(
            pid for pid, m in mem_by_host.items()
            if m.get("device_peak_frac") is not None
            and m["device_peak_frac"] >= hbm_warn_frac)
        if warn:
            out["hbm_warn_frac"] = hbm_warn_frac
            out["hbm_warn_hosts"] = warn
    # fleet front door rollup: the route stream carries the router's own
    # periodic row (per-replica health snapshot included), and the same
    # stream's newest canary/shed/replace events ride along — the
    # operator's one-glance answer to "is the fleet healthy, is a
    # rollout in flight, are we shedding"
    fleets = {name: s["route"] for name, s in streams.items()
              if "route" in s}
    if fleets:
        lead_fleet = max(fleets,
                         key=lambda n: fleets[n].get("requests") or 0)
        fleet = dict(fleets[lead_fleet])
        fleet["stream"] = lead_fleet
        for key in ("canary", "shed", "replica_replace"):
            if key in streams[lead_fleet]:
                fleet[key] = streams[lead_fleet][key]
        out["fleet"] = fleet
    # headline: the fastest train-shaped stream is the chief's
    rates = {name: s["steps_per_sec"] for name, s in streams.items()
             if "steps_per_sec" in s}
    if rates:
        lead = max(rates, key=rates.get)
        out["steps_per_sec"] = rates[lead]
        out["lead_stream"] = lead
    for name, s in streams.items():
        if "goodput" in s:
            out.setdefault("goodput", s["goodput"])
            break
    # newest reshard / mesh_generation event rows across streams (the
    # chief emits them; a fresh generation may write to a new stream)
    for key, field in (("last_reshard", "reshard"),
                       ("mesh_generation_event", "mesh_generation")):
        rows = [s[field] for s in streams.values() if field in s]
        if rows:
            out[key] = max(rows, key=lambda r: r.get("generation") or 0)
            if "mesh_generation" not in out and \
                    out[key].get("generation") is not None:
                out["mesh_generation"] = out[key]["generation"]
    return out


def render(agg: dict) -> str:
    """Human-readable dashboard frame."""
    lines = [f"== drt monitor :: {agg['root']} :: "
             f"{time.strftime('%H:%M:%S', time.localtime(agg['time']))} =="]
    if "steps_per_sec" in agg:
        lines.append(f"  steps/s: {agg['steps_per_sec']:.3f} "
                     f"({agg.get('lead_stream')})")
    if "goodput" in agg:
        gp = agg["goodput"]
        lines.append("  goodput: " + "  ".join(
            f"{c} {gp.get(c, 0):.1f}%" for c in
            ("compute", "input_wait", "checkpoint", "eval", "stall",
             "restart", "reshard") if gp.get(c)))
    if "mesh_generation" in agg:
        bits = [f"  elastic: generation {agg['mesh_generation']}"]
        if "live_hosts" in agg:
            bits.append(f"{agg['live_hosts']} live host(s)")
        rs = agg.get("last_reshard")
        if rs:
            bits.append(
                f"last reshard {rs.get('reason')} "
                f"{rs.get('old_hosts')}->{rs.get('new_hosts')} hosts "
                f"(restore step {rs.get('restore_step')}, "
                f"{rs.get('age_secs', '?')}s ago)")
        lines.append(", ".join(bits))
    if "last_committed_step" in agg:
        lines.append(f"  checkpoint: step {agg['last_committed_step']} "
                     "committed")
    if "fleet" in agg:
        fl = agg["fleet"]
        reps = fl.get("replicas") or {}
        states = " ".join(
            f"r{rid}:{(cell or {}).get('state', '?')}"
            f"@{(cell or {}).get('step', '?')}"
            for rid, cell in sorted(reps.items()))
        bits = ["  fleet:"]
        if fl.get("qps") is not None:
            bits.append(f"qps {fl['qps']:.1f}")
        if fl.get("p99_ms") is not None:
            bits.append(f"p99 {fl['p99_ms']:.0f}ms")
        bits.append(f"errors {fl.get('errors', 0)}")
        bits.append(f"shed {fl.get('shed', 0)}")
        bits.append(f"degraded {fl.get('degraded', 0)}")
        bits.append(f"hedges {fl.get('hedges', 0)}")
        lines.append(" ".join(bits) + f" | {states}")
        cn = fl.get("canary")
        if cn:
            verdict = ("ROLLED BACK" if cn.get("rollback")
                       else cn.get("action"))
            lines.append(
                f"  canary: {verdict} step {cn.get('step')} "
                f"(from {cn.get('from_step')}) on {cn.get('canary')} "
                f"reason {cn.get('reason', '-')}")
        rr = fl.get("replica_replace")
        if rr:
            lines.append(
                f"  replace: replica {rr.get('replica')} "
                f"{rr.get('action')} ({rr.get('reason')})")
    if "ckpt_shard_bytes_total" in agg:
        per_host = agg.get("ckpt_shard_bytes_by_host", {})
        mb = agg["ckpt_shard_bytes_total"] / 1e6
        lines.append(
            f"  ckpt shards: {mb:.1f} MB staged across "
            f"{len(per_host)} host(s) " + " ".join(
                f"p{pid}:{(b or 0) / 1e6:.1f}MB"
                for pid, b in per_host.items()))
    if "memory_by_host" in agg:
        bits = []
        for pid, m in agg["memory_by_host"].items():
            peak = m.get("device_peak_bytes",
                         m.get("live_peak_bytes_total"))
            cell = f"p{pid}:{(peak or 0) / 1e9:.2f}GB"
            if m.get("device_peak_frac") is not None:
                cell += f"({m['device_peak_frac'] * 100:.0f}%)"
            bits.append(cell)
        lines.append("  hbm watermark (per-host device peak): "
                     + " ".join(bits))
        if agg.get("hbm_warn_hosts"):
            lines.append(
                f"  !! hbm above {agg['hbm_warn_frac'] * 100:.0f}% of "
                f"limit on host(s): {agg['hbm_warn_hosts']}")
    if "hosts" in agg:
        lines.append(f"  hosts ({len(agg['hosts'])}; "
                     f"skew {agg.get('host_step_skew', 0)} steps):")
        for pid, b in sorted(agg["hosts"].items()):
            lines.append(
                f"    proc{pid} {b.get('host', '?')}: step "
                f"{b.get('step', '?')} phase {b.get('phase', '?')} "
                f"(beat {b.get('age_secs', '?')}s ago)")
    if agg.get("stale_hosts"):
        lines.append(f"  !! stale hosts: {agg['stale_hosts']}")
    for name, s in sorted(agg["streams"].items()):
        bits = [f"  [{name}]"]
        if "step" in s:
            bits.append(f"step {s['step']}")
        if "steps_per_sec" in s:
            bits.append(f"{s['steps_per_sec']:.3f} st/s")
        for k in ("loss", "precision", "eval_precision"):
            if k in s:
                bits.append(f"{k} {s[k]}")
        if "serve" in s:
            srv = s["serve"]
            bits.append(f"serve req {srv.get('requests')} "
                        f"dropped {srv.get('dropped')}")
        if "trace_dump" in s:
            bits.append(f"TRACE DUMPED ({s['trace_dump'].get('reason')})")
        if "corrupt_records" in s:
            bits.append(f"corrupt_records {s['corrupt_records']}")
        lines.append(" ".join(bits))
    return "\n".join(lines)


def main_monitor(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="main.py monitor",
        description="live cluster rollup over a run's log_root")
    ap.add_argument("--root", default="/tmp/drt_tpu",
                    help="the run's log_root (shared directory)")
    ap.add_argument("--once", action="store_true",
                    help="render one frame and exit")
    ap.add_argument("--json", action="store_true",
                    help="emit the aggregate as JSON instead of text")
    ap.add_argument("--interval", type=float, default=5.0,
                    help="refresh cadence in seconds (live mode)")
    ap.add_argument("--hbm-warn-frac", type=float, default=_HBM_WARN_FRAC,
                    help="flag hosts whose device watermark exceeds this "
                         "share of the reported bytes_limit")
    ns = ap.parse_args(argv)
    try:
        while True:
            agg = aggregate(ns.root, hbm_warn_frac=ns.hbm_warn_frac)
            print(json.dumps(agg) if ns.json else render(agg), flush=True)
            if ns.once:
                return 0
            time.sleep(max(0.2, ns.interval))
    except KeyboardInterrupt:
        return 0
