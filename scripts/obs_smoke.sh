#!/bin/bash
# Observability smoke (docs/observability.md, ISSUE 14) — the performance
# measurement plane end-to-end, no accelerator needed:
#
#   2-process training with one SLOW-BUT-ALIVE peer
#            (DRT_FAULT_SLOW_BATCH_SECS=pid:S@N — delay from batch N, so
#            the perf-anomaly sentinel sees a healthy baseline first).
#            Asserts: a {"event": "perf_anomaly"} row, the anomaly-
#            triggered flight-recorder dump, nonzero {"event": "memory"}
#            rows on BOTH hosts, `main.py trace-merge` producing one
#            valid Perfetto JSON with per-host lanes + clock-offset
#            metadata, and `main.py monitor` rolling up the per-host HBM
#            watermark + windowed steps/s.
#
#   scripts/obs_smoke.sh            # ~2 min on a laptop
#   OBS_SMOKE=1 scripts/chaos_smoke.sh --fast   # opt-in from the gate
set -euo pipefail
cd "$(dirname "$0")/.."

PY=${PYTHON:-python}
TROOT=$(mktemp -d)
trap 'rm -rf "$TROOT"' EXIT

# ---------------------------------------------------------------------------
echo "== obs_smoke: slow-peer run -> anomaly + memory + merge =="
PORT=$((20000 + RANDOM % 20000))
env JAX_PLATFORMS=cpu DRT_FAULT_SLOW_BATCH_SECS="1:0.6@30" \
  timeout -k 10 300 \
  "$PY" -m distributed_resnet_tensorflow_tpu.launch \
  --num_processes 2 --devices_per_process 1 --port "$PORT" -- \
  --preset smoke \
  --set model.name=logistic --set model.input_size=192 \
  --set model.num_classes=10 --set data.image_size=8 \
  --set train.batch_size=16 --set train.train_steps=45 \
  --set train.log_every_steps=10 --set train.summary_every_steps=5 \
  --set "log_root=$TROOT" \
  --set checkpoint.save_every_steps=0 --set checkpoint.save_every_secs=0 \
  --set resilience.watchdog.enabled=on \
  --set resilience.watchdog.interval_secs=0.2 \
  --set resilience.watchdog.peer_timeout_secs=60 \
  --set resilience.watchdog.min_step_timeout_secs=120 \
  --set resilience.watchdog.straggler_window_secs=3 \
  --set telemetry.anomaly_min_samples=12 \
  --set telemetry.anomaly_window=24 \
  --set telemetry.anomaly_cooldown_secs=5

"$PY" - "$TROOT" <<'PY'
import glob, json, sys
root = sys.argv[1]
rows = []
for path in glob.glob(root + "/**/metrics.jsonl", recursive=True):
    for line in open(path):
        try:
            rows.append(json.loads(line))
        except ValueError:
            pass
anoms = [r for r in rows if r.get("event") == "perf_anomaly"]
assert anoms, "no perf_anomaly row — the sentinel missed a 4x-slow step"
assert anoms[0]["step_secs"] > anoms[0]["threshold_secs"]
dumps = [r for r in rows if r.get("event") == "trace_dump"
         and r.get("reason") == "perf_anomaly"]
assert dumps, "anomaly fired but left no flight-recorder trace_dump row"
mem = [r for r in rows if r.get("event") == "memory"]
procs = {r.get("process") for r in mem}
assert len(mem) > 0 and procs >= {0, 1}, \
    f"memory rows missing a host: {len(mem)} rows from processes {procs}"
traces = glob.glob(root + "/telemetry/trace*.json")
assert traces, "no trace*.json dumped"
print(f"  ok: {len(anoms)} perf_anomaly row(s), {len(mem)} memory row(s) "
      f"from processes {sorted(procs)}, {len(traces)} trace dump(s)")
PY

env JAX_PLATFORMS=cpu "$PY" -m distributed_resnet_tensorflow_tpu.main \
  trace-merge --root "$TROOT"
"$PY" - "$TROOT" <<'PY'
import json, sys
doc = json.load(open(sys.argv[1] + "/telemetry/trace.merged.json"))
other = doc["otherData"]
assert other["merged"] is True
lanes = {s["process_index"] for s in other["sources"]}
assert lanes == {0, 1}, f"expected lanes for both hosts, got {lanes}"
assert other["clock_offsets"], "no heartbeat-estimated clock offsets"
spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
assert spans, "merged trace has no spans"
names = [e for e in doc["traceEvents"] if e.get("name") == "process_name"]
assert len(names) == 2
print(f"  ok: merged trace has {len(spans)} span(s) across 2 host lanes, "
      f"offsets for {sorted(other['clock_offsets'])}")
PY

env JAX_PLATFORMS=cpu "$PY" -m distributed_resnet_tensorflow_tpu.main \
  monitor --root "$TROOT" --once --json > "$TROOT/agg.json"
"$PY" - "$TROOT/agg.json" <<'PY'
import json, sys
agg = json.load(open(sys.argv[1]))
assert "steps_per_sec" in agg, "monitor: no windowed steps/s"
mem = agg.get("memory_by_host") or {}
assert set(mem) >= {"0", "1"}, f"monitor: HBM rollup missing a host: {mem}"
print(f"  ok: monitor steps/s {agg['steps_per_sec']} + per-host HBM "
      f"watermark for hosts {sorted(mem)}")
PY

echo "obs_smoke: passed"
