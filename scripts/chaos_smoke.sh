#!/bin/bash
# Chaos smoke — run the fault-injection suite (resilience/faultinject.py):
# signal delivery mid-run, torn/bit-rotted checkpoints, injected NaN loss,
# plus the watchdog cases (killed peer, frozen peer, straggler —
# tests/test_watchdog.py + the subprocess kill-and-detect tests in
# tests/test_resilience.py). Everything runs on the fake-CPU mesh
# (tests/conftest.py) — no accelerator needed.
#
#   scripts/chaos_smoke.sh            # the FULL chaos set (incl. the
#                                     # slow-tier multi-process subprocess
#                                     # kill/freeze tests — ~minutes of real
#                                     # training children)
#   scripts/chaos_smoke.sh --fast     # seconds-fast pre-merge gate:
#                                     # shardcheck + -m "not slow and not heavy"
#   scripts/chaos_smoke.sh --elastic  # elastic-mesh e2e only: freeze one of
#                                     # four workers; assert shrink->grow with
#                                     # rc=0 and NO exit-75 (docs/resilience.md)
#   scripts/chaos_smoke.sh -k nan     # just the NaN-recovery cases
#
# NOTE: the subprocess/watchdog chaos tests are marked `slow` (tier-1 of
# the main suite excludes them for the 870 s budget) — this script is
# where they run, so the default mode deliberately applies NO marker
# filter over the two chaos test files.
set -euo pipefail
cd "$(dirname "$0")/.."

MARK_ARGS=()
if [[ "${1:-}" == "--fast" ]]; then
  MARK_ARGS=(-m "not slow and not heavy")
  shift
  # the fast pre-merge gate also runs shardcheck (lint + static
  # elaboration + hangcheck's collective-schedule/thread/lock passes,
  # scripts/analysis_gate.sh): spec/config/invariant/hang bugs should
  # die here, in seconds, not on the cluster. ANALYSIS_GATE_ARGS
  # passes through (e.g. --no-hangcheck, mirroring --no-zero1-sweep)
  scripts/analysis_gate.sh ${ANALYSIS_GATE_ARGS:-}
  # opt-in observability stage (OBS_SMOKE=1): the slow-peer perf-anomaly
  # + trace-merge end-to-end (scripts/obs_smoke.sh, ~2 min
  # of live 2-process training — too heavy for the default seconds-fast
  # gate, which is why it is opt-in)
  if [[ "${OBS_SMOKE:-0}" == "1" ]]; then
    scripts/obs_smoke.sh
  fi
fi

if [[ "${1:-}" == "--elastic" ]]; then
  shift
  # Elastic-mesh smoke (docs/resilience.md): freeze one of FOUR workers
  # mid-training. The frozen worker's own watchdog exits it 75 (hang in the
  # host-local 'data' phase); the survivors defer their collective-hang
  # exits, attribute the peer loss, and shrink into a 3-host generation
  # restored from the last committed step; the supervisor's respawned
  # rejoiner grows the mesh back to 4 hosts; the run completes rc=0 — the
  # exit-75 requeue contract is now the FALLBACK, not the outcome.
  TROOT=$(mktemp -d)
  trap 'rm -rf "$TROOT"' EXIT
  PORT=$((20000 + RANDOM % 20000))
  set +e
  timeout -k 10 420 env JAX_PLATFORMS=cpu DRT_FAULT_FREEZE_AT_BATCH="3:8" \
    python -m distributed_resnet_tensorflow_tpu.launch \
    --num_processes 4 --devices_per_process 1 --port "$PORT" \
    --elastic --max_respawns 2 --respawn_delay_secs 2 -- \
    --preset smoke \
    --set model.name=logistic --set model.input_size=192 \
    --set model.num_classes=10 --set data.image_size=8 \
    --set train.batch_size=16 --set train.train_steps=60 \
    --set train.log_every_steps=5 --set "log_root=$TROOT" \
    --set checkpoint.save_every_steps=5 --set checkpoint.save_every_secs=0 \
    --set resilience.elastic.enabled=on \
    --set resilience.elastic.settle_secs=1 \
    --set resilience.watchdog.enabled=on \
    --set resilience.watchdog.interval_secs=0.2 \
    --set resilience.watchdog.peer_timeout_secs=5 \
    --set resilience.watchdog.min_step_timeout_secs=3 \
    --set resilience.watchdog.grace_secs=1
  rc=$?
  set -e
  if [[ $rc -ne 0 ]]; then
    echo "chaos_smoke --elastic: run exited $rc, expected 0 (no requeue)" >&2
    exit 1
  fi
  python - "$TROOT/train/metrics.jsonl" <<'PY'
import json, sys
rows = [json.loads(l) for l in open(sys.argv[1]) if l.strip()]
gens = {r["generation"] for r in rows if r.get("event") == "mesh_generation"}
reshards = [r for r in rows if r.get("event") == "reshard"]
reasons = {r["reason"] for r in reshards}
assert {0, 1, 2} <= gens, f"expected generations 0,1,2, saw {gens}"
assert "peer_lost" in reasons and "grow" in reasons, reasons
shrink = next(r for r in reshards if r["reason"] == "peer_lost")
grow = next(r for r in reshards if r["reason"] == "grow")
assert (shrink["old_hosts"], shrink["new_hosts"]) == (4, 3), shrink
assert (grow["old_hosts"], grow["new_hosts"]) == (3, 4), grow
assert shrink["restore_step"] >= 0, "shrink restarted instead of resuming"
print("elastic smoke: shrink restored step", shrink["restore_step"],
      "-> grow live at generation", grow["generation"])
PY
  # protocol trace conformance (analysis/protocol/): the reshard /
  # mesh_generation rows this chaos run recorded must replay cleanly
  # against the declared elastic-reshard-barrier spec, and the seeded
  # illegal-edge self-test proves the witness can actually fail
  env JAX_PLATFORMS=cpu python -m \
    distributed_resnet_tensorflow_tpu.analysis.protocol.conformance \
    "$TROOT/train/metrics.jsonl"
  env JAX_PLATFORMS=cpu python -m \
    distributed_resnet_tensorflow_tpu.analysis.protocol.conformance \
    --self-test-illegal-edge "$TROOT/train/metrics.jsonl"
  echo "chaos_smoke: elastic shrink->grow verified (rc=0, no exit-75," \
       "protocol trace conformant)"
  exit 0
fi

# ${arr[@]+...} form: bash <4.4 trips set -u on expanding an empty array
env JAX_PLATFORMS=cpu python -m pytest \
  tests/test_resilience.py tests/test_watchdog.py -q \
  ${MARK_ARGS[@]+"${MARK_ARGS[@]}"} -p no:cacheprovider "$@"

if [[ ${#MARK_ARGS[@]} -gt 0 ]]; then
  exit 0  # --fast gate: the flight-recorder e2e below is full-mode only
fi

# Flight-recorder smoke (docs/observability.md): freeze one of two live
# workers mid-training (the faultinject env knob) and assert the watchdog
# escalation leaves an AUTOMATIC trace dump — a trace*.json under
# <log_root>/telemetry plus a {"event": "trace_dump"} row in the chief's
# metrics — and the run still exits resumable (75).
TROOT=$(mktemp -d)
trap 'rm -rf "$TROOT"' EXIT
PORT=$((20000 + RANDOM % 20000))
set +e
timeout -k 10 240 env JAX_PLATFORMS=cpu DRT_FAULT_FREEZE_AT_BATCH="1:5" \
  python -m distributed_resnet_tensorflow_tpu.launch \
  --num_processes 2 --devices_per_process 1 --port "$PORT" -- \
  --preset smoke \
  --set model.name=logistic --set model.input_size=192 \
  --set model.num_classes=10 --set data.image_size=8 \
  --set train.batch_size=16 --set train.train_steps=100000 \
  --set train.log_every_steps=1000 --set "log_root=$TROOT" \
  --set checkpoint.save_every_steps=0 --set checkpoint.save_every_secs=0 \
  --set resilience.watchdog.enabled=on \
  --set resilience.watchdog.interval_secs=0.2 \
  --set resilience.watchdog.peer_timeout_secs=5 \
  --set resilience.watchdog.min_step_timeout_secs=3 \
  --set resilience.watchdog.grace_secs=1
rc=$?
set -e
if [[ $rc -ne 75 ]]; then
  echo "chaos_smoke: frozen-peer run exited $rc, expected resumable 75" >&2
  exit 1
fi
if ! ls "$TROOT"/telemetry/trace*.json >/dev/null 2>&1; then
  echo "chaos_smoke: no flight-recorder trace*.json under $TROOT/telemetry" >&2
  exit 1
fi
python - "$TROOT/telemetry" <<'PY'
import glob, json, sys
paths = glob.glob(sys.argv[1] + "/trace*.json")
doc = json.load(open(paths[0]))
assert doc["traceEvents"], "trace dump holds no events"
assert doc["otherData"]["span_schema_version"] >= 1
PY
if ! grep -q '"event": "trace_dump"' "$TROOT"/train/metrics.jsonl; then
  echo "chaos_smoke: no trace_dump event row in the chief's metrics" >&2
  exit 1
fi
echo "chaos_smoke: frozen-peer flight-recorder dump verified"
