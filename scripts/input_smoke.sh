#!/bin/bash
# Input-pipeline smoke (round 9) — the echoing / parallel-decode / fused-
# augment stack exercised end-to-end on synthetic JPEG data, CPU-only,
# in a couple of minutes:
#
#   * builds a tiny ImageNet-format TFRecord dataset (tools/make_synth_imagenet),
#   * trains N steps with data echoing (echo_factor=2), decode worker
#     PROCESSES (decode_processes=2), the fused on-device augmentation
#     (device_augment=on + coalesced_transfer=on) and the cross-thread
#     dispatch sanitizer ARMED,
#   * asserts from metrics.jsonl that the {"event": "input_stages"} rows
#     show more than one busy decode worker and the {"event": "input_echo"}
#     rows show echo hits > 0 — the telemetry contract the input
#     attribution is built on.
#
#   scripts/input_smoke.sh            # full smoke
#
# Exit 0 = green; any assertion failure or training error is nonzero.
set -euo pipefail
cd "$(dirname "$0")/.."

ROOT="${TMPDIR:-/tmp}/drt_input_smoke"
DATA="$ROOT/data"
LOGS="$ROOT/logs"
rm -rf "$ROOT"
mkdir -p "$DATA"

echo "== input_smoke: synthesizing JPEG TFRecord shards"
env JAX_PLATFORMS=cpu python - "$DATA" <<'PYEOF'
import sys, os
sys.path.insert(0, "tools")
from make_synth_imagenet import write_split
write_split(sys.argv[1], "train", 4, 4, num_classes=8, per_class=8, seed=0)
PYEOF

echo "== input_smoke: train with echoing + decode processes + fused augment"
env JAX_PLATFORMS=cpu python -m distributed_resnet_tensorflow_tpu.main \
  --preset imagenet_resnet50 \
  --set model.resnet_size=18 \
  --set model.num_classes=8 \
  --set model.compute_dtype=float32 \
  --set data.data_dir="$DATA" \
  --set data.image_size=32 \
  --set data.echo_factor=2 \
  --set data.decode_processes=2 \
  --set data.num_parallel_calls=2 \
  --set data.device_augment=on \
  --set data.coalesced_transfer=on \
  --set analysis.dispatch_sanitizer=true \
  --set train.batch_size=8 \
  --set train.train_steps=8 \
  --set train.log_every_steps=2 \
  --set train.summary_every_steps=2 \
  --set checkpoint.save_every_steps=0 \
  --set checkpoint.save_every_secs=0 \
  --set resilience.handle_signals=false \
  --set log_root="$LOGS"

echo "== input_smoke: asserting telemetry"
env JAX_PLATFORMS=cpu python - "$LOGS/train" <<'PYEOF'
import sys
from distributed_resnet_tensorflow_tpu.utils.metrics import read_metrics
rows = read_metrics(sys.argv[1], tolerant=True)
stages = [r for r in rows if r.get("event") == "input_stages"]
echo = [r for r in rows if r.get("event") == "input_echo"]
assert stages, "no input_stages rows exported"
last = stages[-1]["stages"]
dec = last.get("decode") or {}
assert dec.get("items", 0) > 0, f"no decode items recorded: {last}"
# >1 busy worker: the decode-process pool's per-worker counter merge
# (_StageDelta) must surface more than one worker cell
assert dec.get("workers", 0) > 1, \
    f"expected >1 busy decode workers, got {dec}"
assert echo, "no input_echo rows exported"
e = echo[-1]
assert e["hits"] > 0, f"expected echo hits > 0: {e}"
assert e["echo_factor"] == 2
print(f"input_smoke OK: decode workers={dec['workers']} "
      f"items={dec['items']}, echo hits={e['hits']} "
      f"hit_rate={e['hit_rate']}")
PYEOF

echo "== input_smoke: green"
