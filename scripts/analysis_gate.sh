#!/bin/bash
# Shardcheck gate — the seconds-fast correctness check that runs BEFORE a
# cluster allocation is spent (docs/static_analysis.md):
#
#   * project-invariant lint (analysis/rules/): stray device_put, cached
#     meshes, bare asserts, undeclared exit codes, metrics-event/config
#     drift against the declared registries;
#   * static elaboration (analysis/elaborate.py): every preset × mesh
#     layout traced abstractly on a virtual CPU mesh — PartitionSpec,
#     shape and config bugs surface here with the offending param path,
#     not as a step-1 _SpecError after a 20-minute queue wait.
#
#   scripts/analysis_gate.sh                 # full gate (lint + elaborate
#                                            #   + zero1 sweep + hangcheck
#                                            #   + plan-drift + protocol)
#   scripts/analysis_gate.sh --lint-only     # sub-second syntax/invariant pass
#   scripts/analysis_gate.sh --no-hangcheck  # skip the hangcheck phases
#                                            #   (mirrors --no-zero1-sweep,
#                                            #   --no-plan-drift,
#                                            #   --no-protocol)
#
# Wired as a pre-submit step in scripts/submit_tpu_slurm.sh and into the
# pre-merge chaos gate (scripts/chaos_smoke.sh --fast). Exit 0 = clean,
# 1 = findings (per the resilience.EXIT_CONTRACT failure code).
#
# Budget contract (docs/static_analysis.md): the FULL gate finishes in
# <300 s — per-phase wall times are printed by the check CLI (lint /
# elaborate / elab-zero1 / hangcheck-schedule / plan-drift / protocol
# lines — the plan-drift phase (ISSUE 17, docs/planner.md) re-costs the
# what-if planner over the committed schedules and refreshes
# analysis/plan_catalog.json; measured ~3-6 s; the protocol phase
# (ISSUE 20) exhaustively model-checks the four declared control-plane
# protocols and refreshes analysis/protocol_models.json; measured
# <0.5 s — both well inside the same
# 300 s envelope), and this script
# fails loudly when the total busts the budget, so creep shows up as a
# red gate in the PR that caused it, not as a slow submit host months
# later. Scoped runs (--lint-only, --preset, --no-*) enforce the same
# ceiling trivially.
#
# Budget history: the original <120 s contract TRIPPED at HEAD on a
# loaded container (129 s, 0 findings — wall time on this box drifts ~2x
# under concurrent load for identical code); with the bucketed-exchange
# traces the full gate measured ~160-260 s and the budget became 300 s.
# Those traces went in PR 31 (the gate measured 168 s after it: elaborate
# 107 s, the schedule phase about 45 s).
# Raise the budget only with a matching measurement, and look at the
# per-phase echo before blaming it.
set -euo pipefail
cd "$(dirname "$0")/.."

GATE_BUDGET_SECS=${GATE_BUDGET_SECS:-300}
start=$(date +%s)

# all presets is `check`'s default — not hardcoded here, so pass-through
# args like `--preset smoke` or `--lint-only` scope the gate cleanly
rc=0
env JAX_PLATFORMS=cpu python -m distributed_resnet_tensorflow_tpu.main \
  check "$@" || rc=$?

elapsed=$(( $(date +%s) - start ))
echo "analysis_gate: total ${elapsed}s (budget ${GATE_BUDGET_SECS}s)"
if [[ $elapsed -gt $GATE_BUDGET_SECS ]]; then
  echo "analysis_gate: BUDGET EXCEEDED — the gate took ${elapsed}s," \
       "contract is <${GATE_BUDGET_SECS}s (docs/static_analysis.md)." \
       "Find the phase that crept in the per-phase times above." >&2
  exit 1
fi
exit $rc
