"""The ``plan-drift`` gate phase: re-check the what-if planner's
predictions and commit the diffable ``analysis/plan_catalog.json``.

Runs inside ``main.py check`` after hangcheck-schedule (which supplies
the freshly traced signatures). Three jobs:

  1. Re-cost every committed (layout, variant) candidate of the
     PLAN_PRESETS with the planner's baked-in REFERENCE constants
     (telemetry/planner.py) — fully deterministic, so the artifact this
     writes is byte-identical across runs and machines. A perf-relevant
     change (new collective, different wire bytes, a model change)
     shows up as a reviewable catalog diff next to the schedule diff.
  2. Sanity-findings on the model itself: every prediction finite and
     positive, every planned preset ranked with a recommendation —
     a catalog that silently lost a preset is a red gate, not a smaller
     file.
  3. Cross-check the fabric's MEASURED bandwidth catalog
     (results/bandwidth/<fabric>.json) against a live micro-probe on
     the virtual-8 mesh: one replicated psum, timed. A catalog claiming
     bandwidth off by more than ``PROBE_SANITY_FACTOR`` in either
     direction is a finding — the seeded-corruption contract
     (tests/test_planner.py): a bandwidth-table lie must fail the gate,
     because every live drift sentinel on this fabric inherits it.

Only three presets are costed (one per model family, including the MoE
member the acceptance bar names) — the phase must fit the analysis
gate's 300s budget next to lint/elaborate/hangcheck, and the other
presets' schedules are already byte-covered by the schedule artifact.
"""
from __future__ import annotations

import logging
import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

from .report import Finding

log = logging.getLogger(__name__)

RULE = "plan-drift"

#: presets the committed catalog covers: one ResNet/CIFAR, one
#: ResNet/ImageNet, one ViT-MoE (the vit/moe family the acceptance bar
#: requires) — a bounded, representative slice of the schedule artifact
PLAN_PRESETS = ("cifar10_resnet50", "imagenet_resnet50", "vit_moe")

#: a measured-catalog bandwidth may differ from the gate's micro-probe
#: by machine load / hardware generation, but not by this factor: wide
#: enough for any honest CPU/TPU spread, narrow enough that a corrupted
#: table (the 1e15 B/s lie) cannot hide
PROBE_SANITY_FACTOR = 100.0


def _micro_probe_bytes_per_sec(n_devices: int = 8,
                               payload_mb: float = 4.0,
                               reps: int = 3) -> Optional[float]:
    """Achieved bytes/sec of one replicated psum over every mesh axis —
    the cheapest honest bandwidth sample this process can take. None
    when the mesh cannot build (the cross-check degrades to skipped,
    not red: the catalog may outlive the machine that can probe it)."""
    import time as _time
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax import lax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from ..parallel.mesh import shard_map_unchecked
        if jax.device_count() < n_devices:
            return None
        devices = np.array(jax.devices()[:n_devices]).reshape(n_devices)
        mesh = Mesh(devices, ("data",))
        elems = max(1, int(payload_mb * 1e6) // 4)

        def _psum(x):
            return lax.psum(x, ("data",))

        fn = jax.jit(shard_map_unchecked(
            _psum, mesh, in_specs=P(), out_specs=P()))
        # deliberate direct put: the micro-probe times ONE replicated
        # psum on a throwaway mesh inside the analysis gate — routing
        # through parallel/sharding's stager would drag the training
        # transfer plumbing into a standalone diagnostic
        x = jax.device_put(jnp.zeros((elems,), jnp.float32),  # shardcheck: ok(stray-device-put)
                           NamedSharding(mesh, P()))
        jax.block_until_ready(fn(x))  # compile + warm
        best = None
        for _ in range(max(1, reps)):
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(x))
            dt = _time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return (elems * 4) / best if best and best > 0 else None
    except Exception as e:
        log.warning("plan-drift micro-probe unavailable (%s); bandwidth "
                    "catalog cross-check skipped", e)
        return None


def check_bandwidth_catalog(probe_bps: Optional[float] = None
                            ) -> List[Finding]:
    """Findings for a measured catalog that contradicts a live
    micro-probe beyond PROBE_SANITY_FACTOR. Silent when no catalog
    exists for this fabric (a fresh checkout has nothing to lie)."""
    from ..telemetry import bandwidth
    doc = bandwidth.load_catalog()
    if not doc:
        return []
    if probe_bps is None:
        probe_bps = _micro_probe_bytes_per_sec()
    if not probe_bps or probe_bps <= 0:
        return []
    findings: List[Finding] = []
    path = bandwidth.catalog_path(doc.get("fabric"))
    for sig in sorted(doc.get("axes", {})):
        bps = float(doc["axes"][sig].get("bytes_per_sec", 0.0))
        if bps <= 0 or not math.isfinite(bps):
            findings.append(Finding(
                RULE, path, 0,
                f"bandwidth catalog axes[{sig!r}]: non-positive/non-"
                f"finite bytes_per_sec {bps!r}"))
            continue
        ratio = bps / probe_bps
        if ratio > PROBE_SANITY_FACTOR or ratio < 1.0 / PROBE_SANITY_FACTOR:
            findings.append(Finding(
                RULE, path, 0,
                f"bandwidth catalog axes[{sig!r}] claims "
                f"{bps:.3g} B/s but a live micro-probe measured "
                f"{probe_bps:.3g} B/s (ratio {ratio:.3g}, tolerance "
                f"×{PROBE_SANITY_FACTOR:g}) — stale or corrupted "
                f"catalog; delete or re-probe it (docs/planner.md)"))
    return findings


def build_catalog(signatures: Dict[str, dict],
                  presets: Sequence[str] = PLAN_PRESETS,
                  n_devices: int = 8) -> Tuple[List[Finding], dict]:
    """(findings, catalog document). The document embeds the reference
    constants it was computed with, so a constant change diffs loudly
    in review instead of silently re-baselining every number."""
    from ..telemetry import planner

    findings: List[Finding] = []
    plans: Dict[str, dict] = {}
    table = planner.BandwidthTable.reference()
    for preset in presets:
        if not any(k.startswith(preset + "@") for k in signatures):
            findings.append(Finding(
                RULE, preset, 0,
                f"planned preset {preset!r} has no committed collective "
                "schedules — the hangcheck-schedule phase must trace it "
                "first"))
            continue
        plan = planner.plan_for_preset(preset, signatures,
                                       n_devices=n_devices,
                                       bandwidth=table)
        for key, cand in sorted(plan["candidates"].items()):
            for field in ("step_secs", "compute_secs", "comm_secs"):
                v = cand.get(field)
                if v is None or not math.isfinite(v) or v < 0 or \
                        (field in ("step_secs", "compute_secs") and v == 0):
                    findings.append(Finding(
                        RULE, f"{preset}:{key}", 0,
                        f"degenerate prediction {field}={v!r} — the "
                        "cost model lost an input (schedule bytes, "
                        "FLOPs table, or bandwidth row)"))
        if not plan.get("recommended"):
            findings.append(Finding(
                RULE, preset, 0,
                "no recommended layout — every candidate failed to "
                "cost"))
        plans[preset] = {
            "candidates": plan["candidates"],
            "ranked": plan["ranked"],
            "recommended": plan["recommended"],
        }
    doc = {
        "schema_version": 1,
        "devices": n_devices,
        "reference": {
            "bytes_per_sec": planner.REFERENCE_BYTES_PER_SEC,
            "latency_secs": planner.REFERENCE_LATENCY_SECS,
            "peak_tflops": planner.REFERENCE_PEAK_TFLOPS,
            "assumed_mfu": planner.ASSUMED_MFU,
            "train_flops_multiplier": planner.TRAIN_FLOPS_MULTIPLIER,
            "act_flops_per_byte": planner.ACT_FLOPS_PER_BYTE,
        },
        "plans": plans,
    }
    return findings, doc


def run_plan_drift(signatures: Optional[Dict[str, dict]] = None,
                   n_devices: int = 8,
                   probe_bps: Optional[float] = None
                   ) -> Tuple[List[Finding], dict]:
    """The whole phase: catalog build + model sanity + bandwidth-catalog
    cross-check. ``signatures`` defaults to the committed schedule
    artifact (the check CLI passes the freshly traced map so the
    catalog matches what the same run just committed)."""
    from .collectives import load_schedules
    if signatures is None:
        signatures = load_schedules()
    findings, doc = build_catalog(signatures, n_devices=n_devices)
    findings += check_bandwidth_catalog(probe_bps=probe_bps)
    return findings, doc


def write_plan_catalog(doc: dict, path: Optional[str] = None) -> str:
    """Commit the catalog — sorted keys, fixed layout, trailing newline,
    atomic replace: byte-identical across runs whenever the predictions
    are (which build_catalog's determinism guarantees)."""
    import json
    if path is None:
        path = plan_catalog_path()
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


def plan_catalog_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "plan_catalog.json")
