"""What-if performance planner: ``main.py plan`` + the drift sentinel.

The repo owns both halves of an analytic cost model and this module
joins them (ROADMAP item 5): the committed static schedule
(``analysis/collective_schedules.json`` — ordered collectives with true
wire bytes per preset × layout × knob variant) says WHAT must move, and
the per-fabric bandwidth catalog (telemetry/bandwidth.py, fed by
``parallel/overlap.probe_comm_plan``) says how fast this fabric has
demonstrably moved it. On top ride a catalogued roofline compute term
and an abstract-state HBM occupancy model, so for any candidate the
planner predicts, WITHOUT running it:

  * per-step wall time   — compute (step FLOPs over an assumed-MFU
    roofline, or a measured step time when the caller has one) plus the
    EXPOSED communication: every scheduled collective costed as
    ``latency + bytes/bandwidth``, with the declared bucket plan's
    exchange earning overlap credit (it hides behind backprop up to
    ``OVERLAP_EFFICIENCY`` of the compute time — arXiv:1711.00705's
    premise, bench.py's overlap row its measurement),
  * per-device HBM watermark — sharded abstract train state + a gradient
    copy + an activation estimate + staging-ring occupancy, the same
    shapes ``analysis/elaborate.py`` validates (calibrated against the
    live ``memory`` rows by the drift sentinel), and
  * comm fraction        — exposed comm over the predicted step.

``main.py plan`` ranks the candidates and RECOMMENDS a layout; the
``plan-drift`` gate phase (analysis/plan_drift.py) re-runs the model
over the committed schedules with the baked-in REFERENCE constants and
commits the diffable ``analysis/plan_catalog.json``. Live runs arm a
:class:`DriftSentinel` (train/hooks.py PlanDriftHook): predicted vs
measured step time (heartbeat EWMA), comm seconds (``comm_timing``
probe) and HBM (``memory`` rows) — sustained divergence beyond
``telemetry.plan_tolerance`` emits a ``plan_drift`` row and a
flight-recorder dump. docs/planner.md is the operator manual.

Every number here is a MODEL, not a measurement: the constants below
are order-of-magnitude anchors chosen once and kept stable so the
committed catalog diffs only when a schedule or the model changes.
Predictions carry their assumptions (``bandwidth_source``) and the
sentinel exists precisely because models drift from reality.
"""
from __future__ import annotations

import argparse
import json
import logging
import math
import time
from typing import Callable, Dict, List, Optional, Tuple

log = logging.getLogger(__name__)

# -- reference constants (the deterministic side of the model) -----------
# Used for the committed plan_catalog.json so it is byte-identical on
# every machine; live predictions prefer the fabric's measured catalog.

#: conservative achieved collective bandwidth (wire bytes/sec) — the
#: order of a virtual-8 CPU psum and well under any real ICI link
REFERENCE_BYTES_PER_SEC = 4.0e8
#: fixed per-collective issue/latency cost
REFERENCE_LATENCY_SECS = 2.0e-4
#: per-device peak (bf16) the roofline compute term assumes — the v4
#: row of utils/profiling.TPU_PEAK_TFLOPS
REFERENCE_PEAK_TFLOPS = 275.0
#: assumed model FLOP utilization of that peak (a well-tuned ResNet/ViT
#: lands 0.3-0.5; docs/planner.md discusses sensitivity)
ASSUMED_MFU = 0.40
#: fraction of compute time the bucketed exchange can hide behind
#: (bench.py's overlap row measures the realized fraction)
OVERLAP_EFFICIENCY = 0.7
#: train-step FLOPs ≈ this × forward FLOPs (fwd + bwd ≈ 3×)
TRAIN_FLOPS_MULTIPLIER = 3.0
#: activation-footprint heuristic: fwd FLOPs per byte of live
#: activation memory (conv/attention stacks land within a small factor)
ACT_FLOPS_PER_BYTE = 50.0

#: schedule ops that can carry a gradient-exchange bucket's payload
#: (same set main.py comm-report matches on)
_EXCHANGE_OPS = ("psum", "psum_scatter")

#: staged (hierarchical) plans additionally issue an intra-tier
#: all-gather leg; only op-wire-ledger matching admits it (a forward
#: fsdp all-gather must never steal a flat bucket match)
_EXCHANGE_OPS_HIER = _EXCHANGE_OPS + ("all_gather",)

#: variants of the committed schedule the planner costs as knob
#: candidates (serve_* and reshard_* variants are not train steps)
PLAN_VARIANTS = ("train", "overlap", "overlap+zero1", "overlap+accum2",
                 "overlap+accum4", "overlap+hier", "bf16+compress")

#: bucket_mb candidates the startup autotune pass costs (the configured
#: value always joins the set)
TUNE_BUCKET_MB = (0.25, 1.0, 4.0, 16.0)

#: a probed tier bandwidth this many × the flat row's is a measurement
#: lie (the seeded-probe-lie tests): the tuner then distrusts the tier
#: rows and falls back to the flat plan, loudly
TUNE_SANITY_FACTOR = 100.0


def layout_label(mesh_cfg) -> str:
    """The catalog-style layout name ("dp", "dp_fsdp", "dp_pp_ep", ...)
    of a MeshConfig — the ``layout`` field of live ``plan`` rows, same
    vocabulary the committed schedule keys use."""
    parts = ["dp"]
    for attr, tag in (("fsdp", "fsdp"), ("tensor", "tp"),
                      ("pipeline", "pp"), ("sequence", "sp"),
                      ("expert", "ep")):
        if getattr(mesh_cfg, attr, 1) > 1:
            parts.append(tag)
    return "_".join(parts)


def _ring_scale(n: int) -> float:
    """Ring-allreduce wire-traffic factor 2(n-1)/n — how scheduled
    bytes (traced on the canonical 8-device mesh) scale to another
    device count."""
    n = max(2, int(n))
    return 2.0 * (n - 1) / n


# -- bandwidth -----------------------------------------------------------
class BandwidthTable:
    """Resolves a reduce-axis signature (``"data+fsdp"``) to
    ``(bytes_per_sec, latency_secs)``. Three sources, in the order a
    live prediction prefers them: a fresh probe snapshot, the fabric's
    persisted catalog, the baked-in reference row."""

    def __init__(self, source: str,
                 axes: Optional[Dict[str, Tuple[float, float]]] = None,
                 default_bps: float = REFERENCE_BYTES_PER_SEC,
                 default_latency: float = REFERENCE_LATENCY_SECS):
        self.source = source
        self.axes = axes or {}
        self.default_bps = float(default_bps)
        self.default_latency = float(default_latency)

    @classmethod
    def reference(cls) -> "BandwidthTable":
        return cls("reference")

    @classmethod
    def from_catalog(cls, doc: Optional[dict]) -> Optional["BandwidthTable"]:
        if not doc or not doc.get("axes"):
            return None
        axes = {}
        for sig, e in doc["axes"].items():
            bps = float(e.get("bytes_per_sec", 0.0))
            lat = float(e.get("latency_secs", 0.0))
            if bps > 0:
                axes[sig] = (bps, max(0.0, lat))
        if not axes:
            return None
        # the fallback for unprobed axis sets: the catalog's own median
        bps_all = sorted(v[0] for v in axes.values())
        lat_all = sorted(v[1] for v in axes.values())
        return cls("catalog", axes,
                   default_bps=bps_all[len(bps_all) // 2],
                   default_latency=lat_all[len(lat_all) // 2])

    @classmethod
    def from_probe(cls, snapshot: Optional[dict]
                   ) -> Optional["BandwidthTable"]:
        """A ``comm_timing`` snapshot/row as a table (bench.py's A/B
        legs predict against the probe they just ran)."""
        if not snapshot or not snapshot.get("buckets"):
            return None
        by_sig: Dict[str, Tuple[float, float]] = {}
        for b in snapshot["buckets"]:
            bps = float(b.get("wire_bytes_per_sec", 0.0))
            lat = float(b.get("probe_secs", 0.0))
            if bps <= 0:
                continue
            sig = b.get("axes") or "data"
            old = by_sig.get(sig)
            by_sig[sig] = (max(bps, old[0]) if old else bps,
                           min(lat, old[1]) if old else lat)
        # hierarchical tier legs (probe hier_k) land under the catalog's
        # tiered key form — "<axes>:intra" / "<axes>:inter"
        for t in snapshot.get("tiers") or []:
            bps = float(t.get("wire_bytes_per_sec", 0.0))
            lat = float(t.get("probe_secs", 0.0))
            if bps <= 0:
                continue
            sig = f"{t.get('axes') or 'data'}:{t.get('tier', 'intra')}"
            old = by_sig.get(sig)
            by_sig[sig] = (max(bps, old[0]) if old else bps,
                           min(lat, old[1]) if old else lat)
        if not by_sig:
            return None
        t = cls("probe", by_sig)
        # defaults from the FLAT rows when any exist: a tier row's
        # bandwidth describes a sub-group, not an unknown full axis set
        flat = {k: v for k, v in by_sig.items() if ":" not in k} or by_sig
        t.default_bps = max(v[0] for v in flat.values())
        t.default_latency = min(v[1] for v in flat.values())
        return t

    def lookup(self, axes_sig: str) -> Tuple[float, float]:
        hit = self.axes.get(axes_sig)
        if hit is not None:
            return hit
        base, _, tier = axes_sig.partition(":")
        if tier:
            # tiered query, no tiered row: the flat row for the same axis
            # set is the honest stand-in (same wire, no tier split)
            hit = self.axes.get(base)
            if hit is not None:
                return hit
        # nearest axis set (most shared names; matching tier preferred;
        # deterministic tie-break)
        want = set(base.split("+"))
        best = None
        for name in sorted(self.axes):
            nbase, _, ntier = name.partition(":")
            score = (len(want & set(nbase.split("+"))),
                     1 if ntier == tier else 0)
            if score[0] and (best is None or score > best[0]):
                best = (score, self.axes[name])
        return best[1] if best else (self.default_bps,
                                     self.default_latency)


def measured_bandwidth_table() -> Optional[BandwidthTable]:
    """This fabric's persisted catalog as a table, when one exists."""
    from . import bandwidth
    return BandwidthTable.from_catalog(bandwidth.load_catalog())


# -- compute (roofline) --------------------------------------------------
def flops_per_example(cfg) -> float:
    """Catalogued FORWARD FLOPs per example — an analytic model per
    family, documented in docs/planner.md. Anchors: RN50@224 ≈ 4.1
    GFLOPs fwd, scaled by depth/width/spatial; ViT from the standard
    24·n·d² + 4·n²·d per block."""
    m = cfg.model
    if m.name == "logistic":
        return 2.0 * m.input_size * m.hidden_units \
            + 2.0 * m.hidden_units * m.num_classes
    if m.name == "vit":
        s = cfg.data.image_size
        n = max(1, s // max(1, m.vit_patch_size)) ** 2
        d = m.vit_dim
        per_block = 24.0 * n * d * d + 4.0 * n * n * d
        if m.vit_num_experts > 0 and m.vit_moe_top_k > 1:
            # top-k>1 routes each token through k expert MLPs (the MLP
            # is 16·n·d² of the 24)
            per_block += (m.vit_moe_top_k - 1) * 16.0 * n * d * d
        return m.vit_depth * per_block + 2.0 * n * d * d  # + patch embed
    # resnet family: anchor RN50@224, scale depth linearly, width
    # quadratically, spatial quadratically
    s = cfg.data.image_size
    return 4.1e9 * (m.resnet_size / 50.0) * (m.width_multiplier ** 2) \
        * (s / 224.0) ** 2


def predict_compute_secs(cfg, n_devices: int, accum: int = 1,
                         peak_tflops: Optional[float] = None) -> float:
    """Roofline compute term for one OPTIMIZER step: global batch ×
    accum microbatches of forward+backward FLOPs, spread ideally over
    the devices, at ``ASSUMED_MFU`` of peak. ``peak_tflops=None`` is the
    OFFLINE planner's reference constant (no device to ask); a live run
    passes its own device's peak (predict_live)."""
    peak = (peak_tflops or REFERENCE_PEAK_TFLOPS) * 1e12
    examples = cfg.train.batch_size * max(1, accum)
    step_flops = examples * flops_per_example(cfg) * TRAIN_FLOPS_MULTIPLIER
    return step_flops / max(1, n_devices) / (peak * ASSUMED_MFU)


# -- communication + step time -------------------------------------------
def _expanded_ops(signature: dict) -> List[dict]:
    out: List[dict] = []
    for op in signature.get("ops", []):
        for _ in range(int(op.get("count", 1))):
            out.append(op)
    return out


def predict_from_signature(signature: dict, bandwidth: BandwidthTable,
                           compute_secs: float,
                           devices: int = 8) -> dict:
    """Cost one committed schedule signature: every scheduled collective
    as ``latency + bytes/bandwidth`` (ring-scaled when predicting a
    device count other than the canonical 8 the schedule traced at),
    overlap credit for the declared bucket plan's exchange ops."""
    plan = signature.get("plan") or {}
    # staged (hierarchical) plans carry the per-op wire ledger, aligned
    # 1:1 with the declared RS→psum→AG sequence — match op-by-op against
    # it; flat plans keep the one-op-per-bucket match
    op_wire = plan.get("bucket_op_wire_bytes")
    if op_wire:
        match_wire = [int(x) for b in op_wire for x in b]
        exchange_ops = _EXCHANGE_OPS_HIER
    else:
        match_wire = [int(b) for b in plan.get("bucket_wire_bytes") or []]
        exchange_ops = _EXCHANGE_OPS
    scale = _ring_scale(devices) / _ring_scale(8)
    comm_secs = 0.0
    exchange_secs = 0.0
    wire_bytes = 0
    cursor = 0
    for op in _expanded_ops(signature):
        nbytes = int(op.get("bytes", 0)) * scale
        sig = "+".join(op.get("axes") or [])
        if op.get("tier"):
            # grouped (two-tier) collectives cost against the tiered
            # bandwidth row ("data+fsdp:intra" / ":inter")
            sig = f"{sig}:{op['tier']}"
        bps, lat = bandwidth.lookup(sig)
        secs = lat + nbytes / bps
        comm_secs += secs
        wire_bytes += int(nbytes)
        # in-order subsequence match against the bucket plan (the
        # comm-report discipline): matched ops are the overlappable
        # gradient exchange
        if op.get("op") in exchange_ops and cursor < len(match_wire) \
                and int(op.get("bytes", -1)) == match_wire[cursor]:
            cursor += 1
            exchange_secs += secs
    exposed = (comm_secs - exchange_secs) \
        + max(0.0, exchange_secs - OVERLAP_EFFICIENCY * compute_secs)
    step_secs = compute_secs + exposed
    return {
        "step_secs": step_secs,
        "compute_secs": compute_secs,
        "comm_secs": comm_secs,
        "comm_exposed_secs": exposed,
        "comm_fraction": exposed / step_secs if step_secs > 0 else 0.0,
        "wire_bytes": wire_bytes,
    }


def tune_comm_plan(snapshot: dict, table: BandwidthTable, *,
                   intra_k: Optional[int],
                   bucket_mb: float,
                   bucket_mb_candidates=TUNE_BUCKET_MB) -> dict:
    """The startup autotune's chooser (comm.autotune=startup): given the
    traced plan snapshot (parallel/overlap.overlap_stats — grad bytes,
    per-bucket reduce-axis sets, the configured compress) and a
    bandwidth table (ideally carrying the probe's tiered rows), cost
    every (bucket_mb × flat-vs-hierarchical × compress) candidate with
    the planner's collective model and return the cheapest. Pure and
    deterministic given its inputs — the autotune-determinism contract
    the tests pin.

    First-order model, documented in docs/planner.md: the gradient is
    one payload on its DOMINANT reduce-axis set (the set carrying the
    most bucket bytes); a flat bucket costs ``lat + W/bps``; a staged
    bucket costs the RS and AG legs on the intra tier plus the 1/k psum
    on the inter tier. Compression candidates never introduce a lossy
    wire dtype the operator didn't configure — options are "off" and
    the snapshot's own compress.

    Fallback discipline (the seeded-probe-lie tests): hierarchical
    candidates are only costed when the table carries MEASURED tier rows
    for the dominant set, and those rows pass the TUNE_SANITY_FACTOR
    plausibility screen against the flat row — otherwise the tuner
    stays flat and logs the reason loudly. Returns {bucket_mb,
    hierarchy (k or 0), compress, predicted_secs, axes, source,
    candidates, fallback}."""
    grad_bytes = int(snapshot.get("grad_bytes") or 0)
    sigs = snapshot.get("bucket_reduce_axes") or ["data+fsdp"]
    sizes = snapshot.get("bucket_bytes") or [grad_bytes]
    by_sig: Dict[str, int] = {}
    for sig, nb in zip(sigs, sizes):
        by_sig[sig] = by_sig.get(sig, 0) + int(nb)
    # dominant reduce-axis set: most bytes, lexicographic tie-break
    sig = sorted(by_sig, key=lambda s: (-by_sig[s], s))[0]
    cur_compress = snapshot.get("compress", "off") or "off"
    compress_opts = ["off"] if cur_compress == "off" \
        else ["off", cur_compress]
    itemsize = {"off": 4, "bf16": 2, "fp16": 2}

    fallback = None
    k = int(intra_k) if intra_k else 0
    if k > 1 and "data" not in sig.split("+"):
        k, fallback = 0, ("dominant reduce set %r has no data axis" % sig)
    bps_f, lat_f = table.lookup(sig)
    if k > 1:
        if f"{sig}:intra" not in table.axes \
                or f"{sig}:inter" not in table.axes:
            k, fallback = 0, (
                f"no measured tier rows for {sig!r} in the "
                f"{table.source} table")
        else:
            bps_i, lat_i = table.lookup(f"{sig}:intra")
            bps_e, lat_e = table.lookup(f"{sig}:inter")
            implausible = [
                f"{t}={bps:.3g} B/s vs flat {bps_f:.3g} B/s"
                for t, bps in (("intra", bps_i), ("inter", bps_e))
                if not (0 < bps <= TUNE_SANITY_FACTOR * bps_f)]
            if implausible:
                k, fallback = 0, (
                    "tier bandwidth rows fail the plausibility screen "
                    f"(×{TUNE_SANITY_FACTOR:g} of the flat row): "
                    + "; ".join(implausible))
    if fallback:
        log.warning("comm autotune: hierarchical candidates DISABLED — "
                    "%s; tuning flat only", fallback)

    def cost(mb: float, hier: int, compress: str) -> float:
        cap = max(1, int(mb * 2 ** 20))
        n = max(1, -(-grad_bytes // cap))  # ceil
        w = (grad_bytes / n) * itemsize[compress] / 4.0
        if hier:
            return n * (2 * (lat_i + w / bps_i)
                        + (lat_e + (w / hier) / bps_e))
        return n * (lat_f + w / bps_f)

    mbs = sorted(set(float(m) for m in bucket_mb_candidates)
                 | {float(bucket_mb)})
    scored = []
    for mb in mbs:
        for hier in ([0, k] if k > 1 else [0]):
            for compress in compress_opts:
                scored.append((round(cost(mb, hier, compress), 9),
                               mb != float(bucket_mb), hier == 0,
                               mb, hier, compress))
    # cheapest wins; ties prefer the configured bucket_mb, then the
    # hierarchical form (it was only admitted with measured tier rows),
    # then the smaller cap / plainer wire — fully deterministic
    scored.sort(key=lambda t: (t[0], t[1], t[2], t[3], t[5]))
    best = scored[0]
    return {
        "bucket_mb": best[3],
        "hierarchy": best[4],
        "compress": best[5],
        "predicted_secs": best[0],
        "axes": sig,
        "source": table.source,
        "fallback": fallback,
        "candidates": {
            f"bucket{mb:g}mb/"
            + (f"hier{hier}" if hier else "flat")
            + (f"/{compress}" if compress != "off" else ""):
            secs for secs, _, _, mb, hier, compress in scored},
    }


# -- HBM watermark -------------------------------------------------------
def _tree_bytes(shapes) -> int:
    import jax
    import numpy as np
    total = 0
    for leaf in jax.tree_util.tree_leaves(shapes):
        total += int(math.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total


def _sharded_bytes_per_device(shapes, shardings, mesh) -> int:
    """Per-device bytes of an abstract tree under its shardings: each
    leaf's bytes divided by the product of the mesh axes its
    PartitionSpec names (replicated leaves land whole on every
    device)."""
    import jax
    import numpy as np
    leaves = jax.tree_util.tree_leaves(shapes)
    shard_leaves = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: hasattr(x, "spec"))
    total = 0
    for leaf, sh in zip(leaves, shard_leaves):
        nbytes = int(math.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        divisor = 1
        spec = getattr(sh, "spec", None)
        for entry in (spec or ()):
            if entry is None:
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            for name in names:
                divisor *= max(1, mesh.shape.get(name, 1))
        total += nbytes // max(1, divisor)
    return total


def predict_hbm_bytes(cfg, trainer, devices: int = 8) -> Optional[dict]:
    """Per-device HBM watermark model: sharded train state (params +
    optimizer) + a gradient copy sized like the params + the activation
    heuristic + two staging-ring slots of input batch. The live
    calibration target is the ``memory`` rows' per-device
    ``live_peak_bytes``."""
    try:
        from ..analysis.collectives import _abstract_state
        from ..parallel.mesh import batch_shard_count
        state = _abstract_state(trainer, cfg)
        shardings = trainer._state_shardings(state)
        mesh = trainer.mesh
        state_pd = _sharded_bytes_per_device(state, shardings, mesh)
        # grads are sized and sharded like the params subtree
        grad_pd = _sharded_bytes_per_device(state.params, shardings.params,
                                            mesh)
        nb = batch_shard_count(mesh)
        # schedule traced at 8 devices; other counts only grow the data
        # axis, which shrinks the per-device batch, not the state
        local_examples = cfg.train.batch_size / max(1, nb) * (8.0 / devices)
        act = local_examples * flops_per_example(cfg) / ACT_FLOPS_PER_BYTE
        if cfg.model.name == "logistic":
            batch_bytes = local_examples * cfg.model.input_size * 4
        else:
            s = cfg.data.image_size
            batch_bytes = local_examples * s * s * 3 * 4
        staging = 2 * batch_bytes
        return {"hbm_bytes": int(state_pd + grad_pd + act + staging),
                "state_bytes": int(state_pd),
                "grad_bytes": int(grad_pd),
                "act_bytes": int(act),
                "staging_bytes": int(staging)}
    except Exception:
        log.exception("HBM watermark model failed (prediction degrades "
                      "to time/comm only)")
        return None


# -- candidate enumeration (main.py plan / the gate phase) ---------------
def _variant_knobs(cfg, variant: str) -> dict:
    accum = 1
    if "accum" in variant:
        accum = int(variant.rsplit("accum", 1)[1])
    return {
        "precision": "bf16" if variant.startswith("bf16") else
        cfg.train.precision,
        "zero1": "zero1" in variant,
        "compress": "bf16" if "compress" in variant else "off",
        "bucket_mb": cfg.comm.bucket_mb,
        "accum": accum,
        "overlap": variant != "train",
        "hierarchy": "hier" in variant,
    }


def plan_for_preset(preset: str, signatures: Dict[str, dict],
                    n_devices: int = 8,
                    bandwidth: Optional[BandwidthTable] = None,
                    include_hbm: bool = True,
                    measured_compute_secs: Optional[float] = None,
                    peak_tflops: Optional[float] = None) -> dict:
    """Cost every committed (layout, variant) candidate of one preset
    and rank them. Pure given its inputs when ``bandwidth`` is the
    reference table — the plan-catalog byte-identity contract."""
    from ..utils.config import get_preset
    from ..analysis.elaborate import candidate_layouts
    from .tracer import recorder

    cfg = get_preset(preset)
    bandwidth = bandwidth or BandwidthTable.reference()
    layouts = dict(candidate_layouts(cfg, n_devices))
    trainers: Dict[str, object] = {}
    candidates: Dict[str, dict] = {}
    for key in sorted(signatures):
        name, rest = key.split("@", 1)
        layout, variant = rest.split("/", 1)
        if name != preset or variant not in PLAN_VARIANTS:
            continue
        with recorder.span("plan.predict", preset=preset, layout=layout,
                           variant=variant):
            knobs = _variant_knobs(cfg, variant)
            compute = measured_compute_secs if measured_compute_secs \
                else predict_compute_secs(cfg, n_devices,
                                          accum=knobs["accum"],
                                          peak_tflops=peak_tflops)
            pred = predict_from_signature(signatures[key], bandwidth,
                                          compute, devices=n_devices)
            if include_hbm and layout in layouts:
                trainer = trainers.get(layout)
                if trainer is None:
                    trainer = _trainer_for_layout(cfg, layouts[layout])
                    trainers[layout] = trainer
                if trainer is not None:
                    hbm = predict_hbm_bytes(cfg, trainer,
                                            devices=n_devices)
                    if hbm:
                        pred.update(hbm)
            pred["knobs"] = knobs
            candidates[f"{layout}/{variant}"] = _round_prediction(pred)
    ranked = rank_candidates(candidates)
    return {"preset": preset, "devices": n_devices,
            "bandwidth_source": bandwidth.source,
            "candidates": candidates,
            "ranked": ranked,
            "recommended": _recommend(candidates, ranked)}


def _trainer_for_layout(cfg, mesh_cfg):
    """A Trainer on a virtual mesh of the layout's shape (shared state
    memo with the hangcheck phase); None when the layout cannot build
    here (the prediction then omits HBM rather than failing)."""
    try:
        import copy
        import jax
        from ..analysis.elaborate import _axis_product
        from ..parallel.mesh import create_mesh
        from ..train.loop import Trainer
        c = copy.deepcopy(cfg)
        c.mesh = copy.deepcopy(mesh_cfg)
        # partial-coverage layouts (dp_pp covers 4 of 8 devices) build on
        # a device slice, the hangcheck-schedule discipline
        mesh = create_mesh(c.mesh,
                           devices=jax.devices()[:_axis_product(c.mesh)])
        return Trainer(c, mesh=mesh)
    except Exception as e:
        log.warning("planner: layout trainer unavailable (%s); HBM "
                    "omitted", e)
        return None


def _round_prediction(pred: dict) -> dict:
    """Stable rounding so the committed catalog never diffs on float
    noise: seconds to microsecond-ish precision, fractions to 1e-4."""
    out = {}
    for k, v in pred.items():
        if k.endswith("_secs"):
            out[k] = round(float(v), 9)
        elif k == "comm_fraction":
            out[k] = round(float(v), 4)
        elif isinstance(v, float):
            out[k] = round(v, 6)
        else:
            out[k] = v
    return out


def rank_candidates(candidates: Dict[str, dict]) -> List[str]:
    """Fastest predicted step first; HBM then name break ties."""
    return sorted(candidates,
                  key=lambda k: (candidates[k]["step_secs"],
                                 candidates[k].get("hbm_bytes", 0), k))


def _recommend(candidates: Dict[str, dict],
               ranked: List[str]) -> Optional[str]:
    """The recommended LAYOUT choice compares like with like: the
    fastest candidate among the plain ``overlap`` variants (every
    layout traces one), falling back to the overall ranking."""
    overlap_only = [k for k in ranked if k.endswith("/overlap")]
    return (overlap_only or ranked or [None])[0]


def recommend_layout(preset: str, n_devices: int = 8,
                     bandwidth: Optional[BandwidthTable] = None
                     ) -> Optional[Tuple[str, object]]:
    """(layout name, MeshConfig) the planner ranks first for this
    preset — launch.py's --auto-layout hook. None when the preset has
    no committed schedules (a new preset must run the gate first)."""
    from ..utils.config import get_preset
    from ..analysis.elaborate import candidate_layouts
    from .comm_report import load_schedules

    signatures = load_schedules()
    if not any(k.startswith(preset + "@") for k in signatures):
        return None
    plan = plan_for_preset(preset, signatures, n_devices=n_devices,
                           bandwidth=bandwidth
                           or measured_bandwidth_table(),
                           include_hbm=False)
    rec = plan.get("recommended")
    if not rec:
        return None
    layout = rec.split("/", 1)[0]
    cfg = get_preset(preset)
    for name, mesh_cfg in candidate_layouts(cfg, n_devices):
        if name == layout:
            return name, mesh_cfg
    return None


# -- live-run prediction (the drift sentinel's reference point) ----------
def predict_live(cfg, trainer,
                 bandwidth: Optional[BandwidthTable] = None
                 ) -> Optional[dict]:
    """Predict THIS run's step time / comm seconds / HBM from the live
    traced bucket plan (parallel/overlap.overlap_stats) — no committed
    schedule needed, so it works for any preset/override combination
    actually running. Returns None until the exchange plan has traced
    (the sentinel arms lazily) or when the run has no bucketed
    exchange to model."""
    import jax
    from ..parallel.overlap import overlap_stats
    from ..utils.profiling import detect_peak_tflops

    snap = overlap_stats.snapshot()
    if snap is None:
        return None
    if bandwidth is None:
        bandwidth = measured_bandwidth_table() or BandwidthTable.reference()
    n_devices = jax.device_count()
    accum = max(1, int(snap.get("accum_steps", 1)))
    # the attached accelerator's own peak; raises for a device_kind the
    # peaks table does not know — a borrowed peak would make every
    # plan_drift row on that machine a fiction
    peak = detect_peak_tflops()
    if peak is None:
        # CPU rehearsal: the host has no peak, so the compute term keeps
        # the catalog's reference constant and the step_secs drift this
        # produces there is expected (docs/planner.md)
        log.info("plan: cpu backend — compute term costed at the "
                 "reference %.0f TFLOP/s, not at a device peak",
                 REFERENCE_PEAK_TFLOPS)
        peak = REFERENCE_PEAK_TFLOPS
    compute = predict_compute_secs(cfg, n_devices, accum=accum,
                                   peak_tflops=peak)
    comm = 0.0
    for wire, sig in zip(snap["bucket_wire_bytes"],
                         snap.get("bucket_reduce_axes",
                                  ["data"] * len(snap["bucket_wire_bytes"]))):
        bps, lat = bandwidth.lookup(sig)
        comm += lat + int(wire) / bps
    exposed = max(0.0, comm - OVERLAP_EFFICIENCY * compute)
    step = compute + exposed
    pred = {
        "step_secs": step,
        "compute_secs": compute,
        "comm_secs": comm,
        "comm_exposed_secs": exposed,
        "comm_fraction": exposed / step if step > 0 else 0.0,
        "wire_bytes": int(snap.get("wire_bytes", 0)),
    }
    hbm = predict_hbm_bytes(cfg, trainer, devices=n_devices)
    if hbm:
        pred.update(hbm)
    return _round_prediction(pred)


# -- drift sentinel ------------------------------------------------------
class DriftSentinel:
    """Predicted-vs-measured divergence detector. Per metric: a check
    whose ratio ``measured/predicted`` leaves ``[1/tolerance,
    tolerance]`` grows a streak; ``window`` consecutive divergent
    checks open an EPISODE, which fires exactly once; the episode ends
    when a check lands back inside tolerance. A global cooldown gates
    successive fires — a persistently mispredicted run must page once,
    not once per cadence (the perf-anomaly sentinel's discipline,
    resilience/watchdog.py)."""

    METRICS = ("step_secs", "comm_secs", "hbm_bytes")

    def __init__(self, predicted: dict, tolerance: float = 3.0,
                 window: int = 8, cooldown_secs: float = 300.0,
                 clock: Callable[[], float] = time.monotonic):
        self.predicted = {m: float(predicted[m]) for m in self.METRICS
                          if float(predicted.get(m) or 0.0) > 0.0}
        self.tolerance = max(1.0 + 1e-9, float(tolerance))
        self.window = max(1, int(window))
        self.cooldown_secs = max(0.0, float(cooldown_secs))
        self._clock = clock
        self._streak: Dict[str, int] = {}
        self._in_episode: Dict[str, bool] = {}
        self._last_fire_t: Optional[float] = None

    def check(self, metric: str, measured: Optional[float]
              ) -> Optional[dict]:
        """Feed one measurement; a dict (the ``plan_drift`` row body)
        exactly when the sentinel fires, else None."""
        predicted = self.predicted.get(metric)
        if predicted is None or measured is None or measured <= 0:
            return None
        ratio = float(measured) / predicted
        divergent = ratio > self.tolerance or ratio < 1.0 / self.tolerance
        if not divergent:
            self._streak[metric] = 0
            self._in_episode[metric] = False
            return None
        self._streak[metric] = self._streak.get(metric, 0) + 1
        if self._streak[metric] < self.window \
                or self._in_episode.get(metric):
            return None
        now = self._clock()
        if self._last_fire_t is not None \
                and now - self._last_fire_t < self.cooldown_secs:
            return None  # cooldown: keep the streak, fire later
        self._last_fire_t = now
        self._in_episode[metric] = True
        return {"metric": metric,
                "predicted": round(predicted, 9),
                "measured": round(float(measured), 9),
                "ratio": round(ratio, 4),
                "tolerance": self.tolerance,
                "windows": self._streak[metric]}


# -- CLI -----------------------------------------------------------------
def render_plan(plan: dict) -> str:
    lines = [f"== plan :: {plan['preset']} @ {plan['devices']} device(s) "
             f"(bandwidth: {plan['bandwidth_source']}) =="]
    hdr = (f"  {'rank':>4} {'layout/variant':<24} {'step ms':>9} "
           f"{'comp ms':>9} {'comm ms':>9} {'frac':>6} {'HBM MB':>8} "
           f"{'wire MB':>8}")
    lines.append(hdr)
    for i, key in enumerate(plan["ranked"], 1):
        c = plan["candidates"][key]
        hbm = c.get("hbm_bytes")
        hbm_txt = f"{hbm / 1e6:>8.1f}" if hbm is not None else f"{'-':>8}"
        lines.append(
            f"  {i:>4} {key:<24} {c['step_secs'] * 1e3:>9.3f} "
            f"{c['compute_secs'] * 1e3:>9.3f} "
            f"{c['comm_secs'] * 1e3:>9.3f} {c['comm_fraction']:>6.3f} "
            f"{hbm_txt} {c['wire_bytes'] / 1e6:>8.2f}")
    if plan.get("recommended"):
        lines.append(f"  recommended: {plan['recommended']}")
    return "\n".join(lines)


def main_plan(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="main.py plan",
        description="what-if performance planner: predict step time / "
                    "HBM / comm fraction per candidate layout from the "
                    "committed collective schedules × the fabric "
                    "bandwidth catalog (docs/planner.md)")
    ap.add_argument("--preset", action="append", default=[],
                    help="preset(s) to plan (default: every preset with "
                         "committed schedules)")
    ap.add_argument("--devices", type=int, default=8,
                    help="device count to predict for (default 8, the "
                         "canonical schedule mesh)")
    ap.add_argument("--bandwidth", default="auto",
                    help="'auto' (fabric catalog, else reference), "
                         "'reference', or a catalog JSON path")
    ap.add_argument("--schedules", default="",
                    help="collective_schedules.json path (default: the "
                         "committed artifact)")
    ap.add_argument("--no-hbm", action="store_true",
                    help="skip the HBM watermark model (no virtual-mesh "
                         "trainer builds — much faster)")
    ap.add_argument("--root", default=None,
                    help="also write registered {'event': 'plan'} rows "
                         "into this log root")
    ap.add_argument("--json", action="store_true",
                    help="emit the plans as JSON")
    ns = ap.parse_args(argv)

    from ..utils.virtual_devices import apply_virtual_cpu
    if not ns.no_hbm:
        apply_virtual_cpu(max(8, ns.devices))
    from . import bandwidth as bw_mod
    from .comm_report import load_schedules

    signatures = load_schedules(ns.schedules or None)
    if not signatures:
        print("plan: no committed schedules — run "
              "`main.py check` first (docs/static_analysis.md)")
        return 1
    if ns.bandwidth == "reference":
        table = BandwidthTable.reference()
    elif ns.bandwidth == "auto":
        table = measured_bandwidth_table() or BandwidthTable.reference()
    else:
        table = BandwidthTable.from_catalog(
            bw_mod.load_catalog(path=ns.bandwidth))
        if table is None:
            print(f"plan: no readable bandwidth catalog at "
                  f"{ns.bandwidth}")
            return 1
    presets = ns.preset or sorted({k.split("@", 1)[0]
                                   for k in signatures})
    plans = []
    for preset in presets:
        if not any(k.startswith(preset + "@") for k in signatures):
            print(f"plan: preset {preset!r} has no committed schedules; "
                  "skipping")
            continue
        plans.append(plan_for_preset(
            preset, signatures, n_devices=ns.devices, bandwidth=table,
            include_hbm=not ns.no_hbm))
    if ns.root:
        import os
        from ..utils.metrics import MetricsWriter
        writer = MetricsWriter(os.path.join(ns.root, "plan"),
                               enable_tensorboard=False)
        for plan in plans:
            for key in plan["ranked"]:
                layout, variant = key.split("/", 1)
                writer.write_event("plan", {
                    "preset": plan["preset"], "layout": layout,
                    "devices": plan["devices"],
                    "knobs": plan["candidates"][key]["knobs"],
                    "predicted": {k: v for k, v in
                                  plan["candidates"][key].items()
                                  if k != "knobs"},
                    "bandwidth_source": plan["bandwidth_source"],
                    "recommended": key == plan["recommended"]})
        writer.flush()
    if ns.json:
        print(json.dumps(plans, indent=1, sort_keys=True))
    else:
        for plan in plans:
            print(render_plan(plan))
    return 0
