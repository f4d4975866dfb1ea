"""What-if performance planner: ``main.py plan``.

The repo owns both halves of an analytic cost model and this module
joins them (ROADMAP item 5): the committed static schedule
(``analysis/collective_schedules.json`` — the collectives a step program
writes out, in order, with true wire bytes per preset × layout) says
WHAT must move, and a per-fabric bandwidth catalog
(telemetry/bandwidth.py) says how fast a fabric moves it, where one
exists. On top ride a catalogued roofline compute term and an
abstract-state HBM occupancy model, so for any candidate the planner
predicts, WITHOUT running it:

  * per-step wall time   — compute (step FLOPs over an assumed-MFU
    roofline) plus every scheduled collective costed as ``latency + bytes/bandwidth``.
    The gradient exchange itself is XLA's (sharding propagation) and is
    not in a jaxpr-level schedule: the planner does not cost it,
  * per-device HBM watermark — sharded abstract train state + a gradient
    copy + an activation estimate + staging-ring occupancy, the same
    shapes ``analysis/elaborate.py`` validates, and
  * comm fraction        — scheduled comm over the predicted step.

``main.py plan`` ranks the candidates and RECOMMENDS a layout; the
``plan-drift`` gate phase (analysis/plan_drift.py) re-runs the model
over the committed schedules with the baked-in REFERENCE constants and
commits the diffable ``analysis/plan_catalog.json``. docs/planner.md is
the operator manual.

Every number here is a MODEL, not a measurement: the constants below
are order-of-magnitude anchors chosen once and kept stable so the
committed catalog diffs only when a schedule or the model changes.
Predictions carry their assumptions (``bandwidth_source``).
"""
from __future__ import annotations

import argparse
import json
import logging
import math
from typing import Dict, List, Optional, Tuple

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
#: train-step FLOPs ≈ this × forward FLOPs (fwd + bwd ≈ 3×)
TRAIN_FLOPS_MULTIPLIER = 3.0
#: activation-footprint heuristic: fwd FLOPs per byte of live
#: activation memory (conv/attention stacks land within a small factor)
ACT_FLOPS_PER_BYTE = 50.0

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
    ``(bytes_per_sec, latency_secs)``. Two sources: the fabric's
    persisted catalog where one exists, else the baked-in reference
    row."""

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


def predict_compute_secs(cfg, n_devices: int) -> float:
    """Roofline compute term for one OPTIMIZER step: the global batch's
    forward+backward FLOPs, spread ideally over the devices, at
    ``ASSUMED_MFU`` of the reference peak (no device to ask)."""
    peak = REFERENCE_PEAK_TFLOPS * 1e12
    step_flops = cfg.train.batch_size * flops_per_example(cfg) \
        * TRAIN_FLOPS_MULTIPLIER
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
    device count other than the canonical 8 the schedule traced at).
    All of it adds to the step: what the schedule holds (pipeline
    hand-offs, expert all-to-alls) sits on the critical path."""
    scale = _ring_scale(devices) / _ring_scale(8)
    comm_secs = 0.0
    wire_bytes = 0
    for op in _expanded_ops(signature):
        nbytes = int(op.get("bytes", 0)) * scale
        bps, lat = bandwidth.lookup("+".join(op.get("axes") or []))
        comm_secs += lat + nbytes / bps
        wire_bytes += int(nbytes)
    step_secs = compute_secs + comm_secs
    return {
        "step_secs": step_secs,
        "compute_secs": compute_secs,
        "comm_secs": comm_secs,
        "comm_fraction": comm_secs / step_secs if step_secs > 0 else 0.0,
        "wire_bytes": wire_bytes,
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
    heuristic + two staging-ring slots of input batch."""
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
def plan_for_preset(preset: str, signatures: Dict[str, dict],
                    n_devices: int = 8,
                    bandwidth: Optional[BandwidthTable] = None,
                    include_hbm: bool = True) -> dict:
    """Cost the committed ``train`` schedule of every layout of one
    preset (``serve_*`` and ``reshard_*`` variants are not a layout's
    train step) and rank them. Pure given its inputs when ``bandwidth`` is the
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
        if name != preset or variant != "train":
            continue
        with recorder.span("plan.predict", preset=preset, layout=layout,
                           variant=variant):
            knobs = {"precision": cfg.train.precision,
                     "zero1": cfg.optimizer.zero1 == "on"}
            pred = predict_from_signature(
                signatures[key], bandwidth,
                predict_compute_secs(cfg, n_devices), devices=n_devices)
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
            "recommended": ranked[0] if ranked else None}


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


def recommend_layout(preset: str, n_devices: int = 8,
                     bandwidth: Optional[BandwidthTable] = None
                     ) -> Optional[Tuple[str, object]]:
    """(layout name, MeshConfig) the planner ranks first for this
    preset — launch.py's --auto-layout hook. None when the preset has
    no committed schedules (a new preset must run the gate first)."""
    from ..utils.config import get_preset
    from ..analysis.elaborate import candidate_layouts
    from ..analysis.collectives import load_schedules

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
    from ..analysis.collectives import load_schedules

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
