"""Collective-schedule extraction: pin the comm program statically.

The collective *schedule* — which collectives a step issues, in what
order, over which axes, with how many wire bytes — is a program property
wherever the program writes its collectives out (the pipeline's ppermute
chains, the expert all-to-all, ring attention): a reordering is a perf
regression at best and a cross-host deadlock at worst (two hosts issuing
collectives in different orders is the hang class the watchdog can only
kill). This phase walks the jaxprs of the already-elaborated step
variants and:

  * emits an ordered signature of collective ops (kind, axis names,
    operand count, payload bytes) per preset × layout × variant. Bytes
    are PER-PARTICIPANT payloads (inside shard_map the traced avals are
    the local shards);
  * asserts the signature is DETERMINISTIC across two elaborations for
    every variant that carries collectives (a schedule that differs
    between traces would differ between hosts);
  * dumps everything as ``analysis/collective_schedules.json`` (inside
    the package, committed) — byte-identical across runs, so any PR that
    changes comm behavior shows a reviewable diff.

Variants per preset (deduped across presets sharing the program, the
``trace_forward`` lesson): the jit train step (its jaxpr-level schedule
is EMPTY for the batch-parallel families by construction — the gradient
exchange is left to XLA sharding propagation; non-empty is itself
information the artifact records) on the batch layout and on the
pipeline/tensor/expert layouts of the transformer family, the same step
on the survivor meshes of an elastic shrink, and the serve/predict step
(smallest + largest AOT bucket).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .report import Finding

RULE = "hangcheck-schedule"

#: the preset whose step is re-traced on the survivor meshes of an
#: elastic shrink (cheapest conv program)
_DET_PROBE = "cifar10_resnet50"

#: jaxpr primitive name → normalized op kind. ``psum_invariant`` is what
#: ``lax.psum`` traces to inside a ``shard_map`` that checks varying mesh
#: axes (``check_vma=True``); ``reduce_scatter`` implements
#: ``lax.psum_scatter``. ``pbroadcast`` is a replication-rule adjustment,
#: not a wire collective — deliberately excluded.
WIRE_PRIMS = {
    "psum": "psum",
    "psum_invariant": "psum",
    "reduce_scatter": "psum_scatter",
    "all_gather": "all_gather",
    "all_to_all": "all_to_all",
    "ppermute": "ppermute",
    "pmax": "pmax",
    "pmin": "pmin",
    "pgather": "pgather",
}


def _axes_of(eqn) -> Tuple[str, ...]:
    axes = eqn.params.get("axes", None)
    if axes is None:
        axes = eqn.params.get("axis_name", ())
    if isinstance(axes, (str, int)):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def _payload_bytes(eqn) -> int:
    total = 0
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is None or dtype is None:
            continue
        total += int(math.prod(shape)) * np.dtype(dtype).itemsize
    return total


def _sub_jaxprs(eqn):
    # duck-typed (stable across jax releases): a ClosedJaxpr carries
    # .jaxpr, a raw Jaxpr carries .eqns; params may hold either, alone
    # or in tuples (scan bodies, cond branches, shard_map/pjit/remat)
    for val in eqn.params.values():
        stack = [val]
        while stack:
            item = stack.pop()
            name = type(item).__name__
            if name == "ClosedJaxpr":
                yield item.jaxpr
            elif name == "Jaxpr":
                yield item
            elif isinstance(item, (list, tuple)):
                stack.extend(item)


def collect_ops(jaxpr) -> List[dict]:
    """Ordered collective signature of a jaxpr (recursing into shard_map
    / pjit / scan / cond / remat sub-jaxprs in eqn order). Loop bodies
    (scan/while) contribute their body's schedule ONCE — the static
    issue order, not the dynamic repetition count."""
    out: List[dict] = []
    for eqn in jaxpr.eqns:
        kind = WIRE_PRIMS.get(eqn.primitive.name)
        if kind is not None:
            out.append({
                "op": kind,
                "axes": list(_axes_of(eqn)),
                "operands": len(eqn.invars),
                "bytes": _payload_bytes(eqn),
            })
        for sub in _sub_jaxprs(eqn):
            out.extend(collect_ops(sub))
    return out


def extract_schedule(fn, *abstract_args) -> List[dict]:
    """Trace ``fn`` abstractly (zero compute) and return its ordered
    collective signature."""
    import jax
    return collect_ops(jax.make_jaxpr(fn)(*abstract_args).jaxpr)


def _schedule_key(name: str, layout: str, variant: str) -> str:
    return f"{name}@{layout}/{variant}"


def _trainer_for(cfg, mesh):
    from ..train.loop import Trainer
    return Trainer(cfg, mesh=mesh)


#: abstract-state memo across variants/presets: state SHAPES depend only
#: on (model, optimizer family, input dims, batch shards) — rebuilding
#: them per traced variant would be the phase's largest fixed cost
_STATE_MEMO: dict = {}


def _abstract_state(trainer, cfg):
    import dataclasses
    from ..train.state import abstract_train_state, init_input
    from ..parallel.mesh import batch_shard_count
    nb = batch_shard_count(trainer.mesh)
    # the memoized state embeds apply_fn — a module bound to ITS mesh.
    # Shaping axes bake into the module's program (pipeline
    # microbatching), so two layouts may share a state only when their
    # full shaping signature matches
    key = repr((dataclasses.asdict(cfg.model), cfg.optimizer.name,
                cfg.data.dataset, cfg.data.image_size, nb,
                tuple(trainer.mesh.shape.get(a, 1)
                      for a in ("pipeline", "tensor", "expert", "seq"))))
    state = _STATE_MEMO.get(key)
    if state is None:
        state = abstract_train_state(
            trainer.model, trainer.tx, init_input(trainer.model, cfg, nb))
        _STATE_MEMO[key] = state
    return state


def run_collectives(preset_names: Optional[Sequence[str]] = None,
                    n_devices: int = 8
                    ) -> Tuple[List[Finding], Dict[str, dict]]:
    """The hangcheck-schedule phase: (findings, signatures). Signatures
    feed ``analysis/collective_schedules.json`` (written by the check
    CLI on full-sweep runs)."""
    import copy
    import dataclasses
    import jax
    from ..parallel.mesh import create_mesh
    from ..utils.config import MeshConfig, PRESETS, get_preset
    from .elaborate import candidate_layouts, has_classifier_forward, \
        _abstract_batch, _axis_product

    findings: List[Finding] = []
    signatures: Dict[str, dict] = {}
    if len(jax.devices()) < n_devices:
        return ([Finding(RULE, "environment", 0,
                         f"{len(jax.devices())} devices present, "
                         f"{n_devices} needed")], signatures)

    seen_programs: set = set()

    def dedupe(kind: str, cfg, layout: str, extra=()) -> bool:
        """True when this (program, layout) was already traced under
        another preset name (the schedule would be identical)."""
        key = repr((kind, dataclasses.asdict(cfg.model), cfg.data.dataset,
                    cfg.data.image_size, layout, tuple(extra)))
        if key in seen_programs:
            return True
        seen_programs.add(key)
        return False

    def record(name: str, layout: str, variant: str, builder,
               deterministic_retrace: bool) -> None:
        """Trace (maybe twice) and record the signature."""
        locus = _schedule_key(name, layout, variant)
        try:
            schedule = builder()
        except Exception as e:
            msg = f"{type(e).__name__}: {e}".splitlines()[0][:300]
            findings.append(Finding(RULE, locus, 0,
                                    f"schedule trace failed: {msg}",
                                    detail=str(e)[:4000]))
            return
        entry: dict = {"ops": schedule}
        if deterministic_retrace and schedule:
            second = builder()
            if second != schedule:
                findings.append(Finding(
                    RULE, locus, 0,
                    "collective schedule is NOT deterministic across two "
                    "elaborations — hosts tracing independently could "
                    "issue different orders (first diff at op "
                    f"{next(i for i, (a, b) in enumerate(zip(schedule, second)) if a != b) if len(second) == len(schedule) else 'count'})"))
        signatures[locus] = entry

    for name in (preset_names or sorted(PRESETS)):
        cfg = get_preset(name)
        layouts = candidate_layouts(cfg, n_devices)
        traced_plain = False
        for label, mesh_cfg in layouts:
            n = _axis_product(mesh_cfg)
            try:
                mesh = create_mesh(mesh_cfg, devices=jax.devices()[:n])
            except Exception as e:
                findings.append(Finding(
                    RULE, _schedule_key(name, label, "train"), 0,
                    f"mesh build failed: {e}"))
                continue
            shaping = max(mesh_cfg.pipeline, 1) > 1 or \
                max(mesh_cfg.tensor, 1) > 1 or \
                max(mesh_cfg.expert, 1) > 1 or \
                max(mesh_cfg.sequence, 1) > 1

            # (1) plain jit train step: once per program (CNN steps don't
            # read the mesh at trace time; shaped transformer layouts do).
            # Batch size, optimizer and precision policy never shape the
            # JAXPR-LEVEL collective schedule of the jit step — grads are
            # param-shaped, the exchange is XLA propagation, the policy
            # changes dtypes not collectives — so the optimizer/precision
            # variants of one base preset dedupe onto it
            if (shaping or not traced_plain) and \
                    not dedupe("train", cfg, label if shaping else "any"):
                traced_plain = True

                def build_train(cfg=cfg, mesh=mesh):
                    trainer = _trainer_for(copy.deepcopy(cfg), mesh)
                    state = _abstract_state(trainer, cfg)
                    batch = _abstract_batch(cfg, cfg.train.batch_size)
                    return extract_schedule(trainer._train_step, state,
                                            batch)

                record(name, label, "train", build_train,
                       deterministic_retrace=shaping)

        # (2) reshard shrink topologies (docs/resilience.md): after an
        # elastic shrink the SAME program is re-elaborated over the
        # survivor sub-mesh, and every survivor traces it independently
        # inside the reshard barrier — so the schedule on each shrunken
        # topology must be deterministic across elaborations, and is
        # pinned here per survivor count. One witness program (the
        # det-probe) on the plain data layout: a shrink changes the
        # device count and the per_host-rescaled global batch, never the
        # program. 6 and 4 of 8 devices model losing one/two hosts of a
        # four-host fleet with two devices each.
        if name == _DET_PROBE:
            per_dev = cfg.train.batch_size // n_devices
            for shrink in (6, 4):

                def build_shrink(cfg=cfg, shrink=shrink, per_dev=per_dev):
                    sub_mesh = create_mesh(MeshConfig(data=shrink),
                                           devices=jax.devices()[:shrink])
                    scfg = copy.deepcopy(cfg)
                    scfg.train.batch_size = per_dev * shrink
                    trainer = _trainer_for(scfg, sub_mesh)
                    state = _abstract_state(trainer, scfg)
                    batch = _abstract_batch(scfg, scfg.train.batch_size)
                    return extract_schedule(trainer._train_step, state,
                                            batch)

                record(name, "dp", f"reshard_s{shrink}", build_shrink,
                       deterministic_retrace=True)

        # (3) serve/predict step: smallest + largest AOT bucket on the
        # first layout — forward-only, so the signature pins that serving
        # carries NO hidden collectives on the batch-parallel meshes
        if layouts and has_classifier_forward(cfg) and \
                not dedupe("serve", cfg, layouts[0][0],
                           (cfg.serve.max_batch,)):
            label, mesh_cfg = layouts[0]
            try:
                import jax as _jax
                mesh = create_mesh(mesh_cfg,
                                   devices=_jax.devices()
                                   [:_axis_product(mesh_cfg)])
                from ..serve.compile_cache import bucket_sizes
                from ..serve.server import serve_image_spec
                trainer = _trainer_for(copy.deepcopy(cfg), mesh)
                state = _abstract_state(trainer, cfg)
                pad_to = trainer.eval_pad_multiple()
                img_shape, img_dtype = serve_image_spec(cfg)
                max_batch = cfg.serve.max_batch or \
                    cfg.data.eval_batch_size
                buckets = bucket_sizes(max_batch, pad_to)
                # the dtype/collective story is bucket-independent; the
                # largest bucket is the signature, the smallest rides
                # along only for the serving workhorse preset
                probe = sorted({buckets[-1]} | (
                    {buckets[0]} if name == "imagenet_resnet50" else set()))
                for bucket in probe:
                    def build_serve(bucket=bucket, trainer=trainer,
                                    state=state):
                        import jax as __jax
                        sbatch = {"images": __jax.ShapeDtypeStruct(
                            (bucket,) + img_shape, img_dtype)}
                        return extract_schedule(trainer._predict_step,
                                                state, sbatch)
                    record(name, label, f"serve_b{bucket}", build_serve,
                           deterministic_retrace=False)
            except Exception as e:
                findings.append(Finding(
                    RULE, _schedule_key(name, layouts[0][0], "serve"), 0,
                    f"serve schedule setup failed: {e}"))
    return findings, signatures


def _rle(ops: Sequence[dict]) -> List[dict]:
    """Run-length-encode consecutive identical ops for the artifact (a
    ResNet's per-BN-layer moment psums are dozens of identical 64-byte
    entries — one ``count`` line diffs better than 50 repeats)."""
    out: List[dict] = []
    for op in ops:
        if out and {k: v for k, v in out[-1].items() if k != "count"} == op:
            out[-1]["count"] += 1
        else:
            out.append({**op, "count": 1})
    return out


def write_artifact(signatures: Dict[str, dict],
                   path: Optional[str] = None) -> str:
    """Dump the signature map as the committed, reviewable artifact —
    sorted keys, fixed layout, trailing newline: byte-identical across
    runs whenever the schedules are (which the determinism check
    enforces)."""
    import json
    import os
    if path is None:
        path = artifact_path()
    doc = {"schema_version": 1, "signatures": {
        key: {**entry, "ops": _rle(entry["ops"])}
        for key, entry in signatures.items()}}
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


def load_schedules(path: Optional[str] = None) -> Dict[str, dict]:
    """The committed artifact's ``signatures`` map; empty when the file is
    absent or unreadable (``main.py plan`` then says to run the gate)."""
    import json
    import logging
    path = path or artifact_path()
    try:
        with open(path) as f:
            return json.load(f).get("signatures", {})
    except (OSError, ValueError) as e:
        logging.getLogger(__name__).warning(
            "no readable collective schedule at %s (%s)", path, e)
        return {}


def artifact_path() -> str:
    import os
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "collective_schedules.json")
