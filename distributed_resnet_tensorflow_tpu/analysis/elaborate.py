"""Static elaboration: trace every preset × mesh layout abstractly.

For each configuration this module builds a VIRTUAL device mesh
(``utils/virtual_devices.py`` — the same fake-CPU-mesh trick the test
suite and ``dryrun_multichip`` use), constructs the real Trainer, and
pushes shape/dtype-only values through:

  * state construction  (``train/state.abstract_train_state``),
  * the sharding rules  (every leaf's PartitionSpec validated against its
    shape and the mesh — the offending PARAM PATH and spec are reported,
    not a 40-frame XLA traceback),
  * the train step      (``jax.eval_shape`` of value_and_grad — this is
    where shard_map in/out-spec errors, rank errors and divisibility
    errors surface at trace time; the pp×ep MoE ``_SpecError`` of
    tests/test_pipeline.py was located exactly this way),
  * the eval step,
  * the serve/predict step, once per batch bucket the inference server
    would AOT-compile (serve/compile_cache.bucket_sizes),
  * the coalesced staged-unpack program — with the fused on-device
    imagenet augmentation when the preset would run it
    (parallel/sharding.abstract_staged_unpack), flat and stacked, and
  * the checkpoint-restore contract (layout stamp + unique leaf paths).

Zero data, zero compute, no compilation: the whole ``--all-presets``
sweep runs in seconds on CPU — cheap enough to be a pre-submit gate
(``scripts/analysis_gate.sh``) instead of a 20-minute queue wait that
ends in a step-1 crash.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .report import Finding


def _findings_from_exc(rule: str, locus: str, phase: str,
                       exc: Exception) -> Finding:
    msg = f"{type(exc).__name__}: {exc}"
    first = msg.splitlines()[0][:300]
    return Finding(rule, locus, 0, f"{phase}: {first}", detail=msg[:4000])


def candidate_layouts(cfg, n_devices: int) -> List[Tuple[str, "object"]]:
    """(label, MeshConfig) pairs worth elaborating for this config.

    Always the two data-parallel shapes every model family supports; for
    the transformer family additionally a pipeline and a tensor layout
    (those axes only have consumers there — Trainer rejects them
    elsewhere). Layouts that cannot satisfy the model's own divisibility
    contracts (depth % stages, heads % tensor, local batch % microbatches)
    are filtered HERE — the elaborator's job is finding bugs in valid
    configs, not re-reporting documented constraints."""
    from ..utils.config import MeshConfig
    out = [("dp", MeshConfig(data=n_devices))]
    if n_devices % 2 == 0:
        out.append(("dp_fsdp", MeshConfig(data=n_devices // 2, fsdp=2)))
    if cfg.model.name == "vit":
        from ..models.pipeline import resolve_microbatches
        depth = cfg.model.vit_depth
        heads = cfg.model.vit_heads
        hidden = 4 * cfg.model.vit_dim
        bs = cfg.train.batch_size
        v = max(1, cfg.model.vit_pipeline_interleave)
        p = 2
        m = resolve_microbatches(cfg.model.vit_pipeline_microbatches, p)

        def pp_ok(local_b: int) -> bool:
            # mirror PipelinedEncoder's OWN contract exactly (depth %
            # (P*v), local batch % M, and M >= P only under the circular
            # schedule's wrap) — stricter filtering here would silently
            # drop layouts that run fine, laxer would re-report the
            # encoder's documented ValueErrors as gate findings
            return depth % (p * v) == 0 and local_b % m == 0 and \
                (v == 1 or m >= p)

        # dp=2 × pp=2: each data shard runs its own 2-stage pipeline
        if pp_ok(bs // 2):
            out.append(("dp_pp", MeshConfig(data=2, pipeline=p)))
        if heads % 2 == 0 and hidden % 2 == 0 and n_devices % 8 == 0:
            out.append(("dp_tp", MeshConfig(data=4, tensor=2)))
        e = cfg.model.vit_num_experts
        if e > 0 and e % 2 == 0 and pp_ok(bs // 2):
            out.append(("dp_pp_ep",
                        MeshConfig(data=2, pipeline=2, expert=2)))
    return out


def _axis_product(mesh_cfg) -> int:
    return math.prod(max(1, s) for s in (
        mesh_cfg.data, mesh_cfg.fsdp, mesh_cfg.tensor, mesh_cfg.pipeline,
        mesh_cfg.sequence, mesh_cfg.expert))


def has_classifier_forward(cfg) -> bool:
    """False for a token model: it has no eval or serve forward yet (token
    serving waits, ROADMAP Queue 2), so its train step is what there is to
    trace."""
    from ..models.transformer import FAMILIES
    return cfg.model.name not in FAMILIES


def _abstract_batch(cfg, batch_size: int):
    """Shape/dtype skeleton of one host batch as the input pipeline would
    deliver it on this backend (float32 images after host-side prep)."""
    import jax
    if cfg.data.dataset == "tokens":  # inputs and next-token targets
        return {"tokens": jax.ShapeDtypeStruct(
            (batch_size, cfg.data.seq_len + 1), np.int32)}
    if cfg.data.dataset == "blockdiff_tokens":  # ids and their noising
        rows = (batch_size, cfg.data.seq_len)
        return {"tokens": jax.ShapeDtypeStruct(rows, np.int32),
                "masked": jax.ShapeDtypeStruct(rows, np.uint8),
                "t": jax.ShapeDtypeStruct(
                    (batch_size, rows[1] // cfg.model.block_length),
                    np.float32)}
    if cfg.model.name == "logistic":
        img = jax.ShapeDtypeStruct((batch_size, cfg.model.input_size),
                                   np.float32)
    else:
        s = cfg.data.image_size
        img = jax.ShapeDtypeStruct((batch_size, s, s, 3), np.float32)
    lab = jax.ShapeDtypeStruct((batch_size,), np.int32)
    return {"images": img, "labels": lab}


def check_spec_tree(state_shapes, shardings, mesh,
                    locus: str) -> Iterable[Finding]:
    """Validate every leaf's PartitionSpec against its shape and the mesh:
    spec rank ≤ array rank, and every named axis (product) divides its
    dimension. This is the report that names the offending param path and
    spec instead of a runtime ``_SpecError``."""
    import jax
    flat_shapes = jax.tree_util.tree_flatten_with_path(state_shapes)[0]
    flat_shard = jax.tree_util.tree_flatten_with_path(shardings)[0]
    shard_by_path = {jax.tree_util.keystr(p): s for p, s in flat_shard}
    for path, leaf in flat_shapes:
        key = jax.tree_util.keystr(path)
        sh = shard_by_path.get(key)
        spec = getattr(sh, "spec", None)
        if spec is None:
            continue
        shape = tuple(getattr(leaf, "shape", ()))
        if len(spec) > len(shape):
            yield Finding(
                "elab-spec", locus, 0,
                f"param {key}: spec {spec} has rank {len(spec)} but the "
                f"leaf has shape {shape} (rank {len(shape)})")
            continue
        for d, names in enumerate(spec):
            if names is None:
                continue
            names = names if isinstance(names, tuple) else (names,)
            size = math.prod(mesh.shape.get(n, 1) for n in names)
            if size and shape[d] % size:
                yield Finding(
                    "elab-spec", locus, 0,
                    f"param {key}: spec {spec} maps dim {d} "
                    f"(size {shape[d]}) onto mesh axes {names} of total "
                    f"size {size}, which does not divide it")


def elaborate_config(cfg, mesh_cfg, locus: str,
                     trace_steps: bool = True,
                     trace_forward: bool = True,
                     _state_cache: Optional[dict] = None,
                     _precision_seen: Optional[set] = None) -> List[Finding]:
    """Elaborate ONE (config, mesh layout): returns findings (empty=clean).

    ``trace_steps=False`` skips the train/eval-step traces (the expensive
    part) — used by run_elaborate for layouts whose step graph is
    IDENTICAL to one already traced: a CNN's step does not read the mesh
    at trace time (only jit placement does), so dp vs dp_fsdp re-traces
    would buy nothing. Transformer configs re-trace per layout (the mesh
    is baked into the pipeline/tensor/expert program). ``_state_cache``
    memoizes the abstract state per batch-shard count for the same
    reason.

    ``trace_forward=False`` additionally skips the OPTIMIZER-INDEPENDENT
    traces (eval step, serve buckets) — used when another preset with
    the identical forward config (model × data × serve) already traced
    them: the large-batch optimizer variants (lars4k/lamb4k/lars32k)
    share imagenet_resnet50's forward exactly, and re-sweeping every
    serve bucket per optimizer would triple the gate's largest cost for
    zero coverage."""
    import jax
    from ..parallel.mesh import batch_shard_count, create_mesh
    from ..train.loop import Trainer
    from ..train.state import (abstract_train_state, init_input,
                               state_shardings)
    from ..utils.config import stacked_layout_stamp

    findings: List[Finding] = []
    n = _axis_product(mesh_cfg)
    devices = jax.devices()[:n]
    if len(devices) < n:
        return [Finding("elab-env", locus, 0,
                        f"layout needs {n} devices but only "
                        f"{len(devices)} present — run under "
                        "utils.virtual_devices.apply_virtual_cpu")]
    try:
        mesh = create_mesh(mesh_cfg, devices=devices)
        trainer = Trainer(cfg, mesh=mesh)
    except Exception as e:
        return [_findings_from_exc("elab-build", locus, "trainer build", e)]

    try:
        nb = batch_shard_count(mesh)
        cache_key = (nb, cfg.model.name == "vit" and (
            mesh.shape.get("pipeline", 1), mesh.shape.get("tensor", 1),
            mesh.shape.get("expert", 1), mesh.shape.get("seq", 1)))
        state_shapes = None if _state_cache is None \
            else _state_cache.get(cache_key)
        if state_shapes is None:
            state_shapes = abstract_train_state(
                trainer.model, trainer.tx,
                init_input(trainer.model, cfg, nb))
            if _state_cache is not None:
                _state_cache[cache_key] = state_shapes
    except Exception as e:
        return [_findings_from_exc("elab-state", locus, "state init", e)]

    try:
        shardings = state_shardings(state_shapes, mesh)
        findings.extend(check_spec_tree(state_shapes, shardings, mesh,
                                        locus))
    except Exception as e:
        findings.append(_findings_from_exc("elab-spec", locus,
                                           "sharding rules", e))
        return findings

    # train step: trace fwd+bwd+optimizer abstractly. shard_map spec/rank
    # mismatches, collective-axis errors and AD residual issues all fire
    # at trace time (zero compute)
    if trace_steps:
        try:
            batch = _abstract_batch(cfg, cfg.train.batch_size)
            jax.eval_shape(trainer._train_step, state_shapes, batch)
        except Exception as e:
            findings.append(_findings_from_exc("elab-train-step", locus,
                                               "train step", e))

        # eval step: batch padded exactly as Trainer.evaluate pads it
        # (batch shards × pipeline microbatches). Optimizer-independent:
        # skipped when an identical-forward preset already traced it
        # (trace_forward)
        try:
            if trace_forward:
                pad_to = trainer.eval_pad_multiple()
                ebs = cfg.data.eval_batch_size
                ebs = ebs + (-ebs) % pad_to  # pad_batch_to_multiple contract
                ebatch = _abstract_batch(cfg, ebs)
                ebatch["mask"] = jax.ShapeDtypeStruct((ebs,), np.float32)
                jax.eval_shape(trainer._eval_step, state_shapes, ebatch)
        except Exception as e:
            findings.append(_findings_from_exc("elab-eval-step", locus,
                                               "eval step", e))

        # serve/predict step: every batch bucket the inference server
        # would AOT-compile for this preset (serve/compile_cache.py —
        # power-of-two buckets in multiples of the eval pad floor, the
        # request dtype from serve_image_spec), traced abstractly so a
        # bucket that can't trace is a gate finding here, not a serving
        # replica that dies warming its compile cache. Optimizer-
        # independent like the eval step (trace_forward).
        buckets = []
        try:
            if trace_forward:
                from ..serve.compile_cache import bucket_sizes
                from ..serve.server import serve_image_spec
                pad_to = trainer.eval_pad_multiple()
                img_shape, img_dtype = serve_image_spec(cfg)
                # the SAME cap resolution the server uses
                # (InferenceServer): a preset pinning serve.max_batch
                # past eval_batch_size gets its real buckets elaborated,
                # not the eval-sized ones
                max_batch = cfg.serve.max_batch or cfg.data.eval_batch_size
                buckets = bucket_sizes(max_batch, pad_to)
        except Exception as e:
            findings.append(_findings_from_exc("elab-serve-step", locus,
                                               "serve step setup", e))
            buckets = []
        for bucket in buckets:
            # per-bucket try: one gate run reports EVERY bad bucket, not
            # whack-a-mole one per run
            try:
                sbatch = {"images": jax.ShapeDtypeStruct(
                    (bucket,) + img_shape, img_dtype)}
                jax.eval_shape(trainer._predict_step, state_shapes, sbatch)
            except Exception as e:
                findings.append(_findings_from_exc(
                    "elab-serve-step", locus,
                    f"serve step (bucket {bucket})", e))

        # bf16 precision-policy step (parallel/precision.py): the
        # train.precision=bf16 variant of this preset × layout, traced
        # abstractly over the SAME f32 master state shapes (the policy's
        # whole contract) — a policy cast that breaks a shard_map spec,
        # a model family that can't take the dtype override, or a
        # fused-kernel dtype mismatch is a gate finding here, not a
        # step-1 crash when an operator first flips the knob. Presets
        # that already pin precision=bf16 were traced above.
        try:
            import copy
            import dataclasses as _dc
            # dedupe across presets sharing the identical
            # (model, data, optimizer) triple — the schedule/batch
            # variants of one base preset would re-trace the same bf16
            # program (the trace_forward lesson from round 11). Batch
            # size is deliberately NOT in the key: this trace hunts
            # DTYPE bugs, which are batch-independent; divisibility is
            # the main elab-train-step trace's job, per preset.
            pkey = repr((_dc.asdict(cfg.model), cfg.data.dataset,
                         cfg.data.image_size, cfg.optimizer.name))
            seen = _precision_seen if _precision_seen is not None \
                else set()
            if cfg.train.precision == "off" and pkey not in seen:
                seen.add(pkey)
                pcfg = copy.deepcopy(cfg)
                pcfg.train.precision = "bf16"
                ptrainer = Trainer(pcfg, mesh=mesh)
                batch = _abstract_batch(pcfg, pcfg.train.batch_size)
                jax.eval_shape(ptrainer._train_step, state_shapes, batch)
                if trace_forward:
                    # the serving reduced-precision VARIANT forwards,
                    # one bucket each (the dtype path is
                    # bucket-independent) — traced over the CAST
                    # abstract state, exactly what ServeCompileCache
                    # compiles each variant against. "bf16" covers the
                    # cast-dtype path, "int8" the weight-only
                    # quantize/dequantize path (marker-dict param tree)
                    from ..parallel.precision import make_variant_cast
                    pad_to = ptrainer.eval_pad_multiple()
                    from ..serve.server import serve_image_spec
                    vshape, vdtype = serve_image_spec(pcfg)
                    vbatch = {"images": jax.ShapeDtypeStruct(
                        (pad_to,) + vshape, vdtype)}
                    for variant in ("bf16", "int8"):
                        vstep = ptrainer.make_variant_predict_step(
                            variant)
                        vstate = jax.eval_shape(
                            make_variant_cast(variant), state_shapes)
                        jax.eval_shape(vstep, vstate, vbatch)
        except Exception as e:
            findings.append(_findings_from_exc(
                "elab-precision-step", locus, "bf16 precision step", e))

        # coalesced staged-unpack program (parallel/sharding._build_unpack)
        # — and, for imagenet presets, the FUSED on-device augmentation
        # riding inside it — traced abstractly per preset, flat and
        # stacked, same gate contract as the serve buckets: an unpack or
        # augment program that cannot trace is a finding here, not a
        # step-1 crash after cluster spin-up. Layouts whose local batch
        # does not divide the batch shards are skipped (every put path
        # rejects those loudly at runtime already — not this gate's bug
        # class).
        try:
            from ..parallel.sharding import (_device_batch_shards,
                                             abstract_staged_unpack)
            bs = cfg.train.batch_size
            n_local = len({s for _, s in _device_batch_shards(mesh)})
            if bs % n_local == 0:
                imagenet = cfg.data.dataset == "imagenet"
                img_dt = np.uint8 if imagenet else np.float32
                # trace the augmenting unpack only when the Trainer
                # would actually build one (imagenet + device_augment
                # not forced off + no transfer reuse — loop.py mirrors
                # this); the neutral unpack is traced for every preset
                fuses = imagenet and cfg.data.device_augment != "off" \
                    and cfg.data.echo_transfer <= 1
                augments = [None] + (
                    [("images", "imagenet_train", cfg.data.augment_pad)]
                    if fuses else [])
                s = cfg.data.image_size
                k = max(2, cfg.train.steps_per_loop)
                for stacked in (False, True):
                    if cfg.model.name == "logistic":
                        ishape = (cfg.model.input_size,)
                    else:
                        ishape = (s, s, 3)
                    lead = (k, bs) if stacked else (bs,)
                    batch_shapes = {
                        "images": jax.ShapeDtypeStruct(lead + ishape,
                                                       img_dt),
                        "labels": jax.ShapeDtypeStruct(lead, np.int32)}
                    for augment in augments:
                        abstract_staged_unpack(
                            mesh, batch_shapes, stacked=stacked,
                            augment=augment, augment_seed=cfg.train.seed)
        except Exception as e:
            findings.append(_findings_from_exc(
                "elab-unpack", locus, "staged unpack (+fused augment)", e))

    # restore contract: the layout stamp must compute, and every leaf path
    # must be unique (the checkpoint manifest is keyed by flattened path)
    try:
        stacked_layout_stamp(cfg)
        flat = jax.tree_util.tree_flatten_with_path(state_shapes)[0]
        keys = [jax.tree_util.keystr(p) for p, _ in flat]
        dupes = {k for k in keys if keys.count(k) > 1}
        if dupes:
            findings.append(Finding(
                "elab-restore", locus, 0,
                f"duplicate state leaf paths {sorted(dupes)[:3]} — the "
                "checkpoint manifest cannot address them"))
    except Exception as e:
        findings.append(_findings_from_exc("elab-restore", locus,
                                           "restore contract", e))
    return findings


#: virtual mesh sizes the ZeRO-1 big-mesh sweep validates against —
#: catching a spec that only breaks at scale (a moment dim 64 devices
#: divide but 256 don't) STATICALLY, before any cluster time
ZERO1_SWEEP_SIZES = (64, 256)


def run_elaborate_zero1(preset_names: Optional[Sequence[str]] = None,
                        sizes: Sequence[int] = ZERO1_SWEEP_SIZES
                        ) -> List[Finding]:
    """The ``elab-zero1`` big-mesh sweep: for every in-envelope preset —
    one that enables ``optimizer.zero1`` (on/auto; a preset with the
    knob off has no ZeRO-1 step or sharded specs to elaborate) and whose
    global batch the layout divides — resolve the ZeRO-1 sharded
    optimizer-state specs on virtual 64- and 256-device dp and dp_fsdp
    meshes and spec-check every leaf (``check_spec_tree`` — the
    offending leaf PATH, not a step-1 ``_SpecError`` on a real pod); for
    presets that PIN the knob on, additionally ``eval_shape`` the full
    ZeRO-1 train step (reduce-scatter constraint + sharded update +
    gather) on the largest mesh. Zero compute; rides the same gate
    budget contract as the 8-device sweep (scripts/analysis_gate.sh)."""
    import copy
    import jax
    from ..parallel.mesh import create_mesh
    from ..parallel.sharding import (ZERO1_MIN_SIZE, Zero1Report,
                                     zero1_state_shardings,
                                     zero1_unsupported_reason)
    from ..train.loop import Trainer
    from ..train.state import abstract_train_state, init_input
    from ..utils.config import MeshConfig, PRESETS, get_preset

    import dataclasses
    findings: List[Finding] = []
    need = max(sizes)
    if len(jax.devices()) < need:
        return [Finding(
            "elab-env", "zero1-sweep", 0,
            f"{len(jax.devices())} devices present, {need} needed — the "
            "check CLI must size the virtual CPU mesh for the ZeRO-1 "
            "sweep before jax initializes")]
    # abstract states shared across presets with the identical
    # (model, optimizer) pair — the large-batch variants of one base
    # preset differ only in schedule hyperparams, not state SHAPES
    shared_states: dict = {}
    for name in (preset_names or sorted(PRESETS)):
        cfg = get_preset(name)
        if cfg.optimizer.zero1 == "off":
            continue  # no ZeRO-1 step/specs to elaborate for this preset
        state_key = repr((dataclasses.asdict(cfg.model),
                          cfg.optimizer.name, cfg.data.dataset,
                          cfg.data.image_size))
        state_shapes = shared_states.get(state_key)
        traced = False
        for n in sorted(sizes, reverse=True):
            if cfg.train.batch_size % n:
                continue  # the layout cannot host this preset's batch
            layouts = [(f"zero1-dp{n}", MeshConfig(data=n)),
                       (f"zero1-dp{n // 2}f2",
                        MeshConfig(data=n // 2, fsdp=2))]
            for label, mesh_cfg in layouts:
                locus = f"{name}@{label}"
                try:
                    mesh = create_mesh(mesh_cfg, devices=jax.devices()[:n])
                except Exception as e:
                    findings.append(_findings_from_exc(
                        "elab-zero1", locus, "mesh build", e))
                    continue
                if zero1_unsupported_reason(cfg, mesh) is not None:
                    continue  # outside the envelope — documented, not a bug
                try:
                    if state_shapes is None:
                        # model/optimizer shapes are mesh-independent for
                        # the batch-parallel families: build once per
                        # (model, optimizer), spec-check every
                        # (preset, size, layout)
                        t = Trainer(copy.deepcopy(cfg), mesh=mesh)
                        state_shapes = abstract_train_state(
                            t.model, t.tx, init_input(t.model, cfg, 1))
                        shared_states[state_key] = state_shapes
                except Exception as e:
                    findings.append(_findings_from_exc(
                        "elab-zero1", locus, "state init", e))
                    break
                try:
                    min_size = cfg.optimizer.zero1_min_size \
                        or ZERO1_MIN_SIZE
                    report = Zero1Report(mesh.shape.get("data", 1))
                    opt_sh = zero1_state_shardings(
                        state_shapes.opt_state, mesh, min_size=min_size,
                        report=report)
                    findings.extend(check_spec_tree(
                        state_shapes.opt_state, opt_sh, mesh, locus))
                    if cfg.optimizer.zero1 == "on" and \
                            report.sharded_leaves == 0:
                        findings.append(Finding(
                            "elab-zero1", locus, 0,
                            "optimizer.zero1=on resolves FULLY replicated "
                            f"at {n} data shards "
                            f"(reasons: {report.reasons}) — the promised "
                            "per-replica memory cut vanishes at this "
                            "scale"))
                except Exception as e:
                    findings.append(_findings_from_exc(
                        "elab-zero1", locus, "zero1 sharding rules", e))
                    continue
                # trace the full ZeRO-1 step once per preset that PINS
                # the knob on, on the largest dp layout — the reduce-
                # scatter constraint / sharded update / gather must
                # TRACE at scale, not just spec-check ("auto" presets
                # spec-check only: their step is covered by the 8-device
                # sweep and the "on" presets' traces)
                if cfg.optimizer.zero1 == "on" and not traced \
                        and mesh_cfg.fsdp <= 1:
                    traced = True
                    try:
                        ocfg = copy.deepcopy(cfg)
                        ocfg.optimizer.zero1 = "on"
                        otrainer = Trainer(ocfg, mesh=mesh)
                        batch = _abstract_batch(ocfg,
                                                ocfg.train.batch_size)
                        jax.eval_shape(otrainer._train_step,
                                       state_shapes, batch)
                    except Exception as e:
                        findings.append(_findings_from_exc(
                            "elab-zero1", locus, "zero1 train step", e))
    return findings


def run_elaborate(preset_names: Optional[Sequence[str]] = None,
                  n_devices: int = 8) -> List[Finding]:
    """Elaborate the named presets (default: all) across their candidate
    layouts. Call ``apply_virtual_cpu(n_devices)`` BEFORE the jax backend
    initializes (main.py's ``check`` subcommand does)."""
    import jax
    from ..utils.config import PRESETS, get_preset

    findings: List[Finding] = []
    if len(jax.devices()) < n_devices:
        return [Finding(
            "elab-env", "environment", 0,
            f"{len(jax.devices())} devices present, {n_devices} needed — "
            "the check CLI must set up the virtual CPU mesh before jax "
            "initializes")]
    import dataclasses
    seen_forward: set = set()
    precision_seen: set = set()  # bf16-trace dedupe across presets
    for name in (preset_names or sorted(PRESETS)):
        cfg = get_preset(name)
        state_cache: dict = {}
        traced = False
        # optimizer-independent traces (eval step, serve buckets) dedupe
        # across presets sharing the identical forward config — the
        # large-batch optimizer variants of one base preset
        fwd_key = repr((dataclasses.asdict(cfg.model),
                        dataclasses.asdict(cfg.data),
                        dataclasses.asdict(cfg.serve)))
        fwd = fwd_key not in seen_forward and has_classifier_forward(cfg)
        seen_forward.add(fwd_key)
        for label, mesh_cfg in candidate_layouts(cfg, n_devices):
            # the step graph only changes with PROGRAM-SHAPING axes
            # (pipeline/tensor/expert/seq bake shard_maps into the model);
            # dp vs dp_fsdp re-traces the identical graph, so trace once
            # per distinct program and spec-check every layout
            shaping = max(mesh_cfg.pipeline, 1) > 1 or \
                max(mesh_cfg.tensor, 1) > 1 or \
                max(mesh_cfg.expert, 1) > 1 or \
                max(mesh_cfg.sequence, 1) > 1
            trace = shaping or not traced
            findings.extend(
                elaborate_config(cfg, mesh_cfg, f"{name}@{label}",
                                 trace_steps=trace,
                                 trace_forward=trace and fwd,
                                 _state_cache=state_cache,
                                 _precision_seen=precision_seen))
            traced = True
    return findings
