"""chip_smoke.py — the quickest proof that the trainer still starts on the chip.

    python chip_smoke.py            # every leg, one child process each
    python chip_smoke.py --leg imagenet -- --set mesh.data=2 --set mesh.fsdp=2

Drives the main path through the entry point a user calls —
``distributed_resnet_tensorflow_tpu.main.main(argv)`` with the argv the CLI
would get — at the full width of the models the repo ships, on whatever TPU
chips ``jax.devices()`` reports (batch is per chip; the global batch scales
with the chip count). Step counts are short, weights are random from the
preset's seed, and the CIFAR files and ImageNet TFRecords come from the
seeded generators ``tools/make_synth_cifar.py`` / ``make_synth_imagenet.py``
in the same run.

The PARENT never imports jax (directly or through the package): a process
that has touched JAX holds the chip, and the child that needs it would fail
or hang. Each leg is a child (``--leg <name>``) that owns the chip until it
exits, calls ``main()``, and then asserts positives in that same process:
the platform is ``tpu``, the step counter advanced by what was asked, every
logged loss is finite, the train step's program contains a
``tpu_custom_call`` wherever a Pallas kernel was resolved (so a quiet
fall-back to optax cross-entropy or dense attention fails the leg), the
allocator reported ``peak_bytes_in_use``, and a checkpoint was committed.

Without a TPU the first child exits 3 within seconds, naming the platform
it found, and the parent stops there. Any failing leg makes the exit code
non-zero and no result line is printed. On success the LAST line of stdout
is one JSON object: ``{"ok": true, "device": {"platform": "tpu", "kind":
..., "count": ...}}``. The steps/s printed per leg are NOT measurements
(per-step host syncs, compile included in the window's head).

Writes only under ``chiprun_out/chip_smoke/`` next to this file and the
compile-cache directory (``JAX_COMPILATION_CACHE_DIR`` or ``.jax_cache``).
"""
from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
LOGS = os.path.join(OUT, "logs")
CIFAR_DIR = os.path.join(OUT, "cifar")
CIFAR_RUN = os.path.join(OUT, "run_cifar")
IMAGENET_DIR = os.path.join(OUT, "imagenet")

#: seeded input files, generated by the parent while the first leg runs
#: (numpy/PIL only — the generators never import jax): leg -> command
DATA_PREP = {
    "cifar": ["tools/make_synth_cifar.py", CIFAR_DIR, "--train", "25600",
              "--test", "1280", "--seed", "0"],
    "imagenet": ["tools/make_synth_imagenet.py", IMAGENET_DIR, "--classes",
                 "16", "--train-per-class", "64", "--val-per-class", "4",
                 "--shards", "8", "--seed", "0"],
}

#: run order. ``serve`` is last and is the one leg the issue allows dropping
LEGS = ("kernels", "cifar", "cifar_resume", "imagenet", "long_context",
        "overlap", "serve")
#: legs that need an earlier leg's artifacts
NEEDS = {"cifar_resume": "cifar", "serve": "cifar"}

#: the contract allows 1200 s; leave room for interpreter start and cleanup
TOTAL_BUDGET_SECS = 1150.0
#: child -> parent only ("stop, there is no chip"); the parent exits 0 or 1
NO_TPU_EXIT = 3

CIFAR_STEPS = 200
CIFAR_RESUME_STEPS = 50
IMAGENET_STEPS = 20
LONG_CONTEXT_STEPS = 3
#: chips the ``overlap`` leg lays ``mesh.data`` over (one v5e host)
OVERLAP_CHIPS = 4


# ---------------------------------------------------------------------------
# parent: no jax here
# ---------------------------------------------------------------------------

def _start(cmd, log_path: str) -> subprocess.Popen:
    """Start ``cmd`` in its own process group, output to ``log_path``."""
    with open(log_path, "w") as log:
        return subprocess.Popen(cmd, cwd=ROOT, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)


def _stop(proc: subprocess.Popen) -> None:
    """Kill the child's whole group if it is still running."""
    if proc.poll() is None:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait()


def _wait(proc: subprocess.Popen, timeout: float) -> int:
    """Exit code of ``proc``; 124 (group killed) if it outlives
    ``timeout`` or we are interrupted while waiting."""
    try:
        return proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"[chip_smoke] timed out after {timeout:.0f}s: "
              f"{' '.join(proc.args[1:])}", flush=True)
        return 124
    finally:
        _stop(proc)


def _echo(log_path: str, failed: bool) -> None:
    """The child's own ``[chip_smoke`` lines; on failure also its tail."""
    with open(log_path, errors="replace") as f:
        lines = f.read().splitlines()
    for line in lines:
        if line.startswith("[chip_smoke"):
            print(line)
    if failed:
        print(f"---- tail of {os.path.relpath(log_path, ROOT)}")
        print("\n".join(lines[-60:]))
        print("----", flush=True)


def parent_main() -> int:
    deadline = time.monotonic() + TOTAL_BUDGET_SECS
    # no wipe of OUT: a run that finds no chip must not destroy the last
    # chip run's evidence. Every leg replaces its own result (written only
    # on success, read only after exit code 0) and its own run directory.
    os.makedirs(LOGS, exist_ok=True)
    results = {}
    failed = []
    prep = {}
    try:
        for name, cmd in DATA_PREP.items():
            prep[name] = _start(
                [sys.executable, os.path.join(ROOT, cmd[0]), *cmd[1:]],
                os.path.join(LOGS, f"prep_{name}.log"))
        for leg in LEGS:
            if leg in NEEDS and NEEDS[leg] not in results:
                print(f"[chip_smoke] {leg}: FAILED (needs "
                      f"{NEEDS[leg]}, which failed)", flush=True)
                failed.append(leg)
                continue
            log_path = os.path.join(LOGS, f"{leg}.log")
            if leg in prep and _wait(
                    prep[leg], max(1.0, deadline - time.monotonic())) != 0:
                _echo(os.path.join(LOGS, f"prep_{leg}.log"), failed=True)
                print(f"[chip_smoke] {leg}: FAILED (input files)",
                      flush=True)
                failed.append(leg)
                continue
            t0 = time.monotonic()
            rc = _wait(_start([sys.executable, os.path.abspath(__file__),
                               "--leg", leg], log_path),
                       max(1.0, deadline - time.monotonic()))
            _echo(log_path, failed=rc not in (0, NO_TPU_EXIT))
            if rc == 0:
                with open(os.path.join(OUT, f"{leg}.json")) as f:
                    results[leg] = json.load(f)
                print(f"[chip_smoke] {leg}: ok in "
                      f"{time.monotonic() - t0:.0f}s", flush=True)
                continue
            print(f"[chip_smoke] {leg}: FAILED rc={rc} after "
                  f"{time.monotonic() - t0:.0f}s", flush=True)
            failed.append(leg)
            if rc == NO_TPU_EXIT:
                break  # no chip: nothing below can pass either
    finally:
        for proc in prep.values():
            _stop(proc)
        # bulky artifacts: the output directory is copied back by the tool
        shutil.rmtree(CIFAR_DIR, ignore_errors=True)
        shutil.rmtree(IMAGENET_DIR, ignore_errors=True)
        for name in os.listdir(OUT):
            shutil.rmtree(os.path.join(OUT, name, "ckpt"),
                          ignore_errors=True)
    if failed or set(results) != set(LEGS):
        print(f"[chip_smoke] FAILED: {failed or 'incomplete'}", flush=True)
        return 1
    devices = {json.dumps(r["device"], sort_keys=True)
               for r in results.values()}
    if len(devices) != 1:
        print(f"[chip_smoke] FAILED: legs disagree on the device: {devices}")
        return 1
    print(json.dumps({"ok": True,
                      "device": next(iter(results.values()))["device"]}),
          flush=True)
    return 0


# ---------------------------------------------------------------------------
# children: one leg each, jax from here on
# ---------------------------------------------------------------------------

def say(leg: str, msg: str) -> None:
    print(f"[chip_smoke:{leg}] {msg}", flush=True)


def check(cond, msg: str) -> None:
    """A smoke assertion — raises (not ``assert``: must survive -O)."""
    if not cond:
        raise AssertionError(msg)


def require_tpu(leg: str) -> dict:
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        say(leg, f"no TPU: jax.devices() found platform="
                 f"{device['platform']!r} ({device['kind']}, "
                 f"{device['count']} device(s)); this smoke only passes on "
                 "the chip")
        sys.exit(NO_TPU_EXIT)
    from importlib.metadata import version
    say(leg, f"platform={device['platform']} device_kind={device['kind']!r} "
             f"chips={device['count']} "
             f"default_backend={jax.default_backend()} "
             f"jax={jax.__version__} jaxlib={version('jaxlib')} "
             f"libtpu={version('libtpu')}")
    return device


class CompileWatch:
    """Counts persistent-cache reads/writes and backend compile seconds
    through ``jax.monitoring`` from construction on."""

    def __init__(self):
        import jax.monitoring as mon
        self.hits = 0
        self.writes = 0
        self.compile_secs = 0.0
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1  # recorded when an entry is WRITTEN

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_secs += secs  # cache retrieval included


def train_rows(log_root: str):
    """(per-step scalar rows, ``memory`` event rows) of a run's stream."""
    from distributed_resnet_tensorflow_tpu.utils.metrics import read_metrics
    rows = read_metrics(os.path.join(log_root, "train"))
    return ([r for r in rows if "event" not in r and "loss" in r],
            [r for r in rows if r.get("event") == "memory"])


def check_training(leg: str, cfg, first_step: int, last_step: int) -> list:
    """Step counter, finite losses, allocator stats, committed checkpoint.
    Returns the loss per step over [first_step, last_step]."""
    import math

    import jax
    from distributed_resnet_tensorflow_tpu.resilience.manifest import (
        committed_steps)
    from distributed_resnet_tensorflow_tpu.utils.config import (
        resolve_checkpoint_dir)
    scalars, memory = train_rows(cfg.log_root)
    by_step = {r["step"]: r for r in scalars}
    want = list(range(first_step, last_step + 1))
    missing = [s for s in want if s not in by_step]
    check(not missing, f"no metrics row for steps {missing[:8]}… — the step "
                       f"counter did not advance {first_step}→{last_step}")
    check(max(by_step) == last_step,
          f"last logged step {max(by_step)} != requested {last_step}")
    losses = [by_step[s]["loss"] for s in want]
    bad = [(s, x) for s, x in zip(want, losses) if not math.isfinite(x)]
    check(not bad, f"non-finite loss at {bad[:4]}")
    steps = committed_steps(resolve_checkpoint_dir(cfg))
    check(steps and steps[-1] == last_step,
          f"no committed checkpoint at step {last_step}: have {steps}")
    stats = jax.devices()[0].memory_stats()
    check(stats and "peak_bytes_in_use" in stats,
          f"device.memory_stats() gave no peak_bytes_in_use: {stats}")
    rows = [r for r in memory if r.get("step", 0) >= first_step]
    check(rows, "no memory row in metrics.jsonl")
    per_dev = rows[-1].get("devices", {})
    check(len(per_dev) == jax.local_device_count()
          and all("peak_bytes_in_use" in c for c in per_dev.values()),
          f"memory row lacks allocator stats per device: {per_dev}")
    in_use = {d: c["bytes_in_use"] for d, c in sorted(per_dev.items())}
    check(max(in_use.values()) <= 4 * max(1, min(in_use.values())),
          f"device memory is not spread over the chips: {in_use}")
    window = [by_step[s] for s in want[len(want) // 2:]]
    rate = (len(window) - 1) / max(window[-1]["time"] - window[0]["time"],
                                   1e-9) if len(window) > 1 else 0.0
    say(leg, f"steps {first_step}..{last_step} logged, loss "
             f"{losses[0]:.4f} → {losses[-1]:.4f}, all finite; checkpoint "
             f"committed at step {steps[-1]}; ~{rate:.1f} steps/s over the "
             "second half (NOT a measurement)")
    say(leg, f"peak_bytes_in_use={stats['peak_bytes_in_use']} (device 0); "
             f"bytes_in_use per device {in_use}")
    return losses


def check_step_program(leg: str, cfg, expect: dict,
                       min_custom_calls: int) -> str:
    """Rebuild ``Trainer(cfg)`` from the leg's own config — the same
    builder of the same program ``main()`` just compiled and ran — and read
    the lowered train step. Returns the step's module name.

    The LOWERED text, not a second ``compile()``: that main() ran is the
    proof that Mosaic compiled the kernels, and compiling again here is a
    full compile, never a cache hit — the Mosaic payload carries the
    trace-time Python call stack, so the same program traced from this
    call site has another cache key (PERF.md, PR 21)."""
    import re

    import jax
    from distributed_resnet_tensorflow_tpu.main import _make_train_source
    from distributed_resnet_tensorflow_tpu.train.loop import Trainer

    trainer = Trainer(cfg)
    got = trainer.resolutions()
    say(leg, "resolved: " + " ".join(f"{k}={v}" for k, v in got.items()))
    wrong = {k: (got[k], v) for k, v in expect.items() if got[k] != v}
    check(not wrong, f"auto resolutions (got, expected): {wrong}")
    trainer.init_state()
    first = next(_make_train_source(cfg, trainer))
    text = trainer.lowered_step(first).as_text()
    calls = text.count("@tpu_custom_call")
    check(calls >= min_custom_calls,
          f"the train step holds {calls} tpu_custom_call(s), expected "
          f">= {min_custom_calls}: a Pallas kernel fell back")
    module = re.search(r"module @(\S+)", text).group(1)
    say(leg, f"train step {module!r}: {calls} tpu_custom_call(s) in the "
             "lowered program")
    for name, x in sorted(trainer.device_batch(first).items()):
        spread = len(x.sharding.device_set)
        check(spread == jax.device_count(),
              f"batch[{name!r}] spans {spread} of {jax.device_count()} "
              f"devices: {x.sharding}")
        say(leg, f"batch[{name!r}] {x.dtype}{list(x.shape)} is sharded "
                 f"{x.sharding.spec} over {spread} device(s)")
    if trainer.mesh.shape.get("fsdp", 1) > 1:
        leaves = jax.tree_util.tree_leaves_with_path(trainer.state.params)
        sharded = [(jax.tree_util.keystr(p), x.sharding.spec)
                   for p, x in leaves if "fsdp" in str(x.sharding.spec)]
        check(sharded, "mesh.fsdp > 1 but no parameter leaf names fsdp")
        say(leg, f"{len(sharded)} of {len(leaves)} parameter leaves are "
                 f"sharded over fsdp, e.g. {sharded[0][0]} "
                 f"{sharded[0][1]}")
    return module


def train_leg(leg: str, argv, first_step: int, last_step: int, expect: dict,
              min_custom_calls: int, cache_dir: str, extra) -> dict:
    """``main(argv)`` then the assertions. ``extra`` are main() arguments
    passed through from the command line (after ``--``). The result holds
    the compile-cache counters and directory listing as main() left
    them."""
    from distributed_resnet_tensorflow_tpu import main as drt_main
    from distributed_resnet_tensorflow_tpu.utils.config import parse_args
    watch = CompileWatch()
    argv = list(argv) + list(extra)
    say(leg, "main.py " + " ".join(argv))
    t0 = time.monotonic()
    drt_main.main(argv)
    wall = time.monotonic() - t0
    cfg = parse_args(argv)  # what main() just ran under
    say(leg, f"main() took {wall:.1f}s, of which backend compile + cache "
             f"retrieval {watch.compile_secs:.1f}s; persistent cache "
             f"entries read {watch.hits}, written {watch.writes}")
    out = {"compile_secs": round(watch.compile_secs, 2),
           "cache_hits": watch.hits, "cache_writes": watch.writes,
           "cache_entries": sorted(os.listdir(cache_dir))}
    out["losses"] = check_training(leg, cfg, first_step, last_step)
    out["step_module"] = check_step_program(leg, cfg, expect,
                                            min_custom_calls)
    return out


def _sets(overrides: dict):
    return [arg for k, v in overrides.items() for arg in ("--set", f"{k}={v}")]


def _cifar_argv(chips: int, steps: int):
    return ["--preset", "cifar10_resnet50"] + _sets({
        "data.data_dir": CIFAR_DIR, "data.use_native_loader": "true",
        "log_root": CIFAR_RUN, "train.batch_size": 128 * chips,
        "train.train_steps": steps, "train.summary_every_steps": 1})


def leg_cifar(device, cache_dir, extra) -> dict:
    """Leg 1 — the reference flagship on real file input: device_dataset,
    device_augment, coalesced_transfer and fused_xent all through auto."""
    shutil.rmtree(CIFAR_RUN, ignore_errors=True)  # a fresh run, not a resume
    out = train_leg(
        "cifar", _cifar_argv(device["count"], CIFAR_STEPS), 1, CIFAR_STEPS,
        {"fused_xent": "on", "coalesced_transfer": "on",
         "device_augment": "on", "device_dataset": "on"},
        min_custom_calls=2, cache_dir=cache_dir, extra=extra)
    head = sum(out["losses"][:20]) / 20
    tail = sum(out["losses"][-20:]) / 20
    check(tail < head, f"loss did not fall over {CIFAR_STEPS} steps: mean "
                       f"of first 20 {head:.4f}, of last 20 {tail:.4f}")
    say("cifar", f"mean loss first 20 steps {head:.4f} → last 20 {tail:.4f}")
    return out


def leg_cifar_resume(device, cache_dir, extra) -> dict:
    """Leg 1b — the same command with 50 more steps: restores leg 1's
    committed checkpoint and must not recompile the train step."""
    with open(os.path.join(OUT, "cifar.json")) as f:
        cold = json.load(f)
    last = CIFAR_STEPS + CIFAR_RESUME_STEPS
    out = train_leg(
        "cifar_resume", _cifar_argv(device["count"], last),
        CIFAR_STEPS + 1, last, {"fused_xent": "on", "device_dataset": "on"},
        min_custom_calls=2, cache_dir=cache_dir, extra=extra)
    rows = len(train_rows(CIFAR_RUN)[0])
    check(rows == last, f"{rows} step rows in metrics.jsonl for {last} "
                        f"steps: the run did not continue from the "
                        f"checkpoint at step {CIFAR_STEPS}")
    new = sorted(set(out["cache_entries"]) - set(cold["cache_entries"]))
    # entries are named <module name>-<hash>-cache
    recompiled = [n for n in new if n.startswith(out["step_module"] + "-")]
    check(out["cache_hits"] > 0,
          f"the resumed process read nothing from {cache_dir}")
    check(not recompiled,
          f"the resumed process recompiled the train step: new cache "
          f"entries {recompiled}")
    say("cifar_resume",
        f"compile cache {cache_dir}: cold compile {cold['compile_secs']}s "
        f"(leg cifar, {cold['cache_writes']} entries written), warm "
        f"{out['compile_secs']}s with {out['cache_hits']} entries read, "
        f"{len(new)} new entries, none for {out['step_module']!r}")
    return out


def leg_imagenet(device, cache_dir, extra) -> dict:
    """Leg 2 — the north-star model: RN50/224², 1001 classes, bf16, bs 128
    per chip (the BASELINE.md workload), fed by the real input pipeline
    from seeded TFRecords. NOT ``data.dataset=synthetic``: the model
    factory keys the architecture on the dataset, and ``synthetic`` builds
    the CIFAR-style network whatever the preset (models/resnet.py)."""
    run = os.path.join(OUT, "run_imagenet")
    shutil.rmtree(run, ignore_errors=True)
    return train_leg(
        "imagenet", ["--preset", "imagenet_resnet50"] + _sets({
            "data.data_dir": IMAGENET_DIR, "log_root": run,
            "train.batch_size": 128 * device["count"],
            "train.train_steps": IMAGENET_STEPS,
            "train.summary_every_steps": 1, "train.log_every_steps": 5}),
        1, IMAGENET_STEPS,
        {"fused_xent": "on", "coalesced_transfer": "on",
         "device_augment": "on"},
        min_custom_calls=2, cache_dir=cache_dir, extra=extra)


def leg_long_context(device, cache_dir, extra) -> dict:
    """Leg 3 — the flash kernels through auto: 4,096 tokens, remat on;
    forward, dQ and dK/dV kernels at the tuned 512×512 tiles."""
    run = os.path.join(OUT, "run_long_context")
    shutil.rmtree(run, ignore_errors=True)
    return train_leg(
        "long_context", ["--preset", "vit_long_context"] + _sets({
            "log_root": run, "train.batch_size": 8 * device["count"],
            "train.train_steps": LONG_CONTEXT_STEPS,
            "train.summary_every_steps": 1, "train.log_every_steps": 1}),
        1, LONG_CONTEXT_STEPS, {"attention": "flash", "fused_xent": "on"},
        # flash fwd + dQ + dK/dV, cross-entropy fwd + bwd
        min_custom_calls=5, cache_dir=cache_dir, extra=extra)


def _two_fused_steps(overrides: dict, devices, options: bool = True):
    """Build a tiny Trainer over ``mesh.data=OVERLAP_CHIPS`` and run ONE
    dispatch of two fused steps on seeded batches. ``options=False`` takes
    the exchange's compiler options off the step before it is built (the
    comparison; no config field does that). Returns (the trainer's
    resolutions, the second step's loss)."""
    import numpy as np
    from distributed_resnet_tensorflow_tpu.parallel.mesh import create_mesh
    from distributed_resnet_tensorflow_tpu.parallel.sharding import (
        shard_stacked_batch)
    from distributed_resnet_tensorflow_tpu.train.loop import Trainer
    from distributed_resnet_tensorflow_tpu.utils.config import get_preset
    cfg = get_preset("smoke")
    for key, value in {**overrides, "mesh.data": OVERLAP_CHIPS}.items():
        cfg.override(key, value)
    trainer = Trainer(cfg, mesh=create_mesh(cfg.mesh, devices=devices))
    if not options:
        trainer._step_compiler_options = None
    trainer.init_state(0)
    rng = np.random.RandomState(30)
    size, batch = cfg.data.image_size, cfg.train.batch_size
    stacked = {
        "images": rng.randn(2, batch, size, size, 3).astype(np.float32),
        "labels": rng.randint(0, cfg.model.num_classes,
                              (2, batch)).astype(np.int32)}
    _, metrics = trainer.jitted_multi_step()(
        trainer.state, shard_stacked_batch(stacked, trainer.mesh))
    return trainer.resolutions(), float(np.asarray(metrics["loss"]))


def leg_overlap(device, cache_dir, extra) -> dict:
    """Leg 5 — the gradient exchange of a one-host mesh. A tiny ViT and a
    tiny cross-replica-BN ResNet on ``mesh.data=4`` must build the step
    under the exchange's compiler options (parallel/overlap.
    exchange_compiler_options: one process, a mesh of data shards alone,
    a TPU backend); two fused steps must then give the loss of the same
    steps built without the options."""
    import jax
    from distributed_resnet_tensorflow_tpu.parallel.overlap import (
        EXCHANGE_COMPILER_OPTIONS)
    if device["count"] < OVERLAP_CHIPS:
        reason = (f"{device['count']} chip(s) present; the leg lays "
                  f"mesh.data={OVERLAP_CHIPS} over one host's chips")
        say("overlap", f"SKIPPED: {reason}")
        return {"skipped": reason}
    devices = jax.devices()[:OVERLAP_CHIPS]
    models = {
        "vit": {"model.name": "vit", "model.vit_dim": 128,
                "model.vit_depth": 2, "model.vit_heads": 4,
                "model.vit_patch_size": 4, "model.attention_impl": "dense",
                "train.batch_size": 32},
        "resnet_cross_replica_bn": {"model.resnet_size": 8,
                                    "model.cross_replica_bn": "true",
                                    "train.batch_size": 32},
    }
    out = {}
    for name, overrides in models.items():
        got, loss = _two_fused_steps(overrides, devices)
        built = got["step.compiler_options"]
        check(all(f"{k}={v}" in built
                  for k, v in EXCHANGE_COMPILER_OPTIONS.items()),
              f"{name}: the step was built under {built!r}")
        plain, loss_plain = _two_fused_steps(overrides, devices,
                                             options=False)
        check(plain["step.compiler_options"] == "none",
              f"{name}: the comparison was built under "
              f"{plain['step.compiler_options']!r}")
        # the options move when an all-reduce runs, not what it sums
        gap = abs(loss - loss_plain) / max(abs(loss_plain), 1e-6)
        check(gap < 1e-3,
              f"{name}: loss after two steps {loss!r} with the options, "
              f"{loss_plain!r} without them")
        say("overlap", f"{name}: step built under {built}; loss after 2 fused steps "
                       f"{loss:.6f}, {loss_plain:.6f} without the options "
                       f"(gap {gap:.1e})")
        out[name] = {"loss": loss, "loss_plain": loss_plain}
    return out


def leg_serve(device, cache_dir, extra) -> dict:
    """Leg 4 — ``main.py serve`` against leg 1's checkpoints with a short
    built-in open-loop load: every bucket AOT-compiled, nothing dropped,
    no compile at request time."""
    import contextlib
    import io

    from distributed_resnet_tensorflow_tpu import main as drt_main
    argv = ["serve", "--preset", "cifar10_resnet50"] + _sets({
        "data.data_dir": CIFAR_DIR, "log_root": CIFAR_RUN,
        "serve.max_batch": 32, "serve.load_qps": 40,
        "serve.load_duration_secs": 8}) + list(extra)
    say("serve", "main.py " + " ".join(argv))
    # main() prints the report as its last stdout line (logs go to stderr)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        drt_main.main(argv)
    sys.stdout.write(buf.getvalue())
    report = json.loads(buf.getvalue().strip().splitlines()[-1])
    last = CIFAR_STEPS + CIFAR_RESUME_STEPS
    check(report["serving_step"] == last,
          f"served step {report['serving_step']}, expected the committed "
          f"{last}")
    check(report["requests"] >= 200 and
          report["completed"] == report["requests"],
          f"requests {report['requests']} completed {report['completed']}")
    check(report["dropped"] == 0 and report["errors"] == 0,
          f"dropped {report['dropped']} errors {report['errors']}")
    check(report["compile"]["serve_time_compiles"] == 0,
          f"compiles at request time: {report['compile']}")
    used = sorted(int(key.split("_")[1])  # "bucket_<n>"
                  for key in report["latency_by_bucket_ms"])
    check(set(used) <= set(report["buckets"]),
          f"served buckets {used} outside the AOT set {report['buckets']}")
    say("serve", f"{report['completed']} of {report['requests']} requests "
                 f"answered from step {report['serving_step']}, dropped 0, "
                 f"buckets {report['buckets']} AOT-compiled in "
                 f"{report['compile']['warm_secs']}s, served from {used}, "
                 "serve_time_compiles 0")
    return {"report": report}


def leg_kernels(device, cache_dir, extra) -> dict:
    """Each compiled Pallas kernel against its jax.numpy reference on
    seeded inputs, forward and gradients.

    Tolerances. Cross-entropy runs in float32 on both sides: |Δ| <= 1e-3
    absolute on losses of order 1-10 and 1e-4 on gradients of order <= 1
    (TPU exp/log differ from XLA's in the last bits; a wrong kernel is off
    by O(1)). Flash attention takes bfloat16 q/k/v like the models do and
    is held against dense attention computed in float32 at the highest
    matmul precision: max|Δ| / max|reference| <= 2e-2 for the output and
    4e-2 for dQ/dK/dV — a few bfloat16 roundings (2^-8 each) of the P and
    dS tiles; a wrong mask or tile is off by O(1) on this scale."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from distributed_resnet_tensorflow_tpu.ops.attention import attention
    from distributed_resnet_tensorflow_tpu.ops.pallas import (
        flash_attention, softmax_xent)
    from distributed_resnet_tensorflow_tpu.train.loop import (
        per_example_cross_entropy)

    checked = []
    for b, c in ((128, 10), (128, 1000), (200, 1001)):
        rng = np.random.RandomState(c)
        logits = jnp.asarray(rng.randn(b, c).astype(np.float32) * 3.0)
        labels = jnp.asarray(rng.randint(0, c, (b,)).astype(np.int32))
        weights = jnp.asarray(rng.rand(b).astype(np.float32))

        def total(fn, x):
            per = fn(x, labels)
            return jnp.sum(per * weights), per

        (_, per_k), g_k = jax.jit(jax.value_and_grad(
            lambda x: total(softmax_xent, x), has_aux=True))(logits)
        (_, per_r), g_r = jax.jit(jax.value_and_grad(
            lambda x: total(per_example_cross_entropy, x),
            has_aux=True))(logits)
        d_loss = float(jnp.max(jnp.abs(per_k - per_r)))
        d_grad = float(jnp.max(jnp.abs(g_k - g_r)))
        check(d_loss <= 1e-3 and d_grad <= 1e-4,
              f"softmax_xent ({b},{c}) vs per_example_cross_entropy: "
              f"|Δloss| {d_loss:.2e} |Δgrad| {d_grad:.2e}")
        checked.append(f"xent({b},{c}) Δloss {d_loss:.1e} Δgrad "
                       f"{d_grad:.1e}")

    def rel(a, ref):
        a, ref = np.asarray(a, np.float32), np.asarray(ref, np.float32)
        return float(np.max(np.abs(a - ref)) / np.max(np.abs(ref)))

    for t, d, causal in ((4096, 64, False), (4096, 64, True),
                         (196, 64, False), (2048, 128, True)):
        rng = np.random.RandomState(t + d)
        q, k, v, w = (jnp.asarray(rng.randn(1, t, 2, d).astype(np.float32))
                      for _ in range(4))

        def flash_loss(q, k, v):
            out = flash_attention(q.astype(jnp.bfloat16),
                                  k.astype(jnp.bfloat16),
                                  v.astype(jnp.bfloat16), causal)
            return jnp.sum(out.astype(jnp.float32) * w), out

        def dense_loss(q, k, v):
            # the same bfloat16-rounded inputs, then float32 throughout
            q, k, v = (x.astype(jnp.bfloat16).astype(jnp.float32)
                       for x in (q, k, v))
            out = attention(q, k, v, causal)
            return jnp.sum(out * w), out

        (_, o_k), g_k = jax.jit(jax.value_and_grad(
            flash_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        with jax.default_matmul_precision("highest"):
            (_, o_r), g_r = jax.jit(jax.value_and_grad(
                dense_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        errs = [rel(o_k, o_r)] + [rel(a, b) for a, b in zip(g_k, g_r)]
        check(errs[0] <= 2e-2 and max(errs[1:]) <= 4e-2,
              f"flash_attention T={t} d={d} causal={causal} vs dense "
              f"attention: relative max error out/dq/dk/dv "
              f"{[f'{e:.2e}' for e in errs]}")
        checked.append(f"flash(T={t},d={d},causal={causal}) out "
                       f"{errs[0]:.1e} grads {max(errs[1:]):.1e}")
    for line in checked:
        say("kernels", "parity " + line)
    return {"checked": checked}


CHILD_LEGS = {"kernels": leg_kernels, "cifar": leg_cifar,
              "cifar_resume": leg_cifar_resume, "imagenet": leg_imagenet,
              "long_context": leg_long_context, "overlap": leg_overlap,
              "serve": leg_serve}


def child_main(leg: str, extra) -> int:
    from distributed_resnet_tensorflow_tpu.utils.compile_cache import (
        configure_compile_cache)
    cache_dir = configure_compile_cache()
    device = require_tpu(leg)
    os.makedirs(cache_dir, exist_ok=True)
    os.makedirs(OUT, exist_ok=True)
    result = CHILD_LEGS[leg](device, cache_dir, extra)
    result["device"] = device
    tmp = os.path.join(OUT, f"{leg}.json.tmp")
    with open(tmp, "w") as f:
        json.dump(result, f)
    os.replace(tmp, os.path.join(OUT, f"{leg}.json"))
    return 0


def main(argv) -> int:
    if not argv:
        return parent_main()
    if len(argv) >= 2 and argv[0] == "--leg" and argv[1] in CHILD_LEGS \
            and (len(argv) == 2 or argv[2] == "--"):
        return child_main(argv[1], argv[3:])
    print(__doc__.split("\n\n")[1], file=sys.stderr)
    print(f"legs: {', '.join(LEGS)}", file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
