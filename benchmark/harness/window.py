"""One run of one cell: set-up, the measured window, the fence, the
comparison. Everything timed goes through ``Trainer.train``, the loop
``main.py train`` runs, with its hooks at the preset's cadences."""
from __future__ import annotations

import glob
import os
import shutil
import sys
import time
from typing import Dict, List

import jax
import numpy as np

from ..reference import follow
from . import check, device, spec, trace as trace_mod
from .program import Program, place_compile_cache

TRACE_DIR = os.path.join(spec.ROOT, ".bench_trace")


def log(msg: str) -> None:
    print(f"[benchmark] {msg}", file=sys.stderr, flush=True)


class CompileWatch:
    """Seconds JAX spent compiling or fetching compiled programs, by phase,
    through ``jax.monitoring`` (as ``chip_smoke.CompileWatch`` reads them)."""

    def __init__(self):
        import jax.monitoring as mon
        self.phase = "setup"
        self.seconds: Dict[str, float] = {}
        self.events: Dict[str, int] = {}
        self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds[self.phase] = self.seconds.get(self.phase, 0.0) + secs
            self.events[self.phase] = self.events.get(self.phase, 0) + 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Recorder:
    """Reads the program at the comparison's boundaries (set-up only)."""

    def __init__(self, program: Program, bounds: List[int]):
        self.program, self.bounds = program, bounds
        self.loss: Dict[int, object] = {}
        self.moment = self.change = None

    def __call__(self, step: int, state, metrics) -> None:
        if step in self.bounds:
            self.loss[step] = metrics["loss"]
        if step == self.bounds[0]:
            self.moment = self.program.read_moment(state.opt_state)
        if step == self.bounds[-1]:
            self.change = self.program.read_change(state.params)

    def readings(self) -> dict:
        pull = lambda tree: jax.tree_util.tree_map(lambda v: float(np.asarray(v)), tree)  # noqa: E731
        return {"loss": pull(self.loss), "moment": pull(self.moment),
                "change": pull(self.change)}


class Counter:
    """Counts dispatches and steps, keeps every dispatch's loss on the
    device (a scalar each), and drives the profiler in a traced run."""

    def __init__(self, k: int, first_step: int, trace_after_s: float = 0.0,
                 trace_dispatches: int = 0):
        self.k, self.first = k, first_step
        self.steps = self.dispatches = 0
        self.losses: List[object] = []
        self.t_start = None
        self.trace_after_s, self.trace_dispatches = trace_after_s, trace_dispatches
        self.tracing = False
        self.traced = 0
        self.trace_done = trace_dispatches == 0

    def __call__(self, step: int, state, metrics) -> None:
        self.steps = step - self.first
        self.dispatches += 1
        self.losses.append(metrics["loss"])
        if self.trace_done:
            return
        now = time.perf_counter()
        if not self.tracing and now - self.t_start >= self.trace_after_s:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
            self.tracing = True
        elif self.tracing:
            self.traced += 1
            if self.traced >= self.trace_dispatches:
                self.stop_trace(state)

    def stop_trace(self, state) -> None:
        if self.tracing:
            jax.block_until_ready(state.step)  # the device catches up first
            jax.profiler.stop_trace()
            self.tracing, self.trace_done = False, True


def layer_metrics(cell: spec.Cell, run: dict) -> Dict[str, float]:
    """Every per-layer metric of the cell whose reader finds something to
    read. A share that reads over 100 stops the run: the operations are
    counted too high or the time leaves out part of the work."""
    units = {m["name"]: m["unit"] for m in cell.per_layer}
    out = {}
    for name, read in cell.readers().items():
        value = read(run)
        if value is None:
            continue
        if units[name] == "%" and value > 100.0:
            raise RuntimeError(f"{name} reads {value:.2f}%: a share over 100 is a "
                               "fault of the count or of the time, not a result")
        out[name] = value
    return out


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             devices, peaks: dict, t_process: float) -> dict:
    """The whole of one run; returns the result line as a dict."""
    watch = CompileWatch()
    place_compile_cache()
    t_imports = time.time() - t_process
    program = Program(cell, seed, devices)
    t_built = time.time() - t_process
    log(f"resolved: {program.resolutions()}")
    k, trainer = program.k, program.trainer
    bounds = check.boundaries(k)
    warm_steps = bounds[-1]

    # set-up drives the object the window will drive through its first
    # steps: this compiles (or fetches) every program the window uses, and
    # leaves the readings the comparison needs
    recorder = Recorder(program, bounds)
    trainer.train(program.data_iter, num_steps=warm_steps,
                  hooks=tuple(program.hooks()) + (recorder,))
    jax.block_until_ready(trainer.state)
    mine = recorder.readings()
    setup_compile_s = watch.seconds.get("setup", 0.0)

    # the measured window
    counter = Counter(k, warm_steps,
                      trace_after_s=min(2.0, seconds / 4) if traced else 0.0,
                      trace_dispatches=cell.traffic["trace_dispatches"] if traced else 0)
    hooks = tuple(program.hooks()) + (counter,)
    stages_before = program.stage_counters()
    watch.phase = "window"
    failed = 0
    t0 = counter.t_start = time.perf_counter()
    setup_s = time.time() - t_process
    deadline = t0 + seconds
    try:
        trainer.train(program.data_iter, num_steps=10 ** 9, hooks=hooks,
                      start_step=warm_steps,
                      stop_fn=lambda: time.perf_counter() >= deadline)
    except program.NanLossError as e:  # NanGuardHook met a loss that is not finite
        log(f"window stopped: {e}")
        failed = 1
    # the fence: the state is ready, then one scalar crosses to the host
    jax.block_until_ready(trainer.state)
    int(np.asarray(trainer.state.step))
    t1 = time.perf_counter()
    counter.stop_trace(trainer.state)
    watch.phase = "after"
    stages_after = program.stage_counters()
    wall = t1 - t0
    if watch.events.get("window", 0):
        raise RuntimeError(f"{watch.events['window']} compilation(s) inside the measured "
                           f"window ({watch.seconds['window']:.2f} s): a shape was not warmed up")
    losses = np.asarray(jax.device_get(counter.losses), np.float64) \
        if counter.losses else np.zeros((0,))
    failed += int(np.sum(~np.isfinite(losses))) * k
    attempted = counter.steps
    dev = device.describe(devices)
    flops = spec.module("flops", cell.config["family"])
    if hasattr(flops, "train_flops_per_step"):  # the count needs the traffic (a sequence length)
        flops_per_step = flops.train_flops_per_step(cell.config, cell.traffic)
    else:
        flops_per_step = flops.train_flops_per_example(cell.config["model"]) * program.global_batch
    run = {
        "chips": len(devices), "peaks": peaks, "compile_s": setup_compile_s,
        "stages_before": stages_before, "stages_after": stages_after, "trace": None,
        "flops_per_step": flops_per_step,
        # what a reader in a new file needs to count a kernel's operations
        "config": cell.config, "traffic": cell.traffic,
        "global_batch": program.global_batch, "steps_per_dispatch": k,
    }
    metrics = {"examples_per_s": attempted * program.global_batch / wall,
               "peak_hbm_gib": dev["memory_peak_bytes"] / 2 ** 30,
               "setup_s": setup_s}
    log(f"window: {attempted} steps in {wall:.3f} s, set-up {setup_s:.2f} s = "
        f"{t_imports:.1f} to the harness + {t_built - t_imports:.1f} trainer, state, "
        f"weights, stream + {setup_s - t_built:.1f} first steps (compile or fetch "
        f"{setup_compile_s:.2f} s in all, cache hits {watch.hits} misses {watch.misses})")

    # the state goes before the reference comes
    augment_seed = program.cfg.train.seed
    batches = [program.stream.batch(i) for i in range(warm_steps)]
    program.close()
    del trainer, recorder, counter, hooks

    breakdown = None
    if traced:
        files = glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb"))
        if not files:
            raise RuntimeError("the traced run left no .xplane.pb")
        reduced = trace_mod.reduce(trace_mod.load(files[0]), steps_per_dispatch=k)
        keep = os.environ.get("BENCH_KEEP_TRACE")
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(files[0], os.path.join(keep, f"{cell.name}.xplane.pb"))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        run["trace"] = reduced
        dev["busy_s"], dev["window_s"] = reduced["busy_s"], reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
        metrics = layer_metrics(cell, run)

    t_ref = time.perf_counter()
    ref = follow.follow(cell.config, seed, batches, bounds, augment_seed=augment_seed)
    numbers, where = check.compare(mine, ref)
    ok, rows = check.verdict(numbers, cell.limits)
    walk = ref["walk"]
    gb = lambda b: "not stated" if b is None else f"{b / 1e9:.2f} GB"  # noqa: E731
    log(f"reference followed {warm_steps} steps in {time.perf_counter() - t_ref:.1f} s; "
        f"device peak {gb(walk['device_peak_bytes_before'])} before the walk, "
        f"{gb(walk['device_peak_bytes'])} after; the moments waited on the "
        f"{walk['moments']}, {walk['groups']} group(s) of leaves")
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    result = {
        "correct": bool(ok and failed == 0), "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {n: {"value": v, "unit": units[n]} for n, v in metrics.items()},
        "device": dev,
    }
    if breakdown:
        result["breakdown"] = breakdown
    result["check"] = {name: {"value": value, "limit": limit,
                              **({"leaf": where[name]} if name in where else {})}
                       for name, value, limit in rows}
    return result
