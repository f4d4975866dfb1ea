"""Multi-process launcher/supervisor — successor of the reference's launcher
tree.

The reference bootstrapped clusters with ~440 lines of bash deriving ps/worker
host:port lists from SLURM and synthesizing per-node scripts
(reference scripts/run_dist_tf_daint.sh:30-206, SURVEY.md §2.18). In the SPMD
world a launcher only needs to start N identical processes with
(coordinator, process_id) — everything else is the same program.

Since the watchdog PR this is a real SUPERVISOR, not a serial waiter: it
polls all children, and when any child exits BADLY (nonzero other than the
resumable 75, or by signal) while siblings are still running it gives the
survivors ``child_grace_secs`` to finish on their own (the in-process
watchdog, resilience/watchdog.py, normally gets them out with exit 75 well
within that), then escalates SIGTERM → SIGKILL so one dead worker can
never wedge the whole allocation until the wall clock. A CLEAN or
RESUMABLE first exit (0 or 75) arms only a much longer backstop grace —
siblings legitimately finish or drain their preemption checkpoint at
different speeds, and killing them would tear the very save the grace
exists to protect.

Exit-code aggregation (docs/resilience.md):
  * any child's real failure (positive code other than 75) wins — a broken
    job must never be masked as "preempted" and requeued forever;
  * otherwise 75 if any child exited resumable OR died by signal (host
    loss / OOM-kill — the requeue-and-resume shape) OR had to be torn down
    by the supervisor;
  * 0 only when every child finished cleanly.

This is a CPU REHEARSAL tool, not a way to use the chips of a host: every
child is forced onto the CPU platform (``virtual_cpu_env``), whatever the
host holds. On a host with TPU chips ONE process drives all of them — run
``main.py`` once and let ``mesh.*`` lay the model out over
``jax.devices()``; a chip belongs to one process at a time.

Modes:
  * ``--num_processes N`` local fan-out — the successor of the reference's
    1ps+2wk localhost smoke cluster (reference scripts/submit_mac_dist.sh,
    run_dist_tf_local.sh: bs=10, 100 steps on CPU). Each child gets a fake
    single-CPU-device platform unless --devices_per_process says otherwise.
  * under SLURM, don't use this at all: ``srun python -m
    distributed_resnet_tensorflow_tpu.main …`` — parallel/distributed.py
    reads SLURM_NTASKS/SLURM_PROCID/nodelist itself (scripts/submit_tpu_slurm.sh).
  * on Cloud TPU pods, run main.py on every TPU VM worker;
    jax.distributed.initialize autodetects the pod topology (no args needed).

Usage:
    python -m distributed_resnet_tensorflow_tpu.launch --num_processes 2 -- \
        --preset smoke --set train.train_steps=20
"""
from __future__ import annotations

import argparse
import logging
import os
import signal
import subprocess
import sys
import time
from typing import List, Optional

from distributed_resnet_tensorflow_tpu.resilience.preemption import (
    INTERRUPT_EXIT_CODE, RESUMABLE_EXIT_CODE)

log = logging.getLogger(__name__)

#: once any child has exited BADLY (non-resumable nonzero / signal), how
#: long the siblings get before SIGTERM
DEFAULT_CHILD_GRACE_SECS = 30.0
#: after SIGTERM, how long before SIGKILL
TERM_TO_KILL_SECS = 10.0
#: grace multiplier/floor when the first exit was CLEAN (code 0): a slower
#: sibling draining a long final checkpoint is the normal end of a healthy
#: run, not a failure — tearing it down would turn success into a requeue.
#: A sibling that instead wedges after a clean exit is covered by its own
#: in-process watchdog (hang detection → exit 75), so this long stop is a
#: backstop, not the primary detector.
CLEAN_EXIT_GRACE_FLOOR_SECS = 300.0
CLEAN_EXIT_GRACE_SCALE = 10.0


def _spawn_one(pid: int, num_processes: int, main_args: List[str],
               devices_per_process: int, port: int,
               rejoin: bool = False) -> subprocess.Popen:
    from distributed_resnet_tensorflow_tpu.utils.virtual_devices import (
        virtual_cpu_env)
    env = virtual_cpu_env(devices_per_process)
    if rejoin:
        # a replacement worker must not re-arm the fault that killed its
        # predecessor, and enters through the elastic join barrier
        # (resilience/elastic.py) instead of the dead generation's
        # coordinator — main.py keys off DRT_ELASTIC_REJOIN
        for key in [k for k in env if k.startswith("DRT_FAULT_")]:
            env.pop(key)
        env["DRT_ELASTIC_REJOIN"] = "1"
    cmd = [sys.executable, "-m", "distributed_resnet_tensorflow_tpu.main",
           *main_args,
           "--set", f"mesh.coordinator_address=127.0.0.1:{port}",
           "--set", f"mesh.num_processes={num_processes}",
           "--set", f"mesh.process_id={pid}"]
    # chief inherits stdout/stderr; others keep their own log files —
    # per-process logs like the reference's worker.$JOBID.$host.log
    # (reference run_dist_train_eval_daint.sh:161,188)
    if pid == 0:
        out = None
    else:
        os.makedirs("/tmp/drt_launch", exist_ok=True)
        out = open(f"/tmp/drt_launch/proc{pid}.log",
                   "a" if rejoin else "w")
    return subprocess.Popen(cmd, env=env, stdout=out, stderr=out)


def _spawn(num_processes: int, main_args: List[str],
           devices_per_process: int, port: int) -> List[subprocess.Popen]:
    from distributed_resnet_tensorflow_tpu.utils.virtual_devices import (
        existing_device_count)

    if not devices_per_process:
        devices_per_process = existing_device_count(
            os.environ.get("XLA_FLAGS", "")) or 1
    return [_spawn_one(pid, num_processes, main_args, devices_per_process,
                       port)
            for pid in range(num_processes)]


def _signal_all(procs: List[subprocess.Popen], sig: int,
                skip_done: bool = True) -> None:
    for p in procs:
        if skip_done and p.poll() is not None:
            continue
        try:
            p.send_signal(sig)
        except ProcessLookupError:
            pass


def terminate_child(proc: subprocess.Popen,
                    grace_secs: float = TERM_TO_KILL_SECS,
                    kill_after: float = TERM_TO_KILL_SECS) -> int:
    """Escalation ladder for ONE child: SIGTERM → wait ``grace_secs`` →
    SIGKILL → wait ``kill_after`` → reap. Returns the exit code (negative
    = signal death). Shared by the serving fleet supervisor
    (serve/fleet.py replica replace) so every child teardown in the tree
    follows the same term-then-kill contract as the training launcher."""
    if proc.poll() is None:
        try:
            proc.terminate()
        except ProcessLookupError:
            pass
        try:
            proc.wait(timeout=grace_secs)
        except subprocess.TimeoutExpired:
            try:
                proc.kill()
            except ProcessLookupError:
                pass
            try:
                proc.wait(timeout=kill_after)
            except subprocess.TimeoutExpired:  # pragma: no cover
                pass
    return proc.returncode if proc.returncode is not None else -signal.SIGKILL


def _aggregate_rc(codes: List[int], forced: set) -> int:
    """Exit-code policy (module docstring): real failure > resumable > 0.
    Signal deaths (negative codes) of children the supervisor did NOT kill
    are host-loss-shaped → resumable. Children the supervisor tore down
    usually carry no information beyond "the run needed teardown" (signal
    death or the graceful 75) — EXCEPT a positive, non-resumable code: a
    forced child that still exited with its own failure code crashed for
    real (racing the teardown), and masking that as 75 would requeue a
    deterministically-broken job until MAX_REQUEUES."""
    rc = 0
    tore_down = False
    for i, code in enumerate(codes):
        if i in forced:
            tore_down = tore_down or code != 0
            if code <= 0 or code == RESUMABLE_EXIT_CODE:
                continue
            # fall through: the child's own real failure still wins
        if code == 0:
            continue
        if code < 0 or code == RESUMABLE_EXIT_CODE:
            if rc == 0:
                rc = RESUMABLE_EXIT_CODE
        else:
            rc = code  # real failure: wins over resumable, first one kept
            break
    if rc == 0 and tore_down:
        # everyone we left alone succeeded but some children had to be
        # killed — the run did not complete; requeue-shaped
        rc = RESUMABLE_EXIT_CODE
    return rc


def launch_local(num_processes: int, main_args: List[str],
                 devices_per_process: int = 0, port: int = 8476,
                 child_grace_secs: float = DEFAULT_CHILD_GRACE_SECS,
                 poll_secs: float = 0.2,
                 procs_out: Optional[list] = None,
                 elastic: bool = False,
                 max_respawns: int = 2,
                 respawn_delay_secs: float = 2.0) -> int:
    """Spawn N copies of main.py on localhost over the loopback coordinator
    and supervise them to completion (see module docstring for the exit-code
    aggregation). ``devices_per_process=0`` (default) honors a device count
    the user already exported via XLA_FLAGS, falling back to 1.

    ``procs_out``: optional list the spawned Popen objects are appended to —
    the fault-injection tests need the children's pids to kill one
    (tests/test_resilience.py kill-and-detect).

    ``elastic``: respawn a child that died respawnable (signal death or
    exit 75) into its ORIGINAL slot with ``DRT_ELASTIC_REJOIN`` set, up to
    ``max_respawns`` times per slot — the replacement joins the live
    fleet's elastic barrier and the mesh grows back
    (resilience/elastic.py). Requires ``resilience.elastic.enabled=on`` in
    ``main_args``; respawnable deaths do NOT arm the bad-exit teardown
    countdown in this mode (the survivors are busy resharding, not
    wedged). A slot's FINAL incarnation decides its exit code."""
    from distributed_resnet_tensorflow_tpu.utils.virtual_devices import (
        existing_device_count)
    if not devices_per_process:
        devices_per_process = existing_device_count(
            os.environ.get("XLA_FLAGS", "")) or 1
    procs = _spawn(num_processes, main_args, devices_per_process, port)
    if procs_out is not None:
        procs_out.extend(procs)

    # forward SIGTERM (SLURM grace-period kill, kill.sh) to every child so
    # each commits its preemption checkpoint and exits resumable; the
    # supervisor then reports the children's own exit code
    def forward_term(signum, frame):
        _signal_all(procs, signal.SIGTERM)

    try:
        prev_term = signal.signal(signal.SIGTERM, forward_term)
    except ValueError:  # not the main thread (embedded use) — no forwarding
        prev_term = None

    clean_grace_secs = max(CLEAN_EXIT_GRACE_SCALE * child_grace_secs,
                           CLEAN_EXIT_GRACE_FLOOR_SECS)
    forced: set = set()
    first_exit_at: Optional[float] = None
    first_bad_exit_at: Optional[float] = None
    termed_at: Optional[float] = None
    respawns = [0] * num_processes
    pending_respawn: dict = {}  # slot -> monotonic due time
    try:
        while True:
            codes = [p.poll() for p in procs]
            now = time.monotonic()
            if elastic and termed_at is None:
                any_clean = any(c == 0 for c in codes)
                for i, c in enumerate(codes):
                    if c is None or i in pending_respawn or i in forced:
                        continue
                    if any_clean:
                        continue  # the run is finishing — no new workers
                    if (c < 0 or c == RESUMABLE_EXIT_CODE) and \
                            respawns[i] < max_respawns:
                        respawns[i] += 1
                        pending_respawn[i] = now + respawn_delay_secs
                        log.warning(
                            "elastic: child %d died respawnable (code %d); "
                            "respawning as a rejoiner in %.0fs "
                            "(attempt %d/%d)", i, c, respawn_delay_secs,
                            respawns[i], max_respawns)
                for i, due in list(pending_respawn.items()):
                    if now >= due:
                        procs[i] = _spawn_one(
                            i, num_processes, main_args,
                            devices_per_process, port, rejoin=True)
                        if procs_out is not None:
                            procs_out.append(procs[i])
                        del pending_respawn[i]
                # slots awaiting (or fresh from) respawn are not exits for
                # the teardown timers; with everyone live again the
                # countdown state resets — the fleet recovered
                codes = [None if i in pending_respawn else p.poll()
                         for i, p in enumerate(procs)]
                if all(c is None for c in codes):
                    first_exit_at = None
                    first_bad_exit_at = None
            live = [i for i, c in enumerate(codes) if c is None]
            if not live:
                break
            if first_exit_at is None and any(c is not None for c in codes):
                first_exit_at = now
            # a deliberate resumable exit (75) is not a failure: during a
            # fleet-wide preemption children exit 75 at different speeds,
            # and the short countdown would SIGKILL a slow sibling mid-
            # preemption-checkpoint — the very save the grace protects
            if first_bad_exit_at is None and \
                    any(c is not None and c != 0 and
                        c != RESUMABLE_EXIT_CODE for c in codes):
                first_bad_exit_at = now
                exited = {i: c for i, c in enumerate(codes) if c is not None}
                log.warning(
                    "child exit(s) %s with %d sibling(s) still running; "
                    "giving them %.0fs before teardown", exited,
                    len(live), child_grace_secs)
            # the short countdown arms only on a BAD exit (nonzero
            # non-resumable, or signal death); after clean/resumable-only
            # exits the survivors get clean_grace_secs (finishing at
            # different speeds is a healthy run's normal shape)
            if first_bad_exit_at is not None:
                teardown_due = now - first_bad_exit_at >= child_grace_secs
            else:
                teardown_due = first_exit_at is not None and \
                    now - first_exit_at >= clean_grace_secs
            if teardown_due and termed_at is None:
                log.warning("teardown: SIGTERM to %d straggling child(ren) "
                            "%.0fs after the first exit", len(live),
                            now - first_exit_at)
                forced.update(live)
                _signal_all(procs, signal.SIGTERM)
                termed_at = now
            if termed_at is not None and now - termed_at >= TERM_TO_KILL_SECS:
                log.error("teardown: SIGKILL to %d child(ren) that ignored "
                          "SIGTERM", len(live))
                forced.update(live)
                _signal_all(procs, signal.SIGKILL)
                termed_at = now  # keep kicking every TERM_TO_KILL_SECS
            time.sleep(poll_secs)
        rc = _aggregate_rc([p.returncode for p in procs], forced)
    except KeyboardInterrupt:  # kill.sh parity (reference scripts/kill.sh)
        _signal_all(procs, signal.SIGTERM, skip_done=False)
        rc = INTERRUPT_EXIT_CODE
    finally:
        if prev_term is not None:
            signal.signal(signal.SIGTERM, prev_term)
        for p in procs:  # reap everything; no zombies left to SLURM
            try:
                p.wait(timeout=TERM_TO_KILL_SECS)
            except subprocess.TimeoutExpired:  # pragma: no cover
                p.kill()
                p.wait()
    if rc == RESUMABLE_EXIT_CODE:
        log.warning("children stopped resumable; exit code %d marks the run "
                    "resumable — relaunch with the same config to resume",
                    RESUMABLE_EXIT_CODE)
    return rc


def _apply_auto_layout(main_args: List[str], num_processes: int,
                       devices_per_process: int) -> List[str]:
    """--auto-layout: resolve the preset the children will run, ask the
    planner for the fastest predicted layout at this world size, and
    prepend the matching ``--set mesh.*`` overrides. Prepend, not
    append: config overrides apply in order, so a user's explicit
    ``--set mesh.*`` later in main_args still wins. Planner failures
    (no committed schedules for the preset, import error on an exotic
    install) log and fall through to the preset's own mesh — the
    launcher must never refuse to launch over an advisory."""
    preset = "cifar10_resnet50"  # utils.config.parse_args default
    for i, a in enumerate(main_args):
        if a == "--preset" and i + 1 < len(main_args):
            preset = main_args[i + 1]
        elif a.startswith("--preset="):
            preset = a.split("=", 1)[1]
    n_devices = num_processes * devices_per_process
    try:
        from .telemetry.planner import recommend_layout
        rec = recommend_layout(preset, n_devices=n_devices)
    except Exception as e:  # advisory only — never block the launch
        log.warning("--auto-layout: planner failed (%s); launching "
                    "with the preset's own mesh", e)
        return main_args
    if rec is None:
        log.warning("--auto-layout: no committed schedules for preset "
                    "%r (run `main.py check` first); launching with "
                    "the preset's own mesh", preset)
        return main_args
    layout, mesh_cfg = rec
    overrides = []
    for axis in ("data", "fsdp", "tensor", "pipeline", "sequence",
                 "expert"):
        overrides += ["--set", f"mesh.{axis}={getattr(mesh_cfg, axis)}"]
    log.info("--auto-layout: planner recommends %s for %s @ %d "
             "device(s): %s", layout, preset, n_devices,
             " ".join(overrides[1::2]))
    return overrides + list(main_args)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="local multi-process SPMD launcher/supervisor")
    ap.add_argument("--num_processes", type=int, default=2)
    ap.add_argument("--devices_per_process", type=int, default=0,
                    help="0 = inherit XLA_FLAGS device count, else 1")
    ap.add_argument("--port", type=int, default=8476)
    ap.add_argument("--child_grace_secs", type=float,
                    default=DEFAULT_CHILD_GRACE_SECS,
                    help="seconds siblings get to exit on their own after "
                         "the first BAD (non-resumable nonzero / signal) "
                         "child exit, before SIGTERM/SIGKILL; clean/75 "
                         "exits arm a 10x/300s-floor backstop instead")
    ap.add_argument("--elastic", action="store_true",
                    help="respawn a child that died respawnable (signal "
                         "or exit 75) into its slot as an elastic "
                         "rejoiner (DRT_ELASTIC_REJOIN); pair with "
                         "--set resilience.elastic.enabled=on")
    ap.add_argument("--max_respawns", type=int, default=2,
                    help="per-slot respawn budget in --elastic mode")
    ap.add_argument("--respawn_delay_secs", type=float, default=2.0,
                    help="delay before an elastic respawn (lets the "
                         "survivors reach the join barrier first)")
    ap.add_argument("--auto-layout", action="store_true",
                    help="ask the what-if planner (telemetry/planner."
                         "recommend_layout, docs/planner.md) for the "
                         "fastest predicted mesh layout at this world "
                         "size and inject the matching --set mesh.* "
                         "overrides BEFORE the user's own args (an "
                         "explicit --set mesh.* still wins)")
    ap.add_argument("main_args", nargs=argparse.REMAINDER,
                    help="args after -- go to main.py")
    ns = ap.parse_args(argv)
    main_args = ns.main_args
    if main_args and main_args[0] == "--":
        main_args = main_args[1:]
    if ns.auto_layout:
        main_args = _apply_auto_layout(
            main_args, ns.num_processes, ns.devices_per_process or 1)
    sys.exit(launch_local(ns.num_processes, main_args,
                          ns.devices_per_process, ns.port,
                          child_grace_secs=ns.child_grace_secs,
                          elastic=ns.elastic,
                          max_respawns=ns.max_respawns,
                          respawn_delay_secs=ns.respawn_delay_secs))


if __name__ == "__main__":
    main()
