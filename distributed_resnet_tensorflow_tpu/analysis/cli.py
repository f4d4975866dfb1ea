"""The ``check`` subcommand: lint + static elaboration in one gate.

    python -m distributed_resnet_tensorflow_tpu.main check --all-presets
    python -m distributed_resnet_tensorflow_tpu.main check --preset smoke
    python -m distributed_resnet_tensorflow_tpu.main check --lint-only

Exit code 0 = clean, 1 = findings (the exit-code contract's real-failure
code: a red gate must fail the submit). Designed to finish in well under
a minute on CPU — scripts/analysis_gate.sh runs it pre-submit
(scripts/submit_tpu_slurm.sh) and pre-merge (scripts/chaos_smoke.sh
--fast). docs/static_analysis.md is the manual.
"""
from __future__ import annotations

import argparse
import time
from typing import Optional, Sequence


def main_check(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="main.py check",
        description="shardcheck: invariant lint + static elaboration")
    scope = p.add_mutually_exclusive_group()
    scope.add_argument("--all-presets", action="store_true",
                       help="elaborate every preset (also the default)")
    scope.add_argument("--preset", action="append", default=[],
                       help="elaborate only this preset (repeatable)")
    depth = p.add_mutually_exclusive_group()
    depth.add_argument("--lint-only", action="store_true",
                       help="skip elaboration")
    depth.add_argument("--elaborate-only", action="store_true",
                       help="skip the linter")
    p.add_argument("--devices", type=int, default=8,
                   help="virtual CPU mesh size for elaboration (default 8)")
    p.add_argument("--no-zero1-sweep", action="store_true",
                   help="skip the 64/256-device ZeRO-1 big-mesh sweep "
                        "(elab-zero1)")
    p.add_argument("--no-hangcheck", action="store_true",
                   help="skip the hangcheck phases (ISSUE 13): the "
                        "collective-schedule extraction and the "
                        "thread/lock contract rules (cross-thread-"
                        "dispatch, untimed-blocking-call, chief-gated-"
                        "collective, lock-order-cycle)")
    p.add_argument("--no-plan-drift", action="store_true",
                   help="skip the plan-drift phase (ISSUE 17): the "
                        "what-if planner's predictions over the "
                        "committed schedules, the plan_catalog.json "
                        "refresh, and the bandwidth-catalog sanity "
                        "cross-check")
    p.add_argument("--no-protocol", action="store_true",
                   help="skip the protocol phase (ISSUE 20): the "
                        "exhaustive model check of the declared control-"
                        "plane protocols (elastic reshard barrier, "
                        "sharded-checkpoint commit, replica health/"
                        "replace ladder, canary swap pin), the "
                        "protocol_models.json refresh, and the "
                        "protocol-drift lint rule")
    p.add_argument("--root", default=None, help=argparse.SUPPRESS)
    # --root scopes the LINT pass to another tree (tests of the exit-code
    # contract run the real CLI over a known-bad fixture repo)
    p.add_argument("-v", "--verbose", action="store_true",
                   help="print finding detail (full tracebacks)")
    ns = p.parse_args(argv)

    findings = []
    t0 = time.perf_counter()
    if not ns.lint_only:
        # the virtual mesh must exist BEFORE the first jax backend use —
        # and the LINT pass is now a backend user too (unsharded-opt-state
        # resolves preset states via eval_shape), so the flags go down
        # before anything else runs. Sized for the big-mesh ZeRO-1 sweep
        # when it runs (virtual CPU devices are threads over one host
        # platform; 256 of them cost ~nothing at eval_shape-only load).
        from ..utils.virtual_devices import apply_virtual_cpu
        from .elaborate import ZERO1_SWEEP_SIZES
        n_virtual = ns.devices if ns.no_zero1_sweep \
            else max(ns.devices, max(ZERO1_SWEEP_SIZES))
        apply_virtual_cpu(n_virtual)
    if not ns.elaborate_only:
        from .lint import run_lint
        rule_names = None
        if ns.no_hangcheck or ns.no_protocol:
            from . import rules as rules_pkg
            off = set()
            if ns.no_hangcheck:
                off |= {m.RULE_NAME for m in rules_pkg.HANGCHECK_RULES}
            if ns.no_protocol:
                off |= {m.RULE_NAME for m in rules_pkg.PROTOCOL_RULES}
            rule_names = [m.RULE_NAME for m in rules_pkg.ALL_RULES
                          if m.RULE_NAME not in off]
        findings += run_lint(root=ns.root, rule_names=rule_names)
        print(f"lint: {len(findings)} finding(s) "
              f"[{time.perf_counter() - t0:.1f}s]")
    if not ns.lint_only:
        from .elaborate import run_elaborate
        t1 = time.perf_counter()
        presets = ns.preset or None  # None = all
        efs = run_elaborate(presets, n_devices=ns.devices)
        print(f"elaborate: {len(efs)} finding(s) "
              f"[{time.perf_counter() - t1:.1f}s]")
        findings += efs
        if not ns.no_zero1_sweep:
            from .elaborate import run_elaborate_zero1
            t2 = time.perf_counter()
            zfs = run_elaborate_zero1(presets)
            print(f"elab-zero1 (64/256-device sweep): {len(zfs)} "
                  f"finding(s) [{time.perf_counter() - t2:.1f}s]")
            findings += zfs
        if not ns.no_hangcheck:
            # hangcheck-schedule (docs/static_analysis.md): collective
            # schedules extracted from the traced jaxprs, determinism
            # check, reviewable artifact.
            from .collectives import run_collectives, write_artifact
            t3 = time.perf_counter()
            cfs, sigs = run_collectives(presets, n_devices=ns.devices)
            print(f"hangcheck-schedule: {len(cfs)} finding(s), "
                  f"{len(sigs)} signature(s) "
                  f"[{time.perf_counter() - t3:.1f}s]")
            findings += cfs
            if presets is None and ns.root is None and ns.devices == 8:
                # full sweeps at the canonical 8-device mesh refresh the
                # committed artifact — a partial run must not shrink it,
                # and a --devices override changes layouts/payload bytes
                # (the artifact diff must only ever mean a comm change)
                path = write_artifact(sigs)
                print(f"hangcheck-schedule: wrote {path}")
        if not ns.no_plan_drift:
            # plan-drift (docs/planner.md): the what-if planner re-costed
            # over the committed collective schedules with the reference
            # constants, plus the measured bandwidth-catalog cross-check
            # against a live micro-probe — a comm/perf regression becomes
            # a reviewable plan_catalog.json diff, a corrupted bandwidth
            # table a red gate
            from .plan_drift import run_plan_drift, write_plan_catalog
            t4 = time.perf_counter()
            sigs_for_plan = None
            if not ns.no_hangcheck and presets is None:
                # full sweeps cost the freshly traced map; a scoped run
                # (--preset X) only traced X's schedules, so costing the
                # planned presets against it would flag every other one
                # as missing — fall back to the committed artifact
                sigs_for_plan = sigs
            pfs, plan_doc = run_plan_drift(sigs_for_plan,
                                           n_devices=ns.devices)
            print(f"plan-drift: {len(pfs)} finding(s), "
                  f"{len(plan_doc.get('plans', {}))} preset plan(s) "
                  f"[{time.perf_counter() - t4:.1f}s]")
            findings += pfs
            if presets is None and ns.root is None and ns.devices == 8:
                # same refresh guard as the schedule artifact above: the
                # plan catalog must only ever diff on a real model /
                # schedule change, never on a partial or resized run
                path = write_plan_catalog(plan_doc)
                print(f"plan-drift: wrote {path}")
        if not ns.no_protocol:
            # protocol (docs/static_analysis.md): BFS over every
            # interleaving of the four declared control-plane protocols
            # at their small-scope bounds — safety counterexamples and
            # liveness traps as findings, model inventory as the
            # committed protocol_models.json artifact
            from .protocol import run_protocol, write_artifact as write_pm
            t5 = time.perf_counter()
            prfs, pm_doc = run_protocol()
            print(f"protocol: {len(prfs)} finding(s), "
                  f"{len(pm_doc.get('specs', {}))} protocol(s) "
                  f"[{time.perf_counter() - t5:.1f}s]")
            findings += prfs
            if ns.root is None:
                # the models live in THIS package's sources, not the
                # --root tree under lint — a fixture-tree run must not
                # rewrite the committed inventory
                path = write_pm(pm_doc)
                print(f"protocol: wrote {path}")

    from .report import format_findings
    print(format_findings(findings, verbose=ns.verbose))
    print(f"shardcheck total: {time.perf_counter() - t0:.1f}s")
    return 1 if findings else 0
