"""bare-assert: runtime invariants in package code must not be ``assert``.

``python -O`` strips assert statements, so an invariant guarded by one
silently vanishes in optimized deployments — PR 1 converted the imagenet
drain invariant to a RuntimeError for exactly this reason. This rule flags
every ``assert`` in package (non-test) code; tests are free to assert
(that is what they are for), and the rare intentional debug-only assert
can carry ``# shardcheck: ok(bare-assert)``.
"""
from __future__ import annotations

import ast
from typing import Iterable

from ..report import Finding

RULE_NAME = "bare-assert"
DOC = __doc__


def check(ctx) -> Iterable[Finding]:
    # package files only: tests/ are not scanned by the driver, and
    # repo-top driver glue (__graft_entry__.py, chip_smoke.py) asserts on its
    # own argv contracts, which die loudly either way
    for sf in ctx.package_py:
        if sf.tree is None:
            continue
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Assert):
                yield Finding(
                    RULE_NAME, sf.rel, node.lineno,
                    "bare assert guards a runtime invariant — it vanishes "
                    "under python -O; raise RuntimeError/ValueError instead")
