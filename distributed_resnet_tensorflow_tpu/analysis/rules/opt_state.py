"""unsharded-opt-state: a ZeRO-1 preset must actually shard something.

``optimizer.zero1=on`` promises the operator that per-replica optimizer
memory shrinks by ~(N-1)/N. The rule table (parallel/sharding.zero1_rules)
keeps that promise only when the model's optimizer-state leaves have a
dim the ``data`` axis divides — a preset whose shapes defeat every rule
(all leaves below ``zero1_min_size``, or no divisible dim on the
canonical dp layout) trains with the FULL replicated state while the
config claims otherwise: silent replicated memory, the exact failure
mode the Trainer's dead-axis checks exist to prevent, except this one
only shows up as an OOM at scale.

This rule RESOLVES each registered preset that sets ``optimizer.zero1``
to ``"on"`` (the static promise; ``auto`` presets make no unconditional
claim) against the canonical 8-way dp layout via the real rule table and
abstract state init — zero devices, zero compute — and flags the preset
FACTORY (file:line in utils/config.py) when the resolution leaves every
optimizer-state leaf replicated.
"""
from __future__ import annotations

import inspect
import os
from typing import Iterable

from ..report import Finding

RULE_NAME = "unsharded-opt-state"
DOC = __doc__

#: canonical layout the promise is checked against — the smallest mesh
#: every dp preset must scale to
CANONICAL_DATA_SHARDS = 8


def _zero1_resolves_sharded(cfg) -> bool:
    """True when at least one optimizer-state leaf shards over ``data``
    on the canonical dp layout. Pure shape/spec work (eval_shape + the
    rule table with a sizes-only mesh stand-in) — no devices needed."""
    from ...models import create_model
    from ...parallel.sharding import (ZERO1_MIN_SIZE, Zero1Report,
                                      _SizesMesh, match_partition_rules,
                                      zero1_rules)
    from ...train.optimizers import create_optimizer
    from ...train.schedules import create_schedule
    from ...train.state import abstract_train_state, init_input

    model = create_model(cfg.model, cfg.data.dataset)
    tx = create_optimizer(cfg.optimizer, create_schedule(cfg.optimizer))
    state = abstract_train_state(model, tx, init_input(model, cfg, 1))
    report = Zero1Report(CANONICAL_DATA_SHARDS)
    match_partition_rules(
        zero1_rules(_SizesMesh({"data": CANONICAL_DATA_SHARDS}),
                    min_size=cfg.optimizer.zero1_min_size
                    or ZERO1_MIN_SIZE,
                    report=report),
        state.opt_state)
    return report.sharded_leaves > 0


def check(ctx) -> Iterable[Finding]:
    from ...utils.config import PRESETS
    for name, factory in sorted(PRESETS.items()):
        try:
            cfg = factory()
        except Exception:
            continue  # a broken preset is someone else's finding
        if cfg.optimizer.zero1 != "on":
            continue
        try:
            if _zero1_resolves_sharded(cfg):
                continue
        except Exception as e:
            detail = f"{type(e).__name__}: {e}"
            yield _finding(ctx, name, factory,
                           f"preset {name!r}: optimizer.zero1=on but the "
                           f"resolution itself failed ({detail[:200]})")
            continue
        yield _finding(
            ctx, name, factory,
            f"preset {name!r} sets optimizer.zero1=on but the rule table "
            f"resolves EVERY optimizer-state leaf replicated on the "
            f"{CANONICAL_DATA_SHARDS}-way dp layout — the config promises "
            "a (N-1)/N per-replica memory cut it cannot deliver; pick "
            "shapes a data axis divides or drop the knob")


def _finding(ctx, name: str, factory, message: str) -> Finding:
    """Anchor the finding at the preset factory's def line, repo-relative
    when the factory lives under the linted root."""
    try:
        path = inspect.getsourcefile(factory) or ""
        line = inspect.getsourcelines(factory)[1]
    except (OSError, TypeError):
        path, line = "", 0
    rel = os.path.relpath(path, ctx.root) if path else \
        "distributed_resnet_tensorflow_tpu/utils/config.py"
    if rel.startswith(".."):
        rel = path
    return Finding(RULE_NAME, rel, line, message)
