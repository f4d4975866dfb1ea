"""event-registry + span-catalog + scope-catalog + config-knob: names must
resolve.

Four drift checks against the project's declared registries:

  * every ``write_event("<name>", ...)`` literal in code and every
    ``{"event": "<name>"}`` mention in docs/scripts must be declared in
    ``utils.metrics.EVENT_SCHEMAS`` — the one source of truth for the
    metrics.jsonl event stream;
  * every ``span("<name>")`` literal passed to the flight-recorder tracer
    (and every ``span("<name>")`` mention in docs/scripts) must be
    declared in ``telemetry.tracer.SPAN_CATALOG`` — trace.json consumers
    and the goodput classifier key on these names, so an unregistered
    span is invisible drift exactly like an unregistered event;
  * every ``jax.named_scope("<name>")`` literal the package puts on device
    operations, as a call or as a decorator (and every
    ``named_scope("<name>")`` mention in docs/scripts), must be declared
    in ``telemetry.tracer.SCOPE_CATALOG`` — the benchmark's per-layer
    readers and ``benchmark/tools/step_parts.py`` find a trace's device
    time by these path components, so a renamed scope is a metric that
    silently reads nothing;
  * every ``--set a.b.c=`` knob referenced in code, scripts or docs must
    resolve against the ``utils.config.ExperimentConfig`` dataclasses —
    the knob a README advertises must actually exist (``cfg.override``
    raises at runtime, but docs and sbatch scripts never run under CI).

All catch the "renamed it in code, forgot the docs/launcher" class that
otherwise surfaces as a crashed job after a 20-minute queue wait.
"""
from __future__ import annotations

import ast
import dataclasses
import re
from typing import Iterable

from ..report import Finding

RULE_NAME = "registry-drift"
DOC = __doc__

# documentation placeholders, not real knobs ("--set k=v", "--set
# dotted.path=value" in usage strings)
_KNOB_PLACEHOLDERS = {"k", "key", "KEY", "a.b.c", "dotted.path", "x.y.z"}

# two reference shapes: a concrete override (requires the trailing "=" so
# usage prose like "--set expects KEY=VALUE" stays quiet) and a wildcard
# section reference ("--set resilience.watchdog.*", no "=" required)
_KNOB_RE = re.compile(
    r'--set[\s"=]+(?:([A-Za-z_][\w.]*\.\*)|([A-Za-z_][\w.]*)\s*=)')
_DOC_EVENT_RE = re.compile(r'"event"\s*:\s*"(\w+)"')
# span-name mentions in docs/scripts: span("input.wait") / ``span("x.y")``
_DOC_SPAN_RE = re.compile(r'span\(\s*"([\w.]+)"')
# scope mentions in docs/scripts: named_scope("rotary")
_DOC_SCOPE_RE = re.compile(r'named_scope\(\s*"([\w.]+)"')


def _event_names() -> set:
    from ...utils.metrics import EVENT_SCHEMAS
    return set(EVENT_SCHEMAS)


def _span_names() -> set:
    from ...telemetry.tracer import SPAN_CATALOG
    return set(SPAN_CATALOG)


def _scope_names() -> set:
    from ...telemetry.tracer import SCOPE_CATALOG
    return set(SCOPE_CATALOG)


def _knob_resolves(dotted: str) -> bool:
    from ...utils.config import ExperimentConfig
    cur = ExperimentConfig()
    for part in dotted.split("."):
        if part == "*":
            # wildcard tail ("resilience.watchdog.*") — the prefix must be
            # a config section (dataclass), not a leaf
            return dataclasses.is_dataclass(cur)
        if not dataclasses.is_dataclass(cur) or not hasattr(cur, part):
            return False
        cur = getattr(cur, part)
    return True


def _is_write_event(node: ast.Call) -> bool:
    fn = node.func
    return isinstance(fn, ast.Attribute) and \
        fn.attr in ("write_event", "_write_event")


def _is_span_call(node: ast.Call) -> bool:
    """``span("...")`` (the module-level convenience) or
    ``recorder.span("...")`` — the two spellings the tracer exports.
    Deliberately NOT any ``<obj>.span(...)``: an unrelated API named span
    (e.g. a regex match group helper) must not turn the gate red."""
    fn = node.func
    if isinstance(fn, ast.Name) and fn.id == "span":
        return True
    return isinstance(fn, ast.Attribute) and fn.attr == "span" and \
        isinstance(fn.value, ast.Name) and fn.value.id == "recorder"


def _is_named_scope(node: ast.Call) -> bool:
    """``jax.named_scope("...")`` or a bare ``named_scope("...")``, as a
    ``with`` item or as a decorator (a decorator is a Call node too)."""
    fn = node.func
    if isinstance(fn, ast.Name):
        return fn.id == "named_scope"
    return isinstance(fn, ast.Attribute) and fn.attr == "named_scope" and \
        isinstance(fn.value, ast.Name) and fn.value.id == "jax"


def check(ctx) -> Iterable[Finding]:
    events = _event_names()
    spans = _span_names()
    scopes = _scope_names()

    # (a) write_event + span + named_scope literals in python
    for sf in ctx.all_python():
        if sf.tree is None:
            continue
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            arg = node.args[0]
            if not (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)):
                continue
            if _is_write_event(node) and arg.value not in events:
                yield Finding(
                    RULE_NAME, sf.rel, node.lineno,
                    f"metrics event {arg.value!r} is not declared in "
                    "utils.metrics.EVENT_SCHEMAS — register it there "
                    "first")
            elif _is_span_call(node) and arg.value not in spans:
                yield Finding(
                    RULE_NAME, sf.rel, node.lineno,
                    f"tracer span {arg.value!r} is not declared in "
                    "telemetry.tracer.SPAN_CATALOG — register it there "
                    "first")
            elif _is_named_scope(node) and arg.value not in scopes:
                yield Finding(
                    RULE_NAME, sf.rel, node.lineno,
                    f"device scope {arg.value!r} is not declared in "
                    "telemetry.tracer.SCOPE_CATALOG — register it there "
                    "first (with the metric that reads it)")

    # (b) {"event": "<name>"}, span("<name>") and named_scope("<name>")
    # mentions in docs + scripts
    for sf in ctx.docs + ctx.scripts:
        for i, line in enumerate(sf.lines, 1):
            for m in _DOC_EVENT_RE.finditer(line):
                if m.group(1) not in events:
                    yield Finding(
                        RULE_NAME, sf.rel, i,
                        f"documented metrics event {m.group(1)!r} does not "
                        "exist in utils.metrics.EVENT_SCHEMAS — stale doc "
                        "or missing registration")
            for m in _DOC_SPAN_RE.finditer(line):
                if m.group(1) not in spans:
                    yield Finding(
                        RULE_NAME, sf.rel, i,
                        f"documented tracer span {m.group(1)!r} does not "
                        "exist in telemetry.tracer.SPAN_CATALOG — stale "
                        "doc or missing registration")
            for m in _DOC_SCOPE_RE.finditer(line):
                if m.group(1) not in scopes:
                    yield Finding(
                        RULE_NAME, sf.rel, i,
                        f"documented device scope {m.group(1)!r} does not "
                        "exist in telemetry.tracer.SCOPE_CATALOG — stale "
                        "doc or missing registration")

    # (c) --set knob references everywhere
    for sf in ctx.all_python() + ctx.scripts + ctx.docs:
        for i, line in enumerate(sf.lines, 1):
            for m in _KNOB_RE.finditer(line):
                knob = m.group(1) or m.group(2)
                if knob in _KNOB_PLACEHOLDERS:
                    continue
                if not _knob_resolves(knob):
                    yield Finding(
                        RULE_NAME, sf.rel, i,
                        f"--set {knob}=... does not resolve against the "
                        "ExperimentConfig dataclasses (utils/config.py) — "
                        "typo or renamed knob")
