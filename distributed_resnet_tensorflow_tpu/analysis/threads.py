"""Thread-role contracts: the static complement of the dispatch sanitizer.

This codebase runs a fixed cast of threads (docs/input_pipeline.md's
thread inventory, docs/static_analysis.md's role table): ONE thread per
process may launch multi-device XLA executions (the train loop, or the
serve dispatch thread), staging threads only move bytes, the checkpoint
writer only does host I/O, and the daemons (heartbeat, watchdog,
checkpoint poller) only read/write files. Two shipped bugs define the
stakes: PR 2's cross-thread multi-device dispatch deadlock and PR 4's
gloo collective hang.

``THREAD_ROLES`` is the explicit registry: every ``threading.Thread(
target=...)`` spawn site (and every executor ``submit`` of a package
function) must resolve to a role here — an unregistered spawn is itself
a finding (``rules/thread_dispatch.py``), which is what keeps the
inventory honest as threads are added. The roles:

  ========  ==========================================================
  role      contract
  ========  ==========================================================
  dispatch  MAY launch multi-device executions; every other role may not
  staging   moves host bytes / issues transfers; never executes programs
  writer    checkpoint host I/O only (the zero-stall contract)
  daemon    heartbeat/watchdog/poller: files and sockets only
  ========  ==========================================================

Registry keys are ``<package-relative-file>::<qualname>`` of the spawn
TARGET (see ``callgraph.FuncNode.short``).
"""
from __future__ import annotations

import ast
from dataclasses import dataclass
from typing import Iterator, List, Optional, Tuple

from .callgraph import CallGraph, FuncNode, body_walk, get_callgraph

ROLE_DISPATCH = "dispatch"
ROLE_STAGING = "staging"
ROLE_WRITER = "writer"
ROLE_DAEMON = "daemon"

#: spawn-target → role. Every Thread/executor spawn in the package must
#: resolve here; rules/thread_dispatch.py flags the ones that don't.
THREAD_ROLES = {
    # the serve dispatch thread: the ONE thread of a serving process that
    # executes compiled programs (docs/serving.md threading contract)
    "serve/batcher.py::DynamicBatcher._run": ROLE_DISPATCH,
    # input pipeline workers (docs/input_pipeline.md): decode/stack/stage
    # threads move bytes; the consumer thread finalizes + dispatches
    "data/device_prefetch.py::threaded_iterator.<locals>.worker":
        ROLE_STAGING,
    "data/imagenet.py::imagenet_iterator.<locals>.feeder": ROLE_STAGING,
    "data/imagenet.py::imagenet_iterator.<locals>.decoder": ROLE_STAGING,
    # checkpoint writer thread: stage → fsync → manifest → commit, host
    # I/O only (the zero-stall contract, docs/resilience.md)
    "checkpoint/manager.py::CheckpointManager._write_async": ROLE_WRITER,
    "checkpoint/manager.py::CheckpointManager._write_sharded_async":
        ROLE_WRITER,
    # daemons: beats, peer-health polling, committed-checkpoint polling —
    # files only, never device work
    "resilience/heartbeat.py::HeartbeatPublisher._run": ROLE_DAEMON,
    "resilience/watchdog.py::Watchdog._run": ROLE_DAEMON,
    "serve/swap.py::CheckpointSwapper._run": ROLE_DAEMON,
    # the fleet front door (docs/serving.md fleet section): the replica-
    # side listener threads decode bytes and park on Futures (submitter
    # role — the batcher's dispatch thread still owns every execution);
    # the router/supervisor threads are numpy-and-sockets only by
    # construction (serve/router.py holds no jax state at all)
    "serve/wire.py::ReplicaListener._accept_loop": ROLE_DAEMON,
    "serve/wire.py::ReplicaListener._handle_conn": ROLE_DAEMON,
    "serve/router.py::Router._dispatch_loop": ROLE_DAEMON,
    "serve/router.py::Router._worker_loop": ROLE_DAEMON,
    "serve/router.py::Router._health_loop": ROLE_DAEMON,
    "serve/fleet.py::FleetSupervisor._watch": ROLE_DAEMON,
    # the reshard teardown's bounded jax.distributed.shutdown: shutting
    # down the dead generation's coordination client can block on a lost
    # peer, so it runs on a joined-with-timeout daemon and is abandoned
    # past the deadline (docs/resilience.md, elastic mesh)
    "parallel/distributed.py::teardown_for_reshard.<locals>._shutdown":
        ROLE_DAEMON,
}

#: entry points that constitute the LOOP/DISPATCH side for the blocking-
#: call rule: the train/eval loop plus the functions the serve dispatch
#: thread runs (the batcher's dispatch_fn callback is dynamic, so the
#: server's dispatch body is rooted explicitly), plus the fleet front
#: door's request path — one untimed wait in the router or a connection
#: handler would let a dead replica park the service forever.
LOOP_ROOTS = (
    "train/loop.py::Trainer.train",
    "train/loop.py::Trainer.evaluate",
    "main.py::run_train",
    "main.py::run_eval",
    "main.py::run_train_and_eval",
    "serve/server.py::InferenceServer._run_bucket",
    "serve/router.py::Router._dispatch_loop",
    "serve/router.py::Router._worker_loop",
    "serve/wire.py::ReplicaListener._handle_conn",
)


@dataclass(frozen=True)
class SpawnSite:
    rel: str
    lineno: int
    kind: str                      # "thread" | "submit"
    target: Optional[FuncNode]     # resolved spawn target (None = dynamic)
    target_desc: str               # what the source said


def role_of(target: FuncNode) -> Optional[str]:
    return THREAD_ROLES.get(target.short())


def _resolve_target_expr(expr: ast.AST, caller: FuncNode,
                         graph: CallGraph) -> Tuple[Optional[FuncNode], str]:
    """Resolve a Thread target= / submit first-arg expression to a
    FuncNode where statically possible."""
    if isinstance(expr, ast.Name):
        cands = graph.resolve_name(expr.id, caller.rel)
        return (cands[0] if len(cands) == 1 else None), expr.id
    if isinstance(expr, ast.Attribute):
        desc = f".{expr.attr}"
        if isinstance(expr.value, ast.Name) and expr.value.id == "self" \
                and caller.cls is not None:
            own = graph.by_class_method.get((caller.cls, expr.attr), [])
            if len(own) == 1:
                return own[0], f"self.{expr.attr}"
        cands = graph.by_name.get(expr.attr, [])
        return (cands[0] if len(cands) == 1 else None), desc
    return None, ast.dump(expr)[:40]


def _is_thread_ctor(call: ast.Call) -> bool:
    fn = call.func
    return (isinstance(fn, ast.Name) and fn.id == "Thread") or \
        (isinstance(fn, ast.Attribute) and fn.attr == "Thread")


def iter_spawn_sites(ctx) -> Iterator[SpawnSite]:
    """Every ``threading.Thread(target=...)`` construction and every
    ``<executor>.submit(fn, ...)`` whose first argument resolves to a
    package function. Tests are out of scope (the linter never sees
    them); repo-top python (chip_smoke.py etc.) is included."""
    graph = get_callgraph(ctx)
    for key, fn in sorted(graph.funcs.items()):
        for node in body_walk(fn.node):
            if not isinstance(node, ast.Call):
                continue
            if _is_thread_ctor(node):
                target_expr = next((kw.value for kw in node.keywords
                                    if kw.arg == "target"), None)
                if target_expr is None:
                    yield SpawnSite(fn.rel, node.lineno, "thread", None,
                                    "<no target=>")
                    continue
                tgt, desc = _resolve_target_expr(target_expr, fn, graph)
                yield SpawnSite(fn.rel, node.lineno, "thread", tgt, desc)
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "submit" and node.args:
                tgt, desc = _resolve_target_expr(node.args[0], fn, graph)
                if tgt is not None:  # batcher/server .submit(image) is
                    yield SpawnSite(fn.rel, node.lineno, "submit", tgt,
                                    desc)  # not a spawn — args are data


# -- dispatch-bearing call detection ----------------------------------------

def is_jitted_execution(call: ast.Call) -> bool:
    """``self.jitted_train_step()(state, batch)`` — calling the RESULT of
    a ``jitted_*`` accessor executes a compiled multi-device program.
    (Calling the accessor alone only builds/returns the jit wrapper —
    ``step_flops`` does that to lower for cost analysis, legally.)"""
    fn = call.func
    return isinstance(fn, ast.Call) and isinstance(fn.func, ast.Attribute) \
        and fn.func.attr.startswith("jitted_")


#: call names that finalize a StagedBatch — a multi-device unpack
#: execution (parallel/sharding.py; the PR 2 deadlock's exact shape)
DISPATCH_CALL_NAMES = ("finalize_staged", "finalize", "put_and_finalize")


def dispatch_bearing_calls(fn: FuncNode) -> Iterator[ast.Call]:
    """Calls in this function's own body that launch a multi-device XLA
    execution: jitted-step executions and StagedBatch finalization."""
    for node in body_walk(fn.node):
        if not isinstance(node, ast.Call):
            continue
        if is_jitted_execution(node):
            yield node
            continue
        name, _ = _call_name(node)
        if name in DISPATCH_CALL_NAMES:
            yield node


def _call_name(call: ast.Call) -> Tuple[Optional[str], Optional[str]]:
    from .callgraph import call_target
    return call_target(call)


# -- collective-bearing call detection --------------------------------------

#: direct cross-process/cross-device collective call names: the lax
#: collectives the shard_map'd paths issue plus the multihost barriers.
#: A function containing one of these (or an explicit jitted execution)
#: is collective-bearing; callers inherit transitively over the graph.
COLLECTIVE_CALL_NAMES = frozenset({
    "psum", "psum_scatter", "all_gather", "all_to_all", "ppermute",
    "pmean", "pmax", "pmin",
    "sync_global_devices", "process_allgather", "broadcast_one_to_all",
})


def contains_direct_collective(fn: FuncNode) -> bool:
    for node in body_walk(fn.node):
        if not isinstance(node, ast.Call):
            continue
        if is_jitted_execution(node):
            return True
        name, _ = _call_name(node)
        if name in COLLECTIVE_CALL_NAMES:
            return True
    return False


def collective_bearing_keys(graph: CallGraph) -> set:
    """Transitive closure: every function that can reach a direct
    collective call over resolved edges."""
    seeds = {key for key, fn in graph.funcs.items()
             if contains_direct_collective(fn)}
    # propagate up: caller of a bearing function is bearing
    bearing = set(seeds)
    changed = True
    while changed:
        changed = False
        for key in graph.funcs:
            if key in bearing:
                continue
            if any(e in bearing for e in graph.edges(key)):
                bearing.add(key)
                changed = True
    return bearing


# -- chief-gate detection ----------------------------------------------------

def _is_chief_test(test: ast.AST) -> bool:
    """``is_chief()`` / ``jax.process_index() == 0`` (and negations are
    handled by the caller via the guard-return form)."""
    if isinstance(test, ast.Call):
        name, _ = _call_name(test)
        return name == "is_chief"
    if isinstance(test, ast.Compare) and len(test.ops) == 1:
        left, right = test.left, test.comparators[0]
        if isinstance(test.ops[0], ast.Eq):
            for a, b in ((left, right), (right, left)):
                if isinstance(b, ast.Constant) and b.value == 0 \
                        and isinstance(a, ast.Call):
                    name, _ = _call_name(a)
                    if name == "process_index":
                        return True
    return False


def _is_not_chief_test(test: ast.AST) -> bool:
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        return _is_chief_test(test.operand)
    if isinstance(test, ast.Compare) and len(test.ops) == 1 \
            and isinstance(test.ops[0], ast.NotEq):
        left, right = test.left, test.comparators[0]
        for a, b in ((left, right), (right, left)):
            if isinstance(b, ast.Constant) and b.value == 0 \
                    and isinstance(a, ast.Call):
                name, _ = _call_name(a)
                if name == "process_index":
                    return True
    return False


def chief_gated_statements(fn: FuncNode) -> Iterator[List[ast.stmt]]:
    """Statement groups that only the chief process executes:

      * the body of ``if is_chief():`` / ``if process_index() == 0:``
        (also via a local name assigned from that expression);
      * everything AFTER an early ``if not is_chief(): return`` guard.
    """
    chief_names = set()
    for node in body_walk(fn.node):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and _is_chief_test(node.value):
            chief_names.add(node.targets[0].id)

    def test_is_chief(test):
        if _is_chief_test(test):
            return True
        return isinstance(test, ast.Name) and test.id in chief_names

    def test_is_not_chief(test):
        if _is_not_chief_test(test):
            return True
        return isinstance(test, ast.UnaryOp) \
            and isinstance(test.op, ast.Not) \
            and isinstance(test.operand, ast.Name) \
            and test.operand.id in chief_names

    def walk_stmts(stmts: List[ast.stmt]):
        for i, stmt in enumerate(stmts):
            if isinstance(stmt, ast.If):
                if test_is_chief(stmt.test):
                    yield stmt.body
                elif test_is_not_chief(stmt.test):
                    if stmt.orelse:
                        yield stmt.orelse
                    if any(isinstance(s, (ast.Return, ast.Raise))
                           for s in stmt.body):
                        yield stmts[i + 1:]
                # branches may nest further gates
                yield from walk_stmts(stmt.body)
                yield from walk_stmts(stmt.orelse)
            elif isinstance(stmt, (ast.For, ast.While, ast.With,
                                   ast.Try, ast.AsyncWith, ast.AsyncFor)):
                for attr in ("body", "orelse", "finalbody", "handlers"):
                    sub = getattr(stmt, attr, None) or []
                    if attr == "handlers":
                        for h in sub:
                            yield from walk_stmts(h.body)
                    else:
                        yield from walk_stmts(sub)

    yield from walk_stmts(getattr(fn.node, "body", []))


def calls_in_statements(stmts: List[ast.stmt],
                        fn: FuncNode) -> Iterator[ast.Call]:
    """Every call in the given statements, excluding nested defs (their
    bodies only run when the nested function is itself invoked)."""
    for stmt in stmts:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        stack = [stmt]
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            if isinstance(node, ast.Call):
                yield node
            stack.extend(ast.iter_child_nodes(node))
