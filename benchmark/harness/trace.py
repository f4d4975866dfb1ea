"""From a profiler trace to numbers: the one reduction every PR shares.

Works on a plain structure, so that a hand-built trace tests it:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [(name, start_ns, duration_ns), ...]}],
                 "tf_op": {name: "jit(step)/jit(main)/forward/dot_general"}}]}

``load`` reads that from the ``.xplane.pb`` the JAX profiler writes; a
plane's ``tf_op`` (optional in a hand-built trace) gives, per operation, the
``jax.named_scope`` path its instruction was traced under.

* The *busy union* of a device is the union of the intervals in which any
  operation of its ``XLA Ops`` line runs; nested operations (a ``while``
  and its body) count once.
* The *window* is aligned to the step program: from the start of the first
  to the start of the last execution of the device's most expensive module
  (``XLA Modules`` line), so it holds a whole number of periods, input
  unpacking and idle gaps between them included, and none of the
  profiler's ramp at either end.
* An operation's *self time* is its duration minus what its children cover.
  Families strip the numeric suffix (``fusion.123`` -> ``fusion``).
* *Exposed collective time* is the time in which a collective operation
  (all-reduce, reduce-scatter, all-gather, collective-permute, all-to-all,
  with their ``-start``/``-done``) runs on the device and no other
  operation does (containers such as ``while`` do not count as another;
  a collective in flight on the ``Async XLA Ops`` line counts as running).
* Every family's self time and event count are kept (``op_s``,
  ``op_events``), so a kernel is found by the name its ``name=`` gave the
  instruction (``softmax_xent_fwd``) whatever its rank; the ten most
  expensive are the result line's ``device_ops``.
* An operation's *scope* is its ``tf_op`` path without the last component
  (the primitive): ``scope_s`` sums self time by scope, ``""`` for what the
  compiler added from no source line; ``scope_seconds`` sums the scopes that
  hold a component. ``module_s`` sums it by the ``XLA Modules`` execution
  the operation starts in.
* An *idle gap* is a hole in the busy union inside the window; it is named
  after the host event that overlaps it most, thread and process ids
  stripped.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns
COLLECTIVE = re.compile(r"all-reduce|reduce-scatter|all-gather|collective-permute|all-to-all")
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    scopes = _tf_ops(path)
    planes = []
    for plane in data.planes:
        lines = [{"name": line.name,
                  "events": [(e.name, float(e.start_ns), float(e.duration_ns))
                             for e in line.events]} for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines,
                       "tf_op": scopes.get(plane.name, {})})
    return {"planes": planes}


def _tf_ops(path: str) -> Dict[str, Dict[str, str]]:
    """Per device plane: operation name -> its ``tf_op`` stat. The stat sits
    on the event's metadata, which ``ProfileData`` does not show, so the
    file is parsed once more against the four messages of TSL's
    ``xplane.proto`` that hold it, declared here (lines and events are left
    as unknown fields; no TensorFlow import)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory
    F = descriptor_pb2.FieldDescriptorProto
    file = descriptor_pb2.FileDescriptorProto(
        name="benchmark/xplane_metadata.proto", package="benchmark_xplane", syntax="proto3")

    def message(name, *fields):
        m = file.message_type.add(name=name)
        for fname, number, ftype, repeated, of in fields:
            m.field.add(name=fname, number=number, type=ftype,
                        label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL,
                        type_name=f".benchmark_xplane.{of}" if of else None)
    message("Stat", ("metadata_id", 1, F.TYPE_INT64, False, None),
            ("str_value", 5, F.TYPE_STRING, False, None),
            ("ref_value", 7, F.TYPE_UINT64, False, None))
    message("EventMetadata", ("name", 2, F.TYPE_STRING, False, None),
            ("stats", 5, F.TYPE_MESSAGE, True, "Stat"))
    message("StatMetadata", ("name", 2, F.TYPE_STRING, False, None))
    # a map<int64, M> field is on the wire a repeated {key = 1; value = 2}
    message("EventEntry", ("value", 2, F.TYPE_MESSAGE, False, "EventMetadata"))
    message("StatEntry", ("key", 1, F.TYPE_INT64, False, None),
            ("value", 2, F.TYPE_MESSAGE, False, "StatMetadata"))
    message("Plane", ("name", 2, F.TYPE_STRING, False, None),
            ("event_metadata", 4, F.TYPE_MESSAGE, True, "EventEntry"),
            ("stat_metadata", 5, F.TYPE_MESSAGE, True, "StatEntry"))
    message("Space", ("planes", 1, F.TYPE_MESSAGE, True, "Plane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    space = message_factory.GetMessageClass(
        pool.FindMessageTypeByName("benchmark_xplane.Space"))()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {}
    for plane in space.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        wanted = {k for k, n in names.items() if n == "tf_op"}
        found = {}
        for entry in plane.event_metadata:
            for st in entry.value.stats:
                if st.metadata_id in wanted:
                    found[entry.value.name] = st.str_value or names.get(st.ref_value, "")
        out[plane.name] = found
    return out


def device_planes(trace: dict) -> List[dict]:
    found = [(int(DEVICE_PLANE.match(p["name"]).group(2)), p)
             for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    return [p for _, p in sorted(found, key=lambda t: t[0])]


def _line(plane: dict, name: str) -> List[Event]:
    for line in plane["lines"]:
        if line["name"] == name:
            return sorted(line["events"], key=lambda e: (e[1], -e[2]))
    return []


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def _total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def family(name: str) -> str:
    """``fusion.123`` -> ``fusion``; ``%all-reduce-start.2`` -> ``all-reduce-start``."""
    name = name.lstrip("%").split(" ")[0].split("(")[0]
    return re.sub(r"([._]\d+)+$", "", name) or name


def strip_ids(name: str) -> str:
    """``pjrt-tpu-tasks/5864:XlaLinearize`` -> ``XlaLinearize``."""
    name = name.rsplit(":", 1)[-1] if re.search(r"/\d+:", name) else name
    return re.sub(r"[#/]?\b\d{3,}\b", "", name).strip() or name


def self_times(events: Sequence[Event]) -> List[Tuple[str, float, float, float, bool]]:
    """(name, start, end, self_ns, has_children) for the events of one line.
    An event is another's child only if it lies wholly inside it; two that
    overlap in part are siblings."""
    out, stack = [], []  # stack of [name, start, end, covered_by_children, has_children]

    def pop():
        name, a, b, covered, parent = stack.pop()
        out.append((name, a, b, max(0.0, (b - a) - covered), parent))

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and (stack[-1][2] <= start or stack[-1][2] < end):
            pop()
        if stack:
            stack[-1][3] += dur
            stack[-1][4] = True
        stack.append([name, start, end, 0.0, False])
    while stack:
        pop()
    return out


def subtract(keep: Sequence[Tuple[float, float]], cut: Sequence[Tuple[float, float]]):
    """The parts of ``keep`` (a union) that no interval of ``cut`` (a union) covers."""
    out = []
    for a, b in keep:
        cursor = a
        for c, d in cut:
            if d <= cursor or c >= b:
                continue
            if c > cursor:
                out.append((cursor, c))
            cursor = max(cursor, d)
        if cursor < b:
            out.append((cursor, b))
    return out


def step_window(plane: dict) -> Optional[Tuple[float, float, int, str]]:
    """(start, end, periods, module) aligned to the most expensive module."""
    modules = _line(plane, "XLA Modules")
    if not modules:
        return None
    cost: Dict[str, float] = {}
    for name, _, dur in modules:
        cost[family(name)] = cost.get(family(name), 0.0) + dur
    top = max(cost, key=cost.get)
    starts = [s for name, s, _ in modules if family(name) == top]
    if len(starts) < 2:
        return None
    return starts[0], starts[-1], len(starts) - 1, top


def scope_of(tf_op: str) -> str:
    """``jit(step)/jit(main)/forward/conv/dot_general`` -> the path without
    its primitive; ``""`` for an operation that carries no ``tf_op``."""
    return tf_op.rsplit("/", 1)[0] if "/" in tf_op else ""


def scope_seconds(reduced: dict, component: str, but_not: Sequence[str] = ()) -> Optional[float]:
    """Self seconds of device 0's operations whose scope path holds
    ``component`` as one of its components and none of ``but_not``; None
    where no operation does (the reader then has nothing to read)."""
    found = [s for path, s in reduced["scope_s"].items()
             if component in path.split("/") and not set(but_not).intersection(path.split("/"))]
    return sum(found) if found else None


def reduce(trace: dict, steps_per_dispatch: int = 1) -> dict:
    """Everything the per-layer readers and the result line take from a trace."""
    planes = device_planes(trace)
    if not planes:
        raise ValueError("the trace holds no device plane")
    first = planes[0]
    win = step_window(first)
    if win is None:
        raise ValueError("the trace holds fewer than two executions of the step "
                         "program on device 0: nothing to align a window to")
    lo, hi, periods, module = win
    window_ns = hi - lo
    busy_per_device = []
    for plane in planes:
        ops = _line(plane, "XLA Ops")
        busy = _clip(union([(s, s + d) for _, s, d in ops]), lo, hi)
        busy_per_device.append(_total(busy))
    ops0 = _line(first, "XLA Ops")
    busy0 = _clip(union([(s, s + d) for _, s, d in ops0]), lo, hi)
    fam_ns: Dict[str, float] = {}
    fam_events: Dict[str, int] = {}
    scope_ns: Dict[str, float] = {}
    module_ns: Dict[str, float] = {}
    tf_op = first.get("tf_op", {})
    modules = [(s, s + d, family(name)) for name, s, d in _line(first, "XLA Modules")]
    module_starts = [m[0] for m in modules]
    collective, compute = [], []
    for name, a, b, self_ns, parent in self_times(ops0):
        inside = _clip([(a, b)], lo, hi)
        if not inside or b <= a:
            continue
        share = _total(inside) / (b - a) * self_ns
        fam = family(name)
        fam_ns[fam] = fam_ns.get(fam, 0.0) + share
        if lo <= a < hi:  # an event is counted in the period it starts in
            fam_events[fam] = fam_events.get(fam, 0) + 1
        scope = scope_of(tf_op.get(name, ""))
        scope_ns[scope] = scope_ns.get(scope, 0.0) + share
        at = bisect.bisect_right(module_starts, a) - 1
        runs_in = modules[at][2] if at >= 0 and a < modules[at][1] else ""
        module_ns[runs_in] = module_ns.get(runs_in, 0.0) + share
        if COLLECTIVE.search(fam):
            collective.append((a, b))
        elif not parent:  # a while or a call only holds what runs inside it
            compute.append((a, b))
    # asynchronous collectives are in flight on a line of their own
    collective += [(a, a + d) for name, a, d in _line(first, "Async XLA Ops")
                   if COLLECTIVE.search(family(name)) and a + d > lo and a < hi]
    exposed_ns = _total(_clip(subtract(union(collective), union(compute)), lo, hi))
    collective_events = len(collective)
    gaps, cursor = [], lo
    for a, b in busy0:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if hi > cursor:
        gaps.append((cursor, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [(name, s, s + d)
            for p in trace["planes"] if p["name"].startswith("/host:")
            for line in p["lines"] for name, s, d in line["events"] if d > 0]
    named = []
    for a, b in gaps[:10]:
        best, best_overlap = "unattributed", 0.0
        for name, s, e in host:
            overlap = min(b, e) - max(a, s)
            # an enclosing span that lasts for many gaps explains none
            if overlap > best_overlap and (e - s) <= 4 * (b - a):
                best, best_overlap = strip_ids(name), overlap
        named.append([best, (b - a) / 1e9])
    steps = periods * steps_per_dispatch
    top_ops = sorted(fam_ns.items(), key=lambda kv: -kv[1])[:10]
    return {
        "module": module, "steps": steps, "periods": periods,
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy_per_device) / len(busy_per_device) / 1e9,
        "busy_s_device0": _total(busy0) / 1e9,
        "busy_s_per_device": [b / 1e9 for b in busy_per_device],
        "collective_exposed_s": exposed_ns / 1e9,
        "collective_events": collective_events,
        "device_ops": [[name, ns / 1e9] for name, ns in top_ops],
        "idle_gaps": named,
        "op_s": {name: ns / 1e9 for name, ns in fam_ns.items()},
        "op_events": fam_events,
        "scope_s": {name: ns / 1e9 for name, ns in scope_ns.items()},
        "module_s": {name: ns / 1e9 for name, ns in module_ns.items()},
    }
