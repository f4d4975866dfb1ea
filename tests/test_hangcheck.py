"""Hangcheck tests (ISSUE 13): each thread/lock contract rule fires on a
known-bad fixture at the expected file:line, the collective-schedule
extractor emits deterministic signatures, and the `main.py check` CLI honors
the exit-code contract (0 clean / 1 findings, findings carry file:line)."""
import json
import os

import pytest

from distributed_resnet_tensorflow_tpu.analysis.lint import (
    run_lint, repo_root)
from distributed_resnet_tensorflow_tpu.analysis.report import format_findings

PKG = "distributed_resnet_tensorflow_tpu"


def _by_rule(findings):
    out = {}
    for f in findings:
        out.setdefault(f.rule, []).append(f)
    return out


# ---------------------------------------------------------------------------
# cross-thread-dispatch
# ---------------------------------------------------------------------------

BAD_SPAWN = '''\
import threading


class Runner:
    def work(self, trainer, staged):
        out = trainer.jitted_train_step()(staged)          # line 6: dispatch
        return out

    def start(self):
        t = threading.Thread(target=self.work)             # line 10: spawn
        t.start()


def mystery():
    threading.Thread(target=getattr(object, "x")).start()  # line 15: dynamic
'''


def test_cross_thread_dispatch_fixture(tmp_path, monkeypatch):
    pkg = tmp_path / PKG
    pkg.mkdir()
    (pkg / "bad_threads.py").write_text(BAD_SPAWN)
    rel = os.path.join(PKG, "bad_threads.py")

    # unregistered spawn target + unresolvable dynamic target both fire
    by_rule = _by_rule(run_lint(str(tmp_path)))
    hits = {(f.path, f.line) for f in by_rule["cross-thread-dispatch"]}
    assert (rel, 10) in hits      # unregistered role
    assert (rel, 15) in hits      # dynamic target

    # registering the target with a NON-dispatch role moves the finding
    # to the dispatch-bearing call site (the jitted execution)
    from distributed_resnet_tensorflow_tpu.analysis import threads
    monkeypatch.setitem(threads.THREAD_ROLES,
                        "bad_threads.py::Runner.work", threads.ROLE_STAGING)
    by_rule = _by_rule(run_lint(str(tmp_path)))
    hits = {(f.path, f.line) for f in by_rule["cross-thread-dispatch"]}
    assert (rel, 6) in hits
    assert (rel, 10) not in hits

    # a dispatch role makes the same call legal
    monkeypatch.setitem(threads.THREAD_ROLES,
                        "bad_threads.py::Runner.work",
                        threads.ROLE_DISPATCH)
    by_rule = _by_rule(run_lint(str(tmp_path)))
    hits = {(f.path, f.line) for f in
            by_rule.get("cross-thread-dispatch", ())}
    assert (rel, 6) not in hits


def test_real_tree_spawn_sites_all_registered():
    """Every Thread/executor spawn in the real tree resolves to a role —
    the inventory in analysis/threads.THREAD_ROLES is complete (the
    docs/static_analysis.md thread-role table mirrors it)."""
    from distributed_resnet_tensorflow_tpu.analysis import threads
    from distributed_resnet_tensorflow_tpu.analysis.lint import build_context
    ctx = build_context()
    spawns = list(threads.iter_spawn_sites(ctx))
    assert len(spawns) >= 8  # batcher/swap/prefetch/imagenet×2/beat/dog/ckpt
    unresolved = [s for s in spawns if s.target is None]
    assert unresolved == [], unresolved
    unregistered = [s.target.short() for s in spawns
                    if threads.role_of(s.target) is None]
    assert unregistered == [], unregistered
    # the fleet front door's thread inventory (ISSUE 20 satellite): the
    # listener's accept/connection threads, the router's pool loops, and
    # the supervisor watch must all be spawned through resolvable,
    # registered targets — these are the roots the socket sweep walks
    shorts = {s.target.short() for s in spawns}
    assert {"serve/wire.py::ReplicaListener._accept_loop",
            "serve/wire.py::ReplicaListener._handle_conn",
            "serve/router.py::Router._health_loop",
            "serve/fleet.py::FleetSupervisor._watch"} <= shorts, shorts


# ---------------------------------------------------------------------------
# untimed-blocking-call
# ---------------------------------------------------------------------------

BAD_LOOP = '''\
import queue


def drain(q):
    item = q.get()                       # line 5: untimed get on the loop
    q.get(timeout=1.0)                   # timed: fine
    cfg = {}.get("x")                    # dict.get with args: fine
    return item


class Trainer:
    def train(self, q, worker):
        out = drain(q)
        worker.join()                    # line 14: untimed join
        return out


def helper_elsewhere(q):
    return q.get()                       # unreachable from roots: fine
'''


def test_untimed_blocking_call_fixture(tmp_path):
    pkg = tmp_path / PKG / "train"
    pkg.mkdir(parents=True)
    (pkg / "loop.py").write_text(BAD_LOOP)
    by_rule = _by_rule(run_lint(str(tmp_path)))
    rel = os.path.join(PKG, "train", "loop.py")
    hits = {(f.path, f.line) for f in by_rule["untimed-blocking-call"]}
    assert (rel, 5) in hits
    assert (rel, 14) in hits
    assert hits == {(rel, 5), (rel, 14)}, hits


BAD_SOCK = '''\
import threading


class Listener:
    def loop(self):
        self.sock.settimeout(None)           # DISARMS: not a blessing
        while True:
            conn, _ = self.sock.accept()     # line 8: untimed accept
            data = conn.recv(4096)           # line 9: untimed recv
            self.handle(data)

    def handle(self, data):
        return data

    def start(self):
        t = threading.Thread(target=self.loop)
        t.start()
'''


def test_socket_wait_sweep_fixture(tmp_path):
    """Socket waits on a spawned thread with no armed settimeout are
    findings; arming the deadline in the lifecycle method before the
    spawn (the serve/wire.py listener idiom) blesses the root."""
    pkg = tmp_path / PKG / "serve"
    pkg.mkdir(parents=True)
    (pkg / "bad_sock.py").write_text(BAD_SOCK)
    rel = os.path.join(PKG, "serve", "bad_sock.py")
    by_rule = _by_rule(run_lint(str(tmp_path)))
    hits = {(f.path, f.line) for f in by_rule["untimed-blocking-call"]
            if "socket" in f.message}
    assert hits == {(rel, 8), (rel, 9)}, hits

    (pkg / "bad_sock.py").write_text(BAD_SOCK.replace(
        "        t = threading.Thread(target=self.loop)",
        "        self.sock.settimeout(0.5)\n"
        "        t = threading.Thread(target=self.loop)"))
    by_rule = _by_rule(run_lint(str(tmp_path)))
    hits = {(f.path, f.line) for f in
            by_rule.get("untimed-blocking-call", ())
            if "socket" in f.message}
    assert hits == set(), hits


# ---------------------------------------------------------------------------
# chief-gated-collective
# ---------------------------------------------------------------------------

BAD_CHIEF = '''\
import jax
from jax import lax


def publish(x):
    return lax.psum(x, "data")


def report(writer, x):
    if jax.process_index() == 0:
        writer.write_scalars(0, {"x": 1.0})     # metrics: fine
        publish(x)                              # line 12: gated collective


def guard_form(x):
    if jax.process_index() != 0:
        return None
    return publish(x)                           # line 18: gated by guard


def everyone(x):
    return publish(x)                           # ungated: fine
'''


def test_chief_gated_collective_fixture(tmp_path):
    pkg = tmp_path / PKG
    pkg.mkdir()
    (pkg / "bad_chief.py").write_text(BAD_CHIEF)
    by_rule = _by_rule(run_lint(str(tmp_path)))
    rel = os.path.join(PKG, "bad_chief.py")
    hits = {(f.path, f.line) for f in by_rule["chief-gated-collective"]}
    assert (rel, 12) in hits
    assert (rel, 18) in hits
    assert hits == {(rel, 12), (rel, 18)}, hits


# ---------------------------------------------------------------------------
# lock-order-cycle
# ---------------------------------------------------------------------------

BAD_LOCKS = '''\
import threading

LOCK_A = threading.Lock()
LOCK_B = threading.Lock()
LOCK_C = threading.Lock()


def forward():
    with LOCK_A:
        takes_b()                        # line 10: A-held call taking B


def takes_b():
    with LOCK_B:
        pass


def backward():
    with LOCK_B:
        takes_a()                        # line 20: B-held call taking A


def takes_a():
    with LOCK_A:
        pass


def leaf_only():
    with LOCK_C:                         # no second lock: fine
        pass
'''


def test_lock_order_cycle_fixture(tmp_path):
    pkg = tmp_path / PKG
    pkg.mkdir()
    (pkg / "bad_locks.py").write_text(BAD_LOCKS)
    by_rule = _by_rule(run_lint(str(tmp_path)))
    rel = os.path.join(PKG, "bad_locks.py")
    findings = by_rule["lock-order-cycle"]
    assert len(findings) == 1, [str(f) for f in findings]
    f = findings[0]
    assert f.path == rel and f.line in (10, 20)
    assert "LOCK_A" in f.message and "LOCK_B" in f.message


def test_lock_order_self_cycle_and_suppression(tmp_path):
    src = (
        "import threading\n\n"
        "LOCK = threading.Lock()\n\n\n"
        "def outer():\n"
        "    with LOCK:\n"
        "        inner()                 # line 8: re-acquires LOCK\n\n\n"
        "def inner():\n"
        "    with LOCK:\n"
        "        pass\n")
    pkg = tmp_path / PKG
    pkg.mkdir()
    (pkg / "bad_relock.py").write_text(src)
    by_rule = _by_rule(run_lint(str(tmp_path)))
    rel = os.path.join(PKG, "bad_relock.py")
    assert {(f.path, f.line) for f in by_rule["lock-order-cycle"]} == \
        {(rel, 8)}
    # the established suppression syntax vets the cycle (marker on the
    # acquisition line, one above the edge's call line)
    (pkg / "bad_relock.py").write_text(src.replace(
        "    with LOCK:\n"
        "        inner()                 # line 8: re-acquires LOCK",
        "    with LOCK:\n"
        "        inner()  # shardcheck: ok(lock-order-cycle)"))
    by_rule = _by_rule(run_lint(str(tmp_path)))
    assert "lock-order-cycle" not in by_rule


# ---------------------------------------------------------------------------
# hangcheck-schedule: extraction, determinism, artifact byte-identity
# ---------------------------------------------------------------------------

def _tiny_conv_preset():
    """A cheap conv preset for schedule tests (resnet8 on
    8×8 synthetic images, batch 16 — divides 8 shards)."""
    from distributed_resnet_tensorflow_tpu.utils.config import get_preset
    cfg = get_preset("cifar10_resnet50")
    cfg.model.resnet_size = 8
    cfg.data.image_size = 8
    cfg.train.batch_size = 16
    cfg.data.eval_batch_size = 16
    return cfg


def test_extract_schedule_orders_explicit_collectives(devices):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from distributed_resnet_tensorflow_tpu.analysis.collectives import (
        extract_schedule)
    mesh = Mesh(np.array(devices).reshape(8,), ("data",))

    def body(x):
        a = jax.lax.psum(x, "data")
        b = jax.lax.psum_scatter(x, "data", scatter_dimension=0,
                                 tiled=True)
        c = jax.lax.all_gather(b, "data", axis=0, tiled=True)
        return a + c

    fn = shard_map(body, mesh=mesh, in_specs=P("data"),
                   out_specs=P("data"))
    sched = extract_schedule(
        fn, jax.ShapeDtypeStruct((64, 4), jnp.float32))
    kinds = [op["op"] for op in sched]
    assert kinds == ["psum", "psum_scatter", "all_gather"]
    assert sched[0]["axes"] == ["data"]
    # bytes are PER-PARTICIPANT payloads: inside shard_map the traced
    # avals are the local shards — (64/8, 4) f32 here
    assert sched[0]["bytes"] == 8 * 4 * 4


def test_artifact_is_byte_identical_across_writes(tmp_path, devices,
                                                  monkeypatch):
    from distributed_resnet_tensorflow_tpu.analysis.collectives import (
        run_collectives, write_artifact)
    from distributed_resnet_tensorflow_tpu.utils import config as config_mod
    monkeypatch.setitem(config_mod.PRESETS, "tiny_conv", _tiny_conv_preset)
    _, sigs1 = run_collectives(["tiny_conv"])
    _, sigs2 = run_collectives(["tiny_conv"])
    p1, p2 = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    write_artifact(sigs1, p1)
    write_artifact(sigs2, p2)
    b1, b2 = open(p1, "rb").read(), open(p2, "rb").read()
    assert b1 == b2
    doc = json.loads(b1)
    assert doc["schema_version"] == 1
    assert any(k.endswith("/train") for k in doc["signatures"])


def test_committed_artifact_matches_entry_shape():
    """The committed analysis/collective_schedules.json parses and has
    the documented shape (docs/static_analysis.md) — the gate rewrites
    it on every full sweep, so drift means someone edited it by hand."""
    from distributed_resnet_tensorflow_tpu.analysis.collectives import (
        artifact_path)
    doc = json.load(open(artifact_path()))
    assert doc["schema_version"] == 1
    sigs = doc["signatures"]
    assert any(k.endswith("/train") for k in sigs)
    for key, entry in sigs.items():
        assert set(entry) == {"ops"}, key
        for op in entry["ops"]:
            assert set(op) == {"op", "axes", "operands", "bytes",
                               "count"}, (key, op)


def test_committed_artifact_holds_one_exchange():
    """The gradient exchange is XLA's: the artifact holds the schedules a
    step program writes out itself (pipeline hand-offs, expert
    all-to-alls) and none of a bucketed, compressed or hierarchical
    exchange, whose 33 signatures were 98% of its 40,685 lines."""
    from distributed_resnet_tensorflow_tpu.analysis.collectives import (
        artifact_path)
    with open(artifact_path()) as f:
        text = f.read()
    assert len(text.splitlines()) < 2000
    for key in json.loads(text)["signatures"]:
        assert not any(w in key for w in ("overlap", "compress", "hier")), key


# ---------------------------------------------------------------------------
# `main.py check` CLI exit-code contract
# ---------------------------------------------------------------------------

BAD_CLI_PY = '''\
import sys


def leave():
    sys.exit(3)                                 # line 5: exit-code-contract
'''


def test_check_cli_exit_zero_on_clean_tree():
    from distributed_resnet_tensorflow_tpu.main import main
    with pytest.raises(SystemExit) as e:
        main(["check", "--lint-only"])
    assert e.value.code == 0


def test_check_cli_exit_nonzero_with_findings_and_file_line(tmp_path,
                                                            capsys):
    from distributed_resnet_tensorflow_tpu.main import main
    pkg = tmp_path / PKG
    pkg.mkdir()
    (pkg / "bad.py").write_text(BAD_CLI_PY)
    with pytest.raises(SystemExit) as e:
        main(["check", "--lint-only", "--root", str(tmp_path)])
    assert e.value.code == 1          # the EXIT_CONTRACT failure code
    out = capsys.readouterr().out
    assert os.path.join(PKG, "bad.py") + ":5" in out
    assert "exit-code-contract" in out


def test_check_cli_no_hangcheck_skips_the_rules(tmp_path):
    """--no-hangcheck mirrors --no-zero1-sweep: the four thread/lock
    rules are excluded from the lint pass (and the schedule phase is
    skipped — lint-only here keeps the test in seconds)."""
    from distributed_resnet_tensorflow_tpu.main import main
    pkg = tmp_path / PKG
    pkg.mkdir()
    (pkg / "bad_chief.py").write_text(BAD_CHIEF)
    with pytest.raises(SystemExit) as e:
        main(["check", "--lint-only", "--root", str(tmp_path)])
    assert e.value.code == 1          # hangcheck rule fires...
    with pytest.raises(SystemExit) as e:
        main(["check", "--lint-only", "--no-hangcheck",
              "--root", str(tmp_path)])
    assert e.value.code == 0          # ...and is opted out cleanly
