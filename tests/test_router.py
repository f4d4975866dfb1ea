"""serve/router.py + serve/fleet.py + serve/loadgen.py — the fleet front
door's tier-1 tables (docs/serving.md fleet section).

The three routing state machines are pure and clock-injected, so every
table here runs with a fake clock and zero sockets: the replica health
SM (warming → ready ⇄ degraded, suspect → dead, drain/readmit), the
canary controller (start → confirm → promote / rollback, bad-step
memory), least-outstanding replica choice, and SLO admission
(shed/degrade). The threaded tests drive a real Router with in-memory
fake replica clients — a dead replica mid-load must cost ZERO client
errors (hedge + retry absorb it), and a seeded p99 regression must roll
the canary back without the bad step ever reaching a baseline replica.
The kill-a-real-process recovery path is the slow tier
(scripts/serve_fleet_smoke.sh and the subprocess test below)."""
import json
import os
import time
from concurrent.futures import Future

import numpy as np
import pytest

from distributed_resnet_tensorflow_tpu.serve.loadgen import (LOAD_SHAPES,
                                                             arrival_times,
                                                             run_open_loop)
from distributed_resnet_tensorflow_tpu.serve.router import (
    CanaryController, ReplicaHealth, RequestShed, RouteError, Router,
    percentile_ms, pick_replica, top1_confidence)
from distributed_resnet_tensorflow_tpu.serve.wire import ReplicaError
from distributed_resnet_tensorflow_tpu.utils.config import RouteConfig


def _rcfg(**kw):
    cfg = RouteConfig()
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


# ---------------------------------------------------------------------------
# registries (the cheap runtime tripwire; the registry-drift lint is the
# static enforcement)
# ---------------------------------------------------------------------------

def test_route_events_and_spans_registered():
    from distributed_resnet_tensorflow_tpu.telemetry.tracer import \
        SPAN_CATALOG
    from distributed_resnet_tensorflow_tpu.utils.metrics import EVENT_SCHEMAS
    for name in ("route", "replica_health", "canary", "shed",
                 "replica_replace"):
        assert name in EVENT_SCHEMAS
    for name in ("route.attempt", "route.health"):
        assert name in SPAN_CATALOG


def test_router_threads_registered_for_lint():
    from distributed_resnet_tensorflow_tpu.analysis.threads import (
        LOOP_ROOTS, THREAD_ROLES)
    for key in ("serve/router.py::Router._dispatch_loop",
                "serve/router.py::Router._worker_loop",
                "serve/router.py::Router._health_loop",
                "serve/wire.py::ReplicaListener._accept_loop",
                "serve/wire.py::ReplicaListener._handle_conn",
                "serve/fleet.py::FleetSupervisor._watch"):
        assert key in THREAD_ROLES
    # the route path is covered by the untimed-blocking-call rule
    assert "serve/router.py::Router._dispatch_loop" in LOOP_ROOTS
    assert "serve/wire.py::ReplicaListener._handle_conn" in LOOP_ROOTS


# ---------------------------------------------------------------------------
# replica health state machine
# ---------------------------------------------------------------------------

def test_health_warming_to_ready_on_probe():
    h = ReplicaHealth(0)
    tr = h.on_success()
    assert (tr.frm, tr.to, tr.reason) == ("warming", "ready", "probe_ok")
    assert h.on_success() is None  # already ready: no edge


def test_health_failures_escalate_suspect_then_dead():
    h = ReplicaHealth(0, suspect_after=2, dead_after=4)
    h.on_success()
    assert h.on_failure() is None                 # 1 failure: still ready
    tr = h.on_failure()
    assert (tr.to, tr.reason) == ("suspect", "failures")
    assert h.on_failure() is None                 # 3: still suspect
    tr = h.on_failure()
    assert (tr.to, tr.reason) == ("dead", "failures")
    assert h.on_failure() is None                 # dead absorbs failures


def test_health_suspect_recovers_on_success():
    h = ReplicaHealth(0, suspect_after=1)
    h.on_success()
    h.on_failure()
    assert h.state == "suspect"
    tr = h.on_success()
    assert (tr.to, tr.reason) == ("ready", "recovered")
    assert h.failures == 0


def test_health_stale_beat_kills_but_warming_exempt():
    h = ReplicaHealth(0, beat_stale_secs=10.0)
    assert h.on_beat(99.0) is None       # warming: supervisor bounds it
    h.on_success()
    assert h.on_beat(9.0) is None
    tr = h.on_beat(11.0)
    assert (tr.to, tr.reason) == ("dead", "beat_stale")
    assert tr.beat_age_secs == 11.0


def test_health_slo_pressure_hysteresis():
    h = ReplicaHealth(0, slo_p99_ms=100.0)
    h.on_success()
    tr = h.on_pressure(150.0)
    assert (tr.to, tr.reason) == ("degraded", "slo_pressure")
    assert h.on_pressure(90.0) is None   # within hysteresis band: stays
    tr = h.on_pressure(70.0)             # < 0.8 × SLO: recovers
    assert (tr.to, tr.reason) == ("ready", "recovered")


def test_health_drain_then_readmit_cycle():
    h = ReplicaHealth(0, suspect_after=1, dead_after=2)
    h.on_success()
    h.on_failure()
    h.on_failure()
    assert h.state == "dead"
    assert h.drain().to == "draining"
    assert h.on_failure() is None        # draining absorbs failures
    tr = h.readmit()
    assert (tr.to, tr.reason) == ("warming", "readmit")
    assert h.failures == 0 and h.beat_age is None
    assert h.on_success().to == "ready"


# ---------------------------------------------------------------------------
# replica choice + small helpers
# ---------------------------------------------------------------------------

def _fleet_health(states):
    out = {}
    for rid, state in enumerate(states):
        h = ReplicaHealth(rid)
        h.state = state
        out[rid] = h
    return out


def test_pick_replica_least_outstanding():
    health = _fleet_health(["ready", "ready", "ready"])
    assert pick_replica(health, {0: 3, 1: 1, 2: 2}) == 1
    assert pick_replica(health, {0: 1, 1: 1, 2: 2}) == 0  # tie → low rid


def test_pick_replica_exclude_is_preference_not_veto():
    health = _fleet_health(["ready", "ready", "dead"])
    assert pick_replica(health, {0: 0, 1: 5}, exclude=(0,)) == 1
    # every routable replica already tried: still goes somewhere
    assert pick_replica(health, {0: 0, 1: 5}, exclude=(0, 1)) == 0


def test_pick_replica_fallback_and_exhaustion():
    health = _fleet_health(["warming", "dead", "draining"])
    assert pick_replica(health, {}) == 0      # warming is the fallback
    health = _fleet_health(["dead", "draining"])
    assert pick_replica(health, {}) is None


def test_percentile_and_confidence_helpers():
    assert percentile_ms([]) is None
    assert percentile_ms([5.0]) == 5.0
    assert percentile_ms(list(range(1, 101)), q=99.0) == 99
    assert percentile_ms([3.0, 1.0, 2.0], q=50.0) == 2.0
    assert top1_confidence(np.array([0.0, 0.0])) == pytest.approx(0.5)
    assert top1_confidence(np.array([100.0, 0.0])) == pytest.approx(1.0)
    assert top1_confidence(np.array([np.nan, 1.0])) == 0.0  # poisoned
    assert top1_confidence(np.array([])) == 0.0


# ---------------------------------------------------------------------------
# canary controller (fake clock throughout)
# ---------------------------------------------------------------------------

def _canary_cfg(**kw):
    kw.setdefault("canary_fraction", 0.25)   # ceil(0.25 × 3) = 1 canary
    kw.setdefault("canary_window_secs", 10.0)
    kw.setdefault("canary_min_samples", 2)
    kw.setdefault("canary_confirm_secs", 30.0)
    return _rcfg(**kw)


def test_canary_start_pins_fraction_and_baseline():
    c = CanaryController(_canary_cfg(), initial_step=2)
    rows, pins = c.observe_commit(4, healthy=[0, 1, 2], all_ids=[0, 1, 2],
                                  now=0.0)
    assert rows[0]["action"] == "start" and rows[0]["step"] == 4
    assert rows[0]["canary"] == [0]      # healthy-sorted prefix
    # canary pinned forward, the rest re-pinned to the incumbent
    assert sorted(pins) == [(0, 4), (1, 2), (2, 2)]
    # a second commit observation while active is a no-op
    assert c.observe_commit(5, [0, 1, 2], [0, 1, 2], 1.0) == ([], [])


def test_canary_always_keeps_a_control_arm():
    # even an absurd fraction leaves one baseline replica to compare
    # against — an all-canary rollout is just an ungated swap
    c = CanaryController(_canary_cfg(canary_fraction=1.0), initial_step=2)
    rows, pins = c.observe_commit(4, [0, 1, 2], [0, 1, 2], now=0.0)
    assert rows[0]["canary"] == [0, 1]
    assert (2, 2) in pins


def test_canary_promote_after_clean_window():
    c = CanaryController(_canary_cfg(), initial_step=2)
    _, pins = c.observe_commit(4, [0, 1, 2], [0, 1, 2], now=0.0)
    canary = {r for r, s in pins if s == 4}
    for rid in canary:
        c.observe_completion(rid, 4, 10.0, 0.9)
        c.observe_completion(rid, 4, 12.0, 0.9)
    for rid in {0, 1, 2} - canary:
        c.observe_completion(rid, 2, 11.0, 0.9)
        c.observe_completion(rid, 2, 9.0, 0.9)
    assert c.tick(5.0) == ([], [])       # window not elapsed
    rows, pins = c.tick(10.5)
    assert rows[0]["action"] == "promote" and not rows[0]["rollback"]
    assert c.fleet_step == 4 and c.active is None
    assert sorted(pins) == [(0, 4), (1, 4), (2, 4)]  # fleet-wide


def test_canary_p99_regression_rolls_back_and_remembers():
    c = CanaryController(_canary_cfg(canary_p99_ratio=2.0), initial_step=2)
    _, pins = c.observe_commit(4, [0, 1, 2], [0, 1, 2], now=0.0)
    canary = {r for r, s in pins if s == 4}
    for rid in canary:
        for _ in range(3):
            c.observe_completion(rid, 4, 500.0, 0.9)   # regressed arm
    for rid in {0, 1, 2} - canary:
        for _ in range(3):
            c.observe_completion(rid, 2, 10.0, 0.9)
    rows, pins = c.tick(10.5)
    assert rows[0]["action"] == "rollback" and rows[0]["rollback"]
    assert rows[0]["reason"] == "p99_regression"
    assert rows[0]["p99_canary_ms"] >= rows[0]["p99_base_ms"]
    assert c.fleet_step == 2 and 4 in c.bad_steps
    assert sorted(pins) == [(r, 2) for r in sorted(canary)]  # back to 2
    # a bad step never restarts a canary
    assert c.observe_commit(4, [0, 1, 2], [0, 1, 2], 20.0) == ([], [])


def test_canary_confidence_collapse_rolls_back():
    c = CanaryController(_canary_cfg(canary_conf_drop=0.2), initial_step=2)
    _, pins = c.observe_commit(4, [0, 1, 2], [0, 1, 2], now=0.0)
    canary = {r for r, s in pins if s == 4}
    for rid in canary:
        for _ in range(3):
            c.observe_completion(rid, 4, 10.0, 0.3)    # garbage checkpoint
    for rid in {0, 1, 2} - canary:
        for _ in range(3):
            c.observe_completion(rid, 2, 10.0, 0.9)
    rows, _ = c.tick(10.5)
    assert rows[0]["reason"] == "confidence_regression"
    assert rows[0]["rollback"] and 4 in c.bad_steps


def test_canary_no_confirm_rolls_back():
    # the canary replica never served the new step (gate held, replica
    # wedged, checkpoint unreadable): after confirm_secs the step is
    # condemned without latency evidence
    c = CanaryController(_canary_cfg(canary_confirm_secs=30.0),
                         initial_step=2)
    c.observe_commit(4, [0, 1, 2], [0, 1, 2], now=0.0)
    assert c.tick(29.0) == ([], [])
    rows, _ = c.tick(31.0)
    assert rows[0]["reason"] == "no_confirm" and rows[0]["rollback"]


def test_canary_ping_observation_confirms_but_never_samples():
    # a canary starved of regular traffic confirms its swap through the
    # health ping's pong step (observe_step); the verdict's latency and
    # confidence evidence still comes only from real completions, so a
    # ping-confirmed-but-unsampled canary rides the starved-promote
    # grace, never a latency comparison against nothing
    cfg = _canary_cfg(canary_fraction=1.0, canary_min_samples=2,
                      canary_window_secs=10.0, canary_confirm_secs=30.0)
    c = CanaryController(cfg, initial_step=2)
    _, pins = c.observe_commit(4, [0, 1, 2], [0, 1, 2], now=0.0)
    canary = sorted(r for r, s in pins if s == 4)
    assert c.unconfirmed == canary
    # traffic concentrates on the first canary; the second only pings
    c.observe_completion(canary[0], 4, 10.0, 0.9)
    c.observe_completion(canary[0], 4, 12.0, 0.9)
    assert c.unconfirmed == canary[1:]
    c.observe_step(canary[1], 2)          # stale pong: not yet swapped
    assert c.unconfirmed == canary[1:]
    c.observe_step(canary[1], 4)          # pong at the canary step
    assert c.unconfirmed == []
    assert len(c.active.c_lat) == 2       # pings contributed no samples
    # control arm never sampled → starved-promote grace, not no_confirm
    assert c.tick(31.0) == ([], [])
    rows, _ = c.tick(41.0)
    assert rows[0]["action"] == "promote" and c.fleet_step == 4


def test_canary_starved_promotes_after_grace():
    # confirmed but traffic died before min_samples accumulated: promote
    # after window + confirm grace instead of wedging forever
    cfg = _canary_cfg(canary_min_samples=50, canary_window_secs=10.0,
                      canary_confirm_secs=30.0)
    c = CanaryController(cfg, initial_step=2)
    _, pins = c.observe_commit(4, [0, 1, 2], [0, 1, 2], now=0.0)
    for rid, s in pins:
        if s == 4:
            c.observe_completion(rid, 4, 10.0, 0.9)
    assert c.tick(15.0) == ([], [])
    rows, _ = c.tick(41.0)
    assert rows[0]["action"] == "promote" and c.fleet_step == 4


def test_canary_single_replica_promotes_directly():
    c = CanaryController(_canary_cfg(), initial_step=2)
    rows, pins = c.observe_commit(4, [0], [0], now=0.0)
    assert rows[0]["action"] == "promote"
    assert rows[0]["reason"] == "single_replica"
    assert pins == [(0, 4)] and c.fleet_step == 4 and c.active is None


# ---------------------------------------------------------------------------
# admission (no threads: submit() decides under the lock)
# ---------------------------------------------------------------------------

def _ready_router(cfg, nreplicas=2):
    clients = {rid: object() for rid in range(nreplicas)}
    router = Router(cfg, clients, image_shape=(4,), image_dtype=np.float32)
    for h in router.health.values():
        h.on_success()
    return router


def test_admission_sheds_past_queue_threshold():
    router = _ready_router(_rcfg(shed_queue_ms=100.0))
    router._ewma_ms = 50.0
    router.outstanding[0] = 4            # est: 4 × 50 / 2 = 100ms ≥ 100
    fut = router.submit(np.zeros(4, np.float32))
    assert isinstance(fut.exception(timeout=1), RequestShed)
    assert router.shed == 1 and router.requests == 0


def test_admission_degrades_unpinned_traffic_first():
    router = _ready_router(_rcfg(shed_queue_ms=10_000.0,
                                 degrade_queue_ms=50.0,
                                 degrade_variant="int8"))
    router._ewma_ms = 50.0
    router.outstanding[0] = 4            # est 100ms: past degrade only
    router.submit(np.zeros(4, np.float32))
    assert router.degraded == 1
    assert router._intake.get_nowait().variant == "int8"
    # a request that PINNED its variant is never rewritten
    router.submit(np.zeros(4, np.float32), variant="f32")
    assert router.degraded == 1
    assert router._intake.get_nowait().variant == "f32"


def test_admission_accepts_under_threshold():
    router = _ready_router(_rcfg(shed_queue_ms=100.0,
                                 degrade_queue_ms=50.0,
                                 degrade_variant="int8"))
    router._ewma_ms = 10.0
    fut = router.submit(np.zeros(4, np.float32))
    assert router.requests == 1 and router.shed == 0
    assert router.degraded == 0
    assert not fut.done()


# ---------------------------------------------------------------------------
# threaded router against in-memory fake replicas
# ---------------------------------------------------------------------------

class _FakeReplica:
    """In-memory stand-in for wire.TcpReplicaClient: request/ping/reset/
    close, a settable step (the pin/swap stand-in), a settable delay and
    a kill switch."""

    def __init__(self, step=2, delay=0.0, dead=False):
        self.step = step
        self.delay = delay
        self.dead = dead
        self.requests = 0

    def request(self, image, variant, timeout_secs):
        if self.dead:
            raise ReplicaError("connection refused")
        if self.delay:
            time.sleep(self.delay)
        self.requests += 1
        return np.array([4.0, 0.0, 0.0, 0.0], np.float32), self.step

    def ping(self, timeout_secs=2.0):
        if self.dead:
            raise ReplicaError("connection refused")
        return {"pong": True, "step": self.step, "outstanding": 0}

    def reset(self):
        pass

    def close(self):
        pass


def _threaded_cfg(**kw):
    kw.setdefault("workers", 2)
    kw.setdefault("health_interval_secs", 0.05)
    kw.setdefault("hedge_ms", 60)
    kw.setdefault("attempt_timeout_ms", 1000)
    kw.setdefault("request_timeout_ms", 4000)
    kw.setdefault("suspect_after_failures", 1)
    kw.setdefault("dead_after_failures", 3)
    kw.setdefault("row_interval_secs", 3600.0)
    return _rcfg(**kw)


def test_router_dead_replica_costs_zero_client_errors():
    # small service time so outstanding piles up and the least-
    # outstanding policy actually spreads attempts onto the dead replica
    fakes = {0: _FakeReplica(delay=0.005), 1: _FakeReplica(delay=0.005),
             2: _FakeReplica(dead=True)}
    router = Router(_threaded_cfg(), fakes, (4,), np.float32).start()
    try:
        futs = [router.submit(np.zeros(4, np.float32)) for _ in range(30)]
        for fut in futs:
            row, step = fut.result(timeout=10.0)
            assert step == 2
        deadline = time.monotonic() + 5.0   # health pings finish the
        while (router.health_state(2) != "dead"      # condemnation
               and time.monotonic() < deadline):
            time.sleep(0.02)
    finally:
        router.close()
    rep = router.report()
    assert rep["completed"] == 30 and rep["errors"] == 0
    assert rep["retries"] + rep["hedges"] >= 1   # the dead replica's
    assert router.health_state(2) == "dead"      # attempts were absorbed
    assert fakes[0].requests + fakes[1].requests >= 30


def test_router_hedge_rescues_a_stalled_attempt():
    # replica 0 answers but far slower than hedge_ms: the hedge lands on
    # replica 1 and resolves the request first
    fakes = {0: _FakeReplica(delay=1.0), 1: _FakeReplica()}
    router = Router(_threaded_cfg(hedge_ms=50, workers=2), fakes,
                    (4,), np.float32).start()
    try:
        t0 = time.monotonic()
        row, step = router.submit(np.zeros(4, np.float32)) \
            .result(timeout=10.0)
        wall = time.monotonic() - t0
    finally:
        router.close()
    assert wall < 1.0                    # did not wait out the slow arm
    assert router.report()["hedges"] >= 1


def test_router_canary_promote_end_to_end_in_memory():
    # pins executed by flipping the fake's step — the swapper stand-in;
    # small bursts of concurrent traffic feed BOTH canary arms
    fakes = {r: _FakeReplica(step=2, delay=0.002) for r in range(3)}

    def pin(rid, step):
        fakes[rid].step = step

    cfg = _threaded_cfg(canary_fraction=0.25, canary_window_secs=0.4,
                        canary_min_samples=2, canary_confirm_secs=5.0)
    router = Router(cfg, fakes, (4,), np.float32,
                    committed_steps_fn=lambda: [2, 4], pin_fn=pin,
                    initial_step=2).start()
    try:
        deadline = time.monotonic() + 15.0
        while (router.canary.fleet_step != 4
               and time.monotonic() < deadline):
            futs = [router.submit(np.zeros(4, np.float32))
                    for _ in range(6)]
            for fut in futs:
                fut.result(timeout=5.0)
            time.sleep(0.01)
    finally:
        router.close()
    assert router.canary.fleet_step == 4
    assert all(f.step == 4 for f in fakes.values())  # promoted fleet-wide
    assert router.report()["errors"] == 0


def test_router_canary_rollback_never_reaches_baseline():
    fakes = {r: _FakeReplica(step=2) for r in range(3)}

    def pin(rid, step):
        # the p99-regressing checkpoint: any replica pinned to step 4
        # becomes slow (DRT_FAULT_SERVE_SLOW_MS=…@4 in the real smoke)
        fakes[rid].step = step
        fakes[rid].delay = 0.2 if step == 4 else 0.0

    # enough workers that the slow canary attempt cannot head-of-line
    # block the control arm (which would inflate baseline p99 and mask
    # the regression)
    cfg = _threaded_cfg(canary_fraction=0.25, canary_window_secs=0.5,
                        canary_min_samples=3, canary_confirm_secs=8.0,
                        canary_p99_ratio=2.0, hedge_ms=5000, workers=8)
    router = Router(cfg, fakes, (4,), np.float32,
                    committed_steps_fn=lambda: [2, 4], pin_fn=pin,
                    initial_step=2).start()
    try:
        deadline = time.monotonic() + 20.0
        while (4 not in router.canary.bad_steps
               and router.canary.fleet_step != 4   # promote = failure,
               and time.monotonic() < deadline):   # fail fast
            futs = [router.submit(np.zeros(4, np.float32))
                    for _ in range(6)]
            for fut in futs:
                fut.result(timeout=5.0)
            time.sleep(0.01)
    finally:
        router.close()
    assert 4 in router.canary.bad_steps
    assert router.canary.fleet_step == 2
    # rollback re-pinned every canary to the incumbent; with the bad
    # step remembered, NO replica ends pinned at 4
    assert all(f.step == 2 for f in fakes.values())


def test_router_close_fails_stuck_requests():
    fakes = {0: _FakeReplica(dead=True)}
    router = Router(_threaded_cfg(request_timeout_ms=60_000,
                                  attempt_timeout_ms=60_000), fakes,
                    (4,), np.float32).start()
    fut = router.submit(np.zeros(4, np.float32))
    time.sleep(0.1)
    router.close()
    with pytest.raises(RouteError):
        fut.result(timeout=1.0)


# ---------------------------------------------------------------------------
# load shapes (coordinated-omission-free arrival schedules)
# ---------------------------------------------------------------------------

def test_arrival_times_monotone_and_bounded():
    for shape in LOAD_SHAPES:
        t = arrival_times(shape, qps=50.0, duration_secs=4.0)
        assert np.all(np.diff(t) >= -1e-9), shape
        assert t[0] >= 0.0 and t[-1] <= 4.0 + 1e-6, shape
        # total offered mass stays the same order as qps × duration
        assert 0.5 * 200 <= len(t) <= 2.0 * 200, (shape, len(t))


def test_arrival_times_steady_is_uniform():
    t = arrival_times("steady", qps=100.0, duration_secs=2.0)
    assert len(t) == 200
    np.testing.assert_allclose(np.diff(t), 0.01, atol=1e-3)


def test_arrival_times_spike_concentrates_midwindow():
    t = arrival_times("spike", qps=100.0, duration_secs=10.0)
    mid = np.sum((t >= 4.5) & (t < 5.5))
    edge = np.sum(t < 1.0)
    assert mid > 3.0 * edge              # 4× rate across the middle tenth


def test_arrival_times_rejects_unknown_shape():
    with pytest.raises(ValueError):
        arrival_times("sawtooth", 10.0, 1.0)


class _InstantServer:
    image_shape = (2, 2, 3)
    image_dtype = np.dtype(np.float32)

    def __init__(self):
        self.submitted = 0

    def submit(self, image, variant=None):
        self.submitted += 1
        fut = Future()
        fut.set_result((np.zeros(4, np.float32), 0))
        return fut


def test_run_open_loop_reports_shape():
    server = _InstantServer()
    rep = run_open_loop(server, qps=200.0, duration_secs=0.25,
                        shape="burst")
    assert rep["shape"] == "burst"
    assert rep["offered"] == server.submitted
    assert rep["completed"] == rep["offered"] and rep["failed"] == 0


# ---------------------------------------------------------------------------
# fault knobs + fleet plumbing (pure FS)
# ---------------------------------------------------------------------------

def test_serve_faults_env_parsing_and_scoping():
    from distributed_resnet_tensorflow_tpu.resilience.faultinject import \
        ServeFaults
    env = {"DRT_FAULT_SERVE_WEDGE_AT_BATCH": "1:5",
           "DRT_FAULT_SERVE_SLOW_MS": "250@4"}
    f0 = ServeFaults.from_env(0, env)
    assert f0.wedge_at_batch is None          # wedge scoped to replica 1
    assert (f0.slow_ms, f0.slow_from_step) == (250.0, 4)
    f1 = ServeFaults.from_env(1, env)
    assert f1.wedge_at_batch == 5 and f1.armed
    assert ServeFaults.from_env(0, {}).armed is False


def test_serve_faults_slow_gates_on_serving_step(monkeypatch):
    from distributed_resnet_tensorflow_tpu.resilience import faultinject
    naps = []
    monkeypatch.setattr(faultinject.time, "sleep", naps.append)
    f = faultinject.ServeFaults(slow_ms=250.0, slow_from_step=4)
    f.maybe_fire(1, serving_step=2)           # below the poisoned step
    assert naps == []
    f.maybe_fire(2, serving_step=4)
    assert naps == [0.25]
    # @0 means "always" but never fires on fresh-init (-1) serving
    g = faultinject.ServeFaults(slow_ms=100.0, slow_from_step=0)
    g.maybe_fire(1, serving_step=-1)
    assert naps == [0.25]


def test_write_pin_atomic_and_gate_holds_without_pin(tmp_path):
    from distributed_resnet_tensorflow_tpu.serve.fleet import (pin_path,
                                                               write_pin)
    from distributed_resnet_tensorflow_tpu.serve.swap import \
        CheckpointSwapper
    write_pin(str(tmp_path), 0, 4)
    path = pin_path(str(tmp_path), 0)
    assert json.load(open(path)) == {"target_step": 4}
    assert not os.path.exists(path + ".tmp")
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    gate = str(tmp_path / "serve-r1" / "SWAP_CONTROL.json")
    swapper = CheckpointSwapper(str(ckpt), gate_path=gate)
    # armed gate with NO pin: hold — never chase the newest commit (the
    # unvalidated-checkpoint leak the canary exists to prevent)
    (ckpt / "7").mkdir()
    assert swapper.poll_once() is None
    # pinned ahead of the directory (pin raced the commit): keep polling
    write_pin(str(tmp_path), 1, 9)
    assert swapper.poll_once() is None
    assert swapper._gate_applied is None


def test_fleet_replica_dir_layout_matches_server():
    # fleet.replica_dir and server.serve_stream_dir must agree — the pin
    # the supervisor writes is the file the replica's swapper reads
    from distributed_resnet_tensorflow_tpu.serve.fleet import (pin_path,
                                                               replica_dir)
    assert replica_dir("/r", 3) == "/r/serve-r3"
    assert pin_path("/r", 3) == "/r/serve-r3/SWAP_CONTROL.json"


# ---------------------------------------------------------------------------
# slow tier: a REAL fleet (subprocess replicas) killed and recovered
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fleet_kill_and_recover_subprocess(tmp_path):
    """SIGKILL a real serving replica process mid-fleet: the router
    condemns it, the supervisor replaces it (kill → respawn → warm →
    readmit rows), and requests keep succeeding throughout with zero
    client-visible errors. The full chaos story (canary rollback on a
    seeded p99 regression, baseline purity) is
    scripts/serve_fleet_smoke.sh."""
    import signal

    from distributed_resnet_tensorflow_tpu.serve.fleet import FleetSupervisor
    from distributed_resnet_tensorflow_tpu.serve.server import \
        serve_image_spec
    from distributed_resnet_tensorflow_tpu.serve.wire import TcpReplicaClient
    from distributed_resnet_tensorflow_tpu.utils.config import get_preset

    cfg = get_preset("smoke")
    cfg.model.resnet_size = 8
    cfg.model.compute_dtype = "float32"
    cfg.data.image_size = 8
    cfg.train.batch_size = 16
    cfg.data.eval_batch_size = 16
    cfg.mesh.data = -1  # replicas inherit the suite's 8 virtual devices
    cfg.log_root = str(tmp_path)
    cfg.checkpoint.directory = os.path.join(str(tmp_path), "ckpt")
    cfg.serve.max_queue_delay_ms = 5.0
    cfg.route.replicas = 2
    cfg.route.health_interval_secs = 0.3
    cfg.route.watch_interval_secs = 0.3
    cfg.route.replica_grace_secs = 2.0
    cfg.route.suspect_after_failures = 1
    cfg.route.dead_after_failures = 2

    fleet = FleetSupervisor(cfg)
    router = None
    try:
        fleet.start()  # no checkpoint: replicas serve fresh-init params
        clients = {rid: TcpReplicaClient("127.0.0.1", port)
                   for rid, port in fleet.ports.items()}
        shape, dtype = serve_image_spec(cfg)
        router = Router(cfg.route, clients, shape, dtype,
                        beats_dir=fleet.beats_dir,
                        initial_step=fleet.pinned_step).start()
        fleet.attach_router(router)
        fleet.start_watch()
        img = np.zeros(shape, dtype)
        for _ in range(4):
            router.submit(img).result(timeout=30.0)

        victim_pid = fleet.procs[0].pid
        os.kill(victim_pid, signal.SIGKILL)
        # traffic keeps flowing while the watchdog replaces replica 0
        deadline = time.monotonic() + 180.0
        while time.monotonic() < deadline:
            router.submit(img).result(timeout=30.0)
            if (fleet.replaces >= 1
                    and router.health_state(0) in ("ready", "degraded")):
                break
            time.sleep(0.2)
        assert fleet.replaces >= 1, "watchdog never replaced the replica"
        assert router.health_state(0) in ("ready", "degraded"), \
            "killed replica never readmitted"
        assert fleet.procs[0].pid != victim_pid
        # the replacement serves: force a request through replica 0
        pong = clients[0].ping(timeout_secs=5.0)
        assert pong.get("pong") is True
        assert router.report()["errors"] == 0
    finally:
        if router is not None:
            router.close()
        fleet.stop()
