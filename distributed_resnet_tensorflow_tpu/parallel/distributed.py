"""Multi-host bootstrap.

Replaces the reference's cluster bring-up — ``tf.train.ClusterSpec`` +
``tf.train.Server`` grpc bootstrap (reference resnet_cifar_main.py:364-380)
and Horovod's ``hvd.init()`` MPI bootstrap (reference
resnet_cifar_main_horovod.py:342) — with ``jax.distributed.initialize`` over
DCN: one process per TPU host, every process runs the same SPMD program.

Topology can come from explicit config, from SLURM env vars (the reference's
launchers derived ps/worker host lists from ``scontrol show hostnames``,
reference scripts/run_dist_tf_daint.sh:30-76 — here SLURM integration is just
reading env), or from TPU-pod metadata (jax autodetects when args are None).
"""
from __future__ import annotations

import logging
import os
import threading
from typing import Optional

import jax

log = logging.getLogger(__name__)


def initialize_from_config(mesh_cfg) -> None:
    """Initialize the distributed runtime if the config asks for >1 process."""
    if mesh_cfg.num_processes <= 1 and not mesh_cfg.coordinator_address:
        return
    initialize(
        coordinator_address=mesh_cfg.coordinator_address or None,
        num_processes=mesh_cfg.num_processes or None,
        process_id=mesh_cfg.process_id,
    )


def _enable_cpu_collectives() -> None:
    """Pick a real cross-process collectives backend for the CPU platform.

    Without one, jaxlib's CPU collectives are single-process only
    ("Multiprocess computations aren't implemented on the CPU backend");
    gloo is the multi-process implementation. jax 0.9 already defaults the
    flag to gloo, so this acts only when something cleared it, and it must
    act before the backend initializes. No-op on non-CPU platforms."""
    if jax.config.jax_cpu_collectives_implementation is not None:
        return  # the default, or the operator's own choice
    platforms = jax.config.jax_platforms or ""
    if platforms.split(",")[0].strip() != "cpu":
        return
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    log.info("CPU platform multi-process: collectives set to gloo")


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Idempotent `jax.distributed.initialize` with SLURM fallback.

    SLURM env contract (successor of the reference's TF_NUM_PS/TF_NUM_WORKERS
    env contract, reference scripts/run_dist_tf_daint.sh:4-27):
      SLURM_NTASKS → num_processes, SLURM_PROCID → process_id,
      SLURM_STEP_NODELIST first node:8476 → coordinator.
    """
    _enable_cpu_collectives()
    if coordinator_address is None and "SLURM_NTASKS" in os.environ and \
            int(os.environ["SLURM_NTASKS"]) > 1:
        num_processes = int(os.environ["SLURM_NTASKS"])
        process_id = int(os.environ["SLURM_PROCID"])
        nodelist = os.environ.get("SLURM_STEP_NODELIST",
                                  os.environ.get("SLURM_NODELIST", ""))
        first = _first_slurm_node(nodelist)
        coordinator_address = f"{first}:8476"
    from ..resilience.retry import retry_call

    def _preinitialized(e: BaseException) -> bool:
        # jax spells it "already initialized" in some paths and
        # "should only be called once" in State.initialize
        msg = str(e).lower()
        return "already" in msg or "only be called once" in msg

    def attempt():
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id)
        except Exception as e:
            # jax assigns its global client BEFORE connect(); without this
            # reset a retry would die on "should only be called once"
            # instead of re-attempting the connect (verified against
            # jax._src.distributed.State.initialize). NEVER shut down a
            # runtime that was initialized before our call, though — that
            # would tear down a live cluster connection
            if not _preinitialized(e):
                try:
                    jax.distributed.shutdown()
                except Exception:  # partially-initialized — best effort
                    pass
            raise

    try:
        # bounded retry: non-chief processes race the coordinator's bind at
        # job start, and transient DNS/connect failures are routine on big
        # clusters — the reference's grpc bootstrap just died there
        retry_call(
            attempt,
            retries=3, base_delay=1.0, max_delay=15.0,
            retry_on=(RuntimeError, ConnectionError, OSError),
            giveup=_preinitialized,
            description="jax.distributed.initialize")
        log.info("jax.distributed initialized: process %d/%d @ %s",
                 jax.process_index(), jax.process_count(), coordinator_address)
    except RuntimeError as e:  # already initialized before our call
        if not _preinitialized(e):
            raise
        log.info("jax.distributed already initialized")


def _first_slurm_node(nodelist: str) -> str:
    """Expand the first hostname from a SLURM nodelist like 'nid0[1234-1241]'.

    Minimal re-implementation of what the reference got from
    ``scontrol show hostnames`` (reference scripts/run_dist_tf_daint.sh:35).
    """
    if "[" not in nodelist:
        return nodelist.split(",")[0].strip()
    prefix, rest = nodelist.split("[", 1)
    spec = rest.split("]", 1)[0]
    first = spec.split(",")[0].split("-")[0]
    return f"{prefix}{first}"


def is_chief() -> bool:
    """Process 0 — successor of the reference's ``is_chief = task_index == 0``
    (reference resnet_cifar_main.py:323-335)."""
    return jax.process_index() == 0


# ---------------------------------------------------------------------------
# Elastic mesh generations (resilience/elastic.py; docs/resilience.md).
# A mesh GENERATION is one (membership, coordinator) epoch of the job.
# Every generation gets its own coordinator endpoint — the old service may
# linger half-dead on the chief (its shutdown blocks on the lost peer and
# is abandoned, below), so generation g must bind somewhere fresh.
# ---------------------------------------------------------------------------

def elastic_coordinator(base_address: str, generation: int,
                        port_stride: int = 7) -> str:
    """The epoch-suffixed coordinator contract: generation ``g`` lives at
    the base coordinator's host, port ``base + g * port_stride``.
    Deterministic from (base, g) alone so survivors and rejoining peers
    derive the SAME endpoint from the shared generation record without
    any further coordination. The chief (worker 0) hosts every
    generation's coordinator — a reshard that loses worker 0 is
    infeasible and falls back to exit 75."""
    host, _, port = base_address.rpartition(":")
    if not host:
        raise ValueError(
            f"coordinator_address {base_address!r} has no host:port — "
            "elastic generations need an explicit base endpoint")
    return f"{host}:{int(port) + generation * port_stride}"


def teardown_for_reshard(timeout_secs: float = 5.0) -> None:
    """Tear down a distributed runtime whose peers may be DEAD so this
    process can re-``initialize`` over the survivors.

    ``jax.distributed.shutdown`` is a barrier — against a dead peer the
    client's shutdown blocks forever, so it runs in an abandoned daemon
    thread (it touches only the local client/service references, never
    jax's global state, so giving up on it is safe). The main thread then
    resets ``jax._src.distributed.global_state`` by hand and drops every
    backend + compilation cache: all live ``jax.Array``s and jitted
    callables die with the old backend, which is why the elastic runtime
    rebuilds the Trainer and restores from the last committed checkpoint
    after calling this (the fields reset below are jax 0.9.0's State)."""
    from jax._src import distributed as _dist
    state = _dist.global_state
    client, service = state.client, state.service

    def _shutdown():
        for leg in (client, service):
            if leg is None:
                continue
            try:
                leg.shutdown()
            except Exception as e:  # dead-peer barrier errors — expected
                log.info("distributed teardown leg: %s: %s",
                         type(e).__name__, e)

    t = threading.Thread(target=_shutdown, daemon=True,
                         name="drt-dist-teardown")
    t.start()
    t.join(timeout=timeout_secs)
    if t.is_alive():
        log.warning("distributed shutdown still blocked on dead peers "
                    "after %.1fs — abandoning it (daemon thread)",
                    timeout_secs)
    state.client = None
    state.service = None
    state.coordinator_address = None
    state.process_id = 0
    state.num_processes = 1
    state.preemption_sync_manager = None
    state.partition_index = None
    import jax.extend.backend
    jax.extend.backend.clear_backends()
    jax.clear_caches()


def reinitialize(coordinator_address: str, num_processes: int,
                 process_id: int) -> None:
    """Re-enter the distributed runtime for a new mesh generation after
    ``teardown_for_reshard`` — the plain ``initialize`` ladder (same
    bounded retry; survivors race the chief's fresh bind exactly like a
    job start). Also the REJOINER's first init: a rejoiner has touched
    the local backend before this (device-count probes while waiting in
    the barrier), and ``jax.distributed.initialize`` refuses to run with
    live backends — drop them first (idempotent after a teardown)."""
    import jax.extend.backend
    jax.extend.backend.clear_backends()
    jax.clear_caches()
    initialize(coordinator_address=coordinator_address,
               num_processes=num_processes, process_id=process_id)
