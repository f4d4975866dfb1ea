"""Virtual CPU device-mesh env setup, shared by every fake-mesh entry point.

The JAX analog of the reference's "local smoke cluster" trick (reference
scripts/submit_mac_dist.sh:9-39 — 1ps+2wk on localhost CPU): N virtual host
devices via ``--xla_force_host_platform_device_count`` so sharding and
collective paths run without real accelerators. Used by the test conftest,
the local multi-process launcher, and the driver's multi-chip dry run.

Deliberately imports nothing heavy (no jax) — callers set the environment
*before* the JAX backend initializes. A subprocess gets ``JAX_PLATFORMS=cpu``
in its environment (``virtual_cpu_env``); a process that is already running
pins the platform through ``jax.config`` (``force_cpu_platform``), which
holds whatever the inherited ``JAX_PLATFORMS`` says, as long as it runs
before first backend use.
"""
from __future__ import annotations

import os
from typing import Mapping, MutableMapping, Optional


def virtual_cpu_flags(n_devices: int, existing: str = "") -> str:
    """XLA_FLAGS value forcing ``n_devices`` virtual host devices, replacing
    (not merely appending to) any existing device-count flag so a stale
    smaller count can't win."""
    flags = [f for f in existing.split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    return " ".join(flags)


def existing_device_count(xla_flags: str) -> int:
    """Device count from an existing --xla_force_host_platform_device_count
    flag, or 0 when absent/malformed."""
    for f in xla_flags.split():
        if "xla_force_host_platform_device_count" in f and "=" in f:
            try:
                return int(f.split("=", 1)[1])
            except ValueError:
                return 0
    return 0


def virtual_cpu_env(n_devices: int,
                    base: Optional[Mapping[str, str]] = None) -> dict:
    """A copy of ``base`` (default ``os.environ``) set up for an
    ``n_devices``-device virtual CPU platform — for subprocess launches."""
    env = dict(os.environ if base is None else base)
    env["XLA_FLAGS"] = virtual_cpu_flags(n_devices, env.get("XLA_FLAGS", ""))
    env["JAX_PLATFORMS"] = "cpu"
    return env


def apply_virtual_cpu(n_devices: int,
                      env: Optional[MutableMapping[str, str]] = None) -> None:
    """In-place variant for the current process: set XLA_FLAGS and force the
    CPU platform. Call before the JAX backend initializes."""
    target = os.environ if env is None else env
    target["XLA_FLAGS"] = virtual_cpu_flags(
        n_devices, target.get("XLA_FLAGS", ""))
    force_cpu_platform()


def force_cpu_platform() -> None:
    """Pin the platform to CPU through jax.config, before the backend
    initializes — a virtual mesh must never land on an accelerator because
    the caller's environment named one. Lazy jax import so merely importing
    this module stays lightweight."""
    import jax

    jax.config.update("jax_platforms", "cpu")
