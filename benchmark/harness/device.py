"""The device gate and the one table of peaks.

A benchmark number is a number from the chip: no accelerator, fewer chips
than the cell asks for, or a ``device_kind`` that ``peaks.json`` does not
list, and the run exits non-zero with no result line."""
from __future__ import annotations

import json
import os


class DeviceError(Exception):
    pass


def peaks_for(kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if kind not in table or kind.startswith("_"):
        raise DeviceError(f"device_kind {kind!r} is not in benchmark/harness/peaks.json: "
                          "add its row with its source before measuring on it")
    return table[kind]


def gate(chips: int):
    """The devices a cell runs on and their peaks, or DeviceError."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise DeviceError("JAX found no accelerator (platform cpu): the benchmark "
                          "prints no number from a CPU")
    if len(devices) < chips:
        raise DeviceError(f"the cell asks for {chips} chips, JAX found {len(devices)}")
    return devices[:chips], peaks_for(devices[0].device_kind)


def describe(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
