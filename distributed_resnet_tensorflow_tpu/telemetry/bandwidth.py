"""Per-fabric achieved-bandwidth catalog (``results/bandwidth/<fabric>.json``).

A small per-fabric document of achieved collective bandwidth keyed by
the reduce-axis set, which the what-if planner (telemetry/planner.py)
costs candidate layouts against and the ``plan-drift`` gate phase holds
to a live micro-probe. This module reads it; nothing in the tree writes
one since the collective probe went with the bucketed exchange (PR 31):
a catalog is measured outside and placed in the directory, and without
one the planner uses its reference row.

A *fabric* is the hardware the numbers are valid for: platform ×
device kind × global device count (``fabric_id``) — a v4-32's ICI numbers
must never cost a v5e-8 plan, and the virtual-8 CPU mesh the tests/gate
run on gets its own file.

Catalog schema (``schema_version`` 2, documented in docs/planner.md)::

    {
     "schema_version": 2,
     "fabric": "cpu-8",            # fabric_id() of the measuring run
     "platform": "cpu",
     "device_kind": "cpu",
     "devices": 8,
     "axes": {                     # keyed by the reduce-axis set
      "data+fsdp": {
       "bytes_per_sec": 4.1e8,     # best standalone WIRE bytes/sec seen
       "latency_secs": 2.3e-4,     # smallest per-collective cost seen
       "samples": 12,
       "min_wire_bytes": 20480,    # payload range the numbers came from
       "max_wire_bytes": 4194304
      },
      "data+fsdp:intra": {         # tier rows (v2): the fast intra-host /
       "tier": "intra",            # slow inter-host sub-groups of the
       ...                         # data axis
      }, ...
     }
    }

v1 documents (no tier rows, no ``tier`` field) load unchanged — every
v1 key is a valid v2 flat key.
"""
from __future__ import annotations

import json
import logging
import os
import re
from typing import Optional

log = logging.getLogger(__name__)

SCHEMA_VERSION = 2

#: env override for the catalog directory (tests point it at a tmpdir;
#: multi-user clusters point it at a shared results tree)
DIR_ENV = "DRT_BANDWIDTH_DIR"


def catalog_dir() -> str:
    override = os.environ.get(DIR_ENV)
    if override:
        return override
    repo_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(repo_root, "results", "bandwidth")


def fabric_id(devices=None) -> str:
    """``<platform>-<n>`` (plus the device kind when it says more than
    the platform does): the key deciding which catalog file a
    measurement lands in / a prediction reads from."""
    if devices is None:
        import jax
        devices = jax.devices()
    d0 = devices[0]
    platform = str(getattr(d0, "platform", "unknown")).lower()
    kind = str(getattr(d0, "device_kind", "") or "").lower()
    parts = [platform]
    if kind and kind != platform:
        parts.append(kind)
    parts.append(str(len(devices)))
    return re.sub(r"[^a-z0-9.]+", "-", "-".join(parts)).strip("-")


def catalog_path(fabric: Optional[str] = None) -> str:
    return os.path.join(catalog_dir(), f"{fabric or fabric_id()}.json")


def load_catalog(path: Optional[str] = None,
                 fabric: Optional[str] = None) -> Optional[dict]:
    """The catalog document, or None when absent/unreadable (callers
    fall back to the planner's reference table)."""
    path = path or catalog_path(fabric)
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        log.debug("bandwidth catalog unreadable at %s (%s)", path, e)
        return None
    if not isinstance(doc, dict) or not isinstance(doc.get("axes"), dict):
        log.warning("bandwidth catalog at %s is malformed; ignoring", path)
        return None
    return doc


def lookup(catalog: Optional[dict], axes_sig: str) -> Optional[dict]:
    """The axes entry for a reduce-axis signature (``"data+fsdp"``),
    falling back to the entry sharing the most axis names (a dp_tp
    prediction on a fabric only probed under dp still gets the measured
    order of magnitude rather than nothing). Deterministic: ties break
    on the entry name."""
    if not catalog:
        return None
    axes = catalog.get("axes", {})
    entry = axes.get(axes_sig)
    if entry is not None:
        return entry
    base, _, tier = axes_sig.partition(":")
    if tier:
        # tiered query without a tiered row: the flat row for the same
        # axis set is the honest stand-in
        entry = axes.get(base)
        if entry is not None:
            return entry
    want = set(base.split("+"))
    best = None
    for name in sorted(axes):
        nbase, _, ntier = name.partition(":")
        overlap = len(want & set(nbase.split("+")))
        key = (overlap, 1 if ntier == tier else 0,
               axes[name].get("samples", 0))
        if best is None or key > best[0]:
            best = (key, axes[name])
    return best[1] if best else None
