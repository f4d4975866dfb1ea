"""The comparison that decides ``correct``.

The program's readings come from the timed path itself: the trainer that
the window goes on to drive, its compiled step, its stager and its feed,
at the timed batch. The reference's come from ``reference/follow.py``.
Numbers compared, each against a limit of its own from
``benchmark/limits/<cell>.json``:

* ``loss_<n>``: |loss - reference| / |reference| at each boundary;
* ``grad_gap``: over the leaves, the widest gap between the program's and
  the reference's norm of the optimizer's first moment after the first
  boundary (after one step that is the gradient as the optimizer got it),
  against the reference's norm of that leaf or of the median leaf,
  whichever is larger;
* ``change_gap``: the same for the norm of the parameters' change after the
  last boundary, over the leaves whose first reference gradient is not
  under a thousandth of the median leaf's (those move by round-off alone)
  and the leaves that a rule of the family moves and no gradient does
  (``ruled`` in the reference's readings);
* ``grad_mid``, ``change_mid``: the median leaf's gap instead of the worst;
* ``grad_dir``, ``change_dir``: the median leaf's gap between the inner
  products of the two vectors with one seeded probe vector, on the same
  scale. A norm moves in second order with an error that is not aligned
  with the vector (rounding, a wrong row, a wrong flip); this moves in
  first order, about as the norm of the two vectors' difference.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List, Sequence, Tuple


def boundaries(k: int) -> List[int]:
    """Steps after which the program can be read: the first three steps of
    a loop that dispatches one step at a time, one dispatch of a fused loop."""
    return [1, 2, 3] if k == 1 else [k]


def gaps(mine: Dict[str, float], ref: Dict[str, float], leaves: Sequence[str],
         scale: Dict[str, float] = None) -> Dict[str, float]:
    """Per leaf: |program's number - reference's| over the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    scale = scale or ref
    floor = statistics.median(scale[n] for n in leaves)
    out = {}
    for n in leaves:
        gap = abs(mine[n] - ref[n]) / max(scale[n], floor, 1e-30)
        out[n] = gap if math.isfinite(gap) else float("inf")
    return out


def compare(mine: dict, ref: dict) -> Tuple[Dict[str, float], Dict[str, str]]:
    """Numbers by name, and for the worst-leaf gaps the leaf that set them."""
    numbers, where = {}, {}
    for n in sorted(ref["loss"], key=int):
        a, b = mine["loss"][n], ref["loss"][n]
        gap = abs(a - b) / abs(b)
        numbers[f"loss_{n}"] = gap if math.isfinite(gap) else float("inf")
    leaves = sorted(ref["moment"]["norm"])
    g1 = ref["grad1"]
    floor = statistics.median(g1.values()) * 1e-3
    moving = [n for n in leaves if g1[n] >= floor or n in ref.get("ruled", ())]
    for name, key, over in (("grad", "moment", leaves), ("change", "change", moving)):
        norm_gaps = gaps(mine[key]["norm"], ref[key]["norm"], over)
        where[f"{name}_gap"] = max(norm_gaps, key=norm_gaps.get)
        numbers[f"{name}_gap"] = norm_gaps[where[f"{name}_gap"]]
        numbers[f"{name}_mid"] = statistics.median(norm_gaps.values())
        # the probes' inner products differ by about the norm of the
        # difference of the two vectors: first order in any error
        numbers[f"{name}_dir"] = statistics.median(
            gaps(mine[key]["probe"], ref[key]["probe"], over, ref[key]["norm"]).values())
    return numbers, where


def verdict(numbers: Dict[str, float], limits: dict) -> Tuple[bool, List[List]]:
    """correct, and each number compared beside its limit."""
    rows, ok = [], True
    skipped = limits.get("not_compared", {})
    for name, value in numbers.items():
        if name in skipped:
            continue
        if name not in limits["limits"]:
            raise KeyError(f"no limit for {name!r} in the cell's limits file")
        limit = limits["limits"][name]
        rows.append([name, value, limit])
        ok = ok and value <= limit
    return ok, rows
