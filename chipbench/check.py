"""The comparison that decides ``correct``.

The numbers, of which a cell compares those its ``limits`` name, each
against its limit:

- ``loss_gap``: the largest |program loss - reference loss| over the
  first steps, in nats;
- ``gnorm_gap``: |program - reference| global norm of the first
  gradient, over the reference's;
- ``grad_gap``: over parameter paths, the largest gap between the norm
  of the program's first gradient as the optimizer got it (read back
  from its first moment and its clip scale) and the reference's, over
  the larger of the reference's norm of that path and of the median
  path;
- ``change_gap``: the same for the norm of each path's change from its
  initial value after the first steps, leaving out paths whose
  reference gradient is under a thousandth of the median path's (they
  move under Adam by round-off alone) unless the reference's ``update``
  sets them (``reftrain``: a router's selection bias).
"""
from __future__ import annotations

import math
import statistics
from typing import Dict

RULE_SHARE = 1e-3


def _gap(prog: Dict[str, float], ref: Dict[str, float], paths) -> float:
    if not paths:
        return float("nan")
    med = statistics.median(ref[p] for p in paths)
    worst = 0.0
    for p in paths:
        g = abs(prog[p] - ref[p]) / max(ref[p], med)
        if not math.isfinite(g):
            return float("inf")
        worst = max(worst, g)
    return worst


def readings(prog: Dict, ref: Dict) -> Dict[str, float]:
    if set(prog["grad"]) != set(ref["grad"]):
        raise ValueError("program and reference name different parameters")
    paths = sorted(ref["grad"])
    med = statistics.median(ref["grad"][p] for p in paths)
    moved = [p for p in paths if ref["grad"][p] >= RULE_SHARE * med
             or p in ref["updated"]]
    loss = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))
    gnorm = abs(prog["gnorm"] - ref["gnorm"]) / ref["gnorm"]
    return {"loss_gap": loss if math.isfinite(loss) else float("inf"),
            "gnorm_gap": gnorm if math.isfinite(gnorm) else float("inf"),
            "grad_gap": _gap(prog["grad"], ref["grad"], paths),
            "change_gap": _gap(prog["change"], ref["change"], moved)}


def leaf_gaps(prog: Dict, ref: Dict, key: str) -> Dict[str, float]:
    """Each path's gap of ``key`` ("grad" or "change"), as ``_gap``
    measures it."""
    med = statistics.median(ref[key].values())
    return {p: abs(prog[key][p] - r) / max(r, med)
            for p, r in ref[key].items()}


def compare(prog: Dict, ref: Dict, limits: Dict[str, float]) -> Dict:
    r = readings(prog, ref)
    return {k: {"value": r[k], "limit": limits[k]} for k in limits}


def passed(numbers: Dict) -> bool:
    return all(math.isfinite(n["value"]) and n["value"] <= n["limit"]
               for n in numbers.values())
