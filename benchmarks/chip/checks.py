"""The comparison that decides ``correct``.

Training: the program's first steps against the reference's, by

* ``loss_gap``: the largest relative gap between the two losses of a step;
* ``grad_gap``: over the leaves (stacked leaves per layer), the largest gap
  between the norms of the first clipped gradient, each measured against
  the reference's norm of that leaf or of the median leaf, whichever is
  larger;
* ``update_gap``: the same for the parameters' change over the checked
  steps.

Leaves whose reference gradient is under ``MIN_SHARE`` of the median
leaf's are nought to rounding (a key bias under softmax) and are left out
of both norms' gaps.

Serving: ``token_gap``, the widest gap by which a served token's reference
logit lies below the reference's best at its position.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

MIN_SHARE = 1e-3


def _worst(prog: Mapping[str, float], ref: Mapping[str, float], keys: Sequence[str]) -> float:
    med = statistics.median(ref[k] for k in keys)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def kept_leaves(ref_grad: Mapping[str, float]) -> List[str]:
    med = statistics.median(ref_grad.values())
    return sorted(k for k, v in ref_grad.items() if v >= MIN_SHARE * med)


def train_numbers(prog: Mapping, ref: Mapping) -> Dict[str, float]:
    """``prog``/``ref``: {"losses": [...], "grad": {leaf: norm}, "change": {leaf: norm}}."""
    keys = kept_leaves(ref["grad"])
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"]))
    if len(prog["losses"]) != len(ref["losses"]):
        loss_gap = math.inf
    return {
        "loss_gap": loss_gap,
        "grad_gap": _worst(prog["grad"], ref["grad"], keys),
        "update_gap": _worst(prog["change"], ref["change"], keys),
    }


def judge(numbers: Mapping[str, float], limits: Mapping[str, Optional[float]]) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Every number with a limit must be finite and at most its limit."""
    shown: Dict[str, Dict[str, float]] = {}
    ok = True
    for name, value in numbers.items():
        limit = limits.get(name)
        shown[name] = {"value": value, "limit": limit}
        if limit is not None and not (math.isfinite(value) and value <= limit):
            ok = False
    missing = [k for k, v in limits.items() if v is not None and k not in numbers]
    if missing:
        ok = False
        for k in missing:
            shown[k] = {"value": None, "limit": limits[k]}
    return ok, shown
