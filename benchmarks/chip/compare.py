"""The comparison that decides ``correct`` for a training cell.

Three numbers, each taken against the plain reference that followed the
same first steps on the same rows:

* ``loss_gap``   the largest |program - reference| / |reference| over the
                 first three steps' losses (later steps drift apart as the
                 two learn; the window's own count of steps varies);
* ``grad_gap``   by the worst leaf, the gap between the program's and the
                 reference's norm of the first gradient (the program's is
                 worked out from its Adam first moment after one step),
                 over the larger of that leaf's reference norm and the
                 median leaf's;
* ``change_gap`` the same for the norm of each leaf's change from its
                 initial value after the run's last step. Leaves whose
                 reference gradient is under a thousandth of the median
                 leaf's move by round-off alone under Adam and are left
                 out.

A fourth, ``bytes_mismatch``, counts the rows where the bytes the program
metered differ from what its own plan predicts; its limit is 0.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Tuple

#: leaves whose reference first-gradient norm is under this share of the
#: median leaf's are left out of ``change_gap``
GRAD_FLOOR = 1e-3
#: the steps whose losses are compared
LOSS_STEPS = 3


def _worst(prog: Dict[str, float], ref: Dict[str, float],
           leaves: List[str]) -> Tuple[float, str]:
    med = statistics.median(ref[k] for k in leaves)
    best = (0.0, "")
    for k in leaves:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med)
        if gap > best[0]:
            best = (gap, k)
    return best


def numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` each hold ``losses`` (per step),
    ``grad_norms`` and ``change_norms`` (per leaf)."""
    if len(prog["losses"]) != len(ref["losses"]):
        raise ValueError(f"{len(prog['losses'])} program losses against "
                         f"{len(ref['losses'])} reference losses")
    missing = set(ref["grad_norms"]) ^ set(prog["grad_norms"])
    if missing:
        raise ValueError(f"leaves on one side only: {sorted(missing)}")
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(
        prog["losses"][:LOSS_STEPS], ref["losses"][:LOSS_STEPS]))
    g_ref = ref["grad_norms"]
    leaves = sorted(g_ref)
    grad_gap, grad_leaf = _worst(prog["grad_norms"], g_ref, leaves)
    floor = GRAD_FLOOR * statistics.median(g_ref.values())
    moved = [k for k in leaves if g_ref[k] >= floor]
    change_gap, change_leaf = _worst(prog["change_norms"],
                                     ref["change_norms"], moved)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap,
            "worst_grad_leaf": grad_leaf, "worst_change_leaf": change_leaf,
            "left_out": sorted(set(leaves) - set(moved))}


def judge(nums: dict, limits: dict) -> Tuple[bool, Dict[str, dict]]:
    """(correct, {name: {"value", "limit"}}) for every limited number."""
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
