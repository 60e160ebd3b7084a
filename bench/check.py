"""The comparison that decides ``correct``.

Three numbers compare a run's first training steps with the reference's
(``bench/reference.py``) from the same seed and batches:

  loss_gap    the largest relative gap between the program's and the
              reference's loss over the compared steps;
  grad_gap    over every leaf (a stacked layer leaf counts once per
              layer), the gap between the program's and the reference's
              norm of the first clipped gradient, over the larger of the
              reference's norm of that leaf and its median leaf norm;
  update_gap  the same for the norm of each leaf's change after the
              compared steps. Leaves whose first reference gradient is
              under a thousandth of the median leaf's move by round-off
              alone and are left out.

A run also fails when anything compiles inside its measured window.
"""
from __future__ import annotations

import statistics
from typing import Dict, Tuple

SMALL_GRAD = 1e-3


def _worst(got: Dict[str, float], want: Dict[str, float], keep) -> Tuple[float, str]:
    med = statistics.median(want.values())
    worst, where = 0.0, ""
    for name in keep:
        gap = abs(got[name] - want[name]) / max(want[name], med)
        if gap >= worst:
            worst, where = gap, name
    return worst, where


def gaps(prog: Dict, ref: Dict) -> Dict:
    """The compared numbers (and the leaf that set each worst gap)."""
    if set(prog["grad"]) != set(ref["grad"]):
        missing = sorted(set(ref["grad"]) ^ set(prog["grad"]))
        raise ValueError(f"leaves differ between program and reference: "
                         f"{missing[:8]}")
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"],
                                                       ref["loss"]))
    if len(prog["loss"]) != len(ref["loss"]):
        loss_gap = float("inf")
    med = statistics.median(ref["grad"].values())
    moving = [k for k, v in ref["grad"].items() if v >= SMALL_GRAD * med]
    grad_gap, grad_leaf = _worst(prog["grad"], ref["grad"], ref["grad"])
    update_gap, update_leaf = _worst(prog["change"], ref["change"], moving)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "update_gap": update_gap, "grad_leaf": grad_leaf,
            "update_leaf": update_leaf,
            "leaves_left_out": len(ref["grad"]) - len(moving)}


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> Tuple[bool, list]:
    """``correct`` and the (name, value, limit) rows: a number passes when
    it is finite and at most its limit."""
    rows = [(k, numbers[k], limits[k]) for k in limits]
    ok = all(v == v and v <= lim for _, v, lim in rows)
    return ok, rows
