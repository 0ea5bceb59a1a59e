"""The comparison that decides ``correct``.

Three numbers, each against the cell's limit (``limits`` in the cell's
workload file):

* ``loss_gap``: the largest ``|loss - reference loss|`` over the first
  three steps;
* ``grad_gap``: over the compared slices (every parameter, a stacked
  layer leaf split into its layers), the largest gap between the program's
  and the reference's norm of the first clipped gradient, as the optimizer
  got it, over the larger of the reference's norm of that slice and the
  median slice's;
* ``update_gap``: the same for the parameters' change over the three
  steps, leaving out slices whose reference gradient is under a thousandth
  of the median slice's (they move by round-off alone).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

__all__ = ["NUMBERS", "gaps", "verdict"]

NUMBERS = ("loss_gap", "grad_gap", "update_gap")


def _worst(prog, ref, keep) -> Tuple[float, int]:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    base = np.maximum(ref, np.median(ref[keep]))
    gap = np.where(keep, np.abs(prog - ref) / base, 0.0)
    gap = np.where(np.isfinite(gap), gap, np.inf)
    i = int(np.argmax(gap))
    return float(gap[i]), i


def gaps(prog: Dict, ref: Dict, names: List[str]) -> Dict:
    """The compared numbers, with the slice that gave each worst gap."""
    pl, rl = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss = np.abs(pl - rl)
    loss = float(np.max(np.where(np.isfinite(loss), loss, np.inf)))
    rg = np.asarray(ref["grad_norms"], np.float64)
    all_ = np.ones(rg.shape, bool)
    grad, gi = _worst(prog["grad_norms"], rg, all_)
    moving = rg >= 1e-3 * np.median(rg)
    upd, ui = _worst(prog["update_norms"], ref["update_norms"], moving)
    return {"loss_gap": loss, "grad_gap": grad, "update_gap": upd,
            "worst": {"grad_gap": names[gi], "update_gap": names[ui]},
            "left_out": [n for n, k in zip(names, moving) if not k]}


def verdict(numbers: Dict, limits: Dict) -> Tuple[bool, Dict]:
    """(correct, {name: {"value", "limit"}}) in ``NUMBERS`` order, for the
    numbers the cell has a limit for (a number with no reading that
    separates sound runs from the control or a fault is not compared)."""
    shown = {k: {"value": numbers[k], "limit": limits[k]} for k in NUMBERS
             if k in limits}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in shown.values())
    return bool(ok), shown
