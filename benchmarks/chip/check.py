"""The numbers that decide ``correct``: how far the timed path's results
lie from the plain reference's (``reference.py``).

Training, over the first three steps of the very session the window
then drives:

- ``loss1_gap``: the relative gap of the first step's loss. (``loss_gap``,
  the largest over all three steps, is recorded too: the first Adam
  steps are chaotic, so later losses drift apart by far more than
  rounding explains; see ``PERF.md``.)
- ``grad_gap``: by the worst leaf, the gap between the norms of the
  program's and the reference's first gradient, over the larger of the
  reference's norm of that leaf and of the median leaf;
- ``update_gap``: the same for the norm of each leaf's change over the
  three steps. Leaves whose reference gradient is under a thousandth of
  the median leaf's are left out: Adam moves them by round-off alone.

Which of these a cell compares, and against what limit, is its
``limits/<cell>.json``; the rest are printed for the record.

"""
from __future__ import annotations

import math
import statistics
from typing import Dict, Sequence

QUIET_LEAF = 1e-3


def _leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
               leaves: Sequence[str]) -> Dict[str, float]:
    floor = statistics.median(ref[k] for k in leaves)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], floor) for k in leaves}


def train_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog`` and ``ref`` each hold ``losses``, ``grad_norms`` and
    ``change_norms`` (leaf name -> norm). Besides the numbers above, the
    median leaf's gaps and the worst leaves are returned for the
    record."""
    losses = [abs(p - r) / abs(r)
              for p, r in zip(prog["losses"], ref["losses"])]
    leaves = sorted(ref["grad_norms"])
    med = statistics.median(ref["grad_norms"].values())
    moving = [k for k in leaves if ref["grad_norms"][k] >= QUIET_LEAF * med]
    grad = _leaf_gaps(prog["grad_norms"], ref["grad_norms"], leaves)
    update = _leaf_gaps(prog["change_norms"], ref["change_norms"], moving)
    return {"loss_gap": max(losses), "grad_gap": max(grad.values()),
            "update_gap": max(update.values()), "loss1_gap": losses[0],
            "grad_median_gap": statistics.median(grad.values()),
            "update_median_gap": statistics.median(update.values()),
            "worst_grad_leaf": max(grad, key=grad.get),
            "worst_update_leaf": max(update, key=update.get)}


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """Each compared number beside its limit, and whether all hold. A
    number that is not finite fails."""
    checks = {k: {"value": float(numbers[k]), "limit": limits[k]}
              for k in sorted(limits)}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return {"ok": ok, "checks": checks}
