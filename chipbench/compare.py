"""The numbers that decide ``correct``: what the timed path produced against
the plain reference, each held to the cell's limit."""
from __future__ import annotations

import math
import statistics

#: leaves whose reference gradient is under this share of the median leaf's
#: move by round-off alone under Adam; they are left out of the change
ZERO_GRAD_SHARE = 1e-3


def _worst_leaf_gap(prog: dict, ref: dict, keep) -> float:
    """Largest | |prog leaf| - |ref leaf| | over the kept leaves, each over
    the larger of the reference's norm of that leaf and of the median leaf."""
    med = statistics.median(ref.values())
    worst = 0.0
    for path, r in ref.items():
        if path not in prog:
            return math.inf
        if keep(path):
            worst = max(worst, abs(prog[path] - r) / max(r, med, 1e-30))
    return worst


def train_numbers(prog: dict, ref: dict) -> dict:
    """prog/ref: {"losses": [step 1..n], "grad1": {leaf: norm}, "change": {leaf:
    norm}} -> {number: value}.  The loss compared is the first step's: the
    later steps' losses swing by up to a fifth of a nat between sound runs
    and the reference (the first updates amplify round-off), so they are
    reported but not compared."""
    g_med = statistics.median(ref["grad1"].values())
    moving = {k for k, g in ref["grad1"].items() if g >= ZERO_GRAD_SHARE * g_med}
    return {
        "first_loss_gap": abs(prog["losses"][0] - ref["losses"][0]),
        "grad1_gap": _worst_leaf_gap(prog["grad1"], ref["grad1"], lambda k: True),
        "change_gap": _worst_leaf_gap(prog["change"], ref["change"],
                                      lambda k: k in moving),
    }


def judge(numbers: dict, limits: dict) -> bool:
    """True where every number is finite and within its limit."""
    return all(math.isfinite(numbers.get(k, math.nan)) and numbers[k] <= lim
               for k, lim in limits.items())


def report(numbers: dict, limits: dict) -> dict:
    """{number: {"value", "limit"}} for the result line."""
    return {k: {"value": numbers.get(k), "limit": lim} for k, lim in limits.items()}
