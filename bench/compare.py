"""The comparison that decides ``correct`` for a training cell.

Three numbers, each held to a limit of its own (kept per cell in
``bench/workloads/<cell>.json`` with the readings it was set from):

``loss_gap``         the largest relative gap, over the steps the
                     reference follows, between the step's loss and the
                     reference's (steps that log no valid loss yet are
                     skipped on both sides);
``grad_norm_gap``    the worst leaf's gap between the norms of the
                     momentum after the first step in which every leaf
                     has a gradient (the gradient as the optimizer got
                     it, times ``1 - gamma``), over the larger of that
                     leaf's reference norm and the median leaf's;
``update_norm_gap``  the same for the weights' change over all the
                     steps, leaving out leaves whose reference gradient
                     is under a thousandth of the median leaf's (they
                     move by round-off alone).

A leaf is an outer weight (embedding, head, final norm) whole, or one
layer of a stacked weight.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List, Optional

NAMES = ("loss_gap", "grad_norm_gap", "update_norm_gap")
TINY_GRAD = 1e-3


def _leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
              keep: Optional[set] = None) -> float:
    if set(prog) != set(ref):
        missing = sorted(set(prog) ^ set(ref))[:5]
        raise ValueError(f"program and reference leaves differ: {missing}")
    names = sorted(ref) if keep is None else sorted(keep)
    med = statistics.median(ref[n] for n in names)
    gaps = [abs(prog[n] - ref[n]) / max(ref[n], med) for n in names]
    return max(gaps) if all(math.isfinite(g) for g in gaps) else math.inf


def numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """The three numbers from the program's and the reference's
    readings (``losses``, ``momentum``, ``change``)."""
    pl: List[Optional[float]] = prog["losses"]
    rl: List[Optional[float]] = ref["losses"]
    pairs = [(p, r) for p, r in zip(pl, rl) if r is not None]
    if len(pl) != len(rl) or not pairs:
        raise ValueError(f"losses do not line up: program {pl}, "
                         f"reference {rl}")
    # a step that logs no valid loss where the reference has one is as
    # wrong as a loss that is not finite
    loss_gap = max(abs(p - r) / abs(r)
                   if p is not None and math.isfinite(p) else math.inf
                   for p, r in pairs)
    g = ref["momentum"]
    med = statistics.median(g.values())
    keep = {n for n, v in g.items() if v >= TINY_GRAD * med}
    return {"loss_gap": loss_gap,
            "grad_norm_gap": _leaf_gap(prog["momentum"], g),
            "update_norm_gap": _leaf_gap(prog["change"], ref["change"],
                                         keep)}


def verdict(nums: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(n in limits and limits[n] is not None
               and nums[n] <= limits[n] for n in NAMES)
