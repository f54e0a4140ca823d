"""The comparison that decides ``correct`` for a training cell.

Both sides are reduced to the same readings: the loss of each of the first
steps, the norm of every leaf of the first gradient as the optimizer got it,
the norm of every leaf's change over those steps and, where the model keeps
batch statistics, the norm of every layer's batch mean and batch variance in
the first step (``stat_norms``). A gap of norms is taken by the worst leaf:
the distance between the program's norm and the reference's (not the norm of
their difference), over the reference's norm of that leaf or of the median
leaf, whichever is larger, since some gradients are all but zero; beside it
the median leaf's gap, which is steady from seed to seed where the worst of
some hundred leaves swings. Leaves whose reference gradient is under a
thousandth of the median leaf's move by round-off alone under Adam and are
left out of the change, by that rule and not by name.
"""

import math
import statistics

TINY_GRADIENT = 1e-3     # of the median leaf's, under which a change is noise


def _worst_leaf(prog: dict, ref: dict, leaves):
    floor = statistics.median(ref[k] for k in ref)
    worst, where = 0.0, None
    for k in leaves:
        gap = abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30)
        if not gap <= worst:          # a NaN counts as the worst there is
            worst, where = (gap if math.isfinite(gap) else math.inf), k
    return worst, where


def _median_leaf(prog: dict, ref: dict):
    """The median leaf's gap, by the same measure as the worst leaf's: steady
    from seed to seed where the worst leaf is the tail of a few hundred."""
    floor = statistics.median(ref.values())
    each = sorted((abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30), k)
                  for k in ref)
    gap, where = each[len(each) // 2]
    return (gap if math.isfinite(gap) else math.inf), where


def gaps(prog: dict, ref: dict) -> dict:
    """``{number: (gap, where)}`` between two sets of readings, each
    ``{"losses", "grad_norms", "delta_norms"}`` and, where the model keeps
    batch statistics, ``"stat_norms"``."""
    if set(prog["grad_norms"]) != set(ref["grad_norms"]):
        raise ValueError("the two sides name different leaves: "
                         f"{sorted(set(prog['grad_norms']) ^ set(ref['grad_norms']))[:6]}")
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        gap = abs(a - b) / abs(b)
        out[f"loss{i}_gap"] = (gap if math.isfinite(gap) else math.inf,
                               f"step {i}")
    if "stat_norms" in ref:
        out["stat_gap"] = _worst_leaf(prog["stat_norms"], ref["stat_norms"],
                                      ref["stat_norms"])
        out["stat_median_gap"] = _median_leaf(prog["stat_norms"],
                                              ref["stat_norms"])
    out["grad_gap"] = _worst_leaf(prog["grad_norms"], ref["grad_norms"],
                                  ref["grad_norms"])
    out["grad_median_gap"] = _median_leaf(prog["grad_norms"],
                                          ref["grad_norms"])
    floor = TINY_GRADIENT * statistics.median(ref["grad_norms"].values())
    moving = [k for k, g in ref["grad_norms"].items() if g >= floor]
    out["delta_gap"] = _worst_leaf(prog["delta_norms"], ref["delta_norms"],
                                   moving)
    out["delta_median_gap"] = _median_leaf(
        {k: prog["delta_norms"][k] for k in moving},
        {k: ref["delta_norms"][k] for k in moving})
    return out


def decide(found: dict, limits: dict):
    """``(correct, rows)``: every number that has a limit is held to it; a
    number without one is printed and not compared. ``rows`` are ``(name,
    value, limit or None, where)``."""
    rows = [(name, gap, limits.get(name), where)
            for name, (gap, where) in found.items()]
    missing = sorted(set(limits) - set(found))
    if missing:
        raise ValueError(f"limits for numbers that were not read: {missing}")
    correct = all(gap <= limit for _, gap, limit, _ in rows
                  if limit is not None)
    return correct, rows
