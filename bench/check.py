"""The comparison that decides ``correct``.

Three numbers, each against the limit that ``bench/limits/<cell>.json``
gives it:

* ``loss_gap``: the largest relative gap between the program's loss and
  the reference's over the first steps;
* ``grad_gap``: by the worst leaf, the gap between the norms of the first
  gradient as the optimizer gets it, in the program and in the reference,
  over the larger of the reference leaf's norm and the median leaf's;
* ``change_gap``: the same for the parameters' change over the first
  steps, over the leaves the reference's gradient moves (a leaf whose
  reference gradient is under a thousandth of the median leaf's moves by
  round-off alone and is left out).
"""

from __future__ import annotations

import math
import statistics

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
STILL = 1e-3     # a leaf's gradient below this share of the median's


def worst_leaf(prog: dict, ref: dict, leaves=None):
    """(gap, leaf) of the worst leaf: |‖p‖ − ‖r‖| / max(‖r‖, median ‖r‖)."""
    leaves = sorted(ref) if leaves is None else leaves
    med = statistics.median(ref[k] for k in leaves)
    worst = (-1.0, None)
    for k in leaves:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med)
        if not math.isfinite(gap):
            return math.inf, k
        if gap > worst[0]:
            worst = (gap, k)
    return worst


def moved_leaves(ref) -> list:
    med = statistics.median(ref.grad_norms.values())
    return sorted(k for k, v in ref.grad_norms.items() if v >= STILL * med)


def numbers(prog, ref) -> dict:
    """The three numbers and the leaf each was set by."""
    if sorted(prog.grad_norms) != sorted(ref.grad_norms):
        raise ValueError("program and reference leaves differ: "
                         f"{sorted(set(prog.grad_norms) ^ set(ref.grad_norms))}")
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog.losses, ref.losses))
    if not all(math.isfinite(x) for x in prog.losses):
        loss = math.inf
    grad, grad_leaf = worst_leaf(prog.grad_norms, ref.grad_norms)
    change, change_leaf = worst_leaf(prog.change_norms, ref.change_norms,
                                     moved_leaves(ref))
    return {"loss_gap": loss, "grad_gap": grad, "change_gap": change,
            "grad_leaf": grad_leaf, "change_leaf": change_leaf}


def verdict(nums: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}): correct when every number is
    within its limit.  A limit of None marks a number that is read and
    printed but not compared: no control or fault separated it from sound
    runs (PERF.md says which, with its readings)."""
    checks = {n: {"value": nums[n], "limit": limits[n]} for n in NUMBERS}
    ok = all(c["limit"] is None or (math.isfinite(c["value"])
                                    and c["value"] <= c["limit"])
             for c in checks.values())
    return ok, checks
