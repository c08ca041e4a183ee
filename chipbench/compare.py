"""The comparison that decides ``correct`` for a training cell.

The program's first update steps (taken by set-up through the window's own
call and feed) against the plain reference's, from the same weights and
batches. ``numbers`` works out, and ``judge`` holds to the limits in the
cell's file under ``chipbench/cells/`` those that have one (PERF.md gives
the readings each limit was set from, and why the others have none):

- ``loss1_gap`` .. ``lossN_gap``: |program - reference| / |reference| of
  each step's loss;
- ``grad_gap`` / ``grad_mid_gap``: over the leaves, the widest and the
  median gap between the program's norm of the first gradient as Adam got
  it (its first moment after one step, over 1 - beta1) and the
  reference's, against the reference's norm of that leaf or of the median
  leaf, whichever is larger;
- ``graddir_gap`` / ``graddir_mid_gap`` / ``graddir_top_gap``: the norm of
  the difference of the two first gradients of a leaf, against the same:
  the widest, the median, and that of the leaf whose reference gradient is
  largest (the last layer's kernel, whose gradient is the best conditioned:
  it is what a lower precision in the forward pass turns first);
- ``change_gap`` / ``change_mid_gap``: the gap of norms again, of each
  leaf's change over the steps, leaving out leaves whose reference gradient
  is under a thousandth of the median leaf's (a bias before BatchNorm has
  no gradient but rounding, and Adam moves it by round-off alone).

A number that is not finite fails whatever its limit.
"""

import math
import statistics

import numpy as np

DEAD_LEAF_SHARE = 1e-3


def leaf_gaps(got: dict, want: dict, keep=None) -> dict:
    """``{leaf: |got - want| / max(want, median want)}``: the gap between
    the two norms of each leaf, not the norm of a difference."""
    names = [k for k in want if keep is None or k in keep]
    floor = statistics.median(want[k] for k in names)
    out = {}
    for k in names:
        gap = abs(got[k] - want[k]) / max(want[k], floor, 1e-30)
        out[k] = gap if math.isfinite(gap) else float("inf")
    return out


def worst_and_middle(gaps: dict):
    """(widest gap, its leaf, median gap)."""
    where = max(gaps, key=gaps.get)
    return gaps[where], where, statistics.median(gaps.values())


def norm(a) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(a, np.float64)))))


def direction_gaps(got: dict, want: dict) -> dict:
    """``{leaf: |got - want| / max(|want|, median |want|)}``: the norm of
    the difference of the two gradients of each leaf."""
    norms = {k: norm(want[k]) for k in want}
    floor = statistics.median(norms.values())
    out = {}
    for k in want:
        gap = norm(np.asarray(got[k], np.float64)
                    - np.asarray(want[k], np.float64)) \
            / max(norms[k], floor, 1e-30)
        out[k] = gap if math.isfinite(gap) else float("inf")
    return out


def numbers(program: dict, reference: dict) -> dict:
    """``{name: value}`` of every number compared; ``program`` and
    ``reference`` each hold ``losses``, ``first_grads`` (arrays on the
    host) and ``change_norms``."""
    out = {}
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"])):
        gap = abs(a - b) / max(abs(b), 1e-30)
        out[f"loss{i + 1}_gap"] = gap if math.isfinite(gap) else float("inf")
    ref_g = {k: norm(g) for k, g in reference["first_grads"].items()}
    got_g = {k: norm(g) for k, g in program["first_grads"].items()}
    out["grad_gap"], out["grad_gap_leaf"], out["grad_mid_gap"] = \
        worst_and_middle(leaf_gaps(got_g, ref_g))
    turned = direction_gaps(program["first_grads"],
                            reference["first_grads"])
    out["graddir_gap"], out["graddir_gap_leaf"], out["graddir_mid_gap"] = \
        worst_and_middle(turned)
    top = max(ref_g, key=ref_g.get)
    out["graddir_top_gap"], out["graddir_top_leaf"] = turned[top], top
    median_g = statistics.median(ref_g.values())
    alive = {k for k, g in ref_g.items() if g >= DEAD_LEAF_SHARE * median_g}
    out["change_gap"], out["change_gap_leaf"], out["change_mid_gap"] = \
        worst_and_middle(leaf_gaps(program["change_norms"],
                                   reference["change_norms"], keep=alive))
    return out


def judge(nums: dict, limits: dict):
    """(correct, checks): ``checks`` holds each number beside its limit.
    Every limit must find its number; a number without a limit is shown
    and not held."""
    checks, correct = {}, True
    for name, limit in limits.items():
        value = nums.get(name)
        ok = value is not None and math.isfinite(value) and value <= limit
        correct = correct and ok
        checks[name] = {"value": value, "limit": limit, "ok": ok}
    for name, value in nums.items():    # shown, not held
        checks.setdefault(name, value)
    return correct, checks
