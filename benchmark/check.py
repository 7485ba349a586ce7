"""The comparison that decides ``correct`` for a training cell.

``program``: what the timed object produced in its first steps (loss
after each, the trees with their leaf values and counts, its initial
score).  ``ref``: what the plain reference worked out following those
trees (``reference/gbdt.py``): per leaf its own count, hessian sum and
gradient sum, the last as an interval where rows lie at a rounding tie
of the stated precision, and so its own value as an interval.  The
numbers compared:

* ``loss_step<k>``: the loss after each followed step, the program's
  own scores against its initial score plus its trees' values row by
  row through the reference's routing; relative gap;
* ``grad_leaf``: the gradient sum of each leaf of the first tree as the
  learner got it, worked out from the state after one step (the leaf's
  value, its count and the constant first hessian), worst leaf;
* ``update_leaf_p90``: the change of the model over the followed steps,
  each tree's leaf values, the gap nine leaves in ten stay under;
* ``update_leaf_worst``: the same change by the worst leaf of the three
  trees, as the gradient sum its value stands for (the value times the
  reference's hessian sum) against the reference's gradient sum;
* ``split_gap``: the widest share by which a split the program chose
  lies below the best split the reference finds for that node;
* ``leaf_count_mismatches``: leaves whose count is not the number of
  rows the reference routes there, and thresholds off the grid; exact.

A leaf's gap is its distance from the reference's interval, taken
against the reference's number for that leaf or for the tree's median
leaf, whichever is larger (some sums are all but zero).  The worst leaf
is taken in gradient sums, not in values: what the program's float32
sums lose is an absolute amount (under 0.4 at 13M rows, in the leaves
split last), which a small leaf's value shows tenfold and its sum
against the median leaf's does not (PERF.md, PR 25).  Each number has a
limit of its own in the workload's file; ``correct`` is every number at
or under its limit.  PERF.md (PR 25) has the readings each limit was
set from.
"""
from __future__ import annotations

import numpy as np


def _leaf_gaps(prog: np.ndarray, lo: np.ndarray, hi: np.ndarray,
               ref: np.ndarray) -> np.ndarray:
    """Distance of ``prog`` from ``[lo, hi]`` against ``ref``'s size."""
    scale = np.maximum(np.abs(ref), np.median(np.abs(ref)))
    away = np.maximum(np.maximum(lo - prog, prog - hi), 0.0)
    return away / np.maximum(scale, 1e-300)


def compare(program: dict, ref: dict, params: dict):
    """``-> ({name: value} of every number compared, [observations])``."""
    lr = float(params["learning_rate"])
    l2 = float(params["lambda_l2"])
    out = {}
    for k, (lp, lr_) in enumerate(zip(program["loss"], ref["loss"])):
        out[f"loss_step{k + 1}"] = abs(lp - lr_) / abs(lr_)

    mismatched = 0
    gaps = []
    split_gap = 0.0
    worst = (0.0, "")
    seen = [f"init score {program['init']:.9g} against the reference's "
            f"{ref['init']:.9g}"]
    for k, (tp, tr) in enumerate(zip(program["trees"], ref["trees"])):
        L = int(tp["num_leaves"])
        cp = np.asarray(tp["leaf_count"][:L], np.float64)
        vp = np.asarray(tp["leaf_value"][:L], np.float64)
        if k == 0:
            # the first tree carries the initial score: each side's own
            vp = vp - float(program["init"])
        mismatched += int(np.sum(cp != tr["leaf_count"])) + tr["off_grid"]
        G, H = tr["leaf_grad"], tr["leaf_hess"] + l2
        g_lo, g_hi = G - tr["leaf_down"], G + tr["leaf_up"]
        with np.errstate(divide="ignore", invalid="ignore"):
            v_lo, v_hi = (np.where(H > 0, -lr * g / H, 0.0)
                          for g in (g_hi, g_lo))
        gaps.append(_leaf_gaps(vp, v_lo, v_hi, tr["leaf_value"]))
        # the gradient sum the program's value stands for
        g = _leaf_gaps(-(vp / lr) * H, g_lo, g_hi, G)
        j = int(np.argmax(g))
        if g[j] > worst[0]:
            worst = (float(g[j]),
                     f"tree {k} leaf {j}: {int(tr['leaf_count'][j])} rows, "
                     f"value {vp[j]:.6g} against the reference's "
                     f"{v_lo[j]:.6g}..{v_hi[j]:.6g}, gradient sum "
                     f"{G[j]:.6g} (the tree's median |sum| "
                     f"{np.median(np.abs(G)):.3g})")
        if len(tr["split_gap"]):
            split_gap = max(split_gap, float(np.max(tr["split_gap"])))
        if k == 0:
            # the gradient sums the learner got, worked out from the
            # state after one step: every row's hessian is p0 (1 - p0)
            p0 = 1.0 / (1.0 + np.exp(-float(program["init"])))
            g_prog = -(vp / lr) * (cp * p0 * (1.0 - p0) + l2)
            out["grad_leaf"] = float(np.max(_leaf_gaps(g_prog, g_lo, g_hi,
                                                       G)))
        wide = int(np.argmax(tr["leaf_up"] + tr["leaf_down"]))
        seen.append(
            f"tree {k}: {tr['tie_rows']} rows within the window of a "
            f"rounding tie; widest at leaf {wide}: its gradient sum "
            f"{G[wide]:.6g} could be {tr['leaf_down'][wide]:.3g} lower or "
            f"{tr['leaf_up'][wide]:.3g} higher")
    seen.append(grid_line(ref))
    out["update_leaf_p90"] = float(np.quantile(np.concatenate(gaps), 0.9))
    out["update_leaf_worst"] = worst[0]
    out["split_gap"] = split_gap
    out["leaf_count_mismatches"] = float(mismatched)
    seen.append(f"worst leaf of the update: gap {worst[0]:.6g} at {worst[1]}")
    return out, seen


def grid_line(ref: dict) -> str:
    """What the ingest layer's bin bounds do to the rows, as the
    reference's own binning finds it (observed, not compared: no control
    and no fault of the cell moves it): how many bins hold rows, and how
    full the fullest bin is that holds more than one value, against an
    even share of the rows among the feature's bins in use."""
    count, lo, hi = ref["bin_count"], ref["bin_lo"], ref["bin_hi"]
    used = (count > 0).sum(axis=1)
    even = count.sum(axis=1, keepdims=True) / np.maximum(used, 1)[:, None]
    fill = np.where((hi > lo) & (count > 0), count / even, 0.0)
    f, b = np.unravel_index(int(np.argmax(fill)), fill.shape)
    return (f"grid: {int(used.min())}..{int(used.max())} bins in use a "
            f"feature; the fullest bin of more than one value is feature "
            f"{f} bin {b} with {int(count[f, b])} rows, {fill[f, b]:.3f} "
            f"of an even share")


def verdict(values: dict, limits: dict):
    """``-> (correct, [(name, value, limit), ...])``; a number with no
    limit in the workload's file is an error, not a pass."""
    rows = []
    for name, value in values.items():
        if name not in limits:
            raise KeyError(f"no limit for compared number {name!r}")
        rows.append((name, float(value), float(limits[name])))
    ok = all(np.isfinite(v) and v <= lim for _, v, lim in rows)
    return bool(ok), rows
