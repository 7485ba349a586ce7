"""The comparison that decides ``correct`` for a training cell with
sampled rows, sampled features and reported metrics: ``check.compare``'s
numbers (over the in-bag sums the plain reference ``gbdt_sampled``
worked out) and five more.

* ``score_off_training`` / ``score_off_valid``: the program's float32
  scores after each followed step against the scores the reference
  arrives at by routing the set's rows through the followed trees
  itself; the furthest row of the set over the followed steps, absolute.
  It is what holds the score updates of both sets (all rows, in the bag
  or not; the held-out rows' update in the block), and what ties the
  two gaps below to the reference's own scores;
* ``eval_logloss_gap``: every log-loss the program reported (each set,
  each followed step) against the reference's float64 log-loss of the
  program's scores after that step; the worst relative gap;
* ``eval_auc_gap``: the same for every reported AUC, against the
  reference's mid-rank AUC; the worst absolute gap;
* ``draw_faults``: conditions of a draw that are broken (a bag's share,
  one bag an epoch, the number of features a tree, a split on a feature
  that was not drawn); exact, 0.

``program["evals"]`` and ``ref["evals"]``: per followed step ``{(set,
metric): value}``; the program's are the values it reported itself.
"""
from __future__ import annotations

from benchmark import check


def compare(program: dict, ref: dict, params: dict):
    """``-> ({name: value} of every number compared, [observations])``."""
    out, seen = check.compare(program, ref, params)
    worst = {"binary_logloss": (0.0, ""), "auc": (0.0, "")}
    for k, (theirs, ours) in enumerate(zip(program["evals"], ref["evals"])):
        for (name, metric), want in ours.items():
            got = theirs.get((name, metric), float("nan"))
            gap = abs(got - want)
            if metric == "binary_logloss":
                gap /= abs(want)
            if not gap <= worst[metric][0]:
                worst[metric] = (gap, f"step {k + 1} {name} {metric}: "
                                 f"reported {got!r}, the reference's "
                                 f"{want!r}")
    out["eval_logloss_gap"] = worst["binary_logloss"][0]
    out["eval_auc_gap"] = worst["auc"][0]
    out["draw_faults"] = float(ref["draw_faults"])
    for name, off in ref["score_off"].items():
        out[f"score_off_{name}"] = off
    seen.extend(f"worst {m} gap {g:.3g} at {where}"
                for m, (g, where) in worst.items() if where)
    seen.extend("draw: " + note for note in ref["draw_notes"])
    # what a value carried as two bfloat16 halves (16 significant bits)
    # can be off by, summed over the followed trees' largest values
    halves = sum(float(abs(t["leaf_value"][:int(t["num_leaves"])]
                           - (program["init"] if k == 0 else 0.0)).max())
                 for k, t in enumerate(program["trees"])) * 2.0 ** -17
    seen.append("scores furthest from the reference's: " + ", ".join(
        f"{name} {off:.3g}" for name, off in ref["score_off"].items())
        + f"; two bfloat16 halves of every followed tree's largest value "
          f"leave at most {halves:.3g}")
    last = ref["evals"][-1]
    seen.append("the reference's metrics after the last followed step: "
                + ", ".join(f"{n} {m} {v:.9f}" for (n, m), v in last.items()))
    return out, seen
