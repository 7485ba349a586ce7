"""Plain reference for gradient-boosted trees grown on sampled rows and
sampled features, with a held-out set and the two binary metrics.

It is ``reference/gbdt.py`` (whose plain pieces it imports; nothing of
the program) for the job the project's ``train.conf`` describes: every
``bagging_freq`` iterations a share of the rows is drawn, every tree a
share of the features, and after every iteration log-loss and AUC of the
training rows and of the held-out rows are reported.

*What a draw is, is data.*  Which rows are in the bag of an iteration and
which features a tree may split on are the program's pseudo-random
choice, as the bin bounds are the ingest layer's: the reference is
handed them (``draws``) and holds them to what a draw must satisfy
(:func:`draw_faults`): a bag's share of the rows within five standard
deviations of ``bagging_fraction``, one bag per bagging epoch and
another in the next, exactly ``int(feature_fraction * F)`` features a
tree, and no split on a feature that was not drawn.

*What it works out itself.*  Per followed tree: gradients of all rows
from the scores before it, rounded as the stated precision says against
one scale over ALL rows of the set (the precision does not know the
bag), summed exactly per (leaf, feature, bin) over the rows IN THE BAG;
so every leaf's count is its in-bag rows, its value is the in-bag sums'
``-lr * G / (H + lambda_l2)``, and the best split of a node is the best
among the drawn features under the constraints on in-bag counts and
hessians.  Then every row, in the bag or not, and every held-out row
moves by its leaf's value (the program's, as ``gbdt.py`` follows the
program's state); the loss of the scores followed to is held against the
program's (``loss_step<k>``).

*The scores and the metrics.*  The reference routes every training row
and every held-out row through each followed tree itself and adds the
leaf's value, in float32 as the configuration's scores are.  The
program's float32 scores after each followed step are handed to it and
held against those, row by row and set by set (``score_off``: the
furthest row of each set; compared, with a limit a set).  That is what
holds the program's score updates: a set whose scores did not move, or
moved by another tree's values, is a leaf's value off (1e-02 and more),
where sound scores differ by roundings: on the held-out rows a
multiply-add rounded once on one side and twice on the other (6e-08 a
tree on a score near 1); on the training rows, which the program moves
by the value its last route kernel emits as a pair of bfloat16 halves
(16 significant bits), up to ``2**-17`` of the tree's unshrunk value a
tree, 1e-06 or so, which adds up over the followed trees.

The metrics (mean log-loss and AUC of both sets, in float64, the AUC by
the reference's own mid-rank computation in integers, ties sharing
their rank) are then worked out of the scores that were handed over and
just held to the reference's own, not of the reference's: an AUC counts
every pair of rows that the last place orders differently on the two
sides, and half a pair in 6.75 million at 6,000 rows is 7e-08, above
what a bucketed AUC is off by, which the comparison has to refuse.  The
two steps together say that the program reported the exact metrics of
the scores the reference arrives at to within ``score_off``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import gbdt as plain


def logloss64(score: np.ndarray, y: np.ndarray) -> float:
    """Mean of ``log(1 + exp(s)) - y s`` in float64."""
    s = score.astype(np.float64)
    return float(np.mean(np.logaddexp(0.0, s) - y.astype(np.float64) * s))


def auc_midrank(score: np.ndarray, y: np.ndarray) -> float:
    """AUC as the Mann-Whitney statistic from mid-ranks, in integers:
    rows of one score share the mean of the ranks they take up.  Twice a
    mid-rank is ``rows below + rows up to and with + 1``."""
    _, inverse, count = np.unique(score, return_inverse=True,
                                  return_counts=True)
    upto = np.cumsum(count)
    rank2 = 2 * upto - count + 1                   # int64, per distinct score
    pos = y > 0
    p = int(np.count_nonzero(pos))
    n = len(y) - p
    if p == 0 or n == 0:
        return 1.0
    u2 = int(np.sum(rank2[inverse[pos]], dtype=np.int64)) - p * (p + 1)
    return u2 / (2 * p * n)


def draw_faults(draws: dict, trees: list, rows: int, features: int,
                params: dict):
    """``-> (how many conditions of a draw are broken, [what])``."""
    frac = float(params["bagging_fraction"])
    freq = int(params["bagging_freq"])
    want = max(1, int(float(params["feature_fraction"]) * features))
    sigma = (frac * (1.0 - frac) / rows) ** 0.5
    faults = []
    bags = draws["bag"]
    for k, bag in enumerate(bags):
        share = float(np.count_nonzero(bag)) / rows
        if abs(share - frac) > 5.0 * sigma:
            faults.append(f"iteration {k}: {share:.6f} of the rows in the "
                          f"bag, {frac} +- {5 * sigma:.2g} expected")
        same_epoch = k > 0 and k // freq == (k - 1) // freq
        if k and same_epoch != bool(np.array_equal(bag, bags[k - 1])):
            faults.append(f"iteration {k}: its bag is "
                          f"{'another than' if same_epoch else 'the same as'}"
                          f" iteration {k - 1}'s")
    for k, (drawn, tree) in enumerate(zip(draws["features"], trees)):
        if int(np.count_nonzero(drawn)) != want:
            faults.append(f"tree {k}: {int(np.count_nonzero(drawn))} "
                          f"features drawn, {want} expected")
        used = np.asarray(tree["split_feature"][:int(tree["num_leaves"]) - 1])
        undrawn = int(np.count_nonzero(~np.asarray(drawn, bool)[used]))
        if undrawn:
            faults.append(f"tree {k}: {undrawn} splits on a feature that "
                          f"was not drawn")
    return len(faults), faults


def _blocks(XT, y, ub32_d, B, R, dev_tables, bags=()):
    """The rows of one set binned and routed through every followed
    tree, in blocks of ``R``; ``bags``: per iteration the rows in the
    bag."""
    F, N = XT.shape
    blocks, spread = [], None
    for a in range(0, N, R):
        b = min(a + R, N)
        xt = np.zeros((F, R), np.float32)
        xt[:, :b - a] = XT[:, a:b]
        yb = np.zeros(R, np.float32)
        yb[:b - a] = y[a:b]
        xt_d = jnp.asarray(xt)
        bins = plain._bin_block(xt_d, ub32_d)
        valid = jnp.arange(R) < (b - a)
        lo, hi = plain._spread_block(xt_d, bins, valid, num_bins=B)
        spread = ((lo, hi) if spread is None else
                  (jnp.minimum(spread[0], lo), jnp.maximum(spread[1], hi)))
        in_bag = []
        for bag in bags:
            m = np.zeros(R, bool)
            m[:b - a] = bag[a:b]
            in_bag.append(jnp.asarray(m))
        blocks.append({
            "bins": bins.astype(jnp.uint8) if B <= 256 else bins,
            "leaf": [plain._route_block(bins, *dt) for dt in dev_tables],
            "y": jnp.asarray(yb), "valid": valid, "bag": in_bag,
            "rows": b - a})
    return blocks, spread


def _scores(blocks) -> np.ndarray:
    return np.concatenate([np.asarray(bl["score"])[:bl["rows"]]
                           for bl in blocks])


def follow(XT: np.ndarray, y: np.ndarray, XvT: np.ndarray, yv: np.ndarray,
           grid: list, params: dict, trees: list, program_init: float,
           precision: str, draws: dict, scores: list,
           log=lambda msg: None) -> dict:
    """Follow ``trees`` over the training rows ``XT [F, N]`` / ``y`` and
    the held-out rows ``XvT [F, Nv]`` / ``yv``.

    ``params``: ``reference/gbdt.py``'s four and ``bagging_fraction``,
    ``bagging_freq``, ``feature_fraction``.  ``draws``: ``bag`` (per
    followed iteration a bool ``[N]``), ``features`` (per tree a bool
    ``[F]``).  ``scores``: per followed step the program's float32
    scores after it, ``(training [N], held-out [Nv])``.  ``->
    gbdt.follow``'s results (counts and sums over the
    in-bag rows, the loss over all training rows) and ``evals``: per
    followed step ``{(set, metric): value}``; ``draw_faults`` and what
    they are (``draw_notes``); ``score_off``: per set, the furthest a
    handed score lies from the score followed to, over the followed
    steps."""
    F, N = XT.shape
    lr = float(params["learning_rate"])
    l2 = float(params["lambda_l2"])
    T = max(len(ub) for ub in grid)
    B = T + 1
    num_bins = np.asarray([len(ub) + 1 for ub in grid])
    ub32 = np.full((F, T), np.inf, np.float32)
    for f, ub in enumerate(grid):
        ub32[f, :len(ub)] = plain.floor_to_f32(np.asarray(ub, np.float64))
    R = max(1024, min(1 << 16, plain.BLOCK_ELEMS // (F * B)) // 1024 * 1024)
    ub32_d = jnp.asarray(ub32)

    pavg = min(max(float(np.mean(y, dtype=np.float64)), 1e-15), 1 - 1e-15)
    init = float(np.log(pavg / (1.0 - pavg)))
    n_faults, notes = draw_faults(draws, trees, N, F, params)

    tables = [plain.tree_tables(t, grid) for t in trees]
    dev_tables = [tuple(jnp.asarray(tb[k]) for k in
                        ("feat", "tbin", "path", "depth")) for tb in tables]
    blocks, spread = _blocks(XT, y, ub32_d, B, R, dev_tables, draws["bag"])
    held, _ = _blocks(XvT, yv, ub32_d, B, R, dev_tables)
    for bl in blocks + held:
        bl["score"] = jnp.full(R, init, jnp.float32)
    log(f"reference: {N} + {XvT.shape[1]} rows binned and routed in "
        f"{len(blocks)} + {len(held)} blocks of {R}")

    out = {"init": init, "loss": [], "trees": [], "evals": [],
           "draw_faults": n_faults, "draw_notes": notes,
           "bin_lo": np.asarray(spread[0]), "bin_hi": np.asarray(spread[1])}
    worst_off = {"training": 0.0, "valid": 0.0}
    for k, tb in enumerate(tables):
        L = tb["num_leaves"]
        gh = [plain._grad_block(bl["score"], bl["y"]) for bl in blocks]
        sg, sh = (jnp.maximum(functools.reduce(jnp.maximum, [
            jnp.max(jnp.where(bl["valid"], jnp.abs(pair[i]), 0.0))
            for bl, pair in zip(blocks, gh)]), 1e-30) for i in (0, 1))
        total = None
        for bl, (g, h) in zip(blocks, gh):
            part = plain._hist_block(
                bl["bins"], bl["leaf"][k], bl["valid"] & bl["bag"][k], g, h,
                sg, sh, precision=precision, num_bins=B, num_leaves=L)
            total = part if total is None else total + part
        sums = np.asarray(jax.device_get(total))
        G, H, C, U, D = plain._dequant(sums, float(sg), float(sh), precision)
        lG, lH, lC, lU, lD = (a[0].sum(axis=0) for a in (G, H, C, U, D))
        with np.errstate(divide="ignore", invalid="ignore"):
            value = np.where(lC > 0, -lr * lG / (lH + l2), 0.0)
        theirs = np.asarray(trees[k]["leaf_value"][:L], np.float64)
        if k == 0:
            theirs = theirs - float(program_init)
            for bl in blocks + held:
                bl["score"] = jnp.full(R, program_init, jnp.float32)
        v_d = jnp.asarray(theirs, jnp.float32)
        parts = []
        for bl in blocks + held:
            bl["score"] = bl["score"] + v_d[bl["leaf"][k]]
        for bl in blocks:
            parts.append(plain._loss_block(bl["score"], bl["y"], bl["valid"]))
        out["loss"].append(
            float(np.sum(jax.device_get(parts), dtype=np.float64)) / N)
        evals = {}
        for name, rows, labels, theirs_s in (
                ("training", blocks, y, scores[k][0]),
                ("valid", held, yv, scores[k][1])):
            # the metrics are those of the program's own scores, held
            # first against the scores followed to: a pair of rows that
            # the two order differently would move an AUC by more than
            # any rounding of its own
            worst_off[name] = max(worst_off[name], float(np.max(
                np.abs(_scores(rows).astype(np.float64) - theirs_s))))
            evals[(name, "binary_logloss")] = logloss64(theirs_s, labels)
            evals[(name, "auc")] = auc_midrank(theirs_s, labels)
        out["evals"].append(evals)
        # the features that were not drawn have no bin to split at
        drawn_bins = np.where(np.asarray(draws["features"][k], bool),
                              num_bins, 1)
        gaps = np.minimum(
            plain.split_gaps(G, H, C, tb, drawn_bins, params),
            plain.split_gaps(G + U - D, H, C, tb, drawn_bins, params))
        tie_rows = int(round(float(sums[0, :, :, -2:].sum())))
        if k == 0:
            out["bin_count"] = C.sum(axis=2)
        out["trees"].append({
            "leaf_value": value, "leaf_count": lC, "leaf_grad": lG,
            "leaf_hess": lH, "leaf_up": lU, "leaf_down": lD,
            "tie_rows": tie_rows, "off_grid": tb["off_grid"],
            "split_gap": gaps})
        log(f"reference: tree {k} followed over "
            f"{int(lC.sum())} rows in the bag, loss {out['loss'][-1]:.9f}, "
            f"valid auc {evals[('valid', 'auc')]:.9f}, {tie_rows} rows at a "
            f"tie")
    out["score_off"] = worst_off
    return out
