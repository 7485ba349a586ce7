"""Plain reference for gradient-boosted trees with a logistic loss.

Straightforward ``jax.numpy`` and numpy; it imports nothing of the
program.  It is given the rows and labels the harness made from the
seed, the configuration's parameters, the thresholds at which the data
was discretised (an input of the timed path: the ingest layer makes
them in set-up, the window does not time it), and the first trees the
program built.  It *follows* those trees, as a language-model reference
follows served tokens: it never copies a value or a count from them,
their structure (which node splits on which feature at which
threshold) and, as the state a later step starts from, the leaf values
that it has just held against its own; everything else it works out from
the raw rows:

* its own initial score from the label mean;
* per tree: gradients and hessians from the scores before that tree (its
  own initial score before the first; after a tree, the program's
  stated initial score plus the values of the program's leaves, row by
  row through its own routing), rounded the way the configuration's
  stated precision says (``int8h``: gradient to 8 bits, hessian to a
  two-level 8+8 bit pair, scales at the largest magnitude; ``int8``: 8
  bits each; ``f32``: not rounded), summed exactly per (leaf, feature,
  bin) by one one-hot matrix product over its own binning of the raw
  rows;
* every leaf's count, gradient and hessian sum and value
  ``-lr * G / (H + lambda_l2)``, and the loss of the scores after it;
* for every node the tree splits, the gain of that split and the gain
  of the best split over all features and thresholds under the
  configuration's constraints, both by its own arithmetic in float64.

*Why the later steps start from the program's values, and what a tie
is.*  After the first tree every row of a leaf has the same score, so
its gradient takes one of two values; an 8-bit code is ``round(g * 127 /
max|g|)``, and where that product lies within float32's reach of a
half, all the rows that share it round up on one side and down on the
other, both soundly: thousands of rows move one code together (PERF.md,
PR 25: one seed in twenty, a leaf's gradient sum off by 21.8, the
second step's loss by 4.5e-07).  A reference that carried its own
scores would differ from the program's in the last place and meet other
ties.  So it follows the program's state, and counts per leaf and per
cell the rows whose code lies within ``TIE_WINDOW`` of a half and could
as soundly be one higher (``up``) or one lower (``down``): a leaf's
gradient sum is an interval, and so is its value.

Rows go through in blocks so that it fits beside nothing else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ELEMS = 1 << 28      # one-hot cells (rows x features x bins) a block
# how near a half, in codes, a gradient's product may lie for either
# rounding to be sound: six times the nearest miss that was seen to
# round the other way on the chip (4.1e-05, PERF.md PR 25)
TIE_WINDOW = 2.5e-4


def floor_to_f32(bounds: np.ndarray) -> np.ndarray:
    """Largest float32 <= each float64 bound, so that ``x > b`` decided
    in float32 on a float32 ``x`` is what float64 would decide."""
    b32 = bounds.astype(np.float32)
    over = b32.astype(np.float64) > bounds
    return np.where(over, np.nextafter(b32, np.float32(-np.inf)), b32)


def tree_tables(tree: dict, grid: list) -> dict:
    """Host tables for routing rows through a followed tree.

    ``tree``: ``split_feature``, ``threshold`` (real valued),
    ``left_child``, ``right_child`` (>= 0 a node, ``~leaf`` a leaf),
    ``num_leaves``.  ``-> feat [M], tbin [M], path [M, L] in {-1,0,1},
    depth [L], off_grid`` (thresholds that are no bound of the grid)."""
    L = int(tree["num_leaves"])
    M = L - 1
    feat = np.asarray(tree["split_feature"][:M], np.int32)
    thr = np.asarray(tree["threshold"][:M], np.float64)
    left = np.asarray(tree["left_child"][:M], np.int64)
    right = np.asarray(tree["right_child"][:M], np.int64)
    tbin = np.zeros(M, np.int32)
    off_grid = 0
    for m in range(M):
        ub = grid[feat[m]]
        t = int(np.searchsorted(ub, thr[m], side="left"))
        if t >= len(ub) or ub[t] != thr[m]:
            off_grid += 1
            t = min(t, len(ub) - 1)
        tbin[m] = t
    path = np.zeros((M, L), np.int8)
    depth = np.zeros(L, np.int32)

    stack = [(0, [])] if M else []
    while stack:
        node, trail = stack.pop()
        if node < 0:                      # ~leaf: its ancestors' decisions
            for m, sign in trail:
                path[m, ~node] = sign
            depth[~node] = len(trail)
            continue
        stack.append((int(left[node]), trail + [(node, 1)]))
        stack.append((int(right[node]), trail + [(node, -1)]))
    return {"feat": feat, "tbin": tbin, "path": path, "depth": depth,
            "off_grid": off_grid, "num_leaves": L}


@jax.jit
def _bin_block(xt, ub32):
    """``xt [F, R] f32, ub32 [F, T] -> bins [F, R] i32``: the bin of a
    value is the number of bounds it exceeds."""
    return jnp.sum(xt[:, :, None] > ub32[:, None, :], axis=-1,
                   dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames="num_bins")
def _spread_block(xt, bins, valid, *, num_bins):
    """Smallest and largest value of each (feature, bin) in one block:
    ``-> lo, hi [F, B]``, ``+inf`` / ``-inf`` where a bin is empty."""
    in_bin = ((bins[:, :, None] == jnp.arange(num_bins)[None, None, :])
              & valid[None, :, None])
    x = xt[:, :, None]
    return (jnp.min(jnp.where(in_bin, x, jnp.inf), axis=1),
            jnp.max(jnp.where(in_bin, x, -jnp.inf), axis=1))


@jax.jit
def _route_block(bins, feat, tbin, path, depth):
    """Leaf of each row of ``bins [F, R]``: the one leaf all of whose
    ancestors' decisions the row agrees with."""
    if path.shape[0] == 0:
        return jnp.zeros(bins.shape[1], jnp.int32)
    go_left = jnp.take(bins, feat, axis=0) <= tbin[:, None]        # [M, R]
    d = jnp.where(go_left, 1, -1).astype(jnp.int8)
    agree = jnp.dot(path.T, d, preferred_element_type=jnp.int32)   # [L, R]
    return jnp.argmax(agree == depth[:, None], axis=0).astype(jnp.int32)


@jax.jit
def _grad_block(score, y):
    p = jax.nn.sigmoid(score)
    return p - y, p * (1.0 - p)


@jax.jit
def _loss_block(score, y, valid):
    return jnp.sum(jnp.where(valid, jax.nn.softplus(score) - y * score, 0.0))


def _codes(g, h, sg, sh, precision: str):
    """Value columns of a row as the stated precision rounds them, then
    a one that counts the row, then ``up`` and ``down``: whether the
    gradient's code could as soundly be one higher or one lower."""

    def q(x, scale):
        return jnp.clip(jnp.round(x * (127.0 / scale)), -127, 127)

    one = jnp.ones_like(g)
    none = jnp.zeros_like(g)
    if precision == "f32":
        return jnp.stack([g, h, one, none, none], axis=1)
    gq = q(g, sg)
    off = g * (127.0 / sg) - gq                  # in [-0.5, 0.5]
    up = (off > 0.5 - TIE_WINDOW) & (gq < 127)
    down = (off < TIE_WINDOW - 0.5) & (gq > -127)
    if precision == "int8":
        cols = [gq, q(h, sh), one, up, down]
    elif precision == "int8h":
        hi = q(h, sh)
        lo = q(h - hi * (sh / 127.0), sh / 127.0)
        cols = [gq, hi, lo, one, up, down]
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.stack(cols, axis=1).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("precision", "num_bins", "num_leaves"))
def _hist_block(bins, leaf, valid, g, h, sg, sh, *, precision, num_bins,
                num_leaves):
    """Sums per (feature, bin, leaf, column) of one block of ``bins
    [F, R]``, exact for the integer precisions: one matrix product of
    two one-hot operands, rows contracted."""
    bins = bins.astype(jnp.int32)
    F, R = bins.shape
    codes = _codes(g, h, sg, sh, precision)
    dt = codes.dtype
    acc = jnp.float32 if precision == "f32" else jnp.int32
    in_leaf = (leaf[:, None] == jnp.arange(num_leaves)[None, :]) \
        & valid[:, None]
    by_leaf = (in_leaf[:, :, None].astype(dt) * codes[:, None, :]).reshape(
        R, -1)                                                 # [R, L * C]
    in_bin = (bins[:, None, :] == jnp.arange(num_bins)[None, :, None])
    by_bin = in_bin.astype(dt).reshape(F * num_bins, R)        # [F * B, R]
    out = jnp.dot(by_bin, by_leaf, precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=acc)
    return out.reshape(F, num_bins, num_leaves, codes.shape[1])


def _dequant(sums: np.ndarray, sg: float, sh: float, precision: str):
    """``[..., C] -> (G, H, count, up, down)`` in float64; ``up`` and
    ``down`` in the gradient's units (rows times one code)."""
    s = sums.astype(np.float64)
    step = 0.0 if precision == "f32" else sg / 127.0
    up, down = s[..., -2] * step, s[..., -1] * step
    if precision == "f32":
        return s[..., 0], s[..., 1], s[..., 2], up, down
    if precision == "int8":
        return (s[..., 0] * (sg / 127.0), s[..., 1] * (sh / 127.0),
                s[..., 2], up, down)
    return (s[..., 0] * (sg / 127.0),
            s[..., 1] * (sh / 127.0) + s[..., 2] * (sh / 16129.0),
            s[..., 3], up, down)


def _gain(g, h, l2):
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.where(h + l2 > 0, g * g / (h + l2), 0.0)


def split_gaps(G, H, C, tables: dict, num_bins: np.ndarray,
               params: dict) -> np.ndarray:
    """For each node the followed tree splits: how far the gain of its
    split lies below the best gain over all features and thresholds, as
    a share of the best.  ``G, H, C`` are ``[F, B, L]`` leaf sums."""
    l2 = float(params["lambda_l2"])
    min_d = float(params["min_data_in_leaf"])
    min_h = float(params["min_sum_hessian_in_leaf"])
    L = tables["num_leaves"]
    M = L - 1
    # a node's rows are the rows of the leaves below it
    below = (tables["path"] != 0).astype(np.float64)          # [M, L]
    F, B = G.shape[:2]

    def per_node(a):
        return (a.reshape(F * B, L) @ below.T).T.reshape(M, F, B)

    nG, nH, nC = per_node(G), per_node(H), per_node(C)
    lg, lh, lc = (np.cumsum(a, axis=-1) for a in (nG, nH, nC))
    tg, th, tc = (a[:, :1, -1:] for a in (lg, lh, lc))        # node totals
    rg, rh, rc = tg - lg, th - lh, tc - lc
    gain = _gain(lg, lh, l2) + _gain(rg, rh, l2) - _gain(tg, th, l2)
    ok = ((lc >= min_d) & (rc >= min_d) & (lh >= min_h) & (rh >= min_h)
          & (np.arange(B)[None, None, :] < (num_bins - 1)[None, :, None]))
    gain = np.where(ok, gain, -np.inf)
    best = gain.reshape(M, -1).max(axis=1)
    chosen = gain[np.arange(M), tables["feat"], tables["tbin"]]
    with np.errstate(invalid="ignore"):
        gap = (best - chosen) / np.maximum(best, 1e-300)
    return np.where(np.isfinite(chosen), np.maximum(gap, 0.0), 1.0)


def follow(XT: np.ndarray, y: np.ndarray, grid: list, params: dict,
           trees: list, program_init: float, precision: str,
           log=lambda msg: None) -> dict:
    """Follow ``trees`` over the rows ``XT [F, N]`` / ``y [N]``.

    ``grid[f]``: the finite upper bounds of feature ``f``'s bins, float64
    ascending.  ``params``: ``learning_rate``, ``lambda_l2``,
    ``min_data_in_leaf``, ``min_sum_hessian_in_leaf``.  A tree also
    gives its ``leaf_value`` (the first tree's with ``program_init`` in
    it): the scores after it are ``program_init`` plus those, each
    leaf's held against the reference's own by the caller.  ``-> init,
    loss [T], per tree: leaf_value, leaf_count, leaf_grad, leaf_hess,
    leaf_up, leaf_down (what the rows within ``TIE_WINDOW`` of a half
    could add to or take from ``leaf_grad``), tie_rows, split_gap,
    off_grid; and of the grid itself bin_count, bin_lo, bin_hi [F, B]:
    the rows and the smallest and largest value of every bin``."""
    F, N = XT.shape
    lr = float(params["learning_rate"])
    l2 = float(params["lambda_l2"])
    T = max(len(ub) for ub in grid)
    B = T + 1
    num_bins = np.asarray([len(ub) + 1 for ub in grid])
    ub32 = np.full((F, T), np.inf, np.float32)
    for f, ub in enumerate(grid):
        ub32[f, :len(ub)] = floor_to_f32(np.asarray(ub, np.float64))
    R = max(1024, min(1 << 16, BLOCK_ELEMS // (F * B)) // 1024 * 1024)
    starts = list(range(0, N, R))
    ub32_d = jnp.asarray(ub32)

    pavg = min(max(float(np.mean(y, dtype=np.float64)), 1e-15), 1 - 1e-15)
    init = float(np.log(pavg / (1.0 - pavg)))

    tables = [tree_tables(t, grid) for t in trees]
    dev_tables = [tuple(jnp.asarray(tb[k]) for k in
                        ("feat", "tbin", "path", "depth")) for tb in tables]
    blocks = []
    spread = None
    for a in starts:
        b = min(a + R, N)
        xt = np.zeros((F, R), np.float32)
        xt[:, :b - a] = XT[:, a:b]
        yb = np.zeros(R, np.float32)
        yb[:b - a] = y[a:b]
        xt_d = jnp.asarray(xt)
        bins = _bin_block(xt_d, ub32_d)
        valid = jnp.arange(R) < (b - a)
        lo, hi = _spread_block(xt_d, bins, valid, num_bins=B)
        spread = ((lo, hi) if spread is None else
                  (jnp.minimum(spread[0], lo), jnp.maximum(spread[1], hi)))
        leaves = [_route_block(bins, *dt) for dt in dev_tables]
        blocks.append({
            "bins": bins.astype(jnp.uint8) if B <= 256 else bins,
            "leaf": leaves, "y": jnp.asarray(yb), "valid": valid,
            "score": jnp.full(R, init, jnp.float32)})
    log(f"reference: {N} rows binned and routed in {len(blocks)} blocks "
        f"of {R}")

    out = {"init": init, "loss": [], "trees": [],
           "bin_lo": np.asarray(spread[0]), "bin_hi": np.asarray(spread[1])}
    for k, tb in enumerate(tables):
        L = tb["num_leaves"]
        gh = [_grad_block(bl["score"], bl["y"]) for bl in blocks]
        sg = functools.reduce(jnp.maximum, [
            jnp.max(jnp.where(bl["valid"], jnp.abs(g), 0.0))
            for bl, (g, _) in zip(blocks, gh)])
        sh = functools.reduce(jnp.maximum, [
            jnp.max(jnp.where(bl["valid"], jnp.abs(h), 0.0))
            for bl, (_, h) in zip(blocks, gh)])
        sg, sh = jnp.maximum(sg, 1e-30), jnp.maximum(sh, 1e-30)
        total = None
        for bl, (g, h) in zip(blocks, gh):
            part = _hist_block(bl["bins"], bl["leaf"][k], bl["valid"], g, h,
                               sg, sh, precision=precision, num_bins=B,
                               num_leaves=L)
            total = part if total is None else total + part
        sums = np.asarray(jax.device_get(total))
        G, H, C, U, D = _dequant(sums, float(sg), float(sh), precision)
        # every feature sees every row once: feature 0's bins sum to the leaf
        lG, lH, lC, lU, lD = (a[0].sum(axis=0) for a in (G, H, C, U, D))
        with np.errstate(divide="ignore", invalid="ignore"):
            value = np.where(lC > 0, -lr * lG / (lH + l2), 0.0)
        # the state the next tree starts from: the program's, as its
        # trees state it (float32 as the program carries it)
        theirs = np.asarray(trees[k]["leaf_value"][:L], np.float64)
        if k == 0:
            theirs = theirs - float(program_init)
            for bl in blocks:
                bl["score"] = jnp.full(R, program_init, jnp.float32)
        v_d = jnp.asarray(theirs, jnp.float32)
        parts = []
        for bl in blocks:
            bl["score"] = bl["score"] + v_d[bl["leaf"][k]]
            parts.append(_loss_block(bl["score"], bl["y"], bl["valid"]))
        out["loss"].append(
            float(np.sum(jax.device_get(parts), dtype=np.float64)) / N)
        # a split is held against the best under either rounding of the
        # rows at a tie: the nearer of the two counts
        gaps = np.minimum(split_gaps(G, H, C, tb, num_bins, params),
                          split_gaps(G + U - D, H, C, tb, num_bins, params))
        tie_rows = int(round(float(sums[0, :, :, -2:].sum())))
        if k == 0:
            out["bin_count"] = C.sum(axis=2)                  # [F, B]
        out["trees"].append({
            "leaf_value": value, "leaf_count": lC, "leaf_grad": lG,
            "leaf_hess": lH, "leaf_up": lU, "leaf_down": lD,
            "tie_rows": tie_rows, "off_grid": tb["off_grid"],
            "split_gap": gaps})
        log(f"reference: tree {k} followed, loss {out['loss'][-1]:.9f}, "
            f"{tie_rows} rows at a tie")
    return out
