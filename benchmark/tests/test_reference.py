"""The plain reference on its own: exact sums, and rounding ties.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_reference.py -q

Run by hand.  The second case builds the event that one seed in twenty
showed on the chip (PERF.md, PR 25): after the first tree all the rows
of a leaf with one label share one gradient, and its 8-bit code lies
within float32's reach of a half, so that the program may soundly have
rounded thousands of rows the other way.
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmark import check                                  # noqa: E402
from benchmark.reference import gbdt as reference            # noqa: E402

PARAMS = {"learning_rate": 0.1, "lambda_l2": 0.0, "min_data_in_leaf": 20,
          "min_sum_hessian_in_leaf": 1e-3}
GRID = [np.array([0.25, 0.5, 0.75])]


def stump(threshold: float, values) -> dict:
    return {"num_leaves": 2, "split_feature": np.array([0]),
            "threshold": np.array([threshold]),
            "left_child": np.array([~0]), "right_child": np.array([~1]),
            "leaf_value": np.asarray(values, np.float64)}


def rows(n=8192, seed=5):
    rng = np.random.default_rng(seed)
    x = rng.random(n, dtype=np.float32)
    y = (rng.random(n) < 0.3).astype(np.float32)
    return x[None, :], y


def grad_f32(score: float, label: float):
    g, h = reference._grad_block(np.float32([score]), np.float32([label]))
    return np.float32(g[0]), np.float32(h[0])


def test_codes_flag_the_rows_at_a_tie():
    sg = np.float32(1.0)
    t = np.float32([42.4, 42.5 - 1e-4, 42.5 + 1e-4, -17.5 + 1e-4, 126.9])
    g = (t / np.float32(127.0)).astype(np.float32)
    codes = np.asarray(reference._codes(g, np.abs(g), sg, sg, "int8h"))
    assert codes[:, 0].tolist() == [42, 42, 43, -17, 127]
    assert codes[:, -2].tolist() == [0, 1, 0, 0, 0]          # up
    assert codes[:, -1].tolist() == [0, 0, 1, 1, 0]          # down


def test_sums_are_the_codes_sums_and_a_tie_is_an_interval():
    XT, y = rows()
    pavg = float(np.mean(y, dtype=np.float64))
    init = float(np.log(pavg / (1 - pavg)))
    # the right leaf far down: its positive rows set the scale max|g|
    sg = abs(float(grad_f32(init - 2.0, 1.0)[0]))

    def product(v):
        return float(grad_f32(np.float32(init) + np.float32(v), 0.0)[0]
                     * np.float32(127.0 / sg))
    # the left leaf's value: walk it to where its negative rows' code
    # lies just under a half
    lo, hi = 0.0, 0.5
    half = np.floor(product(lo)) + 1.5
    assert product(lo) < half < product(hi)
    for _ in range(60):
        mid = float(np.float32((lo + hi) / 2))
        if mid in (lo, hi):
            break
        lo, hi = (mid, hi) if product(mid) < half else (lo, mid)
    assert half - product(lo) < reference.TIE_WINDOW / 4
    trees = [stump(0.5, [init + lo, init - 2.0]), stump(0.25, [0.01, -0.01])]
    ref = reference.follow(XT, y, GRID, PARAMS, trees, init, "int8h")

    left = XT[0] <= 0.5
    tied = int(np.sum(left & (y == 0)))
    t0, t1 = ref["trees"]
    assert t0["leaf_count"].tolist() == [int(left.sum()), int((~left).sum())]
    assert t0["tie_rows"] == 0 and t1["tie_rows"] == tied
    step = sg / 127.0
    assert np.isclose(t1["leaf_up"].sum(), tied * step, rtol=1e-6)
    assert t1["leaf_down"].sum() == 0
    # its own sums are the sums of its codes
    score = np.float32(init) + np.float32(lo)
    code = [np.round(float(grad_f32(score, label)[0]) * 127.0 / sg)
            for label in (0.0, 1.0)]
    assert code[0] == half - 0.5
    in_leaf0 = XT[0] <= 0.25
    assert np.isclose(t1["leaf_grad"][0], step * (
        code[0] * np.sum(in_leaf0 & (y == 0))
        + code[1] * np.sum(in_leaf0 & (y == 1))), rtol=1e-6)

    def program(values1):
        return {"init": init, "loss": list(ref["loss"]), "trees": [
            {**trees[0], "leaf_count": t0["leaf_count"]},
            {**trees[1], "leaf_value": values1,
             "leaf_count": t1["leaf_count"]}]}
    first = dict(trees[0])
    first["leaf_value"] = t0["leaf_value"] + init
    trees[0] = first
    H = t1["leaf_hess"]
    for add, sound in ((0.0, True), (1.0, True), (0.5, True), (2.0, False),
                       (-0.5, False)):
        values = -0.1 * (t1["leaf_grad"] + add * t1["leaf_up"]) / H
        got, _ = check.compare(program(values), ref, PARAMS)
        assert (got["update_leaf_worst"] < 1e-9) == sound, (add, got)
        assert (got["update_leaf_p90"] < 1e-9) == sound, (add, got)
