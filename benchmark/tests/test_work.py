"""``work.py`` by hand: a three-leaf tree gives the hand-computed rows,
bytes and operations.  ``python3 -m pytest benchmark/tests/test_work.py``
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmark import work                                  # noqa: E402

SHAPE = {"rows": 1000, "features": 10, "bins": 63, "leaves": 255,
         "hist_mode": "int8h"}
# root 1000 -> (300, 700); the 700 -> (450, 250): leaves 300, 450, 250
TREE = (1000, [(300, 700), (450, 250)])


def test_three_leaf_tree_rows_bytes_ops():
    # root 1000 + smaller children 300 and 250
    assert work.hist_rows(TREE) == 1550
    h = work.histogram(SHAPE, [TREE])
    assert h["hist_rows"] == 1550
    assert h["bytes"] == 1550 * (10 + 3)        # 10 bin bytes + g, h_hi, h_lo
    assert h["ops"] == 2 * 1550 * 10 and h["unit"] == "int8"
    it = work.iteration(SHAPE, [TREE])
    cells = 255 * 10 * 63
    assert it["bytes"] == h["bytes"] + 16 * 1000 + 26 * 1000 + 12 * cells
    assert it["ops"] == h["ops"] + 12 * 1000 + 12 * cells


def test_least_time_names_its_bound_and_refuses_unknown_chips():
    h = work.histogram(SHAPE, [TREE, TREE])
    seconds, bound = work.least_time(h, "TPU v5 lite")
    assert bound == "bandwidth"
    assert seconds == pytest.approx(2 * 1550 * 13 / 819e9)
    with pytest.raises(KeyError):
        work.least_time(h, "TPU v9")
    with pytest.raises(KeyError):
        work.peaks("source")
