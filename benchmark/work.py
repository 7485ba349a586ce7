"""What the algorithm needs, whatever implements it: operations and
bytes as functions of a cell's shapes and of the trees a window built,
and the least time a chip could take for them from its published peaks.

``shape``: ``rows, features, bins, leaves, hist_mode``.  ``trees``: one
``(rows, [(left count, right count), ...])`` per tree, the pairs those of
its internal nodes.  Never launch counts, never what a kernel happens to
move.
"""
from __future__ import annotations

import json
import os

# value bytes a row brings to a histogram: its gradient and hessian as
# the mode rounds them
VALUE_BYTES = {"int8": 2, "int8h": 3, "int8hh": 4, "bf16": 4, "hilo": 8,
               "hhilo": 6, "ghilo": 6}
INT8_MODES = ("int8", "int8h", "int8hh")


def hist_rows(tree) -> int:
    """Rows histogrammed for one tree under the smaller-child rule: all
    rows for the root, then the smaller child of every split (its
    sibling is the parent less it)."""
    rows, splits = tree
    return rows + sum(min(left, right) for left, right in splits)


def histogram(shape: dict, trees: list) -> dict:
    """Building the histograms of ``trees``: each row histogrammed reads
    its ``features`` bin bytes and its value bytes, and adds a gradient
    and a hessian into one cell per feature."""
    n = sum(hist_rows(t) for t in trees)
    F = int(shape["features"])
    unit = "int8" if shape["hist_mode"] in INT8_MODES else "bf16"
    return {"ops": 2 * n * F, "unit": unit,
            "bytes": n * (F + VALUE_BYTES[shape["hist_mode"]]),
            "hist_rows": n}


def iteration(shape: dict, trees: list) -> dict:
    """One whole boosting iteration per tree: the histograms, one pass
    over scores, labels and gradients for the objective (read score and
    label, write gradient and hessian: 16 bytes a row, ~10 operations),
    one over the bin columns and leaf ids for routing and the score
    update (``features`` + 4 + 4 read, 4 + 4 written), and the split
    scan over leaves x features x bins x 3 floats (~12 operations a
    cell)."""
    h = histogram(shape, trees)
    N, F = int(shape["rows"]), int(shape["features"])
    cells = int(shape["leaves"]) * F * int(shape["bins"])
    T = len(trees)
    return {"ops": h["ops"] + T * (10 * N + 2 * N + 12 * cells),
            "unit": h["unit"],
            "bytes": h["bytes"] + T * (16 * N + (F + 16) * N + 12 * cells),
            "hist_rows": h["hist_rows"]}


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, not a default."""
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]


def least_time(w: dict, device_kind: str):
    """``-> (seconds, "compute" | "bandwidth")``: the larger of
    operations over peak and bytes over peak bandwidth."""
    p = peaks(device_kind)
    rate = p["int8_ops_per_s"] if w["unit"] == "int8" else p["bf16_flops_per_s"]
    by_ops = w["ops"] / rate
    by_bytes = w["bytes"] / p["hbm_bytes_per_s"]
    return (by_ops, "compute") if by_ops >= by_bytes else (by_bytes, "bandwidth")
