"""Job kind ``train_dp``: job ``train`` on a cell of several chips whose
rows are sharded over them (``tree_learner=data``).

Everything is ``train``'s: the rows of all shards are made from the
seed in one process, ``lgb.train`` builds its own ``Mesh`` of the
host's chips from the configuration's ``tree_learner``, the window and
the rate count the rows of all shards, and the plain reference follows
the trees over the union of the rows.  It differs in one place.  The
trace's device times are averages over the devices that ran anything
(``trace.reduce``), so a share of a peak has to be of one chip's work:
the operations and bytes of ``work.py``, which are those of all the
rows, are handed on divided by the number of chips.  (Every chip scans
the whole of a wave's histograms for splits, 13 MB a tree that this
division counts a quarter of: the share reads that much lower, never
higher.)
"""
from __future__ import annotations

from benchmark.jobs import train


def per_chip(w: dict, chips: int) -> dict:
    """One chip's share of the work ``w`` of a row-sharded step."""
    return {k: v if k == "unit" else v / chips for k, v in w.items()}


def run(ctx) -> dict:
    out = train.run(ctx)
    work = out["reading"].get("work")
    if work is not None:
        out["reading"]["work"] = {name: per_chip(w, len(ctx.devices))
                                  for name, w in work.items()}
    return out
