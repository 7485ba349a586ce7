"""What evaluating a set's metrics needs, whatever implements it: each
metric reads its set's scores and labels once (8 bytes a row) and does a
handful of operations a row (the link, a logarithm, a compare: ~10).
An exact AUC also needs the rows in score order; a sort's operations
depend on how it is done, so none are counted for it: the share of the
peak this work enters reads lower for that, never higher."""
from __future__ import annotations


def evaluation(rows_by_set: list, metrics: int) -> dict:
    """One evaluation of ``metrics`` metrics over sets of
    ``rows_by_set`` rows: ``{"ops", "bytes"}``."""
    rows = sum(int(r) for r in rows_by_set)
    return {"ops": 10 * metrics * rows, "bytes": 8 * metrics * rows}


def with_evaluations(w: dict, each: dict, count: int) -> dict:
    """The work ``w`` (``work.iteration``) and ``count`` evaluations
    ``each`` (:func:`evaluation`)."""
    return {**w, "ops": w["ops"] + count * each["ops"],
            "bytes": w["bytes"] + count * each["bytes"]}
