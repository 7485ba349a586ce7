"""``correct`` holds the sum over a chip's row chunks: the tiny cell on
the CPU with the rows one int32 cell may sum cut down to one row tile,
so that every histogram call and the root's sums run in three chunks.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_correct_chunks.py -q

Run by hand; ``tests/test_row_chunks.py`` runs the same two cases among
the repo's tier-1 tests.

* the chunked program reads ``correct`` true;
* with the chunk sum bypassed (the partials added in ONE int32, as a
  single accumulator over all rows adds them) it reads false.  6,000
  rows cannot pass 2^31, so the bypass counts each code 2^13 times over:
  a chunk of 2,048 rows then stays under 2^31 (2,048 x 127 x 8,192 =
  2.13e9) and all 6,000 rows do not (6.24e9, which wraps to a third of
  itself), which is the cell ``criteo-67-b63-c32.train`` to scale:
  13.28M rows a chunk x 127 = 1.69e9, 53.1M rows x 127 = 6.75e9.  (At
  2^12 the root's hessian total wraps below zero and the program grows
  no tree at all, which the harness has no verdict for.)
"""
import contextlib
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
ARGV = ["--seed", "3000000029", "--seconds", "0.5"]
CHUNK_ROWS = 2048              # one row tile: the tiny cell's 6,144 are 3
OVERCOUNT_BITS = 13


@contextlib.contextmanager
def three_chunks(monkeypatch):
    """The quantised kernel path, interpreted, with the bound of a chunk
    at one row tile."""
    import jax
    from lightgbm_tpu.learner import serial
    monkeypatch.setenv("LGBM_TPU_HIST_BACKEND", "pallas")
    monkeypatch.setattr(serial, "_INT8_ROW_LIMIT", CHUNK_ROWS)
    # the kernels' jitted wrappers are traced once a shape: a test before
    # this one may have traced this shape with another sum
    jax.clear_caches()
    try:
        yield
    finally:
        jax.clear_caches()


@pytest.fixture
def chunked(monkeypatch):
    with three_chunks(monkeypatch):
        yield monkeypatch


def run_tiny():
    import rehearse
    return rehearse.patched_run(ARGV)


def over(result):
    return sorted(n for n, c in result["compared"].items()
                  if not c["value"] <= c["limit"])


def one_accumulator(parts):
    """The chunks' partials added as one int32 accumulator adds all the
    rows, every code counted ``2^OVERCOUNT_BITS`` times: the sum wraps
    where that accumulator would."""
    from lightgbm_tpu.ops.pallas_histogram import code_limbs
    total = parts[0] << OVERCOUNT_BITS
    for p in parts[1:]:
        total = total + (p << OVERCOUNT_BITS)
    return code_limbs(total >> OVERCOUNT_BITS)


def test_chunked_run_is_correct(chunked):
    from lightgbm_tpu import obs
    result = run_tiny()
    assert result["correct"], over(result)
    assert result["failed"] == 0 and result["attempted"] >= 1
    gauges = obs.summary()["gauges"]
    assert gauges["hist.row_chunks"] == 3
    assert gauges["gbdt.hist_mode"] == "int8h"


def test_wrapped_sum_is_not_correct(chunked):
    from lightgbm_tpu.learner import serial
    from lightgbm_tpu.ops import pallas_histogram
    chunked.setattr(serial, "sum_code_limbs", one_accumulator)
    chunked.setattr(pallas_histogram, "sum_code_limbs", one_accumulator)
    result = run_tiny()
    assert not result["correct"]
    print("over their limits:", {n: result["compared"][n] for n in over(result)})
    assert {"grad_leaf", "update_leaf_worst"} & set(over(result))
