"""``readers/program_time.py`` and ``readers/self_at.py`` by hand, on a
made-up reading, and the set-up metrics of PR 39 resolved as ``run.py``
resolves every metric: a traced run of the tiny cell on the CPU.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_program_time.py -q

Run by hand (not part of the repo's tier-1 tests; the last case drives a
whole run, ~60 s).
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
from benchmark.readers import program_time, self_at         # noqa: E402

METRICS = os.path.join(os.path.dirname(HERE), "metrics")
SETUP_METRICS = [
    "loop.compile_trace_s", "loop.compile_lower_s", "loop.compile_backend_s",
    "loop.compile_other_s", "loop.small_programs", "loop.small_compile_s",
    "loop.init_s", "loop.train_call_other_s", "ingest.construct_s",
    "ingest.sample_s", "ingest.pack_s", "ingest.other_s",
    "loop.window_programs"]


def row(count, trace, lower, backend):
    return {"count": count, "trace_s": trace, "lower_s": lower,
            "backend_s": backend}


def reading():
    """Three marks of a made-up run: before ``lgb.train`` one small
    program, by ``setup`` the block program and two more small ones,
    afterwards (``live``) a retrace of the block and one new program."""
    start = {"spans": {"io.construct": {"count": 1, "total_s": 14.0,
                                        "max_s": 14.0, "self_s": 0.5},
                       "io.find_bin": {"count": 67, "total_s": 9.0,
                                       "max_s": 0.2}},
             "programs": {"convert_element_type": row(1, 0.1, 0.2, 0.3)}}
    setup = {"spans": dict(start["spans"]),
             "programs": {"convert_element_type": row(2, 0.2, 0.4, 0.6),
                          "block": row(1, 4.0, 3.0, 20.0),
                          "_loss_parts": row(1, 0.01, 0.02, 0.07)}}
    live = {"programs": {**setup["programs"],
                         "block": row(2, 8.0, 6.0, 40.0),
                         "_bin_block": row(1, 0.0, 0.0, 0.1)}}
    return {"obs": {"start": start, "setup": setup}}, live


def spec(name):
    with open(os.path.join(METRICS, name + ".json")) as f:
        return json.load(f)


def test_a_program_by_name_between_two_marks():
    r, _ = reading()
    assert program_time.read(r, spec("loop.compile_trace_s")) == 4.0
    assert program_time.read(r, spec("loop.compile_lower_s")) == 3.0
    assert program_time.read(r, spec("loop.compile_backend_s")) == 20.0
    # a row the table does not hold reads 0, not nothing
    assert program_time.read(r, {"program": "absent", "fields": ["count"],
                                 "between": ["start", "setup"]}) == 0.0


def test_all_but_the_named_programs():
    r, _ = reading()
    # what `start` already held is taken off: one more convert, the loss
    assert program_time.read(r, spec("loop.small_programs")) == 2.0
    assert program_time.read(r, spec("loop.small_compile_s")) == \
        pytest.approx(0.1 + 0.2 + 0.3 + 0.01 + 0.02 + 0.07)


def test_since_a_mark_reads_the_live_summary(monkeypatch):
    r, live = reading()
    from lightgbm_tpu import obs
    monkeypatch.setattr(obs, "summary", lambda: live)
    assert program_time.read(r, spec("loop.window_programs")) == 2.0
    monkeypatch.setattr(obs, "summary", lambda: r["obs"]["setup"])
    assert program_time.read(r, spec("loop.window_programs")) == 0.0


def test_a_program_without_the_table_reads_nothing(monkeypatch):
    r, live = reading()
    for mark in r["obs"].values():
        del mark["programs"]
    from lightgbm_tpu import obs
    monkeypatch.setattr(obs, "summary", lambda: {"spans": {}})
    for name in SETUP_METRICS:
        s = spec(name)
        if s["reader"] == "program_time":
            assert program_time.read(r, s) is None, name


def test_self_time_at_a_mark():
    r, _ = reading()
    assert self_at.read(r, spec("ingest.other_s")) == 0.5
    # no such span, or a span of a program that keeps no self time
    assert self_at.read(r, spec("ingest.sample_s")) is None
    assert self_at.read(r, {"span": "io.find_bin", "at": "start"}) is None


def test_every_set_up_metric_resolves_on_the_tiny_cell(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_HIST_BACKEND", "pallas")
    import rehearse
    result = rehearse.patched_run(
        ["--seed", "3000000019", "--seconds", "0.5", "--trace", "1"])
    got = {n: m["value"] for n, m in result["metrics"].items()}
    assert set(SETUP_METRICS) <= set(got), set(SETUP_METRICS) - set(got)
    assert all(got[n] >= 0 for n in SETUP_METRICS)
    assert got["loop.compile_backend_s"] > 0
    # the identities the numbers are there for (the CPU's few seconds of
    # compile leave the small programs under `gbdt.block_compile` a
    # larger share than the chip's: 5% here, 1% there)
    parts = sum(got["loop.compile_" + p]
                for p in ("trace_s", "lower_s", "backend_s", "other_s"))
    assert parts == pytest.approx(got["loop.compile_s"], rel=0.05)
    ingest = sum(got["ingest." + p] for p in (
        "find_bin_s", "value_to_bin_s", "sample_s", "pack_s", "other_s"))
    assert ingest == pytest.approx(got["ingest.construct_s"], rel=0.01)
    assert got["ingest.construct_s"] == pytest.approx(got["ingest.bin_s"],
                                                      abs=0.1)
