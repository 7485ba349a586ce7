"""Set-up from the inside (PR 39): self time on every span, the compile
record (``compile.trace`` / ``.lower`` / ``.backend`` spans and the
summary's ``programs`` table, from ``jax.monitoring``), and the stages
of ingest and ``lgb.train``.  One small ``jit`` a test at most; the
phases a test needs exactly are fed to ``jax.monitoring`` by hand.
"""
import io
import json
import os
import sys
import threading
import time

import jax
import numpy as np
import pytest
from jax._src import monitoring as jax_monitoring

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs import telemetry as tmod

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.reset()
    yield
    obs.reset()


def _ours(listeners) -> list:
    return [f for f in listeners
            if getattr(f, "__module__", "") == tmod.__name__]


def _phase(event: str, fun: str, dur: float, inside=lambda: None) -> None:
    """One compile phase as JAX reports it: the scalar at entry, the
    duration at exit, ``inside`` run between them."""
    jax.monitoring.record_scalar(event, time.time(), fun_name=fun)
    inside()
    jax.monitoring.record_event_duration_secs(event, dur, fun_name=fun)


def _spans():
    return obs.summary()["spans"]


# ---------------------------------------------------------------------------
# self time
# ---------------------------------------------------------------------------
def test_self_time_of_nested_spans():
    obs.enable()
    with obs.span("outer"):
        time.sleep(0.02)
        with obs.span("mid"):
            time.sleep(0.02)
            with obs.span("leaf"):
                time.sleep(0.02)
    s = _spans()
    for name in ("outer", "mid", "leaf"):
        assert set(s[name]) == {"count", "total_s", "max_s", "self_s"}
    assert s["leaf"]["self_s"] == s["leaf"]["total_s"]
    # a span's self time is its own less its DIRECT children's: the leaf
    # comes off `mid`, and off `outer` only as a part of `mid`
    assert s["mid"]["self_s"] == pytest.approx(
        s["mid"]["total_s"] - s["leaf"]["total_s"], abs=1e-9)
    assert s["outer"]["self_s"] == pytest.approx(
        s["outer"]["total_s"] - s["mid"]["total_s"], abs=1e-9)
    assert 0.015 < s["outer"]["self_s"] < s["outer"]["total_s"] - 0.03


def test_self_time_of_sibling_spans_sums_by_name():
    obs.enable()
    with obs.span("parent"):
        for _ in range(3):
            with obs.span("child"):
                time.sleep(0.01)
        with obs.span("other"):
            time.sleep(0.01)
    s = _spans()
    assert s["child"]["count"] == 3
    assert s["parent"]["self_s"] == pytest.approx(
        s["parent"]["total_s"] - s["child"]["total_s"]
        - s["other"]["total_s"], abs=1e-9)
    assert s["parent"]["self_s"] < 0.01


def test_a_span_on_another_thread_is_no_child():
    """The binning's row blocks run on threads under one span opened
    by the caller: their time stays in the caller's self time."""
    obs.enable()

    def work():
        with obs.span("worker"):
            time.sleep(0.03)

    with obs.span("caller"):
        t = threading.Thread(target=work)
        t.start()
        t.join()
    s = _spans()
    assert s["worker"]["self_s"] == s["worker"]["total_s"] >= 0.03
    assert s["caller"]["self_s"] == s["caller"]["total_s"] >= 0.03


def test_merged_summary_sums_self_time_and_programs():
    obs.enable()
    with obs.span("a"):
        with obs.span("b"):
            time.sleep(0.01)
    _phase(BACKEND, "jit(f)", 0.5)
    one = obs.summary()
    merged = obs.merged_summary(lambda s: [s, json.loads(json.dumps(s))])
    for name in ("a", "b"):
        assert merged["spans"][name]["self_s"] == pytest.approx(
            2 * one["spans"][name]["self_s"])
        assert merged["spans"][name]["count"] == 2
    assert merged["programs"]["f"] == {"count": 2, "trace_s": 0.0,
                                       "lower_s": 0.0, "backend_s": 1.0}
    # a rank of an older program (no self time in its spans) still merges
    del one["spans"]["a"]["self_s"]
    assert obs.merged_summary(lambda s: [one])["spans"]["a"]["self_s"] == 0


def test_span_record_carries_self_time(tmp_path):
    trace = str(tmp_path / "t.jsonl")
    obs.enable(trace_path=trace)
    with obs.span("outer"):
        _phase(LOWER, "jit(g)", 0.25)
    obs.disable()
    with open(trace) as f:
        recs = {r["name"]: r for r in map(json.loads, f)}
    assert recs["compile.lower"]["fun_name"] == "g"
    assert recs["compile.lower"]["parent"] == "outer"
    assert recs["compile.lower"]["depth"] == 1
    assert recs["compile.lower"]["self_s"] == recs["compile.lower"]["dur_s"] \
        == 0.25
    assert recs["outer"]["self_s"] == pytest.approx(
        max(recs["outer"]["dur_s"] - 0.25, 0.0))


# ---------------------------------------------------------------------------
# the compile record
# ---------------------------------------------------------------------------
def test_a_jitted_function_is_one_row_and_three_phases():
    obs.enable()
    x = np.ones(3, np.float32)

    def triple_me(v):
        return jax.lax.mul(v, jax.lax.full_like(v, 3.0))

    f = jax.jit(triple_me)
    with obs.span("caller"):
        f(x)
    s = obs.summary()
    assert s["programs"]["triple_me"]["count"] == 1
    assert all(s["programs"]["triple_me"][c] > 0
               for c in ("trace_s", "lower_s", "backend_s"))
    counts = {k: v["count"] for k, v in s["spans"].items()}
    assert counts["compile.lower"] == counts["compile.backend"] == 1
    assert counts["compile.trace"] >= 1
    # the phases are the caller's children: its self time is none of them
    phases = sum(s["programs"]["triple_me"].values()) - 1
    assert s["spans"]["caller"]["self_s"] == pytest.approx(
        s["spans"]["caller"]["total_s"] - phases, abs=1e-6)
    f(x)                                # cached: nothing compiles
    again = obs.summary()
    assert again["programs"] == s["programs"]
    assert {k: v["count"] for k, v in again["spans"].items()} == counts


def test_a_jit_traced_inside_a_jit_is_not_counted_twice():
    obs.enable()
    with obs.span("site"):
        _phase(TRACE, "outer", 1.0,
               inside=lambda: _phase(TRACE, "inner", 0.4))
        _phase(LOWER, "jit(outer)", 0.5,
               inside=lambda: _phase(TRACE, "less", 0.1))
        _phase(BACKEND, "jit(outer)", 2.0)
    s = obs.summary()
    # by name the inner trace's seconds stand twice in total_s, once in
    # self_s
    assert s["spans"]["compile.trace"]["count"] == 3
    assert s["spans"]["compile.trace"]["total_s"] == pytest.approx(1.5)
    assert s["spans"]["compile.trace"]["self_s"] == pytest.approx(1.1)
    assert s["spans"]["compile.lower"]["self_s"] == pytest.approx(0.4)
    # the program's row holds its own phases whole; what is traced into
    # it has no row
    assert s["programs"] == {"outer": {"count": 1, "trace_s": 1.0,
                                       "lower_s": 0.5, "backend_s": 2.0}}
    site = s["spans"]["site"]
    assert site["self_s"] == pytest.approx(max(site["total_s"] - 3.5, 0.0))


def test_a_real_nested_jit_has_no_row():
    obs.enable()

    @jax.jit
    def inner_fn(v):
        return jax.lax.add(v, v)

    def outer_fn(v):
        return jax.lax.mul(inner_fn(v), v)

    jax.jit(outer_fn)(np.ones(2, np.float32))
    s = obs.summary()
    assert "inner_fn" not in s["programs"]
    assert s["programs"]["outer_fn"]["count"] == 1
    t = s["spans"]["compile.trace"]
    assert t["count"] >= 2 and t["self_s"] < t["total_s"]
    assert s["programs"]["outer_fn"]["trace_s"] <= t["self_s"] + 1e-9


def test_block_compile_self_time_is_none_of_its_phases():
    rng = np.random.RandomState(0)
    X = rng.normal(size=(600, 5)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    obs.enable()
    lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1},
              lgb.Dataset(X, label=y), num_boost_round=2)
    s = obs.summary()
    bc = s["spans"]["gbdt.block_compile"]
    block = s["programs"]["block"]
    assert block["count"] == 1 and block["backend_s"] > 0
    phases = block["trace_s"] + block["lower_s"] + block["backend_s"]
    assert bc["count"] == 1
    assert bc["self_s"] <= bc["total_s"] - phases + 1e-6
    # no counter says the same under a second name
    assert not [k for k in s["counters"] if "block_compile" in k]
    # the stages of the call: the booster's construction holds the
    # upload, the loop the compile
    sp = s["spans"]
    assert sp["gbdt.init"]["count"] == 1
    assert sp["gbdt.init"]["total_s"] >= sp["gbdt.upload"]["total_s"]
    assert sp["engine.train"]["self_s"] <= (
        sp["engine.train"]["total_s"] - sp["gbdt.init"]["total_s"]
        - sp["gbdt.train"]["total_s"] + 1e-6)


def test_a_compile_on_another_thread_is_that_threads():
    """The metric programs compile on threads beside the block program:
    their phases are no children of the span open on the main thread."""
    obs.enable()

    def compile_elsewhere():
        with obs.span("eval.compile"):
            _phase(BACKEND, "jit(eval_program)", 3.0,
                   inside=lambda: time.sleep(0.02))

    with obs.span("gbdt.block_compile"):
        t = threading.Thread(target=compile_elsewhere)
        t.start()
        t.join()
        _phase(BACKEND, "jit(block)", 0.001)
    s = obs.summary()
    bc = s["spans"]["gbdt.block_compile"]
    assert bc["self_s"] == pytest.approx(bc["total_s"] - 0.001, abs=1e-9)
    assert s["spans"]["eval.compile"]["self_s"] == 0.0
    assert s["programs"]["eval_program"]["backend_s"] == 3.0


def test_disabled_registers_nothing_and_records_nothing():
    assert not obs.enabled()
    before = [len(f()) for f in (jax_monitoring.get_scalar_listeners,
                                 jax_monitoring.get_event_duration_listeners,
                                 jax_monitoring.get_event_listeners)]
    with obs.span("x"):
        jax.jit(lambda v: jax.lax.neg(v))(np.ones(2, np.float32))
    assert not _ours(jax_monitoring.get_scalar_listeners())
    assert not _ours(jax_monitoring.get_event_duration_listeners())
    assert not _ours(jax_monitoring.get_event_listeners())
    assert [len(f()) for f in (
        jax_monitoring.get_scalar_listeners,
        jax_monitoring.get_event_duration_listeners,
        jax_monitoring.get_event_listeners)] == before
    s = obs.summary()
    assert s["spans"] == {} and s["programs"] == {} and s["counters"] == {}


def test_listeners_are_registered_once_and_reset_unregisters():
    obs.enable()
    obs.enable()
    with obs.span("x"):
        pass
    for get in (jax_monitoring.get_scalar_listeners,
                jax_monitoring.get_event_duration_listeners,
                jax_monitoring.get_event_listeners):
        assert len(_ours(get())) == 1
    # disabled, the listeners stay and record nothing
    obs.disable()
    _phase(BACKEND, "jit(f)", 1.0)
    assert obs.summary()["programs"] == {}
    obs.reset()
    for get in (jax_monitoring.get_scalar_listeners,
                jax_monitoring.get_event_duration_listeners,
                jax_monitoring.get_event_listeners):
        assert not _ours(get())


def test_programs_table_stops_at_its_bound():
    obs.enable()
    for i in range(tmod._PROGRAMS_MAX + 40):
        _phase(BACKEND, f"jit(f{i})", 0.001)
    table = obs.summary()["programs"]
    assert len(table) == tmod._PROGRAMS_MAX + 1
    assert table["(other)"]["count"] == 40
    assert table["(other)"]["backend_s"] == pytest.approx(0.04)
    assert sum(r["count"] for r in table.values()) == tmod._PROGRAMS_MAX + 40
    # a name the table holds is still found once it is full
    _phase(BACKEND, "jit(f3)", 0.001)
    assert obs.summary()["programs"]["f3"]["count"] == 2


def test_cache_events_are_counters():
    obs.enable()
    with obs.span("x"):
        pass
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/compilation_cache/tasks_using_cache")
    c = obs.summary()["counters"]
    assert c == {"compile.cache_hits": 1, "compile.cache_misses": 2}


def test_a_phase_without_its_start_or_its_end_keeps_the_stack_whole():
    obs.enable()
    with obs.span("outer"):
        # telemetry came on in the middle of this phase: its end alone
        jax.monitoring.record_event_duration_secs(
            LOWER, 0.2, fun_name="jit(half)")
        with obs.span("inner"):
            # a phase that never ends (the interpreter is going down)
            jax.monitoring.record_scalar(TRACE, time.time(), fun_name="lost")
        with obs.span("after"):
            pass
    s = obs.summary()
    assert s["programs"]["half"]["lower_s"] == 0.2
    assert "lost" not in s["programs"]
    assert s["spans"]["outer"]["self_s"] == pytest.approx(
        max(s["spans"]["outer"]["total_s"] - 0.2
            - s["spans"]["inner"]["total_s"]
            - s["spans"]["after"]["total_s"], 0.0), abs=1e-9)
    assert not tmod._tls.stack


# ---------------------------------------------------------------------------
# stages where the seconds are
# ---------------------------------------------------------------------------
def test_construct_children_sum_to_its_total_less_self():
    rng = np.random.RandomState(1)
    X = rng.normal(size=(2000, 6)).astype(np.float32)
    X[:, 4] = 0.0
    X[rng.rand(2000) < 0.05, 4] = 1.0
    X[:, 5] = 0.0
    X[rng.rand(2000) < 0.05, 5] = 2.0
    y = (X[:, 0] > 0).astype(np.float32)
    obs.enable()
    lgb.Dataset(X, label=y).construct()
    s = _spans()
    c = s["io.construct"]
    children = ("io.sample", "io.value_to_bin", "io.efb", "io.pack")
    assert all(s[k]["count"] == 1 for k in children)
    assert sum(s[k]["total_s"] for k in children) == pytest.approx(
        c["total_s"] - c["self_s"], abs=1e-6)
    # `io.find_bin` (one a feature) is `io.sample`'s child
    assert s["io.find_bin"]["count"] == 6
    assert s["io.sample"]["self_s"] == pytest.approx(
        s["io.sample"]["total_s"] - s["io.find_bin"]["total_s"], abs=1e-6)


def test_report_prints_self_time(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)
    trace = str(tmp_path / "t.jsonl")
    obs.enable(trace_path=trace)
    with obs.span("engine.train"):
        _phase(BACKEND, "jit(block)", 0.75)
    summary = obs.summary()
    obs.disable()
    out = io.StringIO()
    telemetry_report.report(telemetry_report.load_records([trace]), out=out)
    lines = out.getvalue().splitlines()
    head = next(l for l in lines if l.startswith("phase"))
    assert head.split() == ["phase", "count", "total_s", "self_s", "share",
                            "max_s"]
    row = next(l for l in lines if "compile.backend" in l).split()
    assert row[:4] == ["compile.backend", "1", "0.750", "0.750"]
    out = io.StringIO()
    telemetry_report.report_summary(summary, out=out)
    text = out.getvalue()
    assert "self_s" in text
    assert any(l.split()[:2] == ["block", "1"] for l in text.splitlines())
