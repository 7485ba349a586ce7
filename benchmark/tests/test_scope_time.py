"""``readers/scope_time.py`` by hand: the wire-format walk on a made-up
``XSpace``, the matching of name stacks, and the whole reading over the
traces recorded on the chip: ``recorded.xplane.pb.gz`` (PR 25, before
the program had scopes: read by ``jit(...)`` component, it gives the
table ISSUE 26 starts from) and ``recorded_scoped.xplane.pb.gz`` (PR 26,
two steps of ``criteo-67-b63.train`` with the scopes in the program).

    python3 -m pytest benchmark/tests/test_scope_time.py -q
"""
import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
from benchmark import trace                                 # noqa: E402
from benchmark.readers import scope_time                    # noqa: E402

RECORDED = os.path.join(HERE, "recorded.xplane.pb.gz")
SCOPED = os.path.join(HERE, "recorded_scoped.xplane.pb.gz")
METRICS = os.path.join(os.path.dirname(HERE), "metrics")
ITERATIONS = 2                                  # steps in each recording


# -- a protobuf writer of four lines, for the made-up XSpace ---------------
def varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def field(num: int, value) -> bytes:
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(num << 3 | 2) + varint(len(value)) + value


def entry(key: int, message: bytes) -> bytes:
    return field(1, key) + field(2, message)


def test_name_stacks_of_a_made_up_xspace():
    stat_meta = (field(5, entry(7, field(1, 7) + field(2, "tf_op")))
                 + field(5, entry(8, field(1, 8) + field(2, "flops")))
                 + field(5, entry(300, field(1, 300)
                                  + field(2, "jit(f)/tree.route/cond:"))))
    by_string = field(1, 1) + field(2, "%a = f32[] add(x, y)") + field(
        5, field(1, 8) + field(3, 12345)) + field(
        5, field(1, 7) + field(5, "jit(f)/obj.grad/add:"))
    by_ref = field(1, 2) + field(2, "%c = conditional(p)") + field(
        5, field(1, 7) + field(7, 300))
    bare = field(1, 3) + field(2, "%w = while(s)")
    line = field(3, field(1, 1) + field(2, "XLA Ops") + field(
        4, field(1, 1) + field(2, 10) + field(3, 20)))
    plane = (field(1, 0) + field(2, "/device:TPU:0") + line
             + field(4, entry(1, by_string)) + field(4, entry(2, by_ref))
             + field(4, entry(3, bare)) + stat_meta)
    other = field(2, "/host:CPU") + field(4, entry(1, bare))
    space = field(1, plane) + field(1, other) + field(4, "hostname")
    assert scope_time.name_stacks(space) == {"/device:TPU:0": {
        "%a = f32[] add(x, y)": "jit(f)/obj.grad/add:",
        "%c = conditional(p)": "jit(f)/tree.route/cond:"}}


def test_matching_of_name_stacks():
    kernel = ("jit(block)/while/body/closed_call/tree.hist/"
              "jit(hist_active_compact)/pallas_call")
    plan = ("jit(block)/while/body/closed_call/tree.hist/"
            "jit(hist_active_compact)/tree.compact.plan/jit(argsort)/sort")
    call, copy = "%hist_active_compact.1 custom-call s32[8,128]", \
        "%copy.5 copy s32[8,128]"

    def m(stack, short, **spec):
        return scope_time.matches(stack, short, spec)
    assert m(kernel, call, scope="tree.hist")
    assert m(plan, "%sort.1 sort s32[8]", scope="tree.hist")  # a component
    assert m(plan, "", scope="tree.compact.plan")
    assert not m(kernel, call, scope="tree.compact.plan")
    assert not m(plan, "", scope="tree.compact")              # no prefixes
    assert m(kernel, call, scope="tree.hist", leaf="pallas_call")
    assert not m(plan, "", scope="tree.hist", leaf="pallas_call")
    assert not m(kernel, call, scope="tree.hist",
                 except_leaf=["pallas_call"])
    op = " custom-call( |$)"
    assert m(kernel, call, scope="tree.hist", leaf="pallas_call", op=op)
    assert not m(kernel, copy, scope="tree.hist", leaf="pallas_call", op=op)
    # no scope of the program: JAX's own components do not count as one
    assert m("jit(block)/while/body/closed_call/jit(_where)/select_n", "",
             scope=None)
    assert m("", "%while.1 while u32[]", scope=None)
    assert not m(kernel, call, scope=None)
    assert not m("jit(f)/collective.hist_psum/psum", "", scope=None)
    assert scope_time.innermost(plan) == "tree.compact.plan"
    assert scope_time.innermost(kernel) == "tree.hist"
    assert scope_time.innermost("jit(block)/while/body/mul") == ""


def per_iter(path: str, **spec) -> float:
    hits = [s for stack, short, s in scope_time.stacked_self_times(path)
            if scope_time.matches(stack, short, spec)]
    return (len(hits) if spec.get("count") else 1000.0 * sum(hits)) \
        / ITERATIONS


def classes():
    return trace.load_classes(
        os.path.join(os.path.dirname(HERE), "op_classes"), ["plan", "hist"])


@pytest.mark.parametrize("component,spec,expected", [
    # ISSUE 26's table: self time per iteration [ms] by jit(...) component
    ("jit(hist_active_compact)", {"except_leaf": ["pallas_call"]}, 1731.5),
    ("jit(hist_active_compact)", {"leaf": "gather"}, 694.3 + 561.4),
    ("jit(_take)", {"leaf": "gather"}, 561.4),
    ("jit(hist_active_compact)", {"leaf": "scatter-add"}, 232.0),
    ("jit(hist_active_compact)", {"leaf": "scatter"}, 176.6),
    ("jit(argsort)", {"leaf": "sort"}, 48.7),
    ("jit(hist_active_pallas)", {"leaf": "pallas_call"}, 302.7),
    ("jit(hist_active_compact)", {"leaf": "pallas_call"}, 102.2),
    ("jit(route_rows_pallas)", {"leaf": "pallas_call"}, 29.7),
    ("jit(route_rows_values_pallas)", {"leaf": "pallas_call"}, 4.9),
])
def test_the_unscoped_recording_by_component(component, spec, expected):
    assert per_iter(RECORDED, scope=component, **spec) == \
        pytest.approx(expected, abs=0.06)


def test_the_unscoped_recording_counts_kernels_and_sums_to_the_total():
    call = {"leaf": "pallas_call", "op": " custom-call( |$)", "count": True}
    assert per_iter(RECORDED, scope="jit(hist_active_pallas)", **call) == 6
    assert per_iter(RECORDED, scope="jit(hist_active_compact)", **call) == 2
    # without `op`, the 2 us copy of a kernel's result counts as a call
    assert per_iter(RECORDED, scope="jit(hist_active_pallas)",
                    leaf="pallas_call", count=True) == 7
    # before the scopes everything is unscoped, and that is the total
    red = trace.reduce(RECORDED, classes())
    assert per_iter(RECORDED, scope=None) * ITERATIONS / 1000.0 == \
        pytest.approx(red["total_self_s"], rel=1e-9)
    assert per_iter(RECORDED, scope="tree.compact.plan") == 0.0


def metric(name: str) -> dict:
    with open(os.path.join(METRICS, name + ".json")) as f:
        spec = json.load(f)
    assert spec.pop("reader") == "scope_time"
    return spec


@pytest.mark.skipif(not os.path.exists(SCOPED), reason="no scoped recording")
def test_the_scoped_recording_partitions_the_device_time():
    red = trace.reduce(SCOPED, classes())
    events = scope_time.stacked_self_times(SCOPED)
    by_scope = {}
    for stack, _short, s in events:
        key = scope_time.innermost(stack)
        by_scope[key] = by_scope.get(key, 0.0) + s
    assert sum(by_scope.values()) == pytest.approx(red["total_self_s"],
                                                   rel=1e-9)
    assert set(by_scope) >= {
        "obj.grad", "tree.pack", "tree.init", "tree.route", "tree.hist",
        "tree.compact.plan", "tree.compact.regroup", "tree.split_find",
        "tree.update", "gbdt.score_update", ""}
    ms = {k: 1000.0 * v / ITERATIONS for k, v in by_scope.items()}
    # the metrics read what the partition holds (none of their scopes
    # nests in another, so a component is an innermost scope)
    for name, scope in (("learner.compact_plan_ms_per_iter",
                         "tree.compact.plan"),
                        ("learner.compact_regroup_ms_per_iter",
                         "tree.compact.regroup"),
                        ("learner.route_ms_per_iter", "tree.route"),
                        ("learner.split_find_ms_per_iter", "tree.split_find"),
                        ("loop.grad_ms_per_iter", "obj.grad"),
                        ("block.unscoped_ms_per_iter", "")):
        assert per_iter(SCOPED, **metric(name)) == \
            pytest.approx(ms[scope], rel=1e-9), name
    # the compaction is where ISSUE 26 found it, now by its own names
    compaction = ms["tree.compact.plan"] + ms["tree.compact.regroup"]
    assert compaction == pytest.approx(1731.4, rel=0.01)
    assert ms["tree.compact.plan"] > ms["tree.compact.regroup"]
    assert ms[""] < 0.01 * 1000.0 * red["total_self_s"] / ITERATIONS
    assert per_iter(SCOPED, **metric("kernels.hist_calls_per_iter")) == 8
    # the kernels under tree.hist are class `hist`, to the nanosecond
    kernels = per_iter(SCOPED, scope="tree.hist", leaf="pallas_call",
                       op=" custom-call( |$)")
    assert kernels == pytest.approx(
        1000.0 * red["class_s"]["hist"] / ITERATIONS, rel=1e-9)
    # the program's spans are on the trace's clock
    assert any(name.startswith("gbdt.") for name, _ in red["idle_gaps"])


def test_read_takes_the_newest_trace_of_the_temporary_directory(
        tmp_path, monkeypatch):
    import tempfile
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    reading = {"trace": {"window_s": 1.0}, "iterations": ITERATIONS}
    spec = {"scope": "jit(hist_active_compact)", "leaf": "pallas_call"}
    assert scope_time.read(reading, spec) is None          # no trace there
    assert scope_time.read({"trace": None}, spec) is None  # --trace 0
    where = tmp_path / "bench_trace_x" / "plugins" / "profile" / "t"
    where.mkdir(parents=True)
    with gzip.open(RECORDED, "rb") as f:
        (where / "host.xplane.pb").write_bytes(f.read())
    assert scope_time.read(reading, spec) == pytest.approx(102.2, abs=0.06)
    assert scope_time.read(reading, {"scope": "tree.route"}) == 0.0
