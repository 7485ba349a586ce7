"""``trace.py`` by hand: the interval arithmetic on made-up events, and
the whole reduction over a small trace recorded on the chip
(``recorded.xplane.pb.gz``: two steps of ``criteo-67-b63.train``, PR 25).

    python3 -m pytest benchmark/tests/test_trace.py -q
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
from benchmark import trace                                 # noqa: E402

RECORDED = os.path.join(HERE, "recorded.xplane.pb.gz")


def test_self_time_takes_children_out_of_their_parent():
    # a while loop of 100 that runs two ops of 30 and 50, then an op of 20
    ev = sorted([("while", 0.0, 100.0), ("a", 10.0, 40.0),
                 ("b", 40.0, 90.0), ("c", 100.0, 120.0)],
                key=lambda t: (t[1], -(t[2] - t[1])))
    assert trace.self_times(ev) == [20.0, 30.0, 50.0, 20.0]


def test_self_time_of_doubly_nested_events():
    ev = [("outer", 0.0, 100.0), ("mid", 10.0, 90.0), ("leaf", 20.0, 30.0)]
    assert trace.self_times(ev) == [20.0, 70.0, 10.0]


def test_union_merges_overlaps_and_keeps_gaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_short_name_keeps_an_instruction_apart_from_its_operands():
    hlo = ("%fusion.192 = u8[13289472,67]{1,0:T(8,128)(4,1)} fusion(u8[67,"
           "13281280]{0,1:T(8,128)(4,1)} %copy.526, s32[13289472]{0:T(1024)"
           "S(1)} %hist_active_pallas.1), kind=kCustom, calls=%fused.192")
    assert trace.short_name(hlo) == \
        "%fusion.192 fusion kCustom u8[13289472,67]"
    kernel = ("%hist_active_pallas.5 = s32[5120,128]{1,0:T(8,128)S(1)} "
              "custom-call(s32[32,1]{1,0:T(8,128)S(1)} %copy.508)")
    assert trace.short_name(kernel) == \
        "%hist_active_pallas.5 custom-call s32[5120,128]"
    tup = ("%sort.265 = (s32[13281280]{0:T(1024)}, s32[13281280]{0:T(1024)})"
           " sort(s32[13281280]{0:T(1024)S(1)} %bitcast.1555), dimensions={0}")
    assert trace.short_name(tup) == "%sort.265 sort s32[13281280]"
    assert trace.short_name("dot_general.1") == "dot_general.1"
    classes = trace.load_classes(
        os.path.join(os.path.dirname(HERE), "op_classes"),
        trace.classes_named([{"class": "plan"},
                             {"except": ["plan", "hist"]},
                             {"time": "window"}, {"time": "hist"}]))
    assert [c for c, _ in classes] == ["plan", "hist"]

    def cls(name):
        return next((c for c, p in classes if p.search(name)), None)
    assert cls(trace.short_name(hlo)) == "plan"
    assert cls(trace.short_name(kernel)) == "hist"
    assert cls(trace.short_name(tup)) == "plan"
    assert cls("%copy.526 copy u8[67,13281280]") is None


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_reduction_over_the_recorded_trace():
    classes = trace.load_classes(
        os.path.join(os.path.dirname(HERE), "op_classes"),
        trace.classes_named([{"class": "plan"},
                             {"except": ["plan", "hist"]},
                             {"time": "window"}, {"time": "hist"}]))
    assert [c for c, _ in classes] == ["plan", "hist"]
    red = trace.reduce(RECORDED, classes)
    assert red["devices"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    # self times partition the busy time
    assert red["total_self_s"] == pytest.approx(red["busy_s"], rel=0.02)
    named = sum(red["class_s"].values())
    assert 0 < named < red["total_self_s"]
    assert red["class_s"]["hist"] > 0 and red["class_s"]["plan"] > 0
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10
    assert all(s >= 0 for _, s in red["device_ops"] + red["idle_gaps"])
