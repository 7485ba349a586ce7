"""From the profiler's ``.xplane.pb`` to the numbers the metrics read.

Reads the file with ``jax.profiler.ProfileData`` and nothing else.  A
TPU's plane ``/device:TPU:<i>`` has a line ``XLA Ops`` with one event
per executed HLO operation; control-flow operations (``while``,
``conditional``, ``call``) span the operations they run, so time is
counted as *self* time: an event's duration less what its children on
the same line cover.  The host's plane holds the ``TraceAnnotation``
spans of the harness (``bench.window``, ``bench.step``) and whatever the
program annotates; all lines share one clock.

* busy: the union of the device's operation intervals inside the
  window, averaged over the devices that ran anything;
* idle gaps: the window's stretches with no operation, each attributed
  to the innermost named host span open at its middle;
* operation classes: name patterns, one file per class in
  ``op_classes/``, matched against the event's short name (its own
  name, operation, fusion kind and result shape: ``short_name``); only
  the classes that the cell's metric files name are loaded, and the
  first of them that matches wins, in the order they are named.
"""
from __future__ import annotations

import glob
import json
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
# host spans an idle gap may be attributed to
HOST_SPANS = re.compile(r"^(bench\.|gbdt\.|io\.|PjitFunction|"
                        r"PjRtCApiLoadedExecutable|TransferFromDevice|"
                        r"block_until_ready)")


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str):
    """The profile of an ``.xplane.pb`` file, or of a gzipped one."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def classes_named(metric_specs: list) -> list:
    """The operation classes a cell's metric files name (``class``,
    ``except``, a ``time`` other than the window), in the order they
    are first named."""
    names = []
    for spec in metric_specs:
        for n in ([spec.get("class")] + list(spec.get("except", ()))
                  + [spec.get("time")]):
            if n and n != "window" and n not in names:
                names.append(n)
    return names


def load_classes(directory: str, names: list) -> list:
    """``[(class, compiled pattern), ...]`` of ``<directory>/<name>.json``
    in the order of ``names``: a class file that no metric of the cell
    names takes no event from the others."""
    out = []
    for name in names:
        with open(os.path.join(directory, name + ".json")) as f:
            spec = json.load(f)
        out.append((spec["class"], re.compile("|".join(spec["patterns"]))))
    return out


_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
_KIND = re.compile(r"kind=(\w+)")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def short_name(hlo: str) -> str:
    """``%fusion.192 = u8[13289472,67]{...} fusion(...), kind=kCustom,
    ...`` -> ``%fusion.192 fusion kCustom u8[13289472,67]``: an event of
    the ``XLA Ops`` line is named by its whole HLO instruction; its own
    name, operation, fusion kind and first result shape identify it,
    and its operands (other instructions' names) must not."""
    lhs, sep, rhs = hlo.partition(" = ")
    if not sep:
        return hlo
    parts = [lhs]
    for pattern in (_OPCODE, _KIND):
        m = pattern.search(rhs)
        if m:
            parts.append(m.group(1))
    m = _SHAPE.search(rhs)
    if m:
        parts.append(m.group(0))
    return " ".join(parts)


def _events(line) -> list:
    """``[(short name, start, end)]`` sorted by start, longest first."""
    out = []
    for e in line.events:
        start = float(e.start_ns)
        out.append((short_name(e.name), start, start + float(e.duration_ns)))
    out.sort(key=lambda t: (t[1], -(t[2] - t[1])))
    return out


def self_times(events: list) -> list:
    """Self time of each event (same order): its duration less what the
    events nested inside it cover."""
    selfs = [e[2] - e[1] for e in events]
    stack = []                                   # indices of open events
    for i, (_, start, end) in enumerate(events):
        while stack and events[stack[-1]][2] <= start:
            stack.pop()
        if stack and end <= events[stack[-1]][2] + 1e-3:
            selfs[stack[-1]] -= end - start
        stack.append(i)
    return [max(s, 0.0) for s in selfs]


def union(intervals: list) -> list:
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _host_spans(profile) -> list:
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith(HOST_PLANE):
            continue
        for line in plane.lines:
            for e in line.events:
                if HOST_SPANS.match(e.name):
                    s = float(e.start_ns)
                    spans.append((e.name, s, s + float(e.duration_ns)))
    return spans


def reduce(path: str, classes: list) -> dict:
    """``-> window_s, busy_s, devices, class_s {class: seconds},
    total_self_s, device_ops [[name, s]], idle_gaps [[span, s]]``; times
    of several devices are averaged over the devices that ran anything."""
    profile = load(path)
    host = _host_spans(profile)
    windows = [(s, e) for name, s, e in host if name == WINDOW_SPAN]
    per_device = []
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            if line.name.startswith(OPS_LINE):
                ev = _events(line)
                if ev:
                    per_device.append(ev)
    if not per_device:
        raise ValueError(f"no '{OPS_LINE}' events on a {DEVICE_PLANE}* "
                         f"plane in {path}")
    if windows:
        lo, hi = min(w[0] for w in windows), max(w[1] for w in windows)
    else:
        lo = min(ev[0][1] for ev in per_device)
        hi = max(max(e[2] for e in ev) for ev in per_device)
    n_dev = len(per_device)
    busy = 0.0
    class_s: dict = {}
    by_name: dict = {}
    total_self = 0.0
    gaps: dict = {}
    for ev in per_device:
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ev
                  if min(e, hi) > max(s, lo)]
        merged = union([(s, e) for _, s, e in inside])
        busy += sum(b - a for a, b in merged)
        for (name, _, _), st in zip(inside, self_times(inside)):
            total_self += st
            by_name[name] = by_name.get(name, 0.0) + st
            for cls, pattern in classes:
                if pattern.search(name):
                    class_s[cls] = class_s.get(cls, 0.0) + st
                    break
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a <= 0:
                continue
            mid = (a + b) / 2
            open_ = [(e - s, n) for n, s, e in host
                     if s <= mid <= e and n != WINDOW_SPAN]
            who = min(open_)[1] if open_ else "(no host span)"
            gaps[who] = gaps.get(who, 0.0) + (b - a)

    def top(d: dict) -> list:
        rows = sorted(d.items(), key=lambda kv: -kv[1])[:10]
        return [[k, v / n_dev / 1e9] for k, v in rows]

    return {"window_s": (hi - lo) / 1e9, "busy_s": busy / n_dev / 1e9,
            "devices": n_dev,
            "class_s": {k: v / n_dev / 1e9 for k, v in class_s.items()},
            "total_self_s": total_self / n_dev / 1e9,
            "device_ops": top(by_name), "idle_gaps": top(gaps)}
