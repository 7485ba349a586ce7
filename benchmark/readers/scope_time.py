"""Device self time per iteration of the traced window, in milliseconds
(or, with ``"count": true``, device events per iteration), of the
operations the program put under one ``jax.named_scope``.

The profiler keeps each device operation's JAX name stack as the stat
``tf_op`` of the operation's *metadata* (``jit(block)/while/body/
closed_call/tree.route/jit(route_rows_pallas)/pallas_call``), which
``jax.profiler.ProfileData`` does not hand out.  This reader walks the
``.xplane.pb`` wire format itself (standard library only) for those
stats and joins them to ``ProfileData``'s events by the event's name,
which is its metadata's name: the whole HLO instruction.  Window,
clipping and self time are ``benchmark.trace``'s.

A spec's ``scope`` is one component of the name stack (``null``: the
events that carry none of the program's scopes); ``leaf`` keeps only
the events whose last component it is, ``except_leaf`` drops those
whose last component it lists, ``op`` keeps those whose short name
(``trace.short_name``) the pattern finds: a copy of a kernel's result
carries the kernel's name stack, and is no call of it.  A trace that
was read gives a number: 0 is a reading.  ``run.py`` hands a reader no
path, so the trace is the newest ``bench_trace_*`` under the temporary
directory.
"""
import functools
import glob
import gzip
import os
import re
import tempfile

from benchmark import trace

# a component of a name stack that the program wrote, and not JAX
PROGRAM_SCOPE = re.compile(r"^(tree|obj|gbdt|collective)\.")


def _varint(buf, i: int):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint or
    a fixed field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"wire type {kind} in an .xplane.pb")
        yield key >> 3, value


def _map_entry(buf):
    entry = dict(_fields(buf))
    return entry.get(1), entry.get(2)


def name_stacks(raw: bytes) -> dict:
    """``{plane name: {event name: tf_op}}`` of an ``XSpace``: for every
    plane, the ``tf_op`` stat of each event metadata that has one
    (``XPlane.event_metadata[*].stats``; the stat's value is a string or
    a reference to a stat metadata's name)."""
    out = {}
    for num, plane in _fields(memoryview(raw)):
        if num != 1:                                  # XSpace.planes
            continue
        plane_name, events, stats = "", [], {}
        for pnum, v in _fields(plane):
            if pnum == 2:                             # XPlane.name
                plane_name = bytes(v).decode()
            elif pnum == 4:                           # .event_metadata
                events.append(_map_entry(v)[1])
            elif pnum == 5:                           # .stat_metadata
                sid, meta = _map_entry(v)
                stats[sid] = next((bytes(x).decode(errors="replace")
                                   for n, x in _fields(meta) if n == 2), "")
        tf_op = {sid for sid, name in stats.items() if name == "tf_op"}
        found = {}
        for meta in events:
            name, stack = "", None
            for mnum, v in _fields(meta):
                if mnum == 2:                         # XEventMetadata.name
                    name = bytes(v).decode(errors="replace")
                elif mnum == 5:                       # .stats: XStat
                    stat = dict(_fields(v))
                    if stat.get(1) in tf_op:
                        stack = (bytes(stat[5]).decode(errors="replace")
                                 if 5 in stat else stats.get(stat.get(7), ""))
            if stack is not None:
                found[name] = stack
        if found:
            out[plane_name] = found
    return out


@functools.lru_cache(maxsize=2)
def stacked_self_times(path: str) -> tuple:
    """``((name stack, short name, self seconds), ...)`` of the device
    events inside the window, one entry an event, the seconds averaged
    over the devices that ran anything (as ``trace.reduce`` averages).
    ``tf_op`` is ``<name stack>:<type>``: the stack is kept."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        stacks = name_stacks(f.read())
    profile = trace.load(path)
    windows = [(s, e) for name, s, e in trace._host_spans(profile)
               if name == trace.WINDOW_SPAN]
    per_device = []
    for plane in profile.planes:
        if not plane.name.startswith(trace.DEVICE_PLANE):
            continue
        of_plane = stacks.get(plane.name, {})
        for line in plane.lines:
            if line.name.startswith(trace.OPS_LINE):
                ev = [((of_plane.get(e.name, "").rpartition(":")[0],
                        trace.short_name(e.name)), float(e.start_ns),
                       float(e.start_ns) + float(e.duration_ns))
                      for e in line.events]
                if ev:
                    ev.sort(key=lambda t: (t[1], -(t[2] - t[1])))
                    per_device.append(ev)
    if not per_device:
        raise ValueError(f"no '{trace.OPS_LINE}' events on a "
                         f"{trace.DEVICE_PLANE}* plane in {path}")
    if windows:
        lo, hi = min(w[0] for w in windows), max(w[1] for w in windows)
    else:
        lo, hi = float("-inf"), float("inf")
    out = []
    for ev in per_device:
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ev
                  if min(e, hi) > max(s, lo)]
        out.extend((*named, st / len(per_device) / 1e9) for (named, _, _), st
                   in zip(inside, trace.self_times(inside)))
    return tuple(out)


def matches(stack: str, short: str, spec: dict) -> bool:
    parts = stack.split("/") if stack else []
    scope = spec["scope"]
    if scope is None:
        if any(PROGRAM_SCOPE.match(p) for p in parts):
            return False
    elif scope not in parts:
        return False
    leaf = parts[-1] if parts else ""
    if "leaf" in spec and leaf != spec["leaf"]:
        return False
    if leaf in spec.get("except_leaf", ()):
        return False
    return "op" not in spec or re.search(spec["op"], short) is not None


def innermost(stack: str) -> str:
    """The program's scope an event lies in: the last component of its
    name stack that the program wrote, or ``""``.  Each event has one,
    so these partition the device's self time."""
    return next((p for p in reversed(stack.split("/"))
                 if PROGRAM_SCOPE.match(p)), "")


def newest_trace():
    """``run.py``'s trace of this run, or None where another caller put
    it elsewhere (``dump_trace.py``)."""
    dirs = glob.glob(os.path.join(tempfile.gettempdir(), "bench_trace_*"))
    return (trace.newest_xplane(max(dirs, key=os.path.getmtime))
            if dirs else None)


def read(reading: dict, spec: dict):
    path = newest_trace() if reading.get("trace") is not None else None
    if path is None:
        return None
    hits = [s for stack, short, s in stacked_self_times(path)
            if matches(stack, short, spec)]
    per_iter = len(hits) if spec.get("count") else 1000.0 * sum(hits)
    return per_iter / reading["iterations"]
