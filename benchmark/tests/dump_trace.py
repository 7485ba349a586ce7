#!/usr/bin/env python3
"""Run one traced run of a cell on the chip and keep its trace: copies
the ``.xplane.pb`` to ``chiprun_out/`` and writes what is in it (planes,
lines, the operations that took most self time with their statistics)
to ``chiprun_out/trace_dump.txt``, for fixing ``op_classes/`` by hand.

    python3 benchmark/tests/dump_trace.py --workload <cell> --seed <n>
"""
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def describe(path: str, out) -> None:
    from jax.profiler import ProfileData
    from benchmark import trace
    profile = ProfileData.from_file(path)
    for plane in profile.planes:
        print("PLANE", plane.name, file=out)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events", file=out)
            if not events:
                continue
            if plane.name.startswith(trace.DEVICE_PLANE):
                ev = trace._events(line)
                selfs = trace.self_times(ev)
                agg = {}
                for (name, s, e), st in zip(ev, selfs):
                    a = agg.setdefault(name, [0.0, 0, name, 0.0])
                    a[0] += st
                    a[1] += 1
                    a[3] += e - s
                rows = sorted(agg.items(), key=lambda kv: -kv[1][0])[:80]
                for name, (st, cnt, text, dur) in rows:
                    print(f"    {st / 1e6:12.3f} ms self {dur / 1e6:12.3f} ms"
                          f" total x{cnt:6d}  {text[:400]}", file=out)
                e0 = events[0]
                print("    first event stats:", dict(e0.stats), file=out)
            else:
                names = {}
                for e in events:
                    names[e.name] = names.get(e.name, 0) + 1
                top = sorted(names.items(), key=lambda kv: -kv[1])[:25]
                print("    ", top, file=out)


def main() -> int:
    from benchmark import run
    keep = os.path.join(ROOT, "chiprun_out", "trace")
    os.makedirs(keep, exist_ok=True)
    tempfile.mkdtemp = lambda prefix="": keep
    real_rmtree = shutil.rmtree
    shutil.rmtree = lambda *a, **k: None
    rc = run.main(sys.argv[1:] + ["--seconds", "1", "--trace", "1"])
    shutil.rmtree = real_rmtree
    from benchmark import trace
    path = trace.newest_xplane(keep)
    shutil.copy(path, os.path.join(ROOT, "chiprun_out", "traced.xplane.pb"))
    with open(os.path.join(ROOT, "chiprun_out", "trace_dump.txt"), "w") as f:
        describe(path, f)
    real_rmtree(keep, ignore_errors=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
