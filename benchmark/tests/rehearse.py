#!/usr/bin/env python3
"""Rehearsal on the CPU: the command end to end on a tiny cell.

    JAX_PLATFORMS=cpu python3 benchmark/tests/rehearse.py [--trace 1] [--seed n]

``run.py`` has no CPU path and no small size.  This script patches what
stands in the way, in the test and not in the harness: the look for a
chip, the backend's missing memory statistics, where the CPU's trace
keeps its operations, and the list of cells (the tiny cell of
``tiny.json`` is no cell of ``BENCHMARK.json``).  Its numbers are no
measurements.
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def patched_run(argv, patches=(), main=None):
    """Run ``benchmark/run.py``'s ``main`` (or another ``main(argv)`` of
    the harness's scripts) on the tiny cell with the harness's look for
    a chip patched out; ``patches`` are further ``(object, attribute,
    value)`` to set for the run (a test breaks the timed path with
    them).  ``-> the last line printed, as a dict``."""
    import contextlib
    import io
    from benchmark import run, trace, work
    with open(os.path.join(HERE, "tiny.json")) as f:
        tiny = json.load(f)
    real_load, real_peaks = run.load_json, work.peaks

    def load_json(*parts):
        if parts == ("BENCHMARK.json",):
            bench = real_load(*parts)
            bench["configs"].append({"name": "tiny", "file": "tiny-config"})
            bench["workloads"].append({"name": "tiny.train", "config": "tiny",
                                       "traffic": "train", "chips": 1})
            for m in bench["per_layer"]:
                m["workloads"] = m["workloads"] + ["tiny.train"]
            return bench
        if parts == ("tiny-config",):
            return tiny["config"]
        if parts == ("benchmark", "workloads", "tiny.train.json"):
            return tiny["cell"]
        return real_load(*parts)

    def find_chip(chips):
        import jax
        return jax.devices()[:chips]

    todo = [(run, "load_json", load_json), (run, "find_chip", find_chip),
            (run.Context, "memory_peak", lambda self: 0),
            (trace, "DEVICE_PLANE", "/host:CPU"), (trace, "OPS_LINE", "tf_XLA"),
            (work, "peaks", lambda kind: real_peaks("TPU v5 lite")),
            *patches]
    saved = [(o, n, getattr(o, n)) for o, n, _ in todo]
    out = io.StringIO()
    try:
        for o, n, v in todo:
            setattr(o, n, v)
        with contextlib.redirect_stdout(out):
            rc = (main or run.main)(["--workload", "tiny.train"] + argv)
    finally:
        for o, n, v in reversed(saved):
            setattr(o, n, v)
    lines = out.getvalue().strip().splitlines()
    print("\n".join(lines[:-1]))
    if rc != 0:
        raise SystemExit(rc)
    return json.loads(lines[-1])


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # the quantised kernel path, interpreted, as test_correct.py takes it:
    # the CPU's default backend (scatter) sums unrounded float32 gradients,
    # which the stated precision's reference rightly reads as not correct
    os.environ.setdefault("LGBM_TPU_HIST_BACKEND", "pallas")
    argv = sys.argv[1:] or ["--seed", "3000000001", "--seconds", "2"]
    if "--seed" not in argv:
        argv += ["--seed", "3000000001"]
    if "--seconds" not in argv:
        argv += ["--seconds", "2"]
    result = patched_run(argv)
    print(json.dumps(result, indent=1))
