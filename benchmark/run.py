#!/usr/bin/env python3
"""The benchmark's one command: one process, one cell, one last line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data found by name: ``BENCHMARK.json`` (the
cells, configurations and metrics), ``benchmark/workloads/<cell>.json``,
the configuration's file, ``benchmark/jobs/<job>.py`` (the kind of work a
workload names), ``benchmark/metrics/<metric>.json`` with the reader it
names in ``benchmark/readers/``.  This file lists none of them.

It exits with a code other than 0 and prints no result line when JAX
finds no TPU or another number of chips than the cell asks for; there
is no CPU path and no smaller size.  The last line of standard output
is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and last
``compared``: every number compared beside its limit); the compared
numbers are also the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                   # noqa: E402
import contextlib                                 # noqa: E402
import importlib                                  # noqa: E402
import json                                       # noqa: E402
import os                                         # noqa: E402
import shutil                                     # noqa: E402
import sys                                        # noqa: E402
import tempfile                                   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def say(msg: str) -> None:
    print(msg, flush=True)


def find_chip(chips: int):
    """The devices of the cell, or exit: a TPU, and as many as asked."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"benchmark: no TPU: jax.devices()[0] is {devs[0]} (platform "
              f"{devs[0].platform!r})", file=sys.stderr)
        sys.exit(2)
    if len(devs) != chips:
        print(f"benchmark: the cell asks for {chips} chip(s), JAX finds "
              f"{len(devs)}", file=sys.stderr)
        sys.exit(2)
    return devs


class Context:
    """What a job gets: the cell, its configuration, the arguments, the
    harness's clocks."""

    def __init__(self, args, cell: dict, cfg: dict, devices):
        self.seed, self.seconds, self.trace = args.seed, args.seconds, \
            bool(args.trace)
        self.cell, self.cfg, self.devices = cell, cfg, devices
        self.clocks: dict = {}
        self.trace_dir = None
        self._compiles = 0
        import jax.monitoring

        def on_duration(event: str, *_a, **_k) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self._compiles += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    say = staticmethod(say)

    @contextlib.contextmanager
    def clock(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.clocks[name] = (self.clocks.get(name, 0.0)
                                 + time.perf_counter() - t0)

    def since_start(self) -> float:
        return time.perf_counter() - T_START

    def compiles(self) -> int:
        return self._compiles

    def memory_peak(self) -> int:
        """Peak bytes in use on the fullest chip."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        if any(p is None for p in peaks):
            raise RuntimeError("the backend reports no peak_bytes_in_use")
        return int(max(peaks))


def cell_metrics(bench: dict, section: str, workload: str,
                 reports: set) -> list:
    """The metrics of ``section`` that this cell reports: those that list
    it, or list nothing and (per layer) move a metric it reports."""
    out = []
    for m in bench[section]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in reports:
            out.append(m)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the yardstick is imported as the package ``benchmark``; the
    # script's own directory leaves the path so that no module of it
    # shadows a library's
    sys.path[:] = [ROOT] + [p for p in sys.path
                            if os.path.abspath(p or ".") != HERE]
    bench = load_json("BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}.get(args.workload)
    if entry is None:
        print(f"benchmark: no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    cfg_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    cell = load_json("benchmark", "workloads", args.workload + ".json")
    cfg = load_json(cfg_entry["file"])

    devices = find_chip(int(entry["chips"]))
    import lightgbm_tpu  # noqa: F401 - fail here, before any output,
    # where the program is not beside the benchmark
    import jax
    say(f"jax {jax.__version__}  device_kind {devices[0].device_kind!r}  "
        f"devices {len(devices)}  compile cache "
        f"{jax.config.jax_compilation_cache_dir}")
    ctx = Context(args, cell, cfg, devices)
    job = importlib.import_module("benchmark.jobs." + cell["job"])
    if ctx.trace:
        ctx.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        out = job.run(ctx)
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": out["memory_peak_bytes"]}
        result = {}
        if ctx.trace:
            from benchmark import trace
            e2e = {m["name"] for m in cell_metrics(
                bench, "end_to_end", args.workload, set())}
            metrics = cell_metrics(bench, "per_layer", args.workload, e2e)
            specs = [load_json("benchmark", "metrics", m["name"] + ".json")
                     for m in metrics]
            red = trace.reduce(
                trace.newest_xplane(ctx.trace_dir),
                trace.load_classes(os.path.join(HERE, "op_classes"),
                                   trace.classes_named(specs)))
            device["busy_s"], device["window_s"] = red["busy_s"], \
                red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
            reading = {**out["reading"], "trace": red, "device": device}
            values = {}
            for m, spec in zip(metrics, specs):
                reader = importlib.import_module(
                    "benchmark.readers." + spec["reader"])
                v = reader.read(reading, spec)
                if v is not None:
                    values[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            values = {
                m["name"]: {"value": out["end_to_end"][m["name"]],
                            "unit": m["unit"]}
                for m in cell_metrics(bench, "end_to_end", args.workload,
                                      set())}
    finally:
        if ctx.trace_dir:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)

    from benchmark import check
    correct, rows = check.verdict(out["compared"], cell["limits"])
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": values, "device": device,
              **result,
              "compared": {n: {"value": v, "limit": lim}
                           for n, v, lim in rows}}
    sys.stdout.flush()
    for n, v, lim in rows:
        print(f"compared {n}: {v:.6g} (limit {lim:.6g})"
              f"{'' if v <= lim else '  <-- over'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
