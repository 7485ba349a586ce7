#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip at
a cell's own size; no measured window (a training cell's readings need
none).

    python3 benchmark/tests/readings.py --workload <cell> --seeds 11,12,13 \
        [--variants program,control,half] [--variant-seeds 3]

For each seed the rows are made and binned once.  Each variant then
builds the timed object (``jobs/train.py``'s ``Booster``, the window's
own call) and drives it through the followed steps, and the plain
reference follows its trees at the configuration's stated precision:

* ``program``: the configuration as it stands (the lower readings);
* ``control``: the program's own next precision down
  (``precision.control`` of the configuration's file);
* ``half``: half of the rows left out of every tree, by the program's
  own bagging (``bagging_fraction=0.5, bagging_freq=1``).

One JSON line per seed and variant, with the verdict of ``check.py``
at the cell's limits (``correct``, and the numbers ``over`` theirs), on
standard output and appended to ``chiprun_out/readings.jsonl``; the
trees of both sides go to ``chiprun_out/leaves_*.npz``.  ``control`` and ``half`` run on the
first ``--variant-seeds`` seeds only.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

VARIANTS = {
    "program": lambda cfg: {},
    "control": lambda cfg: {"hist_mode": cfg["precision"]["control"]},
    "half": lambda cfg: {"bagging_fraction": 0.5, "bagging_freq": 1},
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="program,control,half")
    ap.add_argument("--variant-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    from benchmark import check, run
    from benchmark.jobs import train
    bench = run.load_json("BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}[args.workload]
    cfg = run.load_json(
        {c["name"]: c for c in bench["configs"]}[entry["config"]]["file"])
    cell = run.load_json("benchmark", "workloads", args.workload + ".json")
    devices = run.find_chip(int(entry["chips"]))
    import jax
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    obs.enable()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    variants = args.variants.split(",")
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx = run.Context(argparse.Namespace(seed=seed, seconds=0, trace=0),
                          cell, cfg, devices)
        X, y, ds = train.make_dataset(ctx, lgb)
        grid = train.grid_of(ds)
        for v in variants:
            if v != "program" and i >= args.variant_seeds:
                continue
            t0 = time.perf_counter()
            params = {**train.program_params(cfg), **VARIANTS[v](cfg)}
            b = train.Booster(ctx, lgb, ds, y, params)
            b.followed_steps()
            program = b.outputs()
            t_prog = time.perf_counter() - t0
            del b
            gc.collect()
            t0 = time.perf_counter()
            values, seen, ref = train.against_reference(
                cfg, program, X, y, grid, lambda msg: None)
            np.savez(os.path.join(
                ROOT, "chiprun_out",
                f"leaves_{args.workload}_{seed}_{v}.npz"),
                **{f"prog_{k}_{n}": t[n] for k, t in
                   enumerate(program["trees"]) for n in t},
                **{f"ref_{k}_{n}": t[n] for k, t in enumerate(ref["trees"])
                   for n in t},
                init=program["init"], ref_init=ref["init"],
                loss=program["loss"], ref_loss=ref["loss"])
            correct, rows = check.verdict(values, cell["limits"])
            line = json.dumps({
                "workload": args.workload, "seed": seed, "variant": v,
                "correct": correct,
                "over": [n for n, val, lim in rows if not val <= lim],
                "compared": values, "observed": seen, "program_s": t_prog,
                "reference_s": time.perf_counter() - t0,
                "clocks": dict(ctx.clocks),
                "device": devices[0].device_kind})
            print(line, flush=True)
            with open(os.path.join(ROOT, "chiprun_out", "readings.jsonl"),
                      "a") as f:
                f.write(line + "\n")
        del X, y, ds
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
