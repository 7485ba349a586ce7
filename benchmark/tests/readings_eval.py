#!/usr/bin/env python3
"""Readings that the limits of a ``train_eval`` cell are set from, on
the chip at the cell's own size: whole runs of ``run.py`` in one
process, one after another, the program sound or broken underneath the
harness.

    python3 benchmark/tests/readings_eval.py --workload <cell> \
        --seeds 11,12 [--variants program,control,buckets] [--seconds 1]

``VARIANTS`` are the faults ``test_correct_eval.py`` holds the tiny cell
to; each is a list of ``(object, attribute, value)`` to set for a run:

* ``program``: the configuration as it stands;
* ``control``: the program's own next precision down;
* ``late``: every metric taken from the scores one iteration late;
* ``no_bag`` / ``no_feature_mask``: the learner grows its trees over all
  rows / all features, whatever was drawn;
* ``oob``: the rows outside the bag keep their scores;
* ``valid_stale``: the held-out rows' scores are never moved (the
  block's held-out update adds nothing), so every reported held-out
  metric is the exact metric of scores that stand still;
* ``buckets``: AUC from a 65,536-bucket histogram of the scores.

One JSON line a run (seed, variant, ``correct``, the numbers ``over``
their limits, every compared number, the run's end-to-end metrics) on
standard output and appended to ``chiprun_out/readings_eval.jsonl``.
"""
import argparse
import contextlib
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _control():
    from benchmark.jobs import train
    real = train.program_params

    def lower(cfg):
        return {**real(cfg), "hist_mode": cfg["precision"]["control"]}
    return [(train, "program_params", lower)]


def _late():
    import jax.numpy as jnp
    from lightgbm_tpu.boosting.gbdt import GBDT
    real = GBDT._eval_set

    def one_late(self, name, key, scores, *rest):
        kept = self.__dict__.setdefault("_late", {})
        before = kept.get(key, scores)
        kept[key] = jnp.copy(scores)
        return real(self, name, key, before, *rest)
    return [(GBDT, "_eval_set", one_late)]


def _learner_without(**dropped):
    from lightgbm_tpu.boosting import gbdt
    real = gbdt.build_tree

    def build_tree(*args, **kw):
        return real(*args, **{**kw, **dropped})
    return [(gbdt, "build_tree", build_tree)]


def _oob():
    import jax.numpy as jnp
    from benchmark.jobs import train_eval
    real = train_eval.Booster.step

    def step(self):
        before = jnp.copy(self.g.scores)
        real(self)
        bag = self.g._bagging_mask(self.g.iter - 1)
        self.g.scores = jnp.where(bag[:, None], self.g.scores, before)
    return [(train_eval.Booster, "step", step)]


def _valid_stale():
    import jax.numpy as jnp
    from lightgbm_tpu.boosting import gbdt
    from lightgbm_tpu.learner import serial

    def nothing(tree, data, bins):
        return jnp.zeros(bins.shape[0], jnp.float32)
    return [(serial, "predict_built_tree_matmul", nothing),
            (serial, "predict_built_tree", nothing),
            (gbdt, "predict_built_tree", nothing)]


def _buckets():
    import numpy as np
    from lightgbm_tpu.metric import device
    from lightgbm_tpu.metric.metrics import binary_auc
    real = device.EvalSet.eval

    def eval_(self, score, forms, sigmoid):
        out = real(self, score, forms, sigmoid)
        if out.get("auc") is not None:
            s = np.asarray(score)[:self.n, 0].astype(np.float64)
            width = max(float(s.max() - s.min()), 1e-300)
            bucket = np.floor((s - s.min()) / width * 65535.0)
            out["auc"] = binary_auc(np.asarray(self.label),
                                    bucket.astype(np.float32))
        return out
    return [(device.EvalSet, "eval", eval_)]


VARIANTS = {
    "program": lambda: [],
    "control": _control,
    "late": _late,
    "no_bag": lambda: _learner_without(bag_mask=None),
    "no_feature_mask": lambda: _learner_without(feature_mask=None),
    "oob": _oob,
    "valid_stale": _valid_stale,
    "buckets": _buckets,
}


@contextlib.contextmanager
def patched(patches):
    saved = [(o, n, getattr(o, n)) for o, n, _ in patches]
    try:
        for o, n, v in patches:
            setattr(o, n, v)
        yield
    finally:
        for o, n, v in reversed(saved):
            setattr(o, n, v)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="program")
    ap.add_argument("--seconds", default="1")
    args = ap.parse_args(argv)
    from benchmark import run
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    for seed in args.seeds.split(","):
        for v in args.variants.split(","):
            out = io.StringIO()
            with patched(VARIANTS[v]()), contextlib.redirect_stdout(out):
                rc = run.main(["--workload", args.workload, "--seed", seed,
                               "--seconds", args.seconds, "--trace", "0"])
            lines = out.getvalue().strip().splitlines()
            print("\n".join(lines[:-1]), file=sys.stderr)
            if rc != 0:
                return rc
            result = json.loads(lines[-1])
            line = json.dumps({
                "workload": args.workload, "seed": int(seed), "variant": v,
                "correct": result["correct"],
                "over": [n for n, c in result["compared"].items()
                         if not c["value"] <= c["limit"]],
                "compared": {n: c["value"]
                             for n, c in result["compared"].items()},
                "metrics": result["metrics"], "failed": result["failed"],
                "attempted": result["attempted"]})
            print(line, flush=True)
            with open(os.path.join(ROOT, "chiprun_out",
                                   "readings_eval.jsonl"), "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
