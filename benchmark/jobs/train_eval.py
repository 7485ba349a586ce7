"""Job kind ``train_eval``: the job the project's ``train.conf``
describes, as a user's loop runs it: a training set and a held-out set,
both evaluated after every iteration, early stopping armed, rows and
features sampled.

The window, the clocks, the trace and the rate are ``train``'s: this job
runs ``train.run`` with five of that module's pieces replaced by its own
for the length of the run (``in_place_of``), so a step is timed and
counted exactly as the accepted cells' steps are.

* ``make_dataset``: ``rows + valid.rows`` rows from the seed by the one
  data rule; the first ``rows`` train, the next are held out and binned
  against the training set's bounds (``reference=``, as a user must).
* ``Booster``: set-up is the call a user makes, ``lgb.train(params, ds,
  block_iters, valid_sets=[ds, held], valid_names=["training",
  "valid"], early_stopping_rounds=..., keep_training_booster=True)``; a
  step is ``train``'s (``g.train(block_iters)`` then the barrier on the
  scores): one tree, the held-out scores' update, one evaluation of both
  sets, the stop bookkeeping.  (``GBDT._train`` starts its stall count
  anew at every call, so across one-iteration calls the stop cannot
  fire; its bookkeeping runs every step all the same.)
* ``FOLLOWED_STEPS``: six, so that the reference follows a whole bagging
  epoch and the first tree of the next.
* ``rescued``: a step in which training stops, early or for want of a
  split, is a failed step as well.
* ``against_reference``: ``reference/gbdt_sampled.py`` and
  ``check_sampled.py``.

*The metric values compared are the program's own.*  While the job
runs, ``GBDT.eval_train`` / ``eval_valid`` (what the boosting loop calls
at every evaluation, and prints and stops by) also append what they
return to a list of the job's; nothing is recomputed from the scores.
*The draws* (each followed iteration's bag, each tree's features) are
read from the booster after the window, as the bin bounds are; the
scores after each followed step are fetched in set-up, as the loss is.
"""
from __future__ import annotations

import contextlib

import numpy as np

from benchmark.jobs import train

FOLLOWED_STEPS = 6
SETS = ("training", "valid")


@contextlib.contextmanager
def in_place_of(module, **mine):
    theirs = {name: getattr(module, name) for name in mine}
    for name, value in mine.items():
        setattr(module, name, value)
    try:
        yield
    finally:
        for name, value in theirs.items():
            setattr(module, name, value)


@contextlib.contextmanager
def reported_to(sink: list):
    """While open, whatever the program's boosting loop is handed as an
    evaluation's results is appended to ``sink`` too."""
    from lightgbm_tpu.boosting.gbdt import GBDT

    def also_to_sink(real):
        def evaluate(self):
            results = real(self)
            sink.extend(results)
            return results
        return evaluate

    with in_place_of(GBDT, eval_train=also_to_sink(GBDT.eval_train),
                     eval_valid=also_to_sink(GBDT.eval_valid)):
        yield


class HeldOut:
    def __init__(self, X, y, ds):
        self.X, self.y, self.ds = X, y, ds


def make_dataset(ctx, lgb):
    from benchmark import data
    rows = int(ctx.cfg["data"]["rows"])
    held = int(ctx.cfg["valid"]["rows"])
    with ctx.clock("data"):
        X, y = data.make({**ctx.cfg["data"], "rows": rows + held}, ctx.seed)
    with ctx.clock("ingest.bin"):
        ds = lgb.Dataset(X[:rows], label=y[:rows],
                         params={"max_bin": ctx.cfg["params"]["max_bin"]})
        ds.construct()
        dv = lgb.Dataset(X[rows:], label=y[rows:], reference=ds)
        dv.construct()
    ctx.held_out = HeldOut(X[rows:], y[rows:], dv)
    return X[:rows], y[:rows], ds


class Booster(train.Booster):
    """``train.Booster`` built by the call of a user who watches a
    held-out set; it keeps what every step's evaluation reported."""

    def __init__(self, ctx, lgb, ds, y, params: dict):
        import jax
        import jax.numpy as jnp
        self.jax = jax
        self.block_iters = int(ctx.cell["block_iters"])
        self.rows = len(y)
        self.held = ctx.held_out
        self.used_features = np.asarray(ds._constructed.used_features)
        self.features = len(ds._constructed.mappers)
        self._loss = jax.jit(train._loss_parts)
        self.y_dev = jnp.asarray(y)
        self.loss, self.evals, self.scores = [], [], []
        self.sink = ctx.reported
        with ctx.clock("train_call"), train.written_to_cache(
                jax, ctx.cell.get("compile_cache", True)):
            self.bst = lgb.train(
                params, ds, num_boost_round=self.block_iters,
                valid_sets=[ds, self.held.ds], valid_names=list(SETS),
                early_stopping_rounds=int(ctx.cfg["early_stopping_round"]),
                keep_training_booster=True)
            self.g = self.bst._gbdt
            jax.block_until_ready(self.g.scores)
        self.read_loss()

    def read_loss(self) -> None:
        """After a followed step: the loss, and what the step's
        evaluation reported."""
        super().read_loss()
        self.evals.append({(name, metric): float(value)
                           for name, metric, value, _ in self.sink})
        self.scores.append(tuple(
            np.asarray(s)[:, 0]
            for s in (self.g.scores, self.g._valid_scores[0])))

    def step(self) -> None:
        self.sink.clear()
        super().step()

    def outputs(self) -> dict:
        out = super().outputs()
        g = self.g
        steps = range(len(out["trees"]))
        drawn = []
        for k in steps:
            inner = np.asarray(g._feature_mask(k))
            orig = np.zeros(self.features, bool)
            orig[self.used_features] = inner
            drawn.append(orig)
        out["draws"] = {"bag": [np.asarray(g._bagging_mask(k))
                                for k in steps], "features": drawn}
        out["evals"] = self.evals[:len(out["trees"])]
        out["scores"] = self.scores[:len(out["trees"])]
        out["held_out"] = self.held
        return out

    def tree_counts(self, first: int, last: int) -> list:
        """``train``'s, with a tree's rows the rows in its bag."""
        return [(int(t.internal_count[0]), splits) for t, (_, splits) in
                zip(self.g.models[first:last],
                    super().tree_counts(first, last))]


def rescued(before: dict, after: dict) -> list:
    found = train_rescued(before, after)
    for k, v in after["events"].items():
        if k.startswith(("early_stop", "train_stop")) \
                and v != before["events"].get(k, 0):
            found.append(k)
    return found


train_rescued = train.rescued


def against_reference(cfg: dict, program: dict, X, y, grid, log):
    import time
    from benchmark import check_sampled
    from benchmark.reference import gbdt_sampled as reference
    held = program["held_out"]
    t0 = time.perf_counter()
    ref = reference.follow(
        np.ascontiguousarray(X.T), y, np.ascontiguousarray(held.X.T),
        held.y, grid, cfg["reference_params"], program["trees"],
        program["init"], cfg["precision"]["hist_mode"], program["draws"],
        program["scores"], log=log)
    log(f"reference: {time.perf_counter() - t0:.1f} s")
    for step, evals in enumerate(program["evals"]):
        log(f"reported at step {step + 1}: " + ", ".join(
            f"{n} {m} {v:.9f}" for (n, m), v in evals.items()))
    return (*check_sampled.compare(program, ref, cfg["reference_params"]),
            ref)


def run(ctx) -> dict:
    from benchmark import work_eval
    from lightgbm_tpu import obs
    ctx.reported = []
    with reported_to(ctx.reported), in_place_of(
            train, make_dataset=make_dataset, Booster=Booster,
            FOLLOWED_STEPS=FOLLOWED_STEPS, rescued=rescued,
            against_reference=against_reference):
        out = train.run(ctx)
    seen = obs.summary()
    counters = seen["counters"]
    # a program without the counters (before PR 33) fetches every row
    ctx.say("evaluations: " + (
        f"gbdt.evals={counters['gbdt.evals']} gbdt.eval_host_rows="
        f"{counters.get('gbdt.eval_host_rows', 0)} gbdt.eval_backend="
        f"{seen['gauges'].get('gbdt.eval_backend')}"
        if "gbdt.evals" in counters else "the program does not count them"))
    reading = out["reading"]
    if "work" in reading:
        each = work_eval.evaluation(
            [ctx.cfg["data"]["rows"], ctx.cfg["valid"]["rows"]],
            len(ctx.cfg["params"]["metric"].split(",")))
        reading["work"]["iteration"] = work_eval.with_evaluations(
            reading["work"]["iteration"], each, reading["iterations"])
    return out
