#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that lightgbm_tpu still trains on the chip.

    python3 chip_smoke.py             # one TPU chip (what the driver runs)
    python3 chip_smoke.py --chips 4   # only the multi-chip phase, on four

One process, no arguments needed, no network, no child that touches JAX
(the native CSV parser is built with g++, which ends before the phase
returns).  It drives the trainer's main path through the entry points a
user calls (``lgb.train``, ``Booster.predict``, ``save_model``, the
config-file CLI) at the reference's HIGGS settings — 1,048,576 rows x 28
features, 63 bins, 255 leaves; data and labels made from ``--seed`` —
and checks what comes out by the repo's own means: the train-AUC gate of
the bench, the exact float32 scatter learner as the plain reference, the
tolerance registry, the flip-envelope gate, and the program's own
telemetry (``obs.summary()``) for *which* path ran.

It FAILS (non-zero exit, no result line) when JAX finds no TPU, when any
phase raises or any check does not hold.  On success the LAST line of
standard output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Every other line is an observation of this one run (times, memory, AUCs,
cache entries) — not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

# the reference's HIGGS settings (docs/Experiments.rst; 63 bins as its GPU
# docs recommend) — the one model width this script runs at
N_ROWS = 1 << 20
N_FEATURES = 28
PARAMS = {"objective": "binary", "num_leaves": 255, "max_bin": 63,
          "min_data_in_leaf": 20, "learning_rate": 0.1, "verbose": -1}
BLOCK = 32                  # GBDT._BLOCK_CAP: iterations per fused block
AUC_GATE = 0.93             # bench.py's AUC_GATE for this data rule
AUC_AGREE = 0.005           # default backend vs the scatter reference
LEAF_RATIO = 3.0            # largest |leaf value| vs the scatter reference's
REF_ROWS, REF_ITERS = 131_072, 16
CLI_ROWS, CLI_ITERS = 100_000, 16
EVAL_VALID_ROWS, EVAL_ITERS = 50_000, 12


class SmokeFailure(RuntimeError):
    """A check of the smoke run did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


def higgs_like(n: int, seed: int):
    """bench.py ``synthetic_leg``'s data rule: 28 standard-normal
    features, a noisy linear label on the first three."""
    import numpy as np
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, N_FEATURES)).astype(np.float32)
    y = (X[:, 0] * 2 + X[:, 1] - X[:, 2]
         + rng.normal(scale=1.0, size=n) > 0).astype(np.float32)
    return X, y


def cache_entries(path: str) -> int:
    try:
        return sum(1 for f in os.listdir(path) if not f.endswith("-atime"))
    except FileNotFoundError:
        return 0


def span_count(summary: dict, name: str) -> int:
    return summary["spans"].get(name, {}).get("count", 0)


def span_total(summary: dict, name: str) -> float:
    return summary["spans"].get(name, {}).get("total_s", 0.0)


def train_auc(bst, y) -> float:
    import numpy as np
    from lightgbm_tpu.metric.metrics import binary_auc
    s = np.asarray(bst._gbdt.scores[:, 0])
    check(bool(np.isfinite(s).all()), "non-finite training scores")
    return float(binary_auc(y, s))


def largest_leaf(gbdt, trees: int) -> float:
    """Largest |leaf value| over the first ``trees`` trees, the first
    tree's init-score bias taken out."""
    import numpy as np
    worst = 0.0
    for i, t in enumerate(gbdt.models[:trees]):
        v = np.asarray(t.leaf_value[:t.num_leaves], np.float64)
        if i == 0:
            v = v - gbdt.init_score_value
        worst = max(worst, float(np.abs(v).max()))
    return worst


def check_first_tree(bst, X, y, label: str) -> None:
    """Every leaf of tree 0 holds the value of its own rows' sums.  The
    tree was grown from a constant score, so each row's gradient is known
    in closed form (g = p0 - y, h = p0 (1 - p0)); a leaf may be off by
    its rows' int8 rounding (``scale / 254`` a row), not more.  This sees
    what AUC cannot: a sum that went to the wrong leaf."""
    import numpy as np
    g = bst._gbdt
    tree = g.models[0]
    lr = PARAMS["learning_rate"]
    init = float(g.init_score_value)
    p0 = 1.0 / (1.0 + np.exp(-init))
    leaf = np.asarray(bst.predict(X, pred_leaf=True, num_iteration=1))
    leaf = leaf.reshape(len(X), -1)[:, 0]
    n_leaves = int(tree.num_leaves)
    cnt = np.bincount(leaf, minlength=n_leaves).astype(np.float64)
    pos = np.bincount(leaf, weights=y, minlength=n_leaves)
    G = cnt * p0 - pos
    H = cnt * p0 * (1.0 - p0)
    sg, sh = max(p0, 1.0 - p0), p0 * (1.0 - p0)
    dG, dH = cnt * sg / 254.0, cnt * sh / 254.0 / 127.0
    raw = -G / H
    bound = lr * (dG + np.abs(raw) * dH) / (H - dH) + 1e-5
    got = np.asarray(tree.leaf_value[:n_leaves], np.float64) - init
    err = np.abs(got - lr * raw)
    worst = int(np.argmax(err - bound))
    say(f"{label}: tree 0, {n_leaves} leaves, each against its own rows' "
        f"exact sums: max |value - exact| {err.max():.3e}, worst leaf "
        f"{worst} ({int(cnt[worst])} rows) {err[worst]:.3e} against its "
        f"rounding bound {bound[worst]:.3e}")
    check(bool((cnt == np.asarray(tree.leaf_count[:n_leaves])).all()),
          f"{label}: tree 0 leaf counts differ from the rows routed there")
    check(bool((err <= bound).all()),
          f"{label}: tree 0 leaf {worst} ({int(cnt[worst])} rows) is "
          f"{got[worst]:.5f}, its own rows give {lr * raw[worst]:.5f} "
          f"(bound {bound[worst]:.2e})")


def check_quiet_path(summary: dict) -> None:
    """Nothing on the path may have been rescued: no fallback counter,
    no degrade event, no retried or exhausted device dispatch."""
    c, ev = summary["counters"], summary["events"]
    fell = {k: v for k, v in c.items() if "fallback" in k and v}
    check(not fell, f"fallback counters fired: {fell}")
    degraded = {k: v for k, v in ev.items() if k.startswith("degrade:")}
    check(not degraded, f"degrade events: {degraded}")
    for k in ("retry.device_dispatch.retries",
              "retry.device_dispatch.exhausted"):
        check(c.get(k, 0) == 0, f"{k} = {c.get(k)}")
    say(f"telemetry: gbdt.split_kernel_fallbacks="
        f"{c.get('gbdt.split_kernel_fallbacks', 0)} "
        f"retry.device_dispatch.retries="
        f"{c.get('retry.device_dispatch.retries', 0)} "
        f"retry.device_dispatch.exhausted="
        f"{c.get('retry.device_dispatch.exhausted', 0)} "
        f"attempts={c.get('retry.device_dispatch.attempts', 0)}")


def say_tiling(summary) -> None:
    """The grids the traced histogram calls took, from the program's
    gauges: ``hist.tiling.<cols>`` = ``<feat_tile>x<row tile>``, the
    largest share of padded features, ``hist.feature_pad_pct``, the
    waves' slot counts, ``hist.wave_slots`` = ``<staged waves>|<tail>``,
    the staged waves whose route runs inside their histogram call,
    ``hist.fused_waves`` (``1,2,3,4,5,6,7`` here and on the 13.28M-row
    cells, ``-`` on ``-dp4`` / ``-c32``), and the int32 partials a call
    sums its rows in, ``hist.row_chunks`` (1 here: 4 at the 53.1M rows
    of ``criteo-67-b63-c32.train``)."""
    tiling = {k: v for k, v in sorted(summary["gauges"].items())
              if k.startswith("hist.")}
    say(f"histogram grids: {tiling}")
    check(any(k.startswith("hist.tiling.") for k in tiling),
          "no hist.tiling.<cols> gauge: no histogram kernel was traced")
    check("hist.wave_slots" in tiling, "no hist.wave_slots gauge")
    say(f"fused route+histogram waves: {tiling.get('hist.fused_waves')}")
    check("hist.fused_waves" in tiling, "no hist.fused_waves gauge")
    check(tiling.get("hist.row_chunks") == 1,
          f"hist.row_chunks is {tiling.get('hist.row_chunks')!r}, not 1")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def phase_train(jax, lgb, obs, X, y):
    """lgb.train at full width, 64 iterations = two fused blocks."""
    import numpy as np
    t0 = time.perf_counter()
    ds = lgb.Dataset(X, label=y, params={"max_bin": PARAMS["max_bin"]})
    ds.construct()
    say(f"dataset: {X.shape[0]} x {X.shape[1]} binned in "
        f"{time.perf_counter() - t0:.2f} s (host)")
    before = obs.summary()
    t0 = time.perf_counter()
    bst = lgb.train(PARAMS, ds, num_boost_round=2 * BLOCK,
                    keep_training_booster=True)
    g = bst._gbdt
    jax.block_until_ready(g.scores)
    wall = time.perf_counter() - t0
    after = obs.summary()

    # which path ran, from the program's own telemetry
    backend = after["gauges"].get("gbdt.hist_backend")
    mode = after["gauges"].get("gbdt.hist_mode")
    say(f"resolved backend: {backend}  hist mode: {mode}")
    say_tiling(after)
    check(backend == "pallas" and g.hist_backend == "pallas",
          f"resolved histogram backend is {backend!r}, not 'pallas'")
    blocks = sum(span_count(after, k) - span_count(before, k)
                 for k in ("gbdt.block", "gbdt.block_compile"))
    iters = (span_count(after, "gbdt.iteration")
             - span_count(before, "gbdt.iteration"))
    say(f"spans: gbdt.block(+_compile)={blocks} gbdt.iteration={iters}")
    check(blocks >= 2, f"{blocks} fused block dispatches, expected >= 2")
    check(iters == 0, f"{iters} unfused gbdt.iteration spans, expected 0")
    check_quiet_path(after)
    check(g.iter == 2 * BLOCK and g.num_trees() == 2 * BLOCK,
          f"trained {g.iter} iterations / {g.num_trees()} trees")

    # the kernels are in the program that ran
    fn = g._block_fns[BLOCK]
    aval = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    args = (g.device_data, g._bins_t, (), g.scores, (),
            jax.numpy.float32(0), jax.numpy.int32(0), jax.numpy.int32(0))
    hlo = fn.lower(*jax.tree.map(aval, args)).as_text()
    n_kernels = hlo.count("tpu_custom_call")
    say(f"block program: {n_kernels} tpu_custom_call in its lowering")
    check(n_kernels > 0, "no tpu_custom_call in the block program")

    cold = (span_total(after, "gbdt.block_compile")
            - span_total(before, "gbdt.block_compile"))
    say(f"train: {2 * BLOCK} iterations in {wall:.2f} s wall, synced; "
        f"first block cold (trace + compile + enqueue) {cold:.2f} s")
    auc = train_auc(bst, y)
    say(f"train AUC after {2 * BLOCK} iterations: {auc:.5f} "
        f"(gate {AUC_GATE})")
    check(auc >= AUC_GATE, f"train AUC {auc:.5f} < {AUC_GATE}")
    check_first_tree(bst, X, y, "full width")
    say(f"largest |leaf value|: {largest_leaf(g, REF_ITERS):.3f} in the "
        f"first {REF_ITERS} trees, {largest_leaf(g, 2 * BLOCK):.3f} in all "
        f"{2 * BLOCK}")

    # warm block time, under both barriers (bench.py _sync's question):
    # each block is awaited by one barrier and the other is then timed
    # on the same block, so a barrier that returned early would show as
    # the other one's wait
    by_ready, by_fetch = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        g.train_block(BLOCK)
        jax.block_until_ready(g.scores)
        t1 = time.perf_counter()
        np.asarray(g.scores.ravel()[0])
        by_ready.append((t1 - t0, time.perf_counter() - t1))
        t0 = time.perf_counter()
        g.train_block(BLOCK)
        np.asarray(g.scores.ravel()[0])
        t1 = time.perf_counter()
        jax.block_until_ready(g.scores)
        by_fetch.append((t1 - t0, time.perf_counter() - t1))
    g.join_background()
    say(f"warm block ({BLOCK} iterations), block_until_ready then the "
        f"scalar fetch on the same block: "
        + ", ".join(f"{a:.3f} + {b:.4f}" for a, b in by_ready)
        + " s; scalar fetch then block_until_ready: "
        + ", ".join(f"{a:.3f} + {b:.4f}" for a, b in by_fetch) + " s")
    check_quiet_path(obs.summary())
    return bst, largest_leaf(g, REF_ITERS)


def phase_predict(lgb, bst, X, tmp):
    """Booster.predict (the binned device predictor and the serve
    pack), save_model -> load -> predict (host trees) agreeing."""
    import numpy as np
    from tools.numcheck.tolerance_registry import tol
    Xp = X[:100_000]
    t0 = time.perf_counter()
    p_train = bst.predict(Xp)
    t_train = time.perf_counter() - t0
    t0 = time.perf_counter()
    p_serve = bst.predict(Xp, device=True)
    t_serve = time.perf_counter() - t0
    path = os.path.join(tmp, "smoke_model.txt")
    bst.save_model(path)
    loaded = lgb.Booster(model_file=path)
    t0 = time.perf_counter()
    p_loaded = loaded.predict(Xp)
    t_host = time.perf_counter() - t0
    for name, p in (("trained", p_train), ("serve", p_serve),
                    ("loaded", p_loaded)):
        check(p.shape == (len(Xp),), f"{name} predict shape {p.shape}")
        check(bool(np.isfinite(p).all()), f"{name} predict not finite")
        check(bool(((p >= 0) & (p <= 1)).all()),
              f"{name} predict outside [0, 1]")
    atol = tol("f32_sum_wide")
    d_load = float(np.max(np.abs(p_train - p_loaded)))
    d_serve = float(np.max(np.abs(p_serve - p_loaded)))
    say(f"predict 100000 rows x {bst.num_trees()} trees: device binned "
        f"{t_train:.2f} s, serve pack {t_serve:.2f} s (first call, "
        f"compile included), loaded model on host {t_host:.2f} s; "
        f"max |p - p_loaded|: binned {d_load:.2e}, serve {d_serve:.2e} "
        f"(tolerance {atol})")
    check(d_load <= atol, f"save -> load -> predict differs by {d_load}")
    check(d_serve <= atol, f"serve pack differs from host by {d_serve}")


def phase_reference(lgb, obs, X, y, full_width_leaf: float):
    """The plain reference, outside any timing: the exact float32
    scatter learner against the default backend, cut in rows and
    iterations (chained scatter builds are slow on the chip), not in
    widths.  Held to it: train AUC, and the size of the leaf values —
    AUC moves in the fifth decimal when a few rows' leaf is wrong by
    tens."""
    import numpy as np
    Xr, yr = X[:REF_ROWS], y[:REF_ROWS]
    out, raw, leaf = {}, {}, {}
    for name in ("default", "scatter"):
        ds = lgb.Dataset(Xr, label=yr, params={"max_bin": PARAMS["max_bin"]})
        if name == "scatter":
            os.environ["LGBM_TPU_HIST_BACKEND"] = "scatter"
        try:
            t0 = time.perf_counter()
            bst = lgb.train(PARAMS, ds, num_boost_round=REF_ITERS,
                            keep_training_booster=True)
            auc = train_auc(bst, yr)
            t = time.perf_counter() - t0
        finally:
            os.environ.pop("LGBM_TPU_HIST_BACKEND", None)
        out[name] = (bst._gbdt.hist_backend, auc, t)
        raw[name] = np.asarray(bst._gbdt.scores[:, 0], np.float64)
        leaf[name] = largest_leaf(bst._gbdt, REF_ITERS)
        if name == "default":
            check_first_tree(bst, Xr, yr, "reference, default")
        say(f"reference, {name}: resolved {bst._gbdt.hist_backend}, "
            f"{REF_ROWS} rows x {REF_ITERS} iterations in {t:.2f} s "
            f"(compile included), train AUC {auc:.5f}")
    check(out["default"][0] == "pallas",
          f"default-backend reference resolved {out['default'][0]}")
    check(out["scatter"][0] == "scatter",
          f"scatter reference resolved {out['scatter'][0]}")
    gap = abs(out["default"][1] - out["scatter"][1])
    say(f"reference AUC gap |default - scatter| = {gap:.5f} "
        f"(allowed {AUC_AGREE})")
    check(gap <= AUC_AGREE, f"AUC gap {gap:.5f} > {AUC_AGREE}")
    d = np.abs(raw["default"] - raw["scatter"])
    say(f"reference raw-score gap |default - scatter| over {REF_ROWS} rows: "
        f"max {d.max():.4f}, mean {d.mean():.5f} (observation: the models "
        f"part at the first near-tie)")
    say(f"largest |leaf value| in {REF_ITERS} trees: default "
        f"{leaf['default']:.3f}, scatter {leaf['scatter']:.3f}, full-width "
        f"model {full_width_leaf:.3f} (allowed {LEAF_RATIO} x scatter)")
    for name, v in (("default", leaf["default"]),
                    ("full-width", full_width_leaf)):
        check(v <= LEAF_RATIO * leaf["scatter"],
              f"{name} model's largest |leaf value| {v:.3f} > {LEAF_RATIO}"
              f" x the exact reference's {leaf['scatter']:.3f}")
    check_quiet_path(obs.summary())


def phase_eval(lgb, obs, X, y, seed: int):
    """The documented job's sampled and evaluated parts: bagging 0.8 / 5,
    ``feature_fraction`` 0.8, a 50k held-out set, log-loss and AUC of
    both sets after each of 12 iterations, the metrics worked out on
    the device.  Held to the host functions on the fetched scores."""
    import numpy as np
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.metric.metrics import BinaryLoglossMetric, binary_auc
    Xv, yv = higgs_like(EVAL_VALID_ROWS, seed + 1)
    ds = lgb.Dataset(X, label=y, params={"max_bin": PARAMS["max_bin"]})
    dv = lgb.Dataset(Xv, label=yv, reference=ds)
    params = {**PARAMS, "metric": "binary_logloss,auc", "bagging_freq": 5,
              "bagging_fraction": 0.8, "feature_fraction": 0.8}
    before = obs.summary()["counters"]
    t0 = time.perf_counter()
    bst = lgb.train(params, ds, num_boost_round=EVAL_ITERS,
                    valid_sets=[ds, dv], valid_names=["training", "valid"],
                    keep_training_booster=True)
    g = bst._gbdt
    got = {(n, m): v for n, m, v, _ in g.eval_train() + g.eval_valid()}
    wall = time.perf_counter() - t0
    summary = obs.summary()
    evals = summary["counters"].get("gbdt.evals", 0) \
        - before.get("gbdt.evals", 0)
    host_rows = summary["counters"].get("gbdt.eval_host_rows", 0) \
        - before.get("gbdt.eval_host_rows", 0)
    say(f"eval: {EVAL_ITERS} iterations, bag 0.8 / 5, feature_fraction 0.8, "
        f"{EVAL_VALID_ROWS} held-out rows, {evals} evaluations in "
        f"{wall:.2f} s (compile included); gbdt.eval_backend: "
        f"{summary['gauges'].get('gbdt.eval_backend')}, "
        f"gbdt.eval_host_rows +{host_rows}")
    check(summary["gauges"].get("gbdt.eval_backend") == "device",
          "the metrics were not worked out on the device")
    check(host_rows == 0, f"{host_rows} score rows fetched for a metric")
    check(evals == 2 * EVAL_ITERS + 2, f"{evals} evaluations")
    logloss = BinaryLoglossMetric(Config.from_params({}))
    for name, scores, labels in (("training", g.scores, y),
                                 ("valid", g._valid_scores[0], yv)):
        s = np.asarray(scores)[:, 0]
        auc = float(binary_auc(labels, s))
        ll = logloss.eval(labels, s)[0][1]
        say(f"eval, {name}: device auc {got[(name, 'auc')]!r} host {auc!r}; "
            f"device binary_logloss {got[(name, 'binary_logloss')]!r} "
            f"host {ll!r}")
        check(got[(name, "auc")] == auc,
              f"{name} AUC on the device {got[(name, 'auc')]!r} is not the "
              f"host's {auc!r}")
        check(abs(got[(name, "binary_logloss")] - ll) <= 1e-6 * ll,
              f"{name} log-loss on the device is not the host's")
    check(got[("valid", "auc")] > 0.8, "held-out AUC under 0.8")
    check_quiet_path(summary)


def phase_cli(lgb, X, y, tmp):
    """The config-file entry point: task=train then task=predict on a
    generated CSV — native parser, loader, binning, model file."""
    import numpy as np
    from lightgbm_tpu import cli, native
    from lightgbm_tpu.metric.metrics import binary_auc
    from lightgbm_tpu.utils.log import set_verbosity
    Xc, yc = X[:CLI_ROWS], y[:CLI_ROWS]
    csv = os.path.join(tmp, "smoke.csv")
    t0 = time.perf_counter()
    np.savetxt(csv, np.column_stack([yc, Xc]), delimiter=",", fmt="%.7g")
    say(f"cli: wrote {CLI_ROWS}-row CSV in {time.perf_counter() - t0:.1f} s;"
        f" native parser: "
        f"{'built (g++)' if native.available() else 'NOT built - python parser'}")
    model = os.path.join(tmp, "cli_model.txt")
    result = os.path.join(tmp, "cli_pred.txt")
    conf = os.path.join(tmp, "train.conf")
    with open(conf, "w") as f:
        f.write("task = train\n"
                f"data = {csv}\n"
                "objective = binary\nnum_leaves = 255\nmax_bin = 63\n"
                "min_data_in_leaf = 20\nlearning_rate = 0.1\n"
                f"num_iterations = {CLI_ITERS}\n"
                f"output_model = {model}\nverbose = 0\n")
    try:
        t0 = time.perf_counter()
        check(cli.run([f"config={conf}"]) == 0, "cli task=train failed")
        t_train = time.perf_counter() - t0
        t0 = time.perf_counter()
        check(cli.run(["task=predict", f"data={csv}",
                       f"input_model={model}", f"output_result={result}",
                       "verbose=0"]) == 0, "cli task=predict failed")
        t_pred = time.perf_counter() - t0
    finally:
        set_verbosity(1)            # cli.run sets the package's log level
    p = np.loadtxt(result)
    check(p.shape == (CLI_ROWS,), f"cli predictions shape {p.shape}")
    check(bool(np.isfinite(p).all()), "cli predictions not finite")
    auc = float(binary_auc(yc, p))
    ref = lgb.Booster(model_file=model).predict(Xc)
    d = float(np.max(np.abs(ref - p)))
    say(f"cli: task=train {CLI_ITERS} iterations {t_train:.2f} s, "
        f"task=predict {t_pred:.2f} s, AUC {auc:.5f}, "
        f"max |file - Booster.predict| {d:.2e}")
    check(auc >= 0.85, f"cli model AUC {auc:.5f} < 0.85")
    check(d <= 1e-6, f"cli result file differs from Booster.predict: {d}")


def run_one_chip(jax, seed: int) -> None:
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    obs.enable()
    cache_dir = jax.config.jax_compilation_cache_dir
    n_before = cache_entries(cache_dir)
    say(f"compile cache: {cache_dir} ({n_before} entries at start)")
    X, y = higgs_like(N_ROWS, seed)
    bst, full_width_leaf = phase_train(jax, lgb, obs, X, y)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        phase_predict(lgb, bst, X, tmp)
        del bst
        phase_reference(lgb, obs, X, y, full_width_leaf)
        phase_eval(lgb, obs, X, y, seed)
        phase_cli(lgb, X, y, tmp)
    n_after = cache_entries(cache_dir)
    say(f"compile cache: {n_after - n_before} entries gained "
        f"({n_after} now)")


# ---------------------------------------------------------------------------
# four chips: only the multi-chip phase and what it is compared with
# ---------------------------------------------------------------------------
def run_four_chips(jax, seed: int) -> None:
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    from lightgbm_tpu.parallel.envelope import assert_model_flip_envelope
    obs.enable()
    devs = jax.devices()
    X, y = higgs_like(N_ROWS, seed)

    def dataset():
        return lgb.Dataset(X, label=y, params={"max_bin": PARAMS["max_bin"]})

    def serial_and_data(iters: int):
        """The same job serially on device 0 (what the mesh is compared
        with) and with tree_learner=data on the four-device mesh;
        ``-> ((model text, train AUC), (model text, train AUC))``."""
        out = []
        for learner in ("serial", "data"):
            t0 = time.perf_counter()
            b = lgb.train({**PARAMS, "tree_learner": learner},
                          dataset(), num_boost_round=iters,
                          keep_training_booster=True)
            g = b._gbdt
            auc = train_auc(b, y)
            say(f"tree_learner={learner}: {iters} iterations "
                f"{time.perf_counter() - t0:.2f} s (compile included), "
                f"backend {g.hist_backend}, hist mode {g.hist_mode}, train "
                f"AUC {auc:.5f}")
            check(g.hist_backend == "pallas", f"backend {g.hist_backend}")
            if learner == "serial":
                check(g.mesh_ctx is None, "serial run built a mesh")
            else:
                # root totals summed per shard, then across the mesh
                check_first_tree(b, X, y, "tree_learner=data")
                check(g.mesh_ctx is not None, "tree_learner=data: no mesh")
                check(g.mesh_ctx.mesh.devices.size == 4,
                      f"mesh has {g.mesh_ctx.mesh.devices.size} devices")
                shards = g.device_data.bins.addressable_shards
                rows = [s.data.shape[0] for s in shards]
                on = [s.device for s in shards]
                say(f"data/bins: {len(shards)} shards, rows {rows}, on "
                    f"{[str(d) for d in on]}")
                check(len(shards) == 4 and len(set(on)) == 4,
                      "data/bins is not on four distinct devices")
                check(all(r == N_ROWS // 4 for r in rows),
                      f"data/bins shards are not a quarter each: {rows}")
            out.append((b.model_to_string(), auc, g.hist_mode))
        gap = abs(out[0][1] - out[1][1])
        say(f"AUC gap |serial - data| = {gap:.5f} (allowed {AUC_AGREE})")
        check(gap <= AUC_AGREE, f"AUC gap {gap:.5f} > {AUC_AGREE}")
        return out

    # the job as a user runs it, every default.  At an int8 mode the
    # shards round against one scale and exchange integer code sums, so
    # the four-chip model is the serial one, tree for tree; a float mode
    # is held to the envelope gate in full (first structural flip a
    # near-tie, leaf values of the identical trees within 0.05)
    (model_s, _, mode), (model_d, _, _) = serial_and_data(BLOCK)
    check(span_count(obs.summary(), "gbdt.iteration") == 0,
          "a run left the fused block path")
    say_tiling(obs.summary())
    if mode.startswith("int8"):
        trees_s, trees_d = (m[m.index("Tree=0"):m.index("feature importances:")]
                            for m in (model_s, model_d))
        check(trees_s == trees_d,
              f"serial and data-parallel trees differ at {mode}")
        say(f"serial vs data-parallel at {mode}: {BLOCK} identical trees")
    else:
        rep = assert_model_flip_envelope(model_s, model_d,
                                         label="serial-vs-data-parallel")
        say(f"serial vs data-parallel: flip envelope passed "
            f"({rep['prefix_trees']} identical trees, first flip at tree "
            f"{rep['flip_tree']} node {rep['flip_node']} "
            f"({rep['flip_kind']}, gains {rep['gain_a']} / {rep['gain_b']}, "
            f"near_tie={rep['near_tie']}), max leaf-value gap over the "
            f"identical trees {rep['max_leaf_value_gap']:.3e} <= 0.05)")

    for learner in ("voting", "feature"):
        t0 = time.perf_counter()
        b = lgb.train({**PARAMS, "tree_learner": learner}, dataset(),
                      num_boost_round=8, keep_training_booster=True)
        auc = train_auc(b, y)
        leaves = [t.num_leaves for t in b._gbdt.models]
        say(f"tree_learner={learner} on 4 chips: 8 iterations "
            f"{time.perf_counter() - t0:.2f} s (compile included), leaves "
            f"{min(leaves)}..{max(leaves)}, train AUC {auc:.5f}")
        check(b._gbdt.mesh_ctx is not None, f"{learner} built no mesh")
        check(min(leaves) >= 2, f"{learner} built a stump")
        check(bool(np.isfinite(auc)), f"{learner} AUC not finite")
        del b
    check_quiet_path(obs.summary())


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU: jax.devices()[0] is {devs[0]} "
              f"(platform {devs[0].platform!r})", file=sys.stderr)
        return 2
    if args.chips == 4 and len(devs) != 4:
        print(f"chip_smoke: --chips 4 needs four TPU devices, found "
              f"{len(devs)}", file=sys.stderr)
        return 2
    import lightgbm_tpu  # noqa: F401 - fail here, before any output,
    # where the program is not beside this script
    import jaxlib
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "unknown"
    say(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  libtpu "
        f"{libtpu_version}  device_kind {devs[0].device_kind!r}  devices "
        f"{len(devs)}")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(jax, args.seed)
    else:
        run_one_chip(jax, args.seed)
    stats = devs[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    say("peak HBM on device 0: "
        + (f"{peak / 2**20:.1f} MiB" if peak is not None
           else "not reported by this backend"))
    say(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
