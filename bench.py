"""Benchmark: HIGGS-equivalent binary GBDT training throughput on TPU.

Prints JSON lines of the form:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Emission is INCREMENTAL (VERDICT r5 Weak #1: round 5's driver timeout
mid-ranking-leg erased every leg that had already passed): a parseable
line is printed+flushed right after the 1M headline leg, after the
10.5M full leg, after EVERY aux leg (success, failure, or skip — PR 7),
and finally the complete line — a driver that takes the LAST parseable
line can kill the process at any point after the headline without
losing anything that already ran.  ``BENCH_DEADLINE_S`` (seconds from
process start; 0 = off) is a global budget: once exceeded, remaining
auxiliary legs are recorded as ``"skipped: budget"`` instead of
running, so the final line always lands inside the driver budget.
Aux legs run in never-captured-first order: multichip (device-count
guarded, see below), bin255, rank63, serve, rank, valid.

Multi-chip (PR 7 + ISSUE 11, ROADMAP items 1/2): the ``multichip``
leg trains the HIGGS-shape legs data-parallel on 2/4/8-chip meshes on
the FUSED scan-block path (one dispatch per window) plus the
unfused per-iteration baseline (``LGBM_TPU_MESH_BLOCK=0``), recording
per-chip scaling efficiency against the 1-chip serial anchor,
``fused_speedup`` + the measured dispatch gaps on both dispatch
modes, and a byte-identity parity gate across the two schedules.
On a 1-chip image it records ``"skipped: devices"`` without touching
the single-chip headline; ``--dryrun`` re-execs it on a 2-device
virtual CPU pool as the tier-1 mechanics gate.

Quality gates: the synthetic legs' train AUC must clear ``AUC_GATE``
(``BENCH_AUC_GATE``, default 0.93 — calibrated from the train AUCs
0.95956/0.9549 of an earlier run so a silent learning regression at
0.86 can no longer pass the old 0.85 floor, VERDICT r5 Weak #7), and
the with-valid leg's held-out AUC must clear ``BENCH_VALID_AUC_GATE``
(default 0.90).

Baseline (BASELINE.md): the reference trains HIGGS (10.5M rows x 28
features, 500 iterations, num_leaves=255) in 238.505 s on a dual-Xeon
28-core box -> 22.0M row-iterations/second.  We measure steady-state
training throughput on synthetic HIGGS-shaped data and report
row-iterations/second; vs_baseline > 1 means faster than the reference
CPU number.

Two throughput legs, BOTH at reference shape (28 features, 255 leaves):
  * 1M rows x 64 iterations (fast signal; BENCH_ROWS/BENCH_ITERS tune),
  * the FULL 10.5M rows x 128 iterations (VERDICT r3 #1: the
    extrapolation question — a 10.5M-row uint8 store is ~294 MB and
    fits HBM, so the full-scale number is measured, not inferred; 128 =
    4 exact 32-iteration blocks, so the timed pass holds no residue
    compile and no masked-iteration waste).
    BENCH_FULL=0 skips it; BENCH_FULL_ROWS/BENCH_FULL_ITERS tune.
The reported headline `vs_baseline` is the MINIMUM of the legs run —
no leg may lean on the other.

Every leg reports its compile vs steady-state wall-clock split
(`compile_s` — sourced from the telemetry summary's `gbdt.block_compile`
span — and `steady_s`, the timed pass), so a compile-time regression
can't hide inside a throughput number and vice versa.

Wave regime: right after the headline leg (and incrementally emitted),
``wave_kernel`` records ns/row per active-slot bucket {8, 32, 64, 128}
for the wide one-hot kernel — its cost growing with the slot
count; not measured on the current tree.  ``python bench.py --dryrun``
emits the same table at toy shape on CPU (mechanics gate, tier-1).

With-valid integrity: the ``valid`` leg measures the REAL
``lgb.train(valid_sets=..., early_stopping)`` workflow end-to-end and
derives ``valid_on_block_path`` from telemetry span counts (zero
off-block ``gbdt.iteration`` spans), not from a capability probe.

Real data: when reachable, the bench ALSO trains the reference's own
7000-row binary_classification example at its own train.conf settings
(100 trees, bagging + feature_fraction; eval AUC on binary.test), or any
``BENCH_DATA=train[,test]`` CSV/TSV pair with label in column 0
(``BENCH_DATA_ITERS`` overrides the iteration count).  This leg is
timed COLD (first-touch compile included) — it is the number a new user
sees; `real_data_train_warm_s` reports the steady-state repeat.
"""
import json
import os
import time

import numpy as np

REFERENCE_ROW_ITERS_PER_SEC = 10.5e6 * 500 / 238.505
REF_EXAMPLE = "/root/reference/examples/binary_classification"

_T0 = time.monotonic()
BENCH_DEADLINE_S = float(os.environ.get("BENCH_DEADLINE_S", "0") or 0)
AUC_GATE = float(os.environ.get("BENCH_AUC_GATE", "0.93"))
VALID_AUC_GATE = float(os.environ.get("BENCH_VALID_AUC_GATE", "0.90"))


def _budget_exceeded() -> bool:
    return (BENCH_DEADLINE_S > 0
            and time.monotonic() - _T0 >= BENCH_DEADLINE_S)


def _emit(line) -> None:
    """Print one parseable artifact line NOW (the driver takes the last
    parseable line, so every emission must be self-contained)."""
    print(json.dumps(line), flush=True)


def _peak_field(line, prefix=None) -> None:
    """Record the per-leg ``peak_hbm_bytes`` field (ISSUE 8: BENCH
    artifacts carry memory alongside throughput).  The value is the
    process-cumulative device HBM peak at leg completion
    (``device.memory_stats()``); on backends without allocator stats
    (the CPU tier-1 runs) it is null and ``peak_hbm_reason`` says why
    — an explicit marker, never a silent absence."""
    from lightgbm_tpu.obs.mem_contract import peak_hbm_bytes
    peak, reason = peak_hbm_bytes()
    key = f"{prefix}_peak_hbm_bytes" if prefix else "peak_hbm_bytes"
    line[key] = peak
    if peak is None and reason:
        line.setdefault("peak_hbm_reason", reason)


def _auc(y, s):
    from lightgbm_tpu.metric.metrics import binary_auc
    return binary_auc(y, s)


def _block_compile_s():
    """Cumulative XLA-compile wall-clock so far, sourced from the
    telemetry run summary (the `gbdt.block_compile` span bills every
    dispatch that traced+compiled a new block program).  Legs diff this
    around their warm/timed phases to split compile from steady state."""
    from lightgbm_tpu import obs
    obs.enable()                    # idempotent; in-memory summary only
    spans = obs.summary()["spans"]
    return spans.get("gbdt.block_compile", {}).get("total_s", 0.0)


def real_data_eval():
    """Train on a real dataset file at full depth; -> extra JSON fields
    (or {} when no real data is reachable)."""
    spec = os.environ.get("BENCH_DATA", "")
    if spec:
        # comma-separated "train[,test]" (paths may carry scheme colons)
        parts = spec.split(",")
        train_path, test_path = parts[0], (parts[1] if len(parts) > 1
                                           else parts[0])
        name = os.path.basename(train_path)
    elif os.path.isdir(REF_EXAMPLE):
        train_path = os.path.join(REF_EXAMPLE, "binary.train")
        test_path = os.path.join(REF_EXAMPLE, "binary.test")
        name = "reference binary_classification example"
    else:
        return {"real_data": "unavailable (synthetic-only run)"}

    import jax
    import lightgbm_tpu as lgb
    # the reference example's own train.conf settings
    # (examples/binary_classification/train.conf)
    iters = int(os.environ.get("BENCH_DATA_ITERS", 100))
    params = {"objective": "binary", "metric": "auc", "num_leaves": 63,
              "max_bin": 255, "learning_rate": 0.1,
              "feature_fraction": 0.8, "bagging_freq": 5,
              "bagging_fraction": 0.8, "verbose": -1,
              "num_iterations": iters}
    ds = lgb.Dataset(train_path, params=params)
    c0 = _block_compile_s()
    t0 = time.time()
    bst = lgb.train(params, ds)
    wall = time.time() - t0
    cold_compile_s = _block_compile_s() - c0
    # evaluate the cold-timed model BEFORE the warm re-train appends
    # trees (an early-stopped cold run would otherwise eval warm trees)
    from lightgbm_tpu.io.loader import load_raw_matrix
    Xt, yt = load_raw_matrix(test_path)     # format-autodetected
    auc = _auc(yt.astype(np.float32), bst.predict(Xt, raw_score=True))
    # steady-state repeat: same config, compiles already cached
    g = bst._gbdt
    t0 = time.time()
    g.train_block(iters)
    _sync(g.scores)
    warm = time.time() - t0
    return {"real_data": name, "real_data_iters": iters,
            "real_data_eval_auc": round(auc, 5),
            "real_data_train_s": round(wall, 1),
            "real_data_compile_s": round(cold_compile_s, 3),
            "real_data_train_warm_s": round(warm, 1)}


def synthetic_leg(n, iters, leaves, max_bin, f=28, seed=0):
    """Steady-state training throughput at (n, iters); -> (row_iters/s,
    train AUC, {"compile_s", "steady_s"})."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.basic import Booster
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] * 2 + X[:, 1] - X[:, 2]
         + rng.normal(scale=1.0, size=n) > 0).astype(np.float32)
    ds = lgb.Dataset(X, label=y, params={"max_bin": max_bin})
    ds.construct()
    del X
    params = {"objective": "binary", "num_leaves": leaves,
              "max_bin": max_bin, "learning_rate": 0.1,
              "min_data_in_leaf": 20, "verbose": -1}
    bst = Booster(params=params, train_set=ds)
    c0 = _block_compile_s()
    # warmup: compiles the block program and reaches steady state.  A
    # cap-length window covers every compiled block size the timed pass
    # uses (residue lengths borrow the cap program, masked), so warming
    # the FULL iteration count would only burn wall-clock — at the
    # 10.5M x 500 leg that is ~4 minutes of driver budget
    warm = min(iters, bst._gbdt._block_cap * 2)   # cap is clamped >=1
    bst.update()
    bst._gbdt.train_block(warm)
    _sync(bst._gbdt.scores)
    t0 = time.time()
    bst._gbdt.train_block(iters)
    _sync(bst._gbdt.scores)
    wall = time.time() - t0
    phases = {"compile_s": round(_block_compile_s() - c0, 3),
              "steady_s": round(wall, 3)}

    # accuracy gate (VERDICT r1 #6): the timed model must actually
    # learn — train AUC on the synthetic separable signal, mirroring
    # the reference's GPU-vs-CPU accuracy-parity gating
    # (docs/GPU-Performance.rst:135-161).  A perf change that breaks
    # learning fails the bench.
    auc = float(_auc(y, np.asarray(bst._gbdt.scores[:, 0])))
    # canonical model digest (obs/determinism.py): stamped on every
    # model-training leg so a TPU capture doubles as a cross-host
    # reproducibility check — same seeds, same digest, any machine
    phases["model_digest"] = bst._gbdt.digest(include_scores=False)
    # release this leg's device buffers before the next leg allocates
    # (a lingering 1M-leg working set degraded the 10.5M leg ~2x)
    del bst, ds
    import gc
    gc.collect()
    return n * iters / wall, auc, phases


def _sync(x):
    """Device sync by fetching one scalar to host.  ``chip_smoke.py``
    awaits a warm block with this fetch and then times
    ``jax.block_until_ready`` on the same block, and the other way round:
    in one run on a locally attached v5e neither returned early (the
    second barrier took under 0.1 ms after the fetch, 2 ms after
    ``block_until_ready``), and a block read 0.013-0.019 s (0.2%) longer
    under the fetch (PERF.md section 5, PR 21)."""
    import numpy as np
    return np.asarray(x.ravel()[0])


def _workflow_span_counts():
    """Dispatch-path span counters from the telemetry summary: which
    training path actually RAN (the honest replacement for the old
    `_can_block()` capability probe)."""
    from lightgbm_tpu import obs
    obs.enable()
    spans = obs.summary()["spans"]
    return {k: spans.get(k, {}).get("count", 0)
            for k in ("gbdt.iteration", "gbdt.block",
                      "gbdt.block_compile", "gbdt.eval")}


def valid_leg(leaves, max_bin, f=28):
    """Train WITH a validation set + early stopping through the REAL
    ``lgb.train(valid_sets=..., early_stopping)`` workflow and measure
    THAT (VERDICT r5 headline: the old leg timed hand-driven
    ``train_block()`` calls and reported ``_can_block()`` — a
    capability probe, not a measurement; round 5's actual train() setup
    ran ~3.7 s/iteration off the block path and blew the driver
    budget).

    Reports the cold end-to-end ``lgb.train`` wall, a warm repeat of
    the SAME windowed ``GBDT.train`` loop ``lgb.train`` drives (fused
    blocks to each eval boundary, early-stopping bookkeeping, metrics
    computed from the block-returned valid scores), and a
    TELEMETRY-sourced block-path verdict: ``valid_on_block_path`` is
    true iff the workflow recorded ZERO ``gbdt.iteration`` spans (the
    unfused per-iteration path) and >= 1 block dispatch — what ran,
    not what could have run.

    Eval cadence: ``output_freq`` = ``BENCH_VALID_EVAL_FREQ`` (default
    16, the reference CLI's metric-cadence knob).  Every eval pays one
    host metric round-trip by definition; per-iteration cadence rides
    length-1 block programs since the window=1 fix but would spend the
    leg on metric fetches, not training."""
    import lightgbm_tpu as lgb
    n = int(os.environ.get("BENCH_VALID_ROWS", 1_000_000))
    nv = n // 5
    iters = int(os.environ.get("BENCH_VALID_ITERS", 64))
    freq = int(os.environ.get("BENCH_VALID_EVAL_FREQ", 16))
    rng = np.random.RandomState(3)
    X = rng.normal(size=(n + nv, f)).astype(np.float32)
    y = (X[:, 0] * 2 + X[:, 1] - X[:, 2]
         + rng.normal(scale=1.0, size=n + nv) > 0).astype(np.float32)
    params = {"objective": "binary", "metric": "auc",
              "num_leaves": leaves, "max_bin": max_bin,
              "learning_rate": 0.1, "min_data_in_leaf": 20,
              "output_freq": freq, "verbose": -1}
    ds = lgb.Dataset(X[:n], label=y[:n], params=params)
    vs = lgb.Dataset(X[n:], label=y[n:], reference=ds)
    ds.construct()
    del X
    # early_stopping_round high enough that the timed window never
    # stops: the leg times the with-valid machinery, not a short run
    c0 = _block_compile_s()
    s0 = _workflow_span_counts()
    t0 = time.time()
    bst = lgb.train(dict(params, early_stopping_round=10_000), ds,
                    num_boost_round=iters, valid_sets=[vs],
                    verbose_eval=False, keep_training_booster=True)
    g = bst._gbdt
    _sync(g.scores)
    cold = time.time() - t0
    # warm repeat of the SAME windowed train loop (GBDT.train is what
    # lgb.train's fast path calls), compiles now cached
    t0 = time.time()
    g.train(iters)
    _sync(g.scores)
    wall = time.time() - t0
    s1 = _workflow_span_counts()
    it_spans = s1["gbdt.iteration"] - s0["gbdt.iteration"]
    blocks = (s1["gbdt.block"] + s1["gbdt.block_compile"]
              - s0["gbdt.block"] - s0["gbdt.block_compile"])
    evals = s1["gbdt.eval"] - s0["gbdt.eval"]
    auc = float(_auc(y[n:], np.asarray(g._valid_scores[0][:, 0])))
    digest = g.digest(include_scores=False)
    compile_s = _block_compile_s() - c0
    del bst, ds, vs, g
    import gc
    gc.collect()
    return {"valid_train_rows": n, "valid_rows": nv,
            "valid_iters": iters, "valid_eval_freq": freq,
            "valid_row_iters_per_sec": round(n * iters / wall, 1),
            "valid_train_cold_s": round(cold, 1),
            "valid_eval_auc": round(auc, 5),
            "valid_compile_s": round(compile_s, 3),
            "valid_steady_s": round(wall, 3),
            "valid_block_dispatches": int(blocks),
            "valid_evals": int(evals),
            "valid_model_digest": digest,
            "valid_offblock_iteration_spans": int(it_spans),
            # measured from telemetry over the whole leg (cold train()
            # included): the workflow itself stayed fused
            "valid_on_block_path": bool(it_spans == 0 and blocks > 0)}


def wave_microbench(dryrun: bool = False, f: int = None, max_bin: int = None,
                    buckets=(8, 32, 64, 128), rows: int = None):
    """ns/row per active-slot bucket for the wide one-hot kernel — its
    cost growing with the slot count, tracked per run (not measured on
    the current tree).

    Returns a list of rows ``{"active": A, "wide_ns_per_row": ...}``.
    On TPU this times real dispatches at 1M rows; in
    ``dryrun`` (or off-TPU) it runs interpret-mode kernels at toy shape
    — the TABLE mechanics and kernel paths, not throughput.

    ``f``/``max_bin``/``buckets``/``rows`` override the default
    HIGGS-shape config so the same harness records the 255-bin and
    MSLR-shape (136 features x 255 bins) tables — the reference's own
    headline configs where the last driver capture still loses
    (``north_star.json`` ``wave_kernel_255`` / ``wave_kernel_mslr``)."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.pallas_histogram import (hist_active_pallas,
                                                   pack_values,
                                                   transpose_bins)
    interp = dryrun or jax.default_backend() != "tpu"
    n = rows if rows is not None else int(os.environ.get(
        "BENCH_WAVE_ROWS", 2048 if interp else 1_000_000))
    if f is None:
        f = 4 if interp else 28
    if max_bin is None:
        max_bin = 15 if interp else 63
    L = 255
    reps = 1 if interp else 4
    rng = np.random.RandomState(9)
    bins = rng.randint(0, max_bin, size=(n, f)).astype(np.uint8)
    grad = jnp.asarray(rng.normal(size=n).astype(np.float32))
    hess = jnp.asarray(rng.uniform(0.1, 1.0, size=n).astype(np.float32))
    leaf = rng.randint(0, L, size=n).astype(np.int32)
    bt = jax.jit(transpose_bins)(jnp.asarray(bins))
    leaf_p = jnp.asarray(np.pad(leaf, (0, bt.shape[1] - n),
                                constant_values=-1))
    vals = pack_values(grad, hess, "hilo")

    def timed(fn):
        _sync(fn())                      # warm: compile + steady state
        t0 = time.time()
        for _ in range(reps):
            out = fn()
        _sync(out)
        return (time.time() - t0) / reps / n * 1e9

    table = []
    for A in buckets:
        active = jnp.asarray(
            (np.arange(A, dtype=np.int32) * max(1, L // A)) % L)
        table.append({"active": A, "wide_ns_per_row": round(timed(
            lambda: hist_active_pallas(
                bt, vals, leaf_p, active, num_features=f,
                max_bins=max_bin, mode="hilo", interpret=interp)), 4)})
    return table


# split-finder microbench shapes (ISSUE 9): the reference's own
# headline leaf/bin configs.  Rows land in the `split_finder` table and
# (on TPU runs) fill north_star.json's pending-capture spec.
SPLIT_FINDER_SHAPES = (
    {"leaves": 63, "max_bin": 63}, {"leaves": 63, "max_bin": 255},
    {"leaves": 255, "max_bin": 63}, {"leaves": 255, "max_bin": 255},
)


def split_finder_microbench(dryrun: bool = False):
    """Per-wave split-scan cost, CACHED (the per-leaf best-split cache:
    scan only the ``2A`` newly-histogrammed child slots, ISSUE 9) vs
    FULL (the ``LGBM_TPU_SPLIT_CACHE=0`` rescan of every leaf slot) —
    the O(A·F·B) vs O(L·F·B) regime the reference's
    ``best_split_per_leaf_`` economy wins at 255 leaves.

    One row per (leaves, max_bin) shape: per-wave wall for both scan
    widths, ns per scanned leaf·feature·bin, and the speedup (full /
    cached).  Both scans run the SAME feature-chunked
    ``find_best_splits`` XLA path the 255-leaf learner uses (the fused
    Pallas kernel is row-count-gated off at bench scale).  On TPU the
    feature width is the MSLR 136; in ``--dryrun`` (or off-TPU) shapes
    shrink to CPU-friendly widths — mechanics + the asymptotic ratio,
    not absolute throughput."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.split import SplitParams, find_best_splits
    from lightgbm_tpu.ops.vmem import bin_stride, split_scan_chunk_features
    interp = dryrun or jax.default_backend() != "tpu"
    F = 8 if interp else 136
    reps = 3 if interp else 8
    act = int(os.environ.get("BENCH_SPLIT_ACT", 8))   # splits/tail wave
    params = SplitParams(min_data_in_leaf=20)
    rng = np.random.RandomState(5)
    nb_np = np.full(F, 0, np.int32)
    table = []
    for spec in SPLIT_FINDER_SHAPES:
        L, mb = spec["leaves"], spec["max_bin"]
        B = bin_stride(mb)
        A2 = min(2 * act, L)                   # cached: both new children
        nb = jnp.asarray(nb_np + mb)
        mt = jnp.zeros(F, jnp.int32)
        db = jnp.zeros(F, jnp.int32)
        ic = jnp.zeros(F, bool)
        g = rng.normal(size=(L, F, B)).astype(np.float32)
        h = rng.uniform(0.01, 1.0, size=(L, F, B)).astype(np.float32)
        c = rng.uniform(0.0, 50.0, size=(L, F, B)).astype(np.float32)
        hist = jnp.asarray(np.stack([g, h, c], axis=-1))   # [L, F, B, 3]
        lsg = jnp.sum(hist[:, 0, :, 0], axis=-1)
        lsh = jnp.sum(hist[:, 0, :, 1], axis=-1)
        lcnt = jnp.sum(hist[:, 0, :, 2], axis=-1)

        def scan(grid, sg, sh, sc):
            fc = split_scan_chunk_features(grid.shape[0], F, B)
            return find_best_splits(
                grid, sg, sh, sc, nb, mt, db, ic, params, None,
                any_categorical=False, any_missing=True,
                feature_chunk=fc).gain

        scan_jit = jax.jit(scan)

        def timed(grid):
            args = (grid, lsg[:grid.shape[0]], lsh[:grid.shape[0]],
                    lcnt[:grid.shape[0]])
            _sync(scan_jit(*args))             # warm: compile
            best = float("inf")
            for _ in range(reps):              # min-of-reps: dispatch
                t0 = time.time()               # noise must not fake a
                _sync(scan_jit(*args))         # regression (or a win)
                best = min(best, time.time() - t0)
            return best

        cached_s = timed(hist[:A2])
        full_s = timed(hist)
        table.append({
            "leaves": L, "max_bin": mb, "features": F,
            "cached_slots": A2, "full_slots": L,
            "cached_us_per_wave": round(cached_s * 1e6, 2),
            "full_us_per_wave": round(full_s * 1e6, 2),
            "cached_ns_per_lfb": round(cached_s * 1e9 / (A2 * F * B), 4),
            "full_ns_per_lfb": round(full_s * 1e9 / (L * F * B), 4),
            "speedup": round(full_s / max(cached_s, 1e-12), 2),
        })
    return table


# keys the rank_grad microbench must emit — `--dryrun` validates them
# (tests/test_bench_budget), proving the per-bucket obj.rank_grad.<M>
# spans fire alongside the measured ns/doc
RANK_GRAD_SCHEMA_KEYS = (
    "rank_grad_docs", "rank_grad_queries", "rank_grad_ns_per_doc",
    "rank_grad_buckets", "rank_grad_bucket_spans")


def rank_grad_microbench(dryrun: bool = False):
    """ns/doc of ``LambdarankNDCG.get_gradients`` at the MSLR bucket
    mix (ISSUE 9 satellite: the OTHER half of the 0.27x ranking-leg
    attribution — per-query lambda cost vs split-find/routing).  Runs
    the objective EAGERLY (per-bucket dispatches host-blocked at the
    end) under telemetry, so the ``obj.rank_grad.<M>`` spans record
    which query-size bucket dominates."""
    import gc
    import jax.numpy as jnp
    from lightgbm_tpu import obs
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata
    from lightgbm_tpu.objective.objectives import LambdarankNDCG
    import jax
    interp = dryrun or jax.default_backend() != "tpu"
    nq = int(os.environ.get("BENCH_RANK_GRAD_QUERIES",
                            200 if interp else 19_000))
    reps = 2 if interp else 4
    rng = np.random.RandomState(7)
    # the ranking leg's own MSLR-like query-size mix
    sizes = np.clip(np.round(rng.lognormal(mean=4.55, sigma=0.7,
                                           size=nq)),
                    1, 1251).astype(np.int64)
    n = int(sizes.sum())
    raw = rng.normal(size=n)
    rel = np.digitize(raw, np.quantile(raw, [0.55, 0.78, 0.92, 0.98])
                      ).astype(np.float32)
    obj = LambdarankNDCG(Config.from_params({"objective": "lambdarank"}))
    obj.init(Metadata(label=rel,
                      query_boundaries=np.concatenate(
                          [[0], np.cumsum(sizes)]).astype(np.int32)), n)
    score = jnp.asarray(rng.normal(size=n).astype(np.float32))
    obs.enable()
    spans0 = {k: v.get("count", 0)
              for k, v in obs.summary()["spans"].items()
              if k.startswith("obj.rank_grad.")}
    _sync(obj.get_gradients(score)[0])         # warm: compile buckets
    t0 = time.time()
    for _ in range(reps):
        out = obj.get_gradients(score)[0]
    _sync(out)
    per = (time.time() - t0) / reps
    spans = obs.summary()["spans"]
    bucket_spans = sorted(
        int(k.rsplit(".", 1)[1]) for k, v in spans.items()
        if k.startswith("obj.rank_grad.")
        and v.get("count", 0) > spans0.get(k, 0))
    res = {"rank_grad_docs": n, "rank_grad_queries": nq,
           "rank_grad_ns_per_doc": round(per / n * 1e9, 3),
           "rank_grad_buckets": len(obj.buckets),
           "rank_grad_bucket_spans": bucket_spans,
           "rank_grad_bucket_mix": "MSLR lognormal(4.55,0.7) clip 1..1251"}
    del obj, score
    gc.collect()
    return res


# keys the device-time attribution leg must emit (ISSUE 10) —
# `--dryrun` runs the REAL leg (profiled toy train, parsed capture) on
# CPU and validates them as tier-1 (tests/test_bench_budget)
ATTRIBUTION_SCHEMA_KEYS = (
    "attribution_rows", "attribution_iters", "attribution_windows",
    "attribution_device_time_s", "attribution_coverage",
    "attribution_device_frac", "attribution_host_gap_frac",
    "attribution_collective_frac", "attribution_top_programs",
    "attribution_spans", "attribution_cost_programs",
    "attribution_dispatch_gap_mean_s")


def attribution_leg(dryrun: bool = False):
    """Device-time attribution leg (ISSUE 10): a small train profiled
    under ``LGBM_TPU_PROFILE`` (windowed capture: warmup window, then
    bounded captured windows), reduced to per-leg artifact fields —
    device / host-gap / collective fractions, top programs by device
    time, per-program FLOPs/bytes from the XLA cost model, and the
    always-on ``gbdt.dispatch_gap_mean_s`` host-latency gauge (the
    ROADMAP item-1 signal).  The capture run is SEPARATE from the
    timed legs: profiling overhead (trace + parse) must never sit
    inside a throughput number.  Setting ``LGBM_TPU_PROFILE`` on the
    whole bench process additionally profiles every leg's training —
    this leg exists so the DEFAULT artifact always carries
    attribution."""
    import gc
    import shutil
    import tempfile
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs

    # off-TPU the leg shrinks to toy shape (same rule as the wave /
    # split-finder microbenches): the CPU backend traces one event per
    # executed thunk, so a real-shape capture costs minutes of parse —
    # mechanics there, measurement on TPU
    toy = dryrun or jax.default_backend() != "tpu"
    n = int(os.environ.get("BENCH_ATTR_ROWS", 1_500 if toy else 100_000))
    # >= 3 profile windows: the warmup->capture and capture->stop
    # boundaries are profiler transitions excluded from dispatch-gap
    # accounting, so at least one plain boundary must remain to sample
    # the gbdt.dispatch_gap_mean_s gauge
    iters = int(os.environ.get("BENCH_ATTR_ITERS", 6 if toy else 10))
    f = int(os.environ.get("BENCH_ATTR_FEATURES", 5 if toy else 28))
    leaves = int(os.environ.get("BENCH_ATTR_LEAVES", 7 if toy else 63))
    max_bin = int(os.environ.get("BENCH_ATTR_BIN", 15 if toy else 63))
    rng = np.random.RandomState(13)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] * 2 + X[:, 1] - X[:, 2]
         + rng.normal(scale=1.0, size=n) > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": leaves,
              "max_bin": max_bin, "learning_rate": 0.1,
              "min_data_in_leaf": 20, "verbose": -1}
    ds = lgb.Dataset(X, label=y, params=params)
    del X
    obs.enable()                    # dispatch-gap counters need live obs
    td = tempfile.mkdtemp(prefix="lgbm_attr_")
    prev = os.environ.get("LGBM_TPU_PROFILE")
    os.environ["LGBM_TPU_PROFILE"] = td
    try:
        bst = lgb.train(params, ds, num_boost_round=iters,
                        verbose_eval=False)
    finally:
        if prev is None:
            os.environ.pop("LGBM_TPU_PROFILE", None)
        else:
            os.environ["LGBM_TPU_PROFILE"] = prev
    s = obs.summary()
    da = s.get("device_attribution") or {}
    shutil.rmtree(td, ignore_errors=True)
    if da.get("error") or "device_time_s" not in da:
        raise RuntimeError("attribution capture failed: "
                           f"{da.get('error', 'no capture produced')}")
    wall = max(da.get("capture_wall_s") or 0.0, 1e-9)
    wwall = max(da.get("window_wall_s") or wall, 1e-9)
    cost = (da.get("cost_model") or {}).get("programs") or []
    del bst, ds
    gc.collect()
    return {
        "attribution_rows": n, "attribution_iters": iters,
        "attribution_windows": da.get("windows"),
        "attribution_device_time_s": da["device_time_s"],
        "attribution_coverage": da.get("coverage"),
        "attribution_device_frac": round(
            (da.get("device_busy_s") or 0.0) / wall, 4),
        "attribution_host_gap_frac": round(
            (da.get("host_gap_s") or 0.0) / wwall, 4),
        "attribution_collective_frac": da.get("collective_frac"),
        "attribution_top_programs": da.get("top_programs"),
        "attribution_spans": {
            k: v["device_s"]
            for k, v in list((da.get("spans") or {}).items())[:8]},
        "attribution_cost_programs": [
            {"program": r.get("program"), "flops": r.get("flops"),
             "bytes_accessed": r.get("bytes_accessed"),
             "arith_intensity": r.get("arith_intensity"),
             "bound": r.get("bound")} for r in cost],
        "attribution_dispatch_gap_mean_s": s.get("gauges", {}).get(
            "gbdt.dispatch_gap_mean_s"),
    }


# keys every serve (predict) leg must emit — `--dryrun` validates this
# schema at toy shape as the tier-1 mechanics gate (tests/test_bench_budget)
SERVE_SCHEMA_KEYS = (
    "serve_rows", "serve_trees", "serve_rows_per_sec",
    "serve_binned_rows_per_sec", "serve_host_rows_per_sec",
    "serve_vs_host", "serve_compile_s", "serve_parity_ok",
    "serve_latency_ms", "serve_steady_recompiles", "serve_recompile_ok",
    "serve_requests", "serve_batches")


def serve_leg(dryrun: bool = False):
    """TPU-resident prediction serving (ROADMAP item 3): big-batch
    rows/s through the compiled predictor (`lightgbm_tpu/serve/`), the
    int8-binned fast path, p50/p99 request latency per padding bucket
    through the async micro-batching harness, and a zero-post-warmup-
    recompile check over mixed batch sizes.

    Comparison anchor: the HOST vectorized numpy traversal of the same
    model (`Tree.predict_batch` — the in-repo analog of the reference's
    per-row `src/application/predictor.hpp` walk, which is strictly
    slower still; the reference publishes no predictor throughput
    figure to quote).  Gates: device scores must match the f64 host
    oracle within 1 ulp f32 (`serve_parity_ok`) and steady-state
    serving must never re-enter XLA (`serve_recompile_ok`)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serve import PredictionServer, compile_model
    from lightgbm_tpu.obs.trace_contract import CompileTracker

    f = 5 if dryrun else 28
    n_train = int(os.environ.get("BENCH_SERVE_TRAIN_ROWS",
                                 2_000 if dryrun else 200_000))
    iters = int(os.environ.get("BENCH_SERVE_ITERS", 4 if dryrun else 100))
    leaves = 7 if dryrun else 63
    n_big = int(os.environ.get("BENCH_SERVE_ROWS",
                               2_048 if dryrun else 1 << 20))
    reps = 1 if dryrun else 4
    rng = np.random.RandomState(11)
    X = rng.normal(size=(n_train, f)).astype(np.float32)
    y = (X[:, 0] * 2 + X[:, 1] - X[:, 2]
         + rng.normal(scale=1.0, size=n_train) > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": leaves, "max_bin": 63,
              "learning_rate": 0.1, "min_data_in_leaf": 20, "verbose": -1}
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.train(params, ds, num_boost_round=iters, verbose_eval=False)
    del X, ds

    t0 = time.time()
    cm = compile_model(bst)
    compile_s = time.time() - t0
    Xq = rng.normal(size=(n_big, f)).astype(np.float32)

    def timed_rows(fn):
        fn()                                    # warm: compile + steady
        t0 = time.time()
        for _ in range(reps):
            out = fn()
        _sync_np(out)
        return n_big * reps / (time.time() - t0)

    def _sync_np(x):
        return np.asarray(x).ravel()[:1]

    # one-dispatch big-batch scoring (n_big is itself a bucket size)
    dev_rate = timed_rows(lambda: cm.predict_raw(Xq))
    bins = cm.bin_rows(Xq)
    binned_rate = timed_rows(lambda: cm.predict_raw(bins, binned=True))

    # host anchor: vectorized numpy traversal of the same trees
    n_host = min(n_big, 512 if dryrun else 20_000)
    Xh = Xq[:n_host].astype(np.float64)
    t0 = time.time()
    host = np.zeros(n_host)
    for t in bst._gbdt.models:
        host += t.predict_batch(Xh)
    host_s = time.time() - t0
    host_rate = n_host / max(host_s, 1e-9)

    # parity gate: device raw scores within 1 ulp f32 of the f64 oracle
    dev_sample = np.asarray(cm.predict_raw(Xq[:n_host]), np.float64)
    ulp = np.spacing(np.abs(host).astype(np.float32)).astype(np.float64)
    parity_ok = bool(np.all(np.abs(dev_sample - host) <= ulp))

    # async harness over mixed batch sizes, under a compile tracker:
    # warmup compiles the bucket set, then steady traffic must never
    # re-enter XLA (the padding buckets working as designed)
    buckets = (64, 256, 1024) if dryrun else (256, 1024, 4096)
    sizes = [1, 3, 17, 100, 240, 900]
    n_req = int(os.environ.get("BENCH_SERVE_REQUESTS",
                               30 if dryrun else 300))
    with CompileTracker(track_threads=False) as tracker:
        srv = PredictionServer(cm, max_batch=max(buckets),
                               max_wait_ms=1.0, buckets=buckets,
                               min_bucket=buckets[0], raw_score=True)
        tracker.mark_steady()
        futs = [srv.submit(Xq[(37 * i) % (n_big - 1024):][:sizes[i % len(sizes)]])
                for i in range(n_req)]
        for fu in futs:
            fu.result(120)
        stats = srv.stats()
        srv.close()
    rep = tracker.report()
    return {
        "serve_rows": n_big, "serve_trees": cm.num_trees,
        "serve_rows_per_sec": round(dev_rate, 1),
        "serve_binned_rows_per_sec": round(binned_rate, 1),
        "serve_host_rows_per_sec": round(host_rate, 1),
        "serve_vs_host": round(dev_rate / max(host_rate, 1e-9), 4),
        "serve_compile_s": round(compile_s, 3),
        "serve_parity_ok": parity_ok,
        "serve_latency_ms": stats["latency_ms"],
        "serve_steady_recompiles": rep["compiles_steady"],
        "serve_recompile_ok": bool(rep["steady_ok"]),
        "serve_requests": stats["resolved"],
        "serve_batches": stats["batches"],
        "serve_baseline": "host vectorized numpy traversal of the same "
                          "model (reference predictor.hpp per-row walk "
                          "analog; no published reference figure)",
    }


# keys the serve_load (QPS-sweep) leg must emit — `--dryrun` validates
# this schema at toy shape as the tier-1 gate (tests/test_bench_budget)
SERVE_LOAD_SCHEMA_KEYS = (
    "serve_load_table", "serve_load_duration_s", "serve_load_qps_sweep",
    "serve_load_rows_per_request")


def serve_load_leg(line=None, dryrun: bool = False):
    """Open-loop Poisson QPS sweep against a LIVE ``PredictionServer``
    (ROADMAP item 3c's measurement instrument, ``tools/load_harness``):
    per offered-QPS step, achieved QPS, rows/s, and p50/p99/p99.9
    request latency — arrival times drawn up-front so the generator
    never self-throttles when the server slows down (tail latency
    under OFFERED load is the contract; a closed loop measures the
    flattering one).  Steps are emitted incrementally onto ``line``
    so a driver deadline keeps every step that ran."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serve import PredictionServer, compile_model
    from tools.load_harness import sweep

    f = 5 if dryrun else 28
    n_train = int(os.environ.get("BENCH_SERVE_TRAIN_ROWS",
                                 2_000 if dryrun else 200_000))
    iters = int(os.environ.get("BENCH_SERVE_ITERS", 4 if dryrun else 100))
    leaves = 7 if dryrun else 63
    rng = np.random.RandomState(17)
    X = rng.normal(size=(n_train, f)).astype(np.float32)
    y = (X[:, 0] * 2 + X[:, 1] - X[:, 2]
         + rng.normal(scale=1.0, size=n_train) > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": leaves, "max_bin": 63,
              "learning_rate": 0.1, "min_data_in_leaf": 20, "verbose": -1}
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.train(params, ds, num_boost_round=iters, verbose_eval=False)
    del ds
    cm = compile_model(bst)
    pool = rng.normal(size=(8_192, f)).astype(np.float32)
    qps_env = os.environ.get("BENCH_SERVE_LOAD_QPS", "")
    qps = ([float(q) for q in qps_env.split(",") if q.strip()]
           or ([150.0, 600.0] if dryrun
               else [1_000.0, 5_000.0, 20_000.0, 50_000.0]))
    dur = float(os.environ.get("BENCH_SERVE_LOAD_S",
                               "0.5" if dryrun else "5"))
    k = int(os.environ.get("BENCH_SERVE_LOAD_ROWS", 1))
    buckets = (64, 256, 1024) if dryrun else (256, 1024, 4096)
    out = {"serve_load_qps_sweep": qps, "serve_load_duration_s": dur,
           "serve_load_rows_per_request": k, "serve_load_table": []}

    def _step(row):
        out["serve_load_table"].append(row)
        if line is not None:
            line["serve_load_table"] = out["serve_load_table"]
            line["partial"] = f"serve-load-{row['offered_qps']:g}qps"
            _emit(line)

    srv = PredictionServer(cm, max_batch=max(buckets), max_wait_ms=1.0,
                           buckets=buckets, min_bucket=buckets[0],
                           raw_score=True)
    try:
        sweep(srv, pool, qps, dur, rows_per_request=k, seed=13,
              emit=_step)
    finally:
        srv.close()
    return out


# extra wave-table shapes: the reference's own headline configs where
# the last capture still loses (ROADMAP item 2) — recorded so the
# losing regime (255-leaf split-find/routing vs histogram vs lambdarank
# grads) is attributable per bucket.  Keys land in north_star.json.
WAVE_AUX_SHAPES = {
    # the exact docs/Experiments.rst HIGGS config (255 bins)
    "wave_kernel_255": {"features": 28, "max_bin": 255},
    # MSLR-shape: 136 features x 255 bins (lambdarank leg's store)
    "wave_kernel_mslr": {"features": 136, "max_bin": 255},
}


def wave_aux_tables(dryrun: bool = False):
    """The 255-bin / MSLR-shape wave tables (see WAVE_AUX_SHAPES).  In
    dryrun the shapes shrink to interpret-safe toys (255 bins kept —
    that is the regime under test; feature counts reduced) and only the
    boundary buckets run: mechanics + kernel-path validation, not
    throughput."""
    out = {}
    for key, spec in WAVE_AUX_SHAPES.items():
        if dryrun:
            out[key] = wave_microbench(
                dryrun=True, f=min(4, spec["features"]),
                max_bin=spec["max_bin"], buckets=(8, 128), rows=512)
        else:
            out[key] = wave_microbench(
                dryrun=False, f=spec["features"], max_bin=spec["max_bin"])
    return out


# keys every multichip leg result must emit when the leg RUNS —
# `--dryrun` validates this schema on a 2-device virtual CPU pool as
# the tier-1 mechanics gate (tests/test_bench_budget).  On a 1-chip
# image the leg instead records {"multichip_leg": "skipped: devices"}
# and never touches the single-chip headline.
MULTICHIP_SCHEMA_KEYS = (
    "multichip_devices_visible", "multichip_device_kind",
    "multichip_rows", "multichip_iters", "multichip_leaves",
    "multichip_max_bin",
    "multichip_serial_row_iters_per_sec", "multichip_table",
    "multichip_parity_ok", "multichip_best_vs_baseline")


def _mc_train_rate(ds, y, n, iters, leaves, max_bin, ndev, fused=True):
    """Train ``iters`` data-parallel iterations on an ``ndev``-device
    mesh; -> (row_iters/s, auc, phases, model_text).  ``fused`` toggles
    the scan-block program (``LGBM_TPU_MESH_BLOCK``): fused runs one
    dispatch per window, unfused one length-1 block per iteration —
    byte-identical models either way, which the bit-parity gate
    holds.  ``phases`` additionally carries ``dispatch_gap_mean_s``
    (host gap between training dispatches, from the live telemetry
    counters) — the `gbdt.dispatch_gap_s` regime the fused path
    exists to kill."""
    from lightgbm_tpu import obs
    from lightgbm_tpu.basic import Booster
    prev_mb = os.environ.get("LGBM_TPU_MESH_BLOCK")
    os.environ["LGBM_TPU_MESH_BLOCK"] = "1" if fused else "0"
    try:
        params = {"objective": "binary", "num_leaves": leaves,
                  "max_bin": max_bin, "learning_rate": 0.1,
                  "min_data_in_leaf": 20, "verbose": -1,
                  "tree_learner": "data", "mesh_shape": [ndev]}
        bst = Booster(params=params, train_set=ds)
        g = bst._gbdt
        # warm with the block length the steady phase will dispatch
        # (fused: one full-cap window so the scan program compiles
        # here, not inside the timed phase; residue lengths borrow it)
        warm = min(iters, g._block_cap if fused else 3)
        t0 = time.time()
        bst.update()
        g.train_block(warm)
        _sync(g.scores)
        warm_s = time.time() - t0
        obs.enable()                 # dispatch-gap counters
        c0 = dict(obs.summary()["counters"])
        t0 = time.time()
        g.train_block(iters)
        _sync(g.scores)
        wall = time.time() - t0
        c1 = obs.summary()["counters"]
        gaps = c1.get("gbdt.dispatch_gaps", 0) - c0.get(
            "gbdt.dispatch_gaps", 0)
        gap_s = c1.get("gbdt.dispatch_gap_s", 0.0) - c0.get(
            "gbdt.dispatch_gap_s", 0.0)
        auc = float(_auc(y, np.asarray(g.scores[:, 0])))
        model = g.save_model_to_string()
        phases = {"warm_s": round(warm_s, 3),
                  "steady_s": round(wall, 3),
                  "dispatch_gap_mean_s": (round(gap_s / gaps, 6)
                                          if gaps else None),
                  "model_digest": g.digest(include_scores=False)}
        del bst, g
        import gc
        gc.collect()
        return n * iters / wall, auc, phases, model
    finally:
        if prev_mb is None:
            os.environ.pop("LGBM_TPU_MESH_BLOCK", None)
        else:
            os.environ["LGBM_TPU_MESH_BLOCK"] = prev_mb


def multichip_leg(line=None, dryrun: bool = False):
    """Data-parallel training across a REAL >=2-chip mesh: per-chip
    scaling efficiency + fused/unfused row-iters/s — the ROADMAP item
    1 north-star measurement (projected 8-chip 14.5x vs the 3.0x
    target was, until this leg, arithmetic only).

    Device-count guarded: on a 1-chip/CPU image it records
    ``"skipped: devices"`` and NEVER zeroes the single-chip headline.
    In ``--dryrun`` on a 1-device image it re-execs itself on a
    2-device virtual CPU pool (``--multichip-child``) so the mesh
    mechanics, schema, and the bit-parity gate run as a tier-1
    gate without TPU hardware.

    Per mesh size d (ISSUE 11): row_iters/s on the FUSED scan-block
    path (the production schedule since the partition-rule refactor:
    one dispatch per window), plus the unfused per-iteration baseline
    (``LGBM_TPU_MESH_BLOCK=0`` — one dispatch per iteration, the
    ``gbdt.dispatch_gap_s`` regime) with ``fused_speedup`` and the
    measured ``dispatch_gap_mean_s`` on both dispatch modes;
    ``scaling_efficiency`` = rate / (d x serial_rate) against the
    1-chip serial path (the production single-chip anchor, fused
    blocks), and the two models compared byte-for-byte
    (``multichip_parity_ok`` — a parity break zeroes the headline:
    a wrong-answer speedup must not score).  Results are emitted
    incrementally per mesh size when ``line`` is given."""
    import jax
    ndev = len(jax.devices())
    if ndev < 2:
        if not dryrun:
            return {"multichip_leg": "skipped: devices",
                    "multichip_devices_visible": ndev}
        # dryrun mechanics gate: re-exec on a 2-device virtual CPU pool
        import subprocess
        import sys
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = [x for x in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in x]
        flags.append("--xla_force_host_platform_device_count=2")
        env["XLA_FLAGS"] = " ".join(flags)
        here = os.path.dirname(os.path.abspath(__file__))
        env["PYTHONPATH"] = here + os.pathsep + env.get("PYTHONPATH", "")
        r = subprocess.run(
            [sys.executable, os.path.join(here, "bench.py"),
             "--multichip-child"],
            env=env, cwd=here, capture_output=True, text=True, timeout=360)
        for ln in reversed(r.stdout.splitlines()):
            if ln.startswith("MULTICHIP_CHILD:"):
                out = json.loads(ln[len("MULTICHIP_CHILD:"):])
                out["multichip_dryrun_child"] = True
                return out
        raise RuntimeError(
            f"multichip dryrun child produced no result "
            f"(rc={r.returncode}): {r.stdout[-1000:]} {r.stderr[-2000:]}")

    import gc
    import lightgbm_tpu as lgb
    n = int(os.environ.get("BENCH_MC_ROWS", 2_000 if dryrun else 1_000_000))
    iters = int(os.environ.get("BENCH_MC_ITERS", 2 if dryrun else 48))
    leaves = int(os.environ.get("BENCH_MC_LEAVES", 7 if dryrun else 255))
    max_bin = int(os.environ.get("BENCH_MC_BIN", 15 if dryrun else 63))
    f = 8 if dryrun else 28
    out = {
        "multichip_devices_visible": ndev,
        "multichip_device_kind": jax.devices()[0].platform,
        "multichip_rows": n, "multichip_iters": iters,
        "multichip_leaves": leaves, "multichip_max_bin": max_bin,
    }
    if dryrun:
        out["multichip_dryrun"] = True

    # 1-chip serial anchor: the PRODUCTION single-chip path (fused
    # blocks) at the same shape — scaling efficiency is honest only
    # against the path a 1-chip user actually runs
    serial_rate, serial_auc, _ = synthetic_leg(n, iters, leaves, max_bin,
                                               f=f, seed=0)
    out["multichip_serial_row_iters_per_sec"] = round(serial_rate, 1)
    out["multichip_serial_train_auc"] = round(serial_auc, 5)

    # one shared binned dataset for every mesh run (binning the 1M-row
    # store once, not per mesh size)
    rng = np.random.RandomState(0)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] * 2 + X[:, 1] - X[:, 2]
         + rng.normal(scale=1.0, size=n) > 0).astype(np.float32)
    ds = lgb.Dataset(X, label=y, params={"max_bin": max_bin})
    ds.construct()
    del X

    table = []
    parity_ok = True
    best_vs = 0.0
    for d in [c for c in (2, 4, 8) if c <= ndev]:
        if _budget_exceeded():
            out.setdefault("multichip_skipped_counts", []).append(d)
            continue
        # two runs per mesh size: fused (the production path: one
        # dispatch per window) and the unfused per-iteration baseline
        # (LGBM_TPU_MESH_BLOCK=0: one length-1 block per iteration —
        # the per-dispatch-overhead regime the fused path removes).
        # The two models must be byte-identical.
        r_on, auc_on, ph_on, m_on = _mc_train_rate(
            ds, y, n, iters, leaves, max_bin, d)
        r_uf, _, ph_uf, m_uf = _mc_train_rate(
            ds, y, n, iters, leaves, max_bin, d, fused=False)
        parity_ok = parity_ok and (m_on == m_uf)
        vs = r_on / REFERENCE_ROW_ITERS_PER_SEC
        best_vs = max(best_vs, vs)
        table.append({
            "devices": d,
            "row_iters_per_sec": round(r_on, 1),
            "unfused_row_iters_per_sec": round(r_uf, 1),
            "fused_speedup": round(r_on / max(r_uf, 1e-9), 4),
            "dispatch_gap_mean_s": ph_on["dispatch_gap_mean_s"],
            "unfused_dispatch_gap_mean_s": ph_uf["dispatch_gap_mean_s"],
            "scaling_efficiency": round(
                r_on / max(d * serial_rate, 1e-9), 4),
            "vs_baseline": round(vs, 4),
            "train_auc": round(auc_on, 5),
            "auc_ok": bool(auc_on >= AUC_GATE),
            "warm_s": ph_on["warm_s"],
            "steady_s": ph_on["steady_s"],
            "model_digest": ph_on["model_digest"],
        })
        out["multichip_table"] = table
        out["multichip_parity_ok"] = bool(parity_ok)
        out["multichip_best_vs_baseline"] = round(best_vs, 4)
        if line is not None:
            line.update(out)
            line["partial"] = f"multichip-{d}dev"
            _emit(line)
        gc.collect()
    out["multichip_table"] = table
    out["multichip_parity_ok"] = bool(parity_ok)
    out["multichip_best_vs_baseline"] = round(best_vs, 4)

    # the FULL 10.5M-row HIGGS-shape leg on the widest available mesh
    # (the headline-scale claim; budget-guarded, TPU runs only)
    if (not dryrun and os.environ.get("BENCH_MC_FULL", "1") != "0"
            and not _budget_exceeded() and table):
        d = table[-1]["devices"]
        nf = int(os.environ.get("BENCH_MC_FULL_ROWS", 10_500_000))
        itf = int(os.environ.get("BENCH_MC_FULL_ITERS", 64))
        del ds
        gc.collect()
        rng = np.random.RandomState(1)
        Xf = rng.normal(size=(nf, 28)).astype(np.float32)
        yf = (Xf[:, 0] * 2 + Xf[:, 1] - Xf[:, 2]
              + rng.normal(scale=1.0, size=nf) > 0).astype(np.float32)
        dsf = lgb.Dataset(Xf, label=yf, params={"max_bin": max_bin})
        dsf.construct()
        del Xf
        rf, aucf, phf, _ = _mc_train_rate(dsf, yf, nf, itf, leaves,
                                          max_bin, d)
        out.update({
            "multichip_full_devices": d, "multichip_full_rows": nf,
            "multichip_full_iters": itf,
            "multichip_full_row_iters_per_sec": round(rf, 1),
            "multichip_full_vs_baseline": round(
                rf / REFERENCE_ROW_ITERS_PER_SEC, 4),
            "multichip_full_train_auc": round(aucf, 5),
            "multichip_full_warm_s": phf["warm_s"],
            "multichip_full_steady_s": phf["steady_s"],
            "multichip_full_model_digest": phf["model_digest"],
        })
        del dsf
        gc.collect()
    return out


def multichip_child():
    """``bench.py --multichip-child``: the dryrun mechanics run inside
    the forced 2-device CPU pool (spawned by :func:`multichip_leg`)."""
    out = multichip_leg(dryrun=True)
    print("MULTICHIP_CHILD:" + json.dumps(out), flush=True)


# keys the stream_ingest (out-of-core) leg must emit — `--dryrun`
# validates them plus the byte-identity and SIGKILL-resume gates
STREAM_SCHEMA_KEYS = (
    "stream_rows", "stream_block_rows", "stream_shards", "stream_iters",
    "stream_ingest_rows_per_sec", "stream_row_iters_per_sec",
    "stream_identity_ok", "stream_resume_ok",
    "stream_host_rss_peak_bytes", "stream_model_digest",
    # ISSUE 20: the resolved histogram backend the scale phase streamed
    # on, the ledger-tracked rows/s, and the two A/B verdicts (seeded
    # kernel folds vs forced scatter; pipeline vs serial escape hatch)
    "stream_backend", "stream_rows_per_sec",
    "stream_kernel_speedup", "stream_pipeline_speedup")


def stream_ingest_leg(line=None, dryrun: bool = False):
    """Out-of-core streamed training (ISSUE 14, ROADMAP item 4): rows
    live in the mmap binned shard store (`io/outofcore.py`) and stream
    through the device block-by-block (`boosting/streaming.py`) — the
    leg that trains a dataset that was never going to fit.

    Phases (each emitted incrementally when ``line`` is given, so a
    SIGKILL mid-leg keeps everything that ran):

    1. **resume mechanics** — a REAL SIGKILL mid-ingest in a
       subprocess (``bench.py --stream-child``), then a resuming
       ingest whose manifest must equal a clean ingest's
       (``stream_resume_ok``);
    2. **byte-identity gate** at a fittable size: streamed training ==
       resident in-memory training, model + score digests, on the
       exact-accumulation scatter backend (forced on TPU for the gate;
       the CPU default) — ``stream_identity_ok``;
    3. **scale phase**: ingest ≥100M synthetic rows (toy shape in
       ``--dryrun``) shard-by-shard into the store, then streamed
       training, recording ingest rows/s, train row-iters/s, the
       device HBM peak (must track LGBM_TPU_STREAM_ROWS, not dataset
       rows — memcheck MEM003 `stream_100m` models the same claim),
       and the process host-RSS peak (``ru_maxrss``: the host memory
       wall half of the contract).
    """
    import resource
    import shutil
    import signal as _signal
    import subprocess
    import sys as _sys
    import tempfile

    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.boosting.streaming import StreamTrainer
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io import outofcore as oc
    import jax

    toy = dryrun or jax.default_backend() != "tpu"
    rows = int(os.environ.get("BENCH_STREAM_ROWS",
                              24_576 if toy else 100_000_000))
    block = int(os.environ.get("BENCH_STREAM_BLOCK",
                               8_192 if toy else 1 << 20))
    iters = int(os.environ.get("BENCH_STREAM_ITERS", 2))
    leaves = 15 if toy else 63
    f = 6 if toy else 28
    params = {"objective": "binary", "num_leaves": leaves, "max_bin": 63,
              "learning_rate": 0.1, "verbose": -1}
    cfg = Config.from_params(params)
    out = {"stream_rows": rows, "stream_block_rows": block,
           "stream_iters": iters}

    def _partial(stage):
        if line is not None:
            line.update(out)
            line["partial"] = stage
            _emit(line)

    tmp = tempfile.mkdtemp(prefix="lgbm_stream_")
    try:
        # 1) SIGKILL-resume mechanics (subprocess; three shards, child
        # dies after publishing the first shard's sidecar)
        kid = os.path.join(tmp, "kill")
        argv = [_sys.executable, os.path.abspath(__file__),
                "--stream-child", kid, str(3 * block), str(f), "63",
                str(block)]
        proc = subprocess.run(argv, capture_output=True, timeout=600)
        killed = proc.returncode == -_signal.SIGKILL
        manifest_absent = not os.path.exists(os.path.join(kid, oc.MANIFEST))
        resumed = oc.ingest_synthetic(kid, 3 * block, f, cfg, seed=0,
                                      shard_rows=block)
        clean = oc.ingest_synthetic(os.path.join(tmp, "cleanref"),
                                    3 * block, f, cfg, seed=0,
                                    shard_rows=block)
        out["stream_resume_ok"] = bool(
            killed and manifest_absent
            and resumed.manifest["key"] == clean.manifest["key"]
            and [s["sha256"] for s in resumed.manifest["shards"]]
            == [s["sha256"] for s in clean.manifest["shards"]])
        _partial("stream-resume")

        # 2) byte-identity gate at a fittable size (scatter fold on
        # both sides — the exact-accumulation contract's domain)
        ident_rows = rows if toy else int(
            os.environ.get("BENCH_STREAM_IDENT_ROWS", 262_144))
        prev_backend = os.environ.get("LGBM_TPU_HIST_BACKEND")
        os.environ["LGBM_TPU_HIST_BACKEND"] = "scatter"
        try:
            st = oc.ingest_synthetic(
                os.path.join(tmp, "ident"), ident_rows, f, cfg, seed=1,
                shard_rows=max(block, ident_rows // 3))
            d_str = StreamTrainer(cfg, st, block_rows=block) \
                .train(iters).digest()
            g = GBDT(Config.from_params(params), st.to_binned_dataset(cfg))
            g.train(iters)
            out["stream_identity_rows"] = ident_rows
            out["stream_identity_ok"] = bool(d_str == g.digest())
            del g
        finally:
            if prev_backend is None:
                os.environ.pop("LGBM_TPU_HIST_BACKEND", None)
            else:
                os.environ["LGBM_TPU_HIST_BACKEND"] = prev_backend
        _partial("stream-identity")

        # 3) scale phase: shard-by-shard ingest (SIGKILL-survivable by
        # construction), then streamed training
        import gc
        gc.collect()
        t0 = time.time()
        big = oc.ingest_synthetic(
            os.path.join(tmp, "big"), rows, f, cfg, seed=2,
            shard_rows=max(block, rows // (3 if toy else 32)))
        t_ing = time.time() - t0
        out["stream_shards"] = len(big.manifest["shards"])
        out["stream_ingest_rows_per_sec"] = round(rows / max(t_ing, 1e-9),
                                                  1)
        _partial("stream-ingest")
        tr = StreamTrainer(cfg, big, block_rows=block)
        t0 = time.time()
        bst = tr.train(iters)
        wall = time.time() - t0
        out["stream_train_s"] = round(wall, 3)
        out["stream_row_iters_per_sec"] = round(rows * iters / wall, 1)
        # the perf-ledger row (tools/perf_ledger.py): streamed train
        # throughput at the scale shape, and the RESOLVED histogram
        # backend it rode (kernel folds on TPU, scatter on CPU)
        out["stream_rows_per_sec"] = out["stream_row_iters_per_sec"]
        out["stream_backend"] = tr.backend
        out["stream_model_digest"] = bst.digest(include_scores=False)
        # host memory wall: process peak RSS (lifetime watermark — at
        # 100M rows the streamed state is scores+grad+hess ≈ 12 bytes/
        # row host-side, and the mmap'd store pages stay evictable)
        out["stream_host_rss_peak_bytes"] = \
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        _partial("stream-scale")

        # 4) A/B phase (ISSUE 20): seeded-kernel folds vs forced
        # scatter, and the upload/compute pipeline vs the serial
        # escape hatch.  Both sides ride the platform's DEFAULT
        # backend resolution — on TPU the kernel leg streams through
        # the seeded Pallas folds; on CPU (dryrun) both sides
        # resolve to scatter and the kernel speedup sits at ~1.0 (the
        # schema gate checks presence and sanity, not CPU throughput).
        ab_rows = 2 * block if toy else int(
            os.environ.get("BENCH_STREAM_AB_ROWS", 4 << 20))
        ab_iters = 1 if toy else iters
        ab = oc.ingest_synthetic(os.path.join(tmp, "ab"), ab_rows, f,
                                 cfg, seed=3, shard_rows=ab_rows)

        def _ab_train(backend, pipeline):
            envs = {"LGBM_TPU_STREAM_PIPELINE": pipeline}
            if backend is not None:
                envs["LGBM_TPU_HIST_BACKEND"] = backend
            old = {k: os.environ.get(k) for k in envs}
            os.environ.update(envs)
            try:
                abtr = StreamTrainer(cfg, ab, block_rows=block)
                ta = time.time()
                abtr.train(ab_iters)
                return time.time() - ta
            finally:
                for k, v in old.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v

        t_default = _ab_train(None, "1")
        # toy/CPU: the default already resolves to scatter, so the
        # forced-scatter leg would retrain the identical program —
        # skip it and record the exact ratio 1.0
        t_scatter = t_default if toy else _ab_train("scatter", "1")
        t_serial = _ab_train(None, "0")
        out["stream_kernel_speedup"] = round(
            t_scatter / max(t_default, 1e-9), 3)
        out["stream_pipeline_speedup"] = round(
            t_serial / max(t_default, 1e-9), 3)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def stream_child():
    """``bench.py --stream-child <cache> <rows> <features> <max_bin>
    <shard_rows>``: ingest a synthetic store and SIGKILL ourselves
    right after the FIRST shard's sidecar publishes — the crash the
    resume gate proves survivable."""
    import signal as _signal
    import sys as _sys

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io import outofcore as oc
    cache, rows, f, max_bin, shard_rows = (
        _sys.argv[2], int(_sys.argv[3]), int(_sys.argv[4]),
        int(_sys.argv[5]), int(_sys.argv[6]))
    cfg = Config.from_params({"objective": "binary", "max_bin": max_bin,
                              "verbose": -1})
    orig = oc.atomic_write
    seen = {"sidecars": 0}

    def killer(path, payload, **kw):
        orig(path, payload, **kw)
        if os.path.basename(path).startswith("shard-") \
                and path.endswith(".json"):
            seen["sidecars"] += 1
            if seen["sidecars"] == 1:
                os.kill(os.getpid(), _signal.SIGKILL)

    oc.atomic_write = killer
    oc.ingest_synthetic(cache, rows, f, cfg, seed=0,
                        shard_rows=shard_rows)


# keys the elastic (chaos recovery) leg must emit — `--dryrun` validates
# them plus the SIGKILL shrink+regrow byte-identity verdict
ELASTIC_SCHEMA_KEYS = (
    "elastic_workers", "elastic_shards", "elastic_iters",
    "elastic_kill_iter", "elastic_respawned", "elastic_recovery_ok",
    "elastic_identity_ok", "elastic_wall_s", "elastic_oracle_sha256",
    "elastic_mttr_s", "elastic_mttr_phases")


def elastic_leg(line=None, dryrun: bool = False):
    """Elastic-recovery chaos gate (ISSUE 16): run ``tools/chaos.py``
    for record — a REAL 2-process elastic run (``parallel/elastic.py``
    + ``train_elastic``), SIGKILL one worker the moment its heartbeat
    reports the kill iteration, shrink to world 1, regrow with a
    replacement joiner, and demand every survivor's final model text
    sha AND score digest equal the uninterrupted single-process
    oracle's.

    The whole scenario runs on CPU regardless of the bench backend:
    the identity domain is (data, config, S) on the host collective
    path — there is no device throughput to measure, and the oracle
    must share the workers' platform for the byte comparison to mean
    anything.  When the bench process itself is already on CPU the
    launcher runs in-process (the chaos WORKERS are real subprocesses
    either way — the SIGKILL is always against a live pid); a non-CPU
    bench shells out so the oracle trains on the workers' platform."""
    import shutil
    import subprocess
    import sys as _sys
    import tempfile

    import jax

    workers = int(os.environ.get("BENCH_ELASTIC_WORKERS", 2))
    iters = int(os.environ.get(
        "BENCH_ELASTIC_ITERS", 3 if dryrun else 4))
    rows = int(os.environ.get(
        "BENCH_ELASTIC_ROWS", 192 if dryrun else 256))
    kill_iter = int(os.environ.get(
        "BENCH_ELASTIC_KILL_ITER", 1 if dryrun else 2))
    repo = os.path.dirname(os.path.abspath(__file__))
    rundir = tempfile.mkdtemp(prefix="lgbm_elastic_leg_")
    t0 = time.time()
    try:
        if jax.default_backend() == "cpu":
            from tools.chaos import run_chaos
            verdict = run_chaos(
                workers=workers, shards=workers, iters=iters, rows=rows,
                features=6, leaves=7, snapshot_freq=1,
                kill_iter=kill_iter, respawn=True, rundir=rundir,
                timeout_s=300.0)
        else:
            env = {**os.environ, "JAX_PLATFORMS": "cpu",
                   "PYTHONPATH": repo + os.pathsep
                   + os.environ.get("PYTHONPATH", "")}
            env.pop("XLA_FLAGS", None)
            argv = [_sys.executable, "-m", "tools.chaos",
                    "--workers", str(workers), "--shards", str(workers),
                    "--iters", str(iters), "--rows", str(rows),
                    "--features", "6", "--leaves", "7",
                    "--snapshot-freq", "1",
                    "--kill-iter", str(kill_iter), "--respawn",
                    "--rundir", rundir, "--timeout", "300", "--json"]
            proc = subprocess.run(argv, cwd=repo, env=env,
                                  capture_output=True, text=True,
                                  timeout=600)
            if "{" not in proc.stdout:
                raise RuntimeError(
                    f"chaos harness emitted no verdict "
                    f"(rc={proc.returncode}): {proc.stderr[-500:]}")
            verdict = json.loads(proc.stdout[proc.stdout.index("{"):])
    finally:
        shutil.rmtree(rundir, ignore_errors=True)
    out = {
        "elastic_workers": workers, "elastic_shards": workers,
        "elastic_iters": iters, "elastic_kill_iter": kill_iter,
        "elastic_respawned": verdict.get("respawned"),
        "elastic_recovery_ok": bool(
            verdict.get("killed") and verdict.get("respawned")
            and len(verdict.get("results", [])) == workers),
        "elastic_identity_ok": bool(verdict.get("ok")),
        "elastic_wall_s": round(time.time() - t0, 3),
        "elastic_oracle_sha256": verdict.get("oracle", {}).get(
            "model_sha256", ""),
        # MTTR (ISSUE 17): the slowest survivor-recorded recovery
        # episode; phases (detect/resync/reshard/restore/retrain)
        # sum to mttr_s by construction — the chaos verdict enforces it
        "elastic_mttr_s": verdict.get("mttr_s", 0.0),
        "elastic_mttr_phases": verdict.get("recovery", {}).get(
            "phases", {}),
    }
    if verdict.get("errors"):
        out["elastic_errors"] = verdict["errors"]
    return out


NUM_CONTRACT_SCHEMA_KEYS = (
    "num_contract_rows", "num_contract_iters", "num_contract_windows",
    "num_contract_max_drift_ulps", "num_contract_budget_ulps",
    "num_contract_budget_name", "num_contract_trips",
    "num_contract_ok", "num_reassoc_drift_proof_ok")


def num_contract_leg(dryrun: bool = False):
    """Numerics ulp-contract gate (ISSUE 19), two halves:

    1. a toy training run with the runtime contract armed
       (``LGBM_TPU_NUM_CONTRACT=1``, ``obs/num_contract.py``): every
       window's canonical-f32-vs-f64-oracle drift must stay within the
       registered ``score_root_ulp`` budget — zero trips
       (``num_contract_ok``);
    2. the wall must TRIP when the hazard is real: a child process
       re-runs the S=1 identity matrix (``tools/identity_check.py``)
       with the ``num.reassoc`` fault armed from the environment (the
       canonical root reducer silently reverts to a raw ``jnp.sum`` —
       the PR 14 bug class) and must exit nonzero naming the first
       diverging partition pair (``num_reassoc_drift_proof_ok``).
    """
    import subprocess
    import sys as _sys

    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import num_contract
    import jax

    toy = dryrun or jax.default_backend() != "tpu"
    rows = int(os.environ.get("BENCH_NUM_ROWS", 4_096 if toy else 200_000))
    iters = int(os.environ.get("BENCH_NUM_ITERS", 4))
    rng = np.random.default_rng(19)
    X = rng.normal(size=(rows, 6)).astype(np.float32)
    y = (X[:, 0] + 0.5 * rng.normal(size=rows) > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "num_iterations": iters, "output_freq": 2}
    prev = os.environ.get("LGBM_TPU_NUM_CONTRACT")
    os.environ["LGBM_TPU_NUM_CONTRACT"] = "1"
    try:
        num_contract.reset()
        lgb.train(params, lgb.Dataset(X, label=y, params=params))
        led = num_contract.ledger()
        trips = num_contract.trips()
    finally:
        if prev is None:
            os.environ.pop("LGBM_TPU_NUM_CONTRACT", None)
        else:
            os.environ["LGBM_TPU_NUM_CONTRACT"] = prev
        num_contract.reset()
    out = {
        "num_contract_rows": rows, "num_contract_iters": iters,
        "num_contract_windows": len(led),
        "num_contract_max_drift_ulps": max(
            (d for _, d, _ in led), default=0),
        "num_contract_budget_ulps": num_contract.ULP_BUDGET,
        "num_contract_budget_name": num_contract.BUDGET_NAME,
        "num_contract_trips": len(trips),
        "num_contract_ok": bool(led) and not trips,
    }
    # drift proof: env-armed child (the fault resolves at import of
    # learner/serial.py — arming in THIS process would be a no-op)
    env = {**os.environ, "LGBM_TPU_FAULTS": "num.reassoc:1000000",
           "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [_sys.executable, "-m", "tools.identity_check", "--scenarios",
         "serial,stream1", "--rows", "600", "--rounds", "6"],
        cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
        capture_output=True, text=True, timeout=420)
    named = [ln for ln in proc.stdout.splitlines()
             if "first diverging pair" in ln]
    out["num_reassoc_drift_proof_ok"] = bool(
        proc.returncode != 0 and named)
    if named:
        out["num_reassoc_divergence"] = named[0].strip()
    return out


def _validate_north_star_aux(ns: dict):
    """Validate the extended north_star.json tables: each aux wave key
    is either a measured list of rows (positive ns/row) or a
    pending-capture spec naming its shape; ``multichip`` likewise.
    -> (ok, detail)"""
    detail = {}
    ok = True
    for key in WAVE_AUX_SHAPES:
        v = ns.get(key)
        if isinstance(v, list):
            good = bool(v) and all(
                float(r.get("ns_per_row", r.get("wide_ns_per_row", 0))) > 0
                for r in v)
        elif isinstance(v, dict):
            good = (v.get("status") == "pending-capture"
                    and int(v.get("features", 0)) > 0
                    and int(v.get("max_bin", 0)) > 0)
        else:
            good = False
        detail[key] = "measured" if isinstance(v, list) else (
            "pending-capture" if good else "invalid")
        ok = ok and good
    mc = ns.get("multichip")
    if isinstance(mc, list):
        good = bool(mc) and all(
            int(r.get("devices", 0)) >= 2
            and float(r.get("row_iters_per_sec", 0)) > 0 for r in mc)
    elif isinstance(mc, dict):
        good = mc.get("status") == "pending-capture"
    else:
        good = False
    detail["multichip"] = "measured" if isinstance(mc, list) else (
        "pending-capture" if good else "invalid")
    ok = ok and good
    # split_finder (ISSUE 9): measured rows carry positive cached/full
    # walls + speedup, or an explicit pending-capture spec with shapes
    sf = ns.get("split_finder")
    if isinstance(sf, list):
        good = bool(sf) and all(
            float(r.get("cached_us_per_wave", 0)) > 0
            and float(r.get("full_us_per_wave", 0)) > 0
            and float(r.get("speedup", 0)) > 0 for r in sf)
    elif isinstance(sf, dict):
        good = (sf.get("status") == "pending-capture"
                and bool(sf.get("shapes")))
    else:
        good = False
    detail["split_finder"] = "measured" if isinstance(sf, list) else (
        "pending-capture" if good else "invalid")
    ok = ok and good
    # rank_grad: a measured ns/doc dict or a pending-capture spec
    rg = ns.get("rank_grad")
    good = isinstance(rg, dict) and (
        rg.get("status") == "pending-capture"
        or float(rg.get("ns_per_doc", 0)) > 0)
    detail["rank_grad"] = ("measured" if isinstance(rg, dict)
                           and "ns_per_doc" in rg else
                           ("pending-capture" if good else "invalid"))
    ok = ok and good
    # serve_load (ISSUE 13): measured rows carry offered/achieved QPS +
    # tail columns, or an explicit pending-capture spec with the sweep
    sl = ns.get("serve_load")
    if isinstance(sl, list):
        good = bool(sl) and all(
            float(r.get("offered_qps", 0)) > 0
            and float(r.get("achieved_qps", 0)) > 0
            and float(r.get("p99_ms", 0)) > 0 for r in sl)
    elif isinstance(sl, dict):
        good = (sl.get("status") == "pending-capture"
                and bool(sl.get("qps_sweep")))
    else:
        good = False
    detail["serve_load"] = "measured" if isinstance(sl, list) else (
        "pending-capture" if good else "invalid")
    ok = ok and good
    # device_attribution (ISSUE 10): every future capture is expected
    # to carry attribution columns — a measured fractions dict or an
    # explicit pending-capture spec
    datt = ns.get("device_attribution")
    measured_att = isinstance(datt, dict) and "device_frac" in datt
    good = measured_att or (isinstance(datt, dict)
                            and datt.get("status") == "pending-capture")
    detail["device_attribution"] = ("measured" if measured_att else
                                    ("pending-capture" if good
                                     else "invalid"))
    ok = ok and good
    # stream_ingest (ISSUE 14): a measured dict with positive streamed
    # row-iters/s + passing identity/resume gates, or an explicit
    # pending-capture spec naming the target scale
    si = ns.get("stream_ingest")
    measured_si = isinstance(si, dict) and "row_iters_per_sec" in si
    if measured_si:
        good = (float(si.get("row_iters_per_sec", 0)) > 0
                and bool(si.get("identity_ok"))
                and bool(si.get("resume_ok")))
    else:
        good = (isinstance(si, dict)
                and si.get("status") == "pending-capture"
                and int(si.get("rows", 0)) >= 100_000_000)
    detail["stream_ingest"] = ("measured" if measured_si and good else
                               ("pending-capture" if good else "invalid"))
    ok = ok and good
    # elastic (ISSUE 16): a measured dict with passing recovery +
    # identity verdicts, or an explicit pending-capture spec
    el = ns.get("elastic")
    measured_el = isinstance(el, dict) and "identity_ok" in el
    if measured_el:
        good = bool(el.get("identity_ok")) and bool(el.get("recovery_ok"))
    else:
        good = (isinstance(el, dict)
                and el.get("status") == "pending-capture"
                and int(el.get("workers", 0)) >= 2)
    detail["elastic"] = ("measured" if measured_el and good else
                         ("pending-capture" if good else "invalid"))
    return ok and good, detail


def dryrun_main():
    """``bench.py --dryrun``: emit the per-bucket wave table at toy
    shape (CPU-safe, seconds) and cross-check that the committed
    ``tests/data/north_star.json`` ``wave_kernel`` entries parse — the
    tier-1 gate for the wave-regime tracking mechanics."""
    table = wave_microbench(dryrun=True)
    ns_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "tests", "data", "north_star.json")
    ns_ok, ns_buckets, err = True, [], None
    aux_ok, aux_detail = False, {}
    try:
        with open(ns_path) as fh:
            ns = json.load(fh)
        wk = ns["wave_kernel"]
        ns_buckets = [int(r["active"]) for r in wk]
        ns_ok = bool(wk) and all(float(r["ns_per_row"]) > 0 for r in wk)
        aux_ok, aux_detail = _validate_north_star_aux(ns)
    except Exception as exc:        # noqa: BLE001 - reported on the line
        ns_ok, err = False, f"{type(exc).__name__}: {exc}"
    line = {"metric": "wave_kernel_ns_per_row", "dryrun": True,
            "wave_kernel": table,
            "north_star_wave_buckets": ns_buckets,
            "north_star_parse_ok": ns_ok,
            "north_star_aux_ok": aux_ok,
            "north_star_aux_detail": aux_detail}
    if err:
        line["north_star_parse_error"] = err
    # 255-bin / MSLR-shape wave tables at toy interpret shape: the
    # mechanics gate for the extended north_star.json tables
    try:
        line.update(wave_aux_tables(dryrun=True))
        line["wave_aux_ok"] = all(
            r["wide_ns_per_row"] > 0 for key in WAVE_AUX_SHAPES
            for r in line[key])
    except Exception as exc:        # noqa: BLE001 - reported on the line
        line["wave_aux_ok"] = False
        line["wave_aux_error"] = f"{type(exc).__name__}: {exc}"
    # split-finder microbench gate (ISSUE 9): the cached changed-slot
    # scan must beat the LGBM_TPU_SPLIT_CACHE=0 full rescan >=4x at the
    # 255-leaf/255-bin shape — the acceptance ratio, validated as
    # tier-1 (tests/test_bench_budget)
    try:
        sf = split_finder_microbench(dryrun=True)
        line["split_finder"] = sf
        r255 = next(r for r in sf
                    if r["leaves"] == 255 and r["max_bin"] == 255)
        line["split_finder_speedup_255"] = r255["speedup"]
        line["split_finder_ok"] = bool(
            len(sf) == len(SPLIT_FINDER_SHAPES)
            and all(r["cached_us_per_wave"] > 0
                    and r["full_us_per_wave"] > 0
                    and r["speedup"] > 0 for r in sf)
            and r255["speedup"] >= 4.0)
    except Exception as exc:        # noqa: BLE001 - reported on the line
        line["split_finder_ok"] = False
        line["split_finder_leg"] = f"failed: {type(exc).__name__}: {exc}"
    # rank_grad microbench gate: schema + the per-bucket
    # obj.rank_grad.<M> spans actually fired for every bucket
    try:
        rg = rank_grad_microbench(dryrun=True)
        line.update(rg)
        missing = [k for k in RANK_GRAD_SCHEMA_KEYS if k not in rg]
        line["rank_grad_ok"] = bool(
            not missing and rg["rank_grad_ns_per_doc"] > 0
            and rg["rank_grad_buckets"] > 0
            and len(rg["rank_grad_bucket_spans"])
            == rg["rank_grad_buckets"])
        if missing:
            line["rank_grad_schema_missing"] = missing
    except Exception as exc:        # noqa: BLE001 - reported on the line
        line["rank_grad_ok"] = False
        line["rank_grad_leg"] = f"failed: {type(exc).__name__}: {exc}"
    # multichip mechanics gate: the REAL leg on a 2-device virtual CPU
    # pool (re-exec'd child) — schema + bit-parity validated as
    # tier-1 (tests/test_bench_budget)
    try:
        mleg = multichip_leg(dryrun=True)
        missing = [k for k in MULTICHIP_SCHEMA_KEYS if k not in mleg]
        rows = mleg.get("multichip_table") or []
        sane = (not missing and rows
                and all(r["row_iters_per_sec"] > 0
                        and r["unfused_row_iters_per_sec"] > 0
                        and r["scaling_efficiency"] > 0 for r in rows)
                and mleg["multichip_parity_ok"]
                and mleg["multichip_serial_row_iters_per_sec"] > 0)
        line.update(mleg)
        line["multichip_schema_ok"] = bool(sane)
        if missing:
            line["multichip_schema_missing"] = missing
    except Exception as exc:        # noqa: BLE001 - reported on the line
        line["multichip_schema_ok"] = False
        line["multichip_leg"] = f"failed: {type(exc).__name__}: {exc}"
    # serve (predict) leg schema gate: run the REAL leg at toy shape on
    # CPU and check every field the TPU run will record is present and
    # sane — the tier-1 mechanics gate for the predict-leg artifact
    try:
        sleg = serve_leg(dryrun=True)
        missing = [k for k in SERVE_SCHEMA_KEYS if k not in sleg]
        sane = (not missing and sleg["serve_rows_per_sec"] > 0
                and sleg["serve_host_rows_per_sec"] > 0
                and sleg["serve_parity_ok"] and sleg["serve_recompile_ok"]
                and isinstance(sleg["serve_latency_ms"], dict))
        line.update(sleg)
        line["serve_schema_ok"] = bool(sane)
        if missing:
            line["serve_schema_missing"] = missing
    except Exception as exc:        # noqa: BLE001 - reported on the line
        line["serve_schema_ok"] = False
        line["serve_leg"] = f"failed: {type(exc).__name__}: {exc}"
    # serve_load leg schema gate (ISSUE 13): the REAL open-loop sweep
    # at toy shape/duration — every row carries offered vs achieved
    # QPS and the p50/p99/p99.9 tail columns the TPU artifact will
    # record (tools/load_harness.py mechanics, tier-1 via
    # tests/test_bench_budget)
    try:
        sl = serve_load_leg(dryrun=True)
        missing = [k for k in SERVE_LOAD_SCHEMA_KEYS if k not in sl]
        rows = sl.get("serve_load_table") or []
        sane = (not missing and rows and len(rows) == len(
            sl["serve_load_qps_sweep"]) and all(
            r["offered_qps"] > 0 and r["achieved_qps"] > 0
            and r["requests"] > 0 and r["failures"] == 0
            and r["p999_ms"] >= r["p99_ms"] >= r["p50_ms"] >= 0.0
            for r in rows))
        line.update(sl)
        line["serve_load_ok"] = bool(sane)
        if missing:
            line["serve_load_schema_missing"] = missing
    except Exception as exc:        # noqa: BLE001 - reported on the line
        line["serve_load_ok"] = False
        line["serve_load_leg"] = f"failed: {type(exc).__name__}: {exc}"
    # stream_ingest gate (ISSUE 14): the REAL out-of-core leg at toy
    # shape — multi-block streamed training byte-identical to resident,
    # a REAL SIGKILL mid-ingest resuming to the clean manifest, and the
    # schema the TPU artifact will record (tier-1 via
    # tests/test_bench_budget)
    try:
        stleg = stream_ingest_leg(dryrun=True)
        missing = [k for k in STREAM_SCHEMA_KEYS if k not in stleg]
        line.update(stleg)
        line["stream_schema_ok"] = bool(
            not missing
            and stleg["stream_identity_ok"]
            and stleg["stream_resume_ok"]
            and stleg["stream_ingest_rows_per_sec"] > 0
            and stleg["stream_row_iters_per_sec"] > 0
            and stleg["stream_shards"] > 1
            and stleg["stream_host_rss_peak_bytes"] > 0)
        if missing:
            line["stream_schema_missing"] = missing
    except Exception as exc:        # noqa: BLE001 - reported on the line
        line["stream_schema_ok"] = False
        line["stream_leg"] = f"failed: {type(exc).__name__}: {exc}"
    # elastic chaos gate (ISSUE 16): the REAL SIGKILL shrink+regrow
    # scenario in a CPU subprocess — the survivor and the replacement
    # joiner must both land on the 1-process oracle's bytes (tier-1
    # via tests/test_bench_budget)
    try:
        el = elastic_leg(dryrun=True)
        missing = [k for k in ELASTIC_SCHEMA_KEYS if k not in el]
        line.update(el)
        # MTTR gate (ISSUE 17): a killed run must carry a positive
        # recovery time whose phase breakdown sums to it exactly
        phases = el.get("elastic_mttr_phases") or {}
        mttr_ok = bool(
            el.get("elastic_mttr_s", 0) > 0 and phases
            and abs(sum(phases.values())
                    - el["elastic_mttr_s"]) < 1e-9)
        line["elastic_ok"] = bool(
            not missing
            and el["elastic_identity_ok"]
            and el["elastic_recovery_ok"]
            and el["elastic_wall_s"] > 0
            and mttr_ok)
        if not mttr_ok:
            line["elastic_mttr_ok"] = False
        if missing:
            line["elastic_schema_missing"] = missing
    except Exception as exc:        # noqa: BLE001 - reported on the line
        line["elastic_ok"] = False
        line["elastic_leg"] = f"failed: {type(exc).__name__}: {exc}"
    # numerics ulp-contract gate (ISSUE 19): a toy train with
    # LGBM_TPU_NUM_CONTRACT=1 must stay within the registered
    # score_root_ulp budget, and an env-armed num.reassoc child must
    # BREAK the digest law with the diverging pair named (tier-1 via
    # tests/test_bench_budget)
    try:
        ncleg = num_contract_leg(dryrun=True)
        missing = [k for k in NUM_CONTRACT_SCHEMA_KEYS if k not in ncleg]
        line.update(ncleg)
        line["num_contract_schema_ok"] = bool(
            not missing
            and ncleg["num_contract_ok"]
            and ncleg["num_reassoc_drift_proof_ok"]
            and ncleg["num_contract_windows"] > 0)
        if missing:
            line["num_contract_schema_missing"] = missing
    except Exception as exc:        # noqa: BLE001 - reported on the line
        line["num_contract_schema_ok"] = False
        line["num_contract_leg"] = f"failed: {type(exc).__name__}: {exc}"
    # device-time attribution gate (ISSUE 10): the REAL leg at toy
    # shape on CPU — windowed capture, parse, schema — with the
    # acceptance floor: >=90% of captured device time attributes to
    # named spans, host_gap and per-program cost populated
    try:
        att = attribution_leg(dryrun=True)
        missing = [k for k in ATTRIBUTION_SCHEMA_KEYS if k not in att]
        line.update(att)
        line["attribution_schema_ok"] = bool(
            not missing
            and att["attribution_device_time_s"] > 0
            and att["attribution_coverage"] is not None
            and att["attribution_coverage"] >= 0.90
            and att["attribution_spans"]
            and att["attribution_host_gap_frac"] is not None
            and att["attribution_dispatch_gap_mean_s"] is not None
            and any(r.get("flops") for r in
                    att["attribution_cost_programs"]))
        if missing:
            line["attribution_schema_missing"] = missing
    except Exception as exc:        # noqa: BLE001 - reported on the line
        line["attribution_schema_ok"] = False
        line["attribution_leg"] = f"failed: {type(exc).__name__}: {exc}"
    # perf-ledger gate (ISSUE 10): every committed BENCH_r*.json must
    # load into the cross-round trend table (unparsed driver-timeout
    # rounds stay visible, never crash the ledger), and the newest
    # parsed round must not regress >10% vs the best prior round.  An
    # EMPTY committed history (the state since PR 21) passes: there is
    # nothing to regress against
    try:
        from tools.perf_ledger import check_regressions, load_history
        hist = load_history(os.path.dirname(os.path.abspath(__file__)))
        line["perf_ledger_rounds"] = [h["round"] for h in hist]
        line["perf_ledger_parsed_rounds"] = [
            h["round"] for h in hist if h["parsed"]]
        regs = check_regressions(hist)
        if regs:
            line["perf_ledger_regressions"] = regs
        line["perf_ledger_ok"] = not regs
    except Exception as exc:        # noqa: BLE001 - reported on the line
        line["perf_ledger_ok"] = False
        line["perf_ledger_error"] = f"{type(exc).__name__}: {exc}"
    # model-digest reproducibility gate (ISSUE 12): every model-
    # training leg stamps `model_digest` (obs/determinism.py canonical
    # sha256); two toy trainings from identical seeds must agree — the
    # bench's own train-twice contract, so a capture on the chip
    # doubles as a cross-host reproducibility artifact
    try:
        _, _, ph_a = synthetic_leg(4_000, 4, 15, 15, f=8, seed=0)
        _, _, ph_b = synthetic_leg(4_000, 4, 15, 15, f=8, seed=0)
        line["model_digest"] = ph_a["model_digest"]
        line["model_digest_repeat_ok"] = bool(
            ph_a["model_digest"]
            and ph_a["model_digest"] == ph_b["model_digest"])
    except Exception as exc:        # noqa: BLE001 - reported on the line
        line["model_digest_repeat_ok"] = False
        line["model_digest_error"] = f"{type(exc).__name__}: {exc}"
    # per-leg peak_hbm_bytes (ISSUE 8): every leg the dryrun emitted
    # carries the field — a positive int where the backend exposes
    # allocator stats, null + peak_hbm_reason where it doesn't (CPU) —
    # validated as peak_hbm_schema_ok (tier-1, tests/test_bench_budget)
    for prefix in (None, "waves", "multichip", "serve", "stream"):
        _peak_field(line, prefix)
    peak_keys = ("peak_hbm_bytes", "waves_peak_hbm_bytes",
                 "multichip_peak_hbm_bytes", "serve_peak_hbm_bytes",
                 "stream_peak_hbm_bytes")
    line["peak_hbm_schema_ok"] = all(
        k in line and (
            (isinstance(line[k], int) and line[k] > 0)
            or (line[k] is None and bool(line.get("peak_hbm_reason"))))
        for k in peak_keys)
    _emit(line)


REFERENCE_MSLR_DOC_ITERS_PER_SEC = 2_270_296 * 500 / 215.320316


def ranking_leg(max_bin=255, iters_env="BENCH_RANK_ITERS",
                iters_default=16):
    """MSLR-shaped lambdarank leg (VERDICT r5 #2): ~19k queries /
    ~2.27M docs / 136 features, queries up to ~1.2k docs — the
    reference's MS LTR benchmark shape, trained with its exact
    Experiments.rst config (num_leaves=255, lr=0.1, min_data_in_leaf=0,
    min_sum_hessian_in_leaf=100; 215.320316 s for 500 iterations on the
    28-core box -> 5.27M doc-iters/s).  Reports steady-state doc-iters/s
    and an NDCG@10 gate: the timed model must actually learn to rank.

    ``max_bin``: 255 is the config-exact leg (the baseline's own bin
    count); the one-hot histogram kernel's MXU cost scales with
    features x bins, so 136 x 256 is its worst published shape.  The
    63-bin variant is the reference GPU docs' OWN recommended setting
    for exactly this trade (docs/GPU-Performance.rst:43-44, and their
    MS-LTR GPU runs at 63 bins hold NDCG parity: `:158-159`)."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.basic import Booster
    from lightgbm_tpu.metric.metrics import NDCGMetric
    from lightgbm_tpu.config import Config

    iters = int(os.environ.get(iters_env, iters_default))
    n_q = int(os.environ.get("BENCH_RANK_QUERIES", 19_000))
    rng = np.random.RandomState(7)
    sizes = np.clip(np.round(rng.lognormal(mean=4.55, sigma=0.7,
                                           size=n_q)),
                    1, 1251).astype(np.int64)
    n = int(sizes.sum())
    X = rng.normal(size=(n, 136)).astype(np.float32)
    raw = X[:, 0] + 0.6 * X[:, 1] - 0.4 * X[:, 2] \
        + rng.normal(scale=0.8, size=n)
    # MSLR-like skewed relevance: mostly 0s, few 4s
    rel = np.digitize(raw, np.quantile(raw, [0.55, 0.78, 0.92, 0.98])
                      ).astype(np.float32)
    params = {"objective": "lambdarank", "num_leaves": 255,
              "learning_rate": 0.1, "min_data_in_leaf": 0,
              "min_sum_hessian_in_leaf": 100, "max_bin": max_bin,
              "metric": "ndcg", "ndcg_eval_at": [10], "verbose": -1}
    ds = lgb.Dataset(X, label=rel, group=sizes, params=params)
    ds.construct()
    del X, raw
    import gc
    gc.collect()
    # short fused blocks: at this shape (255 bins x 136 features x
    # 2.3M rows x 255 leaves) a 32-iteration dispatch exceeds the
    # device watchdog and faults the TPU worker
    prev_cap = os.environ.get("LGBM_TPU_BLOCK_CAP")
    os.environ["LGBM_TPU_BLOCK_CAP"] = os.environ.get(
        "BENCH_RANK_BLOCK_CAP", "8")
    try:
        bst = Booster(params=params, train_set=ds)
    finally:
        if prev_cap is None:
            os.environ.pop("LGBM_TPU_BLOCK_CAP", None)
        else:
            os.environ["LGBM_TPU_BLOCK_CAP"] = prev_cap
    g = bst._gbdt
    c0 = _block_compile_s()
    bst.update()                    # compiles block + objective buckets
    g.train_block(iters)
    _sync(g.scores)
    t0 = time.time()
    g.train_block(iters)
    _sync(g.scores)
    wall = time.time() - t0
    compile_s = _block_compile_s() - c0
    m = NDCGMetric(Config.from_params(params))
    qb = np.concatenate([[0], np.cumsum(sizes)])
    (_, ndcg10, _), = m.eval(rel, np.asarray(g.scores[:, 0]), None, qb)
    rate = n * iters / wall
    p = "rank" if max_bin == 255 else f"rank{max_bin}"
    digest = g.digest(include_scores=False)
    del bst, ds, g
    gc.collect()
    return {f"{p}_model_digest": digest,
            f"{p}_docs": n, f"{p}_queries": n_q, f"{p}_iters": iters,
            f"{p}_max_bin": max_bin,
            f"{p}_compile_s": round(compile_s, 3),
            f"{p}_steady_s": round(wall, 3),
            f"{p}_doc_iters_per_sec": round(rate, 1),
            f"{p}_ndcg10": round(float(ndcg10), 5),
            f"{p}_ndcg_ok": bool(ndcg10 >= 0.60),
            f"{p}_vs_baseline": round(
                rate / REFERENCE_MSLR_DOC_ITERS_PER_SEC, 4),
            f"{p}_baseline": "MS LTR 2.27M docs x 500 iters in 215.32s "
                             "(docs/Experiments.rst)"}


def _leg(line, name, fn, retries=1, gate=False):
    """Run an auxiliary bench leg with one retry: a transient dispatch/
    compile error must not erase a leg, and a doubly-failed AUXILIARY leg is recorded
    on the line — visible to any reader — without zeroing the HIGGS
    headline (gate failures inside a leg that RAN still zero it).

    ``gate=True`` marks a GATE-BEARING leg (valid/bin255/rank: a leg
    whose quality gate would zero the headline had it run).  When such
    a leg fails BOTH attempts with the SAME error — a deterministic
    crash, not a transient — it lands in ``legs_hard_failed`` and main
    zeroes ``vs_baseline``: a code regression that crashes the gate
    path must not keep the headline green (ADVICE r5 #2).

    Past the ``BENCH_DEADLINE_S`` budget the leg is not attempted at
    all: it records ``"skipped: budget"`` (an explicit marker, never a
    silent absence) and the headline keeps whatever legs DID run.

    ``BENCH_FORCE_FAIL=<name>`` makes that leg raise deterministically
    on every attempt — the test hook proving a gate-bearing leg's hard
    failure zeroes ``vs_baseline`` (ADVICE r5 #2)."""
    import gc
    if _budget_exceeded():
        line[f"{name}_leg"] = "skipped: budget"
        line.setdefault("legs_skipped", []).append(name)
        return None
    errs = []
    for attempt in range(retries + 1):
        try:
            if os.environ.get("BENCH_FORCE_FAIL") == name:
                raise RuntimeError("forced failure (BENCH_FORCE_FAIL)")
            out = fn()
            _peak_field(line, name)
            return out
        except Exception as exc:
            # keep only the STRING: the exception's traceback pins the
            # failed attempt's frames (and their multi-GB leg buffers)
            # alive, which would turn an OOM-class transient into a
            # deterministic OOM on retry
            errs.append(f"{type(exc).__name__}: {exc}")
            del exc
            gc.collect()
    line[f"{name}_leg"] = f"failed: {errs[-1]}"
    _peak_field(line, name)         # the leg RAN: its peak still counts
    line.setdefault("legs_failed", []).append(name)
    if gate and len(set(errs)) == 1:
        line.setdefault("legs_hard_failed", []).append(name)
    return None


def main():
    n = int(os.environ.get("BENCH_ROWS", 1_000_000))
    # 128 (not 64): the timed window carries ONE end-of-window device
    # sync, a fixed cost the shorter window amortizes worse (its size
    # is unverified on a local chip)
    iters = int(os.environ.get("BENCH_ITERS", 128))
    leaves = int(os.environ.get("BENCH_LEAVES", 255))
    max_bin = int(os.environ.get("BENCH_BIN", 63))

    # real-data leg FIRST: its cold wall-clock is the fresh-runtime
    # first-run experience, which running it after the big synthetic
    # legs distorts (~2 min of extra compile latency in a hot runtime)
    real = {}
    if _budget_exceeded():
        real = {"real_data": "skipped: budget"}
    else:
        try:
            real = real_data_eval()
            if "unavailable" not in str(real.get("real_data", "")):
                _peak_field(real, "real_data")
        except Exception as exc:  # real-data leg must never kill the bench
            real = {"real_data": f"failed: {exc}"}

    rps, auc, ph = synthetic_leg(n, iters, leaves, max_bin)
    auc_ok = bool(auc >= AUC_GATE)
    vs = rps / REFERENCE_ROW_ITERS_PER_SEC
    line = {
        "metric": "higgs_shape_train_row_iters_per_sec",
        "value": round(rps, 1),
        "unit": "row_iters/s",
        "train_auc": round(auc, 5),
        "auc_ok": auc_ok,
        "auc_gate": AUC_GATE,
        "throughput_data": "synthetic HIGGS-shaped",
        "compile_s": ph["compile_s"],
        "steady_s": ph["steady_s"],
        "model_digest": ph["model_digest"],
    }
    # headline checkpoint: from here on a driver timeout can no longer
    # erase the 1M leg (the driver takes the LAST parseable line)
    line["vs_baseline"] = round(vs if auc_ok else 0.0, 4)
    _peak_field(line)               # headline leg's device HBM peak
    line["partial"] = "headline-1M"
    _emit(line)

    # wave-regime microbench right after the headline (cheap — a few
    # kernel dispatches) and emitted incrementally, so every BENCH_r*
    # artifact records ns/row per active-slot bucket even under a later
    # driver timeout: the deep-wave collapse north_star.json quantified
    # is tracked from now on
    if os.environ.get("BENCH_WAVES", "1") != "0":
        waves = _leg(line, "waves", wave_microbench)
        if waves is not None:
            line["wave_kernel"] = waves
            line["partial"] = "headline-1M+waves"
            _emit(line)
        # 255-bin / MSLR-shape tables (north_star.json wave_kernel_255 /
        # wave_kernel_mslr): the losing-regime attribution data ROADMAP
        # item 2 asks for, captured alongside the default-shape table
        if os.environ.get("BENCH_WAVES_AUX", "1") != "0":
            aux = _leg(line, "waves_aux", wave_aux_tables)
            if aux is not None:
                line.update(aux)
                line["partial"] = "headline-1M+waves-aux"
                _emit(line)

    # split-finder microbench (ISSUE 9): cached changed-slot scan vs
    # the LGBM_TPU_SPLIT_CACHE=0 full rescan at the reference's own
    # leaf/bin configs — cheap (a few dispatches), emitted
    # incrementally so a later driver deadline can't erase it
    if os.environ.get("BENCH_SPLIT_FINDER", "1") != "0":
        sf = _leg(line, "split_finder", split_finder_microbench)
        if sf is not None:
            line["split_finder"] = sf
            line["partial"] = "headline-1M+split-finder"
            _emit(line)

    # lambdarank gradient microbench (ISSUE 9 satellite): ns/doc at the
    # MSLR bucket mix + per-bucket obj.rank_grad.<M> span attribution —
    # the other half of the 0.27x ranking-leg accounting
    if os.environ.get("BENCH_RANK_GRAD", "1") != "0":
        rg = _leg(line, "rank_grad", rank_grad_microbench)
        if rg is not None:
            line.update(rg)
            line["partial"] = "headline-1M+rank-grad"
            _emit(line)

    # device-time attribution leg (ISSUE 10): a small profiled train —
    # device/host-gap/collective fractions, top programs by device
    # time, cost-model FLOPs/bytes — on every artifact, so the perf
    # ledger can trend WHERE the time goes round over round, not just
    # how much.  Cheap, separate from the timed legs, emitted
    # incrementally so a driver deadline can't erase it.
    if os.environ.get("BENCH_ATTRIBUTION", "1") != "0":
        att = _leg(line, "attribution", attribution_leg)
        if att is not None:
            line.update(att)
            line["partial"] = "headline-1M+attribution"
            _emit(line)

    if os.environ.get("BENCH_FULL", "1") != "0":
        n_full = int(os.environ.get("BENCH_FULL_ROWS", 10_500_000))
        # 500 = the reference's actual HIGGS iteration count
        # (docs/Experiments.rst:104-116); with a 32-iteration block cap
        # this is 15 full blocks + a 20-iteration residue, so residue
        # compile + masked-iteration effects are inside the timed pass
        # (VERDICT r4 #3)
        it_full = int(os.environ.get("BENCH_FULL_ITERS", 500))
        full = _leg(line, "full", lambda: synthetic_leg(
            n_full, it_full, leaves, max_bin, seed=1))
        if full is not None:
            rps_f, auc_f, ph_f = full
            auc_f_ok = bool(auc_f >= AUC_GATE)
            line.update({
                "full_rows": n_full, "full_iters": it_full,
                "full_row_iters_per_sec": round(rps_f, 1),
                "full_train_auc": round(auc_f, 5),
                "full_auc_ok": auc_f_ok,
                "full_vs_baseline": round(
                    rps_f / REFERENCE_ROW_ITERS_PER_SEC, 4),
                "full_compile_s": ph_f["compile_s"],
                "full_steady_s": ph_f["steady_s"],
                "full_model_digest": ph_f["model_digest"],
            })
            auc_ok = auc_ok and auc_f_ok
            vs = min(vs, rps_f / REFERENCE_ROW_ITERS_PER_SEC)
        elif line.get("full_leg") != "skipped: budget":
            # headline-constitutive when it RAN and crashed: must not
            # pass.  An explicit budget skip keeps the 1M headline (the
            # marker stays loud in the artifact)
            auc_ok = False
        # headline checkpoint #2: both headline legs are now settled
        line["vs_baseline"] = round(vs if auc_ok else 0.0, 4)
        line["partial"] = "headline-full"
        _emit(line)

    def _checkpoint(stage):
        """Flush the line after EVERY aux leg (success, failure, or
        skip): satellite of VERDICT r5 Weak #1/#3 — a driver deadline
        mid-run must never erase a leg that already ran, including its
        failure markers."""
        line["partial"] = stage
        _emit(line)

    # Aux-leg ORDER (VERDICT r5 Weak #3): the never-captured /
    # stale-captured numbers run FIRST so a driver deadline cannot
    # starve them again — multichip (the >=2-chip north star; an
    # instant "skipped: devices" marker on 1-chip images), then bin255
    # (never produced a number), rank63, serve (PR 6 numbers never
    # landed in an artifact), then the heavyweight 255-bin rank leg,
    # and valid (repeatedly captured) last.

    # multichip leg: data-parallel scaling across a real >=2-chip mesh,
    # fused and unfused (ROADMAP item 1).  Gate: the two models must be
    # byte-identical when the leg RAN (a wrong-answer speedup must not
    # score).
    if os.environ.get("BENCH_MC", "1") != "0":
        mleg = _leg(line, "multichip", lambda: multichip_leg(line),
                    gate=True)
        if mleg is not None:
            line.update(mleg)
            if not mleg.get("multichip_parity_ok", True):
                auc_ok = False
        _checkpoint("headline-full+multichip")

    # stream_ingest (ISSUE 14): out-of-core streamed training — ingest
    # >=100M synthetic rows into the mmap shard store and train beyond
    # resident memory, with the byte-identity and SIGKILL-resume gates.
    # Gate-bearing: a failed identity/resume gate zeroes the headline
    # (a streamed model that silently diverges must not score).
    if os.environ.get("BENCH_STREAM", "1") != "0":
        stleg = _leg(line, "stream", lambda: stream_ingest_leg(line),
                     gate=True)
        if stleg is not None:
            line.update(stleg)
            if not (stleg.get("stream_identity_ok")
                    and stleg.get("stream_resume_ok")):
                auc_ok = False
        _checkpoint("aux-stream")

    # elastic chaos (ISSUE 16): the rank-failure recovery gate for
    # record — SIGKILL a worker mid-window, shrink to world 1, regrow
    # with a replacement, and demand the uninterrupted oracle's bytes
    # back.  Gate-bearing: a recovery that diverges must not keep the
    # headline green.
    if os.environ.get("BENCH_ELASTIC", "1") != "0":
        eleg = _leg(line, "elastic", lambda: elastic_leg(line),
                    gate=True)
        if eleg is not None:
            line.update(eleg)
            if not (eleg.get("elastic_identity_ok")
                    and eleg.get("elastic_recovery_ok")):
                auc_ok = False
        _checkpoint("aux-elastic")

    # numerics ulp contract (ISSUE 19): the runtime half of numcheck —
    # a contract-armed toy train must hold the score_root_ulp budget
    # and the env-armed num.reassoc child must break the digest law
    # loudly.  Gate-bearing: silent numerics drift must not keep the
    # headline green.
    if os.environ.get("BENCH_NUM_CONTRACT", "1") != "0":
        ncleg = _leg(line, "num_contract", num_contract_leg, gate=True)
        if ncleg is not None:
            line.update(ncleg)
            if not (ncleg.get("num_contract_ok")
                    and ncleg.get("num_reassoc_drift_proof_ok")):
                auc_ok = False
        _checkpoint("aux-num-contract")

    # 255-bin leg (VERDICT r4 #7): the EXACT docs/Experiments.rst:104-116
    # bin/leaf config (max_bin=255, 255 leaves) at reduced iterations, so
    # the CPU comparison has an apples-to-apples anchor (the 238.5 s CPU
    # run was recorded at 255 bins; the 63-bin default above follows the
    # reference GPU docs' own recommendation).  255 is also the boundary
    # of the Pallas one-hot kernel's bin range — worth pinning.
    if os.environ.get("BENCH_255", "1") != "0":
        n255 = int(os.environ.get("BENCH_255_ROWS", 1_000_000))
        it255 = int(os.environ.get("BENCH_255_ITERS", 32))
        leg255 = _leg(line, "bin255", lambda: synthetic_leg(
            n255, it255, leaves, 255, seed=2), gate=True)
        if leg255 is not None:
            rps_255, auc_255, ph_255 = leg255
            auc_255_ok = bool(auc_255 >= AUC_GATE)
            line.update({
                "bin255_rows": n255, "bin255_iters": it255,
                "bin255_row_iters_per_sec": round(rps_255, 1),
                "bin255_train_auc": round(auc_255, 5),
                "bin255_auc_ok": auc_255_ok,
                "bin255_vs_baseline": round(
                    rps_255 / REFERENCE_ROW_ITERS_PER_SEC, 4),
                "bin255_compile_s": ph_255["compile_s"],
                "bin255_steady_s": ph_255["steady_s"],
            })
            auc_ok = auc_ok and auc_255_ok
        _checkpoint("aux-bin255")

    # ranking legs: their own baseline (MS LTR) and their own NDCG gate
    # — reported alongside, not folded into the HIGGS-headline min (the
    # headline metric is specifically the HIGGS-shape row-iters rate).
    # Gate policy: a leg that RUNS and fails its quality gate zeroes the
    # headline; a leg that CRASHES twice is recorded in legs_failed /
    # legs_ok=false instead — a transient runtime fault must not erase
    # the HIGGS number, and the failure stays loud in the artifact.
    # rank63 (the GPU-docs-recommended 63-bin variant; their own MS-LTR
    # runs hold NDCG parity at 63 bins) runs BEFORE the heavier
    # config-exact 255-bin leg.
    if os.environ.get("BENCH_RANK", "1") != "0":
        # drop the binary legs' compiled programs + buffers before the
        # wide-feature rank datasets allocate.  (Note: rank doc-rates
        # legitimately fall with the iteration window — later
        # iterations build deeper trees; the recorded *_iters says
        # which window a number measures.)
        import gc
        import jax
        gc.collect()
        jax.clear_caches()
        if os.environ.get("BENCH_RANK63", "1") != "0":
            rank63 = _leg(line, "rank63", lambda: ranking_leg(
                max_bin=63, iters_env="BENCH_RANK63_ITERS",
                iters_default=32), gate=True)
            if rank63 is not None:
                line.update(rank63)
                if not rank63["rank63_ndcg_ok"]:
                    auc_ok = False
            _checkpoint("aux-rank63")

    # serve (predict) leg: the inference workload (ROADMAP item 3) —
    # big-batch rows/s, the int8-binned fast path, per-bucket p50/p99
    # through the async harness, and the zero-recompile check.  Its
    # gates (1-ulp parity vs the host oracle, zero post-warmup
    # recompiles) zero the headline when the leg RAN and failed them.
    if os.environ.get("BENCH_SERVE", "1") != "0":
        sleg = _leg(line, "serve", serve_leg, gate=True)
        if sleg is not None:
            line.update(sleg)
            if not (sleg["serve_parity_ok"] and sleg["serve_recompile_ok"]):
                auc_ok = False
        _checkpoint("aux-serve")

    # serve_load (ISSUE 13): open-loop Poisson QPS sweep — p50/p99/
    # p99.9 vs OFFERED load through the live server, each step emitted
    # incrementally as it lands (tools/load_harness.py)
    if os.environ.get("BENCH_SERVE_LOAD", "1") != "0":
        slleg = _leg(line, "serve_load", lambda: serve_load_leg(line))
        if slleg is not None:
            line.update(slleg)
        _checkpoint("aux-serve-load")

    if os.environ.get("BENCH_RANK", "1") != "0":
        import gc
        import jax
        gc.collect()
        jax.clear_caches()
        rank = _leg(line, "rank", ranking_leg, gate=True)  # config-exact 255-bin
        if rank is not None:
            line.update(rank)
            if not rank["rank_ndcg_ok"]:
                auc_ok = False
        _checkpoint("aux-rank")

    # with-valid leg (VERDICT r4 #1): the standard train+valid+early-stop
    # workflow must stay on the fused block path, within ~20% of the
    # no-valid leg's per-iteration cost
    if os.environ.get("BENCH_VALID", "1") != "0":
        vleg = _leg(line, "valid", lambda: valid_leg(leaves, max_bin),
                    gate=True)
        if vleg is not None:
            # held-out AUC gate (VERDICT r5 Weak #7): the with-valid
            # leg must actually generalize, not just stay fast
            vleg["valid_auc_ok"] = bool(
                vleg["valid_eval_auc"] >= VALID_AUC_GATE)
            if not vleg["valid_auc_ok"]:
                auc_ok = False
            vleg["valid_block_ok"] = bool(vleg["valid_on_block_path"])
            # the slowdown gate only means something when the no-valid
            # leg ran the SAME train-row count (shape differences would
            # otherwise masquerade as with-valid overhead)
            if n == vleg["valid_train_rows"]:
                ratio = rps / max(vleg["valid_row_iters_per_sec"], 1e-9)
                vleg["valid_slowdown_vs_novalid"] = round(ratio, 4)
                vleg["valid_block_ok"] = bool(
                    vleg["valid_block_ok"] and ratio <= 1.25)
            line.update(vleg)
            if not vleg["valid_block_ok"]:
                auc_ok = False

    if not auc_ok:
        vs = 0.0    # a bench run that failed to learn scores zero
    if line.get("legs_hard_failed"):
        # a gate-bearing leg crashed deterministically (same error on
        # both attempts): its gate never ran, so the headline must not
        # stay green (ADVICE r5 #2)
        vs = 0.0
    line["vs_baseline"] = round(vs, 4)
    line["legs_ok"] = "legs_failed" not in line
    line["auc_ok"] = auc_ok
    line.pop("partial", None)       # this is the complete line
    if BENCH_DEADLINE_S > 0:
        line["deadline_s"] = BENCH_DEADLINE_S
        line["elapsed_s"] = round(time.monotonic() - _T0, 1)
    line.update(real)
    _emit(line)


if __name__ == "__main__":
    import sys
    if "--multichip-child" in sys.argv:
        multichip_child()
    elif "--stream-child" in sys.argv:
        stream_child()
    elif "--dryrun" in sys.argv:
        dryrun_main()
    else:
        main()
