"""Fused multi-chip scan blocks (ISSUE 11 tentpole).

The acceptance contract of running single-process device meshes
through the SAME fused ``lax.scan`` block program the serial path
uses (one dispatch per window instead of one per iteration):

* models byte-identical between the fused path and the
  ``LGBM_TPU_MESH_BLOCK=0`` per-iteration escape hatch (length-1
  blocks of the same compiled scan body — same arithmetic by
  construction, only the dispatch count changes), across all three
  parallel learners, bagged + feature-fraction sampling, and
  train-with-valid;
* flight-recorder collective-schedule digests identical across the
  two dispatch modes (one ``hist_psum`` fingerprint per wave);
* telemetry proves the dispatch-count claim: the fused mesh path runs
  ONE ``gbdt.block`` span per window and zero off-block
  ``gbdt.iteration`` spans, while the escape hatch dispatches per
  iteration; ``gbdt.dispatch_gap_mean_s`` is recorded on both;
* ``LGBM_TPU_NO_BLOCK=1`` still reaches the legacy eager per-iteration
  loop (``gbdt.iteration`` spans);
* the data-parallel wave exchanges its histograms by ONE reduction of
  the whole operand (flight recorder);
* score-buffer donation through the fused block program changes
  nothing observable: identical models, zero post-warmup recompiles
  under the trace contract — and it is hard-gated OFF on the CPU
  backend, where zero-copy ``np.asarray`` host reads alias the
  memory donation would let XLA reuse.
"""
import os

import numpy as np
import jax
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs import flight_recorder as fr

pytestmark = pytest.mark.skipif(len(jax.devices()) < 2,
                                reason="needs >=2 virtual devices")


def _data(seed=1, n=1500, f=6, nv=400):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] + 0.5 * rng.normal(size=n) > 0).astype(np.float64)
    Xv = rng.normal(size=(nv, f)).astype(np.float32)
    yv = (Xv[:, 0] + 0.5 * rng.normal(size=nv) > 0).astype(np.float64)
    return X, y, Xv, yv


def _train(params, X, y, Xv=None, yv=None, rounds=8, mesh_block="1",
           keep=False):
    prev = os.environ.get("LGBM_TPU_MESH_BLOCK")
    os.environ["LGBM_TPU_MESH_BLOCK"] = mesh_block
    try:
        tr = lgb.Dataset(X, label=y)
        vs = ([lgb.Dataset(Xv, label=yv, reference=tr)]
              if Xv is not None else None)
        return lgb.train(dict(params), tr, num_boost_round=rounds,
                         verbose_eval=False, valid_sets=vs,
                         keep_training_booster=keep)
    finally:
        if prev is None:
            os.environ.pop("LGBM_TPU_MESH_BLOCK", None)
        else:
            os.environ["LGBM_TPU_MESH_BLOCK"] = prev


BASE = {"objective": "binary", "num_leaves": 7, "verbose": -1,
        "min_data_in_leaf": 5, "mesh_shape": [2]}


# ---------------------------------------------------------------------------
# byte-identity: fused vs per-iteration mesh dispatches
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("learner,extra", [
    ("data", {}),
    ("voting", {}),
    ("feature", {}),
    ("data", {"bagging_freq": 2, "bagging_fraction": 0.8,
              "feature_fraction": 0.7}),
])
def test_fused_mesh_model_byte_identical(learner, extra):
    X, y, _, _ = _data()
    params = {**BASE, "tree_learner": learner, **extra}
    out = {}
    for mb in ("0", "1"):
        bst = _train(params, X, y, mesh_block=mb, keep=True)
        out[mb] = (bst._gbdt.save_model_to_string(),
                   np.asarray(bst._gbdt.scores).copy())
    assert out["0"][0] == out["1"][0], (
        f"{learner}/{extra}: fused mesh model != per-iteration mesh model")
    np.testing.assert_array_equal(out["0"][1], out["1"][1])


def test_fused_mesh_with_valid_byte_identical_and_es_state():
    """Valid scores ride the fused block as scan carries — models,
    train scores AND valid scores byte-identical across dispatch
    modes (the early-stopping inputs are the valid scores, so this is
    the ES-state equivalence too)."""
    X, y, Xv, yv = _data()
    params = {**BASE, "tree_learner": "data", "output_freq": 4}
    out = {}
    for mb in ("0", "1"):
        bst = _train(params, X, y, Xv, yv, mesh_block=mb, keep=True)
        g = bst._gbdt
        out[mb] = (g.save_model_to_string(),
                   np.asarray(g._valid_scores[0]).copy())
    assert out["0"][0] == out["1"][0]
    np.testing.assert_array_equal(out["0"][1], out["1"][1])


def test_fused_mesh_flight_recorder_digest_equal():
    """The recorded collective schedule (site/op/axis/shape/order) must
    be identical across the two dispatch modes: one hist_psum
    fingerprint per wave, recorded at trace time — the fused block
    traces the SAME distributed build closure the per-iteration jit
    wraps."""
    X, y, _, _ = _data()
    params = {**BASE, "tree_learner": "data"}
    fps = {}
    for mb in ("0", "1"):
        fr.reset()
        _train(params, X, y, mesh_block=mb)
        fps[mb] = fr.fingerprint()
        fr.reset()
    assert fps["0"][0] > 0, "no collectives recorded"
    assert fps["0"] == fps["1"], fps


# ---------------------------------------------------------------------------
# dispatch-count proof (telemetry spans)
# ---------------------------------------------------------------------------
def _span_counts(params, X, y, mesh_block, rounds=8, no_block=None):
    prev = os.environ.get("LGBM_TPU_NO_BLOCK")
    if no_block:
        os.environ["LGBM_TPU_NO_BLOCK"] = "1"
    obs.reset()
    obs.enable()
    try:
        _train(params, X, y, mesh_block=mesh_block, rounds=rounds)
        s = obs.summary()
        spans = {k: v["count"] for k, v in s["spans"].items()}
        gauges = dict(s["gauges"])
    finally:
        obs.reset()
        if no_block:
            if prev is None:
                os.environ.pop("LGBM_TPU_NO_BLOCK", None)
            else:
                os.environ["LGBM_TPU_NO_BLOCK"] = prev
    return spans, gauges


def test_fused_mesh_one_block_span_per_window():
    """THE dispatch-count assertion: 8 iterations at output_freq=4 are
    2 windows -> exactly 2 block dispatches on the fused mesh path
    (gbdt.block + gbdt.block_compile spans), zero per-iteration
    gbdt.iteration spans, and the dispatch-gap gauge recorded."""
    X, y, _, _ = _data()
    params = {**BASE, "tree_learner": "data", "output_freq": 4,
              "is_training_metric": True}
    spans, gauges = _span_counts(params, X, y, mesh_block="1")
    blocks = spans.get("gbdt.block", 0) + spans.get("gbdt.block_compile", 0)
    assert blocks == 2, spans
    assert spans.get("gbdt.iteration", 0) == 0, spans
    assert "gbdt.dispatch_gap_mean_s" in gauges, gauges


def test_escape_hatch_dispatches_per_iteration():
    """LGBM_TPU_MESH_BLOCK=0: per-iteration dispatch granularity — one
    length-1 block program dispatch per iteration (8 for 8 rounds),
    with the dispatch-gap gauge recorded on this path too."""
    X, y, _, _ = _data()
    params = {**BASE, "tree_learner": "data", "output_freq": 4,
              "is_training_metric": True}
    spans, gauges = _span_counts(params, X, y, mesh_block="0")
    blocks = spans.get("gbdt.block", 0) + spans.get("gbdt.block_compile", 0)
    assert blocks == 8, spans
    assert spans.get("gbdt.iteration", 0) == 0, spans
    assert "gbdt.dispatch_gap_mean_s" in gauges, gauges


def test_no_block_keeps_legacy_eager_path():
    """LGBM_TPU_NO_BLOCK=1 still reaches the pre-refactor eager
    per-iteration loop (gbdt.iteration spans, no blocks) — the legacy
    A/B baseline survives the mesh-block default flip."""
    X, y, _, _ = _data()
    params = {**BASE, "tree_learner": "data"}
    spans, _ = _span_counts(params, X, y, mesh_block="1", no_block=True)
    assert spans.get("gbdt.iteration", 0) == 8, spans
    assert spans.get("gbdt.block", 0) + spans.get(
        "gbdt.block_compile", 0) == 0, spans


# ---------------------------------------------------------------------------
# plumbing
# ---------------------------------------------------------------------------
def test_can_block_on_mesh_and_multiprocess_excluded():
    X, y, _, _ = _data(n=600)
    bst = _train({**BASE, "tree_learner": "data"}, X, y, rounds=1,
                 keep=True)
    g = bst._gbdt
    assert g.mesh_ctx is not None
    assert g._can_block()
    # multi-process layouts stay per-iteration (host-side mask
    # globalization per tree)
    g._pr = object()
    assert not g._can_block()
    g._pr = None


@pytest.mark.parametrize("n", [600, 601])
def test_mesh_scores_and_valid_placed_by_registry(n):
    """The booster's running state is placed under the partition rules
    at init (valid replicated, bins row-sharded; the train scores with
    the rows where the shards divide the row count, else replicated) —
    the registry is the only placement mechanism on the mesh path — and
    the block hands the scores back as it took them: the second block
    compiles nothing (left to the partitioner they came back in another
    layout, and the first step after ``lgb.train`` compiled the block
    program a second time: 46 s of every four-chip run's set-up)."""
    from lightgbm_tpu.basic import Booster
    X, y, Xv, yv = _data(n=n)
    tr = lgb.Dataset(X, label=y)
    va = lgb.Dataset(Xv, label=yv, reference=tr)
    bst = Booster(params={**BASE, "tree_learner": "data"}, train_set=tr)
    bst.add_valid(va, "v0")
    g = bst._gbdt
    ctx = g.mesh_ctx
    shards = ctx.num_data_shards
    assert ctx.scores_sharded == (n % shards == 0)
    assert g.device_data.bins.sharding == ctx.sharding_for("data/bins")
    assert g.scores.sharding == ctx.sharding_for("scores")
    assert g.scores.sharding.is_equivalent_to(
        ctx.row_sharding() if n % shards == 0 else ctx.replicated(),
        g.scores.ndim)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, *_a, **_k: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    g.train(1)
    assert g.scores.sharding == ctx.sharding_for("scores")
    first = len(compiles)
    g.train(1)
    assert len(compiles) == first, "the second block compiled a program"
    assert g._valid_scores[0].sharding.is_equivalent_to(
        ctx.replicated(), g._valid_scores[0].ndim)
    assert g._valid_device[0].bins.sharding.is_equivalent_to(
        ctx.replicated(), g._valid_device[0].bins.ndim)


# ---------------------------------------------------------------------------
# the exchange: one reduction a wave
# ---------------------------------------------------------------------------
def test_one_reduction_a_wave_on_the_flight_recorder(monkeypatch):
    """A 2-shard data-parallel tree records exactly one
    ``parallel.learners.hist_psum`` a wave, of the whole ``[A, G, B, C]``
    operand (int32 code sums at an int8 mode): the unrolled waves of the
    stage plan, then the tail's one traced body.  Around them, once a
    tree: the scales' ``pmax`` and the root totals' ``psum``."""
    import jax.numpy as jnp
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.io.device import to_device
    from lightgbm_tpu.learner import serial
    from lightgbm_tpu.ops.split import SplitParams
    from lightgbm_tpu.parallel.learners import build_tree_distributed
    from lightgbm_tpu.parallel.mesh import make_mesh
    # staged waves at a size the tracer alone sees (nothing executes)
    monkeypatch.setattr(serial, "_COMPILE_LEAN_ROWS", 0)
    X, y, _, _ = _data(n=2048, f=6)
    dd = to_device(BinnedDataset.from_raw(
        X, Config.from_params({"max_bin": 63})))
    p = serial.GrowthParams(num_leaves=31, split=SplitParams(
        min_data_in_leaf=5, min_sum_hessian_in_leaf=0.0))
    plan, A_tail = serial.stage_plan(31)
    assert plan == [8, 8, 8, 8, 8] and A_tail == 16
    fr.reset()
    jax.eval_shape(
        lambda g, h: build_tree_distributed(
            make_mesh(2), "data", "data", dd, g, h, p,
            hist_backend="pallas", hist_mode="int8h"),
        jnp.asarray(y, jnp.float32), jnp.ones(len(y), jnp.float32))
    sites = [(e["site"].rsplit(".", 1)[1], e["op"], e["shape"], e["dtype"])
             for e in fr.snapshot()["last"]]
    fr.reset()
    G, B = dd.num_groups, 64
    assert sites == (
        [("scale_pmax", "pmax", (2,), "float32"),
         ("root_psum", "psum", (4,), "int32")]
        + [("hist_psum", "psum", (A, G, B, 4), "int32")
           for A in plan + [A_tail]])


# ---------------------------------------------------------------------------
# donation: the fused block's score buffers
# ---------------------------------------------------------------------------
def _train_small(n_rounds=12):
    rng = np.random.RandomState(7)
    X = rng.rand(400, 5).astype(np.float32)
    y = (X[:, 0] + 0.2 * rng.rand(400) > 0.6).astype(np.float64)
    Xv = rng.rand(160, 5).astype(np.float32)
    yv = (Xv[:, 0] + 0.2 * rng.rand(160) > 0.6).astype(np.float64)
    train = lgb.Dataset(X, label=y)
    valid = lgb.Dataset(Xv, label=yv, reference=train)
    return lgb.train(
        {"objective": "binary", "num_iterations": n_rounds,
         "num_leaves": 7, "min_data_in_leaf": 5, "output_freq": 4,
         "verbose": -1},
        train, valid_sets=[valid])


def test_donation_gated_off_on_cpu(monkeypatch):
    """Donation is hard-gated to accelerator backends: on CPU,
    ``np.asarray`` host reads are zero-copy views into the very memory
    a donated dispatch lets XLA reuse — eval reading a just-returned
    score buffer flakily SIGSEGVs (reproduced on this image).  So
    ``LGBM_TPU_DONATE=1`` must NOT enable donation on CPU, while the
    same env on an accelerator backend must."""
    from lightgbm_tpu.boosting import gbdt as gbdt_mod
    monkeypatch.setenv("LGBM_TPU_DONATE", "1")
    assert jax.default_backend() == "cpu"
    assert not gbdt_mod._donation_enabled()
    monkeypatch.setattr(gbdt_mod.jax, "default_backend", lambda: "tpu")
    assert gbdt_mod._donation_enabled()
    monkeypatch.setenv("LGBM_TPU_DONATE", "0")
    assert not gbdt_mod._donation_enabled()


def test_donation_env_flip_identical_model_and_zero_steady_recompiles(
        monkeypatch):
    """Flipping ``LGBM_TPU_DONATE`` must never change the model, and
    the block program holds the trace contract — zero post-warmup
    recompiles (the donation gate must not perturb the jit cache).
    On CPU both arms run undonated (see the gating test above)."""
    monkeypatch.setenv("LGBM_TPU_DONATE", "0")
    undonated = _train_small()._gbdt.save_model_to_string()
    monkeypatch.setenv("LGBM_TPU_DONATE", "1")
    monkeypatch.setenv("LGBM_TPU_TRACE_CONTRACT", "1")
    obs.reset()
    try:
        bst = _train_small()
        donated = bst._gbdt.save_model_to_string()
        rep = obs.summary().get("trace_contract")
        assert rep is not None, "trace_contract section missing"
        assert rep["compiles_steady"] == 0 and rep["steady_ok"], rep
    finally:
        obs.reset()
    assert donated == undonated
    # the live score buffers after the run are the block outputs: they
    # must be intact and readable (nothing aliases a dead buffer)
    scores = np.asarray(bst._gbdt.scores)
    assert np.all(np.isfinite(scores))


def test_donation_scores_usable_across_blocks(monkeypatch):
    """Consecutive block dispatches chain each output into the next
    input; eval/metric reads between blocks must see live buffers.
    (On CPU the donation gate keeps dispatches undonated — this is
    exactly the read pattern the gate exists to protect.)"""
    monkeypatch.setenv("LGBM_TPU_DONATE", "1")
    rng = np.random.RandomState(2)
    X = rng.rand(500, 4).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float64)
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "min_data_in_leaf": 5, "verbose": -1}, ds,
                    num_boost_round=3, verbose_eval=False,
                    keep_training_booster=True)
    g = bst._gbdt
    for _ in range(3):
        s = np.asarray(g.scores)       # host read between dispatches
        assert np.all(np.isfinite(s))
        g.train_block(2)
    assert g.num_trees() >= 9


# ---------------------------------------------------------------------------
# placement: the once-placed sharded store
# ---------------------------------------------------------------------------
def test_mesh_place_data_shards_bins_once():
    """place_data puts the bins store on the mesh row-sharded and the
    metadata replicated — the explicit shard rules the per-iteration
    builds then consume in place."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.io.device import to_device
    from lightgbm_tpu.parallel.mesh import MeshContext
    X, _, _, _ = _data(n=2048, f=8)
    dd = to_device(BinnedDataset.from_raw(
        X, Config.from_params({"max_bin": 63})))
    c = Config.from_params({"tree_learner": "data", "mesh_shape": [2]})
    ctx = MeshContext(c)
    placed = ctx.place_data(dd, row_sharded=True)
    assert placed.bins.sharding == ctx.row_sharding()
    assert placed.num_bins.sharding.is_equivalent_to(
        ctx.replicated(), placed.num_bins.ndim)
    np.testing.assert_array_equal(np.asarray(placed.bins),
                                  np.asarray(dd.bins))
    # static metadata survives the round trip
    assert placed.total_bins == dd.total_bins
    assert placed.max_bins == dd.max_bins
