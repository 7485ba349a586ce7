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
  loop (``gbdt.iteration`` spans).
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
