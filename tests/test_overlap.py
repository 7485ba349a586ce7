"""Overlapped-collective multi-chip training (PR 7 tentpole).

The acceptance contract of the double-buffered chunked wave reduction
(`ops/overlap.py`, threaded through the data-parallel learner):

* BIT-exact trees vs the serial-psum schedule on a multi-shard CPU
  mesh (chunked psums are the same elementwise adds — no
  reassociation, so equality is exact, not approximate);
* the flight-recorder schedule digest is IDENTICAL across the two
  lowerings (the recorder pins the logical schedule: one reduction
  per wave, full operand);
* score-buffer donation through the fused block program changes
  nothing observable: identical models, zero post-warmup recompiles
  under the trace contract — and it is hard-gated OFF on the CPU
  backend, where zero-copy ``np.asarray`` host reads alias the
  memory donation would let XLA reuse.
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.io.device import to_device
from lightgbm_tpu.learner.serial import GrowthParams, build_tree
from lightgbm_tpu.ops.overlap import _chunk_bounds, wave_psum
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.parallel.learners import (build_tree_distributed,
                                            shard_map)
from lightgbm_tpu.parallel.mesh import make_mesh
from lightgbm_tpu.obs import flight_recorder as fr

TREE_FIELDS = ("feature", "threshold_bin", "default_left", "is_categorical",
               "left_child", "right_child", "gain", "leaf_value",
               "leaf_count", "leaf_depth", "num_leaves", "row_leaf")


@pytest.fixture(scope="module")
def two_devices():
    if len(jax.devices()) < 2:
        pytest.skip("needs >=2 virtual devices")
    return jax.devices()[:2]


def _setup(n=4096, f=8, leaves=31, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] * 2 + X[:, 1] - 0.5 * X[:, 2]
         + 0.3 * rng.normal(size=n)).astype(np.float32)
    dd = to_device(BinnedDataset.from_raw(
        X, Config.from_params({"max_bin": 63})))
    grad = jnp.asarray(-(y - y.mean()))
    hess = jnp.ones(n)
    p = GrowthParams(num_leaves=leaves, split=SplitParams(
        min_data_in_leaf=10, min_sum_hessian_in_leaf=0.0))
    return dd, grad, hess, p, X, y


# ---------------------------------------------------------------------------
# unit: the chunked lowering itself
# ---------------------------------------------------------------------------
def test_chunk_bounds_cover_and_clamp():
    assert _chunk_bounds(8, 2) == [(0, 4), (4, 8)]
    assert _chunk_bounds(7, 2) == [(0, 4), (4, 7)]
    assert _chunk_bounds(3, 8) == [(0, 1), (1, 2), (2, 3)]  # clamped
    assert _chunk_bounds(5, 1) == [(0, 5)]
    for G, k in ((1, 1), (28, 4), (136, 3)):
        b = _chunk_bounds(G, k)
        assert b[0][0] == 0 and b[-1][1] == G
        assert all(x[1] == y[0] for x, y in zip(b, b[1:]))


def test_chunked_psum_bit_identical_to_plain(two_devices):
    """wave_psum (the chunked lowering) vs one lax.psum on a 2-shard
    mesh: bit-identical — psum reduces elementwise, so chunking along
    a non-reduced axis changes no add order."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.normal(size=(2, 16, 7, 64, 3)).astype(np.float32))
    mesh = make_mesh(2)

    def run(fn):
        f = shard_map(fn, mesh=mesh, in_specs=(jax.sharding.PartitionSpec("data"),),
                      out_specs=jax.sharding.PartitionSpec(),
                      check_vma=False)
        return np.asarray(f(x))

    plain = run(lambda s: jax.lax.psum(s[0], "data"))
    for chunks in (2, 3, 7):
        chunked = run(lambda s, c=chunks: wave_psum(s[0], "data", chunks=c))
        np.testing.assert_array_equal(plain, chunked)


# ---------------------------------------------------------------------------
# tree-level: overlapped vs serial-psum schedule
# ---------------------------------------------------------------------------
def test_overlap_data_parallel_bit_exact(two_devices):
    dd, grad, hess, p, _, _ = _setup()
    mesh = make_mesh(2)
    off = build_tree_distributed(mesh, "data", "data", dd, grad, hess, p,
                                 overlap=False)
    on = build_tree_distributed(mesh, "data", "data", dd, grad, hess, p,
                                overlap=True)
    assert int(on.num_leaves) == p.num_leaves
    for name in TREE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(off, name)), np.asarray(getattr(on, name)),
            err_msg=f"overlap diverged on {name}")
    # and both still match the serial learner exactly at this shape
    serial = build_tree(dd, grad, hess, p)
    np.testing.assert_array_equal(np.asarray(serial.feature),
                                  np.asarray(on.feature))
    np.testing.assert_array_equal(np.asarray(serial.threshold_bin),
                                  np.asarray(on.threshold_bin))


def test_overlap_bit_exact_with_bagging_and_feature_mask(two_devices):
    """The masked/bagged wave path (pad slots, inactive leaves) must
    stay bit-exact too — padding slots carry garbage that the chunked
    apply must drop exactly like the full-block apply."""
    dd, grad, hess, p, _, _ = _setup(n=2048, leaves=15, seed=5)
    rng = np.random.RandomState(11)
    bag = jnp.asarray(rng.rand(2048) < 0.7)
    fmask = jnp.asarray(np.array([1, 1, 0, 1, 1, 0, 1, 1], bool))
    mesh = make_mesh(2)
    kw = dict(bag_mask=bag, feature_mask=fmask)
    off = build_tree_distributed(mesh, "data", "data", dd, grad, hess, p,
                                 overlap=False, **kw)
    on = build_tree_distributed(mesh, "data", "data", dd, grad, hess, p,
                                overlap=True, **kw)
    for name in TREE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(off, name)), np.asarray(getattr(on, name)),
            err_msg=f"overlap diverged on {name}")


def test_overlap_flight_recorder_digest_equal(two_devices):
    """The recorded collective schedule (site/op/axis/shape/order) is
    the LOGICAL one and must be identical across the two lowerings —
    spmdcheck's runtime half stays green with overlap on."""
    dd, grad, hess, p, _, _ = _setup(n=2048, leaves=15)
    mesh = make_mesh(2)
    fps = {}
    for ov in (False, True):
        fr.reset()
        build_tree_distributed(mesh, "data", "data", dd, grad, hess, p,
                               overlap=ov)
        fps[ov] = fr.fingerprint()
    fr.reset()
    assert fps[False][0] > 0, "no collectives recorded"
    assert fps[False] == fps[True], fps


def test_overlap_end_to_end_model_identical(two_devices):
    """Full engine path (GBDT mesh setup, once-placed sharded inputs,
    per-iteration jitted distributed builds): LGBM_TPU_OVERLAP on/off
    must produce byte-identical model files."""
    _, _, _, _, X, yv = _setup(n=3003, f=8, leaves=15)
    y = (yv > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "tree_learner": "data", "mesh_shape": [2],
              "bagging_freq": 2, "bagging_fraction": 0.8}
    models = {}
    prev = os.environ.get("LGBM_TPU_OVERLAP")
    try:
        for ov in ("0", "1"):
            os.environ["LGBM_TPU_OVERLAP"] = ov
            bst = lgb.train(params, lgb.Dataset(X, label=y),
                            num_boost_round=4, verbose_eval=False)
            models[ov] = bst._gbdt.save_model_to_string()
    finally:
        if prev is None:
            os.environ.pop("LGBM_TPU_OVERLAP", None)
        else:
            os.environ["LGBM_TPU_OVERLAP"] = prev
    assert models["0"] == models["1"]


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
    On CPU both arms run undonated (see the gating test above); the
    donated lowering's byte-identity is re-asserted by the bench's
    multichip parity gate on accelerator images."""
    from lightgbm_tpu import obs
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


def test_donation_scores_usable_across_blocks():
    """Consecutive block dispatches chain each output into the next
    input; eval/metric reads between blocks must see live buffers.
    (On CPU the donation gate keeps dispatches undonated — this is
    exactly the read pattern the gate exists to protect.)"""
    prev = os.environ.get("LGBM_TPU_DONATE")
    os.environ["LGBM_TPU_DONATE"] = "1"
    try:
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
    finally:
        if prev is None:
            os.environ.pop("LGBM_TPU_DONATE", None)
        else:
            os.environ["LGBM_TPU_DONATE"] = prev


# ---------------------------------------------------------------------------
# placement: the once-placed sharded store
# ---------------------------------------------------------------------------
def test_mesh_place_data_shards_bins_once(two_devices):
    """place_data puts the bins store on the mesh row-sharded and the
    metadata replicated — the explicit shard rules the per-iteration
    builds then consume in place."""
    from jax.sharding import PartitionSpec as P
    from lightgbm_tpu.parallel.mesh import MeshContext
    dd, _, _, _, _, _ = _setup(n=2048, leaves=15)
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
