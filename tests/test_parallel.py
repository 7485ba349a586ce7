"""Distributed learner tests on the virtual 8-device CPU mesh.

This is the multi-"node" testing the reference could not do in-repo
(SURVEY.md §4): data/feature/voting-parallel learners run as real 8-way
SPMD programs; assertions check (a) agreement with the serial learner
where exact agreement is expected, and (b) fit quality where the strategy
is an approximation (voting).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.io.device import to_device
from lightgbm_tpu.learner.serial import GrowthParams, build_tree
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.parallel.learners import build_tree_distributed
from lightgbm_tpu.parallel.mesh import make_mesh


def _data(n=1024, f=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] * 2 + X[:, 1] + 0.2 * rng.normal(size=n)).astype(np.float32)
    return X, y


def _setup(n=1024, f=8):
    X, y = _data(n, f)
    ds = BinnedDataset.from_raw(X, Config.from_params({"max_bin": 63}))
    dd = to_device(ds)
    grad = jnp.asarray(-(y - y.mean()))
    hess = jnp.ones(n)
    p = GrowthParams(num_leaves=15, split=SplitParams(
        min_data_in_leaf=10, min_sum_hessian_in_leaf=0.0))
    return dd, grad, hess, p, y


@pytest.fixture(scope="module")
def eight_devices():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return jax.devices()[:8]


def test_data_parallel_matches_serial(eight_devices):
    dd, grad, hess, p, y = _setup()
    serial = build_tree(dd, grad, hess, p)
    mesh = make_mesh(8)
    dist = build_tree_distributed(mesh, "data", "data", dd, grad, hess, p)
    assert int(dist.num_leaves) == int(serial.num_leaves)
    np.testing.assert_array_equal(np.asarray(dist.feature),
                                  np.asarray(serial.feature))
    np.testing.assert_array_equal(np.asarray(dist.threshold_bin),
                                  np.asarray(serial.threshold_bin))
    np.testing.assert_array_equal(np.asarray(dist.row_leaf),
                                  np.asarray(serial.row_leaf))
    np.testing.assert_allclose(np.asarray(dist.leaf_value),
                               np.asarray(serial.leaf_value),
                               rtol=1e-4, atol=1e-5)


def test_feature_parallel_matches_serial(eight_devices):
    dd, grad, hess, p, y = _setup()
    serial = build_tree(dd, grad, hess, p)
    mesh = make_mesh(8)
    dist = build_tree_distributed(mesh, "data", "feature", dd, grad, hess, p)
    assert int(dist.num_leaves) == int(serial.num_leaves)
    np.testing.assert_array_equal(np.asarray(dist.feature),
                                  np.asarray(serial.feature))
    np.testing.assert_array_equal(np.asarray(dist.threshold_bin),
                                  np.asarray(serial.threshold_bin))


def test_voting_parallel_quality(eight_devices):
    dd, grad, hess, p, y = _setup(n=2048)
    serial = build_tree(dd, grad, hess, p)
    mesh = make_mesh(8)
    dist = build_tree_distributed(mesh, "data", "voting", dd, grad, hess, p,
                                  top_k=4)
    assert int(dist.num_leaves) > 1
    res = np.asarray(grad) * -1.0
    fit_serial = np.asarray(serial.leaf_value)[np.asarray(serial.row_leaf)]
    fit_vote = np.asarray(dist.leaf_value)[np.asarray(dist.row_leaf)]
    mse_s = np.mean((fit_serial - res) ** 2)
    mse_v = np.mean((fit_vote - res) ** 2)
    # voting is an approximation but must be close on well-separated data
    assert mse_v < mse_s * 1.5 + 1e-3


def test_voting_collective_bytes_scale_with_topk(eight_devices):
    """Structural comm-volume check (VERDICT r2 weak #6): parse the
    compiled SPMD program's HLO and sum the bytes crossing all-reduce /
    all-gather / reduce-scatter.  Voting-parallel's per-wave collective
    volume must be O(2A*2k*B) — a small fraction of data-parallel's
    O(A*F*B) on wide data (`voting_parallel_tree_learner.cpp:164-193`
    vs `data_parallel_tree_learner.cpp:147-162`).
    """
    import re
    n, f = 2048, 96                       # wide: voting's regime
    X, y = _data(n, f, seed=4)
    ds = BinnedDataset.from_raw(X, Config.from_params({"max_bin": 63}))
    dd = to_device(ds)
    grad = jnp.asarray(-(y - y.mean()))
    hess = jnp.ones(n)
    p = GrowthParams(num_leaves=15, split=SplitParams(
        min_data_in_leaf=10, min_sum_hessian_in_leaf=0.0))
    mesh = make_mesh(8, devices=eight_devices)

    DT = {"f64": 8, "f32": 4, "bf16": 2, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f16": 2}

    def collective_bytes(learner, **kw):
        fn = jax.jit(lambda g, h: build_tree_distributed(
            mesh, "data", learner, dd, g, h, p, hist_backend="scatter",
            **kw))
        txt = fn.lower(grad, hess).compile().as_text()
        total = 0
        # HLO: "%name = <shape(s)> all-reduce(...)" — shapes precede the op
        for m in re.finditer(
                r"=\s*(\([^)]*\)|\S+)\s+"
                r"(?:all-reduce|all-gather|reduce-scatter)(?:-start)?\(",
                txt):
            shapes = re.findall(r"(f64|f32|bf16|f16|s64|u64|s32|u32|s8|u8|pred)"
                                r"\[([\d,]*)\]", m.group(1))
            for dt, dims in shapes:
                elems = 1
                for d in dims.split(","):
                    if d:
                        elems *= int(d)
                total += elems * DT[dt]
        assert total > 0, "no collectives found in HLO"
        return total

    dp = collective_bytes("data")
    vp = collective_bytes("voting", top_k=4)
    # voting moves the votes + 2k winning feature columns instead of all
    # F columns: on 96 features with k2=8 the histogram part shrinks
    # ~12x; allow generous slack for the shared best-split sync
    assert vp < dp * 0.45, (vp, dp)


def test_voting_vote_bytes_scale_with_k_not_F(eight_devices):
    """VERDICT r3 #6: the VOTE phase must exchange O(k) (feature id,
    gain) pairs, not a dense [2A, F] tally — so voting-parallel's total
    collective bytes are (near-)constant in F at fixed k.  A dense-vote
    regression makes bytes grow linearly with F and fails this."""
    import re
    DT = {"f64": 8, "f32": 4, "bf16": 2, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f16": 2}
    p = GrowthParams(num_leaves=15, split=SplitParams(
        min_data_in_leaf=10, min_sum_hessian_in_leaf=0.0))
    mesh = make_mesh(8)

    def total_bytes(f):
        n = 2048
        X, y = _data(n, f, seed=4)
        ds = BinnedDataset.from_raw(X, Config.from_params({"max_bin": 63}))
        dd = to_device(ds)
        grad = jnp.asarray(-(y - y.mean()))
        hess = jnp.ones(n)
        fn = jax.jit(lambda g, h: build_tree_distributed(
            mesh, "data", "voting", dd, g, h, p, hist_backend="scatter",
            top_k=4))
        txt = fn.lower(grad, hess).compile().as_text()
        total = 0
        for m in re.finditer(
                r"=\s*(\([^)]*\)|\S+)\s+"
                r"(?:all-reduce|all-gather|reduce-scatter)(?:-start)?\(",
                txt):
            shapes = re.findall(r"(f64|f32|bf16|f16|s64|u64|s32|u32|s8|u8|pred)"
                                r"\[([\d,]*)\]", m.group(1))
            for dt, dims in shapes:
                elems = 1
                for d in dims.split(","):
                    if d:
                        elems *= int(d)
                total += elems * DT[dt]
        return total

    b96, b192 = total_bytes(96), total_bytes(192)
    # doubling F must not grow collective volume meaningfully (dense
    # votes would roughly double it)
    assert b192 < b96 * 1.3, (b96, b192)


def test_end_to_end_data_parallel_training(eight_devices):
    """Full booster run with tree_learner=data on the 8-device mesh, with a
    row count NOT divisible by 8 (exercises padding)."""
    X, yb = _data(n=1003)
    y = (yb > 0).astype(np.float32)
    train = lgb.Dataset(X, label=y)
    evals = {}
    bst = lgb.train({"objective": "binary", "metric": "auc",
                     "tree_learner": "data", "num_leaves": 15,
                     "min_data_in_leaf": 10},
                    train, 10, valid_sets=[train.create_valid(X, label=y)],
                    evals_result=evals, verbose_eval=False)
    assert evals["valid_0"]["auc"][-1] > 0.97
    # serial reference run reaches the same quality
    bst_s = lgb.train({"objective": "binary", "metric": "auc",
                       "num_leaves": 15, "min_data_in_leaf": 10},
                      lgb.Dataset(X, label=y), 10,
                      verbose_eval=False)
    p_d = bst.predict(X[:200], raw_score=True)
    p_s = bst_s.predict(X[:200], raw_score=True)
    np.testing.assert_allclose(p_d, p_s, rtol=1e-3, atol=1e-3)


def test_parallel_learner_on_one_device_warns_and_trains_serially(
        monkeypatch, caplog):
    """tree_learner != serial with one visible device and no mesh_shape
    builds no mesh — and says so, at warning level."""
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    X, y = _data(n=256, f=4)
    with caplog.at_level("WARNING", logger="lightgbm_tpu"):
        bst = lgb.train({"objective": "regression", "tree_learner": "data",
                         "num_leaves": 7, "min_data_in_leaf": 5},
                        lgb.Dataset(X, label=y), 2, verbose_eval=False,
                        keep_training_booster=True)
    assert bst._gbdt.mesh_ctx is None
    assert any("training SERIALLY" in r.getMessage()
               for r in caplog.records)


def test_dryrun_multichip_raises_with_too_few_devices():
    """The driver entry starts no child after touching JAX: with too few
    devices it raises and names the variables to set."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from __graft_entry__ import dryrun_multichip
    need = len(jax.devices()) + 1
    with pytest.raises(RuntimeError) as e:
        dryrun_multichip(need)
    assert "XLA_FLAGS" in str(e.value) and "JAX_PLATFORMS" in str(e.value)
    assert f"device_count={need}" in str(e.value)


# ---------------------------------------------------------------------------
# one tree whatever the shards (ISSUE 28): the int8 modes under
# tree_learner=data
# ---------------------------------------------------------------------------
TREE_FIELDS = ("feature", "threshold_bin", "default_left", "left_child",
               "right_child", "gain", "internal_value", "internal_count",
               "leaf_value", "leaf_count", "leaf_depth", "num_leaves",
               "row_leaf")


# the rows of the trees below: one row tile of the kernels, and the four
# that a cut into row chunks needs
ROWS, ROWS_CHUNKED = 2048, 8192


@pytest.fixture(scope="module")
def uneven():
    """Rows whose largest gradient and hessian differ from shard to
    shard: a shard's own scales would round its rows to other codes.
    ``{rows: (data, grad, hess, params)}``."""
    def make(n):
        X, y = _data(n, 8, seed=4)
        rng = np.random.RandomState(9)
        ds = BinnedDataset.from_raw(X, Config.from_params({"max_bin": 63}))
        grad = -(y - y.mean())
        grad[:n // 4] *= 0.37
        hess = (0.5 + rng.rand(n)).astype(np.float32)
        hess[n // 2:] *= 0.61
        p = GrowthParams(num_leaves=15, split=SplitParams(
            min_data_in_leaf=10, min_sum_hessian_in_leaf=0.0))
        return to_device(ds), jnp.asarray(grad), jnp.asarray(hess), p
    return {n: make(n) for n in (ROWS, ROWS_CHUNKED)}


@pytest.fixture(scope="module")
def row_sets():
    """What a tree is grown on: all rows and features, or a bag of 70%
    of the rows (the same rows however they are cut into shards or
    chunks) under a feature mask."""
    fmask = jnp.asarray(np.array([1, 1, 0, 1, 1, 0, 1, 1], bool))

    def bag(n):
        return jnp.asarray(np.random.RandomState(11).rand(n) < 0.7)
    return {n: {"all": {}, "bagged": dict(bag_mask=bag(n),
                                          feature_mask=fmask)}
            for n in (ROWS, ROWS_CHUNKED)}


@pytest.fixture(scope="module")
def serial_trees(uneven, row_sets):
    """The serial learner's trees, every sum in one int32 accumulator."""
    def grow(n, mode, kw):
        dd, grad, hess, p = uneven[n]
        return jax.jit(lambda g, h: build_tree(
            dd, g, h, p, hist_backend="pallas", hist_mode=mode, **kw))(
                grad, hess)
    return {(n, mode, rows): grow(n, mode, kw)
            for n in (ROWS, ROWS_CHUNKED)
            for mode in ("int8", "int8h", "int8hh")
            for rows, kw in row_sets[n].items()}


# (row shards, row chunks a shard): the cuts of one set of rows
CUTS = [(1, 1), (2, 1), (4, 1), (1, 2), (1, 4), (2, 2)]


@pytest.mark.parametrize("rows", ["all", "bagged"])
@pytest.mark.parametrize("shards,chunks", CUTS,
                         ids=[f"{s}x{k}" for s, k in CUTS])
@pytest.mark.parametrize("mode", ["int8", "int8h", "int8hh"])
def test_quantised_data_parallel_grows_the_serial_tree(
        eight_devices, uneven, row_sets, serial_trees, monkeypatch, mode,
        shards, chunks, rows):
    """Global scales, integer code sums across the shards, one
    dequantisation: every field of the tree, gains and leaf values
    included, is the serial learner's bit for bit — bagged-out rows,
    masked features and padding slots included.  A shard of more rows
    than one int32 cell sums exactly (the bound patched down to a row
    tile or two) sums them in row chunks whose partials add as the
    shards' do: the same tree from 1, 2 and 4 chunks on one chip
    (the serial learner, no exchange) and from two shards of two."""
    from lightgbm_tpu.learner import serial
    n = ROWS if chunks == 1 else ROWS_CHUNKED
    dd, grad, hess, p = uneven[n]
    kw = dict(hist_backend="pallas", hist_mode=mode, **row_sets[n][rows])
    if chunks > 1:
        monkeypatch.setattr(serial, "_INT8_ROW_LIMIT", n // shards // chunks)
        assert serial.shard_row_chunks(n // shards) == chunks
        assert serial.effective_hist_mode(mode, n // shards, shards) == mode
    if shards == 1 and chunks > 1:
        got = jax.jit(lambda g, h: build_tree(dd, g, h, p, **kw))(grad, hess)
    else:
        got = build_tree_distributed(make_mesh(shards), "data", "data", dd,
                                     grad, hess, p, **kw)
    for name in TREE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)),
            np.asarray(getattr(serial_trees[n, mode, rows], name)),
            err_msg=name)


def test_code_sums_cross_the_shards_without_wrapping(eight_devices):
    """``psum_codes``: four shards' int32 cells from the whole int32
    range, whose totals pass 2^31, come out as the limbs of the true
    total, and ``dequant_hist`` rounds it to float32 once (what an
    int32 -> float32 conversion makes of a total that fits)."""
    from lightgbm_tpu.ops.pallas_histogram import dequant_hist
    from lightgbm_tpu.parallel.learners import psum_codes, shard_map
    from jax.sharding import PartitionSpec as P
    rng = np.random.RandomState(2)
    x = rng.randint(-2**31 + 1, 2**31 - 1, size=(4, 4096, 3), dtype=np.int64)
    x[:, :8] = 2**31 - 1                     # every shard at the bound
    x[:, 8:16] = -2**31 + 1
    x[:, 16:24] = np.array([2**24 + 1, 1, 0, 0])[:, None, None]
    total = x.sum(axis=0)
    assert np.abs(total).max() > 2**32
    f = shard_map(lambda s: psum_codes(s[0], "data", 4), mesh=make_mesh(4),
                  in_specs=(P("data"),), out_specs=P(), check_vma=False)
    hi, lo = f(jnp.asarray(x.astype(np.int32)))
    assert lo.dtype == hi.dtype == jnp.int32
    assert int(lo.min()) >= 0 and int(lo.max()) < 2**16
    np.testing.assert_array_equal(
        np.asarray(hi).astype(np.int64) * 2**16 + np.asarray(lo), total)
    # int8: each column times scale / 127; with a scale of 127 the
    # floats are the totals rounded to nearest (int64 -> float32)
    got = dequant_hist((hi, lo), jnp.asarray([127.0, 127.0]), "int8")
    np.testing.assert_array_equal(np.asarray(got), total.astype(np.float32))
    # one chip's int32 goes through the same limbs
    one = dequant_hist(jnp.asarray(x[1].astype(np.int32)),
                       jnp.asarray([127.0, 127.0]), "int8")
    np.testing.assert_array_equal(np.asarray(one), x[1].astype(np.float32))
    with pytest.raises(ValueError, match="at most 511"):
        psum_codes(jnp.zeros(4, jnp.int32), "data", 512)


@pytest.mark.parametrize("case", ["global_over", "shard_over",
                                  "parts_over"])
def test_int8_mode_is_judged_on_the_parts_it_sums(eight_devices, monkeypatch,
                                                  case):
    """What sums in int32 is a chunk of a shard's rows, and what the
    limbs add is shards x chunks.  All rows over the bound of a chunk
    and each shard under it, or a shard over it too (it sums its rows in
    chunks): the int8 mode runs, the gauge says so, no ``degrade``
    event, and the trees are the serial learner's at the same mode.
    More parts than the limbs add exactly: the float mode runs, and
    says so."""
    from lightgbm_tpu import obs
    from lightgbm_tpu.learner import serial
    monkeypatch.setenv("LGBM_TPU_HIST_BACKEND", "pallas")
    X, yb = _data(n=2001, f=6, seed=3)
    y = (yb > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 10,
              "hist_mode": "int8h", "verbose": -1}
    want = lgb.train(params, lgb.Dataset(X, label=y), 3,
                     verbose_eval=False)._gbdt.save_model_to_string()
    monkeypatch.setattr(serial, "_INT8_ROW_LIMIT",
                        400 if case == "shard_over" else 600)
    if case == "parts_over":
        monkeypatch.setattr(serial, "MAX_CODE_SHARDS", 3)
    obs.reset()
    obs.enable()
    try:
        bst = lgb.train({**params, "tree_learner": "data", "mesh_shape": [4]},
                        lgb.Dataset(X, label=y), 3, verbose_eval=False,
                        keep_training_booster=True)
        s = obs.summary()
    finally:
        obs.reset()
    assert bst._gbdt.mesh_ctx.num_data_shards == 4      # 501 rows a shard
    if case == "parts_over":
        assert bst._gbdt.hist_mode == s["gauges"]["gbdt.hist_mode"] == "hhilo"
        assert s["gauges"]["gbdt.hist_mode_requested"] == "int8h"
        assert s["events"]["degrade:hist_mode"] == 1
    else:
        assert bst._gbdt.hist_mode == s["gauges"]["gbdt.hist_mode"] == "int8h"
        assert "gbdt.hist_mode_requested" not in s["gauges"]
        assert not [k for k in s["events"] if k.startswith("degrade:")]
        assert bst._gbdt.save_model_to_string() == want
