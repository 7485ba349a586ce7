"""Tree learner tests: growth correctness on small synthetic datasets.

Validation strategy mirrors the reference's (SURVEY.md §4): behavioral
assertions on small data (a single tree must reproduce an exactly-learnable
function) rather than C++-style unit mocks.
"""
from types import SimpleNamespace

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.io.device import to_device
from lightgbm_tpu.learner.serial import (BuiltTree, GrowthParams, build_tree,
                                         make_hist_fold_fn,
                                         predict_built_tree, resolve_backend,
                                         stage_plan, wave_backend_plan)
from lightgbm_tpu.ops.pallas_histogram import default_backend
from lightgbm_tpu.ops.split import SplitParams


def _make_data(n=800, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, 4).astype(np.float32)
    # piecewise-constant target on feature 0 and 2: exactly learnable
    y = np.where(X[:, 0] < 0.5,
                 np.where(X[:, 2] < 0.3, 1.0, 2.0),
                 np.where(X[:, 2] < 0.7, 3.0, 4.0)).astype(np.float32)
    return X, y


def _build(X, y, num_leaves=8, wave_size=0, **split_kw):
    cfg = Config.from_params({"min_data_in_leaf": 5, "max_bin": 63})
    ds = BinnedDataset.from_raw(X, cfg)
    dd = to_device(ds)
    grad = jnp.asarray(-(y - y.mean()), jnp.float32)   # L2 gradients, score=mean
    hess = jnp.ones(len(y), jnp.float32)
    p = GrowthParams(num_leaves=num_leaves, wave_size=wave_size,
                     split=SplitParams(min_data_in_leaf=5,
                                       min_sum_hessian_in_leaf=0.0, **split_kw))
    tree = build_tree(dd, grad, hess, p)
    return tree, dd, ds, y


def test_tree_fits_piecewise_function():
    X, y = _make_data()
    tree, dd, ds, y = _build(X, y, num_leaves=8)
    assert int(tree.num_leaves) >= 4
    # every leaf value must equal the mean residual of its rows (L2 optimum)
    rl = np.asarray(tree.row_leaf)
    lv = np.asarray(tree.leaf_value)
    res = y - y.mean()
    for l in range(int(tree.num_leaves)):
        m = rl == l
        if m.any():
            np.testing.assert_allclose(lv[l], res[m].mean(), rtol=1e-4,
                                       atol=1e-5)
    # and the tree as a whole should fit this near-separable target well
    pred = lv[rl] + y.mean()
    assert np.mean((pred - y) ** 2) < 0.05


def test_wave_one_equals_leafwise_greedy():
    """wave_size=1 is strict best-first; full wave should reach a fit of
    the same quality on this separable problem."""
    X, y = _make_data()
    t1, dd, _, _ = _build(X, y, num_leaves=8, wave_size=1)
    tw, _, _, _ = _build(X, y, num_leaves=8, wave_size=0)
    p1 = np.asarray(t1.leaf_value)[np.asarray(t1.row_leaf)]
    pw = np.asarray(tw.leaf_value)[np.asarray(tw.row_leaf)]
    res = y - y.mean()
    mse1 = np.mean((p1 - res) ** 2)
    msew = np.mean((pw - res) ** 2)
    assert msew < mse1 * 1.5 + 1e-3


def test_predict_built_tree_matches_row_leaf():
    X, y = _make_data()
    tree, dd, ds, y = _build(X, y)
    pred = np.asarray(predict_built_tree(tree, dd, dd.bins))
    via_leaf = np.asarray(tree.leaf_value)[np.asarray(tree.row_leaf)]
    np.testing.assert_allclose(pred, via_leaf, atol=1e-6)


def test_max_depth_respected():
    X, y = _make_data()
    cfg = Config.from_params({"max_bin": 63})
    ds = BinnedDataset.from_raw(X, cfg)
    dd = to_device(ds)
    grad = jnp.asarray(-(y - y.mean()), jnp.float32)
    hess = jnp.ones(len(y), jnp.float32)
    p = GrowthParams(num_leaves=31, max_depth=2,
                     split=SplitParams(min_data_in_leaf=1,
                                       min_sum_hessian_in_leaf=0.0))
    tree = build_tree(dd, grad, hess, p)
    assert int(tree.num_leaves) <= 4          # depth 2 => at most 4 leaves
    assert int(jnp.max(tree.leaf_depth)) <= 2


def test_bagging_mask_excludes_rows():
    X, y = _make_data()
    cfg = Config.from_params({"max_bin": 63})
    ds = BinnedDataset.from_raw(X, cfg)
    dd = to_device(ds)
    grad = jnp.asarray(-(y - y.mean()), jnp.float32)
    hess = jnp.ones(len(y), jnp.float32)
    bag = jnp.asarray(np.random.RandomState(0).rand(len(y)) < 0.5)
    p = GrowthParams(num_leaves=8, split=SplitParams(
        min_data_in_leaf=5, min_sum_hessian_in_leaf=0.0))
    tree = build_tree(dd, grad, hess, p, bag_mask=bag)
    # in-bag leaf counts sum to bag size
    nl = int(tree.num_leaves)
    assert int(np.asarray(tree.leaf_count)[:nl].sum()) == int(bag.sum())
    # out-of-bag rows still get a leaf assignment
    assert (np.asarray(tree.row_leaf) >= 0).all()


def test_min_data_in_leaf_respected():
    X, y = _make_data()
    tree, dd, ds, y = _build(X, y, num_leaves=16)
    nl = int(tree.num_leaves)
    counts = np.asarray(tree.leaf_count)[:nl]
    assert (counts >= 5).all()


@pytest.mark.parametrize("leaves", [255, 31])
def test_auto_on_a_tpu_is_the_wide_kernel_in_every_wave(monkeypatch, leaves):
    """On a TPU "auto" resolves to "pallas", and a wave of it has one
    histogram path whatever its slot count (PR 27 stopped compacting
    the two deepest waves, PR 31 deleted the compaction, PR 32 sized
    them by the 32 and 64 smaller children they can be handed): the
    fused route+histogram kernel where its gate admits it, the wide
    kernel elsewhere."""
    monkeypatch.delenv("LGBM_TPU_HIST_BACKEND", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert default_backend() == "pallas"
    dd = SimpleNamespace(group_max_bins=63)
    assert resolve_backend(dd, leaves, "auto", "int8h") == "pallas"
    plan, A_tail = stage_plan(leaves)
    assert (plan[-2:], A_tail) == (([32, 64], 128) if leaves == 255
                                   else ([8, 8], 16))
    shape = dict(num_groups=28, max_bins=63, mode="int8h", n_rows=1 << 20)
    for serial, want in ((True, "fused"), (False, "pallas")):
        waves, A, tail = wave_backend_plan(leaves, backend="pallas",
                                           serial=serial, **shape)
        assert [w.slots for w in waves] == plan and A == A_tail
        assert [w.choice for w in waves] == \
            ["pallas"] + [want] * (len(plan) - 1)
        assert tail == want
    # leaf-wise growth: every wave is the 8-slot tail
    assert wave_backend_plan(leaves, wave_size=1, **shape) == \
        ([], 8, "fused")


_CRITEO = dict(num_groups=67, max_bins=63, mode="int8h")


@pytest.mark.parametrize("shape,serial,want,tail", [
    # the one-chip cells: every wave with a route to apply holds the 67
    # features in one tile at its own columns; the tail's 512 do not
    (dict(_CRITEO, n_rows=13_281_280), True, ["pallas"] + ["fused"] * 7,
     "pallas"),
    # -c32: four row chunks, and the fused accumulator is one int32 sum
    (dict(_CRITEO, n_rows=53_125_120), True, ["pallas"] * 8, "pallas"),
    # -dp4: the exchange sits between the histogram and the scan
    (dict(_CRITEO, n_rows=13_281_280), False, ["pallas"] * 8, "pallas"),
    # chip_smoke.py's width: fused wherever a wave has a route, the tail
    # too, as under the tree-wide gate
    (dict(num_groups=28, max_bins=63, mode="int8h", n_rows=1 << 20), True,
     ["pallas"] + ["fused"] * 7, "fused"),
])
def test_wave_backend_plan_judges_each_wave(shape, serial, want, tail):
    """The fused route+histogram call is chosen a wave at a time from
    static shapes (the wave's slots, the leaves its route reads, the
    rows against one chunk, the exchange), and the root wave, which has
    no split to apply, keeps the wide call."""
    waves, A_tail, got = wave_backend_plan(255, serial=serial, **shape)
    assert ([w.choice for w in waves], got) == (want, tail)
    assert [w.route_leaves for w in waves] == [1, 1, 2, 4, 8, 16, 32, 64]
    assert ([w.slots for w in waves], A_tail) == stage_plan(255)


@pytest.mark.parametrize(
    "L", [2, 3, 15, 16, 31, 63, 64, 127, 255, 256, 1024])
def test_stage_plan_sizes_a_wave_by_the_splits_before_it(L):
    """A slot holds the smaller child of a split the wave before
    selected, so wave ``i`` has ``min(round8(need_i), A_tail)`` slots,
    ``need_i`` the most it can be handed, and no more (half the slots of
    a plan sized by the leaves that exist can never be filled).  Walked
    with the worst case, the leaves doubling up to ``L``: the cap ``k <=
    A_out`` of ``_apply_wave`` binds only where the tail's 128 does."""
    from lightgbm_tpu.ops.vmem import round_up

    def round8(x):
        return round_up(x, 8)

    plan, A_tail = stage_plan(L)
    assert A_tail == min(round8(L // 2), 128)
    leaves, need = 1, 1                 # the root wave histograms 1 leaf
    for i, A_in in enumerate(plan):
        assert leaves < L               # no wave the worst case skips
        assert A_in == min(round8(need), A_tail)
        A_out = plan[i + 1] if i + 1 < len(plan) else A_tail
        # wave i selects among nl <= leaves leaves (an unbalanced tree
        # has fewer), k <= min(nl, L - nl) of them
        most = max(min(nl, L - nl) for nl in range(1, leaves + 1))
        assert A_out >= min(most, A_tail)
        need = min(most, A_out)
        leaves += min(leaves, L - leaves, A_out)
    assert leaves == L                  # a balanced tree needs no tail
    if L == 255:
        assert plan == [8, 8, 8, 8, 8, 16, 32, 64]


def _grown(monkeypatch, X, y, lean_rows, **split_kw):
    """One tree of 63 leaves by the kernel path (interpret mode) at
    ``int8h``, the staged waves on (``lean_rows`` 0) or the single
    while-loop body at the tail's width (``None``: as shipped)."""
    from lightgbm_tpu.learner import serial
    if lean_rows is not None:
        monkeypatch.setattr(serial, "_COMPILE_LEAN_ROWS", lean_rows)
    dd = to_device(BinnedDataset.from_raw(
        X, Config.from_params({"max_bin": 63})))
    p = GrowthParams(num_leaves=63, split=SplitParams(
        min_sum_hessian_in_leaf=0.0, **split_kw))
    return build_tree(dd, jnp.asarray(-(y - y.mean()), jnp.float32),
                      jnp.ones(len(y), jnp.float32), p,
                      hist_backend="pallas", hist_mode="int8h")


@pytest.mark.parametrize("min_data,tail_splits", [(1, False), (60, True)])
def test_staged_waves_grow_the_single_bodys_tree(monkeypatch, min_data,
                                                 tail_splits):
    """The staged plan hands wave ``i`` the slots its smaller children
    need and the single body hands every wave the tail's: the same
    leaves are split in the same order, and at an int8 mode the sums are
    exact integers whatever the grid, so every array of the tree is
    equal -- where the unrolled waves finish the tree, and where leaves
    too small to split leave the rest to the tail's waves."""
    rng = np.random.RandomState(3)
    X = rng.rand(3000, 5).astype(np.float32)
    y = (np.sin(6 * X[:, 0]) + X[:, 1] * X[:, 2]
         + 0.1 * rng.randn(3000)).astype(np.float32)
    staged = _grown(monkeypatch, X, y, 0, min_data_in_leaf=min_data)
    single = _grown(monkeypatch, X, y, None, min_data_in_leaf=min_data)
    plan, _ = stage_plan(63)
    nl = int(staged.num_leaves)
    deepest = int(np.asarray(staged.leaf_depth)[:nl].max())
    # a leaf deeper than the unrolled waves is one of the tail's
    assert (deepest > len(plan)) == tail_splits and nl > 32
    assert nl == 63 or tail_splits
    for name in BuiltTree._fields:
        a, b = getattr(staged, name), getattr(single, name)
        for x, z in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(z),
                                          err_msg=name)


@pytest.mark.parametrize("name", ["auto", "pallas", "scatter", "compact"])
def test_hist_backend_names(monkeypatch, name):
    """``hist_backend`` / ``LGBM_TPU_HIST_BACKEND`` take three names.
    Any other would run the scatter learner without a word (no Pallas
    branch recognises it), so it is refused, and the message names the
    three."""
    dd = SimpleNamespace(group_max_bins=63)
    monkeypatch.delenv("LGBM_TPU_HIST_BACKEND", raising=False)
    want = {"auto": default_backend()}.get(name, name)
    for by_env in (False, True):
        if by_env:
            monkeypatch.setenv("LGBM_TPU_HIST_BACKEND", name)
        asked = "auto" if by_env else name
        if name == "compact":
            with pytest.raises(ValueError, match="auto, pallas, scatter"):
                resolve_backend(dd, 255, asked, "int8h")
        else:
            assert resolve_backend(dd, 255, asked, "int8h") == want


def test_resolve_backend_logs_each_substitution_once(caplog):
    """Whenever the resolved backend is not the one asked for, the
    choice and its ground are logged — once per distinct case, at info."""
    from lightgbm_tpu.utils.log import reset_log_once
    reset_log_once()
    dd = SimpleNamespace(group_max_bins=63)
    with caplog.at_level("INFO", logger="lightgbm_tpu"):
        # > 256 bins is outside the kernel model altogether
        for _ in range(2):
            assert resolve_backend(SimpleNamespace(group_max_bins=300),
                                   255, "pallas", "int8h") == "scatter"
        # more leaves than the route kernel's one-hot holds
        assert resolve_backend(dd, 2048, "pallas", "int8h") == "scatter"
        # the backend asked for: nothing to say
        assert resolve_backend(dd, 255, "pallas", "int8h") == "pallas"
        assert resolve_backend(dd, 255, "scatter", "int8h") == "scatter"
    msgs = [r.getMessage() for r in caplog.records
            if "histogram backend" in r.getMessage()]
    assert len(msgs) == 2, msgs
    assert all("scatter (asked for pallas)" in m for m in msgs)
    assert "300 bins" in msgs[0] and "2048 leaves" in msgs[1]


def test_hist_fold_logs_each_substitution_once(caplog):
    """The streamed fold seam's one substitution (seeded wide kernel ->
    carried f32 scatter fold, where the seeded cell is over the VMEM
    model) is logged like resolve_backend's: once, at info, with the
    ground."""
    from lightgbm_tpu.utils.log import reset_log_once
    reset_log_once()
    dd63 = SimpleNamespace(group_max_bins=63, num_groups=28, num_data=8192)
    dd255 = SimpleNamespace(group_max_bins=255, num_groups=28,
                            num_data=8192)
    with caplog.at_level("INFO", logger="lightgbm_tpu"):
        # the fold the stream asked for: nothing to say
        for mode in ("int8h", "hilo"):
            fold = make_hist_fold_fn(dd63, 255, 128, 8192, "pallas", mode)
            assert fold is not None and fold.hist_mode == mode
        # the scatter backend keeps the carried f32 fold: nothing to say
        assert make_hist_fold_fn(dd63, 255, 128, 8192, "scatter",
                                 "int8h") is None
        # the seeded wide cell at 255 bins x 128 slots is over the VMEM
        # model (and the chip's compiler refuses it): scatter fold
        for _ in range(2):
            assert make_hist_fold_fn(dd255, 255, 128, 8192, "pallas",
                                     "int8h") is None
    msgs = [r.getMessage() for r in caplog.records
            if "streamed histogram fold" in r.getMessage()]
    assert len(msgs) == 1, msgs
    assert msgs[0].startswith("streamed histogram fold: scatter (carried "
                              "f32 fold) (resolved backend pallas)")


@pytest.mark.parametrize("F,leaves,fused_calls,tail,grown", [
    # the one-chip cells' width: one tile at 128 and 256 columns, not at
    # the tail's 512
    (67, 255, [1, 2, 4, 8, 16, 32, 64], "pallas", 128),
    # a table narrower than the ids it hands out: wave 9 routes rows to
    # the new ids 256..383 from a 256-lane table, so the table's
    # precision must follow the 511 leaves' ids, not its lanes
    # (the tail fused too, its table all 512 lanes)
    (28, 511, [1, 2, 4, 8, 16, 32, 64, 128, 256], "fused", 384),
], ids=["67x255", "28x511"])
def test_fused_waves_grow_the_unfused_tree(monkeypatch, F, leaves,
                                           fused_calls, tail, grown):
    """Where the per-wave gate admits the unrolled waves, int8h, bagged
    rows and a feature mask, the tree whose routes run inside the
    histogram calls is the tree of the route kernel and the wide call,
    bit for bit: the sums are exact integers and a routed leaf is the
    route kernel's.  The unfused side is reached by the gate refusing
    every wave."""
    from lightgbm_tpu.learner import serial
    monkeypatch.setattr(serial, "_COMPILE_LEAN_ROWS", 0)
    rng = np.random.RandomState(7)
    n = 4096
    X = rng.rand(n, F).astype(np.float32)
    y = (np.sin(6 * X[:, 0]) + X[:, 1] * X[:, 2] + X[:, F - 1]
         + 0.1 * rng.randn(n)).astype(np.float32)
    dd = to_device(BinnedDataset.from_raw(
        X, Config.from_params({"max_bin": 63})))
    p = GrowthParams(num_leaves=leaves, split=SplitParams(
        min_data_in_leaf=2, min_sum_hessian_in_leaf=0.0))
    bag = jnp.asarray(rng.rand(n) < 0.8)
    fmask = jnp.asarray(np.arange(F) % 5 != 3)
    waves, _, got_tail = wave_backend_plan(
        leaves, num_groups=F, max_bins=63, mode="int8h", n_rows=n,
        any_cat=False)
    assert [w.choice for w in waves] == \
        ["pallas"] + ["fused"] * len(fused_calls)
    assert got_tail == tail

    calls, final = [], []
    fused_call = serial.hist_route_pallas
    values_call = serial.route_rows_values_pallas

    def counted(*a, **k):
        calls.append(k["route_leaves"])
        return fused_call(*a, **k)

    def kept(*a, **k):
        out = values_call(*a, **k)
        final.append(out[0])
        return out
    monkeypatch.setattr(serial, "hist_route_pallas", counted)
    monkeypatch.setattr(serial, "route_rows_values_pallas", kept)

    def grow():
        return build_tree(dd, jnp.asarray(-(y - y.mean()), jnp.float32),
                          jnp.ones(n, jnp.float32), p, bag_mask=bag,
                          feature_mask=fmask, hist_backend="pallas",
                          hist_mode="int8h")
    fused = grow()
    fused_calls = fused_calls + ([leaves] if tail == "fused" else [])
    assert calls == fused_calls
    monkeypatch.setattr(serial, "fused_config_ok", lambda *a, **k: False)
    unfused = grow()
    assert len(calls) == len(fused_calls)
    assert int(fused.num_leaves) > grown
    for name in BuiltTree._fields:
        a, b = getattr(fused, name), getattr(unfused, name)
        for x, z in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(z),
                                          err_msg=name)
    # both leaf vectors after the final route: every row's leaf, and the
    # in-bag rows' (bagged-out rows parked at -1)
    np.testing.assert_array_equal(np.asarray(final[0]),
                                  np.asarray(final[1]))
    assert (np.asarray(final[0][1, :n]) == -1).sum() == int((~bag).sum())
