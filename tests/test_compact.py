"""Leaf-compacted deep-wave histogram path (`ops/compact.py`) oracle tests.

The compacted kernel must reproduce the exact-f32 scatter oracle
BIT-exactly at deep-wave slot counts (A in {64, 128}) — dyadic-rational
grad/hess values make every f32 partial sum exact, so summation order
cannot hide a wrong row->leaf-group assignment — including bagged-out
rows, inactive leaves, `-1` active padding, and EFB/categorical-style
group columns at the 255-bin stride.  The quantized default (int8h)
accumulates in int32 and must be BIT-identical to the wide MXU kernel.
Runs in Pallas interpret mode on the CPU test mesh, like
tests/test_pallas_hist.py.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.compact import (COMPACT_GROUP, compact_config_ok,
                                      compact_plan, compact_slot_threshold,
                                      hist_active_compact)
from lightgbm_tpu.ops.pallas_histogram import (bin_stride, hist_active_pallas,
                                               hist_active_scatter,
                                               pack_values, pack_values_q,
                                               transpose_bins)


def _dyadic_data(n, F, L, max_bins, seed=7, bag_frac=0.15):
    """Synthetic rows with dyadic-rational values (multiples of 1/64,
    <= 8 mantissa bits): exact in bf16 operands AND order-independent
    in f32 accumulation, so kernel-vs-scatter comparisons are
    bit-exact."""
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, max_bins, size=(n, F)).astype(np.uint8)
    grad = (rng.randint(-128, 129, size=n) / 64.0).astype(np.float32)
    hess = (rng.randint(1, 129, size=n) / 64.0).astype(np.float32)
    row_leaf = rng.randint(0, L, size=n).astype(np.int32)
    row_leaf[rng.rand(n) < bag_frac] = -1          # bagged-out rows
    return rng, bins, grad, hess, row_leaf


def _padded_leaf(bt, row_leaf):
    n = len(row_leaf)
    return jnp.pad(jnp.asarray(row_leaf), (0, bt.shape[1] - n),
                   constant_values=-1)


@pytest.mark.parametrize("A,mode,max_bins,F", [
    (64, "hilo", 63, 8),
    (128, "hilo", 63, 8),
    (64, "bf16", 63, 8),
    (128, "bf16", 255, 10),    # 255-bin stride forces feature tiling —
    #   the EFB group-column / categorical-group shape (group columns
    #   are just wider bins to the histogram kernel)
])
def test_compact_bitexact_vs_scatter(A, mode, max_bins, F):
    n, L = 5000, 255
    rng, bins, grad, hess, row_leaf = _dyadic_data(n, F, L, max_bins)
    active = np.full(A, -1, np.int32)
    k = A - 4                                       # keep some -1 padding
    active[:k] = rng.choice(L, k, replace=False)

    bt = transpose_bins(jnp.asarray(bins))
    out_c = hist_active_compact(
        bt, pack_values(jnp.asarray(grad), jnp.asarray(hess), mode),
        _padded_leaf(bt, row_leaf), jnp.asarray(active),
        num_features=F, max_bins=max_bins, num_leaf_slots=L, mode=mode,
        interpret=True)
    out_s = hist_active_scatter(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(row_leaf), jnp.asarray(active),
        max_bins=max_bins, num_leaf_slots=L)
    c, s = np.asarray(out_c), np.asarray(out_s)
    assert c.shape == s.shape == (A, F, bin_stride(max_bins), 3)
    np.testing.assert_array_equal(c[:k], s[:k])
    # unlike the wide kernel, -1 active padding slots are exactly zero
    np.testing.assert_array_equal(c[k:], 0.0)


@pytest.mark.parametrize("A", [64, 128])
def test_compact_int8h_bitidentical_to_wide(A):
    """The default quantized mode accumulates exactly in int32, so the
    compacted and wide kernels must agree bit-for-bit — the learner can
    switch per wave without any cross-path drift."""
    n, F, L, max_bins = 4000, 6, 255, 63
    rng, bins, grad, hess, row_leaf = _dyadic_data(n, F, L, max_bins,
                                                   seed=11)
    active = np.full(A, -1, np.int32)
    k = A - 2
    active[:k] = rng.choice(L, k, replace=False)
    bt = transpose_bins(jnp.asarray(bins))
    vals, scales = pack_values_q(jnp.asarray(grad), jnp.asarray(hess),
                                 "int8h")
    leaf_p = _padded_leaf(bt, row_leaf)
    out_c = hist_active_compact(
        bt, vals, leaf_p, jnp.asarray(active), scales,
        num_features=F, max_bins=max_bins, num_leaf_slots=L, mode="int8h",
        interpret=True)
    out_w = hist_active_pallas(
        bt, vals, leaf_p, jnp.asarray(active), scales,
        num_features=F, max_bins=max_bins, mode="int8h", interpret=True)
    np.testing.assert_array_equal(np.asarray(out_c)[:k],
                                  np.asarray(out_w)[:k])


def test_compact_normal_floats_tolerance():
    """Non-dyadic values: same tolerance envelope as the wide kernel's
    oracle tests (f32 order drift only)."""
    rng = np.random.RandomState(3)
    n, F, L, A, max_bins = 6000, 9, 255, 64, 63
    bins = rng.randint(0, max_bins, size=(n, F)).astype(np.uint8)
    grad = rng.normal(size=n).astype(np.float32)
    hess = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
    row_leaf = rng.randint(-1, L, size=n).astype(np.int32)
    active = rng.choice(L, A, replace=False).astype(np.int32)
    bt = transpose_bins(jnp.asarray(bins))
    out_c = hist_active_compact(
        bt, pack_values(jnp.asarray(grad), jnp.asarray(hess), "hilo"),
        _padded_leaf(bt, row_leaf), jnp.asarray(active),
        num_features=F, max_bins=max_bins, num_leaf_slots=L, mode="hilo",
        interpret=True)
    out_s = hist_active_scatter(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(row_leaf), jnp.asarray(active),
        max_bins=max_bins, num_leaf_slots=L)
    c, s = np.asarray(out_c), np.asarray(out_s)
    np.testing.assert_array_equal(c[..., 2], s[..., 2])   # counts exact
    scale = np.abs(s[..., :2]).max() + 1e-9
    np.testing.assert_allclose(c[..., :2] / scale, s[..., :2] / scale,
                               atol=5e-4)


def test_compact_empty_and_sparse_groups_zero():
    """Active slots whose leaves hold ZERO rows (e.g. fully bagged out)
    must come back exactly zero — an unvisited output block would leak
    garbage; the plan forces >= 1 zero-initialized tile per group."""
    n, F, L, max_bins = 3000, 4, 255, 15
    rng, bins, grad, hess, row_leaf = _dyadic_data(n, F, L, max_bins,
                                                   seed=5)
    # leaves 200.. are never assigned to any row
    row_leaf = np.where(row_leaf >= 200, -1, row_leaf).astype(np.int32)
    active = np.arange(120, 248, dtype=np.int32)    # mostly empty slots
    bt = transpose_bins(jnp.asarray(bins))
    out_c = np.asarray(hist_active_compact(
        bt, pack_values(jnp.asarray(grad), jnp.asarray(hess), "hilo"),
        _padded_leaf(bt, row_leaf), jnp.asarray(active),
        num_features=F, max_bins=max_bins, num_leaf_slots=L, mode="hilo",
        interpret=True))
    out_s = np.asarray(hist_active_scatter(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(row_leaf), jnp.asarray(active),
        max_bins=max_bins, num_leaf_slots=L))
    np.testing.assert_array_equal(out_c, out_s)
    assert (out_c[active >= 200] == 0.0).all()


def test_compact_plan_layout():
    """The plan's invariants directly: stable within-group row order,
    tile-aligned group segments, monotone tile->group map, trash rows
    dropped."""
    T = 8  # tiny tile for a readable layout (plan is tile-agnostic)
    hist_leaf = jnp.asarray(
        np.array([0, 5, 0, 7, -1, 5, 9, 0], np.int32))
    active = jnp.asarray(np.array([0, 5, 7], np.int32))
    # G=32 > 3 slots: single group + trash
    src, tile_group, group_active = compact_plan(hist_leaf, active,
                                                 num_leaf_slots=16,
                                                 row_tile=T)
    src = np.asarray(src)
    # group 0 rows keep dataset order; leaf-9 and bagged rows dropped
    np.testing.assert_array_equal(src[:6], [0, 1, 2, 3, 5, 7])
    np.testing.assert_array_equal(src[6:], -1)
    assert len(src) % T == 0
    tg = np.asarray(tile_group)
    assert (np.diff(tg) >= 0).all()
    ga = np.asarray(group_active)
    assert ga.shape == (2, COMPACT_GROUP, 1)        # group 0 + trash page
    np.testing.assert_array_equal(ga[0, :3, 0], [0, 5, 7])
    assert (ga[0, 3:, 0] == -2).all()               # -2 pad: never matches


def test_compact_psum_data_parallel():
    """The 2-shard data-parallel seam: per-shard compacted histograms
    psum'd across a row-sharded mesh must equal the global scatter
    oracle — same [A, F, B, 3] collective shape and schedule as the
    wide kernel, so the spmdcheck/flight-recorder contract is
    untouched."""
    from jax.sharding import Mesh, PartitionSpec as P
    from lightgbm_tpu.parallel.learners import shard_map

    n, F, L, A, max_bins = 4096, 5, 255, 64, 63
    rng, bins, grad, hess, row_leaf = _dyadic_data(n, F, L, max_bins,
                                                   seed=13)
    active = jnp.asarray(rng.choice(L, A, replace=False).astype(np.int32))
    # row tile 1024 keeps each 2048-row shard at >= 2 tiles
    bt = transpose_bins(jnp.asarray(bins), row_tile=1024)
    vals = pack_values(jnp.asarray(grad), jnp.asarray(hess), "hilo",
                       row_tile=1024)
    leaf_p = _padded_leaf(bt, row_leaf)[None, :]

    mesh = Mesh(np.array(jax.devices()[:2]), ("d",))

    def step(bt_s, vals_s, leaf_s):
        h = hist_active_compact(
            bt_s, vals_s, leaf_s[0], active,
            num_features=F, max_bins=max_bins, num_leaf_slots=L,
            mode="hilo", row_tile=1024, interpret=True)
        return jax.lax.psum(h, "d")

    fn = shard_map(step, mesh=mesh,
                   in_specs=(P(None, "d"), P(None, "d"), P(None, "d")),
                   out_specs=P(), check_vma=False)
    out_p = np.asarray(fn(bt, vals, leaf_p))
    out_s = np.asarray(hist_active_scatter(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(row_leaf), active,
        max_bins=max_bins, num_leaf_slots=L))
    np.testing.assert_array_equal(out_p, out_s)


# ---------------------------------------------------------------------------
# dispatcher: the stage_plan-aware backend selection
# ---------------------------------------------------------------------------
def test_wave_backend_plan_selects_compact_above_threshold():
    """Seeded stage_plan dispatch: 255-leaf trees run their shallow
    unrolled waves on the wide fused kernel and their 64/128-slot waves
    (+ the while-loop tail) on the compacted path; a 31-leaf tree never
    compacts."""
    from lightgbm_tpu.learner.serial import stage_plan, wave_backend_plan
    plan, tail = stage_plan(255)
    assert plan[-1] == 128 and tail == 128
    choices, tail_choice = wave_backend_plan(255, backend="compact")
    th = compact_slot_threshold()
    for A, ch in zip(plan, choices):
        assert ch == ("compact" if A > th else "fused"), (A, ch)
    assert "compact" in choices and "fused" in choices
    assert tail_choice == "compact"
    # shallow tree: resolve_backend demotes compact outright
    choices31, tail31 = wave_backend_plan(31, backend="compact")
    assert "compact" not in choices31 and tail31 == "fused"
    # leaf-wise growth (wave_size=1) runs 8-slot waves: never compacts
    _, tail_lw = wave_backend_plan(255, wave_size=1, backend="compact")
    assert tail_lw == "fused"


@pytest.mark.parametrize("leaves", [255, 31])
def test_auto_on_a_tpu_is_the_wide_kernel_in_every_wave(monkeypatch, leaves):
    """"auto" never compacts (PR 27: on the chip the plan and regroup
    cost several times the columns they save): on a TPU it resolves to
    "pallas", whose stage plan names no "compact" wave, the 64- and
    128-slot waves and the tail included.  "compact" is reachable by
    name and still compacts exactly those."""
    from types import SimpleNamespace
    from lightgbm_tpu.learner.serial import (resolve_backend, stage_plan,
                                             wave_backend_plan)
    from lightgbm_tpu.ops.pallas_histogram import default_backend
    monkeypatch.delenv("LGBM_TPU_HIST_BACKEND", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert default_backend() == "pallas"
    dd = SimpleNamespace(group_max_bins=63)
    assert resolve_backend(dd, leaves, "auto", "int8h") == "pallas"
    plan, _ = stage_plan(leaves)
    choices, tail = wave_backend_plan(leaves, backend="pallas")
    assert "compact" not in choices + [tail]
    by_name, tail_by_name = wave_backend_plan(leaves, backend="compact")
    compacted = [A for A, ch in zip(plan, by_name) if ch == "compact"]
    if leaves == 255:
        assert compacted == [64, 128] and tail_by_name == "compact"
        assert resolve_backend(dd, leaves, "compact", "int8h") == "compact"
    else:
        assert not compacted and tail_by_name == "fused"


def test_resolve_backend_compact():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.io.device import to_device
    from lightgbm_tpu.learner.serial import resolve_backend
    rng = np.random.RandomState(0)
    ds = BinnedDataset.from_raw(rng.rand(256, 4).astype(np.float32),
                                Config.from_params({"max_bin": 63}))
    dd = to_device(ds)
    # deep trees keep the compact backend; shallow ones demote to pallas
    assert resolve_backend(dd, 255, "compact", "int8h") == "compact"
    assert resolve_backend(dd, 31, "compact", "int8h") == "pallas"
    assert compact_config_ok(63, "int8h")
    assert COMPACT_GROUP == 32


def test_hist_fn_dispatches_compact(monkeypatch):
    """make_hist_fn on the compact backend must actually call the
    compacted kernel above the slot threshold and the wide kernel at or
    below it."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.io.device import to_device
    from lightgbm_tpu.learner import serial as serial_mod
    from lightgbm_tpu.ops import compact as compact_mod

    rng = np.random.RandomState(1)
    X = rng.rand(2100, 4).astype(np.float32)
    ds = BinnedDataset.from_raw(X, Config.from_params({"max_bin": 63}))
    dd = to_device(ds)
    g = jnp.asarray(rng.normal(size=len(X)).astype(np.float32))
    h = jnp.ones(len(X), jnp.float32)

    calls = []
    real = compact_mod.hist_active_compact

    def spy(*a, **kw):
        calls.append(kw.get("interpret"))
        return real(*a, **kw)

    monkeypatch.setattr(compact_mod, "hist_active_compact", spy)
    hist_fn = serial_mod.make_hist_fn(dd, g, h, num_leaf_slots=255,
                                      backend="compact", hist_mode="hilo")
    leaf = jnp.zeros(len(X), jnp.int32)
    deep = jnp.arange(64, dtype=jnp.int32)          # above threshold
    shallow = jnp.arange(8, dtype=jnp.int32)        # below threshold
    out = hist_fn(leaf, deep)
    assert len(calls) == 1 and out.shape[0] == 64
    out = hist_fn(leaf, shallow)
    assert len(calls) == 1 and out.shape[0] == 8    # wide kernel used


# ---------------------------------------------------------------------------
# full-tree equivalence: compact backend == wide pallas backend
# ---------------------------------------------------------------------------
def test_build_tree_compact_matches_pallas_int8h():
    """A full deep tree (127 leaves -> 64-slot tail waves) built on the
    compact backend is BIT-identical to the wide pallas backend under
    the exact-int32 int8h mode — the parent-subtraction/smaller-child
    bookkeeping (apply_hist_wave) and split scan see identical
    histograms, so every decision matches.  Categorical feature
    included so the routed categorical path is exercised too."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.io.device import to_device
    from lightgbm_tpu.learner.serial import (GrowthParams, SplitParams,
                                             build_tree)
    rng = np.random.RandomState(2)
    n = 4000
    X = rng.rand(n, 5).astype(np.float32)
    X[:, 4] = rng.randint(0, 9, size=n)             # categorical column
    y = (np.sin(7 * X[:, 0]) + X[:, 1] * X[:, 2]
         + 0.3 * (X[:, 4] == 3) + 0.1 * rng.randn(n)).astype(np.float32)
    cfg = Config.from_params({"max_bin": 63})
    ds = BinnedDataset.from_raw(X, cfg, categorical_features=[4])
    dd = to_device(ds)
    grad = jnp.asarray(-(y - y.mean()), jnp.float32)
    hess = jnp.ones(n, jnp.float32)
    p = GrowthParams(num_leaves=127,
                     split=SplitParams(min_data_in_leaf=3,
                                       min_sum_hessian_in_leaf=0.0))
    trees = {}
    for backend in ("pallas", "compact"):
        trees[backend] = jax.tree.map(
            np.asarray, build_tree(dd, grad, hess, p,
                                   hist_backend=backend,
                                   hist_mode="int8h"))
    a, b = trees["pallas"], trees["compact"]
    assert int(a.num_leaves) > 64, "tree too shallow to hit deep waves"
    assert int(a.num_leaves) == int(b.num_leaves)
    np.testing.assert_array_equal(a.row_leaf, b.row_leaf)
    np.testing.assert_array_equal(a.feature, b.feature)
    np.testing.assert_array_equal(a.threshold_bin, b.threshold_bin)
    np.testing.assert_array_equal(a.leaf_value, b.leaf_value)
    np.testing.assert_array_equal(a.leaf_count, b.leaf_count)


def test_resolve_backend_logs_each_substitution_once(caplog):
    """Whenever the resolved backend is not the one asked for, the
    choice and its ground are logged — once per distinct case, at info."""
    from types import SimpleNamespace
    from lightgbm_tpu.learner.serial import resolve_backend
    from lightgbm_tpu.utils.log import reset_log_once
    reset_log_once()
    dd = SimpleNamespace(group_max_bins=63)
    with caplog.at_level("INFO", logger="lightgbm_tpu"):
        # 31 leaves never reach the compaction threshold
        assert resolve_backend(dd, 31, "compact", "int8h") == "pallas"
        assert resolve_backend(dd, 31, "compact", "int8h") == "pallas"
        # > 256 bins is outside the kernel model altogether
        assert resolve_backend(SimpleNamespace(group_max_bins=300), 255,
                               "compact", "int8h") == "scatter"
        # the backend asked for: nothing to say
        assert resolve_backend(dd, 255, "compact", "int8h") == "compact"
    msgs = [r.getMessage() for r in caplog.records
            if "histogram backend" in r.getMessage()]
    assert len(msgs) == 2, msgs
    assert "pallas (asked for compact)" in msgs[0]
    assert "scatter (asked for compact)" in msgs[1]


def test_hist_fold_logs_each_substitution_once(caplog):
    """The streamed fold seam's own substitutions (compact -> wide
    kernel, kernel -> carried scatter fold) are logged like
    resolve_backend's: once per distinct case, at info, with the
    ground."""
    from types import SimpleNamespace
    from lightgbm_tpu.learner.serial import make_hist_fold_fn
    from lightgbm_tpu.utils.log import reset_log_once
    reset_log_once()
    dd63 = SimpleNamespace(group_max_bins=63, num_groups=28, num_data=8192)
    dd255 = SimpleNamespace(group_max_bins=255, num_groups=28,
                            num_data=8192)
    with caplog.at_level("INFO", logger="lightgbm_tpu"):
        # the fold the stream asked for: nothing to say
        fold = make_hist_fold_fn(dd63, 255, 128, 8192, "compact", "int8h")
        assert fold.backend == "compact"
        # float compact chains are inexact: the wide kernel folds instead
        for _ in range(2):
            fold = make_hist_fold_fn(dd63, 255, 128, 8192, "compact",
                                     "hilo")
            assert fold.backend == "pallas"
        # the seeded wide cell at 255 bins x 128 slots is over the VMEM
        # model (and the chip's compiler refuses it): scatter fold
        assert make_hist_fold_fn(dd255, 255, 128, 8192, "pallas",
                                 "hilo") is None
    msgs = [r.getMessage() for r in caplog.records
            if "streamed histogram fold" in r.getMessage()]
    assert len(msgs) == 2, msgs
    assert msgs[0].startswith("streamed histogram fold: pallas (resolved "
                              "backend compact)")
    assert msgs[1].startswith("streamed histogram fold: scatter")
