"""Pallas histogram kernel vs XLA scatter oracle.

The analog of the reference's GPU_DEBUG_COMPARE CPU-vs-GPU histogram
check (`/root/reference/src/treelearner/gpu_tree_learner.cpp:1020-1043`):
the MXU one-hot-matmul kernel must reproduce the exact-f32 scatter within
hi/lo-bf16 tolerance, with exact counts.  Runs in Pallas interpret mode so
it works on the CPU test mesh.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.pallas_histogram import (
    bin_stride, hist_active_pallas, hist_active_scatter, pack_values,
    pack_values_q, transpose_bins)


@pytest.mark.parametrize("max_bins,F,mode", [
    (63, 28, "hilo"),
    (63, 28, "bf16"),
    (255, 10, "hilo"),  # forces feature tiling (acc VMEM budget)
])
def test_kernel_matches_scatter(max_bins, F, mode):
    rng = np.random.RandomState(7)
    n, L, A = 3000, 31, 15
    bins = rng.randint(0, max_bins, size=(n, F)).astype(np.uint8)
    grad = rng.normal(size=n).astype(np.float32)
    hess = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
    # include bagged-out rows (-1) and leaves not in the active list
    row_leaf = rng.randint(-1, L, size=n).astype(np.int32)
    active = np.full(A, -1, np.int32)
    active[:10] = rng.choice(L, 10, replace=False)

    bins_j = jnp.asarray(bins)
    bt = transpose_bins(bins_j)
    vals = pack_values(jnp.asarray(grad), jnp.asarray(hess), mode)
    out_p = hist_active_pallas(
        bt, vals, jnp.asarray(row_leaf), jnp.asarray(active),
        num_features=F, max_bins=max_bins, mode=mode, interpret=True)
    out_s = hist_active_scatter(
        bins_j, jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(row_leaf), jnp.asarray(active),
        max_bins=max_bins, num_leaf_slots=L)
    p = np.asarray(out_p)[:10]
    s = np.asarray(out_s)[:10]
    assert p.shape == s.shape == (10, F, bin_stride(max_bins), 3)
    # counts are exact in any mode (0/1 one-hot, f32 accumulate)
    np.testing.assert_array_equal(p[..., 2], s[..., 2])
    # hilo carries BOTH value columns as hi/lo pairs (~f32); bf16 and
    # hhilo (plain-bf16 gradient column) are bf16-grade on grad sums
    tol = 5e-4 if mode == "hilo" else 2e-2
    scale = np.abs(s[..., :2]).max() + 1e-9
    np.testing.assert_allclose(p[..., :2] / scale, s[..., :2] / scale,
                               atol=tol)


@pytest.mark.parametrize("mode", ["int8", "int8h"])
def test_kernel_int8_matches_scatter(mode):
    """Quantized (int8 MXU) path vs the exact scatter oracle: counts are
    exact (int32 accumulation of a 0/1 one-hot); grad/hess sums agree to
    quantization tolerance — per-row step is max|x|/127, so a leaf-bin
    cell of m rows is within ~m * step / 2 of exact."""
    rng = np.random.RandomState(7)
    n, F, L, A, max_bins = 3000, 6, 31, 15, 63
    bins = rng.randint(0, max_bins, size=(n, F)).astype(np.uint8)
    grad = rng.normal(size=n).astype(np.float32)
    hess = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
    row_leaf = rng.randint(-1, L, size=n).astype(np.int32)
    active = np.full(A, -1, np.int32)
    active[:10] = rng.choice(L, 10, replace=False)

    vals, scales = pack_values_q(jnp.asarray(grad), jnp.asarray(hess), mode)
    assert vals.dtype == jnp.int8
    out_p = hist_active_pallas(
        transpose_bins(jnp.asarray(bins)), vals,
        jnp.asarray(row_leaf), jnp.asarray(active), scales,
        num_features=F, max_bins=max_bins, mode=mode, interpret=True)
    out_s = hist_active_scatter(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(row_leaf), jnp.asarray(active),
        max_bins=max_bins, num_leaf_slots=L)
    p, s = np.asarray(out_p)[:10], np.asarray(out_s)[:10]
    np.testing.assert_array_equal(p[..., 2], s[..., 2])   # counts exact
    # per-cell quantization bound: m rows, half-step each
    step_g = float(np.abs(grad).max()) / 127.0
    step_h = float(np.abs(hess).max()) / 127.0
    if mode == "int8h":
        step_h /= 127.0
    m = s[..., 2]
    assert np.all(np.abs(p[..., 0] - s[..., 0]) <= (m + 1) * step_g / 2)
    assert np.all(np.abs(p[..., 1] - s[..., 1]) <= (m + 1) * step_h / 2)


@pytest.mark.parametrize("A", [8, 64])
@pytest.mark.parametrize("mode", ["int8h", "hilo"])
def test_criteo_width_runs_the_cells_tiles(mode, A):
    """F = 67 at 63 bins, the benchmark cells' width, at a 128- and a
    256-column wave: the grid `ops/vmem.hist_tiling` picks here is the
    one it picks at the cells' 13.28M rows, so the tiles the cells run
    are tiles a test runs.  int8h: every int32 code sum equals the
    scatter oracle's sum of the same codes; hilo: at its tolerance."""
    from lightgbm_tpu.ops.vmem import col_layout, hist_tiling
    rng = np.random.RandomState(67)
    n, F, L, max_bins = 3000, 67, 255, 63
    bins = rng.randint(0, max_bins, size=(n, F)).astype(np.uint8)
    grad = rng.normal(size=n).astype(np.float32)
    hess = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
    row_leaf = jnp.asarray(rng.randint(-1, L, size=n).astype(np.int32))
    active = jnp.asarray(rng.choice(L, A, replace=False).astype(np.int32))
    bins_j = jnp.asarray(bins)
    bt = transpose_bins(bins_j)
    C, _, cols = col_layout(A, mode)
    grid = hist_tiling(F, bt.shape[1], 64, cols, C, mode, 2048)
    assert grid == hist_tiling(F, 13_281_280, 64, cols, C, mode, 2048)
    assert grid[2] <= 72
    if mode == "int8h":     # the whole set in one tile: nothing padded
        assert grid == ((2048, 67, 67) if A == 8 else (1024, 67, 67))

    def scatter(g, h):
        return np.asarray(hist_active_scatter(
            bins_j, jnp.asarray(g), jnp.asarray(h), row_leaf, active,
            max_bins=max_bins, num_leaf_slots=L))

    if mode == "int8h":
        vals, _ = pack_values_q(jnp.asarray(grad), jnp.asarray(hess), mode)
        # scales=None: the [A, F, B, 4] int32 code sums as they are
        p = np.asarray(hist_active_pallas(
            bt, vals, row_leaf, active, None, num_features=F,
            max_bins=max_bins, mode=mode, interpret=True))
        codes = np.asarray(vals)[:, :n].astype(np.float32)
        s01, s2 = scatter(codes[0], codes[1]), scatter(codes[2], codes[3])
        want = np.stack([s01[..., 0], s01[..., 1], s2[..., 0],
                         s01[..., 2]], axis=-1)
        np.testing.assert_array_equal(p, want.astype(np.int32))
    else:
        vals = pack_values(jnp.asarray(grad), jnp.asarray(hess), mode)
        p = np.asarray(hist_active_pallas(
            bt, vals, row_leaf, active, num_features=F,
            max_bins=max_bins, mode=mode, interpret=True))
        s = scatter(grad, hess)
        np.testing.assert_array_equal(p[..., 2], s[..., 2])
        scale = np.abs(s[..., :2]).max() + 1e-9
        np.testing.assert_allclose(p[..., :2] / scale, s[..., :2] / scale,
                                   atol=5e-4)


@pytest.mark.parametrize("A,old_grid,new_grid", [
    (8, (1024, 67, 67), (2048, 67, 67)),    # the 128-column calls
    (32, (1024, 67, 67), (2048, 67, 67)),
    (64, (2048, 24, 72), (1024, 67, 67)),   # the 256-column call
])
def test_int8h_sums_do_not_depend_on_the_grid(monkeypatch, A, old_grid,
                                              new_grid):
    """The raw int32 accumulator of an int8h call at the cells' width,
    on the grid the cells ran through PR 33 and on the one they run
    since PR 34 (the one-hot counted at its own byte): the same integers
    are summed, so every cell is equal bit for bit.  Where the whole
    feature set fits at both row tiles the old grid is asked for
    through ``row_tile``; the feature-tiled one is handed to the
    untraced function in the rule's place."""
    from lightgbm_tpu.ops import pallas_histogram as ph
    from lightgbm_tpu.ops.vmem import col_layout, hist_tiling
    rng = np.random.RandomState(34)
    n, F, L, max_bins = 4000, 67, 255, 63
    bt = transpose_bins(
        jnp.asarray(rng.randint(0, max_bins, size=(n, F)), jnp.uint8))
    vals, _ = pack_values_q(
        jnp.asarray(rng.normal(size=n).astype(np.float32)),
        jnp.asarray(rng.uniform(0.1, 1.0, size=n).astype(np.float32)),
        "int8h")
    row_leaf = jnp.asarray(rng.randint(-1, L, size=n).astype(np.int32))
    active = jnp.asarray(rng.choice(L, A, replace=False).astype(np.int32))
    C, _, cols = col_layout(A, "int8h")
    assert hist_tiling(F, bt.shape[1], 64, cols, C, "int8h",
                       2048) == new_grid
    kw = dict(num_features=F, max_bins=max_bins, mode="int8h",
              interpret=True, raw=True)
    new = np.asarray(hist_active_pallas(bt, vals, row_leaf, active, **kw))
    assert new.dtype == np.int32 and new.shape == (F * 64, cols)
    assert new.any()
    if old_grid[1] == F:
        assert hist_tiling(F, bt.shape[1], 64, cols, C, "int8h",
                           1024) == old_grid
        old = hist_active_pallas(bt, vals, row_leaf, active, row_tile=1024,
                                 **kw)
    else:
        # untraced, so no cached program ever holds the forced grid
        monkeypatch.setattr(ph, "hist_tiling", lambda *a, **k: old_grid)
        old = hist_active_pallas.__wrapped__(bt, vals, row_leaf, active,
                                             **kw)
        assert old.shape == (old_grid[2] * 64, cols)
    np.testing.assert_array_equal(np.asarray(old)[:F * 64], new)


def test_tiling_gauges_are_the_kernels_grids(monkeypatch):
    """`GBDT._record_tiling`'s gauges against what `hist_tiling` returns
    for the arguments the kernels themselves hand it while a tree of the
    cells' plan is traced (staged waves, then the tail: a data set this
    small would otherwise trace the tail alone), and `hist.fused_waves`
    against the waves the trace hands the fused call.  Traced, not compiled:
    the grid is chosen while the call is traced."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    from lightgbm_tpu.learner import serial
    from lightgbm_tpu.ops import pallas_histogram as ph
    monkeypatch.setenv("LGBM_TPU_HIST_BACKEND", "pallas")
    monkeypatch.setattr(serial, "_COMPILE_LEAN_ROWS", 0)
    seen = {}

    def recording(F_pad, n_pad, B, cols, C, mode, *rest, **kw):
        grid = rule(F_pad, n_pad, B, cols, C, mode, *rest, **kw)
        seen[cols] = f"{grid[1]}x{grid[0]}"
        assert mode == "int8h" and (F_pad, B) == (67, 64)
        return grid

    rule = ph.hist_tiling
    monkeypatch.setattr(ph, "hist_tiling", recording)
    fused_calls = []
    fused = serial.hist_route_pallas
    monkeypatch.setattr(serial, "hist_route_pallas", lambda *a, **k: (
        fused_calls.append(k["route_leaves"]) or fused(*a, **k)))
    rng = np.random.RandomState(5)
    X = rng.normal(size=(5000, 67)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    was_on = obs.enabled()
    obs.reset()
    obs.enable()
    try:
        g = lgb.Booster({"objective": "binary", "num_leaves": 255,
                         "max_bin": 63, "hist_mode": "int8h",
                         "verbose": -1},
                        lgb.Dataset(X, label=y,
                                    params={"max_bin": 63}))._gbdt
        gauges = obs.summary()["gauges"]
    finally:
        if not was_on:
            obs.disable()
        obs.reset()
    rows = jax.ShapeDtypeStruct((5000,), jnp.float32)
    jax.eval_shape(
        lambda grad, hess: serial.build_tree(
            g.device_data, grad, hess, g.growth, hist_mode="int8h"),
        rows, rows)
    tiling = {int(k.rsplit(".", 1)[1]): v for k, v in gauges.items()
              if k.startswith("hist.tiling.")}
    assert tiling == {128: "67x2048", 256: "67x1024", 512: "24x2048"}
    assert seen == tiling
    # the waves the trace fused are the gauge's: 1-7, the tail not
    assert gauges["hist.fused_waves"] == "1,2,3,4,5,6,7"
    assert fused_calls == [1, 2, 4, 8, 16, 32, 64]


@pytest.mark.parametrize("mode,max_bins,F,A", [
    (*shape, A) for A in (64, 128) for shape in [
        ("int8h", 63, 67),  # the benchmark cells' 256- and 512-column calls
        ("int8h", 255, 10),     # 255-bin stride: the smallest feature tile
        ("int8", 63, 28),
        ("int8hh", 63, 28),
        ("hilo", 63, 28),
        ("hhilo", 255, 10),
        ("bf16", 63, 28),
    ]] + [("int8h", 63, 28, 8), ("int8h", 63, 28, 32)])  # chip_smoke.py's
def test_wide_kernel_deep_waves_match_scatter(mode, max_bins, F, A):
    """The 64- and 128-slot waves of a 255-leaf tree (half the kernel
    time of the benchmark's cells), and the HIGGS width `chip_smoke.py`
    trains at in a first and a 32-slot wave, against the scatter oracle:
    bagged-out rows, leaves no wave asked for, ``-1`` padding slots,
    slots whose leaf holds no row, and a last row tile that the rows do
    not fill.  A quantised mode's int32 code sums equal the oracle's
    sums of the same codes bit for bit; a float mode's sums hold the
    tolerances above, with exact counts."""
    from lightgbm_tpu.ops.pallas_histogram import (is_quantized,
                                                   pallas_config_ok)
    assert pallas_config_ok(max_bins, 255, mode)
    rng = np.random.RandomState(31)
    n, L, k = 3000, 255, A - 4
    bins_j = jnp.asarray(rng.randint(0, max_bins, size=(n, F)), jnp.uint8)
    grad = rng.normal(size=n).astype(np.float32)
    hess = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
    row_leaf = rng.randint(-1, L, size=n).astype(np.int32)
    row_leaf[row_leaf >= 200] = -1          # leaves 200.. hold no row
    active = np.full(A, -1, np.int32)
    active[:k] = rng.permutation(100 + np.arange(k) * max(1, 150 // k))
    empty = active[:k] >= 200
    assert empty.any() and not empty.all()
    bt = transpose_bins(bins_j)
    assert bt.shape[1] > n
    row_leaf_j, active_j = jnp.asarray(row_leaf), jnp.asarray(active)

    def scatter(g, h):
        return np.asarray(hist_active_scatter(
            bins_j, jnp.asarray(g), jnp.asarray(h), row_leaf_j, active_j,
            max_bins=max_bins, num_leaf_slots=L))[:k]

    if is_quantized(mode):
        vals, _ = pack_values_q(jnp.asarray(grad), jnp.asarray(hess), mode)
        # scales=None: the [A, F, B, C] int32 code sums as they are
        p = np.asarray(hist_active_pallas(
            bt, vals, row_leaf_j, active_j, None, num_features=F,
            max_bins=max_bins, mode=mode, interpret=True))[:k]
        # one oracle pass a code column; the last column is the count
        codes = np.asarray(vals)[:-1, :n].astype(np.float32)
        sums = [scatter(c, c) for c in codes]
        want = np.stack([s[..., 0] for s in sums] + [sums[0][..., 2]],
                        axis=-1)
        assert p.dtype == np.int32 and p.shape == want.shape
        np.testing.assert_array_equal(p, want.astype(np.int32))
        assert np.abs(p[~empty]).max() > 0
    else:
        vals = pack_values(jnp.asarray(grad), jnp.asarray(hess), mode)
        p = np.asarray(hist_active_pallas(
            bt, vals, row_leaf_j, active_j, num_features=F,
            max_bins=max_bins, mode=mode, interpret=True))[:k]
        s = scatter(grad, hess)
        assert p.shape == s.shape == (k, F, bin_stride(max_bins), 3)
        np.testing.assert_array_equal(p[..., 2], s[..., 2])
        tol = 5e-4 if mode == "hilo" else 2e-2
        scale = np.abs(s[..., :2]).max() + 1e-9
        np.testing.assert_allclose(p[..., :2] / scale, s[..., :2] / scale,
                                   atol=tol)
    np.testing.assert_array_equal(p[empty], 0)


def test_hilo_split_survives_jit():
    """Regression: the hi/lo split must be done by bit-masking — XLA's
    simplifier folds ``x.astype(bf16).astype(f32)`` to a no-op under
    jit, which silently collapsed hilo mode to plain bf16 AND rounded
    the route-emitted leaf values (≈0.006 AUC drift at 500 iterations
    against the exact scatter path before the fix)."""
    from lightgbm_tpu.ops.pallas_histogram import split_hi_lo
    rng = np.random.RandomState(0)
    g = jnp.asarray(rng.normal(size=4096).astype(np.float32))
    v = np.asarray(jax.jit(lambda a: pack_values(a, a, "hilo"))(g))
    lo = v[1][:4096]
    assert (lo != 0).mean() > 0.99          # folded split would be all-0
    hi = v[0][:4096]
    # hi exactly bf16-representable: MXU operand rounding keeps it intact
    np.testing.assert_array_equal(
        hi, hi.astype(jnp.bfloat16).__array__().astype(np.float32))
    np.testing.assert_array_equal(hi + lo, np.asarray(g))
    # the jitted helper itself
    h2, l2 = jax.jit(split_hi_lo)(g)
    np.testing.assert_array_equal(np.asarray(h2) + np.asarray(l2),
                                  np.asarray(g))
    assert (np.asarray(l2) != 0).mean() > 0.99


def test_hilo_hist_accuracy_vs_exact():
    """hilo histograms must be ~f32-accurate (not bf16-grade): compare
    against an exact float64 host histogram at a size where the two
    regimes differ by two orders of magnitude."""
    rng = np.random.RandomState(1)
    n, F, B = 20000, 4, 64
    bins = rng.randint(0, 63, size=(n, F)).astype(np.uint8)
    grad = rng.normal(size=n).astype(np.float32)
    hess = rng.uniform(0.1, 0.3, size=n).astype(np.float32)
    exact = np.zeros((F, B))
    for f in range(F):
        exact[f] = np.bincount(bins[:, f], weights=grad.astype(np.float64),
                               minlength=B)[:B]
    leaf = jnp.zeros(n, jnp.int32)
    active = jnp.full(8, -1, jnp.int32).at[0].set(0)
    vals = pack_values(jnp.asarray(grad), jnp.asarray(hess), "hilo")
    hp = np.asarray(hist_active_pallas(
        transpose_bins(jnp.asarray(bins)), vals, leaf, active,
        num_features=F, max_bins=63, mode="hilo",
        interpret=True))[0][..., 0]
    rel = np.abs(hp - exact).max() / np.abs(exact).max()
    assert rel < 5e-5, rel                  # bf16-grade would be ~1e-3


def test_scatter_drops_inactive_and_padding():
    rng = np.random.RandomState(3)
    n, F, L = 500, 4, 7
    max_bins = 15
    bins = rng.randint(0, max_bins, size=(n, F)).astype(np.uint8)
    grad = rng.normal(size=n).astype(np.float32)
    hess = np.ones(n, np.float32)
    row_leaf = rng.randint(0, L, size=n).astype(np.int32)
    active = np.array([3, -1, 5], np.int32)
    out = np.asarray(hist_active_scatter(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(row_leaf), jnp.asarray(active),
        max_bins=max_bins, num_leaf_slots=L))
    # slot 0 == leaf 3, slot 2 == leaf 5; counts match the leaf sizes
    for slot, leaf in ((0, 3), (2, 5)):
        expect = float((row_leaf == leaf).sum())
        assert out[slot, 0, :, 2].sum() == expect
    # padding slot accumulates nothing from in-bag rows
    assert out[1].sum() == 0.0


def test_hist_kernel_small_A_staged():
    """Adaptive column layout: small active lists must match the scatter
    oracle too (the staged wave plan exercises A = 8, 16, 32...)."""
    rng = np.random.RandomState(11)
    n, F, L, max_bins = 2000, 9, 63, 63
    bins = rng.randint(0, max_bins, size=(n, F)).astype(np.uint8)
    grad = rng.normal(size=n).astype(np.float32)
    hess = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
    row_leaf = rng.randint(-1, L, size=n).astype(np.int32)
    for A in (1, 8, 24):
        active = np.full(A, -1, np.int32)
        k = min(A, 6)
        active[:k] = rng.choice(L, k, replace=False)
        out_p = hist_active_pallas(
            transpose_bins(jnp.asarray(bins)),
            pack_values(jnp.asarray(grad), jnp.asarray(hess), "hilo"),
            jnp.asarray(row_leaf), jnp.asarray(active),
            num_features=F, max_bins=max_bins, interpret=True)
        out_s = hist_active_scatter(
            jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(row_leaf), jnp.asarray(active),
            max_bins=max_bins, num_leaf_slots=L)
        p, s = np.asarray(out_p)[:k], np.asarray(out_s)[:k]
        np.testing.assert_array_equal(p[..., 2], s[..., 2])
        scale = np.abs(s[..., :2]).max() + 1e-9
        np.testing.assert_allclose(p[..., :2] / scale, s[..., :2] / scale,
                                   atol=5e-4)


def test_route_kernel_matches_xla():
    """Pallas route kernel vs the XLA oracle, covering numerical splits,
    missing-value default directions, categorical masks, unselected
    leaves, bagged-out rows, and padding."""
    from lightgbm_tpu.ops.pallas_route import (route_rows_pallas,
                                               route_rows_xla)
    from lightgbm_tpu.io.binning import MISSING_NAN, MISSING_NONE, MISSING_ZERO

    rng = np.random.RandomState(5)
    n, F, L, B = 3000, 6, 31, 64
    max_bins = 63
    bins = rng.randint(0, max_bins, size=(n, F)).astype(np.uint8)
    row_leaf = rng.randint(0, L, size=n).astype(np.int32)
    hist_leaf = np.where(rng.rand(n) < 0.8, row_leaf, -1).astype(np.int32)

    feature = rng.randint(0, F, size=L).astype(np.int32)
    threshold = rng.randint(0, max_bins - 1, size=L).astype(np.int32)
    default_left = rng.rand(L) < 0.5
    is_cat = rng.rand(L) < 0.3
    cat_mask = rng.rand(L, B) < 0.5
    sel = rng.rand(L) < 0.6
    new_id = rng.randint(0, L, size=L).astype(np.int32)
    missing_types = rng.choice(
        [MISSING_NONE, MISSING_NAN, MISSING_ZERO], size=F).astype(np.int32)
    nan_bins = np.where(missing_types == MISSING_NAN, max_bins - 1,
                        -1).astype(np.int32)
    default_bins = rng.randint(0, 3, size=F).astype(np.int32)

    bins_j = jnp.asarray(bins)
    bt = transpose_bins(bins_j)
    n_pad = bt.shape[1]
    leaf2 = np.full((2, n_pad), -1, np.int32)
    leaf2[0, :n] = row_leaf
    leaf2[1, :n] = hist_leaf
    leaf2 = jnp.asarray(leaf2)

    args = (jnp.asarray(feature), jnp.asarray(threshold),
            jnp.asarray(default_left), jnp.asarray(is_cat),
            jnp.asarray(cat_mask), jnp.asarray(sel), jnp.asarray(new_id),
            jnp.asarray(missing_types), jnp.asarray(nan_bins),
            jnp.asarray(default_bins),
            jnp.arange(F, dtype=jnp.int32),          # identity groups
            jnp.full(F, -1, jnp.int32),
            jnp.full(F, max_bins, jnp.int32))
    out_p = np.asarray(route_rows_pallas(bt, leaf2, *args, interpret=True))
    out_x = np.asarray(route_rows_xla(bins_j, leaf2, *args))
    np.testing.assert_array_equal(out_p[:, :n], out_x[:, :n])
    # hist_leaf stays parked at -1 for bagged-out rows
    assert (out_p[1, :n][hist_leaf < 0] == -1).all()

    # the values-emitting variant: same routing + per-row leaf values
    # selected by the POST-route leaf (the score-update gather replacement)
    from lightgbm_tpu.ops.pallas_route import route_rows_values_pallas
    leaf_values = rng.normal(scale=0.3, size=L).astype(np.float32)
    out_v, vals = route_rows_values_pallas(
        bt, leaf2, *args, jnp.asarray(leaf_values), interpret=True)
    out_v, vals = np.asarray(out_v), np.asarray(vals)
    np.testing.assert_array_equal(out_v[:, :n], out_x[:, :n])
    expect = leaf_values[out_x[0, :n]]
    np.testing.assert_allclose(vals[:n], expect, rtol=0, atol=2e-5)
    # padding rows (leaf -1) emit exactly 0
    assert (vals[n:] == 0.0).all()


@pytest.mark.parametrize("mode,bagged", [("int8h", False), ("int8", False),
                                         ("int8hh", False),
                                         ("int8h", True)])
def test_quantized_leaf_values_are_their_own_rows_sums(mode, bagged):
    """A tree histogrammed in int8 codes takes its root totals from the
    same codes: every leaf's value is then the value of its own rows'
    sums, off by no more than those rows' rounding (``scale / 254``
    each).  With the root totals from the exact f32 gradients the
    rounding bias of ALL rows — two gradient values here, so one sign per
    class, as in a binary model's first tree — went down the
    ``parent - sibling`` side of every split and ended in one small
    leaf (a value off by ~2 on this data; -8 and +113 at a million rows
    on the chip)."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.io.device import to_device
    from lightgbm_tpu.learner.serial import GrowthParams, build_tree
    from lightgbm_tpu.ops.split import SplitParams
    rng = np.random.RandomState(0)
    n = 8192
    X = rng.normal(size=(n, 4)).astype(np.float32)
    y = (X[:, 0] * 2 + X[:, 1] + rng.normal(size=n) > 0.8)
    p0 = float(y.mean())
    g = (p0 - y).astype(np.float32)
    h = np.full(n, p0 * (1.0 - p0), np.float32)
    bag = rng.rand(n) < 0.7 if bagged else np.ones(n, bool)
    dd = to_device(BinnedDataset.from_raw(
        X, Config.from_params({"max_bin": 63})))
    gp = GrowthParams(num_leaves=31,
                      split=SplitParams(min_data_in_leaf=20,
                                        min_sum_hessian_in_leaf=1e-3))
    t = jax.tree.map(np.asarray, build_tree(
        dd, jnp.asarray(g), jnp.asarray(h), gp,
        bag_mask=jnp.asarray(bag) if bagged else None,
        hist_backend="pallas", hist_mode=mode))
    nl = int(t.num_leaves)
    assert nl == 31
    sg, sh = float(np.abs(g).max()), float(np.abs(h).max())
    for leaf in range(nl):
        rows = (t.row_leaf == leaf) & bag
        cnt = int(rows.sum())
        assert cnt == t.leaf_count[leaf]
        G = float(g[rows].astype(np.float64).sum())
        H = float(h[rows].astype(np.float64).sum())
        dG, dH = cnt * sg / 254.0, cnt * sh / 254.0
        exact = -G / H
        bound = (dG + abs(exact) * dH) / (H - dH) + 1e-5
        assert abs(t.leaf_value[leaf] - exact) <= bound, (
            leaf, cnt, t.leaf_value[leaf], exact, bound)
