"""The rule that chooses a histogram kernel call's grid
(`ops/vmem.hist_tiling`): pure integer arithmetic, held over the widths
the repo trains at (HIGGS 28, Criteo 67, MSLR 136, Expo-like 968,
Epsilon 2,000) at every wave's slot count.

The rule before PR 29 (the largest feature tile that fits, at the row
tile the minimum tile of 8 admits) stays here as the reference: the new
rule may never contract more padded features than it did, nor take
longer by the model it chooses by.

Since PR 34 the footprint a grid is chosen under counts the one-hot and
the weighted values at the size the call's mode builds them in (int8:
one byte; the bf16 modes: two): `test_grid_by_element_size` pins the
grids that moved and the ones that must not.
"""
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops import vmem
from lightgbm_tpu.ops.vmem import (VMEM_BUDGET_BYTES, bin_stride,
                                   cell_vmem_bytes, col_layout,
                                   feat_tile_cap, hist_call_fs,
                                   hist_tiling, round_up, row_tiles)

N_PAD = 13_281_280          # the Criteo cells' shard, padded to 2,048
ROW_TILE = 2048
VALUE_ROWS = {"int8h": 4, "hilo": 5}


def old_rule(F_pad, n_pad, B, cols, C, mode, requested, seeded):
    """``-> (T, feat_tile, F_grid)`` as `pick_row_tile` + `feat_tiling`
    chose them through PR 28."""
    T = requested
    while T > 1024 and (
            n_pad % T != 0
            or cell_vmem_bytes(8, B, cols, T, C, mode, seeded)
            > VMEM_BUDGET_BYTES):
        T //= 2
    cap = feat_tile_cap(B, cols, T, C, mode, seeded)
    ft = F_pad if cap >= F_pad else max(8, (cap // 8) * 8)
    return T, ft, round_up(F_pad, ft)


@pytest.mark.parametrize("seeded", [False, True], ids=["plain", "seeded"])
@pytest.mark.parametrize("mode", ["int8h", "hilo"])
@pytest.mark.parametrize("A", [8, 32, 64, 128])
@pytest.mark.parametrize("max_bin", [15, 63, 255])
@pytest.mark.parametrize("F_pad", [8, 28, 67, 72, 136, 968, 2000])
def test_chosen_grid(F_pad, max_bin, A, mode, seeded):
    B = bin_stride(max_bin)
    C, _, cols = col_layout(A, mode)
    T, ft, F_grid = hist_tiling(F_pad, N_PAD, B, cols, C, mode, ROW_TILE,
                                seeded)

    # a grid the kernel can run
    assert N_PAD % T == 0 and 1024 <= T <= ROW_TILE
    assert ft == F_pad or ft % 8 == 0
    assert F_grid % ft == 0 and F_pad <= F_grid < F_pad + ft
    # it fits wherever the static gate admits the config, and only there
    gate = vmem.hist_fold_cell_ok if seeded else vmem.hist_cell_ok
    fits = (cell_vmem_bytes(ft, B, cols, T, C, mode, seeded)
            <= VMEM_BUDGET_BYTES)
    assert fits == gate(max_bin, A, mode)

    # never more padded work than the rule before, nor more modelled time
    T0, ft0, F_grid0 = old_rule(F_pad, N_PAD, B, cols, C, mode, ROW_TILE,
                                seeded)
    assert F_grid <= F_grid0
    if fits:
        assert (hist_call_fs(ft, F_grid, N_PAD, B, cols, T)
                <= hist_call_fs(ft0, F_grid0, N_PAD, B, cols, T0))
    # the Criteo cells' shape: 67 features are contracted as 72 at most
    if F_pad == 67 and max_bin == 63:
        assert F_grid <= 72

    # the carry a streamed fold allocates is the one the kernel fills
    if seeded and fits:
        from lightgbm_tpu.ops.pallas_histogram import (hist_active_pallas,
                                                       hist_raw_layout)
        shape, dtype = hist_raw_layout(N_PAD, A, F_pad, max_bin, mode)
        assert shape == (F_grid * B, cols)
        s = jax.ShapeDtypeStruct
        vdt = jnp.int8 if mode == "int8h" else jnp.float32
        out = jax.eval_shape(
            lambda *a: hist_active_pallas(
                *a, num_features=F_pad, max_bins=max_bin, mode=mode,
                raw=True),
            s((F_pad, N_PAD), jnp.uint8),
            s((VALUE_ROWS[mode], N_PAD), vdt), s((N_PAD,), jnp.int32),
            s((A,), jnp.int32), None, s(shape, dtype))
        assert (out.shape, out.dtype) == (shape, dtype)


@pytest.mark.parametrize("n_pad,requested,tiles", [
    (N_PAD, 2048, [2048, 1024]),
    (N_PAD, 4096, [2048, 1024]),        # 4,096 does not divide the shard
    (1 << 20, 4096, [4096, 2048, 1024]),
    (3 * 1024, 2048, [1024]),
    (4096, 512, [512]),                 # a tile under 1,024 is taken as is
    (N_PAD, 1024, [1024]),              # LGBM_TPU_ROW_TILE: the upper bound
])
def test_row_tiles(n_pad, requested, tiles):
    assert row_tiles(n_pad, requested) == tiles
    T, _, _ = hist_tiling(67, n_pad, 64, 128, 4, "int8h", requested)
    assert T in tiles


@pytest.mark.parametrize("F_pad,max_bin", [(28, 63), (67, 63), (67, 255)])
def test_whole_set_or_nothing(F_pad, max_bin):
    """The fused route+histogram kernel's grid: one tile of every
    feature at the largest row tile whose cell fits, as its own loop
    chose before."""
    B = bin_stride(max_bin)
    C, _, cols = col_layout(32, "int8h")
    T, ft, F_grid = hist_tiling(F_pad, N_PAD, B, cols, C, "int8h",
                                ROW_TILE, whole=True)
    assert ft == F_grid == F_pad
    fitting = [t for t in row_tiles(N_PAD, ROW_TILE)
               if cell_vmem_bytes(F_pad, B, cols, t, C, "int8h")
               <= VMEM_BUDGET_BYTES]
    assert T == (fitting[0] if fitting else 1024)


@pytest.mark.parametrize("n_pad", [4096, 1 << 20])
@pytest.mark.parametrize("A", [8, 32, 64, 128])
def test_choice_does_not_depend_on_rows(A, n_pad):
    """A call's modelled time is its rows times a function of the grid,
    so a small test runs the tiles the 13.28M-row cell runs."""
    C, _, cols = col_layout(A, "int8h")
    assert (hist_tiling(67, n_pad, 64, cols, C, "int8h", ROW_TILE)[:2]
            == hist_tiling(67, N_PAD, 64, cols, C, "int8h", ROW_TILE)[:2])


# (T, feat_tile, F_grid) at 8 / 16 / 32 / 64 / 128 slots, 67 features,
# 13,281,280 rows.  The bf16 modes': what the model chose before it knew
# the element size (PR 29-33), and must still.  The int8 modes': the
# benchmark cells' grids at 63 bins (the whole set at 2,048 rows a cell
# in the 128-column calls and at 1,024 in the 256-column one: no feature
# padded there), and what the rule picks at 255 bins, which no cell runs
# yet.
WHOLE_1K, WHOLE_2K = (1024, 67, 67), (2048, 67, 67)
T24_1K, T24_2K = (1024, 24, 72), (2048, 24, 72)
T8_1K, T8_2K = (1024, 8, 72), (2048, 8, 72)
GRIDS = {
    ("int8h", 63): [WHOLE_2K, WHOLE_2K, WHOLE_2K, WHOLE_1K, T24_2K],
    ("int8", 63): [WHOLE_2K, WHOLE_2K, WHOLE_2K, WHOLE_1K, WHOLE_1K],
    ("int8hh", 63): [WHOLE_2K, WHOLE_2K, WHOLE_1K, WHOLE_1K, T24_2K],
    ("int8h", 255): [T24_1K, T24_1K, T24_1K, T8_2K, T8_2K],
    ("hilo", 63): [WHOLE_1K, WHOLE_1K, T24_2K, T24_2K, T24_1K],
    ("hhilo", 63): [WHOLE_1K, WHOLE_1K, WHOLE_1K, T24_2K, T24_2K],
    ("ghilo", 63): [WHOLE_1K, WHOLE_1K, WHOLE_1K, T24_2K, T24_2K],
    ("bf16", 63): [WHOLE_1K, WHOLE_1K, WHOLE_1K, T24_2K, T24_2K],
    ("hilo", 255): [T8_2K, T8_2K, T8_2K, T8_1K, T8_1K],
    ("hhilo", 255): [T8_2K, T8_2K, T8_2K, T8_2K, T8_1K],
}


@pytest.mark.parametrize("mode,max_bin", list(GRIDS))
def test_grid_by_element_size(mode, max_bin):
    B = bin_stride(max_bin)
    got = []
    for A in (8, 16, 32, 64, 128):
        C, _, cols = col_layout(A, mode)
        got.append(hist_tiling(67, N_PAD, B, cols, C, mode, ROW_TILE))
    assert got == GRIDS[mode, max_bin]


@pytest.mark.parametrize("mode,bytes_128x2048", [
    ("int8h", 11_579_392),      # 2.20 MB of accumulator, 8.78 of one-hot
    ("hilo", 20_623_360),       # the same cell in bf16: turned away
])
def test_cell_bytes_by_element_size(mode, bytes_128x2048):
    """The 67-feature x 2,048-row cell at 128 columns, 63 bins: the
    one-hot and the weighted values at the mode's element size, the
    accumulator at 4 bytes, the bins tile at 1, the packed values as
    they were."""
    got = cell_vmem_bytes(67, 64, 128, 2048, 4, mode)
    assert got == bytes_128x2048
    assert vmem.operand_bytes(mode) == (1 if mode == "int8h" else 2)
    assert (got <= VMEM_BUDGET_BYTES) == (mode == "int8h")
    assert VMEM_BUDGET_BYTES == 12 * 1024 * 1024


@pytest.mark.parametrize("features,max_bin,leaves,mode,ok", [
    (67, 63, 255, "int8h", False),  # the cells: 56 features fit, not 67
    (28, 63, 255, "int8h", True),   # chip_smoke.py's width: as before
    (28, 63, 255, "hilo", True),
    (67, 63, 255, "hilo", False),
    (56, 63, 255, "int8h", True),   # 60 before the route was counted
    (57, 63, 255, "int8h", False),
    (40, 63, 255, "hhilo", True),   # the bf16 cap: 43 before
    (41, 63, 255, "hhilo", False),
])
def test_fused_gate_by_element_size(features, max_bin, leaves, mode, ok):
    """`fused_config_ok` judged at the tail's columns at 1,024 rows,
    the route's residents counted for a table of all 255 leaves (0.77
    MB there): the widths the tests train at keep the decision they
    had, the caps moved down by what the route holds."""
    from lightgbm_tpu.ops.pallas_histogram import (INT8_ROW_LIMIT,
                                                   fused_config_ok)
    assert fused_config_ok(features, max_bin, leaves, mode, 0,
                           INT8_ROW_LIMIT, slots=128,
                           route_leaves=leaves) == ok


@pytest.mark.parametrize("slots,route_leaves,cols,grid", [
    (8, 1, 128, (2048, 67, 67)),        # waves 1-4: the wide call's grid
    (32, 32, 128, (2048, 67, 67)),      # wave 6
    (64, 64, 256, (1024, 67, 67)),      # wave 7
    (128, 255, 512, None),              # the tail: 67 do not fit
])
def test_fused_gate_by_wave(slots, route_leaves, cols, grid):
    """Judged a wave at a time, the cells' 67 features fit one fused
    tile at 128 columns x 2,048 rows and at 256 x 1,024, the grids the
    wide call takes there, and not at the tail's 512."""
    from lightgbm_tpu.ops.pallas_histogram import (INT8_ROW_LIMIT,
                                                   fused_config_ok,
                                                   route_lanes)
    C, _, got_cols = col_layout(slots, "int8h")
    assert got_cols == cols
    ok = fused_config_ok(67, 63, 255, "int8h", 13_281_280, INT8_ROW_LIMIT,
                         slots=slots, route_leaves=route_leaves)
    assert ok == (grid is not None)
    if ok:
        lanes = route_lanes(route_leaves)
        assert hist_tiling(67, 13_281_280, 64, cols, C, "int8h", ROW_TILE,
                           whole=True, route_lanes=lanes,
                           id_lanes=256) == grid
        assert hist_tiling(67, 13_281_280, 64, cols, C, "int8h",
                           ROW_TILE) == grid


def test_fused_cell_fits_the_budget_at_the_cells_grid():
    """The fused cell at `67x2048`, 128 columns, int8h: the wide cell's
    11.58 MB and the route's 0.80 MB (leaf vectors in and out, the two
    tables at 128 lanes, the bf16 leaf one-hot, the selection) under
    `VMEM_BUDGET_BYTES`."""
    wide = cell_vmem_bytes(67, 64, 128, 2048, 4, "int8h")
    fused = cell_vmem_bytes(67, 64, 128, 2048, 4, "int8h", route_lanes=128,
                            id_lanes=256)
    assert fused - wide == vmem.route_vmem_bytes(2048, 128, 64, 67, 256) \
        == 802_816
    assert fused == 12_382_208 <= VMEM_BUDGET_BYTES
    assert vmem.selection_bytes(256, 67) == 2


@pytest.mark.parametrize("leaves,route_leaves,elem", [
    (255, 64, 2),       # the cells: ids < 256, bf16-exact
    (255, 255, 2),
    (511, 256, 4),      # wave 9 hands out ids 256..383 from 256 lanes
    (511, 128, 4),
    (1024, 255, 4),
])
def test_route_precision_follows_the_ids_not_the_lanes(leaves,
                                                       route_leaves, elem):
    """A fused wave's table is as wide as the leaves its rows can lie
    in, but its new-id row carries ids up to the tree's ``num_leaves``:
    the selection's element size, and so its MXU precision, is taken
    from those ids (bf16 holds integers up to 256 alone)."""
    from lightgbm_tpu.ops.pallas_histogram import route_lanes
    from lightgbm_tpu.ops.pallas_route import table_precision
    id_lanes = route_lanes(leaves)
    assert vmem.selection_bytes(id_lanes, 28) == elem
    assert (table_precision(id_lanes, 28) == jax.lax.Precision.DEFAULT) \
        == (elem == 2)
    lanes = route_lanes(route_leaves)
    assert vmem.route_vmem_bytes(1024, lanes, 64, 28, id_lanes) \
        - vmem.route_vmem_bytes(1024, lanes, 64, 28, 128) \
        == (elem - 2) * lanes * 1024


@pytest.mark.parametrize("max_bin,slots,mode,ok", [
    (255, 128, "int8h", False),     # refused since PR 21: accumulators
    (255, 128, "hilo", False),
    (255, 64, "int8h", True),
    (255, 64, "hilo", False),
    (255, 128, "int8", True),       # 384 columns: admitted by the byte
    (255, 64, "int8hh", True),      # likewise
    (63, 128, "int8h", True),
])
def test_seeded_fold_gate_by_element_size(max_bin, slots, mode, ok):
    """The streamed fold's gate: three accumulator-sized blocks decide
    at 255 bins x 128 slots whatever the one-hot's size; the two cells
    the byte admits are compiled for the described chip in
    `tests/test_tpu_compile.py`."""
    assert vmem.hist_fold_cell_ok(max_bin, slots, mode) == ok


def test_booster_sets_the_tiling_gauges(monkeypatch):
    """`hist.tiling.<cols>`, `hist.feature_pad_pct`, `hist.row_chunks`,
    `hist.fused_waves` beside `gbdt.hist_backend`, as `chip_smoke.py`
    prints them: every wave's grid of a 255-leaf tree at the Criteo
    width, and the waves that take the fused call, from the kernels'
    own rule."""
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    from lightgbm_tpu.learner import serial
    monkeypatch.setenv("LGBM_TPU_HIST_BACKEND", "pallas")
    # the staged waves at any rows, as at the cells' (no tree is built)
    monkeypatch.setattr(serial, "_COMPILE_LEAN_ROWS", 0)
    rng = np.random.RandomState(0)
    X = rng.normal(size=(3000, 67)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    was_on = obs.enabled()
    obs.reset()
    obs.enable()
    try:
        lgb.Booster({"objective": "binary", "num_leaves": 255,
                     "max_bin": 63, "hist_mode": "int8h", "verbose": -1},
                    lgb.Dataset(X, label=y, params={"max_bin": 63}))
        gauges = obs.summary()["gauges"]
    finally:
        if not was_on:
            obs.disable()
        obs.reset()
    assert gauges["gbdt.hist_backend"] == "pallas"
    want = {"hist.feature_pad_pct": 100.0 * (72 - 67) / 67,
            "hist.wave_slots": "8,8,8,8,8,16,32,64|128",
            "hist.fused_waves": "1,2,3,4,5,6,7",
            "hist.row_chunks": 1}
    for A in (8, 16, 32, 64, 128):
        C, _, cols = col_layout(A, "int8h")
        T, ft, _ = hist_tiling(67, 4096, 64, cols, C, "int8h", ROW_TILE)
        want[f"hist.tiling.{cols}"] = f"{ft}x{T}"
    assert {k: v for k, v in gauges.items() if k.startswith("hist.")} == want
    assert want["hist.tiling.128"] == "67x2048"
    assert want["hist.tiling.256"] == "67x1024"
    assert want["hist.tiling.512"] == "24x2048"


@pytest.mark.parametrize("learner,want", [("serial", "1,2,3,4,5,6,7"),
                                          ("data", "-")])
def test_fused_waves_gauge_by_learner(monkeypatch, learner, want):
    """`hist.fused_waves` at the Criteo width: the serial learner fuses
    waves 1-7; the data-parallel one exchanges each wave's histogram
    before its scan, so none of its waves is fused (`-dp4`)."""
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    from lightgbm_tpu.learner import serial
    monkeypatch.setenv("LGBM_TPU_HIST_BACKEND", "pallas")
    monkeypatch.setattr(serial, "_COMPILE_LEAN_ROWS", 0)
    rng = np.random.RandomState(1)
    X = rng.normal(size=(4096, 67)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    was_on = obs.enabled()
    obs.reset()
    obs.enable()
    try:
        lgb.Booster({"objective": "binary", "num_leaves": 255,
                     "max_bin": 63, "hist_mode": "int8h", "verbose": -1,
                     "tree_learner": learner},
                    lgb.Dataset(X, label=y, params={"max_bin": 63}))
        gauges = obs.summary()["gauges"]
    finally:
        if not was_on:
            obs.disable()
        obs.reset()
    assert gauges["hist.fused_waves"] == want
