"""The main path's kernels and programs, compiled for the real chip.

Every other Pallas test in this suite runs ``interpret=True`` on the CPU,
which cannot see what the chip's compiler refuses: a block shape that
breaks Mosaic's (8, 128) rule (a (32, 1) window into a table, before
PR 21), a grid cell that overflows scoped VMEM (the
seeded wide fold at 128 slots before PR 21).  The TPU compiler is
installed in this image and compiles for a chip that is DESCRIBED, not
attached (``jax.experimental.topologies``), so these tests compile the
kernels at their real widths for a ``v5e:2x2`` — no chip time, nothing
runs, and a pass here is not a chip run.

Rules this file keeps (on-chip-measurement guide, section 2): the
topology is described inside a module-scoped fixture that skips where
it cannot be — never while a module is imported, never ``autouse``,
never in ``conftest.py``; everything built from it is built inside the
test; compiles happen in the test's own process (it holds the TPU
library's lock) with the persistent compile cache off around them (an
entry written for a described chip cannot be read back without one);
and all of it lives in this ONE file, so one xdist worker loads the
library.  Code that asks ``jax.default_backend()`` is steered by
monkeypatch in the test, not by an option of the program.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from lightgbm_tpu.io.device import DeviceData
from lightgbm_tpu.learner.serial import GrowthParams, build_tree
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.ops.vmem import bin_stride, hist_fold_cell_ok

# the reference's HIGGS settings: the width chip_smoke.py trains at
N, F, MAX_BIN, LEAVES = 1 << 20, 28, 63, 255
F_CRITEO = 67               # the benchmark cells' width
N_TREE = 131_072            # whole-tree programs: same widths, fewer rows
N_CRITEO = 13_281_280       # a cell's shard, padded to the row tile
N_C32 = 53_125_000          # a chip of 32: four row chunks at an int8 mode
VALUE_ROWS = {"int8": 3, "int8h": 4, "int8hh": 5, "hilo": 5}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - any cause: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """The program's TPU branch (default backend, compiled kernels)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _shapes(sharding):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return s


def _compiled_text(lowered) -> str:
    return lowered.compile().as_text()


def _hist_args(s, n, mode, slots, features=F):
    vdt = jnp.int8 if mode.startswith("int8") else jnp.float32
    return [s((features, n), jnp.uint8), s((VALUE_ROWS[mode], n), vdt),
            s((n,), jnp.int32), s((slots,), jnp.int32),
            s((2,), jnp.float32)]


def _split_tables(s, features=F):
    """The per-leaf split tables + per-feature metadata the route
    kernels take, in their positional order."""
    i32, b = jnp.int32, jnp.bool_
    leaf = (LEAVES,)
    return [s(leaf, i32), s(leaf, i32), s(leaf, b), s(leaf, b),
            s((LEAVES, bin_stride(MAX_BIN)), b), s(leaf, b),
            s(leaf, i32)] + [s((features,), i32) for _ in range(6)]


def _device_data(n, bins_sharding, meta_sharding, features=F):
    s, m = _shapes(bins_sharding), _shapes(meta_sharding)
    meta = lambda: m((features,), jnp.int32)            # noqa: E731
    return DeviceData(
        bins=s((n, features), jnp.uint8), bin_offsets=meta(),
        num_bins=meta(), default_bins=meta(), missing_types=meta(),
        is_categorical=m((features,), jnp.bool_), nan_bins=meta(),
        feat_group=meta(), feat_offset=meta(),
        total_bins=features * MAX_BIN, max_bins=MAX_BIN, has_categorical=False,
        max_group_bins=MAX_BIN, is_bundled=False, has_missing=False)


def _growth():
    return GrowthParams(num_leaves=LEAVES, max_depth=-1, wave_size=0,
                        split=SplitParams(min_data_in_leaf=20,
                                          min_sum_hessian_in_leaf=1e-3))


# ---------------------------------------------------------------------------
# histogram kernels
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("slots", [8, 32, 64, 128])
@pytest.mark.parametrize("mode", ["hilo", "int8h"])
@pytest.mark.parametrize("features", [F, F_CRITEO])
def test_wide_hist_kernel_compiles(one_chip, features, mode, slots):
    """Every wave's grid as `ops/vmem.hist_tiling` chooses it at the
    real row tiles: at the Criteo width a 67-row full-array `u8` block
    (128 columns) and 24-row blocks (256, 512 columns)."""
    from lightgbm_tpu.ops.pallas_histogram import hist_active_pallas
    s = _shapes(one_chip)
    text = _compiled_text(hist_active_pallas.lower(
        *_hist_args(s, N, mode, slots, features), num_features=features,
        max_bins=MAX_BIN, mode=mode))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("slots,grid", [
    (32, (2048, 67, 67)),   # a 128-column call: 11.58 MB by the model
    (64, (1024, 67, 67)),   # the 256-column call: 9.2 MB
    (128, (2048, 24, 72)),  # the tail's: as before PR 34
])
def test_wide_hist_kernel_compiles_at_the_cells_shape(one_chip, slots,
                                                      grid):
    """The benchmark cells' calls as they are made: `[67, 13,281,280]`,
    int8h, 63 bins, on the grids the model admits once it counts the
    int8 one-hot at one byte: the whole feature set in one tile at
    2,048 rows a cell (128 columns) and at 1,024 (256 columns).  The
    chip's compiler takes them under its default scoped-VMEM limit."""
    from lightgbm_tpu.ops.pallas_histogram import hist_active_pallas
    from lightgbm_tpu.ops.vmem import col_layout, hist_tiling
    C, _, cols = col_layout(slots, "int8h")
    assert hist_tiling(F_CRITEO, N_CRITEO, bin_stride(MAX_BIN), cols, C,
                       "int8h", 2048) == grid
    s = _shapes(one_chip)
    text = _compiled_text(hist_active_pallas.lower(
        *_hist_args(s, N_CRITEO, "int8h", slots, F_CRITEO),
        num_features=F_CRITEO, max_bins=MAX_BIN, mode="int8h"))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("slots,cols,tile", [(8, 128, 2048), (32, 128, 2048),
                                             (64, 256, 1024)])
def test_chunked_hist_kernel_compiles_at_the_c32_shape(one_chip, slots, cols,
                                                       tile):
    """`criteo-67-b63-c32.train`'s calls: `[67, 53,125,120]` (3.56e9
    bins, the first array here past 2^31 elements), int8h: four row
    chunks in the accumulator (`s32[4, 4288, cols]`), the output block
    moving on every 6,485 row tiles of 2,048 (12,970 of 1,024), and the
    limb sum after the call."""
    from lightgbm_tpu.ops.pallas_histogram import (hist_active_pallas,
                                                   row_chunks)
    n_pad = N_C32 + 120
    assert row_chunks(n_pad // tile, tile) == (4, 6_485 * 2048 // tile)
    s = _shapes(one_chip)
    text = _compiled_text(hist_active_pallas.lower(
        *_hist_args(s, n_pad, "int8h", slots, F_CRITEO),
        num_features=F_CRITEO, max_bins=MAX_BIN, mode="int8h"))
    assert "tpu_custom_call" in text
    assert f"s32[4,4288,{cols}]" in text


@pytest.mark.parametrize("max_bin,slots,mode", [
    (63, 128, "int8h"),     # 16.76 MB of scoped VMEM before PR 21
    (63, 128, "hilo"),
    (255, 64, "int8h"),
    (255, 128, "int8h"),    # the gate refuses: so does the compiler
    (255, 128, "int8"),     # admitted since the one-hot counts one byte
    (255, 64, "int8hh"),    # (PR 34): 11.97 / 11.98 MB by the model
])
@pytest.mark.parametrize("features", [F, F_CRITEO])
def test_seeded_wide_fold_gate_agrees_with_compiler(one_chip, features,
                                                    max_bin, slots, mode):
    """The streamed fold's seeded wide kernel: wherever the static gate
    (`ops/vmem.hist_fold_cell_ok`) admits a cell the chip's compiler
    takes it, and the cell the gate turns away is one the compiler
    refuses — the gate is true, no runtime rescue is needed."""
    from lightgbm_tpu.ops.pallas_histogram import (hist_active_pallas,
                                                   hist_raw_layout)
    s = _shapes(one_chip)
    shape, dtype = hist_raw_layout(N, slots, features, max_bin, mode)
    lowered = hist_active_pallas.lower(
        *_hist_args(s, N, mode, slots, features), s(shape, dtype),
        num_features=features, max_bins=max_bin, mode=mode, raw=True)
    if hist_fold_cell_ok(max_bin, slots, mode):
        assert "tpu_custom_call" in _compiled_text(lowered)
    else:
        with pytest.raises(Exception, match="vmem"):
            lowered.compile()


@pytest.mark.parametrize("slots", [64, 128])
def test_seeded_wide_fold_compiles_at_deep_waves(one_chip, on_tpu, slots):
    """What a deep wave of streamed training runs on the chip: the fold
    `make_hist_fold_fn` builds for a block of rows at the benchmark
    cells' width (transpose, pack, the seeded wide kernel on the grid
    `hist_tiling` gives a seeded call: one jitted program), at the 256-
    and the 512-column wave."""
    from lightgbm_tpu.learner.serial import make_hist_fold_fn
    s = _shapes(one_chip)
    dd = _device_data(N, one_chip, one_chip, F_CRITEO)
    fold = make_hist_fold_fn(dd, LEAVES, slots, N, hist_mode="int8h")
    assert fold is not None and fold.quantized
    acc = jax.eval_shape(fold.init_acc)
    rows = lambda dtype: s((N,), dtype)                 # noqa: E731
    text = _compiled_text(fold.fold.lower(
        s((N, F_CRITEO), jnp.uint8), rows(jnp.float32), rows(jnp.float32),
        rows(jnp.int32), s((slots,), jnp.int32), s(acc.shape, acc.dtype),
        s((2,), jnp.float32)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("features,mode,slots", [
    (F, "hilo", 32),
    (F, "int8h", 32),
    (56, "int8h", 128),     # the widest set the tail's gate admits at 63
    (56, "int8h", 8),       # bins once the route counts (60 before PR 40)
])
def test_fused_hist_route_kernel_compiles(one_chip, features, mode, slots):
    from lightgbm_tpu.ops.pallas_histogram import (INT8_ROW_LIMIT,
                                                   fused_config_ok,
                                                   hist_route_pallas)
    assert fused_config_ok(features, MAX_BIN, LEAVES, mode, N,
                           INT8_ROW_LIMIT, slots=128, route_leaves=LEAVES)
    s = _shapes(one_chip)
    bins_t, vals, _, active, scales = _hist_args(s, N, mode, slots,
                                                 features)
    text = _compiled_text(hist_route_pallas.lower(
        bins_t, vals, s((2, N), jnp.int32), active,
        *_split_tables(s, features), scales, num_features=features,
        max_bins=MAX_BIN, mode=mode, any_cat=False, route_leaves=LEAVES))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("features", [60, 67])
def test_the_fused_tail_compiles_past_the_models_cap(one_chip, features):
    """The VMEM model is an upper bound: it counts every resident of the
    fused cell as live at once, and refuses the 255-leaf tail's cell
    (128 slots, 512 columns, 1,024 rows) past 56 features.  The chip's
    compiler takes it at 60 (the parent's cap, before the route was
    counted) and at the cells' 67 (at least to 88 by hand, PR 40).  What
    fusing those tails would gain is not measured: the tail keeps the
    unfused pair there."""
    from lightgbm_tpu.ops.pallas_histogram import (INT8_ROW_LIMIT,
                                                   fused_config_ok,
                                                   hist_route_pallas)
    assert not fused_config_ok(features, MAX_BIN, LEAVES, "int8h", N,
                               INT8_ROW_LIMIT, slots=128,
                               route_leaves=LEAVES)
    s = _shapes(one_chip)
    bins_t, vals, _, active, scales = _hist_args(s, N, "int8h", 128,
                                                 features)
    text = _compiled_text(hist_route_pallas.lower(
        bins_t, vals, s((2, N), jnp.int32), active,
        *_split_tables(s, features), scales, num_features=features,
        max_bins=MAX_BIN, mode="int8h", any_cat=False, route_leaves=LEAVES))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("slots,route_leaves,grid", [
    (8, 8, (2048, 67, 67)),         # wave 4 (waves 1-3 alike)
    (16, 16, (2048, 67, 67)),       # wave 5
    (32, 32, (2048, 67, 67)),       # wave 6
    (64, 64, (1024, 67, 67)),       # wave 7
])
def test_fused_hist_route_kernel_compiles_at_the_cells_shape(
        one_chip, slots, route_leaves, grid):
    """The one-chip cells' fused waves as `build_tree` makes them:
    `[67, 13,281,280]`, int8h, 63 bins, the route table as wide as the
    leaves the wave's rows can lie in, on the wide call's own grids
    (6,485 cells of 2,048 rows at 128 columns, 1,024 rows at 256).  The
    chip's compiler takes them under its default scoped-VMEM limit."""
    from lightgbm_tpu.ops.pallas_histogram import (INT8_ROW_LIMIT,
                                                   fused_config_ok,
                                                   hist_route_pallas,
                                                   route_lanes)
    from lightgbm_tpu.ops.vmem import col_layout, hist_tiling
    assert fused_config_ok(F_CRITEO, MAX_BIN, LEAVES, "int8h", N_CRITEO,
                           INT8_ROW_LIMIT, slots=slots,
                           route_leaves=route_leaves)
    C, _, cols = col_layout(slots, "int8h")
    assert hist_tiling(F_CRITEO, N_CRITEO, bin_stride(MAX_BIN), cols, C,
                       "int8h", 2048, whole=True,
                       route_lanes=route_lanes(route_leaves),
                       id_lanes=route_lanes(LEAVES)) == grid
    s = _shapes(one_chip)
    bins_t, vals, _, active, scales = _hist_args(s, N_CRITEO, "int8h",
                                                 slots, F_CRITEO)
    text = _compiled_text(hist_route_pallas.lower(
        bins_t, vals, s((2, N_CRITEO), jnp.int32), active,
        *_split_tables(s, F_CRITEO), scales, num_features=F_CRITEO,
        max_bins=MAX_BIN, mode="int8h", any_cat=False,
        route_leaves=route_leaves))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("values", [False, True], ids=["route", "values"])
def test_route_kernel_compiles(one_chip, values):
    from lightgbm_tpu.ops.pallas_route import (route_rows_pallas,
                                               route_rows_values_pallas)
    s = _shapes(one_chip)
    args = [s((F, N), jnp.uint8), s((2, N), jnp.int32), *_split_tables(s)]
    if values:
        lowered = route_rows_values_pallas.lower(
            *args, s((LEAVES,), jnp.float32), any_cat=False)
    else:
        lowered = route_rows_pallas.lower(*args, any_cat=False)
    assert "tpu_custom_call" in _compiled_text(lowered)


# ---------------------------------------------------------------------------
# split kernel: the HIGGS-255 width and the caps the static gate admits
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("features,stride,leaf_tile", [
    (28, 256, 16),          # 28 features x 256-bin stride
    (64, 256, 8),           # F*B = 16384 = SPLIT_MAX_LANES, min leaf tile
    (42, 128, 32),          # the widest F*B the 32-leaf tile is given
])
def test_split_kernel_compiles_at_cap(one_chip, features, stride,
                                      leaf_tile):
    from lightgbm_tpu.ops import pallas_split as ps
    slots = 256                         # 2A changed slots of a deep wave
    assert ps.split_kernel_ok(features, stride, False, num_rows=1000)
    assert ps._leaf_tile(slots, features * stride) == leaf_tile
    s = _shapes(one_chip)
    split = _growth().split
    fn = jax.jit(lambda g, a, b, c, nb, mt, db: ps.find_best_splits_pallas(
        g, a, b, c, nb, mt, db, B=stride, params=split, any_missing=True))
    leaf = s((slots,), jnp.float32)
    feat = s((features,), jnp.int32)
    text = _compiled_text(fn.lower(
        s((slots, features, stride, 3), jnp.float32), leaf, leaf, leaf,
        feat, feat, feat))
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# whole tree programs: the default backend, serial and on four chips
# ---------------------------------------------------------------------------
def test_build_tree_default_backend_compiles(one_chip, on_tpu):
    """`build_tree` as `lgb.train` traces it on a TPU: default backend
    (the wide kernel in every wave), default hist mode, staged waves,
    histogram + route kernels in one program."""
    from lightgbm_tpu.learner.serial import resolve_backend
    s = _shapes(one_chip)
    dd = _device_data(N_TREE, one_chip, one_chip)
    assert resolve_backend(dd, LEAVES, hist_mode="int8h") == "pallas"
    growth = _growth()
    fn = jax.jit(lambda dd, g, h, bins_t: build_tree(dd, g, h, growth,
                                                     bins_t=bins_t))
    text = _compiled_text(fn.lower(
        dd, s((N_TREE,), jnp.float32), s((N_TREE,), jnp.float32),
        s((F, N_TREE), jnp.uint8)))
    assert text.count("tpu_custom_call") >= 3


def test_build_tree_compiles_at_the_c32_cells_rows(one_chip, on_tpu):
    """A tree of `criteo-67-b63-c32.train`: 53,125,000 x 67 on one chip
    at int8h, every histogram call and the root's sums in four row
    chunks.  XLA's own plan for it (arguments + temporaries) fits a
    v5e's 16 GB with the room the cell asks for (under 12 GiB)."""
    from lightgbm_tpu.learner.serial import (effective_hist_mode,
                                             shard_row_chunks)
    assert effective_hist_mode("int8h", N_C32) == "int8h"
    assert shard_row_chunks(N_C32) == 4
    s = _shapes(one_chip)
    dd = _device_data(N_C32, one_chip, one_chip, F_CRITEO)
    growth = _growth()
    fn = jax.jit(lambda dd, g, h, bins_t: build_tree(
        dd, g, h, growth, bins_t=bins_t, hist_mode="int8h"))
    compiled = fn.lower(
        dd, s((N_C32,), jnp.float32), s((N_C32,), jnp.float32),
        s((F_CRITEO, N_C32 + 120), jnp.uint8)).compile()
    text = compiled.as_text()
    assert "s32[4,4288,128]" in text and "s32[4,4288,256]" in text
    # the limb sum under its scope, and the leaves' rows recounted in
    # integers (53.1M rows are past what float32 counts exactly)
    assert "tree.hist.chunk_sum" in text and "tree.count" in text
    mem = compiled.memory_analysis()
    plan = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"args {mem.argument_size_in_bytes / 2 ** 20:.0f} MiB + temp "
          f"{mem.temp_size_in_bytes / 2 ** 20:.0f} MiB")
    assert 4 * 2 ** 30 < plan < 12 * 2 ** 30


def test_build_tree_distributed_data_compiles(topo, on_tpu):
    """tree_learner=data as one SPMD program over the four described
    chips: rows sharded, kernels per shard, the wave histograms
    all-reduced."""
    from lightgbm_tpu.parallel.learners import build_tree_distributed
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    rows = NamedSharding(mesh, P("data"))
    dd = _device_data(N_TREE, rows, NamedSharding(mesh, P()))
    growth = _growth()
    fn = jax.jit(lambda dd, g, h: build_tree_distributed(
        mesh, "data", "data", dd, g, h, growth))
    grad = jax.ShapeDtypeStruct((N_TREE,), jnp.float32, sharding=rows)
    compiled = fn.lower(dd, grad, grad).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
    # rows are sharded: each device holds a quarter of the bins
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < N_TREE * F // 2
