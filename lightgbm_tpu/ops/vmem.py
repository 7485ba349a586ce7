"""Shared VMEM feasibility model for every Pallas kernel in the repo.

Through PR 5 each kernel carried its own copy of the same question —
"does this config's per-grid-cell working set fit the ~16 MB/core VMEM
with headroom?" — as ``pallas_histogram._cell_vmem_bytes`` /
``_feat_tile_cap`` and the split kernel's ``_vmem_budget_bytes``
leaf-tile chooser.  PR 1's ADVICE-r5 fix (the pallas_split lane cap
blowing VMEM and surfacing as a Mosaic crash instead of a fallback)
showed what happens when a kernel ships WITHOUT the model.  This
module is the single home for that arithmetic, pure int math with
**no jax import**, so:

* every kernel dispatcher keys its config gate on one budget
  (``VMEM_BUDGET_BYTES``, measured headroom under the v5e's ~16 MB/core
  — see the provenance note below), and
* the memcheck static analyzer (``tools/memcheck``, rule MEM004) can
  enforce "no ``pallas_call`` without a VMEM-model predicate" by
  KEYING ON THIS MODULE: ``VMEM_GUARDS`` below names the sanctioned
  predicates; any module that dispatches a Pallas kernel must reference
  one of them (or any ``*vmem*`` helper) on its guard path.

The grid of a histogram kernel call (row tile, feature tile, padded
feature count) is chosen here too: :func:`hist_tiling`, the least
modelled time over the cells this model admits.  A cell's footprint
(:func:`cell_vmem_bytes`) counts each resident at the element size the
call's mode gives it: the one-hot and the weighted values are int8 on a
quantized mode and bf16 on every other (:func:`operand_bytes`), so every
function that sizes a cell takes the mode.

Budget provenance: 12 MiB per grid cell.  The previous spread-matmul
kernel demonstrably ran larger footprints on the v5e, so 12 MiB under
the ~16 MB/core ceiling leaves room for the streamed inputs'
double-buffering (counted inside :func:`cell_vmem_bytes`) plus Mosaic's
own scratch.  The split kernel's budget is the same default, overridable
for hardware-verified tuning via ``LGBM_TPU_SPLIT_VMEM_MB``.
"""
from __future__ import annotations

import os

LANE = 128

# per-grid-cell VMEM budget for the histogram-family kernels' resident
# arrays (4-byte accumulator + the one-hot and the weighted values at the
# mode's operand size + bins tile + value columns: cell_vmem_bytes)
VMEM_BUDGET_BYTES = 12 * 1024 * 1024

# The time model a histogram call's grid is chosen by (hist_tiling): a
# grid cell costs its MACs at HIST_MAC_FS each plus HIST_CELL_FS.
# Provenance (PERF.md section 5, builder's chip run, PR 29): the wide
# kernel alone on a v5e at [67, 13,281,280], int8h, 31 grids over 128 /
# 256 / 512 output columns at 63 and 255 bins: least squares give 5.15-
# 5.27 fs a MAC (the int8 peak is 5.09) and 0.305-0.307 us a cell; the
# model reads every grid within 5% and ranks each wave's grids as the
# chip does.  The bf16 modes read 10.19 fs and 0.36 us (7 grids, hilo):
# twice the MAC, the same order of cell, and the same choice at every
# shape read.  Measured facts, not knobs: no environment variable.
HIST_MAC_FS = 5.2
HIST_CELL_FS = 0.306e9

# The sanctioned VMEM-guard predicate names: tools/memcheck rule MEM004
# parses this tuple (statically — no import) and requires every module
# with a `pallas_call` to reference one of these names, or any name
# containing "vmem", on its dispatch path.  Extend this tuple when a
# new kernel family grows its own predicate.
VMEM_GUARDS = (
    "pallas_config_ok",      # wide one-hot histogram + route table model
    "fused_config_ok",       # fused route+hist kernel
    "hist_cell_ok",          # the generic predicate below
    "hist_fold_cell_ok",     # accumulator-seeded streamed-fold variant
    "split_lane_chunk_features",   # fused split kernel's lane chunking
    "split_scan_chunk_features",   # XLA split scan's HBM chunking
)


def next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1).bit_length())


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def bin_stride(max_bins: int) -> int:
    """Per-feature bin stride used by the kernels' joint index space."""
    return max(8, next_pow2(max_bins))


def col_layout(A: int, mode: str) -> tuple[int, int, int]:
    """-> (C, A_pad, cols): value columns, padded active slots, lane-
    aligned total output columns."""
    C = {"hilo": 5, "ghilo": 4, "hhilo": 4, "int8h": 4,
         "int8hh": 5}.get(mode, 3)
    A_pad = round_up(A, 8)
    cols = round_up(C * A_pad, LANE)
    return C, A_pad, cols


def is_quantized(mode: str) -> bool:
    """The int8 modes: int8 operands on the MXU, exact int32 sums."""
    return mode in ("int8", "int8h", "int8hh")


def operand_bytes(mode: str) -> int:
    """Element size of the two operands a histogram kernel builds in
    VMEM and contracts, the one-hot and the weighted values: int8 on a
    quantized mode, bf16 on every other (``_hist_kernel``'s ``cdt``)."""
    return 1 if is_quantized(mode) else 2


def selection_bytes(id_lanes: int, num_groups: int) -> int:
    """Element size of the route's leaf one-hot and tables on the MXU:
    bf16 where every integer the tables carry is exact in it (leaf ids
    and groups up to 256), f32 past that (`ops/pallas_route.py`
    ``table_precision`` takes its precision from this).  ``id_lanes``:
    the tree's leaf ids rounded up to lanes, ``round_up(num_leaves,
    128)``, since a split's new id may be any of them, however narrow
    the table that carries it."""
    return 2 if id_lanes <= 256 and num_groups <= 256 else 4


def route_vmem_bytes(T: int, L_pad: int, B: int, num_groups: int,
                     id_lanes: int, any_cat: bool = False) -> int:
    """What the route adds to a fused route+histogram cell
    (``ops/pallas_histogram.py`` ``_hist_route_kernel``): the ``[2, T]``
    int32 leaf vectors in and out, each double-buffered; the ``[16,
    L_pad]`` split table and the ``[B, L_pad]`` categorical table (f32,
    double-buffered; ``B`` the group stride, at least the features'); the
    ``[L_pad, T]`` leaf one-hot at :func:`selection_bytes` of the
    tree's ``id_lanes``; the ``[16,
    T]`` f32 selection; with categorical features the ``[B, T]`` f32
    membership rows."""
    return (2 * 2 * (2 * T * 4)
            + 2 * (16 + B) * L_pad * 4
            + L_pad * T * selection_bytes(max(L_pad, id_lanes),
                                          num_groups)
            + 16 * T * 4
            + (B * T * 4 if any_cat else 0))


def cell_vmem_bytes(ft: int, B: int, cols: int, T: int, C: int,
                    mode: str, seeded: bool = False,
                    route_lanes: int = 0, id_lanes: int = 0,
                    any_cat: bool = False) -> int:
    """VMEM footprint of one (feature-tile, row-tile) histogram grid
    cell, each resident at the element size ``mode`` gives it: the
    4-byte accumulator (f32, int32 on a quantized mode), the one-hot
    and the weighted value block (:func:`operand_bytes`), the bins tile
    (double-buffered), and the packed values.
    ``seeded``: the streamed-fold variant also streams the carried
    accumulator IN, double-buffered like every blocked operand — two
    more accumulator-sized blocks (the v5e compiler refused the
    28 x 63-bin x 128-slot seeded cell at 16.76 MB of scoped VMEM
    before this was counted).
    ``route_lanes``: the fused route+histogram cell, whose route table
    is ``route_lanes`` leaves wide and carries ids up to ``id_lanes``,
    also holds the route's residents (:func:`route_vmem_bytes`; ``ft``
    is then the whole feature set)."""
    acc = ft * B * cols * 4          # accumulator (out block)
    e = operand_bytes(mode)
    return (acc + (2 * acc if seeded else 0)
            + ft * B * T * e         # one-hot
            + T * cols * e           # vw
            + 2 * ft * T             # bins tile, double-buffered
            + 2 * T * C * 4          # vals, double-buffered
            + (route_vmem_bytes(T, route_lanes, B, ft, id_lanes, any_cat)
               if route_lanes else 0))


def feat_tile_cap(B: int, cols: int, T: int, C: int, mode: str,
                  seeded: bool = False) -> int:
    """Largest feature tile whose grid cell fits the VMEM budget."""
    ft = max(1, VMEM_BUDGET_BYTES
             // (B * (cols * 4 + T * operand_bytes(mode))))
    while ft > 1 and cell_vmem_bytes(ft, B, cols, T, C, mode,
                                     seeded) > VMEM_BUDGET_BYTES:
        ft -= 1
    return ft


def hist_call_fs(feat_tile: int, F_grid: int, n_pad: int, B: int,
                 cols: int, T: int) -> float:
    """Modelled time [fs] of one histogram kernel call on this grid."""
    cells = (F_grid // feat_tile) * (n_pad // T)
    return cells * (feat_tile * B * cols * T * HIST_MAC_FS + HIST_CELL_FS)


def row_tiles(n_pad: int, requested: int) -> list[int]:
    """The row tiles a histogram call may use: the powers of two from
    ``requested`` down to 1,024 that divide ``n_pad`` (``requested``
    itself where it is 1,024 or less)."""
    tiles = [requested]
    while tiles[-1] > 1024:
        tiles.append(tiles[-1] // 2)
    return [T for T in tiles if n_pad % T == 0] or tiles[-1:]


def hist_tiling(F_pad: int, n_pad: int, B: int, cols: int, C: int,
                mode: str, requested: int, seeded: bool = False,
                whole: bool = False, route_lanes: int = 0,
                id_lanes: int = 0,
                any_cat: bool = False) -> tuple[int, int, int]:
    """``-> (T, feat_tile, F_grid)``: the grid of one histogram kernel
    call, chosen for the least modelled time (:func:`hist_call_fs`)
    over the cells the VMEM model admits.

    Candidates: a row tile ``T`` of :func:`row_tiles`; at each, the
    whole feature set in one tile where that cell fits the budget (a
    full-array block is exempt from Mosaic's sublane rule), else every
    multiple of 8 up to :func:`feat_tile_cap`, the feature count padded
    to a whole number of tiles (``F_grid``).  ``whole``: the whole set
    or nothing (the fused route+histogram kernel reads any feature's
    column from the one tile; ``route_lanes`` / ``id_lanes`` /
    ``any_cat`` count its route, :func:`cell_vmem_bytes`).  Ties go to
    the larger row tile, then the larger feature tile.  Where no cell
    fits (a config the feasibility predicates turn away) the smallest
    one is returned and the compiler is left to refuse it.

    Shared by the wide and fused kernels and the wide kernel's
    raw-layout twin, so a fold's carry can never disagree with the
    kernel that fills it."""
    tiles = row_tiles(n_pad, requested)
    grids = []
    for T in tiles:
        if cell_vmem_bytes(F_pad, B, cols, T, C, mode, seeded,
                           route_lanes, id_lanes,
                           any_cat) <= VMEM_BUDGET_BYTES:
            feat_tiles = [F_pad]
        elif whole:
            feat_tiles = []
        else:
            feat_tiles = range(
                8, feat_tile_cap(B, cols, T, C, mode, seeded) + 1, 8)
        grids += [(T, ft, round_up(F_pad, ft)) for ft in feat_tiles]
    if not grids:
        ft = F_pad if whole else 8
        return tiles[-1], ft, round_up(F_pad, ft)
    return min(grids, key=lambda g: (
        hist_call_fs(g[1], g[2], n_pad, B, cols, g[0]), -g[0], -g[1]))


def hist_cell_ok(max_bins: int, active_slots: int, mode: str,
                 row_tile: int = 1024) -> bool:
    """The generic histogram-kernel feasibility predicate: does the
    minimum-feature-tile grid cell at ``active_slots`` output slots fit
    the budget (at the 1024-row fallback tile ``row_tiles`` goes
    down to)?"""
    B = bin_stride(max_bins)
    C, _, cols = col_layout(active_slots, mode)
    return (cell_vmem_bytes(8, B, cols, row_tile, C, mode)
            <= VMEM_BUDGET_BYTES)


def hist_fold_cell_ok(max_bins: int, active_slots: int, mode: str,
                      row_tile: int = 1024) -> bool:
    """Feasibility of the accumulator-SEEDED histogram cell (the
    out-of-core fold variant of the kernel): on top of
    :func:`hist_cell_ok`'s residents, the carried accumulator operand
    streams in as a double-buffered ``[ft*B, cols]`` block (same
    element size as the output; int32 on the quantized modes) for the
    seed-load."""
    B = bin_stride(max_bins)
    C, _, cols = col_layout(active_slots, mode)
    return (cell_vmem_bytes(8, B, cols, row_tile, C, mode, seeded=True)
            <= VMEM_BUDGET_BYTES)


def split_vmem_budget_bytes() -> int:
    """Working-set budget for the fused split kernel's leaf-tile choice
    (env-tunable: the split kernel holds ~6 concurrent [3*Lc, FB] f32
    arrays in its missing path — see ops/pallas_split.py)."""
    return int(float(os.environ.get("LGBM_TPU_SPLIT_VMEM_MB", 12))
               * (1 << 20))


# ---------------------------------------------------------------------------
# split-scan working-set model (ISSUE 9): both split-finder paths chunk
# the FEATURE axis under the budgets below, so the 255-bin MSLR shape
# (136 features x 256-bin stride) stays inside memory on either path.
# ---------------------------------------------------------------------------

# F*B lane cap per fused-split-kernel call (ops/pallas_split.py: at the
# old 32768 cap the kernel's [3*Lc, FB] f32 intermediates blew the
# ~16 MB/core VMEM).  Wider feature sets run as per-chunk kernel calls.
SPLIT_MAX_LANES = 16384

# concurrent [2, slots, F, B] f32 grids the XLA scan's missing-direction
# variant holds live (lg/lh/lc, rg/rh/rc, num_gain, ok, var_best,
# num_gain_b — ops/split.py:195-223); the no-missing path halves the
# stack and drops the direction axis.
SPLIT_SCAN_LIVE_GRIDS = 10
SPLIT_SCAN_LIVE_GRIDS_NOMISS = 6


def split_lane_chunk_features(num_features: int, B: int) -> int:
    """Features per fused-split-kernel chunk: the largest count whose
    F*B lane width fits ``SPLIT_MAX_LANES`` AND stays LANE-aligned (the
    kernel's block width requirement).  ``B`` is the power-of-two bin
    stride, so alignment needs chunk counts in multiples of
    ``LANE // B`` when ``B < LANE``."""
    fc = max(1, SPLIT_MAX_LANES // B)
    step = max(1, LANE // B)
    fc -= fc % step
    return max(step, min(num_features, fc)) if fc else step


def split_scan_bytes(slots: int, num_features: int, B: int,
                     any_missing: bool = True) -> int:
    """Live HBM bytes of one XLA split scan over a ``[slots, F, B]``
    grid — the ~10-grid f32 stack of the missing-direction variant."""
    if any_missing:
        return SPLIT_SCAN_LIVE_GRIDS * 2 * slots * num_features * B * 4
    return SPLIT_SCAN_LIVE_GRIDS_NOMISS * slots * num_features * B * 4


def split_scan_budget_bytes() -> int:
    """HBM budget for the split scan's live intermediates
    (``LGBM_TPU_SPLIT_SCAN_MB`` overrides; default 512 MiB — small next
    to the 14 GiB device budget, large enough that the default HIGGS
    shapes never chunk)."""
    return int(float(os.environ.get("LGBM_TPU_SPLIT_SCAN_MB", 512))
               * (1 << 20))


def split_scan_chunk_features(slots: int, num_features: int, B: int,
                              any_missing: bool = True) -> int:
    """Features per XLA-scan chunk so the live stack fits the budget.
    Returns ``num_features`` (no chunking) when the whole scan fits —
    the default HIGGS/63-bin shapes — and chunks only when the stack
    would exceed the budget (the 255-bin MSLR regime).
    ``LGBM_TPU_SPLIT_CHUNK_F`` forces an explicit chunk width."""
    forced = os.environ.get("LGBM_TPU_SPLIT_CHUNK_F")
    if forced:
        return max(1, min(num_features, int(forced)))
    per_f = split_scan_bytes(slots, 1, B, any_missing)
    fc = max(1, split_scan_budget_bytes() // max(1, per_f))
    return min(num_features, fc)
