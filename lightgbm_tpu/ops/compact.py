"""Leaf-compacted deep-wave histograms — the TPU ``DataPartition`` analog.

NOT THE DEFAULT (PR 27): "auto" resolves to the wide kernel in every
wave (``ops/pallas_histogram.py default_backend``).  This module is
reachable by name only (``hist_backend="compact"`` /
``LGBM_TPU_HIST_BACKEND=compact``) and stays, with its tests, until a
row compaction that beats the wide deep waves rewrites it (ROADMAP
S1(b)) or ROADMAP D1 deletes it.

Why it exists: per-row MXU work in the wide one-hot kernel
(`ops/pallas_histogram.py`) scales with ``cols = round128(C *
round8(A))`` — every row is contracted against the value columns of ALL
``A`` active leaf slots even though it contributes to exactly one, and
128-slot waves are the deepest two of the reference's 255-leaf headline
configs.  Why it lost: on a v5e at 13.28M x 67 x 63 bins x 255 leaves,
int8h (PERF.md, PR 27 and ledger PR 26), the plan and regroup below cost
1,731 ms an iteration of XLA gathers, scatters and sorts over all rows
(~65 ns a row and wave) to feed two grouped-kernel calls of 51 ms each,
where the wide kernel runs those two waves in 116 and 170 ms (5 and
9 ns a row more than a 32-slot wave); at 255 bins 337 and 683 ms, still
under the plan's cost.  The reference solves the same problem
on CPU with ``DataPartition``'s leaf-contiguous row layout + ordered
gradients
(`/root/reference/src/treelearner/data_partition.hpp`,
`serial_tree_learner.cpp` ordered-bin path): each leaf's histogram only
ever touches that leaf's rows.

This module is the TPU-native analog, in three steps per deep wave:

1. **plan** (:func:`compact_plan`, plain XLA): bucket every row by its
   active-slot *group* (``COMPACT_GROUP = 32`` slots per group),
   stable-sort rows by group, and pad each group's segment to a whole
   number of row tiles.  Rows whose
   leaf is not active (bagged-out ``-1`` included) sort into a trailing
   trash segment and are DROPPED from the compacted stream — deep
   waves histogram only the smaller children, so this alone removes
   the ~half of the stream the wide kernel reads and multiplies by
   zero.
2. **regroup**: one gather applies the permutation to the bins/value
   streams.  It rides the wave's existing pending-split application:
   the routed ``leaf2`` from `ops/pallas_route.py` (whose kernel has
   already streamed the bins once to apply the previous wave's splits)
   is consumed directly, so the plan adds no extra leaf computation —
   the learner (`learner/serial.py`) routes, then compacts from the
   routed vector.
3. **grouped kernel** (:func:`hist_active_compact`): the one-hot matmul
   kernel runs over the compacted stream with a *per-tile* active set
   of ``COMPACT_GROUP`` slots — ``cols = round128(C * 32)`` instead of
   ``round128(C * 128)``.  Each tile's group (and so its output block and its slice of the
   per-group active table) is selected by a scalar-prefetched
   ``tile_group`` vector (`pltpu.PrefetchScalarGridSpec`): segments
   are group-contiguous, so every output block is visited in one
   consecutive run and plain ``@pl.when(first-tile-of-group)``
   zero-init + VMEM accumulation works exactly like the wide kernel's
   row grid.

Cost model: the wide kernel pays ``n * cols_wide`` MACs; the compacted
path pays ``~n_active * cols_group`` MACs plus a stable segment-sort of
an ``[n]`` int32 key and one bins/vals gather.  At A=128 / C=4 that is
a 4x MAC reduction; the kernel's grid is the static bound ``n_pad +
n_groups * T`` rows, so it streams every row tile, trash tiles included
(it saves columns, not rows), and the sort+gathers cost several times
the columns saved at every shape on record (head of this file).

Exactness: identical quantized inputs accumulate in int32 exactly in
both kernels, so the compacted path is BIT-identical to the wide
kernel on the default int8 modes; float modes differ from the scatter
oracle only by f32 summation order (tests pin bit-exactness with
dyadic-rational values, tolerance otherwise).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_histogram import (DEFAULT_ROW_TILE, _col_layout, _onehot_bins,
                               _weighted_cols, bin_stride,
                               combine_hist_cols, is_quantized, pad_features)
from .vmem import hist_tiling

# leaf slots per compacted tile group.  For the default C=4 int8h mode,
# C*32 = 128 fills the lane dimension exactly, so no output column is
# wasted; whether 32 is also where the wide kernel's per-row cost starts
# to climb is unverified on a local chip.
COMPACT_GROUP = 32


def compact_slot_threshold() -> int:
    """Waves with more active slots than this take the compacted path
    (env-tunable for A/B: ``LGBM_TPU_COMPACT_SLOTS``)."""
    return int(os.environ.get("LGBM_TPU_COMPACT_SLOTS", COMPACT_GROUP))


def compact_config_ok(max_bins: int, mode: str) -> bool:
    """VMEM feasibility of the grouped kernel: the shared per-grid-cell
    model (`ops/vmem.hist_cell_ok`) at the compacted cell — same
    resident arrays at the group column count, plus the (negligible)
    [G, 1] group-active slice and [1, T] compacted leaf row, at the
    1024-row fallback tile."""
    from .vmem import hist_cell_ok
    extra = COMPACT_GROUP * 4 + 2 * 1024 * 4   # group actives + leaf row
    return hist_cell_ok(max_bins, COMPACT_GROUP, mode, extra_bytes=extra)


def compact_plan(hist_leaf: jnp.ndarray, active: jnp.ndarray,
                 num_leaf_slots: int, row_tile: int):
    """Leaf-compaction plan for one wave: ``-> (src, tile_group,
    group_active)``.

    Args:
      hist_leaf: ``[n_pad]`` int32 leaf per row (bagged-out/padding
        rows carry ``-1``) — the ROUTED vector, i.e. the wave's pending
        splits have already been applied by the route kernel.
      active: ``[A]`` int32 active leaf ids (``-1`` padding).
      num_leaf_slots: static leaf-slot count L (bounds the inverse
        lookup table).
      row_tile: the kernel's row-tile T; every group segment pads to a
        multiple of it, and every group keeps >= 1 tile so its output
        block is always zero-initialized (an unvisited block would
        hand garbage to an active-but-empty leaf, e.g. bagged to 0
        rows).

    Returns:
      src: ``[n_c]`` int32 — source row for each compacted row, ``-1``
        for segment padding; ``n_c = n_pad + n_groups * T`` (static).
      tile_group: ``[n_c // T]`` int32 — the group each row tile
        serves, non-decreasing; tiles past the used region map to the
        trailing trash group ``n_groups``.
      group_active: ``[n_groups + 1, G, 1]`` int32 — per-group
        active-leaf table (page g = slots ``[g*G, (g+1)*G)`` as a
        ``[G, 1]`` column), ``-2`` padding so neither real leaves nor
        the ``-1`` of padding rows match.  One page per group because
        the kernel's block must span the array's last two dimensions
        (Mosaic's (8, 128) block rule refuses a ``(G, 1)`` window into
        a ``[G, n_groups + 1]`` table).
    """
    n_pad = hist_leaf.shape[0]
    A = active.shape[0]
    G = COMPACT_GROUP
    T = row_tile
    n_groups = -(-A // G)
    L = num_leaf_slots

    # slot of each row in the active list; A = inactive/bagged-out
    safe_act = jnp.where(active >= 0, active, L)
    inv = jnp.full((L + 1,), A, jnp.int32).at[safe_act].set(
        jnp.arange(A, dtype=jnp.int32), mode="drop")
    slot = jnp.where(hist_leaf >= 0,
                     inv[jnp.clip(hist_leaf, 0, L - 1)], A)      # [n_pad]
    grp = jnp.where(slot < A, slot // G, n_groups)

    # stable segment sort by group: rows keep dataset order inside a
    # group (the reference's leaf-contiguous index layout)
    order = jnp.argsort(grp, stable=True)
    sorted_grp = grp[order]
    cnt = jnp.bincount(grp, length=n_groups + 1)[:n_groups]
    pc = jnp.maximum(((cnt + T - 1) // T) * T, T)    # >= 1 tile per group
    pstart = jnp.concatenate(
        [jnp.zeros(1, pc.dtype), jnp.cumsum(pc)])    # [n_groups + 1]
    ustart = jnp.concatenate(
        [jnp.zeros(1, cnt.dtype), jnp.cumsum(cnt)])  # unpadded starts
    rank = (jnp.arange(n_pad, dtype=jnp.int32)
            - ustart[jnp.clip(sorted_grp, 0, n_groups)])
    n_c = n_pad + n_groups * T                       # static bound
    dst = jnp.where(sorted_grp < n_groups,
                    pstart[jnp.clip(sorted_grp, 0, n_groups - 1)] + rank,
                    n_c)                             # trash rows: dropped
    src = jnp.full((n_c,), -1, jnp.int32).at[dst].set(
        order.astype(jnp.int32), mode="drop")

    # tile -> group.  Group starts are non-decreasing and empty groups
    # are zero-width, so "last group starting at or before this tile"
    # is the occupier; tiles past the used region land on the trash
    # block n_groups (searchsorted returns n_groups + 1 there).
    t0 = jnp.arange(n_c // T, dtype=pstart.dtype) * T
    tile_group = (jnp.searchsorted(pstart, t0, side="right")
                  .astype(jnp.int32) - 1)
    tile_group = jnp.clip(tile_group, 0, n_groups)

    ga = jnp.full(((n_groups + 1) * G,), -2, jnp.int32)
    ga = jax.lax.dynamic_update_slice(
        ga, jnp.where(active >= 0, active, -2).astype(jnp.int32), (0,))
    group_active = ga.reshape(n_groups + 1, G, 1)
    return src, tile_group, group_active


def _hist_compact_kernel(tg_ref, ga_ref, bins_ref, vals_ref, leaf_ref,
                         *refs, n_cols: int, B: int, pad_cols: int,
                         seeded: bool = False):
    """One (feature-tile, row-tile) cell of the grouped kernel.  Same
    body as the wide ``_hist_kernel`` at the group's column count; the
    accumulator zero-init fires on the first tile of each group run
    (groups are tile-contiguous, so each output block is one
    consecutive visit).

    ``seeded``: the out-of-core fold variant — the first tile of each
    group run LOADS the carried accumulator block (aliased to the
    output, see the wide kernel) instead of zeroing, making a per-block
    call a bitwise extension of the monolithic one.  The trailing trash
    group seeds garbage, adds only masked zeros, and is dropped at
    unpack — deterministic and harmless.
    """
    if seeded:
        acc_ref, out_ref = refs
    else:
        (out_ref,) = refs
    i = pl.program_id(1)
    prev = tg_ref[jnp.maximum(i - 1, 0)]
    first = jnp.logical_or(i == 0, tg_ref[i] != prev)

    @pl.when(first)
    def _():
        if seeded:
            out_ref[:] = acc_ref[:]
        else:
            out_ref[:] = jnp.zeros_like(out_ref)

    quant = vals_ref.dtype == jnp.int8
    cdt = jnp.int8 if quant else jnp.bfloat16
    oh = _onehot_bins(bins_ref[:].astype(jnp.int32), B, cdt)
    # [G, 1] group actives vs [1, T] compacted leaves -> [G, T] mask;
    # segment-padding rows carry leaf -1 and actives pad with -2, so
    # padding never matches (its bins column is garbage by design)
    m = ga_ref[:] == leaf_ref[:]
    vw = _weighted_cols(m, vals_ref[:], n_cols, pad_cols, cdt)
    out_ref[:] += jax.lax.dot_general(
        oh, vw, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32 if quant else jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("num_features", "max_bins", "num_leaf_slots", "mode",
                     "row_tile", "interpret", "raw"))
def hist_active_compact(bins_t: jnp.ndarray,
                        vals: jnp.ndarray,
                        row_leaf: jnp.ndarray,
                        active: jnp.ndarray,
                        scales: jnp.ndarray | None = None,
                        acc: jnp.ndarray | None = None,
                        *,
                        num_features: int,
                        max_bins: int,
                        num_leaf_slots: int,
                        mode: str = "hilo",
                        row_tile: int = DEFAULT_ROW_TILE,
                        interpret: bool = False,
                        raw: bool = False) -> jnp.ndarray:
    """Leaf-compacted histograms for the active leaves: same contract as
    ``hist_active_pallas`` (``-> [A, F, B, 3]`` f32) with per-row MXU
    work independent of ``A``.

    ``row_leaf`` must be the full ``[n_pad]`` padded leaf vector
    (padding rows ``-1``).  Unlike the wide kernel, ``-1`` padding
    entries of ``active`` yield exact ZERO slots (their rows never
    enter the compacted stream), matching the scatter oracle.

    ``acc`` / ``raw``: the out-of-core fold operands, mirroring the
    wide kernel — ``acc`` is the carried RAW accumulator
    (:func:`compact_raw_layout`, donated via ``input_output_aliases``),
    ``raw=True`` returns the raw grid for the next block's carry
    (finalize with :func:`unpack_hist_compact_raw`).  NOTE: on float
    modes a per-block compact call is NOT chain-exact against the
    monolithic call (block-local group padding changes f32 add order),
    so the fold seam (``learner.serial.make_hist_fold_fn``) only routes
    quantized modes here — int32 accumulation is order-independent.
    """
    F_pad, n_pad = bins_t.shape
    C = vals.shape[0]
    A = active.shape[0]
    B = bin_stride(max_bins)
    G = COMPACT_GROUP
    n_groups = -(-A // G)

    Cc, Gp, cols = _col_layout(G, mode)
    assert Cc == C and Gp == G, (Cc, C, Gp)
    seeded = acc is not None
    # the grid: identical model to the wide kernel's, at the group
    # column count
    T, feat_tile, F_grid = hist_tiling(F_pad, n_pad, B, cols, C, row_tile,
                                       seeded)
    assert n_pad % T == 0, (n_pad, T)
    pad_cols = cols - C * Gp

    # the plan's index arithmetic and the regroup of the data by it are
    # named for the device trace.  The kernel and its unpack stay bare:
    # the TPU compiler names a custom call after the scope right around
    # it (here the jitted wrapper, which the benchmark's class `hist`
    # matches), so their scope, tree.hist, is the caller's
    with jax.named_scope("tree.compact.plan"):
        src, tile_group, group_active = compact_plan(
            row_leaf.astype(jnp.int32), active.astype(jnp.int32),
            num_leaf_slots, T)
    with jax.named_scope("tree.compact.regroup"):
        sc = jnp.maximum(src, 0)
        # the regroup gather: one pass over the bins/value streams
        # applies the leaf-contiguous permutation (the
        # DataPartition::Split + ordered-gradients analog in one shot)
        bins_c = jnp.take(bins_t, sc, axis=1)            # [F_pad, n_c]
        vals_c = jnp.take(vals, sc, axis=1)              # [C, n_c]
        leaf_c = jnp.where(src >= 0, row_leaf.astype(jnp.int32)[sc],
                           -1)[None, :]                  # [1, n_c]
        bins_c = pad_features(bins_c, F_grid)
    nft = F_grid // feat_tile
    n_c = bins_c.shape[1]

    in_specs = [
        # the tile's group page, selected by the prefetched tile_group
        pl.BlockSpec((None, G, 1), lambda j, i, tg: (tg[i], 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((feat_tile, T), lambda j, i, tg: (j, i),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((C, T), lambda j, i, tg: (0, i),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, T), lambda j, i, tg: (0, i),
                     memory_space=pltpu.VMEM),
    ]
    operands = [group_active, bins_c, vals_c, leaf_c]
    if seeded:
        # the carried accumulator walks the OUTPUT's block schedule so
        # the first-tile-of-group seed-load reads the matching block;
        # aliased in place (with PrefetchScalarGridSpec the alias index
        # COUNTS the scalar-prefetch operand: tile_group=0, ga=1,
        # bins=2, vals=3, leaf=4, acc=5)
        in_specs.append(pl.BlockSpec((feat_tile * B, cols),
                                     lambda j, i, tg: (tg[i] * nft + j, 0),
                                     memory_space=pltpu.VMEM))
        operands.append(acc)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nft, n_c // T),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((feat_tile * B, cols),
                               lambda j, i, tg: (tg[i] * nft + j, 0),
                               memory_space=pltpu.VMEM),
    )
    out = pl.pallas_call(
        functools.partial(_hist_compact_kernel, n_cols=C, B=B,
                          pad_cols=pad_cols, seeded=seeded),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            ((n_groups + 1) * F_grid * B, cols),
            jnp.int32 if is_quantized(mode) else jnp.float32),
        input_output_aliases=({5: 0} if seeded else {}),
        interpret=interpret,
    )(tile_group, *operands)

    if raw:
        return out
    return unpack_hist_compact_raw(out, A, num_features, max_bins, mode,
                                   scales)


def compact_raw_layout(n_pad: int, num_active: int, num_features: int,
                       max_bins: int, mode: str,
                       row_tile: int = DEFAULT_ROW_TILE):
    """``-> (((n_groups+1)*F_grid*B, cols), dtype)`` of the RAW grouped
    accumulator — the streamed-fold carry for ``hist_active_compact``
    (twin of ``pallas_histogram.hist_raw_layout``; same tile arithmetic
    as the kernel, so it is call-invariant across same-shaped blocks)."""
    B = bin_stride(max_bins)
    G = COMPACT_GROUP
    n_groups = -(-num_active // G)
    C, Gp, cols = _col_layout(G, mode)
    _, _, F_grid = hist_tiling(num_features, n_pad, B, cols, C, row_tile,
                               seeded=True)
    dtype = jnp.int32 if is_quantized(mode) else jnp.float32
    return ((n_groups + 1) * F_grid * B, cols), dtype


def unpack_hist_compact_raw(out: jnp.ndarray, num_active: int,
                            num_features: int, max_bins: int, mode: str,
                            scales: jnp.ndarray | None = None):
    """RAW grouped accumulator -> ``[A, F, B, 3]`` f32 (trash block
    dropped).  One-shot finalization of a streamed compact fold chain."""
    A = num_active
    B = bin_stride(max_bins)
    G = COMPACT_GROUP
    n_groups = -(-A // G)
    C, Gp, cols = _col_layout(G, mode)

    def cells(o):
        F_grid = o.shape[0] // ((n_groups + 1) * B)
        o = o.reshape(n_groups + 1, F_grid, B, cols)[
            :n_groups, :, :, :C * Gp]
        o = o.reshape(n_groups, F_grid, B, C, Gp)
        o = o.transpose(0, 4, 1, 2, 3).reshape(n_groups * Gp, F_grid, B, C)
        return o[:A, :num_features]
    # `out` may be the limb pair of several shards' accumulators
    return combine_hist_cols(jax.tree.map(cells, out), mode, scales)
