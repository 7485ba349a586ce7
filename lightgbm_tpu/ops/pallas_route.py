"""Pallas TPU row-routing kernel — split application without gathers.

The TPU counterpart of the reference's ``DataPartition::Split``
(`/root/reference/src/treelearner/data_partition.hpp`, threaded index
shuffling) combined with the per-row split decision of
``Dataset::Split`` (`src/io/dataset.h:412-419`).  Our row→leaf vector
design needs, per wave, for every row: look up its leaf's chosen split
(feature, threshold, default direction, categorical mask), read the row's
bin at that feature, and move the row to the right-child id if it goes
right.

In XLA this is a chain of ``[n]``-sized gathers from small tables plus a
``take_along_axis`` over the ``[n, G]`` matrix — each of which lowers to
a serialized gather on TPU (its cost is unverified on a local chip).  Here the
whole decision runs in VMEM per row-tile:

* leaf one-hot ``[L_pad, T]`` (compare against an iota — no gather),
* ALL per-leaf split data — including the split feature's group column,
  EFB offset, bin count, default bin, and missing metadata — fetched by
  ONE small matmul ``tabs[16, L_pad] @ ohL -> [16, T]``,
* the row's stored value at its split feature's group column by a masked
  sublane reduction over the ``[G, T]`` bins tile (no gather), then the
  EFB inverse mapping ``col -> feature bin`` in registers
  (`io/dataset.py` BundleInfo encoding; identity when offset < 0),
* categorical membership by ``cat_mask[B, L_pad] @ ohL`` + a bin one-hot
  reduction.

Two leaf vectors ride together (``row_leaf`` for all rows, ``hist_leaf``
with bagged-out rows parked at -1) so both are routed in one pass.

Streams ``bins_t`` (uint8) + the leaf vectors once per wave — the whole
route costs ~1 stream pass instead of a chain of gathers.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..io.binning import MISSING_NAN, MISSING_ZERO

from .pallas_histogram import DEFAULT_ROW_TILE
from .vmem import selection_bytes

LANE = 128

# tabs row layout (per-leaf split decision table)
_T_GROUP, _T_THR, _T_DL, _T_ISCAT, _T_SEL, _T_NEWID = 0, 1, 2, 3, 4, 5
_T_OFF, _T_NB, _T_DB, _T_MT, _T_NANB = 6, 7, 8, 9, 10
# per-leaf OUTPUT value as a hi+lo bf16 pair (exact to ~2^-17 through the
# bf16 MXU pass) — used by the final per-tree route to emit each row's
# leaf value, replacing the XLA gather lv[row_leaf]
_T_LVH, _T_LVL = 11, 12
_T_ROWS = 16


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def table_precision(id_lanes: int, num_groups: int):
    """MXU precision for the per-leaf table selection dot.

    The table rows carry integers (leaf ids < L, group ids < G, bin ids
    < 256).  bf16 holds integers exactly up to 256, so when every value
    fits, the default single-pass bf16 dot is exact and 6x cheaper than
    HIGHEST (f32-via-bf16x6); larger configs keep HIGHEST.  ``id_lanes``
    is ``round_up(L, 128)`` whatever the table's width: a narrow table
    still hands out new ids up to ``L``.  The VMEM model sizes the
    one-hot by the same rule (``ops/vmem.py`` ``selection_bytes``)."""
    if selection_bytes(id_lanes, num_groups) == 2:
        return jax.lax.Precision.DEFAULT
    return jax.lax.Precision.HIGHEST


def selection_dtype(tab_prec):
    """Operand dtype for the table-selection dots: bf16-exact configs
    also BUILD the ``[L_pad, T]`` leaf one-hot and the tables in bf16 —
    the one-hot is ~1 GB of VMEM writes per wave at 1M rows in f32,
    halved here (0/1 one-hots and <256 integer tables are bf16-exact)."""
    import jax.numpy as _jnp
    return (_jnp.bfloat16 if tab_prec == jax.lax.Precision.DEFAULT
            else _jnp.float32)


def _route_kernel(bins_ref, leaf2_ref, tabs_ref, cat_ref, out_ref, *,
                  B: int, tab_prec=jax.lax.Precision.HIGHEST,
                  any_cat: bool = True):
    _route_body(bins_ref, leaf2_ref, tabs_ref, cat_ref, out_ref, B=B,
                tab_prec=tab_prec, any_cat=any_cat)


class _RowSplit(NamedTuple):
    """Each row's leaf's split, selected from the table rows: ``[1, T]``
    f32 rows of :func:`_route_select`."""
    group: jnp.ndarray
    thr: jnp.ndarray
    dl: jnp.ndarray
    iscat: jnp.ndarray
    selm: jnp.ndarray
    new_id: jnp.ndarray
    off: jnp.ndarray
    nb: jnp.ndarray
    db: jnp.ndarray
    mt: jnp.ndarray
    nanb: jnp.ndarray


def _route_select(leaf, tabs_ref, tab_prec):
    """``leaf [1, T] -> (ohL, sel_dt, split)``: the leaf one-hot and each
    row's leaf's split, ALL per-leaf split data fetched by one small
    matmul ``tabs[16, L_pad] @ ohL -> [16, T]``."""
    T = leaf.shape[1]
    L_pad = tabs_ref.shape[1]

    iota_l = jax.lax.broadcasted_iota(jnp.int32, (L_pad, T), 0)
    sel_dt = selection_dtype(tab_prec)
    ohL = (iota_l == leaf).astype(sel_dt)                     # [L_pad, T]
    # tab_prec (see table_precision): bf16-exact configs use the single
    # default pass — and build ohL/tables in bf16 outright (see
    # selection_dtype); larger ids need HIGHEST.  The cat/ohL dots below
    # stay at default precision — 0/1 operands are exact in bf16 and the
    # MXU accumulates in f32.
    sel16 = jnp.dot(tabs_ref[:].astype(sel_dt), ohL,
                    preferred_element_type=jnp.float32,
                    precision=tab_prec)                       # [16, T]
    return ohL, sel_dt, _RowSplit(*(
        sel16[k:k + 1, :] for k in (_T_GROUP, _T_THR, _T_DL, _T_ISCAT,
                                    _T_SEL, _T_NEWID, _T_OFF, _T_NB,
                                    _T_DB, _T_MT, _T_NANB)))


def _route_apply(c, sp: _RowSplit, leaf, ohL, sel_dt, leaf2_ref, cat_ref,
                 out_ref, *, B: int, any_cat: bool = True):
    """``c [1, T]`` f32, each row's stored value at its split feature's
    group column ``->`` both routed leaf vectors, written to ``out_ref``
    and returned ``(row_leaf', hist_leaf')``."""
    T = leaf.shape[1]
    # EFB inverse mapping: stored column value -> feature bin
    one = jnp.ones_like(c)
    zero = jnp.zeros_like(c)
    rank = c - sp.off
    gt_db = jnp.where(rank >= sp.db, one, zero)
    in_range = jnp.where((rank >= 0) & (rank < sp.nb - 1), one, zero)
    b_bundled = jnp.where(in_range > 0.5, rank + gt_db, sp.db)
    b = jnp.where(sp.off < -0.5, c, b_bundled)                # [1, T]

    # all masks ride as f32 0/1 values (Mosaic rejects bool-valued selects)
    is_missing = jnp.where(
        ((sp.mt == float(MISSING_NAN)) & (b == sp.nanb))
        | ((sp.mt == float(MISSING_ZERO)) & (b == sp.db)), one, zero)

    le_thr = jnp.where(b <= sp.thr, one, zero)
    num_left = jnp.where(is_missing > 0.5, sp.dl, le_thr)
    if any_cat:
        catrow = jnp.dot(cat_ref[:].astype(sel_dt), ohL,
                         preferred_element_type=jnp.float32)  # [B, T]
        iota_b = jax.lax.broadcasted_iota(
            jnp.int32, (B, T), 0).astype(jnp.float32)
        cat_left = jnp.sum(
            jnp.where(iota_b == b, catrow, 0.0), axis=0,
            keepdims=True)                                    # [1, T]
        go_left = jnp.where(sp.iscat > 0.5, cat_left, num_left)
    else:
        # no categorical features in the dataset: skip the [B, L] @
        # [L, T] membership dot + bin one-hot reduction entirely
        go_left = num_left
    in_tree = jnp.where(leaf >= 0, one, zero)
    moved = sp.selm * (one - jnp.minimum(go_left, one)) * in_tree
    nid = sp.new_id.astype(jnp.int32)

    rl = jnp.where(moved > 0.5, nid, leaf)                    # row_leaf'
    hl = leaf2_ref[1:2, :]
    out_ref[0:1, :] = rl
    hl = jnp.where(hl >= 0, rl, hl)                           # hist_leaf'
    out_ref[1:2, :] = hl
    return rl, hl


def _route_body(bins_ref, leaf2_ref, tabs_ref, cat_ref, out_ref, *, B: int,
                tab_prec=jax.lax.Precision.HIGHEST, any_cat: bool = True):
    leaf = leaf2_ref[0:1, :]                                  # [1, T] i32
    ohL, sel_dt, sp = _route_select(leaf, tabs_ref, tab_prec)
    T = leaf.shape[1]
    G_pad = bins_ref.shape[0]

    binsf = bins_ref[:].astype(jnp.int32).astype(jnp.float32)  # [G, T]
    iota_g = jax.lax.broadcasted_iota(
        jnp.int32, (G_pad, T), 0).astype(jnp.float32)
    ohG = jnp.where(iota_g == sp.group, 1.0, 0.0)             # [G, T]
    c = jnp.sum(ohG * binsf, axis=0, keepdims=True)           # [1, T]
    return _route_apply(c, sp, leaf, ohL, sel_dt, leaf2_ref, cat_ref,
                        out_ref, B=B, any_cat=any_cat)[0]


def _route_values_kernel(bins_ref, leaf2_ref, tabs_ref, cat_ref, out_ref,
                         val_ref, *, B: int,
                         tab_prec=jax.lax.Precision.HIGHEST,
                         any_cat: bool = True):
    """Route + emit each row's POST-route leaf value (final tree pass).

    The value rides the tabs as a hi+lo bf16 pair selected by a second
    leaf one-hot built from the routed ids; rows outside the tree
    (leaf -1, padding) emit 0."""
    rl = _route_body(bins_ref, leaf2_ref, tabs_ref, cat_ref, out_ref, B=B,
                     tab_prec=tab_prec, any_cat=any_cat)
    T = rl.shape[1]
    L_pad = tabs_ref.shape[1]
    iota_l = jax.lax.broadcasted_iota(jnp.int32, (L_pad, T), 0)
    # stays f32: the LVL row is the f32 RESIDUAL of the hi/lo pair —
    # not bf16-representable; a bf16 cast here would silently collapse
    # the pair back to bf16 leaf values (the 0.006-AUC drift the hi/lo
    # route values exist to prevent)
    ohL2 = (iota_l == rl).astype(jnp.float32)
    sel2 = jnp.dot(tabs_ref[_T_LVH:_T_LVL + 1, :], ohL2,
                   preferred_element_type=jnp.float32)        # [2, T]
    val_ref[0:1, :] = sel2[0:1, :] + sel2[1:2, :]


def _leaf_tables(feature, threshold, default_left, is_categorical, sel,
                 new_id, missing_types, nan_bins, default_bins, feat_group,
                 feat_offset, num_bins, L_pad, leaf_values=None):
    """Pack the [16, L_pad] per-leaf decision table (tiny [L] gathers)."""
    L = feature.shape[0]
    f = feature
    tabs = jnp.zeros((_T_ROWS, L_pad), jnp.float32)
    tabs = tabs.at[_T_GROUP, :L].set(feat_group[f].astype(jnp.float32))
    tabs = tabs.at[_T_THR, :L].set(threshold.astype(jnp.float32))
    tabs = tabs.at[_T_DL, :L].set(default_left.astype(jnp.float32))
    tabs = tabs.at[_T_ISCAT, :L].set(is_categorical.astype(jnp.float32))
    tabs = tabs.at[_T_SEL, :L].set(sel.astype(jnp.float32))
    tabs = tabs.at[_T_NEWID, :L].set(new_id.astype(jnp.float32))
    tabs = tabs.at[_T_OFF, :L].set(feat_offset[f].astype(jnp.float32))
    tabs = tabs.at[_T_NB, :L].set(num_bins[f].astype(jnp.float32))
    tabs = tabs.at[_T_DB, :L].set(default_bins[f].astype(jnp.float32))
    tabs = tabs.at[_T_MT, :L].set(missing_types[f].astype(jnp.float32))
    tabs = tabs.at[_T_NANB, :L].set(nan_bins[f].astype(jnp.float32))
    if leaf_values is not None:
        from .pallas_histogram import split_hi_lo
        hi, lo = split_hi_lo(leaf_values.astype(jnp.float32))
        tabs = tabs.at[_T_LVH, :L].set(hi)
        tabs = tabs.at[_T_LVL, :L].set(lo)
    return tabs


@functools.partial(jax.jit,
                   static_argnames=("row_tile", "interpret", "any_cat"))
def route_rows_pallas(bins_t: jnp.ndarray,
                      leaf2: jnp.ndarray,
                      feature: jnp.ndarray,
                      threshold: jnp.ndarray,
                      default_left: jnp.ndarray,
                      is_categorical: jnp.ndarray,
                      cat_mask: jnp.ndarray,
                      sel: jnp.ndarray,
                      new_id: jnp.ndarray,
                      missing_types: jnp.ndarray,
                      nan_bins: jnp.ndarray,
                      default_bins: jnp.ndarray,
                      feat_group: jnp.ndarray,
                      feat_offset: jnp.ndarray,
                      num_bins: jnp.ndarray,
                      *,
                      row_tile: int = DEFAULT_ROW_TILE,
                      interpret: bool = False,
                      any_cat: bool = True) -> jnp.ndarray:
    """Apply this wave's splits to both leaf vectors: ``-> [2, n_pad]``.

    Args:
      bins_t: ``[G_pad, n_pad]`` uint8 (shared with the hist kernel).
      leaf2: ``[2, n_pad]`` int32 — row 0 = row_leaf (all rows), row 1 =
        hist_leaf (bagged-out rows parked at -1).  Padding rows = -1.
      feature/threshold/default_left/is_categorical/sel/new_id: ``[L]``
        per-leaf split decision tables (from the wave's SplitResult);
        ``sel`` marks the leaves actually split this wave.
      cat_mask: ``[L, B]`` bool — FEATURE bins going left (categorical).
      missing_types/nan_bins/default_bins/num_bins: ``[F]`` per-feature
        metadata (feature-bin space).
      feat_group/feat_offset: ``[F]`` EFB layout (offset -1 = identity).

    Rows whose leaf is unselected, bagged out, or padding are unchanged.
    """
    return _route_call(bins_t, leaf2, feature, threshold, default_left,
                       is_categorical, cat_mask, sel, new_id, missing_types,
                       nan_bins, default_bins, feat_group, feat_offset,
                       num_bins, None, row_tile, interpret, any_cat)


@functools.partial(jax.jit,
                   static_argnames=("row_tile", "interpret", "any_cat"))
def route_rows_values_pallas(bins_t: jnp.ndarray,
                             leaf2: jnp.ndarray,
                             feature: jnp.ndarray,
                             threshold: jnp.ndarray,
                             default_left: jnp.ndarray,
                             is_categorical: jnp.ndarray,
                             cat_mask: jnp.ndarray,
                             sel: jnp.ndarray,
                             new_id: jnp.ndarray,
                             missing_types: jnp.ndarray,
                             nan_bins: jnp.ndarray,
                             default_bins: jnp.ndarray,
                             feat_group: jnp.ndarray,
                             feat_offset: jnp.ndarray,
                             num_bins: jnp.ndarray,
                             leaf_values: jnp.ndarray,
                             *,
                             row_tile: int = DEFAULT_ROW_TILE,
                             interpret: bool = False,
                             any_cat: bool = True):
    """Final per-tree route: apply pending splits AND emit each row's
    leaf value — ``-> (leaf2 [2, n_pad] i32, values [n_pad] f32)``.

    Replaces the score-update gather ``leaf_value[row_leaf]`` (an
    XLA-serialized op; its cost is unverified on a local chip) with one extra table-row
    dot inside the route pass.  Values ride the MXU as hi+lo bf16 pairs
    (exact to ~2^-17); out-of-tree rows (leaf -1 / padding) emit 0.
    """
    return _route_call(bins_t, leaf2, feature, threshold, default_left,
                       is_categorical, cat_mask, sel, new_id, missing_types,
                       nan_bins, default_bins, feat_group, feat_offset,
                       num_bins, leaf_values, row_tile, interpret, any_cat)


def _route_call(bins_t, leaf2, feature, threshold, default_left,
                is_categorical, cat_mask, sel, new_id, missing_types,
                nan_bins, default_bins, feat_group, feat_offset, num_bins,
                leaf_values, row_tile, interpret, any_cat=True):
    """Shared table/spec construction for both route entry points."""
    G_pad, n_pad = bins_t.shape
    L = feature.shape[0]
    B = cat_mask.shape[1]
    T = row_tile
    assert n_pad % T == 0
    L_pad = _round_up(max(L, 8), LANE)
    with_values = leaf_values is not None

    tabs = _leaf_tables(feature, threshold, default_left, is_categorical,
                        sel, new_id, missing_types, nan_bins, default_bins,
                        feat_group, feat_offset, num_bins, L_pad,
                        leaf_values=leaf_values)
    cat = jnp.zeros((B, L_pad), jnp.float32)
    cat = cat.at[:, :L].set(cat_mask.T.astype(jnp.float32))

    in_specs = [
        pl.BlockSpec((G_pad, T), lambda r: (0, r),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((2, T), lambda r: (0, r),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((_T_ROWS, L_pad), lambda r: (0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((B, L_pad), lambda r: (0, 0),
                     memory_space=pltpu.VMEM),
    ]
    leaf2_spec = pl.BlockSpec((2, T), lambda r: (0, r),
                              memory_space=pltpu.VMEM)
    tab_prec = table_precision(L_pad, G_pad)
    if not with_values:
        return pl.pallas_call(
            functools.partial(_route_kernel, B=B, tab_prec=tab_prec,
                              any_cat=any_cat),
            grid=(n_pad // T,),
            in_specs=in_specs,
            out_specs=leaf2_spec,
            out_shape=jax.ShapeDtypeStruct((2, n_pad), jnp.int32),
            interpret=interpret,
        )(bins_t, leaf2, tabs, cat)

    leaf2_new, vals = pl.pallas_call(
        functools.partial(_route_values_kernel, B=B, tab_prec=tab_prec,
                          any_cat=any_cat),
        grid=(n_pad // T,),
        in_specs=in_specs,
        out_specs=(
            leaf2_spec,
            pl.BlockSpec((1, T), lambda r: (0, r),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((2, n_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        ),
        interpret=interpret,
    )(bins_t, leaf2, tabs, cat)
    return leaf2_new, vals[0]


def route_rows_xla(bins: jnp.ndarray,
                   leaf2: jnp.ndarray,
                   feature: jnp.ndarray,
                   threshold: jnp.ndarray,
                   default_left: jnp.ndarray,
                   is_categorical: jnp.ndarray,
                   cat_mask: jnp.ndarray,
                   sel: jnp.ndarray,
                   new_id: jnp.ndarray,
                   missing_types: jnp.ndarray,
                   nan_bins: jnp.ndarray,
                   default_bins: jnp.ndarray,
                   feat_group: jnp.ndarray,
                   feat_offset: jnp.ndarray,
                   num_bins: jnp.ndarray) -> jnp.ndarray:
    """Same contract from untransposed ``[n, G]`` bins (CPU backend +
    equivalence oracle for the kernel)."""
    n = bins.shape[0]
    rl = leaf2[0, :n]
    hl = leaf2[1, :n]
    safe = jnp.maximum(rl, 0)
    f = feature[safe]
    g = feat_group[f]
    # numcheck: disable=NUM001 -- int32 one-hot group select (g is
    # feat_group, not a gradient); integer adds are exact in any order
    c = jnp.sum(jnp.where(g[:, None] == jnp.arange(bins.shape[1])[None, :],
                          bins.astype(jnp.int32), 0), axis=1)
    b = unbundle_bin(c, feat_offset[f], num_bins[f], default_bins[f])
    mt = missing_types[f]
    is_missing = (((mt == MISSING_NAN) & (b == nan_bins[f]))
                  | ((mt == MISSING_ZERO) & (b == default_bins[f])))
    num_left = jnp.where(is_missing, default_left[safe], b <= threshold[safe])
    cat_left = cat_mask[safe, jnp.minimum(b, cat_mask.shape[1] - 1)]
    go_left = jnp.where(is_categorical[safe], cat_left, num_left)
    moved = sel[safe] & ~go_left & (rl >= 0)
    rl2 = jnp.where(moved, new_id[safe], rl)
    hl2 = jnp.where(hl >= 0, rl2, hl)
    out = jnp.stack([rl2, hl2])
    if leaf2.shape[1] != n:
        pad = jnp.full((2, leaf2.shape[1] - n), -1, jnp.int32)
        out = jnp.concatenate([out, pad], axis=1)
    return out


def unbundle_bin(col: jnp.ndarray, off: jnp.ndarray, nb: jnp.ndarray,
                 db: jnp.ndarray) -> jnp.ndarray:
    """EFB inverse mapping: stored column value -> feature bin
    (`io/dataset.py` BundleInfo encoding; identity when ``off < 0``)."""
    rank = col - off
    in_range = (rank >= 0) & (rank < nb - 1)
    b_bundled = jnp.where(in_range, rank + (rank >= db), db)
    return jnp.where(off < 0, col, b_bundled)
