"""Distributed tree learners: data- / feature- / voting-parallel.

TPU-native redesign of the reference parallel learners
(`/root/reference/src/treelearner/feature_parallel_tree_learner.cpp`,
`data_parallel_tree_learner.cpp`, `voting_parallel_tree_learner.cpp`,
shared sync helpers `parallel_tree_learner.h:184-207`).  The reference
couples each strategy to socket/MPI collectives; here each strategy is a
*wave closure* (histogram the active leaves → subtract siblings → rescan)
run inside one ``shard_map`` over a ``jax.sharding.Mesh``, with XLA
collectives on ICI/DCN:

* **data-parallel** — rows sharded; local active-leaf histograms merged
  with ``lax.psum`` (the ReduceScatter+owner-scan of
  `data_parallel_tree_learner.cpp:147-162` collapses to one collective of
  the wave's ``[A, F, B, 3]`` block — the smaller-child scheduling halves
  the reference's wire bytes the same way it halves its FLOPs).
* **feature-parallel** — rows replicated, feature columns statically
  sliced per shard (`feature_parallel_tree_learner.cpp:31-50`'s
  load-balance partition becomes an equal static slice); each shard keeps
  histogram state only for its own columns; local best splits are
  ``all_gather``-ed and the global argmax-by-gain picked everywhere (the
  ``SyncUpGlobalBestSplit`` max-by-gain reducer,
  `parallel_tree_learner.h:184-207`).
* **voting-parallel (PV-Tree)** — rows sharded; histogram state stays
  local; each shard votes its top-k features per changed leaf by local
  gain; votes are ``psum``-ed and the 2k global winners selected by
  summed local gains (`voting_parallel_tree_learner.cpp:164-193`
  GlobalVoting); only the winners' histogram columns are ``psum``-ed
  (comm O(2A·2k·B) instead of O(2A·F·B)), then the final scan runs on
  the merged columns.

All three return bit-identical trees on every shard (the reference's
distributed-determinism requirement, `application.cpp:249-254`), and the
data-parallel learner for every shard count, in the int8 modes: the
shards round their rows against ONE pair of scales (a ``pmax`` of two
scalars a tree, :func:`global_scales`), what crosses them is the cells'
integer code sums in two 16-bit limbs (:func:`psum_codes`: exact past
2^31, where float32 partials lost the integers above 2^24), and the sum
is dequantized once, as one chip dequantizes its own.  The mode that
runs is judged on what sums in int32, a SHARD's rows.  So
``tree_learner=data`` on 1, 2, 4 or 128 shards grows the serial
learner's trees bit for bit
(``tests/test_parallel.py::test_quantised_data_parallel_grows_the_serial_tree``;
on four v5e chips ``chip_smoke.py --chips 4``: 32 identical trees at
1,048,576 rows, PR 28; before, the first flip came at tree 1).  With
more rows in all than float32 counts exactly (2^24), the finished
leaves' rows are counted in integers (``leaf_row_counts``).  On a Pallas
backend each shard's final route kernel emits its rows' leaf values, so
the mesh block's score update is the serial path's multiply-add and no
gather.  Measured on four v5e chips at 4 x 13,281,250 rows x 67 x 63
bins x 255 leaves (`PERF.md` §5, PR 28): the exchange is 1.0-1.2 ms of a
0.66-0.71 s iteration.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.flight_recorder import record as _fr_record

# the bare name `shard_map` is what the static analyzers' name-based
# root detection (tpulint callgraph, spmdcheck) keys on to see the
# wrapped function as a traced entry point
shard_map = jax.shard_map

from ..io.device import DeviceData
from ..learner.serial import (BuiltTree, GrowthParams, apply_hist_wave,
                              build_tree, default_hist_mode,
                              effective_hist_mode, emits_row_values,
                              make_hist_fn,
                              resolve_backend, split_cache_enabled,
                              uses_pallas)
from ..ops.pallas_histogram import (MAX_CODE_SHARDS, CodeLimbs, bin_stride,
                                    carry_limbs, code_limbs, is_quantized,
                                    quant_scales)
from ..ops.split import (K_MIN_SCORE, SplitParams, SplitResult,
                         find_best_splits)


def psum_codes(x, axis: str, num_shards: int) -> CodeLimbs:
    """The sum over ``axis`` of int32 code sums, exact whatever the
    data: ``-> (hi, lo)`` int32 limbs of each cell's total, ``hi * 2^16
    + lo`` with ``lo`` in ``[0, 2^16)``, for ``dequant_hist`` to round
    to float32 once.  ``x`` is a shard's int32 code sums or, from a
    shard of more rows than one int32 cell sums exactly, the limb pair
    of its row chunks' sums (``sum_code_limbs``).

    An int32 cell is exact while its rows are at most
    ``_INT8_ROW_LIMIT`` (`learner/serial.py`: a chunk), but the total of
    4 x 13.28M rows x 127 is past 2^31, and float32 partial sums would
    lose the integers above 2^24 that the one-chip path keeps.  So a
    cell crosses the shards as two 16-bit limbs (``code_limbs``: ``hi``
    in ``[-2^15, 2^15)``, ``lo`` in ``[0, 2^16)``), each summed in
    int32: over ``S`` parts (shards x chunks) ``|sum hi| <= S * 2^15``
    and ``sum lo < S * 2^16``, nowhere near 2^31.  The carry of ``sum
    lo`` moves to the high limb, which leaves ``|hi| <= S * (2^15 +
    1)``: under the 2^24 to which ``dequant_hist`` converts exactly, and
    under the 2^31 / 127 its hi/lo pairs need, for ``S <= 511``
    (``MAX_CODE_SHARDS``; ``effective_hist_mode`` runs a float mode
    past it).  The limbs of a total are unique, so a total that one
    chip can hold in int32 dequantizes to the same floats whether one
    accumulator summed it or many did: `tests/test_parallel.py` holds 1,
    2 and 4 shards, and 1, 2 and 4 chunks, to the serial learner's
    trees, bit for bit."""
    if num_shards > MAX_CODE_SHARDS:
        raise ValueError(
            f"the exact exchange of quantized histograms holds for at most "
            f"{MAX_CODE_SHARDS} row shards, not {num_shards}")
    limbs = x if isinstance(x, CodeLimbs) else code_limbs(x)
    return carry_limbs(*jax.lax.psum(limbs, axis))


class Psum:
    """The row-sharded learners' sum over the shards of ``axis``:
    float32 arrays by ``lax.psum``, int32 code sums (the quantized
    modes' histograms and root totals) exactly by :func:`psum_codes`,
    as the limb pairs ``dequant_hist`` takes.

    A call records the collective's trace-time fingerprint — each
    process traces its own program, so THIS is where a rank-divergent
    schedule would be born — and reduces under the scope
    ``collective.<what>``, which stamps the flight-recorder site's name
    into the HLO op metadata: profiler captures and HLO dumps name the
    collective by the same site the runtime digest uses.  ``reduce`` is
    the reduction alone: the benchmark's rehearsal of a shard left out
    of the exchange (`benchmark/tests/test_correct_dp.py`) puts its
    fault there."""

    def __init__(self, axis: str, num_shards: int):
        self.axis, self.num_shards = axis, num_shards

    def reduce(self, x, what: str = "hist_psum"):
        def limbs(a):
            return isinstance(a, CodeLimbs)

        def one(a):
            if limbs(a) or jnp.issubdtype(a.dtype, jnp.integer):
                return psum_codes(a, self.axis, self.num_shards)
            return jax.lax.psum(a, self.axis)
        with jax.named_scope("collective." + what):
            return jax.tree.map(one, x, is_leaf=limbs)

    def __call__(self, x, what: str = "hist_psum"):
        _fr_record("parallel.learners." + what, "psum", self.axis, x)
        return self.reduce(x, what)

    def counts(self, x: jnp.ndarray) -> jnp.ndarray:
        """The sum over the shards of int32 row counts, in int32 (a
        count is at most the rows of all shards)."""
        _fr_record("parallel.learners.count_psum", "psum", self.axis, x)
        with jax.named_scope("collective.count_psum"):
            return jax.lax.psum(x, self.axis)


def global_scales(grad, hess, axis: str) -> jnp.ndarray:
    """The quantized modes' rounding scales ``[2] (max|g|, max|h|)``
    over ALL shards' rows: every shard rounds a row to the code the
    serial learner gives it, so the code sums add up to the serial
    histogram whatever the number of shards (a shard's own largest
    magnitude, `pack_values_q`'s default, makes the tree depend on how
    the rows were cut)."""
    local = quant_scales(grad, hess)
    _fr_record("parallel.learners.scale_pmax", "pmax", axis, local)
    with jax.named_scope("collective.scale_pmax"):
        return jax.lax.pmax(local, axis)


def _sync_global_best(best: SplitResult, axis: str) -> SplitResult:
    """All-gather per-leaf SplitResults and keep the max-gain one — the
    ``SyncUpGlobalBestSplit`` reducer (`parallel_tree_learner.h:184-207`)."""
    _fr_record("parallel.learners.sync_global_best", "all_gather", axis,
               best.gain)
    with jax.named_scope("collective.sync_global_best"):
        gathered = jax.tree.map(
            lambda a: jax.lax.all_gather(a, axis), best)  # [S, 2A, ...]
    win = jnp.argmax(gathered.gain, axis=0)               # [2A]

    def pick(a):
        l = jnp.arange(a.shape[1])
        return a[win, l]

    return jax.tree.map(pick, gathered)


# ---------------------------------------------------------------------------
# feature-parallel
# ---------------------------------------------------------------------------
def make_feature_parallel_strategy(data: DeviceData, grad, hess,
                                   params: GrowthParams, feature_mask,
                                   axis: str, num_shards: int,
                                   hist_backend: str = "auto",
                                   hist_mode=None):
    """Features statically sliced per shard; per-shard histogram state
    covers only the local columns; global best via all_gather + argmax.

    EFB composes (VERDICT r3 #7): features are sliced in LOGICAL order
    and each shard gathers its features' group columns from the bundle
    store — a feature whose group is shared simply histograms its own
    copy of the group column, then unbundles its slice, exactly like the
    serial path (reference bundles identically on every rank for all
    learner types, dataset.cpp:138-210)."""
    F = data.num_features
    f_local = -(-F // num_shards)          # ceil
    L = params.num_leaves

    idx = jax.lax.axis_index(axis)
    start = jnp.minimum(idx * f_local, F - f_local)
    nb_loc = jax.lax.dynamic_slice_in_dim(data.num_bins, start, f_local)
    db_loc = jax.lax.dynamic_slice_in_dim(data.default_bins, start, f_local)
    mt_loc = jax.lax.dynamic_slice_in_dim(data.missing_types, start, f_local)
    ic_loc = jax.lax.dynamic_slice_in_dim(data.is_categorical, start, f_local)
    nanb_loc = jax.lax.dynamic_slice_in_dim(data.nan_bins, start, f_local)
    if data.is_bundled:
        fg_loc = jax.lax.dynamic_slice_in_dim(data.feat_group, start,
                                              f_local)
        off_loc = jax.lax.dynamic_slice_in_dim(data.feat_offset, start,
                                               f_local)
        bins_loc = jnp.take(data.bins, fg_loc, axis=1)   # group copies
    else:
        off_loc = jnp.full(f_local, -1, jnp.int32)
        bins_loc = jax.lax.dynamic_slice_in_dim(data.bins, start,
                                                f_local, 1)
    zero_off = jnp.zeros(f_local, jnp.int32)  # unused by the padded grid
    data_loc = DeviceData(bins_loc, zero_off, nb_loc, db_loc, mt_loc, ic_loc,
                          nanb_loc, jnp.arange(f_local, dtype=jnp.int32),
                          off_loc,
                          data.total_bins, data.max_bins,
                          data.has_categorical,
                          max_group_bins=data.max_group_bins)
    hist_fn = make_hist_fn(data_loc, grad, hess, L, hist_backend,
                           hist_mode)

    # mask features overlapping a previous shard (end-clamp duplicates)
    fid_global = start + jnp.arange(f_local)
    owned = fid_global >= idx * f_local
    fmask = owned
    if feature_mask is not None:
        fmask = fmask & jax.lax.dynamic_slice_in_dim(
            feature_mask, start, f_local)

    def wave(hist_state, hist_leaf, act_small, act_parent, act_sibling,
             lsg, lsh, lc):
        new_h = hist_fn(hist_leaf, act_small)            # [A, f_local, B, 3]
        hist_state, ids, grid = apply_hist_wave(
            hist_state, new_h, act_small, act_parent, act_sibling, L)
        if not split_cache_enabled():
            # split-cache escape hatch (ISSUE 9): full per-wave rescan
            # of the local-column histogram state — the post-allgather
            # global best is cached identically either way
            ids = jnp.arange(L, dtype=jnp.int32)
            grid = hist_state
        safe = jnp.clip(ids, 0, L - 1)
        if data.is_bundled:
            from ..ops.histogram import unbundle_grid
            grid = unbundle_grid(grid, lsg[safe], lsh[safe], lc[safe],
                                 jnp.arange(f_local, dtype=jnp.int32),
                                 off_loc, nb_loc, db_loc,
                                 bin_stride(data.max_bins))
        best = find_best_splits(grid, lsg[safe], lsh[safe], lc[safe],
                                nb_loc, mt_loc, db_loc, ic_loc,
                                params.split, fmask,
                                any_categorical=data.has_categorical,
                                any_missing=data.has_missing)
        best = best._replace(feature=(best.feature + start).astype(jnp.int32))
        return hist_state, ids, _sync_global_best(best, axis)

    return wave, f_local


# ---------------------------------------------------------------------------
# voting-parallel (PV-Tree)
# ---------------------------------------------------------------------------
def make_voting_parallel_strategy(data: DeviceData, grad, hess,
                                  params: GrowthParams, feature_mask,
                                  axis: str, num_shards: int, top_k: int,
                                  hist_backend: str = "auto",
                                  hist_mode=None, scales=None):
    """PV-Tree: local active-leaf hists -> local vote -> global top-2k
    features -> psum only their histogram columns -> final scan."""
    F = data.num_features
    L = params.num_leaves
    k2 = min(2 * top_k, F)
    hist_fn = make_hist_fn(data, grad, hess, L, hist_backend, hist_mode,
                           scales=scales)
    # local constraints scaled 1/S like the reference
    # (voting_parallel_tree_learner.cpp:55-56)
    local_params = params.split._replace(
        min_data_in_leaf=max(1, params.split.min_data_in_leaf // num_shards),
        min_sum_hessian_in_leaf=params.split.min_sum_hessian_in_leaf
        / num_shards)

    def wave(hist_state, hist_leaf, act_small, act_parent, act_sibling,
             lsg, lsh, lc):
        new_h = hist_fn(hist_leaf, act_small)            # local histograms
        hist_state, ids, grid = apply_hist_wave(
            hist_state, new_h, act_small, act_parent, act_sibling, L)
        if not split_cache_enabled():
            # escape hatch: vote + winner-column psum over every leaf
            # slot (per-slot results are independent, so the selected
            # splits — and the model — are byte-identical)
            ids = jnp.arange(L, dtype=jnp.int32)
            grid = hist_state
        safe = jnp.clip(ids, 0, L - 1)
        # local leaf totals from the local histogram (column 0's bins
        # contain every in-bag local row exactly once)
        loc_sum_g = jnp.sum(grid[:, 0, :, 0], axis=-1)
        loc_sum_h = jnp.sum(grid[:, 0, :, 1], axis=-1)
        loc_cnt = jnp.sum(grid[:, 0, :, 2], axis=-1)
        if data.is_bundled:
            from ..ops.histogram import unbundle_grid
            from ..ops.pallas_histogram import bin_stride
            grid = unbundle_grid(grid, loc_sum_g, loc_sum_h, loc_cnt,
                                 data.feat_group, data.feat_offset,
                                 data.num_bins, data.default_bins,
                                 bin_stride(data.max_bins))
        local_gain = _per_feature_gains(grid, loc_sum_g, loc_sum_h, loc_cnt,
                                        data, local_params, feature_mask)
        # top-k features per changed leaf locally; exchange ONLY the
        # (feature id, gain) pairs — O(k) wire bytes like the
        # reference's 2x k LightSplitInfo allgather
        # (voting_parallel_tree_learner.cpp:164-193), NOT a dense
        # [2A, F] votes psum whose volume rivals the histogram psum it
        # exists to avoid on wide data (VERDICT r3 #6)
        kk = min(top_k, F)
        _, local_top = jax.lax.top_k(local_gain, kk)
        local_vals = jnp.take_along_axis(local_gain, local_top, axis=1)
        local_vals = jnp.where(
            jnp.isfinite(local_vals) & (local_vals > K_MIN_SCORE / 2),
            local_vals, 0.0)
        _fr_record("parallel.learners.voting.vote_gather", "all_gather",
                   axis, local_top)
        with jax.named_scope("collective.vote_gather"):
            g_top = jax.lax.all_gather(local_top, axis)  # [S, 2A, k] i32
        _fr_record("parallel.learners.voting.vote_gather", "all_gather",
                   axis, local_vals)
        with jax.named_scope("collective.vote_gather"):
            g_val = jax.lax.all_gather(local_vals, axis)  # [S, 2A, k] f32
        # GlobalVoting: weighted-gain vote tally, scattered LOCALLY
        rows = jnp.arange(local_gain.shape[0])[None, :, None]
        votes = jnp.zeros(local_gain.shape).at[rows, g_top].add(g_val)
        _, sel_feats = jax.lax.top_k(votes, k2)          # [2A, k2]
        # psum ONLY the selected features' histogram columns
        sel_grid = jnp.take_along_axis(
            grid, sel_feats[:, :, None, None], axis=1)   # [2A, k2, B, 3]
        _fr_record("parallel.learners.voting.sel_psum", "psum", axis,
                   sel_grid)
        with jax.named_scope("collective.sel_psum"):
            sel_grid = jax.lax.psum(sel_grid, axis)
        nb = data.num_bins[sel_feats]
        mt = data.missing_types[sel_feats]
        db = data.default_bins[sel_feats]
        ic = data.is_categorical[sel_feats]
        best = _find_best_per_leaf_features(
            sel_grid, lsg[safe], lsh[safe], lc[safe], nb, mt, db, ic,
            params.split, data.has_categorical, data.has_missing)
        gfeat = jnp.take_along_axis(sel_feats, best.feature[:, None],
                                    axis=1)[:, 0]
        return hist_state, ids, best._replace(
            feature=gfeat.astype(jnp.int32))

    return wave


def _per_feature_gains(grid, lsg, lsh, lc, data: DeviceData,
                       sp: SplitParams, feature_mask):
    """Best gain per (changed-leaf, feature) — the voting criterion.  A
    simplified (numerical, missing-right) scan: votes only need a ranking,
    the exact scan runs later on the merged winners."""
    from ..ops.split import _split_gain, leaf_split_gain
    g = grid[..., 0]; h = grid[..., 1]; c = grid[..., 2]
    clg = jnp.cumsum(g, axis=-1)
    clh = jnp.cumsum(h, axis=-1)
    clc = jnp.cumsum(c, axis=-1)
    tg = lsg[:, None, None]; th = lsh[:, None, None]; tc = lc[:, None, None]
    gains = _split_gain(clg, clh, tg - clg, th - clh,
                        sp.lambda_l1, sp.lambda_l2)
    ok = ((clc >= sp.min_data_in_leaf) & (tc - clc >= sp.min_data_in_leaf)
          & (clh >= sp.min_sum_hessian_in_leaf)
          & (th - clh >= sp.min_sum_hessian_in_leaf))
    bin_ids = jnp.arange(grid.shape[2])
    ok &= (bin_ids[None, None, :] < (data.num_bins - 1)[None, :, None])
    gains = jnp.where(ok, gains, K_MIN_SCORE)
    per_feat = jnp.max(gains, axis=-1)
    parent = leaf_split_gain(lsg, lsh, sp.lambda_l1, sp.lambda_l2)
    per_feat = per_feat - parent[:, None]
    if feature_mask is not None:
        per_feat = jnp.where(feature_mask[None, :], per_feat, K_MIN_SCORE)
    return per_feat


def _find_best_per_leaf_features(sel_grid, lsg, lsh, lc, nb, mt, db, ic,
                                 sp: SplitParams, any_cat: bool,
                                 any_missing: bool = True):
    """find_best_splits variant where each leaf has its OWN feature set
    (per-leaf gathered columns): vmap the single-leaf scan over leaves."""
    def one_leaf(grid_l, sg, sh, cc, nb_l, mt_l, db_l, ic_l):
        r = find_best_splits(grid_l[None], sg[None], sh[None], cc[None],
                             nb_l, mt_l, db_l, ic_l, sp, None,
                             any_categorical=any_cat,
                             any_missing=any_missing)
        return jax.tree.map(lambda a: a[0], r)
    return jax.vmap(one_leaf)(sel_grid, lsg, lsh, lc, nb, mt, db, ic)


# ---------------------------------------------------------------------------
# shard_map driver
# ---------------------------------------------------------------------------
def build_tree_distributed(mesh: Mesh, axis: str, learner_type: str,
                           data: DeviceData, grad, hess,
                           params: GrowthParams,
                           bag_mask=None, feature_mask=None,
                           top_k: int = 20,
                           hist_backend: str = "auto",
                           hist_mode=None) -> BuiltTree:
    """Run one tree build as an SPMD program over `mesh`.

    Row-sharded inputs (data/voting): ``bins``, ``grad``, ``hess``,
    ``bag_mask`` are sharded on the leading axis; tree outputs are
    replicated; ``row_leaf`` stays sharded.  Feature-parallel replicates
    rows and slices features inside the shard.

    Where the kernels histogram quantized values (an int8 mode that a
    SHARD's rows keep exact in int32: the mode is judged on
    ``bins.shape[0]`` inside the shard), the row-sharded learners round
    against the scales of all shards (:func:`global_scales`) and the
    data-parallel learner exchanges integer code sums (:class:`Psum`):
    its trees are the serial learner's, bit for bit, for any number of
    shards.
    """
    num_shards = mesh.shape[axis]
    row_shard = learner_type in ("data", "voting")
    n = data.num_data
    vec = P(axis) if row_shard else P()

    if bag_mask is None:
        bag_mask = jnp.ones(n, bool)
    if feature_mask is None:
        feature_mask = jnp.ones(data.num_features, bool)

    # static fields are closed over; only arrays cross the shard_map
    # boundary.  Derived from the pytree aux so new static fields can't
    # silently drift out of sync with DeviceData
    statics = data.tree_flatten()[1]

    # what build_tree will resolve inside the shard_map: the mode on the
    # rows that sum in int32 (a shard's where rows are sharded), the
    # backend on shapes that do not depend on the rows
    mode = effective_hist_mode(hist_mode or default_hist_mode(),
                               n // num_shards if row_shard else n,
                               num_shards if learner_type == "data" else 1)
    backend = resolve_backend(data, params.num_leaves, hist_backend, mode)
    quantized_kernels = (row_shard and uses_pallas(backend)
                         and is_quantized(mode))

    def step(bins, offs, nb, db, mt, ic, nanb, fg, fo, grad_l, hess_l,
             bag_l, fmask_l):
        data_l = DeviceData(bins, offs, nb, db, mt, ic, nanb, fg, fo,
                            *statics)
        nhf = None
        scales = (global_scales(grad_l, hess_l, axis) if quantized_kernels
                  else None)
        if learner_type == "data":
            strategy = None        # serial strategy + histogram psum
            psum_fn = Psum(axis, num_shards)
        elif learner_type == "feature":
            strategy, nhf = make_feature_parallel_strategy(
                data_l, grad_l, hess_l, params, fmask_l, axis, num_shards,
                hist_backend, hist_mode)
            psum_fn = None
        elif learner_type == "voting":
            strategy = make_voting_parallel_strategy(
                data_l, grad_l, hess_l, params, fmask_l, axis, num_shards,
                top_k, hist_backend, hist_mode, scales)
            psum_fn = Psum(axis, num_shards)
        else:
            raise ValueError(learner_type)
        return build_tree(data_l, grad_l, hess_l, params, bag_mask=bag_l,
                          feature_mask=fmask_l, strategy=strategy,
                          psum_fn=psum_fn, hist_backend=hist_backend,
                          num_hist_features=nhf, hist_mode=hist_mode,
                          scales=scales)

    # the data-parallel learner on a Pallas backend emits each shard's
    # rows' leaf values from its final route kernel, as the serial
    # learner does (no gather in the score update); elsewhere row_value
    # is empty [0]
    emits = emits_row_values(learner_type == "data", backend)
    out_spec = BuiltTree(
        feature=P(), threshold_bin=P(), default_left=P(), is_categorical=P(),
        cat_mask=P(), left_child=P(), right_child=P(), gain=P(),
        internal_value=P(), internal_count=P(), leaf_value=P(),
        leaf_count=P(), leaf_depth=P(), num_leaves=P(), row_leaf=vec,
        row_value=vec if emits else P())

    in_specs = (vec, P(), P(), P(), P(), P(), P(), P(), P(),
                vec, vec, vec, P())

    fn = shard_map(step, mesh=mesh, in_specs=in_specs,
                   out_specs=out_spec, check_vma=False)
    return fn(data.bins, data.bin_offsets, data.num_bins, data.default_bins,
              data.missing_types, data.is_categorical, data.nan_bins,
              data.feat_group, data.feat_offset,
              grad, hess, bag_mask, feature_mask)
