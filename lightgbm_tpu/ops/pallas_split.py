"""Pallas TPU fused best-split search — one kernel per wave.

The XLA expression of the split scan (`ops/split.py:find_best_splits`)
is ~50 small ops per wave on `[2A, F, B, 3]` grids; at 9 waves per
iteration the op-count overhead is row-independent and becomes the
dominant per-iteration fixed cost on small-to-medium datasets (its size
is unverified on a local chip).  This kernel computes the whole numerical scan — both
missing-direction variants, constraint masking, and the joint
(feature, bin, direction) argmax — in ONE Pallas call over a
``[leaves, F*B]`` lanes layout.

Semantics mirror `find_best_splits`'s numerical path exactly
(reference `feature_histogram.hpp:312-452`):
  * prefix sums over the bin axis give left-side sums per threshold,
  * the missing cell (NaN bin, or the zero bin for
    ``MissingType::Zero``) is excluded from the scan and added wholly
    to the left side in the "missing left" variant,
  * constraints: ``min_data_in_leaf``/``min_sum_hessian_in_leaf`` on
    both sides, no threshold at/after ``num_bins-1`` (-2 with a NaN
    bin), no split ON the zero-missing cell, variant 1 only where the
    feature actually has a missing type,
  * ties: variant 0 (missing right) wins, then lowest feature, then
    lowest bin — the same order the XLA path's argmax chain yields.

The bin prefix sums run as ``log2(B)`` masked-roll rounds on the VPU
(segment-local: rolled-in lanes from the previous feature's segment are
zeroed), with gradients/hessians/counts stacked on sublanes so one
round advances all three.  Floating-point association therefore differs
from ``jnp.cumsum`` in the last ulp — the same envelope the psum
reassociation in the distributed learners already documents; the oracle
test gates sums at ~1e-6 relative and decisions for equality on
non-degenerate gains.

Categorical features are not expressed here; datasets with any
categorical feature stay on the XLA path (`learner/serial.py` gates).

Measured flip envelope (binary_classification example, 7k rows, 255
bins): one near-tie split flip in tree 0 vs the XLA path on TPU (CPU
interpret mode builds the IDENTICAL tree — the flip is compiled-kernel
last-ulp rounding on quantized-histogram near-ties).  Through 100
iterations of bagging+feature_fraction the flip cascades to a model
whose held-out AUC moved -0.0098; without sampling the kernel model
scored +0.0027 — i.e. run-variance on a 7k-row example, not a quality
penalty.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..io.binning import MISSING_NAN, MISSING_ZERO
from .split import (K_EPSILON, K_MIN_SCORE, SplitParams, SplitResult,
                    leaf_output, leaf_split_gain)

LANE = 128

# F*B lane cap PER KERNEL CALL: at the old 32768 cap the kernel's
# [3*Lc, FB] f32 intermediates (ghc/gs/cl0/cl1, ~12 MB each at Lc=32)
# blew the ~16 MB per-core VMEM and surfaced as a Mosaic compile crash
# instead of a fallback (ADVICE r5 #1).  16384 keeps the minimum Lc=8
# tile inside the budget below; the tile shrinks as FB grows toward it.
# Wider feature sets now CHUNK the feature axis into per-call slices of
# this width (ISSUE 9) instead of falling off the kernel path — the
# chunk choice lives in the shared VMEM model
# (`ops/vmem.py:split_lane_chunk_features`), so memcheck's MEM004 and
# this dispatcher agree on where feasibility is decided.
from .vmem import SPLIT_MAX_LANES as MAX_LANES
from .vmem import split_lane_chunk_features

# VMEM working-set budget for the leaf-tile choice: the kernel holds
# roughly 6 concurrent [3*Lc, FB] f32 arrays in the missing path
# (stacked channels, masked copies, both prefix-sum variants), so the
# live set is ~72*Lc*FB bytes.  12 MiB leaves headroom under the ~16 MB
# per-core VMEM for pipelining + the in/out blocks.  Override for
# hardware-verified tuning with LGBM_TPU_SPLIT_VMEM_MB.
_WORKING_SET_BYTES_PER_CELL = 72


def _vmem_budget_bytes() -> int:
    # the shared VMEM model (ops/vmem.py) owns the knob so memcheck's
    # MEM004 and this kernel agree on where feasibility is decided
    from .vmem import split_vmem_budget_bytes
    return split_vmem_budget_bytes()


def split_kernel_ok(num_features: int, B: int,
                    has_categorical: bool, num_rows: int = 0) -> bool:
    """Whether the fused split kernel can express this config (numerical
    features only, power-of-two bin stride, F*B lane-aligned) AND is the
    right default for it.

    Measured A/B on the v5e: at 7k rows the kernel HALVES warm
    time/iteration (the XLA scan's ~50-op-per-wave overhead dominates
    row work), while at 1M rows it is ~5% slower (the ops hide behind
    row-scaled kernels and the fused call adds its own per-wave cost).
    Default: on for datasets at/below the compile-lean row threshold,
    where op overhead rules; LGBM_TPU_SPLIT_KERNEL=1/0 forces."""
    if has_categorical:
        return False
    env = os.environ.get("LGBM_TPU_SPLIT_KERNEL", "")
    if env in ("0", "false"):
        return False
    if B & (B - 1) or B > 256:
        return False
    FB = num_features * B
    # at/below the lane cap the single-call path needs LANE alignment;
    # above it the feature axis chunks into lane-aligned, zero-padded
    # slices (split_lane_chunk_features), so any width is expressible
    if FB <= MAX_LANES and FB % LANE != 0:
        return False
    if env in ("1", "true"):
        return True
    lean = int(os.environ.get("LGBM_TPU_COMPILE_LEAN_ROWS", 65536))
    return num_rows <= lean


def _leaf_tile(L2: int, FB: int = LANE) -> int:
    """Leaf-tile height, budgeted against the F*B lane width so the
    kernel's ~[3*Lc, FB] f32 working set stays inside VMEM (ADVICE r5
    #1: a fixed 32-leaf tile at wide FB compile-crashed instead of
    shrinking).  Power of two in [8, 32]."""
    cap = 32
    budget = _vmem_budget_bytes()
    while cap > 8 and cap * FB * _WORKING_SET_BYTES_PER_CELL > budget:
        cap //= 2
    t = 8
    while t < min(L2, cap):
        t *= 2
    return t


def _seg_cumsum(x, lane_mod, B):
    """Forward prefix sum within each B-lane segment (masked rolls)."""
    k = 1
    while k < B:
        sh = pltpu.roll(x, k, 1)
        x = x + jnp.where(lane_mod >= k, sh, 0.0)
        k *= 2
    return x


def _seg_suffix(x, lane_mod, B, FB):
    """Suffix sum within each B-lane segment (left-roll = right-roll by
    FB-k: pltpu.roll requires a non-negative shift)."""
    k = 1
    while k < B:
        sh = pltpu.roll(x, FB - k, 1)
        x = x + jnp.where(lane_mod < B - k, sh, 0.0)
        k *= 2
    return x


def _split_kernel(g_ref, h_ref, c_ref, tot_ref, const_ref, out_ref, *,
                  B: int, FB: int, Lc: int, any_missing: bool):
    """One leaf-tile: full numerical split scan -> [Lc, LANE] packed
    (gain, feat, bin, default_left, lg, lh, lc)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (3 * Lc, FB), 1)
    lane_mod = lane & (B - 1)

    vmask = const_ref[0:1, :]          # valid & not missing cell
    miss = const_ref[1:2, :]           # the missing cell
    ok_base = const_ref[2:3, :]        # threshold-position validity
    hasmiss = const_ref[3:4, :]        # feature has a missing type
    fmask = const_ref[4:5, :]          # feature_fraction mask
    # hyper-parameters ride in lane memory (they may be traced values
    # when the caller's params pytree crosses a jit boundary)
    l1 = const_ref[5, 0]
    l2 = const_ref[5, 1]
    min_d = const_ref[5, 2]
    min_he = const_ref[5, 3]           # min_sum_hessian + kEpsilon

    # g/h/c stacked on sublanes so one roll round advances all three
    # (rank-2 refs only: rank-3 blocks crash the Mosaic lowering)
    ghc = jnp.concatenate([g_ref[:], h_ref[:], c_ref[:]], axis=0)
    gs = ghc * vmask                                    # scanned cells
    cl0 = _seg_cumsum(gs, lane_mod, B)                  # missing-right
    if any_missing:
        m_only = ghc * miss
        sfx = _seg_suffix(m_only, lane_mod, B, FB)
        m_at0 = jnp.where(lane_mod == 0, sfx, 0.0)      # seg total -> lane 0
        mb = _seg_cumsum(m_at0, lane_mod, B)            # bcast over segment
        cl1 = cl0 + mb                                  # missing-left

    def gain_of(lg, lh):
        # ThresholdL1 applied unconditionally: sign(s)*max(|s|-l1,0)
        # reduces exactly to s at l1=0
        lg = jnp.sign(lg) * jnp.maximum(jnp.abs(lg) - l1, 0.0)
        return lg * lg / (lh + l2)

    # fresh iota, NOT a slice of `lane`: a sliced iota feeding the
    # min-reduce crashes the Mosaic/jellyfish lowering (Check failed:
    # limits[i] <= dim(i)) on this toolchain
    lane1 = jax.lax.broadcasted_iota(jnp.int32, (Lc, FB), 1)
    tg = tot_ref[:, 0:1]
    th = tot_ref[:, 1:2]
    tc = tot_ref[:, 2:3]

    def variant(cl, extra_ok):
        lg, lh, lc = cl[:Lc], cl[Lc:2 * Lc], cl[2 * Lc:]
        rg, rh, rc = tg - lg, th - lh, tc - lc
        ok = ((lc >= min_d) & (rc >= min_d)
              & (lh >= min_he) & (rh >= min_he)
              & (ok_base > 0.5) & (fmask > 0.5) & extra_ok)
        gain = gain_of(lg, lh) + gain_of(rg, rh)
        return jnp.where(ok, gain, K_MIN_SCORE), lg, lh, lc

    g0, lg0, lh0, lc0 = variant(cl0, True)
    if any_missing:
        g1, lg1, lh1, lc1 = variant(cl1, hasmiss > 0.5)
        use1 = g1 > g0                        # tie -> variant 0
        gv = jnp.where(use1, g1, g0)
        lgv = jnp.where(use1, lg1, lg0)
        lhv = jnp.where(use1, lh1, lh0)
        lcv = jnp.where(use1, lc1, lc0)
        varv = use1.astype(jnp.float32)
    else:
        gv, lgv, lhv, lcv = g0, lg0, lh0, lc0
        varv = jnp.zeros_like(g0)

    best = jnp.max(gv, axis=1, keepdims=True)                  # [Lc, 1]
    at_best = gv >= best                    # ties -> lowest joint index
    idx = jnp.min(jnp.where(at_best, lane1, FB), axis=1,
                  keepdims=True)                               # [Lc, 1]
    sel = (lane1 == idx).astype(jnp.float32)

    def pick(x):
        return jnp.sum(x * sel, axis=1, keepdims=True)

    out_lane = jax.lax.broadcasted_iota(jnp.int32, (Lc, LANE), 1)
    idx_f = idx.astype(jnp.float32)
    feat = jnp.floor(idx_f / B)
    binv = idx_f - feat * B
    vals = [best, feat, binv, pick(varv), pick(lgv), pick(lhv),
            pick(lcv)]
    out = jnp.zeros((Lc, LANE), jnp.float32)
    for i, v in enumerate(vals):
        out = jnp.where(out_lane == i, v, out)
    out_ref[:] = out


def find_best_splits_pallas(grid: jnp.ndarray,
                            leaf_sum_grad: jnp.ndarray,
                            leaf_sum_hess: jnp.ndarray,
                            leaf_count: jnp.ndarray,
                            num_bins: jnp.ndarray,
                            missing_types: jnp.ndarray,
                            default_bins: jnp.ndarray,
                            *,
                            B: int,
                            params: SplitParams,
                            feature_mask: jnp.ndarray | None = None,
                            any_missing: bool = True,
                            interpret: bool = False) -> SplitResult:
    """Drop-in numerical-only twin of :func:`ops.split.find_best_splits`
    over a ``[L2, F, B, 3]`` padded grid (``B`` = bin stride).

    Feature sets wider than the per-call lane cap (``F*B >
    SPLIT_MAX_LANES`` — the 255-bin MSLR shape) run as PER-CHUNK kernel
    calls over lane-aligned feature slices (`ops/vmem.py
    split_lane_chunk_features`), merged on the raw packed gains with
    the earlier chunk winning exact ties — the same lowest-feature
    tie-break the single call's joint argmax applies.  Short last
    chunks zero-pad their features (``num_bins = 0`` masks every lane
    to ``K_MIN_SCORE``), so per-chunk results match the single-call
    scan bitwise."""
    L2, F, Bg, _ = grid.shape
    assert Bg == B
    if F * B <= MAX_LANES:
        out = _scan_feature_chunk(
            grid, leaf_sum_grad, leaf_sum_hess, leaf_count, num_bins,
            missing_types, default_bins, feature_mask, B=B,
            params=params, any_missing=any_missing, interpret=interpret)
    else:
        fc = split_lane_chunk_features(F, B)
        out = None
        for s in range(0, F, fc):
            e = min(F, s + fc)
            out_c = _scan_feature_chunk(
                grid[:, s:e], leaf_sum_grad, leaf_sum_hess, leaf_count,
                num_bins[s:e], missing_types[s:e], default_bins[s:e],
                feature_mask[s:e] if feature_mask is not None else None,
                B=B, params=params, any_missing=any_missing,
                interpret=interpret, pad_features=fc)
            if s:
                out_c = out_c.at[:, 1].add(float(s))    # global feature id
                take = out_c[:, 0] > out[:, 0]          # tie -> earlier chunk
                out = jnp.where(take[:, None], out_c, out)
            else:
                out = out_c

    parent_gain = leaf_split_gain(leaf_sum_grad, leaf_sum_hess,
                                  params.lambda_l1, params.lambda_l2)
    gain_shift = parent_gain + params.min_gain_to_split

    b_lg, b_lh, b_lc = out[:, 4], out[:, 5], out[:, 6]
    b_rg = leaf_sum_grad - b_lg
    b_rh = leaf_sum_hess - b_lh
    b_rc = leaf_count - b_lc
    l1, l2 = params.lambda_l1, params.lambda_l2
    return SplitResult(
        gain=(out[:, 0] - gain_shift).astype(jnp.float32),
        feature=out[:, 1].astype(jnp.int32),
        threshold=out[:, 2].astype(jnp.int32),
        default_left=out[:, 3] > 0.5,
        is_categorical=jnp.zeros(L2, bool),
        cat_mask=jnp.zeros((L2, B), bool),
        left_sum_grad=b_lg, left_sum_hess=b_lh, left_count=b_lc,
        right_sum_grad=b_rg, right_sum_hess=b_rh, right_count=b_rc,
        left_output=leaf_output(b_lg, b_lh, l1, l2),
        right_output=leaf_output(b_rg, b_rh, l1, l2),
    )


def _scan_feature_chunk(grid, leaf_sum_grad, leaf_sum_hess, leaf_count,
                        num_bins, missing_types, default_bins,
                        feature_mask, *, B: int, params: SplitParams,
                        any_missing: bool, interpret: bool,
                        pad_features: int = 0) -> jnp.ndarray:
    """One lane-cap-sized kernel call: scan a ``[L2, Fc, B, 3]`` grid
    slice and return the packed per-leaf winner ``[L2, LANE]`` (raw
    gain, LOCAL feature, bin, default_left, left sums).  With
    ``pad_features`` the slice zero-pads to that width (masked lanes,
    LANE-aligned)."""
    L2, F, Bg, _ = grid.shape
    if pad_features and F < pad_features:
        grid = jnp.pad(grid, ((0, 0), (0, pad_features - F),
                              (0, 0), (0, 0)))
        num_bins = jnp.pad(num_bins, (0, pad_features - F))
        missing_types = jnp.pad(missing_types, (0, pad_features - F))
        default_bins = jnp.pad(default_bins, (0, pad_features - F))
        if feature_mask is not None:
            feature_mask = jnp.pad(feature_mask, (0, pad_features - F))
        F = pad_features
    FB = F * B
    Lc = _leaf_tile(L2, FB)
    L_pad = -(-L2 // Lc) * Lc

    chans = [jnp.pad(grid[..., i].reshape(L2, FB),
                     ((0, L_pad - L2), (0, 0))) for i in range(3)]

    tot = jnp.zeros((L_pad, LANE), jnp.float32)
    tot = tot.at[:L2, 0].set(leaf_sum_grad)
    tot = tot.at[:L2, 1].set(leaf_sum_hess)
    tot = tot.at[:L2, 2].set(leaf_count)

    # dataset-constant lane masks (loop-invariant: XLA hoists them out
    # of the wave scan)
    bin_ids = jnp.arange(B)[None, :]                       # [1, B]
    valid = bin_ids < num_bins[:, None]                    # [F, B]
    has_nan = (missing_types == MISSING_NAN)[:, None]
    is_zero = (missing_types == MISSING_ZERO)[:, None]
    nanb = jnp.where(has_nan[:, 0], num_bins - 1, -1)[:, None]
    missb = jnp.where(has_nan[:, 0], nanb[:, 0],
                      jnp.where(is_zero[:, 0], default_bins, -1))[:, None]
    miss_cell = (bin_ids == missb) & valid
    max_t = jnp.where(has_nan[:, 0], num_bins - 2, num_bins - 1)[:, None]
    ok_base = (bin_ids < max_t) & ~(miss_cell & is_zero)
    hasmiss = jnp.broadcast_to(missb >= 0, (F, B))
    fm = (jnp.broadcast_to(feature_mask[:, None], (F, B))
          if feature_mask is not None else jnp.ones((F, B), bool))
    consts = jnp.stack([
        (valid & ~miss_cell).reshape(FB), miss_cell.reshape(FB),
        ok_base.reshape(FB), hasmiss.reshape(FB), fm.reshape(FB),
        jnp.zeros(FB, bool), jnp.zeros(FB, bool), jnp.zeros(FB, bool),
    ]).astype(jnp.float32)                                  # [8, FB]
    hp = jnp.zeros(FB, jnp.float32)
    hp = hp.at[0].set(params.lambda_l1).at[1].set(params.lambda_l2)
    hp = hp.at[2].set(params.min_data_in_leaf * 1.0)
    hp = hp.at[3].set(params.min_sum_hessian_in_leaf + K_EPSILON)
    consts = consts.at[5].set(hp)

    kern = functools.partial(
        _split_kernel, B=B, FB=FB, Lc=Lc, any_missing=any_missing)
    return pl.pallas_call(
        kern,
        grid=(L_pad // Lc,),
        in_specs=[
            pl.BlockSpec((Lc, FB), lambda i: (i, 0)),
            pl.BlockSpec((Lc, FB), lambda i: (i, 0)),
            pl.BlockSpec((Lc, FB), lambda i: (i, 0)),
            pl.BlockSpec((Lc, LANE), lambda i: (i, 0)),
            pl.BlockSpec((8, FB), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((Lc, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((L_pad, LANE), jnp.float32),
        interpret=interpret,
    )(*chans, tot, consts)[:L2]
