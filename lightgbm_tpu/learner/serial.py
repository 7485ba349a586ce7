"""Serial (single-shard) tree learner — staged wave-growth, fully jitted.

TPU-native redesign of the reference ``SerialTreeLearner``
(`/root/reference/src/treelearner/serial_tree_learner.cpp:155-622`).  The
reference grows leaf-wise: a sequential best-first loop that, per split,
builds the smaller child's histograms (OpenMP over feature groups), derives
the sibling by subtraction, scans features for the best split, and
physically repartitions row indices (`data_partition.hpp`).

Here the tree is built by a sequence of *waves*, with the reference's
histogram-economy strategy kept intact
(`serial_tree_learner.cpp:358-372`, `feature_histogram.hpp:64-70`):

  1. histogram ONLY the smaller child of every split made in the previous
     wave (one MXU one-hot-matmul kernel pass over all rows,
     `ops/pallas_histogram.py`; XLA scatter fallback off-TPU),
  2. derive each sibling by parent-minus-child subtraction from the
     persistent per-leaf histogram state ``[L, F, B, 3]`` held in HBM
     (the HistogramPool analog — no LRU needed, it all fits),
  3. re-scan ONLY those changed leaves (vectorized two-direction prefix
     scan, `ops/split.py`) and cache their best splits,
  4. split every positive-gain leaf (up to the wave's slot count) in one
     go, routing rows with one Pallas pass (`ops/pallas_route.py`).

The wave loop is *staged*: the first ``ceil(log2(L))`` waves are unrolled
with active-slot counts growing 8, 8, 16, 32, ... so the histogram
kernel's MXU cost tracks the actual number of active leaves (a tree's
early waves are nearly free), then a ``lax.while_loop`` at a fixed slot
count finishes any leftover splits.  ``wave_size=1`` reproduces the
reference's leaf-wise growth decision-for-decision.

Everything is static-shape: leaf arrays are sized ``[num_leaves]``, tree
node arrays ``[num_leaves-1]``, and finished trees report a dynamic
``num_leaves`` scalar.  The same step runs unchanged under ``shard_map``
for the distributed learners (the active-leaf histograms gain a ``psum``).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..io.binning import MISSING_NAN, MISSING_NONE, MISSING_ZERO
from ..io.device import DeviceData
from ..ops.pallas_histogram import (DEFAULT_ROW_TILE, INT8_ROW_LIMIT,
                                    MAX_CODE_SHARDS, bin_stride,
                                    default_backend,
                                    dequant_hist, fused_config_ok,
                                    hist_active_pallas,
                                    hist_active_scatter, hist_raw_layout,
                                    hist_route_pallas, is_quantized,
                                    pack_values, pack_values_q,
                                    pallas_config_ok, row_chunks,
                                    sum_code_limbs, transpose_bins,
                                    unpack_hist_raw)
from ..ops.pallas_route import (route_rows_pallas, route_rows_values_pallas,
                                route_rows_xla)
from ..ops.split import SplitParams, SplitResult, find_best_splits
from ..ops.vmem import round_up as _round_up

NEG_INF = -1e30


class GrowthParams(NamedTuple):
    """Static tree-growth parameters."""
    num_leaves: int = 31
    max_depth: int = -1
    wave_size: int = 0          # 0 => unlimited (full wave); 1 => leaf-wise
    split: SplitParams = SplitParams()


class BuiltTree(NamedTuple):
    """A finished tree as device arrays (fixed shapes, dynamic num_leaves).

    Node layout matches the reference Tree (`tree.h`): internal nodes
    ``[0, num_leaves-2]``, children ``>=0`` internal / ``~leaf`` for leaves.
    """
    feature: jnp.ndarray         # [L-1] i32 (used-column index)
    threshold_bin: jnp.ndarray   # [L-1] i32
    default_left: jnp.ndarray    # [L-1] bool
    is_categorical: jnp.ndarray  # [L-1] bool
    cat_mask: jnp.ndarray        # [L-1, B] bool  (bins going left)
    left_child: jnp.ndarray      # [L-1] i32
    right_child: jnp.ndarray     # [L-1] i32
    gain: jnp.ndarray            # [L-1] f32
    internal_value: jnp.ndarray  # [L-1] f32 (parent leaf output)
    internal_count: jnp.ndarray  # [L-1] i32
    leaf_value: jnp.ndarray      # [L] f32
    leaf_count: jnp.ndarray      # [L] i32
    leaf_depth: jnp.ndarray      # [L] i32
    num_leaves: jnp.ndarray      # scalar i32
    row_leaf: jnp.ndarray        # [n] i32 final leaf per row (ALL rows)
    row_value: jnp.ndarray       # [n] f32 leaf_value[row_leaf] (emitted by
    #   the final route kernel on the Pallas path, serial and
    #   data-parallel; empty [0] otherwise — the score update falls back
    #   to a gather)


class _WaveState(NamedTuple):
    leaf2: jnp.ndarray           # [2, n_pad] (row_leaf; hist_leaf/-1 bagged)
    nl: jnp.ndarray              # scalar i32 current leaf count
    done: jnp.ndarray            # scalar bool
    leaf_sum_grad: jnp.ndarray   # [L]
    leaf_sum_hess: jnp.ndarray   # [L]
    leaf_count: jnp.ndarray      # [L] f32 (in-bag counts)
    leaf_depth: jnp.ndarray      # [L] i32
    leaf_value: jnp.ndarray      # [L] f32
    leaf_parent: jnp.ndarray     # [L] i32 node idx
    leaf_is_left: jnp.ndarray    # [L] bool
    hist_state: jnp.ndarray      # [L, F_local, B, 3] per-leaf histograms
    best: SplitResult            # [L] cached best split per leaf
    pend_sel: jnp.ndarray        # [L] bool: splits decided last wave,
    pend_new: jnp.ndarray        # [L] i32  not yet applied to the rows
    act_small: jnp.ndarray       # [A] leaf ids to histogram this wave (-1 pad)
    act_parent: jnp.ndarray      # [A] slot holding the parent hist (-1: none)
    act_sibling: jnp.ndarray     # [A] sibling leaf id (-1: none)
    tree: BuiltTree


def _round8(x: int) -> int:
    return -(-x // 8) * 8


def split_cache_enabled() -> bool:
    """Per-leaf best-split cache (ISSUE 9, the reference's
    ``best_split_per_leaf_`` economy, `serial_tree_learner.cpp`): each
    wave scans ONLY the newly-histogrammed child slots and merges them
    into the ``[L]`` cache the selection reads — O(A·F·B) per wave
    instead of O(L·F·B).  ``LGBM_TPU_SPLIT_CACHE=0`` restores the full
    per-wave rescan of every leaf's histogram (the A/B baseline the
    ``split_finder`` bench table measures); models are byte-identical
    either way (unchanged histograms ⇒ unchanged gains ⇒ identical
    argmax tie-breaks — gated by tests/test_split_cache.py)."""
    return _os_env.environ.get("LGBM_TPU_SPLIT_CACHE", "1") not in (
        "0", "false")


# datasets at or below this row count take the single-body compile-lean
# path (override for A/B: LGBM_TPU_COMPILE_LEAN_ROWS)
import os as _os_env
_COMPILE_LEAN_ROWS = int(_os_env.environ.get("LGBM_TPU_COMPILE_LEAN_ROWS",
                                             65536))

# canonical reduction chunk for the root statistics (ISSUE 14): a FIXED
# constant, not a knob — the streamed out-of-core trainer
# (boosting/streaming.py) reproduces the root sums from per-block chunk
# sums, and any run-time variation here would silently fork the
# reduction tree the byte-identity contract pins
STREAM_CHUNK = 8192


def _pairwise_halve(v: jnp.ndarray) -> jnp.ndarray:
    """Reduce the LAST axis (a power of two) to 1 by repeated pairwise
    adds.  Every step is an explicit elementwise ``a + b`` — defined
    IEEE semantics XLA cannot legally reassociate — so the reduction
    tree is identical in every fusion context and on every backend,
    unlike a ``reduce`` op whose internal order is implementation-
    defined (and empirically varies with the surrounding program)."""
    while v.shape[-1] > 1:
        half = v.shape[-1] // 2
        v = v[..., :half] + v[..., half:]
    return v[..., 0]


def root_chunk_sums(grad, hess, bag) -> jnp.ndarray:
    """Per-chunk partial sums of the root statistics ``(g, h, count)``
    over a row range: ``-> [3, m]`` with ``m = ceil(n / STREAM_CHUNK)``.

    The chunk grid is anchored at row 0 of the given range and padded
    with exact zeros, and each chunk reduces through an explicit
    pairwise-halving tree — so a caller that folds this function over
    row blocks whose sizes are multiples of ``STREAM_CHUNK`` (the
    streamed trainer, ``boosting/streaming.py``) produces the
    identical ``[3, m]`` vector as one call over the whole range.
    Partition-invariance is the contract
    (tests/test_streaming.py pins it end-to-end)."""
    n = grad.shape[0]
    m = -(-n // STREAM_CHUNK)
    pad = (0, m * STREAM_CHUNK - n)
    g = jnp.pad(jnp.where(bag, grad, 0.0).astype(jnp.float32), pad)
    h = jnp.pad(jnp.where(bag, hess, 0.0).astype(jnp.float32), pad)
    c = jnp.pad(bag.astype(jnp.float32), pad)
    stacked = jnp.stack([g, h, c])                   # [3, m*C]
    return _pairwise_halve(stacked.reshape(3, m, STREAM_CHUNK))


def reduce_chunk_sums(cs: jnp.ndarray):
    """Reduce ``[3, m]`` chunk sums to root ``(sum_g, sum_h, cnt)``
    with the same fixed pairwise-halving tree over the (zero-padded)
    power-of-two chunk axis.  The tree depends only on ``m`` — never
    on how the rows were partitioned into blocks — which is what makes
    the streamed trainer's root statistics bitwise equal to the
    resident path's."""
    m = cs.shape[1]
    P = 1 << max(0, (m - 1).bit_length())
    v = jnp.pad(cs, ((0, 0), (0, P - m)))
    v = _pairwise_halve(v)
    return v[0], v[1], v[2]


def _reassoc_fault_armed() -> bool:
    # resolved ONCE at import (host side, before any tracing): the
    # fault must not be consulted inside the traced reducer — jit
    # would cache the answer anyway, and a host call from traced
    # scope would drag the faults/telemetry machinery into
    # detcheck's traced closure.  Arm via env in a fresh process
    # (LGBM_TPU_FAULTS="num.reassoc:...").
    from ..utils.faults import fault_flag
    return fault_flag("num.reassoc")


_NUM_REASSOC_FAULT = _reassoc_fault_armed()


def root_stats(grad, hess, bag):
    """Root ``(sum_g, sum_h, cnt)`` via the canonical chunked pairwise
    reduction (replaces the old ``jnp.sum``, whose XLA ``reduce``
    order is implementation-defined, varies with the surrounding
    program, and cannot be reassembled from streamed per-block
    partials)."""
    if _NUM_REASSOC_FAULT:
        # the PR 14 bug, resurrected on demand: a raw reassociable
        # reduction whose order XLA picks per-program — the identity
        # harness (tools/identity_check.py) must name the partition
        # pair this diverges, and numcheck's NUM001 must flag the
        # sums below at file:line.
        b = bag.astype(grad.dtype)
        # numcheck: disable=NUM001 -- deliberate num.reassoc fault body
        sg = jnp.sum(grad * b)
        # numcheck: disable=NUM001 -- deliberate num.reassoc fault body
        sh = jnp.sum(hess * b)
        return sg, sh, jnp.sum(b)
    return reduce_chunk_sums(root_chunk_sums(grad, hess, bag))


def root_code_sums(vals, bag):
    """In-bag sums of the packed int8 value rows (:func:`pack_values_q`):
    ``[C, n_pad] int8 -> [C] int32``.  Integer adds are exact, so the
    result does not depend on the reduction order or on how the rows are
    partitioned into blocks; per-block results add up to the whole.
    More rows than one int32 sums exactly (``_INT8_ROW_LIMIT``) are
    summed in row chunks as the histogram cells are, and the result is
    the limb pair of the total (``sum_code_limbs``), which
    :func:`root_stats_q` and the exchange take alike."""
    n_pad = vals.shape[1]
    bag = jnp.pad(bag, (0, n_pad - bag.shape[0]))

    def part(bag, vals):
        return jnp.sum(jnp.where(bag[None, :], vals.astype(jnp.int32), 0),
                       axis=1)

    K, rows = row_chunks(n_pad, 1, _INT8_ROW_LIMIT)
    if K == 1:
        return part(bag, vals)
    return sum_code_limbs([part(bag[lo:lo + rows], vals[:, lo:lo + rows])
                           for lo in range(0, n_pad, rows)])


def root_stats_q(code_sums, scales, mode: str):
    """Root ``(sum_g, sum_h, cnt)`` of a tree whose histograms hold
    quantized values: :func:`root_code_sums` dequantized the way the
    histogram cells are (:func:`dequant_hist`).

    A split gives one child the histogram's prefix sums and the other
    ``parent total - prefix``.  Were the root total taken from the exact
    f32 gradients, the rounding bias of every row (up to ``scale / 254``
    each, one sign for all rows that share a gradient value) would be in
    no histogram cell: it would travel down the complement side of every
    split and end in one leaf, whose value it then decides.  Taken from
    the same codes, every leaf's totals are the sums of its own rows."""
    tot = dequant_hist(code_sums, scales, mode)
    return tot[0], tot[1], tot[2]


def stage_plan(L: int, wave_size: int = 0):
    """Slot counts of the unrolled waves and of the while-loop tail:
    ``-> (plan, A_tail)``.

    A slot of wave ``i`` holds the smaller child of one split that wave
    ``i - 1`` selected (the sibling is the parent less it), and the
    histogram kernel multiplies a slot's columns whether it is live or
    ``-1``.  So ``plan[i]`` is the most splits wave ``i - 1`` can
    select, not the leaves that exist when wave ``i`` runs.  The root
    wave histograms 1 leaf.  A wave that selects among ``nl`` leaves
    selects ``k <= min(nl, L - nl)`` of them (:func:`_apply_wave`: only
    leaves below ``nl`` carry a gain, and the budget is ``L - nl``), and
    ``nl <= leaves_i``, the most leaves a tree can have when wave ``i``
    selects (1, 2, 4, ...: every leaf split in every wave), whatever
    the data.  Hence ``k <= min(leaves_i, L // 2)`` and

        plan[i + 1] = min(round8(leaves_i), A_tail)

    Why the cap ``k <= min(wave_cap, A_out)`` of ``_apply_wave`` (``A_out
    = plan[i + 1]``) never newly binds: ``A_out >= leaves_i >= nl >= k``
    wherever ``A_tail`` does not cut it; where it does, ``A_tail >= L //
    2 >= k`` up to 256 leaves, and past them the plan sized by the
    leaves that exist, ``plan[i] = min(round8(leaves_i), A_tail)``,
    twice this one, was cut to the same ``A_tail``.  The same leaves are
    split in the same order on any data, balanced or not; the columns
    that are gone were ``-1`` padding by construction.

    The tail finishes whatever the unrolled waves didn't (uneven gain
    distributions).  There ``nl`` can be anything below ``L``, so a tail
    wave can be handed up to ``L // 2`` smaller children: it runs at
    full width, which also lets a balanced tree complete within the
    unrolled waves (a narrow tail forced extra waves on the hot path).
    Leaf-wise mode (``wave_size=1``) splits one leaf per wave, so
    everything runs in a narrow while loop instead.
    """
    plan, _, A_tail = _stage_walk(L, wave_size)
    return plan, A_tail


def _stage_walk(L: int, wave_size: int):
    """The worst case :func:`stage_plan` walks, the leaves doubling up
    to ``L``: ``-> (plan, route_leaves, A_tail)``.  ``route_leaves``:
    the most leaves a tree's rows lie in when each unrolled wave routes
    them, the leaves there were when the wave before selected its
    splits (1 for the root wave, which has none to apply; 1, 1, 2, 4,
    ..., 64 at 255 leaves).  Only a leaf below that holds rows or a
    pending split, so a route table this wide is the whole table.  The
    ``while`` tail's is ``L``."""
    if wave_size == 1:
        return [], [], 8
    A_tail = min(_round8(max(1, L // 2)), 128)
    plan, route_leaves = [], []
    leaves, handed = 1, 1       # the root wave histograms the one leaf
    while leaves < L and len(plan) < 32:
        plan.append(min(_round8(handed), A_tail))
        route_leaves.append(handed)
        handed = leaves         # a smaller child for every leaf it splits
        leaves += min(leaves, A_tail)
    return plan, route_leaves, A_tail


def _empty_best(L: int, B: int) -> SplitResult:
    z = jnp.zeros(L, jnp.float32)
    return SplitResult(
        gain=jnp.full(L, NEG_INF, jnp.float32),
        feature=jnp.zeros(L, jnp.int32),
        threshold=jnp.zeros(L, jnp.int32),
        default_left=jnp.zeros(L, bool),
        is_categorical=jnp.zeros(L, bool),
        cat_mask=jnp.zeros((L, B), bool),
        left_sum_grad=z, left_sum_hess=z, left_count=z,
        right_sum_grad=z, right_sum_hess=z, right_count=z,
        left_output=z, right_output=z)


# ---------------------------------------------------------------------------
# histogram-wave strategies (the learner-type seam, tree_learner.cpp:9-33)
# ---------------------------------------------------------------------------
def uses_pallas(backend: str) -> bool:
    """Whether this (resolved) backend runs the Pallas kernel family:
    the wide histogram kernel, the route kernels, the ``bins_t`` prep."""
    return backend == "pallas"


def _pallas_interpret() -> bool:
    """Pallas kernels run in interpret mode off-TPU (CPU oracle tests /
    forced-backend runs); compiled on the real device."""
    return jax.default_backend() != "tpu"


class Wave(NamedTuple):
    """One unrolled wave of :func:`wave_backend_plan`."""
    slots: int          # the wave's histogram slots (``stage_plan``)
    route_leaves: int   # the most leaves its rows lie in when it routes
    choice: str         # "fused", or the backend: route, then histogram


def wave_backend_plan(L: int, wave_size: int = 0, backend: str = "pallas",
                      *, num_groups: int, max_bins: int, mode: str,
                      n_rows: int, serial: bool = True,
                      any_cat: bool = False):
    """The waves of a tree of ``L`` leaves over ``n_rows`` rows and each
    one's histogram call: ``-> (waves, A_tail, tail)``, a :class:`Wave`
    for each wave :func:`build_tree` unrolls (none off the Pallas path,
    nor below ``_COMPILE_LEAN_ROWS`` rows), the ``while`` tail's slots
    and its call.  ``"fused"``: the wave's route runs inside its
    histogram call (``hist_route_pallas``); ``backend``: the route
    kernel, then the histogram call.  A wave is fused where it has a
    route to apply (not the root wave), the learner is the serial one
    (``serial``: no strategy, no exchange), the backend the Pallas one,
    and ``fused_config_ok`` admits the whole feature set in one tile at
    that wave's slots with its route's leaves.  Static, from shapes:
    :func:`build_tree` takes its dispatch from here and
    ``GBDT._record_tiling`` its gauge ``hist.fused_waves``."""
    if not uses_pallas(backend):
        # staged waves only pay off on the Pallas path (MXU cost ∝
        # slots); the scatter backend compiles one while-loop body
        # instead (8 unrolled stages × shard_map × 3 learners is minutes
        # of XLA-CPU compile time)
        return [], _round8(max(1, L // 2)), backend
    plan, route_leaves, A_tail = _stage_walk(L, wave_size)
    # compile-lean: on small datasets the staged unrolled waves buy
    # nothing (MXU cost ∝ slots×n is trivial) but multiply HLO size ~7x
    # — and XLA compile time, not FLOPs, dominates small-data cold
    # starts (~30 s vs ~1.5 s of device work for 100 iterations).  One
    # full-width while-loop body compiles once and runs the same wave
    # sequence.
    if n_rows <= _COMPILE_LEAN_ROWS and wave_size != 1:
        plan = []
    n_pad = _round_up(n_rows, DEFAULT_ROW_TILE)

    def choice(slots, leaves):
        ok = serial and fused_config_ok(
            num_groups, max_bins, L, mode, n_pad, _INT8_ROW_LIMIT,
            slots=slots, route_leaves=leaves, any_cat=any_cat)
        return "fused" if ok else backend
    waves = [Wave(A, leaves, backend if i == 0 else choice(A, leaves))
             for i, (A, leaves) in enumerate(zip(plan, route_leaves))]
    return waves, A_tail, choice(A_tail, L)


def resolve_backend(data: DeviceData, num_leaf_slots: int,
                    backend: str = "auto", hist_mode: str = "hilo") -> str:
    """The histogram backend this config actually runs.  Whenever that
    is not the one asked for (the platform default for "auto"), the
    choice and its ground are logged once per distinct case, at info —
    a kernel path that a static gate turned away must be visible."""
    if backend == "auto":
        backend = default_backend()
    if backend not in ("pallas", "scatter"):
        raise ValueError(
            f"unknown histogram backend {backend!r} (hist_backend / "
            f"LGBM_TPU_HIST_BACKEND): one of auto, pallas, scatter")
    asked, why = backend, ""
    if uses_pallas(backend) and not pallas_config_ok(
            data.group_max_bins, num_leaf_slots, hist_mode):
        backend = "scatter"     # >256 bins or VMEM-infeasible config
        why = (f"{data.group_max_bins} bins x {num_leaf_slots} leaves / "
               f"{hist_mode} is outside the kernel model "
               f"(pallas_config_ok)")
    if backend != asked:
        from ..utils.log import log_once
        log_once(f"resolve_backend:{asked}:{backend}:{why}",
                 f"histogram backend: {backend} (asked for {asked}): {why}",
                 level="info")
    return backend


# int8 histogram cells accumulate exactly in int32 only while n*127 <
# 2^31 (~16.9M rows into one cell worst-case): the bound of a row CHUNK.
# A chip's rows past it are summed in chunks of at most this many, and
# the chunks' int32 partials add as limbs (``row_chunks``,
# ``sum_code_limbs``); one accumulator over more rows would silently wrap
_INT8_ROW_LIMIT = INT8_ROW_LIMIT


# a float32 holds every integer up to here: the growth carries its row
# counts in float32 (histogram cells, the split scan's prefix sums, the
# leaves' counts), so with more rows than this in all a tree's counts are
# rounded, and build_tree counts the finished leaves' rows in integers
F32_EXACT_ROWS = 1 << 24


def leaf_row_counts(leaf: jnp.ndarray, num_leaves: int) -> jnp.ndarray:
    """``[n] int32`` leaf of each row (negative: not counted) ``->
    [num_leaves] int32`` rows a leaf, exact.  The id is cut in two
    halves of its bits, and the count of ``(high, low)`` is one int8
    matrix product of the halves' one-hots with the rows contracted
    (int32 sums): 2 * sqrt(L) comparisons a row, not L."""
    low_bits = max(1, (num_leaves - 1).bit_length()) // 2
    P = 1 << low_bits
    Q = -(-num_leaves // P)
    high = (leaf >> low_bits)[None, :] == jnp.arange(Q)[:, None]   # [Q, n]
    low = (leaf & (P - 1))[None, :] == jnp.arange(P)[:, None]      # [P, n]
    cnt = jax.lax.dot_general(
        high.astype(jnp.int8), low.astype(jnp.int8),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32)
    return cnt.reshape(-1)[:num_leaves]


def effective_hist_mode(mode: str, n: int, shards: int = 1,
                        chunked: bool = True) -> str:
    """The mode that runs for ``n`` rows on each of ``shards`` row
    shards.  A quantized mode sums a chunk of at most ``_INT8_ROW_LIMIT``
    rows exactly in int32 (the root leaf can concentrate every row in
    one cell); a resident shard of more rows is summed in row chunks
    whose partials add as limbs, so its size alone downgrades nothing.
    What does: more parts (shards x chunks) than the limbs add exactly
    (``MAX_CODE_SHARDS``), or one accumulator carried over all ``n``
    rows (``chunked`` False: the streamed fold, `boosting/streaming.py`)
    past the bound of a chunk.  Then the closest float mode by the
    parity table runs: int8hh (hi/lo grad AND hessian) maps to hilo,
    the others to hhilo."""
    if not is_quantized(mode):
        return mode
    chunks = shard_row_chunks(n)
    if shards * chunks > MAX_CODE_SHARDS or (chunks > 1 and not chunked):
        return "hilo" if mode == "int8hh" else "hhilo"
    return mode


def _row_shards(psum_fn) -> int:
    """The row shards whose sums ``psum_fn`` adds (None: one)."""
    return psum_fn.num_shards if psum_fn is not None else 1


def shard_row_chunks(n: int) -> int:
    """The row chunks a quantized mode sums a resident shard of ``n``
    rows in: the histogram call's own count (``row_chunks`` over its
    row tiles) at the largest tile, 1 up to 16,908,288 rows."""
    return row_chunks(-(-n // DEFAULT_ROW_TILE), DEFAULT_ROW_TILE,
                      _INT8_ROW_LIMIT)[0]


def default_hist_mode() -> str:
    """int8h by default: quantized values on the MXU's int8 path (twice
    the bf16 peak on a v5e by its published figures), with the
    hessian as a two-level int8 hi+lo pair (~14-bit absolute precision;
    gains and leaf outputs divide by hessian sums, so hessian precision
    is what drives full-depth quality).  Every histogram cell
    accumulates EXACTLY in int32 (the one-hot operand is 0/1) — the only
    error is per-row quantization, the reference 4.x quantized-training
    trade-off.

    Chosen from the recorded 500-iteration parity table
    (`tests/data/hist_parity.json`, `tools/hist_parity.py`,
    `tests/test_hist_parity.py`): int8h matches full hi/lo-bf16 ("hilo",
    ~f32 sums) to 0.0003 AUC at reference depth — inside the reference's
    own GPU-parity envelope (`docs/GPU-Performance.rst:135-161`); what
    it costs in wall-clock against the float modes is not measured on a
    local chip.  (The table was recorded before PR 21 took the quantized
    modes' root totals from their own codes; it holds AUCs only, which
    the one misvalued leaf per tree barely moved.)  Plain "int8"
    (single-column hessian) drifts ~0.007 (absolute quantization
    truncates small hessians) and plain "bf16" drifts 0.0035-0.0048;
    both stay available for A/B.  "int8hh" (hi/lo pairs for BOTH grad
    and hessian, 5/4 the MXU work) tightens the 250k-row drift 5x
    (0.0003 vs 0.0016) for a fifth value column — the accuracy-margin
    choice when the parity envelope matters more than peak throughput.
    Overrides: the ``hist_mode`` config parameter (or ``gpu_use_dp``,
    which maps to hilo) wins; the LGBM_TPU_HIST_MODE env var is the
    debug-level override below it."""
    import os
    return os.environ.get("LGBM_TPU_HIST_MODE", "int8h")


def _pack(grad, hess, hist_mode: str, scales=None):
    """The gradients as the kernels' value rows: ``-> (vals, scales)``,
    quantised (``scales`` given: with those, the streamed fold's) or
    bf16 splits (``scales`` None), under the scope ``tree.pack``."""
    with jax.named_scope("tree.pack"):
        if is_quantized(hist_mode):
            return pack_values_q(grad, hess, hist_mode, scales=scales)
        return pack_values(grad, hess, hist_mode), None


def make_hist_fn(data: DeviceData, grad, hess, num_leaf_slots: int,
                 backend: str = "auto", hist_mode: Optional[str] = None,
                 bins_t: Optional[jnp.ndarray] = None,
                 scales: Optional[jnp.ndarray] = None,
                 codes: bool = False):
    """Build the per-wave active-leaf histogram closure
    ``(hist_leaf, active) -> [A, F, B, 3]``.

    backend "pallas" = the MXU one-hot-matmul kernel (TPU);
    "scatter" = XLA scatter-add (CPU tests / oracle).  The two are
    cross-checked by ``tests/test_pallas_hist.py`` the way the reference
    checks GPU vs CPU histograms (`gpu_tree_learner.cpp:1020-1043`).

    ``scales``: the quantized modes round against these and not against
    the largest magnitudes of ``grad`` / ``hess`` (a row-sharded learner
    hands every shard the largest over all shards).  ``codes`` (quantized
    kernel modes only): the closure returns the ``[A, F, B, C]`` int32
    code sums, not dequantized, for the caller to sum over the shards
    and dequantize once.
    """
    if hist_mode is None:
        hist_mode = default_hist_mode()
    hist_mode = effective_hist_mode(hist_mode, data.num_data)
    backend = resolve_backend(data, num_leaf_slots, backend, hist_mode)
    if uses_pallas(backend):
        if bins_t is None:
            bins_t = transpose_bins(data.bins)
        vals, scales = _pack(grad, hess, hist_mode, scales)
        if codes:
            scales = None      # combine_hist_cols: the code sums as they are
        n_pad = bins_t.shape[1]
        n = data.bins.shape[0]
        interp = _pallas_interpret()

        def hist_fn(hist_leaf, active):
            with jax.named_scope("tree.hist"):
                leaf = hist_leaf
                if leaf.shape[0] != n_pad:
                    leaf = jnp.pad(leaf[:n], (0, n_pad - n),
                                   constant_values=-1)
                return hist_active_pallas(
                    bins_t, vals, leaf, active, scales,
                    num_features=data.num_groups,
                    max_bins=data.group_max_bins,
                    mode=hist_mode, interpret=interp,
                    row_limit=_INT8_ROW_LIMIT)
    else:
        n = data.bins.shape[0]

        def hist_fn(hist_leaf, active):
            with jax.named_scope("tree.hist"):
                return hist_active_scatter(
                    data.bins, grad, hess, hist_leaf[:n], active,
                    max_bins=data.group_max_bins,
                    num_leaf_slots=num_leaf_slots)
    return hist_fn


class HistFold(NamedTuple):
    """The streamed kernel-fold seam built by :func:`make_hist_fold_fn`.

    ``fold(bins, grad, hess, hist_leaf, active, acc, scales=None)``
    folds one block's rows into the carried RAW kernel accumulator and
    returns the new carry; ``init_acc()`` allocates the zero carry;
    ``unpack(acc, scales=None)`` finalizes the chain to the
    ``[A, F, B, 3]`` f32 grid the split scan consumes."""
    fold: Callable
    init_acc: Callable
    unpack: Callable
    hist_mode: str
    quantized: bool


def make_hist_fold_fn(data: DeviceData, num_leaf_slots: int,
                      num_active: int, block_rows: int,
                      backend: str = "auto",
                      hist_mode: Optional[str] = None,
                      num_data: Optional[int] = None
                      ) -> Optional[HistFold]:
    """Build the out-of-core histogram FOLD closure — the seeded-kernel
    twin of :func:`make_hist_fn` for streamed training
    (``boosting/streaming.py``).

    A streamed tree histograms each wave as a chain of per-block kernel
    calls that carry the RAW kernel accumulator (``acc`` /
    ``raw=True`` in the kernels) instead of summing unpacked f32 grids:
    on the quantized modes (the default) every cell accumulates exactly
    in int32, and the final :func:`unpack_hist_raw` dequantizes ONCE —
    bitwise what one monolithic in-memory kernel call produces.  This is
    what puts streamed training in the byte-identity domain on the
    kernel backends, not just scatter.

    SANCTIONED REASSOCIATION CONTEXT (tools/numcheck): splitting one
    kernel reduction into per-block seeded calls reorders nothing — the
    seeded kernel replays the monolithic kernel's adds in the monolithic
    order, block boundaries are just program re-entry.  Exactness holds
    per mode: quantized modes are order-free int32; the wide float modes
    reuse the identical per-tile add sequence (same row tile for every
    same-shaped block).

    Args:
      num_active: the streamed wave width (streamed trees run every
        wave at the fixed tail width — ``stage_plan(L)[1]``).
      block_rows: rows per streamed block (every block padded alike,
        which keeps the raw layout call-invariant).
      num_data: GLOBAL stream row count for the quantized-mode row
        bound (``effective_hist_mode`` must see the stream total, not
        the block size — a 1B-row stream can overflow an int32 cell
        even though each block is tiny; the fold carries ONE
        accumulator, so the bound of a chunk is the bound of the
        stream).  Defaults to ``data.num_data``.

    Returns None when the resolved backend is scatter (caller keeps the
    carried-f32 scatter fold) or the SEEDED cell is VMEM-infeasible.
    """
    from ..ops.vmem import hist_fold_cell_ok, round_up

    if hist_mode is None:
        hist_mode = default_hist_mode()
    hist_mode = effective_hist_mode(
        hist_mode, data.num_data if num_data is None else num_data,
        chunked=False)
    backend = resolve_backend(data, num_leaf_slots, backend, hist_mode)
    if not uses_pallas(backend):
        return None
    quantized = is_quantized(hist_mode)
    mb = data.group_max_bins
    if not hist_fold_cell_ok(mb, num_active, hist_mode):
        # the fold seam's own substitution, as visible as
        # resolve_backend's: a kernel a static gate turned away must not
        # stay silent on the streamed path either
        from ..utils.log import log_once
        why = (f"the seeded wide cell at {mb} bins x {num_active} slots / "
               f"{hist_mode} does not fit the VMEM model "
               f"(hist_fold_cell_ok)")
        log_once(f"make_hist_fold_fn:{backend}:{why}",
                 f"streamed histogram fold: scatter (carried f32 fold) "
                 f"(resolved backend {backend}): {why}", level="info")
        return None

    n_pad = round_up(block_rows, DEFAULT_ROW_TILE)
    F_pad = data.num_groups     # per-block transpose_bins(feat_tile=None)
    shape, dtype = hist_raw_layout(n_pad, num_active, F_pad, mb, hist_mode)
    interp = _pallas_interpret()

    def init_acc():
        return jnp.zeros(shape, dtype)

    @jax.jit
    def fold(bins, grad, hess, hist_leaf, active, acc, scales=None):
        bins_t = transpose_bins(bins)
        vals, _ = _pack(grad, hess, hist_mode, scales)
        leaf = hist_leaf.astype(jnp.int32)
        return hist_active_pallas(
            bins_t, vals, leaf, active, scales, acc,
            num_features=F_pad, max_bins=mb, mode=hist_mode,
            interpret=interp, raw=True)

    # the unpack MUST be its own jitted program (not eager): eager
    # elementwise dequant skips XLA's fma contraction and lands 1 ulp
    # off the in-memory kernels' fused in-call unpack — enough to break
    # byte identity.  Jitted, the same elementwise graph compiles to the
    # same contraction and matches bitwise (pinned by the identity
    # matrix in tests/test_streaming.py).
    @jax.jit
    def unpack(acc, scales=None):
        return unpack_hist_raw(acc, num_active, data.num_groups, mb,
                               hist_mode, scales)

    return HistFold(fold, init_acc, unpack, hist_mode, quantized)


def make_route_fn(data: DeviceData, backend: str,
                  bins_t: Optional[jnp.ndarray] = None):
    """Per-wave split application closure ``(leaf2, best, sel, new_id)
    -> leaf2`` (the DataPartition::Split analog).  A ``lax.cond`` skips
    the full-data pass when no splits are pending (the root wave and
    drained tail waves)."""
    if uses_pallas(backend):
        if bins_t is None:
            bins_t = transpose_bins(data.bins)
        interp = _pallas_interpret()

        def route_impl(leaf2, best: SplitResult, sel, new_id):
            return route_rows_pallas(
                bins_t, leaf2, best.feature, best.threshold,
                best.default_left, best.is_categorical, best.cat_mask,
                sel, new_id, data.missing_types, data.nan_bins,
                data.default_bins, data.feat_group, data.feat_offset,
                data.num_bins, any_cat=data.has_categorical,
                interpret=interp)
    else:
        def route_impl(leaf2, best: SplitResult, sel, new_id):
            return route_rows_xla(
                data.bins, leaf2, best.feature, best.threshold,
                best.default_left, best.is_categorical, best.cat_mask,
                sel, new_id, data.missing_types, data.nan_bins,
                data.default_bins, data.feat_group, data.feat_offset,
                data.num_bins)

    def route_fn(leaf2, best: SplitResult, sel, new_id):
        return jax.lax.cond(
            jnp.any(sel),
            lambda l2: route_impl(l2, best, sel, new_id),
            lambda l2: l2,
            leaf2)
    return route_fn


def apply_hist_wave(hist_state, new_h, act_small, act_parent, act_sibling,
                    L: int):
    """Shared per-wave histogram bookkeeping for every learner strategy:
    derive each sibling by parent-minus-child subtraction
    (`feature_histogram.hpp:64-70`), persist both children into the
    per-leaf state, and hand back the changed-leaf ids + their grids.

    Returns ``(hist_state, ids [2A], grid [2A, F, B, 3])``.  The grid is
    exactly ``[new_h; sib_h]`` — no re-gather from state; padding slots
    (id -1) carry garbage and their scan results must be dropped by the
    caller (they are: the best-split scatter drops ids < 0).
    """
    with jax.named_scope("tree.hist"):
        parent_h = hist_state[jnp.clip(act_parent, 0, L - 1)]
        sib_h = parent_h - new_h                         # [A, F, B, 3]
        hist_state = hist_state.at[
            jnp.where(act_small >= 0, act_small, L)].set(new_h, mode="drop")
        hist_state = hist_state.at[
            jnp.where(act_sibling >= 0, act_sibling, L)].set(sib_h,
                                                             mode="drop")
        ids = jnp.concatenate([act_small, act_sibling])  # [2A]
        grid = jnp.concatenate([new_h, sib_h], axis=0)   # [2A, F, B, 3]
    return hist_state, ids, grid


def make_fused_fn(data: DeviceData, grad, hess, hist_mode: str,
                  bins_t: jnp.ndarray,
                  scales: Optional[jnp.ndarray] = None):
    """Fused route+hist closure ``(leaf2, best, sel, new_id, active,
    route_leaves) -> (new_h, leaf2_new)`` — one bins stream per wave
    instead of two; ``route_leaves`` (static) from
    :func:`wave_backend_plan`."""
    vals, scales = _pack(grad, hess, hist_mode, scales)
    interp = _pallas_interpret()

    def fused(leaf2, best: SplitResult, sel, new_id, active, route_leaves):
        with jax.named_scope("tree.hist"):
            h, leaf2_new = hist_route_pallas(
                bins_t, vals, leaf2, active,
                best.feature, best.threshold, best.default_left,
                best.is_categorical, best.cat_mask, sel, new_id,
                data.missing_types, data.nan_bins, data.default_bins,
                data.feat_group, data.feat_offset, data.num_bins, scales,
                num_features=data.num_groups, max_bins=data.group_max_bins,
                mode=hist_mode, any_cat=data.has_categorical,
                interpret=interp, route_leaves=route_leaves)
        return h, leaf2_new
    return fused


def make_serial_strategy(data: DeviceData, grad, hess, params: GrowthParams,
                         feature_mask, psum_fn=None, backend: str = "auto",
                         hist_mode: Optional[str] = None,
                         bins_t: Optional[jnp.ndarray] = None,
                         scales: Optional[jnp.ndarray] = None):
    """The serial (and data-parallel, via `psum_fn`) wave strategy:
    histogram the active leaves, subtract siblings, rescan changed leaves.

    `psum_fn` injects the data-parallel histogram collective — the
    reference's ReduceScatter seam (`data_parallel_tree_learner.cpp:147-162`)
    collapses to one psum of the active-leaf histograms.

    Where the kernels histogram quantized values, what crosses the
    shards is the cells' integer code sums (``codes``): rounded against
    the one ``scales`` of all shards, summed exactly by ``psum_fn``, and
    dequantized once after, as one chip dequantizes its own — so the
    histograms, and the tree, are those of the rows however they are
    cut (``tests/test_parallel.py``)."""
    L = params.num_leaves
    mode = effective_hist_mode(hist_mode or default_hist_mode(),
                               data.num_data, _row_shards(psum_fn))
    backend = resolve_backend(data, L, backend, mode)
    codes = (psum_fn is not None and uses_pallas(backend)
             and is_quantized(mode))
    if codes and scales is None:
        raise ValueError("a quantized histogram exchange needs the scales "
                         "of all shards (parallel/learners.py global_scales)")
    hist_fn = make_hist_fn(data, grad, hess, L, backend, mode, bins_t,
                           scales=scales, codes=codes)
    dequant = ((lambda h: dequant_hist(h, scales, mode)) if codes
               else (lambda h: h))

    def wave(hist_state, hist_leaf, act_small, act_parent, act_sibling,
             lsg, lsh, lc):
        new_h = hist_fn(hist_leaf, act_small)   # [A, G, Bg, 3 | C codes]
        if psum_fn is not None:
            new_h = dequant(psum_fn(new_h))
        return rescan_changed(data, params, feature_mask, hist_state, new_h,
                              act_small, act_parent, act_sibling,
                              lsg, lsh, lc)
    return wave


def rescan_changed(data: DeviceData, params: GrowthParams, feature_mask,
                   hist_state, new_h, act_small, act_parent, act_sibling,
                   lsg, lsh, lc):
    """Shared post-histogram flow for every wave path (serial strategy and
    the fused kernel): sibling subtraction, EFB unbundle, rescan of the
    changed leaves."""
    L = hist_state.shape[0]
    hist_state, ids, grid = apply_hist_wave(
        hist_state, new_h, act_small, act_parent, act_sibling, L)
    return scan_grid(data, params, feature_mask, hist_state, ids, grid,
                     lsg, lsh, lc)


def scan_grid(data: DeviceData, params: GrowthParams, feature_mask,
              hist_state, ids, grid, lsg, lsh, lc):
    """EFB unbundle + best-split rescan of the changed-leaf grids — the
    tail of :func:`rescan_changed`, split out because the streamed
    trainer (`boosting/streaming.py`) applies its folded histograms
    itself and scans the result with this.

    With the per-leaf split cache OFF (``LGBM_TPU_SPLIT_CACHE=0``) the
    changed-slot narrowing is discarded: every wave rescans the FULL
    ``[L, F, B]`` histogram state and rewrites the whole cache — the
    O(L·F·B) baseline.  Results are byte-identical (unchanged leaf
    histograms rescan to the identical floats), only the scanned width
    changes.  Either way the scan chunks its feature axis under the
    shared HBM model (`ops/vmem.py split_scan_chunk_features`) so the
    255-bin MSLR stack stays inside budget."""
    with jax.named_scope("tree.split_find"):
        L = hist_state.shape[0]
        if not split_cache_enabled():
            ids = jnp.arange(L, dtype=jnp.int32)
            grid = hist_state
        safe = jnp.clip(ids, 0, L - 1)
        if data.is_bundled:
            from ..ops.histogram import unbundle_grid
            grid = unbundle_grid(grid, lsg[safe], lsh[safe], lc[safe],
                                 data.feat_group, data.feat_offset,
                                 data.num_bins, data.default_bins,
                                 bin_stride(data.max_bins))
        B = grid.shape[2]
        from ..ops.pallas_split import find_best_splits_pallas, split_kernel_ok
        from ..ops.vmem import split_scan_chunk_features
        interp = _os_env.environ.get("LGBM_TPU_SPLIT_INTERPRET") == "1"
        if (split_kernel_ok(grid.shape[1], B, data.has_categorical,
                            num_rows=data.bins.shape[0])
                and (interp or jax.default_backend() == "tpu")):
            # fused split scan: one Pallas call replaces ~50 small XLA ops
            # per wave (the row-independent per-iteration tax, VERDICT r4 #4)
            res = find_best_splits_pallas(
                grid, lsg[safe], lsh[safe], lc[safe], data.num_bins,
                data.missing_types, data.default_bins, B=B,
                params=params.split, feature_mask=feature_mask,
                any_missing=data.has_missing, interpret=interp)
        else:
            fc = split_scan_chunk_features(grid.shape[0], grid.shape[1], B,
                                           any_missing=data.has_missing)
            res = find_best_splits(grid, lsg[safe], lsh[safe], lc[safe],
                                   data.num_bins, data.missing_types,
                                   data.default_bins, data.is_categorical,
                                   params.split, feature_mask,
                                   any_categorical=data.has_categorical,
                                   any_missing=data.has_missing,
                                   feature_chunk=fc)
    return hist_state, ids, res


def build_tree(data: DeviceData,
               grad: jnp.ndarray,
               hess: jnp.ndarray,
               params: GrowthParams,
               bag_mask: Optional[jnp.ndarray] = None,
               feature_mask: Optional[jnp.ndarray] = None,
               strategy=None,
               psum_fn=None,
               hist_backend: str = "auto",
               num_hist_features: Optional[int] = None,
               bins_t: Optional[jnp.ndarray] = None,
               hist_mode: Optional[str] = None,
               scales: Optional[jnp.ndarray] = None) -> BuiltTree:
    """Grow one tree.  Jittable; `psum_fn` lets the data-parallel learner
    inject a collective over active-leaf histograms; `strategy` replaces
    the whole wave procedure (feature/voting-parallel,
    `parallel/learners.py`).  `num_hist_features` overrides the width of
    the histogram state (feature-parallel shards keep only their slice);
    `bins_t` is the once-per-dataset transposed bins (computed here when
    absent); `scales` are the quantized modes' ``[2]`` rounding scales
    where they are not to be taken from this call's own rows (a
    row-sharded learner's are the largest over all shards)."""
    n = data.bins.shape[0]
    L = params.num_leaves

    mode = effective_hist_mode(hist_mode or default_hist_mode(), n,
                               _row_shards(psum_fn))
    backend = resolve_backend(data, L, hist_backend, mode)
    if uses_pallas(backend) and bins_t is None:
        bins_t = transpose_bins(data.bins)

    # the staged waves and each one's histogram call; fused
    # route+hist, judged a wave at a time: one bins stream for a wave
    # whose own call holds every stored column in one tile (serial
    # Pallas path); the others route, then histogram
    waves, A_tail, tail_choice = wave_backend_plan(
        L, params.wave_size, backend, num_groups=data.num_groups,
        max_bins=data.group_max_bins, mode=mode, n_rows=n,
        serial=strategy is None and psum_fn is None,
        any_cat=data.has_categorical)
    wave_cap = params.wave_size if params.wave_size > 0 else L
    # the final route can emit per-row leaf values (gather-free score
    # update) on the serial Pallas path and, a shard's own rows, on the
    # data-parallel one (the leaf values are the same on every shard) —
    # captured BEFORE the serial strategy closure is assigned below
    emit_values = emits_row_values(strategy is None, backend)
    if strategy is None:
        strategy = make_serial_strategy(data, grad, hess, params,
                                        feature_mask, psum_fn=psum_fn,
                                        backend=backend, bins_t=bins_t,
                                        hist_mode=hist_mode, scales=scales)
    fused_fn = (make_fused_fn(data, grad, hess, mode, bins_t, scales)
                if "fused" in (*(w.choice for w in waves), tail_choice)
                else None)
    route_fn = make_route_fn(data, backend, bins_t)

    def scan_changed(hist_state, new_h, s, lsg, lsh, lc):
        return rescan_changed(data, params, feature_mask, hist_state, new_h,
                              s.act_small, s.act_parent, s.act_sibling,
                              lsg, lsh, lc)

    A0 = waves[0].slots if waves else A_tail
    with jax.named_scope("tree.init"):
        state = _init_state(data, grad, hess, params, bag_mask, psum_fn,
                            backend, bins_t, num_hist_features, A0, mode,
                            scales)

    def body(s: _WaveState, A_out: int, choice: str,
             leaves: int) -> _WaveState:
        # --- 0-3: apply last wave's pending splits to the rows, then
        # histogram the active leaves, subtract siblings, rescan.  The
        # fused kernel does the route inside the histogram's bins stream.
        if choice == "fused":
            new_h, leaf2 = fused_fn(s.leaf2, s.best, s.pend_sel,
                                    s.pend_new, s.act_small, leaves)
            hist_state, ids, res = scan_changed(
                s.hist_state, new_h, s, s.leaf_sum_grad, s.leaf_sum_hess,
                s.leaf_count)
        else:
            with jax.named_scope("tree.route"):
                leaf2 = route_fn(s.leaf2, s.best, s.pend_sel, s.pend_new)
            hist_state, ids, res = strategy(
                s.hist_state, leaf2[1], s.act_small, s.act_parent,
                s.act_sibling, s.leaf_sum_grad, s.leaf_sum_hess,
                s.leaf_count)
        with jax.named_scope("tree.update"):
            return _apply_wave(s, leaf2, hist_state, ids, res, A_out,
                               params, wave_cap)

    # --- staged unrolled waves (slot counts track the growing tree) -----
    for i, w in enumerate(waves):
        A_out = waves[i + 1].slots if i + 1 < len(waves) else A_tail
        state = body(state, A_out, w.choice, w.route_leaves)

    # --- while-loop tail at fixed slot count -----------------------------
    def cond(s: _WaveState):
        return (~s.done) & (s.nl < L)

    final = jax.lax.while_loop(
        cond, lambda s: body(s, A_tail, tail_choice, L), state)
    # apply the last wave's pending splits before reading row_leaf; on the
    # Pallas path the same pass emits each row's leaf value (in place of
    # the score update's lv[row_leaf] gather)
    lv_final = jnp.where(final.nl > 1, final.leaf_value,
                         jnp.zeros_like(final.leaf_value))
    with jax.named_scope("tree.route"):
        if emit_values:
            leaf2_final, row_value = route_rows_values_pallas(
                bins_t, final.leaf2, final.best.feature,
                final.best.threshold, final.best.default_left,
                final.best.is_categorical, final.best.cat_mask,
                final.pend_sel, final.pend_new, data.missing_types,
                data.nan_bins, data.default_bins, data.feat_group,
                data.feat_offset, data.num_bins, lv_final,
                any_cat=data.has_categorical,
                interpret=_pallas_interpret())
            row_value = row_value[:n]
        else:
            leaf2_final = route_fn(final.leaf2, final.best, final.pend_sel,
                                   final.pend_new)
            row_value = jnp.zeros(0, jnp.float32)   # empty: caller gathers
    final = final._replace(leaf2=leaf2_final)
    leaf_count = final.leaf_count.astype(jnp.int32)
    if n * _row_shards(psum_fn) > F32_EXACT_ROWS:
        # more rows than float32 counts exactly: the model's leaf counts
        # are the rows routed there, counted in integers (in-bag rows,
        # as the growth's own), summed over the shards
        with jax.named_scope("tree.count"):
            leaf_count = leaf_row_counts(final.leaf2[1, :n], L)
            if psum_fn is not None:
                leaf_count = psum_fn.counts(leaf_count)
    return final.tree._replace(
        leaf_value=final.leaf_value,
        leaf_count=leaf_count,
        leaf_depth=final.leaf_depth,
        num_leaves=final.nl,
        row_leaf=final.leaf2[0, :n],
        row_value=row_value,
    )


def emits_row_values(serial_strategy: bool, backend: str) -> bool:
    """Whether :func:`build_tree` returns ``row_value`` (``[n]``, from
    the final route kernel) or leaves it empty: the serial wave strategy
    (with or without the data-parallel ``psum_fn``) on a Pallas
    backend."""
    return serial_strategy and uses_pallas(backend)


def _init_state(data: DeviceData, grad, hess, params: GrowthParams,
                bag_mask, psum_fn, backend: str, bins_t,
                num_hist_features: Optional[int], A0: int,
                hist_mode: str,
                scales: Optional[jnp.ndarray] = None) -> _WaveState:
    """Initial wave state: empty tree, root leaf stats, root wave active
    set.  Shared by :func:`build_tree` and :func:`build_tree_phases`.
    ``hist_mode`` is the effective mode the waves histogram in,
    ``scales`` what its quantized values are rounded against where that
    is not this call's own rows' largest."""
    n = data.bins.shape[0]
    L = params.num_leaves
    Lm = max(L - 1, 1)
    B = bin_stride(data.max_bins)                  # feature-space stride
    Bh = bin_stride(data.group_max_bins)           # stored-column stride
    Gh = (num_hist_features if num_hist_features is not None
          else data.num_groups)
    n_pad = bins_t.shape[1] if uses_pallas(backend) else n

    row_leaf0 = jnp.zeros(n, jnp.int32)
    hist_leaf0 = (jnp.where(bag_mask, 0, -1).astype(jnp.int32)
                  if bag_mask is not None else row_leaf0)
    leaf2 = jnp.full((2, n_pad), -1, jnp.int32)
    leaf2 = jax.lax.dynamic_update_slice(leaf2, row_leaf0[None, :], (0, 0))
    leaf2 = jax.lax.dynamic_update_slice(leaf2, hist_leaf0[None, :], (1, 0))

    tree = BuiltTree(
        feature=jnp.zeros(Lm, jnp.int32),
        threshold_bin=jnp.zeros(Lm, jnp.int32),
        default_left=jnp.zeros(Lm, bool),
        is_categorical=jnp.zeros(Lm, bool),
        cat_mask=jnp.zeros((Lm, B), bool),
        left_child=jnp.full(Lm, -1, jnp.int32),
        right_child=jnp.full(Lm, -1, jnp.int32),
        gain=jnp.zeros(Lm, jnp.float32),
        internal_value=jnp.zeros(Lm, jnp.float32),
        internal_count=jnp.zeros(Lm, jnp.int32),
        leaf_value=jnp.zeros(L, jnp.float32),
        leaf_count=jnp.zeros(L, jnp.int32),
        leaf_depth=jnp.zeros(L, jnp.int32),
        num_leaves=jnp.asarray(1, jnp.int32),
        row_leaf=row_leaf0,
        row_value=jnp.zeros(0, jnp.float32),
    )

    # root statistics (in-bag) via the canonical chunked reduction:
    # partition-invariant by construction, so the streamed out-of-core
    # trainer reproduces them bitwise from per-block chunk sums
    # (boosting/streaming.py; the old jnp.sum reduction tree could not
    # be reassembled from block partials).  Where the kernels histogram
    # quantized values the totals come from the same int8 codes
    # (root_stats_q: exact integer sums, partition-invariant too: the
    # shards' code sums are summed exactly and dequantized once)
    bag = (leaf2[1] == 0)
    if uses_pallas(backend) and is_quantized(hist_mode):
        vals, scales = _pack(grad, hess, hist_mode, scales)
        code_sums = root_code_sums(vals, bag[:n])
        if psum_fn is not None:
            code_sums = psum_fn(code_sums, "root_psum")
        sum_g, sum_h, cnt = root_stats_q(code_sums, scales, hist_mode)
    else:
        sum_g, sum_h, cnt = root_stats(grad, hess, bag[:n])
        if psum_fn is not None:
            sum_g, sum_h, cnt = psum_fn((sum_g, sum_h, cnt), "root_psum")

    from ..ops.split import leaf_output as _leaf_out
    root_out = _leaf_out(sum_g, sum_h, params.split.lambda_l1,
                         params.split.lambda_l2)

    return _WaveState(
        leaf2=leaf2,
        nl=jnp.asarray(1, jnp.int32), done=jnp.asarray(False),
        leaf_sum_grad=jnp.zeros(L).at[0].set(sum_g),
        leaf_sum_hess=jnp.zeros(L).at[0].set(sum_h),
        leaf_count=jnp.zeros(L).at[0].set(cnt),
        leaf_depth=jnp.zeros(L, jnp.int32),
        leaf_value=jnp.zeros(L, jnp.float32).at[0].set(root_out),
        leaf_parent=jnp.full(L, -1, jnp.int32),
        leaf_is_left=jnp.zeros(L, bool),
        hist_state=jnp.zeros((L, Gh, Bh, 3), jnp.float32),
        best=_empty_best(L, B),
        pend_sel=jnp.zeros(L, bool),
        pend_new=jnp.zeros(L, jnp.int32),
        act_small=jnp.full(A0, -1, jnp.int32).at[0].set(0),  # root wave
        act_parent=jnp.full(A0, -1, jnp.int32),
        act_sibling=jnp.full(A0, -1, jnp.int32),
        tree=tree,
    )


def make_phases_driver(data: DeviceData,
                       params: GrowthParams,
                       hist_backend: str = "auto",
                       bins_t: Optional[jnp.ndarray] = None,
                       hist_mode: Optional[str] = None):
    """Once-per-booster factory for the per-phase-timed UNFUSED wave
    driver (``LGBM_TPU_TIMETAG=phases``).

    Returns ``build(grad, hess, bag_mask=None, feature_mask=None) ->
    BuiltTree`` running the same wave algorithm as :func:`build_tree`
    but with route / hist / scan / update as SEPARATE device dispatches,
    each wrapped in a timetag — the analog of the reference's per-phase
    TIMETAG counters (`serial_tree_learner.cpp:12-39`), which a single
    fused jitted scan cannot attribute.  The jitted phase functions are
    built HERE, once, with grad/hess as traced arguments, so repeated
    trees reuse the compiled programs and the tags time kernels, not
    compiles.  Every dispatch still pays the host-device round trip
    (its size is unverified on a local chip), so read the REPORT'S
    RATIOS, not its sums, and never compare its totals to the fused
    path's wall clock.  Must be called OUTSIDE jit."""
    from ..utils.timetag import tag
    n = data.bins.shape[0]
    L = params.num_leaves
    mode = effective_hist_mode(hist_mode or default_hist_mode(), n)
    backend = resolve_backend(data, L, hist_backend, mode)
    if uses_pallas(backend) and bins_t is None:
        bins_t = jax.jit(transpose_bins)(data.bins)
    _, A_tail = stage_plan(L, params.wave_size)
    wave_cap = params.wave_size if params.wave_size > 0 else L

    route_fn = make_route_fn(data, backend, bins_t)

    @jax.jit
    def init_jit(grad, hess, bag_mask):
        return _init_state(data, grad, hess, params, bag_mask, None,
                           backend, bins_t, None, A_tail, mode)

    @jax.jit
    def hist_jit(grad, hess, s):
        hist_fn = make_hist_fn(data, grad, hess, L, backend, mode, bins_t)
        return hist_fn(s.leaf2[1], s.act_small)

    @jax.jit
    def scan_jit(s, new_h, feature_mask):
        return rescan_changed(
            data, params, feature_mask, s.hist_state, new_h, s.act_small,
            s.act_parent, s.act_sibling, s.leaf_sum_grad, s.leaf_sum_hess,
            s.leaf_count)

    @jax.jit
    def route_jit(s):
        return route_fn(s.leaf2, s.best, s.pend_sel, s.pend_new)

    update_jit = jax.jit(functools.partial(
        _apply_wave, A_out=A_tail, params=params, wave_cap=wave_cap))

    # obs spans ride the same phase boundaries as the timetags: these
    # dispatches are host-blocked (each done() waits on its outputs),
    # so the span durations ARE device time for route (leaf routing) /
    # hist (histogram build) / scan (split find) / update
    from ..obs import span as obs_span

    def build(grad, hess, bag_mask=None, feature_mask=None) -> BuiltTree:
        with obs_span("tree.init"), tag("tree:init") as done:
            # root statistics + state zero-fill: previously the one
            # unattributed dispatch of the phase-timed path (the
            # device-time attribution parser joins XLA ops to named
            # spans — an unnamed dispatch is a coverage hole)
            state = init_jit(grad, hess, bag_mask)
            done(state.leaf_sum_grad)
        while True:
            with obs_span("tree.route"), tag("tree:route") as done:
                leaf2 = route_jit(state)
                done(leaf2)
            state = state._replace(leaf2=leaf2)
            with obs_span("tree.hist"), tag("tree:hist") as done:
                new_h = hist_jit(grad, hess, state)
                done(new_h)
            with obs_span("tree.split_find"), tag("tree:scan") as done:
                hist_state, ids, res = scan_jit(state, new_h, feature_mask)
                done(res.gain)
            with obs_span("tree.update"), tag("tree:update") as done:
                # memcheck: disable=MEM002 -- wave-loop carry on the
                # unfused profiling path; production training rides the
                # fused block whose score state IS donated (gated)
                state = update_jit(state, leaf2, hist_state, ids, res)
                done(state.nl)
            if bool(state.done) or int(state.nl) >= L:
                break
        with obs_span("tree.route"), tag("tree:route") as done:
            leaf2 = route_jit(state)
            done(leaf2)
        state = state._replace(leaf2=leaf2)
        return state.tree._replace(
            leaf_value=state.leaf_value,
            leaf_count=state.leaf_count.astype(jnp.int32),
            leaf_depth=state.leaf_depth,
            num_leaves=state.nl,
            row_leaf=state.leaf2[0, :n],
            row_value=jnp.zeros(0, jnp.float32),   # debug path: gather
        )

    return build


def _apply_wave(s: _WaveState, leaf2, hist_state, ids, res: SplitResult,
                A_out: int, params: GrowthParams,
                wave_cap: int) -> _WaveState:
    """Post-histogram wave bookkeeping: merge rescanned best splits,
    select this wave's splits by gain rank, record tree nodes, update
    leaf state, and stage the next wave's active sets.  Shared between
    the jitted wave body and the phase-timed debug driver
    (:func:`build_tree_phases`)."""
    L = s.leaf_sum_grad.shape[0]
    Lm = s.tree.feature.shape[0]
    best = jax.tree.map(
        lambda cur, new: cur.at[
            jnp.where(ids >= 0, ids, L)].set(new, mode="drop"),
        s.best, res)

    # --- 4: select this wave's splits -------------------------------
    lid = jnp.arange(L)
    gain = jnp.where(lid < s.nl, best.gain, NEG_INF)
    if params.max_depth > 0:
        gain = jnp.where(s.leaf_depth >= params.max_depth, NEG_INF, gain)
    can = gain > 0.0

    order = jnp.argsort(-gain)                      # leaves by gain desc
    rank = jnp.argsort(order)                       # rank[l]
    budget = L - s.nl
    k = jnp.minimum(jnp.minimum(jnp.sum(can), budget),
                    min(wave_cap, A_out))
    sel = can & (rank < k)

    new_id = jnp.where(sel, s.nl + rank, L)         # L => drop scatter
    node_idx = jnp.where(sel, s.nl - 1 + rank, Lm)  # Lm => drop scatter

    # --- 5: record tree nodes (scatter at node_idx; drop unselected)
    t = s.tree
    dl = jnp.where(best.is_categorical, False, best.default_left)
    t = t._replace(
        feature=t.feature.at[node_idx].set(best.feature, mode="drop"),
        threshold_bin=t.threshold_bin.at[node_idx].set(best.threshold,
                                                       mode="drop"),
        default_left=t.default_left.at[node_idx].set(dl, mode="drop"),
        is_categorical=t.is_categorical.at[node_idx].set(
            best.is_categorical, mode="drop"),
        cat_mask=t.cat_mask.at[node_idx].set(best.cat_mask, mode="drop"),
        gain=t.gain.at[node_idx].set(best.gain, mode="drop"),
        internal_value=t.internal_value.at[node_idx].set(
            s.leaf_value, mode="drop"),
        internal_count=t.internal_count.at[node_idx].set(
            s.leaf_count.astype(jnp.int32), mode="drop"),
        left_child=t.left_child.at[node_idx].set(~lid, mode="drop"),
        right_child=t.right_child.at[node_idx].set(
            ~new_id, mode="drop"),
    )
    # fix the parent's child pointer: leaf l was ~l, becomes node_idx
    parent = jnp.where(sel, s.leaf_parent, -1)
    fix_left = jnp.where(sel & s.leaf_is_left & (parent >= 0),
                         parent, Lm)
    fix_right = jnp.where(sel & ~s.leaf_is_left & (parent >= 0),
                          parent, Lm)
    t = t._replace(
        left_child=t.left_child.at[fix_left].set(node_idx, mode="drop"),
        right_child=t.right_child.at[fix_right].set(node_idx, mode="drop"),
    )

    # --- 6: update leaf state: left child keeps id l, right -> new_id
    depth1 = s.leaf_depth + 1
    lsg = jnp.where(sel, best.left_sum_grad, s.leaf_sum_grad)
    lsh = jnp.where(sel, best.left_sum_hess, s.leaf_sum_hess)
    lc = jnp.where(sel, best.left_count, s.leaf_count)
    lv = jnp.where(sel, best.left_output, s.leaf_value)
    ld = jnp.where(sel, depth1, s.leaf_depth)
    lp = jnp.where(sel, node_idx, s.leaf_parent)
    lil = jnp.where(sel, True, s.leaf_is_left)

    lsg = lsg.at[new_id].set(best.right_sum_grad, mode="drop")
    lsh = lsh.at[new_id].set(best.right_sum_hess, mode="drop")
    lc = lc.at[new_id].set(best.right_count, mode="drop")
    lv = lv.at[new_id].set(best.right_output, mode="drop")
    ld = ld.at[new_id].set(depth1, mode="drop")
    lp = lp.at[new_id].set(node_idx, mode="drop")
    lil = lil.at[new_id].set(False, mode="drop")

    # --- 7: this wave's splits become the pending route, applied at
    # the start of the next wave (or post-loop finalization)
    pend_sel = sel
    pend_new = jnp.where(sel, new_id, 0).astype(jnp.int32)

    # --- 8: next wave's active sets (smaller child + subtraction) ---
    # the smaller child gets histogrammed; the sibling is derived from
    # the parent histogram left in slot l (the left child's id)
    smaller_left = best.left_count <= best.right_count
    small_val = jnp.where(smaller_left, lid, new_id)
    sib_val = jnp.where(smaller_left, new_id, lid)
    slot = jnp.where(sel, rank, A_out)
    pad_out = jnp.full(A_out, -1, jnp.int32)
    act_small = pad_out.at[slot].set(small_val, mode="drop")
    act_parent = pad_out.at[slot].set(lid, mode="drop")
    act_sibling = pad_out.at[slot].set(sib_val, mode="drop")

    nl2 = s.nl + k
    return _WaveState(
        leaf2=leaf2, nl=nl2,
        done=(k == 0),
        leaf_sum_grad=lsg, leaf_sum_hess=lsh, leaf_count=lc,
        leaf_depth=ld, leaf_value=lv, leaf_parent=lp, leaf_is_left=lil,
        hist_state=hist_state, best=best,
        pend_sel=pend_sel, pend_new=pend_new,
        act_small=act_small, act_parent=act_parent,
        act_sibling=act_sibling,
        tree=t)


@jax.jit
def predict_built_tree(tree: BuiltTree, data: DeviceData,
                       bins: jnp.ndarray) -> jnp.ndarray:
    """Leaf value per row of `bins` for a just-built tree (validation score
    update path; train rows use ``tree.row_leaf`` directly)."""
    n = bins.shape[0]
    node = jnp.where(tree.num_leaves > 1, 0, ~0) * jnp.ones(n, jnp.int32)

    from ..ops.pallas_route import unbundle_bin

    def body(_, node):
        is_leaf = node < 0
        nidx = jnp.maximum(node, 0)
        f = tree.feature[nidx]
        c = jnp.take_along_axis(
            bins, data.feat_group[f][:, None], axis=1)[:, 0].astype(jnp.int32)
        b = unbundle_bin(c, data.feat_offset[f], data.num_bins[f],
                         data.default_bins[f])
        mt = data.missing_types[f]
        is_missing = (((mt == MISSING_NAN) & (b == data.nan_bins[f]))
                      | ((mt == MISSING_ZERO) & (b == data.default_bins[f])))
        num_left = jnp.where(is_missing, tree.default_left[nidx],
                             b <= tree.threshold_bin[nidx])
        cat_left = tree.cat_mask[nidx, jnp.minimum(b, tree.cat_mask.shape[-1] - 1)]
        go_left = jnp.where(tree.is_categorical[nidx], cat_left, num_left)
        nxt = jnp.where(go_left, tree.left_child[nidx], tree.right_child[nidx])
        return jnp.where(is_leaf, node, nxt)

    depth = tree.leaf_value.shape[0] - 1
    node = jax.lax.fori_loop(0, depth, body, node)
    leaf = jnp.where(node < 0, ~node, 0)
    return tree.leaf_value[leaf]


def built_tree_path_matrices(tree: BuiltTree):
    """Signed leaf-path matrices of a just-built DEVICE tree, traceably
    (the device analog of ``models/tree.py build_path_matrices``, which
    walks host trees with a Python stack).

    ``P[l, m]`` is +1 / -1 when internal node ``m`` lies on leaf ``l``'s
    root path going left / right, else 0; ``plen[l]`` is the leaf's
    depth (-1 for unused slots, so they can never be selected).  Node
    indices are creation-ordered — a child's index always exceeds its
    parent's — so ONE ascending ``fori_loop`` over the node axis
    propagates root paths with tiny ``[L, M]`` state per step; the
    per-ROW work is deferred to a single MXU contraction in
    ``predict_built_tree_matmul``.  Conditional scatters write to a
    trailing dummy slot, the scan-safe alternative to predication."""
    L = tree.leaf_value.shape[0]
    M = max(L - 1, 1)
    nodeP = jnp.zeros((M + 1, M), jnp.float32)
    node_len = jnp.zeros(M + 1, jnp.int32)
    leafP = jnp.zeros((L + 1, M), jnp.float32)
    # stump: leaf 0's zero-length path matches S == 0
    plen = jnp.where((jnp.arange(L + 1) == 0) & (tree.num_leaves <= 1),
                     0, -1).astype(jnp.int32)

    def body(m, carry):
        nodeP, node_len, leafP, plen = carry
        real = m < tree.num_leaves - 1
        blen = node_len[m] + 1
        for child_arr, sign in ((tree.left_child, 1.0),
                                (tree.right_child, -1.0)):
            c = child_arr[m]
            path = nodeP[m].at[m].set(sign)
            is_leaf = c < 0
            li = jnp.where(real & is_leaf, ~c, L)
            ni = jnp.where(real & ~is_leaf, c, M)
            leafP = leafP.at[li].set(path)
            plen = plen.at[li].set(blen)
            nodeP = nodeP.at[ni].set(path)
            node_len = node_len.at[ni].set(blen)
        return nodeP, node_len, leafP, plen

    _, _, leafP, plen = jax.lax.fori_loop(
        0, M, body, (nodeP, node_len, leafP, plen))
    return leafP[:L], plen[:L]


def _select_row_leaf(sel, leaf_value):
    """Per-row leaf value via single-nonzero selection.

    Each row lands in exactly one leaf, so the leaf-axis sum picks one
    value — exact in any order, and registered as a sanctioned numcheck
    context (tools/numcheck/reduction_registry.py)."""
    return jnp.sum(jnp.where(sel, leaf_value[:, None], 0.0), axis=0)


def predict_built_tree_matmul(tree: BuiltTree, data: DeviceData,
                              bins: jnp.ndarray) -> jnp.ndarray:
    """Leaf value per row of ``bins`` with NO per-row tree walk: every
    node decision at once + one path-agreement contraction (the in-scan
    valid-set scorer; same algorithm as ``predict_binned_matmul`` but
    for a single device-resident ``BuiltTree``).

    Steps (all exact): per-node bin values via a one-hot matmul against
    the stored columns (f32 operands — generalized gathers over
    ``[n, M]`` faulted the TPU worker at scale, r4), EFB unbundling +
    missing handling per node, ``d2 = ±1`` decisions, ``S = d2 @ P^T``
    and the leaf is the unique ``l`` with ``S[l] == plen[l]``.
    Numerical splits only — callers route categorical valid sets
    through ``predict_built_tree``."""
    from ..ops.pallas_route import unbundle_bin
    P, plen = built_tree_path_matrices(tree)
    f = tree.feature                              # [M] used-column ids
    G = bins.shape[1]
    # c[m, n]: node m's stored column value per row, as one matmul
    oh = jax.nn.one_hot(data.feat_group[f], G, dtype=jnp.float32)
    c = jax.lax.dot_general(
        oh, bins.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)       # [M, n]
    b = unbundle_bin(c.astype(jnp.int32), data.feat_offset[f][:, None],
                     data.num_bins[f][:, None], data.default_bins[f][:, None])
    mt = data.missing_types[f][:, None]
    is_missing = (((mt == MISSING_NAN) & (b == data.nan_bins[f][:, None]))
                  | ((mt == MISSING_ZERO)
                     & (b == data.default_bins[f][:, None])))
    go_left = jnp.where(is_missing, tree.default_left[:, None],
                        b <= tree.threshold_bin[:, None])
    d2 = (2.0 * go_left - 1.0).astype(jnp.bfloat16)          # [M, n] ±1
    # S[l, n] = sum_m P[l, m] * d2[m, n]; ±1 operands with f32
    # accumulation keep integer path sums exact up to |plen| <= M
    S = jax.lax.dot_general(
        P.astype(jnp.bfloat16), d2, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)       # [L, n]
    sel = (S == plen[:, None].astype(jnp.float32)) & (plen[:, None] >= 0)
    return _select_row_leaf(sel, tree.leaf_value)
