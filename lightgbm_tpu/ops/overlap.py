"""Overlapped multi-chip wave reduction — chunked async psum with a
double-buffered sibling-subtract/apply.

The data-parallel learner's per-wave collective is ONE ``psum`` of the
active-leaf histogram block ``[A, G, B, 3]``
(`parallel/learners.py`, the ReduceScatter seam of the reference's
`data_parallel_tree_learner.cpp:147-162`).  The unoverlapped schedule
serializes wire and compute: the whole reduction must land before the
first byte of sibling subtraction / split scanning runs.  This module
lowers the SAME logical reduction to ``LGBM_TPU_OVERLAP_CHUNKS``
independent ``psum``s over disjoint stored-column ranges and
double-buffers the per-chunk consumers: chunk ``c``'s sibling
subtraction and histogram-state scatter issue as soon as chunk ``c``
lands, while chunk ``c+1``'s reduction is still in flight — XLA's async
collectives (all-reduce start/done on ICI) overlap the remaining wire
time with that compute.  The cross-feature split scan still joins all
chunks (its argmax spans every feature), so the hidden latency is the
reduction tail, which is exactly the part that grows with chip count.

BIT-EXACTNESS (the multi-chip acceptance contract): ``psum`` reduces
elementwise across shards, so reducing disjoint column slices and
concatenating is bit-identical to reducing the whole block — same adds,
same per-element order, no reassociation.  The per-chunk subtract and
scatters touch disjoint column ranges of the same state, preserving the
unoverlapped read-before-write semantics (the parent slot may BE the
small-child slot; each chunk reads its parent columns before writing
them, exactly like the full-block path).  ``tests/test_overlap.py``
pins tree-for-tree bit equality on a 2-shard CPU mesh and
``__graft_entry__.dryrun_multichip`` re-runs the divergence-envelope
gate with overlap on.

SCHEDULE CONTRACT (spmdcheck + flight recorder): the recorded schedule
is the LOGICAL one — one ``parallel.learners.hist_psum`` fingerprint
per wave with the full ``[A, G, B, 3]`` operand, identical to the
unoverlapped path in site/op/axis/shape/order (``tests/test_overlap.py``
pins digest equality).  The chunked lowering is rank-invariant by
construction: chunk boundaries derive from the static column count, so
every rank issues the identical physical sequence too.

Knobs: ``LGBM_TPU_OVERLAP=0`` disables (plain single-psum schedule);
``LGBM_TPU_OVERLAP_CHUNKS`` sets the chunk count (default 2; clamped to
the column count).

MEASURED (four v5e chips, 4 x 13,281,250 rows x 67 x 63 bins x 255
leaves, int8h; `PERF.md` §5, PR 28): there is nothing to hide.  The
wave reductions take 1.21 ms an iteration chunked and 0.99 ms plain, and
the chunked bookkeeping costs 46 ms an iteration more than the plain
one's (each per-chunk ``.at[...].set`` of the ``[255, 67, 64, 3]``
histogram state copies it: six copies of 6.1 ms): 0.708 s a step with
overlap on, 0.663 s with it off.  Recorded, not acted on: the default
stays on until a ``perf_opt`` PR judges it (ROADMAP S6).
"""
from __future__ import annotations

import os
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..obs.flight_recorder import record as _fr_record


def overlap_enabled() -> bool:
    """Whether the data-parallel wave reduction runs double-buffered
    (default ON: bit-exact vs the serial-psum schedule, so there is no
    accuracy trade — ``LGBM_TPU_OVERLAP=0`` is the A/B escape hatch)."""
    return os.environ.get("LGBM_TPU_OVERLAP", "1") != "0"


def overlap_chunks() -> int:
    return max(1, int(os.environ.get("LGBM_TPU_OVERLAP_CHUNKS", "2") or 2))


def _chunk_bounds(G: int, chunks: int) -> List[Tuple[int, int]]:
    """Static column-range boundaries: ``chunks`` near-equal slices of
    ``[0, G)`` (clamped to at most one column per chunk)."""
    chunks = max(1, min(chunks, G))
    step = -(-G // chunks)
    return [(lo, min(lo + step, G)) for lo in range(0, G, step)]


def wave_psum(x: jnp.ndarray, axis: str,
              chunks: Optional[int] = None) -> jnp.ndarray:
    """The logical ``psum(x, axis)`` of a ``[A, G, ...]`` wave block,
    lowered to independent column-chunk psums (bit-identical; the
    chunks pipeline against each other on the interconnect)."""
    if chunks is None:
        chunks = overlap_chunks()
    bounds = _chunk_bounds(x.shape[1], chunks)
    with jax.named_scope("collective.hist_psum"):
        if len(bounds) <= 1:
            return jax.lax.psum(x, axis)
        return jnp.concatenate(
            [jax.lax.psum(x[:, lo:hi], axis) for lo, hi in bounds], axis=1)


def reduce_apply_overlapped(hist_state: jnp.ndarray, new_h: jnp.ndarray,
                            act_small: jnp.ndarray, act_parent: jnp.ndarray,
                            act_sibling: jnp.ndarray, L: int, axis: str,
                            reduce: Callable, dequant: Callable,
                            chunks: Optional[int] = None):
    """Double-buffered reduce + per-wave histogram bookkeeping: the
    overlapped drop-in for ``psum`` followed by
    :func:`~lightgbm_tpu.learner.serial.apply_hist_wave`.

    Per column chunk: reduce the local block, derive the sibling by
    parent-minus-child subtraction, and persist both children into the
    per-leaf state — so each chunk's subtract/scatter consumes its
    reduction as it lands while later chunks are still on the wire.
    ``reduce`` is the learner's reduction of one chunk over ``axis``
    (`parallel/learners.py` ``Psum.reduce``: under the scope
    ``collective.hist_psum``, float32 histograms by ``psum``, the
    quantized modes' int32 code sums exactly) and ``dequant`` what turns
    a reduced chunk into ``[A, gc, B, 3]`` float32 (dequantizing the
    code sums; the identity for float32 histograms): both elementwise
    over the columns, so the chunks are those of the whole block.
    Returns ``(hist_state, ids [2A], grid [2A, G, B, 3])`` with values
    bit-identical to the unoverlapped path (see module docstring).
    """
    if chunks is None:
        chunks = overlap_chunks()
    # the LOGICAL schedule entry: one reduction per wave, full operand —
    # identical fingerprint to the unoverlapped `_psum` record
    _fr_record("parallel.learners.hist_psum", "psum", axis, new_h)
    # the bookkeeping under the scope `apply_hist_wave` gives the same
    # work on the unoverlapped path; the reductions under their own
    with jax.named_scope("tree.hist"):
        parent_safe = jnp.clip(act_parent, 0, L - 1)
        small_slot = jnp.where(act_small >= 0, act_small, L)
        sib_slot = jnp.where(act_sibling >= 0, act_sibling, L)
        h_parts: List[jnp.ndarray] = []
        sib_parts: List[jnp.ndarray] = []
        for lo, hi in _chunk_bounds(new_h.shape[1], chunks):
            h_c = dequant(reduce(new_h[:, lo:hi]))        # [A, gc, B, 3]
            parent_c = hist_state[parent_safe, lo:hi]
            sib_c = parent_c - h_c
            hist_state = hist_state.at[small_slot, lo:hi].set(h_c,
                                                              mode="drop")
            hist_state = hist_state.at[sib_slot, lo:hi].set(sib_c,
                                                            mode="drop")
            h_parts.append(h_c)
            sib_parts.append(sib_c)
        new_h_red = jnp.concatenate(h_parts, axis=1)
        sib_h = jnp.concatenate(sib_parts, axis=1)
        ids = jnp.concatenate([act_small, act_sibling])      # [2A]
        grid = jnp.concatenate([new_h_red, sib_h], axis=0)   # [2A, G, B, 3]
    return hist_state, ids, grid
