"""Pallas TPU histogram kernel — one-hot matmuls on the MXU.

The TPU answer to the reference's OpenCL histogram machinery
(`/root/reference/src/treelearner/ocl/histogram256.cl:94-130` local-memory
atomic float adds, `src/treelearner/gpu_tree_learner.cpp:581-654` kernel
variants, `:890-975` async pipeline).  TPUs have no atomics, so the
scatter-add becomes dense linear algebra:

For one row-tile of ``T`` rows we build, entirely in VMEM,

* ``oh``  ``[F*B, T]``   one-hot of each row's (feature, bin) joint index,
* ``vw``  ``[T, cols]``  per-row values ``(grad, hess, 1)`` replicated into
  the column block of the row's leaf — nonzero only where the row's leaf
  is in the ``active`` list (the wave's "smaller children",
  `serial_tree_learner.cpp:358-372`),

and accumulate ``oh @ vw -> [F*B, cols]`` into a VMEM accumulator over the
row grid.  The one-hot itself is produced by per-feature broadcast
compares against a bin iota (:func:`_onehot_bins`) — no gathers, no
cross-lane reshapes, and no intermediate beyond the one-hot (bf16, or
int8 on a quantized mode).

The column count adapts to the wave: ``cols = round128(C * round8(A))``,
so MXU work scales with the number of active SLOTS, live or ``-1`` — the
first waves of a tree (1, 1, 2, 4, ... smaller children) cost a fraction
of a full wave.  The staged wave plan in ``learner/serial.py`` exploits
this by sizing each wave's slots by the splits the wave before it can
make.

Memory layout notes:

* ``bins_t`` is the binned matrix TRANSPOSED to ``[F, n]`` uint8 (one
  byte per element on the HBM stream; converted to bf16 in VMEM —
  bin ids <= 256 are exact in bf16; larger bin counts are routed to the
  scatter backend by :func:`pallas_config_ok`).  The transpose is done
  once per dataset; the kernel then streams ``[Ft, T]`` blocks with the
  row dimension on lanes.
* bins are laid out at a fixed power-of-two stride ``B`` per feature, so
  the output is directly the padded ``[A, F, B, 3]`` grid the vectorized
  split scan consumes — no ragged offsets.
* precision: the one-hot is exact in bf16.  Values are either bf16
  (``mode="bf16"``, C=3) or split into hi+lo bf16 pairs
  (``mode="hilo"``, C=5) giving ~f32 accuracy at 5/3 the MACs; counts are
  exact either way (MXU accumulates in f32).  This mirrors the
  reference's GPU single-precision trade-off
  (`docs/GPU-Performance.rst:135-161`).  The default is the QUANTIZED
  path (``mode="int8h"``, :func:`pack_values_q`): int8 operands on the
  MXU's integer path (twice the bf16 peak on a v5e by its published
  figures) with EXACT int32 accumulation.

On 4-bit bin packing (the reference's ``dense_nbits_bin.hpp`` /
Feature4 DWORD lever, twice proposed as the HBM lever): deliberately
not built.  By the kernel's own arithmetic the uint8 bins stream is a
small part of what a wave moves (28 bytes a row at 28 features, next to
the one-hot and value operands built in VMEM), and halving it at
``max_bin<=15`` helps only a config the benchmarks do not use.  Whether
the wave is MXU/VPU-bound, as that reasoning assumes, is unverified on a
local chip: no device trace has been read there (PERF.md).
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the VMEM feasibility model (budget, per-grid-cell arithmetic, tile
# caps) is SHARED across every Pallas kernel — ops/vmem.py is its home
# (and what tools/memcheck's MEM004 keys on); the old underscore names
# stay bound here for the kernels and tests that grew up on them
from .vmem import (VMEM_BUDGET_BYTES, cell_vmem_bytes, hist_cell_ok,
                   hist_tiling, round_up as _round_up)

LANE = 128
# the largest row tile a kernel grid step may take; env-tunable for A/B
# perf work.  Each call takes the tile of its least modelled time among
# the powers of two from this down to 1024 (ops/vmem.hist_tiling).
# transpose_bins/pack_values pad to this, so any power-of-two tile <= it
# divides n_pad; pallas_route imports it for the same reason.
DEFAULT_ROW_TILE = int(os.environ.get("LGBM_TPU_ROW_TILE", 2048))


def split_hi_lo(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Split f32 into a bf16-representable hi + f32 residual lo.

    Done by BIT-MASKING the low 16 mantissa bits, NOT by
    ``x.astype(bf16).astype(f32)``: XLA's simplifier folds that convert
    pair to a no-op under jit, which silently turned every hi/lo pair
    into (x, 0) — hilo histograms degraded to plain bf16 and the
    route-emitted leaf values lost their lo correction (found via a
    500-iteration parity run drifting ~0.006 AUC from the exact scatter
    path).  The masked hi is exactly bf16-representable (truncation), so
    the MXU's operand rounding keeps it intact and ``hi + lo == x``
    recovers f32 to ~2^-15 relative after the lo product's own rounding.
    """
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    hi = jax.lax.bitcast_convert_type(
        bits & jnp.uint32(0xFFFF0000), jnp.float32)
    return hi, x - hi


# layout arithmetic shared with the VMEM model (ops/vmem.py owns it so
# the feasibility predicates and the kernels can never disagree on it)
from .vmem import (bin_stride, col_layout as _col_layout,  # noqa: E402
                   is_quantized)


def pallas_config_ok(max_bins: int, num_leaves: int, mode: str) -> bool:
    """Whether the matmul kernel can handle this config exactly.

    * bin ids ride through bf16, exact only up to 256 — larger bin counts
      (``Dataset`` switches to int32 bins past 256) need the scatter path;
    * the ``[feat_tile*B, cols]`` f32 accumulator must fit the minimum
      feat_tile of 8 within VMEM.
    """
    if max_bins > 256:
        return False
    # the route kernel builds a [round128(L), T] f32 leaf one-hot in VMEM
    # (ops/pallas_route.py); past ~1024 leaves it no longer fits
    if num_leaves > 1024:
        return False
    # the staged wave plan (learner/serial.py stage_plan) caps active
    # slots at 128 regardless of num_leaves; the minimum feature tile
    # of 8 must fit the full VMEM model at the 1024-row fallback tile
    # (ops/vmem.row_tiles goes down to it) — ADVICE r2: the accumulator
    # alone under-counts
    return hist_cell_ok(max_bins, min(max(1, num_leaves // 2), 128), mode)


def transpose_bins(bins: jnp.ndarray, row_tile: int = DEFAULT_ROW_TILE,
                   feat_tile: int | None = None) -> jnp.ndarray:
    """``[n, F] uint8 -> [F_pad, n_pad] uint8`` once-per-dataset prep."""
    n, F = bins.shape
    n_pad = _round_up(n, row_tile)
    F_pad = _round_up(F, feat_tile or F)
    out = jnp.zeros((F_pad, n_pad), jnp.uint8)
    return jax.lax.dynamic_update_slice(
        out, bins.T.astype(jnp.uint8), (0, 0))


def transpose_bins_host(bins: "np.ndarray", row_tile: int = DEFAULT_ROW_TILE,
                        feat_tile: int | None = None) -> "np.ndarray":
    """Host (numpy) twin of :func:`transpose_bins` — same padding layout.
    Used at booster init on small datasets, where the jitted transpose's
    one-time compile costs more than the extra host->device copy."""
    import numpy as np
    n, F = bins.shape
    n_pad = _round_up(n, row_tile)
    F_pad = _round_up(F, feat_tile or F)
    out = np.zeros((F_pad, n_pad), np.uint8)
    out[:F, :n] = np.asarray(bins, np.uint8).T
    return out


def pack_values(grad: jnp.ndarray, hess: jnp.ndarray, mode: str,
                row_tile: int = DEFAULT_ROW_TILE) -> jnp.ndarray:
    """Build the per-row value rows ``[C, n_pad]`` once per tree.

    mode="bf16": C=3 ``(g, h, 1)``; mode="hilo": C=5
    ``(g_hi, g_lo, h_hi, h_lo, 1)`` with ``x == x_hi + x_lo`` to ~2^-17.

    Rows-on-lanes layout: the row dimension is the minor (lane) axis both
    here and in the kernels, so the host-side pad/stack write dense
    ``[C, n]`` tiles (the previous ``[n, C]`` layout put C=3 on lanes —
    a pad+copy per iteration); padding rows carry 0.
    """
    n = grad.shape[0]
    n_pad = _round_up(n, row_tile)
    pad = (0, n_pad - n)

    def p(x):
        return jnp.pad(x.astype(jnp.float32), pad)

    if mode == "hilo":
        g_hi, g_lo = split_hi_lo(grad)
        h_hi, h_lo = split_hi_lo(hess)
        rows = [p(g_hi), p(g_lo), p(h_hi), p(h_lo),
                p(jnp.ones_like(grad))]
    elif mode == "ghilo":
        # hi/lo for GRADIENTS only (C=4).  Parity data: this does NOT
        # help — grad bin sums tolerate bf16; kept for the record
        g_hi, g_lo = split_hi_lo(grad)
        rows = [p(g_hi), p(g_lo), p(hess), p(jnp.ones_like(grad))]
    elif mode == "hhilo":
        # hi/lo for HESSIANS only (C=4): the recorded parity table shows
        # hessian precision is what drives 500-iteration quality (gains
        # and leaf outputs divide by hessian sums), while gradient sums
        # tolerate bf16 — 4/3 the MXU work of bf16 for hilo-grade AUC
        h_hi, h_lo = split_hi_lo(hess)
        rows = [p(grad), p(h_hi), p(h_lo), p(jnp.ones_like(grad))]
    else:
        rows = [p(grad), p(hess), p(jnp.ones_like(grad))]
    return jnp.stack(rows, axis=0)


# a code's step is its scale times one of these: written as products, as
# a compiler writes a division by a constant anyway (an eager division,
# whose divisor is an argument, came out an ulp off the jitted one)
_INV_127 = 1.0 / 127.0
_INV_16129 = 1.0 / 16129.0


def quant_scales(grad: jnp.ndarray, hess: jnp.ndarray) -> jnp.ndarray:
    """``[2] f32 (sg, sh)``: the largest magnitudes of the rows given,
    the scales :func:`pack_values_q` rounds them against.  A maximum is
    exact in any order, so the largest over the shards' results
    (``pmax``, `parallel/learners.py`) is that of all the rows."""
    sg = jnp.maximum(jnp.max(jnp.abs(grad.astype(jnp.float32))), 1e-30)
    sh = jnp.maximum(jnp.max(jnp.abs(hess.astype(jnp.float32))), 1e-30)
    return jnp.stack([sg, sh])


def pack_values_q(grad: jnp.ndarray, hess: jnp.ndarray, mode: str,
                  row_tile: int = DEFAULT_ROW_TILE,
                  key: jnp.ndarray | None = None,
                  scales: jnp.ndarray | None = None):
    """Quantized value rows for the int8 MXU path: ``-> (vals int8
    [C, n_pad], scales f32 [2])``.

    The TPU answer to the reference 4.x quantized-training idea
    (gradient discretization): the MXU's int8 path has twice the bf16
    peak on a v5e (published figures; what the kernel reaches of either
    is not measured on a local chip), and the
    one-hot operand is 0/1 so every histogram cell accumulates EXACTLY
    in int32 (<= n*127 < 2^31 for n <= 16M rows — no float rounding at
    all; the only error is the per-row quantization).

    mode="int8": C=3 ``(g_q, h_q, 1)``, g/h at 127/max|.| scales.
    mode="int8h": C=4 ``(g_q, h_hi, h_lo, 1)`` — the hessian rides as a
    two-level int8 pair (hi at sh/127, lo quantizes the hi residual at
    sh/16129, ~14-bit absolute precision) because leaf values and gains
    divide by hessian sums (see default_hist_mode's parity notes).
    mode="int8hh": C=5 — hi/lo pairs for BOTH gradient and hessian
    (~14-bit each; 5/4 the MXU work of int8h).

    ``key``: optional PRNG key for stochastic rounding (unbiased sums:
    E[q] == x, so quantization noise averages out over a leaf instead
    of accumulating a rounding bias).

    ``scales``: optional precomputed ``[2] f32 (sg, sh)`` — the streamed
    fold path (``boosting/streaming.py``) quantizes each BLOCK of a tree
    with the tree's GLOBAL absmax scales (host-computed over every
    block), and the row-sharded learners each SHARD with the largest
    over all shards (`parallel/learners.py`), so per-row int8 codes —
    and therefore the exact int32 bin sums — are bitwise what the
    monolithic in-memory pack produces.  When omitted, scales are
    derived from this call's rows (:func:`quant_scales`).
    """
    n = grad.shape[0]
    n_pad = _round_up(n, row_tile)
    pad = (0, n_pad - n)
    g = grad.astype(jnp.float32)
    h = hess.astype(jnp.float32)
    if scales is None:
        scales = quant_scales(g, h)
    sg, sh = scales[0], scales[1]

    def q(x, scale, sub):
        t = x * (127.0 / scale)
        if key is not None:
            t = t + jax.random.uniform(
                jax.random.fold_in(key, sub), t.shape, minval=-0.5,
                maxval=0.5)
        return jnp.clip(jnp.round(t), -127, 127)

    def hilo8(x, scale, sub):
        hi = jnp.clip(jnp.round(x * (127.0 / scale)), -127, 127)
        # the residual x - hi * step by two exact products: the step's
        # upper 12 mantissa bits and its rest, each times a 7-bit code,
        # fit float32.  The one inexact product hi * step a compiler
        # contracts into the subtraction or not (an FMA, as the program
        # around it fuses), which moved the residual's last bit: rows
        # near a half took another low code in the sharded program than
        # in the serial one
        step = scale * _INV_127
        top = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(step, jnp.uint32)
            & jnp.uint32(0xFFFFF000), jnp.float32)
        lo = q((x - hi * top) - hi * (step - top), step, sub)
        return hi, lo

    if mode == "int8hh":
        ghi, glo = hilo8(g, sg, 0)
        hhi, hlo = hilo8(h, sh, 1)
        rows = [ghi, glo, hhi, hlo, jnp.ones_like(ghi)]
    elif mode == "int8h":
        hhi, hlo = hilo8(h, sh, 1)
        rows = [q(g, sg, 0), hhi, hlo, jnp.ones_like(hhi)]
    else:
        rows = [q(g, sg, 0), q(h, sh, 1), jnp.ones_like(g)]
    vals = jnp.stack([jnp.pad(r, pad) for r in rows], axis=0)
    return vals.astype(jnp.int8), jnp.stack([sg, sh])


# an int32 cell sums int8 codes exactly while rows * 127 < 2^31: the
# most rows of one CHUNK (16,909,320).  More rows than that are summed
# in chunks, each exact in int32, whose partials add as limbs
INT8_ROW_LIMIT = ((1 << 31) - 1) // 127

# the most int32 partials (row shards x row chunks) whose limbs
# :func:`sum_code_limbs` / ``psum_codes`` add exactly
MAX_CODE_SHARDS = 511


def row_chunks(units: int, unit_rows: int = 1,
               row_limit: int = INT8_ROW_LIMIT):
    """``-> (K, units a chunk)``: the fewest chunks of at most
    ``row_limit`` rows that hold ``units`` units of ``unit_rows`` rows
    (a kernel's row tiles; single rows), the units shared evenly.
    Static, from the shape: ``K == 1`` wherever one int32 sum holds."""
    fit = max(1, row_limit // unit_rows)
    K = -(-units // fit)
    return K, -(-units // K)


class CodeLimbs(NamedTuple):
    """An integer code sum past int32 as two int32 limbs: ``hi * 2^16 +
    lo``.  What tells a limb pair from any other pair of arrays."""
    hi: jnp.ndarray
    lo: jnp.ndarray


def code_limbs(x: jnp.ndarray) -> CodeLimbs:
    """An int32 code sum as two 16-bit limbs ``(hi, lo)``: ``x = hi *
    2^16 + lo`` with ``lo`` in ``[0, 2^16)`` (``>>`` is arithmetic, so
    this holds for negative ``x`` too)."""
    return CodeLimbs(x >> 16, x & 0xFFFF)


def sum_code_limbs(parts) -> CodeLimbs:
    """Several int32 code sums (arrays of one shape: the shards' of a
    stream, the row chunks' of a chip) added exactly: ``-> (hi, lo)``
    limbs of each cell's total, ``lo`` in ``[0, 2^16)``.  The twin of
    the mesh exchange (`parallel/learners.py` ``psum_codes``, whose
    bounds hold here: at most 511 parts)."""
    his, los = zip(*(code_limbs(p) for p in parts))
    return carry_limbs(sum(his[1:], his[0]), sum(los[1:], los[0]))


def carry_limbs(hi: jnp.ndarray, lo: jnp.ndarray) -> CodeLimbs:
    """Limbs whose ``lo`` holds a sum of low limbs ``->`` the pair of
    the same total with ``lo`` back in ``[0, 2^16)``: the one pair a
    total has, whatever sums it was made of."""
    return CodeLimbs(hi + (lo >> 16), lo & 0xFFFF)


def _limbs_f32(hi: jnp.ndarray, lo: jnp.ndarray) -> jnp.ndarray:
    """``hi * 2^16 + lo`` (int32 limbs, ``lo`` of any size that fits) as
    float32.  ``lo``'s carry moves to ``hi`` first; then both
    conversions and the product by 2^16 are exact while ``|hi| <= 2^24``
    and the one addition rounds the total to nearest, as a conversion of
    an integer holding it would.  No product here is inexact, so a
    compiler that contracts a multiply into the add (an FMA, formed or
    not with the program around) gives the same float: serial and
    sharded programs dequantize a cell alike."""
    hi, lo = carry_limbs(hi, lo)
    return hi.astype(jnp.float32) * 65536.0 + lo.astype(jnp.float32)


def dequant_hist(out, scales: jnp.ndarray, mode: str) -> jnp.ndarray:
    """``[A, F, B, C] int32 (+ scales) -> [A, F, B, 3] f32`` — undo
    :func:`pack_values_q` after exact integer accumulation.

    ``out`` is one int32 accumulator's code sums or the sums of several
    (a chip's row chunks, :func:`sum_code_limbs`; all shards' from the
    row-sharded exchange, `parallel/learners.py` ``psum_codes``) as a
    pair of int32 limbs (:func:`code_limbs`): one accumulator's sums go
    through the same limbs, so the same total gives the same floats
    either way.  A hi/lo pair of value columns is combined
    as integers, ``127 * hi + lo`` in units of ``scale / 16129`` (limb
    by limb: ``|127 * hi_limb + lo_limb|`` stays under 2^31 for the 511
    shards ``psum_codes`` admits), and rounded to float32 once: no
    sum of two inexact products, which a compiler may or may not
    contract into an FMA as the program around it fuses."""
    sg, sh = scales[0], scales[1]
    hi, lo = out if isinstance(out, tuple) else code_limbs(out)

    def col(k):
        return _limbs_f32(hi[..., k], lo[..., k])

    def pair(k):
        return _limbs_f32(127 * hi[..., k] + hi[..., k + 1],
                          127 * lo[..., k] + lo[..., k + 1])

    if mode == "int8hh":
        g = pair(0) * (sg * _INV_16129)
        h = pair(2) * (sh * _INV_16129)
        cnt = col(4)
    elif mode == "int8h":
        g = col(0) * (sg * _INV_127)
        h = pair(1) * (sh * _INV_16129)
        cnt = col(3)
    else:
        g = col(0) * (sg * _INV_127)
        h = col(1) * (sh * _INV_127)
        cnt = col(2)
    return jnp.stack([g, h, cnt], axis=-1)


def _onehot_bins(bins_i32: jnp.ndarray, B: int,
                 dtype=jnp.bfloat16) -> jnp.ndarray:
    """``[Ft, T] i32 -> [Ft*B, T]`` joint (feature, bin) one-hot
    (bf16, or int8 on the quantized path).

    ONE rank-3 broadcast-compare ``[Ft, 1, T] == [1, B, T]`` reshaped to
    ``[Ft*B, T]`` (leading-dim merge, layout-free) — no matmul, no f32
    intermediate, and no per-feature concatenate: the concat of Ft
    ``[B, T]`` slices re-copied the whole one-hot (extra VMEM traffic
    that, on the chip the kernel was first tuned on, set the floor of
    small waves; unverified on a local chip)."""
    Ft, T = bins_i32.shape
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (1, B, T), 1)
    oh = bins_i32[:, None, :] == iota_b
    if dtype == jnp.int8:
        # via i32: direct i1->i8 hits Mosaic's unsupported
        # (8,128)->(32,128) relayout
        return oh.astype(jnp.int32).reshape(Ft * B, T).astype(jnp.int8)
    return oh.astype(dtype).reshape(Ft * B, T)


def _weighted_cols(m_bool: jnp.ndarray, vals: jnp.ndarray, n_cols: int,
                   pad_cols: int, dtype) -> jnp.ndarray:
    """``(m_bool [A_pad, T], vals [C, T]) -> vw [cols, T]`` in ``dtype``
    (bf16, or int8 on the quantized path), rows ordered ``c * A_pad + a``
    (c-major, matching the caller's output unpack).  One rank-3
    broadcast + leading-dim merge — a per-column concat would re-copy
    the whole block.  int8 uses a select, not a multiply (Mosaic has no
    vector<i8> muli legalization)."""
    A_pad, T = m_bool.shape
    if dtype == jnp.int8:
        # build in i32, narrow once: Mosaic has no vector<i8> muli and
        # i1->i8 relayout ((8,128) -> (32,128) tiling) is unsupported,
        # but i32 compute + one trunc to i8 legalizes cleanly
        mi = m_bool.astype(jnp.int32)
        vw = (vals.astype(jnp.int32)[:n_cols, None, :]
              * mi[None, :, :]).reshape(n_cols * A_pad, T).astype(jnp.int8)
    else:
        vw = (vals[:n_cols, None, :].astype(dtype)
              * m_bool.astype(dtype)[None, :, :]).reshape(
                  n_cols * A_pad, T)
    if pad_cols:
        vw = jnp.concatenate(
            [vw, jnp.zeros((pad_cols, T), dtype)], axis=0)
    return vw


def pad_features(bins_t: jnp.ndarray, F_grid: int) -> jnp.ndarray:
    """``[F_pad, n_pad] -> [F_grid, n_pad]``: all-zero feature rows up
    to a call's whole number of feature tiles.  One expression for one
    ``F_grid``: the calls of a tree that share it share the copy."""
    F_pad = bins_t.shape[0]
    if F_grid == F_pad:
        return bins_t
    return jnp.pad(bins_t, ((0, F_grid - F_pad), (0, 0)))


def _hist_kernel(active_ref, bins_ref, vals_ref, leaf_ref,
                 *refs, n_cols: int, B: int, pad_cols: int,
                 seeded: bool = False, chunk_tiles: int = 0):
    """One (feature-tile, row-tile) grid cell; accumulates over row tiles.

    Everything rides rows-on-lanes: the leaf mask is built ``[A_pad, T]``
    (no per-tile transpose of the leaf row) and the weighted values as
    ``vw [cols, T]``, contracted against the one-hot on the lane
    dimension of BOTH operands.

    ``seeded``: the out-of-core fold variant.  Instead of zero-initing
    the accumulator on the first row tile of each feature block, the
    kernel LOADS a carried accumulator operand (``acc_ref``, aliased to
    the output buffer via ``input_output_aliases`` so the seed is a
    donated in-place init, not a copy).  A per-block call is then a
    bitwise EXTENSION of the monolithic kernel: same adds in the same
    order, just split across calls — which is what puts streamed
    training in the byte-identity domain on the kernel backends.

    ``chunk_tiles``: where the rows of a call are more than one int32
    cell sums exactly, the output has a leading axis of row chunks (the
    caller's ``BlockSpec`` moves on every ``chunk_tiles`` row tiles) and
    the accumulator starts anew at each chunk's first tile.
    """
    if seeded:
        acc_ref, out_ref = refs
    else:
        (out_ref,) = refs
    rt = pl.program_id(1)

    @pl.when(rt % chunk_tiles == 0 if chunk_tiles else rt == 0)
    def _():
        if seeded:
            out_ref[:] = acc_ref[:]
        else:
            out_ref[:] = jnp.zeros_like(out_ref)

    quant = vals_ref.dtype == jnp.int8
    cdt = jnp.int8 if quant else jnp.bfloat16
    # [Ft*B, T] joint (feature, bin) one-hot
    oh = _onehot_bins(bins_ref[:].astype(jnp.int32), B, cdt)

    # [A_pad, T] leaf membership mask over the active-leaf list
    m = active_ref[:] == leaf_ref[:]
    vals = vals_ref[:]                                 # [C, T] f32/int8
    vw = _weighted_cols(m, vals, n_cols, pad_cols, cdt)      # [cols, T]

    out_ref[:] += jax.lax.dot_general(
        oh, vw, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32 if quant else jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("num_features", "max_bins", "mode", "row_tile",
                     "interpret", "raw", "row_limit"))
def hist_active_pallas(bins_t: jnp.ndarray,
                       vals: jnp.ndarray,
                       row_leaf: jnp.ndarray,
                       active: jnp.ndarray,
                       scales: jnp.ndarray | None = None,
                       acc: jnp.ndarray | None = None,
                       *,
                       num_features: int,
                       max_bins: int,
                       mode: str = "hilo",
                       row_tile: int = DEFAULT_ROW_TILE,
                       interpret: bool = False,
                       raw: bool = False,
                       row_limit: int = INT8_ROW_LIMIT) -> jnp.ndarray:
    """Histograms for the active leaves: ``-> [A, F, B, 3]`` float32.

    Args:
      bins_t: ``[F_pad, n_pad]`` uint8 transposed binned matrix
        (:func:`transpose_bins`).
      vals: ``[C, n_pad]`` f32 packed value rows (:func:`pack_values`).
      row_leaf: ``[n]`` int32 leaf per row; rows whose leaf is not in
        `active` (including bagged-out ``-1``) contribute nothing.
      active: ``[A]`` int32 leaf ids to histogram; ``-1`` entries are
        padding (their output slots contain garbage from bagged-out rows
        and must be dropped by the caller).
      acc: optional carried RAW accumulator ``[F_grid*B, cols]``
        (:func:`hist_raw_layout`; donated — the kernel seeds its output
        buffer from it in place via ``input_output_aliases`` instead of
        zero-initing).  The out-of-core fold operand: this call's rows
        extend the accumulation bitwise, exactly as if they had been
        part of one monolithic call.
      num_features: true F (<= F_pad).
      max_bins: true per-feature bin-count bound; output B = its stride.
      raw: return the RAW ``[F_grid*B, cols]`` kernel accumulator
        (int32 on the quantized path) instead of unpacking — the carry
        for the next block's ``acc``.  Unpack once at the end of the
        fold chain with :func:`unpack_hist_raw`.
      row_limit: the most rows one int32 cell sums exactly (quantized
        modes).  A call over more rows sums them in ``K`` row chunks
        (:func:`row_chunks` over its row tiles): the kernel's output is
        ``[K, F_grid*B, cols]``, each chunk exact in int32 (what
        ``raw=True`` returns then), and the chunks' partials are added
        as limbs (:func:`sum_code_limbs`, scope ``tree.hist.chunk_sum``)
        before the one dequantization; with ``scales`` None the result
        is the limb pair of the ``[A, F, B, C]`` code sums.  ``K == 1``
        is the one-accumulator program.

    Returns:
      ``[A, F, B, 3]`` f32 with B = ``bin_stride(max_bins)``, cells
      ``(sum_grad, sum_hess, count)`` — or the raw accumulator when
      ``raw=True``.

    MXU cost scales with ``round128(C*round8(A))`` — small waves are
    proportionally cheap.  ``A`` is the wave's capacity, not its live
    count: a ``-1`` slot's columns are multiplied like any other, so the
    caller sizes ``active`` by what it can be handed
    (``learner/serial.py`` ``stage_plan``).
    """
    F_pad, n_pad = bins_t.shape
    C = vals.shape[0]
    A = active.shape[0]
    B = bin_stride(max_bins)

    _, A_pad, cols = _col_layout(A, mode)
    seeded = acc is not None
    # the grid: bounded by the per-grid-cell VMEM footprint (the
    # accumulator + the one-hot at the mode's operand size + the bins
    # tile — ADVICE r2: the accumulator alone under-counts by the
    # one-hot's tens of MB on wide low-bin datasets; a seeded call also
    # counts the carried accumulator it streams in)
    T, feat_tile, F_grid = hist_tiling(F_pad, n_pad, B, cols, C, mode,
                                       row_tile, seeded)
    assert n_pad % T == 0, (n_pad, T)
    K, chunk_tiles = (row_chunks(n_pad // T, T, row_limit)
                      if is_quantized(mode) else (1, 0))
    if K == 1:
        chunk_tiles = 0         # the one-accumulator program
    elif seeded:
        raise ValueError(
            f"a seeded histogram call carries one int32 accumulator: "
            f"{n_pad} rows are {K} chunks of it")
    pad_cols = cols - C * A_pad
    bins_t = pad_features(bins_t, F_grid)

    leaf = jnp.full((1, n_pad), -1, jnp.int32)
    leaf = jax.lax.dynamic_update_slice(
        leaf, row_leaf.astype(jnp.int32)[None, :], (0, 0))
    act = jnp.full((A_pad, 1), -2, jnp.int32)
    act = jax.lax.dynamic_update_slice(
        act, active.astype(jnp.int32)[:, None], (0, 0))
    # padded rows carry leaf -1; bagged-out rows carry -1 too.  Use -2 for
    # active padding so neither lands in a real column block; -1 actives
    # (wave padding) DO accumulate bagged-out rows, caller drops them.
    grid = (F_grid // feat_tile, n_pad // T)
    in_specs = [
        pl.BlockSpec((A_pad, 1), lambda f, r: (0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((feat_tile, T), lambda f, r: (f, r),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((C, T), lambda f, r: (0, r),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, T), lambda f, r: (0, r),
                     memory_space=pltpu.VMEM),
    ]
    operands = [act, bins_t, vals, leaf]
    if seeded:
        # the carried accumulator mirrors the OUTPUT's block walk
        # ((f, 0): per-feature-tile, revisited across row tiles) so the
        # rt==0 seed-load reads the matching seed block; aliasing it to
        # the output (input index 4 -> output 0) makes the seed a
        # donated in-place init — no extra HBM buffer, no copy
        in_specs.append(pl.BlockSpec((feat_tile * B, cols),
                                     lambda f, r: (f, 0),
                                     memory_space=pltpu.VMEM))
        operands.append(acc)
    out_block, out_index = (feat_tile * B, cols), lambda f, r: (f, 0)
    out_dims = (F_grid * B, cols)
    if K > 1:
        # a leading axis of row chunks in the accumulator: the output
        # block moves on every `chunk_tiles` row tiles, and each chunk's
        # cells are exact in int32
        out_block, out_dims = (None, *out_block), (K, *out_dims)

        def out_index(f, r):
            return r // chunk_tiles, f, 0
    out = pl.pallas_call(
        functools.partial(_hist_kernel, n_cols=C, B=B, pad_cols=pad_cols,
                          seeded=seeded, chunk_tiles=chunk_tiles),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(out_block, out_index,
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            out_dims, jnp.int32 if is_quantized(mode) else jnp.float32),
        input_output_aliases=({4: 0} if seeded else {}),
        interpret=interpret,
    )(*operands)

    if raw:
        return out
    if K > 1:
        with jax.named_scope("tree.hist.chunk_sum"):
            out = sum_code_limbs(tuple(out[k] for k in range(K)))
    # [F_grid*B, cols] -> [A, F, B, C'] -> combine hi/lo -> [A, F, B, 3]
    return _unpack_hist(out, B, cols, C, A_pad, A, num_features, mode,
                        scales)


def hist_raw_layout(n_pad: int, num_active: int, num_features: int,
                    max_bins: int, mode: str,
                    row_tile: int = DEFAULT_ROW_TILE):
    """``-> ((F_grid*B, cols), dtype)`` of the RAW wide-kernel
    accumulator for this config — the shape a streamed fold carries
    across blocks (``acc`` / ``raw=True`` in :func:`hist_active_pallas`).

    The kernel's own grid for a SEEDED call (``ops/vmem.hist_tiling``),
    so the carry can be allocated before the first call.
    ``num_features`` must equal the
    bins' F_pad (streamed sources transpose with ``feat_tile=None``, so
    F_pad == F); ``n_pad`` is the per-block padded row count — every
    block of a stream uses the same one, which is what keeps the layout
    call-invariant.
    """
    B = bin_stride(max_bins)
    C, A_pad, cols = _col_layout(num_active, mode)
    _, _, F_grid = hist_tiling(num_features, n_pad, B, cols, C, mode,
                               row_tile, seeded=True)
    dtype = jnp.int32 if is_quantized(mode) else jnp.float32
    return (F_grid * B, cols), dtype


def unpack_hist_raw(out: jnp.ndarray, num_active: int, num_features: int,
                    max_bins: int, mode: str,
                    scales: jnp.ndarray | None = None) -> jnp.ndarray:
    """RAW wide-kernel accumulator -> ``[A, F, B, 3]`` f32.  The one-shot
    finalization of a streamed fold chain (dequantize / combine hi-lo
    exactly once, after all blocks have accumulated exactly)."""
    B = bin_stride(max_bins)
    C, A_pad, cols = _col_layout(num_active, mode)
    return _unpack_hist(out, B, cols, C, A_pad, num_active, num_features,
                        mode, scales)


def _unpack_hist(out, B, cols, C, A_pad, A, num_features, mode, scales):
    """``[F_grid*B, cols] -> [A, F, B, 3] f32``: undo the kernel's
    c-major column layout and combine hi/lo (or dequantize) columns.
    ``out`` may be the limb pair of several shards' accumulators
    (:func:`sum_code_limbs`)."""
    def cells(o):
        F_grid = o.shape[0] // B
        o = o.reshape(F_grid, B, cols)[:, :, :C * A_pad]
        o = o.reshape(F_grid, B, C, A_pad)
        return o.transpose(3, 0, 1, 2)[:A, :num_features]    # [A, F, B, C]
    return combine_hist_cols(jax.tree.map(cells, out), mode, scales)


def combine_hist_cols(out, mode, scales):
    """``[..., C]`` raw kernel value columns -> ``[..., 3]`` f32
    ``(sum_grad, sum_hess, count)``: combine hi/lo pairs or dequantize.
    A quantized mode with ``scales`` None leaves the ``[..., C]`` int32
    code sums as they are: the data-parallel learner sums them over the
    shards exactly and dequantizes once after (:func:`dequant_hist`)."""
    if is_quantized(mode):
        return out if scales is None else dequant_hist(out, scales, mode)
    C = out.shape[-1]
    if C == 5:
        g = out[..., 0] + out[..., 1]
        h = out[..., 2] + out[..., 3]
        out = jnp.stack([g, h, out[..., 4]], axis=-1)
    elif C == 4 and mode == "hhilo":
        h = out[..., 1] + out[..., 2]
        out = jnp.stack([out[..., 0], h, out[..., 3]], axis=-1)
    elif C == 4:
        g = out[..., 0] + out[..., 1]
        out = jnp.stack([g, out[..., 2], out[..., 3]], axis=-1)
    return out


# ---------------------------------------------------------------------------
# XLA scatter reference implementation (CPU path + equivalence oracle)
# ---------------------------------------------------------------------------
def hist_active_scatter(bins: jnp.ndarray,
                        grad: jnp.ndarray,
                        hess: jnp.ndarray,
                        row_leaf: jnp.ndarray,
                        active: jnp.ndarray,
                        *,
                        max_bins: int,
                        num_leaf_slots: int) -> jnp.ndarray:
    """Same contract as :func:`hist_active_pallas` (exact f32 scatter),
    from the untransposed ``[n, F]`` integer bins.  The direct analog of
    the reference CPU construction (`dataset.cpp:587-752`) restricted to
    the active leaves."""
    n, F = bins.shape
    A = active.shape[0]
    B = bin_stride(max_bins)
    L = num_leaf_slots
    safe_act = jnp.where(active >= 0, active, L)
    inv = jnp.full((L + 1,), A, jnp.int32).at[safe_act].set(
        jnp.arange(A, dtype=jnp.int32), mode="drop")
    slot = jnp.where(row_leaf >= 0,
                     inv[jnp.clip(row_leaf, 0, L)], A)       # [n]
    idx = (slot[:, None] * (F * B)
           + jnp.arange(F, dtype=jnp.int32)[None, :] * B
           + bins.astype(jnp.int32))                         # [n, F]
    vals = jnp.stack([grad, hess, jnp.ones_like(grad)], -1)  # [n, 3]
    hist = jnp.zeros((A * F * B, 3), jnp.float32)
    hist = hist.at[idx].add(vals[:, None, :].astype(jnp.float32),
                            mode="drop")
    return hist.reshape(A, F, B, 3)


def default_backend() -> str:
    """What "auto" means: "pallas" (the wide MXU kernel in every wave of
    a tree) on TPU, "scatter" elsewhere; ``LGBM_TPU_HIST_BACKEND`` names
    the other (`learner/serial.py` ``resolve_backend`` refuses a name
    that is neither)."""
    forced = os.environ.get("LGBM_TPU_HIST_BACKEND", "")
    if forced and forced != "auto":
        return forced
    return "pallas" if jax.default_backend() == "tpu" else "scatter"


# ---------------------------------------------------------------------------
# Fused route + histogram kernel: one bins stream per wave instead of two
# ---------------------------------------------------------------------------
def _hist_route_kernel(active_ref, bins_ref, vals_ref, leaf2_ref, rtabs_ref,
                       cat_ref, out_ref, leaf2_out_ref, *,
                       n_cols: int, B: int, Bcat: int, pad_cols: int,
                       tab_prec, any_cat: bool = True):
    """Apply the previous wave's pending splits to the leaf vectors, then
    histogram the active leaves — both from ONE VMEM-resident bins tile.
    The route is ``ops/pallas_route.py``'s (its table, selection and
    decision); the split feature's value is read from the same int32
    tile the one-hot is built from, by an int32 masked sublane sum
    (exact: bins < 256), so no f32 copy of the tile is made."""
    from .pallas_route import _route_apply, _route_select
    rt = pl.program_id(0)

    @pl.when(rt == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    bins = bins_ref[:].astype(jnp.int32)                      # [G, T]
    G_pad, T = bins.shape

    # ---- route (previous wave's pending splits) -----------------------
    leaf = leaf2_ref[0:1, :]
    ohL, sel_dt, sp = _route_select(leaf, rtabs_ref, tab_prec)
    iota_g = jax.lax.broadcasted_iota(jnp.int32, (G_pad, T), 0)
    c = jnp.sum(jnp.where(iota_g == sp.group.astype(jnp.int32), bins, 0),
                axis=0, keepdims=True).astype(jnp.float32)    # [1, T]
    _, hl = _route_apply(c, sp, leaf, ohL, sel_dt, leaf2_ref, cat_ref,
                         leaf2_out_ref, B=Bcat, any_cat=any_cat)

    # ---- histogram with the routed in-bag leaves ----------------------
    # rows-on-lanes throughout: mask [A_pad, T] straight off the routed
    # leaf row (no [1,T]->[T,1] relayout), vw [cols, T], lane contraction
    quant = vals_ref.dtype == jnp.int8
    cdt = jnp.int8 if quant else jnp.bfloat16
    oh = _onehot_bins(bins, B, cdt)
    m = active_ref[:] == hl                                   # [A_pad, T]
    vals = vals_ref[:]                                        # [C, T]
    vw = _weighted_cols(m, vals, n_cols, pad_cols, cdt)       # [cols, T]
    out_ref[:] += jax.lax.dot_general(
        oh, vw, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32 if quant else jnp.float32)


def route_lanes(route_leaves: int) -> int:
    """Lanes of the fused kernel's route table for a wave whose rows lie
    in at most ``route_leaves`` leaves."""
    return _round_up(max(route_leaves, 8), LANE)


def fused_config_ok(num_groups: int, max_bins: int, num_leaves: int,
                    mode: str, n_rows: int, row_limit: int, *,
                    slots: int, route_leaves: int,
                    any_cat: bool = False) -> bool:
    """Whether one wave may take the fused route+histogram kernel: the
    whole feature set in one tile at that wave's ``slots`` (the route
    reads the split feature's column, which may live in any tile), with
    the route's residents counted for a table of ``route_leaves``
    leaves carrying ids up to ``num_leaves``, at the 1,024-row tile
    ``hist_tiling`` goes down to; the usual kernel bounds; and, its
    accumulator being one int32 sum, the ``n_rows`` of a quantized call
    one row chunk (:func:`row_chunks`)."""
    if not pallas_config_ok(max_bins, num_leaves, mode):
        return False
    if is_quantized(mode) and n_rows > row_limit:
        return False
    B = bin_stride(max_bins)
    C, _, cols = _col_layout(slots, mode)
    return cell_vmem_bytes(num_groups, B, cols, 1024, C, mode,
                           route_lanes=route_lanes(route_leaves),
                           id_lanes=route_lanes(num_leaves),
                           any_cat=any_cat) <= VMEM_BUDGET_BYTES


@functools.partial(
    jax.jit,
    static_argnames=("num_features", "max_bins", "mode", "row_tile",
                     "interpret", "any_cat", "route_leaves"))
def hist_route_pallas(bins_t, vals, leaf2, active,
                      feature, threshold, default_left, is_categorical,
                      cat_mask, sel, new_id, missing_types, nan_bins,
                      default_bins, feat_group, feat_offset, num_bins_arr,
                      scales=None,
                      *, num_features: int, max_bins: int,
                      mode: str = "hilo", row_tile: int = DEFAULT_ROW_TILE,
                      route_leaves: int,
                      interpret: bool = False, any_cat: bool = True):
    """Fused previous-wave routing + active-leaf histograms.

    -> ``(hist [A, F, B, 3] f32, leaf2_new [2, n_pad] i32)``.  Same
    contracts as :func:`hist_active_pallas` +
    ``ops.pallas_route.route_rows_pallas`` composed (route first).
    ``route_leaves``: the most leaves the rows lie in when this wave's
    route runs (``learner/serial.py`` ``wave_backend_plan``); the table
    and the leaf one-hot are that wide, the split tables' leaves past it
    being neither a row's nor selected.  Its precision follows the ids
    it carries, up to ``L``, not its width.  Requires
    ``fused_config_ok``.
    """
    from .pallas_route import _T_ROWS, _leaf_tables, table_precision
    F_pad, n_pad = bins_t.shape
    C = vals.shape[0]
    A = active.shape[0]
    B = bin_stride(max_bins)

    Lr = route_leaves
    L_pad, id_lanes = route_lanes(Lr), route_lanes(feature.shape[0])
    feature, threshold, default_left, is_categorical, cat_mask, sel, \
        new_id = (x[:Lr] for x in (feature, threshold, default_left,
                                   is_categorical, cat_mask, sel, new_id))
    _, A_pad, cols = _col_layout(A, mode)
    # the fused kernel holds ALL stored columns in one tile, and the
    # route's residents beside them: the row tile of least modelled time
    # at which that cell fits the VMEM budget
    T, _, _ = hist_tiling(F_pad, n_pad, B, cols, C, mode, row_tile,
                          whole=True, route_lanes=L_pad,
                          id_lanes=id_lanes, any_cat=any_cat)
    assert n_pad % T == 0 and leaf2.shape == (2, n_pad)
    pad_cols = cols - C * A_pad
    Bcat = cat_mask.shape[1]

    rtabs = _leaf_tables(feature, threshold, default_left, is_categorical,
                         sel, new_id, missing_types, nan_bins, default_bins,
                         feat_group, feat_offset, num_bins_arr, L_pad)
    cat = jnp.zeros((Bcat, L_pad), jnp.float32)
    cat = cat.at[:, :Lr].set(cat_mask.T.astype(jnp.float32))
    act = jnp.full((A_pad, 1), -2, jnp.int32)
    act = jax.lax.dynamic_update_slice(
        act, active.astype(jnp.int32)[:, None], (0, 0))

    out, leaf2_new = pl.pallas_call(
        functools.partial(_hist_route_kernel, n_cols=C, B=B, Bcat=Bcat,
                          pad_cols=pad_cols, any_cat=any_cat,
                          tab_prec=table_precision(id_lanes, F_pad)),
        grid=(n_pad // T,),
        in_specs=[
            pl.BlockSpec((A_pad, 1), lambda r: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((F_pad, T), lambda r: (0, r),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, T), lambda r: (0, r),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((2, T), lambda r: (0, r),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_T_ROWS, L_pad), lambda r: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((Bcat, L_pad), lambda r: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((F_pad * B, cols), lambda r: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((2, T), lambda r: (0, r),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct(
                (F_pad * B, cols),
                jnp.int32 if is_quantized(mode) else jnp.float32),
            jax.ShapeDtypeStruct((2, n_pad), jnp.int32),
        ),
        interpret=interpret,
    )(act, bins_t, vals, leaf2, rtabs, cat)

    out = _unpack_hist(out, B, cols, C, A_pad, A, num_features, mode,
                       scales)
    return out, leaf2_new
