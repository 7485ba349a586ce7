"""Streaming block trainer — out-of-core training (ROADMAP item 4).

Rows live in the mmap-able binned shard cache (``io/outofcore.py``),
NOT in HBM: per tree, row blocks of ``LGBM_TPU_STREAM_ROWS`` stream
host→device one wave at a time, each block is routed through the
partial tree and its per-wave histograms accumulate into the resident
``[L, F, B, 3]`` state — the histogram trick is what makes GBDT
uniquely streamable (one pass over the data per wave, no resident
rows).  Per-device HBM scales with the block size, never with dataset
rows (memcheck MEM003 models it; the bench ``stream_ingest`` leg's
watermark proves it).  Scores, gradients and hessians are host-
resident and updated per block as the blocks stream.

**Byte-identity contract** (the DET005 seam ``LGBM_TPU_STREAM_ROWS``,
pinned by tests/test_streaming.py): streamed training is
BYTE-IDENTICAL — model text and score digests via ``Booster.digest()``
— to in-memory ``lgb.train`` on the same data, serial AND 2-shard
data-parallel, on BOTH histogram backends.  Three mechanisms:

1. **Carried-accumulator folds.**  On the scatter backend, XLA applies
   same-location scatter-add updates in row order, so folding per-block
   scatters into a carried f32 ``[A, F, B, 3]`` accumulator reproduces
   the monolithic ``hist_active_scatter`` bitwise.  On the
   Pallas kernels the fold carries the RAW kernel accumulator
   instead (``learner.serial.make_hist_fold_fn``): each block's kernel
   call SEEDS its output from the carry via ``input_output_aliases``
   (the ``@pl.when`` zero-init becomes a seed-load), so a chain of
   per-block calls replays the monolithic kernel's adds in the
   monolithic order — exactly int32 on the quantized default modes
   (per-tree global quantization scales are host-derived over every
   block, :func:`_fold_scales`), same-order f32 on the wide float
   modes.  The raw carry is dequantized/unpacked ONCE per wave, by the
   same jitted graph the in-memory kernels fuse in-call.
2. **Canonical chunked root statistics** (``learner/serial.py
   root_stats``): the resident ``_init_state`` derives the root sums
   from fixed ``STREAM_CHUNK``-sized chunk sums reduced by a fixed
   pairwise tree — partition-invariant, so this trainer reassembles
   the identical scalars from per-block chunk sums.  Where the folds
   histogram int8 codes the root sums are the exact int32 sums of the
   same codes (``root_code_sums`` / ``root_stats_q``): block and shard
   results add exactly, and the total is dequantized once.
3. **The fenced block body** (``gbdt._make_block_fn``): the serial
   scan body barriers gradients and the built tree and updates scores
   with the contraction-proof scale-then-gather shape (the PR 11 mesh
   discipline), so this module's standalone per-block programs compile
   to the same last-ulp rounding as the fused in-memory body.

**The upload/compute pipeline** (``LGBM_TPU_STREAM_PIPELINE``, default
on): the wave loop runs a bounded-depth-2 prefetch+staging pipeline —
a single host staging thread reads block k+1 from the ShardStore mmap
and pads it while block k's fold computes on device, and block k+1's
``device_put`` is issued BEFORE block k's fold is awaited, so the
host->device copy rides under kernel time instead of serializing with
it.  Fold order never changes — the pipeline reorders only host
staging work — so ``LGBM_TPU_STREAM_PIPELINE=0`` (the serial escape
hatch) is byte-identical by construction; ``stream.prefetch`` /
``stream.upload`` / ``stream.fold`` spans plus the
``stream.pipeline.overlap_s`` counter prove the overlap instead of
claiming it.  Uploads sit behind the shared retry policy with the
``stream.upload`` fault point: a transient device fault is retried
BEFORE the fold is dispatched, so a retried upload can never tear a
fold.

2-shard data-parallel composes by mirroring the mesh row partition
(``parallel/mesh.py shard_row_ranges``): each shard's blocks fold into
a per-shard accumulator and the shard partials combine in device order
— elementwise adds, exactly what the wave ``psum`` lowers to — so the
streamed model equals the in-memory 2-shard mesh model bitwise.  On the
quantized folds the shards round against one pair of scales (the
largest magnitudes over all rows) and their raw int32 accumulators add
exactly (``sum_code_limbs``) before the one dequantization, as the
mesh's ``psum_codes`` exchange does.

Supported: gbdt boosting, row-wise objectives (regression / binary /
multiclass / xentropy families), ``feature_fraction``, weights, serial
and data-parallel layouts.  Documented descopes (they raise):
bagging/GOSS (the [n]-shaped device mask breaks the memory contract),
DART (host score patching), ranking (row blocks would split queries),
custom ``fobj``, leaf-renewal objectives, valid sets / early stopping.

**Elastic training** (:func:`train_elastic`) rides this trainer because
ALL of its cross-shard communication is explicit host-side combination
of per-shard partials — unlike the in-memory mesh path, whose psum
lives inside an XLA dispatch that cannot be cancelled when a peer
dies.  The protocol fixes a shard count ``S`` for the run's lifetime
(``LGBM_TPU_ELASTIC_SHARDS``; default = the initial world size); each
rank owns shards ``s % world == rank``, folds their blocks exactly as
the local ``S``-shard path would, and the per-shard partials are
allgathered (``parallel/elastic.py``) and combined in SHARD order —
the identical elementwise adds regardless of which rank computed which
shard.  Training is therefore a pure function of ``(data, config, S)``:
any world size, any membership history, and any recovery from a
committed barrier snapshot produce byte-identical models (the chaos
gate ``tools/chaos.py`` proves it with real SIGKILLs).  On a
``RankLostError`` / ``GenerationChanged`` survivors re-rendezvous,
re-own shards at the new world size, and resume from the last
committed barrier (``boosting/snapshot.py`` barrier functions).
"""
from __future__ import annotations

import hashlib
import os
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..io.dataset import BinnedDataset, Metadata
from ..io.device import DeviceData, feature_meta_np
from ..learner.serial import (STREAM_CHUNK, BuiltTree, _WaveState,
                              _apply_wave, _empty_best, apply_hist_wave,
                              make_hist_fold_fn, reduce_chunk_sums,
                              root_chunk_sums, root_code_sums,
                              root_stats_q, scan_grid, stage_plan)
from ..obs import counter_add, event, span as obs_span
from ..objective.objectives import create_objective
from ..ops.pallas_histogram import (bin_stride, pack_values_q,
                                    sum_code_limbs)
from ..ops.vmem import col_layout
from ..ops.pallas_route import route_rows_xla
from ..ops.split import leaf_output as _leaf_output
from ..utils.log import log_info, log_warning
from .gbdt import GBDT, _device_feature_mask, growth_params_from_config

# past this row count the objective's device-label init is skipped (it
# would pin an [n] f32 in HBM) and boost-from-average binds the host
# label vector directly
_RESIDENT_SIDE_ROWS = 1 << 27


def stream_rows() -> int:
    """The configured streaming block size (``LGBM_TPU_STREAM_ROWS``),
    rounded UP to a multiple of ``STREAM_CHUNK`` — block boundaries
    must land on root-statistic chunk boundaries or the partition-
    invariant reduction contract breaks.  0 = streaming off."""
    r = int(os.environ.get("LGBM_TPU_STREAM_ROWS", "0"))
    if r <= 0:
        return 0
    return -(-r // STREAM_CHUNK) * STREAM_CHUNK


_SCALE_CHUNK = 1 << 24


def _fold_scales(grad: np.ndarray, hess: np.ndarray) -> np.ndarray:
    """The largest magnitudes ``[|g|max, |h|max]`` of a row range,
    clamped to 1e-30, f32: the largest over all ranges is a tree's
    quantization scale pair for the seeded kernel folds.

    Every block must quantize against ONE scale pair or the
    int8 codes (and therefore the int32 accumulator) stop being a pure
    function of the data partition.  The in-memory kernels derive the
    same scalars on device as ``max(|x|)`` over the shard's rows —
    f32 absmax is exact and order-independent (no rounding, commutative
    idempotent max), so this chunked host reduction lands the identical
    bit pattern without paginating the full vector through HBM.
    SANCTIONED REASSOCIATION CONTEXT (tools/numcheck): chunking reorders
    only ``max``, never an add."""
    out = np.empty(2, np.float32)
    for i, arr in enumerate((grad, hess)):
        m = np.float32(0.0)
        for lo in range(0, arr.shape[0], _SCALE_CHUNK):
            c = np.max(np.abs(arr[lo:lo + _SCALE_CHUNK]))
            m = np.maximum(m, np.float32(c))
        out[i] = np.maximum(m, np.float32(1e-30))
    return out


class _Source:
    """Uniform block reader over a ShardStore or a resident
    BinnedDataset (the resident form exists so source independence —
    mmap cache vs RAM — is testable, and so the parity harness can
    stream the exact arrays the in-memory path trains on)."""

    def __init__(self, obj, config: Config):
        from ..io.outofcore import ShardStore
        self._store = obj if isinstance(obj, ShardStore) else None
        self._ds = obj if isinstance(obj, BinnedDataset) else None
        if self._store is None and self._ds is None:
            raise TypeError(f"unsupported stream source {type(obj)!r}")
        if self._ds is not None and self._ds.bundle is not None \
                and self._ds.bundle.is_bundled:
            raise ValueError("streaming does not support EFB-bundled "
                             "resident sources (the shard store ingests "
                             "unbundled)")
        self.config = config

    @property
    def n(self) -> int:
        return (self._store.n if self._store is not None
                else self._ds.num_data)

    @property
    def num_features(self) -> int:
        return (self._store.num_features if self._store is not None
                else self._ds.num_features)

    def read_rows(self, start: int, stop: int):
        if self._store is not None:
            return self._store.read_rows(start, stop)
        md = self._ds.metadata
        return (self._ds.bins[start:stop],
                md.label[start:stop] if md.label is not None else
                np.zeros(stop - start, np.float32),
                md.weight[start:stop] if md.weight is not None else None)

    def labels(self) -> np.ndarray:
        return (self._store.labels_array() if self._store is not None
                else self._ds.metadata.label)

    def weights(self) -> Optional[np.ndarray]:
        return (self._store.weights_array() if self._store is not None
                else self._ds.metadata.weight)

    def query_boundaries(self):
        return (None if self._store is not None
                else self._ds.metadata.query_boundaries)

    def light_dataset(self) -> BinnedDataset:
        """A bins-free BinnedDataset shell carrying mappers/feature
        metadata — enough for model IO (``GBDT._to_host_tree`` reads
        mappers and ``used_features``, never the rows)."""
        if self._ds is not None:
            return self._ds
        st = self._store
        ds = BinnedDataset()
        ds.config = self.config
        ds.num_total_features = st.num_total_features
        ds.feature_names = list(st.feature_names)
        ds.mappers = st.mappers
        ds.used_features = list(st.used_features)
        ds.feature_info = st.feature_info
        ds.bins = np.zeros((0, st.num_features), st.dtype)
        return ds


def _check_streamable(config: Config, objective, src: _Source) -> None:
    bad = None
    if config.boosting_type not in ("gbdt",):
        bad = f"boosting={config.boosting_type} (host score patching)"
    elif config.bagging_freq > 0 and config.bagging_fraction < 1.0:
        bad = "bagging (the [n]-shaped device mask breaks the " \
              "block-memory contract)"
    elif config.tree_learner not in ("serial", "data"):
        bad = f"tree_learner={config.tree_learner} (streamed v1 " \
              "composes with data-parallel row sharding only)"
    elif objective is None:
        bad = "objective=none / custom fobj"
    elif objective.need_renew_tree_output:
        bad = f"objective={objective.name} (leaf renewal rewrites " \
              "outputs from per-row scores)"
    elif "rank" in objective.name or src.query_boundaries() is not None:
        bad = "ranking objectives (row blocks would split queries)"
    if bad:
        raise ValueError(
            f"streaming training does not support {bad}; train "
            "in-memory, or see README \"Out-of-core training\" for the "
            "supported envelope")


def _num_shards(config: Config) -> int:
    if config.tree_learner != "data":
        return 1
    shape = tuple(config.mesh_shape) or (len(jax.devices()),)
    return max(1, int(shape[0]))


class StreamTrainer:
    """Host-driven streamed boosting over a block source.

    Produces a regular :class:`~lightgbm_tpu.boosting.gbdt.GBDT` (model
    IO, ``digest()``, prediction through the mapper shell) whose train
    scores are the streamed host-resident score state."""

    def __init__(self, config: Config, source, block_rows: int = 0,
                 num_shards: int = 0, elastic=None):
        self.config = config
        self.src = _Source(source, config)
        self.R = block_rows or stream_rows() or STREAM_CHUNK
        self.R = -(-self.R // STREAM_CHUNK) * STREAM_CHUNK
        # the protocol shard count: explicit > elastic run > mesh shape.
        # Under elastic training S is FIXED for the run's lifetime (it
        # is the identity domain — see the module docstring) while the
        # world size is not.
        self.elastic = elastic
        self.S = (int(num_shards)
                  or (int(elastic.num_shards) if elastic is not None else 0)
                  or _num_shards(config))
        self.owned = (elastic.owned_shards() if elastic is not None
                      else tuple(range(self.S)))
        self._owned_set = frozenset(self.owned)
        n = self.src.n
        if n <= 0:
            raise ValueError("empty stream source")
        self.n = n
        from ..parallel.mesh import shard_row_ranges
        self.ranges = shard_row_ranges(n, self.S)
        self.per = self.ranges[0][1] - self.ranges[0][0]

        booster = GBDT(config, None)
        booster.train_set = self.src.light_dataset()
        booster.growth = growth_params_from_config(config)
        booster.feature_names = booster.train_set.feature_names
        booster.max_feature_idx = booster.train_set.num_total_features - 1
        self.booster = booster
        self.growth = booster.growth

        self.objective = create_objective(config)
        _check_streamable(config, self.objective, self.src)
        self.K = self.objective.num_model_per_iteration
        booster.num_tree_per_iteration = self.K
        # the saved model text must carry the objective header (predict
        # conversion + continued training on reload)
        booster.objective = self.objective

        light = self.src.light_dataset()
        meta = feature_meta_np(light)
        arrays = {k: jnp.asarray(meta[k]) for k in (
            "bin_offsets", "num_bins", "default_bins", "missing_types",
            "is_categorical", "nan_bins", "feat_group", "feat_offset")}
        self._dtype = light.bins.dtype
        # template DeviceData: per-block `bins` swap in, metadata fixed
        self.dd_meta = DeviceData(
            bins=jnp.zeros((self.R, self.src.num_features), self._dtype),
            total_bins=meta["total_bins"], max_bins=meta["max_bins"],
            has_categorical=meta["has_categorical"],
            max_group_bins=meta["max_group_bins"],
            is_bundled=meta["is_bundled"],
            has_missing=meta["has_missing"], **arrays)
        L = self.growth.num_leaves
        self.L = L
        _, self.A_tail = stage_plan(L, self.growth.wave_size)
        self.Bh = bin_stride(self.dd_meta.group_max_bins)
        from ..learner.serial import default_hist_mode, effective_hist_mode
        # the hist mode keys on the GLOBAL row count, not the block
        # size: the fold carries ONE int32 accumulator over the stream,
        # bound on the total rows folded through it (the resident
        # learner sums such rows in chunks; this fold does not yet:
        # ROADMAP R2)
        self.hist_mode = effective_hist_mode(
            config.hist_mode or default_hist_mode(), n, chunked=False)
        # kernel-exact folds: on the Pallas backend every block
        # call SEEDS the kernel accumulator from the carried raw grid
        # (learner.serial.make_hist_fold_fn), so the streamed chain IS
        # the monolithic kernel bitwise; None -> the exact scatter fold
        self._fold = make_hist_fold_fn(
            self.dd_meta, L, self.A_tail, self.R,
            hist_mode=self.hist_mode, num_data=n)
        self.backend = "pallas" if self._fold else "scatter"
        self._kernel_hist = self._fold is not None
        # bounded-depth-2 upload/compute pipeline (module docstring);
        # "0"/"off" is the byte-identical serial escape hatch
        self._pipeline_on = os.environ.get(
            "LGBM_TPU_STREAM_PIPELINE", "1").strip().lower() not in (
                "0", "off", "false")
        self._stager = None

        # host score state [n, K] f32 — the training state that would
        # not fit in HBM; every update happens on device per block and
        # lands back here bitwise
        self.scores = np.zeros((n, self.K), np.float32)
        self._init_scores()
        self._jits = {}
        # open MTTR episode handed over by train_elastic after a
        # recovery: train() closes it (phase `retrain`) once boosting
        # re-reaches the iteration the failure interrupted
        self.recovery_episode = None

    # -- init ------------------------------------------------------------
    def _init_scores(self) -> None:
        obj = self.objective
        if not self.config.boost_from_average:
            return
        y = np.ascontiguousarray(self.src.labels(), np.float32)
        w = self.src.weights()
        if self.n <= _RESIDENT_SIDE_ROWS:
            # the in-memory init path verbatim (device label freed right
            # after): bitwise-identical init score at fittable sizes
            md = Metadata()
            md.set_field("label", y)
            if w is not None:
                md.set_field("weight", np.ascontiguousarray(w))
            obj.init(md, self.n)
            obj.label = None
            obj.weight = None
        else:
            if getattr(self.config, "reg_sqrt", False):
                raise ValueError("reg_sqrt streaming past "
                                 f"{_RESIDENT_SIDE_ROWS} rows is not "
                                 "supported")
            obj._label_np = y
            obj._weight_np = (np.ascontiguousarray(w, np.float32)
                              if w is not None else None)
            obj._check_label()
        v = obj.boost_from_score()
        if v != 0.0:
            self.booster.init_score_value = v
            self.scores[:] = np.float32(v)
            log_info(f"boost from average: init score = {v:.6f}")

    # -- jitted per-step programs ---------------------------------------
    def _jit(self, name, fn, **kw):
        if name not in self._jits:
            self._jits[name] = jax.jit(fn, **kw)
        return self._jits[name]

    def _grad_fn(self):
        obj = self.objective
        K = self.K

        def grads(scores_b, label_b, weight_b):
            # bind the block's label/weight for the trace; row-wise
            # objectives make the block slice exact vs the full call
            obj.label = label_b
            obj.weight = weight_b
            try:
                if K == 1:
                    g, h = obj.get_gradients(scores_b[:, 0])
                    return g[:, None], h[:, None]
                return obj.get_gradients(scores_b)
            finally:
                obj.label = None
                obj.weight = None
        return self._jit("grads", grads)

    def _hist_into(self, acc, bins, grad, hess, hist_leaf, active):
        """Scatter one block's rows INTO the carried accumulator —
        the ``hist_active_scatter`` index arithmetic seeded with the
        fold carry, so the per-location add order equals the monolithic
        scatter's row order (the exactness contract)."""
        A = active.shape[0]
        F = bins.shape[1]
        B = self.Bh
        L = self.L
        safe_act = jnp.where(active >= 0, active, L)
        inv = jnp.full((L + 1,), A, jnp.int32).at[safe_act].set(
            jnp.arange(A, dtype=jnp.int32), mode="drop")
        slot = jnp.where(hist_leaf >= 0,
                         inv[jnp.clip(hist_leaf, 0, L)], A)
        idx = (slot[:, None] * (F * B)
               + jnp.arange(F, dtype=jnp.int32)[None, :] * B
               + bins.astype(jnp.int32))
        vals = jnp.stack([grad, hess, jnp.ones_like(grad)], -1)
        flat = acc.reshape(A * F * B, 3).at[idx].add(
            vals[:, None, :].astype(jnp.float32), mode="drop")
        return flat.reshape(A, F, B, 3)

    def _route(self, data: DeviceData, leaf2, best, pend_sel, pend_new):
        def do_route(l2):
            return route_rows_xla(
                data.bins, l2, best.feature, best.threshold,
                best.default_left, best.is_categorical, best.cat_mask,
                pend_sel, pend_new, data.missing_types, data.nan_bins,
                data.default_bins, data.feat_group, data.feat_offset,
                data.num_bins)
        return jax.lax.cond(jnp.any(pend_sel), do_route,
                            lambda l2: l2, leaf2)

    def _wave_block_fn(self):
        """(bins, leaf2, best, pend_sel, pend_new, acc, grad, hess,
        act_small, scales) -> (leaf2', acc'): route the pending splits
        over this block, then fold its active-leaf histograms into the
        carry — a SEEDED kernel call on the Pallas backend
        (raw carry; ``scales`` is the shard's fixed quantization pair),
        the row-order scatter on the exact f32 path (``scales`` None)."""
        dd = self.dd_meta
        fold = self._fold

        def wave_block(bins, leaf2, best, pend_sel, pend_new, acc,
                       grad, hess, act_small, scales):
            data = dd._replace(bins=bins)
            leaf2 = self._route(data, leaf2, best, pend_sel, pend_new)
            if fold is not None:
                acc = fold.fold(bins, grad, hess, leaf2[1], act_small,
                                acc, scales)
            else:
                acc = self._hist_into(acc, data.bins, grad, hess,
                                      leaf2[1], act_small)
            return leaf2, acc
        return self._jit("wave_block", wave_block)

    def _final_route_fn(self):
        dd = self.dd_meta

        def final_route(bins, leaf2, best, pend_sel, pend_new):
            return self._route(dd._replace(bins=bins), leaf2, best,
                               pend_sel, pend_new)
        return self._jit("final_route", final_route)

    def _init_state_fn(self):
        """Chunk-sum-fed analog of ``learner.serial._init_state``: the
        root statistics arrive as the assembled ``[3, m]`` chunk-sum
        vector (folded over blocks on host) and reduce through the
        same fixed pairwise tree the resident path uses."""
        growth = self.growth
        L = self.L
        dd = self.dd_meta
        A0 = self.A_tail
        Bh = self.Bh
        B = bin_stride(dd.max_bins)

        def init(cs):
            sum_g, sum_h, cnt = reduce_chunk_sums(cs)
            root_out = _leaf_output(sum_g, sum_h, growth.split.lambda_l1,
                                    growth.split.lambda_l2)
            Lm = max(L - 1, 1)
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
                row_leaf=jnp.zeros(0, jnp.int32),
                row_value=jnp.zeros(0, jnp.float32))
            return _WaveState(
                leaf2=jnp.zeros((2, 1), jnp.int32),   # lives per block
                nl=jnp.asarray(1, jnp.int32), done=jnp.asarray(False),
                leaf_sum_grad=jnp.zeros(L).at[0].set(sum_g),
                leaf_sum_hess=jnp.zeros(L).at[0].set(sum_h),
                leaf_count=jnp.zeros(L).at[0].set(cnt),
                leaf_depth=jnp.zeros(L, jnp.int32),
                leaf_value=jnp.zeros(L, jnp.float32).at[0].set(root_out),
                leaf_parent=jnp.full(L, -1, jnp.int32),
                leaf_is_left=jnp.zeros(L, bool),
                hist_state=jnp.zeros((L, dd.num_groups, Bh, 3),
                                     jnp.float32),
                best=_empty_best(L, B),
                pend_sel=jnp.zeros(L, bool),
                pend_new=jnp.zeros(L, jnp.int32),
                act_small=jnp.full(A0, -1, jnp.int32).at[0].set(0),
                act_parent=jnp.full(A0, -1, jnp.int32),
                act_sibling=jnp.full(A0, -1, jnp.int32),
                tree=tree)
        return self._jit("init_state", init)

    def _wave_scan_fn(self):
        """(state, new_h, fmask) -> (hist_state, ids, res): sibling
        subtraction + split rescan on the folded accumulator — the
        same program grouping as the phase driver's ``scan_jit``
        (``rescan_changed``), which is pinned bitwise against the
        fused build."""
        dd = self.dd_meta
        growth = self.growth

        def wave_scan(s, new_h, fmask):
            L = s.hist_state.shape[0]
            hist_state, ids, grid = apply_hist_wave(
                s.hist_state, new_h, s.act_small, s.act_parent,
                s.act_sibling, L)
            return scan_grid(dd, growth, fmask, hist_state, ids, grid,
                             s.leaf_sum_grad, s.leaf_sum_hess,
                             s.leaf_count)
        return self._jit("wave_scan", wave_scan)

    def _wave_apply_fn(self):
        """Wave bookkeeping (``_apply_wave``) as its own program —
        the phase driver's ``update_jit`` grouping."""
        growth = self.growth
        A_tail = self.A_tail
        wave_cap = (growth.wave_size if growth.wave_size > 0
                    else growth.num_leaves)

        def wave_apply(s, hist_state, ids, res):
            return _apply_wave(s, s.leaf2, hist_state, ids, res,
                               A_tail, growth, wave_cap)
        return self._jit("wave_apply", wave_apply)

    def _root_cs_fn(self):
        def root_cs(grad, hess, mask):
            return root_chunk_sums(grad, hess, mask)
        return self._jit("root_cs", root_cs)

    def _root_codes_fn(self):
        """Quantized folds: one block's in-bag int8 code sums ``[C]
        int32`` under the shard's scales.  Block results add exactly, so
        a shard's total is the resident ``_init_state``'s
        (``learner.serial.root_code_sums``) whatever the block size."""
        mode = self._fold.hist_mode

        def root_codes(grad, hess, mask, scales):
            vals, _ = pack_values_q(grad, hess, mode, scales=scales)
            return root_code_sums(vals, mask)
        return self._jit("root_codes", root_codes)

    def _root_dequant_fn(self):
        mode = self._fold.hist_mode

        def root_dequant(code_sums, scales):
            return jnp.stack(root_stats_q(code_sums, scales, mode))
        return self._jit("root_dequant", root_dequant)

    def _score_update_fn(self):
        def update(scores_b, leaf_value, nl, row_leaf, lr, k):
            # the fenced body's update shape: stump-masked leaf values,
            # scale-then-gather — contraction-proof, so this standalone
            # program rounds like the in-memory fused body
            lv = jnp.where(nl > 1, leaf_value, jnp.zeros_like(leaf_value))
            lv_s = lr * lv
            return scores_b.at[:, k].add(lv_s[row_leaf])
        return self._jit("score_update", update, static_argnames=("k",))

    def _combine_codes_fn(self, nparts: int):
        """Quantized folds: the shards' raw int32 accumulators (or root
        code sums) added exactly, as the limb pair the one unpack /
        dequantization takes — what the mesh's ``psum_codes`` hands the
        in-memory data-parallel learner."""
        return self._jit(f"combine_codes{nparts}", sum_code_limbs)

    def _combine_fn(self, nparts: int):
        def combine(parts):
            # shard partials combine in device order — the elementwise
            # adds the wave psum lowers to on a D-shard mesh
            out = parts[0]
            for p in parts[1:]:
                out = out + p
            return out
        return self._jit(f"combine{nparts}", combine)

    # -- block geometry ---------------------------------------------------
    def _blocks(self) -> List[Tuple[int, int, int, int]]:
        """-> [(shard, start, stop, valid_rows)]: blocks subdivide each
        shard's row range (never straddling a shard boundary; padded to
        the uniform R on upload so one compiled program serves all)."""
        out = []
        for s, (lo, hi) in enumerate(self.ranges):
            hi = min(hi, self.n)
            pos = lo
            while pos < hi:
                stop = min(pos + self.R, hi)
                out.append((s, pos, stop, stop - pos))
                pos = stop
        return out

    def _my_blocks(self) -> List[Tuple[int, int, int, int]]:
        """This rank's blocks: under elastic training only the owned
        shards' blocks are read, folded and score-updated here — every
        shard has exactly one owner per generation (``s % world``), so
        the union over ranks is the full block list."""
        blocks = self._blocks()
        if self.elastic is None:
            return blocks
        return [b for b in blocks if b[0] in self._owned_set]

    def _pad_block(self, arr: Optional[np.ndarray], m: int,
                   fill=0) -> Optional[np.ndarray]:
        if arr is None:
            return None
        if m == self.R:
            return np.ascontiguousarray(arr)
        pad = np.full((self.R - m,) + arr.shape[1:], fill, arr.dtype)
        return np.concatenate([np.ascontiguousarray(arr), pad])

    # -- training ---------------------------------------------------------
    def train(self, num_iterations: Optional[int] = None) -> GBDT:
        iters = num_iterations or self.config.num_iterations
        # a restored barrier leaves booster.iter mid-run; continuing
        # from it keeps the per-iteration seeds (feature_fraction keys
        # on the TRUE iteration index) on the uninterrupted schedule
        start = self.booster.iter
        try:
            with obs_span("stream.train", rows=self.n, block=self.R,
                          shards=self.S):
                self._finish_recovery()
                for it in range(start, iters):
                    stopped = self._train_one_iter(it)
                    self._finish_recovery()
                    self._window_contracts(it + 1)
                    if stopped:
                        break
                    if self.elastic is not None:
                        # progress rides the heartbeats: operators (and
                        # the chaos launcher's kill scheduler) see it
                        # in info()
                        self.elastic.client.set_status(iteration=it + 1)
                        self._maybe_barrier(it + 1)
        finally:
            self._close_stager()
        ep = self.recovery_episode
        if ep is not None:
            # early stop before the failure iteration came back around:
            # close the episode at the point training actually ended
            self.recovery_episode = None
            ep.finish(iteration=int(self.booster.iter), truncated=True)
        if self.elastic is not None and self.elastic.world > 1:
            self._sync_scores()
        self.booster.scores = self.scores     # host state IS the digest
        self.booster.trim_trailing_stumps()
        return self.booster

    def _window_contracts(self, it: int) -> None:
        """Window-boundary sampling for the reproducibility contracts
        (``LGBM_TPU_DETERMINISM=1`` digest ledger, ``LGBM_TPU_NUM_
        CONTRACT=1`` ulp ledger) — the streamed analog of the in-memory
        trainer's window hook, over the SAME host score state the
        digest law is defined on.  Zero cost when neither contract is
        armed; skipped mid-run under elastic world > 1 where non-owned
        blocks hold stale scores until the final ``_sync_scores``."""
        from ..obs import determinism as _det
        from ..obs import num_contract as _num
        if not (_det.enabled() or _num.enabled()):
            return
        if self.elastic is not None and self.elastic.world > 1:
            return
        self.booster.scores = self.scores     # host state IS the digest
        if _det.enabled():
            _det.window_digest(self.booster, int(it))
        if _num.enabled():
            _num.window_check(self.scores, it=int(it))

    def _finish_recovery(self) -> None:
        """Close the open recovery episode once boosting has re-reached
        the iteration the failure interrupted — `retrain` ends at full
        recovery, not at re-rendezvous."""
        ep = self.recovery_episode
        if ep is not None and self.booster.iter >= ep.target_iter:
            self.recovery_episode = None
            ep.finish(iteration=int(self.booster.iter))

    def _train_one_iter(self, it: int) -> bool:
        c = self.config
        K = self.K
        grad_fn = self._grad_fn()
        blocks = self._my_blocks()
        n = self.n
        # gradients per block, stored host-side for the tree's waves
        G = np.empty((n, K), np.float32)
        H = np.empty((n, K), np.float32)
        with obs_span("stream.gradients", it=it):
            for _, start, stop, m in blocks:
                _, label, weight = self.src.read_rows(start, stop)
                sc = self._pad_block(self.scores[start:stop], m)
                lb = self._pad_block(
                    np.asarray(label, np.float32), m)
                wb = self._pad_block(
                    np.asarray(weight, np.float32) if weight is not None
                    else None, m)
                g, h = grad_fn(jnp.asarray(sc), jnp.asarray(lb),
                               jnp.asarray(wb) if wb is not None else None)
                G[start:stop] = np.asarray(g)[:m]
                H[start:stop] = np.asarray(h)[:m]

        F = self.src.num_features
        ff_on = c.feature_fraction < 1.0
        kf = max(1, int(c.feature_fraction * F))
        stumps = 0
        for k in range(K):
            # None (not all-ones) when feature_fraction is off — the
            # resident build traces the no-mask program shape
            fmask = (_device_feature_mask(c.feature_fraction_seed,
                                          it * K + k, F, kf)
                     if ff_on else None)
            nl = self._build_streamed_tree(it, k, G[:, k], H[:, k], fmask)
            if nl <= 1:
                stumps += 1
        self.booster.iter += 1
        if stumps == K:
            # mirror the in-memory stop: drop the all-stump iteration
            self.booster._pending = self.booster._pending[:-K]
            self.booster.iter -= 1
            log_warning("stopped streamed training: no more leaves meet "
                        f"the split requirements (iteration {it + 1})")
            return True
        return False

    # -- the upload/compute pipeline --------------------------------------
    def _ensure_stager(self):
        """The single host staging thread.  Depth is bounded at 2 by
        construction: at most one block is staged ahead of the block
        computing, so device residency is one extra block's uploads —
        the footprint model (tools/memcheck shapes.json) charges it."""
        if self._stager is None:
            from concurrent.futures import ThreadPoolExecutor
            self._stager = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="stream-stage")
        return self._stager

    def _close_stager(self) -> None:
        if self._stager is not None:
            self._stager.shutdown(wait=True)
            self._stager = None

    def _stage_block(self, start: int, stop: int, m: int,
                     grad: np.ndarray, hess: np.ndarray):
        """Host staging of one block (ShardStore mmap read + pad): the
        part of a block's turnaround that the pipeline moves onto the
        prefetch thread while the previous block's fold computes."""
        with obs_span("stream.prefetch", rows=m):
            bins, _, _ = self.src.read_rows(start, stop)
            return (self._pad_block(np.asarray(bins), m),
                    self._pad_block(grad[start:stop], m),
                    self._pad_block(hess[start:stop], m))

    def _upload_block(self, staged):
        """Device upload of a staged block behind the shared retry
        policy (``stream.upload`` fault point): a transient device
        fault retries the whole put BEFORE any fold is dispatched
        against these arrays, so a retried upload can never tear a
        fold."""
        from ..utils.faults import fault_point
        from ..utils.retry import retry_call
        bins_h, gb, hb = staged

        def put():
            fault_point("stream.upload")
            return (jnp.asarray(bins_h), jnp.asarray(gb),
                    jnp.asarray(hb))
        with obs_span("stream.upload", rows=int(bins_h.shape[0])):
            return retry_call(put, what="stream.upload")

    def _build_streamed_tree(self, it: int, k: int, grad: np.ndarray,
                             hess: np.ndarray, fmask) -> int:
        L = self.L
        blocks = self._my_blocks()
        wave_block = self._wave_block_fn()
        wave_scan = self._wave_scan_fn()
        wave_apply = self._wave_apply_fn()
        root_cs = self._root_cs_fn()
        combine = self._combine_fn(self.S)
        init_state = self._init_state_fn()
        update = self._score_update_fn()
        A = self.A_tail

        # per-tree quantization scales for the kernel folds — ONE pair
        # for every block, wave and shard: the largest magnitudes over
        # all rows (bitwise the in-memory learners' absmax; the mesh
        # takes the same pair by a pmax over its shards, an empty shard
        # range clamps to 1e-30 on both sides).  None on the float
        # modes and the scatter path.
        fold = self._fold
        quantized = fold is not None and fold.quantized
        exchange = (self.elastic is not None and self.elastic.world > 1)
        combine_codes = self._combine_codes_fn(self.S)
        scales = None
        if quantized:
            own = {}
            for s in self.owned:
                lo, hi = self.ranges[s]
                hi = min(hi, self.n)
                own[str(s)] = _fold_scales(grad[lo:hi], hess[lo:hi])
            if exchange:
                own = self._exchange_arrays(own, site="elastic.scales")
            scales = jnp.asarray(np.max(list(own.values()), axis=0))

        # leaf2 carries on host between waves (the streaming traffic);
        # root statistics fold per shard and shard scalars combine in
        # device order.  Float histograms: f32 chunk sums reduced through
        # the fixed pairwise tree.  Quantized folds: exact int32 sums of
        # the int8 codes the kernels histogram, dequantized once per
        # shard — the totals a leaf's histogram sums are consistent with
        root_codes = self._root_codes_fn() if quantized else None
        leaf2_host: List[np.ndarray] = []
        shard_cs = [[] for _ in range(self.S)]
        for (s, start, stop, m) in blocks:
            mask = np.zeros(self.R, bool)
            mask[:m] = True
            gb = jnp.asarray(self._pad_block(grad[start:stop], m))
            hb = jnp.asarray(self._pad_block(hess[start:stop], m))
            cs = (root_codes(gb, hb, jnp.asarray(mask), scales)
                  if quantized else root_cs(gb, hb, jnp.asarray(mask)))
            shard_cs[s].append(np.asarray(cs))
            l2 = np.full((2, self.R), -1, np.int32)
            l2[0, :] = 0
            l2[1, :m] = 0
            leaf2_host.append(l2)

        def shard_part(s: int, m_chunks: int):
            """Shard ``s``'s root ``[g, h, count]`` as a ``[3]`` f32
            (quantized folds: its ``[C]`` int32 code sums, for
            ``root_total`` to add exactly and dequantize once)."""
            if quantized:
                return jnp.asarray(
                    np.sum(shard_cs[s], axis=0, dtype=np.int32)
                    if shard_cs[s]           # else: a shard of mesh padding
                    else np.zeros(col_layout(1, fold.hist_mode)[0],
                                  np.int32))
            cs = (np.concatenate(shard_cs[s], axis=1) if shard_cs[s]
                  else np.zeros((3, 0), np.float32))
            if cs.shape[1] < m_chunks:       # trailing mesh-pad chunks
                cs = np.concatenate(
                    [cs, np.zeros((3, m_chunks - cs.shape[1]),
                                  np.float32)], axis=1)
            return jnp.stack(reduce_chunk_sums(
                jnp.asarray(cs[:, :m_chunks])))

        def root_total(parts):
            """The shards' root parts as one ``[3]`` f32."""
            if quantized:
                return self._root_dequant_fn()(
                    parts[0] if self.S == 1 else combine_codes(parts),
                    scales)
            return parts[0] if self.S == 1 else combine(parts)

        # in-memory chunk grids: serial = ceil(n/C); data-parallel =
        # ceil(per/C) per shard (mesh padding rows are zero chunks)
        if exchange:
            # per-shard scalars reduce locally (the same reduction any
            # owner would run), travel as [3] f32 arrays, and combine in
            # SHARD order — bitwise what the single-process S-shard
            # branch below computes
            m_chunks = -(-self.per // STREAM_CHUNK)
            payload = {str(s): np.asarray(shard_part(s, m_chunks))
                       for s in self.owned}
            merged = self._exchange_arrays(payload,
                                           site="elastic.root_stats")
            tot = root_total([jnp.asarray(merged[s])
                              for s in range(self.S)])
            state = init_state(tot[:, None])   # [3, 1]: identity reduce
        elif self.S == 1 and quantized:
            state = init_state(root_total([shard_part(0, 0)])[:, None])
        elif self.S == 1:
            m_chunks = -(-self.n // STREAM_CHUNK)
            cs_all = np.concatenate(shard_cs[0], axis=1)[:, :m_chunks]
            state = init_state(jnp.asarray(cs_all))
        else:
            m_chunks = -(-self.per // STREAM_CHUNK)
            tot = root_total([shard_part(s, m_chunks)
                              for s in range(self.S)])
            state = init_state(tot[:, None])   # [3, 1]: identity reduce

        pipelined = self._pipeline_on and len(blocks) > 1
        stager = self._ensure_stager() if pipelined else None

        def _staged(idx: int):
            _, b_start, b_stop, b_m = blocks[idx]
            return self._stage_block(b_start, b_stop, b_m, grad, hess)

        while True:
            if bool(state.done) or int(state.nl) >= L:
                break
            # the wave carry: RAW kernel accumulators on the fold
            # backends (seeded per block, unpacked once below), the f32
            # grid on the exact scatter path
            accs = [fold.init_acc() if fold is not None else
                    jnp.zeros((A, self.dd_meta.num_groups, self.Bh, 3),
                              jnp.float32) for _ in range(self.S)]
            dev = self._upload_block(_staged(0)) if blocks else None
            for bi, (s, start, stop, m) in enumerate(blocks):
                bins_d, gd, hd = dev
                dev = None
                # depth-2 pipeline: hand block k+1 to the staging
                # thread before dispatching block k's fold
                fut = (stager.submit(_staged, bi + 1)
                       if pipelined and bi + 1 < len(blocks) else None)
                with obs_span("stream.fold", block=bi):
                    l2, acc = wave_block(
                        bins_d, jnp.asarray(leaf2_host[bi]), state.best,
                        state.pend_sel, state.pend_new, accs[s], gd, hd,
                        state.act_small, scales)
                accs[s] = acc
                if fut is not None:
                    # block k+1's staging wait + upload land here —
                    # after block k's fold DISPATCH, before its await —
                    # so the host->device copy rides under kernel time.
                    # The counter is the proof of overlap, not a claim.
                    t0 = time.perf_counter()
                    dev = self._upload_block(fut.result())
                    counter_add("stream.pipeline.overlap_s",
                                time.perf_counter() - t0)
                leaf2_host[bi] = np.asarray(l2)     # the fold await
                if dev is None and bi + 1 < len(blocks):
                    # serial escape hatch: stage + upload only after
                    # the fold is awaited (the reference schedule)
                    dev = self._upload_block(_staged(bi + 1))
            if fold is not None and not quantized:
                # float folds: finalize each owned chain ONCE per wave,
                # BEFORE the shard exchange/combine of f32 [A, F, B, 3]
                # partials.  Quantized folds exchange and add the raw
                # int32 accumulators and dequantize once, below
                for s in self.owned:
                    accs[s] = fold.unpack(accs[s])
            if exchange:
                # per-shard wave partials are rank-independent (each
                # shard's carried fold is the same program any owner
                # runs); combining the gathered partials in shard order
                # IS the single-process combine below, bitwise
                merged = self._exchange_arrays(
                    {str(s): np.asarray(accs[s]) for s in self.owned},
                    site="elastic.wave_hist")
                accs = [jnp.asarray(merged[s]) for s in range(self.S)]
            if quantized:
                new_h = fold.unpack(
                    accs[0] if self.S == 1 else combine_codes(accs), scales)
            else:
                new_h = accs[0] if self.S == 1 else combine(accs)
            hist_state, ids, res = wave_scan(state, new_h, fmask)
            state = wave_apply(state, hist_state, ids, res)
            counter_add("stream.waves")

        # final route + per-block score updates
        final_route = self._final_route_fn()
        lr = jnp.float32(self.booster.shrinkage_rate)
        nl = state.nl
        for bi, (s, start, stop, m) in enumerate(blocks):
            bins, _, _ = self.src.read_rows(start, stop)
            bins_d = jnp.asarray(self._pad_block(np.asarray(bins), m))
            l2 = final_route(bins_d, jnp.asarray(leaf2_host[bi]),
                             state.best, state.pend_sel, state.pend_new)
            row_leaf = l2[0]
            sc = self._pad_block(self.scores[start:stop], m)
            out = update(jnp.asarray(sc), state.leaf_value, nl,
                         row_leaf, lr, k=k)
            self.scores[start:stop] = np.asarray(out)[:m]

        # host tree (reuses the GBDT conversion machinery via _pending)
        lv_final = jnp.where(nl > 1, state.leaf_value,
                             jnp.zeros_like(state.leaf_value))
        bt = state.tree._replace(
            leaf_value=lv_final,
            leaf_count=state.leaf_count.astype(jnp.int32),
            leaf_depth=state.leaf_depth,
            num_leaves=nl,
            row_leaf=jnp.zeros(0, jnp.int32),
            row_value=jnp.zeros(0, jnp.float32))
        bias = (self.booster.init_score_value
                if (self.booster._num_models() < self.K
                    and abs(self.booster.init_score_value) > 1e-15)
                else 0.0)
        self.booster._pending.append(
            (bt, self.booster.shrinkage_rate, bias, 1))
        counter_add("stream.trees")
        return int(nl)

    # -- elastic protocol -------------------------------------------------
    def _exchange_arrays(self, payload,
                         site: str = "elastic.exchange") -> dict:
        """Allgather ``{shard: array}`` contributions and return the
        full ``{shard: array}`` map — every protocol shard must be
        covered (the mod-world ownership rule guarantees it; a hole
        means a protocol desync, not a recoverable fault).  ``site``
        names the call point on the collective's trace span — the
        straggler table is per-site, so root-stat, wave-histogram and
        score-sync skew attribute separately."""
        from ..parallel.elastic import decode_array, encode_array
        gathered = self.elastic.allgather(
            {s: encode_array(a) for s, a in payload.items()}, site=site)
        merged = {}
        for part in gathered:
            merged.update(part or {})
        out = {}
        for s in range(self.S):
            enc = merged.get(str(s))
            if enc is None:
                raise RuntimeError(
                    f"elastic exchange is missing shard {s} of {self.S} "
                    f"(world {self.elastic.world}): ranks disagree on "
                    "the shard protocol")
            out[s] = decode_array(enc)
        return out

    def _maybe_barrier(self, iteration: int) -> None:
        freq = int(self.config.snapshot_freq or 0)
        if freq <= 0 or iteration % freq != 0:
            return
        self._barrier_snapshot(iteration)

    def _barrier_snapshot(self, iteration: int) -> None:
        """The coordinated snapshot commit: shard states first, then a
        commit allgather of ``(iteration, model digest, shard shas)``
        that every rank must match, then rank 0 publishes model text +
        manifest (manifest LAST — its appearance is the global commit
        marker).  A SIGKILL anywhere in this sequence leaves either a
        complete barrier or a torn one that validation skips."""
        from .snapshot import commit_barrier, config_hash, \
            write_barrier_shard
        run = self.elastic
        prefix = self.config.output_model
        shard_shas = {}
        for s in self.owned:
            lo, hi = self.ranges[s]
            hi = min(hi, self.n)
            shard_shas[s] = write_barrier_shard(
                prefix, iteration, s, self.scores[lo:hi])
        model_text = self.booster.save_model_to_string(-1)
        digest = hashlib.sha256(model_text.encode()).hexdigest()
        acks = run.allgather({
            "iteration": int(iteration), "digest": digest,
            "shards": {str(s): sha for s, sha in shard_shas.items()}},
            site="elastic.barrier_commit")
        head = (acks[0]["iteration"], acks[0]["digest"])
        for a in acks[1:]:
            if (a["iteration"], a["digest"]) != head:
                event("elastic", "barrier_mismatch",
                      iteration=int(iteration))
                raise RuntimeError(
                    f"barrier commit mismatch at iteration {iteration}: "
                    f"ranks disagree on (iteration, model digest) "
                    f"{[(a['iteration'], a['digest'][:12]) for a in acks]}"
                    " — refusing to publish a snapshot that is not "
                    "globally valid")
        if run.rank == 0:
            merged = {}
            for a in acks:
                merged.update({int(s): sha
                               for s, sha in a["shards"].items()})
            meta = {
                "num_shards": int(self.S),
                "world_size": int(run.world),
                "generation": int(run.generation),
                "config_hash": config_hash(self.config),
                "init_score_value": float(self.booster.init_score_value),
                "num_tree_per_iteration": int(self.K),
            }
            commit_barrier(prefix, iteration, model_text, merged, meta,
                           keep=max(int(self.config.snapshot_keep), 1))
        # all ranks outlive the publish: a rank that raced ahead into
        # the next window could otherwise observe a half-written commit
        run.barrier(f"barrier-committed-{iteration}")
        counter_add("elastic.barriers")

    def restore_barrier(self, prefix: Optional[str] = None,
                        iteration: Optional[int] = None,
                        model_sha: Optional[str] = None) -> int:
        """Adopt the newest COMMITTED barrier under ``prefix`` (trees
        from the model text, scores from the shard state files); returns
        the restored iteration, 0 when there is nothing to restore.
        Rank-oblivious by construction: every rank reads the same
        manifest, and shard states are keyed by protocol shard, not by
        the rank that wrote them.

        ``iteration``/``model_sha`` pin the exact barrier the elastic
        world AGREED on (the restore allgather in ``train_elastic``) —
        a rank that cannot validate that barrier anymore fails fast
        here instead of resuming a different iteration and desyncing
        barrier tags mid-train."""
        from .snapshot import (barrier_paths, config_hash,
                               latest_valid_barrier, validate_barrier)
        prefix = prefix or self.config.output_model
        if iteration is None:
            man = latest_valid_barrier(prefix, num_shards=self.S)
            if man is None:
                return 0
        else:
            man = validate_barrier(barrier_paths(prefix,
                                                 int(iteration))[1])
            if man is None \
                    or int(man.get("num_shards", -1)) != self.S \
                    or (model_sha is not None
                        and man.get("model_sha256") != model_sha):
                raise RuntimeError(
                    f"agreed barrier snapshot (iteration {iteration}) "
                    "is no longer restorable on this rank — it "
                    "validated during the restore allgather but is now "
                    "missing, torn, or a different model; refusing to "
                    "resume from a different iteration than the rest "
                    "of the world")
        if man.get("config_hash") and \
                man["config_hash"] != config_hash(self.config):
            raise ValueError(
                "cannot resume from barrier snapshot: the training "
                "config changed (it would train a different model under "
                "the same prefix); clear the barrier files or keep the "
                "config")
        if int(man.get("num_tree_per_iteration", self.K)) != self.K:
            raise ValueError("barrier snapshot objective shape does not "
                             "match this run")
        with open(man["model_path"]) as f:
            donor = GBDT(self.config, None)
            donor.load_model_from_string(f.read())
        light = self.booster.train_set
        fmap = {f: i for i, f in enumerate(light.used_features)}
        for t in donor.models:
            t.align_with_mappers(light.mappers, fmap)
        self.booster.models = list(donor.models)
        self.booster._pending = []
        self.booster._stacked_cache = None
        self.booster.iter = int(man["iteration"])
        self.booster.init_score_value = float(
            man.get("init_score_value", self.booster.init_score_value))
        for s, path in man["shard_paths"].items():
            lo, hi = self.ranges[int(s)]
            hi = min(hi, self.n)
            arr = np.load(path)["scores"]
            if arr.shape != (hi - lo, self.K):
                raise ValueError(
                    f"barrier shard {s} carries scores of shape "
                    f"{arr.shape}, expected {(hi - lo, self.K)} — the "
                    "data or shard protocol changed under the prefix")
            self.scores[lo:hi] = arr
        counter_add("snapshot.barrier_resumes")
        log_info(f"restored barrier snapshot: iteration "
                 f"{self.booster.iter}, {len(man['shard_paths'])} shard "
                 f"states ({prefix})")
        return self.booster.iter

    def _sync_scores(self) -> None:
        """Train-end score replication: every rank gathers the shards
        it does not own, so the returned booster's ``digest()`` is the
        full-dataset digest on every rank (the identity the chaos gate
        compares)."""
        payload = {}
        for s in self.owned:
            lo, hi = self.ranges[s]
            hi = min(hi, self.n)
            payload[str(s)] = self.scores[lo:hi]
        merged = self._exchange_arrays(payload, site="elastic.score_sync")
        for s in range(self.S):
            lo, hi = self.ranges[s]
            hi = min(hi, self.n)
            self.scores[lo:hi] = merged[s]


def train_streaming(params, source, num_boost_round: Optional[int] = None,
                    cache_dir: Optional[str] = None,
                    block_rows: int = 0) -> GBDT:
    """Train out-of-core: ``source`` is a ShardStore, a list of data
    files (ingested into ``cache_dir`` first), or a resident
    BinnedDataset (streamed from RAM — the source-independence anchor).
    Returns a regular GBDT booster (save/predict/digest)."""
    from ..config import canonicalize_params
    from ..io.outofcore import default_cache_dir, ingest
    config = Config.from_params(canonicalize_params(dict(params)))
    config.check()
    if isinstance(source, (list, tuple)):
        cdir = cache_dir or default_cache_dir(list(source))
        source = ingest(list(source), config, cdir)
    trainer = StreamTrainer(config, source, block_rows=block_rows)
    return trainer.train(num_boost_round)


def elastic_shards(world: int, explicit: int = 0) -> int:
    """The run-lifetime protocol shard count: explicit argument >
    ``LGBM_TPU_ELASTIC_SHARDS`` > the initial world size.  Fixing S
    while the world varies is what makes every membership history land
    on the same bytes (the model is a function of ``(data, config, S)``,
    never of who computed which shard)."""
    s = int(explicit) or int(os.environ.get("LGBM_TPU_ELASTIC_SHARDS",
                                            "0") or 0)
    return s if s > 0 else max(int(world), 1)


def _write_elastic_summary(run) -> None:
    """Train-end merged telemetry summary over the ELASTIC allgather
    (elastic workers are not a jax multi-process world, so the
    ``cli.py`` ``jax_process_allgather`` route never fires for them):
    rank 0 writes ``<trace>.summary.json`` next to its trace file.

    The merge collective is gated only on shared state (``run.world``)
    — every rank participates or none does; whether a rank traces is a
    local decision applied AFTER the gather.  A peer lost between
    train end and here must not restart recovery over a summary, so
    elastic interrupts are swallowed (the trained model already
    returned on every rank's success path)."""
    import re
    from ..obs import merged_summary, write_summary
    from ..obs import telemetry
    from ..parallel.elastic import ELASTIC_INTERRUPTS
    try:
        merged = (merged_summary(
                      lambda obj: run.allgather(obj,
                                                site="elastic.summary"))
                  if run.world > 1 else None)
    except ELASTIC_INTERRUPTS:
        return
    path = telemetry.trace_path()
    if not path or (run.world > 1 and run.rank != 0):
        return
    base = re.sub(r"\.rank\d+$", "", path)
    try:
        write_summary(base + ".summary.json", merged)
    except OSError:
        log_warning("elastic: failed to write merged summary "
                    f"({base}.summary.json)")


def train_elastic(params, source, num_boost_round: Optional[int] = None,
                  coordinator: Optional[str] = None,
                  cache_dir: Optional[str] = None, block_rows: int = 0,
                  num_shards: int = 0, min_world: int = 1,
                  client=None, max_recoveries: int = 64) -> GBDT:
    """Train under the elastic protocol (``parallel/elastic.py``):
    rendezvous with the coordinator, stream-train the owned shard
    slice, commit cross-rank barrier snapshots every ``snapshot_freq``
    iterations, and on ANY elastic interrupt (lost rank, membership
    change, eviction) re-rendezvous at the new world size, re-shard,
    and resume from the last committed barrier.  The recovered model is
    byte-identical to the uninterrupted run at any world size
    (``tools/chaos.py`` is the gate).

    ``source`` follows :func:`train_streaming` (every member must see
    the same data and params — the protocol-agreement allgather checks
    the config hash).  ``coordinator`` defaults to ``LGBM_TPU_ELASTIC``.
    """
    from ..config import canonicalize_params
    from ..io.outofcore import default_cache_dir, ingest
    from ..obs import health
    from ..parallel.elastic import (ELASTIC_INTERRUPTS, ElasticClient,
                                    ElasticRun, EvictedError,
                                    elastic_address)
    from .snapshot import barrier_candidates, config_hash
    config = Config.from_params(canonicalize_params(dict(params)))
    config.check()
    if isinstance(source, (list, tuple)):
        cdir = cache_dir or default_cache_dir(list(source))
        source = ingest(list(source), config, cdir)
    own_client = client is None
    if client is None:
        addr = coordinator or elastic_address()
        if addr is None:
            raise ValueError(
                "elastic training needs a coordinator: pass "
                "coordinator='host:port' or set LGBM_TPU_ELASTIC")
        client = ElasticClient(addr)
    episode = None           # open MTTR episode (obs/fleet.py)
    trainer = None
    try:
        # records emitted during the rendezvous must not open the trace
        # file before this process knows its ELASTIC rank (same
        # discipline as mesh.init_distributed); set_rank makes the
        # coordinator's rank/world the trace identity — each elastic
        # worker is a world-1 jax process
        from ..obs.telemetry import hold_trace, release_trace, set_rank
        hold_trace()
        try:
            world, _, _ = client.join_world(min_world=min_world)
            set_rank(client.rank, client.world)
        finally:
            release_trace()
        S = elastic_shards(world, num_shards)
        chash = config_hash(config)
        recoveries = 0
        while True:
            try:
                run = ElasticRun(client, S)
                # protocol agreement before any work: every member of
                # this generation must train the same config with the
                # same shard count, or the partials are meaningless.
                # The same allgather carries each rank's view of the
                # committed barriers, so the world agrees on ONE
                # restore point up front — a lagging filesystem or a
                # concurrent prune must not let ranks resume different
                # iterations (that desync would only surface later as
                # a mid-train barrier-tag RuntimeError).
                cands = barrier_candidates(config.output_model,
                                           num_shards=S)
                views = run.allgather({
                    "shards": S, "config": chash,
                    "barriers": {str(i): sha
                                 for i, sha in cands.items()}},
                    site="elastic.protocol")
                proto = [{k: v for k, v in view.items()
                          if k != "barriers"} for view in views]
                for v in proto[1:]:
                    if v != proto[0]:
                        raise RuntimeError(
                            "elastic members disagree on the protocol "
                            f"({proto}); every member must train the "
                            "same params with the same shard count")
                common = set(views[0].get("barriers", {}).items())
                for v in views[1:]:
                    common &= set(v.get("barriers", {}).items())
                agreed = (max(common, key=lambda kv: int(kv[0]))
                          if common else None)
                with obs_span("elastic.reshard", world=run.world,
                              generation=run.generation, shards=S):
                    trainer = StreamTrainer(config, source,
                                            block_rows=block_rows,
                                            num_shards=S, elastic=run)
                    if episode is not None:
                        episode.mark("reshard")
                    it0 = (trainer.restore_barrier(
                               iteration=int(agreed[0]),
                               model_sha=agreed[1])
                           if agreed else 0)
                    if episode is not None:
                        episode.mark("restore")
                if it0:
                    log_info(f"elastic: resuming from barrier iteration "
                             f"{it0} as rank {run.rank}/{run.world} "
                             f"(generation {run.generation})")
                if episode is not None:
                    # the trainer closes it (phase `retrain`) when
                    # boosting re-reaches the interrupted iteration
                    trainer.recovery_episode = episode
                    episode = None
                health.mark_ready()
                booster = trainer.train(num_boost_round)
                _write_elastic_summary(run)
                return booster
            except ELASTIC_INTERRUPTS as exc:
                recoveries += 1
                if recoveries > max_recoveries:
                    raise
                counter_add("elastic.recoveries")
                # MTTR accounting: a new episode opens at the moment
                # the failed collective STARTED stalling (the consumed
                # client.op_started) — the deadline wait is the
                # `detect` phase.  A repeat interrupt subsumes any
                # episode still open from the previous attempt.
                from ..obs import fleet
                stall = client.op_started
                client.op_started = None
                if episode is not None:
                    episode.abandon()
                if trainer is not None \
                        and trainer.recovery_episode is not None:
                    trainer.recovery_episode.abandon()
                    trainer.recovery_episode = None
                episode = fleet.RecoveryEpisode(
                    error=type(exc).__name__,
                    generation=int(client.generation),
                    target_iter=(trainer.booster.iter
                                 if trainer is not None else 0),
                    stall_started=stall)
                episode.mark("detect")
                health.mark_recovering(reason=type(exc).__name__)
                with obs_span("elastic.recover",
                              error=type(exc).__name__):
                    event("elastic", "recover", error=type(exc).__name__,
                          generation=int(client.generation))
                    if isinstance(exc, EvictedError):
                        # evicted members come back as fresh members
                        client.join_world(min_world=1)
                    else:
                        try:
                            client.resync()
                        except ELASTIC_INTERRUPTS:
                            client.join_world(min_world=1)
                set_rank(client.rank, client.world)
                episode.mark("resync")
                continue
    finally:
        if own_client:
            try:
                client.leave()
            finally:
                client.close()
