"""GBDT — the boosting engine.

TPU-native counterpart of the reference GBDT
(`/root/reference/src/boosting/gbdt.cpp`, `gbdt.h`; model text IO
`gbdt_model_text.cpp`).  The per-iteration step mirrors ``TrainOneIter``
(`gbdt.cpp:377-472`): gradients from the objective (`gbdt.cpp:194-202`),
bagging, one tree per class via the tree learner, objective-specific leaf
renewal, shrinkage, score update (`ScoreUpdater`, `score_updater.hpp`),
eval + early stopping (`gbdt.cpp:492+`), periodic snapshots
(`gbdt.cpp:309-327`, the fork's snapshot_freq feature).

TPU design: scores/gradients live on device; the tree build is a single
jitted program; the host loop only sequences iterations and handles
serialization.  Trees exist in two forms — the device ``BuiltTree`` right
after training (score updates are pure gathers via ``row_leaf``) and the
host ``Tree`` (numpy) for the model file.
"""
from __future__ import annotations

import math
import os as _os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..io.binning import MISSING_NAN
from ..io.dataset import BinnedDataset
from ..io.device import DeviceData, to_device
from ..learner.serial import (F32_EXACT_ROWS, BuiltTree, GrowthParams,
                              build_tree, predict_built_tree)
from ..metric.metrics import Metric, create_metric, default_metric_for_objective
from ..models.tree import Tree, stack_trees, predict_binned
from ..obs import counter_add, event as obs_event, span as obs_span
from ..objective.objectives import ObjectiveFunction, create_objective
from ..ops.split import SplitParams
from ..utils.log import log_info, log_warning

K_MODEL_VERSION = "v2"     # reference gbdt_model_text.cpp:13

# mem.leak fault sink (tests/test_mem_contract.py): while the fault
# point is armed, _train appends one fresh device array per window
# here — a module-lifetime live-buffer leak the HBM watermark contract
# (obs/mem_contract.py, LGBM_TPU_MEM_CONTRACT=1) must catch and name.
_MEM_LEAK_SINK: List[jnp.ndarray] = []
# bytes leaked per window ~= 4 * this (f32); > the contract's default
# 1 MiB tolerance so a single armed window is visible above it
_MEM_LEAK_ELEMS = int(_os.environ.get("LGBM_TPU_MEM_LEAK_ELEMS", 1 << 19))


def _donation_enabled() -> bool:
    """Buffer donation through the jitted training programs (default
    ON on accelerators): the fused block donates the running score
    state (train + valid) so XLA writes the updated scores in place
    instead of allocating a second [n, K] f32 set per dispatch, and
    the mesh build donates grad/hess.  At the 10.5M-row HIGGS shape
    that is ~120 MB of HBM churn per block removed — headroom the
    wave histograms and the serve pack share.  ``LGBM_TPU_DONATE=0``
    disables for A/B (and restores full mid-execution retryability of
    the dispatch retry).

    CPU is excluded unconditionally.  The win is device memory, which
    a CPU run does not have to spare; and on the CPU backend
    ``np.asarray`` of a device array is a ZERO-COPY view into the XLA
    buffer, which host reads of the score state (eval metrics, feval,
    the C API) take all the time.  Re-checked on the installed jaxlib
    0.9.0 at PR 21: the CPU client DECLINES to donate a buffer while
    such a view is alive (input not deleted, output not aliased, 200
    donated updates read through stale views unchanged), and with
    donation forced on, the valid-set tests ran 3/3 clean — so the
    crash seen under an older jaxlib (a view read racing a donated
    dispatch) did not reproduce.  Donation there would be declined
    half the time and buy nothing, and ``_donate_active``'s one-live-
    score-set contract (obs/mem_contract.py) could not be promised, so
    it stays off.  On TPU every host read is a device->host copy and
    donation always takes."""
    if jax.default_backend() == "cpu":
        return False
    return _os.environ.get("LGBM_TPU_DONATE", "1") != "0"


def _device_bag_mask(seed: int, epoch, n: int, fraction: float):
    """Bernoulli row mask, pure in (seed, bagging epoch).  Traceable:
    ``epoch`` may be a scan carry, so the fused block derives per-epoch
    masks on device with no host RNG in the loop (reference Bagging,
    gbdt.cpp:225-286, re-bags every bagging_freq iterations)."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), epoch)
    return jax.random.uniform(key, (n,)) < fraction


def _device_feature_mask(seed: int, tree_idx, F: int, k: int):
    """Exactly-k feature mask, pure in (seed, global tree index)
    (serial_tree_learner.cpp:240-266 samples k features per tree).
    Top-k over uniforms instead of choice-without-replacement: one sort,
    no sequential draws — and traceable inside ``lax.scan``."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), tree_idx)
    r = jax.random.uniform(key, (F,))
    # scatter the top-k INDICES into a boolean mask: a `r >= kth`
    # threshold admits every tied draw (2^-24 uniform granularity) and
    # breaks the exactly-k contract over hundreds of trees (ADVICE r4)
    idx = jax.lax.top_k(r, k)[1]
    return jnp.zeros(F, bool).at[idx].set(True)


def split_params_from_config(c: Config) -> SplitParams:
    return SplitParams(
        lambda_l1=c.lambda_l1, lambda_l2=c.lambda_l2,
        min_data_in_leaf=c.min_data_in_leaf,
        min_sum_hessian_in_leaf=c.min_sum_hessian_in_leaf,
        min_gain_to_split=c.min_gain_to_split,
        max_cat_threshold=c.max_cat_threshold,
        cat_smooth=c.cat_smooth, cat_l2=c.cat_l2,
        max_cat_to_onehot=c.max_cat_to_onehot)


import functools


@functools.partial(jax.jit,
                   static_argnames=("num_leaves", "max_depth", "wave_size",
                                    "hist_mode"))
def _shared_serial_build(dd, grad, hess, bag, fmask, bins_t, split,
                         *, num_leaves, max_depth, wave_size, hist_mode):
    """Module-level jitted serial tree build: shared across all GBDT
    instances, with SplitParams TRACED (only the shape-determining
    num_leaves/max_depth/wave_size are static) — so boosters differing
    only in regularization / min-data knobs reuse one compiled program
    instead of recompiling (the dominant cost of the CPU test suite)."""
    growth = GrowthParams(num_leaves=num_leaves, max_depth=max_depth,
                          wave_size=wave_size, split=split)
    return build_tree(dd, grad, hess, growth, bag_mask=bag,
                      feature_mask=fmask, bins_t=bins_t,
                      hist_mode=hist_mode)


def _mesh_score_update_impl(scores, lv, row_leaf, lr, *, k):
    """Per-iteration mesh score update as ONE jitted program (one
    dispatch instead of three): gather the shrunk leaf values and add.
    The arithmetic region compiles exactly like the fused mesh block's
    update region, which is what keeps the ``LGBM_TPU_MESH_BLOCK=0``
    escape hatch byte-identical (tests/test_mesh_block.py pins it)."""
    return scores.at[:, k].add((lr * lv)[row_leaf[:scores.shape[0]]])


def _mesh_valid_update_impl(vscore, bt, vd, lr, *, k, matmul):
    """Per-iteration mesh valid-score update, one program — the same
    predictor selection and scale-then-predict arithmetic as the fused
    block (the predictors only gather/select leaf values)."""
    from ..learner.serial import predict_built_tree_matmul
    bts = bt._replace(leaf_value=lr * bt.leaf_value)
    pred = (predict_built_tree_matmul(bts, vd, vd.bins) if matmul
            else predict_built_tree(bts, vd, vd.bins))
    return vscore.at[:, k].add(pred)


# donated + plain lowerings of the mesh update programs: the gated
# dispatchers below pick per call (the gbdt block-fn idiom) — on
# TPU/GPU the running state updates in place, on CPU the zero-copy
# host-read hazard keeps donation off (see _donation_enabled)
_mesh_score_update_donated = functools.partial(
    jax.jit, static_argnames=("k",), donate_argnums=(0,))(
        _mesh_score_update_impl)
_mesh_score_update_plain = functools.partial(
    jax.jit, static_argnames=("k",))(_mesh_score_update_impl)
_mesh_valid_update_donated = functools.partial(
    jax.jit, static_argnames=("k", "matmul"), donate_argnums=(0,))(
        _mesh_valid_update_impl)
_mesh_valid_update_plain = functools.partial(
    jax.jit, static_argnames=("k", "matmul"))(_mesh_valid_update_impl)


def _mesh_score_update(scores, lv, row_leaf, lr, *, k):
    if _donation_enabled():
        return _mesh_score_update_donated(scores, lv, row_leaf, lr, k=k)
    return _mesh_score_update_plain(scores, lv, row_leaf, lr, k=k)


def _mesh_valid_update(vscore, bt, vd, lr, *, k, matmul):
    if _donation_enabled():
        return _mesh_valid_update_donated(vscore, bt, vd, lr, k=k,
                                          matmul=matmul)
    return _mesh_valid_update_plain(vscore, bt, vd, lr, k=k, matmul=matmul)


def growth_params_from_config(c: Config) -> GrowthParams:
    return GrowthParams(
        num_leaves=c.num_leaves, max_depth=c.max_depth,
        wave_size=1 if c.growth_mode == "leafwise" else 0,
        split=split_params_from_config(c))


class GBDT:
    """Gradient Boosting Decision Tree booster."""

    boosting_name = "gbdt"
    average_output = False

    def __init__(self, config: Config, train_set: Optional[BinnedDataset],
                 objective: Optional[ObjectiveFunction] = None,
                 fobj=None):
        self.config = config
        self.train_set = train_set
        self.fobj = fobj or config.extra.get("fobj")
        self.objective = objective
        # host trees are materialized lazily: device BuiltTrees accumulate
        # in _pending and convert in ONE batched device_get (a fetch
        # per iteration would sync the host to the device every tree;
        # what one costs is unverified on a local chip)
        self._host_models: List[Tree] = []
        # pending entries: (device tree pytree, lr, bias, n_models);
        # n_models > 1 marks a scan-stacked block with leading axis [NB(, K)]
        self._pending: List[Tuple[BuiltTree, float, float, int]] = []
        self.iter = 0
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self.init_score_value = 0.0
        self.shrinkage_rate = config.learning_rate
        self.valid_sets: List[BinnedDataset] = []
        self.valid_names: List[str] = []
        self._valid_device: List[DeviceData] = []
        self._valid_scores: List[jnp.ndarray] = []
        self.metrics: List[Metric] = []
        # a set's labels and weights as the device metrics take them,
        # by "train" or the valid set's index (_device_eval_set)
        self._eval_sets: Dict = {}
        self.feature_names: List[str] = []
        self.max_feature_idx = 0
        self._stacked_cache = None
        self._eval_history: Dict[str, Dict[str, List[float]]] = {}

        self.num_class = max(1, config.num_class)
        self.num_tree_per_iteration = config.num_tree_per_iteration
        self.mesh_ctx = None
        self._row_pad = 0
        self.num_data = 0       # no resident train set (streamed, loaded)
        # early-stopping bookkeeping lives on the INSTANCE (not train()
        # locals) so snapshots capture it and a resumed run keeps
        # counting stall rounds from where the dead run stood
        self._es_state: Dict[str, Dict] = {
            "best_scores": {}, "best_iter": {}, "key_order": []}
        # resume flag: train(num_iterations) treats the count as the
        # TOTAL target after resume_from_snapshot (the dead run's
        # target), vs "additional rounds" for continued training
        self._resumed = False
        # device-time attribution session (obs/profiler.py) while
        # train() runs under LGBM_TPU_PROFILE; dispatch-gap timestamp
        # for the ROADMAP item-1 host-latency counters
        self._profiler = None
        self._t_dispatch_ret: Optional[float] = None
        # stall watchdog (obs/health.py), live only inside train()
        self._watchdog = None

        if train_set is not None:
            with obs_span("gbdt.init", rows=train_set.num_data):
                self._init_train(train_set)

    # ------------------------------------------------------------------
    def _init_train(self, train_set: BinnedDataset) -> None:
        c = self.config
        n = train_set.num_data
        self.num_data = n
        # distributed setup: mesh + row padding to a shard multiple
        # (reference: Network::Init + mod-rank row sharding; here one SPMD
        # program over a jax Mesh, rows padded & masked out-of-bag)
        self.mesh_ctx = None
        self._row_pad = 0
        self._pr = None      # ProcessRows: multi-process row-block layout
        if c.tree_learner != "serial":
            from ..parallel.mesh import MeshContext, ProcessRows
            if len(jax.devices()) > 1 or c.mesh_shape:
                self.mesh_ctx = MeshContext(c)
                if c.tree_learner in ("data", "voting"):
                    if jax.process_count() > 1:
                        # cross-process training: this process's local
                        # rows become one padded block of the global
                        # row-sharded arrays (reference mod-rank
                        # sharding, dataset_loader.cpp:639-742).
                        # gbdt/goss/rf compose with it (GOSS samples on
                        # device from global gradients; RF's baseline
                        # scores globalize like the live scores); DART
                        # is the documented descope — its drop
                        # bookkeeping replays per-tree predictions
                        # through host-addressable scores (README
                        # "Multi-process training")
                        if self.boosting_name == "dart":
                            raise NotImplementedError(
                                "boosting=dart is not supported with "
                                "multi-process training (documented "
                                "descope: per-tree drop/renormalize "
                                "score patching assumes addressable "
                                "scores); use gbdt/goss/rf, or "
                                "single-process multi-device meshes")
                        self._pr = ProcessRows(self.mesh_ctx, n)
                        n = self.num_data = self._pr.n_pad
                    else:
                        n_pad = self.mesh_ctx.pad_rows(n)
                        self._row_pad = n_pad - n
                        # no padding rows: the scores live with the rows
                        self.mesh_ctx.scores_sharded = n_pad == n
            else:
                log_warning(f"tree_learner={c.tree_learner} requested but "
                            f"only one device is visible and no mesh_shape "
                            f"is set: training SERIALLY on that device")
        # host side of the placement (the copy itself is asynchronous)
        with obs_span("gbdt.upload", rows=n,
                      features=train_set.bins.shape[1]):
            if self._pr is not None:
                self.device_data = self._to_device_multiproc(train_set)
            elif self._row_pad:
                padded = BinnedDataset.__new__(BinnedDataset)
                padded.__dict__.update(train_set.__dict__)
                padded.bins = np.concatenate(
                    [train_set.bins,
                     np.zeros((self._row_pad, train_set.bins.shape[1]),
                              train_set.bins.dtype)])
                self.device_data = to_device(padded)
            else:
                self.device_data = to_device(train_set)
        self.feature_names = train_set.feature_names
        self.max_feature_idx = train_set.num_total_features - 1
        if self.objective is None and c.objective != "none":
            self.objective = create_objective(c)
        if self.objective is not None:
            with obs_span("gbdt.objective"):
                self.objective.init(train_set.metadata, train_set.num_data)
                self.num_tree_per_iteration = \
                    self.objective.num_model_per_iteration
                if self._pr is not None:
                    # gradients compute over the GLOBAL row axis: every
                    # per-row objective array becomes row-sharded (pad
                    # rows 0), dataset-level statistics recompute globally
                    from ..io.distributed import jax_process_allgather
                    self.objective.globalize_rows(self._pr.globalize,
                                                  jax_process_allgather)

        K = self.num_tree_per_iteration
        # scores built host-side and device_put in one transfer: eager
        # jnp.zeros/full each compile and dispatch a mini-program
        n_local = train_set.num_data
        scores_np = np.zeros((n_local if self._pr is not None else n, K),
                             np.float32)
        # init score from metadata (continued training / custom init)
        ms = train_set.metadata.init_score
        if ms is not None:
            # numcheck: disable=NUM002 -- ingest cast of user-supplied
            # init_score to the f32 score dtype: a data conversion at
            # the model boundary, not an accumulation losing precision
            scores_np = np.asarray(ms, np.float64).reshape(
                -1, K, order="F").astype(np.float32)
        elif c.boost_from_average and self.objective is not None:
            if self._pr is not None:
                # the init score must come from GLOBAL statistics, not
                # this shard's (ranks would diverge otherwise)
                from ..io.distributed import jax_process_allgather
                v = self.objective.boost_from_score_global(
                    jax_process_allgather)
            else:
                v = self.objective.boost_from_score()
            if v != 0.0:
                self.init_score_value = v
                scores_np = np.full_like(scores_np, v)
                log_info(f"boost from average: init score = {v:.6f}")
        if self._pr is not None:
            self.scores = self._pr.globalize(scores_np)
        elif self.mesh_ctx is not None:
            # partition-rule placement (parallel/partition.py): the
            # running scores live under the registry's `scores` rule so
            # the fused mesh block consumes them in place — an
            # unregistered name would raise here, not silently default
            self.scores = self.mesh_ctx.place_scores(scores_np)
        else:
            self.scores = jax.device_put(scores_np)

        self.growth = growth_params_from_config(c)
        self._label = train_set.metadata.label
        self._weight = train_set.metadata.weight
        self._query = train_set.metadata.query_boundaries
        self._setup_metrics()

        self._setup_build_program()

    def _to_device_multiproc(self, train_set: BinnedDataset) -> DeviceData:
        """Cross-process DeviceData: the bins rows are a global
        row-sharded array assembled from every process's local block;
        per-feature metadata is identical everywhere -> replicated.
        (feature_meta_np keeps this from uploading a throwaway local
        copy of the bins matrix.)"""
        from ..io.device import feature_meta_np
        pr = self._pr
        meta = feature_meta_np(train_set)
        rep = {k: pr.replicate(meta[k]) for k in (
            "bin_offsets", "num_bins", "default_bins", "missing_types",
            "is_categorical", "nan_bins", "feat_group", "feat_offset")}
        return DeviceData(
            bins=pr.globalize(train_set.bins),
            total_bins=meta["total_bins"], max_bins=meta["max_bins"],
            has_categorical=meta["has_categorical"],
            max_group_bins=meta["max_group_bins"],
            is_bundled=meta["is_bundled"],
            has_missing=meta["has_missing"], **rep)

    def _setup_build_program(self) -> None:
        """(Re)build the jitted tree-build closure from the CURRENT config
        and growth params; called at init and after ``reset_config`` (a
        stale closure would silently keep the old hyperparameters)."""
        counter_add("gbdt.program_rebuilds")
        c = self.config
        # one jitted tree-build program, traced once per (shapes, params)
        growth = self.growth
        if self.mesh_ctx is None:
            # once-per-dataset transposed bins for the Pallas kernels
            from ..learner.serial import default_hist_mode, resolve_backend
            from ..ops.pallas_histogram import transpose_bins
            # config hist_mode wins; env var / bf16 default otherwise
            # (the gpu_use_dp analog — ADVICE r2)
            from ..learner.serial import effective_hist_mode
            asked_mode = c.hist_mode or default_hist_mode()
            hist_mode = effective_hist_mode(asked_mode, self.num_data)
            self._bins_t = None
            backend = resolve_backend(self.device_data, growth.num_leaves,
                                      hist_mode=hist_mode)
            # the fused 32-iteration block runs on the Pallas backend
            # only: 32 chained SCATTER tree builds
            # in one program are one very long dispatch (an earlier
            # runtime's device watchdog killed it at >256 bins x 300k
            # rows; unverified on a local chip), so scatter configs
            # dispatch per-iteration instead
            from ..learner.serial import uses_pallas
            self._block_backend_ok = (jax.default_backend() != "tpu"
                                      or uses_pallas(backend))
            self._record_backend(backend, hist_mode, asked_mode,
                                 self.num_data)
            if uses_pallas(backend):
                bins_host = (self.train_set.bins
                             if self.train_set is not None else None)
                with obs_span("gbdt.upload", rows=self.num_data,
                              features=self.device_data.bins.shape[1]):
                    if (bins_host is not None
                            and bins_host.shape[0] <= 1 << 20):
                        # small data: transpose on host and pay a second
                        # host->device copy instead of the jitted
                        # transpose's one-time compile.  The 2^20-row
                        # threshold is unverified on a local chip
                        from ..ops.pallas_histogram import \
                            transpose_bins_host
                        self._bins_t = jax.device_put(
                            transpose_bins_host(bins_host))
                    else:
                        self._bins_t = jax.jit(transpose_bins)(
                            self.device_data.bins)
            from ..utils.timetag import phases_enabled
            if phases_enabled():
                # LGBM_TPU_TIMETAG=phases: unfused per-phase-timed waves
                # (VERDICT r2 #8; reference serial_tree_learner.cpp:12-39).
                # The driver is built ONCE so its jitted phase programs
                # are reused across trees (tags time kernels, not
                # compiles).
                from ..learner.serial import make_phases_driver
                phases_build = make_phases_driver(
                    self.device_data, growth, bins_t=self._bins_t,
                    hist_mode=hist_mode)

                def _raw_build(dd, grad, hess, bag, fmask, bins_t=None):
                    return phases_build(grad, hess, bag_mask=bag,
                                        feature_mask=fmask)
            else:
                def _raw_build(dd, grad, hess, bag, fmask, bins_t=None):
                    return _shared_serial_build(
                        dd, grad, hess, bag, fmask, bins_t, growth.split,
                        num_leaves=growth.num_leaves,
                        max_depth=growth.max_depth,
                        wave_size=growth.wave_size,
                        hist_mode=hist_mode)
        else:
            from ..parallel.learners import build_tree_distributed
            mesh = self.mesh_ctx.mesh
            axis = self.mesh_ctx.data_axis
            lt, tk = c.tree_learner, c.top_k
            dist_hist_mode = c.hist_mode or None
            self._bins_t = None
            if self._pr is None:
                # place the dataset ONCE under the partition-rule
                # registry (bins row-sharded / replicated per learner
                # type, metadata replicated): every dispatch then
                # consumes it in place instead of re-laying-out the
                # store to the mesh (the multi-process path is already
                # placed via make_array_from_process_local_data)
                with obs_span("gbdt.place"):
                    self.device_data = self.mesh_ctx.place_data(
                        self.device_data)
            pad = self._row_pad
            # in-program placement constraints come from the SAME
            # registry rules (grad/hess/bag row-sharded for data/
            # voting, replicated for feature) — the registry is the
            # only placement mechanism, eager and traced alike
            grad_ns = self.mesh_ctx.sharding_for("grad")
            hess_ns = self.mesh_ctx.sharding_for("hess")
            bag_ns = self.mesh_ctx.sharding_for("bag_mask")

            def _raw_build(dd, grad, hess, bag, fmask, bins_t=None):
                # row padding + placement INSIDE the jitted program:
                # the old eager per-iteration jnp.concatenate calls
                # were 3 extra host-driven dispatches per tree, each
                # re-placing its output from the default device
                if bag is None:
                    bag = jnp.ones(grad.shape[0], bool)
                if pad:
                    grad = jnp.concatenate(
                        [grad, jnp.zeros(pad, grad.dtype)])
                    hess = jnp.concatenate(
                        [hess, jnp.zeros(pad, hess.dtype)])
                    bag = jnp.concatenate([bag, jnp.zeros(pad, bool)])
                grad = jax.lax.with_sharding_constraint(grad, grad_ns)
                hess = jax.lax.with_sharding_constraint(hess, hess_ns)
                bag = jax.lax.with_sharding_constraint(bag, bag_ns)
                return build_tree_distributed(
                    mesh, axis, lt, dd, grad, hess, growth,
                    bag_mask=bag, feature_mask=fmask, top_k=tk,
                    hist_mode=dist_hist_mode)

            # the fused mesh scan block (see _make_block_fn) runs this
            # same build per scan-body iteration; watchdog-wise the
            # mesh follows the serial rule — long chained-scatter
            # blocks only on Pallas-capable configs
            from ..learner.serial import (default_hist_mode,
                                          effective_hist_mode,
                                          resolve_backend, uses_pallas)
            asked_mode = dist_hist_mode or default_hist_mode()
            # the mode that runs is judged on what the limbs add: a
            # SHARD's row chunks where the learner shards rows (the
            # build judges it on its own bins inside the shard_map)
            # times the shards the exchange adds, all rows where it
            # replicates them
            shard_rows = (
                self.mesh_ctx.pad_rows(self.num_data)
                // self.mesh_ctx.num_data_shards
                if self.mesh_ctx.row_sharded else self.num_data)
            mesh_hist_mode = effective_hist_mode(
                asked_mode, shard_rows,
                self.mesh_ctx.num_data_shards if lt == "data" else 1)
            mesh_backend = resolve_backend(
                self.device_data, growth.num_leaves, hist_mode=mesh_hist_mode)
            self._block_backend_ok = (jax.default_backend() != "tpu"
                                      or uses_pallas(mesh_backend))
            self._record_backend(mesh_backend, mesh_hist_mode, asked_mode,
                                 shard_rows)
        # serial path: already jitted at module level (shared cache);
        # mesh path: per-instance jit (mesh/axis closed over), with
        # grad/hess donated — they die with the build (every caller
        # hands in per-iteration slices), freeing 2 x [n_pad] f32 of
        # HBM for the wave histograms.  Donation is safe with the
        # dispatch retry: the transient class it covers surfaces at
        # compile/enqueue time, before execution consumes the buffers
        # (LGBM_TPU_DONATE=0 restores undonated dispatches for A/B;
        # CPU never donates — see _donation_enabled).
        # the un-jitted build closure: the fused scan block's body
        # traces it inline (one dispatch per block instead of per
        # iteration — the mesh path included since the partition-rule
        # refactor)
        self._raw_build = _raw_build
        if self.mesh_ctx is None:
            self._jit_build = _raw_build
        elif _donation_enabled():
            self._jit_build = jax.jit(_raw_build, donate_argnums=(1, 2))
        else:
            self._jit_build = jax.jit(_raw_build)
        # recorded for the HBM watermark contract's donation-
        # effectiveness probe (obs/mem_contract.py): only meaningful on
        # backends where the score-state donation is actually armed
        self._donate_active = _donation_enabled()
        self._mem_watermark = None
        self._block_fns: Dict[int, object] = {}
        self._block_len_uses: Dict[int, int] = {}
        self._block_compiling: set = set()
        # live background-compile threads (bounded-shutdown contract:
        # join_background reaps them; non-daemon by design, see
        # _spawn_block_compile)
        self._bg_threads: list = []
        # how often the host checks trees for the no-more-splits stop
        # (reference checks every iteration, gbdt.cpp:435-470; each
        # check is a host sync.  Every 16 off the CPU: unverified on a
        # local chip)
        default_sync = 1 if jax.default_backend() == "cpu" else 16
        import os as _os
        self._sync_freq = int(_os.environ.get("LGBM_TPU_SYNC_FREQ",
                                              default_sync))
        # iterations per fused scan dispatch.  The cap of 32 and the
        # advice to set LGBM_TPU_BLOCK_CAP=8 at big shapes (255 bins x
        # 136 features x 2.3M rows) come from an earlier runtime's
        # device watchdog; both are unverified on a local chip
        self._block_cap = max(1, int(_os.environ.get("LGBM_TPU_BLOCK_CAP",
                                                     self._BLOCK_CAP)))

    def _record_backend(self, backend: str, hist_mode: str,
                        asked_mode: str, rows: int) -> None:
        """The RESOLVED histogram backend and accumulation mode of the
        build program, on the instance and in the run summary's gauges
        — what a run on the chip checks to know which kernels it ran.
        Where the mode that runs is not the one asked for
        (``effective_hist_mode``: a quantized mode of more row shards x
        row chunks than the limbs add exactly), the summary says so: gauge
        ``gbdt.hist_mode_requested`` and event ``degrade:hist_mode``
        with the ``rows`` the bound was held against (a shard's under a
        row-sharded learner)."""
        from ..obs import gauge_set
        self.hist_backend = backend
        self.hist_mode = hist_mode
        gauge_set("gbdt.hist_backend", backend)
        gauge_set("gbdt.hist_mode", hist_mode)
        if hist_mode != asked_mode:
            gauge_set("gbdt.hist_mode_requested", asked_mode)
            obs_event("degrade", "hist_mode", requested=asked_mode,
                      effective=hist_mode, rows=int(rows))
        from ..learner.serial import uses_pallas
        if uses_pallas(backend):
            self._record_tiling(hist_mode, rows)

    def _record_tiling(self, hist_mode: str, rows: int) -> None:
        """The grid of every wave's wide histogram call, from the rule
        the kernels take it from (``ops/vmem.hist_tiling``) at the
        shapes they will see (``rows``: a shard's under a row-sharded
        learner): gauges ``hist.tiling.<cols>`` =
        ``"<feat_tile>x<row tile>"``, ``hist.feature_pad_pct``, the
        largest share of all-zero features a wave contracts,
        ``hist.wave_slots``, the staged waves' slot counts and the
        tail's (``"8,8,8,8,8,16,32,64|128"`` at 255 leaves),
        ``hist.fused_waves``, the staged waves whose route runs inside
        their histogram call (``"1,2,3,4,5,6,7"``; ``"-"``: none), by the
        rule ``build_tree`` takes (``wave_backend_plan``), and
        ``hist.row_chunks``, the int32 partials a call sums ``rows`` in
        (1 but for a quantized mode past the rows one cell holds)."""
        from ..learner.serial import (shard_row_chunks, stage_plan,
                                      wave_backend_plan)
        from ..obs import gauge_set
        from ..ops.pallas_histogram import DEFAULT_ROW_TILE
        from ..ops.vmem import (bin_stride, col_layout, hist_tiling,
                                is_quantized, round_up)
        dd = self.device_data
        F, B = dd.num_groups, bin_stride(dd.group_max_bins)
        n_pad = round_up(int(rows), DEFAULT_ROW_TILE)
        plan, A_tail = stage_plan(self.growth.num_leaves,
                                  self.growth.wave_size)
        gauge_set("hist.wave_slots",
                  f"{','.join(map(str, plan))}|{A_tail}")
        F_widest = F
        for A in sorted({*plan, A_tail}):
            C, _, cols = col_layout(A, hist_mode)
            T, feat_tile, F_grid = hist_tiling(F, n_pad, B, cols, C,
                                               hist_mode, DEFAULT_ROW_TILE)
            gauge_set(f"hist.tiling.{cols}", f"{feat_tile}x{T}")
            F_widest = max(F_widest, F_grid)
        gauge_set("hist.feature_pad_pct", 100.0 * (F_widest - F) / F)
        waves, _, _ = wave_backend_plan(
            self.growth.num_leaves, self.growth.wave_size,
            num_groups=F, max_bins=dd.group_max_bins, mode=hist_mode,
            n_rows=int(rows), serial=self.mesh_ctx is None,
            any_cat=dd.has_categorical)
        gauge_set("hist.fused_waves", ",".join(
            str(i) for i, w in enumerate(waves) if w.choice == "fused")
            or "-")
        gauge_set("hist.row_chunks",
                  shard_row_chunks(int(rows)) if is_quantized(hist_mode)
                  else 1)

    def _setup_metrics(self) -> None:
        c = self.config
        names = list(c.metric)
        if not names and c.objective != "none":
            names = [default_metric_for_objective(c.objective)]
        self.metrics = []
        seen = set()
        for nm in names:
            m = create_metric(nm, c)
            if m is not None and m.names[0] not in seen:
                self.metrics.append(m)
                seen.add(m.names[0])

    def add_valid(self, valid_set: BinnedDataset, name: str) -> None:
        """Reference GBDT::AddValidDataset (gbdt.cpp:124+)."""
        if getattr(self, "_block_fns", None):
            # block programs take the valid DeviceData/score pytrees as
            # arguments; a new valid set changes their structure, so
            # cached compiles are for the wrong signature
            self._block_fns = {}
            self._block_len_uses = {}
            self._block_compiling = set()
        self.valid_sets.append(valid_set)
        self.valid_names.append(name)
        self._valid_device.append(to_device(valid_set))
        K = self.num_tree_per_iteration
        n = valid_set.num_data
        # when trees already exist, tree 0 carries the init bias (AddBias)
        init = 0.0 if self.models else self.init_score_value
        score = jnp.full((n, K), init, jnp.float32)
        ms = valid_set.metadata.init_score
        if ms is not None:
            score = jnp.asarray(
                np.asarray(ms, np.float64).reshape(-1, K, order="F"), jnp.float32)
        if self.mesh_ctx is not None and self._pr is None:
            # valid state rides the fused mesh block as scan carries:
            # place the valid store + running scores ONCE under their
            # `valid/<i>/...` partition rules (replicated)
            vd, score = self.mesh_ctx.place_valid(
                len(self._valid_device) - 1, self._valid_device[-1], score)
            self._valid_device[-1] = vd
        # replay existing trees (continued training)
        if self.models:
            for it in range(len(self.models) // K):
                for k in range(K):
                    t = self.models[it * K + k]
                    pred = self._predict_host_tree_binned(t, self._valid_device[-1])
                    score = score.at[:, k].add(pred)
        self._valid_scores.append(score)

    # ------------------------------------------------------------------
    def _bagging_mask(self, it: int) -> Optional[jnp.ndarray]:
        """Row subsampling mask (reference Bagging, gbdt.cpp:225-286 —
        PRNG masks instead of index compaction: TPU-idiomatic).

        Stateless in (seed, iteration): the mask is a pure function of
        ``bagging_seed`` and ``it // bagging_freq``, so the fused block
        path derives the *identical* mask on device inside its
        ``lax.scan`` and block/non-block training produce the same
        models."""
        c = self.config
        if c.bagging_freq <= 0 or c.bagging_fraction >= 1.0:
            return None
        counter_add("gbdt.bagging_masks")
        from ..obs import determinism
        determinism.rng_site("gbdt.bag_mask", "bagging_seed/epoch")
        return _device_bag_mask(c.bagging_seed, it // c.bagging_freq,
                                self.num_data, c.bagging_fraction)

    def _block_sample(self, G, H, it):
        """Per-iteration row sampling inside the fused block: ``(G, H,
        it) -> (G, H, bag_mask_or_None)``.  Plain GBDT applies the
        bagging mask; GOSS overrides with gradient-based one-side
        sampling.  Both are pure in (seed, iteration), so the block and
        per-iteration paths build identical trees."""
        c = self.config
        if c.bagging_freq > 0 and c.bagging_fraction < 1.0:
            return G, H, _device_bag_mask(
                c.bagging_seed, it // c.bagging_freq, self.num_data,
                c.bagging_fraction)
        return G, H, None

    def _feature_mask(self, tree_idx: int) -> Optional[jnp.ndarray]:
        """Per-tree feature subsampling (serial_tree_learner.cpp:240-266),
        stateless in (seed, global tree index) — see _bagging_mask."""
        c = self.config
        F = self.device_data.num_features
        if c.feature_fraction >= 1.0:
            return None
        k = max(1, int(c.feature_fraction * F))
        from ..obs import determinism
        determinism.rng_site("gbdt.feature_mask",
                             "feature_fraction_seed/tree_idx")
        return _device_feature_mask(c.feature_fraction_seed, tree_idx, F, k)

    def _gradients(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(grad, hess) each [n, K] (reference Boosting(), gbdt.cpp:194-202).

        ``health.nan_grad`` fault seam: while armed, one gradient
        element is poisoned to NaN — the numerics-divergence class the
        window-boundary sentinels (``obs/health.py``) must catch and
        attribute to the right window (the NaN folds into the score
        state through this iteration's tree)."""
        g, h = self._gradients_impl()
        from ..utils.faults import fault_flag
        if fault_flag("health.nan_grad"):
            g = g.at[0, 0].set(jnp.nan)
        return g, h

    def _gradients_impl(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        if self.fobj is not None:
            g, h = self.fobj(np.asarray(self.scores).reshape(-1, order="F")
                             if self.num_tree_per_iteration > 1
                             else np.asarray(self.scores[:, 0]),
                             self.train_set)
            g = jnp.asarray(np.asarray(g, np.float32))
            h = jnp.asarray(np.asarray(h, np.float32))
            K = self.num_tree_per_iteration
            return (g.reshape(-1, K, order="F") if g.ndim == 1 and K > 1 else
                    g.reshape(-1, K)), \
                   (h.reshape(-1, K, order="F") if h.ndim == 1 and K > 1 else
                    h.reshape(-1, K))
        K = self.num_tree_per_iteration
        if K > 1:
            g, h = self.objective.get_gradients(self.scores)
            return g, h
        g, h = self.objective.get_gradients(self.scores[:, 0])
        return g[:, None], h[:, None]

    # -- lazy host-tree materialization --------------------------------
    @property
    def models(self) -> List[Tree]:
        """Host Tree list; materializes pending device trees on access."""
        self._flush_pending()
        return self._host_models

    @models.setter
    def models(self, value: List[Tree]) -> None:
        self._pending = []
        self._host_models = list(value)

    def _num_models(self) -> int:
        return len(self._host_models) + sum(p[3] for p in self._pending)

    def _flush_pending(self) -> None:
        if not self._pending:
            return
        from ..utils.timetag import tag
        with obs_span("gbdt.to_host_trees"), tag("to_host_tree"):
            # ONE device->host transfer for all pending trees/blocks
            fetched = jax.device_get([p[0] for p in self._pending])
            K = max(1, self.num_tree_per_iteration)
            for f, (_, lr, bias, count) in zip(fetched, self._pending):
                # blocks carry a leading scan axis even at length 1; the
                # fixed-length block may hold masked residue iterations
                # past `count` trees — never materialized
                if np.ndim(f.num_leaves) == 0:
                    parts = [f]
                elif K == 1:
                    NB = min(f.num_leaves.shape[0], count)
                    parts = [jax.tree.map(lambda a, i=i: a[i], f)
                             for i in range(NB)]
                else:
                    NB = min(f.num_leaves.shape[0], count // K)
                    parts = [jax.tree.map(lambda a, i=i, k=k: a[i, k], f)
                             for i in range(NB) for k in range(K)]
                for pi, bt_np in enumerate(parts):
                    host = self._to_host_tree(bt_np)
                    host.shrinkage(lr)
                    if bias and pi < K:
                        # init score lives in the first tree per class
                        host.add_bias(bias)
                    self._host_models.append(host)
            self._pending = []

    # ------------------------------------------------------------------
    def train_one_iter(self, grad: Optional[jnp.ndarray] = None,
                       hess: Optional[jnp.ndarray] = None) -> bool:
        """One boosting iteration (reference TrainOneIter gbdt.cpp:377-472).
        Returns True if training should stop (no further splits possible).

        Stays on device: no host sync per iteration.  The stump check
        (reference's should_continue) runs every `_sync_freq` iterations;
        stump trees contribute zero score either way (their leaf value is
        zeroed device-side, matching the reference's skipped UpdateScore)."""
        with obs_span("gbdt.iteration", it=self.iter):
            return self._train_one_iter(grad, hess)

    def _train_one_iter(self, grad: Optional[jnp.ndarray],
                        hess: Optional[jnp.ndarray]) -> bool:
        from ..utils.timetag import tag
        c = self.config
        with tag("boosting(grad)") as done:
            if grad is None or hess is None:
                grad, hess = self._gradients()
            done((grad, hess))
        bag = self._bagging_mask(self.iter)

        K = self.num_tree_per_iteration
        iter_trees = []
        raw_leaf_values = []    # pre-zeroing, for the numerics sentinel
        for k in range(K):
            fmask = self._feature_mask(self.iter * K + k)
            self._gap_dispatch_start()
            with tag("tree") as done:
                bt = self._build_tree(grad[:, k], hess[:, k], bag, fmask)
                self._gap_dispatch_done()
                done(bt.num_leaves)
            bt = self._renew_leaves(bt, k)
            # stump => zero contribution (reference skips UpdateScore and
            # Shrinkage for num_leaves<=1 trees, gbdt.cpp:435-460).  The
            # UN-zeroed leaf values are kept (a device reference, no
            # dispatch): a non-finite gradient always yields a stump
            # whose root value is non-finite, and the zeroing below is
            # exactly what used to hide that from every later check —
            # the stump-stop fetch inspects them (obs/health.py).
            raw_leaf_values.append(bt.leaf_value)
            bt = bt._replace(leaf_value=jnp.where(
                bt.num_leaves > 1, bt.leaf_value,
                jnp.zeros_like(bt.leaf_value)))
            iter_trees.append(bt)
            with tag("score") as done:
                self._update_scores(bt, k)
                done(self.scores)
            bias = (self.init_score_value
                    if (self._num_models() < K
                        and abs(self.init_score_value) > 1e-15) else 0.0)
            # row_leaf ([n]) is only needed for the score update above —
            # drop it so pending trees don't pin O(iters x n) HBM or ship
            # dead bytes through the batched device_get
            self._pending.append((bt._replace(row_leaf=bt.row_leaf[:0],
                                              row_value=bt.row_value[:0]),
                                  self.shrinkage_rate, bias, 1))
        self.iter += 1
        self._stacked_cache = None

        finished = False
        if self._sync_freq > 0 and (self.iter % self._sync_freq == 0):
            with tag("stump_check"):
                nls = jax.device_get([bt.num_leaves for bt in iter_trees])
            if all(int(nl) <= 1 for nl in nls):
                finished = True
                # drop this iteration's stump models (gbdt.cpp:462-468)
                self._pending = self._pending[:-K]
                self.iter -= 1
                from ..obs import health as _health
                if _health.sentinels_enabled():
                    # an all-stump stop is EITHER convergence or a
                    # poisoned gradient (every non-finite grad/hess
                    # NaNs the split gains into a stump whose root
                    # value is non-finite): inspect the pre-zeroing
                    # leaf values — one tiny [K, L] fetch on the rare
                    # stop path, zero extra dispatches
                    _health.check_leaf_values(
                        jax.device_get(raw_leaf_values),
                        window=self.iter)
                log_warning(
                    "stopped training because there are no more leaves "
                    f"that meet the split requirements (iteration "
                    f"{self.iter + 1})")
        return finished

    def _build_tree(self, grad: jnp.ndarray, hess: jnp.ndarray,
                    bag: Optional[jnp.ndarray],
                    fmask: Optional[jnp.ndarray]) -> BuiltTree:
        """Run the jitted tree build (serial or distributed)."""
        if self.mesh_ctx is not None:
            n = self.num_data
            if self._pr is not None:
                pr = self._pr
                if isinstance(bag, jnp.ndarray) and not getattr(
                        bag, "is_fully_addressable", True):
                    # the mask is ALREADY a global row-sharded device
                    # array (multi-process GOSS derives it from global
                    # gradients on device; padding rows pre-masked)
                    if fmask is not None:
                        fmask = pr.replicate(np.asarray(fmask))
                    return self._jit_build(self.device_data, grad, hess,
                                           bag, fmask)
                # cross-process: the bagging mask is a pure function of
                # (seed, iteration) so every rank computes the identical
                # full [n_pad] mask; each contributes its block, with
                # its per-block padding rows masked out-of-bag
                mask = pr.valid_mask_local()
                if bag is not None:
                    full = np.asarray(bag)
                    r = jax.process_index()
                    mask = mask & full[r * pr.per:(r + 1) * pr.per]
                bag = pr.globalize(mask, fill=False)
                if fmask is not None:
                    fmask = pr.replicate(np.asarray(fmask))
                return self._jit_build(self.device_data, grad, hess, bag,
                                       fmask)
            # padding + mesh placement of grad/hess/bag happen INSIDE
            # the jitted program (_raw_build) — one dispatch, no eager
            # per-iteration concat round-trips
            bt = self._jit_build(self.device_data, grad, hess, bag, fmask)
            if self._row_pad:
                bt = bt._replace(row_leaf=bt.row_leaf[:n])
            return bt
        return self._jit_build(self.device_data, grad, hess, bag,
                               fmask, self._bins_t)

    def _renew_leaves(self, bt: BuiltTree, k: int) -> BuiltTree:
        """Objective-specific leaf re-fit (RenewTreeOutput,
        serial_tree_learner.cpp:592-622 + regression_objective.hpp)."""
        if (self.objective is not None
                and self.objective.need_renew_tree_output):
            new_vals = self.objective.renew_tree_output(
                self.scores[:, k], bt.row_leaf, self.growth.num_leaves)
            if new_vals is not None:
                bt = bt._replace(leaf_value=jnp.where(
                    jnp.arange(self.growth.num_leaves) < bt.num_leaves,
                    new_vals.astype(jnp.float32), bt.leaf_value))
        return bt

    def _update_scores(self, bt: BuiltTree, k: int) -> None:
        lr = self.shrinkage_rate
        if self.mesh_ctx is not None and self._pr is None:
            # one jitted program per update (see _mesh_score_update):
            # byte-identical arithmetic to the fused mesh block AND
            # fewer per-iteration dispatches on the escape-hatch path
            # (multi-process keeps the eager update: its valid stores
            # are process-local while bt/scores span the global mesh)
            self.scores = _mesh_score_update(
                self.scores, bt.leaf_value, bt.row_leaf,
                jnp.float32(lr), k=k)
            for i, vd in enumerate(self._valid_device):
                self._valid_scores[i] = _mesh_valid_update(
                    self._valid_scores[i], bt, vd, jnp.float32(lr), k=k,
                    matmul=not vd.has_categorical)
            return
        if bt.row_value.shape[0] and not (
                self.objective is not None
                and self.objective.need_renew_tree_output):
            # kernel-emitted per-row values (no gather); renewal rewrites
            # leaf_value after emission, so it must take the gather path
            self.scores = self.scores.at[:, k].add(lr * bt.row_value)
        else:
            self.scores = self.scores.at[:, k].add(
                lr * bt.leaf_value[bt.row_leaf])
        for i, vd in enumerate(self._valid_device):
            pred = predict_built_tree(bt, vd, vd.bins)
            self._valid_scores[i] = self._valid_scores[i].at[:, k].add(lr * pred)

    def _to_host_tree(self, bt) -> Tree:
        """Host-side BuiltTree (numpy pytree from ONE device_get) -> Tree
        with real-valued thresholds."""
        ds = self.train_set
        nl = int(bt.num_leaves)
        t = Tree(max(self.growth.num_leaves, 2))
        t.num_leaves = nl
        m = nl - 1
        if m == 0:
            t.leaf_value[0] = float(bt.leaf_value[0])
            t.leaf_count[0] = int(bt.leaf_count[0])
            return t
        feat_inner = np.asarray(bt.feature)[:m]
        thr_bin = np.asarray(bt.threshold_bin)[:m]
        dl = np.asarray(bt.default_left)[:m]
        is_cat = np.asarray(bt.is_categorical)[:m]
        cat_mask = np.asarray(bt.cat_mask)[:m]
        t.split_feature_inner[:m] = feat_inner
        t.left_child[:m] = np.asarray(bt.left_child)[:m]
        t.right_child[:m] = np.asarray(bt.right_child)[:m]
        t.split_gain[:m] = np.asarray(bt.gain)[:m]
        t.internal_value[:m] = np.asarray(bt.internal_value)[:m]
        t.internal_count[:m] = np.asarray(bt.internal_count)[:m]
        t.leaf_value[:nl] = np.asarray(bt.leaf_value)[:nl]
        t.leaf_count[:nl] = np.asarray(bt.leaf_count)[:nl]
        t.leaf_depth[:nl] = np.asarray(bt.leaf_depth)[:nl]
        if self.num_data > F32_EXACT_ROWS:
            # the growth's float32 counts are rounded past 2^24 rows; the
            # learner recounted the leaves in integers, and a node holds
            # its children's rows (children come after their parent)
            for node in range(m - 1, -1, -1):
                t.internal_count[node] = sum(
                    t.leaf_count[~c] if c < 0 else t.internal_count[c]
                    for c in (int(t.left_child[node]),
                              int(t.right_child[node])))
        for node in range(m):
            inner = int(feat_inner[node])
            orig = ds.used_features[inner]
            mapper = ds.mappers[orig]
            t.split_feature[node] = orig
            mt = mapper.missing_type
            if is_cat[node]:
                bins = np.nonzero(cat_mask[node])[0]
                bins = bins[bins < mapper.num_bin]
                values = sorted(int(mapper.bin_2_categorical[b]) for b in bins)
                from ..models.tree import _construct_bitset
                ci = t.num_cat
                t.decision_type[node] = np.int8(1 | ((mt & 3) << 2))
                t.threshold[node] = float(ci)
                t.threshold_bin[node] = ci
                bitset = _construct_bitset(values)
                t.cat_threshold.extend(bitset)
                t.cat_boundaries.append(len(t.cat_threshold))
                t.cat_left_bins.append(np.asarray(sorted(bins), np.int32))
                t.num_cat += 1
            else:
                dt = np.int8((mt & 3) << 2)
                if dl[node]:
                    dt |= np.int8(2)
                t.decision_type[node] = dt
                t.threshold_bin[node] = int(thr_bin[node])
                t.threshold[node] = mapper.threshold_value(int(thr_bin[node]))
        return t

    @staticmethod
    def _bundle_kw(dd: DeviceData) -> Dict[str, jnp.ndarray]:
        if not dd.is_bundled:
            return {}
        return {"feat_group": dd.feat_group, "feat_offset": dd.feat_offset,
                "num_bins": dd.num_bins}

    def _predict_host_tree_binned(self, tree: Tree, dd: DeviceData) -> jnp.ndarray:
        return self._predict_host_trees_binned([tree], dd)

    def _predict_host_trees_binned(self, trees: List[Tree],
                                   dd: DeviceData) -> jnp.ndarray:
        """SUMMED per-row output of ``trees`` in one stacked dispatch
        (predict_binned accumulates over the stacked tree axis) — the
        batched form DART's drop/renormalize pass relies on.  The tree
        axis pads to a power of two with zero stumps: DART's drop count
        varies every iteration and an unpadded stack would compile one
        program per distinct count."""
        if len(trees) > 1:
            pad = (1 << (len(trees) - 1).bit_length()) - len(trees)
            trees = list(trees) + [Tree(2)] * pad   # stumps: 0 output
        st = stack_trees(trees, max_bins=dd.max_bins,
                         pad_leaves=self.growth.num_leaves
                         if self.train_set is not None else 0)
        pred = predict_binned(st, dd.bins, dd.nan_bins, dd.default_bins,
                              dd.missing_types, **self._bundle_kw(dd))
        if dd is self.device_data and self._row_pad:
            pred = pred[:self.num_data]     # drop distributed padding rows
        return pred

    # ------------------------------------------------------------------
    def rollback_one_iter(self) -> None:
        """Reference RollbackOneIter (gbdt.cpp:474-490)."""
        if self.iter <= 0:
            return
        K = self.num_tree_per_iteration
        for k in range(K):
            tree = self.models.pop()
            kk = K - 1 - k
            pred = self._predict_host_tree_binned(tree, self.device_data)
            self.scores = self.scores.at[:, kk].add(-pred)
            for i, vd in enumerate(self._valid_device):
                vpred = self._predict_host_tree_binned(tree, vd)
                self._valid_scores[i] = self._valid_scores[i].at[:, kk].add(-vpred)
        self.iter -= 1
        self._stacked_cache = None

    def merge_from(self, other: "GBDT") -> None:
        """Merge the other booster's trees in FRONT of this booster's, as
        deep copies (reference GBDT::MergeFrom, gbdt.h:50-67: other's
        trees are pushed first, then the original models, every tree
        copy-constructed — so iteration-limited predict/save and
        tree-indexed leaf access order like the reference, and mutating
        either booster afterwards never aliases the other).  Scores are
        refreshed from the merged trees when a train set is attached."""
        import copy
        if other.num_tree_per_iteration != self.num_tree_per_iteration:
            raise ValueError("cannot merge boosters with different "
                             "num_tree_per_iteration")
        new = [copy.deepcopy(t) for t in other.models]
        self.models = new + list(self.models)
        K = max(1, self.num_tree_per_iteration)
        self.iter = len(self._host_models) // K
        if self.train_set is not None:
            for j, tree in enumerate(new):
                kk = j % K
                pred = self._predict_host_tree_binned(tree, self.device_data)
                self.scores = self.scores.at[:, kk].add(pred)
                for i, vd in enumerate(self._valid_device):
                    vpred = self._predict_host_tree_binned(tree, vd)
                    self._valid_scores[i] = (
                        self._valid_scores[i].at[:, kk].add(vpred))
        self._stacked_cache = None

    def load_model_trees(self, text: str) -> None:
        """Install a saved model's trees into THIS booster, keeping its
        train set and config (ResetTrainingData continue path,
        c_api.h:382-389): scores are replayed so further training
        continues from the loaded model."""
        donor = GBDT(self.config, None)
        donor.load_model_from_string(text)
        self.models = []
        self.iter = 0
        self.merge_from(donor)

    def reset_config(self, params: Dict[str, str]) -> None:
        """Reference ResetConfig (c_api.cpp Booster::ResetConfig): re-read
        training hyperparameters; the dataset and model are kept."""
        from ..config import canonicalize_params
        self.config.update(canonicalize_params(dict(params)))
        self.config.check()
        self.shrinkage_rate = self.config.learning_rate
        if self.train_set is not None:
            self.growth = growth_params_from_config(self.config)
            self._setup_metrics()
            self._setup_build_program()   # drop stale growth/hist closures

    def set_leaf_value(self, tree_idx: int, leaf_idx: int,
                      val: float) -> None:
        """Reference SetLeafValue (c_api.h:723-734); adjusts train scores
        by the delta like GBDT does via the score updater."""
        models = self.models
        tree = models[tree_idx]
        old = float(tree.leaf_value[leaf_idx])
        tree.leaf_value[leaf_idx] = val
        self._stacked_cache = None
        if self.train_set is not None and abs(val - old) > 0:
            kk = tree_idx % max(1, self.num_tree_per_iteration)
            pred_new = self._predict_host_tree_binned(tree, self.device_data)
            tree.leaf_value[leaf_idx] = old
            pred_old = self._predict_host_tree_binned(tree, self.device_data)
            tree.leaf_value[leaf_idx] = val
            self.scores = self.scores.at[:, kk].add(pred_new - pred_old)

    # ------------------------------------------------------------------
    def eval_train(self) -> List[Tuple[str, str, float, bool]]:
        return self._eval_set("training", "train", self.scores,
                              self._label, self._weight, self._query)

    def eval_valid(self) -> List[Tuple[str, str, float, bool]]:
        out = []
        for i, vs in enumerate(self.valid_sets):
            md = vs.metadata
            out.extend(self._eval_set(
                self.valid_names[i], i, self._valid_scores[i],
                md.label, md.weight, md.query_boundaries))
        return out

    def _eval_set(self, name, key, scores, label, weight, query):
        """Every metric of one set (``key``: ``"train"`` or the valid
        set's index) from its device scores ``[n, K]``.  A metric with a
        device form (``metric/device.py``) is worked out where the
        scores live and only its sums cross to the host; for the others
        the scores are fetched once (``gbdt.eval_host_rows`` counts the
        rows) and handed to ``metric/metrics.py``."""
        results = []
        if label is None or not self.metrics:
            return results
        from ..obs import gauge_set
        counter_add("gbdt.evals")
        es = self._device_eval_set(key, label, weight, scores)
        forms = self._device_forms()
        on_device = (es.eval(scores, forms, self.config.sigmoid)
                     if es is not None and forms else {})
        s = None
        for m in self.metrics:
            if m.device_form in on_device:
                found = m.from_device(on_device[m.device_form])
            else:
                if s is None:
                    # multi-process: global training scores span other
                    # processes' devices, so a rank evaluates its own
                    # rows (the reference's machines likewise report
                    # their local shard's training metric)
                    s = (self._pr.local_np(scores) if self._pr is not None
                         and key == "train" else np.asarray(scores))
                    counter_add("gbdt.eval_host_rows", int(s.shape[0]))
                    s = s if s.shape[1] > 1 else s[:, 0]
                    label = np.asarray(label)
                found = m.eval(label, s, weight, query)
            for mname, val, hib in found:
                results.append((name, mname, val, hib))
        gauge_set("gbdt.eval_backend", "device" if s is None else "host")
        return results

    def _device_forms(self) -> Tuple[str, ...]:
        return tuple(m.device_form for m in self.metrics
                     if m.device_form is not None)

    def _compile_evals(self) -> None:
        """Start compiling the device metrics' programs of every set
        that ``_train`` evaluates, each on a thread of its own, before
        the first window: they compile beside the block program, and an
        evaluation between windows compiles nothing (it waits for its
        program where that is still on its way).  A thread is started
        once a program: a later ``_train`` call (a user's loop calls it
        every iteration) starts none.  Avals only: a live array would
        pin the score buffer the first block donates."""
        forms = self._device_forms()
        sets = [(i, vs.metadata.label, vs.metadata.weight,
                 self._valid_scores[i])
                for i, vs in enumerate(self.valid_sets)]
        if self.config.is_training_metric:
            sets.append(("train", self._label, self._weight, self.scores))
        for key, label, weight, scores in sets:
            es = (self._device_eval_set(key, label, weight, scores)
                  if label is not None and forms else None)
            if es is not None and es.first_request(scores, forms,
                                                   self.config.sigmoid):
                aval = jax.ShapeDtypeStruct(scores.shape, scores.dtype,
                                            sharding=scores.sharding)
                self._start_background(
                    es.program, f"lgbm-tpu-eval-compile-{key}", aval, forms,
                    self.config.sigmoid)

    def _device_eval_set(self, key, label, weight, scores):
        """The set's :class:`metric.device.EvalSet`, made at its first
        evaluation; None where the device forms do not apply (several
        scores a row, rows on other processes' devices, weights the
        device's integers do not hold, no rows)."""
        if self._pr is not None or scores.shape[1] != 1:
            return None
        held = self._eval_sets.get(key)
        if held is None or held[0] is not label or held[1] is not weight:
            # made anew where the set's labels or weights were replaced
            from ..metric.device import EvalSet
            # the binary objective's labels are the set's own, resident
            # already; any other objective may have rewritten its copy
            resident = (self.objective.label
                        if key == "train" and self.mesh_ctx is None
                        and getattr(self.objective, "name", "") == "binary"
                        else None)
            es = EvalSet(label, weight, resident)
            held = (label, weight, es if es.usable else None)
            self._eval_sets[key] = held
        return held[2]

    # -- fused multi-iteration training blocks --------------------------
    def _can_block(self) -> bool:
        """Whether iterations can run as ONE jitted ``lax.scan`` block.

        Every enqueued op costs host dispatch time; a block
        collapses a whole window of iterations into a single dispatch
        (gradients → tree build → score update chained on device).
        Single-process device MESHES ride the same fused block since
        the partition-rule refactor: the scan body traces the
        distributed build (shard_map + the wave's psum) in place
        of the serial one, so a d-chip mesh pays one dispatch per
        window instead of one per iteration (``LGBM_TPU_MESH_BLOCK=0``
        is the per-iteration escape hatch / A-B baseline).  Excluded:
        multi-process training (per-iteration host-side mask
        globalization), custom fobj (host callback), leaf renewal
        (quantile-style refit), non-plain boosters (DART/RF override
        the iteration), and the per-phase timetag debug mode
        (host-driven waves).  Valid sets stay IN the block since r5:
        their per-tree scoring runs on device inside the scan
        (path-agreement matmul / node walk).  Bagging and
        feature_fraction stay IN the block: their masks are pure
        functions of (seed, iteration) / (seed, tree index), derived on
        device inside the scan body — identical to the per-iteration
        path's masks."""
        from ..utils.timetag import phases_enabled
        if phases_enabled():
            return False
        if _os.environ.get("LGBM_TPU_NO_BLOCK"):
            # debug / watchdog escape hatch: slow backends (scatter at
            # large n) can push a 32-iteration block past the device's
            # dispatch watchdog; per-iteration dispatches stay short
            return False
        if self.mesh_ctx is not None and self._pr is not None:
            return False
        return (self.boosting_name in ("gbdt", "goss")
                and self.fobj is None
                and self.objective is not None
                and not self.objective.need_renew_tree_output
                and getattr(self, "_block_backend_ok", True))

    def _block_fn(self, cap: int):
        """A jitted fixed-length-``cap`` scan block.  Iterations past
        ``n_active`` run masked: their score update is discarded and
        their trees are never materialized host-side.  Masking decouples
        requested block length from compiled scan length — compile
        count, not FLOPs, is the real cold-start cost (35-44 s per 1M-row
        block program against under 0.3 s per iteration: PERF.md, PR 21,
        one run).  See
        train_block for the reuse policy."""
        fn = self._block_fns.get(cap)
        if fn is not None:
            return fn
        fn = self._make_block_fn(cap)
        self._block_fns[cap] = fn
        return fn

    def _make_block_fn(self, cap: int):
        """Build (without caching) the jitted length-``cap`` block."""
        obj = self.objective
        growth = self.growth
        K = self.num_tree_per_iteration
        c = self.config
        n = self.num_data
        F = self.device_data.num_features
        ff_on = c.feature_fraction < 1.0
        kf = max(1, int(c.feature_fraction * F))

        # dd/bins_t are ARGUMENTS, not closures: closed-over device
        # arrays embed as constants in the compiled program — 28 MB of
        # bins at 1M rows, 294 MB at 10.5M rows, per block length — and
        # in every compile-cache entry.  Valid sets ride
        # the same way: their DeviceData + running scores are scan
        # carries, so train-with-valid (+ early stopping at window
        # boundaries) STAYS on the fused path (VERDICT r4 #1; the
        # reference likewise scores valid data per tree without
        # decelerating training, gbdt.cpp:492+, score_updater.hpp:54-100)
        from ..learner.serial import (predict_built_tree,
                                      predict_built_tree_matmul)
        # the mesh path's scan body traces the SAME distributed build
        # closure the per-iteration path jits (_raw_build: in-program
        # row padding + registry sharding constraints + shard_map wave
        # loop), so the flight-recorder collective schedule per trace —
        # one hist_psum fingerprint per wave — is identical on both
        # paths; only the dispatch count changes (one per window)
        mesh_build = self._raw_build if self.mesh_ctx is not None else None
        scores_ns = (self.mesh_ctx.sharding_for("scores")
                     if self.mesh_ctx is not None and self._pr is None
                     else None)

        def valid_update(vscores, vds, bt, lv_s, k):
            """Valid-set scoring per tree, on device: the path-agreement
            matmul (MXU) for numerical valid sets, the node walk where
            categorical splits need the bitset decision."""
            with jax.named_scope("gbdt.valid_update"):
                bts = bt._replace(leaf_value=lv_s)
                return tuple(
                    vs.at[:, k].add(
                        predict_built_tree(bts, vd, vd.bins)
                        if vd.has_categorical else
                        predict_built_tree_matmul(bts, vd, vd.bins))
                    for vs, vd in zip(vscores, vds))

        def block(dd, bins_t, vds, scores, vscores, lr, it0, n_active):
            def body(carry, it):
                scores, vscores = carry
                active = it - it0 < n_active
                scores_in, vscores_in = scores, vscores
                # the scopes of this body and of the tree build carry
                # the span names of the unfused path: they are metadata
                # on the operations (a device trace names them), and
                # nothing in the compiled program
                with jax.named_scope("obj.grad"):
                    if K == 1:
                        g, h = obj.get_gradients(scores[:, 0])
                        G, H = g[:, None], h[:, None]
                    else:
                        G, H = obj.get_gradients(scores)
                # sampling derived on device, pure in iteration — the
                # same functions the per-iteration path uses, so bagged
                # (and GOSS: _block_sample override) configs stay on
                # the fused fast path
                with jax.named_scope("gbdt.bag_mask"):
                    G, H, bag = self._block_sample(G, H, it)
                # BYTE-identity fence (serial AND mesh since the
                # out-of-core round): eagerly — and in the streamed
                # trainer's standalone per-block programs — gradients
                # materialize as f32 program outputs before the build
                # consumes them; fused, XLA would contract producer/
                # consumer mul+add chains into FMAs with different
                # last-ulp rounding.  The barrier reproduces that
                # program boundary at zero runtime cost, which is what
                # lets boosting/streaming.py match this body bitwise.
                G, H = jax.lax.optimization_barrier((G, H))
                if bag is not None:
                    bag = jax.lax.optimization_barrier(bag)
                outs = []
                for k in range(K):
                    with jax.named_scope("gbdt.feature_mask"):
                        fmask = (_device_feature_mask(
                            c.feature_fraction_seed, it * K + k, F, kf)
                            if ff_on else None)
                    if mesh_build is not None:
                        bt = mesh_build(dd, G[:, k], H[:, k], bag, fmask)
                    else:
                        bt = build_tree(dd, G[:, k], H[:, k], growth,
                                        bag_mask=bag, feature_mask=fmask,
                                        bins_t=bins_t,
                                        hist_mode=c.hist_mode or None)
                    lv = jnp.where(bt.num_leaves > 1, bt.leaf_value,
                                   jnp.zeros_like(bt.leaf_value))
                    bt = bt._replace(leaf_value=lv)
                    if mesh_build is not None:
                        # byte-identity vs the per-iteration mesh path
                        # (LGBM_TPU_MESH_BLOCK=0): the fence keeps the
                        # build subgraph's internal fusion identical to
                        # its standalone jit, and the update mirrors
                        # _mesh_score_update / _mesh_valid_update's
                        # contraction-proof scale-then-gather shape —
                        # identical last-ulp rounding in any fusion
                        # context
                        bt = jax.lax.optimization_barrier(bt)
                        with jax.named_scope("gbdt.score_update"):
                            lv_s = lr * bt.leaf_value            # [L]
                            if bt.row_value.shape[0]:
                                # emitted by each shard's final route
                                # kernel, as on the serial path: no
                                # gather over the shard's rows
                                scores = scores.at[:, k].add(
                                    lr * bt.row_value[:scores.shape[0]])
                            else:
                                scores = scores.at[:, k].add(
                                    lv_s[bt.row_leaf[:scores.shape[0]]])
                    else:
                        # serial branch fenced like the mesh branch
                        # since the out-of-core round: the barrier
                        # keeps the build subgraph's fusion identical
                        # to its standalone jit, and the updates use
                        # the contraction-proof scale-then-gather /
                        # scale-then-predict shapes — so the streamed
                        # trainer's standalone per-block dispatches
                        # (boosting/streaming.py) reproduce the same
                        # last-ulp rounding in any fusion context
                        bt = jax.lax.optimization_barrier(bt)
                        with jax.named_scope("gbdt.score_update"):
                            lv_s = lr * bt.leaf_value            # [L]
                            if bt.row_value.shape[0]:
                                # emitted by the final route kernel
                                # (already stump-masked); avoids the
                                # 1M-row gather
                                scores = scores.at[:, k].add(
                                    lr * bt.row_value)
                            else:
                                scores = scores.at[:, k].add(
                                    lv_s[bt.row_leaf])
                    vscores = valid_update(vscores, vds, bt, lv_s, k)
                    outs.append(bt._replace(row_leaf=bt.row_leaf[:0],
                                            row_value=bt.row_value[:0]))
                stacked = (outs[0] if K == 1 else
                           jax.tree.map(lambda *xs: jnp.stack(xs), *outs))
                # masked residue iteration: keep the pre-iteration scores
                # (its trees are dropped host-side via the pending count)
                scores = jnp.where(active, scores, scores_in)
                vscores = tuple(jnp.where(active, vs, vi)
                                for vs, vi in zip(vscores, vscores_in))
                return (scores, vscores), stacked
            (scores, vscores), trees = jax.lax.scan(
                body, (scores, vscores), it0 + jnp.arange(cap))
            if scores_ns is not None:
                # the scores leave as they came (the registry's rule):
                # left to the partitioner they came back in another
                # layout, and the next block compiled a second program
                scores = jax.lax.with_sharding_constraint(scores, scores_ns)
            return (scores, vscores), trees

        from ..learner.serial import _COMPILE_LEAN_ROWS
        jit_kw = {}
        if _donation_enabled():
            # donate the running score state (train scores + valid
            # scores): the block returns their successors with
            # identical shape/dtype, so XLA aliases the buffers and
            # updates in place — no second [n, K] (+ valid) f32 live
            # set per dispatch.  Safe with _dispatch_retry: its
            # transient class surfaces at compile/enqueue, before
            # execution consumes the inputs.
            jit_kw["donate_argnums"] = (3, 4)
        if n <= _COMPILE_LEAN_ROWS:
            # small data: XLA compile time dominates the cold start and
            # runtime barely responds to optimization effort.  The
            # installed jax 0.9.0 / libtpu 0.0.34 accept the option on
            # both the CPU and the TPU compiler (checked at PR 21), so
            # there is no probe: a compiler that refuses it raises here
            return jax.jit(block, compiler_options={
                "exec_time_optimization_effort": -1.0}, **jit_kw)
        return jax.jit(block, **jit_kw)

    def _spawn_block_compile(self, L: int) -> None:
        """AOT-compile the length-``L`` block program on a background
        thread and install it when ready: recurring residue lengths
        (windowed runs, warm re-trains) upgrade from a borrowed longer
        program to the right size WITHOUT ever stalling the training
        loop on a 10-30 s XLA compile."""
        if L in self._block_fns or L in self._block_compiling:
            return
        counter_add("gbdt.block_compiles_bg")
        self._block_compiling.add(L)
        fn = self._make_block_fn(L)
        # install into THIS config generation's cache object: a
        # reset_config between the spawn and the install swaps the dict,
        # so a stale-config program can only ever land in the dead one
        fns = self._block_fns
        # avals only — capturing live arrays would pin the superseded
        # scores buffer (and a second device_data reference) for the
        # whole compile
        aval = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
            jnp.shape(x), jnp.result_type(x))
        args = (jax.tree.map(aval, self.device_data),
                jax.tree.map(aval, self._bins_t),
                jax.tree.map(aval, tuple(self._valid_device)),
                aval(self.scores),
                jax.tree.map(aval, tuple(self._valid_scores)),
                aval(jnp.float32(0)),
                aval(jnp.int32(0)), aval(jnp.int32(0)))

        def work():
            try:
                fns[L] = fn.lower(*args).compile()
                self._block_compiling.discard(L)
            except Exception as exc:    # noqa: BLE001
                # keep L in _block_compiling: a deterministic compile
                # failure must not be retried every window — borrowed
                # programs serve this length forever
                log_warning(f"background compile of block length {L} "
                            f"failed; keeping the borrowed program "
                            f"({exc})")

        self._start_background(work, f"lgbm-tpu-block-compile-{L}")

    def _start_background(self, work, name: str, *args) -> None:
        """Run ``work(*args)`` on a thread that ``join_background``
        reaps.  NON-daemon: a daemon thread mid-XLA-compile at
        interpreter shutdown races the runtime teardown and segfaults; a
        normal thread just delays exit until the compile lands."""
        import threading
        t = threading.Thread(target=work, args=args, daemon=False, name=name)
        self._bg_threads = [th for th in self._bg_threads
                            if th.is_alive()]
        self._bg_threads.append(t)
        t.start()

    def join_background(self, timeout: Optional[float] = None) -> bool:
        """Wait for in-flight background block compiles (the bounded-
        shutdown contract: every spawned thread has a join path).
        Returns True when none remain; a compile still running after
        ``timeout`` seconds (per thread) leaves its thread alive —
        non-daemon, so it still finishes before interpreter exit."""
        for t in self._bg_threads:
            t.join(timeout)
        self._bg_threads = [t for t in self._bg_threads if t.is_alive()]
        return not self._bg_threads

    _BLOCK_CAP = 32

    def _dispatch_retry(self, fn, *args):
        """Run a PURE jitted dispatch with transient-failure retries
        (the reference's socket layer retries sends the same way,
        linkers_socket.cpp; on a TPU pod the transient class is
        RPC-flavored).  Safe because the block programs are functional —
        inputs are untouched until the result is assigned.  Covers the
        dispatch/compile path (where runtime RPC failures surface
        synchronously); asynchronous execution faults still propagate
        at the next fetch.

        Backoff/deadline/transient classification live on the SHARED
        retry utility (``utils/retry.py``) since the fault-tolerance
        round — the same policy the rendezvous and host collectives use;
        ``LGBM_TPU_RETRY_*`` env knobs tune all of them together."""
        from ..utils.retry import retry_call
        return retry_call(fn, *args, what="device_dispatch")

    def _pick_block_len(self, nb: int) -> int:
        """Compiled scan length for a block of ``nb`` active iterations.

        Right size is the next power of two (masked waste < 2x), but a
        fresh length costs a full XLA compile, so: reuse an exact-length
        program when one exists; otherwise borrow the smallest
        already-compiled length >= nb (a one-off residue — e.g. 100 =
        3x32 + 4 — should never compile a second program just to skip
        28 masked iterations).  Once the same length RECURS (windowed
        runs — output_freq / snapshot_freq — or warm re-trains, which
        would otherwise pay the masked waste on EVERY window), the right
        size compiles on a background thread and takes over when ready —
        the loop itself never stalls on a compile it can mask around."""
        L = 1
        while L < nb:
            L *= 2
        uses = self._block_len_uses.get(L, 0) + 1
        self._block_len_uses[L] = uses
        if L in self._block_fns:
            return L
        # snapshot: the background compile thread inserts into this dict
        # (iterating the live dict would raise on a concurrent insert)
        borrow = [l for l in list(self._block_fns) if l >= nb]
        if not borrow:
            return L                    # nothing to mask with: compile
        if uses >= 2:
            self._spawn_block_compile(L)
        return min(borrow)

    def train_block(self, num_iters: int) -> bool:
        """Run up to ``num_iters`` iterations, batching into scan blocks
        when possible.  Returns True when training finished (no more
        splittable leaves)."""
        from ..utils.timetag import tag
        done = 0
        K = self.num_tree_per_iteration
        c = self.config
        # stump-stop checks are OVERLAPPED: each block's last-iteration
        # leaf count is fetched asynchronously and inspected one block
        # later, so the device never idles a host round-trip between
        # blocks (its size is unverified on a local chip).
        # When a late check fires, the one extra dispatched block is all
        # stumps (zero score contribution) and is rolled back whole.
        # Valid ONLY when gradients are the sole per-iteration input: a
        # stump leaves scores (hence gradients) unchanged, so every
        # later iteration reproduces the stump.  Bagging/feature-
        # fraction resample per iteration/tree and CAN grow real trees
        # after a stump — those configs resolve each check immediately
        # (review r4 finding: a rolled-back real tree would leave its
        # score contribution behind).
        speculate = ((c.bagging_freq <= 0 or c.bagging_fraction >= 1.0)
                     and c.feature_fraction >= 1.0
                     and self.boosting_name == "gbdt")  # GOSS resamples
        prev_check = None                  # pending num_leaves slice
        stopped = False
        # LGBM_TPU_MESH_BLOCK=0: the fused-mesh A/B escape hatch —
        # per-ITERATION dispatch granularity (length-1 blocks of the
        # SAME compiled scan body), so the unfused baseline is
        # byte-identical by construction and the only variable is the
        # dispatch count.  Resolved per call: an env flip mid-run just
        # switches the next window's block length.
        cap = self._block_cap
        if (self.mesh_ctx is not None
                and _os.environ.get("LGBM_TPU_MESH_BLOCK", "1") == "0"):
            cap = 1
        while done < num_iters and not stopped:
            if not self._can_block():
                # unsupported config: per-iteration path
                if self.train_one_iter():
                    return True
                done += 1
                continue
            nb = min(num_iters - done, cap)
            L = self._pick_block_len(nb)
            # a length whose program is not cached yet pays trace +
            # XLA compile inside this dispatch: billed to the
            # `gbdt.block_compile` span so compile and steady-state
            # wall-clock separate in the run summary (the bench's
            # compile_s / steady_s split reads exactly this)
            compiling = L not in self._block_fns
            fn = self._block_fn(L)
            self._gap_dispatch_start()
            with obs_span("gbdt.block_compile" if compiling
                          else "gbdt.block", iters=nb), \
                    tag("block") as tdone:
                args = (self.device_data, self._bins_t,
                        tuple(self._valid_device), self.scores,
                        tuple(self._valid_scores),
                        jnp.float32(self.shrinkage_rate),
                        jnp.int32(self.iter), jnp.int32(nb))
                (self.scores, vscores), trees = self._dispatch_retry(
                    fn, *args)
                self._gap_dispatch_done()
                self._valid_scores = list(vscores)
                tdone(trees.num_leaves)
            if compiling:
                # static XLA cost model (gated on LGBM_TPU_PROFILE /
                # LGBM_TPU_COST_MODEL: one extra lower+compile per
                # program length, acceptable in an explicit profiling
                # run) — FLOPs/bytes per block program for the
                # device_attribution roofline columns
                from ..obs import profiler as obs_profiler
                obs_profiler.record_program_cost(
                    f"gbdt.block[{L}]", fn, args,
                    module_hint="jit_block", iters=int(nb))
            # init-score bias rides the pending entry and is baked into
            # the first K host trees at flush (no separate per-iteration
            # bias-bake dispatch, which cost a whole extra XLA program)
            bias = (self.init_score_value
                    if (self._num_models() == 0
                        and abs(self.init_score_value) > 1e-15) else 0.0)
            self._pending.append((trees, self.shrinkage_rate, bias, nb * K))
            self.iter += nb
            self._stacked_cache = None
            done += nb
            nl = trees.num_leaves[nb - 1]
            if not speculate:
                stopped = self._check_block_stump(nl, rollback=0)
                continue
            try:
                nl.copy_to_host_async()
            # tpulint: disable=TPL006 -- prefetch-only; sync fetch follows
            except Exception:              # noqa: BLE001 - CPU backends
                pass
            if prev_check is not None:
                stopped = self._check_block_stump(prev_check, rollback=1)
            prev_check = nl
        if not stopped and prev_check is not None:
            stopped = self._check_block_stump(prev_check, rollback=0)
        return stopped

    def _check_block_stump(self, nl, rollback: int) -> bool:
        """Resolve an async stump check; on stop, drop the last
        ``rollback`` pending blocks (dispatched before the check
        resolved — all stumps, zero score contribution)."""
        last_nl = np.atleast_1d(jax.device_get(nl))
        if not all(int(x) <= 1 for x in last_nl):
            return False
        K = max(1, self.num_tree_per_iteration)
        for _ in range(min(rollback, len(self._pending))):
            _, _, _, cnt = self._pending.pop()
            self.iter -= cnt // K
        self.trim_trailing_stumps()
        log_warning(
            "stopped training because there are no more leaves "
            f"that meet the split requirements (iteration "
            f"{self.iter + 1})")
        obs_event("train_stop", "no_more_splits", iteration=self.iter)
        return True

    # ------------------------------------------------------------------
    def train(self, num_iterations: Optional[int] = None,
              callbacks: Sequence = ()) -> None:
        """Full training loop with early stopping + snapshots
        (reference GBDT::Train gbdt.cpp:309-327 + Application::Train).

        Under ``LGBM_TPU_TRACE_CONTRACT=1`` the whole loop runs inside a
        :class:`~lightgbm_tpu.obs.trace_contract.CompileTracker`: the
        first window is warmup, everything after must hit the trace
        cache — the report lands in the telemetry summary's
        ``trace_contract`` section (background block-length upgrades
        are counted separately, not as violations).

        Under ``LGBM_TPU_PROFILE=<dir>`` the loop additionally runs a
        WINDOWED device-time capture (``obs/profiler.py``): the first
        window is warmup, the next N windows are profiled, and the
        parsed per-span device-time / host-gap / roofline report lands
        in the summary's ``device_attribution`` section mid-train.

        Under ``LGBM_TPU_DETERMINISM=1`` every window boundary samples
        a canonical model/score digest into the ``determinism`` summary
        section (``obs/determinism.py``), the digest rides the multi-
        process ES sync as a cross-rank consistency check, and every
        keyed RNG derivation site counts into the RNG ledger — the
        runtime reproducibility contract the ``tools/replay_check.py``
        train-twice harness asserts on."""
        from ..obs import determinism, health, num_contract, ops_plane
        from ..obs.mem_contract import maybe_watermark
        from ..obs.profiler import maybe_profile
        from ..obs.trace_contract import maybe_track
        if determinism.enabled() and not self._resumed:
            # a fresh train() starts a fresh ledger; a resumed run keeps
            # accumulating so its digest stream continues the dead run's
            determinism.reset()
        if num_contract.enabled() and not self._resumed:
            # same fresh/resumed ledger discipline for the ulp contract
            num_contract.reset()
        # live ops plane (obs/ops_plane.py, LGBM_TPU_OPS_PORT): mount
        # the /metrics + /healthz scrape surface for this run; warming
        # until the first window lands (mark_ready below).  Host-side
        # only — zero device dispatches, zero recompiles (pinned by
        # tests/test_ops_plane.py).  The stall watchdog
        # (LGBM_TPU_WATCHDOG_S) arms around each window in _train.
        ops_plane.mount("train")
        wd = health.Watchdog.maybe("train")
        self._watchdog = wd
        # resolve the sentinel knob up front: LGBM_TPU_SENTINELS=1
        # activates the health plane even without an ops-plane mount,
        # so the warming->ready transitions below are live for it
        health.sentinels_enabled()
        health.mark_warming("train")
        try:
            with obs_span("gbdt.train"), maybe_track() as tracker, \
                    maybe_watermark("gbdt") as wm, \
                    maybe_profile("gbdt", sync=self._sync_pending) as prof:
                self._trace_tracker = tracker
                self._mem_watermark = wm
                self._profiler = prof
                try:
                    self._train(num_iterations, callbacks)
                finally:
                    self._trace_tracker = None
                    self._mem_watermark = None
                    self._profiler = None
        finally:
            self._watchdog = None
            if wd is not None:
                wd.stop()
        from ..obs import enabled as obs_enabled, gauge_set
        if obs_enabled():
            gauge_set("gbdt.iterations", int(self.iter))
            gauge_set("gbdt.num_trees", int(self._num_models()))
            from ..obs import summary as obs_summary
            c = obs_summary()["counters"]
            gaps = c.get("gbdt.dispatch_gaps", 0)
            if gaps:
                # the ROADMAP item-1 host-latency signal, live on EVERY
                # telemetry run — profiling off included
                gauge_set("gbdt.dispatch_gap_mean_s",
                          c.get("gbdt.dispatch_gap_s", 0.0) / gaps)

    def _sync_pending(self) -> None:
        """Block on in-flight device work (profile-capture hygiene:
        a stopped trace must contain the captured windows' ops).  Host
        code, not traced — the sync is the point."""
        jax.block_until_ready(self.scores)

    # -- dispatch-gap accounting (ROADMAP item 1) -----------------------
    def _gap_dispatch_start(self) -> None:
        """Called right before a training dispatch: the host time since
        the PREVIOUS dispatch returned, summed into the
        ``gbdt.dispatch_gap_s`` counter (mean gauge at end of train).
        NOT device idle time: a dispatch returns as soon as the block
        is enqueued, so wherever the caller waits on the result between
        blocks (the stump check at the end of ``train_block``, a
        ``block_until_ready``, an eval) the gap holds the device's whole
        run time of the block.  On the chip it read 2.22 s a gap beside
        14 ms of idle in the same two steps' trace (PERF.md, PR 26);
        idle time is read from a profiler trace."""
        from ..obs import enabled as obs_enabled
        t = self._t_dispatch_ret
        if t is not None and obs_enabled():
            counter_add("gbdt.dispatch_gap_s", time.perf_counter() - t)
            counter_add("gbdt.dispatch_gaps")

    def _gap_dispatch_done(self) -> None:
        self._t_dispatch_ret = time.perf_counter()

    def _train(self, num_iterations: Optional[int],
               callbacks: Sequence) -> None:
        from ..obs import determinism as _det
        from ..obs import health as _health
        from ..obs import num_contract as _num
        c = self.config
        iters = num_iterations or c.num_iterations
        # ES bookkeeping is INSTANCE state since the fault-tolerance
        # round: snapshots persist it and a resumed run keeps counting
        # stall rounds exactly where the dead run stood.  A fresh (non-
        # resumed) train() starts clean, as the old local dicts did.
        if not self._resumed:
            self._es_state = {"best_scores": {}, "best_iter": {},
                              "key_order": []}
        best_scores: Dict[str, float] = self._es_state["best_scores"]
        best_iter: Dict[str, int] = self._es_state["best_iter"]
        key_order: List[str] = self._es_state["key_order"]
        want_eval = bool(self.metrics
                         and (c.is_training_metric or self.valid_sets))
        es_on = c.early_stopping_round > 0 and bool(self.valid_sets)
        # output_freq silences PRINTING; early stopping still needs the
        # evals (the reference evaluates every iteration and prints
        # every output_freq, gbdt.cpp:492+)
        eval_freq = c.output_freq
        if eval_freq <= 0 and es_on:
            eval_freq = 1
        stopped_early = False
        if want_eval:
            self._compile_evals()
        # resumed: num_iterations is the dead run's TOTAL target and
        # self.iter sits mid-run — continue from there, keeping window
        # boundaries (eval/snapshot cadence) aligned with the original
        it = self.iter if self._resumed else 0
        while it < iters:
            # window to the next eval/snapshot boundary, run as one block
            window = iters - it
            if eval_freq > 0 and want_eval:
                window = min(window, eval_freq - (it % eval_freq))
            if c.snapshot_freq > 0:
                window = min(window, c.snapshot_freq - (it % c.snapshot_freq))
            prof = getattr(self, "_profiler", None)
            if prof is not None:
                # live device-time capture: bound windows so the
                # warmup/capture boundaries fall every few iterations
                # (a fused 500-iteration window would never hand the
                # profiler a post-warmup boundary to start at)
                window = prof.clamp_window(window)
            t0 = time.time()
            # stall watchdog (obs/health.py, LGBM_TPU_WATCHDOG_S):
            # armed around the window's dispatches; on expiry the
            # monitor thread names the active span in a health:stall
            # event + kill-survivable forensic dump while this thread
            # is still wedged.  watchdog.stall fault = synthetic hang.
            wd = self._watchdog
            if wd is not None:
                wd.arm("gbdt.block" if self._can_block()
                       else "gbdt.iteration",
                       it=int(it), window=int(window))
                _health.stall_fault(wd)
            try:
                if self._can_block():
                    # window == 1 (per-iteration eval cadence, the
                    # default with early stopping) STAYS on the fused
                    # path as a length-1 block program: one device
                    # dispatch carrying gradients → tree → score +
                    # valid-score updates, with the eval below reading
                    # the block-returned valid scores.  The old
                    # `window > 1` guard dropped to the unfused
                    # per-iteration path here — ~32 host-synced waves
                    # per iteration (the with-valid pathology of VERDICT
                    # r5 Weak #2; the length-1 block is unverified on a
                    # local chip).
                    stop = self.train_block(window)
                    if _det.enabled():
                        # the fused block derives its masks INSIDE the
                        # scan from the same (seed, step) keys: ledger
                        # one derivation per masked iteration/tree
                        if c.bagging_freq > 0 and c.bagging_fraction < 1.0:
                            _det.rng_site("gbdt.bag_mask",
                                          "bagging_seed/epoch", n=window)
                        if c.feature_fraction < 1.0:
                            _det.rng_site(
                                "gbdt.feature_mask",
                                "feature_fraction_seed/tree_idx",
                                n=window * self.num_tree_per_iteration)
                    it = self.iter if stop else it + window
                else:
                    stop = self.train_one_iter()
                    it += 1
            finally:
                if wd is not None:
                    wd.disarm()
            # first window done == warmup over (idempotent; see train())
            tracker = getattr(self, "_trace_tracker", None)
            if tracker is not None:
                tracker.mark_steady()
            # /healthz: warming -> ready once the first window (compile
            # included) lands; sticky stalled/degraded never downgrade
            _health.mark_ready()
            if prof is not None:
                # window boundary: warmup -> start capture -> after N
                # windows stop + parse + attach device_attribution.
                # A boundary that did heavy profiler work (trace
                # start/stop+parse) must not bill itself to the next
                # window's dispatch-gap counter
                if prof.window(it=int(it)):
                    self._t_dispatch_ret = None
            # mem.leak fault: grow a module-lifetime sink by one fresh
            # device buffer per window (the leak class the watermark
            # contract catches; it != 0 defeats constant folding)
            from ..utils.faults import fault_flag
            if fault_flag("mem.leak"):
                # memcheck: disable=MEM005 -- intentional fault-
                # injection leak sink, armed only by chaos/tier-1 tests
                _MEM_LEAK_SINK.append(
                    jnp.full((_MEM_LEAK_ELEMS,), float(it), jnp.float32))
            wm = getattr(self, "_mem_watermark", None)
            if wm is not None:
                # one sample per window boundary: the leak gate
                wm.sample("gbdt.window", it=int(it))
                if self._donate_active:
                    # donation-effectiveness: the in-place score update
                    # must keep exactly ONE live [n, K] f32 set
                    wm.check_donation(self.scores.shape,
                                      self.scores.dtype, expected=1)
            if _det.enabled():
                # reproducibility contract: one canonical model/score
                # digest per window boundary (obs/determinism.py) —
                # flushing pending device trees costs one batched
                # device_get per window, paid only under the contract
                _det.window_digest(self, int(it))
            if _health.sentinels_enabled() or _num.enabled():
                # ONE score fetch shared by two consumers — a host
                # fetch like the eval below, zero extra device
                # dispatches: the non-finite sentinel (obs/health.py;
                # a NaN grad/hess poisons the scores it folds into, so
                # this names the window) and the ulp-drift contract
                # (obs/num_contract.py: canonical f32 root-sum vs the
                # f64 host oracle over the same fetched bytes).
                s_np = (self._pr.local_np(self.scores)
                        if self._pr is not None
                        else np.asarray(self.scores))
                if _health.sentinels_enabled():
                    _health.check_scores(s_np, window=int(it))
                if _num.enabled():
                    _num.window_check(s_np, it=int(it))
            if stop:
                break
            if want_eval and eval_freq > 0 and it % eval_freq == 0:
                results = []
                with obs_span("gbdt.eval", it=it):
                    if c.is_training_metric:
                        results.extend(self.eval_train())
                    results.extend(self.eval_valid())
                if self._pr is not None and results:
                    # rank-identical stop decisions (r4 weak #3): local
                    # metric values can differ across ranks (training
                    # metric over the local shard; float ties) — every
                    # rank adopts rank 0's values before deciding, the
                    # way the reference pins decisions to identical
                    # synced state (application.cpp:249-254)
                    from ..io.distributed import jax_process_allgather
                    from ..obs import flight_recorder
                    # the metric sync doubles as the window-boundary
                    # schedule cross-check: every rank's collective
                    # flight-recorder fingerprint rides the SAME
                    # allgather (zero extra collectives; a mismatch
                    # takes the rare second gather to localize the
                    # first diverging site+rank — see
                    # obs/flight_recorder.py)
                    gathered = jax_process_allgather(
                        {"vals": [float(r[2]) for r in results],
                         "fr": flight_recorder.fingerprint(),
                         "det": _det.fingerprint()})
                    vals = gathered[0]["vals"]
                    flight_recorder.window_check(
                        [g["fr"] for g in gathered],
                        allgather=jax_process_allgather)
                    # the model is replicated state: every rank's window
                    # digest must agree (obs/determinism.py; the digest
                    # rode the SAME gather — zero extra collectives)
                    _det.window_check([g["det"] for g in gathered],
                                      it=int(it))
                    results = [(n, m, float(v), h) for (n, m, _, h), v
                               in zip(results, vals)]
                if _health.sentinels_enabled():
                    # loss-spike + non-finite-metric sentinels over the
                    # values this boundary already computed
                    _health.check_metrics(results, window=int(it))
                if c.output_freq > 0 and it % c.output_freq == 0:
                    msgs = [f"{name} {mname} : {val:.6f}"
                            for name, mname, val, hib in results]
                    if msgs:
                        log_info(f"[{it}]\t" + "\t".join(msgs)
                                 + f"\t({time.time() - t0:.3f}s)")
                # early stopping on valid metrics: ANY single metric
                # stalling for early_stopping_round triggers the stop
                # (reference EvalAndCheckEarlyStopping / the python
                # callback, callback.py:142+ — round 4's all-metrics
                # rule could train forever on one still-improving
                # metric, review r5)
                if es_on:
                    for name, mname, val, hib in results:
                        if name == "training":
                            continue
                        key = f"{name}:{mname}"
                        if key not in key_order:
                            key_order.append(key)
                        better = (val > best_scores.get(key, -np.inf) if hib
                                  else val < best_scores.get(key, np.inf))
                        if better:
                            best_scores[key] = val
                            best_iter[key] = it
                    stalled = next(
                        (k for k in key_order
                         if it - best_iter[k] >= c.early_stopping_round),
                        None)
                    if stalled is not None:
                        self.best_iteration = best_iter[stalled]
                        for key, val in best_scores.items():
                            nm, mname = key.split(":", 1)
                            self.best_score.setdefault(nm, {})[mname] = val
                        log_info(f"early stopping at iteration {it}, "
                                 f"best iteration {self.best_iteration}")
                        obs_event("early_stop", stalled, iteration=it,
                                  best_iteration=self.best_iteration)
                        stopped_early = True
                        break
            if c.snapshot_freq > 0 and it % c.snapshot_freq == 0:
                self.save_snapshot(it)
        if not stopped_early and es_on and key_order:
            # the stall window never elapsed: still report the best seen
            # (the python callback raises at the final iteration with
            # the first metric's best, callback.py:113-117)
            self.best_iteration = best_iter[key_order[0]]
            for key, val in best_scores.items():
                nm, mname = key.split(":", 1)
                self.best_score.setdefault(nm, {})[mname] = val
        self.trim_trailing_stumps()

    def trim_trailing_stumps(self) -> None:
        """Drop trailing all-stump iterations (the per-iteration stop check
        only runs every `_sync_freq` iterations on remote devices, so a run
        can end with undetected stump trees; reference pops them,
        gbdt.cpp:462-468)."""
        K = self.num_tree_per_iteration
        if not self._pending and not self._host_models:
            return
        self._flush_pending()
        trimmed = 0
        while (len(self._host_models) >= K
               and all(t.num_leaves <= 1 for t in self._host_models[-K:])):
            self._host_models = self._host_models[:-K]
            self.iter -= 1
            trimmed += 1
        if trimmed:
            self._stacked_cache = None
            log_warning(f"dropped {trimmed} trailing iteration(s) with no "
                        f"splittable leaves")

    # -- snapshot / resume (fault tolerance) ----------------------------
    def save_snapshot(self, iteration: Optional[int] = None) -> Optional[str]:
        """Write an atomic snapshot (model + f32 score state + manifest)
        and prune to ``snapshot_keep`` (see ``boosting/snapshot.py``).

        Multi-process: rank 0 writes (every rank used to race the same
        path), under a cross-rank COMMIT BARRIER — ranks first publish
        ``(iteration, model_digest)`` over the host collective and the
        write proceeds only when every rank reports the same pair (a
        desynced mesh must not commit a snapshot that only rank 0's
        model matches); a second collective after the write keeps
        non-zero ranks from racing past an uncommitted manifest."""
        it = self.iter if iteration is None else iteration
        if jax.process_count() > 1:
            from ..io.distributed import jax_process_allgather
            return self._snapshot_barrier(it, jax_process_allgather,
                                          jax.process_index())
        from .snapshot import write_snapshot
        return write_snapshot(self, it)

    def _snapshot_barrier(self, iteration: int, allgather,
                          rank: int) -> Optional[str]:
        """The commit-barrier protocol, parameterized over the
        collective so tier-1 pins it in-process (ThreadedAllgather)."""
        from ..obs import event
        from .snapshot import write_snapshot
        d = self.digest(include_scores=False)
        acks = allgather({"iteration": int(iteration), "digest": d})
        if any(a != acks[0] for a in acks[1:]):
            event("elastic", "barrier_mismatch", iteration=int(iteration),
                  acks=len(acks))
            raise RuntimeError(
                f"snapshot commit barrier at iteration {iteration} "
                f"refused: ranks disagree on (iteration, digest): {acks}")
        path = None
        if rank == 0:
            path = write_snapshot(self, iteration)
        # commit confirmation: no rank proceeds (or treats the snapshot
        # as durable) until rank 0's manifest is on disk
        allgather({"committed": int(iteration)})
        return path

    def resume_from_snapshot(self, path_or_dir: str) -> int:
        """Restore trees, scores, and early-stopping state from the
        latest VALID snapshot under ``path_or_dir`` (a manifest path, a
        snapshot model path, an ``output_model`` prefix, or a
        directory), so a subsequent ``train(total_target)`` continues
        exactly where the dead run died.  Returns the restored
        iteration.

        Scores restore bit-for-bit from the snapshot's f32 state
        sidecar when present (the resumed run is then numerically
        IDENTICAL to an uninterrupted one); without a usable sidecar
        they are replayed from the restored trees — a last-ulp
        approximation, warned about."""
        from .snapshot import resolve_snapshot, config_hash
        with obs_span("snapshot.resume"):
            return self._resume_from_snapshot(path_or_dir, resolve_snapshot,
                                              config_hash)

    def _resume_from_snapshot(self, path_or_dir, resolve_snapshot,
                              config_hash) -> int:
        manifest = resolve_snapshot(path_or_dir)
        if manifest is None:
            raise FileNotFoundError(
                f"no valid snapshot found at {path_or_dir!r}")
        if self.train_set is None:
            raise ValueError("resume_from_snapshot needs a booster with "
                             "an attached training set")
        if manifest["config_hash"] != config_hash(self.config):
            log_warning("resuming with a DIFFERENT config than the "
                        "snapshot was written with; the continued run "
                        "will not match an uninterrupted one")
        # world-size-sensitive fields must MATCH the live mesh: a
        # 2-process snapshot resumed on 1 process (or vice versa) has a
        # different score layout and row sharding — refuse instead of
        # silently training on (older manifests lack the field: warn)
        snap_world = manifest.get("world_size")
        live_world = jax.process_count()
        if snap_world is None:
            if live_world > 1:
                log_warning("snapshot manifest predates world-size "
                            "tracking; cannot verify it matches this "
                            f"{live_world}-process mesh")
        elif int(snap_world) != live_world:
            raise ValueError(
                f"cannot resume: snapshot was written on a "
                f"{int(snap_world)}-process mesh, this run has "
                f"{live_world} process(es); re-shard via elastic "
                f"training (parallel/elastic.py) or restart training")

        from ..utils.file_io import open_read
        with open_read(manifest["model_path"]) as f:
            text = f.read()
        donor = GBDT(self.config, None)
        donor.load_model_from_string(text)
        if donor.num_tree_per_iteration != self.num_tree_per_iteration:
            raise ValueError("cannot resume: num_tree_per_iteration "
                             "differs between snapshot and config")
        fmap = {f: i for i, f in enumerate(self.train_set.used_features)}
        for t in donor.models:
            t.align_with_mappers(self.train_set.mappers, fmap)
        self.models = list(donor.models)
        self.iter = manifest["iteration"]
        self.init_score_value = manifest.get("init_score_value", 0.0)
        self._es_state = {
            "best_scores": dict(manifest.get("best_scores", {})),
            "best_iter": {k: int(v) for k, v in
                          manifest.get("best_iter", {}).items()},
            "key_order": list(manifest.get("key_order", []))}
        self._restore_scores(manifest)
        self.load_snapshot_extra_state(manifest.get("extra_state", {}))
        self._resumed = True
        self._stacked_cache = None
        log_info(f"resumed from snapshot {manifest['model_path']} at "
                 f"iteration {self.iter} ({len(self._host_models)} trees)")
        return self.iter

    def snapshot_extra_state(self) -> Dict:
        """Variant bookkeeping the snapshot manifest must carry beyond
        trees + scores + ES state (DART overrides with its per-tree
        drop weights); JSON-serializable."""
        return {}

    def load_snapshot_extra_state(self, extra: Dict) -> None:
        """Inverse of :meth:`snapshot_extra_state` on resume."""

    def _restore_scores(self, manifest: Dict) -> None:
        """Exact restore from the f32 sidecar when it fits this booster
        (same train shape, same attached valid sets); tree replay
        otherwise."""
        K = max(1, self.num_tree_per_iteration)
        state = None
        if manifest.get("state_path") and self._pr is None:
            state = np.load(manifest["state_path"])
            s = state.get("scores")
            want = (self.num_data, K)
            if s is None or s.shape != want:
                log_warning(f"snapshot score state has shape "
                            f"{None if s is None else s.shape}, booster "
                            f"needs {want}; replaying trees instead")
                state = None
        if state is not None:
            restored = np.asarray(state["scores"], np.float32)
            if self.mesh_ctx is not None:
                # registry placement (scores rule), like _init_train
                self.scores = self.mesh_ctx.place_scores(restored)
            else:
                self.scores = jax.device_put(restored)
            for i in range(len(self._valid_scores)):
                vs = state.get(f"valid_scores_{i}")
                if vs is not None and vs.shape == tuple(
                        self._valid_scores[i].shape):
                    self._valid_scores[i] = jnp.asarray(
                        np.asarray(vs, np.float32))
                else:
                    self._replay_valid_scores(i)
            return
        # fallback: replay restored trees (tree 0 carries the baked
        # init-score bias, so the replay starts from zero)
        self.scores = jnp.zeros_like(self.scores)
        for j, tree in enumerate(self._host_models):
            pred = self._predict_host_tree_binned(tree, self.device_data)
            self.scores = self.scores.at[:, j % K].add(pred)
        for i in range(len(self._valid_scores)):
            self._replay_valid_scores(i)

    def _replay_valid_scores(self, i: int) -> None:
        K = max(1, self.num_tree_per_iteration)
        vd = self._valid_device[i]
        score = jnp.zeros_like(self._valid_scores[i])
        for j, tree in enumerate(self._host_models):
            pred = self._predict_host_tree_binned(tree, vd)
            score = score.at[:, j % K].add(pred)
        self._valid_scores[i] = score

    # ------------------------------------------------------------------
    def num_trees(self) -> int:
        return self._num_models()

    @property
    def current_iteration(self) -> int:
        return self.iter

    def _stacked(self, dd_max_bins: int):
        if self._stacked_cache is None and self.models:
            self._stacked_cache = stack_trees(self.models, max_bins=dd_max_bins)
        return self._stacked_cache

    def predict_raw(self, X: np.ndarray, num_iteration: int = -1) -> np.ndarray:
        """Raw scores for a raw feature matrix (binned through the train
        mappers, then jitted stacked-tree traversal)."""
        if self.train_set is None:
            # loaded model without dataset: host-tree prediction
            return self._predict_loaded(X, num_iteration)
        valid = self.train_set.create_valid(np.asarray(X),
                                            prediction_mode=True)
        dd = to_device(valid)
        K = self.num_tree_per_iteration
        n = X.shape[0]
        T = len(self.models)
        if num_iteration is not None and num_iteration > 0:
            T = min(T, num_iteration * K)
        # init score is baked into tree 0 (AddBias), so start from zero
        out = np.zeros((n, K), np.float64)
        if T == 0:
            out += self.init_score_value
            return out if K > 1 else out[:, 0]
        if self.config is not None and self.config.pred_early_stop:
            return self._predict_raw_early_stop(dd, n, K, T)
        bundle_kw = self._bundle_kw(dd)
        # the matmul predictor (predict_binned_matmul): every node
        # decision at once + one path-agreement contraction — no gathers,
        # no depth loop.  The gather walk serializes depth x trees x rows
        # (minutes at 500 deep trees x 2e5 rows; long dispatches fault
        # the TPU worker).  Covers categorical splits (vectorized bitset
        # lookup) and >256-bin ids (f32 select einsums) since r4; only
        # EFB-bundled columns still take the chunked walk.
        use_matmul = not bundle_kw
        from ..models.tree import (build_path_matrices, predict_binned_matmul,
                                   predict_binned_chunked)
        tchunk = int(_os.environ.get("LGBM_TPU_PRED_TREE_CHUNK",
                                     16 if use_matmul else 128))
        rchunk = int(_os.environ.get("LGBM_TPU_PRED_ROW_CHUNK",
                                     4096 if use_matmul else 1 << 16))
        for k in range(K):
            idx = list(range(k, T, K))
            trees_k = [self.models[i] for i in idx]
            # mask width +2: the sentinel miss bin must index an
            # always-False slot (never clamp onto a real bin)
            sub = stack_trees(trees_k, max_bins=dd.max_bins + 2)
            if use_matmul:
                P, plen = build_path_matrices(trees_k)
                out[:, k] += np.asarray(predict_binned_matmul(
                    sub, jnp.asarray(P), jnp.asarray(plen), dd.bins,
                    dd.nan_bins, dd.default_bins, dd.missing_types,
                    tchunk=tchunk, rchunk=rchunk))
            else:
                out[:, k] += np.asarray(predict_binned_chunked(
                    sub, dd.bins, dd.nan_bins, dd.default_bins,
                    dd.missing_types, tchunk=tchunk, rchunk=rchunk,
                    **bundle_kw))
        return out if K > 1 else out[:, 0]

    def _predict_raw_early_stop(self, dd, n: int, K: int, T: int) -> np.ndarray:
        """Prediction early stopping (reference
        `src/boosting/prediction_early_stop.cpp:1-100`): every
        ``pred_early_stop_freq`` rounds, rows whose margin exceeds
        ``pred_early_stop_margin`` stop accumulating further trees.
        Margin: binary = 2*|score| (`:60`), multiclass = top1 - top2
        (`:38`).  Vectorized: trees run in round chunks over the
        still-active rows."""
        c = self.config
        freq = max(1, c.pred_early_stop_freq)
        margin = c.pred_early_stop_margin
        out = np.zeros((n, K), np.float64)
        active = np.ones(n, bool)
        rounds = -(-(T // K) // freq)
        bundle_kw = self._bundle_kw(dd)
        for r in range(rounds):
            if not active.any():
                break
            rows = np.nonzero(active)[0]
            # pad the active set to a power-of-two bucket: the jitted
            # tree walk compiles per row-count, and shrinking every
            # round would otherwise compile every round
            bucket = 1 << (len(rows) - 1).bit_length()
            rows_pad = np.resize(rows, bucket)
            bins_sub = dd.bins[rows_pad]
            for k in range(K):
                idx = [i for i in range(k, T, K)][r * freq:(r + 1) * freq]
                if not idx:
                    continue
                sub = stack_trees([self.models[i] for i in idx],
                                  max_bins=dd.max_bins + 2,
                                  pad_leaves=self.growth.num_leaves
                                  if self.train_set is not None else 0)
                out[rows, k] += np.asarray(predict_binned(
                    sub, bins_sub, dd.nan_bins, dd.default_bins,
                    dd.missing_types, **bundle_kw))[:len(rows)]
            if K == 1:
                stop = 2.0 * np.abs(out[rows, 0]) > margin
            else:
                part = np.partition(out[rows], K - 2, axis=1)
                stop = (part[:, K - 1] - part[:, K - 2]) > margin
            active[rows[stop]] = False
        return out if K > 1 else out[:, 0]

    def _predict_loaded(self, X, num_iteration=-1):
        X = np.asarray(X, np.float64)
        K = max(1, self.num_tree_per_iteration)
        T = len(self.models)
        if num_iteration is not None and num_iteration > 0:
            T = min(T, num_iteration * K)
        out = np.zeros((X.shape[0], K))
        for i in range(T):
            out[:, i % K] += self.models[i].predict_batch(X)
        return out if K > 1 else out[:, 0]

    def predict(self, X: np.ndarray, raw_score: bool = False,
                num_iteration: int = -1) -> np.ndarray:
        raw = self.predict_raw(X, num_iteration)
        if raw_score or self.objective is None:
            return raw
        if self.average_output:
            T = max(1, len(self.models) // max(1, self.num_tree_per_iteration))
            raw = raw / T
        return np.asarray(self.objective.convert_output(jnp.asarray(raw)))

    def predict_leaf(self, X: np.ndarray,
                     num_iteration: int = -1) -> np.ndarray:
        """Per-tree leaf indices (PredictLeafIndex).

        ``num_iteration`` truncation lives HERE — the same seam
        ``predict_raw`` uses — so every surface (``Booster.predict``,
        sklearn, C API, serve) slices identically, multiclass included
        (``num_iteration * num_tree_per_iteration`` trees), and the
        truncated trees are never stacked or walked at all."""
        from ..models.tree import predict_leaf_binned
        models = self.models
        if num_iteration is not None and num_iteration > 0:
            K = max(1, self.num_tree_per_iteration)
            models = models[:num_iteration * K]
        valid = (self.train_set.create_valid(np.asarray(X),
                                             prediction_mode=True)
                 if self.train_set is not None else None)
        if valid is None:
            Xf = np.asarray(X, np.float64)
            out = np.zeros((len(X), len(models)), np.int32)
            for i, t in enumerate(models):
                out[:, i] = t.predict_leaf_batch(Xf)
            return out
        dd = to_device(valid)
        st = stack_trees(models, max_bins=dd.max_bins + 2)
        return np.asarray(predict_leaf_binned(
            st, dd.bins, dd.nan_bins, dd.default_bins, dd.missing_types,
            **self._bundle_kw(dd)))

    # ------------------------------------------------------------------
    def digest(self, include_scores: bool = True) -> str:
        """Canonical model/score sha256 (the reproducibility contract's
        unit of comparison — see ``obs/determinism.py`` for the exact
        field canonicalization).  Two trainings from identical data,
        config, and seeds must produce identical digests; the bench
        stamps this on every model-training leg as ``model_digest``."""
        from ..obs import determinism
        return determinism.model_digest(self, include_scores=include_scores)

    # ------------------------------------------------------------------
    def feature_importance(self, importance_type: str = "split",
                           num_iteration: int = -1) -> np.ndarray:
        """Reference FeatureImportance (gbdt_model_text.cpp:284+)."""
        n = self.max_feature_idx + 1
        imp = np.zeros(n)
        T = len(self.models)
        if num_iteration and num_iteration > 0:
            T = min(T, num_iteration * self.num_tree_per_iteration)
        for t in self.models[:T]:
            for node in range(t.num_leaves - 1):
                f = int(t.split_feature[node])
                if importance_type == "split":
                    imp[f] += 1
                else:
                    imp[f] += max(0.0, float(t.split_gain[node]))
        return imp

    # -- model text IO (reference gbdt_model_text.cpp:235-315) -----------
    def save_model_to_string(self, num_iteration: int = -1) -> str:
        lines = [self.boosting_name if self.boosting_name != "gbdt" else "tree"]
        lines.append(f"version={K_MODEL_VERSION}")
        lines.append(f"num_class={self.num_class}")
        lines.append(f"num_tree_per_iteration={self.num_tree_per_iteration}")
        lines.append("label_index=0")
        lines.append(f"max_feature_idx={self.max_feature_idx}")
        if self.objective is not None:
            lines.append(f"objective={self.objective.to_string()}")
        if self.average_output:
            lines.append("average_output")
        lines.append("feature_names=" + " ".join(self.feature_names))
        lines.append("feature_infos=" + " ".join(self._feature_infos()))
        T = len(self.models)
        if num_iteration and num_iteration > 0:
            T = min(T, num_iteration * self.num_tree_per_iteration)
        tree_strs = [f"Tree={i}\n" + self.models[i].to_string() + "\n"
                     for i in range(T)]
        lines.append("tree_sizes=" + " ".join(str(len(s)) for s in tree_strs))
        lines.append("")
        body = "\n".join(lines) + "\n" + "".join(tree_strs)
        # feature importances footer
        imp = self.feature_importance("split", num_iteration)
        pairs = sorted([(int(imp[i]), self.feature_names[i])
                        for i in range(len(imp)) if imp[i] > 0],
                       key=lambda p: -p[0])
        body += "\nfeature importances:\n"
        body += "".join(f"{nm}={v}\n" for v, nm in pairs)
        return body

    def save_model(self, path: str, num_iteration: int = -1) -> None:
        from ..utils.file_io import open_write
        with open_write(path) as f:
            f.write(self.save_model_to_string(num_iteration))

    def load_model_from_string(self, text: str) -> None:
        """Reference LoadModelFromString (gbdt_model_text.cpp:317+)."""
        header, _, rest = text.partition("Tree=")
        kv: Dict[str, str] = {}
        for line in header.splitlines():
            line = line.strip()
            if "=" in line:
                k, v = line.split("=", 1)
                kv[k] = v
            elif line:
                kv[line] = ""
        self.num_class = int(kv.get("num_class", 1))
        self.num_tree_per_iteration = int(
            kv.get("num_tree_per_iteration", self.num_class))
        self.max_feature_idx = int(kv.get("max_feature_idx", 0))
        self.feature_names = kv.get("feature_names", "").split()
        self.average_output = "average_output" in kv
        obj_str = kv.get("objective", "")
        if obj_str and self.objective is None:
            name = obj_str.split()[0]
            params = dict(p.split(":", 1) for p in obj_str.split()[1:]
                          if ":" in p)
            cfg_params = {"objective": name}
            if "num_class" in params:
                cfg_params["num_class"] = int(params["num_class"])
            if "sigmoid" in params:
                cfg_params["sigmoid"] = float(params["sigmoid"])
            try:
                cfg = Config.from_params(cfg_params)
                self.objective = create_objective(cfg)
            except ValueError:
                self.objective = None
        self.models = []
        if rest:
            blocks = ("Tree=" + rest).split("Tree=")
            for blk in blocks:
                blk = blk.strip()
                if not blk or blk.startswith("feature importances"):
                    continue
                # strip the tree index line
                body = blk.split("\n", 1)[1] if "\n" in blk else ""
                body = body.split("feature importances:")[0]
                if "num_leaves=" in body:
                    self.models.append(Tree.from_string(body))
        self.iter = len(self.models) // max(1, self.num_tree_per_iteration)

    def _feature_infos(self) -> List[str]:
        if self.train_set is None:
            return ["none"] * (self.max_feature_idx + 1)
        infos = []
        for m in self.train_set.mappers:
            if m.is_trivial:
                infos.append("none")
            elif m.bin_type == 1:
                infos.append(":".join(str(c) for c in m.bin_2_categorical))
            else:
                infos.append(f"[{m.min_val!r}:{m.max_val!r}]")
        return infos

    # ------------------------------------------------------------------
    def refit_dataset(self, ds: BinnedDataset,
                      decay_rate: float = 0.9) -> None:
        """Re-estimate every tree's leaf values on a NEW dataset keeping
        the structures (reference RefitTree, gbdt.cpp:268-280 +
        application.cpp:293-318): attach the dataset, re-map each
        tree's thresholds through its mappers, and refit from the new
        rows' leaf assignments.  Shared by CLI task=refit and
        Booster.refit.  The EXISTING objective (e.g. parsed from the
        model header) is kept; one is created from the config only when
        none is set — a model loaded without params must not silently
        refit binary trees with the default regression gradients."""
        self.train_set = ds
        for t in self.models:
            t.align_with_mappers(
                ds.mappers, {f: i for i, f in enumerate(ds.used_features)})
        self.device_data = to_device(ds)
        self.num_data = ds.num_data
        if self.objective is None:
            self.objective = create_objective(self.config)
        self.objective.init(ds.metadata, ds.num_data)
        K = self.num_tree_per_iteration
        self.scores = jnp.zeros((ds.num_data, K), jnp.float32)
        from ..models.tree import predict_leaf_binned
        dd = self.device_data
        st = stack_trees(self.models, max_bins=dd.max_bins)
        pred_leaf = np.asarray(predict_leaf_binned(
            st, dd.bins, dd.nan_bins, dd.default_bins, dd.missing_types))
        self.refit(pred_leaf, decay_rate=decay_rate)

    def refit(self, pred_leaf: np.ndarray,
              decay_rate: float = 0.9) -> None:
        """Refit leaf outputs with new data (reference RefitTree
        gbdt.cpp:329-351 / FitByExistingTree + the python package's
        refit decay): ``new = decay_rate * old + (1 - decay_rate) *
        refit_output``; leaves no new row reaches keep their old output
        (a 0/0 would poison them with NaN for future rows).

        Sequential like the reference (ADVICE r4): the refit task
        (application.cpp:293-318) calls ``GBDT::Init`` with the new
        data, creating a FRESH ScoreUpdater — scores start at the
        dataset's init_score (or zero), with no old-model replay —
        then RefitTree's loop recomputes gradients at the current
        scores per iteration (``Boosting()``), refits that iteration's
        K trees, and ADDS each refitted tree's output to the scores
        (``AddScore``), so iteration i+1 fits the residual after
        refitted iteration i.  On exit ``self.scores`` equals the
        refitted model's prediction, preserving the invariant every
        other mutation path (rollback/merge/set_leaf_value) keeps."""
        K = self.num_tree_per_iteration
        models = self.models
        c = self.config
        n = pred_leaf.shape[0]
        scores_np = np.zeros((n, K), np.float32)
        ms = (self.train_set.metadata.init_score
              if self.train_set is not None else None)
        if ms is not None:
            # numcheck: disable=NUM002 -- same ingest cast as _boost
            # init: a data conversion at the model boundary
            scores_np = np.asarray(ms, np.float64).reshape(
                -1, K, order="F").astype(np.float32)
        for it in range(len(models) // K):
            self.scores = jnp.asarray(scores_np)
            grad, hess = self._gradients()
            g = np.asarray(grad)
            h = np.asarray(hess)
            for k in range(K):
                i = it * K + k
                tree = models[i]
                leaves = pred_leaf[:, i]
                nl = tree.num_leaves
                sg = np.zeros(nl)
                sh = np.zeros(nl)
                cnt = np.zeros(nl)
                np.add.at(sg, leaves, g[:, k])
                np.add.at(sh, leaves, h[:, k])
                np.add.at(cnt, leaves, 1.0)
                old = np.asarray(tree.leaf_value[:nl], np.float64)
                with np.errstate(divide="ignore", invalid="ignore"):
                    out = (-(np.sign(sg)
                             * np.maximum(np.abs(sg) - c.lambda_l1, 0.0))
                           / (sh + c.lambda_l2))
                new_vals = np.where(
                    cnt > 0,
                    decay_rate * old
                    + (1.0 - decay_rate) * out * self.shrinkage_rate,
                    old)                # untouched leaf keeps its output
                for l in range(nl):
                    tree.set_leaf_output(l, float(new_vals[l]))
                # AddScore: the refitted tree's output joins the scores
                # the NEXT iteration's gradients see
                scores_np[:, k] += np.asarray(
                    tree.leaf_value[:nl], np.float32)[leaves]
        self.scores = jnp.asarray(scores_np)
        self._stacked_cache = None
