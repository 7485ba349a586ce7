"""DART, GOSS, and Random Forest boosting variants.

TPU-native counterparts of the reference subclasses
(`/root/reference/src/boosting/dart.hpp`, `goss.hpp`, `rf.hpp`; factory
`boosting.cpp:30-63`).
"""
from __future__ import annotations

import os
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..learner.serial import build_tree
from ..utils.log import log_info
from .gbdt import GBDT


def _dart_host_rng() -> bool:
    """``LGBM_TPU_DART_HOST_RNG=1`` restores the legacy STATEFUL
    ``np.random.RandomState`` drop stream (pre-PR 12).  The default is
    the pure ``(drop_seed, iteration)``-keyed derivation below: replay-
    stable across resume-from-snapshot (the RandomState stream depended
    on how many draws the dead run had consumed) and rank-identical by
    construction — the DET001 fix that unblocks multi-process DART
    (ROADMAP item 5).  The hatch exists for A/B against the legacy
    stream; parity is pinned by tests/test_determinism.py (registered
    as the `dart-keyed-vs-host-rng` seam in the detcheck parity
    registry)."""
    return os.environ.get("LGBM_TPU_DART_HOST_RNG", "0") == "1"


def _drop_uniforms(drop_seed: int, it: int) -> Tuple[float, np.ndarray]:
    """The keyed drop draws for iteration ``it``: one skip-drop uniform
    plus ``it`` per-past-iteration uniforms, a pure function of
    ``(drop_seed, it)`` via ``jax.random.fold_in`` — the same sanctioned
    idiom as the bagging/feature masks (gbdt.py).  The vector draw is
    padded to the next power of two so the eager uniform program
    compiles O(log iterations) times, not per iteration (trace-contract
    hygiene); the pad values are never read."""
    import jax
    key = jax.random.fold_in(jax.random.PRNGKey(drop_seed), it)
    u_skip = float(jax.random.uniform(jax.random.fold_in(key, 0)))
    pad = 1
    while pad < it:
        pad *= 2
    u = np.asarray(jax.random.uniform(jax.random.fold_in(key, 1), (pad,)))
    return u_skip, u[:it]


class DART(GBDT):
    """Dropout trees (reference dart.hpp:23-199).

    Per iteration (exact reference flow, ``DroppingTrees``/``Normalize``):
    a random subset of past *iterations* is dropped from the training
    score; the new tree is trained with shrinkage ``lr/(1+k)`` (or
    ``lr/(lr+k)`` in xgboost mode); afterwards each dropped tree is
    rescaled to ``k/(k+1)`` (resp. ``k/(k+lr)``) of its old weight and the
    train/valid scores are patched accordingly."""

    boosting_name = "dart"

    def __init__(self, config: Config, train_set, objective=None, fobj=None):
        super().__init__(config, train_set, objective, fobj)
        self._rng_drop = None
        if _dart_host_rng():
            # detcheck: disable=DET001 -- legacy escape hatch
            # (LGBM_TPU_DART_HOST_RNG=1): the stateful pre-PR 12 stream,
            # kept for A/B against the keyed derivation; NOT replay- or
            # rank-stable, documented as such in README "Determinism"
            self._rng_drop = np.random.RandomState(config.drop_seed)
        self._tree_weights: list = []   # per-iteration DART weight
        self._sum_weight = 0.0

    def train_one_iter(self, grad=None, hess=None) -> bool:
        c = self.config
        K = self.num_tree_per_iteration
        lr = c.learning_rate
        drop_iters = self._select_drop()
        k = float(len(drop_iters))
        # The WHOLE drop set's contribution in ONE stacked-predict
        # dispatch per class / valid set (stacked trees sum outputs),
        # reused for both the drop and the renormalize patch — the
        # reference patches scores in one pass the same way
        # (dart.hpp:146-186); the r4 per-tree loop was O(drops) host
        # dispatches per iteration (VERDICT r5 #9; what they cost is
        # unverified on a local chip).  All dropped trees
        # share one ``factor``, so only the summed prediction is needed.
        drop_tp = [None] * K
        drop_vp = [[None] * len(self._valid_device) for _ in range(K)]
        if k:
            for cls in range(K):
                trees = [self.models[di * K + cls] for di in drop_iters]
                tp = self._predict_host_trees_binned(trees,
                                                     self.device_data)
                drop_tp[cls] = tp
                self.scores = self.scores.at[:, cls].add(-tp)
                for vi, vd in enumerate(self._valid_device):
                    drop_vp[cls][vi] = self._predict_host_trees_binned(
                        trees, vd)
        # new-tree shrinkage (dart.hpp:127-134)
        if not c.xgboost_dart_mode:
            self.shrinkage_rate = lr / (1.0 + k)
        else:
            self.shrinkage_rate = lr if k == 0 else lr / (lr + k)
        finished = super().train_one_iter(grad, hess)
        if finished:
            return True
        # Normalize (dart.hpp:146-186): dropped tree weight *= factor;
        # train score had it fully removed -> add back factor * pred;
        # valid score still holds it fully -> add (factor - 1) * pred.
        factor = (k / (k + 1.0)) if not c.xgboost_dart_mode else (
            k / (k + lr) if k > 0 else 1.0)
        if k:
            for cls in range(K):
                self.scores = self.scores.at[:, cls].add(
                    factor * drop_tp[cls])
                for vi in range(len(self._valid_device)):
                    self._valid_scores[vi] = self._valid_scores[vi].at[
                        :, cls].add((factor - 1.0) * drop_vp[cls][vi])
        for di in drop_iters:
            for cls in range(K):
                self.models[di * K + cls].shrinkage(factor)
            if not c.uniform_drop:
                self._sum_weight -= self._tree_weights[di] * (
                    1.0 / (k + 1.0) if not c.xgboost_dart_mode
                    else 1.0 / (k + lr))
                self._tree_weights[di] *= factor
        if not c.uniform_drop:
            self._tree_weights.append(self.shrinkage_rate)
            self._sum_weight += self.shrinkage_rate
        self._stacked_cache = None
        return False

    def snapshot_extra_state(self) -> dict:
        # per-tree DART weights: with the keyed drop RNG these are the
        # ONLY bookkeeping a resume needs beyond trees+scores for a
        # weighted-drop run to continue bit-for-bit
        return {"dart_tree_weights": [float(w) for w in self._tree_weights],
                "dart_sum_weight": float(self._sum_weight)}

    def load_snapshot_extra_state(self, extra: dict) -> None:
        if "dart_tree_weights" in extra:
            self._tree_weights = [float(w)
                                  for w in extra["dart_tree_weights"]]
            self._sum_weight = float(extra.get("dart_sum_weight", 0.0))

    def _select_drop(self) -> np.ndarray:
        """Reference DroppingTrees (dart.hpp:85-125): per-iteration Bernoulli
        with rate drop_rate (weight-scaled unless uniform_drop).

        Default path: draws come from :func:`_drop_uniforms`, pure in
        ``(drop_seed, self.iter)`` — identical expected drop-count
        semantics (same Bernoulli rates, same in-order ``max_drop``
        cap), but byte-stable across resume-from-snapshot and across
        ranks.  ``LGBM_TPU_DART_HOST_RNG=1`` keeps the legacy stream."""
        c = self.config
        iters = self.iter
        if self._rng_drop is not None:
            return self._select_drop_host(iters)
        if iters == 0:
            return np.zeros(0, np.int64)
        from ..obs import determinism
        determinism.rng_site("dart.drop", "drop_seed/iteration")
        u_skip, u = _drop_uniforms(c.drop_seed, iters)
        from ..utils.faults import fault_flag
        if fault_flag("det.rng_drift"):
            # injected RNG drift: consume the NEXT iteration's draws in
            # place of this one's — the silent divergence class the
            # determinism contract (window digests) must localize
            u_skip, u = _drop_uniforms(c.drop_seed, iters + 1)
            u = u[:iters]
        if u_skip < c.skip_drop:
            return np.zeros(0, np.int64)
        return self._drop_from_uniforms(u, iters)

    def _drop_from_uniforms(self, u: np.ndarray, iters: int) -> np.ndarray:
        c = self.config
        out = []
        if not c.uniform_drop and self._sum_weight > 0:
            inv_avg = len(self._tree_weights) / self._sum_weight
            rate = c.drop_rate
            if c.max_drop > 0:
                rate = min(rate, c.max_drop * inv_avg / self._sum_weight)
            for i in range(iters):
                if u[i] < rate * self._tree_weights[i] * inv_avg:
                    out.append(i)
                    if c.max_drop > 0 and len(out) >= c.max_drop:
                        break
        else:
            rate = c.drop_rate
            if c.max_drop > 0:
                rate = min(rate, c.max_drop / max(1.0, float(iters)))
            for i in range(iters):
                if u[i] < rate:
                    out.append(i)
                    if c.max_drop > 0 and len(out) >= c.max_drop:
                        break
        return np.asarray(out, np.int64)

    def _select_drop_host(self, iters: int) -> np.ndarray:
        """The pre-PR 12 stream, VERBATIM (escape hatch): sequential
        ``RandomState`` draws, including the early ``max_drop`` break
        that stops consuming draws — byte-compatible with models
        trained before the migration."""
        c = self.config
        if iters == 0 or self._rng_drop.rand() < c.skip_drop:
            return np.zeros(0, np.int64)
        out = []
        if not c.uniform_drop and self._sum_weight > 0:
            inv_avg = len(self._tree_weights) / self._sum_weight
            rate = c.drop_rate
            if c.max_drop > 0:
                rate = min(rate, c.max_drop * inv_avg / self._sum_weight)
            for i in range(iters):
                if self._rng_drop.rand() < rate * self._tree_weights[i] * inv_avg:
                    out.append(i)
                    if c.max_drop > 0 and len(out) >= c.max_drop:
                        break
        else:
            rate = c.drop_rate
            if c.max_drop > 0:
                rate = min(rate, c.max_drop / max(1.0, float(iters)))
            for i in range(iters):
                if self._rng_drop.rand() < rate:
                    out.append(i)
                    if c.max_drop > 0 and len(out) >= c.max_drop:
                        break
        return np.asarray(out, np.int64)


def _abs_grad_importance(G, H):
    """GOSS per-row importance: sum over classes of ``|g*h|``.

    The class axis K is never partitioned (rows shard, classes
    replicate) and the importance only RANKS rows, so the operand order
    is partition-independent — registered as a sanctioned numcheck
    context (tools/numcheck/reduction_registry.py)."""
    return jnp.sum(jnp.abs(G * H), axis=1)


class GOSS(GBDT):
    """Gradient-based One-Side Sampling (reference goss.hpp:36-214): keep
    the top `top_rate` rows by |grad·hess|, sample `other_rate` of the rest
    and amplify their gradients by (1-a)/b.

    The sampling is a pure jnp transform of (gradients, iteration), so
    it runs INSIDE the fused ``lax.scan`` block (`_block_sample`) —
    GOSS configs keep the single-dispatch fast path; the per-iteration
    override below uses the identical derivation (same
    (seed, iteration)-keyed Bernoulli draw), so both paths build the
    same trees."""

    boosting_name = "goss"
    _goss_mp_sample = None

    def _block_sample(self, G, H, it, valid=None, orig_idx=None):
        import jax
        c = self.config
        a, b = c.top_rate, c.other_rate
        # top_k counts REAL rows: under multi-process sharding the
        # global row axis carries per-block padding whose (0, 0)
        # gradients must not dilute the threshold
        n_real = (self._pr.n_global if self._pr is not None
                  else self.num_data)
        top_k = max(1, int(n_real * a))
        # importance = sum over classes of |g*h| (goss.hpp BaggingHelper)
        imp = _abs_grad_importance(G, H)
        if valid is not None:
            imp = jnp.where(valid, imp, -1.0)
        threshold = jnp.sort(imp)[-top_k]
        is_top = imp >= threshold
        key = jax.random.fold_in(jax.random.PRNGKey(c.bagging_seed), it)
        if orig_idx is None:
            rnd = jax.random.uniform(key, imp.shape)
        else:
            # the mod-rank layout PERMUTES rows: draw in ORIGINAL row
            # order and gather through the layout map, so a distributed
            # run samples the identical row set as a serial run on the
            # same data (padding slots hit the trailing 1.0, never
            # selected)
            rnd = jnp.concatenate(
                [jax.random.uniform(key, (n_real,)),
                 jnp.ones(1)])[orig_idx]
        is_other = (~is_top) & (rnd < b / max(1e-12, 1.0 - a))
        if valid is not None:
            is_top = is_top & valid
            is_other = is_other & valid
        multiplier = (1.0 - a) / max(b, 1e-12)
        scale = jnp.where(is_other, multiplier, 1.0)[:, None]
        return G * scale, H * scale, is_top | is_other

    def train_one_iter(self, grad=None, hess=None) -> bool:
        if grad is None or hess is None:
            grad, hess = self._gradients()
        from ..obs import determinism
        determinism.rng_site("goss.sample", "bagging_seed/iteration")
        if self._pr is not None:
            # multi-process: gradients are global row-sharded arrays;
            # the sampling runs as ONE jitted SPMD program (eagerly
            # mixing replicated PRNG draws with sharded operands would
            # fail device placement), with padding rows masked out
            import jax
            if self._goss_mp_sample is None:
                pr = self._pr
                rank = jax.process_index()
                orig = np.arange(pr.per, dtype=np.int64) * pr.world + rank
                orig[pr.n_local:] = pr.n_global     # pads -> dummy slot
                self._goss_orig = pr.globalize(orig.astype(np.int32),
                                               fill=pr.n_global)
                self._goss_valid = pr.globalize(
                    pr.valid_mask_local(), fill=False)
                self._goss_mp_sample = jax.jit(
                    lambda G, H, it, valid, orig_idx: self._block_sample(
                        G, H, it, valid, orig_idx))
            # memcheck: disable=MEM002 -- per-iteration [n] f32 pair, not
            # persistent state; this path runs in tier-1 on the CPU
            # backend where donation is gated off (zero-copy host reads)
            grad, hess, bag = self._goss_mp_sample(
                grad, hess, jnp.int32(self.iter), self._goss_valid,
                self._goss_orig)
        else:
            grad, hess, bag = self._block_sample(grad, hess, self.iter)
        return self._train_with_bag(grad, hess, bag)

    def _train_with_bag(self, grad, hess, bag) -> bool:
        finished = True
        K = self.num_tree_per_iteration
        for k in range(K):
            fmask = self._feature_mask(self.iter * K + k)
            bt = self._build_tree(grad[:, k], hess[:, k], bag, fmask)
            if int(bt.num_leaves) > 1:
                finished = False
            bt = self._renew_leaves(bt, k)
            # stump => zero contribution (gbdt.cpp:435-460), matching the
            # stump-masked row_value the Pallas path emits
            bt = bt._replace(leaf_value=jnp.where(
                bt.num_leaves > 1, bt.leaf_value,
                jnp.zeros_like(bt.leaf_value)))
            self._update_scores(bt, k)
            host = self._to_host_tree(bt)
            host.shrinkage(self.shrinkage_rate)
            if len(self.models) < K and abs(self.init_score_value) > 1e-15:
                host.add_bias(self.init_score_value)
            self.models.append(host)
        self.iter += 1
        self._stacked_cache = None
        return finished


class RF(GBDT):
    """Random forest mode (reference rf.hpp:15-207): mandatory bagging, no
    shrinkage, gradients always computed from the 0-score baseline, outputs
    averaged over trees."""

    boosting_name = "rf"
    average_output = True

    def __init__(self, config: Config, train_set, objective=None, fobj=None):
        super().__init__(config, train_set, objective, fobj)
        self.shrinkage_rate = 1.0
        # RF gradients are w.r.t. the constant init score only (rf.hpp:80+)
        if train_set is not None:
            K = self.num_tree_per_iteration
            if self._pr is not None:
                # global row-sharded like the live scores: the objective
                # computes gradients over the global row axis
                self._base_score = self._pr.globalize(np.full(
                    (train_set.num_data, K), self.init_score_value,
                    np.float32))
            else:
                self._base_score = jnp.full((self.num_data, K),
                                            self.init_score_value,
                                            jnp.float32)

    def _gradients(self):
        saved = self.scores
        self.scores = self._base_score
        try:
            return super()._gradients()
        finally:
            self.scores = saved

    def _update_scores(self, bt, k):
        # accumulate raw sums; averaging happens at predict time
        self.scores = self.scores.at[:, k].add(bt.leaf_value[bt.row_leaf])
        from ..learner.serial import predict_built_tree
        for i, vd in enumerate(self._valid_device):
            pred = predict_built_tree(bt, vd, vd.bins)
            self._valid_scores[i] = self._valid_scores[i].at[:, k].add(pred)

    def eval_train(self):
        return self._eval_avg(super().eval_train)

    def eval_valid(self):
        return self._eval_avg(super().eval_valid)

    def _eval_avg(self, fn):
        # temporarily average scores for metric evaluation
        T = max(1, len(self.models) // max(1, self.num_tree_per_iteration))
        ss, vs = self.scores, list(self._valid_scores)
        self.scores = self.scores / T
        self._valid_scores = [v / T for v in self._valid_scores]
        try:
            return fn()
        finally:
            self.scores, self._valid_scores = ss, vs


def create_boosting(config: Config, train_set=None, objective=None, fobj=None):
    """Factory (reference Boosting::CreateBoosting, boosting.cpp:30-63)."""
    cls = {"gbdt": GBDT, "dart": DART, "goss": GOSS, "rf": RF}[
        config.boosting_type]
    booster = cls(config, train_set, objective, fobj)
    if config.input_model:
        from ..utils.file_io import open_read
        with open_read(config.input_model) as f:
            booster.load_model_from_string(f.read())
    return booster
