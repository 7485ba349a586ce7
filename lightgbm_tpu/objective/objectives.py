"""Objective functions: gradients/hessians as pure jnp transforms.

TPU-native counterparts of the reference objective classes
(`/root/reference/src/objective/regression_objective.hpp`,
`binary_objective.hpp`, `multiclass_objective.hpp`, `rank_objective.hpp`,
`xentropy_objective.hpp`; factory `objective_function.cpp:10-47`).  The
reference computes per-row gradients in OpenMP loops; here every objective
is one vectorized ``get_gradients(score) -> (grad, hess)`` suitable for
fusion into the jitted boosting step.  Interface parity:

* ``boost_from_score()`` — initial score (``BoostFromScore``,
  `objective_function.h:45`).
* ``renew_tree_output(...)`` — leaf re-fitting for percentile-based
  objectives (L1/quantile/MAPE — ``RenewTreeOutput``,
  `objective_function.h:40`, `regression_objective.hpp:196-259`).
* ``num_model_per_iteration`` — K trees/iter for multiclass
  (`objective_function.h:49`).
* ``convert_output`` — link inversion for prediction
  (sigmoid/exp/softmax).
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config


def _apply_weight(grad, hess, weight):
    if weight is None:
        return grad, hess
    return grad * weight, hess * weight


class ObjectiveFunction:
    """Base class (reference include/LightGBM/objective_function.h:14-79)."""
    name = "none"
    num_model_per_iteration = 1
    is_constant_hessian = False
    need_renew_tree_output = False

    def __init__(self, config: Config, metadata=None):
        self.config = config
        self.label: Optional[jnp.ndarray] = None
        self.weight: Optional[jnp.ndarray] = None
        self.query_boundaries = None
        self.num_data = 0

    def init(self, metadata, num_data: int) -> None:
        self.num_data = num_data
        # host copies kept alongside the device arrays: BoostFromScore
        # runs once at booster init, where every eager device op costs
        # a mini-compile and a dispatch (label/weight arrive host-side
        # anyway, so this is free)
        self._label_np = (np.asarray(metadata.label, np.float32)
                          if metadata.label is not None
                          else np.zeros(num_data, np.float32))
        self._weight_np = (np.asarray(metadata.weight, np.float32)
                           if metadata.weight is not None else None)
        self.label = (jnp.asarray(metadata.label, jnp.float32)
                      if metadata.label is not None else jnp.zeros(num_data))
        self.weight = (jnp.asarray(metadata.weight, jnp.float32)
                       if metadata.weight is not None else None)
        if metadata.query_boundaries is not None:
            self.query_boundaries = np.asarray(metadata.query_boundaries)
        self._check_label()

    def _host_label_mean(self) -> float:
        """Weighted label mean, on host (see init)."""
        y = self._label_np
        if self._weight_np is not None:
            w = self._weight_np
            return float((y * w).sum() / w.sum())
        return float(y.mean())

    # True when boost_from_score keys on the WEIGHTED label mean
    # (xentlambda uses the unweighted one); multi-process init uses this
    # to pick the right global sufficient statistic
    boost_mean_weighted = True

    def globalize_rows(self, globalize, allgather) -> None:
        """Multi-process training: re-align per-row state to the GLOBAL
        row axis and recompute whole-dataset statistics with
        cross-process sufficient stats.  ``globalize(np [n_local, ...])
        -> global row-sharded array`` (pad rows 0); ``allgather(obj) ->
        per-rank list``.  Subclasses with extra per-row arrays or
        dataset-level scalars MUST override (and call super)."""
        self.label = globalize(np.asarray(self._label_np, np.float32))
        if self.weight is not None:
            self.weight = globalize(np.asarray(self._weight_np,
                                               np.float32))

    def boost_from_score_global(self, allgather) -> float:
        """Cross-process BoostFromScore: every current objective's init
        score is a function of the (un)weighted label mean, so allgather
        that sufficient statistic and re-derive through the objective's
        own link (logit/log/...) by evaluating boost_from_score on a
        one-row stand-in.  An objective whose init score is NOT a mean
        function (e.g. a future reference-parity weighted-median L1
        boost) MUST override with its own global statistic."""
        y = np.asarray(self._label_np, np.float64)
        use_w = self.boost_mean_weighted and self._weight_np is not None
        w = (np.asarray(self._weight_np, np.float64) if use_w
             else np.ones_like(y))
        sums = allgather([float((y * w).sum()), float(w.sum())])
        gmean = (sum(s[0] for s in sums)
                 / max(sum(s[1] for s in sums), 1e-30))
        saved = (self._label_np, self._weight_np)
        try:
            self._label_np = np.array([gmean], np.float64)
            self._weight_np = None
            return self.boost_from_score()
        finally:
            self._label_np, self._weight_np = saved

    def _check_label(self) -> None:
        pass

    def get_gradients(self, score: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        raise NotImplementedError

    def boost_from_score(self) -> float:
        return 0.0

    def convert_output(self, score: jnp.ndarray) -> jnp.ndarray:
        return score

    def renew_tree_output(self, score, row_leaf, num_leaves):
        """Return per-leaf output corrections, or None."""
        return None

    def to_string(self) -> str:
        return self.name


# ---------------------------------------------------------------------------
# Regression family (reference regression_objective.hpp)
# ---------------------------------------------------------------------------
class RegressionL2(ObjectiveFunction):
    name = "regression"
    is_constant_hessian = True

    def __init__(self, config, metadata=None):
        super().__init__(config)
        self.sqrt = bool(config.reg_sqrt)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if self.sqrt:
            self.raw_label = self.label
            self._label_np = (np.sign(self._label_np)
                              * np.sqrt(np.abs(self._label_np)))
            self.label = jnp.asarray(self._label_np)

    def get_gradients(self, score):
        grad = score - self.label
        hess = jnp.ones_like(score)
        return _apply_weight(grad, hess, self.weight)

    def boost_from_score(self):
        # weighted mean label (regression_objective.hpp BoostFromScore)
        return self._host_label_mean()

    def convert_output(self, score):
        if self.sqrt:
            return jnp.sign(score) * score * score
        return score


class RegressionL1(ObjectiveFunction):
    name = "regression_l1"
    is_constant_hessian = True
    need_renew_tree_output = True
    _percentile = 0.5

    def get_gradients(self, score):
        diff = score - self.label
        grad = jnp.sign(diff)
        hess = jnp.ones_like(score)
        return _apply_weight(grad, hess, self.weight)

    def renew_tree_output(self, score, row_leaf, num_leaves):
        # leaf output := percentile of (label - score) in the leaf
        # (RenewTreeOutput, regression_objective.hpp:196-259)
        return _leaf_percentile(self.label - score, row_leaf, num_leaves,
                                self._percentile, self.weight)


class Huber(ObjectiveFunction):
    name = "huber"
    is_constant_hessian = True

    def __init__(self, config, metadata=None):
        super().__init__(config)
        self.alpha = float(config.alpha)

    def get_gradients(self, score):
        diff = score - self.label
        grad = jnp.clip(diff, -self.alpha, self.alpha)
        hess = jnp.ones_like(score)
        return _apply_weight(grad, hess, self.weight)


class Fair(ObjectiveFunction):
    name = "fair"

    def __init__(self, config, metadata=None):
        super().__init__(config)
        self.c = float(config.fair_c)

    def get_gradients(self, score):
        diff = score - self.label
        denom = jnp.abs(diff) + self.c
        grad = self.c * diff / denom
        hess = self.c * self.c / (denom * denom)
        return _apply_weight(grad, hess, self.weight)


class Poisson(ObjectiveFunction):
    name = "poisson"

    def __init__(self, config, metadata=None):
        super().__init__(config)
        self.max_delta_step = float(config.poisson_max_delta_step)

    def _check_label(self):
        if (self._label_np < 0).any():
            raise ValueError("poisson objective requires non-negative labels")

    def get_gradients(self, score):
        es = jnp.exp(score)
        grad = es - self.label
        hess = jnp.exp(score + self.max_delta_step)
        return _apply_weight(grad, hess, self.weight)

    def boost_from_score(self):
        return float(np.log(max(self._host_label_mean(), 1e-20)))

    def convert_output(self, score):
        return jnp.exp(score)


class Quantile(ObjectiveFunction):
    name = "quantile"
    is_constant_hessian = True
    need_renew_tree_output = True

    def __init__(self, config, metadata=None):
        super().__init__(config)
        self.alpha = float(config.alpha)

    def get_gradients(self, score):
        diff = score - self.label
        grad = jnp.where(diff >= 0, 1.0 - self.alpha, -self.alpha)
        hess = jnp.ones_like(score)
        return _apply_weight(grad, hess, self.weight)

    def renew_tree_output(self, score, row_leaf, num_leaves):
        return _leaf_percentile(self.label - score, row_leaf, num_leaves,
                                self.alpha, self.weight)


class Mape(ObjectiveFunction):
    name = "mape"
    is_constant_hessian = True
    need_renew_tree_output = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lw = 1.0 / jnp.maximum(1.0, jnp.abs(self.label))
        self.label_weight = lw if self.weight is None else lw * self.weight

    def globalize_rows(self, globalize, allgather):
        lw = np.asarray(self.label_weight, np.float32)
        super().globalize_rows(globalize, allgather)
        self.label_weight = globalize(lw)       # per-row state realigns

    def get_gradients(self, score):
        diff = score - self.label
        grad = jnp.sign(diff) * self.label_weight
        hess = jnp.ones_like(score) * (
            self.label_weight if self.weight is None else self.weight)
        return grad, hess

    def renew_tree_output(self, score, row_leaf, num_leaves):
        return _leaf_percentile(self.label - score, row_leaf, num_leaves,
                                0.5, self.label_weight)


class Gamma(Poisson):
    name = "gamma"

    def get_gradients(self, score):
        ems = jnp.exp(-score)
        grad = 1.0 - self.label * ems
        hess = self.label * ems
        return _apply_weight(grad, hess, self.weight)


class Tweedie(Poisson):
    name = "tweedie"

    def __init__(self, config, metadata=None):
        super().__init__(config)
        self.rho = float(config.tweedie_variance_power)

    def get_gradients(self, score):
        e1 = jnp.exp((1.0 - self.rho) * score)
        e2 = jnp.exp((2.0 - self.rho) * score)
        grad = -self.label * e1 + e2
        hess = (-self.label * (1.0 - self.rho) * e1
                + (2.0 - self.rho) * e2)
        return _apply_weight(grad, hess, self.weight)


# ---------------------------------------------------------------------------
# Binary (reference binary_objective.hpp:13-157)
# ---------------------------------------------------------------------------
class BinaryLogloss(ObjectiveFunction):
    name = "binary"

    def __init__(self, config, metadata=None):
        super().__init__(config)
        self.sigmoid = float(config.sigmoid)
        self.is_unbalance = bool(config.is_unbalance)
        self.scale_pos_weight = float(config.scale_pos_weight)
        self.label_weights = (1.0, 1.0)

    def _check_label(self):
        u = np.unique(self._label_np)
        if not np.all(np.isin(u, [0.0, 1.0])):
            raise ValueError("binary objective requires labels in {0, 1}")

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        cnt_pos = float((self._label_np > 0).sum())
        cnt_neg = float(num_data - cnt_pos)
        if self.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            # weight the smaller class up (binary_objective.hpp Init)
            if cnt_pos > cnt_neg:
                self.label_weights = (1.0, cnt_pos / cnt_neg)
            else:
                self.label_weights = (cnt_neg / cnt_pos, 1.0)
        else:
            self.label_weights = (1.0, self.scale_pos_weight)
        self._cnt_pos, self._cnt_neg = cnt_pos, cnt_neg

    def globalize_rows(self, globalize, allgather):
        super().globalize_rows(globalize, allgather)
        if self.is_unbalance:
            # class counts are a GLOBAL statistic: per-shard counts
            # would bake different scalars into the same SPMD program
            counts = allgather([self._cnt_pos, self._cnt_neg])
            cnt_pos = sum(c[0] for c in counts)
            cnt_neg = sum(c[1] for c in counts)
            self._cnt_pos, self._cnt_neg = cnt_pos, cnt_neg
            if cnt_pos > 0 and cnt_neg > 0:
                self.label_weights = ((1.0, cnt_pos / cnt_neg)
                                      if cnt_pos > cnt_neg
                                      else (cnt_neg / cnt_pos, 1.0))

    def get_gradients(self, score):
        y = self.label
        p = jax.nn.sigmoid(self.sigmoid * score)
        w_cls = jnp.where(y > 0, self.label_weights[1], self.label_weights[0])
        grad = self.sigmoid * (p - y) * w_cls
        hess = self.sigmoid * self.sigmoid * p * (1.0 - p) * w_cls
        return _apply_weight(grad, hess, self.weight)

    def boost_from_score(self):
        # avg label -> logit / sigmoid (binary_objective.hpp BoostFromScore)
        pavg = min(max(self._host_label_mean(), 1e-15), 1.0 - 1e-15)
        return np.log(pavg / (1.0 - pavg)) / self.sigmoid

    def convert_output(self, score):
        return jax.nn.sigmoid(self.sigmoid * score)

    def to_string(self):
        return f"binary sigmoid:{self.sigmoid}"


# ---------------------------------------------------------------------------
# Multiclass (reference multiclass_objective.hpp:16-225)
# ---------------------------------------------------------------------------
class MulticlassSoftmax(ObjectiveFunction):
    name = "multiclass"

    def __init__(self, config, metadata=None):
        super().__init__(config)
        self.num_class = int(config.num_class)
        self.num_model_per_iteration = self.num_class

    def _check_label(self):
        lab = self._label_np
        if lab.min() < 0 or lab.max() >= self.num_class:
            raise ValueError(
                f"multiclass labels must be in [0, {self.num_class})")

    def get_gradients(self, score):
        """score: [n, K] raw scores -> grad/hess [n, K]."""
        p = jax.nn.softmax(score, axis=-1)
        y = jax.nn.one_hot(self.label.astype(jnp.int32), self.num_class)
        grad = p - y
        hess = 2.0 * p * (1.0 - p)      # factor-2 upper bound, like reference
        if self.weight is not None:
            grad = grad * self.weight[:, None]
            hess = hess * self.weight[:, None]
        return grad, hess

    def convert_output(self, score):
        return jax.nn.softmax(score, axis=-1)

    def to_string(self):
        return f"multiclass num_class:{self.num_class}"


class MulticlassOVA(ObjectiveFunction):
    name = "multiclassova"

    def __init__(self, config, metadata=None):
        super().__init__(config)
        self.num_class = int(config.num_class)
        self.num_model_per_iteration = self.num_class
        self.sigmoid = float(config.sigmoid)

    def get_gradients(self, score):
        y = jax.nn.one_hot(self.label.astype(jnp.int32), self.num_class)
        p = jax.nn.sigmoid(self.sigmoid * score)
        grad = self.sigmoid * (p - y)
        hess = self.sigmoid * self.sigmoid * p * (1.0 - p)
        if self.weight is not None:
            grad = grad * self.weight[:, None]
            hess = hess * self.weight[:, None]
        return grad, hess

    def convert_output(self, score):
        return jax.nn.sigmoid(self.sigmoid * score)

    def to_string(self):
        return f"multiclassova num_class:{self.num_class} sigmoid:{self.sigmoid}"


# ---------------------------------------------------------------------------
# Cross-entropy (reference xentropy_objective.hpp:39-270)
# ---------------------------------------------------------------------------
class CrossEntropy(ObjectiveFunction):
    name = "xentropy"

    def _check_label(self):
        lab = self._label_np
        if lab.min() < 0 or lab.max() > 1:
            raise ValueError("xentropy labels must be in [0, 1]")

    def get_gradients(self, score):
        p = jax.nn.sigmoid(score)
        grad = p - self.label
        hess = p * (1.0 - p)
        return _apply_weight(grad, hess, self.weight)

    def boost_from_score(self):
        pavg = min(max(self._host_label_mean(), 1e-15), 1.0 - 1e-15)
        return float(np.log(pavg / (1.0 - pavg)))

    def convert_output(self, score):
        return jax.nn.sigmoid(score)


class CrossEntropyLambda(ObjectiveFunction):
    name = "xentlambda"
    boost_mean_weighted = False   # boost_from_score uses the plain mean

    def get_gradients(self, score):
        # intensity parameterization: p = 1 - exp(-w*exp(score))
        # (xentropy_objective.hpp:142-238)
        w = self.weight if self.weight is not None else 1.0
        es = jnp.exp(score)
        z = w * es
        emz = jnp.exp(-z)
        p = 1.0 - emz
        p = jnp.clip(p, 1e-15, 1 - 1e-15)
        grad = z * (1.0 - self.label / p * emz)
        hess = z * (1.0 - self.label / p * emz * (1.0 - z * (1 - p) / p))
        hess = jnp.maximum(hess, 1e-15)
        return grad, hess

    def boost_from_score(self):
        pavg = float(self._label_np.mean())
        pavg = min(max(pavg, 1e-15), 1.0 - 1e-15)
        return float(np.log(-np.log1p(-pavg)))

    def convert_output(self, score):
        return 1.0 - jnp.exp(-jnp.exp(score))


# ---------------------------------------------------------------------------
# LambdaRank (reference rank_objective.hpp:19-245)
# ---------------------------------------------------------------------------
class LambdarankNDCG(ObjectiveFunction):
    name = "lambdarank"

    def __init__(self, config, metadata=None):
        super().__init__(config)
        self.sigmoid = float(config.sigmoid)
        self.max_position = int(config.max_position)
        gains = config.label_gain
        if not gains:
            gains = tuple(float((1 << i) - 1) for i in range(31))
        self.label_gain = np.asarray(gains, np.float64)

    def globalize_rows(self, globalize, allgather):
        raise NotImplementedError(
            "lambdarank is not supported with MULTI-PROCESS training "
            "(documented descope): its per-query pair structures "
            "address rows by position, which the cross-process "
            "row-block layout breaks.  Single-process distributed "
            "training IS supported — tree_learner=data/voting on a "
            "multi-device mesh shards the histogram work while the "
            "objective sees the full row axis "
            "(tests/test_lambdarank.py::test_lambdarank_data_parallel_mesh).")

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if self.query_boundaries is None:
            raise ValueError("lambdarank requires query data")
        qb = self.query_boundaries
        sizes = (qb[1:] - qb[:-1]).astype(np.int64)
        self.max_query = int(sizes.max())
        labels = self._label_np

        # Queries BUCKETED by ceil-pow2 size: padding every query to the
        # global max wastes ~10x at MSLR shape (mean ~120 docs, max
        # ~1.2k), and the r4 [nq, M, M] full pair grid was out of
        # memory by orders of magnitude there (VERDICT r5 #2).  Within
        # a bucket the pair grid is [T, M]: rows = the top-T
        # score-sorted positions (T = truncation), cols = all sorted
        # positions, pairs r < c — exactly the reference's loop
        # structure (rank_objective.hpp:75-81: `for i < truncation_level;
        # for j = i+1`), so pair count is O(T * docs), not O(docs^2).
        by_size: dict = {}
        for q, s in enumerate(sizes):
            Mb = 1 << max(4, int(s - 1).bit_length())
            by_size.setdefault(Mb, []).append(q)
        self.discounts = jnp.asarray(
            1.0 / np.log2(np.arange(max(by_size) + 1) + 2.0), jnp.float32)
        self.buckets = []
        for Mb in sorted(by_size):
            qs = np.asarray(by_size[Mb], np.int64)
            T = min(self.max_position, Mb)
            idx = qb[:-1][qs, None] + np.arange(Mb)[None, :]
            valid = np.arange(Mb)[None, :] < sizes[qs, None]
            idx = np.where(valid, idx, 0)
            lab = np.where(valid, labels[idx.astype(np.int64)], -1)
            gain = np.where(valid,
                            self.label_gain[lab.astype(int) * (lab >= 0)],
                            0.0)
            # inverse max DCG at truncation (rank_objective.hpp:46-73),
            # bucket-vectorized (a per-query python loop took minutes
            # at 30k queries)
            disc = 1.0 / np.log2(np.arange(T) + 2.0)
            top = -np.sort(-np.where(valid, lab, -1), axis=1)[:, :T]
            ideal = np.where(top >= 0,
                             self.label_gain[top.astype(int) * (top >= 0)],
                             0.0)
            dcg = (ideal * disc[None, :]).sum(axis=1)
            imd = np.where(dcg > 0, 1.0 / np.maximum(dcg, 1e-300), 0.0)
            self.buckets.append({
                "M": Mb, "T": T,
                "idx": jnp.asarray(idx, jnp.int32),
                "valid": jnp.asarray(valid),
                "label": jnp.asarray(np.where(valid, lab, -1), jnp.float32),
                "gain": jnp.asarray(gain, jnp.float32),
                "imd": jnp.asarray(imd, jnp.float32),
            })

    def get_gradients(self, score):
        """Pairwise NDCG-delta-weighted lambdas over the bucketed
        [T, M] sorted-position pair grids (see ``init``).  Traceable —
        runs inside the fused training block.

        Each bucket dispatch is wrapped in an ``obj.rank_grad.<M>``
        telemetry span (ISSUE 9 satellite): on the eager/debug paths
        the spans attribute per-bucket wall-clock (which query-size
        class of the MSLR mix dominates the ranking leg); inside
        a traced block they record trace-time and bucket counts.  The
        ``rank_grad`` bench table measures the same mix end-to-end."""
        from .. import obs
        grad = jnp.zeros_like(score)
        hess = jnp.zeros_like(score)
        # pair-grid entries per dispatched chunk: bounds the [C, T, M]
        # intermediates (~10 live f32 arrays) to a few hundred MB of HBM
        budget = int(os.environ.get("LGBM_TPU_RANK_CHUNK_PAIRS", 8_000_000))
        for bk in self.buckets:
            Mb, T = bk["M"], bk["T"]
            nq = bk["idx"].shape[0]
            C = max(1, min(nq, budget // max(1, T * Mb)))
            with obs.span(f"obj.rank_grad.{Mb}", queries=nq, pair_rows=T):
                g, h = _lambdarank_bucket_grads(
                    score[bk["idx"]], bk["valid"], bk["label"], bk["gain"],
                    bk["imd"], self.discounts[:Mb],
                    jnp.float32(self.sigmoid), T=T, C=C)
                grad = grad.at[bk["idx"].ravel()].add(
                    jnp.where(bk["valid"], g, 0.0).ravel())
                hess = hess.at[bk["idx"].ravel()].add(
                    jnp.where(bk["valid"], h, 0.0).ravel())
        return grad, hess

    def to_string(self):
        return "lambdarank"


def _fold_pair_grid(signed, hh, T, M):
    """Fold one query's [T, M] pair grids to per-doc grad/hess rows.

    Partition-independent by construction: rows of one query are never
    split across shards (ranking descopes row-blocked streaming), so
    the fold order is fixed by the in-query sort alone — registered as
    a sanctioned numcheck context
    (tools/numcheck/reduction_registry.py)."""
    g_sorted = (jnp.pad(jnp.sum(signed, axis=1), (0, M - T))
                - jnp.sum(signed, axis=0))
    h_sorted = (jnp.pad(jnp.sum(hh, axis=1), (0, M - T))
                + jnp.sum(hh, axis=0))
    return g_sorted, h_sorted


@functools.partial(jax.jit, static_argnames=("T", "C"))
def _lambdarank_bucket_grads(s, valid, label, gain, imd, disc, sigma,
                             *, T: int, C: int):
    """(grad, hess) per padded doc slot for one query-size bucket.

    Per query (vmapped, ``lax.map``-chunked by ``C`` queries): sort docs
    by score desc, then the pair grid is ``[T, M]`` over SORTED
    positions — rows the top-T positions, cols all positions, a pair
    live when ``col > row``, both valid, labels differ.  Since row <
    col, "min position < truncation" (the reference's pair condition,
    rank_objective.hpp:75-81) is exactly "row < T".  Each unordered
    pair appears once; the better-labeled side receives ``lam``, the
    worse ``-lam``, both receive ``+hess`` — summed along grid axes and
    scattered back through the sort permutation.
    """
    nq, M = s.shape

    def per_query(args):
        s, valid, label, gain, imd = args
        sm = jnp.where(valid, s, -jnp.inf)
        order = jnp.argsort(-sm)
        s_s = sm[order]
        lab_s = label[order]
        gain_s = gain[order]
        val_s = valid[order]
        dl = lab_s[:T, None] - lab_s[None, :]
        pv = ((jnp.arange(M)[None, :] > jnp.arange(T)[:, None])
              & val_s[None, :] & val_s[:T, None] & (dl != 0))
        delta = jnp.abs((gain_s[:T, None] - gain_s[None, :])
                        * (disc[:T, None] - disc[None, :])) * imd
        better_row = dl > 0
        sd = s_s[:T, None] - s_s[None, :]
        sig = jax.nn.sigmoid(-sigma * jnp.where(better_row, sd, -sd))
        lam = jnp.where(pv, -sigma * sig * delta, 0.0)
        hh = jnp.where(pv, sigma * sigma * sig * (1.0 - sig) * delta, 0.0)
        row_sign = jnp.where(better_row, 1.0, -1.0)
        signed = lam * row_sign
        # accumulate in SORTED coordinates, then one inverse-permutation
        # gather back — the equivalent per-original-index scatter-adds
        # (4 of them) are the slow path on TPU
        g_sorted, h_sorted = _fold_pair_grid(signed, hh, T, M)
        inv = jnp.argsort(order)
        return g_sorted[inv], h_sorted[inv]

    if C >= nq:
        return jax.vmap(per_query)((s, valid, label, gain, imd))
    # chunk the query axis: [ceil(nq/C), C, ...] with dummy (all-invalid)
    # pad queries, sequenced by lax.map so only one [C, T, M] grid set
    # is live at a time
    NC = -(-nq // C)
    pad = NC * C - nq

    def padq(a, fill):
        if pad == 0:
            return a.reshape((NC, C) + a.shape[1:])
        return jnp.concatenate(
            [a, jnp.full((pad,) + a.shape[1:], fill, a.dtype)]
        ).reshape((NC, C) + a.shape[1:])

    g, h = jax.lax.map(
        jax.vmap(per_query),
        (padq(s, 0.0), padq(valid, False), padq(label, -1.0),
         padq(gain, 0.0), padq(imd, 0.0)))
    return (g.reshape(NC * C, M)[:nq], h.reshape(NC * C, M)[:nq])


class CustomObjective(ObjectiveFunction):
    """Wraps a user fobj(score, dataset) -> (grad, hess) (the reference's
    Python custom-objective path, engine.py fobj)."""
    name = "none"

    def __init__(self, config, fobj=None):
        super().__init__(config)
        self.fobj = fobj

    def get_gradients(self, score):
        raise RuntimeError("custom objective gradients are supplied externally")


OBJECTIVES = {
    "regression": RegressionL2,
    "regression_l1": RegressionL1,
    "huber": Huber,
    "fair": Fair,
    "poisson": Poisson,
    "quantile": Quantile,
    "mape": Mape,
    "gamma": Gamma,
    "tweedie": Tweedie,
    "binary": BinaryLogloss,
    "multiclass": MulticlassSoftmax,
    "multiclassova": MulticlassOVA,
    "xentropy": CrossEntropy,
    "xentlambda": CrossEntropyLambda,
    "lambdarank": LambdarankNDCG,
}


def create_objective(config: Config) -> Optional[ObjectiveFunction]:
    """Factory (reference objective_function.cpp:10-47)."""
    if config.objective == "none":
        fobj = config.extra.get("fobj")
        return CustomObjective(config, fobj) if fobj else None
    cls = OBJECTIVES.get(config.objective)
    if cls is None:
        raise ValueError(f"unknown objective {config.objective!r}")
    return cls(config)


def _leaf_percentile(values: jnp.ndarray, row_leaf: jnp.ndarray,
                     num_leaves: int, alpha: float,
                     weight: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Per-leaf (weighted) percentile of ``values`` — RenewTreeOutput's
    kernel (`regression_objective.hpp` PercentileFun/WeightedPercentileFun).

    Sort-based: rows sorted by (leaf, value); per-leaf quantile read at the
    interpolated offset.  Weighted variant uses the cumulative-weight
    crossing rule like the reference.
    """
    leaf = row_leaf.astype(jnp.int32)
    order = jnp.lexsort((values, leaf))
    sv = values[order]
    sl = leaf[order]
    n = values.shape[0]
    lid = jnp.arange(num_leaves)
    start = jnp.searchsorted(sl, lid, side="left")
    end = jnp.searchsorted(sl, lid, side="right")
    cnt = end - start

    if weight is None:
        pos = alpha * (cnt - 1).astype(jnp.float32)
        lo = jnp.floor(pos).astype(jnp.int32)
        hi = jnp.ceil(pos).astype(jnp.int32)
        frac = pos - lo
        vlo = sv[jnp.clip(start + lo, 0, n - 1)]
        vhi = sv[jnp.clip(start + hi, 0, n - 1)]
        out = vlo * (1 - frac) + vhi * frac
    else:
        sw = weight[order]
        cum_w = jnp.cumsum(sw)
        base = jnp.where(start > 0, cum_w[jnp.maximum(start - 1, 0)], 0.0)
        total = jnp.where(end > 0, cum_w[jnp.maximum(end - 1, 0)], 0.0) - base
        # first position where cumulative leaf weight >= alpha * total
        target = base + alpha * total
        pos = jnp.searchsorted(cum_w, target, side="left")
        pos = jnp.clip(pos, start, jnp.maximum(end - 1, start))
        out = sv[jnp.clip(pos, 0, n - 1)]
    return jnp.where(cnt > 0, out, 0.0)
