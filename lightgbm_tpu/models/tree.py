"""Decision tree model — flat structure-of-arrays, jittable prediction.

TPU-native counterpart of the reference ``Tree``
(`/root/reference/include/LightGBM/tree.h:15-300`, `src/io/tree.cpp`):
same flat layout (split_feature / threshold / left_child / right_child /
leaf_value, children encoded as ``>=0`` internal node, ``~leaf`` for
leaves) because that layout is *already* the right one for vectorized
gather-based prediction on TPU.

* ``Tree`` — host-side (numpy) mutable builder + (de)serialization in the
  reference's text model format (`src/io/tree.cpp:209-242`): the same
  ``num_leaves/split_feature/threshold/decision_type/...`` keys, so model
  files interoperate with LightGBM v2.1.0 tooling.
* ``decision_type`` bit layout matches `tree.h:15-16,197-205`:
  bit0 = categorical, bit1 = default_left, bits2-3 = missing type.
* ``stack_trees`` — packs a list of trees into ``[T, ...]`` device arrays;
  ``predict_binned`` walks all trees for all rows with vectorized gathers
  (replacing the reference's per-row pointer chase `tree.h:112-119`) —
  a ``lax.fori_loop`` over tree depth, everything else data-parallel.
"""
from __future__ import annotations

import functools
import math
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..io.binning import MISSING_NAN, MISSING_NONE, MISSING_ZERO

K_CATEGORICAL_MASK = 1     # decision_type bit0 (tree.h:15)
K_DEFAULT_LEFT_MASK = 2    # decision_type bit1 (tree.h:16)
_K_ZERO_THRESHOLD = 1e-35


def _fmt_double(v: float) -> str:
    """Locale-independent double formatting at digits10+2 precision, like
    ``Common::ArrayToString<double>`` in the reference."""
    if math.isinf(v):
        return "inf" if v > 0 else "-inf"
    if math.isnan(v):
        return "nan"
    return repr(float(v))


class Tree:
    """Host-side tree under construction / for serialization."""

    def __init__(self, max_leaves: int) -> None:
        m = max(max_leaves - 1, 1)
        self.max_leaves = max_leaves
        self.num_leaves = 1
        self.num_cat = 0
        # internal-node arrays [max_leaves - 1]
        self.split_feature = np.zeros(m, np.int32)        # original feature idx
        self.split_feature_inner = np.zeros(m, np.int32)  # used-column idx
        self.split_gain = np.zeros(m, np.float32)
        self.threshold = np.zeros(m, np.float64)          # real-valued (numerical)
        self.threshold_bin = np.zeros(m, np.int32)
        self.decision_type = np.zeros(m, np.int8)
        self.left_child = np.full(m, -1, np.int32)
        self.right_child = np.full(m, -1, np.int32)
        self.internal_value = np.zeros(m, np.float64)
        self.internal_count = np.zeros(m, np.int32)
        # leaf arrays [max_leaves]
        self.leaf_value = np.zeros(max_leaves, np.float64)
        self.leaf_count = np.zeros(max_leaves, np.int32)
        self.leaf_parent = np.full(max_leaves, -1, np.int32)
        self.leaf_depth = np.zeros(max_leaves, np.int32)
        # categorical bitsets: values (for raw data) and bins (for binned data)
        self.cat_boundaries: List[int] = [0]
        self.cat_threshold: List[int] = []               # uint32 words (values)
        self.cat_left_bins: List[np.ndarray] = []        # per cat-node left bin ids
        self.shrinkage_rate = 1.0

    # -- construction ----------------------------------------------------
    def _new_node(self, leaf: int) -> int:
        """Turn ``leaf`` into internal node ``num_leaves-1``; left child keeps
        the leaf id, right child becomes leaf ``num_leaves`` (the reference's
        Split bookkeeping, tree.h:54-76 / tree.cpp)."""
        new_node = self.num_leaves - 1
        parent = self.leaf_parent[leaf]
        if parent >= 0:
            if ~self.left_child[parent] == leaf and self.left_child[parent] < 0:
                self.left_child[parent] = new_node
            else:
                self.right_child[parent] = new_node
        return new_node

    def split(self, leaf: int, feature: int, inner_feature: int,
              threshold_bin: int, threshold_double: float,
              left_value: float, right_value: float,
              left_cnt: int, right_cnt: int, gain: float,
              missing_type: int, default_left: bool,
              parent_value: float = 0.0) -> int:
        """Numerical split; returns the new (right-child) leaf id."""
        new_node = self._new_node(leaf)
        right_leaf = self.num_leaves
        dt = np.int8(0)
        if default_left:
            dt |= K_DEFAULT_LEFT_MASK
        dt |= np.int8((missing_type & 3) << 2)
        self.decision_type[new_node] = dt
        self.split_feature[new_node] = feature
        self.split_feature_inner[new_node] = inner_feature
        self.threshold[new_node] = threshold_double
        self.threshold_bin[new_node] = threshold_bin
        self.split_gain[new_node] = gain
        self._finish_split(new_node, leaf, right_leaf, left_value, right_value,
                           left_cnt, right_cnt, parent_value)
        return right_leaf

    def split_categorical(self, leaf: int, feature: int, inner_feature: int,
                          left_bins: Sequence[int], left_values: Sequence[int],
                          left_value: float, right_value: float,
                          left_cnt: int, right_cnt: int, gain: float,
                          missing_type: int, parent_value: float = 0.0) -> int:
        """Categorical (bitset) split; left side = ``left_values`` categories."""
        new_node = self._new_node(leaf)
        right_leaf = self.num_leaves
        self.decision_type[new_node] = np.int8(
            K_CATEGORICAL_MASK | ((missing_type & 3) << 2))
        self.split_feature[new_node] = feature
        self.split_feature_inner[new_node] = inner_feature
        self.split_gain[new_node] = gain
        # threshold holds the cat-node index (tree.cpp SplitCategorical)
        cat_idx = self.num_cat
        self.threshold[new_node] = float(cat_idx)
        self.threshold_bin[new_node] = cat_idx
        bitset = _construct_bitset(left_values)
        self.cat_threshold.extend(bitset)
        self.cat_boundaries.append(len(self.cat_threshold))
        self.cat_left_bins.append(np.asarray(sorted(left_bins), np.int32))
        self.num_cat += 1
        self._finish_split(new_node, leaf, right_leaf, left_value, right_value,
                           left_cnt, right_cnt, parent_value)
        return right_leaf

    def _finish_split(self, new_node, leaf, right_leaf, left_value, right_value,
                      left_cnt, right_cnt, parent_value):
        depth = self.leaf_depth[leaf] + 1
        self.left_child[new_node] = ~leaf
        self.right_child[new_node] = ~right_leaf
        self.internal_value[new_node] = parent_value
        self.internal_count[new_node] = left_cnt + right_cnt
        self.leaf_value[leaf] = _sanitize(left_value)
        self.leaf_value[right_leaf] = _sanitize(right_value)
        self.leaf_count[leaf] = left_cnt
        self.leaf_count[right_leaf] = right_cnt
        self.leaf_parent[leaf] = new_node
        self.leaf_parent[right_leaf] = new_node
        self.leaf_depth[leaf] = depth
        self.leaf_depth[right_leaf] = depth
        self.num_leaves += 1

    def shrinkage(self, rate: float) -> None:
        """Scale outputs (reference Tree::Shrinkage)."""
        self.leaf_value[:self.num_leaves] *= rate
        self.shrinkage_rate *= rate

    def add_bias(self, bias: float) -> None:
        self.leaf_value[:self.num_leaves] += bias

    def set_leaf_output(self, leaf: int, value: float) -> None:
        self.leaf_value[leaf] = _sanitize(value)

    @property
    def max_depth(self) -> int:
        return int(self.leaf_depth[:self.num_leaves].max()) if self.num_leaves > 1 else 0

    # -- host prediction (numpy; used for small batches / verification) --
    def predict_row(self, x: np.ndarray) -> float:
        if self.num_leaves == 1:
            return float(self.leaf_value[0])
        node = 0
        while True:
            node = self._decision(x, node)
            if node < 0:
                return float(self.leaf_value[~node])

    def predict_leaf_row(self, x: np.ndarray) -> int:
        if self.num_leaves == 1:
            return 0
        node = 0
        while True:
            node = self._decision(x, node)
            if node < 0:
                return ~node

    def predict_leaf_batch(self, X: np.ndarray) -> np.ndarray:
        """Vectorized numpy traversal over all rows -> leaf index [n].

        The loaded-model fast path (reference `gbdt_prediction.cpp` per-row
        walk, vectorized here): per depth step, one gather per node array;
        categorical nodes resolve their bitset membership per unique node.
        """
        n = X.shape[0]
        if self.num_leaves == 1:
            return np.zeros(n, np.int64)
        m = self.num_leaves - 1
        sf = np.asarray(self.split_feature[:m], np.int64)
        thr = np.asarray(self.threshold[:m], np.float64)
        dt = np.asarray(self.decision_type[:m], np.int64)
        lc = np.asarray(self.left_child[:m], np.int64)
        rc = np.asarray(self.right_child[:m], np.int64)
        tb = np.asarray(self.threshold_bin[:m], np.int64)
        is_cat = (dt & K_CATEGORICAL_MASK) != 0
        mt = (dt >> 2) & 3
        dl = (dt & K_DEFAULT_LEFT_MASK) != 0
        cat_members = None
        if is_cat.any():
            cat_members = [np.asarray(_bitset_to_values(
                self.cat_threshold[self.cat_boundaries[ci]:
                                   self.cat_boundaries[ci + 1]]))
                for ci in range(len(self.cat_boundaries) - 1)]

        node = np.zeros(n, np.int64)
        active = np.arange(n)
        while active.size:
            nd = node[active]
            f = sf[nd]
            fval = X[active, f].astype(np.float64)
            nan = np.isnan(fval)
            fval0 = np.where(nan & (mt[nd] != MISSING_NAN), 0.0, fval)
            is_missing = (((mt[nd] == MISSING_ZERO)
                           & (np.abs(fval0) <= _K_ZERO_THRESHOLD))
                          | ((mt[nd] == MISSING_NAN) & nan))
            go_left = np.where(is_missing, dl[nd], fval0 <= thr[nd])
            ic = is_cat[nd]
            if ic.any():
                cat_left = np.zeros(ic.sum(), bool)
                sub_nd = nd[ic]
                sub_val = fval[ic]
                ok = ~np.isnan(sub_val) & (sub_val >= 0)
                cats = np.where(ok, sub_val, -1).astype(np.int64)
                for u in np.unique(sub_nd):
                    rows = sub_nd == u
                    cat_left[rows] = np.isin(cats[rows],
                                             cat_members[tb[u]])
                cat_left &= ok
                go_left = np.where(ic, False, go_left)
                go_left[ic] = cat_left
            node[active] = np.where(go_left, lc[nd], rc[nd])
            active = active[node[active] >= 0]
        return ~node

    def predict_batch(self, X: np.ndarray) -> np.ndarray:
        """Vectorized tree output per row -> float64 [n]."""
        return np.asarray(self.leaf_value)[self.predict_leaf_batch(X)]

    def _decision(self, x: np.ndarray, node: int) -> int:
        f = self.split_feature[node]
        fval = x[f]
        dt = int(self.decision_type[node])
        missing_type = (dt >> 2) & 3
        if dt & K_CATEGORICAL_MASK:
            # CategoricalDecision (tree.h:252-271): NaN / unseen -> right
            if np.isnan(fval):
                return self.right_child[node]
            cat = int(fval)
            ci = self.threshold_bin[node]
            if cat >= 0 and _bitset_contains(
                    self.cat_threshold[self.cat_boundaries[ci]:
                                       self.cat_boundaries[ci + 1]], cat):
                return self.left_child[node]
            return self.right_child[node]
        # NumericalDecision (tree.h:212-234)
        if missing_type != MISSING_NAN and np.isnan(fval):
            fval = 0.0
        is_missing = ((missing_type == MISSING_ZERO and abs(fval) <= _K_ZERO_THRESHOLD)
                      or (missing_type == MISSING_NAN and np.isnan(fval)))
        if is_missing:
            return (self.left_child[node] if dt & K_DEFAULT_LEFT_MASK
                    else self.right_child[node])
        if fval <= self.threshold[node]:
            return self.left_child[node]
        return self.right_child[node]

    # -- text serialization (reference tree.cpp:209-242) -----------------
    def to_string(self) -> str:
        n = self.num_leaves
        m = n - 1
        lines = [f"num_leaves={n}", f"num_cat={self.num_cat}"]

        def arr(name, a, cnt, fmt=str):
            lines.append(f"{name}=" + " ".join(fmt(v) for v in a[:cnt]))

        arr("split_feature", self.split_feature, m)
        arr("split_gain", self.split_gain, m, lambda v: _fmt_float(v))
        arr("threshold", self.threshold, m, _fmt_double)
        arr("decision_type", self.decision_type, m)
        arr("left_child", self.left_child, m)
        arr("right_child", self.right_child, m)
        arr("leaf_value", self.leaf_value, n, _fmt_double)
        arr("leaf_count", self.leaf_count, n)
        arr("internal_value", self.internal_value, m, lambda v: _fmt_float(v))
        arr("internal_count", self.internal_count, m)
        if self.num_cat > 0:
            arr("cat_boundaries", np.asarray(self.cat_boundaries),
                self.num_cat + 1)
            arr("cat_threshold", np.asarray(self.cat_threshold, np.uint32),
                len(self.cat_threshold))
        # at full precision, unlike the other informational floats:
        # DART keeps multiplying this after a snapshot is loaded, so a
        # value rounded on the way through the text would make a resumed
        # model differ from an uninterrupted one in the last digit
        lines.append(f"shrinkage={_fmt_double(self.shrinkage_rate)}")
        lines.append("")
        return "\n".join(lines)

    @classmethod
    def from_string(cls, text: str) -> "Tree":
        kv = {}
        for line in text.splitlines():
            line = line.strip()
            if "=" in line:
                k, v = line.split("=", 1)
                kv[k] = v
        n = int(kv["num_leaves"])
        t = cls(max(n, 2))
        t.num_leaves = n
        t.num_cat = int(kv.get("num_cat", 0))
        m = n - 1

        def parse(name, dtype, cnt):
            if cnt == 0 or not kv.get(name):
                return np.zeros(cnt, dtype)
            vals = kv[name].split()
            return np.asarray([float(v) for v in vals[:cnt]]).astype(dtype)

        t.split_feature[:m] = parse("split_feature", np.int32, m)
        t.split_feature_inner[:m] = t.split_feature[:m]
        t.split_gain[:m] = parse("split_gain", np.float32, m)
        t.threshold[:m] = parse("threshold", np.float64, m)
        t.decision_type[:m] = parse("decision_type", np.int8, m)
        t.left_child[:m] = parse("left_child", np.int32, m)
        t.right_child[:m] = parse("right_child", np.int32, m)
        t.leaf_value[:n] = parse("leaf_value", np.float64, n)
        t.leaf_count[:n] = parse("leaf_count", np.int32, n)
        t.internal_value[:m] = parse("internal_value", np.float64, m)
        t.internal_count[:m] = parse("internal_count", np.int32, m)
        if t.num_cat > 0:
            t.cat_boundaries = [int(v) for v in kv["cat_boundaries"].split()]
            t.cat_threshold = [int(v) for v in kv["cat_threshold"].split()]
        t.shrinkage_rate = float(kv.get("shrinkage", 1.0))
        # categorical thresholds are cat-node indices stored as doubles;
        # numerical threshold_bin / cat_left_bins need bin mappers — see
        # align_with_mappers (called by the model loader)
        cat_nodes = (t.decision_type[:m] & K_CATEGORICAL_MASK) != 0
        t.threshold_bin[:m] = np.where(cat_nodes,
                                       t.threshold[:m].astype(np.int32), 0)
        # depths for stacked prediction
        t._recompute_depth()
        return t

    def align_with_mappers(self, mappers, feature_to_inner=None) -> None:
        """Recover bin-space thresholds (``threshold_bin``, ``cat_left_bins``)
        from real-valued thresholds after ``from_string``, using the
        dataset's BinMappers — the inverse of serialization's
        bin→value mapping (reference keeps both forms in memory,
        ``threshold_`` and ``threshold_in_bin_``, tree.h)."""
        m = self.num_leaves - 1
        self.cat_left_bins = [np.zeros(0, np.int32)] * self.num_cat
        for node in range(m):
            f = int(self.split_feature[node])
            if feature_to_inner is not None:
                self.split_feature_inner[node] = feature_to_inner.get(f, 0)
            mapper = mappers[f]
            if self.decision_type[node] & K_CATEGORICAL_MASK:
                ci = int(self.threshold[node])
                self.threshold_bin[node] = ci
                words = self.cat_threshold[self.cat_boundaries[ci]:
                                           self.cat_boundaries[ci + 1]]
                vals = [v for v in range(len(words) * 32)
                        if _bitset_contains(words, v)]
                bins = [mapper.categorical_2_bin[v] for v in vals
                        if v in mapper.categorical_2_bin]
                self.cat_left_bins[ci] = np.asarray(sorted(bins), np.int32)
            else:
                ub = mapper.bin_upper_bound
                from ..io.binning import MISSING_NAN
                if mapper.missing_type == MISSING_NAN:
                    ub = ub[:-1]
                # serialization wrote ub[t] via repr() (lossless), so the
                # exact value is found by left-bisection
                self.threshold_bin[node] = min(
                    int(np.searchsorted(ub, self.threshold[node], side="left")),
                    max(len(ub) - 1, 0))

    def _recompute_depth(self) -> None:
        if self.num_leaves <= 1:
            return
        depth = np.zeros(self.num_leaves - 1, np.int32)
        for node in range(self.num_leaves - 1):
            for child in (self.left_child[node], self.right_child[node]):
                if child >= 0:
                    depth[child] = depth[node] + 1
                else:
                    self.leaf_depth[~child] = depth[node] + 1


def _sanitize(v: float) -> float:
    return float(v) if math.isfinite(v) else 0.0


def _fmt_float(v) -> str:
    return repr(round(float(v), 8)) if np.isfinite(v) else str(v)


def _construct_bitset(values: Sequence[int]) -> List[int]:
    """``Common::ConstructBitset`` analog (utils/common.h)."""
    if len(values) == 0:
        return [0]
    words = [0] * (max(values) // 32 + 1)
    for v in values:
        words[v // 32] |= (1 << (v % 32))
    return words


def _bitset_to_values(words: Sequence[int]) -> List[int]:
    """Expand a LightGBM uint32 bitset into its member values."""
    out = []
    for wi, w in enumerate(words):
        w = int(w)
        base = wi * 32
        while w:
            b = (w & -w).bit_length() - 1
            out.append(base + b)
            w &= w - 1
    return out


def _bitset_contains(words: Sequence[int], v: int) -> bool:
    w = v // 32
    return w < len(words) and bool(words[w] & (1 << (v % 32)))


# ---------------------------------------------------------------------------
# Device-side stacked model for jit prediction
# ---------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
class StackedTrees(NamedTuple):
    """All trees of a model packed into ``[T, ...]`` arrays (device pytree).

    ``max_depth`` is static aux data (it bounds the jitted walk loop),
    so the prediction programs cache across calls."""

    def tree_flatten(self):
        return (tuple(self[:-1]), self.max_depth)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, aux)

    split_feature: jnp.ndarray    # [T, M] inner feature idx
    threshold_bin: jnp.ndarray    # [T, M]
    left_child: jnp.ndarray       # [T, M]
    right_child: jnp.ndarray      # [T, M]
    leaf_value: jnp.ndarray       # [T, L] float32
    default_left: jnp.ndarray     # [T, M] bool
    is_categorical: jnp.ndarray   # [T, M] bool
    cat_bin_mask: jnp.ndarray     # [T, M, B] bool: left bins (B=1 if no cat)
    max_depth: int                # static


def stack_trees(trees: Sequence[Tree], max_bins: int = 1,
                pad_leaves: int = 0) -> StackedTrees:
    """Pack host trees into padded device arrays for vectorized prediction.

    ``pad_leaves`` pads the leaf axis to a caller-stable size so repeated
    single-tree predictions (DART drop sets, rollback, valid replay)
    reuse one compiled program instead of recompiling per tree shape.
    """
    T = len(trees)
    L = max(max(t.num_leaves for t in trees), 2, pad_leaves) if T else 2
    M = L - 1
    any_cat = any(t.num_cat > 0 for t in trees)
    B = max_bins if any_cat else 1
    sf = np.zeros((T, M), np.int32)
    tb = np.zeros((T, M), np.int32)
    lc = np.zeros((T, M), np.int32)
    rc = np.zeros((T, M), np.int32)
    lv = np.zeros((T, L), np.float32)
    dl = np.zeros((T, M), bool)
    ic = np.zeros((T, M), bool)
    cm = np.zeros((T, M, B), bool)
    for i, t in enumerate(trees):
        m = t.num_leaves - 1
        if m == 0:
            # stump: both children point at leaf 0
            lc[i, 0] = rc[i, 0] = ~0
            lv[i, 0] = t.leaf_value[0]
            continue
        sf[i, :m] = t.split_feature_inner[:m]
        tb[i, :m] = t.threshold_bin[:m]
        lc[i, :m] = t.left_child[:m]
        rc[i, :m] = t.right_child[:m]
        lv[i, :t.num_leaves] = t.leaf_value[:t.num_leaves]
        dl[i, :m] = (t.decision_type[:m] & K_DEFAULT_LEFT_MASK) != 0
        ic[i, :m] = (t.decision_type[:m] & K_CATEGORICAL_MASK) != 0
        for node in range(m):
            if ic[i, node]:
                bins = t.cat_left_bins[t.threshold_bin[node]]
                cm[i, node, bins[bins < B]] = True
    depth = max(max((t.max_depth for t in trees), default=1), 1)
    # round the walk depth to a power of two: the fori_loop length is a
    # static program parameter, so raw depths recompile per tree
    depth = 1 << (depth - 1).bit_length()
    return StackedTrees(jnp.asarray(sf), jnp.asarray(tb), jnp.asarray(lc),
                        jnp.asarray(rc), jnp.asarray(lv), jnp.asarray(dl),
                        jnp.asarray(ic), jnp.asarray(cm), depth)


def _sum_tree_axis(per_tree):
    """Sum per-tree score contributions over the tree axis.

    Trees are replicated model state — the tree axis is never
    partitioned across devices or row blocks, so the operand order is
    partition-independent and raw ``jnp.sum`` is sanctioned here
    (tools/numcheck/reduction_registry.py)."""
    return jnp.sum(per_tree, axis=0)


@functools.partial(jax.jit, static_argnames=("start_tree", "num_trees"))
def predict_binned(stacked: StackedTrees, bins: jnp.ndarray,
                   nan_bins: jnp.ndarray, zero_bins: jnp.ndarray,
                   missing_types: jnp.ndarray,
                   start_tree: int = 0, num_trees: Optional[int] = None,
                   feat_group: Optional[jnp.ndarray] = None,
                   feat_offset: Optional[jnp.ndarray] = None,
                   num_bins: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Sum of tree outputs over binned rows — jittable, vectorized.

    Args:
      bins: ``[n, F]`` binned matrix.
      nan_bins: ``[F]`` NaN-bin id per feature (num_bins-1) or -1.
      zero_bins: ``[F]`` bin containing 0.0 per feature.
      missing_types: ``[F]`` MissingType per feature.

    Returns ``[n]`` float32 raw scores.
    """
    trees = jax.tree.map(
        lambda a: a[start_tree:None if num_trees is None else start_tree + num_trees]
        if isinstance(a, jnp.ndarray) else a, stacked._replace(max_depth=0))
    depth = stacked.max_depth

    def one_tree(sf, tb, lc, rc, lv, dl, ic, cm):
        leaf = _tree_leaf_indices(bins, sf, tb, lc, rc, dl, ic, cm,
                                  nan_bins, zero_bins, missing_types, depth,
                                  feat_group, feat_offset, num_bins)
        return lv[leaf]

    per_tree = jax.vmap(one_tree)(
        trees.split_feature, trees.threshold_bin, trees.left_child,
        trees.right_child, trees.leaf_value, trees.default_left,
        trees.is_categorical, trees.cat_bin_mask)          # [T, n]
    return _sum_tree_axis(per_tree)


def build_path_matrices(trees: Sequence[Tree], pad_leaves: int = 0
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-tree leaf-path matrices for the matmul predictor.

    ``P[i, l, m]`` is +1 / -1 when node ``m`` is an ancestor of leaf
    ``l`` in tree ``i`` and the path goes left / right there, else 0;
    ``pathlen[i, l]`` is the leaf's depth (-1 for unused leaf slots, so
    they can never be selected).  A row's leaf is then the unique ``l``
    with ``sum_m P[l, m] * (2*go_left[m] - 1) == pathlen[l]``.
    """
    T = len(trees)
    L = max(max((t.num_leaves for t in trees), default=2), 2, pad_leaves)
    M = L - 1
    P = np.zeros((T, L, M), np.int8)
    plen = np.full((T, L), -1, np.int32)
    for i, t in enumerate(trees):
        if t.num_leaves <= 1:
            plen[i, 0] = 0          # stump: zero-length path matches
            continue
        stack = [(0, [])]
        while stack:
            m, anc = stack.pop()
            for child, d in ((int(t.left_child[m]), 1),
                             (int(t.right_child[m]), -1)):
                path = anc + [(m, d)]
                if child < 0:
                    leaf = ~child
                    for mm, dd in path:
                        P[i, leaf, mm] = dd
                    plen[i, leaf] = len(path)
                else:
                    stack.append((child, path))
    return P, plen


@functools.partial(jax.jit, static_argnames=("tchunk", "rchunk"))
def predict_binned_matmul(stacked: StackedTrees,
                          P: jnp.ndarray, plen: jnp.ndarray,
                          bins: jnp.ndarray,
                          nan_bins: jnp.ndarray, zero_bins: jnp.ndarray,
                          missing_types: jnp.ndarray,
                          *, tchunk: int = 16,
                          rchunk: int = 4096) -> jnp.ndarray:
    """Sum of tree outputs as PURE MATMULS — the TPU-native predictor.

    The gather walk (``_tree_leaf_indices``) serializes ``depth`` levels
    of row gathers: at 500 deep trees x 2*10^5 rows it runs for minutes
    and long single dispatches fault the TPU worker.  Here every node
    decision is evaluated at once and the leaf emerges from one
    path-agreement contraction — no gathers, no depth loop:

      * ``c  = onehot(split_feature) @ bins^T``  (each node's bin value;
        f32 operands, so bin ids past 256 stay exact — reference
        prediction covers all bin widths uniformly, tree.h:112+),
      * per-node missing metadata via the same one-hot against the
        per-feature tables,
      * ``d2 = +-1`` decisions — numerical by threshold compare,
        categorical by a gather-free fold over the bin axis against the
        per-node left-bin bitset ``cat_bin_mask`` (same semantics as
        the walk: the bitset decides, missing bins simply aren't in the
        set; a take_along_axis here compiled to a generalized gather
        that faulted the TPU worker at scale),
      * ``S = P @ d2``; a row lands in leaf l iff ``S[l] == pathlen[l]``
        (exact: ±1 products, f32 MXU accumulation),
      * output = leaf one-hot contracted with leaf values (hi+lo bf16
        pair for ~f32 accuracy).

    ``lax.map`` over (tree-chunk, row-block) keeps the ``[tc, M, rc]``
    intermediates bounded inside ONE compiled program.  Callers gate:
    unbundled columns only (EFB models take the chunked walk).
    """
    T, L = plen.shape
    M = P.shape[2]
    n, F = bins.shape
    tc = min(tchunk, max(T, 1))
    rc = min(rchunk, max(n, 1))
    TC = -(-T // tc)
    RC = -(-n // rc)

    def padT(a, fill):
        pad = TC * tc - T
        if pad == 0:
            return a
        return jnp.concatenate(
            [a, jnp.full((pad,) + a.shape[1:], fill, a.dtype)])

    any_cat = stacked.cat_bin_mask.shape[2] > 1   # B=1 when no cat splits
    chunks = {
        "sf": padT(stacked.split_feature, 0),
        "tb": padT(stacked.threshold_bin, 0),
        "dl": padT(stacked.default_left, False),
        "lv": padT(stacked.leaf_value, 0.0),
        "P": padT(jnp.asarray(P), 0),
        "plen": padT(jnp.asarray(plen), -1),   # -1: never matches
    }
    if any_cat:
        chunks["ic"] = padT(stacked.is_categorical, False)
        chunks["cm"] = padT(stacked.cat_bin_mask, False)
    chunks = {k: v.reshape((TC, tc) + v.shape[1:])
              for k, v in chunks.items()}

    binsT = bins.T.astype(jnp.float32)                   # [F, n]
    n_pad = RC * rc
    if n_pad != n:
        binsT = jnp.concatenate(
            [binsT, jnp.zeros((F, n_pad - n), jnp.float32)], axis=1)
    blocks = binsT.reshape(F, RC, rc).transpose(1, 0, 2)  # [RC, F, rc]

    # per-feature metadata table for the node-level one-hot contraction
    fmeta = jnp.stack([nan_bins.astype(jnp.float32),
                       zero_bins.astype(jnp.float32),
                       missing_types.astype(jnp.float32)], axis=1)  # [F, 3]

    def row_block(blk):                                   # [F, rc]
        def tree_chunk(c):
            sf = c["sf"]                                  # [tc, M]
            # f32 one-hot selects: bin ids (and the sentinel) stay exact
            # past 256, unlike bf16 operands; the select einsums are a
            # rounding error of the path contraction's FLOPs
            ohSF = (sf[:, :, None]
                    == jnp.arange(F)[None, None, :]).astype(jnp.float32)
            cc = jnp.einsum("tmf,fr->tmr", ohSF, blk,
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
            meta = jnp.einsum("tmf,fk->tmk", ohSF, fmeta,
                              preferred_element_type=jnp.float32,
                              precision=jax.lax.Precision.HIGHEST)
            nanb = meta[:, :, 0:1]
            db = meta[:, :, 1:2]
            mt = meta[:, :, 2:3]
            is_missing = (((mt == float(MISSING_NAN)) & (cc == nanb))
                          | ((mt == float(MISSING_ZERO)) & (cc == db)))
            tb = c["tb"].astype(jnp.float32)[:, :, None]
            dec = jnp.where(is_missing, c["dl"][:, :, None], cc <= tb)
            if any_cat:
                # categorical: bitset membership WITHOUT a gather — a
                # take_along_axis here compiled to a generalized gather
                # that faulted the TPU worker at 200k rows x 500 trees
                # (the same fault class the matmul predictor exists to
                # avoid); instead fold over the bin axis with dynamic
                # slices: Bc (<=258) iterations of [tc, M, rc] compares
                Bc = c["cm"].shape[2]
                idx = jnp.minimum(cc.astype(jnp.int32), Bc - 1)

                def cat_body(b, acc):
                    hit = (idx == b) & c["cm"][:, :, b][:, :, None]
                    return acc | hit

                dec_cat = jax.lax.fori_loop(
                    0, Bc, cat_body, jnp.zeros(idx.shape, bool))
                dec = jnp.where(c["ic"][:, :, None], dec_cat, dec)
            d2 = jnp.where(dec, 1.0, -1.0).astype(jnp.bfloat16)
            S = jnp.einsum("tlm,tmr->tlr",
                           c["P"].astype(jnp.bfloat16), d2,
                           preferred_element_type=jnp.float32)
            oh = (S == c["plen"].astype(jnp.float32)[:, :, None])
            from ..ops.pallas_histogram import split_hi_lo
            lv_hi_f, lv_lo_f = split_hi_lo(c["lv"].astype(jnp.float32))
            lv_hi = lv_hi_f.astype(jnp.bfloat16)
            lv_lo = lv_lo_f.astype(jnp.bfloat16)
            ohb = oh.astype(jnp.bfloat16)
            out = jnp.einsum("tl,tlr->r", lv_hi, ohb,
                             preferred_element_type=jnp.float32)
            out += jnp.einsum("tl,tlr->r", lv_lo, ohb,
                              preferred_element_type=jnp.float32)
            return out                                    # [rc]
        return jnp.sum(jax.lax.map(tree_chunk, chunks), axis=0)

    out = jax.lax.map(row_block, blocks)                  # [RC, rc]
    return out.reshape(n_pad)[:n]


@functools.partial(jax.jit, static_argnames=("tchunk", "rchunk"))
def predict_binned_chunked(stacked: StackedTrees, bins: jnp.ndarray,
                           nan_bins: jnp.ndarray, zero_bins: jnp.ndarray,
                           missing_types: jnp.ndarray,
                           feat_group: Optional[jnp.ndarray] = None,
                           feat_offset: Optional[jnp.ndarray] = None,
                           num_bins: Optional[jnp.ndarray] = None,
                           *, tchunk: int = 128,
                           rchunk: int = 1 << 16) -> jnp.ndarray:
    """Sum of tree outputs with BOUNDED walk state: ``lax.map`` over
    (tree-chunk, row-chunk) blocks inside ONE compiled program.

    One unchunked vmapped walk over hundreds of deep 255-leaf trees at
    6-figure row counts faults the TPU worker (its ``[T, n]`` node state
    and per-level gather temporaries); a host-side chunk loop recompiles
    per ragged tail shape and pays a dispatch per block.  Here trees are
    padded with stumps (children ``~0`` -> leaf 0, value 0) and rows
    with zeros to chunk multiples, so the per-step footprint is
    ``[tchunk, rchunk]`` and everything runs in one dispatch.
    """
    T = stacked.split_feature.shape[0]
    n = bins.shape[0]
    depth = stacked.max_depth
    tc = min(tchunk, max(T, 1))
    rc_sz = min(rchunk, max(n, 1))
    TC = -(-T // tc)
    RC = -(-n // rc_sz)

    def pad_tree(a, fill):
        pad = TC * tc - T
        if pad == 0:
            return a
        shape = (pad,) + a.shape[1:]
        return jnp.concatenate([a, jnp.full(shape, fill, a.dtype)])

    arrs = {
        "sf": pad_tree(stacked.split_feature, 0),
        "tb": pad_tree(stacked.threshold_bin, 0),
        "lc": pad_tree(stacked.left_child, ~0),     # stump: -> leaf 0
        "rc": pad_tree(stacked.right_child, ~0),
        "lv": pad_tree(stacked.leaf_value, 0.0),    # leaf 0 emits 0
        "dl": pad_tree(stacked.default_left, False),
        "ic": pad_tree(stacked.is_categorical, False),
        "cm": pad_tree(stacked.cat_bin_mask, False),
    }
    chunked = {k: v.reshape((TC, tc) + v.shape[1:])
               for k, v in arrs.items()}
    n_pad = RC * rc_sz
    bins_p = bins if n_pad == n else jnp.concatenate(
        [bins, jnp.zeros((n_pad - n,) + bins.shape[1:], bins.dtype)])
    bins_blocks = bins_p.reshape((RC, rc_sz) + bins.shape[1:])

    def row_block(rows):
        def tree_block(c):
            def one_tree(sf, tb, lc, rc, lv, dl, ic, cm):
                leaf = _tree_leaf_indices(
                    rows, sf, tb, lc, rc, dl, ic, cm, nan_bins, zero_bins,
                    missing_types, depth, feat_group, feat_offset, num_bins)
                return lv[leaf]
            per = jax.vmap(one_tree)(c["sf"], c["tb"], c["lc"], c["rc"],
                                     c["lv"], c["dl"], c["ic"], c["cm"])
            return jnp.sum(per, axis=0)             # [rc_sz]
        return jnp.sum(jax.lax.map(tree_block, chunked), axis=0)

    out = jax.lax.map(row_block, bins_blocks)       # [RC, rc_sz]
    return out.reshape(n_pad)[:n]


@jax.jit
def predict_leaf_binned(stacked: StackedTrees, bins: jnp.ndarray,
                        nan_bins: jnp.ndarray, zero_bins: jnp.ndarray,
                        missing_types: jnp.ndarray,
                        feat_group: Optional[jnp.ndarray] = None,
                        feat_offset: Optional[jnp.ndarray] = None,
                        num_bins: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Per-tree leaf index per row (``PredictLeafIndex``) -> [n, T]."""
    def one_tree(sf, tb, lc, rc, lv, dl, ic, cm):
        return _tree_leaf_indices(bins, sf, tb, lc, rc, dl, ic, cm,
                                  nan_bins, zero_bins, missing_types,
                                  stacked.max_depth,
                                  feat_group, feat_offset, num_bins)

    leaves = jax.vmap(one_tree)(
        stacked.split_feature, stacked.threshold_bin, stacked.left_child,
        stacked.right_child, stacked.leaf_value, stacked.default_left,
        stacked.is_categorical, stacked.cat_bin_mask)
    return leaves.T


def _tree_leaf_indices(bins, sf, tb, lc, rc, dl, ic, cm,
                       nan_bins, zero_bins, missing_types, depth,
                       feat_group=None, feat_offset=None, num_bins=None):
    n = bins.shape[0]
    node = jnp.zeros(n, jnp.int32)

    def body(_, node):
        is_leaf = node < 0
        nidx = jnp.maximum(node, 0)
        f = sf[nidx]                                    # [n]
        col = f if feat_group is None else feat_group[f]
        b = jnp.take_along_axis(
            bins, col[:, None], axis=1)[:, 0].astype(jnp.int32)
        if feat_offset is not None:
            from ..ops.pallas_route import unbundle_bin
            b = unbundle_bin(b, feat_offset[f], num_bins[f], zero_bins[f])
        mt = missing_types[f]
        is_missing = (((mt == MISSING_NAN) & (b == nan_bins[f]))
                      | ((mt == MISSING_ZERO) & (b == zero_bins[f])))
        num_left = jnp.where(is_missing, dl[nidx], b <= tb[nidx])
        cat_left = cm[nidx, jnp.minimum(b, cm.shape[-1] - 1)]
        go_left = jnp.where(ic[nidx], cat_left, num_left)
        nxt = jnp.where(go_left, lc[nidx], rc[nidx])
        return jnp.where(is_leaf, node, nxt)

    node = jax.lax.fori_loop(0, depth, body, node)
    # any still-internal nodes (shouldn't happen) -> leaf 0
    return jnp.where(node < 0, ~node, 0)
