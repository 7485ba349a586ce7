"""User-facing Dataset and Booster classes.

API parity with the reference Python package
(`/root/reference/python-package/lightgbm/basic.py`: ``Dataset``
`basic.py:572`, ``Booster`` `basic.py:1264`) — same constructor signatures
and core methods, so reference users can switch imports.  Unlike the
reference (ctypes over a C core), the data pipeline here is
numpy→binning→HBM and the booster drives the jitted JAX training step
directly; pandas input is handled the same way (categorical dtype columns
auto-detected, `basic.py:239-305`).
"""
from __future__ import annotations

import copy
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from .config import Config, canonicalize_params
from .io.dataset import BinnedDataset, Metadata
from .utils.log import log_info, log_warning


def _data_to_numpy(data):
    """Accept numpy / pandas / list-of-lists / scipy-CSR-like."""
    if hasattr(data, "toarray"):          # scipy sparse
        return np.asarray(data.toarray(), np.float64), None
    if hasattr(data, "dtypes") and hasattr(data, "columns"):   # pandas
        import pandas as pd               # local import; optional dep
        df = data
        cat_cols = [i for i, dt in enumerate(df.dtypes)
                    if str(dt) == "category"]
        out = np.empty((len(df), df.shape[1]), np.float64)
        for i, col in enumerate(df.columns):
            s = df[col]
            if str(s.dtype) == "category":
                out[:, i] = s.cat.codes.astype(np.float64)
            else:
                out[:, i] = pd.to_numeric(s, errors="coerce").astype(np.float64)
        names = [str(c) for c in df.columns]
        return out, {"categorical": cat_cols, "names": names}
    arr = np.asarray(data)
    if arr.dtype == np.object_:
        arr = arr.astype(np.float64)
    return arr, None


class Dataset:
    """Training data wrapper (reference basic.py:572-1262 API surface)."""

    def __init__(self, data, label=None, reference=None, weight=None,
                 group=None, init_score=None, feature_name="auto",
                 categorical_feature="auto", params=None,
                 free_raw_data=True, silent=False):
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params or {})
        self.free_raw_data = free_raw_data
        self._constructed: Optional[BinnedDataset] = None
        self.used_indices: Optional[np.ndarray] = None

    # -- construction ---------------------------------------------------
    def construct(self) -> "Dataset":
        if self._constructed is not None:
            return self
        from .obs import span
        with span("io.construct") as sp:
            self._construct()
            ds = self._constructed
            sp["rows"], sp["features"] = ds.num_data, ds.num_total_features
        return self

    def _construct(self) -> None:
        if self.reference is not None:
            ref = self.reference.construct()._constructed
        else:
            ref = None
        if isinstance(self.data, str):
            from .io.loader import load_file
            cfg = Config.from_params(self.params)
            rank, world, ag = 0, 1, None
            if cfg.num_machines > 1 and ref is None:
                import jax
                if jax.process_count() > 1:
                    # distributed file load: mod-rank row sharding +
                    # feature-sharded bin-find allgather — ONLY for the
                    # row-sharding learners.  Feature-parallel keeps the
                    # full rows on every machine (reference semantics,
                    # feature_parallel_tree_learner.cpp), and serial
                    # must too (sharding it would silently train each
                    # rank on 1/world of the data)
                    if cfg.tree_learner in ("data", "voting"):
                        from .io.distributed import jax_process_allgather
                        rank = jax.process_index()
                        world = jax.process_count()
                        ag = jax_process_allgather
            ds = load_file(self.data, cfg, reference=ref,
                           rank=rank, num_machines=world, allgather=ag)
            if self.label is None and ds.metadata.label is not None:
                pass
            self._constructed = ds
            self._apply_fields()
            return
        X, pd_info = _data_to_numpy(self.data)
        cat = []
        names = None
        if pd_info is not None:
            names = pd_info["names"]
            if self.categorical_feature == "auto":
                cat = pd_info["categorical"]
        if self.categorical_feature not in ("auto", None):
            cat = [names.index(c) if isinstance(c, str) and names else int(c)
                   for c in self.categorical_feature]
        if isinstance(self.feature_name, (list, tuple)):
            names = list(self.feature_name)
        cfg = Config.from_params(self.params)
        md = Metadata()
        self._constructed = BinnedDataset.from_raw(
            X, cfg, categorical_features=cat, feature_names=names,
            reference=ref, metadata=md)
        self._apply_fields()
        if self.free_raw_data:
            self.data = None

    def _apply_fields(self):
        md = self._constructed.metadata
        if self.label is not None:
            md.set_field("label", np.asarray(self.label).reshape(-1))
        if self.weight is not None:
            md.set_field("weight", self.weight)
        if self.group is not None:
            md.set_field("group", self.group)
        if self.init_score is not None:
            md.set_field("init_score", self.init_score)

    # -- reference API surface ------------------------------------------
    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None):
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score,
                       params=params or self.params)

    def subset(self, used_indices, params=None):
        self.construct()
        sub = Dataset.__new__(Dataset)
        sub.__dict__.update({k: v for k, v in self.__dict__.items()})
        sub._constructed = self._constructed.subset(np.asarray(used_indices))
        sub.used_indices = np.asarray(used_indices)
        sub.reference = self
        return sub

    def set_field(self, name, data):
        self.construct()
        self._constructed.metadata.set_field(name, data)

    def get_field(self, name):
        self.construct()
        return self._constructed.metadata.get_field(name)

    def set_label(self, label):
        self.label = label
        if self._constructed is not None:
            self._constructed.metadata.set_field("label", label)

    def set_weight(self, weight):
        self.weight = weight
        if self._constructed is not None:
            self._constructed.metadata.set_field("weight", weight)

    def set_group(self, group):
        self.group = group
        if self._constructed is not None:
            self._constructed.metadata.set_field("group", group)

    def set_init_score(self, init_score):
        self.init_score = init_score
        if self._constructed is not None:
            self._constructed.metadata.set_field("init_score", init_score)

    def get_label(self):
        return self.get_field("label")

    def get_weight(self):
        return self.get_field("weight")

    def get_group(self):
        qb = self.get_field("group")
        return None if qb is None else np.diff(qb)

    def get_init_score(self):
        return self.get_field("init_score")

    def num_data(self) -> int:
        self.construct()
        return self._constructed.num_data

    def num_feature(self) -> int:
        self.construct()
        return self._constructed.num_total_features

    def save_binary(self, filename: str):
        self.construct()
        self._constructed.save_binary(filename)

    @property
    def feature_names(self):
        self.construct()
        return self._constructed.feature_names


class Booster:
    """Trained model handle (reference basic.py:1264+ API surface)."""

    def __init__(self, params=None, train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None, silent=False):
        params = dict(params or {})
        self.params = params
        self.best_iteration = -1
        self.best_score: Dict = {}
        self._train_dataset = train_set
        if train_set is not None:
            train_set.construct()
            cfg = Config.from_params(params)
            # the first import of the boosting package (the learner, the
            # Pallas kernels) is seconds of a process's first training call
            from .obs import span
            with span("engine.import"):
                from .boosting.variants import create_boosting
            self._gbdt = create_boosting(cfg, train_set._constructed,
                                         fobj=cfg.extra.get("fobj"))
            self._valid_sets: List[Dataset] = []
            self._name_valid_sets: List[str] = []
        elif model_file is not None:
            from .utils.file_io import open_read
            with open_read(model_file) as f:
                text = f.read()
            self._init_from_string(text)
        elif model_str is not None:
            self._init_from_string(model_str)
        else:
            raise ValueError(
                "need at least one of train_set, model_file, model_str")

    def _init_from_string(self, text):
        from .boosting.gbdt import GBDT
        cfg = Config.from_params(self.params)
        self._gbdt = GBDT(cfg, None)
        self._gbdt.load_model_from_string(text)
        self._valid_sets = []
        self._name_valid_sets = []

    # -- training -------------------------------------------------------
    def add_valid(self, data: Dataset, name: str):
        data.construct()
        self._gbdt.add_valid(data._constructed, name)
        self._valid_sets.append(data)
        self._name_valid_sets.append(name)
        return self

    def update(self, train_set=None, fobj=None):
        """One boosting iteration; returns True if fully trained
        (reference Booster.update, basic.py)."""
        if fobj is not None:
            score = self._gbdt.scores
            import jax.numpy as jnp
            K = self._gbdt.num_tree_per_iteration
            s = (np.asarray(score).reshape(-1, order="F") if K > 1
                 else np.asarray(score[:, 0]))
            grad, hess = fobj(s, self._train_dataset)
            grad = np.asarray(grad, np.float32).reshape(-1, K, order="F")
            hess = np.asarray(hess, np.float32).reshape(-1, K, order="F")
            return self._gbdt.train_one_iter(jnp.asarray(grad),
                                             jnp.asarray(hess))
        return self._gbdt.train_one_iter()

    def rollback_one_iter(self):
        self._gbdt.rollback_one_iter()
        return self

    @property
    def current_iteration(self):
        return self._gbdt.current_iteration

    def num_trees(self):
        return self._gbdt.num_trees()

    def digest(self, include_scores: bool = True) -> str:
        """Canonical model/score sha256 — the reproducibility contract's
        unit of comparison (``obs/determinism.py``): identical data +
        config + seeds must reproduce this digest bit-for-bit.  Pass
        ``include_scores=False`` to hash the model alone (e.g. after
        ``free_dataset()`` the score state is gone)."""
        return self._gbdt.digest(include_scores=include_scores)

    # -- evaluation -----------------------------------------------------
    def eval_train(self, feval=None):
        name = getattr(self, "_train_data_name", "training")
        results = [(name, m, v, h) for _, m, v, h in self._gbdt.eval_train()]
        return self._format_eval(results, feval, name, self._train_dataset)

    def eval_valid(self, feval=None):
        out = self._format_eval(self._gbdt.eval_valid(), feval, None, None)
        if feval is not None:
            for i, vs in enumerate(self._valid_sets):
                out.extend(self._custom_eval(
                    feval, self._name_valid_sets[i], vs,
                    np.asarray(self._gbdt._valid_scores[i])))
        return out

    def _format_eval(self, results, feval, train_name, train_set):
        out = [(name, metric, val, hib) for name, metric, val, hib in results]
        if feval is not None and train_name is not None:
            out.extend(self._custom_eval(feval, train_name, train_set,
                                         np.asarray(self._gbdt.scores)))
        return out

    def _custom_eval(self, feval, name, dataset, scores):
        # a feval takes host scores: the fetched rows are counted beside
        # those of the metrics with no device form (GBDT._eval_set)
        from .obs import counter_add
        counter_add("gbdt.eval_host_rows", int(scores.shape[0]))
        s = scores if scores.shape[1] > 1 else scores[:, 0]
        res = feval(s, dataset)
        if isinstance(res, tuple):
            res = [res]
        return [(name, mn, mv, hib) for mn, mv, hib in res]

    # -- prediction -----------------------------------------------------
    def predict(self, data, num_iteration=-1, raw_score=False,
                pred_leaf=False, pred_contrib=False, device=None,
                **kwargs):
        """Predict (reference Booster.predict surface).

        ``num_iteration`` (``<= 0`` -> ``best_iteration`` when set)
        truncates EVERY mode identically — the slicing lives in one
        place per path (``GBDT.predict_raw`` / ``GBDT.predict_leaf`` /
        ``serve.compile_model``), multiclass included.

        ``device`` selects the serving path: ``True`` compiles the
        model once (cached per truncation) into the TPU-resident
        tensorized predictor (``lightgbm_tpu/serve/``) and scores the
        whole batch in one jitted dispatch; ``False`` forces the
        legacy path; ``None`` (default) follows the
        ``LGBM_TPU_PREDICT_DEVICE`` env var (off by default).
        ``pred_contrib`` always takes the host path.
        """
        X, _ = _data_to_numpy(data)
        if num_iteration is None or num_iteration <= 0:
            num_iteration = (self.best_iteration
                             if self.best_iteration > 0 else -1)
        if device is None:
            import os
            device = os.environ.get("LGBM_TPU_PREDICT_DEVICE",
                                    "") not in ("", "0")
        if pred_contrib:
            from .boosting.contrib import predict_contrib
            return predict_contrib(self._gbdt, X, num_iteration)
        if device:
            cm = self._device_predictor(num_iteration)
            if pred_leaf:
                return cm.leaf_indices(X)
            return cm.predict(X, raw_score=raw_score)
        if pred_leaf:
            return self._gbdt.predict_leaf(X, num_iteration=num_iteration)
        return self._gbdt.predict(X, raw_score=raw_score,
                                  num_iteration=num_iteration)

    def _device_predictor(self, num_iteration=-1):
        """The serving-compiled form of this model, cached per
        (model length, truncation) — training another iteration or
        rolling back invalidates by key."""
        from .serve import compile_model
        key = (len(self._gbdt.models), int(num_iteration or -1))
        cache = getattr(self, "_serve_cache", None)
        if cache is None or key not in cache:
            # single-entry cache: stale packs from previous lengths
            # would otherwise pin device memory
            self._serve_cache = {key: compile_model(
                self._gbdt, num_iteration=num_iteration)}
        return self._serve_cache[key]

    def refit(self, data, label, decay_rate: float = 0.9,
              **kwargs) -> "Booster":
        """Re-fit the existing tree structures' leaf values on new data
        (reference python-package ``Booster.refit`` over
        ``LGBM_BoosterRefit`` / RefitTree, gbdt.cpp:268-280):
        ``new_leaf = decay_rate * old + (1 - decay_rate) * refit``.
        Returns a NEW Booster; this one is untouched.  ``kwargs`` apply
        to BOTH the refit dataset and the new booster's config
        (lambda_l1/l2 etc. steer the refit leaf estimates)."""
        params = dict(self.params)
        params.update(kwargs)
        new = Booster(params=params, model_str=self.model_to_string())
        if kwargs:
            new._gbdt.reset_config(params)
        ds = Dataset(data, label=label, params=params)
        ds.construct()
        new._gbdt.refit_dataset(ds._constructed, decay_rate=decay_rate)
        return new

    # -- model IO -------------------------------------------------------
    def save_model(self, filename, num_iteration=-1):
        if num_iteration is None or num_iteration <= 0:
            num_iteration = (self.best_iteration
                             if self.best_iteration > 0 else -1)
        self._gbdt.save_model(filename, num_iteration)
        return self

    def model_to_string(self, num_iteration=-1):
        return self._gbdt.save_model_to_string(num_iteration or -1)

    def model_from_string(self, model_str, verbose=True):
        self._init_from_string(model_str)
        return self

    def dump_model(self, num_iteration=-1):
        """JSON dump (reference DumpModel, gbdt_model_text.cpp:15-49)."""
        g = self._gbdt
        trees = []
        T = len(g.models)
        if num_iteration and num_iteration > 0:
            T = min(T, num_iteration * g.num_tree_per_iteration)
        for i, t in enumerate(g.models[:T]):
            trees.append({
                "tree_index": i,
                "num_leaves": t.num_leaves,
                "num_cat": t.num_cat,
                "shrinkage": t.shrinkage_rate,
                "tree_structure": _tree_to_json(t, 0),
            })
        return {
            "name": "tree",
            "version": "v2",
            "num_class": g.num_class,
            "num_tree_per_iteration": g.num_tree_per_iteration,
            "label_index": 0,
            "max_feature_idx": g.max_feature_idx,
            "feature_names": g.feature_names,
            "objective": (g.objective.to_string() if g.objective else ""),
            "average_output": g.average_output,
            "tree_info": trees,
        }

    def feature_importance(self, importance_type="split", iteration=-1):
        return self._gbdt.feature_importance(importance_type, iteration or -1)

    def feature_name(self):
        return list(self._gbdt.feature_names)

    def num_feature(self):
        return self._gbdt.max_feature_idx + 1

    def free_dataset(self):
        self._train_dataset = None
        return self

    def __copy__(self):
        return self.__deepcopy__(None)

    def __deepcopy__(self, memo):
        return Booster(params=self.params,
                       model_str=self.model_to_string())

    def __getstate__(self):
        state = {"params": self.params,
                 "model_str": self.model_to_string(),
                 "best_iteration": self.best_iteration,
                 "best_score": self.best_score}
        return state

    def __setstate__(self, state):
        self.params = state["params"]
        self.best_iteration = state.get("best_iteration", -1)
        self.best_score = state.get("best_score", {})
        self._init_from_string(state["model_str"])
        self._train_dataset = None


def _tree_to_json(t, node):
    if t.num_leaves == 1:
        return {"leaf_value": float(t.leaf_value[0])}
    if node < 0:
        leaf = ~node
        return {"leaf_index": int(leaf),
                "leaf_value": float(t.leaf_value[leaf]),
                "leaf_count": int(t.leaf_count[leaf])}
    is_cat = bool(t.decision_type[node] & 1)
    d = {
        "split_index": int(node),
        "split_feature": int(t.split_feature[node]),
        "split_gain": float(t.split_gain[node]),
        "threshold": float(t.threshold[node]),
        "decision_type": "==" if is_cat else "<=",
        "default_left": bool(t.decision_type[node] & 2),
        "missing_type": ["None", "Zero", "NaN"][(t.decision_type[node] >> 2) & 3],
        "internal_value": float(t.internal_value[node]),
        "internal_count": int(t.internal_count[node]),
        "left_child": _tree_to_json(t, int(t.left_child[node])),
        "right_child": _tree_to_json(t, int(t.right_child[node])),
    }
    return d
