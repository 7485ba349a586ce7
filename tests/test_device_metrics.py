"""The binary metrics worked out where the scores live (ISSUE 33).

``metric/device.py`` against the host functions of ``metric/metrics.py``
(AUC to the last digits, ties and weights and all; log-loss to float32's
rounding), and through ``GBDT._eval_set``: the same names in the same
order on the fast path and the callback path, no score row fetched, the
same early-stopping iteration as with the host metrics, no tree changed
by watching a held-out set; the plain reference of the benchmark's
``train_eval`` cell against the system at a small size; the names the
new device work carries.
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.boosting.gbdt import GBDT
from lightgbm_tpu.config import Config
from lightgbm_tpu.metric import device
from lightgbm_tpu.metric.metrics import (AucMetric, BinaryErrorMetric,
                                         BinaryLoglossMetric, binary_auc)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FORMS = ("binary_logloss", "auc", "binary_error")


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.reset()
    yield
    obs.reset()


def _case(rows: int, kind: str, seed: int = 0):
    rng = np.random.RandomState(seed + rows)
    score = rng.normal(size=rows).astype(np.float32)
    label = (rng.rand(rows) < 0.3).astype(np.float32)
    weight = None
    if kind == "ties":
        score = np.round(score).clip(-1, 1).astype(np.float32)  # 3 values
    elif kind == "one_class":
        label[:] = 1.0
    elif kind == "weights":
        weight = rng.uniform(0.01, 2.0, size=rows).astype(np.float32)
    return score, label, weight


@pytest.mark.parametrize("kind", ["plain", "ties", "one_class", "weights"])
@pytest.mark.parametrize("rows", [1, 2, 4097, 50000])
def test_device_metrics_equal_the_host_functions(rows, kind):
    score, label, weight = _case(rows, kind)
    es = device.EvalSet(label, weight)
    assert es.usable
    got = es.eval(jnp.asarray(score)[:, None], FORMS, 1.0)
    cfg = Config.from_params({})
    auc = AucMetric(cfg).from_device(got["auc"])[0][1]
    assert abs(auc - binary_auc(label, score, weight)) <= 1e-12
    want = BinaryLoglossMetric(cfg).eval(label, score, weight)[0][1]
    assert abs(got["binary_logloss"] - want) <= 1e-6 * want
    want = BinaryErrorMetric(cfg).eval(label, score, weight)[0][1]
    assert abs(got["binary_error"] - want) <= 1e-7


def test_exact_sum_carries_past_32_bits():
    x = np.full(70001, 0xFFFFFFF0, np.uint32)
    assert device.to_int(jax.jit(device.exact_sum)(jnp.asarray(x))) \
        == 70001 * 0xFFFFFFF0


@pytest.mark.parametrize("weight, limbs", [
    (np.array([1.0, 2.0, 2.0, 0.0], np.float32), 1),
    (np.array([0.5, 0.75, 1.0, 3.0], np.float32), 1),
    (np.array([1.0, 1e-30, 1.0, 1.0], np.float32), None),   # past the limbs
    (np.array([1.0, -1.0, 1.0, 1.0], np.float32), None),
])
def test_weights_as_integers(weight, limbs):
    iw = device.integer_weights(weight, np.array([1, 0, 1, 0], bool))
    if limbs is None:
        assert iw is None
        return
    assert iw.limbs == limbs
    ints = iw.words[0].astype(np.float64) * iw.quantum
    assert np.array_equal(ints, weight.astype(np.float64))
    assert (iw.positive + iw.negative) * iw.quantum == float(weight.sum())


def _sets(n=3000, nv=700, f=8, seed=1):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n + nv, f)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + rng.normal(size=n + nv)
         > 0).astype(np.float32)
    ds = lgb.Dataset(X[:n], label=y[:n])
    return ds, lgb.Dataset(X[n:], label=y[n:], reference=ds)


PARAMS = {"objective": "binary", "metric": "binary_logloss,auc",
          "num_leaves": 15, "min_data_in_leaf": 20, "verbose": -1,
          "bagging_fraction": 0.8, "bagging_freq": 5, "feature_fraction": 0.8}


def _train(path: str, rounds=8, params=PARAMS, **kw):
    """``-> (booster, [(set, metric, value) of every evaluation])``."""
    ds, dv = _sets()
    seen = []
    real = GBDT._eval_set

    def recorded(self, *args):
        out = real(self, *args)
        seen.extend((name, metric, value) for name, metric, value, _ in out)
        return out
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(GBDT, "_eval_set", recorded)
        if path == "callback":
            kw["evals_result"] = {}
        bst = lgb.train(params, ds, rounds, valid_sets=[ds, dv],
                        valid_names=["training", "valid"],
                        verbose_eval=False, **kw)
    if path == "callback":
        result = kw["evals_result"]
        assert [(s, m) for s in result for m in result[s]] == \
            [(s, m) for s, m, _ in seen[:4]]
        assert [result[s][m][-1] for s, m, _ in seen[-4:]] == \
            [v for _, _, v in seen[-4:]]
    return bst, seen


def test_both_paths_report_the_same_metrics_in_the_same_order():
    obs.enable()
    _, fast = _train("fast")
    assert obs.summary()["counters"].get("gbdt.eval_host_rows", 0) == 0
    _, callback = _train("callback")
    summary = obs.summary()
    assert summary["counters"].get("gbdt.eval_host_rows", 0) == 0
    assert summary["counters"]["gbdt.evals"] == 2 * 2 * 8
    assert summary["gauges"]["gbdt.eval_backend"] == "device"
    assert [(s, m) for s, m, _ in fast] == [(s, m) for s, m, _ in callback]
    assert [(s, m) for s, m, _ in fast[:4]] == [
        ("training", "binary_logloss"), ("training", "auc"),
        ("valid", "binary_logloss"), ("valid", "auc")]
    for (_, m, a), (_, _, b) in zip(fast, callback):
        # the two paths' scores differ in their last places
        assert abs(a - b) <= (1e-6 if m == "auc" else 1e-6 * b)


def test_a_metric_without_a_device_form_fetches_the_rows_and_says_so():
    obs.enable()
    _train("fast", rounds=2, params={**PARAMS, "metric": "auc,xentropy"})
    summary = obs.summary()
    assert summary["counters"]["gbdt.eval_host_rows"] == 2 * (3000 + 700)
    assert summary["gauges"]["gbdt.eval_backend"] == "host"


@pytest.mark.parametrize("path", ["fast", "callback"])
def test_early_stopping_stops_where_the_host_metrics_stop(path, monkeypatch):
    params = {**PARAMS, "learning_rate": 0.5, "num_leaves": 31,
              "min_data_in_leaf": 5}
    on_device, seen_d = _train(path, rounds=60, params=params,
                               early_stopping_rounds=3)
    monkeypatch.setattr(GBDT, "_device_eval_set", lambda self, *a: None)
    on_host, seen_h = _train(path, rounds=60, params=params,
                             early_stopping_rounds=3)
    assert 0 < on_device.best_iteration < 57
    assert on_device.best_iteration == on_host.best_iteration
    assert len(seen_d) == len(seen_h)
    for (_, m, a), (_, _, b) in zip(seen_d, seen_h):
        assert abs(a - b) <= (1e-12 if m == "auc" else 1e-6 * b)


def _watched(rounds=1):
    ds, dv = _sets()
    bst = lgb.train(PARAMS, ds, rounds, valid_sets=[ds, dv],
                    valid_names=["training", "valid"], verbose_eval=False,
                    keep_training_booster=True)
    return bst._gbdt


def test_a_later_train_call_starts_no_compile_thread(monkeypatch):
    """A user's loop calls ``train(1)`` every iteration: the metrics'
    programs are compiled on threads before the first window only."""
    g = _watched()
    started = []
    real = GBDT._start_background

    def noted(self, work, name, *args):
        started.append(name)
        real(self, work, name, *args)
    monkeypatch.setattr(GBDT, "_start_background", noted)
    for _ in range(3):
        g.train(1)
    assert not [name for name in started if "eval-compile" in name], started
    assert g.join_background(60.0)


def test_replaced_labels_are_evaluated_not_the_ones_first_seen():
    obs.enable()
    g = _watched(rounds=3)
    auc = {name: v for name, metric, v, _ in g.eval_valid()
           if metric == "auc"}["valid"]
    md = g.valid_sets[0].metadata
    md.label = (1.0 - md.label).astype(np.float32)
    flipped = {name: v for name, metric, v, _ in g.eval_valid()
               if metric == "auc"}["valid"]
    host = binary_auc(md.label, np.asarray(g._valid_scores[0])[:, 0])
    assert abs(flipped - host) <= 1e-12
    assert abs(flipped - (1.0 - auc)) <= 1e-12
    assert obs.summary()["counters"].get("gbdt.eval_host_rows", 0) == 0


def test_watching_a_held_out_set_changes_no_tree():
    ds, _ = _sets()
    plain = lgb.train({**PARAMS, "metric": "none"}, ds, 6)
    watched, _ = _train("fast", rounds=6)
    a, b = plain._gbdt.models, watched._gbdt.models
    assert len(a) == len(b) == 6
    for s, t in zip(a, b):
        for field in ("split_feature", "threshold", "left_child",
                      "right_child", "leaf_value", "leaf_count"):
            assert np.array_equal(getattr(s, field), getattr(t, field)), field


def test_the_sampled_reference_follows_the_system(monkeypatch):
    """The ``train_eval`` cell's comparison at 6,000 + 1,500 rows x 67,
    31 leaves, ``int8h``, bag 0.8 / 5, 53 of 67 features, six
    iterations: every compared number under the cell's own limits."""
    monkeypatch.setenv("LGBM_TPU_HIST_BACKEND", "pallas")
    from benchmark import check, data
    from benchmark.jobs import train, train_eval
    with open(os.path.join(REPO, "benchmark", "tests",
                           "tiny_eval.json")) as f:
        cfg = json.load(f)["config"]
    with open(os.path.join(REPO, "benchmark", "workloads",
                           "criteo-67-b63-conf.train-eval.json")) as f:
        limits = json.load(f)["limits"]
    rows = cfg["data"]["rows"]
    X, y = data.make({**cfg["data"], "rows": rows + cfg["valid"]["rows"]},
                     3000000023)
    ds = lgb.Dataset(X[:rows], label=y[:rows], params={"max_bin": 63})
    dv = lgb.Dataset(X[rows:], label=y[rows:], reference=ds)
    reported, evals = [], []
    with train_eval.reported_to(reported):
        bst = lgb.train(train.program_params(cfg), ds, 1,
                        valid_sets=[ds, dv], valid_names=["training", "valid"],
                        early_stopping_rounds=5, keep_training_booster=True)
        g = bst._gbdt
        loss, scores = [], []
        for step in range(6):
            if step:
                del reported[:]
                g.train(1)
            evals.append({(n, m): v for n, m, v, _ in reported})
            scores.append((np.asarray(g.scores)[:, 0],
                           np.asarray(g._valid_scores[0])[:, 0]))
            s = scores[-1][0].astype(np.float64)
            loss.append(float(np.mean(np.logaddexp(0, s) - y[:rows] * s)))
    assert g.hist_backend == "pallas" and len(g.models) == 6
    trees = [{k: np.array(getattr(t, k))
              for k in ("split_feature", "threshold", "left_child",
                        "right_child", "leaf_value", "leaf_count")}
             | {"num_leaves": int(t.num_leaves)} for t in g.models]
    program = {
        "loss": loss, "trees": trees, "init": float(g.init_score_value),
        "evals": evals, "scores": scores,
        "held_out": train_eval.HeldOut(X[rows:], y[rows:], dv),
        "draws": {"bag": [np.asarray(g._bagging_mask(k)) for k in range(6)],
                  "features": [np.asarray(g._feature_mask(k))
                               for k in range(6)]}}
    values, seen, _ = train_eval.against_reference(
        cfg, program, X[:rows], y[:rows], train.grid_of(ds), lambda m: None)
    correct, table = check.verdict(values, limits)
    assert correct, [(n, v, lim) for n, v, lim in table if not v <= lim]
    assert set(limits) == set(values)


def _block_text():
    ds, dv = _sets(n=600, nv=200)
    bst = lgb.Booster(params=PARAMS, train_set=ds)
    bst.add_valid(dv, "valid")
    g = bst._gbdt
    return g._make_block_fn(1).lower(
        g.device_data, g._bins_t, tuple(g._valid_device), g.scores,
        tuple(g._valid_scores), jnp.float32(0.1), jnp.int32(0),
        jnp.int32(1)).as_text(debug_info=True)


def _eval_text():
    score, label, _ = _case(64, "plain")
    es = device.EvalSet(label, None)
    return device.evaluate.lower(
        jnp.asarray(score)[:, None], es.label, None, (),
        forms=("binary_logloss", "auc"), sigmoid=1.0, limb_bits=1,
        limbs=1).as_text(debug_info=True)


@pytest.mark.parametrize("scope, text", [
    ("gbdt.valid_update", _block_text), ("gbdt.bag_mask", _block_text),
    ("gbdt.feature_mask", _block_text), ("gbdt.eval", _eval_text)])
def test_the_new_device_work_carries_its_name(scope, text):
    assert re.search(r'[/"]' + re.escape(scope) + r'[/"]', text())
