"""``correct`` on a ``train_eval`` cell: the tiny cell of
``tiny_eval.json`` (6,000 + 1,500 rows x 67, 31 leaves, ``int8h``, bag
0.8 every 5 trees, 53 of 67 features a tree, both metrics of both sets
every step) through job ``train_eval`` on the CPU, the kernels in
interpret mode.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_correct_eval.py -q

Run by hand (not part of the repo's tier-1 tests), as its two siblings
are.  The sound run reads true; each fault of ``readings_eval.VARIANTS``
reads false, by the number that should see it.
"""
import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
CELL = "tiny.train-eval"
ARGV = ["--workload", CELL, "--seed", "3000000019", "--seconds", "0.5"]


@pytest.fixture(autouse=True)
def kernel_path(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_HIST_BACKEND", "pallas")


def run_tiny_eval(variant="program", argv=ARGV):
    import readings_eval
    import rehearse
    from benchmark import run
    with open(os.path.join(HERE, "tiny_eval.json")) as f:
        tiny = json.load(f)
    real = run.load_json

    def load_json(*parts):
        if parts == ("BENCHMARK.json",):
            bench = copy.deepcopy(real(*parts))
            bench["configs"].append({"name": "tiny-eval",
                                     "file": "tiny-eval-config"})
            bench["workloads"].append({"name": CELL, "config": "tiny-eval",
                                       "traffic": "train_eval", "chips": 1})
            for m in bench["per_layer"]:
                m["workloads"] = m["workloads"] + [CELL]
            return bench
        if parts == ("tiny-eval-config",):
            return tiny["config"]
        if parts == ("benchmark", "workloads", CELL + ".json"):
            return tiny["cell"]
        return real(*parts)

    return rehearse.patched_run(argv, [(run, "load_json", load_json),
                                       *readings_eval.VARIANTS[variant]()])


def over(result):
    return sorted(n for n, c in result["compared"].items()
                  if not c["value"] <= c["limit"])


def test_sound_run_is_correct():
    result = run_tiny_eval()
    assert result["correct"], over(result)
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert {"eval_logloss_gap", "eval_auc_gap", "draw_faults", "loss_step6",
            "score_off_training", "score_off_valid"} <= set(
                result["compared"])


def test_traced_run_reads_the_new_metrics():
    result = run_tiny_eval(argv=ARGV + ["--trace", "1"])
    assert result["correct"], over(result)
    # the CPU's trace carries no name stacks: a scope reads 0 there
    for name in ("loop.eval_ms_per_iter", "loop.valid_update_ms_per_iter",
                 "loop.sample_ms_per_iter"):
        assert result["metrics"][name]["value"] >= 0, name
    for name in ("loop.eval_host_s", "train.step_mfu_pct"):
        assert result["metrics"][name]["value"] > 0, name


@pytest.mark.parametrize("variant, seen_by", [
    ("control", "update_leaf_p90"),
    ("late", "eval_logloss_gap"),
    ("no_bag", "leaf_count_mismatches"),
    ("no_feature_mask", "draw_faults"),
    ("oob", "score_off_training"),
    ("valid_stale", "score_off_valid"),
    ("buckets", "eval_auc_gap"),
])
def test_a_broken_program_is_not_correct(variant, seen_by):
    result = run_tiny_eval(variant)
    assert not result["correct"]
    assert seen_by in over(result), over(result)
