"""``correct`` on a row-sharded cell: the tiny cell with
``tree_learner=data`` on four (virtual) devices through job ``train_dp``,
the kernels in interpret mode.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python3 -m pytest benchmark/tests/test_correct_dp.py -q

Run by hand (not part of the repo's tier-1 tests), as ``test_correct.py``
is.  The sound run reads true: the plain reference, which rounds against
one scale over all rows and sums exactly, follows the four shards'
trees.  It reads false with

* one shard left out of the exchange (its histograms and root totals
  never reach the sum);
* every shard rounding against its own rows' largest gradient and
  hessian, as the program did before PR 28;
* the control (``hist_mode=int8``) and ``test_correct.py``'s two faults.
"""
import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
ARGV = ["--seed", "3000000019", "--seconds", "0.5"]
SHARDS = 4


@pytest.fixture(autouse=True)
def kernel_path(monkeypatch):
    import jax
    if len(jax.devices()) < SHARDS:
        pytest.skip(f"needs {SHARDS} devices: XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={SHARDS}")
    monkeypatch.setenv("LGBM_TPU_HIST_BACKEND", "pallas")


def run_tiny_dp(patches=()):
    """The tiny cell as a four-chip data-parallel cell."""
    import rehearse
    from benchmark import run
    with open(os.path.join(HERE, "tiny.json")) as f:
        tiny = json.load(f)
    tiny["config"]["params"]["tree_learner"] = "data"
    tiny["cell"].update(job="train_dp", chips=SHARDS)
    real = run.load_json

    def load_json(*parts):
        if parts == ("BENCHMARK.json",):
            bench = copy.deepcopy(real(*parts))
            bench["configs"].append({"name": "tiny", "file": "tiny-config"})
            bench["workloads"].append({"name": "tiny.train", "config": "tiny",
                                       "traffic": "train", "chips": SHARDS})
            for m in bench["per_layer"]:
                m["workloads"] = m["workloads"] + ["tiny.train"]
            return bench
        if parts == ("tiny-config",):
            return tiny["config"]
        if parts == ("benchmark", "workloads", "tiny.train.json"):
            return tiny["cell"]
        return real(*parts)

    return rehearse.patched_run(ARGV, [(run, "load_json", load_json),
                                       *patches])


def over(result):
    return sorted(n for n, c in result["compared"].items()
                  if not c["value"] <= c["limit"])


def test_four_shards_are_followed_by_the_reference():
    result = run_tiny_dp()
    assert result["correct"], over(result)
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["device"]["count"] == SHARDS


def test_a_shard_left_out_of_the_exchange_is_not_correct():
    import jax
    from lightgbm_tpu.parallel import learners
    real = learners.Psum.reduce

    def without_shard_0(self, x, what="hist_psum"):
        here = jax.lax.axis_index(self.axis) != 0
        return real(self, jax.tree.map(lambda a: a * here.astype(a.dtype), x),
                    what)
    result = run_tiny_dp([(learners.Psum, "reduce", without_shard_0)])
    assert not result["correct"]
    assert "leaf_count_mismatches" in over(result)


def test_per_shard_scales_are_not_correct():
    from lightgbm_tpu.parallel import learners
    result = run_tiny_dp([(learners, "global_scales",
                           lambda grad, hess, axis:
                           learners.quant_scales(grad, hess))])
    assert not result["correct"]
    assert "update_leaf_p90" in over(result)


def test_control_lower_precision_is_not_correct():
    from benchmark.jobs import train
    real = train.program_params

    def lower(cfg):
        return {**real(cfg), "hist_mode": cfg["precision"]["control"]}
    result = run_tiny_dp([(train, "program_params", lower)])
    assert not result["correct"]
    assert "update_leaf_p90" in over(result)


def test_state_left_unchanged_is_not_correct():
    import jax.numpy as jnp
    from benchmark.jobs import train
    real = train.Booster.step

    def unchanged(self):
        kept = jnp.copy(self.g.scores)
        real(self)
        self.g.scores = kept
    result = run_tiny_dp([(train.Booster, "step", unchanged)])
    assert not result["correct"]
    assert {"loss_step2", "loss_step3"} <= set(over(result))


def test_half_of_the_batch_left_out_is_not_correct():
    from benchmark.jobs import train
    real = train.program_params

    def half(cfg):
        return {**real(cfg), "bagging_fraction": 0.5, "bagging_freq": 1}
    result = run_tiny_dp([(train, "program_params", half)])
    assert not result["correct"]
    assert "leaf_count_mismatches" in over(result)


def test_one_chips_share_of_the_work():
    from benchmark.jobs import train_dp
    w = {"ops": 8.0, "unit": "int8", "bytes": 4.0, "hist_rows": 12}
    assert train_dp.per_chip(w, 4) == {"ops": 2.0, "unit": "int8",
                                       "bytes": 1.0, "hist_rows": 3.0}
