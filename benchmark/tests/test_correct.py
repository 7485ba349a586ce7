"""``correct`` comes out false when the timed path is broken, and true
when it is not: the tiny cell on the CPU, the kernels in interpret mode.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_correct.py -q

Run by hand (not part of the repo's tier-1 tests).  Each case drives a
whole run of ``run.py`` with the look for a chip patched out, and breaks
the program underneath the harness:

* the control: the program at its own next precision down (``int8``
  hessians where the configuration states ``int8h``);
* a step that returns its state unchanged;
* half of the batch left out of every tree, the sums taken over the rest.

The chip's readings of the same control and fault, at the cells' own
size, come from ``readings.py``; PERF.md has them.
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
ARGV = ["--seed", "3000000019", "--seconds", "0.5"]


@pytest.fixture(autouse=True)
def kernel_path(monkeypatch):
    # the quantised kernel path, interpreted: the CPU's default backend
    # (scatter) sums unrounded float32 gradients
    monkeypatch.setenv("LGBM_TPU_HIST_BACKEND", "pallas")


def run_tiny(patches=()):
    import rehearse
    return rehearse.patched_run(ARGV, patches)


def over(result):
    return sorted(n for n, c in result["compared"].items()
                  if not c["value"] <= c["limit"])


def test_sound_run_is_correct():
    result = run_tiny()
    assert result["correct"], over(result)
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert list(result)[-1] == "compared"


def test_control_lower_precision_is_not_correct():
    from benchmark.jobs import train
    real = train.program_params

    def lower(cfg):
        return {**real(cfg), "hist_mode": cfg["precision"]["control"]}
    result = run_tiny([(train, "program_params", lower)])
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
    result = run_tiny([(train.Booster, "step", unchanged)])
    assert not result["correct"]
    assert {"loss_step2", "loss_step3"} <= set(over(result))


def test_half_of_the_batch_left_out_is_not_correct():
    from benchmark.jobs import train
    real = train.program_params

    def half(cfg):
        return {**real(cfg), "bagging_fraction": 0.5, "bagging_freq": 1}
    result = run_tiny([(train, "program_params", half)])
    assert not result["correct"]
    assert "leaf_count_mismatches" in over(result)


def test_readings_script_gives_a_verdict_for_each_variant(tmp_path,
                                                          monkeypatch):
    import rehearse
    import readings
    monkeypatch.setattr(readings, "ROOT", str(tmp_path))
    last = rehearse.patched_run(
        ["--seeds", "3000000019", "--variants", "program,control"],
        main=readings.main)
    assert last["variant"] == "control" and not last["correct"]
    assert "update_leaf_p90" in last["over"]
    lines = (tmp_path / "chiprun_out" / "readings.jsonl").read_text()
    assert '"variant": "program", "correct": true' in lines
