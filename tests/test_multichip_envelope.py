"""Multi-chip divergence envelope gate on the virtual 8-device CPU mesh
(PR 4 satellite: VERDICT r5 Weak #4).

The bench-shape run reproduces the 1.63% row-leaf mismatch of the
round-5 virtual-CPU-device dry run on the CPU mesh (seed 0), so the gate is exercised against
REAL divergence, not a synthetic stand-in: every mismatched row must
classify as a near-tie artifact (flip within the measured gain margin,
budget flip, or leaf renumbering with value agreement), under a hard
mismatch ceiling.  A fabricated corruption must FAIL the gate with the
flight-recorder schedule attached.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.io.device import to_device
from lightgbm_tpu.learner.serial import GrowthParams, build_tree
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.parallel import envelope
from lightgbm_tpu.parallel.learners import build_tree_distributed
from lightgbm_tpu.parallel.mesh import make_mesh


@pytest.fixture(scope="module")
def eight_devices():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return jax.devices()[:8]


def _bench_shape_pair():
    """Serial + 8-way data-parallel trees at the divergence-bearing
    bench shape (131072 x 28, 255 leaves) — the exact configuration
    where the round-5 dry run measured the ungated 1.63% mismatch."""
    rng = np.random.RandomState(0)
    n, f, leaves = 131_072, 28, 255
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] * 2 + X[:, 1] - X[:, 2]
         + rng.normal(size=n) > 0).astype(np.float32)
    dd = to_device(BinnedDataset.from_raw(
        X, Config.from_params({"max_bin": 63})))
    grad = jnp.asarray(-(y - y.mean()))
    hess = jnp.ones(n) * 0.25
    p = GrowthParams(num_leaves=leaves, split=SplitParams(
        min_data_in_leaf=20, min_sum_hessian_in_leaf=1e-3))
    serial = build_tree(dd, grad, hess, p, hist_backend="scatter")
    dp = jax.jit(lambda g, h: build_tree_distributed(
        make_mesh(8), "data", "data", dd, g, h, p,
        hist_backend="scatter"))(grad, hess)
    return serial, dp, np.asarray(dd.bins)


def test_envelope_gate_on_real_divergence(eight_devices):
    serial, dp, bins = _bench_shape_pair()
    rep = envelope.assert_envelope(serial, dp, bins)
    # the gate must have judged REAL divergence (r05's envelope), not
    # an accidentally identical pair
    assert rep["mismatched_rows"] > 0, rep
    assert rep["mismatch_fraction"] <= 0.03
    # every mismatched row is accounted for by a near-tie class
    accounted = (rep["divergence_points"] + rep["budget_flips"]
                 + rep["renumbered_rows"])
    assert accounted > 0
    assert rep["walker_validated_rows"] > 0
    # renumbered leaves agreed in VALUE within the measured envelope
    assert rep["max_renumbered_value_gap"] <= 0.05, rep


def _small_serial_tree():
    rng = np.random.RandomState(1)
    n, f = 4096, 8
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    dd = to_device(BinnedDataset.from_raw(
        X, Config.from_params({"max_bin": 31})))
    grad = jnp.asarray(-(y - y.mean()))
    hess = jnp.ones(n)
    p = GrowthParams(num_leaves=31, split=SplitParams(
        min_data_in_leaf=10, min_sum_hessian_in_leaf=0.0))
    return build_tree(dd, grad, hess, p), np.asarray(dd.bins)


def _reroute(tree, bins):
    """row_leaf recomputed from the (possibly corrupted) tree arrays so
    the fabricated tree stays routing-consistent for the walker."""
    t = envelope._tree_arrays(tree)
    rl = np.array([envelope._walk_leaf(t, bins[r])
                   for r in range(len(bins))], dtype=np.int32)
    return tree._replace(row_leaf=jnp.asarray(rl))


def test_envelope_catches_fabricated_corruption():
    """A histogram-merge corruption (different split with an O(1) gain
    gap) must FAIL the gate — and the error must carry the flight
    recorder's schedule for attribution."""
    serial, bins = _small_serial_tree()
    thr = np.asarray(serial.threshold_bin).copy()
    gain = np.asarray(serial.gain).copy()
    root_thr = int(thr[0])
    thr[0] = root_thr + 6 if root_thr < 20 else root_thr - 6
    gain[0] = gain[0] * 3.0                 # NOT a near-tie
    corrupted = serial._replace(threshold_bin=jnp.asarray(thr),
                                gain=jnp.asarray(gain))
    corrupted = _reroute(corrupted, bins)
    with pytest.raises(AssertionError) as ei:
        envelope.assert_envelope(serial, corrupted, bins,
                                 mismatch_ceiling=1.0)
    msg = str(ei.value)
    assert "NOT f32 reassociation noise" in msg
    assert "flight recorder" in msg


def test_envelope_ceiling_catches_mass_mismatch():
    serial, bins = _small_serial_tree()
    thr = np.asarray(serial.threshold_bin).copy()
    thr[0] = max(0, int(thr[0]) - 6)
    corrupted = _reroute(serial._replace(threshold_bin=jnp.asarray(thr)),
                         bins)
    with pytest.raises(AssertionError) as ei:
        envelope.assert_envelope(serial, corrupted, bins,
                                 mismatch_ceiling=0.001)
    assert "hard ceiling" in str(ei.value)


def test_walker_self_validation_rejects_inconsistent_routing():
    """If the device routing and the numpy walker disagree (missing /
    categorical semantics the gate does not model), the gate must
    refuse to judge rather than silently pass."""
    serial, bins = _small_serial_tree()
    nl = int(serial.num_leaves)
    rl = np.asarray(serial.row_leaf).copy()
    rl[:512] = (rl[:512] + 1) % nl          # device says otherwise
    fake = serial._replace(row_leaf=jnp.asarray(rl))
    with pytest.raises(AssertionError, match="walker disagrees"):
        envelope.near_tie_report(serial, fake, bins)


def test_identical_trees_report_clean():
    serial, bins = _small_serial_tree()
    rep = envelope.assert_envelope(serial, serial, bins)
    assert rep["mismatched_rows"] == 0
    assert rep["divergence_points"] == 0
