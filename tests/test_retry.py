"""Retry-layer suite: the shared backoff policy, its wiring into the
collectives / rendezvous / dispatch seams, and the fused-split-kernel
compile fallback (ADVICE r5 #1)."""
import threading

import numpy as np
import pytest

from lightgbm_tpu.utils import faults, retry
from lightgbm_tpu.utils.retry import RetryPolicy, retry_call


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    faults.clear()
    monkeypatch.setattr(retry, "_sleep", lambda s: None)
    yield
    faults.clear()


def test_retry_transient_then_success():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("UNAVAILABLE: blip")
        return "ok"

    assert retry_call(flaky, policy=RetryPolicy(attempts=3)) == "ok"
    assert calls["n"] == 3


def test_retry_nonretryable_propagates_immediately():
    calls = {"n": 0}

    def fatal():
        calls["n"] += 1
        raise RuntimeError("INVALID_ARGUMENT: shape mismatch")

    with pytest.raises(RuntimeError, match="INVALID_ARGUMENT"):
        retry_call(fatal, policy=RetryPolicy(attempts=5))
    assert calls["n"] == 1


def test_retry_exhaustion_raises_last_error():
    calls = {"n": 0}

    def always():
        calls["n"] += 1
        raise RuntimeError(f"UNAVAILABLE: try {calls['n']}")

    with pytest.raises(RuntimeError, match="try 2"):
        retry_call(always, policy=RetryPolicy(attempts=2))
    assert calls["n"] == 2


def test_retry_deadline_cuts_attempts_short(monkeypatch):
    # real (tiny) sleeps so the monotonic clock advances past the budget
    import time as _time
    monkeypatch.setattr(retry, "_sleep", _time.sleep)
    calls = {"n": 0}

    def always():
        calls["n"] += 1
        raise RuntimeError("UNAVAILABLE: down")

    with pytest.raises(RuntimeError):
        retry_call(always, policy=RetryPolicy(
            attempts=50, base_s=0.02, jitter=0.0, deadline_s=0.05))
    assert calls["n"] < 50               # deadline, not attempts, ended it


def test_backoff_shape_exponential_and_capped():
    p = RetryPolicy(base_s=1.0, max_s=4.0, jitter=0.0)
    assert [p.sleep_s(k) for k in range(4)] == [1.0, 2.0, 4.0, 4.0]
    j = RetryPolicy(base_s=1.0, jitter=0.5)
    assert 1.0 <= j.sleep_s(0) <= 1.5


def test_policy_env_knobs(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_RETRY_ATTEMPTS", "7")
    monkeypatch.setenv("LGBM_TPU_RETRY_BASE_S", "0.25")
    monkeypatch.setenv("LGBM_TPU_RETRY_DEADLINE_S", "9")
    p = RetryPolicy.from_env(max_s=2.0)
    assert (p.attempts, p.base_s, p.deadline_s, p.max_s) == (7, 0.25, 9, 2.0)


def test_threaded_allgather_faults_recover():
    """Two injected collective failures across a 2-rank ThreadedAllgather
    world recover inside the backoff budget and every rank still gets
    the identical full mapper list."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.distributed import (ThreadedAllgather,
                                             find_bins_distributed)
    cfg = Config.from_params({"max_bin": 16})
    rng = np.random.RandomState(0)
    X = rng.normal(size=(200, 4)).astype(np.float64)
    world = 2
    ag = ThreadedAllgather(world)
    faults.inject("collective.allgather", times=2)
    results, errors = [None] * world, [None] * world

    def work(r):
        try:
            results[r] = find_bins_distributed(
                X[r::world], cfg, r, world, ag.for_rank(r))
        except Exception as exc:          # noqa: BLE001 - asserted below
            errors[r] = exc

    threads = [threading.Thread(target=work, args=(r,)) for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert errors == [None, None]
    assert faults.fired("collective.allgather") == 2
    b0 = [m.to_dict() for m in results[0]]
    b1 = [m.to_dict() for m in results[1]]
    assert b0 == b1 and len(b0) == 4


def test_threaded_allgather_faults_past_budget_raise(monkeypatch):
    """More failures than the attempt budget raise the injected fault
    cleanly (no hang, no half-built mapper list)."""
    monkeypatch.setenv("LGBM_TPU_RETRY_ATTEMPTS", "2")
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.distributed import (ThreadedAllgather,
                                             find_bins_distributed)
    cfg = Config.from_params({"max_bin": 16})
    X = np.random.RandomState(0).normal(size=(50, 2))
    world = 2
    ag = ThreadedAllgather(world)
    faults.inject("collective.allgather", times=100)
    errors = [None] * world

    def work(r):
        try:
            find_bins_distributed(X[r::world], cfg, r, world,
                                  ag.for_rank(r))
        except Exception as exc:          # noqa: BLE001
            errors[r] = exc

    threads = [threading.Thread(target=work, args=(r,)) for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert all(isinstance(e, faults.FaultInjected) for e in errors)


def test_jax_process_allgather_fails_twice_then_succeeds():
    """The production DCN collective seam: two injected failures, then
    success — the call completes and returns every rank's payload."""
    from lightgbm_tpu.io.distributed import jax_process_allgather
    faults.inject("collective.allgather", times=2)
    out = jax_process_allgather({"rank_payload": [1, 2, 3]})
    assert out == [{"rank_payload": [1, 2, 3]}]
    assert faults.fired("collective.allgather") == 2


def test_rendezvous_connect_retried(monkeypatch):
    """init_distributed retries the rendezvous handshake through the
    shared policy (the coordinator coming up late is a transient), and
    raises cleanly past the budget."""
    import jax
    from lightgbm_tpu.parallel.mesh import init_distributed
    called = {"n": 0}
    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda **kw: called.__setitem__("n", called["n"] + 1))
    faults.inject("rendezvous.connect", times=2)
    init_distributed(coordinator_address="127.0.0.1:1")
    assert called["n"] == 1
    assert faults.fired("rendezvous.connect") == 2

    faults.inject("rendezvous.connect", times=10)
    with pytest.raises(faults.FaultInjected):
        init_distributed(coordinator_address="127.0.0.1:1")


def test_dispatch_retry_on_shared_policy(monkeypatch):
    """GBDT._dispatch_retry rides utils/retry now: the LGBM_TPU_RETRY_*
    knobs apply (a 4th-failure success passes with attempts=5, which the
    old hard-coded 3-attempt loop would have raised on), and the
    historical contract — transient retried, deterministic raised —
    holds."""
    monkeypatch.setenv("LGBM_TPU_RETRY_ATTEMPTS", "5")
    from lightgbm_tpu.boosting.gbdt import GBDT
    g = GBDT.__new__(GBDT)               # _dispatch_retry is self-free
    calls = {"n": 0}

    def flaky(*args):
        calls["n"] += 1
        if calls["n"] < 4:
            raise RuntimeError("DEADLINE_EXCEEDED: dispatch stall")
        return args

    assert g._dispatch_retry(flaky, 1, 2) == (1, 2)
    assert calls["n"] == 4

    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        g._dispatch_retry(lambda: (_ for _ in ()).throw(
            RuntimeError("RESOURCE_EXHAUSTED: HBM OOM")))


# -- fused split kernel: VMEM budget + compile fallback ----------------

def test_leaf_tile_budgets_against_lanes():
    from lightgbm_tpu.ops import pallas_split as ps
    # narrow FB keeps the full 32-leaf tile; the widest admitted FB
    # shrinks to the minimum 8 tile
    assert ps._leaf_tile(256, 128) == 32
    assert ps._leaf_tile(256, ps.MAX_LANES) == 8
    budget = ps._vmem_budget_bytes()
    last = 64
    for fb in (128, 1024, 4096, 8192, 16384):
        lc = ps._leaf_tile(256, fb)
        assert 8 <= lc <= 32
        assert lc <= last                # monotone non-increasing
        last = lc
        # the working set fits the budget whenever shrinking can fit it
        if 8 * fb * ps._WORKING_SET_BYTES_PER_CELL <= budget:
            assert lc * fb * ps._WORKING_SET_BYTES_PER_CELL <= budget
    # small leaf counts still tile below the budget cap
    assert ps._leaf_tile(8, 128) == 8


def test_split_kernel_lane_cap_lowered():
    from lightgbm_tpu.ops import pallas_split as ps
    from lightgbm_tpu.ops.vmem import split_lane_chunk_features
    # 128 features x 256 bins = 32768 lanes: the shape ADVICE r5 #1
    # flagged as a VMEM-overflow compile crash.  Since ISSUE 9 it is
    # ACCEPTED again — but as per-chunk kernel calls whose lane width
    # never exceeds the cap the crash forced (the per-call working set
    # is what VMEM bounds, and the chunk model enforces it)
    assert ps.split_kernel_ok(128, 256, False, num_rows=1000)
    assert split_lane_chunk_features(128, 256) * 256 <= ps.MAX_LANES
    assert ps.split_kernel_ok(64, 256, False, num_rows=1000)
    # an unchunkable misalignment below the cap still rejects
    assert not ps.split_kernel_ok(3, 8, False, num_rows=1000)


@pytest.mark.parametrize("path", ["iteration", "block"])
def test_gbdt_kernel_compile_failure_propagates(path):
    """A compile-class failure from a training dispatch is a bug to see:
    it propagates (no process-wide kernel kill switch, no silent
    re-dispatch on another program) and nothing is appended."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(5)
    X = rng.normal(size=(300, 4)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "min_data_in_leaf": 5, "verbose": -1},
                    ds, num_boost_round=2, verbose_eval=False,
                    keep_training_booster=True)
    g = bst._gbdt
    state = {"n": 0}

    def exploding(*args, **kw):
        state["n"] += 1
        raise RuntimeError("INTERNAL: Mosaic failed to compile kernel")

    trees_before = g.num_trees()
    if path == "iteration":
        g._jit_build = exploding
        step = g.train_one_iter
    else:
        g._block_fns = {1: exploding}
        step = lambda: g.train_block(1)             # noqa: E731
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        step()
    assert state["n"] == 1               # dispatched once, not re-tried
    assert g.num_trees() == trees_before
