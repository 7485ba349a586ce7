"""Streamed out-of-core training (ISSUE 14): the byte-identity
contract of the ``LGBM_TPU_STREAM_ROWS`` seam (detcheck DET005
``stream-vs-resident``).

Streamed training — rows in the mmap shard cache, multi-block
host→device streaming, host-resident scores — must be BYTE-IDENTICAL
(model text + score digests via ``Booster.digest()``) to in-memory
``lgb.train`` on the same data, for serial AND 2-shard data-parallel,
on the exact-accumulation scatter backend (the CPU default).  Plus:
source independence (mmap cache vs resident RAM), block-size
invariance, tail blocks, and the documented descopes.

ISSUE 20 extends the matrix to the kernel backends and the pipeline:

* accumulator-SEEDED Pallas folds (``make_hist_fold_fn``) are
  byte-identical to the in-memory monolithic kernels, serial AND
  2-shard (kernels force-run on CPU through the auto-interpret path);
* the depth-2 upload/compute pipeline (``LGBM_TPU_STREAM_PIPELINE``)
  and its serial escape hatch produce the identical model, with the
  overlap PROVEN from telemetry;
* a transient ``stream.upload`` fault retries without tearing a fold,
  and a real SIGKILL landing mid-pipeline leaves the shard cache
  restartable to the clean digest.
"""
import os
import signal
import subprocess
import sys
import textwrap

import numpy as np
import jax
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting.streaming import (StreamTrainer, stream_rows,
                                             train_streaming)
from lightgbm_tpu.config import Config
from lightgbm_tpu.io import outofcore as oc
from lightgbm_tpu.io.dataset import BinnedDataset, Metadata
from lightgbm_tpu.learner.serial import STREAM_CHUNK

N, F = 12000, 6          # > STREAM_CHUNK -> multi-block at R=8192
BASE = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
        "learning_rate": 0.1, "num_iterations": 5, "verbose": -1}


def _data(seed=7, n=N):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, F))
    y = (X[:, 0] + 0.5 * X[:, 1] + rng.normal(scale=0.3, size=n) > 0
         ).astype(np.float32)
    return X, y


def _resident(X, y, params):
    cfg = Config.from_params(params)
    md = Metadata()
    md.set_field("label", y)
    return cfg, BinnedDataset.from_raw(X, cfg, metadata=md)


def _mem_digest(X, y, params):
    ds = lgb.Dataset(X, label=y, params=params)
    return lgb.train(params, ds)._gbdt.digest()


def _stream_digest(params, source, rounds=None, block_rows=STREAM_CHUNK):
    cfg = Config.from_params(params)
    tr = StreamTrainer(cfg, source, block_rows=block_rows)
    assert len(tr._blocks()) > 1, "parity must exercise MULTI-block"
    return tr.train(rounds or params["num_iterations"]).digest()


def test_streamed_cache_byte_identical_to_in_memory(tmp_path):
    """THE gate: multi-block streamed training from the mmap shard
    cache == in-memory training, model text AND scores."""
    X, y = _data()
    rows = np.concatenate([y[:, None], X], axis=1)
    srcs = []
    for i, (a, b) in enumerate([(0, 5000), (5000, N)]):
        p = os.path.join(str(tmp_path), f"p{i}.csv")
        np.savetxt(p, rows[a:b], delimiter=",", fmt="%.9g")
        srcs.append(p)
    cfg = Config.from_params(BASE)
    store = oc.ingest(srcs, cfg, str(tmp_path / "cache"))
    # in-memory side trains on the SAME binned rows (ingest parity is
    # pinned separately in tests/test_outofcore.py)
    from lightgbm_tpu.io.loader import parse_file
    single = os.path.join(str(tmp_path), "all.csv")
    np.savetxt(single, rows, delimiter=",", fmt="%.9g")
    Xp, yp, _, _, _, _ = parse_file(single, cfg)
    d_mem = _mem_digest(Xp, yp, BASE)
    d_str = _stream_digest(BASE, store)
    assert d_str == d_mem


def test_streamed_resident_source_byte_identical():
    """Source independence half: streaming the resident dataset's own
    arrays produces the in-memory digest too (so cache==resident==
    in-memory all agree)."""
    X, y = _data()
    cfg, res = _resident(X, y, BASE)
    assert _stream_digest(BASE, res) == _mem_digest(X, y, BASE)


def test_block_size_invariance():
    """R=8192 and R=2*8192 produce the identical model: the fold/
    chunk-reduction contract, not a lucky block count."""
    X, y = _data(seed=11, n=3 * STREAM_CHUNK + 123)
    cfg, res = _resident(X, y, BASE)
    d1 = _stream_digest(BASE, res, block_rows=STREAM_CHUNK)
    cfg2, res2 = _resident(X, y, BASE)
    tr = StreamTrainer(cfg2, res2, block_rows=2 * STREAM_CHUNK)
    d2 = tr.train(BASE["num_iterations"]).digest()
    assert d1 == d2 == _mem_digest(X, y, BASE)


def test_feature_fraction_parity():
    X, y = _data()
    params = dict(BASE, feature_fraction=0.5)
    cfg, res = _resident(X, y, params)
    assert _stream_digest(params, res) == _mem_digest(X, y, params)


def test_multiclass_parity():
    X, y = _data()
    ym = np.digitize(X[:, 0], [-0.5, 0.5]).astype(np.float32)
    params = {"objective": "multiclass", "num_class": 3, "num_leaves": 7,
              "max_bin": 31, "learning_rate": 0.1, "num_iterations": 3,
              "verbose": -1}
    cfg, res = _resident(X, ym, params)
    assert _stream_digest(params, res) == _mem_digest(X, ym, params)


def test_regression_with_weights_parity():
    rng = np.random.RandomState(3)
    X, _ = _data(seed=3)
    y = (X[:, 0] * 2 + rng.normal(size=N)).astype(np.float32)
    w = np.abs(rng.normal(size=N)).astype(np.float32) + 0.1
    params = dict(BASE, objective="regression")
    cfg = Config.from_params(params)
    md = Metadata()
    md.set_field("label", y)
    md.set_field("weight", w)
    res = BinnedDataset.from_raw(X, cfg, metadata=md)
    ds = lgb.Dataset(X, label=y, weight=w, params=params)
    d_mem = lgb.train(params, ds)._gbdt.digest()
    assert _stream_digest(params, res) == d_mem


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >=2 virtual devices")
def test_two_shard_data_parallel_parity():
    """Streamed per-shard block folds == the in-memory 2-shard
    data-parallel mesh (fused blocks, overlapped psum schedule), with
    an ODD row count so the mesh row padding path is exercised."""
    X, y = _data(seed=9, n=2 * STREAM_CHUNK + 4001)   # odd -> pad row
    params = dict(BASE, tree_learner="data", mesh_shape=[2])
    cfg, res = _resident(X, y, params)
    tr = StreamTrainer(cfg, res, block_rows=STREAM_CHUNK)
    assert tr.S == 2
    d_str = tr.train(BASE["num_iterations"]).digest()
    assert d_str == _mem_digest(X, y, params)


def test_model_roundtrip_and_prediction(tmp_path):
    """The streamed booster is a regular booster: save/load text
    round-trips and predictions work through the mapper shell."""
    X, y = _data()
    cfg, res = _resident(X, y, BASE)
    bst = StreamTrainer(cfg, res, block_rows=STREAM_CHUNK).train(5)
    text = bst.save_model_to_string()
    loaded = lgb.Booster(model_str=text)
    pred = loaded.predict(X[:128])
    assert pred.shape == (128,)
    assert np.isfinite(pred).all()
    assert pred.std() > 0          # the model actually learned something
    # the shell booster predicts directly too (binned fast path vs the
    # loaded model's raw-threshold walk: same trees, float-path class)
    direct = bst.predict(X[:128])
    np.testing.assert_allclose(direct, pred, rtol=0, atol=1e-4)


def test_stream_rows_env_rounds_to_chunk(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_STREAM_ROWS", "1000")
    assert stream_rows() == STREAM_CHUNK
    monkeypatch.setenv("LGBM_TPU_STREAM_ROWS", str(STREAM_CHUNK + 1))
    assert stream_rows() == 2 * STREAM_CHUNK
    monkeypatch.delenv("LGBM_TPU_STREAM_ROWS")
    assert stream_rows() == 0


def test_descopes_raise():
    X, y = _data(n=STREAM_CHUNK)
    for extra, match in (
            ({"bagging_fraction": 0.5, "bagging_freq": 1}, "bagging"),
            ({"boosting": "dart"}, "boosting"),
            ({"boosting": "goss"}, "boosting"),
            ({"tree_learner": "voting"}, "tree_learner"),
            ({"objective": "lambdarank"}, "rank")):
        params = dict(BASE, **extra)
        cfg = Config.from_params(params)
        md = Metadata()
        md.set_field("label", y)
        if "rank" in str(extra.get("objective", "")):
            md.set_field("group", np.full(N // 100, 100, np.int32))
        res = BinnedDataset.from_raw(X, cfg, metadata=md)
        with pytest.raises(ValueError, match=match):
            StreamTrainer(cfg, res)


def test_train_streaming_public_surface(tmp_path):
    """lgb.train_streaming over a file list: ingest + train end to
    end, digest equal to the resident-source streamed run."""
    X, y = _data(seed=13, n=9000)
    rows = np.concatenate([y[:, None], X], axis=1)
    p = os.path.join(str(tmp_path), "all.csv")
    np.savetxt(p, rows, delimiter=",", fmt="%.9g")
    params = dict(BASE, num_iterations=3)
    bst = lgb.train_streaming(params, [p],
                              cache_dir=str(tmp_path / "cache"))
    assert bst.num_trees() == 3
    assert os.path.exists(os.path.join(str(tmp_path / "cache"),
                                       oc.MANIFEST))


# ---------------------------------------------------------------------------
# ISSUE 20: accumulator-seeded kernel folds + the upload/compute pipeline
# ---------------------------------------------------------------------------
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a streamed tree runs every wave at the tail width: 8 slots at 15
# leaves, 40 (a 256-column seeded call, as a deep wave's) at 80
KERNEL_LEAVES = [15, 80]


@pytest.mark.parametrize("leaves", KERNEL_LEAVES)
def test_streamed_kernel_fold_byte_identical(monkeypatch, leaves):
    """ISSUE 20 gate: the accumulator-SEEDED kernel folds (carried
    operand via input_output_aliases) make multi-block streamed
    training byte-identical to the in-memory monolithic kernel — both
    sides forced onto the kernel backend, run on CPU through the
    auto-interpret path."""
    monkeypatch.setenv("LGBM_TPU_HIST_BACKEND", "pallas")
    X, y = _data()
    params = dict(BASE, num_iterations=3, num_leaves=leaves)
    cfg, res = _resident(X, y, params)
    tr = StreamTrainer(cfg, res, block_rows=STREAM_CHUNK)
    assert tr._fold is not None, "seeded fold must engage"
    assert tr.backend == "pallas" and tr.A_tail == (8 if leaves == 15
                                                    else 40)
    assert len(tr._blocks()) > 1, "parity must exercise MULTI-block"
    assert tr.train(3).digest() == _mem_digest(X, y, params)


@pytest.mark.parametrize("leaves", KERNEL_LEAVES)
def test_two_shard_kernel_fold_parity(leaves):
    """Seeded kernel folds under 2-shard data-parallel == the
    in-memory 2-shard mesh.  Re-execed in a child with a forced
    2-device CPU pool (tier-1 runs on one device; XLA_FLAGS must be
    fixed before jax initializes), odd row count for the pad path."""
    child = textwrap.dedent(f"""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=2").strip()
        os.environ["LGBM_TPU_HIST_BACKEND"] = "pallas"
        import sys
        sys.path.insert(0, {_REPO!r})
        import numpy as np
        import lightgbm_tpu as lgb
        from lightgbm_tpu.boosting.streaming import StreamTrainer
        from lightgbm_tpu.config import Config
        from lightgbm_tpu.io.dataset import BinnedDataset, Metadata
        from lightgbm_tpu.learner.serial import STREAM_CHUNK
        rng = np.random.RandomState(9)
        n = 2 * STREAM_CHUNK + 4001
        X = rng.normal(size=(n, 6))
        y = (X[:, 0] + 0.5 * X[:, 1]
             + rng.normal(scale=0.3, size=n) > 0).astype(np.float32)
        params = {{"objective": "binary", "num_leaves": {leaves},
                   "max_bin": 63, "learning_rate": 0.1,
                   "num_iterations": 3, "verbose": -1,
                   "tree_learner": "data", "mesh_shape": [2]}}
        cfg = Config.from_params(params)
        md = Metadata()
        md.set_field("label", y)
        res = BinnedDataset.from_raw(X, cfg, metadata=md)
        tr = StreamTrainer(cfg, res, block_rows=STREAM_CHUNK)
        assert tr.S == 2 and tr._fold is not None
        assert tr.backend == "pallas", tr.backend
        d_str = tr.train(3).digest()
        d_mem = lgb.train(params, lgb.Dataset(X, label=y,
                                              params=params))._gbdt.digest()
        assert d_str == d_mem, (d_str, d_mem)
        print("PARITY-OK", d_str)
    """)
    proc = subprocess.run([sys.executable, "-c", child], cwd=_REPO,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "PARITY-OK" in proc.stdout


def test_pipeline_toggle_byte_identical_and_overlaps(monkeypatch):
    """LGBM_TPU_STREAM_PIPELINE (detcheck DET005
    ``stream-pipeline-vs-serial``): the depth-2 pipeline and the
    serial escape hatch produce the identical model — the fold order
    never changes — and the pipelined run PROVES overlap through the
    ``stream.pipeline.overlap_s`` counter and the staging spans."""
    from lightgbm_tpu.obs import telemetry
    X, y = _data()
    monkeypatch.setenv("LGBM_TPU_STREAM_PIPELINE", "0")
    cfg, res = _resident(X, y, BASE)
    tr = StreamTrainer(cfg, res, block_rows=STREAM_CHUNK)
    assert not tr._pipeline_on
    d_serial = tr.train(5).digest()
    monkeypatch.setenv("LGBM_TPU_STREAM_PIPELINE", "1")
    cfg2, res2 = _resident(X, y, BASE)
    telemetry.reset()
    telemetry.enable()
    try:
        tr2 = StreamTrainer(cfg2, res2, block_rows=STREAM_CHUNK)
        assert tr2._pipeline_on
        d_pipe = tr2.train(5).digest()
        summ = telemetry.summary()
    finally:
        telemetry.reset()
    assert d_pipe == d_serial == _mem_digest(X, y, BASE)
    assert summ["counters"].get("stream.pipeline.overlap_s", 0) > 0
    for span in ("stream.prefetch", "stream.upload", "stream.fold"):
        assert summ["spans"][span]["count"] > 0, span


def test_stream_upload_fault_retried_without_torn_fold(monkeypatch):
    """A transient ``stream.upload`` fault fires BEFORE the block's
    fold is dispatched (the fault point sits inside the retried
    ``put``), so the retry re-uploads the same staged block and no
    fold is torn: the final model equals the clean run's."""
    from lightgbm_tpu.utils import faults, retry
    X, y = _data()
    clean = _mem_digest(X, y, BASE)
    monkeypatch.setattr(retry, "_sleep", lambda s: None)
    cfg, res = _resident(X, y, BASE)
    with faults.injected("stream.upload", times=2):
        d = StreamTrainer(cfg, res,
                          block_rows=STREAM_CHUNK).train(5).digest()
        assert faults.fired("stream.upload") == 2
    assert d == clean


def test_sigkill_mid_pipeline_restart_byte_identical(tmp_path):
    """A real SIGKILL landing mid-pipeline (stager thread armed, an
    upload in flight while the previous block's fold is dispatched)
    cannot tear the on-disk shard cache: a fresh run over the SAME
    store reproduces the clean in-memory digest."""
    X, y = _data(seed=21)
    rows = np.concatenate([y[:, None], X], axis=1)
    p = os.path.join(str(tmp_path), "all.csv")
    np.savetxt(p, rows, delimiter=",", fmt="%.9g")
    cache = str(tmp_path / "cache")
    child = textwrap.dedent(f"""
        import os, signal, sys
        sys.path.insert(0, {_REPO!r})
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from lightgbm_tpu.config import Config
        from lightgbm_tpu.io import outofcore as oc
        from lightgbm_tpu.boosting import streaming
        cfg = Config.from_params({BASE!r})
        store = oc.ingest([{p!r}], cfg, {cache!r})
        orig = streaming.StreamTrainer._upload_block
        calls = [0]
        def killer(self, staged):
            calls[0] += 1
            if calls[0] == 4:
                # 2nd iteration, 2nd block: the stager just staged it
                # and block 0's fold is dispatched but not awaited
                os.kill(os.getpid(), signal.SIGKILL)
            return orig(self, staged)
        streaming.StreamTrainer._upload_block = killer
        streaming.StreamTrainer(cfg, store, block_rows=8192).train(5)
    """)
    proc = subprocess.run([sys.executable, "-c", child], cwd=_REPO,
                          capture_output=True, timeout=600)
    assert proc.returncode == -signal.SIGKILL
    # the cache survived the kill: the manifest is intact and the
    # restart reuses it (no re-ingest), training to the clean digest
    cfg = Config.from_params(BASE)
    store = oc.ingest([p], cfg, cache)
    assert store.n == N
    d = StreamTrainer(cfg, store, block_rows=STREAM_CHUNK).train(5).digest()
    from lightgbm_tpu.io.loader import parse_file
    Xp, yp, _, _, _, _ = parse_file(p, cfg)
    assert d == _mem_digest(Xp, yp, BASE)
