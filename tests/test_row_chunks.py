"""More rows than one int32 cell sums exactly, on one chip: the quantized
modes sum them in row chunks, each exact in int32, and add the chunks'
partials as 16-bit limbs before the one dequantization (the exchange's
own arithmetic, `tests/test_parallel.py`).  The bound of a chunk
(16,909,320 rows) is patched down to a row tile or two, so that a few
thousand rows are 2, 3 and 4 chunks; the trees of 1, 2 and 4 chunks are
held to each other in ``tests/test_parallel.py``
(``test_quantised_data_parallel_grows_the_serial_tree``).
"""
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.learner import serial
from lightgbm_tpu.ops import pallas_histogram as ph

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F, MAX_BIN, SLOTS = 6, 63, 8


def _rows(n, seed=0):
    """``n`` rows of bins, int8h codes near their ends, and leaves."""
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, MAX_BIN, size=(n, F)).astype(np.uint8)
    codes = rng.choice(np.array([-127, -126, 126, 127], np.int8), size=(4, n))
    codes[3] = 1
    leaf = rng.randint(0, 3, size=n).astype(np.int32)
    return bins, codes, leaf


def _int64_sums(bins, codes, leaf, active):
    """``[A, F, B, C]`` sums of the codes by (leaf, feature, bin) in
    int64: what any exact sum of them must equal."""
    B = ph.bin_stride(MAX_BIN)
    out = np.zeros((len(active), F, B, codes.shape[0]), np.int64)
    for a, lf in enumerate(active):
        rows = np.flatnonzero(leaf == lf)
        for f in range(F):
            for c in range(codes.shape[0]):
                np.add.at(out[a, f, :, c], bins[rows, f],
                          codes[c, rows].astype(np.int64))
    return out


# (rows, the patched bound, chunks): 4 and 2 chunks share 4 row tiles
# evenly; 3 chunks hold 5 tiles as 2 + 2 + 1
@pytest.mark.parametrize("n, limit, chunks", [
    (8000, 2048, 4), (8000, 4096, 2), (10000, 4096, 3)])
def test_chunk_partials_add_as_limbs_to_the_int64_sums(n, limit, chunks):
    bins, codes, leaf = _rows(n)
    bins_t = ph.transpose_bins(jnp.asarray(bins))
    n_pad = bins_t.shape[1]
    vals = jnp.pad(jnp.asarray(codes), ((0, 0), (0, n_pad - n)))
    leaf_pad = jnp.pad(jnp.asarray(leaf), (0, n_pad - n), constant_values=-1)
    active = jnp.asarray([0, 2] + [-1] * (SLOTS - 2), jnp.int32)
    kw = dict(num_features=F, max_bins=MAX_BIN, mode="int8h", interpret=True,
              row_limit=limit)
    raw = ph.hist_active_pallas(bins_t, vals, leaf_pad, active, raw=True, **kw)
    assert raw.shape[0] == chunks and raw.dtype == jnp.int32
    want = _int64_sums(bins, codes, leaf, [0, 2])
    limbs = ph.hist_active_pallas(bins_t, vals, leaf_pad, active, **kw)
    assert isinstance(limbs, ph.CodeLimbs)
    hi, lo = (np.asarray(x).astype(np.int64)[:2] for x in limbs)
    assert lo.min() >= 0 and lo.max() < 1 << 16
    np.testing.assert_array_equal(hi * 65536 + lo, want)
    # the raw partials add up to the one accumulator's cells
    one = {**kw, "row_limit": n_pad}
    np.testing.assert_array_equal(
        np.asarray(ph.hist_active_pallas(bins_t, vals, leaf_pad, active,
                                         **one))[:2], want)
    np.testing.assert_array_equal(
        np.asarray(raw).astype(np.int64).sum(axis=0),
        np.asarray(ph.hist_active_pallas(bins_t, vals, leaf_pad, active,
                                         raw=True, **one)))


def test_limbs_of_partials_near_the_ends_of_int32_add_exactly():
    """Synthetic partials: every part the largest int32, every part the
    smallest, the ends against each other, and random ones."""
    rng = np.random.RandomState(1)
    ends = np.array([-2 ** 31, -2 ** 31 + 1, -1, 0, 1, 2 ** 31 - 2,
                     2 ** 31 - 1], np.int64)
    parts = [np.concatenate([[2 ** 31 - 1, -2 ** 31], np.roll(ends, k),
                             rng.randint(-2 ** 31, 2 ** 31, size=50)])
             for k in range(4)]
    for k in (2, 3, 4):
        hi, lo = ph.sum_code_limbs([jnp.asarray(p.astype(np.int32))
                                    for p in parts[:k]])
        want = [sum(int(p[i]) for p in parts[:k])
                for i in range(len(parts[0]))]
        assert want[0] == k * (2 ** 31 - 1) and want[1] == -k * 2 ** 31
        assert [int(h) * 65536 + int(l) for h, l in
                zip(np.asarray(hi), np.asarray(lo))] == want
        assert np.asarray(lo).min() >= 0 and np.asarray(lo).max() < 1 << 16
        # one dequantization, rounded once: the float32 nearest the total
        cells = ph.CodeLimbs(jnp.stack([hi] * 3, -1), jnp.stack([lo] * 3, -1))
        g = ph.dequant_hist(cells, jnp.asarray([127.0, 127.0]), "int8")
        np.testing.assert_array_equal(
            np.asarray(g[..., 0]),
            np.asarray(want, np.float64).astype(np.float32))


@pytest.mark.parametrize("limit, chunks", [(4096, 2), (2048, 4), (8192, 1)])
def test_root_code_sums_by_chunks(monkeypatch, limit, chunks):
    n = 8000
    _, codes, _ = _rows(n, seed=2)
    bag = np.random.RandomState(3).rand(n) < 0.8
    vals = jnp.pad(jnp.asarray(codes), ((0, 0), (0, 8192 - n)))
    monkeypatch.setattr(serial, "_INT8_ROW_LIMIT", limit)
    got = serial.root_code_sums(vals, jnp.asarray(bag))
    want = codes[:, bag].astype(np.int64).sum(axis=1)
    if chunks == 1:
        assert not isinstance(got, tuple)
        np.testing.assert_array_equal(np.asarray(got), want)
    else:
        assert isinstance(got, ph.CodeLimbs)
        np.testing.assert_array_equal(
            np.asarray(got.hi).astype(np.int64) * 65536 + np.asarray(got.lo),
            want)
    tot = serial.root_stats_q(got, jnp.asarray([127.0, 16129.0 / 127]),
                              "int8h")
    assert float(tot[2]) == bag.sum()


def test_a_resident_shard_keeps_its_mode_whatever_its_size():
    """The cell ``criteo-67-b63-c32.train``: 53,125,000 rows on a chip
    are four chunks at ``int8h``.  What still runs a float mode: more
    parts (shards x chunks) than the limbs add exactly, and the
    streamed fold's one accumulator past the bound of a chunk."""
    n = 53_125_000
    assert serial.shard_row_chunks(n) == 4
    assert ph.row_chunks(25_940, 2048) == (4, 6_485)
    assert ph.row_chunks(51_880, 1024) == (4, 12_970)
    assert serial.shard_row_chunks(13_281_250) == 1
    for mode in ("int8", "int8h", "int8hh"):
        assert serial.effective_hist_mode(mode, n) == mode
        assert serial.effective_hist_mode(mode, n, shards=127) == mode
    assert serial.effective_hist_mode("int8h", n, shards=128) == "hhilo"
    assert serial.effective_hist_mode("int8hh", n, shards=128) == "hilo"
    assert serial.effective_hist_mode("int8h", 13_281_250, shards=511) \
        == "int8h"
    assert serial.effective_hist_mode("int8h", 13_281_250, shards=512) \
        == "hhilo"
    assert serial.effective_hist_mode("int8h", 512 * 16_908_288) == "hhilo"
    assert serial.effective_hist_mode("int8h", 511 * 16_908_288) == "int8h"
    assert serial.effective_hist_mode("int8h", n, chunked=False) == "hhilo"
    assert serial.effective_hist_mode("hhilo", n, shards=128) == "hhilo"


def test_the_fused_kernel_keeps_to_one_chunk():
    def ok(mode, n_rows):       # the 255-leaf tail's wave
        return ph.fused_config_ok(28, 63, 255, mode, n_rows,
                                  ph.INT8_ROW_LIMIT, slots=128,
                                  route_leaves=255)
    assert ok("int8h", 0)
    assert ok("int8h", 13_281_280)
    assert not ok("int8h", 53_125_120)
    assert ok("hhilo", 53_125_120) == ok("hhilo", 0)


def test_a_seeded_call_refuses_more_rows_than_its_accumulator_sums():
    bins, codes, leaf = _rows(4096)
    bins_t = ph.transpose_bins(jnp.asarray(bins))
    shape, dtype = ph.hist_raw_layout(4096, SLOTS, F, MAX_BIN, "int8h")
    with pytest.raises(ValueError, match="one int32 accumulator"):
        ph.hist_active_pallas(
            bins_t, jnp.asarray(codes), jnp.asarray(leaf),
            jnp.arange(SLOTS, dtype=jnp.int32), None,
            jnp.zeros(shape, dtype), num_features=F, max_bins=MAX_BIN,
            mode="int8h", interpret=True, raw=True, row_limit=2048)


# sha256 of ``str(jax.make_jaxpr(...))`` of ``hist_active_pallas`` at
# [6, 8192] x 63 bins x 8 slots, recorded from the commit before the row
# chunks (32c1c83) under this jax: one chunk is that program, letter for
# letter.  (Whole trees were held likewise by hand: ``build_tree`` and a
# two-shard ``build_tree_distributed`` at 70,000 x 6, `CHANGES.md` PR 36.)
PARENT_JAX = "0.9.0"
PARENT_JAXPRS = {
    ("int8h", True): "58f2d20e427321d2", ("int8h", False): "2489b648ab543928",
    ("int8", True): "9f2eac34614d7ad4", ("int8", False): "d033b0428eeeacb6",
    ("hhilo", True): "a2475a39d47bed21", ("hhilo", False): "a9d8f0869b139900",
}


def _jaxpr(mode, raw, **kw):
    n, C = 8192, 3 if mode == "int8" else 4
    vdt = jnp.float32 if mode == "hhilo" else jnp.int8

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)

    def f(b, v, l, a, s=None):
        return ph.hist_active_pallas.__wrapped__(
            b, v, l, a, s, num_features=F, max_bins=MAX_BIN, mode=mode,
            interpret=True, raw=raw, **kw)
    args = [S((F, n), jnp.uint8), S((C, n), vdt), S((n,), jnp.int32),
            S((SLOTS,), jnp.int32)]
    if not raw:
        args.append(S((2,), jnp.float32))
    return str(jax.make_jaxpr(f)(*args))


@pytest.mark.parametrize("mode, raw", sorted(PARENT_JAXPRS))
def test_one_chunk_is_the_program_before_the_chunks(mode, raw):
    """Traced, not compiled (ROADMAP D9).  At the default bound, and at
    any bound the rows fit, the call is the parent's; under a bound they
    do not fit it is another (quantized modes only)."""
    text = _jaxpr(mode, raw)
    assert text == _jaxpr(mode, raw, row_limit=8192)
    chunked = _jaxpr(mode, raw, row_limit=4096)
    assert (chunked != text) == (mode != "hhilo")
    if mode != "hhilo":
        assert "i32[2,384,128]" in chunked and "i32[2,384,128]" not in text
    if jax.__version__ != PARENT_JAX:
        pytest.skip(f"the digests were recorded under jax {PARENT_JAX}")
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_JAXPRS[mode, raw]


def test_a_booster_past_the_bound_trains_in_chunks(monkeypatch):
    """Through ``lgb.train`` as it is: the mode asked for runs, the
    summary says in how many chunks, no ``degrade`` event, and the model
    is the one-chunk model to the last byte."""
    from lightgbm_tpu import obs
    monkeypatch.setenv("LGBM_TPU_HIST_BACKEND", "pallas")
    rng = np.random.RandomState(5)
    X = rng.normal(size=(8100, 5)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 10,
              "hist_mode": "int8h", "verbose": -1}

    def train():
        jax.clear_caches()      # the jitted build is traced once a shape
        obs.reset()
        obs.enable()
        try:
            bst = lgb.train(params, lgb.Dataset(X, label=y), 3,
                            verbose_eval=False)
            return bst._gbdt.save_model_to_string(), obs.summary()
        finally:
            obs.reset()
    want, s1 = train()
    assert s1["gauges"]["hist.row_chunks"] == 1
    monkeypatch.setattr(serial, "_INT8_ROW_LIMIT", 2048)
    got, s = train()
    jax.clear_caches()
    assert s["gauges"]["hist.row_chunks"] == 4
    assert s["gauges"]["gbdt.hist_mode"] == "int8h"
    assert "gbdt.hist_mode_requested" not in s["gauges"]
    assert not [k for k in s["events"] if k.startswith("degrade:")]
    assert got == want


sys.path.insert(0, os.path.join(REPO, "benchmark", "tests"))


@pytest.mark.parametrize("case", ["chunked_run_is_correct",
                                  "wrapped_sum_is_not_correct"])
def test_the_benchmarks_verdict_holds_the_chunk_sum(monkeypatch, case):
    """`benchmark/tests/test_correct_chunks.py`'s two cases (the tiny
    cell in three row chunks reads ``correct`` true; with the chunks'
    partials added in one int32 it reads false), which the driver's
    command does not collect from there."""
    import test_correct_chunks as cell
    with cell.three_chunks(monkeypatch):
        getattr(cell, "test_" + case)(monkeypatch)
