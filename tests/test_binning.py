import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.binning import (BIN_CATEGORICAL, MISSING_NAN,
                                     MISSING_NONE, MISSING_ZERO, BinMapper,
                                     greedy_find_bin)
from lightgbm_tpu.io.dataset import BinnedDataset, Metadata


def test_greedy_find_bin_few_distinct():
    dv = np.array([1.0, 2.0, 3.0])
    cnts = np.array([10, 10, 10])
    bounds = greedy_find_bin(dv, cnts, max_bin=255, total_cnt=30, min_data_in_bin=3)
    assert bounds[-1] == np.inf
    assert len(bounds) == 3
    assert bounds[0] > 1.0 and bounds[0] <= 2.0


def test_greedy_find_bin_many_distinct_balanced():
    rng = np.random.RandomState(0)
    vals = np.sort(rng.normal(size=10000))
    dv, cnts = np.unique(vals, return_counts=True)
    bounds = greedy_find_bin(dv, cnts, max_bin=16, total_cnt=len(vals), min_data_in_bin=1)
    assert len(bounds) <= 16
    # bins should be roughly count-balanced
    idx = np.searchsorted(bounds, dv, side="left")
    per_bin = np.bincount(idx, weights=cnts, minlength=len(bounds))
    assert per_bin.max() < 3 * len(vals) / len(bounds)


def test_binmapper_roundtrip_numerical():
    rng = np.random.RandomState(1)
    vals = rng.normal(size=5000)
    m = BinMapper()
    m.find_bin(vals, total_sample_cnt=5000, max_bin=255)
    assert m.missing_type == MISSING_NONE
    bins = m.value_to_bin(vals)
    assert bins.min() >= 0 and bins.max() < m.num_bin
    # monotone: larger value -> same or larger bin
    order = np.argsort(vals)
    assert (np.diff(bins[order]) >= 0).all()
    # boundary semantics: value <= upper_bound[bin]
    ub = m.bin_upper_bound[bins]
    assert (vals <= ub).all()


def test_binmapper_nan_missing():
    vals = np.array([1.0, 2.0, np.nan, 3.0, np.nan, 4.0] * 10)
    m = BinMapper()
    m.find_bin(vals, total_sample_cnt=len(vals), max_bin=255, min_data_in_bin=1)
    assert m.missing_type == MISSING_NAN
    bins = m.value_to_bin(np.array([1.0, np.nan]))
    assert bins[1] == m.num_bin - 1           # NaN -> last bin
    assert bins[0] != bins[1]


def test_binmapper_zero_as_missing():
    vals = np.array([-2.0, -1.0, 1.0, 2.0] * 20)
    m = BinMapper()
    m.find_bin(vals, total_sample_cnt=200, max_bin=255, min_data_in_bin=1,
               zero_as_missing=True)
    assert m.missing_type == MISSING_ZERO
    b = m.value_to_bin(np.array([0.0, np.nan, -1.0, 1.0]))
    assert b[0] == b[1] == m.default_bin      # zero and NaN share default bin
    assert b[2] != b[0] and b[3] != b[0]


def test_binmapper_zero_bin_reserved():
    # dense feature with a zero spike: zero gets its own bin
    rng = np.random.RandomState(2)
    vals = np.concatenate([rng.normal(size=1000)])
    total = 2000  # 1000 implicit zeros
    m = BinMapper()
    m.find_bin(vals, total_sample_cnt=total, max_bin=64, min_data_in_bin=1)
    zb = m.value_to_bin(np.array([0.0]))[0]
    eps = m.value_to_bin(np.array([1e-40, -1e-40]))
    assert (eps == zb).all()
    assert zb == m.default_bin


def test_binmapper_categorical():
    vals = np.array([3.0, 3.0, 3.0, 7.0, 7.0, 1.0] * 10)
    m = BinMapper()
    m.find_bin(vals, total_sample_cnt=len(vals), max_bin=255,
               bin_type=BIN_CATEGORICAL, min_data_in_bin=1)
    assert m.bin_type == BIN_CATEGORICAL
    bins = m.value_to_bin(np.array([3.0, 7.0, 1.0, 999.0]))
    # most frequent category gets bin 0
    assert bins[0] == 0
    assert bins[1] == 1
    assert bins[2] == 2
    assert bins[3] == m.num_bin - 1  # unseen category -> last bin


def test_binmapper_trivial():
    m = BinMapper()
    m.find_bin(np.zeros(0), total_sample_cnt=100, max_bin=255)  # all zeros
    assert m.is_trivial


def test_dataset_construct_and_valid():
    rng = np.random.RandomState(3)
    X = rng.normal(size=(500, 5))
    X[:, 2] = 0.0  # trivial feature dropped
    y = (X[:, 0] > 0).astype(np.float32)
    md = Metadata()
    md.set_field("label", y)
    cfg = Config.from_params({"max_bin": 63})
    ds = BinnedDataset.from_raw(X, cfg, metadata=md)
    assert ds.num_data == 500
    assert ds.num_features == 4           # trivial column removed
    assert ds.feature_info.total_bins == ds.feature_info.num_bins.sum()
    assert ds.bins.dtype == np.uint8

    Xv = rng.normal(size=(100, 5))
    vs = ds.create_valid(Xv)
    assert vs.num_features == 4
    # valid binning uses train boundaries
    f0 = ds.used_features[0]
    expected = ds.mappers[f0].value_to_bin(Xv[:, f0])
    np.testing.assert_array_equal(vs.bins[:, 0], expected.astype(np.uint8))


def test_dataset_binary_roundtrip(tmp_path):
    rng = np.random.RandomState(4)
    X = rng.normal(size=(200, 3))
    y = rng.normal(size=200).astype(np.float32)
    md = Metadata()
    md.set_field("label", y)
    cfg = Config.from_params({})
    ds = BinnedDataset.from_raw(X, cfg, metadata=md)
    p = str(tmp_path / "ds.npz")
    ds.save_binary(p)
    ds2 = BinnedDataset.load_binary(p)
    np.testing.assert_array_equal(ds.bins, ds2.bins)
    np.testing.assert_array_equal(ds.metadata.label, ds2.metadata.label)
    assert len(ds2.mappers) == len(ds.mappers)


def test_metadata_group_field():
    md = Metadata()
    md.set_field("group", [10, 20, 30])   # sizes
    np.testing.assert_array_equal(md.query_boundaries, [0, 10, 30, 60])
    md.set_field("group", [0, 10, 30, 60])  # already boundaries
    np.testing.assert_array_equal(md.query_boundaries, [0, 10, 30, 60])


@pytest.mark.parametrize("n,block", [(10007, 1000), (999, 1000), (4096, 512),
                                     (3001, 3000), (5000, 1 << 18)])
def test_concurrent_ingest_bins_as_one_pass_does(monkeypatch, n, block):
    """``Dataset.construct()`` bins row blocks on threads: the binned
    matrix is byte for byte that of one ``value_to_bin`` call a column
    over all rows — odd row counts, a last block of one row, fewer rows
    than a block, numerical, NaN and categorical columns alike."""
    from lightgbm_tpu.io import dataset
    monkeypatch.setattr(dataset, "BIN_BLOCK_ROWS", block)
    monkeypatch.setattr(dataset.os, "cpu_count", lambda: 5)
    rng = np.random.RandomState(n)
    X = rng.normal(size=(n, 6))
    X[:, 1] = np.floor(np.exp(X[:, 1]))              # ties, many zeros
    X[rng.rand(n) < 0.1, 2] = np.nan                 # a NaN bin
    X[:, 3] = rng.randint(0, 12, size=n)             # categorical
    X[:, 4] = 0.0                                    # trivial: dropped
    X = np.asfortranarray(X.astype(np.float32))
    cfg = Config.from_params({"max_bin": 63})
    ds = BinnedDataset.from_raw(X, cfg, categorical_features=[3])
    assert ds.used_features == [0, 1, 2, 3, 5]
    want = np.stack([ds.mappers[f].value_to_bin(X[:, f])
                     for f in ds.used_features], axis=1).astype(np.uint8)
    assert ds.bins.dtype == np.uint8 and ds.bins.shape == want.shape
    assert ds.bins.tobytes() == want.tobytes()
    # a valid set through the same mappers, C-ordered rows
    valid = ds.create_valid(np.ascontiguousarray(X[: n // 2]))
    assert valid.bins.tobytes() == want[: n // 2].tobytes()
