"""Binning tests (reference behavior: src/io/bin.cpp FindBin family)."""
import os

import numpy as np

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.binning import (BIN_CATEGORICAL, MISSING_NAN, MISSING_NONE,
                                     MISSING_ZERO, BinMapper, greedy_find_bin)
from lightgbm_tpu.io.dataset import BinnedDataset


def _make_mapper(values, total=None, max_bin=255, **kw):
    values = np.asarray(values, dtype=np.float64)
    m = BinMapper()
    m.find_bin(values, total if total is not None else len(values), max_bin, **kw)
    return m


def test_few_distinct_values_get_own_bins():
    vals = np.array([1.0] * 50 + [2.0] * 30 + [3.0] * 20)
    m = _make_mapper(vals, max_bin=255, min_data_in_bin=3)
    assert m.num_bin >= 3  # zero bin + the three values
    b1, b2, b3 = m.value_to_bin(1.0), m.value_to_bin(2.0), m.value_to_bin(3.0)
    assert len({b1, b2, b3}) == 3
    assert b1 < b2 < b3  # bounds ascend


def test_monotonic_binning():
    rng = np.random.default_rng(0)
    vals = rng.normal(size=5000)
    m = _make_mapper(vals, max_bin=63, min_data_in_bin=3)
    assert 2 <= m.num_bin <= 63
    xs = np.sort(rng.normal(size=100))
    bins = m.value_to_bin(xs)
    assert (np.diff(bins) >= 0).all()


def test_equalish_counts():
    rng = np.random.default_rng(1)
    vals = rng.random(20000)
    m = _make_mapper(vals, max_bin=32, min_data_in_bin=1)
    bins = m.value_to_bin(vals)
    counts = np.bincount(bins, minlength=m.num_bin)
    nz = counts[counts > 0]
    assert nz.max() < nz.mean() * 3  # roughly balanced


def test_zero_gets_own_bin():
    rng = np.random.default_rng(2)
    nonzero = rng.normal(size=1000)
    m = _make_mapper(nonzero, total=3000)  # 2000 implicit zeros
    zb = m.value_to_bin(0.0)
    assert m.value_to_bin(1e-40) == zb  # inside the 1e-35 zero threshold
    assert m.value_to_bin(0.5) != zb
    assert m.value_to_bin(-0.5) != zb
    assert m.default_bin == zb
    assert m.most_freq_bin == zb  # zeros dominate


def test_missing_nan_bin():
    vals = np.concatenate([np.random.default_rng(3).normal(size=1000),
                           np.full(100, np.nan)])
    m = _make_mapper(vals, use_missing=True)
    assert m.missing_type == MISSING_NAN
    assert m.value_to_bin(np.nan) == m.num_bin - 1
    m2 = _make_mapper(vals, use_missing=False)
    assert m2.missing_type == MISSING_NONE
    # NaN treated as zero when not using missing
    assert m2.value_to_bin(np.nan) == m2.value_to_bin(0.0)


def test_zero_as_missing():
    vals = np.random.default_rng(4).normal(size=1000)
    m = _make_mapper(vals, total=2000, zero_as_missing=True)
    assert m.missing_type == MISSING_ZERO


def test_trivial_feature():
    # constant non-zero feature: nothing to split on → trivial
    m = _make_mapper(np.full(100, 5.0), total=100)
    assert m.is_trivial
    # all-zero feature → trivial
    m2 = _make_mapper(np.array([]), total=100)
    assert m2.is_trivial
    # half 5.0, half implicit zero → splittable
    m3 = _make_mapper(np.full(100, 5.0), total=200)
    assert not m3.is_trivial


def test_categorical_mapping():
    rng = np.random.default_rng(5)
    cats = rng.choice([1, 2, 3, 7, 9], p=[0.5, 0.2, 0.15, 0.1, 0.05], size=2000)
    m = _make_mapper(cats.astype(float), bin_type=BIN_CATEGORICAL)
    assert m.bin_type == BIN_CATEGORICAL
    # most frequent category gets bin 0 (unless it's category 0)
    assert m.bin_2_categorical[0] == 1
    assert m.value_to_bin(1.0) == 0
    # unseen category maps to the last bin
    assert m.value_to_bin(100.0) == m.num_bin - 1


def test_categorical_negative_is_nan():
    cats = np.array([1.0, 2.0, -3.0] * 100)
    m = _make_mapper(cats, bin_type=BIN_CATEGORICAL)
    assert m.missing_type == MISSING_NAN
    assert m.value_to_bin(-3.0) == m.num_bin - 1


def test_greedy_find_bin_big_counts():
    # a value holding >= mean bin size gets a dedicated bin
    distinct = np.array([1.0, 2.0, 3.0, 4.0, 5.0])
    counts = np.array([10, 10, 960, 10, 10])
    bounds = greedy_find_bin(distinct, counts, max_bin=4, total_cnt=1000, min_data_in_bin=1)
    assert bounds[-1] == np.inf
    b = np.searchsorted(np.asarray(bounds[:-1]), [2.0, 3.0, 4.0], side="left")
    assert b[1] != b[0] and b[1] != b[2]  # 3.0 isolated


def test_mapper_roundtrip():
    vals = np.concatenate([np.random.default_rng(6).normal(size=500), [np.nan] * 10])
    m = _make_mapper(vals)
    m2 = BinMapper.from_dict(m.to_dict())
    xs = np.random.default_rng(7).normal(size=100)
    np.testing.assert_array_equal(m.value_to_bin(xs), m2.value_to_bin(xs))
    assert m2.value_to_bin(np.nan) == m.value_to_bin(np.nan)


def test_dataset_construction():
    rng = np.random.default_rng(8)
    X = rng.normal(size=(1000, 5))
    X[:, 2] = 1.0  # constant → trivial
    X[:, 3] = rng.choice([0.0, 1.0, 2.0], size=1000)
    ds = BinnedDataset.from_matrix(X, Config.from_params({"max_bin": 63}))
    assert ds.num_data == 1000
    assert ds.num_total_features == 5
    assert ds.num_features == 4  # constant column dropped
    assert ds.used_feature_map[2] == -1
    assert ds.X_bin.dtype == np.uint8
    assert ds.X_bin.shape == (1000, 4)
    assert ds.num_total_bin == sum(ds.num_bin(i) for i in range(4))
    for i in range(4):
        assert ds.X_bin[:, i].max() < ds.num_bin(i)


def test_dataset_valid_alignment():
    rng = np.random.default_rng(9)
    X = rng.normal(size=(500, 3))
    ds = BinnedDataset.from_matrix(X, Config())
    Xv = rng.normal(size=(100, 3))
    dv = BinnedDataset.from_matrix(Xv, Config(), reference=ds)
    assert dv.bin_offsets is ds.bin_offsets
    # same binarization as applying mappers directly
    for inner, j in enumerate(ds.real_feature_idx):
        np.testing.assert_array_equal(
            dv.X_bin[:, inner], ds.bin_mappers[j].value_to_bin(Xv[:, j]).astype(np.uint8))


def test_metadata_queries():
    from lightgbm_tpu.io.dataset import Metadata
    md = Metadata(10)
    md.set_label(np.arange(10))
    md.set_query([3, 3, 4])
    np.testing.assert_array_equal(md.query_boundaries, [0, 3, 6, 10])
    assert md.num_queries == 3
    md.set_weights(np.ones(10))
    np.testing.assert_allclose(md.query_weights, [1.0, 1.0, 1.0])


def test_native_binning_matches_python():
    """The C++ kernels (native/binning_native.cpp) must agree bit-for-bit
    with the pure-Python reference implementations across NaN/zero/low-
    cardinality columns — same bounds, same binned matrix."""
    import lightgbm_tpu as lgb
    import lightgbm_tpu.native as nat
    if nat.lib() is None:
        import pytest
        pytest.skip("native toolchain unavailable")
    rng = np.random.default_rng(11)
    X = rng.normal(size=(20_000, 7))
    X[rng.random(X.shape) < 0.04] = np.nan
    X[rng.random(X.shape) < 0.15] = 0.0
    X[:, 2] = np.round(X[:, 2] * 3)
    X[:, 5] = np.abs(X[:, 5])          # all-positive (zero-bin edge)
    X[:, 6] = -np.abs(X[:, 6])         # all-negative
    y = (np.nan_to_num(X[:, 0]) > 0).astype(float)
    ds1 = lgb.Dataset(X, label=y, params={"verbose": -1})
    ds1.construct()
    os.environ["LIGHTGBM_TPU_NO_NATIVE"] = "1"
    nat._lib, nat._tried = None, False
    try:
        ds2 = lgb.Dataset(X, label=y, params={"verbose": -1})
        ds2.construct()
    finally:
        del os.environ["LIGHTGBM_TPU_NO_NATIVE"]
        nat._lib, nat._tried = None, False
    h1, h2 = ds1._handle, ds2._handle
    assert np.array_equal(h1.X_bin, h2.X_bin)
    for a, b in zip(h1.bin_mappers, h2.bin_mappers):
        assert a.num_bin == b.num_bin
        np.testing.assert_array_equal(
            np.asarray(a.bin_upper_bound), np.asarray(b.bin_upper_bound))
        assert a.default_bin == b.default_bin
        assert a.missing_type == b.missing_type


import pytest  # noqa: E402


@pytest.mark.parametrize("case", ["all_kept", "rare_dropped", "with_nan",
                                  "huge_values"])
def test_categorical_lookup_table_is_the_loop(case):
    """``value_to_bin`` of a categorical column is one search a value over
    the sorted categories (PR 34: a pass over the column a category, 255
    passes over 11M rows, took 10 s a column); it has to give what a plain
    dictionary lookup a value gives, for kept, dropped, unseen, negative,
    fractional and missing values alike, however large the categories."""
    from lightgbm_tpu.io.binning import MISSING_NAN
    rng = np.random.default_rng(len(case))
    if case == "all_kept":
        vals = rng.integers(0, 12, 5000).astype(np.float64)
    elif case == "huge_values":
        vals = rng.choice([3, 70000, 2 ** 23, 2 ** 40], 5000).astype(
            np.float64)
    else:
        vals = np.minimum(rng.zipf(1.2, 5000) - 1, 400).astype(np.float64)
    if case == "with_nan":
        vals[::9] = np.nan
    m = BinMapper()
    nz = vals[~((vals > -1e-35) & (vals <= 1e-35))]
    m.find_bin(nz, len(vals), 255 if case != "rare_dropped" else 32, 3, 20,
               BIN_CATEGORICAL, True, False)
    probe = np.concatenate([vals, [np.nan, -1.0, -7.0, 0.4, 11.9, 1e6, 5e18,
                                   -np.inf, np.inf, 401.0, 2.0 ** 23]])
    with np.errstate(invalid="ignore"):
        got = m.value_to_bin(probe)
        # NaN is the pseudo-category -1 where the column has a NaN bin, else
        # category 0; every other value is cut to its integer part
        keys = np.where(np.isnan(probe),
                        -1 if m.missing_type == MISSING_NAN else 0,
                        probe).astype(np.int64)
    want = [m.categorical_2_bin.get(int(k), m.num_bin - 1) for k in keys]
    np.testing.assert_array_equal(got, np.asarray(want, np.int32))
    assert got.dtype == np.int32
    assert (case == "huge_values") == (max(m.categorical_2_bin) >= 1 << 22)
    assert m.value_to_bin(float(probe[0])) == want[0]     # a scalar too
