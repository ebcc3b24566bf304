"""What the seed draws and what it does not, in a table born sparse
(harness/datagen_onehot.py), and what the program's EFB makes of it."""
import numpy as np

from harness import datagen_onehot

SPEC = {"task": "binary", "rows": 300000, "features": 62,
        "variables": [
            {"name": "month", "cardinality": 12, "zipf": 0.3, "effect": 0.35},
            {"name": "day_of_week", "cardinality": 7, "zipf": 0.3,
             "effect": 0.25},
            {"name": "carrier", "cardinality": 9, "zipf": 0.3, "effect": 0.5},
            {"name": "origin", "cardinality": 16, "zipf": 1.0, "effect": 0.5},
            {"name": "destination", "cardinality": 16, "zipf": 1.0,
             "effect": 0.5}],
        "numeric": 2, "numeric_effect": 0.7, "loading": 0.5, "signal": 1.0,
        "label_noise": 1.0, "label_seed": 9}


def test_eight_stored_values_a_row_at_the_configurations_shape():
    from harness import cells
    spec = dict(cells.load_cell("expo-train").config["data"], rows=50000)
    X, y, sizes = datagen_onehot.make_table(spec, 1)
    assert sizes is None and X.shape == (50000, 700) and X.format == "csr"
    assert X.data.dtype == np.float32 and X.indices.dtype == np.int32
    assert (np.diff(X.indptr) == 8).all() and X.has_sorted_indices
    off = datagen_onehot.column_offsets(spec)
    assert off.tolist() == [0, 12, 43, 50, 76, 387, 698]
    ind = X.indices.reshape(-1, 8)
    # one column of each variable's block, then the two numeric columns
    assert ((ind[:, :6] >= off[:-1]) & (ind[:, :6] < off[1:])).all()
    assert (ind[:, 6:] == [698, 699]).all()
    dat = X.data.reshape(-1, 8)
    assert (dat[:, :6] == 1.0).all() and (dat[:, 6:] > 0).all()
    assert set(np.unique(y)) == {0.0, 1.0}


def test_seed_moves_the_numeric_columns_and_nothing_else():
    X1, y1, _ = datagen_onehot.make_table(SPEC, 1)
    X1b, y1b, _ = datagen_onehot.make_table(SPEC, 1)
    X2, y2, _ = datagen_onehot.make_table(SPEC, 2147483659)
    assert (X1 != X1b).nnz == 0 and np.array_equal(y1, y1b)
    assert np.array_equal(y1, y2)            # labels are the configuration's
    assert np.array_equal(X1.indices, X2.indices)       # and every code
    d1, d2 = X1.data.reshape(-1, 7), X2.data.reshape(-1, 7)
    assert np.array_equal(d1[:, :5], d2[:, :5])
    assert not np.array_equal(d1[:, 5:], d2[:, 5:])
    # the numeric columns carry the label, and so does a popular value
    c = [abs(np.corrcoef(np.log(d1[:, j]), y1)[0, 1]) for j in (5, 6)]
    assert min(c) > 0.05
    top = np.asarray(X1[:, 28].todense()).ravel()       # the first origin
    assert 0.2 < top.mean() < 0.4


def test_result_does_not_depend_on_threads(monkeypatch):
    from harness import datagen
    X1, y1, _ = datagen_onehot.make_table(SPEC, 3)
    monkeypatch.setattr(datagen, "_threads", lambda: 1)
    X2, y2, _ = datagen_onehot.make_table(SPEC, 3)
    assert (X1 != X2).nnz == 0 and np.array_equal(y1, y2)


def test_a_prefix_is_a_prefix():
    X, y, _ = datagen_onehot.make_table(SPEC, 4)
    Xs, ys, _ = datagen_onehot.make_table(SPEC, 4, rows=140000)
    assert Xs.shape == (140000, 62)          # not a multiple of the block
    assert (Xs != X[:140000]).nnz == 0 and np.array_equal(ys, y[:140000])


def test_the_bundle_layout_does_not_move_with_the_seed():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    cfg = Config.from_params({"objective": "binary", "verbose": -1,
                              "bin_construct_sample_cnt": 50000})
    got = []
    for seed in (1, 2147483659):
        X, _, _ = datagen_onehot.make_table(SPEC, seed)
        got.append(BinnedDataset.from_csr(X, cfg))
    a, b = (d.bundle for d in got)
    assert a is not None and a.num_phys < 12
    assert a.groups == b.groups and a.num_phys == b.num_phys
    for k in ("feat2phys", "feat_offset", "needs_fix", "phys_num_bin"):
        assert np.array_equal(getattr(a, k), getattr(b, k)), k
    # nor does what else the growth program bakes in: bins a feature and
    # the bin of 0.0 (the numeric columns are positive, 0.0 lies under all)
    assert np.array_equal(got[0].bin_offsets, got[1].bin_offsets)
    assert [m.default_bin for m in got[0].bin_mappers] == \
        [m.default_bin for m in got[1].bin_mappers]
    assert not np.array_equal(got[0].X_bin, got[1].X_bin)
