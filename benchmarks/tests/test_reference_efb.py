"""EFB's conflict rule as the benchmark states it (harness/reference_efb.py):
needed, and the program's encoder's own."""
import numpy as np
import scipy.sparse as sp

from harness import reference, reference_efb


# the reference's own knob lets two blocks that meet in 5% of the rows share
# a column; in the benchmark's cell it is 0 and the sample's luck does it
CONFLICTS = {"verbose": -1, "min_data_in_leaf": 5, "max_conflict_rate": 0.1}


def _forced_conflicts(seed=0, n=3000):
    """Two one-hot blocks of 6 columns, A in the first half of the rows and
    B in the second, that meet in 5% of the rows, and a numeric column."""
    rng = np.random.default_rng(seed)
    a, b = rng.integers(0, 6, n), rng.integers(0, 6, n)
    X = np.zeros((n, 13))
    first = np.arange(n) < n // 2
    meet = ~first & (rng.random(n) < 0.1)
    rows = np.flatnonzero(first | meet)
    X[rows, a[rows]] = 1.0                          # block A: columns 0..5
    rows = np.flatnonzero(~first)
    X[rows, 6 + b[rows]] = 1.0                      # block B: columns 6..11
    X[:, 12] = np.exp(rng.normal(size=n))
    return X, meet


def test_densify_keeps_the_last_member_and_counts_the_rows_it_touched():
    X = np.array([[1, 0, 1, 0, 2.5],     # columns 0 and 2 share a group
                  [0, 1, 0, 0, 1.5],
                  [1, 1, 1, 0, 0.5],     # three meet: the last one stays
                  [0, 0, 0, 1, 3.5]], np.float64)
    c = sp.csr_matrix(X)
    groups = [[1, 0, 2], [3], [4]]
    D, touched = reference_efb.densify(c.indptr, c.indices, c.data, c.shape,
                                       groups)
    assert touched == 2
    assert D.tolist() == [[0, 0, 1, 0, 2.5], [0, 1, 0, 0, 1.5],
                          [0, 0, 1, 0, 0.5], [0, 0, 0, 1, 3.5]]
    # nothing bundled, nothing touched
    D, touched = reference_efb.densify(c.indptr, c.indices, c.data, c.shape,
                                       [[i] for i in range(5)])
    assert touched == 0 and np.array_equal(D, X)


def test_rule_agrees_with_the_programs_encoder_on_forced_conflicts():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io import bundling
    from lightgbm_tpu.io.dataset import BinnedDataset
    X, meet = _forced_conflicts()
    ds = BinnedDataset.from_sample(X, len(X), Config.from_params(CONFLICTS))
    b, used = ds.bundle, ds.real_feature_idx
    members = max(b.groups, key=len)
    assert len(members) == 12            # both blocks in one physical column
    groups = [[int(used[i]) for i in g] for g in b.groups]
    c = sp.csr_matrix(X)
    D, touched = reference_efb.densify(c.indptr, c.indices, c.data, c.shape,
                                       groups)
    assert touched == int(meet.sum()) > 50
    assert (D != X).sum() == touched and ((D != X).any(axis=1) == meet).all()
    # the program's encoder makes one column of the raw rows and of the rows
    # the rule left: what the rule took away the encoder never kept
    mappers = [ds.bin_mappers[int(used[i])] for i in members]

    def encode(M):
        bins = [np.asarray(m.value_to_bin(M[:, int(used[i])]))
                for m, i in zip(mappers, members)]
        return bundling.encode_column(b, members, bins,
                                      [m.default_bin for m in mappers],
                                      len(M), np.uint8)
    assert np.array_equal(encode(X), encode(D))
    # each member reads back from that column what the rule left of it
    col = encode(X).astype(np.int64)
    for m, i in zip(mappers, members):
        off = int(b.feat_offset[i])
        own = (col >= off) & (col < off + m.num_bin)
        back = np.where(own, col - off, m.default_bin)
        assert np.array_equal(back, m.value_to_bin(D[:, int(used[i])]))
    # and the CSR ingest writes the same column
    ds._binarize_csc(c.tocsc())
    assert np.array_equal(ds.X_bin[:, b.groups.index(members)], encode(D))


def test_without_the_rule_a_conflicted_row_walks_to_another_leaf():
    import lightgbm_tpu as lgb
    X, meet = _forced_conflicts(1)
    rng = np.random.default_rng(5)
    w = np.array([1.0, -1, .5, -.5, 1.5, -1.5])
    y = ((X[:, :6] @ w - X[:, 6:12] @ w + 0.3 * rng.normal(size=len(X))) > 0
         ).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 15, "device_type": "cpu",
              **CONFLICTS}
    c = sp.csr_matrix(X)
    ds = lgb.Dataset(c, label=y, params=params)
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(5):
        bst.update()
    groups = ds.bundle_groups()
    assert max(len(g) for g in groups) == 12
    trees = reference.parse_model_string(bst.model_to_string())
    prog = bst._raw_train_score()
    D, touched = reference_efb.densify(c.indptr, c.indices, c.data, c.shape,
                                       groups)
    assert touched == int(meet.sum())

    def rel(raw):
        return np.abs(raw - prog) / (1.0 + np.abs(raw))
    assert rel(reference.predict_raw(trees, D)).max() <= 1e-5
    without = rel(reference.predict_raw(trees, X))
    assert without[~meet].max() <= 1e-5
    assert without[meet].max() > 1e-2           # another leaf, another value
    moved = np.array([reference.tree_leaves(t, D) != reference.tree_leaves(t, X)
                      for t in trees])
    assert moved[:, meet].any() and not moved[:, ~meet].any()
