"""Numeric columns with missing values held to the benchmark's plain
reference (``benchmarks/harness/reference_missing.py``: NumPy, float64,
nothing of the program in it), at toy size on the CPU: two trees of the wave
path (kernel interpreted) and of the serial grower against the NumPy
grower's, the walk of the exported model against ``Booster.predict`` on rows
with NaN, and the counts ``work_counters()`` reads off each tree,
``missing_splits`` and ``scan_bins``, against the NumPy grower's own.

The table is 4,096 rows of 24 columns in six station blocks of four: a row
passes a station or not, and the columns of a station it did not pass are
NaN, and so is one entry in twenty of a station it did pass.  Four cases: no
NaN, 30% and 81% of the stations not passed, and the last with one column
NaN in every row but one.
"""
import os
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import reference_missing as rm  # noqa: E402

CASES = {"none": 0.0, "0.3": 0.3, "0.81": 0.81, "one-left": 0.81}
ROWS, STATIONS, PER_STATION = 4096, 6, 4
ITERS = 2
# the hessian bound ends growth (about 30 leaves at a root sum of ~350),
# well under the cap: under a binding cap the wave path grows another tree
# than a strict best-first grower, by design
PARAMS = {"objective": "binary", "max_bin": 63, "num_leaves": 63,
          "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 10.0,
          "learning_rate": 0.1, "verbose": -1}
LEAF_RTOL = 1e-5


def _table(case: str, seed: int = 0):
    """``(X float32 with NaN, y, init scores)`` (module text)."""
    rng = np.random.default_rng([seed, len(case)])
    F = STATIONS * PER_STATION
    passed = rng.random((ROWS, STATIONS)) >= CASES[case]
    # a station's columns are NaN together, and now and then one alone:
    # columns with one NaN pattern would cut every node alike, and rounding
    # would pick the column
    present = np.repeat(passed, PER_STATION, axis=1) & (
        rng.random((ROWS, F)) >= 0.05 * (CASES[case] > 0))
    k = rng.integers(3, 13, F)
    grid = [np.round(rng.uniform(0.05, 1.0) + 0.01 * np.arange(n), 3)
            for n in k]
    latent = rng.standard_normal(ROWS)
    z = 0.6 * latent[:, None] + 0.8 * rng.standard_normal((ROWS, F))
    u = np.argsort(np.argsort(z, axis=0), axis=0) / ROWS   # ranks in [0, 1)
    X = np.stack([grid[j][(u[:, j] * k[j]).astype(int)] for j in range(F)],
                 axis=1).astype(np.float32)
    X[~present] = np.nan
    if case == "one-left":
        X[:, 0] = np.nan
        X[0, 0] = 0.5
    score = latent + passed @ rng.normal(0.0, 0.7, STATIONS) \
        + rng.standard_normal(ROWS)
    y = (score > np.quantile(score, 0.9)).astype(np.float64)
    base = np.log(y.mean() / (1.0 - y.mean()))
    init = base + 0.5 * rng.standard_normal(ROWS)
    return X, y, init


def _kept(bins, num_bins, min_data):
    """The columns a split could use at all (the reference's NeedFilter):
    some cut of the bins, NaN bin last, with ``min_data`` rows a side."""
    out = []
    for f in range(bins.shape[1]):
        left = np.cumsum(np.bincount(bins[:, f], minlength=num_bins[f]))
        out.append(bool(np.any((left[:-1] >= min_data)
                               & (len(bins) - left[:-1] >= min_data))))
    return np.asarray(out)


def _boost(X, y, init, wave: bool, mp):
    """Two updates from ``init``: the model text, the scores each tree was
    grown from, and the work counters."""
    if wave:
        mp.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    else:
        mp.delenv("LGBM_TPU_FORCE_WAVE", raising=False)
    p = {**PARAMS, "device_type": "tpu" if wave else "cpu"}
    bst = lgb.Booster(params=p, train_set=lgb.Dataset(
        X, label=y, init_score=init, params=p))
    assert bst._gbdt.uses_wave == wave
    scores = []
    for _ in range(ITERS):
        scores.append(np.asarray(bst._raw_train_score(), np.float64).copy())
        bst.update()
    return bst, scores


@pytest.fixture(scope="module")
def grown():
    """``{(case, grower): (X, booster, [(reference tree, program tree)])}``,
    each program tree against the NumPy grower's from the same scores."""
    mp = pytest.MonkeyPatch()
    out = {}
    try:
        for case in CASES:
            X, y, init = _table(case)
            bins, num_bins = rm.bin_columns(X, PARAMS["max_bin"])
            nan_cols = np.isnan(X).any(axis=0)
            for grower in ("wave", "serial"):
                bst, scores = _boost(X, y, init, grower == "wave", mp)
                trees = rm.parse_model_string(bst.model_to_string())
                pairs = []
                for s, tree in zip(scores, trees):
                    p = 1.0 / (1.0 + np.exp(-s))
                    ref = rm.grow_tree(bins, num_bins, nan_cols, p - y,
                                       p * (1.0 - p), PARAMS)
                    pairs.append((ref, tree))
                out[case, grower] = (X, bst, pairs, bins, num_bins)
    finally:
        mp.undo()
    return out


@pytest.mark.parametrize("grower", ["wave", "serial"])
@pytest.mark.parametrize("case", list(CASES))
def test_growers_grow_the_reference_trees(grown, case, grower):
    """Every node the same cut of its rows (a mirror image counts as the
    same: NaN-and-the-empty-bin left is every-value-left, NaN right), on the
    same feature, and every leaf's value within 1e-5 of the tree's largest;
    below a node the NumPy grower calls fragile (its winner within rounding
    of another cut) the trees may part, and most nodes are above one."""
    X, bst, pairs, _, _ = grown[case, grower]
    assert bst.work_counters(last=0)["stamps"]["uses_wave"] == (
        grower == "wave")
    for ref, tree in pairs:
        res = rm.compare_trees(ref, tree, X, leaf_rtol=LEAF_RTOL,
                               shrinkage=PARAMS["learning_rate"])
        assert res["ok"], res
        assert res["tied"] <= 1 and res["same"] > len(ref["nodes"]) // 2 > 5
        assert res["same_feature"] == res["same"]
        if res["tied"] == 0:
            assert tree["num_leaves"] == len(ref["leaf_value"])
        assert tree["num_leaves"] < PARAMS["num_leaves"]
    # the exported missing type: NaN on a column with NaN, None elsewhere
    nan_cols = np.isnan(X).any(axis=0)
    for _, tree in pairs:
        kinds = (tree["decision_type"] >> 2) & 3
        assert np.array_equal(kinds == rm.MISSING_NAN,
                              nan_cols[tree["split_feature"]])
        assert not np.any(kinds == rm.MISSING_ZERO)


@pytest.mark.parametrize("case", list(CASES))
def test_walk_equals_predict_on_rows_with_nan(grown, case):
    """The NumPy walk of the exported model against ``Booster.predict``,
    on the training rows and on rows with NaN where training had none."""
    X, bst, _, _, _ = grown[case, "wave"]
    Xt = X[:512].copy()
    Xt[np.arange(512), np.arange(512) % Xt.shape[1]] = np.nan
    Xt[1::7] = np.nan
    trees = rm.parse_model_string(bst.model_to_string())
    np.testing.assert_allclose(rm.predict_raw(trees, Xt),
                               bst.predict(Xt, raw_score=True),
                               rtol=0, atol=1e-9)
    np.testing.assert_allclose(rm.predict_raw(trees, X),
                               bst.predict(X, raw_score=True),
                               rtol=0, atol=1e-9)


@pytest.mark.parametrize("case", list(CASES))
def test_counters_read_the_reference_counts(grown, case):
    """``missing_splits``: the tree's splits on a column with NaN (the
    column's missing type is NaN); ``scan_bins``: every leaf scanned (the
    root and both children of each split) times the bins of the columns
    the program scans, those a split could use.  Counted on the NumPy
    grower's tree where the program's is the same node for node, else on
    the exported tree."""
    X, bst, pairs, bins, num_bins = grown[case, "wave"]
    kept = _kept(bins, num_bins, PARAMS["min_data_in_leaf"])
    nan_cols = np.isnan(X).any(axis=0)
    work = bst.work_counters()
    assert work["features"] == int(kept.sum())
    same = 0
    for t, (ref, tree) in zip(work["trees"], pairs):
        res = rm.compare_trees(ref, tree, X, leaf_rtol=LEAF_RTOL,
                               shrinkage=PARAMS["learning_rate"])
        feats = ([nd["feature"] for nd in ref["nodes"]] if res["tied"] == 0
                 else list(tree["split_feature"]))
        same += res["tied"] == 0
        assert t["walks"] == len(feats)
        assert t["missing_splits"] == int(nan_cols[feats].sum())
        assert t["scan_bins"] == (2 * len(feats) + 1) \
            * int(num_bins[kept].sum())
    assert same >= 1
    if case == "none":
        assert all(t["missing_splits"] == 0 for t in work["trees"])
    else:
        assert all(0 < t["missing_splits"] for t in work["trees"])


def _mirror_table():
    """One column: 0.5 in the first 60 rows, NaN in the last 40, which are
    the positives; the one split worth making is NaN against the rest."""
    X = np.full((100, 1), np.nan, np.float32)
    X[:60, 0] = 0.5
    y = (np.arange(100) >= 60).astype(np.float64)
    p = np.linspace(0.3, 0.5, 100)              # no two rows alike
    bins, num_bins = rm.bin_columns(X, 63)
    ref = rm.grow_tree(bins, num_bins, [True], p - y, p * (1 - p),
                       {"num_leaves": 2, "min_data_in_leaf": 1,
                        "min_sum_hessian_in_leaf": 1.0})
    return X, ref


def _one_split(threshold: float, default_left: bool, left_value,
               right_value) -> dict:
    """A program tree of one numeric split on column 0, missing type NaN."""
    return {"num_leaves": 2, "split_feature": np.array([0]),
            "threshold": np.array([threshold]),
            "decision_type": np.array([rm.MISSING_NAN << 2
                                       | (2 if default_left else 0)]),
            "left_child": np.array([-1]), "right_child": np.array([-2]),
            "leaf_value": np.array([left_value, right_value])}


@pytest.mark.parametrize("mirror", [False, True])
def test_compare_trees_takes_a_mirror_for_the_same_split(mirror):
    """``compare_trees`` holds nodes as cuts of their rows: the NaN-against-
    the-rest split exported either way round is the same split, and its
    leaves are matched by their rows, not by their side."""
    X, ref = _mirror_table()
    nd, = ref["nodes"]
    vals = 0.1 * ref["leaf_value"]
    rows_left = np.isin(np.arange(100), nd["left_rows"])
    v_nan = vals[0] if rows_left[-1] else vals[1]
    v_num = vals[1] if rows_left[-1] else vals[0]
    tree = (_one_split(1e-35, True, v_nan, v_num) if mirror
            else _one_split(1.0, False, v_num, v_nan))
    res = rm.compare_trees(ref, tree, X, leaf_rtol=1e-12, shrinkage=0.1)
    assert res["ok"] and res["same"] == 1 and res["leaves"] == 2, res


def test_compare_trees_finds_another_cut_and_a_wrong_leaf():
    X, ref = _mirror_table()
    vals = 0.1 * ref["leaf_value"]
    # every row left but NaN... and the first ten values: another cut
    X2 = X.copy()
    X2[:10, 0] = 0.25
    res = rm.compare_trees(ref, _one_split(0.3, False, *vals), X2,
                           shrinkage=0.1)
    assert not res["ok"] and len(res["differ"]) == 1
    nd, = ref["nodes"]
    if np.isin(99, nd["left_rows"]):
        good = _one_split(1e-35, True, *vals)
    else:
        good = _one_split(1.0, False, *vals)
    assert rm.compare_trees(ref, good, X, leaf_rtol=1e-12,
                            shrinkage=0.1)["ok"]
    off = dict(good, leaf_value=good["leaf_value"] * (1 + 1e-3))
    res = rm.compare_trees(ref, off, X, leaf_rtol=1e-4, shrinkage=0.1)
    assert not res["ok"] and len(res["leaf_fail"]) == 2
