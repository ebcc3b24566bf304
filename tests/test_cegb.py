"""CEGB (cost-effective gradient boosting) tests.

Mirrors the reference's CEGB behavior checks (reference:
tests/python_package_test/test_basic.py:236-300,
src/treelearner/cost_effective_gradient_boosting.hpp:21-117).
"""
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb


def _data(seed=0, n=1500, f=8):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    # every feature mildly informative so penalties change the choice set
    w = rng.normal(size=f) * 0.6
    y = (X @ w + rng.logistic(size=n) * 0.5 > 0).astype(np.float64)
    return X, y


def _features_used(bst):
    return {i for i, v in enumerate(bst.feature_importance("split")) if v > 0}


def test_coupled_penalty_narrows_feature_set():
    X, y = _data()
    base = {"objective": "binary", "num_leaves": 31, "verbose": -1,
            "min_data_in_leaf": 5}
    ds = lgb.Dataset(X, label=y, params=base)
    plain = lgb.train(dict(base), ds, num_boost_round=10)
    # huge coupled penalty on all but features 0/1
    pen = [0.0, 0.0] + [1e6] * (X.shape[1] - 2)
    p = dict(base, cegb_penalty_feature_coupled=pen)
    ds2 = lgb.Dataset(X, label=y, params=p)
    constrained = lgb.train(p, ds2, num_boost_round=10)
    assert _features_used(constrained) <= {0, 1}
    assert len(_features_used(plain)) > 2


def test_split_penalty_prunes_splits():
    X, y = _data(seed=1)
    base = {"objective": "binary", "num_leaves": 63, "verbose": -1,
            "min_data_in_leaf": 5}
    ds = lgb.Dataset(X, label=y, params=base)
    plain = lgb.train(dict(base), ds, num_boost_round=5)
    p = dict(base, cegb_penalty_split=0.5)
    ds2 = lgb.Dataset(X, label=y, params=p)
    pruned = lgb.train(p, ds2, num_boost_round=5)
    n_plain = sum(t.num_leaves for t in plain._gbdt.models)
    n_pruned = sum(t.num_leaves for t in pruned._gbdt.models)
    assert n_pruned < n_plain


def test_tradeoff_split_scaling_equality():
    """(tradeoff=a, split=b) == (tradeoff=a*k, split=b/k): the delta is
    their product (reference: DetlaGain, hpp:50-52; equality tested in
    reference test_basic.py:262-300)."""
    X, y = _data(seed=2)
    base = {"objective": "binary", "num_leaves": 31, "verbose": -1,
            "min_data_in_leaf": 5}
    preds = []
    for tr, sp in ((1.0, 0.0004), (4.0, 0.0001)):
        p = dict(base, cegb_tradeoff=tr, cegb_penalty_split=sp)
        ds = lgb.Dataset(X, label=y, params=p)
        bst = lgb.train(p, ds, num_boost_round=8)
        preds.append(bst.predict(X))
    np.testing.assert_allclose(preds[0], preds[1], atol=1e-12)


def test_lazy_penalty_serial_path():
    """Lazy penalties prefer re-using features already paid for on the
    same rows; smoke: training works and reuses a narrower feature set."""
    X, y = _data(seed=3)
    p = {"objective": "binary", "num_leaves": 31, "verbose": -1,
         "min_data_in_leaf": 5,
         "cegb_penalty_feature_lazy": [1e6] * 6 + [0.0, 0.0]}
    ds = lgb.Dataset(X, label=y, params=p)
    bst = lgb.train(p, ds, num_boost_round=8)
    assert _features_used(bst) <= {6, 7}


def test_bad_penalty_length_raises():
    X, y = _data(seed=4)
    p = {"objective": "binary", "verbose": -1,
         "cegb_penalty_feature_coupled": [1.0, 2.0]}
    ds = lgb.Dataset(X, label=y, params=p)
    with pytest.raises(Exception):
        lgb.train(p, ds, num_boost_round=2)


@pytest.mark.skipif(not os.path.isdir("/root/reference/examples"),
                    reason="reference not mounted")
def test_reference_cli_cegb_parity():
    """Reference-CLI oracle (tests/fixtures/ref_cegb_model.txt:
    binary example, num_trees=5, num_leaves=31, min_data_in_leaf=20,
    lr=0.1, cegb_penalty_split=0.02): the per-tree leaf counts under the
    split penalty must match the reference exactly, and the split
    structure of the first tree must agree."""
    import os
    fix = os.path.join(os.path.dirname(__file__), "fixtures")
    ref_txt = open(os.path.join(fix, "ref_cegb_model.txt")).read()

    raw = np.loadtxt(
        "/root/reference/examples/binary_classification/binary.train")
    y, X = raw[:, 0], raw[:, 1:]
    p = {"objective": "binary", "num_leaves": 31, "learning_rate": 0.1,
         "min_data_in_leaf": 20, "verbose": -1,
         "cegb_penalty_split": 0.02, "cegb_tradeoff": 1.0}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p), 5)
    ours = bst.model_to_string()

    def grab(txt, key):
        return [ln.split("=", 1)[1] for ln in txt.splitlines()
                if ln.startswith(key + "=")]

    ref_nl = grab(ref_txt, "num_leaves")  # one line per tree, no header
    our_nl = grab(ours, "num_leaves")
    assert our_nl == ref_nl, (our_nl, ref_nl)
    assert grab(ours, "split_feature")[0] == grab(ref_txt,
                                                  "split_feature")[0]
