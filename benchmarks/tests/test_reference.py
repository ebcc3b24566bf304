"""The plain NumPy reference against the program at toy size: model walk vs
``Booster.predict``, AUC / log loss / NDCG@10 vs the program's host metrics."""
import numpy as np
import pytest

from harness import datagen, reference

import lightgbm_tpu as lgb

TOY = {"num_leaves": 15, "min_data_in_leaf": 20, "verbose": -1}


@pytest.fixture(scope="module")
def binary():
    spec = {"task": "binary", "rows": 3000, "features": 10, "informative": 4,
            "loading": 0.5, "signal": 2.0, "label_noise": 1.0,
            "label_seed": 5}
    X, y, _ = datagen.make_table(spec, seed=1)
    params = {"objective": "binary", "metric": ["auc", "binary_logloss"],
              **TOY}
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=6,
                    keep_training_booster=True)
    return X, y, bst


def test_walk_matches_booster_predict(binary):
    X, _, bst = binary
    trees = reference.parse_model_string(bst.model_to_string())
    assert len(trees) == 6
    want = bst.predict(X, raw_score=True)
    got = reference.predict_raw(trees, X)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-10)
    # and the trainer's own device-side scores, to float32
    np.testing.assert_allclose(got, bst._raw_train_score(), atol=1e-5)


def test_auc_and_logloss_match_host_metrics(binary):
    _, y, bst = binary
    raw = bst._raw_train_score()
    host = {m: v for _, m, v, _ in bst.eval_train()}
    assert reference.auc(y, raw) == pytest.approx(host["auc"], abs=1e-9)
    assert reference.logloss(y, raw) == pytest.approx(
        host["binary_logloss"], abs=1e-6)


def test_auc_shares_ranks_among_ties():
    y = np.array([1, 0, 1, 0, 1, 0])
    s = np.array([.5, .5, .5, .1, .9, .5])
    # pairs (pos, neg): 9; wins 4 (0.9 > three, and three 0.5s > 0.1 ... )
    wins = sum((sp > sn) + 0.5 * (sp == sn)
               for sp in s[y == 1] for sn in s[y == 0])
    assert reference.auc(y, s) == pytest.approx(wins / 9)


def test_ndcg_matches_host_metric():
    spec = {"task": "rank", "rows": 2500, "features": 12, "informative": 4,
            "loading": 0.5, "signal": 1.0, "label_noise": 1.0,
            "label_seed": 5, "queries": {"log_mean": 3.0, "log_sigma": 1.0,
                                         "min": 1, "max": 120}}
    X, y, sizes = datagen.make_table(spec, seed=2)
    assert sizes.sum() == 2500 and sizes.min() >= 1
    params = {"objective": "lambdarank", "metric": "ndcg", "eval_at": [10],
              **TOY}
    bst = lgb.train(params, lgb.Dataset(X, label=y, group=sizes),
                    num_boost_round=4, keep_training_booster=True)
    raw = bst._raw_train_score()
    host = dict((k, v) for k, v, _ in
                bst._gbdt.metrics[0].eval_host(np.asarray(raw)))["ndcg@10"]
    assert reference.ndcg_at_k(y, raw, sizes, 10) == pytest.approx(
        host, abs=1e-9)
    # constant scores keep row order inside a query: still the host's value
    flat = np.zeros_like(raw)
    host0 = dict((k, v) for k, v, _ in
                 bst._gbdt.metrics[0].eval_host(flat))["ndcg@10"]
    assert reference.ndcg_at_k(y, flat, sizes, 10) == pytest.approx(
        host0, abs=1e-9)
