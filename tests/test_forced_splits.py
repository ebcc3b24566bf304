"""Forced splits via forcedsplits_filename (reference:
serial_tree_learner.cpp:607-770 ForceSplits; config.h forcedsplits)."""
import json

import os

import numpy as np
import pytest

import lightgbm_tpu as lgb


def _data(n=800, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 6))
    y = (X[:, 0] + 0.6 * X[:, 1] - 0.3 * X[:, 2] > 0).astype(np.float64)
    return X, y


PARAMS = {"objective": "binary", "num_leaves": 15, "verbose": -1,
          "min_data_in_leaf": 5, "learning_rate": 0.2}


def _train(tmp_path, forced_json, extra=None, rounds=5):
    X, y = _data()
    path = str(tmp_path / "forced.json")
    with open(path, "w") as fh:
        json.dump(forced_json, fh)
    p = dict(PARAMS, forcedsplits_filename=path, **(extra or {}))
    ds = lgb.Dataset(X, label=y, params=p)
    bst = lgb.train(p, ds, num_boost_round=rounds)
    return bst, X, y


def test_root_split_is_forced(tmp_path):
    # feature 5 is pure noise — gain-driven growth would never pick it first
    bst, X, y = _train(tmp_path, {"feature": 5, "threshold": 0.0})
    d = bst.dump_model()
    for t in d["tree_info"]:
        assert t["tree_structure"]["split_feature"] == 5
    # the rest of the tree is gain-driven, so the model still learns
    from sklearn.metrics import roc_auc_score
    assert roc_auc_score(y, bst.predict(X)) > 0.85


def test_bfs_nesting_left_and_right(tmp_path):
    forced = {"feature": 5, "threshold": 0.0,
              "left": {"feature": 4, "threshold": 0.5},
              "right": {"feature": 3, "threshold": -0.5}}
    bst, X, y = _train(tmp_path, forced)
    root = bst.dump_model()["tree_info"][0]["tree_structure"]
    assert root["split_feature"] == 5
    assert root["left_child"]["split_feature"] == 4
    assert root["right_child"]["split_feature"] == 3
    # thresholds round to the bin boundary containing the requested value
    assert abs(root["threshold"]) < 0.2


def test_rejected_forced_split_not_applied(tmp_path):
    # an impossible gain bar rejects the forced split exactly like the
    # reference's 'gain getting worse' path (GatherInfoForThreshold) —
    # and with it every split, so trees stay single-leaf
    forced = {"feature": 5, "threshold": 0.0,
              "left": {"feature": 4, "threshold": 0.0}}
    bst, X, y = _train(tmp_path, forced,
                       extra={"min_gain_to_split": 1e9}, rounds=2)
    root = bst.dump_model()["tree_info"][0]["tree_structure"]
    assert "split_feature" not in root  # single leaf: nothing was forced


def test_forced_split_roundtrips_model_text(tmp_path):
    bst, X, y = _train(tmp_path, {"feature": 5, "threshold": 0.0})
    txt = bst.model_to_string()
    re = lgb.Booster(model_str=txt)
    np.testing.assert_allclose(re.predict(X), bst.predict(X), rtol=1e-6)


def test_missing_file_is_fatal(tmp_path):
    X, y = _data()
    p = dict(PARAMS, forcedsplits_filename=str(tmp_path / "nope.json"))
    ds = lgb.Dataset(X, label=y, params=p)
    with pytest.raises(lgb.LightGBMError):
        lgb.train(p, ds, num_boost_round=2)


@pytest.mark.skipif(not os.path.isdir("/root/reference/examples"),
                    reason="reference not mounted")
def test_reference_cli_forced_splits_parity():
    """Reference-CLI oracle: the captured model in tests/fixtures was
    trained by the reference binary with tests/fixtures/forced_splits.json
    on examples/binary_classification (num_trees=5, num_leaves=15,
    min_data_in_leaf=20, lr=0.1). Our run under the identical config must
    force the same BFS prefix — features AND (bin-boundary) thresholds —
    on every tree."""
    import os
    fix = os.path.join(os.path.dirname(__file__), "fixtures")
    ref_txt = open(os.path.join(fix, "ref_forced_splits_model.txt")).read()

    raw = np.loadtxt(
        "/root/reference/examples/binary_classification/binary.train")
    y, X = raw[:, 0], raw[:, 1:]
    p = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.1,
         "min_data_in_leaf": 20, "verbose": -1,
         "forcedsplits_filename": os.path.join(fix, "forced_splits.json")}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p), 5)
    ours = bst.model_to_string()

    def split_rows(txt, key):
        return [ln.split("=", 1)[1].split() for ln in txt.splitlines()
                if ln.startswith(key + "=")]

    ref_feats = split_rows(ref_txt, "split_feature")
    our_feats = split_rows(ours, "split_feature")
    ref_thr = split_rows(ref_txt, "threshold")
    our_thr = split_rows(ours, "threshold")
    assert len(our_feats) == len(ref_feats) == 5
    for rf, of, rt, ot in zip(ref_feats, our_feats, ref_thr, our_thr):
        assert of[:3] == rf[:3] == ["25", "10", "4"]
        np.testing.assert_allclose([float(v) for v in ot[:3]],
                                   [float(v) for v in rt[:3]], rtol=1e-9)
