"""Device batch forest prediction == host per-tree prediction.

The device path (core/forest.py) replaces the reference's CPU Predictor
pipeline (reference: src/application/predictor.hpp:28-271,
src/boosting/gbdt_prediction.cpp:1-91); these tests pin it to the host
numpy traversal on data with NaNs, categoricals and multiclass outputs.
"""
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb


def _train(params, X, y, rounds=12, cat=None):
    ds = lgb.Dataset(X, label=y,
                     categorical_feature=cat if cat is not None else "auto",
                     params=params)
    return lgb.train(dict(params), ds, num_boost_round=rounds)


def test_device_predict_matches_host_binary():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(1500, 8))
    X[rng.random(X.shape) < 0.05] = np.nan  # exercise missing routing
    y = (np.nansum(X[:, :3], axis=1) > 0).astype(np.float64)
    bst = _train({"objective": "binary", "num_leaves": 15, "verbose": -1,
                  "min_data_in_leaf": 5}, X, y)
    g = bst._gbdt
    Xt = rng.normal(size=(400, 8))
    Xt[rng.random(Xt.shape) < 0.05] = np.nan
    start, stop = g._iter_window(None, 0)
    host = np.zeros((Xt.shape[0], 1))
    for it in range(start, stop):
        host[:, 0] += g.models[it].predict(Xt)
    dev = g._predict_raw_device(Xt, start, stop)
    np.testing.assert_allclose(dev, host, rtol=0, atol=1e-4)


def test_device_predict_matches_host_multiclass_categorical():
    rng = np.random.default_rng(1)
    n = 1200
    Xnum = rng.normal(size=(n, 4))
    Xcat = rng.integers(0, 12, size=(n, 2)).astype(np.float64)
    X = np.hstack([Xnum, Xcat])
    y = ((Xnum[:, 0] > 0).astype(int) + (Xcat[:, 0] > 5).astype(int))
    bst = _train({"objective": "multiclass", "num_class": 3,
                  "num_leaves": 15, "verbose": -1, "min_data_in_leaf": 5},
                 X, y.astype(np.float64), cat=[4, 5])
    g = bst._gbdt
    Xt = np.hstack([rng.normal(size=(300, 4)),
                    rng.integers(-1, 14, size=(300, 2)).astype(np.float64)])
    start, stop = g._iter_window(None, 0)
    K = g.num_tpi
    host = np.zeros((Xt.shape[0], K))
    for it in range(start, stop):
        for k in range(K):
            host[:, k] += g.models[it * K + k].predict(Xt)
    dev = g._predict_raw_device(Xt, start, stop)
    np.testing.assert_allclose(dev, host, rtol=0, atol=1e-4)


def test_prediction_early_stop_converges_to_same_argmax():
    """Early-stopped margins keep the predicted class (reference contract:
    prediction_early_stop.cpp stops only when the margin is decisive)."""
    rng = np.random.default_rng(2)
    X = rng.normal(size=(1000, 6))
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float64)
    bst = _train({"objective": "binary", "num_leaves": 31, "verbose": -1,
                  "min_data_in_leaf": 5}, X, y, rounds=30)
    g = bst._gbdt
    Xt = rng.normal(size=(500, 6))
    full = g.predict(Xt)
    es = {"kind": "binary", "round_period": 5, "margin_threshold": 4.0}
    raw_es = g.predict_raw(Xt, early_stop=es)
    np.testing.assert_array_equal((full > 0.5),
                                  (raw_es[:, 0] > 0.0))
    # device path agrees with host path under early stop
    dev_es = g._predict_raw_device(Xt, *g._iter_window(None, 0),
                                   early_stop=es)
    np.testing.assert_allclose(dev_es, raw_es, rtol=0, atol=1e-4)


def test_booster_predict_uses_device_on_large_work(monkeypatch):
    rng = np.random.default_rng(3)
    X = rng.normal(size=(2000, 5))
    y = (X[:, 0] > 0).astype(np.float64)
    bst = _train({"objective": "binary", "num_leaves": 7, "verbose": -1},
                 X, y, rounds=8)
    g = bst._gbdt
    monkeypatch.setattr(type(g), "_DEVICE_PREDICT_MIN_WORK", 1)
    called = {}
    orig = type(g)._predict_raw_device

    def spy(self, *a, **kw):
        called["yes"] = True
        return orig(self, *a, **kw)

    monkeypatch.setattr(type(g), "_predict_raw_device", spy)
    p_dev = bst.predict(X)
    assert called.get("yes")
    monkeypatch.setattr(type(g), "_DEVICE_PREDICT_MIN_WORK", 10**18)
    p_host = bst.predict(X)
    np.testing.assert_allclose(p_dev, p_host, rtol=0, atol=1e-5)


def test_loaded_model_device_predict_matches_host(tmp_path):
    """Satellite: Booster(model_file=...).predict hits the device path
    (model-derived bin space, serve/packing.py) once the work threshold
    is met — no train_ds required — and matches the host loop."""
    rng = np.random.default_rng(4)
    n = 1200
    X = np.hstack([rng.normal(size=(n, 4)),
                   rng.integers(0, 10, size=(n, 2)).astype(np.float64)])
    X[:, :4][rng.random((n, 4)) < 0.06] = np.nan
    y = (np.nan_to_num(X[:, 0]) + (X[:, 4] > 4) > 0.5).astype(np.float64)
    bst = _train({"objective": "binary", "num_leaves": 15, "verbose": -1,
                  "min_data_in_leaf": 5}, X, y, rounds=15, cat=[4, 5])
    path = str(tmp_path / "m.txt")
    bst.save_model(path)

    import lightgbm_tpu as lgb
    lb = lgb.Booster(model_file=path)
    g = lb._gbdt
    Xt = np.hstack([rng.normal(size=(300, 4)),
                    rng.integers(-1, 13, size=(300, 2)).astype(np.float64)])
    Xt[:, :4][rng.random((300, 4)) < 0.06] = np.nan
    host = lb.predict(Xt)  # work below threshold -> host loop

    cls = type(g)
    old = cls._DEVICE_PREDICT_MIN_WORK
    try:
        cls._DEVICE_PREDICT_MIN_WORK = 1
        called = {}
        orig = cls._predict_raw_device

        def spy(self, *a, **kw):
            called["yes"] = True
            return orig(self, *a, **kw)

        cls._predict_raw_device = spy
        dev = lb.predict(Xt)
    finally:
        cls._DEVICE_PREDICT_MIN_WORK = old
        cls._predict_raw_device = orig
    assert called.get("yes"), "device path not taken for loaded model"
    np.testing.assert_allclose(dev, host, rtol=0, atol=1e-6)


def test_predict_leaf_device_matches_host(tmp_path):
    """Satellite: predict_leaf now has a device path (forest_leaf_fn);
    leaf indices must equal the host per-tree walk EXACTLY, for both a
    live trainer and a file-loaded booster."""
    rng = np.random.default_rng(5)
    X = rng.normal(size=(900, 5))
    X[rng.random(X.shape) < 0.05] = np.nan
    y = (np.nan_to_num(X[:, 0]) > 0).astype(np.float64)
    bst = _train({"objective": "binary", "num_leaves": 15, "verbose": -1,
                  "min_data_in_leaf": 5}, X, y, rounds=10)
    path = str(tmp_path / "m.txt")
    bst.save_model(path)
    Xt = rng.normal(size=(250, 5))
    Xt[rng.random(Xt.shape) < 0.05] = np.nan

    import lightgbm_tpu as lgb
    for booster in (bst, lgb.Booster(model_file=path)):
        g = booster._gbdt
        cls = type(g)
        host = booster.predict(Xt, pred_leaf=True)
        old = cls._DEVICE_PREDICT_MIN_WORK
        try:
            cls._DEVICE_PREDICT_MIN_WORK = 1
            dev = booster.predict(Xt, pred_leaf=True)
        finally:
            cls._DEVICE_PREDICT_MIN_WORK = old
        assert dev.shape == host.shape == (250, 10)
        np.testing.assert_array_equal(dev, host)


def _margin_settles_all(kind):
    """An early-stop spec whose margin threshold 0 settles EVERY row at
    the first check — the sharpest differential oracle available."""
    return {"kind": kind, "round_period": 3, "margin_threshold": 0.0}


def test_pred_early_stop_binary_differential():
    """Satellite coverage for the host early-stop loop: threshold 0
    freezes every row at the first round_period check (all-rows-settled
    early exit), so the result EQUALS the plain sum over the first
    round_period iterations; a huge threshold never settles and EQUALS
    the full sum."""
    rng = np.random.default_rng(6)
    X = rng.normal(size=(400, 6))
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float64)
    bst = _train({"objective": "binary", "num_leaves": 15, "verbose": -1,
                  "min_data_in_leaf": 5}, X, y, rounds=12)
    g = bst._gbdt
    Xt = rng.normal(size=(150, 6))

    full = g.predict_raw(Xt)
    never = g.predict_raw(Xt, early_stop={"kind": "binary",
                                          "round_period": 3,
                                          "margin_threshold": 1e9})
    np.testing.assert_array_equal(never, full)

    settled = g.predict_raw(Xt, early_stop=_margin_settles_all("binary"))
    first3 = g.predict_raw(Xt, num_iteration=3)
    np.testing.assert_array_equal(settled, first3)


def test_pred_early_stop_multiclass_differential():
    """The multiclass margin path (top-2 gap) of the host loop, same
    differential contract as the binary test."""
    rng = np.random.default_rng(7)
    X = rng.normal(size=(400, 5))
    y = (rng.integers(0, 3, 400)).astype(np.float64)
    bst = _train({"objective": "multiclass", "num_class": 3,
                  "num_leaves": 7, "verbose": -1, "min_data_in_leaf": 5},
                 X, y, rounds=9)
    g = bst._gbdt
    Xt = rng.normal(size=(120, 5))

    full = g.predict_raw(Xt)
    never = g.predict_raw(Xt, early_stop={"kind": "multiclass",
                                          "round_period": 2,
                                          "margin_threshold": 1e9})
    np.testing.assert_array_equal(never, full)

    settled = g.predict_raw(Xt,
                            early_stop=_margin_settles_all("multiclass"))
    first3 = g.predict_raw(Xt, num_iteration=3)
    np.testing.assert_array_equal(settled, first3)
    # settled margins keep the argmax of the full sum for decisive rows
    assert settled.shape == (120, 3)


def test_pred_early_stop_device_matches_host_multiclass():
    """Device early-stop (folded into the forest scan) follows the host
    loop's stop schedule: same spec, same outputs."""
    rng = np.random.default_rng(8)
    X = rng.normal(size=(500, 5))
    y = ((X[:, 0] > 0).astype(int) + (X[:, 1] > 0.5).astype(int)
         ).astype(np.float64)
    bst = _train({"objective": "multiclass", "num_class": 3,
                  "num_leaves": 7, "verbose": -1, "min_data_in_leaf": 5},
                 X, y, rounds=8)
    g = bst._gbdt
    Xt = rng.normal(size=(200, 5))
    for es in (None,
               {"kind": "multiclass", "round_period": 2,
                "margin_threshold": 1.5},
               _margin_settles_all("multiclass")):
        host = g.predict_raw(Xt, early_stop=es)
        dev = g._predict_raw_device(Xt, *g._iter_window(None, 0),
                                    early_stop=es)
        np.testing.assert_allclose(dev, host, rtol=0, atol=1e-6)


@pytest.mark.skipif(not os.path.isdir("/root/reference/examples"),
                    reason="reference not mounted")
def test_reference_cli_pred_early_stop_parity(tmp_path):
    """Reference-CLI oracle: predictions with pred_early_stop=true,
    freq=5, margin=1.5 over the reference-trained 20-tree model
    (fixtures ref_plain20_model.txt / ref_pred_early_stop.txt) must match
    our CLI predict on the same model byte-for-byte in value."""
    import os
    import subprocess
    import sys
    fix = os.path.join(os.path.dirname(__file__), "fixtures")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = str(tmp_path / "pred.txt")
    conf = tmp_path / "p.conf"
    conf.write_text(
        "task = predict\n"
        "data = /root/reference/examples/binary_classification/binary.test\n"
        f"input_model = {fix}/ref_plain20_model.txt\n"
        f"output_result = {out}\n"
        "pred_early_stop = true\npred_early_stop_freq = 5\n"
        "pred_early_stop_margin = 1.5\nverbosity = -1\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-m", "lightgbm_tpu",
                        f"config={conf}"], env=env, capture_output=True,
                       text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-1500:]
    ours = np.loadtxt(out)
    ref = np.loadtxt(os.path.join(fix, "ref_pred_early_stop.txt"))
    np.testing.assert_allclose(ours, ref, rtol=1e-6, atol=1e-9)
