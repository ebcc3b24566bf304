"""Public-API extras mirrored from the reference python package tests
(reference: tests/python_package_test/test_engine.py: save_load_copy_pickle,
get_split_value_histogram, trees_to_dataframe, max_bin_by_feature,
pandas_categorical)."""
import copy
import pickle

import numpy as np
import pandas as pd
import pytest

import lightgbm_tpu as lgb

PARAMS = {"objective": "binary", "num_leaves": 15, "verbose": -1,
          "min_data_in_leaf": 5}


def _train(n=600, seed=4, extra=None, rounds=5):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 6))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    p = dict(PARAMS, **(extra or {}))
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p), rounds)
    return bst, X, y


def test_pickle_and_copy_roundtrip():
    bst, X, y = _train()
    want = bst.predict(X)
    re = pickle.loads(pickle.dumps(bst))
    np.testing.assert_allclose(re.predict(X), want, rtol=1e-6)
    assert re.current_iteration() == bst.current_iteration()
    dup = copy.deepcopy(bst)
    np.testing.assert_allclose(dup.predict(X), want, rtol=1e-6)
    shallow = copy.copy(bst)
    np.testing.assert_allclose(shallow.predict(X), want, rtol=1e-6)


def test_predict_rejects_wider_matrix():
    """A prediction matrix with MORE columns than the model trained on is
    an error (the reference C API's column-count check), dense and
    sparse alike; narrower sparse inputs keep the LibSVM padding path."""
    import scipy.sparse as sp
    bst, X, y = _train()
    wide = np.hstack([X, np.zeros((X.shape[0], 2))])
    with pytest.raises(lgb.LightGBMError, match="number of features"):
        bst.predict(wide)
    with pytest.raises(lgb.LightGBMError, match="number of features"):
        bst.predict(sp.csr_matrix(wide))
    # narrower DENSE input has no padding story: same LightGBMError
    # instead of an IndexError deep inside binning
    with pytest.raises(lgb.LightGBMError, match="number of features"):
        bst.predict(X[:, :5])
    # narrower sparse input still pads up to the model width
    narrow = sp.csr_matrix(X[:, :5])
    assert bst.predict(narrow).shape == (X.shape[0],)


def test_get_split_value_histogram():
    bst, X, y = _train(rounds=8)
    hist, edges = bst.get_split_value_histogram(0)
    assert hist.sum() == int(bst.feature_importance("split")[0])
    assert len(edges) == len(hist) + 1
    df = bst.get_split_value_histogram("Column_0", xgboost_style=True)
    assert list(df.columns) == ["SplitValue", "Count"]
    assert df["Count"].sum() == hist.sum()


def test_trees_to_dataframe():
    bst, X, y = _train(rounds=3)
    df = bst.trees_to_dataframe()
    # one leaf more than splits per tree
    for ti in range(3):
        sub = df[df.tree_index == ti]
        leaves = sub[sub.split_feature.isna()]
        splits = sub[~sub.split_feature.isna()]
        assert len(leaves) == len(splits) + 1
        # counts are conserved: root count equals each leaf-count sum
        root = sub[sub.node_depth == 1].iloc[0]
        assert leaves["count"].sum() == root["count"]
    assert df.node_index.is_unique


def test_max_bin_by_feature():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(500, 3))
    y = (X[:, 0] > 0).astype(np.float64)
    p = dict(PARAMS, max_bin_by_feature=[4, 64, 255])
    ds = lgb.Dataset(X, label=y, params=p)
    ds.construct()
    nb = [m.num_bin for m in ds._handle.bin_mappers]
    assert nb[0] <= 5 and nb[1] <= 65  # +1 potential NaN bin
    assert nb[1] > nb[0]
    p_bad = dict(PARAMS, max_bin_by_feature=[4, 64])
    with pytest.raises(lgb.LightGBMError, match="same size"):
        lgb.Dataset(X, label=y, params=p_bad).construct()


def test_pandas_categorical_roundtrip():
    rng = np.random.default_rng(6)
    n = 800
    colors = rng.choice(["red", "green", "blue", "teal"], n)
    x1 = rng.normal(size=n)
    y = ((colors == "red") | (colors == "teal") * (x1 > 0)).astype(float)
    df = pd.DataFrame({"c": pd.Categorical(colors), "x": x1})
    p = dict(PARAMS, min_data_in_leaf=5)
    bst = lgb.train(p, lgb.Dataset(df, label=y, params=p), 10)
    pred = bst.predict(df)
    from sklearn.metrics import roc_auc_score
    assert roc_auc_score(y, pred) > 0.9
    # category order differs at predict time: the TRAIN mapping must win
    df2 = df.copy()
    df2["c"] = df2["c"].cat.set_categories(["teal", "blue", "green", "red"])
    np.testing.assert_allclose(bst.predict(df2), pred, rtol=1e-9)
    # unseen category routes like missing, not like category 0
    df3 = df.copy().astype({"c": str})
    df3.loc[:, "c"] = "violet"
    df3["c"] = pd.Categorical(df3["c"])
    p3 = bst.predict(df3)
    assert np.isfinite(p3).all()
    # mapping survives the model text round-trip
    re = lgb.Booster(model_str=bst.model_to_string())
    assert re.pandas_categorical == bst.pandas_categorical
    np.testing.assert_allclose(re.predict(df2), pred, rtol=1e-6)


def test_pandas_plain_dataframe_unchanged():
    rng = np.random.default_rng(8)
    X = rng.normal(size=(400, 4))
    y = (X[:, 0] > 0).astype(np.float64)
    df = pd.DataFrame(X, columns=[f"f{i}" for i in range(4)])
    bst = lgb.train(PARAMS, lgb.Dataset(df, label=y, params=PARAMS), 3)
    np.testing.assert_allclose(bst.predict(df), bst.predict(X), rtol=1e-9)
    assert bst.feature_name() == ["f0", "f1", "f2", "f3"]


def test_pandas_int_categories_json_roundtrip():
    """Numpy-int category values must survive the model-text JSON line
    (regression: json.dumps on np.int64)."""
    rng = np.random.default_rng(12)
    n = 400
    codes = rng.integers(10, 14, n)
    df = pd.DataFrame({"c": pd.Categorical(codes), "x": rng.normal(size=n)})
    y = (codes % 2).astype(float)
    bst = lgb.train(PARAMS, lgb.Dataset(df, label=y, params=PARAMS), 3)
    txt = bst.model_to_string()          # would raise before the fix
    re = lgb.Booster(model_str=txt)
    np.testing.assert_allclose(re.predict(df), bst.predict(df), rtol=1e-6)
    # pickling also goes through the JSON path
    re2 = pickle.loads(pickle.dumps(bst))
    assert re2.pandas_categorical == bst.pandas_categorical
    np.testing.assert_allclose(re2.predict(df), bst.predict(df), rtol=1e-6)


def test_params_categorical_fallback_with_plain_dataframe():
    """categorical_feature passed via params must survive the pandas path
    when the frame has no category-dtype columns."""
    rng = np.random.default_rng(13)
    X = rng.integers(0, 5, size=(500, 3)).astype(float)
    y = (X[:, 2] % 2).astype(float)
    p = dict(PARAMS, categorical_feature=[2], min_data_in_leaf=5)
    df = pd.DataFrame(X, columns=["a", "b", "c"])
    ds = lgb.Dataset(df, label=y, params=p)
    ds.construct()
    from lightgbm_tpu.io.binning import BIN_CATEGORICAL
    assert ds._handle.bin_mappers[2].bin_type == BIN_CATEGORICAL


def test_lightgbm_import_shim():
    """Reference scripts do `import lightgbm as lgb` — the shim must
    expose the same surface as lightgbm_tpu."""
    import lightgbm as ref_style
    assert ref_style.Dataset is lgb.Dataset
    assert ref_style.Booster is lgb.Booster
    assert ref_style.train is lgb.train
    assert ref_style.LGBMClassifier is lgb.LGBMClassifier
    assert hasattr(ref_style, "plot_importance")
    assert hasattr(ref_style, "cv")


def test_sklearn_estimator_pickles():
    """Fitted sklearn wrappers must pickle (reference:
    test_sklearn.py joblib round-trips) — exercises Booster.__getstate__
    inside the estimator."""
    rng = np.random.default_rng(21)
    X = rng.normal(size=(300, 5))
    y = (X[:, 0] > 0).astype(int)
    clf = lgb.LGBMClassifier(n_estimators=4, num_leaves=7,
                             min_child_samples=5, verbose=-1)
    clf.fit(X, y)
    re = pickle.loads(pickle.dumps(clf))
    np.testing.assert_allclose(re.predict_proba(X), clf.predict_proba(X),
                               rtol=1e-6)
    assert (re.predict(X) == clf.predict(X)).all()


def test_compat_module_flags():
    import importlib.util

    from lightgbm_tpu import compat
    for flag, mod in (("PANDAS_INSTALLED", "pandas"),
                      ("MATPLOTLIB_INSTALLED", "matplotlib"),
                      ("SKLEARN_INSTALLED", "sklearn"),
                      ("GRAPHVIZ_INSTALLED", "graphviz")):
        assert getattr(compat, flag) == bool(
            importlib.util.find_spec(mod))
    import lightgbm
    assert lightgbm.compat is compat
    import json
    assert json.dumps({"v": np.int64(3), "a": np.array([1, 2])},
                      default=compat.json_default_with_numpy) \
        == '{"v": 3, "a": [1, 2]}'


def test_compile_cache_env_var_places_the_cache(tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, JAX already holds that
    directory: the package sets none in code (not even the
    tpu_compile_cache_dir parameter's) and reports the variable's."""
    import jax

    from lightgbm_tpu.utils import compile_cache as cc

    env_dir = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    monkeypatch.setattr(cc, "_state", {"dir": None, "warm": None})
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, val: calls.append(name))
    assert cc.enable_compile_cache(str(tmp_path / "param")) == env_dir
    assert "jax_compilation_cache_dir" not in calls
    assert cc.compile_cache_info() == {"dir": env_dir, "warm": False}
    assert not (tmp_path / "param").exists()


def test_compile_cache_default_dir_is_fixed_and_not_for_the_cpu(monkeypatch):
    """Without the variable or the parameter the directory is the fixed
    <checkout>/.jax_cache — except on the CPU backend, where nothing is
    placed (XLA:CPU reloads under jax 0.9.0: utils/compile_cache.py)."""
    import os

    import jax

    from lightgbm_tpu.utils import compile_cache as cc

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.DEFAULT_DIR == os.path.join(repo, ".jax_cache")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(cc, "_state", {"dir": None, "warm": None})
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, val: calls.append((name, val)))
    assert jax.default_backend() == "cpu"
    assert cc.enable_compile_cache() is None and not calls
    assert cc.compile_cache_info() == {"dir": None, "warm": None}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert cc.enable_compile_cache() == cc.DEFAULT_DIR
    assert ("jax_compilation_cache_dir", cc.DEFAULT_DIR) in calls
    assert cc.compile_cache_info()["dir"] == cc.DEFAULT_DIR


_CACHE_CHILD = """
import json
import sys
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.utils.compile_cache import compile_cache_info
rng = np.random.default_rng(0)
X = rng.normal(size=(200, 3))
y = (X[:, 0] > 0).astype(np.float64)
params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
          "min_data_in_leaf": 5, "tpu_compile_cache_dir": sys.argv[1]}
lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=2)
print(json.dumps(compile_cache_info()))
"""


def test_compile_cache_shared_across_processes_and_warms(tmp_path):
    """Two processes given the same directory share it and the second
    finds it warm.  Placed by the parameter: the default directory is
    not used on the CPU backend, and the suite must not write into the
    checkout."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = str(tmp_path / "cache")
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "JAX_ENABLE_COMPILATION_CACHE")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    infos = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", _CACHE_CHILD, want],
                           env=env, capture_output=True, text=True,
                           timeout=300, cwd=str(tmp_path))
        assert r.returncode == 0, r.stderr[-2000:]
        infos.append(json.loads(r.stdout.strip().splitlines()[-1]))
    assert [i["dir"] for i in infos] == [want, want]
    assert [i["warm"] for i in infos] == [False, True]
    assert any(os.scandir(want)), "no cache entries written"
