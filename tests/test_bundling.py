"""EFB (Exclusive Feature Bundling) tests.

Mirrors the reference's EFB behavior (reference: src/io/dataset.cpp:41-263):
mutually-exclusive sparse features share physical columns, training results
are unchanged, and conflict budgets are honored.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset


def _onehotish(n=3000, blocks=40, seed=0):
    """Sparse mutually-exclusive features: one-hot blocks + 2 dense cols."""
    rng = np.random.default_rng(seed)
    sel = rng.integers(0, blocks, size=n)
    Xs = np.zeros((n, blocks))
    Xs[np.arange(n), sel] = rng.random(n) + 0.5
    Xd = rng.normal(size=(n, 2))
    X = np.hstack([Xd, Xs])
    y = (Xd[:, 0] + (sel < blocks // 2) + rng.logistic(size=n) * 0.3 > 0.5)
    return X, y.astype(np.float64)


def test_bundles_reduce_physical_columns():
    X, y = _onehotish()
    cfg = Config.from_params({"verbose": -1})
    ds = BinnedDataset.from_matrix(X, cfg)
    assert ds.bundle is not None
    assert ds.num_phys_features < ds.num_features
    # the 40 exclusive one-hot columns collapse into very few bundles
    assert ds.num_phys_features <= 2 + 6
    assert ds.num_features == X.shape[1]
    # physical bins stay within uint8
    assert ds.X_bin.dtype == np.uint8
    assert int(ds.phys_max_bins().max()) <= 256


def test_bundle_decode_roundtrip():
    """Physical encode/decode returns each feature's own bin, except the
    default bin (reconstructed via FixHistogram semantics)."""
    X, y = _onehotish(n=800, blocks=10)
    cfg = Config.from_params({"verbose": -1})
    ds = BinnedDataset.from_matrix(X, cfg)
    assert ds.bundle is not None
    b = ds.bundle
    used = ds.real_feature_idx
    for inner in range(ds.num_features):
        m = ds.bin_mappers[int(used[inner])]
        fb = np.asarray(m.value_to_bin(X[:, int(used[inner])]))
        colp = ds.X_bin[:, b.feat2phys[inner]].astype(np.int64)
        off, nb = int(b.feat_offset[inner]), m.num_bin
        inr = (colp >= off) & (colp < off + nb) if off else np.ones_like(colp, bool)
        dec = np.where(inr, colp - off, m.default_bin)
        if off == 0:  # singleton column: exact
            np.testing.assert_array_equal(dec, fb)
        else:
            nz = fb != m.default_bin
            # non-default values survive unless lost to a conflict
            agree = dec[nz] == fb[nz]
            assert agree.mean() > 0.95
            # default rows always decode to default
            np.testing.assert_array_equal(dec[~nz], m.default_bin)


def test_training_metrics_unchanged_vs_no_bundle():
    X, y = _onehotish()
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5, "metric": "auc"}
    out = {}
    for enable in (True, False):
        p = dict(params, enable_bundle=enable)
        ds = lgb.Dataset(X, label=y, params=p)
        bst = lgb.train(p, ds, num_boost_round=15)
        pred = bst.predict(X)
        from sklearn.metrics import roc_auc_score
        out[enable] = roc_auc_score(y, pred)
    assert out[True] > 0.80
    # EFB is an approximation only on conflict rows; exclusive features
    # have none, so quality must match closely
    assert abs(out[True] - out[False]) < 0.01


def test_bundled_predict_device_matches_host():
    X, y = _onehotish(n=2000, blocks=20, seed=3)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5}
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.train(params, ds, num_boost_round=10)
    g = bst._gbdt
    assert g.train_ds.bundle is not None
    Xt, _ = _onehotish(n=700, blocks=20, seed=9)
    start, stop = g._iter_window(None, 0)
    host = np.zeros((Xt.shape[0], 1))
    for it in range(start, stop):
        host[:, 0] += g.models[it].predict(Xt)
    dev = g._predict_raw_device(Xt, start, stop)
    np.testing.assert_allclose(dev, host, rtol=0, atol=1e-4)


def test_bundle_dataset_io_roundtrip(tmp_path):
    from lightgbm_tpu.io.dataset_io import load_dataset, save_dataset
    X, y = _onehotish(n=500, blocks=8)
    cfg = Config.from_params({"verbose": -1})
    ds = BinnedDataset.from_matrix(X, cfg)
    ds.metadata.set_label(y)
    assert ds.bundle is not None
    path = str(tmp_path / "ds.npz")
    save_dataset(ds, path)
    ds2 = load_dataset(path)
    assert ds2.bundle is not None
    np.testing.assert_array_equal(ds2.bundle.feat2phys, ds.bundle.feat2phys)
    np.testing.assert_array_equal(ds2.X_bin, ds.X_bin)
    assert ds2.num_features == ds.num_features


def test_enable_bundle_false_is_identity():
    X, y = _onehotish(n=500, blocks=8)
    cfg = Config.from_params({"verbose": -1, "enable_bundle": False})
    ds = BinnedDataset.from_matrix(X, cfg)
    assert ds.bundle is None
    assert ds.num_phys_features == ds.num_features


def test_wave_grower_bundled_matches_serial():
    """The Pallas wave path's bundle expansion == the XLA serial grower
    (interpret mode; the analog of GPU_DEBUG_COMPARE,
    gpu_tree_learner.cpp:1011-1043)."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.core.grower import make_grower
    from lightgbm_tpu.core.meta import (SplitConfig, build_device_meta,
                                        padded_phys_width)
    from lightgbm_tpu.core.plan import GrowthPlan
    from lightgbm_tpu.core.wave_grower import build_wave_grow_fn

    X, y = _onehotish(n=1200, blocks=12, seed=5)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5}
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    h = ds._handle
    assert h.bundle is not None
    cfg = Config.from_params(params)
    meta, B = build_device_meta(h, cfg)
    B_phys = padded_phys_width(h)
    scfg = SplitConfig.from_config(cfg)
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=h.num_data).astype(np.float32))
    hs = jnp.asarray((rng.random(h.num_data) * 0.25 + 0.1).astype(np.float32))
    mask = jnp.ones(h.num_data, jnp.float32)
    fmask = jnp.ones(h.num_features, bool)

    grow_s = make_grower(meta, scfg, B, B_phys=B_phys, bundled=True)
    tr_s, lid_s = grow_s(jnp.asarray(h.X_bin), g, hs, mask, fmask)

    binsT = jnp.asarray(np.ascontiguousarray(h.X_bin.T))
    # under EFB the sibling is subtracted after the default-bin fix
    grow_w = jax.jit(build_wave_grow_fn(
        meta, scfg, B, GrowthPlan(wave_capacity=1, hist_mode="highest",
                                  interpret=True, bundled=True,
                                  fused_sibling=False), B_phys=B_phys))
    tr_w, lid_w = grow_w(binsT, g, hs, mask, fmask)

    assert int(tr_w.num_leaves) == int(tr_s.num_leaves)
    np.testing.assert_array_equal(np.asarray(tr_w.split_feature),
                                  np.asarray(tr_s.split_feature))
    np.testing.assert_array_equal(np.asarray(tr_w.threshold_bin),
                                  np.asarray(tr_s.threshold_bin))
    np.testing.assert_allclose(np.asarray(tr_w.leaf_value),
                               np.asarray(tr_s.leaf_value), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(lid_w), np.asarray(lid_s))


def test_bundled_dataset_with_parallel_learner():
    """A dataset bundled at construction (serial-default params) must train
    correctly when the BOOSTER params later select a parallel learner —
    the mesh growers expand physical histograms like the serial path."""
    X, y = _onehotish(n=2048, blocks=20, seed=5)
    ds_params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
                 "min_data_in_leaf": 5}
    preds = {}
    for tl in ("serial", "data"):
        ds = lgb.Dataset(X, label=y, params=ds_params)
        ds.construct()
        assert ds._handle.bundle is not None  # bundling actually happened
        p = dict(ds_params, tree_learner=tl)
        bst = lgb.train(p, ds, num_boost_round=5)
        preds[tl] = bst.predict(X)
    np.testing.assert_allclose(preds["data"], preds["serial"], atol=1e-5)


def test_bundled_dataset_feature_parallel_rejected():
    X, y = _onehotish(n=1024, blocks=20, seed=6)
    ds = lgb.Dataset(X, label=y, params={"verbose": -1})
    ds.construct()
    assert ds._handle.bundle is not None
    p = {"objective": "binary", "num_leaves": 7, "verbose": -1,
         "tree_learner": "feature", "min_data_in_leaf": 5}
    with pytest.raises(Exception, match="bundle"):
        lgb.train(p, ds, num_boost_round=2)


def test_bundled_dataset_voting_parallel_full_vote_matches_data():
    """EFB + voting (refused pre-r5; reference packs group histograms for
    any bundling, voting_parallel_tree_learner.cpp:203-259): with top_k
    >= F_phys every physical column survives the gate, so the result
    equals data-parallel exactly."""
    X, y = _onehotish(n=2048, blocks=20, seed=7)
    ds_params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
                 "min_data_in_leaf": 5}
    preds = {}
    for tl in ("data", "voting"):
        ds = lgb.Dataset(X, label=y, params=ds_params)
        ds.construct()
        assert ds._handle.bundle is not None
        p = dict(ds_params, tree_learner=tl, top_k=64)
        bst = lgb.train(p, ds, num_boost_round=5)
        preds[tl] = bst.predict(X)
    np.testing.assert_allclose(preds["voting"], preds["data"], atol=1e-6)


def test_bundled_voting_tight_gate_no_phantom_splits():
    """A tight top_k gates physical columns OFF some passes; their members
    must scan all-zero histograms (skipped default-bin fix), never
    fabricated leaf mass.  Loss must stay sane and every chosen split
    must carry real gain."""
    X, y = _onehotish(n=2048, blocks=20, seed=8)
    ds_params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
                 "min_data_in_leaf": 5}
    ds = lgb.Dataset(X, label=y, params=ds_params)
    ds.construct()  # serial-default params -> bundling happens
    assert ds._handle.bundle is not None
    p = dict(ds_params, tree_learner="voting", top_k=2)
    bst = lgb.train(p, ds, num_boost_round=8)
    pred = bst.predict(X)
    eps = 1e-15
    ll = -np.mean(y * np.log(np.clip(pred, eps, 1))
                  + (1 - y) * np.log(np.clip(1 - pred, eps, 1)))
    assert ll < 0.60, ll  # learns despite the gate; base rate ~0.69
    dump = bst.dump_model()
    def gains(node, out):
        if "split_gain" in node:
            out.append(node["split_gain"])
            gains(node["left_child"], out)
            gains(node["right_child"], out)
    allg = []
    for t in dump["tree_info"]:
        gains(t["tree_structure"], allg)
    assert allg and all(g > 0 for g in allg)


def test_reference_cli_efb_auc_parity():
    """Reference-CLI oracle on bundled sparse data: the reference binary
    (enable_bundle=true, 15 trees, num_leaves=15, lr=0.1,
    min_data_in_leaf=20) reaches valid AUC 0.91748 on
    tests/fixtures/sparse.{train,test}; our EFB path must land within
    0.01 while actually bundling."""
    import os
    fix = os.path.join(os.path.dirname(__file__), "fixtures")
    tr = np.loadtxt(os.path.join(fix, "sparse.train"))
    te = np.loadtxt(os.path.join(fix, "sparse.test"))
    p = {"objective": "binary", "metric": "auc", "num_leaves": 15,
         "learning_rate": 0.1, "min_data_in_leaf": 20,
         "enable_bundle": True, "verbose": -1}
    ds = lgb.Dataset(tr[:, 1:], label=tr[:, 0], params=p)
    dv = lgb.Dataset(te[:, 1:], label=te[:, 0], reference=ds)
    res = {}
    bst = lgb.train(p, ds, 15, valid_sets=[dv], valid_names=["valid"],
                    callbacks=[lgb.record_evaluation(res)])
    assert ds._handle.bundle is not None  # EFB actually engaged
    assert ds._handle.X_bin.shape[1] < 33
    got = res["valid"]["auc"][-1]
    assert abs(got - 0.91748) < 0.01, got
