"""Categorical split search correctness.

The device search (core/splitter.py::_categorical_best) is checked
gain-for-gain against a scalar numpy oracle transcribing the reference's
FindBestThresholdCategorical (reference:
src/treelearner/feature_histogram.hpp:118-279), and the full chain —
train with declared categorical features, category-set partitions, model
text round-trip, device vs host prediction — is exercised end-to-end.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.core.grower import make_grower
from lightgbm_tpu.core.meta import DeviceMeta, SplitConfig, build_device_meta
from lightgbm_tpu.core import splitter
from lightgbm_tpu.core.plan import GrowthPlan
from lightgbm_tpu.core.wave_grower import build_wave_grow_fn

K_EPSILON = 1e-15
FIX = os.path.join(os.path.dirname(__file__), "fixtures")


# ---------------------------------------------------------------------------
# scalar oracle (reference: feature_histogram.hpp:118-279)
# ---------------------------------------------------------------------------

def _leaf_gain(g, h, l1, l2):
    s = np.sign(g) * max(abs(g) - l1, 0.0)
    return s * s / (h + l2)


def _split_gain(gl, hl, gr, hr, l1, l2):
    return _leaf_gain(gl, hl, l1, l2) + _leaf_gain(gr, hr, l1, l2)


def oracle_categorical(g, h, c, sum_g, sum_h, cnt, num_bin, missing_none,
                       cfg: SplitConfig):
    """Best categorical split of one feature; returns
    (gain_above_min_shift, left_bin_set) or (-inf, None)."""
    gain_shift = _leaf_gain(sum_g, sum_h, cfg.lambda_l1, cfg.lambda_l2)
    min_gain_shift = gain_shift + cfg.min_gain_to_split
    used_bin = num_bin - 1 + int(missing_none)
    l2 = cfg.lambda_l2
    best_gain, best_set = -np.inf, None

    if num_bin <= cfg.max_cat_to_onehot:
        for t in range(used_bin):
            if c[t] < cfg.min_data_in_leaf or h[t] < cfg.min_sum_hessian_in_leaf:
                continue
            if cnt - c[t] < cfg.min_data_in_leaf:
                continue
            oh = sum_h - h[t] - K_EPSILON
            if oh < cfg.min_sum_hessian_in_leaf:
                continue
            gain = _split_gain(sum_g - g[t], oh, g[t], h[t] + K_EPSILON,
                               cfg.lambda_l1, l2)
            if gain <= min_gain_shift:
                continue
            if gain > best_gain:
                best_gain, best_set = gain, {t}
    else:
        sorted_idx = [i for i in range(used_bin) if c[i] >= cfg.cat_smooth]
        l2 += cfg.cat_l2
        sorted_idx.sort(key=lambda i: g[i] / (h[i] + cfg.cat_smooth))
        ub = len(sorted_idx)
        max_num_cat = min(cfg.max_cat_threshold, (ub + 1) // 2)
        for dir_, start in ((1, 0), (-1, ub - 1)):
            grp = 0
            lg, lh, lc = 0.0, K_EPSILON, 0.0
            pos = start
            for i in range(min(ub, max_num_cat)):
                t = sorted_idx[pos]
                pos += dir_
                lg += g[t]; lh += h[t]; lc += c[t]; grp += c[t]
                if (lc < cfg.min_data_in_leaf
                        or lh < cfg.min_sum_hessian_in_leaf):
                    continue
                rc = cnt - lc
                if rc < cfg.min_data_in_leaf or rc < cfg.min_data_per_group:
                    break
                rh = sum_h - lh
                if rh < cfg.min_sum_hessian_in_leaf:
                    break
                if grp < cfg.min_data_per_group:
                    continue
                grp = 0
                gain = _split_gain(lg, lh, sum_g - lg, rh, cfg.lambda_l1, l2)
                if gain <= min_gain_shift:
                    continue
                if gain > best_gain:
                    best_gain = gain
                    if dir_ == 1:
                        best_set = set(sorted_idx[: i + 1])
                    else:
                        best_set = set(sorted_idx[ub - 1 - i:])
    if best_set is None:
        return -np.inf, None
    return best_gain - min_gain_shift, best_set


def _unpack(words, B):
    return {b for b in range(B) if (int(words[b // 32]) >> (b % 32)) & 1}


def _cat_meta(num_bins):
    F = len(num_bins)
    return DeviceMeta(
        num_bins=jnp.asarray(num_bins, jnp.int32),
        default_bins=jnp.zeros(F, jnp.int32),
        missing_types=jnp.zeros(F, jnp.int32),   # MISSING_NONE
        monotone=jnp.zeros(F, jnp.int32),
        penalties=jnp.ones(F, jnp.float32),
        is_categorical=jnp.ones(F, bool),
    )


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("onehot", [False, True])
def test_categorical_search_matches_reference_oracle(seed, onehot):
    rng = np.random.default_rng(seed)
    B = 24
    cfg = SplitConfig(num_leaves=31, min_data_in_leaf=3,
                      min_sum_hessian_in_leaf=1e-3, min_data_per_group=5,
                      cat_smooth=2.0, cat_l2=1.0,
                      max_cat_to_onehot=(64 if onehot else 4))
    for trial in range(6):
        nb = int(rng.integers(6, B + 1))
        c = np.zeros(B); g = np.zeros(B); h = np.zeros(B)
        c[:nb] = rng.integers(0, 30, size=nb).astype(float)
        g[:nb] = rng.normal(size=nb) * c[:nb] * 0.1
        h[:nb] = c[:nb] * (0.2 + 0.1 * rng.random(nb))
        sg, sh, sc = g.sum(), h.sum() + 2 * K_EPSILON, c.sum()
        if sc < 2 * cfg.min_data_in_leaf:
            continue
        hist = jnp.asarray(np.stack([g, h, c], axis=-1)[None], jnp.float32)
        bs = splitter.best_split(hist, jnp.float32(sg), jnp.float32(sh - 2 * K_EPSILON),
                                 jnp.float32(sc), _cat_meta([nb]), cfg,
                                 jnp.float32(-np.inf), jnp.float32(np.inf))
        want_gain, want_set = oracle_categorical(
            g, h, c, sg, sh, sc, nb, True, cfg)
        if want_set is None:
            assert float(bs.gain) == -np.inf, (
                f"trial {trial}: oracle found no split, device gain={float(bs.gain)}")
            continue
        np.testing.assert_allclose(float(bs.gain), want_gain, rtol=2e-4,
                                   err_msg=f"trial {trial} gain mismatch")
        got_set = _unpack(np.asarray(bs.cat_bitset), B)
        assert got_set == want_set, f"trial {trial}: {got_set} != {want_set}"


# ---------------------------------------------------------------------------
# end-to-end
# ---------------------------------------------------------------------------

def _cat_problem(n=2000, seed=7):
    rng = np.random.default_rng(seed)
    cat = rng.integers(0, 12, size=n).astype(np.float64)
    x1 = rng.normal(size=n)
    logit = 2.5 * ((cat % 3 == 0).astype(np.float64) - 0.5) + 0.4 * x1
    y = (logit + rng.normal(scale=0.5, size=n) > 0).astype(np.float64)
    X = np.column_stack([cat, x1, rng.normal(size=n)])
    X[rng.random(n) < 0.02, 0] = np.nan
    return X, y


def test_categorical_train_roundtrip_and_predict():
    X, y = _cat_problem()
    params = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.2,
              "min_data_per_group": 20, "verbose": -1}
    ds = lgb.Dataset(X, label=y, categorical_feature=[0], params=params)
    bst = lgb.train(params, ds, num_boost_round=15)
    txt = bst.model_to_string()
    n_cat = sum(int(l.split("=")[1]) for l in txt.splitlines()
                if l.startswith("num_cat="))
    assert n_cat > 0, "no categorical splits were made"

    pred = bst.predict(X)
    from sklearn.metrics import roc_auc_score
    assert roc_auc_score(y, pred) > 0.85

    bst2 = lgb.Booster(model_str=txt)
    np.testing.assert_allclose(bst2.predict(X), pred, atol=1e-12)


def test_categorical_device_replay_matches_host_predict():
    """The bin-space device traversal (used for valid-set replay) and the
    value-space host prediction agree on training data."""
    X, y = _cat_problem(seed=3)
    params = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.2,
              "min_data_per_group": 20, "metric": "binary_logloss",
              "verbose": -1}
    ds = lgb.Dataset(X, label=y, categorical_feature=[0], params=params)
    vs = lgb.Dataset(X, label=y, categorical_feature=[0], params=params,
                     reference=ds)
    ev = {}
    bst = lgb.train(params, ds, num_boost_round=10, valid_sets=[vs],
                    valid_names=["v"],
                    callbacks=[lgb.record_evaluation(ev)])
    pred = bst.predict(X)
    eps = 1e-15
    ll = -np.mean(y * np.log(np.clip(pred, eps, 1))
                  + (1 - y) * np.log(np.clip(1 - pred, eps, 1)))
    np.testing.assert_allclose(ev["v"]["binary_logloss"][-1], ll, rtol=1e-5)


def test_load_reference_categorical_model_predict_parity():
    """tests/fixtures/ref_cat_model.txt was trained by the reference CLI
    (built from /root/reference) with categorical_feature=0 on a synthetic
    dataset; ref_cat_pred.npy holds its own predictions. Loading that
    model here must reproduce them — cross-framework categorical-decision
    parity (reference: tree.h:265-303 CategoricalDecision). The prediction
    rows include NaN, unseen (25, 40), and negative categories, which the
    reference routes right."""
    bst = lgb.Booster(model_file=os.path.join(FIX, "ref_cat_model.txt"))
    rows = np.load(os.path.join(FIX, "cat_rows.npy"))
    expected = np.load(os.path.join(FIX, "ref_cat_pred.npy"))
    np.testing.assert_allclose(bst.predict(rows), expected, atol=1e-12)


def test_wave_categorical_matches_serial():
    """Wave grower (capacity 1, interpret mode) reproduces the serial
    grower node-for-node on a dataset with a categorical feature."""
    X, y = _cat_problem(n=800, seed=5)
    params = {"objective": "binary", "num_leaves": 7,
              "min_data_per_group": 10, "min_data_in_leaf": 5, "verbose": -1}
    ds = lgb.Dataset(X, label=y, categorical_feature=[0], params=params)
    ds.construct()
    handle = ds._handle
    cfg = Config.from_params(params)
    meta, B = build_device_meta(handle, cfg)
    scfg = SplitConfig.from_config(cfg)
    rng = np.random.default_rng(1)
    n = handle.num_data
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray((0.1 + rng.random(size=n)).astype(np.float32))
    mask = jnp.ones((n,), jnp.float32)
    fmask = jnp.ones((handle.num_features,), bool)

    serial = make_grower(meta, scfg, B)
    t1, lid1 = serial(jnp.asarray(handle.X_bin), g, h, mask, fmask)
    wave = jax.jit(build_wave_grow_fn(meta, scfg, B, GrowthPlan(
        wave_capacity=1, hist_mode="highest", interpret=True)))
    t2, lid2 = wave(jnp.asarray(np.ascontiguousarray(handle.X_bin.T)),
                    g, h, mask, fmask)

    nn = int(t1.num_leaves) - 1
    assert int(t2.num_leaves) == nn + 1
    np.testing.assert_array_equal(np.asarray(t1.split_feature[:nn]),
                                  np.asarray(t2.split_feature[:nn]))
    np.testing.assert_array_equal(np.asarray(t1.threshold_bin[:nn]),
                                  np.asarray(t2.threshold_bin[:nn]))
    np.testing.assert_array_equal(np.asarray(t1.default_left[:nn]),
                                  np.asarray(t2.default_left[:nn]))
    np.testing.assert_array_equal(np.asarray(t1.cat_bitset[:nn]),
                                  np.asarray(t2.cat_bitset[:nn]))
    # leaf values too — a wrong l2 (lambda_l2 vs +cat_l2) in the output
    # computation would keep the structure but change the outputs
    np.testing.assert_allclose(np.asarray(t1.leaf_value),
                               np.asarray(t2.leaf_value), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(lid1), np.asarray(lid2))
    # at least one categorical node must exist for this to be a real test
    assert np.any(np.asarray(t1.cat_bitset[:nn]) != 0)


def test_high_cardinality_categorical_uint16_path():
    """A categorical with > 256 distinct values widens X_bin to uint16 and
    disables the uint8 wave kernel; train + split + round-trip must still
    work end to end (reference: bin storage sizing, dataset.cpp)."""
    rng = np.random.default_rng(44)
    n = 4000
    cat = rng.integers(0, 400, n).astype(float)  # 400 categories
    x1 = rng.normal(size=n)
    # direct categorical signal (marginally learnable) + numeric term
    y = (((cat % 7) < 3).astype(float) + 0.5 * (x1 > 0)
         + rng.logistic(size=n) * 0.2 > 0.75).astype(np.float64)
    X = np.column_stack([cat, x1])
    p = {"objective": "binary", "num_leaves": 31, "verbose": -1,
         "min_data_in_leaf": 10, "max_cat_threshold": 64,
         "categorical_feature": [0]}
    ds = lgb.Dataset(X, label=y, params=p)
    ds.construct()
    assert ds._handle.X_bin.dtype == np.uint16
    bst = lgb.train(p, ds, 10)
    from sklearn.metrics import roc_auc_score
    auc = roc_auc_score(y, bst.predict(X))
    assert auc > 0.9, auc
    assert any(t["num_cat"] > 0 for t in bst.dump_model()["tree_info"])
    re = lgb.Booster(model_str=bst.model_to_string())
    np.testing.assert_allclose(re.predict(X), bst.predict(X), rtol=1e-6)
