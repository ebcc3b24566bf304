"""Distributed-mode tests on the virtual 8-device CPU mesh
(conftest sets XLA_FLAGS=--xla_force_host_platform_device_count=8).

Closes the SURVEY §4 gap: the reference never had a multi-node CI fixture;
here data-parallel growth is asserted bit-identical to single-device.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import lightgbm_tpu as lgb
from lightgbm_tpu.core.grower import make_grower
from lightgbm_tpu.core.meta import SplitConfig, build_device_meta, _padded_bin_width
from lightgbm_tpu.parallel import (make_data_parallel_grower,
                                   make_feature_parallel_grower,
                                   make_voting_parallel_grower, shard_rows)


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(0)
    N, F = 512, 6
    X = rng.normal(size=(N, F))
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float64)
    cfg = lgb.Config.from_params({"objective": "binary", "num_leaves": 15,
                                  "min_data_in_leaf": 5, "verbose": -1})
    ds = lgb.Dataset(X, label=y, params={"min_data_in_leaf": 5})
    ds.construct()
    h = ds._handle
    meta, B = build_device_meta(h, cfg)
    scfg = SplitConfig.from_config(cfg)
    bins = jnp.asarray(h.X_bin)
    score = jnp.zeros(N, jnp.float32)
    p = 1.0 / (1.0 + jnp.exp(-score))
    g = (p - jnp.asarray(y, jnp.float32)).astype(jnp.float32)
    hess = (p * (1 - p)).astype(jnp.float32)
    mask = jnp.ones(N, jnp.float32)
    fmask = jnp.ones(h.num_features, bool)
    return meta, scfg, B, bins, g, hess, mask, fmask


def _mesh():
    devs = np.array(jax.devices())
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return Mesh(devs[:8], ("data",))


def test_data_parallel_matches_single_device(setup):
    meta, scfg, B, bins, g, h, mask, fmask = setup
    tree1, leaf1 = make_grower(meta, scfg, B)(bins, g, h, mask, fmask)

    mesh = _mesh()
    grow_dp = make_data_parallel_grower(meta, scfg, B, mesh)
    bins_s, g_s, h_s, mask_s = shard_rows(mesh, bins, g, h, mask)
    tree8, leaf8 = grow_dp(bins_s, g_s, h_s, mask_s, fmask)

    assert int(tree8.num_leaves) == int(tree1.num_leaves)
    np.testing.assert_array_equal(np.asarray(tree8.split_feature),
                                  np.asarray(tree1.split_feature))
    np.testing.assert_array_equal(np.asarray(tree8.threshold_bin),
                                  np.asarray(tree1.threshold_bin))
    np.testing.assert_array_equal(np.asarray(leaf8), np.asarray(leaf1))
    # leaf values agree to f32 reduction-order tolerance
    np.testing.assert_allclose(np.asarray(tree8.leaf_value),
                               np.asarray(tree1.leaf_value), atol=1e-5)


def test_feature_parallel_matches_single_device(setup):
    meta, scfg, B, bins, g, h, mask, fmask = setup
    tree1, _ = make_grower(meta, scfg, B)(bins, g, h, mask, fmask)

    mesh = _mesh()
    grow_fp = make_feature_parallel_grower(meta, scfg, B, mesh)
    tree8, _ = grow_fp(bins, g, h, mask, fmask)
    assert int(tree8.num_leaves) == int(tree1.num_leaves)
    np.testing.assert_array_equal(np.asarray(tree8.split_feature),
                                  np.asarray(tree1.split_feature))
    np.testing.assert_array_equal(np.asarray(tree8.threshold_bin),
                                  np.asarray(tree1.threshold_bin))


def test_voting_parallel_trains(setup):
    meta, scfg, B, bins, g, h, mask, fmask = setup
    mesh = _mesh()
    grow_v = make_voting_parallel_grower(meta, scfg, B, mesh, top_k=3)
    bins_s, g_s, h_s, mask_s = shard_rows(mesh, bins, g, h, mask)
    tree, leaf = grow_v(bins_s, g_s, h_s, mask_s, fmask)
    # voting is approximate: require a usable tree, not bit-parity
    assert int(tree.num_leaves) > 4
    assert np.asarray(leaf).max() < int(tree.num_leaves)


def test_tree_learner_data_trains_end_to_end():
    """params={"tree_learner": "data"} must reach the mesh growers through
    the public API (reference factory: tree_learner.cpp:13-36) and match
    serial training's predictions on the same data."""
    rng = np.random.default_rng(3)
    N = 700  # deliberately NOT a multiple of the 8-device mesh
    X = rng.normal(size=(N, 5))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    preds = {}
    for tl in ("serial", "data", "voting"):
        params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
                  "tree_learner": tl, "min_data_in_leaf": 5}
        ds = lgb.Dataset(X, label=y, params=params)
        bst = lgb.train(params, ds, num_boost_round=5)
        preds[tl] = bst.predict(X)
    np.testing.assert_allclose(preds["data"], preds["serial"], atol=1e-5)
    # voting is approximate by design — just require a sane model
    from sklearn.metrics import roc_auc_score
    assert roc_auc_score(y, preds["voting"]) > 0.8


def test_tree_learner_feature_trains_end_to_end():
    rng = np.random.default_rng(4)
    X = rng.normal(size=(512, 6))
    y = (X[:, 0] - X[:, 2] > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "tree_learner": "feature", "min_data_in_leaf": 5}
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.train(params, ds, num_boost_round=5)
    p1 = bst.predict(X)
    params2 = dict(params, tree_learner="serial")
    ds2 = lgb.Dataset(X, label=y, params=params2)
    bst2 = lgb.train(params2, ds2, num_boost_round=5)
    np.testing.assert_allclose(p1, bst2.predict(X), atol=1e-5)


def test_wave_data_parallel_matches_single_device(setup):
    """Pallas wave kernel + psum compose: row-sharded wave growth (interpret
    mode on the CPU mesh) equals single-device wave growth."""
    from lightgbm_tpu.core.plan import GrowthPlan
    from lightgbm_tpu.core.wave_grower import build_wave_grow_fn
    from lightgbm_tpu.parallel.mesh import make_data_parallel_wave_grower
    meta, scfg, B, bins, g, h, mask, fmask = setup
    mesh = _mesh()
    bins_fm = jnp.asarray(np.ascontiguousarray(np.asarray(bins).T))

    plan = GrowthPlan(wave_capacity=8, hist_mode="highest", interpret=True,
                      gain_gate=0.5)
    single = jax.jit(build_wave_grow_fn(meta, scfg, B, plan))
    t1, lid1 = single(bins_fm, g, h, mask, fmask)

    # under the mesh the sibling is subtracted after the psum
    dp = make_data_parallel_wave_grower(
        meta, scfg, B, mesh, dataclasses.replace(plan, fused_sibling=False))
    t2, lid2 = dp(bins_fm, g, h, mask, fmask)
    nn = int(t1.num_leaves) - 1
    assert int(t2.num_leaves) == nn + 1
    np.testing.assert_array_equal(np.asarray(t1.split_feature[:nn]),
                                  np.asarray(t2.split_feature[:nn]))
    np.testing.assert_array_equal(np.asarray(t1.threshold_bin[:nn]),
                                  np.asarray(t2.threshold_bin[:nn]))
    np.testing.assert_allclose(np.asarray(t1.leaf_value),
                               np.asarray(t2.leaf_value), rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(lid1), np.asarray(lid2))


def test_goss_and_bagging_under_data_parallel():
    """GOSS amplification and bagging masks compose with the row-sharded
    grower exactly as with the serial one (VERDICT r3: untested)."""
    rng = np.random.default_rng(9)
    N = 1200  # not a multiple of the 8-device mesh
    X = rng.normal(size=(N, 5))
    y = (X[:, 0] - 0.5 * X[:, 2] > 0).astype(np.float64)
    outs = {}
    for tl in ("serial", "data"):
        for boosting, extra in (("goss", {}),
                                ("gbdt", {"bagging_freq": 1,
                                          "bagging_fraction": 0.7})):
            p = {"objective": "binary", "num_leaves": 15, "verbose": -1,
                 "tree_learner": tl, "min_data_in_leaf": 5,
                 "boosting": boosting, **extra}
            ds = lgb.Dataset(X, label=y, params=p)
            bst = lgb.train(p, ds, num_boost_round=4)
            outs[(tl, boosting)] = bst.predict(X)
    np.testing.assert_allclose(outs[("data", "goss")],
                               outs[("serial", "goss")], atol=1e-5)
    np.testing.assert_allclose(outs[("data", "gbdt")],
                               outs[("serial", "gbdt")], atol=1e-5)
