"""A one-hot table through EFB on the wave path (the ``expo`` configuration of
the benchmark, at toy size on the CPU): CSR ingest, bundles, the bundled
wave grower with the kernel interpreted against the unbundled serial grower,
and what the trainer says of it in public.
"""
import re

import numpy as np
import pytest

scipy_sparse = pytest.importorskip("scipy.sparse")

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.utils import log

CARDS = (12, 7, 9, 16, 16)          # 60 one-hot columns
N, F = 4096, sum(CARDS) + 2
# no leaf cap in the way: the wave path is not strict best-first and grows
# another tree than the serial grower where the cap binds (4096 / 150 < 31)
PARAMS = {"objective": "binary", "num_leaves": 31, "min_data_in_leaf": 150,
          "learning_rate": 0.1, "verbose": -1}


def _onehot_csr(seed=0):
    """``N x (60 one-hot + 2 numeric)``, seven stored values a row; fewer
    rows than the bin-finding sample takes, so the bundles are built on the
    whole table and no two members of one ever meet in a row."""
    rng = np.random.default_rng(seed)
    off = np.concatenate([[0], np.cumsum(CARDS)])
    codes = [rng.integers(0, c, N) for c in CARDS]
    effects = [rng.normal(size=c) * 0.6 for c in CARDS]
    s = rng.normal(size=N)
    indices = np.stack([o + c for o, c in zip(off, codes)]
                       + [np.full(N, F - 2), np.full(N, F - 1)], axis=1)
    data = np.ones((N, len(CARDS) + 2), np.float32)
    data[:, -2:] = np.exp(0.5 * (0.8 * rng.normal(size=(N, 2))
                                 + 0.6 * s[:, None]))
    latent = sum(e[c] for e, c in zip(effects, codes)) + 0.7 * s
    y = (latent + rng.normal(size=N) > 0).astype(np.float64)
    K = indices.shape[1]
    X = scipy_sparse.csr_matrix(
        (data.ravel(), indices.ravel().astype(np.int32),
         np.arange(0, N * K + 1, K, dtype=np.int32)), shape=(N, F))
    return X, y


def _splits(bst):
    """Every tree as the set of its splits ``(feature, threshold, rows)``:
    the wave path numbers its nodes in another order than the serial one."""
    out = []
    for chunk in bst.model_to_string().split("\nTree=")[1:]:
        kv = dict(line.split("=", 1) for line in
                  chunk.split("end of trees")[0].splitlines() if "=" in line)
        out.append(set(zip(kv["split_feature"].split(),
                           kv["threshold"].split(),
                           kv["internal_count"].split())))
    return out


@pytest.fixture
def wave(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_FORCE_WAVE", "interpret")


def _train(X, y, iters, **extra):
    p = {**PARAMS, **extra}
    bst = lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
    for _ in range(iters):
        bst.update()
    return bst


def test_bundled_wave_path_grows_the_unbundled_serial_trees(wave,
                                                            monkeypatch):
    X, y = _onehot_csr()
    fast = _train(X, y, 3, device_type="tpu")
    work = fast.work_counters(last=0)
    assert work["stamps"]["uses_wave"] and work["bundled"]
    assert work["stamps"]["fused_sibling"] is False     # bundled: XLA sibling
    assert work["features"] == F and work["phys_columns"] < 12
    groups = fast.train_set.bundle_groups()
    assert len(groups) == work["phys_columns"]
    assert sorted(c for g in groups for c in g) == list(range(F))
    # the oracle: no bundle encode, no histogram expansion, no default-bin
    # reconstruction, no physical-column decode in the routing
    monkeypatch.delenv("LGBM_TPU_FORCE_WAVE")
    slow = _train(X, y, 3, device_type="cpu", enable_bundle=False)
    swork = slow.work_counters(last=0)
    assert not swork["stamps"]["uses_wave"] and not swork["bundled"]
    assert swork["features"] == swork["phys_columns"] == F
    assert _splits(fast) == _splits(slow)
    a, b = fast._raw_train_score(), slow._raw_train_score()
    assert np.max(np.abs(a - b)) <= 1e-5 * np.std(b)


def test_trainer_says_bundled_and_the_program_carries_both_scopes(wave):
    X, y = _onehot_csr(1)
    p = {**PARAMS, "device_type": "tpu"}
    bst = lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
    g = bst._gbdt
    work = bst.work_counters()
    assert {"bundled", "features", "phys_columns"} <= set(work)
    assert work["bundled"] is True and work["counted"] is False
    assert work["features"] == g.meta.num_bins.shape[0] == F
    assert work["phys_columns"] == g._grow_bins.shape[0]
    text = jax.jit(g._grow_raw).lower(
        g._grow_bins, jnp.zeros(N), jnp.ones(N), jnp.ones(N),
        jnp.ones(F, bool)).as_text(debug_info=True)
    for scope in ("lgbm/efb_expand", "lgbm/wave_hist_state",
                  "lgbm/wave_partition", "lgbm/wave_compact"):
        assert scope in text, scope
    # the gather out of the physical histograms and the default-bin fix are
    # both under the bundle's scope, in the loop's body
    assert re.search(r"lgbm/efb_expand[^\n]*gather", text)
    assert re.search(r"lgbm/efb_expand[^\n]*scatter", text)


def test_unbundled_program_has_the_state_scope_and_not_the_bundles(wave):
    rng = np.random.default_rng(2)
    X = rng.normal(size=(1024, 5))
    y = (X[:, 0] > 0).astype(np.float64)
    p = {**PARAMS, "device_type": "tpu", "min_data_in_leaf": 20}
    bst = lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
    g = bst._gbdt
    work = bst.work_counters()
    assert work["bundled"] is False
    assert work["features"] == work["phys_columns"] == 5
    assert bst.train_set.bundle_groups() == [[0], [1], [2], [3], [4]]
    text = jax.jit(g._grow_raw).lower(
        g._grow_bins, jnp.zeros(1024), jnp.ones(1024), jnp.ones(1024),
        jnp.ones(5, bool)).as_text(debug_info=True)
    assert "lgbm/wave_hist_state" in text
    assert "lgbm/efb_expand" not in text


@pytest.mark.parametrize("extra,why", [
    ({"tree_learner": "data"}, "tree_learner=data"),
    ({"max_bin": 511}, "max_bin=511 is over 255")])
def test_efb_says_why_it_is_off(extra, why, capsys):
    X, y = _onehot_csr(3)
    level = log.get_verbosity()
    try:
        ds = lgb.Dataset(X, label=y, params={"verbose": 1, **extra})
        ds.construct()
    finally:
        log.set_verbosity(level)
    said = [ln for ln in capsys.readouterr().err.splitlines()
            if "EFB is off" in ln]
    assert len(said) == 1 and why in said[0]
    assert ds._handle.bundle is None
    assert len(ds.bundle_groups()) == F


def test_efb_is_silent_where_it_was_not_asked_for(capsys):
    X, y = _onehot_csr(3)
    level = log.get_verbosity()
    try:
        lgb.Dataset(X, label=y, params={
            "verbose": 1, "enable_bundle": False,
            "tree_learner": "data"}).construct()
    finally:
        log.set_verbosity(level)
    assert "EFB" not in capsys.readouterr().err
