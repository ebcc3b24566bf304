"""Quantized histogram accumulation + fused gradient pass — the ISSUE 11
differential suite.

The quantized pipeline (``tpu_hist_dtype=int16|int8``) stochastic-rounds
g/h to integers under per-tree symmetric scales, accumulates exactly on
the MXU (int16 = exact hi/lo bf16 split, int8 = one exact bf16 pass),
and dequantizes at split-scan time.  These tests pin the accuracy
contract ANALYTICALLY (per-bin deltas bounded by counts x scale —
``quant_error_bound`` / ``splitter.hist_quant_tolerance``), require
BIT-IDENTICAL trees across the packed/triple x fused/unfused layout
grid under quantization (same exactness contract the f32 grid carries),
end-to-end AUC within 1e-3 of the f32 path at a HIGGS-ish shape, and
2-device mesh parity with globally-reduced scales.  The fused gradient
pass (the plan's ``fused_grad``) must be bit-identical to its reference,
the unfused pass.  The
cost-model tests assert the headline acceptance bar: int16 + fused-grad
cuts the per-iteration gradient-stream HBM bytes >= 1.5x vs the PR 8
2xbf16 + unfused baseline at the HIGGS shape (F=28, B=256).
"""
import dataclasses
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.core.meta import SplitConfig, build_device_meta
from lightgbm_tpu.core.plan import GrowthPlan, resolve_hist_mode
from lightgbm_tpu.core.splitter import hist_quant_tolerance
from lightgbm_tpu.core.wave_grower import build_wave_grow_fn
from lightgbm_tpu.ops.pallas_hist import (C_MAX, QUANT_QMAX,
                                          grad_stream_bytes,
                                          hist_pallas_wave, unpack_lanes,
                                          quant_error_bound,
                                          stochastic_round,
                                          wave_kernel_cost)


def _assert_identical(res1, res2, msg=""):
    (t1, l1), (t2, l2) = res1[:2], res2[:2]
    assert int(t1.num_leaves) == int(t2.num_leaves), msg
    for fld in t1._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(t1, fld)), np.asarray(getattr(t2, fld)),
            err_msg=f"{msg}: tree field {fld} diverged")
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2),
                                  err_msg=msg)


def _setup(X, y, params, seed, cat_features=None):
    ds = lgb.Dataset(X, label=y, params=params,
                     categorical_feature=cat_features or "auto")
    ds.construct()
    handle = ds._handle
    cfg = Config.from_params(params)
    meta, B = build_device_meta(handle, cfg)
    scfg = SplitConfig.from_config(cfg)
    n = handle.num_data
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray((0.1 + rng.random(n)).astype(np.float32))
    mask = jnp.ones((n,), jnp.float32)
    fmask = jnp.ones((handle.num_features,), bool)
    bins_fm = jnp.asarray(np.ascontiguousarray(handle.X_bin.T))
    return handle, meta, scfg, B, bins_fm, g, h, mask, fmask


def _case_problem(case, seed):
    rng = np.random.default_rng(seed)
    n, f = 600, 6
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + X[:, 1] * X[:, 2] + 0.3 * rng.normal(size=n) > 0)
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 5, "verbose": -1}
    cats = None
    if case == "nan_default_left":
        X[rng.random((n, f)) < 0.15] = np.nan
    elif case == "categorical_bitset":
        X[:, 3] = rng.integers(0, 40, size=n)
        y = (((X[:, 3].astype(int) % 5) < 2) | (X[:, 0] > 0.7))
        cats = [3]
        params = dict(params, min_data_per_group=5, cat_smooth=1.0,
                      cat_l2=1.0, max_cat_to_onehot=4)
    return X, y.astype(np.float64), params, cats


# ---------------------------------------------------------------------------
# stochastic rounding
# ---------------------------------------------------------------------------

def test_stochastic_round_properties():
    rng = np.random.default_rng(0)
    x = jnp.asarray((rng.normal(size=4096) * 1000).astype(np.float32))
    r1 = np.asarray(stochastic_round(x, 7))
    r2 = np.asarray(stochastic_round(x, 7))
    # deterministic under a fixed seed
    np.testing.assert_array_equal(r1, r2)
    # a different seed rounds SOME values the other way
    r3 = np.asarray(stochastic_round(x, 8))
    assert (r1 != r3).any()
    # always floor or ceil
    xf = np.asarray(x)
    assert np.all((r1 == np.floor(xf)) | (r1 == np.ceil(xf)))
    # exact integers (and exact zeros — the bag mask) are preserved
    ints = jnp.asarray(np.arange(-500, 500, dtype=np.float32))
    np.testing.assert_array_equal(np.asarray(stochastic_round(ints, 3)),
                                  np.asarray(ints))
    # value-based: the same value rounds identically at any position —
    # the property that makes data-parallel shards quantize identically
    shuf = np.asarray(stochastic_round(x[::-1], 7))
    np.testing.assert_array_equal(shuf, r1[::-1])


# ---------------------------------------------------------------------------
# kernel level: analytic error bound + exactness contracts
# ---------------------------------------------------------------------------

def _kernel_inputs(n=400, f=6, seed=0, leaves=(3, 0, 4)):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] > 0)
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 5, "verbose": -1}
    ds = lgb.Dataset(X, label=y.astype(np.float64), params=params)
    ds.construct()
    handle = ds._handle
    cfg = Config.from_params(params)
    _, B = build_device_meta(handle, cfg)
    bins_fm = jnp.asarray(np.ascontiguousarray(handle.X_bin.T))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray((0.1 + rng.random(n)).astype(np.float32))
    cv = jnp.ones((n,), jnp.float32)
    leaf_id = jnp.asarray(rng.integers(0, 5, size=n, dtype=np.int32))
    slot_t = np.full(C_MAX, -1, np.int32)
    slot_p = np.full(C_MAX, -1, np.int32)
    for s, leaf in enumerate(leaves):
        slot_t[3 * s:3 * s + 3] = leaf
        slot_p[2 * s:2 * s + 2] = leaf
    return (bins_fm, g, h, cv, leaf_id, jnp.asarray(slot_t),
            jnp.asarray(slot_p), B, list(leaves))


def _quantize(g, h, mode, seed=7):
    qmax = QUANT_QMAX[mode]
    s_g = float(jnp.maximum(jnp.max(jnp.abs(g)), 1e-30) / qmax)
    s_h = float(jnp.maximum(jnp.max(jnp.abs(h)), 1e-30) / qmax)
    gq = stochastic_round(g / s_g, seed)
    hq = stochastic_round(h / s_h, seed ^ 0x9E3779B9)
    return gq, hq, s_g, s_h


@pytest.mark.parametrize("mode", ["int16", "int8"])
def test_quant_kernel_within_analytic_bound(mode):
    """Dequantized int16/int8 histograms deviate from the f32 oracle by
    at most counts x scale per bin (each row within one quantization
    step, integer accumulation exact) — the analytic contract
    ``quant_error_bound`` / ``splitter.hist_quant_tolerance`` states.
    Counts are bit-exact in every mode (0/1 weights)."""
    (bins_fm, g, h, cv, leaf_id, slot_t, slot_p, B,
     leaves) = _kernel_inputs()
    ref_gh, ref_ct = hist_pallas_wave(bins_fm, g, h, cv, leaf_id, slot_p,
                                      B=B, highest=True, interpret=True,
                                      packed=True)
    gq, hq, s_g, s_h = _quantize(g, h, mode)
    q_gh, q_ct = hist_pallas_wave(bins_fm, gq, hq, cv, leaf_id, slot_p,
                                  B=B, highest=mode, interpret=True,
                                  packed=True)
    # per-leaf [P, F, B, 3], each read where its mode's lanes keep it
    ref = np.asarray(unpack_lanes((ref_gh, ref_ct), "highest", len(leaves)))
    q = np.asarray(unpack_lanes((q_gh, q_ct), mode, len(leaves)))
    np.testing.assert_array_equal(q[..., 2], ref[..., 2])
    # integer sums really are integers
    np.testing.assert_array_equal(q, np.round(q))
    ct = ref[..., 2].transpose(1, 2, 0)
    tol_g, tol_h = hist_quant_tolerance(ct, s_g, s_h)
    for s in range(len(leaves)):
        cnt = ct[:, :, s]
        dg = np.abs(q[s, :, :, 0] * s_g - ref[s, :, :, 0])
        dh = np.abs(q[s, :, :, 1] * s_h - ref[s, :, :, 1])
        assert np.all(dg <= tol_g[:, :, s] + 1e-12)
        assert np.all(dh <= tol_h[:, :, s] + 1e-12)
        # the bound helper itself
        np.testing.assert_allclose(quant_error_bound(cnt, s_g),
                                   cnt * s_g)


def test_quant_kernel_layouts_and_fusion_bit_identical():
    """Under quantization the packed lane-pair layout, the triple
    oracle, and the fused (child, sibling) emission are ALL bit-
    identical: integer units end to end, the sibling subtraction
    included (no dequant happens before the scan)."""
    (bins_fm, g, h, cv, leaf_id, slot_t, slot_p, B,
     leaves) = _kernel_inputs()
    gq, hq, _, _ = _quantize(g, h, "int16")
    hp_gh, hp_ct = hist_pallas_wave(bins_fm, gq, hq, cv, leaf_id, slot_p,
                                    B=B, highest="int16", interpret=True,
                                    packed=True)
    ht = hist_pallas_wave(bins_fm, gq, hq, cv, leaf_id, slot_t, B=B,
                          highest="int16", interpret=True)
    got = np.asarray(unpack_lanes((hp_gh, hp_ct), "int16", len(leaves)))
    want = np.asarray(ht)[:, :, :3 * len(leaves)].reshape(
        *ht.shape[:2], len(leaves), 3).transpose(2, 0, 1, 3)
    np.testing.assert_array_equal(got, want)
    rng = np.random.default_rng(9)
    par = tuple(jnp.asarray(rng.normal(size=np.asarray(x).shape)
                            .astype(np.float32)) for x in (hp_gh, hp_ct))
    child, sib = hist_pallas_wave(bins_fm, gq, hq, cv, leaf_id, slot_p,
                                  B=B, highest="int16", interpret=True,
                                  packed=True, parent=par)
    for c, u in zip(child, (hp_gh, hp_ct)):
        np.testing.assert_array_equal(np.asarray(c), np.asarray(u))
    for s_, p_, c_ in zip(sib, par, child):
        np.testing.assert_array_equal(np.asarray(s_),
                                      np.asarray(p_) - np.asarray(c_))


# ---------------------------------------------------------------------------
# grower level
# ---------------------------------------------------------------------------

def _grow_grid(problem, mode, capacity=6, quant_seed=11,
               grid=((False, False), (True, True))):
    handle, meta, scfg, B, bins_fm, g, h, mask, fmask = problem
    out = []
    for packed, fused in grid:
        grow = jax.jit(build_wave_grow_fn(meta, scfg, B, GrowthPlan(
            wave_capacity=capacity, hist_mode=mode, interpret=True,
            gain_gate=0.5, packed=packed, fused_sibling=fused,
            quant_seed=quant_seed)))
        out.append(grow(bins_fm, g, h, mask, fmask))
    return out


def test_quant_fused_smoke():
    """Quick-tier gate (the run_suite quantized smoke): int16 growth
    through the default packed+fused pipeline bit-matches the
    triple/unfused oracle and grows a real tree.  (Stochastic-rounding
    determinism is value-based and pinned separately above, so one grid
    pass suffices here.)"""
    X, y, params, cats = _case_problem("nan_default_left", 0)
    problem = _setup(X, y, params, 0, cats)
    res = _grow_grid(problem, "int16")
    _assert_identical(res[0], res[1], "int16 packed+fused vs oracle")
    assert int(res[0][0].num_leaves) > 4


@pytest.mark.parametrize("case,seed,mode", [
    ("nan_default_left", 7, "int16"),
    ("categorical_bitset", 7, "int16"),
    ("nan_default_left", 7, "int8"),
    ("categorical_bitset", 23, "int8"),
])
def test_quant_grid_differential(case, seed, mode):
    """Full (packed, fused) grid bit-identical under quantization across
    the layout-sensitive semantics (NaN/default-left routing and
    categorical bitsets) — the same contract the f32 grid carries."""
    X, y, params, cats = _case_problem(case, seed)
    problem = _setup(X, y, params, seed, cats)
    res = _grow_grid(problem, mode,
                     grid=((False, False), (False, True),
                           (True, False), (True, True)))
    for other in res[1:]:
        _assert_identical(res[0], other, f"{mode} grid")
    if case == "categorical_bitset":
        t = res[0][0]
        cb = np.asarray(t.cat_bitset[:int(t.num_leaves) - 1])
        assert (cb != 0).any(), "no categorical split committed"


def test_quant_mesh_parity():
    """2-device data-parallel quantized growth: the pmax-reduced global
    scales + value-based stochastic rounding make every shard quantize
    identically, so the mesh tree matches the single-device tree
    structure-exactly (leaf values to psum rounding, same tolerance as
    the f32 mesh tests)."""
    from jax.sharding import Mesh
    from lightgbm_tpu.parallel.mesh import make_data_parallel_wave_grower

    rng = np.random.default_rng(5)
    n, f = 512, 6
    X = rng.normal(size=(n, f))
    X[rng.random((n, f)) < 0.1] = np.nan
    y = (np.nan_to_num(X[:, 0]) + np.nan_to_num(X[:, 1]) > 0)
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 5, "verbose": -1}
    problem = _setup(X, y.astype(np.float64), params, 5)
    handle, meta, scfg, B, bins_fm, g, h, mask, fmask = problem

    devs = np.array(jax.devices())
    assert len(devs) >= 2
    mesh = Mesh(devs[:2], ("data",))
    plan = GrowthPlan(wave_capacity=6, hist_mode="int16", interpret=True,
                      gain_gate=0.5, quant_seed=11)
    dp = make_data_parallel_wave_grower(
        meta, scfg, B, mesh, dataclasses.replace(plan, fused_sibling=False))
    t2, lid2 = dp(bins_fm, g, h, mask, fmask)
    single = jax.jit(build_wave_grow_fn(meta, scfg, B, plan))
    t1, lid1 = single(bins_fm, g, h, mask, fmask)
    nn = int(t1.num_leaves) - 1
    assert int(t2.num_leaves) == nn + 1
    np.testing.assert_array_equal(np.asarray(t1.split_feature[:nn]),
                                  np.asarray(t2.split_feature[:nn]))
    np.testing.assert_array_equal(np.asarray(t1.threshold_bin[:nn]),
                                  np.asarray(t2.threshold_bin[:nn]))
    np.testing.assert_array_equal(np.asarray(lid1), np.asarray(lid2))
    np.testing.assert_allclose(np.asarray(t1.leaf_value),
                               np.asarray(t2.leaf_value), rtol=1e-4,
                               atol=1e-5)
    assert int(t1.num_leaves) > 4


# ---------------------------------------------------------------------------
# engine level: AUC budget, fused-grad differential, resume
# ---------------------------------------------------------------------------

def _higgs_like(n=1500, f=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    w = rng.normal(size=4)
    y = ((X[:, :4] @ w + 0.5 * X[:, 0] * X[:, 1]
          + rng.logistic(size=n)) > 0).astype(np.float64)
    return X, y


def _auc(y, scores):
    order = np.argsort(scores)
    ranks = np.empty(len(y))
    ranks[order] = np.arange(len(y))
    pos = y > 0
    np_, nn_ = pos.sum(), (~pos).sum()
    return (ranks[pos].sum() - np_ * (np_ - 1) / 2) / (np_ * nn_)


def _train(X, y, params, iters=6):
    p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 10,
         "learning_rate": 0.1, "verbose": -1, "seed": 3, **params}
    ds = lgb.Dataset(X, label=y, params=p)
    bst = lgb.Booster(params=p, train_set=ds)
    for _ in range(iters):
        bst.update()
    return bst


def _trees_text(bst):
    return bst.model_to_string().split("\nparameters:")[0]


def test_quant_training_auc_budget(monkeypatch):
    """End-to-end HIGGS-shape training through the interpret-mode wave
    path: int16 AUC within 1e-3 of the f32 path (the acceptance
    budget), int8 within 1e-2 (coarser steps, documented looser)."""
    monkeypatch.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    X, y = _higgs_like()
    b_f32 = _train(X, y, {"tpu_hist_dtype": "highest"})
    assert b_f32._gbdt.uses_wave
    a_f = _auc(y, b_f32.predict(X, raw_score=True))
    b_q16 = _train(X, y, {"tpu_hist_dtype": "int16"})
    assert b_q16._gbdt._wave_info["hist_mode"] == "int16"
    a_16 = _auc(y, b_q16.predict(X, raw_score=True))
    assert abs(a_f - a_16) <= 1e-3, (a_f, a_16)
    b_q8 = _train(X, y, {"tpu_hist_dtype": "int8"})
    a_8 = _auc(y, b_q8.predict(X, raw_score=True))
    assert abs(a_f - a_8) <= 1e-2, (a_f, a_8)


def test_fused_grad_bit_identical(replace_plan):
    """The run_suite fused-grad smoke: the plan's fused_grad on vs off
    trains BIT-IDENTICAL models (tree text compared) on the XLA grower
    path."""
    X, y = _higgs_like(n=400)
    small = {"num_leaves": 7}
    fused = _train(X, y, small, iters=5)
    assert fused._gbdt.fused_grad_active()
    replace_plan(fused_grad=False)
    unfused = _train(X, y, small, iters=5)
    assert not unfused._gbdt.fused_grad_active()
    assert _trees_text(fused) == _trees_text(unfused)


def test_fused_grad_bit_identical_bagging(replace_plan):
    """The same differential under per-iteration bagging masks — the
    fused pass must compose with the host-side mask refresh."""
    X, y = _higgs_like(n=700)
    bag = {"bagging_freq": 1, "bagging_fraction": 0.7}
    fused = _trees_text(_train(X, y, bag))
    replace_plan(fused_grad=False)
    assert fused == _trees_text(_train(X, y, bag))


def test_fused_grad_bit_identical_wave_path(monkeypatch, replace_plan):
    """The same differential through the interpret-mode wave pipeline,
    quantized — the fused pass feeds the quantize+pack prologue
    directly and must still be bit-identical to the unfused twin."""
    monkeypatch.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    X, y = _higgs_like(n=700)
    q = {"tpu_hist_dtype": "int16"}
    b1 = _train(X, y, q, iters=4)
    replace_plan(fused_grad=False)
    b2 = _train(X, y, q, iters=4)
    assert b1._gbdt._wave_info["fused_grad"] is True
    assert b2._gbdt._wave_info["fused_grad"] is False
    assert _trees_text(b1) == _trees_text(b2)


def test_fused_grad_ineligible_paths():
    """GOSS and RF consume materialized gradients — the fused pass must
    not engage; custom-gradient updates take the unfused path at
    runtime (and still work)."""
    X, y = _higgs_like(n=500)
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
         "verbose": -1, "boosting": "goss", "top_rate": 0.3,
         "other_rate": 0.2, "learning_rate": 0.3}
    ds = lgb.Dataset(X, label=y, params=p)
    bst = lgb.Booster(params=p, train_set=ds)
    bst.update()
    assert bst._gbdt._grow_apply_fused is None
    # custom gradients: fused booster still accepts them
    p2 = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
          "verbose": -1}
    ds2 = lgb.Dataset(X, label=y, params=p2)
    bst2 = lgb.Booster(params=p2, train_set=ds2)
    g = np.asarray(y, np.float32) - 0.5
    h = np.full_like(g, 0.25)
    bst2.update()
    bst2.update(train_set=None, fobj=lambda preds, ds: (g, h))
    assert bst2.num_trees() >= 2


def test_resume_bit_identical_int16(monkeypatch, tmp_path, replace_plan):
    """Crash-resume under tpu_hist_dtype=int16 through the interpret
    wave path: train-N-straight == train-to-crash + resume-to-N,
    bit-identical — and resuming on the unfused gradient pass is NOT
    refused (a reference path of the plan, no part of config_digest)."""
    monkeypatch.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    X, y = _higgs_like(n=500)
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
         "verbose": -1, "seed": 1, "tpu_hist_dtype": "int16"}
    ds = lgb.Dataset(X, label=y, params=dict(p))
    b1 = lgb.train(dict(p), ds, num_boost_round=8, verbose_eval=False)
    p2 = dict(p, tpu_checkpoint_dir=str(tmp_path), tpu_checkpoint_freq=3)
    ds = lgb.Dataset(X, label=y, params=dict(p))
    lgb.train(dict(p2), ds, num_boost_round=5, verbose_eval=False)
    assert glob.glob(os.path.join(str(tmp_path), "ckpt_*"))
    # the resume runs the unfused gradient pass
    replace_plan(fused_grad=False)
    ds = lgb.Dataset(X, label=y, params=dict(p))
    b2 = lgb.train(dict(p2), ds, num_boost_round=8, verbose_eval=False)
    assert _trees_text(b1) == _trees_text(b2)


# ---------------------------------------------------------------------------
# cost model + config + digest + telemetry
# ---------------------------------------------------------------------------

def test_grad_stream_cut_meets_acceptance_bar():
    """THE acceptance assertion: at the HIGGS bench shape (F=28, B=256,
    N=1M rows, ~5 full-pass-equivalent compacted rows per tree),
    wave_kernel_cost/grad_stream_bytes predict >= 1.5x fewer gradient-
    stream HBM bytes per iteration for int16 + fused-grad vs the PR 8
    2xbf16 + unfused baseline — and strictly fewer total kernel bytes."""
    n_rows, rows, waves = 1e6, 5e6, 10
    base = grad_stream_bytes(n_rows, rows, "2xbf16", fused_grad=False)
    quant = grad_stream_bytes(n_rows, rows, "int16", fused_grad=True)
    assert base / quant >= 1.5, (base, quant)
    # and the whole-kernel byte model agrees directionally at F=28/B=256
    _, by_base = wave_kernel_cost(rows, 28, 256, "2xbf16", waves=waves,
                                  packed=True, fused=True,
                                  fused_grad=False, n_rows=n_rows)
    _, by_quant = wave_kernel_cost(rows, 28, 256, "int16", waves=waves,
                                   packed=True, fused=True,
                                   fused_grad=True, n_rows=n_rows)
    assert by_quant < by_base
    # the vector-stream term halves: visible without the grad legs too
    _, vb = wave_kernel_cost(rows, 28, 256, "2xbf16", waves=waves,
                             packed=True, fused=True)
    _, vq = wave_kernel_cost(rows, 28, 256, "int16", waves=waves,
                             packed=True, fused=True)
    assert vb - vq == pytest.approx(rows * 8)


def test_wave_kernel_cost_quant_terms():
    """int16 charges 2 exact MXU passes (+ the packed count fold) — the
    same as 2xbf16 — and int8 one; quantized modes halve the per-row
    vector bytes; ROOFLINE.md's quantized table rows are this model."""
    rows, F, B = 1_000_000, 28, 256
    fl_2x, _ = wave_kernel_cost(rows, F, B, "2xbf16", packed=True)
    fl_16, _ = wave_kernel_cost(rows, F, B, "int16", packed=True)
    fl_8, _ = wave_kernel_cost(rows, F, B, "int8", packed=True)
    assert fl_16 == fl_2x
    assert fl_8 == pytest.approx(fl_2x * 2 / 3)  # (1+1) vs (2+1) passes
    # grad-stream legs: unfused pays write+readback+pack, fused only the
    # packed vector write
    assert grad_stream_bytes(1e6, 0, "int16", False) == \
        pytest.approx(1e6 * 24)
    assert grad_stream_bytes(1e6, 0, "int16", True) == \
        pytest.approx(1e6 * 8)
    assert grad_stream_bytes(1e6, 0, "2xbf16", True) == \
        pytest.approx(1e6 * 16)


def test_config_modes_and_digest(tmp_path):
    """Config accepts the quantized modes (resolution incl. gpu_use_dp
    precedence and the num_leaves int16 cap); config_digest refuses a
    changed hist mode, and a parameter that went with its knob (PR 30) is
    unknown: warned about, no part of the configuration or its digest."""
    from lightgbm_tpu.robust.checkpoint import config_digest
    for val in ("int16", "int8"):
        c = Config.from_params({"tpu_hist_dtype": val, "verbose": -1})
        assert resolve_hist_mode(c) == val
    c = Config.from_params({"tpu_hist_dtype": "int16", "gpu_use_dp": True,
                            "verbose": -1})
    assert resolve_hist_mode(c) == "highest"
    with pytest.raises(Exception):
        Config.from_params({"tpu_hist_dtype": "int4", "verbose": -1})
    with pytest.raises(Exception):
        Config.from_params({"tpu_hist_dtype": "int16",
                            "num_leaves": 40000, "verbose": -1})
    base = Config.from_params({"verbose": -1})
    quant = Config.from_params({"tpu_hist_dtype": "int16", "verbose": -1})
    assert config_digest(base) != config_digest(quant)
    for gone in ("tpu_fused_grad", "tpu_wave_overlap", "tpu_fused_sibling",
                 "tpu_batched_split_apply", "tpu_rank_sharded_grad"):
        c = Config.from_params({gone: False, "verbose": -1})
        assert not hasattr(c, gone)
        assert config_digest(c) == config_digest(base)


def test_iteration_schema_and_digest_fields():
    """The iteration schema accepts the new stamps and the wave-pipeline
    digest/render carry them."""
    from lightgbm_tpu.obs.report import render, summarize, validate_events
    stamps = {"hist_mode": "int16", "wave_capacity": 63,
              "fused_sibling": True, "fused_grad": True,
              "grad_hbm_bytes_saved": 16_000_000}
    events = [
        {"event": "iteration", "_proc": 0, "iteration": i, "iter_s": 0.5,
         "leaves": [63], "waves": 5, "recompiles": 0,
         "metrics": {}, "phase_s": {"tree growth": 0.4},
         "cum_row_iters_per_s": 100.0, **stamps}
        for i in range(3)
    ]
    assert validate_events(events) == []
    digest = summarize(events)
    w = digest["wave_pipeline"]
    assert w["hist_mode"] == "int16"
    assert w["fused_grad"] is True
    assert w["grad_hbm_bytes_saved"] == 16_000_000
    text = render(digest)
    assert "fused_grad=on" in text and "overlap" not in text


def test_bench_history_fused_grad_downgrade_flagged(tmp_path):
    """A fused_grad on->off flip (and a quantized->f32 hist_mode change)
    is flagged like a fused_sibling downgrade, and the new numeric
    fields trend."""
    import importlib.util
    import json
    import sys
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    spec = importlib.util.spec_from_file_location(
        "bench_history_q", os.path.join(tools, "bench_history.py"))
    bh = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bh)

    def round_payload(n, **kw):
        parsed = {"metric": "train_throughput", "value": 1000.0 + n,
                  "unit": "row_iters/s", "vs_baseline": 0.01,
                  "rows": 1000, "iters": 3, "num_leaves": 31,
                  "max_bin": 255, **kw}
        return {"n": n, "parsed": parsed}

    for i, payload in enumerate([
            round_payload(1, hist_mode="int16", fused_grad=True,
                          grad_hbm_bytes_saved=16e6),
            round_payload(2, hist_mode="2xbf16", fused_grad=False,
                          grad_hbm_bytes_saved=0.0),
    ], 1):
        with open(tmp_path / f"BENCH_r{i:02d}.json", "w") as fh:
            json.dump(payload, fh)
    rows = bh.collect([str(tmp_path)])
    assert rows[0]["mode"] == {"hist_mode": "int16", "fused_grad": True}
    mregs = bh.find_mode_regressions(rows)
    assert {m["metric"] for m in mregs} == {"fused_grad", "hist_mode"}
    regs = bh.find_regressions(rows, threshold=0.1)
    flagged = {r["metric"] for r in regs}
    assert "grad_hbm_bytes_saved" in flagged
    text = bh.render(rows, regs, mregs)
    assert "MODE REGRESSIONS" in text and "fused_grad" in text
