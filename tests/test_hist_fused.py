"""Fused wave-histogram pipeline — differential correctness (ISSUE 8).

The wave kernel's fast path is the packed layout (63 leaves/launch, in MXU
passes of 25 leaves at five lanes a leaf since PR 33) with in-kernel sibling
subtraction; the triple-layout unfused path survives purely as the
differential oracle (the plan's ``fused_sibling`` / ``packed`` off).
These tests grow the same randomized problems through every
(packed, fused) combination and require BIT-IDENTICAL trees and row
partitions on the f32 ("highest") path — the same contract the
sequential-split oracle enforced for PR 4 — across NaN/default-left
routing, categorical bitsets, the B=63 feature-pack path, and the
2-device data-parallel mesh.  The kernel-level tests pin the channel
layouts and the fused parent-minus-child emission directly, and the
waves-count tests pin the CPU-measurable win: fewer kernel launches per
tree at packed capacity.
"""
import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.core.meta import SplitConfig, build_device_meta
from lightgbm_tpu.core.plan import (Facts, GrowthPlan, resolve_hist_mode,
                                    select_path)
from lightgbm_tpu.core.wave_grower import build_wave_grow_fn, wave_counts
from lightgbm_tpu.ops.pallas_hist import (C_MAX, P_MAX_PACKED, P_MAX_TRIPLE,
                                          QUANT_MODES, QUANT_QMAX,
                                          feat_pack, hist_pallas_wave,
                                          packed_lanes, pass_leaves,
                                          select_wave_blocks, unpack_lanes,
                                          wave_capacity_max,
                                          wave_kernel_cost, wave_mxu_passes)


def _assert_identical(res1, res2):
    (t1, l1), (t2, l2) = res1[:2], res2[:2]
    assert int(t1.num_leaves) == int(t2.num_leaves)
    for fld in t1._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(t1, fld)), np.asarray(getattr(t2, fld)),
            err_msg=f"tree field {fld} diverged")
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


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


def _grow_grid(problem, capacity=63, grid=((False, False), (True, True))):
    """Grow through each (packed, fused_sibling) combination."""
    handle, meta, scfg, B, bins_fm, g, h, mask, fmask = problem
    out = []
    for packed, fused in grid:
        grow = jax.jit(build_wave_grow_fn(meta, scfg, B, GrowthPlan(
            wave_capacity=min(capacity, wave_capacity_max(packed)),
            hist_mode="highest", interpret=True, gain_gate=0.5,
            packed=packed, fused_sibling=fused)))
        out.append(grow(bins_fm, g, h, mask, fmask))
    return out


def _case_problem(case, seed):
    rng = np.random.default_rng(seed)
    n, f = 600, 6
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + X[:, 1] * X[:, 2] + 0.3 * rng.normal(size=n) > 0)
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 5, "verbose": -1}
    cats = None
    if case == "nan_default_left":
        # missing mass must follow default_left through both layouts and
        # through the fused sibling (parent - child keeps the NaN bin)
        X[rng.random((n, f)) < 0.15] = np.nan
    elif case == "categorical_bitset":
        X[:, 3] = rng.integers(0, 40, size=n)
        y = (((X[:, 3].astype(int) % 5) < 2) | (X[:, 0] > 0.7))
        cats = [3]
        params = dict(params, min_data_per_group=5, cat_smooth=1.0,
                      cat_l2=1.0, max_cat_to_onehot=4)
    return X, y.astype(np.float64), params, cats


# ---------------------------------------------------------------------------
# kernel level
# ---------------------------------------------------------------------------

def _kernel_inputs(n=300, f=6, seed=0, leaves=(3, 0, 4)):
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


def _assert_packed_is_triple(packed_res, triple_res, mode, n_slots):
    """Slot s's (sum_g, sum_h, count) of the packed result, read where
    ``packed_lanes`` keeps them, against lanes 3s..3s+2 of the triple
    layout's: equal bit for bit."""
    got = np.asarray(unpack_lanes(packed_res, mode, n_slots))  # [P,F,B,3]
    ht = np.asarray(triple_res)
    want = ht[:, :, :3 * n_slots].reshape(*ht.shape[:2], n_slots, 3)
    np.testing.assert_array_equal(got, want.transpose(2, 0, 1, 3))


@pytest.mark.parametrize("mode", ["highest", "2xbf16", "bf16"])
def test_packed_channels_bit_match_triple(mode):
    """Packed passes vs (g,h,count) triples: per-lane accumulation is
    independent, both layouts accumulate the hi and the lo sums apart over
    the row blocks and add them on the last, and the count's 0/1 weights
    are bf16-exact, so every leaf's (sum_g, sum_h, count) histograms must
    be BIT-identical between layouts in every precision mode."""
    (bins_fm, g, h, cv, leaf_id, slot_t, slot_p, B,
     leaves) = _kernel_inputs()
    ht = hist_pallas_wave(bins_fm, g, h, cv, leaf_id, slot_t, B=B,
                          highest=mode, interpret=True)
    hp_gh, hp_ct = hist_pallas_wave(bins_fm, g, h, cv, leaf_id, slot_p,
                                    B=B, highest=mode, interpret=True,
                                    packed=True)
    _assert_packed_is_triple((hp_gh, hp_ct), ht, mode, len(leaves))


# leaves a launch x mode: each side of every pass boundary, 25 leaves a
# pass in the split modes and 42 in the single-value ones
_PASS_CASES = ([(n, m) for m in ("2xbf16", "int16")
                for n in (1, 25, 26, 50, 51, 63)]
               + [(n, m) for m in ("bf16", "int8") for n in (42, 43, 63)])


@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
@pytest.mark.parametrize("n_leaves,mode", _PASS_CASES)
def test_packed_passes_match_the_triple_layout(n_leaves, mode, fused):
    """A launch of the packed layout over 1..63 pending leaves (one, two
    or three passes of 25, one or two of 42: ``wave_mxu_passes``) against
    the triple layout over the same leaves, 42 at a time: g and h equal
    bit for bit (both add the hi and the lo sums on the last row step) and,
    in the quantised modes, NumPy's integer sums exactly; counts the bag's
    exactly, the sibling exactly parent - child, and nothing from empty
    slots, rows outside the bag, rows in no pending leaf or the padding
    past the last row (``leaf_id`` -2)."""
    rng = np.random.default_rng(100 * n_leaves + fused)
    n, f, B, br = 700, 5, 64, 256           # 700 rows pad to 768
    bins = rng.integers(0, B, size=(f, n), dtype=np.uint8)
    g = rng.normal(size=n).astype(np.float32)
    h = (0.1 + rng.random(n)).astype(np.float32)
    if mode in QUANT_MODES:
        g = np.rint(g / np.abs(g).max() * QUANT_QMAX[mode])
        h = np.rint(h / h.max() * QUANT_QMAX[mode])
    cv = (rng.random(n) < 0.8).astype(np.float32)       # the bag
    g, h = (g * cv).astype(np.float32), (h * cv).astype(np.float32)
    # leaf ids 0..n_leaves+2: three leaves are pending in no slot
    leaf = rng.integers(0, n_leaves + 3, size=n).astype(np.int32)
    ids = rng.permutation(n_leaves + 3)[:n_leaves].astype(np.int32)
    holes = set(range(1, n_leaves - 1, 7))              # empty slots
    ids[list(holes)] = -1
    slot_p = np.full(C_MAX, -1, np.int32)
    slot_p[:2 * n_leaves] = np.repeat(ids, 2)
    assert int(wave_mxu_passes(n_leaves, mode, True)) \
        == -(-n_leaves // pass_leaves(mode))
    kw = dict(B=B, highest=mode, interpret=True, block_rows=br,
              feat_block=4)
    args = [jnp.asarray(a) for a in (bins, g, h, cv, leaf)]
    parent = None
    if fused:
        parent = tuple(jnp.asarray(
            np.rint(rng.normal(size=(f, B, C_MAX)) * 64.0)
            .astype(np.float32)) for _ in range(2))
    res = hist_pallas_wave(*args, jnp.asarray(slot_p), packed=True,
                           parent=parent, **kw)
    child = res[0] if fused else res
    got = np.asarray(unpack_lanes(child, mode, n_leaves))   # [P, F, B, 3]
    for lo in range(0, n_leaves, P_MAX_TRIPLE):
        part = ids[lo:lo + P_MAX_TRIPLE]
        slot_t = np.full(C_MAX, -1, np.int32)
        slot_t[:3 * len(part)] = np.repeat(part, 3)
        ht = np.asarray(hist_pallas_wave(*args, jnp.asarray(slot_t), **kw))
        want = ht[:, :, :3 * len(part)].reshape(f, B, len(part), 3)
        np.testing.assert_array_equal(got[lo:lo + len(part)],
                                      want.transpose(2, 0, 1, 3))
    onehot = (bins[:, :, None] == np.arange(B)[None, None, :]).astype(
        np.float64)                                         # [F, N, B]
    for s, lf in enumerate(ids):
        rows = (leaf == lf) & (cv > 0) if lf >= 0 else np.zeros(n, bool)
        for k, v in enumerate((g, h, cv)):
            if k == 2 or mode in QUANT_MODES:
                np.testing.assert_array_equal(
                    got[s, :, :, k], np.einsum(
                        "fnb,n->fb", onehot, np.where(rows, v, 0.0)))
        if lf < 0:
            assert not got[s].any()
    # lanes that packed_lanes gives to no slot hold nothing
    cat = np.concatenate([np.asarray(x) for x in child], axis=-1)
    owned = np.zeros(2 * C_MAX, bool)
    owned[packed_lanes(mode)[:, :n_leaves].reshape(-1)] = True
    assert not cat[:, :, ~owned].any()
    if fused:
        for sib, par, ch in zip(res[1], parent, child):
            np.testing.assert_array_equal(
                np.asarray(sib), np.asarray(par) - np.asarray(ch))


@pytest.mark.parametrize("B", [64, 256])
def test_feature_blocks_beyond_the_unroll_loop_the_same(B):
    """A block of 20 features is more than the kernel writes out in a row:
    it loops over groups of them, reading an i32 copy of the bins (at
    B = 64 two features share a pass, ten steps in groups of five).  Both
    layouts against a NumPy histogram of exact integers (int16 mode), and
    against each other bit for bit in 2xbf16."""
    rng = np.random.default_rng(B)
    n, f, n_leaves = 600, 20, 26
    bins = rng.integers(0, B, size=(f, n), dtype=np.uint8)
    cv = (rng.random(n) < 0.8).astype(np.float32)
    g = (np.rint(rng.normal(size=n) * 500.0) * cv).astype(np.float32)
    h = (np.rint(rng.random(n) * 900.0) * cv).astype(np.float32)
    leaf = rng.integers(0, n_leaves, size=n).astype(np.int32)
    slot_p = np.full(C_MAX, -1, np.int32)
    slot_p[:2 * n_leaves] = np.repeat(np.arange(n_leaves), 2)
    slot_t = np.full(C_MAX, -1, np.int32)
    slot_t[:3 * n_leaves] = np.repeat(np.arange(n_leaves), 3)
    args = [jnp.asarray(a) for a in (bins, g, h, cv, leaf)]
    kw = dict(B=B, interpret=True, block_rows=256, feat_block=32)
    want = np.zeros((n_leaves, f, B, 3))
    for k, v in enumerate((g, h, cv)):
        for j in range(f):
            np.add.at(want[:, j, :, k], (leaf, bins[j]), v)
    for mode in ("int16", "2xbf16"):
        packed = unpack_lanes(hist_pallas_wave(
            *args, jnp.asarray(slot_p), highest=mode, packed=True, **kw),
            mode, n_leaves)
        ht = np.asarray(hist_pallas_wave(*args, jnp.asarray(slot_t),
                                         highest=mode, **kw))
        triple = ht[:, :, :3 * n_leaves].reshape(f, B, n_leaves, 3)
        np.testing.assert_array_equal(np.asarray(packed),
                                      triple.transpose(2, 0, 1, 3))
        if mode == "int16":
            np.testing.assert_array_equal(np.asarray(packed), want)


@pytest.mark.parametrize("packed", [False, True])
def test_fused_kernel_emits_parent_minus_child(packed):
    """The fused variant returns (child, sibling) from one pallas_call
    with child identical to the unfused run and sibling EXACTLY
    parent - child (one f32 subtraction in VMEM — bit-equal to the XLA
    subtraction it replaces)."""
    (bins_fm, g, h, cv, leaf_id, slot_t, slot_p, B,
     leaves) = _kernel_inputs()
    slot = slot_p if packed else slot_t
    un = hist_pallas_wave(bins_fm, g, h, cv, leaf_id, slot, B=B,
                          highest=True, interpret=True, packed=packed)
    rng = np.random.default_rng(7)
    if packed:
        parent = tuple(
            jnp.asarray(rng.normal(size=np.asarray(x).shape)
                        .astype(np.float32)) for x in un)
    else:
        parent = jnp.asarray(rng.normal(size=np.asarray(un).shape)
                             .astype(np.float32))
    child, sib = hist_pallas_wave(bins_fm, g, h, cv, leaf_id, slot, B=B,
                                  highest=True, interpret=True,
                                  packed=packed, parent=parent)
    if packed:
        for c, u in zip(child, un):
            np.testing.assert_array_equal(np.asarray(c), np.asarray(u))
        for s, p, c in zip(sib, parent, child):
            np.testing.assert_array_equal(np.asarray(s),
                                          np.asarray(p) - np.asarray(c))
    else:
        np.testing.assert_array_equal(np.asarray(child), np.asarray(un))
        np.testing.assert_array_equal(
            np.asarray(sib), np.asarray(parent) - np.asarray(child))


def test_feature_pack_b64():
    """B <= 64 packs 128//B features' one-hot factors into one MXU pass
    in BOTH kernels now; at max_bin=63 (B=64, the reference GPU backend's
    recommended bin count) the packed wave kernel must still bit-match
    the triple layout."""
    assert feat_pack(64, 32) == 2
    assert feat_pack(32, 32) == 4
    assert feat_pack(256, 32) == 1
    assert feat_pack(64, 3) == 1   # pack must divide the feature block
    rng = np.random.default_rng(4)
    n, f = 400, 8
    X = rng.normal(size=(n, f)).round(2)
    y = (X[:, 0] + X[:, 1] > 0)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
              "min_data_in_leaf": 5, "verbose": -1}
    problem = _setup(X, y.astype(np.float64), params, 4)
    B = problem[3]
    assert B <= 64
    _assert_identical(*_grow_grid(problem))


# ---------------------------------------------------------------------------
# grower level
# ---------------------------------------------------------------------------

def test_fused_packed_smoke():
    """Quick-tier gate (the run_suite fused-kernel smoke): NaN routing +
    default packed/fused grid vs the triple/unfused oracle, bit-exact."""
    X, y, params, cats = _case_problem("nan_default_left", 0)
    problem = _setup(X, y, params, 0, cats)
    res = _grow_grid(problem)
    _assert_identical(res[0], res[1])
    assert int(res[0][0].num_leaves) > 4


@pytest.mark.parametrize("case,seed", [
    ("nan_default_left", 7), ("categorical_bitset", 7),
    ("categorical_bitset", 23),
])
def test_fused_packed_differential(case, seed):
    """Full (packed, fused) grid vs the triple/unfused oracle across the
    layout-sensitive semantics: NaN/default-left and categorical
    bitsets."""
    X, y, params, cats = _case_problem(case, seed)
    problem = _setup(X, y, params, seed, cats)
    res = _grow_grid(problem, grid=((False, False), (False, True),
                                    (True, False), (True, True)))
    for other in res[1:]:
        _assert_identical(res[0], other)
    if case == "categorical_bitset":
        t = res[0][0]
        cb = np.asarray(t.cat_bitset[:int(t.num_leaves) - 1])
        assert (cb != 0).any(), "no categorical split committed — case inert"


def test_packed_capacity_cuts_waves():
    """The CPU-measurable launch reduction (acceptance criterion): a deep
    511-leaf tree takes FEWER kernel launches at packed capacity 63 than
    at the triple layout's 42 — every launch is a full-data histogram
    pass, the dominant per-tree TPU cost.  (The gap needs a ready
    frontier wider than 42, hence the deep unthrottled tree: measured
    19 -> 16 waves here.)"""
    rng = np.random.default_rng(17)
    n, f = 8192, 8
    X = rng.normal(size=(n, f)).round(2)
    y = (X[:, 0] + np.sin(3 * X[:, 1]) + 0.5 * X[:, 2] * X[:, 3]
         + 0.2 * rng.normal(size=n) > 0)
    params = {"objective": "binary", "num_leaves": 511,
              "min_data_in_leaf": 2, "min_sum_hessian_in_leaf": 1e-3,
              "verbose": -1}
    problem = _setup(X, y.astype(np.float64), params, 17)
    handle, meta, scfg, B, bins_fm, g, h, mask, fmask = problem
    waves = {}
    for packed in (False, True):
        grow = jax.jit(build_wave_grow_fn(meta, scfg, B, GrowthPlan(
            wave_capacity=wave_capacity_max(packed), hist_mode="highest",
            interpret=True, packed=packed, counts=True)))
        t, lid, stats = grow(bins_fm, g, h, mask, fmask)
        assert int(t.num_leaves) >= 400
        c = wave_counts(stats)
        waves[packed] = c["waves"]
        # the layout changes how many lanes a launch has, not how many
        # leaves there are to fill them
        assert c["lanes"] == int(t.num_leaves) and c["waves"] <= c["bodies"]
    # triple capacity saturates at 42; packed runs the full 63
    assert waves[True] < waves[False], waves


def test_mesh_data_parallel_packed_matches_single():
    """2-device data-parallel mesh: the packed grower (the sibling is
    parent minus the GLOBAL child, subtracted after the psum: the fused
    sibling cannot run there and the builder says so) is bit-identical to
    the single-device fused path and to the mesh triple oracle."""
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
    res = []
    for packed in (True, False):
        dp = make_data_parallel_wave_grower(meta, scfg, B, mesh, GrowthPlan(
            wave_capacity=6, hist_mode="highest", interpret=True,
            gain_gate=0.5, packed=packed, fused_sibling=False))
        res.append(dp(bins_fm, g, h, mask, fmask))
    _assert_identical(res[0], res[1])
    with pytest.raises(AssertionError, match="fused sibling"):
        make_data_parallel_wave_grower(meta, scfg, B, mesh, GrowthPlan(
            wave_capacity=6, hist_mode="highest", interpret=True))

    # vs single device: structure exact, values to psum rounding (the
    # cross-device sum order differs from the single-device block order
    # by design — same tolerance as test_parallel's wave mesh test)
    single = jax.jit(build_wave_grow_fn(meta, scfg, B, GrowthPlan(
        wave_capacity=6, hist_mode="highest", interpret=True,
        gain_gate=0.5)))
    t1, lid1 = single(bins_fm, g, h, mask, fmask)
    t2, lid2 = res[0]
    nn = int(t1.num_leaves) - 1
    assert int(t2.num_leaves) == nn + 1
    np.testing.assert_array_equal(np.asarray(t1.split_feature[:nn]),
                                  np.asarray(t2.split_feature[:nn]))
    np.testing.assert_array_equal(np.asarray(t1.threshold_bin[:nn]),
                                  np.asarray(t2.threshold_bin[:nn]))
    np.testing.assert_allclose(np.asarray(t1.leaf_value),
                               np.asarray(t2.leaf_value), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(lid1), np.asarray(lid2))
    assert int(res[0][0].num_leaves) > 4


# ---------------------------------------------------------------------------
# cost model + config + telemetry
# ---------------------------------------------------------------------------

def test_capacity_and_block_selection():
    """Layout capacities and the cost-model-driven block picker."""
    assert P_MAX_TRIPLE == 42 and P_MAX_PACKED == 63
    assert wave_capacity_max(True) == 63
    assert wave_capacity_max(False) == 42
    # bin-width specialization in block form: small B affords bigger
    # fused feature blocks than B=256, and the unfused path bigger still
    _, fb64 = select_wave_blocks(64, packed=True, fused=True)
    _, fb256 = select_wave_blocks(256, packed=True, fused=True)
    _, fb256_un = select_wave_blocks(256, packed=True, fused=False)
    assert fb64 > fb256
    assert fb256_un > fb256
    for B in (16, 32, 64, 256):
        br, fb = select_wave_blocks(B)
        assert br >= 128 and fb >= 8 and fb % feat_pack(B, fb) == 0
    # the pipeline gates live in select_path alone (tests/test_plan.py has
    # the table); a plan that asks for what cannot run is refused
    on_chip = Facts(backend="tpu", num_features=4, num_phys_features=4,
                    bin_dtype="uint8", B_phys=256)
    plan = select_path(Config.from_params(
        {"device_type": "tpu", "tpu_wave_capacity": 100, "verbose": -1}),
        on_chip)
    assert (plan.packed, plan.wave_capacity, plan.fused_sibling) \
        == (True, 63, True)                                 # clamped
    plan.check()
    for bad in (dict(wave_capacity=64), dict(packed=False),
                dict(bundled=True), dict(hist_mode="int16", bundled=True,
                                         fused_sibling=False)):
        with pytest.raises(AssertionError):
            dataclasses.replace(plan, **bad).check()
    with pytest.raises(AssertionError):
        plan.check(data_parallel=True)


def test_wave_kernel_cost_packed_fused_terms():
    """The analytical model must reflect the new layout: packed charges
    one extra MXU pass (the folded count) but the fused launch's HBM
    legs stay below the unfused launch + separate XLA subtraction pass
    it replaces (which re-reads the child and parent and writes the
    sibling)."""
    rows, F, B = 1_000_000, 28, 64
    fl_t, by_t = wave_kernel_cost(rows, F, B, "2xbf16", waves=10)
    fl_p, by_p = wave_kernel_cost(rows, F, B, "2xbf16", waves=10,
                                  packed=True)
    assert fl_p == fl_t * 3 / 2          # 2 passes -> 3
    fl_pf, by_pf = wave_kernel_cost(rows, F, B, "2xbf16", waves=10,
                                    packed=True, fused=True)
    assert fl_pf == fl_p                 # subtraction is VPU, not MXU
    hist = F * B * C_MAX * 4
    assert by_pf == by_p + 10 * 2 * hist * 2   # + parent read + sib write
    # the unfused pipeline pays the same sibling legs PLUS a child
    # re-read in its separate XLA pass — fused is strictly cheaper
    unfused_total = by_p + 10 * (2 + 1) * hist * 2
    assert by_pf < unfused_total
    # fewer waves is the packed win the model must reward
    _, by_fewer = wave_kernel_cost(rows, F, B, "2xbf16", waves=7,
                                   packed=True, fused=True)
    assert by_fewer < by_pf


def test_config_defaults_and_dtype_aliases(monkeypatch):
    """tpu_hist_dtype speaks kernel-mode names (2xbf16/bf16/highest) with
    float32/bfloat16 as back-compat aliases; capacity defaults to the
    packed 63; the reference paths are no parameters (an unknown one is
    warned about and ignored)."""
    cfg = Config()
    assert cfg.tpu_hist_dtype == "2xbf16"
    assert not hasattr(cfg, "tpu_fused_sibling")
    assert cfg.tpu_wave_capacity == 63
    for val, mode in (("2xbf16", "2xbf16"), ("float32", "2xbf16"),
                      ("bf16", "bf16"), ("bfloat16", "bf16"),
                      ("highest", "highest"), ("int16", "int16"),
                      ("int8", "int8")):
        c = Config.from_params({"tpu_hist_dtype": val, "verbose": -1})
        assert resolve_hist_mode(c) == mode, (val, mode)
    with pytest.raises(Exception):
        Config.from_params({"tpu_hist_dtype": "f64", "verbose": -1})
    with pytest.raises(Exception):
        Config.from_params({"tpu_wave_capacity": 0, "verbose": -1})


def test_booster_wave_info_and_fused_gate(monkeypatch, replace_plan):
    """A TPU-gated Booster stamps the effective pipeline mode: packed
    capacity 63, fused_sibling on by default, off where the plan says so
    (and the stamps feed per-iteration telemetry)."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(200, 3)).round(1)
    y = (X[:, 0] > 0).astype(np.float64)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    base = {"objective": "binary", "verbose": -1, "device_type": "tpu"}
    bst = lgb.Booster(params=base, train_set=lgb.Dataset(X, label=y,
                                                         params=base))
    info = bst._gbdt._wave_info
    assert info == {"hist_mode": "2xbf16", "wave_capacity": 63,
                    "packed": True, "fused_sibling": True,
                    "interpret": False, "fused_grad": True}
    assert info == bst._gbdt._plan.stamps()
    replace_plan(fused_sibling=False, fused_grad=False)
    off = {**base, "tpu_hist_dtype": "highest"}
    bst2 = lgb.Booster(params=off, train_set=lgb.Dataset(X, label=y,
                                                         params=off))
    info2 = bst2._gbdt._wave_info
    assert info2["fused_sibling"] is False
    assert info2["hist_mode"] == "highest"
    assert info2["fused_grad"] is False
    assert bst2._gbdt._grow_apply_fused is None


def test_wave_pipeline_digest_and_schema():
    """summarize/render surface waves-per-tree + mode stamps, and the
    iteration schema accepts the new fields."""
    from lightgbm_tpu.obs.report import render, summarize, validate_events
    stamps = {"hist_mode": "2xbf16", "wave_capacity": 63,
              "fused_sibling": True}
    events = [
        {"event": "iteration", "_proc": 0, "iteration": i, "iter_s": 0.5,
         "leaves": [63], "waves": 6, "recompiles": 0,
         "metrics": {}, "phase_s": {"tree growth": 0.4},
         "cum_row_iters_per_s": 100.0, **stamps}
        for i in range(4)
    ]
    assert validate_events(events) == []
    digest = summarize(events)
    w = digest["wave_pipeline"]
    assert w["waves_per_tree"] == 6.0
    assert w["waves_total"] == 24 and w["trees_grown"] == 4
    assert w["hist_mode"] == "2xbf16" and w["wave_capacity"] == 63
    assert w["fused_sibling"] is True
    assert digest["per_iteration"][0]["hist_mode"] == "2xbf16"
    text = render(digest)
    assert "waves/tree" in text and "fused_sibling=on" in text
    # no wave path, no section
    assert "wave_pipeline" not in summarize(
        [{"event": "iteration", "_proc": 0, "iteration": 0, "iter_s": 0.1}])
