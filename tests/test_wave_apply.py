"""Wave split application — differential correctness, and the property the
partition pass exists for: no per-row gather.

The wave grower commits a split phase in one of two ways (the plan's
``batched_apply``).  Batched (the default): up to P splits'
[L]-sized metadata in one ``lax.scan``, then ONE streamed pass over the
rows that applies every committed slot (``core/wave_grower.py
build_split_apply_fn``, the kernel ``ops/pallas_route.py``, interpreted
here).  Sequential (``_split_once``, the oracle): one split committed and
walked at a time by an XLA walk of one bin column
(``build_split_route_fn``), the whole state through every slot's ``cond``.
These tests grow the same randomized problems through BOTH paths and
require identical trees and row partitions across the semantics the apply
must preserve: NaN/default-left routing, categorical bitsets, tie-gain
commit order, and bagging masks — plus the sharded composition through
``parallel/mesh.py``.  The apply is also held alone, on hand-made splits
over plain, bundled (EFB) and mixed-width bins, to a NumPy routing of the
same rows and bit for bit to the oracle's walks, for no, one and every
slot committed, at row counts of one block and of several with a ragged
last one; and its jaxpr to holding no gather with a row-sized output (a
per-element gather costs 3-4 ns on the chip, PERF.md 6).
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.config import Config
from lightgbm_tpu.core.meta import (DeviceMeta, SplitConfig,
                                    build_device_meta)
from lightgbm_tpu.core.plan import GrowthPlan
from lightgbm_tpu.core.splitter import bitset_words
from lightgbm_tpu.core.wave_grower import (MixedWidth, WaveSplits,
                                           build_split_apply_fn,
                                           build_split_route_fn,
                                           build_wave_grow_fn, route_view)
from lightgbm_tpu.io.binning import MISSING_NAN, MISSING_NONE, MISSING_ZERO


def _assert_identical(res1, res2):
    (t1, l1), (t2, l2) = res1, res2
    assert int(t1.num_leaves) == int(t2.num_leaves)
    for fld in t1._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(t1, fld)), np.asarray(getattr(t2, fld)),
            err_msg=f"tree field {fld} diverged")
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def _grow_both(X, y, params, seed, capacity, mask=None, cat_features=None):
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
    m = (jnp.ones((n,), jnp.float32) if mask is None
         else jnp.asarray(mask.astype(np.float32)))
    fmask = jnp.ones((handle.num_features,), bool)
    bins_fm = jnp.asarray(np.ascontiguousarray(handle.X_bin.T))
    out = []
    for batched in (False, True):
        grow = jax.jit(build_wave_grow_fn(meta, scfg, B, GrowthPlan(
            wave_capacity=capacity, hist_mode="highest", interpret=True,
            gain_gate=0.5, batched_apply=batched)))
        out.append(grow(bins_fm, g, h, m, fmask))
    return out


def _case_problem(case, seed):
    rng = np.random.default_rng(seed)
    n, f = 600, 6
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + X[:, 1] * X[:, 2] + 0.3 * rng.normal(size=n) > 0)
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 5, "verbose": -1}
    mask = None
    cats = None
    if case == "nan_default_left":
        # missing mass must follow default_left through BOTH partitions
        X[rng.random((n, f)) < 0.15] = np.nan
    elif case == "categorical_bitset":
        # a high-cardinality categorical wins splits via its bin set
        X[:, 3] = rng.integers(0, 40, size=n)
        y = (((X[:, 3].astype(int) % 5) < 2) | (X[:, 0] > 0.7))
        cats = [3]
        params = dict(params, min_data_per_group=5, cat_smooth=1.0,
                      cat_l2=1.0, max_cat_to_onehot=4)
    elif case == "tie_gain":
        # duplicated columns force exactly tied gains: the argmax commit
        # ORDER (lower feature index first) must survive the batched scan
        X[:, 4] = X[:, 0]
        X[:, 5] = X[:, 1]
    elif case == "bagging":
        mask = rng.random(n) < 0.6
    else:  # pragma: no cover
        raise AssertionError(case)
    return X, y.astype(np.float64), params, mask, cats


def test_batched_apply_differential_smoke():
    """Quick-tier smoke (the run_suite differential-apply gate): NaN +
    default-left routing, one seed, batched == sequential byte-for-byte."""
    X, y, params, mask, cats = _case_problem("nan_default_left", 0)
    r1, r2 = _grow_both(X, y, params, 1, capacity=6, mask=mask,
                        cat_features=cats)
    _assert_identical(r1, r2)
    # the tree must actually have grown for the diff to mean anything
    assert int(r1[0].num_leaves) > 4


LAYOUTS = ("plain", "bundled", "mixed")
_N_ROWS = 3000
# one block of the routing kernel, and two whole ones with a third ragged
ROW_COUNTS = (_N_ROWS, 2 * 262_144 + 777)


def _handmade(layout, n=_N_ROWS):
    """Five features in FEATURE space (what a split decides on), a phase of
    hand-made splits, and the same bins laid out as the grower would hold
    them under ``layout``.  Returns (meta, mixed, bins_fm, X, ws, leaf_id,
    feature metadata as NumPy)."""
    rng = np.random.default_rng(11)
    # feature 2's category set fits one bitset word, feature 4's takes more
    num_bins = np.array([20, 12, 30, 16, 300 if layout == "mixed" else 90],
                        np.int32)
    default = np.array([0, 0, 3, 5, 0], np.int32)
    missing = np.array([MISSING_NAN, MISSING_ZERO, MISSING_NONE,
                        MISSING_ZERO, MISSING_NONE], np.int32)
    is_cat = np.array([False, False, True, False, True])
    F = len(num_bins)
    X = np.stack([rng.integers(0, nb, n) for nb in num_bins]).astype(np.int32)
    X[0, rng.random(n) < 0.2] = num_bins[0] - 1     # the NaN bin
    feat2phys = np.arange(F, dtype=np.int32)
    offset = np.zeros(F, np.int32)
    if layout == "bundled":
        # features 1 and 3 are mutually exclusive off their default bins
        # and share physical column 1 (io/bundling.py: member i stores
        # offset_i + b where b != default_i, and 0 where every member
        # sits at its default)
        X[3, X[1] != default[1]] = default[3]
        feat2phys = np.array([0, 1, 2, 1, 3], np.int32)
        offset = np.array([0, 1, 0, 1 + num_bins[1], 0], np.int32)
        phys = np.zeros((4, n), np.int32)
        phys[0], phys[2], phys[3] = X[0], X[2], X[4]
        for f in (1, 3):
            nz = X[f] != default[f]
            phys[1, nz] = offset[f] + X[f, nz]
        bins_fm, mixed = jnp.asarray(phys.astype(np.uint8)), None
    elif layout == "mixed":
        mixed = MixedWidth(narrow_idx=np.array([0, 1, 2, 3], np.int32),
                           wide_idx=np.array([4], np.int32), B_narrow=64)
        bins_fm = (jnp.asarray(X[:4].astype(np.uint8)),
                   jnp.asarray(X[4:].astype(np.uint16)))
    else:
        bins_fm, mixed = jnp.asarray(X.astype(np.uint8)), None
    meta = DeviceMeta(
        num_bins=jnp.asarray(num_bins), default_bins=jnp.asarray(default),
        missing_types=jnp.asarray(missing),
        monotone=jnp.zeros((F,), jnp.int32),
        penalties=jnp.ones((F,), jnp.float32),
        is_categorical=jnp.asarray(is_cat),
        feat2phys=jnp.asarray(feat2phys), feat_offset=jnp.asarray(offset),
        needs_fix=jnp.zeros((F,), bool))
    W = bitset_words(int(num_bins.max()))
    cat_left = {2: rng.choice(30, 15, replace=False),
                4: rng.choice(int(num_bins[4]), 30, replace=False)}
    cb = np.zeros((7, W), np.uint32)
    for slot, f in ((2, 2), (4, 4)):
        for b in cat_left[f]:
            cb[slot, b // 32] |= np.uint32(1) << np.uint32(b % 32)
    # slot: 0 numerical, NaN bin goes LEFT by default | 1 numerical, the
    # zero bin goes RIGHT by default | 2, 4 categorical bitsets | 3
    # numerical on the bundled member | 5 a leaf that holds no row | 6 an
    # empty slot, whose fields must be ignored
    ws = WaveSplits(
        ok=jnp.asarray([True] * 6 + [False]),
        leaf=jnp.asarray([0, 1, 2, 3, 4, 9, 0], jnp.int32),
        new=jnp.asarray([10, 11, 12, 13, 14, 15, 16], jnp.int32),
        feature=jnp.asarray([0, 1, 2, 3, 4, 0, 0], jnp.int32),
        threshold=jnp.asarray([8, 4, 0, 7, 0, 5, 0], jnp.int32),
        default_left=jnp.asarray([True, False, False, True, False, True,
                                  False]),
        cat_bitset=jnp.asarray(cb))
    leaf_id = rng.integers(0, 6, n).astype(np.int32)    # leaf 5 never splits
    return meta, mixed, bins_fm, X, ws, leaf_id, (num_bins, default, missing,
                                                  is_cat, cat_left)


def _numpy_route(leaf_id, X, ws, facts, slots):
    """The first ``slots`` splits of ``ws`` applied to ``leaf_id`` by a
    NumPy routing that reads the feature-space bins directly (no physical
    layout, no decode)."""
    num_bins, default, missing, is_cat, cat_left = facts
    want = leaf_id.copy()
    for p in range(slots):
        f, leaf = int(ws.feature[p]), int(ws.leaf[p])
        col = X[f]
        if is_cat[f]:
            left = np.isin(col, cat_left[f])
        else:
            is_missing = ((missing[f] == MISSING_NAN)
                          & (col == num_bins[f] - 1)) \
                | ((missing[f] == MISSING_ZERO) & (col == default[f]))
            left = np.where(is_missing, bool(ws.default_left[p]),
                            col <= int(ws.threshold[p]))
        want[(leaf_id == leaf) & ~left] = int(ws.new[p])
    return want


@pytest.mark.parametrize("rows", ROW_COUNTS)
@pytest.mark.parametrize("committed", (0, 1, 6))
@pytest.mark.parametrize("layout", LAYOUTS)
def test_apply_alone_routes_as_numpy(layout, committed, rows):
    """The apply's one pass (the kernel, interpreted) on hand-made splits:
    a NaN-missing, a zero-missing and a bundled-member numeric split, two
    categorical bitsets and a leaf without rows.  Equal to the NumPy
    routing, and bit for bit to the oracle's XLA walks of the same
    slots."""
    meta, mixed, bins_fm, X, ws, leaf_id, facts = _handmade(layout, rows)
    bundled = layout == "bundled"
    apply = jax.jit(build_split_apply_fn(meta, bundled=bundled,
                                         interpret=True))
    ws = ws._replace(ok=jnp.arange(7) < committed)
    got, walks, passes = apply(jnp.asarray(leaf_id),
                               route_view(bins_fm, mixed), ws)
    want = _numpy_route(leaf_id, X, ws, facts, committed)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert (int(walks), int(passes)) == (committed, min(committed, 1))
    route = build_split_route_fn(meta, bundled=bundled, mixed=mixed)
    oracle = jnp.asarray(leaf_id)
    for p in range(committed):
        oracle = route(oracle, bins_fm, ws.leaf[p], ws.new[p], ws.feature[p],
                       ws.threshold[p], ws.default_left[p], ws.cat_bitset[p])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))
    if committed == 6:
        # each split moved some rows and kept some; the rowless leaf and
        # the empty slot created nothing
        for p in range(5):
            moved = int((want == int(ws.new[p])).sum())
            assert 0 < moved < int((leaf_id == int(ws.leaf[p])).sum())
        assert not np.isin(want, (15, 16)).any()


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its loops, branches, calls and
    kernels included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_apply_holds_no_row_sized_gather(layout):
    """What the pass exists for, and what a refactor would lose silently:
    one kernel over the rows, looping over the committed slots and reading
    per-split scalars; nothing in it, or around it, gathers an element a
    row."""
    meta, mixed, bins_fm, _, ws, leaf_id, _ = _handmade(layout)
    apply = build_split_apply_fn(meta, bundled=layout == "bundled")
    closed = jax.make_jaxpr(apply)(jnp.asarray(leaf_id),
                                   route_view(bins_fm, mixed), ws)
    eqns = list(_eqns(closed.jaxpr))
    names = [e.primitive.name for e in eqns]
    assert names.count("pallas_call") == 1 and "while" in names
    assert "dma_start" in names         # the slot's column, from the view
    row_sized = [e for e in eqns
                 if any(np.prod(v.aval.shape, dtype=np.int64) >= _N_ROWS
                        for v in e.outvars)]
    assert len(row_sized) > 5           # the pass itself is there
    assert not [e for e in row_sized if "gather" in e.primitive.name]


@pytest.mark.parametrize("case,seed", [
    ("categorical_bitset", 7), ("categorical_bitset", 23),
    ("tie_gain", 7), ("tie_gain", 23),
    ("bagging", 7), ("bagging", 23),
])
def test_batched_apply_differential(case, seed):
    """Randomized differential: batched one-pass apply == sequential
    oracle across categorical-bitset, tie-gain and bagging-mask cases."""
    X, y, params, mask, cats = _case_problem(case, seed)
    for capacity in (1, 6):
        r1, r2 = _grow_both(X, y, params, seed + 1, capacity=capacity,
                            mask=mask, cat_features=cats)
        _assert_identical(r1, r2)
        assert int(r1[0].num_leaves) > 4
    if case == "categorical_bitset":
        nn = int(r1[0].num_leaves) - 1
        cb = np.asarray(r1[0].cat_bitset[:nn])
        assert (cb != 0).any(), "no categorical split committed — case inert"


def test_batched_apply_mesh_parallel():
    """Sharded composition (parallel/mesh.py): on a 2-device mesh the
    row-sharded wave grower's batched apply matches its sequential
    oracle bit-for-bit, and the feature-parallel learner (which rides
    the refactored shared split_decision helper) still reproduces the
    serial grower."""
    from jax.sharding import Mesh
    from lightgbm_tpu.core.grower import make_grower
    from lightgbm_tpu.parallel import make_feature_parallel_grower
    from lightgbm_tpu.parallel.mesh import make_data_parallel_wave_grower

    rng = np.random.default_rng(5)
    n, f = 512, 6
    X = rng.normal(size=(n, f))
    X[rng.random((n, f)) < 0.1] = np.nan
    y = (np.nan_to_num(X[:, 0]) + np.nan_to_num(X[:, 1]) > 0)
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 5, "verbose": -1}
    ds = lgb.Dataset(X, label=y.astype(np.float64), params=params)
    ds.construct()
    handle = ds._handle
    cfg = Config.from_params(params)
    meta, B = build_device_meta(handle, cfg)
    scfg = SplitConfig.from_config(cfg)
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray((0.1 + rng.random(n)).astype(np.float32))
    mask = jnp.ones((n,), jnp.float32)
    fmask = jnp.ones((f,), bool)
    bins = jnp.asarray(handle.X_bin)
    bins_fm = jnp.asarray(np.ascontiguousarray(handle.X_bin.T))

    devs = np.array(jax.devices())
    assert len(devs) >= 2
    mesh = Mesh(devs[:2], ("data",))

    res = []
    for batched in (False, True):
        dp = make_data_parallel_wave_grower(meta, scfg, B, mesh, GrowthPlan(
            wave_capacity=6, hist_mode="highest", interpret=True,
            gain_gate=0.5, batched_apply=batched, fused_sibling=False))
        res.append(dp(bins_fm, g, h, mask, fmask))
    _assert_identical(res[0], res[1])
    assert int(res[0][0].num_leaves) > 4

    t_serial, _ = make_grower(meta, scfg, B)(bins, g, h, mask, fmask)
    fp = make_feature_parallel_grower(meta, scfg, B, mesh)
    t_fp, _ = fp(bins, g, h, mask, fmask)
    assert int(t_fp.num_leaves) == int(t_serial.num_leaves)
    nn = int(t_serial.num_leaves) - 1
    np.testing.assert_array_equal(np.asarray(t_fp.split_feature[:nn]),
                                  np.asarray(t_serial.split_feature[:nn]))
    np.testing.assert_array_equal(np.asarray(t_fp.threshold_bin[:nn]),
                                  np.asarray(t_serial.threshold_bin[:nn]))


def test_default_path_is_batched(monkeypatch, replace_plan):
    """The batched apply is the DEFAULT: a TPU-gated Booster builds its
    wave grower with the one-pass apply; the sequential reference is a
    field of the plan, and the iteration record says which ran."""
    assert not hasattr(Config(), "tpu_batched_split_apply")
    rng = np.random.default_rng(0)
    X = rng.normal(size=(200, 3)).round(1)
    y = (X[:, 0] > 0).astype(np.float64)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    base = {"objective": "binary", "verbose": -1, "device_type": "tpu"}
    ds = lgb.Dataset(X, label=y, params=base)
    bst = lgb.Booster(params=base, train_set=ds)
    assert bst._gbdt.uses_wave and bst._gbdt._plan.batched_apply
    replace_plan(batched_apply=False)
    ds2 = lgb.Dataset(X, label=y, params=base)
    bst2 = lgb.Booster(params=base, train_set=ds2)
    assert bst2._gbdt.uses_wave and not bst2._gbdt._plan.batched_apply
    assert bst2._gbdt._plan.key() != bst._gbdt._plan.key()


def test_partition_cost_model():
    """partition_cost: a split reads one bin byte a row, a pass reads and
    writes ``leaf_id`` (8 bytes a row).  The batched phase's one pass a
    phase against a walk a split (``passes`` left out: 9 bytes a row a
    split); linear in rows, splits and passes."""
    from lightgbm_tpu.core.splitter import partition_cost
    N = 100_000
    fb, bb = partition_cost(N, splits=254, passes=14)
    assert bb == (254 + 8 * 14) * N
    fs, bs = partition_cost(N, splits=254)
    assert fs == fb and bs == 9.0 * 254 * N
    assert partition_cost(N, splits=254, passes=254) == (fs, bs)
    assert partition_cost(N, splits=1)[1] == 9.0 * N
    assert partition_cost(2 * N, splits=254, passes=14)[1] == 2 * bb
    assert partition_cost(N, splits=42, passes=1)[1] == 50.0 * N
    # a tree of HIGGS: 366 bytes a row where 254 walks are 2,286
    assert bb / N == 366 and bs / N == 2286


def test_partition_attribution_emitted(tmp_path):
    """Profile mode separately attributes the partition unit: iteration
    events carry partition_passes/partition_batched and a
    ``lgbm/partition`` kernel_profile event lands in the stream (the
    acceptance telemetry for the batched-apply PR, CPU-runnable)."""
    rng = np.random.default_rng(3)
    X = rng.normal(size=(400, 5))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    obs.reset()
    obs.enable(str(tmp_path / "t"))
    obs.enable_profile()
    try:
        params = {"objective": "binary", "num_leaves": 7,
                  "min_data_in_leaf": 5, "verbose": -1}
        ds = lgb.Dataset(X, label=y, params=params)
        bst = lgb.Booster(params=params, train_set=ds)
        for _ in range(3):
            bst.update()
        digest = obs.digest()
    finally:
        obs.enable_profile(False)
        obs.disable()
        obs.reset()
    events = [json.loads(ln) for ln in
              (tmp_path / "t" / "telemetry.0.jsonl").read_text().splitlines()]
    iters = [e for e in events if e["event"] == "iteration"]
    assert iters
    for e in iters:
        assert e["partition_passes"] >= 1
        # CPU serial grower: one partition walk per split
        assert e["partition_batched"] is False
        assert e["partition_passes"] == sum(
            max(nl - 1, 0) for nl in e["leaves"])
    kp = [e for e in events if e["event"] == "kernel_profile"
          and e["kernel"] == "lgbm/partition"]
    assert kp, "lgbm/partition attribution missing from profile stream"
    assert all(e["flops"] > 0 and e["bytes"] > 0 for e in kp)
    assert "lgbm/partition" in (digest.get("kernels") or {})
