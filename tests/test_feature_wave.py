"""The feature-parallel learner on the wave kernel (``tree_learner=feature``,
``parallel/mesh.py make_feature_parallel_wave_grower``), on four of
conftest's eight virtual devices with the kernel interpreted.

Every chip holds every row and histograms its own run of whole feature
blocks, read through the kernel's index map (``hist_pallas_wave``'s
``col_blocks`` / ``col_window``); the wave's best splits are all-gathered
once a wave and every chip commits the same ones.  That changes which chip
searches which column, not what is found: the trees are the one-device wave
path's bit for bit, and the serial XLA learner's (``device_type=cpu``, the
benchmark's plain oracle) split for split, where the wave path grows one leaf
a launch in f32 (its values then differ from the scatter histograms' only by
the order of f32 sums).

Also here: the kernel launched with exactly the keywords the benchmark's
``reducers/fullpass.py`` passes still covers every column (a signature change
would drop ``pallas_hist_wave_roofline`` from every cell in silence).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import pallas_hist as ph

MESH = {"tree_learner": "feature", "tpu_mesh_shape": "data:4"}
# the wave path in the serial learner's order (one leaf a launch) and in f32
STRICT = {"tpu_wave_capacity": 1, "tpu_hist_dtype": "highest"}
CASES = ("binary_37_columns", "lambdarank_ragged_queries",
         "a_duplicated_column_ties_to_the_lower_index",
         "feature_fraction_0_7")
# integer-valued lines of a tree: the structure, to the bit
EXACT = ("num_leaves", "num_cat", "split_feature", "threshold",
         "decision_type", "left_child", "right_child", "leaf_count",
         "internal_count", "cat_boundaries", "cat_threshold", "shrinkage")


def _table(case, rows=2000, cols=37):
    rng = np.random.default_rng(7)
    X = rng.normal(size=(rows, cols))
    latent = X[:, 0] + X[:, 5] * X[:, cols - 7] + 0.3 * rng.normal(size=rows)
    params = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 20,
              "verbose": -1, "device_type": "tpu"}
    y, group = (latent > 0).astype(np.float64), None
    if case == "lambdarank_ragged_queries":
        group = []
        while sum(group) < rows:
            group.append(int(min(rng.integers(1, 60), rows - sum(group))))
        y = np.digitize(latent, np.quantile(latent, [0.5, 0.75, 0.9, 0.97]))
        params.update(objective="lambdarank", min_data_in_leaf=10)
    elif case == "a_duplicated_column_ties_to_the_lower_index":
        # blocks of 8 columns: column 0 is chip 0's, its copy chip 1's
        X[:, 20] = X[:, 0]
    elif case == "feature_fraction_0_7":
        params.update(feature_fraction=0.7, feature_fraction_seed=3)
    return X, y.astype(np.float64), group, params


def _fit(X, y, group, params, interpret, iters=2):
    with pytest.MonkeyPatch.context() as mp:
        if interpret:
            mp.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
        else:
            mp.delenv("LGBM_TPU_FORCE_WAVE", raising=False)
        ds = lgb.Dataset(X, label=y, group=group, params=params)
        bst = lgb.Booster(params=params, train_set=ds)
        for _ in range(iters):
            bst.update()
    return bst


def _trees(bst) -> str:
    return bst.model_to_string().split("\nparameters:\n")[0]


def _tree_lines(text):
    return [dict(line.split("=", 1) for line in block.splitlines()
                 if "=" in line) for block in text.split("\nTree=")[1:]]


def _same_splits(a: str, b: str, rtol=1e-5):
    """Every tree of ``a`` is ``b``'s: the structure exactly, every value
    (gains, outputs, weights) within ``rtol``."""
    ta, tb = _tree_lines(a), _tree_lines(b)
    assert len(ta) == len(tb) > 0
    for x, y in zip(ta, tb):
        assert x.keys() == y.keys()
        for k in x:
            if k in EXACT:
                assert x[k] == y[k], k
            elif k != "Tree":
                np.testing.assert_allclose(
                    np.array(x[k].split(), float),
                    np.array(y[k].split(), float), rtol=rtol, atol=1e-7,
                    err_msg=k)


@pytest.fixture(scope="module")
def boosters():
    """case -> the feature-parallel booster, the one-device wave path's,
    both at the trainer's plan, and the feature-parallel booster at the
    strict plan beside the serial XLA learner's."""
    out = {}
    for case in CASES:
        X, y, group, params = _table(case)
        out[case] = {
            "feature": _fit(X, y, group, {**params, **MESH}, True),
            "one": _fit(X, y, group, params, True),
            "strict": _fit(X, y, group, {**params, **MESH, **STRICT}, True),
            "xla": _fit(X, y, group, {**params, "device_type": "cpu"},
                        False)}
    return out


@pytest.mark.parametrize("case", CASES)
def test_feature_parallel_wave_grows_the_one_chip_trees(boosters, case):
    b = boosters[case]
    for name in ("feature", "strict"):
        plan = b[name]._gbdt._plan
        assert plan.wave and plan.learner == "feature" and plan.fused_sibling
        assert plan.reasons == ()
    assert not b["xla"]._gbdt.uses_wave
    # the trainer's plan: the one-device wave path's model, to the bit
    assert _trees(b["feature"]) == _trees(b["one"])
    # one leaf a launch in f32: the serial XLA learner's model
    _same_splits(_trees(b["strict"]), _trees(b["xla"]))
    if case == "a_duplicated_column_ties_to_the_lower_index":
        used = {int(f) for t in _tree_lines(_trees(b["feature"]))
                for f in t["split_feature"].split()}
        assert 0 in used and 20 not in used


def _counters_booster(cols):
    X, y, group, params = _table("binary_37_columns", rows=600, cols=cols)
    params.update(num_leaves=5, min_data_in_leaf=20)
    return _fit(X, y, group, {**params, **MESH}, True, iters=2)


# columns -> the fullest chip's padded kernel columns (blocks of 8 dealt
# over four chips as runs: 37 -> 2/1/1/1, 136 -> 5/4/4/4 as MSLR's; six
# columns are one block of six, and three chips hold none)
@pytest.mark.parametrize("cols,hist_columns", [(37, 16), (136, 40), (6, 6)])
def test_split_syncs_are_the_scans_and_hist_columns_the_fullest_chip(
        cols, hist_columns):
    bst = _counters_booster(cols)
    work = bst.work_counters()
    assert work["hist_columns"] == hist_columns
    assert work["kernel_columns"] == (-(-cols // 8) * 8 if cols > 8 else cols)
    assert work["chips"] == 1 and work["rows_per_chip"] == work["rows"]
    assert work["stamps"]["bins_devices"] == 4
    assert work["trees"]
    for tree in work["trees"]:
        # one sync a wave: every wave's children are scanned once, the
        # root's wave included
        assert tree["split_syncs"] == tree["waves"] > 0
        # every chip holds every row: the per-chip counts are one count
        for key in ("kernel_rows", "active_rows", "compact_waves"):
            assert len(tree[key]) == 4 and len(set(tree[key])) == 1
    # the one-device program has no sync, and scans every column
    X, y, group, params = _table("binary_37_columns", rows=600, cols=cols)
    params.update(num_leaves=5, min_data_in_leaf=20)
    one = _fit(X, y, group, params, True, iters=1).work_counters()
    assert one["hist_columns"] == one["kernel_columns"]
    assert all("split_syncs" not in t for t in one["trees"])


def _launch_inputs(F=20, N=2048, P=5, seed=0):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, 200, (F, N)).astype(np.uint8)
    g = rng.normal(size=N).astype(np.float32)
    h = rng.uniform(0.1, 1.0, N).astype(np.float32)
    c = np.ones(N, np.float32)
    leaf = rng.integers(0, P + 1, N).astype(np.int32)   # leaf P: no slot
    return bins, g, h, c, leaf


def _hist_ref(bins, g, h, c, leaf, P, B):
    """[P, F, B, 3] by NumPy: the histograms the launch has to give."""
    F = bins.shape[0]
    out = np.zeros((P, F, B, 3))
    for p in range(P):
        rows = leaf == p
        for f in range(F):
            for k, v in enumerate((g, h, c)):
                out[p, f, :, k] = np.bincount(bins[f, rows], v[rows],
                                              minlength=B)[:B]
    return out


def _per_leaf(res, mode, packed, P):
    if packed:
        return np.asarray(ph.unpack_lanes(res, mode, P))
    r = np.asarray(res)[..., :3 * P]
    return r.reshape(*r.shape[:2], P, 3).transpose(2, 0, 1, 3)


@pytest.mark.parametrize("mode,packed,fused", [
    ("2xbf16", True, True), ("2xbf16", True, False),
    ("highest", True, True), ("2xbf16", False, False)])
def test_the_benchmarks_launch_covers_every_column(mode, packed, fused):
    """``reducers/fullpass.py``'s call, keyword for keyword (the variant
    from ``select_wave_blocks``, every leaf lane in use, ``parent`` where
    the trainer fuses), against NumPy's histograms of every column."""
    B, P = 256, ph.wave_capacity_max(packed)
    bins, gv, hv, cv, leaf = _launch_inputs(P=P)
    F = bins.shape[0]
    _, fb = ph.select_wave_blocks(B, mode=mode, packed=packed, fused=fused,
                                  block_rows=1024)
    lanes = 2 if packed else 3
    slot = np.full(ph.C_MAX, -1, np.int32)
    slot[:lanes * P] = np.repeat(np.arange(P), lanes)
    parent = None
    if fused:
        par = jnp.asarray(np.random.default_rng(1).normal(
            size=(F, B, ph.C_MAX)), jnp.float32)
        parent = (par, par) if packed else par
    out = ph.hist_pallas_wave(
        jnp.asarray(bins), jnp.asarray(gv), jnp.asarray(hv), jnp.asarray(cv),
        jnp.asarray(leaf), jnp.asarray(slot), B=B, block_rows=1024,
        feat_block=fb, highest=mode, interpret=True, packed=packed,
        parent=parent)
    child = out[0] if fused else out
    got = _per_leaf(child, mode, packed, P)
    assert got.shape == (P, F, B, 3)
    np.testing.assert_allclose(got, _hist_ref(bins, gv, hv, cv, leaf, P, B),
                               rtol=1e-4, atol=1e-3)
    if fused:
        sib = out[1]
        want = np.asarray(jnp.concatenate(parent, -1) if packed else parent)
        have = np.asarray(jnp.concatenate(child, -1) if packed else child)
        np.testing.assert_array_equal(
            np.asarray(jnp.concatenate(sib, -1) if packed else sib),
            want - have)


# (first block, blocks owned, blocks a launch) of 20 columns in blocks of 8
# (three blocks, the last two columns short: padded to 24 here): a run
# inside, a run past the last block, a chip's one block, a chip with none
@pytest.mark.parametrize("start,owned,blocks", [(0, 2, 2), (1, 2, 3),
                                                (2, 1, 2), (3, 0, 1)])
@pytest.mark.parametrize("packed", [True, False])
def test_a_windowed_launch_is_its_blocks_of_the_full_launch(start, owned,
                                                            blocks, packed):
    B, P, FB, mode = 256, 5, 8, "2xbf16"
    bins, gv, hv, cv, leaf = _launch_inputs(F=24, P=P)
    lanes = 2 if packed else 3
    slot = np.full(ph.C_MAX, -1, np.int32)
    slot[:lanes * P] = np.repeat(np.arange(P), lanes)
    rng = np.random.default_rng(2)
    par = jnp.asarray(rng.normal(size=(24, B, ph.C_MAX)), jnp.float32)
    args = [jnp.asarray(a) for a in (bins, gv, hv, cv, leaf, slot)]
    kw = dict(B=B, block_rows=1024, feat_block=FB, highest=mode,
              interpret=True, packed=packed)
    full = ph.hist_pallas_wave(*args, **kw,
                               parent=(par, par) if packed else par)
    lo, hi = start * FB, (start + blocks) * FB
    mine = jnp.pad(par, ((0, max(0, hi - 24)), (0, 0), (0, 0)))[lo:hi]
    win = ph.hist_pallas_wave(*args, **kw,
                              parent=(mine, mine) if packed else mine,
                              col_blocks=blocks,
                              col_window=jnp.array([start, owned]))
    for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(win)):
        a, b = np.asarray(a), np.asarray(b)
        assert b.shape[0] == blocks * FB
        np.testing.assert_array_equal(b[:owned * FB],
                                      a[lo:lo + owned * FB])
        assert not b[owned * FB:].any()
