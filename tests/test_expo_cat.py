"""Declared categorical columns held to the benchmark's plain reference
(``benchmarks/harness/reference_cat.py``: NumPy, float64, nothing of the
program in it), at toy size on the CPU with the kernel interpreted: the walk
of an exported model, the Fisher sorted-set search against
``core/splitter.py _categorical_best``, the judge of a wave-grown tree, the
counter ``cat_splits``, and the table of the ``expo`` / ``expo-cat``
configurations in its two encodings.
"""
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.core import splitter
from lightgbm_tpu.core.meta import DeviceMeta, SplitConfig

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import datagen_codes, datagen_onehot  # noqa: E402
from harness import reference_cat as rc  # noqa: E402

CATS = [0, 1, 2]


def _table(seed: int, n: int = 5000):
    """Three declared columns (12 values all kept; 80 Zipf values, the rare
    ones dropped by the bin map; 3 values, the one-hot branch) and two
    numeric ones."""
    rng = np.random.default_rng(seed)
    c0 = rng.integers(0, 12, n)
    c1 = np.minimum(rng.zipf(1.3, n) - 1, 79)
    c2 = rng.integers(0, 3, n)
    x = rng.normal(size=(n, 2))
    e0, e1, e2 = (rng.normal(size=k) for k in (12, 80, 3))
    y = ((e0[c0] + e1[c1] + e2[c2] + x[:, 0]
          + 0.5 * rng.normal(size=n)) > 0).astype(np.float64)
    return np.column_stack([c0, c1, c2, x]).astype(np.float32), y


def _params(**extra):
    return {"objective": "binary", "num_leaves": 31, "min_data_in_leaf": 20,
            "min_data_per_group": 50, "max_bin": 63, "verbose": -1,
            "categorical_feature": CATS, **extra}


# ---------------------------------------------------------------------------
# (i) the walk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_walk_matches_predict_with_nan_negative_and_unseen(seed):
    X, y = _table(seed, 3000)
    p = _params(device_type="cpu")
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p), 5)
    trees = rc.parse_model_string(bst.model_to_string())
    assert sum(t["num_cat"] for t in trees) > 0
    assert any((t["decision_type"] & 1 == 0).any() for t in trees)
    Xt = X.copy()
    Xt[::7, 1] = np.nan
    Xt[::11, 0] = -3
    Xt[::13, 1] = 500           # a value no bin map has seen
    Xt[::17, 3] = np.nan        # a numeric column's missing value
    np.testing.assert_allclose(rc.walk(trees, Xt),
                               bst.predict(Xt, raw_score=True),
                               rtol=0, atol=1e-9)


# ---------------------------------------------------------------------------
# (ii) the search
# ---------------------------------------------------------------------------

def _cat_meta(nb: int, all_kept: bool) -> DeviceMeta:
    one = lambda v, t: jnp.asarray([v], t)          # noqa: E731
    return DeviceMeta(num_bins=one(nb, jnp.int32),
                      default_bins=one(0, jnp.int32),
                      missing_types=one(0 if all_kept else 2, jnp.int32),
                      monotone=one(0, jnp.int32),
                      penalties=one(1.0, jnp.float32),
                      is_categorical=one(True, bool))


def _unpack(words, B):
    return tuple(b for b in range(B) if (int(words[b // 32]) >> (b % 32)) & 1)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("branch", ["onehot", "sorted", "sorted_groups"])
def test_fisher_best_matches_the_program(seed, branch):
    """``fisher_best`` against ``_categorical_best`` (through ``best_split``)
    on seeded histograms: the one-hot branch, the sorted scan, and the sorted
    scan with the ``min_data_per_group`` counter binding."""
    rng = np.random.default_rng([seed, len(branch)])
    B = 32
    per_group = 60 if branch == "sorted_groups" else 5
    ref_p = {"min_data_in_leaf": 3, "min_sum_hessian_in_leaf": 1e-3,
             "min_data_per_group": per_group, "cat_smooth": 2.0,
             "cat_l2": 1.0, "max_cat_to_onehot": 4, "max_cat_threshold": 32}
    cfg = SplitConfig(num_leaves=31, **ref_p)
    found = bound = ties = 0
    for trial in range(8):
        nb = int(rng.integers(2, 5) if branch == "onehot"
                 else rng.integers(6, B + 1))
        all_kept = bool(trial % 2)
        c = np.zeros(B)
        g = np.zeros(B)
        h = np.zeros(B)
        c[:nb] = rng.integers(0, 30, size=nb).astype(float)
        g[:nb] = rng.normal(size=nb) * c[:nb] * 0.1
        h[:nb] = c[:nb] * (0.2 + 0.1 * rng.random(nb))
        want_set, want_gain = rc.fisher_best(g[:nb], h[:nb], c[:nb], ref_p,
                                             all_kept)
        hist = jnp.asarray(np.stack([g, h, c], axis=-1)[None], jnp.float32)
        bs = splitter.best_split(
            hist, jnp.float32(g.sum()), jnp.float32(h.sum()),
            jnp.float32(c.sum()), _cat_meta(nb, all_kept), cfg,
            jnp.float32(-np.inf), jnp.float32(np.inf))
        if want_set is None:
            assert float(bs.gain) == -np.inf, (trial, float(bs.gain))
            continue
        found += 1
        np.testing.assert_allclose(float(bs.gain), want_gain, rtol=2e-4)
        got_set = _unpack(np.asarray(bs.cat_bitset), B)
        if got_set != want_set:
            # only an exact tie may fall the other way (two categories of a
            # one-hot column part the rows the same way from either side)
            np.testing.assert_allclose(
                rc.set_gain(g[:nb], h[:nb], c[:nb], got_set, ref_p),
                want_gain, rtol=1e-9, err_msg=f"{trial}: {got_set}")
            ties += 1
        loose = rc.fisher_best(g[:nb], h[:nb], c[:nb],
                               {**ref_p, "min_data_per_group": 1}, all_kept)
        bound += loose[0] != want_set
    assert found >= 3 and ties <= 1
    if branch == "sorted_groups":
        assert bound >= 1       # the counter changed what was found


def test_ratios_that_tie_are_tried_in_either_order():
    """Bins 1 and 2 have one ratio to 1e-12 and stand at the edge of the
    winning set: the stable order scans 1 (39 rows) before 2 (6 rows) and
    finds {3, 4}; the other order, which a sort on sums rounded another way
    has as well, finds {2, 3, 4} at another gain.  Both are the reference's
    search (its ``std::sort`` leaves equal ratios in any order);
    ``tied_orders`` yields the second, and nothing where no ratio ties."""
    p = {"min_data_in_leaf": 1, "min_data_per_group": 1, "cat_smooth": 1.0,
         "cat_l2": 0.0, "min_sum_hessian_in_leaf": 0.0}
    h = np.asarray([0.5, 9.75, 1.5, 3.75, 2.75, 2.75])
    c = np.asarray([2.0, 39.0, 6.0, 15.0, 11.0, 11.0])
    g = np.asarray([0.4, 0.0, -1.7, -5.3, -3.6, -1.2])
    g[1] = g[2] / (h[2] + 1.0) * (h[1] + 1.0) * (1 + 1e-12)
    s0, g0 = rc.fisher_best(g, h, c, p, True)
    assert s0 == (3, 4)
    orders = list(rc.tied_orders(g, h, c, p, True, about={2}, eps=1e-9))
    assert len(orders) == 1 and sorted(orders[0]) == list(range(6))
    s1, g1 = rc.fisher_best(g, h, c, p, True, order=orders[0])
    assert s1 == (2, 3, 4) and abs(g1 - g0) > 0.1 * g0
    np.testing.assert_allclose(g1, rc.set_gain(g, h, c, s1, p), rtol=1e-12)
    assert not list(rc.tied_orders(g, h, c, p, True, about={5}, eps=1e-9))
    g[1] *= 1.001
    assert not list(rc.tied_orders(g, h, c, p, True, about={2}, eps=1e-9))


# ---------------------------------------------------------------------------
# (iii) the judge, on wave-grown trees
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def grown():
    """``{(seed, mode): (tree, X, y, scores before it, bin maps, params,
    work counters, model text)}``: three warm iterations, then the judged
    one, on the wave path with the kernel interpreted."""
    mp = pytest.MonkeyPatch()
    mp.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    out = {}
    try:
        for seed in (0, 1):
            X, y = _table(seed)
            for mode in ("2xbf16", "bf16"):
                p = _params(device_type="tpu", tpu_hist_dtype=mode)
                ds = lgb.Dataset(X, label=y, params=p)
                bst = lgb.Booster(params=p, train_set=ds)
                for _ in range(3):
                    bst.update()
                score = bst._raw_train_score().copy()
                bst.update()
                text = bst.model_to_string()
                out[seed, mode] = (rc.parse_model_string(text)[-1], X, y,
                                   score, ds.categorical_bins(), p,
                                   bst.work_counters(), text)
    finally:
        mp.undo()
    return out


@pytest.mark.parametrize("seed", [0, 1])
def test_judge_passes_a_wave_grown_tree(grown, seed):
    tree, X, y, score, maps, p, work, _ = grown[seed, "2xbf16"]
    assert work["stamps"]["uses_wave"] and work["stamps"]["interpret"]
    assert maps[1]["all_kept"] is False and maps[0]["all_kept"] is True
    v = rc.judge(tree, X, y, score, maps, p, nodes=8, gain_rtol=2e-4,
                 gain_med_rtol=5e-5)
    assert v["judged"] == 8 and v["ok"], v
    assert v["gain_err_med"] <= v["gain_err_max"] < 1e-4
    assert all(r["rows"] == r["rows_exported"] for r in v["nodes"])
    # the median over some columns' nodes only, which have to be there
    cols = [r["column"] for r in v["nodes"]]
    some = {cols[0]}
    kw = dict(nodes=8, gain_rtol=2e-4, gain_med_rtol=5e-5, med_columns=some)
    w = rc.judge(tree, X, y, score, maps, p, **kw, med_nodes_min=1)
    assert w["ok"] and w["med_nodes"] == cols.count(cols[0])
    assert w["gain_err_med"] == np.median(
        [r["gain_err"] for r in w["nodes"] if r["column"] in some])
    assert not rc.judge(tree, X, y, score, maps, p, **kw,
                        med_nodes_min=w["med_nodes"] + 1)["ok"]


@pytest.mark.parametrize("seed", [0, 1])
def test_judge_fails_a_left_set_altered_by_one_category(grown, seed):
    tree, X, y, score, maps, p, _, _ = grown[seed, "2xbf16"]
    node = rc.categorical_nodes(tree, 1)[0]
    col = int(tree["split_feature"][node])
    inside = rc.node_set(tree, node)
    moved = next(v for v in maps[col]["values"][:-1]
                 if v >= 0 and v not in inside)
    bad = {**tree, "cat_threshold": tree["cat_threshold"].copy()}
    lo = int(tree["cat_boundaries"][int(tree["threshold"][node])])
    hi = int(tree["cat_boundaries"][int(tree["threshold"][node]) + 1])
    if moved // 32 >= hi - lo:      # no word for it: take one out instead
        moved = max(inside)
    bad["cat_threshold"][lo + moved // 32] ^= np.uint64(1 << (moved % 32))
    assert rc.node_set(bad, node) ^ inside == {moved}
    v = rc.judge(bad, X, y, score, maps, p, nodes=8, gain_rtol=2e-4)
    assert not v["ok"]
    first = v["nodes"][0]
    assert first["node"] == node and not first["same_set"]
    assert not first["pass"] and first["tie_err"] > 2e-4


@pytest.mark.parametrize("seed", [0, 1])
def test_judge_fails_gradients_rounded_to_bf16(grown, seed):
    """One bf16 histogram pass (g, h rounded to 8 mantissa bits: the nearest
    precision below the configuration's 2xbf16) fails the gain tolerance."""
    tree, X, y, score, maps, p, _, _ = grown[seed, "bf16"]
    v = rc.judge(tree, X, y, score, maps, p, nodes=8, gain_rtol=2e-4,
                 gain_med_rtol=5e-5)
    assert v["judged"] == 8 and not v["ok"]
    assert v["gain_err_max"] > 1e-3 and v["gain_err_med"] > 5e-5
    # by the median alone too, whatever a node may read, and by the median
    # over the first node's column alone
    assert not rc.judge(tree, X, y, score, maps, p, nodes=8, gain_rtol=1.0,
                        gain_med_rtol=5e-5)["ok"]
    assert not rc.judge(tree, X, y, score, maps, p, nodes=8, gain_rtol=1.0,
                        gain_med_rtol=5e-5,
                        med_columns={v["nodes"][0]["column"]})["ok"]


@pytest.mark.parametrize("seed", [0, 1])
def test_cat_splits_counts_the_categorical_nodes(grown, seed):
    *_, work, text = grown[seed, "2xbf16"]
    assert work["categorical_features"] == 3 and work["wide_columns"] == 0
    trees = rc.parse_model_string(text)
    assert len(trees) == len(work["trees"]) == 4
    for tree, c in zip(trees, work["trees"]):
        assert c["cat_splits"] == int((tree["decision_type"] & 1).sum()) > 0
        assert c["cat_splits"] < c["walks"] == tree["num_leaves"] - 1


# ---------------------------------------------------------------------------
# the table in its two encodings
# ---------------------------------------------------------------------------

SPEC = {"task": "binary", "rows": 3000, "features": 40,
        "variables": [{"name": "a", "cardinality": 5, "zipf": 0.3,
                       "effect": 0.4},
                      {"name": "b", "cardinality": 33, "zipf": 1.0,
                       "effect": 0.5}],
        "numeric": 2, "numeric_effect": 0.7, "loading": 0.5, "signal": 1.0,
        "label_noise": 1.0, "label_seed": 28}


@pytest.mark.parametrize("seed", [5, 2147483659])
def test_the_two_encodings_are_one_table(seed):
    Xs, ys, _ = datagen_onehot.make_table(SPEC, seed)
    codes_spec = {**SPEC, "features": 4, "encoding": "codes"}
    Xc, yc, _ = datagen_codes.make_table(codes_spec, seed)
    assert datagen_codes.categorical_columns(codes_spec) == [0, 1]
    np.testing.assert_array_equal(ys, yc)
    dense = Xs.toarray()
    off = datagen_onehot.column_offsets(SPEC)
    for v in range(2):
        block = dense[:, off[v]:off[v + 1]]
        assert (block.sum(axis=1) == 1).all()
        np.testing.assert_array_equal(block.argmax(axis=1), Xc[:, v])
    np.testing.assert_array_equal(dense[:, off[-1]:], Xc[:, 2:])
    short = datagen_codes.make_table(codes_spec, seed, rows=700)[0]
    np.testing.assert_array_equal(short, Xc[:700])
    with pytest.raises(ValueError):
        datagen_codes.make_table(SPEC, seed)


# ---------------------------------------------------------------------------
# (iv) the bin map the judge pools by
# ---------------------------------------------------------------------------

def _maps(case: str, seed: int):
    """A table whose declared columns' maps go every way the reference's
    loop can end, its program-made bin maps, and the rows they were found
    from."""
    from lightgbm_tpu.utils.random import Random
    X, y = _table(seed, 6000)
    if case == "missing":
        X[::7, 0] = np.nan
        X[::11, 1] = -2.0
    p = _params(device_type="cpu", max_bin=63 if case != "narrow" else 16,
                bin_construct_sample_cnt=6000 if case != "sampled" else 2500)
    maps = lgb.Dataset(X, label=y, params=p).categorical_bins()
    cnt = p["bin_construct_sample_cnt"]
    rows = np.arange(len(y)) if cnt >= len(y) else Random(1).sample(len(y), cnt)
    return X[rows], maps, p["max_bin"]


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("case", ["plain", "missing", "narrow", "sampled"])
def test_the_programs_bin_maps_are_count_ordered(case, seed):
    sample, maps, max_bin = _maps(case, seed)
    assert sorted(maps) == CATS
    for col, bm in maps.items():
        assert rc.bin_map_problems(sample[:, col], bm["values"],
                                   bm["all_kept"], max_bin) == [], (col, bm)
    # the Zipf column keeps going past max_bin until 99% is covered, and
    # still drops its rarest values
    if case != "sampled":
        assert max_bin < len(maps[1]["values"]) < 80
        assert not maps[1]["all_kept"]
    assert maps[0]["all_kept"] == (case != "missing")
    assert (maps[0]["values"][-1] == -1) == (case == "missing")


@pytest.mark.parametrize("fault", ["capped_at_max_bin", "one_more_kept",
                                   "two_swapped", "rare_one_listed",
                                   "all_kept_flag", "category_0_first"])
def test_bin_map_problems_names_a_wrong_map(fault):
    sample, maps, max_bin = _maps("plain", 0)
    col = sample[:, 1]
    vals, kept = list(maps[1]["values"]), maps[1]["all_kept"]
    unlisted = sorted(set(col.astype(int)) - set(vals))
    if fault == "capped_at_max_bin":      # what ISSUE 34 took the map to be
        vals = vals[:max_bin]
    elif fault == "one_more_kept":
        vals.append(unlisted[0])
    elif fault == "two_swapped":          # a frequent one behind a rare one
        vals[1], vals[-1] = vals[-1], vals[1]
    elif fault == "rare_one_listed":      # in place of a more frequent one
        vals[5] = unlisted[-1]
    elif fault == "all_kept_flag":
        kept = not kept
    elif fault == "category_0_first":
        i = vals.index(0)
        vals[0], vals[i] = vals[i], vals[0]
    assert rc.bin_map_problems(col, vals, kept, max_bin) != []
