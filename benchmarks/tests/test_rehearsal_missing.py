"""``bosch-train`` at toy size on the CPU, kernel interpreted, through the
command itself: the missing-value kind's control flow, each of its checks
(and each failing where it should), the line against what ``BENCHMARK.json``
lists for the cell, and the table ``harness/datagen_missing.py`` makes.

Run serially, as ``python -m pytest benchmarks/tests`` does: the traced
rehearsals of one cell share ``.bench_scratch/trace/<cell>``."""
import copy

import numpy as np
import pytest

from harness import cells, datagen_missing, reference_missing
from test_rehearsal import CONTRACT_KEYS, _rehearse

CELL = "bosch-train"
FACTS = {"kernel_bins": 64, "feat_pack": 2, "feat_block": 104,
         "kernel_columns": 104}
NEW = {"grower.missing_split_share"}            # needs no device
# read off the device trace, or the benchmark's own launch of the kernel
DEVICE_ONLY = {"grower.scan_ns_per_bin", "pallas_hist_wave_roofline",
               "pallas_hist_wave.mxu_charged_share"}


@pytest.mark.parametrize("traced", [0, 1])
def test_bosch_train_rehearses(traced):
    result, detail = _rehearse(CELL, traced)
    assert set(result) == CONTRACT_KEYS
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is False and result["metrics"] == {}
    assert result["attempted"] > 0 and result["failed"] == 0
    would = detail["would_print"]
    assert would["correct"] is True, detail["checks"]
    assert detail["counters"]["compiles_in_window"] == 0
    checks = detail["checks"]
    # (a) the 64-lane packed kernel, and no bundle
    assert checks["stamps"]["uses_wave"] and checks["stamps"]["packed"]
    assert checks["facts"] == FACTS and checks["bundled"] is False
    # (b) the walk over rows with NaN, and the hessian bound
    exp = checks["export"]
    assert exp["max_rel_err"] <= exp["tol"] == 2e-6
    assert exp["nan_share"] >= exp["nan_share_min"] > 0
    assert exp["min_leaf_weight"] >= exp["leaf_weight_floor"] > 0
    # (c) staged against the serial grower
    ora = checks["oracle"]
    assert ora["ok"] and ora["same_root"] and not ora["leaf_cap_binds"]
    assert all(st["path_on_path"] and not st["oracle_uses_wave"]
               for st in ora["stages"])
    # (d) the NumPy grower: the same root, no node that parts unless at a tie
    gro = checks["grower"]
    assert gro["ok"] and gro["root_same"] and gro["differ"] == []
    assert gro["same"] > gro["tied"] and gro["leaf_err"] <= gro["leaf_rtol"]
    # (e) every timed split has a default side
    miss = checks["missing_splits"]
    assert miss["ok"] and len(miss["timed"]) == result["attempted"]
    assert all(t["missing_splits"] == t["walks"] > 0 for t in miss["timed"])
    # (f) one more tree of the timed trainer, judged on every row
    jud = checks["judge"]
    assert jud["ok"] and jud["rows"] == 8192 and jud["leaf_counts_ok"]
    assert jud["nodes"] == min(15, jud["of"]) and jud["differ"] == []
    assert jud["gain_err"] <= jud["gain_rtol"]
    assert jud["leaf_err"] <= jud["leaf_rtol"]
    names = set(would["metrics"])
    if not traced:
        assert names == {"setup_s", "train_row_iters_per_s"}
        assert detail["line_lacks"] == []
        return
    assert NEW <= names and not DEVICE_ONLY & names
    lacking = {w.split()[1] for w in detail["line_lacks"]
               if w.startswith("metric ")}
    src = {m["name"]: m["source"] for m in cells.benchmark_doc()["per_layer"]}
    assert DEVICE_ONLY <= lacking and not lacking & names
    assert all(src[n] != "host_clock" for n in lacking), lacking
    assert would["metrics"]["grower.missing_split_share"]["value"] == 100.0
    work = detail["counters"]["work_counters"]
    nb = work["trees"][0]["scan_bins"] // (2 * work["trees"][0]["walks"] + 1)
    assert all(t["scan_bins"] == (2 * t["walks"] + 1) * nb
               for t in work["trees"])


def _toy():
    return cells.load_cell(CELL, True).config


def test_check_a_ends_a_program_without_the_counters(monkeypatch):
    from kinds import boost_missing

    from lightgbm_tpu.boosting import gbdt
    boost_missing._has_counters()
    monkeypatch.delattr(gbdt, "TREE_COUNTS")
    with pytest.raises(SystemExit, match="missing_splits"):
        boost_missing._has_counters()


def test_check_b_walk_refuses_a_flipped_default_side():
    """The walk honours ``decision_type``: the same model with its
    default-left bits flipped sends the NaN rows the other way."""
    import lightgbm_tpu as lgb
    cfg = _toy()
    X, y, _ = datagen_missing.make_table(cfg["data"], 3)
    p = {**cfg["params"], "verbose": -1, "device_type": "cpu"}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p), 3)
    text = bst.model_to_string()
    raw = bst.predict(X, raw_score=True)
    ok = reference_missing.predict_raw(
        reference_missing.parse_model_string(text), X)
    assert np.max(np.abs(ok - raw)) < 1e-9
    flipped = reference_missing.parse_model_string(text)
    for t in flipped:
        t["decision_type"] = t["decision_type"] ^ 2
    bad = reference_missing.predict_raw(flipped, X)
    assert np.max(np.abs(bad - raw)) > 1e-3


def test_check_c_takes_a_mirror_root_and_refuses_another_cut():
    from kinds import boost_missing
    X = np.full((100, 2), np.nan, np.float32)
    X[:60, 0] = 0.5
    X[30:, 1] = 0.25

    def tree(f, thr, dl):
        return {"num_leaves": 2, "split_feature": np.array([f]),
                "threshold": np.array([thr]),
                "decision_type": np.array([8 | (2 if dl else 0)])}
    nan_left, values_left = tree(0, 1e-35, True), tree(0, 1.0, False)
    assert boost_missing._same_root(nan_left, values_left, X)
    assert not boost_missing._same_root(nan_left, tree(1, 1.0, False), X)
    stump = {"num_leaves": 1}
    assert boost_missing._same_root(stump, stump, X)
    assert not boost_missing._same_root(stump, nan_left, X)


def test_check_d_refuses_one_bf16_pass(monkeypatch):
    """One bf16 pass (g and h rounded to 8 bits) through (d) at toy size:
    the NumPy grower's leaf values part from it by more than
    ``grower.leaf_rtol``; 2xbf16 stays inside."""
    from kinds import boost_missing
    monkeypatch.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    cfg = copy.deepcopy(_toy())
    params = {"verbose": -1, **cfg["params"]}
    want = {**cfg["stamps"], **cfg["facts"], "interpret": True}
    honest = boost_missing.grower_check(cfg, params, want, 5)
    assert honest["ok"], honest
    cfg["params"]["tpu_hist_dtype"] = "bf16"
    bf16 = boost_missing.grower_check(
        cfg, {**params, "tpu_hist_dtype": "bf16"},
        {**want, "hist_mode": "bf16"}, 5)
    assert bf16["path_on_path"] and not bf16["ok"]
    assert bf16["leaf_err"] > 10 * honest["leaf_err"]
    assert honest["leaf_err"] <= honest["leaf_rtol"]


def test_check_e_refuses_a_split_without_a_default_side():
    from kinds import boost_missing
    t = {"iteration": 2, "walks": 30, "missing_splits": 30, "scan_bins": 9}
    assert boost_missing.missing_check([t])["ok"]
    assert not boost_missing.missing_check([t, {**t, "missing_splits": 29}])[
        "ok"]
    assert not boost_missing.missing_check([])["ok"]
    assert not boost_missing.missing_check(
        [{**t, "walks": 0, "missing_splits": 0}])["ok"]


def _judged(cfg, dtype=None):
    """One more tree after four of the wave path (interpreted) on the toy
    table, with the scores it was grown from, and the table binned."""
    import lightgbm_tpu as lgb
    X, y, _ = datagen_missing.make_table(cfg["data"], 7)
    p = {**cfg["params"], "verbose": -1,
         **({"tpu_hist_dtype": dtype} if dtype else {})}
    bst = lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
    for _ in range(4):
        bst.update()
    score = np.asarray(bst._raw_train_score(), np.float64)
    bst.update()
    tree = reference_missing.parse_model_string(bst.model_to_string())[-1]
    tb = reference_missing.bin_table(X, int(p["max_bin"]))
    return p, tb, y, score, tree


def test_check_f_refuses_a_moved_cut_gain_or_leaf(monkeypatch):
    """(f) on the toy table: the tree as grown passes; the same tree with
    its default sides flipped, its first gain moved by a part in a
    thousand, or its fullest leaf's value by a hundredth of the largest,
    does not."""
    from kinds import boost_missing
    monkeypatch.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    cfg = _toy()
    p, tb, y, score, tree = _judged(cfg)
    honest = boost_missing.judge_check(cfg, p, tb, y, score, tree)
    assert honest["ok"] and honest["same"] == honest["nodes"], honest
    flipped = {**tree, "decision_type": tree["decision_type"] ^ 2}
    res = boost_missing.judge_check(cfg, p, tb, y, score, flipped)
    assert not res["ok"] and res["differ"] and not res["leaf_counts_ok"]
    gain = tree["split_gain"].copy()
    gain[0] *= 1.001
    res = boost_missing.judge_check(cfg, p, tb, y, score,
                                    {**tree, "split_gain": gain})
    assert not res["ok"] and res["gain_err"] > res["gain_rtol"]
    leaf = tree["leaf_value"].copy()
    leaf[int(np.argmax(tree["leaf_count"]))] += 0.01 * np.abs(leaf).max()
    res = boost_missing.judge_check(cfg, p, tb, y, score,
                                    {**tree, "leaf_value": leaf})
    assert not res["ok"] and res["leaf_err"] > res["leaf_rtol"]


def test_check_f_refuses_one_bf16_pass(monkeypatch):
    """One bf16 pass (g and h rounded to 8 bits) through (f) at toy size:
    the judged gains part from the float64 sums by more than
    ``judge.gain_rtol``, and the leaf values by more than 2xbf16's; 2xbf16
    stays inside both limits."""
    from kinds import boost_missing
    monkeypatch.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    cfg = _toy()
    honest = boost_missing.judge_check(cfg, *_judged(cfg))
    bf16 = boost_missing.judge_check(cfg, *_judged(cfg, "bf16"))
    assert honest["ok"] and not bf16["ok"]
    assert bf16["gain_err"] > bf16["gain_rtol"] > honest["gain_err"]
    assert bf16["leaf_err"] > 10 * honest["leaf_err"]
    assert honest["leaf_err"] <= honest["leaf_rtol"]


# ---------------------------------------------------------------------------
# the table
# ---------------------------------------------------------------------------

def test_table_has_the_configurations_shape_nan_share_and_positives():
    spec = dict(cells.load_cell(CELL).config["data"], rows=40000)
    X, y, sizes = datagen_missing.make_table(spec, 2 ** 31 + 7)
    assert sizes is None and X.shape == (40000, 968)
    assert X.dtype == np.float32 and X.flags.c_contiguous
    assert abs(float(np.isnan(X).mean()) - 0.81) <= 0.01
    # the positives are the whole table's top 0.581%: 6,879 of 1,183,747
    full = dict(spec, rows=1183747)
    lay = datagen_missing.layout(full)
    assert len(lay["line"]) == 52 and len(lay["station"]) == 968
    assert [int(np.sum(lay["line"][lay["station"]] == li))
            for li in range(4)] == [168, 513, 42, 245]
    assert abs(float(y.mean()) - 0.005811) < 0.002
    k = np.asarray([len(g) for g in lay["grid"]])
    assert k.min() >= 2 and k.max() <= 61
    # every value positive and on its column's grid; a station's columns
    # are NaN together but for the dropout
    vals = X[~np.isnan(X)]
    assert vals.min() > 0
    st = lay["station"]
    first = np.asarray([np.flatnonzero(st == s)[0] for s in range(52)])
    same = np.isnan(X[:, first[st]]) == np.isnan(X)
    assert same.mean() > 0.97


def test_same_seed_same_table_other_seed_other_values_same_labels():
    spec = dict(_toy()["data"], rows=6000)
    X1, y1, _ = datagen_missing.make_table(spec, 11)
    X1b, y1b, _ = datagen_missing.make_table(spec, 11)
    X2, y2, _ = datagen_missing.make_table(spec, 12)
    assert np.array_equal(X1, X1b, equal_nan=True)
    assert np.array_equal(y1, y1b) and np.array_equal(y1, y2)
    # the routes (the NaN mask) are the configuration's, the values the seed's
    assert np.array_equal(np.isnan(X1), np.isnan(X2))
    assert not np.array_equal(X1, X2, equal_nan=True)
    # a slice is a prefix
    Xs, ys, _ = datagen_missing.make_table(spec, 11, rows=2500)
    assert np.array_equal(Xs, X1[:2500], equal_nan=True)
    assert np.array_equal(ys, y1[:2500])


def test_result_does_not_depend_on_threads(monkeypatch):
    from harness import datagen
    spec = dict(_toy()["data"], rows=40000)
    X1, _, _ = datagen_missing.make_table(spec, 3)
    monkeypatch.setattr(datagen, "_threads", lambda: 1)
    X2, _, _ = datagen_missing.make_table(spec, 3)
    assert np.array_equal(X1, X2, equal_nan=True)
