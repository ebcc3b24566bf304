"""``expo-cat-train`` at toy size on the CPU, kernel interpreted, through the
command itself: the categorical kind's control flow, its checks, and the line
against what ``BENCHMARK.json`` lists for the cell.

Run serially, as ``python -m pytest benchmarks/tests`` does: the traced
rehearsals of one cell share ``.bench_scratch/trace/<cell>``."""
import numpy as np
import pytest

from harness import cells, datagen_codes, datagen_onehot
from test_rehearsal import CONTRACT_KEYS, _rehearse

CELL = "expo-cat-train"
NEW = {"grower.cat_split_share"}                # needs no device
# read off the device trace: the new scope, and the benchmark's own launch of
# the trainer's kernel (over the narrow columns of the mixed-width pair)
DEVICE_ONLY = {"grower.cat_scan_ms_per_iter", "pallas_hist_wave_roofline",
               "pallas_hist_wave.mxu_charged_share"}


@pytest.mark.parametrize("traced", [0, 1])
def test_expo_cat_train_rehearses(traced):
    result, detail = _rehearse(CELL, traced)
    assert set(result) == CONTRACT_KEYS
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is False and result["metrics"] == {}
    assert result["attempted"] > 0 and result["failed"] == 0
    would = detail["would_print"]
    assert would["correct"] is True, detail["checks"]
    assert detail["counters"]["compiles_in_window"] == 0
    # (a) the path: a column wider than the kernel's 256 bins, so the
    # mixed-width plan; every timed tree split on category sets
    stamps = detail["checks"]["stamps"]
    assert stamps["uses_wave"] and stamps["fused_grad"]
    assert stamps["packed"] is False and stamps["fused_sibling"] is False
    assert stamps["bundled"] is False and stamps["wide_columns"] == 1
    assert stamps["categorical_features"] == 5
    cat = detail["checks"]["cat_splits"]
    assert max(cat["bins"].values()) > 256
    assert len(cat["timed"]) == result["attempted"]
    assert all(0 < t["cat_splits"] <= t["splits"] for t in cat["timed"])
    # (b) the export, over raw codes, rare values among them
    exp = detail["checks"]["export"]
    assert exp["max_rel_err"] <= 1e-5 and exp["cat_nodes"] > 0
    assert exp["rows_with_a_dropped_value"] > 0
    # the bin maps (c1) pools by are count-ordered maps of their sample
    maps = detail["checks"]["bin_maps"]
    assert set(maps["problems"]) == set(cat["bins"])
    assert not any(maps["problems"].values()), maps
    # (c1) the judged tree
    judge = detail["checks"]["judge"]
    assert judge["ok"] and judge["judged"] >= 1
    assert all(n["rows"] == n["rows_exported"] for n in judge["nodes"])
    # (c2) the oracle off the wave path, staged
    ora = detail["checks"]["oracle"]
    assert ora["same_root"] and not ora["oracle_uses_wave"]
    assert ora["score_med"] <= ora["score_med_max"]
    assert all(st["path_on_path"] for st in ora["stages"])
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
    # the benchmark's own launch ran the trainer's kernel: the narrow columns
    # of the pair at their own width, triple layout, sibling unfused
    fp = detail["fullpass"]
    assert fp["features"] == 7 - stamps["wide_columns"] and fp["B"] <= 256
    assert fp["packed"] is False and fp["fused"] is False
    assert len(fp["kernel_s"]) == 3
    work = detail["counters"]["work_counters"]
    share = 100.0 * sum(t["cat_splits"] for t in work["trees"]) \
        / sum(t["walks"] for t in work["trees"])
    assert would["metrics"]["grower.cat_split_share"]["value"] == share


def test_line_of_the_cell_carries_every_listed_metric():
    """A line with exactly what ``BENCHMARK.json`` lists for the cell lacks
    nothing; the two metrics this cell brought are asked of it alone, and
    the two read off the benchmark's own kernel launch of every cell."""
    from harness import line
    doc = cells.benchmark_doc()
    listed = line.listed_metrics(doc, CELL, True)
    assert NEW | DEVICE_ONLY <= set(listed)
    for other in ("higgs-train", "mslr-train", "higgs-dp4-train",
                  "expo-train", "higgs-goss-train"):
        theirs = set(line.listed_metrics(doc, other, True))
        assert not NEW & theirs and DEVICE_ONLY - theirs == {
            "grower.cat_scan_ms_per_iter"}
    good = {"correct": True, "attempted": 8, "failed": 0,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 7 * 10 ** 9, "busy_s": 3.9,
                       "window_s": 4.0},
            "metrics": {n: {"value": 1.0, "unit": u}
                        for n, u in listed.items()}}
    assert line.problems(doc, CELL, True, good) == []


def test_the_cell_trains_on_expos_table():
    """The configuration's data group is ``expo``'s but for the encoding: one
    seed, one table, as 700 one-hot columns there and 8 columns here."""
    expo = cells.load_cell("expo-train").config
    cat = cells.load_cell(CELL).config
    a, b = dict(expo["data"]), dict(cat["data"])
    assert b.pop("encoding") == "codes"
    assert (a.pop("features"), b.pop("features")) == (700, 8)
    assert a == b
    assert cat["params"]["categorical_feature"] == \
        datagen_codes.categorical_columns(cat["data"])
    for k, v in expo["params"].items():
        assert cat["params"][k] == v
    Xs, ys, _ = datagen_onehot.make_table(expo["data"], 5, rows=2000)
    Xc, yc, _ = datagen_codes.make_table(cat["data"], 5, rows=2000)
    off = datagen_onehot.column_offsets(expo["data"])
    np.testing.assert_array_equal(ys, yc)
    np.testing.assert_array_equal(
        Xs.indices.reshape(2000, 8)[:, :6] - off[:6], Xc[:, :6])
    np.testing.assert_array_equal(Xs.data.reshape(2000, 8)[:, 6:], Xc[:, 6:])
