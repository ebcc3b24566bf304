"""``expo-train`` at toy size on the CPU, kernel interpreted, through the
command itself: the CSR kind's control flow, its three checks, and the line
against what ``BENCHMARK.json`` lists for the cell.

Run serially, as ``python -m pytest benchmarks/tests`` does: the traced
rehearsals of one cell share ``.bench_scratch/trace/<cell>``."""
import pytest

from harness import cells
from test_rehearsal import CONTRACT_KEYS, _rehearse

CELL = "expo-train"
NEW = {"dataset.efb_features_per_column"}       # needs no device
DEVICE_ONLY = {"grower.efb_expand_ms_per_iter",
               "grower.hist_state_ms_per_iter"}


@pytest.mark.parametrize("traced", [0, 1])
def test_expo_train_rehearses(traced):
    result, detail = _rehearse(CELL, traced)
    assert set(result) == CONTRACT_KEYS
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is False and result["metrics"] == {}
    assert result["attempted"] > 0 and result["failed"] == 0
    would = detail["would_print"]
    assert would["correct"] is True, detail["checks"]
    assert detail["counters"]["compiles_in_window"] == 0
    # (a) the path: bundled, so the sibling is subtracted outside the kernel
    stamps = detail["checks"]["stamps"]
    assert stamps["bundled"] is True and stamps["fused_sibling"] is False
    assert stamps["uses_wave"] and stamps["packed"] and stamps["fused_grad"]
    # (b) the export, over the sample as EFB shows it
    exp = detail["checks"]["export"]
    assert exp["max_rel_err"] <= 1e-5
    assert 0 <= exp["conflict_share"] < exp["conflict_share_max"]
    assert detail["checks"]["phys_columns"] < 15
    assert detail["checks"]["columns_in_bundles"] >= 50
    # (c) the oracle ran unbundled, off the wave path
    ora = detail["checks"]["oracle"]
    assert ora["same_root"] and not ora["oracle_uses_wave"]
    assert ora["oracle_bundled"] is False
    assert ora["score_med"] <= ora["score_med_max"]
    names = set(would["metrics"])
    if not traced:
        assert names == {"setup_s", "train_row_iters_per_s"}
        assert detail["line_lacks"] == []
        return
    # traced: what the CPU cannot give is all the line lacks
    assert NEW <= names and not DEVICE_ONLY & names
    lacking = {w.split()[1] for w in detail["line_lacks"]
               if w.startswith("metric ")}
    src = {m["name"]: m["source"] for m in cells.benchmark_doc()["per_layer"]}
    assert DEVICE_ONLY <= lacking and not lacking & names
    assert all(src[n] != "host_clock" for n in lacking), lacking
    work = detail["counters"]["work_counters"]
    assert work["bundled"] is True and work["features"] == 62
    assert would["metrics"]["dataset.efb_features_per_column"]["value"] == \
        62 / work["phys_columns"]
    assert {**work["stamps"], "bundled": work["bundled"]} == stamps
    assert detail["fullpass"]["features"] == work["phys_columns"]
    assert detail["fullpass"]["fused"] is False


def test_line_of_the_cell_carries_every_listed_metric():
    """A line with exactly what ``BENCHMARK.json`` lists for the cell lacks
    nothing; the three metrics this cell brought are asked of it alone."""
    from harness import line
    doc = cells.benchmark_doc()
    listed = line.listed_metrics(doc, CELL, True)
    assert NEW | DEVICE_ONLY <= set(listed)
    assert "mesh.collective_ms_per_iter" not in listed
    for other in ("higgs-train", "mslr-train", "higgs-dp4-train"):
        assert not (NEW | DEVICE_ONLY) & set(
            line.listed_metrics(doc, other, True))
    good = {"correct": True, "attempted": 4, "failed": 0,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 8 * 10 ** 9, "busy_s": 1.5,
                       "window_s": 2.0},
            "metrics": {n: {"value": 1.0, "unit": u}
                        for n, u in listed.items()}}
    assert line.problems(doc, CELL, True, good) == []
