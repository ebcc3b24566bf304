"""``higgs-goss-train`` at toy size on the CPU, kernel interpreted, through
the command itself: the GOSS kind's control flow, its checks, and the line
against what ``BENCHMARK.json`` lists for the cell.  The toy configuration
raises ``learning_rate`` to 0.5 so that the run samples after two iterations
(the real file's 0.1 asks twelve warm-up iterations).

Run serially, as ``python -m pytest benchmarks/tests`` does: the traced
rehearsals of one cell share ``.bench_scratch/trace/<cell>``."""
import pytest

from harness import cells, reference_goss
from test_rehearsal import CONTRACT_KEYS, _rehearse

CELL = "higgs-goss-train"
NEW = {"sampler.bag_share", "kernel.rows_per_table_row"}    # need no device
DEVICE_ONLY = {"sampler.goss_ms_per_iter", "objective.grad_ms_per_iter"}


@pytest.mark.parametrize("traced", [0, 1])
def test_goss_train_rehearses(traced):
    result, detail = _rehearse(CELL, traced)
    assert set(result) == CONTRACT_KEYS
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is False and result["metrics"] == {}
    assert result["attempted"] > 0 and result["failed"] == 0
    would = detail["would_print"]
    assert would["correct"] is True, detail["checks"]
    assert detail["counters"]["compiles_in_window"] == 0
    cell = cells.load_cell(CELL, True)
    rows = cell.config["data"]["rows"]
    # (a) the path and the booster, as the program says them
    stamps = detail["checks"]["stamps"]
    assert stamps["uses_wave"] and stamps["fused_sibling"]
    assert stamps["fused_grad"] is False
    assert (stamps["boosting"], stamps["top_rate"], stamps["other_rate"]) \
        == ("goss", 0.2, 0.1)
    # every timed iteration sampled, and its counters are a GOSS sample's
    sampler = detail["checks"]["sampler"]
    assert sampler["ok"]
    assert len(sampler["iterations"]) == result["attempted"] + 2 * traced
    start = reference_goss.sampling_starts(
        cell.config["params"]["learning_rate"])
    assert cell.traffic["warmup_iters"] == start + 2
    assert sampler["iterations"][0]["iteration"] == start + 2
    assert all(s["bag_rows"] < rows for s in sampler["iterations"])
    # (b) the export scores every row, in and out of the bag
    exp = detail["checks"]["export"]
    assert exp["max_rel_err"] <= 1e-5
    assert exp["trees"] == start + 2 + result["attempted"] + 2 * traced
    # (c1) the sample of one more update, judged from the trainer's scores
    smp = detail["checks"]["sample"]
    assert smp["ok"] and smp["counters_match"]
    assert smp["top_missing"] == 0 and smp["root_count"] == smp["bag_rows"]
    assert smp["threshold_rel_err"] <= smp["f32_rtol"]
    assert smp["top_missing_margin"] <= smp["f32_rtol"]
    assert smp["root_weight_rel_err"] <= smp["root_rtol"]
    # (c2) staged: both growers drew the same bag, the oracle off the wave
    ora = detail["checks"]["oracle"]
    assert ora["same_root"] and not ora["oracle_uses_wave"]
    assert ora["score_med"] <= ora["score_med_max"]
    assert ora["unsampled_iters"] == start and len(ora["stages"]) == 2
    assert all(st["same_draw"] and st["sampled"] for st in ora["stages"])
    names = set(would["metrics"])
    if not traced:
        assert names == {"setup_s", "train_row_iters_per_s"}
        assert detail["line_lacks"] == []
        # all the table's rows count, the bag's share of them does not
        host = detail["host"]
        assert would["metrics"]["train_row_iters_per_s"]["value"] == \
            pytest.approx(rows * result["attempted"] / host["window_s"])
        return
    # traced: what the CPU cannot give is all the line lacks
    assert NEW <= names and not DEVICE_ONLY & names
    lacking = {w.split()[1] for w in detail["line_lacks"]
               if w.startswith("metric ")}
    src = {m["name"]: m["source"] for m in cells.benchmark_doc()["per_layer"]}
    assert DEVICE_ONLY <= lacking and not lacking & names
    assert all(src[n] != "host_clock" for n in lacking), lacking
    work = detail["counters"]["work_counters"]
    assert work["boosting"] == "goss" and len(work["sampler"]) == 2
    assert would["metrics"]["sampler.bag_share"]["value"] == pytest.approx(
        100.0 * work["bag_rows"] / (rows * 2))
    assert 25.0 < would["metrics"]["sampler.bag_share"]["value"] < 35.0
    kern = sum(t["kernel_rows"][0] for t in work["trees"])
    assert would["metrics"]["kernel.rows_per_table_row"]["value"] == \
        pytest.approx(kern / (rows * 2))
    # under a bag the root's wave compacts too
    assert all(t["compact_waves"][0] == t["waves"] for t in work["trees"])


def test_line_of_the_cell_carries_every_listed_metric():
    """A line with exactly what ``BENCHMARK.json`` lists for the cell lacks
    nothing; the four metrics this cell brought are asked of it alone."""
    from harness import line
    doc = cells.benchmark_doc()
    listed = line.listed_metrics(doc, CELL, True)
    assert NEW | DEVICE_ONLY <= set(listed)
    assert "mesh.collective_ms_per_iter" not in listed
    for other in ("higgs-train", "mslr-train", "higgs-dp4-train",
                  "expo-train"):
        assert not (NEW | DEVICE_ONLY) & set(
            line.listed_metrics(doc, other, True))
    good = {"correct": True, "attempted": 8, "failed": 0,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 7 * 10 ** 9, "busy_s": 2.3,
                       "window_s": 2.4},
            "metrics": {n: {"value": 1.0, "unit": u}
                        for n, u in listed.items()}}
    assert line.problems(doc, CELL, True, good) == []


def test_parent_without_the_sampler_ends_at_once(monkeypatch):
    """A program whose Booster has no ``bag_mask`` (the parent of the PR
    that added the cell) ends the run before any table is made."""
    import importlib

    import lightgbm_tpu as lgb
    kind = importlib.import_module("kinds.boost_goss")
    monkeypatch.delattr(lgb.Booster, "bag_mask")
    with pytest.raises(SystemExit) as exc:
        kind.run(None)
    assert "bag_mask" in str(exc.value)
