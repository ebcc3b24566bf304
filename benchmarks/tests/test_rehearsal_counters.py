"""Each cell's traced rehearsal prints the metrics that are counts of the
program's own work (they need no device), and leaves the raw per-tree,
per-chip counts in the detail line; the times per row need the chip's trace
and are not there.

Run these serially, as ``python -m pytest benchmarks/tests`` does: two traced
rehearsals of one cell (``test_rehearsal.py`` makes one too) share
``.bench_scratch/trace/<cell>``, and under ``-n`` one removes the other's."""
import pytest

from harness import cells
from test_rehearsal import _rehearse

COUNTED = {"grower.bodies_per_iter", "grower.partition_routed_share",
           "kernel.tier_fill_share", "kernel.lane_fill_share"}


@pytest.mark.parametrize("cell", ["higgs-train", "mslr-train",
                                  "higgs-dp4-train"])
def test_traced_rehearsal_prints_the_counter_metrics(cell):
    result, detail = _rehearse(cell, 1)
    assert result["metrics"] == {} and result["correct"] is False
    would = detail["would_print"]
    assert would["correct"] is True, detail["checks"]
    got = would["metrics"]
    assert COUNTED <= set(got)
    assert not {"grower.partition_ns_per_row", "kernel.hist_ns_per_row",
                "grower.compact_ms_per_iter"} & set(got)
    work = detail["counters"]["work_counters"]
    chips = cells.load_cell(cell, True).chips
    assert work["counted"] and work["chips"] == chips
    assert len(work["iterations"]) == len(work["trees"]) == 2   # traced ones
    for t in work["trees"]:
        assert len(t["kernel_rows"]) == len(t["active_rows"]) == chips
        assert 1 <= t["waves"] <= t["bodies"] and t["lanes"] == 15
    bodies = sum(t["bodies"] for t in work["trees"])
    assert got["grower.bodies_per_iter"]["value"] == bodies / 2
    assert 0 < got["grower.partition_routed_share"]["value"] < 100
    assert 0 < got["kernel.tier_fill_share"]["value"] <= 100
    assert got["kernel.lane_fill_share"]["value"] == pytest.approx(
        100 * 30 / (sum(t["waves"] for t in work["trees"]) * 63))
    # the public accessor's stamps are the ones the benchmark put together
    assert work["stamps"] == detail["checks"]["stamps"]
