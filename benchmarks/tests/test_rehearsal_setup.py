"""The six cells traced at toy size on the CPU, through the command itself:
every ``setup.*`` metric that reads the program's own set-up record
(reducers/setup_span.py) is a finite number on the line the cell would print,
none of them is among what the line lacks, and the sums they are meant to
close do close on the host's clock.

Run serially, as ``python -m pytest benchmarks/tests`` does: the traced
rehearsals of one cell share ``.bench_scratch/trace/<cell>``."""
import math

import pytest

from harness import cells
from test_rehearsal import _rehearse

NEW = {s["name"] for s in cells.layer_metric_specs()
       if s["reducer"] == "setup_span"} | {"setup.start_s"}
CELLS = [w["name"] for w in cells.benchmark_doc()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_setup_metrics_on_the_traced_line(cell):
    _, detail = _rehearse(cell, 1)
    would = detail["would_print"]
    assert would["correct"] is True, detail["checks"]
    got = would["metrics"]
    listed = {m["name"] for m in cells.benchmark_doc()["per_layer"]
              if "workloads" not in m or cell in m["workloads"]}
    assert len(NEW) == 12 and NEW - {"setup.bin_group_s"} <= listed
    assert ("setup.bin_group_s" in listed) == (cell == "expo-train")
    for name in NEW & listed:
        assert math.isfinite(got[name]["value"]) and got[name]["value"] >= 0
    lacking = {w.split()[1] for w in detail["line_lacks"]
               if w.startswith("metric ")}
    assert not lacking & NEW, lacking
    # the three binning spans are what setup.bin_s timed from outside
    host = detail["host"]
    binned = sum(got[n]["value"] for n in (
        "setup.bin_find_s", "setup.bin_group_s", "setup.binarize_s")
        if n in got)
    assert 0 < binned <= host["bin_s"]
    assert got["setup.place_s"]["value"] <= host["init_s"]
    # the first update: its span inside the benchmark's own clock around
    # update() and the sync after it; what is left is the wait for the device
    first = detail["counters"]["setup_trace"]["updates"][0]
    assert first["iteration"] == 0 and first["programs"] >= 1
    assert 0 < first["dur_s"] <= host["first_call_s"]
    assert first["rest_s"] >= 0
    assert got["compile.in_window"]["value"] == 0
    assert got["setup.programs"]["value"] >= first["programs"]
