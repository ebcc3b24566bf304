"""``mslr-fp4-train`` at toy size on the CPU, four virtual devices, kernel
interpreted: the feature-parallel learner through the ``boost`` kind, its
checks, its three metrics, and the line against what ``BENCHMARK.json``
lists for the cell.

Run serially, as ``python -m pytest benchmarks/tests`` does: the traced
rehearsals of one cell share ``.bench_scratch/trace/<cell>``."""
from harness import cells
from test_rehearsal import CONTRACT_KEYS, _rehearse

CELL = "mslr-fp4-train"


def test_mslr_fp4_train_rehearses():
    result, detail = _rehearse(CELL, 1)
    assert set(result) == CONTRACT_KEYS
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == 4
    assert result["correct"] is False and result["metrics"] == {}
    would = detail["would_print"]
    assert would["correct"] is True, detail["checks"]
    checks = detail["checks"]
    # (a) the feature learner on the kernel, the fused sibling kept, the
    # bins on every chip
    assert checks["stamps"]["uses_wave"] and checks["stamps"]["fused_sibling"]
    assert checks["stamps"]["bins_devices"] == 4
    # (c) against the one-chip serial XLA learner, mesh keys dropped
    assert checks["oracle"]["same_root"]
    assert not checks["oracle"]["oracle_uses_wave"]
    # the program's counters under the new metrics: 136 columns in blocks
    # of 8 over four chips, 5/4/4/4; one sync a wave
    m = would["metrics"]
    assert m["kernel.columns_per_chip"]["value"] == 40.0
    work = detail["counters"]["work_counters"]
    assert m["mesh.split_syncs_per_iter"]["value"] == sum(
        t["waves"] for t in work["trees"]) / len(work["iterations"])
    # on the CPU only what a device gives is missing from the line
    src = {e["name"]: e["source"] for e in cells.benchmark_doc()["per_layer"]}
    lacking = {w.split()[1] for w in detail["line_lacks"]
               if w.startswith("metric ")}
    assert "mesh.split_sync_ms_per_iter" in lacking
    assert all(src[n] != "host_clock" for n in lacking), lacking
