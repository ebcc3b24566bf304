"""The cells at toy size on the CPU, kernel interpreted, through the
command itself: a test of control flow (kinds, reducers, checks, the result
line), and of nothing the chip would measure."""
import json
import os
import subprocess
import sys

import pytest

from harness import cells

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _rehearse(cell, traced):
    env = {k: v for k, v in os.environ.items()
           if k not in ("LGBM_TPU_FORCE_WAVE", "XLA_FLAGS")}
    r = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"),
         "--workload", cell, "--seed", "5", "--seconds", "1.5",
         "--trace", str(traced), "--rehearsal"],
        env=env, cwd=cells.ROOT, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    detail = json.loads(lines[-2].split("rehearsal: ", 1)[1])
    return result, detail


@pytest.mark.parametrize("cell,traced", [
    ("higgs-train", 1), ("mslr-train", 0), ("higgs-dp4-train", 1)])
def test_cell_rehearses(cell, traced):
    result, detail = _rehearse(cell, traced)
    # the line a CPU run prints: the contract's keys, the CPU named, not
    # correct, and no number under a device metric's name
    assert set(result) == CONTRACT_KEYS
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == cells.load_cell(cell, True).chips
    assert result["correct"] is False and result["metrics"] == {}
    assert result["attempted"] > 0 and result["failed"] == 0
    # what it would have printed on a chip, and why it would be correct
    would = detail["would_print"]
    assert would["correct"] is True, detail["checks"]
    assert detail["counters"]["compiles_in_window"] == 0
    names = set(would["metrics"])
    if traced:
        assert "setup_s" not in names and "breakdown" in would
        assert {"compile.in_window", "setup.gen_s",
                "setup.first_call_s"} <= names
        assert would["device"]["busy_s"] > 0
        assert would["device"]["window_s"] >= would["device"]["busy_s"] * 0.99
    else:
        assert names == {"setup_s", "train_row_iters_per_s"}
    # the line was held to BENCHMARK.json (harness/line.py): on the CPU only
    # what a device gives is missing from it
    lacking = {w.split()[1] for w in detail["line_lacks"]
               if w.startswith("metric ")}
    src = {m["name"]: m["source"] for m in cells.benchmark_doc()["per_layer"]}
    assert not lacking & names
    assert all(src[n] != "host_clock" for n in lacking), lacking
    if cell == "higgs-dp4-train":
        assert "mesh.collective_ms_per_iter" in names
        assert detail["checks"]["stamps"]["bins_devices"] == 4
    # each iteration timed after its own sync; never more than the mix's
    # count (an interpreted iteration takes long: --seconds ends it sooner)
    k = cells.load_cell(cell, True).traffic["scored_iters"]
    assert 1 <= result["attempted"] == len(detail["host"]["iter_s"]) <= k
    ora = detail["checks"]["oracle"]
    assert ora["same_root"] and not ora["oracle_uses_wave"]
    assert ora["score_med"] <= ora["score_med_max"]
    assert detail["checks"]["export"]["max_rel_err"] <= 1e-5
