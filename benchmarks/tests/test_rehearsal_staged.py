"""``higgs-bin63-train`` at toy size on the CPU, kernel interpreted: the
staged kind's control flow, its checks, and the line against what
``BENCHMARK.json`` lists for the cell.

Run serially, as ``python -m pytest benchmarks/tests`` does: the traced
rehearsals of one cell share ``.bench_scratch/trace/<cell>``."""
import types

import pytest

from harness import cells, datagen
from test_rehearsal import CONTRACT_KEYS, _rehearse

CELL = "higgs-bin63-train"
FACTS = {"kernel_bins": 64, "feat_pack": 2, "feat_block": 28,
         "kernel_columns": 28}
NEW = {"kernel.features_per_pass", "kernel.contractions_per_row"}
# read off the benchmark's own launch of the trainer's kernel
DEVICE_ONLY = {"pallas_hist_wave_roofline",
               "pallas_hist_wave.mxu_charged_share"}


@pytest.mark.parametrize("traced", [0, 1])
def test_higgs_bin63_train_rehearses(traced):
    result, detail = _rehearse(CELL, traced)
    assert set(result) == CONTRACT_KEYS
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is False and result["metrics"] == {}
    assert result["attempted"] > 0 and result["failed"] == 0
    would = detail["would_print"]
    assert would["correct"] is True, detail["checks"]
    assert detail["counters"]["compiles_in_window"] == 0
    # (a) the path and the kernel's shape, as the program says them
    checks = detail["checks"]
    assert checks["stamps"]["uses_wave"] and checks["stamps"]["fused_sibling"]
    assert checks["facts"] == FACTS
    # (b) the export, and the stopping rule on it
    exp = checks["export"]
    assert exp["max_rel_err"] <= exp["tol"] == 2e-6
    assert exp["min_leaf_weight"] >= exp["leaf_weight_floor"] > 0
    # (c) staged: every iteration by both growers, the cap not binding, the
    # path's launches in more than one MXU pass
    ora = checks["oracle"]
    assert ora["ok"] and ora["iters"] == len(ora["stages"]) == 2
    assert ora["same_root"] and not ora["leaf_cap_binds"]
    assert ora["multi_pass"] and ora["leaf_cap"] == 1023
    assert ora["score_med"] <= ora["score_med_max"]
    for st in ora["stages"]:
        assert st["path_on_path"] and not st["oracle_uses_wave"]
        assert max(st["leaves"].values()) < ora["leaf_cap"]
        assert st["kernel_pass_rows"] > st["kernel_rows"] > 0
        assert st["pending_leaves"] == st["leaves"]["path"] > 50
    # the second stage starts from the first's scores: its losses are lower
    assert ora["stages"][1]["loss_oracle"] < ora["stages"][0]["loss_oracle"]
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
    # the benchmark's own launch ran the trainer's variant
    fp = detail["fullpass"]
    assert (fp["features"], fp["B"]) == (28, 64)
    assert fp["packed"] and fp["fused"] and len(fp["kernel_s"]) == 3
    # the two metrics, recounted from the counters they read
    work = detail["counters"]["work_counters"]
    assert {k: work[k] for k in FACTS} == FACTS
    got = would["metrics"]
    assert got["kernel.features_per_pass"]["value"] == 2.0
    passes = sum(t["kernel_pass_rows"][0] for t in work["trees"]) \
        / sum(t["kernel_rows"][0] for t in work["trees"])
    assert got["kernel.contractions_per_row"]["value"] == \
        pytest.approx(passes * 28 / 2)
    assert got["kernel.mxu_passes_per_row"]["value"] == pytest.approx(passes)


def _ctx():
    return types.SimpleNamespace(cell=cells.load_cell(CELL, True), seed=5,
                                 seconds=1.5, trace=False,
                                 evidence={"host": {}})


def test_a_program_without_the_facts_ends_before_the_table(monkeypatch):
    """The parent of the PR that brought the facts: its ``core/plan.py`` has
    no ``KernelShape`` to say.  The run ends at once, with a message and a
    non-zero code; no table is made and no Booster built."""
    import lightgbm_tpu as lgb
    from kinds import boost_staged
    from lightgbm_tpu.core import plan
    monkeypatch.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    made = []
    monkeypatch.setattr(datagen, "make_table",
                        lambda *a, **k: made.append(a) or 1 / 0)
    monkeypatch.setattr(lgb, "Booster", lambda *a, **k: made.append(a) or 1 / 0)
    monkeypatch.delattr(plan, "KernelShape")
    with pytest.raises(SystemExit) as exc:
        boost_staged.run(_ctx())
    assert "does not say" in str(exc.value.code) and made == []
    assert all(k in str(exc.value.code) for k in FACTS)


def test_a_trainer_that_leaves_a_fact_unsaid_has_no_result(monkeypatch):
    """``work_counters()`` with the stamps and no ``feat_pack``: the run ends
    after the Booster is built, before the first iteration."""
    import lightgbm_tpu as lgb
    from kinds import boost_staged
    monkeypatch.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    real = lgb.Booster.work_counters
    monkeypatch.setattr(
        lgb.Booster, "work_counters", lambda self, last=None: {
            k: v for k, v in real(self, last).items() if k != "feat_pack"})
    updates = []
    monkeypatch.setattr(lgb.Booster, "update",
                        lambda self, *a, **k: updates.append(1))
    with pytest.raises(SystemExit) as exc:
        boost_staged.run(_ctx())
    assert "does not say ['feat_pack']" in str(exc.value.code)
    assert updates == []


def test_a_program_on_another_kernel_shape_has_no_result(monkeypatch):
    """One feature a pass where the configuration says two: the run ends
    after the Booster is built, before the first iteration."""
    import lightgbm_tpu as lgb
    from kinds import boost_staged
    monkeypatch.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    real = lgb.Booster.work_counters
    monkeypatch.setattr(
        lgb.Booster, "work_counters", lambda self, last=None: {
            **real(self, last), "feat_pack": 1})
    updates = []
    monkeypatch.setattr(lgb.Booster, "update",
                        lambda self, *a, **k: updates.append(1))
    with pytest.raises(SystemExit) as exc:
        boost_staged.run(_ctx())
    assert "left the configuration's path" in str(exc.value.code)
    assert "'feat_pack': 1" in str(exc.value.code) and updates == []


def test_line_of_the_cell_carries_every_listed_metric():
    """A line with exactly what ``BENCHMARK.json`` lists for the cell lacks
    nothing; the two metrics this cell brought are asked of it and of
    ``higgs-train``, the same table at 256 lanes."""
    from harness import line
    doc = cells.benchmark_doc()
    listed = line.listed_metrics(doc, CELL, True)
    assert NEW | DEVICE_ONLY <= set(listed)
    # and of the pair's other end, so that the ledger holds both
    for other in (w["name"] for w in doc["workloads"] if w["name"] != CELL):
        assert (NEW <= set(line.listed_metrics(doc, other, True))) \
            == (other == "higgs-train")
    good = {"correct": True, "attempted": 8, "failed": 0,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 7 * 10 ** 9, "busy_s": 0.8,
                       "window_s": 0.85},
            "metrics": {n: {"value": 1.0, "unit": u}
                        for n, u in listed.items()}}
    assert line.problems(doc, CELL, True, good) == []


def test_the_cell_trains_on_higgs_trains_table():
    """The data block is ``higgs``'s, so the table is ``higgs-train``'s at
    every seed; the parameters differ in the bins and the stopping rule."""
    higgs = cells.load_cell("higgs-train").config
    bin63 = cells.load_cell(CELL).config
    assert bin63["data"] == higgs["data"]
    assert bin63["stamps"] == higgs["stamps"]
    a, b = dict(higgs["params"]), dict(bin63["params"])
    assert (a.pop("max_bin"), b.pop("max_bin")) == (255, 63)
    assert (a.pop("min_data_in_leaf"), b.pop("min_data_in_leaf")) == (100, 1)
    assert b.pop("min_sum_hessian_in_leaf") == 100 and a == b
    assert bin63["facts"] == FACTS
    # the slice of check (c): the hessian bound (a row's hessian is at most
    # 0.25) ends growth below the cap the check raises, and only that
    ora = bin63["oracle"]
    assert set(ora["params"]) == {"num_leaves"}
    assert b["num_leaves"] < ora["slice_rows"] * 0.25 / 100 \
        < ora["params"]["num_leaves"]
