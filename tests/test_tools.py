"""Observability tooling: telemetry_report multi-process merging,
bench_history trajectory/regression flagging, the tpu_window checklist,
and the cost model behind docs/ROOFLINE.md."""
import json
import os
import runpy
import subprocess
import sys

import numpy as np
import pytest

from lightgbm_tpu.obs.report import (load_events, phase_skew, render,
                                     summarize)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")


def _write_events(path, events):
    with open(path, "w") as fh:
        for e in events:
            fh.write(json.dumps(e) + "\n")


def _iter_event(proc, i, phase_s):
    return {"event": "iteration", "t": 1.0 + i, "iteration": i,
            "num_class": 1, "leaves": [7], "waves": None,
            "iter_s": sum(phase_s.values()), "phase_s": phase_s,
            "metrics": {"training.auc": 0.9 + 0.001 * i + 0.0001 * proc},
            "counters": {}, "recompiles": 0,
            "cum_row_iters_per_s": 1000.0 * (i + 1)}


def _summary_event(phase_s, counters):
    return {"event": "summary", "t": 99.0, "phase_s": phase_s,
            "phase_calls": {k: 3 for k in phase_s}, "counters": counters}


# ---------------------------------------------------------------------------
# telemetry_report: multi-process merge
# ---------------------------------------------------------------------------

def test_report_merges_multiprocess_files(tmp_path):
    """Per-process telemetry.{i}.jsonl files merge into one digest:
    iteration rows from process 0, counters summed across processes,
    and the cross-host phase-skew table computed from the per-process
    summaries."""
    p0 = {"tree growth": 2.0, "boosting (grad/hess)": 0.5}
    p1 = {"tree growth": 3.0, "boosting (grad/hess)": 0.5}
    _write_events(tmp_path / "telemetry.0.jsonl",
                  [_iter_event(0, i, p0) for i in range(3)]
                  + [_summary_event(p0, {"collective/psum/traced_bytes":
                                         1000})])
    _write_events(tmp_path / "telemetry.1.jsonl",
                  [_iter_event(1, i, p1) for i in range(3)]
                  + [_summary_event(p1, {"collective/psum/traced_bytes":
                                         1200})])
    digest = summarize(load_events(str(tmp_path)))
    assert digest["processes"] == [0, 1]
    assert digest["iterations"] == 3
    # process-0 metrics picked for the per-iteration rows
    assert digest["per_iteration"][0]["metrics"]["training.auc"] == 0.9
    # counters summed across both processes' summaries
    assert digest["counters"]["collective/psum/traced_bytes"] == 2200
    # phase totals come from the summaries (both procs)
    assert digest["phase_s"]["tree growth"] == 5.0
    # the straggler table: proc 1 is 1s slower in tree growth
    skew = digest["phase_skew"]["tree growth"]
    assert skew["min_s"] == 2.0 and skew["max_s"] == 3.0
    assert skew["spread_s"] == 1.0
    assert skew["spread_frac"] == pytest.approx(1.0 / 2.5)
    # identical phases show no skew
    assert digest["phase_skew"]["boosting (grad/hess)"]["spread_s"] == 0.0
    text = render(digest)
    assert "phase skew" in text and "tree growth" in text


def test_phase_skew_single_process_empty():
    assert phase_skew({0: {"a": 1.0}}) == {}


def test_report_tool_cli_multiprocess(tmp_path, capsys, monkeypatch):
    p0 = {"tree growth": 1.0}
    _write_events(tmp_path / "telemetry.0.jsonl",
                  [_iter_event(0, 0, p0), _summary_event(p0, {})])
    _write_events(tmp_path / "telemetry.1.jsonl",
                  [_iter_event(1, 0, p0), _summary_event(p0, {})])
    tool = os.path.join(TOOLS, "telemetry_report.py")
    monkeypatch.setattr(sys, "argv", [tool, str(tmp_path), "--json"])
    with pytest.raises(SystemExit) as ei:
        runpy.run_path(tool, run_name="__main__")
    assert ei.value.code == 0
    digest = json.loads(capsys.readouterr().out)
    assert digest["processes"] == [0, 1]


# ---------------------------------------------------------------------------
# bench_history: trajectory + regression flagging
# ---------------------------------------------------------------------------

def _bench_round(n, value, per_iter_s, backend=None, **extra):
    parsed = {"metric": "train_throughput", "value": value,
              "unit": "row_iters/s", "vs_baseline": value / 2.2e7,
              "rows": 1000, "iters": 5, "num_leaves": 31, "max_bin": 255,
              "per_iter_s": per_iter_s, "compile_s": 3.0,
              "train_auc": 0.9}
    if backend:
        parsed["backend"] = backend
    parsed.update(extra)
    return {"n": n, "cmd": "python bench.py", "rc": 0, "parsed": parsed}


def _history(tmp_path, rounds, *args):
    sys.path.insert(0, TOOLS)
    try:
        import bench_history
    finally:
        sys.path.remove(TOOLS)
    for i, r in enumerate(rounds, 1):
        with open(tmp_path / f"BENCH_r{i:02d}.json", "w") as fh:
            json.dump(r, fh)
    rows = bench_history.collect([str(tmp_path)])
    return bench_history, rows


def test_bench_history_flags_regression(tmp_path):
    bh, rows = _history(tmp_path, [
        _bench_round(1, 1000.0, 1.0),
        _bench_round(2, 2000.0, 0.5),
        _bench_round(3, 1200.0, 0.9,           # 40% throughput drop vs r02
                     peak_hbm_bytes=5_000_000),
    ])
    assert [r["round"] for r in rows] == ["r01", "r02", "r03"]
    regs = bh.find_regressions(rows, threshold=0.1)
    by_metric = {r["metric"]: r for r in regs}
    assert "value" in by_metric
    assert by_metric["value"]["best_round"] == "r02"
    assert by_metric["value"]["change_frac"] == pytest.approx(-0.4)
    assert "per_iter_s" in by_metric      # lower-is-better direction
    assert by_metric["per_iter_s"]["change_frac"] == pytest.approx(0.8)
    # peak_hbm_bytes only exists in r03 — no prior, no flag
    assert "peak_hbm_bytes" not in by_metric
    text = bh.render(rows, regs)
    assert "REGRESSIONS" in text and "value" in text


def test_bench_history_no_flags_when_improving(tmp_path):
    bh, rows = _history(tmp_path, [
        _bench_round(1, 1000.0, 1.0),
        _bench_round(2, 3000.0, 0.3),
    ])
    assert bh.find_regressions(rows, threshold=0.1) == []


def test_bench_history_contexts_not_comparable(tmp_path):
    """A CPU-fallback round must not 'regress' against a real round."""
    bh, rows = _history(tmp_path, [
        _bench_round(1, 100000.0, 0.1),
        _bench_round(2, 500.0, 2.0, backend="cpu-fallback"),
    ])
    assert bh.find_regressions(rows, threshold=0.1) == []


def test_bench_history_unparsed_round_and_telemetry_fold(tmp_path):
    """parsed:null rounds ride along noteless-metric; embedded telemetry
    digests contribute peak-HBM and kernel roofline trajectory metrics."""
    td = {"phase_s": {"tree growth": 1.0}, "phase_calls": {},
          "counters": {"jax/compiles": 7},
          "kernels": {"lgbm/grow_apply": {"calls": 3, "achieved_s": 1.0,
                                          "roofline_s": 0.2,
                                          "roofline_frac": 0.2}},
          "memory": {"peak_bytes": 123456, "peak_phase": "tree growth"}}
    bh, rows = _history(tmp_path, [
        {"n": 1, "cmd": "python bench.py", "rc": 0, "parsed": None},
        _bench_round(2, 1000.0, 1.0, telemetry=td),
    ])
    assert rows[0]["note"] == "no parsed bench line"
    m = rows[1]["metrics"]
    assert m["peak_hbm_bytes"] == 123456
    assert m["kernel_roofline/lgbm/grow_apply"] == 0.2
    assert m["jax_compiles"] == 7


def test_bench_history_canary_trend(tmp_path):
    """Degraded-backend rounds stay out of regression baselines but their
    per_iter_s/value movement is surfaced as an informational trend — a
    partition-style win is visible even with no TPU datapoint."""
    bh, rows = _history(tmp_path, [
        _bench_round(1, 500.0, 2.0, backend="cpu-fallback"),
        _bench_round(2, 1000.0, 1.0, backend="cpu-fallback"),
    ])
    trend = bh.canary_trend(rows)
    assert [t["round"] for t in trend] == ["r01", "r02"]
    assert trend[1]["per_iter_s_change_frac"] == pytest.approx(-0.5)
    assert trend[1]["value_change_frac"] == pytest.approx(1.0)
    # canaries still gate NOTHING
    assert bh.find_regressions(rows, threshold=0.05) == []
    text = bh.render(rows, [])
    assert "canary trend" in text and "-50.0%" in text


def test_bench_history_mode_regressions(tmp_path):
    """Wave-pipeline stamps: waves_per_tree trends numerically (lower is
    better) while hist_mode / fused_sibling downgrades are flagged
    categorically — even when throughput improved, because a bf16 round
    can post a better value while computing a worse histogram."""
    bh, rows = _history(tmp_path, [
        _bench_round(1, 1000.0, 1.0, waves_per_tree=16.0,
                     hist_mode="2xbf16", fused_sibling=True),
        _bench_round(2, 1500.0, 0.7, waves_per_tree=19.0,
                     hist_mode="f32", fused_sibling=False),
    ])
    assert rows[0]["mode"] == {"hist_mode": "2xbf16",
                               "fused_sibling": True}
    regs = bh.find_regressions(rows, threshold=0.1)
    by_metric = {r["metric"]: r for r in regs}
    assert "waves_per_tree" in by_metric       # lower-is-better numeric
    mregs = bh.find_mode_regressions(rows)
    assert {m["metric"] for m in mregs} == {"fused_sibling", "hist_mode"}
    text = bh.render(rows, regs, mregs)
    assert "MODE REGRESSIONS" in text and "2xbf16" in text
    # same modes, no prior downgrade → nothing flagged
    bh2, rows2 = _history(tmp_path, [
        _bench_round(1, 1000.0, 1.0, hist_mode="2xbf16",
                     fused_sibling=True),
        _bench_round(2, 900.0, 1.1, hist_mode="2xbf16",
                     fused_sibling=True),
    ])
    assert bh2.find_mode_regressions(rows2) == []


def _serve_round(n, blip=None, steady=None, rollbacks=0):
    return {"n": n, "parsed": {
        "kind": "serve", "backend": "cpu", "trees": 20, "max_batch": 256,
        "closed": {"rows_per_s": 5000.0, "p50_ms": 5.0, "p99_ms": 20.0},
        "open": {"p99_ms": 25.0},
        "server": {"p99_ms": 18.0, "slo_burn": 0.1},
        "occupancy": 0.9, "compiles": 10,
        "swap": {"swap_blip_p99_ms": blip, "steady_p99_ms": steady,
                 "rollbacks": rollbacks}}}


def test_bench_history_swap_blip_flag(tmp_path):
    """A hot-swap blip p99 worse than 2x the steady p99 (and any
    rollback during the swap leg) is flagged on the serving round —
    categorical, like mode regressions, because a blip can double while
    the steady p99 improves."""
    sys.path.insert(0, TOOLS)
    try:
        import bench_history as bh
    finally:
        sys.path.remove(TOOLS)
    with open(tmp_path / "SERVE_r01.json", "w") as fh:
        json.dump(_serve_round(1, blip=90.0, steady=20.0, rollbacks=1),
                  fh)
    with open(tmp_path / "SERVE_r02.json", "w") as fh:
        json.dump(_serve_round(2, blip=30.0, steady=20.0), fh)
    rows = bh.collect([str(tmp_path)])
    assert rows[0]["metrics"]["serve_swap_blip_p99_ms"] == 90.0
    assert rows[0]["swap_blip"] == 4.5
    assert "rollback" in rows[0]["note"]
    assert "swap_blip" not in rows[1]          # 1.5x steady: no flag
    blips = bh.find_swap_blips(rows)
    assert [b["round"] for b in blips] == ["r01"]
    text = bh.render(rows, [], [], blips)
    assert "SWAP BLIPS" in text and "4.5x" in text


def test_run_suite_chaos_tier_stubbed():
    """The chaos tier wraps chaos_serve.py --json; its check map becomes
    the tier's counts and it rides the default tier list."""
    rs = _import_tool("run_suite")
    assert "chaos" in rs._TOOL_TIERS

    def fake(argv, **kw):
        import types
        assert any(isinstance(a, str) and "chaos_serve.py" in a
                   for a in argv)
        line = json.dumps({"kind": "chaos_serve", "ok": True,
                           "checks": {"wedge.all_served": True,
                                      "swap.zero_loss": True,
                                      "rollback.triggered": True}})
        return types.SimpleNamespace(returncode=0, stdout=line + "\n",
                                     stderr="")

    res = rs.run_tool_smoke("chaos", 60, runner=fake)
    assert res["ok"] is True
    assert res["counts"] == {"passed": 3, "failed": 0}


def test_bench_history_cli_exit_codes(tmp_path, monkeypatch, capsys):
    tool = os.path.join(TOOLS, "bench_history.py")
    for i, r in enumerate([_bench_round(1, 2000.0, 0.5),
                           _bench_round(2, 1000.0, 1.0)], 1):
        with open(tmp_path / f"BENCH_r{i:02d}.json", "w") as fh:
            json.dump(r, fh)
    monkeypatch.setattr(sys, "argv", [tool, str(tmp_path), "--json"])
    with pytest.raises(SystemExit) as ei:
        runpy.run_path(tool, run_name="__main__")
    assert ei.value.code == 0          # flags reported, exit 0 by default
    out = json.loads(capsys.readouterr().out)
    assert any(g["metric"] == "value" for g in out["regressions"])
    monkeypatch.setattr(sys, "argv", [tool, str(tmp_path),
                                      "--fail-on-regression"])
    with pytest.raises(SystemExit) as ei:
        runpy.run_path(tool, run_name="__main__")
    assert ei.value.code == 1


def test_bench_without_a_chip_exits_nonzero_and_prints_no_metric():
    env = {k: v for k, v in os.environ.items() if k != "BENCH_FORCE_CPU"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr and "'cpu'" in r.stderr


def test_wave_kernel_cost_matches_roofline_doc():
    """wave_kernel_cost at the HIGGS bench shape reproduces the numbers
    docs/ROOFLINE.md quotes for the v5e (197 bf16 TF): two passes of the
    triple layout 3.67 TFLOP / 18.6 ms, a full packed launch three passes
    (5.51 TFLOP / 28.0 ms), and by the program's own count of pass-rows a
    one-pass launch a third of that."""
    from lightgbm_tpu.obs.peaks import roofline_seconds
    from lightgbm_tpu.ops.pallas_hist import wave_kernel_cost
    flops, nbytes = wave_kernel_cost(1_000_000, 28, 256, "2xbf16")
    assert flops == pytest.approx(2 * 2 * 256 * 128 * 1e6 * 28)
    t = roofline_seconds(flops, nbytes, peaks=(197e12, 820e9))
    assert t == pytest.approx(18.6e-3, rel=0.02)
    full, nb = wave_kernel_cost(1_000_000, 28, 256, "2xbf16", packed=True)
    assert full == pytest.approx(5.51e12, rel=0.01)
    assert roofline_seconds(full, nb, peaks=(197e12, 820e9)) == \
        pytest.approx(28.0e-3, rel=0.02)
    one, _ = wave_kernel_cost(1_000_000, 28, 256, "2xbf16", packed=True,
                              pass_rows=1_000_000)
    assert full == 3 * one
    # feature packing: B=64 really is 4x cheaper
    flops64, _ = wave_kernel_cost(1_000_000, 28, 64, "2xbf16")
    assert flops64 == pytest.approx(flops / 4)


# ---------------------------------------------------------------------------
# bench_history: degraded-backend canaries (VERDICT round-5 weak #4)
# ---------------------------------------------------------------------------

def test_bench_history_canary_rounds_excluded_from_baselines(tmp_path):
    """cpu-fallback rounds are flagged in the table and excluded from the
    regression comparison on BOTH sides — even against each other."""
    bh, rows = _history(tmp_path, [
        _bench_round(1, 100000.0, 0.1),
        _bench_round(2, 5000.0, 1.0, backend="cpu-fallback"),
        _bench_round(3, 500.0, 2.0, backend="cpu-fallback"),  # 90% "drop"
    ])
    assert rows[1]["canary"] == "cpu-fallback"
    assert rows[2]["canary"] == "cpu-fallback"
    assert "canary" not in rows[0]
    # two comparable canaries with a huge drop: still no regression,
    # because canaries never enter the baseline
    assert bh.find_regressions(rows, threshold=0.1) == []
    text = bh.render(rows, [])
    assert "canary — excluded from baselines" in text
    # and a canary is never the "latest" round a real regression is
    # computed for: a real r04 regressing vs r01 still flags
    bh2, rows2 = _history(tmp_path, [
        _bench_round(1, 100000.0, 0.1),
        _bench_round(2, 500.0, 2.0, backend="cpu-fallback"),
        _bench_round(3, 50000.0, 0.2),
        _bench_round(4, 500.0, 2.0, backend="cpu-forced"),
    ])
    regs = bh2.find_regressions(rows2, threshold=0.1)
    by_metric = {r["metric"]: r for r in regs}
    assert by_metric["value"]["round"] == "r03"
    assert by_metric["value"]["best_round"] == "r01"


# ---------------------------------------------------------------------------
# run_suite: per-tier evidence artifact (SUITE_rN.json)
# ---------------------------------------------------------------------------

def _import_tool(name):
    sys.path.insert(0, TOOLS)
    try:
        return __import__(name)
    finally:
        sys.path.remove(TOOLS)


def test_run_suite_parse_counts():
    rs = _import_tool("run_suite")
    out = ("....s..\n"
           "= 5 passed, 1 skipped, 2 deselected, 1 warning in 12.34s =\n")
    c = rs.parse_counts(out)
    assert c == {"passed": 5, "skipped": 1, "deselected": 2, "warning": 1}
    assert rs.parse_counts("3 failed, 2 passed, 1 error in 9s") == {
        "failed": 3, "passed": 2, "error": 1}
    assert rs.parse_counts("garbage") == {}


def test_run_suite_smoke_tiny_selection(tmp_path):
    """The satellite smoke: run_suite against a single tiny quick test
    writes a SUITE_rN.json with per-tier wall clock and pass counts."""
    rs = _import_tool("run_suite")
    rc = rs.main([
        "--tiers", "quick",
        "--select",
        "tests/test_distributed.py::test_parse_machine_list_forms",
        "--out", str(tmp_path), "--timeout", "300"])
    assert rc == 0
    path = tmp_path / "SUITE_r01.json"
    assert path.exists()
    rec = json.loads(path.read_text())
    assert rec["ok"] is True
    assert rec["failed"] == 0
    tier = rec["tiers"]["quick"]
    assert tier["counts"].get("passed") == 1
    assert tier["wall_s"] > 0
    # round numbering advances
    assert rs.next_round(str(tmp_path)) == 2


def test_run_suite_reports_failure(tmp_path):
    """A failing selection yields ok=False and exit 1 (the 0-failure
    evidence must be falsifiable)."""
    rs = _import_tool("run_suite")
    bad = tmp_path / "test_sentinel_fail.py"
    bad.write_text("import pytest\n"
                   "@pytest.mark.quick\n"
                   "def test_always_fails():\n    assert False\n")
    rc = rs.main(["--tiers", "quick", "--select", str(bad),
                  "--out", str(tmp_path), "--timeout", "300"])
    assert rc == 1
    rec = json.loads((tmp_path / "SUITE_r01.json").read_text())
    assert rec["ok"] is False
    assert rec["failed"] == 1


def test_run_suite_serve_leg_stubbed():
    """The serve tier wraps bench_serve.py --smoke: its check map becomes
    the tier's pass/fail counts and a failing check fails the tier."""
    rs = _import_tool("run_suite")

    def fake_ok(argv, **kw):
        import types
        line = json.dumps({"kind": "serve", "ok": True,
                           "checks": {"p99_recorded": True,
                                      "compiles_bounded": True,
                                      "clean_shutdown": True}})
        return types.SimpleNamespace(returncode=0, stdout=line + "\n",
                                     stderr="")

    res = rs.run_serve_smoke(60, runner=fake_ok)
    assert res["ok"] is True
    assert res["counts"] == {"passed": 3, "failed": 0}

    def fake_bad(argv, **kw):
        import types
        line = json.dumps({"kind": "serve", "ok": False,
                           "checks": {"p99_recorded": True,
                                      "compiles_bounded": False}})
        return types.SimpleNamespace(returncode=1, stdout=line + "\n",
                                     stderr="")

    res = rs.run_serve_smoke(60, runner=fake_bad)
    assert res["ok"] is False
    assert res["counts"]["failed"] == 1


# ---------------------------------------------------------------------------
# tpu_window: self-arming measurement watcher
# ---------------------------------------------------------------------------

class _FakeRun:
    """Canned subprocess.run: records invocations, returns scripted
    (returncode, stdout) keyed on a substring of the argv."""

    def __init__(self, outputs, default=(0, "")):
        self.outputs = outputs
        self.default = default
        self.calls = []

    def __call__(self, argv, **kw):
        self.calls.append(argv)
        import types
        r = types.SimpleNamespace()
        key = next((k for k in self.outputs
                    if any(isinstance(a, str) and k in a for a in argv)),
                   None)
        r.returncode, r.stdout = (self.outputs[key] if key is not None
                                  else self.default)
        r.stderr = ""
        return r


def test_tpu_window_probe_and_rounds(tmp_path):
    tw = _import_tool("tpu_window")
    armed, backend = tw.probe_backend(
        runner=_FakeRun({}, default=(0, "TPU v5 lite\n")))
    assert armed and backend == "TPU v5 lite"
    armed, backend = tw.probe_backend(
        runner=_FakeRun({}, default=(2, "cpu\n")))
    assert not armed and backend == "cpu"
    assert tw.next_round(str(tmp_path)) == 1
    (tmp_path / "BENCH_manual_r03.json").write_text("{}")
    assert tw.next_round(str(tmp_path)) == 4
    assert tw._parse_json_tail("junk\n{\"a\": 1}\ntrailer") == {"a": 1}
    assert tw._parse_json_tail("no json") is None


def test_tpu_window_checklist_stubbed(tmp_path):
    """The full checklist plumbing with canned leg outputs: artifact
    layout, the bench_history-compatible BENCH_manual record, and the
    health summary — no real training."""
    tw = _import_tool("tpu_window")
    bench_line = json.dumps({"metric": "train_throughput", "value": 123.0,
                             "unit": "row_iters/s", "vs_baseline": 0.001,
                             "rows": 100, "iters": 3, "num_leaves": 31,
                             "max_bin": 255, "backend": "cpu-forced",
                             "health_checks": 9, "health_failures": 0})
    serve_line = json.dumps({"kind": "serve", "backend": "cpu",
                             "trees": 20, "max_batch": 128,
                             "closed": {"rows_per_s": 9000.0,
                                        "p99_ms": 12.0},
                             "open": {"p99_ms": 15.0,
                                      "explain_frac": 0.5,
                                      "explain_p99_ms": 48.0},
                             "occupancy": 0.7, "compiles": 8,
                             "degraded": False})
    ingest_line = json.dumps({"kind": "ingest", "backend": "cpu",
                              "rows": 60000, "features": 8,
                              "chunk_rows": 2048, "memmap": False,
                              "ingest_rows_per_s": 250000.0,
                              "ingest_wall_s": 0.24,
                              "checks": {"bounded_memory": True},
                              "ok": True})
    fleet_line = json.dumps({"kind": "fleet", "fleet_ranks": 3,
                             "fleet_recoveries": 1, "wall_s": 60.0,
                             "checks": {"fleet.plain.bit_exact": True},
                             "ok": True})
    fake = _FakeRun({
        "bench_serve.py": (0, serve_line + "\n"),
        "ingest_bench.py": (0, ingest_line + "\n"),
        "fleet_smoke.py": (0, fleet_line + "\n"),
        "bench.py": (0, "noise\n" + bench_line + "\n"),
        "-c": (0, "TRACE_OK\n"),
    })
    rec = tw.run_checklist(str(tmp_path), 7, dry_run=True, runner=fake,
                           backend="cpu (dry-run)")
    assert (tmp_path / "BENCH_manual_r07.json").exists()
    assert (tmp_path / "HEALTH_manual_r07.json").exists()
    assert rec["parsed"]["value"] == 123.0
    assert rec["parsed"]["health_failures"] == 0
    assert set(rec["legs"]) == {"bench", "bench_maxbin63", "bench_quant",
                                "bench_rank", "bench_serve",
                                "bench_explain", "bench_ingest",
                                "bench_fleet", "trace"}
    assert (tmp_path / "FLEET_manual_r07.json").exists()
    assert all(leg["rc"] == 0 for leg in rec["legs"].values())
    # bench legs ran four times (clean, maxbin63, quant, rank)
    # — endswith, so tools/ingest_bench.py's
    # leg is not miscounted as a bench.py invocation
    bench_calls = [c for c in fake.calls
                   if any(isinstance(a, str)
                          and a.endswith(os.sep + "bench.py")
                          for a in c)]
    assert len(bench_calls) == 4
    # the rank leg's parsed line landed as BENCH_rank_manual_rN.json
    # and bench_history's BENCH_r* glob picks it up as its own context
    assert (tmp_path / "BENCH_rank_manual_r07.json").exists()
    # the record is bench_history-compatible: it folds into the
    # trajectory as a canary (cpu-forced), never a baseline
    bh = _import_tool("bench_history")
    rows = bh.collect([str(tmp_path / "BENCH_manual_r07.json")])
    assert rows[0]["metrics"]["value"] == 123.0
    assert rows[0]["canary"] == "cpu-forced"
    # the serve leg's parsed line landed as SERVE_manual_rN.json and
    # folds into the trajectory under the serve context
    assert (tmp_path / "SERVE_manual_r07.json").exists()
    srows = bh.collect([str(tmp_path / "SERVE_manual_r07.json")])
    assert srows[0]["context"][0] == "serve"
    assert srows[0]["metrics"]["serve_rows_per_s"] == 9000.0
    assert srows[0]["metrics"]["serve_p99_ms"] == 12.0
    # the explain-heavy leg landed as its own artifact, and the mixed
    # leg's TreeSHAP p99 trends through bench_history
    assert (tmp_path / "SERVE_explain_manual_r07.json").exists()
    xrows = bh.collect([str(tmp_path / "SERVE_explain_manual_r07.json")])
    assert xrows[0]["metrics"]["serve_explain_p99_ms"] == 48.0
    # the ingest leg (--no-write) landed as the window-owned
    # INGEST_manual_rN.json and trends under its own ingest context
    assert (tmp_path / "INGEST_manual_r07.json").exists()
    irows = bh.collect([str(tmp_path / "INGEST_manual_r07.json")])
    assert irows[0]["context"][0] == "ingest"
    assert irows[0]["metrics"]["ingest_rows_per_s"] == 250000.0


def test_tpu_window_leg_triage_classes(tmp_path):
    """ISSUE 17 wedge triage: every non-clean leg gets one of the four
    classes; a fully clean window gets no triage block at all."""
    tw = _import_tool("tpu_window")
    clean = {"rc": 0, "parsed": {"backend": "tpu"}}
    assert tw.leg_triage(clean) is None
    # green-but-on-CPU is only a finding on a real (non-dry) window
    cpu = {"rc": 0, "parsed": {"backend": "cpu"}}
    assert tw.leg_triage(cpu) == "cpu-fallback"
    assert tw.leg_triage(cpu, dry_run=True) is None
    assert tw.leg_triage({"rc": -1, "tail": []}) == "timeout"
    assert tw.leg_triage({"rc": 1, "wedge_class": "transient",
                          "tail": []}) == "backend-wedge"
    # no wedge_class recorded, but the tail still smells like a wedge
    assert tw.leg_triage({"rc": 1, "tail": ["...", "backend wedge "
                          "detected"]}) == "backend-wedge"
    assert tw.leg_triage({"rc": 1, "tail": ["ValueError: bad "
                          "param"]}) == "failure"

    results = {"bench": {"rc": -1, "tail": []},
               "bench_serve": {"rc": 1, "wedge_class": "transient",
                               "tail": []},
               "trace": {"rc": 0, "parsed": {}}}
    tri = tw.triage_legs(results)
    assert tri["legs"] == {"bench": "timeout",
                           "bench_serve": "backend-wedge"}
    assert tri["classes"] == ["backend-wedge", "timeout"]
    assert tw.triage_legs({"trace": {"rc": 0, "parsed": {}}}) is None

    # bench_history surfaces the block in the round's note
    rec = {"round": 3, "timestamp": "2026-08-07T00:00:00",
           "backend": "cpu (forced)", "dry_run": True,
           "parsed": None, "triage": tri, "legs": results}
    p = tmp_path / "BENCH_manual_r03.json"
    p.write_text(json.dumps(rec))
    bh = _import_tool("bench_history")
    rows = bh.collect([str(p)])
    assert rows[0]["triage"] == tri["legs"]
    assert "triage[bench:timeout, bench_serve:backend-wedge]" \
        in rows[0]["note"]


def test_tpu_window_dry_run_end_to_end(tmp_path):
    """Acceptance: `tpu_window.py --dry-run` executes real capture legs
    on CPU and emits a well-formed BENCH_manual artifact + health
    summary.  Restricted to the bench + trace legs to bound wall clock
    (the stubbed test above covers the full leg set)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "tpu_window.py"),
         "--dry-run", "--out", str(tmp_path), "--legs", "bench,trace",
         "--leg-timeout", "420"],
        capture_output=True, text=True, timeout=500, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    rec = json.loads((tmp_path / "BENCH_manual_r01.json").read_text())
    assert rec["dry_run"] is True
    assert rec["parsed"]["backend"] == "cpu-forced"
    assert rec["parsed"]["value"] > 0
    # the bench line certifies itself: health ran and found nothing
    assert rec["parsed"]["health_checks"] > 0
    assert rec["parsed"]["health_failures"] == 0
    assert rec["legs"]["trace"]["rc"] == 0
    assert rec["trace_files"] > 0, "jax.profiler trace left no artifact"
    health = json.loads((tmp_path / "HEALTH_manual_r01.json").read_text())
    assert health["verdict"] == "healthy"
    assert health["events_ok"] is True
    assert health["legs"]["bench"]["health"]["fingerprints"] > 0


# ---------------------------------------------------------------------------
# bench_history: measured-vs-model divergence gating
# ---------------------------------------------------------------------------

def test_bench_history_measured_divergence_flags():
    bh = _import_tool("bench_history")
    rows = [
        {"round": "r01", "context": ("a",),
         "metrics": {"kernel_measured/lgbm/wave_hist": 0.9}},
        {"round": "r02", "context": ("a",),
         "metrics": {"kernel_measured/lgbm/wave_hist": 0.4,
                     "kernel_measured/lgbm/wave_partition": 0.8,
                     "kernel_measured/lgbm/split_scan": 2.6}},
    ]
    flags = bh.find_measured_divergence(rows)
    assert {f["metric"] for f in flags} == {
        "kernel_measured/lgbm/wave_hist",
        "kernel_measured/lgbm/split_scan"}
    assert all(f["round"] == "r02" for f in flags)
    sides = {f["metric"]: f["side"] for f in flags}
    assert sides["kernel_measured/lgbm/wave_hist"] == "off-roofline"
    assert sides["kernel_measured/lgbm/split_scan"] == \
        "model-underprices"
    # canary rounds never gate: r01's clean fracs become latest
    rows[1]["canary"] = "cpu-forced"
    assert bh.find_measured_divergence(rows) == []


def test_bench_history_divergence_gates_exit(tmp_path, monkeypatch,
                                             capsys):
    """A > 2x measured-vs-model divergence fails --fail-on-regression
    exactly like a mode regression."""
    bh = _import_tool("bench_history")
    (tmp_path / "BENCH_r09.json").write_text(json.dumps({
        "metric": "train_throughput", "value": 100.0,
        "unit": "row_iters/s", "rows": 100, "iters": 3,
        "num_leaves": 31, "max_bin": 255,
        "kernel_measured": {"lgbm/wave_hist": 0.3}}))
    monkeypatch.setattr(sys, "argv",
                        ["bench_history.py", str(tmp_path),
                         "--fail-on-regression"])
    assert bh.main() == 1
    assert "MEASURED-VS-MODEL DIVERGENCE" in capsys.readouterr().out
    # the same round without the gate is informational only
    monkeypatch.setattr(sys, "argv",
                        ["bench_history.py", str(tmp_path)])
    assert bh.main() == 0
