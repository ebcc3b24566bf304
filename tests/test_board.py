"""Live training introspection plane (ISSUE 17): the train-side
/metrics + /progress + /debug/flight exporter (obs/board.py), the
per-rank straggler detector and measured-vs-model reconciliation
(obs/ranks.py), and their integration with the trainer.

The acceptance pin is the straggler CI-twin: this CPU container has no
cross-process collectives (jax 0.4.37), so the 2-process fault-injected
run is twinned single-process — the LOCAL rank is genuinely slowed by
the LGBM_TPU_FAULTS sleep harness while a monkeypatched
``train_stats_exchange`` supplies two synthetic fast peers.  The
detector must name this rank and the slowed phase, dump the flight
ring, and surface the skew on the live board.
"""
import json
import math
import threading
import time
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs import board, core, ranks
from lightgbm_tpu.obs.ranks import (PHASES, RankAggregator, Reconciler,
                                    StragglerDetector)
from lightgbm_tpu.robust import faults
from lightgbm_tpu.serve.metrics import parse_prometheus

_PARAMS = {"objective": "binary", "metric": "auc", "num_leaves": 7,
           "min_data_in_leaf": 5, "verbose": -1, "seed": 1}


@pytest.fixture(autouse=True)
def _clean_state():
    faults.disarm()
    yield
    faults.disarm()
    b = board.current()
    if b is not None:
        b.stop()


def _toy(n=600, f=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    return X, y


def _get(url, timeout=5):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read()


def _feed_iterations(n, iter_s=0.1, start=0, **extra):
    for i in range(start, start + n):
        core.event("iteration", iteration=i, iter_s=iter_s,
                   metrics={"training.auc": 0.9}, recompiles=0,
                   phase_s={"tree growth": iter_s * 0.7,
                            "boosting (grad/hess)": iter_s * 0.2},
                   cum_row_iters_per_s=1e6, **extra)


# ---------------------------------------------------------------------------
# the exporter itself
# ---------------------------------------------------------------------------

def test_board_endpoints_and_shared_prometheus_reader():
    b = board.TrainBoard(total_rounds=10, port=0)
    b.start()
    try:
        assert board.active() and board.current() is b
        _feed_iterations(3)
        status, body = _get(b.url + "/metrics")
        assert status == 200
        parsed = parse_prometheus(body.decode())  # the serve-plane reader
        assert parsed["tpu_train_iteration"] == 2.0
        assert parsed["tpu_train_completed_iterations"] == 3.0
        assert parsed["tpu_train_total_rounds"] == 10.0
        assert parsed["tpu_train_row_iters_per_s"] == pytest.approx(1e6)
        status, body = _get(b.url + "/progress")
        pr = json.loads(body)
        assert pr["iteration"] == 2 and pr["total_rounds"] == 10
        assert len(pr["recent"]) == 3
        assert math.isfinite(pr["eta_s"]) and pr["eta_s"] > 0
        assert pr["vs_baseline"] is not None
        status, body = _get(b.url + "/debug/flight")
        fl = json.loads(body)
        assert fl["enabled"] and isinstance(fl["events"], list)
        with pytest.raises(urllib.error.HTTPError):
            _get(b.url + "/nope")
    finally:
        b.stop()
    assert not board.active()
    # unhooked: events after stop must not mutate the dead board
    it = b.progress()["iteration"]
    _feed_iterations(1, start=7)
    assert b.progress()["iteration"] == it


def test_eta_is_this_run_rate_not_wall_since_boot():
    """Satellite 6: a resumed board (start_round=80 of 100) fed 5
    iterations at 0.1s must report ETA ~= remaining * rate — NOT the
    naive uptime * total/completed extrapolation, which for a
    crash-resume would be wall-clock-since-boot scaled."""
    b = board.TrainBoard(total_rounds=100, start_round=80, port=0)
    b.start()
    try:
        _feed_iterations(5, iter_s=0.1, start=80)
        pr = b.progress()
        assert pr["start_round"] == 80
        assert pr["iteration"] == 84 and pr["completed"] == 5
        # remaining = 100 - (84+1) = 15 rounds at EMA 0.1s
        assert pr["eta_s"] == pytest.approx(1.5, rel=0.01)
        # the broken semantic would claim (100-85)/85 * uptime-ish
        # values or scale with the restored offset; pin the ceiling
        assert pr["eta_s"] < 5.0
        assert pr["frac"] == pytest.approx(0.85)
    finally:
        b.stop()


def test_resolve_port_env_and_config(monkeypatch):
    cfg = lgb.Config(tpu_train_metrics_port=8123)

    monkeypatch.delenv("LGBM_TPU_TRAIN_METRICS", raising=False)
    assert board.resolve_port(cfg) == 8123
    assert board.resolve_port(None) is None
    monkeypatch.setenv("LGBM_TPU_TRAIN_METRICS", "0")
    assert board.resolve_port(cfg) == 0
    monkeypatch.setenv("LGBM_TPU_TRAIN_METRICS", "off")
    assert board.resolve_port(cfg) is None
    monkeypatch.setenv("LGBM_TPU_TRAIN_METRICS", "-1")
    assert board.resolve_port(cfg) is None
    monkeypatch.setenv("LGBM_TPU_TRAIN_METRICS", "not-a-port")
    assert board.resolve_port(cfg) is None


def test_config_knob_validation():
    from lightgbm_tpu.utils.log import LightGBMError
    with pytest.raises(LightGBMError):
        lgb.Config.from_params({"tpu_train_metrics_port": 99999})
    with pytest.raises(LightGBMError):
        lgb.Config.from_params({"tpu_straggler_factor": 1.0})
    with pytest.raises(LightGBMError):
        lgb.Config.from_params({"tpu_straggler_iters": -1})


# ---------------------------------------------------------------------------
# straggler detector (pure streak logic)
# ---------------------------------------------------------------------------

def _mat(slow=0.01, fast=0.001, slow_rank=0, ranks=3):
    row_fast = [fast] * len(PHASES)
    rows = [list(row_fast) for _ in range(ranks)]
    rows[slow_rank] = [slow] * len(PHASES)
    return rows


def test_straggler_streak_emits_once_and_resets():
    det = StragglerDetector(factor=2.0, iters=3)
    # two windows of 1 iteration: streak 2 < 3 — silent
    assert det.update(_mat(), 1, iteration=1) == []
    assert det.update(_mat(), 1, iteration=2) == []
    # third consecutive: breach, naming rank and phase
    breaches = det.update(_mat(), 1, iteration=3)
    assert {b["rank"] for b in breaches} == {0}
    assert {b["phase"] for b in breaches} == set(PHASES)
    b = breaches[0]
    assert b["ratio"] == pytest.approx(10.0) and b["consecutive"] == 3
    assert b["breach"] is True
    # streak continues: already emitted, stays quiet
    assert det.update(_mat(), 1, iteration=4) == []
    # recovery resets the streak AND the emitted latch...
    assert det.update(_mat(slow=0.001), 1, iteration=5) == []
    # ...so a relapse emits again after another full streak
    assert det.update(_mat(), 3, iteration=8) != []


def test_straggler_window_iters_count_toward_streak():
    det = StragglerDetector(factor=2.0, iters=4)
    assert det.update(_mat(), 2, iteration=2) == []      # streak 2
    assert det.update(_mat(), 2, iteration=4) != []      # streak 4


def test_straggler_noise_floor_suppresses_microsecond_skew():
    det = StragglerDetector(factor=2.0, iters=1)
    # 10x skew over a 5us median is jitter, not a straggler
    assert det.update(_mat(slow=5e-5, fast=5e-6), 1, iteration=1) == []


def test_two_ranks_cannot_breach_factor_two():
    # with 2 ranks the median contains the straggler: wall > 2*median
    # is arithmetically impossible — documents why the CI twin
    # synthesizes a 3-rank fleet
    det = StragglerDetector(factor=2.0, iters=1)
    rows = [[0.1] * len(PHASES), [0.001] * len(PHASES)]
    assert det.update(rows, 1, iteration=1) == []


def test_rank_aggregator_single_process_is_noop():
    agg = RankAggregator(factor=2.0, iters=1)
    agg.accumulate({"tree growth": 0.1, "boosting (grad/hess)": 0.05})
    assert agg.exchange(iteration=1) is None   # no collective armed
    assert agg.exchange(iteration=2) is None   # empty window short-cuts


# ---------------------------------------------------------------------------
# reconciliation
# ---------------------------------------------------------------------------

def test_reconciler_scores_partition_and_growth():
    rec = Reconciler()
    units = rec.score(
        phase_s={"tree growth": 0.05, "boosting (grad/hess)": 0.01},
        iter_s=0.06, N=10_000, splits=6)
    assert "partition" in units and "tree_growth" in units
    u = units["partition"]
    # the same splits routed in one pass move fewer bytes than a walk each
    one = rec.score(phase_s={"tree growth": 0.05}, iter_s=0.06, N=10_000,
                    splits=6, passes=1)["partition"]
    assert 0 < one["modeled_s"] < u["modeled_s"]
    assert u["measured_s"] == pytest.approx(0.05)
    assert u["modeled_s"] > 0 and u["ratio"] > 0
    assert u["ratio"] == pytest.approx(u["measured_s"] / u["modeled_s"],
                                       rel=1e-3)


def test_reconciler_rank_pair_unit():
    rec = Reconciler()
    units = rec.score(
        phase_s={"tree growth": 0.05, "boosting (grad/hess)": 0.02},
        iter_s=0.07, N=3000, splits=0,
        rank_sizes=np.asarray([100, 200, 50], np.int64))
    assert set(units) == {"rank_pair"}
    assert units["rank_pair"]["measured_s"] == pytest.approx(0.02)


def test_reconciler_shap_unit():
    rec = Reconciler()
    u = rec.score_shap(0.5, N=1000, T=20, L=31, P=6, F=28, K=1)
    assert u["measured_s"] == pytest.approx(0.5) and u["modeled_s"] > 0


def test_reconciler_missing_inputs_yield_none():
    rec = Reconciler()
    assert rec.score(phase_s={}, iter_s=0.01, N=100, splits=0) is None


def test_train_emits_reconciliation_events(tmp_path):
    X, y = _toy()
    obs.enable(str(tmp_path / "telem"))
    try:
        ds = lgb.Dataset(X, label=y, params=dict(_PARAMS))
        lgb.train(dict(_PARAMS), ds, num_boost_round=6,
                  verbose_eval=False)
    finally:
        obs.disable()
    from lightgbm_tpu.obs.report import load_events, summarize
    events = load_events(str(tmp_path / "telem"))
    recs = [e for e in events if e.get("event") == "reconciliation"]
    assert recs, "steady-state iterations must score the cost models"
    units = recs[-1]["units"]
    assert "tree_growth" in units
    for u in units.values():
        assert u["modeled_s"] > 0 and u["ratio"] > 0
    digest = summarize(events)
    assert "tree_growth" in digest["reconciliation"]
    summary = digest["reconciliation"]["tree_growth"]
    assert summary["iterations"] == len(recs)
    assert summary["mean_ratio"] > 0


# ---------------------------------------------------------------------------
# the acceptance pin: fault-injected straggler, end to end
# ---------------------------------------------------------------------------

def test_straggler_acceptance_ci_twin(tmp_path, monkeypatch):
    """A rank slowed by the fault harness must produce: a ``straggler``
    event naming rank and phase, a flight dump, and live /metrics
    showing the skew.  Single-process twin of the 2-process run: the
    sleep fault makes THIS rank slow; the patched exchange supplies two
    synthetic fast peers (3-rank fleet — see the two-rank test above
    for why)."""
    import lightgbm_tpu.parallel.distributed as dist

    def fake_exchange(vec):
        # peers = this rank WITHOUT the injected sleep: identical
        # boosting wall, tree growth scaled way down — so the only
        # breach is in the faulted phase
        gi = PHASES.index("tree growth")
        peer = list(vec)
        peer[gi] = vec[gi] * 0.05
        return [list(vec), peer, list(peer)]

    monkeypatch.setattr(dist, "train_stats_exchange", fake_exchange)
    # every device execute sleeps 30ms — lands in "tree growth" wall
    faults.configure("device_execute:sleep=0.03@n=-1")

    monkeypatch.setenv("LGBM_TPU_TRAIN_METRICS", "0")
    telem = str(tmp_path / "telem")
    obs.enable(telem)

    seen = {"metrics": None, "skew": None}

    def poll():
        while not seen.get("stop"):
            b = board.current()
            if b is not None:
                try:
                    text = b.metrics_text()
                    if "tpu_train_stragglers_total 0" not in text \
                            and "tpu_train_stragglers_total" in text:
                        seen["metrics"] = text
                    if "tpu_train_phase_skew_seconds" in text:
                        seen["skew"] = text
                except Exception:
                    pass
            time.sleep(0.01)

    t = threading.Thread(target=poll, daemon=True)
    t.start()
    try:
        X, y = _toy()
        p = dict(_PARAMS, tpu_straggler_iters=2, tpu_straggler_factor=2.0,
                 tpu_fingerprint_freq=1)
        ds = lgb.Dataset(X, label=y, params=p)
        lgb.train(p, ds, num_boost_round=8, verbose_eval=False)
    finally:
        seen["stop"] = True
        obs.disable()
        faults.disarm()
    t.join(timeout=5)

    # 1. the straggler event names this rank and the slowed phase
    from lightgbm_tpu.obs.report import load_events
    stragglers = [e for e in load_events(telem)
                  if e.get("event") == "straggler"]
    assert stragglers, "slow rank must be reported"
    ev = stragglers[0]
    assert ev["rank"] == 0
    assert ev["phase"] == "tree growth"   # where the sleep fault lands
    assert ev["ratio"] > 2.0
    assert ev["consecutive"] >= 2

    # 2. the flight ring was dumped (conftest points FLIGHT_DIR at tmp)
    dumps = list(tmp_path.glob("FLIGHT_r*.json"))
    assert dumps, "a straggler breach must leave a post-mortem"
    dump = json.load(open(dumps[0]))
    assert dump.get("straggler", {}).get("rank") == 0
    assert "skew" in dump

    # 3. the live board showed the breach and the per-rank skew table
    assert seen["metrics"] is not None, "live /metrics never saw breach"
    parsed = parse_prometheus(seen["metrics"])
    assert parsed["tpu_train_stragglers_total"] >= 1.0
    assert seen["skew"] is not None
    assert 'rank="0"' in seen["skew"] and 'rank="1"' in seen["skew"]
    # the skew series carries the slowed phase for the slow rank
    assert 'tpu_train_phase_skew_seconds{rank="0",phase="tree growth"}' \
        in seen["skew"]


def test_straggler_detection_disabled_by_config(tmp_path):
    X, y = _toy(n=300)
    p = dict(_PARAMS, tpu_straggler_iters=0)
    ds = lgb.Dataset(X, label=y, params=p)
    bst = lgb.train(p, ds, num_boost_round=2, verbose_eval=False)
    assert bst._gbdt._ranks is None


# ---------------------------------------------------------------------------
# engine integration: arming, resume anchoring, teardown
# ---------------------------------------------------------------------------

def test_engine_arms_board_and_stops_after_train(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_TRAIN_METRICS", "0")
    snaps = []

    def poll():
        while not snaps or snaps[-1] != "stop":
            b = board.current()
            if b is not None:
                try:
                    snaps.append(b.progress())
                except Exception:
                    pass
            time.sleep(0.005)

    t = threading.Thread(target=poll, daemon=True)
    t.start()
    X, y = _toy(n=2000)
    ds = lgb.Dataset(X, label=y, params=dict(_PARAMS))
    lgb.train(dict(_PARAMS), ds, num_boost_round=6, verbose_eval=False)
    snaps.append("stop")
    t.join(timeout=5)
    assert not board.active(), "engine must tear the exporter down"
    prs = [s for s in snaps if isinstance(s, dict)]
    assert prs, "board was never live during the train"
    assert any(p["total_rounds"] == 6 for p in prs)
    ws = [p for p in prs if p.get("watchdog")]
    assert ws and "active" in ws[0]["watchdog"]


def test_progress_resume_anchoring_end_to_end(tmp_path, monkeypatch):
    """Crash at 4, resume to 10 with the exporter armed: /progress must
    anchor at the restored iteration (start_round=4) with this-run ETA
    — satellite 6's regression pin at the engine level."""
    X, y = _toy(n=2000)
    ck = str(tmp_path / "ck")
    p = dict(_PARAMS, tpu_checkpoint_dir=ck, tpu_checkpoint_freq=2)
    ds = lgb.Dataset(X, label=y, params=dict(p))
    lgb.train(dict(p), ds, num_boost_round=4, verbose_eval=False)

    monkeypatch.setenv("LGBM_TPU_TRAIN_METRICS", "0")
    prs = []

    def poll():
        while not prs or prs[-1] != "stop":
            b = board.current()
            if b is not None:
                try:
                    prs.append(b.progress())
                except Exception:
                    pass
            time.sleep(0.005)

    t = threading.Thread(target=poll, daemon=True)
    t.start()
    ds = lgb.Dataset(X, label=y, params=dict(p))
    lgb.train(dict(p), ds, num_boost_round=10, verbose_eval=False)
    prs.append("stop")
    t.join(timeout=5)
    snaps = [s for s in prs if isinstance(s, dict)]
    assert snaps, "board never scraped during resume"
    assert all(s["start_round"] == 4 for s in snaps)
    assert all(s["total_rounds"] == 10 for s in snaps)
    late = [s for s in snaps if s["iteration"] is not None
            and s["completed"] >= 2]
    assert late, "no snapshot after the rate estimate settled"
    for s in late:
        # this-run rate: remaining * EMA, NOT uptime-extrapolated
        remaining = s["total_rounds"] - (s["iteration"] + 1)
        # progress() rounds eta_s to 3 decimals
        assert s["eta_s"] == pytest.approx(
            s["ema_iter_s"] * remaining, abs=1e-3)
        # iteration numbering is global (resumed at 4)
        assert s["iteration"] >= 4


# ---------------------------------------------------------------------------
# report plane: straggler/reconciliation digest + CLI entry
# ---------------------------------------------------------------------------

def test_report_digest_renders_straggler_and_reconciliation():
    from lightgbm_tpu.obs.report import render, summarize
    events = [
        {"event": "straggler", "t": 1.0, "rank": 2, "phase": "tree growth",
         "iteration": 10, "ratio": 3.2, "median_s": 0.01, "rank_s": 0.032,
         "consecutive": 3, "breach": True, "_proc": 0},
        {"event": "reconciliation", "t": 2.0, "iteration": 11,
         "units": {"wave_kernel": {"measured_s": 0.02, "modeled_s": 0.01,
                                   "ratio": 2.0}}, "_proc": 0},
        {"event": "reconciliation", "t": 3.0, "iteration": 12,
         "units": {"wave_kernel": {"measured_s": 0.04, "modeled_s": 0.01,
                                   "ratio": 4.0}}, "_proc": 0},
    ]
    digest = summarize(events)
    assert digest["stragglers"][0]["rank"] == 2
    wk = digest["reconciliation"]["wave_kernel"]
    assert wk["iterations"] == 2
    assert wk["mean_ratio"] == pytest.approx(3.0)
    assert wk["worst_ratio"] == pytest.approx(4.0)
    assert wk["worst_iteration"] == 12
    text = render(digest)
    assert "straggler" in text.lower()
    assert "wave_kernel" in text


def test_report_cli_module_entry(tmp_path):
    import subprocess
    import sys
    d = tmp_path / "telem"
    d.mkdir()
    (d / "telemetry.0.jsonl").write_text(json.dumps(
        {"event": "iteration", "t": 1.0, "iteration": 0, "iter_s": 0.1,
         "phase_s": {}, "metrics": {}}) + "\n")
    r = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.obs.report", str(d),
         "--json"], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    digest = json.loads(r.stdout)
    assert digest["iterations"] == 1
    # the deprecated shim still answers
    import os
    shim = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "telemetry_report.py")
    r = subprocess.run([sys.executable, shim, str(d), "--json"],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert json.loads(r.stdout)["iterations"] == 1
    assert "shim" in r.stderr


# ---------------------------------------------------------------------------
# train_watch formatting (pure)
# ---------------------------------------------------------------------------

def test_train_watch_format_iteration():
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    from train_watch import format_iteration
    line = format_iteration(
        {"iteration": 42, "iter_s": 0.213, "cum_row_iters_per_s": 1.23e7,
         "metrics": {"valid_0.auc": 0.9312}, "recompiles": 0}, total=500)
    assert "42/500" in line and "0.213s" in line
    assert "1.23e+07" in line and "valid_0.auc=0.9312" in line
    assert "recompiled" not in line
    line = format_iteration({"iteration": 3, "iter_s": 1.0,
                             "recompiles": 2})
    assert "[recompiled]" in line
    # None-safe on sparse records
    assert format_iteration({}) .startswith("iter")
