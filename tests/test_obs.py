"""Observability subsystem (lightgbm_tpu/obs): telemetry-off must be a
true no-op on the hot path, telemetry-on must stream parseable
per-iteration JSONL, the recompile counter must see forced retraces, and
tools/telemetry_report.py must round-trip a merged summary."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs.report import (load_events, render, summarize,
                                     telemetry_files)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _toy(n=500, f=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    return X, y


_PARAMS = {"objective": "binary", "metric": "auc", "num_leaves": 7,
           "min_data_in_leaf": 5, "verbose": -1}


def _train(n_iter=5, with_valid=False, params=_PARAMS):
    X, y = _toy()
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params=params, train_set=ds)
    if with_valid:
        bst.add_valid(lgb.Dataset(X, label=y, params=params, reference=ds),
                      "v0")
    for _ in range(n_iter):
        bst.update()
    return bst


# ---------------------------------------------------------------------------
# off path
# ---------------------------------------------------------------------------

def test_telemetry_off_no_file_no_sync(monkeypatch):
    """With no sink configured, training must not call block_until_ready
    (async dispatch preserved) and must not open any telemetry file."""
    assert not obs.tracing_enabled(), \
        "LGBM_TPU_TIMETAG/TELEMETRY leaked into the test environment"
    import jax
    # timers another file of this process left running (xdist hands a
    # worker several files) are not this training's
    obs.reset()
    calls = []
    orig = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or orig(x))
    bst = _train(3)
    monkeypatch.undo()
    jax.block_until_ready(bst._gbdt._train_score)  # drain async work
    assert calls == []
    assert obs.sink_path() is None
    assert obs.phase_snapshot() == {}  # timers never accumulated


@pytest.fixture(scope="module")
def telem_run(tmp_path_factory):
    """One telemetry-enabled 5-iteration train shared by the on-path
    assertions (compile time dominates; train once)."""
    sink = tmp_path_factory.mktemp("telem")
    obs.reset()
    obs.enable(str(sink))
    try:
        _train(5, with_valid=True)
        # the atexit summary can't fire inside the test process; emit one
        # explicitly so the merge path sees it like a finished run would
        obs.event("summary", **obs.digest())
    finally:
        obs.disable()
        obs.reset()
    return sink


# ---------------------------------------------------------------------------
# on path
# ---------------------------------------------------------------------------

def test_iteration_records(telem_run):
    f = telem_run / "telemetry.0.jsonl"
    assert f.exists()
    events = [json.loads(ln) for ln in f.read_text().splitlines()]
    iters = [e for e in events if e["event"] == "iteration"]
    assert len(iters) == 5
    assert [e["iteration"] for e in iters] == list(range(5))
    for e in iters:
        assert e["phase_s"], "phase timings missing"
        assert "tree growth" in e["phase_s"]
        assert e["metrics"]["training.auc"] > 0.5
        assert e["metrics"]["v0.auc"] > 0.5
        assert e["leaves"] == [7]
        assert isinstance(e["counters"], dict)
        assert e["cum_row_iters_per_s"] > 0
    # first iteration compiles; steady state must not
    assert iters[0]["recompiles"] > 0
    assert iters[-1]["recompiles"] == 0
    starts = [e for e in events if e["event"] == "train_start"]
    assert starts and starts[0]["num_leaves"] == 7


def test_report_roundtrip(telem_run):
    assert telemetry_files(str(telem_run)) == [
        str(telem_run / "telemetry.0.jsonl")]
    digest = summarize(load_events(str(telem_run)))
    assert digest["processes"] == [0]
    assert digest["iterations"] == 5
    assert digest["phase_s"]["tree growth"] > 0
    assert digest["metrics_last"]["training.auc"] > 0.5
    assert digest["parse_errors"] == 0
    # counters merged from the summary event
    assert digest["counters"].get("jax/compiles", 0) > 0
    text = render(digest)
    assert "tree growth" in text and "training.auc" in text


def test_report_tool_cli(telem_run, capsys, monkeypatch):
    import runpy
    tool = os.path.join(REPO, "tools", "telemetry_report.py")
    monkeypatch.setattr(sys, "argv", [tool, str(telem_run), "--json"])
    with pytest.raises(SystemExit) as ei:
        runpy.run_path(tool, run_name="__main__")
    assert ei.value.code == 0
    digest = json.loads(capsys.readouterr().out)
    assert digest["iterations"] == 5


def test_recompile_counter_fires_on_retrace():
    import jax
    import jax.numpy as jnp
    assert obs.install_recompile_hook()
    c0 = obs.compile_count()
    f = jax.jit(lambda x: x * 2.0 + 1.0)
    f(jnp.ones(3))
    f(jnp.ones(3))          # cache hit: no compile
    f(jnp.ones(5))          # forced retrace
    assert obs.compile_count() >= c0 + 2


def test_collective_accounting_unit(tmp_path):
    obs.reset()
    obs.enable(str(tmp_path / "c"))
    try:
        obs.record_collective("psum", np.zeros((4, 8), np.float32))
        obs.record_collective_host("process_allgather", 1024)
        snap = obs.counters_snapshot()
        assert snap["collective/psum/traced_calls"] == 1
        assert snap["collective/psum/traced_bytes"] == 4 * 8 * 4
        assert snap["collective/process_allgather/calls"] == 1
        assert snap["collective/process_allgather/bytes"] == 1024
        events = [json.loads(ln) for ln in open(obs.sink_path())]
        kinds = [e["kind"] for e in events if e["event"] == "collective"]
        assert kinds == ["psum", "process_allgather"]
    finally:
        obs.disable()
        obs.reset()


def test_psum_traced_accounting_in_shard_map(tmp_path):
    """mesh._psum records at trace time from inside shard_map."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from lightgbm_tpu.parallel import mesh as M

    obs.reset()
    obs.enable(str(tmp_path / "m"))
    try:
        m = M.build_mesh()
        f = M._shard_map(lambda x: M._psum(jnp.sum(x)), m,
                         (P(M.AXIS),), P())
        out = f(jnp.ones(m.devices.size * 2, jnp.float32))
        assert float(out) == m.devices.size * 2
        snap = obs.counters_snapshot()
        assert snap["collective/psum/traced_calls"] >= 1
        assert snap["collective/psum/traced_bytes"] >= 4  # one f32 scalar
    finally:
        obs.disable()
        obs.reset()


# ---------------------------------------------------------------------------
# device peaks + the roofline floor (obs/peaks.py)
# ---------------------------------------------------------------------------

def test_roofline_math():
    flops, bw = 1e12, 1e9
    import lightgbm_tpu.obs.peaks as P
    # compute-bound: 2e12 flops at 1e12/s = 2s floor
    assert P.roofline_seconds(2e12, 1e6, peaks=(flops, bw)) == 2.0
    # memory-bound: 5e9 bytes at 1e9/s = 5s floor
    assert P.roofline_seconds(1e9, 5e9, peaks=(flops, bw)) == 5.0


def test_peak_table_is_keyed_by_device_kind(monkeypatch):
    """The v5e row carries its bf16 peak (197e12; 394e12 is int8), and a
    device that is not in the table raises instead of taking a default."""
    import types

    import lightgbm_tpu.obs.peaks as P
    from lightgbm_tpu.utils.log import LightGBMError
    monkeypatch.delenv("LGBM_TPU_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("LGBM_TPU_PEAK_BW", raising=False)
    v5e = types.SimpleNamespace(device_kind="TPU v5 lite")
    assert P.device_peaks(v5e) == (197e12, 819e9)
    with pytest.raises(LightGBMError, match="TPU v9"):
        P.device_peaks(types.SimpleNamespace(device_kind="TPU v9"))
    # both overrides together stand in for a missing row; one does not
    monkeypatch.setenv("LGBM_TPU_PEAK_FLOPS", "1e12")
    with pytest.raises(LightGBMError):
        P.device_peaks(types.SimpleNamespace(device_kind="TPU v9"))
    assert P.device_peaks(v5e) == (1e12, 819e9)
    monkeypatch.setenv("LGBM_TPU_PEAK_BW", "2e9")
    assert P.device_peaks(
        types.SimpleNamespace(device_kind="TPU v9")) == (1e12, 2e9)


# ---------------------------------------------------------------------------
# one way to attribute time: what went stays gone
# ---------------------------------------------------------------------------

def _inert_run(extra):
    """Three iterations of ``lgb.train`` on the toy table: the model and
    the fused gradient pass's stamp."""
    X, y = _toy()
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
         **extra}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p), 3)
    return (bst.model_to_string(),
            bst.work_counters()["stamps"]["fused_grad"])


@pytest.fixture(scope="module")
def inert_baseline():
    return _inert_run({})


@pytest.mark.parametrize("param,env", [
    ({"tpu_profile": True}, {}), ({"tpu_xprof": True}, {}),
    ({"tpu_xprof_iters": 5}, {}), ({}, {"LGBM_TPU_PROFILE": "1"}),
    ({}, {"LGBM_TPU_XPROF": "1"})],
    ids=["tpu_profile", "tpu_xprof", "tpu_xprof_iters", "LGBM_TPU_PROFILE",
         "LGBM_TPU_XPROF"])
def test_a_retired_measurement_knob_is_inert(inert_baseline, tmp_path,
                                            monkeypatch, capfd, param,
                                            env):
    """Profile mode and the xprof window are gone: their parameters are
    unknown ones, their variables are read by nothing.  The same trees, the
    fused gradient pass still on, and nothing written where the run was.
    A variable was read when the package was imported, so those cases run
    in a fresh interpreter."""
    capfd.readouterr()
    if env:
        tests = os.path.dirname(os.path.abspath(__file__))
        code = ("import json, sys\n"
                f"sys.path.insert(0, {tests!r})\n"
                "from test_obs import _inert_run\n"
                "model, fused = _inert_run({})\n"
                "print(json.dumps([model, fused]))\n")
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            cwd=tmp_path, timeout=240,
            env={**os.environ, **env, "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": REPO + os.pathsep
                 + os.environ.get("PYTHONPATH", "")})
        assert r.returncode == 0, r.stdout + r.stderr
        model, fused = json.loads(r.stdout.strip().splitlines()[-1])
    else:
        monkeypatch.chdir(tmp_path)
        model, fused = _inert_run(param)
        name, = param
        assert f"Unknown parameter: {name}" in capfd.readouterr().err
    assert (model, fused) == inert_baseline
    assert fused is True
    assert list(tmp_path.iterdir()) == []


@pytest.fixture(scope="module")
def fused_booster():
    return _train(1)


@pytest.mark.parametrize("gate,fused", [
    ("nothing", True), ("telemetry_sink", True), ("train_board", True),
    ("health", False), ("fault_harness", False)])
def test_fused_grad_active_reads_only_health_and_faults(fused_booster,
                                                        tmp_path, gate,
                                                        fused):
    """The fused gradient pass goes off only where the materialized g and
    h are read: the health taps and the fault harness's gradients point.
    Watching a run (a telemetry sink, the train board) leaves it on."""
    from lightgbm_tpu.obs import board
    from lightgbm_tpu.robust import faults
    g = fused_booster._gbdt
    flight = obs.flight_enabled()
    tb = None
    try:
        if gate == "telemetry_sink":
            obs.enable(str(tmp_path / "t"))
        elif gate == "train_board":
            tb = board.TrainBoard(total_rounds=1, port=0).start()
            assert obs.board_active()
        elif gate == "health":
            obs.enable_health("monitor")
        elif gate == "fault_harness":
            faults.configure("device_execute:raise@iter=999")
            assert faults.armed()
        assert g.fused_grad_active() is fused
    finally:
        obs.disable()
        obs.enable_health("")
        faults.disarm()
        if tb is not None:
            tb.stop()
        if not flight:
            obs.enable_flight(0)
        obs.reset()


# ---------------------------------------------------------------------------
# CI smoke + overhead guard
# ---------------------------------------------------------------------------

def test_telemetry_env_smoke_subprocess(tmp_path):
    """The env-var path end to end in a fresh interpreter: import-order
    safety (obs enabled before jax does anything) and a clean atexit
    flush (exactly one summary event, parseable file)."""
    sink = tmp_path / "t"
    code = (
        "import numpy as np, lightgbm_tpu as lgb\n"
        "rng = np.random.default_rng(0)\n"
        "X = rng.normal(size=(300, 4)); y = (X[:, 0] > 0).astype(float)\n"
        "p = {'objective': 'binary', 'num_leaves': 4,\n"
        "     'min_data_in_leaf': 5, 'verbose': -1}\n"
        "bst = lgb.train(p, lgb.Dataset(X, label=y, params=p), 3)\n"
        "assert bst.num_trees() == 3\n")
    env = dict(os.environ)
    env["LGBM_TPU_TELEMETRY"] = str(sink)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=240)
    assert r.returncode == 0, r.stdout + r.stderr
    f = sink / "telemetry.0.jsonl"
    assert f.exists()
    events = [json.loads(ln) for ln in f.read_text().splitlines()]
    names = [e["event"] for e in events]
    assert names.count("iteration") == 3
    assert names.count("summary") == 1, "atexit flush missing or doubled"
    # dataset construction then training setup, in import-safe order
    assert names.index("dataset") < names.index("train_start")


def test_off_path_overhead_guard(monkeypatch):
    """The disabled telemetry layer must add <5% to a 5-iteration
    micro-train: measure the time actually spent inside obs entry points
    (phase enter/exit + sync) against total train wall time."""
    assert not obs.tracing_enabled()
    import lightgbm_tpu.utils.timetag as tt
    spent = [0.0]
    orig_tag, orig_sync = tt.timetag, tt.sync

    class TimedTag:
        def __init__(self, name):
            t0 = time.perf_counter()
            self._inner = orig_tag(name)
            spent[0] += time.perf_counter() - t0

        def __enter__(self):
            t0 = time.perf_counter()
            self._inner.__enter__()
            spent[0] += time.perf_counter() - t0
            return self

        def __exit__(self, *exc):
            t0 = time.perf_counter()
            r = self._inner.__exit__(*exc)
            spent[0] += time.perf_counter() - t0
            return r

    def timed_sync(x):
        t0 = time.perf_counter()
        r = orig_sync(x)
        spent[0] += time.perf_counter() - t0
        return r

    monkeypatch.setattr(tt, "timetag", TimedTag)
    monkeypatch.setattr(tt, "sync", timed_sync)
    t0 = time.perf_counter()
    _train(5, params={"objective": "binary", "metric": "auc",
                      "num_leaves": 15, "min_data_in_leaf": 5,
                      "verbose": -1})
    total = time.perf_counter() - t0
    assert spent[0] < 0.05 * total, \
        f"telemetry off-path spent {spent[0]:.4f}s of {total:.4f}s"
