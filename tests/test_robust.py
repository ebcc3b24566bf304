"""Fault tolerance (lightgbm_tpu/robust/): atomic checkpoint/resume
differentials, the device-wedge watchdog, and the fault-injection
harness.

The headline proof is the crash-resume differential: train N straight
vs train-to-crash + resume-to-N must produce BIT-IDENTICAL model text
(forest, leaf values, counts — everything except the parameters block,
which legitimately differs by the checkpoint knobs).  RNG state
(bagging, feature fraction, DART drops), score arrays, and the eval
history all ride the checkpoint, so the differential covers the whole
resume surface the way the sequential-split oracle covers the wave
apply.
"""
from __future__ import annotations

import glob
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.config import Config
from lightgbm_tpu.robust import (CheckpointManager, DeviceWedgedError,
                                 FaultInjected, FaultTransient,
                                 config_digest, faults)
from lightgbm_tpu.robust.watchdog import (DeviceGuard, backoff_delays,
                                          classify_error, classify_text)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

rng = np.random.default_rng(7)
N = 600
X = rng.normal(size=(N, 8))
y = (X[:, 0] + 0.5 * X[:, 1] + 0.1 * rng.normal(size=N) > 0
     ).astype(np.float64)
XV = rng.normal(size=(200, 8))
YV = (XV[:, 0] + 0.5 * XV[:, 1] > 0).astype(np.float64)

BASE = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
        "verbose": -1, "seed": 1}


@pytest.fixture(autouse=True)
def _disarm_faults():
    faults.disarm()
    yield
    faults.disarm()


def _model(booster):
    """Model text minus the parameters block (the checkpoint knobs
    legitimately differ between the straight and the resumed run)."""
    return booster.model_to_string(num_iteration=-1).split(
        "\nparameters:")[0]


def _mk(params):
    ds = lgb.Dataset(X, label=y, params=dict(params))
    vs = lgb.Dataset(XV, label=YV, reference=ds)
    return ds, vs


def _diff_resume(extra, n=12, crash=7, freq=5, es=None, tmp=None):
    """Straight-vs-crash-resume differential; returns (straight booster,
    resumed booster, checkpoint dir)."""
    p = dict(BASE)
    p.update(extra)
    kw = {"verbose_eval": False}
    if es:
        kw["early_stopping_rounds"] = es
    ds, vs = _mk(p)
    b1 = lgb.train(dict(p), ds, num_boost_round=n, valid_sets=[vs], **kw)
    p2 = dict(p, tpu_checkpoint_dir=str(tmp), tpu_checkpoint_freq=freq)
    ds, vs = _mk(p)
    lgb.train(dict(p2), ds, num_boost_round=crash, valid_sets=[vs], **kw)
    ds, vs = _mk(p)
    b2 = lgb.train(dict(p2), ds, num_boost_round=n, valid_sets=[vs], **kw)
    return b1, b2, str(tmp)


# ---------------------------------------------------------------------------
# crash-resume differentials: bit-identical models
# ---------------------------------------------------------------------------

def test_resume_bit_identical_bagging(tmp_path):
    b1, b2, ck = _diff_resume(
        {"bagging_fraction": 0.7, "bagging_freq": 3,
         "feature_fraction": 0.8}, tmp=tmp_path)
    assert _model(b1) == _model(b2)
    # the crash run left a checkpoint behind; the resume run added more
    assert len(glob.glob(os.path.join(ck, "ckpt_*"))) >= 1


def test_resume_bit_identical_goss(tmp_path):
    b1, b2, _ = _diff_resume(
        {"boosting": "goss", "learning_rate": 0.5, "top_rate": 0.3,
         "other_rate": 0.2}, tmp=tmp_path)
    assert _model(b1) == _model(b2)


def test_resume_bit_identical_dart(tmp_path):
    b1, b2, _ = _diff_resume(
        {"boosting": "dart", "drop_rate": 0.5, "skip_drop": 0.2},
        tmp=tmp_path)
    assert _model(b1) == _model(b2)


def test_resume_bit_identical_early_stopping(tmp_path):
    b1, b2, _ = _diff_resume({"learning_rate": 0.3}, n=40, crash=9,
                             freq=4, es=3, tmp=tmp_path)
    assert b1.best_iteration == b2.best_iteration
    assert _model(b1) == _model(b2)


def test_resume_bit_identical_two_device_mesh(tmp_path):
    b1, b2, _ = _diff_resume(
        {"tree_learner": "data", "tpu_mesh_shape": "data:2"},
        tmp=tmp_path)
    assert _model(b1) == _model(b2)


def test_resume_restores_eval_history(tmp_path):
    """record_evaluation continues mid-stream: the resumed run's evals
    dict must equal the straight run's for every iteration, including
    the pre-crash ones it never computed itself."""
    p = dict(BASE, learning_rate=0.3)
    ds, vs = _mk(p)
    ev1: dict = {}
    lgb.train(dict(p), ds, num_boost_round=10, valid_sets=[vs],
              verbose_eval=False, evals_result=ev1)
    p2 = dict(p, tpu_checkpoint_dir=str(tmp_path), tpu_checkpoint_freq=4)
    ds, vs = _mk(p)
    lgb.train(dict(p2), ds, num_boost_round=6, valid_sets=[vs],
              verbose_eval=False)
    ds, vs = _mk(p)
    ev2: dict = {}
    lgb.train(dict(p2), ds, num_boost_round=10, valid_sets=[vs],
              verbose_eval=False, evals_result=ev2)
    assert ev1 == ev2


# ---------------------------------------------------------------------------
# checkpoint mechanics: atomicity, validation, pruning, config digest
# ---------------------------------------------------------------------------

def test_corrupt_newest_checkpoint_falls_back(tmp_path):
    p = dict(BASE, tpu_checkpoint_dir=str(tmp_path), tpu_checkpoint_freq=3)
    ds, vs = _mk(p)
    lgb.train(dict(p), ds, num_boost_round=7, valid_sets=[vs],
              verbose_eval=False)
    cks = sorted(glob.glob(os.path.join(str(tmp_path), "ckpt_*")))
    assert len(cks) == 2  # iterations 3 and 6
    with open(os.path.join(cks[-1], "model.txt"), "a") as fh:
        fh.write("corruption")
    mgr = CheckpointManager(str(tmp_path))
    peeked = mgr.peek(Config.from_params(p))
    assert peeked is not None
    assert peeked[0] == cks[0]  # fell back to the older valid one
    assert peeked[1]["iteration"] == 3


def test_orphan_tmp_dirs_ignored_and_swept(tmp_path):
    orphan = tmp_path / ".tmp-9999-5"
    orphan.mkdir()
    (orphan / "model.txt").write_text("partial")
    p = dict(BASE, tpu_checkpoint_dir=str(tmp_path), tpu_checkpoint_freq=4)
    mgr = CheckpointManager(str(tmp_path))
    assert mgr.peek(Config.from_params(p)) is None  # orphan is invisible
    ds, vs = _mk(p)
    lgb.train(dict(p), ds, num_boost_round=5, valid_sets=[vs],
              verbose_eval=False)
    assert not orphan.exists()  # swept by the first real save


def test_checkpoint_pruning_keeps_newest(tmp_path):
    p = dict(BASE, tpu_checkpoint_dir=str(tmp_path), tpu_checkpoint_freq=2,
             tpu_checkpoint_keep=2)
    ds, vs = _mk(p)
    lgb.train(dict(p), ds, num_boost_round=9, valid_sets=[vs],
              verbose_eval=False)
    names = sorted(os.path.basename(d) for d in
                   glob.glob(os.path.join(str(tmp_path), "ckpt_*")))
    assert names == ["ckpt_00000006", "ckpt_00000008"]


def test_stale_foreign_config_checkpoints_pruned(tmp_path):
    """A reused checkpoint dir: a previous run's HIGHER-iteration
    checkpoints under a different config must not shadow (and then
    out-prune) the fresh run's — after the fresh run saves, its own
    checkpoint is the resumable one."""
    old = dict(BASE, num_leaves=15, tpu_checkpoint_dir=str(tmp_path),
               tpu_checkpoint_freq=5)
    ds, vs = _mk(old)
    lgb.train(dict(old), ds, num_boost_round=11, valid_sets=[vs],
              verbose_eval=False)  # leaves ckpt_00000005/10 under old cfg
    new = dict(BASE, tpu_checkpoint_dir=str(tmp_path),
               tpu_checkpoint_freq=3)
    ds, vs = _mk(new)
    lgb.train(dict(new), ds, num_boost_round=4, valid_sets=[vs],
              verbose_eval=False)  # digest mismatch -> fresh + ckpt at 3
    names = sorted(os.path.basename(d) for d in
                   glob.glob(os.path.join(str(tmp_path), "ckpt_*")))
    assert names == ["ckpt_00000003"]  # stale foreign ones removed
    mgr = CheckpointManager(str(tmp_path))
    peeked = mgr.peek(Config.from_params(new))
    assert peeked is not None and peeked[1]["iteration"] == 3


def test_resume_bit_identical_learning_rate_schedule(tmp_path):
    """A reset_parameter(learning_rate=[...]) schedule across a crash:
    the first resumed iteration must train at the SCHEDULED rate, not
    the checkpoint-restored one."""
    # the silent-skip case: params carry learning_rate=0.1 and the
    # schedule value AT the resume iteration is also 0.1, while the
    # restored shrinkage is 0.2 — an unreconciled reset_parameter sees
    # "no change" and trains the first resumed iteration at 0.2
    p = dict(BASE, learning_rate=0.1)
    n = 8
    lrs = [0.2, 0.2, 0.2, 0.2, 0.1, 0.1, 0.1, 0.1]
    ds, vs = _mk(p)
    b1 = lgb.train(dict(p), ds, num_boost_round=n, valid_sets=[vs],
                   verbose_eval=False, learning_rates=list(lrs))
    p2 = dict(p, tpu_checkpoint_dir=str(tmp_path), tpu_checkpoint_freq=2)
    # crash from a USER callback at iteration 5 (a wedge would write a
    # boundary checkpoint carrying the already-reset rate, hiding the
    # bug): the newest checkpoint is the periodic one at iteration 4,
    # whose restored shrinkage (0.2, from iteration 3) differs from the
    # schedule at the resume point (0.1)

    class _Boom(Exception):
        pass

    def boom(env):
        if env.iteration == 5:
            raise _Boom()
    boom.order = 99
    ds, vs = _mk(p)
    with pytest.raises(_Boom):
        lgb.train(dict(p2), ds, num_boost_round=n, valid_sets=[vs],
                  verbose_eval=False, learning_rates=list(lrs),
                  callbacks=[boom])
    ds, vs = _mk(p)
    b2 = lgb.train(dict(p2), ds, num_boost_round=n, valid_sets=[vs],
                   verbose_eval=False, learning_rates=list(lrs))
    assert _model(b1) == _model(b2)


def test_config_mismatch_refuses_resume(tmp_path):
    p = dict(BASE, tpu_checkpoint_dir=str(tmp_path), tpu_checkpoint_freq=3)
    ds, vs = _mk(p)
    lgb.train(dict(p), ds, num_boost_round=4, valid_sets=[vs],
              verbose_eval=False)
    mgr = CheckpointManager(str(tmp_path))
    assert mgr.peek(Config.from_params(p)) is not None
    changed = dict(p, num_leaves=15)
    assert mgr.peek(Config.from_params(changed)) is None


# config_digest at PR 30's parent (2711f6e): the default Config, BASE, one
# configuration away from the defaults, and BASE with the double-buffered
# wave schedule on (``tpu_wave_overlap``, which went with PR 30)
PARENT_DIGESTS = {"default": "cc1dad09c31e07f7", "base": "9ea97afdd4caf36b",
                  "other": "e416cc55c7d18a87",
                  "base_overlap_on": "ff6f311be236980c"}
# the measurement knobs that went later (profile mode, the xprof window)
# at what a run set them to: the parent's default digest
PARENT_DIGESTS["measurement_knobs"] = PARENT_DIGESTS["default"]


@pytest.mark.parametrize("case,params", [
    ("default", {}), ("base", BASE),
    ("other", {"objective": "binary", "num_leaves": 63,
               "learning_rate": 0.05, "tpu_hist_dtype": "int16",
               "bagging_fraction": 0.8, "bagging_freq": 1, "verbose": -1}),
    ("measurement_knobs", {"tpu_profile": True, "tpu_xprof": False,
                           "tpu_xprof_iters": 3})])
def test_config_digest_is_the_parents_across_the_knobs_that_went(case,
                                                                 params):
    """Five parameters left the configuration in PR 30; four were skipped by
    the digest and one (``tpu_wave_overlap``) was hashed, so it stays in the
    hashed items as the constant every run now has.  So do the two hashed
    knobs of the retired measured-roofline capture (``tpu_xprof``,
    ``tpu_xprof_iters``); ``tpu_profile`` was skipped.  A digest that moved
    would refuse every resume across the change."""
    cfg = Config.from_params(dict(params)) if params else Config()
    assert config_digest(cfg) == PARENT_DIGESTS[case]


def test_checkpoints_of_the_parent_resume_or_refuse_as_they_did(tmp_path):
    """A checkpoint the parent wrote resumes under the change; one it wrote
    with the wave schedule on grew other trees, and is still refused."""
    p = dict(BASE, tpu_checkpoint_dir=str(tmp_path), tpu_checkpoint_freq=3)
    ds, vs = _mk(p)
    lgb.train(dict(p), ds, num_boost_round=4, valid_sets=[vs],
              verbose_eval=False)
    mgr = CheckpointManager(str(tmp_path))
    (path, meta), = [mgr.peek(Config.from_params(p))]
    assert meta["config_digest"] == PARENT_DIGESTS["base"]
    meta_file = os.path.join(path, "meta.json")
    with open(meta_file) as fh:
        doc = json.load(fh)
    doc["config_digest"] = PARENT_DIGESTS["base_overlap_on"]
    with open(meta_file, "w") as fh:
        json.dump(doc, fh)
    # asking for the schedule is an unknown parameter now: it changes nothing
    assert mgr.peek(Config.from_params(dict(p, tpu_wave_overlap=True))) \
        is None
    assert mgr.peek(Config.from_params(p)) is None


def test_config_digest_ignores_operational_knobs():
    a = Config.from_params(dict(BASE))
    b = Config.from_params(dict(BASE, tpu_checkpoint_dir="/x",
                                tpu_telemetry="/y", output_model="z.txt",
                                tpu_watchdog=True))
    c = Config.from_params(dict(BASE, learning_rate=0.42))
    assert config_digest(a) == config_digest(b)
    assert config_digest(a) != config_digest(c)


def test_checkpoint_events_validate(tmp_path):
    from lightgbm_tpu.obs.report import (load_events, robust_summary,
                                         validate_events)
    sink = tmp_path / "telem"
    obs.enable(str(sink))
    try:
        p = dict(BASE, tpu_checkpoint_dir=str(tmp_path / "ck"),
                 tpu_checkpoint_freq=3)
        ds, vs = _mk(p)
        lgb.train(dict(p), ds, num_boost_round=4, valid_sets=[vs],
                  verbose_eval=False)
        ds, vs = _mk(p)
        lgb.train(dict(p), ds, num_boost_round=6, valid_sets=[vs],
                  verbose_eval=False)
    finally:
        obs.disable()
    events = load_events(str(sink))
    assert validate_events(events) == []
    r = robust_summary(events)
    assert r["checkpoints"] >= 2
    assert r["restores"] == 1
    assert r["resumed_from_iteration"] == 3
    assert r["last_checkpoint"]["iteration"] == 6


# ---------------------------------------------------------------------------
# fault-injection harness
# ---------------------------------------------------------------------------

def test_fault_spec_grammar():
    specs = faults.parse_spec(
        "device_execute:transient@iter=3&n=2;"
        "serve_device:raise;collective:sleep=0.5@call=2&p=0.5")
    assert [s.point for s in specs] == ["device_execute", "serve_device",
                                       "collective"]
    assert specs[0].action == "transient" and specs[0].iter_ == 3 \
        and specs[0].remaining == 2
    assert specs[1].action == "raise" and specs[1].remaining == 1
    assert specs[2].action == "sleep" and specs[2].arg == 0.5 \
        and specs[2].call == 2 and specs[2].p == 0.5
    for bad in ("nocolon", "p:unknown_action", "p:raise@call"):
        with pytest.raises(ValueError):
            faults.parse_spec(bad)


def test_fault_fires_deterministically():
    faults.configure("pt:transient@call=2&n=1")
    faults.check("pt")                      # call 1: no fire
    with pytest.raises(FaultTransient):
        faults.check("pt")                  # call 2: fires
    faults.check("pt")                      # n exhausted
    faults.configure("pt:raise@iter=5")
    faults.check("pt", iteration=4)
    with pytest.raises(FaultInjected):
        faults.check("pt", iteration=5)


def test_fault_probability_seeded():
    def fires(seed):
        faults.configure("pt:raise@p=0.5&n=-1", seed=seed)
        out = []
        for _ in range(32):
            try:
                faults.check("pt")
                out.append(0)
            except FaultInjected:
                out.append(1)
        return out
    a, b, c = fires(3), fires(3), fires(4)
    assert a == b            # same seed -> identical schedule
    assert a != c            # different seed -> different schedule
    assert 0 < sum(a) < 32   # actually probabilistic


# ---------------------------------------------------------------------------
# watchdog: classification, backoff, retry, policies, stall
# ---------------------------------------------------------------------------

def test_classify_error_patterns():
    assert classify_error(RuntimeError("UNAVAILABLE: socket closed")) \
        == "transient"
    assert classify_error(RuntimeError("RESOURCE_EXHAUSTED: hbm")) \
        == "transient"
    assert classify_error(FaultTransient("x")) == "transient"
    assert classify_error(FaultInjected("x")) == "fatal"
    assert classify_error(ValueError("bad shape")) == "fatal"
    assert classify_text("", timed_out=True) == "wedge"
    assert classify_text("DEADLINE_EXCEEDED while waiting") == "transient"
    assert classify_text("AssertionError: 1 != 2") is None


def test_backoff_deterministic_bounded():
    a = backoff_delays(5, base_s=0.1, cap_s=0.8, seed=9)
    b = backoff_delays(5, base_s=0.1, cap_s=0.8, seed=9)
    assert a == b
    assert all(d <= 0.8 * 1.25 + 1e-9 for d in a)
    assert a[1] > a[0]  # exponential growth below the cap


def test_guard_retries_transient_then_succeeds():
    faults.configure("pt:transient@n=2")
    guard = DeviceGuard(policy="retry", retries=3, backoff_base_s=0.001,
                        stall_timeout_s=-1.0)
    calls = []
    out = guard.run(lambda: calls.append(1) or "ok", point="pt")
    assert out == "ok"
    assert len(calls) == 1          # the two faulted attempts never ran fn
    assert guard.retry_count == 2


def test_guard_abort_policy_no_retry():
    faults.configure("pt:transient@n=-1")
    guard = DeviceGuard(policy="abort", retries=3, stall_timeout_s=-1.0)
    with pytest.raises(DeviceWedgedError):
        guard.run(lambda: "never", point="pt")


def test_guard_fallback_reexecutes():
    faults.configure("pt:raise")
    guard = DeviceGuard(policy="fallback", retries=0, stall_timeout_s=-1.0)
    assert guard.run(lambda: 42, point="pt") == 42


def test_guard_inactive_is_passthrough():
    guard = DeviceGuard(policy="retry", enabled=False)
    assert not guard.active
    assert guard.run(lambda: 7) == 7


def test_guard_stall_stamped_in_flight_ring():
    obs.enable_flight(32)
    guard = DeviceGuard(policy="retry", enabled=True, stall_timeout_s=0.05)
    guard.run(lambda: time.sleep(0.15) or 1, point="slowpt")
    stalls = [e for e in obs.flight_snapshot()
              if e.get("event") == "device_stall"
              and e.get("point") == "slowpt"]
    assert len(stalls) == 1
    assert stalls[0]["deadline_s"] == 0.05


def test_train_wedge_abort_writes_boundary_checkpoint(tmp_path):
    """A fatal device fault mid-train under abort: DeviceWedgedError +
    a rolled-back boundary checkpoint that resumes bit-exactly."""
    p = dict(BASE, bagging_fraction=0.8, bagging_freq=2)
    ds, vs = _mk(p)
    b_ref = lgb.train(dict(p), ds, num_boost_round=6, valid_sets=[vs],
                      verbose_eval=False)
    faults.configure("device_execute:raise@iter=3")
    p2 = dict(p, tpu_on_device_error="abort",
              tpu_checkpoint_dir=str(tmp_path), tpu_checkpoint_freq=0)
    ds, vs = _mk(p2)
    with pytest.raises(DeviceWedgedError):
        lgb.train(dict(p2), ds, num_boost_round=6, valid_sets=[vs],
                  verbose_eval=False)
    faults.disarm()
    cks = glob.glob(os.path.join(str(tmp_path), "ckpt_*"))
    assert len(cks) == 1 and cks[0].endswith("ckpt_00000003")
    ds, vs = _mk(p2)
    b2 = lgb.train(dict(p2), ds, num_boost_round=6, valid_sets=[vs],
                   verbose_eval=False)
    assert _model(b_ref) == _model(b2)


def test_train_transient_retry_bit_identical():
    p = dict(BASE)
    ds, vs = _mk(p)
    b_ref = lgb.train(dict(p), ds, num_boost_round=5, valid_sets=[vs],
                      verbose_eval=False)
    faults.configure("device_execute:transient@iter=2")
    ds, vs = _mk(p)
    b2 = lgb.train(dict(p), ds, num_boost_round=5, valid_sets=[vs],
                   verbose_eval=False)
    assert _model(b_ref) == _model(b2)


# ---------------------------------------------------------------------------
# serve: degradation is no longer a one-way latch
# ---------------------------------------------------------------------------

def _serve_booster():
    ds = lgb.Dataset(X, label=y, params=dict(BASE))
    return lgb.train(dict(BASE), ds, num_boost_round=4, verbose_eval=False)


def test_serve_reprobe_recovers():
    from lightgbm_tpu.serve import PredictorSession
    from lightgbm_tpu.serve.metrics import (parse_prometheus,
                                            render_prometheus)
    bst = _serve_booster()
    ref = bst.predict(X[:16])
    faults.configure("serve_device:raise@call=1")
    with PredictorSession(bst, config=dict(
            BASE, tpu_serve_reprobe_s=0.05,
            tpu_serve_max_batch=64)) as sess:
        out1 = sess.predict(X[:16])
        st = sess.stats()
        assert st["degraded"] and st["degraded_transitions"] == 1
        np.testing.assert_allclose(out1, ref, atol=1e-6)
        prom = parse_prometheus(render_prometheus(sess))
        assert prom["tpu_serve_degraded"] == 1.0
        time.sleep(0.06)
        out2 = sess.predict(X[:16])
        st = sess.stats()
        assert not st["degraded"] and st["recoveries"] == 1
        np.testing.assert_allclose(out2, ref, atol=1e-6)
        prom = parse_prometheus(render_prometheus(sess))
        assert prom["tpu_serve_degraded"] == 0.0
        assert prom["tpu_serve_degraded_transitions_total"] == 1.0
        assert prom["tpu_serve_recoveries_total"] == 1.0


def test_serve_reprobe_zero_keeps_latch():
    from lightgbm_tpu.serve import PredictorSession
    bst = _serve_booster()
    faults.configure("serve_device:raise@call=1")
    with PredictorSession(bst, config=dict(
            BASE, tpu_serve_reprobe_s=0.0,
            tpu_serve_max_batch=64)) as sess:
        sess.predict(X[:8])
        assert sess.stats()["degraded"]
        time.sleep(0.05)
        sess.predict(X[:8])
        assert sess.stats()["degraded"]  # 0 disables re-probing


def test_serve_health_recovers_over_http():
    from lightgbm_tpu.serve import PredictorSession, PredictServer
    import urllib.request
    bst = _serve_booster()
    faults.configure("serve_device:raise@call=1")
    sess = PredictorSession(bst, config=dict(
        BASE, tpu_serve_reprobe_s=0.05, tpu_serve_max_batch=64))
    with PredictServer(sess) as srv:
        body = json.dumps({"rows": X[:4].tolist()}).encode()
        req = urllib.request.Request(srv.url + "/predict", data=body,
                                     headers={"Content-Type":
                                              "application/json"})
        urllib.request.urlopen(req, timeout=10).read()
        health = json.loads(urllib.request.urlopen(
            srv.url + "/health", timeout=10).read())
        assert health["status"] == "degraded"
        time.sleep(0.06)
        urllib.request.urlopen(req, timeout=10).read()
        health = json.loads(urllib.request.urlopen(
            srv.url + "/health", timeout=10).read())
        assert health["status"] == "ok"
        assert health["recoveries"] == 1


# ---------------------------------------------------------------------------
# graceful preemption: SIGTERM mid-train -> checkpoint -> resume
# ---------------------------------------------------------------------------

_CHILD = r"""
import os, sys, time
import numpy as np
import lightgbm_tpu as lgb

data = np.load(sys.argv[1])
ckpt = sys.argv[2]
p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
     "verbose": -1, "seed": 1, "bagging_fraction": 0.8, "bagging_freq": 2,
     "tpu_checkpoint_dir": ckpt, "tpu_checkpoint_freq": 2}

def beat(env):
    print(f"ITER {env.iteration + 1}", flush=True)
    time.sleep(0.15)
beat.order = 99

ds = lgb.Dataset(data["X"], label=data["y"], params=p)
print("READY", flush=True)
lgb.train(p, ds, num_boost_round=12, verbose_eval=False, callbacks=[beat])
print("FINISHED", flush=True)
"""


@pytest.mark.slow
def test_sigterm_checkpoints_and_resumes(tmp_path):
    """Kill a training subprocess mid-run: it must write a final
    checkpoint and exit 143; resuming in-process must reproduce the
    uninterrupted model bit-exactly."""
    data = tmp_path / "data.npz"
    np.savez(data, X=X, y=y)
    ck = tmp_path / "ck"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    proc = subprocess.Popen([sys.executable, "-c", _CHILD, str(data),
                             str(ck)], stdout=subprocess.PIPE, text=True,
                            env=env, cwd=REPO)
    try:
        deadline = time.time() + 300
        seen = 0
        for line in proc.stdout:
            if line.startswith("ITER"):
                seen = int(line.split()[1])
                if seen >= 3:
                    proc.send_signal(signal.SIGTERM)
                    break
            assert time.time() < deadline
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert seen >= 3
    assert rc == 143  # 128 + SIGTERM: graceful-preemption exit
    cks = glob.glob(os.path.join(str(ck), "ckpt_*"))
    assert cks, "preemption checkpoint missing"

    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
         "verbose": -1, "seed": 1, "bagging_fraction": 0.8,
         "bagging_freq": 2}
    ds = lgb.Dataset(X, label=y, params=dict(p))
    b_ref = lgb.train(dict(p), ds, num_boost_round=12, verbose_eval=False)
    p2 = dict(p, tpu_checkpoint_dir=str(ck), tpu_checkpoint_freq=2)
    ds = lgb.Dataset(X, label=y, params=dict(p2))
    b2 = lgb.train(dict(p2), ds, num_boost_round=12, verbose_eval=False)
    assert _model(b_ref) == _model(b2)


# ---------------------------------------------------------------------------
# tools: wedge-retry path + fault-matrix plumbing
# ---------------------------------------------------------------------------

def _import_tool(name):
    tools = os.path.join(REPO, "tools")
    sys.path.insert(0, tools)
    try:
        return __import__(name)
    finally:
        sys.path.remove(tools)


def test_tpu_window_wedge_retry_recovers():
    """A leg that dies with a transient runtime error once and succeeds
    on retry is stamped wedge_retries=1/recovered and the window is NOT
    abandoned."""
    tw = _import_tool("tpu_window")
    calls = {"n": 0}

    def runner(argv, **kw):
        import types
        calls["n"] += 1
        if calls["n"] == 1:
            return types.SimpleNamespace(
                returncode=1, stdout="",
                stderr="RuntimeError: UNAVAILABLE: backend wedge")
        return types.SimpleNamespace(returncode=0,
                                     stdout='{"value": 1}\n', stderr="")

    legs = [{"name": "bench", "argv": ["python", "bench.py"], "env": {},
             "parse_json": True}]
    res = tw.run_legs(legs, runner=runner, timeout=10, wedge_retries=2,
                      backoff_s=0.01)
    rec = res["bench"]
    assert rec["rc"] == 0
    assert rec["wedge_retries"] == 1
    assert rec["wedge_class"] == "transient"
    assert rec["recovered"] is True
    assert rec["parsed"] == {"value": 1}
    assert calls["n"] == 2


def test_tpu_window_unrecovered_leg_not_counted_as_recovered():
    """A leg that retries and STILL fails must not contribute to the
    round-level wedge_retries stamp — the round is broken, not
    recovered."""
    tw = _import_tool("tpu_window")

    def runner(argv, **kw):
        import types
        return types.SimpleNamespace(
            returncode=1, stdout="",
            stderr="RuntimeError: UNAVAILABLE: backend wedge")

    legs = [{"name": "bench", "argv": ["python", "bench.py"], "env": {},
             "parse_json": False}]
    res = tw.run_legs(legs, runner=runner, timeout=10, wedge_retries=2,
                      backoff_s=0.01)
    rec = res["bench"]
    assert rec["rc"] == 1
    assert rec["wedge_retries"] == 2
    assert rec["recovered"] is False
    # the round-level stamp counts only RECOVERED legs' retries
    total = sum(r.get("wedge_retries", 0) for r in res.values()
                if r.get("recovered"))
    assert total == 0


def test_tpu_window_real_failure_not_retried():
    tw = _import_tool("tpu_window")
    calls = {"n": 0}

    def runner(argv, **kw):
        import types
        calls["n"] += 1
        return types.SimpleNamespace(returncode=1, stdout="",
                                     stderr="AssertionError: wrong value")

    legs = [{"name": "bench", "argv": ["python", "bench.py"], "env": {},
             "parse_json": False}]
    res = tw.run_legs(legs, runner=runner, timeout=10, wedge_retries=3,
                      backoff_s=0.01)
    assert res["bench"]["rc"] == 1
    assert "wedge_retries" not in res["bench"]
    assert calls["n"] == 1


def test_bench_history_flags_recovered_rounds(tmp_path):
    bh = _import_tool("bench_history")
    # no "backend" field: bench.py emits it only on degraded rounds,
    # which take the separate canary path
    rec = {"n": 1, "kind": "manual_window", "wedge_retries": 2,
           "parsed": {"rows": 1000, "iters": 5, "num_leaves": 31,
                      "max_bin": 255, "value": 2.5,
                      "unit": "row_iters_per_s"}}
    path = tmp_path / "BENCH_manual_r01.json"
    path.write_text(json.dumps(rec))
    row = bh.load_round(str(path))
    assert row["recovered"] == 2
    assert "recovered after 2 wedge retries" in row["note"]
    assert row["metrics"]["wedge_retries"] == 2.0
    # a clean round carries no flag
    rec2 = dict(rec, wedge_retries=0)
    path2 = tmp_path / "BENCH_manual_r02.json"
    path2.write_text(json.dumps(rec2))
    assert "recovered" not in bh.load_round(str(path2))


def test_run_suite_faults_tier_stubbed():
    rs = _import_tool("run_suite")

    def fake(argv, **kw):
        import types
        line = json.dumps({"kind": "fault_matrix", "ok": True,
                           "checks": {"a": True, "b": True}})
        return types.SimpleNamespace(returncode=0, stdout=line + "\n",
                                     stderr="")

    res = rs.run_tool_smoke("faults", 60, runner=fake)
    assert res["ok"] is True
    assert res["counts"] == {"passed": 2, "failed": 0}
    assert res["cmd"] == "tools/fault_matrix.py --json"
