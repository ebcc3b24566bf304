"""Serving fleet tests: registry hot-swap/rollback, replica router,
circuit breakers, priority shedding (ISSUE 10).

The contracts under test:

- a canary-gated swap is ATOMIC and request-loss-free under concurrent
  mixed /predict + /explain traffic, with every response attributable
  to exactly one model version (version echoed, predictions bit-match
  that version's model);
- a canary rejection leaves the old version serving, untouched;
- rollback (manual and automatic post-swap) restores the resident
  previous version instantly;
- one wedged replica of a routed pair degrades capacity, not
  availability (breaker opens, half-open probe recovers);
- overload sheds low-priority requests first, with per-class counters.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.config import Config
from lightgbm_tpu.robust import faults
from lightgbm_tpu.robust.watchdog import CircuitBreaker
from lightgbm_tpu.serve import (ModelRegistry, PredictorSession,
                                PredictServer, ReplicaRouter,
                                ServeOverloadError, SwapRejected)

P = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
     "verbose": -1}


@pytest.fixture(autouse=True)
def _disarm():
    faults.disarm()
    yield
    faults.disarm()


@pytest.fixture(scope="module")
def fleet_models(tmp_path_factory):
    """Two small models over the same feature space whose predictions
    differ, saved to files, plus the probe matrix."""
    rng = np.random.default_rng(3)
    X = rng.normal(size=(500, 6))
    X[rng.random(X.shape) < 0.04] = np.nan
    y = (np.nan_to_num(X[:, 0]) - 0.4 * np.nan_to_num(X[:, 2]) > 0
         ).astype(np.float64)
    b1 = lgb.train(P, lgb.Dataset(X, label=y, params=P),
                   num_boost_round=4)
    P2 = dict(P, num_leaves=5, learning_rate=0.2)
    b2 = lgb.train(P2, lgb.Dataset(X, label=y, params=P2),
                   num_boost_round=6)
    d = tmp_path_factory.mktemp("fleet_models")
    m1, m2 = str(d / "m1.txt"), str(d / "m2.txt")
    b1.save_model(m1)
    b2.save_model(m2)
    return m1, b1, m2, b2, X


def _cfg(**over):
    base = dict(P, tpu_serve_max_batch=64, tpu_serve_max_wait_ms=1.0,
                tpu_serve_canary_rows=16, tpu_serve_canary_probes=2,
                tpu_serve_rollback_watch_s=0.0, tpu_serve_reprobe_s=0.0)
    base.update(over)
    return Config.from_params(base)


# ---------------------------------------------------------------------
# circuit breaker unit behavior
# ---------------------------------------------------------------------

def test_breaker_trips_and_half_opens():
    br = CircuitBreaker(trip_after=2, backoff_base_s=0.05,
                        backoff_cap_s=0.1, seed=0)
    assert br.allow() and br.state == "closed"
    br.record_failure(RuntimeError("UNAVAILABLE: hiccup"))
    assert br.state == "closed"          # one transient is not a trip
    br.record_failure(RuntimeError("UNAVAILABLE: hiccup"))
    assert br.state == "open" and not br.allow()
    time.sleep(0.08)
    assert br.allow() and br.state == "half_open"  # exactly one probe
    assert not br.allow()                # second concurrent probe denied
    br.record_ok()
    assert br.state == "closed" and br.allow()


def test_breaker_fatal_trips_immediately_and_reopens_on_probe_failure():
    br = CircuitBreaker(trip_after=5, backoff_base_s=0.03,
                        backoff_cap_s=0.05, seed=1)
    assert br.record_failure(ValueError("nonsense")) == "fatal"
    assert br.state == "open" and br.opens == 1
    time.sleep(0.05)
    assert br.allow()                    # half-open probe
    br.record_failure(RuntimeError("UNAVAILABLE: still dead"))
    assert br.state == "open" and br.opens == 2  # probe failure reopens


# ---------------------------------------------------------------------
# replica router
# ---------------------------------------------------------------------

def test_router_failover_on_wedged_replica(fleet_models):
    m1, b1, _, _, X = fleet_models
    router = ReplicaRouter(m1, n_replicas=2, config=_cfg())
    ref = b1.predict(X[:8])
    try:
        faults.configure("serve_replica_0:raise@n=-1")
        for _ in range(6):
            t = router.submit(X[:8])
            assert t.replica.idx == 1    # survivor carries the traffic
            assert np.allclose(router.result(t, timeout=30), ref,
                               atol=1e-6)
        st = router.stats()
        assert st["replicas"][0]["breaker"]["state"] in ("open",
                                                         "half_open")
        assert st["failovers"] >= 1
        assert not st["degraded"]        # fleet still serving
        faults.disarm()
        # half-open probe re-admits replica 0 once the backoff passes
        deadline = time.time() + 10
        while (router.replicas[0].breaker.state != "closed"
               and time.time() < deadline):
            router.result(router.submit(X[:4]), timeout=30)
            time.sleep(0.1)
        assert router.replicas[0].breaker.state == "closed"
        assert router.routable_count() == 2
    finally:
        router.close()


def test_router_drain_removes_replica_from_routing(fleet_models):
    m1, _, _, _, X = fleet_models
    router = ReplicaRouter(m1, n_replicas=2, config=_cfg())
    try:
        router.drain(0)
        for _ in range(4):
            t = router.submit(X[:4])
            assert t.replica.idx == 1
        assert router.routable_count() == 1
        router.undrain(0)
        assert router.routable_count() == 2
    finally:
        router.close()


def test_router_all_replicas_down_raises_overload(fleet_models):
    m1, _, _, _, X = fleet_models
    router = ReplicaRouter(m1, n_replicas=2, config=_cfg())
    try:
        router.drain(0)
        router.drain(1)
        with pytest.raises(ServeOverloadError):
            router.submit(X[:4])
    finally:
        router.close()


# ---------------------------------------------------------------------
# registry: swap / canary / rollback
# ---------------------------------------------------------------------

def test_swap_flips_and_rollback_restores(fleet_models):
    m1, b1, m2, b2, X = fleet_models
    reg = ModelRegistry(config=_cfg(), n_replicas=1)
    try:
        reg.add_model("default", m1)
        t = reg.submit(X[:8])
        assert t.version == 1
        assert np.allclose(reg.result(t), b1.predict(X[:8]), atol=1e-6)

        report = reg.swap("default", m2)
        assert report["ok"] and report["to_version"] == 2
        assert report["canary"]["checks"]["parity"]
        t2 = reg.submit(X[:8])
        assert t2.version == 2
        assert np.allclose(reg.result(t2), b2.predict(X[:8]), atol=1e-6)

        rb = reg.rollback("default", reason="test")
        assert rb["to_version"] == 1
        t3 = reg.submit(X[:8])
        assert t3.version == 1
        assert np.allclose(reg.result(t3), b1.predict(X[:8]), atol=1e-6)
        row = reg.models()[0]
        assert row["swaps"] == 1 and row["rollbacks"] == 1
        # after a rollback nothing is resident to roll back to
        with pytest.raises(RuntimeError):
            reg.rollback("default")
    finally:
        reg.close()


def test_canary_rejection_leaves_old_model_serving(fleet_models):
    m1, b1, m2, _, X = fleet_models
    reg = ModelRegistry(config=_cfg(), n_replicas=1)
    try:
        reg.add_model("default", m1)
        faults.configure("serve_canary:raise@call=1")
        with pytest.raises(SwapRejected):
            reg.swap("default", m2)
        faults.disarm()
        row = reg.models()[0]
        assert row["live_version"] == 1 and row["swaps_rejected"] == 1
        t = reg.submit(X[:8])
        assert t.version == 1
        assert np.allclose(reg.result(t), b1.predict(X[:8]), atol=1e-6)
    finally:
        reg.close()


def test_injected_swap_fault_aborts_before_flip(fleet_models):
    m1, b1, m2, _, X = fleet_models
    reg = ModelRegistry(config=_cfg(), n_replicas=1)
    try:
        reg.add_model("default", m1)
        faults.configure("serve_swap:raise@call=1")
        with pytest.raises(SwapRejected):
            reg.swap("default", m2)
        faults.disarm()
        assert reg.resolve(None).version == 1
        t = reg.submit(X[:4])
        assert np.allclose(reg.result(t), b1.predict(X[:4]), atol=1e-6)
    finally:
        reg.close()


def test_postswap_regression_triggers_auto_rollback(fleet_models,
                                                    tmp_path,
                                                    monkeypatch):
    m1, b1, m2, _, X = fleet_models
    monkeypatch.setenv("LGBM_TPU_FLIGHT_DIR", str(tmp_path))
    reg = ModelRegistry(config=_cfg(tpu_serve_rollback_degraded=1),
                        n_replicas=1)
    try:
        reg.add_model("default", m1)
        assert reg.swap("default", m2)["ok"]
        faults.configure("serve_device:raise@n=-1")
        for _ in range(3):   # degrade v2 (host fallback keeps serving)
            reg.result(reg.submit(X[:4]), timeout=30)
        out = reg.check_postswap("default")
        faults.disarm()
        assert out is not None and str(out["reason"]).startswith("auto:")
        assert reg.resolve(None).version == 1
        assert list(tmp_path.glob("FLIGHT_*.json"))  # rollback post-mortem
        t = reg.submit(X[:4])
        assert np.allclose(reg.result(t), b1.predict(X[:4]), atol=1e-6)
    finally:
        faults.disarm()
        reg.close()


def test_swap_under_concurrent_mixed_traffic_is_loss_free(fleet_models):
    """The tentpole contract: a hot swap under concurrent mixed
    predict + explain traffic loses nothing, and every response is
    bit-consistent with the version it claims."""
    m1, b1, m2, b2, X = fleet_models
    reg = ModelRegistry(config=_cfg(), n_replicas=1)
    expected = {
        1: (b1.predict(X[:32]), b1.predict(X[:32], pred_contrib=True)),
        2: (b2.predict(X[:32]), b2.predict(X[:32], pred_contrib=True)),
    }
    results, lock = [], threading.Lock()
    stop = threading.Event()

    def client(seed):
        rng = np.random.default_rng(seed)
        while not stop.is_set():
            n = int(rng.integers(1, 9))
            lo = int(rng.integers(0, 32 - n + 1))
            explain = rng.random() < 0.3
            try:
                if explain:
                    t = reg.submit_explain(X[lo:lo + n])
                else:
                    t = reg.submit(X[lo:lo + n])
                out = reg.result(t, timeout=60)
                with lock:
                    results.append((t.version, explain, lo, n,
                                    np.asarray(out)))
            except Exception as exc:  # noqa: BLE001 — counted as loss
                with lock:
                    results.append((None, explain, lo, n, repr(exc)))
            time.sleep(0.005)

    try:
        reg.add_model("default", m1)
        threads = [threading.Thread(target=client, args=(s,))
                   for s in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.4)
        assert reg.swap("default", m2)["ok"]
        # until the new version has answered: a fixed 0.4 s was too short
        # for one reply of it under a whole suite's load
        deadline = time.time() + 20.0
        while time.time() < deadline:
            time.sleep(0.4)
            with lock:
                if any(r[0] == 2 for r in results):
                    break
        stop.set()
        for t in threads:
            t.join(30)
        lost = [r for r in results if r[0] is None]
        assert not lost, lost[:3]
        assert len(results) > 10
        versions = {r[0] for r in results}
        assert versions == {1, 2}
        for ver, explain, lo, n, out in results:
            want = expected[ver][1 if explain else 0][lo:lo + n]
            assert out.shape == np.asarray(want).shape
            assert np.allclose(out, want, atol=1e-5), (ver, explain, lo)
    finally:
        stop.set()
        reg.close()


# ---------------------------------------------------------------------
# priority shedding
# ---------------------------------------------------------------------

def test_low_priority_sheds_first(fleet_models, monkeypatch):
    m1, _, _, _, X = fleet_models
    sess = PredictorSession(m1, config=_cfg(
        tpu_serve_max_batch=16, tpu_serve_queue_depth=64,
        tpu_serve_max_wait_ms=50.0))
    orig = sess._run_device

    def slow(bins, **kw):
        time.sleep(0.1)
        return orig(bins, **kw)

    monkeypatch.setattr(sess, "_run_device", slow)
    tickets = [sess.submit(X[:8], priority="normal") for _ in range(6)]
    with pytest.raises(ServeOverloadError) as exc_info:
        sess.submit(X[:8], priority="low")
    assert exc_info.value.priority == "low" and exc_info.value.shed
    tickets.append(sess.submit(X[:8], priority="high"))
    for t in tickets:
        sess.result(t, timeout=60)
    snap = sess.metrics.snapshot()
    assert snap["shed_by_priority"].get("low") == 1
    assert snap["shed_by_priority"].get("high") is None
    assert snap["served_by_priority"].get("high") == 1
    assert snap["served_by_priority"].get("normal") == 6
    sess.close()


def test_unknown_priority_serves_as_normal(fleet_models):
    m1, _, _, _, X = fleet_models
    sess = PredictorSession(m1, config=_cfg())
    t = sess.submit(X[:4], priority="urgent-nonsense")
    sess.result(t, timeout=30)
    assert sess.metrics.snapshot()["served_by_priority"] == {"normal": 1}
    sess.close()


# ---------------------------------------------------------------------
# HTTP fleet surface
# ---------------------------------------------------------------------

def _post(url, payload, timeout=60):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read()), dict(resp.headers)


def test_http_fleet_roundtrip_swap_and_models(fleet_models):
    m1, b1, m2, b2, X = fleet_models
    reg = ModelRegistry(config=_cfg(), n_replicas=2)
    reg.add_model("default", m1)
    server = PredictServer(reg).start()
    url = server.url
    try:
        code, body, _ = _post(url + "/predict",
                              {"rows": X[:4].tolist(),
                               "priority": "high"})
        assert code == 200 and body["version"] == 1
        assert body["model"] == "default" and "replica" in body
        assert np.allclose(body["predictions"], b1.predict(X[:4]),
                           atol=1e-6)
        # /models listing + per-model health
        with urllib.request.urlopen(url + "/models", timeout=30) as r:
            listing = json.loads(r.read())
        assert listing["default"] == "default"
        assert listing["models"][0]["live_version"] == 1
        with urllib.request.urlopen(url + "/health", timeout=30) as r:
            health = json.loads(r.read())
        assert health["status"] == "ok"
        assert len(health["replicas"]) == 2
        assert health["models"]["default"]["live_version"] == 1
        # swap over HTTP, then traffic reflects v2
        code, rep, _ = _post(url + "/models/default/swap",
                             {"model_file": m2}, timeout=120)
        assert code == 200 and rep["ok"] and rep["to_version"] == 2
        code, body, _ = _post(url + "/predict", {"rows": X[:4].tolist()})
        assert body["version"] == 2
        assert np.allclose(body["predictions"], b2.predict(X[:4]),
                           atol=1e-6)
        # rollback over HTTP
        code, rb, _ = _post(url + "/models/default/rollback",
                            {"reason": "test"})
        assert code == 200 and rb["to_version"] == 1
        code, body, _ = _post(url + "/predict", {"rows": X[:4].tolist()})
        assert body["version"] == 1
        # unknown model -> 404
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(url + "/predict", {"rows": X[:4].tolist(),
                                     "model": "nope"})
        assert err.value.code == 404
    finally:
        server.stop(close_session=True)


def test_http_fleet_metrics_exposition(fleet_models):
    from lightgbm_tpu.serve import parse_prometheus
    m1, _, _, _, X = fleet_models
    reg = ModelRegistry(config=_cfg(), n_replicas=2)
    reg.add_model("default", m1)
    server = PredictServer(reg).start()
    try:
        _post(server.url + "/predict", {"rows": X[:4].tolist()})
        with urllib.request.urlopen(server.url + "/metrics",
                                    timeout=30) as r:
            pm = parse_prometheus(r.read().decode())
        assert pm.get('tpu_serve_model_version{model="default"}') == 1.0
        assert pm.get('tpu_serve_swaps_total{model="default"}') == 0.0
        assert pm.get('tpu_serve_rollbacks_total{model="default"}') == 0.0
        assert 'tpu_serve_replica_healthy{replica="r0"}' in pm
        assert 'tpu_serve_replica_breaker_state{replica="r1"}' in pm
        assert 'tpu_serve_shed_total{priority="low"}' in pm
        assert pm.get('tpu_serve_served_total{priority="normal"}') >= 1.0
    finally:
        server.stop(close_session=True)


def test_bare_session_server_unchanged(fleet_models):
    """Back-compat: a server over a bare session has no fleet fields and
    404s the fleet endpoints."""
    m1, b1, _, _, X = fleet_models
    sess = PredictorSession(m1, config=_cfg())
    server = PredictServer(sess).start()
    try:
        code, body, _ = _post(server.url + "/predict",
                              {"rows": X[:3].tolist()})
        assert code == 200 and "version" not in body
        assert np.allclose(body["predictions"], b1.predict(X[:3]),
                           atol=1e-6)
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(server.url + "/models", timeout=30)
        assert err.value.code == 404
    finally:
        server.stop(close_session=True)


# ---------------------------------------------------------------------
# telemetry schemas
# ---------------------------------------------------------------------

def test_fleet_events_validate(fleet_models, tmp_path):
    from lightgbm_tpu.obs.report import (load_events, serve_summary,
                                         validate_events)
    m1, _, m2, _, X = fleet_models
    obs.enable(str(tmp_path / "telem"))
    try:
        reg = ModelRegistry(config=_cfg(), n_replicas=2)
        reg.add_model("default", m1)
        reg.swap("default", m2)
        reg.result(reg.submit(X[:4]))
        reg.rollback("default", reason="test")
        faults.configure("serve_replica_0:raise@n=1")
        router = reg.resolve(None).router
        router.result(router.submit(X[:4]))
    finally:
        faults.disarm()
        reg.close()
        obs.disable()
    events = load_events(str(tmp_path / "telem"))
    names = {e.get("event") for e in events}
    assert {"serve_swap", "serve_canary", "serve_rollback"} <= names
    problems = validate_events(events)
    assert not problems, problems[:5]
    digest = serve_summary(events)
    # the initial deploy is counted apart from real hot-swaps (matching
    # the registry's swaps counter and tpu_serve_swaps_total)
    assert digest["fleet"]["swaps"] == 1
    assert digest["fleet"]["deploys"] == 1
    assert digest["fleet"]["rollbacks"] == 1
