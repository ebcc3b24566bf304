"""Trace plane (ISSUE 6): spans, live /metrics, and the flight recorder.

Pins the request-level tracing contract end to end: an ``X-Request-Id``
entering the HTTP edge must come out as a complete span tree
(request -> queue_wait/coalesce/pad/device_execute, one trace_id),
including the host-fallback path; ``GET /metrics`` must expose a
well-formed Prometheus text document (checked with the minimal parser
the bench shares); a forced device-death degradation must dump a
``FLIGHT_rN.json`` whose last events explain the flip; and
``tools/trace_export.py`` must round-trip a fixture JSONL into a
Perfetto-loadable Chrome trace document.  All CPU-runnable, quick tier.
"""
import glob
import json
import os
import sys
import time
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.serve import (PredictorSession, PredictServer,
                                parse_prometheus)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))


@pytest.fixture(autouse=True)
def _obs_clean():
    """Trace/flight gates are process-wide; every test leaves them off
    (and the phase accumulators trace mode filled are cleared — the
    off-path obs tests assert they never accumulate)."""
    yield
    obs.disable()
    obs.enable_trace(False)
    obs.enable_flight(0)
    obs.reset()


@pytest.fixture(scope="module")
def binary_model(tmp_path_factory):
    rng = np.random.default_rng(0)
    X = rng.normal(size=(600, 5))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5}
    bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                    num_boost_round=10)
    path = str(tmp_path_factory.mktemp("trace") / "binary.txt")
    bst.save_model(path)
    return path


def _post(url, payload, headers=None, timeout=60):
    h = {"Content-Type": "application/json"}
    h.update(headers or {})
    req = urllib.request.Request(url, data=json.dumps(payload).encode(),
                                 headers=h)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, dict(resp.headers), json.loads(resp.read())


def _get(url, timeout=30, raw=False):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        body = resp.read()
        return (resp.status, body.decode()) if raw else \
            (resp.status, json.loads(body))


# ---------------------------------------------------------------------------
# span API
# ---------------------------------------------------------------------------

def test_span_api_nesting_and_sink(tmp_path):
    obs.enable(str(tmp_path / "telem"))
    obs.enable_trace()
    with obs.span("outer", trace_id="t-1", kind="test") as outer:
        assert obs.current_context() == ("t-1", outer.span_id)
        with obs.span("inner") as inner:
            assert inner.trace_id == "t-1"
            assert inner.parent_id == outer.span_id
    assert obs.current_context() == (None, None)
    obs.disable()
    from lightgbm_tpu.obs.report import (load_events, trace_summary,
                                         validate_events)
    events = load_events(str(tmp_path / "telem"))
    spans = [e for e in events if e.get("event") == "span"]
    assert sorted(e["name"] for e in spans) == ["inner", "outer"]
    assert all(e["trace_id"] == "t-1" for e in spans)
    # inner completed first (spans emit at exit) and links to outer
    assert spans[0]["name"] == "inner"
    assert spans[0]["parent_id"] == spans[1]["span_id"]
    assert validate_events(events) == []
    t = trace_summary(events)
    assert t["spans"] == 2 and t["traces"] == 1


def test_trace_id_honors_and_sanitizes_seed():
    assert obs.new_trace_id("req-42") == "req-42"
    assert obs.new_trace_id("a b;c\n") == "a_b_c"
    assert obs.new_trace_id("") != obs.new_trace_id("")
    assert len(obs.new_trace_id("x" * 500)) == 64


def test_span_off_path_is_noop():
    assert not obs.span_record_enabled()
    assert obs.begin_span("nope") is None
    obs.end_span(None)  # must not raise
    assert obs.emit_span("nope", time.time(), 1.0, "t") is None


# ---------------------------------------------------------------------------
# end-to-end propagation: header in -> span tree out
# ---------------------------------------------------------------------------

def test_http_trace_propagation_span_tree(binary_model, tmp_path):
    obs.enable(str(tmp_path / "telem"))
    obs.enable_trace()
    sess = PredictorSession(binary_model, max_batch=32)
    with PredictServer(sess) as server:
        code, headers, body = _post(
            server.url + "/predict", {"rows": np.zeros((6, 5)).tolist()},
            headers={"X-Request-Id": "req-e2e-1"})
        assert code == 200
        assert body["trace_id"] == "req-e2e-1"
        assert headers.get("X-Request-Id") == "req-e2e-1"
    sess.close()
    obs.disable()
    from lightgbm_tpu.obs.report import load_events, validate_events
    events = load_events(str(tmp_path / "telem"))
    assert validate_events(events) == []
    spans = [e for e in events if e.get("event") == "span"
             and e.get("trace_id") == "req-e2e-1"]
    names = {e["name"] for e in spans}
    assert {"serve/request", "serve/queue_wait", "serve/coalesce",
            "serve/pad", "serve/device_execute"} <= names
    root = next(e for e in spans if e["name"] == "serve/request")
    kids = [e for e in spans if e.get("parent_id") == root["span_id"]]
    assert {"serve/queue_wait", "serve/coalesce", "serve/pad",
            "serve/device_execute"} <= {e["name"] for e in kids}
    assert root["attrs"]["status"] == 200
    # the access log rode along: one serve_access per reply
    acc = [e for e in events if e.get("event") == "serve_access"]
    assert any(e["trace_id"] == "req-e2e-1" and e["status"] == 200
               and e["path"] == "/predict" for e in acc)


def test_trace_host_fallback_and_flight_dump(binary_model, tmp_path,
                                             monkeypatch):
    monkeypatch.setenv("LGBM_TPU_FLIGHT_DIR", str(tmp_path))
    obs.enable(str(tmp_path / "telem"))
    obs.enable_trace()
    sess = PredictorSession(binary_model, max_batch=32)

    def boom(forest, bins):
        raise RuntimeError("device backend died mid-flight")

    monkeypatch.setattr(sess, "_device_fn", boom)
    ticket = sess.submit(np.zeros((4, 5)), trace_id="req-fallback")
    out = sess.result(ticket, timeout=30)
    assert out.shape == (4,)
    sess.close()
    obs.disable()
    from lightgbm_tpu.obs.report import load_events
    events = load_events(str(tmp_path / "telem"))
    spans = [e for e in events if e.get("event") == "span"
             and e.get("trace_id") == "req-fallback"]
    names = {e["name"] for e in spans}
    assert "serve/host_fallback" in names
    assert "serve/queue_wait" in names
    assert "serve/device_execute" not in names
    # the degradation dumped the flight ring; its tail explains the flip
    dumps = glob.glob(str(tmp_path / "FLIGHT_r*.json"))
    assert dumps, "degradation must write a FLIGHT_rN.json"
    rec = json.load(open(dumps[0]))
    assert rec["reason"] == "serve_degraded"
    tail = [e["event"] for e in rec["events"][-6:]]
    assert "serve_degraded" in tail
    deg = next(e for e in rec["events"] if e["event"] == "serve_degraded")
    assert "device backend died" in deg["error"]
    assert rec["stats"]["degraded"] is True


def test_degradation_dump_not_suppressed_by_storm_cooldown(binary_model,
                                                           tmp_path,
                                                           monkeypatch):
    """A recent overload-storm dump must not swallow the one-shot
    degradation post-mortem (the cooldown exists to rate-limit storms)."""
    monkeypatch.setenv("LGBM_TPU_FLIGHT_DIR", str(tmp_path))
    sess = PredictorSession(binary_model, max_batch=32)
    sess._flight_dump("overload_storm")
    assert len(glob.glob(str(tmp_path / "FLIGHT_r*.json"))) == 1

    def boom(forest, bins):
        raise RuntimeError("device died seconds after the storm")

    monkeypatch.setattr(sess, "_device_fn", boom)
    sess.predict(np.zeros((3, 5)))  # degrades -> must still dump
    sess.close()
    dumps = sorted(glob.glob(str(tmp_path / "FLIGHT_r*.json")))
    assert len(dumps) == 2
    assert json.load(open(dumps[1]))["reason"] == "serve_degraded"


def test_flight_env_zero_disables_training_ring(monkeypatch):
    """LGBM_TPU_FLIGHT=0 must win over the config default in the
    training path too (a strict-health abort then writes no dump)."""
    monkeypatch.setenv("LGBM_TPU_FLIGHT", "0")
    obs.enable_health("monitor")
    try:
        rng = np.random.default_rng(4)
        X = rng.normal(size=(150, 3))
        y = (X[:, 0] > 0).astype(np.float64)
        params = {"objective": "binary", "num_leaves": 7, "verbose": -1}
        ds = lgb.Dataset(X, label=y, params=params)
        lgb.Booster(params=params, train_set=ds).update()
        assert not obs.flight_enabled()
    finally:
        obs.enable_health("")


def test_keepalive_malformed_followup_gets_fresh_access_state(
        binary_model, tmp_path):
    """On a keep-alive connection, a malformed follow-up request (which
    errors before do_POST/_begin run) must not log under the previous
    request's trace id."""
    import socket

    def read_response(s):
        """Full HTTP response (status line + headers + body) — recv can
        return partial reads, and leftover body bytes would be misread
        as the next response."""
        buf = b""
        while b"\r\n\r\n" not in buf:
            buf += s.recv(65536)
        head, _, rest = buf.partition(b"\r\n\r\n")
        length = 0
        for line in head.split(b"\r\n"):
            if line.lower().startswith(b"content-length:"):
                length = int(line.split(b":", 1)[1])
        while len(rest) < length:
            rest += s.recv(65536)
        return head.split(b"\r\n", 1)[0], rest[:length]

    obs.enable(str(tmp_path / "telem"))
    sess = PredictorSession(binary_model, max_batch=32)
    with PredictServer(sess) as server:
        body = json.dumps({"rows": np.zeros((2, 5)).tolist()}).encode()
        with socket.create_connection((server.host, server.port),
                                      timeout=30) as s:
            s.sendall(b"POST /predict HTTP/1.1\r\n"
                      b"Host: x\r\nContent-Type: application/json\r\n"
                      b"X-Request-Id: keepalive-1\r\n"
                      + f"Content-Length: {len(body)}\r\n\r\n".encode()
                      + body)
            status1, _ = read_response(s)
            assert b"200" in status1
            s.sendall(b"BOGUS\r\n\r\n")
            second = s.recv(65536)
            assert b"400" in second.split(b"\r\n", 1)[0]
    sess.close()
    obs.disable()
    from lightgbm_tpu.obs.report import load_events
    acc = [e for e in load_events(str(tmp_path / "telem"))
           if e.get("event") == "serve_access"]
    bad = [e for e in acc if e["status"] == 400]
    assert bad, "the malformed request must still be access-logged"
    assert bad[0]["trace_id"] == "-", \
        "stale trace id reused for the malformed follow-up"
    assert bad[0]["latency_ms"] == 0.0
    assert any(e["trace_id"] == "keepalive-1" and e["status"] == 200
               for e in acc)


# ---------------------------------------------------------------------------
# live introspection: /metrics, /stats, /health signals, /debug/flight
# ---------------------------------------------------------------------------

def test_metrics_endpoint_prometheus(binary_model):
    sess = PredictorSession(binary_model, max_batch=32)
    with PredictServer(sess) as server:
        for i in range(5):
            _post(server.url + "/predict",
                  {"rows": np.zeros((2 + i, 5)).tolist()})
        code, text = _get(server.url + "/metrics", raw=True)
        assert code == 200
        pm = parse_prometheus(text)
        # request counts by status
        assert pm['tpu_serve_requests_total{status="200"}'] >= 5
        # fixed-bucket histogram: cumulative, monotone, count-consistent
        from lightgbm_tpu.serve.metrics import LATENCY_BUCKETS_MS
        cum = [pm['tpu_serve_request_latency_ms_bucket{le="%g"}' % b]
               for b in LATENCY_BUCKETS_MS]
        assert cum == sorted(cum)
        assert pm['tpu_serve_request_latency_ms_bucket{le="+Inf"}'] \
            == pm["tpu_serve_request_latency_ms_count"] >= 5
        assert pm["tpu_serve_request_latency_ms_sum"] > 0
        # gauges the SLO story needs
        assert pm["tpu_serve_degraded"] == 0
        assert pm["tpu_serve_slo_p99_ms"] > 0
        assert "tpu_serve_slo_burn" in pm
        assert pm["tpu_serve_recompiles_total"] >= 1
        assert "tpu_serve_queue_rows" in pm
        assert "tpu_serve_batch_occupancy" in pm
        assert "tpu_serve_pad_waste_rows_total" in pm

        # /stats mirrors the same numbers as JSON
        code, st = _get(server.url + "/stats")
        assert code == 200
        assert st["metrics"]["latency_count"] \
            == pm["tpu_serve_request_latency_ms_count"]
        # /health carries the load-balancer signals
        code, health = _get(server.url + "/health")
        assert code == 200
        for f in ("queue_rows", "uptime_s", "compile_count", "slo_burn"):
            assert f in health, f
        assert health["uptime_s"] >= 0
        assert health["compile_count"] >= 1
    sess.close()


def test_slo_burn_counts_over_target(binary_model):
    sess = PredictorSession(binary_model, max_batch=32)
    sess.metrics.slo_p99_ms = 10.0
    for ms in (1.0, 2.0, 3.0, 50.0):  # 1 of 4 over target
        sess.metrics.observe(ms)
    # 25% over / 1% budget = 25x burn
    assert sess.metrics.slo_burn() == pytest.approx(25.0)
    sess.metrics.slo_p99_ms = 0.0
    assert sess.metrics.slo_burn() is None
    sess.close()


def test_flight_ring_bounded_and_endpoint(binary_model):
    obs.enable_flight(8)
    for i in range(30):
        obs.emit_span(f"s{i}", time.time(), 0.1, "t-ring")
    snap = obs.flight_snapshot()
    assert len(snap) == 8
    assert snap[-1]["name"] == "s29"  # newest kept, oldest evicted
    sess = PredictorSession(binary_model, max_batch=32)
    with PredictServer(sess) as server:
        _post(server.url + "/predict", {"rows": np.zeros((3, 5)).tolist()})
        code, fl = _get(server.url + "/debug/flight")
        assert code == 200
        assert fl["enabled"] is True and fl["ring_len"] == 8
        assert isinstance(fl["events"], list) and fl["events"]
        # request spans land in the ring even with NO telemetry sink
        assert any(e.get("event") == "span"
                   and e.get("name") == "serve/device_execute"
                   for e in fl["events"])
    sess.close()


# ---------------------------------------------------------------------------
# trace_export round-trip
# ---------------------------------------------------------------------------

def _fixture_events(tmp_path):
    rows = [
        {"event": "span", "t": 100.0, "dur_ms": 5.0, "name": "serve/request",
         "trace_id": "req-1", "span_id": "r1",
         "attrs": {"status": 200, "path": "/predict"}},
        {"event": "span", "t": 100.001, "dur_ms": 1.2,
         "name": "serve/queue_wait", "trace_id": "req-1", "span_id": "q1",
         "parent_id": "r1", "attrs": {"rows": 4}},
        {"event": "span", "t": 100.002, "dur_ms": 2.0,
         "name": "serve/device_execute", "trace_id": "req-1",
         "span_id": "d1", "parent_id": "r1", "attrs": {"bucket": 4}},
        {"event": "span", "t": 99.5, "dur_ms": 400.0,
         "name": "train/iteration", "trace_id": "train-1", "span_id": "i0",
         "attrs": {"iteration": 0}},
        {"event": "span", "t": 99.6, "dur_ms": 300.0,
         "name": "phase/tree growth", "trace_id": "train-1",
         "span_id": "p0", "parent_id": "i0"},
        {"event": "iteration", "t": 101.0, "iteration": 1, "iter_s": 0.4,
         "phase_s": {"tree growth": 0.3}},
    ]
    path = tmp_path / "fixture.jsonl"
    with open(path, "w") as fh:
        for r in rows:
            fh.write(json.dumps(r) + "\n")
    return str(path)


def test_trace_export_roundtrip_fixture(tmp_path):
    import trace_export
    src = _fixture_events(tmp_path)
    out = str(tmp_path / "out.trace.json")
    assert trace_export.main([src, "--out", out]) == 0
    doc = json.load(open(out))  # round-trip through disk
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    # both planes on one timeline: a serving request AND training spans
    assert {e["args"]["trace_id"] for e in xs} == {"req-1", "train-1"}
    assert {m["args"]["name"] for m in metas} == {"req-1", "train-1"}
    # ts rebased to the earliest span; durations in microseconds
    assert min(e["ts"] for e in xs) == 0.0
    exec_ev = next(e for e in xs if e["name"] == "serve/device_execute")
    assert exec_ev["dur"] == pytest.approx(2000.0)
    assert exec_ev["args"]["parent_id"] == "r1"
    assert exec_ev["args"]["bucket"] == 4
    # real span events win; the iteration record is NOT synthesized twice
    assert sum(1 for e in xs if e["name"] == "train/iteration") == 1


def test_trace_export_synthesizes_from_iterations(tmp_path):
    import trace_export
    events = [{"event": "iteration", "t": 10.0 + i, "iteration": i,
               "iter_s": 0.5, "_proc": 0,
               "phase_s": {"tree growth": 0.3, "boosting (grad/hess)": 0.1}}
              for i in range(3)]
    doc = trace_export.events_to_chrome(events)
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert sum(1 for e in xs if e["name"] == "train/iteration") == 3
    assert all(e["args"].get("synthesized") for e in xs)
    assert sum(1 for e in xs if e["name"].startswith("phase/")) == 6


def test_trace_export_empty_stream(tmp_path):
    import trace_export
    doc = trace_export.events_to_chrome([{"event": "summary"}])
    assert doc["traceEvents"] == []


def test_trace_export_straggler_and_reconciliation_tracks():
    """ISSUE 17: the introspection plane's straggler breaches render as
    instants on their own track, flagged like drift latches."""
    import trace_export
    events = [
        {"event": "straggler", "t": 10.0, "rank": 1,
         "phase": "tree growth", "iteration": 4, "ratio": 3.5,
         "median_s": 0.01, "rank_s": 0.035, "consecutive": 3,
         "breach": True, "_proc": 0},
        {"event": "reconciliation", "t": 11.0, "iteration": 5,
         "units": {"partition": {"measured_s": 0.02, "modeled_s": 0.01,
                                 "ratio": 2.0}}, "_proc": 0},
    ]
    doc = trace_export.events_to_chrome(events)
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    st = next(e for e in xs if e["args"]["trace_id"] == "ops/straggler")
    # a straggler always carries breach=True -> the BREACH suffix
    assert st["name"] == "straggler/BREACH"
    assert st["args"]["rank"] == 1 and st["args"]["ratio"] == 3.5
    assert st["args"]["synthesized"] is True
    # the retired reconciliation events have no track of their own: an
    # unknown kind, passed over without a crash
    assert [e["args"]["trace_id"] for e in xs] == ["ops/straggler"]


def test_trace_export_unknown_event_kind_roundtrips():
    """An event kind the exporter has never heard of must pass through
    without crashing — future planes can add kinds freely."""
    import trace_export
    events = [
        {"event": "from_the_future", "t": 1.0, "payload": {"a": [1, 2]},
         "_proc": 0},
        {"event": "reconciliation", "t": 2.0, "iteration": 1,
         "units": {}, "_proc": 0},
    ]
    doc = trace_export.events_to_chrome(events)   # must not raise
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert all(e["args"]["trace_id"] != "from_the_future" for e in xs)
    # and the schema validator skips unknown kinds instead of flagging
    # them
    from lightgbm_tpu.obs.report import validate_events
    problems = validate_events(events)
    assert not any("from_the_future" in p for p in problems)


# ---------------------------------------------------------------------------
# training iteration spans (same schema, same timeline)
# ---------------------------------------------------------------------------

def test_iteration_span_closed_on_health_abort(tmp_path):
    """A strict-health abort mid-iteration must neither leak the
    iteration span onto the thread-local context stack nor lose the
    aborting iteration's span (train_one_iter's try/finally)."""
    rng = np.random.default_rng(3)
    X = rng.normal(size=(200, 3))
    y = (X[:, 0] > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              "tpu_telemetry": str(tmp_path / "telem"), "tpu_trace": True}
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params=params, train_set=ds)
    obs.enable_health("strict")
    try:
        bst.update()  # healthy iteration

        def bad_fobj(preds, train_data):
            g = np.zeros(len(y))
            g[7] = np.nan
            return g, np.ones(len(y))

        with pytest.raises(obs.TrainingHealthError):
            bst.update(fobj=bad_fobj)
    finally:
        obs.enable_health("")
    assert obs.current_context() == (None, None)
    obs.disable()
    obs.enable_trace(False)
    from lightgbm_tpu.obs.report import load_events
    events = load_events(str(tmp_path / "telem"))
    iters = [e for e in events if e.get("event") == "span"
             and e["name"] == "train/iteration"]
    # the aborting iteration's span was still emitted (iterations 0 + 1)
    assert [e["attrs"]["iteration"] for e in iters] == [0, 1]


def test_training_iteration_spans(tmp_path):
    rng = np.random.default_rng(1)
    X = rng.normal(size=(300, 4))
    y = (X[:, 0] > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              "tpu_telemetry": str(tmp_path / "telem"), "tpu_trace": True}
    lgb.train(params, lgb.Dataset(X, label=y, params=params),
              num_boost_round=3)
    obs.disable()
    obs.enable_trace(False)
    from lightgbm_tpu.obs.report import load_events, validate_events
    events = load_events(str(tmp_path / "telem"))
    assert validate_events(events) == []
    spans = [e for e in events if e.get("event") == "span"]
    iters = [e for e in spans if e["name"] == "train/iteration"]
    assert len(iters) == 3
    assert len({e["trace_id"] for e in iters}) == 1  # one training trace
    kids = [e for e in spans
            if e.get("parent_id") == iters[0]["span_id"]]
    assert any(e["name"] == "phase/tree growth" for e in kids)
    assert iters[0]["attrs"]["iteration"] == 0


# ---------------------------------------------------------------------------
# off-path overhead guard (extends test_obs.py's): tracing disabled,
# the span layer must cost <5% of a serve workload
# ---------------------------------------------------------------------------

def test_serve_off_path_span_overhead(binary_model, monkeypatch):
    assert not obs.trace_enabled()
    from lightgbm_tpu.obs import spans as sp
    spent = [0.0]
    orig_emit = sp.emit_span

    def timed_emit(*a, **kw):
        t0 = time.perf_counter()
        r = orig_emit(*a, **kw)
        spent[0] += time.perf_counter() - t0
        return r

    monkeypatch.setattr(sp, "emit_span", timed_emit)
    monkeypatch.setattr(obs, "emit_span", timed_emit)
    # the default serving config: flight ring armed, trace off
    sess = PredictorSession(binary_model, max_batch=32, max_wait_ms=0.5)
    assert obs.flight_enabled()
    X = np.zeros((4, 5))
    sess.predict(X)  # compile outside the timed window
    t0 = time.perf_counter()
    for _ in range(60):
        ticket = sess.submit(X)
        sess.result(ticket, timeout=30)
    total = time.perf_counter() - t0
    sess.close()
    assert spent[0] < 0.05 * total, \
        f"span layer spent {spent[0]:.4f}s of {total:.4f}s serve wall"


def test_serve_drift_armed_overhead(binary_model, monkeypatch):
    """Same budget for the drift plane (ISSUE 16): with the monitor
    armed at its DEFAULT knobs (the shipped configuration — prediction
    histogram every batch, features sampled at tpu_drift_sample_rate),
    observe + cadence gate must stay under 5% of the serve wall."""
    sess = PredictorSession(binary_model, max_batch=32, max_wait_ms=0.5)
    mon = sess._drift
    assert mon is not None, "sidecar beside the model must arm drift"
    assert mon.sample_rate == 0.05
    spent = [0.0]
    orig_observe, orig_check = mon.observe, mon.maybe_check

    # thread CPU time, not wall: observe runs on the batcher worker
    # thread, and wall-clock spans there charge GIL handoffs to the
    # submitting thread against the drift plane
    def timed(orig):
        def run(*a, **kw):
            t0 = time.thread_time()
            r = orig(*a, **kw)
            spent[0] += time.thread_time() - t0
            return r
        return run

    # full 32-row batches: the drift plane's cost is per-batch numpy
    # constants, so the budget is judged at the batch size the session
    # actually dispatches, not the 4-row extreme the span guard uses
    # (spans are ~ns per event; histograms are not)
    X = np.zeros((32, 5))
    sess.predict(X)  # compile outside the timed window
    monkeypatch.setattr(mon, "observe", timed(orig_observe))
    monkeypatch.setattr(mon, "maybe_check", timed(orig_check))
    t0 = time.perf_counter()
    for _ in range(200):
        ticket = sess.submit(X)
        sess.result(ticket, timeout=30)
    total = time.perf_counter() - t0
    assert sess.stats()["drift"]["pred_rows"] >= 200 * 32
    sess.close()
    assert spent[0] < 0.05 * total, \
        f"drift plane spent {spent[0]:.4f}s of {total:.4f}s serve wall"
