"""Serving bench: closed-loop + open-loop (Poisson) latency/throughput.

The training benches (bench.py) answer "how fast does it learn"; this
answers "how does it serve" — the serve/ subsystem's round artifact:

1. **closed-loop**: N client threads fire mixed-size requests
   back-to-back through ``PredictorSession.submit``/``result`` for a
   fixed duration — the saturation number (rows/s, request p50/p99).
2. **open-loop**: requests arrive on a Poisson clock at a fixed rate
   with mixed sizes, so latency includes real queueing delay instead of
   the closed-loop's self-throttling — the SLO number.  With
   ``--explain-frac p`` (or SERVE_EXPLAIN_FRAC) a fraction ``p`` of the
   Poisson arrivals are ``submit_explain`` TreeSHAP requests riding
   their own microbatch queue — the mixed-load leg that writes
   ``explain_p99`` into the artifact.
3. **swap leg** (default on; ``SERVE_SWAP=0`` disables): a multi-model
   Poisson mix over a registry fleet (models ``a``+``b``,
   ``SERVE_REPLICAS`` sessions each) with a canary-gated hot swap of
   model ``a`` mid-run — records ``swap_blip_p99_ms`` (p99 of requests
   completing inside the swap window) vs ``steady_p99_ms`` and the
   rollback count; ``bench_history.py`` trends both and flags a blip
   worse than 2x steady.
4. **cold-start leg** (default on; ``SERVE_COLDSTART=0`` disables): a
   FRESH SUBPROCESS boots against the serialized-executable store
   (serve/aot.py) and answers request #1 — time-to-first-response and
   request-#1 latency, A/B'd AOT-on vs AOT-off.  Records
   ``serve_coldstart_ms`` (the on number) which ``bench_history.py``
   trends, plus the cold compile count: zero with the store armed, the
   full pow2 family without it.
5. **arena leg** (default on; ``SERVE_ARENA=0`` disables): SERVE_TENANTS
   tenant models under a heavy-tail (Zipf) request mix at batch-starved
   sizes (1-4 rows), served closed-loop twice — once by dedicated
   per-model ``PredictorSession``s, once by one ``ForestArena`` with
   cross-model microbatching — and records the throughput ratio as
   ``speedup`` (bench_history trends it) plus per-tenant parity.
6. **HTTP smoke** (``--smoke``): starts ``PredictServer`` in-process,
   fires concurrent mixed-size POST /predict + GET /health, then
   asserts p99 recorded, the compile count bounded by the pow2 bucket
   set (<= ceil(log2(max_batch)) + 1), zero request loss across the
   swap leg, and a clean shutdown.  This is the ``serve`` leg
   ``tools/run_suite.py`` runs in CI.

Writes ``SERVE_r{N}.json`` (``--out``/``--round``; ``--json`` prints the
record instead) which ``tools/bench_history.py`` folds into the
trajectory table.  CPU-runnable end to end; on a TPU window
``tools/tpu_window.py`` captures the same record as
``SERVE_manual_r{N}.json``.

Env knobs (smoke sizes in parens): SERVE_ROWS train rows (2000),
SERVE_TREES boosting rounds (20), SERVE_FEATURES (8), SERVE_MAX_BATCH
(256), SERVE_CLIENTS closed-loop threads (4), SERVE_DURATION_S per-loop
seconds (2), SERVE_RATE open-loop req/s (50), SERVE_EXPLAIN_FRAC
fraction of open-loop arrivals that are /explain requests (0.2 smoke,
0.1 full), SERVE_TENANTS arena-leg tenant models (4 smoke, 8 full),
SERVE_ARENA_REQS arena-leg request count (240 smoke, 1600 full),
SERVE_MODEL serve an existing model file instead of training one.
"""
from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import sys
import tempfile
import threading
import time
from typing import Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_DEFAULTS = dict(rows=20000, trees=60, features=12, max_batch=1024,
                 clients=8, duration_s=5.0, rate=200.0,
                 explain_frac=0.1, tenants=8, arena_reqs=1600)
_SMOKE = dict(rows=2000, trees=20, features=8, max_batch=256,
              clients=4, duration_s=2.0, rate=50.0, explain_frac=0.2,
              tenants=4, arena_reqs=240)


def _env(name, cast, fallback):
    v = os.environ.get(name, "")
    if v:
        try:
            return cast(v)
        except ValueError:
            pass
    return fallback


def knobs(smoke: bool) -> dict:
    base = dict(_SMOKE if smoke else _DEFAULTS)
    return dict(
        rows=_env("SERVE_ROWS", int, base["rows"]),
        trees=_env("SERVE_TREES", int, base["trees"]),
        features=_env("SERVE_FEATURES", int, base["features"]),
        max_batch=_env("SERVE_MAX_BATCH", int, base["max_batch"]),
        clients=_env("SERVE_CLIENTS", int, base["clients"]),
        duration_s=_env("SERVE_DURATION_S", float, base["duration_s"]),
        rate=_env("SERVE_RATE", float, base["rate"]),
        explain_frac=_env("SERVE_EXPLAIN_FRAC", float,
                          base["explain_frac"]),
        tenants=_env("SERVE_TENANTS", int, base["tenants"]),
        arena_reqs=_env("SERVE_ARENA_REQS", int, base["arena_reqs"]),
        model=os.environ.get("SERVE_MODEL", ""),
    )


def build_model(k: dict, workdir: str, name: str = "serve_bench_model.txt",
                num_leaves: int = 31, trees: Optional[int] = None,
                seed: int = 7) -> str:
    """Train a small binary model (NaN-heavy + categorical, so the bench
    exercises the full binning surface) and save it; or reuse
    SERVE_MODEL.  ``name``/``num_leaves``/``trees``/``seed`` let the
    swap leg train model VARIANTS over the same feature space."""
    if k["model"] and name == "serve_bench_model.txt":
        return k["model"]
    path = os.path.join(workdir, name)
    if os.path.exists(path):   # the cold-start prep child built it
        return path
    import numpy as np

    import lightgbm_tpu as lgb
    rng = np.random.default_rng(seed)
    F = k["features"]
    Xnum = rng.normal(size=(k["rows"], F - 1))
    Xnum[rng.random(Xnum.shape) < 0.05] = np.nan
    Xcat = rng.integers(0, 16, size=(k["rows"], 1)).astype(np.float64)
    X = np.hstack([Xnum, Xcat])
    y = ((np.nan_to_num(Xnum[:, 0]) + 0.25 * (Xcat[:, 0] % 3)) > 0
         ).astype(np.float64)
    params = {"objective": "binary", "num_leaves": num_leaves,
              "verbose": -1, "min_data_in_leaf": 5}
    ds = lgb.Dataset(X, label=y, categorical_feature=[F - 1], params=params)
    bst = lgb.train(params, ds,
                    num_boost_round=trees if trees else k["trees"])
    bst.save_model(path)
    return path


def request_pool(k: dict):
    """The 4096-row request pool every leg draws from (NaN-heavy plus
    one categorical column with unseen/negative values)."""
    import numpy as np
    rng = np.random.default_rng(3)
    F = k["features"]
    Xpool = np.hstack([rng.normal(size=(4096, F - 1)),
                       rng.integers(-1, 20, size=(4096, 1)
                                    ).astype(np.float64)])
    Xpool[:, :F - 1][rng.random((4096, F - 1)) < 0.05] = np.nan
    return Xpool


def _percentiles(lat):
    # the one shared nearest-rank definition (obs/report.py) so the
    # bench record can't diverge from the digest / health endpoint
    from lightgbm_tpu.obs.report import percentile
    lat = sorted(lat)
    return percentile(lat, 0.50), percentile(lat, 0.99)


def _request_sizes(rng, max_batch: int):
    """Mixed request sizes: mostly small single-user lookups, a tail of
    bulk scoring calls — the traffic shape the microbatcher exists for."""
    import numpy as np
    if rng.random() < 0.8:
        return int(rng.integers(1, 17))
    return int(rng.integers(17, max(max_batch // 2, 18)))


def closed_loop(sess, Xpool, k: dict) -> dict:
    import numpy as np
    stop_at = time.perf_counter() + k["duration_s"]
    lat, rows_done, errors = [], [0], []
    lock = threading.Lock()

    def client(seed):
        rng = np.random.default_rng(seed)
        while time.perf_counter() < stop_at:
            n = _request_sizes(rng, k["max_batch"])
            lo = int(rng.integers(0, max(Xpool.shape[0] - n, 1)))
            t0 = time.perf_counter()
            try:
                ticket = sess.submit(Xpool[lo:lo + n])
                sess.result(ticket, timeout=60.0)
            except Exception as exc:  # noqa: BLE001
                errors.append(f"{type(exc).__name__}: {exc}")
                continue
            with lock:
                lat.append((time.perf_counter() - t0) * 1e3)
                rows_done[0] += n

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(s,))
               for s in range(k["clients"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    p50, p99 = _percentiles(lat)
    return {"clients": k["clients"], "duration_s": round(wall, 2),
            "requests": len(lat), "rows": rows_done[0],
            "req_per_s": round(len(lat) / wall, 1),
            "rows_per_s": round(rows_done[0] / wall, 1),
            "p50_ms": p50, "p99_ms": p99, "errors": len(errors),
            "error_sample": errors[:3]}


def open_loop(sess, Xpool, k: dict) -> dict:
    """Poisson arrivals at SERVE_RATE req/s; latency measured from the
    scheduled submit to future completion, so queueing delay counts.
    A fraction ``explain_frac`` of the arrivals are ``submit_explain``
    TreeSHAP requests riding their own microbatch queue — the mixed
    load that makes ``explain_p99`` an under-contention number instead
    of an idle-path one."""
    import numpy as np
    rng = np.random.default_rng(11)
    lat, overloads, failures = [], [0], [0]
    xlat, xfailures = [], [0]
    lock = threading.Lock()
    pending = []
    stop_at = time.perf_counter() + k["duration_s"]
    from lightgbm_tpu.serve import ServeOverloadError
    xfrac = (min(max(k.get("explain_frac", 0.0), 0.0), 1.0)
             if getattr(sess, "explain_enabled", False) else 0.0)

    def on_done(t0, sink, fail):
        def cb(fut):
            with lock:
                if fut.exception() is None:
                    sink.append((time.perf_counter() - t0) * 1e3)
                else:
                    fail[0] += 1
        return cb

    n_sent, x_sent = 0, 0
    while time.perf_counter() < stop_at:
        gap = rng.exponential(1.0 / max(k["rate"], 1e-6))
        time.sleep(gap)
        explain = rng.random() < xfrac
        n = _request_sizes(rng, k["max_batch"])
        lo = int(rng.integers(0, max(Xpool.shape[0] - n, 1)))
        t0 = time.perf_counter()
        try:
            if explain:
                ticket = sess.submit_explain(Xpool[lo:lo + n])
            else:
                ticket = sess.submit(Xpool[lo:lo + n])
        except ServeOverloadError:
            overloads[0] += 1
            continue
        if explain:
            x_sent += 1
            cb = on_done(t0, xlat, xfailures)
        else:
            n_sent += 1
            cb = on_done(t0, lat, failures)
        for fut, _ in ticket.parts:
            fut.add_done_callback(cb)
            pending.append(fut)
    deadline = time.time() + 30
    for fut in pending:
        try:
            fut.result(max(deadline - time.time(), 0.1))
        except Exception:  # noqa: BLE001 — on_done already counted it;
            pass           # a failed request must not kill the bench
    p50, p99 = _percentiles(lat)
    out = {"rate_rps": k["rate"], "requests": n_sent,
           "completed": len(lat), "overloads": overloads[0],
           "failures": failures[0], "p50_ms": p50, "p99_ms": p99,
           "explain_frac": xfrac}
    if xfrac > 0:
        xp50, xp99 = _percentiles(xlat)
        out.update(explain_requests=x_sent, explain_completed=len(xlat),
                   explain_failures=xfailures[0],
                   explain_p50_ms=xp50, explain_p99_ms=xp99)
    return out


def http_smoke(server, Xpool, k: dict) -> dict:
    """Concurrent mixed-size POST /predict + GET /health over real HTTP,
    with a poller hammering /metrics and /debug/flight THROUGHOUT the
    storm — the introspection endpoints must answer under load, not just
    on an idle server (run_suite.py's serve tier gates on this)."""
    import urllib.request

    import numpy as np
    url = server.url
    lat, errors = [], []
    poll = {"metrics": 0, "flight": 0, "explain": 0, "errors": []}
    done = threading.Event()
    lock = threading.Lock()

    xfrac = (min(max(k.get("explain_frac", 0.0), 0.0), 1.0)
             if getattr(server.session, "explain_enabled", False) else 0.0)

    def post(seed):
        rng = np.random.default_rng(seed)
        for _ in range(4):
            explain = rng.random() < xfrac
            n = _request_sizes(rng, k["max_batch"])
            lo = int(rng.integers(0, max(Xpool.shape[0] - n, 1)))
            body = json.dumps(
                {"rows": Xpool[lo:lo + n].tolist()}).encode()
            path = "/explain" if explain else "/predict"
            req = urllib.request.Request(
                url + path, data=body,
                headers={"Content-Type": "application/json",
                         "X-Request-Id": f"smoke-{seed}-{n}"})
            t0 = time.perf_counter()
            try:
                with urllib.request.urlopen(req, timeout=60) as resp:
                    payload = json.loads(resp.read())
                field = "contributions" if explain else "predictions"
                if len(payload[field]) != n:
                    raise ValueError("row count mismatch")
                with lock:
                    lat.append((time.perf_counter() - t0) * 1e3)
                    if explain:
                        poll["explain"] += 1
            except Exception as exc:  # noqa: BLE001
                with lock:
                    errors.append(f"{type(exc).__name__}: {exc}")

    def poller():
        from lightgbm_tpu.serve.metrics import parse_prometheus
        while not done.is_set():
            try:
                with urllib.request.urlopen(url + "/metrics",
                                            timeout=30) as resp:
                    pm = parse_prometheus(resp.read().decode())
                if "tpu_serve_slo_burn" in pm:
                    poll["metrics"] += 1
                with urllib.request.urlopen(url + "/debug/flight",
                                            timeout=30) as resp:
                    fl = json.loads(resp.read())
                if isinstance(fl.get("events"), list):
                    poll["flight"] += 1
            except Exception as exc:  # noqa: BLE001
                poll["errors"].append(f"{type(exc).__name__}: {exc}")
            done.wait(0.05)

    threads = [threading.Thread(target=post, args=(s,))
               for s in range(k["clients"])]
    pt = threading.Thread(target=poller)
    pt.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    done.set()
    pt.join(30)
    with urllib.request.urlopen(url + "/health", timeout=10) as resp:
        health = json.loads(resp.read())
    p50, p99 = _percentiles(lat)
    return {"requests": len(lat), "errors": errors[:5],
            "p50_ms": p50, "p99_ms": p99, "health": health,
            "explain_requests": poll["explain"],
            "metrics_polls": poll["metrics"],
            "flight_polls": poll["flight"],
            "poll_errors": poll["errors"][:5]}


def swap_leg(k: dict, workdir: str, model_a: str) -> dict:
    """Multi-model Poisson mix with a hot-swap mid-run (ROADMAP item 3):
    two models serve behind the registry, Poisson arrivals split across
    them, and halfway through model 'a' hot-swaps to a retrained
    variant.  The artifact records ``swap_blip_p99_ms`` — the p99 of
    requests completing inside the swap window (pack + canary + flip +
    fresh-bucket compiles) — against ``steady_p99_ms``, plus the
    registry's rollback count.  ``bench_history.py`` trends both and
    flags a blip worse than 2x steady."""
    import numpy as np
    from lightgbm_tpu.serve import ModelRegistry, ServeOverloadError
    model_b = build_model(k, workdir, name="serve_bench_model_b.txt",
                          num_leaves=15, seed=11)
    model_a2 = build_model(k, workdir, name="serve_bench_model_a2.txt",
                           num_leaves=23, seed=13)
    reps = _env("SERVE_REPLICAS", int, 1)
    reg = ModelRegistry(n_replicas=reps, max_batch=k["max_batch"],
                        max_wait_ms=2.0)
    reg.add_model("a", model_a)
    reg.add_model("b", model_b)
    for name in ("a", "b"):
        reg.resolve(name).router.warmup()
    rng = np.random.default_rng(23)
    F = k["features"]
    Xpool = np.hstack([rng.normal(size=(2048, F - 1)),
                       rng.integers(-1, 20, size=(2048, 1)
                                    ).astype(np.float64)])
    lock = threading.Lock()
    done = []            # (t_complete, lat_ms, ok)
    pending = []
    overloads = 0
    n_sent = 0
    by_model = {"a": 0, "b": 0}
    duration = k["duration_s"] * 2
    t_begin = time.perf_counter()
    stop_at = t_begin + duration
    swap_at = t_begin + duration / 2
    swap_info = {}

    def do_swap():
        t0 = time.perf_counter()
        try:
            rep = reg.swap("a", model_a2)
            swap_info.update(ok=bool(rep.get("ok")),
                             to_version=rep.get("to_version"))
        except Exception as exc:  # noqa: BLE001 — leg must finish
            swap_info.update(ok=False,
                             error=f"{type(exc).__name__}: {exc}")
        swap_info.update(t0=t0, t1=time.perf_counter())

    swap_thread = None
    while time.perf_counter() < stop_at:
        time.sleep(rng.exponential(1.0 / max(k["rate"], 1e-6)))
        if swap_thread is None and time.perf_counter() >= swap_at:
            swap_thread = threading.Thread(target=do_swap)
            swap_thread.start()
        model = "a" if rng.random() < 0.7 else "b"
        n = _request_sizes(rng, k["max_batch"])
        lo = int(rng.integers(0, max(Xpool.shape[0] - n, 1)))
        t0 = time.perf_counter()
        try:
            ticket = reg.submit(Xpool[lo:lo + n], model=model)
        except ServeOverloadError:
            overloads += 1
            continue
        n_sent += 1
        by_model[model] += 1

        def cb(fut, t0=t0):
            with lock:
                done.append((time.perf_counter(),
                             (time.perf_counter() - t0) * 1e3,
                             fut.exception() is None))
        for fut, _ in ticket.parts:
            fut.add_done_callback(cb)
            pending.append(fut)
    if swap_thread is None:
        do_swap()
    else:
        swap_thread.join(120)
    deadline = time.time() + 60
    for fut in pending:
        try:
            fut.result(max(deadline - time.time(), 0.1))
        except Exception:  # noqa: BLE001 — cb already counted it
            pass
    s0, s1 = swap_info.get("t0", swap_at), swap_info.get("t1", swap_at)
    with lock:
        # steady = completions strictly BEFORE the swap began (a clean
        # baseline no flip cost can pollute); blip = completions from
        # swap start until 1s past the flip — where pack/canary/warmup
        # contention and any leaked compiles would land
        steady = [lat for t, lat, ok in done if ok and t < s0]
        blip = [lat for t, lat, ok in done if ok and s0 <= t <= s1 + 1.0]
        failures = sum(1 for _, _, ok in done if not ok)
    rollbacks = sum(m["rollbacks"] for m in reg.models())
    reg.close()
    sp50, sp99 = _percentiles(steady)
    _, bp99 = _percentiles(blip)
    return {
        "rate_rps": k["rate"], "requests": n_sent,
        "completed": len(done), "failures": failures,
        "overloads": overloads, "by_model": by_model,
        "replicas": reps,
        "swap_ok": swap_info.get("ok"),
        "swap_error": swap_info.get("error"),
        "swap_ms": round((s1 - s0) * 1e3, 1),
        "swap_window_requests": len(blip),
        "steady_p50_ms": sp50, "steady_p99_ms": sp99,
        "swap_blip_p99_ms": bp99,
        "rollbacks": rollbacks,
    }


# the cold-boot measurement runs in a FRESH interpreter: imports, model
# load, session construction (which loads the persisted executables when
# $LGBM_TPU_SERVE_AOT_DIR points at a warmed store), request #1, then a
# full pow2 sweep — printing one JSON line the parent A/B-compares
_COLD_CHILD = r"""
import json, sys, time
t0 = time.perf_counter()
import numpy as np
sys.path.insert(0, sys.argv[1])
from lightgbm_tpu import obs
from lightgbm_tpu.serve import PredictorSession
model_path, xpath, max_batch = sys.argv[2], sys.argv[3], int(sys.argv[4])
obs.install_recompile_hook()
c0 = obs.compile_count()
sess = PredictorSession(model_path, max_batch=max_batch, max_wait_ms=1.0)
X = np.load(xpath)
t1 = time.perf_counter()
out1 = sess.predict(X[:16])
t2 = time.perf_counter()
n = 1
while n <= max_batch:
    sess.predict(X[:n])
    n *= 2
aot = sess.stats().get("aot") or {}
print(json.dumps({
    "boot_to_first_ms": round((t2 - t0) * 1e3, 1),
    "request1_ms": round((t2 - t1) * 1e3, 2),
    "compiles": int(obs.compile_count() - c0),
    "aot_buckets": len(aot.get("buckets") or []),
    "probe": np.asarray(out1, dtype=np.float64).tolist(),
}))
sess.close()
"""


# cold-start prep, also in a process of its own: build the model the
# whole bench serves, warm the executable store from it, and save the
# request rows — so the PARENT has not touched JAX (and does not hold the
# chip) while the two boot children below need it
_PREP_CHILD = r"""
import json, os, sys
import numpy as np
sys.path.insert(0, sys.argv[1])
sys.path.insert(0, os.path.join(sys.argv[1], "tools"))
import bench_serve
from lightgbm_tpu.serve import PredictorSession
k, workdir, aot_dir, xpath = (json.loads(sys.argv[2]), sys.argv[3],
                              sys.argv[4], sys.argv[5])
model_path = bench_serve.build_model(k, workdir)
warm = PredictorSession(model_path, max_batch=k["max_batch"],
                        max_wait_ms=1.0,
                        config={"tpu_serve_aot_dir": aot_dir, "verbose": -1})
warm.warmup()
entries = (warm.stats().get("aot") or {}).get("entries")
warm.close()
np.save(xpath, np.ascontiguousarray(
    bench_serve.request_pool(k)[:max(k["max_batch"], 16)]))
print(json.dumps({"model_path": model_path, "store_entries": entries}))
"""


def _run_child(code: str, argv: list, env: dict) -> dict:
    """One chip-holding child at a time; its failure fails the bench."""
    import subprocess
    t0 = time.perf_counter()
    proc = subprocess.run([sys.executable, "-c", code] + argv,
                          capture_output=True, text=True, env=env,
                          timeout=900)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    if proc.returncode != 0 or not lines:
        raise RuntimeError(
            f"cold-start child exited {proc.returncode}: "
            + (proc.stderr or proc.stdout)[-2000:])
    rec = json.loads(lines[-1])
    rec["wall_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
    return rec


def coldstart_leg(k: dict, workdir: str) -> dict:
    """Fresh-subprocess cold start, AOT-on vs AOT-off (ISSUE 19): a prep
    child warms the executable store once, then two children boot — one
    pointed at the store, one without it.  ``serve_coldstart_ms`` is the
    AOT-on time from exec to request-#1 response; the off run is the JIT
    baseline the store exists to delete.  A zero cold compile count
    across the full pow2 sweep is the tentpole's contract.

    Runs BEFORE the parent touches JAX: a chip belongs to one process at
    a time, so the three children take it one after another while the
    parent stays off it."""
    aot_dir = os.path.join(workdir, "aot_store")
    xpath = os.path.join(workdir, "coldstart_X.npy")
    env = dict(os.environ)
    env.pop("LGBM_TPU_SERVE_AOT_DIR", None)
    prep = _run_child(_PREP_CHILD,
                      [REPO, json.dumps(k), workdir, aot_dir, xpath], env)
    boot_argv = [REPO, prep["model_path"], xpath, str(k["max_batch"])]
    on = _run_child(_COLD_CHILD, boot_argv,
                    {**env, "LGBM_TPU_SERVE_AOT_DIR": aot_dir})
    off = _run_child(_COLD_CHILD, boot_argv, env)
    probe_on, probe_off = on.pop("probe", None), off.pop("probe", None)
    return {
        "store_entries": prep["store_entries"],
        "aot_on": on, "aot_off": off,
        # the headline numbers bench_history.py trends
        "serve_coldstart_ms": on.get("boot_to_first_ms"),
        "serve_coldstart_off_ms": off.get("boot_to_first_ms"),
        "request1_ms": on.get("request1_ms"),
        "request1_off_ms": off.get("request1_ms"),
        "cold_compiles": on.get("compiles"),
        "cold_compiles_off": off.get("compiles"),
        # the AOT path must change WHEN, never WHAT: request #1 through
        # a deserialized executable is bit-identical to the JIT path
        "bit_identical": (probe_on == probe_off
                          if probe_on is not None and probe_off is not None
                          else None),
    }


def arena_leg(k: dict, workdir: str, Xpool) -> dict:
    """Heavy-tail multi-tenant serving, arena vs per-model sessions
    (ISSUE 19): SERVE_TENANTS models, request mix Zipf over tenants at
    batch-starved sizes (1-4 rows), identical closed-loop work-list
    through both data planes.  Per-model sessions each coalesce only
    their own trickle; the arena coalesces the CROSS-model stream into
    shared device dispatches — ``speedup`` is the throughput ratio
    bench_history.py trends (>= 1.5x is the ISSUE 19 target)."""
    import numpy as np
    from lightgbm_tpu.serve import ForestArena, PredictorSession
    T = max(k["tenants"], 2)
    paths = [build_model(k, workdir, name=f"arena_tenant_{i}.txt",
                         num_leaves=11 + 2 * (i % 5),
                         trees=max(k["trees"] // 3, 5), seed=100 + i)
             for i in range(T)]
    # Zipf-ish tenant popularity: p(i) ~ 1/(i+1)^1.2 — one hot tenant,
    # a long cold tail, the mix that starves per-model batches
    w = (np.arange(T) + 1.0) ** -1.2
    p = w / w.sum()
    rng = np.random.default_rng(29)
    reqs = []
    for _ in range(max(k["arena_reqs"], 8)):
        n = int(rng.integers(1, 5))
        lo = int(rng.integers(0, max(Xpool.shape[0] - n, 1)))
        reqs.append((int(rng.choice(T, p=p)), n, lo))

    def run(call):
        idx = [0]
        lat, rows, failures = [], [0], [0]
        lock = threading.Lock()

        def client():
            while True:
                with lock:
                    if idx[0] >= len(reqs):
                        return
                    ti, n, lo = reqs[idx[0]]
                    idx[0] += 1
                t0 = time.perf_counter()
                try:
                    call(ti, Xpool[lo:lo + n])
                except Exception:  # noqa: BLE001 — counted below
                    with lock:
                        failures[0] += 1
                    continue
                with lock:
                    lat.append((time.perf_counter() - t0) * 1e3)
                    rows[0] += n

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client)
                   for _ in range(k["clients"])]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        p50, p99 = _percentiles(lat)
        return {"wall_s": round(wall, 2),
                "req_per_s": round(len(lat) / wall, 1),
                "rows_per_s": round(rows[0] / wall, 1),
                "p50_ms": p50, "p99_ms": p99, "failures": failures[0]}

    # side A: one dedicated session per tenant (the per-model baseline)
    solo = {i: PredictorSession(paths[i], max_batch=k["max_batch"],
                                max_wait_ms=2.0) for i in range(T)}
    for s in solo.values():
        s.warmup()

    def solo_call(ti, X):
        sess = solo[ti]
        sess.result(sess.submit(X), timeout=60.0)

    solo_res = run(solo_call)

    # side B: one arena, every tenant resident, one shared microbatcher
    arena = ForestArena(max_batch=k["max_batch"], max_wait_ms=2.0)
    for i in range(T):
        arena.admit(f"t{i}", paths[i])
    arena.warmup()

    def arena_call(ti, X):
        arena.result(arena.submit(X, model=f"t{ti}"), timeout=60.0)

    arena_res = run(arena_call)

    # per-tenant parity: one data plane, two routes, identical answers
    probe = Xpool[:32]
    parity = all(
        np.array_equal(arena.predict(probe, model=f"t{i}"),
                       solo[i].predict(probe)) for i in range(T))
    st = arena.stats()
    for s in solo.values():
        s.close()
    arena.close()
    base = max(solo_res["rows_per_s"], 1e-9)
    return {
        "tenants": T, "requests": len(reqs), "zipf_exp": 1.2,
        "solo": solo_res, "arena": arena_res,
        "speedup": round(arena_res["rows_per_s"] / base, 3),
        "parity": bool(parity),
        "batches": st["batches"],
        "cross_model_batches": st["cross_model_batches"],
        "occupancy": st["occupancy"],
    }


def scrape_metrics(server) -> dict:
    """One end-of-run /metrics scrape, parsed (the server-side view
    embedded in SERVE_rN.json next to the client-observed numbers)."""
    import urllib.request
    from lightgbm_tpu.serve.metrics import parse_prometheus
    with urllib.request.urlopen(server.url + "/metrics", timeout=30) as r:
        return parse_prometheus(r.read().decode())


def next_round(out_dir: str) -> int:
    n = 0
    for f in glob.glob(os.path.join(out_dir, "SERVE_r*.json")):
        m = re.search(r"SERVE_r(\d+)\.json$", os.path.basename(f))
        if m:
            n = max(n, int(m.group(1)))
    return n + 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Serving bench (serve/)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes + HTTP leg + assertions; prints one "
                         "JSON line, writes no artifact (CI leg)")
    ap.add_argument("--json", action="store_true",
                    help="print the record as one JSON line, no file")
    ap.add_argument("--out", default=REPO,
                    help="artifact directory (default: repo root)")
    ap.add_argument("--round", type=int, default=0,
                    help="round number (default: next free SERVE_rN)")
    ap.add_argument("--explain-frac", type=float, default=None,
                    help="fraction of open-loop arrivals that are "
                         "/explain TreeSHAP requests (default: "
                         "SERVE_EXPLAIN_FRAC or 0.1 full / 0.2 smoke; "
                         "0 disables the mixed leg)")
    args = ap.parse_args(argv)
    k = knobs(args.smoke)
    if args.explain_frac is not None:
        k["explain_frac"] = args.explain_frac

    with tempfile.TemporaryDirectory(prefix="serve_bench_") as workdir:
        coldstart = None
        if _env("SERVE_COLDSTART", int, 1):
            # fresh-subprocess cold boot, AOT store on vs off.  FIRST,
            # while this process has not touched JAX: the children need
            # the chip, and a parent that holds it starves them
            coldstart = coldstart_leg(k, workdir)

        import jax
        from lightgbm_tpu import obs
        from lightgbm_tpu.serve import PredictServer, PredictorSession

        if not obs.enabled():
            # a sink arms the recompile counter; the serve_* events feed
            # the digest embedded below
            obs.enable(os.path.join(workdir, "telem"))
        model_path = build_model(k, workdir)
        Xpool = request_pool(k)

        compiles0 = obs.counter_value("jax/compiles")
        sess = PredictorSession(model_path, max_batch=k["max_batch"],
                                max_wait_ms=2.0)
        sess.warmup()
        if k["explain_frac"] > 0 and sess.explain_enabled:
            # pre-compile the explain bucket family too, so the mixed
            # leg's explain_p99 measures serving, not XLA compilation
            sess.warmup_explain()
        record = {
            "kind": "serve", "t": round(time.time(), 1),
            "backend": jax.default_backend(),
            "rows": k["rows"], "trees": sess.num_trees,
            "num_class": sess.num_tpi, "max_batch": sess.max_batch,
            "warm_compiles": int(obs.counter_value("jax/compiles")
                                 - compiles0),
        }
        record["closed"] = closed_loop(sess, Xpool, k)
        record["open"] = open_loop(sess, Xpool, k)
        server = PredictServer(sess).start()
        if args.smoke:
            record["http"] = http_smoke(server, Xpool, k)
        # end-of-run /metrics scrape: the SERVER-SIDE latency view rides
        # the artifact next to the client-observed one, so
        # bench_history.py can flag client-vs-server skew (network/queue
        # pathology the session never sees).  Best-effort: a transient
        # scrape failure must not void a completed bench round (same
        # contract as tpu_window.py's export_serve_trace)
        try:
            record["metrics_snapshot"] = scrape_metrics(server)
        except Exception as exc:  # noqa: BLE001 — capture must survive
            record["metrics_snapshot"] = None
            record["metrics_scrape_error"] = f"{type(exc).__name__}: {exc}"
        server.stop()
        st = sess.stats()
        record["server"] = {
            "p50_ms": st["p50_ms"], "p99_ms": st["p99_ms"],
            "slo_p99_ms": st["slo_p99_ms"], "slo_burn": st["slo_burn"],
            "uptime_s": st["uptime_s"],
            "compile_count": st["compile_count"],
        }
        flight_out = os.environ.get("SERVE_FLIGHT_OUT", "")
        if flight_out:
            # tpu_window.py's bench_serve leg: one good window leaves a
            # flight artifact beside the trace/telemetry captures
            with open(flight_out, "w") as fh:
                json.dump({"kind": "flight", "reason": "bench_serve",
                           "t": round(time.time(), 1),
                           "events": obs.flight_snapshot()},
                          fh, indent=1, default=str)
            record["flight_out"] = flight_out
        if st.get("explain_armed"):
            # the server-side TreeSHAP view beside the client-observed
            # explain_p99 (bench_history.py trends both)
            record["explain"] = {
                f: st.get(f) for f in
                ("explain_requests", "explain_ok", "explain_batches",
                 "explain_rows", "explain_occupancy", "explain_p50_ms",
                 "explain_p99_ms", "explain_buckets",
                 "explain_max_batch")}
            record["explain"]["compile_bound"] = int(
                math.ceil(math.log2(max(sess.explain_max_batch, 2)))) + 1
        sess.close()
        record["compiles"] = int(obs.counter_value("jax/compiles")
                                 - compiles0)
        # two independent pow2 bucket families, each with its own
        # compile budget: predict's and (when armed) explain's
        record["compile_bound"] = int(
            math.ceil(math.log2(max(sess.max_batch, 2)))) + 1
        if "explain" in record:
            record["compile_bound"] += record["explain"]["compile_bound"]
        record["occupancy"] = st["occupancy"]
        record["buckets"] = st["buckets"]
        record["degraded"] = st["degraded"]
        record["batcher_alive"] = sess._batcher._thread.is_alive()
        if _env("SERVE_SWAP", int, 1):
            # multi-model Poisson mix + hot-swap mid-run: its own
            # registry/fleet, run AFTER the single-session compile
            # accounting above (the fleet's packs/warmups must not
            # count against the session's pow2 bucket budget)
            record["swap"] = swap_leg(k, workdir, model_path)
        if coldstart is not None:
            record["coldstart"] = coldstart
        if _env("SERVE_ARENA", int, 1):
            # multi-tenant Zipf mix: per-model sessions vs one arena
            record["arena"] = arena_leg(k, workdir, Xpool)

    if args.smoke:
        checks = {
            "p99_recorded": record["closed"]["p99_ms"] is not None,
            "http_ok": bool(record["http"]["requests"])
            and not record["http"]["errors"],
            "health_ok": record["http"]["health"].get("status")
            in ("ok", "degraded"),
            # /health must carry the load-balancer signals (ISSUE 6)
            "health_signals": all(
                f in record["http"]["health"]
                for f in ("queue_rows", "uptime_s", "compile_count",
                          "slo_burn")),
            # /metrics + /debug/flight answered while the POST storm ran
            "metrics_under_load": record["http"]["metrics_polls"] >= 1
            and not record["http"]["poll_errors"],
            "flight_under_load": record["http"]["flight_polls"] >= 1,
            "server_p99_recorded":
                record["server"]["p99_ms"] is not None,
            "compiles_bounded":
                record["compiles"] <= record["compile_bound"],
            "no_errors": record["closed"]["errors"] == 0
            and record["open"]["failures"] == 0,
            "not_degraded": not record["degraded"],
            "clean_shutdown": not record["batcher_alive"],
        }
        if record["open"].get("explain_frac", 0) > 0:
            x = record.get("explain") or {}
            checks.update({
                # the mixed leg actually exercised the explain queue…
                "explain_served":
                    record["open"].get("explain_completed", 0) > 0,
                "explain_no_failures":
                    record["open"].get("explain_failures", 0) == 0,
                # …within its own pow2 bucket family's compile budget
                "explain_buckets_bounded":
                    len(x.get("explain_buckets") or [])
                    <= x.get("compile_bound", 0),
            })
        if record.get("swap"):
            sw = record["swap"]
            checks.update({
                # the hot swap completed and cost zero requests: every
                # Poisson arrival admitted before/during/after the flip
                # resolved successfully (the zero-in-flight-loss
                # contract), no rollback fired, and the blip p99 was
                # measurable
                "swap_ok": bool(sw.get("swap_ok")),
                "swap_no_request_loss": sw.get("failures") == 0
                and sw.get("completed", 0) > 0,
                "swap_no_rollback": sw.get("rollbacks") == 0,
                "swap_steady_p99_recorded":
                    sw.get("steady_p99_ms") is not None,
            })
        if record.get("coldstart"):
            cs = record["coldstart"]
            checks.update({
                # the tentpole contract: a cold process with a warmed
                # store serves the whole pow2 sweep with ZERO compiles…
                "coldstart_zero_compiles": cs.get("cold_compiles") == 0,
                # …the JIT baseline actually pays them (the A/B is live)…
                "coldstart_off_pays_jit":
                    (cs.get("cold_compiles_off") or 0) >= 1,
                # …and the deserialized executables answer bit-identically
                "coldstart_bit_identical": cs.get("bit_identical") is True,
                "coldstart_measured":
                    cs.get("serve_coldstart_ms") is not None,
            })
        if record.get("arena"):
            ar = record["arena"]
            checks.update({
                "arena_parity": ar.get("parity") is True,
                "arena_no_failures": ar["solo"]["failures"] == 0
                and ar["arena"]["failures"] == 0,
                # the whole point: requests for different tenants shared
                # device dispatches (speedup itself is trended, not
                # gated — CPU smoke boxes are too noisy to pin 1.5x)
                "arena_cross_model_coalesced":
                    ar.get("cross_model_batches", 0) >= 1,
                "arena_speedup_recorded": ar.get("speedup") is not None,
            })
        record["checks"] = checks
        record["ok"] = all(checks.values())
        print(json.dumps(record))
        return 0 if record["ok"] else 1

    n = args.round or next_round(args.out)
    record["n"] = n
    if args.json:
        print(json.dumps(record))
        return 0
    path = os.path.join(args.out, f"SERVE_r{n:02d}.json")
    with open(path, "w") as fh:
        json.dump(record, fh, indent=1)
    print(f"# wrote {path}")
    print(json.dumps({"n": n,
                      "closed_rows_per_s": record["closed"]["rows_per_s"],
                      "closed_p99_ms": record["closed"]["p99_ms"],
                      "open_p99_ms": record["open"]["p99_ms"],
                      "explain_p99_ms":
                          record["open"].get("explain_p99_ms"),
                      "server_p99_ms": record["server"]["p99_ms"],
                      "slo_burn": record["server"]["slo_burn"],
                      "occupancy": record["occupancy"],
                      "swap_blip_p99_ms":
                          (record.get("swap") or {}).get(
                              "swap_blip_p99_ms"),
                      "rollbacks":
                          (record.get("swap") or {}).get("rollbacks"),
                      "serve_coldstart_ms":
                          (record.get("coldstart") or {}).get(
                              "serve_coldstart_ms"),
                      "cold_compiles":
                          (record.get("coldstart") or {}).get(
                              "cold_compiles"),
                      "arena_speedup":
                          (record.get("arena") or {}).get("speedup"),
                      "compiles": record["compiles"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
