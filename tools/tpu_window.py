"""Self-arming TPU measurement watcher.

Probes backend liveness in a SUBPROCESS on an interval — the parent
never touches JAX, so it never holds the chip its legs need (a chip
belongs to one process at a time) — and the moment
``jax.default_backend() != 'cpu'`` it runs the whole capture checklist,
one leg process after another, with health monitoring enabled:

1. ``python bench.py`` — the clean throughput number (async dispatch
   intact; health monitor + telemetry certify it carried no NaNs);
2. ``python bench.py`` under ``LGBM_TPU_PROFILE=1`` — per-kernel
   roofline fractions + the HBM census;
3. ``python bench.py`` with ``BENCH_MAXBIN=63`` — the 4x-denser MXU
   packing variant the roofline model predicts wins;
3c. ``python bench.py`` with ``BENCH_QUANT=int16`` — the quantized-
   accumulation A/B (ISSUE 11): same problem, quantization-only delta,
   so one window prices the int16 grad/hess lanes against leg 1;
3e. ``python bench.py`` with ``BENCH_TASK=rank`` — the dedicated
   MSLR-shaped lambdarank leg (ISSUE 13: device lambda pair pass +
   device NDCG eval), written as ``BENCH_rank_manual_r{N}.json`` so
   one window finally yields a clean ``rank_vs_baseline`` trajectory
   point beside the HIGGS one;
4. ``tools/prof_kernels.py`` (``PROF_JSON=1``) — the leg decomposition,
   including the wave-partition legs (batched one-pass split apply vs
   the sequential per-split oracle, against ``partition_cost``) and the
   packed/fused kernel-layout legs (triple vs lane-pair vs fused);
5. a ``jax.profiler`` trace capture of a short training run, taken
   with telemetry armed so the ``lgbm/*`` scope annotations land in
   the artifacts; the window then parses its OWN capture through the
   measured-roofline plane (``obs/xprof.py``, ISSUE 18) and embeds
   the per-kernel ``kernel_measured`` table (achieved ms vs cost-model
   ms, roofline fraction, boundedness) into ``BENCH_manual_r{N}`` —
   a captured-but-unparseable trace is classified into ``triage`` as
   ``unparseable-trace`` instead of silently passing the file-count
   check;
6. ``tools/bench_serve.py --json`` — the serving engine's closed-loop +
   Poisson open-loop numbers on the live backend, written as
   ``SERVE_manual_r{N}.json`` (bench_history.py trends it alongside
   the ``SERVE_r*.json`` CI rounds).  The leg runs with
   ``LGBM_TPU_TRACE=1`` and a flight capture, so one good window also
   yields a Perfetto-loadable ``serve_trace.json`` (request span trees)
   and a ``FLIGHT_serve.json`` flight record in the artifacts dir.
   Since ISSUE 10 the leg also exercises ONE registry hot-swap under
   its Poisson mix (bench_serve's swap leg), and the window record
   stamps ``swap_blip_p99_ms`` / ``rollbacks`` at top level — a real
   on-TPU datapoint for "what does a model push cost the p99";
7. ``tools/bench_serve.py --json --explain-frac 0.5`` — the
   explanation-serving leg (ISSUE 9): half the open-loop Poisson
   arrivals are ``/explain`` TreeSHAP requests, so the window captures
   ``explain_p99`` under real mixed contention on the live backend,
   written as ``SERVE_explain_manual_r{N}.json``;
8. ``tools/ingest_bench.py --json`` — the streaming-ingestion leg
   (ISSUE 14): synthetic-stream two-pass construction throughput
   (``ingest_rows_per_s``) + the bounded-memory proof on the window's
   host, written as ``INGEST_manual_r{N}.json`` (pass the file to
   ``bench_history.py`` explicitly to fold it into the trend beside
   the auto-globbed CI ``INGEST_r*`` rounds, like ``SERVE_manual``);
9. ``tools/fleet_smoke.py --json`` — the elastic-fleet leg (ISSUE 20):
   3-process gang launch over the host-TCP transport, bit-exactness vs
   the single-process oracle on plain/bagging/ranking, and the
   kill-one-rank recovery, written as ``FLEET_manual_r{N}.json`` (same
   pass-explicitly convention as the other manual records).

Artifacts (``--out``, default repo root):

- ``BENCH_manual_r{N}.json`` — one bench_history.py-compatible record:
  the clean bench's parsed JSON line (which now embeds
  ``health_checks``/``health_failures``) plus every leg's rc/seconds/
  parsed output and the merged health summary.  Since ISSUE 17 the
  headline leg runs with the train-side metrics exporter armed
  (``LGBM_TPU_TRAIN_METRICS``) and a mid-leg scraper embeds the live
  ``/progress`` snapshot + measured-vs-model ``reconciliation`` table
  at top level, and a ``triage`` block classifies every non-clean leg
  (``timeout`` / ``backend-wedge`` / ``cpu-fallback`` / ``failure``)
  so the record says WHY a window yielded no clean point;
- ``HEALTH_manual_r{N}.json`` — the health/fingerprint/divergence digest
  per leg + event-schema validation verdict;
- ``tpu_window_r{N}/`` — per-leg telemetry dirs + the profiler trace.

``--dry-run`` forces the CPU backend at smoke sizes and skips the
probe gate, so the ENTIRE pipeline is testable in this container (CI
runs it; on a real window only the sizes differ).  ``--once`` probes a
single time instead of looping; ``--max-wait`` bounds the loop.

Run: python tools/tpu_window.py
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import socket
import subprocess
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# smoke sizes for --dry-run: every leg finishes in O(compile time) on the
# 1-CPU container while exercising the exact artifact pipeline
_DRY_BENCH_ENV = {
    "JAX_PLATFORMS": "cpu",
    "BENCH_FORCE_CPU": "1", "BENCH_CPU_ROWS": "20000", "BENCH_ITERS": "3",
    "BENCH_LEAVES": "31", "BENCH_RANK_ROWS": "5000", "BENCH_RANK_ITERS": "2",
}
_DRY_PROF_ENV = {
    "JAX_PLATFORMS": "cpu",
    "PROF_INTERPRET": "1", "PROF_ROWS": "4096", "PROF_FEATURES": "6",
    "PROF_LEAVES": "7", "PROF_MAXBIN": "63", "PROF_REPEAT": "1",
    "PROF_LEGS": "kernel,kernelpacked,kernelfused,kernelint16,"
                 "kernelint8,fusedgrad,gathers,partition",
}
_DRY_SERVE_ENV = {
    "JAX_PLATFORMS": "cpu",
    "SERVE_ROWS": "2000", "SERVE_TREES": "20", "SERVE_FEATURES": "8",
    "SERVE_MAX_BATCH": "128", "SERVE_CLIENTS": "2",
    "SERVE_DURATION_S": "1.5", "SERVE_RATE": "40",
}
# ingest_bench's built-in defaults ARE smoke-sized (120k rows, ~2s);
# shrinking them further would starve the bounded-memory check of the
# raw-matrix headroom it measures against, so the dry leg only pins
# the backend
_DRY_INGEST_ENV = {"JAX_PLATFORMS": "cpu"}

_TRACE_CODE = """
import sys
import numpy as np
import jax
import lightgbm_tpu as lgb
rows, trace_dir = int(sys.argv[1]), sys.argv[2]
rng = np.random.default_rng(0)
X = rng.normal(size=(rows, 12))
y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(np.float64)
p = {"objective": "binary", "num_leaves": 31, "min_data_in_leaf": 5,
     "verbose": -1}
ds = lgb.Dataset(X, label=y, params=p)
bst = lgb.Booster(params=p, train_set=ds)
bst.update()  # compile outside the trace
with jax.profiler.trace(trace_dir):
    for _ in range(2):
        bst.update()
    jax.block_until_ready(bst._gbdt._train_score)
print("TRACE_OK")
"""


def probe_backend(timeout_s: int = 120, py: str = sys.executable,
                  runner=subprocess.run):
    """(armed, backend_name): True when a non-CPU backend answered within
    the timeout.  Subprocess-isolated so a wedged lease cannot hang the
    watcher itself."""
    code = ("import jax, sys\n"
            "b = jax.default_backend()\n"
            "print(b)\n"
            "sys.exit(0 if b != 'cpu' else 2)\n")
    try:
        r = runner([py, "-c", code], timeout=timeout_s,
                   capture_output=True, text=True)
    except (subprocess.TimeoutExpired, OSError):
        return False, "timeout"
    out = (r.stdout or "").strip().splitlines()
    return r.returncode == 0, (out[-1] if out else "")


def next_round(out_dir: str) -> int:
    n = 0
    for f in glob.glob(os.path.join(out_dir, "BENCH_manual_r*.json")):
        m = re.search(r"BENCH_manual_r(\d+)\.json$", os.path.basename(f))
        if m:
            n = max(n, int(m.group(1)))
    return n + 1


def _free_port() -> int:
    """A currently-free TCP port for the bench leg's train board — the
    subprocess needs a KNOWN port (ephemeral 0 would hide it from the
    mid-leg scraper).  Tiny bind race, acceptable for a manual tool."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def checklist_legs(art_dir: str, dry_run: bool, py: str = sys.executable):
    """The ROOFLINE.md first-window checklist as (name, argv, env) legs.
    Every leg runs with health monitoring on and its own telemetry dir,
    so the capture certifies itself."""
    bench = os.path.join(REPO, "bench.py")
    prof = os.path.join(REPO, "tools", "prof_kernels.py")
    serve = os.path.join(REPO, "tools", "bench_serve.py")
    ingest = os.path.join(REPO, "tools", "ingest_bench.py")
    fleet = os.path.join(REPO, "tools", "fleet_smoke.py")
    trace_dir = os.path.join(art_dir, "trace")

    def env_for(tag, extra=None, dry_env=None):
        env = {"LGBM_TPU_HEALTH": "monitor",
               "LGBM_TPU_TELEMETRY": os.path.join(art_dir, f"telem_{tag}"),
               # every leg carries a flight ring dumping into the
               # artifacts dir, so a wedged leg leaves its own
               # post-mortem beside the bench numbers (ISSUE 7)
               "LGBM_TPU_FLIGHT": "256",
               "LGBM_TPU_FLIGHT_DIR": art_dir}
        if dry_run:
            env.update(dry_env if dry_env is not None else _DRY_BENCH_ENV)
        if extra:
            env.update(extra)
        return env

    trace_rows = "2000" if dry_run else "50000"
    # the trace leg runs with telemetry ARMED: core.phase only stamps
    # the lgbm/* TraceAnnotations the measured-roofline parser
    # attributes by when a sink is live (obs/core._trace_annotation),
    # so a bare capture would parse to zero attributed kernels.
    # LGBM_TPU_XPROF=0 disarms the in-process capture window — the
    # leg's outer jax.profiler.trace IS the capture here, and a nested
    # profiler session would abort it.
    trace_env = env_for("trace", {"LGBM_TPU_XPROF": "0"},
                        dry_env={"JAX_PLATFORMS": "cpu"})
    # the headline leg runs with the train-side metrics exporter armed
    # (ISSUE 17): the window scrapes /metrics + /progress MID-LEG and
    # embeds the live measured-vs-model reconciliation table into
    # BENCH_manual_rN — proof the introspection plane works on the real
    # backend, not just in the CPU smoke
    board_port = _free_port()
    return [
        {"name": "bench", "argv": [py, bench],
         "env": env_for("bench",
                        {"LGBM_TPU_TRAIN_METRICS": str(board_port)}),
         "scrape_port": board_port, "parse_json": True},
        {"name": "bench_profile", "argv": [py, bench],
         "env": env_for("bench_profile", {"LGBM_TPU_PROFILE": "1"}),
         "parse_json": True},
        {"name": "bench_maxbin63", "argv": [py, bench],
         "env": env_for("bench_maxbin63", {"BENCH_MAXBIN": "63"}),
         "parse_json": True},
        # the quantized-accumulation A/B (ISSUE 11): same problem,
        # quantization-only delta — bench_history reads the hist_mode
        # stamp so the legs trend separately and a silent downgrade to
        # f32 is flagged like a fused_sibling flip
        {"name": "bench_quant", "argv": [py, bench],
         "env": env_for("bench_quant", {"BENCH_QUANT": "int16"}),
         "parse_json": True},
        # the ranking-plane leg (ISSUE 13): a dedicated BENCH_TASK=rank
        # run at full rank size (the headline's embedded rank leg runs
        # at reduced BENCH_RANK_ROWS), written as BENCH_rank_manual_rN
        # — the first clean window prices the device lambda/NDCG plane
        # and bench_history trends its rank_vs_baseline point
        {"name": "bench_rank", "argv": [py, bench],
         "env": env_for("bench_rank", {"BENCH_TASK": "rank",
                                       "BENCH_CPU_ROWS": "8000"}),
         "parse_json": True},
        {"name": "prof_kernels", "argv": [py, prof],
         "env": env_for("prof_kernels", {"PROF_JSON": "1"},
                        dry_env=_DRY_PROF_ENV),
         "parse_json": True},
        {"name": "bench_serve", "argv": [py, serve, "--json"],
         "env": env_for("bench_serve",
                        # trace + flight capture: one good window leaves
                        # a Perfetto-exportable span stream AND a flight
                        # record beside the bench numbers (ISSUE 6).
                        # SERVE_COLDSTART pinned on (ISSUE 19): the
                        # window stamps serve_coldstart_ms — a real
                        # on-TPU exec-to-request-#1 number with the AOT
                        # store armed — beside the swap blip
                        {"LGBM_TPU_TRACE": "1",
                         "SERVE_COLDSTART": "1",
                         "SERVE_FLIGHT_OUT": os.path.join(
                             art_dir, "FLIGHT_serve.json")},
                        dry_env=_DRY_SERVE_ENV),
         "parse_json": True},
        # explanation-serving leg (ISSUE 9): an explain-heavy mix so the
        # window yields a TreeSHAP p99 under contention, not an
        # idle-path number — its own telemetry dir keeps the span
        # streams separable
        {"name": "bench_explain",
         "argv": [py, serve, "--json", "--explain-frac", "0.5"],
         # the hot-swap / cold-start / arena exercises belong to the
         # bench_serve leg; this one stays a pure explain-mix
         # measurement
         "env": env_for("bench_explain", {"SERVE_SWAP": "0",
                                          "SERVE_COLDSTART": "0",
                                          "SERVE_ARENA": "0"},
                        dry_env=_DRY_SERVE_ENV),
         "parse_json": True},
        # streaming-ingestion leg (ISSUE 14): the synthetic-stream
        # two-pass bench — ingest_rows_per_s + the bounded-memory proof
        # on whatever host backs this window; artifact written by the
        # window itself (INGEST_manual_rN) so the repo root stays clean
        {"name": "bench_ingest",
         "argv": [py, ingest, "--json", "--no-write"],
         "env": env_for("bench_ingest", dry_env=_DRY_INGEST_ENV),
         "parse_json": True},
        # elastic-fleet leg (ISSUE 20): a real 3-process gang launch
        # over the host-TCP transport — bit-exactness vs the
        # single-process oracle plus the kill-one-rank recovery, on
        # whatever host backs this window; artifact written by the
        # window itself (FLEET_manual_rN) so the repo root stays clean
        {"name": "bench_fleet",
         "argv": [py, fleet, "--json", "--no-write"],
         "env": env_for("bench_fleet", dry_env={"JAX_PLATFORMS": "cpu"}),
         "parse_json": True},
        {"name": "trace",
         "argv": [py, "-c", _TRACE_CODE, trace_rows, trace_dir],
         "env": trace_env, "parse_json": False},
    ], trace_dir


def _parse_json_tail(stdout: str):
    """Last parseable JSON object line of a leg's stdout (bench.py and
    PROF_JSON both print exactly one)."""
    for line in reversed((stdout or "").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    return None


def _run_one(leg, runner, timeout):
    env = {**os.environ, **leg["env"]}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    try:
        r = runner(leg["argv"], env=env, cwd=REPO, timeout=timeout,
                   capture_output=True, text=True)
        return r.returncode, r.stdout or "", r.stderr or "", False
    except subprocess.TimeoutExpired as exc:
        # keep the partial output: how far a leg got before wedging
        # IS the diagnostic this watcher exists to capture
        def _s(b):
            return (b.decode(errors="replace")
                    if isinstance(b, bytes) else (b or ""))
        return (-1, _s(exc.stdout),
                _s(exc.stderr) + f"\n[timed out after {timeout}s]", True)
    except OSError as exc:
        return -2, "", f"{type(exc).__name__}: {exc}", False


def _scrape_board(port: int, state: dict, stop: threading.Event,
                  poll_s: float = 0.15) -> None:
    """Poller thread body: keep the LAST successful /progress +
    /metrics snapshot from a leg's train board.  Misses are normal
    (the board only exists while the subprocess trains)."""
    base = f"http://127.0.0.1:{port}"
    while not stop.is_set():
        try:
            with urllib.request.urlopen(base + "/progress",
                                        timeout=2) as resp:
                pr = json.loads(resp.read())
            state["progress"] = pr
            if pr.get("reconciliation"):
                # bench arms several boards back to back (headline +
                # embedded rank leg); keep the last snapshot that
                # carries the reconciliation table so a later tiny
                # leg's board can't blank the embed
                state["progress_recon"] = pr
            with urllib.request.urlopen(base + "/metrics",
                                        timeout=2) as resp:
                state["metrics_text"] = resp.read().decode()
            state["scrapes"] = state.get("scrapes", 0) + 1
        except Exception:
            pass
        stop.wait(poll_s)


def _board_snapshot(state: dict):
    """Trim a scraped board state into the record's ``board`` block:
    the reconciliation table + headline progress, plus proof the
    exposition parses through the shared serve reader."""
    pr = state.get("progress_recon") or state.get("progress")
    if not pr:
        return None
    snap = {
        "scrapes": state.get("scrapes", 0),
        "iteration": pr.get("iteration"),
        "total_rounds": pr.get("total_rounds"),
        "eta_s": pr.get("eta_s"),
        "row_iters_per_s": pr.get("row_iters_per_s"),
        "vs_baseline": pr.get("vs_baseline"),
        "reconciliation": pr.get("reconciliation"),
        "stragglers": pr.get("stragglers"),
    }
    mtext = state.get("metrics_text")
    if mtext:
        try:
            from lightgbm_tpu.serve.metrics import parse_prometheus
            snap["metrics_series"] = len(parse_prometheus(mtext))
        except Exception:
            snap["metrics_series"] = None
    return snap


def leg_triage(rec: dict, dry_run: bool = False):
    """Why did this leg not yield a clean point?  ``None`` for a clean
    leg; else one of ``timeout`` (the subprocess hit the window's
    deadline), ``backend-wedge`` (transient runtime failure shape —
    robust/watchdog.py classify_text — that exhausted its retries),
    ``cpu-fallback`` (ran green but on the CPU backend, so the number
    is not a device point), ``unparseable-trace`` (the capture leg
    left artifacts the measured-roofline parser could not read —
    ISSUE 18 — so the window yielded no per-kernel truth), or
    ``failure`` (a real error: retrying would only repeat it)."""
    parsed = rec.get("parsed") or {}
    if rec.get("trace_unparseable"):
        # checked BEFORE the rc == 0 early-return: the capture
        # subprocess exits green even when its artifacts are garbage
        return "unparseable-trace"
    if rec.get("rc", 1) == 0:
        if not dry_run and parsed.get("backend") == "cpu":
            return "cpu-fallback"
        return None
    if rec.get("rc") == -1:
        return "timeout"
    if rec.get("wedge_class"):
        return "backend-wedge"
    from lightgbm_tpu.robust.watchdog import classify_text
    tail = "\n".join(rec.get("tail") or [])
    if classify_text(tail) is not None:
        return "backend-wedge"
    return "failure"


def triage_legs(results: dict, dry_run: bool = False):
    """The record's top-level ``triage`` block (ISSUE 17): per-leg
    classification of every non-clean leg so bench_history.py can say
    WHY a window produced no clean point.  ``None`` when every leg was
    clean (the block's absence IS the clean signal)."""
    legs = {name: cls for name, rec in results.items()
            for cls in [leg_triage(rec, dry_run=dry_run)] if cls}
    if not legs:
        return None
    return {"legs": legs, "classes": sorted(set(legs.values()))}


def run_legs(legs, runner=subprocess.run, timeout: int = 1800,
             wedge_retries: int = 1, backoff_s: float = 5.0):
    """Run the checklist legs; a leg that dies in a WEDGE-shaped way
    (timeout, or a transient runtime error in its output tail) is
    retried up to ``wedge_retries`` times with exponential backoff +
    seeded jitter instead of abandoning the window — the same
    classify/backoff path the in-process watchdog applies, lifted to
    the subprocess level (robust/watchdog.py classify_text).  Each
    leg's record carries ``wedge_retries``/``wedge_class`` so
    bench_history.py can distinguish recovered rounds from clean
    ones."""
    from lightgbm_tpu.robust.watchdog import backoff_delays, classify_text
    results = {}
    for leg in legs:
        t0 = time.time()
        print(f"# leg {leg['name']}: {' '.join(leg['argv'][:2])} ...",
              flush=True)
        attempts = 0
        wedge_class = None
        scrape_state, scrape_stop = None, None
        if leg.get("scrape_port"):
            # mid-leg board scrape (ISSUE 17): runs across retries too —
            # the last snapshot before a wedge is still a diagnostic
            scrape_state, scrape_stop = {}, threading.Event()
            threading.Thread(
                target=_scrape_board,
                args=(leg["scrape_port"], scrape_state, scrape_stop),
                daemon=True).start()
        delays = backoff_delays(max(wedge_retries, 0), base_s=backoff_s,
                                cap_s=8 * backoff_s)
        while True:
            rc, out, err, timed_out = _run_one(leg, runner, timeout)
            if rc == 0 or attempts >= wedge_retries:
                break
            cls = classify_text(out + "\n" + err, timed_out=timed_out)
            if cls is None:
                break  # a real failure — retrying would only repeat it
            wedge_class = cls
            delay = delays[min(attempts, len(delays) - 1)] if delays else 0
            print(f"# leg {leg['name']}: {cls} failure (rc={rc}) — "
                  f"retrying in {delay:.1f}s "
                  f"({attempts + 1}/{wedge_retries})", flush=True)
            time.sleep(delay)
            attempts += 1
        rec = {"rc": rc, "seconds": round(time.time() - t0, 1)}
        if scrape_stop is not None:
            scrape_stop.set()
            board = _board_snapshot(scrape_state)
            if board is not None:
                rec["board"] = board
        if attempts:
            rec["wedge_retries"] = attempts
            rec["wedge_class"] = wedge_class
            rec["recovered"] = rc == 0
        if leg["parse_json"]:
            rec["parsed"] = _parse_json_tail(out)
        tail = (out + ("\n" + err if err else "")).splitlines()[-8:]
        rec["tail"] = tail
        results[leg["name"]] = rec
        status = "ok" if rc == 0 else f"rc={rc}"
        if attempts:
            status += f" after {attempts} wedge retr" \
                      f"{'y' if attempts == 1 else 'ies'}"
        print(f"# leg {leg['name']}: {status} ({rec['seconds']}s)",
              flush=True)
    return results


def collect_health(art_dir: str) -> dict:
    """Merge every leg's telemetry dir into per-leg health digests +
    schema validation (obs/report.py — imported lazily so the module
    stays light for the probe loop)."""
    from lightgbm_tpu.obs.report import (health_summary, load_events,
                                         validate_events)
    out = {"legs": {}, "problems": [], "events_ok": True}
    for d in sorted(glob.glob(os.path.join(art_dir, "telem_*"))):
        tag = os.path.basename(d)[len("telem_"):]
        events = load_events(d)
        problems = validate_events(events)
        hs = health_summary(events)
        n_iter = sum(1 for e in events if e.get("event") == "iteration")
        out["legs"][tag] = {"events": len(events), "iterations": n_iter,
                            "health": hs, "schema_problems": len(problems)}
        out["problems"].extend(f"{tag}: {p}" for p in problems[:10])
        if problems:
            out["events_ok"] = False
    fails = sum((leg.get("health") or {}).get("failures", 0)
                for leg in out["legs"].values())
    divs = sum((leg.get("health") or {}).get("divergence_failures", 0)
               for leg in out["legs"].values())
    out["failures"] = fails
    out["divergence_failures"] = divs
    out["verdict"] = ("DIVERGED" if divs else
                      "FAILED" if fails else "healthy")
    return out


def export_serve_trace(art_dir: str):
    """Post-process the bench_serve leg's telemetry into a Perfetto
    trace file (the leg ran with LGBM_TPU_TRACE=1, so its JSONL carries
    the span stream).  Best-effort: a missing/empty stream returns None
    rather than failing the capture."""
    telem = os.path.join(art_dir, "telem_bench_serve")
    if not os.path.isdir(telem):
        return None, 0
    try:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import trace_export
        from lightgbm_tpu.obs.report import load_events
        doc = trace_export.events_to_chrome(load_events(telem))
        if not doc["traceEvents"]:
            return None, 0
        path = os.path.join(art_dir, "serve_trace.json")
        with open(path, "w") as fh:
            json.dump(doc, fh)
        return path, len(doc["traceEvents"])
    except Exception as exc:  # noqa: BLE001 — capture must survive
        print(f"# serve trace export failed: {exc}", file=sys.stderr)
        return None, 0


def ingest_trace(trace_dir: str, dry_run: bool):
    """Parse the trace leg's OWN capture through the measured-roofline
    plane (obs/xprof.py, ISSUE 18) and join it against the analytic
    cost models at the leg's training shape.

    Returns ``(rows, summary)`` — the per-kernel ``kernel_measured``
    table plus a parse summary.  The parser itself never raises on bad
    artifacts (truncated gzip, corrupt json → ``errors`` entries), so
    a captured-but-unparseable trace surfaces as ``parsed == 0`` with
    the per-file failures listed, not as an exception."""
    from lightgbm_tpu.obs import xprof
    parsed = xprof.parse_trace_dir(trace_dir)
    attrib = xprof.attribute(parsed)
    # _TRACE_CODE's shape: rows x 12 features, 31 leaves, default bins,
    # 2 traced updates
    context = {"rows": 2000 if dry_run else 50000, "features": 12,
               "leaves": 31, "bins": 255, "iters": 2}
    rows = xprof.measured_rooflines(attrib, context)
    lgbm = [r for r in rows if r["kernel"].startswith("lgbm/")
            and r.get("measured_ms", 0) > 0]
    summary = {
        "files": attrib["files"],
        "parsed": attrib["parsed"],
        "errors": attrib["errors"][:5],
        "window_ms": attrib["window_ms"],
        "kernels_attributed": len(lgbm),
    }
    return rows, summary


def run_checklist(out_dir: str, n: int, dry_run: bool,
                  runner=subprocess.run, timeout: int = 1800,
                  backend: str = "", only=None,
                  wedge_retries: int = 1) -> dict:
    art_dir = os.path.join(out_dir, f"tpu_window_r{n:02d}")
    os.makedirs(art_dir, exist_ok=True)
    legs, trace_dir = checklist_legs(art_dir, dry_run)
    if only:
        legs = [leg for leg in legs if leg["name"] in only]
    results = run_legs(legs, runner=runner, timeout=timeout,
                       wedge_retries=wedge_retries)
    health = collect_health(art_dir)
    trace_n_files = sum(len(fs) for _, _, fs in os.walk(trace_dir))
    # parse the trace leg's own capture (ISSUE 18): the per-kernel
    # measured table rides in BENCH_manual_rN, and a captured-but-
    # unparseable trace becomes a triage class instead of silently
    # passing the trace_files > 0 check
    kernel_measured, trace_parse = [], None
    trace_rec = results.get("trace")
    if trace_rec is not None:
        try:
            kernel_measured, trace_parse = ingest_trace(trace_dir,
                                                        dry_run)
        except Exception as exc:  # noqa: BLE001 — record must survive
            trace_parse = {"files": trace_n_files, "parsed": 0,
                           "errors": [f"{type(exc).__name__}: {exc}"],
                           "window_ms": 0.0, "kernels_attributed": 0}
        trace_rec["trace_parse"] = trace_parse
        if trace_n_files > 0 and not trace_parse.get("parsed"):
            trace_rec["trace_unparseable"] = True
    bench_parsed = (results.get("bench") or {}).get("parsed")
    record = {
        "n": n,
        "kind": "manual_window",
        "t": round(time.time(), 1),
        "dry_run": dry_run,
        "backend_probe": backend,
        "cmd": "python tools/tpu_window.py"
               + (" --dry-run" if dry_run else ""),
        "rc": 0 if all(r["rc"] == 0 for r in results.values()) else 1,
        "parsed": bench_parsed,
        "legs": results,
        # total wedge retries across RECOVERED legs: >0 marks a
        # recovered round — bench_history.py flags it so a number that
        # needed retries is never quoted as a clean datapoint.  Legs
        # that retried and STILL failed leave rc!=0 on the record; their
        # attempts must not dress the round up as recovered
        "wedge_retries": sum(r.get("wedge_retries", 0)
                             for r in results.values()
                             if r.get("recovered")),
        "health": health,
        # live-introspection embed (ISSUE 17): the mid-leg board scrape
        # of the headline bench — its measured-vs-model reconciliation
        # table rides in the manual record so a TPU window prices the
        # cost models against real device walls
        "board": (results.get("bench") or {}).get("board"),
        "reconciliation": ((results.get("bench") or {}).get("board")
                           or {}).get("reconciliation"),
        # wedge triage (ISSUE 17): why each non-clean leg failed —
        # absent when the window was clean
        "triage": triage_legs(results, dry_run=dry_run),
        "trace_dir": os.path.relpath(trace_dir, out_dir),
        "trace_files": trace_n_files,
        # the measured-roofline embed (ISSUE 18): per-kernel achieved
        # ms joined against the analytic cost models, straight from the
        # trace leg's own capture — bench_history.py trends the
        # roofline fractions from these rows
        "kernel_measured": kernel_measured,
        "trace_parse": trace_parse,
        "artifacts_dir": os.path.relpath(art_dir, out_dir),
    }
    bench_path = os.path.join(out_dir, f"BENCH_manual_r{n:02d}.json")
    with open(bench_path, "w") as fh:
        json.dump(record, fh, indent=1)
    health_path = os.path.join(out_dir, f"HEALTH_manual_r{n:02d}.json")
    with open(health_path, "w") as fh:
        json.dump(health, fh, indent=1)
    print(f"# wrote {bench_path}")
    print(f"# wrote {health_path}")
    rank_parsed = (results.get("bench_rank") or {}).get("parsed")
    if rank_parsed:
        # the dedicated rank record: bench.py's BENCH_TASK=rank line
        # verbatim (value/vs_baseline + the hist_mode/fused_grad
        # stamps) — the BENCH_r* glob in bench_history.py picks
        # "BENCH_rank_manual_r*" up as its own context, so one good
        # window leaves a trendable rank_vs_baseline point
        rank_parsed = dict(rank_parsed, n=n, dry_run=dry_run)
        rank_path = os.path.join(out_dir, f"BENCH_rank_manual_r{n:02d}.json")
        with open(rank_path, "w") as fh:
            json.dump(rank_parsed, fh, indent=1)
        record["rank_path"] = rank_path
        print(f"# wrote {rank_path}")
    serve_parsed = (results.get("bench_serve") or {}).get("parsed")
    if serve_parsed:
        serve_parsed = dict(serve_parsed, n=n, dry_run=dry_run)
        # the leg's hot-swap exercise (ISSUE 10): stamp the blip p99 and
        # rollback count at top level so one window leaves a trendable
        # swap datapoint even if the embedded record shape changes
        sw = serve_parsed.get("swap") or {}
        serve_parsed["swap_blip_p99_ms"] = sw.get("swap_blip_p99_ms")
        serve_parsed["swap_steady_p99_ms"] = sw.get("steady_p99_ms")
        serve_parsed["rollbacks"] = sw.get("rollbacks")
        # the zero-cold-start + arena legs (ISSUE 19): stamp the boot
        # and throughput-ratio numbers at top level too, so one window
        # leaves trendable cold-start datapoints on the live backend
        cs = serve_parsed.get("coldstart") or {}
        serve_parsed["serve_coldstart_ms"] = cs.get("serve_coldstart_ms")
        serve_parsed["cold_compiles"] = cs.get("cold_compiles")
        serve_parsed["arena_speedup"] = (
            serve_parsed.get("arena") or {}).get("speedup")
        serve_path = os.path.join(out_dir, f"SERVE_manual_r{n:02d}.json")
        with open(serve_path, "w") as fh:
            json.dump(serve_parsed, fh, indent=1)
        record["serve_path"] = serve_path
        print(f"# wrote {serve_path}")
    ingest_parsed = (results.get("bench_ingest") or {}).get("parsed")
    if ingest_parsed:
        # the ingest leg runs --no-write; the window owns the artifact.
        # Like SERVE_manual_rN it is NOT auto-globbed by bench_history's
        # directory scan (that scan takes the CI INGEST_r* rounds) —
        # pass the file explicitly to fold a window point into the table
        ingest_parsed = dict(ingest_parsed, n=n, dry_run=dry_run)
        ingest_path = os.path.join(out_dir, f"INGEST_manual_r{n:02d}.json")
        with open(ingest_path, "w") as fh:
            json.dump(ingest_parsed, fh, indent=1)
        record["ingest_path"] = ingest_path
        print(f"# wrote {ingest_path}")
    fleet_parsed = (results.get("bench_fleet") or {}).get("parsed")
    if fleet_parsed:
        # the fleet leg runs --no-write; the window owns the artifact.
        # Same convention as INGEST_manual_rN: not auto-globbed by
        # bench_history (that scan takes the CI FLEET_r* rounds) — pass
        # the file explicitly to fold a window point into the trend
        fleet_parsed = dict(fleet_parsed, n=n, dry_run=dry_run)
        fleet_path = os.path.join(out_dir, f"FLEET_manual_r{n:02d}.json")
        with open(fleet_path, "w") as fh:
            json.dump(fleet_parsed, fh, indent=1)
        record["fleet_path"] = fleet_path
        print(f"# wrote {fleet_path}")
    explain_parsed = (results.get("bench_explain") or {}).get("parsed")
    if explain_parsed:
        explain_parsed = dict(explain_parsed, n=n, dry_run=dry_run)
        explain_path = os.path.join(out_dir,
                                    f"SERVE_explain_manual_r{n:02d}.json")
        with open(explain_path, "w") as fh:
            json.dump(explain_parsed, fh, indent=1)
        record["explain_path"] = explain_path
        print(f"# wrote {explain_path}")
    if "bench_serve" in results:
        st_path, st_events = export_serve_trace(art_dir)
        if st_path:
            record["serve_trace"] = os.path.relpath(st_path, out_dir)
            record["serve_trace_events"] = st_events
            print(f"# wrote {st_path} ({st_events} trace events)")
        flight_path = os.path.join(art_dir, "FLIGHT_serve.json")
        if os.path.isfile(flight_path):
            record["serve_flight"] = os.path.relpath(flight_path, out_dir)
    if bench_parsed:
        print(f"# headline: {bench_parsed.get('value')} "
              f"{bench_parsed.get('unit')} "
              f"(vs_baseline {bench_parsed.get('vs_baseline')}, "
              f"backend {bench_parsed.get('backend', 'accelerator')})")
    print(f"# health: {health['verdict']} "
          f"({health['failures']} failures, schema "
          f"{'ok' if health['events_ok'] else 'PROBLEMS'})")
    if record["triage"]:
        tr = record["triage"]
        legs_s = ", ".join(f"{k}={v}" for k, v in sorted(
            tr["legs"].items()))
        print(f"# triage: {legs_s}")
    if record["board"]:
        b = record["board"]
        rec_units = sorted((b.get("reconciliation") or {})
                           .get("units", {}) or {})
        print(f"# board: {b.get('scrapes', 0)} scrapes, iteration "
              f"{b.get('iteration')}, reconciliation units "
              f"{rec_units or 'none'}")
    record["bench_path"] = bench_path
    record["health_path"] = health_path
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Probe for a live TPU backend and capture the "
                    "ROOFLINE first-window checklist the moment one "
                    "appears")
    ap.add_argument("--interval", type=float, default=60.0,
                    help="seconds between liveness probes (default 60)")
    ap.add_argument("--probe-timeout", type=int, default=120,
                    help="per-probe subprocess timeout (default 120)")
    ap.add_argument("--leg-timeout", type=int, default=1800,
                    help="per-checklist-leg timeout (default 1800)")
    ap.add_argument("--max-wait", type=float, default=0.0,
                    help="give up after this many seconds of probing "
                         "(0 = wait forever)")
    ap.add_argument("--once", action="store_true",
                    help="probe a single time instead of looping")
    ap.add_argument("--dry-run", action="store_true",
                    help="skip the probe gate and run the whole "
                         "checklist on the CPU backend at smoke sizes")
    ap.add_argument("--out", default=REPO,
                    help="artifact directory (default: repo root)")
    ap.add_argument("--round", type=int, default=0,
                    help="round number for the artifact names "
                         "(default: next free BENCH_manual_rN)")
    ap.add_argument("--legs", default="",
                    help="comma list restricting which checklist legs "
                         "run (bench,bench_profile,bench_maxbin63,"
                         "bench_quant,"
                         "bench_rank,prof_kernels,bench_serve,"
                         "bench_explain,bench_ingest,bench_fleet,trace); "
                         "default all")
    ap.add_argument("--wedge-retries", type=int, default=1,
                    help="times a wedge-shaped leg failure (timeout / "
                         "transient runtime error) is retried with "
                         "backoff before the leg is abandoned "
                         "(default 1; 0 restores the old behavior)")
    args = ap.parse_args(argv)
    only = {s.strip() for s in args.legs.split(",") if s.strip()} or None

    deadline = time.time() + args.max_wait if args.max_wait else None
    probes = 0
    while True:
        if args.dry_run:
            armed, backend = True, "cpu (dry-run)"
        else:
            armed, backend = probe_backend(args.probe_timeout)
        probes += 1
        if armed:
            n = args.round or next_round(args.out)
            print(f"# backend '{backend}' alive after {probes} probe(s); "
                  f"capturing window as round r{n:02d}", flush=True)
            rec = run_checklist(args.out, n, args.dry_run,
                                timeout=args.leg_timeout, backend=backend,
                                only=only,
                                wedge_retries=args.wedge_retries)
            # exit 0 only for a FULLY clean capture: every leg rc 0 and
            # (when the bench leg ran) a parsed headline line — a failed
            # trace/prof leg must be visible to cron wrappers even though
            # the artifacts were still written
            bench_ok = ("bench" not in (only or {"bench"}) or
                        rec["parsed"] is not None)
            return 0 if rec["rc"] == 0 and bench_ok else 2
        if args.once or (deadline and time.time() >= deadline):
            print(f"# no live backend after {probes} probe(s) "
                  f"(last: {backend or 'cpu'})", file=sys.stderr)
            return 3
        print(f"# probe {probes}: backend '{backend or 'cpu'}' — "
              f"sleeping {args.interval:g}s", flush=True)
        time.sleep(args.interval)


if __name__ == "__main__":
    sys.path.insert(0, REPO)
    sys.exit(main())
