"""Run the quick + slow test tiers and record per-tier evidence.

Every round needs "0 failures" to be a CHECKABLE claim, not a memory:
this tool runs each tier (the conftest.py quick/slow markers) as its own
pytest subprocess with the tier-1 hardening flags, times it, parses the
summary counts, and writes one ``SUITE_r{N}.json`` next to the
``BENCH_r*.json`` round artifacts (VERDICT round-5 next-round item #8).

    python tools/run_suite.py                      # quick + slow tiers
    python tools/run_suite.py --tiers quick        # tier-1 only
    python tools/run_suite.py --select tests/test_config.py --tiers quick

``--select`` narrows the collection target (a file or node id) — the
smoke path CI exercises.  Exit code: 0 when every tier passed (an empty
selection counts as passed and is noted), 1 otherwise.

The quick tier carries the differential-apply smoke
(``tests/test_wave_apply.py::test_batched_apply_differential_smoke``):
every quick run re-proves the batched one-pass wave split apply byte-
identical to the sequential oracle before any perf number is trusted.
It also carries the fused-kernel smoke (ISSUE 8,
``tests/test_hist_fused.py::test_fused_packed_smoke``): the packed
lane-pair + in-kernel-sibling wave kernel, run in Pallas interpret mode
on CPU, bit-matches the triple-layout unfused oracle — so a histogram-
pipeline regression can never hide behind a green perf round.  Since
ISSUE 11 it additionally carries the quantized + fused-grad smoke
(``tests/test_hist_quant.py::test_quant_fused_smoke``): int16
stochastic-rounded accumulation within its analytic error bound,
bit-identical across the packed/fused layout grid, and the fused
gradient pass bit-identical to its unfused oracle — the new modes
can't rot between TPU windows.  Since ISSUE 13 it also carries the
ranking-plane smoke (``tests/test_rank_device.py::
test_rank_wave_smoke_device_metric_parity``): a small lambdarank train
end-to-end through the wave path (``LGBM_TPU_FORCE_WAVE=interpret``)
with the device NDCG kernel asserted against the host oracle — CPU CI
exercises the whole ranking plane every quick run.

The ``serve`` tier is not a pytest marker: it runs
``tools/bench_serve.py --smoke`` — start the HTTP server in-process,
fire concurrent mixed-size requests, assert p99 recorded + the compile
count bounded by the pow2 bucket set + clean shutdown, and (ISSUE 6)
that ``/metrics`` and ``/debug/flight`` keep answering while the POST
storm runs and ``/health`` carries the load-balancer signals
(queue_rows, uptime_s, compile_count, slo_burn) — so every suite round
re-proves the serving engine AND its introspection plane end to end on
CPU.  Since ISSUE 9 the smoke pins ``--explain-frac 0.2``: a fifth of
the open-loop Poisson arrivals are ``/explain`` TreeSHAP requests, so
the explanation plane (its own microbatch queue + pow2 bucket family)
is re-proved by the same round — ``explain_served``,
``explain_no_failures`` and ``explain_buckets_bounded`` join the
check map.

The ``chaos`` tier (ISSUE 10) runs ``tools/chaos_serve.py --json``: the
serving chaos matrix — replica wedge (one wedged replica of a routed
pair costs capacity, never availability; breaker opens, half-open probe
recovers it), hot-swap under concurrent mixed /predict + /explain
traffic (zero request loss, no 5xx from the swap, every response
bit-consistent with its echoed model version), canary-gate rejection
(409, old version keeps serving), post-swap regression -> automatic
rollback + flight dump, and priority shedding (low shed first,
Retry-After on the 503, per-class counters in /metrics) — so every
suite round re-proves the whole serving resilience plane on CPU.

The ``faults`` tier (ISSUE 7) runs ``tools/fault_matrix.py --json``:
every ``LGBM_TPU_FAULTS`` injection point x recovery mode — transient
retry (bit-identical model), fatal abort (wedge checkpoint + flight
dump + bit-exact resume), CPU fallback, collective retry, stall
stamping, serve degrade-and-reprobe, checkpoint-write faults and
corrupt-checkpoint fallback — so every suite round re-proves the whole
fault-tolerance plane on CPU.  Since ISSUE 12 it also covers the
online loop: a refit fault leaves the old version serving, a crash
mid-train-continue resumes bit-exactly, and an ingest stall skips the
cadence with a logged + telemetry-stamped event.

The ``ingest`` tier (ISSUE 14) runs ``tools/ingest_bench.py --json``:
the streaming-ingestion smoke — a synthetic chunked stream two-pass
ingested with the bounded-memory proof (tracemalloc peak strictly
below the raw [N, F] f64 bytes the in-RAM path would materialize),
streamed-vs-``from_matrix`` bit identity on the same reservoir
sample, chunk-size invariance, and the distribution-shifted-tail
sampling regression — so every suite round re-proves that out-of-core
ingestion produces the exact same datasets the in-RAM loaders would.

The ``online`` tier (ISSUE 12) runs ``tools/online_smoke.py --json``:
the closed-loop end-to-end check — a drifting labeled stream drives
the OnlineLoop to >= 2 refreshed versions through
``POST /models/{name}/swap`` under concurrent zero-loss /predict
traffic, and a deliberately poisoned refit bounces off the canary
gate with the old version still serving.  Its JSON carries
``online_refresh_s`` / ``online_swap_ok``, trended by
``tools/bench_history.py`` from the ``ONLINE_r*.json`` artifact.

The ``drift`` tier (ISSUE 16) runs ``tools/drift_report.py --smoke
--json``: the model-quality monitoring plane — profile sidecar written
at ``save_model``, an i.i.d. replay scoring below ``tpu_drift_psi_warn``
(no false alarm), a seeded covariate-shift replay breaching and
latching within one cadence check, per-replica sketch merge bit-exact
vs the single-sketch oracle, and a label-flipped quality window
dropping windowed AUC past ``tpu_quality_drop_warn`` with the breach
annotated in the registry.  Its ``DRIFT_r*.json`` carries
``drift_psi_max`` / ``quality_auc_delta`` for ``bench_history``.

The ``board`` tier (ISSUE 17) runs ``tools/board_smoke.py --json``:
the live-training-introspection smoke — a short CPU train with the
train-side metrics exporter armed (``tpu_train_metrics_port=0``) while
a concurrent poller scrapes it: the Prometheus exposition parses
through the SAME reader the serving plane uses
(``serve.metrics.parse_prometheus``), ``/progress`` answers the full
JSON contract with a finite, converging ETA, ``/debug/flight`` serves
the live ring, and the train-thread seconds spent inside the board
hook stay under the 5% off-path overhead guard.

The ``arena`` tier (ISSUE 19) runs ``tools/arena_smoke.py --json``:
the zero-cold-start + multi-tenant plane — a warmed session exports
every pow2 bucket executable to the AOT store, a fresh session
deserializes and serves the full sweep with the compile counter pinned
at 0 and bit-identical output; binary-NaN / multiclass / categorical
tenants packed into one ``ForestArena`` predict bit-identically to
dedicated sessions; interleaved mixed-tenant submits coalesce into
shared device batches; and an impossible byte budget forces an LRU
eviction whose victim is transparently re-admitted on its next request.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the tier-1 hardening flags (ROADMAP.md "Tier-1 verify"), minus the
# marker selection this tool owns per tier
_PYTEST_FLAGS = ["-q", "--continue-on-collection-errors",
                 "-p", "no:cacheprovider"]

_COUNT_RE = re.compile(
    r"(\d+)\s+(passed|failed|error(?:s)?|skipped|deselected|xfailed|"
    r"xpassed|warning(?:s)?)")


def parse_counts(output: str) -> dict:
    """Counts from pytest's final summary line (the last line that
    carries any '<n> passed/failed/...' tokens)."""
    counts = {}
    for line in reversed((output or "").splitlines()):
        found = _COUNT_RE.findall(line)
        if found:
            for n, kind in found:
                counts[kind.rstrip("s") if kind != "passed" else kind] = \
                    int(n)
            break
    return counts


def next_round(out_dir: str) -> int:
    n = 0
    for f in glob.glob(os.path.join(out_dir, "SUITE_r*.json")):
        m = re.search(r"SUITE_r(\d+)\.json$", os.path.basename(f))
        if m:
            n = max(n, int(m.group(1)))
    return n + 1


def run_tier(tier: str, select: str, timeout: int,
             runner=subprocess.run, py: str = sys.executable) -> dict:
    target = select or os.path.join(REPO, "tests")
    argv = [py, "-m", "pytest", target, "-m", tier] + _PYTEST_FLAGS
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    t0 = time.time()
    try:
        r = runner(argv, env=env, cwd=REPO, timeout=timeout,
                   capture_output=True, text=True)
        rc, out, err = r.returncode, r.stdout or "", r.stderr or ""
    except subprocess.TimeoutExpired:
        rc, out, err = -1, "", f"timed out after {timeout}s"
    counts = parse_counts(out)
    # pytest exit 5 = nothing collected for this tier/selection — that is
    # evidence of an empty tier, not of a failure
    ok = rc == 0 or rc == 5
    return {
        "tier": tier,
        "cmd": " ".join(argv[2:]),
        "rc": rc,
        "ok": ok,
        "empty": rc == 5,
        "wall_s": round(time.time() - t0, 1),
        "counts": counts,
        "tail": (out + ("\n" + err if err else "")).splitlines()[-5:],
    }


# built-in (non-pytest) tiers: tier name -> argv tail under tools/
_TOOL_TIERS = {
    # --explain-frac pinned so the suite's serve leg always smokes the
    # explain plane (bench_serve adds explain_served /
    # explain_buckets_bounded checks when the mixed leg runs), even if
    # the environment zeroes SERVE_EXPLAIN_FRAC
    "serve": ["bench_serve.py", "--smoke", "--explain-frac", "0.2"],
    "faults": ["fault_matrix.py", "--json"],
    # serving chaos matrix (ISSUE 10): replica wedge, swap-mid-flight,
    # canary rejection, post-swap rollback, priority shedding — every
    # fleet failure mode re-proved on CPU each suite round
    "chaos": ["chaos_serve.py", "--json"],
    # online loop end-to-end (ISSUE 12): ingest -> refit -> canary-gated
    # swap under live traffic, poisoned refit rejected — the closed loop
    # re-proved on CPU each suite round
    "online": ["online_smoke.py", "--json"],
    # streaming ingestion (ISSUE 14): the synthetic-stream bench's
    # verdict map — bounded-memory proof (peak << raw [N,F] bytes),
    # streamed-vs-in-RAM bit identity, chunk-size invariance, and the
    # shifted-tail sampling regression — re-proved on CPU each round;
    # its INGEST_rN.json carries ingest_rows_per_s for bench_history
    "ingest": ["ingest_bench.py", "--json"],
    # drift/quality plane (ISSUE 16): profile sidecar written at save,
    # i.i.d. replay quiet, seeded covariate shift breaches + latches,
    # sketch merge bit-exact, label-flip quality breach annotated in the
    # registry — the monitoring plane re-proved on CPU each round; its
    # DRIFT_rN.json carries drift_psi_max / quality_auc_delta for
    # bench_history
    "drift": ["drift_report.py", "--smoke", "--json"],
    # live training introspection (ISSUE 17): exporter-armed CPU train
    # scraped concurrently — Prometheus exposition parses through the
    # shared serve reader, /progress carries a finite converging ETA,
    # the flight endpoint answers, and the board hook stays inside the
    # 5% off-path overhead guard
    "board": ["board_smoke.py", "--json"],
    # zero-cold-start plane (ISSUE 19): AOT export -> deserialize ->
    # serve round-trip with the compile counter pinned at 0 and
    # bit-identical output, multi-tenant arena parity across the
    # binning surface (NaN / multiclass / categorical), cross-model
    # coalescing, and byte-budget eviction with transparent
    # re-admission — re-proved on CPU each suite round
    "arena": ["arena_smoke.py", "--json"],
    # elastic multi-host fleet (ISSUE 20): 3-process gang launches over
    # the host-TCP transport — plain/bagging/ranking bit-exact vs the
    # single-process oracle, quiet healthy-path event trail, and the
    # kill-one-rank detect/rollback/heal recovery completing bit-exact;
    # its FLEET_rN.json carries fleet_ranks / fleet_recoveries for
    # bench_history
    "fleet": ["fleet_smoke.py", "--json"],
}


def run_tool_smoke(tier: str, timeout: int, runner=subprocess.run,
                   py: str = sys.executable) -> dict:
    """A built-in tool tier (serve smoke / fault matrix): one subprocess
    whose last JSON line carries a per-check verdict map — that map
    becomes the tier's counts."""
    tool = _TOOL_TIERS[tier]
    argv = [py, os.path.join(REPO, "tools", tool[0])] + tool[1:]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = env.get("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    t0 = time.time()
    try:
        r = runner(argv, env=env, cwd=REPO, timeout=timeout,
                   capture_output=True, text=True)
        rc, out, err = r.returncode, r.stdout or "", r.stderr or ""
    except subprocess.TimeoutExpired:
        rc, out, err = -1, "", f"timed out after {timeout}s"
    parsed = None
    for line in reversed(out.splitlines()):
        if line.strip().startswith("{"):
            try:
                parsed = json.loads(line)
                break
            except ValueError:
                continue
    checks = (parsed or {}).get("checks") or {}
    counts = {"passed": sum(1 for v in checks.values() if v),
              "failed": sum(1 for v in checks.values() if not v)}
    return {
        "tier": tier,
        "cmd": "tools/" + " ".join(tool),
        "rc": rc,
        "ok": rc == 0 and bool((parsed or {}).get("ok")),
        "empty": False,
        "wall_s": round(time.time() - t0, 1),
        "counts": counts,
        "checks": checks,
        "tail": (out + ("\n" + err if err else "")).splitlines()[-5:],
    }


def run_serve_smoke(timeout: int, runner=subprocess.run,
                    py: str = sys.executable) -> dict:
    """Back-compat alias for the serve tool tier."""
    return run_tool_smoke("serve", timeout, runner=runner, py=py)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Run the quick/slow test tiers and write SUITE_rN.json")
    ap.add_argument("--tiers", default="quick,slow,serve,faults,chaos,"
                                       "online,ingest,drift,board,"
                                       "arena,fleet",
                    help="comma list of tiers: pytest markers plus the "
                         "built-in 'serve' smoke, 'faults' matrix, "
                         "'chaos' serving-chaos, 'online' closed-loop, "
                         "'ingest' streaming-ingestion, 'drift' "
                         "monitoring, 'board' train-introspection, "
                         "'arena' zero-cold-start and 'fleet' "
                         "elastic-fleet legs (default quick,slow,serve,"
                         "faults,chaos,online,ingest,drift,board,arena,"
                         "fleet)")
    ap.add_argument("--select", default="",
                    help="pytest collection target (file or node id) "
                         "instead of the whole tests/ dir")
    ap.add_argument("--timeout", type=int, default=3600,
                    help="per-tier subprocess timeout (default 3600)")
    ap.add_argument("--out", default=REPO,
                    help="artifact directory (default: repo root)")
    ap.add_argument("--round", type=int, default=0,
                    help="round number (default: next free SUITE_rN)")
    ap.add_argument("--no-write", action="store_true",
                    help="print the record without writing SUITE_rN.json")
    args = ap.parse_args(argv)

    tiers = [t.strip() for t in args.tiers.split(",") if t.strip()]
    if args.select and len(tiers) > 1:
        # --select narrows pytest collection; the tool tiers are not
        # pytest tiers, so a narrowed run drops them — unless a tool
        # tier is the ONLY tier asked for (then it runs, ignoring the
        # selection)
        tiers = [t for t in tiers if t not in _TOOL_TIERS]
    record = {"kind": "suite", "t": round(time.time(), 1), "tiers": {}}
    total = 0.0
    for tier in tiers:
        if tier in _TOOL_TIERS:
            print(f"# tier {tier}: tools/"
                  f"{' '.join(_TOOL_TIERS[tier])} ...", flush=True)
            res = run_tool_smoke(tier, args.timeout)
            record["tiers"][tier] = res
            total += res["wall_s"]
            print(f"# tier {tier}: rc={res['rc']} {res['counts']} "
                  f"({res['wall_s']}s)", flush=True)
            continue
        print(f"# tier {tier}: pytest -m {tier} "
              f"{args.select or 'tests/'} ...", flush=True)
        res = run_tier(tier, args.select, args.timeout)
        record["tiers"][tier] = res
        total += res["wall_s"]
        print(f"# tier {tier}: rc={res['rc']} {res['counts']} "
              f"({res['wall_s']}s)", flush=True)
    record["wall_s"] = round(total, 1)
    record["ok"] = all(t["ok"] for t in record["tiers"].values())
    record["failed"] = sum(t["counts"].get("failed", 0)
                           + t["counts"].get("error", 0)
                           for t in record["tiers"].values())
    n = args.round or next_round(args.out)
    record["n"] = n
    if not args.no_write:
        path = os.path.join(args.out, f"SUITE_r{n:02d}.json")
        with open(path, "w") as fh:
            json.dump(record, fh, indent=1)
        print(f"# wrote {path}")
    print(json.dumps({k: record[k] for k in
                      ("n", "ok", "failed", "wall_s")}))
    return 0 if record["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
