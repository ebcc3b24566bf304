"""Merge per-round bench results (+ telemetry digests) into a metric
trajectory table and flag regressions.

Every round the driver runs ``python bench.py`` and stores its one JSON
line (plus exit metadata) as ``BENCH_r{NN}.json``.  Those files answer
"what was the number THIS round"; nothing answered "is the number moving
the wrong way".  This tool does:

    python tools/bench_history.py [path ...] [--json] [--threshold 0.1]
                                  [--fail-on-regression]

``path`` entries are bench-round JSON files, serving-round files
(``SERVE_r*.json`` from ``tools/bench_serve.py``), online-loop rounds
(``ONLINE_r*.json`` from ``tools/online_smoke.py``), streaming-ingest
rounds (``INGEST_r*.json`` from ``tools/ingest_bench.py``), drift
rounds (``DRIFT_r*.json`` from ``tools/drift_report.py --smoke`` —
``drift_psi_max`` / ``quality_auc_delta`` trended, rounds with failed
checks flagged like canaries), multi-chip legs (``MULTICHIP_r*.json``,
driver-written — ``n_devices`` + ok trended, a device-count drop or an
ok->failed flip flagged like a mode regression), elastic-fleet rounds
(``FLEET_r*.json`` from ``tools/fleet_smoke.py`` — ``fleet_ranks`` /
``fleet_recoveries`` trended, failed checks flagged like canaries),
telemetry digest JSON files (``telemetry_report.py --json`` output), or
directories to glob for ``BENCH_r*.json`` + ``SERVE_r*.json`` +
``ONLINE_r*.json`` + ``INGEST_r*.json`` + ``DRIFT_r*.json`` +
``MULTICHIP_r*.json`` + ``FLEET_r*.json`` (default: the repo root).
Rounds whose bench produced no parseable line (``"parsed": null`` —
e.g. round 1's empty tail) are listed but carry no metrics.  Serving
rounds trend rows/s + p50/p99 + batch occupancy under their own
context, and a round that degraded to the host predictor is excluded
from baselines like a CPU-fallback canary.  A manual-window round whose
legs needed wedge retries (``wedge_retries`` > 0, stamped by
``tools/tpu_window.py``) is flagged "recovered" in the table —
distinguishable from clean rounds without being discarded (the backend
did answer in the end).

Regression flagging compares each metric of the LATEST comparable round
against the best earlier comparable round — comparable meaning the same
(backend, rows, iters, num_leaves, max_bin) context.  Rounds whose bench
ran on a degraded backend (``backend: cpu-fallback`` / ``cpu-forced``)
are wedge canaries: they are flagged in the table and excluded from the
regression baseline on BOTH sides, so a canary is never quoted as a perf
datapoint nor used as the bar a real round must clear.  A separate
INFORMATIONAL canary trend still surfaces ``per_iter_s`` alongside
throughput across same-context canary rounds, so a real speedup (e.g.
the batched split apply) is visible even when every recent round ran on
the CPU fallback.  Direction is per-metric (throughput up is good,
per-iter seconds down is good); a move worse than ``--threshold``
(default 10%) is flagged.  ``--fail-on-regression`` turns flags into
exit code 1 for CI use.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import List, Optional

# metric name (or prefix ending in *) -> True when higher is better
_DIRECTIONS = [
    ("value", True),
    ("vs_baseline", True),
    ("train_auc", True),
    ("train_ndcg10", True),
    ("rank_row_iters_per_s", True),
    ("rank_vs_baseline", True),
    ("rank_train_ndcg10", True),
    ("kernel_roofline/*", True),
    # trace-attributed measured rooflines (ISSUE 18, obs/xprof.py): the
    # fraction of the analytic roofline each kernel actually achieves
    # in a profiler window — the MEASURED companion of the
    # host-bracketed kernel_roofline estimate above
    ("kernel_measured/*", True),
    # wave-pipeline stamps (ISSUE 8): more kernel launches per tree, or a
    # capacity drop, is a scheduling regression even when throughput
    # noise hides it
    ("waves_per_tree", False),
    ("wave_capacity", True),
    # HBM bytes the fused gradient pass saved per iteration
    ("grad_hbm_bytes_saved", True),
    ("per_iter_s", False),
    ("rank_per_iter_s", False),
    ("compile_s", False),
    ("rank_compile_s", False),
    ("binning_s", False),
    ("rank_binning_s", False),
    ("implied_higgs_500iter_s", False),
    ("implied_mslr_500iter_s", False),
    ("peak_hbm_bytes", False),
    # serving rounds (SERVE_r*.json, tools/bench_serve.py)
    ("serve_rows_per_s", True),
    ("serve_p50_ms", False),
    ("serve_p99_ms", False),
    ("serve_open_p99_ms", False),
    ("serve_explain_p99_ms", False),
    ("serve_occupancy", True),
    ("serve_server_p99_ms", False),
    ("serve_slo_burn", False),
    ("serve_client_server_skew", False),
    # hot-swap leg (ISSUE 10, bench_serve.py swap_leg): the p99 of
    # requests completing inside the swap window, the steady-state p99
    # beside it, and how many swaps bounced to a rollback
    ("serve_swap_blip_p99_ms", False),
    ("serve_steady_p99_ms", False),
    ("serve_rollbacks", False),
    # zero-cold-start + arena legs (ISSUE 19, bench_serve.py): fresh
    # subprocess exec -> request-#1 response with the AOT store armed,
    # the request-#1 latency itself, the cold compile count (0 IS the
    # contract — any growth means the store stopped covering a bucket),
    # and the arena-vs-per-model-sessions throughput ratio under the
    # Zipf tenant mix
    ("serve_coldstart_ms", False),
    ("serve_request1_ms", False),
    ("serve_cold_compiles", False),
    ("serve_arena_speedup", True),
    # online-loop rounds (ONLINE_r*.json, tools/online_smoke.py): how
    # long a refresh takes end to end (refit + save + canary-gated
    # swap) and how many refreshed versions made it through the gate
    ("online_refresh_s", False),
    ("online_swap_ok", True),
    # streaming-ingestion rounds (INGEST_r*.json, tools/ingest_bench.py):
    # two-pass construction throughput and the traced peak of the
    # bounded-memory proof (growth = the O(chunk + bins) contract
    # eroding)
    ("ingest_rows_per_s", True),
    ("ingest_wall_s", False),
    ("peak_traced_bytes", False),
    # drift rounds (DRIFT_r*.json, tools/drift_report.py --smoke): the
    # shifted-replay PSI (the detection margin — shrinking toward the
    # warn threshold means the plane is losing sensitivity) and the
    # label-flip windowed AUC drop the quality tracker caught
    ("drift_psi_max", True),
    ("drift_psi_iid", False),
    ("quality_auc_delta", True),
    # multi-chip legs (MULTICHIP_r*.json, driver-written): how many
    # devices the distributed leg actually saw, and whether it passed —
    # the categorical drop/flip companion lives in
    # find_device_regressions
    ("n_devices", True),
    ("multichip_ok", True),
    # elastic-fleet rounds (FLEET_r*.json, tools/fleet_smoke.py): the
    # gang world size, and how long the whole smoke took.  Recoveries
    # trend as a series without a direction — the kill leg makes
    # exactly one heal by construction, so neither more nor fewer is
    # "better"; a change shows in the table, not the regression gate
    ("fleet_ranks", True),
    ("fleet_wall_s", False),
]

# a swap blip worse than this multiple of the steady p99 is flagged: the
# hot swap is supposed to be invisible to traffic — a 2x p99 excursion
# means the flip (pack/canary/fresh-bucket compiles) is leaking into the
# request path
_SWAP_BLIP_FLAG = 2.0

# a trace-measured kernel more than this multiple off its analytic
# model (in either direction) is flagged: the cost models arbitrate the
# repo's perf claims, so a 2x divergence means either the kernel or the
# model is lying (ISSUE 18)
_DIVERGENCE_FLAG = 2.0

# the headline columns of the human table, in order
_TABLE_COLS = ["value", "vs_baseline", "per_iter_s", "compile_s",
               "train_auc", "waves_per_tree", "rank_row_iters_per_s",
               "peak_hbm_bytes", "serve_p99_ms", "serve_server_p99_ms",
               "serve_occupancy", "n_devices", "multichip_ok",
               "fleet_ranks", "fleet_recoveries"]

_CONTEXT_KEYS = ("backend", "rows", "iters", "num_leaves", "max_bin")

# client-observed p99 more than this multiple of the server-side p99 is
# flagged: the excess lives in the network / front-end queue, not the
# session (tools/bench_serve.py embeds both views per round)
_SKEW_FLAG = 3.0


def metric_direction(name: str) -> Optional[bool]:
    """True = higher is better, False = lower, None = untracked."""
    for pat, up in _DIRECTIONS:
        if pat.endswith("*"):
            if name.startswith(pat[:-1]):
                return up
        elif name == pat:
            return up
    return None


def _round_tag(path: str, payload: dict) -> str:
    m = re.search(r"r(\d+)", os.path.basename(path))
    if m:
        return f"r{int(m.group(1)):02d}"
    n = payload.get("n")
    return f"r{int(n):02d}" if isinstance(n, int) else os.path.basename(path)


def _apply_triage(row: dict, payload: dict) -> None:
    """Fold the window's own failure classification (ISSUE 17) into the
    row: WHY a round produced no clean point — rendered verbatim so a
    timeout round never reads like a code regression."""
    tri = payload.get("triage")
    if not (isinstance(tri, dict) and tri.get("legs")):
        return
    row["triage"] = dict(tri["legs"])
    legs_s = ", ".join(f"{k}:{v}" for k, v in sorted(tri["legs"].items()))
    row["note"] = ((row.get("note", "") + "; ") if row.get("note")
                   else "") + f"triage[{legs_s}]"


def load_round(path: str) -> dict:
    """One trajectory row from a bench-round file or a telemetry digest.

    Returns {"round", "context", "metrics", "note"?}; metrics is flat
    {name: number} with telemetry-derived entries namespaced
    (``phase_s/<phase>``, ``kernel_roofline/<kernel>``)."""
    with open(path) as fh:
        payload = json.load(fh)
    row = {"round": _round_tag(path, payload), "path": path, "metrics": {}}
    parsed = payload.get("parsed", payload)
    if parsed is None:
        # the fully-failed window: no bench line at all — the triage
        # block (when the window wrote one) and any trace-attributed
        # measured rows are the only story the row can tell
        row["note"] = "no parsed bench line"
        row["context"] = None
        _apply_triage(row, payload)
        _fold_measured(row, {}, payload)
        return row
    if parsed.get("kind") == "ingest":  # a tools/ingest_bench.py round
        row["context"] = ("ingest", parsed.get("backend"),
                          parsed.get("rows"), parsed.get("features"),
                          parsed.get("chunk_rows"), parsed.get("memmap"))
        for name in ("ingest_rows_per_s", "ingest_wall_s",
                     "peak_traced_bytes", "rows"):
            v = parsed.get(name)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                row["metrics"][name] = float(v)
        checks = parsed.get("checks") or {}
        failed = [k for k, v in checks.items() if not v]
        if failed:
            row["note"] = ("ingest checks FAILED: " + ", ".join(failed)
                           + " — excluded from baselines")
            row["canary"] = "ingest-failed"
        return row
    if parsed.get("kind") == "fleet" or "fleet_ranks" in parsed:
        # a tools/fleet_smoke.py round (ISSUE 20): the 3-process
        # elastic-fleet smoke — world size + recovery count trended
        row["context"] = ("fleet", parsed.get("fleet_ranks"))
        for name, v in (("fleet_ranks", parsed.get("fleet_ranks")),
                        ("fleet_recoveries",
                         parsed.get("fleet_recoveries")),
                        ("fleet_wall_s", parsed.get("wall_s"))):
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                row["metrics"][name] = float(v)
        checks = parsed.get("checks") or {}
        failed = [k for k, v in checks.items() if not v]
        if failed:
            row["note"] = ("fleet checks FAILED: " + ", ".join(failed)
                           + " — excluded from baselines")
            row["canary"] = "fleet-failed"
        return row
    if "n_devices" in parsed and "kind" not in parsed:
        # a driver-written MULTICHIP_r*.json leg: how many devices the
        # distributed run saw, and whether it passed.  Skipped legs
        # (no multi-device backend in the container) are canaries —
        # evidence the gate ran, never a distributed datapoint
        row["context"] = ("multichip",)
        row["metrics"]["n_devices"] = float(parsed["n_devices"])
        row["metrics"]["multichip_ok"] = float(bool(parsed.get("ok")))
        if parsed.get("skipped"):
            row["canary"] = "multichip-skipped"
            row["note"] = ("distributed leg skipped — excluded from "
                           "baselines")
        elif not parsed.get("ok"):
            row["canary"] = "multichip-failed"
            row["note"] = (f"multichip leg FAILED (rc {parsed.get('rc')})"
                           " — excluded from baselines")
        return row
    if parsed.get("kind") == "online":  # a tools/online_smoke.py round
        row["context"] = ("online", parsed.get("backend"))
        for name in ("online_refresh_s", "online_swap_ok",
                     "online_swap_rejected", "rows_ingested"):
            v = parsed.get(name)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                row["metrics"][name] = float(v)
        checks = parsed.get("checks") or {}
        failed = [k for k, v in checks.items() if not v]
        if failed:
            row["note"] = ("online checks FAILED: " + ", ".join(failed)
                           + " — excluded from baselines")
            row["canary"] = "online-failed"
        return row
    if parsed.get("kind") == "drift":  # a tools/drift_report.py round
        row["context"] = ("drift", parsed.get("backend"))
        for name in ("drift_psi_max", "drift_psi_iid",
                     "quality_auc_delta"):
            v = parsed.get(name)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                row["metrics"][name] = float(v)
        checks = parsed.get("checks") or {}
        failed = [k for k, v in checks.items() if not v]
        if failed:
            # a failed check means the differential itself broke (false
            # alarm or missed shift) — flagged like a canary round, its
            # scores never join the baseline window
            row["note"] = ("drift checks FAILED: " + ", ".join(failed)
                           + " — excluded from baselines")
            row["canary"] = "drift-failed"
        return row
    if parsed.get("kind") == "serve":  # a bench_serve.py round
        row["context"] = ("serve", parsed.get("backend"),
                          parsed.get("trees"), parsed.get("max_batch"))
        closed = parsed.get("closed") or {}
        opened = parsed.get("open") or {}
        server = parsed.get("server") or {}
        for name, v in (("serve_rows_per_s", closed.get("rows_per_s")),
                        ("value", closed.get("rows_per_s")),
                        ("serve_p50_ms", closed.get("p50_ms")),
                        ("serve_p99_ms", closed.get("p99_ms")),
                        ("serve_open_p99_ms", opened.get("p99_ms")),
                        # mixed-load TreeSHAP leg (bench_serve.py
                        # --explain-frac): client-observed explain p99
                        ("serve_explain_p99_ms",
                         opened.get("explain_p99_ms")),
                        ("serve_occupancy", parsed.get("occupancy")),
                        ("serve_server_p99_ms", server.get("p99_ms")),
                        ("serve_slo_burn", server.get("slo_burn")),
                        ("jax_compiles", parsed.get("compiles"))):
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                row["metrics"][name] = float(v)
        # hot-swap leg (bench_serve.py swap_leg): blip vs steady p99 +
        # rollback count.  A blip worse than _SWAP_BLIP_FLAG x steady is
        # flagged here so a leaky flip is visible in the table even
        # before the regression pass runs
        sw = parsed.get("swap") or {}
        for name, v in (("serve_swap_blip_p99_ms",
                         sw.get("swap_blip_p99_ms")),
                        ("serve_steady_p99_ms", sw.get("steady_p99_ms")),
                        ("serve_rollbacks", sw.get("rollbacks"))):
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                row["metrics"][name] = float(v)
        blip = row["metrics"].get("serve_swap_blip_p99_ms")
        steady = row["metrics"].get("serve_steady_p99_ms")
        if blip and steady and blip > _SWAP_BLIP_FLAG * steady:
            row["swap_blip"] = round(blip / steady, 2)
            row["note"] = ((row.get("note", "") + "; ")
                           if row.get("note") else "") + \
                f"swap blip p99 {blip / steady:.1f}x steady p99"
        if sw.get("rollbacks"):
            row["note"] = ((row.get("note", "") + "; ")
                           if row.get("note") else "") + \
                f"{sw['rollbacks']} rollback(s) during the swap leg"
        # client-vs-server p99 skew: the server-side number (session
        # submit->result) excludes HTTP/network and client queueing — a
        # big ratio means latency is accumulating OUTSIDE the session
        # (network or front-end queue pathology), which no server-side
        # metric would ever show
        cp99 = row["metrics"].get("serve_p99_ms")
        sp99 = row["metrics"].get("serve_server_p99_ms")
        if cp99 and sp99:
            skew = round(cp99 / sp99, 3) if sp99 > 0 else None
            if skew is not None:
                row["metrics"]["serve_client_server_skew"] = skew
                if skew > _SKEW_FLAG:
                    row["note"] = (row.get("note", "") + "; " if
                                   row.get("note") else "") + \
                        f"client p99 {skew:g}x server p99 — " \
                        "network/queue pathology"
        # zero-cold-start leg (ISSUE 19, bench_serve.py coldstart_leg):
        # the AOT-on boot + request-#1 numbers, and the cold compile
        # count — nonzero on a warmed store is called out the way a
        # rollback is, even before the regression pass runs
        cs = parsed.get("coldstart") or {}
        for name, v in (("serve_coldstart_ms",
                         cs.get("serve_coldstart_ms")),
                        ("serve_request1_ms", cs.get("request1_ms")),
                        ("serve_cold_compiles",
                         cs.get("cold_compiles"))):
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                row["metrics"][name] = float(v)
        if isinstance(cs.get("cold_compiles"), int) \
                and cs["cold_compiles"] > 0:
            row["note"] = ((row.get("note", "") + "; ")
                           if row.get("note") else "") + \
                f"{cs['cold_compiles']} JIT compile(s) on a warmed-" \
                "store cold boot"
        # arena leg (ISSUE 19, bench_serve.py arena_leg): cross-model
        # coalescing throughput vs dedicated per-model sessions
        ar = parsed.get("arena") or {}
        if isinstance(ar.get("speedup"), (int, float)) \
                and not isinstance(ar.get("speedup"), bool):
            row["metrics"]["serve_arena_speedup"] = float(ar["speedup"])
        # serving mode stamp: did the cold boot actually ride persisted
        # executables?  find_mode_regressions flags an on -> off flip
        # exactly like fused_sibling — a disarmed store posts the same
        # green checks while silently re-paying JIT on every boot
        if cs:
            row["mode"] = {"serve_aot": bool(
                (cs.get("aot_on") or {}).get("aot_buckets"))}
        if parsed.get("degraded"):
            row["canary"] = "serve-degraded"
            row["note"] = "degraded to host predictor — excluded from " \
                          "baselines"
        return row
    if "per_iteration" in parsed:  # a telemetry_report.py --json digest
        row["context"] = ("telemetry",)
        if parsed.get("cum_row_iters_per_s"):
            row["metrics"]["value"] = float(parsed["cum_row_iters_per_s"])
        for k, v in (parsed.get("phase_s") or {}).items():
            row["metrics"][f"phase_s/{k}"] = float(v)
        for k, v in (parsed.get("metrics_last") or {}).items():
            row["metrics"][k] = float(v)
        _fold_digest(row["metrics"], parsed)
        return row
    row["context"] = tuple(parsed.get(k) for k in _CONTEXT_KEYS)
    wr = payload.get("wedge_retries")
    if isinstance(wr, int) and wr > 0:
        # a RECOVERED round (tools/tpu_window.py retried wedged legs):
        # the numbers are real — the backend answered in the end — but
        # the flag distinguishes them from clean rounds when judging a
        # flaky window
        row["recovered"] = wr
        row["metrics"]["wedge_retries"] = float(wr)
        row["note"] = ((row.get("note", "") + "; ") if row.get("note")
                       else "") + f"recovered after {wr} wedge retr" \
            f"{'y' if wr == 1 else 'ies'}"
    backend = parsed.get("backend")
    if backend:
        # cpu-fallback / cpu-forced rounds are wedge CANARIES: evidence
        # the machinery still runs, never perf datapoints.  They are
        # excluded from regression baselines entirely (find_regressions)
        # and flagged in the table so a degraded number is never quoted
        # as a trajectory point (VERDICT round-5 weak #4).
        row["canary"] = str(backend)
        row["note"] = f"{backend} canary — excluded from baselines"
    # triage comes after the canary note, which assigns rather than
    # appends
    _apply_triage(row, payload)
    for k, v in parsed.items():
        if isinstance(v, bool) or k == "n":
            continue
        if isinstance(v, (int, float)):
            row["metrics"][k] = v
    if isinstance(parsed.get("kernel_roofline"), dict):
        for k, v in parsed["kernel_roofline"].items():
            row["metrics"][f"kernel_roofline/{k}"] = float(v)
    _fold_measured(row, parsed, payload)
    td = parsed.get("telemetry")
    if isinstance(td, dict):
        _fold_digest(row["metrics"], td)
    # wave-pipeline mode stamps (non-numeric — hist_mode is a string,
    # fused_sibling a bool — so the numeric fold above skips them): kept
    # on the row for find_mode_regressions, bench.py flat fields first,
    # the embedded digest's wave_pipeline section as fallback
    wp = (td.get("wave_pipeline") if isinstance(td, dict) else None) or {}
    mode = {}
    for k in ("hist_mode", "fused_sibling", "fused_grad"):
        v = parsed.get(k, wp.get(k))
        if v is not None:
            mode[k] = v
    if mode:
        row["mode"] = mode
    return row


def _fold_measured(row: dict, parsed: dict, payload: dict) -> None:
    """Fold measured-roofline rows (ISSUE 18) into a trajectory row.

    bench.py embeds a flat ``{kernel: roofline_frac}`` dict on the
    bench line; tpu_window.py embeds the full ``kernel_measured`` row
    list at the record's top level.  Both trend as
    ``kernel_measured/<kernel>``, and the full rows ride on the row
    for ``find_measured_divergence``."""
    km = parsed.get("kernel_measured")
    if isinstance(km, dict):
        for k, v in km.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                row["metrics"][f"kernel_measured/{k}"] = float(v)
    km_rows = payload.get("kernel_measured")
    if isinstance(km_rows, list):
        measured = [r for r in km_rows
                    if isinstance(r, dict) and r.get("kernel")]
        for r in measured:
            frac = r.get("roofline_frac")
            if isinstance(frac, (int, float)):
                row["metrics"].setdefault(
                    f"kernel_measured/{r['kernel']}", float(frac))
        if measured:
            row["measured"] = measured


def _fold_digest(metrics: dict, digest: dict) -> None:
    """Pull trajectory-worthy numbers out of an obs digest."""
    wp = digest.get("wave_pipeline") or {}
    for k in ("waves_per_tree", "wave_capacity"):
        if isinstance(wp.get(k), (int, float)):
            metrics.setdefault(k, float(wp[k]))
    counters = digest.get("counters") or {}
    if "jax/compiles" in counters:
        metrics.setdefault("jax_compiles", float(counters["jax/compiles"]))
    mem = digest.get("memory") or {}
    if mem.get("peak_bytes"):
        metrics.setdefault("peak_hbm_bytes", float(mem["peak_bytes"]))
    for k, v in (digest.get("kernels") or {}).items():
        metrics.setdefault(f"kernel_roofline/{k}",
                           float(v.get("roofline_frac", 0.0)))
    for k, v in ((digest.get("xprof") or {}).get("kernels") or {}).items():
        if isinstance(v, dict) and v.get("roofline_frac") is not None:
            metrics.setdefault(f"kernel_measured/{k}",
                               float(v["roofline_frac"]))
    comp = digest.get("compile") or {}
    for name in ("cache_hits", "cache_misses", "retraces"):
        if isinstance(comp.get(name), (int, float)):
            metrics.setdefault(f"compile_{name}", float(comp[name]))


def collect(paths: List[str]) -> List[dict]:
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            files.extend(sorted(glob.glob(os.path.join(p, "BENCH_r*.json"))))
            files.extend(sorted(glob.glob(os.path.join(p, "SERVE_r*.json"))))
            files.extend(sorted(glob.glob(os.path.join(p, "ONLINE_r*.json"))))
            files.extend(sorted(glob.glob(os.path.join(p, "INGEST_r*.json"))))
            files.extend(sorted(glob.glob(os.path.join(p, "DRIFT_r*.json"))))
            files.extend(sorted(glob.glob(
                os.path.join(p, "MULTICHIP_r*.json"))))
            files.extend(sorted(glob.glob(os.path.join(p, "FLEET_r*.json"))))
        else:
            files.append(p)
    rows = []
    for f in files:
        try:
            rows.append(load_round(f))
        except (OSError, ValueError) as exc:
            rows.append({"round": os.path.basename(f), "path": f,
                         "context": None, "metrics": {},
                         "note": f"unreadable: {exc}"})
    rows.sort(key=lambda r: r["round"])
    return rows


def find_regressions(rows: List[dict], threshold: float) -> List[dict]:
    """Latest comparable round vs the best earlier comparable value, per
    tracked metric.  Canary rounds (degraded-backend runs, see
    ``load_round``) participate on NEITHER side of the comparison."""
    rows = [r for r in rows if not r.get("canary")]
    latest = next((r for r in reversed(rows) if r["metrics"]), None)
    if latest is None:
        return []
    prior = [r for r in rows
             if r is not latest and r["metrics"]
             and r["context"] == latest["context"]]
    if not prior:
        return []
    out = []
    for name, cur in latest["metrics"].items():
        up = metric_direction(name)
        if up is None:
            continue
        vals = [(r["round"], r["metrics"][name]) for r in prior
                if name in r["metrics"]]
        if not vals:
            continue
        best_round, best = (max if up else min)(vals, key=lambda rv: rv[1])
        if not best:
            continue
        change = (cur - best) / abs(best)
        worse = -change if up else change
        if worse > threshold:
            out.append({
                "metric": name, "round": latest["round"],
                "value": cur, "best": best, "best_round": best_round,
                "change_frac": round(change, 4),
                "direction": "higher_is_better" if up
                else "lower_is_better",
            })
    return sorted(out, key=lambda r: -abs(r["change_frac"]))


def find_mode_regressions(rows: List[dict]) -> List[dict]:
    """Wave-pipeline MODE downgrades, flagged like perf regressions
    (ISSUE 8): a round whose histogram precision mode changed, or whose
    in-kernel sibling fusion silently flipped off, against the most
    recent comparable prior round.  These are categorical, not numeric —
    a bf16 round can post a better throughput while computing a worse
    histogram, which no threshold on ``value`` would ever catch.
    (waves_per_tree / wave_capacity drift is numeric and handled by
    ``find_regressions``.)"""
    rows = [r for r in rows if not r.get("canary")]
    latest = next((r for r in reversed(rows) if r.get("mode")), None)
    if latest is None:
        return []
    prior = next((r for r in reversed(rows)
                  if r is not latest and r.get("mode")
                  and r["context"] == latest["context"]), None)
    if prior is None:
        return []
    out = []
    lm, pm = latest["mode"], prior["mode"]
    for knob in ("fused_sibling", "fused_grad", "serve_aot"):
        # a fused pass silently flipping off is a pipeline downgrade
        # even when throughput noise hides it (fused_grad joins
        # fused_sibling in ISSUE 11 — the unfused twin re-pays the [N]
        # g/h round-trip every iteration; serve_aot joins in ISSUE 19 —
        # a disarmed executable store re-pays the full pow2 compile
        # family on every replica boot)
        if pm.get(knob) is True and lm.get(knob) is False:
            out.append({"metric": knob, "round": latest["round"],
                        "value": "off", "prior": "on",
                        "prior_round": prior["round"]})
    if (lm.get("hist_mode") and pm.get("hist_mode")
            and lm["hist_mode"] != pm["hist_mode"]):
        # ANY hist-mode change is flagged — which covers the ISSUE 11
        # downgrade of interest (a quantized int16/int8 round silently
        # reverting to an f32-family mode re-pays the full vector
        # stream and MXU passes)
        out.append({"metric": "hist_mode", "round": latest["round"],
                    "value": lm["hist_mode"], "prior": pm["hist_mode"],
                    "prior_round": prior["round"]})
    return out


def find_device_regressions(rows: List[dict]) -> List[dict]:
    """Multi-chip CATEGORICAL flags (ISSUE 20): the latest real (non-
    skipped) ``MULTICHIP_r*`` leg against the most recent real prior
    one — a device-count drop (the lease handed back a smaller slice,
    or the mesh config silently shrank) and an ok -> failed flip are
    both regressions no throughput threshold would catch.  Skipped
    legs (no multi-device backend in the container) participate on
    neither side, like canaries in ``find_regressions``."""
    mc = [r for r in rows
          if r.get("context") == ("multichip",)
          and r.get("canary") != "multichip-skipped"]
    if len(mc) < 2:
        return []
    latest, prior = mc[-1], mc[-2]
    out = []
    ln = latest["metrics"].get("n_devices")
    pn = prior["metrics"].get("n_devices")
    if ln is not None and pn is not None and ln < pn:
        out.append({"metric": "n_devices", "round": latest["round"],
                    "value": ln, "prior": pn,
                    "prior_round": prior["round"]})
    if (prior["metrics"].get("multichip_ok") == 1.0
            and latest["metrics"].get("multichip_ok") == 0.0):
        out.append({"metric": "multichip_ok", "round": latest["round"],
                    "value": "failed", "prior": "ok",
                    "prior_round": prior["round"]})
    return out


def find_measured_divergence(rows: List[dict],
                             factor: float = _DIVERGENCE_FLAG
                             ) -> List[dict]:
    """Measured-vs-model divergence (ISSUE 18): kernels on the latest
    non-canary round carrying trace-attributed measured rows whose
    roofline fraction is more than ``factor`` x off the analytic model
    in either direction — ``frac < 1/factor`` means the kernel runs far
    off the roofline the model promises (a real perf bug or a wrong
    machine-peak assumption), ``frac > factor`` means the model
    under-prices the op, so every prediction built on it (wave
    scheduling, reconciliation, A/B expectations) is wrong.  Reported
    and exit-code gated like ``find_mode_regressions``: categorical
    flags a threshold on throughput would never catch."""
    rows = [r for r in rows if not r.get("canary")]
    latest = next(
        (r for r in reversed(rows)
         if any(k.startswith("kernel_measured/") for k in r["metrics"])),
        None)
    if latest is None:
        return []
    out = []
    for k in sorted(latest["metrics"]):
        if not k.startswith("kernel_measured/"):
            continue
        frac = latest["metrics"][k]
        if frac <= 0:
            continue
        if frac > factor or frac < 1.0 / factor:
            out.append({
                "metric": k, "round": latest["round"],
                "roofline_frac": round(frac, 4),
                "divergence": round(max(frac, 1.0 / frac), 2),
                "side": ("model-underprices" if frac > 1
                         else "off-roofline"),
            })
    return sorted(out, key=lambda r: -r["divergence"])


def find_swap_blips(rows: List[dict]) -> List[dict]:
    """Serving rounds whose hot-swap blip p99 exceeded
    ``_SWAP_BLIP_FLAG`` x their steady p99 (stamped by ``load_round``),
    reported like mode regressions: categorical flags the numeric
    threshold pass would miss (a blip can double while the steady p99
    improves)."""
    return [{"metric": "swap_blip_p99_ms", "round": r["round"],
             "value": r["metrics"].get("serve_swap_blip_p99_ms"),
             "steady": r["metrics"].get("serve_steady_p99_ms"),
             "ratio": r["swap_blip"]}
            for r in rows if r.get("swap_blip")]


def canary_trend(rows: List[dict]) -> List[dict]:
    """per_iter_s + throughput trajectory across CANARY rounds of the
    same context.  Canaries never enter regression baselines
    (``find_regressions`` drops them), which also meant a perf win was
    INVISIBLE when consecutive rounds all ran on the CPU fallback — this
    surfaces per-iteration seconds alongside throughput for those rounds
    as an informational trend (never a gate): a partition-path speedup
    shows up as a per_iter_s drop between canaries even without a TPU
    datapoint."""
    prev: dict = {}
    out = []
    for r in rows:
        if not r.get("canary") or not r["metrics"]:
            continue
        ent = {"round": r["round"], "backend": r.get("canary"),
               "per_iter_s": r["metrics"].get("per_iter_s"),
               "value": r["metrics"].get("value")}
        p = prev.get(r["context"])
        if p:
            for m in ("per_iter_s", "value"):
                cur, base = ent.get(m), p.get(m)
                if cur is not None and base:
                    ch = (cur - base) / abs(base)
                    ent[f"{m}_change_frac"] = round(ch, 4)
        prev[r["context"]] = ent
        out.append(ent)
    return out


def render(rows: List[dict], regressions: List[dict],
           mode_regressions: List[dict] = (),
           swap_blips: List[dict] = (),
           measured_divergence: List[dict] = (),
           device_regressions: List[dict] = ()) -> str:
    cols = [c for c in _TABLE_COLS
            if any(c in r["metrics"] for r in rows)]
    out = [f"{'round':<6}{'context':<34}"
           + "".join(f"{c:>22}" for c in cols)]
    for r in rows:
        ctx = "-" if r["context"] is None else \
            ",".join(str(x) for x in r["context"])
        line = f"{r['round']:<6}{ctx[:33]:<34}"
        for c in cols:
            v = r["metrics"].get(c)
            if v is None:
                line += f"{'-':>22}"
            elif abs(v) >= 1e6:
                line += f"{v:>22,.0f}"
            else:
                line += f"{v:>22,.4g}"
        if r.get("note"):
            line += f"  ({r['note']})"
        out.append(line)
    if regressions:
        out.append("")
        out.append("REGRESSIONS (latest vs best comparable prior round):")
        for g in regressions:
            out.append(
                f"  {g['metric']:<32} {g['value']:>14,.6g} vs best "
                f"{g['best']:>14,.6g} ({g['best_round']}) "
                f"{g['change_frac']:+.1%} [{g['direction']}]")
    else:
        out.append("")
        out.append("no regressions against comparable prior rounds")
    if mode_regressions:
        out.append("")
        out.append("MODE REGRESSIONS (wave-pipeline downgrade vs prior "
                   "comparable round):")
        for g in mode_regressions:
            out.append(f"  {g['metric']:<32} {g['value']} vs "
                       f"{g['prior']} ({g['prior_round']})")
    if swap_blips:
        out.append("")
        out.append(f"SWAP BLIPS (hot-swap p99 > {_SWAP_BLIP_FLAG:g}x "
                   "steady p99 — the flip leaked into the request path):")
        for g in swap_blips:
            out.append(f"  {g['round']}: blip {g['value']:g}ms vs steady "
                       f"{g['steady']:g}ms ({g['ratio']:g}x)")
    if device_regressions:
        out.append("")
        out.append("DEVICE REGRESSIONS (latest multi-chip leg vs the "
                   "prior real one):")
        for g in device_regressions:
            out.append(f"  {g['metric']:<32} {g['value']} vs "
                       f"{g['prior']} ({g['prior_round']})")
    if measured_divergence:
        out.append("")
        out.append(f"MEASURED-VS-MODEL DIVERGENCE (> {_DIVERGENCE_FLAG:g}x "
                   "off the analytic roofline — the kernel or the cost "
                   "model is lying):")
        for g in measured_divergence:
            out.append(f"  {g['metric']:<40} frac "
                       f"{g['roofline_frac']:g} "
                       f"({g['divergence']:g}x {g['side']}) "
                       f"[{g['round']}]")
    trend = [t for t in canary_trend(rows)
             if "per_iter_s_change_frac" in t or "value_change_frac" in t]
    if trend:
        out.append("")
        out.append("canary trend (informational — degraded-backend rounds, "
                   "never a baseline):")
        for t in trend:
            bits = [f"  {t['round']} [{t['backend']}]"]
            if t.get("per_iter_s") is not None:
                bits.append(f"per_iter_s {t['per_iter_s']:g}")
                if "per_iter_s_change_frac" in t:
                    bits.append(f"({t['per_iter_s_change_frac']:+.1%})")
            if t.get("value") is not None:
                bits.append(f"value {t['value']:,.4g}")
                if "value_change_frac" in t:
                    bits.append(f"({t['value_change_frac']:+.1%})")
            out.append(" ".join(bits))
    return "\n".join(out)


def main() -> int:
    ap = argparse.ArgumentParser(
        description="Bench-round trajectory table + regression flags")
    ap.add_argument("paths", nargs="*",
                    default=[os.path.dirname(os.path.dirname(
                        os.path.abspath(__file__)))],
                    help="BENCH_r*.json files, telemetry digests, or "
                         "directories (default: repo root)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable digest instead of the table")
    ap.add_argument("--threshold", type=float, default=0.1,
                    help="relative worsening that counts as a regression "
                         "(default 0.10)")
    ap.add_argument("--fail-on-regression", action="store_true",
                    help="exit 1 when any regression is flagged")
    args = ap.parse_args()
    rows = collect(args.paths)
    if not rows:
        print("no bench rounds found", file=sys.stderr)
        return 1
    regressions = find_regressions(rows, args.threshold)
    mode_regressions = find_mode_regressions(rows)
    swap_blips = find_swap_blips(rows)
    measured_divergence = find_measured_divergence(rows)
    device_regressions = find_device_regressions(rows)
    if args.json:
        print(json.dumps({"rounds": rows, "regressions": regressions,
                          "mode_regressions": mode_regressions,
                          "swap_blips": swap_blips,
                          "measured_divergence": measured_divergence,
                          "device_regressions": device_regressions,
                          "canary_trend": canary_trend(rows)}))
    else:
        print(render(rows, regressions, mode_regressions, swap_blips,
                     measured_divergence, device_regressions))
    if ((regressions or mode_regressions or swap_blips
         or measured_divergence or device_regressions)
            and args.fail_on_regression):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
