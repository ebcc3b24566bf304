"""Generate docs/PARAMETERS.md from the Config dataclass + alias table.

The reference generates Parameters.rst from config.h with
helpers/parameter_generator.py — one annotated source of truth.  This is
the same property for the TPU build: ``lightgbm_tpu/config.py`` defines
every field, default, and alias; this script renders them, grouped by the
dataclass's section comments, with inline ``#`` comments as descriptions.

Run: python tools/gen_param_docs.py   (rewrites docs/PARAMETERS.md)
"""
from __future__ import annotations

import inspect
import os
import re
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lightgbm_tpu import config as cfgmod
from lightgbm_tpu.config import _ALIASES, _MULTI_VALUE, Config


def parse_sections():
    """(section, name, default_repr, comment) in declaration order."""
    src = inspect.getsource(Config)
    section = "Other"
    rows = []
    for line in src.splitlines():
        s = line.strip()
        m = re.match(r"# ---- (.+?) ----", s)
        if m:
            section = m.group(1)
            continue
        m = re.match(r"(\w+):\s*[\w\[\]\.]+\s*=\s*(.+?)(?:\s*#\s*(.*))?$", s)
        if m and not s.startswith("#"):
            name, default, comment = m.groups()
            default = default.strip()
            if default.startswith("field(default_factory=list)"):
                default = "[]"
            elif "default_factory=lambda" in default:
                inner = re.search(r"lambda:\s*(.+?)\)\s*$", default)
                default = inner.group(1) if inner else default
            rows.append((section, name, default, comment or ""))
    return rows


# Process-level switches living outside the Config surface (they must work
# before any Config exists — at import time).  Rendered as their own
# section so the generated doc stays the one place parameters live.
ENV_VARS = [
    ("LGBM_TPU_TIMETAG",
     "set to `1` to accumulate per-phase wall times (binning, boosting, "
     "tree growth, score update, predict) and print them at process exit "
     "— the reference's compiled-in `TIMETAG` analog.  Synchronizes the "
     "device after each phase, so throughput drops while it is on."),
    ("LGBM_TPU_TELEMETRY",
     "path of the structured telemetry sink: a directory (per-process "
     "`telemetry.{process_index}.jsonl` files inside it) or a `*.jsonl` "
     "file.  Streams JSONL events — one `iteration` record per boosting "
     "iteration (phase timings, train/valid metrics, leaves, wave count, "
     "counter snapshots, recompile deltas), `collective` records for "
     "psum/all_gather traffic, and an atexit `summary`.  Merge with "
     "`python tools/telemetry_report.py <path>`.  Equivalent to the "
     "`tpu_telemetry` parameter.  Implies the same per-phase device "
     "synchronization as `LGBM_TPU_TIMETAG`."),
    ("LGBM_TPU_HEALTH",
     "training-health sentinels (equivalent to the `tpu_health` "
     "parameter): `monitor` (or `1`) finite-checks every iteration's "
     "gradients/hessians (attributed to the objective that produced "
     "them, plus GOSS's amplifier and DART's renormalized scores), "
     "split gains and leaf values (attributed to node + feature), and "
     "histogram-total conservation (leaf count/weight sums vs the "
     "root); emits `health` events on failure and per-iteration "
     "`fingerprint` events (cheap hash of the score vector + tree "
     "arrays, interval set by `tpu_fingerprint_freq`); under "
     "multi-process training the fingerprints are compared across "
     "ranks each iteration and a mismatch ABORTS with which-rank "
     "attribution (`divergence` event).  `strict` additionally aborts "
     "on the first numerics failure with a `TrainingHealthError` "
     "naming the phase/iteration (and node/feature).  PROCESS-WIDE "
     "once on, like the telemetry sink; checks synchronize the device "
     "each iteration, so expect a few percent overhead — off (unset) "
     "costs one boolean per check site.  `tools/tpu_window.py` runs "
     "every capture leg with `monitor` on so a TPU-window datapoint "
     "certifies itself."),
    ("LGBM_TPU_TRACE",
     "set to `1` for trace mode (equivalent to the `tpu_trace` "
     "parameter): the span layer (`obs/spans.py`) emits one `span` "
     "event per completed span — serving requests "
     "(queue→coalesce→pad→device-execute, trace_id minted at the HTTP "
     "edge from `X-Request-Id`) and training iterations (iteration + "
     "its phase timers) share the schema, so "
     "`python tools/trace_export.py <telemetry path>` renders both on "
     "one Perfetto/Chrome timeline.  PROCESS-WIDE once on; it "
     "sync-brackets phases — attribution runs only, never benchmarks."),
    ("LGBM_TPU_FLIGHT",
     "flight-recorder ring length (equivalent to the `tpu_flight_len` "
     "parameter, default 256; `0` disables): the last N spans + "
     "operational events (health, degradation, overload, iteration, "
     "serve batches) kept in memory with no telemetry sink needed, and "
     "dumped as `FLIGHT_rN.json` on a serve degradation flip, an "
     "overload storm, a `TrainingHealthError`/divergence abort, or on "
     "demand via `GET /debug/flight`.  `LGBM_TPU_FLIGHT_DIR` chooses "
     "the dump directory (default: the working directory)."),
    ("LGBM_TPU_TRAIN_METRICS",
     "train-side metrics exporter port (overrides the "
     "`tpu_train_metrics_port` parameter): `0` binds an ephemeral "
     "port, `N>0` binds `N + process_index` (each rank of a multi-host "
     "run exports locally without colliding), `off`/`false`/`-1` "
     "disarms.  While a train runs, `GET /metrics` serves the "
     "Prometheus exposition (iteration, ETA, cumulative "
     "`row_iters_per_s`, per-phase wall fractions, checkpoint age, "
     "watchdog/retry/stall counters, recompiles, collective bytes, "
     "straggler skew), "
     "`GET /progress` the JSON progress view (smoothed ETA, last-K "
     "iteration records, live `vs_baseline`), and `GET /debug/flight` "
     "the live flight ring.  `tools/train_watch.py <url>` tails it as "
     "a console view."),
    ("LGBM_TPU_SERVE_SLO_P99_MS",
     "serving-engine override for `tpu_serve_slo_p99_ms` — the p99 "
     "latency objective the `/metrics` + `/health` SLO-burn gauge "
     "measures against (over-target fraction of recent requests "
     "divided by the 1% budget a p99 objective allows; 1.0 = burning "
     "budget exactly at the allowed rate)."),
    ("LGBM_TPU_SERVE_AOT_DIR",
     "AOT executable store directory (overrides the "
     "`tpu_serve_aot_dir` parameter; `serve/aot.py`).  When set, every "
     "pow2-bucket executable a `PredictorSession` (or the arena) "
     "compiles is serialized there, keyed by kind | backend platform | "
     "jax version | row bucket | forest-content digest — a later "
     "process with the same model boots from the store and serves "
     "request #1 with zero JIT compiles (`serve_coldstart_ms` in "
     "`SERVE_rN.json` measures the A/B).  Stale, corrupt, or "
     "cross-backend entries fall back to JIT loudly (`aot_fallback` "
     "flight event + `serve/aot_fallbacks` counter) with bit-identical "
     "output.  `tpu_serve_aot=false` disarms the store entirely."),
    ("LGBM_TPU_SERVE_MAX_BATCH",
     "serving-engine override for `tpu_serve_max_batch` (the per-batch "
     "row cap of `serve.PredictorSession`); lets an operator retune a "
     "running deployment's batching without editing model/config files. "
     "`LGBM_TPU_SERVE_MAX_WAIT_MS` and `LGBM_TPU_SERVE_QUEUE_DEPTH` "
     "override the matching `tpu_serve_*` parameters the same way; an "
     "explicit constructor argument still wins over the env var."),
    ("LGBM_TPU_SERVE_MAX_WAIT_MS",
     "serving-engine override for `tpu_serve_max_wait_ms` — the longest "
     "the microbatcher holds the oldest queued request while coalescing "
     "(the latency knob of the latency/throughput trade)."),
    ("LGBM_TPU_SERVE_QUEUE_DEPTH",
     "serving-engine override for `tpu_serve_queue_depth` — the queued-"
     "row bound after which `submit` fails fast with an overload error "
     "(explicit backpressure instead of unbounded buffering)."),
    ("LGBM_TPU_FAULTS",
     "deterministic fault-injection spec (robust/faults.py) — "
     "`point:action[@cond[&cond...]]` legs separated by `;`.  Points: "
     "`device_execute`, `gradients`, `collective`, `serve_device`, "
     "`serve_explain_submit`, `serve_explain_device`, `serve_replica` "
     "(plus per-replica `serve_replica_{i}`), `serve_swap`, "
     "`serve_canary`, `checkpoint_write`, `online_ingest`, "
     "`online_refit`, `online_swap`.  Actions: `raise` (fatal), "
     "`transient` (the watchdog's retry path), `sleep=S` (stall the "
     "step), `hang`.  Conds: `iter=N` (boosting iteration), `call=N` "
     "(N-th check at that point), `p=F` (seeded probability), `n=N` "
     "(fire at most N times, default 1, -1 = always).  Example: "
     "`device_execute:transient@iter=3&n=2;serve_device:raise`.  Used "
     "by the `tools/fault_matrix.py` and `tools/chaos_serve.py` suite "
     "tiers to prove every recovery branch on CPU."),
    ("LGBM_TPU_FAULTS_SEED",
     "seed for the fault harness's probabilistic conds (`p=`); the same "
     "spec + seed replays the identical fault schedule (default 0)."),
    ("LGBM_TPU_FORCE_WAVE",
     "test hook: set to `interpret` to route the serial grower through "
     "the wave pipeline with the Pallas INTERPRETER on any backend, so "
     "CPU CI trains end to end through the packed/fused/quantized "
     "kernel path (tests/test_hist_quant.py's AUC-budget and "
     "resume differentials ride it).  Orders of magnitude slower than "
     "both the XLA fallback and a real TPU — never benchmark with it."),
    ("LGBM_TPU_EXPLAIN",
     "serving-engine override for `tpu_explain` — set to `0`/`false` to "
     "remove `POST /explain` and `PredictorSession.explain()` from a "
     "running deployment (the endpoint answers 404, the session raises), "
     "or `1` to force it on.  The TreeSHAP forest pack (per-node cover "
     "counts + path metadata) is built lazily on the first explain call "
     "either way, so predict-only sessions never pay the HBM cost."),
    ("LGBM_TPU_EXPLAIN_MAX_BATCH",
     "serving-engine override for `tpu_explain_max_batch` — the row cap "
     "of the explain plane's OWN microbatcher and pow2 bucket family "
     "(compiles at most `ceil(log2(max_batch)) + 1` TreeSHAP kernel "
     "shapes, counted by the same recompile counter as predict's).  "
     "Kept separate from `tpu_serve_max_batch` because one explained "
     "row costs O(leaves x depth^2) where a predicted row costs "
     "O(depth)."),
    ("LGBM_TPU_EXPLAIN_MAX_WAIT_MS",
     "serving-engine override for `tpu_explain_max_wait_ms` — the "
     "longest the explain microbatcher holds the oldest queued request "
     "while coalescing."),
    ("LGBM_TPU_SERVE_REPROBE_S",
     "serving-engine override for `tpu_serve_reprobe_s` — seconds "
     "between device re-probes while a session is degraded to the host "
     "predictor; a successful probe flips `/health` back to `ok` "
     "(`0` disables, restoring the old one-way latch)."),
    ("LGBM_TPU_SERVE_REPLICAS",
     "serving-fleet override for `tpu_serve_replicas` — how many "
     "`PredictorSession` replicas each registered model version packs "
     "behind the failover router (per-device on a multi-chip host, "
     "thread-pool replicas on CPU).  One wedged replica then costs "
     "capacity, never availability (its circuit breaker opens and a "
     "half-open probe re-admits it when it recovers)."),
    ("LGBM_TPU_SERVE_ROLLBACK_WATCH_S",
     "serving-fleet override for `tpu_serve_rollback_watch_s` — how "
     "long after a hot-swap the registry watches the new live version's "
     "metrics (failed-request rate, degraded transitions, SLO burn) and "
     "rolls back AUTOMATICALLY to the still-resident previous version "
     "on a regression (`0` disables the watch; manual "
     "`POST /models/{name}/rollback` always works)."),
    ("LGBM_TPU_SERVE_SHED_LOW_FRAC",
     "serving-engine override for `tpu_serve_shed_low_frac` — the "
     "fraction of the queue-row budget low-priority requests may fill "
     "before overload sheds them (`Retry-After` on the 503; per-class "
     "served/shed counters in `/metrics`).  "
     "`LGBM_TPU_SERVE_SHED_NORMAL_FRAC` overrides the normal-priority "
     "budget the same way; high priority always owns the full queue."),
    ("LGBM_TPU_ONLINE_REFIT_EVERY",
     "online-loop override for `tpu_online_refit_every` — the row "
     "cadence of `task=online`'s refresh cycle (refit/continue + "
     "canary-gated swap every N freshly ingested labeled rows); lets "
     "an operator retune a running loop's refresh rate without "
     "editing config files.  `LGBM_TPU_ONLINE_WINDOW` overrides "
     "`tpu_online_window` the same way."),
    ("LGBM_TPU_ONLINE_WINDOW",
     "online-loop override for `tpu_online_window` — the bounded "
     "ingest window: how many of the freshest labeled rows the loop "
     "keeps for the next refresh (older rows fall out; memory-bounded "
     "like the serve queue)."),
    ("LGBM_TPU_INGEST_CHUNK_ROWS",
     "streaming-ingestion override for `tpu_ingest_chunk_rows` — rows "
     "per streamed chunk for the array/`.npy`/`.npz`/LibSVM readers "
     "(the peak-raw-memory knob of `ingest/`); lets an operator retune "
     "a running pipeline's chunking without editing configs.  Chunk "
     "size never changes the constructed dataset (test-pinned), so it "
     "also sits in the checkpoint config-digest skip list."),
    ("LGBM_TPU_INGEST_MEMMAP",
     "streaming-ingestion override for `tpu_ingest_memmap` — back the "
     "binned matrix with an `np.memmap` file instead of host RAM: a "
     "directory (per-shard `X_bin.shardN.npy` inside) or a file path.  "
     "With it set, peak host RAM during ingestion is O(chunk + "
     "sample) even though the constructed dataset may be far larger."),
    ("LGBM_TPU_PREDICT_MIN_WORK",
     "CLI `task=predict` routing override: the rows x trees work "
     "threshold above which value predictions go through the serving "
     "session (device-resident forest, pow2 buckets) instead of the "
     "host loop.  `0` forces every predict through the session; a huge "
     "value forces the host loop.  Unset uses the booster's built-in "
     "dispatch-overhead heuristic."),
    ("LGBM_TPU_CONTRIB_MIN_WORK",
     "`predict_contrib` routing override: the rows x trees work "
     "threshold above which contribution requests go through the "
     "batched device TreeSHAP kernel (`explain/`) instead of the host "
     "oracle (`core/shap.py`).  `0` forces every contrib through the "
     "device kernel; a huge value forces the host oracle.  Unset uses "
     "the built-in threshold (50k), which keeps tiny ad-hoc calls off "
     "the compile path."),
    ("LGBM_TPU_DRIFT_SAMPLE_RATE",
     "drift-plane override for `tpu_drift_sample_rate` — the fraction "
     "of served feature rows the serve-side sketch samples (the "
     "prediction histogram always takes every response).  `1.0` "
     "sketches every batch — what the drift smoke pins; the default "
     "0.05 keeps the off-path overhead negligible.  "
     "`LGBM_TPU_DRIFT_CHECK_S`, `LGBM_TPU_DRIFT_MIN_ROWS` and "
     "`LGBM_TPU_DRIFT_PSI_WARN` override the cadence, the row floor "
     "and the breach threshold the same way; `LGBM_TPU_DRIFT=0` "
     "disarms the monitor entirely."),
    ("LGBM_TPU_QUALITY_WINDOW",
     "quality-plane override for `tpu_quality_window` — labeled rows "
     "per rolling evaluation window (the online loop's labeled stream "
     "feeds it).  `LGBM_TPU_QUALITY_DROP_WARN` overrides the windowed-"
     "AUC drop that counts as a breach."),
    ("LGBM_TPU_SERVE_ROLLBACK_ON_DRIFT",
     "registry override for `tpu_serve_rollback_on_drift` — opt a "
     "fleet into automatic post-swap rollback on a latched drift or "
     "quality breach.  Default off: breaches annotate the post-swap "
     "health report and dump the flight recorder, but never gate — "
     "drift is a property of TRAFFIC, and rolling back a good model "
     "because the world changed is usually wrong."),
    ("LGBM_TPU_FLEET",
     "elastic multi-host gang size (overrides the `tpu_fleet` "
     "parameter; `lightgbm_tpu/fleet/`).  `task=train` with a value "
     "N > 1 gang-launches N single-rank worker processes, rendezvoused "
     "through `rendezvous.json` in the fleet dir, and supervises them: "
     "liveness rides the fingerprint-gather cadence (zero extra sync "
     "points on the healthy path), a silent or dead rank is rolled "
     "back to the last common checkpoint and the survivors resume at "
     "the shrunk world, and (with `tpu_fleet_heal`) a replacement "
     "rank is relaunched and folds back in mid-run.  In the "
     "replicate-mode CI twin the final model is bit-identical to a "
     "single-process run at any world size.  Env overrides win over "
     "the config knobs so a CI wrapper can gang an unmodified "
     "params file."),
    ("LGBM_TPU_FLEET_HEARTBEAT_S",
     "override for `tpu_fleet_heartbeat_s` — the silence window "
     "(seconds, relative to each gather's first arrival) after which "
     "the coordinator classifies a rank dead and starts elastic "
     "recovery.  A rank merely lagging past half the window is "
     "stamped as a `fleet_stall` event but NOT killed."),
    ("LGBM_TPU_FLEET_TRANSPORT",
     "override for `tpu_fleet_transport`: `jax` forces "
     "`jax.distributed` device collectives, `host` forces the "
     "host-TCP coordinator (the CI twin that runs on CPU-only "
     "containers), `auto` (default) probes for cross-process device "
     "collective support and picks accordingly."),
    ("LGBM_TPU_FLEET_DIR",
     "override for `tpu_fleet_dir` — the rendezvous + fleet artifact "
     "directory (rendezvous address file, `fleet_events.jsonl` "
     "lifecycle trail, per-rank checkpoints, the `done.json` "
     "completion marker late joiners consult).  Default: a fresh "
     "`lgbm_tpu_fleet_*` temp directory per launch.  "
     "`LGBM_TPU_FLEET_RANK` / `LGBM_TPU_FLEET_JOIN` are internal "
     "per-worker stamps the launcher sets — setting them by hand "
     "makes a process act as a worker instead of the launcher."),
    ("LGBM_TPU_PEAK_FLOPS",
     "override the device peak FLOP/s that `chip_smoke.py` prices its "
     "kernel checks against (used with `LGBM_TPU_PEAK_BW`).  The "
     "built-in table (`obs/peaks.py DEVICE_PEAKS`) is keyed by "
     "`device_kind`; a device that is not in it raises unless both "
     "overrides are set."),
    ("LGBM_TPU_PEAK_BW",
     "override the device peak HBM bytes/s."),
    ("JAX_PLATFORMS",
     "standard JAX backend selector (`cpu` forces the XLA host path)."),
    ("JAX_COMPILATION_CACHE_DIR",
     "standard JAX variable placing the persistent XLA compilation "
     "cache.  When set, the package sets no directory in code and "
     "reports this one; unset, every entry point caches under the "
     "fixed `<checkout>/.jax_cache` (or `tpu_compile_cache_dir`), "
     "except that on the CPU backend no cache is placed by default.  See "
     "`lightgbm_tpu/utils/compile_cache.py`; `chip_smoke.py` and "
     "`bench.py` print the directory and whether it was warm."),
]

PROFILER_NOTE = (
    "Profiler scope naming: every device phase is annotated for "
    "`jax.profiler` traces under the `lgbm/` prefix — host-side phases "
    "as `lgbm/<phase name>` (TraceAnnotation, e.g. `lgbm/tree growth`), "
    "compiled regions as XLA metadata scopes (`lgbm/hist_onehot`, "
    "`lgbm/hist_scatter`, `lgbm/hist_wave_xla`, "
    "`lgbm/pallas_hist_wave`, `lgbm/wave_hist`, `lgbm/wave_split_phase`, "
    "`lgbm/wave_partition`, `lgbm/split_scan`, `lgbm/tree_traverse`, "
    "`lgbm/forest_predict`, `lgbm/forest_leaf`).")


def main() -> None:
    rows = parse_sections()
    aliases = defaultdict(list)
    for a, canon in _ALIASES.items():
        aliases[canon].append(a)

    out = ["# Parameters", "",
           "Generated from `lightgbm_tpu/config.py` by "
           "`tools/gen_param_docs.py` — the single source of truth for "
           "names, defaults, and aliases (the analog of the reference's "
           "`Parameters.rst` generated from `config.h`). Parameter names "
           "and aliases match LightGBM v2.3.2; see `README.md` for the "
           "TPU-specific additions (`tpu_*`).", ""]
    cur = None
    for section, name, default, comment in rows:
        if section != cur:
            out += [f"## {section}", ""]
            cur = section
        bits = [f"- **`{name}`** = `{default}`"]
        if name in _MULTI_VALUE:
            bits.append("(comma-separated list)")
        if comment:
            bits.append(f"— {comment}")
        out.append(" ".join(bits))
        al = sorted(aliases.get(name, []))
        if al:
            out.append(f"  - aliases: " + ", ".join(f"`{a}`" for a in al))
    out += ["## Environment variables", ""]
    for name, desc in ENV_VARS:
        out.append(f"- **`{name}`** — {desc}")
    out += ["", PROFILER_NOTE]
    out.append("")
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "PARAMETERS.md")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        fh.write("\n".join(out))
    print(f"wrote {path}: {sum(1 for r in rows)} parameters, "
          f"{sum(len(v) for v in aliases.values())} aliases")


if __name__ == "__main__":
    main()
