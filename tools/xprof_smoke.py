"""Measured-roofline plane smoke — the ``xprof`` suite tier (ISSUE 18).

Runs a short CPU train with the capture window + compile observer
armed (``LGBM_TPU_XPROF``, telemetry sink, metrics board, persistent
compile cache all on), then proves the plane end to end:

- **trace_captured**: the windowed ``jax.profiler`` capture produced
  at least one ``.trace.json.gz`` artifact and parsed cleanly;
- **kernels_attributed**: >= 3 distinct ``lgbm/*`` kernels with
  nonzero measured ms (plus the ``unattributed`` device residual);
- **model_joined**: at least one attributed kernel carries the
  analytic-model join (model_ms / roofline_frac / bound);
- **events_validate**: the emitted ``kernel_measured`` + ``compile``
  events pass ``report_mod.validate_events`` against their schemas;
- **digest_renders**: ``report.render`` of the sink digest contains
  the measured-roofline table and the compile-plane line;
- **compile_observed / cache_counted**: backend-compile walls and
  persistent-cache misses landed in the compile digest;
- **board_compile_metrics**: cache hit/miss + retrace gauges and the
  per-jit compile walls are visible in the board's ``/metrics`` text;
- **overhead_ok**: off-window ``step()`` accounting stays under 5% of
  train wall — the same off-path guard board_smoke.py pins.

The train shape is deliberately tiny: on the CPU backend the thunk
executor emits one TraceMe per HLO op per while-loop iteration, so
capture volume (and stop_trace export time) scales with row count.

    python tools/xprof_smoke.py --json

Last stdout line is the ``{"ok": ..., "checks": ...}`` verdict map
(the tools/run_suite.py tool-tier contract).  Exit 0 iff all pass.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROUNDS = 6
WINDOW_ITERS = 2


def _fetch(url: str, timeout: float = 3.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read()


def run_smoke() -> dict:
    work = tempfile.mkdtemp(prefix="lgbm_xprof_smoke_")
    telem = os.path.join(work, "telem")
    # env overrides beat outer settings so the smoke can't be disarmed
    os.environ["LGBM_TPU_XPROF"] = str(WINDOW_ITERS)
    os.environ["LGBM_TPU_TELEMETRY"] = telem
    os.environ["LGBM_TPU_TRAIN_METRICS"] = "0"  # ephemeral board port

    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    from lightgbm_tpu.obs import board, xprof
    import importlib
    report_mod = importlib.import_module('lightgbm_tpu.obs.report')

    if not obs.enabled():  # env gate ran at import; belt-and-braces
        obs.enable(telem)

    rng = np.random.default_rng(7)
    X = rng.normal(size=(500, 10))
    y = (X[:, 0] + 0.4 * X[:, 1] - 0.2 * X[:, 2] > 0).astype(np.float64)
    # the cache is placed explicitly, at its fixed default path: this
    # smoke also runs on the CPU backend, where the package places none
    # by default, and the compile plane's hit/miss counters need one
    from lightgbm_tpu.utils.compile_cache import DEFAULT_DIR
    params = {"objective": "binary", "num_leaves": 7,
              "min_data_in_leaf": 5, "verbose": -1,
              "tpu_train_metrics_port": 0,
              "tpu_compile_cache_dir": DEFAULT_DIR}
    ds = lgb.Dataset(X, label=y, params=params)

    state = {"metrics": None}

    # scrape /metrics once mid-train via a callback — the board dies
    # with the run and the compile gauges only exist while it serves
    def scrape(env):
        if state["metrics"] is None and env.iteration >= 2:
            b = board.current()
            if b is not None and b.port:
                try:
                    state["metrics"] = _fetch(b.url + "/metrics").decode()
                except Exception:
                    pass

    t0 = time.perf_counter()
    lgb.train(params, ds, num_boost_round=ROUNDS, callbacks=[scrape])
    wall = time.perf_counter() - t0

    digest = obs.digest()
    xp = digest.get("xprof") or {}
    comp = digest.get("compile") or {}

    checks = {}
    checks["trace_captured"] = (xp.get("trace_files", 0) > 0
                                and xp.get("trace_parsed", 0) > 0
                                and not xp.get("errors"))
    lgbm_kernels = {k: v for k, v in (xp.get("kernels") or {}).items()
                    if k.startswith("lgbm/") and v.get("measured_ms", 0) > 0}
    checks["kernels_attributed"] = len(lgbm_kernels) >= 3
    checks["model_joined"] = any(
        v.get("roofline_frac") is not None for v in lgbm_kernels.values())

    events = report_mod.load_events(telem)
    emitted = [e for e in events
               if e.get("event") in ("kernel_measured", "compile")]
    problems = report_mod.validate_events(
        events, kinds=("kernel_measured", "compile"))
    checks["events_validate"] = bool(emitted) and not problems

    rendered = report_mod.render(report_mod.summarize(events))
    checks["digest_renders"] = ("measured roofline" in rendered
                                and "compile plane" in rendered)

    checks["compile_observed"] = (comp.get("compiles", 0) > 0
                                  and comp.get("wall_s", 0) > 0
                                  and bool(comp.get("by_jit")))
    # warm on a rerun: every compile is a recorded hit or miss
    checks["cache_counted"] = (comp.get("cache_misses", 0)
                               + comp.get("cache_hits", 0)) > 0

    mtext = state["metrics"] or ""
    checks["board_compile_metrics"] = all(
        name in mtext for name in ("tpu_train_compile_cache_hits_total",
                                   "tpu_train_compile_cache_misses_total",
                                   "tpu_train_retraces_total",
                                   "tpu_train_compile_seconds_total"))

    # off-window overhead: re-run the same shape with the window pushed
    # past the horizon, so every step() takes the disarmed branch
    win = xprof.WindowedCapture(os.path.join(work, "never"),
                                iters=1, skip=10 ** 9)
    t1 = time.perf_counter()
    bst2 = lgb.Booster(params=params, train_set=ds)
    for _ in range(ROUNDS):
        bst2.update()
        win.step()
    wall2 = time.perf_counter() - t1
    checks["overhead_ok"] = win.hook_s < 0.05 * wall2

    return {
        "kind": "xprof",
        "t": round(time.time(), 1),
        "rounds": ROUNDS,
        "window_iters": WINDOW_ITERS,
        "wall_s": round(wall, 3),
        "hook_s": round(win.hook_s, 6),
        "window_ms": xp.get("window_ms"),
        "kernels": sorted(lgbm_kernels),
        "kernel_measured_events": sum(
            1 for e in emitted if e.get("event") == "kernel_measured"),
        "compiles": comp.get("compiles"),
        "cache_misses": comp.get("cache_misses"),
        "cache_hits": comp.get("cache_hits"),
        "validate_problems": problems[:5],
        "checks": checks,
        "ok": all(checks.values()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Capture->parse->attribute CPU smoke (xprof tier)")
    ap.add_argument("--json", action="store_true",
                    help="print only the JSON verdict line")
    args = ap.parse_args(argv)
    record = run_smoke()
    if not args.json:
        for k, v in record["checks"].items():
            print(f"  {'PASS' if v else 'FAIL'}  {k}")
    print(json.dumps(record))
    return 0 if record["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
