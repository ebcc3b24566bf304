"""Benchmark harness — HIGGS-like binary training throughput.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

Needs a TPU: without one, or when a leg fails or did not run the compiled
wave kernel, it exits non-zero and prints no metric.  BENCH_FORCE_CPU=1 is
the explicit control-flow dry run the tool tests use; it runs reduced
sizes on the CPU and prints under the name ``cpu_dry_run``, never under a
device metric's.

Baseline: the reference trains HIGGS (10.5M rows x 28 features, num_leaves
255, 500 iters) in 238.5 s on 2x E5-2670v3 (BASELINE.md, reference
docs/Experiments.rst:106) => 2.20e7 row-iterations/second.  This harness
trains the same shape of problem (synthetic unless a real HIGGS csv is
present at $HIGGS_PATH) and reports steady-state row-iterations/second;
vs_baseline > 1 means faster than the reference CPU result.

Env knobs: BENCH_ROWS (default 1_000_000), BENCH_ITERS (default 10),
BENCH_LEAVES (default 255), BENCH_MAXBIN (default 255 — 63 fills the
MXU 4x denser via feature packing, see docs/ROOFLINE.md),
BENCH_QUANT=int16|int8 (quantized histogram accumulation — the
bench_quant A/B leg; same problem, quantization-only delta).
BENCH_TASK=rank switches to an
MSLR-WEB30K-shaped lambdarank run only (ragged queries of 1..1251 docs,
136 features, NDCG@10) against the reference's published MSLR CPU time
(BASELINE.md: 215.32 s for 500 iters over 2.27M rows).  The rank legs
ride the SAME pipeline A/B knob as the headline (BENCH_QUANT) and stamp
the effective hist_mode / fused_grad into the rank_* line.

The DEFAULT run also appends the rank numbers (prefixed rank_*) to the
single JSON line, sized by BENCH_RANK_ROWS (default 200_000) /
BENCH_RANK_ITERS (default 5, minimum 2 — iteration 1 is compile warmup);
BENCH_RANK_ROWS=0 skips the rank leg.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REF_ROW_ITERS_PER_SEC = 10_500_000 * 500 / 238.5  # 2.2013e7
# MSLR-WEB30K train fold: 2,270,296 rows; reference CPU 500-iter time
# 215.32 s (BASELINE.md) => 5.272e6 row-iterations/second
REF_RANK_ROW_ITERS_PER_SEC = 2_270_296 * 500 / 215.32


def _telemetry_digest():
    """Machine-readable telemetry summary for the JSON line, when the run
    had LGBM_TPU_TELEMETRY / tpu_telemetry active; None otherwise.  The
    live counters digest (obs.digest) is enriched with the event-stream
    sections (wave_pipeline — waves_per_tree + the hist_mode/
    fused_sibling/fused_grad stamps) by reading the sink back through
    report.summarize: the live digest never carried them, which silently
    kept the mode stamps OFF the bench line (the ISSUE 8 flatten below
    read an always-absent key)."""
    try:
        from lightgbm_tpu import obs
        if not obs.enabled():
            return None
        d = obs.digest()
        try:
            from lightgbm_tpu.obs.core import sink_path
            from lightgbm_tpu.obs.report import load_events, summarize
            sink = sink_path()
            if sink and os.path.exists(sink):
                full = summarize(load_events(sink))
                for key in ("wave_pipeline",):
                    if full.get(key) is not None:
                        d[key] = full[key]
        except Exception:  # stream readback is best-effort
            pass
        return d
    except Exception:  # telemetry must never cost the bench its number
        pass
    return None


def _embed_compile_cache(result: dict) -> None:
    """Record whether this run had the persistent XLA compilation cache,
    and whether it was warm when enabled — a compile_s read without these
    fields can't be compared round over round (a warm-cache 0.3 s
    "compile" is a different measurement from a cold 4.4 s one)."""
    try:
        from lightgbm_tpu.utils.compile_cache import compile_cache_info
        info = compile_cache_info()
        if info.get("dir"):
            result["compile_cache_dir"] = info["dir"]
            result["compile_cache_warm"] = bool(info.get("warm"))
    except Exception:  # cache introspection must never cost the number
        pass


def _embed_observability(result: dict) -> None:
    """Fold the telemetry digest into the JSON line, with the compile
    plane flattened so bench_history.py can trend it round over round."""
    td = _telemetry_digest()
    if td is None:
        return
    result["telemetry"] = td
    comp = td.get("compile") or {}
    if comp:
        result["compile_cache_hits"] = comp.get("cache_hits", 0)
        result["compile_cache_misses"] = comp.get("cache_misses", 0)
    wave = td.get("wave_pipeline") or {}
    # flat wave-pipeline stamps: bench_history trends these so a silent
    # histogram-mode downgrade is flagged like a perf regression
    if wave.get("waves_per_tree") is not None:
        result["waves_per_tree"] = wave["waves_per_tree"]
    if wave.get("hist_mode"):
        result["hist_mode"] = wave["hist_mode"]
    if wave.get("fused_sibling") is not None:
        result["fused_sibling"] = wave["fused_sibling"]
    # a fused_grad on->off flip is flagged like a fused_sibling downgrade,
    # and the per-iteration HBM saving trends numerically
    if wave.get("fused_grad") is not None:
        result["fused_grad"] = wave["fused_grad"]
    if wave.get("grad_hbm_bytes_saved") is not None:
        result["grad_hbm_bytes_saved"] = wave["grad_hbm_bytes_saved"]
    counters = td.get("counters") or {}
    if counters.get("health/checks"):
        # health-mode runs carry their verdict in the bench line itself,
        # so a captured number is self-certifying (tools/tpu_window.py)
        result["health_checks"] = int(counters["health/checks"])
        result["health_failures"] = int(counters.get("health/failures", 0))


def mslr_like_data(rows: int):
    """MSLR-shaped synthetic: ragged queries (1..1251 docs, mean ~72),
    136 features, graded 0-4 relevance correlated with a feature blend.
    Query sizes come from the shared ``ops/rank.py mslr_like_sizes``
    generator, so the ROOFLINE ranking-plane numbers price exactly this
    shape."""
    from lightgbm_tpu.ops.rank import mslr_like_sizes
    rng = np.random.default_rng(0)
    qsizes = mslr_like_sizes(rows, rng=rng).tolist()
    n = sum(qsizes)
    X = rng.normal(size=(n, 136)).astype(np.float64)
    w = rng.normal(size=12)
    score = X[:, :12] @ w + rng.logistic(size=n) * 2.0
    # per-query grading to 0..4 by within-query rank quantiles
    y = np.zeros(n)
    lo = 0
    for s in qsizes:
        q = score[lo:lo + s]
        y[lo:lo + s] = np.searchsorted(
            np.quantile(q, [0.5, 0.75, 0.9, 0.97]), q)
        lo += s
    return X, y, np.asarray(qsizes, np.int64)


def _mode_params() -> dict:
    """Pipeline-mode params from the BENCH_QUANT A/B env knob — shared by
    the headline AND rank legs, so the rank bench rides the quantized
    pipeline (BENCH_QUANT=int16) instead of silently clamping to f32
    defaults."""
    params = {}
    # BENCH_QUANT=int16|int8 (or the convenience "1" -> int16): the
    # quantized-accumulation A/B leg (bench_quant) — same problem/trees
    # shape, quantization-only delta.  Unknown values ABORT rather than
    # silently pricing the wrong mode into a window record.
    quant = os.environ.get("BENCH_QUANT", "")
    if quant in ("int16", "int8"):
        params["tpu_hist_dtype"] = quant
    elif quant == "1":
        params["tpu_hist_dtype"] = "int16"
    elif quant not in ("", "0"):
        raise SystemExit(f"BENCH_QUANT must be int16, int8, 1 or 0 "
                         f"(got {quant!r})")
    return params


def _measure(params: dict, X, y, group, iters: int, metric_prefix: str):
    """Shared protocol for both benches: bin, one compile-warmup update,
    (iters-1) steady-state updates, then read the train metric.
    Returns (per_iter_s, compile_s, bin_s, metric_value, num_rows,
    mode_stamps) — mode_stamps carries the EFFECTIVE hist_mode (None
    when the run never hit the wave kernel) and fused_grad flag read
    off the trainer, so legs can stamp what actually ran."""
    import lightgbm_tpu as lgb

    import jax

    t_bin0 = time.time()
    ds = lgb.Dataset(X, label=y, group=group, params=params)
    ds.construct()
    bin_time = time.time() - t_bin0
    booster = lgb.Booster(params=params, train_set=ds)
    # train-board exporter (ISSUE 17): bench drives Booster.update()
    # directly (no engine.train), so it arms the board itself — purely
    # env-gated (LGBM_TPU_TRAIN_METRICS; tpu_window.py's headline leg
    # sets it and scrapes /metrics + /progress mid-leg).  Off by
    # default: resolve_port(None) only honors the env var.
    from lightgbm_tpu.obs import board as _board
    train_board = _board.maybe_start(None, total_rounds=iters)
    try:
        t0 = time.time()
        booster.update()
        jax.block_until_ready(booster._gbdt._train_score)
        compile_time = time.time() - t0
        t1 = time.time()
        for _ in range(iters - 1):
            booster.update()
        # sync: updates dispatch asynchronously — without this the loop
        # measures enqueue time, not compute (wildly optimistic at
        # small iters)
        jax.block_until_ready(booster._gbdt._train_score)
        per_iter = (time.time() - t1) / max(iters - 1, 1)
    finally:
        if train_board is not None:
            train_board.stop()
    mval = next((v for (_, m, v, _) in booster.eval_train()
                 if m.startswith(metric_prefix)), None)
    gbdt = booster._gbdt
    # fused_grad is stamped with its RUNTIME truth (the trainer's own
    # fused_grad_active predicate, the same one the training loop's
    # fused_now reads), matching the telemetry digest's wave_pipeline
    # section (which overrides these at embed time when a sink is
    # armed): health/fault modes force the unfused path per
    # iteration even when the fused closure is armed, and a window leg
    # under LGBM_TPU_HEALTH must not claim a fused number it didn't run
    wave = gbdt._wave_info or {}
    stamps = {
        "uses_wave": bool(gbdt.uses_wave),
        "interpret": bool(wave.get("interpret", False)),
        "hist_mode": wave.get("hist_mode"),
        "fused_grad": bool(gbdt.fused_grad_active()),
    }
    return per_iter, compile_time, bin_time, mval, len(y), stamps


def _run_rank(iters: int, leaves: int, rows: int,
              forced_cpu: bool = False) -> dict:
    X, y, q = mslr_like_data(rows)
    params = {"objective": "lambdarank", "metric": "ndcg",
              "eval_at": [10], "num_leaves": leaves, "learning_rate": 0.1,
              "max_bin": 255, "min_data_in_leaf": 50,
              "min_sum_hessian_in_leaf": 5.0, "verbose": -1}
    # the rank leg rides the SAME pipeline A/B knob as the headline
    # (BENCH_QUANT)
    params.update(_mode_params())
    per_iter, compile_time, bin_time, ndcg, n, stamps = _measure(
        params, X, y, q, iters, "ndcg")
    if not forced_cpu:
        _require_compiled_wave(stamps, "rank")
    rps = n / per_iter
    return {
        "metric": "rank_train_throughput",
        "value": round(rps, 1),
        "unit": "row_iters/s",
        "vs_baseline": round(rps / REF_RANK_ROW_ITERS_PER_SEC, 4),
        "rows": n, "queries": len(q), "iters": iters,
        "num_leaves": leaves,
        "per_iter_s": round(per_iter, 3),
        "compile_s": round(compile_time, 1),
        "binning_s": round(bin_time, 1),
        "train_ndcg10": None if ndcg is None else round(float(ndcg), 5),
        "implied_mslr_500iter_s": round(2_270_296 * 500 / rps, 1),
        # mode stamps, like the headline leg's: which histogram kernel
        # the rank trees were grown with and whether the gradient pass
        # was fused — bench_history flags a silent downgrade
        "hist_mode": stamps["hist_mode"],
        "fused_grad": stamps["fused_grad"],
    }


def higgs_like_data(rows: int, seed: int = 0):
    """HIGGS-shaped synthetic: 28 features, 8 informative plus one
    interaction, logistic noise."""
    rng = np.random.default_rng(seed)
    n_informative = 8
    X = rng.normal(size=(rows, 28)).astype(np.float32)
    w = rng.normal(size=n_informative)
    logit = X[:, :n_informative] @ w + 0.5 * X[:, 0] * X[:, 1]
    y = (logit + rng.logistic(size=rows) > 0).astype(np.float64)
    return X.astype(np.float64), y


def _load_data(rows: int):
    path = os.environ.get("HIGGS_PATH", "")
    if path and os.path.exists(path):
        data = np.loadtxt(path, delimiter=",", max_rows=rows)
        return data[:, 1:29], data[:, 0]
    return higgs_like_data(rows)


def _device_or_exit(forced_cpu: bool) -> dict:
    """The device every number in this run comes from.  No chip is a
    non-zero exit (no probe in a child, no shrink to the CPU): a
    measurement path that finds no accelerator fails."""
    import jax
    if forced_cpu:
        jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    d = devs[0]
    if not forced_cpu and d.platform != "tpu":
        raise SystemExit(
            f"bench.py needs a TPU; JAX found platform={d.platform!r} "
            f"({d.device_kind}).  BENCH_FORCE_CPU=1 runs the control-flow "
            "dry run and prints no device metric.")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _require_compiled_wave(stamps: dict, leg: str) -> None:
    if not stamps["uses_wave"] or stamps["interpret"]:
        raise SystemExit(
            f"bench.py {leg} leg did not run the compiled wave kernel "
            f"(uses_wave={stamps['uses_wave']}, "
            f"interpret={stamps['interpret']}); refusing to print a "
            "metric for another path")


def main() -> None:
    rows = int(os.environ.get("BENCH_ROWS", 1_000_000))
    iters = int(os.environ.get("BENCH_ITERS", 10))
    leaves = int(os.environ.get("BENCH_LEAVES", 255))
    max_bin = int(os.environ.get("BENCH_MAXBIN", 255))
    if iters < 2:
        raise SystemExit("BENCH_ITERS must be >= 2: the first iteration is "
                         "compile warmup and is excluded from throughput")

    forced_cpu = bool(os.environ.get("BENCH_FORCE_CPU", ""))
    device = _device_or_exit(forced_cpu)
    if forced_cpu:
        # dry-run sizes: the scatter-histogram CPU path finishes in
        # minutes and exercises the same control flow and artifacts
        rows = min(rows, int(os.environ.get("BENCH_CPU_ROWS", 200_000)))
        iters = min(iters, 3)
        leaves = min(leaves, 31)
        print(f"# BENCH_FORCE_CPU set: control-flow dry run on the CPU at "
              f"rows={rows}, iters={iters} (no device metric is printed)",
              file=sys.stderr)

    def finish(result: dict) -> None:
        result["device"] = device
        if forced_cpu:
            # the tools' artifact pipeline keeps its shape, but nothing
            # here may pass for a device number
            result["metric"] = "cpu_dry_run"
            result["backend"] = "cpu-forced"
            for k in ("vs_baseline", "rank_vs_baseline",
                      "implied_higgs_500iter_s", "implied_mslr_500iter_s"):
                result.pop(k, None)
        _embed_compile_cache(result)
        _embed_observability(result)
        print(json.dumps(result))

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    # persistent compilation cache: must precede the first jit; compile_s
    # then says (compile_cache_warm) which kind of compile it measured
    from lightgbm_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if os.environ.get("BENCH_TASK", "").lower() == "rank":
        # rank mode bounds: 255 leaves (uint8 bin kernels) and 500k rows
        # (synthetic generation time); clamping is reported, not silent
        if leaves > 255 or rows > 500_000:
            print(f"# clamping rank bench to rows<=500000, leaves<=255 "
                  f"(asked rows={rows}, leaves={leaves})", file=sys.stderr)
        finish(_run_rank(iters, min(leaves, 255), min(rows, 500_000),
                         forced_cpu))
        return
    X, y = _load_data(rows)
    params = {"objective": "binary", "metric": "auc", "num_leaves": leaves,
              "learning_rate": 0.1, "max_bin": max_bin,
              "min_data_in_leaf": 100, "verbose": -1}
    params.update(_mode_params())
    per_iter, compile_time, bin_time, auc_val, _, stamps = _measure(
        params, X, y, None, iters, "auc")
    if not forced_cpu:
        _require_compiled_wave(stamps, "headline")

    row_iters_per_sec = rows / per_iter
    result = {
        "metric": "train_throughput",
        "value": round(row_iters_per_sec, 1),
        "unit": "row_iters/s",
        "vs_baseline": round(row_iters_per_sec / REF_ROW_ITERS_PER_SEC, 4),
        "rows": rows,
        "iters": iters,
        "num_leaves": leaves,
        "max_bin": max_bin,
        "per_iter_s": round(per_iter, 3),
        "compile_s": round(compile_time, 1),
        "binning_s": round(bin_time, 1),
        "train_auc": None if auc_val is None else round(float(auc_val), 5),
        "implied_higgs_500iter_s": round(10_500_000 * 500 / row_iters_per_sec, 1),
    }
    # Rank leg: fold the MSLR north-star numbers into the same JSON line so
    # the driver's plain `python bench.py` run always captures them.
    rank_rows = int(os.environ.get("BENCH_RANK_ROWS", 200_000))
    rank_iters = max(int(os.environ.get("BENCH_RANK_ITERS", 5)), 2)
    if forced_cpu:
        rank_rows = min(rank_rows, 50_000)
        rank_iters = min(rank_iters, 3)
    if rank_rows > 0:
        if rank_rows > 500_000 or leaves > 255:
            print(f"# clamping rank leg to rows<=500000, leaves<=255 "
                  f"(asked rows={rank_rows}, leaves={leaves})",
                  file=sys.stderr)
        # a failed rank leg fails the run: no number for half a bench
        rr = _run_rank(rank_iters, min(leaves, 255),
                       min(rank_rows, 500_000), forced_cpu)
        result.update({
            "rank_row_iters_per_s": rr["value"],
            "rank_vs_baseline": rr["vs_baseline"],
            "rank_rows": rr["rows"],
            "rank_queries": rr["queries"],
            "rank_iters": rr["iters"],
            "rank_per_iter_s": rr["per_iter_s"],
            "rank_compile_s": rr["compile_s"],
            "rank_binning_s": rr["binning_s"],
            "rank_train_ndcg10": rr["train_ndcg10"],
            "rank_hist_mode": rr["hist_mode"],
            "rank_fused_grad": rr["fused_grad"],
            "implied_mslr_500iter_s": rr["implied_mslr_500iter_s"],
        })
    finish(result)


if __name__ == "__main__":
    main()
