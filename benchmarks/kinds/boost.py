"""Traffic kind ``boost``: ``Booster.update()`` back to back.

The loop a user's ``train(params, ds, num_boost_round=...)`` runs, without a
valid set or an eval callback: make the table from the seed, bin it, build the
Booster, warm up, then update ``scored_iters`` times (the traffic file's key),
or until ``--seconds`` are over where they take longer.  The clock reads after
a ``block_until_ready`` on the train score after every iteration.
``train_row_iters_per_s`` is rows times iterations over the time from the
window's start to the last sync.  A fixed count, because an iteration's cost
drifts up with the boosting round: a window that ran to ``--seconds`` would
charge a faster change for the later rounds it reaches (PERF.md 2).

Correctness, outside the window (ISSUE 22):
 (a) the path stamps read off the trainer are the configuration's, else the
     run ends non-zero with no result;
 (b) the benchmark's own walk of the exported model over a seeded sample
     reproduces the trainer's raw scores, and its own AUC / NDCG@10 of them
     clears the cell's floor;
 (c) on a slice, a few iterations of the same path and of the repository's
     plain oracle (``device_type=cpu``: the serial XLA grower, float32
     scatter histograms) agree: same root split; the median over the rows
     of the difference in raw score, over the standard deviation of the
     oracle's scores, at most ``score_med_max``; loss within
     ``loss_ratio_max``.  The slice is small enough that the leaf cap never
     binds (``slice_rows / min_data_in_leaf < num_leaves``): the wave path
     is not strict best-first (``tpu_wave_gain_gate``), and under a binding
     cap it grows another tree than the serial grower, by design.  The
     median, because a near-tie between two splits falls either way and
     moves every row under that node: it moves the mean and the maximum a
     thousandfold and the median not at all, while a coarser histogram (one
     bf16 pass) moves every leaf value, and the median with them (PERF.md 2
     has the figures).
"""
from __future__ import annotations

import os
import sys
import time
from contextlib import contextmanager

import numpy as np

from harness import compiles, datagen, reference, trace
from harness.cells import scratch_dir
from harness.device import memory_parts

END_TO_END = {"train_row_iters_per_s": "row_iters/s"}
WARMUP_ITERS = 2    # the first call loads or compiles, the second runs warm
TRACE_ITERS = 2     # iterations of the traced window
SCORE_TOL = 1e-5    # (b): the model walk against the trainer's scores
ORACLE = {"device_type": "cpu"}     # (c): the program's serial XLA grower


@contextmanager
def _env_without(name: str):
    """The trainer reads ``LGBM_TPU_FORCE_WAVE`` when a Booster is built; a
    rehearsal sets it, and the oracle must be built without it."""
    prev = os.environ.pop(name, None)
    try:
        yield
    finally:
        if prev is not None:
            os.environ[name] = prev


def _stamps(bst) -> dict:
    g = bst._gbdt
    info = g._wave_info or {}
    bins = g._grow_bins
    return {"uses_wave": bool(g.uses_wave),
            "interpret": bool(info.get("interpret", False)),
            "hist_mode": info.get("hist_mode"),
            "packed": info.get("packed"),
            "fused_sibling": info.get("fused_sibling"),
            "fused_grad": bool(g.fused_grad_active()),
            "bins_devices": (len(bins.sharding.device_set)
                             if hasattr(bins, "sharding") else 1)}


def _fit(params, X, y, sizes, iters):
    import jax

    import lightgbm_tpu as lgb
    ds = lgb.Dataset(X, label=y, group=sizes, params=params)
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(iters):
        bst.update()
    jax.block_until_ready(bst._gbdt._train_score)
    return bst


def _quality(task: str, y, raw, sizes):
    """(name, value, loss-like value where lower is better)."""
    if task == "rank":
        v = reference.ndcg_at_k(y, raw, sizes, 10)
        return "ndcg@10", v, 1.0 - v
    return "auc", reference.auc(y, raw), reference.logloss(y, raw)


def _sample_rows(n: int, sizes, cap: int, seed: int) -> tuple:
    """Row indices of a seeded sample of at most ``cap`` rows (whole queries
    where there are queries), ascending, and the sample's query sizes."""
    rng = np.random.default_rng([int(seed), 11])
    if sizes is None:
        idx = np.sort(rng.choice(n, size=min(cap, n), replace=False))
        return idx, None
    order = rng.permutation(len(sizes))
    take = order[:max(int(np.searchsorted(np.cumsum(sizes[order]), cap,
                                          "right")), 1)]
    take.sort()
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    idx = np.concatenate([np.arange(starts[q], starts[q] + sizes[q])
                          for q in take])
    return idx, sizes[take]


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    import lightgbm_tpu as lgb

    cell, cfg, traffic = ctx.cell, ctx.cell.config, ctx.cell.traffic
    host = ctx.evidence["host"]
    params = {"verbose": -1, **cfg["params"]}
    spec = cfg["data"]
    task = spec["task"]

    # ---- set-up: table from the seed, bins, Booster, warm-up --------------
    t = time.perf_counter()
    X, y, sizes = datagen.make_table(spec, ctx.seed)
    n = len(y)
    host["gen_s"] = time.perf_counter() - t
    idx, sizes_s = _sample_rows(n, sizes, int(cfg["check"]["sample_rows"]),
                                ctx.seed)
    Xs, ys = X[idx].copy(), y[idx].copy()

    t = time.perf_counter()
    ds = lgb.Dataset(X, label=y, group=sizes, params=params)
    ds.construct()
    host["bin_s"] = time.perf_counter() - t
    del X

    t = time.perf_counter()
    bst = lgb.Booster(params=params, train_set=ds)
    host["init_s"] = time.perf_counter() - t

    def sync():
        jax.block_until_ready(bst._gbdt._train_score)

    stamps = _stamps(bst)
    want = dict(cfg["stamps"])
    if cell.rehearsal:
        want["interpret"] = True
    got = {k: stamps[k] for k in want}
    if got != want:
        sys.exit(f"benchmark: the trainer left the configuration's path: "
                 f"{got} != {want}")

    t = time.perf_counter()
    bst.update()
    sync()
    host["first_call_s"] = time.perf_counter() - t
    for _ in range(WARMUP_ITERS - 1):
        bst.update()
    sync()
    host["warmup_s"] = time.perf_counter() - t

    # ---- the window --------------------------------------------------------
    scored = int(traffic["scored_iters"])
    comp0 = compiles.snapshot()
    ctx.window_starts()
    t0 = time.perf_counter()
    ends = []                   # seconds from t0 to the sync after each
    attempted = failed = 0
    while attempted < scored and time.perf_counter() - t0 < ctx.seconds:
        attempted += 1
        try:
            with jax.profiler.TraceAnnotation("bench/update"):
                stopped = bst.update()
            with jax.profiler.TraceAnnotation("bench/sync"):
                sync()
        except Exception as exc:  # noqa: BLE001 — counted, then reported
            print(f"benchmark: iteration {attempted} raised "
                  f"{type(exc).__name__}: {exc}", file=sys.stderr)
            failed += 1
            break
        if stopped:
            print("benchmark: the trainer found nothing left to split",
                  file=sys.stderr)
            failed += 1
            break
        ends.append(time.perf_counter() - t0)
    done = len(ends)
    comp1 = compiles.snapshot()
    in_window = comp1["programs"] - comp0["programs"]
    rate = n * done / ends[-1] if done else 0.0
    host["iter_s"] = [b - a for a, b in zip([0.0] + ends, ends)]
    host["window_s"] = ends[-1] if done else 0.0
    ctx.evidence["counters"].update(
        compiles_in_window=in_window, iterations=done,
        cache_hits=comp1["cache_hits"], cache_misses=comp1["cache_misses"],
        programs=comp1["programs"])
    ctx.evidence["memory"].update(memory_parts(ctx.devices))

    finite = bool(jnp.isfinite(bst._gbdt._train_score).all())
    if not finite:
        failed = attempted
    failed = min(attempted, failed + in_window)

    # ---- the traced window: a few more iterations --------------------------
    if ctx.trace:
        tdir = scratch_dir("trace", cell.name)
        with trace.capture(tdir):
            with jax.profiler.TraceAnnotation("bench/traced_window"):
                for _ in range(TRACE_ITERS):
                    with jax.profiler.TraceAnnotation("bench/update"):
                        bst.update()
                    with jax.profiler.TraceAnnotation("bench/sync"):
                        sync()
        ctx.evidence["trace"] = trace.parse_dir(tdir)
        ctx.evidence["trace_steps"] = TRACE_ITERS
        ctx.collect({"booster": bst})

    # ---- (b) the exported model against the trainer's own scores -----------
    checks = {"stamps": stamps, "finite": finite,
              "compiles_in_window": in_window}
    raw_prog = np.asarray(bst._gbdt._train_score, np.float64)[idx, 0]
    trees = reference.parse_model_string(bst.model_to_string())
    raw_ref = reference.predict_raw(trees, Xs)
    err = float(np.max(np.abs(raw_ref - raw_prog)
                       / (1.0 + np.abs(raw_ref))))
    qname, qval, _ = _quality(task, ys, raw_prog, sizes_s)
    floor = float(cell.expect.get(qname, {}).get("min", 0.0))
    checks["export"] = {"rows": int(len(idx)), "trees": len(trees),
                        "max_rel_err": err, "tol": SCORE_TOL,
                        qname: qval, "floor": floor}
    ok = (finite and err <= SCORE_TOL and qval >= floor
          and len(trees) == done + WARMUP_ITERS
          + (TRACE_ITERS if ctx.trace else 0))

    # ---- (c) the same path against the repository's plain oracle -----------
    ora = cfg["oracle"]
    del bst, ds
    Xo, yo, so = datagen.make_table(spec, ctx.seed, rows=int(ora["slice_rows"]))
    iters = int(ora["iters"])
    fast = _fit(params, Xo, yo, so, iters)
    fast_stamps = _stamps(fast)
    oparams = {k: v for k, v in params.items()
               if k not in ora.get("params_drop", [])}
    oparams.update(ORACLE)
    with _env_without("LGBM_TPU_FORCE_WAVE"):
        slow = _fit(oparams, Xo, yo, so, iters)
    res = {}
    for name, b in (("path", fast), ("oracle", slow)):
        raw = b._raw_train_score()
        tr = reference.parse_model_string(b.model_to_string())
        res[name] = {"root": reference.root_split(tr[0]), "raw": raw,
                     "loss": _quality(task, yo, raw, so)[2]}
    score_med = float(
        np.median(np.abs(res["path"]["raw"] - res["oracle"]["raw"]))
        / np.std(res["oracle"]["raw"]))
    same_root = res["path"]["root"] == res["oracle"]["root"]
    checks["oracle"] = {"rows": int(len(yo)), "iters": iters,
                        "same_root": same_root, "root": res["path"]["root"],
                        "score_med": score_med,
                        "score_med_max": float(ora["score_med_max"]),
                        "loss_path": res["path"]["loss"],
                        "loss_oracle": res["oracle"]["loss"],
                        "loss_ratio_max": float(ora["loss_ratio_max"]),
                        "oracle_uses_wave": bool(slow._gbdt.uses_wave)}
    ok = (ok and same_root and not slow._gbdt.uses_wave
          and {k: fast_stamps[k] for k in want} == want
          and score_med <= float(ora["score_med_max"])
          and res["path"]["loss"]
          <= float(ora["loss_ratio_max"]) * res["oracle"]["loss"])
    ctx.evidence["checks"] = checks
    return {"correct": bool(ok and failed == 0), "attempted": attempted,
            "failed": failed,
            "end_to_end": {"train_row_iters_per_s": rate}}
