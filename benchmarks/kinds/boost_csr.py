"""Traffic kind ``boost_csr``: ``Booster.update()`` back to back on a table
that is born sparse and trained from as a CSR.

The protocol of ``kinds/boost.py`` (its module text says why each part is as
it is): table from the seed, bins, Booster, ``WARMUP_ITERS`` warm-up
iterations, then ``scored_iters`` updates with a ``block_until_ready`` after
each; ``train_row_iters_per_s`` is rows times iterations over the time to the
last sync; ``TRACE_ITERS`` more under the profiler when traced.  Its helpers
are imported, not copied.  It differs in three places:

- the table is ``harness/datagen_onehot.py``'s CSR and is handed to
  ``lgb.Dataset`` as it is: the program bins it without densifying and packs
  its one-hot columns into shared physical columns (EFB);
- (a) the stamps are what the program says of itself in public
  (``Booster.work_counters(last=0)``: its ``stamps``, and ``bundled`` from
  the facts beside them), not private attributes.  A program without them
  ends the run at once, before any table is made;
- (b) the sample the exported model is walked over is made dense under EFB's
  conflict rule first (``harness/reference_efb.py``, with the groups the
  program names in public, ``Dataset.bundle_groups()``), and the share of
  sampled rows the rule touched has to stay under the configuration's
  ``check.conflict_share_max``;
- (c) the oracle is the serial XLA grower on the UNBUNDLED data
  (``oracle.params_set``: ``enable_bundle`` false), which runs no bundle
  encode, no histogram expansion, no default-bin reconstruction and no
  physical-column decode in its routing: a reference for all four.  On the
  slice the bin-finding sample is the whole slice, so bundling there is
  conflict-free and exact.  The ``oracle.iters`` iterations are compared
  one at a time, each grown by both growers from the SAME scores (the
  oracle's so far, handed to both as ``init_score``).  In the first tree of
  a binary job a split's gain is a function of two row counts, so two
  splits of a small node tie exactly, often; the two growers round
  differently and break the tie differently; the few hundred rows that
  moves then tilt the next tree's gradients, and where that tree's first
  splits are closely contested (a numeric column's neighbouring thresholds)
  it comes out another tree for most of the rows: 1 of 39 seeds read a
  median of 0.042 so, on honest arithmetic (CPU runs, PERF.md 6, PR 28).
  From common scores a tie moves its own node's rows and nothing after it,
  and every iteration's arithmetic is still held to the limit.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from harness import compiles, datagen_onehot, reference, reference_efb, trace
from harness.cells import scratch_dir
from harness.device import memory_parts

from .boost import (END_TO_END, ORACLE, SCORE_TOL, TRACE_ITERS,  # noqa: F401
                    WARMUP_ITERS, _env_without, _fit, _quality, _sample_rows)


def _stamps(bst) -> dict:
    """The path the trainer takes, as it says itself."""
    work = bst.work_counters(last=0)
    if "stamps" not in work or "bundled" not in work:
        sys.exit("benchmark: this program's Booster.work_counters() does "
                 "not say whether the training set is bundled; the cell "
                 "cannot tell the path it times")
    return {**work["stamps"], "bundled": work["bundled"]}


def _fit_from(params, X, y, init):
    """One iteration from the scores ``init`` (None: from the label
    average, ``boost.py``'s ``_fit``)."""
    if init is None:
        return _fit(params, X, y, None, 1)
    import jax

    import lightgbm_tpu as lgb
    ds = lgb.Dataset(X, label=y, init_score=init, params=params)
    bst = lgb.Booster(params=params, train_set=ds)
    bst.update()
    jax.block_until_ready(bst._gbdt._train_score)
    return bst


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    import lightgbm_tpu as lgb

    if not hasattr(lgb.Dataset, "bundle_groups"):
        sys.exit("benchmark: this program has no Dataset.bundle_groups(); "
                 "the reference cannot apply EFB's conflict rule to the "
                 "sample, and the cell does not run")
    cell, cfg, traffic = ctx.cell, ctx.cell.config, ctx.cell.traffic
    host = ctx.evidence["host"]
    params = {"verbose": -1, **cfg["params"]}
    spec = cfg["data"]
    task = spec["task"]

    # ---- set-up: CSR from the seed, bins and bundles, Booster, warm-up ----
    t = time.perf_counter()
    X, y, _ = datagen_onehot.make_table(spec, ctx.seed)
    n = len(y)
    host["gen_s"] = time.perf_counter() - t
    idx, _ = _sample_rows(n, None, int(cfg["check"]["sample_rows"]), ctx.seed)
    Xs, ys = X[idx], y[idx].copy()

    t = time.perf_counter()
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    host["bin_s"] = time.perf_counter() - t
    groups = ds.bundle_groups()
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

    # ---- the window (kinds/boost.py's, statement for statement) ------------
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

    # ---- (b) the exported model, walked over the sample as EFB shows it ----
    checks = {"stamps": stamps, "finite": finite,
              "compiles_in_window": in_window,
              "phys_columns": len(groups),
              "columns_in_bundles": sum(len(g) for g in groups if len(g) > 1)}
    raw_prog = np.asarray(bst._gbdt._train_score, np.float64)[idx, 0]
    trees = reference.parse_model_string(bst.model_to_string())
    Xd, touched = reference_efb.densify(Xs.indptr, Xs.indices, Xs.data,
                                        Xs.shape, groups)
    raw_ref = reference.predict_raw(trees, Xd)
    del Xd
    err = float(np.max(np.abs(raw_ref - raw_prog)
                       / (1.0 + np.abs(raw_ref))))
    conflict_share = touched / len(idx)
    conflict_max = float(cfg["check"]["conflict_share_max"])
    qname, qval, _ = _quality(task, ys, raw_prog, None)
    floor = float(cell.expect.get(qname, {}).get("min", 0.0))
    checks["export"] = {"rows": int(len(idx)), "trees": len(trees),
                        "max_rel_err": err, "tol": SCORE_TOL,
                        "conflict_share": conflict_share,
                        "conflict_share_max": conflict_max,
                        qname: qval, "floor": floor}
    ok = (finite and err <= SCORE_TOL and qval >= floor
          and conflict_share < conflict_max
          and len(trees) == done + WARMUP_ITERS
          + (TRACE_ITERS if ctx.trace else 0))

    # ---- (c) the same path against the serial grower, unbundled ------------
    ora = cfg["oracle"]
    del bst, ds
    Xo, yo, _ = datagen_onehot.make_table(spec, ctx.seed,
                                          rows=int(ora["slice_rows"]))
    iters = int(ora["iters"])
    oparams = {k: v for k, v in params.items()
               if k not in ora.get("params_drop", [])}
    oparams.update(ora.get("params_set", {}))
    oparams.update(ORACLE)
    init, stages = None, []
    for _ in range(iters):
        fast = _fit_from(params, Xo, yo, init)
        with _env_without("LGBM_TPU_FORCE_WAVE"):
            slow = _fit_from(oparams, Xo, yo, init)
        fast_stamps, slow_stamps = _stamps(fast), _stamps(slow)
        raw = {"path": fast._raw_train_score(),
               "oracle": slow._raw_train_score()}
        root = {k: reference.root_split(reference.parse_model_string(
            b.model_to_string())[0]) for k, b in (("path", fast),
                                                  ("oracle", slow))}
        stages.append({
            "same_root": root["path"] == root["oracle"],
            "root": root["path"],
            "score_med": float(np.median(np.abs(raw["path"] - raw["oracle"]))
                               / np.std(raw["oracle"])),
            "loss_path": _quality(task, yo, raw["path"], None)[2],
            "loss_oracle": _quality(task, yo, raw["oracle"], None)[2],
            "path_on_path": {k: fast_stamps[k] for k in want} == want,
            "oracle_uses_wave": slow_stamps["uses_wave"],
            "oracle_bundled": slow_stamps["bundled"]})
        init = raw["oracle"]
    score_med = max(st["score_med"] for st in stages)
    loss_ratio = max(st["loss_path"] / st["loss_oracle"] for st in stages)
    same_root = all(st["same_root"] for st in stages)
    off_path = any(st["oracle_uses_wave"] or st["oracle_bundled"]
                   or not st["path_on_path"] for st in stages)
    checks["oracle"] = {"rows": int(len(yo)), "iters": iters,
                        "same_root": same_root, "root": stages[0]["root"],
                        "score_med": score_med,
                        "score_med_max": float(ora["score_med_max"]),
                        "loss_path": stages[-1]["loss_path"],
                        "loss_oracle": stages[-1]["loss_oracle"],
                        "loss_ratio": loss_ratio,
                        "loss_ratio_max": float(ora["loss_ratio_max"]),
                        "oracle_uses_wave": stages[-1]["oracle_uses_wave"],
                        "oracle_bundled": stages[-1]["oracle_bundled"],
                        "stages": stages}
    ok = (ok and same_root and not off_path
          and score_med <= float(ora["score_med_max"])
          and loss_ratio <= float(ora["loss_ratio_max"]))
    ctx.evidence["checks"] = checks
    return {"correct": bool(ok and failed == 0), "attempted": attempted,
            "failed": failed,
            "end_to_end": {"train_row_iters_per_s": rate}}
