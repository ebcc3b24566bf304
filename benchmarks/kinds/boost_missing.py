"""Traffic kind ``boost_missing``: ``Booster.update()`` back to back on a
dense table whose every column has missing values.

The protocol of ``kinds/boost_staged.py`` (and through it ``kinds/boost.py``,
whose module text says why each part is as it is): table from the seed, bins,
Booster, the traffic file's ``warmup_iters`` warm-up iterations, then
``scored_iters`` updates with a ``block_until_ready`` after each;
``train_row_iters_per_s`` is rows times iterations over the time to the last
sync; ``TRACE_ITERS`` more under the profiler when traced.  Its helpers are
imported, not copied.  It differs in these places:

- the table is ``harness/datagen_missing.py``'s: float32 with a NaN wherever
  a row did not pass a column's station;
- the run ends at once, before the table is made, where ``work_counters()``
  does not read ``missing_splits`` and ``scan_bins`` off its trees
  (``boosting/gbdt.py TREE_COUNTS`` without them), or the program has no
  facts of the kernel to say (``boost_staged._has_facts``);
- (a) the stamps and facts are ``boost_staged``'s, and the training set is
  not bundled (``work_counters()["bundled"]``, the configuration's
  ``bundled``): a NaN is a non-default bin, so no two columns are exclusive;
- (b) the exported model is walked by ``harness/reference_missing.py``, which
  honours ``decision_type``, over a seeded sample whose entries are at least
  ``check.nan_share_min`` NaN, to ``check.export_tol``; no leaf below
  ``min_sum_hessian_in_leaf`` (to ``check.leaf_weight_rtol``);
- (c) is ``boost_staged``'s staged oracle (its module text) on this table's
  slice, with two changes.  The two growers' first splits are compared as
  the rows they send left: a column's NaN-against-the-rest split is reached
  by both scans at one gain, as "NaN and the empty bin under zero go left"
  and as "every value goes left, NaN right", and which of the two mirror
  images comes out is rounding's choice, which the two growers make apart.
  And the path's launches have to reach more than one MXU pass on the slice
  only where a timed tree's did: under the hessian bound of this job a tree
  has some 68 leaves, and its launches may all fit one pass;
- (d) new: on the same slice and parameters, one tree grown by the
  configuration's path and by ``reference_missing.grow_tree`` (NumPy,
  float64) from the same scores, held node by node by
  ``reference_missing.compare_trees`` to the configuration's ``grower``
  limits: the same root, the same split wherever the NumPy grower's winner
  was not within rounding of another candidate (or of the hessian bound),
  and the leaf values.  The scores are the base rate's log-odds plus a
  seeded normal (``grower.init_noise``): from equal scores, every split with
  the same positives and negatives a side ties exactly;
- (e) new: ``missing_splits`` equals ``walks`` in every timed tree, since
  every column's missing type is NaN: a fact of the table, which the program
  reads off the trees it grew;
- (f) new, the timed trainer at its own size: after the windows (and (b))
  the trainer's scores are read, one more ``update()`` runs, and
  ``reference_missing.judge`` holds that tree on all the table's rows, binned
  by ``reference_missing.bin_table``, to the configuration's ``judge``
  limits: its first ``judge.nodes`` nodes breadth-first each the best cut of
  the rows it is brought (or within rounding of it), with the ``split_gain``
  of its own cut, and every leaf's rows and value.  After twelve rounds no
  two rows' scores are alike by construction, so the trees of the timed
  trainer are judged as grown, where (d) needs its seeded scores.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from harness import compiles, datagen_missing, reference_missing, trace
from harness.cells import scratch_dir
from harness.device import memory_parts

from .boost import (END_TO_END, ORACLE, TRACE_ITERS,  # noqa: F401
                    _env_without, _quality, _sample_rows)
from .boost_csr import _fit_from
from .boost_staged import _has_facts, _leaf_weights, _said

COUNTERS = ("missing_splits", "scan_bins")


def _has_counters() -> None:
    """End the run before the table is made where the program does not read
    off its trees what this cell reads."""
    from lightgbm_tpu.boosting import gbdt
    lacks = [k for k in COUNTERS if k not in getattr(gbdt, "TREE_COUNTS", ())]
    if lacks:
        sys.exit(f"benchmark: this program's work counters do not read "
                 f"{lacks}; the cell cannot tell its missing-value splits, "
                 f"and does not run")


def _tree(bst) -> dict:
    """A one-tree Booster's tree, as the plain reference parses it."""
    return reference_missing.parse_model_string(bst.model_to_string())[0]


def _same_root(a: dict, b: dict, X: np.ndarray) -> bool:
    """The two trees' first splits send the same rows of ``X`` left, or each
    other's (module text, (c))."""
    if a["num_leaves"] <= 1 or b["num_leaves"] <= 1:
        return a["num_leaves"] == b["num_leaves"]
    left = [reference_missing.goes_left(X[:, t["split_feature"][0]],
                                        t["threshold"][0],
                                        t["decision_type"][0]) for t in (a, b)]
    return bool(np.array_equal(*left) or np.array_equal(left[0], ~left[1]))


def staged(cfg: dict, params: dict, want: dict, seed: int,
           need_multi_pass: bool) -> dict:
    """Check (c): ``boost_staged.staged``'s stages, the roots compared as
    partitions of the slice's rows, more than one MXU pass asked for where
    ``need_multi_pass`` (module text)."""
    ora, task = cfg["oracle"], cfg["data"]["task"]
    facts = [k for k in want if k in cfg["facts"]]
    Xo, yo, _ = datagen_missing.make_table(cfg["data"], seed,
                                           rows=int(ora["slice_rows"]))
    sparams = {**params, **ora.get("params", {})}
    oparams = {**sparams, **ORACLE}
    cap = int(sparams["num_leaves"])
    init, stages = None, []
    for _ in range(int(ora["iters"])):
        fast = _fit_from(sparams, Xo, yo, init)
        with _env_without("LGBM_TPU_FORCE_WAVE"):
            slow = _fit_from(oparams, Xo, yo, init)
        fast_said = _said(fast, facts)
        grown, = fast.work_counters(last=1)["trees"]
        rows_hist, rows_passes = (max(grown[k]) for k in
                                  ("kernel_rows", "kernel_pass_rows"))
        raw = {"path": fast._raw_train_score(),
               "oracle": slow._raw_train_score()}
        tree = {"path": _tree(fast), "oracle": _tree(slow)}
        stages.append({
            "same_root": _same_root(tree["path"], tree["oracle"], Xo),
            "root": (int(tree["path"]["split_feature"][0]),
                     float(tree["path"]["threshold"][0]),
                     int(tree["path"]["decision_type"][0]))
            if tree["path"]["num_leaves"] > 1 else None,
            "leaves": {k: t["num_leaves"] for k, t in tree.items()},
            "score_med": float(np.median(np.abs(raw["path"] - raw["oracle"]))
                               / np.std(raw["oracle"])),
            "loss_path": _quality(task, yo, raw["path"], None)[2],
            "loss_oracle": _quality(task, yo, raw["oracle"], None)[2],
            "launches": grown["waves"], "pending_leaves": grown["lanes"],
            "kernel_rows": rows_hist, "kernel_pass_rows": rows_passes,
            "multi_pass": rows_passes > rows_hist,
            "path_on_path": {k: fast_said[k] for k in want} == want,
            "oracle_uses_wave": _said(slow, ())["uses_wave"]})
        init = raw["oracle"]
    score_med = max(st["score_med"] for st in stages)
    loss_ratio = max(st["loss_path"] / st["loss_oracle"] for st in stages)
    same_root = all(st["same_root"] for st in stages)
    off_path = any(st["oracle_uses_wave"] or not st["path_on_path"]
                   for st in stages)
    cap_binds = any(v >= cap for st in stages for v in st["leaves"].values())
    multi_pass = all(st["multi_pass"] for st in stages)
    return {"rows": int(len(yo)), "iters": len(stages),
            "same_root": same_root, "root": stages[0]["root"],
            "score_med": score_med,
            "score_med_max": float(ora["score_med_max"]),
            "loss_ratio": loss_ratio,
            "loss_ratio_max": float(ora["loss_ratio_max"]),
            "leaf_cap": cap, "leaf_cap_binds": cap_binds,
            "multi_pass": multi_pass, "need_multi_pass": need_multi_pass,
            "stages": stages,
            "ok": bool(same_root and not off_path and not cap_binds
                       and (multi_pass or not need_multi_pass)
                       and score_med <= float(ora["score_med_max"])
                       and loss_ratio <= float(ora["loss_ratio_max"]))}


def grower_check(cfg: dict, params: dict, want: dict, seed: int) -> dict:
    """Check (d) (module text): one tree by the path and by the NumPy grower
    on the oracle's slice, from the same seeded scores."""
    ora, lim = cfg["oracle"], cfg["grower"]
    Xo, yo, _ = datagen_missing.make_table(cfg["data"], seed,
                                           rows=int(ora["slice_rows"]))
    sparams = {**params, **ora.get("params", {})}
    rng = np.random.default_rng([int(seed), 31])
    base = float(np.log(yo.mean() / (1.0 - yo.mean())))
    init = base + float(lim["init_noise"]) * rng.standard_normal(len(yo))
    fast = _fit_from(sparams, Xo, yo, init)
    said = _said(fast, [k for k in want if k in cfg["facts"]])
    path = _tree(fast)
    del fast
    t = time.perf_counter()
    bins, num_bins = reference_missing.bin_columns(
        Xo, int(sparams["max_bin"]))
    p = 1.0 / (1.0 + np.exp(-init))
    grown = reference_missing.grow_tree(
        bins, num_bins, np.isnan(Xo).any(axis=0), p - yo, p * (1.0 - p),
        sparams, tie_rtol=float(lim["tie_rtol"]),
        hess_rtol=float(lim["hess_rtol"]))
    res = reference_missing.compare_trees(
        grown, path, Xo, leaf_rtol=float(lim["leaf_rtol"]),
        shrinkage=float(sparams["learning_rate"]))
    leaves = {"path": int(path["num_leaves"]),
              "reference": len(grown["leaf_value"])}
    cap = int(sparams["num_leaves"])
    return {**res, "rows": int(len(yo)), "grow_s": time.perf_counter() - t,
            "leaves": leaves, "leaf_cap": cap,
            "ok": bool(res["ok"] and max(leaves.values()) < cap),
            "fragile": sum(nd["fragile"] for nd in grown["nodes"]),
            "differ": res["differ"][:5], "leaf_fail": res["leaf_fail"][:5],
            "leaf_rtol": float(lim["leaf_rtol"]),
            "path_on_path": {k: said[k] for k in want} == want}


def judge_check(cfg: dict, params: dict, tb: dict, y: np.ndarray,
                score: np.ndarray, tree: dict) -> dict:
    """Check (f) (module text): ``tree``, grown by the timed trainer from
    ``score``, judged on every row of the table ``tb`` holds binned."""
    lim = cfg["judge"]
    t = time.perf_counter()
    p = 1.0 / (1.0 + np.exp(-np.asarray(score, np.float64)))
    res = reference_missing.judge(
        tree, tb, p - y, p * (1.0 - p), params, nodes=lim["nodes"],
        shrinkage=float(params["learning_rate"]),
        tie_rtol=float(lim["tie_rtol"]), hess_rtol=float(lim["hess_rtol"]),
        gain_rtol=float(lim["gain_rtol"]), leaf_rtol=float(lim["leaf_rtol"]),
        tie_share_max=float(lim["tie_share_max"]))
    return {**res, "rows": int(len(y)), "judge_s": time.perf_counter() - t}


def missing_check(timed: list) -> dict:
    """Check (e): every timed tree split, and every split of it has a
    default side (``missing_splits == walks``)."""
    return {"timed": timed,
            "ok": bool(timed) and all(t["missing_splits"] == t["walks"] > 0
                                      for t in timed)}


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    import lightgbm_tpu as lgb

    cell, cfg, traffic = ctx.cell, ctx.cell.config, ctx.cell.traffic
    host = ctx.evidence["host"]
    params = {"verbose": -1, **cfg["params"]}
    spec, chk, facts = cfg["data"], cfg["check"], dict(cfg["facts"])
    task = spec["task"]
    want = {**cfg["stamps"], **facts}
    if cell.rehearsal:
        want["interpret"] = True
    _has_facts(facts)
    _has_counters()

    # ---- set-up: table from the seed, bins, Booster, warm-up --------------
    t = time.perf_counter()
    X, y, _ = datagen_missing.make_table(spec, ctx.seed)
    n = len(y)
    host["gen_s"] = time.perf_counter() - t
    idx, _ = _sample_rows(n, None, int(chk["sample_rows"]), ctx.seed)
    Xs, ys = X[idx].copy(), y[idx].copy()

    t = time.perf_counter()
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    host["bin_s"] = time.perf_counter() - t

    t = time.perf_counter()
    bst = lgb.Booster(params=params, train_set=ds)
    host["init_s"] = time.perf_counter() - t

    def sync():
        jax.block_until_ready(bst._gbdt._train_score)

    said = _said(bst, facts)
    got = {k: said[k] for k in want}
    bundled = bool(bst.work_counters(last=0).get("bundled"))
    if got != want or bundled != bool(cfg["bundled"]):
        sys.exit(f"benchmark: the trainer left the configuration's path: "
                 f"{got}, bundled {bundled} != {want}, bundled "
                 f"{cfg['bundled']}")

    warmup = int(traffic["warmup_iters"])
    t = time.perf_counter()
    bst.update()
    sync()
    host["first_call_s"] = time.perf_counter() - t
    for _ in range(warmup - 1):
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
    # (e) the timed trees' own counts, fetched now that the clock is off
    timed = [{"multi_pass": max(t["kernel_pass_rows"]) > max(t["kernel_rows"]),
              **{k: t[k] for k in ("iteration", "walks", *COUNTERS)}}
             for t in bst.work_counters(last=done)["trees"]] if done else []

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

    # ---- (b) the exported model: the walk, and the stopping rule -----------
    checks = {"stamps": {k: v for k, v in said.items() if k not in facts},
              "facts": {k: said[k] for k in facts}, "bundled": bundled,
              "finite": finite, "compiles_in_window": in_window}
    raw_prog = bst._raw_train_score()[idx]
    text = bst.model_to_string()
    trees = reference_missing.parse_model_string(text)
    raw_ref = reference_missing.predict_raw(trees, Xs)
    err = float(np.max(np.abs(raw_ref - raw_prog)
                       / (1.0 + np.abs(raw_ref))))
    tol = float(chk["export_tol"])
    nan_share = float(np.isnan(Xs).mean())
    qname, qval, _ = _quality(task, ys, raw_prog, None)
    floor = float(cell.expect.get(qname, {}).get("min", 0.0))
    weights = _leaf_weights(text)
    hess_min = float(params["min_sum_hessian_in_leaf"])
    hess_floor = hess_min * (1.0 - float(chk["leaf_weight_rtol"]))
    checks["export"] = {"rows": int(len(idx)), "trees": len(trees),
                        "nan_share": nan_share,
                        "nan_share_min": float(chk["nan_share_min"]),
                        "max_rel_err": err, "tol": tol,
                        qname: qval, "floor": floor,
                        "leaves": [t["num_leaves"] for t in trees],
                        "min_leaf_weight": float(weights.min()),
                        "leaf_weight_floor": hess_floor}
    ok = (finite and err <= tol and qval >= floor
          and nan_share >= float(chk["nan_share_min"])
          and len(weights) == sum(t["num_leaves"] for t in trees)
          and float(weights.min()) >= hess_floor
          and len(trees) == done + warmup + (TRACE_ITERS if ctx.trace else 0))

    # ---- (e) every timed split has a default side ---------------------------
    checks["missing_splits"] = missing_check(timed)
    ok = ok and checks["missing_splits"]["ok"]

    # ---- (f) one more tree of the timed trainer, judged on every row --------
    score = np.asarray(bst._raw_train_score(), np.float64)
    bst.update()
    sync()
    tree = reference_missing.parse_model_string(bst.model_to_string())[-1]
    del bst, ds
    t = time.perf_counter()
    tb = reference_missing.bin_table(X, int(params["max_bin"]))
    del X
    bin_s = time.perf_counter() - t
    checks["judge"] = {**judge_check(cfg, params, tb, y, score, tree),
                       "bin_s": bin_s}
    del tb
    ok = ok and checks["judge"]["ok"]

    # ---- (c) the serial grower, staged; (d) the NumPy grower ---------------
    checks["oracle"] = staged(cfg, params, want, ctx.seed,
                              any(t["multi_pass"] for t in timed))
    checks["grower"] = grower_check(cfg, params, want, ctx.seed)
    ok = (ok and checks["oracle"]["ok"] and checks["grower"]["ok"]
          and checks["grower"]["path_on_path"])
    ctx.evidence["checks"] = checks
    return {"correct": bool(ok and failed == 0), "attempted": attempted,
            "failed": failed,
            "end_to_end": {"train_row_iters_per_s": rate}}
