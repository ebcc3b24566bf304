"""Traffic kind ``boost_cat``: ``Booster.update()`` back to back on a dense
table whose categorical columns are integer codes DECLARED to the trainer.

The protocol of ``kinds/boost.py`` (its module text says why each part is as
it is): table from the seed, bins, Booster, ``WARMUP_ITERS`` warm-up
iterations, then ``scored_iters`` updates with a ``block_until_ready`` after
each; ``train_row_iters_per_s`` is rows times iterations over the time to the
last sync; ``TRACE_ITERS`` more under the profiler when traced.  Its helpers,
and ``kinds/boost_csr.py``'s staged fit, are imported, not copied.  It
differs in these places:

- the table is ``harness/datagen_codes.py``'s: the rows, codes, numeric
  columns and labels of the one-hot configuration on the same data group, as
  ``len(variables) + numeric`` dense float32 columns.  The code columns are
  declared by the configuration's own ``params.categorical_feature`` (the
  parameter a user sets; ``lgb.Dataset(X, label=y, params=params)`` reads
  it), and the program bins them by count, searches category sets (Fisher's
  sorted scan) and routes rows by bitsets;
- (a) the stamps and the facts (``bundled``, ``categorical_features``,
  ``wide_columns``) are what the program says of itself in public
  (``Booster.work_counters(last=0)``).  A program without the counter
  ``cat_splits`` (it came with ``Dataset.categorical_bins()``) ends the run
  at once, before any table is made.  Every timed iteration's own
  ``cat_splits`` has to reach ``check.cat_splits_min_per_iter``: a run in
  which numeric splits won everything does not time the mechanism;
- (b) the exported model is walked by ``harness/reference_cat.py`` (numeric
  and categorical nodes) over a seeded sample of the RAW codes, against the
  trainer's own scores; the sampled rows that hold a value some column's bin
  map dropped are counted and have to be there (the trainer scores them in
  bin space, the model in value space);
- (c1) the search at the timed size: after the windows the trainer's scores
  are read, one more ``update()`` runs, and ``reference_cat.judge`` holds
  that tree's first ``check.judged_nodes`` categorical nodes (breadth-first)
  to the reference's search on float64 gradients of those scores over ALL the
  rows: every node's exported gain within ``check.gain_rtol`` of its set's
  gain on the reference's sums and the median of those errors within
  ``check.gain_med_rtol``, taken over the nodes of columns of at most
  ``check.med_max_bins`` bins (the kernel's: a wider column's histogram is
  the float32 side-pass's whatever the kernel's precision), of which there
  have to be ``check.med_nodes_min``; the left set the reference's or, for
  at most ``check.tie_share_max`` of the nodes, a tie with it (by gain, or
  by the order of ratios that agree to the tolerance);
- the bin map that (c1) pools dropped values by is the program's own
  (``Dataset.categorical_bins()``), so before (c1) uses it
  ``reference_cat.bin_map_problems`` holds each declared column's map to the
  rows the bins were found from: count-ordered, cut at 99% / ``max_bin``.
  The rows are drawn as the program draws them (``utils/random.py Random`` at
  ``data_random_seed``, ``bin_construct_sample_cnt`` of them, the documented
  defaults 1 and 200,000 unless the configuration says otherwise): any
  sample is a legitimate one, the map made from it is what is held;
- traced, ``reducers/fullpass.py`` launches the kernel as the trainer does,
  from the trainer's ``_grow_bins`` and ``B_phys``.  On the mixed-width plan
  those are a pair (narrow, wide) and the wide columns' width, and the
  kernel the trainer launches runs over the narrow array at
  ``plan.mixed.B_narrow`` (``core/wave_grower.py _wave_hist``), so the
  reader is handed ``_kernel_view``: that array and that width under the
  names it reads, everything else the trainer's.  The launch it times is
  then the trainer's own variant, over the columns the kernel holds;
- (c2) the staged oracle of ``kinds/boost_csr.py``, on the slice: each of
  ``oracle.iters`` iterations grown by the configuration's path and by the
  serial XLA grower (``device_type=cpu``) from the same scores; same root
  (feature, and threshold or category set), ``score_med_max``,
  ``loss_ratio_max``.  The root is read by ``reference_cat`` (the other
  walk refuses a categorical node).
"""
from __future__ import annotations

import sys
import time
import types

import numpy as np

from harness import compiles, datagen_codes, reference_cat, trace
from harness.cells import scratch_dir
from harness.device import memory_parts

from .boost import (END_TO_END, ORACLE, SCORE_TOL, TRACE_ITERS,  # noqa: F401
                    WARMUP_ITERS, _env_without, _quality, _sample_rows)
from .boost_csr import _fit_from

FACTS = ("bundled", "categorical_features", "wide_columns")
# docs/Parameters.rst, v2.3.2: how many rows the bins are found from, and
# the seed of their draw
BIN_SAMPLE_DEFAULTS = {"bin_construct_sample_cnt": 200000,
                       "data_random_seed": 1}


def _stamps(bst) -> dict:
    """The path the trainer takes and what it says of the columns."""
    work = bst.work_counters(last=0)
    if "stamps" not in work or any(k not in work for k in FACTS):
        sys.exit("benchmark: this program's Booster.work_counters() does "
                 "not say how many columns are declared categorical; the "
                 "cell cannot tell the path it times")
    return {**work["stamps"], **{k: work[k] for k in FACTS}}


def _kernel_view(bst):
    """``bst`` as ``reducers/fullpass.py`` has to see it (module text): on
    the mixed-width plan the narrow array and its width in place of the
    pair; the Booster itself on any other plan, or where the trainer's state
    has moved (fullpass then says so itself)."""
    g = bst._gbdt
    mixed = getattr(getattr(g, "_plan", None), "mixed", None)
    if mixed is None:
        return bst
    narrow, _ = g._grow_bins
    return types.SimpleNamespace(
        work_counters=bst.work_counters,
        _gbdt=types.SimpleNamespace(
            _wave_info=g._wave_info, uses_wave=g.uses_wave, config=g.config,
            _grow_bins=narrow, B_phys=int(mixed.B_narrow)))


def _bin_sample(n: int, params: dict) -> np.ndarray:
    """The rows the program finds its bins from (module text)."""
    from lightgbm_tpu.utils.random import Random
    cnt, seed = (int(params.get(k, d))
                 for k, d in BIN_SAMPLE_DEFAULTS.items())
    return (np.arange(n) if cnt >= n
            else np.asarray(Random(seed).sample(n, cnt), np.int64))


def _root(bst) -> tuple:
    """A Booster's first split: feature, and threshold or category set."""
    tree = reference_cat.parse_model_string(bst.model_to_string())[0]
    return reference_cat.root_split(tree)


def _same_root(a, b, bin_maps: dict) -> bool:
    """The two first splits part the rows the same way: equal, or two
    category sets that are each other's complement in a column whose every
    value has a bin (the sorted scan reaches that partition from either end
    at one gain, and rounding picks the end)."""
    if a == b:
        return True
    if a is None or b is None or a[0] != b[0] \
            or not (isinstance(a[1], tuple) and isinstance(b[1], tuple)):
        return False
    bm = bin_maps[a[0]]
    return (bm["all_kept"] and not set(a[1]) & set(b[1])
            and set(a[1]) | set(b[1]) == {v for v in bm["values"] if v >= 0})


def _dropped_rows(Xs: np.ndarray, bin_maps: dict) -> int:
    """Rows of the sample that hold, in some declared column, a value with
    no bin of its own."""
    hit = np.zeros(len(Xs), bool)
    for col, bm in bin_maps.items():
        hit |= ~np.isin(Xs[:, col].astype(np.int64),
                        [v for v in bm["values"] if v >= 0])
    return int(hit.sum())


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    import lightgbm_tpu as lgb

    if not hasattr(lgb.Dataset, "categorical_bins"):
        sys.exit("benchmark: this program has no Dataset.categorical_bins() "
                 "and no cat_splits counter; the cell cannot tell a run in "
                 "which the categorical search worked, and does not run")
    cell, cfg, traffic = ctx.cell, ctx.cell.config, ctx.cell.traffic
    host = ctx.evidence["host"]
    params = {"verbose": -1, **cfg["params"]}
    spec, chk = cfg["data"], cfg["check"]
    task = spec["task"]
    declared = datagen_codes.categorical_columns(spec)
    if sorted(params.get("categorical_feature", [])) != declared:
        sys.exit(f"benchmark: params.categorical_feature has to declare the "
                 f"table's code columns {declared}")

    # ---- set-up: table from the seed, bins, Booster, warm-up --------------
    t = time.perf_counter()
    X, y, _ = datagen_codes.make_table(spec, ctx.seed)
    n = len(y)
    host["gen_s"] = time.perf_counter() - t
    idx, _ = _sample_rows(n, None, int(chk["sample_rows"]), ctx.seed)
    Xs, ys = X[idx].copy(), y[idx].copy()

    t = time.perf_counter()
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    host["bin_s"] = time.perf_counter() - t
    bin_maps = ds.categorical_bins()

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
        ctx.collect({"booster": _kernel_view(bst)})

    # ---- (a) every timed iteration split on category sets -------------------
    grown = done + WARMUP_ITERS + (TRACE_ITERS if ctx.trace else 0)
    per_tree = bst.work_counters(last=grown)["trees"]
    cat_floor = int(chk["cat_splits_min_per_iter"])
    timed = [{"iteration": t["iteration"], "cat_splits": t["cat_splits"],
              "splits": t["walks"]} for t in per_tree
             if WARMUP_ITERS <= t["iteration"] < WARMUP_ITERS + done]
    cat_ok = (len(timed) == done
              and all(t["cat_splits"] >= cat_floor for t in timed))
    checks = {"stamps": stamps, "finite": finite,
              "compiles_in_window": in_window,
              "cat_splits": {"timed": timed, "min_per_iter": cat_floor,
                             "bins": {str(c): len(bm["values"])
                                      for c, bm in bin_maps.items()}}}

    # ---- (b) the exported model, walked over the sample's raw codes --------
    raw_all = bst._raw_train_score()
    raw_prog = raw_all[idx]
    text = bst.model_to_string()
    trees = reference_cat.parse_model_string(text)
    raw_ref = reference_cat.walk(trees, Xs)
    err = float(np.max(np.abs(raw_ref - raw_prog)
                       / (1.0 + np.abs(raw_ref))))
    dropped = _dropped_rows(Xs, bin_maps)
    qname, qval, _ = _quality(task, ys, raw_prog, None)
    floor = float(cell.expect.get(qname, {}).get("min", 0.0))
    checks["export"] = {"rows": int(len(idx)), "trees": len(trees),
                        "cat_nodes": sum(t["num_cat"] for t in trees),
                        "max_rel_err": err, "tol": SCORE_TOL,
                        "rows_with_a_dropped_value": dropped,
                        qname: qval, "floor": floor}
    ok = (finite and cat_ok and err <= SCORE_TOL and qval >= floor
          and dropped > 0 and len(trees) == grown)

    # ---- the bin map (c1) pools by, held to the rows it was found from ------
    found = X[_bin_sample(n, params)]
    map_problems = {
        str(c): reference_cat.bin_map_problems(
            found[:, c], bm["values"], bm["all_kept"],
            max_bin=int(params.get("max_bin", 255)),
            min_data_in_bin=int(params.get("min_data_in_bin", 3)))
        for c, bm in bin_maps.items()}
    checks["bin_maps"] = {"sample_rows": int(len(found)),
                          "problems": map_problems}
    ok = ok and not any(map_problems.values())

    # ---- (c1) one more tree's category sets, judged at the timed size ------
    bst.update()
    sync()
    tree = reference_cat.parse_model_string(bst.model_to_string())[-1]
    t = time.perf_counter()
    verdict = reference_cat.judge(
        tree, X, y, raw_all, bin_maps, params,
        nodes=int(chk["judged_nodes"]), gain_rtol=float(chk["gain_rtol"]),
        tie_share_max=float(chk["tie_share_max"]),
        gain_med_rtol=float(chk["gain_med_rtol"]),
        med_columns={c for c, bm in bin_maps.items()
                     if len(bm["values"]) <= int(chk["med_max_bins"])},
        med_nodes_min=int(chk["med_nodes_min"]))
    verdict["seconds"] = time.perf_counter() - t
    checks["judge"] = verdict
    ok = ok and verdict["ok"]

    # ---- (c2) the same path against the serial grower, staged --------------
    ora = cfg["oracle"]
    del bst, ds, X
    Xo, yo, _ = datagen_codes.make_table(spec, ctx.seed,
                                         rows=int(ora["slice_rows"]))
    iters = int(ora["iters"])
    oparams = {k: v for k, v in params.items()
               if k not in ora.get("params_drop", [])}
    oparams.update(ora.get("params_set", {}))
    oparams.update(ORACLE)
    init, stages = None, []
    slice_maps = lgb.Dataset(Xo, label=yo, params=params).categorical_bins()
    for _ in range(iters):
        fast = _fit_from(params, Xo, yo, init)
        with _env_without("LGBM_TPU_FORCE_WAVE"):
            slow = _fit_from(oparams, Xo, yo, init)
        fast_stamps, slow_stamps = _stamps(fast), _stamps(slow)
        raw = {"path": fast._raw_train_score(),
               "oracle": slow._raw_train_score()}
        root = {"path": _root(fast), "oracle": _root(slow)}
        stages.append({
            "same_root": _same_root(root["path"], root["oracle"],
                                    slice_maps),
            "root": root["path"], "root_oracle": root["oracle"],
            "score_med": float(np.median(np.abs(raw["path"] - raw["oracle"]))
                               / np.std(raw["oracle"])),
            "loss_path": _quality(task, yo, raw["path"], None)[2],
            "loss_oracle": _quality(task, yo, raw["oracle"], None)[2],
            "path_on_path": {k: fast_stamps[k] for k in want} == want,
            "oracle_uses_wave": slow_stamps["uses_wave"]})
        init = raw["oracle"]
    score_med = max(st["score_med"] for st in stages)
    loss_ratio = max(st["loss_path"] / st["loss_oracle"] for st in stages)
    same_root = all(st["same_root"] for st in stages)
    off_path = any(st["oracle_uses_wave"] or not st["path_on_path"]
                   for st in stages)
    checks["oracle"] = {"rows": int(len(yo)), "iters": iters,
                        "same_root": same_root, "root": stages[0]["root"],
                        "score_med": score_med,
                        "score_med_max": float(ora["score_med_max"]),
                        "loss_path": stages[-1]["loss_path"],
                        "loss_oracle": stages[-1]["loss_oracle"],
                        "loss_ratio": loss_ratio,
                        "loss_ratio_max": float(ora["loss_ratio_max"]),
                        "oracle_uses_wave": stages[-1]["oracle_uses_wave"],
                        "stages": stages}
    ok = (ok and same_root and not off_path
          and score_med <= float(ora["score_med_max"])
          and loss_ratio <= float(ora["loss_ratio_max"]))
    ctx.evidence["checks"] = checks
    return {"correct": bool(ok and failed == 0), "attempted": attempted,
            "failed": failed,
            "end_to_end": {"train_row_iters_per_s": rate}}
