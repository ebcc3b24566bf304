"""Traffic kind ``boost_goss``: ``Booster.update()`` back to back under
``boosting=goss``, timed only once the sampler samples.

The protocol of ``kinds/boost.py`` (its module text says why each part is as
it is): table from the seed, bins, Booster, warm-up, then ``scored_iters``
updates with a ``block_until_ready`` on the train score after each;
``train_row_iters_per_s`` is ALL the table's rows times iterations over the
time to the last sync, as in every other cell, so that the ratio to the plain
cell on the same table is what GOSS buys a user; ``TRACE_ITERS`` more under
the profiler when traced.  Its helpers are imported, not copied.  It differs
in four places:

- the warm-up is the traffic file's ``warmup_iters``, which has to be
  ``int(1 / learning_rate) + 2``: GOSS samples nothing in its first
  ``int(1 / learning_rate)`` iterations (``goss.hpp:144``), so under
  ``boost.py``'s two warm-up iterations a window at ``learning_rate`` 0.1
  would never time the sampler.  The last two warm-up iterations have to
  have sampled (``bag_rows < rows``), else the run ends with no result;
- (a) the stamps and the facts (``boosting``, ``top_rate``, ``other_rate``)
  are what the program says of itself in public
  (``Booster.work_counters(last=0)``), and in every scored and traced
  iteration the sampler's own counters have to be a GOSS sample's
  (``harness/reference_goss.py counts_ok``: the top set within 0.01% of the
  rows of ``top_k``, the bag within five standard deviations of ``top_k +
  other_k``).  A program without the sampler's counters or without
  ``Booster.bag_mask()`` ends the run at once, before any table is made;
- (c1) the sampler at the timed size: after the window (and the traced one)
  the trainer's scores are read, one more ``update()`` runs, and
  ``harness/reference_goss.py judge`` says whether that iteration's
  threshold, ``bag_mask()`` and root (``internal_count``,
  ``internal_weight`` of the exported tree) are a legal sample of the
  gradients of those scores, computed in float64;
- (c2) the grower under a bag, staged as ``kinds/boost_csr.py`` stages its
  check and for its reason (a tie in one tree moves the next tree's
  gradients, and here also its sample): the oracle (``device_type=cpu``: the
  serial XLA grower, float32 scatter) runs the ``int(1 / learning_rate)``
  unsampled iterations on the slice; then, twice, the path and the oracle
  each grow ONE sampled iteration from that same forest (``init_model``:
  both replay it onto their scores with one program, so both samplers see
  the same gradients and draw the same bag, which the check holds them to),
  and the two trees are compared as ``boost.py`` compares (same root, the
  median of the difference in raw score over the standard deviation of the
  oracle's, the loss ratio); the oracle's forest goes on to the next stage.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from harness import compiles, datagen, reference, reference_goss, trace
from harness.cells import scratch_dir
from harness.device import memory_parts

from .boost import (END_TO_END, ORACLE, SCORE_TOL, TRACE_ITERS,  # noqa: F401
                    _env_without, _fit, _quality, _sample_rows)

FACTS = ("boosting", "top_rate", "other_rate")


def _stamps(bst) -> dict:
    """The path the trainer takes and the sampler it runs, as it says
    itself."""
    work = bst.work_counters(last=0)
    if "stamps" not in work or any(k not in work for k in FACTS):
        sys.exit("benchmark: this program's Booster.work_counters() does "
                 "not say which booster samples the rows; the cell cannot "
                 "tell the path it times")
    return {**work["stamps"], **{k: work[k] for k in FACTS}}


def _sampler(bst, last: int) -> list:
    """The sampler's counters of the last ``last`` iterations, oldest
    first: ``iteration``, ``top_rows``, ``bag_rows``, ``threshold``."""
    return list(bst.work_counters(last=last).get("sampler", []))


def _continue_one(params, X, y, base):
    """One iteration on from the forest of ``base`` (a Booster), through
    ``train(init_model=...)``: the trainer replays the forest onto its
    scores and numbers the iteration after it."""
    import jax

    import lightgbm_tpu as lgb
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.train(params, ds, num_boost_round=1, init_model=base,
                    keep_training_booster=True, verbose_eval=False)
    jax.block_until_ready(bst._gbdt._train_score)
    return bst


def _root_of_last_tree(text: str) -> tuple:
    """``(internal_count[0], internal_weight[0])`` of a model text's last
    tree."""
    kv = {}
    for line in text.split("\nTree=")[-1].split("end of trees")[0] \
            .splitlines():
        k, sep, v = line.partition("=")
        if sep:
            kv[k.strip()] = v.strip()
    return (int(kv["internal_count"].split()[0]),
            float(kv["internal_weight"].split()[0]))


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    import lightgbm_tpu as lgb

    if not hasattr(lgb.Booster, "bag_mask"):
        sys.exit("benchmark: this program has no Booster.bag_mask(); the "
                 "reference cannot judge the timed path's own sample, and "
                 "the cell does not run")
    cell, cfg, traffic = ctx.cell, ctx.cell.config, ctx.cell.traffic
    host = ctx.evidence["host"]
    params = {"verbose": -1, **cfg["params"]}
    spec = cfg["data"]
    task = spec["task"]
    top_rate, other_rate = params["top_rate"], params["other_rate"]
    start = reference_goss.sampling_starts(params["learning_rate"])
    warmup = int(traffic["warmup_iters"])
    if warmup != start + 2:
        sys.exit(f"benchmark: warmup_iters {warmup} is not int(1 / "
                 f"learning_rate) + 2 = {start + 2}: the window would not "
                 f"time the sampler")

    # ---- set-up: table from the seed, bins, Booster, warm-up --------------
    t = time.perf_counter()
    X, y, sizes = datagen.make_table(spec, ctx.seed)
    n = len(y)
    host["gen_s"] = time.perf_counter() - t
    idx, _ = _sample_rows(n, None, int(cfg["check"]["sample_rows"]), ctx.seed)
    Xs, ys = X[idx].copy(), y[idx].copy()

    t = time.perf_counter()
    ds = lgb.Dataset(X, label=y, params=params)
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
    for _ in range(warmup - 1):
        bst.update()
    sync()
    host["warmup_s"] = time.perf_counter() - t
    warm = _sampler(bst, 2)
    if len(warm) != 2 or any(s["bag_rows"] >= n for s in warm):
        sys.exit(f"benchmark: the last two warm-up iterations did not "
                 f"sample: {warm}")

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
    traced = TRACE_ITERS if ctx.trace else 0
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

    # ---- (a) every timed iteration's sample, by the program's counters -----
    checks = {"stamps": stamps, "finite": finite,
              "compiles_in_window": in_window}
    sampled = _sampler(bst, done + traced)
    counts_ok = (len(sampled) == done + traced and all(
        reference_goss.counts_ok(n, s["top_rows"], s["bag_rows"], top_rate,
                                 other_rate) for s in sampled))
    checks["sampler"] = {"iterations": sampled, "ok": counts_ok,
                         "sizes": reference_goss.sizes(n, top_rate,
                                                       other_rate)}

    # ---- (b) the exported model against the trainer's own scores -----------
    raw_all = np.asarray(bst._gbdt._train_score, np.float64)[:, 0]
    raw_prog = raw_all[idx]
    trees = reference.parse_model_string(bst.model_to_string())
    raw_ref = reference.predict_raw(trees, Xs)
    err = float(np.max(np.abs(raw_ref - raw_prog)
                       / (1.0 + np.abs(raw_ref))))
    qname, qval, _ = _quality(task, ys, raw_prog, None)
    floor = float(cell.expect.get(qname, {}).get("min", 0.0))
    checks["export"] = {"rows": int(len(idx)), "trees": len(trees),
                        "max_rel_err": err, "tol": SCORE_TOL,
                        qname: qval, "floor": floor}
    ok = (finite and counts_ok and err <= SCORE_TOL and qval >= floor
          and len(trees) == done + warmup + traced)

    # ---- (c1) the sampler at the timed size: one more update, judged -------
    bst.update()
    sync()
    last = _sampler(bst, 1)[-1]
    mask = np.asarray(bst.bag_mask()).astype(bool)
    root_count, root_weight = _root_of_last_tree(bst.model_to_string())
    g64, h64 = reference_goss.binary_gradients(
        raw_all, y, float(params.get("sigmoid", 1.0)))
    verdict = reference_goss.judge(
        g64, h64, mask, top_rate, other_rate,
        program_threshold=last["threshold"], root_count=root_count,
        root_weight=root_weight)
    verdict["counters"] = last
    verdict["counters_match"] = (last["bag_rows"] == verdict["bag_rows"]
                                 and last["bag_rows"] == root_count)
    checks["sample"] = verdict
    ok = ok and verdict["ok"] and verdict["counters_match"]
    del g64, h64, mask, raw_all

    # ---- (c2) the grower under a bag against the serial grower, staged -----
    ora = cfg["oracle"]
    del bst, ds
    Xo, yo, _ = datagen.make_table(spec, ctx.seed,
                                   rows=int(ora["slice_rows"]))
    oparams = {k: v for k, v in params.items()
               if k not in ora.get("params_drop", [])}
    oparams.update(ORACLE)
    with _env_without("LGBM_TPU_FORCE_WAVE"):
        base = _fit(oparams, Xo, yo, None, start)
    stages = []
    for _ in range(int(ora["iters"])):
        fast = _continue_one(params, Xo, yo, base)
        with _env_without("LGBM_TPU_FORCE_WAVE"):
            slow = _continue_one(oparams, Xo, yo, base)
        fast_stamps, slow_stamps = _stamps(fast), _stamps(slow)
        draw = {"path": _sampler(fast, 1), "oracle": _sampler(slow, 1)}
        raw = {"path": fast._raw_train_score(),
               "oracle": slow._raw_train_score()}
        root = {k: reference.root_split(reference.parse_model_string(
            b.model_to_string())[-1]) for k, b in (("path", fast),
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
            "same_draw": draw["path"] == draw["oracle"],
            "sampled": bool(draw["oracle"]) and all(
                s["bag_rows"] < len(yo) for s in draw["oracle"]),
            "draw": draw["oracle"]})
        base = slow
    score_med = max(st["score_med"] for st in stages)
    loss_ratio = max(st["loss_path"] / st["loss_oracle"] for st in stages)
    same_root = all(st["same_root"] for st in stages)
    off_path = any(st["oracle_uses_wave"] or not st["path_on_path"]
                   or not st["same_draw"] or not st["sampled"]
                   for st in stages)
    checks["oracle"] = {"rows": int(len(yo)), "iters": int(ora["iters"]),
                        "unsampled_iters": start,
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
