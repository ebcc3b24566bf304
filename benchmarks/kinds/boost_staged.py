"""Traffic kind ``boost_staged``: ``Booster.update()`` back to back on a dense
table, by a program that says which kernel it launches.

The protocol of ``kinds/boost.py`` (its module text says why each part is as
it is): table from the seed, bins, Booster, the traffic file's
``warmup_iters`` warm-up iterations, then ``scored_iters`` updates with a
``block_until_ready`` after each; ``train_row_iters_per_s`` is rows times
iterations over the time to the last sync; ``TRACE_ITERS`` more under the
profiler when traced.  Its helpers, and ``kinds/boost_csr.py``'s staged fit,
are imported, not copied.  It differs in these places:

- (a) the stamps are what the program says of itself in public
  (``Booster.work_counters(last=0)``), and after them every key of the
  configuration's ``facts`` is held to the same answer: the bin lanes a
  feature the wave kernel runs at (``kernel_bins``), the features a grid step
  covers (``feat_block``), the features whose one-hot factors share one MXU
  pass (``feat_pack``) and the columns a launch covers (``kernel_columns``).
  A launch that fell back to one feature a pass (a bin width padded past 64,
  a block the pack does not divide) would time as a slow honest run; here it
  ends the run with no result.  A program that has no such facts to say
  (``core/plan.py`` without a ``KernelShape`` of those names: the parent of
  the PR that brought them) ends the run at once, before the cell's table is
  made;
- (b) as ``boost.py``, with the tolerance of the model walk read from the
  configuration (``check.export_tol``), and the source's stopping rule held
  on the exported model: no leaf of any tree below
  ``min_sum_hessian_in_leaf`` (``leaf_weight``, to ``check.leaf_weight_rtol``:
  the bound is tested on float32 histogram sums);
- (c) is staged as ``boost_csr.py`` stages it, and for its reason (its module
  text; ``boost.py``'s two iterations run on read ``score_med`` 0.0486 on an
  honest run at one ``higgs-train`` seed of six, PERF.md 7): each of the
  ``oracle.iters`` iterations is grown by the configuration's path and by
  the serial XLA grower (``device_type=cpu``, the same parameters) from the
  SAME scores, the oracle's so far, as ``init_score``.  Each stage holds: the
  same root, ``score_med_max``, ``loss_ratio_max``, the path still on the
  configuration's stamps and facts, both trees under the leaf cap (the
  hessian bound ends growth on the slice; under a binding cap the wave path
  grows another tree than the serial grower, by design), and **the path's
  launches in more than one MXU pass**: the stage's own counters
  (``work_counters(last=1)``) read ``kernel_pass_rows`` above ``kernel_rows``
  on the fullest chip.  The timed trees' launches hold up to 63 pending
  leaves, two and three passes; a slice whose every launch fits one pass
  would hold only the kernel's one-pass branch against the oracle.  Under
  the cell's own cap a tree reaches launches of more than 50 only where the
  cap binds, so the configuration sizes the slice (``oracle.slice_rows``) and
  raises the cap of both growers over what the hessian bound allows there
  (``oracle.params``, laid over the cell's parameters in this check alone).
"""
from __future__ import annotations

import sys
import time

import numpy as np

from harness import compiles, datagen, reference, trace
from harness.cells import scratch_dir
from harness.device import memory_parts

from .boost import (END_TO_END, ORACLE, TRACE_ITERS,  # noqa: F401
                    _env_without, _quality, _sample_rows)
from .boost_csr import _fit_from


def _ends(lacks) -> None:
    sys.exit(f"benchmark: this program's Booster.work_counters() does not "
             f"say {lacks}; the cell cannot tell which kernel it times, and "
             f"does not run")


def _has_facts(facts) -> None:
    """End the run before the table is made where the program has no such
    facts to say: they are the fields of ``core/plan.py KernelShape``."""
    from lightgbm_tpu.core import plan
    said = getattr(getattr(plan, "KernelShape", None), "_fields", ())
    lacks = [k for k in facts if k not in said]
    if lacks:
        _ends(lacks)


def _said(bst, facts) -> dict:
    """Stamps and facts, as the program says them itself; the run ends where
    it does not say one."""
    work = bst.work_counters(last=0)
    lacks = [k for k in ("stamps", *facts) if k not in work]
    if lacks:
        _ends(lacks)
    return {**work["stamps"], **{k: work[k] for k in facts}}


def _leaf_weights(text: str) -> np.ndarray:
    """Every ``leaf_weight`` (a leaf's sum of hessians) of a model text."""
    out = [np.asarray(line.partition("=")[2].split(), np.float64)
           for line in text.splitlines() if line.startswith("leaf_weight=")]
    return np.concatenate(out) if out else np.zeros(0)


def staged(cfg: dict, params: dict, want: dict, seed: int) -> dict:
    """Check (c) (module text): each of ``oracle.iters`` iterations grown on
    the slice by ``params`` and by the serial XLA grower from the oracle's
    scores so far; ``oracle.params`` over both (the leaf cap of the slice's
    trees).  ``want`` is the stamps and facts the path has to stay on."""
    ora, task = cfg["oracle"], cfg["data"]["task"]
    facts = [k for k in want if k in cfg["facts"]]
    Xo, yo, _ = datagen.make_table(cfg["data"], seed,
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
        tree = {k: reference.parse_model_string(b.model_to_string())[0]
                for k, b in (("path", fast), ("oracle", slow))}
        root = {k: reference.root_split(t) for k, t in tree.items()}
        stages.append({
            "same_root": root["path"] == root["oracle"],
            "root": root["path"],
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
            "multi_pass": multi_pass, "stages": stages,
            "ok": bool(same_root and not off_path and not cap_binds
                       and multi_pass
                       and score_med <= float(ora["score_med_max"])
                       and loss_ratio <= float(ora["loss_ratio_max"]))}


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

    # ---- set-up: table from the seed, bins, Booster, warm-up --------------
    t = time.perf_counter()
    X, y, _ = datagen.make_table(spec, ctx.seed)
    n = len(y)
    host["gen_s"] = time.perf_counter() - t
    idx, _ = _sample_rows(n, None, int(chk["sample_rows"]), ctx.seed)
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

    said = _said(bst, facts)
    got = {k: said[k] for k in want}
    if got != want:
        sys.exit(f"benchmark: the trainer left the configuration's path: "
                 f"{got} != {want}")

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
              "facts": {k: said[k] for k in facts}, "finite": finite,
              "compiles_in_window": in_window}
    raw_prog = bst._raw_train_score()[idx]
    text = bst.model_to_string()
    trees = reference.parse_model_string(text)
    raw_ref = reference.predict_raw(trees, Xs)
    err = float(np.max(np.abs(raw_ref - raw_prog)
                       / (1.0 + np.abs(raw_ref))))
    tol = float(chk["export_tol"])
    qname, qval, _ = _quality(task, ys, raw_prog, None)
    floor = float(cell.expect.get(qname, {}).get("min", 0.0))
    weights = _leaf_weights(text)
    hess_min = float(params["min_sum_hessian_in_leaf"])
    hess_floor = hess_min * (1.0 - float(chk["leaf_weight_rtol"]))
    checks["export"] = {"rows": int(len(idx)), "trees": len(trees),
                        "max_rel_err": err, "tol": tol,
                        qname: qval, "floor": floor,
                        "leaves": [t["num_leaves"] for t in trees],
                        "min_leaf_weight": float(weights.min()),
                        "leaf_weight_floor": hess_floor}
    ok = (finite and err <= tol and qval >= floor
          and len(weights) == sum(t["num_leaves"] for t in trees)
          and float(weights.min()) >= hess_floor
          and len(trees) == done + warmup + (TRACE_ITERS if ctx.trace else 0))

    # ---- (c) the same path against the serial grower, staged ---------------
    del bst, ds
    checks["oracle"] = staged(cfg, params, want, ctx.seed)
    ok = ok and checks["oracle"]["ok"]
    ctx.evidence["checks"] = checks
    return {"correct": bool(ok and failed == 0), "attempted": attempted,
            "failed": failed,
            "end_to_end": {"train_row_iters_per_s": rate}}
