#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the trainer and the server start
on the chip.

One process, through the entry points a user calls (``lightgbm_tpu.train``,
``serve.PredictorSession``), at the full width of the two north-star
configurations, with depth cut to a few iterations and seeded synthetic data:

  train  HIGGS-shaped binary: 1,000,000 x 28, 255 leaves, max_bin 255, 5 iters
  serve  the forest just trained: warmup, ~20 requests of 1..4,096 rows
  rank   MSLR-shaped lambdarank: ~200k x 136, ragged queries, NDCG@10, 3 iters
  mesh   (``--chips N`` only, and then first) tree_learner=data over N chips
         at train's shape

Each phase asserts WHAT RAN, read off the trainer (compiled wave kernel,
2xbf16, packed lanes, fused sibling, not interpreted; a session that never
degraded), and checks the result against the repository's plain oracles on a
20k-row slice at the same width: ``hist_scatter``, the serial XLA grower,
``Booster.predict``, the host NDCG loop.  Nothing is caught and carried past:
a phase that raises ends the run non-zero with no result line.

Exits non-zero unless ``jax.devices()[0].platform == "tpu"``, and when
``LGBM_TPU_FORCE_WAVE`` is set (that switch interprets the kernel).  The last
stdout line is the result the chip check reads, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`` with
the device as JAX reports it.  The line before it, ``chip_smoke: summary
{...}``, is the report: versions, path stamps, oracle figures, per-phase
seconds, compile cache, binning path.  It claims nothing (``"claim": null``):
the seconds in it are set-up information, not a benchmark.  The phase
functions take ``interpret=True`` for tests/test_chip_smoke.py, which runs
them at toy size on the CPU — a test of control flow only.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

import numpy as np

TRAIN_PARAMS = {
    "objective": "binary", "metric": ["auc", "binary_logloss"],
    "num_leaves": 255, "max_bin": 255, "learning_rate": 0.1,
    "min_data_in_leaf": 100, "verbose": -1}
RANK_PARAMS = {
    "objective": "lambdarank", "metric": "ndcg", "eval_at": [10],
    "num_leaves": 255, "max_bin": 255, "learning_rate": 0.1,
    "min_data_in_leaf": 50, "min_sum_hessian_in_leaf": 5.0, "verbose": -1}


@contextlib.contextmanager
def _kernel_interpreted(interpret: bool):
    """CPU control-flow runs only: route training through the wave path with
    the Pallas interpreter, via the trainer's existing test hook (read once,
    when a Booster is built).  ``main`` refuses to start with it set."""
    if not interpret:
        yield
        return
    prev = os.environ.get("LGBM_TPU_FORCE_WAVE")
    os.environ["LGBM_TPU_FORCE_WAVE"] = "interpret"
    try:
        yield
    finally:
        if prev is None:
            del os.environ["LGBM_TPU_FORCE_WAVE"]
        else:
            os.environ["LGBM_TPU_FORCE_WAVE"] = prev


def _fit(params, X, y, iters, group=None, interpret=False, callbacks=()):
    """``lightgbm_tpu.train`` with the train set as its own valid set.
    ``first_call_s`` is Booster construction plus iteration 1 (transfer,
    trace, compile, first eval); every iteration ends in
    ``block_until_ready`` on the train score.  Returns the booster, the
    metric history and the seconds (binning, first call, whole fit)."""
    import jax

    import lightgbm_tpu as lgb

    t0 = time.perf_counter()
    ds = lgb.Dataset(X, label=y, group=group, params=params)
    ds.construct()
    t1 = time.perf_counter()
    marks = [t1]

    def clock(env):
        jax.block_until_ready(env.model._gbdt._train_score)
        marks.append(time.perf_counter())

    evals: dict = {}
    with _kernel_interpreted(interpret):
        bst = lgb.train(params, ds, num_boost_round=iters, valid_sets=[ds],
                        valid_names=["train"], evals_result=evals,
                        verbose_eval=False, keep_training_booster=True,
                        callbacks=[clock, *callbacks])
    jax.block_until_ready(bst._gbdt._train_score)
    assert len(marks) == iters + 1, \
        f"training stopped after {len(marks) - 1} of {iters} iterations"
    times = {"bin_s": round(t1 - t0, 3),
             "first_call_s": round(marks[1] - marks[0], 3),
             "wall_s": round(time.perf_counter() - t0, 3)}
    return bst, evals["train"], times


def _path_stamps(bst, interpret: bool, fused_sibling: bool = True) -> dict:
    """What ran, read off the trainer — and the refusal when it is not the
    default path: wave kernel, 2xbf16, packed lane pairs, sibling
    subtraction fused in the kernel (after the psum under a mesh, so
    ``fused_sibling=False`` there), interpreted only when asked."""
    g = bst._gbdt
    info = g._wave_info or {}
    stamps = {"uses_wave": bool(g.uses_wave),
              "hist_mode": info.get("hist_mode"),
              "packed": info.get("packed"),
              "fused_sibling": info.get("fused_sibling"),
              "fused_grad": bool(g.fused_grad_active()),
              "wave_capacity": info.get("wave_capacity"),
              "interpret": info.get("interpret")}
    want = {"uses_wave": True, "hist_mode": "2xbf16", "packed": True,
            "fused_sibling": fused_sibling, "interpret": bool(interpret)}
    got = {k: stamps[k] for k in want}
    assert got == want, f"trainer took another path: {got} != {want}"
    return stamps


def kernel_vs_scatter(bins_fm, B, mode="2xbf16", packed=True, fused=True,
                      interpret=False, seed=1) -> dict:
    """One ``hist_pallas_wave`` launch over ``bins_fm`` [F, N] uint8 against
    ``hist_scatter``: every lane in use, leaves beyond the wave and bagged-out
    rows present, a random parent when ``fused``.  The f32 bounds are those
    of tests/test_wave.py; the quantized modes must be integer-exact; the
    fused sibling must be bit-equal to parent minus child.  Blocks come from
    ``select_wave_blocks``, as in the grower."""
    import jax.numpy as jnp

    from lightgbm_tpu.core.histogram import hist_scatter
    from lightgbm_tpu.ops import pallas_hist as ph

    F, N = bins_fm.shape
    rng = np.random.default_rng(seed)
    P = ph.wave_capacity_max(packed)
    lanes = 2 if packed else 3
    leaves = rng.permutation(P + 2)          # the last two get no slot
    slot = np.full(ph.C_MAX, -1, np.int32)
    slot[:lanes * P] = np.repeat(leaves[:P], lanes)
    leaf_id = rng.integers(0, P + 2, N).astype(np.int32)
    cv = (rng.random(N) < 0.8).astype(np.float32)
    g = jnp.asarray(rng.normal(size=N).astype(np.float32) * cv)
    h = jnp.asarray((0.1 + rng.random(N)).astype(np.float32) * cv)
    quant = mode in ph.QUANT_MODES
    if quant:
        qmax = ph.QUANT_QMAX[mode]
        g = ph.stochastic_round(g / (jnp.max(jnp.abs(g)) / qmax), 0)
        h = ph.stochastic_round(h / (jnp.max(jnp.abs(h)) / qmax), 0)
    parent = None
    if fused:
        def par():
            return jnp.asarray(np.rint(rng.normal(
                size=(F, B, ph.C_MAX)) * 64).astype(np.float32))
        parent = (par(), par()) if packed else par()
    _, feat_block = ph.select_wave_blocks(B, mode=mode, packed=packed,
                                          fused=fused)
    out = ph.hist_pallas_wave(
        jnp.asarray(bins_fm), g, h, jnp.asarray(cv), jnp.asarray(leaf_id),
        jnp.asarray(slot), B=B, feat_block=feat_block, highest=mode,
        interpret=interpret, packed=packed, parent=parent)
    child, sib = out if fused else (out, None)

    # oracle: one scatter-add with the wave's slot folded into the bin axis
    slot_of = np.full(P + 2, -1, np.int64)
    slot_of[leaves[:P]] = np.arange(P)
    s = slot_of[leaf_id]
    bins_aug = (bins_fm.T.astype(np.int32)
                + (np.maximum(s, 0) * B).astype(np.int32)[:, None])
    want = np.asarray(hist_scatter(
        jnp.asarray(bins_aug), g, h, jnp.asarray(cv * (s >= 0)), B=B * P))
    want = want.reshape(F, P, B, 3).transpose(0, 2, 1, 3)     # [F, B, P, 3]
    if packed:
        # slot s's (g, h, count) sit where packed_lanes keeps them
        got = np.asarray(ph.unpack_lanes(child, mode, P)).transpose(
            1, 2, 0, 3)
        cat = np.concatenate([np.asarray(x) for x in child], axis=-1)
        dead = [np.delete(cat, ph.packed_lanes(mode).reshape(-1), axis=-1)]
    else:
        hw = np.asarray(child)
        got = np.stack([hw[:, :, k:3 * P:3] for k in range(3)], axis=-1)
        dead = [hw[:, :, 3 * P:]]
    scale = float(np.abs(want[..., :2]).max())
    if quant:
        tol = dict(rtol=1e-6, atol=0.0)       # integer sums, f32-exact here
    elif mode == "2xbf16":
        tol = dict(rtol=2 ** -15, atol=2 ** -16 * scale * 4)
    elif mode == "bf16":
        tol = dict(rtol=2 ** -7, atol=2 ** -8 * scale * 4)
    else:
        tol = dict(rtol=1e-5, atol=1e-5 * scale)
    np.testing.assert_allclose(got[..., :2], want[..., :2], **tol)
    np.testing.assert_array_equal(got[..., 2], want[..., 2])
    for lanes_off in dead:
        assert not lanes_off.any(), "unused lanes carry mass"
    if fused:
        pairs = (zip(parent, child, sib) if packed
                 else [(parent, child, sib)])
        for pa, ch, sb in pairs:
            np.testing.assert_array_equal(
                np.asarray(sb), np.asarray(pa) - np.asarray(ch))
    return {"mode": mode, "packed": packed, "fused": fused, "B": int(B),
            "features": int(F), "rows": int(N),
            "feat_block": ph.wave_feature_blocks(
                B, F, feat_block, mode=mode, packed=packed, fused=fused,
                block_rows=1024)[0],
            "max_abs_err": float(np.abs(got - want).max()),
            "atol": float(tol["atol"])}


def _slice_kernel_check(bst, rows: int, interpret: bool) -> dict:
    """The trainer's own kernel configuration on a slice of its own bins."""
    g = bst._gbdt
    x_bin = g.train_ds.X_bin[:rows]
    return kernel_vs_scatter(np.ascontiguousarray(x_bin.T), g.B_phys,
                             mode=g._wave_info["hist_mode"],
                             packed=g._wave_info["packed"],
                             fused=g._wave_info["fused_sibling"],
                             interpret=interpret)


def _root(bst):
    t = bst._gbdt.models[0]
    return int(t.split_feature[0]), int(t.threshold_bin[0])


def phase_train(rows=1_000_000, iters=5, oracle_rows=20_000,
                interpret=False, params=None):
    """HIGGS-shaped binary training, every ``tpu_*`` knob at its default.
    Whole trees are not compared with the serial grower: at the default
    wave capacity the wave grower commits splits in another order by
    design; the root split and the training loss are.  Returns the
    report, the booster and the rows it was trained on."""
    from bench import higgs_like_data

    params = {**TRAIN_PARAMS, **(params or {})}
    X, y = higgs_like_data(rows)
    bst, evals, times = _fit(params, X, y, iters, interpret=interpret)
    stamps = _path_stamps(bst, interpret)
    auc = evals["auc"]
    score = np.asarray(bst._gbdt._train_score)
    assert score.shape == (rows, 1) and np.isfinite(score).all()
    assert np.isfinite(auc).all() and auc[-1] > auc[0] \
        and np.diff(auc).min() > -1e-3, f"train AUC does not rise: {auc}"

    n = min(oracle_rows, rows)
    kern = _slice_kernel_check(bst, n, interpret)
    # wave (as shipped) against the serial XLA grower on the same slice:
    # device_type=cpu is the parameter that selects core/grower.py
    wave, wave_evals, _ = _fit(params, X[:n], y[:n], iters,
                               interpret=interpret)
    _path_stamps(wave, interpret)
    serial, serial_evals, _ = _fit({**params, "device_type": "cpu"},
                                   X[:n], y[:n], iters)
    assert not serial._gbdt.uses_wave
    root_w, root_s = _root(wave), _root(serial)
    assert root_w == root_s, f"root split {root_w} != serial {root_s}"
    loss_w = wave_evals["binary_logloss"][-1]
    loss_s = serial_evals["binary_logloss"][-1]
    # the bound of test_wave_gated_boosting_matches_serial_loss
    assert loss_w <= 1.03 * loss_s, (loss_w, loss_s)
    report = {"rows": rows, "features": int(X.shape[1]), "iters": iters,
              "stamps": stamps, "train_auc": [round(a, 6) for a in auc],
              "kernel_vs_scatter": kern,
              "oracle": {"rows": n, "root_split": list(root_w),
                         "loss_wave": round(loss_w, 6),
                         "loss_serial": round(loss_s, 6)},
              **times}
    return report, bst, X


def phase_serve(bst, X, max_rows=4096, n_requests=20, seed=3) -> dict:
    """The forest just trained behind ``PredictorSession``: warmup, then
    requests of 1..max_rows rows through the synchronous and the queued
    entry points alike, each equal to ``Booster.predict`` to 1e-6 — served
    by the device path (the session answers 200 from the host predictor
    when the device path raises; that must not have happened)."""
    from lightgbm_tpu import obs
    from lightgbm_tpu.serve import PredictorSession

    # the session's default ring (256 records) would roll over; what the
    # process recorded before this phase is not this session's
    obs.enable_flight(8192)
    since = time.time()
    rng = np.random.default_rng(seed)
    sizes = np.unique(np.rint(
        np.geomspace(1, max_rows, n_requests)).astype(int))
    t0 = time.perf_counter()
    err = 0.0
    with PredictorSession(bst) as sess:
        buckets = sess.warmup()
        first_call_s = time.perf_counter() - t0
        for i, n in enumerate(sizes):
            lo = int(rng.integers(0, len(X) - n + 1))
            rows = X[lo:lo + n]
            got = (sess.predict(rows) if i % 2 == 0
                   else sess.result(sess.submit(rows), timeout=300))
            want = bst.predict(rows)
            assert got.shape == want.shape and np.isfinite(got).all()
            err = max(err, float(np.abs(got - want).max()))
        stats = sess.stats()
    assert err <= 1e-6, f"session vs Booster.predict: {err}"
    assert stats["degraded"] is False
    bad = [e for e in obs.flight_snapshot() if e.get("t", since) >= since
           and (e.get("event") in ("serve_degraded", "aot_fallback")
                or e.get("name") == "serve/host_fallback")]
    assert not bad, f"the session left the device path: {bad[:3]}"
    return {"requests": int(len(sizes)), "max_rows": int(sizes[-1]),
            "warmed_buckets": int(buckets), "max_abs_err": err,
            "degraded": False, "compile_count": stats["compile_count"],
            "first_call_s": round(first_call_s, 3),
            "wall_s": round(time.perf_counter() - t0, 3)}


def phase_rank(rows=200_000, iters=3, oracle_rows=20_000, interpret=False,
               params=None) -> dict:
    """MSLR-shaped lambdarank: 136 features tile the kernel differently
    from 28, and NDCG@10 is evaluated on the device; the per-query host
    loop is its oracle."""
    from bench import mslr_like_data

    params = {**RANK_PARAMS, **(params or {})}
    X, y, q = mslr_like_data(rows)
    bst, evals, times = _fit(params, X, y, iters, group=q,
                             interpret=interpret)
    stamps = _path_stamps(bst, interpret)
    g = bst._gbdt
    ndcg = evals["ndcg@10"]
    metric = g.metrics[0]
    assert metric.accepts_device_score, "NDCG was not evaluated on the device"
    host = dict((k, v) for k, v, _ in metric.eval_host(
        np.asarray(g._train_score[:, 0])))["ndcg@10"]
    assert np.isfinite(ndcg).all() and ndcg[-1] > ndcg[0], ndcg
    assert abs(ndcg[-1] - host) <= 1e-6, (ndcg[-1], host)
    kern = _slice_kernel_check(bst, min(oracle_rows, len(y)), interpret)
    return {"rows": int(len(y)), "queries": int(len(q)),
            "features": int(X.shape[1]), "iters": iters, "stamps": stamps,
            "train_ndcg10": [round(v, 6) for v in ndcg],
            "host_ndcg10": round(host, 6), "kernel_vs_scatter": kern,
            **times}


def _bytes_in_use(devices) -> list:
    import jax
    stats = [d.memory_stats() for d in devices]
    if all(s and "bytes_in_use" in s for s in stats):
        return [int(s["bytes_in_use"]) for s in stats]
    # CPU devices report no memory stats: size the live arrays' shards
    # (from the sharding, not through .data, which would add live arrays)
    per = dict.fromkeys(devices, 0)
    for a in jax.live_arrays():
        nbytes = (int(np.prod(a.sharding.shard_shape(a.shape)))
                  * a.dtype.itemsize)
        for d in a.sharding.addressable_devices:
            if d in per:
                per[d] += nbytes
    return [per[d] for d in devices]


def phase_mesh(n_devices, rows=1_000_000, iters=5, interpret=False,
               params=None):
    """``tree_learner=data`` over ``n_devices`` chips at the train phase's
    shape: bins placed once across the mesh, memory even across chips after
    construction, the wave kernel under ``psum``.  ``main`` runs it FIRST:
    bytes in use are read as a difference, and earlier phases' arrays being
    freed in between would skew the first chip's.  Returns the report and
    the booster, for ``mesh_vs_one_chip``."""
    import jax

    from bench import higgs_like_data

    devices = jax.devices()[:n_devices]
    assert len(devices) == n_devices, \
        f"{n_devices} devices asked, {len(jax.devices())} visible"
    params = {**TRAIN_PARAMS, **(params or {}), "tree_learner": "data",
              "tpu_mesh_shape": f"data:{n_devices}"}
    X, y = higgs_like_data(rows)
    gc.collect()
    before = _bytes_in_use(devices)
    built = {}

    def after_construction(env):
        if not built:
            bins = env.model._gbdt._grow_bins
            built["bins_devices"] = len(bins.sharding.device_set)
            built["bins_shard_shape"] = list(
                bins.sharding.shard_shape(bins.shape))
            built["bytes"] = [a - b for a, b in
                              zip(_bytes_in_use(devices), before)]
    after_construction.before_iteration = True

    bst, evals, times = _fit(params, X, y, iters, interpret=interpret,
                             callbacks=[after_construction])
    # under a mesh the sibling is parent minus the GLOBAL child: after psum
    stamps = _path_stamps(bst, interpret, fused_sibling=False)
    assert bst._gbdt.config.tree_learner == "data"
    assert built["bins_devices"] == n_devices, built
    per = built["bytes"]
    spread = (max(per) - min(per)) / max(per)
    assert min(per) > 0 and spread <= 0.05, \
        f"bytes in use after construction uneven across chips: {per}"
    auc = evals["auc"]
    assert np.isfinite(auc).all() and auc[-1] > auc[0], auc
    report = {"devices": n_devices, "rows": rows, "iters": iters,
              "stamps": stamps, "bins_devices": built["bins_devices"],
              "bins_shard_shape": built["bins_shard_shape"],
              "bytes_after_construction": per,
              "bytes_spread": round(spread, 4),
              "train_auc": [round(a, 6) for a in auc], **times}
    return report, bst


def mesh_vs_one_chip(mesh_bst, mesh_report, one_bst, one_report) -> dict:
    """The mesh run must grow the one-chip run's tree 0 — or, if f32
    reduction order under psum flipped a near-tie, reach its AUC."""
    t4, t1 = mesh_bst._gbdt.models[0], one_bst._gbdt.models[0]
    differs = [f for f in ("split_feature", "threshold_bin", "left_child",
                           "right_child")
               if not np.array_equal(getattr(t4, f), getattr(t1, f))]
    if t4.leaf_value.shape != t1.leaf_value.shape or not np.allclose(
            t4.leaf_value, t1.leaf_value, rtol=1e-4, atol=1e-6):
        differs.append("leaf_value")
    auc_delta = abs(mesh_report["train_auc"][-1]
                    - one_report["train_auc"][-1])
    assert not differs or auc_delta <= 1e-3, \
        f"mesh run departs from one chip: {differs}, AUC {auc_delta}"
    return {"tree0_equals_one_chip": not differs,
            "tree0_fields_differing": differs,
            "tree0_leaves": [int(t4.num_leaves), int(t1.num_leaves)],
            "auc_delta_vs_one_chip": round(auc_delta, 6)}


def _versions() -> dict:
    from importlib import metadata

    import jax
    import jaxlib
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="with N > 1, add the tree_learner=data phase over "
                         "N chips; fails when fewer are visible")
    args = ap.parse_args(argv)
    if os.environ.get("LGBM_TPU_FORCE_WAVE"):
        print("chip_smoke: LGBM_TPU_FORCE_WAVE is set; it interprets the "
              "kernel, which is what this script exists to rule out",
              file=sys.stderr)
        return 2

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    versions = _versions()
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']} "
          + " ".join(f"{k}={v}" for k, v in versions.items()), flush=True)
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform="
              f"{device['platform']!r}", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} device(s) "
              "visible", file=sys.stderr)
        return 1

    from lightgbm_tpu import native
    from lightgbm_tpu.obs.peaks import device_peaks
    from lightgbm_tpu.utils.compile_cache import (compile_cache_info,
                                                  enable_compile_cache)
    peak_flops, peak_bw = device_peaks()   # raises for a kind not in the table
    enable_compile_cache()
    cache = compile_cache_info()
    print(f"chip_smoke: compile cache {cache['dir']} "
          f"({'warm' if cache['warm'] else 'cold'})", flush=True)

    t0 = time.perf_counter()
    phases = {}
    if args.chips > 1:
        phases["mesh"], mesh_bst = phase_mesh(args.chips)
    phases["train"], bst, X = phase_train()
    print(f"chip_smoke: train ok {json.dumps(phases['train'])}", flush=True)
    if args.chips > 1:
        phases["mesh"].update(mesh_vs_one_chip(
            mesh_bst, phases["mesh"], bst, phases["train"]))
        del mesh_bst
        print(f"chip_smoke: mesh ok {json.dumps(phases['mesh'])}",
              flush=True)
    phases["serve"] = phase_serve(bst, X)
    print(f"chip_smoke: serve ok {json.dumps(phases['serve'])}", flush=True)
    del bst, X
    phases["rank"] = phase_rank()
    print(f"chip_smoke: rank ok {json.dumps(phases['rank'])}", flush=True)

    summary = {
        "device": device, "versions": versions,
        "peaks": {"bf16_flops": peak_flops, "hbm_bytes_per_s": peak_bw},
        "binning": "native" if native.lib() is not None else "numpy",
        "compile_cache": cache, "phases": phases,
        "wall_s": round(time.perf_counter() - t0, 1),
        "claim": None}
    print(f"chip_smoke: summary {json.dumps(summary)}", flush=True)
    # the result line: these keys and no others (the chip check's contract)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
