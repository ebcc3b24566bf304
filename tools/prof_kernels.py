"""Wave-grower cost decomposition — the supported attribution harness.

Promoted from the round-5 throwaway ``prof_decompose.py``: same four cost
hypotheses, now sharing the cost-model code the profile mode uses
(``obs.profile`` device peaks + ``ops.pallas_hist.wave_kernel_cost``), so
every leg prints measured time NEXT TO its analytical roofline and the
achieved fraction — the numbers ``docs/ROOFLINE.md``'s "measured" column
is filled from, and the first thing to run in a TPU window.

Legs (``PROF_LEGS`` comma-list, default all):
  kernel       — bare ``hist_pallas_wave`` full passes (triple-layout
                 oracle) vs the MXU roofline
  kernelpacked — bare packed-lane kernel pass (63 leaves, count folded —
                 the shipped layout; packed-vs-kernel is the
                 launches-per-tree win at equal per-pass cost)
  kernelfused  — packed kernel WITH in-kernel sibling subtraction (the
                 shipped fast path; fused-vs-kernelpacked measures the
                 saved XLA subtraction + HBM round-trip)
  kernelint16  — packed+fused kernel in QUANTIZED int16 mode (ISSUE 11:
                 stochastic-rounded integer g/h, exact hi/lo bf16
                 passes, int16 vector stream — vs the same-shape f32
                 legs the delta is the quantization economics)
  kernelint8   — same at int8 (one exact bf16 pass)
  fusedgrad    — gradient-stream microbench: (grad jit -> [N] g/h ->
                 grow jit) vs ONE jit computing gradients inline
                 (the plan's ``fused_grad``), against ``grad_stream_bytes`` — the
                 per-iteration [N] round-trip the fused pass deletes
  full         — ``build_wave_grow_fn`` as shipped (packed + fused +
                 batched split apply)
  nofuse       — ``fused_sibling`` off in the plan (the separate XLA
                 subtraction pass — full-vs-nofuse is the fusion win)
  triple       — packed=False, fused off (the PR-7-era grower, the
                 packed-channel differential oracle end to end)
  seqapply     — ``batched_apply=False`` (the per-split partition oracle)
  nokernel     — kernel stubbed to shaped noise (everything-but-kernel)
  gathers      — compaction-primitive microbenches (index build + tier
                 gathers)
  partition    — wave-partition microbench: the batched phase's apply
                 (``build_split_apply_fn``: one streamed pass for all the
                 slots) AND a hand-written per-split walk on the same slot
                 tables, each against ``splitter.partition_cost``
  variants     — NOT in the default set: every wave-kernel variant
                 ``config.py`` can reach (bin width x precision mode x
                 lane layout) compiled and run once on a 20k-row slice and
                 compared with ``hist_scatter`` (``chip_smoke.py
                 kernel_vs_scatter``); ``PROF_VARIANT_BINS`` narrows the
                 widths.  The first thing to run when the kernel or the
                 compiler changes

A leg that raises — a kernel the compiler refuses included — is reported
on stderr, the remaining legs still run, the failure is listed under
``failed`` in the JSON line and the exit code is 1.

Env knobs: ``PROF_ROWS`` (1_000_000), ``PROF_FEATURES`` (28),
``PROF_LEAVES`` (255), ``PROF_MAXBIN`` (255), ``PROF_CAPACITY`` (63),
``PROF_REPEAT`` (3), ``PROF_LEGS``, ``PROF_JSON=1`` (append one
machine-readable JSON line), ``PROF_INTERPRET=1`` (Pallas interpreter
mode — the CPU smoke path CI exercises between TPU windows).
``PROF_TRACE_DIR=<dir>`` switches to trace-report mode: instead of
running legs, parse an existing ``jax.profiler`` capture through
``obs/xprof.py`` and print its measured-roofline table (the same
``kernel_measured`` rows training runs emit); ``PROF_TRACE_ITERS``
(1) tells the cost models how many iterations the window covered.

With a telemetry sink configured (``LGBM_TPU_TELEMETRY``) every timed leg
also emits a ``kernel_profile`` event, so ``tools/telemetry_report.py``
and ``bench_history.py`` see harness runs like training runs.

Run: python tools/prof_kernels.py
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu import obs  # noqa: E402
from lightgbm_tpu.core import wave_grower  # noqa: E402
from lightgbm_tpu.core.histogram import hist_onehot_cost  # noqa: E402
from lightgbm_tpu.core.meta import (SplitConfig,  # noqa: E402
                                    build_device_meta)
from lightgbm_tpu.core.plan import GrowthPlan  # noqa: E402
from lightgbm_tpu.core.splitter import split_scan_cost  # noqa: E402
from lightgbm_tpu.obs.profile import (cost_analysis_dict,  # noqa: E402
                                      device_peaks, extract_cost,
                                      roofline_seconds)
from lightgbm_tpu.ops import pallas_hist  # noqa: E402

INTERP = os.environ.get("PROF_INTERPRET", "") not in ("", "0")
MODE = "2xbf16"


def _env_int(name, default):
    return int(os.environ.get(name, default))


def timeit(fn, *args, n=3, warmup=1):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    out = None
    for _ in range(n):
        out = jax.block_until_ready(fn(*args))
    return (time.time() - t0) / n, out


def build_problem(rows: int, F: int, leaves: int, max_bin: int):
    """Synthetic HIGGS-shaped problem + device-resident inputs."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(rows, F))
    w = rng.normal(size=min(8, F))
    y = (X[:, :len(w)] @ w + 0.5 * X[:, 0] * X[:, 1]
         + rng.logistic(size=rows) > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": leaves,
              "min_data_in_leaf": max(rows // 10_000, 5), "verbose": -1,
              "max_bin": max_bin}
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    cfg = lgb.Config.from_params(params)
    meta, B = build_device_meta(ds._handle, cfg)
    scfg = SplitConfig.from_config(cfg)
    binsT = jnp.asarray(np.ascontiguousarray(ds._handle.X_bin.T))
    g = jnp.asarray(rng.normal(size=rows).astype(np.float32))
    h = jnp.asarray((rng.random(rows) * 0.25).astype(np.float32))
    mask = jnp.ones(rows, jnp.float32)
    fmask = jnp.ones(F, bool)
    return dict(meta=meta, B=B, scfg=scfg, binsT=binsT, g=g, h=h,
                mask=mask, fmask=fmask, rows=rows, F=F,
                capacity=_env_int("PROF_CAPACITY", 63),
                block_rows=_env_int("PROF_BLOCK_ROWS", 1024))


def _report(results: dict, name: str, seconds: float, flops=None,
            nbytes=None, extra=None):
    """Record one measured leg: print, remember, and (sink permitting)
    emit the kernel_profile event through the shared profile machinery."""
    rec = {"seconds": round(seconds, 6)}
    line = f"{name:<26} {seconds * 1e3:9.2f} ms"
    if flops is not None:
        rf = roofline_seconds(flops, nbytes or 0.0)
        rec.update(flops=flops, bytes=nbytes,
                   roofline_s=round(rf, 9),
                   roofline_frac=round(rf / seconds, 6) if seconds else 0.0)
        line += (f"  roofline {rf * 1e3:9.3f} ms"
                 f"  frac {rec['roofline_frac']:8.4f}")
        obs.record_kernel(f"prof/{name}", flops, nbytes or 0.0, seconds,
                          source="prof_kernels")
    if extra:
        rec.update(extra)
    results[name] = rec
    print(line, flush=True)


def leg_kernel(p, results, n_rep: int, name="kernel full pass",
               packed=False, fused=False, mode=None):
    """Bare wave-kernel full passes vs the analytical MXU roofline AND
    XLA's own cost_analysis of the compiled kernel.  ``packed`` runs the
    lane-pair layout (63 leaves, count folded), ``fused`` additionally
    feeds a parent operand so the sibling subtraction happens in-kernel,
    ``mode`` overrides the precision mode (quantized legs pre-quantize
    g/h with ``stochastic_round`` exactly as the grower does) — the
    variants share one problem, so their deltas ARE the layout/precision
    economics."""
    rows, F, B = p["rows"], p["F"], p["B"]
    mode = mode or MODE
    rng = np.random.default_rng(1)
    lanes = 2 if packed else 3
    Pcap = max(1, min(p["capacity"], pallas_hist.wave_capacity_max(packed)))
    sl = np.full(pallas_hist.C_MAX, -1, np.int32)
    sl[:lanes * Pcap] = np.repeat(np.arange(Pcap), lanes)
    slot_leaf = jnp.asarray(sl)
    leaf_id = jnp.asarray(rng.integers(0, Pcap, rows, dtype=np.int32))
    g, h = p["g"], p["h"]
    if mode in pallas_hist.QUANT_MODES:
        qmax = pallas_hist.QUANT_QMAX[mode]
        s_g = jnp.maximum(jnp.max(jnp.abs(g)), 1e-30) / qmax
        s_h = jnp.maximum(jnp.max(jnp.abs(h)), 1e-30) / qmax
        g = pallas_hist.stochastic_round(g / s_g, 0)
        h = pallas_hist.stochastic_round(h / s_h, 0)
    parent = None
    if fused:
        shape = (F, B, pallas_hist.C_MAX)
        par = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        parent = (par, par) if packed else par
    # feat_block from the same VMEM model the grower uses — the fused
    # blocks at B=256 don't fit the default FB=32 on a real chip
    _, FBk = pallas_hist.select_wave_blocks(
        B, mode=mode, packed=packed, fused=fused,
        block_rows=p["block_rows"])
    kf = jax.jit(lambda: pallas_hist.hist_pallas_wave(
        p["binsT"], g, h, p["mask"], leaf_id, slot_leaf, B=B,
        block_rows=p["block_rows"], feat_block=FBk, highest=mode,
        interpret=INTERP, packed=packed, parent=parent))
    flops, nbytes = pallas_hist.wave_kernel_cost(rows, F, B, mode,
                                                 packed=packed, fused=fused)
    # a kernel the compiler refuses raises HERE and fails the leg
    ca = extract_cost(cost_analysis_dict(kf.lower().compile()))
    extra = {"leaves_per_launch": Pcap, "feat_block": FBk,
             "xla_flops": ca[0], "xla_bytes": ca[1]}
    dt, _ = timeit(kf, n=n_rep)
    _report(results, name, dt, flops, nbytes, extra)


def leg_variants(p, results, failed):
    """Every wave-kernel variant reachable from ``config.py``, once each,
    against ``hist_scatter``: bin widths 8..256 (``_padded_bin_width``),
    the five ``tpu_hist_dtype`` modes, and the three layouts the grower
    selects — packed+fused (the serial default), packed unfused
    (the plan's ``fused_sibling`` off: EFB bundles, every mesh learner) and
    triple unfused (the mixed-width side-pass).  A variant that raises or
    misses its bound is recorded under ``failed`` and the rest still run."""
    from chip_smoke import kernel_vs_scatter
    rows = min(p["rows"], 20_000)
    F = p["F"]
    rng = np.random.default_rng(5)
    widths = [int(b) for b in os.environ.get(
        "PROF_VARIANT_BINS", "256,128,64,32,16,8").split(",")]
    layouts = (("packed+fused", True, True), ("packed", True, False),
               ("triple", False, False))
    for B in widths:
        bins_fm = rng.integers(0, B, size=(F, rows), dtype=np.uint8)
        for mode in ("2xbf16", "bf16", "highest", "int16", "int8"):
            for lname, packed, fused in layouts:
                name = f"variant B={B} {mode} {lname}"
                t0 = time.time()
                try:
                    rec = kernel_vs_scatter(bins_fm, B, mode=mode,
                                            packed=packed, fused=fused,
                                            interpret=INTERP)
                except Exception as exc:  # noqa: BLE001 — leg by leg
                    _leg_failed(failed, name, exc)
                    continue
                rec["seconds_incl_compile"] = round(time.time() - t0, 2)
                results[name] = rec
                print(f"{name:<40} ok  FB={rec['feat_block']:<4}"
                      f"err {rec['max_abs_err']:.3g}  "
                      f"{rec['seconds_incl_compile']:.1f}s", flush=True)


def _leg_failed(failed: dict, name: str, exc: BaseException) -> None:
    msg = f"{type(exc).__name__}: {exc}"
    failed[name] = msg[:2000]
    print(f"{name:<40} FAILED  {msg[:2000]}", file=sys.stderr, flush=True)
    traceback.print_exc(limit=4)


def leg_partition(p, results, n_rep: int):
    """Wave-partition leg: the batched phase's split apply (the grower's
    own ``build_split_apply_fn``: one streamed pass for all the slots,
    the kernel interpreted under PROF_INTERPRET) vs a hand-written XLA
    walk a split, on identical synthetic slot tables, each against
    ``splitter.partition_cost`` (the leg names are the JSON's keys and
    stay)."""
    from lightgbm_tpu.core.grower import go_left_node
    from lightgbm_tpu.core.splitter import bitset_words, partition_cost
    from lightgbm_tpu.core.wave_grower import (WaveSplits,
                                               build_split_apply_fn,
                                               route_view)
    rows, F, B = p["rows"], p["F"], p["B"]
    meta = p["meta"]
    Pcap = max(1, min(p["capacity"], pallas_hist.C_MAX // 3))
    rng = np.random.default_rng(4)
    W = bitset_words(B)
    feats = rng.integers(0, F, Pcap).astype(np.int32)
    nb = np.asarray(meta.num_bins)
    ws = WaveSplits(
        ok=jnp.ones((Pcap,), bool),
        leaf=jnp.arange(Pcap, dtype=jnp.int32),
        new=jnp.arange(Pcap, 2 * Pcap, dtype=jnp.int32),
        feature=jnp.asarray(feats),
        threshold=jnp.asarray((nb[feats] // 2).astype(np.int32)),
        default_left=jnp.asarray(rng.random(Pcap) < 0.5),
        cat_bitset=jnp.zeros((Pcap, W), jnp.uint32))
    leaf_id0 = jnp.asarray(rng.integers(0, Pcap, rows, dtype=np.int32))
    binsT = p["binsT"]

    apply_fn = jax.jit(build_split_apply_fn(meta, interpret=INTERP))
    dt, _ = timeit(apply_fn, leaf_id0, route_view(binsT), ws, n=n_rep)
    flops, nbytes = partition_cost(rows, splits=Pcap, passes=1)
    _report(results, "partition one-pass", dt, flops, nbytes,
            {"splits": Pcap})

    def seq(leaf_id):
        def body(i, lid):
            f = ws.feature[i]
            col = binsT[f].astype(jnp.int32)
            go = go_left_node(col, ws.threshold[i], ws.default_left[i],
                              meta.is_categorical[f], ws.cat_bitset[i],
                              meta.missing_types[f], meta.num_bins[f],
                              meta.default_bins[f])
            return jnp.where((lid == ws.leaf[i]) & ~go, ws.new[i], lid)
        return jax.lax.fori_loop(0, Pcap, body, leaf_id)

    dt2, _ = timeit(jax.jit(seq), leaf_id0, n=n_rep)
    flops2, nbytes2 = partition_cost(rows, splits=Pcap)
    _report(results, "partition sequential", dt2, flops2, nbytes2,
            {"splits": Pcap,
             "speedup_one_pass": round(dt2 / dt, 2) if dt else None})


def leg_grow(p, results, name: str, n_rep: int,
             stub_kernel=False, batched_apply=True, packed=True,
             fused=True):
    """One grower variant, timed end to end per tree."""
    rows, F, B = p["rows"], p["F"], p["B"]
    real = pallas_hist.hist_pallas_wave
    if stub_kernel:
        def stub(bins_fm, gv, hv, cv, leaf_id, slot_leaf, B, packed=False,
                 parent=None, **kw):
            """Shape-compatible fake histograms with enough structure that
            the grower keeps splitting (positive counts/hessians, wiggly g
            sums) — measures everything-but-kernel.  Speaks both channel
            layouts and the fused (child, sibling) contract."""
            Fdim = bins_fm.shape[0]
            i = jnp.arange(B, dtype=jnp.float32)[None, :, None]
            c = jnp.arange(pallas_hist.C_MAX, dtype=jnp.float32)[None, None, :]
            f = jnp.arange(Fdim, dtype=jnp.float32)[:, None, None]
            base = jnp.sin(i * 0.37 + c * 1.3 + f * 2.1)
            s = (gv[0] + hv[0] + cv[0] + leaf_id[0].astype(jnp.float32)) * 0
            if packed:
                # per-leaf (g, h, count), then the lanes the kernel keeps
                # them in (one f32 array per slot: [P, F, B, 3])
                Pm = pallas_hist.wave_capacity_max(True)
                per_leaf = jnp.stack(
                    [base[..., :Pm] * 3.0, 40.0 + 0.0 * base[..., :Pm],
                     160.0 + 0.0 * base[..., :Pm]],
                    axis=-1).transpose(2, 0, 1, 3) + s
                child = pallas_hist.pack_lanes(per_leaf, MODE)
            else:
                kind = (jnp.arange(pallas_hist.C_MAX) % 3)[None, None, :]
                child = jnp.where(
                    kind == 0, base * 3.0,
                    jnp.where(kind == 1, 40.0 + 0.0 * base,
                              160.0 + 0.0 * base)) + s
            if parent is None:
                return child
            if packed:
                sib = tuple(pa - ch for pa, ch in zip(parent, child))
            else:
                sib = parent - child
            return child, sib
        wave_grower.hist_pallas_wave = stub
    try:
        grow = jax.jit(wave_grower.build_wave_grow_fn(
            p["meta"], p["scfg"], B, GrowthPlan(
                wave_capacity=min(p["capacity"],
                                  pallas_hist.wave_capacity_max(packed)),
                hist_mode=MODE, gain_gate=0.5, block_rows=p["block_rows"],
                interpret=INTERP, counts=True, batched_apply=batched_apply,
                packed=packed, fused_sibling=fused)))
        t0 = time.time()
        tr, lid, stats = grow(p["binsT"], p["g"], p["h"], p["mask"],
                              p["fmask"])
        jax.block_until_ready(lid)
        compile_s = time.time() - t0
        dt, (tr, lid, stats) = timeit(grow, p["binsT"], p["g"], p["h"],
                                      p["mask"], p["fmask"], n=n_rep)
    finally:
        wave_grower.hist_pallas_wave = real
    counts = wave_grower.wave_counts(stats)
    waves, kern_rows = counts["waves"], counts["kernel_rows"][0]
    leaves = int(tr.num_leaves)
    flops = nbytes = None
    if not stub_kernel:
        # kernel share of this tree, from the EXACT rows histogrammed
        flops, nbytes = pallas_hist.wave_kernel_cost(
            kern_rows, F, B, MODE, waves=waves, packed=packed, fused=fused,
            pass_rows=counts["kernel_pass_rows"][0])
    _report(results, name, dt, flops, nbytes,
            {"leaves": leaves, "waves": waves, "kernel_rows": kern_rows,
             "compile_s": round(compile_s, 1), "packed": packed,
             "fused_sibling": fused,
             "full_pass_equiv": round(kern_rows / rows, 2)})


def leg_fusedgrad(p, results, n_rep: int):
    """Gradient-stream microbench (ISSUE 11): the per-iteration
    [N]-sized legs the fused gradient pass deletes.  "gradstream separate"
    computes a binary-logloss-shaped gradient in its OWN jit (g/h
    materialize as device arrays) and consumes them in a second jit —
    the unfused pipeline's structure; "gradstream fused" runs the SAME
    math inside one jit so XLA fuses the gradient chain into the
    consumer.  Both legs report against ``grad_stream_bytes``.  The
    consumer is the quantize+pack prologue (int16), the exact fusion
    partner the quantized wave path feeds.  Both legs pay the same
    score/label reads, which grad_stream_bytes deliberately leaves out
    — the modeled DELTA between the legs is the round-trip, and the
    delta is what the A/B arbitrates."""
    rows = p["rows"]
    rng = np.random.default_rng(3)
    score = jnp.asarray(rng.normal(size=rows).astype(np.float32))
    label = jnp.asarray((rng.random(rows) < 0.5).astype(np.float32))
    qmax = pallas_hist.QUANT_QMAX["int16"]

    def grad(score):
        prob = 1.0 / (1.0 + jnp.exp(-score))
        return prob - label, prob * (1.0 - prob)

    # the REAL quantize+pack prologue shape: all four vector lanes
    # (g, h, count-weight, leaf) as [N, 4] int16 — so the measured
    # write stream is the same 8 B/row grad_stream_bytes charges
    leaf = jnp.zeros((rows,), jnp.float32)
    cv = jnp.ones((rows,), jnp.float32)

    def pack(g, h):
        s_g = jnp.maximum(jnp.max(jnp.abs(g)), 1e-30) / qmax
        s_h = jnp.maximum(jnp.max(jnp.abs(h)), 1e-30) / qmax
        gq = pallas_hist.stochastic_round(g / s_g, 0)
        hq = pallas_hist.stochastic_round(h / s_h, 0)
        return jnp.stack([gq, hq, cv, leaf], axis=1).astype(jnp.int16)

    grad_jit = jax.jit(grad)
    pack_jit = jax.jit(pack)

    def separate(score):
        g, h = grad_jit(score)          # [N] f32 g/h materialize
        return pack_jit(g, h)           # ...and are read back

    fused_jit = jax.jit(lambda s: pack(*grad(s)))
    nb_sep = pallas_hist.grad_stream_bytes(rows, 0.0, "int16",
                                           fused_grad=False)
    nb_fus = pallas_hist.grad_stream_bytes(rows, 0.0, "int16",
                                           fused_grad=True)
    dt, _ = timeit(separate, score, n=n_rep)
    _report(results, "gradstream separate", dt, 8.0 * rows, nb_sep)
    dt2, _ = timeit(fused_jit, score, n=n_rep)
    _report(results, "gradstream fused", dt2, 8.0 * rows, nb_fus,
            {"speedup_fused": round(dt / dt2, 2) if dt2 else None})


def leg_gathers(p, results, n_rep: int):
    """Compaction-primitive microbenches: are the tier gathers cheaper
    than the kernel rows saved?"""
    rows = p["rows"]
    rng = np.random.default_rng(2)
    active = jnp.asarray(rng.random(rows) < 0.3)
    T = max(rows // 2, 1)
    binsT = p["binsT"]
    bins_rm = jnp.asarray(np.asarray(binsT).T.copy())

    def idx_build():
        pos = jnp.cumsum(active.astype(jnp.int32))
        return jnp.zeros((rows,), jnp.int32).at[
            jnp.where(active, pos - 1, rows)
        ].set(jnp.arange(rows, dtype=jnp.int32), mode="drop")

    dt, idx = timeit(jax.jit(idx_build), n=n_rep)
    _report(results, "index build", dt)
    idx_t = idx[:T]
    dt, _ = timeit(jax.jit(
        lambda i: jnp.transpose(jnp.take(bins_rm, i, axis=0))), idx_t,
        n=n_rep)
    _report(results, f"tier gather T={T}", dt)
    g3 = jax.jit(lambda i: jnp.stack([p["g"], p["h"], p["mask"]], 1)[i])
    dt, _ = timeit(g3, idx_t, n=n_rep)
    _report(results, "vec3 gather", dt)


def report_trace(trace_dir: str, rows: int, F: int, leaves: int,
                 max_bin: int) -> int:
    """Measured-roofline table from an existing profiler capture.

    ``PROF_TRACE_DIR=<dir>`` replaces the microbench legs with the
    obs/xprof.py pipeline over a trace some training run (or
    tpu_window leg) already captured: parse, attribute per ``lgbm/*``
    scope, join against the cost models under the PROF_* problem shape
    — the exact ``kernel_measured`` rows the digest/report render, so
    the harness and the training plane arbitrate from ONE table."""
    from lightgbm_tpu.obs import xprof
    parsed = xprof.parse_trace_dir(trace_dir)
    if parsed["files"] == 0:
        print(f"no trace artifacts under {trace_dir}", flush=True)
        return 1
    attrib = xprof.attribute(parsed)
    context = {"rows": rows, "features": F, "bins": max_bin,
               "leaves": leaves, "mode": MODE,
               "iters": _env_int("PROF_TRACE_ITERS", 1)}
    rows_out = xprof.measured_rooflines(attrib, context)
    if parsed["errors"]:
        print("parse errors: " + "; ".join(parsed["errors"]), flush=True)
    print(f"trace: {parsed['parsed']}/{parsed['files']} artifact(s), "
          f"window {attrib['window_ms']:.1f} ms", flush=True)
    print(f"{'kernel':<30}{'ops':>7}{'measured':>11}{'model':>11}"
          f"{'frac':>8}{'bound':>7}", flush=True)
    for r in sorted(rows_out, key=lambda r: -r["measured_ms"]):
        model = (f"{r['model_ms']:>9.3f}ms" if r.get("model_ms") is not None
                 else f"{'—':>11}")
        frac = (f"{r['roofline_frac']:>8.4f}"
                if r.get("roofline_frac") is not None else f"{'—':>8}")
        print(f"{r['kernel']:<30}{r['ops']:>7}{r['measured_ms']:>9.3f}ms"
              f"{model}{frac}{r.get('bound', '—'):>7}", flush=True)
    if os.environ.get("PROF_JSON", "") not in ("", "0"):
        print(json.dumps({
            "tool": "prof_kernels", "source": "xprof",
            "trace_dir": trace_dir, "window_ms": attrib["window_ms"],
            "parse_errors": parsed["errors"],
            "kernel_measured": rows_out}))
    return 0


def main() -> int:
    rows = _env_int("PROF_ROWS", 1_000_000)
    F = _env_int("PROF_FEATURES", 28)
    leaves = _env_int("PROF_LEAVES", 255)
    max_bin = _env_int("PROF_MAXBIN", 255)
    n_rep = _env_int("PROF_REPEAT", 3)
    trace_dir = os.environ.get("PROF_TRACE_DIR", "")
    if trace_dir:
        return report_trace(trace_dir, rows, F, leaves, max_bin)
    legs = [s for s in os.environ.get(
        "PROF_LEGS",
        "kernel,kernelpacked,kernelfused,kernelint16,kernelint8,fusedgrad,"
        "full,nofuse,triple,seqapply,nokernel,gathers,partition"
    ).split(",") if s]
    pf, pb = device_peaks()
    print(f"backend: {jax.default_backend()}  interpret: {INTERP}  "
          f"peaks: {pf / 1e12:.1f} TFLOP/s, {pb / 1e9:.0f} GB/s",
          flush=True)
    p = build_problem(rows, F, leaves, max_bin)
    results = {}
    failed = {}
    run = {
        "kernel": lambda: leg_kernel(p, results, n_rep),
        "kernelpacked": lambda: leg_kernel(
            p, results, n_rep, name="kernel packed", packed=True),
        "kernelfused": lambda: leg_kernel(
            p, results, n_rep, name="kernel packed+fused", packed=True,
            fused=True),
        "kernelint16": lambda: leg_kernel(
            p, results, n_rep, name="kernel int16", packed=True,
            fused=True, mode="int16"),
        "kernelint8": lambda: leg_kernel(
            p, results, n_rep, name="kernel int8", packed=True,
            fused=True, mode="int8"),
        "fusedgrad": lambda: leg_fusedgrad(p, results, n_rep),
        "full": lambda: leg_grow(p, results, "grow full", n_rep),
        "nofuse": lambda: leg_grow(p, results, "grow nofuse", n_rep,
                                   fused=False),
        "triple": lambda: leg_grow(p, results, "grow triple", n_rep,
                                   packed=False, fused=False),
        "seqapply": lambda: leg_grow(p, results, "grow seqapply", n_rep,
                                     batched_apply=False),
        "nokernel": lambda: leg_grow(p, results, "grow nokernel", n_rep,
                                     stub_kernel=True),
        "gathers": lambda: leg_gathers(p, results, n_rep),
        "partition": lambda: leg_partition(p, results, n_rep),
        "variants": lambda: leg_variants(p, results, failed),
    }
    for leg in legs:
        try:
            if leg not in run:
                raise ValueError(f"unknown leg {leg!r}")
            run[leg]()
        except Exception as exc:  # noqa: BLE001 — leg by leg, exit 1 below
            _leg_failed(failed, leg, exc)

    # the split-scan hypothesis (ROOFLINE.md step 3): expected non-kernel
    # floor from the analytical scan cost alone
    sf, sb = split_scan_cost(F, p["B"], leaves=2 * p["capacity"])
    print(f"split-scan model (per wave, 2P leaves): "
          f"{roofline_seconds(sf, sb) * 1e3:.3f} ms", flush=True)
    oh = hist_onehot_cost(rows, F, p["B"])
    print(f"XLA one-hot fallback roofline (same pass): "
          f"{roofline_seconds(*oh) * 1e3:.3f} ms", flush=True)

    if os.environ.get("PROF_JSON", "") not in ("", "0"):
        print(json.dumps({
            "tool": "prof_kernels", "backend": jax.default_backend(),
            "interpret": INTERP, "rows": rows, "features": F,
            "leaves": leaves, "max_bin": max_bin, "mode": MODE,
            "peak_flops": pf, "peak_bw": pb, "legs": results,
            "failed": failed}))
    if failed:
        print(f"prof_kernels: {len(failed)} leg(s) failed: "
              + ", ".join(failed), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
