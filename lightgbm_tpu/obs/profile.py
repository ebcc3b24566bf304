"""Profile mode: per-kernel cost attribution against the analytical roofline.

The telemetry layer (``core``) records *what* happened per iteration; this
module explains *why it is slow*.  With the gate on (``LGBM_TPU_PROFILE=1``
or the ``tpu_profile`` parameter) every profiled compiled program — the
jitted units the trainer dispatches, each named after the ``lgbm/*`` scope
it wraps — is:

- **sync-bracketed**: ``block_until_ready`` after every call, so the
  measured time is device compute, not enqueue (this deliberately breaks
  the training loop's async pipelining — profile mode is for attribution
  runs, never for benchmark numbers);
- **cost-analyzed**: FLOPs and bytes-accessed come from XLA's own
  ``lowered.compile().cost_analysis()``, cached per input signature;
- **roofline-scored**: achieved time is compared against
  ``max(flops/peak_flops, bytes/peak_bw)`` for the local device (peaks
  from the table below, overridable via env), and a ``kernel_profile``
  event carries the fraction — ``docs/ROOFLINE.md``'s hand-written model,
  machine-checked on every run.

Everything is OFF-path when disabled: ``wrap`` returns its argument
unchanged, so the hot loop sees zero new code.  Events only reach disk
when a telemetry sink is configured (``core.event`` gates); without one,
the per-kernel aggregates still accumulate and surface in
``obs.digest()`` (which ``bench.py`` embeds).
"""
from __future__ import annotations

import os
import sys
import time
from typing import Dict, Optional, Tuple

from ..utils import log
from . import core

# device_kind (exactly as ``jax.devices()[0].device_kind`` reports it) ->
# (peak bf16 FLOP/s, peak HBM bytes/s) per device.  The histogram kernels
# run bf16 MXU passes, so the matmul peak is the bf16 one (the v5e's
# 394e12 is its int8 rate).  Source: Google Cloud TPU documentation,
# system-architecture pages (v5e: 197 TFLOP/s bf16, 819 GB/s HBM);
# jax/_src/pallas/mosaic/tpu_info.py carries the same figures.  A device
# that is not in the table is an error, not a default.  The "cpu" row is
# a nominal figure that lets CI exercise the profile machinery on the
# test backend; a roofline fraction computed against it means nothing.
DEVICE_PEAKS = {
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5p": (459e12, 2765e9),
    "TPU v5": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v6e": (918e12, 1640e9),
    "cpu": (100e9, 20e9),
}

_env = os.environ.get("LGBM_TPU_PROFILE", "")
_on = _env not in ("", "0", "false")

_agg: Dict[str, dict] = {}   # kernel name -> aggregate record
_ca_warned = set()


def profile_enabled() -> bool:
    """True when profile mode is on (env LGBM_TPU_PROFILE or enable)."""
    return _on


_announced = False


def enable_profile(on: bool = True) -> None:
    """Flip the PROCESS-WIDE profile gate (same scope as the telemetry
    sink: ``tpu_profile`` on one Booster leaves it on for every later
    Booster until ``enable_profile(False)``).  Takes effect for boosters
    built AFTER the flip — instrumentation is decided when the jitted
    closures are wrapped at Booster init, not per call."""
    global _on, _announced
    _on = bool(on)
    core._set_profile_active(_on)
    if _on and not _announced:
        _announced = True
        log.info("profile mode ON for the rest of the process: every "
                 "phase/kernel is sync-bracketed (async dispatch "
                 "disabled) — do not read throughput numbers from this "
                 "run; obs.enable_profile(False) turns it off")


def device_peaks(device=None) -> Tuple[float, float]:
    """(peak FLOP/s, peak HBM bytes/s) for ``device`` (default: local
    device 0), from ``DEVICE_PEAKS``.  ``LGBM_TPU_PEAK_FLOPS`` /
    ``LGBM_TPU_PEAK_BW`` override the table (each independently); a
    ``device_kind`` that is neither in the table nor fully overridden
    raises — a roofline against guessed peaks is worse than none."""
    env_f = os.environ.get("LGBM_TPU_PEAK_FLOPS", "")
    env_b = os.environ.get("LGBM_TPU_PEAK_BW", "")
    if env_f and env_b:
        return float(env_f), float(env_b)
    import jax
    d = device if device is not None else jax.devices()[0]
    base = DEVICE_PEAKS.get(str(d.device_kind))
    if base is None:
        raise log.LightGBMError(
            f"device_kind {d.device_kind!r} is not in obs/profile.py "
            f"DEVICE_PEAKS ({sorted(DEVICE_PEAKS)}); add its published "
            "peaks with their source, or set both LGBM_TPU_PEAK_FLOPS "
            "and LGBM_TPU_PEAK_BW")
    return (float(env_f) if env_f else base[0],
            float(env_b) if env_b else base[1])


def device_kind() -> str:
    jx = sys.modules.get("jax")
    if jx is None:
        return "unknown"
    try:
        return str(jx.devices()[0].device_kind)
    except Exception:  # noqa: BLE001
        return "unknown"


def roofline_seconds(flops: float, nbytes: float,
                     peaks: Optional[Tuple[float, float]] = None) -> float:
    """Analytical floor time: the slower of the compute and memory legs."""
    pf, pb = peaks if peaks is not None else device_peaks()
    return max(flops / pf if pf else 0.0, nbytes / pb if pb else 0.0)


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` as a plain dict (None -> empty)."""
    return dict(compiled.cost_analysis() or {})


def extract_cost(ca: dict) -> Tuple[float, float]:
    """(flops, bytes accessed) from an XLA cost-analysis dict."""
    return (float(ca.get("flops", 0.0) or 0.0),
            float(ca.get("bytes accessed", 0.0) or 0.0))


def _sig(args, kwargs):
    import jax
    leaves, _ = jax.tree_util.tree_flatten((args, kwargs))
    out = []
    for x in leaves:
        shp = getattr(x, "shape", None)
        if shp is not None:
            out.append((tuple(shp), str(getattr(x, "dtype", ""))))
        else:
            out.append(repr(x))
    return tuple(out)


def record_kernel(name: str, flops: float, nbytes: float, achieved_s: float,
                  phase: str = None, **extra) -> None:
    """Fold one kernel execution into the aggregates + emit its
    ``kernel_profile`` event.  Also the entry point for ANALYTICAL
    attributions (kernels fused inside a larger program whose work is
    known from the model, e.g. the wave kernel's rows-histogrammed count —
    pass ``source="analytical"``).  ``phase`` overrides the phase
    attribution for callers emitting outside the phase timer that did the
    work (the per-iteration analytical records)."""
    rf = roofline_seconds(flops, nbytes)
    frac = rf / achieved_s if achieved_s > 0 else 0.0
    a = _agg.get(name)
    if a is None:
        a = _agg[name] = {"calls": 0, "achieved_s": 0.0, "flops": 0.0,
                          "bytes": 0.0, "roofline_s": 0.0, "best_frac": 0.0}
    a["calls"] += 1
    a["achieved_s"] += achieved_s
    a["flops"] += flops
    a["bytes"] += nbytes
    a["roofline_s"] += rf
    a["best_frac"] = max(a["best_frac"], frac)
    core.event("kernel_profile", kernel=name,
               phase=phase if phase is not None else core.current_phase(),
               flops=flops, bytes=nbytes, achieved_s=round(achieved_s, 6),
               roofline_s=round(rf, 9), roofline_frac=round(frac, 6),
               device=device_kind(), **extra)


class _Profiled:
    """Sync-bracketing, cost-analyzing wrapper around one jitted callable.

    The cost analysis is cached per input signature (shapes/dtypes/static
    values), so steady-state calls pay one time read + one sync — exactly
    the bracketing profile mode promises."""

    __slots__ = ("name", "fn", "_costs")

    def __init__(self, name: str, fn):
        self.name = name
        self.fn = fn
        self._costs: Dict[tuple, Tuple[float, float]] = {}

    def __call__(self, *args, **kwargs):
        if not _on:
            return self.fn(*args, **kwargs)
        import jax
        key = _sig(args, kwargs)
        cost = self._costs.get(key)
        if cost is None:
            try:
                ca = cost_analysis_dict(
                    self.fn.lower(*args, **kwargs).compile())
                cost = extract_cost(ca)
            except Exception as exc:  # noqa: BLE001 — AOT API varies
                if self.name not in _ca_warned:
                    _ca_warned.add(self.name)
                    log.warning("cost_analysis unavailable for %s (%s); "
                                "profiling time only", self.name, exc)
                cost = (0.0, 0.0)
            self._costs[key] = cost
            # warm the jit dispatch cache: the AOT lower().compile()
            # above does NOT populate it, so without this untimed call
            # the first recorded achieved_s would be dominated by
            # trace+compile and poison the roofline aggregates (the fn
            # is pure; the duplicated device work is profile-mode cost)
            jax.block_until_ready(self.fn(*args, **kwargs))
        t0 = time.perf_counter()
        out = self.fn(*args, **kwargs)
        jax.block_until_ready(out)
        record_kernel(self.name, cost[0], cost[1],
                      time.perf_counter() - t0)
        return out


def wrap(name: str, fn):
    """Instrument a jitted callable under ``lgbm/<name>``-style naming.
    Identity when profiling is off — the disabled path costs nothing.

    When the xprof plane is armed, the retrace watcher composes outside
    the profiled wrapper (the wrapper still needs the raw ``lower()``),
    so every ``wrap`` point gets retrace attribution for free."""
    if fn is None:
        return fn
    from . import xprof  # lazy: avoids import work on the off path
    if isinstance(fn, xprof._Watched):  # already fully wrapped
        return fn
    if _on and not isinstance(fn, _Profiled):
        fn = _Profiled(name, fn)
    return xprof.watch_jit(name, fn)


def profile_digest() -> dict:
    """Per-kernel aggregates for ``obs.digest()`` / bench embedding."""
    out = {}
    for name, a in _agg.items():
        ach = a["achieved_s"]
        out[name] = {
            "calls": a["calls"],
            "achieved_s": round(ach, 6),
            "flops": a["flops"],
            "bytes": a["bytes"],
            "roofline_s": round(a["roofline_s"], 9),
            "roofline_frac": round(a["roofline_s"] / ach, 6) if ach else 0.0,
            "best_frac": round(a["best_frac"], 6),
        }
    return out


def reset_profile() -> None:
    _agg.clear()


core._register_reset(reset_profile)
if _on:
    core._set_profile_active(True)
