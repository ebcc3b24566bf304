"""Published device peaks and the roofline floor they give.

``chip_smoke.py`` prices its kernel checks against these, and
``docs/ROOFLINE.md``'s hand-written model uses the same figures.
"""
from __future__ import annotations

import os
from typing import Optional, Tuple

from ..utils import log

# device_kind (exactly as ``jax.devices()[0].device_kind`` reports it) ->
# (peak bf16 FLOP/s, peak HBM bytes/s) per device.  The histogram kernels
# run bf16 MXU passes, so the matmul peak is the bf16 one (the v5e's
# 394e12 is its int8 rate).  Source: Google Cloud TPU documentation,
# system-architecture pages (v5e: 197 TFLOP/s bf16, 819 GB/s HBM);
# jax/_src/pallas/mosaic/tpu_info.py carries the same figures.  A device
# that is not in the table is an error, not a default.  The "cpu" row is
# a nominal figure that lets CI exercise the arithmetic on the test
# backend; a roofline fraction computed against it means nothing.
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
            f"device_kind {d.device_kind!r} is not in obs/peaks.py "
            f"DEVICE_PEAKS ({sorted(DEVICE_PEAKS)}); add its published "
            "peaks with their source, or set both LGBM_TPU_PEAK_FLOPS "
            "and LGBM_TPU_PEAK_BW")
    return (float(env_f) if env_f else base[0],
            float(env_b) if env_b else base[1])


def roofline_seconds(flops: float, nbytes: float,
                     peaks: Optional[Tuple[float, float]] = None) -> float:
    """Analytical floor time: the slower of the compute and memory legs."""
    pf, pb = peaks if peaks is not None else device_peaks()
    return max(flops / pf if pf else 0.0, nbytes / pb if pb else 0.0)
