"""The device every number of a run comes from, and the table of peaks."""
from __future__ import annotations

import os
import sys

from .cells import load_json

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def require_devices(chips: int, rehearsal: bool) -> list:
    """``jax.devices()`` if they are what the cell asks for: a TPU with
    exactly ``chips`` chips.  Anything else ends the run non-zero before a
    result can be printed; a rehearsal takes the CPU's (virtual) devices."""
    import jax
    devs = jax.devices()
    plat = devs[0].platform
    if rehearsal:
        if len(devs) < chips:
            sys.exit(f"benchmark: rehearsal needs {chips} devices, "
                     f"{len(devs)} visible")
        return devs[:chips]
    if plat != "tpu":
        sys.exit(f"benchmark: needs a TPU; JAX found platform={plat!r} "
                 f"({devs[0].device_kind})")
    if len(devs) != chips:
        sys.exit(f"benchmark: the cell asks for {chips} chip(s), "
                 f"{len(devs)} visible")
    return devs


def describe(devices: list) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_parts(devices: list) -> dict:
    """The fullest chip's peak, in the two parts the TPU runtime keeps apart:
    ``peak_bytes_in_use`` (live buffers: bins, scores, labels, outputs; the
    figure the on-chip guide names) and ``peak_bytes_reserved`` (the scratch
    the loaded programs reserve for their temporaries, which ``bytes_in_use``
    leaves out: a program whose ``memory_analysis()`` says 64,347,136 temp
    bytes moved ``bytes_reserved`` by 64,176,128 and ``bytes_in_use`` not at
    all; chip run, PR 22).  Both are reported, each under its own name;
    ``peak_bytes``, their sum, is what the chip cannot give to anything else
    while the run holds it, and is the line's ``memory_peak_bytes``.  PERF.md
    4 says what that means for the memory floor.  Zeros where the backend
    keeps no statistics (the CPU)."""
    best = {"peak_bytes_in_use": 0, "peak_bytes_reserved": 0, "peak_bytes": 0}
    for d in devices:
        st = d.memory_stats() or {}
        use = int(st.get("peak_bytes_in_use", 0))
        res = int(st.get("peak_bytes_reserved", 0))
        if use + res > best["peak_bytes"]:
            best = {"peak_bytes_in_use": use, "peak_bytes_reserved": res,
                    "peak_bytes": use + res}
    return best


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip.  A device that is not in the table is an
    error, not a default."""
    table = load_json(_PEAKS)
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"{os.path.basename(_PEAKS)}")
    return table[device_kind]
