"""HBM memory census: live-byte attribution, peak tracking, release audit.

Training's device footprint is a handful of logical buffers — the binned
matrix (the feature-major resident copy), grad/hess vectors and their
word rows, the per-leaf histogram stack, the streamed tier's scratch,
train/valid scores, and the stacked forest for device prediction.  ``snapshot`` attributes
``jax.live_arrays()`` bytes to whichever of those the caller names,
reports the unattributed remainder, folds in ``device.memory_stats()``
where the backend provides it (TPU does; CPU returns None and the
live-array sum stands in), and tracks the peak across the run.

The release audit is the donation check: a caller registers a buffer it
expects a phase to CONSUME (donated into a jit, or simply dropped when
the new value lands) via ``expect_released``; ``audit`` then warns when
the buffer survived — an extra reference pinning HBM that the schedule
believed was free.

All entry points no-op unless telemetry or profile mode is on; events
additionally need a telemetry sink (``core.event`` gates), but peak
tracking works sink-less so ``bench.py`` can embed the figure from
``obs.digest()`` alone.
"""
from __future__ import annotations

import sys
import weakref
from typing import Dict, List, Optional, Tuple

from ..utils import log
from . import core

_peak_bytes = 0
_peak_phase = ""
_phase_peaks: Dict[str, int] = {}   # phase name -> max live bytes at exit
_expected: List[tuple] = []         # (name, weakref, registered-phase)
_warned_survivors = set()
_snapshots = 0


def _active() -> bool:
    from . import profile
    return core.enabled() or profile.profile_enabled()


def _tree_bytes(buf) -> int:
    """Total nbytes across a buffer pytree (arrays, tuples of arrays)."""
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(buf):
        nb = getattr(leaf, "nbytes", None)
        if nb is not None:
            total += int(nb)
    return total


def _device_stats() -> Tuple[Optional[int], Optional[int]]:
    """(bytes_in_use, peak_bytes_in_use) summed over local devices, or
    (None, None) when the backend has no allocator stats (CPU)."""
    jx = sys.modules.get("jax")
    if jx is None:
        return None, None
    in_use = peak = None
    try:
        for d in jx.local_devices():
            st = d.memory_stats()
            if not st:
                continue
            in_use = (in_use or 0) + int(st.get("bytes_in_use", 0))
            peak = (peak or 0) + int(st.get("peak_bytes_in_use",
                                            st.get("bytes_in_use", 0)))
    except Exception:  # noqa: BLE001 — stats are best-effort everywhere
        return None, None
    return in_use, peak


def _live_total() -> Tuple[int, int]:
    """(total bytes, array count) over ``jax.live_arrays()``."""
    jx = sys.modules.get("jax")
    if jx is None:
        return 0, 0
    try:
        live = jx.live_arrays()
    except Exception:  # noqa: BLE001
        return 0, 0
    return sum(int(getattr(a, "nbytes", 0)) for a in live), len(live)


def _note_peak(nbytes: int, phase: str) -> None:
    global _peak_bytes, _peak_phase
    if nbytes > _peak_bytes:
        _peak_bytes = nbytes
        _peak_phase = phase


def snapshot(phase: str, buffers: Optional[dict] = None) -> Optional[dict]:
    """One census point: attribute live bytes to the named logical
    buffers, record device allocator stats, update the peak, and emit a
    ``memory_census`` event.  Returns the record (None when inactive)."""
    global _snapshots
    if not _active():
        return None
    import jax
    attributed = {}
    seen = set()  # logical names may alias one device array; count once
    for name, buf in (buffers or {}).items():
        if buf is None:
            continue
        nb = 0
        for leaf in jax.tree_util.tree_leaves(buf):
            b = getattr(leaf, "nbytes", None)
            if b is not None and id(leaf) not in seen:
                seen.add(id(leaf))
                nb += int(b)
        if nb:
            attributed[name] = nb
    live_bytes, live_count = _live_total()
    dev_in_use, dev_peak = _device_stats()
    basis = dev_in_use if dev_in_use is not None else live_bytes
    _note_peak(max(basis, dev_peak or 0), phase)
    _snapshots += 1
    rec = {
        "phase": phase,
        "buffers": attributed,
        "live_bytes": live_bytes,
        "live_count": live_count,
        "unattributed_bytes": max(live_bytes - sum(attributed.values()), 0),
        "peak_bytes": _peak_bytes,
    }
    if dev_in_use is not None:
        rec["device_bytes_in_use"] = dev_in_use
        rec["device_peak_bytes"] = dev_peak
    core.event("memory_census", **rec)
    return rec


def phase_probe(phase: str) -> None:
    """Lightweight per-phase-exit hook (installed by ``core.phase`` while
    profile mode is on): tracks per-phase live-byte peaks without the
    full attribution/event cost of ``snapshot``."""
    live_bytes, _ = _live_total()
    dev_in_use, dev_peak = _device_stats()
    basis = dev_in_use if dev_in_use is not None else live_bytes
    if basis > _phase_peaks.get(phase, 0):
        _phase_peaks[phase] = basis
    _note_peak(max(basis, dev_peak or 0), phase)


def expect_released(name: str, arr) -> None:
    """Register ``arr`` as a buffer the current phase should consume —
    the next ``audit`` warns if it is still alive (neither garbage
    collected nor donation-deleted).

    Re-registering a name REPLACES the pending entry: a stop path that
    returns before its audit leaves a stale registration behind, and a
    later run (another booster in the same process) must not report that
    earlier, legitimately-alive buffer as its own leak."""
    if not _active() or arr is None:
        return
    try:
        ref = weakref.ref(arr)
    except TypeError:
        return
    _expected[:] = [e for e in _expected if e[0] != name]
    _expected.append((name, ref, core.current_phase()))


def audit(phase: str = "") -> List[str]:
    """Check every registered release expectation; returns the survivor
    names.  Survivors warn once per buffer name and emit a
    ``donation_audit`` event — an extra reference is pinning HBM the
    schedule expected back."""
    if not _expected:
        return []
    survivors = []
    for name, ref, reg_phase in _expected:
        a = ref()
        if a is None:
            continue
        deleted = False
        try:
            deleted = bool(a.is_deleted())
        except Exception:  # noqa: BLE001
            pass
        if not deleted:
            survivors.append(name)
            if name not in _warned_survivors:
                _warned_survivors.add(name)
                log.warning(
                    "memory census: buffer %r (%s bytes, registered in "
                    "phase %r) survived phase %r — an extra reference is "
                    "pinning HBM that was expected to be released",
                    name, _tree_bytes(a), reg_phase, phase)
    _expected.clear()
    if survivors:
        core.event("donation_audit", phase=phase, survivors=survivors,
                   survived=True)
    return survivors


def peak_bytes() -> int:
    """Peak observed device bytes (allocator peak where available, else
    the live-array sum) across all snapshots/probes so far."""
    return _peak_bytes


def memory_digest() -> dict:
    """Census summary for ``obs.digest()`` (empty when nothing probed)."""
    if not _snapshots and not _phase_peaks:
        return {}
    out = {"peak_bytes": _peak_bytes, "peak_phase": _peak_phase,
           "snapshots": _snapshots}
    if _phase_peaks:
        out["phase_peak_bytes"] = dict(sorted(_phase_peaks.items()))
    return out


def reset_memory() -> None:
    global _peak_bytes, _peak_phase, _snapshots
    _peak_bytes = 0
    _peak_phase = ""
    _snapshots = 0
    _phase_peaks.clear()
    _expected.clear()
    _warned_survivors.clear()


core._register_reset(reset_memory)
