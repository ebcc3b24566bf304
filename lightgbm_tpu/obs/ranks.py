"""Per-rank training aggregation: live straggler detection + the
measured-vs-model reconciliation scorer (ISSUE 17).

The reference keeps its Network layer introspectable per rank; this
module is the JAX-graft analog for the training loop.  Three pieces:

- :class:`StragglerDetector` — pure streak logic over a
  ``[num_ranks, num_phases]`` per-iteration wall matrix: a rank whose
  phase wall exceeds the fleet median by ``tpu_straggler_factor`` for
  ``tpu_straggler_iters`` consecutive iterations is a straggler.
- :class:`RankAggregator` — accumulates this rank's per-iteration phase
  deltas and, on the fingerprint cadence, exchanges the window sums over
  the existing host collectives (``parallel/distributed.
  train_stats_exchange`` — piggybacked, so no new sync points).  Rank 0
  runs the detector, emits the ``straggler`` event (rank + phase + skew
  ratio stamped) and dumps the flight recorder — direction 2's "lost
  host" as telemetry instead of a silent stall.
- :class:`Reconciler` — scores each iteration's measured phase times
  against the analytic cost models (``wave_kernel_cost``,
  ``partition_cost``, ``rank_pair_cost``) into a ``reconciliation``
  event, so a TPU window self-attributes where docs/ROOFLINE.md's model
  is wrong without a manual ``prof_kernels`` session.

Everything here is host-side and allocation-light: the per-iteration
work is a few float adds; the exchange rides an already-scheduled
collective.  obs/board.py renders the live skew table and the last
reconciliation row on ``/metrics``.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence

from . import core

# the phases the straggler detector watches: hist/split wall lives in
# "tree growth", gradient work in "boosting (grad/hess)" — the two
# device-bound legs a wedged or slow host shows up in first (the valid
# scoring leg is optional per run, so skew there is config, not fault)
PHASES = ("boosting (grad/hess)", "tree growth")

# below this per-iteration median wall (seconds) a phase is noise — a
# 2x ratio over microseconds is measurement jitter, not a straggler
_MIN_MEDIAN_S = 1e-4


def _median(xs: Sequence[float]) -> float:
    s = sorted(xs)
    n = len(s)
    if not n:
        return 0.0
    m = n // 2
    return s[m] if n % 2 else 0.5 * (s[m - 1] + s[m])


class StragglerDetector:
    """Streak logic over per-rank, per-phase iteration walls.

    ``update(means, window_iters, iteration)`` takes the fleet's
    per-iteration mean wall matrix for the window just exchanged
    (``means[rank][phase_idx]`` seconds) plus how many iterations the
    window covered, and returns the breaches that *crossed* the
    consecutive-iterations threshold on this update (each streak emits
    once; recovery resets it so a relapse emits again).
    """

    def __init__(self, factor: float, iters: int,
                 phases: Sequence[str] = PHASES):
        self.factor = float(factor)
        self.iters = max(int(iters), 1)
        self.phases = tuple(phases)
        self._streak: Dict[tuple, int] = {}   # (rank, phase) -> iters
        self._emitted: set = set()            # streaks already reported

    def update(self, means: Sequence[Sequence[float]], window_iters: int,
               iteration: int) -> List[dict]:
        breaches = []
        window_iters = max(int(window_iters), 1)
        for pi, phase in enumerate(self.phases):
            col = [float(row[pi]) for row in means]
            med = _median(col)
            if med < _MIN_MEDIAN_S:
                for r in range(len(col)):
                    self._streak.pop((r, phase), None)
                    self._emitted.discard((r, phase))
                continue
            for r, wall in enumerate(col):
                key = (r, phase)
                if wall > self.factor * med:
                    streak = self._streak.get(key, 0) + window_iters
                    self._streak[key] = streak
                    if streak >= self.iters and key not in self._emitted:
                        self._emitted.add(key)
                        breaches.append({
                            "rank": r,
                            "phase": phase,
                            "iteration": int(iteration),
                            "ratio": round(wall / med, 4),
                            "median_s": round(med, 6),
                            "rank_s": round(wall, 6),
                            "consecutive": int(streak),
                            "breach": True,
                        })
                else:
                    self._streak.pop(key, None)
                    self._emitted.discard(key)
        return breaches


# live skew table for the board: the last exchanged per-rank,
# per-iteration phase walls — written by the train thread on each
# exchange, read by the exporter's HTTP thread
_skew_lock = threading.Lock()
_skew: dict = {}


def skew_table() -> dict:
    """Last exchanged skew snapshot: ``{"iteration": n, "window_iters":
    k, "ranks": {rank: {phase: per_iter_s}}, "stragglers": [...]}`` —
    empty before the first multi-process exchange."""
    with _skew_lock:
        return dict(_skew)


def _reset_skew() -> None:
    with _skew_lock:
        _skew.clear()


core._register_reset(_reset_skew)


class RankAggregator:
    """Accumulate this rank's phase walls; exchange + detect on the
    fingerprint cadence.  Single-process runs cost one branch per tick
    (``train_stats_exchange`` returns None before any collective)."""

    def __init__(self, factor: float = 2.0, iters: int = 3,
                 phases: Sequence[str] = PHASES):
        self.phases = tuple(phases)
        self.detector = StragglerDetector(factor, iters, self.phases)
        self._win = [0.0] * len(self.phases)
        self._win_iters = 0

    def accumulate(self, phase_s: dict) -> None:
        """Fold one iteration's phase deltas into the open window."""
        for i, p in enumerate(self.phases):
            self._win[i] += float(phase_s.get(p, 0.0) or 0.0)
        self._win_iters += 1

    def exchange(self, iteration: int) -> Optional[List[dict]]:
        """Exchange the open window across ranks (non-blocking w.r.t.
        extra sync points: rides the fingerprint tick, which already
        synchronizes).  Returns the breaches rank 0 detected, None when
        single-process or the window is empty."""
        if not self._win_iters:
            return None
        vec = list(self._win) + [float(self._win_iters)]
        self._win = [0.0] * len(self.phases)
        self._win_iters = 0
        from ..parallel.distributed import train_stats_exchange
        mat = train_stats_exchange(vec)
        if mat is None:
            return None
        rows = [[float(v) for v in row] for row in mat]
        means = [[w / max(row[-1], 1.0) for w in row[:-1]] for row in rows]
        window_iters = int(max(r[-1] for r in rows))
        table = {r: {p: round(means[r][pi], 6)
                     for pi, p in enumerate(self.phases)}
                 for r in range(len(means))}
        breaches = self.detector.update(means, window_iters, iteration)
        with _skew_lock:
            _skew.clear()
            _skew.update(iteration=int(iteration),
                         window_iters=window_iters, ranks=table,
                         stragglers=list(breaches))
        if core._process_index() != 0:
            return breaches
        for b in breaches:
            core.event("straggler", **b)
            from . import spans
            if spans.flight_enabled():
                spans.flight_dump(
                    f"straggler:rank{b['rank']}",
                    extra={"straggler": b, "skew": table})
        return breaches


class Reconciler:
    """Score one iteration's measured phase walls against the analytic
    cost models — the ``reconciliation`` event's ``units`` map, where
    each unit carries ``measured_s`` / ``modeled_s`` / ``ratio``
    (measured over modeled: >> 1 means the roofline model is
    optimistic for that unit on this backend).  All inputs are
    best-effort: a unit whose model inputs are missing is skipped, not
    guessed."""

    def __init__(self):
        self._peaks = None

    def _roofline(self, flops: float, nbytes: float) -> float:
        from .profile import device_peaks, roofline_seconds
        if self._peaks is None:
            self._peaks = device_peaks()
        return roofline_seconds(flops, nbytes, self._peaks)

    @staticmethod
    def _unit(measured: float, modeled: float) -> Optional[dict]:
        if modeled <= 0 or measured < 0:
            return None
        return {"measured_s": round(measured, 6),
                "modeled_s": round(modeled, 6),
                "ratio": round(measured / modeled, 4)}

    def score(self, *, phase_s: dict, iter_s: float, N: int,
              kern_rows=None, kern_pass_rows=None, waves=None,
              wave_cost_args=None,
              splits: int = 0, passes: Optional[int] = None,
              rank_sizes=None) -> Optional[dict]:
        units = {}
        growth = float(phase_s.get("tree growth", iter_s) or 0.0)
        modeled_growth = 0.0
        if kern_rows and kern_rows > 0 and wave_cost_args:
            try:
                from ..ops.pallas_hist import wave_kernel_cost
                Fk, Bk, mode, packed_k, fused_k = wave_cost_args
                flops, nbytes = wave_kernel_cost(
                    kern_rows, Fk, Bk, mode, waves=waves or 1,
                    packed=packed_k, fused=fused_k,
                    pass_rows=kern_pass_rows)
                modeled = self._roofline(flops, nbytes)
                modeled_growth += modeled
                u = self._unit(growth, modeled)
                if u:
                    units["wave_kernel"] = u
            except Exception:  # noqa: BLE001 — scoring must not fail train
                pass
        if splits > 0:
            try:
                from ..core.splitter import partition_cost
                pflops, pbytes = partition_cost(
                    int(N), splits=int(splits), passes=passes)
                modeled = self._roofline(pflops, pbytes)
                modeled_growth += modeled
                u = self._unit(growth, modeled)
                if u:
                    units["partition"] = u
            except Exception:  # noqa: BLE001
                pass
        if modeled_growth > 0:
            # the combined growth-phase verdict: measured wall over the
            # SUM of the in-phase unit models — the single number the
            # digest's reconciliation table leads with
            u = self._unit(growth, modeled_growth)
            if u:
                units["tree_growth"] = u
        if rank_sizes is not None and len(rank_sizes):
            try:
                from ..ops.rank import rank_pair_cost
                rflops, rbytes = rank_pair_cost(rank_sizes)
                boosting = float(
                    phase_s.get("boosting (grad/hess)", 0.0) or 0.0)
                u = self._unit(boosting, self._roofline(rflops, rbytes))
                if u:
                    units["rank_pair"] = u
            except Exception:  # noqa: BLE001
                pass
        return units or None

    def score_shap(self, measured_s: float, *, N: int, T: int, L: int,
                   P: int, F: int, K: int = 1) -> Optional[dict]:
        """Score a TreeSHAP contribution pass against ``ops/treeshap.
        shap_cost`` — the explain plane's unit of the reconciliation
        table (emitted from the trainer's ``pred_contrib`` path, where
        the batched scan is host-bracketed)."""
        try:
            from ..ops.treeshap import shap_cost
            flops, nbytes = shap_cost(N, T, L, P, F, K)
            return self._unit(float(measured_s),
                              self._roofline(flops, nbytes))
        except Exception:  # noqa: BLE001 — scoring must not fail predict
            return None

    def score_measured(self, rows) -> Optional[dict]:
        """Fold ``kernel_measured`` rows (obs/xprof.py) into the same
        ``units`` shape ``score`` emits — one unit per trace-attributed
        kernel that carries a model join.  Where ``score`` ratios a
        coarse host phase wall against the models, this ratios the
        per-kernel trace truth: the two agreeing is the cost model
        validated end to end; diverging, the phase wall is hiding
        dispatch gaps or unattributed work."""
        units = {}
        for row in rows or ():
            model_ms = row.get("model_ms")
            if not model_ms:
                continue
            u = self._unit(float(row.get("measured_ms", 0.0)) / 1e3,
                           float(model_ms) / 1e3)
            if u:
                units[row.get("kernel", "?")] = u
        return units or None
