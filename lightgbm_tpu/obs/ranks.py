"""Per-rank training aggregation: live straggler detection.

The reference keeps its Network layer introspectable per rank; this
module is the JAX-graft analog for the training loop.  Two pieces:

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

Everything here is host-side and allocation-light: the per-iteration
work is a few float adds; the exchange rides an already-scheduled
collective.  obs/board.py renders the live skew table on ``/metrics``.
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

