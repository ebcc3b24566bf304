"""Device-wedge watchdog: classify, retry, stamp stalls, fail safely.

The TPU failure modes this exists for are a backend that stops
answering mid-run and a run that quietly continues on another path.
``DeviceGuard`` wraps the
trainer's synced device dispatch (boosting/gbdt.py) and gives every
failure a deliberate outcome instead of a stack trace at iteration
499/500:

- **classify** — :func:`classify_error` sorts exceptions into
  ``transient`` (UNAVAILABLE / RESOURCE_EXHAUSTED / DEADLINE_EXCEEDED /
  ABORTED — the runtime says "try again") vs ``fatal`` (everything
  else).  :func:`classify_text` applies the same patterns to a
  subprocess's output tail (tools/tpu_window.py reuses it for bench
  legs).
- **retry** — transient failures re-dispatch with bounded exponential
  backoff + DETERMINISTIC jitter (seeded, so a fault-injection replay
  produces the identical schedule).  The guarded closures are
  functional (inputs unread after dispatch), so a retry is a pure
  re-execution.
- **stall** — a ``threading.Timer`` heartbeat stamps a step that blows
  its deadline (explicit ``tpu_wedge_timeout_s``, else 4x the rolling
  per-step p99 with a floor) with a ``device_stall`` event and a flight
  dump.  Advisory by design: Python cannot interrupt a wedged XLA call,
  so the stamp is the post-mortem and the supervisor (SIGTERM handler,
  ``tools/tpu_window.py`` leg timeout) is the kill.
- **fatal** — dump the flight recorder, invoke ``on_fatal`` (the
  trainer's boundary-checkpoint hook), then per ``tpu_on_device_error``:
  ``abort`` raises :class:`DeviceWedgedError`; ``fallback`` re-executes
  the step once under the CPU default device (best-effort — committed
  TPU buffers may still pin the old backend); ``retry`` means transient
  retries first, then abort.

The guard is ACTIVE only when ``tpu_watchdog=true`` or the fault
harness is armed; inactive it forwards the call untouched (no extra
sync), so default runs keep their async pipelining.
"""
from __future__ import annotations

import re
import threading
import time
from collections import deque
from typing import Callable, Optional

import numpy as np

from ..utils import log
from . import faults


class DeviceWedgedError(RuntimeError):
    """A device step failed fatally (or exhausted its retries) and the
    policy said abort.  By the time this propagates the flight recorder
    has dumped and the boundary checkpoint hook has run."""


# substrings that mark a failure as transient — the gRPC/absl status
# names the TPU runtime uses for "the hardware/runtime hiccupped, the
# program is fine" (plus the injection harness's own marker)
_TRANSIENT_PATTERNS = (
    "UNAVAILABLE", "RESOURCE_EXHAUSTED", "DEADLINE_EXCEEDED", "ABORTED",
    "CANCELLED", "UNKNOWN: ", "injected transient",
    "socket closed", "connection reset", "network error",
)

# output-tail substrings that mark a SUBPROCESS bench leg as wedged /
# retryable (tools/tpu_window.py); a plain assertion failure matches
# none of these and is never retried
_WEDGE_TEXT_PATTERNS = _TRANSIENT_PATTERNS + (
    "timed out", "backend wedge", "heartbeat", "hbm oom",
    "failed to connect", "tpu initialization",
)


def classify_error(exc: BaseException) -> str:
    """``'transient'`` or ``'fatal'`` for an in-process exception."""
    if isinstance(exc, faults.FaultTransient):
        return "transient"
    if isinstance(exc, faults.FaultInjected):
        return "fatal"
    msg = f"{type(exc).__name__}: {exc}"
    low = msg.lower()
    for pat in _TRANSIENT_PATTERNS:
        if pat.lower() in low:
            return "transient"
    return "fatal"


def classify_text(text: str, timed_out: bool = False) -> Optional[str]:
    """Classify a subprocess output tail: ``'wedge'`` (timeout / hang),
    ``'transient'`` (retryable runtime error), or None (a real failure
    that retrying would only repeat)."""
    if timed_out:
        return "wedge"
    low = (text or "").lower()
    for pat in _WEDGE_TEXT_PATTERNS:
        if pat.lower() in low:
            return "transient"
    return None


def backoff_delays(retries: int, base_s: float = 0.05, cap_s: float = 2.0,
                   seed: int = 0) -> list:
    """The full deterministic backoff schedule: ``base * 2^k`` capped,
    plus up to 25% seeded jitter (decorrelates a fleet of workers
    retrying the same wedge without sacrificing replayability)."""
    rng = np.random.default_rng(seed)
    return [min(base_s * (2.0 ** k), cap_s) * (1.0 + 0.25 * rng.random())
            for k in range(max(retries, 0))]


class DeviceGuard:
    """Retry/stall/fatal policy around one trainer's device dispatch."""

    def __init__(self, policy: str = "retry", retries: int = 3,
                 backoff_base_s: float = 0.05, backoff_cap_s: float = 2.0,
                 stall_timeout_s: float = 0.0, stall_floor_s: float = 60.0,
                 seed: int = 0, enabled: bool = False,
                 on_fatal: Optional[Callable] = None, name: str = "train"):
        if policy not in ("abort", "fallback", "retry"):
            raise ValueError(f"unknown device-error policy {policy!r}")
        self.policy = policy
        self.retries = max(int(retries), 0)
        self.stall_timeout_s = float(stall_timeout_s)
        self.stall_floor_s = float(stall_floor_s)
        self.enabled = bool(enabled)
        self.on_fatal = on_fatal
        self.name = name
        self._delays = backoff_delays(self.retries, backoff_base_s,
                                      backoff_cap_s, seed)
        self._durations: deque = deque(maxlen=64)
        self._lock = threading.Lock()
        self.retry_count = 0
        self.stall_count = 0

    @property
    def active(self) -> bool:
        """The guard engages when armed explicitly (``tpu_watchdog``) or
        when the fault harness is live — otherwise ``run`` is a passthrough
        and the training loop keeps async dispatch."""
        return self.enabled or faults.armed()

    # ------------------------------------------------------------------
    def _deadline_s(self) -> float:
        """Stall deadline: explicit timeout wins (negative disables the
        heartbeat); else 4x the rolling per-step p99 once enough steps
        are measured, floored so early iterations (compiles!) never
        false-positive."""
        if self.stall_timeout_s < 0:
            return 0.0
        if self.stall_timeout_s > 0:
            return self.stall_timeout_s
        with self._lock:
            samples = sorted(self._durations)
        if len(samples) >= 8:
            p99 = samples[min(int(np.ceil(0.99 * len(samples))) - 1,
                              len(samples) - 1)]
            return max(4.0 * p99, self.stall_floor_s)
        return self.stall_floor_s

    def snapshot(self) -> dict:
        """Scrapeable retry/stall state for the live train board
        (obs/board.py provider hook).  ``_deadline_s`` takes the lock
        itself, so it is resolved BEFORE the state read — never while
        holding it."""
        deadline = self._deadline_s()
        with self._lock:
            return {
                "active": self.active,
                "policy": self.policy,
                "retries_budget": self.retries,
                "retry_count": self.retry_count,
                "stall_count": self.stall_count,
                "deadline_s": round(deadline, 3),
            }

    def _on_stall(self, point: str, iteration, t0: float,
                  deadline: float) -> None:
        from .. import obs
        with self._lock:
            self.stall_count += 1
        elapsed = time.perf_counter() - t0
        log.warning("%s watchdog: step %r stalled — %.1fs elapsed, "
                    "deadline %.1fs (iteration %s); dumping flight "
                    "recorder (a hung XLA call cannot be interrupted "
                    "from Python — the supervisor owns the kill)",
                    self.name, point, elapsed, deadline, iteration)
        obs.event("device_stall", point=point, elapsed_s=round(elapsed, 3),
                  deadline_s=round(deadline, 3),
                  **({} if iteration is None else {"iteration": iteration}))
        if obs.flight_enabled():
            obs.flight_dump(f"device_stall:{point}")

    # ------------------------------------------------------------------
    def run(self, fn: Callable, point: str = "device_execute",
            iteration: Optional[int] = None):
        """Execute ``fn()`` under the policy.  Inactive: a passthrough.
        Active: injection check, dispatch, block-until-ready (errors must
        surface HERE, not at a later async fetch), retry/fatal
        handling."""
        if not self.active:
            return fn()
        import jax
        attempt = 0
        while True:
            deadline = self._deadline_s()
            t0 = time.perf_counter()
            timer = None
            if deadline > 0:
                timer = threading.Timer(
                    deadline, self._on_stall, (point, iteration, t0,
                                               deadline))
                timer.daemon = True
                timer.start()
            try:
                faults.check(point, iteration=iteration)
                out = jax.block_until_ready(fn())
                with self._lock:
                    self._durations.append(time.perf_counter() - t0)
                return out
            except Exception as exc:  # noqa: BLE001 — the classify point
                cls = classify_error(exc)
                can_retry = (cls == "transient" and attempt < self.retries
                             and self.policy != "abort")
                self._note_retry(point, attempt, cls, exc, can_retry,
                                 iteration)
                if not can_retry:
                    return self._fatal(exc, cls, fn, point, iteration)
                time.sleep(self._delays[attempt])
                attempt += 1
            finally:
                if timer is not None:
                    timer.cancel()

    def _note_retry(self, point, attempt, cls, exc, will_retry,
                    iteration) -> None:
        from .. import obs
        with self._lock:
            self.retry_count += 1
        action = ("retry" if will_retry
                  else "fallback" if self.policy == "fallback" else "abort")
        delay = (round(self._delays[attempt] * 1e3, 3)
                 if will_retry else None)
        log.warning("%s watchdog: %s failure at %r (attempt %d): %s — %s%s",
                    self.name, cls, point, attempt + 1,
                    f"{type(exc).__name__}: {exc}", action,
                    f" in {delay}ms" if delay is not None else "")
        fields = dict(point=point, attempt=attempt, classify=cls,
                      action=action, error=f"{type(exc).__name__}: {exc}")
        if delay is not None:
            fields["delay_ms"] = delay
        if iteration is not None:
            fields["iteration"] = iteration
        obs.event("retry", **fields)

    def _fatal(self, exc, cls, fn, point, iteration):
        """Flight dump + boundary-checkpoint hook, then abort or CPU
        fallback per policy."""
        from .. import obs
        if obs.flight_enabled():
            obs.flight_dump(f"device_wedge:{point}",
                            extra={"error": f"{type(exc).__name__}: {exc}",
                                   "classify": cls})
        if self.on_fatal is not None:
            try:
                self.on_fatal(f"device_wedge:{point}", exc)
            except Exception as hook_exc:  # noqa: BLE001
                log.warning("%s watchdog: on_fatal hook failed (%s: %s)",
                            self.name, type(hook_exc).__name__, hook_exc)
        if self.policy == "fallback":
            import jax
            log.warning("%s watchdog: continuing on the CPU backend "
                        "(tpu_on_device_error=fallback; best-effort — "
                        "buffers committed to the dead backend may still "
                        "fail)", self.name)
            cpu = jax.devices("cpu")[0]
            with jax.default_device(cpu):
                return jax.block_until_ready(fn())
        raise DeviceWedgedError(
            f"device step {point!r} failed ({cls})"
            + (f" at iteration {iteration}" if iteration is not None else "")
            + f": {type(exc).__name__}: {exc}") from exc


class CircuitBreaker:
    """Per-replica circuit breaker for the serving router (serve/router.py).

    The serving twin of :class:`DeviceGuard`: same failure classes
    (:func:`classify_error`), same bounded deterministic backoff
    (:func:`backoff_delays`) — but instead of retrying in place it takes
    a replica OUT of the routing set, so one wedged replica costs
    capacity, never availability.  States:

    - **closed** — healthy; every request is allowed.
    - **open** — tripped (a FATAL failure immediately, or ``trip_after``
      consecutive transient ones); requests are routed elsewhere until
      the backoff delay expires.  Re-trips walk the bounded backoff
      schedule, so a flapping replica is probed less and less often.
    - **half_open** — the backoff expired; exactly ONE probe request is
      let through.  Success closes the breaker, failure re-opens it at
      the next backoff step.
    """

    def __init__(self, trip_after: int = 3, backoff_base_s: float = 0.5,
                 backoff_cap_s: float = 30.0, seed: int = 0):
        self.trip_after = max(int(trip_after), 1)
        # a long-enough schedule that a permanently dead replica keeps
        # being probed at the cap instead of running off the end
        self._delays = backoff_delays(16, backoff_base_s, backoff_cap_s,
                                      seed)
        self._lock = threading.Lock()
        self.state = "closed"
        self.failures = 0            # lifetime failure count
        self.consecutive = 0         # consecutive failures since last ok
        self.opens = 0               # times the breaker tripped
        self._open_until = 0.0
        self._open_step = 0          # index into the backoff schedule
        # a half-open probe that never resolves (the probing request was
        # never dispatched — e.g. a sibling replica answered first, or
        # its thread died) must not strand the breaker: after this long
        # in half_open without a verdict, another probe is allowed
        self._probe_timeout_s = max(float(backoff_cap_s), 1.0)
        self._half_open_since = 0.0
        self.last_error = ""

    def allow(self) -> bool:
        """True when a request may be routed to this replica.  While
        open, flips to half_open (one probe) once the backoff expires;
        a probe that evaporates is re-allowed after the probe timeout."""
        with self._lock:
            now = time.monotonic()
            if self.state == "closed":
                return True
            if self.state == "half_open":
                if now - self._half_open_since > self._probe_timeout_s:
                    self._half_open_since = now
                    return True  # the earlier probe never resolved
                return False  # a probe is already in flight
            if now >= self._open_until:
                self.state = "half_open"
                self._half_open_since = now
                return True
            return False

    def record_ok(self) -> None:
        with self._lock:
            self.consecutive = 0
            if self.state == "open":
                # only the half-open probe may close a tripped breaker:
                # a success belonging to a request dispatched BEFORE the
                # trip (a stale in-flight result) must not re-admit the
                # replica or reset the backoff escalation
                return
            self.state = "closed"
            self._open_step = 0

    def record_failure(self, exc: BaseException) -> str:
        """Account one failure; returns the classification.  A fatal
        failure (or a half-open probe failure, or ``trip_after``
        consecutive transients) opens the breaker."""
        cls = classify_error(exc)
        with self._lock:
            self.failures += 1
            self.consecutive += 1
            self.last_error = f"{type(exc).__name__}: {exc}"
            trip = (cls == "fatal" or self.state == "half_open"
                    or self.consecutive >= self.trip_after)
            if trip:
                self.state = "open"
                self.opens += 1
                delay = self._delays[min(self._open_step,
                                         len(self._delays) - 1)]
                self._open_step += 1
                self._open_until = time.monotonic() + delay
        return cls

    def snapshot(self) -> dict:
        with self._lock:
            open_for = (max(self._open_until - time.monotonic(), 0.0)
                        if self.state == "open" else 0.0)
            return {"state": self.state, "failures": self.failures,
                    "consecutive": self.consecutive, "opens": self.opens,
                    "open_for_s": round(open_for, 3),
                    "last_error": self.last_error or None}


# convenience for one-off guarded calls (the host collective path uses
# this — a full per-trainer guard would be overkill there; heartbeat
# disabled: collectives are guarded for retries only)
_ONEOFF = DeviceGuard(policy="retry", retries=2, backoff_base_s=0.02,
                      stall_timeout_s=-1.0, name="collective")


def guarded_call(fn: Callable, point: str):
    """Run ``fn`` with transient-retry semantics (active only when the
    fault harness is armed — real collective errors pass through
    unchanged, preserving existing behavior)."""
    return _ONEOFF.run(fn, point=point)
