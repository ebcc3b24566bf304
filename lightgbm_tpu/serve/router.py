"""Replica router: N ``PredictorSession`` replicas behind one surface.

One session is one batcher thread, one device binding, one degradation
state — a single point of failure.  The router fronts ``n_replicas``
sessions packed from the SAME model version (per-device on a multi-chip
host — replicas round-robin over ``jax.local_devices()`` — thread-pool
replicas on CPU) so a wedged replica degrades CAPACITY, not
availability:

- **health-based routing** — submits go to the routable replica with
  the shallowest batcher queue; draining and breaker-open replicas are
  skipped.
- **per-replica circuit breakers** — ``robust/watchdog.py
  CircuitBreaker``: the same transient/fatal classes and bounded
  deterministic backoff the training watchdog uses.  A replica whose
  dispatch fails trips its breaker and drops out of the routing set;
  after the backoff one half-open probe request is let through, and a
  success closes the breaker again.
- **failover** — a submit that fails on one replica is retried on the
  next routable one before the caller ever sees an error; only when
  EVERY replica rejects does the router re-raise (an all-overloaded
  fleet raises ``ServeOverloadError`` so the backpressure contract is
  preserved).
- **draining** — ``drain(i)`` removes a replica from the routing set
  without killing its in-flight work (the ops hatch for rolling a
  replica out of a fleet).

The router duck-types the session surface the HTTP front end and the
benches consume (``submit``/``submit_explain``/``result``/``predict``/
``explain``/``stats``/``metrics``/``warmup``/``close``), so
``PredictServer`` serves a router exactly like a bare session.  All
replicas of one version share ONE ``ServeMetrics`` — the fleet latency
histogram and shed counters aggregate without a merge step.

Fault injection (robust/faults.py): every dispatch passes
``serve_replica`` and ``serve_replica_{i}`` points, so a chaos run can
wedge exactly one replica (``serve_replica_0:raise@n=-1``) and prove
requests keep succeeding on the survivors.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import List, Optional

import numpy as np

from .. import obs
from ..robust import faults
from ..robust.watchdog import CircuitBreaker
from ..utils import log
from .batcher import ServeOverloadError
from .metrics import ServeMetrics
from .session import PredictorSession, Ticket


class NoReplicaAvailable(ServeOverloadError):
    """Every replica is breaker-open or draining — the fleet has zero
    routable capacity.  A ``ServeOverloadError`` subclass so the HTTP
    edge maps it to 503 + ``Retry-After`` like any other backpressure."""


class RoutedTicket:
    """A session ticket plus the fleet identity that resolved it: which
    replica ran it and which model version the answer came from —
    ``result()`` must be redeemed against the SAME session that issued
    the inner ticket, and responses echo the version so every answer is
    attributable to exactly one model."""

    __slots__ = ("inner", "replica", "model", "version", "router")

    def __init__(self, inner: Ticket, replica: "Replica",
                 model: Optional[str], version: Optional[int],
                 router: Optional["ReplicaRouter"] = None):
        self.inner = inner
        self.replica = replica
        self.model = model
        self.version = version
        self.router = router

    @property
    def rows(self) -> int:
        return self.inner.rows

    @property
    def parts(self):
        return self.inner.parts

    @property
    def kind(self) -> str:
        return self.inner.kind


class Replica:
    """One session + its breaker + drain flag."""

    def __init__(self, idx: int, session: PredictorSession,
                 breaker: CircuitBreaker):
        self.idx = idx
        self.session = session
        self.breaker = breaker
        self.draining = False

    @property
    def routable(self) -> bool:
        return not self.draining and self.breaker.allow()

    def stats_row(self) -> dict:
        st = self.session.stats()
        return {
            "replica": f"r{self.idx}",
            "healthy": (not self.draining
                        and self.breaker.state == "closed"
                        and not st["degraded"]),
            "draining": self.draining,
            "degraded": st["degraded"],
            "explain_degraded": st["explain_degraded"],
            "breaker": self.breaker.snapshot(),
            "queue_rows": st["queue_rows"],
            "requests": st["requests"],
            "batches": st["batches"],
            "buckets": st["buckets"],
            "uptime_s": st["uptime_s"],
        }


class ReplicaRouter:
    """Health-routed fleet of replicas serving one model version."""

    def __init__(self, model, n_replicas: int = 2, config=None,
                 name: Optional[str] = None,
                 version: Optional[int] = None,
                 metrics: Optional[ServeMetrics] = None,
                 sessions: Optional[List[PredictorSession]] = None,
                 **session_kw):
        self.name = name
        self.version = version
        # the model source + build inputs are kept so restart_replica
        # can cold-boot a replacement replica (chaos: replica restart
        # under load); caller-provided session lists have no source to
        # rebuild from, so restart is unsupported there
        self._model_src = model if sessions is None else None
        self._config = config
        self._session_kw = dict(session_kw)
        if sessions is None:
            n = max(int(n_replicas), 1)
            devices = self._replica_devices(n)
            # one DriftMonitor per version, shared by every replica
            # (like ServeMetrics below): the sidecar loads once and the
            # merged sketch needs no cross-replica merge step
            from ..obs.drift import DriftMonitor
            shared_drift = DriftMonitor.maybe_load(model, config)
            sessions = [PredictorSession(model, config=config,
                                         metrics=metrics,
                                         device=devices[i],
                                         drift=shared_drift,
                                         **session_kw)
                        for i in range(n)]
        if not sessions:
            raise ValueError("router needs at least one replica")
        # all replicas share the first session's metrics unless the
        # caller provided one (the registry passes a fresh instance per
        # version so post-swap health deltas start from zero)
        self.metrics = metrics if metrics is not None \
            else sessions[0].metrics
        cfg = config if config is not None else sessions[0].config
        if isinstance(cfg, dict):
            cfg = None  # knobs below fall back to defaults
        trip = int(getattr(cfg, "tpu_serve_breaker_trip", 3) or 3)
        base = float(getattr(cfg, "tpu_serve_breaker_backoff_s", 0.5)
                     or 0.5)
        # drift: adopt replica 0's monitor (caller-built sessions may
        # each have armed "auto" — unify to one so the sketch merges)
        self.drift = getattr(sessions[0], "_drift", None)
        self.replicas = []
        for i, s in enumerate(sessions):
            s.model_name = self.name
            s.model_version = self.version
            s.replica_id = f"r{i}"
            s.metrics = self.metrics
            s._drift = self.drift
            self.replicas.append(Replica(
                i, s, CircuitBreaker(trip_after=trip, backoff_base_s=base,
                                     seed=i)))
        if self.drift is not None:
            self.drift.model_name = self.name or "default"
            self.drift.model_version = int(self.version or 0)
        self._rr = itertools.count()
        self._lock = threading.Lock()
        self.failovers = 0
        self._t_start = time.time()
        # compile accounting is router-level: the obs counter is
        # process-global, so per-session deltas (each measured from its
        # own construction) would double-count across replicas
        obs.install_recompile_hook()
        self._compiles0 = obs.compile_count()

    @staticmethod
    def _replica_devices(n: int):
        """Round-robin device assignment: on a multi-chip host each
        replica pins its forest + dispatch to its own device; with one
        device (CPU) every replica shares it (thread-pool replicas)."""
        try:
            import jax
            devs = jax.local_devices()
        except Exception:  # noqa: BLE001 — backend not up yet
            return [None] * n
        if len(devs) <= 1:
            return [None] * n
        return [devs[i % len(devs)] for i in range(n)]

    # ---- session-surface passthroughs --------------------------------
    @property
    def session(self) -> PredictorSession:
        """The first replica's session (canary/introspection surface)."""
        return self.replicas[0].session

    def __getattr__(self, item):
        # static model facts (num_features, num_tpi, num_trees,
        # explain_enabled, max_batch, ...) are identical across replicas
        if "replicas" not in self.__dict__:  # guard __init__ recursion
            raise AttributeError(item)
        return getattr(self.replicas[0].session, item)

    def warmup(self) -> int:
        return sum(r.session.warmup() for r in self.replicas)

    def warmup_explain(self) -> int:
        return sum(r.session.warmup_explain() for r in self.replicas)

    # ---- routing ------------------------------------------------------
    def _candidates(self) -> List[Replica]:
        """Routable replicas, shallowest queue first (round-robin tiebreak
        via the submit counter so equal-depth replicas share load).  A
        replica whose breaker just flipped to half-open sorts FIRST: its
        one probe request must actually reach it — otherwise a healthier
        sibling absorbs every request and the breaker never closes (the
        probe is safe: a failure fails over to the next candidate)."""
        rot = next(self._rr) % max(len(self.replicas), 1)
        order = self.replicas[rot:] + self.replicas[:rot]
        avail = [r for r in order if r.routable]
        avail.sort(key=lambda r: (0 if r.breaker.state == "half_open"
                                  else 1,
                                  r.session._batcher.queue_rows))
        return avail

    def _dispatch(self, kind: str, X, **kw) -> RoutedTicket:
        cands = self._candidates()
        if not cands:
            self.metrics.count_shed(str(kw.get("priority") or "normal"))
            raise NoReplicaAvailable(
                f"no routable replica ({len(self.replicas)} total, all "
                "breaker-open or draining)",
                priority=str(kw.get("priority") or "normal"))
        last_exc: Optional[BaseException] = None
        for rep in cands:
            try:
                faults.check("serve_replica")
                faults.check(f"serve_replica_{rep.idx}")
                fn = (rep.session.submit if kind == "predict"
                      else rep.session.submit_explain)
                ticket = fn(X, **kw)
                rep.breaker.record_ok()
                return RoutedTicket(ticket, rep, self.name, self.version,
                                    router=self)
            except ServeOverloadError as exc:
                # a full queue on one replica is load, not sickness: no
                # breaker strike, just spill to the next replica
                last_exc = exc
            except Exception as exc:  # noqa: BLE001 — failover point
                last_exc = exc
                cls = rep.breaker.record_failure(exc)
                with self._lock:
                    self.failovers += 1
                log.warning("serve router: replica r%d %s failure (%s: "
                            "%s) — breaker %s; failing over",
                            rep.idx, cls, type(exc).__name__, exc,
                            rep.breaker.state)
                obs.event("serve_failover", replica=rep.idx,
                          classify=cls, breaker=rep.breaker.state,
                          error=f"{type(exc).__name__}: {exc}")
        if isinstance(last_exc, ServeOverloadError):
            # the CLIENT-visible shed is counted here, once — replica
            # sessions skip their own count inside a router so a spill
            # that succeeded on a sibling never inflates the counters
            self.metrics.count_shed(
                getattr(last_exc, "priority", None)
                or str(kw.get("priority") or "normal"))
        raise last_exc if last_exc is not None else NoReplicaAvailable(
            "no replica accepted the request")

    def submit(self, X, **kw) -> RoutedTicket:
        return self._dispatch("predict", X, **kw)

    def submit_explain(self, X, **kw) -> RoutedTicket:
        return self._dispatch("explain", X, **kw)

    def result(self, ticket: RoutedTicket, timeout: Optional[float] = None
               ) -> np.ndarray:
        if not isinstance(ticket, RoutedTicket):
            # a bare ticket can only have come from replica 0's session
            # surface (sync predict path) — redeem it there
            return self.replicas[0].session.result(ticket, timeout)
        try:
            out = ticket.replica.session.result(ticket.inner, timeout)
        except Exception as exc:
            from .batcher import DeadlineExceeded
            from concurrent.futures import TimeoutError as _FT
            if not isinstance(exc, (DeadlineExceeded, _FT,
                                    ServeOverloadError)):
                # a worker-side failure is a replica-health signal; a
                # deadline/timeout is the caller's budget, not sickness
                ticket.replica.breaker.record_failure(exc)
            raise
        ticket.replica.breaker.record_ok()
        return out

    def predict(self, X, raw_score: bool = False) -> np.ndarray:
        ticket = self.submit(X, raw_score=raw_score)
        return self.result(ticket)

    def explain(self, X) -> np.ndarray:
        ticket = self.submit_explain(X)
        return self.result(ticket)

    # ---- fleet management --------------------------------------------
    def drain(self, idx: int) -> None:
        self.replicas[idx].draining = True
        obs.event("serve_drain", replica=idx, draining=True)

    def undrain(self, idx: int) -> None:
        self.replicas[idx].draining = False
        obs.event("serve_drain", replica=idx, draining=False)

    def restart_replica(self, idx: int) -> dict:
        """Kill one replica and cold-boot a replacement in place: a new
        ``PredictorSession`` packed from the router's model source, a
        fresh breaker, the shared metrics/drift.  With an AOT store
        armed the reboot loads its bucket executables instead of
        compiling — the "replica restart under load" chaos scenario
        asserts the rebooted replica's first request pays zero JIT
        compiles.  In-flight work on the old replica fails over like any
        dispatch failure (its batcher drains with errors on close)."""
        if self._model_src is None:
            raise RuntimeError(
                "router built from caller-provided sessions has no "
                "model source to restart a replica from")
        rep = self.replicas[idx]
        rep.draining = True          # drop out of the routing set now
        device = getattr(rep.session, "_device", None)
        t0 = time.perf_counter()
        c0 = obs.compile_count()
        sess = PredictorSession(self._model_src, config=self._config,
                                metrics=self.metrics, device=device,
                                drift=self.drift, **self._session_kw)
        sess.model_name = self.name
        sess.model_version = self.version
        sess.replica_id = f"r{idx}"
        cfg = self._config if not isinstance(self._config, dict) else None
        trip = int(getattr(cfg, "tpu_serve_breaker_trip", 3) or 3)
        base = float(getattr(cfg, "tpu_serve_breaker_backoff_s", 0.5)
                     or 0.5)
        fresh = Replica(idx, sess,
                        CircuitBreaker(trip_after=trip,
                                       backoff_base_s=base, seed=idx))
        old = rep.session
        self.replicas[idx] = fresh   # atomic: list item assignment
        try:
            old.close()
        except Exception as exc:  # noqa: BLE001 — replacement already live
            log.warning("restart_replica(%d): old session close failed "
                        "(%s: %s)", idx, type(exc).__name__, exc)
        boot = {"replica": idx,
                "boot_ms": round((time.perf_counter() - t0) * 1e3, 3),
                "boot_compiles": int(obs.compile_count() - c0),
                "aot": (sess.stats() or {}).get("aot") is not None}
        obs.event("serve_replica_restart", **boot)
        log.info("router: replica r%d restarted in %.1fms "
                 "(%d compile(s) at boot)", idx, boot["boot_ms"],
                 boot["boot_compiles"])
        return boot

    def routable_count(self) -> int:
        return sum(1 for r in self.replicas
                   if not r.draining and r.breaker.state != "open")

    def stats(self) -> dict:
        """Aggregate fleet stats in the single-session shape (so
        ``render_prometheus`` and ``/health`` consumers keep working)
        plus the per-replica rows."""
        rows = [r.stats_row() for r in self.replicas]
        per = [r.session.stats() for r in self.replicas]
        agg = {}
        for key in ("requests", "ok", "deadline_missed", "overloads",
                    "batches", "rows", "padded_rows", "explain_requests",
                    "explain_ok", "explain_batches", "explain_rows",
                    "explain_padded_rows", "queue_rows",
                    "explain_deadline_missed"):
            agg[key] = sum(int(s.get(key) or 0) for s in per)
        # one process-global counter: a router-level delta, NOT a sum of
        # per-session deltas (those each start at their own construction
        # and would count every sibling's compiles again).  Still shared
        # across fleets in one process — a per-model split would need
        # per-compile attribution the jax hook does not expose
        agg["compile_count"] = int(obs.compile_count() - self._compiles0)
        from ..obs.report import percentile
        all_lat, all_xlat = [], []
        for r in self.replicas:
            with r.session._lock:  # reservoirs mutate under this lock
                all_lat.extend(r.session._lat_ms)
                all_xlat.extend(r.session._xlat_ms)
        all_lat.sort()
        all_xlat.sort()
        agg["p50_ms"] = percentile(all_lat, 0.50)
        agg["p99_ms"] = percentile(all_lat, 0.99)
        agg["explain_p50_ms"] = percentile(all_xlat, 0.50)
        agg["explain_p99_ms"] = percentile(all_xlat, 0.99)
        agg["explain_occupancy"] = (
            round(agg["explain_rows"] / agg["explain_padded_rows"], 4)
            if agg["explain_padded_rows"] else None)
        agg["explain_buckets"] = sorted(
            {b for s in per for b in s["explain_buckets"]})
        agg["explain_max_batch"] = per[0]["explain_max_batch"]
        agg["occupancy"] = (round(agg["rows"] / agg["padded_rows"], 4)
                            if agg["padded_rows"] else None)
        agg["buckets"] = sorted({b for s in per for b in s["buckets"]})
        agg["degraded"] = all(s["degraded"] for s in per)
        agg["any_degraded"] = any(s["degraded"] for s in per)
        agg["explain_degraded"] = all(s["explain_degraded"] for s in per)
        agg["degraded_transitions"] = self.metrics.degraded_transitions
        agg["recoveries"] = self.metrics.recoveries
        agg["slo_p99_ms"] = per[0]["slo_p99_ms"]
        agg["slo_burn"] = self.metrics.slo_burn()
        agg["uptime_s"] = round(time.time() - self._t_start, 1)
        agg["trees"] = per[0]["trees"]
        agg["num_class"] = per[0]["num_class"]
        agg["num_features"] = per[0]["num_features"]
        agg["max_batch"] = per[0]["max_batch"]
        agg["explain_enabled"] = per[0]["explain_enabled"]
        agg["explain_armed"] = any(s["explain_armed"] for s in per)
        agg["model"] = self.name
        agg["version"] = self.version
        agg["n_replicas"] = len(self.replicas)
        agg["routable_replicas"] = self.routable_count()
        agg["failovers"] = self.failovers
        agg["resident_bytes"] = self.resident_bytes()
        # AOT executable store (serve/aot.py): per-replica stores share
        # one directory, so entries come from any row while the traffic
        # counters (loads/fallbacks) sum across replicas
        aots = [s.get("aot") for s in per if s.get("aot")]
        agg["aot"] = ({"dir": aots[0].get("dir"),
                       "entries": aots[0].get("entries"),
                       "loaded": sum(int(a.get("loaded") or 0)
                                     for a in aots),
                       "saved": sum(int(a.get("saved") or 0)
                                    for a in aots),
                       "fallbacks": sum(int(a.get("fallbacks") or 0)
                                        for a in aots),
                       "save_errors": sum(int(a.get("save_errors") or 0)
                                          for a in aots)}
                      if aots else None)
        agg["drift"] = (self.drift.status()
                        if self.drift is not None else None)
        agg["replicas"] = rows
        return agg

    def resident_bytes(self) -> int:
        """Device bytes this version's replicas hold resident (the
        ``tpu_serve_resident_bytes`` gauge; each replica packs its own
        forest, so the total is a sum even on a shared device)."""
        return sum(int(r.session.resident_bytes())
                   for r in self.replicas)

    def close(self) -> None:
        for r in self.replicas:
            r.session.close()

    def __enter__(self) -> "ReplicaRouter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
