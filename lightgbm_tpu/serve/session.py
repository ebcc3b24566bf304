"""``PredictorSession``: pack a model once, serve it device-resident.

The trainer's device predict path (boosting/gbdt.py) is tied to live
training state; the session is the serving-side replacement — the
reference's dedicated ``Predictor`` (src/application/predictor.hpp)
rebuilt for TPU batch traversal:

- the model (a ``Booster``, a bare ``GBDT``/``LoadedGBDT``, or a model
  file path) is packed ONCE into a stacked bin-space ``ForestArrays``
  plus a model-derived ``DeviceMeta`` (serve/packing.py — no train_ds);
- ``predict(X)`` is the synchronous path (chunks internally to the
  batch cap); ``submit(X) -> ticket`` / ``result(ticket)`` the async
  one, coalesced by the dynamic microbatcher (serve/batcher.py);
- ``explain(X)`` / ``submit_explain(X)`` are the SHAP-contribution
  twins: the batched device TreeSHAP kernel (explain/) behind its own
  microbatcher and pow2 bucket family (``tpu_explain_max_batch`` /
  ``tpu_explain_max_wait_ms``), packed lazily on first use so
  predict-only sessions never pay the path-metadata HBM cost;
- every device call pads its rows to the next power-of-two bucket, so
  the jitted forest scan compiles at most ``ceil(log2(max_batch)) + 1``
  shapes — the obs recompile counter (obs/trace.py) verifies the bound;
- if the device backend dies mid-flight the session degrades to the
  host numpy predictor (per-tree value-space traversal) instead of
  failing requests; ``stats()['degraded']`` and the ``serve_degraded``
  event record it, and the HTTP /health endpoint reports it.

Telemetry (when a sink is configured): ``serve_request`` per request
(rows, total_ms, ok), ``serve_batch`` per device batch (rows, padded,
bucket, queue_rows, exec_ms), ``serve_overload`` / ``serve_degraded``
on the respective transitions.  ``obs/report.py serve_summary`` folds
them into the serving digest (p50/p99, occupancy, pad waste).
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque
from concurrent.futures import InvalidStateError
from typing import Optional

import numpy as np

from .. import obs
from ..config import Config
from ..robust import faults
from ..utils import log
from .batcher import (DeadlineExceeded, MicroBatcher, Request,
                      ServeOverloadError, normalize_priority)
from .metrics import ServeMetrics
from .packing import ServeBinSpace

_LAT_RESERVOIR = 8192  # latency samples kept for the p50/p99 estimate

# an overload STORM (>= _STORM_N rejects inside _STORM_WINDOW_S) dumps
# the flight ring once per _STORM_COOLDOWN_S — the post-mortem for "why
# did the queue blow up", rate-limited so a sustained storm writes one
# artifact, not thousands
_STORM_N = 16
_STORM_WINDOW_S = 5.0
_STORM_COOLDOWN_S = 60.0


def _safe_resolve(future, result=None, error=None) -> None:
    """Resolve a request future, tolerating the overload-cancellation
    race: a submit that overloaded cancels its already-queued chunks,
    and cancel() can land between any done() check and the set_* call —
    an InvalidStateError here must not poison the rest of the batch."""
    try:
        if error is not None:
            future.set_exception(error)
        else:
            future.set_result(result)
    except InvalidStateError:
        pass


def _env_num(name: str, cast, fallback):
    v = os.environ.get(name, "")
    if v:
        try:
            return cast(v)
        except ValueError:
            log.warning("ignoring non-numeric %s=%r", name, v)
    return fallback


class Ticket:
    """Handle for an async submission (one or more batcher requests —
    oversize submissions are chunked to the batch cap).  ``kind`` is
    ``"predict"`` or ``"explain"`` — it picks the result conversion and
    which accounting stream (latency histogram, events) the ticket's
    outcome lands in."""

    __slots__ = ("parts", "rows", "raw_score", "t0", "counted", "kind",
                 "priority")

    def __init__(self, parts, rows: int, raw_score: bool,
                 kind: str = "predict", priority: str = "normal"):
        self.parts = parts          # [(future, n_rows), ...]
        self.rows = rows
        self.raw_score = raw_score
        self.t0 = time.perf_counter()
        self.counted = False        # request-level stats recorded once
        self.kind = kind
        self.priority = priority


class PredictorSession:
    """Device-resident inference over one packed model window."""

    def __init__(self, model, config=None, num_iteration: Optional[int] = None,
                 start_iteration: int = 0, max_batch: Optional[int] = None,
                 max_wait_ms: Optional[float] = None,
                 queue_depth: Optional[int] = None,
                 metrics: Optional[ServeMetrics] = None,
                 device=None, drift="auto"):
        gbdt = model
        # fleet identity (serve/router.py + serve/registry.py stamp
        # these): which model/version/replica this session serves, and an
        # optional pinned device (per-device replicas on a multi-chip
        # host; None = the backend default)
        self.model_name: Optional[str] = None
        self.model_version: Optional[int] = None
        self.replica_id: Optional[str] = None
        self._device = device
        if isinstance(model, str):
            from ..io.model_io import load_model_file
            gbdt, loaded_cfg = load_model_file(model)
            if config is None:
                config = loaded_cfg
        elif hasattr(model, "_gbdt"):  # a basic.Booster
            gbdt = model._gbdt
            if config is None:
                config = getattr(model, "config", None)
        if config is None:
            config = getattr(gbdt, "config", None) or Config()
        elif isinstance(config, dict):
            config = Config.from_params(config)
        self.config = config
        self.gbdt = gbdt
        self.objective = getattr(gbdt, "objective", None)
        K = self.num_tpi = int(gbdt.num_tpi)

        start, stop = gbdt._iter_window(num_iteration, start_iteration)
        trees = list(gbdt.models)[start * K:stop * K]
        if not trees:
            raise ValueError("cannot serve an empty model")
        self._trees = trees
        self.num_trees = len(trees)
        # rf-style averaged forests divide the summed raw score by the
        # iteration window (io/model_io.py LoadedGBDT.predict_raw)
        self.average_factor = (float(max(stop - start, 1))
                               if getattr(gbdt, "average_output", False)
                               else 0.0)
        if gbdt.train_ds is not None:
            F = int(gbdt.train_ds.num_total_features)
        else:
            F = int(getattr(gbdt, "num_features", 0)
                    or len(getattr(gbdt, "feature_names", []) or []))
        if F <= 0:
            raise ValueError("model declares no feature space to bin into")
        self.num_features = F

        self.max_batch = int(max_batch if max_batch is not None else _env_num(
            "LGBM_TPU_SERVE_MAX_BATCH", int,
            getattr(config, "tpu_serve_max_batch", 1024)))
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.max_wait_ms = float(
            max_wait_ms if max_wait_ms is not None else _env_num(
                "LGBM_TPU_SERVE_MAX_WAIT_MS", float,
                getattr(config, "tpu_serve_max_wait_ms", 2.0)))
        self.queue_depth = int(
            queue_depth if queue_depth is not None else _env_num(
                "LGBM_TPU_SERVE_QUEUE_DEPTH", int,
                getattr(config, "tpu_serve_queue_depth", 8192)))
        # ---- explanation serving (explain/ TreeSHAP) -----------------
        env_x = os.environ.get("LGBM_TPU_EXPLAIN", "").strip().lower()
        self.explain_enabled = (env_x not in ("0", "false", "off")
                                if env_x
                                else bool(getattr(config, "tpu_explain",
                                                  True)))
        self.explain_max_batch = max(int(_env_num(
            "LGBM_TPU_EXPLAIN_MAX_BATCH", int,
            getattr(config, "tpu_explain_max_batch", 256))), 1)
        self.explain_max_wait_ms = max(float(_env_num(
            "LGBM_TPU_EXPLAIN_MAX_WAIT_MS", float,
            getattr(config, "tpu_explain_max_wait_ms", 5.0))), 0.0)
        # packed lazily on first explain()/submit_explain(): the path
        # metadata + its batcher cost host time and HBM a predict-only
        # session must not pay
        self._explain = None
        self._explain_lock = threading.Lock()
        self._explain_buckets: set = set()
        self._explain_batches = 0
        self._explain_rows = 0
        self._explain_padded = 0
        self._n_explain = 0
        self._n_explain_ok = 0
        self._n_explain_deadline = 0
        self._xlat_ms: list = []
        # the explain plane degrades apart from predict's: the TreeSHAP
        # kernel's [N, L, P] working set can fail (HBM OOM) while 1-row
        # predicts still succeed, so a shared flag would let the predict
        # reprobe re-arm a kernel that is still broken — a sustained
        # degrade/recover oscillation routing predict to the host path
        self._explain_degraded = False
        self._last_explain_probe = 0.0

        # ---- pack once: bin space + stacked forest + jitted scan ------
        self.space = ServeBinSpace(trees, F)
        class_ids = np.asarray([i % K for i in range(len(trees))], np.int32)
        self.forest = self.space.pack(trees, class_ids)
        from ..core.forest import forest_predict_fn
        early_stop = (gbdt._early_stop_spec()
                      if hasattr(gbdt, "_early_stop_spec") else None)
        fn = forest_predict_fn(self.space.meta, K, early_stop)
        self._raw_fn = fn  # the jit fn itself — the AOT lower/compile unit
        self._device_fn = fn

        # ---- AOT executable store (serve/aot.py): zero-compile boot --
        # entries present for this exact forest/bin-space/backend load as
        # ready-to-call executables keyed per bucket; a warmed store
        # makes request #1 pay zero JIT compiles.  Load failures fall
        # back to the jit path above, loudly (aot_fallback event).
        from .aot import AOTStore, resolve_aot_dir
        self._aot_fns: dict = {}
        self._aot_x_fns: dict = {}
        self._aot = None
        aot_dir = resolve_aot_dir(config)
        if aot_dir:
            self._aot = AOTStore(aot_dir)
            # the executable bakes forest + meta in as constants: hash
            # CONTENT, not shapes (see serve/aot.py key schema)
            self._aot_digest = AOTStore._digest_tree(
                (self.forest, self.space.meta))
            self._aot_extra = (f"K={K}|F={F}|es={early_stop!r}"
                               f"|dev={self._device}")
            for b in self._bucket_sweep(self.max_batch):
                st, afn = self._aot.load("predict", self._aot.key(
                    "predict", b, self._aot_digest, self._aot_extra))
                if st == "hit":
                    self._aot_fns[b] = afn

        # ---- serving state -------------------------------------------
        self._degraded = False
        self._closed = False
        self._lock = threading.Lock()
        self._lat_ms: list = []
        self._n_req = 0
        self._n_ok = 0
        self._n_deadline = 0
        self._n_overload = 0
        self._batches = 0
        self._real_rows = 0
        self._padded_rows = 0
        self._buckets: set = set()
        # ---- observability: live metrics + trace plane ---------------
        self._t_start = time.time()
        obs.install_recompile_hook()
        self._compiles0 = obs.compile_count()
        self.slo_p99_ms = float(_env_num(
            "LGBM_TPU_SERVE_SLO_P99_MS", float,
            getattr(config, "tpu_serve_slo_p99_ms", 250.0)))
        # replicas of one model version share ONE ServeMetrics (the
        # router passes it in) so the fleet's latency histogram and
        # shed counters aggregate without a merge step
        self.metrics = (metrics if metrics is not None
                        else ServeMetrics(slo_p99_ms=self.slo_p99_ms))
        # ---- drift monitoring (obs/drift.py) -------------------------
        # "auto" arms only for a file-loaded model with a .quality.json
        # sidecar beside it (and tpu_drift on); the router passes a
        # shared DriftMonitor instead so one sketch covers every
        # replica of a version, like ServeMetrics above.  Unarmed, the
        # hot path pays exactly one is-None branch.
        if drift == "auto":
            from ..obs.drift import DriftMonitor
            self._drift = DriftMonitor.maybe_load(model, config)
        else:
            self._drift = drift or None
        # probe-and-recover: while degraded, re-try the device every
        # reprobe_s seconds so a transient backend error is not a
        # one-way latch (0 disables — the pre-ISSUE-7 behavior)
        self.reprobe_s = float(_env_num(
            "LGBM_TPU_SERVE_REPROBE_S", float,
            getattr(config, "tpu_serve_reprobe_s", 30.0)))
        self._last_probe = 0.0
        if getattr(config, "tpu_trace", False):
            obs.enable_trace()
        if not obs.flight_enabled():
            obs.enable_flight(obs.flight_len_from_env(
                getattr(config, "tpu_flight_len", 256)))
        self._overload_times: deque = deque(maxlen=_STORM_N)
        self._last_flight_dump = None  # monotonic() of the last dump
        # priority shedding (serve/batcher.py): per-class queue budgets
        # so overload drops low-priority bulk traffic before interactive
        # requests
        self._shed_fracs = {
            "low": float(_env_num(
                "LGBM_TPU_SERVE_SHED_LOW_FRAC", float,
                getattr(config, "tpu_serve_shed_low_frac", 0.5))),
            "normal": float(_env_num(
                "LGBM_TPU_SERVE_SHED_NORMAL_FRAC", float,
                getattr(config, "tpu_serve_shed_normal_frac", 0.85))),
        }
        self._batcher = MicroBatcher(
            self._execute_batch, max_batch=self.max_batch,
            max_wait_s=self.max_wait_ms / 1e3,
            max_queue_rows=self.queue_depth,
            shed_fracs=self._shed_fracs)
        if obs.enabled():
            obs.event("serve_start", trees=self.num_trees, num_class=K,
                      num_features=F, max_batch=self.max_batch,
                      max_wait_ms=self.max_wait_ms,
                      queue_depth=self.queue_depth)

    # ------------------------------------------------------------------
    @staticmethod
    def _bucket_sweep(cap: int):
        """Every bucket size ``_bucket`` can produce, ascending: the
        pow2 ladder clamped at the batch cap (a non-power-of-two cap IS
        the top bucket)."""
        b = 1
        while True:
            size = min(b, cap)
            yield size
            if size >= cap:
                return
            b *= 2

    def warmup(self) -> int:
        """Pre-compile every bucket shape up to the batch cap so the
        first real request never pays a compile.  The probe is clamped
        to the cap — a non-power-of-two ``max_batch`` IS the top bucket
        (``_bucket`` clamps the same way), so warmup compiles exactly
        the shapes real traffic can produce.  With the AOT store armed,
        buckets it did not already hold are lowered/compiled ONCE here,
        registered for dispatch, and persisted — the next process boots
        with zero compiles.  Returns the bucket count."""
        n = 0
        for size in self._bucket_sweep(self.max_batch):
            if self._aot is not None and size not in self._aot_fns:
                self._aot_export(size)
            self._run_device(np.zeros((size, self.num_features), np.int32))
            n += 1
        return n

    def _aot_export(self, size: int) -> None:
        """Lower + compile the predict fn for one bucket and persist it
        (serve/aot.py).  The compiled executable also joins the dispatch
        table so this process pays the compile exactly once.  Failure is
        logged and costs the next boot a compile, never a request."""
        import jax
        import jax.numpy as jnp
        try:
            bins = jnp.asarray(
                np.zeros((size, self.num_features), np.int32))
            if self._device is not None:
                with jax.default_device(self._device):
                    comp = self._raw_fn.lower(self.forest, bins).compile()
            else:
                comp = self._raw_fn.lower(self.forest, bins).compile()
            self._aot_fns[size] = comp
            self._aot.save("predict", self._aot.key(
                "predict", size, self._aot_digest, self._aot_extra),
                comp, note={"bucket": size, "model": self.model_name})
        except Exception as exc:  # noqa: BLE001 — store is best-effort
            log.warning("AOT export failed for bucket %d (%s: %s)",
                        size, type(exc).__name__, exc)

    def _bucket(self, n: int) -> int:
        b = 1
        while b < n:
            b *= 2
        return min(b, self.max_batch)

    def _run_device(self, bins: np.ndarray, span_ctx=None):
        """Pad to the pow2 bucket, run the jitted scan, slice the pad
        off.  Returns ([n, K] f64 raw scores, bucket).  ``span_ctx`` is
        a list of (trace_id, parent_id) pairs to attribute the pad /
        device-execute spans to (one pair per member request — the batch
        phases are shared, the trace trees are per request)."""
        import jax.numpy as jnp
        n = bins.shape[0]
        t_pad0 = time.time()
        b = self._bucket(n)
        if b > n:
            bins = np.concatenate(
                [bins, np.zeros((b - n, bins.shape[1]), bins.dtype)])
        with self._lock:
            self._buckets.add(b)
        t_exec0 = time.time()
        faults.check("serve_device")
        aot_fn = self._aot_fns.get(b)
        if aot_fn is not None:
            # AOT-loaded executable: already compiled for this device,
            # no jit dispatch, no compile — the zero-cold-start path
            out = aot_fn(self.forest, jnp.asarray(bins))
        elif self._device is not None:
            import jax
            with jax.default_device(self._device):
                out = self._device_fn(self.forest, jnp.asarray(bins))
        else:
            out = self._device_fn(self.forest, jnp.asarray(bins))
        raw = np.asarray(out, dtype=np.float64)[:n]
        if self.average_factor:
            raw /= self.average_factor
        if span_ctx:
            t_end = time.time()
            for tid, pid in span_ctx:
                obs.emit_span("serve/pad", t_pad0, (t_exec0 - t_pad0) * 1e3,
                              tid, parent_id=pid,
                              attrs={"rows": n, "bucket": b})
                obs.emit_span("serve/device_execute", t_exec0,
                              (t_end - t_exec0) * 1e3, tid, parent_id=pid,
                              attrs={"bucket": b})
        return raw, b

    def _run_host(self, X: np.ndarray, span_ctx=None) -> np.ndarray:
        """Degraded path: per-tree value-space traversal on the host."""
        t0 = time.time()
        K = self.num_tpi
        out = np.zeros((X.shape[0], K))
        for i, tree in enumerate(self._trees):
            out[:, i % K] += tree.predict(X)
        if self.average_factor:
            out /= self.average_factor
        if span_ctx:
            dur = (time.time() - t0) * 1e3
            for tid, pid in span_ctx:
                obs.emit_span("serve/host_fallback", t0, dur, tid,
                              parent_id=pid,
                              attrs={"rows": int(X.shape[0])})
        return out

    def _note_degraded(self, exc: BaseException) -> None:
        if not self._degraded:
            self._degraded = True
            self._last_probe = time.monotonic()
            self.metrics.set_degraded(True)
            log.warning("serve: device predictor failed (%s: %s); "
                        "degrading to the host predictor"
                        + (" (re-probing every %.3gs)" % self.reprobe_s
                           if self.reprobe_s > 0 else ""),
                        type(exc).__name__, exc)
            obs.event("serve_degraded",
                      error=f"{type(exc).__name__}: {exc}")
            # the flip is exactly what the flight recorder exists for:
            # persist the last N spans/events leading up to it.  force=
            # True: each degradation is a distinct incident, so the
            # storm cooldown must never swallow ITS post-mortem
            self._flight_dump("serve_degraded", force=True)

    def _maybe_reprobe(self) -> bool:
        """While degraded, periodically try one tiny device execution;
        success flips the session (and /health, and the /metrics
        ``degraded`` gauge) back to the device path.  Returns True when
        the probe recovered the device."""
        if not self._degraded or self.reprobe_s <= 0:
            return False
        now = time.monotonic()
        with self._lock:
            if now - self._last_probe < self.reprobe_s:
                return False
            self._last_probe = now
        try:
            self._run_device(
                np.zeros((1, self.num_features), np.int32))
        except Exception as exc:  # noqa: BLE001 — stay degraded
            obs.event("serve_probe", ok=False,
                      error=f"{type(exc).__name__}: {exc}")
            return False
        self._degraded = False
        self.metrics.set_degraded(False)
        obs.event("serve_probe", ok=True)
        obs.event("serve_recovered")
        log.info("serve: device probe succeeded — leaving degraded mode, "
                 "device predictions resume")
        return True

    def _note_degraded_explain(self, exc: BaseException) -> None:
        if not self._explain_degraded:
            self._explain_degraded = True
            self._last_explain_probe = time.monotonic()
            log.warning("serve: device TreeSHAP kernel failed (%s: %s); "
                        "degrading /explain to the host oracle"
                        + (" (re-probing every %.3gs)" % self.reprobe_s
                           if self.reprobe_s > 0 else ""),
                        type(exc).__name__, exc)
            obs.event("serve_degraded", plane="explain",
                      error=f"{type(exc).__name__}: {exc}")
            self._flight_dump("serve_degraded", force=True)

    def _maybe_reprobe_explain(self) -> bool:
        """Explain-plane twin of ``_maybe_reprobe`` — the probe runs the
        TreeSHAP kernel itself (a 1-row predict proving nothing about
        the much larger explain working set)."""
        if not self._explain_degraded or self.reprobe_s <= 0:
            return False
        now = time.monotonic()
        with self._lock:
            if now - self._last_explain_probe < self.reprobe_s:
                return False
            self._last_explain_probe = now
        try:
            self._run_device_explain(
                np.zeros((1, self.num_features), np.int32))
        except Exception as exc:  # noqa: BLE001 — stay degraded
            obs.event("serve_probe", plane="explain", ok=False,
                      error=f"{type(exc).__name__}: {exc}")
            return False
        self._explain_degraded = False
        obs.event("serve_probe", plane="explain", ok=True)
        obs.event("serve_recovered", plane="explain")
        log.info("serve: TreeSHAP probe succeeded — leaving explain "
                 "degraded mode, device explanations resume")
        return True

    def _note_overload(self, rows: int, queue_rows: int,
                       priority: str = "normal") -> None:
        """Shared overload accounting for both submit paths: counter,
        per-priority shed count, event, and the storm check (>= _STORM_N
        rejects inside _STORM_WINDOW_S dumps the flight ring once per
        cooldown)."""
        storm = False
        now = time.monotonic()
        with self._lock:
            self._n_overload += 1
            self._overload_times.append(now)
            storm = (len(self._overload_times) == _STORM_N
                     and now - self._overload_times[0] <= _STORM_WINDOW_S)
        if self.replica_id is None:
            # shed counters mean CLIENT-VISIBLE rejections.  A fleet
            # replica's queue-full may still be served by a sibling
            # (failover spill), so inside a router the ROUTER counts the
            # shed — exactly once, on final rejection — while the
            # per-replica serve_overload event below keeps the
            # queue-level diagnostic
            self.metrics.count_shed(priority)
        obs.event("serve_overload", rows=int(rows), queue_rows=queue_rows,
                  priority=priority)
        if storm:
            self._flight_dump("overload_storm")

    def _flight_dump(self, reason: str, force: bool = False) -> None:
        """Rate-limited flight-ring dump (no-op when the ring is off).
        ``force`` bypasses the cooldown for one-shot events whose dump
        must not be suppressed by an earlier storm's."""
        now = time.monotonic()
        with self._lock:
            if (not force and self._last_flight_dump is not None
                    and now - self._last_flight_dump < _STORM_COOLDOWN_S):
                return
            self._last_flight_dump = now
        if obs.flight_enabled():
            obs.flight_dump(reason, extra={"stats": self.stats()})

    def _convert(self, raw: np.ndarray, raw_score: bool) -> np.ndarray:
        squeezed = raw if self.num_tpi > 1 else raw[:, 0]
        if raw_score or self.objective is None:
            return squeezed
        return np.asarray(self.objective.convert_output(squeezed))

    # ------------------------------------------------------------------
    def predict(self, X, raw_score: bool = False) -> np.ndarray:
        """Synchronous prediction, bypassing the queue (still bucketed,
        so it shares the bounded compile set with the async path)."""
        X = self._check_input(X)
        t0 = time.perf_counter()
        raw = np.zeros((X.shape[0], self.num_tpi))
        for lo in range(0, X.shape[0], self.max_batch):
            chunk = X[lo:lo + self.max_batch]
            raw[lo:lo + chunk.shape[0]] = self._predict_chunk(chunk)
        self._note_request(X.shape[0], (time.perf_counter() - t0) * 1e3)
        if self._drift is not None:
            try:
                self._drift.observe(X, raw)
                self._drift.maybe_check()
            except Exception as exc:  # noqa: BLE001 — monitor never fails serving
                log.warning("drift observe failed: %s", exc)
        return self._convert(raw, raw_score)

    def _predict_chunk(self, X: np.ndarray) -> np.ndarray:
        if self._degraded:
            self._maybe_reprobe()
        if not self._degraded:
            try:
                return self._run_device(self.space.bin_matrix(X))[0]
            except Exception as exc:  # noqa: BLE001 — degrade, don't fail
                self._note_degraded(exc)
        return self._run_host(X)

    # ------------------------------------------------------------------
    # explanation serving: batched device TreeSHAP (explain/)
    # ------------------------------------------------------------------
    def _ensure_explain(self):
        """Pack the TreeSHAP state on first use: per-leaf path metadata
        (zero fractions from the trees' cover counts), the jitted
        EXTEND/UNWIND kernel, and a second microbatcher with its OWN
        pow2 bucket family — explain rows cost O(leaves x depth^2), so
        they must not share predict's row buckets or its queue budget
        accounting would lie.  Raises on a model without cover counts
        (TreeSHAP cannot be computed) or when explaining is disabled."""
        if not self.explain_enabled:
            raise RuntimeError(
                "explanation serving is disabled (tpu_explain=false)")
        got = self._explain
        if got is not None:
            return got
        with self._explain_lock:
            if self._explain is None:
                from ..explain import forest_shap_fn, stack_explain
                K, F = self.num_tpi, self.num_features
                trees_np = [self.space.tree_arrays_np(t, with_counts=True)
                            for t in self._trees]
                arrays = stack_explain(trees_np, F)
                # the kernel reads only the decision arrays — the counts
                # were folded into the path metadata host-side, so the
                # stacked forest stays count-free (no HBM growth over
                # the predict forest; it IS the predict forest)
                forest = self.forest
                fn = forest_shap_fn(self.space.meta, K, F)
                self._raw_explain_fn = fn
                if self._aot is not None:
                    from .aot import AOTStore
                    self._aot_x_digest = AOTStore._digest_tree(
                        (forest, arrays, self.space.meta))
                    for b in self._bucket_sweep(self.explain_max_batch):
                        st, afn = self._aot.load("explain", self._aot.key(
                            "explain", b, self._aot_x_digest,
                            self._aot_extra))
                        if st == "hit":
                            self._aot_x_fns[b] = afn
                batcher = MicroBatcher(
                    self._execute_explain_batch,
                    max_batch=self.explain_max_batch,
                    max_wait_s=self.explain_max_wait_ms / 1e3,
                    max_queue_rows=self.queue_depth,
                    name="lgbm-serve-explain",
                    shed_fracs=self._shed_fracs)
                self._explain = (forest, arrays, fn, batcher)
        return self._explain

    def warmup_explain(self) -> int:
        """Pre-compile every explain bucket shape (the analog of
        ``warmup`` for the TreeSHAP kernel's own bucket family), AOT-
        persisting missing buckets when the store is armed.  Returns the
        bucket count."""
        self._ensure_explain()
        n = 0
        for size in self._bucket_sweep(self.explain_max_batch):
            if self._aot is not None and size not in self._aot_x_fns:
                self._aot_export_explain(size)
            self._run_device_explain(
                np.zeros((size, self.num_features), np.int32))
            n += 1
        return n

    def _aot_export_explain(self, size: int) -> None:
        """Explain twin of ``_aot_export``: one TreeSHAP bucket lowered,
        compiled, registered, persisted."""
        import jax
        import jax.numpy as jnp
        forest, arrays, _, _ = self._ensure_explain()
        try:
            bins = jnp.asarray(
                np.zeros((size, self.num_features), np.int32))
            if self._device is not None:
                with jax.default_device(self._device):
                    comp = self._raw_explain_fn.lower(
                        forest, arrays, bins).compile()
            else:
                comp = self._raw_explain_fn.lower(
                    forest, arrays, bins).compile()
            self._aot_x_fns[size] = comp
            self._aot.save("explain", self._aot.key(
                "explain", size, self._aot_x_digest, self._aot_extra),
                comp, note={"bucket": size, "model": self.model_name})
        except Exception as exc:  # noqa: BLE001 — store is best-effort
            log.warning("AOT explain export failed for bucket %d (%s: %s)",
                        size, type(exc).__name__, exc)

    def _bucket_explain(self, n: int) -> int:
        b = 1
        while b < n:
            b *= 2
        return min(b, self.explain_max_batch)

    def _run_device_explain(self, bins: np.ndarray, span_ctx=None):
        """Pad to the explain pow2 bucket, run the jitted TreeSHAP scan,
        slice the pad off.  Returns ([n, K, F+1] f64 contributions,
        bucket)."""
        import jax.numpy as jnp
        forest, arrays, fn, _ = self._ensure_explain()
        n = bins.shape[0]
        t_pad0 = time.time()
        b = self._bucket_explain(n)
        if b > n:
            bins = np.concatenate(
                [bins, np.zeros((b - n, bins.shape[1]), bins.dtype)])
        with self._lock:
            self._explain_buckets.add(b)
        t_exec0 = time.time()
        # the explain plane's OWN injection point (ISSUE 10): a wedge in
        # the TreeSHAP kernel must be injectable without touching the
        # predict plane, or the degrade-isolation contract is untestable
        faults.check("serve_explain_device")
        aot_fn = self._aot_x_fns.get(b)
        if aot_fn is not None:
            out = aot_fn(forest, arrays, jnp.asarray(bins))
        elif self._device is not None:
            import jax
            with jax.default_device(self._device):
                out = fn(forest, arrays, jnp.asarray(bins))
        else:
            out = fn(forest, arrays, jnp.asarray(bins))
        contrib = np.asarray(out, dtype=np.float64)[:n]
        if span_ctx:
            t_end = time.time()
            for tid, pid in span_ctx:
                obs.emit_span("explain/pad", t_pad0,
                              (t_exec0 - t_pad0) * 1e3, tid, parent_id=pid,
                              attrs={"rows": n, "bucket": b})
                obs.emit_span("explain/device_execute", t_exec0,
                              (t_end - t_exec0) * 1e3, tid, parent_id=pid,
                              attrs={"bucket": b})
        return contrib, b

    def _run_host_explain(self, X: np.ndarray, span_ctx=None) -> np.ndarray:
        """Degraded path: the host TreeSHAP recursion (core/shap.py) —
        per-row Python, slow, but requests keep succeeding."""
        from ..core.shap import _expected_value, _tree_shap
        t0 = time.time()
        K, F = self.num_tpi, self.num_features
        out = np.zeros((X.shape[0], K, F + 1))
        for i, tree in enumerate(self._trees):
            k = i % K
            out[:, k, F] += _expected_value(tree)
            if tree.num_leaves > 1:
                for r in range(X.shape[0]):
                    _tree_shap(tree, X[r], out[r, k, :F], 0, 0, [],
                               1.0, 1.0, -1)
        if span_ctx:
            dur = (time.time() - t0) * 1e3
            for tid, pid in span_ctx:
                obs.emit_span("explain/host_fallback", t0, dur, tid,
                              parent_id=pid,
                              attrs={"rows": int(X.shape[0])})
        return out

    def _convert_explain(self, contrib: np.ndarray) -> np.ndarray:
        """[n, K, F+1] -> the ``predict_contrib`` surface: [n, F+1], or
        [n, K*(F+1)] for multiclass (last column per class = expected
        value).  Contributions live in raw-score space — no objective
        conversion, matching the host oracle."""
        n, K = contrib.shape[0], self.num_tpi
        return (contrib.reshape(n, K * (self.num_features + 1))
                if K > 1 else contrib[:, 0, :])

    def explain(self, X) -> np.ndarray:
        """Synchronous SHAP contributions, bypassing the queue (still
        bucketed, so it shares the bounded explain compile set with the
        async path)."""
        X = self._check_input(X)
        self._ensure_explain()
        t0 = time.perf_counter()
        K, F = self.num_tpi, self.num_features
        out = np.zeros((X.shape[0], K, F + 1))
        for lo in range(0, X.shape[0], self.explain_max_batch):
            chunk = X[lo:lo + self.explain_max_batch]
            out[lo:lo + chunk.shape[0]] = self._explain_chunk(chunk)
        self._note_explain_request(X.shape[0],
                                   (time.perf_counter() - t0) * 1e3)
        return self._convert_explain(out)

    def _explain_chunk(self, X: np.ndarray) -> np.ndarray:
        if self._degraded:
            self._maybe_reprobe()
        if self._explain_degraded:
            self._maybe_reprobe_explain()
        if not (self._degraded or self._explain_degraded):
            try:
                return self._run_device_explain(
                    self.space.bin_matrix(X))[0]
            except Exception as exc:  # noqa: BLE001 — degrade, don't fail
                self._note_degraded_explain(exc)
        return self._run_host_explain(X)

    def submit_explain(self, X, deadline_ms: Optional[float] = None,
                       trace_id: Optional[str] = None,
                       parent_id: Optional[str] = None,
                       priority: str = "normal") -> Ticket:
        """Queue rows for the next coalesced TreeSHAP batch — the
        explain analog of ``submit`` (same chunking, deadline,
        backpressure and priority-shedding semantics, its own queue +
        bucket family)."""
        X = self._check_input(X)
        if self._closed:
            raise RuntimeError("session is closed")
        # explain-plane injection point (ISSUE 10): a fault here models
        # an admission-side failure (bad pack state, OOM on metadata)
        # distinct from the device kernel's
        faults.check("serve_explain_submit")
        priority = normalize_priority(priority)
        _, _, _, batcher = self._ensure_explain()
        if trace_id is None and obs.span_record_enabled():
            trace_id = obs.new_trace_id()
        deadline = (time.monotonic() + float(deadline_ms) / 1e3
                    if deadline_ms is not None else None)
        parts = []
        try:
            for lo in range(0, max(X.shape[0], 1),
                            self.explain_max_batch):
                chunk = X[lo:lo + self.explain_max_batch]
                req = Request(self.space.bin_matrix(chunk), chunk,
                              deadline=deadline, trace_id=trace_id,
                              parent_id=parent_id, priority=priority)
                parts.append((batcher.submit(req), chunk.shape[0]))
        except ServeOverloadError:
            self._note_overload(X.shape[0], batcher.queue_rows,
                                priority=priority)
            for fut, _ in parts:  # a partially queued ticket must not leak
                fut.cancel()
            raise
        return Ticket(parts, int(X.shape[0]), False, kind="explain",
                      priority=priority)

    def _execute_explain_batch(self, reqs) -> None:
        """Explain batcher callback: expire, coalesce, pad, dispatch the
        TreeSHAP kernel, split — ``_execute_batch`` semantics with the
        explain bucket family, ``explain/*`` spans and the
        ``explain_batch`` event."""
        now = time.monotonic()
        live = []
        for r in reqs:
            if r.future.cancelled():
                continue
            if r.deadline is not None and now > r.deadline:
                waited = (now - r.t_submit) * 1e3
                _safe_resolve(r.future, error=DeadlineExceeded(
                    f"request expired after {waited:.1f}ms in queue"))
            else:
                live.append(r)
        if not live:
            return
        rows = sum(r.n for r in live)
        span_ctx = None
        if obs.span_record_enabled():
            t_dispatch = time.time()
            span_ctx = []
            for r in live:
                tid = r.trace_id or obs.new_trace_id()
                obs.emit_span("explain/queue_wait", r.t_submit_wall,
                              (now - r.t_submit) * 1e3, tid,
                              parent_id=r.parent_id, attrs={"rows": r.n})
                obs.emit_span("explain/coalesce", r.t_submit_wall,
                              max(t_dispatch - r.t_submit_wall, 0.0)
                              * 1e3, tid, parent_id=r.parent_id,
                              attrs={"requests": len(live), "rows": rows})
                span_ctx.append((tid, r.parent_id))
        t0 = time.perf_counter()
        if self._degraded:
            self._maybe_reprobe()
        if self._explain_degraded:
            self._maybe_reprobe_explain()
        degraded = self._degraded or self._explain_degraded
        contrib, bucket = None, rows
        if not degraded:
            try:
                bins = (live[0].bins if len(live) == 1
                        else np.concatenate([r.bins for r in live]))
                contrib, bucket = self._run_device_explain(
                    bins, span_ctx=span_ctx)
            except Exception as exc:  # noqa: BLE001 — degrade, don't fail
                self._note_degraded_explain(exc)
                degraded = True
        if degraded:
            contrib = (np.concatenate([self._run_host_explain(r.raw)
                                       for r in live])
                       if len(live) > 1
                       else self._run_host_explain(live[0].raw,
                                                   span_ctx=span_ctx))
            if span_ctx and len(live) > 1:
                # chunk-level spans would mis-attribute across requests;
                # one fallback span per request trace instead (the
                # predict twin's convention)
                t_end = time.time()
                for tid, pid in span_ctx:
                    obs.emit_span("explain/host_fallback", t_dispatch,
                                  (t_end - t_dispatch) * 1e3, tid,
                                  parent_id=pid, attrs={"rows": rows})
        exec_ms = (time.perf_counter() - t0) * 1e3
        off = 0
        for r in live:
            _safe_resolve(r.future, result=contrib[off:off + r.n])
            off += r.n
        with self._lock:
            self._explain_batches += 1
            self._explain_rows += rows
            self._explain_padded += bucket
        batcher = self._explain[3] if self._explain else None
        obs.event("explain_batch", rows=rows, padded=int(bucket),
                  requests=len(live),
                  queue_rows=batcher.queue_rows if batcher else 0,
                  exec_ms=round(exec_ms, 3), degraded=degraded)

    def _note_explain_request(self, rows: int, total_ms: float,
                              priority: str = "normal") -> None:
        with self._lock:
            self._n_explain += 1
            self._n_explain_ok += 1
            self._xlat_ms.append(total_ms)
            if len(self._xlat_ms) > _LAT_RESERVOIR:
                del self._xlat_ms[:_LAT_RESERVOIR // 2]
        self.metrics.observe_explain(total_ms, ok=True)
        self.metrics.count_served(priority)
        obs.event("explain_request", rows=int(rows),
                  total_ms=round(total_ms, 3), ok=True)

    # ------------------------------------------------------------------
    def submit(self, X, deadline_ms: Optional[float] = None,
               raw_score: bool = False, trace_id: Optional[str] = None,
               parent_id: Optional[str] = None,
               priority: str = "normal") -> Ticket:
        """Queue rows for the next coalesced batch.  Raises
        ``ServeOverloadError`` when the bounded queue is full (explicit
        backpressure) — or when this request's ``priority`` class has
        exhausted its share of the queue budget (load shedding: low
        sheds first).  Oversize submissions are chunked to the batch
        cap; a chunk is never split across device batches.  ``trace_id``
        /``parent_id`` thread the request's trace context through the
        batcher (the HTTP edge mints them from ``X-Request-Id``); a
        direct caller gets a fresh trace id when recording is on."""
        X = self._check_input(X)
        if self._closed:
            raise RuntimeError("session is closed")
        priority = normalize_priority(priority)
        if trace_id is None and obs.span_record_enabled():
            trace_id = obs.new_trace_id()
        deadline = (time.monotonic() + float(deadline_ms) / 1e3
                    if deadline_ms is not None else None)
        parts = []
        try:
            for lo in range(0, max(X.shape[0], 1), self.max_batch):
                chunk = X[lo:lo + self.max_batch]
                req = Request(self.space.bin_matrix(chunk), chunk,
                              deadline=deadline, trace_id=trace_id,
                              parent_id=parent_id, priority=priority)
                parts.append((self._batcher.submit(req), chunk.shape[0]))
        except ServeOverloadError:
            self._note_overload(X.shape[0], self._batcher.queue_rows,
                                priority=priority)
            for fut, _ in parts:  # a partially queued ticket must not leak
                fut.cancel()
            raise
        return Ticket(parts, int(X.shape[0]), raw_score,
                      priority=priority)

    def result(self, ticket: Ticket, timeout: Optional[float] = None
               ) -> np.ndarray:
        """Block for a ticket's predictions (converted like
        ``predict``).  Raises what the batch raised — including
        ``DeadlineExceeded`` for requests that outlived their deadline.
        Request-level accounting (stats + ``serve_request`` events)
        happens HERE, once per ticket, so every outcome the caller sees
        — success, deadline, worker failure, wait timeout — is counted
        the same way."""
        end = None if timeout is None else time.monotonic() + timeout
        chunks = []
        try:
            for fut, _ in ticket.parts:
                left = (None if end is None
                        else max(end - time.monotonic(), 0.0))
                chunks.append(fut.result(left))
        except BaseException as exc:
            self._note_failure(ticket, exc)
            raise
        raw = chunks[0] if len(chunks) == 1 else np.concatenate(chunks)
        total_ms = (time.perf_counter() - ticket.t0) * 1e3
        if ticket.kind == "explain":
            if not ticket.counted:
                ticket.counted = True
                self._note_explain_request(ticket.rows, total_ms,
                                           priority=ticket.priority)
            return self._convert_explain(raw)
        if not ticket.counted:
            ticket.counted = True
            self._note_request(ticket.rows, total_ms,
                               priority=ticket.priority)
        return self._convert(raw, ticket.raw_score)

    def _note_failure(self, ticket: Ticket, exc: BaseException) -> None:
        if ticket.counted:
            return
        ticket.counted = True
        reason = ("deadline" if isinstance(exc, DeadlineExceeded)
                  else type(exc).__name__)
        total_ms = (time.perf_counter() - ticket.t0) * 1e3
        if ticket.kind == "explain":
            with self._lock:
                self._n_explain += 1
                if reason == "deadline":
                    self._n_explain_deadline += 1
            self.metrics.observe_explain(total_ms, ok=False)
            obs.event("explain_request", rows=int(ticket.rows),
                      total_ms=round(total_ms, 3), ok=False, reason=reason)
            return
        with self._lock:
            self._n_req += 1
            if reason == "deadline":
                self._n_deadline += 1
        self.metrics.observe(total_ms, ok=False)
        obs.event("serve_request", rows=int(ticket.rows),
                  total_ms=round(total_ms, 3), ok=False, reason=reason)

    # ------------------------------------------------------------------
    def _execute_batch(self, reqs) -> None:
        """Batcher callback: expire, coalesce, pad, dispatch, split."""
        now = time.monotonic()
        live = []
        for r in reqs:
            if r.future.cancelled():
                # an overloaded submit cancelled its partial ticket; the
                # already-queued chunks must not be scored (resolution
                # races are still possible later — _safe_resolve absorbs
                # them)
                continue
            if r.deadline is not None and now > r.deadline:
                # stats/events for the miss are recorded by result() —
                # the one accounting point every outcome flows through
                waited = (now - r.t_submit) * 1e3
                _safe_resolve(r.future, error=DeadlineExceeded(
                    f"request expired after {waited:.1f}ms in queue"))
            else:
                live.append(r)
        if not live:
            return
        rows = sum(r.n for r in live)
        span_ctx = None
        if obs.span_record_enabled():
            # queue-wait + coalesce spans per member request: the batch
            # phases are shared wall time, but each request's trace tree
            # must carry the whole queue->coalesce->pad->execute chain
            t_dispatch = time.time()
            span_ctx = []
            for r in live:
                tid = r.trace_id or obs.new_trace_id()
                obs.emit_span("serve/queue_wait", r.t_submit_wall,
                              (now - r.t_submit) * 1e3, tid,
                              parent_id=r.parent_id,
                              attrs={"rows": r.n})
                # the coalesce span starts at THIS request's submit, not
                # the batch's oldest member — a child slice must not
                # begin before its root span nor charge other requests'
                # wait to this trace
                obs.emit_span("serve/coalesce", r.t_submit_wall,
                              max(t_dispatch - r.t_submit_wall, 0.0)
                              * 1e3, tid, parent_id=r.parent_id,
                              attrs={"requests": len(live), "rows": rows})
                span_ctx.append((tid, r.parent_id))
        t0 = time.perf_counter()
        if self._degraded:
            self._maybe_reprobe()
        degraded = self._degraded
        raw, bucket = None, rows
        if not degraded:
            try:
                bins = (live[0].bins if len(live) == 1
                        else np.concatenate([r.bins for r in live]))
                raw, bucket = self._run_device(bins, span_ctx=span_ctx)
            except Exception as exc:  # noqa: BLE001 — degrade, don't fail
                self._note_degraded(exc)
                degraded = True
        if degraded:
            raw = (np.concatenate([self._run_host(r.raw) for r in live])
                   if len(live) > 1
                   else self._run_host(live[0].raw, span_ctx=span_ctx))
            if span_ctx and len(live) > 1:
                # chunk-level spans would mis-attribute across requests;
                # one fallback span per request trace instead
                t_end = time.time()
                for tid, pid in span_ctx:
                    obs.emit_span("serve/host_fallback", t_dispatch,
                                  (t_end - t_dispatch) * 1e3, tid,
                                  parent_id=pid, attrs={"rows": rows})
        exec_ms = (time.perf_counter() - t0) * 1e3
        if self._drift is not None:
            # before the futures resolve: observe() is a buffered append
            # (histogramming runs on flush), so the latency cost is a few
            # microseconds — and a caller that saw result() return can
            # then force a check knowing this batch is already in the
            # sketch. maybe_check (the expensive part) stays after.
            try:
                self._drift.observe([r.raw for r in live], raw)
            except Exception as exc:  # noqa: BLE001 — monitor never fails serving
                log.warning("drift observe failed: %s", exc)
        off = 0
        for r in live:
            _safe_resolve(r.future, result=raw[off:off + r.n])
            off += r.n
        if self._drift is not None:
            try:
                self._drift.maybe_check()
            except Exception as exc:  # noqa: BLE001 — monitor never fails serving
                log.warning("drift observe failed: %s", exc)
        with self._lock:
            self._batches += 1
            self._real_rows += rows
            self._padded_rows += bucket
        obs.event("serve_batch", rows=rows, padded=int(bucket),
                  requests=len(live), queue_rows=self._batcher.queue_rows,
                  exec_ms=round(exec_ms, 3), degraded=degraded)

    def _check_input(self, X) -> np.ndarray:
        X = np.ascontiguousarray(np.asarray(X), dtype=np.float64)
        if X.ndim == 1:
            X = X[None, :]
        if X.ndim != 2 or X.shape[1] != self.num_features:
            raise ValueError(
                f"The number of features in data "
                f"({X.shape[1] if X.ndim == 2 else '?'}) is not the same "
                f"as it was in training data ({self.num_features})")
        return X

    def _note_request(self, rows: int, total_ms: float,
                      priority: str = "normal") -> None:
        with self._lock:
            self._n_req += 1
            self._n_ok += 1
            self._lat_ms.append(total_ms)
            if len(self._lat_ms) > _LAT_RESERVOIR:
                del self._lat_ms[:_LAT_RESERVOIR // 2]
        self.metrics.observe(total_ms, ok=True)
        self.metrics.count_served(priority)
        obs.event("serve_request", rows=int(rows),
                  total_ms=round(total_ms, 3), ok=True)

    # ------------------------------------------------------------------
    def resident_bytes(self) -> int:
        """Device bytes this session's packed model holds resident: the
        stacked forest plus (when armed) the TreeSHAP arrays — the
        per-version residency figure behind
        ``tpu_serve_resident_bytes`` (the first brick of
        memory-pressure-aware registry residency)."""
        import jax
        total = 0
        for leaf in jax.tree_util.tree_leaves(self.forest):
            if hasattr(leaf, "nbytes"):
                total += int(leaf.nbytes)
        if self._explain is not None:
            for leaf in jax.tree_util.tree_leaves(self._explain[:3]):
                if hasattr(leaf, "nbytes"):
                    total += int(leaf.nbytes)
        return total

    def stats(self) -> dict:
        """Serving counters + latency percentiles (for /health and the
        serve bench)."""
        from ..obs.report import percentile
        with self._lock:
            lat = sorted(self._lat_ms)
            xlat = sorted(self._xlat_ms)

            def pct(p):
                return percentile(lat, p)

            padded = self._padded_rows
            explain = {
                "explain_enabled": self.explain_enabled,
                "explain_armed": self._explain is not None,
                "explain_requests": self._n_explain,
                "explain_ok": self._n_explain_ok,
                "explain_batches": self._explain_batches,
                "explain_rows": self._explain_rows,
                "explain_padded_rows": self._explain_padded,
                "explain_occupancy": (
                    round(self._explain_rows / self._explain_padded, 4)
                    if self._explain_padded else None),
                "explain_p50_ms": percentile(xlat, 0.50),
                "explain_p99_ms": percentile(xlat, 0.99),
                "explain_buckets": sorted(self._explain_buckets),
                "explain_max_batch": self.explain_max_batch,
                "explain_deadline_missed": self._n_explain_deadline,
                "explain_degraded": self._explain_degraded,
            }
            return {
                **explain,
                "requests": self._n_req,
                "ok": self._n_ok,
                "deadline_missed": self._n_deadline,
                "overloads": self._n_overload,
                "batches": self._batches,
                "rows": self._real_rows,
                "padded_rows": padded,
                "occupancy": (round(self._real_rows / padded, 4)
                              if padded else None),
                "p50_ms": pct(0.50),
                "p99_ms": pct(0.99),
                "buckets": sorted(self._buckets),
                "queue_rows": (0 if self._closed
                               else self._batcher.queue_rows),
                "degraded": self._degraded,
                "trees": self.num_trees,
                "num_class": self.num_tpi,
                "num_features": self.num_features,
                "max_batch": self.max_batch,
                # load-balancer-grade health signals (ISSUE 6): how long
                # this replica has lived, how many XLA compiles it paid,
                # and how fast it is burning its p99 error budget
                "uptime_s": round(time.time() - self._t_start, 1),
                "compile_count": int(obs.compile_count()
                                     - self._compiles0),
                "slo_p99_ms": self.slo_p99_ms or None,
                "slo_burn": self.metrics.slo_burn(),
                # probe-and-recover (ISSUE 7): degradation is no longer
                # a one-way latch — these say how often it flipped
                "reprobe_s": self.reprobe_s or None,
                "degraded_transitions": self.metrics.degraded_transitions,
                "recoveries": self.metrics.recoveries,
                # fleet identity (None outside a router/registry): which
                # model version this session's numbers belong to
                "model": self.model_name,
                "version": self.model_version,
                "replica": self.replica_id,
                "resident_bytes": self.resident_bytes(),
                # drift plane (obs/drift.py): None when unarmed
                "drift": (self._drift.status()
                          if self._drift is not None else None),
                # AOT executable store (serve/aot.py): None when unarmed.
                # aot_buckets says which bucket shapes dispatch without
                # the jit path at all — a fully-AOT session shows
                # compile_count 0 after a cold boot
                "aot": (None if self._aot is None else {
                    **self._aot.stats(),
                    "buckets": sorted(self._aot_fns),
                    "explain_buckets": sorted(self._aot_x_fns),
                }),
            }

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self._batcher.close()
            if self._explain is not None:
                self._explain[3].close()
            if obs.enabled():
                obs.event("serve_stop", **{k: v for k, v in
                                           self.stats().items()
                                           if not isinstance(v, list)})

    def __enter__(self) -> "PredictorSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
