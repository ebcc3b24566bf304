"""Structured training telemetry: events, counters, gauges, phase timers.

This is the always-available observability layer the TIMETAG accumulators
(``utils/timetag.py``, now a façade over this module) grew into.  Two
independent gates:

- ``LGBM_TPU_TIMETAG=1`` — phase wall-time accumulation + atexit report,
  exactly the reference's compiled-in TIMETAG behavior (reference:
  src/treelearner/serial_tree_learner.cpp:21-60).
- ``LGBM_TPU_TELEMETRY=<path>`` (or the ``tpu_telemetry`` parameter, or
  :func:`enable`) — a structured JSONL event stream.  ``<path>`` is a
  directory (files ``telemetry.{process_index}.jsonl`` inside it) or a
  ``*.jsonl`` file (non-zero ranks insert ``.{process_index}`` before the
  extension), so multi-host runs never interleave writers.

Because JAX dispatch is asynchronous, a phase that launches device work
must synchronize before its timer stops or it only measures enqueue time.
``sync(x)`` blocks on ``x`` ONLY while either gate is on, so the training
loop keeps its async pipelining in normal runs (the overlap matters: see
the lag-1 stop note in boosting/gbdt.py).  When both gates are off every
entry point here is a dict lookup + early return — the hot path pays a
few attribute accesses per phase, nothing else.

Events are one JSON object per line, each carrying ``event`` (name) and
``t`` (unix seconds); ``tools/telemetry_report.py`` merges the per-process
files back into per-phase / per-iteration summaries.
"""
from __future__ import annotations

import atexit
import itertools
import json
import math
import os
import sys
import threading
import time
from collections import defaultdict
from typing import Optional

from ..utils import log

TIMETAG_ENABLED = os.environ.get("LGBM_TPU_TIMETAG", "") not in ("", "0",
                                                                 "false")

_acc = defaultdict(float)       # phase name -> accumulated seconds
_cnt = defaultdict(int)         # phase name -> completed enter/exit pairs
_counters = defaultdict(float)  # counter name -> value (monotonic)
_gauges = {}                    # gauge name -> last value

_path: Optional[str] = None     # configured sink (dir or *.jsonl file)
_fh = None                      # lazily-opened per-process file handle
_cur_phase = ""                 # innermost active phase (collective attr.)
_atexit_on = False
_write_warned = False
_spans_active = False           # set by obs.spans (trace mode)
_span_phase_hook = None         # obs.spans phase->span promotion hook
_flight_hook = None             # obs.spans flight-recorder event forward
_board_hook = None              # obs.board live-exporter event forward
_reset_hooks = []               # submodule state cleared by reset()


def _set_spans_active(on: bool, phase_hook=None) -> None:
    """Trace mode flips this so phase timers run (and become spans) even
    without a telemetry sink (obs/spans.py owns the gate; core can't
    import it — spans imports core)."""
    global _spans_active, _span_phase_hook
    _spans_active = bool(on)
    _span_phase_hook = phase_hook
    if on:
        _ensure_atexit()


def _set_flight_hook(hook) -> None:
    """obs/spans.py installs this so operational events reach the flight
    ring even with no sink configured (one None check when disarmed)."""
    global _flight_hook
    _flight_hook = hook


def _set_board_hook(hook) -> None:
    """obs/board.py installs this so the live train exporter sees every
    event (and phase timers accumulate) even with no sink configured —
    same reasoning as the flight hook: core can't import board."""
    global _board_hook
    _board_hook = hook
    if hook is not None:
        _ensure_atexit()


def _register_reset(hook) -> None:
    _reset_hooks.append(hook)


def enabled() -> bool:
    """True when a telemetry sink is configured (events will be written)."""
    return _path is not None


def tracing_enabled() -> bool:
    """True when phase timers accumulate and :func:`sync` blocks."""
    return (TIMETAG_ENABLED or _path is not None or _spans_active
            or _board_hook is not None)


def enable(path: str) -> None:
    """Point the JSONL sink at ``path`` (directory, or a ``*.jsonl`` file).

    Idempotent for the same path; switching paths closes the old sink.
    Also installs the recompile counter (see :mod:`.trace`).
    """
    global _path
    if not path:
        return
    if _path is not None and _path != path:
        _close_sink()
    _path = path
    _ensure_atexit()
    from .trace import install_recompile_hook
    install_recompile_hook()


def disable() -> None:
    """Close the sink and stop writing events (accumulators are kept —
    use :func:`reset` to clear them)."""
    global _path
    _close_sink()
    _path = None


def _close_sink() -> None:
    global _fh
    if _fh is not None:
        try:
            _fh.close()
        except OSError:
            pass
        _fh = None


def _process_index() -> int:
    """This process's rank for the per-process file name.  Resolved
    without initializing a backend on the single-host path (mirrors
    parallel.distributed._runtime_active's reasoning).  Before
    jax.distributed comes up, fall back to the launcher-provided rank
    (same resolution order as parallel.distributed.process_id) so early
    events — dataset construction precedes the in-engine bootstrap —
    land in the right per-process file from the first write."""
    jx = sys.modules.get("jax")
    if jx is not None and jx.distributed.is_initialized():
        return int(jx.process_index())
    for var in ("JAX_PROCESS_ID", "LGBM_TPU_RANK"):
        v = os.environ.get(var, "")
        if v:
            try:
                return int(v)
            except ValueError:
                pass
    try:
        from ..parallel import mesh as _mesh
        r = _mesh.NETWORK.get("rank")
        if r:
            return int(r)
    except Exception:  # noqa: BLE001
        pass
    return 0


def _sink_target(pidx: int) -> str:
    if _path.endswith(".jsonl"):
        if pidx:
            return f"{_path[:-len('.jsonl')]}.{pidx}.jsonl"
        return _path
    return os.path.join(_path, f"telemetry.{pidx}.jsonl")


def sink_path() -> Optional[str]:
    """The resolved per-process file this process writes (None when
    disabled).  Resolves (and creates directories) without opening."""
    if _path is None:
        return None
    return _sink_target(_process_index())


_fh_idx = None  # process index the open handle was resolved with


def _open_sink():
    global _fh, _fh_idx
    idx = _process_index()
    if _fh is not None and idx != _fh_idx:
        # the rank became known after the sink opened (jax.distributed
        # initialized mid-run): move subsequent writes to the right
        # per-process file; the handful of pre-init events stay behind
        # in the old file, flagged by the marker below
        old_target = _sink_target(_fh_idx)
        _close_sink()
        _fh_idx = None
        fh = _open_sink()
        fh.write(json.dumps(
            {"event": "sink_reattached", "t": round(time.time(), 6),
             "early_events_in": os.path.basename(old_target)},
            separators=(",", ":")) + "\n")
        return fh
    if _fh is None:
        _fh_idx = idx
        target = sink_path()
        d = os.path.dirname(target)
        if d:
            os.makedirs(d, exist_ok=True)
        # line-buffered: every event lands on disk at its newline, so a
        # crash mid-run loses at most the record being written
        _fh = open(target, "a", buffering=1)
    return _fh


def _json_default(o):
    try:
        return o.item()  # numpy / jax scalars
    except Exception:  # noqa: BLE001
        return repr(o)


def event(name: str, **fields) -> None:
    """Append one structured record to the JSONL sink (no-op when
    disabled).  Keep field values JSON-representable; numpy scalars are
    unwrapped automatically."""
    if _flight_hook is not None:
        _flight_hook(name, fields)
    if _board_hook is not None:
        _board_hook(name, fields)
    if _path is None:
        return
    rec = {"event": name, "t": round(time.time(), 6)}
    rec.update(fields)
    write_record(rec)


def write_record(rec: dict) -> None:
    """Low-level sink append for a pre-built record (obs/spans.py's span
    records carry their own ``name``/``t`` fields, which the keyword
    surface of :func:`event` cannot express).  No-op when disabled."""
    global _write_warned
    if _path is None:
        return
    try:
        _open_sink().write(
            json.dumps(rec, separators=(",", ":"), default=_json_default)
            + "\n")
    except (OSError, TypeError, ValueError) as exc:
        if not _write_warned:
            _write_warned = True
            log.warning("telemetry write failed (%s); further write "
                        "errors are silenced", exc)


def count(name: str, n=1) -> None:
    """Bump a monotonic counter (no-op when disabled)."""
    if _path is not None or _board_hook is not None:
        _counters[name] += n


def gauge(name: str, value) -> None:
    """Record the latest value of a gauge (no-op when disabled)."""
    if _path is not None or _board_hook is not None:
        _gauges[name] = value


def counter_value(name: str) -> float:
    return _counters.get(name, 0)


def counters_snapshot() -> dict:
    """Counters + gauges as one JSON-friendly dict."""
    out = {}
    for k, v in _counters.items():
        fv = float(v)
        out[k] = int(fv) if fv.is_integer() else round(fv, 6)
    out.update(_gauges)
    return out


# ---------------------------------------------------------------------------
# Phase timers (the TIMETAG accumulators) + XLA-profile annotation
# ---------------------------------------------------------------------------

def _trace_annotation(name: str):
    """A jax.profiler.TraceAnnotation so captured XLA profiles carry our
    phase names (``lgbm/<phase>``); None when telemetry is off or jax is
    not imported yet (never import jax from the telemetry layer)."""
    if _path is None:
        return None
    jx = sys.modules.get("jax")
    if jx is None:
        return None
    try:
        return jx.profiler.TraceAnnotation("lgbm/" + name)
    except Exception:  # noqa: BLE001
        return None


SETUP_SPAN_LIMIT = 64   # spans one set-up root keeps; the rest are counted

_setup_tls = threading.local()      # .trace: the thread's open SetupTrace
_setup_ids = itertools.count(1)


class SetupTrace:
    """The spans of one object's set-up (a binned data set, a trainer), kept
    on that object whether telemetry is on or off.  A ``phase`` given
    ``record=<this>`` is a set-up root; until it closes, every phase the
    thread opens is recorded here, as a record of ``obs/spans.py``'s schema
    with the duration in seconds: ``name``, ``t`` (unix seconds at entry),
    ``dur_s`` (``perf_counter``), ``span_id``, ``parent_id``, ``attrs``.
    Two clock reads a span; ``SETUP_SPAN_LIMIT`` spans, the rest counted in
    ``dropped``."""

    __slots__ = ("spans", "dropped", "_open")

    def __init__(self):
        self.spans = []
        self.dropped = 0
        self._open = []     # span ids of the phases open now, outermost first

    def add(self, name: str, t: float, dur_s: float, span_id=None,
            parent_id=None, **attrs) -> None:
        """Record a closed span: a phase's, or one timed by the caller (the
        trainer's ``update`` spans)."""
        if len(self.spans) >= SETUP_SPAN_LIMIT:
            self.dropped += 1
            return
        self.spans.append({"name": name, "t": t, "dur_s": dur_s,
                           "span_id": span_id or f"u{next(_setup_ids):x}",
                           "parent_id": parent_id, "attrs": attrs})


def open_setup_trace() -> Optional[SetupTrace]:
    """The :class:`SetupTrace` this thread's open set-up root records into,
    or None outside any."""
    return getattr(_setup_tls, "trace", None)


class phase:
    """Context manager accumulating wall time under ``name`` when tracing
    is enabled (exported as ``utils.timetag.timetag``).  Under a set-up
    root (:class:`SetupTrace`) it is recorded there too, tracing or not;
    ``attrs`` (and what the body adds to ``.attrs``) go into that record."""

    __slots__ = ("name", "attrs", "t0", "_t0w", "_on", "_prev", "_ta",
                 "_root", "_rec", "_outer", "_sid")

    def __init__(self, name: str, record: Optional[SetupTrace] = None,
                 **attrs):
        self.name = name
        self.attrs = attrs
        self._root = record
        self._on = False

    def __enter__(self):
        rec = self._root
        if rec is not None:
            self._outer = getattr(_setup_tls, "trace", None)
            _setup_tls.trace = rec
        else:
            rec = getattr(_setup_tls, "trace", None)
        self._rec = rec
        self._t0w = None
        if rec is not None:
            self._sid = f"u{next(_setup_ids):x}"
            rec._open.append(self._sid)
            self._t0w = time.time()
        if tracing_enabled():
            global _cur_phase
            self._on = True
            self._prev = _cur_phase
            _cur_phase = self.name
            self._ta = _trace_annotation(self.name)
            if self._ta is not None:
                self._ta.__enter__()
            # trace mode promotes this timer to a span (obs/spans.py);
            # the span schema wants a wall-clock start
            if self._t0w is None and _span_phase_hook is not None:
                self._t0w = time.time()
        if self._on or rec is not None:
            self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type=None, exc_value=None, tb=None):
        rec = self._rec
        if not self._on and rec is None:
            return False
        dur = time.perf_counter() - self.t0
        if rec is not None:
            rec._open.pop()
            rec.add(self.name, self._t0w, dur, self._sid,
                    rec._open[-1] if rec._open else None, **self.attrs)
            if self._root is not None:
                _setup_tls.trace = self._outer
            self._rec = None
        if self._on:
            global _cur_phase
            _acc[self.name] += dur
            _cnt[self.name] += 1
            _cur_phase = self._prev
            if self._ta is not None:
                self._ta.__exit__(exc_type, exc_value, tb)
            if _span_phase_hook is not None and self._t0w is not None:
                _span_phase_hook(self.name, self._t0w, dur)
            self._on = False
        return False


def current_phase() -> str:
    return _cur_phase


def sync(x):
    """Block on a jax value only when tracing — keeps async dispatch
    intact in normal runs. Returns ``x``."""
    if x is not None and tracing_enabled():
        import jax

        jax.block_until_ready(x)
    return x


def add(name: str, seconds: float) -> None:
    """Manual accumulation for phases timed externally."""
    if tracing_enabled():
        _acc[name] += seconds
        _cnt[name] += 1


def phase_snapshot() -> dict:
    """Current per-phase accumulated seconds (copy)."""
    return dict(_acc)


def phase_delta(snapshot: dict) -> dict:
    """Per-phase seconds accumulated since ``snapshot`` (only phases that
    moved)."""
    out = {}
    for name, total in _acc.items():
        d = total - snapshot.get(name, 0.0)
        if d > 0.0:
            out[name] = round(d, 6)
    return out


def reset() -> None:
    _acc.clear()
    _cnt.clear()
    _counters.clear()
    _gauges.clear()
    for hook in _reset_hooks:
        hook()


def digest() -> dict:
    """Machine-readable run summary: phase totals/call counts + counter
    snapshot (+ the compile plane once JAX has built a program).  The
    atexit ``summary`` event carries it."""
    d = {
        "phase_s": {k: round(v, 4) for k, v in _acc.items()},
        "phase_calls": dict(_cnt),
        "counters": counters_snapshot(),
    }
    from .trace import compile_digest
    comp = compile_digest()
    if comp:
        d["compile"] = comp
    return d


def report() -> None:
    """Print accumulated phase times (reference prints at GBDT/learner
    destructors, gbdt.cpp:46-56) and any counters."""
    if _acc:
        total = sum(_acc.values())
        log.info("TIMETAG phase times:")
        for name, t in sorted(_acc.items(), key=lambda kv: -kv[1]):
            log.info("  %-24s %8.3f s  (%d calls, %4.1f%%)",
                     name, t, _cnt[name], 100.0 * t / total if total else 0.0)
    if _counters:
        log.info("telemetry counters:")
        for name, v in sorted(_counters.items()):
            fv = float(v)
            log.info("  %-32s %s", name,
                     int(fv) if fv.is_integer() else round(fv, 4))


# ---------------------------------------------------------------------------
# Collective-traffic accounting (parallel/mesh.py, parallel/distributed.py)
# ---------------------------------------------------------------------------

def record_collective(kind: str, x) -> None:
    """Account an in-``jit`` collective (psum/all_gather) at TRACE time.

    Inside compiled code the per-execution call can't be observed from
    Python, but tracing sees every collective op with its exact payload
    shape — so these are bytes/calls PER COMPILED PROGRAM EXECUTION
    (counter suffix ``traced_*``); multiply by the grower's execution
    count for total traffic.  Attributed to the phase active when tracing
    ran (tracing happens under the first call's phase timer).
    """
    if _path is None:
        return
    try:
        nbytes = int(math.prod(x.shape)) * int(x.dtype.itemsize)
        shape = list(x.shape)
    except Exception:  # noqa: BLE001 — exotic aval; count the call anyway
        nbytes, shape = 0, None
    _counters[f"collective/{kind}/traced_calls"] += 1
    _counters[f"collective/{kind}/traced_bytes"] += nbytes
    event("collective", kind=kind, bytes=nbytes, shape=shape,
          phase=_cur_phase, traced=True)


def record_collective_host(kind: str, nbytes: int) -> None:
    """Account a host-driven collective (multihost_utils gathers) with its
    ACTUAL runtime byte count."""
    if _path is None:
        return
    _counters[f"collective/{kind}/calls"] += 1
    _counters[f"collective/{kind}/bytes"] += int(nbytes)
    event("collective", kind=kind, bytes=int(nbytes), phase=_cur_phase,
          traced=False)


# ---------------------------------------------------------------------------
# Process lifecycle
# ---------------------------------------------------------------------------

def _at_exit() -> None:
    if _path is not None:
        event("summary", **digest())
        _close_sink()
    if TIMETAG_ENABLED:
        report()


def _ensure_atexit() -> None:
    global _atexit_on
    if not _atexit_on:
        atexit.register(_at_exit)
        _atexit_on = True


if TIMETAG_ENABLED:
    _ensure_atexit()

_env_sink = os.environ.get("LGBM_TPU_TELEMETRY", "")
if _env_sink and _env_sink != "0":
    enable(_env_sink)
