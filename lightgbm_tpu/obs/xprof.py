"""Measured roofline plane: profiler capture, trace parsing, attribution.

Closes the loop from a captured ``jax.profiler`` trace to the four
analytic cost models.  Four pieces, all CPU-smokeable:

1. **Windowed capture** — :func:`maybe_window` arms a
   :class:`WindowedCapture` around ``tpu_xprof_iters`` mid-train
   iterations (skipping the warmup/compile iteration) when
   ``tpu_xprof`` / ``LGBM_TPU_XPROF`` is set.  ``engine.train`` and
   ``bench.py`` drive it with one ``step()`` per completed iteration;
   the trace lands under the telemetry sink (``<sink>/xprof``) so one
   artifact dir carries both event stream and profile.

2. **Stdlib trace parsing** — :func:`parse_trace_dir` reads the
   ``*.trace.json.gz`` Chrome-trace stream the profiler emits (gzip +
   json only, no tensorboard/tsl import) and never raises on empty,
   truncated, or gzip-corrupt artifacts: failures land in the result's
   ``errors`` list so callers can triage instead of crash.

3. **Attribution + measured roofline** — :func:`attribute` buckets
   complete-event durations by the ``lgbm/*`` scopes the codebase
   already stamps (``core.phase`` TraceAnnotations on the host track,
   ``named_scope`` metadata in device-op names/args on TPU) plus an
   ``unattributed`` residual per device track.  ``measured_rooflines``
   joins the buckets against ``wave_kernel_cost`` / ``partition_cost``
   / ``rank_pair_cost`` / ``shap_cost`` into ``kernel_measured`` rows
   (achieved ms vs model ms, roofline fraction, HBM-vs-MXU bound) that
   the digest, report, Reconciler, bench_history and prof_kernels all
   consume.

4. **Compile observability** — ``obs/trace.py`` holds the process's one
   ``jax.monitoring`` registration (a record a program: per-jit stage
   walls, persistent compile-cache hits/misses), and :func:`watch_jit`
   (composed into ``profile.wrap``) attributes retraces to the argument
   whose signature changed.  Everything surfaces as ``compile`` events,
   board gauges, and :func:`compile_digest`.
"""
from __future__ import annotations

import glob
import gzip
import json
import logging
import os
import re
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from . import core, trace

log = logging.getLogger("lightgbm_tpu.obs.xprof")

__all__ = [
    "WindowedCapture",
    "attribute",
    "compile_digest",
    "maybe_window",
    "measured_rooflines",
    "parse_trace_dir",
    "record_measured",
    "reset_xprof",
    "resolve_trace_dir",
    "resolve_window",
    "trace_files",
    "train_context",
    "watch_jit",
    "xprof_digest",
]

# ---------------------------------------------------------------------------
# trace parsing (stdlib only)
# ---------------------------------------------------------------------------

# scopes stamped by core.phase / profile.wrap / named_scope throughout
# the codebase; anything matching is attributable
_SCOPE_RE = re.compile(r"lgbm/[A-Za-z0-9_.\-]+")

# device-op events whose name is executor plumbing, not kernel work —
# they overlap the real op events and would double-count the residual
_INFRA_RE = re.compile(r"::")


def trace_files(path: str) -> List[str]:
    """All Chrome-trace artifacts under *path* (recursive).

    ``jax.profiler`` writes ``plugins/profile/<ts>/<host>.trace.json.gz``;
    plain ``.trace.json`` is accepted too for hand-built fixtures.
    """
    if not path or not os.path.isdir(path):
        return []
    out = []
    for pat in ("*.trace.json.gz", "*.trace.json"):
        out.extend(glob.glob(os.path.join(path, "**", pat), recursive=True))
    return sorted(set(out))


def _load_trace(path: str) -> Dict[str, Any]:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as fh:
        doc = json.loads(fh.read().decode("utf-8", "replace"))
    if not isinstance(doc, dict):
        raise ValueError("trace root is not an object")
    return doc


def _is_device_track(proc: str, thread: str) -> bool:
    """True when a (process, thread) pair carries real device-op events.

    TPU/GPU traces give ops their own ``/device:...`` processes; CPU
    traces run the XLA thunk executor on host threads whose names carry
    the ``XLA`` client marker.  The plain ``python`` thread is host-side
    profiler noise (every interpreted call) and is never a device track.
    """
    if "/device:" in proc or proc.startswith("/tpu") or proc.startswith("/gpu"):
        return True
    return "xla" in thread.lower()


def parse_trace_dir(path: str) -> Dict[str, Any]:
    """Parse every trace artifact under *path* into one flat op list.

    Never raises for bad artifacts: empty dirs, truncated gzip streams
    and corrupt json all produce an explicit empty result with the
    per-file failure recorded in ``errors``.

    Returns ``{"dir", "files", "parsed", "errors", "ops", "tracks",
    "window_us"}``.  ``ops`` holds only the SCOPED events — each
    ``{"name", "scope", "device", "thread", "dur_us", "ts"}`` with
    ``device`` the process/track label for device tracks and ``""``
    for host annotation events.  Unscoped device-op work is aggregated
    on the fly into ``tracks`` (``{track: {ops, busy_us,
    unattributed_us}}``) — a CPU while-loop can emit 10^5..10^6 tiny
    thunk events per iteration and materializing them all would cost
    hundreds of MB.
    """
    files = trace_files(path)
    out: Dict[str, Any] = {
        "dir": path, "files": len(files), "parsed": 0,
        "errors": [], "ops": [], "tracks": {}, "window_us": 0.0,
    }
    for f in files:
        try:
            doc = _load_trace(f)
        except (OSError, EOFError, ValueError) as exc:
            out["errors"].append(
                "%s: %s" % (os.path.basename(f), type(exc).__name__))
            continue
        events = doc.get("traceEvents")
        if not isinstance(events, list):
            out["errors"].append(
                "%s: no traceEvents list" % os.path.basename(f))
            continue
        out["parsed"] += 1
        _fold_events(events, out)
    return out


def _fold_events(events: Sequence[Any], out: Dict[str, Any]) -> None:
    procs: Dict[Any, str] = {}
    threads: Dict[Tuple[Any, Any], str] = {}
    for e in events:  # metadata pass: pid/tid -> names
        if not isinstance(e, dict) or e.get("ph") != "M":
            continue
        args = e.get("args") or {}
        if e.get("name") == "process_name":
            procs[e.get("pid")] = str(args.get("name", ""))
        elif e.get("name") == "thread_name":
            threads[(e.get("pid"), e.get("tid"))] = str(args.get("name", ""))

    t_lo, t_hi = None, None
    tracks = out["tracks"]
    for e in events:
        if not isinstance(e, dict) or e.get("ph") != "X":
            continue
        try:
            ts = float(e.get("ts", 0.0))
            dur = float(e.get("dur", 0.0))
        except (TypeError, ValueError):
            continue
        if dur < 0:
            continue
        t_lo = ts if t_lo is None else min(t_lo, ts)
        t_hi = ts + dur if t_hi is None else max(t_hi, ts + dur)
        name = str(e.get("name", ""))
        proc = procs.get(e.get("pid"), "")
        thread = threads.get((e.get("pid"), e.get("tid")), "")
        device = _is_device_track(proc, thread)
        scope = _scope_of(name, e.get("args") if device else None)
        if device and not _INFRA_RE.search(name):
            track = proc or "device"
            t = tracks.get(track)
            if t is None:
                t = tracks[track] = {"ops": 0, "busy_us": 0.0,
                                     "unattributed_us": 0.0}
            t["ops"] += 1
            t["busy_us"] += dur
            if scope is None:
                t["unattributed_us"] += dur
        if scope is None:
            continue  # unscoped: host interpreter noise / aggregated above
        out["ops"].append({
            "name": name[:160],
            "scope": scope,
            "device": (proc or "device") if device else "",
            "thread": thread,
            "dur_us": dur,
            "ts": ts,
        })
    if t_lo is not None:
        out["window_us"] = max(out["window_us"], t_hi - t_lo)


def _scope_of(name: str, args: Any) -> Optional[str]:
    if name.startswith("lgbm/"):
        # host TraceAnnotations carry the full phase name verbatim
        # ("lgbm/tree growth" — spaces allowed); device-op paths are
        # slash-separated identifiers ("lgbm/wave_hist/fusion.3") whose
        # first component is the scope
        if " " in name:
            return name
        m = _SCOPE_RE.match(name)
        return m.group(0) if m else name
    m = _SCOPE_RE.search(name)
    if m:
        return m.group(0)
    if isinstance(args, dict):
        # TPU device ops carry the named_scope path in metadata args
        # (long_name / tf_op); scan values only on device tracks
        for v in args.values():
            if isinstance(v, str):
                m = _SCOPE_RE.search(v)
                if m:
                    return m.group(0)
    return None


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------

def attribute(parsed: Dict[str, Any]) -> Dict[str, Any]:
    """Bucket parsed op durations by ``lgbm/*`` scope, per device.

    Returns ``{"window_ms", "kernels": {scope: {ops, measured_ms,
    devices}}, "devices": {track: {ops, busy_ms, unattributed_ms}},
    "errors", "files", "parsed"}``.  The ``unattributed`` residual only
    accumulates on device tracks — host annotation spans either match a
    scope or are interpreter noise, never missing kernel work.
    """
    kernels: Dict[str, Dict[str, Any]] = {}
    for op in parsed.get("ops", ()):
        k = kernels.setdefault(
            op["scope"], {"ops": 0, "measured_ms": 0.0, "devices": set()})
        k["ops"] += 1
        k["measured_ms"] += op["dur_us"] / 1e3
        k["devices"].add(op["device"] or "host")
    for k in kernels.values():
        k["devices"] = sorted(k["devices"])
        k["measured_ms"] = round(k["measured_ms"], 4)
    devices = {
        track: {"ops": int(t["ops"]),
                "busy_ms": round(t["busy_us"] / 1e3, 4),
                "unattributed_ms": round(t["unattributed_us"] / 1e3, 4)}
        for track, t in parsed.get("tracks", {}).items()}
    return {
        "window_ms": round(parsed.get("window_us", 0.0) / 1e3, 4),
        "kernels": kernels,
        "devices": devices,
        "errors": list(parsed.get("errors", ())),
        "files": parsed.get("files", 0),
        "parsed": parsed.get("parsed", 0),
    }


# ---------------------------------------------------------------------------
# measured roofline: join attribution against the analytic cost models
# ---------------------------------------------------------------------------

# scope -> cost-model family.  The hist scopes all describe one full
# histogram pass over the binned matrix; partition scopes move every row
# once per split wave; grad is the objective (rank_pair when lambdarank
# query sizes are in the context); shap is the explainer sweep.
_HIST_SCOPES = frozenset((
    "lgbm/pallas_hist_wave", "lgbm/wave_hist",
    "lgbm/hist_onehot", "lgbm/hist_scatter", "lgbm/hist_wave_xla",
    "lgbm/grow", "lgbm/grow_apply_fused",
))
_PART_SCOPES = frozenset((
    "lgbm/wave_partition", "lgbm/partition", "lgbm/grow_apply",
    "lgbm/apply_leaf", "lgbm/wave_split_phase",
))


def train_context(booster: Any = None, **extra: Any) -> Dict[str, Any]:
    """Cost-model context for :func:`measured_rooflines`.

    Pulls dataset shape and wave-pipeline state off a live ``Booster``
    when given; ``extra`` overrides/extends (``iters`` — the number of
    captured iterations — always comes from the capture window).
    """
    ctx: Dict[str, Any] = {}
    gbdt = getattr(booster, "_gbdt", None)
    if gbdt is not None:
        ds = getattr(gbdt, "train_ds", None)
        if ds is not None:
            ctx["rows"] = int(getattr(ds, "num_data", 0) or 0)
            ctx["features"] = int(getattr(ds, "num_features", 0) or 0)
        cfg = getattr(gbdt, "config", None)
        if cfg is not None:
            ctx["bins"] = int(getattr(cfg, "max_bin", 255) or 255)
            ctx["leaves"] = int(getattr(cfg, "num_leaves", 31) or 31)
        counters = getattr(gbdt, "work_counters", None)
        trees = counters(last=1)["trees"] if counters else None
        if trees:       # the wave grower's own count of its passes
            ctx["partition_passes"] = trees[-1]["route_passes"]
        wi = getattr(gbdt, "_wave_info", None) or {}
        if wi.get("hist_mode"):
            ctx["mode"] = wi["hist_mode"]
        if wi.get("fused_sibling") is not None:
            ctx["fused"] = bool(wi["fused_sibling"])
    ctx.update({k: v for k, v in extra.items() if v is not None})
    return ctx


def _model_cost(scope: str, ctx: Dict[str, Any]
                ) -> Optional[Tuple[float, float, str]]:
    """(flops, nbytes, model-name) for *scope* over the window, or None.

    Costs are per full pass and scaled by ``ctx["iters"]`` (captured
    iterations); scopes with no analytic model stay measured-only rows.
    """
    if not ctx:
        return None
    iters = max(int(ctx.get("iters", 1) or 1), 1)
    N = int(ctx.get("rows", 0) or 0)
    F = int(ctx.get("features", 0) or 0)
    B = int(ctx.get("bins", 255) or 255)
    try:
        if scope in _HIST_SCOPES and N and F:
            from ..ops.pallas_hist import wave_kernel_cost
            flops, nbytes = wave_kernel_cost(
                N, F, B, mode=str(ctx.get("mode") or "2xbf16"),
                packed=bool(ctx.get("packed", False)),
                fused=bool(ctx.get("fused", False)))
            return flops * iters, nbytes * iters, "wave_kernel"
        if scope in _PART_SCOPES and N:
            from ..core.splitter import partition_cost
            splits = max(int(ctx.get("leaves", 31) or 31) - 1, 1)
            flops, nbytes = partition_cost(
                N, splits=splits, passes=ctx.get("partition_passes"))
            return flops * iters, nbytes * iters, "partition"
        if scope == "lgbm/grad" and ctx.get("query_sizes"):
            from ..ops.rank import rank_pair_cost
            sizes = list(ctx["query_sizes"])
            flops, nbytes = rank_pair_cost(
                sizes, int(ctx.get("chunk_elems", 1 << 20)))
            return flops * iters, nbytes * iters, "rank_pair"
        if scope == "lgbm/forest_shap" and ctx.get("shap"):
            from ..ops.treeshap import shap_cost
            flops, nbytes = shap_cost(**ctx["shap"])
            return float(flops), float(nbytes), "shap"
    except Exception as exc:  # a bad context must not kill the report
        log.debug("cost model for %s failed: %s", scope, exc)
    return None


def measured_rooflines(attrib: Dict[str, Any],
                       context: Optional[Dict[str, Any]] = None
                       ) -> List[Dict[str, Any]]:
    """Join attributed kernels against the analytic cost models.

    One row per attributed scope (plus one ``unattributed`` row per
    device track with residual time), shaped for the ``kernel_measured``
    event schema: achieved ms vs roofline-model ms, roofline fraction
    (model/achieved, 1.0 = running at the roofline) and whether the
    model says the kernel is MXU- or HBM-bound.
    """
    context = context or {}
    window_ms = float(attrib.get("window_ms", 0.0) or 0.0)
    rows: List[Dict[str, Any]] = []
    for scope in sorted(attrib.get("kernels", ())):
        k = attrib["kernels"][scope]
        measured_ms = float(k["measured_ms"])
        row: Dict[str, Any] = {
            "kernel": scope,
            "ops": int(k["ops"]),
            "measured_ms": round(measured_ms, 4),
            "window_ms": window_ms,
            "source": "xprof",
            "device": ",".join(k.get("devices", ())) or "host",
        }
        if window_ms > 0:
            row["occupancy"] = round(measured_ms / window_ms, 4)
        cost = _model_cost(scope, context)
        if cost is not None and measured_ms > 0:
            flops, nbytes, model = cost
            try:
                from .profile import device_peaks, roofline_seconds
                pf, pb = device_peaks()
                model_ms = roofline_seconds(flops, nbytes) * 1e3
            except Exception:
                model_ms, pf, pb = 0.0, 0.0, 0.0
            if model_ms > 0:
                row.update({
                    "flops": float(flops), "bytes": float(nbytes),
                    "model": model,
                    "model_ms": round(model_ms, 4),
                    "roofline_frac": round(model_ms / measured_ms, 4),
                    "bound": ("mxu" if pf and pb
                              and flops / pf >= nbytes / pb else "hbm"),
                })
        rows.append(row)
    for dev in sorted(attrib.get("devices", ())):
        d = attrib["devices"][dev]
        if d.get("unattributed_ms", 0.0) <= 0:
            continue
        row = {
            "kernel": "unattributed",
            "ops": int(d["ops"]),
            "measured_ms": round(float(d["unattributed_ms"]), 4),
            "window_ms": window_ms,
            "source": "xprof",
            "device": dev,
        }
        if window_ms > 0:
            row["occupancy"] = round(row["measured_ms"] / window_ms, 4)
        rows.append(row)
    return rows


# ---------------------------------------------------------------------------
# module state: digest + event emission
# ---------------------------------------------------------------------------

def _fresh_state() -> Dict[str, Any]:
    return {"kernels": {}, "window_ms": 0.0, "devices": {},
            "trace_dir": "", "errors": [], "files": 0, "parsed": 0}


_state = _fresh_state()


def record_measured(attrib: Dict[str, Any],
                    context: Optional[Dict[str, Any]] = None,
                    trace_dir: str = "") -> List[Dict[str, Any]]:
    """Emit ``kernel_measured`` events + fold into the xprof digest."""
    rows = measured_rooflines(attrib, context)
    _state["window_ms"] = float(attrib.get("window_ms", 0.0) or 0.0)
    _state["devices"] = {
        d: dict(v) for d, v in attrib.get("devices", {}).items()}
    _state["trace_dir"] = trace_dir or str(attrib.get("dir", ""))
    _state["errors"] = list(attrib.get("errors", ()))
    _state["files"] = int(attrib.get("files", 0) or 0)
    _state["parsed"] = int(attrib.get("parsed", 0) or 0)
    for row in rows:
        key = row["kernel"]
        if key == "unattributed" and row.get("device"):
            key = "unattributed(%s)" % row["device"]
        _state["kernels"][key] = {
            f: row[f] for f in (
                "ops", "measured_ms", "model_ms", "roofline_frac",
                "bound", "occupancy", "model") if f in row}
        core.event("kernel_measured", **row)
    return rows


def xprof_digest() -> Dict[str, Any]:
    """Measured-roofline block for ``core.digest()`` (``{}`` when idle)."""
    if not _state["kernels"] and not _state["errors"]:
        return {}
    out = {
        "window_ms": round(_state["window_ms"], 3),
        "trace_files": _state["files"],
        "trace_parsed": _state["parsed"],
        "kernels": {k: dict(v) for k, v in sorted(_state["kernels"].items())},
    }
    if _state["errors"]:
        out["errors"] = list(_state["errors"])
    if _state["trace_dir"]:
        out["trace_dir"] = _state["trace_dir"]
    return out


# ---------------------------------------------------------------------------
# compile observability
# ---------------------------------------------------------------------------

def compile_digest() -> Dict[str, Any]:
    """Compile-plane block for ``core.digest()`` (``{}`` when idle), read
    off the one observer (``obs/trace.py``): its counters, and ``by_jit``
    by JAX's own ``fun_name`` over the program records it still holds."""
    count = trace.compile_count()
    hits = int(core.counter_value("jax/compile_cache_hits"))
    misses = int(core.counter_value("jax/compile_cache_misses"))
    retraces = int(core.counter_value("jax/retraces"))
    if not (count or hits or misses or retraces):
        return {}
    by_jit: Dict[str, Dict[str, Any]] = {}
    for rec in trace.program_records():
        ent = by_jit.setdefault(rec["fun_name"] or "<top>",
                                {"count": 0, "wall_s": 0.0})
        ent["count"] += 1
        ent["wall_s"] += rec["backend_s"]
    return {
        "compiles": count,
        "wall_s": round(trace.compile_seconds(), 4),
        "by_jit": {k: {"count": v["count"], "wall_s": round(v["wall_s"], 4)}
                   for k, v in sorted(by_jit.items())},
        "cache_hits": hits,
        "cache_misses": misses,
        "retraces": retraces,
    }


# --- retrace attribution ----------------------------------------------------

def _arg_sig(args: Tuple[Any, ...], kwargs: Dict[str, Any]
             ) -> Tuple[Tuple[str, str], ...]:
    """Flat (label, "shape dtype"/repr) signature of a call's leaves."""
    sig: List[Tuple[str, str]] = []

    def leaf(label: str, v: Any) -> None:
        shape = getattr(v, "shape", None)
        dtype = getattr(v, "dtype", None)
        if shape is not None and dtype is not None:
            sig.append((label, "%s %s" % (tuple(shape), dtype)))
        elif isinstance(v, (list, tuple)):
            for i, item in enumerate(v):
                leaf("%s[%d]" % (label, i), item)
        elif isinstance(v, dict):
            for k in sorted(v, key=str):
                leaf("%s[%r]" % (label, k), v[k])
        else:
            sig.append((label, type(v).__name__))

    for i, a in enumerate(args):
        leaf("arg%d" % i, a)
    for k in sorted(kwargs):
        leaf(k, kwargs[k])
    return tuple(sig)


def _sig_diff(old: Tuple[Tuple[str, str], ...],
              new: Tuple[Tuple[str, str], ...]) -> List[str]:
    prev = dict(old)
    cur = dict(new)
    changed = []
    for label in sorted(set(prev) | set(cur)):
        a, b = prev.get(label, "<absent>"), cur.get(label, "<absent>")
        if a != b:
            changed.append("%s: %s -> %s" % (label, a, b))
    return changed or ["call structure changed"]


# true while any WindowedCapture is tracing — _Watched wrappers stamp
# their jit's TraceAnnotation only inside the window
_capturing = [False]


class _Watched:
    """Retrace watcher: flags per-jit argument-signature changes.

    A signature change after the first call is exactly the condition
    under which ``jax.jit`` retraces — the diff names the argument that
    forced it, which is the attribution direction 3's AOT work needs.
    """

    def __init__(self, name: str, fn: Callable):
        self._name = name
        self._fn = fn
        self._last: Optional[Tuple[Tuple[str, str], ...]] = None
        self._sigs: set = set()

    def __getattr__(self, item: str) -> Any:  # lower(), trace(), ...
        return getattr(self._fn, item)

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        try:
            sig = _arg_sig(args, kwargs)
        except Exception:
            sig = None
        if sig is not None:
            if self._last is not None and sig != self._last \
                    and sig not in self._sigs:
                core._counters["jax/retraces"] += 1.0
                changed = _sig_diff(self._last, sig)
                core.event("compile", kind="retrace", jit=self._name,
                           changed=changed[:8],
                           signatures=len(self._sigs) + 1)
                log.info("retrace %s: %s", self._name,
                         "; ".join(changed[:3]))
            self._sigs.add(sig)
            self._last = sig
        if _capturing[0]:
            # stamp the dispatch span so the trace attributes this jit
            # unit even on backends where named_scope metadata is lost
            # (CPU thunks) — the host-side annotation IS the scope
            import jax
            with jax.profiler.TraceAnnotation(self._name):
                return self._fn(*args, **kwargs)
        return self._fn(*args, **kwargs)


def watch_jit(name: str, fn: Optional[Callable]) -> Optional[Callable]:
    """Wrap *fn* with retrace attribution when the xprof plane is armed.

    Identity when disarmed or already wrapped — safe to compose into
    ``profile.wrap`` unconditionally.
    """
    if fn is None or not _armed() or isinstance(fn, _Watched):
        return fn
    return _Watched(name, fn)


# ---------------------------------------------------------------------------
# windowed capture
# ---------------------------------------------------------------------------

def _start_session(out_dir: str) -> None:
    """Open a profiler trace with the Python-call tracer OFF.

    The default ``jax.profiler.start_trace`` traces every interpreter
    call; a GBDT iteration does enough host work that the capture
    drowns in ``$builtins`` frames and ``stop_trace`` spends minutes
    serializing them.

    On the CPU backend the thunk executor emits one TraceMe per HLO op
    *per while-loop iteration*, so capture volume scales with row count
    — keep CPU windows on small shapes (the smoke uses ~500 rows).  TPU
    device tracing does not have this pathology.
    """
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)


class WindowedCapture:
    """Arms ``jax.profiler`` around a few mid-train iterations.

    Drive with one :meth:`step` per *completed* training iteration: the
    first ``skip`` iterations (warmup + compile) pass through, then the
    trace starts, runs ``iters`` iterations, syncs, stops, and ingests
    itself (parse → attribute → ``kernel_measured`` events).  ``close``
    in a ``finally`` handles windows the loop never finished.

    Off the capture window each ``step`` is a couple of integer
    compares; ``hook_s`` accounts that cost so smokes can pin it.
    """

    def __init__(self, out_dir: str, iters: int = 3, skip: int = 1,
                 context: Optional[Dict[str, Any]] = None,
                 sync: Optional[Callable[[], Any]] = None):
        self.out_dir = out_dir
        self.iters = max(int(iters), 1)
        self.skip = max(int(skip), 0)
        self.context = dict(context or {})
        self.context.setdefault("iters", self.iters)
        self._sync = sync
        self._seen = 0
        self._active = False
        self._done = False
        self.hook_s = 0.0
        self.rows: List[Dict[str, Any]] = []
        self.attrib: Optional[Dict[str, Any]] = None
        self.error = ""

    @property
    def active(self) -> bool:
        return self._active

    @property
    def done(self) -> bool:
        return self._done

    def step(self) -> None:
        """Call once after each completed training iteration."""
        if self._done:
            return
        t0 = time.perf_counter()
        self._seen += 1
        if not self._active:
            if self._seen > self.skip:
                self._start()
            self.hook_s += time.perf_counter() - t0
            return
        if self._seen >= self.skip + 1 + self.iters:
            self._finish()
        # while active the capture cost is deliberate, not hook overhead

    def close(self) -> None:
        """Finish an incomplete window (call from ``finally``)."""
        if self._active:
            self._finish()
        self._done = True

    # -- internals ----------------------------------------------------------

    def _start(self) -> None:
        try:
            os.makedirs(self.out_dir, exist_ok=True)
            _start_session(self.out_dir)
        except Exception as exc:  # already tracing / no backend
            self.error = "start_trace: %s" % exc
            log.warning("xprof capture failed to start: %s", exc)
            self._done = True
            return
        self._active = True
        _capturing[0] = True
        log.info("xprof window open: %d iters -> %s", self.iters,
                 self.out_dir)

    def _finish(self) -> None:
        self._active = False
        self._done = True
        _capturing[0] = False
        try:
            if self._sync is not None:
                self._sync()
        except Exception:
            pass
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as exc:
            self.error = "stop_trace: %s" % exc
            log.warning("xprof capture failed to stop: %s", exc)
            return
        self._ingest()

    def _ingest(self) -> None:
        parsed = parse_trace_dir(self.out_dir)
        self.attrib = attribute(parsed)
        self.rows = record_measured(self.attrib, self.context,
                                    trace_dir=self.out_dir)
        try:
            # the Reconciler scores the same rows: per-kernel trace
            # truth over model, beside its coarse phase-wall units
            from .ranks import Reconciler
            units = Reconciler().score_measured(self.rows)
            if units:
                core.event("reconciliation", iteration=int(self._seen),
                           units=units, source="xprof")
        except Exception:
            pass
        if parsed["files"] and not parsed["parsed"]:
            self.error = "unparseable trace: %s" % "; ".join(
                parsed["errors"][:3])
            log.warning("xprof window %s", self.error)
            return
        n_kern = sum(1 for r in self.rows if r["kernel"] != "unattributed")
        log.info("xprof window closed: %d files, %d lgbm kernels, "
                 "window %.1f ms", parsed["parsed"], n_kern,
                 self.attrib["window_ms"])


# ---------------------------------------------------------------------------
# arming: env / config resolution
# ---------------------------------------------------------------------------

_FALSY = ("", "0", "false", "off", "no")


def _armed(config: Any = None) -> bool:
    return resolve_window(config) > 0


def resolve_window(config: Any = None) -> int:
    """Captured-iteration count, or 0 when the plane is off.

    ``LGBM_TPU_XPROF`` wins over config: ``1``/``true`` arms with
    ``tpu_xprof_iters`` (default 3), a number > 1 sets the window
    directly, falsy strings disarm even when ``tpu_xprof`` is set.
    """
    cfg_iters = int(getattr(config, "tpu_xprof_iters", 0) or 0) or 3
    env = os.environ.get("LGBM_TPU_XPROF", "").strip().lower()
    if env:
        if env in _FALSY[1:]:
            return 0
        if env in ("1", "true", "on", "yes"):
            return cfg_iters
        try:
            return max(int(env), 1)
        except ValueError:
            return cfg_iters
    if getattr(config, "tpu_xprof", False):
        return cfg_iters
    return 0


def resolve_trace_dir(config: Any = None) -> str:
    """Capture dir: env > telemetry sink sibling > tempdir."""
    env = os.environ.get("LGBM_TPU_XPROF_DIR", "")
    if env:
        return env
    sink = core._path or str(getattr(config, "tpu_telemetry", "") or "")
    if sink:
        if sink.endswith(".jsonl"):
            return sink[:-len(".jsonl")] + "_xprof"
        return os.path.join(sink, "xprof")
    import tempfile
    return tempfile.mkdtemp(prefix="lgbm_xprof_")


def maybe_window(config: Any = None,
                 context: Optional[Dict[str, Any]] = None,
                 sync: Optional[Callable[[], Any]] = None,
                 skip: int = 1) -> Optional[WindowedCapture]:
    """Arm a capture window when ``tpu_xprof``/``LGBM_TPU_XPROF`` says so
    (compile walls and cache traffic reach the same digest through the
    observer every trainer installs, ``obs/trace.py``).  Returns None
    when off.
    """
    iters = resolve_window(config)
    if iters <= 0:
        return None
    return WindowedCapture(resolve_trace_dir(config), iters=iters,
                           skip=skip, context=context, sync=sync)


# ---------------------------------------------------------------------------
# reset + env-arming
# ---------------------------------------------------------------------------

def reset_xprof() -> None:
    global _state
    _state = _fresh_state()


core._register_reset(reset_xprof)
