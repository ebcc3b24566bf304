"""JAX-native instrumentation: one observer of every program JAX builds.

XLA recompilation is the classic silent TPU-performance killer — a shape
or static-argument change retraces the whole grower (~40-60s, see the
_JIT_CACHE note in boosting/gbdt.py) and nothing in the training loop
says so.  ``jax.monitoring`` hands a listener every stage of building a
program as a time span, ``(event, start, end, fun_name=...)`` in unix
seconds (``jax/_src/dispatch.py LogElapsedTimeContextManager``):

- ``jaxpr_trace_duration``: Python tracing of the jitted function (spans
  of the functions it calls nest inside it; only the outermost counts,
  and none that begins while a lowering is open: a stage's start arrives
  too, as a scalar);
- ``jaxpr_to_mlir_module_duration``: lowering the jaxpr to a module (a
  Pallas kernel's Mosaic lowering lands here, once an instance);
- ``backend_compile_duration``: ``compile_or_get_cached``: the cache key,
  then a retrieval from the persistent cache or the compile itself.

The persistent cache's own events (``cache_hits``, ``cache_misses``,
``cache_retrieval_time_sec``, ``compile_time_saved_sec``,
``jax/_src/compiler.py``) come without a name, from inside the backend
span, and are attached to the span that closes after them.

Each backend span closes one **program record**: ``seq`` (its number in
this process), ``fun_name`` (JAX's module name, ``jit(<function>)``),
``t`` (unix start of its first stage), ``trace_s``, ``lower_s``,
``backend_s``, ``cache`` (``hit``: loaded from the persistent cache;
``miss``: compiled and written to it; ``off``: compiled, and the cache was
not asked or keeps no program this small), ``retrieval_s``, ``saved_s``.  The newest ``PROGRAM_LIMIT`` records are kept;
``programs_seen()`` counts all of them, and is what a caller snapshots
around a call to learn whether the call built a program.  The
``jax/compiles`` / ``jax/compile_s`` counters, the cache counters,
:func:`compile_digest` and the ``compile`` telemetry events are all fed
from here.

:func:`install_recompile_hook` registers the listeners, once in a
process: from :func:`.core.enable`, from ``GBDT.init`` and from the
serving sessions, telemetry on or off (jax is imported by then; ``obs``
itself never imports it).  They cannot be unregistered without clearing
everyone's, and fire only when JAX builds or loads a program.
"""
from __future__ import annotations

import threading
from collections import deque
from typing import Any, Dict

from . import core

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_STAGES = (_TRACE_EVENT, _LOWER_EVENT, _BACKEND_EVENT)
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                 "/jax/compilation_cache/cache_misses": "cache_misses"}
_CACHE_DURATIONS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s"}

PROGRAM_LIMIT = 256     # program records kept; programs_seen() counts all
_PENDING_LIMIT = 64     # trace / lower spans waiting for their program

_installed = False
_programs = deque(maxlen=PROGRAM_LIMIT)
_seen = 0
_lock = threading.Lock()    # _seen and _programs: sessions compile on threads
_tls = threading.local()


def _pending() -> dict:
    """This thread's stages that no backend span has closed yet: a program
    is traced, lowered and compiled on the thread that called it."""
    st = getattr(_tls, "pending", None)
    if st is None:
        st = _tls.pending = {"trace": [], "lower": [], "cache": {},
                             "lowering": 0}
    return st


def _inner_name(fun_name):
    """``jit(f)`` / ``pmap(f)`` -> ``f``: the name its trace span carries."""
    if fun_name and fun_name.endswith(")") and "(" in fun_name:
        return fun_name[fun_name.index("(") + 1:-1]
    return fun_name


def _take(spans: list, name, before: float):
    """Pop the newest span called ``name`` that began by ``before`` (and
    whatever is older: its program never came); None if there is none."""
    for i in range(len(spans) - 1, -1, -1):
        if spans[i][0] == name and spans[i][1] <= before:
            got = spans[i]
            del spans[:i + 1]
            return got
    return None


def _on_span(event: str, start: float, end: float, fun_name=None,
             **_kw) -> None:
    if event not in _STAGES:
        return
    st = _pending()
    dur = float(end) - float(start)
    if event == _TRACE_EVENT:
        if st["lowering"]:
            # lowering traces too (the functions a lowering rule calls):
            # that is part of the open lowering, not a program's trace
            return
        # a function's span closes after those of the functions it calls:
        # what began inside it is part of it
        spans = st["trace"]
        while spans and spans[-1][1] >= start:
            spans.pop()
        spans.append((fun_name, start, dur))
        del spans[:-_PENDING_LIMIT]
        return
    if event == _LOWER_EVENT:
        st["lowering"] = max(st["lowering"] - 1, 0)
        traced = _take(st["trace"], _inner_name(fun_name), start)
        st["lower"].append((fun_name, start, dur, traced))
        del st["lower"][:-_PENDING_LIMIT]
        return
    global _seen
    lower = _take(st["lower"], fun_name, start)
    traced = lower[3] if lower else None
    cache, st["cache"] = st["cache"], {}
    first = traced or lower
    rec = {"fun_name": fun_name,
           "t": first[1] if first else start,
           "trace_s": traced[2] if traced else 0.0,
           "lower_s": lower[2] if lower else 0.0,
           "backend_s": dur,
           "cache": ("hit" if cache.get("cache_hits")
                     else "miss" if cache.get("cache_misses") else "off"),
           "retrieval_s": cache.get("retrieval_s", 0.0),
           "saved_s": cache.get("saved_s", 0.0)}
    with _lock:
        rec["seq"] = _seen
        _programs.append(rec)
        _seen += 1
    # straight into the accumulators, bypassing core.count's enabled()
    # gate: the listener outlives disable()/enable() cycles and compile
    # counts are cheap to keep
    core._counters["jax/compiles"] += 1
    core._counters["jax/compile_s"] += dur
    core.event("compile", kind="backend_compile", jit=fun_name or "<top>",
               wall_s=round(dur, 4))


def _on_event(event: str, **_kw) -> None:
    key = _CACHE_EVENTS.get(event)
    if key is None:
        return
    _pending()["cache"][key] = True
    core._counters["jax/compile_" + key] += 1.0
    core.event("compile", kind=key[:-1])    # cache_hit / cache_miss


def _on_stage_start(event: str, _start: float, **_kw) -> None:
    if event == _LOWER_EVENT:
        _pending()["lowering"] += 1


def _on_duration(event: str, secs: float, **_kw) -> None:
    key = _CACHE_DURATIONS.get(event)
    if key is not None:
        _pending()["cache"][key] = float(secs)


def install_recompile_hook() -> bool:
    """Register the listeners (idempotent).  False when jax.monitoring is
    unavailable or the registration API changed."""
    global _installed
    if _installed:
        return True
    try:
        import jax.monitoring as monitoring
        monitoring.register_event_time_span_listener(_on_span)
        monitoring.register_scalar_listener(_on_stage_start)
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
    except Exception:  # noqa: BLE001
        return False
    _installed = True
    return True


def programs_seen() -> int:
    """Programs JAX has built or loaded since the hook was installed."""
    return _seen


def program_records(since: int = 0) -> list:
    """Copies of the kept program records numbered ``since`` or later."""
    with _lock:
        kept = list(_programs)
    return [dict(r) for r in kept if r["seq"] >= since]


core._register_reset(_programs.clear)


def compile_count() -> int:
    """Backend compiles observed since the hook was installed."""
    return int(core._counters.get("jax/compiles", 0))


def compile_seconds() -> float:
    return float(core._counters.get("jax/compile_s", 0.0))


def compile_digest() -> Dict[str, Any]:
    """Compile-plane block for ``core.digest()`` (``{}`` when idle): the
    counters above, and ``by_jit`` by JAX's own ``fun_name`` over the
    program records still held."""
    count = compile_count()
    hits = int(core.counter_value("jax/compile_cache_hits"))
    misses = int(core.counter_value("jax/compile_cache_misses"))
    if not (count or hits or misses):
        return {}
    by_jit: Dict[str, Dict[str, Any]] = {}
    for rec in program_records():
        ent = by_jit.setdefault(rec["fun_name"] or "<top>",
                                {"count": 0, "wall_s": 0.0})
        ent["count"] += 1
        ent["wall_s"] += rec["backend_s"]
    return {
        "compiles": count,
        "wall_s": round(compile_seconds(), 4),
        "by_jit": {k: {"count": v["count"], "wall_s": round(v["wall_s"], 4)}
                   for k, v in sorted(by_jit.items())},
        "cache_hits": hits,
        "cache_misses": misses,
    }
