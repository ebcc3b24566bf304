"""Count compilations through jax.monitoring.

``/jax/core/compile/backend_compile_duration`` brackets
``compile_or_get_cached``: it fires for every program JAX had to build or
load, whether the persistent cache held it or not.  Inside the measured
window there should be none of either kind.
"""
from __future__ import annotations

_COMPILE = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"

counts = {"programs": 0, "seconds": 0.0, "cache_hits": 0, "cache_misses": 0}
_installed = False


def install() -> None:
    global _installed
    if _installed:
        return
    import jax.monitoring as monitoring

    def on_duration(name, secs, **kw):
        if name == _COMPILE:
            counts["programs"] += 1
            counts["seconds"] += float(secs)

    def on_event(name, **kw):
        if name == _HIT:
            counts["cache_hits"] += 1
        elif name == _MISS:
            counts["cache_misses"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    _installed = True


def snapshot() -> dict:
    return dict(counts)
