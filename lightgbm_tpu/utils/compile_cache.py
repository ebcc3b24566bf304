"""Persistent XLA compilation-cache wiring.

Every training process pays the grower compile even though the compiled
program is byte-identical run to run.  JAX ships a content-addressed
persistent cache; this module is the ONE place this package decides
where it lives:

- if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already taken that
  directory from its environment — no directory is set in code, and
  that one is reported (the machine that runs the program places the
  cache; the path is part of what makes a later run hit);
- otherwise the ``tpu_compile_cache_dir`` parameter, and failing that
  the fixed ``<checkout>/.jax_cache`` (git-ignored).  A fixed path, never
  a temporary one: a cache directory that moves between runs never hits;
- except that on the CPU backend nothing is placed by default, only by
  the variable or the parameter.  Under jax 0.9.0 an XLA:CPU executable
  that came out of the cache cannot be serialised again (it loads, then
  fails at run time with ``Function ... not found``), which poisons the
  serving executable store (serve/aot.py) of any process that compiled
  against a warm cache; it is not guaranteed bit-identical to a fresh
  compile; and every reload logs two ``cpu_aot_loader`` errors.  On the
  TPU the same round trip is sound (checked on a v5e, PR 21).

``enable_compile_cache`` is idempotent and must run BEFORE the first
``jit`` compilation it should capture; every entry point calls it
(``engine.train``/``cv``, ``GBDT.init``, ``bench.py``,
``chip_smoke.py``).  ``compile_cache_info`` reports the directory in
effect and whether it was WARM (held entries) when enabled, so a
recorded first-call time says which kind of compile it measured.
"""
from __future__ import annotations

import os
from typing import Optional

from . import log

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_state = {"dir": None, "warm": None}


def _entry_count(path: str) -> int:
    try:
        return sum(len(fs) for _, _, fs in os.walk(path))
    except OSError:
        return 0


def enable_compile_cache(path: Optional[str] = None) -> Optional[str]:
    """Turn on JAX's persistent compilation cache and return its
    directory: ``$JAX_COMPILATION_CACHE_DIR`` when set (then nothing is
    set in code), else ``path`` (``tpu_compile_cache_dir``), else
    ``<checkout>/.jax_cache`` — the last not on the CPU backend (see the
    module docstring).

    Returns None when no cache was placed, or when JAX refused the
    configuration (logged, never raised — a cache failure must not cost
    a training run)."""
    import jax
    env = os.environ.get(ENV_DIR, "")
    if not (env or path) and jax.default_backend() == "cpu":
        return None
    p = os.path.abspath(os.path.expanduser(str(env or path or DEFAULT_DIR)))
    if _state["dir"] == p:
        return p
    warm = _entry_count(p) > 0
    try:
        if not env:
            jax.config.update("jax_compilation_cache_dir", p)
        # cache EVERYTHING: the default minimums (1s compile) would skip
        # the many small helper jits around the grower
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        # jax initializes the cache backend lazily at the FIRST compile
        # and then ignores config changes; if anything compiled before
        # this call (warm process, earlier Booster), that decision is
        # already frozen — reset so this configuration takes effect
        from jax.experimental.compilation_cache.compilation_cache import \
            reset_cache
        reset_cache()
    except Exception as exc:  # noqa: BLE001 — see docstring
        log.warning("persistent compilation cache disabled (%s: %s)",
                    type(exc).__name__, exc)
        return None
    _state["dir"] = p
    _state["warm"] = warm
    log.info("persistent XLA compilation cache at %s (%s%s)", p,
             "warm" if warm else "cold",
             f", placed by ${ENV_DIR}" if env else "")
    return p


def compile_cache_info() -> dict:
    """{"dir": path-or-None, "warm": bool-or-None} as of enable time."""
    return dict(_state)


# ---------------------------------------------------------------------
# executable-store plumbing (serve/aot.py)
#
# The XLA cache above still pays trace + lowering + a cache probe per
# bucket shape on every boot.  The serving AOT store (serve/aot.py)
# goes one step further — whole serialized EXECUTABLES, loaded without
# touching the compiler at all — and shares this module's on-disk
# hygiene: durable atomic writes and warm/cold introspection.
# ---------------------------------------------------------------------

def atomic_write_bytes(path: str, data: bytes) -> None:
    """Write ``data`` to ``path`` via a same-directory temp file +
    ``os.replace`` so a concurrent reader (another serving process
    loading the store) sees either the old entry or the complete new
    one, never a torn write.  Raises on failure — callers decide how
    loud a store write failure is."""
    d = os.path.dirname(path) or "."
    os.makedirs(d, exist_ok=True)
    tmp = os.path.join(d, f".{os.path.basename(path)}.tmp.{os.getpid()}")
    try:
        with open(tmp, "wb") as fh:
            fh.write(data)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)
    finally:
        try:
            if os.path.exists(tmp):
                os.remove(tmp)
        except OSError:
            pass


def store_entries(path: Optional[str], suffix: str = ".aot") -> list:
    """Entry filenames under an executable-store directory (sorted;
    empty for a missing/unreadable dir — a cold store, not an error)."""
    if not path:
        return []
    try:
        return sorted(f for f in os.listdir(path) if f.endswith(suffix))
    except OSError:
        return []
