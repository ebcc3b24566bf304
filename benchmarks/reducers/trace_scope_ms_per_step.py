"""Device milliseconds per step under the program's named scopes.

Self time of the traced window's operations whose innermost ``lgbm/`` scope
(of their JAX name stack) is one of ``scopes``, averaged over the chips, over
the steps traced.  Returns nothing where the trace resolves no scope at all, or none of
these: a time that was not measured is not printed as 0.
"""
from harness import trace


def read(spec: dict, ev: dict):
    parsed = ev.get("trace")
    if not parsed or not parsed["devices"]:
        return None
    secs = trace.scope_seconds(trace.traced_window(ev)[0], spec["scopes"])
    if not secs:
        return None
    steps = ev.get("trace_steps")
    return secs * 1e3 / steps if steps else None
