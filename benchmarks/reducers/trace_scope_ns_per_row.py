"""Device nanoseconds per row under the program's named scopes.

The self time ``trace_scope_ms_per_step`` takes (operations of the traced
window whose innermost ``lgbm/`` scope is one of ``scopes``, averaged over the
chips), over the rows the program says it put through that scope in the
traced iterations: the product of the ``rows`` terms of its work counters
(``reducers/work_counter.py``; ``"chip": "max"`` takes the fullest chip).  A
time per pass times a count of passes is what ``*_ms_per_iter`` records; this
is the first factor alone.  Nothing where the trace resolves no scope or the
program counts nothing.
"""
from harness import trace
from reducers import work_counter

collect = work_counter.collect


def read(spec: dict, ev: dict):
    parsed, work = ev.get("trace"), ev.get("work")
    if not parsed or not parsed["devices"] or not work:
        return None
    secs = trace.scope_seconds(trace.traced_window(ev)[0], spec["scopes"])
    rows = work_counter.product(work, spec["rows"], spec.get("chip", "sum"))
    if not secs or not rows:
        return None
    return secs * 1e9 / rows
