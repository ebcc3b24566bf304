"""From a profiler trace to numbers: capture, parse, reduce.

Capture: ``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.

Parse (``parse_xplane``), as read by hand on a v5e trace of this program
(PR 22; ``tools/xplane_summary.py`` prints what was looked at):

- a plane named ``/device:TPU:<n>`` is chip ``n`` (other planes: ``#Chip0
  ...``, ``/device:CUSTOM:Megascale Trace``, ``/host:metadata``, ``Task
  Environment`` are empty or not operations).  Its line ``XLA Ops`` holds one
  event per executed HLO instruction; its line ``XLA Modules`` one event per
  executed program (``jit_grow_apply(<fingerprint>)``).  ``Async XLA Ops``
  (copy-start .. copy-done spans of DMAs that overlap the operations) and the
  empty ``Scalar Unit`` / ``TC Overlay`` lines are not read.
- an event's name is the instruction's whole HLO text (``%fusion.419 = u8[...]
  fusion(...)``); the name kept is ``fusion.419``.  The Pallas kernel is the
  custom call ``pallas_hist_wave.<n>``.
- the JAX name stack, with the program's ``lgbm/<scope>`` names in it, is the
  statistic ``tf_op`` of the event's *metadata* (``jit(grow_apply)/while/body/
  lgbm/wave_partition/gather:``), next to ``hlo_category`` and ``source``
  (file:line).  ``jax.profiler.ProfileData`` does not show metadata
  statistics, hence ``harness/xplane.py``.
- ``while`` and ``conditional`` instructions are events that span the events
  of their bodies on the same line.
- the plane ``/host:CPU`` holds the host threads; the benchmark's own
  ``jax.profiler.TraceAnnotation`` spans (``bench/...``) are events on the
  calling thread's line, on the same clock as the device lines (an update's
  program starts about 1 ms after ``bench/update`` does).
- on the CPU backend (rehearsals) there is no device plane: thunks run on
  ``/host:CPU`` lines named ``tf_XLA...`` with no scope; they are read as one
  pseudo device so that the control flow is exercised, and no device metric
  is printed from them.

The parsed form is plain lists and JSON as it stands, small enough to keep a
recorded chip trace in ``fixtures/`` (``tests/test_trace_reduction.py``).

Reduce: an instruction that spans others (a loop, a branch) is not itself
work.  ``self_times`` gives every event its duration minus what the events
nested in it cover, and all sums are sums of self time; busy time is the
union of the intervals of the events that span no other.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
from collections import defaultdict
from contextlib import contextmanager

from . import xplane

SCOPE_RE = re.compile(r"lgbm/[A-Za-z0-9_.\-]+")
_INSTR_RE = re.compile(r"^%?([^ =]+)")
_SRC_RE = re.compile(r"([^/]+/[^/:]+:\d+)$")


@contextmanager
def capture(trace_dir: str):
    """Trace what runs inside into a fresh ``trace_dir``; host events at the
    level of TraceAnnotation, no Python call tracer."""
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        yield trace_dir
    finally:
        jax.profiler.stop_trace()


def xplane_files(trace_dir: str) -> list:
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))


def _op(start, dur, text, mstats, cache) -> list:
    hit = cache.get(text)
    if hit is None:
        src = _SRC_RE.search(str(mstats.get("source", "")))
        hit = cache[text] = (
            _INSTR_RE.match(text).group(1),
            SCOPE_RE.findall(str(mstats.get("tf_op", ""))),
            src.group(1) if src else str(mstats.get("hlo_category", "")))
    return [start, dur, hit[0], hit[1], hit[2]]


def parse_xplane(path: str) -> dict:
    """``{"devices": {plane: {"ops": [[start_ns, dur_ns, name, [scopes],
    where]], "modules": [[start_ns, dur_ns, name]]}}, "host": [[start_ns,
    dur_ns, name]]}``: ``where`` is the instruction's source ``dir/file:line``
    or, without one, its HLO category; host holds the ``bench/`` annotations."""
    devices, host, cpu_ops = {}, [], []
    for plane in xplane.read(path):
        pname = plane["name"]
        if pname.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": []}
            cache = {}
            for line in plane["lines"]:
                if line["name"] == "XLA Ops":
                    dev["ops"] = [_op(s, d, n, st, cache)
                                  for s, d, n, st in line["events"]]
                elif line["name"] == "XLA Modules":
                    dev["modules"] = [[s, d, n.split("(")[0]]
                                      for s, d, n, _ in line["events"]]
            devices[pname] = dev
        elif pname == "/host:CPU":
            for line in plane["lines"]:
                thunks = line["name"].startswith("tf_XLA")
                for s, d, n, _ in line["events"]:
                    if n.startswith("bench/"):
                        host.append([s, d, n])
                    elif thunks and d > 0 and "::" not in n \
                            and not n.startswith("end: "):
                        cpu_ops.append([s, d, n, SCOPE_RE.findall(n), ""])
    if not devices and cpu_ops:
        devices["/host:CPU (rehearsal)"] = {"ops": cpu_ops, "modules": []}
    return {"devices": devices, "host": host}


def parse_dir(trace_dir: str) -> dict:
    files = xplane_files(trace_dir)
    if not files:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return parse_xplane(files[-1])


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def device_names(parsed: dict) -> list:
    def chip(name):
        m = re.search(r"(\d+)\s*$", name)
        return int(m.group(1)) if m else 0
    return sorted(parsed["devices"], key=chip)


def clip(parsed: dict, t0: float, t1: float) -> dict:
    """The part of a trace inside ``[t0, t1]`` ns (events cut at the edges)."""
    def cut(evs):
        out = []
        for e in evs:
            lo, hi = max(e[0], t0), min(e[0] + e[1], t1)
            if hi > lo:
                out.append([lo, hi - lo, *e[2:]])
        return out
    return {"devices": {k: {"ops": cut(v["ops"]), "modules": cut(v["modules"])}
                        for k, v in parsed["devices"].items()},
            "host": cut(parsed["host"])}


def window_of(parsed: dict, annotation: str = None):
    """``(t0, t1)`` ns: the span of the host annotations named ``annotation``
    (first start to last end), or of everything traced."""
    spans = [(s, s + d) for s, d, n in parsed["host"]
             if annotation is None or n == annotation]
    if not spans:
        spans = [(e[0], e[0] + e[1]) for dev in parsed["devices"].values()
                 for e in dev["ops"]]
    if not spans:
        return 0.0, 0.0
    return min(s for s, _ in spans), max(e for _, e in spans)


def traced_window(ev: dict) -> tuple:
    """``(clipped trace, t0, t1)`` of a run's traced window (the span of the
    ``bench/traced_window`` annotation), worked out once per run."""
    if "trace_window" not in ev:
        t0, t1 = window_of(ev["trace"], "bench/traced_window")
        ev["trace_window"] = (clip(ev["trace"], t0, t1), t0, t1)
    return ev["trace_window"]


def nest(ops: list) -> list:
    """``[[self_ns, spans_others, op]]`` in start order: each event's
    duration minus what the events nested in it on the line cover, and
    whether any is."""
    out, stack = [], []                  # stack: (end, index into out)
    for op in sorted(ops, key=lambda e: (e[0], -e[1])):
        s, d = op[0], op[1]
        while stack and s >= stack[-1][0]:
            stack.pop()
        if stack:
            end, i = stack[-1]
            out[i][0] -= min(d, end - s)
            out[i][1] = True
        out.append([d, False, op])
        stack.append((s + d, len(out) - 1))
    for rec in out:
        rec[0] = max(rec[0], 0.0)
    return out


def busy_intervals(ops: list) -> list:
    """Union of the intervals of the events that span no other (a loop that
    spans its body is not itself work), as sorted ``[start, end]``."""
    out = []
    for _, spans, op in nest(ops):
        if spans:
            continue
        s, e = op[0], op[0] + op[1]
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_seconds(parsed: dict) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    per = [sum(e - s for s, e in busy_intervals(dev["ops"]))
           for dev in parsed["devices"].values()]
    return sum(per) / len(per) / 1e9 if per else 0.0


def scope_seconds(parsed: dict, scopes: list, device: str = None):
    """Self time of the operations whose innermost ``lgbm/`` scope is one of
    ``scopes`` (a partition called inside the split phase is partition, not
    split phase), on ``device`` or averaged over the chips; None if no
    operation carries any scope at all (then the trace cannot resolve scopes
    and nothing is claimed)."""
    want = set(scopes)
    names = [device] if device else list(parsed["devices"])
    per, any_scope = [], False
    for n in names:
        tot = 0.0
        for t, _, op in nest(parsed["devices"][n]["ops"]):
            any_scope = any_scope or bool(op[3])
            if op[3] and op[3][-1] in want:
                tot += t
        per.append(tot)
    if not per or not any_scope:
        return None
    return sum(per) / len(per) / 1e9


def op_seconds(parsed: dict, pattern: str, device: str) -> float:
    """Self time of the operations whose instruction name matches."""
    rx = re.compile(pattern)
    return sum(t for t, _, op in nest(parsed["devices"][device]["ops"])
               if rx.search(op[2])) / 1e9


def _label(op: list) -> str:
    if op[3]:
        return op[3][-1]
    kind = re.sub(r"[.\d]+$", "", op[2])
    return f"unscoped:{op[4]}:{kind}" if op[4] else f"unscoped:{kind}"


def top_device_ops(parsed: dict, n: int = 10) -> list:
    """``[[label, seconds]]`` on the first chip, by self time: the innermost
    ``lgbm/`` scope, or where there is none the instruction's source line (or
    HLO category) and kind."""
    names = device_names(parsed)
    if not names:
        return []
    agg = defaultdict(float)
    for t, _, op in nest(parsed["devices"][names[0]]["ops"]):
        agg[_label(op)] += t
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def idle_gaps(parsed: dict, t0: float, t1: float, n: int = 10) -> list:
    """``[[label, seconds]]``: the first chip's idle time inside ``[t0, t1]``,
    summed by what was going on.  A gap inside a running program is
    ``device:in_program`` (the chip waits on itself); otherwise it takes the
    name of the innermost of the benchmark's host spans over its middle, or
    ``host:unannotated``."""
    names = device_names(parsed)
    if not names:
        return []
    dev = parsed["devices"][names[0]]
    gaps, cur = [], t0
    for s, e in busy_intervals(dev["ops"]):
        if e <= t0 or s >= t1:
            continue
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    mods = sorted((s, s + d) for s, d, _ in dev["modules"])
    mod_starts = [s for s, _ in mods]
    host = sorted((s, s + d, nm) for s, d, nm in parsed["host"])
    agg = defaultdict(float)
    active, nxt = [], 0                  # host spans open at the sweep point
    for gs, ge in gaps:                  # gaps come sorted
        mid = 0.5 * (gs + ge)
        i = bisect.bisect_right(mod_starts, mid) - 1
        if i >= 0 and mods[i][1] >= mid:
            agg["device:in_program"] += ge - gs
            continue
        while nxt < len(host) and host[nxt][0] <= mid:
            active.append(host[nxt])
            nxt += 1
        active = [h for h in active if h[1] >= mid]
        label = (min(active, key=lambda h: h[1] - h[0])[2] if active
                 else "host:unannotated")
        agg[label] += ge - gs
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]
