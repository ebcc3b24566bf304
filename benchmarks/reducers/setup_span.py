"""A time or a count off the program's own record of its set-up.

After the traced window ``collect`` asks the live trainer once for
``Booster.setup_trace()``: the spans the program recorded inside
``Dataset.construct()``, ``Booster(...)`` and every ``update()`` that built
a program, and one record a program JAX built or loaded in this process, all
on the host's unix clock (``lightgbm_tpu/basic.py Booster.setup_trace`` names
the keys).  It leaves a summary under ``evidence["counters"]["setup_trace"]``
for the ``benchmark: detail`` line: every span with its self seconds, every
``update`` span split into its programs' stages and its rest, and the
programs that took 10 ms or more.  A program without the accessor (the
parent of the PR that added it) leaves nothing, and the metrics are left out
of the line.

``read`` gives, by the spec's ``stat``:

- ``self_s``: the duration of the spans named in ``spans`` less what their
  child spans cover (a span's self time), summed;
- ``program_s``: ``field`` (``trace_s``, ``lower_s`` or ``backend_s``) summed
  over every program record up to the return of the newest ``update()``
  (``programs_at_update``): the trainer's set-up, the first call, the later
  warm-up iterations, and the windows, where there should be none.  What
  came later is the benchmark's own: the readers that collect before this
  one launch kernels and draw arrays (``reducers/fullpass.py``);
- ``programs``: the count of those programs, or with ``cache`` of the
  records among them whose ``cache`` field reads that (``miss``: compiled,
  and written to the persistent cache);
- ``update_rest_s``: the ``update`` spans' duration less their programs'
  three stages: Python between the programs, dispatch, any wait inside
  ``update()``;
- ``unattributed_share``: the self time of the roots named in ``spans``
  over their duration, in percent: what no child span covers.
"""
from __future__ import annotations

import sys

STAGES = ("trace_s", "lower_s", "backend_s")


def _accessor(booster):
    """``booster.setup_trace``; one kind hands the readers a view of its
    Booster that carries ``work_counters`` alone (``kinds/boost_cat.py
    _kernel_view``): the method is bound to the Booster itself."""
    fn = getattr(booster, "setup_trace", None)
    if fn is None:
        owner = getattr(getattr(booster, "work_counters", None), "__self__",
                        None)
        fn = getattr(owner, "setup_trace", None)
    return fn


def collect(spec: dict, live: dict, ctx) -> None:
    ev = ctx.evidence
    if "setup_trace" in ev or live.get("booster") is None:
        return
    ev["setup_trace"] = None
    fn = _accessor(live["booster"])
    if fn is None:
        print("benchmark: setup_span: the trainer has no setup_trace(); "
              "metrics left out", file=sys.stderr)
        return
    trace = fn()
    ev["setup_trace"] = trace
    ev["counters"]["setup_trace"] = summary(trace)


def self_seconds(trace: dict) -> dict:
    """``{span_id: seconds}``: each span's duration less its children's,
    a child counted as far as it lies inside its parent."""
    spans = {s["span_id"]: s for s in trace["spans"]}
    out = {sid: s["dur_s"] for sid, s in spans.items()}
    for s in trace["spans"]:
        parent = spans.get(s.get("parent_id"))
        if parent is None:
            continue
        lo = max(s["t"], parent["t"])
        hi = min(s["t"] + s["dur_s"], parent["t"] + parent["dur_s"])
        out[parent["span_id"]] -= max(hi - lo, 0.0)
    return {sid: max(v, 0.0) for sid, v in out.items()}


def update_splits(trace: dict) -> list:
    """One dict an ``update`` span: ``iteration``, ``dur_s``, its programs'
    stage sums, and ``rest_s``, what is left of it."""
    out = []
    for s in trace["spans"]:
        if s["name"] != "update":
            continue
        mine = [p for p in trace["programs"]
                if p.get("parent_id") == s["span_id"]]
        row = {"iteration": s["attrs"].get("iteration"),
               "dur_s": s["dur_s"], "programs": len(mine)}
        for stage in STAGES:
            row[stage] = sum(p[stage] for p in mine)
        row["rest_s"] = s["dur_s"] - sum(row[stage] for stage in STAGES)
        out.append(row)
    return out


def summary(trace: dict) -> dict:
    """What the detail line prints (module text)."""
    selfs = self_seconds(trace)
    names = {s["span_id"]: s["name"] for s in trace["spans"]}
    t0 = min((s["t"] for s in trace["spans"]), default=0.0)
    big = [p for p in trace["programs"]
           if sum(p[stage] for stage in STAGES) >= 0.01]
    return {
        "clock": trace.get("clock"),
        "spans": [{"name": s["name"], "at_s": round(s["t"] - t0, 4),
                   "dur_s": round(s["dur_s"], 4),
                   "self_s": round(selfs[s["span_id"]], 4),
                   "under": names.get(s.get("parent_id")),
                   "attrs": s.get("attrs") or {}}
                  for s in trace["spans"]],
        "updates": [{k: round(v, 4) if isinstance(v, float) else v
                     for k, v in row.items()}
                    for row in update_splits(trace)],
        "programs_seen": trace["programs_seen"],
        "programs_at_update": trace["programs_at_update"],
        "programs_kept": len(trace["programs"]),
        "programs_10ms": [
            {"fun_name": p["fun_name"], "at_s": round(p["t"] - t0, 4),
             **{stage: round(p[stage], 4) for stage in STAGES},
             "cache": p["cache"], "retrieval_s": round(p["retrieval_s"], 4),
             "saved_s": round(p["saved_s"], 4),
             "under": names.get(p.get("parent_id"))} for p in big],
    }


def read(spec: dict, ev: dict):
    trace = ev.get("setup_trace")
    if not trace:
        return None
    stat = spec["stat"]
    if stat in ("program_s", "programs"):
        end = trace["programs_at_update"]
        mine = [p for p in trace["programs"] if p["seq"] < end]
        if stat == "program_s":
            return float(sum(p[spec["field"]] for p in mine))
        want = spec.get("cache")
        return float(end if want is None
                     else sum(1 for p in mine if p["cache"] == want))
    if stat == "update_rest_s":
        rows = update_splits(trace)
        return float(sum(r["rest_s"] for r in rows)) if rows else None
    named = [s for s in trace["spans"] if s["name"] in spec["spans"]]
    if not named:
        return None
    selfs = self_seconds(trace)
    own = sum(selfs[s["span_id"]] for s in named)
    if stat == "self_s":
        return float(own)
    if stat == "unattributed_share":
        whole = sum(s["dur_s"] for s in named)
        return 100.0 * own / whole if whole > 0 else None
    raise ValueError(f"setup_span: unknown stat {stat!r}")
