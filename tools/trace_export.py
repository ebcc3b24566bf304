"""Convert telemetry JSONL into Chrome trace-event / Perfetto JSON.

The trace plane (obs/spans.py) writes one ``span`` event per completed
span — serving requests (queue->coalesce->pad->execute) and training
iterations (iteration->phases) share the schema, so this tool renders
BOTH on one timeline: load the output at https://ui.perfetto.dev (or
``chrome://tracing``).

    python tools/trace_export.py /tmp/telem --out trace.json
    python tools/trace_export.py run.jsonl            # -> run.trace.json

Input is anything ``obs.report.load_events`` resolves (a telemetry dir,
a ``.jsonl`` file, or a glob).  Rows:

- every ``span`` event becomes one complete ("ph": "X") trace event;
  ``pid`` is the telemetry process index, ``tid`` a stable per-trace_id
  lane (named via thread_name metadata), so each request/iteration
  renders as its own track;
- when a stream has NO span events (tracing was off) but carries
  ``iteration`` records, per-iteration phase spans are synthesized from
  ``phase_s`` (stacked sequentially inside the iteration window) so a
  plain telemetry run still gets an approximate timeline — synthesized
  events are marked ``args.synthesized``;
- operational-plane events (online refreshes, drift checks, straggler
  breaches and ``compile`` walls) ride on their own ``ops/*`` tracks
  beside the spans (``_OPS_TRACKS``).

Timestamps are rebased to the earliest event so the timeline starts at
zero (Perfetto dislikes 50-year offsets).  Stdlib only.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _span_rows(events):
    return [e for e in events if e.get("event") == "span"
            and isinstance(e.get("t"), (int, float))]


def _synth_from_iterations(events):
    """Approximate span rows from ``iteration`` records: the iteration
    window is exact ([t - iter_s, t]); its phases stack sequentially in
    declaration order (their true overlap is not recorded)."""
    out = []
    for e in events:
        if e.get("event") != "iteration":
            continue
        t1 = e.get("t")
        dur_s = e.get("iter_s")
        if not isinstance(t1, (int, float)) or not dur_s:
            continue
        t0 = t1 - float(dur_s)
        trace = f"train-iter-{e.get('iteration')}"
        proc = e.get("_proc", 0)
        out.append({"event": "span", "t": t0,
                    "dur_ms": float(dur_s) * 1e3,
                    "name": "train/iteration", "trace_id": trace,
                    "span_id": f"it{e.get('iteration')}", "_proc": proc,
                    "attrs": {"iteration": e.get("iteration"),
                              "synthesized": True}})
        cursor = t0
        for phase, s in (e.get("phase_s") or {}).items():
            out.append({"event": "span", "t": cursor,
                        "dur_ms": float(s) * 1e3,
                        "name": f"phase/{phase}", "trace_id": trace,
                        "span_id": f"it{e.get('iteration')}/{phase}",
                        "parent_id": f"it{e.get('iteration')}",
                        "_proc": proc,
                        "attrs": {"synthesized": True}})
            cursor += float(s)
    return out


_OPS_TRACKS = {
    # telemetry event -> (track name, duration-field, scale to ms)
    "online_refresh": ("ops/online", "ms", 1.0),
    "refit": ("ops/online", "wall_s", 1e3),
    "drift_snapshot": ("ops/drift", None, 0.0),
    "quality_window": ("ops/drift", None, 0.0),
    # live-introspection plane (ISSUE 17): straggler breaches as
    # instants on their own track (a straggler event always carries
    # breach=True, so it renders as .../BREACH like a drift latch)
    "straggler": ("ops/straggler", None, 0.0),
    # the compile plane (obs/trace.py): backend-compile walls as spans,
    # cache hits/misses as instants, on their own track
    "compile": ("ops/compile", "wall_s", 1e3),
}


def _synth_ops_tracks(events):
    """Span rows for the operational planes — online refreshes/refits
    as duration spans, drift snapshots and quality windows as instants —
    so the ops cadence renders on its own Perfetto track beside the
    request/iteration spans."""
    out = []
    for e in events:
        kind = e.get("event")
        spec = _OPS_TRACKS.get(kind)
        if spec is None or not isinstance(e.get("t"), (int, float)):
            continue
        trace, dur_field, scale = spec
        dur_ms = (float(e.get(dur_field, 0.0) or 0.0) * scale
                  if dur_field else 0.0)
        attrs = {k: v for k, v in e.items()
                 if k not in ("event", "t", "_proc")
                 and isinstance(v, (int, float, str, bool))}
        attrs["synthesized"] = True
        name = kind
        if kind == "compile" and e.get("kind"):
            name = f"compile/{e['kind']}"
        if e.get("breach"):
            name += "/BREACH"
        out.append({"event": "span", "t": float(e["t"]) - dur_ms / 1e3,
                    "dur_ms": dur_ms, "name": name, "trace_id": trace,
                    "span_id": f"{kind}@{e['t']}",
                    "_proc": e.get("_proc", 0), "attrs": attrs})
    return out


def events_to_chrome(events) -> dict:
    """Merged telemetry events -> a Chrome trace-event document (dict).
    Round-trips: ``json.dump`` the result and Perfetto loads it."""
    spans = _span_rows(events)
    if not spans:
        spans = _synth_from_iterations(events)
    # the ops planes (online refresh/refit, drift/quality checks) ride
    # along whenever present — they have no true span events, so the
    # synthesized track is additive, not a fallback
    spans = spans + _synth_ops_tracks(events)
    if not spans:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    t_min = min(e["t"] for e in spans)
    tids = {}
    trace_events = []
    for e in spans:
        trace = str(e.get("trace_id") or "?")
        pid = int(e.get("_proc", 0) or 0)
        key = (pid, trace)
        if key not in tids:
            tids[key] = len(tids) + 1
            trace_events.append({
                "ph": "M", "name": "thread_name", "pid": pid,
                "tid": tids[key], "args": {"name": trace}})
        args = {"trace_id": trace, "span_id": e.get("span_id")}
        if e.get("parent_id"):
            args["parent_id"] = e["parent_id"]
        args.update(e.get("attrs") or {})
        trace_events.append({
            "ph": "X", "name": str(e.get("name", "?")),
            "cat": str(e.get("name", "?")).split("/")[0],
            "ts": round((float(e["t"]) - t_min) * 1e6, 3),
            "dur": round(float(e.get("dur_ms", 0.0) or 0.0) * 1e3, 3),
            "pid": pid, "tid": tids[key], "args": args})
    return {"traceEvents": trace_events, "displayTimeUnit": "ms",
            "otherData": {"source": "lightgbm_tpu tools/trace_export.py",
                          "t_origin_unix_s": round(t_min, 6),
                          "spans": len(spans), "tracks": len(tids)}}


def export(path: str, out: str) -> dict:
    from lightgbm_tpu.obs.report import load_events
    doc = events_to_chrome(load_events(path))
    with open(out, "w") as fh:
        json.dump(doc, fh)
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Telemetry JSONL -> Chrome trace-event / Perfetto "
                    "JSON (serving request + training iteration spans on "
                    "one timeline)")
    ap.add_argument("path", help="telemetry dir, .jsonl file, or glob")
    ap.add_argument("--out", default="",
                    help="output file (default: <path>.trace.json)")
    args = ap.parse_args(argv)
    base = args.path.rstrip("/")
    if base.endswith(".jsonl"):
        base = base[:-len(".jsonl")]
    out = args.out or base + ".trace.json"
    doc = export(args.path, out)
    n = len(doc["traceEvents"])
    print(f"# wrote {out}: {n} trace event(s)"
          + ("" if n else " (no spans — was LGBM_TPU_TRACE on?)"))
    print("# open at https://ui.perfetto.dev or chrome://tracing")
    return 0 if n else 1


if __name__ == "__main__":
    sys.exit(main())
