"""What the driver holds a result line to, as far as the contract says it.

The driver refused PR 22's first check over one traced line: a per-layer
metric that ``BENCHMARK.json`` listed for the cell (``objective.grad_ms_per_
iter``) was not on it, because its reader had found nothing to read.  A
reader may return nothing and the harness then leaves the metric out, but a
metric that is listed for a cell has to be on that cell's line: list only
what today's program lets the reader find.  ``problems`` says what a line
lacks; ``run.py`` prints it on standard error, and
``tools/check_line.py`` checks a recorded line.
"""
from __future__ import annotations

import math

LINE_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


def listed_metrics(doc: dict, workload: str, traced: bool) -> dict:
    """``{name: unit}`` of the metrics ``BENCHMARK.json`` lists for this cell
    in this kind of run: per-layer when traced, end-to-end when not."""
    return {m["name"]: m["unit"]
            for m in doc["per_layer" if traced else "end_to_end"]
            if "workloads" not in m or workload in m["workloads"]}


def problems(doc: dict, workload: str, traced: bool, line: dict) -> list:
    """Why the driver would refuse ``line``; empty if it would not."""
    out = [f"no key {k!r}" for k in LINE_KEYS if k not in line]
    if out:
        return out
    got = line["metrics"]
    for name, unit in listed_metrics(doc, workload, traced).items():
        m = got.get(name)
        if m is None:
            out.append(f"metric {name} is listed for {workload} and is not "
                       f"on the line")
        elif not isinstance(m.get("value"), (int, float)) \
                or not math.isfinite(m["value"]):
            out.append(f"metric {name}: value {m.get('value')!r}")
        elif m.get("unit") != unit:
            out.append(f"metric {name}: unit {m.get('unit')!r}, listed "
                       f"{unit!r}")
    dev = line["device"]
    out += [f"device has no {k!r}" for k in DEVICE_KEYS if k not in dev]
    if traced:
        busy, window = dev.get("busy_s"), dev.get("window_s")
        if busy is None or window is None or not 0 < busy <= window:
            out.append(f"device: busy_s {busy!r} has to be above 0 and at "
                       f"most window_s {window!r}")
        for key, rows in line.get("breakdown", {}).items():
            if len(rows) > 10:
                out.append(f"breakdown.{key} has {len(rows)} entries")
    return out
