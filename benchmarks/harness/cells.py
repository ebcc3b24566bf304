"""Where a cell's files are, found by the names in ``BENCHMARK.json``.

A cell ``<cell>`` is ``workloads/<cell>.json`` (config, traffic, chips, why,
expect); its configuration is ``configs/<config>.json``, its traffic mix
``traffic/<traffic>.json``, and every per-layer metric is one
``layer_metrics/<metric>.json``.  A later PR adds a cell, a configuration, a
mix or a metric by adding files and ``BENCHMARK.json`` entries; no file here
names one.  Rehearsal cells (toy size, CPU) live in the same layout under
``rehearsal/`` and say ``"base": "<name>"`` to start from the real file of
that name and override keys.
"""
from __future__ import annotations

import glob
import json
import os
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = (_merge(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


def _load_named(kind_dir: str, name: str, rehearsal: bool) -> dict:
    """``<kind_dir>/<name>.json``; under rehearsal the toy file of that name
    if there is one.  A file that says ``"base": "<name>"`` is merged over
    the real file of that name (rehearsals and throw-away cells; a
    configuration in ``BENCHMARK.json`` is a whole file)."""
    path = os.path.join(BENCH_DIR, kind_dir, name + ".json")
    if rehearsal:
        toy = os.path.join(BENCH_DIR, "rehearsal", kind_dir, name + ".json")
        path = toy if os.path.exists(toy) else path
    if not os.path.exists(path):
        raise SystemExit(f"benchmark: no file {os.path.relpath(path, ROOT)}")
    doc = load_json(path)
    base = doc.pop("base", None)
    if base is not None:
        doc = _merge(_load_named(kind_dir, base, False), doc)
    return doc


@dataclass
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    expect: dict = field(default_factory=dict)
    rehearsal: bool = False

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def load_cell(name: str, rehearsal: bool = False) -> Cell:
    doc = _load_named("workloads", name, rehearsal)
    cell = Cell(name=name, chips=int(doc["chips"]), why=doc.get("why", ""),
                config_name=doc["config"], traffic_name=doc["traffic"],
                config=_load_named("configs", doc["config"], rehearsal),
                traffic=_load_named("traffic", doc["traffic"], rehearsal),
                expect=doc.get("expect", {}), rehearsal=rehearsal)
    listed = benchmark_entry(name)
    if listed is not None and not rehearsal:
        # BENCHMARK.json is what the driver reads; the cell's own file is
        # what the harness runs.  They may not disagree.
        got = (cell.config_name, cell.traffic_name, cell.chips)
        want = (listed["config"], listed["traffic"], int(listed["chips"]))
        if got != want:
            raise SystemExit(f"benchmark: workloads/{name}.json says {got}, "
                             f"BENCHMARK.json says {want}")
    return cell


def benchmark_doc() -> dict:
    path = os.path.join(ROOT, "BENCHMARK.json")
    return load_json(path) if os.path.exists(path) else {}


def benchmark_entry(name: str):
    for w in benchmark_doc().get("workloads", []):
        if w["name"] == name:
            return w
    return None


def layer_metric_specs() -> list:
    """Every ``layer_metrics/*.json``, by name.  The file's name is the
    metric's name."""
    out = []
    for path in sorted(glob.glob(os.path.join(BENCH_DIR, "layer_metrics",
                                              "*.json"))):
        spec = load_json(path)
        spec["name"] = os.path.basename(path)[:-len(".json")]
        out.append(spec)
    return out


def scratch_dir(*parts: str) -> str:
    """A fixed directory inside the checkout for what a run leaves behind
    (profiler traces); git-ignored."""
    path = os.path.join(ROOT, ".bench_scratch", *parts)
    os.makedirs(path, exist_ok=True)
    return path
