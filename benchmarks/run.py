#!/usr/bin/env python3
"""One cell, one run, one process.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's files (``harness/cells.py``), checks the device, runs the
cell's traffic kind (``kinds/<kind>.py``), and prints as the last line of
standard output one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, traced, ``breakdown``.  With ``--trace 0`` the
metrics are the cell's end-to-end metrics, taken on the host's clock with the
profiler off; with ``--trace 1`` a short traced window follows the measured
one and the metrics are the per-layer metrics, each read by the reducer its
``layer_metrics/<name>.json`` names (``reducers/<reducer>.py``).

Exits non-zero, with no result line, unless JAX finds a TPU with exactly the
chips the cell asks for, and where the program is not there to import (a
directory that holds only the benchmark).

``--rehearsal`` runs the toy cell of the same name under ``rehearsal/`` on
the CPU, with the kernel interpreted and ``chips`` virtual devices: a test of
control flow.  Its result line says platform ``cpu``, ``correct`` false and
no metric; what it measured on the host is on the ``rehearsal:`` line before
it and is not a device number.
"""
from __future__ import annotations

import argparse
import gzip
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from harness import cells, clock, compiles, line, trace  # noqa: E402
from harness import device as devmod  # noqa: E402


class Context:
    """What a kind and the reducers share for one run."""

    def __init__(self, cell, seed, seconds, traced, devices, specs):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.devices, self.specs = traced, devices, specs
        self.setup_s = None
        self.evidence = {"host": {}, "counters": {}, "memory": {},
                         "trace": None, "trace_steps": 0,
                         "device_kind": devices[0].device_kind,
                         "platform": devices[0].platform}

    def window_starts(self) -> None:
        """Set-up ends here: process start to now."""
        self.setup_s = time.time() - clock.process_start_time()
        self.evidence["host"]["setup_s"] = self.setup_s

    def collect(self, live: dict) -> None:
        """Let each per-layer reader take what only the live system can give
        (a reducer's optional ``collect``); traced runs only."""
        for spec, mod in self.specs:
            fn = getattr(mod, "collect", None)
            if fn is not None:
                fn(spec, live, self)


def _applicable(cell, kind_mod) -> list:
    """``[(spec, reducer module)]``: the per-layer metrics whose end-to-end
    metric this cell reports, with the reader each names."""
    have = set(kind_mod.END_TO_END) | {"setup_s"}
    out = []
    for spec in cells.layer_metric_specs():
        if spec["moves"] not in have:
            continue
        if "chips" in spec and int(spec["chips"]) != cell.chips:
            continue
        out.append((spec, importlib.import_module(
            f"reducers.{spec['reducer']}")))
    return out


def _place_compile_cache(rehearsal: bool) -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where set (JAX has taken it), else the
    fixed ``<checkout>/.jax_cache`` the program uses too; everything is
    cached, however quick to compile.  None on the CPU (a rehearsal)."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if rehearsal and not env:
        return None
    path = env or os.path.join(ROOT, ".jax_cache")
    if not env:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--keep-trace", metavar="DIR", default=None,
                    help="traced runs: also write the parsed trace there, "
                         "as <cell>.trace.json.gz (how fixtures/ was made)")
    args = ap.parse_args(argv)

    cell = cells.load_cell(args.workload, args.rehearsal)
    seconds = args.seconds
    if seconds is None:
        seconds = float(cells.benchmark_doc().get("run_seconds", 10))
    if not os.path.isdir(os.path.join(ROOT, "lightgbm_tpu")):
        print("benchmark: the program (lightgbm_tpu/) is not in this "
              "checkout", file=sys.stderr)
        return 3
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["LGBM_TPU_FORCE_WAVE"] = "interpret"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{cell.chips}").strip()
    elif os.environ.get("LGBM_TPU_FORCE_WAVE"):
        print("benchmark: LGBM_TPU_FORCE_WAVE is set; it interprets the "
              "kernel", file=sys.stderr)
        return 2
    sys.path.insert(1, ROOT)

    devices = devmod.require_devices(cell.chips, args.rehearsal)
    cache_dir = _place_compile_cache(args.rehearsal)
    compiles.install()
    kind_mod = importlib.import_module(f"kinds.{cell.kind}")
    specs = _applicable(cell, kind_mod) if args.trace else []
    ctx = Context(cell, args.seed, seconds, bool(args.trace), devices, specs)
    print(f"benchmark: cell {cell.name} ({cell.config_name} x "
          f"{cell.traffic_name}, kind {cell.kind}) seed {args.seed} "
          f"seconds {seconds} trace {args.trace} on "
          f"{devmod.describe(devices)} cache {cache_dir}", flush=True)

    ctx.evidence["host"]["start_s"] = (time.time()
                                       - clock.process_start_time())
    out = kind_mod.run(ctx)
    ev = ctx.evidence

    dev = devmod.describe(devices)
    dev["memory_peak_bytes"] = ev["memory"].get(
        "peak_bytes", devmod.memory_parts(devices)["peak_bytes"])
    result = {"correct": bool(out["correct"]),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": {}, "device": dev}
    if args.trace:
        for spec, mod in specs:
            val = mod.read(spec, ev)
            if val is not None:
                result["metrics"][spec["name"]] = {"value": val,
                                                   "unit": spec["unit"]}
        if args.keep_trace:
            os.makedirs(args.keep_trace, exist_ok=True)
            with gzip.open(os.path.join(
                    args.keep_trace, cell.name + ".trace.json.gz"), "wt") as fh:
                json.dump(ev["trace"], fh)
        win, t0, t1 = trace.traced_window(ev)
        dev["busy_s"] = trace.busy_seconds(win)
        dev["window_s"] = (t1 - t0) / 1e9
        result["breakdown"] = {
            "device_ops": trace.top_device_ops(win),
            "idle_gaps": trace.idle_gaps(win, t0, t1)}
    else:
        result["metrics"]["setup_s"] = {"value": ctx.setup_s, "unit": "s"}
        for name, val in out["end_to_end"].items():
            result["metrics"][name] = {"value": val,
                                       "unit": kind_mod.END_TO_END[name]}

    detail = {"host": ev["host"], "counters": ev["counters"],
              "memory": ev["memory"], "fullpass": ev.get("fullpass"),
              "checks": ev.get("checks")}
    lacks = (line.problems(cells.benchmark_doc(), cell.name, bool(args.trace),
                           result)
             if cells.benchmark_entry(cell.name) is not None else [])
    if args.rehearsal:
        # a CPU run: nothing here may pass for a device number
        print("rehearsal: " + json.dumps(
            {**detail, "would_print": result, "line_lacks": lacks},
            default=str), flush=True)
        result.update(correct=False, metrics={})
        result.pop("breakdown", None)
        for k in ("busy_s", "window_s"):
            dev.pop(k, None)
    else:
        print("benchmark: detail " + json.dumps(detail, default=str),
              flush=True)
        for why in lacks:
            print(f"benchmark: the driver will refuse this line: {why}",
                  file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
