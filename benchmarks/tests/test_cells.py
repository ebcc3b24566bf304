"""BENCHMARK.json against its contract and against the files it names; the
command's refusals; the cost copy against the program's."""
import json
import os
import re
import subprocess
import sys

import pytest

from harness import cells, costs, line

ROOT = cells.ROOT
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def doc():
    return cells.benchmark_doc()


def test_keys_names_and_limits(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert 1 <= doc["run_seconds"] <= 51 and isinstance(doc["run_seconds"], int)
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in doc[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names), names
    assert all(len(e["why"]) <= 200 for k in ("configs", "workloads")
               for e in doc[k])
    assert 2 <= len(doc["workloads"]) <= 24
    four = [w for w in doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(doc["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in doc["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_name_has_its_files(doc):
    used = set()
    for w in doc["workloads"]:
        cell = cells.load_cell(w["name"])      # raises where they disagree
        assert cell.chips == cell.config["chips"] == w["chips"]
        used.add(w["config"])
    files = [c["file"] for c in doc["configs"]]
    assert len(files) == len(set(files))
    for c in doc["configs"]:
        assert c["name"] in used, f"configuration {c['name']} has no cell"
        assert c["file"].startswith(doc["paths"][0] + "/")
        conf = cells.load_json(os.path.join(ROOT, c["file"]))
        assert "base" not in conf               # a whole file, as it is run
        assert conf["reduced"] == c["reduced"] and conf["source"] == c["source"]
        assert set(conf["reduced"]) == set(conf["reduced_why"])


def test_metrics_are_well_formed(doc):
    e2e = {e["name"]: e for e in doc["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] == 0.1
    for e in doc["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")
        assert e["better"] in ("higher", "lower")
    specs = {s["name"]: s for s in cells.layer_metric_specs()}
    for m in doc["per_layer"]:
        assert m["source"] in SOURCES and m["moves"] in e2e
        spec = specs[m["name"]]                # each has a reader of its own
        assert os.path.exists(os.path.join(
            cells.BENCH_DIR, "reducers", spec["reducer"] + ".py"))
        for k in ("unit", "better", "source", "layer", "moves"):
            assert spec[k] == m[k], (m["name"], k)
    # a roofline share is named <kernel>_roofline and is a percentage
    assert any(m["name"].endswith("_roofline") and m["unit"] == "%"
               for m in doc["per_layer"])


def test_every_reader_is_listed_and_every_listed_metric_has_one(doc):
    # the harness reads every layer_metrics/*.json; the driver wants every
    # per_layer entry of a cell on its traced line: they are the same set
    assert {s["name"] for s in cells.layer_metric_specs()} == \
        {m["name"] for m in doc["per_layer"]}


def _line(doc, cell, traced):
    """A line with exactly what BENCHMARK.json lists for the cell."""
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
           "memory_peak_bytes": 5 * 10 ** 9}
    if traced:
        dev.update(busy_s=1.5, window_s=2.0)
    return {"correct": True, "attempted": 4, "failed": 0, "device": dev,
            "metrics": {n: {"value": 1.0, "unit": u} for n, u in
                        line.listed_metrics(doc, cell, traced).items()}}


@pytest.mark.parametrize("traced", [0, 1])
def test_line_check_finds_what_the_driver_refuses(doc, traced):
    cell = doc["workloads"][0]["name"]
    good = _line(doc, cell, traced)
    assert line.problems(doc, cell, traced, good) == []
    name = sorted(good["metrics"])[0]
    # the first check of PR 22 was refused over a listed metric left out
    bad = {**good, "metrics": {k: v for k, v in good["metrics"].items()
                               if k != name}}
    assert any(name in w for w in line.problems(doc, cell, traced, bad))
    bad = {**good, "metrics": {**good["metrics"],
                               name: {"value": 1.0, "unit": "furlongs"}}}
    assert any("unit" in w for w in line.problems(doc, cell, traced, bad))
    assert line.problems(doc, cell, traced,
                         {k: v for k, v in good.items() if k != "failed"})
    if traced:
        bad = {**good, "device": {**good["device"], "busy_s": 2.5}}
        assert any("busy_s" in w for w in line.problems(doc, cell, 1, bad))
        # a metric of four-chip cells only is not asked of a one-chip cell
        only = [m for m in doc["per_layer"] if "workloads" in m]
        assert all((m["name"] in good["metrics"]) == (cell in m["workloads"])
                   for m in only)


def _run(*args, env=None, cwd=ROOT):
    e = {k: v for k, v in os.environ.items() if k != "LGBM_TPU_FORCE_WAVE"}
    e.update(env or {})
    return subprocess.run([sys.executable, *args], env=e, cwd=cwd,
                          capture_output=True, text=True, timeout=300)


def test_command_refuses_the_cpu(doc):
    r = _run(*doc["command"][1:], "--workload", doc["workloads"][0]["name"],
             "--seed", "1", "--seconds", "1", "--trace", "0",
             env={"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0 and "needs a TPU" in r.stderr
    assert '"correct"' not in r.stdout


def test_command_refuses_the_interpret_switch(doc):
    r = _run(*doc["command"][1:], "--workload", doc["workloads"][0]["name"],
             env={"JAX_PLATFORMS": "cpu", "LGBM_TPU_FORCE_WAVE": "interpret"})
    assert r.returncode != 0 and '"correct"' not in r.stdout


def test_command_fails_alone_in_a_directory(doc, tmp_path):
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, doc["paths"][0]),
                    tmp_path / doc["paths"][0],
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(*doc["command"][1:], "--workload", doc["workloads"][0]["name"],
             env={"JAX_PLATFORMS": "cpu"}, cwd=tmp_path)
    assert r.returncode != 0 and '"correct"' not in r.stdout
    assert "not in this checkout" in r.stderr


def test_charged_flops_are_the_programs():
    from lightgbm_tpu.ops.pallas_hist import wave_kernel_cost
    for B, fb in ((256, 8), (256, 32), (64, 32), (32, 32), (16, 8)):
        for mode in ("highest", "2xbf16", "bf16", "int16", "int8"):
            for packed in (True, False):
                assert costs.wave_kernel_charged_flops(
                    10 ** 6, 28, B, mode, fb, packed) == wave_kernel_cost(
                    10 ** 6, 28, B, mode, feat_block=fb, packed=packed)[0]
    assert costs.hist_pass_min_bytes(10 ** 6, 28) == 36e6
