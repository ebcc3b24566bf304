"""Set-up records its own spans, and one observer records every program.

``Booster.setup_trace()`` on the CPU with the kernel interpreted, for the
three construct paths the benchmark's cells take (a dense matrix, a one-hot
CSR table that EFB bundles, a dense matrix under ``tree_learner=data`` over
four devices): the spans the ``setup.*`` metrics read are there once, inside
their parents and on one clock; an ``update`` span stands for an update that
built a program and for no other; the off path reads no clock and opens no
new phase in a steady-state update; and the compile counters, the digest and
the program records are one record.
"""
import collections
import json
import threading
import time
import traceback

import numpy as np
import pytest

scipy_sparse = pytest.importorskip("scipy.sparse")

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs import core as obs_core

# shapes and a leaf count no other test file trains at: the compiled growth
# programs are cached process-wide, and a worker that had built this one for
# another file would, rightly, leave no ``update`` span here
PARAMS = {"objective": "binary", "num_leaves": 6, "verbose": -1,
          "min_data_in_leaf": 5, "device_type": "tpu"}
DATASET_SPANS = ("convert", "sample", "bin_find", "bundle", "binarize")
BOOSTER_SPANS = ("create", "objective_init", "meta", "plan", "place_bins",
                 "build_grower", "place_scores", "jit_helpers")
STAGES = ("trace_s", "lower_s", "backend_s")
WARM_UPDATES = 3


def _dense(rows=524, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, 6))
    return X, (X[:, 0] + X[:, 1] > 0).astype(np.float64)


def _onehot_csr(rows=1028, seed=4):
    rng = np.random.default_rng(seed)
    X = np.zeros((rows, 22))
    X[np.arange(rows), rng.integers(0, 20, rows)] = 1.0
    X[:, 20:] = np.exp(rng.normal(size=(rows, 2)))
    y = (X[:, :10].sum(axis=1) + 0.5 * rng.normal(size=rows) > 0.5
         ).astype(np.float64)
    return scipy_sparse.csr_matrix(X), y


PATHS = {
    "from_matrix": (_dense, {}),
    "from_csr": (_onehot_csr, {}),
    "mesh4": (_dense, {"tree_learner": "data", "tpu_mesh_shape": "data:4"}),
}


def _booster(X, y, **extra):
    params = {**PARAMS, **extra}
    return lgb.Booster(params=params,
                       train_set=lgb.Dataset(X, label=y, params=params))


@pytest.fixture(scope="module", params=list(PATHS))
def trained(request):
    """``(path, booster, trace)``: a Booster of that path after
    ``WARM_UPDATES`` updates, and its set-up trace then."""
    make, extra = PATHS[request.param]
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
        bst = _booster(*make(), **extra)
        assert bst._gbdt.uses_wave
        for _ in range(WARM_UPDATES):
            bst.update()
        yield request.param, bst, bst.setup_trace()


def test_spans_the_metrics_name_are_there_once(trained):
    path, bst, trace = trained
    assert trace["clock"] == "unix_s" and trace["dropped_spans"] == 0
    json.dumps(trace)
    names = collections.Counter(s["name"] for s in trace["spans"])
    for name in ("setup/dataset", "setup/booster", *BOOSTER_SPANS,
                 "convert", "sample", "bin_find", "binarize"):
        assert names[name] == 1, (name, names)
    # EFB grouping runs where a data set is built for the serial learner
    assert names["bundle"] == (0 if path == "mesh4" else 1)
    assert bst.work_counters(last=0)["bundled"] is (path == "from_csr")
    by_id = {s["span_id"]: s for s in trace["spans"]}
    root = {s["name"]: s for s in trace["spans"] if s["parent_id"] is None}
    assert root["setup/dataset"]["attrs"]["path"] == (
        "from_csr" if path == "from_csr" else "from_matrix")
    for s in trace["spans"]:
        assert set(s) == {"name", "t", "dur_s", "span_id", "parent_id",
                          "attrs"}
        assert s["dur_s"] >= 0
        if s["name"] in DATASET_SPANS:
            assert s["parent_id"] == root["setup/dataset"]["span_id"]
        if s["name"] in BOOSTER_SPANS:
            assert s["parent_id"] == root["setup/booster"]["span_id"]
        parent = by_id.get(s["parent_id"])
        if parent is not None:      # a child lies inside its parent
            assert parent["t"] <= s["t"]
            assert (s["t"] + s["dur_s"]
                    <= parent["t"] + parent["dur_s"] + 1e-3)
    # one clock, in order: the data set, the trainer, the updates
    starts = [s["t"] for s in trace["spans"]]
    assert starts == sorted(starts)
    assert abs(starts[0] - time.time()) < 3600
    order = [s["name"] for s in trace["spans"] if s["parent_id"] is None]
    assert order[:3] == ["setup/dataset", "setup/booster", "update"]
    attrs = {s["name"]: s["attrs"] for s in trace["spans"]}
    rows = bst._gbdt.train_ds.num_data
    assert attrs["bin_find"]["sample_rows"] == rows
    assert attrs["binarize"]["rows"] == rows
    assert attrs["binarize"]["bytes"] == bst._gbdt.train_ds.X_bin.nbytes
    assert attrs["place_bins"]["devices"] == (4 if path == "mesh4" else 1)


def test_first_update_span_holds_its_programs(trained):
    _, bst, trace = trained
    updates = [s for s in trace["spans"] if s["name"] == "update"]
    first = updates[0]
    assert first["attrs"]["iteration"] == 0
    mine = [p for p in trace["programs"]
            if p["parent_id"] == first["span_id"]]
    assert len(mine) == first["attrs"]["programs"] >= 1
    for p in mine:
        assert p["fun_name"] and all(p[k] >= 0.0 for k in STAGES)
        assert p["cache"] in ("hit", "miss", "off")
        assert first["t"] <= p["t"] <= first["t"] + first["dur_s"]
    # the growth program is among them, traced, lowered and compiled
    grow = [p for p in mine if "grow" in p["fun_name"]]
    assert grow and all(p[k] > 0.0 for p in grow for k in STAGES)
    assert sum(p[k] for p in mine for k in STAGES) <= first["dur_s"] + 1e-3
    # warm-up iterations may build helpers; nothing later builds anything
    assert {s["attrs"]["iteration"] for s in updates} <= {0, 1}


def test_steady_updates_leave_nothing_and_read_no_clock(trained,
                                                        monkeypatch):
    _, bst, _ = trained
    g = bst._gbdt
    # the gates are process-wide, and a worker that ran other files first
    # may hold one open (trace mode turns every phase into a span): the
    # off path is what is under test
    obs.disable()
    obs.enable_trace(False)
    obs.enable_profile(False)
    obs.enable_flight(0)
    obs.enable_health("")
    assert not obs.tracing_enabled()
    spans0 = len(g._setup_trace.spans)
    ds_spans0 = len(g.train_ds.setup_trace.spans)
    seen0 = obs.programs_seen()
    opened = []
    clock_reads = []
    real_time, real_enter = time.time, obs_core.phase.__enter__
    me = threading.get_ident()

    def counting_time():
        # this thread's reads only: a worker that ran other files first
        # may still hold their polling threads
        if threading.get_ident() == me:
            clock_reads.append("".join(traceback.format_stack(limit=6)))
        return real_time()

    def counting_enter(self):
        opened[-1].append(self.name)
        return real_enter(self)

    monkeypatch.setattr(time, "time", counting_time)
    monkeypatch.setattr(obs_core.phase, "__enter__", counting_enter)
    for _ in range(10):
        opened.append([])
        bst.update()
    jax.block_until_ready(g._train_score)
    monkeypatch.undo()
    assert obs.programs_seen() == seen0
    assert len(g._setup_trace.spans) == spans0
    assert len(g.train_ds.setup_trace.spans) == ds_spans0
    assert clock_reads == [], clock_reads[:2]
    # every update opens the phases the first of them opened, and none of
    # set-up's
    assert all(names == opened[0] for names in opened), opened
    assert not set(opened[0]) & {"update", *DATASET_SPANS, *BOOSTER_SPANS}


def test_a_retrace_leaves_an_update_span(monkeypatch):
    """A second Booster of another shape builds its growth program anew:
    its first update has a span, its later ones none."""
    monkeypatch.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    bst = _booster(*_dense(rows=644, seed=5))
    seen0 = obs.programs_seen()
    for _ in range(3):
        bst.update()
    trace = bst.setup_trace()
    updates = [s for s in trace["spans"] if s["name"] == "update"]
    assert updates and updates[0]["attrs"]["iteration"] == 0
    assert updates[0]["attrs"]["first_program"] >= seen0
    assert any("grow" in p["fun_name"] for p in trace["programs"]
               if p["parent_id"] == updates[0]["span_id"])
    assert all(s["attrs"]["iteration"] < 2 for s in updates)


def test_counters_digest_and_records_are_one_record():
    obs.reset()
    obs.install_recompile_hook()
    seen0, count0 = obs.programs_seen(), obs.compile_count()

    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2

    @jax.jit
    def outer(x):
        return inner(x).sum() + jnp.cos(x).sum()

    outer(jnp.ones(8))
    outer(jnp.ones(8))                  # cached: no program
    outer(jnp.ones(16))                 # another shape: one more
    built = obs.program_records(seen0)
    assert obs.programs_seen() - seen0 == len(built)
    assert obs.compile_count() - count0 == len(built)
    mine = [p for p in built if p["fun_name"] == "jit(outer)"]
    assert len(mine) == 2
    # the function's own trace, not those of the functions it calls
    assert all(p["trace_s"] > 0 and p["lower_s"] > 0 and p["backend_s"] > 0
               for p in mine)
    assert not [p for p in built if p["fun_name"] == "jit(inner)"]
    assert [p["seq"] for p in built] == list(range(seen0, seen0 + len(built)))
    digest = obs.compile_digest()
    assert digest["compiles"] == obs.compile_count() == len(built)
    assert digest["by_jit"]["jit(outer)"]["count"] == 2
    assert sum(e["count"] for e in digest["by_jit"].values()) == len(built)
    assert digest["wall_s"] == pytest.approx(
        sum(p["backend_s"] for p in built), abs=1e-3)
    assert obs.compile_seconds() == pytest.approx(
        sum(p["backend_s"] for p in built))


def test_setup_trace_event_with_telemetry_on(tmp_path, monkeypatch):
    """``engine.train`` writes the accessor's answer once, after the first
    iteration, and the same phases still feed the accumulators."""
    monkeypatch.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    obs.reset()
    obs.enable(str(tmp_path))
    try:
        X, y = _dense(rows=388, seed=6)
        lgb.train(PARAMS, lgb.Dataset(X, label=y, params=PARAMS), 3)
        phases = obs.phase_snapshot()
    finally:
        obs.disable()
        obs.reset()
    from lightgbm_tpu.obs.report import load_events, validate_events
    events = load_events(str(tmp_path))
    assert not validate_events(events)
    sent = [e for e in events if e["event"] == "setup_trace"]
    assert len(sent) == 1
    names = {s["name"] for s in sent[0]["spans"]}
    assert {"setup/dataset", "setup/booster", "update", "bin_find",
            "place_bins"} <= names
    assert sent[0]["programs"] and sent[0]["clock"] == "unix_s"
    assert {"bin_find", "binarize", "place_bins", "tree growth"} <= set(phases)
