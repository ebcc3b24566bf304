"""What the benchmark's two readers of private state depend on is there.

``benchmarks/kinds/boost.py _stamps`` and ``benchmarks/reducers/fullpass.py
_launch`` put their reads together from private names of the trainer and the
kernel module; the benchmark's files may not be edited by the PR that moves
one.  Such a PR then loses ``pallas_hist_wave_roofline`` and
``pallas_hist_wave.mxu_charged_share`` from its traced line and the driver
refuses the line (PERF.md 7).  This holds the names, and their shapes, at
home; ``Booster.work_counters()`` is the public twin of the stamps and has
to agree with them.  ``benchmarks/kinds/boost_csr.py`` reads that public twin
alone: its ``stamps``, and the facts ``bundled``, ``features`` and
``phys_columns`` beside them.
"""
import importlib
import inspect
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import pallas_hist as ph

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
STAMP_KEYS = ("uses_wave", "interpret", "hist_mode", "packed",
              "fused_sibling", "fused_grad", "bins_devices")


def _booster(monkeypatch, **extra):
    monkeypatch.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    rng = np.random.default_rng(3)
    X = rng.normal(size=(512, 6))
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              "min_data_in_leaf": 5, "device_type": "tpu", **extra}
    return lgb.Booster(params=params,
                       train_set=lgb.Dataset(X, label=y, params=params))


@pytest.fixture
def benchmark_stamps(monkeypatch):
    """The benchmark's own reader, ``benchmarks/kinds/boost.py _stamps``."""
    monkeypatch.syspath_prepend(BENCH)
    return importlib.import_module("kinds.boost")._stamps


@pytest.mark.parametrize("extra,chips", [
    ({}, 1), ({"tree_learner": "data", "tpu_mesh_shape": "data:4"}, 4)],
    ids=["one_device", "data4"])
def test_trainer_state_the_readers_take(monkeypatch, benchmark_stamps,
                                        extra, chips):
    bst = _booster(monkeypatch, **extra)
    g = bst._gbdt
    assert g.uses_wave is True
    assert {"hist_mode", "wave_capacity", "packed", "fused_sibling",
            "interpret"} <= set(g._wave_info)
    assert g._wave_info["hist_mode"] in ("highest", "2xbf16", "bf16",
                                         *ph.QUANT_MODES)
    # the resident bins: feature-major [F, N], one shard a chip
    bins = g._grow_bins
    assert bins.dtype == np.uint8 and bins.shape == (6, 512)
    assert len(bins.sharding.device_set) == chips
    assert bins.addressable_shards[0].data.shape == (6, 512 // chips)
    assert isinstance(g.B_phys, int) and 0 < g.B_phys <= 256
    assert isinstance(g.config.tpu_block_rows, int)
    assert callable(g.fused_grad_active)
    # the public accessor says what the benchmark assembles by hand
    wc = bst.work_counters()
    assert tuple(wc["stamps"]) == STAMP_KEYS
    assert wc["stamps"] == benchmark_stamps(bst)
    assert wc["wave_capacity"] == g._wave_info["wave_capacity"]
    assert wc["block_rows"] == g.config.tpu_block_rows
    assert (wc["chips"], wc["rows_per_chip"]) == (chips, 512 // chips)
    assert wc["counted"] is False       # nothing trained yet
    # the facts about the training set (dense here: a feature a column)
    assert wc["bundled"] is False
    assert wc["features"] == wc["phys_columns"] == bins.shape[0]


def test_public_twin_says_bundled_where_efb_packed_the_columns(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    monkeypatch.syspath_prepend(BENCH)
    rng = np.random.default_rng(4)
    n = 1024
    X = np.zeros((n, 22))
    X[np.arange(n), rng.integers(0, 20, n)] = 1.0       # one one-hot block
    X[:, 20:] = np.exp(rng.normal(size=(n, 2)))
    y = (X[:, :10].sum(axis=1) + 0.5 * rng.normal(size=n) > 0.5
         ).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              "min_data_in_leaf": 5, "device_type": "tpu"}
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params=params, train_set=ds)
    wc = bst.work_counters(last=0)
    assert wc["bundled"] is True and wc["features"] == 22
    assert wc["phys_columns"] == bst._gbdt._grow_bins.shape[0] == 3
    assert wc["stamps"]["fused_sibling"] is False
    # Dataset.bundle_groups(): the data's columns, a list a physical column
    groups = ds.bundle_groups()
    assert len(groups) == 3 and sorted(map(len, groups)) == [1, 1, 20]
    assert sorted(c for g in groups for c in g) == list(range(22))
    # the kind that reads them puts the stamps together from these alone
    stamps = importlib.import_module("kinds.boost_csr")._stamps(bst)
    assert stamps == {**wc["stamps"], "bundled": True}


def test_kernel_module_names_the_launch_takes():
    assert ph.C_MAX == 128 and set(ph.QUANT_MODES) == {"int16", "int8"}
    assert ph.wave_capacity_max(True) * 2 <= ph.C_MAX
    assert ph.wave_capacity_max(False) * 3 <= ph.C_MAX
    blocks = ph.select_wave_blocks(256, mode="2xbf16", packed=True,
                                   fused=True, block_rows=1024)
    assert len(blocks) == 2 and all(isinstance(b, int) for b in blocks)
    params = inspect.signature(ph.hist_pallas_wave).parameters
    assert list(params)[:6] == ["bins_fm", "gv", "hv", "cv", "leaf_id",
                                "slot_leaf"]
    assert {"B", "block_rows", "feat_block", "highest", "interpret",
            "packed", "parent"} <= set(params)


def test_setup_trace_keys_the_setup_reducer_takes(monkeypatch):
    """``benchmarks/reducers/setup_span.py`` reads ``Booster.setup_trace()``
    by these keys and span names; every ``setup.*`` metric it feeds comes
    out a finite number, ``setup.bin_group_s`` where EFB grouping ran."""
    import math
    import types
    monkeypatch.syspath_prepend(BENCH)
    setup_span = importlib.import_module("reducers.setup_span")
    cells = importlib.import_module("harness.cells")
    bst = _booster(monkeypatch)
    bst.update()
    trace = bst.setup_trace()
    assert set(trace) >= {"clock", "spans", "programs", "programs_seen",
                          "programs_at_update"}
    assert trace["programs_at_update"] == trace["programs_seen"]
    assert {"name", "t", "dur_s", "span_id", "parent_id", "attrs"} == \
        set(trace["spans"][0])
    assert {"seq", "fun_name", "t", "trace_s", "lower_s", "backend_s",
            "cache", "retrieval_s", "saved_s", "parent_id"} == \
        set(trace["programs"][0])
    ev = {"setup_trace": trace}
    specs = [s for s in cells.layer_metric_specs()
             if s["reducer"] == "setup_span"]
    assert len(specs) == 11
    for spec in specs:
        val = setup_span.read(spec, ev)
        assert val is not None and math.isfinite(val) and val >= 0, spec
    named = {n for s in specs for n in s.get("spans", [])}
    assert named <= {s["name"] for s in trace["spans"]}
    # one kind hands the readers a view that carries work_counters alone
    view = types.SimpleNamespace(work_counters=bst.work_counters)
    assert setup_span._accessor(view)()["clock"] == "unix_s"
    assert setup_span._accessor(types.SimpleNamespace()) is None
