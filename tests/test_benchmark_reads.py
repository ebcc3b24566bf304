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

Every layer metric of ``benchmarks/layer_metrics/`` whose ``source`` is the
device trace sums the device time under its ``lgbm/*`` scopes.  A scope that
left the program would not fail such a metric: it would read zero.  So each
scope those files name is looked for in the lowered text of a trainer's own
program that runs it, built here at toy size on the CPU with the kernel
interpreted (the names are the program's, whatever the backend).
"""
import glob
import importlib
import inspect
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

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


# ---------------------------------------------------------------------------
# the scopes the device-trace metrics read
# ---------------------------------------------------------------------------

def _device_trace_scopes() -> list:
    scopes = set()
    for path in glob.glob(os.path.join(BENCH, "layer_metrics", "*.json")):
        with open(path) as fh:
            spec = json.load(fh)
        if spec.get("source") == "device_trace":
            scopes.update(spec.get("scopes") or ())
    return sorted(scopes)


SCOPES = _device_trace_scopes()

# scope -> the trainer whose program runs it
PROGRAM_OF = {
    "lgbm/grad": "binary", "lgbm/pallas_hist_wave": "binary",
    "lgbm/score_update": "binary", "lgbm/split_scan": "binary",
    "lgbm/wave_split_phase": "binary", "lgbm/wave_compact": "binary",
    "lgbm/wave_hist_state": "binary", "lgbm/wave_partition": "binary",
    "lgbm/cat_scan": "categorical", "lgbm/efb_expand": "bundled",
    "lgbm/goss_sample": "goss", "lgbm/split_sync": "feature_parallel",
}
SCOPED_BASE = {"objective": "binary", "num_leaves": 7,
               "min_data_in_leaf": 20, "verbose": -1, "device_type": "tpu"}


def _scoped_table(rows=2000, cols=10, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, cols))
    y = (X[:, 0] + X[:, 1] * X[:, 2] + 0.3 * rng.normal(size=rows) > 0)
    return X, y.astype(np.float64)


def _scoped_booster(X, y, **extra):
    params = {**SCOPED_BASE, **extra}
    return lgb.Booster(params=params,
                       train_set=lgb.Dataset(X, label=y, params=params))


def _growth_text(bst) -> str:
    """The growth program an iteration dispatches: the fused one where the
    trainer runs it, else grow + shrink + score update with g and h given."""
    g = bst._gbdt
    fused = g.fused_grad_active()
    N, K = g._train_score.shape
    gh = None if fused else jnp.zeros((N, K), jnp.float32)
    fn = g._grow_apply_fused if fused else g._grow_apply
    return fn.lower(g._grow_bins, gh, gh, g._bag_mask, g._feature_mask(),
                    g._train_score, jnp.float32(0.1), 0,
                    seed=jnp.uint32(0)).as_text(debug_info=True)


def _binary():
    return _growth_text(_scoped_booster(*_scoped_table()))


def _categorical():
    X, y = _scoped_table()
    X[:, 3] = np.random.default_rng(4).integers(0, 12, len(y))
    y = ((X[:, 3] % 3 == 0) ^ (X[:, 0] > 0)).astype(np.float64)
    return _growth_text(_scoped_booster(X, y, categorical_feature=[3]))


def _bundled():
    from test_expo_config import PARAMS, _onehot_csr
    X, y = _onehot_csr(1)
    bst = _scoped_booster(X, y, **PARAMS, device_type="tpu")
    assert bst.work_counters()["bundled"]
    return _growth_text(bst)


def _goss():
    X, y = _scoped_table()
    g = _scoped_booster(X, y, boosting="goss")._gbdt
    N = g.train_ds.num_data
    return g._sample.lower(jnp.zeros((N, 1)), jnp.ones((N, 1)),
                           g._sample_key, jnp.int32(0)).as_text(
                               debug_info=True)


def _feature_parallel():
    X, y = _scoped_table(cols=37)
    return _growth_text(_scoped_booster(X, y, tree_learner="feature",
                                 tpu_mesh_shape="data:4"))


BUILDERS = {"binary": _binary, "categorical": _categorical,
            "bundled": _bundled, "goss": _goss,
            "feature_parallel": _feature_parallel}


@pytest.fixture(scope="module")
def program_text():
    texts = {}

    def get(name):
        if name not in texts:
            with pytest.MonkeyPatch.context() as mp:
                mp.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
                texts[name] = BUILDERS[name]()
        return texts[name]
    return get


@pytest.mark.parametrize("scope", SCOPES)
def test_every_scope_a_layer_metric_reads_is_in_its_program(program_text,
                                                            scope):
    assert scope in PROGRAM_OF, (
        f"{scope} is read by a layer metric: name the trainer that runs it")
    assert scope in program_text(PROGRAM_OF[scope])
