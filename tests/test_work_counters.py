"""The growth program's own work counters (``Booster.work_counters``), each
against a recount that does not use them: the exported model's node counts.

Small enough for the interpreted kernel on the CPU (``LGBM_TPU_FORCE_WAVE``);
a CPU run gives counts and correctness, never a time.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.boosting import gbdt as gbdt_mod
from lightgbm_tpu.config import Config
from lightgbm_tpu.core import wave_grower
from lightgbm_tpu.core.meta import SplitConfig, build_device_meta
from lightgbm_tpu.core.plan import GrowthPlan

ROWS, ITERS = 3000, 3
BASE = {"num_leaves": 15, "min_data_in_leaf": 20, "verbose": -1,
        "device_type": "tpu"}
CASES = {
    "binary": {"objective": "binary"},
    "lambdarank": {"objective": "lambdarank", "lambdarank_truncation_level": 10},
    "data4": {"objective": "binary", "tree_learner": "data",
              "tpu_mesh_shape": "data:4"},
}


def _table(case: str):
    rng = np.random.default_rng(7)
    X = rng.normal(size=(ROWS, 8))
    score = X[:, 0] + X[:, 1] * X[:, 2] + 0.3 * rng.normal(size=ROWS)
    if case != "lambdarank":
        return X, (score > 0).astype(np.float64), None
    sizes = []                          # ragged queries, 1..120 rows each
    while sum(sizes) < ROWS:
        sizes.append(int(min(rng.integers(1, 121), ROWS - sum(sizes))))
    y = np.clip(np.round(score + 1.5), 0, 4)
    return X, y, np.asarray(sizes)


def _train(case: str, iters: int = ITERS, **extra):
    X, y, sizes = _table(case)
    params = {**BASE, **CASES[case], **extra}
    ds = lgb.Dataset(X, label=y, group=sizes, params=params)
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(iters):
        bst.update()
    return bst


def _model_trees(model_str: str) -> list:
    """Per tree of the exported model: its integer arrays by name."""
    trees = []
    for block in model_str.split("\nTree=")[1:]:
        fields = dict(line.split("=", 1) for line in block.splitlines()
                      if "=" in line)
        tree = {"num_leaves": int(fields["num_leaves"])}
        for k in ("left_child", "right_child", "leaf_count",
                  "internal_count"):
            tree[k] = [int(v) for v in fields[k].split()]
        trees.append(tree)
    return trees


def _recount(tree: dict) -> dict:
    """What the counters should say, from the tree alone.  The grower
    queues the root and, of every split, the child with fewer rows; without
    bagging every row carries weight, so the rows that went into launches
    are all of them at the root plus every smaller child."""
    def count(child):
        return (tree["leaf_count"][~child] if child < 0
                else tree["internal_count"][child])
    smaller = sum(min(count(l), count(r)) for l, r in
                  zip(tree["left_child"], tree["right_child"]))
    return {"lanes": tree["num_leaves"],
            "routed_rows": sum(tree["internal_count"]),
            "active_rows": tree["internal_count"][0] + smaller}


@pytest.fixture(autouse=True)
def _interpreted_wave(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_FORCE_WAVE", "interpret")


@pytest.fixture(scope="module")
def boosters():
    """One trained Booster a case, shared: the compile dominates."""
    mp = pytest.MonkeyPatch()
    mp.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    # the fused growers' cache holds four and empties itself when a fifth
    # comes: start from none, so that what another file of this process
    # left there cannot evict the three that the tests below hold to
    gbdt_mod._FUSED_JIT_CACHE.clear()
    try:
        yield {case: _train(case) for case in CASES}
    finally:
        mp.undo()


@pytest.mark.parametrize("case", list(CASES))
def test_counts_equal_the_recount_from_the_exported_model(boosters, case):
    bst = boosters[case]
    wc = bst.work_counters()
    chips = 4 if case == "data4" else 1
    assert wc["counted"] and wc["iterations"] == list(range(ITERS))
    assert (wc["rows"], wc["chips"]) == (ROWS, chips)
    assert wc["rows_per_chip"] == ROWS // chips
    assert wc["wave_capacity"] == 63 and wc["block_rows"] == 1024
    trees = _model_trees(bst.model_to_string())
    assert len(trees) == len(wc["trees"]) == ITERS
    per = wc["rows_per_chip"]
    for i, (tree, c) in enumerate(zip(trees, wc["trees"])):
        want = _recount(tree)
        assert (c["iteration"], c["class_id"]) == (i, 0)
        assert tree["num_leaves"] > 4
        assert c["lanes"] == want["lanes"]
        assert c["routed_rows"] == want["routed_rows"]
        assert want["active_rows"] > ROWS
        assert sum(c["active_rows"]) == want["active_rows"]
        assert 1 <= c["waves"] <= c["bodies"] <= tree["num_leaves"]
        assert len(c["kernel_rows"]) == len(c["active_rows"]) == chips
        for kern, act in zip(c["kernel_rows"], c["active_rows"]):
            assert per <= kern <= c["waves"] * per
            assert act <= kern
        assert "overlap" not in c       # the schedule went with PR 30


@pytest.mark.parametrize("case", list(CASES))
def test_walks_are_one_a_committed_split(boosters, case):
    """The partition's dense walks, counted where they run: one a
    committed split, so ``num_leaves - 1`` a tree, whatever the objective
    and on every chip of a mesh alike (``walks`` is a shared word)."""
    bst = boosters[case]
    trees = _model_trees(bst.model_to_string())
    counts = bst.work_counters()["trees"]
    assert len(trees) == len(counts) == ITERS
    for tree, c in zip(trees, counts):
        assert isinstance(c["walks"], int)
        assert c["walks"] == tree["num_leaves"] - 1 > 3
        # fewer walks than a pass a slot a body, more than one a body
        assert c["bodies"] < c["walks"] < c["bodies"] * 63


@pytest.mark.parametrize("case", list(CASES))
def test_route_passes_are_the_phases_that_committed(boosters, case):
    """The batched apply routes a phase's splits in ONE pass over the rows:
    every body but a tree's first (the root's wave alone) commits a split,
    so a tree's passes are its bodies less one, on every chip of a mesh
    alike (``route_passes`` is a shared word)."""
    counts = boosters[case].work_counters()["trees"]
    assert len(counts) == ITERS
    for c in counts:
        assert isinstance(c["route_passes"], int)
        assert 0 < c["route_passes"] == c["bodies"] - 1 < c["walks"]


@pytest.mark.parametrize("case", list(CASES))
def test_compact_waves_are_the_waves_below_the_full_tier(boosters, case):
    """Without bagging every row is active in the root's wave, which takes
    the full tier and compacts nothing; every later wave holds the smaller
    children, at most half the rows, and fits the tier below: ``waves - 1``
    a tree.  A chip of the mesh holds 750 rows, under one block of 1,024:
    its ladder has the full tier alone and nothing is ever compacted."""
    for c in boosters[case].work_counters()["trees"]:
        if case == "data4":
            assert c["compact_waves"] == [0] * 4
        else:
            assert c["compact_waves"] == [c["waves"] - 1] and c["waves"] > 2


@pytest.mark.parametrize("case", list(CASES))
def test_stream_waves_are_the_compact_waves(boosters, case):
    """Every tier below the full one is filled by the streamed pass
    (``ops/pallas_compact.py``), so on each chip the launches it filled are
    the launches that compacted at all: the counter would fall short of
    ``compact_waves`` only if a tier were ever filled another way."""
    chips = 4 if case == "data4" else 1
    for c in boosters[case].work_counters()["trees"]:
        assert len(c["stream_waves"]) == chips
        assert all(isinstance(v, int) for v in c["stream_waves"])
        assert c["stream_waves"] == c["compact_waves"]
        assert max(c["stream_waves"]) <= c["waves"]


@pytest.mark.parametrize("case", list(CASES))
def test_stream_blocks_are_the_sub_blocks_the_passes_streamed(boosters, case):
    """A compacting wave streams every row its chip holds, 128 a sub-block:
    ``stream_blocks`` is ``compact_waves`` times that, and ``placed_blocks``
    the part of them that held an active row, some and never all of them
    past the root (the smaller children are at most half the rows).  A chip
    of the mesh's 750 rows never compacts and counts nothing."""
    wc = boosters[case].work_counters()
    blocks = -(-wc["rows_per_chip"] // 128)
    for c in wc["trees"]:
        assert len(c["stream_blocks"]) == len(c["placed_blocks"]) == wc["chips"]
        for waves, streamed, placed in zip(
                c["compact_waves"], c["stream_blocks"], c["placed_blocks"]):
            assert isinstance(streamed, int) and isinstance(placed, int)
            assert streamed == waves * blocks
            if case == "data4":
                assert streamed == placed == 0
            else:
                assert 0 < placed <= streamed


def test_placed_blocks_equal_a_recount_of_the_masks(monkeypatch):
    """``placed_blocks`` against NumPy on the same history: every mask a
    streamed pass was given, as the growth program made it, recounted on
    the host by sub-blocks of 128 rows that hold a row (on a table sorted
    by its strongest column, so that not every sub-block does)."""
    masks = []
    real = wave_grower.stream_rows

    def recording(bins_fm, words, leaf_id, active, *rest, **kw):
        jax.debug.callback(lambda a: masks.append(np.asarray(a)), active)
        return real(bins_fm, words, leaf_id, active, *rest, **kw)
    monkeypatch.setattr(wave_grower, "stream_rows", recording)
    X, y, _ = _table("binary")
    order = np.argsort(X[:, 0])     # rows of a leaf lie together: some
    X, y = X[order], y[order]       # sub-blocks hold no active row
    params = {**BASE, "objective": "binary"}
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    cfg = Config.from_params(params)
    meta, B = build_device_meta(ds._handle, cfg)
    grow = jax.jit(wave_grower.build_wave_grow_fn(
        meta, SplitConfig.from_config(cfg), B, GrowthPlan(
            hist_mode="highest", interpret=True, counts=True,
            block_rows=128)))
    tree, _, stats = grow(
        jnp.asarray(np.ascontiguousarray(ds._handle.X_bin.T)),
        jnp.asarray(0.5 - y, jnp.float32), jnp.full((ROWS,), 0.25),
        jnp.ones((ROWS,)), jnp.ones((X.shape[1],), bool))
    jax.effects_barrier()
    c = wave_grower.wave_counts(stats)
    assert int(tree.num_leaves) > 4
    assert len(masks) == c["compact_waves"][0] == c["waves"] - 1 > 2
    blocks = -(-ROWS // 128)
    want = sum(int(np.pad(m, (0, blocks * 128 - ROWS))
                   .reshape(blocks, 128).any(axis=1).sum()) for m in masks)
    assert c["stream_blocks"] == [len(masks) * blocks]
    assert c["placed_blocks"] == [want] and 0 < want < len(masks) * blocks


def test_compact_waves_are_equal_on_every_chip():
    """Blocks of 128 rows give a chip's 750 a ladder (750, 512, 384, ...):
    each chip takes the tier its own active rows fit, and on rows dealt
    evenly every chip compacts, by its own streamed pass, in every wave but
    the root's."""
    bst = _train("data4", iters=2, tpu_block_rows=128)
    trees = bst.work_counters()["trees"]
    assert len(trees) == 2 and bst.work_counters()["block_rows"] == 128
    for c in trees:
        assert c["compact_waves"] == [c["waves"] - 1] * 4 and c["waves"] > 2
        assert c["stream_waves"] == c["compact_waves"]
        assert c["stream_blocks"] == [w * 6 for w in c["compact_waves"]]
        assert all(0 < p <= s for p, s in
                   zip(c["placed_blocks"], c["stream_blocks"]))
        assert max(c["kernel_rows"]) < c["waves"] * 750


@pytest.mark.parametrize("batched", [True, False],
                         ids=["batched", "sequential"])
def test_a_stump_walks_nothing(batched):
    """No leaf can split: the one body launches the root's histogram and
    makes no pass over the rows for the partition."""
    X, y, _ = _table("binary")
    params = {**BASE, "objective": "binary"}
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    meta, B = build_device_meta(ds._handle, Config.from_params(params))
    # the Dataset drops features that no leaf size admits, so only the
    # grower is told that none does
    stop = Config.from_params({**params, "min_data_in_leaf": ROWS})
    grow = jax.jit(wave_grower.build_wave_grow_fn(
        meta, SplitConfig.from_config(stop), B, GrowthPlan(
            hist_mode="highest", interpret=True, counts=True,
            batched_apply=batched)))
    tree, leaf_id, stats = grow(
        jnp.asarray(np.ascontiguousarray(ds._handle.X_bin.T)),
        jnp.asarray(0.5 - y, jnp.float32), jnp.full((ROWS,), 0.25),
        jnp.ones((ROWS,)), jnp.ones((X.shape[1],), bool))
    c = wave_grower.wave_counts(stats)
    assert int(tree.num_leaves) == 1 and not np.asarray(leaf_id).any()
    assert (c["walks"], c["route_passes"]) == (0, 0)
    assert (c["routed_rows"], c["lanes"]) == (0, 1)
    assert c["bodies"] == c["waves"] == 1
    assert c["compact_waves"] == c["stream_waves"] == [0]
    assert c["stream_blocks"] == c["placed_blocks"] == [0]


@pytest.mark.parametrize("case", list(CASES))
def test_pass_rows_are_counted_on_every_chip(boosters, case):
    """``kernel_pass_rows``, a chip: each launch's tier times the MXU
    passes it ran.  A tree of 15 leaves never holds more than 25 pending,
    so every launch ran one pass and the two counters agree."""
    chips = 4 if case == "data4" else 1
    for c in boosters[case].work_counters()["trees"]:
        assert len(c["kernel_pass_rows"]) == chips
        assert all(isinstance(v, int) for v in c["kernel_pass_rows"])
        assert c["kernel_pass_rows"] == c["kernel_rows"]


def test_pass_rows_are_tier_times_passes_summed_over_the_launches(
        monkeypatch):
    """A tree of 255 leaves on 3,000 rows (noise for gradients and no gain
    gate, so every leaf splits) holds up to 63 pending leaves a launch: ``kernel_pass_rows`` is the sum over its launches of the rows
    the launch covered times ``ceil(pending leaves / 25)``, each launch
    recorded where the grower calls the kernel."""
    launches = []
    real = wave_grower.hist_pallas_wave

    def recording(bins, gv, hv, cv, leaf, slot_leaf, **kw):
        jax.debug.callback(
            lambda n, t=bins.shape[1]: launches.append((t, int(n))),
            jnp.sum(slot_leaf[::2] >= 0))
        return real(bins, gv, hv, cv, leaf, slot_leaf, **kw)
    monkeypatch.setattr(wave_grower, "hist_pallas_wave", recording)
    X, y, _ = _table("binary")
    params = {**BASE, "objective": "binary", "num_leaves": 255,
              "min_data_in_leaf": 2}
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    cfg = Config.from_params(params)
    meta, B = build_device_meta(ds._handle, cfg)
    grow = jax.jit(wave_grower.build_wave_grow_fn(
        meta, SplitConfig.from_config(cfg), B, GrowthPlan(
            hist_mode="2xbf16", interpret=True, counts=True, packed=True,
            fused_sibling=True, wave_capacity=63, block_rows=256,
            gain_gate=0.0)))
    tree, _, stats = grow(
        jnp.asarray(np.ascontiguousarray(ds._handle.X_bin.T)),
        jnp.asarray(np.random.default_rng(3).normal(size=ROWS), jnp.float32),
        jnp.full((ROWS,), 0.25), jnp.ones((ROWS,)),
        jnp.ones((X.shape[1],), bool))
    jax.effects_barrier()
    c = wave_grower.wave_counts(stats)
    assert int(tree.num_leaves) > 100 and len(launches) == c["waves"]
    assert sum(n for _, n in launches) == c["lanes"]
    assert c["kernel_rows"] == [sum(t for t, _ in launches)]
    passes = [-(-n // 25) for _, n in launches]
    assert set(passes) == {1, 2, 3}, [n for _, n in launches]
    assert c["kernel_pass_rows"] == [
        sum(t * p for (t, _), p in zip(launches, passes))]
    assert launches[0] == (ROWS, 1)     # the root's: every row, one pass


def test_per_chip_counts_sum_to_the_one_device_figures(boosters):
    one, four = boosters["binary"], boosters["data4"]
    t1, t4 = (_model_trees(b.model_to_string()) for b in (one, four))
    assert t1 == t4                     # same trees, so the same work
    for a, b in zip(one.work_counters()["trees"],
                    four.work_counters()["trees"]):
        for k in ("bodies", "waves", "lanes", "routed_rows", "walks",
                  "route_passes"):
            assert a[k] == b[k], k
        assert sum(b["active_rows"]) == a["active_rows"][0]
        assert len(b["active_rows"]) == 4 and min(b["active_rows"]) > 0
        assert len(a["compact_waves"]) == 1 and len(b["compact_waves"]) == 4


def _slots(sizes, chips: int = 1) -> int:
    """The pair pass's padded slots on the fullest chip, recounted from the
    bucket geometry alone: each bucket's queries over the chips, rounded
    up, then up to whole chunks."""
    from lightgbm_tpu.core.query import bucket_shapes, query_pads
    pads = query_pads(np.asarray(sizes))
    return sum(-(-(-(-int((pads == P).sum()) // chips)) // qc) * qc * P
               for P, _, qc in bucket_shapes(sizes))


def test_pair_slots_are_the_pair_pass_on_the_fullest_chip(boosters):
    """``pair_slots``: one chip's pair pass covers ``Qp x P`` of every bucket
    (``core/query.py bucket_shapes``); under the feature learner on four
    devices each bucket's queries are dealt over the chips, so the fullest
    holds a quarter of them, rounded up, in whole chunks.  No pair pass, no
    slots."""
    from lightgbm_tpu.core.query import bucket_shapes
    _, _, sizes = _table("lambdarank")
    one = boosters["lambdarank"].work_counters(last=0)["pair_slots"]
    assert one == sum(Qp * P for P, Qp, _ in bucket_shapes(sizes))
    assert one == _slots(sizes)
    for case in ("binary", "data4"):
        assert boosters[case].work_counters(last=0)["pair_slots"] is None
    # enough queries of 9-16 rows to fill two chunks of the P=16 bucket
    # (2,048 queries a chunk); nothing is grown, the objective is armed
    rng = np.random.default_rng(3)
    sizes = rng.integers(9, 17, size=2600)
    X = rng.normal(size=(int(sizes.sum()), 4))
    y = rng.integers(0, 5, size=len(X)).astype(np.float64)
    got = {}
    for chips, extra in ((1, {}), (4, {"tree_learner": "feature",
                                       "tpu_mesh_shape": "data:4"})):
        params = {**BASE, **CASES["lambdarank"], **extra}
        bst = lgb.Booster(params=params, train_set=lgb.Dataset(
            X, label=y, group=sizes, params=params))
        assert bst._gbdt._plan.rank_sharded_grad is (chips == 4)
        got[chips] = bst.work_counters()["pair_slots"]
        assert got[chips] == _slots(sizes, chips)
    assert got == {1: 2 * 2048 * 16, 4: 2048 * 16}


def test_stamps_are_the_trainers(boosters):
    for case, bst in boosters.items():
        g, st = bst._gbdt, bst.work_counters(last=0)["stamps"]
        assert st["uses_wave"] and st["interpret"] and st["packed"]
        assert st["hist_mode"] == g._wave_info["hist_mode"] == "2xbf16"
        assert st["fused_sibling"] == (case != "data4")
        assert st["fused_grad"] == g.fused_grad_active()
        assert st["bins_devices"] == (4 if case == "data4" else 1)


def test_update_fetches_nothing_and_last_n_returns_n(monkeypatch):
    calls = []
    real = wave_grower.wave_counts
    monkeypatch.setattr(wave_grower, "wave_counts",
                        lambda st: calls.append(1) or real(st))
    monkeypatch.setattr(gbdt_mod, "WORK_RING_ITERS", 2)
    bst = _train("binary", iters=3)
    assert not calls                    # three updates decoded nothing
    ring = bst._gbdt._work_ring
    assert len(ring) == 2               # bounded: the oldest went
    assert all(isinstance(st.shared, jax.Array) for _, sts, _ in ring
               for st in sts)
    assert all(sampler is None for _, _, sampler in ring)   # no GOSS here
    wc = bst.work_counters()
    assert wc["iterations"] == [1, 2] and len(calls) == 2
    assert bst.work_counters(last=1)["iterations"] == [2]
    assert bst.work_counters(last=0)["trees"] == []
    assert bst.work_counters(last=5)["iterations"] == [1, 2]
    # a tree that was taken back is not reported, and the iteration grown
    # again reports once
    bst.rollback_one_iter()
    assert bst.work_counters()["iterations"] == [1]
    bst.update()
    again = bst.work_counters()
    assert again["iterations"] == [1, 2] and len(again["trees"]) == 2


@pytest.mark.parametrize("case", list(CASES))
def test_telemetry_on_compiles_no_second_grower(tmp_path, boosters, case):
    off = boosters[case]
    jitted = off._gbdt._grow_apply_fused
    size = jitted._cache_size()
    obs.reset()
    obs.enable(str(tmp_path))
    try:
        on = _train(case)
    finally:
        obs.disable()
        obs.reset()
    if off._gbdt._mesh is None:
        # the process-wide cache hands both the one grower; a mesh learner
        # builds its own a Booster
        assert on._gbdt._grow_raw is off._gbdt._grow_raw
        assert on._gbdt._grow_apply_fused is jitted
    # telemetry on traces the program telemetry off runs, and only once
    assert on._gbdt._grow_apply_fused._cache_size() == size == 1
    assert on.model_to_string() == off.model_to_string()
    assert on.work_counters()["trees"] == off.work_counters()["trees"]
    # the iteration records read the same array the accessor does
    import json
    events = [json.loads(line) for f in tmp_path.glob("*.jsonl")
              for line in f.read_text().splitlines()]
    its = [e for e in events if e.get("event") == "iteration"]
    assert len(its) == ITERS
    for e, c in zip(its, on.work_counters()["trees"]):
        assert e["waves"] == c["waves"]
        assert e["kernel_rows"] == sum(c["kernel_rows"])
        assert e["kernel_pass_rows"] == sum(c["kernel_pass_rows"])
        assert e["partition_passes"] == c["route_passes"] < c["walks"]
        assert e["compact_waves"] == max(c["compact_waves"])
        assert e["stream_waves"] == max(c["stream_waves"])
        assert e["placed_blocks"] == max(c["placed_blocks"])


@pytest.mark.parametrize("extra", [
    {"boosting": "rf", "bagging_freq": 1, "bagging_fraction": 0.7},
    {"cegb_penalty_split": 1e-6},
    {"device_type": "cpu"}], ids=["rf", "cegb", "xla_grower"])
def test_growers_that_do_not_count_say_so(monkeypatch, extra):
    if extra.get("device_type") == "cpu":
        monkeypatch.delenv("LGBM_TPU_FORCE_WAVE")
    bst = _train("binary", iters=2, **extra)
    wc = bst.work_counters()
    assert wc["counted"] is False and wc["trees"] == []
    assert wc["iterations"] == [] and wc["rows"] == ROWS
    assert wc["stamps"]["uses_wave"] == (extra.get("device_type") != "cpu")
    assert bst.num_trees() == 2
    loaded = lgb.Booster(model_str=bst.model_to_string())
    assert loaded.work_counters()["counted"] is False


def test_the_sequential_oracle_counts_the_same_walks(boosters, replace_plan):
    # last in the file: a fourth fused grower evicts the shared ones
    # (gbdt._FUSED_JIT_CACHE holds four), whose identity
    # test_telemetry_on_compiles_no_second_grower holds
    replace_plan(batched_apply=False)
    seq = _train("binary")
    assert not seq._gbdt._plan.batched_apply
    assert _model_trees(seq.model_to_string()) == \
        _model_trees(boosters["binary"].model_to_string())
    # the same counts but for the passes: the oracle walks the rows once a
    # split, the batched apply once a phase
    for a, b in zip(seq.work_counters()["trees"],
                    boosters["binary"].work_counters()["trees"]):
        assert a["route_passes"] == a["walks"] > b["route_passes"]
        assert {**a, "route_passes": b["route_passes"]} == b


def test_numeric_programs_carry_no_categorical_counter(boosters):
    """``cat_splits`` is counted only by the program of a training set that
    declares a categorical column (static, as the split scan's ``has_cat``):
    every other program returns the seven shared words of a numeric table
    (six before PR 35's ``route_passes``), and its trees read 0."""
    for bst in boosters.values():
        wc = bst.work_counters()
        assert wc["categorical_features"] == 0 and wc["wide_columns"] == 0
        assert [t["cat_splits"] for t in wc["trees"]] == [0] * ITERS
        assert all(st.shared.shape[-1] == 7
                   for _, sts, _ in bst._gbdt._work_ring for st in sts)


@pytest.mark.parametrize("batched", [True, False],
                         ids=["batched", "sequential"])
def test_cat_splits_are_the_committed_categorical_splits(batched):
    """Two declared columns beside two numeric ones: the seventh shared word
    counts the committed splits whose feature is categorical, whichever way
    the phase commits, and the numeric program of the same rows has no such
    word and no sort (the search's ``argsort``) in it."""
    rng = np.random.default_rng(11)
    c0, c1 = rng.integers(0, 9, ROWS), rng.integers(0, 30, ROWS)
    x = rng.normal(size=(ROWS, 2))
    e0, e1 = rng.normal(size=9), rng.normal(size=30)
    y = (e0[c0] + e1[c1] + x[:, 0] > 0).astype(np.float64)
    X = np.column_stack([c0, c1, x])
    params = {**BASE, "objective": "binary", "min_data_per_group": 30}
    cfg = Config.from_params(params)
    plan = GrowthPlan(hist_mode="highest", interpret=True, counts=True,
                      batched_apply=batched)

    def build(cats):
        ds = lgb.Dataset(X, label=y, categorical_feature=cats, params=params)
        ds.construct()
        meta, B = build_device_meta(ds._handle, cfg)
        grow = wave_grower.build_wave_grow_fn(
            meta, SplitConfig.from_config(cfg), B, plan)
        args = (jnp.asarray(np.ascontiguousarray(ds._handle.X_bin.T)),
                jnp.asarray(0.5 - y, jnp.float32), jnp.full((ROWS,), 0.25),
                jnp.ones((ROWS,)), jnp.ones((4,), bool))
        return meta, grow, args
    meta, grow, args = build([0, 1])
    tree, _, stats = jax.jit(grow)(*args)
    assert stats.shared.shape == (8,)
    c = wave_grower.wave_counts(stats)
    feats = np.asarray(tree.split_feature)[:int(tree.num_leaves) - 1]
    assert c["cat_splits"] == int(np.asarray(meta.is_categorical)[feats]
                                  .sum()) > 0
    assert c["cat_splits"] < c["walks"] == len(feats)
    _, grow_num, args_num = build([])
    text = str(jax.make_jaxpr(grow_num)(*args_num))
    assert " sort[" not in text
    assert " sort[" in str(jax.make_jaxpr(grow)(*args))
    assert jax.eval_shape(grow_num, *args_num)[2].shared.shape == (7,)


def _mixed_table():
    """Seven numeric columns and a declared categorical one of 400 values:
    more than the kernel's 256 bins, so the plan is the mixed-width one."""
    rng = np.random.default_rng(9)
    X = rng.normal(size=(ROWS, 8))
    X[:, 7] = rng.integers(0, 400, size=ROWS)
    return X, (X[:, 0] + (X[:, 7] % 3) > 1).astype(np.float64)


@pytest.mark.parametrize("case,shape", [
    ("bins63", (64, 8, 2, 8)), ("bins255", (256, 8, 1, 8)),
    ("bins15", (16, 8, 8, 8)), ("mixed63", (64, 7, 1, 7)),
    ("xla_grower", (None, None, None, None))])
def test_the_kernels_shape_is_said_as_the_plan_decided_it(monkeypatch, case,
                                                          shape):
    """``kernel_bins``, ``feat_block``, ``feat_pack``, ``kernel_columns`` at
    the top level of ``work_counters()``, beside ``bundled`` and not inside
    ``stamps``: two features an MXU pass at 64 lanes and one at 256; under
    the mixed plan the narrow columns' width and count (seven columns: a
    block the pack of two does not divide, one feature a pass, and the fact
    says so); None off the wave path."""
    params = {**BASE, "objective": "binary"}
    X, y, _ = _table("binary")
    cat = "auto"
    if case == "xla_grower":
        monkeypatch.delenv("LGBM_TPU_FORCE_WAVE")
        params["device_type"] = "cpu"
    elif case == "mixed63":
        (X, y), cat = _mixed_table(), [7]
        params.update(max_bin=63, min_data_per_group=5)
    else:
        params["max_bin"] = int(case[4:])
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(
        X, label=y, categorical_feature=cat, params=params))
    wc, plan = bst.work_counters(last=0), bst._gbdt._plan
    names = ("kernel_bins", "feat_block", "feat_pack", "kernel_columns")
    assert tuple(wc[k] for k in names) == shape
    if case != "xla_grower":        # and it is what the grower launches at
        assert plan.kernel(shape[0], shape[3]) == shape
    assert not set(names) & set(wc["stamps"])
    assert wc["stamps"]["uses_wave"] == (case != "xla_grower")
    assert (plan.mixed is not None) == (case == "mixed63")
    if case == "mixed63":
        assert wc["wide_columns"] == 1 and plan.mixed.B_narrow == 64


def test_the_plan_and_the_kernel_cut_pack_and_pad_by_one_rule():
    """``GrowthPlan.kernel`` derives the shape from the plan's own fields by
    the rule ``hist_pallas_wave`` launches by (``wave_feature_blocks``): the
    block cut to the columns there are, the pack only where it divides the
    block; a block under the u8 tile over more columns, the block of whole
    loop groups that fits the VMEM cap and covers them at the least cost; a
    larger one that would pad the columns, one block of them all if it
    keeps the loop body and fits the cap."""
    from lightgbm_tpu.ops.pallas_hist import (select_wave_blocks,
                                              wave_feature_blocks)
    plan = GrowthPlan()
    layout = dict(mode=plan.hist_mode, packed=plan.packed,
                  fused=plan.fused_sibling, block_rows=plan.block_rows)
    fb = select_wave_blocks(64, **layout)[1]
    assert plan.kernel(64, 28) == (64, 28, 2, 28) and fb == 32
    for bins, columns, shape in ((256, 28, (256, 28, 1, 28)),
                                 (64, 7, (64, 7, 1, 7)),
                                 (64, 40, (64, 40, 2, 40)),
                                 (64, 41, (64, 32, 2, 64))):   # odd: no pack
        assert plan.kernel(bins, columns) == shape
    assert wave_feature_blocks(64, 28, fb, **layout) == (28, 2, 28)
    # a caller's block of 8 at 64 lanes is under the tile: one block of 32,
    # two features a pass (16 steps looped by four, as 8 wrote 4 out; 28
    # would loop 14 by two)
    assert wave_feature_blocks(64, 28, 8, **layout) == (32, 2, 32)
    assert wave_feature_blocks(256, 28, 8, **layout) == (28, 1, 28)
    assert wave_feature_blocks(256, 28, 8, windowed=True,
                               **layout) == (8, 1, 32)


# (bins, columns, plan fields) -> (feat_block, feat_pack, kernel_columns):
# the benchmark's shapes and the rule's edges
_RULE_CASES = {
    "higgs_fused_28_one_block": (256, 28, {}, (28, 1, 28)),
    "mslr_136_five_blocks_of_28_padded": (256, 136, {}, (28, 1, 140)),
    "bin63_28_cut_packed": (64, 28, {}, (28, 2, 28)),
    "unfused_16_cut": (256, 16, dict(fused_sibling=False), (16, 1, 16)),
    "unfused_28_cut": (256, 28, dict(fused_sibling=False), (28, 1, 28)),
    "feature_learner_28_windowed": (256, 28, dict(learner="feature"),
                                    (8, 1, 32)),
    "feature_learner_136_windowed": (256, 136, dict(learner="feature"),
                                     (8, 1, 136)),
    "fused_36_fits_the_cap": (256, 36, {}, (36, 1, 36)),
    "fused_44_over_the_cap_padded": (256, 44, {}, (24, 1, 48)),
    "fused_29_another_loop_body_padded": (256, 29, {}, (32, 1, 32)),
    "fused_137_five_blocks_of_28_padded": (256, 137, {}, (28, 1, 140)),
    "bosch_992_at_64_lanes_kept": (64, 992, {}, (32, 2, 992)),
    "bin63_41_odd_no_pack": (64, 41, {}, (32, 2, 64)),
}


@pytest.mark.parametrize("case", sorted(_RULE_CASES))
def test_one_block_of_every_column_where_padding_would_add_zeros(case):
    """The shape ``KernelShape`` says and ``wave_feature_blocks`` gives the
    launch, for the plan's layout.  The fused pick at 256 lanes (8, under
    the u8 tile) becomes the block of whole loop groups (four columns)
    whose launch's VMEM (``wave_vmem_bytes``) fits ``_VMEM_CAP`` and that
    covers the columns at the least cost, a contraction a column and
    ``_BLOCK_COST`` more a block: one block of 28 or 36 columns, one of 32
    for 29 (29 would loop by one), two of 24 for 44 (one would not fit),
    five of 28 for MSLR's 136 and for 137 (four of 36 cost a little
    more).  A
    pick of a whole tile or more (the unfused one, the 64-lane one that
    Bosch's configuration pins) is cut to the columns, or, where whole
    blocks would pad them, one block of them all only if that keeps the
    loop body and fits the cap (41 columns at 64 lanes would lose the
    pack).  The feature learner's windowed launch keeps whole blocks of
    ``select_wave_blocks``."""
    from lightgbm_tpu.ops.pallas_hist import (_VMEM_CAP, select_wave_blocks,
                                              wave_feature_blocks,
                                              wave_vmem_bytes)
    bins, columns, fields, (fb, pack, cols) = _RULE_CASES[case]
    plan = dataclasses.replace(GrowthPlan(), **fields)
    layout = dict(mode=plan.hist_mode, packed=plan.packed,
                  fused=plan.fused_sibling, block_rows=plan.block_rows)
    assert plan.kernel(bins, columns) == (bins, fb, pack, cols)
    picked = select_wave_blocks(bins, **layout)[1]
    assert wave_feature_blocks(
        bins, columns, picked, windowed=plan.learner == "feature",
        **layout) == (fb, pack, cols)
    if "_cap" in case:
        vmem = wave_vmem_bytes(bins, columns, plan.block_rows, plan.hist_mode,
                               plan.packed, plan.fused_sibling)
        assert (vmem <= _VMEM_CAP) == case.endswith("fits_the_cap")
