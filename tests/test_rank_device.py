"""Device ranking plane (ISSUE 13): the NDCG@k kernel against the host
oracle across every fixture branch, query-aligned data-parallel lambda
sharding against the single-device oracle, fused rank gradients through
``_grow_apply_fused``, and the ranking-plane cost models ROOFLINE.md
quotes.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Metadata
from lightgbm_tpu.metric.rank import NDCGMetric
from lightgbm_tpu.objective.rank import LambdarankNDCG


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _metric(sizes, label, *, weights=None, eval_at=(1, 3, 5),
            device=True, label_gain=None):
    params = {"objective": "lambdarank", "eval_at": list(eval_at),
              "tpu_rank_device_eval": device, "verbose": -1}
    if label_gain is not None:
        params["label_gain"] = list(label_gain)
    cfg = Config.from_params(params)
    m = NDCGMetric(cfg)
    N = int(np.sum(sizes))
    md = Metadata(N)
    md.set_label(np.asarray(label, np.float64))
    if weights is not None:
        md.set_weights(np.asarray(weights, np.float32))
    md.set_query(np.asarray(sizes, np.int64))
    m.init(md, N)
    return m


def _assert_device_matches_host(m, score_f32, atol=1e-6):
    import jax.numpy as jnp
    assert m.accepts_device_score and m._dev_fn is not None
    dev = dict((k, v) for k, v, _ in m.eval(jnp.asarray(score_f32), None))
    host = dict((k, v) for k, v, _ in m.eval_host(np.asarray(score_f32)))
    assert set(dev) == set(host)
    for k in dev:
        assert abs(dev[k] - host[k]) <= atol, (k, dev[k], host[k])
    return dev


# ---------------------------------------------------------------------------
# 1. device NDCG kernel vs the host oracle — every fixture branch
# ---------------------------------------------------------------------------

def test_device_ndcg_matches_host_ragged():
    rng = np.random.default_rng(0)
    sizes = np.concatenate([rng.integers(1, 50, size=60), [1, 1, 200]])
    N = int(sizes.sum())
    label = rng.integers(0, 5, size=N)
    score = rng.normal(size=N).astype(np.float32)
    m = _metric(sizes, label)
    _assert_device_matches_host(m, score)


def test_device_ndcg_mslr_sized_queries():
    """Ragged MSLR-shaped sizes — a 1251-doc query (the real MSLR max)
    beside single-doc ones, pow2 pads from 8 to 2048."""
    rng = np.random.default_rng(1)
    sizes = np.concatenate([[1251, 1, 700, 3], rng.integers(1, 200, 20)])
    N = int(sizes.sum())
    label = rng.integers(0, 5, size=N)
    score = rng.normal(size=N).astype(np.float32)
    m = _metric(sizes, label, eval_at=(1, 5, 10, 100))
    _assert_device_matches_host(m, score)


def test_device_ndcg_ties_stable_doc_order():
    """Exact score ties: both paths stable-sort, so tied documents keep
    dataset order and the values agree exactly."""
    rng = np.random.default_rng(2)
    sizes = np.asarray([7, 30, 64, 12])
    N = int(sizes.sum())
    label = rng.integers(0, 5, size=N)
    # heavy exact ties: scores quantized to 4 levels
    score = (rng.integers(0, 4, size=N) * 0.25).astype(np.float32)
    m = _metric(sizes, label)
    _assert_device_matches_host(m, score)
    # all-tied degenerate query set too
    m2 = _metric(sizes, label)
    _assert_device_matches_host(m2, np.zeros(N, np.float32))


def test_device_ndcg_zero_relevance_counts_perfect():
    """All-zero-relevance queries count as perfect in BOTH paths
    (reference: NDCGMetric::Eval empty-dcg case)."""
    rng = np.random.default_rng(3)
    sizes = np.asarray([10, 5, 8, 20])
    N = int(sizes.sum())
    label = rng.integers(0, 4, size=N)
    label[:15] = 0.0                      # queries 0+1 fully irrelevant
    score = rng.normal(size=N).astype(np.float32)
    m = _metric(sizes, label)
    dev = _assert_device_matches_host(m, score)
    # degenerate: EVERY query zero-relevance -> ndcg == 1 exactly
    m2 = _metric(sizes, np.zeros(N))
    import jax.numpy as jnp
    vals = dict((k, v) for k, v, _ in m2.eval(jnp.asarray(score), None))
    assert all(abs(v - 1.0) < 1e-7 for v in vals.values()), vals
    assert dev  # parity already asserted above


def test_device_ndcg_query_weights_parity():
    rng = np.random.default_rng(4)
    sizes = np.concatenate([rng.integers(1, 30, size=25), [1, 90]])
    N = int(sizes.sum())
    label = rng.integers(0, 5, size=N)
    weights = (0.25 + rng.random(N)).astype(np.float32)
    score = rng.normal(size=N).astype(np.float32)
    m = _metric(sizes, label, weights=weights)
    assert m.query_weights is not None
    _assert_device_matches_host(m, score)


def test_device_eval_knob_off_keeps_host_oracle():
    rng = np.random.default_rng(5)
    sizes = np.asarray([4, 9, 17])
    label = rng.integers(0, 3, size=int(sizes.sum()))
    m = _metric(sizes, label, device=False)
    assert m.accepts_device_score is False and m._dev_fn is None


def test_trainer_routes_device_score_to_ndcg():
    """metric=ndcg defaults to the device kernel inside training: the
    trainer hands the metric its DEVICE score and the recorded values
    match the host oracle run on the same buffer."""
    rng = np.random.default_rng(6)
    sizes = np.concatenate([rng.integers(1, 30, size=20), [1, 70]])
    N = int(sizes.sum())
    X = rng.normal(size=(N, 6))
    y = rng.integers(0, 5, size=N).astype(np.float64)
    params = {"objective": "lambdarank", "metric": "ndcg",
              "eval_at": [1, 5], "num_leaves": 15, "min_data_in_leaf": 5,
              "verbose": -1}
    ds = lgb.Dataset(X, label=y, group=sizes, params=params)
    res = {}
    bst = lgb.train(params, ds, 4, valid_sets=[ds], valid_names=["t"],
                    evals_result=res, verbose_eval=False)
    g = bst._gbdt
    m = g.metrics[0]
    assert m.accepts_device_score and m._dev_fn is not None
    host = dict((k, v) for k, v, _ in
                m.eval_host(np.asarray(g._train_score[:, 0])))
    assert abs(res["t"]["ndcg@5"][-1] - host["ndcg@5"]) <= 1e-6
    # lambdamart_norm off rides the same eval plane
    p2 = dict(params, lambdamart_norm=False)
    ds2 = lgb.Dataset(X, label=y, group=sizes, params=p2)
    res2 = {}
    lgb.train(p2, ds2, 4, valid_sets=[ds2], valid_names=["t"],
              evals_result=res2, verbose_eval=False)
    assert np.all(np.isfinite(res2["t"]["ndcg@5"]))
    # the norm knob changes gradients, so trajectories must differ
    assert res2["t"]["ndcg@5"] != res["t"]["ndcg@5"]


def test_lambdamart_norm_branches_device_host_parity():
    """Device-vs-oracle NDCG parity holds on scores produced by BOTH
    lambdamart_norm branches (the satellite's norm on/off coverage, at
    the metric layer where the kernel actually runs)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(7)
    sizes = np.concatenate([rng.integers(1, 40, size=30), [1, 120]])
    N = int(sizes.sum())
    label = rng.integers(0, 5, size=N).astype(np.float64)
    md = Metadata(N)
    md.set_label(label)
    md.set_query(np.asarray(sizes, np.int64))
    score = rng.normal(size=N).astype(np.float32)
    for norm in (True, False):
        cfg = Config.from_params({"objective": "lambdarank",
                                  "lambdamart_norm": norm, "verbose": -1})
        obj = LambdarankNDCG(cfg)
        obj.init(md, N)
        g, _h = obj.get_gradients(jnp.asarray(score))
        stepped = (score - 0.1 * np.asarray(g)).astype(np.float32)
        m = _metric(sizes, label)
        _assert_device_matches_host(m, stepped)


# ---------------------------------------------------------------------------
# 2. query-aligned data-parallel lambdarank
# ---------------------------------------------------------------------------

def _rank_problem(seed=5, nq=50, max_docs=60, extra=(1, 200, 3)):
    rng = np.random.default_rng(seed)
    sizes = np.concatenate([rng.integers(1, max_docs, size=nq),
                            list(extra)])
    N = int(sizes.sum())
    label = rng.integers(0, 5, size=N).astype(np.float64)
    score = rng.normal(size=N).astype(np.float32)
    return sizes, N, label, score


def _init_objective(sizes, N, label, **params):
    cfg = Config.from_params({"objective": "lambdarank", "verbose": -1,
                              **params})
    obj = LambdarankNDCG(cfg)
    md = Metadata(N)
    md.set_label(label)
    md.set_query(np.asarray(sizes, np.int64))
    obj.init(md, N)
    return obj


def test_query_shard_plan_snaps_to_query_boundaries():
    from lightgbm_tpu.parallel.rank_shard import plan_query_shards
    sizes, N, label, _ = _rank_problem()
    b = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
    for D in (2, 3, 4, 8):
        plan = plan_query_shards(b, D)
        # every cut IS a query boundary — no query straddles a shard
        assert set(plan.row_cuts.tolist()) <= set(b.tolist())
        assert plan.row_cuts[0] == 0 and plan.row_cuts[-1] == N
        # gather covers each original row exactly once; padding slots
        # carry the sentinel N
        real = plan.gather[plan.gather < N]
        assert len(real) == N and len(set(real.tolist())) == N
        spans = (plan.row_cuts[1:] - plan.row_cuts[:-1])
        assert plan.S == spans.max()
        # greedy balance: no shard exceeds the ideal share by more
        # than the largest single query
        assert plan.S <= N / D + sizes.max()


@pytest.mark.parametrize("D", [2, 3])
def test_sharded_rank_grads_match_single_device_oracle(D):
    """The 2-device (and 3-device) mesh differential: pair lambdas
    computed INSIDE the mesh over query-aligned shards are BIT-IDENTICAL
    to the single-device oracle — every query lives wholly on one shard,
    so per-row sums see the same addends in the same order."""
    import jax.numpy as jnp

    from lightgbm_tpu.parallel.mesh import build_mesh
    from lightgbm_tpu.parallel.rank_shard import enable_query_sharded_grads
    sizes, N, label, score = _rank_problem()
    for norm in (True, False):
        obj = _init_objective(sizes, N, label, lambdamart_norm=norm)
        g0, h0 = map(np.asarray, obj.get_gradients(jnp.asarray(score)))
        mesh = build_mesh(f"data:{D}")
        assert mesh.devices.size == D
        sh = enable_query_sharded_grads(obj, mesh)
        assert sh.plan.D == D
        g1, h1 = map(np.asarray, obj.get_gradients(jnp.asarray(score)))
        np.testing.assert_array_equal(g0, g1)
        np.testing.assert_array_equal(h0, h1)


def test_sharded_rank_grads_weighted_rows():
    """Row weights apply AFTER the shard_map unpad, so weighted
    gradients match the oracle too."""
    import jax.numpy as jnp

    from lightgbm_tpu.parallel.mesh import build_mesh
    from lightgbm_tpu.parallel.rank_shard import enable_query_sharded_grads
    rng = np.random.default_rng(13)
    sizes, N, label, score = _rank_problem(seed=13, nq=25, max_docs=40)
    w = (0.5 + rng.random(N)).astype(np.float32)
    cfg = Config.from_params({"objective": "lambdarank", "verbose": -1})
    obj = LambdarankNDCG(cfg)
    md = Metadata(N)
    md.set_label(label)
    md.set_weights(w)
    md.set_query(np.asarray(sizes, np.int64))
    obj.init(md, N)
    g0, h0 = map(np.asarray, obj.get_gradients(jnp.asarray(score)))
    enable_query_sharded_grads(obj, build_mesh("data:2"))
    g1, h1 = map(np.asarray, obj.get_gradients(jnp.asarray(score)))
    np.testing.assert_array_equal(g0, g1)
    np.testing.assert_array_equal(h0, h1)


def test_rank_data_parallel_end_to_end(replace_plan):
    """tree_learner=data on a 2-device CPU mesh arms the query-aligned
    sharding; the eval trajectory is identical with the sharding on vs
    off (same mesh; the global pair pass is the plan's reference) and
    close to the serial learner."""
    rng = np.random.default_rng(17)
    sizes = np.concatenate([rng.integers(1, 50, size=40), [1, 150]])
    N = int(sizes.sum())
    X = rng.normal(size=(N, 8))
    y = rng.integers(0, 5, size=N).astype(np.float64)
    base = {"objective": "lambdarank", "metric": "ndcg", "eval_at": [5],
            "num_leaves": 15, "min_data_in_leaf": 5, "verbose": -1}

    def train(extra):
        p = dict(base, **extra)
        ds = lgb.Dataset(X, label=y, group=sizes, params=p)
        res = {}
        bst = lgb.train(p, ds, 6, valid_sets=[ds], valid_names=["t"],
                        evals_result=res, verbose_eval=False)
        return bst, res["t"]["ndcg@5"]

    b1, t1 = train({"tree_learner": "data", "tpu_mesh_shape": "data:2"})
    assert b1._gbdt._plan.rank_sharded_grad is True
    assert b1._gbdt.objective._shard is not None
    replace_plan(rank_sharded_grad=False)
    b2, t2 = train({"tree_learner": "data", "tpu_mesh_shape": "data:2"})
    assert b2._gbdt.objective._shard is None
    assert t1 == t2
    replace_plan()
    _, t0 = train({})
    np.testing.assert_allclose(t0, t1, atol=5e-3)


# ---------------------------------------------------------------------------
# 3. fused rank gradients through _grow_apply_fused
# ---------------------------------------------------------------------------

def _train_scores(X, y, sizes, params, iters=6):
    ds = lgb.Dataset(X, label=y, group=sizes, params=params)
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(iters):
        bst.update()
    return bst, np.asarray(bst._gbdt._train_score)


def test_fused_rank_gradients_bit_identical(replace_plan):
    """lambdarank inherits supports_fused_grad=True — this pins it: the
    pair pass traced INSIDE the growth jit produces bit-identical train
    scores to the unfused oracle (the differential PR 11 ran for binary,
    now for rank)."""
    rng = np.random.default_rng(19)
    sizes = np.concatenate([rng.integers(1, 40, size=30), [1, 120]])
    N = int(sizes.sum())
    X = rng.normal(size=(N, 8))
    y = rng.integers(0, 5, size=N).astype(np.float64)
    base = {"objective": "lambdarank", "num_leaves": 15,
            "min_data_in_leaf": 5, "verbose": -1}
    bf, sf = _train_scores(X, y, sizes, dict(base))
    assert bf._gbdt._plan.fused_grad is True
    assert bf._gbdt._grow_apply_fused is not None
    replace_plan(fused_grad=False)
    bu, su = _train_scores(X, y, sizes, dict(base))
    assert bu._gbdt._grow_apply_fused is None
    np.testing.assert_array_equal(sf, su)


def test_fused_rank_gradients_bit_identical_wave_interpret(monkeypatch,
                                                           replace_plan):
    """The same fused/unfused differential END TO END through the wave
    pipeline (LGBM_TPU_FORCE_WAVE=interpret) — the growth jit the fused
    pass actually shares on TPU."""
    monkeypatch.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    rng = np.random.default_rng(23)
    sizes = np.concatenate([rng.integers(1, 25, size=16), [1, 60]])
    N = int(sizes.sum())
    X = rng.normal(size=(N, 5))
    y = rng.integers(0, 4, size=N).astype(np.float64)
    base = {"objective": "lambdarank", "num_leaves": 7,
            "min_data_in_leaf": 5, "verbose": -1}
    bf, sf = _train_scores(X, y, sizes, dict(base), iters=3)
    assert bf._gbdt.uses_wave is True
    assert bf._gbdt._plan.fused_grad is True
    replace_plan(fused_grad=False)
    bu, su = _train_scores(X, y, sizes, dict(base), iters=3)
    assert bu._gbdt.uses_wave is True
    np.testing.assert_array_equal(sf, su)


def test_rank_wave_smoke_device_metric_parity(monkeypatch):
    """run_suite quick-tier rank smoke: a small lambdarank train runs
    END TO END through the wave path on CPU (Pallas interpreter) with
    the device NDCG kernel as the eval plane, and the recorded metric
    matches the host oracle."""
    monkeypatch.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    rng = np.random.default_rng(29)
    sizes = np.concatenate([rng.integers(1, 25, size=14), [1, 50]])
    N = int(sizes.sum())
    X = rng.normal(size=(N, 5))
    y = rng.integers(0, 4, size=N).astype(np.float64)
    params = {"objective": "lambdarank", "metric": "ndcg",
              "eval_at": [3], "num_leaves": 7, "min_data_in_leaf": 5,
              "verbose": -1}
    ds = lgb.Dataset(X, label=y, group=sizes, params=params)
    res = {}
    bst = lgb.train(params, ds, 3, valid_sets=[ds], valid_names=["t"],
                    evals_result=res, verbose_eval=False)
    g = bst._gbdt
    assert g.uses_wave is True
    m = g.metrics[0]
    assert m.accepts_device_score is True
    host = dict((k, v) for k, v, _ in
                m.eval_host(np.asarray(g._train_score[:, 0])))
    assert abs(res["t"]["ndcg@3"][-1] - host["ndcg@3"]) <= 1e-6
    assert np.all(np.isfinite(res["t"]["ndcg@3"]))


# ---------------------------------------------------------------------------
# 4. cost models + config plumbing
# ---------------------------------------------------------------------------

def test_rank_pair_cost_scaling():
    from lightgbm_tpu.ops.rank import bucket_shapes, rank_pair_cost
    # enough queries that chunk padding doesn't distort the ratio
    f1, b1 = rank_pair_cost([64] * 1024)
    f2, b2 = rank_pair_cost([128] * 1024)
    # doubling every query size quadruples the pair-slot flops and
    # doubles the stream bytes
    assert f2 / f1 == pytest.approx(4.0, rel=0.05)
    assert b2 / b1 == pytest.approx(2.0, rel=0.01)
    # pow2 padding is charged: 65-doc queries cost like 128-doc ones
    f3, _ = rank_pair_cost([65] * 1024)
    assert f3 == f2
    # chunk padding is charged too: a 10-query bucket pads its query
    # count to one full lax.map chunk (the [qc, P, P] tensor the map
    # step really materializes)
    assert bucket_shapes([64] * 10) == [(64, 128, 128)]


def test_ndcg_eval_cost_scaling():
    from lightgbm_tpu.ops.rank import ndcg_eval_cost
    f1, _ = ndcg_eval_cost([64] * 1024, num_at=1)
    f2, _ = ndcg_eval_cost([128] * 1024, num_at=1)
    # sort-dominated: slightly superlinear in P, far below quadratic
    assert 2.0 <= f2 / f1 <= 2.7
    fk1, bk1 = ndcg_eval_cost([64] * 1024, num_at=1)
    fk5, bk5 = ndcg_eval_cost([64] * 1024, num_at=5)
    assert fk5 > fk1 and bk5 > bk1
    # eval is orders cheaper than the pair pass at the same shape
    from lightgbm_tpu.ops.rank import rank_pair_cost
    assert rank_pair_cost([64] * 1024)[0] / fk1 > 10


def test_roofline_ranking_plane_numbers():
    """docs/ROOFLINE.md's 'Ranking plane' table is machine-checked
    here: the quoted GFLOP/MB numbers at the two canonical shapes come
    from these helpers."""
    from lightgbm_tpu.ops.rank import (mslr_like_sizes, ndcg_eval_cost,
                                       rank_pair_cost)
    sizes = mslr_like_sizes(200_000)
    assert len(sizes) == 2848 and int(sizes.sum()) == 200_000
    fp, bp = rank_pair_cost(sizes)
    assert fp / 1e9 == pytest.approx(1.83, rel=0.01)
    assert bp / 1e6 == pytest.approx(12.6, rel=0.01)
    fe, be = ndcg_eval_cost(sizes, num_at=1)
    assert fe / 1e9 == pytest.approx(0.022, rel=0.05)
    sizes = mslr_like_sizes(2_270_296)
    assert len(sizes) == 31098
    fp, bp = rank_pair_cost(sizes)
    assert fp / 1e9 == pytest.approx(23.0, rel=0.01)
    assert bp / 1e6 == pytest.approx(107.4, rel=0.01)
    fe, _ = ndcg_eval_cost(sizes, num_at=1)
    assert fe / 1e9 == pytest.approx(0.211, rel=0.01)
    # VPU-seconds the doc quotes (~2 TFLOP/s elementwise)
    assert fp / 2e12 * 1e3 == pytest.approx(11.5, rel=0.02)


def test_rank_knobs_resume_neutral_and_documented():
    """``tpu_rank_device_eval`` is resume-neutral (eval-only): flipping it
    must not refuse a checkpoint resume.  Nor does naming the sharded pair
    pass, which is a reference path of the plan and no parameter."""
    from lightgbm_tpu.robust.checkpoint import config_digest
    base = Config.from_params({"objective": "lambdarank", "verbose": -1})
    assert base.tpu_rank_device_eval is True  # defaults on
    assert not hasattr(base, "tpu_rank_sharded_grad")
    for knob in ("tpu_rank_device_eval", "tpu_rank_sharded_grad"):
        flipped = Config.from_params({"objective": "lambdarank",
                                      knob: False, "verbose": -1})
        assert config_digest(base) == config_digest(flipped), knob


def test_bench_rank_data_matches_cost_model_shape():
    """bench.py's rank generator and the ROOFLINE cost helpers draw the
    SAME query-size distribution (the satellite contract that lets one
    table price the bench shape)."""
    import bench
    from lightgbm_tpu.ops.rank import mslr_like_sizes
    X, y, q = bench.mslr_like_data(5_000)
    assert int(q.sum()) == len(y) == X.shape[0] == 5_000
    rng = np.random.default_rng(0)
    np.testing.assert_array_equal(q, mslr_like_sizes(5_000, rng=rng))
