"""The score update's look-up (``core/predict.py leaf_value_lookup``): equal
to the gather ``leaf_value[leaf_id]`` bit for bit on both sides of its
constant, through every caller (``grow_apply`` with fused and unfused
gradients, the slow path's ``apply_leaf``), and the invariant it depends on
and the gather did not: 0 <= ``leaf_id`` < L on every row, on every grower
path.

A CPU run gives results and counts, never a time (PERF.md 6, PR 39 has the
chip's readings).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting import gbdt as gbdt_mod
from lightgbm_tpu.core.predict import (DENSE_LOOKUP_MAX_LEAVES,
                                       leaf_value_lookup)

C = DENSE_LOOKUP_MAX_LEAVES
SPECIAL = np.array([-0.0, np.inf, 1e-40, -np.inf, -1e-41, 0.0, 0.25, 0.25],
                   np.float32)     # a denormal of each sign, a repeated value


def _bits(x):
    return np.asarray(x).view(np.int32)


def _fresh_jit_cache():
    """The look-up's form is chosen when a closure is traced, and the
    trainer's jitted helpers are cached process-wide: a training that must
    trace the helper as it stands now starts from empty caches."""
    gbdt_mod._JIT_CACHE.clear()
    gbdt_mod._FUSED_JIT_CACHE.clear()


@pytest.fixture(autouse=True)
def _no_closure_outlives_its_test():
    yield
    _fresh_jit_cache()


def _values(L: int, rng) -> np.ndarray:
    lv = (rng.normal(size=L) * 0.1).astype(np.float32)
    k = min(L, SPECIAL.size)
    lv[L - k:] = SPECIAL[:k]        # the specials in the LAST leaves: a
    return lv                       # form that drops a tail drops them


@pytest.mark.parametrize("N", [1, 127, 128, 1_000_003])
@pytest.mark.parametrize("L", [1, 2, 31, 255, 256, C, C + 1])
def test_lookup_equals_gather_bit_for_bit(L, N):
    rng = np.random.default_rng(L * 7 + N)
    lv = _values(L, rng)
    lid = rng.integers(0, L, size=N, dtype=np.int32)
    lid[-1] = L - 1                 # the last leaf, whatever the draw
    lid[0] = 0 if N > 1 else L - 1
    got = jax.jit(leaf_value_lookup)(jnp.asarray(lv), jnp.asarray(lid))
    assert got.dtype == jnp.float32 and got.shape == (N,)
    assert np.array_equal(_bits(got), _bits(lv[lid]))


@pytest.mark.parametrize("L", [255, C + 1], ids=["selects", "gather"])
def test_form_is_chosen_by_the_static_leaf_count(L):
    """Up to the constant no ``gather`` is traced; above it the gather is
    back.  The shape decides, under ``jit``: no parameter, no plan field."""
    text = str(jax.make_jaxpr(leaf_value_lookup)(
        jnp.zeros((L,), jnp.float32), jnp.zeros((64,), jnp.int32)))
    assert ("gather" in text) == (L > C)


def test_ungrown_tree_leaves_the_score_untouched():
    """``grow_apply`` zeroes a tree that did not grow (``lv`` all zero) and
    its rows all sit in leaf 0: the update adds +0.0 to every row."""
    rng = np.random.default_rng(3)
    score = rng.normal(size=1000).astype(np.float32)
    score[:3] = [np.inf, -np.inf, 0.0]
    add = leaf_value_lookup(jnp.zeros((255,), jnp.float32),
                            jnp.zeros((1000,), jnp.int32))
    assert not _bits(add).any()
    assert np.array_equal(_bits(jnp.asarray(score) + add), _bits(score))


def test_id_out_of_range_reads_by_its_low_bits_where_the_gather_clamped():
    """What the docstring says of an id no producer makes: the select tree
    reads the leaf the id's low bits name (the gather reads the last leaf).
    Held so that a change of the form shows here, not in a model."""
    lv = jnp.asarray([1.0, 2.0, 3.0, 4.0], jnp.float32)
    lid = jnp.asarray([0, 3, 4, 5], jnp.int32)
    assert np.array_equal(np.asarray(leaf_value_lookup(lv, lid)),
                          [1.0, 4.0, 1.0, 2.0])
    assert np.array_equal(np.asarray(lv[lid]), [1.0, 4.0, 4.0, 4.0])


# ---- through the trainer ---------------------------------------------------

ROWS = 1500


def _table(rows: int = ROWS, seed: int = 5):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, 6))
    y = X[:, 0] + X[:, 1] * X[:, 2] + 0.3 * rng.normal(size=rows)
    X[rng.random(size=X.shape) < 0.05] = np.nan
    return X, y


def _train(params, iters: int = 3, rows: int = ROWS, binary: bool = True):
    """``iters`` updates from a fresh jit cache.  Returns the Booster, the
    model's tree section, the train score's bits after every update (and
    before the first) and, for every tree, ``(leaf_id, leaf capacity, the
    leaf values the update added)`` as the growth programs and
    ``apply_leaf`` saw them."""
    _fresh_jit_cache()
    X, y = _table(rows)
    if binary:
        y = (y > 0).astype(np.float64)
    params = {"verbose": -1, "num_leaves": 15, "min_data_in_leaf": 5,
              "learning_rate": 0.3, "boost_from_average": False, **params}
    bst = lgb.Booster(params=params,
                      train_set=lgb.Dataset(X, label=y, params=params))
    g = bst._gbdt
    seen, grown = [], []

    def recording(fn, fused_update: bool):
        def call(*a, **kw):
            out = fn(*a, **kw)
            tree = (np.asarray(out[1]), int(out[0].leaf_value.shape[0]))
            if fused_update:    # grow_apply: the values are the shrunk ones
                seen.append((*tree, np.asarray(out[0].leaf_value)))
            else:               # the slow path: apply_leaf says them
                grown.append(tree)
            return out
        return call

    def recording_apply(fn):
        def call(score_col, leaf_id, leaf_values):
            seen.append((*grown.pop(), np.asarray(leaf_values)))
            return fn(score_col, leaf_id, leaf_values)
        return call
    for name in ("_grow_apply", "_grow_apply_fused", "_grow"):
        if getattr(g, name, None) is not None:
            setattr(g, name, recording(getattr(g, name), name != "_grow"))
    g._apply_leaf = recording_apply(g._apply_leaf)
    scores = [_bits(g._train_score)]
    for _ in range(iters):
        bst.update()
        scores.append(_bits(g._train_score))
    model = bst.model_to_string().split("\nparameters:")[0]
    return bst, model, scores, seen


def _numpy_gather(leaf_value, leaf_id):
    """``leaf_value[leaf_id]`` by NumPy, from inside the program: the gather
    with nothing fused into it.  A ``jnp`` gather will not do as the
    reference here: XLA:CPU fuses ``leaf_value * lr`` into the gather and
    contracts it with the add into one FMA a row, an ulp off the exported
    leaf value in a third of the rows (neither a gather of the bit patterns
    nor an ``optimization_barrier`` stops it), where the select tree adds
    the exported value exactly (PERF.md 6, PR 39)."""
    return jax.pure_callback(
        lambda lv, lid: np.asarray(lv)[np.asarray(lid)],
        jax.ShapeDtypeStruct(leaf_id.shape, jnp.float32), leaf_value, leaf_id)


PATHS = {
    # the fused gradients, the plan's default for a built-in objective
    "grow_apply_fused": ({"objective": "binary", "device_type": "cpu"}, {}),
    "grow_apply_unfused": ({"objective": "binary", "device_type": "cpu"},
                           {"fused_grad": False}),
    # a renew objective takes the slow path: ``_grow`` then ``apply_leaf``
    "slow_path_apply_leaf": ({"objective": "regression_l1",
                              "device_type": "cpu"}, {}),
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_three_iterations_equal_the_gather_to_the_bit(path, monkeypatch,
                                                      replace_plan):
    params, plan_fields = PATHS[path]
    if plan_fields:
        replace_plan(**plan_fields)
    binary = params["objective"] == "binary"
    bst, model, scores, seen = _train(params, binary=binary)
    g = bst._gbdt
    assert g.fused_grad_active() == (path == "grow_apply_fused")
    assert (g.objective.is_renew_tree_output
            == (path == "slow_path_apply_leaf"))
    assert len(seen) == 3
    # NumPy's replay: each update added the tree's own leaf values, exactly
    for before, after, (leaf_id, _, lv) in zip(scores, scores[1:], seen):
        assert len(np.unique(leaf_id)) > 1          # the tree did grow
        want = before.view(np.float32)[:, 0] + lv[leaf_id]
        assert np.array_equal(after[:, 0], _bits(want))
    # the program's: the same three iterations with the gather everywhere
    monkeypatch.setattr(gbdt_mod, "leaf_value_lookup", _numpy_gather)
    _, model_g, scores_g, _ = _train(params, binary=binary)
    assert model == model_g
    assert np.array_equal(scores[-1], scores_g[-1])


def _wave(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    return {"objective": "binary", "device_type": "tpu"}


def _xla(monkeypatch):
    monkeypatch.delenv("LGBM_TPU_FORCE_WAVE", raising=False)
    return {"objective": "binary", "device_type": "cpu"}


def _goss(monkeypatch):
    # sampling starts after int(1 / learning_rate) = 2 iterations
    return {**_wave(monkeypatch), "boosting": "goss", "top_rate": 0.2,
            "other_rate": 0.1, "learning_rate": 0.5}


def _bagged(monkeypatch):
    return {**_xla(monkeypatch), "bagging_fraction": 0.5, "bagging_freq": 1}


def _mesh_xla(monkeypatch):
    # 1,501 rows over four devices: three rows of padding
    return {**_xla(monkeypatch), "tree_learner": "data",
            "tpu_mesh_shape": "data:4"}


def _mesh_wave(monkeypatch):
    return {**_wave(monkeypatch), "tree_learner": "data",
            "tpu_mesh_shape": "data:4"}


GROWERS = {"serial_wave": _wave, "xla_grower": _xla,
           "goss_out_of_bag_rows": _goss, "bagging_out_of_bag_rows": _bagged,
           "mesh_xla_rows_not_dividing": _mesh_xla,
           "mesh_wave_rows_not_dividing": _mesh_wave}


@pytest.mark.parametrize("grower", sorted(GROWERS))
def test_leaf_id_is_in_range_on_every_row(grower, monkeypatch):
    """The invariant the look-up depends on: every row a growth program
    returns, in the bag or out of it, carries 0 <= ``leaf_id`` < L, and the
    ids a grown tree uses are exactly its leaves."""
    rows = 1501 if grower.startswith("mesh") else ROWS
    iters = 4 if grower.startswith("goss") else 3
    bst, _, _, seen = _train(GROWERS[grower](monkeypatch), iters=iters,
                             rows=rows)
    g = bst._gbdt
    assert g._plan.wave == ("wave" in grower or "goss" in grower)
    if grower.startswith("mesh"):
        assert g._plan.learner == "data" and rows % 4
    if "out_of_bag" in grower:
        bag = np.asarray(bst.bag_mask())
        assert 0 < int(bag.sum()) < rows            # rows outside the bag
    assert len(seen) == iters
    trees = bst.dump_model()["tree_info"]
    for (leaf_id, L, _), tree in zip(seen, trees):
        assert leaf_id.shape == (rows,) and L == 15
        assert leaf_id.min() >= 0 and leaf_id.max() < L
        assert set(np.unique(leaf_id)) == set(range(tree["num_leaves"]))
