"""Compaction in ``_wave``: the index a tier gathers by, built without
touching every row one at a time.

``pack_active_rows`` is the half that costs per row the chip holds (the
active-row mask by compare, 32 rows a packed word, a running count of the
words) and ``compact_index`` the half that costs per row of the tier (a
word's first output placed by a scatter of the word starts, the rest by a
running maximum, the row within the word as its k-th set bit).  Held here:

- the index against ``np.flatnonzero(active)[:T]`` on the shapes that break
  such builds, and what each layout of the bins gathers by it;
- the growth program's jaxpr: no gather or scatter with an index a row, no
  sort over the rows, the loop's invariants outside the loop;
- the tree and ``leaf_id`` against the index build this one replaced (a
  table gather for the mask, a ``cumsum``, an N-element scatter), kept as
  NumPy below, bit for bit: plain, bundled, and row-sharded over four
  virtual devices.

A CPU run gives indices and trees, never a time (PERF.md 5 and 6 have the
chip's).
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.core import wave_grower
from lightgbm_tpu.core.meta import (SplitConfig, build_device_meta,
                                    padded_phys_width)
from lightgbm_tpu.core.plan import GrowthPlan
from lightgbm_tpu.core.wave_grower import (build_wave_grow_fn, compact_index,
                                           pack_active_rows)

PEND = np.array([3, -1, 7, 12, -1, -1, 0], np.int32)    # -1: empty slots


def _old_mask(leaf_id, pend_small, weighted):
    """The mask as it was built until PR 29, statement for statement as
    NumPy: a table of the pending leaves (empty slots write the dead entry)
    gathered by every row's leaf id."""
    top = int(max(leaf_id.max(initial=0), pend_small.max())) + 2
    tbl = np.zeros(top + 1, bool)
    tbl[np.where(pend_small >= 0, pend_small, top)] = pend_small >= 0
    return tbl[np.clip(leaf_id, 0, top)] & weighted


def _old_index(active, T):
    """The index as it was built until PR 29: a running count of the mask
    and an N-element scatter of the row numbers, inactive rows dropped."""
    pos = np.cumsum(active.astype(np.int32))
    idx = np.zeros(len(active), np.int32)
    idx[pos[active] - 1] = np.flatnonzero(active)
    return idx[:T]


def _rows(case):
    """``(leaf_id, weighted, T)`` of a case; active rows are those in a
    leaf of ``PEND`` that carry weight."""
    rng = np.random.default_rng(3)
    n = {"not_a_multiple_of_128": 1000 + 37, "one_row": 1}.get(case, 1024)
    idle, busy = 5, PEND[PEND >= 0]
    leaf = np.full(n, idle, np.int32)
    weighted = np.ones(n, bool)
    T = 256
    if case == "none_active":
        pass
    elif case == "every_row_active":
        leaf[:] = rng.choice(busy, n)
        T = n
    elif case in ("n_active_is_T", "n_active_is_T_plus_1"):
        k = T + (case == "n_active_is_T_plus_1")
        leaf[rng.choice(n, k, replace=False)] = rng.choice(busy, k)
    elif case == "not_a_multiple_of_128":
        leaf[rng.random(n) < 0.2] = 7
        leaf[-1] = 3                    # the last row, in the ragged word
    elif case == "only_the_last_group":
        leaf[-5:] = 12
    elif case == "a_run_of_empty_groups":
        leaf[:40] = 3
        leaf[40 + 128 * 5:40 + 128 * 5 + 3] = 0     # 20 empty words between
        leaf[-1] = 7
    elif case == "zero_weights":
        leaf[:] = rng.choice(np.append(busy, idle), n)
        weighted = rng.random(n) < 0.5  # bagging: half the rows carry none
    elif case == "one_row":
        leaf[:] = 3
        T = 1
    else:  # pragma: no cover
        raise AssertionError(case)
    return leaf, weighted, T


CASES = ("none_active", "every_row_active", "n_active_is_T",
         "n_active_is_T_plus_1", "not_a_multiple_of_128",
         "only_the_last_group", "a_run_of_empty_groups", "zero_weights",
         "one_row")


def _bins(layout, n):
    """Row-major bins as ``_wave`` gathers them under a layout: one array,
    or the (narrow u8, wide u16) pair of the mixed-width path."""
    rng = np.random.default_rng(17)
    if layout == "mixed":
        return (rng.integers(0, 64, (n, 4)).astype(np.uint8),
                rng.integers(0, 300, (n, 1)).astype(np.uint16))
    cols = 3 if layout == "bundled" else 5      # EFB: fewer, fuller columns
    return (rng.integers(0, 250, (n, cols)).astype(np.uint8),)


@pytest.mark.parametrize("layout", ["plain", "bundled", "mixed"])
@pytest.mark.parametrize("case", CASES)
def test_index_is_flatnonzero(case, layout):
    leaf, weighted, T = _rows(case)
    active = np.isin(leaf, PEND[PEND >= 0]) & weighted
    want = np.zeros(T, np.int32)
    nz = np.flatnonzero(active)[:T]
    want[:len(nz)] = nz                 # past n_active the index repeats row 0
    words, start, n_active = pack_active_rows(
        jnp.asarray(leaf), jnp.asarray(PEND), jnp.asarray(weighted))
    got = np.asarray(compact_index(words, start, n_active, T))
    assert int(n_active) == active.sum()
    np.testing.assert_array_equal(got, want)
    old = _old_mask(leaf, PEND, weighted)
    np.testing.assert_array_equal(old, active)
    np.testing.assert_array_equal(got, _old_index(old, T))  # as before
    # the packed words are the mask, and the starts its running count
    bits = np.unpackbits(np.asarray(words).view(np.uint8),
                         bitorder="little")
    np.testing.assert_array_equal(bits[:len(leaf)].astype(bool), active)
    assert not bits[len(leaf):].any()
    np.testing.assert_array_equal(
        np.asarray(start),
        np.concatenate([[0], np.cumsum(bits.reshape(-1, 32).sum(1))[:-1]]))
    # what a tier gathers by it, whatever the layout of the bins
    for rm in _bins(layout, len(leaf)):
        np.testing.assert_array_equal(
            np.asarray(jnp.take(jnp.asarray(rm), jnp.asarray(got), axis=0)),
            rm[want])


def test_the_next_tier_takes_one_row_more():
    """``n_active == T`` fits tier T whole; one more row and every entry of
    the T-sized index is a real row with one left over, which is why the
    ladder picks the smallest tier ``>= n_active``."""
    for case, left_over in (("n_active_is_T", 0), ("n_active_is_T_plus_1", 1)):
        leaf, weighted, T = _rows(case)
        words, start, n = pack_active_rows(
            jnp.asarray(leaf), jnp.asarray(PEND), jnp.asarray(weighted))
        idx = np.asarray(compact_index(words, start, n, T))
        assert int(n) - T == left_over
        assert (np.diff(idx) > 0).all() and np.isin(leaf[idx], PEND).all()


# ---------------------------------------------------------------------------
# the growth program
# ---------------------------------------------------------------------------

ROWS = 5000     # more than any feature x bin table holds entries (14 x 256)
PARAMS = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 20,
          "verbose": -1}


def _problem(kind):
    """A binned table and what ``build_wave_grow_fn`` needs of it: plain
    columns with NaNs, or a one-hot block that EFB bundles."""
    rng = np.random.default_rng(5)
    if kind == "bundled":
        codes = rng.integers(0, 12, ROWS)
        X = np.zeros((ROWS, 14))
        X[np.arange(ROWS), codes] = 1.0
        X[:, 12:] = rng.normal(size=(ROWS, 2))
        score = rng.normal(size=12)[codes] + X[:, 12]
    else:
        X = rng.normal(size=(ROWS, 6))
        X[rng.random(X.shape) < 0.1] = np.nan
        score = np.nan_to_num(X[:, 0]) + np.nan_to_num(X[:, 1] * X[:, 2])
    y = (score + 0.3 * rng.normal(size=ROWS) > 0).astype(np.float64)
    ds = lgb.Dataset(X, label=y, params=PARAMS)
    ds.construct()
    handle = ds._handle
    cfg = Config.from_params(PARAMS)
    meta, B = build_device_meta(handle, cfg)
    bundled = handle.bundle is not None
    assert bundled == (kind == "bundled")
    g = jnp.asarray((0.5 - y + 0.1 * rng.normal(size=ROWS)).astype(np.float32))
    h = jnp.asarray((0.1 + rng.random(ROWS)).astype(np.float32))
    # bagging: a third of the rows carry no weight and leave the mask
    mask = jnp.asarray((rng.random(ROWS) < 0.67).astype(np.float32))
    # EFB subtracts the sibling after the default-bin fix (and a mesh
    # after the psum: _grow)
    plan = GrowthPlan(hist_mode="highest", interpret=True, gain_gate=0.5,
                      block_rows=128, counts=True, bundled=bundled,
                      fused_sibling=not bundled)
    kw = dict(plan=plan, B_phys=padded_phys_width(handle))
    args = (jnp.asarray(np.ascontiguousarray(handle.X_bin.T)), g, h, mask,
            jnp.ones((handle.num_features,), bool))
    return meta, SplitConfig.from_config(cfg), B, kw, args


def _eqns(jaxpr, inside=()):
    """Every equation of a jaxpr with the primitives it sits under."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner, inside + (eqn.primitive.name,))


def _size(var):
    return int(np.prod(var.aval.shape, dtype=np.int64))


@pytest.mark.parametrize("kind", ["plain", "bundled"])
def test_growth_holds_no_row_sized_gather_scatter_or_sort(kind):
    """What the change exists for, and what a refactor would lose in
    silence: with N rows on the chip, no gather or scatter in the growth
    program takes N indices (a tier's take fewer), nothing sorts N keys,
    and ``[N, 3]`` is stacked once a tree, outside the loop."""
    meta, scfg, B, kw, args = _problem(kind)
    grow = build_wave_grow_fn(meta, scfg, B, **kw)
    eqns = list(_eqns(jax.make_jaxpr(grow)(*args).jaxpr))
    indexed = [(e, _size(e.invars[1])) for e, _ in eqns
               if e.primitive.name == "gather"
               or e.primitive.name.startswith("scatter")]
    assert indexed and max(n for _, n in indexed) < ROWS
    # the tiers below the full one do gather, each by fewer indices
    sizes = {n for e, n in indexed if e.primitive.name == "gather"
             and e.invars[0].aval.shape[:1] == (ROWS,)}
    assert len(sizes) > 3 and max(sizes) < ROWS
    # a word's first output is placed by a scatter of the words' starts
    words = -(-ROWS // 128) * 4
    assert any(n == words for e, n in indexed
               if e.primitive.name == "scatter-max")
    assert not [e for e, _ in eqns if e.primitive.name == "sort"
                and max(_size(v) for v in e.invars) >= ROWS]
    stacked = [inside for e, inside in eqns
               if e.primitive.name == "concatenate"
               and e.outvars[0].aval.shape == (ROWS, 3)]
    assert stacked == [()]              # once, under no loop or branch


class _OldBuild:
    """``pack_active_rows`` / ``compact_index`` as they were before: the
    same contract between the two halves (what the first returns the
    second takes), the mask by a table gather and the index by ``cumsum``
    and an N-element scatter, in NumPy on the host."""

    @staticmethod
    def pack(leaf_id, pend_small, weighted):
        def host(leaf_id, pend_small, weighted):
            active = _old_mask(leaf_id, pend_small, weighted)
            return active, np.int32(active.sum())
        n = leaf_id.shape[0]
        active, n_active = jax.pure_callback(
            host, (jax.ShapeDtypeStruct((n,), jnp.bool_),
                   jax.ShapeDtypeStruct((), jnp.int32)),
            leaf_id, pend_small, weighted)
        return active, None, n_active

    @staticmethod
    def index(active, _, n_active, T):
        return jax.pure_callback(
            lambda active: _old_index(active, T),
            jax.ShapeDtypeStruct((T,), jnp.int32), active)


def _grow(kind, monkeypatch, old):
    if old:
        monkeypatch.setattr(wave_grower, "pack_active_rows", _OldBuild.pack)
        monkeypatch.setattr(wave_grower, "compact_index", _OldBuild.index)
    meta, scfg, B, kw, args = _problem("plain" if kind == "data4" else kind)
    if kind == "data4":
        from lightgbm_tpu.parallel.mesh import (
            AXIS, make_data_parallel_wave_grower)
        mesh = Mesh(np.asarray(jax.devices()[:4]), (AXIS,))
        grow = make_data_parallel_wave_grower(
            meta, scfg, B, mesh,
            dataclasses.replace(kw["plan"], fused_sibling=False))
    else:
        grow = jax.jit(build_wave_grow_fn(meta, scfg, B, **kw))
    tree, leaf_id, stats = grow(*args)
    return tree, np.asarray(leaf_id), wave_grower.wave_counts(stats)


@pytest.mark.parametrize("kind", ["plain", "bundled", "data4"])
def test_tree_and_leaf_id_equal_the_old_builds(kind, monkeypatch):
    new_tree, new_leaf, counts = _grow(kind, monkeypatch, old=False)
    old_tree, old_leaf, old_counts = _grow(kind, monkeypatch, old=True)
    assert int(new_tree.num_leaves) > 8
    for field in new_tree._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(new_tree, field)),
            np.asarray(getattr(old_tree, field)), err_msg=field)
    np.testing.assert_array_equal(new_leaf, old_leaf)
    assert counts == old_counts
    # the differential means something: waves below the full tier built
    # an index and gathered by it, on every chip
    chips = 4 if kind == "data4" else 1
    assert len(counts["compact_waves"]) == chips
    assert min(counts["compact_waves"]) > 2
    for built, kern in zip(counts["compact_waves"], counts["kernel_rows"]):
        assert kern < counts["waves"] * (ROWS // chips)
        assert built <= counts["waves"]
