"""Compaction in ``_wave``: a tier's rows by one streamed pass.

``pack_active_rows`` counts the wave's active rows (the mask by compare, 32
rows a packed word, a running count of the words), which picks the tier;
below the full tier the streamed kernel (``ops/pallas_compact.py``) then
passes over every row the chip holds once and writes the active ones, in
row order, to the front of the tier: bins feature-major as the histogram
kernel reads them, the row vectors and ``leaf_id`` as 32-bit word rows
placed by the same lane permutation.  Held here, with the kernel
interpreted:

- the streamed tier against ``np.flatnonzero(active)`` bit for bit on the
  shapes that break such builds, under each layout of the bins, and its
  tail (leaf -2, finite vectors);
- the two pieces the placement is made of, alone: the word packing there
  and back whatever the bits mean, and the compress network (ranks to
  source lanes) against ``np.flatnonzero`` a line;
- the growth program's jaxpr: no gather or scatter with an index a row or
  a row of a tier (the lane gather INSIDE the streamed kernel permutes
  128 lanes of a register and is no XLA gather: the jaxpr walk stops at
  the kernel's call), no sort over the rows, one streamed pass shared by
  the tiers, the loop's invariants outside the loop;
- the tree and ``leaf_id`` against the build this one replaced (an index
  by ``cumsum`` and scatter, the tier gathered by it out of row-major bins,
  the tail repeating row 0), kept as NumPy below, bit for bit: plain,
  bundled, and row-sharded over four virtual devices.

A CPU run gives rows and trees, never a time (PERF.md 5 and 6 have the
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
from lightgbm_tpu.core.wave_grower import (active_rows, build_wave_grow_fn,
                                           pack_active_rows, tier_ladder)
from lightgbm_tpu.ops.pallas_compact import (compress_lanes, row_words,
                                             rows_value, stream_rows,
                                             tier_front, word_rows)

PEND = np.array([3, -1, 7, 12, -1, -1, 0], np.int32)    # -1: empty slots
GRID_ROWS = 128 * 128           # rows a grid step of the streamed kernel


def _old_mask(leaf_id, pend_small, weighted):
    """The mask as it was built until PR 29, statement for statement as
    NumPy: a table of the pending leaves (empty slots write the dead entry)
    gathered by every row's leaf id."""
    top = int(max(leaf_id.max(initial=0), pend_small.max())) + 2
    tbl = np.zeros(top + 1, bool)
    tbl[np.where(pend_small >= 0, pend_small, top)] = pend_small >= 0
    return tbl[np.clip(leaf_id, 0, top)] & weighted


def _old_index(active, T):
    """The index a tier gathered by until PR 31: a running count of the
    mask and a scatter of the row numbers, inactive rows dropped; past
    ``n_active`` it repeats row 0."""
    pos = np.cumsum(active.astype(np.int32))
    idx = np.zeros(max(len(active), T), np.int32)
    idx[pos[active] - 1] = np.flatnonzero(active)
    return idx[:T]


def _rows(case):
    """``(leaf_id, weighted, T)`` of a case; active rows are those in a
    leaf of ``PEND`` that carry weight."""
    rng = np.random.default_rng(3)
    n = {"not_a_multiple_of_128": 1000 + 37, "one_row": 1,
         "a_run_across_a_block_edge": GRID_ROWS + 700}.get(case, 1024)
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
    elif case == "a_run_across_a_block_edge":
        # 1,000 scattered rows, then an unbroken run over the edge between
        # two grid steps of the kernel: it crosses a sub-block of 128 rows,
        # a grid step, and the staging buffer's flush at 1,024 rows
        leaf[rng.choice(GRID_ROWS - 200, 1000, replace=False)] = 7
        leaf[GRID_ROWS - 150:GRID_ROWS + 150] = rng.choice(busy, 300)
        T = 1408
    else:  # pragma: no cover
        raise AssertionError(case)
    return leaf, weighted, T


CASES = ("none_active", "every_row_active", "n_active_is_T",
         "n_active_is_T_plus_1", "not_a_multiple_of_128",
         "only_the_last_group", "a_run_of_empty_groups", "zero_weights",
         "one_row", "a_run_across_a_block_edge")

# what a row vector may hold, bit for bit: the copy is of words
ODD_FLOATS = np.array([-0.0, 1e-40, -1e-45, 1e30, -3.0, 65536.0, np.inf],
                      np.float32)


def _bins(layout, n):
    """Feature-major bins as ``_wave`` streams them under a layout: one
    array, or the (narrow u8, wide u16) pair of the mixed-width path."""
    rng = np.random.default_rng(17)
    if layout == "mixed":
        return (rng.integers(0, 64, (4, n)).astype(np.uint8),
                rng.integers(0, 300, (2, n)).astype(np.uint16))
    cols = 3 if layout == "bundled" else 5      # EFB: fewer, fuller columns
    return (rng.integers(0, 256, (cols, n)).astype(np.uint8), None)


def _streamed(leaf, weighted, bins, g, h, c, cap):
    """``(n_active, streamed pair)`` of one wave, as ``_wave`` makes them."""
    leaf = jnp.asarray(leaf)
    active = active_rows(leaf, jnp.asarray(PEND), jnp.asarray(weighted))
    _, start, n_active = pack_active_rows(active)
    narrow, wide = bins
    streamed = stream_rows(
        jnp.asarray(narrow),
        row_words(*(jnp.asarray(v) for v in (g, h, c)),
                  wide=None if wide is None else jnp.asarray(wide)),
        leaf, active, start, n_active, cap, interpret=True)
    return n_active, streamed


def _bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("layout", ["plain", "bundled", "mixed"])
@pytest.mark.parametrize("case", CASES)
def test_streamed_tier_is_flatnonzero(case, layout):
    leaf, weighted, T = _rows(case)
    n = len(leaf)
    active = np.isin(leaf, PEND[PEND >= 0]) & weighted
    nz = np.flatnonzero(active)[:T]
    rng = np.random.default_rng(23)
    g = rng.normal(size=n).astype(np.float32)
    h = rng.integers(-40, 40, n).astype(np.float32)     # quantised modes
    g[rng.choice(n, min(n, 64))] = rng.choice(ODD_FLOATS, min(n, 64))
    c = weighted.astype(np.float32)
    narrow, wide = bins = _bins(layout, n)
    n_active, streamed = _streamed(leaf, weighted, bins, g, h, c, T)
    assert int(n_active) == active.sum()
    bins_c, gc, hc, cc, leaf_c, wide_c = tier_front(
        *streamed, n_active, T, narrow.shape[0],
        wide=None if wide is None else (wide.dtype, wide.shape[0]))
    k = len(nz)
    np.testing.assert_array_equal(np.asarray(bins_c)[:, :k], narrow[:, nz])
    for got, src in ((gc, g), (hc, h), (cc, c)):
        assert got.dtype == jnp.float32 and got.shape == (T,)
        np.testing.assert_array_equal(_bits(got)[:k], _bits(src[nz]))
        assert not np.asarray(got)[k:].any()    # the tail: zeros, finite
    np.testing.assert_array_equal(np.asarray(leaf_c)[:k], leaf[nz])
    assert (np.asarray(leaf_c)[k:] == -2).all()
    if wide is None:
        assert wide_c is None
    else:
        assert wide_c.dtype == wide.dtype
        np.testing.assert_array_equal(np.asarray(wide_c)[:, :k], wide[:, nz])
    # the same rows, in the same order, as the index the tiers gathered by
    old = _old_mask(leaf, PEND, weighted)
    np.testing.assert_array_equal(old, active)
    np.testing.assert_array_equal(nz, _old_index(old, T)[:k])
    # the packed words are the mask, and the starts its running count
    words, start, _ = pack_active_rows(jnp.asarray(active))
    bits = np.unpackbits(np.asarray(words).view(np.uint8),
                         bitorder="little")
    np.testing.assert_array_equal(bits[:n].astype(bool), active)
    assert not bits[n:].any()
    np.testing.assert_array_equal(
        np.asarray(start),
        np.concatenate([[0], np.cumsum(bits.reshape(-1, 32).sum(1))[:-1]]))


def test_the_next_tier_takes_one_row_more():
    """``n_active == T`` fits tier T whole; one more row and every column
    of the T-sized tier is a real row with one left over, which is why the
    ladder picks the smallest tier ``>= n_active``."""
    for case, left_over in (("n_active_is_T", 0), ("n_active_is_T_plus_1", 1)):
        leaf, weighted, T = _rows(case)
        ones = np.ones(len(leaf), np.float32)
        bins = (np.arange(len(leaf), dtype=np.uint8)[None], None)
        n, streamed = _streamed(leaf, weighted, bins, ones, ones, ones, T)
        leaf_c = np.asarray(tier_front(*streamed, n, T, 1)[4])
        assert int(n) - T == left_over
        assert np.isin(leaf_c, PEND[PEND >= 0]).all()


@pytest.mark.parametrize("dtype", ["float32", "int32", "uint16", "uint8"])
def test_word_rows_round_trip(dtype):
    """Whatever the bits mean (negative zero, denormals, NaN payloads,
    negative leaf ids), rows of 32-bit words carry them and give them
    back: 4 / itemsize rows a word, zero rows where they do not fill the
    last one."""
    rng = np.random.default_rng(1)
    k = np.dtype(dtype).itemsize
    raw = rng.integers(0, 256, (3, 50, k), dtype=np.uint8)
    x = raw.view(dtype)[..., 0].copy()
    if dtype == "float32":
        x[:, :len(ODD_FLOATS)] = ODD_FLOATS
    words = word_rows(jnp.asarray(x))
    assert words.dtype == jnp.int32
    assert words.shape == (-(-3 * k // 4), 50)
    back = np.asarray(rows_value(words, dtype, 3))
    assert back.dtype == np.dtype(dtype) and back.shape == x.shape
    np.testing.assert_array_equal(back.view(np.uint8), x.view(np.uint8))
    # what the waves stream: the vectors' bits as they are, leaf_id's row
    # left for the kernel, whole tiles of 8 rows
    if dtype == "float32":
        rows = np.asarray(row_words(*jnp.asarray(x)))
        assert rows.shape == (8, 50) and not rows[3:].any()
        np.testing.assert_array_equal(rows[:3], x.view(np.int32))


@pytest.mark.parametrize("share", ["none", "one_row", 0.005, 0.3, 1.0])
def test_compress_lanes_is_flatnonzero(share):
    """The network alone, as a jitted function on ``[S, 128]`` mask lines:
    lane ``j`` of a line names the lane of its j-th active row, for every
    ``j`` below the line's count, and every lane names some lane (the
    placement gathers by all 128)."""
    rng = np.random.default_rng(11)
    S = 256
    if share == "none":
        m = np.zeros((S, 128), bool)
    elif share == "one_row":
        m = np.zeros((S, 128), bool)
        m[np.arange(S), rng.integers(0, 128, S)] = True
        m[0, :] = False
        m[1, 0] = m[2, 127] = True
    else:
        m = rng.random((S, 128)) < share
    src = np.asarray(jax.jit(compress_lanes)(jnp.asarray(m, jnp.bfloat16)))
    assert src.dtype == np.int32 and src.shape == (S, 128)
    assert src.min() >= 0 and src.max() < 128
    for line, got in zip(m, src):
        nz = np.flatnonzero(line)
        np.testing.assert_array_equal(got[:len(nz)], nz)


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
    """What the changes exist for, and what a refactor would lose in
    silence: with N rows on the chip, no gather or scatter in the growth
    program takes an index a row, of the chip or of a tier; nothing sorts
    N keys; the tiers below the full one share ONE streamed pass, whose
    results they slice; and the row vectors become word rows once a tree,
    outside the loop.  (The streamed kernel permutes lanes by a gather of
    its own, 128 lanes of a register at a time: it is inside the
    ``pallas_call``, which this walk does not enter, and is no XLA gather
    with an index a row.)"""
    meta, scfg, B, kw, args = _problem(kind)
    grow = build_wave_grow_fn(meta, scfg, B, **kw)
    eqns = list(_eqns(jax.make_jaxpr(grow)(*args).jaxpr))
    tiers = tier_ladder(ROWS, kw["plan"].block_rows)
    assert len(tiers) > 4
    indexed = [e for e, _ in eqns if e.primitive.name == "gather"
               or e.primitive.name.startswith("scatter")]
    assert indexed
    for e in indexed:
        assert e.invars[1].aval.shape[0] < min(tiers), e    # [L]-sized
        if e.primitive.name == "gather":                # nothing by row
            assert ROWS not in e.invars[0].aval.shape, e
    assert not [e for e, _ in eqns if e.primitive.name == "sort"
                and max(_size(v) for v in e.invars) >= min(tiers)]
    # three kinds of kernel: the histogram's, once a tier; the streamed
    # pass, once for all the tiers below the full one, under the cond that
    # skips it at the full tier; and the split phase's one pass over the
    # rows (``leaf_id`` as lines of 128), under the cond that skips a
    # phase that committed nothing
    kernels = [(e, inside) for e, inside in eqns
               if e.primitive.name == "pallas_call"]
    streams = [(e, inside) for e, inside in kernels
               if [v.aval.dtype for v in e.outvars] == [jnp.int32, jnp.uint8]]
    routes = [inside for e, inside in kernels
              if [v.aval.dtype for v in e.outvars] == [jnp.int32]]
    assert len(kernels) == len(tiers) + 2
    assert len(streams) == len(routes) == 1
    stream, inside = streams[0]
    assert inside.count("cond") == 2 and "while" in inside
    # nothing converts the pass's outputs after it (until PR 37 an
    # ``s8 -> u8`` copy of both followed the kernel)
    assert "bitcast_convert_type" not in {
        u.primitive.name for u, _ in eqns
        if any(v in stream.outvars for v in u.invars)}
    assert routes[0].count("cond") == 1 and "while" in routes[0]
    # every tier below the full one slices the pass's output to its size
    sliced = {e.outvars[0].aval.shape[-1] for e, _ in eqns
              if e.primitive.name == "slice"
              and e.outvars[0].aval.dtype == jnp.uint8}
    assert sliced >= set(tiers[1:])
    words = [inside for e, inside in eqns
             if e.primitive.name == "concatenate"
             and e.outvars[0].aval.shape == (8, ROWS)
             and e.outvars[0].aval.dtype == jnp.int32]
    assert words == [()]                # once, under no loop or branch


class _OldBuild:
    """A tier's inputs as they were made until PR 31, in NumPy on the
    host: the index by ``cumsum`` and scatter, the bins gathered by it out
    of the row-major twin and transposed back, the vectors out of ``[N,
    3]``, ``leaf_id`` by the same index, the tail repeating row 0 under
    leaf -2.  ``stream`` stands in for ``stream_rows`` (it keeps what the
    tiers will gather from), ``front`` for ``tier_front``."""

    @staticmethod
    def stream(bins_fm, words, leaf_id, active, start, n_active, cap,
               interpret=False):
        assert words.shape[0] == 8              # no wide columns here
        return bins_fm, (words[:3], leaf_id, active)

    @staticmethod
    def front(bins_fm, rows, n_active, T, F, wide=None):
        def host(bins_fm, vec_words, leaf_id, active):
            idx = _old_index(active, T)
            vecs3 = np.ascontiguousarray(vec_words.T).view(np.float32)
            vc = vecs3[idx]
            leaf_c = np.where(np.arange(T) < active.sum(), leaf_id[idx], -2)
            return (np.ascontiguousarray(bins_fm.T)[idx].T, vc[:, 0],
                    vc[:, 1], vc[:, 2], leaf_c.astype(np.int32))
        f32 = jax.ShapeDtypeStruct((T,), jnp.float32)
        out = jax.pure_callback(
            host, (jax.ShapeDtypeStruct((F, T), jnp.uint8), f32, f32, f32,
                   jax.ShapeDtypeStruct((T,), jnp.int32)), bins_fm, *rows)
        return (*out, None)


def _grow(kind, monkeypatch, old):
    if old:
        monkeypatch.setattr(wave_grower, "stream_rows", _OldBuild.stream)
        monkeypatch.setattr(wave_grower, "tier_front", _OldBuild.front)
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
    # the differential means something: waves below the full tier were
    # filled by the streamed pass, on every chip
    chips = 4 if kind == "data4" else 1
    assert len(counts["compact_waves"]) == chips
    assert min(counts["compact_waves"]) > 2
    assert counts["stream_waves"] == counts["compact_waves"]
    for built, kern in zip(counts["compact_waves"], counts["kernel_rows"]):
        assert kern < counts["waves"] * (ROWS // chips)
        assert built <= counts["waves"]
