"""Kernels of the main path compiled for the chip, without one.

The TPU's compiler is installed here and compiles for a device that is
described, not attached (the on-chip-measurement guide, section 2).  It
refuses what the Pallas interpreter accepts: the streamed compaction's first
builds passed every interpreted test and were refused here for a compare of
bf16 vectors, for an SMEM block that is not XLA's tile of 1,024 ``i32``, and,
wider than ~200 columns, for more VMEM than a kernel's scoped limit; since
PR 37 its placement rests on what only Mosaic can refuse (``pltpu.bitcast``
of whole ``u8`` tiles to words and back, a lane gather's operand shapes, a
matmul over a grid step shrunk to 16 lines).  Nothing runs: a compile that
passes says nothing about results or times.

The topology is described inside a fixture, by the one worker that is given
this file; every test here uses it, and no other file may.
"""
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.pallas_compact import stream_rows


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# rows x columns x word rows beside the bins: the benchmark's four resident
# shapes, a payload of two tiles of word rows, a table wide enough that the
# blocks must shrink to stay in VMEM, a small one, and expo-cat's mixed
# layout (six narrow columns; the two u16 columns ride as one word row)
SHAPES = [(10_500_000, 28, 8), (11_000_000, 16, 8), (3_771_125, 136, 8),
          (7_000_000, 28, 8), (100_000, 4, 16), (20_000, 1000, 8),
          (5_000, 6, 8), (11_000_000, 6, 8)]


@pytest.mark.parametrize("rows,cols,words", SHAPES)
def test_streamed_compaction_compiles_for_the_v5e(one_chip, rows, cols,
                                                  words):
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    cap = max(1024, -(-(rows * 2 // 3) // 1024) * 1024)
    compiled = jax.jit(
        lambda b, p, l, a, s, n: stream_rows(b, p, l, a, s, n, cap)).lower(
        arg((cols, rows), jnp.uint8), arg((words, rows), jnp.int32),
        arg((rows,), jnp.int32), arg((rows,), jnp.bool_),
        arg((-(-rows // 128) * 4,), jnp.int32), arg((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# rows a chip x columns x sibling fusion x bin lanes -> (feat_block,
# feat_pack): the four resident shapes of the benchmark's 256-lane cells
# (higgs, mslr: fused, feat_block 8; a chip of the mesh, expo's 16 bundled
# columns: unfused, a block of 32 cut to the columns there are) and
# higgs-bin63's: fused at 64 lanes, two features an MXU pass, the 28 columns
# one block, 14 steps rolled in groups
HIST_SHAPES = [(10_500_000, 28, True, 256, (8, 1)),
               (3_771_125, 136, True, 256, (8, 1)),
               (7_000_000, 28, False, 256, (28, 1)),
               (11_000_000, 16, False, 256, (16, 1)),
               (10_500_000, 28, True, 64, (28, 2))]


@pytest.mark.parametrize("rows,cols,fused,B,block", HIST_SHAPES)
def test_wave_histogram_compiles_for_the_v5e(one_chip, rows, cols, fused, B,
                                             block):
    """The packed kernel in the benchmark's mode at the block shape the
    plan gives the trainer (``core/plan.py GrowthPlan.kernel``): what Mosaic may
    refuse and the interpreter does not (the pass count as a prefetched
    scalar, a whole step under ``pl.when``, the lane rotate of a pass's
    result, the rolled feature loop over the bins' i32 copy, two features'
    one-hot factors in one operand at 64 lanes, the scoped VMEM limit)."""
    from lightgbm_tpu.core.plan import GrowthPlan
    from lightgbm_tpu.ops.pallas_hist import C_MAX, hist_pallas_wave
    mode, block_rows = "2xbf16", 1024
    shape = GrowthPlan(hist_mode=mode, packed=True, fused_sibling=fused,
                       block_rows=block_rows).kernel(B, cols)
    assert (shape.feat_block, shape.feat_pack) == block
    fb = shape.feat_block

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def launch(bins, g, h, c, leaf, slot, par):
        return hist_pallas_wave(
            bins, g, h, c, leaf, slot, B=B, block_rows=block_rows,
            feat_block=fb, highest=mode, packed=True,
            parent=(par, par) if fused else None)
    vec = arg((rows,), jnp.float32)
    compiled = jax.jit(launch).lower(
        arg((cols, rows), jnp.uint8), vec, vec, vec,
        arg((rows,), jnp.int32), arg((C_MAX,), jnp.int32),
        arg((cols, B, C_MAX), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# rows a chip x physical columns x view dtype x bundled x bitset words: the
# four resident shapes of the benchmark's cells and expo-cat's mixed-width
# view (six narrow columns and two of 291 / 292 bins in one u16 view)
ROUTE_SHAPES = [(10_500_000, 28, "uint8", False, 0),
                (3_771_125, 136, "uint8", False, 0),
                (7_000_000, 28, "uint8", False, 0),
                (11_000_000, 16, "uint8", True, 0),
                (11_000_000, 8, "uint16", False, 16)]


@pytest.mark.parametrize("rows,cols,dtype,bundled,words", ROUTE_SHAPES)
def test_split_routing_compiles_for_the_v5e(one_chip, rows, cols, dtype,
                                            bundled, words):
    """The split phase's one pass over the rows (``ops/pallas_route.py``):
    what Mosaic may refuse and the interpreter does not (a dynamic trip
    count around a double-buffered manual DMA out of ``pl.ANY``, the
    ``u8`` / ``u16`` widening, ``split_decision``'s booleans, a bitcast of
    the bitset's words, a branch a kind of split)."""
    from lightgbm_tpu.ops.pallas_route import column_view, route_rows

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    view = jax.eval_shape(column_view, arg((cols, rows), dtype))
    names = ["phys", "leaf", "new", "threshold", "default_left", "missing",
             "num_bins", "default_bins"]
    names += ["feat_offset"] * bundled + ["is_cat"] * bool(words)
    compiled = jax.jit(
        lambda lid, v, n, slots, cb: route_rows(lid, v, n, slots, cb,
                                                bundled=bundled)).lower(
        arg((rows,), jnp.int32), arg(view.shape, view.dtype),
        arg((), jnp.int32), {k: arg((63,), jnp.int32) for k in names},
        arg((63, words), jnp.uint32) if words else None).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_goss_sampler_compiles_for_the_v5e(one_chip):
    """The one jitted sampler of ``boosting/goss.py`` at the published HIGGS
    size: one program, every instruction of it under ``lgbm/goss_sample``
    (what ``sampler.goss_ms_per_iter`` reads), the threshold by a sort
    (``lax.top_k`` at k = 2.1M), and scratch that fits beside the trainer's
    7.4 GB."""
    from lightgbm_tpu.boosting.goss import build_sampler
    n = 10_500_000

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = build_sampler(n, n // 5, n // 10).lower(
        arg((n, 1), jnp.float32), arg((n, 1), jnp.float32),
        arg((2,), jnp.uint32), arg((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "lgbm/goss_sample" in text and "sort" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30
    # g', h', the mask and three scalars come back; nothing else
    assert mem.output_size_in_bytes < 3 * 4 * n + (1 << 20)


def _score_update(rows, leaves, one_chip):
    """``grow_apply``'s last statement, compiled alone for the chip: the
    score as ``f32[N, 1]``, the shrunk leaf values, the grower's ``leaf_id``."""
    from lightgbm_tpu.core.predict import leaf_value_lookup

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def update(score, lv, leaf_id):
        with jax.named_scope("lgbm/score_update"):
            return score.at[:, 0].add(leaf_value_lookup(lv, leaf_id))
    return jax.jit(update).lower(
        arg((rows, 1), jnp.float32), arg((leaves,), jnp.float32),
        arg((rows,), jnp.int32)).compile()


@pytest.mark.parametrize("rows", [10_500_000, 3_771_125])
def test_score_update_holds_no_gather_on_the_v5e(one_chip, rows):
    """At the cells' 255 leaves the look-up is selects, fused with the add
    under the scope the layer's metric reads: no ``gather`` (8 ns a row on
    the chip: PERF.md 6, PR 39), and no array of the rows' size beside the
    score (the leaf values as scalars take a few MB of staging)."""
    compiled = _score_update(rows, 255, one_chip)
    text = compiled.as_text()
    assert " gather(" not in text
    assert "lgbm/score_update" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * rows


def test_score_update_keeps_the_gather_above_its_constant(one_chip):
    """One leaf more than ``DENSE_LOOKUP_MAX_LEAVES`` and the gather is
    back: the selects' program grows with the leaves, the gather's does
    not, and the static shape decides."""
    from lightgbm_tpu.core.predict import DENSE_LOOKUP_MAX_LEAVES
    compiled = _score_update(3_771_125, DENSE_LOOKUP_MAX_LEAVES + 1, one_chip)
    assert " gather(" in compiled.as_text()


@pytest.mark.parametrize("airports", [40, 300], ids=["narrow", "wide"])
def test_categorical_growth_program_compiles_for_the_v5e(one_chip, airports):
    """The growth program of a training set with declared categorical
    columns, at a small size: the sorted-set search (``argsort``, gathers,
    ``cumsum``, the ``min_data_per_group`` scan) and the bitset walks inside
    the growth loop, under the plan ``select_path`` gives (a column of 300
    values is wider than the kernel's 256 bins and takes the mixed-width
    plan, as the benchmark's ``expo-cat`` does).  A change that stops either
    compiling for the chip fails here, on the CPU."""
    import dataclasses

    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.core import plan as plan_mod
    from lightgbm_tpu.core.meta import (SplitConfig, build_device_meta,
                                        padded_phys_width)
    from lightgbm_tpu.core.wave_grower import build_wave_grow_fn

    rows = 20_000
    rng = np.random.default_rng(0)
    X = np.column_stack([rng.integers(0, 12, rows),
                         rng.integers(0, airports, rows),
                         rng.normal(size=(rows, 2))])
    y = (rng.random(rows) < 0.5).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 63, "verbose": -1,
              "categorical_feature": [0, 1]}
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    h = ds._handle
    cfg = Config.from_params(params)
    meta, B = build_device_meta(h, cfg)
    bins = [int(b) for b in h.feature_max_bins()]
    plan = plan_mod.select_path(cfg, plan_mod.Facts(
        backend="tpu", num_features=4, num_phys_features=4,
        bin_dtype=h.X_bin.dtype.name, B_phys=padded_phys_width(h),
        phys_bins=tuple(bins)))
    wide = airports > 256
    assert plan.wave and (plan.mixed is not None) == wide
    assert plan.fused_sibling == plan.packed == (not wide)
    grow = build_wave_grow_fn(meta, SplitConfig.from_config(cfg), B,
                              dataclasses.replace(plan, counts=True),
                              B_phys=padded_phys_width(h))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    vec = arg((rows,), jnp.float32)
    bins_fm = ((arg((3, rows), jnp.uint8), arg((1, rows), jnp.uint16))
               if wide else arg((4, rows), jnp.uint8))
    compiled = jax.jit(grow).lower(bins_fm, vec, vec, vec,
                                   arg((4,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "lgbm/cat_scan" in text
    assert ("lgbm/hist_wave_xla" in text) == wide
