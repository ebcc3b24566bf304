"""Wave-scheduled leaf-wise tree growth — the TPU-native fast path.

The reference pays one histogram pass over the smaller child's rows per
split (reference: serial_tree_learner.cpp:496-522); its cost model is
gather-friendly CPU caches. On TPU a data pass costs the same for 1 or 42
leaf masks (the MXU processes 128 output lanes regardless — see
ops/pallas_hist.py), so growth is re-scheduled into waves:

  split phase: best-first split every histogram-ready leaf with positive
      gain (up to the wave capacity), exactly like the reference's loop;
  wave phase:  ONE kernel pass computes the smaller child's histogram for
      every split just made (up to 63 leaves per launch, in MXU passes
      of 25; see ops/pallas_hist.py) AND, fused in the same
      launch, each sibling by parent-minus-child subtraction; children's
      best splits are then scanned with a vmap.

With capacity 1 this is exactly the reference's leaf-wise order; with
capacity 63 a 255-leaf tree needs ~6-10 data passes instead of 254.  The
split ORDER can deviate from strict global best-first (a pending child's
gain is unknown until its wave), which matches the spirit of the
reference's voting/feature-parallel approximations and is measurably
accuracy-neutral; exactness is recovered with wave_capacity=1.

Bins are feature-major [F, N] here (see ops/pallas_hist.py layout note).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from ..ops.pallas_compact import row_words, stream_rows, tier_front
from ..ops.pallas_route import column_view, route_rows
from ..ops.pallas_hist import (C_MAX, QUANT_MODES, QUANT_QMAX, _resolve_mode,
                               gather_lanes, hist_pallas_wave, pack_lanes,
                               wave_mxu_passes, stochastic_round)
from .grower import TreeArrays, _empty_tree, decode_feature_col
from .histogram import expand_bundled, fix_default_bins, hist_wave_xla
from .meta import DeviceMeta, SplitConfig, pad_meta
from .plan import GrowthPlan, MixedCols
from .splitter import (best_split, bitset_words, has_categorical,
                       leaf_output, split_decision)

NEG_INF = -jnp.inf


class WaveSplits(NamedTuple):
    """One split phase's committed splits, slot-per-entry — the batched
    form of ``_split_once``'s per-split partition arguments.  ``ok`` rows
    with False are empty slots (phase committed fewer than P splits); the
    committed slots are a PREFIX (once ``_pick_split`` fails it fails for
    the rest of the phase), which ``build_split_apply_fn`` relies on."""
    ok: jnp.ndarray            # bool [P] slot committed a split
    leaf: jnp.ndarray          # i32 [P] split leaf (left child keeps id)
    new: jnp.ndarray           # i32 [P] right child's new leaf id
    feature: jnp.ndarray       # i32 [P] inner feature index
    threshold: jnp.ndarray     # i32 [P] bin-space threshold
    default_left: jnp.ndarray  # bool [P]
    cat_bitset: jnp.ndarray    # u32 [P, W] left-going bin set


class MixedWidth(NamedTuple):
    """Static physical-column partition for the mixed-width wave path.

    The Pallas kernel's VMEM one-hot layout tops out at 256 bins per
    feature; a dataset with even one wider column (a high-cardinality
    categorical, say) used to fall off the wave path entirely.  Instead
    the narrow columns stay on the kernel and the wide ones take the XLA
    side-pass (histogram.hist_wave_xla), merged before the split scan.

    narrow_idx / wide_idx: np.int32 physical-column indices;
    B_narrow: padded bin width of the narrow group (<= 256)."""
    narrow_idx: np.ndarray
    wide_idx: np.ndarray
    B_narrow: int

    @classmethod
    def of(cls, cols: MixedCols) -> "MixedWidth":
        """The plan's hashable ``MixedCols`` as index arrays (None as None)."""
        if cols is None:
            return None
        return cls(np.asarray(cols.narrow, np.int32),
                   np.asarray(cols.wide, np.int32), int(cols.B_narrow))

    def place(self) -> np.ndarray:
        """Physical column -> its row in ``concat(narrow, wide)``."""
        return np.argsort(np.concatenate(
            [self.narrow_idx, self.wide_idx])).astype(np.int32)


def build_split_route_fn(meta: DeviceMeta, bundled: bool = False,
                         mixed: MixedWidth = None):
    """``route(leaf_id, bins_fm, leaf, new, f, t, dl, cb) -> leaf_id``: ONE
    committed split as one dense XLA walk of the rows — the reference
    routing: what the sequential oracle (``_split_once``, the plan's
    ``batched_apply=False``) walks with and what the tests hold the batched
    phase's one pass (``build_split_apply_fn``) to.  It reads physical column
    ``feat2phys[f]`` as one contiguous ``[N]`` row of the FEATURE-major
    bins (the ``(narrow, wide)`` pair under ``mixed``), decodes it under
    ``bundled`` (EFB), decides on the split's SCALAR threshold, default
    direction, missing type and categorical bitset (``split_decision``),
    and sends the rows of ``leaf`` that go right to ``new``.  Everything
    per row is elementwise: a per-element gather costs 3-4 ns on the
    chip, this walk 0.066 ns a row (ledger, PR 34), so the bitset word
    of a row's bin is picked by ``W`` dense selects, not by a gather."""
    if mixed is not None:
        n_phys = len(mixed.narrow_idx) + len(mixed.wide_idx)
        _pos = np.zeros(n_phys, np.int32)
        _pos[mixed.narrow_idx] = np.arange(len(mixed.narrow_idx))
        _pos[mixed.wide_idx] = np.arange(len(mixed.wide_idx))
        _isw = np.zeros(n_phys, bool)
        _isw[mixed.wide_idx] = True
        pos_c = jnp.asarray(_pos)
        is_wide_c = jnp.asarray(_isw)

    def route(leaf_id, bins_fm, leaf, new, f, t, dl, cb):
        p = meta.feat2phys[f] if bundled else f
        if mixed is None:
            col = bins_fm[p].astype(jnp.int32)
        else:
            bins_n, bins_w = bins_fm
            pos = pos_c[p]
            coln = bins_n[jnp.minimum(pos, bins_n.shape[0] - 1)]
            colw = bins_w[jnp.minimum(pos, bins_w.shape[0] - 1)]
            col = jnp.where(is_wide_c[p], colw.astype(jnp.int32),
                            coln.astype(jnp.int32))
        if bundled:
            col = decode_feature_col(col, f, meta)
        W = cb.shape[0]
        word = jax.lax.select_n(
            jnp.clip(col // 32, 0, W - 1),
            *(jnp.broadcast_to(cb[w], col.shape) for w in range(W)))
        go_left = split_decision(col, t, dl, meta.is_categorical[f], word,
                                 meta.missing_types[f], meta.num_bins[f],
                                 meta.default_bins[f])
        return jnp.where((leaf_id == leaf) & ~go_left, new, leaf_id)

    return route


def route_view(bins_fm, mixed: MixedWidth = None):
    """The bins as ``build_split_apply_fn``'s pass reads them, built once a
    tree (``ops/pallas_route.py column_view``): ``[F_phys, G, 128]``, a
    column's rows contiguous; under ``mixed`` ONE view of the ``(narrow,
    wide)`` pair in the wider dtype, its rows in physical order."""
    with jax.named_scope("lgbm/wave_partition"):
        return column_view(bins_fm,
                           mixed.place() if mixed is not None else None)


def build_split_apply_fn(meta: DeviceMeta, bundled: bool = False,
                         interpret: bool = False):
    """A split phase's committed splits applied to the row partition.

    Returns ``apply(leaf_id, view, ws: WaveSplits) -> (leaf_id, walks,
    passes)``: ONE streamed pass over the rows the chip holds
    (``ops/pallas_route.py route_rows``, under ``lgbm/wave_partition``)
    that applies the COMMITTED slots (``ws.ok`` is a prefix), each reading
    its one physical column off ``view`` (``route_view`` of the bins) and
    deciding as ``build_split_route_fn`` does, on the split's scalars
    looked up here by its feature.  ``walks`` (i32) is the slots routed,
    ``passes`` the passes made: a phase that committed nothing (the first
    loop body of every tree) makes none.  The order of the slots is
    immaterial: a leaf splits at most once a phase and a phase never
    targets a child it created (``hist_ready`` is cleared on commit).  The
    sequential oracle (``_split_once``) walks the rows right after each
    commit instead; what ``batched_apply`` selects is how the [L]-sized
    metadata is committed and which of the two routes the rows.

    Until PR 27 this was one pass of eleven per-row gathers over every row
    in every body (39.8 ns a row a body on the v5e; ledger, PR 24); until
    PR 35 one XLA walk a committed split, 0.066 ns a walked row, 254 a tree
    (ledger, PR 34).  ``interpret`` runs the kernel interpreted (CPU).
    """
    has_cat = has_categorical(meta)

    @jax.named_scope("lgbm/wave_partition")
    def apply(leaf_id, view, ws: WaveSplits):
        f = ws.feature
        slots = dict(
            phys=meta.feat2phys[f] if bundled else f, leaf=ws.leaf,
            new=ws.new, threshold=ws.threshold, default_left=ws.default_left,
            missing=meta.missing_types[f], num_bins=meta.num_bins[f],
            default_bins=meta.default_bins[f])
        if bundled:
            slots["feat_offset"] = meta.feat_offset[f]
        if has_cat:
            slots["is_cat"] = meta.is_categorical[f]
        n = jnp.sum(ws.ok.astype(jnp.int32))
        leaf_id = jax.lax.cond(
            n > 0,
            lambda lid: route_rows(
                lid, view, n, slots, ws.cat_bitset if has_cat else None,
                bundled=bundled, interpret=interpret),
            lambda lid: lid, leaf_id)
        return leaf_id, n, (n > 0).astype(jnp.int32)

    return apply


_WIDE_BITS = 24


def _wide_add(c, x):
    """Add i32 ``x`` (0 <= x < 2**31 - 2**24) to the two-word counter ``c``
    (i32 [2]: high word, low 24 bits).  Row counts outgrow both f32's 24
    bits (28M rows x 16 bodies) and an i32 (10.5M rows x 255 capacity-1
    waves); the pair is exact to 2**55."""
    lo = c[1] + x
    return jnp.stack([c[0] + (lo >> _WIDE_BITS),
                      lo & ((1 << _WIDE_BITS) - 1)])


_WORD = 32      # rows a word of the packed active-row mask


def tier_ladder(N: int, block_rows: int) -> list:
    """The kernel sizes a wave may take: N, N/1.5, N/1.5^2, ...
    (``block_rows``-aligned below N, down to one block).  A wave runs at the
    smallest that holds its active rows."""
    tiers, t = [], N
    while True:
        tiers.append(t)
        nt = max(block_rows, ((t * 2 // 3 + block_rows - 1)
                              // block_rows) * block_rows)
        if nt >= t:
            return tiers
        t = nt


def active_rows(leaf_id, pend_small, weighted):
    """bool [N]: a row is active where its leaf is one of ``pend_small``
    (i32 [P]; empty slots are -1 and match no row: leaf ids are never
    negative) and it is ``weighted`` (bool [N], any N)."""
    return weighted & jnp.any(pend_small[:, None] == leaf_id[None, :],
                              axis=0)


def pack_active_rows(active):
    """The wave's active rows (``active_rows``: bool [N]) counted, as
    streaming passes: nothing in it gathers, scatters or sorts an element a
    row.  Returns ``(words, start, n_active)``: ``words`` u32 [G], bit b of
    word w set where row ``32 w + b`` is active (rows past N are not);
    ``start`` i32 [G], the active rows before word w; ``n_active`` i32,
    which picks the wave's tier.  What is still read: ``n_active``, and of
    ``start`` every fourth entry, where a sub-block of 128 rows starts in
    the tier (the streamed compaction, ``ops/pallas_compact.py``, places it
    there; ``placed_sub_blocks`` counts those that hold a row).  Nothing
    reads ``words`` (the 32-row packing served PR 29's index build; taking
    it out is queued, ROADMAP S2)."""
    N = active.shape[0]
    G4 = -(-N // 128)
    # 128 rows a line: the reshape is the rows' own tiling, the reduce runs
    # along lanes, and each quarter of a line packs into one word
    lane = jnp.arange(128, dtype=jnp.uint32)
    bits = (jnp.pad(active, (0, G4 * 128 - N)).reshape(G4, 128)
            .astype(jnp.uint32) << (lane % _WORD))
    words = jnp.stack(
        [jnp.sum(jnp.where(lane // _WORD == q, bits, 0), axis=1)
         for q in range(128 // _WORD)], axis=1).reshape(-1)
    cnt = jax.lax.population_count(words).astype(jnp.int32)
    end = jnp.cumsum(cnt)
    return words, end - cnt, end[-1]


def placed_sub_blocks(start, n_active):
    """i32: the sub-blocks of 128 rows that hold an active row, off
    ``pack_active_rows``' running count (one entry a sub-block: no pass
    over the rows)."""
    sub = start.reshape(-1, 128 // _WORD)[:, 0]
    end = jnp.concatenate([sub[1:], jnp.reshape(n_active, (1,))])
    return jnp.sum((end > sub).astype(jnp.int32))


class WaveCounts(NamedTuple):
    """What growing one tree cost, counted by the growth loop itself with
    scalar arithmetic on state it carries anyway (no pass over the rows).
    ``*_rows`` are ``_wide_add`` pairs; the rest i32 scalars."""
    bodies: jnp.ndarray       # trips of the growth loop: each commits one
    #   phase of splits and pays at most one launch
    waves: jnp.ndarray        # kernel launches
    lanes: jnp.ndarray        # pending leaves the launches histogrammed, of
    #   the effective wave capacity a launch: the tree's num_leaves
    walks: jnp.ndarray        # committed splits routed, each by one bin
    #   column read over every row the chip holds (num_leaves - 1 a tree),
    #   counted where the rows are routed
    route_passes: jnp.ndarray  # passes over every row the chip holds that
    #   applied those splits: one a phase that committed any (the batched
    #   apply's streamed pass; the sequential oracle walks once a split)
    routed_rows: jnp.ndarray  # rows whose leaf split in the body (the sum
    #   of internal_count): what the partition pass had to move or keep
    kernel_rows: jnp.ndarray  # rows the launches covered (the tier's size);
    #   THIS chip's under a mesh
    kernel_pass_rows: jnp.ndarray  # the same, each launch's tier times the
    #   MXU passes it was charged (``ops/pallas_hist.py wave_mxu_passes``:
    #   by the leaves it held under the packed layout), THIS chip's
    active_rows: jnp.ndarray  # rows that carried weight into a launch, THIS
    #   chip's
    compact_waves: jnp.ndarray  # launches below the full tier: the waves
    #   that compacted their active rows to the front of a smaller tier,
    #   THIS chip's (a chip takes the tier its own active rows fit)
    stream_waves: jnp.ndarray  # of those, the launches whose tier was filled
    #   by the streamed pass (``ops/pallas_compact.py``): all of them
    stream_blocks: jnp.ndarray  # sub-blocks of 128 rows those passes
    #   streamed: ceil(rows THIS chip holds / 128) a compacting wave
    placed_blocks: jnp.ndarray  # of those, the sub-blocks that held an
    #   active row (``placed_sub_blocks``): how often placement had rows
    #   to place, THIS chip's
    cat_splits: jnp.ndarray = None  # committed splits whose feature is
    #   categorical (a bitset, not a threshold).  Only in the program of a
    #   training set that declares a categorical column (static, as
    #   ``best_split``'s ``has_cat``): every other program is what it was
    split_syncs: jnp.ndarray = None  # all-gathers of a wave's best splits
    #   over the chips (``ColumnShare.sync``): one a wave, the root's
    #   included.  Only in the feature-parallel learner's program (static)


class WaveStats(NamedTuple):
    """``WaveCounts`` as the grower returns them: ``shared`` i32 [7]
    (bodies, waves, lanes, walks, route_passes, routed_rows high and low
    word; an eighth, cat_splits, where the training set declares a
    categorical column; under the feature-parallel learner the eighth, 0
    where nothing is categorical, and a ninth, split_syncs)
    is the same on every chip of a mesh, ``per_chip`` i32 [chips, 10]
    (kernel_rows, active_rows and kernel_pass_rows, high and low word;
    compact_waves; stream_waves; stream_blocks; placed_blocks) has one row
    a chip.  Read with
    ``wave_counts``."""
    shared: jnp.ndarray
    per_chip: jnp.ndarray


def _pack_counts(c: WaveCounts) -> WaveStats:
    shared = [jnp.stack([c.bodies, c.waves, c.lanes, c.walks,
                         c.route_passes]), c.routed_rows]
    if c.cat_splits is not None:
        shared.append(c.cat_splits[None])
    elif c.split_syncs is not None:
        shared.append(jnp.zeros((1,), jnp.int32))   # the eighth word's place
    if c.split_syncs is not None:
        shared.append(c.split_syncs[None])
    return WaveStats(
        shared=jnp.concatenate(shared),
        per_chip=jnp.concatenate([c.kernel_rows, c.active_rows,
                                  c.kernel_pass_rows,
                                  c.compact_waves[None],
                                  c.stream_waves[None],
                                  c.stream_blocks[None],
                                  c.placed_blocks[None]])[None])


def wave_counts(stats: WaveStats) -> dict:
    """``WaveStats`` (device or host arrays; this fetches them, in one go)
    as exact Python ints, the per-chip counters as one list entry a chip.
    Exact wherever the counted quantities are: ``routed_rows`` sums the
    tree's own ``internal_count``, which comes off f32 histogram counts and
    is exact to 2**24 rows a leaf."""
    shared, chips = jax.device_get(tuple(stats))
    shared = [int(v) for v in np.reshape(shared, -1)]
    chips = np.reshape(chips, (-1, 10))

    def wide(hi, lo):
        return (int(hi) << _WIDE_BITS) + int(lo)
    return {"bodies": shared[0], "waves": shared[1], "lanes": shared[2],
            "walks": shared[3], "route_passes": shared[4],
            "routed_rows": wide(shared[5], shared[6]),
            # a program without the counter has no categorical column
            "cat_splits": shared[7] if len(shared) > 7 else 0,
            # only the feature-parallel learner's program syncs its splits
            **({"split_syncs": shared[8]} if len(shared) > 8 else {}),
            "kernel_rows": [wide(r[0], r[1]) for r in chips],
            "active_rows": [wide(r[2], r[3]) for r in chips],
            "kernel_pass_rows": [wide(r[4], r[5]) for r in chips],
            "compact_waves": [int(r[6]) for r in chips],
            "stream_waves": [int(r[7]) for r in chips],
            "stream_blocks": [int(r[8]) for r in chips],
            "placed_blocks": [int(r[9]) for r in chips]}


class ColumnShare(NamedTuple):
    """This chip's share of the columns under the feature-parallel learner
    (``parallel/mesh.py make_feature_parallel_wave_grower``): every chip
    holds every row and histograms a run of whole kernel feature blocks of
    ``feat_block`` columns, ``blocks`` of them in its launches (the fullest
    chip's run: the blocks past a chip's own come out zero and are masked
    from its scan).  ``window()`` is this chip's ``(first block, blocks
    owned)`` as i32 [2], traced inside ``shard_map``; ``sync(bs)`` takes a
    wave's ``BestSplit`` batch, global feature indices, and returns the
    best over the chips, candidate by candidate, a tie to the lowest chip
    (the lowest feature)."""
    blocks: int
    feat_block: int
    window: object
    sync: object


class _WaveState(NamedTuple):
    leaf_id: jnp.ndarray        # i32 [N]
    hist: jnp.ndarray           # f32 [L+1, F, B, 3] (slot L = scratch)
    leaf_g: jnp.ndarray         # f32 [L+1]
    leaf_h: jnp.ndarray
    leaf_c: jnp.ndarray
    leaf_depth: jnp.ndarray     # i32 [L+1]
    leaf_min_c: jnp.ndarray
    leaf_max_c: jnp.ndarray
    leaf_out: jnp.ndarray
    hist_ready: jnp.ndarray     # bool [L+1]
    best_gain: jnp.ndarray      # f32 [L+1]
    best_feat: jnp.ndarray
    best_thr: jnp.ndarray
    best_dl: jnp.ndarray
    best_lg: jnp.ndarray
    best_lh: jnp.ndarray
    best_lc: jnp.ndarray
    best_lout: jnp.ndarray      # f32 [L+1] winning split's left child output
    best_rout: jnp.ndarray      # f32 [L+1]
    best_cb: jnp.ndarray        # u32 [L+1, W] winning categorical bin set
    leaf_parent: jnp.ndarray
    leaf_is_right: jnp.ndarray
    pend_small: jnp.ndarray     # i32 [P] leaf ids (-1 empty)
    pend_large: jnp.ndarray     # i32 [P]
    pend_cnt: jnp.ndarray       # i32
    tree: TreeArrays
    cegb_coupled: jnp.ndarray = None  # f32 [F] CEGB pending coupled penalties
    counts: "WaveCounts" = None  # the tree's work counters (plan.counts)


def build_wave_grow_fn(meta: DeviceMeta, cfg: SplitConfig, B: int,
                       plan: GrowthPlan, B_phys: int = None, cegb=None,
                       reduce_fn=None, reduce_max_fn=None,
                       columns: ColumnShare = None):
    """Unjitted ``grow(bins_fm, g, h, sample_mask, feature_mask)`` using the
    Pallas wave kernel, built as ``plan`` says (``core/plan.py``: every field
    effective; a combination that cannot run is an ``AssertionError`` here,
    nothing is downgraded).  Returns (TreeArrays, leaf_id); with
    ``plan.counts`` a third output ``WaveStats`` carries the tree's
    ``WaveCounts`` (read them with ``wave_counts``): loop bodies, kernel
    launches and the leaf lanes they filled, rows the launches covered
    (tier-compaction aware) and rows that carried weight into them, the
    splits routed, the passes that routed them and the rows they moved or
    kept.  The loop counts
    them itself from [L]- and [P]-sized state, a few scalar adds a body,
    so the trainer keeps them on in the one program it runs
    (``Booster.work_counters``).

    ``plan.mixed`` set, ``bins_fm`` is a PAIR ``(narrow_u8 [Fn, N],
    wide [Fw, N])``: narrow physical columns ride the kernel at
    ``mixed.B_narrow`` bins while the wide ones take the XLA one-hot
    side-pass, merged into one ``[F_phys, B_phys, C]`` histogram before
    the split scan: one >256-bin feature does not evict the whole
    dataset from the fast path.

    ``reduce_fn`` (e.g. ``lambda x: jax.lax.psum(x, "data")``) makes the
    grower row-shard-aware for use under ``shard_map``: root statistics and
    every wave's kernel histograms are globally reduced, so all devices
    take identical split decisions while each histograms only its local
    rows (reference: data_parallel_tree_learner.cpp:119-164).

    ``columns`` (a ``ColumnShare``) makes it the feature-parallel learner's
    chip, also under ``shard_map``: every chip holds every row and the full
    bins, histograms its own run of feature blocks (the kernel reads them
    through its index map) into a histogram state of those columns only,
    scans them, and takes the wave's best splits from ``columns.sync``
    under ``lgbm/split_sync``; then every chip commits the same splits and
    routes its own copy of the rows (reference:
    feature_parallel_tree_learner.cpp:33-76).  The fused sibling, the
    quantised modes and ``feature_mask`` keep their one-chip arithmetic.

    ``plan.interpret`` runs the Pallas kernel in interpreter mode so the
    wave path is testable on CPU (the analog of the reference's
    GPU_DEBUG_COMPARE harness, gpu_tree_learner.cpp:1011-1043).

    ``plan.gain_gate`` throttles the deviation from strict best-first order:
    a split phase only commits leaves whose gain is at least ``gain_gate``
    times the phase's best ready gain, so low-gain leaves never displace
    higher-gain children still waiting for their wave.  0 disables the
    gate (split everything positive, max throughput); 1 is strict
    best-of-phase only.

    ``plan.batched_apply`` commits each split phase's [L]-sized bookkeeping
    in a ``lax.scan`` over the P slots, then applies the committed splits
    to ``leaf_id`` in one streamed pass over the rows
    (``build_split_apply_fn``); the commit order, and therefore the tree,
    is exactly the sequential path's.  ``False`` keeps ``_split_once``,
    which commits one split and walks the rows for it at once
    (``build_split_route_fn``): the differential-testing reference.

    ``plan.hist_mode`` is the histogram matmul precision: "highest" keeps
    f32 operands (exact, ~3 MXU passes); "2xbf16" (the engine default)
    splits g/h into hi+lo bf16 terms, ~16 mantissa bits with f32
    accumulation in 2 passes (the reference accumulates float even in
    single-precision GPU mode, gpu_tree_learner.h:80-84); "bf16" is one
    bf16 pass, g/h rounded to ~8 mantissa bits, which can flip near-tied
    split gains.  "int16" / "int8" turn on QUANTIZED accumulation
    (LightGBM 4.x quantized training): per-tree symmetric scales
    s_g = max|g| / QMAX (global maxima via ``reduce_max_fn`` under data
    parallelism, so every shard quantizes identically), g/h
    stochastic-rounded to integers (``stochastic_round``, value-based,
    seeded by ``plan.quant_seed``), exact integer accumulation in the
    kernel and an f32 dequant at the split scan.  The f32 modes stay the
    bit-exactness reference; the differential suite bounds the histogram
    deltas analytically (``quant_error_bound``).

    ``plan.packed`` uses the packed channel layout (ops/pallas_hist.py):
    63 leaves per kernel launch instead of 42, in MXU passes of 25 leaves
    that a launch pays by the leaves it holds (one pass for the root's,
    three for a full one).  Off under ``mixed`` (the XLA side-pass speaks
    the triple layout).  Histograms are bit-identical between layouts.

    ``plan.fused_sibling`` computes the parent-minus-child sibling
    histograms inside the SAME kernel launch (the parent blocks stream
    into VMEM and the siblings are written on the final row step) instead
    of a separate XLA subtraction pass.  One device, un-bundled, un-mixed
    only: under ``reduce_fn`` the subtraction must wait for the
    cross-device psum (the reference likewise subtracts after its
    histogram exchange, data_parallel_tree_learner.cpp:246), and under
    ``bundled`` it must follow default-bin reconstruction; both keep the
    XLA subtraction, which is bit-identical.

    The kernel's shape (``kernel_bins`` lanes a feature, ``feat_block``
    features a grid step, ``feat_pack`` features an MXU pass) is the plan's
    too: ``plan.kernel`` derives it for the width and the columns a launch
    sees, by the rule the kernel itself cuts, packs and pads by
    (``ops/pallas_hist.py wave_feature_blocks``).
    """
    plan.check(data_parallel=reduce_fn is not None)
    highest, interpret = plan.hist_mode, plan.interpret
    bundled, report_waves = plan.bundled, plan.counts
    packed, fused, P = plan.packed, plan.fused_sibling, plan.wave_capacity
    batched_apply, block_rows = plan.batched_apply, plan.block_rows
    quant_seed = plan.quant_seed
    mixed = MixedWidth.of(plan.mixed)
    L = cfg.num_leaves
    mode_r = _resolve_mode(highest)
    quant = mode_r in QUANT_MODES
    if quant:
        assert reduce_fn is None or reduce_max_fn is not None, \
            "data-parallel quantized growth needs reduce_max_fn so the " \
            "quantization scales are global"
        assert L + 2 < 32768, "quantized vecs carry leaf ids as int16"
    if B_phys is None:
        B_phys = B
    if cegb is not None and cegb.lazy is not None:
        raise ValueError("cegb_penalty_feature_lazy needs per-row state the "
                         "wave path does not carry; use the serial grower")
    assert not (report_waves and cegb is not None), \
        "report_waves and cegb both add a third output; pick one"
    split_pen = float(cegb.tradeoff * cegb.penalty_split) if cegb else 0.0
    has_cat = has_categorical(meta)
    F = int(meta.num_bins.shape[0])
    F_hist = F              # the columns of the histogram state
    if columns is not None:
        assert mixed is None and not bundled and cegb is None \
            and reduce_fn is None, "the feature-parallel wave grower " \
            "takes narrow, unbundled columns and holds every row"
        FB_c = columns.feat_block
        F_hist = columns.blocks * FB_c
        # the meta padded so that the fullest chip's run can start at any
        # block: the padding features have one bin and never split
        F_meta = (-(-F // FB_c) + columns.blocks) * FB_c
        meta_pad = pad_meta(meta, F_meta)

    def column_share(feature_mask):
        """This chip's first column, its meta and the mask of the columns
        it scans: its own, within the features, and sampled."""
        win = columns.window()
        col0 = win[0] * FB_c
        local = DeviceMeta(*[jax.lax.dynamic_slice_in_dim(a, col0, F_hist)
                             for a in meta_pad])
        c = jnp.arange(F_hist, dtype=jnp.int32)
        mask = (c < win[1] * FB_c) & (col0 + c < F)
        if feature_mask is not None:
            mask = mask & jax.lax.dynamic_slice_in_dim(
                jnp.pad(feature_mask, (0, F_meta - F)), col0, F_hist)
        return col0, local, mask
    # gain_gate > 1 would make _split_once never commit while loop_cond
    # stays true — an infinite while_loop on device
    gain_gate = min(max(float(plan.gain_gate), 0.0), 1.0)

    def _count(st: "_WaveState", **inc) -> "_WaveState":
        """``st`` with its work counters advanced (``WaveCounts`` field ->
        i32 increment); as it came where nothing is counted."""
        if not report_waves:
            return st
        c = st.counts
        return st._replace(counts=c._replace(**{
            k: (_wide_add(getattr(c, k), v) if k.endswith("_rows")
                else getattr(c, k) + v) for k, v in inc.items()}))

    if mixed is not None:
        Fn, Fw = len(mixed.narrow_idx), len(mixed.wide_idx)
        assert Fn > 0 and Fw > 0, "mixed needs both narrow and wide columns"
        inv_perm = jnp.asarray(mixed.place())
        B_kern = int(mixed.B_narrow)
    else:
        B_kern = B_phys

    _route = build_split_route_fn(meta, bundled=bundled, mixed=mixed)

    @jax.named_scope("lgbm/wave_hist")
    def _wave_hist(nb_fm, wide_rm, gvx, hvx, cvx, leafx, slot_leaf,
                   parent=None):
        """One wave's physical histograms: Pallas kernel over the narrow
        columns (+ XLA side-pass over the wide ones when mixed, merged
        back into physical order).  Returns the kernel's channel-layout
        result — [F, B, C] (triple), (gh, cnt) (packed), and with
        ``parent`` the (child, sibling) pair of either.  Quantized modes
        return INTEGER-unit sums; the split scan dequantizes."""
        if columns is not None:
            return hist_pallas_wave(
                nb_fm, gvx, hvx, cvx, leafx, slot_leaf, B=B_kern,
                block_rows=block_rows, feat_block=FB_c, highest=highest,
                interpret=interpret, packed=packed, parent=parent,
                col_blocks=columns.blocks, col_window=columns.window())
        hw = hist_pallas_wave(nb_fm, gvx, hvx, cvx, leafx, slot_leaf,
                              B=B_kern, block_rows=block_rows,
                              feat_block=plan.kernel(
                                  B_kern, nb_fm.shape[0]).feat_block,
                              highest=highest, interpret=interpret,
                              packed=packed, parent=parent)
        if mixed is None:
            return hw
        hw_w = hist_wave_xla(wide_rm, gvx, hvx, cvx, leafx, slot_leaf,
                             B=B_phys)
        if B_phys > B_kern:
            hw = jnp.pad(hw, ((0, 0), (0, B_phys - B_kern), (0, 0)))
        return jnp.concatenate([hw, hw_w], axis=0)[inv_perm]

    def _scan_leaf(hist_leaf, sg, sh, sc, min_c, max_c, depth, feature_mask,
                   cegb_coupled, scales, scan_meta=None):
        if quant:
            # f32 dequant at SPLIT-SCAN time — the one place the integer
            # sums are consumed as values.  Everything upstream (kernel
            # accumulation, fused/XLA sibling subtraction, psum under
            # data parallelism) stays in exact integer units, which is
            # what keeps the packed/triple/fused/unfused layouts
            # bit-identical under quantization.  Count channel scale 1.
            hist_leaf = hist_leaf * jnp.stack(
                [scales[0], scales[1], jnp.float32(1.0)])
        pen = (split_pen * sc + cegb_coupled) if cegb is not None else None
        if scan_meta is None:
            bs = best_split(hist_leaf, sg, sh, sc, meta, cfg, min_c, max_c,
                            feature_mask=feature_mask, penalty_sub=pen)
        else:
            bs = best_split(hist_leaf, sg, sh, sc, scan_meta, cfg, min_c,
                            max_c, feature_mask=feature_mask,
                            has_cat=has_cat)
        depth_ok = (cfg.max_depth <= 0) | (depth < cfg.max_depth)
        return bs._replace(gain=jnp.where(depth_ok, bs.gain, NEG_INF))

    # ---------------- split phase --------------------------------------
    def _pick_split(st: _WaveState, phase_max):
        """Best ready leaf this step + whether its split may commit."""
        gains = jnp.where(st.hist_ready[:L], st.best_gain[:L], NEG_INF)
        leaf = jnp.argmax(gains).astype(jnp.int32)
        ok = ((gains[leaf] > 0.0)
              & (gains[leaf] >= gain_gate * phase_max)
              & (st.tree.num_leaves < L)
              & (st.pend_cnt < P))
        return leaf, ok

    def _commit_split_meta(st: _WaveState, leaf):
        """Commit ``leaf``'s cached best split into the [L]-sized state
        (tree arrays, child stats, monotone windows, pend queues, CEGB)
        — everything a split does EXCEPT the [N] ``leaf_id`` partition,
        which the caller applies per split (``_split_once``) or batched
        per phase (``_split_phase_batched``).  Returns
        ``(st, feature, threshold, default_left, cat_bitset, new)``."""
        new = st.tree.num_leaves.astype(jnp.int32)  # next leaf index
        k = new - 1                                  # node index
        f = st.best_feat[leaf]
        t = st.best_thr[leaf]
        dl = st.best_dl[leaf]
        cb = st.best_cb[leaf]
        lg, lh, lc = st.best_lg[leaf], st.best_lh[leaf], st.best_lc[leaf]
        pg, ph, pc = st.leaf_g[leaf], st.leaf_h[leaf], st.leaf_c[leaf]
        rg, rh, rc = pg - lg, ph - lh, pc - lc
        min_c, max_c = st.leaf_min_c[leaf], st.leaf_max_c[leaf]
        out_l, out_r = st.best_lout[leaf], st.best_rout[leaf]
        mono = meta.monotone[f]
        mid = (out_l + out_r) / 2.0
        l_min = jnp.where(mono < 0, mid, min_c)
        l_max = jnp.where(mono > 0, mid, max_c)
        r_min = jnp.where(mono > 0, mid, min_c)
        r_max = jnp.where(mono < 0, mid, max_c)

        tr = st.tree
        parent_node = st.leaf_parent[leaf]
        has_parent = parent_node >= 0
        pn = jnp.maximum(parent_node, 0)
        new_lc_ptr = jnp.where(has_parent & ~st.leaf_is_right[leaf],
                               k, tr.left_child[pn])
        new_rc_ptr = jnp.where(has_parent & st.leaf_is_right[leaf],
                               k, tr.right_child[pn])
        cc = st.cegb_coupled
        if cegb is not None:
            cc = cc.at[f].set(0.0)
        tr = tr._replace(
            split_feature=tr.split_feature.at[k].set(f),
            threshold_bin=tr.threshold_bin.at[k].set(t),
            default_left=tr.default_left.at[k].set(dl),
            split_gain=tr.split_gain.at[k].set(st.best_gain[leaf]),
            internal_value=tr.internal_value.at[k].set(st.leaf_out[leaf]),
            internal_count=tr.internal_count.at[k].set(pc.astype(jnp.int32)),
            internal_weight=tr.internal_weight.at[k].set(ph),
            left_child=tr.left_child.at[pn].set(new_lc_ptr).at[k].set(~leaf),
            right_child=tr.right_child.at[pn].set(new_rc_ptr).at[k].set(~new),
            num_leaves=tr.num_leaves + 1,
            cat_bitset=tr.cat_bitset.at[k].set(cb),
        )

        small = jnp.where(lc < rc, leaf, new)
        large = jnp.where(lc < rc, new, leaf)
        d = st.leaf_depth[leaf] + 1

        def upd(a, v1, v2):
            return a.at[leaf].set(v1).at[new].set(v2)

        st = st._replace(
            leaf_g=upd(st.leaf_g, lg, rg),
            leaf_h=upd(st.leaf_h, lh, rh),
            leaf_c=upd(st.leaf_c, lc, rc),
            leaf_depth=upd(st.leaf_depth, d, d),
            leaf_min_c=upd(st.leaf_min_c, l_min, r_min),
            leaf_max_c=upd(st.leaf_max_c, l_max, r_max),
            leaf_out=upd(st.leaf_out, out_l, out_r),
            hist_ready=upd(st.hist_ready, False, False),
            best_gain=upd(st.best_gain, NEG_INF, NEG_INF),
            leaf_parent=upd(st.leaf_parent, k, k),
            leaf_is_right=upd(st.leaf_is_right, False, True),
            pend_small=st.pend_small.at[st.pend_cnt].set(small),
            pend_large=st.pend_large.at[st.pend_cnt].set(large),
            pend_cnt=st.pend_cnt + 1,
            tree=tr,
            cegb_coupled=cc,
        )
        st = _count(st, routed_rows=pc.astype(jnp.int32))
        if has_cat:
            st = _count(st, cat_splits=meta.is_categorical[f].astype(
                jnp.int32))
        return st, f, t, dl, cb, new

    @jax.named_scope("lgbm/wave_split_phase")
    def _split_once(st: _WaveState, bins_fm, feature_mask, phase_max):
        """Sequential oracle: commit ONE split and walk the rows for it at
        once — the reference's one-split-at-a-time partition order, kept
        behind ``batched_apply=False`` for differential testing.  The walk
        is the batched phase's (``build_split_route_fn``); what differs is
        that the whole ``_WaveState`` rides the ``cond`` of every slot."""
        leaf, ok = _pick_split(st, phase_max)

        def do(st: _WaveState) -> _WaveState:
            st, f, t, dl, cb, new = _commit_split_meta(st, leaf)
            with jax.named_scope("lgbm/wave_partition"):
                leaf_id = _route(st.leaf_id, bins_fm, leaf, new, f, t, dl,
                                 cb)
            return _count(st._replace(leaf_id=leaf_id), walks=1,
                          route_passes=1)

        return jax.lax.cond(ok, do, lambda s: s, st)

    if batched_apply:
        _apply_splits = build_split_apply_fn(meta, bundled=bundled,
                                             interpret=interpret)
        W_slots = bitset_words(B)

    @jax.named_scope("lgbm/wave_split_phase")
    def _split_phase_batched(st: _WaveState, view, feature_mask,
                             phase_max):
        """Batched split phase: commit up to P splits' [L]-sized metadata
        in a ``lax.scan`` (the commit ORDER — argmax over the updated
        gains each step — is exactly the sequential fori_loop's, so the
        tree is identical), then route the rows for all of them in one
        pass over ``view`` (``build_split_apply_fn``).  A leaf splits at
        most once per phase and a phase never targets a child it created
        (``hist_ready``/``best_gain`` are cleared on commit), so the
        order of the slots is immaterial."""
        def step(st, _):
            leaf, ok = _pick_split(st, phase_max)

            def do(st):
                st, f, t, dl, cb, new = _commit_split_meta(st, leaf)
                return st, WaveSplits(jnp.bool_(True), leaf, new, f, t,
                                      dl, cb)

            def skip(st):
                return st, WaveSplits(
                    jnp.bool_(False), jnp.int32(-1), jnp.int32(-1),
                    jnp.int32(0), jnp.int32(0), jnp.bool_(False),
                    jnp.zeros((W_slots,), jnp.uint32))

            return jax.lax.cond(ok, do, skip, st)

        st, slots = jax.lax.scan(step, st, None, length=P)
        leaf_id, walks, passes = _apply_splits(st.leaf_id, view, slots)
        return _count(st._replace(leaf_id=leaf_id), walks=walks,
                      route_passes=passes)

    # ---------------- wave phase ---------------------------------------
    def _scan_children(st: _WaveState, smalls, larges, feature_mask,
                       scales=None):
        """Best-split scan for one wave's children (both sides) + the
        [L]-sized ready/best bookkeeping, inline at wave time.
        ``scales`` dequantizes the integer histograms per leaf scan
        under the quantized modes."""
        cand = jnp.concatenate([smalls, larges])         # [2P]
        valid = cand >= 0
        cl = jnp.where(valid, cand, 0)
        scan = _scan_leaf
        if columns is not None:
            col0, local, feature_mask = column_share(feature_mask)
            scan = functools.partial(_scan_leaf, scan_meta=local)
        bs = jax.vmap(
            scan, in_axes=(0, 0, 0, 0, 0, 0, 0, None, None, None))(
            st.hist[cl], st.leaf_g[cl], st.leaf_h[cl], st.leaf_c[cl],
            st.leaf_min_c[cl], st.leaf_max_c[cl], st.leaf_depth[cl],
            feature_mask, st.cegb_coupled, scales)
        if columns is not None:
            with jax.named_scope("lgbm/split_sync"):
                bs = columns.sync(bs._replace(feature=jnp.where(
                    bs.feature >= 0, bs.feature + col0, bs.feature)))
            st = _count(st, split_syncs=1)
        cl_w = jnp.where(valid, cand, L)
        return st._replace(
            hist_ready=st.hist_ready.at[cl_w].set(True),
            best_gain=st.best_gain.at[cl_w].set(bs.gain),
            best_feat=st.best_feat.at[cl_w].set(bs.feature),
            best_thr=st.best_thr.at[cl_w].set(bs.threshold),
            best_dl=st.best_dl.at[cl_w].set(bs.default_left),
            best_lg=st.best_lg.at[cl_w].set(bs.left_g),
            best_lh=st.best_lh.at[cl_w].set(bs.left_h),
            best_lc=st.best_lc.at[cl_w].set(bs.left_c),
            best_lout=st.best_lout.at[cl_w].set(bs.left_out),
            best_rout=st.best_rout.at[cl_w].set(bs.right_out),
            best_cb=st.best_cb.at[cl_w].set(bs.cat_bitset),
        )

    def _wave(st: _WaveState, bins_fm, wide_rm, gv, hv, cv, words,
              weighted, feature_mask, scales=None):
        def do(st: _WaveState) -> _WaveState:
            c_idx = jnp.arange(C_MAX) // (2 if packed else 3)
            slot_leaf = jnp.where(c_idx < P, st.pend_small[jnp.minimum(c_idx, P - 1)],
                                  -1).astype(jnp.int32)
            smalls = st.pend_small                       # [P]
            larges = st.pend_large
            dead = smalls < 0
            no_sib = larges < 0
            parents = jnp.minimum(smalls, jnp.where(no_sib, smalls, larges))
            parents = jnp.maximum(parents, 0)
            kern_parent = None
            if fused:
                # parent histograms in the kernel's channel layout; fused
                # implies un-bundled + un-mixed, so st.hist's feature/bin
                # space IS the kernel's physical space.  Dead slots gather
                # leaf 0's histogram — their sibling output is garbage the
                # masked writes below discard, exactly as on the XLA path.
                with jax.named_scope("lgbm/wave_hist_state"):
                    par = st.hist[parents]               # [P, F, B, 3]
                    Fh = par.shape[1]
                    if packed:
                        kern_parent = pack_lanes(par, highest)
                    else:
                        kern_parent = jnp.pad(
                            par.transpose(1, 2, 0, 3).reshape(Fh, B, 3 * P),
                            ((0, 0), (0, 0), (0, C_MAX - 3 * P)))
            bins_n_fm = bins_fm[0] if mixed is not None else bins_fm

            # ---- active-row compaction --------------------------------
            # Only rows sitting in a pending-small leaf (and carrying
            # weight — bagging/GOSS masks zero the rest) contribute to
            # this wave.  Compact them to the front, then dispatch to the
            # smallest statically-compiled kernel size tier that fits:
            # the per-tree histogram cost becomes sum-of-smaller-children
            # (each overshooting at most 2x), the reference's cost model
            # (serial_tree_learner.cpp:496-522), instead of N x waves.
            # Static tiers keep the Pallas grid fully pipelined — a
            # dynamically bounded grid defeats Mosaic's DMA scheduling.
            N = bins_n_fm.shape[1]
            # Counting the active rows costs per row the chip holds and
            # is streaming passes only: 0.9-1.4 ms for 10.5-11M rows on
            # the v5e, 0.09-0.13 ns a row (PERF.md 6, PR 29 and 31).
            with jax.named_scope("lgbm/wave_compact"):
                active = active_rows(st.leaf_id, st.pend_small, weighted)
                _, start, n_active = pack_active_rows(active)
                placed = placed_sub_blocks(start, n_active)

            # size tiers (``tier_ladder``): tier k is the smallest still
            # >= n_active,
            # so late waves (tiny pending sets) pay a tiny kernel.  The
            # full tier compacts nothing (inactive rows' leaves miss
            # every slot, so they contribute zero in-kernel).  Below it
            # the tier's rows arrive by ONE sequential pass over every
            # row the chip holds (ops/pallas_compact.py), made once for
            # whichever tier the wave takes; the tier's branch slices
            # its first T columns.  On the v5e that pass costs, a row the
            # chip holds: 0.22-0.23 ns at 6-28 columns and 0.29-0.38 at
            # 136, whatever share of the rows is active (every sub-block
            # of 128 rows is placed the same way, with or without rows:
            # PERF.md 6, PR 37's step 0; the placement matmul it replaced
            # cost 1.3-1.6 / 2.3 ns at 30% active and 0.7 / 1.2 at
            # 0.5%).  The index and the three gathers of PR 29 cost
            # 55-70 ns a row OF THE TIER and never under 5 ms a wave (a
            # gather goes by its output rows and by latency), where a
            # streamed wave is now 2.4 ms at 10.5-11M rows.
            tiers = tier_ladder(N, block_rows)
            K = len(tiers)

            def full_tier(_):
                return _wave_hist(bins_n_fm, wide_rm, gv, hv, cv,
                                  st.leaf_id, slot_leaf, parent=kern_parent)

            def tier_call(T):
                def f(streamed):
                    with jax.named_scope("lgbm/wave_compact"):
                        bins_c, gc, hc, cc, leaf_c, wide_c = tier_front(
                            *streamed, n_active, T, bins_n_fm.shape[0],
                            wide=((bins_fm[1].dtype, Fw)
                                  if mixed is not None else None))
                        if wide_c is not None:
                            wide_c = wide_c.T            # the side-pass's
                    return _wave_hist(bins_c, wide_c, gc, hc, cc, leaf_c,
                                      slot_leaf, parent=kern_parent)
                return f

            def compacted(_):
                with jax.named_scope("lgbm/wave_compact"):
                    streamed = stream_rows(
                        bins_n_fm, words, st.leaf_id, active, start,
                        n_active, tiers[1], interpret=interpret)
                return jax.lax.switch(
                    k - 1, [tier_call(T) for T in tiers[1:]], streamed)

            if K == 1:
                hw = full_tier(0)
                tsize = jnp.int32(N)
            else:
                # smallest tier >= n_active: count tiers that fit
                thresholds = jnp.asarray(np.asarray(tiers, np.int32))
                k = jnp.clip(jnp.sum(
                    (thresholds >= jnp.maximum(n_active, 1)).astype(
                        jnp.int32)) - 1, 0, K - 1)
                hw = jax.lax.cond(k == 0, full_tier, compacted, 0)
                tsize = thresholds[k]                     # [F, B, C]
            hw_sib = None
            if fused:
                hw, hw_sib = hw
            if reduce_fn is not None:
                # global histograms: every device now sees the same wave
                # result and takes identical split decisions (fused is
                # off here — the subtraction must follow the psum)
                hw = (tuple(reduce_fn(x) for x in hw) if packed
                      else reduce_fn(hw))

            def to_leaf_major(h):
                """Channel layout -> per-leaf [P, F, B, 3] histograms; where
                bundled, physical columns -> features on the way
                (io/bundling.py layout; the elided default bins are fixed
                below, once the lanes are leaves)."""
                if packed:
                    # the slots' own lanes first: what follows handles
                    # 3 P channels, not the layout's 256
                    h = gather_lanes(h, highest, P)      # [F, B, 3 P]
                if bundled:
                    with jax.named_scope("lgbm/efb_expand"):
                        h = expand_bundled(h, meta, B)
                Fdim = h.shape[0]
                if packed:
                    return h.reshape(Fdim, B, 3, P).transpose(3, 0, 1, 2)
                return h[:, :, :3 * P].reshape(
                    Fdim, B, P, 3).transpose(2, 0, 1, 3)

            # the per-leaf histogram state's own shuffles carry
            # lgbm/wave_hist_state: they grow with F x B, not with the rows
            # (the parents' gather above, the lanes back to leaves, the
            # sibling subtraction, the two writes into st.hist)
            with jax.named_scope("lgbm/wave_hist_state"):
                ws = to_leaf_major(hw)
            if bundled:
                sl = jnp.maximum(smalls, 0)
                with jax.named_scope("lgbm/efb_expand"):
                    ws = jax.vmap(fix_default_bins,
                                  in_axes=(0, 0, 0, 0, None))(
                        ws, st.leaf_g[sl], st.leaf_h[sl], st.leaf_c[sl],
                        meta)
            with jax.named_scope("lgbm/wave_hist_state"):
                # the sibling: from the fused kernel when it rode along,
                # else parent-minus-child in XLA (post-psum /
                # post-default-bin-fix)
                sib = (to_leaf_major(hw_sib) if fused
                       else st.hist[parents] - ws)       # [P, F, B, 3]

                smalls_w = jnp.where(dead, L, smalls)
                larges_w = jnp.where(dead | no_sib, L, larges)
                hist = st.hist.at[smalls_w].set(ws)
                hist = hist.at[larges_w].set(sib)

            below = (tsize < N).astype(jnp.int32)
            st = _count(st, waves=1, lanes=st.pend_cnt, kernel_rows=tsize,
                        kernel_pass_rows=tsize * wave_mxu_passes(
                            st.pend_cnt, highest, packed),
                        active_rows=n_active, compact_waves=below,
                        stream_waves=below,
                        stream_blocks=below * (-(-N // 128)),
                        placed_blocks=below * placed)
            st = st._replace(
                hist=hist,
                pend_small=jnp.full((P,), -1, jnp.int32),
                pend_large=jnp.full((P,), -1, jnp.int32),
                pend_cnt=jnp.int32(0),
            )
            return _scan_children(st, smalls, larges, feature_mask, scales)

        return jax.lax.cond(st.pend_cnt > 0, do, lambda s: s, st)

    # ---------------- driver -------------------------------------------
    def grow(bins_fm, g, h, sample_mask, feature_mask, cegb_coupled=None):
        N = (bins_fm[0] if mixed is not None else bins_fm).shape[1]
        W = bitset_words(B)
        if cegb is not None and cegb_coupled is None:
            cegb_coupled = jnp.zeros((F,), jnp.float32)
        if cegb is None:
            cegb_coupled = None
        gv = (g * sample_mask).astype(jnp.float32)
        hv = (h * sample_mask).astype(jnp.float32)
        cv = sample_mask.astype(jnp.float32)
        scales = None
        if quant:
            # per-tree symmetric scales from the GLOBAL |g|/|h| maxima
            # (reduce_max_fn under data parallelism — every shard must
            # quantize with the same step or the psum'd integer sums
            # would mix units), then value-hash stochastic rounding.
            # Masked-out rows are exact zeros and stay zeros, so the
            # bag mask survives quantization bit-exactly.
            qmax = QUANT_QMAX[mode_r]
            ag = jnp.max(jnp.abs(gv))
            ah = jnp.max(jnp.abs(hv))
            if reduce_max_fn is not None:
                ag = reduce_max_fn(ag)
                ah = reduce_max_fn(ah)
            s_g = jnp.maximum(ag, jnp.float32(1e-30)) / qmax
            s_h = jnp.maximum(ah, jnp.float32(1e-30)) / qmax
            gv = stochastic_round(gv / s_g, jnp.uint32(quant_seed))
            hv = stochastic_round(hv / s_h,
                                  jnp.uint32(quant_seed) ^
                                  jnp.uint32(0x9E3779B9))
            scales = (s_g, s_h)
        sum_g = jnp.sum(gv)
        sum_h = jnp.sum(hv)
        cnt = jnp.sum(cv)
        if reduce_fn is not None:
            sum_g = reduce_fn(sum_g)
            sum_h = reduce_fn(sum_h)
            cnt = reduce_fn(cnt)
        if quant:
            # root sums back to value units AFTER the global reduce, so
            # they are s * (exact integer total) on every shard
            sum_g = sum_g * scales[0]
            sum_h = sum_h * scales[1]

        Lf = jnp.zeros((L + 1,), jnp.float32)
        Li = jnp.zeros((L + 1,), jnp.int32)
        inf = jnp.float32(jnp.inf)
        st = _WaveState(
            leaf_id=jnp.zeros((N,), jnp.int32),
            hist=jnp.zeros((L + 1, F_hist, B, 3), jnp.float32),
            leaf_g=Lf.at[0].set(sum_g),
            leaf_h=Lf.at[0].set(sum_h),
            leaf_c=Lf.at[0].set(cnt),
            leaf_depth=Li,
            leaf_min_c=jnp.full((L + 1,), -inf),
            leaf_max_c=jnp.full((L + 1,), inf),
            leaf_out=Lf.at[0].set(leaf_output(sum_g, sum_h, cfg)),
            hist_ready=jnp.zeros((L + 1,), bool),
            best_gain=jnp.full((L + 1,), NEG_INF),
            best_feat=Li, best_thr=Li,
            best_dl=jnp.zeros((L + 1,), bool),
            best_lg=Lf, best_lh=Lf, best_lc=Lf,
            best_lout=Lf, best_rout=Lf,
            best_cb=jnp.zeros((L + 1, W), jnp.uint32),
            leaf_parent=jnp.full((L + 1,), -1, jnp.int32),
            leaf_is_right=jnp.zeros((L + 1,), bool),
            pend_small=jnp.full((P,), -1, jnp.int32).at[0].set(0),
            pend_large=jnp.full((P,), -1, jnp.int32),
            pend_cnt=jnp.int32(1),
            tree=_empty_tree(L, W),
            cegb_coupled=cegb_coupled,
            counts=(WaveCounts(**{k: jnp.zeros(
                (2,) if k.endswith("_rows") else (), jnp.int32)
                for k in WaveCounts._fields
                if (has_cat or k != "cat_splits")
                and (columns is not None or k != "split_syncs")})
                if report_waves else None),
        )
        # Alternate split and wave phases until no ready leaf has positive
        # gain and nothing is pending.  The first body iteration has no
        # ready leaves, so it falls straight through to the root wave.
        # A while_loop (not fori) so a finished tree stops paying for
        # kernel passes — each iteration either splits a leaf or is the
        # root wave, so it runs at most L times.
        def loop_cond(st):
            ready = jnp.where(st.hist_ready[:L], st.best_gain[:L], NEG_INF)
            can_split = (jnp.max(ready) > 0.0) & (st.tree.num_leaves < L)
            return (st.pend_cnt > 0) | can_split

        # compaction's invariants of the tree: the three row vectors as
        # the word rows the streamed pass carries, and the rows that
        # carry weight at all (bagging / GOSS zero the rest).  Behind a
        # barrier: fused with them, the root sums above would be tiled
        # another way and add up in another order.  Under the mixed
        # layout also the wide columns: two a word for the streamed
        # pass, and row-major for the XLA side-pass over the full tier.
        with jax.named_scope("lgbm/wave_compact"):
            g3 = jax.lax.optimization_barrier((gv, hv, cv))
            words = row_words(
                *g3, wide=bins_fm[1] if mixed is not None else None)
            weighted = (g3[0] != 0) | (g3[1] != 0) | (g3[2] != 0)
        wide_rm = jnp.transpose(bins_fm[1]) if mixed is not None else None
        # what the split phase's one pass reads, held for the tree: a
        # second copy of the bins with each column's rows contiguous
        view = route_view(bins_fm, mixed) if batched_apply else None

        def loop_body(st):
            st = _count(st, bodies=1)
            ready = jnp.where(st.hist_ready[:L], st.best_gain[:L], NEG_INF)
            phase_max = jnp.max(ready)

            if batched_apply:
                st = _split_phase_batched(st, view, feature_mask, phase_max)
            else:
                def split_body(_, st):
                    return _split_once(st, bins_fm, feature_mask, phase_max)
                st = jax.lax.fori_loop(0, P, split_body, st)
            return _wave(st, bins_fm, wide_rm, gv, hv, cv, words,
                         weighted, feature_mask, scales)

        st = jax.lax.while_loop(loop_cond, loop_body, st)

        tr = st.tree._replace(
            leaf_value=st.leaf_out[:L],
            leaf_count=st.leaf_c[:L].astype(jnp.int32),
            leaf_weight=st.leaf_h[:L],
        )
        if cegb is not None:
            return tr, st.leaf_id, st.cegb_coupled
        if report_waves:
            return tr, st.leaf_id, _pack_counts(st.counts)
        return tr, st.leaf_id

    return grow

