"""Vectorized best-split search over per-leaf histograms.

The reference scans each feature's histogram twice (left-to-right and
right-to-left) with running sums, missing-value routing, min-data /
min-hessian guards and L1/L2-regularized gain
(reference: src/treelearner/feature_histogram.hpp:91-653, FindBestThreshold*).
On TPU both directions become masked prefix/suffix sums over the padded
``[F, B, 3]`` histogram, evaluated for every feature and threshold at once,
followed by a single argmax.

Semantics preserved from the reference:
- ``missing_type == Zero``: the zero (default) bin is excluded from the
  running sums, so its mass implicitly lands on the side opposite the scan —
  the "default" side recorded as ``default_left = (dir == -1)``.
- ``missing_type == NaN``: the last bin holds NaNs; it is excluded from both
  running sums and its mass lands on the default side via the
  total-minus-accumulated subtraction.
- Features with ``num_bin <= 2`` or no missing use only the right-to-left
  scan (reference: feature_histogram.hpp:104-111).
- kEpsilon hessian seeding and the strict ``gain > gain_shift +
  min_gain_to_split`` comparison match the reference bit-for-bit in f32.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from ..io.binning import MISSING_NAN, MISSING_NONE, MISSING_ZERO
from .meta import DeviceMeta, SplitConfig

K_EPSILON = 1e-15
NEG_INF = -jnp.inf


def bitset_words(B: int) -> int:
    """uint32 words needed for a bin-space bitset."""
    return max(1, (B + 31) // 32)


def threshold_l1(s, l1):
    """Soft-threshold by the L1 penalty (reference: ThresholdL1,
    feature_histogram.hpp:446-449)."""
    if l1 <= 0.0:
        return s
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def leaf_output_l2(g, h, cfg: SplitConfig, l2):
    """Regularized leaf output with an explicit L2 (categorical splits add
    cat_l2; reference: CalculateSplittedLeafOutput,
    feature_histogram.hpp:450-457)."""
    ret = -threshold_l1(g, cfg.lambda_l1) / (h + l2)
    if cfg.max_delta_step > 0.0:
        ret = jnp.clip(ret, -cfg.max_delta_step, cfg.max_delta_step)
    return ret


def leaf_output(g, h, cfg: SplitConfig):
    """Regularized leaf output (reference: CalculateSplittedLeafOutput,
    feature_histogram.hpp:450-457)."""
    return leaf_output_l2(g, h, cfg, cfg.lambda_l2)


def leaf_output_constrained(g, h, cfg: SplitConfig, min_c, max_c):
    """Leaf output clamped into the monotone value constraint window
    (reference: feature_histogram.hpp:481-490)."""
    return jnp.clip(leaf_output(g, h, cfg), min_c, max_c)


def leaf_gain_given_output(g, h, out, cfg: SplitConfig, l2=None):
    """(reference: GetLeafSplitGainGivenOutput, feature_histogram.hpp:503-506)."""
    if l2 is None:
        l2 = cfg.lambda_l2
    sg = threshold_l1(g, cfg.lambda_l1)
    return -(2.0 * sg * out + (h + l2) * out * out)


def leaf_split_gain(g, h, cfg: SplitConfig):
    """Gain of keeping a leaf unsplit (reference: GetLeafSplitGain,
    feature_histogram.hpp:497-501)."""
    return leaf_gain_given_output(g, h, leaf_output(g, h, cfg), cfg)


def _split_gains(gl, hl, gr, hr, cfg: SplitConfig, min_c, max_c, monotone,
                 l2=None):
    """Pairwise split gain with monotone rejection (reference: GetSplitGains,
    feature_histogram.hpp:459-472). All args broadcastable arrays."""
    if l2 is None:
        l2 = cfg.lambda_l2
    out_l = jnp.clip(leaf_output_l2(gl, hl, cfg, l2), min_c, max_c)
    out_r = jnp.clip(leaf_output_l2(gr, hr, cfg, l2), min_c, max_c)
    gain = (leaf_gain_given_output(gl, hl, out_l, cfg, l2)
            + leaf_gain_given_output(gr, hr, out_r, cfg, l2))
    violates = ((monotone > 0) & (out_l > out_r)) | ((monotone < 0) & (out_l < out_r))
    return jnp.where(violates, 0.0, gain)


class BestSplit(NamedTuple):
    """Scalar result of a leaf's best-split search (the SplitInfo analog,
    reference: src/treelearner/split_info.hpp:22)."""
    gain: jnp.ndarray          # f32 — gain minus (parent gain + min_gain_to_split)
    feature: jnp.ndarray       # i32 — inner feature index (-1 if none)
    threshold: jnp.ndarray     # i32 — bin-space threshold (numerical)
    default_left: jnp.ndarray  # bool
    left_g: jnp.ndarray        # f32 — left child sum of gradients
    left_h: jnp.ndarray        # f32
    left_c: jnp.ndarray        # f32 — left child row count
    left_out: jnp.ndarray      # f32 — left child output (reference SplitInfo
    right_out: jnp.ndarray     # f32   carries outputs; cat splits use +cat_l2)
    # categorical: bitset over bins, left = bins in set (all-zero if numerical)
    cat_bitset: jnp.ndarray    # uint32 [(B+31)/32]


def _pack_bitset(member, B: int):
    """Pack a [B] bool membership vector into uint32 words (the device form
    of Common::ConstructBitset, reference: utils/common.h)."""
    W = bitset_words(B)
    pad = W * 32 - B
    m = member.astype(jnp.uint32)
    if pad:
        m = jnp.pad(m, (0, pad))
    weights = jnp.left_shift(jnp.uint32(1),
                             jnp.arange(W * 32, dtype=jnp.uint32) % 32)
    return (m * weights).reshape(W, 32).sum(axis=1).astype(jnp.uint32)


def bitset_contains(words, idx):
    """Elementwise bit test: words uint32 [..., W], idx int32 [...]."""
    w = (idx // 32).astype(jnp.int32)
    b = (idx % 32).astype(jnp.uint32)
    word = jnp.take_along_axis(words, w[..., None], axis=-1)[..., 0]
    return (jnp.right_shift(word, b) & jnp.uint32(1)) != 0


def split_decision(col, threshold, default_left, is_cat, cat_word,
                   missing_type, num_bin, default_bin):
    """Bin-space go-left decision, fully vectorized — the ONE place the
    reference's Tree::Decision / DenseBin::Split semantics live
    (reference: src/io/dense_bin.hpp:152-231, tree.h:221-303), shared by
    tree growth (``core/grower.py go_left_bins/go_left_node``), the wave
    grower's batched split apply (``core/wave_grower.py``) and device
    prediction (``core/predict.py``).

    All args broadcastable arrays: ``col`` i32 bin values; ``cat_word``
    u32 — the bitset word already gathered for ``col`` (word index
    ``col // 32``; pass 0 for numerical-only callers).  Missing routing:
    the NaN bin (``num_bin - 1`` under MISSING_NAN) and the default bin
    (under MISSING_ZERO) take ``default_left``; everything else compares
    ``col <= threshold``.  Categorical nodes test bit ``col % 32`` of
    ``cat_word`` instead.

    ``is_cat`` given as a Python bool, or ``missing_type`` as the Python int
    ``MISSING_NONE``, is a STATIC fact of the call: only that side is traced
    (the routing kernel, ``ops/pallas_route.py``, compiles one branch a kind
    of split and picks it by the split's scalars).
    """
    def num_go():
        if isinstance(missing_type, int) and missing_type == MISSING_NONE:
            return col <= threshold
        is_missing = (((missing_type == MISSING_NAN) & (col == num_bin - 1))
                      | ((missing_type == MISSING_ZERO)
                         & (col == default_bin)))
        # a select between booleans, spelt so that Mosaic lowers it too
        return (is_missing & default_left) | (~is_missing
                                              & (col <= threshold))

    def cat_go():
        return (jnp.right_shift(cat_word, (col % 32).astype(jnp.uint32))
                & jnp.uint32(1)) != 0
    if isinstance(is_cat, bool):
        return cat_go() if is_cat else num_go()
    return jnp.where(is_cat, cat_go(), num_go())


def _categorical_best(g, h, c, sum_g, sum_h, cnt, meta: DeviceMeta,
                      cfg: SplitConfig, min_c, max_c, min_gain_shift):
    """Per-feature best categorical split over raw per-bin histograms
    (reference: FindBestThresholdCategorical, feature_histogram.hpp:118-279).

    One-hot for features with num_bin <= max_cat_to_onehot; otherwise the
    sorted-by-g/h-ratio two-direction scan with cat_l2/cat_smooth and the
    min_data_per_group batching.  Returns per-feature arrays plus the
    selection info needed to rebuild the winning bin set.
    """
    F, B = g.shape
    bins = jnp.arange(B, dtype=jnp.int32)[None, :]
    nb = meta.num_bins[:, None]
    is_full = (meta.missing_types == MISSING_NONE)[:, None]
    used_bin = nb - 1 + is_full.astype(jnp.int32)            # [F, 1]
    in_range = bins < used_bin
    f_idx = jnp.arange(F)

    # ---- one-hot: left = single category t (hpp:139-169) -------------
    h_e = h + K_EPSILON
    other_h = sum_h - h - K_EPSILON
    ok_oh = (in_range & (c >= cfg.min_data_in_leaf)
             & (h >= cfg.min_sum_hessian_in_leaf)
             & (cnt - c >= cfg.min_data_in_leaf)
             & (other_h >= cfg.min_sum_hessian_in_leaf))
    gain_oh = _split_gains(sum_g - g, other_h, g, h_e, cfg, min_c, max_c, 0)
    gain_oh = jnp.where(ok_oh & (gain_oh > min_gain_shift), gain_oh, NEG_INF)
    t_oh = jnp.argmax(gain_oh, axis=1).astype(jnp.int32)     # [F]
    best_oh = gain_oh[f_idx, t_oh]
    lg_oh, lh_oh, lc_oh = g[f_idx, t_oh], h_e[f_idx, t_oh], c[f_idx, t_oh]
    lout_oh = jnp.clip(leaf_output(lg_oh, lh_oh, cfg), min_c, max_c)
    rout_oh = jnp.clip(leaf_output(sum_g - lg_oh, sum_h - lh_oh, cfg),
                       min_c, max_c)

    # ---- sorted-ratio scan (hpp:170-239) ------------------------------
    l2s = cfg.lambda_l2 + cfg.cat_l2
    ok_bin = in_range & (c >= cfg.cat_smooth)
    ratio = jnp.where(ok_bin, g / (h + cfg.cat_smooth), jnp.inf)
    order = jnp.argsort(ratio, axis=1, stable=True).astype(jnp.int32)
    used = jnp.sum(ok_bin, axis=1).astype(jnp.int32)         # [F]
    max_num_cat = jnp.minimum(cfg.max_cat_threshold, (used + 1) // 2)

    gather = lambda a, idx: jnp.take_along_axis(a, idx, axis=1)
    sg1, sh1, sc1 = gather(g, order), gather(h, order), gather(c, order)
    # dir=-1 visits sorted positions used-1, used-2, ...
    idx2 = jnp.clip(used[:, None] - 1 - bins, 0, B - 1)
    sg2, sh2, sc2 = gather(sg1, idx2), gather(sh1, idx2), gather(sc1, idx2)

    def dir_arrays(sg, sh, sc):
        lg = jnp.cumsum(sg, axis=1)
        lh = jnp.cumsum(sh, axis=1) + K_EPSILON
        lc = jnp.cumsum(sc, axis=1)
        rc, rh = cnt - lc, sum_h - lh
        valid_i = (bins < used[:, None]) & (bins < max_num_cat[:, None])
        left_ok = ((lc >= cfg.min_data_in_leaf)
                   & (lh >= cfg.min_sum_hessian_in_leaf))
        # break guards fire only at visited positions that pass the left
        # "continue" guards (hpp:212-219); the breaking position itself is
        # not evaluated, so the exclusion is inclusive-cumulative
        brk = (((rc < cfg.min_data_in_leaf) | (rc < cfg.min_data_per_group)
                | (rh < cfg.min_sum_hessian_in_leaf))
               & left_ok & valid_i)
        broken = jnp.cumsum(brk.astype(jnp.int32), axis=1) > 0
        eligible = valid_i & left_ok & ~broken
        gain = _split_gains(lg, lh, sum_g - lg, sum_h - lh, cfg,
                            min_c, max_c, 0, l2=l2s)
        return lg, lh, lc, eligible, gain

    lg1c, lh1c, lc1c, el1, gg1 = dir_arrays(sg1, sh1, sc1)
    lg2c, lh2c, lc2c, el2, gg2 = dir_arrays(sg2, sh2, sc2)

    # min_data_per_group batching: a candidate is only evaluated (and the
    # group counter reset) once the accumulated group reaches the minimum
    # (hpp:221-224) — a sequential recurrence, scanned over the bin axis
    cc = jnp.stack([sc1, sc2], axis=1)                       # [F, 2, B]
    el = jnp.stack([el1, el2], axis=1)

    def step(grp, xs):
        c_i, elig_i = xs
        grp = grp + c_i
        ev = elig_i & (grp >= cfg.min_data_per_group)
        return jnp.where(ev, 0.0, grp), ev

    _, evs = jax.lax.scan(step, jnp.zeros((F, 2), cc.dtype),
                          (jnp.moveaxis(cc, 2, 0), jnp.moveaxis(el, 2, 0)))
    evs = jnp.moveaxis(evs, 0, 2)                            # [F, 2, B]

    gains_s = jnp.stack([gg1, gg2], axis=1)
    gains_s = jnp.where(evs & (gains_s > min_gain_shift), gains_s, NEG_INF)
    flat = gains_s.reshape(F, 2 * B)                         # dir-major order
    w_s = jnp.argmax(flat, axis=1).astype(jnp.int32)
    best_s = flat[f_idx, w_s]
    dir_s = w_s // B                                         # 0 → +1, 1 → -1
    i_s = w_s % B
    pick_d = lambda a1, a2: jnp.where(dir_s == 0, a1[f_idx, i_s], a2[f_idx, i_s])
    lg_s, lh_s, lc_s = pick_d(lg1c, lg2c), pick_d(lh1c, lh2c), pick_d(lc1c, lc2c)
    lout_s = jnp.clip(leaf_output_l2(lg_s, lh_s, cfg, l2s), min_c, max_c)
    rout_s = jnp.clip(leaf_output_l2(sum_g - lg_s, sum_h - lh_s, cfg, l2s),
                      min_c, max_c)

    # ---- merge the two paths per feature ------------------------------
    use_oh = nb[:, 0] <= cfg.max_cat_to_onehot
    sel = lambda a, b: jnp.where(use_oh, a, b)
    return dict(
        gain=sel(best_oh, best_s),
        left_g=sel(lg_oh, lg_s),
        left_h=sel(lh_oh, lh_s) - K_EPSILON,
        left_c=sel(lc_oh, lc_s),
        left_out=sel(lout_oh, lout_s),
        right_out=sel(rout_oh, rout_s),
        use_oh=use_oh, t_oh=t_oh, order=order, used=used,
        dir_s=dir_s, i_s=i_s,
    )


def _cat_winner_bitset(cat: dict, f_best, B: int):
    """Left-going bin set of the winning categorical split, packed."""
    bins = jnp.arange(B, dtype=jnp.int32)
    orow = cat["order"][f_best]
    u = cat["used"][f_best]
    i = cat["i_s"][f_best]
    pos_member = jnp.where(cat["dir_s"][f_best] == 0,
                           bins <= i,
                           (bins >= u - 1 - i) & (bins < u))
    member_sorted = jnp.zeros((B,), bool).at[orow].set(pos_member)
    member_oh = bins == cat["t_oh"][f_best]
    member = jnp.where(cat["use_oh"][f_best], member_oh, member_sorted)
    return _pack_bitset(member, B)


def split_scan_cost(F: int, B: int, leaves: int = 1):
    """Analytical (FLOPs, bytes) of ``best_split`` over ``leaves`` leaf
    scans: ~a few dozen elementwise ops per [F, B] cell (prefix sums,
    gain formula, constraint masks — the constant is an empirical op
    count, not a derivation): how much of the non-kernel wave time the
    split scans could explain (docs/ROOFLINE.md's "everything-but-kernel"
    hypothesis).  No caller in the program."""
    ops_per_cell = 48.0
    flops = ops_per_cell * leaves * F * B
    nbytes = float(leaves) * F * B * 3 * 4 * 2
    return flops, nbytes


def partition_cost(N: int, splits: int = 1, passes: int = None):
    """Analytical (FLOPs, HBM bytes) of applying ``splits`` committed
    splits to the ``leaf_id: i32[N]`` row-partition vector in ``passes``
    passes over the rows — ``wave_kernel_cost``'s sibling for the
    NON-kernel side of the wave loop.

    A split reads its one bin column (1 byte a row); a pass reads and
    writes ``leaf_id`` (4 + 4 bytes a row):

        bytes = N * (splits + 8 * passes),  ~12 ops a row and split

    The wave grower's batched phase makes ONE pass for all the splits it
    committed (``core/wave_grower.py build_split_apply_fn``: 13-14 passes
    for a tree's 254 splits, ``WaveCounts.route_passes``); where every
    split is a walk of its own (the sequential oracle
    ``build_split_route_fn``, the XLA growers) ``passes`` is ``splits``,
    the default, and a split costs 9 bytes a row.  That is the floor, not
    what the chip read of the XLA walk: 0.066 ns a walked row, 54 bytes at
    819 GB/s, because ``u8 [F, N]`` tiles interleave the columns (ledger,
    PR 34; PERF.md 6, PR 35).  Until PR 27 the batched path was one pass
    PER WAVE of eleven per-row gathers, and a gathered element costs 3-4 ns
    whatever its width (39.8 ns a row-pass; ledger, PR 24): a cost model
    of such a path counts gathered ELEMENTS at nanoseconds each before it
    counts bytes.

    The op constant is an empirical tally, not a derivation — same
    contract as ``split_scan_cost``.  No caller in the program: the
    device trace's ``lgbm/wave_partition`` scope measures the walks.
    """
    splits = float(max(int(splits), 1))
    passes = splits if passes is None else float(max(int(passes), 1))
    return 12.0 * splits * N, (splits + 8.0 * passes) * N


def hist_quant_tolerance(counts, s_g, s_h, headroom: float = 1.01):
    """Per-bin |Δ| tolerances ``(tol_g, tol_h)`` between a QUANTIZED
    histogram (``tpu_hist_dtype=int16|int8``, dequantized by the kernel
    before this scan consumes it) and the f32 oracle histogram.

    The split scan is where the dequantized sums are actually consumed
    (``best_split`` runs on value units), so this is the layer that owns
    the accuracy contract: each row's stochastic-rounded g is within one
    quantization step ``s_g`` of its f32 value and the integer
    accumulation is exact, so a bin of ``counts`` rows deviates by at
    most ``counts * s_g`` (ops/pallas_hist.quant_error_bound), times a
    small ``headroom`` for f32 accumulation rounding past 2^24.  Count
    channels carry exact 0/1 weights in every mode — zero tolerance.
    tests/test_hist_quant.py asserts the kernel against these bounds."""
    from ..ops.pallas_hist import quant_error_bound
    tol_g = quant_error_bound(counts, s_g) * headroom
    tol_h = quant_error_bound(counts, s_h) * headroom
    return tol_g, tol_h


def tree_health_stats(tree) -> jnp.ndarray:
    """Device-side reduction of a grown tree's numeric-health invariants
    (obs/health.py's gain/histogram tap — one small fetch per tree).

    Every quantity here flows from the histogram channels: split gains
    from the scan above, leaf weights/counts from the g/h/c sums the
    growers thread through parent-minus-child subtraction.  Two invariant
    families are reduced:

    - finiteness of split gains and of leaf/internal values and weights
      over the ACTIVE nodes/leaves (unused fixed-capacity slots are
      zero-filled by construction and excluded);
    - conservation: the leaves of a split tree partition the root, so
      ``sum(leaf_count) == internal_count[0]`` (exact — counts ride the
      f32 histogram count channel) and ``sum(leaf_weight) ~=
      internal_weight[0]`` (f32/2xbf16 accumulation tolerance), the
      cheapest end-to-end check that histogram totals were not corrupted
      anywhere in the wave/serial growth pipeline.

    Returns f32 [10]: [n_bad_gain, n_bad_value, n_bad_weight,
    first_bad_node, first_bad_feature, leaf_count_sum, root_count,
    leaf_weight_sum, root_weight, num_leaves].
    """
    nl = tree.num_leaves
    n = tree.split_gain.shape[0]
    node_act = jnp.arange(n) < (nl - 1)
    leaf_act = jnp.arange(tree.leaf_value.shape[0]) < nl
    bad_gain = node_act & ~jnp.isfinite(tree.split_gain)
    bad_val = ((leaf_act & ~jnp.isfinite(tree.leaf_value)) |
               jnp.pad(node_act & ~jnp.isfinite(tree.internal_value),
                       (0, tree.leaf_value.shape[0] - n)))
    bad_w = ((leaf_act & ~jnp.isfinite(tree.leaf_weight)) |
             jnp.pad(node_act & ~jnp.isfinite(tree.internal_weight),
                     (0, tree.leaf_weight.shape[0] - n)))
    first_bad = jnp.argmax(bad_gain).astype(jnp.int32)
    f32 = jnp.float32
    return jnp.stack([
        jnp.sum(bad_gain).astype(f32),
        jnp.sum(bad_val).astype(f32),
        jnp.sum(bad_w).astype(f32),
        first_bad.astype(f32),
        tree.split_feature[first_bad].astype(f32),
        jnp.sum(jnp.where(leaf_act, tree.leaf_count, 0)).astype(f32),
        tree.internal_count[0].astype(f32),
        jnp.sum(jnp.where(leaf_act, tree.leaf_weight, 0.0)),
        tree.internal_weight[0],
        nl.astype(f32),
    ])


def has_categorical(meta: DeviceMeta) -> bool:
    """Whether ``meta`` declares a categorical feature, as a static flag:
    True where ``meta`` is a tracer (safe: the categorical gains only apply
    where ``is_categorical``)."""
    try:
        return bool(np.any(np.asarray(meta.is_categorical)))
    except jax.errors.TracerArrayConversionError:
        return True


@jax.named_scope("lgbm/split_scan")
def best_split(hist, sum_g, sum_h, cnt, meta: DeviceMeta, cfg: SplitConfig,
               min_constraint, max_constraint, feature_mask=None,
               has_cat=None, penalty_sub=None) -> BestSplit:
    """Find the best (feature, threshold) split of one leaf.

    hist: f32 [F, B, 3]; sum_g/sum_h/cnt: leaf totals (scalars).
    min/max_constraint: monotone value window for this leaf (scalars).
    feature_mask: optional bool [F] — feature_fraction sampling.
    has_cat: static flag gating the categorical search; None derives it from
    ``meta`` when concrete (callers whose meta is a tracer — e.g. the
    feature-parallel grower's per-device block slice — must pass it).
    penalty_sub: optional f32 [F] additive gain penalty per feature — CEGB's
    DeltaGain (reference: cost_effective_gradient_boosting.hpp:50-61),
    subtracted from every candidate of that feature before the argmax.
    """
    if has_cat is None:
        has_cat = has_categorical(meta)
    F, B, _ = hist.shape
    g = hist[..., 0]
    h = hist[..., 1]
    c = hist[..., 2]
    bins = jnp.arange(B, dtype=jnp.int32)[None, :]           # [1, B]
    nb = meta.num_bins[:, None]                              # [F, 1]
    missing = meta.missing_types[:, None]
    valid_bin = bins < nb

    use_both = (nb > 2) & (missing != MISSING_NONE)          # [F, 1]
    skip_zero = use_both & (missing == MISSING_ZERO) & (bins == meta.default_bins[:, None])
    nan_bin_idx = nb - 1
    skip_nan = use_both & (missing == MISSING_NAN) & (bins == nan_bin_idx)
    acc = (valid_bin & ~skip_zero & ~skip_nan).astype(jnp.float32)

    gm, hm, cm = g * acc, h * acc, c * acc
    total_h = sum_h + 2.0 * K_EPSILON
    parent_gain = leaf_split_gain(sum_g, total_h, cfg)
    min_gain_shift = parent_gain + cfg.min_gain_to_split

    # ---- dir = +1 (left-to-right; missing/defaults land right) -----------
    lg1 = jnp.cumsum(gm, axis=1)
    lh1 = jnp.cumsum(hm, axis=1) + K_EPSILON
    lc1 = jnp.cumsum(cm, axis=1)
    rg1, rh1, rc1 = sum_g - lg1, total_h - lh1, cnt - lc1
    t_ok1 = bins <= nb - 2

    # ---- dir = -1 (right-to-left; missing/defaults land left) ------------
    # right side at threshold t accumulates bins t+1..B-1
    suff_g = jnp.cumsum(gm[:, ::-1], axis=1)[:, ::-1]
    suff_h = jnp.cumsum(hm[:, ::-1], axis=1)[:, ::-1]
    suff_c = jnp.cumsum(cm[:, ::-1], axis=1)[:, ::-1]
    zeros = jnp.zeros((F, 1), dtype=jnp.float32)
    rg2 = jnp.concatenate([suff_g[:, 1:], zeros], axis=1)
    rh2 = jnp.concatenate([suff_h[:, 1:], zeros], axis=1) + K_EPSILON
    rc2 = jnp.concatenate([suff_c[:, 1:], zeros], axis=1)
    lg2, lh2, lc2 = sum_g - rg2, total_h - rh2, cnt - rc2
    # threshold range: t <= num_bin - 2 - (NaN scan exclusion)
    na_excl = (use_both & (missing == MISSING_NAN)).astype(jnp.int32)
    t_ok2 = bins <= nb - 2 - na_excl

    monotone = meta.monotone[:, None]

    penalties = meta.penalties[:, None]

    def _gains(lg, lh, lc, rg, rh, rc, t_ok):
        data_ok = ((lc >= cfg.min_data_in_leaf) & (rc >= cfg.min_data_in_leaf)
                   & (lh >= cfg.min_sum_hessian_in_leaf)
                   & (rh >= cfg.min_sum_hessian_in_leaf))
        gain = _split_gains(lg, lh, rg, rh, cfg, min_constraint, max_constraint,
                            monotone)
        ok = t_ok & data_ok & (gain > min_gain_shift)
        # reported gain is shifted then penalty-scaled (reference:
        # FindBestThresholdNumerical tail + FindBestThreshold penalty)
        return jnp.where(ok, (gain - min_gain_shift) * penalties, NEG_INF)

    gains1 = _gains(lg1, lh1, lc1, rg1, rh1, rc1, t_ok1)
    gains2 = _gains(lg2, lh2, lc2, rg2, rh2, rc2, t_ok2)

    # features with a single scan use dir=-1 only (reference:
    # feature_histogram.hpp:104-111); disable dir=+1 there
    gains1 = jnp.where(use_both, gains1, NEG_INF)
    # categorical features are handled by best_split_categorical
    is_num = ~meta.is_categorical[:, None]
    gains1 = jnp.where(is_num, gains1, NEG_INF)
    gains2 = jnp.where(is_num, gains2, NEG_INF)
    if feature_mask is not None:
        fm = feature_mask[:, None]
        gains1 = jnp.where(fm, gains1, NEG_INF)
        gains2 = jnp.where(fm, gains2, NEG_INF)

    # ---- per-feature best with reference-faithful tie order --------------
    # per feature the reference tries dir=-1 first (high t to low), then
    # dir=+1 (low t to high), keeping the FIRST strict max; across features
    # lower index wins.  Flatten as [F, (rev dir-1 block, dir+1 block)].
    stacked = jnp.concatenate([gains2[:, ::-1], gains1], axis=1)  # [F, 2B]
    within_f = jnp.argmax(stacked, axis=1).astype(jnp.int32)      # [F]
    feat_gain = jnp.take_along_axis(stacked, within_f[:, None], 1)[:, 0]

    # ---- categorical candidates (skipped entirely when the dataset has
    # none — ``has_cat`` is static) ----------------------------------------
    W = bitset_words(B)
    if has_cat:
        # a scope of its own: what it times is the categorical search, and
        # lgbm/split_scan beside it the numeric scan alone
        with jax.named_scope("lgbm/cat_scan"):
            cat = _categorical_best(g, h, c, sum_g, sum_h, cnt, meta, cfg,
                                    min_constraint, max_constraint,
                                    min_gain_shift)
        cat_gain = jnp.where(cat["gain"] > NEG_INF,
                             (cat["gain"] - min_gain_shift) * meta.penalties,
                             NEG_INF)
        feat_gain = jnp.where(meta.is_categorical, cat_gain, feat_gain)
    if feature_mask is not None:
        feat_gain = jnp.where(feature_mask, feat_gain, NEG_INF)
    if penalty_sub is not None:
        feat_gain = jnp.where(feat_gain > NEG_INF,
                              feat_gain - penalty_sub, NEG_INF)

    f_best = jnp.argmax(feat_gain).astype(jnp.int32)
    best_gain = feat_gain[f_best]

    # ---- numerical payload at the winner ---------------------------------
    within = within_f[f_best]
    is_dir2 = within < B
    t_best = jnp.where(is_dir2, B - 1 - within, within - B).astype(jnp.int32)

    # default_left: dir=-1 => True; single-scan features: True unless the
    # 2-bin NaN fixup forces False (reference: feature_histogram.hpp:106-110)
    feat_missing = meta.missing_types[f_best]
    feat_use_both = (meta.num_bins[f_best] > 2) & (feat_missing != MISSING_NONE)
    default_left = jnp.where(
        feat_use_both, is_dir2,
        feat_missing != MISSING_NAN)

    pick = lambda a1, a2: jnp.where(is_dir2, a2[f_best, t_best], a1[f_best, t_best])
    left_g = pick(lg1, lg2)
    left_h = pick(lh1, lh2) - K_EPSILON
    left_c = pick(lc1, lc2)
    left_out = jnp.clip(leaf_output(left_g, left_h, cfg),
                        min_constraint, max_constraint)
    right_out = jnp.clip(leaf_output(sum_g - left_g, sum_h - left_h, cfg),
                         min_constraint, max_constraint)
    cat_bitset = jnp.zeros((W,), dtype=jnp.uint32)

    # ---- swap in the categorical payload when a categorical feature won --
    if has_cat:
        win_cat = meta.is_categorical[f_best]
        sel = lambda cv, nv: jnp.where(win_cat, cv, nv)
        t_best = sel(jnp.int32(0), t_best)
        default_left = sel(False, default_left)
        left_g = sel(cat["left_g"][f_best], left_g)
        left_h = sel(cat["left_h"][f_best], left_h)
        left_c = sel(cat["left_c"][f_best], left_c)
        left_out = sel(cat["left_out"][f_best], left_out)
        right_out = sel(cat["right_out"][f_best], right_out)
        with jax.named_scope("lgbm/cat_scan"):
            cat_bitset = jnp.where(win_cat,
                                   _cat_winner_bitset(cat, f_best, B),
                                   cat_bitset)

    found = best_gain > NEG_INF
    return BestSplit(
        gain=best_gain.astype(jnp.float32),
        feature=jnp.where(found, f_best, -1).astype(jnp.int32),
        threshold=jnp.where(found, t_best, 0).astype(jnp.int32),
        default_left=default_left,
        left_g=left_g, left_h=left_h, left_c=left_c,
        left_out=left_out, right_out=right_out,
        cat_bitset=cat_bitset,
    )
