"""Pallas TPU histogram kernel — the ConstructHistogram replacement.

The reference's hottest loop gathers bins and accumulates (g, h, count)
per bin with scalar code (reference: src/io/dense_bin.hpp:71-135) or
workgroup atomics (reference: src/treelearner/ocl/histogram256.cl:350).
TPUs have no fast scatter, so this kernel turns accumulation into MXU
matmuls with the one-hot factor built directly in VMEM — it never touches
HBM, unlike the XLA fallback in core/histogram.py which materializes
one-hot tiles.

Channel packing: the MXU processes 128 output lanes per pass regardless of
how many are used, so the kernel accumulates ``C=128`` weight channels at
once.  Two channel layouts exist:

* ``packed`` (the default fast path): a pass is
  one one-hot contraction ``[B, BR] x [BR, 128]`` a feature whose lanes
  are kind-major over ``pass_leaves(mode)`` leaves.  In the split modes
  ("2xbf16", "int16") a leaf takes FIVE lanes, one in each of ``[g hi |
  h hi | count | g lo | h lo]``: 25 leaves a pass (125 lanes).  Each pass
  accumulates over the row blocks in a VMEM scratch of its own; on the
  last row step the hi and lo sums are added by one lane rotate and the
  passes' ``[g | h | count]`` laid into the two result arrays.  The count's
  weights are the 0/1 bag mask, exact in bf16 with f32 accumulation.  In
  the single-value modes ("bf16", "int8") a leaf takes three lanes, 42
  leaves a pass.  A launch runs ``ceil(leaves / pass_leaves)`` passes, 1
  to 3 (1 to 2), read off the slots it was given: the count is a
  prefetched scalar and each pass count's whole step sits under one
  ``pl.when``, so a launch over one leaf (the root's, over every row)
  pays one pass where the lane-pair layout this replaced paid three
  whatever it held.  Capacity stays 63 leaves a launch
  (``P_MAX_PACKED``); ``packed_lanes`` says where the two result arrays
  keep each slot, ``pack_lanes`` / ``unpack_lanes`` turn per-leaf
  histograms into that order and back.  Under "highest" the f32 pass is
  not split: g and h in the two halves of one pass at
  ``Precision.HIGHEST`` and the count in a bf16 pass of its own, whatever
  the launch holds.
* ``triple`` (the differential oracle): (g*m, h*m, m) triples for up to
  42 leaf masks — the original layout, kept for packed-vs-triple
  differential testing and for the mixed-width XLA side-pass, which
  speaks this layout.

Quantized accumulation (``tpu_hist_dtype=int16|int8`` — LightGBM 4.x's
quantized-training trick, Shi et al.): g/h arrive as stochastic-rounded
INTEGERS under per-tree symmetric scales (``stochastic_round`` below;
the grower computes scales on device from the global |g|/|h| maxima).
The integer values are fed to the MXU exactly — int16 as an exact hi/lo
bf16 split (|hi/256| <= 129 and lo in [0, 255] are both exactly
representable in bf16's 8-bit mantissa), int8 as one exact bf16 pass —
so accumulation is INTEGER-exact up to f32's 2^24 mantissa, layout- and
shard-independent, and the fused sibling subtraction runs in integer
units (bit-identical to the XLA oracle by construction).  The f32
dequant (value = sum * scale per channel) happens downstream at
split-scan time in the wave grower, the one place the sums are
consumed as values.
The HBM win: the per-row vector stream shrinks from [N, 4] f32 (16 B)
to [N, 4] int16 (8 B), and with the fused gradient pass the f32 g/h
arrays never round-trip HBM at all (``grad_stream_bytes`` models both legs).

Sibling fusion: with a ``parent`` operand the kernel also emits
parent-minus-child sibling histograms from the same ``pallas_call`` —
the parent block is read into VMEM once per feature block and the
sibling written on the final row step, eliminating the separate XLA
subtraction pass and its extra [F, B, C] HBM round-trip per wave
(reference: serial_tree_learner.cpp:567 subtracts the smaller child
from the parent the same way).

Data layout: bins are FEATURE-MAJOR ``[F, N]`` uint8 (the TPU-native
resident layout — per-feature column access is a contiguous row slice, and
the uint8 32-sublane tile constraint lands on the feature axis).  A
column's rows therefore lie along LANES, and the one-hot factor is built
that way round (``_onehot_t``: bins on sublanes, rows on lanes, a sublane
broadcast and a compare), which makes the contraction a plain ``[B, BR] @
[BR, C]`` matmul with the channel matrix as the MXU's stationary operand.
Built the other way round (rows on sublanes, contracted over dimension 0)
the column needs a lane-to-sublane relayout and the factor a transpose a
feature and row block, and that XLU work, not the MXU, bounded the kernel
until PR 33 (PERF.md 6).

Per grid step (j=feature block, i=row block):
  bins block  [FB, BR]   uint8 (every column's [F, BR] where FB is neither
                         whole sublane tiles nor all the columns: the
                         step converts its own rows j FB.. to i32)
  gh block    [BR, C]    bf16/f32 (pre-masked channels), built from the
                         [BR, 4] row vectors
  acc block   [FB, B, C] f32 a pass (VMEM scratch), accumulated across the
                         i sweep; the outputs are written on its last step
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# channel capacity: one MXU lane pass
C_MAX = 128
_DEF_BR = 1024
_DEF_FB = 32  # uint8 sublane tile
# wave capacity per layout: triple = 3 lanes/leaf; packed = 63 (what the
# lane-pair layout held, kept: it decides which splits a body commits),
# three passes of 25 or two of 42 (``pass_leaves``)
P_MAX_TRIPLE = C_MAX // 3       # 42
P_MAX_PACKED = C_MAX // 2 - 1   # 63
# What select_wave_blocks lets one grid step's blocks take, counted once
# each: the child, parent and sibling blocks and the streamed operands.
# It picks the feature block, 8 fused and 32 unfused at B=256; what a
# launch asks of VMEM is ``wave_vmem_bytes`` (the pipeline's double
# buffers, the passes' scratch accumulators, the body's temporaries): 31
# MiB fused at 8, 47 unfused at 32, over the compiler's default scoped
# limit, so ``hist_pallas_wave`` passes it as ``vmem_limit_bytes``.
_VMEM_BUDGET = 10 * 2 ** 20
# What ``wave_feature_blocks`` lets a widened block's launch ask for: the
# 128 MiB of VMEM a v5e core has (jax's pallas tpu_info), less room for
# what the compiler adds beside the blocks.  A fused block of HIGGS's 28
# columns at B=256 asks for 71 MiB.
_VMEM_CAP = 100 * 2 ** 20
# A grid step's fixed work in one-pass contractions of one feature, what
# ``wave_feature_blocks`` weighs a block by: fitted to one launch at MSLR's
# shape on the v5e, blocks of 28 and of 36 against blocks of 8 at one pass
# (2.4 each; 3.3 at two passes, 3.4 at three: PERF.md 6)
_BLOCK_COST = 2.4


def wave_capacity_max(packed: bool) -> int:
    """Leaves one kernel launch can histogram under the given layout."""
    return P_MAX_PACKED if packed else P_MAX_TRIPLE


def pass_leaves(mode) -> int:
    """Leaves one MXU pass of the ``packed`` layout carries: 25 at five
    lanes a leaf (the split modes), 42 at three (one value a channel), and
    the whole launch under "highest", whose f32 pass is not split."""
    mode = _resolve_mode(mode)
    if mode == "highest":
        return P_MAX_PACKED
    return C_MAX // (5 if mode in ("2xbf16", "int16") else 3)


def wave_mxu_passes(n_leaves, mode, packed: bool):
    """bf16 passes of the MXU a feature and row block that a launch over
    ``n_leaves`` pending leaves (a Python or traced int) is charged: what
    ``WaveCounts.kernel_pass_rows`` multiplies the tier by.  The packed
    layout pays by the leaves it holds, ``ceil(n_leaves / pass_leaves)``;
    "highest" and the triple layout pay the same whatever they hold."""
    mode = _resolve_mode(mode)
    if not packed:
        return WAVE_MXU_PASSES[mode]
    if mode == "highest":
        return WAVE_MXU_PASSES[mode] + 1
    per = pass_leaves(mode)
    return jnp.clip((n_leaves + per - 1) // per, 1, -(-P_MAX_PACKED // per))


def packed_lanes(mode) -> np.ndarray:
    """Where the packed result keeps slot s's (sum_g, sum_h, count): i32
    [3, P_MAX_PACKED] of lanes in ``concatenate([gh, cnt], -1)``.

    A pass is kind-major, ``per = pass_leaves(mode)`` lanes a kind, and
    comes out folded to ``[g | h | count]`` in its first ``3 per`` lanes.
    Pass 0 lands in ``gh``, pass 1 in ``cnt``; a third pass (the split
    modes' leaves 50..62) puts g and h into ``gh``'s lanes past the first
    pass and its counts into ``cnt``'s.  "highest" keeps g and h in the
    two halves of ``gh`` and the counts in ``cnt``."""
    s = np.arange(P_MAX_PACKED)
    if _resolve_mode(mode) == "highest":
        return np.stack([s, C_MAX // 2 + s, C_MAX + s]).astype(np.int32)
    per = pass_leaves(mode)
    p, j = s // per, s % per
    base = np.where(p == 1, C_MAX, 0)
    g = np.where(p < 2, base + j, 3 * per + j)
    h = np.where(p < 2, base + per + j, 4 * per + j)
    c = np.where(p < 2, base + 2 * per + j, C_MAX + 3 * per + j)
    return np.stack([g, h, c]).astype(np.int32)


def _runs(idx):
    """``idx`` (1-d ints) as maximal ``(start, stop)`` runs of consecutive
    values, in order."""
    idx = np.asarray(idx)
    cuts = np.flatnonzero(np.diff(idx) != 1) + 1
    return [(int(r[0]), int(r[-1]) + 1) for r in np.split(idx, cuts)]


def gather_lanes(res, mode, P: int):
    """The packed result ``(gh, cnt)`` (each ``[F, B, C_MAX]``) with the
    lanes of slots 0..P-1 alone, kind-major: ``[F, B, 3 * P]`` = ``[g | h |
    count]``.  Slices of whole lane runs, no gather."""
    cat = jnp.concatenate(res, axis=-1)
    return jnp.concatenate(
        [cat[..., a:b] for row in packed_lanes(mode)
         for a, b in _runs(row[:P])], axis=-1)


def unpack_lanes(res, mode, P: int):
    """The packed result as per-leaf histograms ``[P, F, B, 3]``."""
    x = gather_lanes(res, mode, P)
    return x.reshape(*x.shape[:2], 3, P).transpose(3, 0, 1, 2)


def pack_lanes(hist, mode):
    """Per-leaf histograms ``[P, F, B, 3]`` as the packed ``(gh, cnt)`` pair
    the kernel takes for ``parent`` (``unpack_lanes``' inverse; lanes that
    hold no slot are zero)."""
    P = hist.shape[0]
    src = np.full(2 * C_MAX, -1)                 # lane -> kind * P + slot
    lanes = packed_lanes(mode)[:, :P]
    src[lanes.reshape(-1)] = np.arange(3 * P)
    flat = hist.transpose(1, 2, 3, 0).reshape(*hist.shape[1:3], 3 * P)
    pieces, lane = [], 0
    for a, b in _runs(np.flatnonzero(src >= 0)):
        if a > lane:
            pieces.append(jnp.zeros((*flat.shape[:2], a - lane), flat.dtype))
        pieces += [flat[..., c:d] for c, d in _runs(src[a:b])]
        lane = b
    pieces.append(jnp.zeros((*flat.shape[:2], 2 * C_MAX - lane), flat.dtype))
    cat = jnp.concatenate(pieces, axis=-1)
    return cat[..., :C_MAX], cat[..., C_MAX:]


# quantized-accumulation modes (tpu_hist_dtype) and their symmetric
# integer range: q in [-QMAX, QMAX], scale = max|x| / QMAX per tree
QUANT_MODES = ("int16", "int8")
QUANT_QMAX = {"int16": 32767.0, "int8": 127.0}


def stochastic_round(x, seed=0):
    """Value-hash stochastic rounding to integers: ``floor(x + u(x))``
    with ``u`` in [0, 1) derived from the float's own bit pattern mixed
    with ``seed`` (two rounds of a murmur-style finalizer).

    Properties the quantized path relies on:
      * deterministic under a fixed seed (the satellite test pins it);
      * value-based, not position-based — a row's rounding depends only
        on its gradient VALUE, so data-parallel shards quantize
        identically to the single-device run (mesh-parity for free);
      * exact zeros stay zero (``floor(0 + u) == 0`` for u < 1), so
        bag-masked rows never leak quantization noise;
      * the result is always floor(x) or ceil(x).

    ``seed`` may be a Python int or a traced uint32 scalar."""
    xf = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(xf, jnp.uint32)
    z = bits ^ jnp.uint32(seed)
    z = (z ^ (z >> 16)) * jnp.uint32(0x7FEB352D)
    z = (z ^ (z >> 15)) * jnp.uint32(0x846CA68B)
    z = z ^ (z >> 16)
    u = (z >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    return jnp.floor(xf + u)


def quant_error_bound(counts, scale):
    """Analytic per-bin bound on |dequantized − f32| histogram deltas:
    each row's stochastic-rounded value is within one quantization step
    of its f32 value, and the integer accumulation is exact, so a bin
    accumulating ``counts`` rows is off by at most ``counts * scale``
    (plus f32 accumulation rounding, covered by the 1.01 headroom the
    differential suite applies).  The contract tests/test_hist_quant.py
    asserts against the kernel."""
    return np.asarray(counts, np.float64) * float(scale)


def feat_pack(B: int, FB: int) -> int:
    """Features whose one-hot factors share one MXU pass (B <= 64)."""
    pack = max(1, 128 // B)
    return pack if 128 % B == 0 and FB % pack == 0 else 1


def wave_feature_blocks(B: int, F: int, feat_block: int, *, mode,
                        packed: bool, fused: bool, block_rows: int,
                        windowed: bool = False) -> tuple:
    """``(FB, pack, Fp)`` of a launch over ``F`` columns of ``B`` bins at a
    feature block of ``feat_block``: the features a grid step covers, those
    whose one-hot factors share an MXU pass, and the columns the launch
    covers, ``F`` padded to whole blocks.  The block is cut to the columns
    there are.

    A block under the uint8 sublane tile (``_DEF_FB``; 8 fused at B=256)
    over more columns than it covers becomes the block of whole loop
    groups (the features ``_unroll`` writes out in a row, so the loop
    keeps its body: four in the modes of more than one contraction) whose
    launch (``wave_vmem_bytes``) fits ``_VMEM_CAP`` and covers the columns
    at the least cost: a column, zero columns included, one contraction
    a pass, and a block ``_BLOCK_COST`` more, the fixed work a grid step
    does once a feature block (the row vectors read, a row block's operands
    built).  So HIGGS's 28 columns run as one block of 28 and MSLR's 136 as
    five of 28 (four of them zeros), where blocks of 8 build the operands
    four and seventeen times (PERF.md 6).  A block of a whole tile or more
    is the 64-lane pick (and the unfused one), whose launches the
    benchmark's configurations pin; there a block that would pad the
    columns becomes all ``F`` columns only if that keeps the loop body (the
    pack and the features ``_unroll`` writes out in a row) and fits the
    cap.  A ``windowed`` launch (the feature-parallel learner's) deals
    whole blocks over the chips and keeps them.  ``hist_pallas_wave``
    launches by it and ``core/plan.py KernelShape`` says it: one rule, so
    neither can go stale."""
    F = int(F)
    FB = min(int(feat_block), max(F, 1))
    most = _contractions(_resolve_mode(mode), packed)[1]

    def fits(fb):
        return (wave_vmem_bytes(B, fb, block_rows, mode, packed, fused)
                <= _VMEM_CAP)

    if not windowed and FB < _DEF_FB and F > FB:
        group = feat_pack(B, FB) * (_UNROLL if most == 1 else _UNROLL // 2)
        FB = min((fb for fb in range(group, F + group, group) if fits(fb)),
                 key=lambda fb: -(-F // fb) * (fb + _BLOCK_COST))
    elif not windowed and F % FB:
        def body(fb):
            return feat_pack(B, fb), _unroll(fb // feat_pack(B, fb), most)

        if body(F) == body(FB) and fits(F):
            FB = F
    return FB, feat_pack(B, FB), -(-F // FB) * FB


# features whose one-hot and contractions are written out in a row: eight
# where a feature runs one contraction, four where it runs more; a feature
# block beyond that loops over groups of as many.  The program's size is
# what this trades: a block of 28 or 32 written out whole, three pass
# counts of it, is 100k bundles and ran 3x slower than the loop on the
# v5e, and what is written out is traced and lowered once a tier, 22 times
# a growth program, in every process, warm cache or not: a second of the
# first call for each eighth of a second an instance (PERF.md 6, PR 33)
_UNROLL = 8


def _unroll(steps: int, contractions: int = 1):
    """``(u, looped)``: the features of a block's ``steps`` written out in
    a row (a divisor of ``steps``), and whether the rest is a loop."""
    most = _UNROLL if contractions == 1 else _UNROLL // 2
    if steps <= most:
        return steps, False
    return max(d for d in range(1, most + 1) if steps % d == 0), True


def _feature_loop(bins_ref, bins32_ref, FB: int, pack: int, feature,
                  contractions: int = 1, block=None):
    """``feature(row_of, f)`` for f = 0, pack, 2 pack, ... < FB, where
    ``row_of(f)`` is column f of the block as i32 ``[1, BR]``; by
    ``_unroll`` written out, or a ``fori_loop`` over groups that reads the
    i32 copy made in ``bins32_ref`` (a dynamic row of the packed u8 block
    cannot be sliced).  ``block`` (traced): ``bins_ref`` holds every column
    of the row block, and the copy takes this block's rows of it, a static
    slice under one ``pl.when`` a block."""
    steps = FB // pack
    u, looped = _unroll(steps, contractions)
    if block is None and not looped:
        for k in range(steps):
            feature(lambda f: bins_ref[f:f + 1, :].astype(jnp.int32),
                    k * pack)
        return
    if block is None:
        bins32_ref[...] = bins_ref[...].astype(jnp.int32)
    else:
        for b in range(bins_ref.shape[0] // FB):
            @pl.when(block == b)
            def _copy(b=b):
                bins32_ref[...] = bins_ref[b * FB:(b + 1) * FB, :].astype(
                    jnp.int32)

    def group(k, _):
        for d in range(u):
            feature(lambda f: bins32_ref[pl.ds(f, 1), :], (k * u + d) * pack)
    jax.lax.fori_loop(0, steps // u, group, None)


def _accumulate(ref, f, acc, B: int, pack: int):
    """``acc`` ``[pack * B, C]``, the sums of features f..f+pack-1, onto
    their blocks of ``ref`` ``[FB, B, C]``."""
    for q in range(pack):
        ref[f + q] += acc[q * B:(q + 1) * B]


def _onehot_t(row_of, f, B: int, pack: int, sub, dtype):
    """The one-hot factor of features f..f+pack-1 with the ROWS ALONG
    LANES, ``[pack * B, BR]``: bin b of feature f+q on sublane q*B + b.
    A column of the feature-major bins already has its rows along lanes,
    so this is a sublane broadcast and a compare against ``sub`` (the
    sublane iota, built once), and the contraction ``[pack*B, BR] @ [BR,
    C]`` is a plain matmul.  The factor with rows along sublanes, which
    this replaced, cost a lane-to-sublane relayout of the column and a
    transpose of the factor a feature and row block, and those (the XLU),
    not the MXU, bounded the kernel (PERF.md 6, PR 33).

    Feature packing: with B <= 64 a single feature's one-hot only spans B
    of the MXU's 128 rows; ``pack`` features' factors stacked in one
    operand fill them, so a max_bin=63 run really is ~4x cheaper than
    max_bin=255 (the reference's GPU backend has the same
    bins-per-workgroup economics and recommends 63 bins,
    docs/GPU-Performance.rst:128-130)."""
    row = row_of(f)
    for q in range(1, pack):
        row = jnp.where(sub >= q * B, row_of(f + q), row)
    return (row == (sub & (B - 1))).astype(dtype)       # B is 2^k


def _contractions(mode: str, packed: bool):
    """``(scratch accumulators, the most contractions a feature runs)`` of
    a layout: the packed layout's passes in the bf16 modes, each with an
    accumulator of its own; else the lo sums of the split modes beside the
    output block, and under "highest" the f32 contraction (and the packed
    layout's bf16 count pass) straight into the output blocks."""
    if packed and mode != "highest":
        n = -(-P_MAX_PACKED // pass_leaves(mode))
        return n, n
    if mode == "highest":
        return 0, 2 if packed else 1
    return (1, 2) if mode in ("2xbf16", "int16") else (0, 1)


def wave_vmem_bytes(B: int, FB: int, block_rows: int, mode, packed: bool,
                    fused: bool) -> int:
    """The VMEM a launch at a feature block of ``FB`` asks for
    (``vmem_limit_bytes``): the histogram blocks (inputs and outputs
    double-buffered, the scratch accumulators and the bins' i32 copy, where
    the block loops, once each), the streamed blocks (a ``[BR, 4]`` block is
    tiled to 128 lanes) and the step's temporaries (the one-hot factor, the
    passes' operands), with 8 MiB to spare.  The default scoped limit (16
    MiB on the v5e, of 128) refuses the unfused block of 32 features with
    three pass accumulators beside it."""
    mode = _resolve_mode(mode)
    n_out = 2 if packed else 1
    n_par = n_out if fused else 0
    n_res = n_out * (2 if fused else 1)
    n_acc, most = _contractions(mode, packed)
    n_scratch = n_acc + int(_unroll(FB // feat_pack(B, FB), most)[1])
    block = FB * B * C_MAX * 4
    return (block * (2 * (n_par + n_res) + n_scratch)
            + 2 * (FB * block_rows + block_rows * C_MAX * 4)
            + block_rows * C_MAX * 4 * 8 + max(B, C_MAX) * block_rows * 8
            + (8 << 20))


def _hist_wave_kernel(*refs, B: int, FB: int, mode: str, packed: bool,
                      fused: bool, windowed: bool = False, steps=None):
    """Multi-leaf histogram step.  The per-leaf channel matrix is built in
    VMEM from leaf_id + the slot->leaf map (``slot_ref``, a row a
    contraction's lanes; -1: no leaf), never touching HBM; a feature runs
    one to three one-hot contractions against it, each into an accumulator
    of its own, and the last row step folds them into the outputs.

    ``mode`` selects the matmul precision/throughput trade:
      "highest" - f32 operands at Precision.HIGHEST (~3 MXU passes);
      "2xbf16"  - hi/lo bf16 split of the channel values, two bf16
                  channels a value: the one-hot operand is exactly
                  representable in bf16 and accumulation is always f32, so
                  only g/h are rounded - to ~16 mantissa bits, tighter than
                  one bf16 pass and ~1.5x faster than "highest".  The hi
                  and lo sums are accumulated apart over the row blocks and
                  added on the last one, in every layout, so the layouts
                  stay bit-identical;
      "bf16"    - single bf16 channel (~8 mantissa bits on g/h).

    Layouts.  ``packed`` in the bf16 modes: a pass is kind-major over ``per
    = pass_leaves(mode)`` lanes a kind, ``[g hi | h hi | count | g lo |
    h lo]`` in the split modes and ``[g | h | count]`` in the single-value
    ones, one contraction a feature for every ``per`` pending leaves: the
    launch pays MXU passes by the leaves it holds (``npass_ref[0]``, scalar
    prefetch) and not by the most it could.  The whole feature loop sits
    under ``pl.when(npass == n)`` once for each n: straight-line code a
    pass count, which the scheduler packs as well as a kernel built for
    that count alone (PERF.md 6, PR 33).  On the last row step the hi and lo
    sums are added (one lane rotate a feature block and launch, where a
    fold a row block cost a rotate of ``[B, C]`` a feature and row block on
    the XLU that bounds this kernel) and the passes' ``[g | h | count]`` laid
    where ``packed_lanes`` says - pass 0 in ``gh``, pass 1 in ``cnt``, a
    third pass in the lanes those two leave free, every other lane zero.
    ``packed`` under "highest": g in the lower half of the lanes and h in
    the upper of one f32 contraction, 63 leaves, and the counts in a bf16
    one of their own, whatever the launch holds - the membership weights
    are the 0/1 bag mask, exact in bf16, and accumulation is f32, so the
    counts are bit-identical to dedicated count lanes.  Not packed (the
    oracle): (g, h, count) lane triples of 42 leaves; the split modes run
    the hi and the lo contraction over the same lanes.

    ``fused`` adds parent blocks as inputs and sibling blocks as outputs:
    on the final row step (the outputs now hold the full child histograms
    for this feature block) the sibling is written as parent - child
    straight from VMEM.

    Quantized modes ("int16" / "int8"): vecs arrive as int16 integers;
    int16 splits each value into an EXACT hi/lo bf16 pair (like 2xbf16 but
    with zero representation error), int8 is one exact bf16 channel.
    Everything - accumulators, emitted histograms, the fused sibling
    subtraction, and the parent operand - stays in INTEGER units:
    dequantization happens downstream at split-scan time
    (core/wave_grower.py), which keeps fused and unfused siblings
    bit-identical (an in-kernel dequant would let the compiler fuse
    ``parent - child*scale`` into an FMA whose rounding the separate XLA
    subtraction cannot reproduce).

    ``windowed``: a prefetched ``(first block, blocks owned)`` follows the
    pass count; a grid step past the blocks owned writes zeros on its last
    row step and computes nothing (its index maps hold the bins and row
    vectors the last owned step fetched, so nothing is fetched either)."""
    quant = mode in QUANT_MODES
    split = mode in ("2xbf16", "int16")
    by_pass = packed and mode != "highest"
    refs = list(refs)
    if windowed:
        # the grid's position is read out here: not under a ``pl.when``
        win_ref = refs.pop(1 if by_pass else 0)
        steps = (pl.program_id(1), pl.num_programs(1) - 1)
        own = pl.program_id(0) < win_ref[1]
        pl.when(own)(functools.partial(
            _hist_wave_kernel, *refs, B=B, FB=FB, mode=mode, packed=packed,
            fused=fused, steps=steps))
        n_out = 2 if packed else 1
        first = int(by_pass) + 3 + (n_out if fused else 0)

        @pl.when(~own & (steps[0] == steps[1]))
        def _unowned():
            for r in refs[first:first + n_out * (2 if fused else 1)]:
                r[...] = jnp.zeros_like(r)
        return
    npass_ref = refs.pop(0) if by_pass else None
    bins_ref, vecs_ref, slot_ref = refs[:3]
    n_out = 2 if packed else 1
    n_par = n_out if fused else 0
    par_refs = refs[3:3 + n_par]
    out_refs = refs[3 + n_par:3 + n_par + n_out]
    sib_refs = refs[3 + n_par + n_out:3 + 2 * n_par + n_out]
    scratch = refs[3 + 2 * n_par + n_out:]
    n_acc = _contractions(mode, packed)[0]
    accs = scratch[:n_acc]
    bins32_ref = scratch[n_acc] if len(scratch) > n_acc else None
    i = pl.program_id(1) if steps is None else steps[0]

    @pl.when(i == 0)
    def _init():
        for r in (*(() if by_pass else out_refs), *accs):
            r[...] = jnp.zeros_like(r)

    vecs = vecs_ref[...]                                  # [BR, 4]
    if quant:
        vecs = vecs.astype(jnp.int32)                     # int16 -> i32
    leaf = vecs[:, 3].astype(jnp.int32)[:, None]          # [BR, 1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, C_MAX), 1)
    # the row vector a lane carries: 0 g, 1 h, 2 the count weight
    if by_pass:
        per = pass_leaves(mode)
        kind = lane // per
        lo = kind >= 3
        src = jnp.where(lo, kind - 3, kind)
    else:
        src = lane // (C_MAX // 2) if packed else lane % 3
    vals = jnp.where(src == 0, vecs[:, 0][:, None],
                     jnp.where(src == 1, vecs[:, 1][:, None],
                               vecs[:, 2][:, None]))      # [BR, C]
    zero = 0 if quant else 0.0

    def halves(x):
        """(hi, lo) of a split mode's values, both exact in bf16.  int16:
        hi is a multiple of 256 with |hi| <= 33024 (|hi/256| <= 129 fits
        bf16's 8-bit mantissa), lo in [0, 255], so two channels accumulate
        the integer sum with no representation error at all."""
        hi = ((x >> 8) << 8) if quant else \
            x.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, x - hi

    if by_pass and mode == "2xbf16":
        vals = jnp.where(lo, halves(vals)[1], vals)   # the cast takes hi
    elif by_pass and split:
        hi, low = halves(vals)    # the count lanes' 0/1 weights go whole
        vals = jnp.where(lo, low, jnp.where(kind == 2, vals, hi))

    def operand(row, x, dtype=jnp.bfloat16):
        slot = slot_ref[row, :].astype(jnp.int32)[None, :]
        return jnp.where((leaf == slot) & (slot >= 0), x,
                         zero).astype(dtype)

    def contractions(n):
        """(accumulator, channel matrix, precision) of each contraction a
        feature runs; ``n``: the passes of this launch."""
        if by_pass:
            return [(accs[p], operand(p, vals), None) for p in range(n)]
        if mode == "highest":
            cs = [(out_refs[0], operand(0, vals, jnp.float32),
                   jax.lax.Precision.HIGHEST)]
            if packed:
                cs.append((out_refs[1], operand(1, vecs[:, 2][:, None]),
                           None))
            return cs
        if split:
            hi, low = halves(vals)
            return [(out_refs[0], operand(0, hi), None),
                    (accs[0], operand(0, low), None)]
        # int8: |q| <= 127 is exact in bf16 - one channel, zero error
        return [(out_refs[0], operand(0, vals), None)]

    pack = feat_pack(B, FB)
    # a block of every column (``hist_pallas_wave``'s ``whole``): this
    # step's feature block is rows j FB.. of it
    block = pl.program_id(0) if bins_ref.shape[0] > FB else None
    sub = jax.lax.broadcasted_iota(jnp.int32,
                                   (pack * B, bins_ref.shape[1]), 0)

    def run(n=0):
        cs = contractions(n)

        def feature(row_of, f):
            oh = _onehot_t(row_of, f, B, pack, sub, cs[0][1].dtype)
            for accr, w, precision in cs:
                _accumulate(accr, f, jnp.dot(
                    oh.astype(w.dtype), w, precision=precision,
                    preferred_element_type=jnp.float32), B, pack)
        _feature_loop(bins_ref, bins32_ref, FB, pack, feature, len(cs),
                      block)

    if by_pass:
        for n in range(1, n_acc + 1):
            pl.when(npass_ref[0] == n)(functools.partial(run, n))
    else:
        run()

    @pl.when(i == (pl.num_programs(1) - 1 if steps is None else steps[1]))
    def _last():
        if by_pass:
            lane2 = jax.lax.broadcasted_iota(jnp.int32, (FB * B, C_MAX), 1)
            done = []
            for accr in accs:
                x = accr[...].reshape(FB * B, C_MAX)
                if split:   # hi + lo: the lo kinds sit 3 per lanes above
                    x = x + jnp.where(lane2 < 2 * per,
                                      pltpu.roll(x, C_MAX - 3 * per, 1), 0.0)
                done.append(x)
            for r, outr in enumerate(out_refs):
                x = done[r]
                if split:
                    # past the pass's own 3 per lanes: the third pass's g
                    # and h (in gh), its counts (in cnt); nothing further up
                    third = pltpu.roll(done[2], (3 - 2 * r) * per, 1)
                    x = jnp.where(lane2 < 3 * per, x, jnp.where(
                        lane2 < (5 - r) * per, third, 0.0))
                outr[...] = x.reshape(FB, B, C_MAX)
        elif split:
            out_refs[0][...] += accs[0][...]
        # the outputs hold the complete child histograms of this feature
        # block: the sibling leaves without the child's round trip
        for par, outr, sibr in zip(par_refs, out_refs, sib_refs):
            sibr[...] = par[...] - outr[...]


def _resolve_mode(highest) -> str:
    """Back-compat: bool True -> "highest", False -> "bf16"; strings pass
    through ("highest" | "2xbf16" | "bf16" | "int16" | "int8")."""
    if isinstance(highest, str):
        assert highest in ("highest", "2xbf16", "bf16") + QUANT_MODES, \
            highest
        return highest
    return "highest" if highest else "bf16"


# MXU passes per precision mode (see _hist_wave_kernel): int16 is the
# exact hi/lo integer split (2 passes, like 2xbf16 but representation-
# error-free); int8 is one exact bf16 pass
WAVE_MXU_PASSES = {"highest": 3, "2xbf16": 2, "bf16": 1,
                   "int16": 2, "int8": 1}

# per-row bytes of the packed vector stream the kernel reads from HBM:
# [N, 4] f32 (g, h, count-weight, leaf) vs [N, 4] int16 quantized
_VEC_BYTES = {"highest": 16, "2xbf16": 16, "bf16": 16,
              "int16": 8, "int8": 8}


def grad_stream_bytes(n_rows, rows, mode="2xbf16",
                      fused_grad: bool = False):
    """Per-ITERATION HBM bytes of the gradient stream — the [N]-sized
    legs this pipeline exists to shrink, modeled separately from the
    bins/histogram legs so the quantized + fused-grad win is a checkable
    prediction (docs/ROOFLINE.md "gradient stream" table):

      * unfused: the objective writes g and h as [N] f32 (2*4*n), the
        quantize/pack pass reads them back (2*4*n) and writes the packed
        [N, 4] vector array (vec_bytes*n);
      * fused (the plan's ``fused_grad``): gradients are computed inside
        the same jit that quantizes and packs — the only [N] write is the
        vector array itself;
      * both pay the kernel's per-histogrammed-row vector read
        (vec_bytes per row over the tier-compacted ``rows`` total).

    int16+fused vs the PR 8 2xbf16+unfused baseline at the HIGGS bench
    shape is a ~2.3x byte cut (the >= 1.5x acceptance bar,
    tests/test_hist_quant.py pins it)."""
    mode = _resolve_mode(mode)
    vb = _VEC_BYTES[mode]
    pack_legs = float(n_rows) * (vb if fused_grad else (8 + 8 + vb))
    return pack_legs + float(rows) * vb


def wave_kernel_cost(rows, F: int, B: int, mode="2xbf16",
                     feat_block: int = _DEF_FB, waves: int = 1,
                     packed: bool = False, fused: bool = False,
                     fused_grad: bool = False, n_rows=None, pass_rows=None):
    """Analytical (FLOPs, HBM bytes) of ``hist_pallas_wave`` over ``rows``
    total rows across ``waves`` kernel launches — ``docs/ROOFLINE.md``'s
    hand-written cost model in code, the numbers the doc quotes
    (``benchmarks/harness/costs.py`` keeps a copy for the device trace's
    charged-FLOP rate).

    FLOPs are what the MXU is CHARGED, not useful work: the one-hot
    operand is 255/256 zeros but every lane is paid for.  Mirrors the
    kernel's feature packing (B <= 64 packs 128//B features per matmul);
    an unpacked B < 128 operand still occupies one full 128-lane group.
    ``pass_rows`` is what the program counted
    (``WaveCounts.kernel_pass_rows``: each launch's rows times the passes
    it ran, ``wave_mxu_passes``); without it every launch is charged as a
    full one, ``rows`` times the passes of ``wave_capacity_max`` leaves
    (the mode's g/h passes and the count's: three in the split modes,
    which is also what three passes of 25 leaves come to).
    Bytes count the HBM legs only — bins + packed [N, 4] vectors read
    once per ROW, the histogram outputs written once per LAUNCH (hence
    ``waves``; two output arrays when packed); ``fused`` adds the parent
    read and sibling write per launch, and is what REPLACES the separate
    XLA subtraction pass (which paid the same parent/sibling legs PLUS a
    re-read of the child).  The one-hot factor lives in VMEM and never
    touches HBM.  ``rows`` is the tier-compacted total (the wave
    grower's ``report_waves`` stats carry exactly this figure).

    Quantized modes ("int16"/"int8") charge their exact-integer MXU
    passes (2 / 1, see ``WAVE_MXU_PASSES``) and halve the per-row
    vector-stream bytes ([N, 4] int16 vs f32).  With ``n_rows`` given
    the model additionally charges the per-iteration gradient legs
    (``grad_stream_bytes``): the f32 g/h round-trip the unfused path
    pays and ``fused_grad`` deletes.
    """
    mode = _resolve_mode(mode)
    if pass_rows is None:
        pass_rows = float(rows) * int(wave_mxu_passes(
            wave_capacity_max(packed), mode, packed))
    pack = feat_pack(B, feat_block)
    lanes = max(pack * B, C_MAX) / pack      # charged output rows / feature
    flops = 2.0 * float(pass_rows) * F * lanes * C_MAX
    hist_bytes = F * B * C_MAX * 4
    n_out = 2 if packed else 1
    per_launch = hist_bytes * n_out          # child histogram write(s)
    if fused:
        per_launch += 2 * hist_bytes * n_out  # parent read + sibling write
    nbytes = (float(rows) * (F * 1 + _VEC_BYTES[mode])
              + max(int(waves), 1) * per_launch)
    if n_rows is not None:
        # grad_stream_bytes counts the kernel's vector read too — that
        # leg is already in nbytes above, so only the pack legs add here
        nbytes += (grad_stream_bytes(n_rows, 0.0, mode,
                                     fused_grad=fused_grad))
    return flops, nbytes


def select_wave_blocks(B: int, mode="2xbf16", packed: bool = True,
                       fused: bool = True, block_rows: int = _DEF_BR,
                       vmem_budget: int = _VMEM_BUDGET):
    """Cost-model-driven (block_rows, feat_block) for ``hist_pallas_wave``.

    The per-grid-step VMEM residency is dominated by the [FB, B, C] f32
    histogram blocks: 1 (triple) or 2 (packed) child blocks, plus parent
    and sibling blocks of the same shape when fused (the passes' scratch
    accumulators are not counted: see ``_VMEM_BUDGET``).  This picks the
    largest feat_block whose blocks + streamed operands fit the budget —
    bin-width specialization in block form: B=64 picks FB=32 fused where
    B=256 must drop to FB=8, and the unfused/triple oracle paths get the
    larger blocks their smaller footprint allows; ``wave_feature_blocks``
    lays the pick over the columns.  ``block_rows`` is passed through (row
    blocking is an HBM-streaming knob, not a VMEM one, at these
    shapes)."""
    mode = _resolve_mode(mode)
    n_out = 2 if packed else 1
    n_big = n_out * (3 if fused else 1)   # acc (+ parent + sibling)
    for FB in (128, 64, 32, 16, 8):
        pack = feat_pack(B, FB)
        oh_bytes = block_rows * max(pack * B, C_MAX) * \
            (4 if mode == "highest" else 2)
        # bins + vecs double-buffered stream; quantized vecs are int16
        stream = 2 * (FB * block_rows + block_rows * _VEC_BYTES[mode])
        total = FB * B * C_MAX * 4 * n_big + oh_bytes + stream
        if total <= vmem_budget:
            return block_rows, FB
    return block_rows, 8


@functools.partial(jax.jit,
                   static_argnames=("B", "block_rows", "feat_block", "highest",
                                    "interpret", "packed", "col_blocks"))
@jax.named_scope("lgbm/pallas_hist_wave")
def hist_pallas_wave(bins_fm, gv, hv, cv, leaf_id, slot_leaf, B: int,
                     block_rows: int = 1024, feat_block: int = _DEF_FB,
                     highest="bf16", interpret: bool = False,
                     packed: bool = False, parent=None, col_blocks=None,
                     col_window=None):
    """Wave histogram: bins_fm [F, N] uint8; gv/hv/cv f32 [N] (bag-masked
    g, h, ones); leaf_id i32 [N]; slot_leaf i32 [C_MAX] maps channel c to
    a leaf id (-1 = unused).

    Channel layouts (``packed``):
      triple (False) — channel kinds cycle g,h,count; returns
        [F, B, C_MAX] f32 where channels 3s..3s+2 hold leaf
        slot_leaf[3s]'s (sum_g, sum_h, count) histograms.
      packed (True) — slot s's leaf is slot_leaf[2s] (== slot_leaf[2s+1]:
        the map keeps the lane-pair form the layout began with), up to
        63 slots.  Returns ``(gh, cnt)``, two [F, B, C_MAX] arrays that
        keep slot s's (sum_g, sum_h, count) in the lanes ``packed_lanes``
        gives (``unpack_lanes`` / ``pack_lanes`` turn them into per-leaf
        histograms and back); every other lane is zero.  In the bf16
        modes the launch runs as many MXU passes as the slots in use
        need (``wave_mxu_passes``), read off ``slot_leaf`` itself.
        Exactness: count weights are the 0/1 bag mask — exact in bf16
        with f32 accumulation, so counts bit-match dedicated f32 lanes in
        every precision mode.

    ``parent`` fuses sibling subtraction in-kernel: pass the parent
    histograms in the SAME channel layout as the output ([F, B, C_MAX],
    or the (gh, cnt) pair when packed) and the call returns
    ``(child, sibling)`` with sibling = parent - child written from VMEM
    on the final row step — no separate XLA subtraction pass, no child
    re-read from HBM.

    ``highest``: precision mode — True/"highest", "2xbf16", "int16",
    "int8", or False/"bf16" (see _hist_wave_kernel).  The quantized
    modes take gv/hv as INTEGER-valued arrays (``stochastic_round``
    output) and return histograms in INTEGER units — the caller
    dequantizes at split-scan time (value = sum * scale).  The vector
    stream travels as [N, 4] int16 (half the f32 HBM bytes), so leaf
    ids must fit int16 (config caps ``num_leaves`` accordingly).

    ``col_blocks`` (static) and ``col_window`` (i32 [2], traced) make the
    launch a chip's share of the columns under the feature-parallel
    learner: ``col_blocks`` feature blocks of ``bins_fm``, starting at
    block ``col_window[0]``, read through the bins' index map (no slice is
    copied), of which the first ``col_window[1]`` are histogrammed and the
    rest come out zero.  The result, and ``parent``, then have
    ``col_blocks`` x the feature block columns; ``bins_fm`` must hold whole
    blocks.  Unset (the default), the launch covers every column."""
    F, N = bins_fm.shape
    # rows lie along lanes in the kernel: whole lane tiles a block
    BR = min(block_rows, -(-max(N, 1) // C_MAX) * C_MAX)
    mode = _resolve_mode(highest)
    windowed = col_blocks is not None
    fused = parent is not None
    FB, pack, Fp = wave_feature_blocks(
        B, F, feat_block, mode=mode, packed=packed, fused=fused,
        block_rows=block_rows, windowed=windowed)
    if windowed:
        assert Fp == F, "a windowed launch reads whole blocks of the bins"
        n_blk, Fp = F // FB, int(col_blocks) * FB
    par_arrs = (list(parent) if packed else [parent]) if fused else []
    pad_rows = (-N) % BR
    if pad_rows:
        bins_fm = jnp.pad(bins_fm, ((0, 0), (0, pad_rows)))
        gv = jnp.pad(gv, (0, pad_rows))
        hv = jnp.pad(hv, (0, pad_rows))
        cv = jnp.pad(cv, (0, pad_rows))
        leaf_id = jnp.pad(leaf_id, (0, pad_rows), constant_values=-2)
    pad_f = 0 if windowed else Fp - F
    if pad_f:
        bins_fm = jnp.pad(bins_fm, ((0, pad_f), (0, 0)))
        par_arrs = [jnp.pad(pa, ((0, pad_f), (0, 0), (0, 0)))
                    for pa in par_arrs]
    Np = bins_fm.shape[1]
    # a block of the bins is whole sublane tiles or all the columns (Pallas
    # TPU's rule for a block's last two dimensions): where the feature
    # block is neither, as MSLR's five of 28, a grid step reads all the
    # columns of its row block and converts its own to i32 (a reshape of
    # the bins to [Fp / FB, FB, N] is a relayout copy)
    whole = bool(FB % 8) and Fp > FB and not windowed
    quant = mode in QUANT_MODES
    # pack row vectors into one [N, 4] array (g, h, count-weight, leaf_id);
    # leaf ids are exact in f32 up to 2^24.  Quantized modes carry the
    # stream as int16 — the values are already integers by construction
    # (stochastic_round output, 0/1 count weights, leaf ids capped), so
    # the cast is exact and the HBM read halves.
    vecs = jnp.stack([gv, hv, cv, leaf_id.astype(jnp.float32)], axis=1)
    if quant:
        vecs = vecs.astype(jnp.int16)
    nb = Np // BR

    # scratch: the accumulators the last row step folds into the outputs,
    # then the bins' i32 copy where the block loops
    n_acc, most = _contractions(mode, packed)
    scratch = [pltpu.VMEM((FB, B, C_MAX), jnp.float32)] * n_acc
    if whole or _unroll(FB // pack, most)[1]:
        scratch = scratch + [pltpu.VMEM((FB, BR), jnp.int32)]
    scalars = []
    if packed:
        # each contraction's lanes as slots, a row each (slot_leaf[2s] is
        # slot s's leaf; P_MAX_PACKED and up: no slot)
        leaves = slot_leaf[:2 * P_MAX_PACKED:2]
        lane = np.arange(C_MAX)
        if mode == "highest":       # [g | h]; the count pass
            rows = [lane % (C_MAX // 2), lane]
        else:
            per = pass_leaves(mode)
            kinds = 5 if mode in ("2xbf16", "int16") else 3
            rows = [np.where(lane < kinds * per, per * p + lane % per,
                             P_MAX_PACKED) for p in range(n_acc)]
            # the passes this launch runs, from the slots it was given
            live = jnp.max(jnp.where(leaves >= 0,
                                     jnp.arange(1, P_MAX_PACKED + 1), 0))
            scalars = [wave_mxu_passes(live, mode, True).astype(
                jnp.int32).reshape(1)]
        slot = jnp.stack([
            jnp.where(s < P_MAX_PACKED,
                      leaves[np.minimum(s, P_MAX_PACKED - 1)], -1)
            for s in rows])
    else:
        slot = slot_leaf.reshape(1, C_MAX)

    n_out = 2 if packed else 1
    # index maps take the prefetched scalars as trailing arguments
    hist_spec = pl.BlockSpec((FB, B, C_MAX), lambda j, i, *_: (j, 0, 0),
                             memory_space=pltpu.VMEM)
    if windowed:
        scalars.append(jnp.asarray(col_window, jnp.int32).reshape(2))

        def rows_of(j, i, w):
            # past the blocks owned: the last owned step's rows, refetched
            # never
            return jnp.where(j < w[1], i, nb - 1)

        def bins_map(j, i, *s):
            w = s[-1]
            blk = w[0] + jnp.minimum(j, jnp.maximum(w[1] - 1, 0))
            return jnp.minimum(blk, n_blk - 1), rows_of(j, i, w)
        bins_spec = pl.BlockSpec((FB, BR), bins_map, memory_space=pltpu.VMEM)
        vecs_spec = pl.BlockSpec((BR, 4),
                                 lambda j, i, *s: (rows_of(j, i, s[-1]), 0),
                                 memory_space=pltpu.VMEM)
    elif whole:
        bins_spec = pl.BlockSpec((Fp, BR), lambda j, i, *_: (0, i),
                                 memory_space=pltpu.VMEM)
    else:
        bins_spec = pl.BlockSpec((FB, BR), lambda j, i, *_: (j, i),
                                 memory_space=pltpu.VMEM)
    if not windowed:
        vecs_spec = pl.BlockSpec((BR, 4), lambda j, i, *_: (i, 0),
                                 memory_space=pltpu.VMEM)
    in_specs = [
        bins_spec,
        vecs_spec,
        pl.BlockSpec((slot.shape[0], C_MAX), lambda j, i, *_: (0, 0),
                     memory_space=pltpu.VMEM),
    ] + [hist_spec] * len(par_arrs)
    n_res = n_out * (2 if fused else 1)
    vmem_limit = wave_vmem_bytes(B, FB, BR, mode, packed, fused)
    res = pl.pallas_call(
        functools.partial(_hist_wave_kernel, B=B, FB=FB, mode=mode,
                          packed=packed, fused=fused, windowed=windowed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(Fp // FB, nb),
            in_specs=in_specs, out_specs=[hist_spec] * n_res,
            scratch_shapes=scratch),
        out_shape=[jax.ShapeDtypeStruct((Fp, B, C_MAX), jnp.float32)
                   for _ in range(n_res)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(vmem_limit)),
        interpret=interpret,
    )(*scalars, bins_fm, vecs, slot, *par_arrs)
    if not windowed:
        res = [r[:F] for r in res]
    child = (res[0], res[1]) if packed else res[0]
    if not fused:
        return child
    sib = (res[2], res[3]) if packed else res[1]
    return child, sib
