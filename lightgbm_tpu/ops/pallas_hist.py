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

* ``packed`` (the default fast path): each leaf owns a LANE PAIR (g*m,
  h*m) — 63 leaves per wave — and the count channel is folded into the
  same accumulation as ONE extra single-pass matmul whose channel matrix
  is the 0/1 membership mask.  The mask is exactly representable in
  bf16 and accumulation is f32, so the folded counts are bit-identical
  to dedicated f32 count lanes while costing one hardware pass instead
  of a third of the lane budget.  Capacity 42 -> 63 leaves per launch
  means ~1.5x fewer kernel launches (and full bins-array reads) per
  tree.
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
the uint8 32-sublane tile constraint lands on the feature axis).

Per grid step (j=feature block, i=row block):
  bins block  [FB, BR]   uint8
  gh block    [BR, C]    f32 (pre-masked channels)
  out block   [FB, B, C] f32, accumulated across the i sweep
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# channel capacity: one MXU lane pass
C_MAX = 128
_DEF_BR = 1024
_DEF_FB = 32  # uint8 sublane tile
# wave capacity per layout: triple = 3 lanes/leaf; packed = a lane pair
# per leaf with the top pair left free (63, matching the max_bin=63
# economics the docs quote) so the count-lane map keeps a dead sentinel
P_MAX_TRIPLE = C_MAX // 3       # 42
P_MAX_PACKED = C_MAX // 2 - 1   # 63
# What select_wave_blocks lets one grid step's blocks take, counted once
# each.  The pipeline double-buffers them and the body adds temporaries,
# so the real footprint is about twice this (~18-22 MiB at B=256) — of the
# 128 MiB of VMEM a v5e core has (jax's pallas tpu_info).  The v5e's
# compiler accepted every block shape this budget selects under its
# default scoped limit (chip run, PR 21), so no vmem_limit_bytes is
# passed.
_VMEM_BUDGET = 10 * 2 ** 20


def wave_capacity_max(packed: bool) -> int:
    """Leaves one kernel launch can histogram under the given layout."""
    return P_MAX_PACKED if packed else P_MAX_TRIPLE


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
    import numpy as np
    return np.asarray(counts, np.float64) * float(scale)


def _feat_pack(B: int, FB: int) -> int:
    """Features whose one-hot factors share one MXU pass (B <= 64)."""
    pack = max(1, 128 // B)
    return pack if 128 % B == 0 and FB % pack == 0 else 1


def _hist_wave_kernel(*refs, B: int, FB: int, mode: str, packed: bool,
                      fused: bool):
    """Multi-leaf histogram step: the per-leaf channel matrix is built in
    VMEM from leaf_id + the slot->leaf map, never touching HBM.

    ``mode`` selects the matmul precision/throughput trade:
      "highest" — f32 operands at Precision.HIGHEST (~3 MXU passes);
      "2xbf16"  — hi/lo bf16 split of the channel matrix, 2 MXU passes:
                  the one-hot operand is exactly representable in bf16 and
                  accumulation is always f32, so only g/h are rounded — to
                  ~16 mantissa bits, tighter than one bf16 pass and ~1.5x
                  faster than "highest";
      "bf16"    — single bf16 pass (~8 mantissa bits on g/h).

    ``packed`` selects the channel layout: lane pairs (g, h) per leaf with
    the count channel folded into one extra single-pass matmul (63 leaves)
    vs (g, h, count) lane triples (42 leaves).  The folded count pass runs
    in bf16 in EVERY mode — the membership weights are the 0/1 bag mask,
    exact in bf16, and accumulation is f32, so folded counts are
    bit-identical to dedicated count lanes at any precision mode.

    ``fused`` adds parent blocks as inputs and sibling blocks as outputs:
    on the final row step (the accumulators now hold the full child
    histograms for this feature block) the sibling is written as
    parent - child straight from VMEM.

    Quantized modes ("int16" / "int8"): vecs arrive as int16 integers;
    int16 splits each value into an EXACT hi/lo bf16 pair (2 MXU
    passes, like 2xbf16 but with zero representation error), int8 is
    one exact bf16 pass.  Everything — accumulators, emitted
    histograms, the fused sibling subtraction, and the parent operand —
    stays in INTEGER units: dequantization happens downstream at
    split-scan time (core/wave_grower.py), which keeps fused and
    unfused siblings bit-identical (an in-kernel dequant would let the
    compiler fuse ``parent - child*scale`` into an FMA whose rounding
    the separate XLA subtraction cannot reproduce)."""
    quant = mode in QUANT_MODES
    n_out = 2 if packed else 1
    n_par = n_out if fused else 0
    bins_ref, vecs_ref, slot_ref = refs[:3]
    par_refs = refs[3:3 + n_par]
    acc_refs = refs[3 + n_par:3 + n_par + n_out]
    sib_refs = refs[3 + n_par + n_out:]

    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        for r in acc_refs:
            r[...] = jnp.zeros_like(r)

    vecs = vecs_ref[...]                                  # [BR, 4]
    if quant:
        vecs = vecs.astype(jnp.int32)                     # int16 -> i32
    leaf = vecs[:, 3].astype(jnp.int32)                   # [BR]
    slot_leaf = slot_ref[0, :].astype(jnp.int32)          # [C]
    lanes = 2 if packed else 3
    kind = jax.lax.broadcasted_iota(jnp.int32, (1, C_MAX), 1) % lanes
    m = (leaf[:, None] == slot_leaf[None, :]) & (slot_leaf >= 0)[None, :]
    zero = 0 if quant else 0.0
    if packed:
        vals = jnp.where(kind == 0, vecs[:, 0][:, None], vecs[:, 1][:, None])
        slot_ct = slot_ref[1, :].astype(jnp.int32)        # [C] count lanes
        mc = (leaf[:, None] == slot_ct[None, :]) & (slot_ct >= 0)[None, :]
        ct_src = vecs[:, 2][:, None]
        ct_b = jnp.where(mc, ct_src, zero).astype(jnp.bfloat16)
    else:
        vals = jnp.where(kind == 0, vecs[:, 0][:, None],
                         jnp.where(kind == 1, vecs[:, 1][:, None],
                                   vecs[:, 2][:, None]))
    gh = jnp.where(m, vals, zero)                         # [BR, C]
    if mode == "2xbf16":
        gh_hi = gh.astype(jnp.bfloat16)
        gh_lo = (gh - gh_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    elif mode == "int16":
        # exact integer hi/lo split: hi is a multiple of 256 with
        # |hi| <= 33024 (|hi/256| <= 129 fits bf16's 8-bit mantissa),
        # lo in [0, 255] — both EXACT in bf16, so two passes accumulate
        # the integer sum with no representation error at all
        gh_hi_i = (gh >> 8) << 8
        gh_hi = gh_hi_i.astype(jnp.bfloat16)
        gh_lo = (gh - gh_hi_i).astype(jnp.bfloat16)
    elif mode in ("bf16", "int8"):
        # int8: |q| <= 127 is exact in bf16 — one pass, zero error
        gh_b = gh.astype(jnp.bfloat16)

    # Feature packing: with B <= 64 a single feature's one-hot only spans B
    # of the MXU's 128 output rows — ``pack`` features' one-hot factors side
    # by side in one [BR, pack*B] operand fill the systolic array, so a
    # max_bin=63 run really is ~4x cheaper than max_bin=255 (the reference's
    # GPU backend has the same bins-per-workgroup economics and recommends
    # 63 bins, docs/GPU-Performance.rst:128-130).  Lane group p carries
    # feature f+p's bins, placed by selects: Mosaic refuses to concatenate
    # the i1 compare results along lanes ("Invalid vector register cast").
    pack = _feat_pack(B, FB)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, pack * B), 1)
    iota = lane & (B - 1)          # bin within the lane group (B is 2^k)
    dims = (((0,), (0,)), ((), ()))
    for f in range(0, FB, pack):
        col = bins_ref[f, :].astype(jnp.int32)[:, None]
        for p in range(1, pack):
            col = jnp.where(lane >= p * B,
                            bins_ref[f + p, :].astype(jnp.int32)[:, None],
                            col)                        # [BR, pack*B]
        eq = col == iota
        if mode == "highest":
            oh = eq.astype(jnp.float32)
            acc = jax.lax.dot_general(
                oh, gh, dims,
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        elif mode in ("2xbf16", "int16"):
            oh = eq.astype(jnp.bfloat16)
            acc = (jax.lax.dot_general(
                       oh, gh_hi, dims,
                       preferred_element_type=jnp.float32)
                   + jax.lax.dot_general(
                       oh, gh_lo, dims,
                       preferred_element_type=jnp.float32))
        else:
            oh = eq.astype(jnp.bfloat16)
            acc = jax.lax.dot_general(
                oh, gh_b, dims,
                preferred_element_type=jnp.float32)
        if packed:
            acc_ct = jax.lax.dot_general(
                eq.astype(jnp.bfloat16), ct_b, dims,
                preferred_element_type=jnp.float32)
        if pack == 1:
            acc_refs[0][f] += acc
            if packed:
                acc_refs[1][f] += acc_ct
        else:
            for p in range(pack):
                acc_refs[0][f + p] += acc[p * B:(p + 1) * B]
                if packed:
                    acc_refs[1][f + p] += acc_ct[p * B:(p + 1) * B]

    if fused:
        # final row step: accumulators hold the complete child histograms
        # for this feature block — emit the sibling without the child
        # ever round-tripping through HBM
        @pl.when(i == pl.num_programs(1) - 1)
        def _sibling():
            for par, accr, sibr in zip(par_refs, acc_refs, sib_refs):
                sibr[...] = par[...] - accr[...]


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
                     fused_grad: bool = False, n_rows=None):
    """Analytical (FLOPs, HBM bytes) of ``hist_pallas_wave`` over ``rows``
    total rows across ``waves`` kernel launches — ``docs/ROOFLINE.md``'s
    hand-written cost model in code, so profile mode and
    ``tools/prof_kernels.py`` compare measured kernel time against the
    same numbers the doc quotes.

    FLOPs are what the MXU is CHARGED, not useful work: the one-hot
    operand is 255/256 zeros but every lane is paid for.  Mirrors the
    kernel's feature packing (B <= 64 packs 128//B features per matmul);
    an unpacked B < 128 operand still occupies one full 128-lane group.
    ``packed`` charges the folded count as one extra hardware pass on
    top of the mode's g/h passes (the lane-pair layout fits 63 leaves
    where triples fit 42, so per-LEAF MXU cost is unchanged — the win is
    1.5x fewer launches, i.e. fewer ``waves`` and fewer bins reads).
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
    passes = WAVE_MXU_PASSES[mode] + (1 if packed else 0)
    pack = _feat_pack(B, feat_block)
    lanes = max(pack * B, C_MAX) / pack      # charged output rows / feature
    flops = passes * 2.0 * float(rows) * F * lanes * C_MAX
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
    histogram blocks: 1 (triple) or 2 (packed) accumulators, plus parent
    and sibling blocks of the same shape when fused.  This picks the
    largest feat_block whose blocks + streamed operands fit the budget —
    bin-width specialization in block form: B=64 runs FB=32 fused where
    B=256 must drop to FB=8, and the unfused/triple oracle paths get the
    larger blocks their smaller footprint allows.  ``block_rows`` is
    passed through (row blocking is an HBM-streaming knob, not a VMEM
    one, at these shapes)."""
    mode = _resolve_mode(mode)
    n_out = 2 if packed else 1
    n_big = n_out * (3 if fused else 1)   # acc (+ parent + sibling)
    for FB in (128, 64, 32, 16, 8):
        pack = _feat_pack(B, FB)
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
                                    "interpret", "packed"))
@jax.named_scope("lgbm/pallas_hist_wave")
def hist_pallas_wave(bins_fm, gv, hv, cv, leaf_id, slot_leaf, B: int,
                     block_rows: int = 1024, feat_block: int = _DEF_FB,
                     highest="bf16", interpret: bool = False,
                     packed: bool = False, parent=None):
    """Wave histogram: bins_fm [F, N] uint8; gv/hv/cv f32 [N] (bag-masked
    g, h, ones); leaf_id i32 [N]; slot_leaf i32 [C_MAX] maps channel c to
    a leaf id (-1 = unused).

    Channel layouts (``packed``):
      triple (False) — channel kinds cycle g,h,count; returns
        [F, B, C_MAX] f32 where channels 3s..3s+2 hold leaf
        slot_leaf[3s]'s (sum_g, sum_h, count) histograms.
      packed (True) — channels pair up (g, h) per leaf (slot_leaf[2s] ==
        slot_leaf[2s+1] is leaf s); the count channel is folded into the
        same accumulation as one extra bf16 pass whose lane s carries
        leaf slot_leaf[2s]'s count.  Returns ``(gh, cnt)``: gh [F, B,
        C_MAX] with the lane pairs, cnt [F, B, C_MAX] with counts in the
        first C_MAX//2 lanes.  Exactness: count weights are the 0/1 bag
        mask — exact in bf16 with f32 accumulation, so folded counts
        bit-match dedicated lanes in every precision mode.

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
    ids must fit int16 (config caps ``num_leaves`` accordingly)."""
    F, N = bins_fm.shape
    BR = min(block_rows, max(128, N))
    FB = min(feat_block, max(F, 1))
    fused = parent is not None
    par_arrs = (list(parent) if packed else [parent]) if fused else []
    pad_rows = (-N) % BR
    if pad_rows:
        bins_fm = jnp.pad(bins_fm, ((0, 0), (0, pad_rows)))
        gv = jnp.pad(gv, (0, pad_rows))
        hv = jnp.pad(hv, (0, pad_rows))
        cv = jnp.pad(cv, (0, pad_rows))
        leaf_id = jnp.pad(leaf_id, (0, pad_rows), constant_values=-2)
    pad_f = (-F) % FB
    if pad_f:
        bins_fm = jnp.pad(bins_fm, ((0, pad_f), (0, 0)))
        par_arrs = [jnp.pad(pa, ((0, pad_f), (0, 0), (0, 0)))
                    for pa in par_arrs]
    Fp, Np = bins_fm.shape
    mode = _resolve_mode(highest)
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

    if packed:
        # second slot row: the count-lane map (lane s -> leaf of pair s)
        half = C_MAX // 2
        slot_ct = jnp.concatenate(
            [slot_leaf[::2],
             jnp.full((C_MAX - half,), -1, slot_leaf.dtype)])
        slot = jnp.stack([slot_leaf, slot_ct])
    else:
        slot = slot_leaf.reshape(1, C_MAX)

    n_out = 2 if packed else 1
    hist_spec = pl.BlockSpec((FB, B, C_MAX), lambda j, i: (j, 0, 0),
                             memory_space=pltpu.VMEM)
    in_specs = [
        pl.BlockSpec((FB, BR), lambda j, i: (j, i),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((BR, 4), lambda j, i: (i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((slot.shape[0], C_MAX), lambda j, i: (0, 0),
                     memory_space=pltpu.VMEM),
    ] + [hist_spec] * len(par_arrs)
    n_res = n_out * (2 if fused else 1)
    grid = (Fp // FB, nb)
    res = pl.pallas_call(
        functools.partial(_hist_wave_kernel, B=B, FB=FB, mode=mode,
                          packed=packed, fused=fused),
        grid=grid,
        in_specs=in_specs,
        out_specs=[hist_spec] * n_res,
        out_shape=[jax.ShapeDtypeStruct((Fp, B, C_MAX), jnp.float32)
                   for _ in range(n_res)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(bins_fm, vecs, slot, *par_arrs)
    res = [r[:F] for r in res]
    child = (res[0], res[1]) if packed else res[0]
    if not fused:
        return child
    sib = (res[2], res[3]) if packed else res[1]
    return child, sib
