"""Histogram construction — the hottest loop of the framework.

The reference accumulates per-feature (sum_grad, sum_hess, count) bins with a
scalar gather-accumulate loop on CPU (reference: src/io/dense_bin.hpp:71-135)
and a workgroup-atomic OpenCL kernel on GPU (reference:
src/treelearner/ocl/histogram256.cl:350).  TPUs have no fast arbitrary
scatter, so the TPU-native formulation turns bin accumulation into one-hot
matmuls that run on the MXU:

    hist[f, b, :] = sum_r onehot(X_bin[r, f])[b] * (g, h, 1)[r]

i.e. a single ``[F*B, C] @ [C, 3]`` contraction per row-chunk, scanned over
chunks so the one-hot tile never exceeds a few tens of MB.  A Pallas kernel
(ops/pallas_hist.py) implements the same contraction with the one-hot tile
built directly in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# target one-hot tile size in elements (f32): ~32MB
_TILE_ELEMS = 8 * 1024 * 1024


def _chunk_rows(F: int, B: int) -> int:
    c = max(128, _TILE_ELEMS // (F * B))
    # round down to a multiple of 128 (sublane-friendly)
    return max(128, (c // 128) * 128)


@functools.partial(jax.jit, static_argnames=("B",))
@jax.named_scope("lgbm/hist_onehot")
def hist_onehot(bins, g, h, mask, B: int):
    """Dense histogram via chunked one-hot contraction.

    Parameters
    ----------
    bins : uint8/int32 [C, F] per-row bin indices (feature-local, unpadded)
    g, h : float32 [C] gradients / hessians
    mask : float32 [C] 1.0 for rows to accumulate (bagging x leaf membership)
    B : static padded bin width

    Returns
    -------
    float32 [F, B, 3] — (sum_grad, sum_hess, count) per feature x bin.
    """
    C, F = bins.shape
    ghc = jnp.stack([g, h, jnp.ones_like(g)], axis=-1) * mask[:, None]  # [C,3]
    chunk = _chunk_rows(F, B)
    if C <= chunk:
        return _hist_block(bins, ghc, B)
    n_chunks = -(-C // chunk)
    pad = n_chunks * chunk - C
    if pad:
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        ghc = jnp.pad(ghc, ((0, pad), (0, 0)))
    bins_c = bins.reshape(n_chunks, chunk, F)
    ghc_c = ghc.reshape(n_chunks, chunk, 3)

    def body(acc, xs):
        b, z = xs
        return acc + _hist_block(b, z, B), None

    init = jnp.zeros((F, B, 3), dtype=jnp.float32)
    out, _ = jax.lax.scan(body, init, (bins_c, ghc_c))
    return out


def _hist_block(bins, ghc, B: int):
    """One-hot contraction for a single row block: [c,F],[c,3] -> [F,B,3]."""
    c, F = bins.shape
    oh = jax.nn.one_hot(bins.astype(jnp.int32), B, dtype=jnp.float32)  # [c,F,B]
    # contract over rows: [c, F*B]^T @ [c, 3]
    flat = oh.reshape(c, F * B)
    out = jax.lax.dot_general(
        flat, ghc, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    return out.reshape(F, B, 3)


@functools.partial(jax.jit, static_argnames=("B",))
@jax.named_scope("lgbm/hist_scatter")
def hist_scatter(bins, g, h, mask, B: int):
    """Scatter-add histogram for VERY wide physical layouts (wide-sparse
    EFB datasets): cost O(N*F) instead of the one-hot path's O(N*F*B).

    The reference's answer to wide sparse data is SparseBin's
    nonzero-stream accumulate (src/io/sparse_bin.hpp:72); a dense one-hot
    contraction over 50k+ features x thousands of bundle bins would
    materialize terabytes.  Scatter-add is not MXU-friendly, but at these
    shapes it is the only formulation with a feasible op count — and
    wide-sparse is a CPU/host-dominant regime in the reference too.

    Same contract as ``hist_onehot``: bins [N, F] -> f32 [F, B, 3].
    """
    N, F = bins.shape
    ghc = jnp.stack([g, h, jnp.ones_like(g)], axis=-1) * mask[:, None]
    offsets = (jnp.arange(F, dtype=jnp.int32) * B)[None, :]
    # chunk rows so the broadcasted [c, F, 3] update tensor stays ~100MB
    chunk = max(256, min(N, (8 * 1024 * 1024) // max(F, 1)))
    out = jnp.zeros((F * B, 3), jnp.float32)
    if N <= chunk:
        flat = bins.astype(jnp.int32) + offsets
        out = out.at[flat].add(ghc[:, None, :])
        return out.reshape(F, B, 3)
    n_chunks = -(-N // chunk)
    pad = n_chunks * chunk - N
    if pad:
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        ghc = jnp.pad(ghc, ((0, pad), (0, 0)))
    bins_c = bins.reshape(n_chunks, chunk, F)
    ghc_c = ghc.reshape(n_chunks, chunk, 3)

    def body(acc, xs):
        b, z = xs
        flat = b.astype(jnp.int32) + offsets
        return acc.at[flat].add(z[:, None, :]), None

    out, _ = jax.lax.scan(body, out, (bins_c, ghc_c))
    return out.reshape(F, B, 3)


@functools.partial(jax.jit, static_argnames=("B",))
@jax.named_scope("lgbm/hist_wave_xla")
def hist_wave_xla(bins_rm, gv, hv, cv, leaf_id, slot_leaf, B: int):
    """XLA analog of ``ops.pallas_hist.hist_pallas_wave`` for WIDE
    (>256-bin) features — the side-pass of the mixed-width wave path.

    The Pallas kernel's one-hot tile is built per uint8 feature block in
    VMEM; features with more than 256 bins don't fit that layout, so the
    few wide columns (high-cardinality categoricals, mostly) take this
    chunked one-hot contraction instead and are merged with the kernel's
    output before the split scan (core/wave_grower.py).

    bins_rm: ROW-major [N, Fw] bin indices; gv/hv/cv: f32 [N] (bag-masked
    g, h, ones); leaf_id: i32 [N]; slot_leaf: i32 [C] channel->leaf map
    (kinds cycle g,h,count; -1 = unused).  Returns [Fw, B, C] f32 matching
    the kernel's channel semantics.
    """
    N, Fw = bins_rm.shape
    C = slot_leaf.shape[0]
    kind = jnp.arange(C, dtype=jnp.int32) % 3
    vals = jnp.stack([gv, hv, cv], axis=1)               # [N, 3]
    chunk = _chunk_rows(Fw, B)
    if N > chunk:
        pad = (-N) % chunk
        if pad:
            bins_rm = jnp.pad(bins_rm, ((0, pad), (0, 0)))
            vals = jnp.pad(vals, ((0, pad), (0, 0)))
            leaf_id = jnp.pad(leaf_id, (0, pad), constant_values=-2)
        n_chunks = bins_rm.shape[0] // chunk
        bins_c = bins_rm.reshape(n_chunks, chunk, Fw)
        vals_c = vals.reshape(n_chunks, chunk, 3)
        leaf_c = leaf_id.reshape(n_chunks, chunk)
    else:
        bins_c = bins_rm[None]
        vals_c = vals[None]
        leaf_c = leaf_id[None]

    def body(acc, xs):
        b, v, l = xs
        m = (l[:, None] == slot_leaf[None, :]) & (slot_leaf >= 0)[None, :]
        gh = jnp.where(m, v[:, kind], 0.0)               # [c, C]
        oh = jax.nn.one_hot(b.astype(jnp.int32), B, dtype=jnp.float32)
        out = jax.lax.dot_general(
            oh.reshape(b.shape[0], Fw * B), gh, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)          # [Fw*B, C]
        return acc + out, None

    init = jnp.zeros((Fw * B, C), jnp.float32)
    out, _ = jax.lax.scan(body, init, (bins_c, vals_c, leaf_c))
    return out.reshape(Fw, B, C)


def hist_subtract(parent, child):
    """Sibling histogram by subtraction (reference:
    src/treelearner/feature_histogram.hpp:75-81, serial_tree_learner.cpp:567)."""
    return parent - child


def expand_bundled(hist_phys, meta, B_out: int):
    """EFB bundle expansion: physical-column histograms -> per-feature
    histograms (see io/bundling.py for the bin layout).

    hist_phys: f32 [F_phys, B_phys, C]; returns [F, B_out, C] where
    out[f, b] = hist_phys[feat2phys[f], feat_offset[f] + b] for b within
    feature f's bins, zero elsewhere.  Histogram-sized (not data-sized), so
    the gather is cheap relative to the kernel pass it follows.
    """
    Fp, Bp, C = hist_phys.shape
    b = jnp.arange(B_out, dtype=jnp.int32)
    idx = (meta.feat2phys[:, None] * Bp + meta.feat_offset[:, None]
           + b[None, :])                                  # [F, B_out]
    valid = (b[None, :] < meta.num_bins[:, None]) & \
        (meta.feat_offset[:, None] + b[None, :] < Bp)
    flat = hist_phys.reshape(Fp * Bp, C)
    out = flat[jnp.where(valid, idx, 0)]
    return out * valid[..., None]


def fix_default_bins(hist, tg, th, tc, meta, alive=None):
    """Reconstruct each bundled member's elided default-bin mass from the
    leaf totals (reference: Dataset::FixHistogram, src/io/dataset.cpp:
    1044-1063): hist[f, default_bin_f] += total - sum_b hist[f, b].

    hist: f32 [F, B, 3]; tg/th/tc: scalar leaf totals.  ``alive`` (bool
    [F_phys], optional) marks physical columns that survived a lossy
    reduce (voting-parallel's top-k gate): members of a gated-OFF column
    must stay all-zero — fixing them would fabricate the whole leaf mass
    at their default bin and produce phantom splits."""
    sums = hist.sum(axis=1)                               # [F, 3]
    totals = jnp.stack([tg, th, tc]).astype(hist.dtype)   # [3]
    fix = meta.needs_fix
    if alive is not None:
        fix = fix & alive[meta.feat2phys]
    resid = jnp.where(fix[:, None], totals[None, :] - sums, 0.0)
    F = hist.shape[0]
    return hist.at[jnp.arange(F), meta.default_bins].add(resid)
