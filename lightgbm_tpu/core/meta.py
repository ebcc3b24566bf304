"""Static device-side feature metadata and split hyperparameters.

The reference carries per-feature metadata as ``FeatureMetainfo`` structs
(reference: src/treelearner/feature_histogram.hpp:20-35) and threads the full
``Config`` through the gain math. On TPU everything the jitted grower needs is
packed once into small device arrays (``DeviceMeta``) plus a hashable frozen
dataclass of scalar hyperparameters (``SplitConfig``) that is closed over at
trace time.

Histogram layout: per-leaf histograms are padded dense ``[F, B, 3]`` arrays
(features x padded-bin x (grad, hess, count)).  Unlike the reference we store
*every* bin — no most-frequent-bin elision and therefore no ``FixHistogram``
reconstruction (reference: src/io/dataset.cpp:1044-1063); HBM is cheap and
dense fixed shapes are what XLA wants.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

from ..io.binning import BIN_CATEGORICAL, MISSING_NAN, MISSING_NONE, MISSING_ZERO


class DeviceMeta(NamedTuple):
    """Per-feature metadata as device arrays (all shaped [F] unless noted).

    The last three fields carry the EFB bundle mapping (io/bundling.py):
    feature f lives in physical column ``feat2phys[f]`` at bin offset
    ``feat_offset[f]``; ``needs_fix[f]`` marks members whose default-bin
    histogram mass must be reconstructed from leaf totals (the reference's
    Dataset::FixHistogram, src/io/dataset.cpp:1044-1063).  Identity arrays
    when the dataset is unbundled."""
    num_bins: "jax.Array"       # int32 — actual bin count per feature
    default_bins: "jax.Array"   # int32 — bin of value 0.0
    missing_types: "jax.Array"  # int32 — MISSING_{NONE,ZERO,NAN}
    monotone: "jax.Array"       # int32 — -1/0/+1 monotone constraint
    penalties: "jax.Array"      # float32 — per-feature gain penalty (feature_contri)
    is_categorical: "jax.Array"  # bool
    feat2phys: "jax.Array" = None    # int32 — physical X_bin column
    feat_offset: "jax.Array" = None  # int32 — bin offset inside the column
    needs_fix: "jax.Array" = None    # bool — default-bin mass elided


def pad_meta(meta: DeviceMeta, F_pad: int) -> DeviceMeta:
    """``meta`` padded to ``F_pad`` features with trivial ones (one bin, no
    constraint, penalty 1: they never split), the bundle mapping as the
    identity: for a learner that searches an unbundled set in blocks of
    columns (the feature-parallel growers, ``parallel/mesh.py``)."""
    import jax.numpy as jnp
    F = int(meta.num_bins.shape[0])

    def pad(a, fill):
        return jnp.concatenate(
            [a, jnp.full((F_pad - F,), fill, a.dtype)]) if F_pad > F else a
    return DeviceMeta(
        num_bins=pad(meta.num_bins, 1),
        default_bins=pad(meta.default_bins, 0),
        missing_types=pad(meta.missing_types, 0),
        monotone=pad(meta.monotone, 0),
        penalties=pad(meta.penalties, 1.0),
        is_categorical=pad(meta.is_categorical, False),
        feat2phys=jnp.arange(F_pad, dtype=jnp.int32),
        feat_offset=jnp.zeros(F_pad, jnp.int32),
        needs_fix=jnp.zeros(F_pad, bool),
    )


@dataclass(frozen=True)
class SplitConfig:
    """Scalar split hyperparameters (static at trace time).

    Mirrors the subset of ``Config`` read by the reference gain math
    (reference: src/treelearner/feature_histogram.hpp:446-506).
    """
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    max_delta_step: float = 0.0
    num_leaves: int = 31
    max_depth: int = -1
    # categorical split parameters (reference: config.h:378-430)
    max_cat_threshold: int = 32
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_to_onehot: int = 4
    min_data_per_group: int = 100

    @classmethod
    def from_config(cls, config) -> "SplitConfig":
        return cls(
            lambda_l1=float(config.lambda_l1),
            lambda_l2=float(config.lambda_l2),
            min_data_in_leaf=int(config.min_data_in_leaf),
            min_sum_hessian_in_leaf=float(config.min_sum_hessian_in_leaf),
            min_gain_to_split=float(config.min_gain_to_split),
            max_delta_step=float(config.max_delta_step),
            num_leaves=int(config.num_leaves),
            max_depth=int(config.max_depth),
            max_cat_threshold=int(config.max_cat_threshold),
            cat_l2=float(config.cat_l2),
            cat_smooth=float(config.cat_smooth),
            max_cat_to_onehot=int(config.max_cat_to_onehot),
            min_data_per_group=int(config.min_data_per_group),
        )


def _padded_bin_width(max_num_bin: int) -> int:
    """Pad the per-feature bin axis to the next power of two (min 8)."""
    b = 8
    while b < max_num_bin:
        b *= 2
    return b


_META_CACHE: dict = {}


def build_device_meta(dataset, config=None):
    """Build (DeviceMeta, B) from a constructed ``BinnedDataset``.

    ``B`` is the static padded bin width shared by all features.
    """
    import jax.numpy as jnp

    nbins = dataset.feature_max_bins().astype(np.int32)
    F = len(nbins)
    default_bins = np.zeros(F, dtype=np.int32)
    missing = np.zeros(F, dtype=np.int32)
    is_cat = np.zeros(F, dtype=bool)
    for inner in range(F):
        m = dataset.inner_to_mapper(inner)
        default_bins[inner] = m.default_bin
        missing[inner] = m.missing_type
        is_cat[inner] = m.bin_type == BIN_CATEGORICAL
    monotone = np.zeros(F, dtype=np.int32)
    penalties = np.ones(F, dtype=np.float32)
    if config is not None:
        mc = getattr(config, "monotone_constraints", None) or []
        fc = getattr(config, "feature_contri", None) or []
        for inner in range(F):
            orig = int(dataset.real_feature_idx[inner])
            if orig < len(mc):
                monotone[inner] = int(mc[orig])
            if orig < len(fc):
                penalties[inner] = float(fc[orig])
    B = _padded_bin_width(int(nbins.max(initial=1)))
    bundle = getattr(dataset, "bundle", None)
    if bundle is not None:
        feat2phys = bundle.feat2phys
        feat_offset = bundle.feat_offset
        needs_fix = bundle.needs_fix
    else:
        feat2phys = np.arange(F, dtype=np.int32)
        feat_offset = np.zeros(F, dtype=np.int32)
        needs_fix = np.zeros(F, dtype=bool)
    # Content-cached: equal datasets (e.g. GridSearchCV re-binning the
    # same matrix per clone) get the SAME DeviceMeta object back, which
    # keeps downstream jitted-closure caches (boosting/gbdt.py _JIT_CACHE)
    # hitting instead of recompiling per Booster.
    key = (nbins.tobytes(), default_bins.tobytes(), missing.tobytes(),
           monotone.tobytes(), penalties.tobytes(), is_cat.tobytes(),
           np.asarray(feat2phys).tobytes(),
           np.asarray(feat_offset).tobytes(),
           np.asarray(needs_fix).tobytes(), B)
    hit = _META_CACHE.get(key)
    if hit is not None:
        return hit
    meta = DeviceMeta(
        num_bins=jnp.asarray(nbins),
        default_bins=jnp.asarray(default_bins),
        missing_types=jnp.asarray(missing),
        monotone=jnp.asarray(monotone),
        penalties=jnp.asarray(penalties),
        is_categorical=jnp.asarray(is_cat),
        feat2phys=jnp.asarray(feat2phys),
        feat_offset=jnp.asarray(feat_offset),
        needs_fix=jnp.asarray(needs_fix),
    )
    if len(_META_CACHE) >= 32:
        _META_CACHE.clear()
    _META_CACHE[key] = (meta, B)
    return meta, B


def padded_phys_width(dataset) -> int:
    """Static padded bin width of the PHYSICAL columns — what the
    histogram kernels must cover (== the split width unless bundled)."""
    return _padded_bin_width(int(dataset.phys_max_bins().max(initial=1)))
