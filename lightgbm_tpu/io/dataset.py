"""Binned dataset: the HBM-resident training representation.

TPU-native rebuild of the reference's ``Dataset``/``Metadata``/``DatasetLoader``
(reference: include/LightGBM/dataset.h:41-669, src/io/dataset_loader.cpp).
Instead of per-feature-group ``Bin`` columns with sparse/dense variants and
most-frequent-bin elision, the TPU representation is a single dense
unsigned-int matrix ``X_bin[num_data, num_features]`` (uint8 normally; widened
to uint16/uint32 when a categorical feature exceeds 256 bins) laid out for
streaming into the Pallas histogram kernel, plus a flat bin-offset table so
all features share one histogram address space (the analog of the reference's
``NumTotalBin`` flat layout). Sparse storage is intentionally dropped: EFB
densifies exclusive sparse features into shared columns instead
(SURVEY.md §7 "hard parts" #5 documents the deviation).
"""
from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from .. import obs
from ..config import Config
from ..utils import log
from ..utils.random import Random
from ..utils.timetag import timetag
from .binning import (BIN_CATEGORICAL, BIN_NUMERICAL, MISSING_NAN, MISSING_NONE,
                      MISSING_ZERO, BinMapper)


class Metadata:
    """Labels, weights, query boundaries and init scores
    (reference: Metadata, dataset.h:41-250)."""

    def __init__(self, num_data: int = 0):
        self.num_data = num_data
        self.label: Optional[np.ndarray] = None          # float32 [num_data]
        self.weights: Optional[np.ndarray] = None        # float32 [num_data]
        self.query_boundaries: Optional[np.ndarray] = None  # int32 [num_queries+1]
        self.query_weights: Optional[np.ndarray] = None
        self.init_score: Optional[np.ndarray] = None     # float64 [num_data * k]

    def set_label(self, label) -> None:
        label = np.ascontiguousarray(label, dtype=np.float32).ravel()
        log.check(len(label) == self.num_data, "label length != num_data")
        self.label = label

    def set_weights(self, weights) -> None:
        if weights is None:
            self.weights = None
            return
        weights = np.ascontiguousarray(weights, dtype=np.float32).ravel()
        log.check(len(weights) == self.num_data, "weights length != num_data")
        log.check(bool((weights >= 0).all()), "weights should be non-negative")
        self.weights = weights
        self._update_query_weights()

    def set_query(self, group) -> None:
        """``group`` is per-query sizes (LightGBM convention) or boundaries."""
        if group is None:
            self.query_boundaries = None
            self.query_weights = None
            return
        group = np.ascontiguousarray(group, dtype=np.int64).ravel()
        if group.sum() == self.num_data:  # sizes
            self.query_boundaries = np.concatenate(
                [[0], np.cumsum(group)]).astype(np.int32)
        elif len(group) >= 1 and group[0] == 0 and group[-1] == self.num_data:
            self.query_boundaries = group.astype(np.int32)
        else:
            log.fatal("Initial sizes of queries do not sum to num_data")
        self._update_query_weights()

    def _update_query_weights(self) -> None:
        if self.query_boundaries is None or self.weights is None:
            self.query_weights = None
            return
        b = self.query_boundaries
        sums = np.add.reduceat(self.weights, b[:-1])
        cnts = np.diff(b)
        self.query_weights = (sums / np.maximum(cnts, 1)).astype(np.float32)

    def set_init_score(self, init_score) -> None:
        if init_score is None:
            self.init_score = None
            return
        init_score = np.ascontiguousarray(init_score, dtype=np.float64).ravel()
        log.check(len(init_score) % self.num_data == 0,
                  "init_score length must be a multiple of num_data")
        self.init_score = init_score

    @property
    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None else len(self.query_boundaries) - 1


def _construct_root(path: str, **attrs):
    """``(trace, span)`` of one construct path: the ``setup/dataset`` root
    that records the path's spans into a new ``obs.SetupTrace``, telemetry
    on or off; under another path's root (``from_sample`` called by
    ``from_matrix``) that root's trace and no span of its own.  The path
    leaves ``trace`` on the data set it returns (``setup_trace``)."""
    trace = obs.open_setup_trace()
    if trace is not None:
        return trace, contextlib.nullcontext()
    trace = obs.SetupTrace()
    return trace, timetag("setup/dataset", record=trace, path=path, **attrs)


class BinnedDataset:
    """The constructed training dataset (reference: Dataset, dataset.h:283).

    Attributes
    ----------
    X_bin : np.ndarray  uint8/uint16/uint32 [num_data, num_features]
        Binned feature matrix (only non-trivial features).
    bin_mappers : list[BinMapper]
        One per *original* feature column (trivial ones included).
    used_feature_map : np.ndarray int32 [num_total_features]
        original feature → inner column index, -1 if unused
        (reference: used_feature_map_, dataset.h:629).
    bin_offsets : np.ndarray int32 [num_features+1]
        flat histogram offsets per inner column.
    """

    def __init__(self):
        self.num_data: int = 0
        self.num_total_features: int = 0
        self.X_bin: Optional[np.ndarray] = None
        self.bin_mappers: List[BinMapper] = []
        self.used_feature_map: Optional[np.ndarray] = None
        self.real_feature_idx: Optional[np.ndarray] = None  # inner → original
        self.bin_offsets: Optional[np.ndarray] = None
        self.metadata: Metadata = Metadata()
        self.feature_names: List[str] = []
        self.max_bin: int = 255
        # EFB bundle info (io.bundling.BundleInfo; None = no bundling)
        self.bundle = None
        # the spans of the construct path that built this data set
        # (obs.SetupTrace; None where none did: a subset, a loaded file)
        self.setup_trace = None

    # ------------------------------------------------------------------
    @property
    def num_features(self) -> int:
        """Number of used (inner) features — NOT physical columns; with
        EFB several features share one ``X_bin`` column."""
        if self.real_feature_idx is not None:
            return len(self.real_feature_idx)
        return 0 if self.X_bin is None else self.X_bin.shape[1]

    @property
    def num_phys_features(self) -> int:
        """Physical ``X_bin`` columns (== num_features unless bundled)."""
        return 0 if self.X_bin is None else self.X_bin.shape[1]

    def phys_max_bins(self) -> np.ndarray:
        """Bins per PHYSICAL column (kernel histogram width)."""
        if self.bundle is not None:
            return self.bundle.phys_num_bin
        return self.feature_max_bins()

    @property
    def num_total_bin(self) -> int:
        return 0 if self.bin_offsets is None else int(self.bin_offsets[-1])

    def num_bin(self, inner_feature: int) -> int:
        return int(self.bin_offsets[inner_feature + 1] - self.bin_offsets[inner_feature])

    def inner_to_mapper(self, inner_feature: int) -> BinMapper:
        return self.bin_mappers[int(self.real_feature_idx[inner_feature])]

    # ------------------------------------------------------------------
    @classmethod
    def from_matrix(cls, data: np.ndarray, config: Config,
                    categorical_features: Sequence[int] = (),
                    feature_names: Optional[List[str]] = None,
                    reference: Optional["BinnedDataset"] = None,
                    sample_indices: Optional[np.ndarray] = None) -> "BinnedDataset":
        """Construct from a dense float matrix.

        Mirrors the reference path DatasetLoader::CostructFromSampleData →
        BinMapper::FindBin → Dataset::Construct (dataset_loader.cpp:574,
        bin.cpp:325, dataset.cpp:265): sample rows, find per-feature bin
        bounds, then binarize every row. With ``reference`` given, bin mappers
        are shared so validation data aligns with the training bin space
        (reference: LoadFromFileAlignWithOtherDataset, dataset_loader.cpp:230).
        """
        data = np.asarray(data)
        if data.dtype not in (np.float32, np.float64):
            data = data.astype(np.float64)
        n, p = data.shape
        if n == 0:
            log.fatal("Cannot construct a Dataset from an empty matrix (0 rows)")

        trace, root = _construct_root("from_matrix", rows=n, columns=p)
        if reference is not None:
            ds = cls._aligned(reference, n, p)
            with root:
                ds._alloc_X()
                ds._binarize_span(lambda: ds._binarize_chunk(data, 0))
            ds.setup_trace = trace
            return ds

        with root:
            # ---- sample rows for bin finding ----
            with timetag("sample"):
                sample_cnt = min(config.bin_construct_sample_cnt, n)
                if sample_indices is None:
                    rng = Random(config.data_random_seed)
                    sample_indices = (
                        np.arange(n, dtype=np.int64) if sample_cnt >= n
                        else rng.sample(n, sample_cnt).astype(np.int64))
                sample = data[sample_indices]
            ds = cls.from_sample(sample, n, config,
                                 categorical_features=categorical_features,
                                 feature_names=feature_names)
            ds._alloc_X()
            ds._binarize_span(lambda: ds._binarize_chunk(data, 0))
        return ds

    @classmethod
    def _aligned(cls, reference: "BinnedDataset", n: int,
                 p: int) -> "BinnedDataset":
        """An empty data set of ``n`` rows in ``reference``'s bin space."""
        ds = cls()
        ds.num_data = n
        ds.num_total_features = p
        ds.metadata = Metadata(n)
        log.check(p == reference.num_total_features,
                  "validation data has a different number of features")
        ds.bin_mappers = reference.bin_mappers
        ds.used_feature_map = reference.used_feature_map
        ds.real_feature_idx = reference.real_feature_idx
        ds.bin_offsets = reference.bin_offsets
        ds.feature_names = reference.feature_names
        ds.max_bin = reference.max_bin
        ds.bundle = reference.bundle
        return ds

    def _binarize_span(self, fill) -> None:
        """``binarize``: ``fill()`` writes every row's bins into ``X_bin``
        (and converts what it must first: CSR to CSC)."""
        with timetag("binarize", rows=self.num_data) as span:
            fill()
            span.attrs.update(columns=int(self.X_bin.shape[1]),
                              bytes=int(self.X_bin.nbytes))

    @classmethod
    def from_sample(cls, sample: np.ndarray, num_data: int, config: Config,
                    categorical_features: Sequence[int] = (),
                    feature_names: Optional[List[str]] = None) -> "BinnedDataset":
        """Build mappers/feature-map/bundles from a row SAMPLE, leaving
        ``X_bin`` unallocated — the constructor half of the reference's
        two-pass loading (DatasetLoader::ConstructFromSampleData +
        two_round, dataset_loader.cpp:574,807-827).  Callers then
        ``_alloc_X()`` and stream rows through ``_binarize_chunk``.
        """
        trace, root = _construct_root("from_sample", rows=int(num_data),
                                      columns=int(sample.shape[1]))
        with root:
            ds = cls._from_sample(sample, num_data, config,
                                  categorical_features, feature_names)
        ds.setup_trace = trace
        return ds

    @classmethod
    def _from_sample(cls, sample, num_data: int, config: Config,
                     categorical_features: Sequence[int],
                     feature_names: Optional[List[str]]) -> "BinnedDataset":
        ds = cls()
        p = sample.shape[1]
        ds.num_data = int(num_data)
        ds.num_total_features = p
        ds.metadata = Metadata(ds.num_data)
        ds.max_bin = config.max_bin
        ds.feature_names = (list(feature_names) if feature_names
                            else [f"Column_{i}" for i in range(p)])
        sample_csc = sample.tocsc() if hasattr(sample, "tocsc") else None
        if sample_csc is None:
            # multi-host: pool every host's sample so all processes derive
            # identical mappers; sample-vs-data ratios below must then use
            # the GLOBAL row count (no-op single-host;
            # parallel/distributed.py)
            from ..parallel.distributed import global_bin_sample
            sample, n_global = global_bin_sample(sample, ds.num_data)
        else:
            # multi-host sparse: pool the samples as COO triplets so
            # every process derives identical mappers (no densifying)
            from ..parallel.distributed import global_bin_sample_sparse
            sample_csc, n_global = global_bin_sample_sparse(
                sample_csc, ds.num_data)
            sample = sample_csc

        cat_set = set(int(c) for c in categorical_features)
        ds.bin_mappers = []
        forced = _load_forced_bins(config.forcedbins_filename, p, config.max_bin)
        # min-data filter threshold scaled to the bin-finding sample
        # (reference: dataset_loader.cpp:599 filter_cnt)
        filter_cnt = int(config.min_data_in_leaf * sample.shape[0] / n_global)
        mbf = [int(v) for v in (config.max_bin_by_feature or [])]
        if mbf:
            # reference: dataset_loader.cpp:438-441
            log.check(len(mbf) == p, "max_bin_by_feature should be the "
                      "same size as feature number")
            log.check(min(mbf) > 1,
                      "max_bin_by_feature values should be greater than 1")
        with timetag("bin_find", features=p,
                     sample_rows=int(sample.shape[0])):
            for j in range(p):
                if sample_csc is not None:
                    # only stored entries can be non-zero; implicit zeros
                    # are exactly the dropped |v| <= kZeroThreshold values
                    lo, hi = sample_csc.indptr[j], sample_csc.indptr[j + 1]
                    col = np.asarray(sample_csc.data[lo:hi], np.float64)
                else:
                    col = sample[:, j]
                # drop "zero" values (|v| <= kZeroThreshold); NaN compares
                # False so NaNs are kept for the missing-type decision
                non_zero = col[~((col > -1e-35) & (col <= 1e-35))]
                mapper = BinMapper()
                bt = BIN_CATEGORICAL if j in cat_set else BIN_NUMERICAL
                mapper.find_bin(non_zero, sample.shape[0],
                                mbf[j] if mbf else config.max_bin,
                                config.min_data_in_bin, filter_cnt, bt,
                                config.use_missing, config.zero_as_missing,
                                forced.get(j))
                ds.bin_mappers.append(mapper)
        ds._finalize_features()
        tl = getattr(config, "tree_learner", "serial")
        wanted = config.enable_bundle and len(ds.real_feature_idx) >= 2
        why_off = ("max_bin=%d is over 255" % config.max_bin
                   if config.max_bin > 255 else
                   "tree_learner=%s; a dataset is bundled only when "
                   "constructed for the serial learner" % tl
                   if tl != "serial" else None)
        if wanted and why_off:
            log.info("EFB is off (%s): every feature keeps a column of its "
                     "own", why_off)
        elif wanted:
            from .bundling import build_bundles
            # wide-sparse datasets get uint16-wide bundle columns so EFB
            # can pack hundreds of features per column (the histogram
            # switches to the scatter path past 32k physical bins)
            wide = len(ds.real_feature_idx) > 2048
            with timetag("bundle",
                         features=len(ds.real_feature_idx)) as span:
                bundle = build_bundles(
                    ds.bin_mappers, ds.real_feature_idx, sample, n_global,
                    config.max_conflict_rate,
                    max_bins_per_group=4096 if wide else 256)
                span.attrs["columns"] = int(bundle.num_phys)
            if not bundle.is_trivial:
                ds.bundle = bundle
        if obs.enabled():
            obs.event("dataset", num_data=ds.num_data,
                      num_total_features=p,
                      num_used_features=int(len(ds.real_feature_idx)),
                      total_bins=int(ds.bin_offsets[-1]),
                      bundled=getattr(ds, "bundle", None) is not None,
                      sample_rows=int(sample.shape[0]))
        return ds

    def _finalize_features(self) -> None:
        used = [j for j, m in enumerate(self.bin_mappers) if not m.is_trivial]
        self.used_feature_map = np.full(self.num_total_features, -1, dtype=np.int32)
        for inner, j in enumerate(used):
            self.used_feature_map[j] = inner
        self.real_feature_idx = np.asarray(used, dtype=np.int32)
        nbins = [self.bin_mappers[j].num_bin for j in used]
        self.bin_offsets = np.concatenate([[0], np.cumsum(nbins)]).astype(np.int32)
        if not used:
            log.warning("There are no meaningful features, as all feature values are constant.")

    @classmethod
    def from_csr(cls, X, config: Config,
                 categorical_features: Sequence[int] = (),
                 feature_names: Optional[List[str]] = None,
                 reference: Optional["BinnedDataset"] = None) -> "BinnedDataset":
        """Construct from a scipy.sparse matrix WITHOUT densifying the raw
        values — the memory-bounded replacement for the reference's
        ``SparseBin`` streams (src/io/sparse_bin.hpp:72,
        ordered_sparse_bin.hpp:1; trade-off at bin.h:224-277).

        Bin finding reads stored entries per CSC column; EFB packs the
        mutually-exclusive (within ``max_conflict_rate``) sparse features
        into shared physical columns; binarization scatters only stored
        non-default bins.  Peak memory is the CSC copy + the binned
        matrix — never rows x features x 8 bytes.  Genuinely conflicting
        wide data that EFB cannot pack still materializes one physical
        column per feature; raise ``max_conflict_rate`` (the reference's
        own EFB knob) to trade exactness for packing.
        """
        import scipy.sparse as sp

        X = X.tocsr() if not sp.issparse(X) or X.format != "csr" else X
        n, p = X.shape
        if n == 0:
            log.fatal("Cannot construct a Dataset from an empty matrix (0 rows)")

        trace, root = _construct_root("from_csr", rows=n, columns=p)
        if reference is not None:
            ds = cls._aligned(reference, n, p)
            with root:
                ds._binarize_span(lambda: ds._binarize_csc(X.tocsc()))
            ds.setup_trace = trace
            return ds

        with root:
            with timetag("sample"):
                sample_cnt = min(config.bin_construct_sample_cnt, n)
                rng = Random(config.data_random_seed)
                sample_indices = (
                    np.arange(n, dtype=np.int64) if sample_cnt >= n
                    else rng.sample(n, sample_cnt).astype(np.int64))
                sample = X[sample_indices]
            ds = cls.from_sample(sample, n, config,
                                 categorical_features=categorical_features,
                                 feature_names=feature_names)
            ds._binarize_span(lambda: ds._binarize_csc(X.tocsc()))
        return ds

    def _binarize_csc(self, X_csc) -> None:
        """Scatter stored non-default bins into the physical matrix.

        Unbundled columns init to the feature's default bin (the bin of
        value 0.0 — implicit entries); bundle columns init to physical
        bin 0 (= every member at default, io/bundling.py layout)."""
        from .binning import BIN_CATEGORICAL

        used = self.real_feature_idx
        groups = (self.bundle.groups if self.bundle is not None
                  else [[i] for i in range(len(used))])
        self._alloc_X()  # single source of the widest/dtype ladder
        X = self.X_bin
        X.fill(0)  # implicit entries: bin 0 until default-bin init below
        dtype = X.dtype
        indptr, indices, data = X_csc.indptr, X_csc.indices, X_csc.data
        for gp, members in enumerate(groups):
            if len(members) == 1:
                inner = members[0]
                j = int(used[inner])
                m = self.bin_mappers[j]
                lo, hi = indptr[j], indptr[j + 1]
                fb = np.asarray(m.value_to_bin(
                    np.asarray(data[lo:hi], np.float64)))
                if m.default_bin:
                    X[:, gp] = m.default_bin
                X[indices[lo:hi], gp] = fb.astype(dtype)
                continue
            for inner in members:
                j = int(used[inner])
                m = self.bin_mappers[j]
                lo, hi = indptr[j], indptr[j + 1]
                fb = np.asarray(m.value_to_bin(
                    np.asarray(data[lo:hi], np.float64)))
                nz = fb != m.default_bin
                off = self.bundle.feat_offset[inner]
                X[indices[lo:hi][nz], gp] = (off + fb[nz]).astype(dtype)
        self.X_bin = X

    def _bin_matrix_spec(self):
        """``(columns, dtype)`` of the physical bin matrix — the single
        source of the width/dtype ladder, shared by the in-RAM
        ``_alloc_X`` and the streaming ingestion path's memmap
        allocation (ingest/stream.py)."""
        if self.bundle is not None:
            widest = int(max(self.bundle.phys_num_bin.max(initial=0),
                             self.feature_max_bins().max(initial=0)))
            cols = self.bundle.num_phys
        else:
            # size storage by the ACTUAL bin counts: categorical bin
            # finding can exceed max_bin (reference sizes by num_bin,
            # bin.cpp CreateBin)
            widest = int(self.feature_max_bins().max(initial=0))
            cols = len(self.real_feature_idx)
        dtype = (np.uint8 if widest <= 256
                 else np.uint16 if widest <= 65536 else np.uint32)
        if dtype != np.uint8 and self.max_bin <= 256:
            log.warning(
                "A feature has %d bins (> 256, from a high-cardinality "
                "categorical); the whole binned matrix is widened to %s",
                widest, np.dtype(dtype).name)
        return cols, dtype

    def _alloc_X(self) -> None:
        """Allocate the binned matrix for ``num_data`` rows (filled by
        ``_binarize_chunk`` — whole-matrix or streaming two_round)."""
        cols, dtype = self._bin_matrix_spec()
        self.X_bin = np.empty((self.num_data, cols), dtype=dtype)

    def _binarize(self, data: np.ndarray) -> None:
        self._alloc_X()
        self._binarize_chunk(data, 0)

    def _binarize_chunk(self, data: np.ndarray, row0: int) -> None:
        """Bin ``data``'s rows into ``X_bin[row0:row0+len(data)]``."""
        if self.bundle is not None:
            self._binarize_bundled_chunk(data, row0)
            return
        used = self.real_feature_idx
        n = len(data)
        X = self.X_bin[row0:row0 + n]
        dtype = X.dtype
        from .. import native as _native
        from .binning import BIN_NUMERICAL, MISSING_NAN
        fast = _native.lib() is not None and dtype == np.uint8
        # one contiguous transpose of ONLY the used numerical columns:
        # per-feature reads become sequential instead of 8-bytes-per-
        # cache-line strided column walks, without doubling peak memory
        # on wide matrices with unused/categorical columns
        dt, dt_row = None, {}
        if fast and data.dtype == np.float64:
            num_cols = [int(j) for j in used
                        if self.bin_mappers[int(j)].bin_type == BIN_NUMERICAL]
            if num_cols:
                # fill a preallocated transpose column-by-column: one extra
                # copy of the numerical submatrix, never two at once
                dt = np.empty((len(num_cols), n), np.float64)
                for r, j in enumerate(num_cols):
                    dt[r] = data[:, j]
                dt_row = {j: r for r, j in enumerate(num_cols)}
        for inner, j in enumerate(used):
            m = self.bin_mappers[int(j)]
            if dt is not None and int(j) in dt_row:
                ns = m.num_bin - (1 if m.missing_type == MISSING_NAN else 0)
                _native.binarize_numerical_u8(
                    dt[dt_row[int(j)]], m.bin_upper_bound, ns - 1,
                    m.missing_type, m.num_bin, X[:, inner])
            else:
                X[:, inner] = m.value_to_bin(data[:, int(j)]).astype(dtype)

    def _binarize_bundled(self, data: np.ndarray) -> None:
        self._alloc_X()
        self._binarize_bundled_chunk(data, 0)

    def _binarize_bundled_chunk(self, data: np.ndarray, row0: int) -> None:
        """Binarize into EFB physical columns (see io/bundling.py layout;
        reference: Dataset::PushOneRow -> FeatureGroup::PushData,
        dataset.h:333-359)."""
        from .bundling import encode_column
        bundle = self.bundle
        used = self.real_feature_idx
        n = len(data)
        X = self.X_bin[row0:row0 + n]
        dtype = X.dtype
        for gp, members in enumerate(bundle.groups):
            if len(members) == 1:
                inner = members[0]
                m = self.bin_mappers[int(used[inner])]
                X[:, gp] = m.value_to_bin(data[:, int(used[inner])]).astype(dtype)
                continue
            mappers = [self.bin_mappers[int(used[inner])]
                       for inner in members]
            feat_bins = [np.asarray(m.value_to_bin(data[:, int(used[i])]))
                         for m, i in zip(mappers, members)]
            X[:, gp] = encode_column(
                bundle, members, feat_bins,
                [m.default_bin for m in mappers], n, dtype)

    # ------------------------------------------------------------------
    def create_valid(self, data: np.ndarray) -> "BinnedDataset":
        """Bin a validation matrix in this dataset's bin space."""
        return BinnedDataset.from_matrix(data, Config(), reference=self)

    def feature_max_bins(self) -> np.ndarray:
        """num_bin per inner feature, int32 [num_features]."""
        return np.diff(self.bin_offsets).astype(np.int32)


def _load_forced_bins(path: str, num_features: int, max_bin: int) -> Dict[int, List[float]]:
    """Read forced bin bounds from JSON: [{"feature": i, "bin_upper_bound":
    [...]}] (reference: DatasetLoader::GetForcedBins, dataset_loader.cpp:1246)."""
    if not path:
        return {}
    import json
    with open(path) as fh:
        entries = json.load(fh)
    out: Dict[int, List[float]] = {}
    for e in entries:
        j = int(e["feature"])
        if 0 <= j < num_features:
            bounds = sorted(float(x) for x in e["bin_upper_bound"])[: max_bin]
            out[j] = bounds
    return out
