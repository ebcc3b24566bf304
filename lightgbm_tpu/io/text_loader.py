"""Text dataset loading for the CLI driver.

The analog of the reference's DatasetLoader text path (reference:
src/io/dataset_loader.cpp:168,807-1042): dense TSV/CSV files with the
label in a configurable column, optional header, weight/group columns, and
the ``<data>.weight`` / ``<data>.query`` sidecar files.  Sparse LibSVM
input is not supported (the TPU path is dense; see io/dataset.py).
"""
from __future__ import annotations

import os
from typing import List, Optional, Tuple

import numpy as np

from ..utils import log


def _sniff_delimiter(line: str) -> str:
    for d in ("\t", ",", " "):
        if d in line:
            return d
    return "\t"


_CHUNK_BYTES = 64 * 1024 * 1024


class _ParseError(Exception):
    """Native parser rejected the file; fall back to np.loadtxt."""


def _file_ncol(mm, pos: int, size: int, delim: str) -> int:
    nl = mm.find(b"\n", pos)
    first = mm[pos:(nl if nl >= 0 else size)].decode(
        "utf-8", "replace").rstrip("\r")
    return len(first.split() if delim == " " else first.split(delim))


def _mmap_windows(path: str, skiprows: int, chunk_bytes: int = _CHUNK_BYTES):
    """Yield ``(mm, lo, hi)`` newline-aligned windows over an mmap of the
    file — the parser reads straight out of the page cache, no bytes
    copies, no carry-over concatenation."""
    import mmap

    with open(path, "rb") as fh:
        size = os.fstat(fh.fileno()).st_size
        if size == 0:
            return
        mm = mmap.mmap(fh.fileno(), 0, access=mmap.ACCESS_READ)
        try:
            pos = 0
            for _ in range(skiprows):
                nl = mm.find(b"\n", pos)
                pos = (nl + 1) if nl >= 0 else size
            while pos < size:
                hi = min(pos + chunk_bytes, size)
                if hi < size:
                    nl = mm.rfind(b"\n", pos, hi)
                    if nl < pos:  # a single line longer than the window
                        nl = mm.find(b"\n", hi)
                        hi = size if nl < 0 else nl + 1
                    else:
                        hi = nl + 1
                yield mm, pos, hi
                pos = hi
        finally:
            mm.close()


def _iter_dense_chunks(path: str, delim: str, skiprows: int,
                       chunk_bytes: int = _CHUNK_BYTES):
    """Stream-parse a dense numeric text file with the native chunk parser
    (native/binning_native.cpp csv_parse — the reference's
    TextReader/PipelineReader analog, utils/text_reader.h:1-341), yielding
    row-major f64 arrays.  Raises ``_ParseError`` when the native library
    is unavailable or the file needs np.loadtxt's leniency.
    """
    from .. import native as _native
    if _native.lib() is None:
        raise _ParseError("native library unavailable")
    ncol = None
    for mm, lo, hi in _mmap_windows(path, skiprows, chunk_bytes):
        if ncol is None:
            ncol = _file_ncol(mm, lo, len(mm), delim)
        arr = _native.csv_parse(mm, delim, ncol, offset=lo, length=hi - lo)
        if arr is None:
            raise _ParseError("malformed row (inconsistent columns?)")
        if len(arr):
            yield arr


def _read_dense(path: str, delim: str, skiprows: int) -> np.ndarray:
    """Whole-file dense parse: native mmap parse with the lenient
    np.loadtxt fallback."""
    try:
        # one window over the whole file: a single exactly-sized output
        # array, no per-chunk vstack copy
        size = max(os.path.getsize(path), 1)
        parts = list(_iter_dense_chunks(path, delim, skiprows,
                                        chunk_bytes=size))
        if parts:
            return parts[0] if len(parts) == 1 else np.vstack(parts)
    except _ParseError as exc:
        log.info("Native text parse unavailable (%s); using np.loadtxt",
                 exc)
    return np.loadtxt(path, delimiter=None if delim == " " else delim,
                      skiprows=skiprows, ndmin=2, dtype=np.float64)


def _resolve_column(spec: str, names: List[str], what: str) -> Optional[int]:
    """Column spec: "" -> None, "3" -> 3, "name:foo" -> index of foo
    (reference: dataset_loader.cpp column-by-name needs a header)."""
    if spec == "":
        return None
    if spec.startswith("name:"):
        name = spec[5:]
        if name not in names:
            log.fatal(f"{what} column {name!r} not found in header")
        return names.index(name)
    try:
        return int(spec)
    except ValueError:
        log.fatal(f"Bad {what} column spec {spec!r}")


def load_text(path: str, config) -> Tuple[np.ndarray, Optional[np.ndarray],
                                          Optional[np.ndarray],
                                          Optional[np.ndarray], List[str]]:
    """Load a dense text data file.

    Returns (X, label, weight, group, feature_names); label/weight/group
    are None when absent.  ``label_column`` counts ALL file columns;
    integer weight/group/ignore indices do NOT count the label column
    (reference: config.h weight_column doc), while ``name:`` specs are
    absolute header positions.
    """
    if not os.path.exists(path):
        log.fatal(f"Data file {path} does not exist")
    with open(path) as fh:
        first = fh.readline()
    if ":" in first and not getattr(config, "header", False):
        return _load_libsvm(path, config)
    delim = _sniff_delimiter(first.rstrip("\n"))
    names: List[str] = []
    skip = 0
    if getattr(config, "header", False):
        names = [t.strip() for t in first.rstrip("\n").split(delim)]
        skip = 1
    data = _read_dense(path, delim, skip)
    ncol = data.shape[1]
    names, label_col, weight_col, group_col, keep = _column_plan(
        names, ncol, config)

    label = data[:, label_col]
    weight = data[:, weight_col] if weight_col is not None else None
    group_raw = data[:, group_col] if group_col is not None else None
    X = data[:, keep]
    feat_names = [names[i] for i in keep]

    weight, group = _load_sidecars(path, weight, None)
    return X, label, weight, group if group is not None else _group_from_col(
        group_raw), feat_names


def _column_plan(names: List[str], ncol: int, config):
    """Resolve the label/weight/group/ignore column layout of a data file
    -> (names, label_col, weight_col, group_col, keep_columns)."""
    if not names:
        names = [f"Column_{i}" for i in range(ncol)]

    label_col = _resolve_column(getattr(config, "label_column", ""),
                                names, "label")
    if label_col is None:
        label_col = 0

    def skip_label(col: Optional[int], spec) -> Optional[int]:
        """Integer weight/group/ignore indices do NOT count the label
        column (reference: config.h weight_column doc — "index starts
        from 0 and it doesn't count the label column when passing type
        is int"); name: specs are absolute."""
        if col is None or str(spec).startswith("name:"):
            return col
        return col + 1 if col >= label_col else col

    wspec = getattr(config, "weight_column", "")
    gspec = getattr(config, "group_column", "")
    weight_col = skip_label(_resolve_column(wspec, names, "weight"), wspec)
    group_col = skip_label(_resolve_column(gspec, names, "group"), gspec)

    drop = {label_col}
    if weight_col is not None:
        drop.add(weight_col)
    if group_col is not None:
        drop.add(group_col)
    ignore = getattr(config, "ignore_column", "")
    if ignore:
        for tok in str(ignore).split(","):
            tok = tok.strip()
            c = skip_label(_resolve_column(tok, names, "ignore"), tok)
            if c is not None:
                drop.add(c)
    keep = [i for i in range(ncol) if i not in drop]
    return names, label_col, weight_col, group_col, keep


def _group_from_col(group_raw):
    if group_raw is None:
        return None
    # per-row query ids -> query sizes (reference converts ordered ids)
    ids = group_raw.astype(np.int64)
    change = np.flatnonzero(np.diff(ids)) + 1
    bounds = np.concatenate([[0], change, [len(ids)]])
    return np.diff(bounds)


def _load_libsvm(path: str, config):
    """Sparse ``label [qid:Q] idx:val ...`` rows (the MSLR-WEB30K format)
    -> a scipy CSR matrix (implicit entries are 0.0, which the zero-bin /
    SparseBin-analog handling treats natively; reference:
    dataset_loader.cpp sparse parser).  Native chunked parser with a
    Python fallback; ``qid:`` tokens become query boundaries unless a
    ``.query`` sidecar overrides them."""
    from .. import native as _native

    labels_l, qids_l, trip = [], [], []
    max_idx = -1
    if _native.lib() is not None:
        for mm, lo, hi in _mmap_windows(path, 0):
            out = _native.libsvm_parse(mm, offset=lo, length=hi - lo)
            if out is None:
                labels_l = []
                break  # malformed for the strict parser: Python fallback
            lab, qid, indptr, idx, vals, mf = out
            labels_l.append(lab)
            qids_l.append(qid)
            trip.append((indptr, idx, vals))
            max_idx = max(max_idx, mf)
    if labels_l:
        label = np.concatenate(labels_l)
        qids = np.concatenate(qids_l)
        counts = np.concatenate([np.diff(t[0]) for t in trip])
        indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        indices = np.concatenate([t[1] for t in trip])
        values = np.concatenate([t[2] for t in trip])
    else:
        # lenient Python fallback (also exercised with
        # LIGHTGBM_TPU_NO_NATIVE=1)
        labels_py: List[float] = []
        qids_py: List[int] = []
        indptr_py = [0]
        idx_py: List[int] = []
        val_py: List[float] = []
        with open(path) as fh:
            for line in fh:
                toks = line.split()
                if not toks:
                    continue
                labels_py.append(float(toks[0]))
                q = -1
                for tok in toks[1:]:
                    i, _, v = tok.partition(":")
                    if i == "qid":
                        q = int(v)
                        continue
                    fi = int(i)
                    idx_py.append(fi)
                    val_py.append(float(v))
                    if fi > max_idx:
                        max_idx = fi
                qids_py.append(q)
                indptr_py.append(len(idx_py))
        label = np.asarray(labels_py)
        qids = np.asarray(qids_py, np.int64)
        indptr = np.asarray(indptr_py, np.int64)
        indices = np.asarray(idx_py, np.int32)
        values = np.asarray(val_py, np.float64)

    import scipy.sparse as sp
    X = sp.csr_matrix((values, indices, indptr),
                      shape=(len(label), max_idx + 1))
    names = [f"Column_{i}" for i in range(max_idx + 1)]
    has_q = qids >= 0
    qid_group = None
    if len(qids) and has_q.any():
        if has_q.all():
            qid_group = _group_from_col(qids)
        else:
            log.warning("LibSVM file has qid: on only %d of %d rows; "
                        "ignoring qids (provide a .query sidecar or "
                        "annotate every row)", int(has_q.sum()), len(qids))
    weight, group = _load_sidecars(path, None, None)
    return X, label, weight, group if group is not None else qid_group, names


def _load_sidecars(path: str, weight, group):
    """``<data>.weight`` / ``<data>.query`` / ``<data>.group`` files
    (reference: dataset_loader.cpp LoadWeights/LoadQueryBoundaries)."""
    wpath = path + ".weight"
    if weight is None and os.path.exists(wpath):
        weight = np.loadtxt(wpath, dtype=np.float64, ndmin=1)
        log.info("Loading weights from %s", wpath)
    if group is None:
        for suffix in (".query", ".group"):
            qpath = path + suffix
            if os.path.exists(qpath):
                group = np.loadtxt(qpath, dtype=np.int64, ndmin=1)
                log.info("Loading query boundaries from %s", qpath)
                break
    return weight, group


def load_text_two_round(path: str, config, categorical_features=(),
                        reference=None):
    """Two-pass streaming load: construct a ``BinnedDataset`` from a text
    file WITHOUT materializing the full float64 matrix (the reference's
    ``two_round`` path: sample on the first read, push binned rows on the
    second — dataset_loader.cpp:807-827, config.h two_round).

    Pass 1 streams the file counting rows, reservoir-sampling
    ``bin_construct_sample_cnt`` rows for bin finding, and collecting the
    label/weight/group columns.  Pass 2 streams again, binning each chunk
    straight into the preallocated ``X_bin``.  Peak memory is one parsed
    chunk + the binned matrix (1-2 bytes/cell) instead of 8 bytes/cell.

    Returns ``(handle, label, weight, group, feature_names)`` where
    ``handle`` is a constructed BinnedDataset.  With ``reference`` given
    (a constructed BinnedDataset), its bin mappers are reused and the
    sampling pass only counts rows (validation alignment).
    """
    from .dataset import BinnedDataset, Metadata

    if not os.path.exists(path):
        log.fatal(f"Data file {path} does not exist")
    with open(path) as fh:
        first = fh.readline()
    if ":" in first and not getattr(config, "header", False):
        # LibSVM streams through the chunked ingest reader: reservoir
        # bin-sampling over the whole stream, chunk-at-a-time binning —
        # the full sparse matrix (and its dense sample slice) is never
        # materialized, and the constructed dataset bit-matches the
        # in-RAM from_csr path (tests/test_ingest_stream.py)
        from ..ingest.readers import LibSVMSource
        from ..ingest.stream import chunk_rows_from_config, ingest_dataset
        log.info("two_round: streaming LibSVM input through the "
                 "chunked ingest reader")
        src = LibSVMSource(path,
                           chunk_rows=chunk_rows_from_config(config))
        # two_round keeps the pre-ingest contract: the WHOLE file, in
        # RAM — the tpu_ingest_shards/tpu_ingest_memmap knobs (and the
        # memmap env var) only govern the explicit tpu_ingest path, so
        # an ambient ingest config can't silently halve this dataset
        # or write X_bin files from an unrelated job's location
        handle = ingest_dataset(
            src, config, categorical_features=categorical_features,
            reference=reference, num_shards=1, shard_id=0,
            memmap_path="")
        md = handle.metadata
        group_sizes = (np.diff(md.query_boundaries)
                       if md.query_boundaries is not None else None)
        weight, group = _load_sidecars(path, md.weights, group_sizes)
        if weight is not None and md.weights is None:
            handle.metadata.set_weights(weight)
        if group is not None and group_sizes is None:
            handle.metadata.set_query(group)
        return handle, md.label, weight, group, list(handle.feature_names)
    delim = _sniff_delimiter(first.rstrip("\n"))
    names: List[str] = []
    skip = 0
    if getattr(config, "header", False):
        names = [t.strip() for t in first.rstrip("\n").split(delim)]
        skip = 1
    try:
        return _two_round_streamed(path, config, categorical_features,
                                   reference, names, skip, delim)
    except _ParseError as exc:
        # the strict native parser rejected the file (or is unavailable):
        # degrade to the lenient in-memory path rather than erroring
        log.warning("two_round streaming unavailable (%s); falling back "
                    "to in-memory loading", exc)
        X, label, weight, group, fnames = load_text(path, config)
        cats = []
        for c in categorical_features or ():
            if isinstance(c, str):
                if c in fnames:
                    cats.append(fnames.index(c))
            else:
                cats.append(int(c))
        handle = BinnedDataset.from_matrix(
            X, config, categorical_features=cats, feature_names=fnames,
            reference=reference)
        return handle, label, weight, group, fnames


def _two_round_streamed(path, config, categorical_features, reference,
                        names, skip, delim):
    from .dataset import BinnedDataset, Metadata

    # ---- pass 1: count rows, parse ONLY the side columns, and
    # reservoir-sample line BYTE RANGES (the sampled lines are fully
    # parsed once at the end — ~200k lines instead of the whole file)
    from .. import native as _native
    sample_cnt = int(getattr(config, "bin_construct_sample_cnt", 200000))
    rng = np.random.default_rng(getattr(config, "data_random_seed", 1))
    plan = None
    n_rows = 0
    res_off = res_len = None  # sampled line byte ranges
    labels, weights, groups = [], [], []
    side_vals = {}
    for mm, lo, hi in _mmap_windows(path, skip):
        if plan is None:
            if _native.lib() is None:
                log.fatal("two_round loading needs the native parser "
                          "(g++ unavailable?); set two_round=false")
            ncol = _file_ncol(mm, lo, len(mm), delim)
            plan = _column_plan(names, ncol, config)
            names, label_col, weight_col, group_col, keep = plan
            side_cols = sorted({label_col}
                               | ({weight_col} if weight_col is not None
                                  else set())
                               | ({group_col} if group_col is not None
                                  else set()))
            side_pos = {c: i for i, c in enumerate(side_cols)}
        sv = _native.csv_parse_cols(mm, delim, side_cols, offset=lo,
                                    length=hi - lo)
        if sv is None:
            raise _ParseError("malformed row (inconsistent columns?)")
        labels.append(sv[:, side_pos[label_col]].copy())
        if weight_col is not None:
            weights.append(sv[:, side_pos[weight_col]].copy())
        if group_col is not None:
            groups.append(sv[:, side_pos[group_col]].copy())
        if reference is None:
            offs = _native.csv_line_offsets(mm, offset=lo, length=hi - lo)
            offs = offs[:len(sv)]  # a dropped trailing blank line
            lens = np.diff(np.append(offs, hi - lo)).astype(np.int64)
            offs = offs + lo
            if res_off is None:
                res_off = np.empty(sample_cnt, np.int64)
                res_len = np.empty(sample_cnt, np.int64)
            filled = min(n_rows, sample_cnt)
            take = min(max(sample_cnt - filled, 0), len(offs))
            if take:
                res_off[filled:filled + take] = offs[:take]
                res_len[filled:filled + take] = lens[:take]
            if take < len(offs):
                # Algorithm R, vectorized per chunk: row with global index
                # g replaces a random slot with probability sample_cnt/(g+1)
                gi = np.arange(n_rows + take, n_rows + len(offs))
                slots = rng.integers(0, gi + 1)
                hit = slots < sample_cnt
                res_off[slots[hit]] = offs[take:][hit]
                res_len[slots[hit]] = lens[take:][hit]
        n_rows += len(sv)
    if n_rows == 0:
        log.fatal(f"Data file {path} is empty")
    label = np.concatenate(labels)
    weight = np.concatenate(weights) if weights else None
    group_raw = np.concatenate(groups) if groups else None
    feat_names = [names[i] for i in keep]
    # name-based categorical specs resolve against the KEPT feature names
    # (same convention as basic.Dataset._resolve_categorical)
    cats = []
    for c in categorical_features or ():
        if isinstance(c, str):
            if c in feat_names:
                cats.append(feat_names.index(c))
            else:
                log.warning("categorical_feature %r not found in feature "
                            "names; ignored", c)
        else:
            cats.append(int(c))
    categorical_features = sorted(set(cats))

    # ---- mappers from the sample --------------------------------------
    if reference is None:
        m = min(n_rows, sample_cnt)
        with open(path, "rb") as fh:
            import mmap as _mmap
            mm = _mmap.mmap(fh.fileno(), 0, access=_mmap.ACCESS_READ)
            try:
                # per-line newline: the file's FINAL line may lack one, and
                # it can land in any reservoir slot
                pieces = []
                for o, l in zip(res_off[:m], res_len[:m]):
                    b = bytes(mm[int(o):int(o + l)])
                    pieces.append(b if b.endswith(b"\n") else b + b"\n")
                joined = b"".join(pieces)
            finally:
                mm.close()
        sample_full = _native.csv_parse(joined, delim, len(names))
        if sample_full is None:
            raise _ParseError("malformed sampled row")
        sample = sample_full[:, keep]
        handle = BinnedDataset.from_sample(
            sample, n_rows, config,
            categorical_features=categorical_features,
            feature_names=feat_names)
    else:
        log.check(len(keep) == reference.num_total_features,
                  "validation data has a different number of features")
        handle = BinnedDataset()
        handle.num_data = n_rows
        handle.num_total_features = len(keep)
        handle.metadata = Metadata(n_rows)
        handle.bin_mappers = reference.bin_mappers
        handle.used_feature_map = reference.used_feature_map
        handle.real_feature_idx = reference.real_feature_idx
        handle.bin_offsets = reference.bin_offsets
        handle.feature_names = reference.feature_names
        handle.max_bin = reference.max_bin
        handle.bundle = reference.bundle

    # ---- pass 2: stream rows into the binned matrix -------------------
    from ..utils.timetag import timetag
    handle._alloc_X()
    with timetag("binarize", record=handle.setup_trace, rows=n_rows,
                 columns=int(handle.X_bin.shape[1]),
                 bytes=int(handle.X_bin.nbytes)):
        row0 = 0
        for chunk in _iter_dense_chunks(path, delim, skip):
            handle._binarize_chunk(chunk[:, keep], row0)
            row0 += len(chunk)
    log.check(row0 == n_rows, "data file changed between two_round passes")

    weight, group = _load_sidecars(path, weight, None)
    if group is None:
        group = _group_from_col(group_raw)
    return handle, label, weight, group, feat_names
