"""Two-pass streaming construction of ``BinnedDataset`` — bounded host
memory, no raw [N, F] matrix, shard-aware.

The reference architecture separates exactly these concerns (PAPER.md
layers 2-3): ``DatasetLoader`` samples, finds bins, then streams rows
through ``BinMapper``s; ``Network`` syncs the mappers so every rank bins
identically.  This module composes the repo's existing primitives the
same way:

- **pass 1** — one guarded walk of the chunk source: count rows,
  feed the seeded reservoir (``ingest/sample.py``, honoring
  ``bin_construct_sample_cnt``), collect the streamed label/weight/query
  side columns;
- **bin finding** — ``BinnedDataset.from_sample`` on the reservoir
  sample (its internal ``global_bin_sample`` pooling makes pre-sharded
  multi-host ranks derive bit-identical mappers over the host
  collectives);
- **pass 2** — a second guarded walk binning chunk-at-a-time through
  the existing ``_binarize_chunk``/``_binarize_bundled_chunk`` into a
  preallocated (optionally ``np.memmap``-backed) bin matrix, each shard
  touching ONLY its rows of the :class:`~.shard.RowShardPlan`.

Peak host memory is O(chunk + sample + bin matrix) — never
O(N * F * 8).  Correctness is differential: with the same sample, the
streamed dataset (bin matrix, mappers, metadata, and the model trained
from it) is BIT-IDENTICAL to the in-RAM ``from_matrix``/``from_csr``
oracle (tests/test_ingest_stream.py pins dense/NaN/categorical/bundled/
ranking fixtures and a sharded 2-process agreement leg).

Fault surface: every chunk fetch passes the ``ingest_chunk`` injection
point under a ``robust/watchdog.DeviceGuard`` — transient read faults
retry with backoff, fatal ones abort loudly, and a stalled read is
stamped (``device_stall`` event + flight dump) when
``tpu_wedge_timeout_s`` is set.  A chunk whose geometry disagrees with
the stream (column-count drift, a pass-2 row count different from
pass 1's) raises :class:`IngestError` — corrupt input must never bin
silently.
"""
from __future__ import annotations

import hashlib
import json
import os
import time
from typing import List, Optional, Sequence

import numpy as np

from ..config import Config
from ..utils import log
from .readers import open_source
from .sample import ReservoirSampler, sample_seed
from .shard import local_query_sizes, plan_row_shards, resolve_shard

_DONE = object()


class IngestError(RuntimeError):
    """Corrupt or inconsistent stream input — ingestion aborts loudly
    rather than binning garbage."""


def chunk_rows_from_config(config) -> int:
    """``tpu_ingest_chunk_rows`` with the ``LGBM_TPU_INGEST_CHUNK_ROWS``
    env override (ops retune without editing configs, like the serve
    knobs)."""
    env = os.environ.get("LGBM_TPU_INGEST_CHUNK_ROWS", "")
    if env:
        try:
            return max(int(env), 1)
        except ValueError:
            log.warning("ignoring malformed LGBM_TPU_INGEST_CHUNK_ROWS=%r",
                        env)
    return max(int(getattr(config, "tpu_ingest_chunk_rows", 65536)), 1)


def memmap_from_config(config) -> str:
    env = os.environ.get("LGBM_TPU_INGEST_MEMMAP", "")
    return env or str(getattr(config, "tpu_ingest_memmap", "") or "")


def _memmap_file(base: str, shard_id: int, num_shards: int) -> str:
    """Resolve the memmap target: a directory (or trailing separator)
    gets a per-shard file inside it; a file path gains a shard suffix
    only when sharding.  An EXISTING target is never reused — open_memmap
    mode='w+' would truncate the inode a live dataset (e.g. the train
    set, while its valid set ingests with the same config) still maps —
    so the name walks to the first free ``.k`` suffix instead."""
    if os.path.isdir(base) or base.endswith(os.sep):
        os.makedirs(base, exist_ok=True)
        path = os.path.join(base, f"X_bin.shard{shard_id}.npy")
    elif num_shards > 1:
        root, ext = os.path.splitext(base)
        path = f"{root}.shard{shard_id}{ext or '.npy'}"
    else:
        path = base
    if os.path.exists(path):
        root, ext = os.path.splitext(path)
        k = 1
        while os.path.exists(f"{root}.{k}{ext}"):
            k += 1
        log.warning("ingest: memmap target %s already exists (another "
                    "dataset may still map it); writing %s.%d%s instead",
                    path, root, k, ext)
        path = f"{root}.{k}{ext}"
    return path


def _guard(config):
    from ..robust.watchdog import DeviceGuard
    timeout = float(getattr(config, "tpu_wedge_timeout_s", 0.0) or 0.0)
    return DeviceGuard(
        policy="retry",
        retries=int(getattr(config, "tpu_device_retries", 3)),
        stall_timeout_s=timeout if timeout > 0 else -1.0,
        enabled=bool(getattr(config, "tpu_watchdog", False)),
        name="ingest")


def _iter_guarded(source, guard, pass_no: int, expect_cols=None):
    """Yield ``(chunk_index, stream_row0, X, side)`` with the
    ``ingest_chunk`` fault point, retry/stall guard, and corrupt-chunk
    validation applied to every fetch."""
    from .. import obs
    it = iter(source)

    def _next():
        try:
            return next(it)
        except StopIteration:
            return _DONE

    ci = 0
    row0 = 0
    cols = expect_cols
    while True:
        out = guard.run(_next, point="ingest_chunk")
        if out is _DONE:
            break
        try:
            X, side = out
        except (TypeError, ValueError):
            raise IngestError(
                f"ingest pass {pass_no}: chunk {ci} is not an "
                f"(X, side) pair (got {type(out).__name__})")
        if getattr(X, "ndim", 2) != 2:
            raise IngestError(
                f"ingest pass {pass_no}: chunk {ci} is not 2-D "
                f"(shape {getattr(X, 'shape', None)})")
        sparse = hasattr(X, "tocsr")
        if not sparse:
            if cols is None:
                cols = int(X.shape[1])
            elif int(X.shape[1]) != cols:
                raise IngestError(
                    f"ingest pass {pass_no}: chunk {ci} has "
                    f"{int(X.shape[1])} columns, stream started with "
                    f"{cols} — corrupt chunk, aborting")
        m = int(X.shape[0])
        for name, arr in (side or {}).items():
            if arr is not None and len(arr) != m:
                raise IngestError(
                    f"ingest pass {pass_no}: chunk {ci} side column "
                    f"{name!r} has {len(arr)} rows for {m} data rows")
        if obs.enabled():
            obs.event("ingest_chunk", **{"pass": int(pass_no)},
                      chunk=ci, rows=m, stream_row0=row0)
        yield ci, row0, X, side or {}
        ci += 1
        row0 += m


def _group_sizes_from_qids(qids: np.ndarray):
    """Per-row query ids -> per-query sizes (ids must be grouped; same
    convention as ``io/text_loader._group_from_col``)."""
    if qids is None or not len(qids):
        return None
    has_q = qids >= 0
    if not has_q.any():
        return None
    if not has_q.all():
        log.warning("ingest: qid present on only %d of %d rows; "
                    "ignoring query structure", int(has_q.sum()),
                    len(qids))
        return None
    change = np.flatnonzero(np.diff(qids)) + 1
    bounds = np.concatenate([[0], change, [len(qids)]])
    return np.diff(bounds)


def _densify(chunk, n_cols: int) -> np.ndarray:
    """One sparse row block -> dense f64 with the stream's final width
    (implicit entries are 0.0 — the zero-bin handling makes that exact,
    io/dataset.py)."""
    out = np.zeros((int(chunk.shape[0]), int(n_cols)), np.float64)
    coo = chunk.tocoo()
    out[coo.row, coo.col] = coo.data
    return out


def dataset_digest(ds) -> str:
    """Content digest of a constructed dataset — bin matrix (hashed in
    bounded row blocks: the matrix may be a memmap far larger than
    RAM), mappers, offsets and labels.  Two deterministic re-streams of
    the same source produce the same digest, which is what makes
    crash-mid-ingest resume provable (re-ingest, compare, resume
    bit-exactly — tests/test_ingest_stream.py)."""
    h = hashlib.sha256()
    X = ds.X_bin
    if X is not None:
        h.update(str(X.dtype).encode())
        h.update(np.asarray(X.shape, np.int64).tobytes())
        step = max((1 << 24) // max(int(X.shape[1]), 1), 1)
        for lo in range(0, int(X.shape[0]), step):
            h.update(np.ascontiguousarray(X[lo:lo + step]).tobytes())
    h.update(json.dumps([m.to_dict() for m in ds.bin_mappers],
                        sort_keys=True).encode())
    if ds.bin_offsets is not None:
        h.update(np.asarray(ds.bin_offsets, np.int64).tobytes())
    md = ds.metadata
    for arr in (md.label, md.weights, md.query_boundaries):
        if arr is not None:
            h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()[:16]


# ---------------------------------------------------------------------------
def ingest_dataset(source, config=None, *, categorical_features: Sequence = (),
                   feature_names: Optional[List[str]] = None,
                   reference=None, num_shards: Optional[int] = None,
                   shard_id: Optional[int] = None,
                   memmap_path: Optional[str] = None,
                   group=None, weight=None, seed: Optional[int] = None):
    """Construct a ``BinnedDataset`` from a chunked ``source`` without
    materializing the raw matrix.  Returns the LOCAL shard's dataset
    (the whole stream when unsharded); ``ds.ingest_row_range`` records
    the global ``[lo, hi)`` rows it holds so callers can align other
    whole-stream side arrays (init scores) to the shard.

    ``source``: re-iterable of ``(X_chunk, side)`` (ingest/readers.py).
    ``reference``: a constructed BinnedDataset whose mappers are reused
    (validation-set alignment; sampling is skipped).  ``num_shards`` /
    ``shard_id`` default to the config surface (``resolve_shard``);
    ``memmap_path`` (or ``tpu_ingest_memmap``) backs the bin matrix
    with an ``np.memmap`` file.  ``group`` / ``weight`` override the
    stream's query structure (per-query sizes) and row weights — both
    whole-stream length, sliced to the shard here (sidecar files ride
    in this way so the shard plan can still query-align on them).
    """
    from .. import obs
    from ..io.dataset import BinnedDataset, Metadata
    from ..utils.timetag import timetag

    config = config if config is not None else Config()
    t_start = time.perf_counter()
    guard = _guard(config)
    if num_shards is None or shard_id is None:
        d_cfg, s_cfg = resolve_shard(config)
        num_shards = d_cfg if num_shards is None else int(num_shards)
        shard_id = s_cfg if shard_id is None else int(shard_id)
    num_shards = max(int(num_shards), 1)
    shard_id = int(shard_id)
    log.check(0 <= shard_id < num_shards,
              f"shard_id {shard_id} out of range for {num_shards} shards")
    if memmap_path is None:
        memmap_path = memmap_from_config(config) or None

    # ---- pass 1: count, sample, side columns -------------------------
    sampler = None
    if reference is None:
        sample_cnt = int(getattr(config, "bin_construct_sample_cnt",
                                 200000))
        sampler = ReservoirSampler(
            sample_cnt, seed=sample_seed(config) if seed is None
            else int(seed))
    n_rows = 0
    chunks_seen = 0
    labels, weights, qids = [], [], []
    with timetag("ingest pass1"):
        for ci, row0, X, side in _iter_guarded(source, guard, 1):
            m = int(X.shape[0])
            if sampler is not None:
                sampler.add(X)
            if side.get("label") is not None:
                labels.append(np.asarray(side["label"], np.float64))
            if side.get("weight") is not None:
                weights.append(np.asarray(side["weight"], np.float64))
            if side.get("qid") is not None:
                qids.append(np.asarray(side["qid"], np.int64))
            n_rows += m
            chunks_seen = ci + 1
    if n_rows == 0:
        raise IngestError("ingest: the source yielded no rows")

    label = np.concatenate(labels) if labels else None
    # a weight column IN the stream wins over the sidecar fallback (the
    # load_text convention); an explicit query override (sidecar) wins
    # over stream qids (ditto)
    if weights:
        weight = np.concatenate(weights)
    elif weight is not None:
        weight = np.asarray(weight, np.float64).ravel()
    if label is not None and len(label) != n_rows:
        raise IngestError(
            f"ingest: stream carried {len(label)} labels for "
            f"{n_rows} rows")
    if weight is not None and len(weight) != n_rows:
        raise IngestError(
            f"ingest: {len(weight)} weights for {n_rows} rows")
    if group is None:
        group = getattr(source, "group_sizes", None)
    if group is None and qids:
        group = _group_sizes_from_qids(np.concatenate(qids))
    group = None if group is None else np.asarray(group).ravel()
    if group is not None and int(group.sum()) != n_rows:
        raise IngestError(
            f"ingest: query sizes sum to {int(group.sum())} for "
            f"{n_rows} rows")
    boundaries = (np.concatenate([[0], np.cumsum(group)]).astype(np.int64)
                  if group is not None else None)

    # sparse streams discover their width in pass 1 (LibSVM max index)
    n_cols = getattr(source, "n_features", None)
    if feature_names is None:
        feature_names = getattr(source, "feature_names", None)

    # ---- shard plan --------------------------------------------------
    plan = plan_row_shards(n_rows, num_shards, boundaries) \
        if num_shards > 1 else None
    lo, hi = (plan.shard_range(shard_id) if plan is not None
              else (0, n_rows))
    local_n = hi - lo

    # ---- bin mappers -------------------------------------------------
    sample_rows = 0
    if reference is not None:
        ds = BinnedDataset()
        ds.num_data = local_n
        ds.num_total_features = reference.num_total_features
        if n_cols is not None:
            log.check(int(n_cols) <= reference.num_total_features,
                      "ingest stream has more features than the "
                      "reference dataset")
        ds.metadata = Metadata(local_n)
        ds.bin_mappers = reference.bin_mappers
        ds.used_feature_map = reference.used_feature_map
        ds.real_feature_idx = reference.real_feature_idx
        ds.bin_offsets = reference.bin_offsets
        ds.feature_names = reference.feature_names
        ds.max_bin = reference.max_bin
        ds.bundle = reference.bundle
        n_cols = reference.num_total_features
    else:
        sample, _indices = sampler.finish()
        sample_rows = int(sample.shape[0])
        if n_cols is None:
            n_cols = int(sample.shape[1])
        if hasattr(sample, "tocsr") and int(sample.shape[1]) < n_cols:
            import scipy.sparse as sp
            s = sample.tocsr()
            sample = sp.csr_matrix((s.data, s.indices, s.indptr),
                                   shape=(s.shape[0], n_cols))
        # name-based categorical specs resolve against the KEPT feature
        # names (same convention as io/text_loader._two_round_streamed)
        cats = []
        for c in categorical_features or ():
            if isinstance(c, str):
                if feature_names and c in feature_names:
                    cats.append(feature_names.index(c))
                else:
                    log.warning("categorical_feature %r not found in "
                                "feature names; ignored", c)
            else:
                cats.append(int(c))
        # ``from_sample`` builds mappers/feature-map/bundles and — under
        # an initialized multi-host runtime — pools every rank's sample
        # over the host collectives so pre-sharded ranks derive
        # bit-identical mappers (parallel/distributed.global_bin_sample)
        ds = BinnedDataset.from_sample(
            sample, n_rows, config,
            categorical_features=sorted(set(cats)),
            feature_names=feature_names)
        if plan is not None:
            # mappers/bundles describe the GLOBAL stream; this process
            # materializes only its shard's rows
            ds.num_data = local_n
            ds.metadata = Metadata(local_n)

    # ---- allocate the bin matrix (RAM or memmap) ---------------------
    memmap_file = None
    if memmap_path:
        cols, dtype = ds._bin_matrix_spec()
        memmap_file = _memmap_file(memmap_path, shard_id, num_shards)
        ds.X_bin = np.lib.format.open_memmap(
            memmap_file, mode="w+", dtype=dtype, shape=(local_n, cols))
    else:
        ds._alloc_X()

    # ---- pass 2: bin chunk-at-a-time into [lo, hi) -------------------
    # bin-occupancy capture rides the binarize pass for free: each
    # just-binned slice of X_bin folds into the per-feature occupancy
    # accumulator the quality profile (obs/drift.py) is built from —
    # no extra scan over a matrix that may be memmap-backed
    from ..obs.drift import accumulate_occupancy, init_occupancy
    occupancy = init_occupancy(ds)
    with timetag("binarize", record=ds.setup_trace, rows=local_n,
                 columns=int(ds.X_bin.shape[1]),
                 bytes=int(ds.X_bin.nbytes)):
        seen = 0
        filled = 0
        for ci, row0, X, side in _iter_guarded(source, guard, 2):
            m = int(X.shape[0])
            s = max(lo - row0, 0)
            e = min(hi - row0, m)
            if s < e:
                sub = X[s:e]
                if hasattr(sub, "tocsr"):
                    sub = _densify(sub, n_cols)
                else:
                    sub = np.asarray(sub, np.float64)
                    if sub.shape[1] != n_cols:
                        raise IngestError(
                            f"ingest pass 2: chunk {ci} width "
                            f"{sub.shape[1]} != stream width {n_cols}")
                ds._binarize_chunk(sub, filled)
                accumulate_occupancy(ds, occupancy, filled, e - s)
                filled += e - s
            seen += m
    ds.quality_occupancy = occupancy
    if seen != n_rows:
        raise IngestError(
            f"ingest: stream changed between passes ({seen} rows on "
            f"pass 2, {n_rows} on pass 1)")
    if filled != local_n:
        raise IngestError(
            f"ingest: shard {shard_id} binned {filled} rows, plan "
            f"expected {local_n}")

    # ---- metadata ----------------------------------------------------
    if label is not None:
        ds.metadata.set_label(label[lo:hi])
    if weight is not None:
        ds.metadata.set_weights(weight[lo:hi])
    if group is not None:
        sizes = (local_query_sizes(plan, shard_id, boundaries)
                 if plan is not None else group)
        if sizes is not None and len(sizes):
            ds.metadata.set_query(sizes)

    # the global rows this local dataset holds — callers align other
    # whole-stream side arrays (init scores, sidecars) with this
    ds.ingest_row_range = (int(lo), int(hi))
    ds.ingest_num_rows = int(n_rows)

    # ---- telemetry ---------------------------------------------------
    from .. import obs as _obs
    wall = time.perf_counter() - t_start
    fields = dict(rows=int(n_rows), local_rows=int(local_n),
                  chunks=int(chunks_seen), sample_rows=int(sample_rows),
                  shards=int(num_shards), shard_id=int(shard_id),
                  memmap=bool(memmap_file),
                  wall_s=round(wall, 4),
                  rows_per_s=round(n_rows / wall, 1) if wall > 0 else 0.0,
                  source=str(getattr(source, "kind", type(source).__name__)))
    if _obs.enabled() or _obs.flight_enabled():
        fields["digest"] = dataset_digest(ds)
    _obs.event("ingest_summary", **fields)
    log.info("ingest: %d rows (%d local, shard %d/%d) through %d "
             "chunk(s), %d-row sample, %.2fs (%s rows/s)%s",
             n_rows, local_n, shard_id, num_shards, chunks_seen,
             sample_rows, wall, f"{fields['rows_per_s']:,.0f}",
             f", memmap {memmap_file}" if memmap_file else "")
    return ds


# ---------------------------------------------------------------------------
def ingest_file(path: str, config, categorical_features: Sequence = (),
                reference=None, **kw):
    """CLI-facing file ingestion: pick a chunked reader for ``path``
    (CSV/TSV via the native parser, LibSVM, ``.npy``/``.npz``), stream
    it through :func:`ingest_dataset`, and return
    ``(handle, label, weight, group, feature_names)`` — the same
    contract as ``io/text_loader.load_text_two_round``, with the
    returned side arrays LOCAL to the shard.  The ``<data>.weight``/
    ``.query`` sidecars are read BEFORE ingestion so the shard plan can
    query-align on a sidecar's boundaries and the whole-stream weights
    slice to the shard (instead of crashing a sharded load)."""
    from ..io.text_loader import _load_sidecars

    sc_weight, sc_group = _load_sidecars(path, None, None)
    src = open_source(path, config,
                      chunk_rows=chunk_rows_from_config(config))
    ds = ingest_dataset(src, config,
                        categorical_features=categorical_features,
                        reference=reference, weight=sc_weight,
                        group=sc_group, **kw)
    md = ds.metadata
    group = (np.diff(md.query_boundaries)
             if md.query_boundaries is not None else None)
    return ds, md.label, md.weights, group, list(ds.feature_names)


def dataset_from_stream(source, params=None, *,
                        categorical_features: Sequence = (),
                        feature_names=None, **kw):
    """Engine-facing entry: stream ``source`` into a constructed
    :class:`lightgbm_tpu.Dataset` ready for ``lightgbm_tpu.train`` —
    labels/weights/queries carried by the stream are already attached
    to the handle's metadata."""
    from ..basic import Dataset

    params = dict(params or {})
    cfg = Config.from_params(params)
    handle = ingest_dataset(source, cfg,
                            categorical_features=categorical_features,
                            feature_names=feature_names, **kw)
    ds = Dataset(None, params=params,
                 feature_name=list(handle.feature_names))
    ds._handle = handle
    return ds
