"""Ranking objectives — lambdarank NDCG
(reference: src/objective/rank_objective.hpp:23-254).

The reference runs a per-query O(n^2) pair loop on the CPU
(GetGradientsForOneQuery, rank_objective.hpp:117-166).  The TPU
formulation keeps the same math but turns the ragged per-query loops
into dense array ops over the shared padded query blocks
(``core/query.py QueryBlocks`` — the same structure the device NDCG
metric kernel sorts):

- queries are bucketed by padded length (powers of two), giving a few
  static shapes to jit instead of one shape per query size;
- each bucket holds ``[Q, P]`` doc-index/label matrices built once at
  ``init``; invalid slots carry index ``N`` so device gathers clamp and
  scatters drop them;
- per boosting iteration the whole pair tensor ``[q_chunk, P, P]`` of
  sigmoid lambdas is evaluated at once on the VPU (``lax.map`` over
  query chunks bounds memory), then scatter-added back into the flat
  gradient vector.

Under a data-parallel mesh the pair pass runs INSIDE the mesh over
query-aligned row shards (parallel/rank_shard.py arms ``_shard``):
every query lives wholly on one device, so the per-shard blocks drive
the same ``pair_lambdas`` math shard-locally.

Deviation from the reference: the 1M-entry sigmoid LUT
(rank_objective.hpp:196-209) is a CPU memoization trick — the VPU
computes ``exp`` at full throughput, so the sigmoid is evaluated
exactly.  The reference's kMinScore sentinel handling (scores pinned to
-inf) is dropped: predictions here are always finite.
"""
from __future__ import annotations

import numpy as np

from ..core.query import (MAX_LABEL, build_query_blocks,  # noqa: F401
                          default_label_gain)
from ..utils import log
from .base import Objective


def _check_rank_labels(label: np.ndarray, num_gains: int) -> None:
    """(reference: DCGCalculator::CheckLabel)."""
    if not np.all(label == np.floor(label)):
        log.fatal("label should be int type (met type with decimals) for ranking task")
    if label.min(initial=0) < 0 or label.max(initial=0) >= num_gains:
        log.fatal(f"label excel [0, {num_gains}) range for ranking task")


def pair_lambdas(score, buckets, sigmoid: float, norm: bool):
    """Gradients/hessians over padded query buckets — the vectorized
    form of GetGradientsForOneQuery (rank_objective.hpp:117-166).

    ``buckets`` is any iterable of objects carrying chunk-reshaped
    ``idx``/``labs``/``gains`` ``[nc, qc, P]`` and ``inv`` ``[nc, qc]``
    (core/query.py QueryBucket, or the shard-local reconstruction in
    parallel/rank_shard.py).  Row indices at or past ``len(score)``
    are invalid: gathers clamp, scatters drop.  Returns flat f32
    (g, h) shaped like ``score``.
    """
    import jax
    import jax.numpy as jnp

    sig = sigmoid
    neg_inf = jnp.float32(-jnp.inf)

    def chunk_fn(args):
        idx, labs, gains, inv = args          # [qc,P] ... [qc]
        valid = idx < score.shape[0]
        s_raw = score[idx]                    # OOB gathers clamp; masked
        s_sort = jnp.where(valid, s_raw, neg_inf)
        # rank positions via double argsort (stable, ties keep doc order
        # like the reference's stable_sort)
        order = jnp.argsort(-s_sort, axis=-1, stable=True)
        pos = jnp.argsort(order, axis=-1, stable=True)
        disc = 1.0 / jnp.log2(pos.astype(jnp.float32) + 2.0)

        sv = jnp.where(valid, s_raw, 0.0)
        best = jnp.max(s_sort, axis=-1)
        worst = jnp.min(jnp.where(valid, s_raw, jnp.inf), axis=-1)

        ds = sv[:, :, None] - sv[:, None, :]              # [qc,P,P]
        dcg_gap = gains[:, :, None] - gains[:, None, :]
        pd = jnp.abs(disc[:, :, None] - disc[:, None, :])
        delta = dcg_gap * pd * inv[:, None, None]
        if norm:
            delta = jnp.where((best != worst)[:, None, None],
                              delta / (0.01 + jnp.abs(ds)), delta)
        p0 = jax.nn.sigmoid(-sig * ds)
        vp = (valid[:, :, None] & valid[:, None, :]
              & (labs[:, :, None] > labs[:, None, :]))
        pl = jnp.where(vp, -sig * delta * p0, 0.0)
        ph = jnp.where(vp, sig * sig * delta * p0 * (1.0 - p0), 0.0)

        lam = pl.sum(axis=2) - pl.sum(axis=1)
        hes = ph.sum(axis=2) + ph.sum(axis=1)
        if norm:
            sum_lambdas = -2.0 * pl.sum(axis=(1, 2))
            factor = jnp.where(
                sum_lambdas > 0.0,
                jnp.log2(1.0 + sum_lambdas) / jnp.maximum(sum_lambdas, 1e-30),
                1.0)
            lam = lam * factor[:, None]
            hes = hes * factor[:, None]
        return lam.astype(jnp.float32), hes.astype(jnp.float32)

    g = jnp.zeros(score.shape, jnp.float32)
    h = jnp.zeros(score.shape, jnp.float32)
    for bk in buckets:
        lam, hes = jax.lax.map(
            chunk_fn, (bk.idx, bk.labs, bk.gains, bk.inv))
        flat_idx = bk.idx.reshape(-1)      # OOB scatters drop
        g = g.at[flat_idx].add(lam.reshape(-1), mode="drop")
        h = h.at[flat_idx].add(hes.reshape(-1), mode="drop")
    return g, h


class LambdarankNDCG(Objective):
    name = "lambdarank"
    need_accurate_prediction = False
    # the pair pass is pure traced jnp over static blocks, so it can
    # shard query-locally (parallel/rank_shard.py) and fold into the
    # growth jit (the fused gradient pass: differential-tested bit-identical
    # through _grow_apply_fused in tests/test_rank_device.py)
    supports_query_sharding = True

    def __init__(self, config):
        super().__init__(config)
        self.sigmoid = float(config.sigmoid)
        self.norm = bool(config.lambdamart_norm)
        self.optimize_pos_at = int(config.max_position)
        gains = list(config.label_gain or [])
        self.label_gain = (np.asarray(gains, dtype=np.float64) if gains
                           else default_label_gain())
        self._shard = None   # parallel/rank_shard.py ShardedRankGrads
        if self.sigmoid <= 0.0:
            log.fatal(f"Sigmoid param {self.sigmoid} should be greater than zero")

    # ------------------------------------------------------------------
    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            log.fatal("Lambdarank tasks require query information")
        label = np.asarray(self.label, dtype=np.float64)
        _check_rank_labels(label, len(self.label_gain))
        self.query_boundaries = np.asarray(metadata.query_boundaries,
                                           dtype=np.int64)
        # the shared padded-query-bucket structure (core/query.py) —
        # the device NDCG metric builds the same blocks from the same
        # boundaries, plus its per-k eval tables
        self.qblocks = build_query_blocks(
            self.query_boundaries, label, self.label_gain,
            optimize_pos_at=self.optimize_pos_at, sentinel=num_data)

    # ------------------------------------------------------------------
    def get_gradients(self, score):
        """Gradients/hessians for the whole dataset via ``pair_lambdas``
        over the padded query blocks; when parallel/rank_shard.py armed
        query-aligned sharding, the pair pass runs inside the mesh and
        only the flat [N] g/h leave the shard_map."""
        if self._shard is not None:
            g, h = self._shard(score)
            return self._apply_weight(g, h)
        g, h = pair_lambdas(score, self.qblocks.buckets,
                            self.sigmoid, self.norm)
        return self._apply_weight(g, h)
