"""Ranking metrics: NDCG@k and MAP@k
(reference: src/metric/rank_metric.hpp:19, map_metric.hpp:20,
src/metric/dcg_calculator.cpp).

NDCG is a per-iteration eval on the training loop's critical path: the
reference walks all queries in a host loop per round, which on
MSLR-WEB30K (~31k queries) forced a device->host score copy plus ~31k
Python iterations per eval.  The device kernel
(``tpu_rank_device_eval``, default on) evaluates every query at once
over the shared padded query blocks (core/query.py — the same structure
the lambdarank objective bucketed): stable sort per padded block,
gain-times-discount cumsum, one gather per ``eval_at`` k against
host-precomputed ideal-DCG tables, query-weighted mean.  Only the final
``[len(eval_at)]`` vector leaves the device.  The host loop below is
retained verbatim as the differential oracle
(``tpu_rank_device_eval=false``), including the
all-zero-relevance-counts-as-perfect and ``query_weights`` branches.
"""
from __future__ import annotations

from typing import List

import numpy as np

from ..utils import log
from .basic import EvalResult, Metric


class _RankMetric(Metric):
    higher_is_better = True

    def __init__(self, config):
        super().__init__(config)
        self.eval_at = [int(k) for k in (config.eval_at or [1, 2, 3, 4, 5])]

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            log.fatal(f"The {self.name} metric requires query information")
        self.query_boundaries = metadata.query_boundaries
        self.query_weights = metadata.query_weights


def _ndcg_device_fn(qb):
    """Jitted NDCG@k kernel over ``QueryBlocks`` built with eval
    tables: per bucket a stable sort of the padded scores (invalid
    slots pinned to -inf sort last; ties keep doc order like the
    reference's stable_sort), gain-times-discount cumsum, DCG gathered
    at each k's host-precomputed index, then
    ``dcg*inv_k + one_k`` — the zero-relevance/degenerate-ideal
    branches are baked into the tables, so the kernel is pure gather/
    sort/fma.  Returns the query-weighted NDCG mean, shape
    ``[len(eval_at)]``."""
    import jax
    import jax.numpy as jnp

    nK = len(qb.eval_at)
    sentinel = qb.sentinel
    wsum = max(qb.wsum, 1e-300)
    neg_inf = jnp.float32(-jnp.inf)

    @jax.jit
    def fn(score):
        sums = jnp.zeros((nK,), jnp.float32)
        for bk in qb.buckets:
            Qt, P = bk.nc * bk.qc, bk.P
            idx = bk.idx.reshape(Qt, P)
            valid = idx < sentinel
            s = jnp.where(valid, score[idx], neg_inf)
            order = jnp.argsort(-s, axis=-1, stable=True)
            gs = jnp.take_along_axis(bk.gains.reshape(Qt, P), order,
                                     axis=-1)
            # a host table like the others: the chip's log2 is an
            # approximation, and the host loop is this kernel's oracle
            disc = jnp.asarray(1.0 / np.log2(np.arange(P) + 2.0),
                               jnp.float32)
            cum = jnp.cumsum(gs * disc, axis=-1)
            dcg = jnp.take_along_axis(cum, bk.k_idx.reshape(Qt, nK),
                                      axis=-1)
            ndcg = (dcg * bk.inv_k.reshape(Qt, nK)
                    + bk.one_k.reshape(Qt, nK))
            sums = sums + (bk.qw.reshape(Qt, 1) * ndcg).sum(axis=0)
        return sums / jnp.float32(wsum)
    return fn


class NDCGMetric(_RankMetric):
    """NDCG@k averaged over queries; label gain 2^l - 1
    (reference: rank_metric.hpp:19-100, dcg_calculator.cpp)."""
    name = "ndcg"
    # flipped on in init() when the device kernel is armed — the
    # trainer then hands this metric the DEVICE score array instead of
    # paying the [N] device->host copy every eval round
    accepts_device_score = False

    def __init__(self, config):
        super().__init__(config)
        from ..core.query import default_label_gain
        gains = config.label_gain or []
        self.label_gain = (np.asarray(gains, dtype=np.float64) if gains
                           else default_label_gain())

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        self._dev_fn = None
        if bool(getattr(self.config, "tpu_rank_device_eval", True)):
            from ..core.query import build_query_blocks
            self._qblocks = build_query_blocks(
                self.query_boundaries, self.label, self.label_gain,
                eval_at=self.eval_at, query_weights=self.query_weights,
                sentinel=num_data, with_labels=False)
            self._dev_fn = _ndcg_device_fn(self._qblocks)
            self.accepts_device_score = True

    def _dcg_at_k(self, ks, labels, order):
        """DCG at each k for one query given ranking order."""
        gains = self.label_gain[labels[order].astype(np.int64)]
        discounts = 1.0 / np.log2(np.arange(len(order)) + 2.0)
        gd = gains * discounts
        cum = np.cumsum(gd)
        return [float(cum[min(k, len(order)) - 1]) if len(order) else 0.0
                for k in ks]

    def eval(self, score, objective) -> List[EvalResult]:
        if self._dev_fn is not None and not isinstance(score, np.ndarray):
            vals = np.asarray(self._dev_fn(score))
            return [(f"{self.name}@{k}", float(vals[i]), True)
                    for i, k in enumerate(self.eval_at)]
        return self.eval_host(score)

    def eval_host(self, score) -> List[EvalResult]:
        """The per-query host loop — the differential oracle the device
        kernel is pinned against (``tpu_rank_device_eval=false``)."""
        score = np.asarray(score, dtype=np.float64).ravel()
        b = self.query_boundaries
        nq = len(b) - 1
        sums = np.zeros(len(self.eval_at))
        wsum = 0.0
        for q in range(nq):
            lo, hi = int(b[q]), int(b[q + 1])
            lab = self.label[lo:hi]
            sc = score[lo:hi]
            qw = (float(self.query_weights[q])
                  if self.query_weights is not None else 1.0)
            wsum += qw
            ideal = np.argsort(-lab, kind="stable")
            if self.label_gain[lab.astype(np.int64)].max(initial=0.0) <= 0:
                # all-zero-relevance queries count as perfect (reference:
                # NDCGMetric::Eval empty-dcg case)
                sums += qw
                continue
            pred = np.argsort(-sc, kind="stable")
            idcg = self._dcg_at_k(self.eval_at, lab, ideal)
            dcg = self._dcg_at_k(self.eval_at, lab, pred)
            for i in range(len(self.eval_at)):
                sums[i] += qw * (dcg[i] / idcg[i] if idcg[i] > 0 else 1.0)
        return [(f"{self.name}@{k}", float(sums[i] / max(wsum, 1e-300)), True)
                for i, k in enumerate(self.eval_at)]


class MapMetric(_RankMetric):
    """MAP@k (reference: map_metric.hpp:20-120)."""
    name = "map"

    def eval(self, score, objective) -> List[EvalResult]:
        score = np.asarray(score, dtype=np.float64).ravel()
        b = self.query_boundaries
        nq = len(b) - 1
        sums = np.zeros(len(self.eval_at))
        wsum = 0.0
        for q in range(nq):
            lo, hi = int(b[q]), int(b[q + 1])
            lab = (self.label[lo:hi] > 0).astype(np.float64)
            sc = score[lo:hi]
            qw = (float(self.query_weights[q])
                  if self.query_weights is not None else 1.0)
            wsum += qw
            order = np.argsort(-sc, kind="stable")
            rel = lab[order]
            hits = np.cumsum(rel)
            prec = hits / (np.arange(len(rel)) + 1.0)
            for i, k in enumerate(self.eval_at):
                kk = min(k, len(rel))
                npos = rel[:kk].sum()
                if npos > 0:
                    sums[i] += qw * float((prec[:kk] * rel[:kk]).sum() / npos)
                else:
                    sums[i] += qw
        return [(f"{self.name}@{k}", float(sums[i] / max(wsum, 1e-300)), True)
                for i, k in enumerate(self.eval_at)]
