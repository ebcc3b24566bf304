"""Query-aligned row sharding for data-parallel lambdarank.

``parallel/mesh.py`` shards rows with no query awareness, so a ranking
dataset's queries straddle shard boundaries and the per-query O(P^2)
pair pass could not run shard-locally — the whole lambda computation
executed globally on the dispatch side while the mesh only saw the
finished g/h.  This module snaps data-parallel shard boundaries to
QUERY boundaries (the reference keeps query boundaries in ``Metadata``
for exactly this: its data-parallel learner never splits a query across
workers):

- ``plan_query_shards``: greedy balanced contiguous partition of the
  query list over the mesh size — each cut lands on the query boundary
  nearest the ideal rows/D split, every shard is padded to the largest
  shard's row count (``S``), and a gather map carries padded position
  -> original row (sentinel N for padding).
- ``build_shard_blocks``: one ``core/query.py`` block set per shard
  with LOCAL row indices (sentinel = S), aligned to identical bucket
  shapes across shards and stacked on a leading device axis.
- ``ShardedRankGrads``: a ``shard_map`` over the stacked blocks —
  each device runs the SAME ``pair_lambdas`` math the single-device
  objective runs, on its local score slice, and only the flat [N] g/h
  leave the mesh.  Per-row lambdas are per-query sums and every query
  lives wholly on one shard, so the result matches the single-device
  oracle (pinned by tests/test_rank_device.py's 2-device differential).
"""
from __future__ import annotations

from typing import List

import numpy as np

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.query import (CHUNK_ELEMS, QueryBucket, build_query_blocks,
                          chunk_queries)
from ..utils import log
from .mesh import AXIS, _shard_map


class QueryShardPlan:
    """Static shard geometry: query cuts, row cuts, padded shard rows
    ``S``, and the [D*S] padded-position -> original-row gather map."""
    __slots__ = ("D", "S", "n_rows", "query_cuts", "row_cuts", "gather")

    def __init__(self, D, S, n_rows, query_cuts, row_cuts, gather):
        self.D = int(D)
        self.S = int(S)
        self.n_rows = int(n_rows)
        self.query_cuts = query_cuts
        self.row_cuts = row_cuts
        self.gather = gather


def plan_query_shards(query_boundaries, D: int) -> QueryShardPlan:
    """Greedy balanced partition of contiguous queries over ``D``
    shards: cut ``d`` lands on the query boundary nearest ``d*N/D``
    rows, monotone in ``d``, so shards stay row-balanced up to one
    query's worth of slack and no query ever straddles a shard."""
    b = np.asarray(query_boundaries, dtype=np.int64)
    nq = len(b) - 1
    N = int(b[-1])
    cuts = np.zeros(D + 1, dtype=np.int64)
    cuts[D] = nq
    for d in range(1, D):
        target = (d * N) // D
        j = int(np.searchsorted(b, target))
        if j > 0 and (j > nq or abs(int(b[j - 1]) - target)
                      <= abs(int(b[min(j, nq)]) - target)):
            j -= 1
        cuts[d] = min(max(j, int(cuts[d - 1])), nq)
    row_cuts = b[cuts]
    S = int(max((row_cuts[1:] - row_cuts[:-1]).max(initial=1), 1))
    gather = np.full(D * S, N, dtype=np.int32)
    for d in range(D):
        lo, hi = int(row_cuts[d]), int(row_cuts[d + 1])
        gather[d * S:d * S + (hi - lo)] = np.arange(lo, hi, dtype=np.int32)
    return QueryShardPlan(D, S, N, cuts, row_cuts, gather)


def build_shard_blocks(plan: QueryShardPlan, query_boundaries, label,
                       label_gain, optimize_pos_at: int,
                       chunk_elems: int = CHUNK_ELEMS) -> List[dict]:
    """Per-shard padded query blocks, aligned to IDENTICAL bucket
    shapes across shards (the union of bucket pads, each padded to the
    max chunk count) and stacked on a leading device axis — the form
    ``shard_map`` slices one device's blocks from.  Returns a list of
    ``{"P", "qc", "nc", "idx", "labs", "gains", "inv"}`` with arrays
    shaped ``[D, nc, qc, ...]``; indices are shard-LOCAL with sentinel
    ``plan.S``."""
    per_shard = []
    for d in range(plan.D):
        qids = np.arange(int(plan.query_cuts[d]),
                         int(plan.query_cuts[d + 1]), dtype=np.int64)
        per_shard.append(build_query_blocks(
            query_boundaries, label, label_gain,
            optimize_pos_at=optimize_pos_at, query_ids=qids,
            base=int(plan.row_cuts[d]), sentinel=plan.S,
            chunk_elems=chunk_elems))
    # union of bucket shapes: every shard must present the same pytree
    shapes = {}
    for blocks in per_shard:
        for bk in blocks.buckets:
            shapes[bk.P] = max(shapes.get(bk.P, 0), bk.nc)
    stacked = []
    for Pq in sorted(shapes):
        nc = shapes[Pq]
        qc = chunk_queries(Pq, chunk_elems)
        idx = np.full((plan.D, nc, qc, Pq), plan.S, dtype=np.int32)
        labs = np.zeros((plan.D, nc, qc, Pq), dtype=np.float32)
        gains = np.zeros((plan.D, nc, qc, Pq), dtype=np.float32)
        inv = np.zeros((plan.D, nc, qc), dtype=np.float32)
        for d, blocks in enumerate(per_shard):
            bk = next((x for x in blocks.buckets if x.P == Pq), None)
            if bk is None:
                continue
            idx[d, :bk.nc] = np.asarray(bk.idx)
            labs[d, :bk.nc] = np.asarray(bk.labs)
            gains[d, :bk.nc] = np.asarray(bk.gains)
            inv[d, :bk.nc] = np.asarray(bk.inv)
        stacked.append({"P": Pq, "qc": qc, "nc": nc,
                        "idx": jnp.asarray(idx), "labs": jnp.asarray(labs),
                        "gains": jnp.asarray(gains),
                        "inv": jnp.asarray(inv)})
    return stacked


class ShardedRankGrads:
    """Callable ``score [N] -> (g, h) [N]`` computing the lambdarank
    pair pass inside the mesh over query-aligned shards.  Traceable —
    it composes into the trainer's gradient jit and the fused growth
    jit (the fused gradient pass) unchanged."""

    def __init__(self, mesh, plan: QueryShardPlan, stacked: List[dict],
                 sigmoid: float, norm: bool):
        from ..objective.rank import pair_lambdas
        self.mesh = mesh
        self.plan = plan
        self._stacked = stacked
        self._gather = jnp.asarray(plan.gather)
        n_arrays = 4 * len(stacked)

        def local(sp, *arrs):
            # each device sees its [1, nc, qc, ...] slice of every
            # stacked bucket array; squeeze to shard-local QueryBuckets
            buckets = []
            for i in range(len(arrs) // 4):
                idx, labs, gains, inv = (a[0] for a in
                                         arrs[i * 4:(i + 1) * 4])
                buckets.append(QueryBucket(idx=idx, labs=labs,
                                           gains=gains, inv=inv))
            return pair_lambdas(sp, buckets, sigmoid, norm)

        in_specs = (P(AXIS),) + (P(AXIS),) * n_arrays
        self._fn = _shard_map(local, mesh, in_specs, (P(AXIS), P(AXIS)))
        self._flat = [a for bk in stacked
                      for a in (bk["idx"], bk["labs"], bk["gains"],
                                bk["inv"])]

    def __call__(self, score):
        N = self.plan.n_rows
        # padded-position score: pad slots gather a clamped row but are
        # never referenced by any bucket index, so their value is inert
        sp = jnp.take(score, self._gather, mode="clip")
        gp, hp = self._fn(sp, *self._flat)
        g = jnp.zeros((N,), jnp.float32).at[self._gather].add(
            gp, mode="drop")
        h = jnp.zeros((N,), jnp.float32).at[self._gather].add(
            hp, mode="drop")
        return g, h


def enable_query_sharded_grads(objective, mesh,
                               chunk_elems: int = CHUNK_ELEMS):
    """Arm ``objective`` (an initialized LambdarankNDCG) with the
    mesh-sharded pair pass; returns the ShardedRankGrads.  Idempotent
    per (objective, mesh): re-arming with the same mesh returns the
    existing instance instead of rebuilding the device blocks."""
    D = int(mesh.devices.size)
    cur = getattr(objective, "_shard", None)
    if cur is not None and cur.mesh is mesh and cur.plan.D == D:
        return cur
    plan = plan_query_shards(objective.query_boundaries, D)
    label = np.asarray(objective.label, dtype=np.float64)
    stacked = build_shard_blocks(plan, objective.query_boundaries, label,
                                 objective.label_gain,
                                 objective.optimize_pos_at,
                                 chunk_elems=chunk_elems)
    objective._shard = ShardedRankGrads(mesh, plan, stacked,
                                        objective.sigmoid, objective.norm)
    log.info("query-aligned lambdarank sharding: %d queries over %d "
             "devices, %d rows/shard (padded from %s)",
             len(objective.query_boundaries) - 1, D, plan.S,
             (plan.row_cuts[1:] - plan.row_cuts[:-1]).tolist())
    return objective._shard
