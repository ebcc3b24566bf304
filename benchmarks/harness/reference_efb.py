"""Exclusive Feature Bundling's conflict rule, stated by the benchmark itself.

NumPy only, independent of the code under test.  EFB packs columns that are
(almost) never non-zero in the same row into one physical column, grouping
them on a row sample.  Two members of one group that never met in the sample
can still meet in the table; the physical column then holds one value, and
the trainer sees every other member of that row at its default, zero (the
reference's documented approximation, bounded by ``max_conflict_rate``; here
the sample bounds it; the benchmark's bundled columns hold 0 or 1, so
non-zero and non-default are one thing).  A walk of the exported model over
the raw rows does not know that, and differs from the trainer's own scores in
just those rows.

So the rule is applied to the sample before the walk: in a row where several
members of one group are non-zero, the one the encoder keeps stays (the LAST
in the group's order: the encoder writes the members in order, and a later
write replaces an earlier one) and the others are set to zero.  What comes
out is the table the trainer was given, as far as a tree can tell.
"""
from __future__ import annotations

import numpy as np


def densify(indptr, indices, data, shape, groups) -> tuple:
    """``(X [S, F] float64, touched)`` for a CSR sample (its three arrays
    and its shape) under ``groups``, one list of column indices a physical
    column, in the encoder's order.  ``touched`` counts the rows in which
    the rule set at least one stored non-zero value to zero."""
    S, F = int(shape[0]), int(shape[1])
    indptr = np.asarray(indptr, np.int64)
    cols = np.asarray(indices, np.int64)
    vals = np.asarray(data, np.float64)
    rows = np.repeat(np.arange(S, dtype=np.int64), np.diff(indptr))
    group_of = np.full(F, -1, np.int64)
    rank = np.zeros(F, np.int64)
    multi = [g for g in groups if len(g) > 1]
    for gi, members in enumerate(multi):
        group_of[members] = gi
        rank[members] = np.arange(len(members))
    # a stored entry competes where it is non-zero and its column shares a
    # physical column with others
    comp = (vals != 0.0) & (group_of[cols] >= 0)
    G = max(len(multi), 1)
    key = rows[comp] * G + group_of[cols[comp]]
    best = np.full(S * G, -1, np.int64)
    np.maximum.at(best, key, rank[cols[comp]])
    lost = np.zeros(len(vals), bool)
    lost[comp] = rank[cols[comp]] < best[key]
    X = np.zeros((S, F), np.float64)
    X[rows[~lost], cols[~lost]] = vals[~lost]
    return X, int(len(np.unique(rows[lost])))
