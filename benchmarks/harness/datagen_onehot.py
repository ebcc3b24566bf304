"""Inputs from the seed: a one-hot encoded categorical table, born sparse.

The shape of the reference's Expo job (the airline on-time table with its
categorical columns one-hot encoded): ``variables`` categorical variables,
each one block of 0/1 columns of which exactly one is set in a row, then
``numeric`` dense positive columns.  Dense it would be rows x features x 8
bytes (61.6 GB at 11M x 700), so it is made as the CSR it is trained from:
``len(variables) + numeric`` stored values a row.

Same contract as ``datagen.make_table``, and the same split between the two
seeds, for the same reason (the program bakes what it closes over into its
compiled growers as HLO constants, which key the compile cache):

- from the configuration's ``label_seed``, the same in every run: every
  categorical code, so every one-hot column and with it the layout of the
  bundles EFB packs them into (``feat2phys`` / ``feat_offset`` are constants
  of the growth program: a layout that moved with ``--seed`` would compile
  every grower anew in every run, and give the two sides of a comparison
  different programs); a per-value effect for each variable; the latent
  score (the sum of the row's effects plus a numeric latent); the label;
- from ``--seed``: the numeric columns, noisy lognormal views of the numeric
  latent as in ``datagen.py``, so trees, partitions and leaf counts move with
  the seed as they do in the dense cells.

Generation is by fixed blocks of rows, one stream a block: a prefix of the
table is a prefix of the draw, whatever the number of threads.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

from .datagen import _blocks, _normal, _par


def popularity(cardinality: int, zipf: float) -> np.ndarray:
    """Zipf popularity of a variable's values: ``p(k) ~ 1 / (k + 1)**zipf``."""
    p = 1.0 / np.arange(1, int(cardinality) + 1, dtype=np.float64) ** zipf
    return p / p.sum()


def column_offsets(spec: dict) -> np.ndarray:
    """First column of each variable's one-hot block, then of the numeric
    columns: ``[V + 1]``."""
    return np.concatenate(
        [[0], np.cumsum([int(v["cardinality"]) for v in spec["variables"]])])


def make_table(spec: dict, seed: int, rows: int = None):
    """``(X csr [N, F] float32 data / int32 indices, y [N], None)`` for a
    configuration's ``data`` group.  ``rows`` cuts the table to its first
    rows, for the oracle's slice: same codes, same labels, same features."""
    variables = spec["variables"]
    V, M = len(variables), int(spec["numeric"])
    off = column_offsets(spec)
    F = int(off[-1]) + M
    if F != int(spec["features"]):
        raise ValueError(f"the variables' cardinalities and the numeric "
                         f"columns make {F} columns, the configuration says "
                         f"{spec['features']}")
    n = int(spec["rows"]) if rows is None else min(int(rows),
                                                   int(spec["rows"]))
    ls = int(spec["label_seed"])
    a = float(spec["loading"])
    c = float(np.sqrt(1.0 - a * a))
    cdfs = [np.cumsum(popularity(v["cardinality"], v["zipf"]))
            for v in variables]
    effects = [np.random.default_rng([ls, 4, i]).standard_normal(
        int(v["cardinality"])) * float(v["effect"])
        for i, v in enumerate(variables)]

    s_num = _normal(n, ls, 0)
    latent = float(spec["numeric_effect"]) * s_num
    K = V + M
    indices = np.empty((n, K), np.int32)
    data = np.ones((n, K), np.float32)
    indices[:, V:] = off[-1] + np.arange(M)

    def fill(b, lo, hi):
        # row by row, so a block cut short draws what the whole one does
        u = np.random.default_rng([ls, 5, b]).random((hi - lo, V))
        for i in range(V):
            code = np.minimum(np.searchsorted(cdfs[i], u[:, i], "right"),
                              len(cdfs[i]) - 1)
            indices[lo:hi, i] = off[i] + code
            latent[lo:hi] += effects[i][code]
        z = np.random.default_rng([int(seed), 2, b]).standard_normal(
            (hi - lo, M))
        z *= c
        z += a * s_num[lo:hi, None]
        data[lo:hi, V:] = np.exp(0.5 * z)
    _par(fill, _blocks(n))

    noisy = float(spec["signal"]) * latent + float(
        spec["label_noise"]) * _normal(n, ls, 1)
    y = (noisy > 0).astype(np.float64)
    X = sp.csr_matrix(
        (data.reshape(-1), indices.reshape(-1),
         np.arange(0, n * K + 1, K, dtype=np.int32)), shape=(n, F))
    return X, y, None
