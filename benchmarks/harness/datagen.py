"""Inputs from the seed: tables to train on.

One general generator, driven by the ``data`` group of a configuration file:
a new configuration is a new file of parameters, not new code.

What the seed draws and what it does not.  The program bakes what it closes
over into its compiled growers: the label vector, the query layout and the
per-feature bin metadata become constants of the HLO (jax 0.9 embeds a
closed-over array), and the constants are part of the persistent compile
cache's key.  A label vector drawn from ``--seed`` would therefore compile
the grower anew in every run (a minute or more), and no run after the first
would be warm.  So a table has two parts:

- from the configuration's ``label_seed``, the same in every run: a latent
  score per row, the labels graded from it, and the query sizes;
- from ``--seed``: every feature value.  Informative features are noisy views
  of the latent score (``loading``), the others are noise; all are made
  positive (lognormal, like momenta and masses), so that 0.0 lies under every
  bin and the ``default_bin`` the program bakes in does not move with the
  draw.

Generation is by fixed blocks of rows, one stream per block, so the result
does not depend on the number of threads.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 131072
_GRADE_CUTS = (0.5, 0.75, 0.9, 0.97)     # within-query quantiles -> 0..4


def _threads() -> int:
    return max(1, min(16, (os.cpu_count() or 2) - 1))


def _blocks(n: int):
    return [(b, lo, min(lo + BLOCK, n))
            for b, lo in enumerate(range(0, n, BLOCK))]


def _par(fn, jobs) -> None:
    with ThreadPoolExecutor(_threads()) as ex:
        for _ in ex.map(lambda j: fn(*j), jobs):
            pass


def _normal(n: int, *key: int) -> np.ndarray:
    out = np.empty(n, np.float64)

    def fill(b, lo, hi):
        np.random.default_rng([*key, b]).standard_normal(out=out[lo:hi])
    _par(fill, _blocks(n))
    return out


def query_sizes(rows: int, spec: dict, label_seed: int) -> np.ndarray:
    """Ragged query sizes, lognormal clamped to [min, max], summing to
    ``rows`` (the last query is cut short)."""
    rng = np.random.default_rng([label_seed, 3])
    sizes, total = [], 0
    while total < rows:
        s = np.clip(rng.lognormal(spec["log_mean"], spec["log_sigma"],
                                  size=65536).astype(np.int64),
                    spec["min"], spec["max"])
        sizes.append(s)
        total += int(s.sum())
    sizes = np.concatenate(sizes)
    keep = int(np.searchsorted(np.cumsum(sizes), rows)) + 1
    sizes = sizes[:keep].copy()
    sizes[-1] -= int(sizes.sum()) - rows
    return sizes


def _grade_in_queries(score: np.ndarray, sizes: np.ndarray) -> np.ndarray:
    """Relevance 0..4 by rank quantile of ``score`` inside each query."""
    qid = np.repeat(np.arange(len(sizes)), sizes)
    order = np.lexsort((score, qid))
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    q_sorted = qid[order]
    frac = (np.arange(len(score)) - starts[q_sorted] + 0.5) / sizes[q_sorted]
    y = np.empty(len(score), np.float64)
    y[order] = np.searchsorted(np.asarray(_GRADE_CUTS), frac)
    return y


def make_table(spec: dict, seed: int, rows: int = None):
    """``(X [N, F] float64 C-order, y [N], query sizes or None)`` for a
    configuration's ``data`` group.  ``rows`` cuts the table to its first rows
    (whole queries), for the oracle's slice: same labels, same features."""
    n_full, F = int(spec["rows"]), int(spec["features"])
    k, a = int(spec["informative"]), float(spec["loading"])
    ls = int(spec["label_seed"])
    sizes = None
    if spec["task"] == "rank":
        sizes = query_sizes(n_full, spec["queries"], ls)
    n = n_full
    if rows is not None and rows < n_full:
        n = int(rows)
        if sizes is not None:
            nq = max(int(np.searchsorted(np.cumsum(sizes), n, "right")), 1)
            sizes = sizes[:nq]
            n = int(sizes.sum())
    # the latent score and the label noise are drawn block by block, so a
    # prefix of the table is a prefix of the draw
    s = _normal(n, ls, 0)
    noisy = float(spec["signal"]) * s + float(spec["label_noise"]) * _normal(
        n, ls, 1)
    y = (_grade_in_queries(noisy, sizes) if sizes is not None
         else (noisy > 0).astype(np.float64))
    X = np.empty((n, F), np.float64)
    c = float(np.sqrt(1.0 - a * a))

    def fill(b, lo, hi):
        blk = X[lo:hi]
        np.random.default_rng([int(seed), 2, b]).standard_normal(out=blk)
        blk[:, :k] *= c
        blk[:, :k] += a * s[lo:hi, None]
        np.multiply(blk, 0.5, out=blk)
        np.exp(blk, out=blk)
    _par(fill, _blocks(n))
    return X, y, sizes
