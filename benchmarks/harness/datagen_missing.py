"""Inputs from the seed: a production-line table whose columns are mostly
missing.

The shape of Kaggle's "Bosch Production Line Performance" numeric training
file (``train_numeric.csv``): float columns named ``L{line}_S{station}_F{n}``
over a few lines and their stations.  A part passes some lines and, on a line,
some of its stations; the columns of a station it did not pass are empty
(NaN), and so now and then is one column of a station it did pass.  The label
says whether the part failed its final check, and is rare.  Each column takes
few distinct values (a gauge's resolution).

Same contract as ``datagen.make_table``, and the same split between the two
seeds, for the same reason (the program bakes each column's bin count,
default bin and missing type into its compiled growers as HLO constants, which
key the compile cache; so does the label vector):

- from the configuration's ``label_seed``, the same in every run: the layout
  (how many columns each station has), each column's value grid, every row's
  route (the stations it passed) and with it the whole missing mask, a latent
  quality score per row, and the label, which rises with the latent and with
  the stations a row passed (``route_weight``): which side a NaN goes to
  decides a split;
- from ``--seed``: every value.  ``informative`` columns are noisy views of
  the latent (``loading``), the others noise; a value is the grid point of
  its quantile, so every grid point is drawn about equally often and the
  column's bins do not move with the draw.  Grid points are positive, as in
  ``datagen.py``: 0.0 lies under every bin.

The data group: ``rows``, ``features`` (the sum over ``lines`` of their
``features``), ``lines`` (``stations`` and ``features`` each, and ``visit``,
the share of rows that pass the line; a line that says ``"or_line": k`` is
passed by the rows that do not pass line ``k``), ``missing_share`` (the share
of entries that are NaN, met in expectation by scaling every station's pass
rate), ``dropout`` (NaN share inside a station a row passed), ``values``
(``[least, most]`` distinct values a column), ``informative``, ``loading``,
``signal``, ``route_weight``, ``label_noise``, ``positive_rate`` and
``label_seed``.

Generation is by fixed blocks of rows, one stream a block: a prefix of the
table is a prefix of the draw, whatever the number of threads.
"""
from __future__ import annotations

import numpy as np
from scipy.special import ndtr

from .datagen import _par

BLOCK = 16384       # rows a stream; small enough that every core gets work


def layout(spec: dict) -> dict:
    """What ``label_seed`` fixes of the columns: ``station`` [F] (the station
    of each column), ``line`` [S] (the line of each station), ``rate`` [S]
    (the share of the line's rows that pass the station), ``grid`` (each
    column's distinct values, ascending) and ``informative`` (the columns
    that view the latent)."""
    rng = np.random.default_rng([int(spec["label_seed"]), 20])
    lines = spec["lines"]
    F = sum(int(ln["features"]) for ln in lines)
    if F != int(spec["features"]):
        raise ValueError(f"the lines' columns make {F}, the configuration "
                         f"says {spec['features']}")
    station, line = [], []
    for li, ln in enumerate(lines):
        S, Fl = int(ln["stations"]), int(ln["features"])
        # each station at least one column, the rest by uneven weights
        extra = rng.multinomial(Fl - S, rng.dirichlet(np.full(S, 0.7)))
        first = len(line)
        station += [first + s for s in range(S) for _ in range(1 + extra[s])]
        line += [li] * S
    station, line = np.asarray(station), np.asarray(line)
    raw = rng.uniform(0.2, 1.0, len(line))
    visit = _line_visit(lines)
    cols_of = np.bincount(station, minlength=len(line))
    kept = (1.0 - float(spec["dropout"])) / F
    want = 1.0 - float(spec["missing_share"])
    lo, hi = 0.0, 1.0 / raw.min()       # present share rises with the scale
    for _ in range(60):
        c = (lo + hi) / 2
        got = kept * np.sum(cols_of * visit[line] * np.minimum(1.0, c * raw))
        lo, hi = (c, hi) if got < want else (lo, c)
    if got < want - 1e-6:
        raise ValueError(f"the lines cannot be passed often enough for a "
                         f"missing share of {spec['missing_share']}")
    least, most = (int(v) for v in spec["values"])
    k = np.exp(rng.uniform(np.log(least), np.log(most + 1), F)).astype(int)
    k = np.clip(k, least, most)
    base = np.round(rng.uniform(0.01, 1.0, F), 3)
    step = np.round(rng.uniform(0.001, 0.05, F), 3)
    grid = [np.round(base[j] + step[j] * np.arange(k[j]), 3)
            for j in range(F)]
    informative = np.sort(rng.choice(F, int(spec["informative"]),
                                     replace=False))
    return {"station": station, "line": line,
            "rate": np.minimum(1.0, c * raw), "grid": grid,
            "informative": informative}


def _line_visit(lines) -> np.ndarray:
    """Share of all rows that pass each line."""
    out = np.zeros(len(lines))
    for li, ln in enumerate(lines):
        other = ln.get("or_line")
        out[li] = (1.0 - out[int(other)]) if other is not None \
            else float(ln["visit"])
    return out


def _routes(spec: dict, lay: dict, n: int) -> np.ndarray:
    """``[n, S]`` bool: the stations each row passed (``label_seed``)."""
    rng = np.random.default_rng([int(spec["label_seed"]), 21])
    lines = spec["lines"]
    on = np.zeros((n, len(lines)), bool)
    for li, ln in enumerate(lines):
        u = rng.random(n)
        other = ln.get("or_line")
        on[:, li] = (~on[:, int(other)]) if other is not None \
            else u < float(ln["visit"])
    return on[:, lay["line"]] & (rng.random((n, len(lay["line"])))
                                 < lay["rate"])


def make_table(spec: dict, seed: int, rows: int = None):
    """``(X [N, F] float32 C-order with NaN, y [N] float64, None)`` for a
    configuration's ``data`` group.  ``rows`` cuts the table to its first
    rows, for the oracle's slice: same routes, same labels, same values."""
    n_full, F = int(spec["rows"]), int(spec["features"])
    lay = layout(spec)
    ls = int(spec["label_seed"])
    # the routes and the label are made for the whole table, so that a slice
    # is a prefix of it: the label's cut is the table's quantile
    passed = _routes(spec, lay, n_full)
    rng = np.random.default_rng([ls, 22])
    s = rng.standard_normal(n_full)
    w = rng.standard_normal(len(lay["line"])) * float(spec["route_weight"])
    score = (float(spec["signal"]) * s + passed.astype(np.float64) @ w
             + float(spec["label_noise"]) * rng.standard_normal(n_full))
    pos = int(round(float(spec["positive_rate"]) * n_full))
    y = np.zeros(n_full, np.float64)
    y[np.argpartition(-score, pos)[:pos]] = 1.0
    n = n_full if rows is None else min(int(rows), n_full)
    y, s, passed = y[:n].copy(), s[:n], passed[:n]

    k = np.asarray([len(g) for g in lay["grid"]])
    offset = np.concatenate([[0], np.cumsum(k)[:-1]])
    flat = np.concatenate(lay["grid"]).astype(np.float32)
    inf = lay["informative"]
    a = float(spec["loading"])
    c = float(np.sqrt(1.0 - a * a))
    drop = float(spec["dropout"])
    X = np.empty((n, F), np.float32)

    def fill(b, lo, hi):
        # one stream a draw, so that a block cut short draws a prefix
        r = np.random.default_rng([int(seed), 23, b])
        idx = (r.random((hi - lo, F), np.float32) * k).astype(np.int32)
        r = np.random.default_rng([int(seed), 25, b])
        z = a * s[lo:hi, None] + c * r.standard_normal((hi - lo, len(inf)))
        idx[:, inf] = np.minimum(ndtr(z) * k[inf], k[inf] - 1).astype(
            np.int32)
        blk = X[lo:hi]
        np.take(flat, idx + offset, out=blk)
        present = passed[lo:hi][:, lay["station"]]
        m = np.random.default_rng([ls, 24, b])
        present &= m.random((hi - lo, F), np.float32) >= drop
        blk[~present] = np.nan
    _par(fill, [(b, lo, min(lo + BLOCK, n))
                for b, lo in enumerate(range(0, n, BLOCK))])
    return X, y, None
