"""Inputs from the seed: the one-hot table of ``datagen_onehot.py`` with its
categorical variables kept as integer codes.

The same draw, not a copy of it: ``datagen_onehot.make_table`` makes the CSR
(one stored column index a variable and row), and the code of a variable is
that index less the first column of the variable's one-hot block.  So a seed
gives the rows, the codes, the numeric columns and the labels of the
``expo`` configuration, as ``len(variables) + numeric`` dense float32
columns: the categorical ones first, declared to the trainer by
``categorical_columns``.  The data group is ``expo``'s with ``features``
the dense count and ``"encoding": "codes"``.
"""
from __future__ import annotations

import numpy as np

from . import datagen_onehot


def categorical_columns(spec: dict) -> list:
    """The columns of ``make_table``'s matrix that hold category codes."""
    return list(range(len(spec["variables"])))


def make_table(spec: dict, seed: int, rows: int = None):
    """``(X [N, V + M] float32, y [N], None)`` for a configuration's ``data``
    group whose ``encoding`` is ``codes``.  ``rows`` cuts the table to its
    first rows (the oracle's slice): same codes, same labels."""
    if spec.get("encoding") != "codes":
        raise ValueError("datagen_codes makes tables whose data group says "
                         "\"encoding\": \"codes\"")
    V, M = len(spec["variables"]), int(spec["numeric"])
    if int(spec["features"]) != V + M:
        raise ValueError(f"{V} variables and {M} numeric columns make "
                         f"{V + M} columns, the configuration says "
                         f"{spec['features']}")
    off = datagen_onehot.column_offsets(spec)
    Xs, y, _ = datagen_onehot.make_table(
        {**spec, "features": int(off[-1]) + M}, seed, rows)
    n = len(y)
    X = np.empty((n, V + M), np.float32)
    X[:, :V] = Xs.indices.reshape(n, V + M)[:, :V] - off[:V]
    X[:, V:] = Xs.data.reshape(n, V + M)[:, V:]
    return X, y, None
