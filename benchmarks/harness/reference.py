"""The plain reference: NumPy only, independent of the code under test.

A LightGBM model text is parsed into flat arrays and walked row by row in
float64 (numerical splits, ``x <= threshold`` goes left; the benchmark's data
has no missing values and no categorical feature, and a model that has one is
refused rather than guessed at).  AUC, NDCG@10 and log loss are the
reference's definitions (src/metric/binary_metric.hpp, rank_metric.hpp,
dcg_calculator.cpp), written out.
"""
from __future__ import annotations

import numpy as np


def parse_model_string(text: str) -> list:
    """Trees of a model text as dicts of arrays: ``split_feature``,
    ``threshold``, ``left_child``, ``right_child`` ([L-1]) and ``leaf_value``
    ([L]).  A child ``c >= 0`` is a node, ``c < 0`` the leaf ``~c``."""
    trees = []
    for chunk in text.split("\nTree=")[1:]:
        kv = {}
        for line in chunk.split("end of trees")[0].splitlines():
            k, sep, v = line.partition("=")
            if sep:
                kv[k.strip()] = v.strip()
        if int(kv.get("num_cat", "0")) != 0:
            raise ValueError("the plain reference walks numerical splits only")

        def arr(key, dtype):
            return np.asarray(kv.get(key, "").split(), dtype=dtype)
        tree = {"num_leaves": int(kv["num_leaves"]),
                "split_feature": arr("split_feature", np.int64),
                "threshold": arr("threshold", np.float64),
                "left_child": arr("left_child", np.int64),
                "right_child": arr("right_child", np.int64),
                "leaf_value": arr("leaf_value", np.float64)}
        trees.append(tree)
    return trees


def tree_leaves(tree: dict, X: np.ndarray) -> np.ndarray:
    """Leaf index of every row of ``X``."""
    n = X.shape[0]
    if tree["num_leaves"] <= 1:
        return np.zeros(n, np.int64)
    node = np.zeros(n, np.int64)
    rows = np.arange(n)
    sf, th = tree["split_feature"], tree["threshold"]
    lc, rc = tree["left_child"], tree["right_child"]
    while rows.size:
        nd = node[rows]
        left = X[rows, sf[nd]] <= th[nd]
        nxt = np.where(left, lc[nd], rc[nd])
        node[rows] = nxt
        rows = rows[nxt >= 0]
    return ~node


def predict_raw(trees: list, X: np.ndarray) -> np.ndarray:
    """Sum of the trees' leaf values, float64."""
    X = np.asarray(X, np.float64)
    out = np.zeros(X.shape[0], np.float64)
    for tree in trees:
        out += tree["leaf_value"][tree_leaves(tree, X)]
    return out


def root_split(tree: dict):
    """(feature, threshold) of a tree's first split, or None for a stump."""
    if tree["num_leaves"] <= 1:
        return None
    return int(tree["split_feature"][0]), float(tree["threshold"][0])


def auc(y: np.ndarray, score: np.ndarray) -> float:
    """Area under the ROC curve, ties sharing their rank."""
    y = np.asarray(y) > 0
    _, inv, cnt = np.unique(score, return_inverse=True, return_counts=True)
    rank = (np.cumsum(cnt) - (cnt - 1) / 2.0)[inv]      # 1-based, averaged
    n_pos = int(y.sum())
    n_neg = len(y) - n_pos
    if n_pos == 0 or n_neg == 0:
        return 1.0
    return float((rank[y].sum() - n_pos * (n_pos + 1) / 2.0)
                 / (n_pos * n_neg))


def logloss(y: np.ndarray, raw: np.ndarray) -> float:
    """Binary log loss of raw scores under sigmoid:1."""
    z = np.where(np.asarray(y) > 0, raw, -raw)
    return float(np.mean(np.logaddexp(0.0, -z)))


def ndcg_at_k(y: np.ndarray, score: np.ndarray, sizes: np.ndarray,
              k: int = 10) -> float:
    """Mean NDCG@k over queries: gain ``2^label - 1``, discount
    ``1 / log2(2 + position)``, ties in score kept in row order, and a query
    with no relevant document counted as 1 (the reference's convention)."""
    y = np.asarray(y, np.float64)
    sizes = np.asarray(sizes, np.int64)
    n, nq = len(y), len(sizes)
    qid = np.repeat(np.arange(nq), sizes)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    gain = np.exp2(y) - 1.0

    def dcg(order):
        pos = np.arange(n) - starts[qid]           # qid is sorted already
        top = pos < k
        g = gain[order] * top / np.log2(2.0 + pos)
        return np.add.reduceat(g, starts)
    got = dcg(np.lexsort((np.arange(n), -np.asarray(score, np.float64), qid)))
    best = dcg(np.lexsort((np.arange(n), -gain, qid)))
    with np.errstate(invalid="ignore", divide="ignore"):
        nd = np.where(best > 0, got / best, 1.0)
    return float(nd.mean())
