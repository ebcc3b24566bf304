"""The plain reference for tables with missing values: NumPy and float64,
independent of the code under test.

(i) ``predict_raw``: a walk of the model text that honours each node's
``decision_type`` as the reference's ``Tree::NumericalDecision`` does
(include/LightGBM/tree.h): bit 1 is default-left, bits 2-3 the missing type
(0 None, 1 Zero, 2 NaN).  A NaN becomes 0.0 where the missing type is not
NaN; a NaN goes the default way under NaN, a zero (``|x| <= 1e-35``) under
Zero; every other value goes left where ``x <= threshold``.

(ii) ``grow_tree``: one leaf-wise tree on binned data, by the rules of the
reference's ``FindBestThresholdSequence`` / ``FindBestThresholdNumerical``
(src/treelearner/feature_histogram.hpp) and ``SerialTreeLearner::Train``:
histograms by ``np.bincount`` (the smaller child's; the larger is the parent
less it); a feature whose missing type is NaN and that has more than two bins
is scanned right to left and then left to right, the NaN bin in neither
running sum (its rows land on the side the scan leaves: ``default_left`` is
``dir == -1``); any other only right to left; a side below
``min_data_in_leaf`` rows or ``min_sum_hessian_in_leaf`` is no candidate; the
gain is ``G_l^2 / H_l + G_r^2 / H_r`` and has to pass the parent's
``G^2 / H``; the first strict maximum wins, so a tie goes to the right-to-left
scan, the higher threshold within it, the lower feature, and among leaves the
lower leaf index; growth stops where no leaf has a positive gain or at
``num_leaves``.  A leaf's value is ``-G / H``.

(iii) ``bin_columns``: the reference's bin layout for a column of positive
values with NaNs (``BinMapper::FindBin``, src/io/bin.cpp) where every
distinct value keeps a bin of its own: bin 0 for values at or under zero (it
holds none here), one bin a distinct value in ascending order, the NaN bin
last.

``compare_trees`` holds a program's tree to ``grow_tree``'s on the same rows,
node by node as partitions of the node's rows (module text of
``kinds/boost_missing.py``).

(iv) ``judge``: a program's tree grown on a table too large for
``grow_tree``, held node by node to the best cut of the rows the tree brings
each node (the scan of (ii) on that node's histograms), its gains and its
leaf values to float64 sums; ``bin_table`` bins such a table feature-major
in bytes.

Departures from the reference, each harmless to what is compared:

- no ``kEpsilon`` (1e-15) seeds the hessian sums: it moves a gain in the
  15th digit, where the leaves' hessians here are 1e-3 and more;
- ``lambda_l1``, ``lambda_l2``, ``max_delta_step``, ``min_gain_to_split``,
  ``max_depth``, monotone constraints, feature sampling and categorical
  columns are not modelled: ``grow_tree`` refuses parameters that set them;
- ``bin_columns`` refuses a column the reference would merge bins of (more
  distinct values than ``max_bin - 2``, a value but the largest under
  ``min_data_in_bin`` rows, or a value at or under zero), rather than copy
  the greedy merge;
- the histograms sum float64 gradients and hessians of float64 scores; the
  reference's are float64 sums of float32 gradients.
"""
from __future__ import annotations

import numpy as np

from . import reference

K_ZERO = 1e-35          # kZeroThreshold
_DEFAULT_LEFT = 2       # kDefaultLeftMask
MISSING_NONE, MISSING_ZERO, MISSING_NAN = 0, 1, 2
_REFUSED = {"lambda_l1": 0.0, "lambda_l2": 0.0, "max_delta_step": 0.0,
            "min_gain_to_split": 0.0}


# ---- (i) the walk ---------------------------------------------------------
_MORE_KEYS = {"decision_type": np.int64, "split_gain": np.float64,
              "internal_count": np.int64, "leaf_count": np.int64}


def parse_model_string(text: str) -> list:
    """``reference.parse_model_string``'s trees, each with its
    ``decision_type``, ``split_gain`` and ``internal_count`` ([L-1]) and
    ``leaf_count`` ([L])."""
    trees = reference.parse_model_string(text)
    for tree, chunk in zip(trees, text.split("\nTree=")[1:]):
        lines = chunk.split("end of trees")[0].splitlines()
        for key, dtype in _MORE_KEYS.items():
            line = next((ln for ln in lines if ln.startswith(key + "=")),
                        key + "=")
            tree[key] = np.asarray(line.partition("=")[2].split(), dtype)
        if np.any(tree["decision_type"] & 1):
            raise ValueError("the plain reference walks numerical splits "
                             "only")
    return trees


def goes_left(x: np.ndarray, threshold: float, decision_type: int):
    """``Tree::NumericalDecision`` over a column of values."""
    x = np.asarray(x, np.float64)
    missing = (int(decision_type) >> 2) & 3
    nan = np.isnan(x)
    if missing != MISSING_NAN:
        x = np.where(nan, 0.0, x)
    if missing == MISSING_ZERO:
        miss = np.abs(x) <= K_ZERO
    elif missing == MISSING_NAN:
        miss = nan
    else:
        miss = np.zeros(x.shape, bool)
    left = x <= threshold           # False for NaN
    return np.where(miss, bool(int(decision_type) & _DEFAULT_LEFT), left)


def tree_leaves(tree: dict, X: np.ndarray) -> np.ndarray:
    """Leaf index of every row of ``X``."""
    n = X.shape[0]
    leaf = np.zeros(n, np.int64)
    if tree["num_leaves"] <= 1:
        return leaf
    stack = [(0, np.arange(n))]
    while stack:
        node, rows = stack.pop()
        left = goes_left(X[rows, tree["split_feature"][node]],
                         tree["threshold"][node], tree["decision_type"][node])
        for child, sub in ((tree["left_child"][node], rows[left]),
                           (tree["right_child"][node], rows[~left])):
            if child < 0:
                leaf[sub] = ~child
            else:
                stack.append((child, sub))
    return leaf


def predict_raw(trees: list, X: np.ndarray) -> np.ndarray:
    """Sum of the trees' leaf values, float64."""
    out = np.zeros(X.shape[0], np.float64)
    for tree in trees:
        out += tree["leaf_value"][tree_leaves(tree, X)]
    return out


# ---- (iii) the bins -------------------------------------------------------
def _bin_column(x: np.ndarray, max_bin: int, min_data_in_bin: int,
                f: int) -> tuple:
    """One column's bins (``bin_columns``), its distinct values and
    whether it has a NaN."""
    nan = np.isnan(x)
    xv = x[~nan]
    v, cnt = np.unique(xv, return_counts=True)
    # a value closes its bin where the rows so far reach min_data_in_bin;
    # the last closes it anyway (GreedyFindBin)
    if len(v) == 0 or v[0] <= 0.0 or len(v) > max_bin - 2 \
            or (len(v) > 1 and cnt[:-1].min() < min_data_in_bin):
        raise ValueError(f"column {f}: the reference would merge its "
                         f"bins; this layout does not model that")
    b = np.full(len(x), len(v) + 1, np.int64)
    b[~nan] = np.searchsorted(v, xv) + 1
    return b, v.astype(np.float64), bool(nan.any())


def bin_columns(X: np.ndarray, max_bin: int, min_data_in_bin: int = 3):
    """``(bins [N, F] int32, num_bins [F])``: bin 0 empty, then one bin a
    distinct value in ascending order, the NaN bin last, where the column
    has a NaN (its missing type is then NaN; else None)."""
    N, F = X.shape
    bins = np.empty((N, F), np.int32)
    num_bins = np.empty(F, np.int64)
    for f in range(F):
        b, v, nan = _bin_column(X[:, f], max_bin, min_data_in_bin, f)
        num_bins[f] = len(v) + 1 + int(nan)
        bins[:, f] = b
    return bins, num_bins


def bin_table(X: np.ndarray, max_bin: int, min_data_in_bin: int = 3,
              block: int = 32) -> dict:
    """``bin_columns``'s layout for a table too large for its ``[N, F]``
    int32 copy: ``bins`` feature-major ``uint8 [F, N]``, ``num_bins``,
    ``values`` (a column's distinct values, bin ``b`` >= 1 holding
    ``values[b - 1]``) and ``missing_nan`` [F], binned ``block`` columns at
    a time."""
    N, F = X.shape
    if max_bin > 256:
        raise ValueError("bin_table keeps a bin in a byte")
    bins = np.empty((F, N), np.uint8)
    num_bins = np.empty(F, np.int64)
    missing_nan = np.empty(F, bool)
    values = []
    for c0 in range(0, F, block):
        cols = np.ascontiguousarray(X[:, c0:c0 + block].T)
        for j, x in enumerate(cols):
            f = c0 + j
            b, v, nan = _bin_column(x, max_bin, min_data_in_bin, f)
            bins[f] = b
            num_bins[f] = len(v) + 1 + int(nan)
            missing_nan[f] = nan
            values.append(v)
    return {"bins": bins, "num_bins": num_bins, "values": values,
            "missing_nan": missing_nan}


# ---- (ii) the grower ------------------------------------------------------
def _histogram(bins, g, h, rows, offset, size, chunk=8192):
    """``[3, size]`` float64 sums of g, h and 1 by (feature, bin) over
    ``rows``, by ``np.bincount``."""
    out = np.zeros((3, size))
    F = bins.shape[1]
    for lo in range(0, len(rows), chunk):
        r = rows[lo:lo + chunk]
        flat = (bins[r] + offset).ravel()
        out[0] += np.bincount(flat, np.repeat(g[r], F), size)
        out[1] += np.bincount(flat, np.repeat(h[r], F), size)
        out[2] += np.bincount(flat, minlength=size)
    return out


def _scan(hist, num_bins, missing_nan, G, H, n, params, hess_tol):
    """Every candidate of one leaf, ``[F, 2B]``: the right-to-left scan by
    descending threshold, then the left-to-right one by ascending, as the
    reference tries them.  ``gain`` (over the parent's; -inf where no
    candidate), ``raw`` (the same where the row counts and the hessian bound
    are held, of any sign), ``at_bound``, ``left_h``, ``left_c``,
    ``threshold``, ``default_left``, and ``scale``, the parent's
    ``G^2 / H``, which every gain is the difference of a sum of two such
    terms with.  The gains of ``at_bound`` are those of the candidates with
    a side within ``hess_tol`` of the hessian bound, on either side of it."""
    F, B = len(num_bins), hist.shape[2]
    g, h, c = hist
    t = np.arange(B)[None, :]
    nb = num_bins[:, None]
    both = (nb > 2) & missing_nan[:, None]
    acc = (t < nb) & ~(both & (t == nb - 1))
    g, h, c = g * acc, h * acc, c * acc
    # left-to-right: left holds bins 0..t
    lg1, lh1, lc1 = (np.cumsum(a, axis=1) for a in (g, h, c))
    ok1 = both & (t <= nb - 2)
    # right-to-left: right holds bins t+1.. (the NaN bin excluded)
    rg2, rh2, rc2 = (np.concatenate([np.cumsum(a[:, ::-1], axis=1)[:, ::-1]
                                     [:, 1:], np.zeros((F, 1))], axis=1)
                     for a in (g, h, c))
    lg2, lh2, lc2 = G - rg2, H - rh2, n - rc2
    ok2 = t <= nb - 2 - both
    shift = G * G / H
    md, mh = params["min_data_in_leaf"], params["min_sum_hessian_in_leaf"]

    def gains(lg, lh, lc, ok):
        rg, rh, rc = G - lg, H - lh, n - lc
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = lg * lg / lh + rg * rg / rh - shift
        counts = ok & (lc >= md) & (rc >= md) & (lh > 0) & (rh > 0)
        raw = np.where(counts, gain, -np.inf)
        bound = (lh >= mh) & (rh >= mh)
        near = np.abs(np.minimum(lh, rh) - mh) <= hess_tol
        return (np.where(bound & (raw > 0), raw, -np.inf),
                np.where(bound, raw, -np.inf), np.where(near, raw, -np.inf))
    gain1, raw1, near1 = gains(lg1, lh1, lc1, ok1)
    gain2, raw2, near2 = gains(lg2, lh2, lc2, ok2)
    cat = np.concatenate
    tt = np.broadcast_to(t, (F, B))
    return {"gain": cat([gain2[:, ::-1], gain1], axis=1),
            "raw": cat([raw2[:, ::-1], raw1], axis=1),
            "at_bound": cat([near2[:, ::-1], near1], axis=1),
            "left_h": cat([lh2[:, ::-1], lh1], axis=1),
            "left_c": cat([lc2[:, ::-1], lc1], axis=1),
            "threshold": cat([tt[:, ::-1], tt], axis=1),
            "default_left": cat([np.ones((F, B), bool),
                                 np.zeros((F, B), bool)], axis=1),
            "scale": shift}


def _best(sc, H, n, tie_rtol) -> dict:
    """The winner of a leaf's candidates (None where there is none), and
    ``fragile``: whether rounding may give another answer there.  Gains are
    differences of sums of ``G^2 / H`` terms, so a gain is told apart from
    another, or from zero, only beyond ``tie_rtol`` of those terms
    (``tol``).  Fragile: a candidate that splits the rows otherwise within
    ``tol`` of the winner (``margin``, relative to the winner's gain), a
    winner itself within ``tol`` of zero (a pure leaf: every split gains
    nothing, and the sign of the rounding decides), or, at the hessian
    bound, a candidate within ``tol`` of the winner (of zero, where there is
    no winner) that rounding may let in or keep out (``at_bound``)."""
    gain = sc["gain"]
    per_f = np.argmax(gain, axis=1)
    fbest = int(np.argmax(gain[np.arange(len(per_f)), per_f]))
    j = per_f[fbest]
    best = float(gain[fbest, j])
    edge = float(sc["at_bound"].max())
    if not best > 0:
        tol = tie_rtol * sc["scale"]
        return {"split": None,
                "fragile": bool(float(sc["raw"].max()) > -tol
                                or edge > -tol)}
    tol = tie_rtol * (sc["scale"] + best)
    lc, lh = sc["left_c"][fbest, j], sc["left_h"][fbest, j]
    rc, rh = n - lc, H - lh
    # a candidate that splits the same rows (an empty bin's neighbour, the
    # mirror of the NaN-against-the-rest split, a column with the same
    # partition) has the same unordered pair of sides
    eps = 1e-9 * H
    same = (((sc["left_c"] == lc) & (np.abs(sc["left_h"] - lh) <= eps))
            | ((sc["left_c"] == rc) & (np.abs(sc["left_h"] - rh) <= eps)))
    runner = float(np.where(same, -np.inf, gain).max())
    split = {"feature": fbest, "threshold": int(sc["threshold"][fbest, j]),
             "default_left": bool(sc["default_left"][fbest, j]),
             "gain": best, "margin": (best - runner) / best}
    return {"split": split,
            "fragile": bool(best - runner <= tol or best <= tol
                            or edge >= best - tol)}


def grow_tree(bins, num_bins, missing_nan, g, h, params, tie_rtol=1e-6,
              hess_rtol=1e-5) -> dict:
    """One tree (module text).  ``bins`` [N, F] ints, ``num_bins`` [F],
    ``missing_nan`` [F] bool (the last bin holds the NaNs), ``g``, ``h``
    [N].  ``params``: ``num_leaves``, ``min_data_in_leaf``,
    ``min_sum_hessian_in_leaf``.  Returns ``nodes`` (split order: ``leaf``,
    ``feature``, ``threshold`` bin, ``default_left``, ``gain``, ``margin``,
    ``fragile``, ``rows`` and ``left_rows``, ``children`` as the model text
    numbers them), and by leaf index ``leaf_rows``, ``leaf_value`` and
    ``leaf_fragile`` (rounding may split it).

    A leaf's sums in a float32 program are the parent's less the sibling's
    (or its own pass, rounded as the operands are): their error goes by the
    root's sums, not the leaf's.  So ``tie_rtol`` is taken of a leaf's gain
    terms times the root's hessian over the leaf's, and ``hess_rtol`` of the
    root's hessian."""
    for k, v in _REFUSED.items():
        if float(params.get(k, v)) != v:
            raise ValueError(f"the plain grower does not model {k}")
    bins = np.asarray(bins, np.int64)
    g, h = np.asarray(g, np.float64), np.asarray(h, np.float64)
    num_bins = np.asarray(num_bins, np.int64)
    missing_nan = np.asarray(missing_nan, bool)
    F = bins.shape[1]
    B = int(num_bins.max())
    offset = np.arange(F, dtype=np.int64) * B
    size = F * B
    L = int(params["num_leaves"])
    H_root = float(h.sum())

    def leaf_state(rows, hist):
        G, H, n = float(g[rows].sum()), float(h[rows].sum()), len(rows)
        sc = _scan(hist.reshape(3, F, B), num_bins, missing_nan, G, H, n,
                   params, hess_rtol * H_root)
        return {"rows": rows, "hist": hist, "G": G, "H": H,
                **_best(sc, H, n, tie_rtol * H_root / H)}
    all_rows = np.arange(bins.shape[0])
    leaves = [leaf_state(all_rows, _histogram(bins, g, h, all_rows, offset,
                                              size))]
    parent = [(-1, False)]          # (node, is right child) of each leaf
    nodes = []
    while len(leaves) < L:
        gains = [lf["split"]["gain"] if lf["split"] else -np.inf
                 for lf in leaves]
        li = int(np.argmax(gains))
        if not gains[li] > 0:
            break
        lf, sp = leaves[li], leaves[li]["split"]
        col = bins[lf["rows"], sp["feature"]]
        nb = num_bins[sp["feature"]]
        is_nan = missing_nan[sp["feature"]] & (col == nb - 1)
        left = np.where(is_nan, sp["default_left"], col <= sp["threshold"])
        lrows, rrows = lf["rows"][left], lf["rows"][~left]
        small = lrows if len(lrows) < len(rrows) else rrows
        hs = _histogram(bins, g, h, small, offset, size)
        hl = lf["hist"] - hs
        hist_l, hist_r = (hs, hl) if small is lrows else (hl, hs)
        k = len(nodes)
        pn, right = parent[li]
        if pn >= 0:
            nodes[pn]["children"][int(right)] = k
        new = len(leaves)
        nodes.append({**sp, "leaf": li, "fragile": lf["fragile"],
                      "rows": lf["rows"], "left_rows": lrows,
                      "children": [~li, ~new]})
        leaves[li] = leaf_state(lrows, hist_l)
        leaves.append(leaf_state(rrows, hist_r))
        parent[li] = (k, False)
        parent.append((k, True))
    return {"nodes": nodes,
            "leaf_rows": [lf["rows"] for lf in leaves],
            "leaf_value": np.asarray([-lf["G"] / lf["H"] for lf in leaves]),
            "leaf_fragile": [lf["fragile"] for lf in leaves]}


def root_of(grown: dict):
    """(feature, threshold bin, default_left) of the first split, or None."""
    if not grown["nodes"]:
        return None
    nd = grown["nodes"][0]
    return nd["feature"], nd["threshold"], nd["default_left"]


def compare_trees(grown: dict, tree: dict, X: np.ndarray,
                  leaf_rtol=1e-4, shrinkage=1.0) -> dict:
    """A program's tree (``parse_model_string``'s, walked over ``X``, the
    rows ``grown`` grew on) against ``grow_tree``'s, from the root down: two
    nodes are the same split where they cut the node's rows into the same
    two sets (either way round: the mirror of a NaN-against-the-rest split
    is the same split).  Below a node or leaf ``grow_tree`` calls fragile
    (rounding may give another answer there) the trees may part, and
    nothing more is compared.  Every other node has to be the same split,
    and every leaf reached on both sides the same rows with a value within
    ``leaf_rtol`` of the tree's largest (the program's leaf values carry the
    learning rate, ``shrinkage``): a leaf's sums are the parent's less the
    sibling's, so its error goes by its ancestors' sums, not its own."""
    out = {"nodes": 0, "same": 0, "same_feature": 0, "tied": 0,
           "differ": [], "leaves": 0, "leaf_err": 0.0, "leaf_fail": [],
           "root": None, "root_fragile": False, "root_same": False,
           "ok": False}
    nodes = grown["nodes"]
    ref_value = shrinkage * grown["leaf_value"]
    scale = float(np.abs(ref_value).max())

    def visit(rc, pc, rows):
        """Reference child ``rc`` against program child ``pc``, both over
        ``rows`` (ascending)."""
        fragile = (nodes[rc]["fragile"] if rc >= 0
                   else grown["leaf_fragile"][~rc])
        if rc < 0 and pc < 0:
            v, pv = ref_value[~rc], float(tree["leaf_value"][~pc])
            err = abs(pv - v) / scale
            out["leaves"] += 1
            out["leaf_err"] = max(out["leaf_err"], err)
            if err > leaf_rtol:
                out["leaf_fail"].append({"rows": len(rows), "ref": v,
                                         "prog": pv})
            return
        if rc < 0 or pc < 0:
            if fragile:
                out["tied"] += 1
            else:
                out["differ"].append({"rows": len(rows), "ref_leaf": rc < 0,
                                      "prog_leaf": pc < 0})
            return
        nd = nodes[rc]
        out["nodes"] += 1
        f = int(tree["split_feature"][pc])
        pleft = goes_left(X[rows, f], tree["threshold"][pc],
                          tree["decision_type"][pc])
        rleft = np.isin(rows, nd["left_rows"], assume_unique=True)
        kids = (nd["children"], (tree["left_child"][pc],
                                 tree["right_child"][pc]))
        if np.array_equal(pleft, rleft):
            pairs = zip(*kids, (rleft, ~rleft))
        elif np.array_equal(pleft, ~rleft):
            pairs = zip(kids[0], kids[1][::-1], (rleft, ~rleft))
        else:
            if fragile:
                out["tied"] += 1
            else:
                out["differ"].append({"rows": len(rows), "ref": (
                    nd["feature"], nd["threshold"], nd["default_left"]),
                    "prog_feature": f, "margin": nd["margin"]})
            return
        out["same"] += 1
        out["same_feature"] += int(f == nd["feature"])
        for r, p, m in pairs:
            visit(r, p, rows[m])

    if not nodes or tree["num_leaves"] <= 1:
        out["root_same"] = out["ok"] = bool(not nodes) == bool(
            tree["num_leaves"] <= 1)
        return out
    out["root"] = root_of(grown)
    out["root_fragile"] = bool(nodes[0]["fragile"])
    visit(0, 0, np.arange(X.shape[0]))
    out["root_same"] = out["same"] > 0
    out["ok"] = bool(out["root_same"] and not out["differ"]
                     and not out["leaf_fail"])
    return out


# ---- (iv) a tree of the timed trainer, judged at its own size --------------
def _node_hist(tb: dict, g, h, rows, B: int) -> np.ndarray:
    """``[3, F, B]`` float64 sums of g, h and 1 by (feature, bin) over
    ``rows`` (every row where None), a column at a time."""
    bins = tb["bins"]
    out = np.zeros((3, bins.shape[0], B))
    gr, hr = (g, h) if rows is None else (g[rows], h[rows])
    for f in range(bins.shape[0]):
        b = (bins[f] if rows is None else bins[f][rows]).astype(np.intp)
        out[0, f] = np.bincount(b, gr, B)
        out[1, f] = np.bincount(b, hr, B)
        out[2, f] = np.bincount(b, minlength=B)
    return out


def _bin_goes_left(tb: dict, tree: dict, node: int) -> tuple:
    """The node's column, and ``goes_left`` of each of its bins' values
    (bin 0, under zero, as 0.0; the NaN bin as NaN)."""
    f = int(tree["split_feature"][node])
    rep = np.concatenate([[0.0], tb["values"][f],
                          [np.nan] if tb["missing_nan"][f] else []])
    return f, goes_left(rep, tree["threshold"][node],
                        tree["decision_type"][node])


def _judge_node(tree, node, hist, rows, left, tb, g, h, params, H_root,
                tie_rtol, hess_rtol) -> dict:
    """One node of the program's tree against the best cut of its rows
    (``judge``)."""
    prog = (int(tree["split_feature"][node]), float(tree["threshold"][node]),
            int(tree["decision_type"][node]))
    gl, hl = g[rows[left]].sum(), h[rows[left]].sum()
    G, H, n = float(g[rows].sum()), float(h[rows].sum()), len(rows)
    gr, hr = G - gl, H - hl
    if not H > 0:                   # the walk brings the node no row
        return {"node": int(node), "rows": n, "count_ok": False,
                "same": False, "tied": False, "bound_ok": False,
                "gain_err": float("inf"), "ref": None, "prog": prog}
    with np.errstate(divide="ignore", invalid="ignore"):
        terms = gl * gl / hl + gr * gr / hr     # NaN where a side is empty
        own = terms - G * G / H     # the program's cut, on float64 sums
        gain_err = (abs(float(tree["split_gain"][node]) - own) / terms
                    * H / H_root)
    sc = _scan(hist, tb["num_bins"], tb["missing_nan"], G, H, n, params,
               hess_rtol * H_root)
    rtol = tie_rtol * H_root / H
    best = _best(sc, H, n, rtol)
    sp = best["split"]
    mh = float(params["min_sum_hessian_in_leaf"])
    hess_tol = hess_rtol * H_root
    same = tied = False
    if sp is not None:
        f, nb = sp["feature"], tb["num_bins"][sp["feature"]]
        col = tb["bins"][f][rows]
        rleft = np.where(tb["missing_nan"][f] & (col == nb - 1),
                         sp["default_left"], col <= sp["threshold"])
        same = bool(np.array_equal(left, rleft)
                    or np.array_equal(left, ~rleft))
        tol = rtol * (G * G / H + sp["gain"])
        # another cut within rounding of the best one, or one at the
        # hessian bound that rounding let in
        tied = (not same and own >= sp["gain"] - tol
                and (own <= sp["gain"] + tol
                     or abs(min(hl, hr) - mh) <= hess_tol))
    else:
        tied = best["fragile"]
    return {"node": int(node), "rows": n,
            "count_ok": n == int(tree["internal_count"][node]),
            "same": same, "tied": bool(tied),
            "bound_ok": bool(min(hl, hr) >= mh - hess_tol),
            "gain_err": float(gain_err),
            "ref": None if sp is None else (sp["feature"], sp["threshold"],
                                            sp["default_left"]),
            "prog": prog}


def judge(tree: dict, tb: dict, g, h, params: dict, nodes: int = None,
          shrinkage: float = 1.0, tie_rtol: float = 1e-6,
          hess_rtol: float = 1e-5, gain_rtol: float = 1e-4,
          leaf_rtol: float = 1e-4, tie_share_max: float = 0.5) -> dict:
    """A program's tree (``parse_model_string``'s) held to the rules of
    ``grow_tree`` on every row it was grown from, without growing one: the
    table too large for ``grow_tree``'s ``[N, F]`` copy.  ``tb`` is
    ``bin_table``'s, ``g`` and ``h`` the gradients of the scores the tree
    was grown from.  The tree is walked over the bins (``goes_left`` of each
    bin's value), and its first ``nodes`` nodes breadth-first (every node
    where None) are judged on the rows the walk brings them: (1) as many as
    the node's ``internal_count``; (2) its cut is the best cut of those rows
    (as a cut of the rows: the mirror of a NaN-against-the-rest split is
    the same) or, counted as a tie, within rounding of it by ``_best``'s
    tolerance (``tie_rtol`` of the gain terms times the root's hessian over
    the node's), or a cut at the hessian bound (within ``hess_rtol`` of the
    root's hessian) that rounding let in; no side below the bound by more;
    (3) its exported ``split_gain`` is the gain of its own cut on float64
    sums, to ``gain_rtol`` of the two terms ``G_l^2 / H_l + G_r^2 / H_r``
    the gain is the difference of, times the node's hessian over the
    root's (a node's sums are its parent's less its sibling's, so their
    error goes by the root's, as ``grow_tree``'s ``tie_rtol``).  Every
    leaf: as many rows as its ``leaf_count``, and its value
    ``-shrinkage G / H`` of those rows to ``leaf_rtol`` of the largest,
    times the leaf's hessian over the root's, for the same reason.  Histograms: the root's in one pass, then
    of two children the smaller's, the larger the parent's less it."""
    g, h = np.asarray(g, np.float64), np.asarray(h, np.float64)
    N = tb["bins"].shape[1]
    B = int(tb["num_bins"].max())
    H_root = float(h.sum())
    lc, rc = tree["left_child"], tree["right_child"]
    order, queue = [], [0] if tree["num_leaves"] > 1 else []
    while queue:
        n = queue.pop(0)
        order.append(n)
        queue += [int(c) for c in (lc[n], rc[n]) if c >= 0]
    judged = set(order[:nodes] if nodes is not None else order)
    rows_of, hist_of, leaf_rows, out = {0: np.arange(N)}, {}, {}, []
    for n in order:
        rows = rows_of.pop(n)
        f, lut = _bin_goes_left(tb, tree, n)
        full = len(rows) == N
        left = lut[tb["bins"][f] if full else tb["bins"][f][rows]]
        kids = ((int(lc[n]), rows[left]), (int(rc[n]), rows[~left]))
        if n in judged:
            hist = hist_of.pop(n, None)
            if hist is None:
                hist = _node_hist(tb, g, h, None if full else rows, B)
            out.append(_judge_node(tree, n, hist, rows, left, tb, g, h,
                                   params, H_root, tie_rtol, hess_rtol))
            if any(c in judged for c, _ in kids):
                (cs, rs), (cl, _) = sorted(kids, key=lambda k: len(k[1]))
                hs = _node_hist(tb, g, h, rs, B)
                hist_of.update({c: v for c, v in ((cs, hs), (cl, hist - hs))
                                if c in judged})
        for c, r in kids:
            if c >= 0:
                rows_of[c] = r
            else:
                leaf_rows[~c] = r
    L = int(tree["num_leaves"])
    if L == 1:
        leaf_rows[0] = np.arange(N)
    leaf_h = np.asarray([h[leaf_rows[j]].sum() for j in range(L)])
    with np.errstate(divide="ignore", invalid="ignore"):  # a leaf no row
        ref = np.asarray([-shrinkage * g[leaf_rows[j]].sum()   # reaches
                          for j in range(L)]) / leaf_h
        leaf_err = (np.abs(tree["leaf_value"][:L] - ref)
                    / float(np.abs(ref).max()) * leaf_h / H_root)
    counts_ok = all(len(leaf_rows[j]) == int(tree["leaf_count"][j])
                    for j in range(L))
    ties = sum(nd["tied"] for nd in out)
    gain_err = float(np.max([nd["gain_err"] for nd in out] or [0.0]))
    bad = [nd for nd in out if not (nd["count_ok"] and nd["bound_ok"]
                                    and (nd["same"] or nd["tied"]))]
    return {"nodes": len(out), "of": len(order),
            "same": sum(nd["same"] for nd in out), "tied": ties,
            "differ": bad[:5],
            "gain_err": gain_err, "gain_rtol": gain_rtol,
            "leaves": L, "leaf_err": float(leaf_err.max()),
            "leaf_rtol": leaf_rtol, "leaf_counts_ok": counts_ok,
            "root": out[0]["ref"] if out else None,
            "ok": bool(out and not bad and ties <= tie_share_max * len(out)
                       and gain_err <= gain_rtol
                       and float(leaf_err.max()) <= leaf_rtol
                       and counts_ok)}
