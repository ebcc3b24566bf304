"""The plain reference for declared categorical columns: NumPy, float64, plain
loops, nothing of the program imported.

Why there are two walks.  ``reference.py`` walks numerical splits only and
refuses a model text that holds a categorical node (``num_cat != 0``); the
kinds ``boost``, ``boost_csr`` and ``boost_goss`` use it, on tables whose
every column is numeric, and a categorical node in one of their models is a
fault it is right to stop at.  This file walks both kinds of node and is what
``kinds/boost_cat.py`` uses.  The two are not merged here because no file the
benchmark has may be edited outside a ``benchmark`` PR (PERF.md 7 asks for
that PR: one kind with hooks; one walk would go with it).

Three parts:

(i) ``walk``: the model text of ``model_to_string()`` over raw feature values.
    A numerical node as ``reference.py`` has it, with the format's missing
    handling written out (``decision_type`` bits 2-3: none / zero / NaN; bit 1
    the default direction).  A categorical node is ``decision_type`` bit 0:
    ``threshold`` is the node's index into ``cat_boundaries``, whose slice of
    ``cat_threshold`` is a bitset of ``uint32`` words over category VALUES;
    the row goes left where ``int(value)`` is in the set, and a value outside
    it, negative or NaN goes right (include/LightGBM/tree.h:262-303).

(ii) ``fisher_best``: the reference's search of one node and one categorical
    column (feature_histogram.hpp:118-279, v2.3.2; docs/Features.rst,
    "Optimal Split for Categorical Features": Fisher 1958) from per-category
    ``(sum_g, sum_h, count)``: with at most ``max_cat_to_onehot`` bins each
    category against the rest; else the categories with ``count >=
    cat_smooth`` sorted by ``sum_g / (sum_h + cat_smooth)`` and scanned from
    both ends, at most ``min(max_cat_threshold, (used + 1) // 2)`` of them,
    under ``lambda_l2 + cat_l2``, with the ``min_data_in_leaf`` /
    ``min_sum_hessian_in_leaf`` continues on the left, the breaks on the
    right, and the ``min_data_per_group`` counter that lets a prefix be a
    candidate only once the categories added since the last candidate hold
    that many rows.

(iii) ``judge``: given scores, labels, raw values and one exported tree: the
    binary gradients in float64, the rows of each judged categorical node by
    (i)'s walk, that node's per-category sums over them, (ii) on that column,
    and the verdict.

(iv) ``bin_map_problems``: whether a column's bin map, which (iii) pools
    categories by, is a count-ordered map of the bin-finding sample as the
    reference makes one (bin.cpp:424-497): categories by falling count in
    the sample, taken while they cover under 99% of it or are fewer than
    ``max_bin``.  ``judge`` takes the map from the program; this holds the
    map itself to the sample, so a wrong one does not pass unseen.

Departures from the published description, each by intent:

- The sort is NumPy's stable one; the reference's ``std::sort`` leaves the
  order of equal ratios open.  Where two orders give another set the gains
  tie, which is what ``judge``'s second test is for.
- Categories are indexed by the program's BIN (``Dataset.categorical_bins()``
  says in public which value each bin holds and whether every value was
  kept), not by value: the reference searches bins too, the first strict
  maximum wins, and the order decides exact ties.  Values the bin map
  dropped (rarer than the 99% / ``max_bin`` cut of the bin-finding sample),
  negative and missing ones are pooled into the LAST bin, as the program
  pools them (bin.h ValueToBin), and where anything was dropped or missing
  that bin is no candidate (``used_bin = num_bin - 1``): its rows go right.
- ``judge`` holds a node whose set EQUALS the reference's to the gain
  tolerance as well (the issue asked for one or the other).  The exported
  ``split_gain`` is the program's own arithmetic on its own sums and moves
  with every error in them, while the set moves only where two candidates
  nearly tie: a coarser histogram pass shows in the gain first.
- ``bin_map_problems`` holds the order of a map by COUNTS, not by values:
  categories of one count may stand in any order among themselves (the
  reference leaves it to ``std::stable_sort`` on the counts; which of two
  such categories keeps the last bin before the cut is then the sort's).
- The node's hessian total is taken as it is; the reference adds ``2 *
  kEpsilon`` (1e-15) before the search, which float64 sums of thousands of
  rows do not show.
"""
from __future__ import annotations

import itertools

import numpy as np

K_EPSILON = 1e-15
K_ZERO = 1e-35
MIN_SCORE = -np.inf

# docs/Parameters.rst, v2.3.2: what a configuration that does not say
# otherwise runs with
DEFAULTS = {"lambda_l1": 0.0, "lambda_l2": 0.0, "max_delta_step": 0.0,
            "min_gain_to_split": 0.0, "min_data_in_leaf": 20,
            "min_sum_hessian_in_leaf": 1e-3, "max_cat_threshold": 32,
            "cat_l2": 10.0, "cat_smooth": 10.0, "max_cat_to_onehot": 4,
            "min_data_per_group": 100}


# ---------------------------------------------------------------------------
# (i) the walk
# ---------------------------------------------------------------------------

def parse_model_string(text: str) -> list:
    """Trees of a model text as dicts of arrays.  A child ``c >= 0`` is a
    node, ``c < 0`` the leaf ``~c``."""
    trees = []
    for chunk in text.split("\nTree=")[1:]:
        kv = {}
        for line in chunk.split("end of trees")[0].splitlines():
            k, sep, v = line.partition("=")
            if sep:
                kv[k.strip()] = v.strip()

        def arr(key, dtype):
            return np.asarray(kv.get(key, "").split(), dtype=dtype)
        trees.append({
            "num_leaves": int(kv["num_leaves"]),
            "num_cat": int(kv.get("num_cat", "0")),
            "split_feature": arr("split_feature", np.int64),
            "split_gain": arr("split_gain", np.float64),
            "threshold": arr("threshold", np.float64),
            "decision_type": arr("decision_type", np.int64),
            "left_child": arr("left_child", np.int64),
            "right_child": arr("right_child", np.int64),
            "leaf_value": arr("leaf_value", np.float64),
            "internal_count": arr("internal_count", np.int64),
            "cat_boundaries": arr("cat_boundaries", np.int64),
            "cat_threshold": arr("cat_threshold", np.uint64)})
    return trees


def node_set(tree: dict, node: int) -> set:
    """The category values that go left at a categorical node."""
    idx = int(tree["threshold"][node])
    lo, hi = tree["cat_boundaries"][idx], tree["cat_boundaries"][idx + 1]
    out = set()
    for w, word in enumerate(tree["cat_threshold"][lo:hi]):
        for b in range(32):
            if (int(word) >> b) & 1:
                out.add(32 * w + b)
    return out


def root_split(tree: dict):
    """(feature, threshold) of a tree's first split, (feature, sorted
    category values) where it is categorical, None for a stump."""
    if tree["num_leaves"] <= 1:
        return None
    f = int(tree["split_feature"][0])
    if tree["decision_type"][0] & 1:
        return f, tuple(sorted(node_set(tree, 0)))
    return f, float(tree["threshold"][0])


def go_left(tree: dict, nodes: np.ndarray, x: np.ndarray) -> np.ndarray:
    """The decision of node ``nodes[i]`` on value ``x[i]`` (float64)."""
    dt = tree["decision_type"][nodes]
    thr = tree["threshold"][nodes]
    missing = (dt >> 2) & 3             # 0 none, 1 zero, 2 NaN
    default_left = (dt & 2) != 0
    nan = np.isnan(x)
    v = np.where(nan & (missing != 2), 0.0, x)
    is_missing = (((missing == 1) & (np.abs(v) <= K_ZERO))
                  | ((missing == 2) & np.isnan(v)))
    with np.errstate(invalid="ignore"):
        out = np.where(is_missing, default_left, v <= thr)
    cat = (dt & 1) != 0
    if cat.any():
        ci = np.flatnonzero(cat)
        xv = x[ci]
        ok = ~np.isnan(xv) & (xv >= 0)
        iv = np.where(ok, xv, 0).astype(np.int64)
        cidx = thr[ci].astype(np.int64)
        lo = tree["cat_boundaries"][cidx]
        hi = tree["cat_boundaries"][cidx + 1]
        word = iv // 32
        ok &= word < hi - lo
        words = tree["cat_threshold"][np.where(ok, lo + word, 0)]
        bit = (words >> (iv % 32).astype(np.uint64)) & np.uint64(1)
        out[ci] = ok & (bit != 0)
    return out


def walk_rows(tree: dict, X: np.ndarray, nodes=(), leaves: bool = True):
    """``(leaf [N], {node: rows that pass through it})`` of one tree over raw
    values, level by level; with ``leaves`` false it stops once every node
    of ``nodes`` was met, and the first part is None."""
    n = X.shape[0]
    through = {}
    if tree["num_leaves"] <= 1:
        return np.zeros(n, np.int64), through
    want = set(int(k) for k in nodes)
    cur = np.zeros(n, np.int64)
    rows = np.arange(n)
    while rows.size and (leaves or want):
        nd = cur[rows]
        if want:
            here = np.bincount(nd, minlength=tree["num_leaves"])
            for k in [k for k in want if here[k]]:
                through[k] = rows[nd == k]
                want.discard(k)
        x = X[rows, tree["split_feature"][nd]].astype(np.float64)
        left = go_left(tree, nd, x)
        nxt = np.where(left, tree["left_child"][nd], tree["right_child"][nd])
        cur[rows] = nxt
        rows = rows[nxt >= 0]
    return (~cur if leaves else None), through


def walk(trees: list, X: np.ndarray) -> np.ndarray:
    """Sum of the trees' leaf values over the rows of ``X``, float64."""
    out = np.zeros(X.shape[0], np.float64)
    for tree in trees:
        out += tree["leaf_value"][walk_rows(tree, X)[0]]
    return out


def categorical_nodes(tree: dict, limit: int = None) -> list:
    """The tree's categorical internal nodes in breadth-first order."""
    if tree["num_leaves"] <= 1:
        return []
    out, level = [], [0]
    while level:
        nxt = []
        for k in level:
            if tree["decision_type"][k] & 1:
                out.append(k)
            for c in (tree["left_child"][k], tree["right_child"][k]):
                if c >= 0:
                    nxt.append(int(c))
        level = nxt
    return out[:limit]


# ---------------------------------------------------------------------------
# (ii) the search
# ---------------------------------------------------------------------------

def _threshold_l1(s: float, l1: float) -> float:
    return float(np.sign(s) * max(abs(s) - l1, 0.0)) if l1 > 0 else s


def _output(g: float, h: float, p: dict, l2: float) -> float:
    out = -_threshold_l1(g, p["lambda_l1"]) / (h + l2)
    if p["max_delta_step"] > 0:
        out = min(max(out, -p["max_delta_step"]), p["max_delta_step"])
    return out


def _gain_given_output(g: float, h: float, out: float, p: dict,
                       l2: float) -> float:
    return -(2.0 * _threshold_l1(g, p["lambda_l1"]) * out
             + (h + l2) * out * out)


def _leaf_gain(g: float, h: float, p: dict, l2: float) -> float:
    return _gain_given_output(g, h, _output(g, h, p, l2), p, l2)


def _split_gain(gl, hl, gr, hr, p: dict, l2: float) -> float:
    return _leaf_gain(gl, hl, p, l2) + _leaf_gain(gr, hr, p, l2)


def _sorted_bins(g, h, c, p: dict, used_bin: int) -> tuple:
    """``(bins, ratios)`` of the sorted scan: the candidate bins with
    ``count >= cat_smooth`` by ascending ``sum_g / (sum_h + cat_smooth)``."""
    kept = [i for i in range(used_bin) if c[i] >= p["cat_smooth"]]
    ratio = np.asarray([g[i] / (h[i] + p["cat_smooth"]) for i in kept])
    by = np.argsort(ratio, kind="stable")
    return [kept[i] for i in by], ratio[by]


def fisher_best(sum_g, sum_h, count, params: dict = None,
                all_kept: bool = True, order=None):
    """``(left bins, gain)`` of the best categorical split of one node on one
    column, or ``(None, -inf)`` where no candidate passes.  ``sum_g``,
    ``sum_h``, ``count``: the node's sums a bin of the column (bin ``b`` one
    category; the last bin also what the bin map pooled, see the module
    text); the node's totals are their sums.  ``gain`` is what a model text
    prints as ``split_gain``: the best candidate's gain less the unsplit
    node's and ``min_gain_to_split``.  ``order`` replaces the sorted order of
    the scanned bins by a given one (``tied_orders``)."""
    p = {**DEFAULTS, **(params or {})}
    g = np.asarray(sum_g, np.float64)
    h = np.asarray(sum_h, np.float64)
    c = np.asarray(count, np.float64)
    G, H, N = float(g.sum()), float(h.sum()), float(c.sum())
    l2 = p["lambda_l2"]
    min_gain_shift = _leaf_gain(G, H, p, l2) + p["min_gain_to_split"]
    num_bin = len(g)
    used_bin = num_bin - 1 + (1 if all_kept else 0)
    best_gain, best_set = MIN_SCORE, None

    if num_bin <= p["max_cat_to_onehot"]:
        for t in range(used_bin):
            if (c[t] < p["min_data_in_leaf"]
                    or h[t] < p["min_sum_hessian_in_leaf"]):
                continue
            if N - c[t] < p["min_data_in_leaf"]:
                continue
            other_h = H - h[t] - K_EPSILON
            if other_h < p["min_sum_hessian_in_leaf"]:
                continue
            gain = _split_gain(G - g[t], other_h, g[t], h[t] + K_EPSILON,
                               p, l2)
            if gain <= min_gain_shift:
                continue
            if gain > best_gain:
                best_gain, best_set = gain, (t,)
    else:
        base, _ = _sorted_bins(g, h, c, p, used_bin)
        order = list(order) if order is not None else base
        assert sorted(order) == sorted(base)
        used = len(order)
        l2 += p["cat_l2"]
        max_num_cat = min(int(p["max_cat_threshold"]), (used + 1) // 2)
        for direction, start in ((1, 0), (-1, used - 1)):
            pos, group = start, 0.0
            lg, lh, lc = 0.0, K_EPSILON, 0.0
            for i in range(min(used, max_num_cat)):
                t = order[pos]
                pos += direction
                lg += g[t]
                lh += h[t]
                lc += c[t]
                group += c[t]
                if (lc < p["min_data_in_leaf"]
                        or lh < p["min_sum_hessian_in_leaf"]):
                    continue
                rc = N - lc
                if rc < p["min_data_in_leaf"] or rc < p["min_data_per_group"]:
                    break
                rh = H - lh
                if rh < p["min_sum_hessian_in_leaf"]:
                    break
                if group < p["min_data_per_group"]:
                    continue
                group = 0.0
                gain = _split_gain(lg, lh, G - lg, rh, p, l2)
                if gain <= min_gain_shift:
                    continue
                if gain > best_gain:
                    best_gain = gain
                    best_set = tuple(order[:i + 1] if direction == 1
                                     else order[used - 1 - i:])
    if best_set is None:
        return None, MIN_SCORE
    return tuple(sorted(best_set)), best_gain - min_gain_shift


def tied_orders(sum_g, sum_h, count, params: dict = None,
                all_kept: bool = True, about=(), eps: float = 1e-4,
                cap: int = 64):
    """The other orders the sorted scan may legitimately have had: the
    reference's ``std::sort`` leaves equal ratios in any order, and a sort on
    sums rounded another way orders ratios that agree to ``eps`` (of the
    largest ratio's size) either way.  Yields every order (at most ``cap``)
    that permutes, within themselves, the runs of such ratios that hold a
    bin of ``about``; the stable order itself is not among them."""
    p = {**DEFAULTS, **(params or {})}
    g = np.asarray(sum_g, np.float64)
    h = np.asarray(sum_h, np.float64)
    c = np.asarray(count, np.float64)
    base, r = _sorted_bins(g, h, c, p, len(g) - 1 + (1 if all_kept else 0))
    if len(g) <= p["max_cat_to_onehot"] or len(base) < 2:
        return
    tol = eps * float(np.max(np.abs(r)))
    runs, start = [], 0
    for i in range(1, len(base) + 1):
        if i == len(base) or r[i] - r[i - 1] > tol:
            if i - start > 1 and set(base[start:i]) & set(about):
                runs.append((start, i))
            start = i
    runs = [(a, b) for a, b in runs if b - a <= 4][:3]
    choices = [list(itertools.permutations(base[a:b])) for a, b in runs]
    for n, combo in enumerate(itertools.product(*choices)):
        if n >= cap:
            return
        order = list(base)
        for (a, b), perm in zip(runs, combo):
            order[a:b] = perm
        if order != base:
            yield order


def unsplit_gain(sum_g, sum_h, params: dict = None) -> float:
    """What every candidate's gain is held against: the node's own gain
    unsplit plus ``min_gain_to_split`` (the reference's
    ``min_gain_shift``)."""
    p = {**DEFAULTS, **(params or {})}
    return (_leaf_gain(float(np.sum(sum_g)), float(np.sum(sum_h)), p,
                       p["lambda_l2"]) + p["min_gain_to_split"])


def set_gain(sum_g, sum_h, count, left_bins, params: dict = None) -> float:
    """``split_gain`` of sending ``left_bins`` left, whatever the search
    thinks of it (no guard is applied)."""
    p = {**DEFAULTS, **(params or {})}
    g = np.asarray(sum_g, np.float64)
    h = np.asarray(sum_h, np.float64)
    G, H = float(g.sum()), float(h.sum())
    idx = list(left_bins)
    onehot = len(g) <= p["max_cat_to_onehot"]
    l2 = p["lambda_l2"] + (0.0 if onehot else p["cat_l2"])
    lg, lh = float(g[idx].sum()), float(h[idx].sum()) + K_EPSILON
    return (_split_gain(lg, lh, G - lg, H - lh, p, l2)
            - unsplit_gain(g, h, p))


# ---------------------------------------------------------------------------
# (iii) the judge
# ---------------------------------------------------------------------------

def binary_gradients(score: np.ndarray, y: np.ndarray) -> tuple:
    """``(g, h)`` of the binary log loss under sigmoid 1, unweighted
    (src/objective/binary_objective.hpp:105-135), float64."""
    s = np.asarray(score, np.float64)
    p = 0.5 * (1.0 + np.tanh(0.5 * s))          # a stable sigmoid
    return p - (np.asarray(y) > 0), p * (1.0 - p)


def bins_of(values: np.ndarray, bin_values: list) -> np.ndarray:
    """The bin of each raw value under a column's bin map (``bin_values[b]``
    the category of bin ``b``): what is not listed, negative or NaN shares
    the last bin."""
    last = len(bin_values) - 1
    v = np.asarray(values, np.float64)
    ok = ~np.isnan(v) & (v >= 0)
    iv = np.where(ok, v, 0).astype(np.int64)
    top = max(max(bin_values), 0)
    lut = np.full(top + 2, last, np.int64)
    for b, cat in enumerate(bin_values):
        if cat >= 0:
            lut[cat] = b
    return np.where(ok, lut[np.minimum(iv, top + 1)], last)


def judge(tree: dict, X: np.ndarray, y: np.ndarray, score: np.ndarray,
          bin_maps: dict, params: dict = None, nodes: int = 8,
          gain_rtol: float = 1e-3, tie_share_max: float = 0.5,
          gain_med_rtol: float = None, grads: tuple = None,
          med_columns=None, med_nodes_min: int = 1) -> dict:
    """Whether ``tree``'s first ``nodes`` categorical nodes (breadth-first)
    are what the reference's search finds on the gradients of ``score``.

    ``X`` raw values, ``y`` labels, ``score`` the raw scores the tree was
    grown from; ``bin_maps`` what ``Dataset.categorical_bins()`` says:
    ``{column: {"values": [...], "all_kept": bool}}``; ``params`` the
    trainer's parameters (the ``DEFAULTS`` keys are read).  A node passes
    where (1) the rows the walk brings to it are as many as its
    ``internal_count``; (2) the exported gain agrees with the gain of the
    exported set on the reference's sums (the program's arithmetic; where
    the set is the reference's that is the reference's gain) to
    ``gain_rtol`` of the best gain plus the unsplit node's, the two sizes a
    ``split_gain`` is the difference of; (3) the exported left set is the reference's (or its mirror
    image in a column whose every value has a bin: the scan reaches that
    partition from either end at one gain) or, counted as a tie, another
    set whose gain is the best one's to ``gain_rtol`` or which the search
    finds under another order of ratios that agree to ``gain_rtol``
    (``tied_orders``; ``tie_by_order``).  ``ok`` needs every judged node to
    pass, at least one judged, at most ``tie_share_max`` of them ties and,
    where ``gain_med_rtol`` is given, the median of (2)'s error within it:
    a coarser histogram pass moves every node's gain and the median with
    them, where one node's cancellation moves the largest alone (the
    argument of PERF.md 2 for ``score_med``).  The median is over the judged
    nodes that split a column of ``med_columns`` (all, if None), and there
    have to be ``med_nodes_min`` of them: where some columns' histograms
    are made in another precision than the rest's (a float32 side-pass
    beside the kernel), their nodes carry only what comes down from their
    ancestors' totals, and a median they outnumber would see less."""
    g, h = grads if grads is not None else binary_gradients(score, y)
    judged = categorical_nodes(tree, nodes)
    _, through = walk_rows(tree, X, judged, leaves=False)
    out = []
    for k in judged:
        rows = through.get(k, np.zeros(0, np.int64))
        col = int(tree["split_feature"][k])
        bm = bin_maps[col]
        nb = len(bm["values"])
        b = bins_of(X[rows, col], bm["values"])
        sg = np.bincount(b, weights=g[rows], minlength=nb)
        sh = np.bincount(b, weights=h[rows], minlength=nb)
        sc = np.bincount(b, minlength=nb).astype(np.float64)
        ref_bins, ref_gain = fisher_best(sg, sh, sc, params, bm["all_kept"])
        ref_vals = (set() if ref_bins is None else
                    {bm["values"][i] for i in ref_bins
                     if bm["values"][i] >= 0})
        got_vals = node_set(tree, k)
        got_gain = float(tree["split_gain"][k])
        listed = {v for v in bm["values"] if v >= 0}
        mirror = (bm["all_kept"] and bool(ref_vals)
                  and got_vals == listed - ref_vals)
        same = got_vals == ref_vals or mirror
        # errors are taken against what the gain was subtracted from: a
        # ``split_gain`` is the candidate's gain less the unsplit node's,
        # and both the program's rounding and a coarser pass's go by the
        # size of those two, not of their difference (a day-of-week split of
        # gain 2,280 under a parent's 1e5 read ten times a carrier split's
        # error against the gain alone: chip runs, PERF.md 2, PR 34)
        scale = abs(ref_gain) + abs(unsplit_gain(sg, sh, params)) + 1e-300
        err = tie_err = float("inf")
        by_order = False
        if ref_bins is not None and got_vals <= listed:
            got_bins = [bm["values"].index(v) for v in got_vals]
            # the program's arithmetic: its gain against its own set's
            # gain on the reference's sums
            own = set_gain(sg, sh, sc, got_bins, params)
            err = abs(got_gain - own) / scale
            # its choice: the reference's set, or a tie with it, by gain or
            # by the order of ratios that agree to the tolerance
            tie_err = 0.0 if same else abs(own - ref_gain) / scale
            if tie_err > gain_rtol:
                want = tuple(sorted(got_bins))
                differ = set(want) ^ set(ref_bins)
                by_order = any(
                    fisher_best(sg, sh, sc, params, bm["all_kept"],
                                order=o)[0] == want
                    for o in tied_orders(sg, sh, sc, params, bm["all_kept"],
                                         about=differ, eps=gain_rtol))
        rec = {"node": int(k), "column": col, "rows": int(len(rows)),
               "rows_exported": int(tree["internal_count"][k]),
               "same_set": bool(same), "mirror": bool(mirror),
               "set_size": len(got_vals), "ref_set_size": len(ref_vals),
               "gain": got_gain, "ref_gain": float(ref_gain),
               "gain_err": float(err), "tie_err": float(tie_err),
               "tie_by_order": bool(by_order),
               "pass": bool(err <= gain_rtol
                            and (tie_err <= gain_rtol or by_order)
                            and len(rows) == tree["internal_count"][k])}
        if not same:
            rec["differ"] = sorted(got_vals ^ ref_vals)[:8]
        out.append(rec)
    ties = sum(1 for r in out if r["pass"] and not r["same_set"])
    of = [r["gain_err"] for r in out
          if med_columns is None or r["column"] in med_columns]
    med = float(np.median(of)) if of else float("inf")
    return {"judged": len(out), "ties": ties,
            "tie_share": ties / len(out) if out else 0.0,
            "gain_err_max": max((r["gain_err"] for r in out), default=0.0),
            "gain_err_med": med, "med_nodes": len(of),
            "med_nodes_min": med_nodes_min, "gain_rtol": gain_rtol,
            "gain_med_rtol": gain_med_rtol, "tie_share_max": tie_share_max,
            "nodes": out,
            "ok": bool(out and all(r["pass"] for r in out)
                       and ties <= tie_share_max * len(out)
                       and (gain_med_rtol is None
                            or (len(of) >= med_nodes_min
                                and med <= gain_med_rtol)))}


# ---------------------------------------------------------------------------
# (iv) the bin map
# ---------------------------------------------------------------------------

def bin_map_problems(sample: np.ndarray, bin_values: list, all_kept: bool,
                     max_bin: int = 255, min_data_in_bin: int = 3) -> list:
    """Why ``bin_values`` (``bin_values[b]`` the category of bin ``b``, -1
    the pseudo-category of missing values) with ``all_kept`` is not the
    count-ordered map of ``sample``, one column of the rows the bins were
    found from; empty where it is (bin.cpp:424-497, v2.3.2).

    NaN and negative values count as missing; a value is its integer part.
    The categories go by falling count; the most frequent one gives bin 0 to
    the second where it is category 0; categories are taken while those
    taken cover under 99% of the present values or are fewer than
    ``min(categories, max_bin)``, and none past the second with under
    ``min_data_in_bin`` rows; -1 closes the map where every category was
    taken and something was missing.  ``all_kept``: every category taken and
    nothing missing."""
    v = np.asarray(sample, np.float64)
    present = v[~np.isnan(v)].astype(np.int64)
    present = present[present >= 0]
    missing = len(v) - len(present)
    cats, cnt = np.unique(present, return_counts=True)
    count_of = dict(zip(cats.tolist(), cnt.tolist()))
    falling = sorted(cnt.tolist(), reverse=True)
    listed = [c for c in bin_values if c >= 0]
    out = []
    if len(set(listed)) != len(listed) or any(c not in count_of
                                              for c in listed):
        return [f"lists a category twice or one the sample lacks: {listed}"]
    if listed[:1] == [0] and len(falling) > 1:
        out.append("bin 0 holds category 0")
    elif listed[1:2] == [0] and count_of[0] == falling[0]:
        falling[:2] = falling[1::-1]     # the most frequent one gave way
    cut = int(len(present) * 0.99)
    most = min(len(falling), max_bin)
    taken = covered = 0
    while taken < len(falling) and (covered < cut or taken < most):
        if falling[taken] < min_data_in_bin and taken > 1:
            break
        covered += falling[taken]
        taken += 1
    got = [count_of[c] for c in listed]
    if got != falling[:taken]:
        k = next((i for i, (a, b) in enumerate(zip(got, falling)) if a != b),
                 min(len(got), taken))
        out.append(f"{len(got)} categories listed, {taken} to take; counts "
                   f"part at bin {k}: listed {got[k:k + 3]}, by falling "
                   f"count {falling[k:min(k + 3, taken)]}")
    closes = taken == len(falling) and missing > 0
    if (bin_values[-1:] == [-1]) != closes or -1 in bin_values[:-1]:
        out.append(f"-1 {'has' if closes else 'has not'} to close the map")
    if bool(all_kept) != (taken == len(falling) and missing == 0):
        out.append(f"all_kept {all_kept} with {taken} of {len(falling)} "
                   f"categories taken and {missing} values missing")
    return out
