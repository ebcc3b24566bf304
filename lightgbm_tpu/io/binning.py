"""Feature binning: value → bin mapping.

TPU-native rebuild of the reference's ``BinMapper``
(reference: include/LightGBM/bin.h:65-222, src/io/bin.cpp:78-529). The
*algorithm* is the same — greedy near-equal-count bin boundaries over a value
sample, with zero isolated in its own bin, the three missing modes
{None, Zero, NaN}, and count-ordered categorical mapping — but the
implementation is host-side NumPy producing a dense unsigned-int binned
matrix for the device, instead of per-feature-group ``Bin`` objects.

All bin construction happens once on the host; the device only ever sees the
binned matrix and the per-feature bound arrays needed to binarize prediction
inputs.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..utils import log
from .. import native as _native

# Values in (-kZeroThreshold, kZeroThreshold] are "zero"
# (reference: include/LightGBM/meta.h:53).
K_ZERO_THRESHOLD = 1e-35

MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2

BIN_NUMERICAL = 0
BIN_CATEGORICAL = 1

_MISSING_NAMES = {MISSING_NONE: "None", MISSING_ZERO: "Zero", MISSING_NAN: "NaN"}


def _upper_bound(v: float) -> float:
    """Smallest double strictly greater than v (reference: Common::GetDoubleUpperBound)."""
    return float(np.nextafter(v, np.inf))


def _close_ordered(a: float, b: float) -> bool:
    """b <= nextafter(a, inf) (reference: Common::CheckDoubleEqualOrdered)."""
    return b <= np.nextafter(a, np.inf)


def greedy_find_bin(distinct_values: np.ndarray, counts: np.ndarray, max_bin: int,
                    total_cnt: int, min_data_in_bin: int) -> List[float]:
    """Greedy near-equal-count bin upper bounds over sorted distinct values.

    Values with count >= mean bin size get dedicated bins; the rest are packed
    to roughly equal counts (reference: GreedyFindBin, bin.cpp:78-155).
    Returns ascending upper bounds; the last is +inf.
    """
    if _native.lib() is not None:
        return _native.greedy_find_bin(
            np.asarray(distinct_values, np.float64),
            np.asarray(counts, np.int64), max_bin, total_cnt, min_data_in_bin)
    n = len(distinct_values)
    if n == 0:
        return [math.inf]
    bounds: List[float] = []
    if n <= max_bin:
        cur = 0
        for i in range(n - 1):
            cur += int(counts[i])
            if cur >= min_data_in_bin:
                val = _upper_bound((distinct_values[i] + distinct_values[i + 1]) / 2.0)
                if not bounds or not _close_ordered(bounds[-1], val):
                    bounds.append(val)
                    cur = 0
        bounds.append(math.inf)
        return bounds

    if min_data_in_bin > 0:
        max_bin = max(1, min(max_bin, total_cnt // min_data_in_bin))
    mean_size = total_cnt / max_bin
    is_big = counts >= mean_size
    rest_bins = max_bin - int(is_big.sum())
    rest_cnt = total_cnt - int(counts[is_big].sum())
    mean_size = rest_cnt / rest_bins if rest_bins > 0 else math.inf

    uppers: List[float] = []
    lowers: List[float] = [float(distinct_values[0])]
    cur = 0
    for i in range(n - 1):
        if not is_big[i]:
            rest_cnt -= int(counts[i])
        cur += int(counts[i])
        if (is_big[i] or cur >= mean_size
                or (is_big[i + 1] and cur >= max(1.0, mean_size * 0.5))):
            uppers.append(float(distinct_values[i]))
            lowers.append(float(distinct_values[i + 1]))
            if len(uppers) >= max_bin - 1:
                break
            cur = 0
            if not is_big[i]:
                rest_bins -= 1
                mean_size = rest_cnt / rest_bins if rest_bins > 0 else math.inf
    for i in range(len(uppers)):
        val = _upper_bound((uppers[i] + lowers[i + 1]) / 2.0)
        if not bounds or not _close_ordered(bounds[-1], val):
            bounds.append(val)
    bounds.append(math.inf)
    return bounds


def find_bin_with_zero_as_one_bin(distinct_values: np.ndarray, counts: np.ndarray,
                                  max_bin: int, total_cnt: int,
                                  min_data_in_bin: int) -> List[float]:
    """Bin bounds with zero guaranteed its own bin: negative side and positive
    side are binned independently around (-eps, eps]
    (reference: FindBinWithZeroAsOneBin, bin.cpp:256-312)."""
    neg = distinct_values <= -K_ZERO_THRESHOLD
    pos = distinct_values > K_ZERO_THRESHOLD
    zero_cnt = int(counts[~neg & ~pos].sum())
    left_cnt_data = int(counts[neg].sum())
    right_cnt_data = int(counts[pos].sum())
    n_left = int(neg.sum())

    bounds: List[float] = []
    if n_left > 0 and max_bin > 1:
        denom = max(total_cnt - zero_cnt, 1)
        left_max_bin = max(1, int(left_cnt_data / denom * (max_bin - 1)))
        bounds = greedy_find_bin(distinct_values[:n_left], counts[:n_left],
                                 left_max_bin, left_cnt_data, min_data_in_bin)
        if bounds:
            bounds[-1] = -K_ZERO_THRESHOLD

    right_start = None
    idx = np.nonzero(pos)[0]
    if len(idx) > 0:
        right_start = int(idx[0])
    right_max_bin = max_bin - 1 - len(bounds)
    if right_start is not None and right_max_bin > 0:
        right = greedy_find_bin(distinct_values[right_start:], counts[right_start:],
                                right_max_bin, right_cnt_data, min_data_in_bin)
        bounds.append(K_ZERO_THRESHOLD)
        bounds.extend(right)
    else:
        bounds.append(math.inf)
    return bounds


def find_bin_with_predefined_bounds(distinct_values: np.ndarray, counts: np.ndarray,
                                    max_bin: int, total_cnt: int, min_data_in_bin: int,
                                    forced_bounds: Sequence[float]) -> List[float]:
    """Forced-bounds variant: user bounds are fixed, remaining bin budget is
    spread across the gaps proportionally to their sample mass
    (reference: FindBinWithPredefinedBin, bin.cpp:157-254)."""
    neg = distinct_values <= -K_ZERO_THRESHOLD
    pos = distinct_values > K_ZERO_THRESHOLD
    n_left = int(neg.sum())
    has_right = bool(pos.any())

    bounds: List[float] = []
    if max_bin == 2:
        bounds.append(K_ZERO_THRESHOLD if n_left == 0 else -K_ZERO_THRESHOLD)
    elif max_bin >= 3:
        if n_left > 0:
            bounds.append(-K_ZERO_THRESHOLD)
        if has_right:
            bounds.append(K_ZERO_THRESHOLD)
    bounds.append(math.inf)

    max_to_insert = max_bin - len(bounds)
    inserted = 0
    for b in forced_bounds:
        if inserted >= max_to_insert:
            break
        if abs(b) > K_ZERO_THRESHOLD:
            bounds.append(float(b))
            inserted += 1
    bounds.sort()

    free_bins = max_bin - len(bounds)
    to_add: List[float] = []
    value_ind = 0
    n = len(distinct_values)
    for i, ub in enumerate(bounds):
        cnt_in_bin = 0
        bin_start = value_ind
        while value_ind < n and distinct_values[value_ind] < ub:
            cnt_in_bin += int(counts[value_ind])
            value_ind += 1
        bins_remaining = max_bin - len(bounds) - len(to_add)
        num_sub_bins = int(round(cnt_in_bin * free_bins / max(total_cnt, 1)))
        num_sub_bins = min(num_sub_bins, bins_remaining) + 1
        if i == len(bounds) - 1:
            num_sub_bins = bins_remaining + 1
        sub = greedy_find_bin(distinct_values[bin_start:value_ind],
                              counts[bin_start:value_ind],
                              num_sub_bins, cnt_in_bin, min_data_in_bin)
        to_add.extend(sub[:-1])  # last bound is inf
    bounds.extend(to_add)
    bounds.sort()
    return bounds


def _distinct_with_zero(values: np.ndarray, zero_cnt: int):
    """Sorted distinct values + counts, with the implicit zeros inserted at
    their ordered position (reference: BinMapper::FindBin, bin.cpp:353-389).
    ``values`` excludes zeros and NaNs."""
    values = np.sort(values.astype(np.float64), kind="stable")
    if _native.lib() is not None:
        return _native.distinct_with_zero(values, zero_cnt)
    if len(values) == 0:
        return np.array([0.0]), np.array([zero_cnt], dtype=np.int64)
    # merge near-equal neighbours (keep the larger value, sum counts)
    distinct: List[float] = [float(values[0])]
    counts: List[int] = [1]
    for v in values[1:]:
        if _close_ordered(distinct[-1], v):
            distinct[-1] = float(v)
            counts[-1] += 1
        else:
            if distinct[-1] < 0.0 and v > 0.0:
                distinct.append(0.0)
                counts.append(zero_cnt)
            distinct.append(float(v))
            counts.append(1)
    if values[0] > 0.0 and zero_cnt > 0:
        distinct.insert(0, 0.0)
        counts.insert(0, zero_cnt)
    if values[-1] < 0.0 and zero_cnt > 0:
        distinct.append(0.0)
        counts.append(zero_cnt)
    return np.asarray(distinct), np.asarray(counts, dtype=np.int64)


class BinMapper:
    """Per-feature value↔bin mapping (reference: BinMapper, bin.h:65)."""

    def __init__(self):
        self.num_bin: int = 1
        self.missing_type: int = MISSING_NONE
        self.is_trivial: bool = True
        self.sparse_rate: float = 1.0
        self.bin_type: int = BIN_NUMERICAL
        self.min_val: float = 0.0
        self.max_val: float = 0.0
        self.bin_upper_bound: np.ndarray = np.array([np.inf])
        self.bin_2_categorical: List[int] = []
        self.categorical_2_bin: Dict[int, int] = {}
        self.default_bin: int = 0
        self.most_freq_bin: int = 0

    # ------------------------------------------------------------------
    def find_bin(self, values: np.ndarray, total_sample_cnt: int, max_bin: int,
                 min_data_in_bin: int = 3, min_split_data: int = 20,
                 bin_type: int = BIN_NUMERICAL, use_missing: bool = True,
                 zero_as_missing: bool = False,
                 forced_bounds: Optional[Sequence[float]] = None) -> None:
        """Build the mapping from a value sample. ``values`` excludes zeros;
        ``total_sample_cnt - len(values)`` are implicit zeros
        (reference: BinMapper::FindBin, bin.cpp:325)."""
        values = np.asarray(values, dtype=np.float64)
        nan_mask = np.isnan(values)
        na_cnt = int(nan_mask.sum())
        values = values[~nan_mask]
        if not use_missing:
            self.missing_type = MISSING_NONE
        elif zero_as_missing:
            self.missing_type = MISSING_ZERO
        else:
            self.missing_type = MISSING_NAN if na_cnt > 0 else MISSING_NONE

        self.bin_type = bin_type
        self.default_bin = 0
        zero_cnt = int(total_sample_cnt - len(values) - na_cnt)
        distinct, counts = _distinct_with_zero(values, zero_cnt)
        self.min_val = float(distinct[0])
        self.max_val = float(distinct[-1])

        if bin_type == BIN_NUMERICAL:
            forced = list(forced_bounds) if forced_bounds else []
            if self.missing_type == MISSING_NAN:
                eff_max_bin, eff_total = max_bin - 1, total_sample_cnt - na_cnt
            else:
                eff_max_bin, eff_total = max_bin, total_sample_cnt
            if forced:
                bounds = find_bin_with_predefined_bounds(
                    distinct, counts, eff_max_bin, eff_total, min_data_in_bin, forced)
            else:
                bounds = find_bin_with_zero_as_one_bin(
                    distinct, counts, eff_max_bin, eff_total, min_data_in_bin)
            if self.missing_type == MISSING_ZERO and len(bounds) == 2:
                self.missing_type = MISSING_NONE
            if self.missing_type == MISSING_NAN:
                bounds.append(math.nan)
            self.bin_upper_bound = np.asarray(bounds)
            self.num_bin = len(bounds)
            # each distinct value lands in the first bin whose upper bound
            # is >= it (bounds ascend; the count loop of the reference)
            n_num = (self.num_bin - 1 if self.missing_type == MISSING_NAN
                     else self.num_bin)
            which = np.searchsorted(self.bin_upper_bound[:n_num - 1],
                                    distinct, side="left")
            cnt_in_bin = np.bincount(
                which, weights=counts, minlength=self.num_bin
            ).astype(np.int64)
            if self.missing_type == MISSING_NAN:
                cnt_in_bin[-1] = na_cnt
            log.check(self.num_bin <= max_bin, "num_bin exceeds max_bin")
        else:
            cnt_in_bin = self._find_bin_categorical(
                distinct, counts, total_sample_cnt, na_cnt, max_bin, min_data_in_bin)

        self.is_trivial = self.num_bin <= 1
        if not self.is_trivial and self._need_filter(cnt_in_bin, total_sample_cnt,
                                                     min_split_data):
            self.is_trivial = True
        if not self.is_trivial:
            self.default_bin = int(self.value_to_bin(0.0))
            if bin_type == BIN_CATEGORICAL:
                log.check(self.default_bin > 0, "categorical default_bin must be > 0")
            self.most_freq_bin = int(np.argmax(cnt_in_bin))
            self.sparse_rate = float(cnt_in_bin[self.default_bin]) / max(total_sample_cnt, 1)
            max_rate = float(cnt_in_bin[self.most_freq_bin]) / max(total_sample_cnt, 1)
            if self.most_freq_bin != self.default_bin and max_rate > 0.7:
                self.sparse_rate = max_rate
            else:
                self.most_freq_bin = self.default_bin
        else:
            self.sparse_rate = 1.0

    def _find_bin_categorical(self, distinct, counts, total_sample_cnt, na_cnt,
                              max_bin, min_data_in_bin):
        """Count-ordered categorical mapping; rare categories and negatives go
        to the NaN bin (reference: bin.cpp:424-497)."""
        vals_int: List[int] = []
        counts_int: List[int] = []
        for v, c in zip(distinct, counts):
            iv = int(v)
            if iv < 0:
                na_cnt += int(c)
                log.warning("Met negative value in categorical features, converting to NaN")
            elif vals_int and iv == vals_int[-1]:
                counts_int[-1] += int(c)
            else:
                vals_int.append(iv)
                counts_int.append(int(c))
        self.num_bin = 0
        cnt_in_bin: List[int] = []
        rest_cnt = total_sample_cnt - na_cnt
        if rest_cnt > 0 and vals_int:
            order = np.argsort(np.asarray(counts_int), kind="stable")[::-1]
            vals_sorted = [vals_int[i] for i in order]
            cnts_sorted = [counts_int[i] for i in order]
            # bin 0 must not be category 0 (0 is the "default"/elided value)
            if vals_sorted[0] == 0:
                if len(vals_sorted) == 1:
                    vals_sorted.append(vals_sorted[0] + 1)
                    cnts_sorted.append(0)
                vals_sorted[0], vals_sorted[1] = vals_sorted[1], vals_sorted[0]
                cnts_sorted[0], cnts_sorted[1] = cnts_sorted[1], cnts_sorted[0]
            cut_cnt = int((total_sample_cnt - na_cnt) * 0.99)
            eff_max_bin = min(len(vals_sorted), max_bin)
            self.categorical_2_bin = {}
            self.bin_2_categorical = []
            used_cnt = 0
            cur = 0
            while cur < len(vals_sorted) and (used_cnt < cut_cnt or self.num_bin < eff_max_bin):
                if cnts_sorted[cur] < min_data_in_bin and cur > 1:
                    break
                self.bin_2_categorical.append(vals_sorted[cur])
                self.categorical_2_bin[vals_sorted[cur]] = self.num_bin
                used_cnt += cnts_sorted[cur]
                cnt_in_bin.append(cnts_sorted[cur])
                self.num_bin += 1
                cur += 1
            if cur == len(vals_sorted) and na_cnt > 0:
                self.bin_2_categorical.append(-1)
                self.categorical_2_bin[-1] = self.num_bin
                cnt_in_bin.append(0)
                self.num_bin += 1
            self.missing_type = (MISSING_NONE if cur == len(vals_sorted) and na_cnt == 0
                                 else MISSING_NAN)
            if cnt_in_bin:
                cnt_in_bin[-1] += total_sample_cnt - used_cnt
        return np.asarray(cnt_in_bin, dtype=np.int64)

    def _need_filter(self, cnt_in_bin: np.ndarray, total_cnt: int,
                     filter_cnt: int) -> bool:
        """True if no split on this feature could satisfy min_data_in_leaf on
        both sides (reference: NeedFilter, bin.cpp:54-76). Numerical features
        use the cumulative left/right check over every boundary; categoricals
        are only filtered when they have <= 2 bins (per-bin check)."""
        if self.bin_type == BIN_NUMERICAL:
            left = 0
            for i in range(len(cnt_in_bin) - 1):
                left += int(cnt_in_bin[i])
                if left >= filter_cnt and total_cnt - left >= filter_cnt:
                    return False
            return True
        if len(cnt_in_bin) <= 2:
            for i in range(len(cnt_in_bin) - 1):
                left = int(cnt_in_bin[i])
                if left >= filter_cnt and total_cnt - left >= filter_cnt:
                    return False
            return True
        return False

    # ------------------------------------------------------------------
    def value_to_bin(self, value) -> np.ndarray:
        """Vectorized value→bin (reference: BinMapper::ValueToBin, bin.h:472)."""
        scalar = np.isscalar(value)
        v = np.atleast_1d(np.asarray(value, dtype=np.float64))
        if self.bin_type == BIN_NUMERICAL:
            n_search = self.num_bin - (1 if self.missing_type == MISSING_NAN else 0)
            if _native.lib() is not None and v.ndim == 1 and len(v) > 1024:
                res = _native.binarize_numerical(
                    v, self.bin_upper_bound, n_search - 1,
                    self.missing_type, self.num_bin)
            else:
                nan = np.isnan(v)
                vv = np.where(nan, 0.0, v)
                # first bin i with value <= bin_upper_bound[i]; bounds
                # ascend, the last searchable bound is +inf so the result
                # is always < n_search
                out = np.searchsorted(self.bin_upper_bound[:n_search - 1], vv,
                                      side="left")
                if self.missing_type == MISSING_NAN:
                    out = np.where(nan, self.num_bin - 1, out)
                res = out.astype(np.int32)
        else:
            # NaN is converted to 0.0 before categorical lookup unless this
            # feature's missing type is NaN (reference: bin.h:473-478)
            nan_cat = -1 if self.missing_type == MISSING_NAN else 0
            iv = np.where(np.isnan(v), nan_cat, v).astype(np.int64)
            # one search a value over the sorted categories, not one pass
            # over the column a category (255 passes over 11M rows took 10 s
            # a column); what is not in the map takes the last bin
            res = np.full(v.shape, self.num_bin - 1, dtype=np.int32)
            if self.categorical_2_bin:
                cats = np.array(sorted(self.categorical_2_bin), np.int64)
                bins = np.array([self.categorical_2_bin[c] for c in cats],
                                np.int32)
                pos = np.searchsorted(cats, iv)
                res = np.where(cats.take(pos, mode="clip") == iv,
                               bins.take(pos, mode="clip"), res)
        return int(res[0]) if scalar else res

    def value_to_bin_predict(self, value, sentinel: int) -> np.ndarray:
        """Prediction-time value→bin for CATEGORICAL features: any value
        that is NaN, negative or an unseen category maps to ``sentinel`` (a
        bin index outside every node's category bitset), so bin-space
        traversal routes it right — exactly the reference's
        CategoricalDecision, which casts to int and sends negatives/unknowns
        down the right child before any missing handling (reference:
        include/LightGBM/tree.h:262-303)."""
        v = np.atleast_1d(np.asarray(value, dtype=np.float64))
        res = np.full(v.shape, sentinel, dtype=np.int32)
        iv = np.where(np.isnan(v) | (v < 0), -1, v).astype(np.int64)
        for cat, b in self.categorical_2_bin.items():
            if cat >= 0:
                res = np.where(iv == cat, b, res)
        return res

    # ------------------------------------------------------------------
    def to_dict(self) -> dict:
        return {
            "num_bin": self.num_bin, "missing_type": self.missing_type,
            "is_trivial": self.is_trivial, "sparse_rate": self.sparse_rate,
            "bin_type": self.bin_type, "min_val": self.min_val, "max_val": self.max_val,
            "bin_upper_bound": [float(x) for x in self.bin_upper_bound],
            "bin_2_categorical": list(self.bin_2_categorical),
            "default_bin": self.default_bin, "most_freq_bin": self.most_freq_bin,
        }

    @classmethod
    def from_thresholds(cls, thresholds, missing_type: int = MISSING_NONE
                        ) -> "BinMapper":
        """Serving-side numerical mapper built from a forest's split
        thresholds instead of a data sample (serve/packing.py).

        Traversal only needs every node DECISION reproduced, not the
        training quantization: with the sorted distinct thresholds as bin
        upper bounds, ``value_to_bin(v) <= value_to_bin(thr)`` holds
        exactly when ``v <= thr`` for every threshold in the set, so
        bin-space compares equal the host's value-space compares.  Under
        MISSING_ZERO the zero value gets its own bin (bounds at
        +-kZeroThreshold, reference: meta.h:53) so only "zero" rows take
        the default-left route; under MISSING_NAN the trailing NaN bin is
        appended like ``find_bin``'s."""
        m = cls()
        vals = np.unique(np.asarray(thresholds, dtype=np.float64))
        vals = vals[np.isfinite(vals)]
        if missing_type == MISSING_ZERO:
            vals = np.unique(np.concatenate(
                [vals, [-K_ZERO_THRESHOLD, K_ZERO_THRESHOLD]]))
        bounds = list(vals) + [math.inf]
        if missing_type == MISSING_NAN:
            bounds.append(math.nan)
        m.bin_upper_bound = np.asarray(bounds, dtype=np.float64)
        m.num_bin = len(bounds)
        m.missing_type = int(missing_type)
        m.bin_type = BIN_NUMERICAL
        m.is_trivial = False
        if len(vals):
            m.min_val, m.max_val = float(vals[0]), float(vals[-1])
        m.default_bin = int(m.value_to_bin(0.0))
        m.most_freq_bin = m.default_bin
        m.sparse_rate = 0.0
        return m

    @classmethod
    def categorical_from_categories(cls, categories) -> "BinMapper":
        """Model-derived CATEGORICAL mapper for the online
        train-continue path (online/binspace.py): the bins are exactly
        the category values the forest's bitsets reference, plus a
        trailing NaN/unseen bin that no node bitset can contain — so
        NaN, negatives and categories the model never saw all land in a
        bin whose bit is set nowhere and route right, exactly like the
        reference's CategoricalDecision (tree.h:262-303).

        Follows ``_find_bin_categorical``'s conventions: bin 0 must not
        be category 0 (0 is the default/elided value; find_bin swaps it
        out of bin 0, and ``find_bin`` checks ``default_bin > 0``), and
        the NaN catch-all is category ``-1`` at the LAST bin (which is
        also where ``value_to_bin`` sends unmatched categories)."""
        m = cls()
        cats = sorted({int(c) for c in categories if int(c) >= 0})
        if not cats:
            return m  # trivial: the model references no category
        if cats[0] == 0:
            if len(cats) == 1:
                cats.append(1)
            cats[0], cats[1] = cats[1], cats[0]
        cats.append(-1)  # NaN / unseen catch-all, never in a bitset
        m.bin_2_categorical = cats
        m.categorical_2_bin = {c: i for i, c in enumerate(cats)}
        m.num_bin = len(cats)
        m.bin_type = BIN_CATEGORICAL
        m.missing_type = MISSING_NAN
        m.is_trivial = False
        m.sparse_rate = 0.0
        m.min_val = float(min(c for c in cats if c >= 0))
        m.max_val = float(max(cats))
        m.default_bin = int(m.value_to_bin(0.0))
        m.most_freq_bin = m.default_bin
        return m

    @classmethod
    def from_dict(cls, d: dict) -> "BinMapper":
        m = cls()
        m.num_bin = int(d["num_bin"])
        m.missing_type = int(d["missing_type"])
        m.is_trivial = bool(d["is_trivial"])
        m.sparse_rate = float(d["sparse_rate"])
        m.bin_type = int(d["bin_type"])
        m.min_val = float(d["min_val"])
        m.max_val = float(d["max_val"])
        m.bin_upper_bound = np.asarray(d["bin_upper_bound"], dtype=np.float64)
        m.bin_2_categorical = [int(x) for x in d["bin_2_categorical"]]
        m.categorical_2_bin = {c: i for i, c in enumerate(m.bin_2_categorical)}
        m.default_bin = int(d["default_bin"])
        m.most_freq_bin = int(d["most_freq_bin"])
        return m

    def missing_type_name(self) -> str:
        return _MISSING_NAMES[self.missing_type]

    def bin_to_value(self, bin_idx: int) -> float:
        """Representative threshold value for a bin (its upper bound)."""
        if self.bin_type == BIN_NUMERICAL:
            return float(self.bin_upper_bound[bin_idx])
        return float(self.bin_2_categorical[bin_idx])
