"""Gradient-based one-side sampling, stated by the benchmark itself.

NumPy only, float64, independent of the code under test.  The semantics are
the reference's (LightGBM v2.3.2 ``src/boosting/goss.hpp:91-139``) as the
program's ``boosting/goss.py`` states them: with ``top_k = int(N *
top_rate)`` and ``other_k = int(N * other_rate)``,

- a row's weight is ``|g * h|`` (summed over the classes);
- every row whose weight is at or above the exact ``top_k``-th largest weight
  (the threshold) is kept as it is: the top set, ``top_k`` rows and the ties
  at the threshold;
- each other row (``rest_k`` of them) is kept with probability ``other_k /
  rest_k`` and its gradient and hessian multiplied by ``(N - top_k) /
  other_k``, so that the sampled rest stands for all of it;
- nothing is sampled in the first ``int(1 / learning_rate)`` iterations.

The program departs from the reference in one place, stated in the
configuration's ``departs``: the rest is a Bernoulli sample at the fixed
probability, where the reference draws a running remainder that ends at
exactly ``other_k`` rows.  So a sample cannot be compared row for row with
anything; ``judge`` says whether a mask IS a legal sample of given gradients:
the top set is in it (but for rows within ``F32_RTOL`` of the threshold, which
float32 and float64 weights may rank either way), the sampled rest counts
what a binomial draw counts to ``SIGMAS`` standard deviations, the amplified
sums estimate the full sums inside the same band (the estimate is unbiased:
``multiply * other_k / rest_k = (N - top_k) / rest_k``, which is 1 without
ties), and, given the grown tree's root, its count is the bag's and its
hessian sum is the bag's with the top rows unamplified and the rest amplified
(a sampler that amplified the top rows too would read several times that).
"""
from __future__ import annotations

import numpy as np

# What a float32 gradient pass may differ by from this file's float64, as a
# share of ``|g*h|``: it bounds both the threshold's error and how far above
# the threshold a row may lie and still be ranked below it.  Set between two
# readings (PERF.md 2, PR 32): on the v5e the program's threshold reads
# 6.6e-7 to 8.4e-7 above float64's and the farthest row it ranked the other
# way 1.2e-6 above (the chip's ``exp``; ISSUE 32's first guess of 1e-6 for the
# rows was inside that error and refused an honest run); bf16 gradients read
# 8e-4 on the rows and 1.1e-3 on the threshold (tests/test_goss.py).
F32_RTOL = 1e-5
SIGMAS = 5.0         # the band of everything a random draw decides
ROOT_RTOL = 1e-4     # a float32 sum of millions of hessians against float64


def binary_gradients(raw, y, sigmoid: float = 1.0) -> tuple:
    """``(g, h)`` of the binary log loss at raw scores ``raw`` for labels
    ``y`` (positive where ``y > 0``), float64
    (``src/objective/binary_objective.hpp:101-116``, unweighted)."""
    raw = np.asarray(raw, np.float64)
    lab = np.where(np.asarray(y) > 0, 1.0, -1.0)
    response = -lab * sigmoid / (1.0 + np.exp(lab * sigmoid * raw))
    a = np.abs(response)
    return response, a * (sigmoid - a)


def sizes(n: int, top_rate: float, other_rate: float) -> tuple:
    """``(top_k, other_k, multiply)`` for ``n`` rows."""
    top_k = max(1, int(n * top_rate))
    other_k = max(1, int(n * other_rate))
    return top_k, other_k, (n - top_k) / other_k


def sampling_starts(learning_rate: float) -> int:
    """The first iteration (0-based) that samples."""
    return int(1.0 / learning_rate)


def weights(g, h) -> np.ndarray:
    w = np.abs(np.asarray(g, np.float64) * np.asarray(h, np.float64))
    return w if w.ndim == 1 else w.sum(axis=1)


def threshold(w: np.ndarray, top_k: int) -> float:
    """The exact ``top_k``-th largest of ``w`` (an nth-element, as the
    reference's ``ArgMaxAtK``; no sort)."""
    at = len(w) - top_k
    return float(np.partition(w, at)[at])


def judge(g, h, mask, top_rate: float, other_rate: float,
          program_threshold: float = None, root_count: int = None,
          root_weight: float = None) -> dict:
    """Whether ``mask`` (bool [N]) is a legal GOSS sample of the gradients
    ``g``, ``h`` (module text); every number that decided it, and ``ok``."""
    g = np.asarray(g, np.float64).reshape(len(mask), -1)
    h = np.asarray(h, np.float64).reshape(len(mask), -1)
    mask = np.asarray(mask, bool)
    n = len(mask)
    top_k, other_k, multiply = sizes(n, top_rate, other_rate)
    w = weights(g, h)
    thr = threshold(w, top_k)
    sure_top = w > thr * (1.0 + F32_RTOL)
    sure_rest = w < thr * (1.0 - F32_RTOL)
    band = int(n - sure_top.sum() - sure_rest.sum())
    is_top = w >= thr
    rest_k = max(int(n - is_top.sum()), 1)
    p = other_k / rest_k
    out = {"rows": n, "top_k": top_k, "other_k": other_k,
           "multiply": multiply, "threshold": thr, "rows_in_tie_band": band,
           "top_rows": int(is_top.sum()), "bag_rows": int(mask.sum()),
           "sigmas": SIGMAS}
    # the top set is in the bag: of the rows above the threshold and not in
    # it, the farthest above (as a share of the threshold; a reading on every
    # run) is inside what float32 may misrank
    above = (w > thr) & ~mask
    out["top_missing"] = int((sure_top & ~mask).sum())
    out["top_missing_margin"] = (float(w[above].max() / thr - 1.0)
                                 if above.any() else 0.0)
    out["f32_rtol"] = F32_RTOL
    ok = out["top_missing"] == 0
    # the sampled rest counts what a binomial draw counts
    sampled = int((mask & sure_rest).sum())
    sd = float(np.sqrt(rest_k * p * (1.0 - p)))
    out["rest_sampled"], out["rest_expected"], out["rest_sd"] = (
        sampled, float(rest_k * p), sd)
    ok = ok and abs(sampled - rest_k * p) <= SIGMAS * sd + band
    # the amplified sums estimate the full sums
    amp = np.where(is_top, 1.0, multiply)[:, None]
    rest = ~is_top
    for name, x in (("g", g), ("h", h)):
        got = float((x * amp * mask[:, None]).sum())
        want = float(x[is_top].sum() + multiply * p * x[rest].sum())
        sd_x = float(multiply * np.sqrt(p * (1.0 - p)
                                        * (x[rest] ** 2).sum()))
        out[f"sum_{name}"] = {"amplified": got, "expected": want, "sd": sd_x,
                              "full": float(x.sum())}
        ok = ok and abs(got - want) <= SIGMAS * sd_x + 1e-9 * abs(want)
    if program_threshold is not None:
        rel = abs(float(program_threshold) - thr) / thr
        out["program_threshold"] = float(program_threshold)
        out["threshold_rel_err"] = rel
        ok = ok and rel <= F32_RTOL
    if root_count is not None:
        out["root_count"] = int(root_count)
        ok = ok and int(root_count) == out["bag_rows"]
    if root_weight is not None:
        want = float((h * amp * mask[:, None]).sum())
        out["root_weight"] = float(root_weight)
        out["root_weight_expected"] = want
        out["root_weight_rel_err"] = abs(float(root_weight) - want) / want
        out["root_rtol"] = ROOT_RTOL
        ok = ok and out["root_weight_rel_err"] <= ROOT_RTOL
    out["ok"] = bool(ok)
    return out


def counts_ok(rows: int, top_rows: int, bag_rows: int, top_rate: float,
              other_rate: float, tie_share: float = 1e-4) -> bool:
    """A sampled iteration's two counters: the top set ``top_k`` rows and
    the ties at the threshold, at most ``tie_share`` of the rows (0.01%: a
    dozen rounds into a job on millions of rows few rows share a score; a
    toy table's first trees tie by the hundred, and its test says so), the
    bag within ``SIGMAS`` standard deviations of what the rest's draw
    gives."""
    top_k, other_k, _ = sizes(rows, top_rate, other_rate)
    rest_k = max(rows - top_rows, 1)
    p = min(other_k / rest_k, 1.0)
    sd = float(np.sqrt(rest_k * p * (1.0 - p)))
    return (0 <= top_rows - top_k <= tie_share * rows
            and abs(bag_rows - top_rows - rest_k * p) <= SIGMAS * sd)
