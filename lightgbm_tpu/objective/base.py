"""Objective interface (reference: include/LightGBM/objective_function.h:19-91)."""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np


class Objective:
    """Base objective: subclasses implement ``get_gradients`` with jnp ops."""

    name = "none"
    is_constant_hessian = False
    is_renew_tree_output = False
    need_accurate_prediction = True
    num_tree_per_iteration = 1
    # get_gradients is pure traced jnp on (score, captured label/weight
    # arrays) for every built-in objective, so the trainer may fold it
    # into the growth jit (the fused gradient pass) — an objective that
    # ever computes gradients host-side must flip this off
    supports_fused_grad = True

    def __init__(self, config):
        self.config = config
        self.num_data = 0
        self.label: Optional[np.ndarray] = None
        self.weights: Optional[np.ndarray] = None

    # -- lifecycle -----------------------------------------------------
    def init(self, metadata, num_data: int) -> None:
        """Bind label/weights (reference: ObjectiveFunction::Init)."""
        self.num_data = num_data
        self.label = metadata.label
        self.weights = metadata.weights
        self._to_device()

    def _to_device(self) -> None:
        import jax.numpy as jnp
        self._label_d = jnp.asarray(self.label) if self.label is not None else None
        self._weights_d = (jnp.asarray(self.weights)
                           if self.weights is not None else None)

    def _apply_weight(self, g, h):
        if self._weights_d is not None:
            return g * self._weights_d, h * self._weights_d
        return g, h

    # -- core ----------------------------------------------------------
    def get_gradients(self, score) -> Tuple["jnp.ndarray", "jnp.ndarray"]:
        raise NotImplementedError

    def health_tap(self, g, h, iteration: int) -> bool:
        """Numerics sentinel over this objective's gradient/hessian
        output — the trainer calls it once per iteration when
        ``LGBM_TPU_HEALTH`` / ``tpu_health`` is on, so a non-finite
        gradient is attributed to the OBJECTIVE that produced it (the
        exp/log link functions are where NaNs are born) rather than to
        whatever downstream phase first consumed it.  True = healthy."""
        from ..obs import health
        return health.check_gradients(g, h, phase="boosting (grad/hess)",
                                      iteration=iteration,
                                      objective=self.name)

    def boost_from_score(self, class_id: int = 0) -> float:
        return 0.0

    def class_need_train(self, class_id: int) -> bool:
        return True

    def convert_output(self, raw: np.ndarray) -> np.ndarray:
        """Raw margin -> user-space prediction."""
        return raw

    def renew_leaf_values(self, residual: np.ndarray, leaf_id: np.ndarray,
                          num_leaves: int, bag_mask: np.ndarray) -> np.ndarray:
        """Per-leaf refit for percentile-style losses
        (reference: RenewTreeOutput impls + serial_tree_learner.cpp:855-893).
        Returns new leaf outputs, shape [num_leaves]; NaN = keep current."""
        raise NotImplementedError

    def __str__(self) -> str:
        return self.name


def percentile(values: np.ndarray, weights: Optional[np.ndarray],
               alpha: float) -> float:
    """(Weighted) percentile matching the reference's interpolation
    (reference: PercentileFun / WeightedPercentileFun,
    src/objective/regression_objective.hpp:18-76)."""
    cnt = len(values)
    if cnt == 0:
        return 0.0
    if cnt == 1:
        return float(values[0])
    if weights is None:
        order = np.argsort(values, kind="stable")
        data = values[order]
        float_pos = (1.0 - alpha) * cnt
        pos = int(float_pos)
        if pos < 1:
            return float(data[-1])
        if pos >= cnt:
            return float(data[0])
        bias = float_pos - pos
        # reference selects the (pos-1)/pos-th largest
        v1 = data[cnt - pos]
        v2 = data[cnt - pos - 1]
        return float(v1 - (v1 - v2) * bias)
    order = np.argsort(values, kind="stable")
    data = values[order]
    w = weights[order]
    cdf = np.cumsum(w)
    threshold = cdf[-1] * alpha
    pos = int(np.searchsorted(cdf, threshold, side="right"))
    pos = min(pos, cnt - 1)
    if pos == 0 or pos == cnt - 1:
        return float(data[pos])
    v1, v2 = float(data[pos - 1]), float(data[pos])
    if cdf[pos + 1] - cdf[pos] >= 1.0:
        return float((threshold - cdf[pos]) / (cdf[pos + 1] - cdf[pos]) * (v2 - v1) + v1)
    return v2
