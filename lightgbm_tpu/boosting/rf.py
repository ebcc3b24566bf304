"""Random forest mode (reference: src/boosting/rf.hpp:25-218).

Bagging is mandatory, shrinkage is 1, gradients come from the fixed init
score, and scores are maintained as the *average* of tree outputs
(``average_output``), using the reference's multiply-update-multiply dance.
"""
from __future__ import annotations

import numpy as np

from ..core.tree import Tree
from ..utils import log
from .gbdt import GBDT, K_EPSILON, _constant_tree


class RF(GBDT):
    average_output = True

    # gradients are FROZEN from the constant init score (computed once in
    # init) — there is nothing to fuse into the per-iteration growth jit
    _fused_grad_capable = False

    def init(self, config, train_ds, objective, metrics) -> None:
        if not (config.bagging_freq > 0 and 0.0 < config.bagging_fraction < 1.0):
            log.fatal("RF mode requires bagging "
                      "(bagging_freq > 0 and bagging_fraction in (0, 1))")
        if not (0.0 < config.feature_fraction <= 1.0):
            log.fatal("RF mode requires feature_fraction in (0, 1]")
        super().init(config, train_ds, objective, metrics)
        self.shrinkage_rate = 1.0
        # gradients from the constant init score, computed once
        # (reference: rf.hpp:82-101 Boosting)
        import jax.numpy as jnp
        self.init_scores = [self._rf_init_score(k) for k in range(self.num_tpi)]
        base = jnp.stack(
            [jnp.full((train_ds.num_data,), s, jnp.float32)
             for s in self.init_scores], axis=1)
        score = base[:, 0] if self.num_tpi == 1 else base
        self._g_fixed, self._h_fixed = objective.get_gradients(score)
        if self._g_fixed.ndim == 1:
            self._g_fixed = self._g_fixed[:, None]
            self._h_fixed = self._h_fixed[:, None]

    def _rf_init_score(self, k: int) -> float:
        if self.objective is None:
            log.fatal("RF mode does not support custom objective functions")
        if not self.config.boost_from_average:
            return 0.0
        return float(self.objective.boost_from_score(k))

    def _multiply_score(self, k: int, val: float) -> None:
        self._train_score = self._train_score.at[:, k].multiply(val)
        for i in range(len(self._valid_scores)):
            self._valid_scores[i] = self._valid_scores[i].at[:, k].multiply(val)

    def train_one_iter(self, gradients=None, hessians=None) -> bool:
        """(reference: rf.hpp:105-168)."""
        if gradients is not None or hessians is not None:
            log.fatal("RF mode does not support custom objective functions")
        g, h = self._bagging(self.iter_, self._g_fixed, self._h_fixed)
        feature_mask = self._feature_mask()
        K = self.num_tpi
        for k in range(K):
            if self.class_need_train[k] and self.train_ds.num_features > 0:
                # the wave grower's third output (its WaveStats) is left
                # where it is: RF reports no work counters
                arrs, leaf_id = self._grow(self._grow_bins, g[:, k], h[:, k],
                                           self._bag_mask, feature_mask)[:2]
                nl = int(arrs.num_leaves)
            else:
                arrs, nl = None, 1
            if nl > 1:
                arrs = self._renew_rf_output(arrs, leaf_id, k)
                if abs(self.init_scores[k]) > K_EPSILON:
                    arrs = arrs._replace(
                        leaf_value=arrs.leaf_value + self.init_scores[k])
                tree = Tree.from_device(arrs, self.train_ds, shrinkage=1.0)
                self._multiply_score(k, self.iter_)
                lid = leaf_id
                self._train_score = self._train_score.at[:, k].set(
                    self._apply_leaf(self._train_score[:, k], lid, arrs.leaf_value))
                for i in range(len(self._valid_scores)):
                    self._valid_scores[i] = self._valid_scores[i].at[:, k].set(
                        self._traverse_add(self._valid_scores[i][:, k], arrs,
                                           self._valid_bins[i]))
                self._multiply_score(k, 1.0 / (self.iter_ + 1))
            else:
                output = 0.0
                if len(self.models) < K and not self.class_need_train[k]:
                    output = float(self.objective.boost_from_score(k))
                tree = _constant_tree(output)
                self._multiply_score(k, self.iter_)
                self._train_score = self._train_score.at[:, k].add(output)
                for i in range(len(self._valid_scores)):
                    self._valid_scores[i] = self._valid_scores[i].at[:, k].add(output)
                self._multiply_score(k, 1.0 / (self.iter_ + 1))
            self.models.append(tree)
        self.iter_ += 1
        return False

    def _renew_rf_output(self, arrs, leaf_id, k: int):
        """Leaf renewal against the constant init score (reference:
        rf.hpp:117-121)."""
        if self.objective is None or not self.objective.is_renew_tree_output:
            return arrs
        import jax.numpy as jnp
        nl = int(arrs.num_leaves)
        residual = (self.train_ds.metadata.label.astype(np.float64)
                    - self.init_scores[k])
        new_vals = self.objective.renew_leaf_values(
            residual, np.asarray(leaf_id), nl, self._bag_mask_host)
        lv = np.asarray(arrs.leaf_value).copy()
        ok = ~np.isnan(new_vals)
        lv[:nl][ok] = new_vals[ok]
        return arrs._replace(leaf_value=jnp.asarray(lv))

    def predict_raw(self, X, num_iteration=None, start_iteration: int = 0,
                    early_stop=None):
        raw = super().predict_raw(X, num_iteration, start_iteration,
                                  early_stop)
        start, stop = self._iter_window(num_iteration, start_iteration)
        return raw / max(stop - start, 1)
