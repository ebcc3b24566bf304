"""GOSS — gradient-based one-side sampling
(reference: src/boosting/goss.hpp:30-217).

The reference's per-thread sequential sampler is ONE jitted program on the
device (``build_sampler``, scope ``lgbm/goss_sample``): every row whose
``|g*h|`` (summed over the classes) is at or above the exact ``top_k``-th
largest value is kept as it is, each other row is kept with probability
``other_k / rest_k`` and its gradient and hessian amplified by ``(N - top_k)
/ other_k`` (goss.hpp:91-139); nothing is sampled in the first ``int(1 /
learning_rate)`` iterations (goss.hpp:144-146).  The threshold is the last
of ``lax.top_k``'s values: on the v5e a sort of the 10.5M weights with their
indices, 28.4 ms of a 1.2 s iteration (PERF.md 6, PR 32), so the sort stays.
One departure: the sampling probability is the fixed ``other_k / rest_k``
where the reference draws a running remainder that ends at exactly
``other_k`` rows: identical in expectation.

The program returns the amplified gradients, the mask as the growth
program's row weights and three scalars (rows at or above the threshold,
rows in the bag, the threshold), all left on the device: ``update()`` copies
nothing to the host.  The scalars ride in the trainer's counters ring
(``GBDT.work_counters``); the host's copy of the mask is fetched where the
L1 leaf refit, RF or a checkpoint asks for it (``GBDT._bag_mask_host``).
The gradients must exist outside the growth program for all this, so the
fused gradient pass does not apply (``core/plan.py`` says so in the plan's
reasons).
"""
from __future__ import annotations

from .. import obs
from ..utils import log
from .gbdt import GBDT, _cached_jit, _device_scalar


def build_sampler(n: int, top_k: int, other_k: int):
    """``sample(g, h, key, it) -> (g', h', mask, (top_rows, bag_rows,
    threshold))`` for ``[n, K]`` gradients: the module's semantics, jitted.
    ``key`` is the booster's PRNG key (of ``bagging_seed``), ``it`` the
    iteration folded into it; ``mask`` is f32 ``[n]``, 1 in the bag."""
    import jax
    import jax.numpy as jnp
    multiply = (n - top_k) / other_k

    @jax.jit
    def sample(g, h, key, it):
        with jax.named_scope("lgbm/goss_sample"):
            weight = jnp.abs(g * h).sum(axis=1)  # summed over classes
            threshold = jax.lax.top_k(weight, top_k)[0][-1]
            is_top = weight >= threshold
            top_rows = jnp.sum(is_top, dtype=jnp.int32)
            rest_k = jnp.maximum(n - top_rows, 1)
            unif = jax.random.uniform(jax.random.fold_in(key, it), (n,))
            sampled_rest = (~is_top) & (unif < other_k / rest_k)
            mask = is_top | sampled_rest
            amp = jnp.where(sampled_rest, multiply,
                            1.0)[:, None].astype(jnp.float32)
            return (g * amp, h * amp, mask.astype(jnp.float32),
                    (top_rows, jnp.sum(mask, dtype=jnp.int32), threshold))
    return sample


class GOSS(GBDT):
    # the sampler ranks |g*h| and AMPLIFIES the sampled gradients before
    # growth — the [N] g/h arrays must exist outside the growth jit, so
    # the fused gradient pass cannot apply
    _fused_grad_capable = False

    def init(self, config, train_ds, objective, metrics) -> None:
        import jax
        super().init(config, train_ds, objective, metrics)
        if config.top_rate + config.other_rate > 1.0:
            log.fatal("top_rate + other_rate should be <= 1.0 in GOSS")
        if config.top_rate <= 0.0 or config.other_rate <= 0.0:
            log.fatal("top_rate and other_rate should be positive in GOSS")
        if config.bagging_freq > 0 and config.bagging_fraction != 1.0:
            log.fatal("Cannot use bagging in GOSS")
        log.info("Using GOSS")
        N = train_ds.num_data
        top_k = max(1, int(N * config.top_rate))
        other_k = max(1, int(N * config.other_rate))
        self._sample = _cached_jit(("goss_sample", N, top_k, other_k),
                                   lambda: build_sampler(N, top_k, other_k))
        self._sample_key = jax.random.PRNGKey(config.bagging_seed)
        self._full_bag = self._bag_mask     # every row, placed at init

    def _bagging(self, it: int, g, h):
        N = self.train_ds.num_data
        self._bag_mask_host = None          # fetched where it is asked for
        # no sampling for the first 1/learning_rate iterations
        # (reference: goss.hpp:144-146)
        if it < int(1.0 / self.config.learning_rate):
            self._bag_mask = self._full_bag
            self._sample_stats = (N, N, 0.0)
            return g, h
        g, h, self._bag_mask, self._sample_stats = self._sample(
            g, h, self._sample_key, _device_scalar(it, "int32"))
        if obs.health_enabled():
            # the amplifier multiplies the sampled rest by (1-a)/b, which
            # can overflow f32 for tiny other_rate — attribute that here,
            # not to the objective's (already checked) raw gradients
            obs.check_gradients(g, h, phase="goss amplification",
                                iteration=it, objective="goss")
        return g, h
