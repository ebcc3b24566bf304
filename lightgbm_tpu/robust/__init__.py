"""Fault tolerance: atomic checkpoint/resume, the device-wedge watchdog,
and the deterministic fault-injection harness.

Three cooperating layers, each usable alone:

- :mod:`.checkpoint` — ``CheckpointManager``: versioned
  write-temp-fsync-rename checkpoints (forest + RNG + score state + eval
  history + config digest) every ``tpu_checkpoint_freq`` iterations, and
  bit-exact resume from the newest valid one (``engine.train`` drives it
  when ``tpu_checkpoint_dir`` is set).
- :mod:`.watchdog` — ``DeviceGuard``: classify device failures
  (transient vs fatal), retry transients with bounded exponential
  backoff + deterministic jitter, stamp stalled steps against a rolling
  per-step p99 deadline, and on a fatal wedge dump the flight recorder,
  write a boundary checkpoint, and abort / fall back to CPU per
  ``tpu_on_device_error``.  ``CircuitBreaker`` applies the same
  classes + backoff to serving-replica routing (serve/router.py): a
  wedged replica drops out of the routing set and a half-open probe
  re-admits it.
- :mod:`.faults` — the ``LGBM_TPU_FAULTS`` injection harness: seeded,
  deterministic faults (``raise``/``transient``/``sleep``) at named
  points (device_execute, gradients, collective, serve_device,
  serve_explain_submit/serve_explain_device, serve_replica{_i},
  serve_swap, serve_canary, checkpoint_write) so every recovery branch
  — training (tools/fault_matrix.py) and serving
  (tools/chaos_serve.py) — is CI-provable on CPU.
"""
from .checkpoint import CheckpointManager, config_digest
from .faults import FaultInjected, FaultTransient
from .watchdog import (CircuitBreaker, DeviceGuard, DeviceWedgedError,
                       classify_error)

__all__ = [
    "CheckpointManager", "config_digest",
    "CircuitBreaker", "DeviceGuard", "DeviceWedgedError",
    "classify_error",
    "FaultInjected", "FaultTransient",
]
