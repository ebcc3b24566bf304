"""Observability subsystem: structured telemetry, phase timers, JAX
instrumentation (see ``core`` for the event/counter API and the set-up
records, ``trace`` for the compile observer, ``peaks`` for the device
peak table, ``report`` for JSONL merging).  Where time goes inside a
step is read from the device trace under the ``lgbm/*`` scopes
(``benchmarks/``); the counters are ``Booster.work_counters()``.

Quick start::

    LGBM_TPU_TELEMETRY=/tmp/telem python train.py
    python tools/telemetry_report.py /tmp/telem

or programmatically ``obs.enable("/tmp/telem")`` / the ``tpu_telemetry``
parameter.  ``LGBM_TPU_TIMETAG=1`` keeps the plain phase-time report.
``LGBM_TPU_HEALTH=monitor|strict`` (or ``tpu_health``) arms the
training-health sentinels (``health``): per-iteration numerics guards,
model-state fingerprints, and the cross-rank divergence audit.
``LGBM_TPU_TRACE=1`` (or ``tpu_trace``) turns on the span layer
(``spans``): request/iteration trace events one schema wide, exported to
Perfetto by ``tools/trace_export.py``; ``LGBM_TPU_FLIGHT=<n>`` (or
``tpu_flight_len``) sizes the flight recorder ring dumped as
``FLIGHT_rN.json`` on degradations and health aborts.
"""
from .board import TrainBoard
from .board import active as board_active
from .board import current as train_board
from .core import (TIMETAG_ENABLED, SetupTrace, add, count, counter_value,
                   counters_snapshot, current_phase, digest, disable,
                   enable, enabled, event, gauge, open_setup_trace, phase,
                   phase_delta,
                   phase_snapshot, record_collective,
                   record_collective_host, report, reset, sink_path, sync,
                   tracing_enabled)
from .drift import (DriftMonitor, DriftSketch, QualityProfile,
                    accumulate_occupancy, bin_features, coarsen,
                    compute_occupancy, init_occupancy, ks, profile_path,
                    psi)
from .health import (TrainingHealthError, check_gradients, check_score,
                     check_tree, divergence_audit, enable_health,
                     health_enabled, health_mode, model_fingerprint)
from .peaks import device_peaks, roofline_seconds
from .spans import (Span, begin_span, current_context, emit_span,
                    enable_flight, enable_trace, end_span, flight_dump,
                    flight_enabled, flight_len, flight_len_from_env,
                    flight_snapshot, new_span_id, new_trace_id, span,
                    span_record_enabled, trace_enabled)
from .ranks import RankAggregator, StragglerDetector, skew_table
from .trace import (compile_count, compile_digest, compile_seconds,
                    install_recompile_hook, program_records, programs_seen)

__all__ = [
    "TIMETAG_ENABLED", "SetupTrace", "add", "count", "counter_value",
    "counters_snapshot", "current_phase", "digest", "disable", "enable",
    "enabled", "event", "gauge", "open_setup_trace", "phase", "phase_delta",
    "phase_snapshot",
    "record_collective", "record_collective_host", "report", "reset",
    "sink_path", "sync", "tracing_enabled",
    "DriftMonitor", "DriftSketch", "QualityProfile",
    "accumulate_occupancy", "bin_features", "coarsen",
    "compute_occupancy", "init_occupancy", "ks", "profile_path", "psi",
    "compile_count", "compile_digest", "compile_seconds",
    "install_recompile_hook", "program_records", "programs_seen",
    "device_peaks", "roofline_seconds",
    "TrainingHealthError", "check_gradients", "check_score", "check_tree",
    "divergence_audit", "enable_health", "health_enabled", "health_mode",
    "model_fingerprint",
    "Span", "begin_span", "current_context", "emit_span", "enable_flight",
    "enable_trace", "end_span", "flight_dump", "flight_enabled",
    "flight_len", "flight_len_from_env", "flight_snapshot", "new_span_id",
    "new_trace_id", "span", "span_record_enabled", "trace_enabled",
    "TrainBoard", "board_active", "train_board",
    "RankAggregator", "StragglerDetector", "skew_table",
]
