"""Configuration system.

TPU-native rebuild of the reference's single-source-of-truth parameter struct
(reference: include/LightGBM/config.h:31-872 and the generated alias table in
src/io/config_auto.cpp:10). Every public LightGBM v2.3.2 parameter name and
alias is accepted, so configs and ``train.conf`` files written for the
reference work unchanged. New here: ``device_type`` gains ``"tpu"`` (the
default), and TPU-specific knobs live in the ``tpu_*`` namespace.

Parsing follows the reference's pipeline: raw strings → alias resolution →
typed ``Config`` fields → inter-parameter consistency checks
(reference: src/io/config.cpp Config::Set).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from .utils import log

# ---------------------------------------------------------------------------
# Alias table (reference: src/io/config_auto.cpp:10-200). Maps alias → canonical.
# ---------------------------------------------------------------------------
_ALIASES: Dict[str, str] = {
    "config_file": "config",
    "task_type": "task",
    "objective_type": "objective", "app": "objective", "application": "objective",
    "boosting_type": "boosting", "boost": "boosting",
    "train": "data", "train_data": "data", "train_data_file": "data", "data_filename": "data",
    "test": "valid", "valid_data": "valid", "valid_data_file": "valid",
    "test_data": "valid", "test_data_file": "valid", "valid_filenames": "valid",
    "num_iteration": "num_iterations", "n_iter": "num_iterations",
    "num_tree": "num_iterations", "num_trees": "num_iterations",
    "num_round": "num_iterations", "num_rounds": "num_iterations",
    "num_boost_round": "num_iterations", "n_estimators": "num_iterations",
    "shrinkage_rate": "learning_rate", "eta": "learning_rate",
    "num_leaf": "num_leaves", "max_leaves": "num_leaves", "max_leaf": "num_leaves",
    "tree": "tree_learner", "tree_type": "tree_learner", "tree_learner_type": "tree_learner",
    "num_thread": "num_threads", "nthread": "num_threads", "nthreads": "num_threads",
    "n_jobs": "num_threads",
    "device": "device_type",
    "random_seed": "seed", "random_state": "seed",
    "min_data_per_leaf": "min_data_in_leaf", "min_data": "min_data_in_leaf",
    "min_child_samples": "min_data_in_leaf",
    "min_sum_hessian_per_leaf": "min_sum_hessian_in_leaf",
    "min_sum_hessian": "min_sum_hessian_in_leaf", "min_hessian": "min_sum_hessian_in_leaf",
    "min_child_weight": "min_sum_hessian_in_leaf",
    "sub_row": "bagging_fraction", "subsample": "bagging_fraction", "bagging": "bagging_fraction",
    "pos_sub_row": "pos_bagging_fraction", "pos_subsample": "pos_bagging_fraction",
    "pos_bagging": "pos_bagging_fraction",
    "neg_sub_row": "neg_bagging_fraction", "neg_subsample": "neg_bagging_fraction",
    "neg_bagging": "neg_bagging_fraction",
    "subsample_freq": "bagging_freq",
    "bagging_fraction_seed": "bagging_seed",
    "sub_feature": "feature_fraction", "colsample_bytree": "feature_fraction",
    "sub_feature_bynode": "feature_fraction_bynode", "colsample_bynode": "feature_fraction_bynode",
    "early_stopping_rounds": "early_stopping_round", "early_stopping": "early_stopping_round",
    "n_iter_no_change": "early_stopping_round",
    "max_tree_output": "max_delta_step", "max_leaf_output": "max_delta_step",
    "reg_alpha": "lambda_l1",
    "reg_lambda": "lambda_l2", "lambda": "lambda_l2",
    "min_split_gain": "min_gain_to_split",
    "rate_drop": "drop_rate",
    "topk": "top_k",
    "mc": "monotone_constraints", "monotone_constraint": "monotone_constraints",
    "feature_contrib": "feature_contri", "fc": "feature_contri", "fp": "feature_contri",
    "feature_penalty": "feature_contri",
    "fs": "forcedsplits_filename", "forced_splits_filename": "forcedsplits_filename",
    "forced_splits_file": "forcedsplits_filename", "forced_splits": "forcedsplits_filename",
    "verbose": "verbosity",
    "max_bins": "max_bin",
    "subsample_for_bin": "bin_construct_sample_cnt",
    "hist_pool_size": "histogram_pool_size",
    "data_seed": "data_random_seed",
    "model_output": "output_model", "model_out": "output_model",
    "save_period": "snapshot_freq",
    "model_input": "input_model", "model_in": "input_model",
    "model_file": "input_model",
    "predict_result": "output_result", "prediction_result": "output_result",
    "predict_name": "output_result", "prediction_name": "output_result",
    "pred_name": "output_result", "name_pred": "output_result",
    "init_score_filename": "initscore_filename", "init_score_file": "initscore_filename",
    "init_score": "initscore_filename", "input_init_score": "initscore_filename",
    "is_pre_partition": "pre_partition",
    "is_enable_bundle": "enable_bundle", "bundle": "enable_bundle",
    "is_sparse": "is_enable_sparse", "enable_sparse": "is_enable_sparse", "sparse": "is_enable_sparse",
    "two_round_loading": "two_round", "use_two_round_loading": "two_round",
    "is_save_binary": "save_binary", "is_save_binary_file": "save_binary",
    "has_header": "header",
    "label": "label_column",
    "weight": "weight_column",
    "group": "group_column", "group_id": "group_column",
    "query_column": "group_column", "query": "group_column", "query_id": "group_column",
    "ignore_feature": "ignore_column", "blacklist": "ignore_column",
    "cat_feature": "categorical_feature", "categorical_column": "categorical_feature",
    "cat_column": "categorical_feature",
    "is_predict_raw_score": "predict_raw_score", "predict_rawscore": "predict_raw_score",
    "raw_score": "predict_raw_score",
    "is_predict_leaf_index": "predict_leaf_index", "leaf_index": "predict_leaf_index",
    "is_predict_contrib": "predict_contrib", "contrib": "predict_contrib",
    "convert_model_file": "convert_model",
    "num_classes": "num_class",
    "unbalance": "is_unbalance", "unbalanced_sets": "is_unbalance",
    "metrics": "metric", "metric_types": "metric",
    "output_freq": "metric_freq",
    "training_metric": "is_provide_training_metric", "is_training_metric": "is_provide_training_metric",
    "train_metric": "is_provide_training_metric",
    "ndcg_eval_at": "eval_at", "ndcg_at": "eval_at", "map_eval_at": "eval_at", "map_at": "eval_at",
    "num_machine": "num_machines",
    "local_port": "local_listen_port", "port": "local_listen_port",
    "machine_list_file": "machine_list_filename", "machine_list": "machine_list_filename",
    "mlist": "machine_list_filename",
    "workers": "machines", "nodes": "machines",
}

# Parameters whose value is a comma-separated list.
_MULTI_VALUE = {
    "valid", "metric", "monotone_constraints", "feature_contri", "label_gain",
    "eval_at", "auc_mu_weights", "cegb_penalty_feature_lazy", "cegb_penalty_feature_coupled",
    "ignore_column", "categorical_feature", "interaction_constraints",
    "max_bin_by_feature",
}

_OBJECTIVE_ALIASES = {
    "regression": "regression", "regression_l2": "regression", "l2": "regression",
    "mean_squared_error": "regression", "mse": "regression", "l2_root": "regression",
    "root_mean_squared_error": "regression", "rmse": "regression",
    "regression_l1": "regression_l1", "l1": "regression_l1",
    "mean_absolute_error": "regression_l1", "mae": "regression_l1",
    "huber": "huber", "fair": "fair", "poisson": "poisson", "quantile": "quantile",
    "mape": "mape", "mean_absolute_percentage_error": "mape",
    "gamma": "gamma", "tweedie": "tweedie",
    "binary": "binary",
    "multiclass": "multiclass", "softmax": "multiclass",
    "multiclassova": "multiclassova", "multiclass_ova": "multiclassova",
    "ova": "multiclassova", "ovr": "multiclassova",
    "cross_entropy": "cross_entropy", "xentropy": "cross_entropy",
    "cross_entropy_lambda": "cross_entropy_lambda", "xentlambda": "cross_entropy_lambda",
    "lambdarank": "lambdarank",
    "none": "none", "null": "none", "custom": "none", "na": "none",
}


def parse_objective_alias(name: str) -> str:
    name = name.strip().lower()
    if name in _OBJECTIVE_ALIASES:
        return _OBJECTIVE_ALIASES[name]
    return name


@dataclass
class Config:
    """Typed parameter set. Field names match reference parameter names.

    Groups follow the reference layout: Core, Learning Control, IO, Objective,
    Metric, Network, Device (reference: include/LightGBM/config.h regions).
    """
    # ---- Core ----
    config: str = ""
    task: str = "train"                 # train, predict, serve, online, convert_model, refit
    objective: str = "regression"
    boosting: str = "gbdt"              # gbdt, rf, dart, goss
    data: str = ""
    valid: List[str] = field(default_factory=list)
    num_iterations: int = 100
    learning_rate: float = 0.1
    num_leaves: int = 31
    tree_learner: str = "serial"        # serial, feature, data, voting
    num_threads: int = 0
    device_type: str = "tpu"            # cpu, tpu (reference: cpu, gpu)
    seed: int = 0

    # ---- Learning control ----
    max_depth: int = -1
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    bagging_fraction: float = 1.0
    pos_bagging_fraction: float = 1.0
    neg_bagging_fraction: float = 1.0
    bagging_freq: int = 0
    bagging_seed: int = 3
    feature_fraction: float = 1.0
    feature_fraction_bynode: float = 1.0
    feature_fraction_seed: int = 2
    early_stopping_round: int = 0
    first_metric_only: bool = False
    max_delta_step: float = 0.0
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0
    drop_rate: float = 0.1
    max_drop: int = 50
    skip_drop: float = 0.5
    xgboost_dart_mode: bool = False
    uniform_drop: bool = False
    drop_seed: int = 4
    top_rate: float = 0.2
    other_rate: float = 0.1
    min_data_per_group: int = 100
    max_cat_threshold: int = 32
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_to_onehot: int = 4
    top_k: int = 20
    monotone_constraints: List[int] = field(default_factory=list)
    feature_contri: List[float] = field(default_factory=list)
    max_bin_by_feature: List[int] = field(default_factory=list)
    forcedsplits_filename: str = ""
    forcedbins_filename: str = ""
    refit_decay_rate: float = 0.9
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0
    cegb_penalty_feature_lazy: List[float] = field(default_factory=list)
    cegb_penalty_feature_coupled: List[float] = field(default_factory=list)
    verbosity: int = 1

    # ---- IO ----
    max_bin: int = 255
    min_data_in_bin: int = 3
    bin_construct_sample_cnt: int = 200000
    histogram_pool_size: float = -1.0
    data_random_seed: int = 1
    output_model: str = "LightGBM_model.txt"
    snapshot_freq: int = -1
    input_model: str = ""
    output_result: str = "LightGBM_predict_result.txt"
    initscore_filename: str = ""
    valid_data_initscores: List[str] = field(default_factory=list)
    pre_partition: bool = False
    enable_bundle: bool = True
    max_conflict_rate: float = 0.0
    is_enable_sparse: bool = True
    sparse_threshold: float = 0.8
    use_missing: bool = True
    zero_as_missing: bool = False
    two_round: bool = False
    save_binary: bool = False
    header: bool = False
    label_column: str = ""
    weight_column: str = ""
    group_column: str = ""
    ignore_column: str = ""
    categorical_feature: str = ""
    predict_raw_score: bool = False
    predict_leaf_index: bool = False
    predict_contrib: bool = False
    num_iteration_predict: int = -1
    pred_early_stop: bool = False
    pred_early_stop_freq: int = 10
    pred_early_stop_margin: float = 10.0
    predict_disable_shape_check: bool = False
    convert_model_language: str = ""
    convert_model: str = "gbdt_prediction.cpp"

    # ---- Objective ----
    num_class: int = 1
    is_unbalance: bool = False
    scale_pos_weight: float = 1.0
    sigmoid: float = 1.0
    boost_from_average: bool = True
    reg_sqrt: bool = False
    alpha: float = 0.9
    fair_c: float = 1.0
    poisson_max_delta_step: float = 0.7
    tweedie_variance_power: float = 1.5
    max_position: int = 20
    lambdamart_norm: bool = True
    label_gain: List[float] = field(default_factory=list)

    # ---- Metric ----
    metric: List[str] = field(default_factory=list)
    metric_freq: int = 1
    is_provide_training_metric: bool = False
    eval_at: List[int] = field(default_factory=lambda: [1, 2, 3, 4, 5])
    multi_error_top_k: int = 1
    auc_mu_weights: List[float] = field(default_factory=list)

    # ---- Network ----
    num_machines: int = 1
    local_listen_port: int = 12400
    time_out: int = 120
    machine_list_filename: str = ""
    machines: str = ""

    # ---- Elastic multi-host fleet (fleet/ subsystem) ----
    tpu_fleet: int = 0                  # task=train gang size: launch
                                        # this many training ranks with
                                        # file/TCP rendezvous + elastic
                                        # lost-host recovery; 0/1 = off
    tpu_fleet_heartbeat_s: float = 30.0  # silence window (relative to
                                        # the other ranks' heartbeat
                                        # arrivals) before a rank is
                                        # classified dead; heartbeats
                                        # ride the fingerprint cadence —
                                        # no new sync points
    tpu_fleet_transport: str = "auto"   # auto = jax.distributed when the
                                        # backend runs cross-process
                                        # device collectives, else the
                                        # host-TCP CI-twin transport;
                                        # jax / host force one
    tpu_fleet_dir: str = ""             # rendezvous + fleet artifact
                                        # directory (rank logs, event
                                        # trail, default checkpoints);
                                        # empty = a fresh temp dir
    tpu_fleet_port: int = 0             # coordinator TCP port
                                        # (0 = ephemeral)
    tpu_fleet_min_ranks: int = 1        # abort instead of resuming when
                                        # survivors drop below this
    tpu_fleet_heal: bool = True         # relaunch a lost rank and fold
                                        # it back in at the next resize
    tpu_fleet_max_recoveries: int = 2   # elastic recoveries tolerated
                                        # per rank (and heals per
                                        # launcher) before aborting

    # ---- Device (reference gpu_* kept for compat; tpu_* are new) ----
    gpu_platform_id: int = -1
    gpu_device_id: int = -1
    gpu_use_dp: bool = False
    tpu_hist_dtype: str = "2xbf16"      # histogram matmul input precision,
                                        # by kernel-mode name: 2xbf16 =
                                        # hi/lo bf16 split (~16 mantissa
                                        # bits on g/h, f32 accum, 2 MXU
                                        # passes), highest = exact f32
                                        # (3 passes; also via gpu_use_dp),
                                        # bf16 = 1 pass (~8 bits).
                                        # int16 / int8 = QUANTIZED
                                        # accumulation (LightGBM 4.x's
                                        # quantized-training trick):
                                        # per-tree symmetric scales
                                        # computed on device, stochastic-
                                        # rounded integer g/h, exact
                                        # integer MXU accumulation (2 / 1
                                        # passes) with in-kernel f32
                                        # dequant before the split scan;
                                        # halves the per-row HBM vector
                                        # stream.  Wave-kernel path only
                                        # (mixed-width datasets fall back
                                        # to 2xbf16); f32 modes stay the
                                        # bit-exactness oracle.
                                        # Back-compat aliases: float32 ->
                                        # 2xbf16, bfloat16 -> bf16
    tpu_rank_device_eval: bool = True   # ranking eval path: true = the
                                        # device NDCG@k kernel over the
                                        # shared padded query blocks
                                        # (metric/rank.py — stable sort
                                        # per block, gain-discount
                                        # cumsum, per-k gather; one tiny
                                        # [len(eval_at)] D2H per eval
                                        # instead of the full [N] score
                                        # copy + ~per-query host loop);
                                        # false = the host per-query
                                        # loop (the differential oracle)
    tpu_block_rows: int = 1024          # Pallas histogram kernel row-block
    tpu_wave_capacity: int = 63         # leaves histogrammed per wave pass
                                        # (<= 63: the packed layout's
                                        # two result arrays of the
                                        # 128-lane Pallas kernel, filled
                                        # in MXU passes of 25 leaves)
    tpu_wave_gain_gate: float = 0.5     # split-phase throttle: only commit
                                        # leaves with gain >= gate * best
                                        # ready gain (1 = strict best-first
                                        # order, 0 = max wave throughput)
    tpu_compile_cache_dir: str = ""     # persistent XLA compilation-cache
                                        # directory: compiled growers
                                        # survive process restarts.  Yields
                                        # to $JAX_COMPILATION_CACHE_DIR;
                                        # "" means <checkout>/.jax_cache
                                        # (no cache on the CPU backend)
    tpu_mesh_shape: str = ""            # e.g. "data:8" or "data:4,feature:2"
    tpu_telemetry: str = ""             # structured-telemetry sink: a dir
                                        # (telemetry.{proc}.jsonl inside) or
                                        # a .jsonl path; same switch as the
                                        # LGBM_TPU_TELEMETRY env var
    tpu_health: str = ""                # training-health sentinels
                                        # (obs/health.py): "" off,
                                        # monitor = per-iteration numerics
                                        # guards + model fingerprints +
                                        # cross-rank divergence audit with
                                        # health/fingerprint telemetry
                                        # events, strict = additionally
                                        # abort on the first failure with
                                        # phase/node/feature attribution.
                                        # PROCESS-WIDE once on (like
                                        # tpu_telemetry); syncs the device
                                        # per iteration (LGBM_TPU_HEALTH
                                        # env var)
    tpu_fingerprint_freq: int = 1       # iterations between model-state
                                        # fingerprints (and the divergence
                                        # audit under multi-process
                                        # training) when tpu_health is on;
                                        # 0 disables fingerprinting
    tpu_profile: bool = False           # profile mode: sync-bracket every
                                        # phase/kernel, emit kernel_profile
                                        # roofline events + HBM memory
                                        # census (LGBM_TPU_PROFILE env).
                                        # PROCESS-WIDE once enabled (like
                                        # tpu_telemetry); breaks async
                                        # pipelining — attribution runs
                                        # only, never benchmarks
    tpu_xprof: bool = False             # measured-roofline capture
                                        # (obs/xprof.py): arm a windowed
                                        # jax.profiler trace around
                                        # tpu_xprof_iters mid-train
                                        # iterations (warmup/compile
                                        # iteration skipped), parse the
                                        # trace, attribute device ops by
                                        # lgbm/* scope and emit
                                        # kernel_measured roofline events
                                        # into the telemetry dir.
                                        # LGBM_TPU_XPROF env wins: 1/true
                                        # arms, a number > 1 sets the
                                        # window width, 0/false disarms
    tpu_xprof_iters: int = 3            # captured iterations per xprof
                                        # window when tpu_xprof is armed
                                        # (LGBM_TPU_XPROF=<n> overrides)
    tpu_trace: bool = False             # trace mode (obs/spans.py): emit
                                        # span events (trace_id/span_id/
                                        # parent_id, one schema for serve
                                        # requests AND training iteration
                                        # phases; export to Perfetto with
                                        # tools/trace_export.py).
                                        # PROCESS-WIDE once on; like
                                        # profile mode it sync-brackets
                                        # phases — attribution, never
                                        # benchmarks (LGBM_TPU_TRACE env)
    tpu_checkpoint_dir: str = ""        # fault-tolerance checkpoint
                                        # directory (robust/checkpoint.py):
                                        # when set, engine.train writes an
                                        # atomic versioned checkpoint
                                        # (forest + RNG + score state +
                                        # eval history) every
                                        # tpu_checkpoint_freq iterations
                                        # and RESUMES bit-exactly from the
                                        # newest valid one on restart;
                                        # "" disables checkpointing
    tpu_checkpoint_freq: int = 100      # boosting iterations between
                                        # checkpoints (0 = only the
                                        # preemption/wedge checkpoints);
                                        # used only with
                                        # tpu_checkpoint_dir set
    tpu_checkpoint_keep: int = 3        # newest checkpoints retained;
                                        # older ones are pruned after
                                        # each successful save
    tpu_on_device_error: str = "retry"  # device-wedge policy
                                        # (robust/watchdog.py): retry =
                                        # re-dispatch transient failures
                                        # with bounded exponential
                                        # backoff + seeded jitter, abort
                                        # on fatal; abort = fail fast
                                        # (flight dump + boundary
                                        # checkpoint + DeviceWedgedError);
                                        # fallback = after the dump/
                                        # checkpoint, re-execute the step
                                        # on the CPU backend and continue
                                        # (best-effort)
    tpu_watchdog: bool = False          # arm the device-wedge watchdog
                                        # for this trainer even without
                                        # faults injected: every device
                                        # step is synced + guarded
                                        # (classify/retry/stall heartbeat)
                                        # — trades the async-dispatch
                                        # overlap for fail-safety, like
                                        # health mode trades it for
                                        # certainty
    tpu_device_retries: int = 3         # bounded retry budget for
                                        # transient device failures
                                        # (watchdog policy retry/fallback)
    tpu_wedge_timeout_s: float = 0.0    # stall heartbeat deadline in
                                        # seconds; 0 = automatic (4x the
                                        # rolling per-step p99, floored
                                        # at 60s).  A step exceeding it
                                        # is stamped with a device_stall
                                        # event + flight dump (advisory:
                                        # a hung XLA call cannot be
                                        # interrupted from Python)
    tpu_flight_len: int = 256           # flight-recorder ring length:
                                        # the last N spans + operational
                                        # events kept in memory and
                                        # dumped as FLIGHT_rN.json on a
                                        # serve degradation, an overload
                                        # storm, a TrainingHealthError,
                                        # or GET /debug/flight; 0
                                        # disables (LGBM_TPU_FLIGHT env)
    tpu_train_metrics_port: int = -1    # live train introspection board
                                        # (obs/board.py): HTTP port for
                                        # GET /metrics + /progress +
                                        # /debug/flight during training.
                                        # -1 disables, 0 picks an
                                        # ephemeral port and logs it, >0
                                        # binds port+rank per process
                                        # (LGBM_TPU_TRAIN_METRICS env
                                        # wins: a port number, or
                                        # off/false to disarm)
    tpu_straggler_factor: float = 2.0   # live straggler detector: a rank
                                        # whose per-iteration hist/split
                                        # wall exceeds the fleet median
                                        # by this factor is suspect
                                        # (multi-process runs only)
    tpu_straggler_iters: int = 3        # consecutive suspect iterations
                                        # before a straggler event is
                                        # emitted (+ flight dump on
                                        # rank 0); 0 disables detection

    # ---- Serving (serve/ subsystem) ----
    tpu_serve_max_batch: int = 1024     # row cap per coalesced device
                                        # batch; requests pad to power-of-
                                        # two buckets, so the jitted
                                        # predictor compiles at most
                                        # ceil(log2(max_batch))+1 shapes
                                        # (LGBM_TPU_SERVE_MAX_BATCH env)
    tpu_serve_max_wait_ms: float = 2.0  # longest the microbatcher holds
                                        # the oldest queued request while
                                        # coalescing — the latency knob
                                        # (LGBM_TPU_SERVE_MAX_WAIT_MS env)
    tpu_serve_queue_depth: int = 8192   # queued-ROW bound: a full queue
                                        # rejects submits with an explicit
                                        # overload error (backpressure,
                                        # never OOM)
                                        # (LGBM_TPU_SERVE_QUEUE_DEPTH env)
    tpu_serve_host: str = "127.0.0.1"   # bind address for task=serve
    tpu_serve_port: int = 0             # task=serve HTTP port (0 = pick
                                        # an ephemeral port and log it)
    tpu_serve_reprobe_s: float = 30.0   # seconds between device
                                        # re-probes while a serving
                                        # session is degraded to the
                                        # host predictor: a successful
                                        # probe flips /health back from
                                        # "degraded" (probe-and-recover
                                        # instead of the old one-way
                                        # latch); 0 disables re-probing
                                        # (LGBM_TPU_SERVE_REPROBE_S env)
    tpu_serve_slo_p99_ms: float = 250.0  # serving p99 latency objective:
                                        # /metrics + /health report the
                                        # SLO-burn rate against it (the
                                        # fraction of recent requests
                                        # over the target divided by the
                                        # 1% budget a p99 allows; 1.0 =
                                        # burning at exactly the allowed
                                        # rate); 0 disables the gauge
                                        # (LGBM_TPU_SERVE_SLO_P99_MS env)

    # ---- Serving fleet (serve/router.py + serve/registry.py) ----
    tpu_serve_replicas: int = 2         # PredictorSession replicas per
                                        # model version behind the
                                        # router: per-device on a multi-
                                        # chip host, thread-pool
                                        # replicas on CPU — one wedged
                                        # replica costs capacity, not
                                        # availability
                                        # (LGBM_TPU_SERVE_REPLICAS env)
    tpu_serve_breaker_trip: int = 3     # consecutive transient failures
                                        # that open a replica's circuit
                                        # breaker (a FATAL failure opens
                                        # it immediately)
    tpu_serve_breaker_backoff_s: float = 0.5  # base of the breaker's
                                        # bounded exponential backoff:
                                        # how long an open breaker waits
                                        # before letting one half-open
                                        # probe request through
    tpu_serve_canary_rows: int = 64     # pinned probe-set rows the
                                        # canary gate scores on a swap
                                        # candidate (device-vs-host
                                        # parity + finite-output checks)
    tpu_serve_canary_probes: int = 16   # single-row latency probes the
                                        # canary gate times (p99
                                        # recorded in the swap report)
    tpu_serve_canary_p99_ms: float = 0.0  # reject a swap whose canary
                                        # p99 exceeds this; 0 = record
                                        # the p99 but never gate on it
                                        # (CI latency is too noisy to
                                        # gate by default)
    tpu_serve_rollback_watch_s: float = 30.0  # post-swap health-watch
                                        # window: the new live version's
                                        # metrics are monitored this
                                        # long and a regression triggers
                                        # AUTOMATIC rollback to the
                                        # still-resident previous
                                        # version; 0 disables the watch
                                        # (manual rollback still works)
                                        # (LGBM_TPU_SERVE_ROLLBACK_WATCH_S
                                        # env)
    tpu_serve_rollback_error_rate: float = 0.5  # post-swap failed-
                                        # request fraction (over the
                                        # watch window) that triggers
                                        # automatic rollback
    tpu_serve_rollback_degraded: int = 2  # post-swap degraded
                                        # transitions that trigger
                                        # automatic rollback (the new
                                        # version's device path keeps
                                        # dying)
    tpu_serve_rollback_slo_burn: float = 0.0  # post-swap SLO-burn rate
                                        # that triggers automatic
                                        # rollback; 0 = never gate the
                                        # rollback on burn
    tpu_serve_shed_low_frac: float = 0.5  # fraction of the queue-row
                                        # budget low-priority requests
                                        # may fill before being shed
                                        # (overload drops bulk traffic
                                        # first)
                                        # (LGBM_TPU_SERVE_SHED_LOW_FRAC
                                        # env)
    tpu_serve_shed_normal_frac: float = 0.85  # queue-budget fraction for
                                        # normal-priority requests
                                        # (high priority always owns
                                        # the full queue)
                                        # (LGBM_TPU_SERVE_SHED_NORMAL_FRAC
                                        # env)
    tpu_serve_retry_after_s: float = 1.0  # Retry-After header seconds on
                                        # shed (503) responses — when a
                                        # rejected client should come
                                        # back
    tpu_serve_swap_warmup: bool = True  # compile every bucket shape of
                                        # a swap candidate BEFORE the
                                        # atomic flip (the old version
                                        # keeps serving meanwhile), so
                                        # post-flip traffic never pays
                                        # the new forest's XLA compiles
                                        # — the zero-cold-start half of
                                        # zero-downtime; false flips
                                        # immediately after the canary
    tpu_serve_aot: bool = True          # arm the AOT executable store
                                        # when a directory is set: a
                                        # warmed store lets a cold
                                        # process serve request #1 with
                                        # ZERO JIT compiles (serve/
                                        # aot.py); false disarms without
                                        # unsetting the directory
    tpu_serve_aot_dir: str = ""         # AOT executable store directory
                                        # — serialized per-bucket
                                        # executables keyed by forest
                                        # content + backend + jax
                                        # version; empty = store off
                                        # (LGBM_TPU_SERVE_AOT_DIR env
                                        # wins)
    tpu_serve_arena_bytes: int = 0      # device-byte budget for the
                                        # multi-tenant forest arena
                                        # (serve/arena.py): admissions
                                        # past the budget LRU-evict the
                                        # coldest tenant (re-admitted
                                        # transparently on its next
                                        # request); 0 = unbounded
                                        # (LGBM_TPU_SERVE_ARENA_BYTES
                                        # env)

    # ---- Explanation serving (explain/ subsystem) ----
    tpu_explain: bool = True            # arm POST /explain and
                                        # PredictorSession.explain():
                                        # packs the per-node cover counts
                                        # + path metadata on FIRST use
                                        # (predict-only sessions never
                                        # pay the HBM cost); false
                                        # removes the endpoint
                                        # (LGBM_TPU_EXPLAIN env)
    tpu_explain_max_batch: int = 256    # row cap per coalesced device
                                        # TreeSHAP batch — its OWN pow2
                                        # bucket family, compiling at
                                        # most ceil(log2(max_batch))+1
                                        # shapes; smaller than predict's
                                        # because each row costs
                                        # O(leaves x depth^2)
                                        # (LGBM_TPU_EXPLAIN_MAX_BATCH env)
    tpu_explain_max_wait_ms: float = 5.0  # longest the explain
                                        # microbatcher holds the oldest
                                        # queued request while coalescing
                                        # (LGBM_TPU_EXPLAIN_MAX_WAIT_MS
                                        # env)

    # ---- Online learning (online/ subsystem) ----
    tpu_refit_device: bool = True       # leaf-refit path: true = the
                                        # device refit kernel (one
                                        # stacked leaf-index scan + a
                                        # jitted per-iteration segment-
                                        # sum/closed-form step,
                                        # online/refit.py); false = the
                                        # host per-tree bincount loop,
                                        # retained as the differential
                                        # oracle (per-leaf 1e-6 parity
                                        # pinned in tests/test_online.py)
    tpu_online_mode: str = "refit"      # task=online refresh strategy:
                                        # refit = re-estimate the frozen
                                        # forest's leaves over the
                                        # window (decay-mixed), continue
                                        # = boost tpu_online_trees NEW
                                        # trees in the model's own bin
                                        # space (no training-data
                                        # rebinning either way)
    tpu_online_window: int = 50000      # bounded ingest window: the
                                        # freshest labeled rows kept for
                                        # the next refresh; older rows
                                        # fall out (memory-bounded, like
                                        # the serve queue)
                                        # (LGBM_TPU_ONLINE_WINDOW env)
    tpu_online_refit_every: int = 5000  # row cadence: refresh after
                                        # this many newly ingested rows;
                                        # 0 = rows never trigger
                                        # (LGBM_TPU_ONLINE_REFIT_EVERY
                                        # env)
    tpu_online_refit_every_s: float = 0.0  # time cadence in seconds
                                        # (OR-composed with the row
                                        # cadence); a firing with no
                                        # fresh rows is an ingest stall:
                                        # skipped + logged + telemetry-
                                        # stamped, never a stale refit;
                                        # 0 = time never triggers
    tpu_online_trees: int = 10          # boosting rounds added per
                                        # refresh in continue mode
    tpu_online_decay: float = -1.0      # refit decay for the online
                                        # loop (new leaf = decay*old +
                                        # (1-decay)*refit); negative =
                                        # inherit refit_decay_rate
    tpu_online_model: str = "default"   # registry model name the loop
                                        # pushes refreshed versions to
                                        # (POST /models/{name}/swap)
    tpu_online_source: str = ""         # label stream for task=online: a
                                        # JSONL file of {"x": [...],
                                        # "y": <label>} lines ("" falls
                                        # back to data)
    tpu_online_follow: bool = False     # tail the stream for appended
                                        # lines instead of stopping at
                                        # EOF (the feeder-process mode)
    tpu_online_dir: str = ""            # where refreshed model versions
                                        # are written ("" = a fresh temp
                                        # directory)

    # ---- Out-of-core ingestion (ingest/ subsystem) ----
    tpu_ingest: bool = False            # task=train file loading routes
                                        # through the streaming ingest
                                        # subsystem (ingest/): two-pass
                                        # chunked readers (CSV/TSV,
                                        # LibSVM, .npy/.npz), seeded
                                        # reservoir bin-sampling over the
                                        # WHOLE stream, chunk-at-a-time
                                        # binning — the raw [N,F] f64
                                        # matrix is never materialized.
                                        # Bit-identical to the in-RAM
                                        # path given the same sample
                                        # (differential-test pinned)
    tpu_ingest_chunk_rows: int = 65536  # rows per streamed chunk for the
                                        # array/.npy/.npz/LibSVM readers
                                        # — the peak-raw-memory knob
                                        # (text files chunk by bytes via
                                        # the mmap windows).  Chunk size
                                        # never changes the constructed
                                        # dataset (test-pinned)
                                        # (LGBM_TPU_INGEST_CHUNK_ROWS env)
    tpu_ingest_memmap: str = ""         # back the binned matrix with an
                                        # np.memmap file instead of host
                                        # RAM: a directory (per-shard
                                        # X_bin.shardN.npy inside) or a
                                        # file path.  "" keeps the
                                        # matrix in RAM
                                        # (LGBM_TPU_INGEST_MEMMAP env)
    tpu_ingest_shards: int = 0          # row-shard plan: how many
                                        # contiguous shards the stream
                                        # splits into (query-aligned for
                                        # ranking data), each worker
                                        # binning ONLY its own rows.
                                        # 0/1 = no sharding
    tpu_ingest_shard_id: int = -1       # which shard THIS process bins;
                                        # -1 = the recorded process rank
                                        # (parallel/distributed.py)
    tpu_ingest_sample_seed: int = -1    # reservoir sampling seed for
                                        # streamed bin finding; -1 =
                                        # inherit data_random_seed (so
                                        # flipping tpu_ingest keeps the
                                        # sample schedule stable)

    # ---- Drift & quality monitoring (obs/drift.py + serve/quality.py) ----
    tpu_drift: bool = True              # arm serve-side drift monitoring
                                        # when a .quality.json profile
                                        # sits beside the loaded model
                                        # file; off = the session takes
                                        # one is-None branch and nothing
                                        # more (LGBM_TPU_DRIFT env)
    tpu_quality_profile: bool = True    # write the <model>.quality.json
                                        # reference profile (per-feature
                                        # bin occupancy + training
                                        # prediction histogram + train
                                        # AUC baseline) beside every
                                        # saved model that still has its
                                        # training dataset attached
    tpu_drift_sample_rate: float = 0.05  # fraction of served rows whose
                                        # raw features feed the drift
                                        # sketch (deterministic batch-
                                        # granularity sampling); the
                                        # prediction histogram is taken
                                        # on every response regardless
                                        # (LGBM_TPU_DRIFT_SAMPLE_RATE
                                        # env)
    tpu_drift_check_s: float = 30.0     # cadence for scoring the live
                                        # sketch against the reference
                                        # profile (PSI + KS) and
                                        # emitting drift_snapshot events
                                        # (LGBM_TPU_DRIFT_CHECK_S env)
    tpu_drift_min_rows: int = 200       # sketch rows required before a
                                        # cadence firing scores at all —
                                        # tiny samples make PSI scream
                                        # (LGBM_TPU_DRIFT_MIN_ROWS env)
    tpu_drift_psi_warn: float = 0.25    # PSI breach threshold (feature
                                        # max or prediction histogram):
                                        # above it the monitor dumps the
                                        # flight recorder and latches a
                                        # breach for the registry's
                                        # post-swap watch
                                        # (LGBM_TPU_DRIFT_PSI_WARN env)
    tpu_quality_window: int = 512       # labeled rows per rolling
                                        # quality window (windowed AUC /
                                        # NDCG / calibration error from
                                        # the online loop's label
                                        # stream) (LGBM_TPU_QUALITY_WINDOW
                                        # env)
    tpu_quality_drop_warn: float = 0.05  # AUC drop below the profile's
                                        # training baseline that counts
                                        # as a quality breach
                                        # (LGBM_TPU_QUALITY_DROP_WARN
                                        # env)
    tpu_serve_rollback_on_drift: bool = False  # opt-in: a drift/quality
                                        # breach during the post-swap
                                        # health watch triggers rollback
                                        # like an error-rate burn;
                                        # default only annotates the
                                        # watch report
                                        # (LGBM_TPU_SERVE_ROLLBACK_ON_DRIFT
                                        # env)

    # ---- derived (not user-settable) ----
    is_parallel: bool = dataclasses.field(default=False, repr=False)

    # ------------------------------------------------------------------
    @staticmethod
    def str2map(params_str: str) -> Dict[str, str]:
        """Parse a CLI/conf style ``key=value`` string list separated by
        whitespace (reference: Config::Str2Map, config.h:78)."""
        out: Dict[str, str] = {}
        for tok in params_str.split():
            Config.kv2map(out, tok)
        return out

    @staticmethod
    def kv2map(out: Dict[str, str], kv: str) -> None:
        if "=" not in kv:
            if kv.strip():
                log.warning("Unknown token '%s' ignored", kv)
            return
        k, v = kv.split("=", 1)
        k, v = k.strip(), v.strip()
        if k and not k.startswith("#"):
            out[k] = v

    @classmethod
    def from_params(cls, params: Optional[Dict[str, Any]]) -> "Config":
        cfg = cls()
        cfg.update(params or {})
        return cfg

    # ------------------------------------------------------------------
    def update(self, params: Dict[str, Any]) -> None:
        resolved: Dict[str, Any] = {}
        for key, value in params.items():
            canon = _ALIASES.get(key, key)
            if canon in resolved and key != canon:
                continue  # explicit canonical name wins over alias
            resolved[canon] = value
        fields = {f.name: f for f in dataclasses.fields(self)}
        for key, value in resolved.items():
            if key not in fields:
                log.warning("Unknown parameter: %s", key)
                continue
            setattr(self, key, _coerce(fields[key], value))
        self._post_process()

    def _post_process(self) -> None:
        log.set_verbosity(self.verbosity)
        self.objective = parse_objective_alias(self.objective)
        self.boosting = {"gbrt": "gbdt", "random_forest": "rf"}.get(self.boosting, self.boosting)
        self.tree_learner = {
            "serial_tree_learner": "serial", "feature_parallel": "feature",
            "feature_parallel_tree_learner": "feature", "data_parallel": "data",
            "data_parallel_tree_learner": "data", "voting_parallel": "voting",
            "voting_parallel_tree_learner": "voting", "voting_tree_learner": "voting",
        }.get(self.tree_learner, self.tree_learner)
        if self.tree_learner not in ("serial", "feature", "data", "voting"):
            log.fatal(f"Unknown tree learner type {self.tree_learner}")
        if self.device_type not in ("cpu", "tpu", "gpu"):
            log.fatal(f"Unknown device type {self.device_type}")
        if self.device_type == "gpu":
            # The reference's OpenCL device does not exist here; the TPU path is
            # its replacement (reference: src/treelearner/gpu_tree_learner.h).
            log.warning("device_type=gpu mapped to tpu in lightgbm_tpu")
            self.device_type = "tpu"
        self.is_parallel = self.tree_learner != "serial" or self.num_machines > 1
        # consistency checks (reference: Config::CheckParamConflict, config.cpp)
        if self.is_parallel and self.monotone_constraints:
            log.fatal("Cannot use monotone constraints in parallel learning")
        if not (0.0 < self.bagging_fraction <= 1.0):
            log.fatal("bagging_fraction should be in (0.0, 1.0]")
        if not (0.0 < self.feature_fraction <= 1.0):
            log.fatal("feature_fraction should be in (0.0, 1.0]")
        if not (0.0 < self.feature_fraction_bynode <= 1.0):
            log.fatal("feature_fraction_bynode should be in (0.0, 1.0]")
        if self.num_leaves < 2:
            log.fatal("num_leaves should be >= 2")
        if not (1 < self.max_bin <= 65535):
            log.fatal("max_bin should be in (1, 65535]")
        if self.boosting == "goss" and self.top_rate + self.other_rate > 1.0:
            log.fatal("top_rate + other_rate should be <= 1.0 for GOSS")
        if self.objective in ("multiclass", "multiclassova") and self.num_class < 2:
            log.fatal(f"num_class must be >= 2 for objective {self.objective}")
        if self.objective not in ("multiclass", "multiclassova", "none") and self.num_class != 1:
            log.fatal(f"num_class must be 1 for objective {self.objective}")
        if self.boosting == "rf":
            if self.bagging_freq <= 0 or not (0.0 < self.bagging_fraction < 1.0):
                log.fatal("bagging_freq and bagging_fraction (in (0,1)) are required for rf")
        if not (0.0 <= self.tpu_wave_gain_gate <= 1.0):
            log.fatal("tpu_wave_gain_gate should be in [0.0, 1.0]")
        if self.tpu_hist_dtype not in ("2xbf16", "bf16", "highest",
                                       "int16", "int8",
                                       "float32", "bfloat16"):
            log.fatal("tpu_hist_dtype should be 2xbf16, bf16, highest, "
                      "int16 or int8 "
                      "(aliases: float32 -> 2xbf16, bfloat16 -> bf16)")
        if self.tpu_hist_dtype in ("int16", "int8") \
                and self.num_leaves > 32000:
            log.fatal("quantized histogram modes carry leaf ids in the "
                      "int16 vector stream: num_leaves must be <= 32000")
        if self.tpu_wave_capacity < 1:
            log.fatal("tpu_wave_capacity should be >= 1")
        if self.tpu_block_rows < 128 or self.tpu_block_rows % 128 != 0:
            log.fatal("tpu_block_rows should be a positive multiple of 128 "
                      "(TPU lane-tile alignment)")
        # normalize the health-mode synonyms to the canonical "",
        # "monitor", "strict" via the ONE parser in obs/health.py —
        # unknown values are fatal on the parameter path (the env path
        # warns instead: it cannot raise at import time)
        from .obs.health import parse_mode
        self.tpu_health = parse_mode(self.tpu_health, fatal=True)
        if self.tpu_fingerprint_freq < 0:
            log.fatal("tpu_fingerprint_freq should be >= 0")
        if self.tpu_serve_max_batch < 1:
            log.fatal("tpu_serve_max_batch should be >= 1")
        if self.tpu_serve_max_wait_ms < 0:
            log.fatal("tpu_serve_max_wait_ms should be >= 0")
        if self.tpu_serve_queue_depth < self.tpu_serve_max_batch:
            log.fatal("tpu_serve_queue_depth should be >= "
                      "tpu_serve_max_batch")
        if not (0 <= self.tpu_serve_port <= 65535):
            log.fatal("tpu_serve_port should be in [0, 65535]")
        if self.tpu_serve_slo_p99_ms < 0:
            log.fatal("tpu_serve_slo_p99_ms should be >= 0")
        if self.tpu_serve_replicas < 1:
            log.fatal("tpu_serve_replicas should be >= 1")
        if self.tpu_serve_breaker_trip < 1:
            log.fatal("tpu_serve_breaker_trip should be >= 1")
        if self.tpu_serve_canary_rows < 1:
            log.fatal("tpu_serve_canary_rows should be >= 1")
        if not (0.0 <= self.tpu_serve_rollback_error_rate <= 1.0):
            log.fatal("tpu_serve_rollback_error_rate should be in [0, 1]")
        if not (0.0 <= self.tpu_serve_shed_low_frac <= 1.0):
            log.fatal("tpu_serve_shed_low_frac should be in [0, 1]")
        if not (0.0 <= self.tpu_serve_shed_normal_frac <= 1.0):
            log.fatal("tpu_serve_shed_normal_frac should be in [0, 1]")
        if self.tpu_serve_rollback_watch_s < 0:
            log.fatal("tpu_serve_rollback_watch_s should be >= 0")
        if self.tpu_serve_arena_bytes < 0:
            log.fatal("tpu_serve_arena_bytes should be >= 0")
        if self.tpu_explain_max_batch < 1:
            log.fatal("tpu_explain_max_batch should be >= 1")
        if self.tpu_explain_max_wait_ms < 0:
            log.fatal("tpu_explain_max_wait_ms should be >= 0")
        if self.tpu_flight_len < 0:
            log.fatal("tpu_flight_len should be >= 0")
        if not (-1 <= self.tpu_train_metrics_port <= 65535):
            log.fatal("tpu_train_metrics_port should be in [-1, 65535]")
        if self.tpu_straggler_factor <= 1.0:
            log.fatal("tpu_straggler_factor should be > 1")
        if self.tpu_straggler_iters < 0:
            log.fatal("tpu_straggler_iters should be >= 0")
        if self.tpu_on_device_error not in ("abort", "fallback", "retry"):
            log.fatal("tpu_on_device_error should be abort, fallback or "
                      "retry")
        if self.tpu_checkpoint_freq < 0:
            log.fatal("tpu_checkpoint_freq should be >= 0")
        if self.tpu_checkpoint_keep < 1:
            log.fatal("tpu_checkpoint_keep should be >= 1")
        if self.tpu_device_retries < 0:
            log.fatal("tpu_device_retries should be >= 0")
        if self.tpu_wedge_timeout_s < 0:
            log.fatal("tpu_wedge_timeout_s should be >= 0")
        if self.tpu_serve_reprobe_s < 0:
            log.fatal("tpu_serve_reprobe_s should be >= 0")
        if self.tpu_online_mode not in ("refit", "continue"):
            log.fatal("tpu_online_mode should be refit or continue")
        if self.tpu_online_window < 1:
            log.fatal("tpu_online_window should be >= 1")
        if self.tpu_online_refit_every < 0:
            log.fatal("tpu_online_refit_every should be >= 0")
        if self.tpu_online_refit_every_s < 0:
            log.fatal("tpu_online_refit_every_s should be >= 0")
        if self.tpu_online_trees < 1:
            log.fatal("tpu_online_trees should be >= 1")
        if self.tpu_online_decay > 1.0:
            log.fatal("tpu_online_decay should be <= 1 (negative = "
                      "inherit refit_decay_rate)")
        if (self.task == "online" and self.tpu_online_refit_every <= 0
                and self.tpu_online_refit_every_s <= 0):
            log.fatal("task=online needs a refresh cadence: set "
                      "tpu_online_refit_every (rows) and/or "
                      "tpu_online_refit_every_s (seconds)")
        if self.tpu_ingest_chunk_rows < 1:
            log.fatal("tpu_ingest_chunk_rows should be >= 1")
        if self.tpu_ingest_shards < 0:
            log.fatal("tpu_ingest_shards should be >= 0")
        if (self.tpu_ingest_shards > 1
                and self.tpu_ingest_shard_id >= self.tpu_ingest_shards):
            log.fatal("tpu_ingest_shard_id should be < tpu_ingest_shards "
                      "(or -1 for the process rank)")
        if self.tpu_fleet < 0:
            log.fatal("tpu_fleet should be >= 0")
        if self.tpu_fleet_heartbeat_s <= 0:
            log.fatal("tpu_fleet_heartbeat_s should be > 0 (seconds)")
        if self.tpu_fleet_transport not in ("auto", "jax", "host"):
            log.fatal("tpu_fleet_transport should be auto, jax or host")
        if self.tpu_fleet_min_ranks < 1:
            log.fatal("tpu_fleet_min_ranks should be >= 1")
        if self.tpu_fleet_max_recoveries < 0:
            log.fatal("tpu_fleet_max_recoveries should be >= 0")
        if not 0.0 <= self.tpu_drift_sample_rate <= 1.0:
            log.fatal("tpu_drift_sample_rate should be in [0, 1]")
        if self.tpu_drift_check_s <= 0:
            log.fatal("tpu_drift_check_s should be > 0")
        if self.tpu_drift_min_rows < 1:
            log.fatal("tpu_drift_min_rows should be >= 1")
        if self.tpu_drift_psi_warn <= 0:
            log.fatal("tpu_drift_psi_warn should be > 0")
        if self.tpu_quality_window < 1:
            log.fatal("tpu_quality_window should be >= 1")
        if self.tpu_quality_drop_warn <= 0:
            log.fatal("tpu_quality_drop_warn should be > 0")

    # ------------------------------------------------------------------
    def num_model_per_iteration(self) -> int:
        if self.objective in ("multiclass", "multiclassova"):
            return self.num_class
        return 1

    def to_params(self) -> Dict[str, Any]:
        out = {}
        for f in dataclasses.fields(self):
            if f.name == "is_parallel":
                continue
            v = getattr(self, f.name)
            if v != (f.default if f.default is not dataclasses.MISSING else None):
                out[f.name] = v
        return out


def _coerce(fld: dataclasses.Field, value: Any):
    """Coerce a raw parameter value (possibly a string from a conf file) to
    the field's declared type."""
    name = fld.name
    ftype = fld.type if isinstance(fld.type, str) else getattr(fld.type, "__name__", str(fld.type))
    is_list = "List" in ftype
    if is_list:
        if value is None:
            return []
        if isinstance(value, str):
            items = [x for x in value.replace(",", " ").split() if x]
        elif isinstance(value, (set, frozenset)):
            # sets are a documented reference idiom: {'l2', 'l1'}; sort
            # for a deterministic order — numerically when the values are
            # numeric (eval_at={5,10,20} must stay [5,10,20])
            try:
                items = sorted(value, key=float)
            except (TypeError, ValueError):
                items = sorted(value, key=str)
        elif isinstance(value, (list, tuple)):
            items = list(value)
        else:
            items = [value]
        if "int" in ftype:
            return [int(float(x)) for x in items]
        if "float" in ftype:
            return [float(x) for x in items]
        return [str(x) for x in items]
    if "bool" in ftype:
        if isinstance(value, str):
            return value.strip().lower() in ("true", "1", "yes", "+", "t")
        return bool(value)
    if ftype.startswith("int"):
        return int(float(value))
    if ftype.startswith("float"):
        return float(value)
    if name == "valid":  # declared List[str] but handled above
        return value
    return str(value)


def read_config_file(path: str) -> Dict[str, str]:
    """Parse a LightGBM ``train.conf``-style file: one ``key = value`` per
    line, ``#`` comments (reference: Application::LoadParameters)."""
    out: Dict[str, str] = {}
    with open(path, "r") as fh:
        for line in fh:
            line = line.split("#", 1)[0].strip()
            if not line or "=" not in line:
                continue
            k, v = line.split("=", 1)
            out[k.strip()] = v.strip()
    return out
