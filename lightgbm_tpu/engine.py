"""Training/CV drivers (reference: python-package/lightgbm/engine.py:18,373)."""
from __future__ import annotations

import collections
from typing import Any, Dict, List, Optional

import numpy as np

from . import callback, obs
from .basic import Booster, Dataset
from .utils import log
from .utils.log import LightGBMError


def train(params: Dict[str, Any], train_set: Dataset,
          num_boost_round: int = 100,
          valid_sets: Optional[List[Dataset]] = None,
          valid_names: Optional[List[str]] = None,
          fobj=None, feval=None, init_model=None,
          feature_name="auto", categorical_feature="auto",
          early_stopping_rounds: Optional[int] = None,
          evals_result: Optional[Dict] = None,
          verbose_eval=True, learning_rates=None,
          keep_training_booster: bool = False,
          callbacks: Optional[List] = None) -> Booster:
    """Train a booster (reference: engine.py:18-250)."""
    params = dict(params or {})
    # persistent XLA compilation cache: configure before the Booster's
    # first jit compile (see utils/compile_cache.py for where it lives)
    from .utils.compile_cache import enable_compile_cache
    enable_compile_cache(params.get("tpu_compile_cache_dir") or None)
    for alias in ("num_boost_round", "num_iterations", "num_iteration",
                  "n_iter", "num_tree", "num_trees", "num_round", "num_rounds",
                  "n_estimators"):
        if alias in params:
            num_boost_round = int(params.pop(alias))
            log.warning(f"Found `{alias}` in params. Will use it instead of argument")
    if fobj is not None:
        params["objective"] = "none"

    if not isinstance(train_set, Dataset):
        raise TypeError(f"Training only accepts Dataset object, "
                        f"met {type(train_set).__name__}")
    if feature_name != "auto":
        train_set.feature_name = feature_name
    if categorical_feature != "auto":
        train_set.categorical_feature = categorical_feature

    init_trees = None
    init_model_desc = None
    if init_model is not None:
        # continued training (reference: boosting.cpp:35-69 — a model file
        # or Booster seeds the forest and scores before the first iteration)
        if isinstance(init_model, Booster):
            init_trees = list(init_model._gbdt.models)
            init_model_desc = (f"<in-memory Booster, {len(init_trees)} "
                               "tree(s)>")
        elif isinstance(init_model, (str, bytes)) or hasattr(init_model,
                                                             "__fspath__"):
            import os
            from .io.model_io import load_model_file
            init_model_desc = os.fsdecode(init_model)
            loaded, _ = load_model_file(init_model_desc)
            init_trees = list(loaded.models)
        else:
            raise TypeError("init_model should be a Booster or a model "
                            f"file path, met {type(init_model).__name__}")

    booster = Booster(params=params, train_set=train_set)
    # fault tolerance (robust/checkpoint.py): with tpu_checkpoint_dir
    # set, periodic atomic checkpoints + bit-exact resume from the
    # newest valid one.  The peek happens BEFORE init_model seeding —
    # a checkpoint (this run's own progress) supersedes the init model
    # it was itself seeded from.
    from .robust.checkpoint import CheckpointManager
    ckpt_mgr = CheckpointManager.from_config(booster.config)
    ckpt_peeked = ckpt_mgr.peek(booster.config) if ckpt_mgr else None
    if init_trees:
        if ckpt_peeked is not None:
            # both paths in ONE line: a stale-refresh incident (online
            # loop resuming over a leftover checkpoint when a fresher
            # init_model exists) is only debuggable if the log says
            # WHICH init model lost to WHICH checkpoint
            log.warning("init_model %s ignored: resuming from checkpoint "
                        "%s (a checkpoint is this run's own progress and "
                        "supersedes the init model it was seeded from; "
                        "delete the checkpoint directory to restart from "
                        "the init model)",
                        init_model_desc, ckpt_peeked[0])
        else:
            booster._gbdt.load_initial_models(init_trees)
    is_valid_contain_train = False
    train_data_name = "training"
    if valid_sets is not None:
        if isinstance(valid_sets, Dataset):
            valid_sets = [valid_sets]
        names = valid_names or []
        for i, vs in enumerate(valid_sets):
            name = names[i] if i < len(names) else f"valid_{i}"
            if vs is train_set:
                is_valid_contain_train = True
                train_data_name = name
                continue
            # valid sets must share the train set's bin mappers (reference:
            # engine.py:193 valid_data.set_reference(train_set)); add_valid
            # raises if vs was already constructed with different mappers
            if vs._handle is None:
                vs.reference = train_set
            booster.add_valid(vs, name)
    booster._train_data_name = train_data_name

    cbs = set(callbacks or [])
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        cbs.add(callback.early_stopping(early_stopping_rounds, verbose=bool(verbose_eval)))
    if verbose_eval is True:
        cbs.add(callback.print_evaluation())
    elif isinstance(verbose_eval, int) and verbose_eval > 0:
        cbs.add(callback.print_evaluation(verbose_eval))
    if learning_rates is not None:
        cbs.add(callback.reset_parameter(learning_rate=learning_rates))
    if evals_result is not None:
        cbs.add(callback.record_evaluation(evals_result))

    cbs_before = [c for c in cbs if getattr(c, "before_iteration", False)]
    cbs_after = [c for c in cbs if not getattr(c, "before_iteration", False)]
    cbs_before.sort(key=lambda c: getattr(c, "order", 0))
    cbs_after.sort(key=lambda c: getattr(c, "order", 0))

    # ---- checkpoint resume (robust/checkpoint.py) --------------------
    # Restore AFTER valid sets attach (their score slots must exist),
    # then replay the recorded eval history through the STATEFUL
    # callbacks so early stopping / record_evaluation continue exactly
    # mid-stream; display-only callbacks (skip_on_resume) stay silent.
    evaluation_result_list: List = []
    eval_history: List = []
    start_round = 0
    stopped_in_replay = False
    if ckpt_peeked is not None:
        resume = ckpt_mgr.resume(booster, ckpt_peeked)
        start_round = resume.iteration
        eval_history = list(resume.eval_history)
        # reconcile the callback-visible params with the restored state:
        # a reset_parameter(learning_rate=[...]) schedule compares the
        # scheduled value against env.params, and a fresh process's
        # params still hold the ORIGINAL learning rate — without this
        # the first resumed iteration would silently train at the
        # checkpoint's restored rate when the schedule says otherwise
        params["learning_rate"] = booster._gbdt.shrinkage_rate
        try:
            for it, entries in eval_history:
                env = callback.CallbackEnv(
                    model=booster, params=params, iteration=it,
                    begin_iteration=0, end_iteration=num_boost_round,
                    evaluation_result_list=entries)
                for cb in cbs_after:
                    if getattr(cb, "skip_on_resume", False):
                        continue
                    cb(env)
                evaluation_result_list = entries
        except callback.EarlyStopException as es:
            booster.best_iteration = es.best_iteration + 1
            evaluation_result_list = es.best_score
            stopped_in_replay = True

    # ---- graceful preemption (SIGTERM/SIGINT) ------------------------
    # Only armed while checkpointing is configured: the first signal
    # finishes the current iteration, writes a final checkpoint + flight
    # record, and re-raises; a second signal falls through to the
    # default handler (hard kill).
    import signal as _signal
    import threading as _threading
    preempted: Dict[str, int] = {}
    prev_handlers = {}
    arm_signals = (ckpt_mgr is not None
                   and _threading.current_thread()
                   is _threading.main_thread())
    if arm_signals:
        def _on_signal(signum, frame):
            preempted["sig"] = signum
            for s, h in prev_handlers.items():   # next signal acts default
                _signal.signal(s, h)
            log.warning("signal %d: finishing the current iteration, "
                        "then checkpointing and exiting (send again to "
                        "kill immediately)", signum)
        for s in (_signal.SIGTERM, _signal.SIGINT):
            try:
                prev_handlers[s] = _signal.signal(s, _on_signal)
            except (ValueError, OSError):   # non-main thread / platform
                prev_handlers.pop(s, None)

    completed = start_round
    if ckpt_mgr is not None:
        # the wedge hook: a fatal device error mid-iteration rolls back
        # to the iteration boundary and checkpoints it (eval_history is
        # captured by reference, so the hook always sees the latest).
        # Checkpoints are numbered by the ENGINE loop counter — under
        # init_model continue the trainer's iter_ includes the seeded
        # iterations, and saving under that number would shadow the
        # periodic checkpoints and make the resume skip the remaining
        # rounds (found by the fault matrix's crash-mid-continue leg)
        num_init = booster._gbdt.iter_ - start_round
        booster._gbdt._ckpt_hook = (
            lambda reason: ckpt_mgr.save(
                booster, booster._gbdt.iter_ - num_init,
                eval_history, reason=reason))
    # live train introspection board (obs/board.py): armed alongside
    # the telemetry sink when tpu_train_metrics_port /
    # LGBM_TPU_TRAIN_METRICS asks for it.  start_round anchors the
    # board at the trainer's CURRENT counter (checkpoint resume and
    # init_model continue both included), so /progress ETA measures
    # this run's live rate over the genuinely remaining rounds — never
    # wall-clock-since-boot after a crash-resume.
    from .obs import board as _board
    train_board = _board.maybe_start(
        booster.config,
        total_rounds=booster._gbdt.iter_ + (num_boost_round - start_round),
        start_round=booster._gbdt.iter_)
    if train_board is not None:
        train_board.set_provider("watchdog",
                                 booster._gbdt._guard.snapshot)
    try:
        for i in range(start_round, num_boost_round):
            if stopped_in_replay or preempted:
                break
            for cb in cbs_before:
                cb(callback.CallbackEnv(model=booster, params=params, iteration=i,
                                        begin_iteration=0,
                                        end_iteration=num_boost_round,
                                        evaluation_result_list=None))
            if booster.update(fobj=fobj):
                break  # can't split anymore
            completed = i + 1
            if i == start_round and obs.enabled():
                # where set-up went, once the first call has built its
                # programs (Booster.setup_trace names the keys)
                obs.event("setup_trace", **booster.setup_trace())
            evaluation_result_list = []
            # evaluate only when something consumes the result: attached valid
            # sets, or the train set explicitly requested via valid_sets
            # (the reference likewise skips evaluation without valid_sets —
            # a per-iteration metric pass costs an O(N) device sync)
            if booster.valid_sets or is_valid_contain_train:
                entries = booster._eval_all(feval,
                                            include_train=is_valid_contain_train)
                if is_valid_contain_train:
                    evaluation_result_list.extend(
                        e for e in entries if e[0] == train_data_name)
                evaluation_result_list.extend(
                    e for e in entries if e[0] != train_data_name)
            try:
                for cb in cbs_after:
                    cb(callback.CallbackEnv(model=booster, params=params,
                                            iteration=i, begin_iteration=0,
                                            end_iteration=num_boost_round,
                                            evaluation_result_list=evaluation_result_list))
            except callback.EarlyStopException as es:
                booster.best_iteration = es.best_iteration + 1
                evaluation_result_list = es.best_score
                break
            if ckpt_mgr is not None:
                eval_history.append((i, list(evaluation_result_list)))
                if ckpt_mgr.should_save(i + 1):
                    ckpt_mgr.save(booster, i + 1, eval_history)
    finally:
        if train_board is not None:
            train_board.stop()
        for s, h in prev_handlers.items():
            try:
                _signal.signal(s, h)
            except (ValueError, OSError):
                pass
    if preempted:
        ckpt_mgr.save(booster, completed, eval_history, reason="preempted")
        if obs.flight_enabled():
            obs.flight_dump("preempted")
        sig = preempted["sig"]
        log.warning("training preempted by signal %d at iteration %d; "
                    "checkpoint written to %s — rerun with the same "
                    "tpu_checkpoint_dir to resume", sig, completed,
                    ckpt_mgr.dir)
        if sig == _signal.SIGINT:
            raise KeyboardInterrupt
        raise SystemExit(128 + sig)

    booster.best_score = collections.defaultdict(collections.OrderedDict)
    for ds_name, mname, value, _ in (evaluation_result_list or []):
        booster.best_score[ds_name][mname] = value
    if not keep_training_booster:
        booster.free_dataset()
    return booster


class CVBooster:
    """Ensemble of per-fold boosters (reference: engine.py:253-278 _CVBooster)."""

    def __init__(self):
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def append(self, booster: Booster) -> None:
        self.boosters.append(booster)

    def __getattr__(self, name):
        def handler_function(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]
        return handler_function


def _make_n_folds(full_data: Dataset, folds, nfold: int,
                  stratified: bool, shuffle: bool, seed: int):
    """(reference: engine.py:281-341)."""
    # subset() needs the raw matrix, so keep it through construction
    full_data.free_raw_data = False
    full_data.construct()
    num_data = full_data.num_data()
    if folds is not None:
        if not hasattr(folds, "__iter__") and not hasattr(folds, "split"):
            raise AttributeError("folds should be a generator or iterator of "
                                 "(train_idx, test_idx) tuples or an object with a split method")
        if hasattr(folds, "split"):
            group_info = full_data.get_group()
            group = (np.repeat(np.arange(len(group_info)), group_info)
                     if group_info is not None else None)
            folds = folds.split(X=np.empty(num_data), y=full_data.get_label(),
                                groups=group)
        return list(folds)
    rng = np.random.default_rng(seed)
    if stratified:
        label = np.asarray(full_data.get_label())
        classes = np.unique(label)
        idx_per_fold = [[] for _ in range(nfold)]
        for c in classes:
            cidx = np.flatnonzero(label == c)
            if shuffle:
                cidx = rng.permutation(cidx)
            for i, chunk in enumerate(np.array_split(cidx, nfold)):
                idx_per_fold[i].extend(chunk.tolist())
        test_sets = [np.asarray(sorted(f)) for f in idx_per_fold]
    else:
        idx = rng.permutation(num_data) if shuffle else np.arange(num_data)
        test_sets = [np.sort(chunk) for chunk in np.array_split(idx, nfold)]
    out = []
    for i in range(nfold):
        test_idx = test_sets[i]
        mask = np.ones(num_data, dtype=bool)
        mask[test_idx] = False
        out.append((np.flatnonzero(mask), test_idx))
    return out


def _agg_cv_result(raw_results):
    """(reference: engine.py:344-370)."""
    cvmap = collections.OrderedDict()
    metric_type = {}
    for one_result in raw_results:
        for ds_name, mname, value, hib in one_result:
            key = f"{ds_name} {mname}"
            metric_type[key] = hib
            cvmap.setdefault(key, []).append(value)
    return [("cv_agg", k, float(np.mean(v)), metric_type[k], float(np.std(v)))
            for k, v in cvmap.items()]


def cv(params: Dict[str, Any], train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True, shuffle: bool = True,
       metrics=None, fobj=None, feval=None, init_model=None,
       feature_name="auto", categorical_feature="auto",
       early_stopping_rounds: Optional[int] = None, fpreproc=None,
       verbose_eval=None, show_stdv: bool = True, seed: int = 0,
       callbacks: Optional[List] = None, eval_train_metric: bool = False,
       return_cvbooster: bool = False):
    """Cross-validation (reference: engine.py:373-580)."""
    if not isinstance(train_set, Dataset):
        raise TypeError(f"Training only accepts Dataset object, "
                        f"met {type(train_set).__name__}")
    params = dict(params or {})
    from .utils.compile_cache import enable_compile_cache
    enable_compile_cache(params.get("tpu_compile_cache_dir") or None)
    for alias in ("num_boost_round", "num_iterations", "num_iteration",
                  "n_iter", "num_tree", "num_trees", "num_round", "num_rounds",
                  "n_estimators"):
        if alias in params:
            num_boost_round = int(params.pop(alias))
    if fobj is not None:
        params["objective"] = "none"
    if metrics is not None:
        params["metric"] = metrics
    if train_set.data is None:
        raise LightGBMError("cv needs raw data; construct Dataset with "
                            "free_raw_data=False")

    results = collections.defaultdict(list)
    cvfolds = _make_n_folds(train_set, folds, nfold, stratified,
                            shuffle, seed)
    boosters = CVBooster()
    full = train_set
    for train_idx, test_idx in cvfolds:
        tr = full.subset(train_idx)
        if fpreproc is not None:
            va_raw = full.subset(test_idx)
            tr, va_raw, params = fpreproc(tr, va_raw, params.copy())
            va = va_raw
        else:
            va = full.subset(test_idx)
            va.reference = tr
        bst = Booster(params=params, train_set=tr)
        bst.add_valid(va, "valid")
        if eval_train_metric:
            bst._train_data_name = "train"
        boosters.append(bst)

    cbs = set(callbacks or [])
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        cbs.add(callback.early_stopping(early_stopping_rounds, verbose=False))
    if verbose_eval is True:
        cbs.add(callback.print_evaluation(show_stdv=show_stdv))
    elif isinstance(verbose_eval, int) and verbose_eval:
        cbs.add(callback.print_evaluation(verbose_eval, show_stdv))
    cbs_before = sorted([c for c in cbs if getattr(c, "before_iteration", False)],
                        key=lambda c: getattr(c, "order", 0))
    cbs_after = sorted([c for c in cbs if not getattr(c, "before_iteration", False)],
                       key=lambda c: getattr(c, "order", 0))

    for i in range(num_boost_round):
        for cb in cbs_before:
            cb(callback.CallbackEnv(model=boosters, params=params, iteration=i,
                                    begin_iteration=0,
                                    end_iteration=num_boost_round,
                                    evaluation_result_list=None))
        fold_results = []
        for bst in boosters.boosters:
            bst.update(fobj=fobj)
            entries = bst.eval_valid(feval)
            if eval_train_metric:
                entries = bst.eval_train(feval) + entries
            fold_results.append(entries)
        res = _agg_cv_result(fold_results)
        for _, key, mean, _, std in res:
            results[key + "-mean"].append(mean)
            results[key + "-stdv"].append(std)
        try:
            for cb in cbs_after:
                cb(callback.CallbackEnv(model=boosters, params=params,
                                        iteration=i, begin_iteration=0,
                                        end_iteration=num_boost_round,
                                        evaluation_result_list=res))
        except callback.EarlyStopException as es:
            boosters.best_iteration = es.best_iteration + 1
            for bst in boosters.boosters:
                bst.best_iteration = boosters.best_iteration
            for k in results:
                results[k] = results[k][:boosters.best_iteration]
            break

    out = dict(results)
    if return_cvbooster:
        out["cvbooster"] = boosters
    return out
