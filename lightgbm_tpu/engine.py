"""Training and cross-validation drivers (python-package/lightgbm/engine.py).

``train()`` mirrors engine.py:18-270: parameter normalization, callback
ordering (before/after iteration), early stopping via EarlyStopException,
evals_result recording.  ``cv()`` mirrors engine.py:375-580 with
group-aware / stratified / random folds and mean-stdv aggregation.
"""
from __future__ import annotations

import collections
import copy
import time
from typing import Any, Dict, List, Optional

import numpy as np

from . import callback, obs
from .basic import Booster, Dataset, LightGBMError
from .config import alias_transform
from .utils.compile_cache import enable_compilation_cache
from .utils.log import Log
from .obs import spans as _spans

__all__ = ["train", "cv", "serve", "serve_and_train", "CVBooster"]

_NUM_BOOST_ROUND_ALIASES = ("num_boost_round", "num_iterations", "num_iteration",
                            "n_iter", "num_tree", "num_trees", "num_round",
                            "num_rounds", "n_estimators")
_EARLY_STOP_ALIASES = ("early_stopping_round", "early_stopping_rounds",
                       "early_stopping", "n_iter_no_change")


def train(params: Dict[str, Any], train_set: Dataset, num_boost_round: int = 100,
          valid_sets: Optional[List[Dataset]] = None,
          valid_names: Optional[List[str]] = None,
          fobj=None, feval=None, init_model=None,
          feature_name="auto", categorical_feature="auto",
          early_stopping_rounds: Optional[int] = None,
          evals_result: Optional[Dict] = None, verbose_eval=True,
          learning_rates=None, keep_training_booster: bool = False,
          callbacks=None, checkpoint_prefix: Optional[str] = None,
          preemption_checkpoint: bool = False) -> Booster:
    """Train with given parameters; returns the trained Booster.

    ``checkpoint_prefix`` enables the fault-tolerant runtime: the full train
    state (model + RNG streams + score caches + early-stopping bookkeeping,
    lightgbm_tpu/checkpoint.py) is written atomically to
    ``<prefix>.ckpt_iter_<n>`` every ``snapshot_freq`` iterations (param;
    retention bounded by ``snapshot_keep``), and an interrupted run invoked
    again with the same prefix resumes bit-exactly from the newest valid
    checkpoint — corrupt/truncated files fall back to the previous good one.
    A call that completes removes its checkpoints (resume covers interrupted
    calls, not finished ones — continue a finished model via ``init_model``).
    Known limit: the ``early_stopping_rounds`` CALLBACK keeps its
    best-score/patience counters in a closure the checkpoint cannot reach,
    so they restart on resume (the resumed run may stop later than the
    uninterrupted one); the CLI / ``GBDT.train`` driver's internal
    early-stopping state rides the checkpoint and resumes bit-exactly.

    ``preemption_checkpoint=True`` (or the param of the same name) arms the
    SIGTERM/SIGINT preemption path: the handler sets a flag, the loop polls
    it at iteration boundaries, writes a leader-gated emergency checkpoint
    to ``checkpoint_prefix`` and raises
    :class:`~lightgbm_tpu.resilience.TrainingPreempted` — drivers convert
    that into exit code ``resilience.EXIT_PREEMPTED`` so a supervisor can
    tell resumable from failed.  ``watchdog_timeout_s > 0`` additionally
    arms the dispatch watchdog for the duration of the call.
    """
    params = copy.deepcopy(params) if params else {}
    for alias in _NUM_BOOST_ROUND_ALIASES:
        if alias in params:
            num_boost_round = int(params.pop(alias))
            Log.warning("Found `%s` in params. Will use it instead of argument",
                        alias)
    for alias in _EARLY_STOP_ALIASES:
        if alias in params:
            early_stopping_rounds = int(params.pop(alias))
            Log.warning("Found `%s` in params. Will use it instead of argument",
                        alias)
    first_metric_only = bool(params.get("first_metric_only", False))
    params.pop("first_metric_only", None)

    if fobj is not None:
        params["objective"] = "none"
    if num_boost_round <= 0:
        raise ValueError("num_boost_round should be greater than zero.")

    if not isinstance(train_set, Dataset):
        raise TypeError("Training only accepts Dataset object")
    if feature_name != "auto":
        train_set.feature_name = feature_name
    if categorical_feature != "auto":
        train_set.categorical_feature = categorical_feature
    params["num_iterations"] = num_boost_round

    # round-18 kernel planner: engage the persisted tuned-plan cache (the
    # plan_cache param, or the default location next to the XLA cache)
    # BEFORE the Booster constructs its tree learner — the learner
    # resolves its dispatch plan at construction.  No cache present (the
    # default) means analytic plans, byte-equal to the hand-tuned
    # constants; an unusable cache degrades to analytic with one warning
    # and the plan_cache_fallbacks counter.
    from .plan import state as _plan_state
    enable_compilation_cache()
    _plan_state.configure(
        str(alias_transform(dict(params)).get("plan_cache", "") or "")
        or None)

    booster = Booster(params=params, train_set=train_set)
    if init_model is not None:
        if isinstance(init_model, str):
            with open(init_model) as fh:
                model_str = fh.read()
        elif isinstance(init_model, Booster):
            model_str = init_model.model_to_string()
        else:
            raise TypeError("init_model should be a path or a Booster")
        booster._booster.load_model_from_string(model_str)
        booster._booster.reset_training_data(train_set.handle,
                                             booster._booster.objective)
        # replay the loaded model onto the training scores in one blocked
        # binned pass (core/predict_fused.py) instead of per-tree dispatches
        booster._booster.replay_train_score()
    init_iteration = booster._booster.num_init_iteration

    if valid_sets is not None:
        if isinstance(valid_sets, Dataset):
            valid_sets = [valid_sets]
        if valid_names is None:
            valid_names = []
        elif isinstance(valid_names, str):
            valid_names = [valid_names]
        for i, vs in enumerate(valid_sets):
            if vs is train_set:
                continue
            name = valid_names[i] if i < len(valid_names) else "valid_%d" % i
            if vs.reference is None:
                vs.set_reference(train_set)
            booster.add_valid(vs, name)
    is_valid_contain_train = valid_sets is not None and any(
        vs is train_set for vs in (valid_sets or []))
    train_data_name = "training"
    if is_valid_contain_train and valid_names:
        idx = [i for i, vs in enumerate(valid_sets) if vs is train_set]
        if idx and idx[0] < len(valid_names):
            train_data_name = valid_names[idx[0]]

    resumed_iter = 0
    if checkpoint_prefix is not None:
        # restore AFTER the valid sets are attached: their score caches ride
        # the checkpoint and are restored positionally
        resumed_iter = booster._booster.resume_from_checkpoint(
            checkpoint_prefix)

    callbacks = set() if callbacks is None else set(callbacks)
    if verbose_eval is True:
        callbacks.add(callback.print_evaluation())
    elif isinstance(verbose_eval, int) and verbose_eval > 0:
        callbacks.add(callback.print_evaluation(verbose_eval))
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        callbacks.add(callback.early_stopping(
            early_stopping_rounds, first_metric_only,
            verbose=bool(verbose_eval)))
    if learning_rates is not None:
        callbacks.add(callback.reset_parameter(learning_rate=learning_rates))
    if evals_result is not None:
        callbacks.add(callback.record_evaluation(evals_result))

    callbacks_before_iter = {cb for cb in callbacks
                             if getattr(cb, "before_iteration", False)}
    callbacks_after_iter = callbacks - callbacks_before_iter
    callbacks_before_iter = sorted(callbacks_before_iter,
                                   key=lambda cb: getattr(cb, "order", 0))
    callbacks_after_iter = sorted(callbacks_after_iter,
                                  key=lambda cb: getattr(cb, "order", 0))

    # telemetry: a telemetry_out param turns this run self-recording (JSONL
    # events + <out>.summary.json); a run configured by the caller
    # is recorded into but finalized by its owner.  Under a pod every rank
    # records into its own <out>.rank<k>.jsonl shard (obs.configure picks
    # the path) and only the leader writes the merged summary at finalize;
    # metrics_port > 0 additionally serves the run live over HTTP
    # (obs/exporter.py), with an in-memory run when telemetry_out is unset.
    t_out = str(getattr(booster.config, "telemetry_out", "") or "")
    m_port = int(getattr(booster.config, "metrics_port", 0))
    from .parallel.learners import is_write_leader
    if t_out or m_port > 0:
        tele = obs.configure(
            out=t_out or None,
            freq=int(getattr(booster.config, "telemetry_freq", 1)),
            metrics_port=m_port,
            metrics_addr=str(getattr(booster.config, "metrics_addr", "")
                             or "127.0.0.1"),
            alert_rules=str(getattr(booster.config, "alert_rules", "")
                            or "") or None,
            alert_interval_s=float(getattr(booster.config,
                                           "alert_interval_s", 1.0)),
            flight_recorder=bool(getattr(booster.config,
                                         "flight_recorder", False)),
            entry="engine.train")
        own_tele = True
    else:
        tele = obs.active()
        own_tele = False
    t_start = time.perf_counter()

    # resilience supervision (lightgbm_tpu/resilience.py): SIGTERM/SIGINT
    # -> flag -> emergency checkpoint + TrainingPreempted; a watchdog
    # timeout arms the stalled-dispatch monitor for this call
    from . import resilience
    preempt = bool(preemption_checkpoint) or bool(
        getattr(booster.config, "preemption_checkpoint", False))
    if preempt and checkpoint_prefix is None:
        Log.warning("preemption_checkpoint is set without a "
                    "checkpoint_prefix: a preempted run exits cleanly "
                    "but has nothing to resume from")
    owned_handler, own_wd = resilience.arm_supervision(
        preempt, float(getattr(booster.config, "watchdog_timeout_s", 0.0)),
        artifact_base=t_out or checkpoint_prefix)

    try:
        ckpt_freq = int(getattr(booster.config, "snapshot_freq", -1))
        if checkpoint_prefix is not None:
            write_ckpt = is_write_leader(booster._booster.mesh)
            if ckpt_freq <= 0:
                Log.warning(
                    "checkpoint_prefix is set but snapshot_freq is not (<= 0): "
                    "no checkpoints will be written — pass snapshot_freq in "
                    "params to choose the cadence")
        else:
            write_ckpt = False
        # pre-assign: the loop body may never run (num_boost_round=0, or a
        # resume that restored the final iteration) yet the epilogue reads it
        evaluation_result_list = []
        for i in range(init_iteration + resumed_iter,
                       init_iteration + num_boost_round):
            for cb in callbacks_before_iter:
                cb(callback.CallbackEnv(model=booster, params=params, iteration=i,
                                        begin_iteration=init_iteration,
                                        end_iteration=init_iteration + num_boost_round,
                                        evaluation_result_list=None))
            it_t0 = time.perf_counter() if tele is not None else 0.0
            finished = booster.update(fobj=fobj)
            if tele is not None and (i + 1 - init_iteration) % tele.freq == 0:
                dt_it = time.perf_counter() - it_t0
                n_rows = int(booster._booster.num_data)
                tele.histogram("iteration_dispatch_s").observe(dt_it)
                tele.histogram("chunk_rows_per_s").observe(
                    n_rows / dt_it if dt_it > 0 else 0.0)
                tele.event("iteration", iteration=int(i), dt_s=dt_it,
                           rows_per_s=(n_rows / dt_it if dt_it > 0 else 0.0))
            evaluation_result_list = []
            if valid_sets is not None or booster._booster.train_metrics:
                if is_valid_contain_train:
                    evaluation_result_list.extend(
                        [(train_data_name, m, v, h)
                         for (_, m, v, h) in booster.eval_train(feval)])
                evaluation_result_list.extend(booster.eval_valid(feval))
            try:
                for cb in callbacks_after_iter:
                    cb(callback.CallbackEnv(
                        model=booster, params=params, iteration=i,
                        begin_iteration=init_iteration,
                        end_iteration=init_iteration + num_boost_round,
                        evaluation_result_list=evaluation_result_list))
            except callback.EarlyStopException as earlyStopException:
                booster.best_iteration = earlyStopException.best_iteration + 1
                evaluation_result_list = earlyStopException.best_score
                break
            if (write_ckpt and ckpt_freq > 0
                    and booster._booster.iter_ % ckpt_freq == 0):
                # best-effort like every periodic durability write: a
                # disk-full checkpoint skip must not kill a healthy run
                from .checkpoint import save_checkpoint_best_effort
                save_checkpoint_best_effort(booster._booster,
                                            checkpoint_prefix)
            if preempt and resilience.preemption_requested():
                # ONE preempt-exit sequence for every driver: drain
                # in-flight device work, emergency checkpoint, consume the
                # flag, raise TrainingPreempted
                booster._booster._preempt_exit(checkpoint_prefix)
            if finished:
                break
        # the trailing < _poll_freq iterations' isfinite reductions
        # (nan_policy=raise) are only fetched by _poll_stop; drain them here so
        # a bad batch near the end still raises instead of returning NaN trees
        booster._booster._drain_nonfinite_checks()
        if write_ckpt:
            # this call COMPLETED (ran its rounds or stopped early): drop its
            # checkpoints so a rerun with the same prefix trains instead of
            # silently returning the finished run's model.  An interrupted call
            # never reaches this line — its checkpoints survive for the resume.
            from .checkpoint import cleanup_checkpoints
            cleanup_checkpoints(checkpoint_prefix)
        booster.best_score = collections.defaultdict(collections.OrderedDict)
        for data_name, eval_name, e_val, _ in (evaluation_result_list or []):
            booster.best_score[data_name][eval_name] = e_val
        if booster.best_iteration <= 0:
            booster.best_iteration = booster.current_iteration()
        if tele is not None:
            wall = time.perf_counter() - t_start
            b = booster._booster
            # iterations trained by THIS call (a checkpoint resume restored
            # `resumed_iter` of them before the loop; the wall covers only the
            # post-restore work, so must the iter count)
            iters_run = int(b.iter_) - int(resumed_iter)
            tele.gauge("train_rows").set(int(b.num_data))
            tele.gauge("train_iterations").set(iters_run)
            tele.gauge("train_wall_s").set(wall)
            if own_tele:
                from .obs.report import finalize_run
                finalize_run(tele, gbdt=b, wall_s=wall, iters=iters_run)
                # this call OWNS the run: close it so a later train() in the
                # same process (refits, CV loops, notebooks) doesn't append
                # events past run_end or clobber the headline gauges
                obs.disable()
        # reference exit-time dump at the end of the training driver too
        # (Log.debug-gated on verbosity)
        Log.debug("%s", _spans.summary())
        return booster
    finally:
        resilience.disarm_supervision(owned_handler, own_wd)
        # exception path (nan_policy=raise, user fobj/callback
        # errors): the owned run must not stay process-active —
        # close it so a later train() cannot leak into the artifact
        if own_tele and obs.active() is tele:
            obs.disable()



def _configure_owned_telemetry(cfg, entry: str):
    """Serving-entry telemetry bootstrap shared by :func:`serve` and
    :func:`serve_and_train`: when the params ask for a run
    (``telemetry_out`` and/or ``metrics_port``) and none is active,
    configure one owned by the caller (its Server finalizes + closes it).
    Returns the Telemetry or None."""
    t_out = str(getattr(cfg, "telemetry_out", "") or "")
    m_port = int(getattr(cfg, "metrics_port", 0))
    if not (t_out or m_port > 0) or obs.active() is not None:
        return None
    # metrics_port without telemetry_out still gets a (memory-sink) run:
    # the live scrape surface needs a registry to render
    return obs.configure(out=t_out or None,
                         freq=int(getattr(cfg, "telemetry_freq", 1)),
                         metrics_port=m_port,
                         metrics_addr=str(getattr(cfg, "metrics_addr", "")
                                          or "127.0.0.1"),
                         alert_rules=str(getattr(cfg, "alert_rules", "")
                                         or "") or None,
                         alert_interval_s=float(
                             getattr(cfg, "alert_interval_s", 1.0)),
                         flight_recorder=bool(
                             getattr(cfg, "flight_recorder", False)),
                         entry=entry)


def serve(models, params: Optional[Dict[str, Any]] = None, **server_kwargs):
    """Start a serving tier (lightgbm_tpu/serving) over one or many models.

    ``models`` is a Booster / GBDT / model-file path, or a dict of
    ``name -> one of those`` for multi-model residency.  ``params`` feeds
    the serving knobs (``max_batch_wait_us``, ``serve_residency_budget_mb``,
    ``serve_single_row_fast``, plus ``telemetry_out`` if the caller has not
    configured a run); extra keyword arguments go to
    :class:`~lightgbm_tpu.serving.Server` (e.g. ``max_queue_depth``).
    Returns the running :class:`~lightgbm_tpu.serving.Server` — submit with
    ``server.submit(name, rows)`` / ``server.predict``, republish with
    ``server.swap``, and ``server.close()`` when done (also a context
    manager)."""
    from .config import Config
    from .serving import Server

    cfg = Config(alias_transform(dict(params or {})))
    own_tele = _configure_owned_telemetry(cfg, "engine.serve")
    # tuned-plan cache (round 18): engaged before any predictor stacks so
    # the warmup compiles under the plan the run will serve with
    from .plan import state as _plan_state
    enable_compilation_cache()
    _plan_state.configure_from_config(cfg)
    server = None
    try:
        # the run stays open for telemetry_summary() reads while serving;
        # server.close() finalizes it into <telemetry_out>.summary.json and
        # releases the process-active slot (same ownership rule as
        # engine.train)
        server = Server(config=cfg, owned_telemetry=own_tele,
                        **server_kwargs)
        if not isinstance(models, dict):
            models = {"model": models}
        for name, model in models.items():
            if isinstance(model, str):
                from .boosting.gbdt import GBDT
                model = GBDT.load_model(model, cfg)
            server.register(name, model)
    except BaseException:
        # a failed construction/load/register must not leak the dispatcher
        # thread or hold the process-active telemetry slot hostage (no
        # summary is finalized for a run that never served)
        if server is not None:
            server.disown_telemetry()
            server.close(drain=False)
        if own_tele is not None and obs.active() is own_tele:
            obs.disable()
        raise
    return server


def serve_and_train(booster, train_set=None,
                    params: Optional[Dict[str, Any]] = None,
                    name: str = "model",
                    checkpoint_prefix: Optional[str] = None,
                    publish_out: Optional[str] = None,
                    warm=True, **server_kwargs):
    """Start the train-while-serve loop (lightgbm_tpu/online): one process
    that serves ``booster`` through the round-13 tier while a trainer
    thread ingests fresh labeled rows (``controller.ingest(X, y)``) and
    republishes each continued generation through ``ModelRegistry.swap``.

    ``booster`` is a Booster / GBDT / model-file path; ``train_set`` the
    base :class:`~lightgbm_tpu.io.dataset.BinnedDataset` (or
    :class:`Dataset`) whose bin layout every ingested window is binned
    against (defaults to the booster's attached training data).
    ``params`` feeds both the serving knobs and the ``online_*`` policy
    params (cadence ``online_min_rows``/``online_interval_s``, the drift
    trigger, the freshness SLO, ``online_rounds``/``online_update``);
    ``checkpoint_prefix`` arms the steady-state checkpoint path (cycle
    windows + snapshot/emergency checkpoints land under it, and a rerun
    resumes the preempted cycle), ``publish_out`` persists each published
    generation's model text so a restarted process warm-starts from the
    newest one.  Extra keyword arguments go to
    :class:`~lightgbm_tpu.serving.Server`.

    Returns the running
    :class:`~lightgbm_tpu.online.OnlineController` — submit with
    ``controller.submit(rows)``, feed with ``controller.ingest(X, y)``,
    and ``controller.close()`` when done (also a context manager)."""
    from .config import Config
    from .online import OnlineController
    from .serving import Server

    cfg = Config(alias_transform(dict(params or {})))
    own_tele = _configure_owned_telemetry(cfg, "engine.serve_and_train")
    from .plan import state as _plan_state
    enable_compilation_cache()
    _plan_state.configure_from_config(cfg)
    server = None
    try:
        server = Server(config=cfg, owned_telemetry=own_tele,
                        **server_kwargs)
        if isinstance(booster, str):
            from .boosting.gbdt import GBDT
            booster = GBDT.load_model(booster, cfg)
        if train_set is not None:
            construct = getattr(train_set, "construct", None)
            if construct is not None:
                train_set = construct()
            train_set = getattr(train_set, "handle", train_set)
        controller = OnlineController(
            server=server, name=name, booster=booster, base_ds=train_set,
            config=cfg, checkpoint_prefix=checkpoint_prefix,
            publish_out=publish_out, warm=warm)
        controller.start()
    except BaseException:
        # a failed construction must not leak the dispatcher thread or
        # hold the process-active telemetry slot hostage (same unwind as
        # engine.serve)
        if server is not None:
            server.disown_telemetry()
            server.close(drain=False)
        if own_tele is not None and obs.active() is own_tele:
            obs.disable()
        raise
    return controller


class CVBooster:
    """Ensemble of per-fold boosters (engine.py:277 _CVBooster)."""

    def __init__(self):
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def _append(self, booster: Booster) -> None:
        self.boosters.append(booster)

    def __getattr__(self, name):
        def handler_function(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]
        return handler_function


def _make_n_folds(full_data: Dataset, folds, nfold, params, seed,
                  fpreproc=None, stratified=True, shuffle=True,
                  eval_train_metric=False):
    full_data = full_data.construct()
    num_data = full_data.num_data()
    if folds is not None:
        if not hasattr(folds, "__iter__") and not hasattr(folds, "split"):
            raise AttributeError("folds should be a generator or iterator of "
                                 "(train_idx, test_idx) tuples or scikit-learn "
                                 "splitter object with split method")
        if hasattr(folds, "split"):
            group_info = full_data.get_group()
            if group_info is not None:
                group_info = np.asarray(group_info, dtype=np.int32)
                flatted_group = np.repeat(range(len(group_info)),
                                          repeats=group_info)
            else:
                flatted_group = np.zeros(num_data, dtype=np.int32)
            folds = folds.split(X=np.empty(num_data),
                                y=full_data.get_label(),
                                groups=flatted_group)
    else:
        if any(params.get(name) in {"lambdarank", "rank_xendcg"}
               for name in ("objective", "application")):
            # group-aware fold split (engine.py:313)
            group_info = np.asarray(full_data.get_group(), dtype=np.int32)
            num_group = len(group_info)
            group_kfold = _LGBMGroupKFold(n_splits=nfold)
            flatted_group = np.repeat(range(num_group), repeats=group_info)
            folds = group_kfold.split(np.empty(num_data), groups=flatted_group)
        elif stratified:
            labels = np.asarray(full_data.get_label())
            order = np.argsort(labels, kind="stable")
            folds_idx = [order[i::nfold] for i in range(nfold)]
            folds = [(np.setdiff1d(np.arange(num_data), fi), np.sort(fi))
                     for fi in folds_idx]
        else:
            if shuffle:
                randidx = np.random.RandomState(seed).permutation(num_data)
            else:
                randidx = np.arange(num_data)
            kstep = int(num_data / nfold)
            test_id = [randidx[i:i + kstep] for i in range(0, num_data, kstep)
                       ][:nfold]
            folds = [(np.setdiff1d(randidx, ti), np.sort(ti)) for ti in test_id]

    ret = CVBooster()
    for train_idx, test_idx in folds:
        train_subset = full_data.subset(sorted(train_idx))
        valid_subset = full_data.subset(sorted(test_idx))
        if fpreproc is not None:
            train_subset, valid_subset, tparam = fpreproc(
                train_subset, valid_subset, params.copy())
        else:
            tparam = params
        cvbooster = Booster(tparam, train_subset)
        if eval_train_metric:
            cvbooster.add_valid(train_subset, "train")
        cvbooster.add_valid(valid_subset, "valid")
        ret._append(cvbooster)
    return ret


class _LGBMGroupKFold:
    """Minimal GroupKFold (sklearn-compatible subset) for ranking cv."""

    def __init__(self, n_splits=5):
        self.n_splits = n_splits

    def split(self, X, y=None, groups=None):
        groups = np.asarray(groups)
        unique = np.unique(groups)
        for i in range(self.n_splits):
            test_groups = unique[i::self.n_splits]
            test_mask = np.isin(groups, test_groups)
            yield np.where(~test_mask)[0], np.where(test_mask)[0]


def _agg_cv_result(raw_results, eval_train_metric=False):
    cvmap = collections.OrderedDict()
    metric_type = {}
    for one_result in raw_results:
        for one_line in one_result:
            if eval_train_metric:
                key = "%s %s" % (one_line[0], one_line[1])
            else:
                key = one_line[1]
            metric_type[key] = one_line[3]
            cvmap.setdefault(key, [])
            cvmap[key].append(one_line[2])
    return [("cv_agg", k, np.mean(v), metric_type[k], np.std(v))
            for k, v in cvmap.items()]


def cv(params, train_set, num_boost_round=100, folds=None, nfold=5,
       stratified=True, shuffle=True, metrics=None, fobj=None, feval=None,
       init_model=None, feature_name="auto", categorical_feature="auto",
       early_stopping_rounds=None, fpreproc=None, verbose_eval=None,
       show_stdv=True, seed=0, callbacks=None, eval_train_metric=False,
       return_cvbooster=False):
    """Cross-validation; returns dict of 'metric-mean'/'metric-stdv' lists."""
    if not isinstance(train_set, Dataset):
        raise TypeError("Training only accepts Dataset object")
    params = copy.deepcopy(params) if params else {}
    for alias in _NUM_BOOST_ROUND_ALIASES:
        if alias in params:
            num_boost_round = int(params.pop(alias))
    for alias in _EARLY_STOP_ALIASES:
        if alias in params:
            early_stopping_rounds = int(params.pop(alias))
    first_metric_only = bool(params.pop("first_metric_only", False))
    if fobj is not None:
        params["objective"] = "none"
    if metrics is not None:
        params["metric"] = metrics
    params["num_iterations"] = num_boost_round
    if feature_name != "auto":
        train_set.feature_name = feature_name
    if categorical_feature != "auto":
        train_set.categorical_feature = categorical_feature

    results = collections.defaultdict(list)
    cvfolds = _make_n_folds(train_set, folds=folds, nfold=nfold,
                            params=params, seed=seed, fpreproc=fpreproc,
                            stratified=stratified, shuffle=shuffle,
                            eval_train_metric=eval_train_metric)

    callbacks = set() if callbacks is None else set(callbacks)
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        callbacks.add(callback.early_stopping(early_stopping_rounds,
                                              first_metric_only, verbose=False))
    if verbose_eval is True:
        callbacks.add(callback.print_evaluation(show_stdv=show_stdv))
    elif isinstance(verbose_eval, int) and verbose_eval > 0:
        callbacks.add(callback.print_evaluation(verbose_eval, show_stdv))
    callbacks_before_iter = sorted(
        (cb for cb in callbacks if getattr(cb, "before_iteration", False)),
        key=lambda cb: getattr(cb, "order", 0))
    callbacks_after_iter = sorted(
        (cb for cb in callbacks if not getattr(cb, "before_iteration", False)),
        key=lambda cb: getattr(cb, "order", 0))

    for i in range(num_boost_round):
        for cb in callbacks_before_iter:
            cb(callback.CallbackEnv(model=cvfolds, params=params, iteration=i,
                                    begin_iteration=0,
                                    end_iteration=num_boost_round,
                                    evaluation_result_list=None))
        for b in cvfolds.boosters:
            b.update(fobj=fobj)
        res = _agg_cv_result([b.eval_valid(feval) for b in cvfolds.boosters],
                             eval_train_metric)
        for _, key, mean, _, std in res:
            results[key + "-mean"].append(mean)
            results[key + "-stdv"].append(std)
        try:
            for cb in callbacks_after_iter:
                cb(callback.CallbackEnv(model=cvfolds, params=params,
                                        iteration=i, begin_iteration=0,
                                        end_iteration=num_boost_round,
                                        evaluation_result_list=res))
        except callback.EarlyStopException as earlyStopException:
            cvfolds.best_iteration = earlyStopException.best_iteration + 1
            for k in results:
                results[k] = results[k][:cvfolds.best_iteration]
            break
    if return_cvbooster:
        results["cvbooster"] = cvfolds
    return dict(results)
