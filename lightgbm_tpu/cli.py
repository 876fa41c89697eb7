"""Command-line application: ``python -m lightgbm_tpu config=train.conf``.

Counterpart of the reference CLI (src/main.cpp, src/application/application.cpp):
parameter precedence argv key=val over config-file lines (:49-82), task
dispatch train/predict/convert_model/refit (:204-260), rank-aware data loading
(:84-165), per-metric_freq evaluation logging, snapshots, and the
``LightGBM_predict_result.txt`` output format (predictor.hpp).
"""
from __future__ import annotations

import os
import sys
from typing import Dict, List, Optional

import numpy as np

from .boosting import create_boosting
from .boosting.gbdt import GBDT
from .config import Config, parse_config_file
from .io.loader import DatasetLoader
from .metric.metric import create_metrics
from .objective import create_objective
from .utils.compile_cache import enable_compilation_cache
from .utils.log import Log
from .obs import spans as _spans


def parse_args(argv: List[str]) -> Dict[str, str]:
    """argv ``k=v`` pairs + optional ``config=file`` (application.cpp:49-82);
    command-line values win over config-file values."""
    params: Dict[str, str] = {}
    for arg in argv:
        if "=" not in arg:
            Log.warning("Unknown argument %s", arg)
            continue
        k, v = arg.split("=", 1)
        params[k.strip()] = v.strip()
    if "config" in params:
        file_params = parse_config_file(params.pop("config"))
        for k, v in file_params.items():
            params.setdefault(k, v)
    return params


class Application:
    """CLI application (src/application/application.h)."""

    def __init__(self, argv: List[str]) -> None:
        self.params = parse_args(argv)
        self.config = Config(self.params)
        Log.reset_level(Log.level_from_verbosity(int(self.config.verbosity)))
        enable_compilation_cache()
        # round-18 kernel planner: the tuned-plan cache lives next to the
        # XLA compilation cache (plan_cache param overrides); absent =
        # analytic plans, unusable = analytic + one warning + the
        # plan_cache_fallbacks counter
        from .plan import state as _plan_state
        _plan_state.configure_from_config(self.config)

    def run(self) -> None:
        task = self.config.task
        if task == "train":
            self.train()
        elif task in ("predict", "prediction", "test"):
            self.predict()
        elif task == "convert_model":
            self.convert_model()
        elif task == "refit":
            self.refit()
        elif task == "serve":
            self.serve()
        elif task == "online":
            self.online()
        else:
            Log.fatal("Unknown task: %s", task)

    # ---- task=train (application.cpp:84-213) ----

    def _configure_telemetry(self):
        """Start a telemetry run when the config asks for one
        (``telemetry_out=...`` and/or a live scrape surface via
        ``metrics_port>0``); returns the Telemetry or None.  Under a pod
        each process records into its own ``<out>.rank<k>.jsonl`` shard
        (obs.configure resolves the rank) and only the leader writes the
        summary at finalize — ``tools/obs_report.py --merge`` reassembles
        the shards."""
        cfg = self.config
        t_out = str(getattr(cfg, "telemetry_out", "") or "")
        m_port = int(getattr(cfg, "metrics_port", 0))
        if not t_out and m_port <= 0:
            return None
        from . import obs
        return obs.configure(out=t_out or None,
                             freq=int(getattr(cfg, "telemetry_freq", 1)),
                             metrics_port=m_port,
                             metrics_addr=str(
                                 getattr(cfg, "metrics_addr", "")
                                 or "127.0.0.1"),
                             alert_rules=str(
                                 getattr(cfg, "alert_rules", "")
                                 or "") or None,
                             alert_interval_s=float(
                                 getattr(cfg, "alert_interval_s", 1.0)),
                             flight_recorder=bool(
                                 getattr(cfg, "flight_recorder", False)),
                             entry="cli", task=str(cfg.task))

    @staticmethod
    def _close_telemetry(tele):
        """Ownership backstop: close the CLI-owned run if it is still the
        process-active one (the success paths finalize + disable first; an
        exception mid-task must not leak the run into a later command)."""
        if tele is None:
            return
        from . import obs
        if obs.active() is tele:
            obs.disable()

    def _arm_resilience(self):
        """Install the supervision layer the config asks for: the
        SIGTERM/SIGINT preemption flag (``preemption_checkpoint=true``,
        task=train only) and the stalled-dispatch watchdog
        (``watchdog_timeout_s > 0``).  One shared policy with engine.train
        (resilience.arm_supervision); returns its ownership pair for
        :meth:`_disarm_resilience`."""
        from . import resilience
        cfg = self.config
        preempt = bool(getattr(cfg, "preemption_checkpoint", False)) \
            and cfg.task in ("train", "online")
        base = (str(getattr(cfg, "telemetry_out", "") or "")
                or cfg.output_model or None)
        return resilience.arm_supervision(
            preempt, float(getattr(cfg, "watchdog_timeout_s", 0.0)),
            artifact_base=base)

    def _disarm_resilience(self, owned_handler: bool, own_wd: bool) -> None:
        from . import resilience
        resilience.disarm_supervision(owned_handler, own_wd)

    def train(self) -> None:
        import time
        cfg = self.config
        tele = self._configure_telemetry()
        preempt, own_wd = self._arm_resilience()
        t_start = time.perf_counter()
        try:
            loader = DatasetLoader(cfg)
            num_machines = max(int(cfg.num_machines), 1)
            # pod rank resolution: under jax.distributed each host process
            # loads (and with data_chunk_rows, even SCANS) only its row
            # stripe; a single-process runtime keeps rank 0 and the
            # in-process multi-chip parallelism unchanged
            from .parallel.distdata import pod_info
            rank, pod = pod_info()
            if pod > 1:
                if int(cfg.num_machines) > 1 and int(cfg.num_machines) != pod:
                    Log.warning("num_machines=%d but the jax.distributed pod "
                                "has %d processes; using the pod size",
                                int(cfg.num_machines), pod)
                num_machines = pod
            else:
                rank = 0
            from .resilience import EXIT_PREEMPTED, TrainingPreempted
            try:
                train_data = loader.load_from_file(cfg.data, rank,
                                                   num_machines)
            except TrainingPreempted as exc:
                # mid-ingest preemption: nothing durable was written (the
                # binned store only hits disk via save_binary's atomic
                # rename AFTER the last chunk), so a rerun simply
                # re-ingests — same resumable exit code as training
                Log.warning("preempted during ingest (%s); exiting with "
                            "code %d (resumable: rerun re-ingests)", exc,
                            EXIT_PREEMPTED)
                raise SystemExit(EXIT_PREEMPTED)
            Log.info("Finished loading data: %d rows, %d features",
                     train_data.num_data, train_data.num_features)
            objective = create_objective(cfg.objective, cfg)
            booster = create_boosting(cfg.boosting, cfg, train_data, objective)
            # preemption recovery: when snapshots are enabled and a previous run
            # of this command left a checkpoint, resume it (newest VALID file —
            # a corrupt/truncated latest falls back to the previous good one).
            # Discovery happens up front so input_model loading is skipped, but
            # the restore itself waits until the valid sets are attached (their
            # score caches ride the checkpoint).
            ckpt_state = None
            resumable = (cfg.snapshot_freq > 0
                         or getattr(cfg, "preemption_checkpoint", False))
            if resumable and cfg.output_model:
                # preemption_checkpoint runs are resumable even without
                # periodic snapshots: the emergency checkpoint written at
                # SIGTERM is discovered the same way
                from .checkpoint import load_latest_checkpoint
                ckpt_state = load_latest_checkpoint(cfg.output_model)
            if ckpt_state is None and cfg.input_model:
                with open(cfg.input_model) as fh:
                    booster.load_model_from_string(fh.read())
                booster.reset_training_data(train_data, objective)
                # one blocked binned pass over the whole loaded model instead
                # of a per-tree device dispatch (core/predict_fused.py)
                booster.replay_train_score()
            if cfg.is_provide_training_metric:
                booster.add_train_metrics(create_metrics(cfg.metric, cfg))
            for i, valid_file in enumerate(cfg.valid or []):
                valid = loader.load_from_file(valid_file, reference=train_data)
                booster.add_valid_data(valid, "valid_%d" % (i + 1),
                                       create_metrics(cfg.metric, cfg))
            if ckpt_state is not None:
                from .checkpoint import restore_state
                restore_state(booster, ckpt_state)
            it_start = int(booster.iter_)  # nonzero on a checkpoint resume
            try:
                booster.train(snapshot_out=cfg.output_model)
            except TrainingPreempted as exc:
                # the emergency checkpoint is on disk (leader): exit with
                # the distinct code so a supervisor reruns this command to
                # resume instead of treating the run as failed
                Log.warning("%s; exiting with code %d (resumable)", exc,
                            EXIT_PREEMPTED)
                raise SystemExit(EXIT_PREEMPTED)
            from .parallel.learners import is_write_leader
            if is_write_leader(getattr(booster, "mesh", None)):
                # same leader-only write discipline as the in-loop snapshots:
                # d hosts must not race the final rename or the cleanup unlinks
                booster.save_model(cfg.output_model)
                if resumable and cfg.output_model:
                    # the run COMPLETED: drop its checkpoints so a rerun of
                    # this command trains fresh instead of resuming a finished
                    # run
                    from .checkpoint import cleanup_checkpoints
                    cleanup_checkpoints(cfg.output_model)
            if tele is not None:
                # GBDT.train recorded the run gauges; write
                # <telemetry_out>.summary.json — one flag made this run
                # self-recording.  The CLI owns the run: close it.
                from . import obs
                from .obs.report import finalize_run
                # iterations trained THIS process only: a resumed run's wall
                # excludes the pre-preemption work, so must its iter count
                finalize_run(tele, gbdt=booster,
                             wall_s=time.perf_counter() - t_start,
                             iters=int(booster.iter_) - it_start)
                obs.disable()
            if cfg.verbosity > 0:
                Log.debug("%s", _spans.summary())
        finally:
            self._disarm_resilience(preempt, own_wd)
            self._close_telemetry(tele)

    # ---- task=predict (application.cpp:215-252, predictor.hpp) ----

    @staticmethod
    def _write_result(path: str, out) -> None:
        """The LightGBM_predict_result.txt format (predictor.hpp), shared
        by task=predict and task=serve so their outputs stay comparable."""
        with open(path, "w") as fh:
            for row in np.atleast_1d(out):
                if np.ndim(row) == 0:
                    fh.write("%g\n" % row)
                else:
                    fh.write("\t".join("%g" % v for v in row) + "\n")

    def predict(self) -> None:
        cfg = self.config
        if not cfg.input_model:
            # validate BEFORE starting a telemetry run: Log.fatal raises,
            # and a run opened here would leak past the try/finally below
            Log.fatal("Need input_model for prediction task")
        tele = self._configure_telemetry()
        # the watchdog covers serving dispatch too (sharded_predict
        # collectives hang exactly like training ones on a dead peer)
        preempt, own_wd = self._arm_resilience()
        try:
            booster = GBDT.load_model(cfg.input_model, cfg)
            loader = DatasetLoader(cfg)
            X = loader.load_prediction_data(cfg.data)
            num_iter = int(cfg.num_iteration_predict)
            precision = str(cfg.predict_precision)
            if cfg.predict_leaf_index:
                # leaf routing is integer work with no lossy tier: indices
                # are identical under bf16, so a precision knob here would
                # only suggest a difference that cannot exist
                out = booster.predict_leaf_index(X, num_iter)
            elif cfg.predict_contrib:
                if precision != "exact":
                    # contributions have no lossy tier (additivity is the
                    # contract); silently upgrading would hide the knob
                    Log.fatal("predict_contrib has no bf16 tier — "
                              "predict_precision must be exact")
                out = booster.predict_contrib(X, num_iter)
            else:
                out = booster.predict(X, raw_score=bool(cfg.predict_raw_score),
                                      num_iteration=num_iter,
                                      precision=precision)
            self._write_result(cfg.output_result, out)
            Log.info("Finished prediction, wrote results to %s", cfg.output_result)
            if tele is not None:
                # per-bucket predict latencies + recompile counts ride the run
                from . import obs
                from .obs.report import finalize_run
                finalize_run(tele, extra={"rows_predicted": int(len(X))})
                obs.disable()
        finally:
            self._disarm_resilience(preempt, own_wd)
            self._close_telemetry(tele)

    # ---- task=serve (the round-13 serving tier over task=predict data) ----

    def serve(self) -> None:
        """Score ``data`` THROUGH the serving tier: rows are submitted as
        individual requests (micro-batches for large files), coalesced by
        the continuous-batching scheduler into the shape-bucket ladder, and
        written to ``output_result`` in the task=predict format — a CLI
        smoke of the whole serving stack whose telemetry run
        (``telemetry_out=...``) carries the serving SLO block.  Output is
        bit-identical to ``task=predict`` whenever predict takes the fused
        device path (>= 512 rows); below that predict's host small-batch
        path accumulates in f64, so scores agree to f32-rounding only.
        ``predict_contrib=true`` serves SHAP contributions instead: each
        request rides the scheduler with the per-request ``pred_contrib``
        knob (round 19), so explanations ship through the same
        continuous-batching ladder as scores."""
        import time
        cfg = self.config
        if not cfg.input_model:
            Log.fatal("Need input_model for serve task")
        if cfg.predict_leaf_index:
            # leaf indices are a different output format the serving tier
            # does not produce; silently writing scores instead would be
            # a data corruption.  (predict_contrib IS served: it rides
            # the scheduler as a per-request knob below.)
            Log.fatal("task=serve serves scores and pred_contrib; "
                      "predict_leaf_index is not supported — use "
                      "task=predict (or predict_leaf_index_binned via the "
                      "Python API for binned routing)")
        contrib = bool(cfg.predict_contrib)
        precision = str(cfg.predict_precision)
        if contrib and precision != "exact":
            # Server.submit rejects the combination per request; fail the
            # whole task up front instead of after N-1 good futures
            Log.fatal("predict_contrib has no bf16 tier — "
                      "predict_precision must be exact")
        tele = self._configure_telemetry()
        preempt, own_wd = self._arm_resilience()
        t_start = time.perf_counter()
        try:
            from .serving import Server
            booster = GBDT.load_model(cfg.input_model, cfg)
            loader = DatasetLoader(cfg)
            X = loader.load_prediction_data(cfg.data)
            server = Server(config=cfg)
            try:
                server.register("model", booster)
                # single-row requests exercise the coalescer (and the fast
                # path when serve_single_row_fast=true); very large files
                # fall back to micro-batches so the replay stays
                # O(batches) host work
                step = 1 if len(X) <= 8192 else 256
                num_iter = int(cfg.num_iteration_predict)
                futures = [server.submit(
                    "model", X[lo:lo + step],
                    raw_score=bool(cfg.predict_raw_score),
                    num_iteration=num_iter, pred_contrib=contrib,
                    precision=precision)
                    for lo in range(0, len(X), step)]
                outs = [f.result() for f in futures]
            finally:
                # a failed register/submit/result must not leak the
                # dispatcher thread (close is idempotent on the happy path)
                server.close()
            stats = server.stats()
            if stats["dropped"]:
                Log.fatal("serving replay dropped %d requests",
                          stats["dropped"])
            # a header-only prediction file serves zero requests; write the
            # same empty result task=predict produces
            out = (np.concatenate([np.atleast_1d(o) for o in outs])
                   if outs else np.zeros(0))
            self._write_result(cfg.output_result, out)
            Log.info("Served %d rows in %d requests / %d batches "
                     "(single-row fast: %d), wrote results to %s",
                     len(X), stats["submitted"], stats["batches"],
                     stats["single_row_fast"], cfg.output_result)
            if tele is not None:
                from . import obs
                from .obs.report import finalize_run
                finalize_run(tele, extra={
                    "rows_served": int(len(X)),
                    "serve_requests": int(stats["submitted"]),
                    "serve_batches": int(stats["batches"]),
                    "serve_wall_s": time.perf_counter() - t_start})
                obs.disable()
        finally:
            self._disarm_resilience(preempt, own_wd)
            self._close_telemetry(tele)

    # ---- task=online (the round-17 train-while-serve loop) ----

    def online(self) -> None:
        """One process that serves and trains: bootstrap (or load) a model
        over ``data``, start the serving tier + online trainer
        (lightgbm_tpu/online), then replay ``online_feed`` — a labeled
        file binned against the training layout — as BOTH serving
        requests and trainer ingest.  Scores land in ``output_result``
        (request order), every published generation is persisted to
        ``output_model``, and the cycle checkpoints ride the same prefix
        so a SIGTERM exits ``EXIT_PREEMPTED`` (75) and a rerun resumes
        the interrupted cycle before continuing the feed."""
        import time
        cfg = self.config
        tele = self._configure_telemetry()
        preempt, own_wd = self._arm_resilience()
        t_start = time.perf_counter()
        controller = None
        try:
            from .online import OnlineController
            from .resilience import EXIT_PREEMPTED, TrainingPreempted
            from .serving import Server
            loader = DatasetLoader(cfg)
            train_data = loader.load_from_file(cfg.data)
            Log.info("Finished loading data: %d rows, %d features",
                     train_data.num_data, train_data.num_features)
            objective = create_objective(cfg.objective, cfg)
            booster = create_boosting(cfg.boosting, cfg, train_data,
                                      objective)
            if cfg.input_model:
                with open(cfg.input_model) as fh:
                    booster.load_model_from_string(fh.read())
                # the controller's warm-start binding replays the loaded
                # model onto the training scores and aligns the clock
            else:
                booster.train()  # bootstrap: num_iterations rounds
            server = Server(config=cfg)
            prefix = cfg.output_model or None
            try:
                controller = OnlineController(
                    server=server, name="model", booster=booster,
                    base_ds=train_data, config=cfg,
                    checkpoint_prefix=prefix, publish_out=prefix)
                controller.start()
            except BaseException:
                server.close(drain=False)
                raise
            futures = []
            if getattr(cfg, "online_feed", ""):
                feed = loader.load_from_file(cfg.online_feed,
                                             reference=train_data)
                if feed.raw_data is None:
                    Log.fatal("online_feed must load with raw values "
                              "(dense input) to replay as requests")
                Xf = np.asarray(feed.raw_data, dtype=np.float32)
                yf = np.asarray(feed.metadata.label, dtype=np.float64)
                step = max(1, min(256, len(Xf) // 8 or 1))
                for lo in range(0, len(Xf), step):
                    if controller.preempted is not None:
                        break
                    futures.append(controller.submit(
                        Xf[lo:lo + step],
                        raw_score=bool(cfg.predict_raw_score)))
                    controller.ingest(Xf[lo:lo + step].astype(np.float64),
                                      yf[lo:lo + step])
                controller.flush(timeout=600.0)
            outs = [f.result() for f in futures]
            try:
                # surfaces a TrainingPreempted the trainer thread caught
                controller.wait(timeout=0.0)
            except TrainingPreempted as exc:
                # serving drained (accepted requests all completed above);
                # the emergency checkpoint + window are on disk: exit with
                # the distinct resumable code
                controller.close(drain=True)
                Log.warning("%s; exiting with code %d (resumable)", exc,
                            EXIT_PREEMPTED)
                raise SystemExit(EXIT_PREEMPTED)
            out = (np.concatenate([np.atleast_1d(o) for o in outs])
                   if outs else np.zeros(0))
            self._write_result(cfg.output_result, out)
            st = controller.stats()
            if st["serving"]["dropped"]:
                Log.fatal("online replay dropped %d requests",
                          st["serving"]["dropped"])
            Log.info("Online run: %d cycles (%d generations), %d rows "
                     "ingested, %d served requests, results in %s",
                     st["cycles"], st["generation"], st["rows_ingested"],
                     st["serving"]["submitted"], cfg.output_result)
            if tele is not None:
                from . import obs
                from .obs.report import finalize_run
                finalize_run(tele, gbdt=controller.booster,
                             wall_s=time.perf_counter() - t_start,
                             extra={"online_cli": st["cycles"]})
                obs.disable()
        finally:
            if controller is not None:
                controller.close()
            self._disarm_resilience(preempt, own_wd)
            self._close_telemetry(tele)

    # ---- task=convert_model (gbdt_model_text.cpp:87 ModelToIfElse) ----

    def convert_model(self) -> None:
        cfg = self.config
        if not cfg.input_model:
            Log.fatal("Need input_model for convert_model task")
        booster = GBDT.load_model(cfg.input_model, cfg)
        from .model_codegen import model_to_cpp
        code = model_to_cpp(booster)
        out = cfg.convert_model or "gbdt_prediction.cpp"
        with open(out, "w") as fh:
            fh.write(code)
        Log.info("Wrote converted model to %s", out)

    # ---- task=refit (application.cpp:216-252 + gbdt.cpp:299 RefitTree) ----

    def refit(self) -> None:
        cfg = self.config
        if not cfg.input_model:
            Log.fatal("Need input_model for refit task")
        loader = DatasetLoader(cfg)
        train_data = loader.load_from_file(cfg.data)
        objective = create_objective(cfg.objective, cfg)
        booster = create_boosting(cfg.boosting, cfg, train_data, objective)
        with open(cfg.input_model) as fh:
            booster.load_model_from_string(fh.read())
        booster.reset_training_data(train_data, objective)
        if train_data.raw_data is not None:
            # raw values available: route with exact v <= thr per node
            # (reference RefitTree semantics even for externally-trained
            # models whose thresholds are not this dataset's bin bounds)
            leaf_preds = booster.predict_leaf_index(
                np.asarray(train_data.raw_data), -1)
        else:
            # CSR-loaded datasets keep no raw matrix: route through the
            # BINNED fast path (bit-parity with raw routing whenever the
            # model's thresholds sit on this dataset's bin upper bounds,
            # i.e. it was trained on these mappers)
            leaf_preds = booster.predict_leaf_index_binned()
        booster.refit(leaf_preds)
        booster.save_model(cfg.output_model)
        Log.info("Finished refit, saved model to %s", cfg.output_model)


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv:
        print("Usage: python -m lightgbm_tpu config=<config file> [key=value ...]")
        return 1
    try:
        Application(argv).run()
    except Exception as exc:  # main.cpp:23-41 catch-all
        Log.warning("Met Exceptions:")
        Log.warning(str(exc))
        raise SystemExit(1)
    return 0
