"""Job kind ``train_api``: the Python entry point most users call.

ONE call of ``lightgbm_tpu.train(params, Dataset, num_boost_round=<huge>)``;
one unit of work is one boosting iteration (``Booster.update``).  The window is
cut out of that call by two callbacks: after ``warmup_iters`` iterations the
after-iteration callback waits for the device and starts the clock; once
``--seconds`` have passed it waits again, stops the clock and ends training
with ``EarlyStopException``.  Inside the window the host never waits for the
device, as a user's call does not.

A ``--trace 1`` run does not go by the clock: its untraced stretch ends when
the booster holds ``trace_first_tree`` trees, and the next ``trace_units``
iterations run under the profiler, so that every commit traces the same trees
however fast it is.  ``unit_wall_ms_per_tree`` and ``recompiles_in_window``
of such a run are over the trees between the warm-up and ``trace_first_tree``.

Traffic parameters: ``warmup_iters``, ``auc_trees``, ``trace_units``
(iterations traced, under one ``bench.unit`` span that ends when the device has
finished them), ``trace_first_tree`` (``warmup_iters`` plus a whole number of
``trace_units``, at least one; a mix that is never traced may leave it out).
"""
from __future__ import annotations

import jax

import gbdt_job
from gbdt_job import clock
from trace_reduce import UNIT_ANNOTATION


class Job:
    def __init__(self, cfg, wl, seed, rehearse_rows=None):
        self.cfg, self.wl, self.seed = cfg, wl, seed
        self.rehearse_rows = rehearse_rows
        self.warmup = int(wl["warmup_iters"])
        self.auc_trees = int(wl["auc_trees"])
        self.trace_first_tree = gbdt_job.trace_first_tree(
            wl, warmup=self.warmup, unit=int(wl["trace_units"]))
        self.host_timers = {}
        self.counters = {}
        self.attempted = self.failed = 0
        self.traced_trees = []

    def setup(self):
        """Data and the binned Dataset.  The booster and its warm-up belong to
        the one ``train`` call, so set-up goes on inside :meth:`run` until the
        clock starts."""
        import lightgbm_tpu as lgb
        gbdt_job.quiet()
        t0 = clock()
        X, self.y, self.Xh, self.yh = gbdt_job.make_data(
            self.cfg, self.seed, self.rehearse_rows)
        self.host_timers["datagen_s"] = clock() - t0
        self.params = dict(self.cfg["params"], verbosity=-1)
        t0 = clock()
        self.train_set = lgb.Dataset(X, label=self.y,
                                     params=self.params).construct()
        self.host_timers["bin_s"] = clock() - t0
        self.dataset = self.train_set.handle

    def run(self, seconds, tracer):
        import lightgbm_tpu as lgb
        from lightgbm_tpu import callback, obs
        job = self
        state = {"phase": "warmup", "t0": clock(), "span": None}

        def wait():
            job.gbdt.train_score.block_until_ready()

        def after_iteration(env):
            done = env.iteration + 1
            job.gbdt = env.model._booster
            if state["phase"] == "warmup":
                if done < job.warmup:
                    return
                wait()
                job.host_timers["first_unit_s"] = clock() - state["t0"]
                job.score_after_warmup = job.gbdt.train_score
                obs.recompile.reset()
                obs.launches.reset()
                state["phase"] = "window"
                job.t_start = clock()
            elif state["phase"] == "window":
                if gbdt_job.untraced_goes_on(job, tracer, seconds, done):
                    return
                wait()
                job.t_end = clock()
                job.window_trees = done - job.warmup
                gbdt_job.read_counters(job)
                if tracer is None:
                    raise callback.EarlyStopException(env.iteration, [])
                state["phase"], state["first"] = "trace", done
                tracer.__enter__()
                state["span"] = jax.profiler.TraceAnnotation(UNIT_ANNOTATION)
                state["span"].__enter__()
            elif done - state["first"] >= int(job.wl["trace_units"]):
                wait()
                state["span"].__exit__(None, None, None)
                tracer.__exit__(None, None, None)
                state["span"] = None
                job.traced_trees = job.gbdt.models[state["first"]:done]
                raise callback.EarlyStopException(env.iteration, [])

        try:
            lgb.train(self.params, self.train_set, num_boost_round=10 ** 6,
                      callbacks=[after_iteration])
        finally:
            if state["span"] is not None:      # training raised while tracing
                state["span"].__exit__(None, None, None)
                tracer.__exit__(None, None, None)
        if state["phase"] == "warmup" or not hasattr(self, "t_end"):
            raise RuntimeError("training ended before the window did "
                               "(phase %r)" % state["phase"])
        self.attempted = self.gbdt.iter_ - self.warmup
        self.host_timers["unit_wall_ms_per_tree"] = (
            1e3 * (self.t_end - self.t_start) / self.window_trees)
        traced = ""
        if self.traced_trees:
            traced = "; traced trees %d-%d" % (
                state["first"], state["first"] + len(self.traced_trees) - 1)
        print("window %.3f s: %d iterations of one tree on %d rows%s"
              % (self.t_end - self.t_start, self.window_trees,
                 self.gbdt.num_data, traced), flush=True)

    def end_to_end(self):
        return gbdt_job.end_to_end(self)

    def check(self):
        # not _fuse_failed: this path does not fuse iterations today, and the
        # cell exists to show that
        return gbdt_job.checks(self, must_stay_fused=False)
