"""Job kind ``train_chunks``: what the CLI's ``GBDT.train`` does.

Make data, bin it, build a booster, run one warm-up chunk, then call the fused
``train_chunk(K)`` — K trees in one XLA program — back to back until the
booster holds the traffic file's ``window_end_tree`` trees.  One unit of work
is one chunk, timed to ``block_until_ready`` of the training scores.  The
window is the same trees on every seed and every commit (later trees cost
more: their windows hold more rows), and its true length divides the rate.
``--seconds`` is only a ceiling there: once ``gbdt_job.CEILING`` times it
have passed, the window ends at the last finished chunk and the run says so.
A traffic file that states no ``window_end_tree`` runs chunks until
``--seconds`` have passed.

A ``--trace 1`` run has no such window: after the warm-up chunk it runs
chunks until the booster holds ``trace_first_tree`` trees, then
``trace_units`` chunks under the profiler, so that every commit traces the
same trees however fast it is.  ``unit_wall_ms_per_tree`` and
``recompiles_in_window`` of such a run are over the trees between the warm-up
and ``trace_first_tree``.

Traffic parameters (the cell's file): ``trees_per_chunk``, ``auc_trees``,
``trace_units``, ``trace_first_tree`` and ``window_end_tree`` (each the
warm-up chunk plus a whole number of chunks, at least one; a kind's subclass
or a test that never traces may leave the first out, one that goes by the
clock the second).
"""
from __future__ import annotations

import statistics
import traceback

import jax

import gbdt_job
from gbdt_job import clock
from trace_reduce import UNIT_ANNOTATION


class Job:
    def __init__(self, cfg, wl, seed, rehearse_rows=None):
        self.cfg, self.wl, self.seed = cfg, wl, seed
        self.rehearse_rows = rehearse_rows
        self.k = int(wl["trees_per_chunk"])
        self.auc_trees = int(wl["auc_trees"])
        self.trace_first_tree = gbdt_job.trace_first_tree(
            wl, warmup=self.k, unit=self.k)
        self.window_end_tree = gbdt_job.window_end_tree(
            wl, warmup=self.k, unit=self.k)
        self.host_timers = {}
        self.counters = {}
        self.attempted = self.failed = 0
        self.unit_walls = []
        self.traced_trees = []

    # ---- set-up: everything before the first timed dispatch ---------------

    def setup(self):
        from lightgbm_tpu import obs
        from lightgbm_tpu.boosting.gbdt import GBDT
        from lightgbm_tpu.config import Config
        from lightgbm_tpu.objective import create_objective

        gbdt_job.quiet()
        params = dict(self.cfg["params"])
        self.make_dataset(params)
        t0 = clock()
        config = Config(verbosity=-1, **params)
        self.gbdt = GBDT(config, self.dataset,
                         create_objective(params["objective"], config))
        self._unit()                                    # compile or cache load
        self.host_timers["first_unit_s"] = clock() - t0
        if self.failed:
            raise RuntimeError("the warm-up unit failed")
        self.score_after_warmup = self.gbdt.train_score  # a device reference
        self.attempted = 0
        self.unit_walls = []
        obs.recompile.reset()
        obs.launches.reset()

    def make_dataset(self, params):
        """Rows from the seed and the program's own binning of them:
        ``self.y``, ``self.Xh``, ``self.yh``, ``self.dataset`` and the host
        timers ``datagen_s`` and ``bin_s``.  A kind on other input replaces
        this."""
        from lightgbm_tpu.io.dataset import BinnedDataset
        t0 = clock()
        X, self.y, self.Xh, self.yh = gbdt_job.make_data(
            self.cfg, self.seed, self.rehearse_rows)
        self.host_timers["datagen_s"] = clock() - t0
        t0 = clock()
        self.dataset = BinnedDataset.from_matrix(
            X, label=self.y, max_bin=int(params["max_bin"]))
        self.host_timers["bin_s"] = clock() - t0

    def _unit(self):
        """One chunk of K trees, to the end of the device's work.  Failed when
        it raised or the booster did not advance by K iterations (a non-finite
        roll-back, or no leaf left to split)."""
        self.attempted += 1
        before = self.gbdt.iter_
        t0 = clock()
        try:
            with jax.profiler.TraceAnnotation(UNIT_ANNOTATION):
                stopped = self.gbdt.train_chunk(self.k)
                # as GBDT.train does after every chunk
                stopped = self.gbdt._guard_chunk_scores() or stopped
                self.gbdt.train_score.block_until_ready()
        except Exception:
            traceback.print_exc()
            stopped = True
        self.unit_walls.append(clock() - t0)
        ok = not stopped and self.gbdt.iter_ == before + self.k
        self.failed += 0 if ok else 1
        return ok

    # ---- the measured window, then the traced units -----------------------

    def run(self, seconds, tracer):
        self.t_start = clock()
        first_tree = self.gbdt.iter_
        while gbdt_job.untraced_goes_on(self, tracer, seconds,
                                        self.gbdt.iter_) and self._unit():
            pass
        self.t_end = clock()
        self.window_trees = self.gbdt.iter_ - first_tree
        gbdt_job.read_counters(self)
        self.host_timers["unit_wall_ms_per_tree"] = (
            1e3 * statistics.median(self.unit_walls) / self.k)
        chunks = self.attempted
        walls = " ".join("%.3f" % w for w in self.unit_walls)
        if tracer is not None and not self.failed:
            first = self.gbdt.iter_
            with tracer:
                for _ in range(int(self.wl["trace_units"])):
                    if not self._unit():
                        break
            self.traced_trees = self.gbdt.models[first:self.gbdt.iter_]
            walls += "; traced trees %d-%d in %s" % (
                first, self.gbdt.iter_ - 1,
                " ".join("%.3f" % w for w in self.unit_walls[chunks:]))
        print("window %.3f s: %d chunks of %d trees on %d rows, %d failed; "
              "seconds per chunk %s"
              % (self.t_end - self.t_start, chunks, self.k,
                 self.gbdt.num_data, self.failed, walls), flush=True)

    def program_temp_bytes(self):
        """Temporaries of the fused chunk program by the compiler's own
        analysis.  The TPU runtime keeps a program's temporaries outside the
        allocator's statistics: ``peak_bytes_in_use`` read 0.77 GB after
        training 10.5M rows, less than the row store the program builds."""
        import jax.numpy as jnp
        fused = next(iter(self.gbdt._fused_cache.values()))
        compiled = fused.lower(self.gbdt.train_score, (),
                               jnp.int32(0)).compile()   # a cache hit
        analysis = compiled.memory_analysis()
        print("fused chunk program, compiler's memory analysis: %s"
              % str(analysis).replace("\n", " "), flush=True)
        return int(analysis.temp_size_in_bytes)

    def end_to_end(self):
        return gbdt_job.end_to_end(self)

    def check(self):
        return gbdt_job.checks(self, must_stay_fused=True)
