"""Run by hand, on the chips, with ``run.py``'s own arguments:

    python3 benchmarks/tests/controls_sharded.py --workload criteo_dp4_train \
        --seed <n> --seconds 20 --trace 1

First the cell's run exactly as ``run.py`` makes it (this file calls
``run.main``: same set-up, window, traced iterations, checks and result
line).  Then, in the same process and on the same binned table, so that the
data and its binning are paid once, the CONTROLS of ``plain_first_splits``:
the program is trained again for ``warmup_iters + 1`` trees with one fault put
in, and the check is asked again.  It has to come out NOT ok for each:

- ``hist_reduce_drops_a_chip``: chip 0's histogram is zeroed before every
  histogram reduce, so every split is chosen and priced from three quarters
  of the rows;
- ``score_update_skips_a_chip``: after every tree the score of chip 0's rows
  is put back to what it was, so from tree 1 on a quarter of the gradients
  are stale: tree 0 passes, the window's first tree must not.

The last line is ``{"controls": {name: the check came out ok}}``; the exit code
is 1 when a control came out ok, which means the check cannot see that fault.
With ``--rehearse-rows`` the same on virtual CPU devices (``XLA_FLAGS`` set by
hand), for the control flow only.
"""
import contextlib
import gc
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import run  # noqa: E402

T0 = run.T0     # this process's start, near enough: the kind prints set-up from it


@contextlib.contextmanager
def hist_reduce_drops_a_chip():
    import jax
    real = jax.lax.psum_scatter

    def faulty(x, axis_name, **how):
        keep = (jax.lax.axis_index(axis_name) != 0).astype(x.dtype)
        return real(x * keep, axis_name, **how)
    jax.lax.psum_scatter = faulty
    try:
        yield
    finally:
        jax.lax.psum_scatter = real


@contextlib.contextmanager
def score_update_skips_a_chip():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from lightgbm_tpu.boosting.gbdt import GBDT
    real = GBDT._add_tree_output

    def faulty(self, arrays, class_id):
        before = self.train_score
        real(self, arrays, class_id)
        rows = before.shape[1]
        stale = self.learner.shard_rows(
            np.arange(rows) < rows // jax.device_count())
        self.train_score = jnp.where(stale[None, :], before, self.train_score)
    GBDT._add_tree_output = faulty
    try:
        yield
    finally:
        GBDT._add_tree_output = real


def main():
    kind = importlib.import_module("kinds.train_api_sharded")
    jobs = []

    class Job(kind.Job):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            jobs.append(self)
    kind.Job = Job
    run.main()                       # the cell's run and its result line
    job, = jobs

    import jax
    import lightgbm_tpu as lgb
    from gbdt_job import clock
    came_out_ok = {}
    for fault in (hist_reduce_drops_a_chip, score_update_skips_a_chip):
        job.gbdt = None
        gc.collect()
        jax.clear_caches()           # a traced build must not be found again
        t0 = clock()
        with fault():
            booster = lgb.train(job.params, job.train_set,
                                num_boost_round=job.warmup + 1)
            job.gbdt = booster._booster
            job.gbdt.train_score.block_until_ready()
        trained = clock() - t0
        ok, found = job.check_plain_splits()
        came_out_ok[fault.__name__] = bool(ok)
        print("control %s (%d trees in %.1f s, the check %.1f s): "
              "plain_first_splits %s: %s"
              % (fault.__name__, job.gbdt.iter_, trained,
                 clock() - t0 - trained, "ok" if ok else "NOT ok", found),
              flush=True)
    print(json.dumps({"controls": came_out_ok}), flush=True)
    sys.exit(1 if any(came_out_ok.values()) else 0)


if __name__ == "__main__":
    main()
