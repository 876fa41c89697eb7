"""Run by hand, on the chip, with ``run.py``'s own arguments:

    python3 benchmarks/tests/controls_subsampled.py --workload higgs_prod_train \
        --seed <n> --seconds 20 --trace 0

First the cell's run exactly as ``run.py`` makes it (this file calls
``run.main``: same set-up, window, checks and result line).  Then, in the same
process and on the same binned table, so that the data and its binning are
paid once, the CONTROLS of the kind's checks: the program trains two chunks
from scratch with one fault put into a draw, and ``plain_first_splits``,
``mask_honoured`` and ``sampled_as_configured`` are asked again.  One of them
has to come out NOT ok for each (at 10.5M rows two bags of one table price a
split alike to a part in ten thousand, so a wrong bag of the right size is
seen far more surely by its count, which has to be the plain bag's to the
row, than by the splits it grows):

- ``mask_never_redrawn``: every tree searches the features of iteration 0's
  mask.  Tree 0 passes; tree K (the second chunk's first) must not, or some
  tree splits outside its own iteration's mask;
- ``bag_ignored``: every row is live in every tree, as if ``live`` lost the
  bag.  Tree 0 must already fail: its sums and counts are the whole table's;
- ``bag_never_redrawn``: every tree is grown on the bag of window 0.  Tree 0
  passes; tree K, whose window is another, must not, and every tree from
  iteration ``bagging_freq`` on counts another bag than its window's.

The last line is ``{"controls": {name: every check came out ok}}``; the exit
code is 1 when a control came out ok, which means the checks cannot see that
fault.  With ``--rehearse-rows`` the same on the CPU in interpret mode, for
the control flow and (``tests/test_subsampled_cell.py``) at a small size.
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


@contextlib.contextmanager
def _patched(name, faulty_of):
    from lightgbm_tpu.boosting import gbdt
    real = getattr(gbdt, name)
    setattr(gbdt, name, faulty_of(real))
    try:
        yield
    finally:
        setattr(gbdt, name, real)


def mask_never_redrawn():
    return _patched("feature_mask_of", lambda real: (
        lambda features, used, seed, it: real(features, used, seed, it * 0)))


def bag_ignored():
    import jax.numpy as jnp
    return _patched("_bag_mask", lambda real: (
        lambda row_ids, seed, it, freq, frac: jnp.ones(row_ids.shape,
                                                       jnp.float32)))


def bag_never_redrawn():
    return _patched("_bag_mask", lambda real: (
        lambda row_ids, seed, it, freq, frac: real(row_ids, seed, it * 0,
                                                   freq, frac)))


FAULTS = (mask_never_redrawn, bag_ignored, bag_never_redrawn)


def checks_under(job, fault):
    """{check: (it came out ok, what it found)} after two chunks trained on
    the job's own table with ``fault`` in the program's draw."""
    import jax
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.objective import create_objective
    job.gbdt = None
    gc.collect()
    jax.clear_caches()               # a traced draw must not be found again
    params = dict(job.cfg["params"])
    config = Config(verbosity=-1, **params)
    with fault():
        job.gbdt = GBDT(config, job.dataset,
                        create_objective(params["objective"], config))
        for _ in range(2):
            job.gbdt.train_chunk(job.k)
        job.gbdt.train_score.block_until_ready()
    job.copy_sampling_counts()
    return {"plain_first_splits": job.check_plain_splits(),
            "mask_honoured": job.check_masks(),
            "sampled_as_configured": job.check_sampling()}


def main():
    kind = importlib.import_module("kinds.train_chunks_sub")
    jobs = []

    class Job(kind.Job):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            jobs.append(self)
    kind.Job = Job
    run.main()                       # the cell's run and its result line
    job, = jobs

    from gbdt_job import clock
    came_out_ok = {}
    for fault in FAULTS:
        t0 = clock()
        found = checks_under(job, fault)
        came_out_ok[fault.__name__] = all(ok for ok, _ in found.values())
        for check, (ok, what) in found.items():
            print("control %s (%.1f s): %s %s: %s"
                  % (fault.__name__, clock() - t0, check,
                     "ok" if ok else "NOT ok", what), flush=True)
    print(json.dumps({"controls": came_out_ok}), flush=True)
    sys.exit(1 if any(came_out_ok.values()) else 0)


if __name__ == "__main__":
    main()
