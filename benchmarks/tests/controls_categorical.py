"""Run by hand, on the chip, with ``run.py``'s own arguments:

    python3 benchmarks/tests/controls_categorical.py --workload expo_cat_train \
        --seed <n> --seconds 20 --trace 0

First the cell's run exactly as ``run.py`` makes it (this file calls
``run.main``: same set-up, window, checks and result line).  Then, in the same
process and on the same table, so that the data and its ingest are paid once,
the CONTROLS of the checks the kind adds: the program trains two more chunks
from scratch with a fault put in, and the checks are asked again.  At least
one of them has to come out NOT ok for each fault:

- ``cat_l2_zero_in_the_search``: the program searches and sets its leaf
  values under ``cat_l2=0`` while the configuration, and so the plain
  reference, says 10.  On ten million rows the first splits' sums are far too
  large for 10 to move a choice or a recorded gain past its limit
  (``plain_first_splits`` stays ok there, and says by how little); the leaves
  a late many-vs-many split makes are small enough: ``plain_leaf_values``;
- ``a_left_bin_flipped_before_routing``: the split kernel routes every
  categorical split by the left bin set the tree records with bin 0's bit
  flipped, as a bitset word lost between the search and the kernel's scalars
  would make it.  The children then hold other rows than the tree says:
  ``plain_first_splits`` (their next splits, their gains) and
  ``plain_leaf_values``.

The last line is ``{"controls": {fault: every check came out ok}}``; the exit
code is 1 when a fault left every check ok, which means they cannot see it.
With ``--rehearse-rows`` the same on the CPU in interpret mode
(``tests/test_categorical_table.py`` at a small size).
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

# build_tree_partitioned's scalars of a split (core/tree_learner.py,
# ``tree.split``): twelve words, the ninth says categorical, then the left
# bin set's words
IS_CATEGORICAL, BITSET = 8, 12


@contextlib.contextmanager
def cat_l2_zero_in_the_search(params):
    params["cat_l2"] = 0
    yield


@contextlib.contextmanager
def a_left_bin_flipped_before_routing(params):
    from lightgbm_tpu.core import tree_learner
    real = tree_learner.partition_hist_pallas

    def faulty(rows, scal, **how):
        flipped = scal[BITSET] ^ (scal[IS_CATEGORICAL] & 1)
        return real(rows, scal.at[BITSET].set(flipped), **how)
    tree_learner.partition_hist_pallas = faulty
    try:
        yield
    finally:
        tree_learner.partition_hist_pallas = real


FAULTS = (cat_l2_zero_in_the_search, a_left_bin_flipped_before_routing)


def checks_under(job, fault):
    """{check: (it came out ok, what it found)} after two chunks trained on
    the job's own table with ``fault`` in the program."""
    import jax
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.objective import create_objective
    job.gbdt = None
    gc.collect()
    jax.clear_caches()               # a traced build must not be found again
    params = dict(job.cfg["params"])
    with fault(params):
        config = Config(verbosity=-1, **params)
        job.gbdt = GBDT(config, job.dataset,
                        create_objective(params["objective"], config))
        for _ in range(2):
            job.gbdt.train_chunk(job.k)
        job.gbdt.train_score.block_until_ready()
    jax.clear_caches()               # ... nor the faulty one by a later build
    return {"plain_first_splits": job.check_plain_splits(),
            "plain_leaf_values": job.check_leaf_values(),
            "plain_walk": job.check_walk()}


def main():
    kind = importlib.import_module("kinds.train_chunks_cat")
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
