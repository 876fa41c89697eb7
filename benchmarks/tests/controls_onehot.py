"""Run by hand, on the chip, with ``run.py``'s own arguments:

    python3 benchmarks/tests/controls_onehot.py --workload expo_onehot_train \
        --seed <n> --seconds 20 --trace 0

First the cell's run exactly as ``run.py`` makes it (this file calls
``run.main``: same set-up, window, checks and result line).  Then, in the same
process and on the same table, so that the data and its ingest are paid once,
the CONTROL of ``plain_first_splits``: the program trains one more chunk from
scratch with a fault put into the group layout, and the check is asked again.
It has to come out NOT ok:

- ``group_offsets_shifted_by_one``: every feature of a group of several reads
  and routes by the codes one bin above its own (``bin_offset + 1``: its
  neighbour's rows), as a wrong offset in ``_assign_group_layout`` or in the
  learner's ``unpack_lanes`` would make it.

The last line is ``{"controls": {name: the check came out ok}}``; the exit code
is 1 when the control came out ok, which means the check cannot see that fault.
With ``--rehearse-rows`` the same on the CPU in interpret mode, for the control
flow and (``tests/test_onehot_table.py``) at a small size.
"""
import gc
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import run  # noqa: E402


def group_offsets_shifted_by_one(job):
    """(the check came out ok, what it found) after one chunk trained on the
    job's own table with the offsets of every bundled feature shifted."""
    import numpy as np
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.objective import create_objective
    ds = job.dataset
    right = ds.bin_offset
    bundled = np.asarray([len(ds.feature_groups[g]) > 1 for g in ds.group_idx])
    job.gbdt = None
    gc.collect()
    ds.bin_offset = right + bundled.astype(right.dtype)
    try:
        params = dict(job.cfg["params"])
        config = Config(verbosity=-1, **params)
        job.gbdt = GBDT(config, ds, create_objective(params["objective"],
                                                     config))
        job.gbdt.train_chunk(job.k)
        job.gbdt.train_score.block_until_ready()
        return job.check_plain_splits()
    finally:
        ds.bin_offset = right


def main():
    kind = importlib.import_module("kinds.train_chunks_csr")
    jobs = []

    class Job(kind.Job):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            jobs.append(self)
    kind.Job = Job
    run.main()                       # the cell's run and its result line
    job, = jobs

    from gbdt_job import clock
    t0 = clock()
    ok, found = group_offsets_shifted_by_one(job)
    print("control group_offsets_shifted_by_one (%.1f s): plain_first_splits "
          "%s: %s" % (clock() - t0, "ok" if ok else "NOT ok", found),
          flush=True)
    print(json.dumps({"controls": {"group_offsets_shifted_by_one": bool(ok)}}),
          flush=True)
    sys.exit(1 if ok else 0)


if __name__ == "__main__":
    main()
