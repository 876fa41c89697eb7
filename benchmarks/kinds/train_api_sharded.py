"""Job kind ``train_api_sharded``: kind ``train_api`` (one
``lightgbm_tpu.train`` call, one tree per iteration, the same window, callbacks
and end-to-end numbers) on a cell of several chips, where the configuration's
``tree_learner`` shards the rows over them.  ``train_row_trees_per_s`` counts
ALL rows of the table: the model is the whole table's, whichever chip holds a
row.

It adds what only exists across chips: the sharded build program's
temporaries per device for ``memory_peak_bytes``, the program's collective
counters (``obs.comm``; a program without them has nothing to copy, and the
check that needs them fails), and three checks in place of the root split's:

- ``sharded_4``: the learner is ``DataParallelTreeLearner`` over as many
  shards as the cell has chips, and ``learner.bins``, ``train_score`` and the
  last gradients each have one addressable shard of rows / chips on every chip;
- ``plain_first_splits``: the first ``PLAIN_SPLITS`` splits of tree 0 and of
  the window's first tree are, each on the tree so far, those of
  ``plain_tree.grow_steps`` on the WHOLE binned table (or a near tie of the
  plain choice), and the gains the program recorded for them are the plain
  gains.  The later tree's gradients are NumPy's, from a walk of the
  program's earlier trees over the whole table: a score, a gradient or a mask
  that a chip kept stale, or a histogram that missed a chip, moves them;
- ``no_row_collective``: no program of an iteration holds a collective with a
  row-sized operand.
"""
from __future__ import annotations

import sys

import jax
import numpy as np

import gbdt_job
import plain_tree
from kinds import train_api

PLAIN_SPLITS = 8


def shard_rows_of(array):
    """{device: rows of its one shard} of a per-row array (rows on the axis
    the array is sharded over); None for an array that is not a jax.Array."""
    if not isinstance(array, jax.Array):
        return None
    out = {}
    for shard in array.addressable_shards:
        out.setdefault(shard.device, []).append(max(shard.data.shape))
    return out


class Job(train_api.Job):
    def setup(self):
        self.t_setup = gbdt_job.clock()
        super().setup()

    def run(self, seconds, tracer):
        super().run(seconds, tracer)
        # where setup_s went, for the runs of a call that read it differently
        t0 = getattr(sys.modules.get("__main__"), "T0", self.t_setup)
        print("set-up by phase: %.1f s from the process's start to the kind "
              "(interpreter, imports, reaching the chips), data %.1f s, "
              "binning %.1f s, booster and %d warm-up iterations %.1f s"
              % (self.t_setup - t0, self.host_timers["datagen_s"],
                 self.host_timers["bin_s"], self.warmup,
                 self.host_timers["first_unit_s"]), flush=True)
        try:
            from lightgbm_tpu.obs import comm
        except ImportError:
            return
        self.counters.update(comm.per_tree() or {})
        count = getattr(self.gbdt, "count_row_collectives", lambda: None)()
        if count is not None:
            self.counters["row_collectives"] = float(count)

    def program_temp_bytes(self):
        """Temporaries of the sharded build program on one device, by the
        compiler's analysis; 0 for a program that cannot say."""
        build = getattr(self.gbdt.learner, "compiled_build", lambda: None)()
        if build is None:
            return 0
        return int(build.memory_analysis().temp_size_in_bytes)

    def check_sharded(self):
        gbdt, learner = self.gbdt, self.gbdt.learner
        chips = jax.device_count()      # run.py held it to the cell's chips
        rows = gbdt.num_data + learner.padded_rows
        # the gradients of the next iteration, by the program's own step
        per_row = {"learner.bins": learner.bins,
                   "train_score": gbdt.train_score,
                   "gradients": gbdt._get_gradients()[0]}
        found = {name: shard_rows_of(a) for name, a in per_row.items()}
        ok = (type(learner).__name__ == "DataParallelTreeLearner"
              and getattr(learner, "num_shards", 1) == chips and all(
                  f is not None and len(f) == chips
                  and all(r == [rows // chips] for r in f.values())
                  for f in found.values()))
        return ok, ("%s over %d shards on %d chips; rows %d; shard rows %r"
                    % (type(learner).__name__,
                       getattr(learner, "num_shards", 1), chips, rows,
                       {n: f and sorted(sum(f.values(), []))
                        for n, f in found.items()}))

    def plain_gradients(self, tree):
        """(grad, hess) of binary logloss on every row as tree ``tree`` of the
        model should have seen them, by NumPy: the label mean before tree 0
        (boost_from_average), else the walk of the program's own earlier
        trees over the whole binned table."""
        y = self.y.astype(np.float64)
        if tree == 0:
            p = np.full(len(y), np.mean(y))
        else:
            p = 1.0 / (1.0 + np.exp(-plain_tree.scores_of(
                self.gbdt.models[:tree], self.dataset.binned)))
        return p - y, p * (1.0 - p)

    def check_plain_splits(self):
        """Tree 0, built from the constant first score, and the window's
        first tree, built from what the sharded score update, gradients and
        masking made of the trees before it."""
        params = self.cfg["params"]
        if any(len(g) != 1 for g in self.dataset.feature_groups):
            return False, "bundled feature groups: the plain grower reads " \
                          "one bin-code column per feature"
        if params["objective"] != "binary":
            return False, "plain gradients are binary logloss's"
        said = []
        for tree in (0, self.warmup):
            t0 = gbdt_job.clock()
            model = self.gbdt.models[tree]
            mine = plain_tree.tree_splits(model, PLAIN_SPLITS)
            grad, hess = self.plain_gradients(tree)
            ok, found = plain_tree.splits_agree(
                plain_tree.grow_steps(
                    self.dataset.binned, grad, hess,
                    num_bins=int(params["max_bin"]) + 1, splits=PLAIN_SPLITS,
                    min_data_in_leaf=int(params["min_data_in_leaf"]),
                    min_sum_hessian_in_leaf=float(
                        params["min_sum_hessian_in_leaf"]), follow=mine),
                mine, np.asarray(model.split_gain[:len(mine)], np.float64))
            said.append("tree %d (%.1f s): %s" % (tree, gbdt_job.clock() - t0,
                                                  found))
            if not ok:
                return False, "; ".join(said)
        return True, "; ".join(said)

    def check(self):
        n = self.counters.get("row_collectives")
        return gbdt_job.checks(self, must_stay_fused=False,
                               skip=("plain_root_split",)) + [
            ("sharded_4",) + self.check_sharded(),
            ("plain_first_splits",) + self.check_plain_splits(),
            ("no_row_collective", n == 0,
             "%s collectives with a row-sized operand in the iteration's "
             "programs" % ("no count of" if n is None else "%d" % n)),
        ]
