"""Job kind ``train_chunks_sub``: kind ``train_chunks`` (fused ``train_chunk(K)``
back to back, the same window, units and end-to-end numbers) under row and
column subsampling: ``feature_fraction``, ``bagging_fraction`` and
``bagging_freq`` of the configuration's ``params``.

It asks for the program's sampling counters (``lightgbm_tpu.obs.sampling``)
before it makes any data: a program without them, which would train such a
configuration one tree a host round, fails at once.  In place of the root
split's check it holds the run to what the configuration adds:

- ``fused_every_tree``: every tree of the booster was built by a fused chunk
  (``sampling.fused_trees``), none by ``train_one_iter``;
- ``sampled_as_configured``: every tree searched as many features as the
  plain mask holds, and every tree's bag (its root count) is the plain bag of
  its window to the row, within ``BAG_SIGMAS`` standard deviations of
  ``bagging_fraction`` of the rows; the newest tree's is ``sampling.bag_rows``;
- ``mask_honoured``: no tree of the run splits on a feature outside the plain
  mask of its iteration;
- ``plain_first_splits``: the first ``PLAIN_SPLITS`` splits of tree 0 and of
  the window's first tree (tree K: another bag window, another mask) are,
  each on the tree so far, those of ``plain_subsampled.grow_steps`` on the
  rows of that tree's bag and the columns of that tree's mask (or a near tie),
  and the gains the program recorded for them are the plain gains.  The later
  tree's gradients are NumPy's, from a walk of the program's own earlier
  trees over the whole table.  ``plain_tree.GAIN_RTOL`` and
  ``RECORDED_GAIN_RTOL`` say why each limit is what it is.

Traffic parameters: those of ``train_chunks``.
"""
from __future__ import annotations

import numpy as np

import gbdt_job
import plain_subsampled
import plain_tree
from gbdt_job import clock
from kinds import train_chunks

PLAIN_SPLITS = 8
BAG_SIGMAS = 4.0


class Job(train_chunks.Job):
    def setup(self):
        from lightgbm_tpu.obs import sampling      # before any data is made
        sampling.reset()
        super().setup()

    def run(self, seconds, tracer):
        super().run(seconds, tracer)
        counts = self.copy_sampling_counts()
        print("sampling: %s" % ", ".join(
            "%s %g" % kv for kv in sorted(counts.items())), flush=True)

    def copy_sampling_counts(self):
        """The program's sampling counts into ``self.counters`` (dots to
        underscores), as the checks and the readers ask for them."""
        from lightgbm_tpu.obs import sampling
        counts = sampling.counts()
        self.counters.update({name.replace(".", "_"): float(count)
                              for name, count in counts.items()})
        return counts

    # ---- the plain draws of this configuration ----------------------------

    def plain_mask(self, iteration):
        p = self.cfg["params"]
        return plain_subsampled.mask_of(
            self.dataset.num_features, int(p["feature_fraction_seed"]),
            iteration, float(p["feature_fraction"]))

    def plain_bag(self, iteration):
        p = self.cfg["params"]
        return plain_subsampled.bag_of(
            len(self.y), int(p["bagging_seed"]), iteration,
            int(p["bagging_freq"]), float(p["bagging_fraction"]))

    # ---- the guarantees ---------------------------------------------------

    def check_fused(self):
        trees = self.gbdt.iter_
        fused = self.counters.get("sampling_fused_trees")
        per_iteration = self.counters.get("sampling_per_iteration_trees")
        return (fused == trees and per_iteration == 0,
                "%d trees in the booster; fused chunks built %s, "
                "train_one_iter %s" % (trees, fused, per_iteration))

    def check_sampling(self):
        p, rows = self.cfg["params"], len(self.y)
        freq, share = int(p["bagging_freq"]), float(p["bagging_fraction"])
        want_used = plain_subsampled.features_used(
            self.dataset.num_features, float(p["feature_fraction"]))
        used = {int(self.plain_mask(it).sum())
                for it in range(self.gbdt.iter_)}
        allowed = BAG_SIGMAS * np.sqrt(rows * share * (1.0 - share))
        plain = {w: int(self.plain_bag(w).sum())
                 for w in range(0, self.gbdt.iter_, freq)}
        mine = [int(t.internal_count[0]) if t.num_leaves > 1 else None
                for t in self.gbdt.models]
        off = [(it, n, plain[it - it % freq]) for it, n in enumerate(mine)
               if n != plain[it - it % freq]]
        newest = plain[(self.gbdt.iter_ - 1) - (self.gbdt.iter_ - 1) % freq]
        widest = max(abs(n - share * rows) for n in plain.values())
        ok = (used == {want_used}
              and self.counters.get("sampling_features_used") == want_used
              and not off and widest <= allowed
              and self.counters.get("sampling_bag_rows") == newest)
        return ok, (
            "features a tree: plain masks %r, the program's count %s, "
            "configured %d of %d; %d bag windows of %d iterations, plain "
            "bags %d..%d rows, at most %.0f from %.2f of %d rows (allowed "
            "%.0f: %g standard deviations); trees whose root count is not "
            "their window's plain bag: %r; the newest tree's bag: plain %d, "
            "sampling.bag_rows %s"
            % (sorted(used), self.counters.get("sampling_features_used"),
               want_used, self.dataset.num_features, len(plain), freq,
               min(plain.values()), max(plain.values()), widest, share, rows,
               allowed, BAG_SIGMAS, off[:4], newest,
               self.counters.get("sampling_bag_rows")))

    def check_masks(self):
        outside = []
        for it, tree in enumerate(self.gbdt.models):
            mask = self.plain_mask(it)
            split_on = np.asarray(
                tree.split_feature_inner[:int(tree.num_leaves) - 1])
            bad = sorted(set(split_on[~mask[split_on]].tolist()))
            if bad:
                outside.append((it, bad))
        return not outside, (
            "%d trees, each split of each on a feature of its iteration's "
            "plain mask; outside it (tree, features): %r"
            % (len(self.gbdt.models), outside[:4]))

    def check_plain_splits(self, trees=None):
        """Tree 0 and the window's first tree against the plain grower on
        the rows of the tree's bag and the columns of its mask."""
        params = self.cfg["params"]
        if any(len(g) != 1 for g in self.dataset.feature_groups):
            return False, "bundled feature groups: the plain grower reads " \
                          "one bin-code column per feature"
        if params["objective"] != "binary":
            return False, "plain gradients are binary logloss's"
        codes, said = self.dataset.binned, []
        for tree in (0, self.k) if trees is None else trees:
            t0 = clock()
            model = self.gbdt.models[tree]
            mine = plain_tree.tree_splits(model, PLAIN_SPLITS)
            grad, hess = plain_subsampled.binary_gradients(
                self.y, self.gbdt.models[:tree], codes)
            bag, mask = self.plain_bag(tree), self.plain_mask(tree)
            ok, found = plain_tree.splits_agree(
                plain_subsampled.grow_steps(
                    codes, grad, hess, bag, mask,
                    num_bins=int(params["max_bin"]) + 1, splits=PLAIN_SPLITS,
                    min_data_in_leaf=int(params["min_data_in_leaf"]),
                    min_sum_hessian_in_leaf=float(
                        params["min_sum_hessian_in_leaf"]), follow=mine),
                mine, np.asarray(model.split_gain[:len(mine)], np.float64))
            said.append("tree %d on %d bag rows and %d features (%.1f s): %s"
                        % (tree, int(bag.sum()), int(mask.sum()),
                           clock() - t0, found))
            if not ok:
                return False, "; ".join(said)
        return True, "; ".join(said)

    def check(self):
        return gbdt_job.checks(self, must_stay_fused=True,
                               skip=("plain_root_split",)) + [
            ("fused_every_tree",) + self.check_fused(),
            ("sampled_as_configured",) + self.check_sampling(),
            ("mask_honoured",) + self.check_masks(),
            ("plain_first_splits",) + self.check_plain_splits(),
        ]
