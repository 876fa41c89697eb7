"""Job kind ``train_chunks_cat``: kind ``train_chunks`` (fused ``train_chunk(K)``
back to back, the same window, units and end-to-end numbers) on a table whose
categorical columns are handed over as integer codes with
``categorical_feature`` set.

The rows are ``datagen_onehot``'s, seed for seed those of the one-hot cell:
the level index of every categorical block (from 0) as a column of its own,
then the numeric columns, an ``[N, blocks + numeric]`` matrix through the
program's own ``BinnedDataset.from_matrix(..., categorical_feature=...)``
(what ``lightgbm_tpu.Dataset(X, categorical_feature=...)`` calls).  The
held-out rows are made the same way, for ``predict`` and the plain walk.

It asks for the program's categorical counters
(``lightgbm_tpu.obs.categorical``) before it makes any data: a program
without them fails at once.  In place of the root split's check and of the
numerical walk it holds the run to what the configuration adds:

- ``categorical_ingest``: every block a categorical mapper with as many bins
  as it has levels (at most ``max_bin``), one device column a feature,
  nothing bundled, and the compiled search scanning at most
  ``max_cat_threshold`` steps a direction;
- ``plain_first_splits``: the first ``PLAIN_SPLITS`` splits of tree 0 and of
  the window's first tree (tree K, its gradients NumPy's from a walk of the
  program's own earlier trees over the whole table) are, each on the tree so
  far, those of ``plain_categorical.grow_steps`` on the program's bin codes:
  the same column and the same left bin set or threshold, or a near tie, and
  the gains the program recorded are the plain gains; at least one of them a
  many-vs-many split, or the cell does not work its mechanism;
- ``plain_leaf_values``: every leaf of tree 0 carries ``-G / (H + l2)`` of
  the rows a plain walk of the tree sends there, ``l2`` with ``cat_l2`` where
  a many-vs-many split made the leaf, and those leaves lie nearer the plain
  values under the configuration's ``cat_l2`` than under none (on ten million
  rows the first splits' sums are too large for ``cat_l2`` to show; the late
  leaves' are not);
- ``plain_walk``: ``plain_categorical.walk`` on the held-out rows' raw
  values, the category sets read off ``cat_boundaries`` / ``cat_threshold``.

Traffic parameters: those of ``train_chunks``.
"""
from __future__ import annotations

import numpy as np

import datagen_onehot
import gbdt_job
import plain_categorical
from gbdt_job import clock
from kinds import train_chunks

PLAIN_SPLITS = 8
# the program's leaf values are f32 quotients of sums that went through f32
# histograms and their subtractions, the plain ones of f64 sums over the
# leaf's own rows.  The leaves a many-vs-many split made have to lie at most
# this share as far from the plain values under the configuration's cat_l2
# as from those under none (PERF.md section 6 has the readings it lies
# between: a sound run's, and two faults')
LEAF_L2_SHARE = 0.1


def code_matrix(levels, numeric):
    """[n, blocks + numeric] float32: each block's level index, then the
    numeric columns."""
    X = np.empty((levels.shape[0], levels.shape[1] + numeric.shape[1]),
                 np.float32)
    X[:, :levels.shape[1]] = levels
    X[:, levels.shape[1]:] = numeric
    return X


def plain_columns(dataset):
    """``plain_categorical.Column`` of every used feature of a data set of the
    program's: its mapping (how many bins, whether the last is the other-bin)
    is taken as given."""
    from lightgbm_tpu.io.binning import BinType, MissingType
    mappers = [dataset.bin_mappers[i] for i in dataset.used_feature_idx]
    return [plain_categorical.Column(
        m.bin_type == BinType.CATEGORICAL, int(m.num_bin),
        int(m.num_bin) - 1 + int(m.missing_type == MissingType.NONE))
        for m in mappers]


class Job(train_chunks.Job):
    def setup(self):
        from lightgbm_tpu.obs import categorical     # before any data is made
        from lightgbm_tpu.obs import spans
        categorical.reset()
        super().setup()
        print("set-up: data %.1f s, from_matrix %.1f s (%s), booster and the "
              "warm-up chunk %.1f s; %s"
              % (self.host_timers["datagen_s"], self.host_timers["bin_s"],
                 ", ".join("%s %.2f" % (name, seconds) for name, seconds
                           in sorted(spans.seconds().items())
                           if name.startswith("ingest.")),
                 self.host_timers["first_unit_s"],
                 ", ".join("%s %d" % kv
                           for kv in sorted(categorical.counts().items()))),
              flush=True)

    def make_dataset(self, params):
        from lightgbm_tpu.io.dataset import BinnedDataset
        gen = self.cfg["generator"]
        rows, held = int(self.cfg["rows"]), int(self.cfg["heldout_rows"])
        if self.rehearse_rows:
            rows = int(self.rehearse_rows)
            held = max(rows // 4, gbdt_job.WALK_ROWS)
        self.categorical = [int(c) for c in params["categorical_feature"]]
        if (len(gen["blocks"]) + len(gen["numeric"])
                != int(self.cfg["features"])
                or self.categorical != list(range(len(gen["blocks"])))):
            raise ValueError("the configuration's features are not the "
                             "generator's blocks, all categorical, and its "
                             "numeric columns")
        t0 = clock()
        levels, numeric, y = datagen_onehot.draw(self.seed, rows + held, gen)
        X = code_matrix(levels, numeric)
        del levels, numeric
        self.Xh, self.y, self.yh = X[rows:], y[:rows], y[rows:]
        self.host_timers["datagen_s"] = clock() - t0
        t0 = clock()
        # the arguments lightgbm_tpu.Dataset(X, categorical_feature=...,
        # params=...) hands on
        self.dataset = BinnedDataset.from_matrix(
            X[:rows], label=self.y, max_bin=int(params["max_bin"]),
            min_data_in_leaf=int(params["min_data_in_leaf"]),
            categorical_feature=self.categorical)
        self.host_timers["bin_s"] = clock() - t0

    def run(self, seconds, tracer):
        super().run(seconds, tracer)
        from lightgbm_tpu.obs import categorical
        len(self.gbdt.models)          # every tree on the host, and counted
        counts = categorical.counts()
        self.counters.update({name.replace(".", "_"): float(count)
                              for name, count in counts.items()})
        print("categorical: %s" % ", ".join(
            "%s %d" % kv for kv in sorted(counts.items())), flush=True)

    def plain_columns(self):
        return plain_columns(self.dataset)

    # ---- the guarantees ----------------------------------------------------

    def check_categorical_ingest(self):
        ds, params = self.dataset, self.cfg["params"]
        max_bin = int(params["max_bin"])
        columns = self.plain_columns()
        levels = [b["levels"] for b in self.cfg["generator"]["blocks"]]
        bins = [c.num_bin for c in columns[:len(levels)]]
        # a bin a level, up to max_bin; a rehearsal's few rows miss levels
        most = [min(lv, max_bin) for lv in levels]
        as_levels = all(1 < nb <= m for nb, m in zip(bins, most)) and (
            self.rehearse_rows is not None
            or all(nb == lv for nb, lv in zip(bins, levels) if lv <= max_bin))
        steps = self.counters.get("cat_scan_steps")
        ok = (len(columns) == int(self.cfg["features"])
              and [c.categorical for c in columns]
              == [i in self.categorical for i in range(len(columns))]
              and as_levels
              and ds.binned.shape == (len(self.y), len(columns))
              and all(len(g) == 1 for g in ds.feature_groups)
              and self.counters.get("cat_features") == len(levels)
              and steps is not None
              and 0 < steps <= int(params["max_cat_threshold"]))
        return ok, ("%d used features, categorical %r with %r bins for %r "
                    "levels (max_bin %d), binned %r %s in %d groups, "
                    "cat.bins %s, cat.scan_steps %s (max_cat_threshold %d)"
                    % (len(columns),
                       [i for i, c in enumerate(columns) if c.categorical],
                       bins, levels, max_bin, ds.binned.shape, ds.binned.dtype,
                       len(ds.feature_groups), self.counters.get("cat_bins"),
                       steps, int(params["max_cat_threshold"])))

    def check_plain_splits(self, trees=None):
        """Tree 0 and the window's first tree against the plain grower on
        the program's bin codes."""
        params = self.cfg["params"]
        if params["objective"] != "binary":
            return False, "plain gradients are binary logloss's"
        codes, num_bins = self.dataset.binned, int(params["max_bin"]) + 1
        columns = self.plain_columns()
        p = plain_categorical.params_of(params)
        said, many = [], 0
        for tree in (0, self.k) if trees is None else trees:
            t0 = clock()
            model = self.gbdt.models[tree]
            mine = plain_categorical.tree_splits(model, PLAIN_SPLITS)
            grad, hess = plain_categorical.binary_gradients(
                self.y, self.gbdt.models[:tree], codes, num_bins)
            ok, found, n = plain_categorical.splits_agree(
                plain_categorical.grow_steps(
                    codes, grad, hess, columns, p, num_bins=num_bins,
                    splits=PLAIN_SPLITS, follow=mine),
                mine, np.asarray(model.split_gain[:len(mine)], np.float64),
                columns, p)
            many += n
            said.append("tree %d (%.1f s): %s" % (tree, clock() - t0, found))
            if not ok:
                return False, "; ".join(said)
        if not many:
            return False, ("no many-vs-many split among them: the cell does "
                           "not work its mechanism; " + "; ".join(said))
        return True, "; ".join(said)

    def check_leaf_values(self):
        params = self.cfg["params"]
        t0 = clock()
        codes, num_bins = self.dataset.binned, int(params["max_bin"]) + 1
        tree = self.gbdt.models[0]
        y = self.y.astype(np.float64)
        grad, hess = plain_categorical.binary_gradients(y, [], codes, num_bins)
        leaf_of_row = plain_categorical.leaves_of(tree, codes, num_bins)
        p = plain_categorical.params_of(params)
        want, none = (plain_categorical.leaf_values(
            tree, leaf_of_row, grad, hess, self.plain_columns(), q,
            float(params["learning_rate"]),
            bias=float(np.log(y.mean() / (1.0 - y.mean()))))
            for q in (p, p._replace(cat_l2=0.0)))
        got = np.asarray(tree.leaf_value[:int(tree.num_leaves)], np.float64)
        made = want != none         # the leaves a many-vs-many split made
        gap = np.abs(got - want)
        worst = int(np.argmax(np.where(np.isfinite(gap), gap, np.inf)))
        # medians: one leaf of the tree carries the whole of what the root's
        # sum and its histogram differ by (plain_categorical.SET_GAIN_RTOL)
        near = float(np.median(gap[made])) if made.any() else np.inf
        far = float(np.median(np.abs(got - none)[made])) if made.any() else 0.0
        share = near / far if far else np.inf
        return bool(share <= LEAF_L2_SHARE), (
            "tree 0 (%.1f s): %d leaves, %d of them made by a many-vs-many "
            "split: these lie %.3g (the median) from the plain values under "
            "cat_l2=%g and %.3g from those under cat_l2=0, %.3g of it "
            "(allowed %.2g); over all leaves max |d| %.3g at leaf %d (program "
            "%.6f, plain %.6f)"
            % (clock() - t0, len(got), int(made.sum()), near, p.cat_l2, far,
               share, LEAF_L2_SHARE, gap[worst], worst, got[worst],
               want[worst]))

    def check_walk(self):
        X = self.Xh[:gbdt_job.WALK_ROWS]
        trees = self.auc_trees
        got = np.asarray(self.gbdt.predict(X, raw_score=True,
                                           num_iteration=trees),
                         np.float64).reshape(-1)
        want = plain_categorical.walk(self.gbdt.models[:trees], X)
        err = float(np.max(np.abs(got - want)))
        cat = sum(int(t.num_cat) for t in self.gbdt.models[:trees])
        return err <= gbdt_job.WALK_ATOL, (
            "plain walk of %d trees (%d categorical nodes) on %d held-out "
            "rows: max |d| %.3g (allowed %.0e)"
            % (trees, cat, len(X), err, gbdt_job.WALK_ATOL))

    def check(self):
        found = gbdt_job.checks(self, must_stay_fused=True,
                                skip=("plain_root_split", "plain_walk"))
        return found + [
            ("categorical_ingest",) + self.check_categorical_ingest(),
            ("plain_first_splits",) + self.check_plain_splits(),
            ("plain_leaf_values",) + self.check_leaf_values(),
            ("plain_walk",) + self.check_walk(),
        ]
