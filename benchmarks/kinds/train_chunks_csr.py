"""Job kind ``train_chunks_csr``: kind ``train_chunks`` (fused ``train_chunk(K)``
back to back, the same window, units and end-to-end numbers) on a sparse
one-hot table that reaches the program as CSR.

The rows come from ``datagen_onehot``; the data set is the program's own
``BinnedDataset.from_csr`` (what ``lightgbm_tpu.Dataset(scipy_csr)`` calls),
which bins the non-zeros and bundles mutually exclusive columns into a few
device columns (EFB) without ever building the dense table.  Only the held-out
rows are made dense, for ``predict`` and the plain walk.

It asks for the program's bundling counters (``lightgbm_tpu.obs.efb``) before
it makes any data: a program without them fails at once.  In place of the root
split's check it holds the run to what the configuration adds:

- ``sparse_ingest``: no raw table kept, the binned matrix has one column per
  group, and the groups are fewer than ``MAX_DEVICE_COLUMNS``;
- ``plain_first_splits``: the first ``PLAIN_SPLITS`` splits of tree 0 are,
  each on the tree so far, those of ``plain_sparse.grow_steps`` on the RAW CSR
  columns under the program's bin boundaries (or a near tie), and the gains
  the program recorded for them are the plain gains.  A wrong offset in the
  group layout, a default bin recovered from the wrong totals or a feature's
  rows lost to a conflict moves them.

Traffic parameters: those of ``train_chunks``.
"""
from __future__ import annotations

import numpy as np

import datagen_onehot
import gbdt_job
import plain_sparse
import plain_tree
from gbdt_job import clock
from kinds import train_chunks

PLAIN_SPLITS = 8
MAX_DEVICE_COLUMNS = 32


class Job(train_chunks.Job):
    def setup(self):
        from lightgbm_tpu.obs import efb           # before any data is made
        super().setup()
        # the split kernel reads efb_groups columns of a row, this many bins each
        self.counters["kernel_bins"] = float(self.gbdt.learner.num_bins)
        print("set-up: data %.1f s, from_csr %.1f s (%s), booster and the "
              "warm-up chunk %.1f s"
              % (self.host_timers["datagen_s"], self.host_timers["bin_s"],
                 ", ".join("%s %d" % kv for kv in sorted(efb.counts().items())),
                 self.host_timers["first_unit_s"]), flush=True)

    def make_dataset(self, params):
        from lightgbm_tpu.io.dataset import BinnedDataset
        from lightgbm_tpu.obs import efb
        gen = self.cfg["generator"]
        rows, held = int(self.cfg["rows"]), int(self.cfg["heldout_rows"])
        if self.rehearse_rows:
            rows = int(self.rehearse_rows)
            held = max(rows // 4, gbdt_job.WALK_ROWS)
        if datagen_onehot.num_features(gen) != int(self.cfg["features"]):
            raise ValueError("the generator's blocks do not add up to the "
                             "configuration's features")
        t0 = clock()
        levels, numeric, y = datagen_onehot.draw(self.seed, rows + held, gen)
        self.csr = datagen_onehot.to_csr(levels[:rows], numeric[:rows], gen)
        self.Xh = datagen_onehot.to_dense(levels[rows:], numeric[rows:], gen)
        self.y, self.yh = y[:rows], y[rows:]
        del levels, numeric
        self.host_timers["datagen_s"] = clock() - t0
        t0 = clock()
        # the arguments lightgbm_tpu.Dataset(csr, params=...) hands on
        self.dataset = BinnedDataset.from_csr(
            *self.csr, label=self.y, max_bin=int(params["max_bin"]),
            min_data_in_leaf=int(params["min_data_in_leaf"]))
        self.host_timers["bin_s"] = clock() - t0
        self.counters.update({name.replace(".", "_"): float(count)
                              for name, count in efb.counts().items()})

    def run(self, seconds, tracer):
        super().run(seconds, tracer)
        # where a late chunk lost its time: the host's own spans inside the
        # window (the scan's dispatch, the finite check); the rest of a
        # chunk's seconds is the wait for the device
        from lightgbm_tpu.obs import spans
        for name in ("fused_train_chunk", "gbdt.guard_chunk_scores"):
            print("span %s inside the window, seconds: %s" % (name, " ".join(
                "%.3f" % (r["end"] - r["start"]) for r in spans.records(name)
                if self.t_start <= r["start"] and r["end"] <= self.t_end)),
                flush=True)

    def check_sparse_ingest(self):
        ds = self.dataset
        groups = len(ds.feature_groups)
        ok = (ds.raw_data is None and ds.binned.shape == (len(self.y), groups)
              and self.counters.get("efb_groups") == groups
              and groups < MAX_DEVICE_COLUMNS
              and ds.num_total_features == int(self.cfg["features"]))
        return ok, ("%d columns in, %d used features in %d device columns "
                    "(fewer than %d), binned %r %s, raw table kept: %r, "
                    "%d conflict rows"
                    % (ds.num_total_features, len(ds.used_feature_idx), groups,
                       MAX_DEVICE_COLUMNS, ds.binned.shape, ds.binned.dtype,
                       ds.raw_data is not None,
                       self.counters.get("efb_conflict_rows", -1)))

    def check_plain_splits(self):
        """Tree 0 against the plain sparse grower.  Of the program's data set
        it reads the bin boundaries and which columns are used features (the
        model's inner feature ids), never the bundled matrix."""
        params = self.cfg["params"]
        if params["objective"] != "binary":
            return False, "plain gradients are binary logloss's"
        t0 = clock()
        ds, model = self.dataset, self.gbdt.models[0]
        bounds = [np.asarray(ds.bin_mappers[i].bin_upper_bound, np.float64)
                  [:ds.bin_mappers[i].num_bin] for i in ds.used_feature_idx]
        indptr, indices, values, _ = self.csr
        table = plain_sparse.Table(indptr, indices, values,
                                   ds.used_feature_idx, bounds,
                                   int(params["max_bin"]) + 1)
        y = self.y.astype(np.float64)
        p = np.full(len(y), y.mean())                  # boost_from_average
        mine = plain_tree.tree_splits(model, PLAIN_SPLITS)
        ok, found = plain_tree.splits_agree(
            plain_sparse.grow_steps(
                table, p - y, p * (1.0 - p), splits=PLAIN_SPLITS,
                min_data_in_leaf=int(params["min_data_in_leaf"]),
                min_sum_hessian_in_leaf=float(
                    params["min_sum_hessian_in_leaf"]), follow=mine),
            mine, np.asarray(model.split_gain[:len(mine)], np.float64))
        return ok, "tree 0 (%.1f s): %s" % (clock() - t0, found)

    def check(self):
        return gbdt_job.checks(self, must_stay_fused=True,
                               skip=("plain_root_split",)) + [
            ("sparse_ingest",) + self.check_sparse_ingest(),
            ("plain_first_splits",) + self.check_plain_splits(),
        ]
