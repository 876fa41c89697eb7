"""Sparse one-hot tables end to end at a small size: the benchmark's generator
(``benchmarks/datagen_onehot.py``), the program's CSR ingest and bundling
(``BinnedDataset.from_csr``, EFB), the grouped fused path in interpret mode,
and the plain sparse grower that holds it to the unbundled table's tree
(``benchmarks/plain_sparse.py``, which imports nothing of the program).

The schema is the configuration's cut down: its month, day-of-month,
day-of-week, carrier and origin blocks (12 + 31 + 7 + 29 + 307 levels) and the
two numeric columns, 388 columns in all.
"""
import copy
import json
import os
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
sys.path[:0] = [BENCH, os.path.join(BENCH, "tests")]

import datagen_onehot  # noqa: E402
import plain_sparse  # noqa: E402
import plain_tree  # noqa: E402

from lightgbm_tpu.boosting.gbdt import GBDT  # noqa: E402
from lightgbm_tpu.config import Config  # noqa: E402
from lightgbm_tpu.io.dataset import BinnedDataset  # noqa: E402
from lightgbm_tpu.obs import efb  # noqa: E402
from lightgbm_tpu.objective import create_objective  # noqa: E402

ROWS = 24576           # six chunks of the fused path's 4096 rows
B = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELL = "expo_onehot_train"
# the cell's metrics: every entry that lists it, its own (suffix ``.efb``)
# and those it shares with other cells
EFB_METRICS = sorted(m["name"] for m in B["per_layer"]
                     if CELL in m.get("workloads", ()))


def named(values, name, suffix=".efb"):
    """A metric's value by its name, with the cell's suffix or without."""
    return values[name + suffix] if name + suffix in values else values[name]


@pytest.fixture(scope="module")
def config():
    """The cell's configuration, cut down to five blocks and 31 leaves."""
    cfg = json.load(open(os.path.join(BENCH, "configs", "expo-onehot.json")))
    cfg = copy.deepcopy(cfg)
    gen = cfg["generator"]
    gen["blocks"] = gen["blocks"][:5]
    cfg["features"] = datagen_onehot.num_features(gen)
    cfg["params"].update(num_leaves=31, min_sum_hessian_in_leaf=5)
    assert cfg["features"] == 12 + 31 + 7 + 29 + 307 + 2
    return cfg


@pytest.fixture(scope="module")
def table(config):
    gen = config["generator"]
    levels, numeric, y = datagen_onehot.draw(2147483659, ROWS, gen)
    return types.SimpleNamespace(
        gen=gen, levels=levels, numeric=numeric, y=y,
        csr=datagen_onehot.to_csr(levels, numeric, gen),
        dense=datagen_onehot.to_dense(levels, numeric, gen))


# ---- the generator ---------------------------------------------------------

def test_one_active_level_a_block(table):
    starts = datagen_onehot.block_starts(table.gen)
    X = table.dense
    for b in range(len(table.gen["blocks"])):
        block = X[:, starts[b]:starts[b + 1]]
        assert np.all(block.sum(axis=1) == 1) and set(np.unique(block)) == {0, 1}
    assert np.all(X[:, starts[-1]:] > 0)
    indptr, indices, values, columns = table.csr
    assert columns == X.shape[1] and np.all(np.diff(indptr) == 7)
    again = np.zeros_like(X)
    again[np.repeat(np.arange(ROWS), 7), indices] = values
    np.testing.assert_array_equal(again, X)
    assert set(np.unique(table.y)) == {0.0, 1.0}


@pytest.mark.parametrize("threads,seed,same", [(1, 11, True), (5, 11, True),
                                               (1, 12, False)])
def test_rows_come_from_the_seed_alone(table, monkeypatch, threads, seed, same):
    monkeypatch.setattr(datagen_onehot, "BLOCK_ROWS", 1000)   # 7 blocks
    base = datagen_onehot.draw(11, 6500, table.gen, threads=3)
    got = datagen_onehot.draw(seed, 6500, table.gen, threads=threads)
    assert all(np.array_equal(a, b) for a, b in zip(base, got)) == same


# ---- ingest and bundling ---------------------------------------------------

def conflicts_of(ds, dense):
    """Rows in which a group holds more than one active feature, each
    counted once per feature beyond the first, straight off the dense table."""
    codes = np.stack([ds.bin_mappers[i].values_to_bins(dense[:, i]) != 0
                      for i in ds.used_feature_idx], axis=1)
    return sum(int(np.maximum(codes[:, g].sum(axis=1) - 1, 0).sum())
               for g in ds.feature_groups if len(g) > 1)


def test_csr_and_dense_ingest_bundle_alike(table):
    sparse = BinnedDataset.from_csr(*table.csr, label=table.y, max_bin=255,
                                    min_data_in_leaf=0)
    counted = efb.counts()
    dense = BinnedDataset.from_matrix(table.dense, label=table.y, max_bin=255,
                                      min_data_in_leaf=0)
    # bin mappers handed in: the groups come from the whole binned columns
    handed = BinnedDataset.from_matrix(table.dense, label=table.y, max_bin=255,
                                       bin_mappers=sparse.bin_mappers)
    assert sparse.raw_data is None
    assert sparse.feature_groups == dense.feature_groups \
        == handed.feature_groups
    assert sparse.used_feature_idx == dense.used_feature_idx
    np.testing.assert_array_equal(sparse.binned, dense.binned)
    np.testing.assert_array_equal(sparse.binned, handed.binned)
    assert sparse.binned.shape[1] == len(sparse.feature_groups) < 16
    assert counted == {"efb.features": len(sparse.used_feature_idx),
                       "efb.groups": len(sparse.feature_groups),
                       "efb.conflict_rows": conflicts_of(sparse, table.dense)}
    assert dense.conflict_rows == sparse.conflict_rows


def test_no_conflict_row_when_groups_stay_within_blocks(table):
    """Calendar and carrier blocks only: every level has rows in the EFB
    sample, so the greedy grouping can bundle nothing across blocks."""
    gen = dict(table.gen, blocks=table.gen["blocks"][:4],
               interaction=dict(table.gen["interaction"], blocks=[3, 3],
                                v=table.gen["interaction"]["u"]))
    levels, numeric, y = datagen_onehot.draw(3, 20000, gen)
    ds = BinnedDataset.from_csr(*datagen_onehot.to_csr(levels, numeric, gen),
                                label=y, min_data_in_leaf=0)
    starts = datagen_onehot.block_starts(gen)
    for group in ds.feature_groups:
        blocks = {int(np.searchsorted(starts, ds.used_feature_idx[j],
                                      side="right")) for j in group}
        assert len(blocks) == 1
    assert efb.counts()["efb.conflict_rows"] == 0
    assert efb.counts()["efb.groups"] == 6 and ds.binned.shape == (20000, 6)


def first_splits_and_scores(ds, trees=5):
    cfg = Config(objective="binary", num_leaves=15, learning_rate=0.2,
                 max_bin=255, min_data_in_leaf=0, min_sum_hessian_in_leaf=5,
                 verbosity=-1)
    g = GBDT(cfg, ds, create_objective("binary", cfg))
    for _ in range(trees):
        g.train_one_iter()
    model = g.models[0]
    return (plain_tree.tree_splits(model, 8), np.asarray(model.split_gain[:8]),
            np.asarray(g.train_score[0, :ds.num_data]))


def test_three_layouts_grow_the_same_trees(table):
    """CSR in and bundled, dense in and bundled, dense in and one column a
    feature: the same first splits, and scores within the tolerance
    tests/test_efb.py states for ties (the shared default bin comes back by
    subtraction, whose float noise can flip two equal gains)."""
    how = dict(label=table.y, max_bin=255, min_data_in_leaf=0)
    splits, gains, scores = zip(*[first_splits_and_scores(ds) for ds in (
        BinnedDataset.from_csr(*table.csr, **how),
        BinnedDataset.from_matrix(table.dense, **how),
        BinnedDataset.from_matrix(table.dense, enable_bundle=False, **how))])
    assert splits[0] == splits[1] == splits[2]
    for other in (1, 2):
        np.testing.assert_allclose(gains[other], gains[0], rtol=1e-4)
        np.testing.assert_allclose(scores[other], scores[0], rtol=1e-4,
                                   atol=1e-5)


# ---- the job kind, the plain sparse grower and its control -----------------

@pytest.fixture(scope="module")
def job(config):
    """The benchmark's kind on the cut-down configuration: a rehearsal in
    this process (interpret-mode kernels, the fused carried path), set up and
    run for a window of one chunk."""
    from kinds import train_chunks_csr
    from lightgbm_tpu import resilience
    from lightgbm_tpu.plan import cache as plan_cache
    resilience.reset_fallbacks()      # the process's counters: other files'
    plan_cache.reset_fallbacks()      # tests of the degraded paths raise them
    with pytest.MonkeyPatch.context() as m:
        m.setenv("LIGHTGBM_TPU_PALLAS_INTERPRET", "1")
        wl = {"kind": "train_chunks_csr", "trees_per_chunk": 2,
              "auc_trees": 2, "trace_units": 1}
        job = train_chunks_csr.Job(config, wl, 2147483659, rehearse_rows=ROWS)
        job.setup()
        assert job.gbdt._can_fuse_iters() and job.gbdt._can_carry_rows()
        assert job.gbdt.learner.grouped
        job.run(1e-3, None)         # one chunk
        yield job


def test_the_kinds_checks_hold_on_the_fused_grouped_path(job):
    found = job.check()
    assert [name for name, _, _ in found] == [
        "no_degraded_path", "no_recompile_in_window", "plain_walk",
        "training_loss_falls", "sparse_ingest", "plain_first_splits"]
    assert all(holds for _, holds, _ in found), found
    assert "8 splits" in found[-1][2]
    assert not job.failed and job.gbdt.iter_ == 4 and not job.gbdt._fuse_failed


def test_every_efb_metric_has_something_to_read(job):
    """The host-clock and program-sourced metrics on the rehearsal itself,
    the trace-sourced ones on a made-up trace over the rehearsal's own chunk
    program: an op under every scope, every kernel by its name."""
    import run
    from lightgbm_tpu.obs.scopes import op_scopes
    from readers import trace_scope
    text = job.gbdt.chunk_program_text(job.k)
    scope_of = op_scopes(text, trace_scope.all_scopes())
    # the children's unbundling runs under vmap: the path says so
    vmapped = [ln.split(" = ")[0].split()[-1] for ln in text.splitlines()
               if "vmap(tree.unpack)" in ln and " = " in ln]
    assert vmapped and all(scope_of[op] == "tree.unpack" for op in vmapped)
    own = {}
    for scope in trace_scope.all_scopes() + ["unscoped"]:
        ops = [op for op, s in scope_of.items() if s == scope]
        if scope == "tree.unpack":
            assert ops, "no instruction of the chunk is under tree.unpack"
        # every op of the search's two parents, three of any other scope
        own.update({op: 1000.0 for op in (
            ops if scope in ("tree.find_split", "tree.unpack") else ops[:3])})
    own.update({"%partition_hist_pallas_c4096.1": 5e6,
                "%partition_hist_pallas_c1024.2": 1e6,
                "%histogram_pallas_rows_factored.3": 2e6,
                "%row_state_pass.4": 1e6, "%while.5": 3000.0})
    busy = sum(own.values())
    trace = {"own": own, "busy_ns": busy, "window_ns": busy / 0.99, "idle": {}}
    job.traced_trees = job.gbdt.models[:2]
    ctx = {"job": job, "trace": trace, "cfg": job.cfg, "wl": job.wl,
           "device_kind": "TPU v5 lite"}
    got = run.layer_metrics(B, CELL, "train_chunks_csr", ctx)
    assert sorted(got) == EFB_METRICS
    value = {name: m["value"] for name, m in got.items()}
    parts = [n for n in EFB_METRICS if n.startswith("glue_")]
    assert len(parts) == 11
    assert sum(value[n] for n in parts) == pytest.approx(
        named(value, "xla_glue_ms_per_tree"), rel=1e-9)
    assert named(value, "glue_unpack_ms_per_tree") > 0
    assert named(value, "efb_groups") == job.dataset.binned.shape[1]
    # one level down (PR 39): the search's parts and what no part claims add
    # up to its two parents, and the scans run under tree.unpack here
    find = [n for n in EFB_METRICS if n.startswith("find_")]
    assert len(find) == 6
    assert sum(value[n] for n in find) == pytest.approx(
        named(value, "glue_find_split_ms_per_tree")
        + named(value, "glue_unpack_ms_per_tree"), rel=1e-9)
    assert all(value[n] > 0 for n in find if n != "find_rest_ms_per_tree")
    assert value["find_scan_ms_per_tree"] == pytest.approx(
        named(value, "glue_unpack_ms_per_tree"), rel=1e-9)
    assert value["chunk_score_out_ms_per_tree"] \
        + value["chunk_rest_ms_per_tree"] == pytest.approx(
            named(value, "glue_unscoped_ms_per_tree"), rel=1e-9)
    # the roofline counts the bytes of the device columns, not of the
    # configuration's features: the same reading off the plain reader's count
    import roofline
    nbytes, ops, _, _ = roofline.split_work(
        job.traced_trees, features=job.dataset.binned.shape[1], bins=256)
    least, _ = roofline.least_seconds(nbytes, ops,
                                      roofline.peaks("TPU v5 lite"))
    assert named(value, "split_kernel_roofline") == pytest.approx(
        100.0 * least / 6e-3)


def test_the_check_sees_group_offsets_shifted_by_one(job):
    """Last of the file: it retrains the job's booster with the fault in."""
    import controls_onehot
    ok, found = controls_onehot.group_offsets_shifted_by_one(job)
    assert not ok, found
    assert "short by" in found or "recorded gain" in found


def test_plain_sparse_histograms_are_the_dense_tables(table):
    """The sparse histograms (non-zeros by bincount, the zero bin by what the
    totals leave) against ``plain_tree.histograms`` on the dense bin codes."""
    ds = BinnedDataset.from_matrix(table.dense, label=table.y, max_bin=255,
                                   enable_bundle=False, min_data_in_leaf=0)
    bounds = [np.asarray(ds.bin_mappers[i].bin_upper_bound)
              [:ds.bin_mappers[i].num_bin] for i in ds.used_feature_idx]
    sparse = plain_sparse.Table(*table.csr[:3], ds.used_feature_idx, bounds,
                                256)
    rng = np.random.default_rng(0)
    grad, hess = rng.standard_normal(ROWS), rng.random(ROWS)
    in_leaf = rng.random(ROWS) < 0.3
    np.testing.assert_allclose(
        sparse.histograms(in_leaf, grad, hess),
        plain_tree.histograms(ds.binned[in_leaf], grad[in_leaf],
                              hess[in_leaf], 256), rtol=1e-9, atol=1e-9)
    np.testing.assert_array_equal(sparse.codes_of_feature(40),
                                  ds.binned[:, 40])
