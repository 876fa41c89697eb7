"""The subsampled deployment end to end at a small size: the benchmark's job
kind (``benchmarks/kinds/train_chunks_sub.py``) on the fused carried path in
interpret mode, the plain reference that recomputes the bag and the masks
independently (``benchmarks/plain_subsampled.py``, which imports nothing of
the program), the cell's per-layer metrics and the three fault controls
(``benchmarks/tests/controls_subsampled.py``).
"""
import copy
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
sys.path[:0] = [BENCH, os.path.join(BENCH, "tests")]

import plain_subsampled  # noqa: E402

from lightgbm_tpu.boosting import gbdt as G  # noqa: E402

ROWS = 24576           # six chunks of the fused path's 4096 rows
B = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELL = "higgs_prod_train"
# the cell's metrics: every entry that lists it, its own (suffix ``.sub``)
# and those it shares with other cells
SUB_METRICS = sorted(m["name"] for m in B["per_layer"]
                     if CELL in m.get("workloads", ()))
# the 16 PR 36 gave the cell, by name: a later fold may drop the suffix
PR36_METRICS = [
    "first_unit_s", "unit_wall_ms_per_tree", "recompiles_in_window",
    "device_idle_share", "split_kernel_ms_per_tree",
    "split_ns_per_window_row", "split_kernel_roofline",
    "hist_kernel_ms_per_tree", "row_pass_ms_per_tree", "xla_glue_ms_per_tree",
    "glue_find_split_ms_per_tree", "features_used_per_tree", "bag_rows_share",
    "per_iteration_trees", "glue_sample_ms_per_tree",
    "dead_window_rows_share"]


def name_in(values, name, suffix=".sub"):
    """A metric's name as ``values`` has it: with the cell's suffix, or
    without; None when it has neither."""
    return next((n for n in (name + suffix, name) if n in values), None)


# ---- the plain draws are the program's, bit for bit ------------------------

@pytest.mark.parametrize("seed,iteration,freq,fraction", [
    (3, 0, 5, 0.8), (3, 4, 5, 0.8), (3, 5, 5, 0.8), (3, 13, 5, 0.8),
    (2147483659, 7, 1, 0.5), (0, 2, 3, 0.999)])
def test_plain_bag_is_the_programs(seed, iteration, freq, fraction):
    import jax.numpy as jnp
    rows = 50000
    mine = np.asarray(G._bag_mask(jnp.arange(rows, dtype=jnp.int32), seed,
                                  jnp.int32(iteration), freq, fraction)) > 0
    plain = plain_subsampled.bag_of(rows, seed, iteration, freq, fraction)
    np.testing.assert_array_equal(plain, mine)
    assert abs(plain.mean() - fraction) < 4 * np.sqrt(0.25 / rows)
    same_window = plain_subsampled.bag_of(
        rows, seed, iteration - iteration % freq, freq, fraction)
    np.testing.assert_array_equal(plain, same_window)
    assert not np.array_equal(plain, plain_subsampled.bag_of(
        rows, seed, iteration + freq, freq, fraction))


@pytest.mark.parametrize("features,fraction,seed", [
    (28, 0.8, 2), (28, 0.8, 7), (700, 0.5, 2), (10, 0.3, 2147483659),
    (5, 1.0, 2)])
def test_plain_mask_is_the_programs(features, fraction, seed):
    used = plain_subsampled.features_used(features, fraction)
    assert used == G.features_used(features, fraction)
    for iteration in (0, 1, 8, 1000):
        np.testing.assert_array_equal(
            plain_subsampled.mask_of(features, seed, iteration, fraction),
            np.asarray(G.feature_mask_of(features, used, seed, iteration)))


def test_plain_grower_prices_splits_in_the_whole_tables_feature_ids():
    rng = np.random.RandomState(0)
    codes = rng.randint(0, 16, size=(4000, 6)).astype(np.uint8)
    grad = (codes[:, 4] > 7) - 0.5 + rng.normal(scale=0.1, size=4000)
    bag = rng.rand(4000) < 0.8
    mask = np.array([True, False, True, False, True, True])
    how = dict(num_bins=16, splits=2, min_data_in_leaf=1,
               min_sum_hessian_in_leaf=0.0)
    steps = list(plain_subsampled.grow_steps(codes, grad, np.ones(4000), bag,
                                             mask, **how))
    assert (steps[0]["feature"], steps[0]["bin"]) == (4, 7)
    table = steps[0]["gains"][0]
    assert table.shape == (6, 15) and np.all(np.isneginf(table[[1, 3]]))
    assert np.isfinite(table[4, 7])
    # the bag's rows only: the root's gain is the bagged table's
    import plain_tree
    whole = next(plain_tree.grow_steps(codes[bag], grad[bag], np.ones(
        int(bag.sum())), **how))
    assert steps[0]["gain"] == pytest.approx(whole["gain"], rel=1e-12)
    # a split on a masked feature is followed no further and priced -inf
    followed = list(plain_subsampled.grow_steps(
        codes, grad, np.ones(4000), bag, mask, follow=[(0, 1, 3)], **how))
    ok, found = plain_tree.splits_agree(iter(followed), [(0, 1, 3)])
    assert not ok and "short by inf" in found


# ---- the job kind on the fused carried path --------------------------------

@pytest.fixture(scope="module")
def config():
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "higgs-10m5-sub.json")))
    cfg = copy.deepcopy(cfg)
    cfg["params"].update(num_leaves=15, min_sum_hessian_in_leaf=5)
    return cfg


@pytest.fixture(scope="module")
def job(config):
    """The benchmark's kind at 24,576 rows and 15 leaves: a rehearsal in this
    process (interpret-mode kernels), set up and run for a window of one
    chunk, so the booster holds 16 trees over four bag windows."""
    from kinds import train_chunks_sub
    from lightgbm_tpu import resilience
    from lightgbm_tpu.plan import cache as plan_cache
    resilience.reset_fallbacks()      # the process's counters: other files'
    plan_cache.reset_fallbacks()      # tests of the degraded paths raise them
    with pytest.MonkeyPatch.context() as m:
        m.setenv("LIGHTGBM_TPU_PALLAS_INTERPRET", "1")
        wl = json.load(open(os.path.join(BENCH, "traffic",
                                         "chunks_k8_sub.json")))
        job = train_chunks_sub.Job(config, wl, 2147483659, rehearse_rows=ROWS)
        job.setup()
        assert job.gbdt._can_fuse_iters() and job.gbdt._can_carry_rows()
        assert job.gbdt.learner.use_pallas
        job.run(1e-3, None)         # one chunk
        yield job


def test_the_kinds_checks_hold_on_the_fused_path(job):
    found = job.check()
    assert [name for name, _, _ in found] == [
        "no_degraded_path", "no_recompile_in_window", "plain_walk",
        "training_loss_falls", "fused_every_tree", "sampled_as_configured",
        "mask_honoured", "plain_first_splits"]
    assert all(holds for _, holds, _ in found), found
    said = found[-1][2]
    assert "tree 0 on" in said and "tree 8 on" in said
    assert said.count("8 splits") == 2 and "22 features" in said
    assert not job.failed and job.gbdt.iter_ == 16
    assert job.counters["sampling_fused_trees"] == 16
    assert job.counters["sampling_per_iteration_trees"] == 0


def test_a_program_without_the_counters_fails_before_any_data(config,
                                                              monkeypatch):
    from kinds import train_chunks_sub
    import gbdt_job
    import lightgbm_tpu.obs
    monkeypatch.setitem(sys.modules, "lightgbm_tpu.obs.sampling", None)
    monkeypatch.delattr(lightgbm_tpu.obs, "sampling")
    monkeypatch.setattr(gbdt_job, "make_data", lambda *a, **k: pytest.fail(
        "data was made for a program that cannot run the cell"))
    wl = {"kind": "train_chunks_sub", "trees_per_chunk": 8, "auc_trees": 16,
          "trace_units": 1}
    with pytest.raises(ImportError):
        train_chunks_sub.Job(config, wl, 1, rehearse_rows=ROWS).setup()


def test_every_sub_metric_has_something_to_read(job):
    """The host-clock and program-sourced metrics on the rehearsal itself,
    the trace-sourced ones on a made-up trace over the rehearsal's own chunk
    program: an op under ``gbdt.sample``, every kernel by its name."""
    import run
    from lightgbm_tpu.obs.scopes import op_scopes
    from readers import trace_scope
    assert "gbdt.sample" in trace_scope.all_scopes()
    text = job.gbdt.chunk_program_text(job.k)
    scope_of = op_scopes(text, trace_scope.all_scopes())
    drawn = [op for op, s in scope_of.items() if s == "gbdt.sample"]
    assert drawn, "no instruction of the chunk is under gbdt.sample"
    own = {op: 1000.0 for op in drawn[:3]}
    own.update({op: 1000.0 for op, s in scope_of.items()
                if s == "tree.find_split"})
    searched = len(own) - 3
    own.update({"%partition_hist_pallas_c4096.1": 5e6,
                "%histogram_pallas_rows_factored.3": 2e6,
                "%row_state_pass.4": 1e6, "%while.5": 3000.0})
    busy = sum(own.values())
    trace = {"own": own, "busy_ns": busy, "window_ns": busy / 0.99, "idle": {}}
    job.traced_trees = job.gbdt.models[8:16]
    ctx = {"job": job, "trace": trace, "cfg": job.cfg, "wl": job.wl,
           "device_kind": "TPU v5 lite"}
    got = run.layer_metrics(B, CELL, "train_chunks_sub", ctx)
    assert sorted(got) == SUB_METRICS
    mine = [name_in(got, name) for name in PR36_METRICS]
    assert None not in mine and len(set(mine)) == 16
    value = {name: got[name_in(got, name)]["value"] for name in PR36_METRICS}
    assert value["features_used_per_tree"] == 22
    assert value["per_iteration_trees"] == 0
    assert value["glue_sample_ms_per_tree"] == pytest.approx(3e-3 / 8)
    assert value["glue_find_split_ms_per_tree"] == pytest.approx(
        searched * 1e-3 / 8)
    assert 78 < value["bag_rows_share"] < 82
    # dead rows: 1 - the bag share, weighted by each tree's window rows
    assert 18 < value["dead_window_rows_share"] < 22
    shares = [t.internal_count[0] / ROWS for t in job.traced_trees]
    assert 100 * (1 - max(shares)) <= value["dead_window_rows_share"] \
        <= 100 * (1 - min(shares))
    # one level down (PR 39): the search's parts add up to their parent on
    # the subsampled program too
    find = [n for n in SUB_METRICS if n.startswith("find_")]
    assert len(find) == 6
    assert sum(got[n]["value"] for n in find) == pytest.approx(
        value["glue_find_split_ms_per_tree"], rel=1e-9)
    # the flagship's kind reads none of those 16
    flagship = run.layer_metrics(B, CELL, "train_chunks", ctx)
    assert not set(mine) & set(flagship)


def test_the_window_rows_really_hold_the_out_of_bag_rows(job):
    """What ``dead_window_rows_share.sub`` reckons from the bag share,
    counted: walk every training row down a traced tree and sum the rows of
    each split's window, in the bag and out of it."""
    import plain_tree
    codes = job.dataset.binned
    for it in (8, 12):
        tree = job.gbdt.models[it]
        bag = job.plain_bag(it)
        leaf = plain_tree.leaves_of(tree, codes)
        nodes = int(tree.num_leaves) - 1
        # rows under each internal node = rows of the leaves below it
        under = {}

        def rows_under(node):
            if node < 0:
                at = leaf == ~node
                return np.array([np.sum(at & bag), np.sum(at & ~bag)])
            if node not in under:
                under[node] = (rows_under(int(tree.left_child[node]))
                               + rows_under(int(tree.right_child[node])))
            return under[node]
        live, dead = sum(rows_under(n) for n in range(nodes))
        counted = sum(int(tree.internal_count[n]) for n in range(nodes))
        assert counted == pytest.approx(live, rel=0.02)   # in-bag estimates
        share = tree.internal_count[0] / ROWS
        assert dead == pytest.approx(live / share - live, rel=0.05)


# ---- the controls: each fault is seen --------------------------------------

@pytest.mark.parametrize("fault,passes,fails_at", [
    ("mask_never_redrawn", "tree 0 on", "tree 8"),
    ("bag_ignored", None, "tree 0"),
    ("bag_never_redrawn", "tree 0 on", "tree 8"),
])
def test_the_checks_see_the_fault(job, fault, passes, fails_at):
    """Last of the file: each retrains the job's booster with a fault in."""
    import controls_subsampled
    found = controls_subsampled.checks_under(
        job, getattr(controls_subsampled, fault))
    ok, said = found["plain_first_splits"]
    masks_ok, masks_said = found["mask_honoured"]
    bags_ok, bags_said = found["sampled_as_configured"]
    assert not (ok and masks_ok), found
    if fault == "mask_never_redrawn":
        assert not masks_ok and "(1, [" in masks_said    # from tree 1 on
        assert bags_ok, bags_said
    else:
        assert masks_ok
        # a wrong bag is another count than the plain bag's, to the row
        first = {"bag_ignored": "[(0, %d, " % ROWS,
                 "bag_never_redrawn": "[(5, "}[fault]
        assert not bags_ok and "plain bag: " + first in bags_said, bags_said
    if not ok:
        last = said.split("; tree ")[-1]
        assert last.startswith(fails_at.replace("tree ", "")) \
            or said.startswith(fails_at), said
        assert "short by" in said or "recorded gain" in said \
            or "plain splits against" in said
        if passes:
            assert said.startswith(passes) and "8 splits" in said
