"""Categorical columns handed over as categories, end to end at a small size:
the program's categorical bin finder and search on the fused carried path in
interpret mode, held to the plain grower and walk of
``benchmarks/plain_categorical.py`` (which imports nothing of the program);
the bounded many-vs-many scan against the whole one; the cell
``expo_cat_train``'s layout, kind, counters, scopes and controls.
"""
import copy
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
sys.path[:0] = [BENCH, os.path.join(BENCH, "tests")]

import plain_categorical as pc  # noqa: E402

from lightgbm_tpu.boosting.gbdt import GBDT  # noqa: E402
from lightgbm_tpu.config import Config  # noqa: E402
from lightgbm_tpu.core import split as S  # noqa: E402
from lightgbm_tpu.io.binning import MissingType  # noqa: E402
from lightgbm_tpu.io.dataset import BinnedDataset  # noqa: E402
from lightgbm_tpu.obs import categorical  # noqa: E402
from lightgbm_tpu.obs.scopes import FIND_CAT_PARTS  # noqa: E402
from lightgbm_tpu.objective import create_objective  # noqa: E402

ROWS = 4096
TREES = 5
SPLITS = 8
B = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELL = "expo_cat_train"
CAT_METRICS = sorted(m["name"] for m in B["per_layer"]
                     if CELL in m.get("workloads", ()))

# (levels of each categorical column, max_bin, how fast a level's share
# falls): what each case is about
CASES = {
    "one_against_the_rest": ((3, 4), 255, 1.2),  # num_bin <= max_cat_to_onehot
    "many_vs_many": ((12, 29), 255, 1.2),
    "more_sortable_bins_than_max_cat_threshold": ((90, 7), 255, 1.2),
    # a few levels hold 99% of the rows: the rest go to the other-bin
    "more_levels_than_max_bin": ((300, 12), 63, 3.0),
}
PARAMS = dict(objective="binary", num_leaves=15, learning_rate=0.1,
              min_data_in_leaf=0, min_sum_hessian_in_leaf=5.0, cat_smooth=10,
              cat_l2=10, max_cat_threshold=32, max_cat_to_onehot=4,
              min_data_per_group=50)


def make_table(levels, seed=7, rows=ROWS, falls=1.2):
    """Categorical columns with Zipf-like levels, one numeric column, and a
    label that depends on each level's own effect."""
    rng = np.random.default_rng(seed)
    cols, g = [], np.zeros(rows)
    for lv in levels:
        p = (np.arange(1, lv + 1) + 3.0) ** -falls
        code = rng.choice(lv, size=rows, p=p / p.sum())
        g += rng.normal(size=lv)[code]
        cols.append(code)
    x = rng.normal(size=rows)
    X = np.stack(cols + [x], axis=1).astype(np.float32)
    y = (g + 0.7 * x + rng.normal(size=rows) > 0).astype(np.float32)
    return X, y


_trained = {}


def trained(case):
    """(data set, booster after TREES trees on the fused path in interpret
    mode, the table) of a case, trained once a session."""
    if case not in _trained:
        levels, max_bin, falls = CASES[case]
        X, y = make_table(levels, falls=falls)
        cats = list(range(len(levels)))
        ds = BinnedDataset.from_matrix(X, label=y, max_bin=max_bin,
                                       min_data_in_leaf=0,
                                       categorical_feature=cats)
        cfg = Config(verbosity=-1, max_bin=max_bin, categorical_feature=cats,
                     **PARAMS)
        g = GBDT(cfg, ds, create_objective("binary", cfg))
        g.learner.use_pallas = g.learner.pallas_interpret = True
        assert g._fuse_refusal() is None
        g.train_chunk(TREES)
        assert g.iter_ == TREES and not g._fuse_failed
        _trained[case] = (ds, g, X, y, max_bin)
    return _trained[case]


def columns_of(ds):
    from kinds import train_chunks_cat
    return train_chunks_cat.plain_columns(ds)


# ---- the program against the plain reference -------------------------------

@pytest.mark.parametrize("case", list(CASES))
def test_the_cases_table_is_what_the_case_says(case):
    ds, g, X, y, max_bin = trained(case)
    columns, p = columns_of(ds), pc.params_of(PARAMS)
    cat = [c for c in columns if c.categorical]
    assert len(cat) == len(CASES[case][0]) and not columns[-1].categorical
    if case == "one_against_the_rest":
        assert all(pc.is_onehot(c, p) for c in cat)
    elif case == "more_sortable_bins_than_max_cat_threshold":
        assert cat[0].used_bin > 2 * p.max_cat_threshold
    elif case == "more_levels_than_max_bin":
        # the rarest levels share the other-bin, which is never searched
        assert cat[0].num_bin <= max_bin and cat[0].used_bin \
            == cat[0].num_bin - 1
        assert len(np.unique(X[:, 0])) > cat[0].num_bin
    else:
        assert not any(pc.is_onehot(c, p) for c in cat)


@pytest.mark.parametrize("tree", [0, TREES - 1])
@pytest.mark.parametrize("case", list(CASES))
def test_first_splits_are_the_plain_growers(case, tree):
    ds, g, X, y, max_bin = trained(case)
    columns, p = columns_of(ds), pc.params_of(PARAMS)
    model = g.models[tree]
    mine = pc.tree_splits(model, SPLITS)
    assert len(mine) == SPLITS
    grad, hess = pc.binary_gradients(y, g.models[:tree], ds.binned,
                                     max_bin + 1)
    ok, found, many = pc.splits_agree(
        pc.grow_steps(ds.binned, grad, hess, columns, p,
                      num_bins=max_bin + 1, splits=SPLITS, follow=mine),
        mine, np.asarray(model.split_gain[:SPLITS], np.float64), columns, p)
    assert ok, found
    categorical_nodes = sum(columns[f].categorical for _, f, _ in mine)
    assert categorical_nodes, "no categorical split among the first eight"
    assert many == (0 if case == "one_against_the_rest"
                    else categorical_nodes)


@pytest.mark.parametrize("case", list(CASES))
def test_every_trees_walk_is_the_plain_walk(case):
    ds, g, X, y, max_bin = trained(case)
    Xn = np.concatenate([X[:512], make_table(CASES[case][0], seed=99,
                                             rows=512, falls=1.2)[0]])
    # never seen: a negative or a large value goes right, NaN counts as
    # category 0 where the column's mapping has no other-bin
    Xn[-3:, 0] = [-1.0, 100000.0, np.nan]
    for trees in range(1, TREES + 1):
        got = np.asarray(g.predict(Xn, raw_score=True, num_iteration=trees),
                         np.float64).reshape(-1)
        assert np.max(np.abs(got - pc.walk(g.models[:trees], Xn))) <= 1e-5
    # ... and in bin space the training rows reach the leaves the program's
    # own scores say they do
    scores = pc.scores_of(g.models, ds.binned, max_bin + 1)
    mine = np.asarray(g.train_score)[0, :len(y)]
    assert np.max(np.abs(scores - mine)) <= 1e-5


@pytest.mark.parametrize("case", list(CASES))
def test_leaf_values_carry_cat_l2_where_a_many_vs_many_split_made_them(case):
    ds, g, X, y, max_bin = trained(case)
    tree = g.models[0]
    grad, hess = pc.binary_gradients(y, [], ds.binned, max_bin + 1)
    mean = float(np.mean(y))
    want = pc.leaf_values(tree, pc.leaves_of(tree, ds.binned, max_bin + 1),
                          grad, hess, columns_of(ds), pc.params_of(PARAMS),
                          PARAMS["learning_rate"],
                          bias=np.log(mean / (1 - mean)))
    got = np.asarray(tree.leaf_value[:tree.num_leaves], np.float64)
    assert np.max(np.abs(got - want)) <= 1e-5


# ---- the bounded scan ------------------------------------------------------

def _search_inputs(seed, F=5, bins=256, used=(200, 90, 40, 3, 33)):
    rng = np.random.default_rng(seed)
    num_bin = np.asarray(used, np.int32)
    live = np.arange(bins)[None, :] < num_bin[:, None]
    rows = np.where(live, rng.integers(0, 400, size=(F, bins)), 0)
    h = (0.25 * rows).astype(np.float32)
    g = np.where(live, rng.normal(size=(F, bins)) * np.sqrt(rows + 1.0),
                 0.0).astype(np.float32)
    hist = jnp.asarray(np.stack([g, h], axis=1))
    feat = S.FeatureInfo(
        num_bin=jnp.asarray(num_bin),
        missing_type=jnp.asarray([int(MissingType.NONE), int(MissingType.NAN),
                                  int(MissingType.NONE), int(MissingType.NONE),
                                  int(MissingType.NAN)], jnp.int32),
        default_bin=jnp.zeros(F, jnp.int32),
        is_categorical=jnp.ones(F, bool))
    return hist, feat, jnp.ones(F, bool), jnp.float32(g.sum(axis=1)[0]), \
        jnp.float32(h.sum(axis=1)[0]), jnp.int32(rows.sum(axis=1)[0])


@pytest.mark.parametrize("seed,how", [
    (0, {}), (1, {"min_data_per_group": 1000}), (2, {"cat_smooth": 150.0}),
    (3, {"max_cat_threshold": 7}), (4, {"min_sum_hessian_in_leaf": 900.0})])
def test_the_bounded_scan_is_the_whole_scan_bit_for_bit(seed, how):
    hist, feat, mask, sg, sh, n = _search_inputs(seed)
    # one total for every feature: make each feature's bins add up to it
    hist = hist.at[1:, :, 255].add(hist[0].sum(axis=1)[None, :]
                                   - hist[1:].sum(axis=2))
    p = S.SplitParams(**dict(dict(min_data_in_leaf=0,
                                  min_sum_hessian_in_leaf=100.0), **how))
    assert S.cat_scan_steps(256, p) == min(32, p.max_cat_threshold)
    run = jax.jit(S.per_feature_best_categorical, static_argnums=(6, 9))
    bounded = run(hist, feat, mask, sg, sh, n, p, None, None, None)
    whole = run(hist, feat, mask, sg, sh, n, p, None, None, 256)
    sortable = (np.asarray(hist[:, 1, :]) * float(n) / float(sh) >= p.cat_smooth
                ).sum(axis=1)
    assert sortable.max() > 2 * 32, sortable    # the bound cuts real steps
    assert np.isfinite(np.asarray(bounded.gain)).sum() >= 2
    for name, a, b in zip(bounded._fields, bounded, whole):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
    assert np.asarray(bounded.cat_bitset).any()


# ---- the counters ----------------------------------------------------------

def test_the_counts_say_what_the_search_sorts_and_what_the_trees_hold():
    categorical.reset()
    _trained.pop("many_vs_many", None)
    ds, g, *_ = trained("many_vs_many")
    assert categorical.counts() == {
        "cat.features": 2, "cat.bins": 12 + 29, "cat.scan_steps": 32,
        "cat.splits": 0, "cat.onehot_splits": 0}
    trees = g.models                       # turned into host trees: counted
    found = categorical.counts()
    assert found["cat.splits"] == sum(t.num_cat for t in trees) > 0
    assert found["cat.onehot_splits"] == 0
    categorical.reset()
    assert categorical.counts()["cat.splits"] == 0
    assert categorical.counts()["cat.bins"] == 41


def test_one_against_the_rest_splits_are_counted_apart():
    categorical.reset()
    _trained.pop("one_against_the_rest", None)
    ds, g, *_ = trained("one_against_the_rest")
    splits = sum(t.num_cat for t in g.models)
    found = categorical.counts()
    assert found["cat.splits"] == found["cat.onehot_splits"] == splits > 0


def test_a_numerical_learner_scans_nothing():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(512, 3)).astype(np.float32)
    ds = BinnedDataset.from_matrix(X, label=(X[:, 0] > 0).astype(np.float32))
    cfg = Config(verbosity=-1, objective="binary", num_leaves=4)
    GBDT(cfg, ds, create_objective("binary", cfg))
    found = categorical.counts()
    assert (found["cat.features"], found["cat.bins"],
            found["cat.scan_steps"]) == (0, 0, 0)


# ---- the cell's layout -----------------------------------------------------

def test_the_cell_its_configuration_and_its_eight_entries():
    cell = next(w for w in B["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "expo-cat", "chunks_k8_cat", 1)
    assert len(B["workloads"]) == len(B["configs"]) == 5
    entry = next(c for c in B["configs"] if c["name"] == "expo-cat")
    cfg = json.load(open(os.path.join(REPO, entry["file"])))
    assert entry["reduced"] == cfg["reduced"] == ["trees"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    one_hot = json.load(open(os.path.join(BENCH, "configs",
                                          "expo-onehot.json")))
    assert cfg["generator"] == one_hot["generator"]     # the same rows
    assert (cfg["rows"], cfg["heldout_rows"]) == (one_hot["rows"],
                                                  one_hot["heldout_rows"])
    assert cfg["features"] == 8 == len(cfg["generator"]["blocks"]) + len(
        cfg["generator"]["numeric"])
    params = cfg["params"]
    assert params["categorical_feature"] == [0, 1, 2, 3, 4, 5]
    assert {k: params[k] for k in ("cat_smooth", "cat_l2", "max_cat_threshold",
                                   "max_cat_to_onehot", "min_data_per_group")
            } == dict(cat_smooth=10, cat_l2=10, max_cat_threshold=32,
                      max_cat_to_onehot=4, min_data_per_group=100)
    for k in ("objective", "num_leaves", "learning_rate", "max_bin",
              "min_data_in_leaf", "min_sum_hessian_in_leaf"):
        assert params[k] == one_hot["params"][k], k
    traffic = json.load(open(os.path.join(BENCH, "traffic",
                                          "chunks_k8_cat.json")))
    assert traffic["kind"] == "train_chunks_cat"
    assert traffic["trace_first_tree"] == 64
    assert 64 % traffic["trees_per_chunk"] == 0
    assert traffic["trees_per_chunk"] * traffic["trace_units"] == 8


def test_the_eight_entries_are_the_last_and_the_cells_alone():
    assert len(B["per_layer"]) == 128 and len(CAT_METRICS) == 8
    assert sorted(m["name"] for m in B["per_layer"][-8:]) == CAT_METRICS
    for m in B["per_layer"][-8:]:
        assert m["name"].endswith(".cat") and m["workloads"] == [CELL]
        assert m["moves"] == "train_row_trees_per_s"
        spec = json.load(open(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".json")))
        assert spec["kinds"] == ["train_chunks_cat"]
    # the search's parts are read among themselves: a trace_scope file on one
    # of them would take its ops out of tree.find_split in the parents' map
    from readers import trace_scope
    assert not set(trace_scope.all_scopes()) & set(FIND_CAT_PARTS)
    for name, scope in (("find_cat_sort_ms_per_tree.cat", "find.cat_sort"),
                        ("find_cat_scan_ms_per_tree.cat", "find.cat_scan")):
        spec = json.load(open(os.path.join(BENCH, "layer_metrics",
                                           name + ".json")))
        sub = json.load(open(os.path.join(
            BENCH, "layer_metrics", "glue_find_split_ms_per_tree.sub.json")))
        assert spec["reader"] == "trace_scope_among"
        assert spec["args"] == {
            "scope": scope, "among": list(FIND_CAT_PARTS),
            "within": ["tree.find_split"],
            "exclude_prefixes": sub["args"]["exclude_prefixes"]}


# ---- the kind --------------------------------------------------------------

@pytest.fixture(scope="module")
def config():
    """The cell's configuration at 15 leaves."""
    cfg = copy.deepcopy(json.load(open(os.path.join(BENCH, "configs",
                                                    "expo-cat.json"))))
    cfg["params"].update(num_leaves=15, min_sum_hessian_in_leaf=5)
    return cfg


@pytest.fixture(scope="module")
def job(config):
    """The benchmark's kind on it: a rehearsal in this process (interpret-mode
    kernels, the fused carried path), set up and run for a window of one
    chunk."""
    from kinds import train_chunks_cat
    from lightgbm_tpu import resilience
    from lightgbm_tpu.plan import cache as plan_cache
    resilience.reset_fallbacks()      # the process's counters: other files'
    plan_cache.reset_fallbacks()      # tests of the degraded paths raise them
    with pytest.MonkeyPatch.context() as m:
        m.setenv("LIGHTGBM_TPU_PALLAS_INTERPRET", "1")
        wl = {"kind": "train_chunks_cat", "trees_per_chunk": 2,
              "auc_trees": 2, "trace_units": 1}
        job = train_chunks_cat.Job(config, wl, 2147483659, rehearse_rows=8192)
        job.setup()
        assert job.gbdt._can_fuse_iters() and job.gbdt._can_carry_rows()
        assert job.gbdt.learner.has_categorical
        assert not job.gbdt.learner.grouped
        job.run(1e-3, None)         # one chunk
        yield job


def test_the_kinds_checks_hold_on_the_fused_path(job):
    found = job.check()
    assert [name for name, _, _ in found] == [
        "no_degraded_path", "no_recompile_in_window", "training_loss_falls",
        "categorical_ingest", "plain_first_splits", "plain_leaf_values",
        "plain_walk"]
    assert all(holds for _, holds, _ in found), found
    said = dict((name, what) for name, _, what in found)
    assert "tree 0 (" in said["plain_first_splits"]
    assert "tree 2 (" in said["plain_first_splits"]
    assert "8 splits" in said["plain_first_splits"]
    assert "categorical nodes" in said["plain_walk"]
    assert not job.failed and job.gbdt.iter_ == 4 and not job.gbdt._fuse_failed
    assert job.counters["cat_scan_steps"] == 32
    assert job.counters["cat_features"] == 6
    assert job.counters["cat_splits"] > 0


def test_a_program_without_the_counters_fails_before_any_data(config,
                                                              monkeypatch):
    import datagen_onehot
    import lightgbm_tpu.obs
    from kinds import train_chunks_cat
    monkeypatch.setitem(sys.modules, "lightgbm_tpu.obs.categorical", None)
    monkeypatch.delattr(lightgbm_tpu.obs, "categorical")
    monkeypatch.setattr(datagen_onehot, "draw", lambda *a, **k: pytest.fail(
        "data was made for a program without the categorical counters"))
    wl = {"kind": "train_chunks_cat", "trees_per_chunk": 2, "auc_trees": 2,
          "trace_units": 1}
    with pytest.raises(ImportError):
        train_chunks_cat.Job(config, wl, 1, rehearse_rows=4096).setup()


def test_the_search_is_under_its_scopes_in_a_categorical_program_only(job):
    text = job.gbdt.chunk_program_text(job.k)
    for part in FIND_CAT_PARTS:
        under = [ln for ln in text.splitlines() if part in ln]
        assert under, part
        # inside the split loop under the children's vmap, and at the root
        assert any("tree.find_split/vmap(%s)" % part in ln for ln in under)
        assert any("tree.root/%s" % part in ln for ln in under), part
    ds, g, *_ = trained("many_vs_many")
    assert "find.cat_" in g.chunk_program_text(TREES)
    rng = np.random.default_rng(1)
    X = rng.normal(size=(ROWS, 3)).astype(np.float32)
    nds = BinnedDataset.from_matrix(X, label=(X[:, 0] + X[:, 1] > 0).astype(
        np.float32), max_bin=63)
    cfg = Config(verbosity=-1, objective="binary", num_leaves=7,
                 min_data_in_leaf=5)
    n = GBDT(cfg, nds, create_objective("binary", cfg))
    n.learner.use_pallas = n.learner.pallas_interpret = True
    n.train_chunk(2)
    plain = n.chunk_program_text(2)
    assert "tree.find_split" in plain and "find.cat_" not in plain


def test_every_cat_metric_has_something_to_read(job):
    """The host-clock and program-sourced metrics on the rehearsal itself,
    the trace-sourced ones on a made-up trace over the rehearsal's own chunk
    program: every op of the search, every kernel by its name."""
    import run
    from lightgbm_tpu.obs.scopes import op_scopes
    from readers import trace_scope
    text = job.gbdt.chunk_program_text(job.k)
    scope_of = op_scopes(text, trace_scope.all_scopes())
    own = {op: 1000.0 for op, s in scope_of.items() if s == "tree.find_split"}
    own.update({"%partition_hist_pallas_c4096.1": 5e6,
                "%partition_hist_pallas_small.2": 1e6,
                "%histogram_pallas_rows.3": 2e6, "%while.5": 3000.0})
    busy = sum(own.values())
    trace = {"own": own, "busy_ns": busy, "window_ns": busy / 0.9, "idle": {}}
    job.traced_trees = job.gbdt.models[:2]
    ctx = {"job": job, "trace": trace, "cfg": job.cfg, "wl": job.wl,
           "device_kind": "TPU v5 lite"}
    got = run.layer_metrics(B, CELL, "train_chunks_cat", ctx)
    assert sorted(got) == CAT_METRICS
    value = {name: m["value"] for name, m in got.items()}
    assert value["split_kernel_ms_per_tree.cat"] == pytest.approx(3.0)
    assert value["device_idle_share.cat"] == pytest.approx(10.0)
    assert value["cat_scan_steps_per_leaf.cat"] == 32
    splits = sum(t.num_leaves - 1 for t in job.traced_trees)
    assert value["cat_splits_share.cat"] == pytest.approx(
        100.0 * sum(t.num_cat for t in job.traced_trees) / splits)
    assert 0 < value["cat_splits_share.cat"] <= 100
    sort, scan = (value["find_cat_sort_ms_per_tree.cat"],
                  value["find_cat_scan_ms_per_tree.cat"])
    assert sort > 0 and scan > 0
    assert sort + scan <= value["glue_find_split_ms_per_tree.cat"]
    # the other cells' entries are not this kind's, and the parents' map is
    # what it was: no cat scope among trace_scope's
    assert not set(scope_of.values()) & set(FIND_CAT_PARTS)


@pytest.mark.parametrize("fault,sees", [
    ("cat_l2_zero_in_the_search", ("plain_first_splits",
                                   "plain_leaf_values")),
    ("a_left_bin_flipped_before_routing", ("plain_first_splits",
                                           "plain_leaf_values"))])
def test_the_checks_see_the_fault(job, fault, sees):
    """Last of the file: each retrains the job's booster with a fault in."""
    import controls_categorical
    found = controls_categorical.checks_under(
        job, getattr(controls_categorical, fault))
    assert any(not found[name][0] for name in sees), found
    assert found["plain_walk"][0], found["plain_walk"]


def test_the_rehearsal_reaches_its_result_line(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147483659", "--seconds", "1", "--trace", "1",
         "--rehearse-rows", "4096"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=1200)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is False and last["metrics"] == {}
    # 15 chunks of 4 trees from the warm-up's to tree 64, then the traced two
    assert last["failed"] == 0 and last["attempted"] == 17
    checks = [ln for ln in lines if ln.startswith(("ok ", "NOT"))]
    assert len(checks) == 7 and all(ln.startswith("ok ") for ln in checks)
    assert "traced trees 64-71" in "\n".join(lines)
