"""``tree_learner=data`` as a deployment: per-row state lives with its rows.

On a mesh that shards rows, every per-row array of the boosting loop (labels,
scores, gradients, bag mask, ``row_leaf``) is created in the row blocks of
``learner.bins`` and stays there; the iteration's programs hold no collective
with a row-sized operand; what one tree sends across the mesh, read off the
compiled build program (``obs.comm.per_run``), is the closed form this file
states; and the model is the whole table's: the serial learner's, and the
plain NumPy grower's (``benchmarks/plain_tree.py``, which imports nothing of
the program).
"""
import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting.gbdt import GBDT
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.objective import create_objective
from lightgbm_tpu.obs import comm
from lightgbm_tpu.parallel import learners
from lightgbm_tpu.utils.log import Log
from lightgbm_tpu.parallel import (DataParallelTreeLearner,
                                   FeatureParallelTreeLearner,
                                   PartitionedDataParallelTreeLearner,
                                   VotingParallelTreeLearner, default_mesh)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F, ROWS, CHIPS, LEAVES = 67, 4001, 4, 15       # 67 pads to 68, 4001 to 4004


def _plain_tree():
    spec = importlib.util.spec_from_file_location(
        "plain_tree", os.path.join(REPO, "benchmarks", "plain_tree.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _table(rows=ROWS, features=F, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(rows, features)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] - 0.5 * X[:, features - 1]
         + rng.normal(scale=0.5, size=rows) > 0).astype(np.float32)
    return X, y


def _params(**more):
    return dict(objective="binary", num_leaves=LEAVES, min_data_in_leaf=5,
                verbosity=-1, **more)


@pytest.fixture
def four_chips(monkeypatch):
    """``lightgbm_tpu.train`` takes every device it finds; here it finds 4."""
    monkeypatch.setattr(learners, "default_mesh",
                        lambda *a, **k: default_mesh(CHIPS))


@pytest.fixture(scope="module")
def trained():
    """{tree_learner: GBDT after 4 iterations of ``lightgbm_tpu.train``}, the
    data-parallel one on a 4-device mesh."""
    X, y = _table()
    out = {}
    with pytest.MonkeyPatch.context() as m:
        m.setattr(learners, "default_mesh",
                  lambda *a, **k: default_mesh(CHIPS))
        for kind in ("data", "serial"):
            comm.reset()
            booster = lgb.train(_params(tree_learner=kind),
                                lgb.Dataset(X, label=y), num_boost_round=4)
            out[kind] = booster._booster
            out["comm", kind] = comm.per_tree()
    return out


def _row_blocks(array):
    """[(device id, first row, rows)] of a per-row array's shards, rows on
    the axis it is sharded over."""
    axis = max(range(array.ndim), key=lambda a: array.shape[a])
    return sorted((s.device.id, s.index[axis].start or 0, s.data.shape[axis])
                  for s in array.addressable_shards)


@pytest.mark.parametrize("name", ["train_score", "gradients", "hessians",
                                  "masked_gradients", "labels", "row_leaf"])
def test_per_row_state_is_sharded_in_the_blocks_of_bins(trained, name):
    g = trained["data"]
    learner = g.learner
    assert type(learner) is DataParallelTreeLearner
    assert learner.num_shards == CHIPS and g._rows_sharded
    rows = ROWS + learner.padded_rows
    assert rows % CHIPS == 0 and learner.padded_rows > 0
    grad, hess = g._get_gradients()
    arrays = {"train_score": g.train_score, "gradients": grad,
              "hessians": hess, "labels": g.objective.label,
              "masked_gradients": g._masked_gradients(grad[0], hess[0])[0],
              "row_leaf": g._last_iter_arrays[0].row_leaf}
    want = [(i, i * rows // CHIPS, rows // CHIPS) for i in range(CHIPS)]
    assert _row_blocks(learner.bins) == want
    assert _row_blocks(arrays[name]) == want, arrays[name].sharding
    if name == "masked_gradients":      # the padding rows carry nothing
        assert not np.asarray(arrays[name])[ROWS:].any()


def test_iteration_programs_have_no_row_collective(trained):
    g = trained["data"]
    texts = g.iteration_program_texts()
    # gradients, their finiteness, masking, score update, the sharded build
    assert len(texts) == 5 and set(g._row_fns) == {
        "gradients", "finite", "mask", "update_score/0"}
    assert any("reduce-scatter" in t or "all-reduce" in t for t in texts)
    assert g.count_row_collectives() == 0 == comm.row_collectives()
    assert trained["serial"].iteration_program_texts() is None


def test_count_row_collectives_sees_a_gathered_row_array(trained):
    """What the parent did every iteration: ``score[:, :num_data]`` of a
    row-sharded score is an all-gather of the whole row axis."""
    g = trained["data"]
    rows = g.train_score.shape[1]
    text = jax.jit(lambda s: s[:, :ROWS]).lower(
        g.train_score).compile().as_text()
    assert comm.count_row_collectives([text], (rows, rows // CHIPS)) == 1
    assert comm.count_row_collectives([text], (7,)) == 0


_OP = re.compile(r"stablehlo\.(all_gather|all_reduce|reduce_scatter|"
                 r"all_to_all|collective_permute)")
_BYTES = {"f32": 4, "i32": 4, "ui32": 4, "i1": 1}


def _lowered_collectives(learner, rows):
    """(count, operand bytes on one chip) of the collectives in the lowered
    build program: the root's and, once, the loop body's."""
    zeros = learner.pad_rows(jnp.zeros((rows,), jnp.float32))
    fm = jnp.ones((learner.feat.num_bin.shape[0],), bool)
    args = (learner.bins, zeros, zeros, jnp.int32(rows), fm, learner.feat)
    if isinstance(learner, PartitionedDataParallelTreeLearner):
        args += ((), ())
    lines = learner._build_fn.lower(*args, jnp.int32(0)).as_text().splitlines()
    count = nbytes = 0
    for i, line in enumerate(lines):
        if _OP.search(line) is None:
            continue
        operands = re.search(r":\s*\(([^)]*)\)\s*->",
                             " ".join(lines[i:i + 12])).group(1)
        for dims, dtype in re.findall(r"tensor<((?:\d+x)*)(\w+)>", operands):
            nbytes += _BYTES[dtype] * math.prod(
                int(d) for d in dims.split("x") if d)
        count += 1
    return count, nbytes


def closed_form(mode, features, bins, num_leaves, top_k=20):
    """(collectives, bytes of their operands) one chip takes part in per tree
    build with f32 histograms, as the builder is written: the root's, plus
    ``num_leaves - 1`` times a split's (the loop runs its collectives on dead
    iterations too).  ``features`` is the builder's histogram width (padded
    to the mesh in ``rs``), ``bins`` its kernel bin count.  A split reduces
    ONE histogram (the smaller child's) and syncs the best split of BOTH
    children in one vmapped collective."""
    hist = 8 * features * bins               # [F, 2, B] f32
    record = 4 * (12 + bins // 32)           # split.py::sync_best
    sums = (2, 8)                            # root sum_g, sum_h
    if mode == "rs":
        root, split = (2, hist + record), (2, hist + 2 * record)
    elif mode == "psum":
        root, split = (1, hist), (1, hist)
    elif mode == "feature":
        sums = (0, 0)                        # rows replicated
        root, split = (1, record), (1, 2 * record)
    else:                                    # voting
        kk = min(top_k, features)
        elected = 8 * min(2 * kk, features) * bins
        root = (3, 8 * kk + elected)
        split = (3, 2 * (8 * kk + elected))
    return tuple(s + r + (num_leaves - 1) * p
                 for s, r, p in zip(sums, root, split))


@pytest.mark.parametrize("cls,features,chips,max_bin", [
    (DataParallelTreeLearner, 67, 4, 255),
    (DataParallelTreeLearner, 28, 2, 63),
    (PartitionedDataParallelTreeLearner, 28, 2, 63),
    (FeatureParallelTreeLearner, 28, 2, 63),
    (VotingParallelTreeLearner, 28, 2, 63),
])
def test_comm_counters_are_the_closed_form(cls, features, chips, max_bin):
    X, y = _table(rows=1024, features=features)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=max_bin)
    cfg = Config(num_leaves=LEAVES, min_data_in_leaf=5, top_k=5)
    learner = cls(ds, cfg, mesh=default_mesh(chips))
    bins = max_bin + 1
    mode = learner.comm.mode
    padded = features + (-features) % chips if mode in (
        "rs", "feature") else features
    assert learner.num_bins == bins
    # the closed form against the program as written: the lowered text holds
    # the root's collectives and one split's, which is a tree of two leaves
    assert closed_form(mode, padded, bins, 2, top_k=5) == \
        _lowered_collectives(learner, 1024)
    # ... and against what obs.comm reads off the COMPILED program for a
    # build of LEAVES leaves: the loop's collectives once a trip.  The bytes
    # are the closed form's; the compiler may merge collectives that do not
    # depend on each other (the root's two sums), never add one.
    comm.reset()
    assert learner.comm_per_build() is None and comm.per_tree() is None
    g = jnp.asarray(0.5 - y)
    learner.train(g, jnp.full((1024,), 0.25, jnp.float32), 1024)
    per_tree = comm.per_tree()
    count, nbytes = closed_form(mode, padded, bins, LEAVES, top_k=5)
    assert per_tree["comm_bytes_per_tree"] == nbytes
    # (here: the root's sums into one all-reduce, in ``psum`` mode together
    # with the root's histogram)
    merged = {"rs": 1, "psum": 2, "feature": 0, "voting": 1}[mode]
    assert per_tree["collectives_per_tree"] == count - merged
    if mode == "rs":
        # the docstring's form: a reduce-scatter of [F, 2, B] f32 a split
        # (8 * F * B bytes a chip, of which a chip keeps 8 * F * B / d) and
        # ONE all-gather of the best-split record, 4 * (12 + B / 32) bytes
        # for each of the two children; the root's sums are 8 bytes
        record = 4 * (12 + bins // 32)
        hist = 8 * padded * bins
        assert (count, nbytes) == (4 + 2 * (LEAVES - 1),
                                   hist + 8 + record
                                   + (LEAVES - 1) * (hist + 2 * record))
        # two collectives a split, and the root's two sums as one all-reduce
        assert per_tree["collectives_per_tree"] == 3 + 2 * (LEAVES - 1)


def test_comm_counters_follow_the_program():
    """A collective taken out of, or put into, the compiled text moves the
    counters: they are read off the program, not reckoned beside it."""
    X, y = _table(rows=1024, features=28)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=63)
    learner = DataParallelTreeLearner(
        ds, Config(num_leaves=LEAVES, min_data_in_leaf=5),
        mesh=default_mesh(2))
    learner.train(jnp.asarray(0.5 - y), jnp.full((1024,), 0.25, jnp.float32),
                  1024)
    text = learner.compiled_build().as_text()
    count, nbytes = comm.per_run(text)
    assert (count, nbytes) == learner.comm_per_build()
    lines = text.splitlines()
    in_loop = next(i for i, ln in enumerate(lines)
                   if "all-gather(" in ln and "while/body" in ln)
    without = "\n".join(lines[:in_loop] + lines[in_loop + 1:])
    record = 4 * (12 + 64 // 32)
    assert comm.per_run(without) == (count - (LEAVES - 1),
                                     nbytes - (LEAVES - 1) * 2 * record)
    at_root = next(i for i, ln in enumerate(lines)
                   if "all-gather(" in ln and "while/body" not in ln)
    twice = "\n".join(lines[:at_root + 1] + [
        lines[at_root].replace(" = ", ".again = ", 1)] + lines[at_root + 1:])
    assert comm.per_run(twice) == (count + 1, nbytes + record)
    # a loop whose trip count the compiler does not state takes the caller's
    unstated = re.sub(r'"known_trip_count":\{"n":"\d+"\},?', "", text)
    assert comm.per_run(unstated, unknown_trips=3)[0] == \
        count - (LEAVES - 1 - 3) * 2


def test_comm_closed_form_of_the_deployment(trained):
    """(F, d, B) = (67, 4, 256) through ``lightgbm_tpu.train``."""
    count, nbytes = closed_form("rs", 68, 256, LEAVES)
    assert trained["comm", "serial"] is None
    assert (trained["comm", "data"]["collectives_per_tree"],
            trained["comm", "data"]["comm_bytes_per_tree"]) == (
                count - 1, nbytes)
    assert (count, nbytes) == (4 + 2 * 14,
                               139264 + 8 + 80 + 14 * (139264 + 160))


def _same_trees(a, b):
    assert len(a.models) == len(b.models) > 0
    for ta, tb in zip(a.models, b.models):
        n = ta.num_leaves
        assert n == tb.num_leaves == LEAVES
        assert np.array_equal(ta.split_feature_inner[:n - 1],
                              tb.split_feature_inner[:n - 1])
        assert np.array_equal(ta.threshold_in_bin[:n - 1],
                              tb.threshold_in_bin[:n - 1])
        # psum order differs from one device's sum: values agree, not bits
        np.testing.assert_allclose(ta.leaf_value[:n], tb.leaf_value[:n],
                                   rtol=1e-4, atol=1e-6)


def test_model_equals_the_serial_learners_tree_for_tree(trained):
    _same_trees(trained["data"], trained["serial"])


@pytest.mark.parametrize("bagging", [
    dict(bagging_fraction=0.7, bagging_freq=2),
    dict(pos_bagging_fraction=0.6, neg_bagging_fraction=0.9, bagging_freq=1),
], ids=["plain", "balanced"])
def test_bagged_model_equals_the_serial_learners(four_chips, bagging):
    X, y = _table()
    boosters = [lgb.train(_params(tree_learner=kind, **bagging),
                          lgb.Dataset(X, label=y), num_boost_round=4)._booster
                for kind in ("data", "serial")]
    g = boosters[0]
    assert g._rows_sharded and g.bag_mask is not None
    assert g.bag_mask.sharding.is_equivalent_to(g.learner.row_sharding, 1)
    assert not np.asarray(g.bag_mask)[ROWS:].any()
    assert g.bag_data_cnt == boosters[1].bag_data_cnt
    _same_trees(*boosters)
    assert g.count_row_collectives() == 0 and "bag_mask" in g._row_fns


def _gradients(plain, g, y, tree, score=None):
    """Binary logloss gradients of every row for tree ``tree`` of booster
    ``g``, from the plain walk of its earlier trees (or from ``score``)."""
    if score is None:
        score = (plain.scores_of(g.models[:tree], g.train_data.binned) if tree
                 else np.full(len(y), np.log(np.mean(y) / (1 - np.mean(y)))))
    p = 1.0 / (1.0 + np.exp(-score))
    return p - y, p * (1 - p)


def _plain_check(plain, g, y, tree, score=None, gains=None):
    """(the tree's first 8 splits, ``splits_agree`` of them against the
    plain grower on :func:`_gradients`, the recorded gains beside)."""
    mine = plain.tree_splits(g.models[tree], 8)
    steps = plain.grow_steps(g.train_data.binned,
                             *_gradients(plain, g, y, tree, score),
                             num_bins=256, splits=8, min_data_in_leaf=5,
                             min_sum_hessian_in_leaf=1e-3, follow=mine)
    if gains is None:
        gains = np.asarray(g.models[tree].split_gain[:8], np.float64)
    return mine, plain.splits_agree(steps, mine, gains)


@pytest.mark.parametrize("tree", [0, 3])
@pytest.mark.parametrize("kind", ["serial", "data"])
def test_first_splits_are_the_plain_growers(trained, kind, tree):
    """Tree 0 from the constant first score; tree 3 from what three score
    updates, gradients and maskings (shard-local under ``data``) made."""
    plain = _plain_tree()
    g = trained[kind]
    y = _table()[1].astype(np.float64)
    mine, (ok, message) = _plain_check(plain, g, y, tree)
    assert ok and message.startswith("8 splits") and "recorded gains" in message
    if tree == 0:       # the 8th is a tie of empty bins taken at the other end
        assert "1 near ties" in message
    # ... and a split the plain grower would not make is told apart
    steps = plain.grow(g.train_data.binned, *_gradients(plain, g, y, tree),
                       num_bins=256, splits=8, min_data_in_leaf=5,
                       min_sum_hessian_in_leaf=1e-3, follow=mine)
    leaf, feature, t = mine[5]
    other = mine[:5] + [(leaf, (feature + 7) % F, t)] + mine[6:]
    assert not plain.splits_agree(steps, other)[0]


def test_plain_walk_is_the_programs_score(trained):
    plain = _plain_tree()
    g = trained["data"]
    got = plain.scores_of(g.models, g.train_data.binned)
    np.testing.assert_allclose(got, np.asarray(g.train_score)[0, :ROWS],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("fault", ["stale_score_on_one_shard",
                                   "sums_of_three_shards"])
def test_the_plain_check_sees_a_shard_left_out(trained, fault):
    """What the benchmark's controls put into the program on the chip, here
    put into what the check is given: a model whose tree 3 was built from a
    score that one shard never updated, or whose recorded gains are those of
    three quarters of the rows, is not the whole table's."""
    plain = _plain_tree()
    g = trained["data"]
    y = _table()[1].astype(np.float64)
    if fault == "stale_score_on_one_shard":
        # the plain side walks every row; the "program" is then the one
        # whose gradients lagged on the first quarter: the same comparison
        # with the sides exchanged
        score = plain.scores_of(g.models[:3], g.train_data.binned)
        score[:ROWS // CHIPS] = np.log(np.mean(y) / (1 - np.mean(y)))
        ok, message = _plain_check(plain, g, y, 3, score=score)[1]
        assert not ok and "split 0" in message
    else:
        gains = 0.75 * np.asarray(g.models[0].split_gain[:8], np.float64)
        ok, message = _plain_check(plain, g, y, 0, gains=gains)[1]
        assert not ok and "recorded gain" in message and "0.25" in message


def test_plain_tree_imports_nothing_of_the_program():
    source = open(os.path.join(REPO, "benchmarks", "plain_tree.py")).read()
    assert "lightgbm_tpu" not in source.split('"""', 2)[2]
    assert re.findall(r"^(?:import|from) (\S+)", source, re.M) == [
        "__future__", "os", "concurrent.futures", "numpy"]


def _chunk_text(mesh, tree_learner):
    X, y = _table(rows=2048, features=8)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=63)
    cfg = Config(objective="binary", num_leaves=7, min_data_in_leaf=5,
                 tree_learner=tree_learner, verbosity=-1)
    g = GBDT(cfg, ds, create_objective("binary", cfg), mesh=mesh)
    assert not g._rows_sharded and g._can_fuse_iters()
    fn = (g._make_fused_train_carried(2) if g._can_carry_rows()
          else g._make_fused_train(2))
    return fn.lower(g.train_score, (), jnp.int32(0)).as_text()


def test_serial_chunk_program_is_unchanged_by_a_mesh_of_one_device():
    assert _chunk_text(None, "serial") == _chunk_text(default_mesh(1), "data")


@pytest.mark.parametrize("objective", ["regression", "multiclass",
                                       "multiclassova"])
def test_other_objectives_shard_their_rows_too(four_chips, objective):
    X, y = _table(rows=1201, features=8)
    label = (y + (X[:, 3] > 0)) if "multi" in objective else X[:, 0] + y
    more = dict(num_class=3) if "multi" in objective else {}
    params = dict(_params(), objective=objective, **more)
    boosters = [lgb.train(dict(params, tree_learner=kind),
                          lgb.Dataset(X, label=label),
                          num_boost_round=3)._booster
                for kind in ("data", "serial")]
    g = boosters[0]
    assert g._rows_sharded and g.count_row_collectives() == 0
    assert g.train_score.sharding.is_equivalent_to(
        jax.sharding.NamedSharding(
            g.learner.mesh, jax.sharding.PartitionSpec(None, "data")), 2)
    np.testing.assert_allclose(
        np.asarray(g.train_score)[:, :1201],
        np.asarray(boosters[1].train_score)[:, :1201], rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("boosting", ["goss", "dart", "rf"])
def test_boosters_that_read_whole_table_arrays_keep_their_state(four_chips,
                                                               boosting):
    X, y = _table(rows=1201, features=8)
    more = (dict(bagging_fraction=0.7, bagging_freq=1, feature_fraction=0.8)
            if boosting == "rf" else {})
    said, level = [], Log._level
    Log.reset_callback(said.append)
    try:
        g = lgb.train(dict(_params(tree_learner="data", boosting=boosting),
                           **more, verbosity=0),
                      lgb.Dataset(X, label=y), num_boost_round=3)._booster
    finally:
        Log.reset_callback(None)
        Log.reset_level(level)
    assert not g._rows_sharded and g.iteration_program_texts() is None
    assert g.count_row_collectives() is None
    # ... and says so: the data-parallel contract does not hold for them
    assert sum("boosting=%s keeps scores and gradients whole" % boosting
               in line for line in said) == 1, said
    assert len(g.models) == 3
