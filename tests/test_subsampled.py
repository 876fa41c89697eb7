"""Row and column subsampling on the fused path: ``feature_fraction`` and plain
bagging are stateless functions of the iteration, drawn inside the chunk
program's scan, so fused chunks and ``train_one_iter`` grow the same model,
and a rollback, a retry or a resume needs no RNG state.

"Equal tree for tree" is the carried path's own standard
(tests/test_carried_rows.py): the same splits in the same order, leaf values
to f32 rounding (the fused scan sums gradients in another order)."""
import copy

import numpy as np
import pytest

import jax

from lightgbm_tpu.boosting import gbdt as G
from lightgbm_tpu.boosting.gbdt import GBDT, feature_mask_of, features_used
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.objective import create_objective
from lightgbm_tpu.obs import sampling, spans
from lightgbm_tpu.utils.log import Log

ROWS = 2048
SAMPLING = {
    "features": dict(feature_fraction=0.8),
    "bag": dict(bagging_fraction=0.8, bagging_freq=5),
    "both": dict(feature_fraction=0.8, bagging_fraction=0.8, bagging_freq=5),
}
TREES = 16


def _plain_table():
    rng = np.random.RandomState(5)
    X = rng.normal(size=(ROWS, 10)).astype(np.float32)
    y = X[:, 0] + X[:, 1] * X[:, 2] + rng.normal(scale=0.5, size=ROWS) > 0
    return BinnedDataset.from_matrix(X, label=y.astype(np.float32),
                                     max_bin=63)


def _bundled_table():
    """Two one-hot blocks of 20 and 12 levels and two numeric columns: 34
    features in 4 device columns."""
    rng = np.random.RandomState(3)
    a, b = rng.randint(0, 20, size=ROWS), rng.randint(0, 12, size=ROWS)
    X = np.zeros((ROWS, 34), np.float32)
    X[np.arange(ROWS), a] = 1
    X[np.arange(ROWS), 20 + b] = 1
    X[:, 32:] = rng.normal(size=(ROWS, 2))
    y = (rng.normal(size=20)[a] + rng.normal(size=12)[b] + X[:, 32]
         + rng.normal(size=ROWS) > 0)
    ds = BinnedDataset.from_matrix(X, label=y.astype(np.float32), max_bin=255,
                                   min_data_in_leaf=0)
    assert [len(g) for g in ds.feature_groups] == [20, 12, 1, 1]
    return ds


@pytest.fixture(scope="module")
def tables():
    return {"plain": _plain_table(), "bundled": _bundled_table()}


def booster(ds, **params):
    cfg = Config(objective="binary", num_leaves=7, min_data_in_leaf=5,
                 min_sum_hessian_in_leaf=1.0, verbosity=-1, **params)
    return GBDT(cfg, ds, create_objective("binary", cfg))


def assert_same_trees(got, want, leaf_rtol=2e-4):
    assert len(got) == len(want)
    for i, (a, b) in enumerate(zip(got, want)):
        n = int(a.num_leaves)
        assert n == int(b.num_leaves), "tree %d" % i
        for field in ("split_feature_inner", "threshold_in_bin",
                      "left_child", "right_child"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a, field))[:n - 1],
                np.asarray(getattr(b, field))[:n - 1],
                err_msg="tree %d %s" % (i, field))
        np.testing.assert_allclose(a.leaf_value[:n], b.leaf_value[:n],
                                   rtol=leaf_rtol, atol=2e-5,
                                   err_msg="tree %d" % i)


@pytest.fixture(scope="module")
def per_iteration(tables):
    """{(table, sampling): the 16 trees ``train_one_iter`` grows}."""
    grown = {}

    def of(table, how):
        if (table, how) not in grown:
            g = booster(tables[table], **SAMPLING[how])
            for _ in range(TREES):
                g.train_one_iter()
            grown[table, how] = g.models
        return grown[table, how]
    return of


# ---- fused chunks against train_one_iter -----------------------------------

@pytest.mark.parametrize("chunk", [1, 5, 8])
@pytest.mark.parametrize("how", list(SAMPLING))
@pytest.mark.parametrize("table", ["plain", "bundled"])
def test_fused_chunks_grow_the_per_iteration_model(tables, per_iteration,
                                                   table, how, chunk):
    """Chunks of 1, 5 and 8 against a bag window of 5: windows turn over
    inside a chunk and a chunk starts mid-window."""
    sampling.reset()
    g = booster(tables[table], **SAMPLING[how])
    assert g._can_fuse_iters() and g._can_carry_rows()
    assert g.learner.grouped == (table == "bundled")
    trees = chunk * (2 if chunk > 1 else 6)
    for _ in range(trees // chunk):
        g.train_chunk(chunk)
    counts = sampling.counts()
    assert counts["sampling.fused_trees"] == trees == g.iter_
    assert counts["sampling.per_iteration_trees"] == 0
    assert_same_trees(g.models, per_iteration(table, how)[:trees])
    # the newest tree's bag is its root's count, the realised draw
    assert counts["sampling.bag_rows"] == int(g.models[-1].internal_count[0])
    if "bagging_freq" in SAMPLING[how]:
        assert 0.7 * ROWS < counts["sampling.bag_rows"] < 0.9 * ROWS
    else:
        assert counts["sampling.bag_rows"] == ROWS


def test_the_acceptance_configuration_runs_one_fused_program_a_chunk(tables):
    sampling.reset()
    g = booster(tables["plain"], **SAMPLING["both"])
    g.train_chunk(8)
    assert len(g._fused_cache) == 1 and not g._fuse_failed
    assert sampling.counts() == {
        "sampling.features": 10, "sampling.features_used": 8,
        "sampling.bag_fraction": 0.8, "sampling.bag_freq": 5,
        "sampling.fused_trees": 8, "sampling.per_iteration_trees": 0,
        "sampling.bag_rows": int(g.models[-1].internal_count[0])}


def test_the_plain_fused_program_samples_alike(tables):
    """A weighted data set cannot carry its rows: ``_make_fused_train``'s own
    scan draws the same mask and bag."""
    ds = _plain_table()
    ds.metadata.set_weights(np.linspace(0.5, 1.5, ROWS).astype(np.float32))
    g = booster(ds, **SAMPLING["both"])
    assert g._can_fuse_iters() and not g._can_carry_rows()
    g.train_chunk(8)
    h = booster(ds, **SAMPLING["both"])
    for _ in range(8):
        h.train_one_iter()
    assert_same_trees(g.models, h.models)


# ---- the mask --------------------------------------------------------------

@pytest.mark.parametrize("features,fraction,used", [
    (28, 0.8, 22), (10, 0.8, 8), (700, 0.5, 350), (3, 0.1, 1), (28, 1.0, 28),
    (1, 0.5, 1)])
def test_the_mask_holds_exactly_its_count(features, fraction, used):
    assert features_used(features, fraction) == used
    masks = [np.asarray(feature_mask_of(features, used, 2, it))
             for it in range(6)]
    assert all(m.dtype == bool and m.shape == (features,) and m.sum() == used
               for m in masks)
    if 1 < used < features:
        assert len({m.tobytes() for m in masks}) > 1   # iterations differ


def test_the_mask_is_a_function_of_seed_and_iteration_only(tables):
    a = booster(tables["plain"], feature_fraction=0.5)
    b = booster(tables["plain"], feature_fraction=0.5)
    for _ in range(3):
        b.train_one_iter()                  # a draws nothing meanwhile
    b.iter_ = 0
    for it in (0, 7, 1, 7):
        np.testing.assert_array_equal(np.asarray(a._feature_mask(it)),
                                      np.asarray(b._feature_mask(it)))
    other = booster(tables["plain"], feature_fraction=0.5,
                    feature_fraction_seed=9)
    assert any(not np.array_equal(np.asarray(a._feature_mask(it)),
                                  np.asarray(other._feature_mask(it)))
               for it in range(4))
    # traced, as the scan draws it
    traced = jax.jit(lambda it: a._feature_mask(it))(np.int32(7))
    np.testing.assert_array_equal(np.asarray(traced),
                                  np.asarray(a._feature_mask(7)))
    assert booster(tables["plain"])._feature_mask() is None


@pytest.mark.parametrize("table", ["plain", "bundled"])
def test_no_tree_splits_outside_its_mask(tables, table):
    g = booster(tables[table], feature_fraction=0.3)
    g.train_chunk(8)
    nf, used = tables[table].num_features, g._features_used()
    assert used == max(1, round(nf * 0.3))
    seen = set()
    for it, tree in enumerate(g.models):
        mask = np.asarray(feature_mask_of(nf, used, 2, it))
        split_on = np.asarray(
            tree.split_feature_inner[:int(tree.num_leaves) - 1])
        assert mask[split_on].all(), (it, split_on, np.flatnonzero(mask))
        seen |= set(split_on.tolist())
    assert len(seen) > used          # the masks moved over the features


# ---- rollback, retry and resume need no RNG state --------------------------

def test_a_rolled_back_chunk_is_retried_to_the_same_trees(tables):
    """Non-finite scores after a chunk: the chunk is rolled back and retried
    per iteration, then the fused path is re-armed.  The retry draws the
    masks and bags of the same iterations again."""
    clean = booster(tables["plain"], **SAMPLING["both"])
    clean.train_chunk(8)
    clean.train_chunk(8)
    g = booster(tables["plain"], nan_policy="skip_iter", **SAMPLING["both"])
    g.train_chunk(8)
    g.train_score = g.train_score.at[0, 3].set(np.nan)
    assert g._guard_chunk_scores() is False and g.iter_ == 0
    assert g._fuse_refusal().startswith("a fused chunk failed earlier")
    g.train_chunk(8)                        # the retry, per iteration
    assert g._guard_chunk_scores() is False and g.iter_ == 8
    assert g._can_fuse_iters()              # re-armed
    g.train_chunk(8)
    assert_same_trees(g.models, clean.models)


def test_rollback_one_iter_then_the_same_tree_again(tables):
    g = booster(tables["plain"], **SAMPLING["both"])
    for _ in range(7):
        g.train_one_iter()
    before = copy.deepcopy(g.models[6])    # the rollback shrinks it in place
    g.rollback_one_iter()
    assert g.iter_ == 6
    g.train_one_iter()
    assert_same_trees([g.models[6]], [before], leaf_rtol=1e-5)


def _resumed(tables, meta_edit=lambda meta: None):
    full = booster(tables["plain"], **SAMPLING["both"])
    full.train_chunk(8)
    meta, arrays, model = full.capture_train_state()
    meta_edit(meta)
    full.train_chunk(8)
    again = booster(tables["plain"], **SAMPLING["both"])
    again.restore_train_state(meta, arrays, model)
    assert again.iter_ == 8
    again.train_chunk(8)
    return full, again, meta


def test_a_resume_grows_the_same_trees_with_no_feature_stream(tables):
    full, again, meta = _resumed(tables)
    assert "feat_rng" not in meta
    assert again.save_model_to_string() == full.save_model_to_string()


def test_an_old_checkpoint_that_carries_feat_rng_still_loads(tables):
    from lightgbm_tpu.checkpoint import encode_rng_state

    def as_before(meta):
        meta["feat_rng"] = encode_rng_state(np.random.RandomState(2))
    full, again, _ = _resumed(tables, as_before)
    assert again.save_model_to_string() == full.save_model_to_string()


# ---- which path built the tree: said, not guessed --------------------------

def test_a_refused_fused_path_is_said_once_and_counted(tables):
    sampling.reset()
    spans.reset()
    said, level = [], Log._level
    g = booster(tables["plain"], pos_bagging_fraction=0.5, bagging_freq=1)
    Log.reset_callback(said.append)
    Log.reset_level(Log.level_from_verbosity(1))
    try:
        g.train_chunk(2)
        g.train_chunk(2)
    finally:
        Log.reset_callback(None)
        Log.reset_level(level)
    why = "pos/neg_bagging_fraction need the labels beside the bag"
    assert g._fuse_refusal() == why
    assert sum("not in fused chunks: " + why in line for line in said) == 1
    assert [r["name"] for r in spans.records()
            if r["name"].startswith("gbdt.per_iteration")] \
        == ["gbdt.per_iteration: " + why]
    counts = sampling.counts()
    assert counts["sampling.per_iteration_trees"] == 4
    assert counts["sampling.fused_trees"] == 0


# ---- a program that samples nothing is the program it always was -----------

def _chunk_jaxpr(ds, **params):
    """(jaxpr text, compiled text) of a fused chunk of 2 trees, traced
    afresh."""
    jax.clear_caches()
    jaxprs = []
    hoist = G._hoisted_jit

    def spy(fused, *example):
        jaxprs.append(str(jax.make_jaxpr(fused)(*example)))
        return hoist(fused, *example)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(G, "_hoisted_jit", spy)
        g = booster(ds, **params)
        g.train_chunk(2)
    return jaxprs[0], g.chunk_program_text(2)


@pytest.mark.parametrize("table", ["plain", "bundled"])
def test_without_sampling_no_draw_is_entered(tables, monkeypatch, table):
    """``feature_fraction = 1`` and no bagging: neither draw is traced, no
    ``gbdt.sample`` scope is opened and the chunk has its three outputs, so
    the lowered program of ``higgs_train`` and of the bundled table stays
    what it was (compared byte for byte with the parent commit's by hand,
    PERF.md section 6, PR 36)."""
    sampled, sampled_text = _chunk_jaxpr(tables[table], **SAMPLING["both"])
    assert "gbdt.sample/" in sampled_text

    def never(*a, **k):
        raise AssertionError("a draw was traced with nothing to sample")
    monkeypatch.setattr(G, "feature_mask_of", never)
    monkeypatch.setattr(G, "_bag_mask", never)
    jaxpr, text = _chunk_jaxpr(tables[table])
    assert "gbdt.sample" not in text and jaxpr != sampled
