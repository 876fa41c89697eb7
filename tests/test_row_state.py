"""The hand-over of the carried row store from one tree to the next
(core/row_state.py): one in-place pass that adds each row's leaf value to its
score and writes the next tree's gradients beside it.

- the Pallas kernel, in interpret mode, against the plain XLA form: every byte
  of the store (tests/test_chip_compile.py compiles the same kernel for a
  described v5e: interpret mode cannot see what Mosaic refuses);
- the plain form against the three passes it took the place of, kept here as a
  NumPy loop over the windows;
- the turned boosting scan: the model text of a carried run is, byte for byte,
  what the tree before this pass existed (PR 27) wrote.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.boosting import gbdt as G
from lightgbm_tpu.config import Config
from lightgbm_tpu.core import row_state as RS
from lightgbm_tpu.core.partition import CHUNK
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.objective import create_objective

N = 2 * CHUNK            # rows of the table, padding included
N_DATA = N - 190         # real rows: the last 190 are the learner's padding
L = 31
BAG = (0.7, 2)
IT = 5


def _put(rows, off, col):
    rows[:, off:off + 4] = np.ascontiguousarray(col).view(np.uint8).reshape(
        len(col), 4)


def _col(rows, off, dtype):
    return np.ascontiguousarray(
        np.asarray(rows)[:, off:off + 4]).view(dtype).reshape(-1)


def _store(objective, W, voff, seed=0):
    """A leaf-partitioned store as the fused builder leaves it: the table's
    rows permuted over [0, N), then the spare chunk (order >= N)."""
    rng = np.random.default_rng(seed)
    n_arr = N + CHUNK
    rows = rng.integers(0, 256, (n_arr, W), dtype=np.uint8)
    _put(rows, voff + 8, np.concatenate(
        [rng.permutation(N), np.arange(N, n_arr)]).astype(np.int32))
    if objective == "binary":    # sign: the label, magnitude: class weight
        aux = rng.choice([-1.0, 1.0], n_arr) * rng.choice([1.0, 2.5], n_arr)
    else:
        aux = rng.normal(size=n_arr)
    _put(rows, voff + 12, aux.astype(np.float32))
    _put(rows, voff + 16, rng.normal(size=n_arr).astype(np.float32))
    return rows


def _windows(seed=1):
    """21 live leaves tiling [0, N) in shuffled slots of L=31: begins that are
    multiples neither of 32 nor of the tile, one leaf of one row, a tile
    boundary inside a leaf, dead slots with stale begins."""
    rng = np.random.default_rng(seed)
    free = np.arange(33, N - 33)
    free = free[(free % 32 != 0) & (free % 32 != 31)]
    cuts = np.sort(rng.choice(free, 19, replace=False))
    cuts = np.unique(np.concatenate([cuts, [cuts[5] + 1]]))
    assert len(cuts) == 20 and np.all(cuts % 32 != 0)
    starts = np.concatenate([[0], cuts])
    counts = np.diff(np.concatenate([starts, [N]]))
    assert 1 in counts
    begin = rng.integers(0, N, L).astype(np.int32)       # stale garbage
    wcount = np.zeros(L, np.int32)
    slots = rng.permutation(24)[:21]                     # num_leaves = 24:
    begin[slots], wcount[slots] = starts, counts         # 3 empty live slots
    value = rng.normal(scale=0.1, size=L).astype(np.float32)
    return begin, wcount, value, 24


def _grad_fn(objective, bag):
    cfg = Config(objective=objective, verbosity=-1)
    return G._carried_fns(create_objective(objective, cfg), N_DATA, bag,
                          3)[1]


def _both(rows, grad_fn, voff, **kw):
    begin, wcount, value, num_leaves = _windows()
    bs, vs = RS.leaf_windows(jnp.asarray(begin), jnp.asarray(wcount),
                             jnp.asarray(value), jnp.int32(num_leaves), N)
    rows = jnp.asarray(rows)
    plain = jax.jit(lambda r: RS.advance_row_state_xla(
        r, bs, vs, grad_fn, jnp.int32(IT), voff=voff, n=N))(rows)
    kernel = jax.jit(lambda r: RS.row_state_pass(
        r, bs, vs, grad_fn, jnp.int32(IT), voff=voff, interpret=True,
        **kw))(rows)
    return plain, kernel


def test_leaf_windows_sorts_live_slots_and_parks_the_rest():
    begin, wcount, value, num_leaves = _windows()
    bs, vs = RS.leaf_windows(jnp.asarray(begin), jnp.asarray(wcount),
                             jnp.asarray(value), jnp.int32(num_leaves), N)
    bs, vs = np.asarray(bs), np.asarray(vs)
    live = (np.arange(L) < num_leaves) & (wcount > 0)
    order = np.argsort(begin[live])
    assert live.sum() == 21 and bs[0] == 0
    np.testing.assert_array_equal(bs[:21], begin[live][order])
    np.testing.assert_array_equal(vs[:21], value[live][order])
    assert np.all(bs[21:] == N) and np.all(vs[21:] == 0.0)


@pytest.mark.parametrize("objective", ["binary", "regression"])
@pytest.mark.parametrize("bag", [None, BAG], ids=["all_rows", "bagged"])
def test_kernel_equals_the_plain_form(objective, bag):
    voff, W = 28, 128
    rows = _store(objective, W, voff)
    (p_rows, p_g, p_h), (k_rows, k_g, k_h) = _both(
        rows, _grad_fn(objective, bag), voff)
    np.testing.assert_array_equal(np.asarray(k_rows), np.asarray(p_rows))
    np.testing.assert_allclose([k_g, k_h], [p_g, p_h], rtol=1e-5)
    # what the pass may touch, and what it must have done there
    out = np.asarray(k_rows)
    changed = np.zeros(W, bool)
    changed[voff:voff + 8] = changed[voff + 16:voff + 20] = True
    np.testing.assert_array_equal(out[:, ~changed], rows[:, ~changed])
    grad, hess = _col(out, voff, np.float32), _col(out, voff + 4, np.float32)
    order = _col(out, voff + 8, np.int32)
    assert np.all(grad[order >= N_DATA] == 0) and np.all(
        hess[order >= N_DATA] == 0)
    assert np.all(hess[order < N_DATA] >= 0) and np.any(grad != 0)
    if bag is not None:
        share = np.mean(hess[order < N_DATA] > 0)
        assert abs(share - bag[0]) < 0.03
    # the spare chunk lies past every window: its score stands
    np.testing.assert_array_equal(out[N:, voff + 16:voff + 20],
                                  rows[N:, voff + 16:voff + 20])


@pytest.mark.parametrize("W,voff,block", [
    (1024, 968, (896, 128, 4096)),      # wide store: one lane block moves
    (256, 120, (0, 256, 4096)),         # the slab straddles two lane blocks
    (512, 250, (0, 512, 2048)),         # ... in an odd block: all of W
])
def test_kernel_moves_only_the_state_lanes(W, voff, block):
    assert RS._blocking(voff, W) == block
    rows = _store("binary", W, voff)
    (p_rows, _, _), (k_rows, _, _) = _both(rows, _grad_fn("binary", None),
                                           voff)
    np.testing.assert_array_equal(np.asarray(k_rows), np.asarray(p_rows))


def test_kernel_tile_does_not_change_the_bytes():
    voff = 28
    rows = _store("binary", 128, voff)
    (p_rows, _, _), (k_rows, _, _) = _both(rows, _grad_fn("binary", BAG),
                                           voff, tile=1024)
    np.testing.assert_array_equal(np.asarray(k_rows), np.asarray(p_rows))


@pytest.mark.parametrize("objective", ["binary", "regression"])
def test_plain_form_equals_the_three_passes_it_replaced(objective):
    """Through PR 27: ``tree.finish`` spread each window's leaf value over
    its rows and added it to the score column, ``gbdt.gradients`` read score,
    aux and order back and ``tree.store`` wrote the gradient bytes."""
    voff = 28
    rows = _store(objective, 128, voff)
    begin, wcount, value, num_leaves = _windows()
    score = _col(rows, voff + 16, np.float32).copy()
    for leaf in range(num_leaves):
        if wcount[leaf] > 0:
            score[begin[leaf]:begin[leaf] + wcount[leaf]] += value[leaf]
    grad, hess = _grad_fn(objective, None)(
        jnp.asarray(score), jnp.asarray(_col(rows, voff + 12, np.float32)),
        jnp.asarray(_col(rows, voff + 8, np.int32)), jnp.int32(IT))
    want = rows.copy()
    _put(want, voff, np.asarray(grad))
    _put(want, voff + 4, np.asarray(hess))
    _put(want, voff + 16, score)
    (p_rows, p_g, p_h), _ = _both(rows, _grad_fn(objective, None), voff)
    got = np.asarray(p_rows)
    for off in (voff, voff + 4, voff + 16):      # as numbers: -0.0 == 0.0
        np.testing.assert_array_equal(_col(got, off, np.float32),
                                      _col(want, off, np.float32))
    keep = np.ones(128, bool)
    keep[voff:voff + 8] = False
    np.testing.assert_array_equal(got[:, keep], want[:, keep])
    np.testing.assert_allclose(
        [p_g, p_h], [np.sum(np.asarray(grad)[:N], dtype=np.float64),
                     np.sum(np.asarray(hess)[:N], dtype=np.float64)],
        rtol=1e-5)


def test_bag_uniforms_round_like_the_u32_cast():
    """``_bag_uniforms`` converts its hash to f32 through two 16-bit halves
    (Mosaic has no u32 -> f32 cast); the one rounded add is the cast's own
    rounding."""
    ids = np.concatenate([np.arange(70000), 2 ** 31 - 1 - np.arange(1000)]
                         ).astype(np.int32)
    for seed, itw in ((3, 0), (2 ** 31 + 5, 7), (12345, 1000)):
        x = ids.astype(np.uint32) * np.uint32(2654435761)
        x = x ^ np.uint32((seed + itw * 0x9E3779B9) & 0xFFFFFFFF)
        x = x ^ (x >> np.uint32(16))
        x = x * np.uint32(2246822519)
        x = x ^ (x >> np.uint32(13))
        x = x * np.uint32(3266489917)
        x = x ^ (x >> np.uint32(16))
        assert np.any(x > 2 ** 24)          # where the cast has to round
        want = x.astype(np.float32) * np.float32(1.0 / 4294967296.0)
        got = G._bag_uniforms(jnp.asarray(ids), seed, jnp.int32(itw))
        np.testing.assert_array_equal(np.asarray(got), want)


# ---- the turned scan: [build tree t] -> [pass: score of t, gradients of t+1]

def _data(objective, n=3000, f=8, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    if objective == "binary":
        y = ((X[:, 0] + X[:, 1] ** 2 + rng.normal(scale=0.4, size=n)) > 0.4
             ).astype(np.float64)
    else:
        y = (X[:, 0] * 3 + np.sin(X[:, 1]) + rng.normal(scale=0.1, size=n)
             ).astype(np.float64)
    return X, y


def _carried(objective, chunks, **extra):
    X, y = _data(objective)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=63)
    cfg = Config(objective=objective, num_leaves=15, num_iterations=6,
                 learning_rate=0.2, max_bin=63, verbosity=-1, **extra)
    booster = G.GBDT(cfg, ds, create_objective(objective, cfg))
    assert booster._can_carry_rows()
    for c in chunks:
        booster.train_chunk(c)
    return booster, X


def _trees_text(booster):
    text = booster.save_model_to_string()
    return text[:text.index("\nparameters:")]


# sha256 of the trees' text, recorded from commit 01dd561 (PR 27), where the
# score, the gradients and their bytes took three whole-store passes a tree.
# One chunk of 6 and two chunks of 3 differ, there as here: a chunk boundary
# rebuilds the store in original row order, which reorders f32 sums.
PARENT_TREES = {
    ("binary", False, (6,)): "7260d4bd52b2b7ad",
    ("binary", False, (3, 3)): "9abca3f75a944402",
    ("binary", True, (6,)): "a0f1c71c7196ca7e",
    ("binary", True, (3, 3)): "b08681792db6652c",
    ("regression", False, (6,)): "0174ed74f1703af5",
    ("regression", False, (3, 3)): "46fc086becbbeae7",
    ("regression", True, (6,)): "6c3b9cc244059d89",
    ("regression", True, (3, 3)): "d91f06e8611f1a6d",
}


@pytest.mark.parametrize("objective,bagged,chunks", sorted(PARENT_TREES))
def test_carried_trees_are_the_parents_byte_for_byte(objective, bagged,
                                                     chunks):
    extra = dict(bagging_fraction=0.7, bagging_freq=2) if bagged else {}
    booster, _ = _carried(objective, chunks, **extra)
    digest = hashlib.sha256(_trees_text(booster).encode()).hexdigest()[:16]
    assert digest == PARENT_TREES[objective, bagged, chunks]


@pytest.mark.parametrize("objective", ["binary", "regression"])
def test_carried_score_is_the_models_prediction(objective):
    """The score that rides the store through six passes and a chunk boundary
    is what the finished trees predict for the same rows."""
    booster, X = _carried(objective, (4, 2))
    np.testing.assert_allclose(
        np.asarray(booster.train_score[0, :len(X)]),
        np.asarray(booster.predict(X, raw_score=True)), rtol=2e-5, atol=2e-5)


def test_carried_kernel_path_trains_like_the_plain_path():
    """The fused learner (every kernel in interpret mode, the pass among
    them) against the XLA learner on the same table."""
    rng = np.random.RandomState(5)
    X = rng.normal(size=(CHUNK, 6)).astype(np.float32)
    y = (X[:, 0] - X[:, 1] * X[:, 2] + rng.normal(scale=0.3, size=CHUNK) > 0
         ).astype(np.float64)
    scores = {}
    for fused in (False, True):
        ds = BinnedDataset.from_matrix(X, label=y, max_bin=63)
        cfg = Config(objective="binary", num_leaves=7, num_iterations=3,
                     learning_rate=0.2, max_bin=63, verbosity=-1,
                     bagging_fraction=0.8, bagging_freq=1)
        booster = G.GBDT(cfg, ds, create_objective("binary", cfg))
        booster.learner.use_pallas = fused
        booster.learner.pallas_interpret = fused
        booster.train_chunk(3)
        assert not booster._fuse_failed and len(booster.models) == 3
        scores[fused] = np.asarray(booster.train_score[0, :CHUNK])
    np.testing.assert_allclose(scores[True], scores[False], rtol=2e-4,
                               atol=2e-4)
