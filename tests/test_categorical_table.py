"""Categorical columns handed over as categories, end to end at a small size:
the program's categorical bin finder and search on the fused carried path in
interpret mode, held to the plain grower and walk of
``benchmarks/plain_categorical.py`` (which imports nothing of the program);
the bounded many-vs-many scan against the whole one; the cell
``expo_cat_train``'s layout, kind, counters, scopes and controls.
"""
import collections
import copy
import json
import os
import re
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
from lightgbm_tpu.core.split import (  # noqa: E402  (the oracle's names)
    K_EPSILON, K_MIN_SCORE, FeatureBest, _bits_to_words, _leaf_output_l2,
    _split_gains_clamped, cat_scan_steps, leaf_split_gain)
from lightgbm_tpu.io.binning import MissingType  # noqa: E402
from lightgbm_tpu.obs import scopes as _scopes  # noqa: E402
from lightgbm_tpu.io.dataset import BinnedDataset  # noqa: E402
from lightgbm_tpu.obs import categorical  # noqa: E402
from lightgbm_tpu.obs.scopes import _TRANSFORMED, FIND_CAT_PARTS  # noqa: E402
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


# ---- the oracle: the serial walk as the program ran it until PR 42 ---------

def loop_search(hist, feat, feature_mask, sum_grad, sum_hess, num_data,
                params, cmin=None, cmax=None, scan_steps=None):
    """``per_feature_best_categorical`` as it stood at PR 41, body verbatim:
    ``argsort`` and three gathers through the order, a vmapped ``lax.scan`` a
    direction over ``scan_steps`` sorted positions (all of them at 256), the
    winning prefix scattered back to bin order.  The reference the straight-
    line search is held to, bit for bit."""
    F, _, B = hist.shape
    p = params
    steps = cat_scan_steps(B, p) if scan_steps is None else int(scan_steps)
    g = hist[:, 0, :]
    h = hist[:, 1, :]
    total_h = sum_hess + 2 * K_EPSILON
    total_g = sum_grad
    num_data_f = num_data.astype(jnp.float32)
    cnt_factor = num_data_f / total_h
    cnt = jnp.round(h * cnt_factor)

    is_full = feat.missing_type == int(MissingType.NONE)
    used_bin = feat.num_bin - 1 + is_full.astype(jnp.int32)     # [F]
    t = jnp.arange(B, dtype=jnp.int32)[None, :]
    in_range = t < used_bin[:, None]

    gain_shift = leaf_split_gain(total_g, total_h, p.lambda_l1, p.lambda_l2,
                                 p.max_delta_step)
    min_gain_shift = gain_shift + p.min_gain_to_split
    use_onehot = feat.num_bin <= p.max_cat_to_onehot                # [F]

    # ---------- one-hot: category t vs rest (:157-189) ----------
    fidx = jnp.arange(F)
    with jax.named_scope(_scopes.FIND_CAT_ONEHOT):
        other_g = total_g - g
        other_h = total_h - h - K_EPSILON
        other_cnt = num_data_f - cnt
        ok1 = (in_range & (cnt >= p.min_data_in_leaf)
               & (h >= p.min_sum_hessian_in_leaf)
               & (other_cnt >= p.min_data_in_leaf)
               & (other_h >= p.min_sum_hessian_in_leaf))
        oh_gain, oh_lo, oh_ro = _split_gains_clamped(
            g, h + K_EPSILON, other_g, other_h, p, p.lambda_l2, cmin, cmax)
        oh_gain = jnp.where(ok1 & (oh_gain > min_gain_shift), oh_gain,
                            K_MIN_SCORE)
        oh_t = jnp.argmax(oh_gain, axis=1).astype(jnp.int32)        # first max
        oh_best = oh_gain[fidx, oh_t]

    # ---------- sorted many-vs-many (:191-268) ----------
    l2c = p.lambda_l2 + p.cat_l2
    with jax.named_scope(_scopes.FIND_CAT_SORT):
        valid_sort = in_range & (cnt >= p.cat_smooth)
        ctr = g / (h + p.cat_smooth)
        sort_key = jnp.where(valid_sort, ctr, jnp.inf)
        order = jnp.argsort(sort_key, axis=1, stable=True).astype(jnp.int32)
        used = valid_sort.sum(axis=1).astype(jnp.int32)             # [F]
        max_num_cat = jnp.minimum(p.max_cat_threshold, (used + 1) // 2)

        gs = jnp.take_along_axis(g, order, axis=1)
        hs = jnp.take_along_axis(h, order, axis=1)
        cs = jnp.take_along_axis(cnt, order, axis=1)

    def scan_dir(gs_f, hs_f, cs_f, used_f, maxcat_f, backward):
        def idx(i):
            return jnp.where(backward, jnp.maximum(used_f - 1 - i, 0), i)

        def step(state, i):
            sum_lg, sum_lh, left_c, cnt_grp, stop, bgain, bi = state
            j = idx(i)
            active = (i < used_f) & (i < maxcat_f) & ~stop
            af = active.astype(jnp.float32)
            sum_lg = sum_lg + gs_f[j] * af
            sum_lh = sum_lh + hs_f[j] * af
            left_c = left_c + cs_f[j] * af
            cnt_grp = cnt_grp + cs_f[j] * af
            cont1 = ((left_c < p.min_data_in_leaf)
                     | (sum_lh < p.min_sum_hessian_in_leaf))
            right_c = num_data_f - left_c
            sum_rh = total_h - sum_lh
            brk = ((right_c < p.min_data_in_leaf)
                   | (right_c < p.min_data_per_group)
                   | (sum_rh < p.min_sum_hessian_in_leaf))
            reached_group = active & ~cont1 & ~brk & \
                (cnt_grp >= p.min_data_per_group)
            sum_rg = total_g - sum_lg
            gain, _, _ = _split_gains_clamped(sum_lg, sum_lh, sum_rg, sum_rh,
                                              p, l2c, cmin, cmax)
            cand = reached_group & (gain > min_gain_shift) & (gain > bgain)
            bgain = jnp.where(cand, gain, bgain)
            bi = jnp.where(cand, i, bi)
            cnt_grp = jnp.where(reached_group, 0.0, cnt_grp)
            stop = stop | (active & brk)
            return (sum_lg, sum_lh, left_c, cnt_grp, stop, bgain, bi), None

        init = (jnp.float32(0), jnp.float32(K_EPSILON), jnp.float32(0),
                jnp.float32(0), jnp.bool_(False), jnp.float32(K_MIN_SCORE),
                jnp.int32(-1))
        (slg, slh, lc, cg, st, bgain, bi), _ = jax.lax.scan(
            step, init, jnp.arange(steps, dtype=jnp.int32))
        return bgain, bi

    with jax.named_scope(_scopes.FIND_CAT_SCAN):
        vscan = jax.vmap(scan_dir, in_axes=(0, 0, 0, 0, 0, None))
        fwd_gain, fwd_i = vscan(gs, hs, cs, used, max_num_cat, False)
        bwd_gain, bwd_i = vscan(gs, hs, cs, used, max_num_cat, True)
        use_bwd = bwd_gain > fwd_gain                                # fwd ties
        so_gain = jnp.where(use_bwd, bwd_gain, fwd_gain)
        so_i = jnp.where(use_bwd, bwd_i, fwd_i)

        # recompute left sums at the winning prefix (inclusive of so_i)
        pos = jnp.arange(B, dtype=jnp.int32)[None, :]
        in_prefix = jnp.where(use_bwd[:, None],
                              (pos >= jnp.maximum(used - 1 - so_i, 0)[:, None])
                              & (pos < used[:, None]),
                              pos <= so_i[:, None])
        in_prefix &= so_i[:, None] >= 0
        so_lg = jnp.sum(jnp.where(in_prefix, gs, 0.0), axis=1)
        so_lh = jnp.sum(jnp.where(in_prefix, hs, 0.0), axis=1) + K_EPSILON
        so_lc = jnp.sum(jnp.where(in_prefix, cs, 0.0), axis=1)

    # ---------- combine one-hot / sorted per feature ----------
    oh = use_onehot
    cat_gain = jnp.where(oh, oh_best, so_gain)
    l_g = jnp.where(oh, g[fidx, oh_t], so_lg)
    l_h = jnp.where(oh, h[fidx, oh_t] + K_EPSILON, so_lh)
    l_c = jnp.where(oh, cnt[fidx, oh_t], so_lc)
    eff_l2 = jnp.where(oh, p.lambda_l2, l2c)
    r_g = total_g - l_g
    r_h = total_h - l_h
    r_c = num_data_f - l_c
    l_out = _leaf_output_l2(l_g, l_h, p, eff_l2)
    r_out = _leaf_output_l2(r_g, r_h, p, eff_l2)
    if cmin is not None:
        l_out = jnp.clip(l_out, cmin, cmax)
        r_out = jnp.clip(r_out, cmin, cmax)

    # left-bin bitsets: one-hot -> {oh_t}; sorted -> prefix through order
    with jax.named_scope(_scopes.FIND_CAT_SORT):
        bits_oh = t == oh_t[:, None]
        bits_sorted = jnp.zeros((F, B), dtype=bool)
        scatter_f = jnp.broadcast_to(fidx[:, None], (F, B)).reshape(-1)
        bits_sorted = bits_sorted.at[scatter_f, order.reshape(-1)].set(
            in_prefix.reshape(-1))
        bits = jnp.where(oh[:, None], bits_oh, bits_sorted)
        words = _bits_to_words(bits)

    found = (cat_gain > K_MIN_SCORE) & feature_mask & feat.is_categorical
    zero = jnp.zeros((F,), jnp.float32)
    return FeatureBest(
        gain=jnp.where(found, cat_gain - min_gain_shift, K_MIN_SCORE),
        threshold=jnp.where(oh, oh_t, so_i + 1).astype(jnp.int32),
        default_left=jnp.zeros((F,), bool),
        left_sum_grad=jnp.where(found, l_g, zero),
        left_sum_hess=jnp.where(found, l_h - K_EPSILON, zero),
        left_count=jnp.where(found, l_c, zero),
        right_sum_grad=jnp.where(found, r_g, zero),
        right_sum_hess=jnp.where(found, r_h - K_EPSILON, zero),
        right_count=jnp.where(found, r_c, zero),
        left_output=l_out,
        right_output=r_out,
        cat_bitset=jnp.where(found[:, None], words, 0).astype(jnp.uint32),
    )


# ---- the straight-line search against the oracle ---------------------------

def _search_inputs(seed, F=5, bins=256, used=(200, 90, 40, 3, 33),
                   missing=None, ties=False):
    rng = np.random.default_rng(seed)
    num_bin = np.asarray(used, np.int32)
    live = np.arange(bins)[None, :] < num_bin[:, None]
    rows = np.where(live, rng.integers(0, 400, size=(F, bins)), 0)
    h = (0.25 * rows).astype(np.float32)
    g = np.where(live, rng.normal(size=(F, bins)) * np.sqrt(rows + 1.0),
                 0.0).astype(np.float32)
    if ties:
        # a few values of g over one of h: most sort keys are shared
        h = np.where(live & (rows >= 40), 50.0, 0.0).astype(np.float32)
        g = np.where(h > 0, np.round(g / 8.0) * 8.0, 0.0).astype(np.float32)
    hist = jnp.asarray(np.stack([g, h], axis=1))
    if missing is None:
        missing = [int(MissingType.NONE), int(MissingType.NAN),
                   int(MissingType.NONE), int(MissingType.NONE),
                   int(MissingType.NAN)]
    feat = S.FeatureInfo(
        num_bin=jnp.asarray(num_bin),
        missing_type=jnp.asarray(missing, jnp.int32),
        default_bin=jnp.zeros(F, jnp.int32),
        is_categorical=jnp.ones(F, bool))
    # one total for every feature: each feature's bins add up to feature 0's
    # (the rest goes to bin 255, past every feature's range)
    hist = hist.at[1:, :, 255].add(hist[0].sum(axis=1)[None, :]
                                   - hist[1:].sum(axis=2))
    n = 4.0 * float(hist[0, 1].sum())             # 4 rows a unit of hessian
    return hist, feat, jnp.ones(F, bool), jnp.float32(hist[0, 0].sum()), \
        jnp.float32(hist[0, 1].sum()), jnp.int32(round(n))


def _sorted_keys(hist, feat, n, sh, p):
    """(sort key of every bin with inf where it is not sortable) as the
    search makes them, in NumPy."""
    g, h = np.asarray(hist[:, 0, :]), np.asarray(hist[:, 1, :])
    cnt = np.round(h * (np.float32(n) / (np.float32(sh) + 2e-15)))
    full = np.asarray(feat.missing_type) == int(MissingType.NONE)
    used_bin = np.asarray(feat.num_bin) - 1 + full
    ok = (np.arange(h.shape[1])[None, :] < used_bin[:, None]) \
        & (cnt >= p.cat_smooth)
    return np.where(ok, g / (h + np.float32(p.cat_smooth)), np.inf)


def _left_bins(fb):
    """[F, B] bool out of the bitset words."""
    words = np.asarray(fb.cat_bitset)
    return ((words[:, :, None] >> np.arange(32, dtype=np.uint32)) & 1
            ).astype(bool).reshape(words.shape[0], -1)


BASE = dict(min_data_in_leaf=0, min_sum_hessian_in_leaf=100.0)
# (seed, SplitParams over BASE; keys that are none of its fields shape the
# input): the first five are PR 41's, which held 32 steps to 256
SEARCHES = [
    (0, {}), (1, {"min_data_per_group": 1000}), (2, {"cat_smooth": 150.0}),
    (3, {"max_cat_threshold": 7}), (4, {"min_sum_hessian_in_leaf": 900.0}),
    pytest.param(5, {"bounds": (-0.02, 0.03)}, id="monotone_bounds"),
    pytest.param(6, {"max_cat_threshold": 64}, id="max_cat_threshold_64"),
    pytest.param(7, {"used": (200, 1, 40, 5, 33),
                     "min_sum_hessian_in_leaf": 20.0},
                 id="no_sortable_bin_and_five"),
    pytest.param(8, {"backward": True}, id="the_backward_walk_wins"),
    pytest.param(9, {"ties": True, "min_sum_hessian_in_leaf": 200.0},
                 id="tied_sort_keys"),
    pytest.param(10, {"min_data_per_group": 450,
                      "min_sum_hessian_in_leaf": 10.0},
                 id="the_batching_resets_several_times"),
    pytest.param(11, {"missing": [int(MissingType.NAN)] * 5},
                 id="nan_bin_features"),
    pytest.param(12, {"children": True}, id="vmapped_over_two_children"),
]
SHAPES = ("bounds", "used", "backward", "ties", "missing", "children")


@pytest.mark.parametrize("seed,how", SEARCHES)
def test_the_bounded_scan_is_the_whole_scan_bit_for_bit(seed, how):
    """Every field of the straight-line search equals the serial walk's at
    256 steps, bit for bit."""
    p = S.SplitParams(**dict(BASE, **{k: v for k, v in how.items()
                                      if k not in SHAPES}))
    steps = S.cat_scan_steps(256, p)
    assert steps == min(256, p.max_cat_threshold)
    inputs = {k: how[k] for k in ("used", "missing", "ties") if k in how}
    hist, feat, mask, sg, sh, n = _search_inputs(seed, **inputs)
    cmin, cmax = how.get("bounds", (None, None))

    def search(fn, **kw):
        def one(hist, sg, sh, n):
            return fn(hist, feat, mask, sg, sh, n, p,
                      None if cmin is None else jnp.float32(cmin),
                      None if cmax is None else jnp.float32(cmax), **kw)
        return jax.jit(jax.vmap(one) if how.get("children") else one)

    if how.get("children"):
        # the two children of a split, as tree_learner's best_of is vmapped
        other = _search_inputs(seed + 100)
        hist, sg, sh, n = (jnp.stack([a, b]) for a, b in zip(
            (hist, sg, sh, n), (other[0],) + other[3:]))
    new = search(S.per_feature_best_categorical)(hist, sg, sh, n)
    whole = search(loop_search, scan_steps=256)(hist, sg, sh, n)
    for name, a, b in zip(new._fields, new, whole):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
    assert np.asarray(new.cat_bitset).any()
    if how.get("children"):
        assert not np.array_equal(np.asarray(new.gain[0]),
                                  np.asarray(new.gain[1]))
        return
    keys = _sorted_keys(hist, feat, n, sh, p)
    sortable = np.isfinite(keys).sum(axis=1)
    found = np.isfinite(np.asarray(new.gain))
    if "used" in how:
        assert sortable[1] == 0 and not found[1]
        assert sortable[3] == 5 and found[3]     # 3 steps a side of 5: overlap
    else:
        assert sortable.max() > 2 * steps, sortable  # positions never walked
        assert found.sum() >= 2
    left = _left_bins(new)
    many = found & (np.asarray(feat.num_bin) > p.max_cat_to_onehot)
    # a winner of the backward walk holds the bin of the largest key and
    # not that of the smallest
    top = np.where(np.isfinite(keys), keys, -np.inf).argmax(axis=1)
    backward = many & left[np.arange(len(top)), top] \
        & ~left[np.arange(len(top)), keys.argmin(axis=1)]
    if how.get("backward"):
        assert backward.any()
    if how.get("ties"):
        shared = [len(np.unique(k[np.isfinite(k)])) < np.isfinite(k).sum() / 2
                  for k in keys[many]]
        assert many.any() and all(shared)
    if "min_data_per_group" in how and how["min_data_per_group"] < 1000:
        # a winner several batches in: its left side holds three groups
        assert (np.asarray(new.left_count)[many]
                >= 3 * p.min_data_per_group).any()


# ---- the structure: what the lowered search holds --------------------------

_LOC_DEF = re.compile(r'^(#loc\d+) = loc\("([^"]*)"')
_LOC_REF = re.compile(r'loc\((#loc\d+)\)\s*$')
_OP = re.compile(r'(?:^|[\s"])(?:(?:stablehlo|chlo)\.([a-z_]+)|(call) @([\w.$-]+))')
_FUNC = re.compile(r'^\s*func\.func\s+(?:public |private )?@([\w.$-]+)')
SEARCH = (_scopes.FIND_CAT_SORT, _scopes.FIND_CAT_SCAN)
INDEXED_OR_LOOPED = ("while", "gather", "scatter", "dynamic_slice",
                     "dynamic_gather", "dynamic_update_slice")


def ops_under(text, scopes, within=()):
    """Counter of the StableHLO op names of ``text`` (a lowered program's
    ``as_text(debug_info=True)``) at a location whose path holds one of
    ``scopes`` and every one of ``within``, with the ops of the functions
    called from there."""
    lines = text.splitlines()
    paths = dict(m.groups() for m in map(_LOC_DEF.match, lines) if m)

    def wanted(ref):
        parts = _TRANSFORMED.sub(r"\1", paths.get(ref, "")).split("/")
        return any(s in parts for s in scopes) and all(
            w in parts for w in within)
    ops, func = collections.defaultdict(list), None  # a function's (op, loc, callee)
    for k, line in enumerate(lines):
        opens = _FUNC.match(line)
        if opens:
            func = opens.group(1)
        op = None if opens or line.lstrip().startswith(("#loc", "}")) \
            else _OP.search(line)
        if op is None:
            continue
        ref = _LOC_REF.search(line)
        if ref is None:          # an op with regions: its loc closes the last
            close = " " * (len(line) - len(line.lstrip())) + "}"
            ref = next(filter(None, (_LOC_REF.search(later)
                                     for later in lines[k + 1:]
                                     if later.startswith(close))), None)
        ops[func].append((op.group(1) or op.group(2), ref and ref.group(1),
                          op.group(3)))
    found = collections.Counter()

    def take(func, everything):
        for name, ref, callee in ops[func]:
            if everything or wanted(ref):
                found[name] += 1
                if callee:
                    take(callee, True)
    for func in list(ops):
        take(func, False)
    return found


def _lowered_search(fn):
    F = 8
    feat = S.FeatureInfo(
        num_bin=jnp.full(F, 200, jnp.int32),
        missing_type=jnp.zeros(F, jnp.int32),
        default_bin=jnp.zeros(F, jnp.int32), is_categorical=jnp.ones(F, bool))
    return jax.jit(fn, static_argnums=(6,)).lower(
        jnp.zeros((F, 2, 256)), feat, jnp.ones(F, bool), jnp.float32(1),
        jnp.float32(1), jnp.int32(1), S.SplitParams()
    ).as_text(debug_info=True)


def test_the_lowered_search_is_one_sort_and_no_loop_gather_or_scatter():
    text = _lowered_search(S.per_feature_best_categorical)
    found = ops_under(text, SEARCH)
    assert found["sort"] == 1 and found["reduce"] and found["compare"], found
    assert not [op for op in INDEXED_OR_LOOPED if found[op]], found
    for scope in SEARCH:        # both still hold what a trace can time
        assert sum(ops_under(text, (scope,)).values()) > 20, scope
    # ... and the reading sees them where they are: the serial walk's program
    was = ops_under(_lowered_search(loop_search), SEARCH)
    assert was["sort"] == 1 and was["while"] == 2, was
    assert was["gather"] and was["scatter"] and was["dynamic_slice"], was


def test_the_chunk_programs_search_is_one_sort_a_site_and_no_loop():
    ds, g, *_ = trained("many_vs_many")
    text = g._lowered_chunk(TREES).as_text(debug_info=True)
    for site in ("tree.root", "tree.find_split"):
        found = ops_under(text, SEARCH, within=(site,))
        assert found["sort"] == 1 and found["reduce"], (site, found)
        assert not [op for op in INDEXED_OR_LOOPED if found[op]], (site, found)
    # the split loop itself is a while outside the search, so they are seen
    assert ops_under(text, ("tree.find_split",))["compare"]
    assert sum(ops_under(text, ("tree.finish", "tree.store")).values())


def test_a_numerical_tables_chunk_program_holds_no_search_scope():
    rng = np.random.default_rng(1)
    X = rng.normal(size=(ROWS, 3)).astype(np.float32)
    nds = BinnedDataset.from_matrix(X, label=(X[:, 0] + X[:, 1] > 0).astype(
        np.float32), max_bin=63)
    cfg = Config(verbosity=-1, objective="binary", num_leaves=7,
                 min_data_in_leaf=5)
    n = GBDT(cfg, nds, create_objective("binary", cfg))
    n.learner.use_pallas = n.learner.pallas_interpret = True
    n.train_chunk(2)
    text = n._lowered_chunk(2).as_text(debug_info=True)
    assert not sum(ops_under(text, FIND_CAT_PARTS).values())
    assert "find.cat_" not in text
    assert ops_under(text, ("tree.find_split",))["compare"]


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
