"""The split search on a bundled table's own lanes (``split.group_scans`` +
``split.group_best``) against the path it replaces: the group histogram
unbundled into ``[F, 2, feat_bins]`` and searched a feature at a time
(``per_feature_best_combined`` + ``reduce_feature_best``).  Same feature,
threshold and ``default_left`` exactly; gain, sums, counts and outputs to f32
tolerance (the right-to-left direction now accumulates the right side itself
where the old path took a difference of prefixes).

Leaves are row subsets of real bundled data sets, histogrammed in NumPy off
``ds.binned``, so the lane maps are held to ``_assign_group_layout`` and to
conflict rows as ingest leaves them.  Ties and precision are hand-laid
histograms on hand-laid groups.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.core import split as S
from lightgbm_tpu.core.tree_learner import SerialTreeLearner
from lightgbm_tpu.io.binning import MissingType
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.obs import efb

NONE, ZERO, NAN = (int(MissingType.NONE), int(MissingType.ZERO),
                   int(MissingType.NAN))


# ---- the two paths on one leaf ---------------------------------------------

def unbundle(h, group, lidx, lmask, sg, sh):
    """``tree_learner.build_tree_partitioned.unpack``: the reference here."""
    hf = jnp.take_along_axis(h[group], lidx[:, None, :], axis=2)
    hf = hf * lmask[:, None, :]
    rest = jnp.sum(hf, axis=2)
    return hf.at[:, 0, 0].set(sg - rest[:, 0]).at[:, 1, 0].set(sh - rest[:, 1])


@functools.partial(jax.jit, static_argnames=("params", "bounded"))
def old_search(h, feat, lidx, lmask, mask, sg, sh, cnt, cmin, cmax, params,
               bounded):
    fb = S.per_feature_best_combined(
        unbundle(h, feat.group, lidx, lmask, sg, sh), feat, mask, sg, sh, cnt,
        params, any_categorical=False,
        cmin=cmin if bounded else None, cmax=cmax if bounded else None)
    if params.feature_contri:
        contri = jnp.maximum(jnp.asarray(params.feature_contri), 0.0)
        fb = fb._replace(gain=jnp.where(fb.gain > S.K_MIN_SCORE,
                                        fb.gain * contri, fb.gain))
    return S.reduce_feature_best(fb, jnp.arange(mask.shape[0], dtype=jnp.int32))


@functools.partial(jax.jit, static_argnames=("params", "bounded", "feat_bins"))
def new_search(h, lanes, mask, sg, sh, cnt, cmin, cmax, params, bounded,
               feat_bins):
    of_lane = jnp.minimum(lanes.feature, mask.shape[0] - 1)
    contri = (jnp.maximum(jnp.asarray(params.feature_contri), 0.0)[
        jnp.minimum(lanes.feature2, mask.shape[0] - 1)]
        if params.feature_contri else None)
    return S.group_best(
        S.group_scans(h, lanes, sg, sh, cnt), lanes,
        lanes.valid & mask[of_lane], sg, sh,
        cnt, params, feat_bins, cmin=cmin if bounded else None,
        cmax=cmax if bounded else None, lane_contri=contri)


def both(h, feat, lidx, lmask, lanes, mask, sg, sh, cnt, params,
         bounds=None):
    f32 = jnp.float32
    cmin, cmax = (f32(b) for b in (bounds or (-np.inf, np.inf)))
    args = (f32(sg), f32(sh), jnp.int32(cnt), cmin, cmax)
    h, mask = jnp.asarray(h, f32), jnp.asarray(mask)
    old = old_search(h, feat, lidx, lmask, mask, *args, params=params,
                     bounded=bounds is not None)
    new = new_search(h, lanes, mask, *args, params=params,
                     bounded=bounds is not None, feat_bins=lidx.shape[1])
    return (S.BestSplit(*[np.asarray(x) for x in old]),
            S.BestSplit(*[np.asarray(x) for x in new]))


def assert_same_split(old, new, rtol=2e-5):
    assert np.isfinite(old.gain) == np.isfinite(new.gain), (old, new)
    assert new.cat_bitset.shape == old.cat_bitset.shape
    assert not new.cat_bitset.any()
    if not np.isfinite(old.gain):
        return
    assert (int(new.feature), int(new.threshold), bool(new.default_left)) == \
        (int(old.feature), int(old.threshold), bool(old.default_left)), \
        (old, new)
    scale = abs(float(old.left_sum_grad)) + abs(float(old.right_sum_grad))
    for name in ("left_sum_grad", "right_sum_grad"):
        np.testing.assert_allclose(getattr(new, name), getattr(old, name),
                                   rtol=rtol, atol=rtol * scale, err_msg=name)
    for name in ("left_sum_hess", "right_sum_hess", "left_count",
                 "right_count", "left_output", "right_output"):
        np.testing.assert_allclose(getattr(new, name), getattr(old, name),
                                   rtol=rtol, atol=1e-6, err_msg=name)
    np.testing.assert_allclose(new.gain, old.gain, rtol=1e-3,
                               atol=1e-4 * max(scale, 1.0))


# ---- bundled data sets -----------------------------------------------------

def exclusive_block(rng, n, sizes, active=0.9, nan_share=0.0):
    """One column a entry of ``sizes``; a row is non-zero in at most one of
    them, with a whole value in 1..size (``nan_share`` of those NaN)."""
    X = np.zeros((n, len(sizes)), np.float32)
    which = rng.randint(0, len(sizes), size=n)
    on = rng.rand(n) < active
    value = 1 + (rng.rand(n) * np.asarray(sizes)[which]).astype(np.int64)
    X[np.arange(n)[on], which[on]] = value[on]
    if nan_share:
        lost = on & (rng.rand(n) < nan_share)
        X[np.arange(n)[lost], which[lost]] = np.nan
    return X


def onehot_table():
    """The ``expo-onehot`` schema at a small size: one-hot blocks of two-bin
    columns and two dense numeric columns alone."""
    rng = np.random.RandomState(11)
    n = 8192
    X = np.concatenate([exclusive_block(rng, n, [1] * levels, active=1.0)
                        for levels in (12, 31, 7, 29, 60)]
                       + [rng.normal(size=(n, 2)).astype(np.float32)], axis=1)
    return X, {}


def mixed_table(**how):
    """Sparse multi-bin columns (3-60 bins) bundled; NaN-bearing sparse
    columns bundled and a NaN-bearing dense column alone; dense columns of
    255 bins whose zero is not bin 0; two sparse columns that share rows."""
    rng = np.random.RandomState(12)
    n = 30000
    a = np.zeros((n, 2), np.float32)
    rows = rng.permutation(n)
    a[rows[:900], 0] = 1.0
    a[rows[900:1800], 1] = 1.0 + (np.arange(900) % 3)
    a[rows[900:902], 0] = 1.0                  # conflict rows, under the budget
    # a seventh of the dense values exact zeros: an empty zero bin under
    # zero_as_missing would tie the two directions at every threshold, to be
    # settled by rounding
    dense = rng.normal(size=(n, 3)).astype(np.float32)
    dense[rng.rand(n, 3) < 0.15] = 0.0
    dense[rng.rand(n) < 0.1, 0] = np.nan
    X = np.concatenate([
        exclusive_block(rng, n, [2, 5, 9, 17, 30, 44, 59, 3, 7, 12]),
        exclusive_block(rng, n, [3, 6, 11, 20, 4, 8], nan_share=0.2),
        a, dense], axis=1)
    return X, how


TABLES = {"onehot": onehot_table, "mixed": mixed_table,
          "zero_as_missing": functools.partial(mixed_table,
                                               zero_as_missing=True)}


@functools.lru_cache(maxsize=None)
def learner_of(table, **config):
    X, how = TABLES[table]()
    rng = np.random.RandomState(1)
    y = (rng.rand(len(X)) < 0.4).astype(np.float32)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=255,
                                   min_data_in_leaf=0, **how)
    assert ds.is_bundled
    cfg = Config(objective="binary", verbosity=-1, **config)
    return ds, SerialTreeLearner(ds, cfg)


def leaf_of(ds, lrn, seed, share):
    """A random leaf: (group histogram [G, 2, Bg], sum g, sum h, rows)."""
    rng = np.random.RandomState(seed)
    rows = np.flatnonzero(rng.rand(ds.num_data) < share)
    g = rng.normal(size=len(rows))
    h = rng.uniform(0.05, 0.25, size=len(rows))
    hist = np.zeros((ds.binned.shape[1], 2, lrn.num_bins))
    for c in range(ds.binned.shape[1]):
        code = ds.binned[rows, c].astype(np.int64)
        hist[c, 0] = np.bincount(code, weights=g, minlength=lrn.num_bins)
        hist[c, 1] = np.bincount(code, weights=h, minlength=lrn.num_bins)
    return hist.astype(np.float32), g.sum(), h.sum(), len(rows)


def test_the_tables_hold_what_the_cases_need():
    ds, lrn = learner_of("onehot")
    nb = np.asarray(ds.num_bin_per_feature)
    sizes = sorted(len(g) for g in ds.feature_groups)
    assert (nb == 2).sum() == 139 and sizes[:2] == [1, 1] and sizes[-1] > 20
    alone = [g[0] for g in ds.feature_groups if len(g) == 1]
    assert all(nb[j] > 200 and ds.default_bins()[j] != 0 for j in alone)

    ds, lrn = learner_of("mixed")
    nb, mt = np.asarray(ds.num_bin_per_feature), ds.missing_types()
    bundled = np.asarray([len(ds.feature_groups[g]) > 1 for g in ds.group_idx])
    assert ds.conflict_rows == 2
    assert nb[bundled].min() <= 3 and 40 <= nb[bundled].max() <= 61
    assert (mt[bundled] == NAN).sum() >= 4 and (mt[~bundled] == NAN).sum() == 1
    assert ((mt == NONE) & ~bundled & (ds.default_bins() != 0)).sum() == 2

    ds, lrn = learner_of("zero_as_missing")
    mt = ds.missing_types()
    bundled = np.asarray([len(ds.feature_groups[g]) > 1 for g in ds.group_idx])
    assert (mt == ZERO).sum() >= 20 and bundled.sum() >= 10
    assert ((ds.default_bins() != 0) & ~bundled).sum() >= 2


BASE = dict(min_data_in_leaf=5, min_sum_hessian_in_leaf=1e-3)
PARAMS = {
    "plain": S.SplitParams(**BASE),
    "minimums_cut": S.SplitParams(min_data_in_leaf=150,
                                  min_sum_hessian_in_leaf=25.0),
    "l1_l2_delta": S.SplitParams(lambda_l1=0.5, lambda_l2=2.0,
                                 max_delta_step=0.3, **BASE),
    "min_gain": S.SplitParams(min_gain_to_split=3.0, **BASE),
    "extra_trees": S.SplitParams(extra_trees=True, extra_seed=9, **BASE),
}


def contri_of(nf):
    return tuple(np.random.RandomState(5).choice([0.0, 0.3, 1.0, 2.5],
                                                 size=nf).tolist())


@pytest.mark.parametrize("share,seed", [(1.0, 1), (0.3, 2), (0.02, 3)])
@pytest.mark.parametrize("case", list(PARAMS) + ["feature_fraction",
                                                 "feature_contri", "monotone"])
@pytest.mark.parametrize("table", list(TABLES))
def test_random_leaves(table, case, share, seed):
    config, bounds = {}, None
    if case == "monotone":
        ds, _ = learner_of(table)
        config = {"monotone_constraints": tuple(
            np.random.RandomState(3).choice(
                [-1, 0, 1], size=ds.num_total_features).tolist())}
        bounds = (-0.15, 0.25)
    ds, lrn = learner_of(table, **config)
    nf = ds.num_features
    params = PARAMS.get(case, PARAMS["plain"])
    if case == "feature_contri":
        params = params._replace(feature_contri=contri_of(nf))
    mask = np.ones(nf, bool)
    if case == "feature_fraction":
        mask = np.random.RandomState(seed).rand(nf) < 0.5
    h, sg, sh, cnt = leaf_of(ds, lrn, seed, share)
    lidx, lmask, lanes = lrn.unpack_lanes
    old, new = both(h, lrn.feat, lidx, lmask, lanes, mask, sg, sh, cnt,
                    params, bounds)
    if share > 0.02:
        assert np.isfinite(old.gain)       # there was something to compare
    assert_same_split(old, new)


def test_every_candidate_is_a_lane():
    """Each (feature, threshold 0..nb-2) has exactly one lane, in the
    feature's group, holding the bin above the threshold."""
    for table in TABLES:
        ds, lrn = learner_of(table)
        lanes = S.GroupLanes(*[np.asarray(x) for x in lrn.unpack_lanes[2]])
        nf = ds.num_features
        nb = np.asarray(ds.num_bin_per_feature)
        seen = {}
        for g, l in zip(*np.nonzero(lanes.feature < nf)):
            f, t = int(lanes.feature[g, l]), int(lanes.threshold[g, l])
            assert (f, t) not in seen and g == ds.group_idx[f]
            assert l == ds.bin_offset[f] + t and 0 <= t <= nb[f] - 2
            mine = (np.arange(lrn.num_bins) >= ds.bin_offset[f]) \
                & (np.arange(lrn.num_bins) < ds.bin_offset[f] + nb[f] - 1)
            after = lanes.scan[g, :, l].astype(bool)
            before = lanes.scan[g, :, lrn.num_bins + l].astype(bool)
            np.testing.assert_array_equal(
                after, mine & (np.arange(lrn.num_bins) >= l))
            np.testing.assert_array_equal(
                before, mine & (np.arange(lrn.num_bins) < l))
            assert lanes.num_bin[g, l] == nb[f]
            seen[f, t] = (g, l)
        assert len(seen) == int((nb - 1).sum())
        assert (lanes.num_bin[lanes.feature == nf] == 0).all()
        assert not lanes.scan[lanes.feature == nf].any()


# ---- hand-laid groups: ties and precision ----------------------------------

class Hand:
    """Groups laid out as ``_assign_group_layout`` lays them, from
    ``[(group, num_bin, missing_type, default_bin)]`` in feature order."""

    def __init__(self, feats, group_bins=256, feat_bins=256):
        self.nf = len(feats)
        group, nb, mt, dbin = (np.asarray(x, np.int32) for x in zip(*feats))
        self.G = int(group.max()) + 1
        offset = np.zeros(self.nf, np.int32)
        used = np.ones(self.G, np.int32)
        for f in range(self.nf):
            offset[f] = used[group[f]]
            used[group[f]] += nb[f] - 1
        self.offset, self.nb, self.group_bins = offset, nb, group_bins
        lane = np.arange(feat_bins, dtype=np.int32)[None, :]
        self.lidx = jnp.asarray(np.clip(offset[:, None] + lane - 1, 0,
                                        group_bins - 1))
        self.lmask = jnp.asarray(((lane >= 1) & (lane < nb[:, None])
                                  ).astype(np.float32))
        mono = np.zeros(self.nf, np.int32)
        self.lanes = S.group_lanes(group, offset, nb, mt, dbin, mono, self.G,
                                   group_bins)
        self.feat = S.FeatureInfo(
            num_bin=jnp.asarray(nb), missing_type=jnp.asarray(mt),
            default_bin=jnp.asarray(dbin),
            is_categorical=jnp.zeros(self.nf, bool),
            monotone=jnp.asarray(mono), group=jnp.asarray(group),
            offset=jnp.asarray(offset))
        self.group = group

    def hist(self, bins):
        """Group histogram from ``{feature: [(g, h) of bins 1..nb-1]}``."""
        h = np.zeros((self.G, 2, self.group_bins), np.float32)
        for f, gh in bins.items():
            for k, (g, hh) in enumerate(gh):
                h[self.group[f], :, self.offset[f] + k] = (g, hh)
        return h

    def search(self, h, sg, sh, cnt, params=S.SplitParams(
            min_data_in_leaf=1, min_sum_hessian_in_leaf=1e-3)):
        return both(h, self.feat, self.lidx, self.lmask, self.lanes,
                    np.ones(self.nf, bool), sg, sh, cnt, params)


def test_an_empty_bin_inside_a_feature_takes_the_largest_threshold():
    hand = Hand([(0, 3, NONE, 0), (0, 6, NONE, 0)])
    # feature 1: bins 2 and 3 hold nothing, so thresholds 1, 2 and 3 cut the
    # same rows; halves and quarters, so every sum is exact
    h = hand.hist({0: [(0.5, 1.0), (-0.25, 1.0)],
                   1: [(4.0, 2.0), (0, 0), (0, 0), (-6.0, 2.0), (-1.0, 1.0)]})
    old, new = hand.search(h, sg=-2.0, sh=12.0, cnt=48)
    assert (int(old.feature), int(old.threshold)) == (1, 3)
    assert_same_split(old, new, rtol=0)
    np.testing.assert_allclose(new.gain, old.gain, rtol=1e-6)


def test_of_two_equal_features_the_smaller_id_wins_whatever_its_group():
    # feature 0 lives in group 1, features 1 and 2 in group 0: the flat order
    # of the lanes is not the order of the ids
    hand = Hand([(1, 4, NONE, 0), (0, 3, NONE, 0), (0, 4, NONE, 0)])
    same = [(3.0, 2.0), (-1.5, 1.0), (-2.0, 3.0)]
    h = hand.hist({0: same, 1: [(0.25, 1.0), (0.5, 0.5)], 2: same})
    old, new = hand.search(h, sg=1.0, sh=16.0, cnt=64)
    assert int(old.feature) == 0
    assert_same_split(old, new, rtol=0)
    np.testing.assert_allclose(new.gain, old.gain, rtol=1e-6)


def test_a_tie_between_the_directions_goes_to_the_first():
    # NaN missing, the NaN bin (the last) empty: both directions cut the same
    # rows at every threshold; direction 0 reports default_left
    hand = Hand([(0, 5, NAN, 0), (0, 3, NONE, 0)])
    h = hand.hist({0: [(2.0, 1.0), (-3.0, 2.0), (1.0, 1.0), (0, 0)],
                   1: [(0.25, 1.0), (0.5, 0.5)]})
    old, new = hand.search(h, sg=-0.5, sh=8.0, cnt=32)
    assert int(old.feature) == 0 and bool(old.default_left)
    assert_same_split(old, new, rtol=0)
    np.testing.assert_allclose(new.gain, old.gain, rtol=1e-6)


def test_two_bins_and_nan_report_default_right():
    hand = Hand([(0, 2, NAN, 0), (0, 2, NONE, 0)])
    h = hand.hist({0: [(3.0, 2.0)], 1: [(0.25, 1.0)]})
    old, new = hand.search(h, sg=1.0, sh=8.0, cnt=32)
    assert int(old.feature) == 0 and not bool(old.default_left)
    assert_same_split(old, new, rtol=0)


def test_a_small_level_keeps_its_own_rounding():
    """A level of 50 rows (and a three-bin feature of 80) behind levels that
    hold a million rows with g near 0.5: the scans give its side of the split
    to the rounding of its OWN sum, where a difference of group-wide prefix
    sums gives it the group's."""
    rng = np.random.RandomState(7)
    hand = Hand([(0, 2, NONE, 0)] * 20 + [(0, 2, NONE, 0), (0, 3, NONE, 0)])
    big = [(50000 * g, 50000 * 0.25) for g in rng.uniform(0.45, 0.55, 20)]
    small = [(50 * 0.4871239, 50 * 0.25)]
    pair = [(45 * 0.5123411, 45 * 0.25), (35 * 0.4922107, 35 * 0.25)]
    h = hand.hist({**{f: [big[f]] for f in range(20)}, 20: small, 21: pair})
    sg = float(np.float32(h[0, 0].astype(np.float64).sum() + 1234.5))
    sh = float(h[0, 1].astype(np.float64).sum() + 3000.0)
    lanes = hand.lanes
    scans = S.group_scans(jnp.asarray(h), lanes, jnp.float32(sg),
                          jnp.float32(sh), jnp.int32(1_020_000))
    after = np.asarray(scans.after)
    l20, l21 = hand.offset[20], hand.offset[21]
    exact = h[0, 0].astype(np.float64)
    want = {l20: exact[l20], l21: exact[l21] + exact[l21 + 1],
            l21 + 1: exact[l21 + 1]}
    for lane, value in want.items():
        assert abs(after[0, 0, lane] - value) <= 1e-6 * abs(value)
    # what a difference of group-wide f32 prefixes would have made of them
    prefix = np.cumsum(h[0, 0], dtype=np.float32)
    wide = np.float32(prefix[l20]) - np.float32(prefix[l20 - 1])
    assert abs(wide - want[l20]) > 1e-4 * abs(want[l20])
    # and the searched split's sums against the old path's, whose right side
    # is a difference of the FEATURE's prefixes, bin 0 (the rest of the leaf)
    # among them: equal to the rounding of the leaf's total, no closer
    old, new = hand.search(h, sg, sh, 1_020_000)
    assert_same_split(old, new, rtol=1e-6)


# ---- fallbacks and the counter ---------------------------------------------

def table_with_a_categorical():
    rng = np.random.RandomState(4)
    n = 6000
    X = np.concatenate([exclusive_block(rng, n, [1] * 30, active=1.0),
                        rng.randint(0, 6, size=(n, 1)).astype(np.float32),
                        rng.normal(size=(n, 1)).astype(np.float32)], axis=1)
    y = ((X[:, 3] + X[:, 5] + (X[:, 30] == 2) + 0.5 * X[:, 31]
          + rng.normal(scale=0.5, size=n)) > 0.5).astype(np.float32)
    return X, y


@pytest.mark.parametrize("name,how,config,in_groups", [
    ("bundled", {}, {}, True),
    ("categorical", {"categorical_feature": (30,)}, {}, False),
    ("cegb", {}, {"cegb_penalty_split": 1e-4}, False),
])
def test_search_lanes_says_which_path(name, how, config, in_groups):
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.objective import create_objective
    X, y = table_with_a_categorical()
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=63, min_data_in_leaf=0,
                                   **how)
    assert ds.is_bundled and "efb.search_lanes" not in efb.counts()
    cfg = Config(objective="binary", num_leaves=7, min_data_in_leaf=5,
                 verbosity=-1, **config)
    g = GBDT(cfg, ds, create_objective("binary", cfg))
    lrn = g.learner
    assert lrn.grouped
    want = (len(ds.feature_groups) * lrn.num_bins if in_groups
            else ds.num_features * lrn.feat_bins)
    assert efb.counts()["efb.search_lanes"] == want
    g.train_one_iter()
    assert g.models[0].num_leaves > 2


def test_an_unbundled_table_records_no_search_lanes():
    rng = np.random.RandomState(0)
    X = rng.normal(size=(500, 4)).astype(np.float32)
    ds = BinnedDataset.from_matrix(X, label=(X[:, 0] > 0).astype(np.float32))
    SerialTreeLearner(ds, Config(objective="binary", verbosity=-1))
    assert not ds.is_bundled and "efb.search_lanes" not in efb.counts()
