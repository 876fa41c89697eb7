"""``plain_categorical`` on leaves small enough to know the answer: the best
subset by enumeration of every subset, the batching and the stops by hand,
and the walk's categorical decision."""
import itertools
import types

import numpy as np
import pytest

import conftest  # noqa: F401  (puts benchmarks/ on the path)
import plain_categorical as pc


def free(**how):
    """Parameters under which every prefix of the sorted order is offered."""
    how = dict(dict(min_data_in_leaf=0, min_sum_hessian_in_leaf=0.0,
                    cat_l2=0.0, cat_smooth=0.0, max_cat_threshold=64,
                    max_cat_to_onehot=0, min_data_per_group=0), **how)
    return pc.Params(**how)


def best_by_enumeration(g, h):
    """(gain, the two sides) of the best of all 2^(k-1) - 1 partitions."""
    k, sg, sh = len(g), g.sum(), h.sum()
    found = (-np.inf, None)
    for size in range(1, k):
        for left in itertools.combinations(range(k), size):
            at = list(left)
            gl, hl = g[at].sum(), h[at].sum()
            gain = gl * gl / hl + (sg - gl) ** 2 / (sh - hl) - sg * sg / sh
            if gain > found[0] + 1e-12:
                found = (gain, frozenset(left))
    gain, left = found
    return gain, {left, frozenset(range(k)) - left}


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_the_sorted_walk_finds_the_best_of_all_subsets(seed):
    rng = np.random.default_rng(seed)
    k = 9
    counts = rng.integers(20, 200, size=k).astype(np.float64)
    h = 0.25 * counts                      # a constant hessian a row
    g = rng.normal(size=k) * np.sqrt(counts)
    column = pc.Column(True, k, k)
    gain, bins = pc.categorical_best(g, h, counts.sum(), column, free())
    want, sides = best_by_enumeration(g, h)
    assert frozenset(bins) in sides
    assert gain == pytest.approx(want, rel=1e-12)
    assert pc.set_gain(g, h, bins, column, free()) == pytest.approx(gain)


def test_one_against_the_rest_where_the_column_has_few_bins():
    g = np.array([4.0, -6.0, 1.0, 1.0])
    h = np.array([10.0, 10.0, 10.0, 10.0])
    p = free(max_cat_to_onehot=4, cat_l2=10.0)
    gain, bins = pc.categorical_best(g, h, 40, pc.Column(True, 4, 4), p)
    assert bins == (1,)
    # under lambda_l2 alone: cat_l2 belongs to the many-vs-many search
    assert gain == pytest.approx(36 / 10 + 36 / 30)
    # the other-bin is never a candidate
    gain, bins = pc.categorical_best(g, h, 40, pc.Column(True, 4, 1), p)
    assert bins == (0,)


def test_the_walk_stops_batches_and_regularises_as_described():
    g = np.array([-5.0, -3.0, -1.0, 1.0, 3.0, 5.0])
    h = np.full(6, 25.0)                   # 100 rows a bin at 0.25 a row
    column = pc.Column(True, 6, 6)
    # prefixes of 1, 2, 3 bins from either end: the middle one is best
    gain, bins = pc.categorical_best(g, h, 600, column, free())
    assert bins in ((0, 1, 2), (3, 4, 5))
    # cat_l2 on both children
    gain10, _ = pc.categorical_best(g, h, 600, column, free(cat_l2=10.0))
    assert gain10 == pytest.approx(2 * 81 / 85)
    # at most max_cat_threshold bins go left
    _, bins = pc.categorical_best(g, h, 600, column, free(max_cat_threshold=1))
    assert bins in ((0,), (5,))
    # a candidate only every min_data_per_group rows: 250 rows are 3 bins
    _, bins = pc.categorical_best(g, h, 600, column,
                                  free(min_data_per_group=250))
    assert len(bins) == 3
    # bins under cat_smooth rows are not sorted at all
    thin = h.copy()
    thin[0] = 1.0                          # 4 rows
    _, bins = pc.categorical_best(g, thin, 504, column, free(cat_smooth=10.0))
    assert 0 not in bins or len(bins) == 5
    # nothing to offer: a right side under min_data_per_group
    assert pc.categorical_best(g[:2], h[:2], 200, pc.Column(True, 2, 2),
                               free(min_data_per_group=150)) is None


def tree_of(**arrays):
    return types.SimpleNamespace(**{k: (np.asarray(v) if k != "num_leaves"
                                        else v) for k, v in arrays.items()})


def test_the_walk_sends_a_row_left_when_its_value_is_in_the_set():
    # node 0: categorical on column 0, left set {1, 3, 40}; node 1 (its right
    # child): numerical on column 1 at 0.5
    tree = tree_of(num_leaves=3, split_feature=[0, 1], threshold=[0.0, 0.5],
                   decision_type=[1, 0], left_child=[-1, -2],
                   right_child=[1, -3], leaf_value=[10.0, 20.0, 30.0],
                   cat_boundaries=[0, 2],
                   cat_threshold=[(1 << 1) | (1 << 3), 1 << (40 - 32)])
    X = np.array([[1, 0], [3, 9], [40, 0], [0, 0.2], [2, 0.7], [41, 0.5],
                  [-1, 0], [64, 0], [1000, 0], [np.nan, 1], [np.inf, 0]],
                 np.float64)
    assert pc.walk([tree], X).tolist() == [10, 10, 10, 20, 30, 20, 20, 20,
                                           20, 30, 20]
    assert pc.walk([tree, tree], X[:1]).tolist() == [20]
    # NaN: category 0 where the column's missing type is not NaN (bits 2-3)
    tree.cat_threshold = np.asarray([1, 0])
    assert pc.walk([tree], X[-2:-1]).tolist() == [10]
    tree.decision_type = np.asarray([1 | (2 << 2), 0])
    assert pc.walk([tree], X[-2:-1]).tolist() == [30]


def test_leaves_in_bin_space_and_the_leaf_values_l2():
    # bin space: node 0 categorical, left bins {0, 2}; node 1 numerical <= 1
    tree = tree_of(num_leaves=3, split_feature_inner=[0, 1],
                   threshold_in_bin=[0, 1], decision_type=[1, 0],
                   left_child=[-1, -2], right_child=[1, -3],
                   leaf_value=[1.0, 2.0, 3.0],
                   cat_boundaries_inner=[0, 1], cat_threshold_inner=[0b101])
    codes = np.array([[0, 0], [2, 3], [1, 1], [1, 2], [3, 0]], np.uint8)
    assert pc.tree_splits(tree, 8) == [(0, 0, (0, 2)), (1, 1, 1)]
    assert pc.leaves_of(tree, codes, 4).tolist() == [0, 0, 1, 2, 1]
    assert pc.scores_of([tree], codes, 4).tolist() == [1, 1, 2, 3, 2]
    columns = [pc.Column(True, 8, 8), pc.Column(False)]
    p = free(cat_l2=10.0)
    want = pc.leaf_values(tree, np.array([0, 0, 1, 2, 1]),
                          np.array([1.0, 1, 2, 3, 4]), np.full(5, 0.5),
                          columns, p, 0.1, bias=0.25)
    # leaf 0 was made by the many-vs-many split, leaves 1 and 2 were not
    assert want.tolist() == pytest.approx(
        [0.25 - 0.1 * 2 / 11, 0.25 - 0.1 * 6 / 1, 0.25 - 0.1 * 3 / 0.5])
