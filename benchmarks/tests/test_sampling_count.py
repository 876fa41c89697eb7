"""``readers/sampling_count.py`` on hand-made trees: the bag's share of the
rows from the program's count, and the out-of-bag share of the traced trees'
window rows from each tree's own root count; nothing to read without the
program's sampling counts or without traced trees."""
import types

import numpy as np
import pytest

from readers import sampling_count

CFG = {"features": 4, "params": {"max_bin": 255}}


def tree(internal, leaf, left, right):
    return types.SimpleNamespace(
        num_leaves=len(leaf), internal_count=np.asarray(internal),
        leaf_count=np.asarray(leaf), left_child=np.asarray(left),
        right_child=np.asarray(right))


def ctx_of(trees, counters, rows=1000):
    job = types.SimpleNamespace(traced_trees=trees, counters=counters,
                                gbdt=types.SimpleNamespace(num_data=rows))
    return {"job": job, "trace": None, "cfg": CFG}


def test_bag_share_is_the_programs_count_over_the_rows():
    ctx = ctx_of([], {"sampling_bag_rows": 803.0})
    assert sampling_count.read({"what": "bag_rows_share"}, ctx) \
        == pytest.approx(80.3)


def test_dead_rows_by_each_trees_own_bag():
    # tree a: a bag of 800 of 1000 rows; windows of 800 + 500 in-bag rows,
    # which hold 1000 + 625 rows: 325 dead.  tree b: a bag of 500; one window
    # of 500 in-bag rows holding 1000: 500 dead.  A stump has no window.
    a = tree([800, 500], [300, 200, 300], [1, ~1], [~0, ~2])
    b = tree([500], [250, 250], [~0], [~1])
    stump = tree([0], [1000], [0], [0])
    ctx = ctx_of([a, b, stump], {"sampling_bag_rows": 500.0})
    got = sampling_count.read({"what": "dead_window_rows_share"}, ctx)
    assert got == pytest.approx(100.0 * (325 + 500) / (1300 + 500 + 825))
    full = tree([1000], [400, 600], [~0], [~1])      # nothing sampled out
    assert sampling_count.read({"what": "dead_window_rows_share"},
                               ctx_of([full], {"sampling_bag_rows": 1000.0})) == 0


@pytest.mark.parametrize("what", ["bag_rows_share", "dead_window_rows_share"])
def test_nothing_to_read(what):
    t = tree([800], [400, 400], [~0], [~1])
    assert sampling_count.read({"what": what}, ctx_of([t], {})) is None
    if what == "dead_window_rows_share":
        assert sampling_count.read(
            {"what": what}, ctx_of([], {"sampling_bag_rows": 800.0})) is None
