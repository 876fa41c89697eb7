"""``readers/right_rows.py`` on hand-made trees: the right children's rows,
an internal child by ``internal_count`` and a leaf by ``leaf_count``, per
traced tree; nothing to read without traced trees."""
import types

import numpy as np

from readers import right_rows


def tree(internal, leaf, left, right):
    return types.SimpleNamespace(
        num_leaves=len(leaf), internal_count=np.asarray(internal),
        leaf_count=np.asarray(leaf), left_child=np.asarray(left),
        right_child=np.asarray(right))


def ctx_of(trees):
    return {"job": types.SimpleNamespace(traced_trees=trees), "trace": None}


def test_right_children_by_internal_and_leaf_counts():
    # node 0 (100 rows) -> node 1 (60) | leaf 0 (40); node 1 -> leaf 1 (25) |
    # node 2 (35); node 2 -> leaf 2 (30) | leaf 3 (5)
    t = tree([100, 60, 35], [40, 25, 30, 5], [1, ~1, ~2], [~0, 2, ~3])
    stump = tree([0], [100], [0], [0])          # no split made: no rows
    assert right_rows.read({}, ctx_of([t])) == 40 + 35 + 5
    assert right_rows.read({}, ctx_of([t, stump])) == (40 + 35 + 5) / 2


def test_no_traced_trees_is_nothing_to_read():
    assert right_rows.read({}, ctx_of([])) is None

