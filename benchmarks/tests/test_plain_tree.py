"""``plain_tree.grow`` against a brute force on a small table, and what
``splits_agree`` lets pass."""
import numpy as np

import plain_reference
import plain_tree

LIMITS = dict(min_data_in_leaf=20, min_sum_hessian_in_leaf=1e-3)


def table(rows=2000, features=6, bins=16, seed=5):
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, bins, size=(rows, features), dtype=np.uint8)
    y = (codes[:, 0] + 2.0 * (codes[:, 1] > 7) * codes[:, 2]
         + rng.normal(scale=4.0, size=rows) > 12).astype(np.float64)
    p0 = y.mean()
    return codes, y, p0 - y, np.full(rows, p0 * (1 - p0))


def brute_best(codes, grad, hess, rows, bins):
    """(gain, feature, bin) of the best split of the leaf holding ``rows``,
    every candidate priced from scratch."""
    best = (-np.inf, -1, -1)
    G, H = grad[rows].sum(), hess[rows].sum()
    for f in range(codes.shape[1]):
        for t in range(bins - 1):
            left = rows[codes[rows, f] <= t]
            right = rows[codes[rows, f] > t]
            if min(len(left), len(right)) < LIMITS["min_data_in_leaf"]:
                continue
            gl, hl = grad[left].sum(), hess[left].sum()
            gain = gl * gl / hl + (G - gl) ** 2 / (H - hl) - G * G / H
            if gain > best[0] + 1e-12:
                best = (gain, f, t)
    return best


def test_grow_is_the_brute_force_best_leaf_first():
    codes, _, grad, hess = table()
    steps = plain_tree.grow(codes, grad, hess, num_bins=16, splits=8, **LIMITS)
    assert len(steps) == 8
    leaves = {0: np.arange(len(codes))}
    for k, step in enumerate(steps):
        priced = {leaf: brute_best(codes, grad, hess, rows, 16)
                  for leaf, rows in leaves.items()}
        leaf = max(priced, key=lambda l: (priced[l][0], -l))
        gain, f, t = priced[leaf]
        assert (step["leaf"], step["feature"], step["bin"]) == (leaf, f, t)
        assert step["gain"] == np.float64(gain) or abs(
            step["gain"] - gain) < 1e-9 * abs(gain)
        rows = leaves[leaf]
        leaves[leaf] = rows[codes[rows, f] <= t]
        leaves[k + 1] = rows[codes[rows, f] > t]


def test_root_step_is_plain_references_root_split():
    codes, y, grad, hess = table()
    step = plain_tree.grow(codes, grad, hess, num_bins=16, splits=1,
                           **LIMITS)[0]
    gains = plain_reference.root_gains(codes, y, num_bins=16, **LIMITS)
    assert (step["feature"], step["bin"]) == np.unravel_index(
        np.argmax(gains), gains.shape)


def test_constraints_end_the_tree():
    codes, _, grad, hess = table(rows=60)
    steps = plain_tree.grow(codes, grad, hess, num_bins=16, splits=8, **LIMITS)
    assert len(steps) <= 2     # 60 rows cannot make 9 leaves of 20


def test_splits_agree_takes_the_plain_choice_and_near_ties_only():
    codes, _, grad, hess = table()
    steps = plain_tree.grow(codes, grad, hess, num_bins=16, splits=4, **LIMITS)
    mine = [(s["leaf"], s["feature"], s["bin"]) for s in steps]
    ok, said = plain_tree.splits_agree(steps, mine)
    assert ok and said.startswith("4 splits") and "0 near ties" in said
    # another split of real gain, far from the best, is told apart
    leaf, f, t = mine[2]
    ok, said = plain_tree.splits_agree(
        steps, mine[:2] + [(leaf, (f + 1) % 6, t)] + mine[3:])
    assert not ok and said.startswith("split 2:")
    # fewer splits than the plain grower made is not agreement
    assert not plain_tree.splits_agree(steps, mine[:3])[0]


def test_a_tie_taken_the_other_way_is_followed_to_the_end():
    """With the bin above the root's threshold emptied, "<= t" and "<= t + 1"
    are one split; a grower that takes the other end is priced on its own
    tree afterwards."""
    codes, _, grad, hess = table()
    _, f, t = [(s["leaf"], s["feature"], s["bin"]) for s in plain_tree.grow(
        codes, grad, hess, num_bins=16, splits=1, **LIMITS)][0]
    assert t + 2 < 16
    codes = codes.copy()
    codes[codes[:, f] == t + 1, f] = t + 2
    steps = plain_tree.grow(codes, grad, hess, num_bins=16, splits=4, **LIMITS)
    mine = [(s["leaf"], s["feature"], s["bin"]) for s in steps]
    assert mine[0] == (0, f, t)
    other = [(0, f, t + 1)] + mine[1:]
    followed = plain_tree.grow(codes, grad, hess, num_bins=16, splits=4,
                               follow=other, **LIMITS)
    ok, said = plain_tree.splits_agree(followed, other)
    assert ok and "1 near ties" in said
    # the same tree grows on: the later steps are the plain grower's own
    assert [(s["leaf"], s["feature"], s["bin"]) for s in followed] == mine
    # following a split that is no tie still prices it as wrong
    bad = list(mine)
    bad[1] = (mine[1][0], (mine[1][1] + 1) % 6, mine[1][2])
    followed = plain_tree.grow(codes, grad, hess, num_bins=16, splits=4,
                               follow=bad, **LIMITS)
    assert not plain_tree.splits_agree(followed, bad)[0]
