"""Row and column subsampling as a deployment states it, in NumPy, independent
of the program under test: it imports nothing of it.

The semantics: a tree is grown on the rows of its BAG and the columns of its
MASK.  Out-of-bag rows add nothing to any histogram, count or leaf value of
that tree; masked features are never candidates; the bag changes every
``bagging_freq`` iterations, the mask every iteration.

Both draws are stateless functions the program documents
(``lightgbm_tpu/boosting/gbdt.py``: ``_hash_u32``, ``_bag_uniforms``,
``feature_mask_of``), written here again from that description:

- ``hash(id, seed, key)``, uint32 throughout: ``x = id * 2654435761``;
  ``x ^= seed + key * 0x9E3779B9``; ``x ^= x >> 16``; ``x *= 2246822519``;
  ``x ^= x >> 13``; ``x *= 3266489917``; ``x ^= x >> 16``.
- the bag of iteration ``it``: ``key`` is the window's first iteration,
  ``it - it % bagging_freq``; row ``r`` is in the bag when
  ``float32(hash(r, bagging_seed, key)) * 2**-32 < float32(bagging_fraction)``,
  the conversion made from the two 16-bit halves (``hi * 65536 + lo`` in
  float32: the one rounded add is the conversion's own rounding).  An
  independent Bernoulli draw a row: the bag's size is what comes out.
- the mask of iteration ``it``: ``hash(f, feature_fraction_seed, it)`` of every
  feature id, the ``max(1, round(F * feature_fraction))`` smallest taken,
  equal hashes to the smaller id.
"""
from __future__ import annotations

import numpy as np

import plain_tree


def hash_u32(ids, seed, key):
    """[n] uint32 hash of (``ids``, ``seed``, ``key``)."""
    x = np.asarray(ids).astype(np.uint32) * np.uint32(2654435761)
    mixed = ((int(seed) & 0xFFFFFFFF) + int(key) * 0x9E3779B9) & 0xFFFFFFFF
    x ^= np.uint32(mixed)
    x ^= x >> np.uint32(16)
    x *= np.uint32(2246822519)
    x ^= x >> np.uint32(13)
    x *= np.uint32(3266489917)
    x ^= x >> np.uint32(16)
    return x


def bag_of(rows, seed, iteration, freq, fraction):
    """[rows] bool: the rows in the bag of ``iteration``."""
    x = hash_u32(np.arange(rows, dtype=np.uint32), seed,
                 iteration - iteration % freq)
    u = ((x >> np.uint32(16)).astype(np.float32) * np.float32(65536.0)
         + (x & np.uint32(0xFFFF)).astype(np.float32))
    return u * np.float32(1.0 / 4294967296.0) < np.float32(fraction)


def features_used(features, fraction):
    if fraction >= 1.0 or features <= 1:
        return features
    return max(1, int(round(features * fraction)))


def mask_of(features, seed, iteration, fraction):
    """[features] bool: the features ``iteration`` may split on."""
    order = np.argsort(hash_u32(np.arange(features), seed, iteration),
                       kind="stable")
    mask = np.zeros(features, bool)
    mask[order[:features_used(features, fraction)]] = True
    return mask


def grow_steps(codes, grad, hess, bag, mask, *, follow=(), **how):
    """``plain_tree.grow_steps`` on ``codes[bag][:, mask]``, told in the whole
    table's feature ids: a step's ``feature`` is a column of ``codes`` and its
    ``gains`` tables have a row for every feature, -inf for a masked one, so
    that ``plain_tree.splits_agree`` prices any split another grower made.
    ``follow`` (that grower's splits, whole-table feature ids) is followed as
    far as its features are in the mask: a split on a masked feature cannot
    be taken, and ``splits_agree`` fails there."""
    columns = np.flatnonzero(mask)
    column_of = np.full(len(mask), -1)
    column_of[columns] = np.arange(len(columns))
    can_follow = []
    for leaf, feature, t in follow:
        if column_of[feature] < 0:
            break
        can_follow.append((leaf, int(column_of[feature]), t))
    live = np.flatnonzero(bag)
    sub = np.ascontiguousarray(codes[live][:, columns])
    for step in plain_tree.grow_steps(sub, np.asarray(grad)[live],
                                      np.asarray(hess)[live],
                                      follow=can_follow, **how):
        gains = {}
        for leaf, table in step["gains"].items():
            gains[leaf] = np.full((len(mask),) + table.shape[1:], -np.inf)
            gains[leaf][columns] = table
        yield dict(step, feature=int(columns[step["feature"]]), gains=gains)


def binary_gradients(y, trees, codes):
    """(grad, hess) of binary logloss on every row as the tree after
    ``trees`` should see them: the label mean before the first tree
    (boost_from_average), else from a walk of ``trees`` over ``codes``."""
    y = np.asarray(y, np.float64)
    if not trees:
        p = np.full(len(y), np.mean(y))
    else:
        p = 1.0 / (1.0 + np.exp(-plain_tree.scores_of(trees, codes)))
    return p - y, p * (1.0 - p)
