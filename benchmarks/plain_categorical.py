"""A plain leaf-wise grower and tree walk for tables with CATEGORICAL columns
(NumPy, float64, plain Python loops), independent of the program under test:
it imports nothing of it.

Written from the description of LightGBM's search
(``feature_histogram.hpp:136-304`` ``FindBestThresholdCategorical``,
``docs/Features.rst`` "Optimal Split for Categorical Features").  A
categorical column arrives as bin codes under a mapping that is taken as
given (which levels share a bin is the ingest's decision, as
``plain_sparse`` takes the program's bin boundaries): ``Column.num_bin`` bins
of which the first ``Column.used_bin`` are categories that may be searched
(the last bin is the other-bin when the mapping dropped rare levels).

For one leaf and one categorical column, from the per-bin sums of gradients
``g`` and hessians ``h``:

- every bin's row count is ESTIMATED from its hessians, as the reference does,
  ``round(h * n / sum_h)`` with ``n`` the leaf's row count;
- a column of at most ``max_cat_to_onehot`` bins offers one category against
  the rest, under ``lambda_l2``;
- any other column keeps the bins with count >= ``cat_smooth``, sorts them by
  ``g / (h + cat_smooth)`` and walks the order from both ends, at most
  ``min(max_cat_threshold, (used + 1) // 2)`` bins: a prefix is a candidate
  once both sides hold ``min_data_in_leaf`` rows and
  ``min_sum_hessian_in_leaf``, the right side ``min_data_per_group`` rows, and
  the prefix has grown by ``min_data_per_group`` rows since the last
  candidate; its gain is taken under ``lambda_l2 + cat_l2``.

The reported gain is the candidate's less the leaf's own ``G^2 / (H +
lambda_l2)``.  Numerical columns are searched by ``plain_tree.split_gains``.
The tree grows leaf-wise as ``plain_tree.grow_steps`` does; a child's row
count is the split's estimate of it, again as the reference hands it on.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import numpy as np

import plain_tree


class Column(NamedTuple):
    categorical: bool
    num_bin: int = 0      # categorical columns only: the mapping's bins
    used_bin: int = 0     # ... and how many of them are searchable categories


class Params(NamedTuple):
    min_data_in_leaf: int
    min_sum_hessian_in_leaf: float
    lambda_l2: float = 0.0
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_threshold: int = 32
    max_cat_to_onehot: int = 4
    min_data_per_group: int = 100


def params_of(params):
    """:class:`Params` from those of a configuration's ``params`` that the
    search reads."""
    kinds = dict(min_data_in_leaf=int, min_sum_hessian_in_leaf=float,
                 lambda_l2=float, cat_l2=float, cat_smooth=float,
                 max_cat_threshold=int, max_cat_to_onehot=int,
                 min_data_per_group=int)
    return Params(**{name: kind(params[name]) for name, kind in kinds.items()
                     if name in params})


def _pair_gain(gl, hl, gr, hr, l2):
    return gl * gl / (hl + l2) + gr * gr / (hr + l2)


def estimated_counts(h, n, sum_h):
    """[bins] the rows of each bin as the reference estimates them."""
    return np.round(np.asarray(h, np.float64) * (n / sum_h))


def is_onehot(column, p):
    return column.num_bin <= p.max_cat_to_onehot


def set_gain(g, h, bins, column, p):
    """The gain (less the leaf's own) of sending exactly ``bins`` left, under
    the regularisation of the column's mode, whether or not the search would
    have offered it: what a choice that is not the plain one is priced at."""
    sum_g, sum_h = float(np.sum(g)), float(np.sum(h))
    at = sorted(bins)
    gl, hl = float(np.sum(g[at])), float(np.sum(h[at]))
    l2 = p.lambda_l2 + (0.0 if is_onehot(column, p) else p.cat_l2)
    return (_pair_gain(gl, hl, sum_g - gl, sum_h - hl, l2)
            - sum_g * sum_g / (sum_h + p.lambda_l2))


def categorical_best(g, h, n, column, p):
    """(gain less the leaf's own, the left bins as a sorted tuple) of the best
    split of one leaf on one categorical column, or None.  ``g``, ``h``:
    [bins] float64 sums of the leaf's rows; ``n``: its row count."""
    sum_g, sum_h = float(np.sum(g)), float(np.sum(h))
    shift = sum_g * sum_g / (sum_h + p.lambda_l2)
    cnt = estimated_counts(h, n, sum_h)
    best_gain, best_bins = -np.inf, None
    if is_onehot(column, p):
        for t in range(column.used_bin):
            if cnt[t] < p.min_data_in_leaf \
                    or h[t] < p.min_sum_hessian_in_leaf:
                continue
            if n - cnt[t] < p.min_data_in_leaf \
                    or sum_h - h[t] < p.min_sum_hessian_in_leaf:
                continue
            gain = _pair_gain(g[t], h[t], sum_g - g[t], sum_h - h[t],
                              p.lambda_l2)
            if gain > shift and gain > best_gain:
                best_gain, best_bins = gain, (t,)
        return None if best_bins is None else (best_gain - shift, best_bins)
    order = [b for b in range(column.used_bin) if cnt[b] >= p.cat_smooth]
    order.sort(key=lambda b: g[b] / (h[b] + p.cat_smooth))   # stable
    used = len(order)
    most = min(p.max_cat_threshold, (used + 1) // 2)
    l2 = p.lambda_l2 + p.cat_l2
    for walk in (order, order[::-1]):
        gl = hl = 0.0
        left = group = 0.0
        for i in range(min(used, most)):
            b = walk[i]
            gl, hl = gl + g[b], hl + h[b]
            left, group = left + cnt[b], group + cnt[b]
            if left < p.min_data_in_leaf or hl < p.min_sum_hessian_in_leaf:
                continue
            right = n - left
            if right < p.min_data_in_leaf or right < p.min_data_per_group \
                    or sum_h - hl < p.min_sum_hessian_in_leaf:
                break
            if group < p.min_data_per_group:
                continue
            group = 0.0
            gain = _pair_gain(gl, hl, sum_g - gl, sum_h - hl, l2)
            if gain > shift and gain > best_gain:
                best_gain, best_bins = gain, tuple(sorted(walk[:i + 1]))
    return None if best_bins is None else (best_gain - shift, best_bins)


class Leaf(NamedTuple):
    """What a live leaf's search read: its histograms [3, F, bins] (rows,
    gradients, hessians), the row count the reference would hand it, the
    numerical columns' gain table and each categorical column's best."""
    hist: np.ndarray
    n: float
    numeric: np.ndarray         # [F, bins - 1]; -inf on categorical columns
    categorical: dict           # {column: (gain, bins)}

    def best(self):
        """(gain, column, threshold bin or the left bins' tuple); ties to
        the smaller column."""
        f, t = (int(i) for i in np.unravel_index(np.argmax(self.numeric),
                                                 self.numeric.shape))
        found = (float(self.numeric[f, t]), f, t)
        for column, (gain, bins) in sorted(self.categorical.items()):
            if gain > found[0] or (gain == found[0] and column < found[1]):
                found = (float(gain), column, bins)
        return found


def search(hist, n, columns, p):
    numeric = plain_tree.split_gains(
        hist, min_data_in_leaf=p.min_data_in_leaf,
        min_sum_hessian_in_leaf=p.min_sum_hessian_in_leaf)
    categorical = {}
    for f, column in enumerate(columns):
        if column.categorical:
            numeric[f] = -np.inf
            found = categorical_best(hist[1, f], hist[2, f], n, column, p)
            if found is not None:
                categorical[f] = found
    return Leaf(hist, float(n), numeric, categorical)


def goes_left_lut(choice, num_bins):
    """[num_bins] bool: whether a bin code goes left under ``choice``, a
    threshold bin (code <= it) or the left bins of a categorical split."""
    lut = np.zeros(num_bins, bool)
    if isinstance(choice, (int, np.integer)):
        lut[:int(choice) + 1] = True
    else:
        lut[list(choice)] = True
    return lut


def left_count(leaf, feature, choice):
    """The rows the reference would say go left: the estimated counts of the
    left bins, summed."""
    h = leaf.hist[2, feature]
    cnt = estimated_counts(h, leaf.n, float(h.sum()))
    return float(cnt[goes_left_lut(choice, len(h))].sum())


def gain_of(leaf, feature, choice, columns, p):
    """The plain gain of splitting ``leaf`` on ``feature`` by ``choice``."""
    if columns[feature].categorical:
        if isinstance(choice, (int, np.integer)):
            return -np.inf
        return set_gain(leaf.hist[1, feature], leaf.hist[2, feature], choice,
                        columns[feature], p)
    if not isinstance(choice, (int, np.integer)):
        return -np.inf
    return float(leaf.numeric[feature, choice])


def grow_steps(codes, grad, hess, columns, p, *, num_bins, splits, follow=()):
    """The first ``splits`` splits of the leaf-wise tree on rows ``codes``
    ([n, F] bin codes), one step at a time: ``{"leaf", "feature", "choice",
    "gain", "leaves"}``; ``choice`` is a threshold bin or the sorted tuple of
    the bins that go left, ``leaves`` every live leaf's :class:`Leaf` as it
    stood when the step chose.  ``follow`` [(leaf, feature, choice)]: as in
    ``plain_tree.grow_steps``, step ``k`` records the plain choice and the
    tree then takes ``follow[k]``."""
    grad = np.asarray(grad, np.float64)
    hess = np.asarray(hess, np.float64)
    if codes.size and int(codes.max()) >= num_bins:
        raise ValueError("a bin code of %d or more" % num_bins)
    rows = {0: np.arange(codes.shape[0], dtype=np.int32)}
    leaves = {0: search(plain_tree.histograms(codes, grad, hess, num_bins),
                        codes.shape[0], columns, p)}
    for k in range(splits):
        if k:
            idx, parent = rows[leaf], leaves[leaf]
            goes_left = goes_left_lut(choice, num_bins)[codes[idx, feature]]
            left, right = idx[goes_left], idx[~goes_left]
            small = left if len(left) <= len(right) else right
            h_small = plain_tree.histograms(codes[small], grad[small],
                                            hess[small], num_bins)
            h_large = parent.hist - h_small
            n_left = left_count(parent, feature, choice)
            rows[leaf], rows[k] = left, right
            h_left, h_right = ((h_small, h_large) if small is left
                               else (h_large, h_small))
            leaves[leaf] = search(h_left, n_left, columns, p)
            leaves[k] = search(h_right, parent.n - n_left, columns, p)
        bests = {l: leaves[l].best() for l in leaves}
        leaf = max(bests, key=lambda l: (bests[l][0], -l))
        gain, feature, choice = bests[leaf]
        if not gain > 0:
            return
        yield {"leaf": leaf, "feature": feature, "choice": choice,
               "gain": gain, "leaves": dict(leaves)}
        if k < len(follow):
            leaf, feature, choice = follow[k]
            if leaf not in rows:
                return


# ---- a grown tree, read off its node arrays --------------------------------

CATEGORICAL_BIT = 1       # of a node's decision_type
MISSING_NAN = 2           # its bits 2-3: the column's missing type


def _bits(words):
    return tuple(32 * w + b for w, word in enumerate(words)
                 for b in range(32) if (int(word) >> b) & 1)


def node_choice(tree, node):
    """A node's decision in bin space: its threshold bin, or the sorted tuple
    of the bins that go left (``cat_boundaries_inner`` /
    ``cat_threshold_inner``, indexed by the node's ``threshold_in_bin``)."""
    if not int(tree.decision_type[node]) & CATEGORICAL_BIT:
        return int(tree.threshold_in_bin[node])
    i = int(tree.threshold_in_bin[node])
    lo, hi = tree.cat_boundaries_inner[i], tree.cat_boundaries_inner[i + 1]
    return _bits(tree.cat_threshold_inner[lo:hi])


def tree_splits(tree, count):
    """[(leaf, feature, choice)] of the first ``count`` splits of a grown
    tree (``plain_tree.tree_splits`` with a categorical node's left bins in
    the threshold's place)."""
    return [(leaf, feature, node_choice(tree, node)) for node, (
        leaf, feature, _) in enumerate(plain_tree.tree_splits(tree, count))]


def leaves_of(tree, codes, num_bins, chunk=262144):
    """[n] the leaf each row of bin codes reaches (``plain_tree.leaves_of``
    with a per-node table of the bins that go left, so that both kinds of
    node are one look-up; blocks of rows side by side on the host's cores)."""
    out = np.zeros(codes.shape[0], np.int32)
    nodes = int(tree.num_leaves) - 1
    if nodes < 1:
        return out
    feature = np.asarray(tree.split_feature_inner[:nodes])
    left, right = np.asarray(tree.left_child), np.asarray(tree.right_child)
    member = np.stack([goes_left_lut(node_choice(tree, node), num_bins)
                       for node in range(nodes)])

    def walk_block(start):
        block = codes[start:start + chunk]
        node = np.zeros(block.shape[0], np.int32)
        live = np.arange(block.shape[0])
        while live.size:
            nd = node[live]
            goes_left = member[nd, block[live, feature[nd]]]
            node[live] = np.where(goes_left, left[nd], right[nd])
            live = live[node[live] >= 0]
        out[start:start + chunk] = ~node

    with ThreadPoolExecutor(min(16, os.cpu_count() or 1)) as pool:
        list(pool.map(walk_block, range(0, codes.shape[0], chunk)))
    return out


def scores_of(trees, codes, num_bins):
    """[n] float64: the sum over ``trees`` of the value of the leaf each row
    of bin codes reaches."""
    out = np.zeros(codes.shape[0], np.float64)
    for tree in trees:
        out += np.asarray(tree.leaf_value, np.float64)[
            leaves_of(tree, codes, num_bins)]
    return out


def binary_gradients(y, trees, codes, num_bins):
    """(grad, hess) of binary logloss at the scores of ``trees`` on the
    training table's bin codes; tree 0 carries the starting score."""
    y = np.asarray(y, np.float64)
    if not trees:
        p = np.full(len(y), y.mean())                  # boost_from_average
    else:
        p = 1.0 / (1.0 + np.exp(-scores_of(trees, codes, num_bins)))
    return p - y, p * (1.0 - p)


def walk(trees, X):
    """``plain_reference.walk`` plus the categorical decision: at a node whose
    ``decision_type`` has the categorical bit, a row goes left when its raw
    value, taken as a whole number, is in the node's category set (word
    ``threshold`` of ``cat_boundaries`` into ``cat_threshold``); a negative,
    unseen, infinite or too large value goes right.  NaN goes right at a node
    whose missing type (bits 2-3 of ``decision_type``) is NaN and counts as
    category 0 at any other, as the reference's ``CategoricalDecision``
    has it."""
    X = np.asarray(X, np.float64)
    out = np.zeros(X.shape[0], np.float64)
    rows = np.arange(X.shape[0])
    for t in trees:
        if t.num_leaves <= 1:
            out += t.leaf_value[0]
            continue
        is_cat = (np.asarray(t.decision_type) & CATEGORICAL_BIT).astype(bool)
        bounds = np.asarray(t.cat_boundaries, np.int64)
        words = np.asarray(list(t.cat_threshold) + [0], np.uint64)
        node = np.zeros(X.shape[0], np.int64)
        live = node >= 0
        while live.any():
            nd = node[live]
            value = X[rows[live], t.split_feature[nd]]
            go_left = value <= t.threshold[nd]
            at = is_cat[nd]
            if at.any():
                which = np.asarray(t.threshold)[nd[at]].astype(np.int64)
                v = value[at]
                nan_right = (np.asarray(t.decision_type)[nd[at]] >> 2) & 3 \
                    == MISSING_NAN
                v = np.where(np.isnan(v), np.where(nan_right, -1.0, 0.0), v)
                whole = np.where(np.isfinite(v), v, -1.0).astype(np.int64)
                word = whole >> 5
                inside = (whole >= 0) & (word < bounds[which + 1]
                                         - bounds[which])
                held = words[np.where(inside, bounds[which] + word, -1)]
                go_left[at] = inside & (
                    (held >> (whole & 31).astype(np.uint64)) & 1).astype(bool)
            node[live] = np.where(go_left, t.left_child[nd], t.right_child[nd])
            live = node >= 0
        out += t.leaf_value[~node]
    return out


# ---- the comparison --------------------------------------------------------

# How far the plain gain of a choice may lie from the plain best's when one of
# the two is a set of categories.  ``plain_tree.GAIN_RTOL`` (1e-4) is for two
# thresholds of one sorted column, whose sums share their rounding; two
# prefixes from opposite ends of a sorted order share nothing, and the
# program prices the side it does not sum as the leaf's total less the other,
# the total handed down from the root's own sum of the gradients and the other
# from histograms of their bf16 high and low parts.  On tree 0, where every
# row holds one of two gradient values, the two differ by up to 2^-17 of the
# sum of |gradient| (16.6 on ten million rows), all of it down one chain of
# children: a candidate pair 2.4e-4 apart came out the other way on one seed
# of eight on the chip, while a left bin flipped before the kernel routes by
# it reads 0.87 (PERF.md section 6 has both readings).
SET_GAIN_RTOL = 2e-3

def splits_agree(steps, program_splits, recorded_gains, columns, p):
    """(ok, message, many-vs-many splits): the program's splits [(leaf,
    feature, choice)] against the ``steps`` of ``grow_steps(...,
    follow=program_splits)``, as ``plain_tree.splits_agree`` compares
    thresholds: each must be the plain choice on the tree so far (the same
    column and the same left bins or threshold) or priced by the plain sums
    within ``plain_tree.GAIN_RTOL`` of it (:data:`SET_GAIN_RTOL` where either
    is a set of categories), and the gain the program recorded for it within
    ``plain_tree.RECORDED_GAIN_RTOL`` of its plain gain.  A
    left bin set is priced whether or not the plain search offered it, so a
    set that beats the plain best is no tie either, nor one of more than
    ``max_cat_threshold`` bins.  The third value counts the program's splits
    on a categorical column searched many-vs-many."""
    ties, widest_tie, widest_gap, many = [], 0.0, 0.0, 0
    steps, made = iter(steps), len(program_splits)
    for k, got in enumerate(program_splits):
        step = next(steps, None)
        if step is None:
            return False, "%d plain splits against %d of the program" % (
                k, made), many
        want = (step["leaf"], step["feature"], step["choice"])
        leaf, feature, choice = got
        held = step["leaves"].get(leaf)
        gain = -np.inf if held is None else gain_of(held, feature, choice,
                                                    columns, p)
        if columns[feature].categorical:
            if len(choice) > max(p.max_cat_threshold, 1):
                return False, ("split %d: %d bins go left, max_cat_threshold "
                               "is %d" % (k, len(choice),
                                          p.max_cat_threshold)), many
            many += not is_onehot(columns[feature], p)
        if tuple(got) != want:
            short = abs(step["gain"] - gain) / abs(step["gain"])
            allowed = (SET_GAIN_RTOL if columns[feature].categorical
                       or columns[step["feature"]].categorical
                       else plain_tree.GAIN_RTOL)
            if not (np.isfinite(gain) and short <= allowed):
                return False, (
                    "split %d: program %r (plain gain %.6f), plain %r (gain "
                    "%.6f): %.3g of it apart (allowed %.0e)"
                    % (k, tuple(got), gain, want, step["gain"], short,
                       allowed)), many
            widest_tie = max(widest_tie, short)
            ties.append("split %d %r for the plain %r (plain gains %.6f, "
                        "%.6f)" % (k, tuple(got), want, gain, step["gain"]))
        gap = abs(float(recorded_gains[k]) - gain) / abs(gain)
        if not gap <= plain_tree.RECORDED_GAIN_RTOL:
            return False, (
                "split %d %r: the program recorded gain %.6f, its plain gain "
                "is %.6f: %.3g of it apart (allowed %.0e)"
                % (k, tuple(got), recorded_gains[k], gain, gap,
                   plain_tree.RECORDED_GAIN_RTOL)), many
        widest_gap = max(widest_gap, gap)
    if not made or next(steps, None) is not None:
        return False, ("more than %d plain splits against %d of the program"
                       % (made, made)), many
    return True, (
        "%d splits (%d categorical many-vs-many), each the plain grower's on "
        "the tree so far; %d near ties taken the other way%s (widest %.3g of "
        "the gain, allowed %.0e between thresholds and %.0e where a set of "
        "categories is one of the two); recorded gains within %.3g of the "
        "plain gains (allowed %.0e)"
        % (made, many, len(ties), ": " + "; ".join(ties) if ties else "",
           widest_tie, plain_tree.GAIN_RTOL, SET_GAIN_RTOL, widest_gap,
           plain_tree.RECORDED_GAIN_RTOL)), many


def leaf_values(tree, leaf_of_row, grad, hess, columns, p, learning_rate,
                bias=0.0):
    """[leaves] the value every leaf of a grown tree should carry: ``bias -
    G / (H + l2) * learning_rate`` of the rows that reach it, ``l2`` that of
    the split that made the leaf (``cat_l2`` more under a many-vs-many
    split); ``bias``: the starting score, which tree 0 carries."""
    leaves = int(tree.num_leaves)
    g = np.bincount(leaf_of_row, weights=grad, minlength=leaves)
    h = np.bincount(leaf_of_row, weights=hess, minlength=leaves)
    l2 = np.full(leaves, p.lambda_l2)
    for node in range(leaves - 1):
        column = columns[int(tree.split_feature_inner[node])]
        if column.categorical and not is_onehot(column, p):
            for child in (tree.left_child[node], tree.right_child[node]):
                if child < 0:
                    l2[~int(child)] = p.lambda_l2 + p.cat_l2
    with np.errstate(invalid="ignore", divide="ignore"):
        # a leaf no row reaches (a tree that routed by other sets than it
        # records) has no value to compare: NaN
        return bias - g / (h + l2) * learning_rate
