"""A plain leaf-wise tree grower over a SPARSE table as it arrived: the raw CSR
columns and a list of bin boundaries per feature.  NumPy, float64; it imports
nothing of the program under test, never sees the program's bundled matrix and
forms no groups.

It is the semantics of Exclusive Feature Bundling: however the program packs
mutually exclusive columns into device columns, the tree is the tree of the
unbundled table.  A feature's histogram is a ``bincount`` over the leaf's
non-zeros of it; the bin that holds the value 0 takes what the leaf's totals
leave (the rows where the column is absent).  Gains, ties and leaf numbering
are ``plain_tree``'s (``split_gains``; split ``k`` keeps the left child under
the split leaf's id and gives the right child id ``k + 1``), so
``plain_tree.splits_agree`` compares the steps as it compares that grower's.
"""
from __future__ import annotations

import numpy as np

import plain_tree


def codes_of(bounds, values):
    """Bin codes of ``values`` under one feature's upper ``bounds`` (a value
    belongs to the first bin whose bound is not below it, the last bin at
    most).  No missing values: the benchmark's tables have none."""
    return np.minimum(np.searchsorted(bounds, values, side="left"),
                      len(bounds) - 1)


class Table:
    """The non-zeros of the used columns, by column: ``row`` (ascending
    within a column), ``slot`` = feature * num_bins + bin code, where a
    feature's non-zeros lie (``start``), and the code of the value 0 in
    every feature (``zero_code``)."""

    def __init__(self, indptr, indices, values, columns, bounds, num_bins):
        """``columns[j]`` is the CSR column of feature ``j`` and ``bounds[j]``
        its bins' upper bounds; other columns are dropped."""
        n = len(indptr) - 1
        columns = np.asarray(columns)
        # 16-bit keys sort by radix; the order within a column stays the rows'
        narrow = int(np.max(indices, initial=0)) < 1 << 16
        order = np.argsort(np.asarray(indices).astype(
            np.uint16 if narrow else np.int64), kind="stable")
        col_sorted = np.asarray(indices)[order]
        row_of = np.repeat(np.arange(n, dtype=np.int32), np.diff(indptr))
        begin = np.searchsorted(col_sorted, columns, side="left")
        end = np.searchsorted(col_sorted, columns, side="right")
        self.rows, self.features, self.num_bins = n, len(columns), num_bins
        self.start = np.concatenate([[0], np.cumsum(end - begin)])
        self.row = np.empty(self.start[-1], np.int32)
        self.slot = np.empty(self.start[-1], np.int32)
        self.zero_code = np.empty(len(columns), np.int64)
        for j, (b, e) in enumerate(zip(begin, end)):
            at = order[b:e]
            mine = slice(self.start[j], self.start[j + 1])
            self.row[mine] = row_of[at]
            self.slot[mine] = j * num_bins + codes_of(
                bounds[j], np.asarray(values)[at].astype(np.float64))
            self.zero_code[j] = codes_of(bounds[j], np.zeros(1))[0]

    def histograms(self, in_leaf, grad, hess):
        """[3, F, num_bins] float64 (rows, sum of grad, sum of hess) of the
        rows where ``in_leaf`` holds."""
        size = self.features * self.num_bins
        mine = in_leaf[self.row]
        at, rows = self.slot[mine], self.row[mine]
        out = np.stack([
            np.bincount(at, minlength=size).astype(np.float64),
            np.bincount(at, weights=grad[rows], minlength=size),
            np.bincount(at, weights=hess[rows], minlength=size),
        ]).reshape(3, self.features, self.num_bins)
        totals = np.array([in_leaf.sum(), grad[in_leaf].sum(),
                           hess[in_leaf].sum()], np.float64)
        # the rows of the leaf in which the column is absent hold a 0
        out[:, np.arange(self.features), self.zero_code] += (
            totals[:, None] - out.sum(axis=2))
        return out

    def codes_of_feature(self, feature):
        """[rows] the bin code of every row in one feature."""
        out = np.full(self.rows, self.zero_code[feature], np.int32)
        mine = slice(self.start[feature], self.start[feature + 1])
        out[self.row[mine]] = self.slot[mine] - feature * self.num_bins
        return out


def grow_steps(table, grad, hess, *, splits, min_data_in_leaf,
               min_sum_hessian_in_leaf, follow=()):
    """``plain_tree.grow_steps`` on a :class:`Table`: the first ``splits``
    splits, one step at a time, ``follow`` as there.  A split histograms its
    smaller child and takes the larger one's from the parent by subtraction,
    and is made when the next step is asked for."""
    grad = np.asarray(grad, np.float64)
    hess = np.asarray(hess, np.float64)
    limits = dict(min_data_in_leaf=min_data_in_leaf,
                  min_sum_hessian_in_leaf=min_sum_hessian_in_leaf)
    leaf_of = np.zeros(table.rows, np.int32)
    hist = {0: table.histograms(leaf_of == 0, grad, hess)}
    gains = {0: plain_tree.split_gains(hist[0], **limits)}
    for k in range(splits):
        if k:
            goes_right = (leaf_of == leaf) & (
                table.codes_of_feature(feature) > t)
            n_right = int(goes_right.sum())
            n_left = int((leaf_of == leaf).sum()) - n_right
            leaf_of[goes_right] = k
            small = leaf if n_left <= n_right else k
            h_small = table.histograms(leaf_of == small, grad, hess)
            h_large = hist[leaf] - h_small
            hist[leaf], hist[k] = ((h_small, h_large) if small == leaf
                                   else (h_large, h_small))
            for child in (leaf, k):
                gains[child] = plain_tree.split_gains(hist[child], **limits)
        leaf = max(gains, key=lambda l: (gains[l].max(), -l))
        best = gains[leaf]
        feature, t = (int(i) for i in np.unravel_index(np.argmax(best),
                                                       best.shape))
        if not best[feature, t] > 0:
            return
        yield {"leaf": leaf, "feature": feature, "bin": t,
               "gain": float(best[feature, t]), "gains": dict(gains)}
        if k < len(follow):
            leaf, feature, t = follow[k]
            if leaf not in hist:
                return
