"""Plain references, independent of the program under test (NumPy, float64).

``root_split``: the best first split of a binary-logloss GBDT by LightGBM's
gain, from per-feature ``np.bincount`` histograms over bin codes.
``walk``: raw scores of a list of trees by walking their node arrays.
"""
from __future__ import annotations

import numpy as np


def root_gains(codes, y, *, num_bins, min_data_in_leaf, min_sum_hessian_in_leaf):
    """[F, num_bins - 1] gain of splitting the root at "code <= t" for every
    feature and threshold bin t; -inf where a child breaks a constraint.

    Tree 0 of binary logloss starts every row at the label mean p0, so
    grad = p0 - y and hess = p0 * (1 - p0); gain = GL^2/HL + GR^2/HR (no
    regularisation; the parent's term is the same for every candidate)."""
    y = np.asarray(y, np.float64)
    p0 = y.mean()
    grad = p0 - y
    hess_row = p0 * (1.0 - p0)
    n, f = codes.shape
    gains = np.full((f, num_bins - 1), -np.inf)
    for j in range(f):
        col = codes[:, j]
        cnt = np.bincount(col, minlength=num_bins)[:num_bins].astype(np.float64)
        g = np.bincount(col, weights=grad, minlength=num_bins)[:num_bins]
        cl = np.cumsum(cnt)[:-1]
        gl = np.cumsum(g)[:-1]
        cr, gr = n - cl, g.sum() - gl
        hl, hr = cl * hess_row, cr * hess_row
        ok = ((cl >= max(min_data_in_leaf, 1)) & (cr >= max(min_data_in_leaf, 1))
              & (hl >= min_sum_hessian_in_leaf) & (hr >= min_sum_hessian_in_leaf))
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = gl * gl / hl + gr * gr / hr
        gains[j, ok] = gain[ok]
    return gains


# f32 accumulation on the chip against f64 here can swap near-ties between two
# candidate splits, so the program's split passes when it IS the plain best or
# its plain gain is within this relative distance of the plain best
ROOT_GAIN_RTOL = 1e-4


def root_split_agrees(gains, feature, threshold_bin):
    """(ok, message) for the program's root split against ``root_gains``."""
    best_f, best_t = np.unravel_index(np.argmax(gains), gains.shape)
    best = gains[best_f, best_t]
    got = gains[feature, threshold_bin]
    ok = (feature, threshold_bin) == (best_f, best_t) or (
        np.isfinite(got) and (best - got) <= ROOT_GAIN_RTOL * abs(best))
    return bool(ok), ("program root split (%d, %d) gain %.6f; plain best "
                      "(%d, %d) gain %.6f" % (feature, threshold_bin, got,
                                              best_f, best_t, best))


def walk(trees, X):
    """Sum over ``trees`` of the value of the leaf each row of ``X`` reaches.

    A tree is its node arrays: ``split_feature``, ``threshold`` (a row goes
    left when value <= threshold), ``left_child`` / ``right_child`` (a negative
    child ``c`` is leaf ``~c``) and ``leaf_value``.  Numerical splits without
    missing values only, which is all the benchmark's data can produce."""
    X = np.asarray(X, np.float64)
    out = np.zeros(X.shape[0], np.float64)
    rows = np.arange(X.shape[0])
    for t in trees:
        if t.num_leaves <= 1:
            out += t.leaf_value[0]
            continue
        node = np.zeros(X.shape[0], np.int64)
        live = node >= 0
        while live.any():
            nd = node[live]
            go_left = X[rows[live], t.split_feature[nd]] <= t.threshold[nd]
            node[live] = np.where(go_left, t.left_child[nd], t.right_child[nd])
            live = node >= 0
        out += t.leaf_value[~node]
    return out
