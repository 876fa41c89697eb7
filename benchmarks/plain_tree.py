"""A plain leaf-wise tree grower over the WHOLE binned table (NumPy, float64),
independent of the program under test: it imports nothing of it.

It is the semantics of a data-parallel deployment: however the rows are spread
over workers, the model is the whole table's model, not a shard's.  Histograms
by ``np.bincount`` per feature; the gain of ``plain_reference.root_gains``
(GL^2/HL + GR^2/HR, no regularisation) less the leaf's own G^2/H, so that
leaves compare; the leaf with the best gain splits first, ties to the smaller
leaf id and, within a leaf, to the smaller (feature, bin).  Leaves are
numbered as LightGBM numbers them: split ``k`` (from 0) keeps the left child
under the split leaf's id and gives the right child id ``k + 1``.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def histograms(codes, grad, hess, num_bins, chunk=4096):
    """[3, F, num_bins] float64: rows, sum of grad, sum of hess per feature
    and bin code.  The table is read as it lies, a block of rows at a time:
    one ``bincount`` over ``feature * num_bins + code`` fills every feature's
    bins from a block that stays in the cache (every code < ``num_bins``)."""
    n, f = codes.shape
    out = np.zeros((3, f * num_bins))
    same_hess = n > 0 and np.all(hess == hess[0])
    offset = (np.arange(f, dtype=np.int32) * num_bins)[None, :]
    for start in range(0, n, chunk):
        block = slice(start, start + chunk)
        at = (codes[block] + offset).ravel()
        out[0] += np.bincount(at, minlength=f * num_bins)
        out[1] += np.bincount(at, weights=np.repeat(grad[block], f),
                              minlength=f * num_bins)
        if not same_hess:
            out[2] += np.bincount(at, weights=np.repeat(hess[block], f),
                                  minlength=f * num_bins)
    if same_hess:
        out[2] = out[0] * float(hess[0])
    return out.reshape(3, f, num_bins)


def split_gains(hist, *, min_data_in_leaf, min_sum_hessian_in_leaf):
    """[F, num_bins - 1] gain of splitting the leaf whose histograms are
    ``hist`` at "code <= t"; -inf where a child breaks a constraint."""
    cnt, g, h = hist
    n, sg, sh = cnt[0].sum(), g[0].sum(), h[0].sum()   # every row is in one bin
    cl, gl, hl = (np.cumsum(a, axis=1)[:, :-1] for a in (cnt, g, h))
    cr, gr, hr = n - cl, sg - gl, sh - hl
    least = max(min_data_in_leaf, 1)
    ok = ((cl >= least) & (cr >= least) & (hl >= min_sum_hessian_in_leaf)
          & (hr >= min_sum_hessian_in_leaf))
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = gl * gl / hl + gr * gr / hr - sg * sg / sh
    return np.where(ok, gain, -np.inf)


def grow_steps(codes, grad, hess, *, num_bins, splits, min_data_in_leaf,
               min_sum_hessian_in_leaf, follow=()):
    """The first ``splits`` splits of the leaf-wise tree on rows ``codes``
    ([n, F] bin codes) with per-row ``grad`` and ``hess``, one step at a time:
    ``{"leaf", "feature", "bin", "gain", "gains"}``, where ``gains`` holds
    every live leaf's [F, num_bins - 1] gain table as it stood when the step
    chose (so a caller can price another choice).  Ends early when no leaf has
    a split of positive gain left.  A split histograms its smaller child and
    takes the larger one's from the parent by subtraction; it is made when the
    NEXT step is asked for, so a caller that stops asking pays for no more.

    ``follow``, another grower's splits [(leaf, feature, bin)]: step ``k``
    still records the plain choice, but the tree then takes ``follow[k]``, so
    that every later step prices that grower's choice on its own tree so far
    (two growers that take a tie differently are still compared to the end)."""
    grad = np.asarray(grad, np.float64)
    hess = np.asarray(hess, np.float64)
    if codes.size and int(codes.max()) >= num_bins:
        raise ValueError("a bin code of %d or more" % num_bins)
    limits = dict(min_data_in_leaf=min_data_in_leaf,
                  min_sum_hessian_in_leaf=min_sum_hessian_in_leaf)
    rows = {0: np.arange(codes.shape[0], dtype=np.int32)}
    hist = {0: histograms(codes, grad, hess, num_bins)}
    gains = {0: split_gains(hist[0], **limits)}
    for k in range(splits):
        if k:
            idx = rows[leaf]
            goes_left = codes[idx, feature] <= t
            left, right = idx[goes_left], idx[~goes_left]
            small = left if len(left) <= len(right) else right
            h_small = histograms(codes[small], grad[small], hess[small],
                                 num_bins)
            h_large = hist[leaf] - h_small
            rows[leaf], rows[k] = left, right
            hist[leaf], hist[k] = ((h_small, h_large) if small is left
                                   else (h_large, h_small))
            for child in (leaf, k):
                gains[child] = split_gains(hist[child], **limits)
        leaf = max(gains, key=lambda l: (gains[l].max(), -l))
        table = gains[leaf]
        feature, t = (int(i) for i in np.unravel_index(np.argmax(table),
                                                       table.shape))
        if not table[feature, t] > 0:
            return
        yield {"leaf": leaf, "feature": feature, "bin": t,
               "gain": float(table[feature, t]), "gains": dict(gains)}
        if k < len(follow):
            leaf, feature, t = follow[k]
            if leaf not in rows:
                return


def grow(codes, grad, hess, **how):
    """Every step of :func:`grow_steps`, as a list."""
    return list(grow_steps(codes, grad, hess, **how))


def leaves_of(tree, codes, chunk=262144):
    """[n] the leaf each row of ``codes`` reaches in a grown tree, given as
    its node arrays in bin space (``num_leaves``, ``split_feature_inner``,
    ``threshold_in_bin``, ``left_child`` / ``right_child``: a row goes left
    when its code <= the threshold; a negative child ``c`` is leaf ``~c``).
    Numerical splits without missing values only.  A block of rows at a time,
    every row of it one level down a pass; the blocks side by side on the
    host's cores (indexing and comparing release the interpreter's lock)."""
    out = np.zeros(codes.shape[0], np.int32)
    if int(tree.num_leaves) <= 1:
        return out
    feature = np.asarray(tree.split_feature_inner)
    threshold = np.asarray(tree.threshold_in_bin)
    left, right = np.asarray(tree.left_child), np.asarray(tree.right_child)

    def walk(start):
        block = codes[start:start + chunk]
        node = np.zeros(block.shape[0], np.int32)
        live = np.arange(block.shape[0])
        while live.size:
            nd = node[live]
            goes_left = block[live, feature[nd]] <= threshold[nd]
            node[live] = np.where(goes_left, left[nd], right[nd])
            live = live[node[live] >= 0]
        out[start:start + chunk] = ~node

    with ThreadPoolExecutor(min(16, os.cpu_count() or 1)) as pool:
        list(pool.map(walk, range(0, codes.shape[0], chunk)))
    return out


def scores_of(trees, codes):
    """[n] float64: the sum over ``trees`` of the value of the leaf each row
    reaches (``leaf_value`` beside the arrays :func:`leaves_of` reads)."""
    out = np.zeros(codes.shape[0], np.float64)
    for tree in trees:
        out += np.asarray(tree.leaf_value, np.float64)[leaves_of(tree, codes)]
    return out


def tree_splits(tree, count):
    """[(leaf, feature, bin)] of the first ``count`` splits of a grown tree,
    given as its node arrays (``num_leaves``, ``left_child``,
    ``split_feature_inner``, ``threshold_in_bin``): node ``k`` is split ``k``,
    and the leaf it split is the one its left-most descendant still carries
    (the left child keeps the id)."""
    out = []
    for node in range(min(count, int(tree.num_leaves) - 1)):
        child = int(tree.left_child[node])
        while child >= 0:
            child = int(tree.left_child[child])
        out.append((~child, int(tree.split_feature_inner[node]),
                    int(tree.threshold_in_bin[node])))
    return out


# f32 accumulation on the chip against f64 here can swap near-ties between two
# candidate splits (plain_reference.ROOT_GAIN_RTOL, the root check's reason)
GAIN_RTOL = 1e-4
# ... and the gain the program RECORDED for a split (from its own sums: bf16
# high and low parts of each gradient, summed in f32) against the plain gain of
# that split.  The chip reads 1.75e-4 on tree 0, whose two gradient values
# round the same way on every row, and 3.6e-6 on tree 4; sums that miss a
# worker's rows, or gradients from a score that was not updated everywhere,
# move it by tenths (PERF.md section 6 has both readings)
RECORDED_GAIN_RTOL = 1e-2


def splits_agree(steps, program_splits, recorded_gains=None):
    """(ok, message): the program's splits [(leaf, feature, bin)], in the
    order it made them, against the ``steps`` of ``grow_steps(...,
    follow=program_splits)`` (a list or the generator: the comparison stops
    asking at the first split that fails).  Each must be the plain choice on
    the tree so far, or a near tie of it: its plain gain within ``GAIN_RTOL``
    of the step's best (an empty bin makes two thresholds the same split: a
    tie to the last digit).  With ``recorded_gains``, the gain the program
    recorded for each split must also be the plain gain of that split within
    ``RECORDED_GAIN_RTOL``.  The message gives both readings: the widest near
    tie taken and the widest gap of a recorded gain."""
    ties, widest_tie, widest_gap = [], 0.0, 0.0
    steps, made = iter(steps), len(program_splits)
    for k, got in enumerate(program_splits):
        step = next(steps, None)
        if step is None:
            return False, "%d plain splits against %d of the program" % (k, made)
        want = (step["leaf"], step["feature"], step["bin"])
        leaf, feature, t = got
        table = step["gains"].get(leaf)
        gain = -np.inf if table is None else table[feature, t]
        if tuple(got) != want:
            short = (step["gain"] - gain) / abs(step["gain"])
            if not (np.isfinite(gain) and short <= GAIN_RTOL):
                return False, ("split %d: program %r (plain gain %.6f), "
                               "plain %r (gain %.6f): short by %.3g of it "
                               "(allowed %.0e)" % (k, tuple(got), gain, want,
                                                   step["gain"], short,
                                                   GAIN_RTOL))
            widest_tie = max(widest_tie, short)
            ties.append("split %d %r for the plain %r (plain gains %.6f, "
                        "%.6f)" % (k, tuple(got), want, gain, step["gain"]))
        if recorded_gains is not None:
            gap = abs(float(recorded_gains[k]) - gain) / abs(gain)
            if not gap <= RECORDED_GAIN_RTOL:
                return False, ("split %d %r: the program recorded gain %.6f, "
                               "its plain gain is %.6f: %.3g of it apart "
                               "(allowed %.0e)" % (k, tuple(got),
                                                   recorded_gains[k], gain,
                                                   gap, RECORDED_GAIN_RTOL))
            widest_gap = max(widest_gap, gap)
    if not made or next(steps, None) is not None:
        return False, ("more than %d plain splits against %d of the program"
                       % (made, made))
    said = ("%d splits, each the plain grower's on the tree so far; %d near "
            "ties taken the other way%s (widest %.3g of the gain, allowed "
            "%.0e)" % (made, len(ties),
                       ": " + "; ".join(ties) if ties else "", widest_tie,
                       GAIN_RTOL))
    if recorded_gains is not None:
        said += ("; recorded gains within %.3g of the plain gains (allowed "
                 "%.0e)" % (widest_gap, RECORDED_GAIN_RTOL))
    return True, said
