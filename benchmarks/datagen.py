"""The benchmark's own data: Higgs-shaped rows and labels from a seed.

Features are standard normal float32.  The label is Bernoulli(sigmoid(s * g(x)))
where ``g`` is a FIXED function kept in the configuration file (``generator``):
linear terms, pairwise products and squares over 12 of the features,
standardised to zero mean and unit variance.  The seed decides the rows and the
label noise only, so every seed is the same task and has the same Bayes AUC.

Rows are made in blocks of ``BLOCK_ROWS``; block ``i`` draws from child ``i`` of
``np.random.SeedSequence(seed)``, so the rows do not depend on how many threads
fill the blocks (numpy's generators release the GIL while they draw).
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 1 << 18


def raw_g(X, gen):
    """The unstandardised label function on rows ``X`` ([n, F] float)."""
    g = np.zeros(X.shape[0], np.float64)
    for j, a in gen["linear"]:
        g += a * X[:, j]
    for j, k, c in gen["products"]:
        g += c * (X[:, j].astype(np.float64) * X[:, k])
    for j, b in gen["squares"]:
        g += b * np.square(X[:, j], dtype=np.float64)
    return g


def true_probability(X, gen):
    """P(y = 1 | x): what a perfect model would predict."""
    z = gen["scale"] * (raw_g(X, gen) - gen["g_mean"]) / gen["g_std"]
    return 1.0 / (1.0 + np.exp(-z))


def _fill_block(i, child, X, y, gen):
    lo = i * BLOCK_ROWS
    hi = min(lo + BLOCK_ROWS, X.shape[0])
    rng = np.random.default_rng(child)
    rng.standard_normal(out=X[lo:hi], dtype=np.float32)
    u = rng.random(hi - lo, dtype=np.float64)
    y[lo:hi] = u < true_probability(X[lo:hi], gen)


def make(seed, rows, features, gen, threads=None):
    """``rows`` x ``features`` float32 rows and their float32 0/1 labels."""
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)
    blocks = -(-rows // BLOCK_ROWS)
    children = np.random.SeedSequence(int(seed)).spawn(blocks)
    threads = threads or min(8, os.cpu_count() or 1)
    with ThreadPoolExecutor(max_workers=threads) as pool:
        # list() reads every future's result, so a worker's exception raises
        list(pool.map(lambda ic: _fill_block(ic[0], ic[1], X, y, gen),
                      enumerate(children)))
    return X, y


def auc(y, score):
    """Area under the ROC curve by ranks (ties get their mean rank)."""
    y = np.asarray(y) > 0.5
    score = np.asarray(score, np.float64)
    order = np.argsort(score, kind="stable")
    s = score[order]
    ranks = np.empty(len(s), np.float64)
    # mean rank within each run of equal scores
    starts = np.flatnonzero(np.r_[True, s[1:] != s[:-1]])
    ends = np.r_[starts[1:], len(s)]
    mean_rank = (starts + ends + 1) / 2.0
    ranks[order] = np.repeat(mean_rank, ends - starts)
    n_pos = int(y.sum())
    n_neg = len(y) - n_pos
    return float((ranks[y].sum() - n_pos * (n_pos + 1) / 2.0)
                 / (n_pos * n_neg))
