"""The benchmark's sparse one-hot table: rows, labels and CSR arrays from a seed.

A row is one flight-like record: a level of each categorical block (month, day
of month, day of week, carrier, origin, destination), one-hot coded into the
block's columns, and two positive numeric columns after them.  Every row has
exactly one active level a block, so a row has ``len(blocks) + len(numeric)``
non-zeros.  Level ``k`` (from 1) of a block is drawn with probability
proportional to ``(k + q) ** -s``: ``s`` near 0 is a calendar, a larger ``s``
a carrier or an airport, where a few levels hold most rows and many hold a few
hundred of ten million.

The label is Bernoulli(sigmoid(scale * (g - g_mean) / g_std)), ``g`` the sum of
one effect per active level, two numeric terms and one carrier-by-origin
product.  All of that is FIXED in the configuration file (``generator``); the
seed decides the rows and the label noise only, so every seed is the same task
with the same Bayes AUC.

Rows are made in blocks of ``BLOCK_ROWS``; block ``i`` draws from child ``i`` of
``np.random.SeedSequence(seed)``, so the table does not depend on how many
threads fill the blocks.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 1 << 18


def num_features(gen):
    return sum(b["levels"] for b in gen["blocks"]) + len(gen["numeric"])


def block_starts(gen):
    """First column of every categorical block; the numeric columns follow."""
    return np.cumsum([0] + [b["levels"] for b in gen["blocks"]])


def level_probabilities(block):
    k = np.arange(1, block["levels"] + 1, dtype=np.float64)
    p = (k + block["zipf_q"]) ** -block["zipf_s"]
    return p / p.sum()


def numeric_terms(numeric, gen):
    """[n] the label function's numeric part on ``numeric`` ([n, 2] values)."""
    g = np.zeros(numeric.shape[0], np.float64)
    for j, spec in enumerate(gen["numeric"]):
        x = numeric[:, j].astype(np.float64)
        if spec["transform"] == "log":
            x = np.log(x)
        g += spec["coefficient"] * (x - spec["center"]) / spec["spread"]
    return g


def raw_g(levels, numeric, gen):
    """The unstandardised label function: ``levels`` [n, blocks] are the
    active level of each block (from 0), ``numeric`` [n, 2] the values."""
    g = numeric_terms(numeric, gen)
    for b, block in enumerate(gen["blocks"]):
        g += np.asarray(block["effects"], np.float64)[levels[:, b]]
    pair = gen["interaction"]
    a, b = pair["blocks"]
    g += pair["weight"] * (np.asarray(pair["u"], np.float64)[levels[:, a]]
                           * np.asarray(pair["v"], np.float64)[levels[:, b]])
    return g


def true_probability(levels, numeric, gen):
    """P(y = 1 | row): what a perfect model would predict."""
    z = gen["scale"] * (raw_g(levels, numeric, gen) - gen["g_mean"]) \
        / gen["g_std"]
    return 1.0 / (1.0 + np.exp(-z))


def _fill_block(i, child, levels, numeric, y, gen, cdfs):
    lo = i * BLOCK_ROWS
    hi = min(lo + BLOCK_ROWS, levels.shape[0])
    rng = np.random.default_rng(child)
    for b, cdf in enumerate(cdfs):
        u = rng.random(hi - lo, dtype=np.float64)
        levels[lo:hi, b] = np.minimum(np.searchsorted(cdf, u, side="right"),
                                      len(cdf) - 1)
    for j, spec in enumerate(gen["numeric"]):
        if spec["draw"] == "uniform":
            # (0, high]: a numeric column never holds a zero, which CSR drops
            numeric[lo:hi, j] = spec["high"] * (1.0 - rng.random(hi - lo))
        else:
            numeric[lo:hi, j] = np.exp(
                spec["mu"] + spec["sigma"] * rng.standard_normal(hi - lo))
    u = rng.random(hi - lo, dtype=np.float64)
    y[lo:hi] = u < true_probability(levels[lo:hi], numeric[lo:hi], gen)


def draw(seed, rows, gen, threads=None):
    """(levels [rows, blocks] int16, numeric [rows, 2] float32, y [rows]
    float32 of 0/1) from ``seed``."""
    levels = np.empty((rows, len(gen["blocks"])), np.int16)
    numeric = np.empty((rows, len(gen["numeric"])), np.float32)
    y = np.empty(rows, np.float32)
    cdfs = [np.cumsum(level_probabilities(b)) for b in gen["blocks"]]
    children = np.random.SeedSequence(int(seed)).spawn(-(-rows // BLOCK_ROWS))
    threads = threads or min(8, os.cpu_count() or 1)
    with ThreadPoolExecutor(max_workers=threads) as pool:
        # list() reads every future's result, so a worker's exception raises
        list(pool.map(lambda ic: _fill_block(ic[0], ic[1], levels, numeric, y,
                                             gen, cdfs), enumerate(children)))
    return levels, numeric, y


def to_csr(levels, numeric, gen, threads=None):
    """(indptr int64, indices int32, values float32, columns) of the one-hot
    table: a row's non-zeros are its active level's column in each block, in
    the blocks' order, then the numeric columns."""
    n, nb = levels.shape
    starts = block_starts(gen)
    per_row = nb + numeric.shape[1]
    indices = np.empty((n, per_row), np.int32)
    values = np.empty((n, per_row), np.float32)
    first = starts[:-1].astype(np.int32)[None, :]
    last = starts[-1] + np.arange(numeric.shape[1], dtype=np.int32)

    def fill(lo):
        at = slice(lo, lo + BLOCK_ROWS)
        indices[at, :nb] = levels[at] + first
        indices[at, nb:] = last
        values[at, :nb] = 1.0
        values[at, nb:] = numeric[at]
    with ThreadPoolExecutor(threads or min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, range(0, n, BLOCK_ROWS)))
    indptr = np.arange(n + 1, dtype=np.int64) * per_row
    return indptr, indices.reshape(-1), values.reshape(-1), num_features(gen)


def to_dense(levels, numeric, gen):
    """[n, features] float32: the same rows with their zeros written out (the
    held-out rows only: the training table is never dense)."""
    n, nb = levels.shape
    starts = block_starts(gen)
    X = np.zeros((n, num_features(gen)), np.float32)
    rows = np.arange(n)
    for b in range(nb):
        X[rows, starts[b] + levels[:, b]] = 1.0
    X[:, starts[-1]:] = numeric
    return X
