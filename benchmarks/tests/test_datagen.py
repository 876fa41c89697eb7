import json
import os

import numpy as np
import pytest

import datagen
from conftest import BENCH

CFG = json.load(open(os.path.join(BENCH, "configs", "higgs-10m5.json")))


def test_same_seed_same_rows_whatever_the_thread_count():
    rows = 2 * datagen.BLOCK_ROWS + 777
    X1, y1 = datagen.make(2147483659, rows, 28, CFG["generator"], threads=1)
    X8, y8 = datagen.make(2147483659, rows, 28, CFG["generator"], threads=8)
    assert np.array_equal(X1, X8) and np.array_equal(y1, y8)
    X2, _ = datagen.make(2147483660, rows, 28, CFG["generator"], threads=8)
    assert not np.array_equal(X1, X2)
    # a shorter table is a prefix of a longer one only block by block; what
    # matters is that nothing but the seed and the size decides the rows
    assert X1.dtype == np.float32 and set(np.unique(y1)) == {0.0, 1.0}


def test_auc_by_ranks():
    assert datagen.auc([0, 0, 1, 1], [0.1, 0.2, 0.3, 0.4]) == 1.0
    assert datagen.auc([0, 1, 0, 1], [0.5, 0.5, 0.5, 0.5]) == 0.5
    assert datagen.auc([1, 0, 0, 1], [0.1, 0.2, 0.3, 0.4]) == 0.5


def test_bayes_auc_is_what_the_config_states():
    gen = CFG["generator"]
    X, y = datagen.make(777, 1_000_000, 28, gen)
    bayes = datagen.auc(y, datagen.true_probability(X, gen))
    assert bayes == pytest.approx(gen["bayes_auc"], abs=0.002)
    assert abs(bayes - 0.86) < 0.005
    g = (datagen.raw_g(X, gen) - gen["g_mean"]) / gen["g_std"]
    assert abs(g.mean()) < 0.01 and abs(g.std() - 1.0) < 0.01
    used = ({j for j, _ in gen["linear"]} | {j for j, _ in gen["squares"]}
            | {j for a, b, _ in gen["products"] for j in (a, b)})
    assert len(used) == 12
