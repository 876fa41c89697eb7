"""``chip_smoke.py`` trains the benchmark's task, and its AUC floor fits it.

The two programs that run on the chip (``benchmarks/run.py`` and
``chip_smoke.py``) draw their rows from one generator, so what one proves
holds for the table the other measures.  CPU-sized: a few thousand rows.
"""
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, HELD_OUT = 8192, 4096


@pytest.fixture(scope="module")
def chip_smoke():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.pop(0)
    return chip_smoke


@pytest.fixture(scope="module")
def generator():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "higgs-10m5.json")) as fh:
        return json.load(fh)["generator"]


@pytest.fixture(scope="module")
def table(chip_smoke):
    return chip_smoke.benchmark_table(ROWS, HELD_OUT, chip_smoke.SEED + 1)


def test_table_is_the_benchmarks_byte_for_byte(chip_smoke, generator, table):
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    try:
        import datagen
    finally:
        sys.path.pop(0)
    X, y, Xh, yh = table
    want_X, want_y = datagen.make(chip_smoke.SEED + 1, ROWS + HELD_OUT,
                                  chip_smoke.F, generator)
    assert X.dtype == want_X.dtype and y.dtype == want_y.dtype
    assert np.concatenate([X, Xh]).tobytes() == want_X.tobytes()
    assert np.concatenate([y, yh]).tobytes() == want_y.tobytes()
    # another seed is another table of the same task
    other = chip_smoke.benchmark_table(ROWS, HELD_OUT, chip_smoke.SEED + 2)
    assert other[0].tobytes() != X.tobytes()


def test_label_rate_is_balanced(table):
    _, y, _, yh = table
    assert set(np.unique(y)) == {0.0, 1.0}
    # the generator standardises g, so P(y = 1) is a half: 4 sigma of 8192
    assert abs(float(y.mean()) - 0.5) < 0.022
    assert abs(float(yh.mean()) - 0.5) < 0.032


def test_auc_floor_sits_under_a_cpu_model_and_the_bayes_auc(
        chip_smoke, generator, table):
    import lightgbm_tpu as lgb
    X, y, Xh, yh = table
    bst = lgb.train(dict(objective="binary", num_leaves=15,
                         learning_rate=0.1, max_bin=chip_smoke.MAX_BIN,
                         verbosity=-1),
                    lgb.Dataset(X, label=y), num_boost_round=16)
    a = chip_smoke.auc(yh, bst.predict(Xh))
    assert 0.5 < chip_smoke.AUC_FLOOR < a < generator["bayes_auc"], a
