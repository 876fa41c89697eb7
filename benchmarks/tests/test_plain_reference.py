"""The plain NumPy references against the program's XLA learner on the CPU."""
import json
import os

import numpy as np

import datagen
import gbdt_job
import plain_reference
from conftest import BENCH

CFG = json.load(open(os.path.join(BENCH, "configs", "higgs-10m5.json")))


def test_root_split_and_walk_agree_with_the_xla_learner():
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.objective import create_objective
    X, y = datagen.make(3, 20000, 28, CFG["generator"])
    params = dict(CFG["params"], num_leaves=15)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=params["max_bin"])
    cfg = Config(verbosity=-1, **params)
    gbdt = GBDT(cfg, ds, create_objective("binary", cfg))
    for _ in range(3):
        gbdt.train_one_iter()
    ok, found = gbdt_job.check_root_split(gbdt, ds, y, params)
    assert ok, found
    ok, found = gbdt_job.check_walk(gbdt, X, 3)
    assert ok, found
    # a split that is not the best one is told apart
    gains = plain_reference.root_gains(
        ds.binned, y, num_bins=256, min_data_in_leaf=0,
        min_sum_hessian_in_leaf=100.0)
    f, t = np.unravel_index(np.argmax(gains), gains.shape)
    assert not plain_reference.root_split_agrees(gains, (f + 1) % 28, t)[0]


def test_constraints_rule_candidates_out():
    codes = np.array([[0], [0], [1], [2], [2], [2]], np.uint8)
    y = np.array([0, 0, 1, 1, 1, 0], np.float64)
    g = plain_reference.root_gains(codes, y, num_bins=4, min_data_in_leaf=3,
                                   min_sum_hessian_in_leaf=0.0)
    assert np.isfinite(g[0, 1]) and not np.isfinite(g[0, 0])
    assert not np.isfinite(g[0, 2])           # nothing to the right of bin 2
