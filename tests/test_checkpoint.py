"""Fault-tolerant training runtime: atomic checkpoints, bit-exact resume,
corruption fallback, non-finite guards.

The contract under test (ISSUE 5 acceptance): for GBDT, DART and GOSS with
bagging + valid sets + early stopping, ``train(N)`` and
``train(k) -> kill -> resume -> N`` produce byte-identical model strings;
a corrupt/truncated newest checkpoint falls back to the last good one; a
kill during an atomic write never leaves a truncated destination file; and
``nan_policy`` turns a poisoned gradient batch into an error / a skipped
iteration / a clipped batch instead of NaN trees.
"""
import glob
import os
import sys

import numpy as np
import pytest

from lightgbm_tpu.boosting import create_boosting
from lightgbm_tpu.checkpoint import (CheckpointError, list_checkpoints,
                                     load_checkpoint, load_latest_checkpoint,
                                     serialize_state)
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.metric.metric import create_metrics
from lightgbm_tpu.objective import create_objective
from lightgbm_tpu.utils import file_io
from lightgbm_tpu.utils.log import LightGBMError

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
from fault_injection import corrupt_file, truncate_file  # noqa: E402


def make_data(n=400, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.uniform(-2, 2, size=(n, 5))
    y = (X[:, 0] * 2 + np.sin(X[:, 1] * 2)
         + 0.1 * rng.normal(size=n)).astype(np.float32)
    return X, y


BASE = dict(objective="regression", num_leaves=15, min_data_in_leaf=5,
            metric_freq=4, verbosity=-1)


def build_booster(params, n_iter, snapshot_freq=-1):
    cfg = Config(dict(params, num_iterations=n_iter,
                      snapshot_freq=snapshot_freq))
    X, y = make_data()
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=cfg.max_bin,
                                   min_data_in_leaf=cfg.min_data_in_leaf)
    booster = create_boosting(cfg.boosting, cfg, ds,
                              create_objective(cfg.objective, cfg))
    booster.add_train_metrics(create_metrics(cfg.metric, cfg))
    Xv, yv = make_data(200, 7)
    vs = BinnedDataset.from_matrix(Xv, label=yv, reference=ds)
    booster.add_valid_data(vs, "valid_1")
    return booster


def run_full_and_resumed(params, total=20, sf=7, tmp_path=None):
    """(full model string, resumed model string, checkpoint prefix)."""
    out = str(tmp_path / "model.txt")
    full = build_booster(params, total, snapshot_freq=sf)
    full.train(snapshot_out=out)
    # "kill": a fresh process-equivalent booster that only has the on-disk
    # checkpoints; resume must reconstruct the full trainer state
    resumed = build_booster(params, total, snapshot_freq=sf)
    it = resumed.resume_from_checkpoint(out)
    assert 0 < it < total
    resumed.train()
    return full.save_model_to_string(), resumed.save_model_to_string(), out


@pytest.fixture
def fault_hook():
    """Install an atomic-write fault hook; always cleared on exit."""
    def install(hook):
        file_io.set_fault_hook(hook)
    yield install
    file_io.set_fault_hook(None)


# ---- atomic writes ----

def test_atomic_write_survives_midwrite_fault(tmp_path, fault_hook):
    path = str(tmp_path / "f.txt")
    file_io.atomic_write(path, "generation-1")

    class Boom(RuntimeError):
        pass

    def die(stage, p):
        raise Boom(stage)

    fault_hook(die)
    with pytest.raises(Boom):
        file_io.atomic_write(path, "generation-2-partial")
    file_io.set_fault_hook(None)
    # the kill left the previous complete file and no temp litter
    # (os.listdir, not glob: the temp name is dot-prefixed)
    with open(path) as fh:
        assert fh.read() == "generation-1"
    assert os.listdir(tmp_path) == ["f.txt"]
    file_io.atomic_write(path, "generation-2")
    with open(path) as fh:
        assert fh.read() == "generation-2"


def test_crc_trailer_detects_truncation_and_bitflips():
    blob = file_io.append_crc_trailer(b"payload bytes" * 100)
    assert file_io.check_crc_trailer(blob) == b"payload bytes" * 100
    with pytest.raises(ValueError, match="length mismatch|trailer missing"):
        file_io.check_crc_trailer(blob[:-40])
    flipped = bytes([blob[0] ^ 0xFF]) + blob[1:]
    with pytest.raises(ValueError, match="CRC32 mismatch"):
        file_io.check_crc_trailer(flipped)


# ---- bit-exact kill/resume across boosting modes ----

def test_resume_bit_exact_gbdt_fused_bagging(tmp_path):
    # fused lax.scan path: bagging + valid set ride the scan
    params = dict(BASE, bagging_fraction=0.8, bagging_freq=3)
    full, resumed, _ = run_full_and_resumed(params, tmp_path=tmp_path)
    assert full == resumed


def test_resume_bit_exact_gbdt_early_stopping(tmp_path):
    # early-stopping bookkeeping (_es_state) must survive the resume: the
    # restored run may not reset the best-score counters
    params = dict(BASE, early_stopping_round=3, metric_freq=1)
    full, resumed, _ = run_full_and_resumed(params, tmp_path=tmp_path)
    assert full == resumed


def test_resume_bit_exact_dart(tmp_path):
    # DART: drop RNG stream + tree weight history + dropout-mutated old
    # trees; scores are restored binary because the incremental f32 sum is
    # order-dependent under dropout
    params = dict(BASE, boosting="dart", bagging_fraction=0.8, bagging_freq=2)
    full, resumed, _ = run_full_and_resumed(params, total=16, sf=6,
                                            tmp_path=tmp_path)
    assert full == resumed


def test_resume_bit_exact_goss(tmp_path):
    # GOSS: the sequential _bag_rng stream drives other-sample selection
    params = dict(BASE, boosting="goss", learning_rate=0.3)
    full, resumed, _ = run_full_and_resumed(params, total=16, sf=6,
                                            tmp_path=tmp_path)
    assert full == resumed


def test_resume_bit_exact_rf(tmp_path):
    # RF: gradients are taken at CONSTANT init scores; after a resume the
    # model is non-empty so a naive recompute would return 0.0 — the init
    # scores ride the checkpoint (rf.py _extra_train_state)
    params = dict(BASE, boosting="rf", bagging_fraction=0.7, bagging_freq=1,
                  feature_fraction=0.7)
    full, resumed, _ = run_full_and_resumed(params, total=12, sf=8,
                                            tmp_path=tmp_path)
    assert full == resumed


def test_resume_bit_exact_feature_fraction(tmp_path):
    # the feature mask is a stateless function of (seed, iteration) since
    # PR 36: the resumed run draws iteration k's mask, not iteration 0's
    params = dict(BASE, feature_fraction=0.6)
    full, resumed, _ = run_full_and_resumed(params, tmp_path=tmp_path)
    assert full == resumed


def test_resume_bit_exact_cegb(tmp_path):
    # CEGB carries cross-iteration state on the LEARNER (coupled-penalty
    # feature-used flags + lazy per-(row,feature) paid bits); both ride the
    # checkpoint as binary arrays
    params = dict(BASE, cegb_tradeoff=0.5,
                  cegb_penalty_feature_coupled=[3.0] * 5,
                  cegb_penalty_feature_lazy=[0.01] * 5)
    full, resumed, _ = run_full_and_resumed(params, total=12, sf=8,
                                            tmp_path=tmp_path)
    assert full == resumed


def test_resume_midwindow_bagging_mask(tmp_path):
    # snapshot at iteration 8 with bagging_freq=3: iteration 8 sits MID
    # bagging window (window start 6), so the restore must rebuild the
    # window-start mask, not draw a fresh one
    params = dict(BASE, bagging_fraction=0.7, bagging_freq=3)
    full, resumed, _ = run_full_and_resumed(params, total=12, sf=8,
                                            tmp_path=tmp_path)
    assert full == resumed


# ---- discovery, fallback, retention ----

def test_corrupt_latest_falls_back_to_previous(tmp_path):
    params = dict(BASE, bagging_fraction=0.8, bagging_freq=3)
    out = str(tmp_path / "model.txt")
    full = build_booster(params, 20, snapshot_freq=7)
    full.train(snapshot_out=out)
    ckpts = list_checkpoints(out)
    assert [it for it, _ in ckpts] == [14, 7]
    corrupt_file(ckpts[0][1])
    with pytest.raises(CheckpointError):
        load_checkpoint(ckpts[0][1])
    # fallback: newest VALID one wins, and the resumed run still completes
    resumed = build_booster(params, 20, snapshot_freq=7)
    assert resumed.resume_from_checkpoint(out) == 7
    resumed.train()
    assert resumed.save_model_to_string() == full.save_model_to_string()


def test_truncated_checkpoint_rejected(tmp_path):
    params = dict(BASE)
    out = str(tmp_path / "model.txt")
    booster = build_booster(params, 10, snapshot_freq=8)
    booster.train(snapshot_out=out)
    (it, path), = list_checkpoints(out)
    truncate_file(path, 0.4)
    assert load_latest_checkpoint(out) is None
    fresh = build_booster(params, 10, snapshot_freq=8)
    assert fresh.resume_from_checkpoint(out) == 0  # untouched booster


def test_snapshot_keep_retention(tmp_path):
    params = dict(BASE, snapshot_keep=2)
    out = str(tmp_path / "model.txt")
    booster = build_booster(params, 20, snapshot_freq=4)
    booster.train(snapshot_out=out)
    # boundaries 4, 8, 12, 16, 20 -> newest 2 kept for BOTH file kinds
    assert [it for it, _ in list_checkpoints(out)] == [20, 16]
    snaps = sorted(glob.glob(out + ".snapshot_iter_*"))
    assert [os.path.basename(p) for p in snaps] == \
        ["model.txt.snapshot_iter_16", "model.txt.snapshot_iter_20"]


def test_checkpoint_requires_matching_valid_sets(tmp_path):
    params = dict(BASE)
    out = str(tmp_path / "model.txt")
    booster = build_booster(params, 10, snapshot_freq=5)
    booster.train(snapshot_out=out)
    cfg = Config(dict(params, num_iterations=10))
    X, y = make_data()
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=cfg.max_bin,
                                   min_data_in_leaf=cfg.min_data_in_leaf)
    bare = create_boosting(cfg.boosting, cfg, ds,
                           create_objective(cfg.objective, cfg))
    with pytest.raises(CheckpointError, match="valid sets"):
        bare.resume_from_checkpoint(out)


def test_checkpoint_rejects_different_dataset(tmp_path):
    """Resume-vs-wrong-data guard: the dataset fingerprint (num_rows,
    num_features, bin-mapper digest) rides the checkpoint header and a
    restore against ANY other dataset hard-errors instead of silently
    training the restored scores against rows they do not describe."""
    params = dict(BASE)
    out = str(tmp_path / "model.txt")
    booster = build_booster(params, 10, snapshot_freq=5)
    booster.train(snapshot_out=out)

    def booster_on(X, y):
        cfg = Config(dict(params, num_iterations=10, snapshot_freq=5))
        ds = BinnedDataset.from_matrix(X, label=y, max_bin=cfg.max_bin,
                                       min_data_in_leaf=cfg.min_data_in_leaf)
        b = create_boosting(cfg.boosting, cfg, ds,
                            create_objective(cfg.objective, cfg))
        b.add_train_metrics(create_metrics(cfg.metric, cfg))
        Xv, yv = make_data(200, 7)
        b.add_valid_data(BinnedDataset.from_matrix(Xv, label=yv,
                                                   reference=ds), "valid_1")
        return b

    # same shape, different values -> different bin bounds -> digest differs
    Xw, yw = make_data(seed=99)
    with pytest.raises(CheckpointError, match="different dataset"):
        booster_on(Xw, yw).resume_from_checkpoint(out)
    # different row count
    X, y = make_data()
    with pytest.raises(CheckpointError, match="different dataset"):
        booster_on(X[:-5], y[:-5]).resume_from_checkpoint(out)
    # the matching dataset still resumes (newest checkpoint: iteration 10)
    assert booster_on(X, y).resume_from_checkpoint(out) == 10


def test_checkpoint_boosting_mode_mismatch(tmp_path):
    out = str(tmp_path / "model.txt")
    booster = build_booster(dict(BASE), 10, snapshot_freq=5)
    booster.train(snapshot_out=out)
    dart = build_booster(dict(BASE, boosting="dart"), 10, snapshot_freq=5)
    with pytest.raises(CheckpointError, match="boosting"):
        dart.resume_from_checkpoint(out)


def test_serialize_roundtrip_and_version_gate():
    meta = {"iteration": 3, "nested": {"a": [1, 2]}}
    arrays = {"x": np.arange(12, dtype=np.float32).reshape(3, 4),
              "flags": np.array([True, False])}
    blob = serialize_state(meta, arrays, "model text\nwith lines\n")
    from lightgbm_tpu.checkpoint import deserialize_state
    m2, a2, s2 = deserialize_state(blob)
    assert m2 == meta and s2 == "model text\nwith lines\n"
    assert np.array_equal(a2["x"], arrays["x"])
    assert a2["flags"].dtype == np.bool_
    with pytest.raises(CheckpointError, match="magic"):
        deserialize_state(file_io.append_crc_trailer(b"not a checkpoint\nx"))


# ---- engine-level resume ----

def test_engine_train_checkpoint_prefix(tmp_path):
    import lightgbm_tpu as lgb
    X, y = make_data()
    prefix = str(tmp_path / "engine_ckpt")
    params = dict(objective="regression", num_leaves=15, min_data_in_leaf=5,
                  snapshot_freq=4, verbosity=-1)
    full = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=12)

    # interrupted call: a callback dies at iteration 8, AFTER the iter-8
    # checkpoint landed; the exception path must leave checkpoints behind
    class Preempted(RuntimeError):
        pass

    def kill_at(env):
        if env.iteration == 8:
            raise Preempted()

    with pytest.raises(Preempted):
        lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=12,
                  checkpoint_prefix=prefix, callbacks=[kill_at])
    assert [it for it, _ in list_checkpoints(prefix)] == [8, 4]
    resumed = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=12,
                        checkpoint_prefix=prefix)
    assert resumed.current_iteration() == 12
    assert resumed.model_to_string() == full.model_to_string()
    # the completed call cleans up: a rerun trains fresh, never silently
    # returns the finished run's model
    assert list_checkpoints(prefix) == []


# ---- non-finite guards ----

def _poison(booster, nan_at, value=float("nan")):
    """Make the objective emit a bad gradient batch at one iteration
    (NaN by default; clip turns it into zeroed rows, skip_iter into a
    constant tree, raise into a LightGBMError)."""
    orig = booster.objective.get_gradients
    state = {"it": 0}

    def poisoned(score):
        import jax.numpy as jnp
        g, h = orig(score)
        if state["it"] == nan_at:
            g = g.at[:7].set(value)
        state["it"] += 1
        return g, h

    booster.objective.get_gradients = poisoned
    booster._fuse_failed = True  # host-side hook: keep the traced scan off


def test_nan_policy_raise(tmp_path):
    booster = build_booster(dict(BASE), 12)
    _poison(booster, nan_at=5)
    with pytest.raises(LightGBMError, match="non-finite"):
        booster.train()


def test_nan_policy_skip_iter(tmp_path):
    booster = build_booster(dict(BASE, nan_policy="skip_iter"), 12)
    _poison(booster, nan_at=5)
    booster.train()
    assert booster.num_trees == 12  # skipped iteration holds a constant tree
    score = np.asarray(booster.train_score)
    assert np.isfinite(score).all()
    # exactly one zero-output tree: the skipped iteration's placeholder
    zero_trees = [t for t in booster.models
                  if t.num_leaves == 1 and t.leaf_value[0] == 0.0]
    assert len(zero_trees) == 1


def test_nan_policy_clip(tmp_path):
    booster = build_booster(dict(BASE, nan_policy="clip"), 12)
    _poison(booster, nan_at=5)
    booster.train()
    assert booster.num_trees == 12
    assert np.isfinite(np.asarray(booster.train_score)).all()
    assert all(t.num_leaves > 1 for t in booster.models)  # no skips: clipped


def test_nan_policy_custom_gradients_host_guard():
    # the c_api/fobj path hands host arrays in; the guard must act before
    # any device work
    booster = build_booster(dict(BASE, nan_policy="skip_iter"), 6)
    n = booster.num_data
    g = np.full(n, np.nan, dtype=np.float32)
    h = np.ones(n, dtype=np.float32)
    assert booster.train_one_iter(g, h) is False
    assert booster.num_trees == 1 and booster.models[0].num_leaves == 1
    booster2 = build_booster(dict(BASE), 6)  # default: raise
    with pytest.raises(LightGBMError, match="non-finite"):
        booster2.train_one_iter(g, h)


def test_nan_policy_raise_drains_trailing_handles():
    # the lazy path batches raise-policy isfinite reductions into _poll_stop
    # (every 16 iterations); a bad batch in the trailing window must still
    # raise via the end-of-training drain (engine.train calls it too)
    booster = build_booster(dict(BASE), 6)
    _poison(booster, nan_at=5)
    for _ in range(6):
        booster.train_one_iter()
    with pytest.raises(LightGBMError, match="non-finite"):
        booster._drain_nonfinite_checks()


def test_nan_policy_rf_guard():
    # RF overrides train_one_iter; the guard must still fire there
    booster = build_booster(dict(BASE, boosting="rf", bagging_fraction=0.7,
                                 bagging_freq=1, feature_fraction=0.7), 6)
    _poison(booster, nan_at=0)
    with pytest.raises(LightGBMError, match="non-finite"):
        booster.train()


def test_nan_policy_skip_iter_keeps_init_score():
    # a FIRST-iteration skip must still carry the boost_from_average offset
    # into the model (the scores already contain it), or every saved
    # prediction would be shifted by -mean(y)
    booster = build_booster(dict(BASE, nan_policy="skip_iter"), 4)
    _poison(booster, nan_at=0)
    booster.train()
    X, _ = make_data()
    pred = booster.predict(X, raw_score=True)
    score = np.asarray(booster.train_score[0, :booster.num_data])
    np.testing.assert_allclose(pred, score, rtol=1e-5, atol=1e-5)


def test_resume_bit_exact_after_stall(tmp_path):
    # splits exhaust mid-run (min_gain_to_split): the deferred stall poll is
    # settled BEFORE each checkpoint capture, so the checkpoint never holds
    # iterations the uninterrupted run would later trim
    params = dict(BASE, learning_rate=0.5, min_gain_to_split=1.0,
                  num_leaves=7)
    out = str(tmp_path / "model.txt")
    full = build_booster(params, 20, snapshot_freq=4)
    full.train(snapshot_out=out)
    stalled_at = full.num_trees
    assert 4 < stalled_at < 20, stalled_at  # stalled after a checkpoint
    resumed = build_booster(params, 20, snapshot_freq=4)
    assert resumed.resume_from_checkpoint(out) > 0
    resumed.train()
    assert resumed.save_model_to_string() == full.save_model_to_string()


def test_nan_policy_param_validation():
    with pytest.raises(LightGBMError, match="nan_policy"):
        Config(nan_policy="explode")
    cfg = Config(non_finite_policy="CLIP")  # alias + case normalization
    assert cfg.nan_policy == "clip"
    cfg2 = Config(checkpoint_keep=5)  # snapshot_keep alias
    assert cfg2.snapshot_keep == 5


# ---- model parse hardening ----

def test_model_parse_errors_name_the_section(tmp_path):
    booster = build_booster(dict(BASE), 6)
    for _ in range(6):
        booster.train_one_iter()
    text = booster.save_model_to_string()
    fresh = build_booster(dict(BASE), 6)
    with pytest.raises(LightGBMError, match="empty"):
        fresh.load_model_from_string("")
    with pytest.raises(LightGBMError, match="end of trees"):
        fresh.load_model_from_string(text[:text.find("end of trees")])
    # truncated BEFORE the first tree block: the header still declares its
    # trees, so this must error, not load as a silent 0-tree model
    with pytest.raises(LightGBMError, match="tree_sizes declares"):
        fresh.load_model_from_string(text[:text.find("\nTree=0")])
    # drop one whole tree block but keep the sentinel: count mismatch
    start = text.find("Tree=5")
    end = text.find("end of trees")
    with pytest.raises(LightGBMError, match="tree_sizes declares"):
        fresh.load_model_from_string(text[:start] + text[end:])
    # mangle a tree body: error names the tree index
    mangled = text.replace("num_leaves=", "num_leaves=bogus_", 1)
    with pytest.raises(LightGBMError, match="Tree=0 is malformed"):
        fresh.load_model_from_string(mangled)
    # the intact string still parses after all those rejections
    fresh.load_model_from_string(text)
    assert fresh.num_trees == 6
